"""Tests of the benchmark itself; run by hand: ``python -m pytest bench/tests``.

They sit outside the repository's tier-1 ``testpaths``.  Harness runs go
through subprocesses with ``--rehearse`` (CPU, tiny sizes, interpret-mode
kernel), as the chip path never falls back to the CPU.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def run_cell(root: Path, workload: str, *args, seed: int = 7,
             seconds: float = 3, timeout: int = 600):
    """Run the harness rehearsed in checkout `root`: (returncode, result
    dict or None, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         workload, "--seed", str(seed), "--seconds", str(seconds),
         "--rehearse", *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
    lines = proc.stdout.strip().splitlines()
    result = None
    if lines:
        try:
            result = json.loads(lines[-1])
        except json.JSONDecodeError:
            result = None
    return proc.returncode, result, proc.stderr

