"""Record the small profiler trace `test_trace_reduce.py` reads (on a TPU).

    python3 bench/tests/record_trace.py [--out PATH]

A 2-slot, 1-block grid runs a 16-bit multiply three times inside the
harness's window annotation, with a short host pause between dispatches
so the trace has idle gaps to name.  The ``.xplane.pb`` is copied to
``--out`` (default ``bench/tests/data/small.xplane.pb``).
"""
import argparse
import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))

import jax  # noqa: E402

from repro.core.comefa import ComefaGrid, program  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=Path,
                    default=HERE / "data" / "small.xplane.pb")
    dst = ap.parse_args(argv).out
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    rows = (list(range(16)), list(range(16, 32)), list(range(32, 64)))
    mul16 = program.mul(*rows).optimize()
    grid = ComefaGrid(2, n_blocks=1, engine="packed")
    grid.run(mul16)
    jax.block_until_ready(jax.live_arrays())
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            # the device's timestamps run up to ~1 ms ahead of the host's
            # in a v5e trace: a lead-in keeps the first kernel inside
            with jax.profiler.TraceAnnotation("bench.pause"):
                time.sleep(0.005)
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    grid.run(mul16)
                jax.block_until_ready(jax.live_arrays())
                with jax.profiler.TraceAnnotation("bench.pause"):
                    time.sleep(0.002)
        jax.profiler.stop_trace()
        src = sorted(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True))[-1]
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, dst)
    print(f"record_trace: wrote {dst} ({dst.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
