"""Launcher/example smoke tests: the public entry points run end to end."""
import pytest

pytestmark = pytest.mark.slow  # minutes-long end-to-end tier (see pytest.ini)
import os
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=600, env_extra=None):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.update(env_extra or {})
    out = subprocess.run([sys.executable] + args, capture_output=True,
                         text=True, timeout=timeout, env=env, cwd=REPO)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    return out.stdout


def test_quickstart_example():
    out = _run([os.path.join(REPO, "examples", "quickstart.py")])
    assert "paper formula n^2+3n-2 = 86" in out
    assert "packed linear == jnp oracle: True" in out
    assert "finite: True" in out


def test_comefa_programs_example():
    out = _run([os.path.join(REPO, "examples", "comefa_programs.py")])
    assert "160 records matched+cleared in 40 cycles" in out
    assert "'comefa-d': (6.7, 6.7)" in out


def test_train_launcher_reduced(tmp_path):
    out = _run(["-m", "repro.launch.train", "--arch", "smollm-360m",
                "--steps", "6", "--reduced", "--batch", "4", "--seq", "32",
                "--ckpt", str(tmp_path)])
    assert "finished at step 6" in out
    assert any(n.startswith("step_") for n in os.listdir(tmp_path))


def test_serve_launcher_reduced():
    out = _run(["-m", "repro.launch.serve", "--arch", "smollm-360m",
                "--reduced", "--batch", "2", "--steps", "4"])
    assert "generated token ids:" in out


def test_serve_launcher_quantized():
    out = _run(["-m", "repro.launch.serve", "--arch", "smollm-360m",
                "--reduced", "--batch", "1", "--steps", "2",
                "--quant", "4"])
    assert "generated token ids:" in out
