"""The check that decides ``correct``, at rehearsal size on the CPU.

Every cell runs end to end through the harness (``--rehearse``: tiny
sizes, interpret-mode kernel) and comes out correct; the lower-precision
control, judged in the program's place, comes out not correct; and each
fault the cell can have, planted under the timed window, makes
``correct`` false.
"""
import pytest

from conftest import BENCH, ROOT, run_cell

FLEET = ("fleet.mul16-shared", "fleet.mul-perslot")
SERVE = "smollm-360m.decode"
# long enough on the CPU for rows to finish their prompts and serve tokens
SERVE_SECONDS = 25


def _ok(rc, result, err):
    assert rc == 0, err[-3000:]
    assert result is not None, err[-3000:]
    return result


@pytest.mark.parametrize("workload", FLEET)
def test_fleet_cell_correct_and_control_fails(workload):
    res = _ok(*run_cell(ROOT, workload, seconds=2))
    assert res["correct"], res
    assert res["checks"]["product_mismatch"]["value"] == 0
    assert res["info"]["dispatches"] == res["attempted"] + 1
    assert set(res["metrics"]) == {"sim_lane_cycles_per_s", "setup_s"}
    ctl = _ok(*run_cell(ROOT, workload, "--control", seconds=1))
    assert ctl["correct"] is False, ctl
    assert ctl["checks"]["product_mismatch"]["value"] > \
        ctl["checks"]["product_mismatch"]["limit"]


@pytest.mark.parametrize("workload", FLEET)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fleet_fault_makes_incorrect(workload, fault):
    res = _ok(*run_cell(ROOT, workload, "--fault", fault, seconds=1))
    assert res["correct"] is False
    assert res["checks"]["product_mismatch"]["value"] > 0


def test_serve_cell_correct_and_control_fails():
    res = _ok(*run_cell(ROOT, SERVE, seconds=SERVE_SECONDS))
    assert res["correct"], res
    assert res["checks"]["proj_rel_err"]["value"] == 0.0
    assert set(res["metrics"]) == {"decode_tok_s", "setup_s"}
    ctl = _ok(*run_cell(ROOT, SERVE, "--control", seconds=SERVE_SECONDS))
    assert ctl["correct"] is False, ctl
    # at rehearsal widths the control's logits stay near the reference's
    # (at the cell's widths they read 3x the limit): the projections fail
    checks = ctl["checks"]
    assert checks["proj_rel_err"]["value"] > checks["proj_rel_err"]["limit"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_serve_fault_makes_incorrect(fault):
    res = _ok(*run_cell(ROOT, SERVE, "--fault", fault,
                        seconds=SERVE_SECONDS))
    assert res["correct"] is False, res


def test_traced_run_reports_per_layer_metrics():
    res = _ok(*run_cell(ROOT, "fleet.mul-perslot", "--trace", "1",
                        seconds=1))
    assert res["correct"]
    # the CPU has no device plane: only the span and count metrics
    assert set(res["metrics"]) == {"dispatch_host_us.fleet",
                                   "slot_pad_share"}
    assert 0 < res["metrics"]["slot_pad_share"]["value"] < 100


def test_rejects_a_host_without_a_tpu():
    """Without --rehearse the harness needs a TPU: it exits non-zero and
    prints no result."""
    import os
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         FLEET[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_fleet_window_holds_two_states_at_most():
    """The window keeps one dispatch queued behind the running one and no
    more: the live device arrays do not grow with the dispatches made."""
    import time

    import jax

    import benchlib
    cfg = benchlib.load_json(BENCH / "configs" / "arria10-gx900-fleet.json")
    mix = benchlib.load_json(BENCH / "traffic" / "mul16-shared.json")
    fleet = benchlib.load_module(BENCH / "systems" / "fleet.py")
    ref = benchlib.load_module(BENCH / "configs" /
                               "arria10-gx900-fleet_ref.py")
    system = fleet.System(config=cfg, mix=mix, seed=5, rehearse=True,
                          reference=ref)
    system.setup(annotate=False)
    before = len(jax.live_arrays())
    seen = []
    orig = system._dispatch

    def dispatch():
        out = orig()
        seen.append(len(jax.live_arrays()))
        return out
    system._dispatch = dispatch
    result = system.window(time.perf_counter(), 1.0)
    assert result["dispatches"] >= 3
    assert max(seen) <= before + 4, (before, seen)
