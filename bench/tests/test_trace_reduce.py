"""The trace reduction, on a synthetic trace and on one recorded on a v5e
(``data/small.xplane.pb``, made by ``record_trace.py``)."""
from pathlib import Path

import pytest

import trace_reduce

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def _synthetic():
    ms = 1_000_000
    return {
        "device": {"/device:TPU:0": [
            ("fusion.1", 1 * ms, 3 * ms, "fusion.1 jit(f)/add"),
            # overlaps fusion.1; found by its stats, not its name
            ("custom-call.2", 2 * ms, 4 * ms,
             'custom-call.2 custom_call_target="tpu_custom_call" '
             "kernel_name=_step_kernel"),
            ("%run_packed.1 = custom-call", 6 * ms, 7 * ms,
             '%run_packed.1 = custom-call custom_call_target="tpu_custom_call"'),
            # the layout copy beside the kernel is not the kernel
            ("copy.4", 6 * ms, 6.5 * ms, "copy.4 jit(run_packed)/copy"),
            ("outside", 20 * ms, 30 * ms, "outside")]},   # after the window
        "host": [("bench.window", 0, 10 * ms, 0),
                 ("bench.dispatch", 4 * ms, 6 * ms, 0),
                 ("TransferToDevice", 4 * ms, 5 * ms, 1),
                 ("bench.tail", 7 * ms, 10 * ms, 0)]}


def test_busy_idle_kernels_and_gaps():
    r = trace_reduce.reduce(_synthetic())
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.004)         # [1,4] + [6,7] ms
    k = r["kernels"]["comefa_step"]
    assert k["calls"] == 2 and k["seconds"] == pytest.approx(0.003)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.002)]
    assert ["copy.4", pytest.approx(0.0005)] in r["device_ops"]
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.window"] == pytest.approx(0.001)          # [0,1]
    assert gaps["bench.dispatch / TransferToDevice"] == \
        pytest.approx(0.002)                                     # [4,6]
    assert gaps["bench.tail"] == pytest.approx(0.003)            # [7,10]


def test_no_window_or_device_gives_nothing():
    t = _synthetic()
    assert trace_reduce.reduce(dict(t, host=t["host"][1:])) is None
    assert trace_reduce.reduce(dict(t, device={})) is None


@pytest.mark.skipif(not DATA.exists(), reason="no recorded trace")
def test_recorded_v5e_trace():
    r = trace_reduce.reduce(trace_reduce.load(DATA))
    assert r is not None and r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"] < 1.0
    k = r["kernels"]["comefa_step"]
    assert k["calls"] == 3 and 0 < k["seconds"] <= r["busy_s"]
    labels = [name for name, _ in r["idle_gaps"]]
    assert any(name.startswith("bench.pause") for name in labels)
