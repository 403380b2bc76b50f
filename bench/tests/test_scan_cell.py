"""The TPC-H Q6 cell (``tpch.q6-sf10``) at rehearsal size on the CPU.

The rehearsal comes out correct and the float32 control does not; each
fault planted under the timed window makes the run incorrect; the
generated columns stay inside the domains of spec clause 4.2.3; and a
program without the scan module (the commit before the cell) exits
non-zero within a minute, with no hang.
"""
import shutil
import time

import numpy as np
import pytest

from conftest import BENCH, ROOT, run_cell

CELL = "tpch.q6-sf10"


def _ok(rc, result, err):
    assert rc == 0, err[-3000:]
    assert result is not None, err[-3000:]
    return result


def test_rehearsal_correct_and_control_fails():
    res = _ok(*run_cell(ROOT, CELL, seconds=2))
    assert res["correct"], res
    assert res["checks"]["revenue_mismatch"]["value"] == 0
    assert res["attempted"] >= 1 and res["info"]["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"sim_lane_cycles_per_s", "setup_s"}
    ctl = _ok(*run_cell(ROOT, CELL, "--control", seconds=1))
    assert ctl["correct"] is False, ctl
    assert ctl["checks"]["revenue_mismatch"]["value"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fault_makes_incorrect(fault):
    res = _ok(*run_cell(ROOT, CELL, "--fault", fault, seconds=1))
    assert res["correct"] is False, res
    assert res["checks"]["revenue_mismatch"]["value"] > 0


def test_traced_rehearsal_reports_the_scan_metrics():
    res = _ok(*run_cell(ROOT, CELL, "--trace", "1", seconds=1))
    assert res["correct"], res
    # the CPU has no device plane: only the span and count metrics
    assert set(res["metrics"]) == {"dispatch_host_us.fleet",
                                   "row_load_us.scan", "cycles_per_fill.scan",
                                   "readout_share.scan"}
    # 4 fills of 176 cycles and one readout of 1,380 per query
    assert res["metrics"]["cycles_per_fill.scan"]["value"] == \
        (4 * 176 + 1380) / 4


def test_generated_columns_stay_in_the_spec_domains():
    import benchlib
    scan = benchlib.load_module(BENCH / "systems" / "scan.py")
    n = 200_000
    cols = scan.lineitem(seed=2**31 + 12345, n_rows=n, scale_factor=10)
    assert all(len(v) == n for v in cols.values())
    ship, disc, qty, price = (cols[k].astype(np.int64) for k in
                              ("shipdate", "discount", "quantity", "price"))
    # O_ORDERDATE in [1992-01-01, 1998-12-31 - 151 days], + 1..121 days
    assert ship.min() >= 1 and ship.max() <= 2405 + 121
    assert disc.min() == 0 and disc.max() == 10
    assert qty.min() == 1 and qty.max() == 50
    # extendedprice = quantity x retail price, retail in [900.00, 2099.00]
    assert np.all(price % qty == 0)
    retail = price // qty
    assert retail.min() >= 90_000 and retail.max() <= 209_900
    again = scan.lineitem(seed=2**31 + 12345, n_rows=n, scale_factor=10)
    assert all(np.array_equal(cols[k], again[k]) for k in cols)


def test_program_without_the_scan_module_fails_fast(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "src", root / "src",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src" / "repro" / "kernels" / "comefa_scan.py").unlink()
    t0 = time.monotonic()
    rc, res, _ = run_cell(root, CELL, seconds=1, timeout=60)
    assert rc != 0 and res is None
    assert time.monotonic() - t0 < 60


def _read(name, spans=(), counters=None):
    import benchlib
    from repro.obs.trace import TraceEvent
    events = [TraceEvent(n, "wall", 1, float(ts), float(dur), {})
              for n, ts, dur in spans]
    run = benchlib.RunView(spans=benchlib.Spans(events, 0.0, 1000.0),
                           counters=counters or {}, device=None, peaks={},
                           facts={})
    return benchlib.load_module(BENCH / "metrics" / f"{name}.py").read(run)


def test_scan_readers_on_synthetic_spans():
    fills = [("scan.fill", 0, 300), ("grid.write_rows", 10, 40),
             ("scan.fill", 300, 300), ("grid.write_rows", 310, 60),
             ("scan.readout", 600, 250)]
    assert _read("row_load_us.scan", fills) == pytest.approx(50.0)
    assert _read("readout_share.scan", fills) == pytest.approx(25.0)
    counters = {"scan.fills": 2.0,
                "comefa.kernel_cycles{kernel=q6_scan}": 370.0,
                "comefa.kernel_cycles{kernel=gemv_batched}": 999.0}
    assert _read("cycles_per_fill.scan", counters=counters) == 185.0
    # nothing recorded, nothing read (the commit before the cell)
    for name in ("row_load_us.scan", "readout_share.scan",
                 "cycles_per_fill.scan"):
        assert _read(name) is None
