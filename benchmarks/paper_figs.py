"""Benchmarks reproducing each table/figure of the CoMeFa paper.

Each function returns a list of (name, value, paper_value_or_None) rows.
These drive the analytical FPGA model whose cycle formulas are validated
bit-exactly by the simulator tests (tests/test_comefa_sim.py).  Run as a
script, the module prints every row as ``name,us_per_call,derived,paper``
CSV:

  PYTHONPATH=src python benchmarks/paper_figs.py
"""
from __future__ import annotations

import time
from typing import List, Optional, Tuple

from repro.core.fpga_model import area, energy, perf, resources as R, throughput

Row = Tuple[str, float, Optional[float]]


def fig8_throughput() -> List[Row]:
    """Peak MAC throughput (GigaMACs/s) per precision per resource."""
    rows: List[Row] = []
    for prec in ("int4", "int8", "int16", "hfp8", "fp16"):
        base = throughput.fpga_mac_throughput(prec)
        rows.append((f"fig8/{prec}/lb_gmacs", base["lb"] / 1e9, None))
        rows.append((f"fig8/{prec}/dsp_gmacs", base["dsp"] / 1e9, None))
        for var in ("comefa-d", "comefa-a", "ccb"):
            t = throughput.comefa_mac_throughput(R.VARIANTS[var], prec)
            rows.append((f"fig8/{prec}/{var}_gmacs", t / 1e9, None))
        rows.append((f"fig8/{prec}/gain_comefa-d",
                     throughput.throughput_gain(prec, "comefa-d"),
                     throughput.PAPER_GAINS_D[prec]))
        rows.append((f"fig8/{prec}/gain_comefa-a",
                     throughput.throughput_gain(prec, "comefa-a"),
                     throughput.PAPER_GAINS_A[prec]))
    return rows


def fig9_speedups() -> List[Row]:
    rows: List[Row] = []
    res = perf.run_all()
    for bench, targets in perf.PAPER_SPEEDUPS.items():
        for var, target in targets.items():
            rows.append((f"fig9/{bench}/{var}", res[bench][var], target))
    return rows


def fig10_energy() -> List[Row]:
    rows: List[Row] = []
    for bench, d in energy.all_savings().items():
        for var, saving in d.items():
            rows.append((f"fig10/{bench}/{var}_savings", saving, None))
    s = energy.all_savings()
    rows.append(("fig10/max/comefa-d",
                 max(d["comefa-d"] for d in s.values()), 0.52))
    rows.append(("fig10/max/comefa-a",
                 max(d["comefa-a"] for d in s.values()), 0.56))
    return rows


def fig11_comapping() -> List[Row]:
    rows: List[Row] = []
    for var in ("comefa-d", "comefa-a"):
        sweep = perf.comapping_sweep(var)
        best_alpha, best = max(sweep, key=lambda t: t[1])
        rows.append((f"fig11/{var}/best_alpha", best_alpha, None))
        rows.append((f"fig11/{var}/best_speedup", best, None))
        for alpha, s in sweep[::4]:
            rows.append((f"fig11/{var}/speedup@{alpha:.1f}", s, None))
    return rows


def fig12_precision_sweep() -> List[Row]:
    rows: List[Row] = []
    paper = {("comefa-d", 4): 5.3, ("comefa-d", 20): 2.7,
             ("comefa-a", 4): 3.3, ("comefa-a", 20): 1.7}
    for var in ("comefa-d", "comefa-a", "ccb"):
        for bits in (4, 8, 12, 16, 20):
            s = perf.reduction(var, bits=bits).speedup
            rows.append((f"fig12/{var}/p{bits}", s, paper.get((var, bits))))
    return rows


def tab3_tab4_area() -> List[Row]:
    rows: List[Row] = []
    for variant, d in area.TABLE_III.items():
        for comp, pct in d.items():
            rows.append((f"tab3/{variant}/{comp}_pct", pct, pct))
    for var in ("comefa-d", "comefa-a", "ccb"):
        rows.append((f"tab4/{var}/block_overhead_um2",
                     area.BLOCK_OVERHEAD_UM2[var],
                     area.BLOCK_OVERHEAD_UM2[var]))
        rows.append((f"tab4/{var}/chip_overhead_derived",
                     area.chip_overhead(var),
                     area.CHIP_OVERHEAD_FRAC[var]))
    return rows


ALL = [fig8_throughput, fig9_speedups, fig10_energy, fig11_comapping,
       fig12_precision_sweep, tab3_tab4_area]


def run(out_rows: list) -> None:
    for fn in ALL:
        t0 = time.perf_counter()
        rows = fn()
        us = (time.perf_counter() - t0) * 1e6
        for name, value, paper_val in rows:
            out_rows.append((name, us / max(len(rows), 1), value, paper_val))


if __name__ == "__main__":
    rows: list = []
    run(rows)
    print("name,us_per_call,derived,paper")
    for name, us, derived, paper in rows:
        p = "" if paper is None else f"{paper:.6g}"
        print(f"{name},{us:.2f},{derived:.6g},{p}")
