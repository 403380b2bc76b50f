"""Benchmark driver. Prints ``name,us_per_call,derived[,paper]`` CSV.

Sections:
  * paper_figs  - one benchmark per CoMeFa paper table/figure (Figs 8-12,
                  Tables III/IV), driven by the analytical FPGA model.
  * comefa_sim  - wall-time of the bit-level simulator on representative
                  programs (throughput of the functional model itself),
                  including the tiled-GEMM LCU-vs-serial schedule rows.
  * tpu_kernels - bit-plane TPU kernel benchmarks (CPU wall-time of the
                  jnp reference path + Pallas interpret-mode correctness;
                  roofline numbers come from launch/dryrun.py instead).

``--json PATH`` additionally writes the rows as machine-readable JSON.
"""
from __future__ import annotations

import argparse
import json


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows as JSON to PATH")
    args = ap.parse_args(argv)
    from repro import compile_cache
    compile_cache.enable()

    rows: list = []   # (name, us_per_call, derived, paper)
    from benchmarks import paper_figs, sim_speed, tpu_kernels
    paper_figs.run(rows)
    sim_speed.run(rows)
    tpu_kernels.run(rows)

    if args.json is not None:
        from benchmarks.sim_speed import _rows_as_json
        payload = _rows_as_json(rows)
        payload["benchmark"] = "run_all"
        with open(args.json, "w") as f:
            f.write(json.dumps(payload, indent=2) + "\n")

    print("name,us_per_call,derived,paper")
    for name, us, derived, paper in rows:
        p = "" if paper is None else f"{paper:.6g}"
        print(f"{name},{us:.2f},{derived:.6g},{p}")


if __name__ == "__main__":
    main()
