"""Wall-time benchmarks of the bit-level CoMeFa simulator itself.

Reports, for the representative add / mul / OOOR-dot programs:
  * cycles before/after the IR pass pipeline (dead-write elim, constant
    folding, dual-port co-issue) - the scheduler's cycle-count win;
  * wall-clock per call before/after - fewer scan steps plus the keyed
    encode cache;
  * repeat-call timing for a freshly rebuilt (structurally equal) program
    vs. the first call - demonstrating that the encode cache eliminates
    re-encoding on repeated kernel invocations;
  * `run_programs` batching: N programs in one `lax.scan` dispatch;
  * execution engines: the fused G=8 grid dispatch on the uint8
    reference scan vs the bit-packed uint32 engine (`engine="packed"`);
  * the tiled GEMM: LCU-overlapped vs serial-phase schedule cycles and
    the sim-backed `comefa_gemm` wall-clock.

Run directly with ``--json PATH`` to emit the rows as machine-readable
JSON (the nightly workflow uploads that file as an artifact).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core.comefa import (ComefaArray, block, layout, plan_gemm,
                               program, timing)


def _bench(fn, *, reps=10):
    fn()  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6


def _run_synced(sim, prog) -> None:
    """`sim.run(prog)` plus a device fence - state is lazily
    device-resident now, so an unfenced run() only measures dispatch."""
    sim.run(prog)
    jax.block_until_ready(sim._dev)


def run(rows: list) -> None:
    rng = np.random.default_rng(0)

    arr = ComefaArray(n_blocks=8)
    n = 8
    a = rng.integers(0, 1 << n, size=(8, 160))
    b = rng.integers(0, 1 << n, size=(8, 160))
    layout.place(arr, a, 0, n)
    layout.place(arr, b, n, n)

    def mk_mul():
        return program.mul(list(range(n)), list(range(n, 2 * n)),
                           list(range(2 * n, 4 * n)))

    def mk_add():
        return program.add(list(range(n)), list(range(n, 2 * n)),
                           list(range(2 * n, 3 * n + 1)))

    def mk_dot():
        k, wb, accb = 4, 6, 20
        x = [0b010101 & ((1 << wb) - 1)] * k
        w_rows = [list(range(j * wb, (j + 1) * wb)) for j in range(k)]
        acc = list(range(k * wb, k * wb + accb))
        return program.ooor_dot(w_rows, x, wb, acc)

    for name, mk in (("mul8", mk_mul), ("add8", mk_add), ("dot", mk_dot)):
        raw = mk()
        opt = raw.optimize()
        us_raw = _bench(lambda: _run_synced(arr, raw))
        us_opt = _bench(lambda: _run_synced(arr, opt))
        rows.append((f"sim/{name}_cycles_unopt", 0.0, raw.cycles, None))
        rows.append((f"sim/{name}_cycles_coissue", 0.0, opt.cycles, None))
        rows.append((f"sim/{name}_us_unopt", us_raw, us_raw, None))
        rows.append((f"sim/{name}_us_coissue", us_opt, us_opt, None))

    lanes = 8 * 160
    opt_mul = mk_mul().optimize()
    us = _bench(lambda: _run_synced(arr, opt_mul))
    rows.append(("sim/mul8_results_per_s", us, lanes / (us / 1e6), None))

    # encode cache: rebuilding a structurally equal program and running it
    # must skip re-encoding (cache keyed on the instruction stream)
    block._ENCODE_CACHE.clear()
    block.ENCODE_CACHE_STATS.update(hits=0, misses=0)
    t0 = time.perf_counter()
    _run_synced(arr, mk_mul())              # first call: encodes
    first_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    for _ in range(5):
        _run_synced(arr, mk_mul())          # rebuilt fresh: cache hits
    repeat_us = (time.perf_counter() - t0) / 5 * 1e6
    rows.append(("sim/mul8_first_call_us", first_us, first_us, None))
    rows.append(("sim/mul8_repeat_call_us", repeat_us, repeat_us, None))
    rows.append(("sim/encode_cache_hits", 0.0,
                 block.ENCODE_CACHE_STATS["hits"], None))

    # run_programs: one scan dispatch for a batch of programs
    progs = [mk_add().optimize() for _ in range(8)]
    us_loop = _bench(lambda: ([arr.run(p) for p in progs],
                              jax.block_until_ready(arr._dev)))
    us_batch = _bench(lambda: (arr.run_programs(progs),
                               jax.block_until_ready(arr._dev)))
    rows.append(("sim/add8_x8_looped_us", us_loop, us_loop, None))
    rows.append(("sim/add8_x8_batched_us", us_batch, us_batch, None))

    # grid-vs-loop: G independent arrays executing one shared program -
    # a Python loop of ComefaArray.run() calls (G separate scan
    # dispatches + G host/device round trips) vs ONE fused ComefaGrid
    # scan over the stacked state.  The fused dispatch must win
    # for G >= 8: that is the speedup every sharded sweep rides on.
    from repro.core.comefa import ComefaGrid
    grid_prog = mk_mul().optimize()
    for g in (1, 8):
        arrays = [ComefaArray(n_blocks=2) for _ in range(g)]
        for i, ga in enumerate(arrays):
            av = rng.integers(0, 1 << n, size=(2, 160))
            bv = rng.integers(0, 1 << n, size=(2, 160))
            layout.place(ga, av, 0, n)
            layout.place(ga, bv, n, n)
        gridarr = ComefaGrid.from_arrays(arrays)
        us_gloop = _bench(lambda: [_run_synced(ga, grid_prog)
                                   for ga in arrays])
        us_fused = _bench(lambda: _run_synced(gridarr, grid_prog))
        rows.append((f"sim/grid_g{g}_loop_us", us_gloop, us_gloop, None))
        rows.append((f"sim/grid_g{g}_fused_us", us_fused, us_fused, None))
        rows.append((f"sim/grid_g{g}_fused_speedup", 0.0,
                     us_gloop / us_fused, None))
    # modelled fleet-level counterpart: shared-FSM slices vs one looped
    # FSM on CoMeFa-D hardware (perf.gemv_grid)
    from repro.core.fpga_model import perf
    rows.append(("sim/grid_g8_hw_speedup_comefa_d", 0.0,
                 perf.gemv_grid("comefa-d", g=8).speedup, None))

    # execution engines: the same fused grid dispatch on the uint8
    # reference scan vs the bit-packed uint32 engine, at a
    # fleet-representative working set (G=8 slots x 8 blocks, 16-bit
    # mul, 280 cycles).  The reference moves 8x the bytes the state
    # holds; at this state size its per-step update also scales worse
    # than bandwidth, so the packed engine clears 10x with room.
    n16 = 16
    mul16 = program.mul(list(range(n16)), list(range(n16, 2 * n16)),
                        list(range(2 * n16, 4 * n16))).optimize()

    def _engine_grid(engine):
        egrid = ComefaGrid(8, n_blocks=8, engine=engine)
        for g in range(8):
            slot = egrid.slot(g)
            layout.place(slot, rng.integers(0, 1 << n16, size=(8, 160)),
                         0, n16)
            layout.place(slot, rng.integers(0, 1 << n16, size=(8, 160)),
                         n16, n16)
        return egrid

    ref_grid = _engine_grid("reference")
    us_eng_ref = _bench(lambda: _run_synced(ref_grid, mul16), reps=3)
    packed_grid = _engine_grid("packed")
    us_eng_packed = _bench(lambda: _run_synced(packed_grid, mul16), reps=3)
    rows.append(("sim/grid_g8_engine_reference_us", us_eng_ref,
                 us_eng_ref, None))
    rows.append(("sim/grid_g8_engine_packed_us", us_eng_packed,
                 us_eng_packed, None))
    rows.append(("sim/grid_g8_engine_packed_speedup", 0.0,
                 us_eng_ref / us_eng_packed, None))
    # informational: the Pallas kernel runs interpret-mode off-TPU, where
    # it emulates rather than accelerates - one rep, not a criterion row
    pallas_grid = _engine_grid("pallas")
    us_eng_pallas = _bench(lambda: _run_synced(pallas_grid, mul16), reps=1)
    rows.append(("sim/grid_g8_engine_pallas_interpret_us", us_eng_pallas,
                 us_eng_pallas, None))

    # tracing-disabled overhead: every dispatch crosses a handful of
    # obs spans (run + dispatch + host-sync + encode probe) and counter
    # bumps; with REPRO_COMEFA_TRACE unset each span is the shared
    # NULL_SPAN no-op.  Price that no-op path directly and express it as
    # a fraction of the packed-engine dispatch above - check_regression
    # gates the fraction (default < 2%).
    from repro.obs import trace as obs_trace
    assert not obs_trace.enabled(), \
        "overhead row must be measured with tracing off"
    probe = block._DISPATCHES
    spans_per_dispatch = 4
    n_probe = 10_000
    t0 = time.perf_counter()
    for _ in range(n_probe):
        with obs_trace.span("bench.noop"):
            probe.inc(kind="bench", engine="noop")
    per_span_us = (time.perf_counter() - t0) / n_probe * 1e6
    frac = spans_per_dispatch * per_span_us / us_eng_packed
    rows.append(("sim/grid_g8_trace_disabled_overhead_frac", 0.0,
                 frac, None))

    # modelled CoMeFa-D hardware time for the same program, for scale
    hw_us = timing.mul_cycles(n) / 588e6 * 1e6
    rows.append(("sim/mul8_hw_us_comefa_d", 0.0, hw_us, None))
    rows.append(("sim/mul8_hw_us_comefa_d_coissue", 0.0,
                 timing.achieved_cycles("mul", n) / 588e6 * 1e6, None))

    # chained vs single-block reduction: cycles to one scalar over ALL
    # lanes of nb chained blocks (Sec. III-F block hops dominate the tail)
    red_bits = 8
    for nb in (1, 2, 4):
        cyc = timing.chained_reduction_cycles(red_bits, n_blocks=nb)
        ach = timing.achieved_chained_reduction_cycles(red_bits, nb)
        rows.append((f"sim/chain_reduce_nb{nb}_cycles", 0.0, cyc, None))
        rows.append((f"sim/chain_reduce_nb{nb}_cycles_coissue",
                     0.0, ach, None))
    # wall-clock of the chained 2-block scalar reduction on the simulator
    nb2, rb = 2, 4
    steps, chain_steps = program.full_reduce_steps(nb2)
    total = steps + chain_steps
    red_arr = ComefaArray(n_blocks=nb2, chain=True)
    vals = rng.integers(0, 1 << rb, size=nb2 * 160)
    layout.plan_chain(nb2 * 160).place(red_arr, vals, 0, rb)
    val = list(range(rb + total))
    scratch = list(range(rb + total, 2 * (rb + total) - 1))
    red_prog = program.reduce_to_scalar(val, scratch, rb,
                                        n_blocks=nb2).optimize()
    us_red = _bench(lambda: _run_synced(red_arr, red_prog), reps=3)
    rows.append(("sim/chain_reduce_nb2_us", us_red, us_red, None))

    # streamed-operand recoding: GEMV chunk compute cycles under naive /
    # Booth / NAF digit streams (ir.specialize_streams over the same
    # symbolic GemvPlan template), on two activation profiles - uniform
    # random bits (NAF's ~n/3-vs-n/2 density win) and runs-of-ones
    # (thermometer-coded, Booth's sweet spot)
    from repro.core.comefa import ir as cir, plan_gemv
    gk, gwb, gxb, gaccb = 25, 8, 8, 27
    x_rand = [int(v) for v in rng.integers(0, 1 << gxb, size=gk)]
    x_runs = [0b01111110] * gk
    for xname, xs in (("rand", x_rand), ("runs", x_runs)):
        for rc in ("naive", "booth", "naf"):
            plan = plan_gemv(gk, 160, gwb, gxb, gaccb, k_tile=5,
                             reserve_neg=cir.recode_is_signed(rc))
            sched = plan.schedule(xs, optimized=True, recode=rc)
            compute = sum(c[1] for c in sched.tile_costs)
            rows.append((f"sim/gemv_recode_{xname}_{rc}_cycles",
                         0.0, compute, None))

    # grid-batched GEMV: shared mask-predicated broadcast program (the
    # value-independent PR-4 trade) vs per-slot stream specialization
    # (run_per_slot: each slice's FSM streams its own recoded digits) -
    # modelled compute cycles per slot, sparse-bit activations
    from repro.kernels import comefa_sim as _cs
    bg, bk, bn, bwb, bxb, baccb = 4, 12, 160, 4, 6, 20
    bw = rng.integers(0, 1 << bwb, size=(bg, bk, bn))
    bx = (1 << rng.integers(0, bxb, size=(bg, bk))).astype(np.int64)

    def _batched_cycles(recode, x=bx):
        stats = {}
        _cs.comefa_gemv_batched(bw, x, w_bits=bwb, x_bits=bxb,
                                acc_bits=baccb, recode=recode, stats=stats)
        return stats["cycles"]

    cyc_mask = _batched_cycles(None)
    rows.append(("sim/gemv_batched_mask_cycles", 0.0, cyc_mask, None))
    for rc in ("naive", "naf"):
        cyc_ps = _batched_cycles(rc)
        rows.append((f"sim/gemv_batched_perslot_{rc}_cycles",
                     0.0, cyc_ps, None))
        rows.append((f"sim/gemv_batched_perslot_{rc}_cycle_speedup",
                     0.0, cyc_mask / cyc_ps, None))

    # adaptive recode selection (recode="auto"): per-wave/per-slot exact
    # pricing must match-or-beat the best fixed global knob on BOTH
    # activation profiles.  Sparse reuses the one-hot stream above; dense
    # mixes a carry-run slot (NAF territory) with an adjacent-pair slot
    # (naive territory) so no single fixed recode can win the makespan.
    # check_regression gates these ratios at >= 0.98 absolute.
    bx_dense = np.full((bg, bk), (1 << bxb) - 1, np.int64)
    bx_dense[0] = 3
    for sname, sx in (("sparse", bx), ("dense", bx_dense)):
        fixed = {rc: _batched_cycles(rc, sx)
                 for rc in (None, "naive", "booth", "naf")}
        auto = _batched_cycles("auto", sx)
        rows.append((f"gemv/auto_vs_best_fixed_ratio_{sname}", 0.0,
                     min(fixed.values()) / auto, None))

    # FIR steady-state per-sample cycles (taps resident across the chain,
    # samples streamed OOOR) vs the generic-MAC closed form
    rows.append(("sim/fir_per_sample_cycles_coissue", 0.0,
                 timing.achieved_fir_cycles_per_sample(16, 16, 36), None))
    rows.append(("sim/fir_per_sample_cycles_closed_form", 0.0,
                 timing.fir_cycles(1, 16, 36, include_init=False,
                                   x_values=[0b0101010101010101]), None))
    rows.append(("sim/fir_per_sample_cycles_generic_mac", 0.0,
                 timing.mac_cycles(16, 36) / 2, None))

    # serving on the grid: continuous-batched decode with every packed
    # projection executed on the bit-level ComefaGrid simulator.  Six
    # staggered-length requests over 2 slots keep the admission queue
    # non-empty until the drain, so grid occupancy stays >= 90% - the
    # check_regression gate pins both the occupancy floor and tokens/sec.
    import dataclasses as _dc

    from repro import configs as _cfgs
    from repro.core.fpga_model import perf as _perf
    from repro.models import common as _cm, lm as _lm
    from repro.serve import engine as _engine
    from repro.serve.comefa_exec import GridLinearExecutor

    scfg = _dc.replace(
        _cm.reduced(_cfgs.get("smollm-360m"), vocab=64, n_layers=1,
                    d_model=32, d_ff=64, n_heads=2, kv_heads=2,
                    head_dim=16, dtype="float32"),
        quant_bits=8)
    sparams = _lm.init(jax.random.PRNGKey(0), scfg)
    sreqs = [_engine.Request(np.arange(1, 2 + i % 3), 2 + (i * 2) % 5)
             for i in range(6)]
    sstats: dict = {}
    sexec = GridLinearExecutor(slots=2, backend="grid")
    _engine.serve_continuous(sparams, sreqs, scfg, slots=2, max_len=12,
                             executor=sexec, stats=sstats)     # warmup/encode
    sstats.clear()
    sexec2 = GridLinearExecutor(slots=2, backend="grid")
    t0 = time.perf_counter()
    souts = _engine.serve_continuous(sparams, sreqs, scfg, slots=2,
                                     max_len=12, executor=sexec2,
                                     stats=sstats)
    serve_s = time.perf_counter() - t0
    n_tokens = sum(len(o) for o in souts)
    rows.append(("serve/decode_tok_s", serve_s / n_tokens * 1e6,
                 n_tokens / serve_s, None))
    rows.append(("serve/grid_occupancy", 0.0, sstats["occupancy"], None))
    rows.append(("serve/grid_cycles_per_token", 0.0,
                 sexec2.grid_cycles / n_tokens, None))

    # adaptive serving: the same staggered sweep under each recode knob.
    # Decode activations are offset-encoded around 2^(x-1), splitting
    # into one-digit values and carry runs - the mixed regime where the
    # per-chunk selector wins.  check_regression pins cycles_per_token
    # auto strictly below EVERY fixed global recode (all deterministic).
    def _sreqs():
        return [_engine.Request(np.arange(1, 2 + i % 3), 2 + (i * 2) % 5)
                for i in range(6)]

    for src in ("naive", "booth", "naf"):
        sexec_rc = GridLinearExecutor(slots=2, backend="grid", recode=src)
        souts_rc = _engine.serve_continuous(sparams, _sreqs(), scfg,
                                            slots=2, max_len=12,
                                            executor=sexec_rc)
        rows.append((f"serve/grid_cycles_per_token_{src}", 0.0,
                     sexec_rc.grid_cycles / sum(map(len, souts_rc)), None))
    sexec_a = GridLinearExecutor(slots=2, backend="grid", recode="auto")
    _engine.serve_continuous(sparams, _sreqs(), scfg, slots=2,
                             max_len=12, executor=sexec_a)    # warm caches
    sexec_a2 = GridLinearExecutor(slots=2, backend="grid", recode="auto")
    t0 = time.perf_counter()
    souts_a = _engine.serve_continuous(sparams, _sreqs(), scfg,
                                       slots=2, max_len=12,
                                       executor=sexec_a2)
    auto_s = time.perf_counter() - t0
    n_tok_a = sum(len(o) for o in souts_a)
    rows.append(("serve/decode_tok_s_auto", auto_s / n_tok_a * 1e6,
                 n_tok_a / auto_s, None))
    rows.append(("serve/grid_cycles_per_token_auto", 0.0,
                 sexec_a2.grid_cycles / n_tok_a, None))
    # modelled serving roofline: decode tokens/sec-per-mm^2 density gain
    # of the augmented chip over the DSP baseline (perf.serve_roofline)
    sroof = _perf.serve_roofline()
    for var in ("comefa-d", "comefa-a"):
        rows.append((f"serve/roofline_density_gain_{var}", 0.0,
                     sroof[var]["gain"], None))

    # tiled GEMM: LCU-overlapped vs serial-phase schedules (cycles), plus
    # the sim-backed comefa_gemm wall-clock for the same shape
    from repro.kernels import comefa_sim
    gm, gk, gn, gbits, gnb = 5, 40, 9, 2, 4      # 5 tiles, ragged last
    plan = plan_gemm(gm, gk, gn, gbits, n_blocks=gnb)
    ser = plan.schedule(optimized=False)
    opt = plan.schedule(optimized=True)
    tag = f"sim/gemm_m{gm}k{gk}n{gn}_nb{gnb}"
    rows.append((f"{tag}_cycles_serial", 0.0, ser.serial_cycles, None))
    rows.append((f"{tag}_cycles_lcu", 0.0, ser.total_cycles, None))
    rows.append((f"{tag}_cycles_lcu_coissue", 0.0, opt.total_cycles, None))
    rows.append((f"{tag}_steady_state_cycles", 0.0,
                 ser.steady_state_cycles, None))
    rows.append((f"{tag}_serial_tile_cycles", 0.0,
                 ser.serial_tile_cycles, None))
    ga = rng.integers(0, 1 << gbits, size=(gm, gk))
    gb = rng.integers(0, 1 << gbits, size=(gk, gn))
    us_gemm = _bench(lambda: comefa_sim.comefa_gemm(ga, gb, bits=gbits,
                                                    n_blocks=gnb), reps=3)
    us_gemm_unopt = _bench(
        lambda: comefa_sim.comefa_gemm(ga, gb, bits=gbits, n_blocks=gnb,
                                       optimized=False), reps=3)
    rows.append((f"{tag}_us_coissue", us_gemm, us_gemm, None))
    rows.append((f"{tag}_us_unopt", us_gemm_unopt, us_gemm_unopt, None))
    # modelled CoMeFa-D hardware time: LCU-pipelined vs serial phases
    rows.append((f"{tag}_hw_us_comefa_d_lcu", 0.0,
                 opt.total_cycles / 588e6 * 1e6, None))
    rows.append((f"{tag}_hw_us_comefa_d_serial", 0.0,
                 opt.serial_cycles / 588e6 * 1e6, None))


def _rows_as_json(rows: list) -> dict:
    """Machine-readable form of the benchmark rows (nightly artifact).

    Besides the timing rows, the payload carries a ``metrics`` block:
    the `repro.obs.metrics` registry summary accumulated while the
    benchmarks ran (encode-cache hit rates, host syncs, per-engine
    dispatch counts) - so one artifact answers both "how fast" and
    "what did the run actually do".
    """
    from repro.obs import export as obs_export
    return {
        "benchmark": "sim_speed",
        "columns": ["name", "us_per_call", "derived", "paper"],
        "rows": [
            {"name": name, "us_per_call": us, "derived": derived,
             "paper": paper}
            for name, us, derived, paper in rows],
        "metrics": obs_export.metrics_summary(),
    }


def main(argv=None) -> None:
    import argparse
    import json
    import sys

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write rows as JSON to PATH ('-' for stdout)")
    args = ap.parse_args(argv)
    from repro import compile_cache
    compile_cache.enable()
    rows: list = []
    run(rows)
    if args.json is not None:
        payload = json.dumps(_rows_as_json(rows), indent=2)
        if args.json == "-":
            print(payload)
        else:
            with open(args.json, "w") as f:
                f.write(payload + "\n")
    if args.json != "-":
        print("name,us_per_call,derived,paper")
        for name, us, derived, paper in rows:
            p = "" if paper is None else f"{paper:.6g}"
            print(f"{name},{us:.2f},{derived:.6g},{p}")


if __name__ == "__main__":
    main()
