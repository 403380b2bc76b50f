"""Simulator-backed CoMeFa kernels, driven by the program IR.

The Pallas kernels in this package model CoMeFa's bit-serial math on the
MXU/VPU; this module runs the *same* workloads through the bit-level
`ComefaArray` instead, using `ProgramBuilder`-assembled, IR-optimized
programs.  It is the validation backend that ties the kernel layer to the
hardware model, and the showcase for the encode cache: shape-dependent
programs (elementwise mul) are built and encoded once, then every batch
reuses the cached engine matrix.  Every kernel takes ``engine=`` and
threads it to the simulator (`core.comefa.block.get_engine`), so the
bit-packed engines accelerate these workloads without touching call sites
- ``REPRO_COMEFA_ENGINE=packed`` flips the whole module.

Row budgets are bounded by one block's register file (`isa.USABLE_ROWS`:
the 128 wordlines minus the reserved all-zeros/all-ones constant rows),
so this backend targets correctness checks and benchmarking, not
throughput.  *Lane* budgets are not bounded: `comefa_dot` and
`comefa_fir` spread one logical operand across ``n_blocks * 160`` lanes
of a chain=True array (Sec. III-F shift chaining) and reduce across the
whole chain, and `comefa_gemm` / `comefa_gemv` tile whole GEMM/GEMV
problems through `core.comefa.schedule`'s double-buffered LCU plans.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from ..core.comefa import (ComefaArray, ComefaGrid, N_COLS, layout, program,
                           schedule)
from ..core.comefa import ir as ir_mod
from ..core.comefa import recode as recode_mod
from ..core.comefa.ir import Program, RowAllocator
from ..core.comefa.isa import (Instr, N_ROWS, PRED_MASK, RESERVED_ROWS,
                               TT_COPY_A, USABLE_ROWS, ceil_log2)
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace

# modelled compute cycles per kernel invocation; the registry-side home of
# the legacy ``stats={"cycles": ...}`` side channel (which keeps working)
_KERNEL_CYCLES = obs_metrics.counter("comefa.kernel_cycles")

# shape-keyed cache of built + optimized programs (the expensive part is
# Python-side generation; the engine-matrix encode cache in `block.py`
# additionally skips re-encoding when equal programs are rebuilt)
_PROGRAMS: Dict[Tuple, Tuple[Program, tuple]] = {}

# FIR per-sample programs are keyed by the sample *value* (the schedule
# depends on exactly its set bits), so up to 2^x_bits entries can exist -
# bounded with FIFO eviction, mirroring block.py's encode cache
_FIR_CACHE: Dict[Tuple, Program] = {}
_FIR_CACHE_MAX = 1024
_LANE0 = np.array([0])


def _eltwise_mul_program(bits: int) -> Tuple[Program, tuple]:
    key = ("eltwise_mul", bits)
    if key not in _PROGRAMS:
        b = program.ProgramBuilder(f"eltwise_mul{bits}")
        x = b.input(bits, "x")
        y = b.input(bits, "y")
        prod = b.mul(x, y)
        _PROGRAMS[key] = (b.build(), (x, y, prod))
    return _PROGRAMS[key]


def comefa_eltwise_mul(a: np.ndarray, b: np.ndarray, *, bits: int,
                       optimized: bool = True,
                       engine=None) -> np.ndarray:
    """Unsigned elementwise multiply on the bit-level simulator.

    Tiles the flat inputs across blocks x 160 lanes, runs one cached
    co-issued program per array (all blocks execute it SIMD), and returns
    the 2*bits-bit products.
    """
    a = np.asarray(a).ravel()
    b = np.asarray(b).ravel()
    assert a.shape == b.shape
    prog, (rx, ry, rout) = _eltwise_mul_program(bits)
    if not optimized:
        key = ("eltwise_mul_raw", bits)
        if key not in _PROGRAMS:
            raw = program.mul(rx, ry, rout)
            _PROGRAMS[key] = (raw, (rx, ry, rout))
        prog = _PROGRAMS[key][0]
    n = a.shape[0]
    lanes = N_COLS
    n_blocks = max(1, -(-n // lanes))
    pad = n_blocks * lanes - n
    a2 = np.pad(a, (0, pad)).reshape(n_blocks, lanes)
    b2 = np.pad(b, (0, pad)).reshape(n_blocks, lanes)
    arr = ComefaArray(n_blocks=n_blocks, engine=engine)
    layout.place(arr, a2, rx.base, bits)
    layout.place(arr, b2, ry.base, bits)
    arr.run(prog)
    out = layout.extract(arr, rout.base, 2 * bits)
    return out.reshape(-1)[:n]


def comefa_gemv(w: np.ndarray, x: np.ndarray, *, w_bits: int,
                x_bits: int, acc_bits: int = 32,
                optimized: bool = True,
                recode: str = "naive", engine=None) -> np.ndarray:
    """y = w.T @ x with resident weights and a streamed vector (OOOR).

    w: [k, n] unsigned ints; x: [k] unsigned ints.  The k dimension is
    chunked through `schedule.GemvPlan`'s double-buffered weight regions
    (chunk t+1 would load while chunk t computes on hardware), so k is no
    longer capped by the one-shot row budget.  Chunk programs are the
    plan's shared *symbolic* templates specialized per x through
    `ir.specialize_streams` (the FSM inspecting the outside operand -
    Sec. III-I): ``recode`` picks the digit schedule - ``"naive"``
    zero-skips binary bits, ``"booth"`` / ``"naf"`` stream signed digits
    (the plan reserves a complement scratch region), ``"auto"`` lets
    `core.comefa.recode.select_chunk` pick the cheapest schedule per
    chunk from its exact digit statistics - and the result is bit-exact
    under every mode.  Partial sums accumulate in the shared
    accumulator; all n outputs extract after the last chunk.
    """
    w = np.asarray(w)
    x = np.asarray(x).ravel()
    k, n = w.shape
    assert x.shape[0] == k
    # "auto" may pick a signed schedule per chunk: plan for the worst case
    reserve = recode == "auto" or ir_mod.recode_is_signed(recode)
    plan = schedule.cached_plan_gemv(k, n, w_bits, x_bits, acc_bits,
                                     reserve_neg=reserve)
    nb, lanes = plan.n_blocks, N_COLS
    pad = nb * lanes - n
    arr = ComefaArray(n_blocks=nb, engine=engine)
    costs = []
    with obs_trace.span("kernel.gemv", k=k, n=n, recode=recode) as sp:
        for tile in plan.tiles():
            buf = plan.buffers[tile.buffer]
            for j_local, j in enumerate(range(tile.k_start, tile.k_end)):
                wj = np.pad(w[j], (0, pad)).reshape(nb, lanes)
                rows = buf.weight_rows(j_local, w_bits)
                layout.place(arr, wj, rows.base, w_bits)
            prog = plan.tile_program(tile, x[tile.k_start:tile.k_end],
                                     optimized=optimized, recode=recode)
            arr.run(prog)
            if obs_trace.enabled():
                costs.append((plan.load_cycles(tile), prog.cycles,
                              plan.unload_cycles(tile)))
        sp.set(cycles=arr.cycles)
    _KERNEL_CYCLES.inc(arr.cycles, kernel="gemv", mode=recode)
    if costs:
        schedule.Schedule(costs, name=f"gemv_k{k}").emit_trace()
    out = layout.extract(arr, plan.acc.base, acc_bits)
    return out.reshape(-1)[:n]


def comefa_gemm(a: np.ndarray, b: np.ndarray, *, bits: int,
                n_blocks: int = 1, optimized: bool = True,
                engine=None) -> np.ndarray:
    """C = a @ b on the bit-level simulator via the tiled LCU plan.

    a: [m, k], b: [k, n] unsigned ints below 2**bits.  `schedule.plan_gemm`
    packs `dots_per_tile` output dot products per tile across the
    ``n_blocks * 160``-lane chain (each in a ``2^ceil(log2(k))``-lane
    group); the tile program - a lane-wise multiply plus a
    `program.reduce_tree` group reduction - leaves every packed dot in
    its group-head lane.  Tiles alternate between the plan's two
    double-buffered row regions (the layout that lets load/unload overlap
    compute on hardware; the simulator executes them back-to-back) and
    results drain from the head lanes after each tile.

    Bit-exact against ``np.matmul``; with ``optimized=False`` the total
    simulator cycles are exactly ``n_tiles`` times the closed-form tile
    compute cost priced inside `timing.gemm_cycles`.
    """
    a = np.asarray(a)
    b = np.asarray(b)
    assert a.ndim == 2 and b.ndim == 2 and a.shape[1] == b.shape[0]
    m, k = a.shape
    n = b.shape[1]
    plan = schedule.plan_gemm(m, k, n, bits, n_blocks=n_blocks)
    lane_plan = plan.lane_plan()
    arr = ComefaArray(n_blocks=plan.n_blocks, chain=True, engine=engine)
    out = np.empty(plan.n_outputs, dtype=np.int64)
    with obs_trace.span("kernel.gemm", m=m, k=k, n=n, bits=bits) as sp:
        for tile in plan.tiles():
            buf = plan.buffers[tile.buffer]
            xv, yv = plan.tile_operands(tile, a, b)
            lane_plan.place(arr, xv, buf.x.base, bits)
            lane_plan.place(arr, yv, buf.y.base, bits)
            arr.run(plan.compute_program(tile.buffer, optimized=optimized))
            heads = plan.head_lanes(tile)
            vals = np.empty(tile.n_dots, dtype=np.int64)
            for blk in range(plan.n_blocks):
                sel = (heads // N_COLS) == blk
                if sel.any():
                    vals[sel] = layout.extract(arr, buf.acc.base,
                                               plan.acc_bits,
                                               lanes=heads[sel] % N_COLS,
                                               block=blk)
            out[tile.out_start:tile.out_end] = vals
        sp.set(cycles=arr.cycles)
    _KERNEL_CYCLES.inc(arr.cycles, kernel="gemm", mode="chained")
    if obs_trace.enabled():
        plan.schedule(optimized=optimized).emit_trace()
    return out.reshape(m, n)


def comefa_dot(a: np.ndarray, b: np.ndarray, *, bits: int,
               optimized: bool = True, engine=None) -> int:
    """Full dot product <a, b> reduced to ONE scalar across all blocks.

    Where `comefa_gemv` stops at per-lane partial sums, this kernel
    places the two vectors one element per lane across
    ``ceil(n / 160)`` chained blocks (`layout.plan_chain`), multiplies
    lane-wise, then runs the chained tree reduction
    (`program.reduce_to_scalar`): doubling-distance shift+add steps whose
    final hops cross block boundaries through the corner PEs
    (Sec. III-F).  The scalar lands in lane 0 of block 0.

    The unoptimized reduction segment costs exactly
    `timing.chained_reduction_cycles(2 * bits, n_blocks=...)` cycles.
    """
    a = np.asarray(a).ravel()
    b = np.asarray(b).ravel()
    assert a.shape == b.shape
    n = a.shape[0]
    plan = layout.plan_chain(n)
    nb = plan.n_blocks
    steps, chain_steps = program.full_reduce_steps(nb)
    acc_bits = 2 * bits + steps + chain_steps
    demand = 2 * bits + acc_bits + (acc_bits - 1)   # x, y, acc, scratch
    assert demand <= USABLE_ROWS, (
        f"operands need {demand} rows (2 x {bits}-bit inputs + "
        f"{acc_bits}-bit accumulator + reduction scratch), only "
        f"{USABLE_ROWS} usable rows per block")
    key = ("dot", bits, nb, optimized)
    if key not in _PROGRAMS:
        bld = program.ProgramBuilder(f"dot{bits}_nb{nb}")
        rx = bld.input(bits, "x")
        ry = bld.input(bits, "y")
        acc = bld.input(acc_bits, "acc")
        bld.emit(program.mul(rx, ry, acc[:2 * bits]))
        bld.emit(program.zero_rows(acc[2 * bits:]))
        bld.reduce_all(acc, 2 * bits, n_blocks=nb)
        _PROGRAMS[key] = (bld.build(optimize=optimized), (rx, ry, acc))
    prog, (rx, ry, acc) = _PROGRAMS[key]
    arr = ComefaArray(n_blocks=nb, chain=True, engine=engine)
    plan.place(arr, a, rx.base, bits)
    plan.place(arr, b, ry.base, bits)
    arr.run(prog)
    return int(layout.extract(arr, acc.base, acc_bits, block=0)[0])


def comefa_fir(taps: np.ndarray, x: np.ndarray, *, tap_bits: int,
               x_bits: int, acc_bits: Optional[int] = None,
               optimized: bool = True, recode: str = "naive",
               engine=None) -> np.ndarray:
    """y[t] = sum_j taps[j] * x[t-j]: resident taps, streamed samples.

    The paper's FIR benchmark (Sec. IV-C): taps live transposed one per
    lane across ``ceil(n_taps / 160)`` chained blocks, samples stream
    through the instruction generator (OOOR).  Each sample costs one
    accumulator add per *set* sample bit plus a chained left shift of the
    partial sums - the transposed-form delay line, with partials hopping
    block seams through the corner PEs.  y[t] drains from lane 0 of
    block 0 after each sample's accumulate phase.  Sample programs are
    specialized from the symbolic `program.fir_sample_stream` template;
    ``recode`` picks the digit schedule (signed Booth/NAF modes allocate
    a tap-complement scratch region beside the accumulator).

    With ``optimized=False`` (and the default naive recoding) the total
    simulator cycles equal
    `timing.fir_cycles(len(x), x_bits, acc_bits, x_values=x)` exactly.
    """
    taps = np.asarray(taps).ravel()
    x = np.asarray(x).ravel()
    n_taps = taps.shape[0]
    plan = layout.plan_chain(n_taps)
    nb = plan.n_blocks
    if acc_bits is None:
        acc_bits = tap_bits + x_bits + ceil_log2(max(2, n_taps))
    signed = ir_mod.recode_is_signed(recode)
    demand = tap_bits + acc_bits + (tap_bits if signed else 0)
    assert demand <= USABLE_ROWS, (
        f"taps + accumulator{' + complement scratch' if signed else ''} "
        f"need {demand} rows, only {USABLE_ROWS} usable rows per block")
    alloc = RowAllocator()
    tap_rows = alloc.alloc(tap_bits, "taps")
    acc = alloc.alloc(acc_bits, "acc")
    neg = alloc.alloc(tap_bits, "neg") if signed else None
    arr = ComefaArray(n_blocks=nb, chain=True, engine=engine)
    plan.place(arr, taps, tap_rows.base, tap_bits)

    # per-phase programs are cached: repeated samples skip both
    # Python-side generation and the IR pass pipeline
    def cached(key_tail, build):
        key = (tap_bits, x_bits, acc_bits, optimized) + key_tail + (recode,)
        prog = _FIR_CACHE.get(key)
        if prog is None:
            prog = build()
            if optimized:
                prog = prog.optimize()
            if len(_FIR_CACHE) >= _FIR_CACHE_MAX:
                _FIR_CACHE.pop(next(iter(_FIR_CACHE)))   # FIFO eviction
            _FIR_CACHE[key] = prog
        return prog

    arr.run(cached(("init",), lambda: program.zero_rows(acc)))
    shift = cached(("shift",),
                   lambda: program.shift_lanes(acc, acc, left=True))
    y = np.empty(x.shape[0], dtype=np.int64)
    for t, x_t in enumerate(x):
        arr.run(cached((int(x_t),),
                       lambda: program.fir_sample(tap_rows, acc, int(x_t),
                                                  x_bits, shift=False,
                                                  recode=recode,
                                                  neg_scratch=neg)))
        # y[t] sits in lane 0 of block 0 between accumulate and shift
        y[t] = layout.extract(arr, acc.base, acc_bits, lanes=_LANE0,
                              block=0)[0]
        arr.run(shift)
    return y


# ---------------------------------------------------------------------------
# grid sweeps: G independent problem instances, one shared program stream
# (ComefaGrid: Sec. III-D shared-FSM broadcast at array-of-arrays scale)
# ---------------------------------------------------------------------------

def comefa_gemm_batched(a: np.ndarray, b: np.ndarray, *, bits: int,
                        n_blocks: int = 1, optimized: bool = True,
                        mesh=None, engine=None) -> np.ndarray:
    """C[g] = a[g] @ b[g] for G independent same-shape GEMMs on ONE grid.

    a: [G, m, k], b: [G, k, n] unsigned ints below 2**bits.  Every grid
    slot owns one problem instance; the `schedule.plan_gemm` tile
    programs depend only on the shape, so all G slots execute the same
    instruction stream per tile (one fused grid scan dispatch instead of
    a Python loop of G `ComefaArray.run` calls) and the per-slot results
    are bit-identical to G separate `comefa_gemm` calls.  Pass `mesh`
    to shard the grid axis across devices.
    """
    a = np.asarray(a)
    b = np.asarray(b)
    assert a.ndim == 3 and b.ndim == 3 and a.shape[0] == b.shape[0]
    assert a.shape[2] == b.shape[1]
    G, m, k = a.shape
    n = b.shape[2]
    plan = schedule.plan_gemm(m, k, n, bits, n_blocks=n_blocks)
    lane_plan = plan.lane_plan()
    grid = ComefaGrid(G, n_blocks=plan.n_blocks, chain=True, mesh=mesh,
                      engine=engine)
    out = np.empty((G, plan.n_outputs), dtype=np.int64)
    for tile in plan.tiles():
        buf = plan.buffers[tile.buffer]
        for g in range(G):
            xv, yv = plan.tile_operands(tile, a[g], b[g])
            slot = grid.slot(g)
            lane_plan.place(slot, xv, buf.x.base, bits)
            lane_plan.place(slot, yv, buf.y.base, bits)
        grid.run(plan.compute_program(tile.buffer, optimized=optimized))
        heads = plan.head_lanes(tile)
        for g in range(G):
            slot = grid.slot(g)
            vals = np.empty(tile.n_dots, dtype=np.int64)
            for blk in range(plan.n_blocks):
                sel = (heads // N_COLS) == blk
                if sel.any():
                    vals[sel] = layout.extract(slot, buf.acc.base,
                                               plan.acc_bits,
                                               lanes=heads[sel] % N_COLS,
                                               block=blk)
            out[g, tile.out_start:tile.out_end] = vals
    return out.reshape(G, m, n)


def gemv_batched_k_tile(w_bits: int, x_bits: int, acc_bits: int) -> int:
    """Largest chunk fitting double-buffered weights + resident x bits."""
    return (USABLE_ROWS - acc_bits) // (2 * w_bits + x_bits)


def _gemv_batched_layout(plan: schedule.GemvPlan):
    """Per-chunk activation-bit rows, allocated beside the plan's regions.

    The batched GEMV keeps each slot's streamed activations *resident*
    (broadcast across all lanes of that slot) instead of encoding them
    into the instruction stream, so one value-independent program can
    drive every slot.  Rows come from whatever the `GemvPlan` left free.
    """
    used = set(plan.acc)
    for buf in plan.buffers:
        used |= set(buf.rows)
    free = sorted(set(range(N_ROWS)) - set(RESERVED_ROWS) - used)
    alloc = RowAllocator.from_rows(free)
    return [alloc.alloc(plan.x_bits, f"x{j}") for j in range(plan.k_tile)]


def _gemv_batched_chunk_program(plan: schedule.GemvPlan,
                                tile: schedule.GemvTile,
                                x_rows, optimized: bool) -> Program:
    """Shared (value-independent) accumulate program for one k-chunk.

    For each resident weight j and each activation bit b, the program
    loads the mask latch from the slot's broadcast x[j] bit-b row, then
    mask-predicates the `add_into` at offset b - the same predication
    pattern `program.mul` uses per multiplier bit.  Slots where the bit
    is 0 retire the adds as no-ops; the cycle count is value-independent
    (the price of sharing one FSM stream across the grid, vs the per-x
    OOOR zero-skipping of `comefa_gemv`).
    """
    key = ("gemv_batched", plan.w_bits, plan.x_bits, plan.acc_bits,
           plan.k_tile, tile.n_elems, tile.buffer, tile.index == 0,
           optimized)
    if key not in _PROGRAMS:
        buf = plan.buffers[tile.buffer]
        prog = Program(name=f"gemv_batched_chunk{tile.index}")
        if tile.index == 0:
            prog += program.zero_rows(plan.acc)
        for j in range(tile.n_elems):
            w = buf.weight_rows(j, plan.w_bits)
            for b in range(plan.x_bits):
                prog.append(Instr(src1_row=x_rows[j][b],
                                  truth_table=TT_COPY_A, m_en=1, c_rst=1))
                prog += program.add_into(plan.acc, w, b,
                                         pred_sel=PRED_MASK)
        prog = prog.with_live_out(set(plan.acc))
        if optimized:
            prog = prog.optimize()
        _PROGRAMS[key] = (prog, ())
    return _PROGRAMS[key][0]


# per-shape cached broadcast quotes for the auto selector (the underlying
# plan and chunk programs are themselves shape-cached; this just skips
# re-walking the tiles per wave)
_BCAST_QUOTES: Dict[Tuple, Optional[recode_mod.BroadcastQuote]] = {}


def _broadcast_quote(k: int, n: int, w_bits: int, x_bits: int,
                     acc_bits: int,
                     optimized: bool) -> Optional[recode_mod.BroadcastQuote]:
    """Price the shared-FSM broadcast alternative for the auto selector.

    None when the shrunk broadcast chunk (`gemv_batched_k_tile`) has no
    room at all; otherwise a `recode.BroadcastQuote` carrying the
    broadcast-geometry plan and the actual mask-program length per tile
    - the selector prices the x-row load traffic on top.
    """
    key = (k, n, w_bits, x_bits, acc_bits, optimized)
    if key not in _BCAST_QUOTES:
        k_tile = gemv_batched_k_tile(w_bits, x_bits, acc_bits)
        if k_tile < 1:
            _BCAST_QUOTES[key] = None
        else:
            plan = schedule.cached_plan_gemv(k, n, w_bits, x_bits,
                                             acc_bits,
                                             k_tile=min(k, k_tile))
            x_rows = _gemv_batched_layout(plan)
            comp = tuple(
                _gemv_batched_chunk_program(plan, t, x_rows,
                                            optimized).cycles
                for t in plan.tiles())
            _BCAST_QUOTES[key] = recode_mod.BroadcastQuote(
                plan=plan, compute_cycles=comp)
    return _BCAST_QUOTES[key]


def _extract_batched(grid: ComefaGrid, base: int, acc_bits: int,
                     n: int) -> np.ndarray:
    """Every slot's first `n` accumulator lanes, ``[G, n]``, in a
    ``kernel.extract`` span (after ``kernel.gemv_batched`` has closed)."""
    out = np.empty((grid.g, n), dtype=np.int64)
    with obs_trace.span("kernel.extract"):
        for g in range(grid.g):
            vals = layout.extract(grid.slot(g), base, acc_bits)
            out[g] = vals.reshape(-1)[:n]
    return out


def comefa_gemv_batched(w: np.ndarray, x: np.ndarray, *, w_bits: int,
                        x_bits: int, acc_bits: int = 32,
                        optimized: bool = True, mesh=None,
                        recode: Optional[str] = None,
                        stats: Optional[Dict] = None,
                        engine=None) -> np.ndarray:
    """y[g] = w[g].T @ x[g] for G independent GEMVs on ONE grid dispatch.

    w: [G, k, n], x: [G, k] unsigned ints.  Two execution modes:

      * ``recode=None`` (the shared-FSM broadcast): geometry from the
        same `schedule.plan_gemv` double-buffered chunking as
        `comefa_gemv`, with the k-chunk shrunk so each chunk's
        activation bits fit as broadcast rows (`gemv_batched_k_tile`) -
        every slot loads its own weights AND its own x bits, then all
        slots execute one shared mask-predicated accumulate program
        whose cycle count is value-independent (no zero-skipping: the
        PR-4 trade for grid-wide SIMD).
      * ``recode="naive" | "booth" | "naf"`` (per-slot streams): one
        instruction FSM per grid slice.  The plan's *symbolic* chunk
        template is shared, each slot's activation chunk specializes it
        into its own digit stream (`ir.specialize_streams`), and
        `ComefaGrid.run_per_slot` dispatches the per-slot programs
        together - the grid sweep regains the OOOR zero-skipping (and
        Booth/NAF recoding) the broadcast mode gave up, with per-slot
        cycle counts matching `comefa_gemv` for the same recode.
      * ``recode="auto"`` (adaptive): `recode.select_wave` prices every
        candidate - the broadcast mask program on its own shrunk
        geometry, naive/Booth/NAF per slot - against the wave's *actual*
        activation values and executes the cheapest pipelined makespan;
        per-slot FSMs make mixed recodes across slots (and across
        k-chunks) legal, so sparse and dense slots each get their
        cheapest digit schedule.

    Bit-identical per slot to G separate `comefa_gemv` calls in every
    mode.  Pass `mesh` to shard the grid axis; a `stats` dict receives
    the grid's modelled compute ``cycles`` (the per-slot lockstep /
    makespan count - how the benchmark rows compare the two modes) and
    the executed ``mode`` ("broadcast" or "per_slot").  The same count
    also lands in the ``comefa.kernel_cycles`` counter (labels
    ``kernel="gemv_batched"``, ``mode``) of the `repro.obs.metrics`
    registry - prefer that for new callers; the ``stats`` side channel
    is kept for compatibility.
    """
    w = np.asarray(w)
    x = np.asarray(x)
    assert w.ndim == 3 and x.ndim == 2 and w.shape[0] == x.shape[0]
    assert w.shape[1] == x.shape[1]
    G, k, n = w.shape
    choices = None
    if recode == "auto":
        plan_ps = schedule.cached_plan_gemv(k, n, w_bits, x_bits, acc_bits,
                                            reserve_neg=True)
        sel = recode_mod.select_wave(
            plan_ps, x, broadcast=_broadcast_quote(k, n, w_bits, x_bits,
                                                   acc_bits, optimized))
        if sel.mode == "broadcast":
            recode = None            # the shared mask program won
        else:
            choices = sel.choices
    if recode is not None:
        return _comefa_gemv_per_slot(w, x, w_bits=w_bits, x_bits=x_bits,
                                     acc_bits=acc_bits, optimized=optimized,
                                     mesh=mesh, recode=recode,
                                     choices=choices, stats=stats,
                                     engine=engine)
    k_tile = gemv_batched_k_tile(w_bits, x_bits, acc_bits)
    if k_tile < 1:
        raise ValueError(
            f"no room for a double-buffered {w_bits}-bit weight plus "
            f"{x_bits} broadcast x rows beside a {acc_bits}-bit "
            f"accumulator ({USABLE_ROWS} usable rows)")
    plan = schedule.cached_plan_gemv(k, n, w_bits, x_bits, acc_bits,
                                     k_tile=min(k, k_tile))
    x_rows = _gemv_batched_layout(plan)
    nb, lanes = plan.n_blocks, N_COLS
    pad = nb * lanes - n
    grid = ComefaGrid(G, n_blocks=nb, mesh=mesh, engine=engine)
    costs = []
    with obs_trace.span("kernel.gemv_batched", slots=G, k=k, n=n,
                        mode="broadcast") as sp:
        for tile in plan.tiles():
            buf = plan.buffers[tile.buffer]
            with obs_trace.span("kernel.place"):
                for g in range(G):
                    slot = grid.slot(g)
                    for j_local, j in enumerate(range(tile.k_start,
                                                      tile.k_end)):
                        wj = np.pad(w[g, j], (0, pad)).reshape(nb, lanes)
                        rows = buf.weight_rows(j_local, w_bits)
                        layout.place(slot, wj, rows.base, w_bits)
                        assert 0 <= int(x[g, j]) < (1 << x_bits)
                        layout.place(slot, np.full(lanes, int(x[g, j])),
                                     x_rows[j_local].base, x_bits)
            prog = _gemv_batched_chunk_program(plan, tile, x_rows,
                                               optimized=optimized)
            grid.run(prog)
            if obs_trace.enabled():
                costs.append((plan.load_cycles(tile), prog.cycles,
                              plan.unload_cycles(tile)))
        sp.set(cycles=grid.cycles)
    _KERNEL_CYCLES.inc(grid.cycles, kernel="gemv_batched",
                       mode="broadcast")
    if costs:
        # the broadcast chunk program is shared by every slot, so one
        # timeline stands in for all G lockstep pipelines
        schedule.Schedule(costs, name=f"gemv_k{k}").emit_trace(
            name=f"broadcast_g{G}/gemv_k{k}")
    if stats is not None:
        stats["cycles"] = grid.cycles
        stats["mode"] = "broadcast"
    return _extract_batched(grid, plan.acc.base, acc_bits, n)


def _comefa_gemv_per_slot(w: np.ndarray, x: np.ndarray, *, w_bits: int,
                          x_bits: int, acc_bits: int, optimized: bool,
                          mesh, recode: str, choices=None,
                          stats: Optional[Dict] = None,
                          engine=None) -> np.ndarray:
    """Per-slot-stream batched GEMV (`comefa_gemv_batched(recode=...)`).

    Same `schedule.plan_gemv` geometry as the single-instance kernel (no
    broadcast x rows needed - activations live in the instruction
    streams), one shared symbolic chunk template, per-slot digit-stream
    specialization, `run_per_slot` dispatch.  With ``choices`` (the
    [slot][tile] winners from `recode.select_wave`) each slot's chunk
    runs its own pre-selected digit schedule - mixed recodes across
    slots are legal because every grid slice has its own FSM.
    """
    G, k, n = w.shape
    reserve = recode == "auto" or ir_mod.recode_is_signed(recode)
    plan = schedule.cached_plan_gemv(k, n, w_bits, x_bits, acc_bits,
                                     reserve_neg=reserve)
    nb, lanes = plan.n_blocks, N_COLS
    pad = nb * lanes - n
    grid = ComefaGrid(G, n_blocks=nb, mesh=mesh, engine=engine)
    costs = [[] for _ in range(G)]
    with obs_trace.span("kernel.gemv_batched", slots=G, k=k, n=n,
                        mode="per_slot", recode=recode) as sp:
        for tile in plan.tiles():
            buf = plan.buffers[tile.buffer]
            with obs_trace.span("kernel.place"):
                for g in range(G):
                    slot = grid.slot(g)
                    for j_local, j in enumerate(range(tile.k_start,
                                                      tile.k_end)):
                        wj = np.pad(w[g, j], (0, pad)).reshape(nb, lanes)
                        rows = buf.weight_rows(j_local, w_bits)
                        layout.place(slot, wj, rows.base, w_bits)
            progs = [
                plan.tile_program(
                    tile, x[g, tile.k_start:tile.k_end],
                    optimized=optimized,
                    recode=(choices[g][tile.index].recode
                            if choices is not None else recode))
                for g in range(G)]
            grid.run_per_slot(progs)
            if obs_trace.enabled():
                for g in range(G):
                    costs[g].append((plan.load_cycles(tile),
                                     progs[g].cycles,
                                     plan.unload_cycles(tile)))
        sp.set(cycles=grid.cycles)
    _KERNEL_CYCLES.inc(grid.cycles, kernel="gemv_batched",
                       mode="per_slot")
    if obs_trace.enabled():
        # one model track per slot: Perfetto shows the G digit-stream
        # pipelines side by side, makespan = the slowest slot's timeline
        for g in range(G):
            schedule.Schedule(costs[g], name=f"gemv_k{k}").emit_trace(
                track=g, name=f"slot{g}/gemv_k{k}")
    if stats is not None:
        stats["cycles"] = grid.cycles
        stats["mode"] = "per_slot"
    return _extract_batched(grid, plan.acc.base, acc_bits, n)
