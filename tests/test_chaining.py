"""Multi-block chained-reduction programs (paper Sec. III-F / IV-C).

Covers the chaining subsystem end-to-end: the block-aware placement
planner (`layout.plan_chain`), chained-shift generators
(`program.reduce_to_scalar` / `program.fir`), the closed-form cycle
models (`timing.chained_reduction_cycles` / `timing.fir_cycles`), the
sim-backed `comefa_dot` / `comefa_fir` kernels, and the achieved-count
wiring into `fpga_model/perf.py`.  Bit-exactness is asserted across
n_blocks in {1, 2, 4} with chain=True (n_blocks=1 is the degenerate
chain).
"""
import numpy as np
import pytest

from repro.core.comefa import (ComefaArray, N_COLS, layout, plan_chain,
                               program, timing)
from repro.core.comefa.ir import RowAllocator
from repro.kernels import comefa_sim, ref

RNG = np.random.default_rng(42)


def fir_ref(taps: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Causal FIR with zero initial state: y[t] = sum_j h[j] x[t-j]."""
    k = len(taps)
    return np.array([
        sum(int(taps[j]) * int(x[t - j]) for j in range(min(k, t + 1)))
        for t in range(len(x))], dtype=np.int64)


# ---------------------------------------------------------------------------
# placement planner
# ---------------------------------------------------------------------------

def test_plan_chain_block_count_and_limit():
    assert plan_chain(1).n_blocks == 1
    assert plan_chain(160).n_blocks == 1
    assert plan_chain(161).n_blocks == 2
    assert plan_chain(640).n_blocks == 4
    with pytest.raises(ValueError):
        plan_chain(161, max_blocks=1)


def test_plan_chain_linear_lane_mapping_is_flat_identity():
    plan = plan_chain(400)
    g = plan.lanes()
    np.testing.assert_array_equal(g, np.arange(400))


def test_plan_chain_port_order_matches_load_transposed_phases():
    """Phase-correct mapping: element e -> lane COL_MUX*(e%40) + e//40."""
    plan = plan_chain(320, order="port")
    g = plan.lanes()
    for j in (0, 39, 40, 159):                  # block 0 spot checks
        assert g[j] == layout.lane_of(j)
    for j in (160, 200, 319):                   # block 1: same phase map
        assert g[j] == N_COLS + layout.lane_of(j - 160)


@pytest.mark.parametrize("order", ["linear", "port"])
@pytest.mark.parametrize("n", [1, 39, 100, 161, 333, 479])
def test_plan_place_extract_roundtrip(order, n):
    """Round trips on both lane orders, including ragged shapes: n not a
    multiple of 160 (39, 161, 333, 479) and n below one block (1, 39)."""
    plan = plan_chain(n, order=order)
    assert plan.n_blocks == -(-n // N_COLS)
    vals = RNG.integers(0, 256, size=n)
    arr = ComefaArray(n_blocks=plan.n_blocks, chain=True)
    plan.place(arr, vals, 4, 8)
    np.testing.assert_array_equal(plan.extract(arr, 4, 8), vals)


@pytest.mark.parametrize("order", ["linear", "port"])
@pytest.mark.parametrize("n", [1, 39, 161, 479])
def test_plan_chain_ragged_lanes_in_bounds_and_unique(order, n):
    """Ragged plans keep every element on a distinct in-range global lane
    (a duplicate or out-of-range lane would silently alias elements)."""
    plan = plan_chain(n, order=order)
    g = plan.lanes()
    assert g.shape == (n,)
    assert g.min() >= 0 and g.max() < plan.total_lanes
    assert len(np.unique(g)) == n


def test_plan_chain_ragged_place_leaves_other_lanes_untouched():
    """Placing a ragged operand must not clobber lanes past n_elems."""
    n = 161
    plan = plan_chain(n)
    arr = ComefaArray(n_blocks=plan.n_blocks, chain=True)
    sentinel = np.ones((plan.n_blocks, N_COLS), dtype=np.int64)
    layout.place(arr, sentinel, 20, 1)            # mark every lane
    plan.place(arr, np.zeros(n, dtype=np.int64), 20, 1)
    got = layout.extract(arr, 20, 1).reshape(-1)
    assert not got[:n].any()                      # placed lanes cleared
    assert got[n:].all()                          # the rest untouched


# ---------------------------------------------------------------------------
# chained tree reduction: bit-exact + exact closed-form cycles
# ---------------------------------------------------------------------------

def test_full_reduce_steps_split():
    assert program.full_reduce_steps(1) == (8, 0)     # degenerate chain
    assert program.full_reduce_steps(2) == (8, 1)
    assert program.full_reduce_steps(4) == (8, 2)


@pytest.mark.parametrize("n_blocks,bits", [(1, 4), (2, 3), (4, 2), (4, 4),
                                           (1, 8), (2, 16)])
def test_reduce_to_scalar_bit_exact_and_cycles(n_blocks, bits):
    steps, chain_steps = program.full_reduce_steps(n_blocks)
    total_steps = steps + chain_steps
    n = n_blocks * N_COLS
    vals = RNG.integers(0, 1 << bits, size=n)
    plan = plan_chain(n)
    arr = ComefaArray(n_blocks=n_blocks, chain=True)
    val = list(range(bits + total_steps))
    scratch = list(range(bits + total_steps, 2 * (bits + total_steps) - 1))
    plan.place(arr, vals, 0, bits)
    cyc = arr.run(program.reduce_to_scalar(val, scratch, bits,
                                           n_blocks=n_blocks))
    assert cyc == timing.chained_reduction_cycles(bits, n_blocks=n_blocks)
    got = int(layout.extract(arr, 0, bits + total_steps, block=0)[0])
    assert got == ref.bitserial_reduce_ref(vals)


def test_chained_groups_straddle_block_seams():
    """A 2^6-lane group crossing lanes 128..191 sums across the seam."""
    nb, bits, S = 2, 2, 6
    vals = RNG.integers(0, 1 << bits, size=nb * N_COLS)
    arr = ComefaArray(n_blocks=nb, chain=True)
    plan_chain(nb * N_COLS).place(arr, vals, 0, bits)
    val = list(range(bits + S))
    scratch = list(range(bits + S, 2 * (bits + S) - 1))
    arr.run(program.reduce_tree(val, scratch, bits, steps=S))
    got = layout.extract(arr, 0, bits + S).reshape(-1)
    # group heads at multiples of 64; group [128..191] spans both blocks
    heads = np.arange(0, nb * N_COLS, 1 << S)
    expect = vals.reshape(-1, 1 << S).sum(axis=1)
    np.testing.assert_array_equal(got[heads], expect)


def test_unchained_array_loses_cross_seam_partials():
    """Negative control: without chain=True the seam shifts in zeros."""
    nb, bits, S = 2, 2, 6
    vals = np.ones(nb * N_COLS, dtype=np.int64)
    arr = ComefaArray(n_blocks=nb, chain=False)
    plan_chain(nb * N_COLS).place(arr, vals, 0, bits)
    val = list(range(bits + S))
    scratch = list(range(bits + S, 2 * (bits + S) - 1))
    arr.run(program.reduce_tree(val, scratch, bits, steps=S))
    got = layout.extract(arr, 0, bits + S).reshape(-1)
    assert got[128] < 64        # straddling group came up short
    assert got[0] == 64         # in-block group unaffected


@pytest.mark.parametrize("n_blocks", [1, 2, 4])
def test_optimized_chained_reduction_is_bit_identical(n_blocks):
    """IR pass pipeline preserves chained-program semantics."""
    bits = 2
    steps, chain_steps = program.full_reduce_steps(n_blocks)
    S = steps + chain_steps
    vals = RNG.integers(0, 1 << bits, size=n_blocks * N_COLS)

    def run(opt):
        arr = ComefaArray(n_blocks=n_blocks, chain=True)
        plan_chain(n_blocks * N_COLS).place(arr, vals, 0, bits)
        val = list(range(bits + S))
        scratch = list(range(bits + S, 2 * (bits + S) - 1))
        p = program.reduce_to_scalar(val, scratch, bits, n_blocks=n_blocks)
        cyc = arr.run(p.optimize() if opt else p)
        return cyc, arr.mem.copy()

    c0, m0 = run(False)
    c1, m1 = run(True)
    assert c1 <= c0
    np.testing.assert_array_equal(m0, m1)


def test_achieved_chained_counts_never_exceed_closed_forms():
    for nb in (1, 2, 4):
        assert (timing.achieved_chained_reduction_cycles(8, nb)
                <= timing.chained_reduction_cycles(8, n_blocks=nb))
    assert (timing.achieved_fir_cycles(3, 8, 8, 20)
            <= timing.fir_cycles(3, 8, 20,
                                 x_values=[0b01010101] * 3))


# ---------------------------------------------------------------------------
# sim-backed kernels: comefa_dot (full reduction) and comefa_fir
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_blocks,n,bits", [(1, 150, 4), (2, 300, 4),
                                             (4, 640, 3)])
def test_comefa_dot_reduces_all_blocks_to_scalar(n_blocks, n, bits):
    a = RNG.integers(0, 1 << bits, size=n)
    b = RNG.integers(0, 1 << bits, size=n)
    assert plan_chain(n).n_blocks == n_blocks
    got = comefa_sim.comefa_dot(a, b, bits=bits)
    assert got == int((a.astype(np.int64) * b).sum())


def test_comefa_dot_unoptimized_cycles_match_closed_forms():
    bits, n = 3, 2 * N_COLS
    a = RNG.integers(0, 1 << bits, size=n)
    b = RNG.integers(0, 1 << bits, size=n)
    got = comefa_sim.comefa_dot(a, b, bits=bits, optimized=False)
    assert got == int((a.astype(np.int64) * b).sum())
    prog, _ = comefa_sim._PROGRAMS[("dot", bits, 2, False)]
    steps, chain_steps = program.full_reduce_steps(2)
    expect = (timing.mul_cycles(bits) + (steps + chain_steps)
              + timing.chained_reduction_cycles(2 * bits, n_blocks=2))
    assert prog.cycles == expect
    opt, _ = comefa_sim._PROGRAMS.get(("dot", bits, 2, True),
                                      (None, None))
    if opt is not None:
        assert opt.cycles <= expect


@pytest.mark.parametrize("n_blocks,n_taps", [(1, 96), (2, 290), (4, 520)])
def test_comefa_fir_bit_exact_across_blocks(n_blocks, n_taps):
    tb = xb = 3
    taps = RNG.integers(0, 1 << tb, size=n_taps)
    x = RNG.integers(0, 1 << xb, size=6)
    assert plan_chain(n_taps).n_blocks == n_blocks
    got = comefa_sim.comefa_fir(taps, x, tap_bits=tb, x_bits=xb)
    np.testing.assert_array_equal(got, fir_ref(taps, x))


def test_comefa_fir_unoptimized_cycles_equal_fir_cycles():
    tb, xb, K, T = 3, 4, 200, 5
    taps = RNG.integers(0, 1 << tb, size=K)
    x = RNG.integers(0, 1 << xb, size=T)
    acc_bits = tb + xb + 8
    # re-run the kernel's exact schedule on a counting array
    alloc = RowAllocator()
    tap_rows = alloc.alloc(tb)
    acc = alloc.alloc(acc_bits)
    plan = plan_chain(K)
    arr = ComefaArray(n_blocks=plan.n_blocks, chain=True)
    plan.place(arr, taps, tap_rows.base, tb)
    arr.run(program.zero_rows(acc))
    y = []
    for x_t in x:
        arr.run(program.fir_sample(tap_rows, acc, int(x_t), xb,
                                   shift=False))
        y.append(int(layout.extract(arr, acc.base, acc_bits, block=0)[0]))
        arr.run(program.shift_lanes(acc, acc, left=True))
    assert arr.cycles == timing.fir_cycles(T, xb, acc_bits, x_values=x)
    np.testing.assert_array_equal(np.array(y), fir_ref(taps, x))
    # the full generator emits the identical schedule
    full = program.fir(tap_rows, acc, [int(v) for v in x], xb)
    assert full.cycles == arr.cycles
    assert full.optimize().cycles <= full.cycles


def test_fir_cache_fifo_eviction_bound_and_correctness(monkeypatch):
    """Overflow the per-sample program cache: the FIFO eviction must keep
    the size bounded AND evicted entries must rebuild correctly when
    their sample value recurs later in the stream."""
    monkeypatch.setattr(comefa_sim, "_FIR_CACHE", {})
    monkeypatch.setattr(comefa_sim, "_FIR_CACHE_MAX", 4)
    tb = xb = 3
    taps = RNG.integers(0, 1 << tb, size=8)
    # 7 distinct sample values + the init/shift entries >> 4 slots; the
    # tail revisits 1, 2, 3 after they were evicted
    x = np.array([1, 2, 3, 4, 5, 6, 7, 1, 2, 3], dtype=np.int64)
    got = comefa_sim.comefa_fir(taps, x, tap_bits=tb, x_bits=xb)
    np.testing.assert_array_equal(got, fir_ref(taps, x))
    assert 0 < len(comefa_sim._FIR_CACHE) <= 4


def test_fir_cache_eviction_is_fifo_order(monkeypatch):
    monkeypatch.setattr(comefa_sim, "_FIR_CACHE", {})
    monkeypatch.setattr(comefa_sim, "_FIR_CACHE_MAX", 3)
    tb = xb = 2
    taps = RNG.integers(0, 1 << tb, size=4)
    comefa_sim.comefa_fir(taps, np.array([1, 2, 3]), tap_bits=tb, x_bits=xb)
    keys = list(comefa_sim._FIR_CACHE)
    # insertion order was init, shift, 1, 2, 3: the oldest two evicted
    tails = [k[4] for k in keys]
    assert "init" not in tails and "shift" not in tails
    assert tails == [1, 2, 3]


def test_fir_cycles_average_density_estimate_is_close():
    xs = [0b0101, 0b1010, 0b0110, 0b1001]
    exact = timing.fir_cycles(len(xs), 4, 12, x_values=xs)
    est = timing.fir_cycles(len(xs), 4, 12)
    assert abs(est - exact) / exact < 0.1


# ---------------------------------------------------------------------------
# perf wiring: FIR priced from the scheduled multi-block program
# ---------------------------------------------------------------------------

def test_perf_fir_achieved_prices_from_scheduled_program():
    from repro.core.fpga_model import perf
    closed = perf.fir("comefa-d").speedup
    achieved = perf.fir("comefa-d", achieved=True).speedup
    assert achieved > 1.0                  # chaining still buys a speedup
    assert achieved != closed              # really priced differently
    # scheduled per-sample count: at most the closed form for the same
    # average-density stream, and well under the generic-MAC estimate
    per = timing.achieved_fir_cycles_per_sample(16, 16, 36)
    pattern = 0b0101010101010101
    exact = timing.fir_cycles(1, 16, 36, x_values=[pattern],
                              include_init=False)
    assert per <= exact <= timing.mac_cycles(16, 36)
    # CCB has no chaining: achieved pricing cannot conjure a speedup
    assert perf.fir("ccb", achieved=True).speedup == 1.0
