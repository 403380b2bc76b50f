"""Property suite pinning `ComefaGrid` to per-slot `ComefaArray` semantics.

The contract under test: slot g of a grid dispatch is bit-identical -
mem, carry, mask, AND cycle counts - to an independent `ComefaArray`
executing the same program on the same initial state, for *random*
programs (arbitrary legal field combinations, not just the curated
generators), across G in {1, 2, 8}, chained and unchained blocks, and
`run_programs` latch-reset boundaries.  Plus the encode-cache keying
regression (structurally equal programs on arrays that differ only in
`chain` must not share a compiled step) and the batched sweep kernels.
"""
import numpy as np
import pytest

try:
    from hypothesis import example, given, settings, strategies as st
except ImportError:
    # no hypothesis in this environment (the container image has no pip):
    # fall back to the deterministic seeded sampler (tests/_minihyp.py)
    from _minihyp import given, settings, strategies as st

from repro.core.comefa import (ComefaArray, ComefaGrid, N_COLS, block,
                               grid_mesh, ir, isa, layout, program)
from repro.core.comefa import grid as grid_mod
from repro.core.comefa.grid import grid_shardings
from repro.obs import metrics
from repro.core.comefa.isa import PRED_CARRY, ROW_ONES, ROW_ZEROS

SEEDS = st.integers(0, 2**31 - 1)


# ---------------------------------------------------------------------------
# random-program generation: arbitrary legal field combinations
# ---------------------------------------------------------------------------

def _random_instr(rng) -> isa.Instr:
    return isa.Instr(
        src1_row=int(rng.integers(0, isa.N_ROWS)),
        src2_row=int(rng.integers(0, isa.N_ROWS)),
        dst_row=int(rng.integers(0, isa.N_ROWS)),
        truth_table=int(rng.integers(0, 16)),
        pred_sel=int(rng.integers(0, 4)),
        w1_sel=int(rng.choice([isa.W1_S, isa.W1_DIN, isa.W1_RIGHT])),
        w2_sel=int(rng.choice([isa.W2_CARRY, isa.W2_DIN, isa.W2_LEFT])),
        wp1_en=int(rng.integers(0, 2)),
        wp2_en=int(rng.integers(0, 2)),
        c_en=int(rng.integers(0, 2)),
        c_rst=int(rng.integers(0, 2)),
        m_en=int(rng.integers(0, 2)),
        ext_bit=int(rng.integers(0, 2)),
        b_ext=int(rng.integers(0, 2)))


# fixed program lengths keep the number of distinct scan shapes (and so
# jit traces) small across examples
PROG_LEN = 16


def _random_program(rng, length: int = PROG_LEN):
    return [_random_instr(rng) for _ in range(length)]


def _randomize_state(arr: ComefaArray, rng) -> None:
    arr.mem[:] = rng.integers(0, 2, size=arr.mem.shape, dtype=np.uint8)
    arr.mem[:, ROW_ZEROS, :] = 0
    arr.mem[:, ROW_ONES, :] = 1
    arr.carry[:] = rng.integers(0, 2, size=arr.carry.shape, dtype=np.uint8)
    arr.mask[:] = rng.integers(0, 2, size=arr.mask.shape, dtype=np.uint8)


def _assert_slots_equal(grid: ComefaGrid, arrays) -> None:
    assert grid.g == len(arrays)
    for g, a in enumerate(arrays):
        np.testing.assert_array_equal(grid.mem[g], a.mem, err_msg=f"slot {g} mem")
        np.testing.assert_array_equal(grid.carry[g], a.carry,
                                      err_msg=f"slot {g} carry")
        np.testing.assert_array_equal(grid.mask[g], a.mask,
                                      err_msg=f"slot {g} mask")
        assert grid.cycles == a.cycles, f"slot {g} cycle count"


# ---------------------------------------------------------------------------
# the core bit-identity property
# ---------------------------------------------------------------------------

@given(g=st.sampled_from([1, 2, 8]), n_blocks=st.sampled_from([1, 2]),
       chain=st.booleans(), seed=SEEDS)
@settings(max_examples=10, deadline=None)
def test_grid_run_bit_identical_to_per_slot_arrays(g, n_blocks, chain, seed):
    rng = np.random.default_rng(seed)
    prog = _random_program(rng)
    arrays = [ComefaArray(n_blocks=n_blocks, chain=chain) for _ in range(g)]
    for a in arrays:
        _randomize_state(a, rng)
    grid = ComefaGrid.from_arrays(arrays)
    cyc = grid.run(prog)
    for a in arrays:
        assert a.run(prog) == cyc
    _assert_slots_equal(grid, arrays)


@given(g=st.sampled_from([1, 2, 8]), reset=st.booleans(), seed=SEEDS)
@settings(max_examples=8, deadline=None)
def test_grid_run_programs_matches_arrays_at_boundaries(g, reset, seed):
    """Batched dispatch with/without latch resets == per-slot batches."""
    rng = np.random.default_rng(seed)
    progs = [_random_program(rng, 8) for _ in range(3)]
    arrays = [ComefaArray(n_blocks=1) for _ in range(g)]
    for a in arrays:
        _randomize_state(a, rng)
    grid = ComefaGrid.from_arrays(arrays)
    counts = grid.run_programs(progs, reset_latches=reset)
    assert len(counts) == 3 and sum(counts) == grid.cycles
    for a in arrays:
        assert a.run_programs(progs, reset_latches=reset) == counts
    _assert_slots_equal(grid, arrays)


@given(g=st.sampled_from([2, 8]), seed=SEEDS)
@settings(max_examples=6, deadline=None)
def test_grid_chained_reduction_per_slot(g, seed):
    """A real chained multi-block program (corner-PE hops included) is
    bit-identical per slot - and actually correct - on the grid."""
    rng = np.random.default_rng(seed)
    width, n_blocks = 3, 2
    n = n_blocks * N_COLS
    steps, chain_steps = program.full_reduce_steps(n_blocks)
    total = steps + chain_steps
    val = list(range(width + total))
    scratch = list(range(width + total, 2 * (width + total) - 1))
    prog = program.reduce_to_scalar(val, scratch, width, n_blocks=n_blocks)

    vals = [rng.integers(0, 1 << width, size=n) for _ in range(g)]
    arrays = [ComefaArray(n_blocks=n_blocks, chain=True) for _ in range(g)]
    grid = ComefaGrid(g, n_blocks=n_blocks, chain=True)
    plan = layout.plan_chain(n)
    for i in range(g):
        plan.place(arrays[i], vals[i], 0, width)
        plan.place(grid.slot(i), vals[i], 0, width)
    cyc = grid.run(prog)
    for i in range(g):
        assert arrays[i].run(prog) == cyc
        got = int(layout.extract(grid.slot(i), 0, width + total, block=0)[0])
        assert got == int(vals[i].sum())
    _assert_slots_equal(grid, arrays)


def test_grid_run_programs_latch_reset_blocks_carry_leak():
    """Program 1 presets the carry; program 2 predicates a copy on it.
    With the default reset the copy must NOT retire; without, it must -
    on every slot."""
    for reset, expect in ((True, 0), (False, 1)):
        grid = ComefaGrid(3)
        for g in range(3):
            layout.place(grid.slot(g), np.ones(N_COLS, int), 0, 1)
        grid.run_programs(
            [program.preset_carry(),
             program.copy_rows([0], [1], pred_sel=PRED_CARRY)],
            reset_latches=reset)
        for g in range(3):
            got = layout.extract(grid.slot(g), 1, 1, block=0)
            np.testing.assert_array_equal(got, np.full(N_COLS, expect))


# ---------------------------------------------------------------------------
# encode-cache keying: structurally equal programs, different chain flags
# ---------------------------------------------------------------------------

def _seam_shift_result(kind: str, chain: bool) -> int:
    """Run the SAME (structurally equal) one-row left shift on a fresh
    2-block array/grid and report block 0's seam lane (159) afterwards.
    Only block 1 holds data, so a 1 appears at the seam iff the shift
    actually chained across blocks."""
    prog = program.shift_lanes([0], [1], left=True)
    if kind == "array":
        arr = ComefaArray(n_blocks=2, chain=chain)
        layout.place(arr, np.ones(N_COLS, int), 0, 1, block=1)
        arr.run(prog)
        return int(arr.mem[0, 1, N_COLS - 1])
    grid = ComefaGrid(2, n_blocks=2, chain=chain)
    layout.place(grid.slot(0), np.ones(N_COLS, int), 0, 1, block=1)
    grid.run(prog)
    return int(grid.mem[0, 0, 1, N_COLS - 1])


@pytest.mark.parametrize("kind", ["array", "grid"])
@pytest.mark.parametrize("first", [False, True])
def test_encode_cache_not_shared_across_chain_flags(kind, first):
    """Regression for a cross-`chain` cache collision.

    The encode cache keys on program *structure* only (correct: encoding
    is chain-independent), so the compiled step dispatched afterwards
    must be keyed on the `chain` flag as well - if it were shared, the
    second run below would reuse the first's seam behaviour.  Both warm
    orders are exercised."""
    assert _seam_shift_result(kind, chain=first) == int(first)
    assert _seam_shift_result(kind, chain=not first) == int(not first)


# ---------------------------------------------------------------------------
# sharded path + state plumbing
# ---------------------------------------------------------------------------

def test_sharded_grid_matches_unsharded():
    rng = np.random.default_rng(7)
    prog = program.mul(list(range(4)), list(range(4, 8)),
                       list(range(8, 16))).optimize()
    plain = ComefaGrid(3, n_blocks=2)
    shard = ComefaGrid(3, n_blocks=2, mesh=grid_mesh())
    vals = rng.integers(0, 16, size=(3, 2, N_COLS))
    for g in range(3):
        for grid in (plain, shard):
            layout.place(grid.slot(g), vals[g], 0, 4)
            layout.place(grid.slot(g), vals[g] ^ 5, 4, 4)
    assert plain.run(prog) == shard.run(prog)
    np.testing.assert_array_equal(plain.mem, shard.mem)
    np.testing.assert_array_equal(plain.carry, shard.carry)
    np.testing.assert_array_equal(plain.mask, shard.mask)


def test_grid_shardings_shapes_and_pruning():
    mesh = grid_mesh()
    s_mem, s_latch, s_prog = grid_shardings(mesh, g=3, n_blocks=2)
    # one host device: every spec must have pruned to (at most) trivial
    # sharding and the program is always fully replicated
    assert s_prog.spec == type(s_prog.spec)()
    assert len(s_mem.spec) <= 4 and len(s_latch.spec) <= 3


def test_from_to_arrays_roundtrip_and_slot_io():
    rng = np.random.default_rng(3)
    arrays = [ComefaArray(n_blocks=2, chain=True) for _ in range(2)]
    for a in arrays:
        _randomize_state(a, rng)
    grid = ComefaGrid.from_arrays(arrays)
    back = grid.to_arrays()
    for a, b in zip(arrays, back):
        np.testing.assert_array_equal(a.mem, b.mem)
        assert b.n_blocks == 2 and b.chain is True
    # hybrid-port words on a slot view mirror ComefaArray and count IO
    fresh = ComefaGrid(2, n_blocks=2)
    fresh.slot(1).write_word(0, 12, 0xABCDE)
    assert fresh.io_words == 1
    assert fresh.slot(1).read_word(0, 12) == 0xABCDE
    assert fresh.io_words == 2
    arr = ComefaArray(n_blocks=2)
    arr.write_word(0, 12, 0xABCDE)
    np.testing.assert_array_equal(fresh.mem[1][:, 3], arr.mem[:, 3])


def test_grid_accepts_legacy_encoded_matrix_and_empty_programs():
    """`encoded()` program forms all work on the grid: an `ir.Program`,
    a raw instruction list, a legacy [T, N_FIELDS] matrix (widened with
    dst2/pred2 engine columns), and the empty program (0 cycles)."""
    n = 4
    rows = (list(range(n)), list(range(n, 2 * n)),
            list(range(2 * n, 3 * n + 1)))
    prog = program.add(*rows)
    legacy = np.array([i.to_vector() for i in prog.instrs()],
                      dtype=np.int32)
    assert legacy.shape[1] == isa.N_FIELDS
    rng = np.random.default_rng(5)
    vals = rng.integers(0, 1 << n, size=(2, N_COLS))
    grid = ComefaGrid(2)
    arr = ComefaArray()
    for g in range(2):
        layout.place(grid.slot(g), vals[g], 0, n)
        layout.place(grid.slot(g), vals[g] ^ 9, n, n)
    layout.place(arr, vals[0], 0, n)
    layout.place(arr, vals[0] ^ 9, n, n)
    assert grid.run(legacy) == arr.run(legacy) == prog.cycles
    np.testing.assert_array_equal(grid.mem[0], arr.mem)
    got = layout.extract(grid.slot(1), 2 * n, n + 1, block=0)
    np.testing.assert_array_equal(got, vals[1] + (vals[1] ^ 9))
    # empty programs dispatch nothing and cost nothing
    before = grid.cycles
    assert grid.run(ir.Program()) == 0
    assert grid.run_programs([]) == []
    assert grid.cycles == before


# ---------------------------------------------------------------------------
# per-slot stack reuse
# ---------------------------------------------------------------------------

def _mac_progs(widths):
    """One ``p <- a * b; acc <- acc + p`` program per n-bit width."""
    return [(program.mul(list(range(n)), list(range(n, 2 * n)),
                         list(range(2 * n, 4 * n)))
             + program.add_into(list(range(4 * n, 6 * n)),
                                list(range(2 * n, 4 * n)), 0)).optimize()
            for n in widths]


def _slot_stack_events():
    c = metrics.counter("comefa.slot_stack")
    return c.value(event="build"), c.value(event="reuse")


def test_run_per_slot_reuses_the_frozen_stack_of_a_repeated_list():
    """The second dispatch of one program list reuses the first one's
    frozen stack and leaves every slot as fresh stacks and isolated
    arrays would."""
    grid_mod._SLOT_STACK_CACHE.clear()
    widths = (2, 3, 4)
    progs = _mac_progs(widths)
    rng = np.random.default_rng(11)
    grid = ComefaGrid(3, n_blocks=2, engine="packed-xla")
    ref = ComefaGrid(3, n_blocks=2, engine="reference")
    arrays = [ComefaArray(n_blocks=2) for _ in widths]
    for g, n in enumerate(widths):
        vals = rng.integers(0, 1 << n, size=(2, N_COLS))
        for tgt in (grid.slot(g), ref.slot(g), arrays[g]):
            layout.place(tgt, vals, 0, n)
            layout.place(tgt, vals ^ 1, n, n)
    build, reuse = _slot_stack_events()
    counts = grid.run_per_slot(progs)
    assert _slot_stack_events() == (build + 1, reuse)
    (mats, stack), = grid_mod._SLOT_STACK_CACHE.values()
    assert not stack.flags.writeable
    with pytest.raises(ValueError):
        stack[0, 0, 0] = 1
    assert grid.run_per_slot(progs) == counts
    assert _slot_stack_events() == (build + 1, reuse + 1)
    for _ in range(2):                 # a fresh stack for every dispatch
        grid_mod._SLOT_STACK_CACHE.clear()
        assert ref.run_per_slot(progs) == counts
    assert counts == [p.cycles for p in progs]
    for arr, p in zip(arrays, progs):
        arr.run(p)
        arr.run(p)
    assert grid.cycles == ref.cycles == 2 * max(counts)
    for g, arr in enumerate(arrays):
        for got in (grid, ref):
            np.testing.assert_array_equal(got.mem[g], arr.mem)
            np.testing.assert_array_equal(got.carry[g], arr.carry)
            np.testing.assert_array_equal(got.mask[g], arr.mask)


def test_run_per_slot_builds_a_new_stack_for_a_changed_list():
    """Another list, another order or one replaced program builds; an
    equal rebuild of the list reuses; eviction drops the device copy."""
    grid_mod._SLOT_STACK_CACHE.clear()
    progs = _mac_progs((2, 3, 4))
    grid = ComefaGrid(3, engine="reference")

    def builds(lst):
        before = _slot_stack_events()
        grid.run_per_slot(lst)
        after = _slot_stack_events()
        return after[0] - before[0], after[1] - before[1]

    assert builds(progs) == (1, 0)
    (_, first), = grid_mod._SLOT_STACK_CACHE.values()
    assert id(first) in block._DEVICE_MAT_CACHE
    swapped = list(progs)
    swapped[1] = _mac_progs((4,))[0]
    assert builds(swapped) == (1, 0)
    assert builds(progs[::-1]) == (1, 0)
    assert builds(_mac_progs((2, 3, 4))) == (0, 1)   # structurally equal
    # a writable matrix may change after the call: built every time and
    # never cached
    raw = [np.array(block.encoded(p)) for p in progs]
    cached = dict(grid_mod._SLOT_STACK_CACHE)
    assert builds(raw) == (1, 0) and builds(raw) == (1, 0)
    assert grid_mod._SLOT_STACK_CACHE == cached
    for n in range(2, 2 + grid_mod._SLOT_STACK_CACHE_MAX):
        assert builds(_mac_progs((n, n, n))) == (1, 0)
    assert len(grid_mod._SLOT_STACK_CACHE) == grid_mod._SLOT_STACK_CACHE_MAX
    assert id(first) not in block._DEVICE_MAT_CACHE


def test_grid_rejects_mismatched_arrays():
    with pytest.raises(AssertionError):
        ComefaGrid.from_arrays([ComefaArray(n_blocks=1),
                                ComefaArray(n_blocks=2)])
    with pytest.raises(AssertionError):
        ComefaGrid.from_arrays([ComefaArray(chain=True),
                                ComefaArray(chain=False)])


# ---------------------------------------------------------------------------
# batched sweep kernels: per-slot bit-exactness
# ---------------------------------------------------------------------------

@given(g=st.sampled_from([1, 3]), k=st.sampled_from([3, 5, 9]),
       bits=st.sampled_from([2, 3]), seed=SEEDS)
@settings(max_examples=5, deadline=None)
def test_comefa_gemm_batched_matches_numpy_per_slot(g, k, bits, seed):
    from repro.kernels import comefa_sim
    rng = np.random.default_rng(seed)
    m, n = int(rng.integers(1, 5)), int(rng.integers(1, 5))
    a = rng.integers(0, 1 << bits, size=(g, m, k))
    b = rng.integers(0, 1 << bits, size=(g, k, n))
    got = comefa_sim.comefa_gemm_batched(a, b, bits=bits, n_blocks=1)
    assert got.shape == (g, m, n)
    for i in range(g):
        np.testing.assert_array_equal(got[i], a[i] @ b[i])


# modelled broadcast cycles (value-independent) of shapes pinned to what the
# per-element placement loop gave: k 23 is three 7-element tiles and a short
# 2-element one, n 330 spans 3 blocks with 150 padding lanes
_PINNED_BCAST_CYCLES = {(23, 330): 2657}


@given(g=st.sampled_from([1, 4]), k=st.sampled_from([1, 5, 19]),
       n=st.sampled_from([1, 40, 200]), seed=SEEDS)
@example(g=4, k=23, n=330, seed=2214000001)
@settings(max_examples=5, deadline=None)
def test_comefa_gemv_batched_matches_numpy_per_slot(g, k, n, seed):
    from repro.kernels import comefa_sim
    rng = np.random.default_rng(seed)
    w_bits, x_bits, acc_bits = 4, 5, 24
    w = rng.integers(0, 1 << w_bits, size=(g, k, n))
    x = rng.integers(0, 1 << x_bits, size=(g, k))
    stats = {}
    got = comefa_sim.comefa_gemv_batched(w, x, w_bits=w_bits, x_bits=x_bits,
                                         acc_bits=acc_bits, stats=stats)
    assert got.shape == (g, n)
    quote = comefa_sim._broadcast_quote(k, n, w_bits, x_bits, acc_bits,
                                        optimized=True)
    assert stats == {"mode": "broadcast",
                     "cycles": sum(quote.compute_cycles)}
    if (k, n) in _PINNED_BCAST_CYCLES:
        assert stats["cycles"] == _PINNED_BCAST_CYCLES[k, n]
    for i in range(g):
        np.testing.assert_array_equal(got[i], w[i].T.astype(np.int64)
                                      @ x[i].astype(np.int64))


@pytest.mark.parametrize("x_bits,w_bits", [(4, 4), (8, 4), (2, 8)])
def test_comefa_gemv_batched_bit_widths(x_bits, w_bits):
    """Per-slot weights at several activation/weight widths, exact against
    the int64 product."""
    from repro.kernels import comefa_sim
    rng = np.random.default_rng(17)
    g, k, n = 2, 32, 128
    w = rng.integers(0, 1 << w_bits, size=(g, k, n))
    x = rng.integers(0, 1 << x_bits, size=(g, k))
    got = comefa_sim.comefa_gemv_batched(w, x, w_bits=w_bits, x_bits=x_bits,
                                         acc_bits=w_bits + x_bits + 5)
    np.testing.assert_array_equal(
        got, np.einsum("gkn,gk->gn", w.astype(np.int64), x.astype(np.int64)))


def test_comefa_gemv_batched_agrees_with_single_instance_kernel():
    """The grid sweep and G separate OOOR `comefa_gemv` calls disagree in
    *cycles* (the shared-FSM variant cannot zero-skip) but must agree
    bit-for-bit in results."""
    from repro.kernels import comefa_sim
    rng = np.random.default_rng(11)
    g, k, n, w_bits, x_bits = 3, 23, 170, 3, 4
    w = rng.integers(0, 1 << w_bits, size=(g, k, n))
    x = rng.integers(0, 1 << x_bits, size=(g, k))
    got = comefa_sim.comefa_gemv_batched(w, x, w_bits=w_bits, x_bits=x_bits,
                                         acc_bits=20)
    for i in range(g):
        ref = comefa_sim.comefa_gemv(w[i], x[i], w_bits=w_bits,
                                     x_bits=x_bits, acc_bits=20)
        np.testing.assert_array_equal(got[i], ref)


def test_fused_grid_dispatch_faster_than_loop_for_g8():
    """Acceptance: ONE fused grid dispatch beats a Python loop of 8
    per-array `ComefaArray.run` calls (8 dispatches + 8 host syncs).
    Measured margin is ~2.8x; best-of-3 timing with up to 3 measurement
    rounds keeps this robust against noisy-neighbour stalls on loaded
    CI machines."""
    import time
    n, g = 8, 8
    prog = program.mul(list(range(n)), list(range(n, 2 * n)),
                       list(range(2 * n, 4 * n))).optimize()
    arrays = [ComefaArray(n_blocks=2) for _ in range(g)]
    grid = ComefaGrid.from_arrays(arrays)
    for a in arrays:                       # warm both jit caches
        a.run(prog)
    grid.run(prog)

    def best_of(fn, reps=3):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    for _ in range(3):                     # re-measure rather than flake
        t_loop = best_of(lambda: [a.run(prog) for a in arrays])
        t_fused = best_of(lambda: grid.run(prog))
        if t_fused < t_loop:
            return
    assert t_fused < t_loop, (t_fused, t_loop)


def test_comefa_gemm_batched_agrees_with_single_instance_kernel():
    from repro.kernels import comefa_sim
    rng = np.random.default_rng(13)
    g, m, k, n, bits, nb = 2, 3, 40, 3, 2, 4
    a = rng.integers(0, 1 << bits, size=(g, m, k))
    b = rng.integers(0, 1 << bits, size=(g, k, n))
    got = comefa_sim.comefa_gemm_batched(a, b, bits=bits, n_blocks=nb)
    for i in range(g):
        ref = comefa_sim.comefa_gemm(a[i], b[i], bits=bits, n_blocks=nb)
        np.testing.assert_array_equal(got[i], ref)
