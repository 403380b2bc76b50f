"""Adaptive recode selection: exact pricing, argmin optimality, caches.

The tentpole contract under test: `core.comefa.recode` prices every
candidate digit schedule *exactly* (cycle-equal to the generated
unoptimized chunk programs, i.e. to the pinned
`timing.streamed_mac_cycles` expansion), so ``recode="auto"`` can never
model-cost more than the best fixed recode on the per-slot path - and
stays bit-exact against the int64 reference under every mixed selection.
Also covered: the vectorized digit-pattern closed forms vs
`ir.recode_digits`, the shape-keyed plan memoization, and the
digit-stream-keyed specialization cache.
"""
import numpy as np
import pytest

try:
    from hypothesis import example, given, settings, strategies as st
except ImportError:
    from _minihyp import example, given, settings, strategies as st

from repro.core.comefa import ir, schedule, timing
from repro.core.comefa import recode as rmod
from repro.kernels import comefa_sim
from repro.obs import metrics

SEEDS = st.integers(0, 2**31 - 1)
RECODES = ("naive", "booth", "naf")


# ---------------------------------------------------------------------------
# digit-pattern closed forms vs the reference recoders
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rc", RECODES)
@pytest.mark.parametrize("n", [1, 2, 3, 5, 8])
def test_digit_patterns_match_recode_digits(n, rc):
    """Exhaustive: the vectorized masks == ir.recode_digits, every value."""
    vals = np.arange(1 << n)
    nz, neg = timing.digit_patterns(vals, n, rc)
    for v in vals:
        digits = ir.recode_digits(int(v), n, rc)
        want_nz = sum(1 << i for i, d in enumerate(digits) if d != 0)
        want_neg = sum(1 << i for i, d in enumerate(digits) if d < 0)
        assert nz[v] == want_nz, (rc, n, v)
        assert neg[v] == want_neg, (rc, n, v)


def test_digit_patterns_rejects_unknown_recode():
    with pytest.raises(ValueError):
        timing.digit_patterns([1], 4, "radix4")


@pytest.mark.parametrize("rc", RECODES)
def test_nonzero_digit_count_scalar_matches_stream_length(rc):
    for v in range(1 << 6):
        digits = ir.recode_digits(v, 6, rc)
        want = sum(1 for d in digits if d != 0)
        assert timing.nonzero_digit_count(v, 6, rc) == want


# ---------------------------------------------------------------------------
# chunk pricing is cycle-exact against the generated programs
# ---------------------------------------------------------------------------

@given(seed=SEEDS, rc=st.sampled_from(list(RECODES)))
@settings(max_examples=20, deadline=None)
def test_chunk_stream_cycles_equals_generated_program(seed, rc):
    """Vectorized price == tile_program(optimized=False).cycles, per tile."""
    rng = np.random.default_rng(seed)
    k, n, wb, xb = int(rng.integers(3, 14)), 8, 4, 6
    acc = int(rng.integers(wb + xb + 2, 24))
    plan = schedule.plan_gemv(k, n, wb, xb, acc, reserve_neg=True)
    x = rng.integers(0, 1 << xb, size=k)
    for t in plan.tiles():
        chunk = [int(v) for v in x[t.k_start:t.k_end]]
        prog = plan.tile_program(t, chunk, optimized=False, recode=rc)
        want = rmod.chunk_stream_cycles(
            chunk, w_bits=wb, x_bits=xb, acc_bits=acc, recode=rc,
            zero_acc=t.index == 0)
        assert prog.cycles == want, (rc, t.index, chunk)


@given(seed=SEEDS, rc=st.sampled_from(list(RECODES)))
@settings(max_examples=20, deadline=None)
def test_chunk_stream_cycles_equals_mac_sum_with_truncation(seed, rc):
    """Price == sum of pinned streamed_mac_cycles, incl. the signed-mode
    accumulator-capacity truncation (acc_bits barely above w_bits)."""
    rng = np.random.default_rng(seed)
    wb, xb = 4, 6
    acc = int(rng.integers(wb, wb + xb + 3))   # forces truncation often
    vals = rng.integers(0, 1 << xb, size=int(rng.integers(1, 9)))
    want = sum(timing.streamed_mac_cycles(wb, acc, int(v), xb, rc)
               for v in vals)
    got = rmod.chunk_stream_cycles(vals, w_bits=wb, x_bits=xb,
                                   acc_bits=acc, recode=rc)
    assert got == want


# ---------------------------------------------------------------------------
# selection: argmin over exact prices, deterministic tie-breaks
# ---------------------------------------------------------------------------

def _tiny_plan(k=6, wb=4, xb=6, acc=20, reserve_neg=True):
    return schedule.plan_gemv(k, 8, wb, xb, acc, reserve_neg=reserve_neg)


def test_select_chunk_is_argmin():
    plan = _tiny_plan()
    tile = plan.tiles()[0]
    rng = np.random.default_rng(5)
    chunk = [int(v) for v in rng.integers(0, 1 << plan.x_bits,
                                          size=tile.n_elems)]
    best = rmod.select_chunk(chunk, plan, tile, record=False)
    prices = {rc: rmod.chunk_stream_cycles(
        chunk, w_bits=plan.w_bits, x_bits=plan.x_bits,
        acc_bits=plan.acc_bits, recode=rc, zero_acc=True)
        for rc in rmod.SIGNED_CANDIDATES}
    assert best.cycles == min(prices.values())
    assert prices[best.recode] == best.cycles


def test_select_chunk_prefers_naive_on_sparse_naf_on_dense():
    """Powers of two have one naive digit (naive wins); all-ones values
    are a carry run (NAF halves the stream; ties vs booth go to naf)."""
    plan = _tiny_plan()
    tile = plan.tiles()[0]
    sparse = [1 << (i % plan.x_bits) for i in range(tile.n_elems)]
    dense = [(1 << plan.x_bits) - 1] * tile.n_elems
    assert rmod.select_chunk(sparse, plan, tile, record=False).recode == \
        "naive"
    assert rmod.select_chunk(dense, plan, tile, record=False).recode == "naf"


def test_select_chunk_unsigned_plan_only_naive():
    plan = _tiny_plan(reserve_neg=False)
    assert rmod.candidates_for(plan) == ("naive",)
    tile = plan.tiles()[0]
    dense = [(1 << plan.x_bits) - 1] * tile.n_elems
    assert rmod.select_chunk(dense, plan, tile, record=False).recode == \
        "naive"


def test_select_chunk_records_counter():
    plan = _tiny_plan()
    tile = plan.tiles()[0]
    c = metrics.counter("comefa.recode_selected")
    before = c.value(choice="naive")
    rmod.select_chunk([1] * tile.n_elems, plan, tile)
    assert c.value(choice="naive") == before + 1


def test_select_wave_mixed_slots_and_makespan():
    """Slot recodes mix freely; the per-tile price is the max over slots."""
    plan = _tiny_plan(k=6)
    (tile,) = plan.tiles()
    sparse = [1 << (i % plan.x_bits) for i in range(plan.k)]
    dense = [(1 << plan.x_bits) - 1] * plan.k
    sel = rmod.select_wave(plan, np.array([sparse, dense]))
    assert sel.mode == "per_slot"
    assert sel.choices[0][0].recode == "naive"
    assert sel.choices[1][0].recode == "naf"
    want = schedule.Schedule(
        [(plan.load_cycles(tile),
          max(sel.choices[0][0].cycles, sel.choices[1][0].cycles),
          plan.unload_cycles(tile))]).total_cycles
    assert sel.per_slot_cycles == want


def test_select_wave_broadcast_wins_when_quoted_cheaper():
    plan = _tiny_plan(k=6)
    x = np.array([[(1 << plan.x_bits) - 1] * plan.k] * 2)
    honest = rmod.select_wave(plan, x)
    assert honest.broadcast_cycles is None        # no quote -> per_slot
    bplan = schedule.plan_gemv(plan.k, plan.n, plan.w_bits, plan.x_bits,
                               plan.acc_bits)
    cheap = rmod.BroadcastQuote(plan=bplan,
                                compute_cycles=(1,) * bplan.n_tiles)
    sel = rmod.select_wave(plan, x, broadcast=cheap)
    assert sel.mode == "broadcast"
    assert sel.broadcast_cycles == cheap.total_cycles
    assert sel.broadcast_cycles < sel.per_slot_cycles


# ---------------------------------------------------------------------------
# satellite: auto never model-costs more than the best fixed recode, and
# stays bit-exact under every mixed selection (property test)
# ---------------------------------------------------------------------------

@given(seed=SEEDS)
@settings(max_examples=8, deadline=None)
@example(seed=0)
def test_auto_cycles_le_best_fixed_and_bitexact(seed):
    """auto executed cycles <= min over fixed per-slot recodes (unoptimized,
    where the pricing is provably exact); results == int64 einsum.  When
    auto picks broadcast, its compute cycles equal the broadcast run's."""
    rng = np.random.default_rng(seed)
    g = int(rng.integers(1, 4))
    k = int(rng.integers(4, 20))
    n = int(rng.integers(1, 12))
    wb, xb = 4, 6
    acc = wb + xb + 5
    w = rng.integers(0, 1 << wb, size=(g, k, n))
    x = rng.integers(0, 1 << xb, size=(g, k))
    if rng.integers(2):                    # sparsify some slots
        x[0] = 1 << rng.integers(0, xb, size=k)
    ref = np.einsum("gkn,gk->gn", w, x)
    cycles = {}
    for rc in (None,) + RECODES + ("auto",):
        stats = {}
        out = comefa_sim.comefa_gemv_batched(
            w, x, w_bits=wb, x_bits=xb, acc_bits=acc, optimized=False,
            recode=rc, stats=stats)
        np.testing.assert_array_equal(out, ref, err_msg=str(rc))
        cycles[rc] = (stats["cycles"], stats["mode"])
    auto_cycles, auto_mode = cycles["auto"]
    if auto_mode == "broadcast":
        assert auto_cycles == cycles[None][0]
    else:
        assert auto_cycles <= min(cycles[rc][0] for rc in RECODES)
    # default pipeline (optimized=True) stays bit-exact too
    out = comefa_sim.comefa_gemv_batched(w, x, w_bits=wb, x_bits=xb,
                                         acc_bits=acc, recode="auto")
    np.testing.assert_array_equal(out, ref)


def test_auto_beats_every_fixed_recode_on_mixed_slots():
    """A naive-favouring slot + a NAF-favouring slot: the wave makespan is
    the max over slots, so any single global recode pays its losing
    slot's penalty - per-chunk auto takes each slot's cheapest schedule
    and executes strictly fewer cycles than ALL fixed choices."""
    rng = np.random.default_rng(11)
    k, n, wb, xb = 24, 8, 4, 6
    acc = wb + xb + 5
    g = 2
    w = rng.integers(0, 1 << wb, size=(g, k, n))
    x = np.empty((g, k), np.int64)
    # value 3 = 0b11: NAF/Booth match naive's two digits but pay the
    # per-value w_bits complement -> naive strictly wins, and this slot
    # is the signed modes' makespan bottleneck (dearer than slot 1's NAF)
    x[0] = 3
    x[1] = (1 << xb) - 1                               # carry run: naf wins
    ref = np.einsum("gkn,gk->gn", w, x)
    cycles = {}
    for rc in RECODES + ("auto",):
        stats = {}
        out = comefa_sim.comefa_gemv_batched(
            w, x, w_bits=wb, x_bits=xb, acc_bits=acc, optimized=False,
            recode=rc, stats=stats)
        np.testing.assert_array_equal(out, ref)
        cycles[rc] = stats["cycles"]
        if rc == "auto":
            assert stats["mode"] == "per_slot"
    assert cycles["auto"] < min(cycles[rc] for rc in RECODES), cycles


# ---------------------------------------------------------------------------
# satellite: shape-keyed plan memoization + digit-stream spec cache
# ---------------------------------------------------------------------------

def test_cached_plan_gemv_hits_and_misses():
    """Unique shape (counters reset per test, module cache persists):
    first call misses, repeat hits, different shape misses again."""
    c = metrics.counter("comefa.plan_cache")
    h0, m0 = c.value(event="hits"), c.value(event="misses")
    shape = dict(w_bits=3, x_bits=5, acc_bits=19)
    p1 = schedule.cached_plan_gemv(41, 7, **shape)
    p2 = schedule.cached_plan_gemv(41, 7, **shape)
    assert p1 is p2
    schedule.cached_plan_gemv(43, 7, **shape)
    assert c.value(event="misses") == m0 + 2
    assert c.value(event="hits") == h0 + 1
    # same args as plan_gemv, same plan geometry
    q = schedule.plan_gemv(41, 7, **shape)
    assert (p1.k, p1.n, p1.k_tile, p1.n_tiles) == (q.k, q.n, q.k_tile,
                                                   q.n_tiles)


def test_spec_cache_keys_on_digit_stream():
    """Same (shape, recode, values) -> cached program object; a different
    recode or chunk re-specializes.  Unique shape keeps it deterministic
    across test orderings."""
    c = metrics.counter("comefa.spec_cache")
    h0, m0 = c.value(event="hits"), c.value(event="misses")
    plan = schedule.plan_gemv(5, 3, 3, 7, 21, reserve_neg=True)
    tile = plan.tiles()[0]
    chunk = [3, 0, 99, 1, 64]
    p1 = plan.tile_program(tile, chunk, recode="booth")
    p2 = plan.tile_program(tile, chunk, recode="booth")
    assert p1 is p2
    p3 = plan.tile_program(tile, chunk, recode="naf")
    p4 = plan.tile_program(tile, list(reversed(chunk)), recode="booth")
    assert p3 is not p1 and p4 is not p1
    assert c.value(event="misses") == m0 + 3
    assert c.value(event="hits") == h0 + 1
    # optimization ran under the cache: cached object is the "+opt" form
    assert p1.name == "gemv_chunk0@booth+opt"
    assert p1.cycles <= plan.tile_program(tile, chunk, optimized=False,
                                          recode="booth").cycles


def test_spec_cache_callable_recoder_bypasses_cache():
    """Custom recoder callables can't be keyed - they must not poison the
    cache, and must still specialize correctly every call."""
    plan = schedule.plan_gemv(4, 3, 3, 5, 21, reserve_neg=True)
    tile = plan.tiles()[0]

    def naf_like(v, b):
        return ir.recode_digits(v, b, "naf")

    chunk = [2, 9, 0, 30]
    p1 = plan.tile_program(tile, chunk, recode=naf_like)
    p2 = plan.tile_program(tile, chunk, recode=naf_like)
    assert p1 is not p2
    assert p1.cycles == plan.tile_program(tile, chunk, recode="naf").cycles
