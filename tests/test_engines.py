"""Engine-equivalence suite: packed/Pallas engines vs the uint8 reference.

The contract: every execution engine (`block.get_engine`) is bit-identical
to the reference uint8 scan - mem, carry, mask, and cycle accounting - for
*random* instruction streams (arbitrary legal field combinations, every
W1/W2 select, predication reading stale latches), across chained and
unchained multi-block arrays, `run_programs` latch-reset boundaries both
ways, and per-slot grid dispatch.  Plus the device-residency regressions:
a `run(); run()` pair performs no intermediate host copy, and repeated
dispatches of one cached program re-hit the device-side program matrix.
"""
import numpy as np
import pytest

try:
    from hypothesis import example, given, settings, strategies as st
except ImportError:
    # no hypothesis in this environment (the container image has no pip):
    # fall back to the deterministic seeded sampler (tests/_minihyp.py)
    from _minihyp import example, given, settings, strategies as st

from repro.core.comefa import (ComefaArray, ComefaGrid, engine_packed,
                               get_engine, isa)
from repro.core.comefa import block
from repro.core.comefa.isa import ROW_ONES, ROW_ZEROS

SEEDS = st.integers(0, 2**31 - 1)

# both packed engines run everywhere (pallas in interpret mode on CPU);
# the pallas leg uses fewer examples - interpret mode emulates the kernel
PACKED = ["packed-xla", "pallas"]


def _random_instr(rng) -> isa.Instr:
    return isa.Instr(
        src1_row=int(rng.integers(0, isa.N_ROWS)),
        src2_row=int(rng.integers(0, isa.N_ROWS)),
        dst_row=int(rng.integers(0, isa.N_ROWS)),
        truth_table=int(rng.integers(0, 16)),
        pred_sel=int(rng.integers(0, 4)),
        w1_sel=int(rng.choice([isa.W1_S, isa.W1_DIN, isa.W1_RIGHT])),
        w2_sel=int(rng.choice([isa.W2_CARRY, isa.W2_DIN, isa.W2_LEFT,
                               isa.W2_ZERO])),
        wp1_en=int(rng.integers(0, 2)),
        wp2_en=int(rng.integers(0, 2)),
        c_en=int(rng.integers(0, 2)),
        c_rst=int(rng.integers(0, 2)),
        m_en=int(rng.integers(0, 2)),
        ext_bit=int(rng.integers(0, 2)),
        b_ext=int(rng.integers(0, 2)))


PROG_LEN = 16    # fixed length bounds distinct scan shapes (jit retraces)


def _random_program(rng, length: int = PROG_LEN):
    return [_random_instr(rng) for _ in range(length)]


def _randomize_state(arr: ComefaArray, rng) -> None:
    arr.mem[:] = rng.integers(0, 2, size=arr.mem.shape, dtype=np.uint8)
    arr.mem[:, ROW_ZEROS, :] = 0
    arr.mem[:, ROW_ONES, :] = 1
    arr.carry[:] = rng.integers(0, 2, size=arr.carry.shape, dtype=np.uint8)
    arr.mask[:] = rng.integers(0, 2, size=arr.mask.shape, dtype=np.uint8)


def _clone(arr: ComefaArray, engine) -> ComefaArray:
    other = ComefaArray(n_blocks=arr.n_blocks, chain=arr.chain,
                        engine=engine)
    other.mem = arr.mem.copy()
    other.carry = arr.carry.copy()
    other.mask = arr.mask.copy()
    return other


def _assert_state_equal(a: ComefaArray, b: ComefaArray, label: str) -> None:
    np.testing.assert_array_equal(a.mem, b.mem, err_msg=f"{label} mem")
    np.testing.assert_array_equal(a.carry, b.carry, err_msg=f"{label} carry")
    np.testing.assert_array_equal(a.mask, b.mask, err_msg=f"{label} mask")
    assert a.cycles == b.cycles, f"{label} cycles"


# ---------------------------------------------------------------------------
# packing layout
# ---------------------------------------------------------------------------

def test_pack_unpack_roundtrip_and_bit_mapping():
    rng = np.random.default_rng(0)
    bits = rng.integers(0, 2, size=(3, 7, isa.N_COLS), dtype=np.uint8)
    words = engine_packed.pack_bits(bits)
    assert words.shape == (3, 7, engine_packed.N_WORDS)
    assert words.dtype == np.uint32
    np.testing.assert_array_equal(engine_packed.unpack_bits(words), bits)
    # lane c lives in word c // 32, bit c % 32 (LSB first)
    one = np.zeros(isa.N_COLS, dtype=np.uint8)
    for lane in (0, 1, 31, 32, 95, 159):
        one[:] = 0
        one[lane] = 1
        w = engine_packed.pack_bits(one)
        assert w[lane // 32] == np.uint32(1) << (lane % 32), lane
        assert (w != 0).sum() == 1


# ---------------------------------------------------------------------------
# the core bit-identity property: random streams, every select, both
# chain modes, multi-block arrays
# ---------------------------------------------------------------------------

@given(engine=st.sampled_from(PACKED), n_blocks=st.sampled_from([1, 2]),
       chain=st.booleans(), seed=SEEDS)
@settings(max_examples=10, deadline=None)
@example(engine="pallas", n_blocks=1, chain=False, seed=0)
@example(engine="pallas", n_blocks=1, chain=True, seed=0)
def test_packed_engine_bit_identical_on_random_streams(
        engine, n_blocks, chain, seed):
    rng = np.random.default_rng(seed)
    prog = _random_program(rng)
    ref = ComefaArray(n_blocks=n_blocks, chain=chain)
    _randomize_state(ref, rng)
    alt = _clone(ref, engine)
    assert ref.run(prog) == alt.run(prog)
    _assert_state_equal(ref, alt, engine)


@given(engine=st.sampled_from(PACKED), reset=st.booleans(), seed=SEEDS)
@settings(max_examples=6, deadline=None)
@example(engine="pallas", reset=False, seed=0)
def test_run_programs_boundaries_match(engine, reset, seed):
    """Latch-clear boundaries (and deliberate latch threading) agree."""
    rng = np.random.default_rng(seed)
    progs = [_random_program(rng, 8) for _ in range(3)]
    ref = ComefaArray(n_blocks=2)
    _randomize_state(ref, rng)
    alt = _clone(ref, engine)
    counts = ref.run_programs(progs, reset_latches=reset)
    assert alt.run_programs(progs, reset_latches=reset) == counts
    _assert_state_equal(ref, alt, engine)


@given(seed=SEEDS)
@settings(max_examples=4, deadline=None)
def test_chain_shift_heavy_streams_match(seed):
    """Cross-word AND cross-block funnel-shift seams, shift-only streams."""
    rng = np.random.default_rng(seed)
    prog = [isa.Instr(src1_row=int(rng.integers(0, isa.N_ROWS)),
                      src2_row=int(rng.integers(0, isa.N_ROWS)),
                      dst_row=int(rng.integers(0, isa.N_ROWS)),
                      truth_table=int(rng.integers(0, 16)),
                      w1_sel=isa.W1_RIGHT, w2_sel=isa.W2_LEFT,
                      wp1_en=1, wp2_en=int(rng.integers(0, 2)),
                      c_en=1, m_en=1)
            for _ in range(PROG_LEN)]
    ref = ComefaArray(n_blocks=3, chain=True)
    _randomize_state(ref, rng)
    alt = _clone(ref, "packed-xla")
    ref.run(prog)
    alt.run(prog)
    _assert_state_equal(ref, alt, "chain shifts")


@pytest.mark.parametrize("engine", PACKED)
def test_predication_reads_stale_latches(engine):
    """Predication must see the *previous* cycle's latches, not this one's."""
    prog = [
        # cycle 1: clear both latches (all-zeros operands, CGEN(0,0)=0)
        isa.Instr(src1_row=ROW_ZEROS, src2_row=ROW_ZEROS,
                  truth_table=isa.TT_AND, c_en=1, c_rst=1, m_en=1),
        # cycle 2: the FIRST cycle to raise carry/mask (CGEN(1,1)=1) also
        # predicates a write on PRED_CARRY - it must read the STALE zero
        # latch from cycle 1, so the write may not land
        isa.Instr(src1_row=ROW_ONES, src2_row=ROW_ONES,
                  truth_table=isa.TT_AND, dst_row=0, wp1_en=1,
                  pred_sel=isa.PRED_CARRY, c_en=1, c_rst=1, m_en=1),
        # cycle 3: now the latched values are visibly 1
        isa.Instr(src1_row=ROW_ONES, src2_row=ROW_ONES,
                  truth_table=isa.TT_AND, dst_row=1, wp1_en=1,
                  pred_sel=isa.PRED_MASK, c_rst=1),
    ]
    ref = ComefaArray(n_blocks=1)
    alt = _clone(ref, engine)
    for arr in (ref, alt):
        arr.run(prog)
    _assert_state_equal(ref, alt, engine)
    # the semantics themselves, not just agreement: cycle 2 blocked on the
    # stale zero carry, cycle 3 passed on the fresh mask
    assert (ref.mem[:, 0, :] == 0).all()
    assert (ref.mem[:, 1, :] == 1).all()


@given(engine=st.sampled_from(PACKED), g=st.sampled_from([1, 4]),
       seed=SEEDS)
@settings(max_examples=4, deadline=None)
@example(engine="pallas", g=1, seed=0)
def test_grid_per_slot_dispatch_matches_reference(engine, g, seed):
    """`run_per_slot` (different stream per slot, padded stacks) agrees."""
    rng = np.random.default_rng(seed)
    progs = [_random_program(rng, int(rng.integers(4, 12)))
             for _ in range(g)]
    ref = ComefaGrid(g, n_blocks=2)
    ref.mem[:] = rng.integers(0, 2, size=ref.mem.shape, dtype=np.uint8)
    ref.mem[:, :, ROW_ZEROS, :] = 0
    ref.mem[:, :, ROW_ONES, :] = 1
    alt = ComefaGrid(g, n_blocks=2, engine=engine)
    alt.mem = ref.mem.copy()
    assert ref.run_per_slot(progs) == alt.run_per_slot(progs)
    np.testing.assert_array_equal(ref.mem, alt.mem)
    np.testing.assert_array_equal(ref.carry, alt.carry)
    np.testing.assert_array_equal(ref.mask, alt.mask)
    assert ref.cycles == alt.cycles


@given(seed=SEEDS)
@settings(max_examples=3, deadline=None)
def test_grid_shared_program_matches_reference(seed):
    rng = np.random.default_rng(seed)
    prog = _random_program(rng)
    ref = ComefaGrid(4, n_blocks=2, chain=True)
    ref.mem[:] = rng.integers(0, 2, size=ref.mem.shape, dtype=np.uint8)
    ref.mem[:, :, ROW_ZEROS, :] = 0
    ref.mem[:, :, ROW_ONES, :] = 1
    alt = ComefaGrid(4, n_blocks=2, chain=True, engine="packed-xla")
    alt.mem = ref.mem.copy()
    assert ref.run(prog) == alt.run(prog)
    np.testing.assert_array_equal(ref.mem, alt.mem)
    np.testing.assert_array_equal(ref.carry, alt.carry)
    np.testing.assert_array_equal(ref.mask, alt.mask)


# ---------------------------------------------------------------------------
# engine selection
# ---------------------------------------------------------------------------

def test_engine_selection_env_var(monkeypatch):
    monkeypatch.setenv("REPRO_COMEFA_ENGINE", "packed-xla")
    assert ComefaArray().engine.name == "packed"
    monkeypatch.delenv("REPRO_COMEFA_ENGINE")
    assert ComefaArray().engine.name == "reference"
    # explicit argument beats the env default
    monkeypatch.setenv("REPRO_COMEFA_ENGINE", "packed-xla")
    assert ComefaArray(engine="reference").engine.name == "reference"


def test_engine_registry():
    assert get_engine("reference") is block._REFERENCE_ENGINE
    assert isinstance(get_engine("packed-xla"),
                      engine_packed.PackedXlaEngine)
    assert isinstance(get_engine("pallas"), engine_packed.PallasEngine)
    # "packed" auto-selects; on CPU that is the XLA fallback
    assert get_engine("packed").name in ("packed", "pallas")
    with pytest.raises(ValueError):
        get_engine("warp-drive")
    # engine objects pass through, so arrays can share one
    eng = get_engine("packed-xla")
    assert get_engine(eng) is eng
    assert ComefaArray(engine=eng).engine is eng


def test_grid_engine_inherited_through_conversions():
    eng = get_engine("packed-xla")
    arrays = [ComefaArray(engine=eng) for _ in range(2)]
    grid = ComefaGrid.from_arrays(arrays)
    assert grid.engine is eng
    assert all(a.engine is eng for a in grid.to_arrays())


# ---------------------------------------------------------------------------
# device residency: no host round-trips between dispatches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["reference", "packed-xla"])
def test_back_to_back_runs_stay_on_device(engine):
    rng = np.random.default_rng(0)
    prog = _random_program(rng)
    arr = ComefaArray(n_blocks=2, engine=engine)
    _randomize_state(arr, rng)
    syncs0, puts0 = arr.host_syncs, arr.device_puts
    arr.run(prog)
    arr.run(prog)
    # one upload before the first run, zero host materializations between
    assert arr.device_puts == puts0 + 1
    assert arr.host_syncs == syncs0
    # first host access after the pair syncs exactly once...
    _ = arr.mem
    _ = arr.carry
    assert arr.host_syncs == syncs0 + 1
    # ...and the next dispatch re-uploads the (possibly mutated) state
    arr.run(prog)
    assert arr.device_puts == puts0 + 2


def test_device_resident_pair_equals_synced_pair():
    """Chaining device state is bit-identical to syncing between runs."""
    rng = np.random.default_rng(1)
    p1, p2 = _random_program(rng), _random_program(rng)
    a = ComefaArray(n_blocks=2)
    _randomize_state(a, rng)
    b = _clone(a, "reference")
    a.run(p1)
    a.run(p2)                  # stays device-resident between the two
    b.run(p1)
    _ = b.mem                  # force a host round-trip in the middle
    b.run(p2)
    _assert_state_equal(a, b, "device-resident pair")


def test_grid_back_to_back_runs_stay_on_device():
    rng = np.random.default_rng(2)
    prog = _random_program(rng)
    grid = ComefaGrid(4, n_blocks=2, engine="packed-xla")
    grid.run(prog)
    grid.run(prog)
    assert grid.device_puts == 1
    assert grid.host_syncs == 0
    _ = grid.mem
    assert grid.host_syncs == 1


# ---------------------------------------------------------------------------
# device-side program-matrix cache
# ---------------------------------------------------------------------------

def test_device_program_cache_hits_across_dispatches():
    block._ENCODE_CACHE.clear()
    block._DEVICE_MAT_CACHE.clear()
    block.ENCODE_CACHE_STATS.update(hits=0, misses=0,
                                    device_hits=0, device_misses=0)
    rng = np.random.default_rng(3)
    prog = _random_program(rng)
    arr = ComefaArray()
    arr.run(prog)
    assert block.ENCODE_CACHE_STATS["device_misses"] == 1
    assert block.ENCODE_CACHE_STATS["device_hits"] == 0
    arr.run(prog)                      # same program: device matrix re-hits
    other = ComefaArray(engine="packed-xla")
    other.run(prog)                    # other arrays/engines share it too
    assert block.ENCODE_CACHE_STATS["device_misses"] == 1
    assert block.ENCODE_CACHE_STATS["device_hits"] == 2


def test_device_program_cache_skips_writable_matrices():
    block._DEVICE_MAT_CACHE.clear()
    block.ENCODE_CACHE_STATS.update(device_hits=0, device_misses=0)
    mat = np.zeros((4, isa.N_ENGINE_FIELDS), dtype=np.int32)
    block.device_mat(mat)              # writable temp: uploads, never caches
    block.device_mat(mat)
    assert block.ENCODE_CACHE_STATS == {
        **block.ENCODE_CACHE_STATS, "device_hits": 0, "device_misses": 0}
    assert not block._DEVICE_MAT_CACHE
