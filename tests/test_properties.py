"""Property-based tests (hypothesis) on system invariants."""
import numpy as np

try:
    from hypothesis import example, given, settings, strategies as st
except ImportError:
    # no hypothesis in this environment (the container image has no pip):
    # fall back to the deterministic seeded sampler so this module RUNS
    # instead of perpetually skipping (see tests/_minihyp.py)
    from _minihyp import example, given, settings, strategies as st

import jax.numpy as jnp

from repro.core.comefa import ComefaArray, N_COLS, isa, layout, program, \
    timing
from repro.kernels import ref
from repro.quant import bitplane as bp


# ---------------------------------------------------------------------------
# CoMeFa simulator invariants
# ---------------------------------------------------------------------------

@given(n=st.integers(2, 12), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=12, deadline=None)
def test_add_commutes(n, seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 1 << n, size=N_COLS)
    b = rng.integers(0, 1 << n, size=N_COLS)

    def run(x, y):
        arr = ComefaArray()
        layout.place(arr, x, 0, n)
        layout.place(arr, y, n, n)
        arr.run(program.add(list(range(n)), list(range(n, 2 * n)),
                            list(range(2 * n, 3 * n + 1))))
        return layout.extract(arr, 2 * n, n + 1, block=0)

    np.testing.assert_array_equal(run(a, b), run(b, a))


@given(n=st.integers(2, 8), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_mul_identity_and_zero(n, seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 1 << n, size=N_COLS)
    for other, expect in ((np.ones(N_COLS, np.int64), a),
                          (np.zeros(N_COLS, np.int64), np.zeros_like(a))):
        arr = ComefaArray()
        layout.place(arr, a, 0, n)
        layout.place(arr, other, n, n)
        arr.run(program.mul(list(range(n)), list(range(n, 2 * n)),
                            list(range(2 * n, 4 * n))))
        got = layout.extract(arr, 2 * n, 2 * n, block=0)
        np.testing.assert_array_equal(got, expect)


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_shift_left_then_right_loses_only_edges(seed):
    rng = np.random.default_rng(seed)
    n = 6
    a = rng.integers(0, 1 << n, size=N_COLS)
    arr = ComefaArray()
    layout.place(arr, a, 0, n)
    arr.run(program.shift_lanes(list(range(n)), list(range(n, 2 * n)),
                                left=True))
    arr.run(program.shift_lanes(list(range(n, 2 * n)),
                                list(range(2 * n, 3 * n)), left=False))
    got = layout.extract(arr, 2 * n, n, block=0)
    np.testing.assert_array_equal(got[1:-1], a[1:-1])
    assert got[0] == 0                       # edge lane zero-filled


@given(width=st.integers(2, 3), n_blocks=st.sampled_from([1, 2]),
       seed=st.integers(0, 2**31 - 1))
@settings(max_examples=6, deadline=None)
@example(width=2, n_blocks=1, seed=0)         # the degenerate chain
@example(width=3, n_blocks=2, seed=1)         # cross-block hops
def test_chained_reduce_tree_matches_numpy(width, n_blocks, seed):
    """Chained scalar reduction over multi-block operands is bit-identical
    to the numpy sum for random shapes/precisions, n_blocks=1 included."""
    rng = np.random.default_rng(seed)
    n = n_blocks * N_COLS
    vals = rng.integers(0, 1 << width, size=n)
    steps, chain_steps = program.full_reduce_steps(n_blocks)
    total = steps + chain_steps
    arr = ComefaArray(n_blocks=n_blocks, chain=True)
    layout.plan_chain(n).place(arr, vals, 0, width)
    val = list(range(width + total))
    scratch = list(range(width + total, 2 * (width + total) - 1))
    cyc = arr.run(program.reduce_to_scalar(val, scratch, width,
                                           n_blocks=n_blocks))
    assert cyc == timing.chained_reduction_cycles(width, n_blocks=n_blocks)
    got = int(layout.extract(arr, 0, width + total, block=0)[0])
    assert got == int(vals.sum())


@given(width=st.sampled_from([4, 8, 12]), used=st.integers(1, N_COLS),
       seed=st.integers(0, 2**31 - 1))
@settings(max_examples=8, deadline=None)
def test_reduce_to_scalar_matches_ref_on_partial_rows(width, used, seed):
    """A scalar reduction over the first `used` lanes (the rest zero)
    equals the reference sum of those values, at wide precisions."""
    rng = np.random.default_rng(seed)
    vals = rng.integers(0, 1 << width, size=used)
    steps, _ = program.full_reduce_steps(1)
    arr = ComefaArray()
    layout.place(arr, vals, 0, width)
    val = list(range(width + steps))
    scratch = list(range(width + steps, 2 * (width + steps) - 1))
    arr.run(program.reduce_to_scalar(val, scratch, width))
    got = int(layout.extract(arr, 0, width + steps, block=0)[0])
    assert got == ref.bitserial_reduce_ref(vals)


@given(n=st.integers(2, 10))
@settings(max_examples=9, deadline=None)
def test_cycle_formulas_monotone(n):
    assert timing.mul_cycles(n + 1) > timing.mul_cycles(n)
    assert timing.add_cycles(n + 1) > timing.add_cycles(n)
    assert timing.fp_mul_cycles(5, n + 1) > timing.fp_mul_cycles(5, n)


@given(words=st.lists(st.integers(0, (1 << 40) - 1), min_size=1,
                      max_size=10))
@settings(max_examples=25, deadline=None)
def test_instruction_decode_encode_identity(words):
    for w in words:
        # mask off reserved bits which encode() never sets
        w &= (1 << 38) - 1
        assert isa.Instr.decode(w).encode() == w


# ---------------------------------------------------------------------------
# quantization invariants
# ---------------------------------------------------------------------------

@given(bits=st.integers(2, 8), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_quantize_scale_invariance(bits, seed):
    """quantize(c*w) has scale c*s and identical integer codes."""
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
    q1, s1 = bp.quantize(w, bits, axis=0)
    q2, s2 = bp.quantize(w * 4.0, bits, axis=0)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s2), 4.0 * np.asarray(s1),
                               rtol=1e-6)


@given(bits=st.integers(2, 8), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_dequantize_error_bound(bits, seed):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(64, 4)), jnp.float32)
    q, s = bp.quantize(w, bits, axis=0)
    err = jnp.abs(bp.dequantize(q, s) - w)
    # error <= scale/2 per element (round-to-nearest)
    assert float((err - 0.5 * s - 1e-6).max()) <= 0.0


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_packed_linear_linearity(seed):
    """The packed projection is linear in x: f(a*x1 + x2) = a*f(x1) + f(x2)."""
    from repro import configs
    from repro.models import common
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(128, 128)), jnp.float32)
    packed, scale = bp.quantize_pack(w, 4, axis=0)
    x1 = jnp.asarray(rng.normal(size=(8, 128)), jnp.float32)
    x2 = jnp.asarray(rng.normal(size=(8, 128)), jnp.float32)
    def f(x):
        return common.linear({"packed": packed, "scale": scale}, x,
                             configs.get("smollm-360m"))
    lhs = f(2.0 * x1 + x2)
    rhs = 2.0 * f(x1) + f(x2)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                               rtol=1e-4, atol=1e-3)


@given(e=st.integers(2, 6), m=st.integers(1, 10),
       seed=st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_float_quantize_idempotent(e, m, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(256,)) * 4, jnp.float32)
    q1 = bp.quantize_float(x, e, m)
    q2 = bp.quantize_float(q1, e, m)
    np.testing.assert_allclose(np.asarray(q1), np.asarray(q2), rtol=1e-6)


# ---------------------------------------------------------------------------
# data pipeline invariants
# ---------------------------------------------------------------------------

@given(step=st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_any_step_reproducible(step):
    from repro.data.pipeline import DataConfig, SyntheticLM
    cfg = DataConfig(vocab=64, global_batch=2, seq_len=16, seed=1)
    a = SyntheticLM(cfg).batch_at(step)
    b = SyntheticLM(cfg).batch_at(step)
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))
