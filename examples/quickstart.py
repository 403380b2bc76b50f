"""Quickstart: CoMeFa in 60 seconds, all three layers of the system.

  1. bit-level CoMeFa RAM simulator - run a SIMD multiply in a 20Kb block
  2. a packed projection - w4 bit-plane weights through `linear`
  3. a quantized model layer - the technique inside a transformer

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.comefa import ComefaArray, layout, program, timing
from repro.kernels import ref
from repro.quant import bitplane as bp


def demo_simulator():
    print("=== 1. CoMeFa RAM: 160-lane bit-serial multiply ===")
    arr = ComefaArray(n_blocks=1)
    rng = np.random.default_rng(0)
    n = 8
    a = rng.integers(0, 1 << n, size=160)
    b = rng.integers(0, 1 << n, size=160)
    # assemble through the program IR: allocator-managed operands, then
    # the optimizing pass pipeline (dual-port co-issue et al.)
    bld = program.ProgramBuilder("mul8")
    ra = bld.input(n, "a")
    rb = bld.input(n, "b")
    rp = bld.mul(ra, rb)
    prog = bld.build()                               # optimized Program
    layout.place(arr, a, base_row=ra.base, n_bits=n)  # transposed layout
    layout.place(arr, b, base_row=rb.base, n_bits=n)
    cycles = arr.run(prog)
    got = layout.extract(arr, rp.base, 2 * n, block=0)
    assert np.array_equal(got, a * b)
    print(f"  160 8-bit multiplies in {cycles} cycles "
          f"(paper formula n^2+3n-2 = {timing.mul_cycles(n)}; dual-port "
          f"co-issue packs {prog.n_instrs} instrs into {prog.cycles}) - "
          f"{cycles / 588e6 * 1e9:.0f} ns at CoMeFa-D's 588 MHz")


def demo_linear():
    print("=== 2. packed projection: w4 weights x f32 activations ===")
    from repro import configs
    from repro.models import common
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
    packed, scale = bp.quantize_pack(w, 4, axis=0)
    y4 = common.linear({"packed": packed, "scale": scale}, x,
                       configs.get("smollm-360m"))
    dense = x @ w
    rel = float(jnp.linalg.norm(y4 - dense) / jnp.linalg.norm(dense))
    print(f"  4-bit bit-plane projection vs dense: rel err {rel:.3f}; "
          f"weight bytes 4x smaller in HBM")
    y_ref = ref.bitplane_matmul_ref(x, packed, scale, bits=4)
    print(f"  packed linear == jnp oracle: "
          f"{bool(jnp.allclose(y4, y_ref, atol=1e-4))}")


def demo_model():
    print("=== 3. Quantized transformer (CoMeFa as a config flag) ===")
    from repro import configs
    from repro.models import common, lm
    cfg = common.reduced(configs.get("smollm-360m"), d_model=64, d_ff=128,
                         quant_bits=4)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab)
    logits, _ = lm.forward(params, tokens, cfg)
    n_packed = sum(1 for p in jax.tree.leaves(params)
                   if p.dtype == jnp.uint32)
    print(f"  smollm (reduced) with {n_packed} packed bit-plane weight "
          f"tensors -> logits {logits.shape}, finite: "
          f"{bool(jnp.isfinite(logits).all())}")


if __name__ == "__main__":
    demo_simulator()
    demo_linear()
    demo_model()
