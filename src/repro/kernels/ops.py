"""Public jit'd wrappers around the Pallas kernels.

Handles: interpret-mode selection (CPU backend -> interpret=True so the
kernel body runs under the Pallas interpreter; TPU -> compiled), input
padding to block multiples, and the quantize+pack convenience entry points
used by `quant.layers.QuantizedLinear`.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from ..quant import bitplane
from . import bit_transpose as _bt
from . import bitplane_matmul as _bpm
from . import bitserial_matmul as _bsm
from . import bitserial_reduce as _bsr
from . import bulk_bitwise as _bb


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def bitplane_matmul(x, w_packed, scale, *, bits, block_m=128, block_n=128,
                    block_k=256, interpret=None, out_dtype=jnp.float32):
    """Padded/dispatched `kernels.bitplane_matmul` (docs there).

    A K or N block that does not divide its dimension becomes the whole
    dimension, which Mosaic always accepts (e.g. K=960 in one block).
    The default ``block_k=256`` keeps the packed weight block at K/32 = 8
    sublanes, the uint32 tile height on a TPU.
    """
    if interpret is None:
        interpret = _interpret_default()
    m, k = x.shape
    n = w_packed.shape[2]
    bm = min(block_m, max(8, m))
    bk = block_k if k % block_k == 0 else k
    bn = block_n if n % block_n == 0 else n
    xp = _pad_to(x, 0, bm)
    yp = _bpm.bitplane_matmul(
        xp, w_packed, scale, bits=bits, bm=bm, bn=bn,
        bk=bk, interpret=interpret, out_dtype=out_dtype)
    return yp[:m]


def bitserial_matmul(x_packed, w_packed, x_scale, w_scale, *, a_bits, w_bits,
                     block_m=8, block_n=128, block_k=512, interpret=None,
                     out_dtype=jnp.float32):
    if interpret is None:
        interpret = _interpret_default()
    m = x_packed.shape[0]
    k = x_packed.shape[2] * 32
    bm = min(block_m, m) if m % min(block_m, m) == 0 else block_m
    bk = min(block_k, k)
    xp = _pad_to(x_packed, 0, bm)
    sp = _pad_to(x_scale, 0, bm)
    yp = _bsm.bitserial_matmul(
        xp, w_packed, sp, w_scale, a_bits=a_bits, w_bits=w_bits, bm=bm,
        bn=block_n, bk=bk, interpret=interpret, out_dtype=out_dtype)
    return yp[:m]


def quantized_matmul(x, w, *, bits, interpret=None, **blocks):
    """Quantize w to `bits`, pack, run the bit-plane kernel: one-stop API."""
    packed, scale = bitplane.quantize_pack(w, bits, axis=0)
    return bitplane_matmul(x, packed, scale, bits=bits,
                           interpret=interpret, **blocks)


def search_replace(packed, *, bits, key, interpret=None, block_w=512):
    if interpret is None:
        interpret = _interpret_default()
    w = packed.shape[1]
    bw = min(block_w, w)
    return _bb.search_replace(packed, bits=bits, key=key, bw=bw,
                              interpret=interpret)


def raid_xor(stripes, *, interpret=None, block_w=512):
    if interpret is None:
        interpret = _interpret_default()
    bw = min(block_w, stripes.shape[1])
    return _bb.raid_xor(stripes, bw=bw, interpret=interpret)


def bitserial_reduce(packed, *, bits, interpret=None, block_w=512):
    if interpret is None:
        interpret = _interpret_default()
    bw = min(block_w, packed.shape[1])
    return _bsr.bitserial_reduce(packed, bits=bits, bw=bw,
                                 interpret=interpret)


def bit_transpose(x, *, bits, interpret=None, block_w=256):
    if interpret is None:
        interpret = _interpret_default()
    bw = min(block_w, x.shape[0] // 32)
    return _bt.bit_transpose(x, bits=bits, bw=bw, interpret=interpret)


def bit_untranspose(packed, *, bits, signed=True, interpret=None,
                    block_w=256):
    if interpret is None:
        interpret = _interpret_default()
    bw = min(block_w, packed.shape[1])
    return _bt.bit_untranspose(packed, bits=bits, bw=bw, signed=signed,
                               interpret=interpret)
