"""Pure-jnp oracles for every Pallas kernel (the `ref.py` contract).

Each oracle computes the same function as its kernel using only dense jnp
ops on the *unpacked* representation, so kernel bugs and packing bugs are
caught independently.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..quant.bitplane import unpack


def bitplane_matmul_ref(x: jax.Array, w_packed: jax.Array,
                        scale: jax.Array, *, bits: int) -> jax.Array:
    """y = x @ (unpacked ints * scale), all in f32."""
    q = unpack(w_packed, bits, axis=0)                     # [K, N] int32
    w = q.astype(jnp.float32) * scale                      # [K, N] * [1, N]
    return x.astype(jnp.float32) @ w


def bitserial_matmul_ref(x_packed: jax.Array, w_packed: jax.Array,
                         x_scale: jax.Array, w_scale: jax.Array, *,
                         a_bits: int, w_bits: int) -> jax.Array:
    qx = unpack(jnp.moveaxis(x_packed, 1, 0), a_bits, axis=1)  # [M, K]
    qw = unpack(w_packed, w_bits, axis=0)                      # [K, N]
    y = qx.astype(jnp.float32) @ qw.astype(jnp.float32)
    return y * x_scale * w_scale


def search_replace_ref(records: np.ndarray, key: int) -> np.ndarray:
    """Element-level oracle on raw integer records."""
    return np.where(records == key, 0, records)


def raid_xor_ref(stripes: np.ndarray) -> np.ndarray:
    return np.bitwise_xor.reduce(stripes, axis=0)


def bitserial_reduce_ref(values: np.ndarray) -> float:
    return float(values.astype(np.int64).sum())


def bit_transpose_ref(x: np.ndarray, bits: int) -> np.ndarray:
    """Element-major ints -> packed planes, in numpy."""
    n = x.shape[0]
    u = x.astype(np.uint32)
    planes = np.zeros((bits, n // 32), dtype=np.uint32)
    for i in range(bits):
        b = ((u >> i) & 1).reshape(-1, 32)
        planes[i] = (b << np.arange(32, dtype=np.uint32)).sum(
            axis=1).astype(np.uint32)
    return planes


def q6_revenue_ref(cols, year: int, discount: int, quantity: int) -> int:
    """TPC-H Q6 in int64 numpy: sum(price * discount) over the rows with
    January 1 of `year` <= shipdate < January 1 of `year + 1`,
    discount - 1 <= l_discount <= discount + 1 and l_quantity < quantity.

    `cols` maps shipdate (days since 1992-01-01), discount (hundredths),
    quantity and price (cents) to integer arrays; the revenue is in
    1/10,000 dollar, exact.
    """
    epoch = np.datetime64("1992-01-01", "D")
    lo = int((np.datetime64(f"{year:04d}-01-01", "D") - epoch).astype(int))
    hi = int((np.datetime64(f"{year + 1:04d}-01-01", "D") - epoch)
             .astype(int))
    ship, disc = cols["shipdate"], cols["discount"]
    keep = ((ship >= lo) & (ship < hi) & (disc >= discount - 1)
            & (disc <= discount + 1) & (cols["quantity"] < quantity))
    price = np.asarray(cols["price"])[keep].astype(np.int64)
    return int(np.sum(price * np.asarray(disc)[keep].astype(np.int64)))
