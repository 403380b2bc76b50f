"""Plain reference for arria10-gx900-fleet: integer arithmetic, bit rows.

A CoMeFa RAM stores values transposed: row ``base + i`` holds bit i of
the value in every column (lane).  `read_rows` decodes such a field from
one slice's state ``[n_blocks, rows, lanes]`` of 0/1 values.  Each
dispatch of the cell is a multiply-accumulate on n-bit unsigned
operands: ``p = a * b`` (2n bits), ``acc = (acc + p) mod 2^(2n)``; after
k dispatches from a zero accumulator `state` gives every field.
Imports nothing of the program.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import numpy as np


def fields(n: int) -> Dict[str, Tuple[int, int]]:
    """(first row, width) of each field for n-bit operands."""
    return {"a": (0, n), "b": (n, n), "p": (2 * n, 2 * n),
            "acc": (4 * n, 2 * n)}


def read_rows(mem: np.ndarray, base: int, width: int) -> np.ndarray:
    """Unsigned values ``[n_blocks, lanes]`` in rows base..base+width."""
    bits = np.asarray(mem[:, base:base + width, :], np.int64)
    weights = np.int64(1) << np.arange(width, dtype=np.int64)
    return np.einsum("brl,r->bl", bits, weights)


def products(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Exact unsigned products, lane by lane."""
    return np.asarray(a, np.int64) * np.asarray(b, np.int64)


def products_float32(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The control: the same products rounded to float32."""
    p = np.asarray(a, np.float32) * np.asarray(b, np.float32)
    return p.astype(np.float64).astype(np.int64)


def state(a: np.ndarray, b: np.ndarray, n: int, k: int,
          products: Callable = products) -> Dict[str, np.ndarray]:
    """Every field after k multiply-accumulates of n-bit a and b."""
    p = products(a, b)
    modulus = np.int64(1) << np.int64(2 * n)
    assert 0 <= k < 2 ** 31, k        # so k * p stays below 2^63
    acc = (np.int64(k) * (p % modulus)) % modulus
    return {"a": np.asarray(a, np.int64), "b": np.asarray(b, np.int64),
            "p": p % modulus, "acc": acc}
