"""The one traffic generator: turns a traffic mix's data file into work.

A mix is a JSON file under ``bench/traffic/``; its ``kind`` says which
reader below applies.  Every seed draws the same multiset of sizes (the
quantiles of the stated distribution) in another order, so two seeds do
the same amount of work and differ only in its order and its values.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np


def rng(seed: int, stream: str) -> np.random.Generator:
    """Independent numpy stream per purpose; any non-negative seed."""
    tag = int.from_bytes(stream.encode(), "little") % (1 << 32)
    return np.random.default_rng([int(seed) % (1 << 64), tag])


def sizes(dist: Dict, n: int) -> np.ndarray:
    """The n quantiles of a size distribution (same multiset every seed)."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if kind == "uniform_int":
        out = np.floor(lo + u * (hi + 1 - lo))
    elif kind == "loguniform":
        out = np.floor(np.exp(math.log(lo) + u * (math.log(hi + 1)
                                                  - math.log(lo))))
    else:
        raise ValueError(f"unknown size distribution {kind!r}")
    return np.clip(out, lo, hi).astype(np.int64)


def requests(mix: Dict, seed: int, vocab: int) -> List[Tuple[np.ndarray, int]]:
    """(prompt tokens, tokens to generate) per request, in arrival order."""
    assert mix["kind"] == "requests", mix["kind"]
    if mix["loop"] != "closed":
        raise SystemExit(f"bench: request loop {mix['loop']!r}: only a "
                         "closed loop is generated")
    n = int(mix["requests"])
    r = rng(seed, "requests")
    prompt = r.permutation(sizes(mix["prompt_len"], n))
    output = r.permutation(sizes(mix["output_len"], n))
    if (prompt + output).max() > mix["max_len"]:
        raise ValueError("a request is longer than the mix's max_len")
    return [(r.integers(0, vocab, size=int(p)).astype(np.int32), int(o))
            for p, o in zip(prompt, output)]


def grid_widths(mix: Dict, seed: int, slots: int) -> np.ndarray:
    """Operand width per FSM slice (one width for a shared program)."""
    assert mix["kind"] == "grid_ops", mix["kind"]
    if not mix["per_slot"]:
        return sizes(mix["bits"], 1).repeat(slots)
    return rng(seed, "widths").permutation(sizes(mix["bits"], slots))


def grid_operands(seed: int, widths: np.ndarray, n_blocks: int,
                  lanes: int) -> Tuple[np.ndarray, np.ndarray]:
    """Random unsigned operands a, b ``[G, nb, lanes]``, slot g at widths[g]."""
    r = rng(seed, "operands")
    hi = (np.int64(1) << widths.astype(np.int64))[:, None, None]
    shape = (len(widths), n_blocks, lanes)
    return r.integers(0, hi, size=shape), r.integers(0, hi, size=shape)
