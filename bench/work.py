"""Operations and bytes from shapes: the yardstick's arithmetic.

Everything here is computed from sizes alone, never from a run, so every
PR prices the same call the same way.
"""
from __future__ import annotations

LANE_BITS = 32          # the packed engine keeps 32 lanes in one uint32 word
WORD_BYTES = 4


def packed_words(cols: int) -> int:
    """uint32 words one packed row of `cols` lanes occupies."""
    return -(-cols // LANE_BITS)


def step_kernel_bytes(slots: int, n_blocks: int, n_instr: int,
                      n_fields: int, *, per_slot: bool, rows: int = 128,
                      cols: int = 160) -> int:
    """HBM bytes one call of the packed step kernel must move.

    The packed state - ``mem[S, nb, rows, W]`` plus the carry and mask
    latches ``[S, nb, W]`` - is read once and written once; the int32
    program matrix ``[T, F]`` (``[S, T, F]`` per slot) is read once.
    """
    w = packed_words(cols)
    state = slots * n_blocks * (rows * w + 2 * w) * WORD_BYTES
    prog = (slots if per_slot else 1) * n_instr * n_fields * WORD_BYTES
    return 2 * state + prog


def lm_flops_per_token(*, hidden: int, intermediate: int, heads: int,
                       kv_heads: int, head_dim: int, layers: int,
                       vocab: int, context: float) -> float:
    """Model FLOPs of one decoded token of a Llama-style decoder.

    Two FLOPs per multiply-accumulate: seven projections per layer, the
    attention scores and the weighted sum over ``context`` cached
    positions, and the tied LM head.  Norms, RoPE and softmax are left
    out (they are not matrix work).
    """
    q_out = heads * head_dim
    kv_out = kv_heads * head_dim
    proj = (hidden * q_out + 2 * hidden * kv_out + q_out * hidden
            + 3 * hidden * intermediate)
    attn = 2 * heads * head_dim * context
    return 2.0 * (layers * (proj + attn) + hidden * vocab)
