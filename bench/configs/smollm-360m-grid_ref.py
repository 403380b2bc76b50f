"""Plain reference for smollm-360m-grid: a Llama-style decoder in float32.

Straight ``jax.numpy`` at ``highest`` matmul precision, no cache, no
batching tricks: every position of a token sequence attends causally to
the ones before it.  Each projection is computed as the configuration
states it is served: int4 weights with one float32 scale per output
column (made by the benchmark, not by the program), activations
quantized per token to ``x_bits`` symmetric integers with scale
``absmax / (2^(x_bits-1) - 1)``, round half to even, the integer dot
product exact, then ``acc * (scale_w * scale_x)``.

Departure from SmolLM-360M, noted in the configuration file: token
embeddings are scaled by sqrt(hidden_size), as the program does.

Imports nothing of the program.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

PROJECTIONS = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj",
               "up_proj", "down_proj")


def quantize_rows(x, x_bits: int):
    """Per-row symmetric quantization: (integer values, float32 scale)."""
    qmax = 2.0 ** (x_bits - 1) - 1
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.where(absmax > 0, absmax / qmax, 1.0)
    q = jnp.clip(jnp.round(x / s), -qmax - 1, qmax)
    return q, s


def qlinear(x, w, x_bits: int):
    """x [..., K] float32 through an int weight (q [K, N], scale [N])."""
    q_w, s_w = w
    q_x, s_x = quantize_rows(x, x_bits)
    acc = jnp.einsum("...k,kn->...n", q_x, q_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return acc * (s_w * s_x)


def rmsnorm(x, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, theta: float):
    """Rotary embedding on [B, T, H, D], halves rotated (Llama layout)."""
    t, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def forward(weights: Dict, tokens, dims: Dict, x_bits: int):
    """Logits [B, T, V] float32 at every position of `tokens` [B, T]."""
    heads, kv, hd = dims["heads"], dims["kv_heads"], dims["head_dim"]
    eps, theta = dims["eps"], dims["rope_theta"]
    embed = weights["embed"].astype(jnp.float32)
    b, t = tokens.shape
    x = embed[tokens] * math.sqrt(embed.shape[1])
    causal = jnp.tril(jnp.ones((t, t), bool))
    for lw in weights["layers"]:
        h = rmsnorm(x, eps)
        q = qlinear(h, lw["q_proj"], x_bits).reshape(b, t, heads, hd)
        k = qlinear(h, lw["k_proj"], x_bits).reshape(b, t, kv, hd)
        v = qlinear(h, lw["v_proj"], x_bits).reshape(b, t, kv, hd)
        q, k = rope(q, theta), rope(k, theta)
        k = jnp.repeat(k, heads // kv, axis=2)
        v = jnp.repeat(v, heads // kv, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + qlinear(o.reshape(b, t, heads * hd), lw["o_proj"], x_bits)
        h = rmsnorm(x, eps)
        g = qlinear(h, lw["gate_proj"], x_bits)
        u = qlinear(h, lw["up_proj"], x_bits)
        x = x + qlinear(jax.nn.silu(g) * u, lw["down_proj"], x_bits)
    x = rmsnorm(x, eps)
    return jnp.einsum("btd,vd->btv", x, embed,
                      precision=jax.lax.Precision.HIGHEST)


def projection(x: np.ndarray, q_w: np.ndarray, s_w: np.ndarray,
               x_bits: int) -> np.ndarray:
    """One projection of rows x [R, K] on the host, in numpy float32 and
    exact int64 - the answer a grid-executed projection must give."""
    x = np.asarray(x, np.float32)
    qmax = float(2 ** (x_bits - 1) - 1)
    absmax = np.abs(x).max(axis=1)
    s_x = np.where(absmax > 0, absmax / qmax, 1.0).astype(np.float32)
    q_x = np.clip(np.rint(x / s_x[:, None]), -qmax - 1, qmax)
    acc = q_x.astype(np.int64) @ np.asarray(q_w, np.int64)
    s_w = np.asarray(s_w, np.float32).reshape(1, -1)
    return acc.astype(np.float32) * (s_w * s_x[:, None])
