"""Model substrate: config schema, param init, primitive layers.

Functional style: every module is (init(key, cfg) -> params,
specs(cfg) -> logical-axis tree mirroring params, apply(params, x, ...)).
Params are nested dicts of arrays; the specs tree carries one tuple of
logical axis names per array (see parallel/sharding.py).

The CoMeFa technique enters through `linear()`: with cfg.quant_bits set,
weight-stationary projections store *packed bit-planes* (uint32, w bits per
weight in HBM) and contract via the bit-plane path - 'xla' mode expresses
unpack+dot in jnp (lowers everywhere incl. the dry-run, XLA fuses the
unpack into the matmul prologue), 'pallas' mode calls the Pallas kernel.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..quant import bitplane

Params = Dict[str, Any]

# (mixer, ffn) kinds per layer
MIXERS = ("global", "local", "bidir", "cross_global", "mlstm", "slstm",
          "rglru")
FFNS = ("mlp", "moe", "moe_dense", "none")


@dataclasses.dataclass(frozen=True)
class Config:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                      # 0 -> d_model // n_heads
    pattern: Tuple[Tuple[str, str], ...] = (("global", "mlp"),)
    # attention
    window: int = 4096                     # sliding window for "local"
    rope_theta: float = 10_000.0
    attn_softcap: float = 0.0
    final_softcap: float = 0.0
    qk_norm: bool = False
    prefix_lm: bool = False                # bidirectional prefix (VLM)
    # ffn
    act: str = "silu"
    # moe
    n_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 1.25
    moe_group: int = 512
    # enc-dec
    family: str = "decoder"                # "decoder" | "encdec"
    enc_layers: int = 0
    enc_pattern: Tuple[Tuple[str, str], ...] = (("bidir", "mlp"),)
    # modality frontend stub: inputs arrive as embeddings, not token ids
    frontend: str = "none"                 # none | audio_stub | vision_stub
    frontend_len: int = 0                  # frames/patches per example
    # recurrent dims
    conv_width: int = 4                    # RG-LRU temporal conv
    lru_width: int = 0                     # 0 -> d_model
    # CoMeFa bit-plane quantization (weight-only)
    quant_bits: Optional[int] = None
    # numerics / misc
    dtype: str = "bfloat16"
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    remat: bool = True
    scan_layers: bool = True

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def adtype(self):
        return jnp.dtype(self.dtype)

    def layer_kinds(self, n_layers: Optional[int] = None,
                    pattern=None) -> list:
        pattern = pattern or self.pattern
        n = self.n_layers if n_layers is None else n_layers
        return [pattern[i % len(pattern)] for i in range(n)]


def reduced(cfg: Config, **overrides) -> Config:
    """Tiny same-family config for CPU smoke tests."""
    shrink = dict(
        n_layers=max(len(cfg.pattern), 2 if cfg.family == "encdec" else
                     len(cfg.pattern)),
        d_model=64,
        n_heads=4, kv_heads=min(cfg.kv_heads, 2), head_dim=16,
        d_ff=0 if cfg.d_ff == 0 else 128, vocab=256,
        n_experts=min(cfg.n_experts, 4) if cfg.n_experts else 0,
        moe_group=64, window=min(cfg.window, 32),
        enc_layers=min(cfg.enc_layers, 2),
        frontend_len=min(cfg.frontend_len, 8) if cfg.frontend_len else 0,
        lru_width=0, scan_layers=False, remat=False, dtype="float32",
    )
    shrink.update(overrides)
    return dataclasses.replace(cfg, **shrink)


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def _init_dense(key, in_dim: int, out_dim: int, cfg: Config,
                quantize: bool) -> Params:
    std = 1.0 / math.sqrt(in_dim)
    w = jax.random.normal(key, (in_dim, out_dim), jnp.float32) * std
    if quantize and cfg.quant_bits and in_dim % 32 == 0:
        packed, scale = bitplane.quantize_pack(w, cfg.quant_bits, axis=0)
        return {"packed": packed, "scale": scale}
    return {"w": w.astype(cfg.adtype)}


def _dense_specs(in_axis: Optional[str], out_axis: Optional[str],
                 cfg: Config, quantize: bool) -> Params:
    if quantize and cfg.quant_bits:
        return {"packed": ("bits", in_axis, out_axis),
                "scale": (None, out_axis)}
    return {"w": (in_axis, out_axis)}


# Host-side interceptor for packed-projection contractions.  The serving
# layer installs an executor here to route eager decode-step GEMVs onto the
# CoMeFa grid; traced (jitted) calls never see it - the hook only fires on
# concrete values.  Signature: hook(params, x2 [rows, K], bits) -> [rows, N]
# array, or None to fall through to the XLA path.
_LINEAR_HOOK = None


def set_linear_hook(hook):
    """Install (or clear, with None) the packed-linear hook.

    Returns the previous hook so callers can restore it in a finally
    block - the serving engine scopes the executor to one generate call.
    """
    global _LINEAR_HOOK
    prev = _LINEAR_HOOK
    _LINEAR_HOOK = hook
    return prev


def linear(params: Params, x: jax.Array, cfg: Config) -> jax.Array:
    """y = x @ W with optional bit-plane packed weights (CoMeFa path)."""
    if "w" in params:
        return x @ params["w"].astype(x.dtype)
    packed, scale = params["packed"], params["scale"]
    bits = packed.shape[0]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if _LINEAR_HOOK is not None and not isinstance(x, jax.core.Tracer):
        y = _LINEAR_HOOK(params, x2, bits)
        if y is not None:
            return y.reshape(*lead, -1).astype(x.dtype)
    # XLA-expressible bit-plane contraction: unpack planes with shifts
    # (fused by XLA into the dot prologue) - weights cost w bits in HBM.
    q = bitplane.unpack(packed, bits, axis=0)              # [K, N] int32
    w = q.astype(x.dtype) * scale.astype(x.dtype)
    y = x2 @ w
    return y.reshape(*lead, -1).astype(x.dtype)


def rmsnorm_init(dim: int) -> Params:
    return {"g": jnp.ones((dim,), jnp.float32)}


def rmsnorm(params: Params, x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * (1.0 + params["g"])
    return y.astype(x.dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "relu": jax.nn.relu}[name]


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: [..., S, H, D], positions: [..., S]."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    angles = positions[..., None].astype(jnp.float32) * freqs   # [..., S, D/2]
    angles = angles[..., None, :]                               # [..., S, 1, D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def embed_init(key, cfg: Config) -> Params:
    e = jax.random.normal(key, (cfg.vocab, cfg.d_model), jnp.float32)
    return {"e": (e * 0.02).astype(cfg.adtype)}


def embed_specs() -> Params:
    return {"e": ("vocab", "embed")}
