"""Execute decode-step projections on the CoMeFa grid.

This closes the repo's priced-not-executed serving gap: with
``cfg.quant_bits`` set, `models.common.linear` stores w-bit bit-plane
packed weights, but (before this module) the decode-step GEMVs those
weights feed still ran as float XLA matmuls - the CoMeFa stack only ever
*modelled* them.  `GridLinearExecutor` is a `models.common.set_linear_hook`
interceptor that runs each packed projection on a `ComefaGrid` via
`kernels.comefa_sim.comefa_gemv_batched`, one decode request per grid
slot (batches wider than the grid take multiple waves; `active_mask`
lets the continuous batcher skip retired slots).

The grid kernels take **unsigned** operands, so both sides are
offset-encoded around their zero points and corrected on the host:

    q_w in [-2^(w-1), 2^(w-1)-1]   ->  w_u = q_w + 2^(w-1)
    q_x in [-2^(x-1), 2^(x-1)-1]   ->  x_u = q_x + 2^(x-1)

    q_w.T q_x = w_u.T x_u - b_w * sum_k x_u - b_x * sum_k w_u
                + K * b_w * b_x          (b_w = 2^(w-1), b_x = 2^(x-1))

Activations are quantized per request row (symmetric, `x_bits`); the
final dequantize multiplies the integer accumulator by
``scale_w * scale_x`` in float32.  ``backend="reference"`` replaces ONLY
the integer GEMV with an int64 ``einsum`` - every other op (quantize,
offsets, corrections, dequantize) is byte-for-byte the same code path,
so grid-executed logits are required to be bit-exact against the
int-quantized reference, which is what the tests pin.

``recode=None`` dispatches the value-independent broadcast program;
``"naive" | "booth" | "naf"`` uses `ComefaGrid.run_per_slot` per-slot
digit-stream specialization (PR 5) - each slot's FSM streams its own
recoded activation digits.  ``"auto"`` hands the choice to
`core.comefa.recode.select_wave` per wave/slot/chunk: decode activations
are offset-encoded around ``2^(x-1)``, so small ``|q_x|`` splits into
one-digit values (``128``) and long carry runs (``127``) - exactly the
mix where per-chunk selection beats any global knob.  The
``REPRO_COMEFA_RECODE`` environment variable overrides the default for
whole sweeps without touching call sites.
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.comefa.isa import ceil_log2
from ..kernels import comefa_sim
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..quant import bitplane

_GRID_WAVES = obs_metrics.counter("serve.grid_waves")
_GRID_OCCUPANCY = obs_metrics.gauge("serve.grid_occupancy")

def _resolve_recode(recode):
    """Apply the ``REPRO_COMEFA_RECODE`` override to the default recode.

    An explicit constructor argument (including ``None``) always wins;
    only the ``"env"`` sentinel default consults the environment.
    ``none``/``broadcast`` map to the shared broadcast program, ``auto``
    to per-wave adaptive selection, the rest to fixed per-slot digit
    schedules; unset keeps the broadcast default.
    """
    if recode != "env":
        return recode
    val = os.environ.get("REPRO_COMEFA_RECODE", "").strip().lower()
    if val in ("", "none", "broadcast"):
        return None
    if val in ("auto", "naive", "booth", "naf"):
        return val
    raise ValueError(
        f"REPRO_COMEFA_RECODE={val!r}: expected one of "
        f"none|broadcast|auto|naive|booth|naf")


def acc_bits_for(w_bits: int, x_bits: int, k: int) -> int:
    """Accumulator width covering the worst-case unsigned dot product.

    max(w_u.T x_u) = (2^w - 1)(2^x - 1) * K < 2^(w + x + ceil_log2(K)).
    """
    return w_bits + x_bits + ceil_log2(max(2, k))


class GridLinearExecutor:
    """Route packed-projection GEMVs through the CoMeFa grid.

    Install with ``models.common.set_linear_hook(executor)`` (the serving
    engine does this for the duration of one generate / serve call).  The
    hook only fires on concrete (eager) activations - traced calls fall
    through to the XLA path untouched.

    Parameters
    ----------
    slots: grid width G - decode requests per dispatch wave.
    x_bits: activation quantization width (weights carry their own width
        in ``packed.shape[0]``).
    recode: None for the shared broadcast program, "naive"/"booth"/
        "naf" for a fixed per-slot digit-stream specialization, or
        "auto" for per-wave/per-slot/per-chunk adaptive selection
        (`core.comefa.recode`).  The default ``"env"`` sentinel reads
        the ``REPRO_COMEFA_RECODE`` environment override (falling back
        to the broadcast program when unset).
    backend: "grid" executes on the bit-level simulator; "reference"
        swaps ONLY the integer GEMV for an int64 einsum (the bit-exact
        oracle the tests compare against).
    engine: forwarded to the simulator (`REPRO_COMEFA_ENGINE` default).
    """

    def __init__(self, slots: int = 4, x_bits: int = 8,
                 recode: Optional[str] = "env", backend: str = "grid",
                 engine=None):
        assert backend in ("grid", "reference"), backend
        self.slots = slots
        self.x_bits = x_bits
        self.recode = _resolve_recode(recode)
        self.backend = backend
        self.engine = engine
        # continuous batching: bool [rows] marking live requests; None
        # means every row is live (plain generate)
        self.active_mask: Optional[np.ndarray] = None
        # occupancy accounting: live slots dispatched / slot capacity
        self.slot_steps = 0
        self.slot_capacity = 0
        self.calls = 0
        self.grid_cycles = 0
        self._wcache: Dict[int, Tuple] = {}

    # -- weights -----------------------------------------------------------
    def _weights(self, packed, bits: int):
        """Unpacked offset-encoded weights, per-column sums and the grid's
        `comefa_sim.GemvWeights` of them, cached.

        Params are immutable across decode steps, so the unpack runs once
        per projection (keyed on the packed array's identity), and the
        weights' bit planes, once built on the device, stay there.
        """
        key = id(packed)
        ent = self._wcache.get(key)
        if ent is None or ent[0] is not packed:
            q = np.asarray(bitplane.unpack(packed, bits, axis=0),
                           np.int64)                       # [K, N] signed
            w_u = q + (1 << (bits - 1))                    # unsigned
            ent = (packed, w_u, w_u.sum(axis=0), comefa_sim.GemvWeights(w_u))
            self._wcache[key] = ent
        return ent[1:]

    # -- stats -------------------------------------------------------------
    def occupancy(self) -> float:
        """Mean fraction of grid slots carrying a live request."""
        if not self.slot_capacity:
            return 0.0
        return self.slot_steps / self.slot_capacity

    # -- the hook ----------------------------------------------------------
    def __call__(self, params, x2, bits: int):
        """hook(params, x2 [rows, K] float, bits) -> [rows, N] float32."""
        packed, scale = params["packed"], params["scale"]
        w_u, col_sum, w_grid = self._weights(packed, bits)
        k, n = w_u.shape
        xf = np.asarray(x2, np.float32)
        rows = xf.shape[0]
        # per-row symmetric activation quantization (mirrors
        # bitplane.quantize, including the -qmax-1 clip edge)
        qmax = float(2 ** (self.x_bits - 1) - 1)
        absmax = np.abs(xf).max(axis=1)
        s_x = np.where(absmax > 0, absmax / qmax, 1.0).astype(np.float32)
        q_x = np.clip(np.rint(xf / s_x[:, None]), -qmax - 1, qmax)
        b_w = 1 << (bits - 1)
        b_x = 1 << (self.x_bits - 1)
        x_u = q_x.astype(np.int64) + b_x                   # in [0, 2^x)
        if self.active_mask is None:
            live = np.arange(rows)
        else:
            live = np.flatnonzero(np.asarray(self.active_mask, bool))
        acc_bits = acc_bits_for(bits, self.x_bits, k)
        acc = np.zeros((rows, n), np.int64)
        self.calls += 1
        with obs_trace.span("serve.grid_linear", rows=rows, k=k, n=n,
                            backend=self.backend) as sp:
            for start in range(0, len(live), self.slots):
                wave = live[start:start + self.slots]
                g = len(wave)
                self.slot_steps += g
                self.slot_capacity += self.slots
                _GRID_WAVES.inc(backend=self.backend)
                if self.backend == "grid":
                    stats: Dict = {}
                    acc[wave] = comefa_sim.comefa_gemv_batched(
                        w_grid, x_u[wave],
                        w_bits=bits, x_bits=self.x_bits, acc_bits=acc_bits,
                        recode=self.recode, stats=stats, engine=self.engine)
                    self.grid_cycles += stats["cycles"]
                else:
                    acc[wave] = np.einsum("gk,kn->gn", x_u[wave], w_u)
            sp.set(waves=-(-len(live) // self.slots) if len(live) else 0)
        _GRID_OCCUPANCY.set(self.occupancy(), backend=self.backend)
        # zero-point corrections recover the signed accumulator, then
        # dequantize: y = (q_w.T q_x) * scale_w * scale_x
        acc_q = (acc - b_w * x_u.sum(axis=1)[:, None]
                 - b_x * col_sum[None, :] + k * b_w * b_x)
        scale_w = np.asarray(scale, np.float32).reshape(1, -1)
        y = acc_q.astype(np.float32) * (scale_w * s_x[:, None])
        return jnp.asarray(y)
