"""Serving: prefill + batched decode (the `serve_step` of the dry-run).

`prefill` runs the full-sequence forward and (for attention layers) fills
the KV cache; `decode_step`/`serve_step` generates one token for the whole
batch against the cache / recurrent state.  The cache sequence axis is
sharded over `model` (flash-decoding style) so kv_heads < mesh axis never
blocks scaling; recurrent archs (xlstm / recurrentgemma) carry O(1) state.

With cfg.quant_bits set, every projection streams w-bit packed bit-plane
weights (the CoMeFa path) - the decode step is memory-bound, so weight
bytes are the roofline term this feature attacks.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import common as cm
from ..models import lm
from ..models.common import Config
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..parallel import sharding as shd

_QUEUE_DEPTH = obs_metrics.gauge("serve.queue_depth")
_REQUESTS_DONE = obs_metrics.counter("serve.requests_completed")


def prefill(params, tokens, cfg: Config, max_len: int,
            *, enc_inputs=None) -> Tuple[jax.Array, Dict[str, Any]]:
    """Process the prompt; returns (last-token logits, primed state).

    For simplicity and HLO compactness the cache is primed by running the
    per-token decode path under a scan for recurrent archs; attention
    caches are filled vectorised from the full-sequence K/V.
    """
    b, s = tokens.shape
    with obs_trace.span("serve.prefill", batch=b, seq=s,
                        family=cfg.family):
        logits, _ = lm.forward(params, tokens, cfg, enc_inputs=enc_inputs)
        states = lm.decode_state_init(cfg, b, max_len)
    return logits[:, -1:], states


def decode_step(params, token, states, index, cfg: Config, *, ctx=None):
    logits, states = lm.decode_step(params, token, states, index, cfg,
                                    ctx=ctx)
    return logits, states


def sample(logits: jax.Array, key, temperature: float = 0.0) -> jax.Array:
    if temperature == 0.0:
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits[:, -1] / temperature).astype(
        jnp.int32)


def generate(params, prompt, cfg: Config, *, steps: int, max_len: int,
             temperature: float = 0.0, key=None, enc_inputs=None,
             executor=None):
    """Greedy/temperature generation loop (host-driven, jitted steps).

    ``executor`` (a `serve.comefa_exec.GridLinearExecutor`) routes every
    packed projection of the prime + decode steps through the CoMeFa
    grid for the duration of this call; without one, packed weights
    contract on the XLA bit-plane path as before.
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    b, s = prompt.shape
    if s == 0:
        raise ValueError(
            "generate() needs a non-empty prompt (got shape "
            f"{tuple(prompt.shape)}): with no prompt tokens there are no "
            "logits to sample the first output token from")
    ctx = lm.encode(params, enc_inputs, cfg) if cfg.family == "encdec" \
        else None
    prev_hook = cm.set_linear_hook(executor) if executor is not None \
        else None
    try:
        states = lm.decode_state_init(cfg, b, max_len)
        # replay the prompt through the decode path to prime caches
        # exactly (spans wrap the host-driven dispatch, never the jitted
        # step body); per-token child spans give the trace host-sync
        # attribution per position - span() is the shared NULL_SPAN no-op
        # when tracing is off, so the loop stays unbounded-alloc-free
        logits = None
        with obs_trace.span("serve.prime", batch=b, seq=s,
                            family=cfg.family):
            for t in range(s):
                with obs_trace.span("serve.prime_token", step=t):
                    logits, states = lm.decode_step(
                        params, prompt[:, t:t + 1], states, jnp.int32(t),
                        cfg, ctx=ctx)
        out = []
        tok = sample(logits, key)
        for t in range(steps):
            out.append(tok)
            key, sub = jax.random.split(key)
            with obs_trace.span("serve.decode_step", step=t):
                logits, states = lm.decode_step(params, tok[:, None],
                                                states, jnp.int32(s + t),
                                                cfg, ctx=ctx)
                tok = sample(logits, sub, temperature)
    finally:
        if executor is not None:
            cm.set_linear_hook(prev_hook)
    return jnp.stack(out, axis=1)


# ---------------------------------------------------------------------------
# continuous batching: admit/retire requests between grid dispatches
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    """One serving request: a prompt to replay, then `steps` new tokens."""
    prompt: Any                      # [s] int tokens, s >= 1
    steps: int


def _sample_token(logits_row, key, temperature: float) -> int:
    """Sample one token from a [1, V] logits row (greedy at T=0)."""
    if temperature == 0.0:
        return int(jnp.argmax(logits_row[-1]))
    return int(jax.random.categorical(key, logits_row[-1] / temperature))


def _reset_state_slot(states, fresh, specs, slot: int):
    """Restore batch row `slot` of every decode-state leaf to fresh-init.

    The specs tree names each leaf's logical axes, so the batch axis is
    found positionally whatever the layout (scanned stacks prepend a
    "layers" axis).  Attention KV caches would self-clean through the
    per-row validity mask, but recurrent leaves carry state forward
    unconditionally (and some initialize non-zero, e.g. mLSTM's
    stabilizer m = -1e30) - copying from the init template keeps one
    admission rule for every mixer.
    """
    def leaf(s, f, axes):
        idx = tuple([slice(None)] * axes.index("batch") + [slot])
        return s.at[idx].set(f[idx])

    # specs is flattened *up to* the states treedef, so each axes tuple
    # arrives whole at its leaf position
    return jax.tree.map(leaf, states, fresh, specs)


def serve_continuous(params, requests: List[Request], cfg: Config, *,
                     slots: int, max_len: int, temperature: float = 0.0,
                     key=None, executor=None,
                     stats: Optional[Dict] = None) -> List[np.ndarray]:
    """Token-level continuous batching over a fixed-width decode batch.

    The batch is `slots` wide (one CoMeFa grid slot per row when an
    `executor` is installed).  Every step runs ONE batched decode over
    all rows at per-row sequence positions (the vector-`index` decode
    path); between steps, finished requests retire and queued requests
    admit into the freed rows, so grid slots never idle on finished
    sequences while work remains.  A newly admitted request replays its
    prompt token-by-token in its row while other rows keep decoding -
    prefill and decode share the same dispatch.

    Sampling keys fold in (request id, emission index) only, so a
    request's tokens are independent of batch composition - the
    continuous-batching property test pins that running requests
    together is token-identical to running each alone.

    Returns the emitted tokens per request, in submission order.  A
    ``stats`` dict receives ``steps`` (batched dispatches),
    ``occupancy`` (mean live-row fraction) and ``slot_steps``.
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    for i, r in enumerate(requests):
        if len(r.prompt) == 0:
            raise ValueError(f"request {i} has an empty prompt")
        if len(r.prompt) + r.steps > max_len:
            raise ValueError(f"request {i} needs {len(r.prompt) + r.steps}"
                             f" positions, max_len is {max_len}")
    specs = lm.decode_state_specs(cfg)
    states = lm.decode_state_init(cfg, slots, max_len)
    fresh = states                  # admission template: fresh-init rows
    queue = deque(enumerate(requests))
    outputs: List[Optional[List[int]]] = [None] * len(requests)
    slot_req = [None] * slots        # request id per row, None = idle
    slot_pos = [0] * slots           # prompt tokens consumed per row
    tok = np.zeros((slots, 1), np.int32)
    index = np.zeros((slots,), np.int32)
    step = slot_steps = 0
    prev_hook = cm.set_linear_hook(executor) if executor is not None \
        else None
    try:
        while queue or any(r is not None for r in slot_req):
            # admit: fill every idle row from the queue
            for g in range(slots):
                if slot_req[g] is not None or not queue:
                    continue
                rid, req = queue.popleft()
                states = _reset_state_slot(states, fresh, specs, g)
                slot_req[g], slot_pos[g], index[g] = rid, 0, 0
                outputs[rid] = []
                tok[g, 0] = int(req.prompt[0])
            _QUEUE_DEPTH.set(len(queue))
            live = np.array([r is not None for r in slot_req])
            if executor is not None:
                executor.active_mask = live
            slot_steps += int(live.sum())
            step += 1
            with obs_trace.span("serve.batch_step", step=step,
                                live=int(live.sum())):
                logits, states = lm.decode_step(
                    params, jnp.asarray(tok), states, jnp.asarray(index),
                    cfg)
            # per-row advance: next prompt token, or sample / retire
            for g in range(slots):
                rid = slot_req[g]
                if rid is None:
                    continue
                req = requests[rid]
                slot_pos[g] += 1
                index[g] += 1
                if slot_pos[g] < len(req.prompt):
                    tok[g, 0] = int(req.prompt[slot_pos[g]])
                    continue
                emitted = outputs[rid]
                sub = jax.random.fold_in(jax.random.fold_in(key, rid),
                                         len(emitted))
                t = _sample_token(logits[g], sub, temperature)
                emitted.append(t)
                tok[g, 0] = t
                if len(emitted) >= req.steps:
                    slot_req[g] = None
                    _REQUESTS_DONE.inc()
    finally:
        if executor is not None:
            executor.active_mask = None
            cm.set_linear_hook(prev_hook)
    if stats is not None:
        stats["steps"] = step
        stats["slot_steps"] = slot_steps
        stats["occupancy"] = slot_steps / (step * slots) if step else 0.0
    return [np.asarray(o, np.int32) for o in outputs]


def make_jitted_serve_step(mesh, cfg: Config, rules: Optional[dict] = None):
    """jit the one-token decode step with sharded cache/state."""
    shd.set_active_rules(rules)
    pspecs = shd.tree_specs(lm.specs(cfg), rules)
    sspecs = shd.tree_specs(lm.decode_state_specs(cfg), rules)
    tok_spec = shd.spec_for(("batch", None), rules)
    fn = functools.partial(decode_step, cfg=cfg)
    return jax.jit(
        fn,
        in_shardings=(shd.shardings(mesh, pspecs),
                      jax.sharding.NamedSharding(mesh, tok_spec),
                      shd.shardings(mesh, sspecs), None),
        out_shardings=(None, shd.shardings(mesh, sspecs)),
        donate_argnums=(2,))
