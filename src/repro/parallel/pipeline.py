"""Pipeline parallelism: microbatch pipelining over a `stage` mesh axis.

GPipe-style schedule expressed with shard_map + collective_permute: the
layer stack is split into S stages (params sharded over the stage axis);
a rotating buffer carries microbatch activations stage-to-stage.  With M
microbatches the bubble fraction is (S-1)/(M+S-1) - the classic formula,
asserted in tests.

The production mesh for the assigned models stays 2D+pod (they fit without
PP); this module exists because a 1000+-node deployment of deeper models
needs the stage axis, and proves our stack composes with it.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipelined_apply(fn: Callable, mesh: Mesh, axis: str = "stage"):
    """Build a pipelined forward: y = fn_S(...fn_1(x)) over stage-sharded
    params.

    fn(stage_params, x) -> x is the per-stage computation.  Input x:
    [n_micro, mb, ...]; stage_params leaves have a leading stage dim.
    Returns a function (params, x) -> y with the same global signature.
    """
    n_stages = mesh.shape[axis]

    def per_shard(params, x):
        # params: this stage's slice (leading dim 1) ; x: all microbatches
        sp = jax.tree.map(lambda a: a[0], params)
        n_micro = x.shape[0]
        stage = jax.lax.axis_index(axis)
        total = n_micro + n_stages - 1

        def step(carry, t):
            buf, outs = carry
            # t-th tick: stage s works on microbatch t-s (if valid)
            mb_idx = t - stage
            valid = (mb_idx >= 0) & (mb_idx < n_micro)
            # first stage reads fresh input; others read the rotated buffer
            fresh = jax.lax.dynamic_index_in_dim(
                x, jnp.clip(mb_idx, 0, n_micro - 1), 0, keepdims=False)
            inp = jnp.where(stage == 0, fresh, buf)
            out = fn(sp, inp)
            out = jnp.where(valid, out, jnp.zeros_like(out))
            # pass to the next stage
            buf_next = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            # last stage records its finished microbatch
            outs = jax.lax.cond(
                valid & (stage == n_stages - 1),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.clip(mb_idx, 0, n_micro - 1), 0),
                lambda o: o, outs)
            return (buf_next, outs), None

        buf0 = jnp.zeros_like(x[0])
        outs0 = jnp.zeros_like(x)
        (_, outs), _ = jax.lax.scan(step, (buf0, outs0),
                                    jnp.arange(total))
        # every stage holds zeros except the last; share the result
        outs = jax.lax.psum(outs, axis)
        return outs

    return jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(axis), P()), out_specs=P(),
        check_vma=False)
