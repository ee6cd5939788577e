"""GPipe-style pipeline parallelism over a mesh axis via shard_map +
collective_permute.

Layers are grouped into S = mesh.shape[axis] stages (each device holds its
stage's parameter slice); M microbatches flow through a T = M + S - 1 tick
schedule; stage boundaries move activations with ``ppermute`` (one hop per
tick, fully overlappable with the next tick's compute on TPU). Backward is
ordinary autodiff through the schedule (ppermute transposes to the reverse
permutation), i.e. GPipe's synchronous fill-drain pipeline with re-
materialized stages.

This is a feature module for very deep models (the fixed production mesh
uses DP x TP by default); tests exercise it on a host-device mesh and check
exact equivalence with the sequential stack, including gradients.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stacked_params, x: jax.Array,
                   mesh, *, axis: str = "model", n_micro: int = None):
    """Run ``y = stage_fn(params_s, y)`` for s = 0..S-1 over the pipeline.

    stacked_params: pytree with leading dim S (one slice per stage).
    x: (B, ...) global batch; split into n_micro microbatches (default S).
    Returns y with the same shape as x.
    """
    S = mesh.shape[axis]
    M = n_micro or S
    B = x.shape[0]
    assert B % M == 0, (B, M)

    p_spec = jax.tree.map(lambda _: P(axis), stacked_params)
    x_spec = P()          # replicated in; every stage sees all microbatches
    out_spec = P()

    def fn(params_local, xl):
        # params_local: leading dim 1 (this stage's slice)
        params_s = jax.tree.map(lambda a: a[0], params_local)
        s = jax.lax.axis_index(axis)
        micro = xl.reshape((M, B // M) + xl.shape[1:])
        buf = jnp.zeros_like(micro[0])          # incoming activation
        outs = jnp.zeros_like(micro)
        fwd = [(i, (i + 1) % S) for i in range(S)]

        def tick(t, carry):
            buf, outs = carry
            # stage 0 injects microbatch t (if in range); others take buf
            mb_idx = jnp.clip(t - s, 0, M - 1)
            inject = jax.lax.dynamic_index_in_dim(micro, mb_idx, 0,
                                                  keepdims=False)
            inp = jnp.where(s == 0, inject, buf)
            active = (t - s >= 0) & (t - s < M)
            y = stage_fn(params_s, inp)
            y = jnp.where(active, y, buf)
            # last stage banks its result at position t-(S-1)
            bank = (s == S - 1) & active
            pos = jnp.clip(t - (S - 1), 0, M - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, pos, 0, keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(bank, y, cur), pos, 0)
            buf_next = jax.lax.ppermute(y, axis, fwd)
            return buf_next, outs

        buf, outs = jax.lax.fori_loop(0, M + S - 1, tick, (buf, outs))
        # only stage S-1 banked non-zero outputs; psum broadcasts them
        # (other stages contribute exact zeros)
        outs = jax.lax.psum(outs, axis)
        return outs.reshape(xl.shape)

    return shard_map(fn, mesh=mesh, in_specs=(p_spec, x_spec),
                     out_specs=out_spec, check_vma=False)(stacked_params, x)


def sequential_apply(stage_fn: Callable, stacked_params, x: jax.Array):
    """Reference: the same stack applied sequentially."""
    def body(y, p):
        return stage_fn(p, y), None
    y, _ = jax.lax.scan(body, x, stacked_params)
    return y
