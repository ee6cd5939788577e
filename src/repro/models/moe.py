"""Mixture-of-Experts FFN with explicit expert-parallel (EP) or
tensor-parallel (TP) sharding.

Design (see DESIGN.md §4): activations are *replicated over the "model"
axis* (Megatron convention), so EP dispatch never needs an all-to-all —
each model shard masks out the tokens routed to its local experts, runs a
capacity-bounded grouped matmul, and the final ``psum`` over "model" both
sums expert contributions and restores replication. TP sharding (Grok: 8
experts < 16-way model axis) shards every expert's FFN hidden dim instead;
the dispatch code is identical with ``n_local_experts == num_experts``.

``apply_moe_local`` is the single-device oracle used by smoke tests and as
the reference for the sharded path.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import PD, activation_fn


def moe_desc(cfg: ModelConfig) -> Dict:
    m = cfg.moe
    d, f = cfg.d_model, m.d_ff_expert
    desc = {
        "router": PD((d, m.num_experts), ("embed", "experts_r")),
        "wi": PD((m.num_experts, d, 2, f), ("experts", "embed", None, "expert_mlp")),
        "wo": PD((m.num_experts, f, d), ("experts", "expert_mlp", "embed")),
    }
    if m.num_shared_experts:
        fs = f * m.num_shared_experts
        desc["shared_wi"] = PD((d, 2, fs), ("embed", None, "mlp"))
        desc["shared_wo"] = PD((fs, d), ("mlp", "embed"))
    return desc


def _route(cfg: ModelConfig, logits: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Return (weights (N,K), ids (N,K), probs (N,E))."""
    m = cfg.moe
    logits = logits.astype(jnp.float32)
    if m.router_softmax_order == "softmax_then_topk":
        probs = jax.nn.softmax(logits, axis=-1)
        w, ids = jax.lax.top_k(probs, m.top_k)
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    else:
        top_logits, ids = jax.lax.top_k(logits, m.top_k)
        w = jax.nn.softmax(top_logits, axis=-1)
        probs = jax.nn.softmax(logits, axis=-1)
    return w, ids, probs


def _dispatch_compute(cfg: ModelConfig, x_flat: jax.Array, w: jax.Array,
                      ids: jax.Array, wi: jax.Array, wo: jax.Array,
                      e0: int, n_local: int, capacity: int) -> jax.Array:
    """Capacity-bounded grouped-matmul MoE for experts [e0, e0+n_local).

    x_flat: (N, D); w/ids: (N, K); wi: (El, D, 2, F); wo: (El, F, D).
    Returns (N, D) partial output (only local experts' contributions).
    """
    n, d = x_flat.shape
    k = ids.shape[1]
    nk = n * k
    ids_f = ids.reshape(nk)
    w_f = w.reshape(nk)
    tok_f = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)

    le = ids_f - e0
    sel = (le >= 0) & (le < n_local)
    le = jnp.clip(le, 0, n_local - 1)
    # Position of each entry within its expert queue (stable order).
    onehot = jax.nn.one_hot(le, n_local, dtype=jnp.int32) * sel[:, None].astype(jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), le[:, None], axis=1)[:, 0] - 1
    valid = sel & (pos < capacity)
    dump = n_local * capacity  # overflow slot
    slot = jnp.where(valid, le * capacity + pos, dump)

    # Scatter tokens into the (El*C+1, D) buffer (last row = dump).
    buf = jnp.zeros((n_local * capacity + 1, d), x_flat.dtype)
    buf = buf.at[slot].add(jnp.take(x_flat, tok_f, axis=0))
    buf = buf[:-1].reshape(n_local, capacity, d)

    h = jnp.einsum("ecd,edgf->ecgf", buf, wi.astype(buf.dtype))
    h = activation_fn(cfg, h[..., 0, :]) * h[..., 1, :]
    out = jnp.einsum("ecf,efd->ecd", h, wo.astype(buf.dtype))
    out = out.reshape(n_local * capacity, d)

    # Map slots back to tokens; dump/invalid entries carry weight 0.
    slot_tok = jnp.zeros((n_local * capacity + 1,), jnp.int32).at[slot].set(tok_f)
    slot_w = jnp.zeros((n_local * capacity + 1,), jnp.float32).at[slot].set(
        jnp.where(valid, w_f, 0.0))
    y = jnp.zeros((n, d), x_flat.dtype)
    y = y.at[slot_tok[:-1]].add(out * slot_w[:-1, None].astype(out.dtype))
    return y


def _aux_loss(probs: jax.Array, ids: jax.Array, num_experts: int) -> jax.Array:
    """Switch-style load-balancing loss (mean over tokens)."""
    frac = jnp.mean(
        jax.nn.one_hot(ids.reshape(-1), num_experts, dtype=jnp.float32), axis=0)
    imp = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(frac * imp)


def _capacity(cfg: ModelConfig, n_tokens: int, n_shards: int) -> int:
    """Per-expert token capacity (same for EP and TP sharding)."""
    m = cfg.moe
    per_expert = n_tokens * m.top_k / m.num_experts
    cap = int(per_expert * m.capacity_factor) + 1
    return max(8, -(-cap // 8) * 8)  # round up to 8


def apply_moe_local(cfg: ModelConfig, p: Dict, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Single-device oracle: all experts local."""
    m = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    logits = jnp.einsum("nd,de->ne", xf, p["router"].astype(x.dtype))
    w, ids, probs = _route(cfg, logits)
    cap = _capacity(cfg, b * s, 1)
    y = _dispatch_compute(cfg, xf, w, ids, p["wi"], p["wo"], 0, m.num_experts, cap)
    if m.num_shared_experts:
        h = jnp.einsum("nd,dgf->ngf", xf, p["shared_wi"].astype(x.dtype))
        y = y + jnp.einsum("nf,fd->nd",
                           activation_fn(cfg, h[:, 0]) * h[:, 1],
                           p["shared_wo"].astype(x.dtype))
    return y.reshape(b, s, d), _aux_loss(probs, ids, m.num_experts)


def apply_moe_sharded(cfg: ModelConfig, p: Dict, x: jax.Array, mesh,
                      dp_axes: Tuple[str, ...], tp_axis: str) -> Tuple[jax.Array, jax.Array]:
    """shard_map MoE: EP (experts over tp_axis) or TP (FFN dim over tp_axis)."""
    m = cfg.moe
    n_model = mesh.shape[tp_axis]
    ep = m.sharding == "ep"
    if ep:
        assert m.num_experts % n_model == 0, (m.num_experts, n_model)
        wi_spec, wo_spec = P(tp_axis, None, None, None), P(tp_axis, None, None)
        n_local = m.num_experts // n_model
    else:
        wi_spec, wo_spec = P(None, None, None, tp_axis), P(None, tp_axis, None)
        n_local = m.num_experts
    x_spec = P(dp_axes, None, None)
    router_spec = P(None, None)

    b, s, d = x.shape
    n_dp = 1
    for a in dp_axes:
        n_dp *= mesh.shape[a]
    cap = _capacity(cfg, (b // n_dp) * s, n_model)

    def fn(xl, router, wi, wo):
        bl, sl, _ = xl.shape
        xf = xl.reshape(bl * sl, d)
        logits = jnp.einsum("nd,de->ne", xf, router.astype(xf.dtype))
        w, ids, probs = _route(cfg, logits)
        if ep:
            e0 = jax.lax.axis_index(tp_axis) * n_local
        else:
            e0 = 0
        y = _dispatch_compute(cfg, xf, w, ids, wi, wo, e0, n_local, cap)
        y = jax.lax.psum(y, tp_axis)
        aux = _aux_loss(probs, ids, m.num_experts)
        aux = jax.lax.pmean(aux, dp_axes)
        return y.reshape(bl, sl, d), aux

    y, aux = shard_map(
        fn, mesh=mesh,
        in_specs=(x_spec, router_spec, wi_spec, wo_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"], p["wi"], p["wo"])

    if m.num_shared_experts:  # shared experts: plain TP MLP outside shard_map
        h = jnp.einsum("bsd,dgf->bsgf", x, p["shared_wi"].astype(x.dtype))
        y = y + jnp.einsum("bsf,fd->bsd",
                           activation_fn(cfg, h[..., 0, :]) * h[..., 1, :],
                           p["shared_wo"].astype(x.dtype))
    return y, aux


def apply_moe(cfg: ModelConfig, p: Dict, x: jax.Array, mesh=None,
              dp_axes: Tuple[str, ...] = ("data",), tp_axis: str = "model"
              ) -> Tuple[jax.Array, jax.Array]:
    if mesh is None:
        return apply_moe_local(cfg, p, x)
    return apply_moe_sharded(cfg, p, x, mesh, dp_axes, tp_axis)
