"""Expert parallelism: Mixture-of-Experts with all-to-all dispatch.

Beyond-reference extension (SURVEY.md §2.5: the reference ships the
``alltoall`` collective but no MoE strategy; this module is the strategy).
Switch/GShard-style top-k routing with capacity: tokens are dispatched to
experts sharded over the 'ep' mesh axis via XLA ``all-to-all`` — the exact
use case the reference's AlltoallOp existed to serve, here fused into the
compiled step.

All functions run inside a shard_map body.  Shapes per shard:
tokens ``x: [T, d]``; experts_per_shard local experts; global expert count
E = ep_size * experts_per_shard.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    n_experts: int
    d_model: int
    d_ff: int
    top_k: int = 2
    capacity_factor: float = 1.25


def init_moe_params(key, cfg: MoeConfig, experts_per_shard: int,
                    dtype=jnp.float32):
    """Per-shard expert weights (swiglu FFN per expert) + replicated router.

    In the ep-sharded world each shard holds ``experts_per_shard`` experts;
    stacking over shards yields the full expert set.
    """
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d, f = cfg.d_model, cfg.d_ff
    s = 1.0 / math.sqrt(d)
    return {
        "router": (jax.random.normal(k1, (d, cfg.n_experts)) * s
                   ).astype(dtype),
        "w1": (jax.random.normal(k2, (experts_per_shard, d, f)) * s
               ).astype(dtype),
        "w3": (jax.random.normal(k3, (experts_per_shard, d, f)) * s
               ).astype(dtype),
        "w2": (jax.random.normal(k4, (experts_per_shard, f, d)) *
               (1.0 / math.sqrt(f))).astype(dtype),
    }


def _dispatch_tensors(gates, top_k: int, n_experts: int, capacity: int):
    """Build dispatch/combine tensors (GShard-style cumsum position slots).

    gates: [T, E] softmax router probabilities.
    Returns dispatch [T, E, C] (bool) and combine [T, E, C] (weights).
    """
    t = gates.shape[0]
    topk_w, topk_e = lax.top_k(gates, top_k)
    # Renormalize selected weights.
    topk_w = topk_w / jnp.maximum(topk_w.sum(-1, keepdims=True), 1e-9)
    dispatch = jnp.zeros((t, n_experts, capacity), bool)
    combine = jnp.zeros((t, n_experts, capacity), gates.dtype)
    # Fill expert slots choice-by-choice so earlier choices get priority,
    # mirroring the reference MoE implementations' greedy capacity rule.
    used = jnp.zeros((n_experts,), jnp.int32)
    for j in range(top_k):
        e = topk_e[:, j]
        onehot = jax.nn.one_hot(e, n_experts, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - 1) + used[None, :]
        pos_t = (pos * onehot).sum(-1)
        keep = pos_t < capacity
        slot = jax.nn.one_hot(pos_t, capacity, dtype=jnp.bool_)
        d_j = (onehot.astype(bool)[:, :, None] & slot[:, None, :]
               & keep[:, None, None])
        dispatch = dispatch | d_j
        combine = combine + d_j.astype(combine.dtype) * \
            topk_w[:, j][:, None, None]
        used = used + onehot.sum(0)
    return dispatch, combine


def moe_ffn(params, x, cfg: MoeConfig, axis_name: Optional[str] = "ep"):
    """Top-k routed swiglu FFN with expert parallelism.

    ``x: [T, d]`` per shard.  When ``axis_name`` is None (or ep=1) the
    all-to-alls drop out and this is a dense-local MoE.
    """
    n_shards = lax.axis_size(axis_name) if axis_name else 1
    t, d = x.shape
    e_total = cfg.n_experts
    e_local = params["w1"].shape[0]
    assert e_local * n_shards == e_total, (e_local, n_shards, e_total)
    capacity = max(1, int(math.ceil(
        t * cfg.top_k * cfg.capacity_factor / e_total)))

    logits = x @ params["router"].astype(x.dtype)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    dispatch, combine = _dispatch_tensors(gates, cfg.top_k, e_total, capacity)

    # [T, E, C] x [T, d] -> [E, C, d]
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)

    if n_shards > 1:
        # [E, C, d] -> [ep, E_local, C, d]; shard i keeps its experts,
        # receiving one [E_local, C, d] slab from every source shard.
        expert_in = expert_in.reshape(n_shards, e_local, capacity, d)
        expert_in = lax.all_to_all(expert_in, axis_name, split_axis=0,
                                   concat_axis=0, tiled=True)
        expert_in = expert_in.reshape(n_shards, e_local, capacity, d)
        # -> [E_local, ep*C, d]: fold source shards into the slot axis.
        expert_in = expert_in.transpose(1, 0, 2, 3).reshape(
            e_local, n_shards * capacity, d)
    else:
        expert_in = expert_in.reshape(e_local, capacity, d)

    # Per-expert swiglu, batched over local experts on the MXU.
    h = jnp.einsum("esd,edf->esf", expert_in, params["w1"].astype(x.dtype))
    g = jnp.einsum("esd,edf->esf", expert_in, params["w3"].astype(x.dtype))
    act = jax.nn.silu(h) * g
    expert_out = jnp.einsum("esf,efd->esd", act,
                            params["w2"].astype(x.dtype))

    if n_shards > 1:
        expert_out = expert_out.reshape(
            e_local, n_shards, capacity, d).transpose(1, 0, 2, 3)
        expert_out = expert_out.reshape(n_shards * e_local, capacity, d)
        expert_out = lax.all_to_all(expert_out, axis_name, split_axis=0,
                                    concat_axis=0, tiled=True)
        expert_out = expert_out.reshape(e_total, capacity, d)
    else:
        expert_out = expert_out.reshape(e_total, capacity, d)

    # Weighted return to token positions: [T, E, C] x [E, C, d] -> [T, d]
    y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), expert_out)
    return y, aux_load_balance_loss(gates, dispatch)


def aux_load_balance_loss(gates, dispatch):
    """Switch-transformer load-balancing auxiliary loss."""
    e = gates.shape[1]
    frac_tokens = dispatch.any(-1).astype(jnp.float32).mean(0)
    frac_gates = gates.mean(0)
    return e * jnp.sum(frac_tokens * frac_gates)
