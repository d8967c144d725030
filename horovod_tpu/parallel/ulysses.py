"""Ulysses-style sequence parallelism: all-to-all head/sequence exchange.

The alltoall-based alternative to ring attention (DeepSpeed-Ulysses
pattern; SURVEY.md §2.5 notes the reference's ``hvd.alltoall`` is exactly
the primitive this strategy needs — here it becomes XLA ``all-to-all``
over the 'sp' axis).  Layout A (sequence-sharded, heads full) is what the
rest of the transformer uses; attention wants layout B (heads sharded,
sequence full).  Two all-to-alls bracket any attention kernel:

    A: [B, S/n, H, D]  --seq_to_heads-->  B: [B, S, H/n, D]
    B                  --heads_to_seq-->  A

Works for any attention implementation in between (including a Pallas
flash kernel), at the cost of 2 all-to-alls vs ring's n ppermutes.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .ring_attention import local_attention


def seq_to_heads(x, axis_name: str = "sp"):
    """[B, S/n, H, D] -> [B, S, H/n, D] via all-to-all."""
    # Split the head axis across shards, gather the sequence axis.
    return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def heads_to_seq(x, axis_name: str = "sp"):
    """[B, S, H/n, D] -> [B, S/n, H, D] via all-to-all (inverse)."""
    return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_attention(q, k, v, axis_name: str = "sp", causal: bool = True,
                      attn_fn=None):
    """Attention with Ulysses layout exchange inside a shard_map body.

    q/k/v: [B, S/n, H, D] (sequence-sharded).  Requires H divisible by the
    axis size.  ``attn_fn(q, k, v, causal)`` runs with full sequence and
    sharded heads; defaults to the exact local attention.
    """
    n = lax.axis_size(axis_name)
    if q.shape[2] % n or k.shape[2] % n:
        raise ValueError(
            "Ulysses needs heads (%d q / %d kv) divisible by sp=%d"
            % (q.shape[2], k.shape[2], n))
    attn_fn = attn_fn or local_attention
    q_h = seq_to_heads(q, axis_name)
    k_h = seq_to_heads(k, axis_name)
    v_h = seq_to_heads(v, axis_name)
    out_h = attn_fn(q_h, k_h, v_h, causal=causal)
    return heads_to_seq(out_h, axis_name)
