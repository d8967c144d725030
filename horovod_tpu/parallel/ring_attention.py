"""Ring attention: sequence/context parallelism over the ICI ring.

Beyond-reference extension (SURVEY.md §5: the reference predates
long-context techniques; its only related primitive is the raw alltoall).
On TPU the natural long-sequence design is blockwise ring attention:
each sequence-parallel shard holds a Q block and rotates K/V blocks around
the 'sp' mesh axis with ``collective-permute`` (``lax.ppermute``), which
maps directly onto the physical ICI ring; softmax is accumulated online
(flash-attention style, max/sum carried in f32) so the full [S, S] score
matrix never materializes.

Shapes inside the shard_map body (per shard): q/k/v are
``[batch, seq_local, heads, head_dim]``; output matches q.  GQA is
supported by passing fewer KV heads (they are repeated locally).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _block_attn(q, k, v, mask, m_prev, l_prev, acc):
    """One flash-attention accumulation step for a KV block.

    q: [B, Sq, H, D]; k/v: [B, Skv, H, D]; mask broadcastable to
    [B, H, Sq, Skv]; carries in f32.
    """
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    # Guard fully-masked rows: keep exp argument finite.
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(jnp.where(m_prev <= NEG_INF / 2, NEG_INF, m_prev) - m_safe)
    corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, corr)
    l_new = l_prev * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, acc_new


def _repeat_kv(kv, n_rep: int):
    if n_rep == 1:
        return kv
    b, s, h, d = kv.shape
    return jnp.repeat(kv, n_rep, axis=2)


def pvary_missing(v, axes):
    """Mark ``v`` varying over any of ``axes`` it is not already
    varying over (vma tracking for check_vma=True shard_maps).  Loop
    carries must enter with the varying-axes superset their outputs
    acquire."""
    have = jax.typeof(v).vma
    missing = tuple(a for a in axes if a not in have)
    if not missing:
        return v
    return lax.pcast(v, missing, to="varying")


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = True,
                   query_offset=None, kv_offset=None):
    """Blockwise ring attention inside a shard_map over ``axis_name``.

    Each shard computes attention of its local Q block against every KV
    block; KV blocks travel around the ring, one ppermute per step, so
    communication overlaps the block computation XLA schedules between
    permutes.  Causal masking uses *global* positions derived from the
    shard index (or explicit ``query_offset``/``kv_offset`` arrays).
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, s_q, h, d = q.shape
    n_rep = h // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    s_kv = k.shape[1]

    if query_offset is None:
        query_offset = idx * s_q
    q_pos = query_offset + jnp.arange(s_q)

    m0 = jnp.full((b, h, s_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_q), jnp.float32)
    acc0 = jnp.zeros((b, s_q, h, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(t, carry):
        k_cur, v_cur, m, l, acc = carry
        # After t rotations shard ``idx`` holds the KV block that started
        # on shard (idx - t) mod n.
        src = (idx - t) % n
        base = kv_offset if kv_offset is not None else src * s_kv
        kv_pos = base + jnp.arange(s_kv) if kv_offset is None else \
            base + jnp.arange(s_kv)
        if causal:
            mask = kv_pos[None, None, None, :] <= q_pos[None, None, :, None]
        else:
            mask = jnp.ones((1, 1, s_q, s_kv), bool)
        m, l, acc = _block_attn(q, k_cur, v_cur, mask, m, l, acc)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, m, l, acc

    vma = tuple(jax.typeof(q).vma)
    init = tuple(pvary_missing(c, vma) for c in
                 (k, v, m0, l0, acc0))
    _, _, m, l, acc = lax.fori_loop(0, n, body, init)
    l_t = l.transpose(0, 2, 1)[..., None]
    out = acc / jnp.maximum(l_t, 1e-30)
    return out.astype(q.dtype)


def local_attention(q, k, v, causal: bool = True, window=None):
    """Single-shard reference attention (same math, no ring) — used by the
    dense model when sp=1 and by tests as the ground truth.  ``window``: a
    causal query sees its last ``window`` keys, itself among them."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    n_rep = q.shape[2] // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        s_q, s_kv = q.shape[1], k.shape[1]
        mask = jnp.arange(s_kv)[None, :] <= jnp.arange(s_q)[:, None]
        if window is not None:
            mask &= jnp.arange(s_kv)[None, :] \
                > jnp.arange(s_q)[:, None] - window
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
