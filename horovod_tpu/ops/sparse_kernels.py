"""Pallas TPU kernels of learned sparse attention: a lightning indexer
scores every causal key of a query, the query keeps the ``top_k`` keys of
the largest score (exactly: a tie goes to the lower position), and softmax
attention runs over the kept keys alone, with the indexer's own loss (the
KL divergence of its softmax over the kept keys from the attention's
probabilities there) beside it.

The indexer (DeepSeek-V3.2's), for ``HI`` heads of ``DI`` over one key:

    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])        s <= t

``select`` finds, for every query, the ``min(top_k, t + 1)``-th largest
score by a bisection over the bits of a 32-bit key whose integer order is
the scores' (``order_key``; -0 is taken as +0), then the position of the
last tie that still fits by a bisection over the positions.  It holds a
block of queries' scores over the whole causal prefix in VMEM while it
does, and hands every later kernel the choice as one bit a (query, key)
pair (``[B, S / 32, S]`` int32: in the words of query block ``j`` (rows
``j r .. j r + r - 1``, ``r = block / 32``), bit ``b`` of row ``j r + i``,
column ``s`` is query ``j block + b r + i``'s choice of key ``s``), and the
log-sum-exp of the scores it kept.

The attention kernels are the flash kernels' online softmax on the same
causal schedule of block pairs (``pallas_kernels._block_schedule``), every
query head of the layer in one grid step so that a pair's bits are read
once: the selection is scattered over nearly every block (an eighth of the
causal keys at 32768 positions), so a pair is computed whole under the mask
of its bits, and no block is skipped.  Forward: output and row statistics.
``kl``: the indexer's loss a query, from the attention's row statistics
(``p``, the heads' probabilities summed and divided by their number, is
never written).  Backward, one kernel a sweep: by query block dq and the
indexer's query and weight gradients, by key block dk, dv and the indexer's
key gradient; each builds ``p`` again from the scores it recomputes anyway.
The attention's gradient reaches only q, k and v, the indexer's loss's only
the indexer's inputs: the choice is a step function, and ``p`` is the
target.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ..common import scopes
from ..common.device import on_tpu
from .pallas_kernels import (_FIRST, _LAST, _NEG_INF, _block_schedule,
                             _scheduled_pair, _sds)

# Rows and columns of an attention kernel's block pair, and the rows of the
# choice's kernel: it packs ``PACK / 32`` rows in a word, a sublane tile of
# them at 256, and holds their scores over the whole causal prefix.
BLOCK = 256
PACK = 256
_INT_MIN = np.int32(-2 ** 31)
_VMEM_ROOM = 16 << 20
# ``checkpoint_name``s of the choice: a recomputed layer keeps them (the
# bits are 128 MiB a layer at 32768 positions) and does not choose again.
SAVED = ("sparse_mask", "sparse_lse_i")


def plan(seq: int, block=None):
    """(attention block, choice block) of a call over ``seq`` positions."""
    block = block or min(BLOCK, seq)
    pack = min(PACK, block)
    if seq % block or block % pack or pack % 32:
        raise ValueError("sparse attention runs %d positions in blocks of "
                         "%d: a block is 32 rows or a multiple and divides "
                         "the sequence" % (seq, block))
    return block, pack


def order_key(x):
    """float32 -> int32 whose signed order is the floats' (-0 taken as +0)."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    bits = jnp.where(x == 0, 0, bits)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def from_key(key):
    """The inverse of ``order_key``."""
    bits = jnp.where(key < 0, key ^ jnp.int32(0x7FFFFFFF), key)
    return lax.bitcast_convert_type(bits, jnp.float32)


def _dot_t(a, b):
    """``a b^T`` in float32."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _t_dot(a, b):
    """``a^T b`` in float32."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def index_terms(qi, ki, heads_i: int):
    """The indexer's per-head products ``qI[:, j] . kI`` in float32, a head
    after another (``qi(j)`` gives head ``j``'s ``[rows, DI]``)."""
    return [_dot_t(qi(j), ki) for j in range(heads_i)]


def index_scores(terms, w):
    """``sum_j w(j) relu(terms[j])`` summed from head 0 up: the order every
    form and the plain reference keep, so that equal inputs give equal
    scores to the bit.  relu has no slope at 0, as the kernels' gradient."""
    out = None
    for j, r in enumerate(terms):
        term = jnp.where(r > 0, r, 0.0) * w(j)
        out = term if out is None else out + term
    return out


def _pack(sel):
    """``[block, n]`` bool -> ``[block / 32, n]`` int32 words."""
    r = sel.shape[0] // 32
    words = jnp.zeros((r, sel.shape[1]), jnp.int32)
    for b in range(32):
        words = words | (sel[b * r:(b + 1) * r].astype(jnp.int32) << b)
    return words


def _unpack(words, r):
    """The inverse of ``_pack``, a choice block (``r`` rows of words) after
    another."""
    return jnp.concatenate(
        [(words[g:g + r] >> b) & 1
         for g in range(0, words.shape[0], r) for b in range(32)], 0) == 1


def _tables(pairs):
    flags = _FIRST * pairs.first + _LAST * pairs.last
    return tuple(jnp.asarray(t, jnp.int32) for t in (pairs.q, pairs.k, flags))


def _spec(shape, which):
    """A BlockSpec of ``shape`` (batch first, the sequence next to last)
    whose sequence block is the pair's query block (``which`` 0) or its key
    block (1)."""
    def index(b, step, *tables):
        at = [b] + [0] * (len(shape) - 1)
        at[-2] = tables[which][step]
        return tuple(at)
    return pl.BlockSpec(shape, index)


def _words_spec(r, block):
    return pl.BlockSpec((1, r, block),
                        lambda b, step, q, k, f: (b, q[step], k[step]))


def _params(interpret, nbytes):
    from jax.experimental.pallas import tpu as pltpu
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=int(nbytes) + _VMEM_ROOM)


def _bytes(*shapes_dtypes):
    """VMEM of ``(shape, dtype, times)`` entries (a block counts twice, the
    pipeline's two buffers), lanes and sublanes rounded up to the tiles."""
    total = 0
    for shape, dtype, times in shapes_dtypes:
        shape = list(shape)
        shape[-1] = -(-shape[-1] // 128) * 128
        if len(shape) > 1:
            shape[-2] = -(-shape[-2] // 8) * 8
        total += times * int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    return total


# -- the choice ---------------------------------------------------------------

def _select_kernel(q_blocks, k_blocks, flags, qi_ref, ki_ref, w_ref,
                   mask_ref, lse_ref, keys_scr, *, block, heads_i, top_k,
                   seq):
    j, kb, first, last, _ = _scheduled_pair(q_blocks, k_blocks, flags)
    shape = (block, block)
    scores = index_scores(
        index_terms(lambda h: qi_ref[0, h], ki_ref[0], heads_i),
        lambda h: w_ref[0, h])
    rows = j * block + lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = kb * block + lax.broadcasted_iota(jnp.int32, shape, 1)
    keys_scr[:, pl.ds(pl.multiple_of(kb * block, block), block)] = \
        jnp.where(cols <= rows, order_key(scores), _INT_MIN)

    @pl.when(last)
    def _choose():
        chunks = j + 1
        t = j * block + lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        need = jnp.minimum(t + 1, top_k)

        def count(pred):
            def body(c, acc):
                at = pl.multiple_of(c * block, block)
                kc = keys_scr[:, pl.ds(at, block)]
                col = at + lax.broadcasted_iota(jnp.int32, shape, 1)
                return acc + jnp.sum(pred(kc, col).astype(jnp.int32), 1,
                                     keepdims=True)
            return lax.fori_loop(0, chunks, body,
                                 jnp.zeros((block, 1), jnp.int32))

        # The largest key with at least ``need`` keys at or above it, a bit
        # at a time from the top (in the unsigned order of ``key ^ 2^31``,
        # which is the signed order shifted: adding a bit never carries).
        tau = jnp.full((block, 1), _INT_MIN, jnp.int32)
        for bit in range(31, -1, -1):
            cand = tau + np.int32(np.uint32(1 << bit).view(np.int32))
            tau = jnp.where(count(lambda kc, col: kc >= cand) >= need,
                            cand, tau)
        # Of the keys equal to it, the first ``need - above`` by position:
        # the largest p with fewer of them before it is the last one taken.
        want = need - count(lambda kc, col: kc > tau)
        pos = jnp.zeros((block, 1), jnp.int32)
        for bit in range(max(seq - 1, 1).bit_length() - 1, -1, -1):
            cand = pos + (1 << bit)
            fewer = count(lambda kc, col: (kc == tau) & (col < cand)) < want
            pos = jnp.where(fewer, cand, pos)

        kmax = lax.fori_loop(
            0, chunks, lambda c, m: jnp.maximum(m, jnp.max(
                keys_scr[:, pl.ds(pl.multiple_of(c * block, block), block)],
                1, keepdims=True)),
            jnp.full((block, 1), _INT_MIN, jnp.int32))
        top = from_key(kmax)

        def write(c, total):
            at = pl.multiple_of(c * block, block)
            kc = keys_scr[:, pl.ds(at, block)]
            col = at + lax.broadcasted_iota(jnp.int32, shape, 1)
            sel = (kc > tau) | ((kc == tau) & (col <= pos))
            mask_ref[0, :, pl.ds(at, block)] = _pack(sel)
            return total + jnp.sum(
                jnp.where(sel, jnp.exp(from_key(kc) - top), 0.0), 1,
                keepdims=True)

        total = lax.fori_loop(0, chunks, write,
                              jnp.zeros((block, 1), jnp.float32))

        def clear(c, _):
            at = pl.multiple_of(c * block, block)
            mask_ref[0, :, pl.ds(at, block)] = jnp.zeros(
                (block // 32, block), jnp.int32)
            return 0

        lax.fori_loop(chunks, seq // block, clear, 0)
        lse_ref[0] = top + jnp.log(total)


def select(qi, ki, w, top_k: int, block=None, interpret=None):
    """The choice of every query: ``qi`` ``[B, HI, S, DI]``, ``ki`` ``[B, S,
    DI]``, ``w`` ``[B, HI, S, 1]`` float32 -> (the bits ``[B, S / 32, S]``
    int32, the log-sum-exp of the kept scores ``[B, S, 1]`` float32)."""
    from jax.experimental.pallas import tpu as pltpu
    b, heads_i, seq, di = qi.shape
    block = plan(seq, block)[1]
    interpret = not on_tpu() if interpret is None else interpret
    pairs = _block_schedule(seq, block, block, True)[0]
    r = block // 32
    kernel = functools.partial(_select_kernel, block=block, heads_i=heads_i,
                               top_k=top_k, seq=seq)
    vmem = _bytes(((heads_i, block, di), qi.dtype, 2),
                  ((block, di), ki.dtype, 2), ((heads_i, block, 1),
                                               jnp.float32, 2),
                  ((r, seq), jnp.int32, 2), ((block, 1), jnp.float32, 2),
                  ((block, seq), jnp.int32, 1), ((block, block),
                                                 jnp.float32, 8))
    with jax.named_scope(scopes.INDEXER_TOPK):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(b, len(pairs.q)),
                in_specs=[_spec((1, heads_i, block, di), 0),
                          _spec((1, block, di), 1),
                          _spec((1, heads_i, block, 1), 0)],
                out_specs=[pl.BlockSpec((1, r, seq),
                                        lambda b_, s, q, k, f: (b_, q[s], 0)),
                           pl.BlockSpec((1, block, 1),
                                        lambda b_, s, q, k, f: (b_, q[s], 0))],
                scratch_shapes=[pltpu.VMEM((block, seq), jnp.int32)]),
            out_shape=[_sds((b, seq // 32, seq), jnp.int32, qi),
                       _sds((b, seq, 1), jnp.float32, qi)],
            compiler_params=_params(interpret, vmem),
            interpret=interpret,
            name=scopes.kernel_name(scopes.INDEXER_TOPK),
        )(*_tables(pairs), qi, ki, w)


# -- attention over the choice ------------------------------------------------

def _each_head(heads, body):
    """``body(h, carry)`` for every query head, the loop unrolled whole: on
    the v5e at the 32768-position cell's shape the forward kernel took 239
    -> 95 ms a layer, the indexer loss's 78 -> 41 and the two backward 489
    -> 297 against a rolled loop (PERF.md, section 6)."""
    lax.fori_loop(0, heads, body, 0, unroll=True)


def _bias(mask_ref, bias_scr, r):
    bias_scr[...] = jnp.where(_unpack(mask_ref[0], r), 0.0, _NEG_INF)


def _fwd_kernel(q_blocks, k_blocks, flags, q_ref, k_ref, v_ref, mask_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr, bias_scr, *, heads,
                group, r):
    _, _, first, last, _ = _scheduled_pair(q_blocks, k_blocks, flags)

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    _bias(mask_ref, bias_scr, r)

    def head(h, carry):
        g = h // group
        # A row none of whose keys in this pair is kept reads exp(0) here
        # while no kept key came before; its first kept key's correction
        # exp(-1e30 - m) wipes that out, and every row keeps one.
        s = _dot_t(q_ref[0, h], k_ref[0, g]) + bias_scr[...]
        m_prev = m_scr[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, 1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_scr[h] = m_new
        l_scr[h] = l_scr[h] * corr + jnp.sum(p, 1, keepdims=True)
        acc_scr[h] = acc_scr[h] * corr + _dot(p.astype(v_ref.dtype),
                                              v_ref[0, g])
        return carry

    _each_head(heads, head)

    @pl.when(last)
    def _finish():
        def out(h, carry):
            o_ref[0, h] = (acc_scr[h] / l_scr[h]).astype(o_ref.dtype)
            lse_ref[0, h] = m_scr[h] + jnp.log(l_scr[h])
            return carry
        _each_head(heads, out)


def _kl_kernel(q_blocks, k_blocks, flags, q_ref, k_ref, lse_ref, qi_ref,
               ki_ref, w_ref, mask_ref, lsei_ref, kl_ref, ent_scr, cross_scr,
               mass_scr, bias_scr, p_scr, *, heads, group, heads_i, r):
    _, _, first, last, _ = _scheduled_pair(q_blocks, k_blocks, flags)

    @pl.when(first)
    def _init():
        ent_scr[...] = jnp.zeros_like(ent_scr)
        cross_scr[...] = jnp.zeros_like(cross_scr)
        mass_scr[...] = jnp.zeros_like(mass_scr)

    _bias(mask_ref, bias_scr, r)
    _heads_p(q_ref, k_ref, lse_ref, bias_scr, p_scr, heads, group)
    p = p_scr[...] * (1.0 / heads)
    scores = index_scores(
        index_terms(lambda h: qi_ref[0, h], ki_ref[0], heads_i),
        lambda h: w_ref[0, h])
    ent_scr[...] += jnp.sum(
        jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-37)), 0.0), 1,
        keepdims=True)
    cross_scr[...] += jnp.sum(p * scores, 1, keepdims=True)
    mass_scr[...] += jnp.sum(p, 1, keepdims=True)

    @pl.when(last)
    def _finish():
        kl_ref[0] = ent_scr[...] - cross_scr[...] \
            + lsei_ref[0] * mass_scr[...]


def _heads_p(q_ref, k_ref, lse_ref, bias_scr, p_scr, heads, group,
             each=None):
    """``p_scr`` = the kept probabilities of the pair summed over the
    heads; ``each(h, g, probs)`` also sees every head's."""
    p_scr[...] = jnp.zeros_like(p_scr)

    def head(h, carry):
        g = h // group
        probs = jnp.exp(_dot_t(q_ref[0, h], k_ref[0, g]) + bias_scr[...]
                        - lse_ref[0, h])
        p_scr[...] += probs
        if each is not None:
            each(h, g, probs)
        return carry

    _each_head(heads, head)


def _indexer_grad(qi_ref, ki_ref, w_ref, lsei_ref, gkl_ref, bias_scr, p_scr,
                  heads, heads_i, each):
    """The gradient of the indexer's loss at its scores, ``gkl (softmax(I)
    - p)`` over the kept keys, and ``each(j, r_j, dr_j)`` for every head of
    the indexer (``r_j = qI_j . kI``, ``dr_j`` its gradient)."""
    terms = index_terms(lambda h: qi_ref[0, h], ki_ref[0], heads_i)
    scores = index_scores(terms, lambda h: w_ref[0, h])
    d_scores = gkl_ref[0] * (
        jnp.exp(scores + bias_scr[...] - lsei_ref[0])
        - p_scr[...] * (1.0 / heads))
    for j, r in enumerate(terms):
        each(j, r, jnp.where(r > 0, d_scores * w_ref[0, j], 0.0), d_scores)


def _dq_kernel(q_blocks, k_blocks, flags, q_ref, k_ref, v_ref, do_ref,
               lse_ref, delta_ref, qi_ref, ki_ref, w_ref, mask_ref, lsei_ref,
               gkl_ref, dq_ref, dqi_ref, dw_ref, dq_scr, dqi_scr, dw_scr,
               bias_scr, p_scr, *, heads, group, heads_i, r):
    _, _, first, last, _ = _scheduled_pair(q_blocks, k_blocks, flags)

    @pl.when(first)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        dqi_scr[...] = jnp.zeros_like(dqi_scr)
        dw_scr[...] = jnp.zeros_like(dw_scr)

    _bias(mask_ref, bias_scr, r)

    def each(h, g, probs):
        dp = _dot_t(do_ref[0, h], v_ref[0, g])
        ds = probs * (dp - delta_ref[0, h])
        dq_scr[h] += _dot(ds.astype(k_ref.dtype), k_ref[0, g])

    _heads_p(q_ref, k_ref, lse_ref, bias_scr, p_scr, heads, group, each)

    def index_head(j, r, dr, d_scores):
        dw_scr[j] += jnp.sum(d_scores * jnp.where(r > 0, r, 0.0), 1,
                             keepdims=True)
        dqi_scr[j] += _dot(dr.astype(ki_ref.dtype), ki_ref[0])

    _indexer_grad(qi_ref, ki_ref, w_ref, lsei_ref, gkl_ref, bias_scr, p_scr,
                  heads, heads_i, index_head)

    @pl.when(last)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)
        dqi_ref[0] = dqi_scr[...].astype(dqi_ref.dtype)
        dw_ref[0] = dw_scr[...]


def _dkv_kernel(q_blocks, k_blocks, flags, q_ref, k_ref, v_ref, do_ref,
                lse_ref, delta_ref, qi_ref, ki_ref, w_ref, mask_ref,
                lsei_ref, gkl_ref, dk_ref, dv_ref, dki_ref, dk_scr, dv_scr,
                dki_scr, bias_scr, p_scr, *, heads, group, heads_i, r):
    _, _, first, last, _ = _scheduled_pair(q_blocks, k_blocks, flags)

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        dki_scr[...] = jnp.zeros_like(dki_scr)

    _bias(mask_ref, bias_scr, r)

    def each(h, g, probs):
        dv_scr[g] += _t_dot(probs.astype(do_ref.dtype), do_ref[0, h])
        dp = _dot_t(do_ref[0, h], v_ref[0, g])
        ds = probs * (dp - delta_ref[0, h])
        # q arrives scaled: ds^T q is dk whole.
        dk_scr[g] += _t_dot(ds.astype(q_ref.dtype), q_ref[0, h])

    _heads_p(q_ref, k_ref, lse_ref, bias_scr, p_scr, heads, group, each)

    def index_head(j, r, dr, d_scores):
        dki_scr[...] += _t_dot(dr.astype(qi_ref.dtype), qi_ref[0, j])

    _indexer_grad(qi_ref, ki_ref, w_ref, lsei_ref, gkl_ref, bias_scr, p_scr,
                  heads, heads_i, index_head)

    @pl.when(last)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)
        dki_ref[0] = dki_scr[...].astype(dki_ref.dtype)


def _grid(kernel, pairs, ins, in_specs, outs, out_specs, scratch, name,
          interpret, vmem):
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(ins[0].shape[0], len(pairs.q)),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=outs, compiler_params=_params(interpret, vmem),
        interpret=interpret, name=name)(*_tables(pairs), *ins)


class _Shape:
    """Sizes of a call and the BlockSpecs its kernels share."""

    def __init__(self, q, k, qi, block, by):
        self.b, self.heads, self.seq, self.d = q.shape
        self.kv = k.shape[1]
        self.heads_i, self.di = (0, 0) if qi is None else qi.shape[1:4:2]
        self.group = self.heads // self.kv
        self.block = block
        self.q = _spec((1, self.heads, block, self.d), 0)
        self.rows = _spec((1, self.heads, block, 1), 0)
        self.k = _spec((1, self.kv, block, self.d), 1)
        self.qi = _spec((1, self.heads_i, block, self.di), 0)
        self.w = _spec((1, self.heads_i, block, 1), 0)
        self.ki = _spec((1, block, self.di), 1)
        self.row = _spec((1, block, 1), 0)
        self.words = _words_spec(block // 32, block)
        # ``by`` 0: a row of the sweep is a query block, 1: a key block.
        self.pairs = _block_schedule(self.seq, block, block, True)[by]

    def scratch(self, *shapes):
        from jax.experimental.pallas import tpu as pltpu
        return [pltpu.VMEM(s, jnp.float32) for s in shapes]


def _forward(q, k, v, mask, blocks, interpret):
    block, pack = blocks
    sh = _Shape(q, k, None, block, 0)
    h, bl, d = sh.heads, block, sh.d
    kernel = functools.partial(_fwd_kernel, heads=h, group=sh.group,
                               r=pack // 32)
    vmem = _bytes(((h, bl, d), q.dtype, 4), ((sh.kv, bl, d), k.dtype, 4),
                  ((h, bl, 1), jnp.float32, 4), ((h, bl, d), jnp.float32, 1),
                  ((bl, bl), jnp.float32, 8))
    with jax.named_scope(scopes.SPARSE_FLASH_FWD):
        return _grid(kernel, sh.pairs, (q, k, v, mask),
                     [sh.q, sh.k, sh.k, sh.words],
                     [_sds(q.shape, q.dtype, q),
                      _sds((sh.b, h, sh.seq, 1), jnp.float32, q)],
                     [sh.q, sh.rows],
                     sh.scratch((h, bl, 1), (h, bl, 1), (h, bl, d),
                                (bl, bl)),
                     scopes.kernel_name(scopes.SPARSE_FLASH_FWD), interpret,
                     vmem)


def _kl(q, k, lse, qi, ki, w, mask, lse_i, blocks, interpret):
    block, pack = blocks
    sh = _Shape(q, k, qi, block, 0)
    h, bl, d = sh.heads, block, sh.d
    kernel = functools.partial(_kl_kernel, heads=h, group=sh.group,
                               heads_i=sh.heads_i, r=pack // 32)
    vmem = _bytes(((h, bl, d), q.dtype, 2), ((sh.kv, bl, d), k.dtype, 2),
                  ((h, bl, 1), jnp.float32, 2),
                  ((sh.heads_i, bl, sh.di), qi.dtype, 2),
                  ((sh.heads_i, bl, 1), jnp.float32, 2),
                  ((bl, bl), jnp.float32, 2 + 2 * sh.heads_i + 8))
    with jax.named_scope(scopes.INDEXER):
        return _grid(kernel, sh.pairs, (q, k, lse, qi, ki, w, mask, lse_i),
                     [sh.q, sh.k, sh.rows, sh.qi, sh.ki, sh.w, sh.words,
                      sh.row],
                     _sds((sh.b, sh.seq, 1), jnp.float32, q), sh.row,
                     sh.scratch((bl, 1), (bl, 1), (bl, 1), (bl, bl),
                                (bl, bl)),
                     scopes.kernel_name(scopes.INDEXER) + "_kl", interpret,
                     vmem)


def _backward(q, k, v, do, lse, delta, qi, ki, w, mask, lse_i, gkl, blocks,
              interpret):
    block, pack = blocks
    ins = (q, k, v, do, lse, delta, qi, ki, w, mask, lse_i, gkl)
    by_q = _Shape(q, k, qi, block, 0)
    h, kv, bl, d = by_q.heads, by_q.kv, block, by_q.d
    hi, di = by_q.heads_i, by_q.di
    vmem = _bytes(((h, bl, d), q.dtype, 8), ((kv, bl, d), k.dtype, 8),
                  ((h, bl, 1), jnp.float32, 4), ((hi, bl, di), qi.dtype, 4),
                  ((hi, bl, 1), jnp.float32, 4), ((h, bl, d), jnp.float32, 1),
                  ((hi, bl, di), jnp.float32, 1),
                  ((bl, bl), jnp.float32, 4 + 2 * hi + 8))
    with jax.named_scope(scopes.SPARSE_FLASH_BWD):
        dq, dqi, dw = _grid(
            functools.partial(_dq_kernel, heads=h, group=by_q.group,
                              heads_i=hi, r=pack // 32),
            by_q.pairs, ins,
            [by_q.q, by_q.k, by_q.k, by_q.q, by_q.rows, by_q.rows, by_q.qi,
             by_q.ki, by_q.w, by_q.words, by_q.row, by_q.row],
            [_sds(q.shape, q.dtype, q), _sds(qi.shape, qi.dtype, qi),
             _sds(w.shape, jnp.float32, w)],
            [by_q.q, by_q.qi, by_q.w],
            by_q.scratch((h, bl, d), (hi, bl, di), (hi, bl, 1), (bl, bl),
                         (bl, bl)),
            scopes.kernel_name(scopes.SPARSE_FLASH_BWD) + "_dq", interpret,
            vmem)
        by_k = _Shape(q, k, qi, block, 1)
        dk, dv, dki = _grid(
            functools.partial(_dkv_kernel, heads=h, group=by_k.group,
                              heads_i=hi, r=pack // 32),
            by_k.pairs, ins,
            [by_k.q, by_k.k, by_k.k, by_k.q, by_k.rows, by_k.rows, by_k.qi,
             by_k.ki, by_k.w, by_k.words, by_k.row, by_k.row],
            [_sds(k.shape, k.dtype, k), _sds(v.shape, v.dtype, v),
             _sds(ki.shape, ki.dtype, ki)],
            [by_k.k, by_k.k, by_k.ki],
            by_k.scratch((kv, bl, d), (kv, bl, d), (bl, di), (bl, bl),
                         (bl, bl)),
            scopes.kernel_name(scopes.SPARSE_FLASH_BWD) + "_dkv", interpret,
            vmem)
    return dq, dk, dv, dqi, dki, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _core(q, k, v, qi, ki, w, mask, lse_i, blocks, interpret):
    return _core_fwd(q, k, v, qi, ki, w, mask, lse_i, blocks, interpret)[0]


def _core_fwd(q, k, v, qi, ki, w, mask, lse_i, blocks, interpret):
    o, lse = _forward(q, k, v, mask, blocks, interpret)
    kl = _kl(q, k, lse, qi, ki, w, mask, lse_i, blocks, interpret)
    return (o, kl), (q, k, v, qi, ki, w, mask, lse_i, o, lse)


def _core_bwd(blocks, interpret, res, cts):
    q, k, v, qi, ki, w, mask, lse_i, o, lse = res
    do, gkl = cts
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1,
                    keepdims=True)
    dq, dk, dv, dqi, dki, dw = _backward(
        q, k, v, do.astype(q.dtype), lse, delta, qi, ki, w, mask, lse_i,
        gkl.astype(jnp.float32), blocks, interpret)
    return dq, dk, dv, dqi, dki, dw.astype(w.dtype), None, None


_core.defvjp(_core_fwd, _core_bwd)


def sparse_attention(q, k, v, qi, ki, w, top_k: int, block=None,
                     interpret=None):
    """Causal attention of every query over its ``top_k`` keys of the
    largest indexer score, and the indexer's loss a query.

    ``q`` ``[B, S, H, D]``, ``k``, ``v`` ``[B, S, G, D]`` (a key/value head
    for ``H / G`` query heads), ``qi`` ``[B, S, HI, DI]``, ``ki`` ``[B, S,
    DI]``, ``w`` ``[B, S, HI]`` (the indexer's weights, already scaled).
    Returns (``[B, S, H, D]``, ``[B, S]`` float32): the attention's output
    (scores over ``sqrt(D)``) and ``KL(p || softmax(I over the kept keys))``
    a query, where ``p`` is the heads' probabilities over the kept keys
    summed and divided by ``H``.  The choice and ``p`` take no gradient;
    the output's reaches q, k and v, the loss's qi, ki and w."""
    interpret = not on_tpu() if interpret is None else interpret
    b, seq, heads, d = q.shape
    blocks = plan(seq, block)

    def heads_first(x):
        return jnp.swapaxes(x, 1, 2)

    qi_h, ki_, w_h = heads_first(qi), ki, heads_first(w)[..., None]
    with jax.named_scope(scopes.INDEXER):
        mask, lse_i = select(*(lax.stop_gradient(x)
                               for x in (qi_h, ki_, w_h)),
                             top_k, blocks[1], interpret)
    mask, lse_i = (checkpoint_name(x, name)
                   for x, name in zip((mask, lse_i), SAVED))
    o, kl = _core(heads_first(q * (1.0 / math.sqrt(d))).astype(q.dtype),
                  heads_first(k), heads_first(v), qi_h, ki_,
                  w_h.astype(jnp.float32), mask, lse_i, blocks, interpret)
    return heads_first(o), kl[..., 0]


# -- the same mathematics in XLA ----------------------------------------------

def choose_xla(scores, top_k: int):
    """``[.., S, S]`` float32 scores -> the kept (query, key) pairs as
    bool: a query's ``min(top_k, t + 1)`` causal keys of the largest score,
    a tie to the lower position (``lax.top_k``'s order), -0 as +0."""
    seq = scores.shape[-1]
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(scores == 0, 0.0, scores)
    _, ids = lax.top_k(jnp.where(causal, scores, -jnp.inf), min(top_k, seq))
    kept = jnp.any(ids[..., None] == jnp.arange(seq), -2)
    return kept & causal


def sparse_attention_xla(q, k, v, qi, ki, w, top_k: int):
    """``sparse_attention`` with every score in HBM: the model's form where
    the kernels do not run (the CPU), from the same equations in the same
    order."""
    b, seq, heads, d = q.shape
    group = heads // k.shape[2]
    f32 = jnp.float32
    with jax.named_scope(scopes.INDEXER):
        terms = [jnp.einsum("bti,bsi->bts", qi[:, :, j], ki,
                            preferred_element_type=f32)
                 for j in range(qi.shape[2])]
        scores = index_scores(terms, lambda j: w[:, :, j, None].astype(f32))
        with jax.named_scope(scopes.INDEXER_TOPK):
            kept = choose_xla(lax.stop_gradient(scores), top_k)
    kk, vv = (jnp.repeat(x, group, axis=2) for x in (k, v))
    logits = jnp.einsum("bthd,bshd->bhts", q * (1.0 / math.sqrt(d)), kk,
                        preferred_element_type=f32)
    probs = jax.nn.softmax(jnp.where(kept[:, None], logits, -jnp.inf), -1)
    o = jnp.einsum("bhts,bshd->bthd", probs.astype(v.dtype), vv,
                   preferred_element_type=f32).astype(q.dtype)
    with jax.named_scope(scopes.INDEXER):
        p = lax.stop_gradient(probs.mean(1))
        log_q = jax.nn.log_softmax(jnp.where(kept, scores, -jnp.inf), -1)
        held = kept & (p > 0)
        kl = jnp.sum(
            jnp.where(held, p * jnp.log(jnp.where(held, p, 1.0)), 0.0)
            - jnp.where(kept, p * jnp.where(kept, log_q, 0.0), 0.0), -1)
    return o, kl
