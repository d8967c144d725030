"""Pallas TPU kernels for the chunked gated delta rule
(``models/linear_attention.py`` has the recurrence and the XLA form).

A chunk of ``C`` steps of two heads a grid step (of one where the heads
are odd): a head's state (``Dk x Dv``, kept transposed, ``Z = S^T``, so a
key channel's decay scales a lane) and every chunk-local matrix stay in
VMEM.  Keys (q, k and their decays) are ``Dk`` lanes a head, values ``Dv``;
the heads of a grid step fill whole lane tiles together
(``models/linear_attention.py`` pads with zeros, which is exact, where
they would not: ``padded``).  The kernels take
``kb = beta k`` and ``vb = beta v`` beside ``k``, so ``beta`` itself never
enters them:

    N = tril(kb k^T . decay, -1)       P = tril(q k^T . decay)
    [u | w_k] = (I + N)^-1 [vb | kb e^G]
    w = u - w_k S      o = scale ((q e^G) S + P w)
    S' = Diag(e^{G_C}) S + (k e^{G_C - G})^T w

The decay has one of two forms, and each its own pair of kernels, chosen
by ``g``'s shape: one for every key channel (``g`` ``[B, S, H Dk]`` as the
keys are; ``hvd_kda_fwd`` / ``hvd_kda_bwd``) or one a head (``g`` ``[B, S,
H]``; ``hvd_kda_fwd_head`` / ``hvd_kda_bwd_head``).  Both share the
inverse, the solve, the state's update and the output; ``e^G`` and
``e^{G_C - G}`` scale a row's channels (``[C, Dk]``) or the row
(``[C, 1]``).

**Decayed products with no positive exponent.**  ``decay[r, i] =
exp(G_r - G_i)`` cannot be split into a row's and a column's factor
without overflow, unless the split point lies between ``i`` and ``r``.
With a decay for every channel it differs from channel to channel, and
the chunk is halved again and again: at the level of blocks of
``s`` rows an entry ``(r, i)`` with ``r`` in an odd block and ``i`` in the
even block before it is split at the odd block's first row, so the level
is one matrix product of ``a e^{x}`` against ``b e^{x}`` under a mask, with
``x`` a sum of log-decays (never a difference of running sums) that a
constant 0/1 matrix takes from ``g``; every entry below the diagonal
belongs to exactly one level.  With one decay a head ``decay`` is one
``C x C`` matrix ``D`` for every channel: its exponents, the sums of
``g`` over ``(i, r]``, are one product of ``g`` (masked below the
diagonal) against a 0/1 matrix, and ``[P; N]`` is ONE product of ``[q;
kb]`` against ``k`` times ``[D; D]``.  ``(I + N)^-1`` climbs the levels in
both forms: ``X_2s = X_s - X_s (N . level_s) X_s``, which is the block
formula ``[[a, 0], [c, b]]^-1 = [[a^-1, 0], [-b^-1 c a^-1, b^-1]]`` on
every pair of blocks at once.

The backward kernel walks the segments in reverse; inside one it first
sweeps forward and leaves in VMEM what every chunk starts from and
computes before it meets the state (the state, ``P``, ``(I + N)^-1``,
``u``, ``w_k``, and the decays' exponentials or, with one decay a head,
the decayed ``N``: 14 MiB for two heads of 128 and 16 chunks a segment
with a decay for every channel), then takes the chunks in reverse with
the state's gradient carried in VMEM.  A head's ``D`` and exponentials
are computed again from ``g`` on the way back.  The gradient of a head's
decay a step is the sum, over the entries ``(r, i)`` of ``D`` with ``i <
j <= r``, of ``dP . P + dN . N``, plus the row scales' terms: one more
product against a 0/1 matrix.

Float32 throughout, every product at ``Precision.HIGHEST`` (Mosaic's six
bfloat16 passes); the sums of log-decays, and of their gradients with one
decay a head, take three, exactly (``_sums``, ``_summed``).  On the v5e
most of a chunk's time is those passes and the cuts of their operands;
the ten dependent 64 x 64 products of the inverse are two fifths of the
forward kernel with a decay for every channel (``PERF.md``, PR 28).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from ..common import scopes
from ..common.device import on_tpu
from ..parallel.ring_attention import pvary_missing
from .pallas_kernels import _sds

HI = lax.Precision.HIGHEST
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot(a, b, dims=_NN):
    return lax.dot_general(a, b, (dims, ((), ())), precision=HI,
                           preferred_element_type=jnp.float32)


def _grid(c):
    """Each entry's row and column of a ``[C, C]`` matrix."""
    return (lax.broadcasted_iota(jnp.int32, (c, c), 0),
            lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _eye(c):
    rows, cols = _grid(c)
    return jnp.where(rows == cols, 1.0, 0.0)


def lanes(size: int) -> int:
    """``size`` rounded up to whole lane tiles."""
    return -(-size // 128) * 128


def padded(size: int, heads: int) -> int:
    """What a head's keys or values take in the kernels: ``size`` where the
    heads of a grid step fill whole lane tiles together (two heads of 192
    are three tiles: the kernels slice the second at lane 64), else
    ``size`` padded to whole tiles."""
    return size if _heads_a_step(heads) * size % 128 == 0 else lanes(size)


def takes(key_size: int, value_size: int, chunk: int) -> bool:
    """The shapes the kernels are run for: keys and values that fill more
    than half of the lane tiles they are padded to (128 and 256 whole, 96
    and 192 padded to 128 and 256; 64 or 16 would be mostly padding), a
    chunk that halves down to single rows and fills sublanes."""
    return all(2 * size > lanes(size) for size in (key_size, value_size)) \
        and chunk >= 8 and chunk & (chunk - 1) == 0


@functools.lru_cache(maxsize=None)
def _constants(c: int):
    """``sums`` ``[(L + 2) C, C]``: for each of the ``L = log2 C`` levels
    (blocks of C/2 ... 1 rows) the 0/1 matrix that takes a row's exponent
    from ``g`` (rows of an odd block: the log-decays after the block's
    first row up to the row; rows of an even block: those after the row up
    to the next block's first), then the running sum up to a row and the
    sum after it.  ``masks`` ``[L, 2 C, C]``: each level's entries for
    ``[P; N]`` stacked."""
    r = np.arange(c)
    j = r[None, :]
    sums, masks = [], []
    s = c // 2
    while s >= 1:
        block = r // s
        odd = (block % 2 == 1)[:, None]
        first, following = (block * s)[:, None], ((block + 1) * s)[:, None]
        sums.append(np.where(odd, (j > first) & (j <= r[:, None]),
                             (j > r[:, None]) & (j <= following)))
        level = odd & (block[None, :] == block[:, None] - 1)
        masks.append(np.concatenate([level, level]))
        s //= 2
    sums += [j <= r[:, None], j > r[:, None]]
    return (np.concatenate(sums).astype(jnp.bfloat16),
            np.stack(masks).astype(np.float32))


@functools.lru_cache(maxsize=None)
def _triangles(c: int):
    """``[2, C, C]`` 0/1: the entries below the diagonal, then those
    above it; with one decay a head they take the place of ``sums``."""
    r = np.arange(c)
    return np.stack([r[:, None] > r[None, :],
                     r[:, None] < r[None, :]]).astype(jnp.bfloat16)


def _pieces(x):
    """The three bfloat16 pieces that add up to float32 ``x``."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    low = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, low


def _ones_dot(a, b, dims):
    # DEFAULT said outright: bfloat16 operands, whatever precision a caller
    # sets for the float32 products round the kernel.
    return lax.dot_general(a, b, (dims, ((), ())),
                           precision=lax.Precision.DEFAULT,
                           preferred_element_type=jnp.float32)


def _sums(sums, x, dims=_NN):
    """``sums`` (0 or 1, bfloat16) against float32 ``x`` in three passes,
    no less exact than ``HIGHEST``'s six: ``x`` is cut into the three
    bfloat16 pieces that add up to it and each of their products with a 0
    or a 1 is exact."""
    d = x.shape[1]
    parts = _ones_dot(sums, jnp.concatenate(_pieces(x), axis=1), dims)
    return parts[:, :d] + (parts[:, d:2 * d] + parts[:, 2 * d:])


def _summed(x, ones):
    """``x @ ones`` as ``_sums``, float32 ``x`` on the left."""
    c = x.shape[0]
    parts = _ones_dot(jnp.concatenate(_pieces(x)), ones, _NN)
    return parts[:c] + (parts[c:2 * c] + parts[2 * c:])


def _exponentials(g, sums):
    """``exp`` of every sum of log-decays ``_constants`` lists, ``[(L + 2)
    C, D]``."""
    return jnp.exp(_sums(sums[:], g))


def _decays(e, c):
    """(each level's factors, ``e^G``, ``e^{G_C - G}``), ``[C, D]`` each."""
    levels = e.shape[0] // c - 2
    return ([e[l * c:(l + 1) * c] for l in range(levels)],
            e[levels * c:(levels + 1) * c], e[(levels + 1) * c:])


def _head_decays(g, triangles):
    """One head's decays from its log-decays ``g`` ``[1, C]`` (steps on
    lanes): (``D`` ``[C, C]``, ``exp`` of the sum of ``g`` over ``(i, r]``
    below the diagonal and 0 on and above it; ``e^G``, ``e^{G_C - G}``
    ``[C, 1]``)."""
    rows, cols = _grid(g.shape[1])
    up_to = jnp.where(rows >= cols, g, 0.0)         # [r, j]: g_j, j <= r
    d = jnp.where(rows > cols, jnp.exp(_summed(up_to, triangles[0])), 0.0)
    after = jnp.where(rows < cols, g, 0.0)
    return (d, jnp.exp(jnp.sum(up_to, axis=1, keepdims=True)),
            jnp.exp(jnp.sum(after, axis=1, keepdims=True)))


def _channel_gram(q, k, kb, e_lvl, masks):
    """``[P; N]`` below the diagonal, a level at a time."""
    both = jnp.concatenate([q, kb])
    pn = 0.0
    for l in range(masks.shape[0]):
        pn = pn + masks[l] * _dot(
            both * jnp.concatenate([e_lvl[l], e_lvl[l]]), k * e_lvl[l], _NT)
    return pn


def _head_gram(q, k, kb, d):
    """``[P; N]`` below the diagonal: one product times ``[D; D]``."""
    return jnp.concatenate([d, d]) * _dot(jnp.concatenate([q, kb]), k, _NT)


def _local(q, k, kb, vb, pn, e_in, masks):
    """What a chunk computes before it meets the state, from ``[P; N]``
    below the diagonal: ``P``, ``(I + N)^-1``, ``u``, ``w_k``."""
    c, dv = vb.shape
    levels = masks.shape[0]
    n = pn[c:]
    eye = _eye(c)
    x = eye - masks[levels - 1, c:] * n
    for l in range(levels - 2, -1, -1):
        x = x - _dot(_dot(x, masks[l, c:] * n), x)
    solved = _dot(x, jnp.concatenate([vb, kb * e_in], axis=1))
    # P alone has a diagonal: a step's own key, undecayed.
    p = pn[:c] + eye * jnp.sum(q * k, axis=1, keepdims=True)
    return p, x, solved[:, :dv], solved[:, dv:]


def _advance(z, k, u, w_k, decays):
    """(pseudo-values, state after the chunk) from the state before it."""
    _, e_in, e_out = decays
    w = u - _dot(w_k, z, _NT)
    return w, z * e_in[-1:] + _dot(w, k * e_out, _TN)


def _channel_back(q, k, kb, e_lvl, masks, dpn, dq, dk, dkb):
    """The levels' share of the gradients of q, k, kb, and the gradient of
    each level's exponents."""
    c = q.shape[0]
    both = jnp.concatenate([q, kb])
    dx = []
    for l in range(masks.shape[0]):
        e = e_lvl[l]
        m = masks[l] * dpn
        d_left = _dot(m, k * e)
        d_right = _dot(m, both * jnp.concatenate([e, e]), _TN)
        dq = dq + d_left[:c] * e
        dkb = dkb + d_left[c:] * e
        dk = dk + d_right * e
        dx.append((d_left[:c] * q + d_left[c:] * kb + d_right * k) * e)
    return dq, dk, dkb, dx


def _head_back(q, k, kb, d, triangles, p, n, dpn, dq, dk, dkb, d_in, d_out,
               d_whole):
    """``[P; N]``'s share of the gradients of q, k, kb, and the gradient of
    the head's log-decays ``[1, C]``.  ``d_in`` and ``d_out`` ``[C, 1]``
    are the gradients of ``G_r`` and ``G_C - G_r`` through ``e^G`` and
    ``e^{G_C - G}``, ``d_whole`` ``[1, Dk]`` that of ``G_C`` through the
    state's decay."""
    c = q.shape[0]
    m = jnp.concatenate([d, d]) * dpn
    d_left = _dot(m, k)
    dq, dkb = dq + d_left[:c], dkb + d_left[c:]
    dk = dk + _dot(m, jnp.concatenate([q, kb]), _TN)
    # The gradient of D[r, i]'s exponent, dP . P + dN . N, goes to each g_j
    # with i < j <= r: [r, j] sums row r's entries left of j.  G_r sums the
    # g_j with j <= r, G_C - G_r those with j > r.
    rows, cols = _grid(c)
    through = _summed(dpn[:c] * p + dpn[c:] * n, triangles[1])
    dg = jnp.sum(jnp.where(rows >= cols, through + d_in, d_out), axis=0,
                 keepdims=True)
    return dq, dk, dkb, dg + jnp.sum(d_whole, axis=1, keepdims=True)


def _fwd_kernel(sums_ref, masks_ref, q_ref, k_ref, kb_ref, vb_ref, g_ref,
                o_ref, starts_ref, z_scr, *, per: int, scale: float,
                head: bool):
    t = pl.program_id(2)
    dv, dk = z_scr.shape[1:]

    @pl.when(t == 0)
    def _():
        z_scr[:] = jnp.zeros_like(z_scr)

    @pl.when(t % per == 0)
    def _():
        starts_ref[0, :, 0] = z_scr[:]

    # Under a condition like the rest: the interpreter, run inside a
    # shard_map that checks what varies over the mesh, only takes a
    # kernel's constants beside its varying blocks inside one.
    @pl.when(t >= 0)
    def _():
        # The heads of a step share nothing: their chains of small
        # dependent products fill each other's waits.
        for i in range(z_scr.shape[0]):
            at, at_v = slice(i * dk, (i + 1) * dk), slice(i * dv, (i + 1) * dv)
            q, k = q_ref[0, :, at], k_ref[0, :, at]
            if head:
                decays = _head_decays(g_ref[0, 0, 0, i:i + 1], sums_ref)
            else:
                decays = _decays(_exponentials(g_ref[0, :, at], sums_ref),
                                 q.shape[0])
            kb, vb = kb_ref[0, :, at], vb_ref[0, :, at_v]
            pn = _head_gram(q, k, kb, decays[0]) if head \
                else _channel_gram(q, k, kb, decays[0], masks_ref)
            p, _, u, w_k = _local(q, k, kb, vb, pn, decays[1], masks_ref)
            z = z_scr[i]
            w, z_scr[i] = _advance(z, k, u, w_k, decays)
            o_ref[0, :, at_v] = scale * (_dot(q * decays[1], z, _NT)
                                         + _dot(p, w))


def _bwd_kernel(sums_ref, masks_ref, q_ref, k_ref, kb_ref, vb_ref, g_ref,
                do_ref, starts_ref, dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref,
                z_scr, dz_scr, e_scr, p_scr, x_scr, u_scr, wk_scr, *,
                per: int, scale: float, head: bool):
    """``e_scr`` keeps a chunk's exponentials (a decay for every channel)
    or its decayed ``N`` (one a head)."""
    j, t = pl.program_id(2), pl.program_id(3)
    masks = masks_ref
    heads, dv, dk = dz_scr.shape
    c = q_ref.shape[1]

    def head_decays(i):
        return _head_decays(g_ref[0, 0, 0, i:i + 1], sums_ref)

    @pl.when(jnp.logical_and(j == 0, t == 0))
    def _():
        dz_scr[:] = jnp.zeros_like(dz_scr)

    @pl.when(t == 0)
    def _():
        z_scr[:, 0] = starts_ref[0, :, 0]

    @pl.when(t < per)
    def _sweep():
        # Forward again over the segment: the state every chunk starts
        # from and what a chunk computes before it meets the state.
        for i in range(heads):
            at = slice(i * dk, (i + 1) * dk)
            k = k_ref[0, :, at]
            if head:
                decays = head_decays(i)
            else:
                e = _exponentials(g_ref[0, :, at], sums_ref)
                decays = _decays(e, c)
            q, kb = q_ref[0, :, at], kb_ref[0, :, at]
            vb = vb_ref[0, :, slice(i * dv, (i + 1) * dv)]
            pn = _head_gram(q, k, kb, decays[0]) if head \
                else _channel_gram(q, k, kb, decays[0], masks)
            p, x, u, w_k = _local(q, k, kb, vb, pn, decays[1], masks)
            e_scr[i, t], p_scr[i, t], x_scr[i, t] = \
                pn[c:] if head else e, p, x
            u_scr[i, t], wk_scr[i, t] = u, w_k
            z_scr[i, t + 1] = _advance(z_scr[i, t], k, u, w_k, decays)[1]

    def _back_chunk(i, t, at, at_v):
        q, k, kb = q_ref[0, :, at], k_ref[0, :, at], kb_ref[0, :, at]
        decays = head_decays(i) if head else _decays(e_scr[i, t], c)
        e_lvl, e_in, e_out = decays
        p, x, u, w_k = p_scr[i, t], x_scr[i, t], u_scr[i, t], wk_scr[i, t]
        z, dz_next = z_scr[i, t], dz_scr[i]
        whole = e_in[-1:]
        w = u - _dot(w_k, z, _NT)
        do = scale * do_ref[0, :, at_v]
        q_in, k_out = q * e_in, k * e_out
        dq_in = _dot(do, z)
        dw = _dot(p, do, _TN) + _dot(k_out, dz_next, _NT)
        dk_out = _dot(w, dz_next)
        d_whole = jnp.sum(z * dz_next, axis=0, keepdims=True) * whole
        dz_scr[i] = dz_next * whole + _dot(do, q_in, _TN) \
            - _dot(dw, w_k, _TN)
        # [u | w_k] = X [vb | kb e^G]; dN = -X^T dX X^T, below the diagonal.
        back = _dot(x, jnp.concatenate([dw, -_dot(dw, z)], axis=1), _TN)
        dvb, d_rhs = back[:, :dv], back[:, dv:]
        dp = _dot(do, w, _NT)
        dpn = jnp.concatenate([
            dp, -_dot(back, jnp.concatenate([u, w_k], axis=1), _NT)])
        diag = jnp.sum(_eye(c) * dp, axis=1, keepdims=True)
        dq = dq_in * e_in + diag * k
        dk = dk_out * e_out + diag * q
        dkb = d_rhs * e_in
        if head:
            dq, dk, dkb, dg = _head_back(
                q, k, kb, e_lvl, sums_ref, p, e_scr[i, t], dpn, dq, dk, dkb,
                jnp.sum(dq_in * q + d_rhs * kb, axis=1, keepdims=True) * e_in,
                jnp.sum(dk_out * k, axis=1, keepdims=True) * e_out, d_whole)
        else:
            dq, dk, dkb, dx = _channel_back(q, k, kb, e_lvl, masks, dpn, dq,
                                            dk, dkb)
            dx.append((dq_in * q + d_rhs * kb) * e_in)
            dx.append(dk_out * k * e_out)
        dq_ref[0, :, at], dk_ref[0, :, at] = dq, dk
        dkb_ref[0, :, at], dvb_ref[0, :, at_v] = dkb, dvb
        if head:
            dg_ref[0, 0, 0, i:i + 1] = dg
        else:
            dg_ref[0, :, at] = _sums(sums_ref[:], jnp.concatenate(dx), _TN) \
                + d_whole

    @pl.when(t >= per)
    def _back():
        for i in range(heads):
            _back_chunk(i, 2 * per - 1 - t, slice(i * dk, (i + 1) * dk),
                        slice(i * dv, (i + 1) * dv))


def _constant_inputs(c, like, head):
    vma = tuple(jax.typeof(like).vma)
    sums, masks = _constants(c)
    return tuple(pvary_missing(jnp.asarray(x), vma)
                 for x in ((_triangles(c) if head else sums), masks))


def _heads_a_step(h):
    return 2 if h % 2 == 0 else 1


def _whole(shape):
    return pl.BlockSpec(shape, lambda *_: (0,) * len(shape))


def _params(interpret, grid_rank, vmem_bytes):
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel")
        + ("arbitrary",) * (grid_rank - 2),
        vmem_limit_bytes=vmem_bytes)


def _rows(chunk, width, index_map):
    return pl.BlockSpec((1, chunk, width), index_map)


def _per_head(g, chunk, heads):
    """A decay a head ``[B, S, H]`` as the kernels read it, ``[B, H /
    heads, S / C, heads, C]``: a grid step's heads' log-decays of a chunk,
    a row a head, steps on lanes."""
    bsz, s, h = g.shape
    return g.reshape(bsz, s // chunk, chunk, h // heads, heads) \
        .transpose(0, 3, 1, 4, 2)


def _name(scope, head):
    return scopes.kernel_name(scope) + ("_head" if head else "")


@jax.named_scope(scopes.KDA_FWD)
def forward(q, k, kb, vb, g, chunk, per, h, scale):
    """q, k, kb ``[B, S, H Dk]``, vb ``[B, S, H Dv]`` and g ``[B, S, H Dk]``
    (a decay for every channel) or ``[B, S, H]`` (one a head) -> (o ``[B,
    S, H Dv]``, the transposed state every segment of ``per`` chunks starts
    from ``[B, H, S / (per C), Dv, Dk]``); ``o = scale (S^T q)``."""
    from jax.experimental.pallas import tpu as pltpu
    bsz, s, width = q.shape
    dk, dv, n = width // h, vb.shape[-1] // h, s // chunk
    interpret = not on_tpu()
    head = g.shape[-1] == h
    sums, masks = _constant_inputs(chunk, q, head)
    heads = _heads_a_step(h)
    keys, values = (_rows(chunk, heads * size, lambda b, i, t: (b, t, i))
                    for size in (dk, dv))
    decay = pl.BlockSpec((1, 1, 1, heads, chunk),
                         lambda b, i, t: (b, i, t, 0, 0)) if head else keys
    return pl.pallas_call(
        functools.partial(_fwd_kernel, per=per, scale=scale, head=head),
        grid=(bsz, h // heads, n),
        in_specs=[_whole(sums.shape), _whole(masks.shape)]
        + [keys, keys, keys, values, decay],
        out_specs=[values,
                   pl.BlockSpec((1, heads, 1, dv, dk),
                                lambda b, i, t: (b, i, t // per, 0, 0))],
        out_shape=[_sds((bsz, s, h * dv), jnp.float32, q),
                   _sds((bsz, h, n // per, dv, dk), jnp.float32, q)],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=_params(interpret, 3, 32 << 20),
        interpret=interpret,
        name=_name(scopes.KDA_FWD, head),
    )(sums, masks, q, k, kb, vb, _per_head(g, chunk, heads) if head else g)


@jax.named_scope(scopes.KDA_BWD)
def backward(q, k, kb, vb, g, do, starts, chunk, per, h, scale):
    """Gradients of q, k, kb, vb, g, each of its input's shape."""
    from jax.experimental.pallas import tpu as pltpu
    bsz, s, width = q.shape
    dk, dv, n = width // h, vb.shape[-1] // h, s // chunk
    segments = n // per
    interpret = not on_tpu()
    head = g.shape[-1] == h
    sums, masks = _constant_inputs(chunk, q, head)

    def chunk_of(j, t):
        # Steps 0 .. per - 1 sweep forward over the segment, steps per ..
        # 2 per - 1 take its chunks last to first.
        first = (segments - 1 - j) * per
        return first + jnp.where(t < per, t, 2 * per - 1 - t)

    def chunk_back(j, t):
        # What only the way back reads or writes waits at the last chunk.
        return (segments - 1 - j) * per + per - 1 - jnp.maximum(t - per, 0)

    heads = _heads_a_step(h)
    swept_k, swept_v = (
        _rows(chunk, heads * size, lambda b, i, j, t: (b, chunk_of(j, t), i))
        for size in (dk, dv))
    back_k, back_v = (
        _rows(chunk, heads * size, lambda b, i, j, t: (b, chunk_back(j, t), i))
        for size in (dk, dv))
    if head:
        swept_g, back_g = (
            pl.BlockSpec((1, 1, 1, heads, chunk),
                         lambda b, i, j, t, at=at: (b, i, at(j, t), 0, 0))
            for at in (chunk_of, chunk_back))
        g_in = _per_head(g, chunk, heads)
        kept = (chunk, chunk)
    else:
        swept_g, back_g, g_in, kept = swept_k, back_k, g, (sums.shape[0], dk)
    *grads, dg = pl.pallas_call(
        functools.partial(_bwd_kernel, per=per, scale=scale, head=head),
        grid=(bsz, h // heads, segments, 2 * per),
        in_specs=[_whole(sums.shape), _whole(masks.shape)]
        + [swept_k, swept_k, swept_k, swept_v, swept_g]
        + [back_v, pl.BlockSpec((1, heads, 1, dv, dk), lambda b, i, j, t:
                                (b, i, segments - 1 - j, 0, 0))],
        out_specs=[back_k, back_k, back_k, back_v, back_g],
        out_shape=[_sds(x.shape, jnp.float32, q)
                   for x in (q, k, kb, vb, g_in)],
        scratch_shapes=[pltpu.VMEM((heads, per + 1, dv, dk), jnp.float32),
                        pltpu.VMEM((heads, dv, dk), jnp.float32),
                        pltpu.VMEM((heads, per) + kept, jnp.float32),
                        pltpu.VMEM((heads, per, chunk, chunk), jnp.float32),
                        pltpu.VMEM((heads, per, chunk, chunk), jnp.float32),
                        pltpu.VMEM((heads, per, chunk, dv), jnp.float32),
                        pltpu.VMEM((heads, per, chunk, dk), jnp.float32)],
        compiler_params=_params(interpret, 4, 48 << 20),
        interpret=interpret,
        name=_name(scopes.KDA_BWD, head),
    )(sums, masks, q, k, kb, vb, g_in, do, starts)
    if head:
        dg = dg.transpose(0, 2, 4, 1, 3).reshape(g.shape)
    return (*grads, dg)
