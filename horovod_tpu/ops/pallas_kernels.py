"""Pallas TPU kernels for the hot ops.

The reference keeps its hot device code in hand-written CUDA
(``horovod/common/ops/cuda/cuda_kernels.cu`` — batched fusion memcpy,
fused scale+sum).  The TPU-native equivalents live here as Pallas
kernels (SURVEY.md §7 phase 7):

* ``flash_attention`` — fused blocked attention with online softmax:
  scores never materialize in HBM (O(seq) memory instead of O(seq²)),
  K/V stream through VMEM block by block, matmuls hit the MXU at
  (block_q × block_k) tiles.  This is the hot op of the transformer
  family; the sequence-parallel ring attention composes with it (ring
  moves KV between chips, this kernel computes each local block).
* ``fused_scale_sum`` — the reference's fused prescale+sum kernel
  (``ScaleAdd`` in cuda_kernels.cu): one VPU pass over fused gradient
  buffers instead of two HBM round trips.

Both run compiled on TPU and interpreted on the CPU test world
(``common/device.py`` decides, and refuses any other backend), so the
tests exercise the same kernel code path.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..common import metrics, scopes
from ..common.device import on_tpu

LOG = logging.getLogger("horovod_tpu")

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

# A full call's grid is a SCHEDULE of block pairs, made as the call is traced:
# the pairs that hold any work and no others (under the causal mask the ones
# that meet the triangle: 72 of a head's 128 at 8192 positions in blocks of
# 512 x 1024), a pair a step along one sequential grid axis.  The tables that
# say which pair a step visits go in as scalar prefetch, the index maps read
# the blocks from them, and in the forward kernel a pair the diagonal does
# not cut takes the body without the mask.

_FIRST, _LAST, _CROSSES = 1, 2, 4       # a pair's flags, as the kernels read them


class _Pairs(NamedTuple):
    """Block pairs in the order a grid visits them, a row after another."""
    q: np.ndarray           # the pair's query block
    k: np.ndarray           # and its key block
    first: np.ndarray       # first of its row: the row's scratch starts here
    last: np.ndarray        # last of its row: the row's output is written
    crosses: np.ndarray     # the diagonal cuts the pair: it needs the mask


@functools.lru_cache(maxsize=None)
def _block_schedule(seq: int, block_q: int, block_k: int, causal: bool):
    """``(by query block, by key block)``: the block pairs of a full call in
    the two orders its kernels sweep them.  By query block (forward, dq) a
    row is a query block with its key blocks ascending; by key block (dk/dv
    and the one backward kernel) a key block with its query blocks
    ascending.  A causal call has the pairs with a key at or before a query
    and no others; a pair with a key after one of its queries crosses the
    diagonal.  Without the mask every pair is there and none crosses."""
    nq, nk = seq // block_q, seq // block_k

    def live(j, t):
        return not causal or t * block_k <= j * block_q + block_q - 1

    def crosses(j, t):
        return causal and t * block_k + block_k - 1 > j * block_q

    def sweep(rows):
        out = []
        for row in rows:
            row = [pair for pair in row if live(*pair)]
            out += [(j, t, n == 0, n == len(row) - 1, crosses(j, t))
                    for n, (j, t) in enumerate(row)]
        return _Pairs(*(np.asarray(column) for column in zip(*out)))

    return (sweep([[(j, t) for t in range(nk)] for j in range(nq)]),
            sweep([[(j, t) for j in range(nq)] for t in range(nk)]))


def _schedule_tables(pairs: _Pairs, kernel: str):
    """The scalar-prefetch operands of a scheduled grid (query block, key
    block and flags of every step), counted as they are made: once for every
    time a call's kernel is traced, like ``hvd_flash_backward_calls_total``."""
    diagonal = int(pairs.crosses.sum())
    for kind, n in (("interior", len(pairs.q) - diagonal),
                    ("diagonal", diagonal)):
        metrics.counter("hvd_flash_block_pairs_total", kernel=kernel,
                        kind=kind).inc(n)
    flags = _FIRST * pairs.first + _LAST * pairs.last \
        + _CROSSES * pairs.crosses
    return tuple(jnp.asarray(table, jnp.int32)
                 for table in (pairs.q, pairs.k, flags))


def _scheduled_pair(q_blocks, k_blocks, flags):
    """``(query block, key block, first, last, crosses)`` of the pair this
    step of a scheduled grid ``(bh, pair)`` visits."""
    step = pl.program_id(1)
    flag = flags[step]
    return (q_blocks[step], k_blocks[step], (flag & _FIRST) != 0,
            (flag & _LAST) != 0, (flag & _CROSSES) != 0)


def _scheduled_specs(block_q: int, block_k: int, d: int, heads: int):
    """``(query, key, row statistics)`` BlockSpecs of a scheduled grid: the
    tables say which block of the sequence a step takes."""
    def spec(rows, width, table):
        return pl.BlockSpec(
            (heads, rows, width),
            lambda i, step, *tables: (i, tables[table][step], 0))
    return spec(block_q, d, 0), spec(block_k, d, 1), spec(block_q, 1, 0)


# A key in two parts (latent attention: every head's own columns and, behind
# them, the one rotary key a position that all heads of a batch entry end
# in): the shared part goes to a full call's kernels as an operand of its
# own, ``[B, S, d_s]``, a block a key block by the call's schedule, and is
# never repeated over the heads in HBM.

def _shared_key_spec(block_k: int, d_s: int, group: int, kernel: str):
    """BlockSpec of the shared key part on a scheduled grid: flat head ``i``
    reads batch entry ``i // group``'s block, by the table its own key block
    follows.  Counted as it is made: once for every time a call's kernel is
    traced, like ``hvd_flash_block_pairs_total``."""
    metrics.counter("hvd_flash_shared_key_calls_total", kernel=kernel).inc()
    return pl.BlockSpec(
        (1, block_k, d_s),
        lambda i, step, *tables: (i // group, tables[1][step], 0))


class _JoinedKey:
    """A key block in two refs, every head's own columns and the shared ones
    behind them, for a kernel written for one: read (``ref[0]``), the two are
    joined in VMEM; written (the key's gradient, once a key block), each ref
    takes its columns."""

    def __init__(self, own, shared):
        self.own, self.shared, self.dtype = own, shared, own.dtype

    def __getitem__(self, index):
        return jnp.concatenate([self.own[index], self.shared[index]], axis=-1)

    def __setitem__(self, index, value):
        d_k = self.own.shape[-1]
        self.own[index] = value[:, :d_k]
        self.shared[index] = value[:, d_k:]


def _key_joined(kernel, *at):
    """``kernel`` (written for a key in one ref) over refs that hold a
    shared key part right behind the key's own: ``at`` says where, the key
    among the inputs first and then its gradient among the outputs, each
    place counted with the pairs before it already joined."""
    def body(*refs):
        refs = list(refs)
        for i in at:
            refs[i:i + 2] = [_JoinedKey(refs[i], refs[i + 1])]
        kernel(*refs)
    return body


# A window of ``w`` keys (sliding-window attention): query ``i`` sees key
# ``j`` when ``i - w < j <= i``, itself among them.  The kernels below run
# their inner grid axis over the blocks that meet that band and no others:
# the axis has as many steps as the widest query (key) block needs, a step
# adds the block's first live partner to its index, and an index past the
# band's end is held at the end, so the pipeline fetches nothing new for a
# step the kernel skips.

def _band_first_k(j, block_q: int, block_k: int, window: int):
    """The first key block that query block ``j`` meets."""
    return jnp.maximum(j * block_q - (window - 1), 0) // block_k


def _band_last_k(j, block_q: int, block_k: int):
    return (j * block_q + block_q - 1) // block_k


def _band_first_q(t, block_q: int, block_k: int):
    """The first query block that meets key block ``t``."""
    return (t * block_k) // block_q


def _band_last_q(t, block_q: int, block_k: int, window: int, nq: int):
    return jnp.minimum((t * block_k + block_k - 1 + window - 1) // block_q,
                       nq - 1)


def _band_steps(seq: int, block_q: int, block_k: int, window: int):
    """(key blocks the widest query block meets, query blocks the widest
    key block is met by): the lengths of the banded grids' inner axes."""
    nq, nk = seq // block_q, seq // block_k
    k_steps = max(
        (j * block_q + block_q - 1) // block_k
        - max(j * block_q - (window - 1), 0) // block_k + 1
        for j in range(nq))
    q_steps = max(
        min((t * block_k + block_k - 1 + window - 1) // block_q, nq - 1)
        - (t * block_k) // block_q + 1
        for t in range(nk))
    return k_steps, q_steps


def _seen(rows, cols, window):
    seen = cols <= rows
    if window is not None:
        seen = jnp.logical_and(seen, cols > rows - window)
    return seen


def _seen_in_block(j, kb, block_q: int, block_k: int, window):
    """Which scores of block ``(j, kb)`` the mask keeps.  Under a window
    that is one unsigned comparison: how far a row is ahead of a column,
    ``0 <= rows - cols < window``, where a column ahead of its row wraps
    round to a large number (the kernels are bound by the vector unit, and
    two comparisons and their conjunction cost them a fifth more time)."""
    shape = (block_q, block_k)
    if window is None:
        rows = j * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        cols = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        return cols <= rows
    ahead = (j * block_q - kb * block_k) + (
        jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        - jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    return jax.lax.bitcast_convert_type(ahead, jnp.uint32) \
        < jnp.uint32(window)


def _flash_attn_kernel(*refs, block_q: int, block_k: int, causal: bool,
                       window=None):
    # K/V stream through VMEM one block a step (double-buffered by the
    # Pallas pipeline); the online-softmax state (m, l, acc) persists in
    # VMEM scratch across a query block's steps.  A full call's grid is
    # (bh, pair) with the schedule's tables ahead of the blocks; a banded
    # call's is (bh, nq, step) and its inner axis counts from the band's
    # first block.
    banded = window is not None
    if banded:
        j = pl.program_id(1)
        t = pl.program_id(2)
        nk = pl.num_programs(2)
        kb = _band_first_k(j, block_q, block_k, window) + t
    else:
        j, kb, first, last, crosses = _scheduled_pair(*refs[:3])
        refs = refs[3:]
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs

    @pl.when(t == 0 if banded else first)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _update(masked: bool):
        # matmuls stay in the input dtype (bf16 hits the MXU at full
        # rate; accumulation is f32 via preferred_element_type)
        # q arrives PRE-SCALED by 1/sqrt(d) (one cheap (BH,S,D) pass
        # outside the kernel) — a per-block (BQ,BK) scale multiply
        # here would cost ~16x more VPU work over the whole grid.
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (BQ, BK)
        if masked:
            # A row that a banded block hides whole reads exp(0) here; the
            # block that holds its diagonal comes later and its correction
            # exp(-1e30 - m) wipes that out.
            s = jnp.where(_seen_in_block(j, kb, block_q, block_k, window),
                          s, _NEG_INF)
        m_prev = m_scr[:]
        l_prev = l_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)

    if banded:
        # a step past the band's end (its block's index held) adds nothing
        in_band = jnp.logical_or(
            jnp.logical_not(causal),
            kb * block_k <= j * block_q + block_q - 1)
        pl.when(in_band)(lambda: _update(True))
    elif causal:
        # A pair the diagonal does not cut is seen whole and takes the body
        # without the mask: 2.4 % of this kernel's time, which the vector
        # unit sets.  (The backward kernels, nearer the MXU's time, gain
        # nothing from a second body and mask every pair; PERF.md, PR 36.)
        pl.when(crosses)(lambda: _update(True))
        pl.when(jnp.logical_not(crosses))(lambda: _update(False))
    else:
        _update(False)

    @pl.when(t == nk - 1 if banded else last)
    def _finish():
        o_ref[0] = (acc_scr[:] /
                    jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)
        # log-sum-exp per row: the backward recomputes softmax as
        # exp(s - lse) without a second online pass.
        lse_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))


class _HeadOf:
    """Head ``g`` of a ref that holds several, for a kernel written for one:
    the kernels read and write a head's block whole (``ref[0]``) and a
    scratch whole (``ref[:]``), and both are ``ref[g]`` here; the rows of a
    whole-head scratch (``ref[rows, :]``, a tuple) are ``ref[g, rows, :]``.
    (A view, ``ref.at[g]``, is refused by Mosaic for the row statistics'
    unit lane dimension.)"""

    def __init__(self, ref, g: int):
        self.ref, self.g = ref, g
        self.shape, self.dtype = ref.shape[1:], ref.dtype

    def _at(self, index):
        return (self.g,) + index if isinstance(index, tuple) else self.g

    def __getitem__(self, index):
        return self.ref[self._at(index)]

    def __setitem__(self, index, value):
        self.ref[self._at(index)] = value


def _heads_a_step(kernel, heads: int, tables: int = 0):
    """``kernel`` (written for blocks of one flat head) over blocks and
    scratch of ``heads``, a head after another; a scheduled grid's
    ``tables`` come first and are every head's.  A banded call's grid steps
    are short (two key blocks a query block where a full call has nine), so
    what a step costs whatever it computes weighs more, and several heads a
    step divide it."""
    if heads == 1:
        return kernel

    def body(*refs):
        for g in range(heads):
            kernel(*refs[:tables],
                   *(_HeadOf(ref, g) for ref in refs[tables:]))
    return body


def _sds(shape, dtype, like):
    """ShapeDtypeStruct inheriting ``like``'s varying-manual-axes so
    pallas_call outputs type-check inside ``check_vma=True`` shard_maps
    (per-shard kernel outputs vary exactly like their inputs)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _k_spec(block_q: int, block_k: int, d: int, window, heads: int = 1):
    """Key/value blocks of a banded grid ``(bh, query block, key step)``:
    the band's blocks (its last one held for the steps a narrower query
    block has left over)."""
    def index(i, j, t):
        return (i, jnp.minimum(
            _band_first_k(j, block_q, block_k, window) + t,
            _band_last_k(j, block_q, block_k)), 0)
    return pl.BlockSpec((heads, block_k, d), index)


class _Sweep(NamedTuple):
    """A swept grid: the schedule's tables (none under a window), the axes
    after the heads' with their semantics, and the BlockSpecs of a query
    block and a key block at the query/key size, of the row statistics, and
    of a query block and a key block at the value's size; a key in two
    parts has ``k`` at its own columns' size and the shared part's BlockSpec
    in ``shared``."""
    tables: tuple
    axes: tuple
    semantics: tuple
    q: object
    k: object
    rows: object
    o: object
    v: object
    shared: tuple = ()


def _query_sweep(seq: int, block_q: int, block_k: int, d: int, causal: bool,
                 window, heads: int, kernel: str, d_v=None, shared=None):
    """The grid of a kernel that sweeps a query block's keys (forward, dq).
    A full call's is its schedule, a banded call's every query block by the
    band's key steps.  ``d`` is the query/key size, ``d_v`` the value's
    where it is another; ``shared`` is ``(d_s, flat heads a batch entry)``
    where the last ``d_s`` of the key's ``d`` are a part of their own."""
    d_v = d if d_v is None else d_v
    if window is None:
        pairs = _block_schedule(seq, block_q, block_k, causal)[0]
        q, k, rows = _scheduled_specs(block_q, block_k, d, heads)
        o, v, _ = _scheduled_specs(block_q, block_k, d_v, heads)
        tables = _schedule_tables(pairs, kernel)
        shared_spec = ()
        if shared is not None:
            k = _scheduled_specs(block_q, block_k, d - shared[0], heads)[1]
            shared_spec = (_shared_key_spec(block_k, *shared, kernel),)
        return _Sweep(tables, (len(pairs.q),), ("arbitrary",), q, k, rows, o,
                      v, shared_spec)

    def q_spec(width):
        return pl.BlockSpec((heads, block_q, width), lambda i, j, t: (i, j, 0))

    return _Sweep(
        (), (seq // block_q, _band_steps(seq, block_q, block_k, window)[0]),
        ("parallel", "arbitrary"), q_spec(d),
        _k_spec(block_q, block_k, d, window, heads),
        # unit lane dim keeps the (sublane, lane) tiling legal and
        # broadcasts against (block_q, block_k) scores directly
        q_spec(1), q_spec(d_v), _k_spec(block_q, block_k, d_v, window, heads))


def _sized(name: str, d: int, d_v: int, d_s: int = 0) -> str:
    """A kernel's name with the call's two head sizes where they differ, so
    that a trace tells such a call's kernels from the others; a key in two
    parts names both (``hvd_flash_fwd_128s64x128``: 64 shared behind 128)."""
    if d_s:
        return "%s_%ds%dx%d" % (name, d - d_s, d_s, d_v)
    return name if d == d_v else "%s_%dx%d" % (name, d, d_v)


def _shared_of(k_shared, flat_heads: int):
    """``((d_s, flat heads a batch entry), (k_shared,), d_s)`` of a flat
    call's shared key part ``[B, S, d_s]``, or of none."""
    if k_shared is None:
        return None, (), 0
    d_s = k_shared.shape[-1]
    return (d_s, flat_heads // k_shared.shape[0]), (k_shared,), d_s


def _flash_attention_fwd_flat(q, k, v, *, causal: bool, block_q: int,
                              block_k: int, interpret: bool, window=None,
                              heads: int = 1, k_shared=None):
    """(BH, S, D) q, k and (BH, S, Dv) v → ((BH, S, Dv) output, (BH, S, 1)
    lse), the sizes as ``_flash_fwd`` pads them.  ``heads`` flat heads a
    grid step (``_heads_a_step``).  With ``k_shared`` (B, S, Ds) of a full
    call, k holds the key's first D - Ds columns."""
    from jax.experimental.pallas import tpu as pltpu
    bh, seq, d = q.shape
    d_v = v.shape[-1]
    banded = window is not None
    shared, shared_key, d_s = _shared_of(k_shared, bh)
    kernel = functools.partial(
        _flash_attn_kernel, block_q=block_q, block_k=block_k,
        causal=causal, window=window)
    sweep = _query_sweep(seq, block_q, block_k, d, causal, window, heads,
                         "fwd", d_v, shared)
    if shared:
        kernel = _key_joined(kernel, len(sweep.tables) + 1)
    slab = () if heads == 1 else (heads,)
    with jax.named_scope(scopes.FLASH_WINDOW_FWD) if banded \
            else jax.named_scope(scopes.FLASH_FWD):
        return pl.pallas_call(
            _heads_a_step(kernel, heads, len(sweep.tables)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(sweep.tables),
                grid=(bh // heads,) + sweep.axes,
                in_specs=[sweep.q, sweep.k, *sweep.shared, sweep.v],
                out_specs=[sweep.o, sweep.rows],
                scratch_shapes=[
                    pltpu.VMEM(slab + (block_q, 1), jnp.float32),
                    pltpu.VMEM(slab + (block_q, 1), jnp.float32),
                    pltpu.VMEM(slab + (block_q, d_v), jnp.float32),
                ]),
            out_shape=[
                _sds((bh, seq, d_v), q.dtype, q),
                _sds((bh, seq, 1), jnp.float32, q),
            ],
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel",) + sweep.semantics),
            interpret=interpret,
            name=_sized(scopes.kernel_name(
                scopes.FLASH_WINDOW_FWD if banded else scopes.FLASH_FWD),
                d, d_v, d_s),
        )(*sweep.tables, q, k, *shared_key, v)


def _reference_attention(q, k, v, causal: bool, window=None):
    """Plain attention on (B, S, H, D): the single oracle shared with
    the model's non-TPU path and the SP tests."""
    from ..parallel.ring_attention import local_attention
    return local_attention(q, k, v, causal=causal, window=window)


def _chunked_attention_bwd(q, k, v, g, causal: bool, block_q: int,
                           window=None):
    """Memory-efficient attention backward: iterate q blocks, so peak
    extra memory is O(block_q·seq) per (batch,head) instead of the
    O(seq²) score matrix (the standard flash-attention backward
    recurrence, expressed in XLA ops)."""
    b, s, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    qf = jnp.swapaxes(q, 1, 2).astype(jnp.float32)   # (B,H,S,D)
    kf = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vf = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    gf = jnp.swapaxes(g, 1, 2).astype(jnp.float32)
    nq = s // block_q

    def step(carry, i):
        dk, dv = carry
        start = i * block_q
        q_blk = jax.lax.dynamic_slice_in_dim(qf, start, block_q, 2)
        g_blk = jax.lax.dynamic_slice_in_dim(gf, start, block_q, 2)
        s_blk = jnp.einsum("bhqd,bhkd->bhqk", q_blk, kf) * scale
        if causal:
            rows = start + jnp.arange(block_q)[:, None]
            cols = jnp.arange(s)[None, :]
            s_blk = jnp.where(_seen(rows, cols, window), s_blk, _NEG_INF)
        p = jax.nn.softmax(s_blk, axis=-1)             # (B,H,BQ,S)
        dv = dv + jnp.einsum("bhqk,bhqd->bhkd", p, g_blk)
        dp = jnp.einsum("bhqd,bhkd->bhqk", g_blk, vf)
        ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
        dq_blk = jnp.einsum("bhqk,bhkd->bhqd", ds, kf) * scale
        dk = dk + jnp.einsum("bhqk,bhqd->bhkd", ds, q_blk) * scale
        return (dk, dv), dq_blk

    (dk, dv), dq_blocks = jax.lax.scan(
        step, (jnp.zeros_like(kf), jnp.zeros_like(vf)),
        jnp.arange(nq))
    dq = jnp.moveaxis(dq_blocks, 0, 2).reshape(b, h, s, d)
    to_out = lambda x, like: jnp.swapaxes(x, 1, 2).astype(like.dtype)
    return to_out(dq, q), to_out(dk, k), to_out(dv, v)


# (seq, d_pad) -> (block_q, block_k) pinned by autotune_flash_blocks:
# the measured winner of the on-device block sweep.  Env overrides
# still win (an explicit A/B must never be silently retuned); the
# default chains below are only the cold fallback.
# A call under a window keeps its own pins, ``(seq, d_pad, window)``.
_TUNED_BLOCKS: dict = {}

_BLOCK_Q_DEFAULTS = (512, 256, 128, 64)
_BLOCK_K_DEFAULTS = (1024, 512, 256, 128, 64)
# (seq, d_qk, d_v, bytes an element) -> (block_q, block_k) of a full call
# whose query/key size is another than the value's: the one shape the chip has
# run, in bfloat16 (every other such call takes the default chains: in float32
# these blocks pass the forward kernel's VMEM; ``autotune_flash_blocks``
# sweeps ``d_qk == d_v`` calls).  At 192 over 128 a block pair costs more
# whatever it computes (q, k and the one backward kernel's dq rows lie in 256
# lanes) and 1024 x 1024 halves the pairs: in kanana's step a forward call of
# 64 flat heads went 12.86 -> 11.93 ms and the one backward kernel 27.80 ->
# 26.96 against 512 x 1024 (PERF.md, PR 37; 512 x 2048 passes the one
# kernel's VMEM limit).
_TWO_SIZE_BLOCKS = {(8192, 192, 128, 2): (1024, 1024)}


def export_tuned_blocks() -> dict:
    """The pinned-block registry as a JSON-safe dict
    (``"<seq>x<d_pad>" -> [block_q, block_k]``, a window's pins
    ``"<seq>x<d_pad>x<window>"``) — the flash-block leg
    of the persistent plan cache (``utils/plancache.py``), so kernel
    and collective plans persist in one plane."""
    return {"x".join("%d" % v for v in key): [int(bq), int(bk)]
            for key, (bq, bk) in _TUNED_BLOCKS.items()}


def seed_tuned_blocks(blocks: dict):
    """Seed the registry from a persisted plan (``hvd.init()`` warm
    start).  Entries a live ``autotune_flash_blocks`` sweep pins later
    overwrite these; env block overrides are handled by the CALLER
    (they win and suppress seeding, the r9 precedence rule) and by
    ``_plan`` itself at trace time.  Malformed entries are skipped
    loudly — a corrupt plan must never pin an invalid block shape."""
    for key, pair in (blocks or {}).items():
        try:
            s, d_pad, *window = (int(v) for v in str(key).split("x"))
            bq, bk = int(pair[0]), int(pair[1])
            if min(bq, bk) < 64 or bq % 16 or bk % 16 or s % bq or s % bk \
                    or len(window) > 1:
                raise ValueError("invalid block pair")
            _TUNED_BLOCKS[(s, d_pad, *window)] = (bq, bk)
        except (ValueError, TypeError, IndexError):
            LOG.warning("ignoring malformed tuned-block entry %r: %r",
                        key, pair)


# Flat heads a grid step of a banded call: the most of these that divides
# the heads there are (``_heads_a_step``; PERF.md, PR 31 has the sweep on
# the chip).  A full call keeps one.
_WINDOW_HEADS_A_STEP = (4, 2, 1)


def _heads_of(flat_heads: int, window) -> int:
    return 1 if window is None else next(
        g for g in _WINDOW_HEADS_A_STEP if flat_heads % g == 0)


# The one backward kernel (``_flash_bwd_onepass_kernel``) holds dq of its
# heads whole in VMEM: a float32 accumulator ``[seq, d_pad]`` a head and the
# whole-head output block it is cast into at the end, twice (the pipeline's
# two buffers).  A call takes it where that fits this budget and the two
# kernels beyond it (the v5e has 128 MiB).  The limit the compiler is given
# is those bytes and room for the blocks and the score-shaped products, and
# no more: what a kernel may use XLA cannot lend to the fusions round it
# (at a flat 64 MiB `mlm512` kept 67 fewer buffers in VMEM and gave back
# 0.9 of the 1.2 ms the kernel won; PERF.md, PR 32).
_ONEPASS_VMEM_BUDGET = 32 << 20
_ONEPASS_VMEM_ROOM = 16 << 20


def _onepass_vmem_bytes(heads: int, s: int, d: int, itemsize: int):
    """dq of ``heads`` flat heads as the one kernel holds it, at the lanes
    its query/key size takes in VMEM (192 lies in 256)."""
    return heads * s * _d_pad(d) * (4 + 2 * itemsize)


def _onepass_heads(flat_heads: int, s: int, d_pad: int, itemsize: int,
                   window):
    """Flat heads a grid step of the one backward kernel: as many as the
    other kernels of the call take (``_heads_of``) if their dq fits the
    budget, else the most that does; None where one head's does not."""
    most = _heads_of(flat_heads, window)
    return next(
        (g for g in _WINDOW_HEADS_A_STEP if most % g == 0
         and _onepass_vmem_bytes(g, s, d_pad, itemsize)
         <= _ONEPASS_VMEM_BUDGET), None)


def _backward_form(flat_heads: int, s: int, d_pad: int, itemsize: int,
                   window):
    """``(form, heads a step)`` of a call's backward pass, read from
    ``HVD_TPU_FLASH_BWD`` at TRACE time (under jit the choice is baked into
    the compiled function: set it before the first train step, not between
    steps).  Unset, the shape decides: the one kernel where dq of a grid
    step's heads fits ``_ONEPASS_VMEM_BUDGET``, the two kernels beyond it.
    ``pallas`` is the two kernels whatever the shape (the A/B hatch),
    ``pallas_onepass`` the one kernel or an error where it does not fit,
    ``chunked`` the XLA form.  Unknown values fail loudly so a typo can't
    silently invalidate an A/B comparison."""
    import os
    choice = os.environ.get("HVD_TPU_FLASH_BWD")
    if choice not in (None, "pallas", "pallas_onepass", "chunked"):
        raise ValueError(
            "HVD_TPU_FLASH_BWD must be 'pallas', 'pallas_onepass' or "
            "'chunked', got %r" % choice)
    if choice == "chunked":
        return "chunked", 1
    heads = None if choice == "pallas" else _onepass_heads(
        flat_heads, s, d_pad, itemsize, window)
    if heads is not None:
        return "onepass", heads
    if choice == "pallas_onepass":
        raise ValueError(
            "HVD_TPU_FLASH_BWD=pallas_onepass: dq of one flat head "
            "(%d x %d float32 and its output block twice) does not fit the "
            "%d MiB the one kernel may hold in VMEM; unset the variable "
            "for the two kernels" % (s, d_pad, _ONEPASS_VMEM_BUDGET >> 20))
    return "two_kernel", _heads_of(flat_heads, window)


def _d_pad(d: int) -> int:
    return max(128, ((d + 127) // 128) * 128)


def _plan(s: int, d: int, window=None, d_v=None, itemsize: int = 2,
          two_part_key: bool = False):
    """Block plan shared by fwd and bwd.  Large tiles amortize
    per-grid-step overhead; MXU tiles are 128-aligned so any divisor
    ≥64 works.  The head dim is lane-padded to 128 (zero columns add 0
    to every dot product); where the value's size ``d_v`` is another than
    the query/key size ``d``, the query/key size goes in as it is (it is a
    contraction, and a block as wide as its array is legal: 192 stays 192
    in HBM), the value's is padded as ever by the caller, and the blocks
    are the measured ones of ``_TWO_SIZE_BLOCKS`` or the default chains (the
    pins are of ``d == d_v`` calls); so it is with a key in two parts (the
    kernels join them, and q has to be as wide as the two) whatever ``d_v``.
    Precedence: HVD_TPU_FLASH_BLOCK_Q/K env
    overrides (must divide the sequence length) > blocks pinned by
    ``autotune_flash_blocks`` (the measured sweep) > the default
    chains.  Under a window the chains stop at the window: a block pair
    is computed whole wherever the band touches it, so a 1024-wide key
    block over a band of 512 does twice the work."""
    import os

    def _env_block(name, tuned, dflt_chain):
        v = os.environ.get(name)
        if v:
            # Fail loudly, like HVD_TPU_FLASH_BWD below: a silently
            # ignored override would mislabel an A/B comparison.
            try:
                b = int(v)
            except ValueError:
                raise ValueError("%s=%r is not an integer" % (name, v))
            if b < 64 or b % 16 or s % b:
                raise ValueError(
                    "%s=%d invalid: blocks must be >=64, sublane-"
                    "aligned (multiple of 16), and divide the "
                    "sequence length %d" % (name, b, s))
            return b
        if tuned is not None:
            return tuned
        return next((b for b in dflt_chain if s % b == 0 and b <= cap),
                    None)

    if d_v in (None, d) and not two_part_key:
        d_pad = _d_pad(d)
        tuned = _TUNED_BLOCKS.get((s, d_pad) if window is None
                                  else (s, d_pad, window))
    else:
        d_pad = d
        tuned = None if window else _TWO_SIZE_BLOCKS.get(
            (s, d, d_v, itemsize))
    cap = s if window is None else max(64, window)
    block_q = _env_block("HVD_TPU_FLASH_BLOCK_Q",
                         tuned[0] if tuned else None, _BLOCK_Q_DEFAULTS)
    block_k = _env_block("HVD_TPU_FLASH_BLOCK_K",
                         tuned[1] if tuned else None, _BLOCK_K_DEFAULTS)
    # The FULL attention scale folds into one pre-multiply of q (the
    # kernels do no scaling at all): one (BH,S,D) pass replaces a
    # (BQ,BK) pass per grid block (~16x more elements at seq 2048,
    # d 128) in the fwd and both bwd kernels.  Padding needs no
    # correction precisely because the kernels don't scale.
    pre_scale = 1.0 / math.sqrt(d)
    return block_q, block_k, d_pad, pre_scale


def _to_flat(x, d_pad):
    b, s, h, d = x.shape
    x = jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)
    if d_pad != d:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, d_pad - d)))
    return x


def _from_flat(x, b, h, d, like):
    s = x.shape[1]
    x = x[:, :, :d].reshape(b, h, s, d)
    return jnp.swapaxes(x, 1, 2).astype(like.dtype)


def whole_key(k, k_shared):
    """The key ``[B, S, H, d_k + d_s]`` of a key in two parts: the shared
    part ``[B, S, d_s]`` behind every head's own ``[B, S, H, d_k]`` (what a
    caller hands an attention that takes one key; ``k`` itself where there
    is no shared part)."""
    if k_shared is None:
        return k
    return jnp.concatenate(
        [k, jnp.broadcast_to(k_shared[:, :, None, :],
                             k.shape[:3] + k_shared.shape[-1:])], axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_attention(q, k, v, causal, window, k_shared=None):
    return _flash_attention_impl(q, k, v, causal, window, k_shared)


def _flash_attention_impl(q, k, v, causal, window, k_shared=None):
    return _flash_fwd(q, k, v, causal, window, k_shared)[0]


def _flash_fwd(q, k, v, causal, window, k_shared=None):
    b, s, h, d = q.shape
    d_v = v.shape[-1]
    d_s = 0 if k_shared is None else k_shared.shape[-1]
    block_q, block_k, d_pad, pre_scale = _plan(s, d, window, d_v,
                                               q.dtype.itemsize, bool(d_s))
    if block_q is None or block_k is None:
        out = _reference_attention(q, whole_key(k, k_shared), v, causal,
                                   window)
        return out, (q, k, v, k_shared, None, None)
    out, lse = _flash_attention_fwd_flat(
        _to_flat(q * pre_scale, d_pad), _to_flat(k, d_pad - d_s),
        _to_flat(v, _d_pad(d_v)), causal=causal, block_q=block_q,
        block_k=block_k, interpret=not on_tpu(), window=window,
        heads=_heads_of(b * h, window), k_shared=k_shared)
    out = out[:, :, :d_v].reshape(b, h, s, d_v)
    out = jnp.swapaxes(out, 1, 2)
    return out, (q, k, v, k_shared, out, lse)


def _flash_bwd_dq_kernel(*refs, block_q: int, block_k: int, causal: bool,
                         window=None):
    # K/V stream a block a step while this q block's dq accumulates in VMEM
    # scratch (mirror of the fwd, and its two grids).
    banded = window is not None
    if banded:
        j = pl.program_id(1)
        t = pl.program_id(2)
        nk = pl.num_programs(2)
        kb = _band_first_k(j, block_q, block_k, window) + t
    else:
        j, kb, first, last, _ = _scheduled_pair(*refs[:3])
        refs = refs[3:]
    q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs

    @pl.when(t == 0 if banded else first)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _update(masked: bool):
        # q pre-scaled by 1/sqrt(d): s needs no per-block multiply.
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (BQ, BK)
        # softmax from saved stats: p = exp(s - lse)
        p = jnp.exp(s - lse_ref[0])
        if masked:
            p = jnp.where(_seen_in_block(j, kb, block_q, block_k, window),
                          p, 0.0)
        dp = jax.lax.dot_general(
            g_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (BQ, BK)
        # ds carries NO scale: the caller folds 1/sqrt(d) into the
        # final (BH,S,D) dq multiply — one pass instead of one per
        # (BQ,BK) block.
        ds = p * (dp - delta_ref[0])
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (BQ, D)

    if banded:
        in_band = jnp.logical_or(
            jnp.logical_not(causal),
            kb * block_k <= j * block_q + block_q - 1)
        pl.when(in_band)(lambda: _update(True))
    else:
        _update(causal)

    @pl.when(t == nk - 1 if banded else last)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(*refs, block_q: int, block_k: int, causal: bool,
                          window=None, n_q_blocks=None, dq_ref=None,
                          dq_scr=None):
    # Q/G stream a block a step while this k block's dk/dv accumulate in
    # VMEM scratch.  A full call's grid is (bh, pair), its schedule by key
    # block.  A banded call's is (bh, nk, step): the inner axis counts from
    # the first query block that meets this key block and a step past the
    # band's last (or the sequence's last) is skipped.
    # With ``dq_ref`` this is the ONE backward kernel: dq of the whole flat
    # head waits in ``dq_scr`` ([seq, d] float32) through the head's sweep,
    # block (t, j) adds its ``ds k`` to the rows of query block j (for a
    # query block the key blocks still come in ascending order, as in the
    # dq kernel), and the head's last grid step casts it into ``dq_ref``, a
    # whole-head output block: scores, exp, mask and ``dp`` are computed
    # once, and no float32 dq and no partial ever lies in HBM.
    banded = window is not None
    if banded:
        t = pl.program_id(1)
        u = pl.program_id(2)
        nq = pl.num_programs(2)
        j = _band_first_q(t, block_q, block_k) + u
    else:
        j, t, first, last, _ = _scheduled_pair(*refs[:3])
        refs = refs[3:]
    (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr,
     dv_scr) = refs

    @pl.when(u == 0 if banded else first)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if dq_ref is not None:
        @pl.when(jnp.logical_and(t == 0, u == 0) if banded
                 else pl.program_id(1) == 0)
        def _init_head():
            dq_scr[:] = jnp.zeros_like(dq_scr)

    def _update(masked: bool):
        # q pre-scaled by 1/sqrt(d): s needs no per-block multiply.
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (BQ, BK)
        p = jnp.exp(s - lse_ref[0])
        if masked:
            p = jnp.where(_seen_in_block(j, t, block_q, block_k, window),
                          p, 0.0)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(g_ref.dtype), g_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (BK, D)
        dp = jax.lax.dot_general(
            g_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (BQ, BK)
        # ds @ q_prescaled == scale * (ds_raw @ q): with q carrying
        # 1/sqrt(d), dk needs NO scale anywhere.
        ds = p * (dp - delta_ref[0])
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (BK, D)
        if dq_ref is not None:
            rows = pl.ds(pl.multiple_of(j * block_q, block_q), block_q)
            dq_scr[rows, :] += jax.lax.dot_general(
                ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # (BQ, D)

    if banded:
        in_band = jnp.logical_or(
            jnp.logical_not(causal),
            j * block_q + block_q - 1 >= t * block_k)
        in_band = jnp.logical_and(
            in_band,
            j <= _band_last_q(t, block_q, block_k, window, n_q_blocks))
        pl.when(in_band)(lambda: _update(True))
    else:
        _update(causal)

    @pl.when(u == nq - 1 if banded else last)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    if dq_ref is not None:
        @pl.when(jnp.logical_and(t == pl.num_programs(1) - 1, u == nq - 1)
                 if banded else pl.program_id(1) == pl.num_programs(1) - 1)
        def _finish_head():
            dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_attention_bwd_flat(q, k, v, g, lse, delta, *, causal: bool,
                              block_q: int, block_k: int,
                              interpret: bool, window=None, heads: int = 1,
                              k_shared=None):
    """Flat backward via the two Pallas kernels above ((BH, S, D) q, k and
    (BH, S, Dv) v, g); returns (dq, dk, dv) with dq still in the fwd's q
    scaling.  ``heads`` flat heads a grid step (``_heads_a_step``).  With
    ``k_shared`` (B, S, Ds) of a full call, k and dk hold the key's first
    D - Ds columns and the shared part's gradient comes behind dk, a flat
    head's share each: (dq, dk, (BH, S, Ds), dv)."""
    from jax.experimental.pallas import tpu as pltpu
    bh, seq, d = q.shape
    d_v = v.shape[-1]
    banded = window is not None
    slab = () if heads == 1 else (heads,)
    shared, shared_key, d_s = _shared_of(k_shared, bh)
    sweep = _query_sweep(seq, block_q, block_k, d, causal, window, heads,
                         "dq", d_v, shared)
    kernel = functools.partial(
        _flash_bwd_dq_kernel, block_q=block_q, block_k=block_k,
        causal=causal, window=window)
    if shared:
        kernel = _key_joined(kernel, len(sweep.tables) + 1)
    with jax.named_scope(scopes.FLASH_WINDOW_DQ) if banded \
            else jax.named_scope(scopes.FLASH_DQ):
        dq = pl.pallas_call(
            _heads_a_step(kernel, heads, len(sweep.tables)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(sweep.tables),
                grid=(bh // heads,) + sweep.axes,
                in_specs=[sweep.q, sweep.k, *sweep.shared, sweep.v, sweep.o,
                          sweep.rows, sweep.rows],
                out_specs=sweep.q,
                scratch_shapes=[
                    pltpu.VMEM(slab + (block_q, d), jnp.float32)]),
            out_shape=_sds((bh, seq, d), q.dtype, q),
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel",) + sweep.semantics),
            interpret=interpret,
            name=_sized(scopes.kernel_name(
                scopes.FLASH_WINDOW_DQ if banded else scopes.FLASH_DQ),
                d, d_v, d_s),
        )(*sweep.tables, q, k, *shared_key, v, g, lse, delta)
    with jax.named_scope(scopes.FLASH_WINDOW_DKV) if banded \
            else jax.named_scope(scopes.FLASH_DKV):
        return (dq, *_flash_bwd_by_key_block(
            q, k, v, g, lse, delta, causal=causal, block_q=block_q,
            block_k=block_k, interpret=interpret, window=window, heads=heads,
            name=scopes.kernel_name(scopes.FLASH_WINDOW_DKV if banded
                                    else scopes.FLASH_DKV),
            k_shared=k_shared))


def _flash_bwd_by_key_block(q, k, v, g, lse, delta, *, causal: bool,
                            block_q: int, block_k: int, interpret: bool,
                            window, heads: int, name: str,
                            with_dq: bool = False, k_shared=None):
    """The ``pallas_call`` that sweeps a key block's queries: a full call's
    grid is its schedule by key block, a banded call's (bh, k block, q
    step), the query blocks that meet the key block (the last one held for
    the steps a narrower key block has left over).  (dk, dv), or with
    ``with_dq`` (dq, dk, dv) from the one kernel, whose dq is a whole-head
    block that stays put through the head's sweep.  q, k, dq and dk are
    ``d`` wide, v, g and dv ``d_v``.  With ``k_shared`` (B, S, Ds) of a full
    call, k and dk are the key's own ``d - Ds`` columns and the shared
    part's gradient, a flat head's share (BH, S, Ds), comes behind dk."""
    from jax.experimental.pallas import tpu as pltpu
    bh, seq, d = q.shape
    d_v = v.shape[-1]
    slab = () if heads == 1 else (heads,)
    nq = seq // block_q
    shared, shared_key, d_s = _shared_of(k_shared, bh)
    shared_in = shared_out = ()
    if window is None:
        kind = "onepass" if with_dq else "dkv"
        pairs = _block_schedule(seq, block_q, block_k, causal)[1]
        tables = _schedule_tables(pairs, kind)
        axes, semantics = (len(pairs.q),), ("arbitrary",)
        qspec2, kspec2, rowspec2 = _scheduled_specs(block_q, block_k, d,
                                                    heads)
        gspec2, vspec2, _ = _scheduled_specs(block_q, block_k, d_v, heads)
        if shared:
            kspec2 = _scheduled_specs(block_q, block_k, d - d_s, heads)[1]
            shared_in = (_shared_key_spec(block_k, *shared, kind),)
            shared_out = (_scheduled_specs(block_q, block_k, d_s, heads)[1],)
    else:
        def q_at(t, u):
            return jnp.minimum(
                _band_first_q(t, block_q, block_k) + u,
                _band_last_q(t, block_q, block_k, window, nq))

        def q_spec(width):
            return pl.BlockSpec((heads, block_q, width),
                                lambda i, t, u: (i, q_at(t, u), 0))

        def k_spec(width):
            return pl.BlockSpec((heads, block_k, width),
                                lambda i, t, j: (i, t, 0))

        tables = ()
        axes = (seq // block_k, _band_steps(seq, block_q, block_k, window)[1])
        # Every key block of a head but its last leaves the one kernel's dq
        # unfinished, so the key axis is then nobody's to split.
        semantics = ("arbitrary" if with_dq else "parallel", "arbitrary")
        qspec2, kspec2, rowspec2 = q_spec(d), k_spec(d), q_spec(1)
        gspec2, vspec2 = q_spec(d_v), k_spec(d_v)
    kernel = functools.partial(
        _flash_bwd_onepass_kernel if with_dq else _flash_bwd_dkv_kernel,
        block_q=block_q, block_k=block_k, causal=causal, window=window,
        n_q_blocks=nq)
    if shared:
        # the key among the inputs, its gradient among the outputs
        kernel = _key_joined(kernel, len(tables) + 1,
                             len(tables) + 6 + with_dq)
    out_specs = [kspec2, *shared_out, vspec2]
    out_shape = [_sds((bh, seq, d - d_s), k.dtype, k),
                 *(_sds((bh, seq, d_s), k.dtype, k) for _ in shared_out),
                 _sds((bh, seq, d_v), v.dtype, v)]
    scratch = [pltpu.VMEM(slab + (block_k, d), jnp.float32),
               pltpu.VMEM(slab + (block_k, d_v), jnp.float32)]
    params = {}
    if with_dq:
        out_specs.insert(0, pl.BlockSpec((heads, seq, d),
                                         lambda i, *at: (i, 0, 0)))
        out_shape.insert(0, _sds((bh, seq, d), q.dtype, q))
        scratch.insert(0, pltpu.VMEM(slab + (seq, d), jnp.float32))
        params = {"vmem_limit_bytes": _ONEPASS_VMEM_ROOM + _onepass_vmem_bytes(
            heads, seq, d, q.dtype.itemsize)}
    return pl.pallas_call(
        _heads_a_step(kernel, heads, len(tables)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(bh // heads,) + axes,
            in_specs=[qspec2, kspec2, *shared_in, vspec2, gspec2, rowspec2,
                      rowspec2],
            out_specs=out_specs,
            scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",) + semantics, **params),
        interpret=interpret,
        name=_sized(name, d, d_v, d_s),
    )(*tables, q, k, *shared_key, v, g, lse, delta)


def _flash_bwd_onepass_kernel(*refs, **plan):
    """ONE kernel for dq, dk and dv: the dk/dv kernel with dq of the whole
    flat head kept in VMEM (refs in ``pallas_call``'s order: a schedule's
    tables, the six inputs, then dq ahead of dk and dv twice)."""
    *inputs, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = refs
    _flash_bwd_dkv_kernel(*inputs, dk_ref, dv_ref, dk_scr, dv_scr,
                          dq_ref=dq_ref, dq_scr=dq_scr, **plan)


def _flash_attention_bwd_onepass_flat(q, k, v, g, lse, delta, *,
                                      causal: bool, block_q: int,
                                      block_k: int, interpret: bool,
                                      window=None, heads: int = 1,
                                      k_shared=None):
    """Flat (BH, S, D) backward via the one kernel; returns (dq, dk, dv)
    in the inputs' dtypes with dq still in the fwd's q scaling.  A banded
    call's kernel sits under ``hvd.flash_window_dkv``: the scope that reads
    the banded backward's time.  ``k_shared`` as in
    ``_flash_attention_bwd_flat``: (dq, dk, the shared part's, dv)."""
    banded = window is not None
    with jax.named_scope(scopes.FLASH_WINDOW_DKV) if banded \
            else jax.named_scope(scopes.FLASH_BWD_ONEPASS):
        return _flash_bwd_by_key_block(
            q, k, v, g, lse, delta, causal=causal, block_q=block_q,
            block_k=block_k, interpret=interpret, window=window, heads=heads,
            name=scopes.kernel_name(scopes.FLASH_WINDOW_DKV if banded
                                    else scopes.FLASH_BWD_ONEPASS),
            with_dq=True, k_shared=k_shared)


def _flash_bwd_chunked(causal, window, res, g):
    q, k, v = res
    b, s, h, _ = q.shape
    # bigger blocks = fewer scan steps (measured 23% faster at 2048 vs
    # 512 for seq 4096 on one chip).  Peak extra memory per step is ~3
    # concurrent (b,h,block,s) f32 score-shaped temporaries (p, dp,
    # ds); cap that at ~4 GB (a quarter of a 16 GB-HBM chip) when
    # choosing the block.
    budget = 4 << 30
    per_block_row = 3 * b * h * s * 4
    cap = max(64, budget // max(1, per_block_row))
    block = next((bq for bq in (2048, 1024, 512, 256, 128, 64)
                  if bq <= cap and s % bq == 0), None)
    if block is None:  # irregular/large: direct vjp on the reference
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _reference_attention(q_, k_, v_, causal,
                                                    window),
            q, k, v)
        return vjp(g)
    return _chunked_attention_bwd(q, k, v, g, causal, block, window)


def _flash_bwd(causal, window, res, g):
    q, k, v, k_shared, o, lse = res

    def count(form):
        # As the call is traced: once for every time the layer scan and the
        # recomputation trace it, not once a step.
        metrics.counter("hvd_flash_backward_calls_total", form=form,
                        window=str(int(window is not None))).inc()

    if lse is None:  # fwd fell back to plain XLA attention
        count("xla")
        _, vjp = jax.vjp(
            lambda q_, k_, v_, shared_: _reference_attention(
                q_, whole_key(k_, shared_), v_, causal, window),
            q, k, v, k_shared)
        return vjp(g)
    b, s, h, d = q.shape
    d_v = v.shape[-1]
    d_s = 0 if k_shared is None else k_shared.shape[-1]
    block_q, block_k, d_pad, pre_scale = _plan(s, d, window, d_v,
                                               q.dtype.itemsize, bool(d_s))
    form, heads = _backward_form(b * h, s, d_pad, q.dtype.itemsize, window)
    count(form)
    if form == "chunked":
        # A/B escape hatch (docs/benchmarks.md records the comparison).
        whole, parts = jax.vjp(whole_key, k, k_shared)
        dq, dk, dv = _flash_bwd_chunked(causal, window, (q, whole, v), g)
        dk, dk_shared = parts(dk)
        return dq, dk, dv, dk_shared
    # delta = rowsum(g ⊙ o): the softmax-jacobian correction term,
    # cheap in XLA (one elementwise pass).  Unit lane dim to match the
    # lse layout.
    delta = jnp.sum(jnp.swapaxes(g, 1, 2).astype(jnp.float32)
                    * jnp.swapaxes(o, 1, 2).astype(jnp.float32),
                    axis=-1).reshape(b * h, s, 1)
    bwd_flat = (_flash_attention_bwd_onepass_flat if form == "onepass"
                else _flash_attention_bwd_flat)
    dq, dk, *dk_shared, dv = bwd_flat(
        _to_flat(q * pre_scale, d_pad), _to_flat(k, d_pad - d_s),
        _to_flat(v, _d_pad(d_v)), _to_flat(g, _d_pad(d_v)), lse, delta,
        causal=causal, block_q=block_q, block_k=block_k,
        interpret=not on_tpu(), window=window, heads=heads,
        k_shared=k_shared)
    # The kernels differentiate w.r.t. the PRE-SCALED q, so
    # d(loss)/d(q) = dq_flat * pre_scale; dk comes out exact with no
    # correction (ds^T @ q_prescaled == scale * ds_raw^T @ q).  The
    # scale multiply runs in f32 BEFORE the final dtype cast so dq
    # picks up one rounding, not two.
    # The shared part's gradient is the sum of its heads' shares, added up
    # in float32 ([b h, s, d_s] -> [b, s, d_s]).
    return (_from_flat(dq.astype(jnp.float32) * pre_scale, b, h, d, q),
            _from_flat(dk, b, h, d - d_s, k),
            _from_flat(dv, b, h, d_v, v),
            dk_shared[0].reshape(b, h, s, d_s).sum(1, dtype=jnp.float32)
            .astype(k_shared.dtype) if dk_shared else None)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = True, window=None,
                    k_shared=None):
    """Fused blocked attention, layout ``(batch, seq, heads, dim)``
    (the framework's attention layout).  Differentiable; compiled
    Pallas on TPU, interpreted elsewhere.  Sequences not divisible by
    64 fall back to plain XLA attention.  ``q`` and ``k`` are ``[B, S, H,
    d_qk]``, ``v`` ``[B, S, H, d_v]`` and the result ``[B, S, H, d_v]``; the
    scores are scaled by ``d_qk^-1/2``.  The two sizes may differ (latent
    attention's 192 over 128): the same kernels then contract the scores
    over ``d_qk`` as it is and keep ``d_v`` for the values, the output and
    their gradients, under names that end in the sizes
    (``hvd_flash_fwd_192x128``); nothing is padded to the larger size, and
    where the sizes are equal the call is what it was.  Grouped queries
    (fewer key/value heads than query heads): every key/value head is
    repeated for its group before the kernels, and the repeat's transpose
    sums the group's gradients.  A call's kernels visit the block
    pairs that hold work and no others (``_block_schedule``: under the
    causal mask the pairs that meet the triangle).  ``window``: a causal
    query sees its last ``window`` keys, itself among them; the kernels
    then visit the blocks of that band alone, under scopes and names of
    their own (``hvd.flash_window_*``), at one head size or two.
    ``k_shared``: ``[B, S, d_s]``, columns that every head's key ends in
    (latent attention's one rotary key a position): ``k`` is then the
    heads' own ``[B, S, H, d_k]``, ``q`` ``[B, S, H, d_k + d_s]``, the
    scores ``q[..., :d_k] . k + q[..., d_k:] . k_shared`` scaled by ``(d_k +
    d_s)^-1/2``, and the result and the gradients those of a call with
    ``whole_key(k, k_shared)``; the kernels read the part as an operand of
    its own, once a batch entry, and the key is never built at ``d_k + d_s``
    a head in HBM.  Full calls only: under a window it is refused."""
    if window is not None:
        if not causal or window < 1:
            raise ValueError("a window of %r keys needs a causal mask and "
                             "at least the query itself" % (window,))
        if window >= q.shape[1]:
            window = None           # the band is the whole triangle
    if k_shared is not None:
        if window is not None:
            raise ValueError(
                "a window of %d keys over a key with a shared part: the "
                "banded kernels take the key whole (hand them "
                "whole_key(k, k_shared))" % window)
        if k_shared.shape != k.shape[:2] + (q.shape[-1] - k.shape[-1],):
            raise ValueError(
                "k_shared %s is not [B, S, d_qk - d_k] of q %s and k %s"
                % (k_shared.shape, q.shape, k.shape))
        # One key for heads that a mesh axis may split (tp): varying like
        # them from here on, so that its gradient is summed over that axis
        # as the repeat over the heads would have had it summed.
        from ..parallel.ring_attention import pvary_missing
        k_shared = pvary_missing(k_shared, tuple(jax.typeof(k).vma))
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return _flash_attention(q, k, v, causal, window, k_shared)


def use_flash_attention() -> bool:
    """Whether a model calls ``flash_attention``: on the TPU, where it is
    compiled.  On the CPU test world the models take their XLA attention,
    because the interpreted kernel is too slow for a training loop.  Any
    other backend is an error (``common/device.py``)."""
    return on_tpu()


# ---------------------------------------------------------------------------
# flash block autotune (the kernel-parameter leg of the autotune plane)
# ---------------------------------------------------------------------------

def flash_plan_info(s: int, d: int) -> dict:
    """Attribution record for the benchmark JSON: which blocks the plan
    would pick for (seq, head_dim) of a ``d_qk == d_v`` call and WHY (env override, autotuned
    pin, or default chain), plus the active backward variant.  Pure
    metadata — never traces or compiles anything."""
    import os
    block_q, block_k, d_pad, _ = _plan(s, d)
    if os.environ.get("HVD_TPU_FLASH_BLOCK_Q") or \
            os.environ.get("HVD_TPU_FLASH_BLOCK_K"):
        source = "env"
    elif (s, d_pad) in _TUNED_BLOCKS:
        source = "autotuned"
    elif block_q is None or block_k is None:
        source = "fallback_xla"
    else:
        source = "default"
    bwd = "xla" if source == "fallback_xla" else _backward_form(
        1, s, d_pad, jnp.dtype(jnp.bfloat16).itemsize, None)[0]
    return {"block_q": block_q, "block_k": block_k, "d_pad": d_pad,
            "source": source, "bwd": bwd}


def flash_block_candidates(seq: int, d: int,
                           vmem_budget_bytes: int = 12 << 20):
    """(block_q, block_k) sweep grid for one (seq, head_dim) shape of a
    ``d_qk == d_v`` call: every sublane-aligned pair dividing the sequence whose resident
    f32 working set (scores + dq/dk/dv accumulators + double-buffered
    in/out blocks) fits the VMEM budget (~16 MB/core minus headroom)."""
    d_pad = _d_pad(d)
    out = []
    for bq in (64, 128, 256, 512, 1024):
        if seq % bq:
            continue
        for bk in (64, 128, 256, 512, 1024, 2048):
            if seq % bk:
                continue
            est = (4 * (2 * bq * bk + bq * d_pad + 2 * bk * d_pad)
                   + 2 * 2 * (bq + bk) * d_pad)
            if est <= vmem_budget_bytes:
                out.append((bq, bk))
    return out


def _time_device(fn, args, iters: int) -> float:
    """Per-call seconds via differential timing: a loop of 2N calls
    minus a loop of N, each loop ended by fetching one scalar of its
    last output, so the fixed dispatch and fetch cost cancels (the
    bench.py discipline)."""
    import time

    def first_leaf(tree):
        leaves = jax.tree_util.tree_leaves(tree)
        return leaves[0]

    fetch = jax.jit(lambda v: v.reshape(-1)[0].astype(jnp.float32))

    def run(n):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn(*args)
        float(np.asarray(fetch(first_leaf(out))))
        return time.perf_counter() - t0

    run(max(1, iters // 2))  # warm (compile + dispatch path)
    t1, t2 = run(iters), run(2 * iters)
    return max(t2 - t1, 1e-9) / iters


def autotune_flash_blocks(seq: int, d: int, *, batch_heads: int = 8,
                          dtype=None, causal: bool = True,
                          iters: int = 4, candidates=None,
                          include_bwd: bool = True,
                          allreduce_scores=None, report_core=True,
                          pin: bool = True):
    """Measure fwd(+bwd) TFLOP/s for each (block_q, block_k) candidate
    of a ``d_qk == d_v`` call (a call at two head sizes takes the default
    chains, ``_plan``)
    on the local device and PIN the winner into the plan registry, so
    the blocks the kernels run with are tuned, not hardcoded (the
    kernel-parameter leg of the autotune plane; fusion/cycle stay with
    the GP tuner).

    SPMD safety: every rank must compile the SAME kernel.  Pass
    ``allreduce_scores`` (e.g. ``lambda v: hvd.allreduce(v, op=Average)``)
    to average the per-candidate scores across ranks before the argmax
    — a deterministic reduction of identical-length vectors, so every
    rank pins the same pair.  Scores are also reported to the native
    core's KernelTuner (``hvd_tcp_kernel_tune_record``) when the TCP
    control plane is up, for cross-run observability.

    Returns the attribution dict: candidates, per-candidate TFLOP/s,
    the winner, and whether an env override suppressed pinning.
    """
    import os

    from ..utils.autotune import KernelBlockTuner

    dtype = dtype or jnp.bfloat16
    d_pad = _d_pad(d)
    cands = list(candidates or flash_block_candidates(seq, d))
    if not cands:
        return {"candidates": [], "best": None, "pinned": False}
    interp = not on_tpu()
    bh = int(batch_heads)
    rng = np.random.RandomState(0)
    # Random payloads: softmax over real score ranges, not the
    # degenerate all-equal rows a constant input would give.
    q = jnp.asarray(rng.randn(bh, seq, d_pad), dtype)
    k = jnp.asarray(rng.randn(bh, seq, d_pad), dtype)
    v = jnp.asarray(rng.randn(bh, seq, d_pad), dtype)
    g = jnp.asarray(rng.randn(bh, seq, d_pad), dtype)
    # Causal attention touches half the tiles; 2 matmuls fwd, 5 bwd.
    tile_frac = 0.5 if causal else 1.0
    fwd_flops = 4.0 * bh * seq * seq * d_pad * tile_frac
    bwd_flops = 2.5 * fwd_flops

    tuner = KernelBlockTuner(cands)
    records = {}
    for idx, (bq, bk) in enumerate(cands):
        fwd = jax.jit(functools.partial(
            _flash_attention_fwd_flat, causal=causal, block_q=bq,
            block_k=bk, interpret=interp))
        t_fwd = _time_device(fwd, (q, k, v), iters)
        total_t, total_f = t_fwd, fwd_flops
        t_bwd = None
        if include_bwd:
            out, lse = fwd(q, k, v)
            delta = jnp.sum(g.astype(jnp.float32)
                            * out.astype(jnp.float32),
                            axis=-1, keepdims=True)
            # the form a call of this shape takes (the one kernel where
            # dq fits in VMEM), at one head a grid step
            onepass = _backward_form(bh, seq, d_pad, q.dtype.itemsize,
                                     None)[0] == "onepass"
            bwd = jax.jit(functools.partial(
                _flash_attention_bwd_onepass_flat if onepass
                else _flash_attention_bwd_flat, causal=causal, block_q=bq,
                block_k=bk, interpret=interp))
            t_bwd = _time_device(bwd, (q, k, v, g, lse, delta), iters)
            total_t += t_bwd
            total_f += bwd_flops
        score = total_f / total_t
        tuner.record(idx, score)
        records[(bq, bk)] = {
            "fwd_tflops": fwd_flops / t_fwd / 1e12,
            "bwd_tflops": (bwd_flops / t_bwd / 1e12
                           if t_bwd else None),
            "score_tflops": score / 1e12,
        }

    scores = tuner.scores_vector()
    if allreduce_scores is not None:
        # Cross-rank mean: identical argmax input on every rank.
        scores = np.asarray(allreduce_scores(
            np.asarray(scores, np.float64)))
    if report_core:
        try:
            from ..common import basics
            core = basics._get_tcp_core()
            if core is not None:
                for idx in range(len(cands)):
                    core.kernel_tune_record(idx, float(scores[idx]))
        except Exception:  # noqa: BLE001 - observability only
            pass
    best = cands[int(np.argmax(scores))]
    pinned = False
    if pin and not (os.environ.get("HVD_TPU_FLASH_BLOCK_Q")
                    or os.environ.get("HVD_TPU_FLASH_BLOCK_K")):
        # Env overrides win over the tuner (explicit A/Bs must stay
        # what the operator asked for).
        _TUNED_BLOCKS[(seq, d_pad)] = best
        pinned = True
    return {"candidates": cands, "samples": records, "best": best,
            "pinned": pinned,
            "scores_tflops": [float(x) / 1e12 for x in scores]}


# ---------------------------------------------------------------------------
# fused scale + sum (the reference's ScaleAdd fusion kernel)
# ---------------------------------------------------------------------------

def _scale_sum_kernel(a_ref, b_ref, o_ref, *, alpha: float, beta: float):
    o_ref[:] = (alpha * a_ref[:].astype(jnp.float32) +
                beta * b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def fused_scale_sum(a, b, alpha: float = 1.0, beta: float = 1.0):
    """``alpha*a + beta*b`` in one VPU pass (reference ``ScaleAdd`` in
    ``cuda_kernels.cu``, used for pre/postscaled fusion-buffer math).
    Gridded in ~2MB tiles so fusion buffers far larger than VMEM
    stream through."""
    flat_a = a.reshape(-1)
    flat_b = b.reshape(-1)
    n = flat_a.shape[0]
    lane = 128
    block_rows = 4096                       # 4096×128 f32 = 2 MiB/tile
    rows = (n + lane - 1) // lane
    rows = ((rows + block_rows - 1) // block_rows) * block_rows
    pad = rows * lane - n
    if pad:
        flat_a = jnp.pad(flat_a, (0, pad))
        flat_b = jnp.pad(flat_b, (0, pad))
    kernel = functools.partial(_scale_sum_kernel, alpha=alpha,
                               beta=beta)
    out = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, lane), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, lane), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, lane), lambda i: (i, 0)),
        out_shape=_sds((rows, lane), a.dtype, a),
        interpret=not on_tpu(),
    )(flat_a.reshape(rows, lane), flat_b.reshape(rows, lane))
    return out.reshape(-1)[:n].reshape(a.shape)
