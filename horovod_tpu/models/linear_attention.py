"""Linear attention by the gated delta rule with a decay for every channel
("KDA"): the mixer of a hybrid decoder's linear layers.

For each head, with keys and values of size ``D``, a state ``S`` (``D x D``,
zero where a sequence starts) follows

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = D^-1/2 * S_t^T q_t

``g_t <= 0`` is a log-decay for every key channel and ``beta_t`` in (0, 2)
the writing strength; above 1 the transition has negative eigenvalues.
``kda_chunked`` computes this a chunk of ``C`` steps at a time.  With
``G`` the running sum of ``g`` inside a chunk and ``S`` the state at its
start, the pseudo-values ``w_r = beta_r (v_r - (k_r e^{G_r})^T S -
sum_{i<r} A_ri w_i)`` solve one unit-triangular system a chunk, where
``A_ri = sum_d k_rd k_id e^{G_rd - G_id}``; then

    S' = Diag(e^{G_C}) S + sum_i (k_i e^{G_C - G_i}) w_i^T
    o_r = D^-1/2 ((q_r e^{G_r})^T S + sum_{i<=r} P_ri w_i)

with ``P`` as ``A`` but of q against k.  Everything of a chunk that does
not need ``S`` is matrix products over many chunks at once; only
``S' = M S + N`` is a scan over chunks.  No exponent is ever positive
(``_decayed_gram``), so a strong decay neither overflows nor loses the
near steps.  State and decays are float32 and the products run at full
float32 precision: they are a hundredth of the step's operations and the
state is reused 128 times a sequence.

Two forms, chosen by shape (``kda_chunked``).  Heads that fill the 128
lanes take the Pallas kernels of ``ops/kda_kernels.py``: a forward kernel
that keeps the head's state and a chunk's matrices in VMEM, and a custom
VJP whose backward kernel walks the segments in reverse.  Other heads
(the tests' small configurations) take the XLA form below, whose backward
pass is autodiff through ``_segment``; it is also the kernels' oracle.

``linear_attention_block`` is the whole mixer: projections, a causal
depthwise convolution, the core, a gated RMSNorm over each head and the
output projection.  Heads are sharded over the tensor-parallel axis like
attention's; the sequence is not split (the state would have to travel).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..common import scopes
from ..ops import kda_kernels
from ..parallel.ring_attention import pvary_missing

HI = lax.Precision.HIGHEST
# ``checkpoint_name``s of what a recomputed layer keeps of its delta rule:
# the state each segment starts from and the core's output.
SAVED = ("kda_segment_state", "kda_output")


@dataclasses.dataclass(frozen=True)
class KdaConfig:
    n_heads: int                # heads held here (sharded over tp)
    head_size: int = 128        # of keys and of values
    conv_size: int = 4          # taps of the causal depthwise convolution
    gate_rank: int = 128        # inner width of the two low-rank gates
    chunk: int = 64
    norm_eps: float = 1e-5

    @property
    def width(self) -> int:
        return self.n_heads * self.head_size


def init_kda_params(key, d_model: int, cfg: KdaConfig, n: int, dtype):
    """``n`` stacked layers.  The decay's ``a_log`` and ``decay_bias``
    start where the gated-delta-rule family's published layers start
    them: ``exp(a_log)`` uniform in [1, 16], ``softplus(decay_bias)``
    log-uniform in [0.001, 0.1]."""
    h, w, r = cfg.n_heads, cfg.width, cfg.gate_rank
    ks = jax.random.split(key, 13)

    def norm(k, shape, fan_in):
        return (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(dtype)

    dt = jnp.exp(jax.random.uniform(ks[11], (n, w), minval=math.log(1e-3),
                                    maxval=math.log(1e-1)))
    return {
        "wq": norm(ks[0], (n, d_model, w), d_model),
        "wk": norm(ks[1], (n, d_model, w), d_model),
        "wv": norm(ks[2], (n, d_model, w), d_model),
        "conv_q": norm(ks[3], (n, cfg.conv_size, w), cfg.conv_size),
        "conv_k": norm(ks[4], (n, cfg.conv_size, w), cfg.conv_size),
        "conv_v": norm(ks[5], (n, cfg.conv_size, w), cfg.conv_size),
        "w_fa": norm(ks[6], (n, d_model, r), d_model),
        "w_fb": norm(ks[7], (n, r, w), r),
        "w_ga": norm(ks[8], (n, d_model, r), d_model),
        "w_gb": norm(ks[9], (n, r, w), r),
        "w_beta": norm(ks[10], (n, d_model, h), d_model),
        # softplus^-1(dt) = dt + log(1 - exp(-dt))
        "decay_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "a_log": jnp.log(jax.random.uniform(
            ks[12], (n, h), minval=1.0, maxval=16.0)).astype(dtype),
        "o_norm": jnp.ones((n, cfg.head_size), dtype),
        "wo": norm(jax.random.fold_in(key, 13), (n, w, d_model), w),
    }


def kda_param_specs(tp):
    """Heads over ``tp``: projections by column, ``wo`` by row, the
    gates' inner factor whole."""
    from jax.sharding import PartitionSpec as P
    cols = {name: P(None, None, tp) for name in
            ("wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "w_fb", "w_gb",
             "w_beta")}
    return dict(cols, w_fa=P(None, None, None), w_ga=P(None, None, None),
                decay_bias=P(None, tp), a_log=P(None, tp),
                o_norm=P(None, None), wo=P(None, tp, None))


# --------------------------------------------------------------------------
# The core
# --------------------------------------------------------------------------

def _decayed_gram(a, b, cum, strict: bool):
    """``M[r, i] = sum_d a[r, d] b[i, d] exp(cum[r, d] - cum[i, d])`` for
    ``i < r`` (``strict``) or ``i <= r``, else 0, over the last two axes
    ``[C, D]``; ``cum`` does not increase along ``C``.

    ``exp(-cum[i])`` alone overflows under a strong decay, so the chunk
    is cut into four sub-blocks.  Between two of them the exponent is
    split at the first row of the later one, ``(cum[r] - cum[s]) +
    (cum[s] - cum[i])`` with ``i < s <= r``, both parts <= 0, and the
    block is one matrix product; inside one the differences are taken
    directly."""
    c_len, d = a.shape[-2:]
    sub = max(1, c_len // 4)
    nb = c_len // sub
    lead = a.shape[:-2]
    a, b, cum = (x.reshape(lead + (nb, sub, d)) for x in (a, b, cum))
    start = cum[..., :1, :]                                 # [.., nb, 1, D]
    a_in = a * jnp.exp(cum - start)
    later = jnp.arange(nb)[:, None] > jnp.arange(nb)[None, :]   # [I, J]
    to_start = jnp.where(later[:, :, None, None],
                         start[..., :, None, :, :] - cum[..., None, :, :, :],
                         -jnp.inf)                          # [.., I, J, sub, D]
    between = jnp.einsum("...Ird,...IJid->...IrJi", a_in,
                         b[..., None, :, :, :] * jnp.exp(to_start),
                         precision=HI)
    rows = jnp.arange(sub)
    keep = rows[:, None] > rows[None, :] if strict \
        else rows[:, None] >= rows[None, :]
    diff = jnp.where(keep[:, :, None],
                     cum[..., :, None, :] - cum[..., None, :, :], -jnp.inf)
    inside = jnp.sum(a[..., :, None, :] * b[..., None, :, :] * jnp.exp(diff),
                     axis=-1)                               # [.., nb, sub, sub]
    same = jnp.eye(nb, dtype=bool)[:, None, :, None]
    out = jnp.where(same, inside[..., :, :, None, :], between)
    return out.reshape(lead + (c_len, c_len))


def _unit_lower_inverse(n):
    """``(I + n)^-1`` for strictly lower-triangular ``n`` over the last two
    axes ``[C, C]``, ``C`` a multiple of 4.  The four diagonal blocks by
    forward substitution, a row at a time (every chunk at once, so a row
    is one small batched product); then the blocks are merged two by two,
    ``[[a, 0], [c, b]]^-1 = [[a^-1, 0], [-b^-1 c a^-1, b^-1]]``, in matrix
    products.  As stable as substitution; XLA's triangular solve on the
    TPU takes ten times as long (PERF.md, PR 27)."""
    c_len = n.shape[-1]
    sub = c_len // 4
    lead = n.shape[:-2]
    blocks = n.reshape(lead + (4, sub, 4, sub))
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(4)], axis=-3)
    inv = jnp.broadcast_to(jnp.eye(sub, dtype=n.dtype), diag.shape)
    for r in range(1, sub):
        row = -jnp.einsum("...i,...ij->...j", diag[..., r, :r],
                          inv[..., :r, :], precision=HI)
        inv = inv.at[..., r, :].add(row)
    parts = [inv[..., i, :, :] for i in range(4)]
    size = sub
    while len(parts) > 1:
        merged = []
        for at in range(0, len(parts), 2):
            a, b = parts[at], parts[at + 1]
            lo = at * size
            c = n[..., lo + size:lo + 2 * size, lo:lo + size]
            c = -jnp.einsum("...ij,...jk,...kl->...il", b, c, a,
                            precision=HI)
            merged.append(jnp.concatenate(
                [jnp.concatenate([a, jnp.zeros_like(a)], axis=-1),
                 jnp.concatenate([c, b], axis=-1)], axis=-2))
        parts, size = merged, 2 * size
    return parts[0]


def _segment(state, q, k, v, g, beta):
    """A run of chunks from ``state`` ``[B, H, D, D]``: inputs
    ``[B, H, N, C, D]`` (``beta`` ``[B, H, N, C]``); returns the state
    after it and the outputs, unscaled."""
    d = q.shape[-1]
    cum = jnp.cumsum(g, axis=-2)
    whole = cum[..., -1:, :]                                # [B,H,N,1,D]
    k_in = k * jnp.exp(cum)
    q_in = q * jnp.exp(cum)
    k_out = k * jnp.exp(whole - cum)
    a = _decayed_gram(k, k, cum, strict=True)
    p = _decayed_gram(q, k, cum, strict=False)
    # (I + Diag(beta) A) [u | w_k] = Diag(beta) [v | k e^G]
    solved = jnp.einsum(
        "...ri,...ie->...re", _unit_lower_inverse(beta[..., None] * a),
        beta[..., None] * jnp.concatenate([v, k_in], axis=-1), precision=HI)
    u, w_k = solved[..., :d], solved[..., d:]
    # S' = M S + N, one chunk after another.
    m = jnp.exp(whole)[..., 0, :, None] * jnp.eye(d, dtype=jnp.float32) \
        - jnp.einsum("...cd,...ce->...de", k_out, w_k, precision=HI)
    nn = jnp.einsum("...cd,...ce->...de", k_out, u, precision=HI)

    def advance(state, mn):
        m_c, n_c = mn
        return jnp.einsum("bhde,bhef->bhdf", m_c, state, precision=HI) \
            + n_c, state

    state, starts = lax.scan(advance, state, (jnp.moveaxis(m, 2, 0),
                                              jnp.moveaxis(nn, 2, 0)))
    starts = jnp.moveaxis(starts, 0, 2)                     # [B,H,N,D,D]
    w = u - jnp.einsum("...cd,...de->...ce", w_k, starts, precision=HI)
    return state, jnp.einsum("...cd,...de->...ce", q_in, starts,
                             precision=HI) \
        + jnp.einsum("...ri,...ie->...re", p, w, precision=HI)


def kda_chunked_xla(q, k, v, g, beta, chunk: int, segment: int = 16):
    """``kda_chunked`` in XLA operations, for the shapes the kernels do not
    take.  A segment's chunk-local matrices are computed anew in the
    backward pass, which is autodiff through ``_segment``: what outlives
    a segment is its ``[B, H, D, D]`` start state."""
    bsz, s, h, d = q.shape
    n = s // chunk
    per = math.gcd(n, segment)

    def segments(x):        # [B, S, H, ...] -> [segments, B, H, per, C, ...]
        x = x.astype(jnp.float32).reshape(
            (bsz, n // per, per, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 4, 1), 2, 0)

    args = tuple(segments(x) for x in (q, k, v, g, beta))
    # The start state has to vary over the mesh axes the inputs vary over.
    start = pvary_missing(jnp.zeros((bsz, h, d, d), jnp.float32),
                          tuple(jax.typeof(args[0]).vma))

    def walk(state, xs):
        state = checkpoint_name(state, SAVED[0])
        state, o = jax.checkpoint(_segment)(state, *xs)
        return state, checkpoint_name(o, SAVED[1])

    _, o = lax.scan(walk, start, args)
    # [segments, B, H, per, C, D] -> [B, S, H, D]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 4).reshape(bsz, s, h, d)
    return o * (1.0 / math.sqrt(d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda_kernels(q, k, kb, vb, g, chunk, per, d):
    """``ops/kda_kernels.py`` over ``[B, S, H D]``, with ``kb = beta k``
    and ``vb = beta v`` so that autodiff outside takes ``beta``'s part."""
    return kda_kernels.forward(q, k, kb, vb, g, chunk, per, d)[0]


def _kda_kernels_fwd(q, k, kb, vb, g, chunk, per, d):
    o, starts = kda_kernels.forward(q, k, kb, vb, g, chunk, per, d)
    return checkpoint_name(o, SAVED[1]), \
        (q, k, kb, vb, g, checkpoint_name(starts, SAVED[0]))


def _kda_kernels_bwd(chunk, per, d, res, do):
    return kda_kernels.backward(*res[:5], do, res[5], chunk, per, d)


_kda_kernels.defvjp(_kda_kernels_fwd, _kda_kernels_bwd)


def kda_chunked(q, k, v, g, beta, chunk: int, segment: int = 16):
    """The recurrence at the top of this file over ``[B, S, H, D]``
    (``beta`` ``[B, S, H]``), float32 out, ``S`` a multiple of ``chunk``.
    The sequence is walked ``segment`` chunks at a time; a layer's
    recomputation keeps the state each segment starts from and the output
    (``SAVED``: 8 + 64 MiB a layer at 2 x 8192 tokens), so that a layer
    recomputed in the backward pass does not walk the sequence again.
    Shapes choose the form: heads that fill the lanes take the kernels."""
    bsz, s, h, d = q.shape
    if s % chunk:
        raise ValueError("the delta rule runs in chunks of %d steps; a "
                         "sequence of %d is not a multiple" % (chunk, s))
    if not kda_kernels.takes(d, chunk):
        return kda_chunked_xla(q, k, v, g, beta, chunk, segment)

    def rows(x):
        return x.astype(jnp.float32).reshape(bsz, s, h * d)

    by = beta.astype(jnp.float32)[..., None]
    o = _kda_kernels(rows(q), rows(k), rows(by * k), rows(by * v), rows(g),
                     chunk, math.gcd(s // chunk, segment), d)
    return o.reshape(bsz, s, h, d)


# --------------------------------------------------------------------------
# The mixer
# --------------------------------------------------------------------------

def causal_conv(x, taps):
    """Depthwise over time: ``y_t = sum_j taps[j] x_{t-(n-1)+j}``, zeros
    before the sequence's start.  ``x`` ``[B, S, W]``, ``taps`` ``[n, W]``."""
    n = taps.shape[0]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    s = x.shape[1]
    return sum(padded[:, j:j + s] * taps[j].astype(x.dtype) for j in range(n))


def _l2norm(x):
    xf = x.astype(jnp.float32)
    return xf * lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + 1e-6)


@jax.named_scope(scopes.LINEAR_ATTENTION)
def linear_attention_block(x, lp, cfg: KdaConfig):
    """``x`` ``[B, S, d]`` normed; returns this shard's heads' part of the
    mixer's output, ``[B, S, d]`` before the sum over ``tp``."""
    b, s, _ = x.shape
    d = cfg.head_size

    def heads(y):
        return y.reshape(b, s, -1, d)

    def branch(name):
        y = x @ lp["w" + name].astype(x.dtype)
        return jax.nn.silu(causal_conv(y, lp["conv_" + name]))

    q, k = heads(_l2norm(heads(branch("q")))), heads(_l2norm(heads(branch("k"))))
    v = heads(branch("v"))
    lowrank = (x @ lp["w_fa"].astype(x.dtype)) @ lp["w_fb"].astype(x.dtype)
    g = -jnp.exp(lp["a_log"].astype(jnp.float32))[:, None] * heads(
        jax.nn.softplus(lowrank.astype(jnp.float32)
                        + lp["decay_bias"].astype(jnp.float32)))
    beta = 2.0 * jax.nn.sigmoid(
        (x @ lp["w_beta"].astype(x.dtype)).astype(jnp.float32))
    with jax.named_scope(scopes.KDA_CORE):
        o = kda_chunked(q, k, v, g, beta, cfg.chunk)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
    o = o * lp["o_norm"].astype(jnp.float32)
    gate = (x @ lp["w_ga"].astype(x.dtype)) @ lp["w_gb"].astype(x.dtype)
    o = o.reshape(b, s, -1) * jax.nn.sigmoid(gate.astype(jnp.float32))
    return o.astype(x.dtype) @ lp["wo"].astype(x.dtype)
