"""Linear attention by the gated delta rule: the mixer of a hybrid
decoder's linear layers.

For each head, with keys of size ``Dk`` and values of size ``Dv``, a state
``S`` (``Dk x Dv``, zero where a sequence starts) follows

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = Dk^-1/2 * S_t^T q_t

``g_t <= 0`` is a log-decay and ``beta_t`` in (0, 2) the writing strength;
above 1 the transition has negative eigenvalues.  The decay has one of two
forms (``KdaConfig.decay``): one for every key channel (``"channel"``,
KDA's: a low-rank product of the input) or one scalar a head (``"head"``,
Gated DeltaNet's: ``Diag(exp(g_t))`` is ``exp(g_t) I``).  Both run the same
chunked form; the XLA form below broadcasts a head's decay over the key
channels (exact), the kernels take it as it is.
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

Two forms of the computation, chosen by shape (``kda_chunked``).  Keys
and values that fill more than half of the lane tiles they round up to
take the Pallas kernels of ``ops/kda_kernels.py`` (``kda_kernels.takes``),
zero-padded where a grid step's heads would not fill whole tiles, which is
exact: zero key channels add nothing to ``k k^T``, ``q k^T`` or ``k^T w``
and the padded rows and columns of ``S`` stay zero.  A forward kernel
keeps the head's state and a chunk's matrices in VMEM, and a custom VJP's
backward kernel walks the segments in reverse; a decay a head enters its
own pair of kernels as one value a step, which computes a chunk's decayed
products as one ``C x C`` matrix for every channel.  Other heads (the tests'
small configurations) take the XLA form below, whose backward pass is
autodiff through ``_segment``; it is also the kernels' oracle.

``linear_attention_block`` is the whole mixer: projections, a causal
depthwise convolution, the core, an RMSNorm over each head's values times
an output gate (KDA's low-rank sigmoid, or Gated DeltaNet's full-rank
``silu``), and the output projection.  Heads are sharded over the
tensor-parallel axis like attention's; the sequence is not split (the
state would have to travel).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..common import metrics, scopes
from ..ops import kda_kernels
from ..parallel.ring_attention import pvary_missing

HI = lax.Precision.HIGHEST
# ``checkpoint_name``s of what a recomputed layer keeps of its delta rule:
# the state each segment starts from and the core's output.
SAVED = ("kda_segment_state", "kda_output")


DECAYS = ("channel", "head")


@dataclasses.dataclass(frozen=True)
class KdaConfig:
    """A delta-rule mixer: ``n_heads`` heads held here (sharded over tp),
    keys (and queries) of ``head_size``, values of ``value_size`` (None:
    ``head_size`` too), and the decay and output gate of one of two
    families.  ``decay="channel"`` (KDA's): a log-decay for every key
    channel from a low-rank product ``x W_fa W_fb`` (inner width
    ``gate_rank``), and a low-rank sigmoid gate ``sigmoid(x W_ga W_gb)``.
    ``decay="head"`` (Gated DeltaNet's): one log-decay a head,
    ``-exp(a_log) softplus(x W_a + dt_bias)``, and a full-rank gate
    ``silu(x W_g)``; ``gate_rank`` is then unused."""
    n_heads: int
    head_size: int = 128        # of keys
    conv_size: int = 4          # taps of the causal depthwise convolution
    gate_rank: int = 128        # inner width of the two low-rank gates
    chunk: int = 64
    norm_eps: float = 1e-5
    value_size: Optional[int] = None
    decay: str = "channel"

    def __post_init__(self):
        if self.decay not in DECAYS:
            raise ValueError("a delta rule decays by one of %s, not %r"
                             % (DECAYS, self.decay))

    @property
    def values(self) -> int:
        return self.value_size or self.head_size

    @property
    def width(self) -> int:
        return self.n_heads * self.head_size

    @property
    def value_width(self) -> int:
        return self.n_heads * self.values


def init_kda_params(key, d_model: int, cfg: KdaConfig, n: int, dtype):
    """``n`` stacked layers.  The decay's ``a_log`` and its bias
    (``decay_bias`` for every channel, ``dt_bias`` a head) start where the
    gated-delta-rule family's published layers start them: ``exp(a_log)``
    uniform in [1, 16], the bias's softplus log-uniform in [0.001, 0.1]."""
    h, w, wv, r = cfg.n_heads, cfg.width, cfg.value_width, cfg.gate_rank
    ks = jax.random.split(key, 13)

    def norm(k, shape, fan_in):
        return (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(dtype)

    per_head = cfg.decay == "head"
    dt = jnp.exp(jax.random.uniform(ks[11], (n, h if per_head else w),
                                    minval=math.log(1e-3),
                                    maxval=math.log(1e-1)))
    # softplus^-1(dt) = dt + log(1 - exp(-dt))
    bias = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    params = {
        "wq": norm(ks[0], (n, d_model, w), d_model),
        "wk": norm(ks[1], (n, d_model, w), d_model),
        "wv": norm(ks[2], (n, d_model, wv), d_model),
        "conv_q": norm(ks[3], (n, cfg.conv_size, w), cfg.conv_size),
        "conv_k": norm(ks[4], (n, cfg.conv_size, w), cfg.conv_size),
        "conv_v": norm(ks[5], (n, cfg.conv_size, wv), cfg.conv_size),
        "w_beta": norm(ks[10], (n, d_model, h), d_model),
        "a_log": jnp.log(jax.random.uniform(
            ks[12], (n, h), minval=1.0, maxval=16.0)).astype(dtype),
        "o_norm": jnp.ones((n, cfg.values), dtype),
        "wo": norm(jax.random.fold_in(key, 13), (n, wv, d_model), wv),
    }
    if per_head:
        params.update(w_a=norm(ks[6], (n, d_model, h), d_model),
                      dt_bias=bias,
                      w_g=norm(ks[8], (n, d_model, wv), d_model))
    else:
        params.update(w_fa=norm(ks[6], (n, d_model, r), d_model),
                      w_fb=norm(ks[7], (n, r, w), r),
                      w_ga=norm(ks[8], (n, d_model, r), d_model),
                      w_gb=norm(ks[9], (n, r, w), r),
                      decay_bias=bias)
    return params


def kda_param_specs(cfg: KdaConfig, tp):
    """Heads over ``tp``: projections by column, ``wo`` by row, the
    low-rank gates' inner factor whole."""
    from jax.sharding import PartitionSpec as P
    if cfg.decay == "head":
        cols = ("wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "w_a", "w_g",
                "w_beta")
        whole, heads = {}, ("dt_bias", "a_log")
    else:
        cols = ("wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "w_fb",
                "w_gb", "w_beta")
        whole = {"w_fa": P(None, None, None), "w_ga": P(None, None, None)}
        heads = ("decay_bias", "a_log")
    return dict({name: P(None, None, tp) for name in cols}, **whole,
                **{name: P(None, tp) for name in heads},
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
    """A run of chunks from ``state`` ``[B, H, Dk, Dv]``: inputs
    ``[B, H, N, C, D]`` (``D`` of the values' size for ``v``, of the keys'
    for the others; ``beta`` ``[B, H, N, C]``); returns the state after it
    and the outputs, unscaled."""
    d, dv = q.shape[-1], v.shape[-1]
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
    u, w_k = solved[..., :dv], solved[..., dv:]
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
    a segment is its ``[B, H, Dk, Dv]`` start state."""
    bsz, s, h, d = q.shape
    dv = v.shape[-1]
    g = _per_channel(g, d)
    n = s // chunk
    per = math.gcd(n, segment)

    def segments(x):        # [B, S, H, ...] -> [segments, B, H, per, C, ...]
        x = x.astype(jnp.float32).reshape(
            (bsz, n // per, per, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 4, 1), 2, 0)

    args = tuple(segments(x) for x in (q, k, v, g, beta))
    # The start state has to vary over the mesh axes the inputs vary over.
    start = pvary_missing(jnp.zeros((bsz, h, d, dv), jnp.float32),
                          tuple(jax.typeof(args[0]).vma))

    def walk(state, xs):
        state = checkpoint_name(state, SAVED[0])
        state, o = jax.checkpoint(_segment)(state, *xs)
        return state, checkpoint_name(o, SAVED[1])

    _, o = lax.scan(walk, start, args)
    # [segments, B, H, per, C, Dv] -> [B, S, H, Dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 4).reshape(bsz, s, h, dv)
    return o * (1.0 / math.sqrt(d))


def _per_channel(g, d):
    """A decay a head ``[B, S, H]`` as the same decay on each of the ``d``
    key channels (exact); a decay for every channel as it is."""
    if g.ndim == 3:
        return jnp.broadcast_to(g[..., None], g.shape + (d,))
    return g


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _kda_kernels(q, k, kb, vb, g, chunk, per, h, scale):
    """``ops/kda_kernels.py`` over ``[B, S, H D]``, with ``kb = beta k``
    and ``vb = beta v`` so that autodiff outside takes ``beta``'s part."""
    return kda_kernels.forward(q, k, kb, vb, g, chunk, per, h, scale)[0]


def _kda_kernels_fwd(q, k, kb, vb, g, chunk, per, h, scale):
    o, starts = kda_kernels.forward(q, k, kb, vb, g, chunk, per, h, scale)
    return checkpoint_name(o, SAVED[1]), \
        (q, k, kb, vb, g, checkpoint_name(starts, SAVED[0]))


def _kda_kernels_bwd(chunk, per, h, scale, res, do):
    return kda_kernels.backward(*res[:5], do, res[5], chunk, per, h, scale)


_kda_kernels.defvjp(_kda_kernels_fwd, _kda_kernels_bwd)


def kda_chunked(q, k, v, g, beta, chunk: int, segment: int = 16):
    """The recurrence at the top of this file over q, k ``[B, S, H, Dk]``,
    v ``[B, S, H, Dv]``, g ``[B, S, H, Dk]`` (a decay for every channel)
    or ``[B, S, H]`` (one a head) and beta ``[B, S, H]``: o ``[B, S, H,
    Dv]`` float32, ``S`` a multiple of ``chunk``.  The sequence is walked
    ``segment`` chunks at a time; a layer's recomputation keeps the state
    each segment starts from and the output (``SAVED``: 8 + 64 MiB a layer
    at 2 x 8192 tokens of 8 heads of 128), so that a layer recomputed in
    the backward pass does not walk the sequence again.  Shapes choose the
    form (``kda_kernels.takes``); the kernels see keys and values padded
    with zeros where a grid step's heads would not fill whole lane tiles
    (``kda_kernels.padded``), and a decay a head as it is (their own pair,
    ``form=head_kernel``)."""
    bsz, s, h, d = q.shape
    dv = v.shape[-1]
    if s % chunk:
        raise ValueError("the delta rule runs in chunks of %d steps; a "
                         "sequence of %d is not a multiple" % (chunk, s))
    kernel = kda_kernels.takes(d, dv, chunk)
    per_head = g.ndim == 3
    # As the core is traced: once for every time a layer scan or a
    # recomputation traces it, not once a step.
    metrics.counter("hvd_delta_rule_calls_total",
                    form=("head_kernel" if per_head else "kernel") if kernel
                    else "xla",
                    decay="head" if per_head else "channel").inc()
    if not kernel:
        return kda_chunked_xla(q, k, v, g, beta, chunk, segment)

    def rows(x):
        size = x.shape[-1]
        x = x.astype(jnp.float32)
        pad = kda_kernels.padded(size, h) - size
        if pad:
            x = jnp.pad(x, ((0, 0),) * 3 + ((0, pad),))
        return x.reshape(bsz, s, -1)

    by = beta.astype(jnp.float32)[..., None]
    o = _kda_kernels(rows(q), rows(k), rows(by * k), rows(by * v),
                     g.astype(jnp.float32) if per_head else rows(g), chunk,
                     math.gcd(s // chunk, segment), h, 1.0 / math.sqrt(d))
    return o.reshape(bsz, s, h, -1)[..., :dv]


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
    """``x`` ``[B, S, d]`` (normed in a pre-norm block); returns this
    shard's heads' part of the mixer's output, ``[B, S, d]`` before the sum
    over ``tp``.  The core of a layer with a decay a head sits under
    ``hvd.gated_delta_core``, one with a decay for every channel under
    ``hvd.kda_core``."""
    b, s, _ = x.shape
    d = cfg.head_size

    def heads(y, size=d):
        return y.reshape(b, s, -1, size)

    def branch(name):
        y = x @ lp["w" + name].astype(x.dtype)
        return jax.nn.silu(causal_conv(y, lp["conv_" + name]))

    def f32(y):
        return y.astype(jnp.float32)

    q, k = heads(_l2norm(heads(branch("q")))), heads(_l2norm(heads(branch("k"))))
    v = heads(branch("v"), cfg.values)
    if cfg.decay == "head":
        g = -jnp.exp(f32(lp["a_log"])) * jax.nn.softplus(
            f32(x @ lp["w_a"].astype(x.dtype)) + f32(lp["dt_bias"]))
    else:
        lowrank = (x @ lp["w_fa"].astype(x.dtype)) @ lp["w_fb"].astype(x.dtype)
        g = -jnp.exp(f32(lp["a_log"]))[:, None] * heads(
            jax.nn.softplus(f32(lowrank) + f32(lp["decay_bias"])))
    beta = 2.0 * jax.nn.sigmoid(f32(x @ lp["w_beta"].astype(x.dtype)))
    if cfg.decay == "head":
        with jax.named_scope(scopes.GATED_DELTA_CORE):
            o = kda_chunked(q, k, v, g, beta, cfg.chunk)
    else:
        with jax.named_scope(scopes.KDA_CORE):
            o = kda_chunked(q, k, v, g, beta, cfg.chunk)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
    o = o * f32(lp["o_norm"])
    if cfg.decay == "head":
        o = o.reshape(b, s, -1) * jax.nn.silu(
            f32(x @ lp["w_g"].astype(x.dtype)))
    else:
        gate = (x @ lp["w_ga"].astype(x.dtype)) @ lp["w_gb"].astype(x.dtype)
        o = o.reshape(b, s, -1) * jax.nn.sigmoid(f32(gate))
    return o.astype(x.dtype) @ lp["wo"].astype(x.dtype)
