"""A Mamba-2 state-space mixer: the layer a hybrid decoder runs where it
has no attention.

For each head, with inputs of size ``P`` and a state of ``P x N`` (zero
where a sequence starts),

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T
    y_t = H_t C_t + D x_t

``A < 0`` and ``D`` are one scalar a head, ``dt_t > 0`` one a head and
step, ``B_t`` and ``C_t`` (size ``N``) are shared by the heads of a group.
``ssd_chunked`` computes this a chunk of ``Q`` steps at a time (the
"state-space dual" form).  With ``a_t = dt_t A <= 0`` and ``cum_t`` its
running sum inside a chunk:

    Y_intra = (L . (C B^T)) (dt . X)        L_ts = exp(cum_t - cum_s), s <= t
    S_c     = sum_s exp(cum_end - cum_s) dt_s x_s B_s^T     the chunk's own
    H_c     = exp(cum_end) H_{c-1} + S_c                    carried over
    Y_inter_t = exp(cum_t) H_{c-1} C_t
    y = Y_intra + Y_inter + D x

Everything of a chunk that does not need the carried state is matrix
products over all chunks at once.  The carry over chunks is linear with a
scalar decay, so the state a chunk starts from is one more such product,
``H_{c-1} = sum_{c' < c} exp(T_{c-1} - T_{c'}) S_{c'}`` with ``T`` the
running sum of the chunks' ``cum_end``: no loop walks the sequence.  Every
exponent is a difference taken before the exponential and is never
positive, so a strong decay neither overflows nor loses the near steps.
``dt``, the log-decays, their sums, the exponentials and the states are
float32 whatever the activations are; the products take their operands in
the activations' dtype and accumulate in float32, as the published kernels
do.

Two forms compute it and shapes choose between them (``ssd_chunked``).
Where the blocks tile the chip (``ops/ssd_kernels.py: takes``: a group's
channels and the state whole lane tiles, a chunk of 128 or 256) two Pallas
kernels behind a ``jax.custom_vjp`` walk the chunks with the group's state
in VMEM, forward, and its gradient, backward: the carry is the recurrence
``H_c = exp(cum_end) H_{c-1} + S_c`` itself, and neither ``L`` nor ``C B^T``
nor a state leaves the chip but the states the backward kernel starts its
chunks from.  Every other shape takes ``ssd_chunked_xla``, the products
above in XLA operations with autodiff behind them, which is also what the
kernels are tested against.

``state_space_block`` is the whole mixer: one input projection to the gate
``z``, the scan's ``x``, ``B``, ``C`` and ``dt``, a causal depthwise
convolution over ``x B C``, the scan, a gated RMSNorm over groups of
channels and the output projection.  The heads are not split over the
tensor-parallel axis (one projection holds ``z``, ``x``, ``B``, ``C`` and
``dt`` side by side, as published) and the sequence is not split either
(the state would have to travel).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..common import metrics, scopes
from ..ops import ssd_kernels
from ..parallel.ring_attention import pvary_missing
from .linear_attention import causal_conv

HI = lax.Precision.HIGHEST
# ``checkpoint_name``s of what a recomputed layer keeps of its scan:
# nothing.  The gate and the norm need ``y`` again, so the forward kernel
# runs again in the backward pass whatever is kept, and it writes the
# chunks' start states as it goes (``PERF.md``, PR 34).
SAVED = ()


@dataclasses.dataclass(frozen=True)
class SsmConfig:
    n_heads: int
    head_size: int = 64         # P: channels of a head
    n_groups: int = 8           # n_heads / n_groups heads share B and C
    state_size: int = 128       # N
    conv_size: int = 4          # taps of the causal depthwise convolution
    chunk: int = 128
    norm_eps: float = 1e-5
    dt_min: float = 1e-3        # dt's start: log-uniform in [dt_min, dt_max],
    dt_max: float = 1e-1        # not under dt_floor
    dt_floor: float = 1e-4

    def __post_init__(self):
        if self.n_groups < 1 or self.n_heads % self.n_groups:
            raise ValueError("%d groups do not divide %d state-space heads"
                             % (self.n_groups, self.n_heads))

    @property
    def width(self) -> int:
        """Inner channels: what ``x``, ``z`` and the norm are wide."""
        return self.n_heads * self.head_size

    @property
    def conv_width(self) -> int:
        """Channels under the convolution: ``x``, ``B`` and ``C``."""
        return self.width + 2 * self.n_groups * self.state_size


def init_ssm_params(key, d_model: int, cfg: SsmConfig, n: int, dtype):
    """``n`` stacked layers, started as the published layers start:
    ``exp(a_log)`` uniform in [1, 16], ``softplus(dt_bias)`` log-uniform in
    ``[dt_min, dt_max]`` and not under ``dt_floor``, ``D`` = 1, the
    convolution's taps and bias uniform within ``conv_size^-1/2``, the
    norm's scale 1, both projections normal at ``fan_in^-1/2``."""
    h, w = cfg.n_heads, cfg.width
    ks = jax.random.split(key, 6)

    def norm(k, shape, fan_in):
        return (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(dtype)

    def conv(k, shape):
        bound = 1.0 / math.sqrt(cfg.conv_size)
        return jax.random.uniform(k, shape, minval=-bound,
                                  maxval=bound).astype(dtype)

    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        ks[0], (n, h), minval=math.log(cfg.dt_min),
        maxval=math.log(cfg.dt_max))), cfg.dt_floor)
    return {
        "in_proj": norm(ks[1], (n, d_model, w + cfg.conv_width + h),
                        d_model),
        "conv_w": conv(ks[2], (n, cfg.conv_size, cfg.conv_width)),
        "conv_b": conv(ks[3], (n, cfg.conv_width)),
        # softplus^-1(dt) = dt + log(1 - exp(-dt))
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "a_log": jnp.log(jax.random.uniform(
            ks[4], (n, h), minval=1.0, maxval=16.0)).astype(dtype),
        "d_skip": jnp.ones((n, h), dtype),
        "ssm_norm": jnp.ones((n, w), dtype),
        "out_proj": norm(ks[5], (n, w, d_model), w),
    }


def ssm_param_specs():
    """Every leaf whole on every shard: the heads are not split."""
    from jax.sharding import PartitionSpec as P
    matrices = ("in_proj", "conv_w", "out_proj")
    vectors = ("conv_b", "dt_bias", "a_log", "d_skip", "ssm_norm")
    return {**{name: P(None, None, None) for name in matrices},
            **{name: P(None, None) for name in vectors}}


# --------------------------------------------------------------------------
# The scan
# --------------------------------------------------------------------------

def ssd_chunked_xla(x, dt, a, b, c, d_skip, chunk: int):
    """``ssd_chunked`` in XLA operations, for the shapes the kernels do not
    take: every chunk's products at once, the carry over chunks one more
    product, the backward pass autodiff through them."""
    bsz, s, h, p = x.shape
    g = b.shape[2]
    nc, per = s // chunk, h // g
    act = x.dtype

    def chunks(y):          # [B, S, ...] -> [B, chunks, Q, ...]
        return y.reshape((bsz, nc, chunk) + y.shape[2:])

    dt = chunks(dt.astype(jnp.float32))                     # [B,C,Q,H]
    cum = jnp.cumsum(dt * a.astype(jnp.float32), axis=2)    # <= 0, falling
    x, b, c = chunks(x), chunks(b), chunks(c)
    xg = x.reshape(bsz, nc, chunk, g, per, p)
    dtx = x.astype(jnp.float32) * dt[..., None]             # dt . X

    # Inside a chunk: step t reads step s <= t through exp(cum_t - cum_s).
    steps = jnp.arange(chunk)
    seen = steps[:, None] >= steps[None, :]                 # [t, s]
    cum_h = jnp.moveaxis(cum, 2, 3)                         # [B,C,H,Q]
    decay = jnp.exp(jnp.where(
        seen, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf))
    cb = jnp.einsum("bctgn,bcsgn->bcgts", c, b,
                    preferred_element_type=jnp.float32)
    mixed = (decay.reshape(bsz, nc, g, per, chunk, chunk)
             * cb[:, :, :, None]).astype(act)               # [B,C,G,K,t,s]
    y = jnp.einsum("bcgkts,bcsgkp->bctgkp", mixed,
                   dtx.astype(act).reshape(xg.shape),
                   preferred_element_type=jnp.float32)

    # The chunk's own state, as seen from its end: [B,C,G,K,P,N].
    whole = cum[:, :, -1:]                                  # [B,C,1,H]
    to_end = (dtx * jnp.exp(whole - cum)[..., None]).astype(act)
    own = jnp.einsum("bcsgkp,bcsgn->bcgkpn", to_end.reshape(xg.shape), b,
                     preferred_element_type=jnp.float32)

    # The state a chunk starts from: the earlier chunks' own states, each
    # decayed over the chunks between.  ``total[c]`` is the log-decay up
    # to the end of chunk ``c`` and ``starts[c]`` up to its start; chunk
    # ``c'`` has decayed by ``starts[c] - total[c']`` when chunk ``c``
    # starts.
    total = jnp.cumsum(jnp.moveaxis(whole[:, :, 0], 1, 2), axis=-1)
    starts = jnp.pad(total[:, :, :-1], ((0, 0), (0, 0), (1, 0)))  # [B,H,C]
    at = jnp.arange(nc)
    between = jnp.exp(jnp.where(
        at[:, None] > at[None, :],
        starts[:, :, :, None] - total[:, :, None, :], -jnp.inf))
    start = jnp.einsum(
        "bgkce,begkpn->bcgkpn", between.reshape(bsz, g, per, nc, nc), own,
        precision=HI)
    y = y + jnp.einsum(
        "bctgn,bcgkpn->bctgkp", c, start.astype(act),
        preferred_element_type=jnp.float32) \
        * jnp.exp(cum).reshape(bsz, nc, chunk, g, per)[..., None]
    y = y + xg.astype(jnp.float32) \
        * d_skip.astype(jnp.float32).reshape(g, per)[:, :, None]
    return y.reshape(bsz, s, h, p)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_kernels(x, scal, b, c, d_row, p):
    """``ops/ssd_kernels.py`` over ``[B, S, H P]`` rows, with the running
    sums and ``D`` a channel made outside so that autodiff takes ``A``'s,
    ``dt``'s and ``D``'s parts."""
    return ssd_kernels.forward(x, scal, b, c, d_row, p)[0]


def _ssd_kernels_fwd(x, scal, b, c, d_row, p):
    y, starts = ssd_kernels.forward(x, scal, b, c, d_row, p)
    return y, (x, scal, b, c, d_row, starts)


def _ssd_kernels_bwd(p, res, dy):
    dx, dscal, db, dc, d_skip = ssd_kernels.backward(*res[:5], dy, res[5],
                                                     p)
    # [B, G, 8, K P] partial sums -> D's row [1, H P]
    return dx, dscal, db, dc, jnp.sum(d_skip, axis=(0, 2)).reshape(1, -1)


_ssd_kernels.defvjp(_ssd_kernels_fwd, _ssd_kernels_bwd)


def ssd_chunked(x, dt, a, b, c, d_skip, chunk: int):
    """The recurrence at the top of this file, a chunk at a time.  ``x``
    ``[B, S, H, P]``, ``dt`` ``[B, S, H]`` (positive), ``a`` ``[H]``
    (negative), ``b``, ``c`` ``[B, S, G, N]``, ``d_skip`` ``[H]``; ``S`` a
    multiple of ``chunk``.  Returns ``y`` ``[B, S, H, P]`` float32.  Shapes
    choose the form: groups and states that fill lane tiles take the
    kernels."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    if s % chunk:
        raise ValueError("the state-space scan runs in chunks of %d steps; "
                         "a sequence of %d is not a multiple" % (chunk, s))
    nc, per = s // chunk, h // g
    kernel = ssd_kernels.takes(p, per, n, chunk)
    # As the scan is traced: once for every time a layer scan or a
    # recomputation traces it, not once a step.
    metrics.counter("hvd_ssd_scan_calls_total",
                    form="kernel" if kernel else "xla").inc()
    if not kernel:
        return ssd_chunked_xla(x, dt, a, b, c, d_skip, chunk)

    def rows(v):            # [B, S, H] -> [B, G, chunks, K, Q]: steps on lanes
        return jnp.transpose(v.reshape(bsz, nc, chunk, g, per),
                             (0, 3, 1, 4, 2))

    dt = dt.astype(jnp.float32)
    cum = jnp.cumsum((dt * a.astype(jnp.float32)).reshape(bsz, nc, chunk, h),
                     axis=2).reshape(bsz, s, h)             # <= 0, falling
    fill = ssd_kernels.scalar_rows(per) - 2 * per
    scal = jnp.pad(jnp.concatenate([rows(cum), rows(dt)], axis=3),
                   ((0, 0),) * 3 + ((0, fill), (0, 0)))
    # D is a parameter, whole on every shard: its row has to vary over the
    # mesh axes the tokens vary over before it meets them in a kernel.
    d_row = pvary_missing(jnp.repeat(d_skip.astype(jnp.float32), p)[None],
                          tuple(jax.typeof(x).vma))
    y = _ssd_kernels(x.reshape(bsz, s, h * p), scal,
                     b.reshape(bsz, s, g * n), c.reshape(bsz, s, g * n),
                     d_row, p)
    return y.reshape(bsz, s, h, p)


# --------------------------------------------------------------------------
# The mixer
# --------------------------------------------------------------------------

@jax.named_scope(scopes.STATE_SPACE)
def state_space_block(x, lp, cfg: SsmConfig):
    """``x`` ``[B, S, d]`` normed; returns the mixer's output ``[B, S,
    d]``.  The gate multiplies before the norm, and the norm runs over
    groups of ``width / n_groups`` channels under one ``width``-wide
    scale."""
    bsz, s, _ = x.shape
    w, gn = cfg.width, cfg.n_groups * cfg.state_size
    zxbcdt = x @ lp["in_proj"].astype(x.dtype)
    z, xbc, dt = jnp.split(zxbcdt, (w, w + cfg.conv_width), axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, lp["conv_w"])
                      + lp["conv_b"].astype(x.dtype))
    xs, b, c = jnp.split(xbc, (w, w + gn), axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + lp["dt_bias"].astype(jnp.float32))
    with jax.named_scope(scopes.SSD_CORE):
        y = ssd_chunked(
            xs.reshape(bsz, s, cfg.n_heads, cfg.head_size), dt,
            -jnp.exp(lp["a_log"].astype(jnp.float32)),
            b.reshape(bsz, s, cfg.n_groups, cfg.state_size),
            c.reshape(bsz, s, cfg.n_groups, cfg.state_size),
            lp["d_skip"], cfg.chunk)
    y = y.reshape(bsz, s, w) * jax.nn.silu(z.astype(jnp.float32))
    y = y.reshape(bsz, s, cfg.n_groups, -1)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
    y = y.reshape(bsz, s, w) * lp["ssm_norm"].astype(jnp.float32)
    return y.astype(x.dtype) @ lp["out_proj"].astype(x.dtype)
