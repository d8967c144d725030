"""Fused BatchNorm(+residual add)(+ReLU) Pallas TPU kernels.

The headline ResNet-50 benchmark spends ~31% of its step in train-mode
BatchNorm (docs/benchmarks.md), an HBM-bandwidth-bound op.  The XLA
lowering of flax ``nn.BatchNorm`` + relu + residual-add costs ~8
activation traversals per layer (fwd+bwd, measured); these kernels do
the minimum the semantics allow:

* forward: one stats pass (sum + sum-of-squares in a single read of
  ``x``, f32 VMEM accumulators) + one apply pass that fuses normalize,
  affine, the residual add, and the ReLU into a single read+write;
* backward: one fused reduction pass producing BOTH dbeta and dgamma
  (with the ReLU mask recomputed in-register from ``x`` — the mask is
  never materialized in HBM) + one dx pass that also emits the residual
  gradient.

Reference parity note: the reference has no BN kernel of its own (BN
backward rides cuDNN, ``torch.nn.BatchNorm2d``); this is the
TPU-native equivalent of that vendor-kernel dependence, in the same
spirit as ``pallas_kernels.py`` (SURVEY.md §7 phase 7).

All kernels run compiled on TPU and through the Pallas interpreter
on the CPU test world (``common/device.py`` decides); tests
compare y/dx/dgamma/dbeta/dres against an f32 XLA oracle
(tests/test_pallas_bn.py).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common.device import on_tpu


def _largest_divisor(m: int, cap: int) -> Optional[int]:
    """Largest d <= cap with m % d == 0 and d % 8 == 0 (sublane tiling)."""
    for d in range(min(cap, m), 7, -1):
        if m % d == 0 and d % 8 == 0:
            return d
    return None


def _plan(m: int, c: int):
    """(fold, c_block) for the (M/fold, fold*C) view, or None when the
    shape doesn't tile.

    Small channel counts are folded: viewing row-major (M, C) as
    (M/k, k*C) is free and fills the 128-wide VPU lanes; per-channel
    sums are then k partial sums combined outside the kernel.  Each
    kernel wrapper picks its own M block from a VMEM budget scaled by
    its operand count (_m_for).
    """
    fold = 1
    if c < 128:
        if c % 8 or 128 % c:
            return None
        fold = 128 // c
        if m % fold:
            return None
        m, c = m // fold, c * fold
    if c <= 256:
        c_blk = c
    elif c % 256 == 0:
        c_blk = 256
    elif c % 128 == 0:
        c_blk = 128
    else:
        return None
    if _m_for(m, c_blk, 5) is None:
        return None
    return fold, c_blk


def _m_for(m: int, c_blk: int, n_ops: int) -> Optional[int]:
    """M block size for a kernel moving n_ops activation-sized
    operands: double-buffered blocks must fit a ~8 MiB VMEM budget."""
    cap = max(8, (8 << 20) // (c_blk * 2 * 2 * n_ops))
    return _largest_divisor(m, cap)


# ---------------------------------------------------------------------------
# kernels (all operate on x reshaped to (M, C))
# ---------------------------------------------------------------------------


def _stats_kernel(x_ref, sum_ref, sq_ref, s_scr, q_scr):
    # grid = (nc, nm): the channel tile's f32 accumulators live in VMEM
    # scratch across the inner M axis; x is read exactly once.  Outputs
    # are raw column sums — the (tiny) mean/var math happens outside so
    # the folded small-C view can combine its partial columns first.
    t = pl.program_id(1)
    nm = pl.num_programs(1)

    @pl.when(t == 0)
    def _init():
        s_scr[:] = jnp.zeros_like(s_scr)
        q_scr[:] = jnp.zeros_like(q_scr)

    xb = x_ref[...].astype(jnp.float32)
    s_scr[:] += jnp.sum(xb, axis=0, keepdims=True)
    q_scr[:] += jnp.sum(xb * xb, axis=0, keepdims=True)

    @pl.when(t == nm - 1)
    def _finish():
        sum_ref[...] = s_scr[:]
        sq_ref[...] = q_scr[:]


def _apply_kernel(x_ref, mean_ref, var_ref, gamma_ref, beta_ref, *rest,
                  eps, relu, residual):
    if residual:
        res_ref, y_ref = rest
    else:
        (y_ref,) = rest
    xb = x_ref[...].astype(jnp.float32)
    rinv = jax.lax.rsqrt(var_ref[...] + eps)
    z = (xb - mean_ref[...]) * (rinv * gamma_ref[...]) + beta_ref[...]
    if residual:
        z = z + res_ref[...].astype(jnp.float32)
    if relu:
        z = jnp.maximum(z, 0.0)
    y_ref[...] = z.astype(y_ref.dtype)


def _dy_eff(xh, dy_raw, gamma_ref, beta_ref, res_ref, relu, residual):
    """ReLU-masked upstream gradient; the pre-activation is recomputed
    in-register (never stored)."""
    dy = dy_raw.astype(jnp.float32)
    if relu:
        z = xh * gamma_ref[...] + beta_ref[...]
        if residual:
            z = z + res_ref[...].astype(jnp.float32)
        dy = jnp.where(z > 0.0, dy, 0.0)
    return dy


def _bwd_red_kernel(x_ref, dy_ref, mean_ref, var_ref, gamma_ref,
                    beta_ref, *rest, eps, relu, residual):
    # One read of (x, dy) produces BOTH reductions.
    if residual:
        res_ref, db_ref, dg_ref, db_scr, dg_scr = rest
    else:
        db_ref, dg_ref, db_scr, dg_scr = rest
        res_ref = None
    t = pl.program_id(1)
    nm = pl.num_programs(1)

    @pl.when(t == 0)
    def _init():
        db_scr[:] = jnp.zeros_like(db_scr)
        dg_scr[:] = jnp.zeros_like(dg_scr)

    xb = x_ref[...].astype(jnp.float32)
    rinv = jax.lax.rsqrt(var_ref[...] + eps)
    xh = (xb - mean_ref[...]) * rinv
    dy = _dy_eff(xh, dy_ref[...], gamma_ref, beta_ref, res_ref, relu,
                 residual)
    db_scr[:] += jnp.sum(dy, axis=0, keepdims=True)
    dg_scr[:] += jnp.sum(dy * xh, axis=0, keepdims=True)

    @pl.when(t == nm - 1)
    def _finish():
        db_ref[...] = db_scr[:]
        dg_ref[...] = dg_scr[:]


def _bwd_dx_kernel(x_ref, dy_ref, mean_ref, var_ref, gamma_ref,
                   beta_ref, db_ref, dg_ref, *rest, eps, relu,
                   residual, inv_m):
    if residual:
        res_ref, dx_ref, dres_ref = rest
    else:
        (dx_ref,) = rest
        res_ref = None
    xb = x_ref[...].astype(jnp.float32)
    rinv = jax.lax.rsqrt(var_ref[...] + eps)
    xh = (xb - mean_ref[...]) * rinv
    dy = _dy_eff(xh, dy_ref[...], gamma_ref, beta_ref, res_ref, relu,
                 residual)
    dx = (gamma_ref[...] * rinv) * (
        dy - db_ref[...] * inv_m - xh * (dg_ref[...] * inv_m))
    dx_ref[...] = dx.astype(dx_ref.dtype)
    if residual:
        dres_ref[...] = dy.astype(dres_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call orchestration (2-D (M, C) views)
# ---------------------------------------------------------------------------


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _params(interpret, reduce_m: bool):
    """Mosaic grid semantics: channel tiles are independent
    ("parallel"); the inner M axis accumulates into VMEM scratch for
    reduction kernels ("arbitrary") and is independent otherwise."""
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",
                             "arbitrary" if reduce_m else "parallel"))


def _row_spec(m_blk, c_blk):
    return pl.BlockSpec((m_blk, c_blk), lambda c, t: (t, c))


def _chan_spec(c_blk):
    return pl.BlockSpec((1, c_blk), lambda c, t: (0, c))


def _stats(x2, c_blk, interpret):
    m, c = x2.shape
    m_blk = _m_for(m, c_blk, 1)
    out = jax.ShapeDtypeStruct((1, c), jnp.float32)
    sums, sqs = pl.pallas_call(
        _stats_kernel,
        grid=(c // c_blk, m // m_blk),
        in_specs=[_row_spec(m_blk, c_blk)],
        out_specs=[_chan_spec(c_blk), _chan_spec(c_blk)],
        out_shape=[out, out],
        scratch_shapes=[_vmem((1, c_blk), jnp.float32),
                        _vmem((1, c_blk), jnp.float32)],
        compiler_params=_params(interpret, reduce_m=True),
        interpret=interpret,
        name="hvd_bn_stats",
    )(x2)
    return sums, sqs


def _apply(x2, mean, var, gamma, beta, res2, c_blk, eps, relu,
           interpret):
    m, c = x2.shape
    residual = res2 is not None
    m_blk = _m_for(m, c_blk, 3 if residual else 2)
    args = [x2, mean, var, gamma, beta] + ([res2] if residual else [])
    return pl.pallas_call(
        functools.partial(_apply_kernel, eps=eps, relu=relu,
                          residual=residual),
        grid=(c // c_blk, m // m_blk),
        in_specs=[_row_spec(m_blk, c_blk)] + [_chan_spec(c_blk)] * 4
        + ([_row_spec(m_blk, c_blk)] if residual else []),
        out_specs=_row_spec(m_blk, c_blk),
        out_shape=jax.ShapeDtypeStruct((m, c), x2.dtype),
        compiler_params=_params(interpret, reduce_m=False),
        interpret=interpret,
        name="hvd_bn_apply",
    )(*args)


def _bwd_reductions(x2, dy2, mean, var, gamma, beta, res2, c_blk,
                    eps, relu, interpret):
    m, c = x2.shape
    residual = res2 is not None
    m_blk = _m_for(m, c_blk, 3 if residual else 2)
    args = [x2, dy2, mean, var, gamma, beta] + (
        [res2] if residual else [])
    out = jax.ShapeDtypeStruct((1, c), jnp.float32)
    db, dg = pl.pallas_call(
        functools.partial(_bwd_red_kernel, eps=eps, relu=relu,
                          residual=residual),
        grid=(c // c_blk, m // m_blk),
        in_specs=[_row_spec(m_blk, c_blk)] * 2 + [_chan_spec(c_blk)] * 4
        + ([_row_spec(m_blk, c_blk)] if residual else []),
        out_specs=[_chan_spec(c_blk), _chan_spec(c_blk)],
        out_shape=[out, out],
        scratch_shapes=[_vmem((1, c_blk), jnp.float32),
                        _vmem((1, c_blk), jnp.float32)],
        compiler_params=_params(interpret, reduce_m=True),
        interpret=interpret,
        name="hvd_bn_bwd_reductions",
    )(*args)
    return db, dg


def _bwd_dx(x2, dy2, mean, var, gamma, beta, db, dg, res2, c_blk,
            eps, relu, total_m, interpret):
    m, c = x2.shape
    residual = res2 is not None
    m_blk = _m_for(m, c_blk, 5 if residual else 3)
    args = [x2, dy2, mean, var, gamma, beta, db, dg] + (
        [res2] if residual else [])
    outs = [jax.ShapeDtypeStruct((m, c), x2.dtype)]
    if residual:
        outs.append(jax.ShapeDtypeStruct((m, c), res2.dtype))
    out_specs = [_row_spec(m_blk, c_blk)] * len(outs)
    res = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, eps=eps, relu=relu,
                          residual=residual, inv_m=1.0 / total_m),
        grid=(c // c_blk, m // m_blk),
        in_specs=[_row_spec(m_blk, c_blk)] * 2 + [_chan_spec(c_blk)] * 6
        + ([_row_spec(m_blk, c_blk)] if residual else []),
        out_specs=out_specs if residual else out_specs[0],
        out_shape=outs if residual else outs[0],
        compiler_params=_params(interpret, reduce_m=False),
        interpret=interpret,
        name="hvd_bn_bwd_dx",
    )(*args)
    return res if residual else (res, None)


# ---------------------------------------------------------------------------
# custom-vjp op
# ---------------------------------------------------------------------------


def _tile_cols(vec_c, fold, cv):
    """[C] per-channel vector -> [1, fold*C] row matching the folded
    view's column order (column j holds channel j % C)."""
    return jnp.tile(vec_c.reshape(-1), fold).reshape(1, cv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _bn_act_apply(x2, gamma, beta, res2, mean, var, eps, relu, plan):
    """Normalize+affine(+add)(+relu) with the full fused BN backward.

    Operates on the (M/fold, fold*C) view; every per-channel vector
    arrives pre-tiled to the view's columns.  ``mean``/``var`` arrive
    stop-gradiented: their x-dependence is already inside the backward
    formula (the standard BN dx), so the stats pass itself never needs
    differentiating.
    """
    fold, c_blk = plan
    return _apply(x2, mean, var, gamma, beta, res2, c_blk, eps,
                  relu, not on_tpu())


def _bn_act_apply_fwd(x2, gamma, beta, res2, mean, var, eps, relu,
                      plan):
    y = _bn_act_apply(x2, gamma, beta, res2, mean, var, eps, relu,
                      plan)
    return y, (x2, gamma, beta, res2, mean, var)


def _bn_act_apply_bwd(eps, relu, plan, saved, dy):
    x2, gamma, beta, res2, mean, var = saved
    fold, c_blk = plan
    interpret = not on_tpu()
    mv, cv = x2.shape
    c = cv // fold
    # Raw per-view-column sums: exactly the cotangents of the TILED
    # gamma/beta rows (jnp.tile's transpose outside folds them to [C]).
    db_v, dg_v = _bwd_reductions(x2, dy, mean, var, gamma, beta, res2,
                                 c_blk, eps, relu, interpret)
    if fold > 1:
        db_t = _tile_cols(db_v.reshape(fold, c).sum(0), fold, cv)
        dg_t = _tile_cols(dg_v.reshape(fold, c).sum(0), fold, cv)
    else:
        db_t, dg_t = db_v, dg_v
    dx, dres = _bwd_dx(x2, dy, mean, var, gamma, beta, db_t, dg_t,
                       res2, c_blk, eps, relu, mv * fold,
                       interpret)
    return (dx, dg_v.astype(gamma.dtype), db_v.astype(beta.dtype),
            dres, jnp.zeros_like(mean), jnp.zeros_like(var))


_bn_act_apply.defvjp(_bn_act_apply_fwd, _bn_act_apply_bwd)


def batch_norm_act(x, gamma, beta, residual=None, *, eps: float = 1e-5,
                   relu: bool = True):
    """Fused train-mode BN (+residual add) (+ReLU) over the last axis.

    Returns ``(y, mean, var)``; mean/var are f32 batch statistics for
    the running-stats update and are NOT differentiated through (their
    effect on dx is already inside the fused backward -- they are
    stop-gradient side outputs, exactly flax's running-stats usage).
    Returns None when the shape doesn't tile -- caller falls back to
    the XLA path.
    """
    c = x.shape[-1]
    m = x.size // c
    plan = _plan(m, c)
    if plan is None:
        return None
    fold, c_blk = plan
    mv, cv = m // fold, c * fold
    x2 = x.reshape(mv, cv)  # row-major: free view
    res2 = None if residual is None else residual.reshape(mv, cv)
    interpret = not on_tpu()
    # stop_gradient BEFORE the stats kernel: its x-dependence is folded
    # into the fused backward's dx formula, so the pallas_call itself
    # must never be traced for autodiff.
    sums, sqs = _stats(jax.lax.stop_gradient(x2), c_blk, interpret)
    s = sums.reshape(fold, c).sum(0)
    q = sqs.reshape(fold, c).sum(0)
    mean = jax.lax.stop_gradient(s / m)
    var = jax.lax.stop_gradient(
        jnp.maximum(q / m - jnp.square(s / m), 0.0))
    g = gamma.astype(jnp.float32)
    b = beta.astype(jnp.float32)
    y = _bn_act_apply(x2, _tile_cols(g, fold, cv),
                      _tile_cols(b, fold, cv), res2,
                      _tile_cols(mean, fold, cv),
                      _tile_cols(var, fold, cv), eps, relu, plan)
    return (y.reshape(x.shape), mean, var)
