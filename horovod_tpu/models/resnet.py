"""ResNet-v1.5 family (ResNet-50 flagship) in flax.

Parity with the reference's headline benchmark workload
(``examples/pytorch/pytorch_imagenet_resnet50.py`` +
``pytorch_synthetic_benchmark.py``; BASELINE.md metric
"ResNet-50 images/sec/chip").  TPU-first choices: NHWC layout (XLA's
native conv layout on TPU), bf16 activations on the MXU, optional
cross-replica SyncBatchNorm via the framework's DP axis.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

STAGE_SIZES = {
    18: [2, 2, 2, 2],
    34: [3, 4, 6, 3],
    50: [3, 4, 6, 3],
    101: [3, 4, 23, 3],
    152: [3, 8, 36, 3],
}


def _pallas_bn_enabled() -> bool:
    """Opt-in fused Pallas BN kernels (HVD_TPU_PALLAS_BN=1 on TPU,
    =force off-TPU via the interpreter).

    Default OFF after measurement: mid-CNN custom calls constrain
    operand layouts to plain row-major, and XLA brackets every kernel
    with full-activation layout copies (323 copy ops vs 7, measured on
    the ResNet-50 train step -> 112 ms/step vs 47 ms).  XLA's own
    fused BN+relu+add is within ~2x of the HBM floor, so the copies
    cost far more than the fusion saves.  The kernels stay correct and
    tested (tests/test_pallas_bn.py) for standalone use, where no
    layout boundary exists.  See docs/benchmarks.md."""
    v = os.environ.get("HVD_TPU_PALLAS_BN", "0").lower()
    if v in ("0", "false", "no", ""):
        return False
    if v == "force":
        return True
    from ..common.device import on_tpu
    return on_tpu()


class NormAct(nn.Module):
    """BatchNorm + optional residual add + optional ReLU, as ONE op.

    Train mode on TPU runs the fused Pallas kernels
    (``ops/pallas_bn.py``: single-read stats, fused
    normalize+add+relu, fused dbeta/dgamma reductions, fused
    dx+dresidual); eval mode, sync-BN (``axis_name``), and non-tiling
    shapes use the plain XLA path.  Parameter/stat names match flax
    ``nn.BatchNorm`` (scale/bias, batch_stats mean/var).
    """

    relu: bool = True
    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16
    axis_name: Optional[str] = None
    scale_init: Callable = nn.initializers.ones

    @nn.compact
    def __call__(self, x, residual=None):
        c = x.shape[-1]
        scale = self.param("scale", self.scale_init, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,),
                          jnp.float32)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((c,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((c,), jnp.float32))

        if self.use_running_average:
            mean, var = ra_mean.value, ra_var.value
            y = self._xla_apply(x, mean, var, scale, bias, residual)
            return y

        out = None
        if self.axis_name is None and _pallas_bn_enabled():
            from ..ops.pallas_bn import batch_norm_act
            out = batch_norm_act(x, scale, bias, residual,
                                 eps=self.epsilon, relu=self.relu)
        if out is not None:
            y, mean, var = out
        else:
            xf = x.astype(jnp.float32)
            axes = tuple(range(x.ndim - 1))
            mean = jnp.mean(xf, axes)
            sq = jnp.mean(jnp.square(xf), axes)
            if self.axis_name is not None:
                mean = jax.lax.pmean(mean, self.axis_name)
                sq = jax.lax.pmean(sq, self.axis_name)
            var = jnp.maximum(sq - jnp.square(mean), 0.0)
            y = self._xla_apply(x, mean, var, scale, bias, residual)
        if not self.is_initializing():
            m = self.momentum
            ra_mean.value = m * ra_mean.value + (1 - m) * mean
            ra_var.value = m * ra_var.value + (1 - m) * var
        return y

    def _xla_apply(self, x, mean, var, scale, bias, residual):
        # [C]-sized math stays f32; the activation-sized elementwise
        # pass runs in the compute dtype (flax semantics — bf16 keeps
        # the HBM traffic at half width).
        mul = (jax.lax.rsqrt(var + self.epsilon) * scale).astype(
            self.dtype)
        add = (bias - mean * jax.lax.rsqrt(var + self.epsilon)
               * scale).astype(self.dtype)
        z = x.astype(self.dtype) * mul + add
        if residual is not None:
            z = z + residual.astype(self.dtype)
        if self.relu:
            z = jnp.maximum(z, 0)
        return z.astype(self.dtype)


class BottleneckBlock(nn.Module):
    filters: int
    strides: Tuple[int, int]
    norm: Callable  # NormAct factory; kwargs: relu, scale_init
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        residual = x
        y = nn.Conv(self.filters, (1, 1), use_bias=False,
                    dtype=self.dtype)(x)
        y = self.norm()(y)
        y = nn.Conv(self.filters, (3, 3), self.strides, use_bias=False,
                    dtype=self.dtype)(y)
        y = self.norm()(y)
        y = nn.Conv(self.filters * 4, (1, 1), use_bias=False,
                    dtype=self.dtype)(y)
        if residual.shape[-1] != self.filters * 4 or \
                self.strides != (1, 1):
            residual = nn.Conv(self.filters * 4, (1, 1), self.strides,
                               use_bias=False, dtype=self.dtype)(residual)
            residual = self.norm(relu=False)(residual)
        # One fused op: BN(y) + residual, then ReLU.
        return self.norm(scale_init=nn.initializers.zeros)(y, residual)


class BasicBlock(nn.Module):
    filters: int
    strides: Tuple[int, int]
    norm: Callable
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        residual = x
        y = nn.Conv(self.filters, (3, 3), self.strides, use_bias=False,
                    dtype=self.dtype)(x)
        y = self.norm()(y)
        y = nn.Conv(self.filters, (3, 3), use_bias=False,
                    dtype=self.dtype)(y)
        if residual.shape[-1] != self.filters or self.strides != (1, 1):
            residual = nn.Conv(self.filters, (1, 1), self.strides,
                               use_bias=False, dtype=self.dtype)(residual)
            residual = self.norm(relu=False)(residual)
        return self.norm(scale_init=nn.initializers.zeros)(y, residual)


class ResNet(nn.Module):
    depth: int = 50
    num_classes: int = 1000
    dtype: Any = jnp.bfloat16
    sync_batch_norm: bool = False
    axis_name: Optional[str] = "hvd"

    @nn.compact
    def __call__(self, x, train: bool = True):
        norm = partial(
            NormAct, use_running_average=not train, momentum=0.9,
            epsilon=1e-5, dtype=self.dtype,
            axis_name=self.axis_name if (self.sync_batch_norm and train)
            else None)
        block = BottleneckBlock if self.depth >= 50 else BasicBlock
        x = x.astype(self.dtype)
        x = nn.Conv(64, (7, 7), (2, 2), padding=[(3, 3), (3, 3)],
                    use_bias=False, dtype=self.dtype)(x)
        x = norm()(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, n_blocks in enumerate(STAGE_SIZES[self.depth]):
            for j in range(n_blocks):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = block(64 * 2 ** i, strides, norm, self.dtype)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x.astype(jnp.float32)


def create_resnet50(num_classes: int = 1000, dtype=jnp.bfloat16,
                    sync_batch_norm: bool = False):
    return ResNet(depth=50, num_classes=num_classes, dtype=dtype,
                  sync_batch_norm=sync_batch_norm)


def create_resnet101(num_classes: int = 1000, dtype=jnp.bfloat16,
                     sync_batch_norm: bool = False):
    """The reference's published ~90% scaling-efficiency row pairs
    ResNet-101 with Inception-V3 (BASELINE.md); depth 101 reuses the
    same bottleneck stack ([3, 4, 23, 3] stages)."""
    return ResNet(depth=101, num_classes=num_classes, dtype=dtype,
                  sync_batch_norm=sync_batch_norm)


def resnet_loss_fn(model: ResNet, variables, batch, train: bool = True):
    """Cross-entropy + batch-stat update handling for flax BatchNorm."""
    if train:
        logits, new_state = model.apply(
            variables, batch["x"], train=True, mutable=["batch_stats"])
    else:
        logits = model.apply(variables, batch["x"], train=False)
        new_state = {}
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, batch["y"][:, None], axis=1).mean()
    return nll, new_state
