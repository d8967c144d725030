"""Pallas TPU kernel for the expert layer's row writes
(``parallel/moe.py: _routed``).

A block of the sorted (token, expert) pairs ends by adding its rows into
``y`` at their tokens.  XLA's scatter-add walks the rows one after another
(0.4 us a row of 4096 bfloat16 on the v5e, whatever it is told about its
indices), because two rows may hit one token.  Here they cannot
(``parallel/moe.py: _block``), so ``combine`` moves every row by a DMA of
its own, with no order among them: the tokens' rows of ``y`` into VMEM, one
vector add, and back, ``y`` updated in place.

A DMA moves whole tiles, and one row of a ``[T, d]`` array is an eighth of
its ``(8, 128)`` tiles (of bfloat16, half of a packed sixteenth), so the
loop carries ``y`` as ``[T, d / 128, 128]``, where a token's row is ``d /
128`` sublanes of whole tiles (``as_rows``); the one copy back to ``[T,
d]`` after the loop is what the kernel costs beside its own time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..common.device import on_tpu
from .pallas_kernels import _sds

COMBINE = "hvd_moe_combine"


def as_rows(shape):
    """``[T, d]`` as the shape ``combine`` takes ``y`` in.  A token's row
    is whole tiles only where its sublanes are a multiple of 8: a ``d`` of
    21 x 128 is carried as 24 sublanes, the last three zero
    (``from_rows`` drops them)."""
    t, d = shape
    if d % 128:
        return t, 1, d
    sublanes = d // 128
    return t, -(-sublanes // 8) * 8, 128


def from_rows(y, shape):
    """``y`` as ``as_rows(shape)`` carried it, back as ``shape``."""
    t, d = shape
    y = y.reshape(t, -1)
    return y if y.shape[1] == d else y[:, :d]


def _combine_kernel(tokens, y_in, rows, y, buf, sems, *, chunk):
    from jax.experimental.pallas import tpu as pltpu
    del y_in                                    # aliased: ``y`` is it
    first = pl.program_id(0) * chunk

    def each(row, act):
        """``act`` on the copy of every real row, to VMEM or back."""
        def body(r, _):
            token = tokens[first + r]
            there, here = y.at[pl.ds(token, 1)], buf.at[pl.ds(r, 1)]
            copy = pltpu.make_async_copy(there, here, sems.at[0]) if row \
                else pltpu.make_async_copy(here, there, sems.at[1])
            # A padded row goes nowhere.
            pl.when(token < y.shape[0])(functools.partial(act, copy))
            return 0
        lax.fori_loop(0, chunk, body, 0)

    # Under a condition, as in ``kda_kernels``: the interpreter, run inside
    # a shard_map that checks what varies over the mesh, only takes a
    # kernel's own values beside its varying operands inside one.
    @pl.when(first >= 0)
    def _():
        each(True, lambda copy: copy.start())
        each(True, lambda copy: copy.wait())
        buf[...] = buf[...] + rows[...]
        each(False, lambda copy: copy.start())
        each(False, lambda copy: copy.wait())


def combine(y, rows, tokens):
    """``y`` ``[T, s, l]`` with ``rows`` ``[R, d]`` (``d <= s * l``, zeros
    behind) added at ``tokens`` ``[R]``, in place.  The tokens in range are
    distinct; one out of range (``>= T``) marks a row that is not
    written."""
    from jax.experimental.pallas import tpu as pltpu
    _, s, l = y.shape
    n, d = rows.shape
    if d < s * l:                       # ``as_rows`` padded the sublanes
        rows = jnp.pad(rows, ((0, 0), (0, s * l - d)))
    chunk = math.gcd(n, 128)
    return pl.pallas_call(
        functools.partial(_combine_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // chunk,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((chunk, s, l), lambda i, _: (i, 0, 0))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((chunk, s, l), y.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=_sds(y.shape, y.dtype, y),
        input_output_aliases={1: 0},
        interpret=not on_tpu(),
        name=COMBINE,
    )(tokens, y, rows.reshape(n, s, l))
