"""Pipeline parallelism: GPipe-style microbatching over the 'pp' axis.

Beyond-reference extension (SURVEY.md §2.5: PP is absent from the
reference).  TPU-native design: stages are mesh shards; activations flow
stage-to-stage with ``collective-permute`` (``lax.ppermute``) inside one
compiled program, microbatches filling the pipeline in a ``lax.fori_loop``
(M + n_stages - 1 ticks).  Backward is jax AD straight through the loop —
the transposed program pipelines gradients in the reverse direction with
the transposed permutes.

Layer-stacked parameters ``[L, ...]`` are sharded over 'pp' on dim 0, so
every shard holds a contiguous group of layers (its stage).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def pipeline_apply(stage_params, microbatches, stage_fn: Callable,
                   axis_name: str = "pp"):
    """Run microbatches through the stage pipeline.

    stage_params: this shard's layer-group params (pytree; leaves stacked
      [L_local, ...] to be scanned by ``stage_fn``).
    microbatches: [M, mb, ...] — every shard receives the same stacked
      microbatch inputs (only stage 0 actually consumes them).
    stage_fn(stage_params, activation) -> activation for one stage.

    Returns [M, mb, ...] final-stage outputs, replicated to all shards.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m = microbatches.shape[0]
    act_shape = microbatches.shape[1:]
    total_ticks = m + n - 1
    fwd_perm = [(i, (i + 1) % n) for i in range(n)]

    state = jnp.zeros(act_shape, microbatches.dtype)
    outputs = jnp.zeros((m,) + act_shape, microbatches.dtype)

    def tick(t, carry):
        state, outputs = carry
        # Stage 0 injects microbatch t (when one remains); other stages
        # consume what arrived from their predecessor last tick.
        mb_index = jnp.minimum(t, m - 1)
        inject = lax.dynamic_index_in_dim(microbatches, mb_index, axis=0,
                                          keepdims=False)
        inp = jnp.where(idx == 0, inject, state)
        act = stage_fn(stage_params, inp)
        # The last stage's act for tick t belongs to microbatch t-(n-1).
        out_index = jnp.clip(t - (n - 1), 0, m - 1)
        is_valid = (idx == n - 1) & (t >= n - 1)
        current = lax.dynamic_index_in_dim(outputs, out_index, axis=0,
                                           keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(is_valid, act, current), out_index, axis=0)
        state = lax.ppermute(act, axis_name, fwd_perm)
        return state, outputs

    _, outputs = lax.fori_loop(0, total_ticks, tick, (state, outputs))
    # Replicate the last stage's outputs to every shard (cheap vs compute;
    # keeps loss computation and out_specs uniform).
    mask = (idx == n - 1).astype(outputs.dtype)
    return lax.psum(outputs * mask, axis_name)


def split_microbatches(batch, num_microbatches: int):
    """[B, ...] -> [M, B/M, ...] for pipeline_apply."""
    b = batch.shape[0]
    if b % num_microbatches:
        raise ValueError("batch %d not divisible by microbatches %d"
                         % (b, num_microbatches))
    return batch.reshape((num_microbatches, b // num_microbatches)
                         + batch.shape[1:])
