"""The one backward flash kernel compiled for a described v5e at the shapes
the benchmark's cells run (no chip: ``jax.experimental.topologies``).  What
Mosaic refuses (a slice off the tiling, more VMEM than the limit) it
refuses here, which interpret mode cannot show.  A compile, not a speed."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # Such a compile can be written to the persistent cache and not read
    # back without a chip: keep it out.
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache)


# laguna's full and sliding layers, pt8k's and nemotron's softmax layers,
# BERT's (head 64 padded), and float32 inputs, whose output block halves the
# heads a step.
@pytest.mark.parametrize("flat_heads, seq, window, causal, dtype", [
    (96, 8192, None, True, jnp.bfloat16),
    (128, 8192, 512, True, jnp.bfloat16),
    (16, 8192, None, True, jnp.bfloat16),
    (64, 8192, None, True, jnp.bfloat16),
    (64, 512, None, False, jnp.bfloat16),
    (8, 8192, 512, True, jnp.float32)])
def test_the_one_backward_kernel_compiles_for_v5e(one_chip, flat_heads, seq,
                                                  window, causal, dtype):
    block_q, block_k, d_pad, _ = pk._plan(seq, 128, window)
    form, heads = pk._backward_form(flat_heads, seq, d_pad,
                                    jnp.dtype(dtype).itemsize, window)
    assert form == "onepass"
    x = jax.ShapeDtypeStruct((flat_heads, seq, d_pad), dtype,
                             sharding=one_chip)
    rows = jax.ShapeDtypeStruct((flat_heads, seq, 1), jnp.float32,
                                sharding=one_chip)
    compiled = jax.jit(functools.partial(
        pk._flash_attention_bwd_onepass_flat, causal=causal, block_q=block_q,
        block_k=block_k, interpret=False, window=window,
        heads=heads)).lower(x, x, x, x, rows, rows).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    # dq, dk, dv in the inputs' dtype and nothing else: no partial in HBM
    assert [(o.shape, o.dtype) for o in compiled.out_info] \
        == [((flat_heads, seq, d_pad), jnp.dtype(dtype))] * 3
