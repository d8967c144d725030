"""Pallas kernel tests (interpret mode on the CPU world; the same
kernel code compiles on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common import metrics
from horovod_tpu.ops import pallas_kernels as pk
from horovod_tpu.ops.pallas_kernels import (flash_attention,
                                            fused_scale_sum,
                                            _reference_attention)


def _qkv(b=2, s=128, h=2, d=32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_reference(causal):
    q, k, v = _qkv()
    got = flash_attention(q, k, v, causal=causal)
    want = _reference_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_blocks_span_sequence():
    # seq 256 → multiple q and k blocks; checks the online-softmax
    # accumulation across blocks
    q, k, v = _qkv(b=1, s=256, h=1, d=64, seed=1)
    got = flash_attention(q, k, v, causal=True)
    want = _reference_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_irregular_seq_falls_back():
    q, k, v = _qkv(b=1, s=96, h=1, d=16, seed=2)
    got = flash_attention(q, k, v, causal=True)
    want = _reference_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_grad(causal):
    # The Pallas backward (dq AND dk/dv kernels) against autodiff of
    # the reference oracle.
    q, k, v = _qkv(b=1, s=128, h=2, d=32, seed=3)

    def loss_flash(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=causal) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(_reference_attention(q_, k_, v_, causal) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, lbl in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4,
                                   err_msg="d%s mismatch" % lbl)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_grad_multiblock_grid(causal):
    # s=192 -> 3x3 grid of 64-blocks: exercises cross-block scratch
    # accumulation, the init/finish grid boundaries, and the causal
    # block-live skip in BOTH backward kernels (s=128 is a 1x1 grid
    # where those paths degenerate).
    q, k, v = _qkv(b=1, s=192, h=2, d=32, seed=7)

    def loss_flash(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=causal) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(_reference_attention(q_, k_, v_, causal) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, lbl in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4,
                                   err_msg="d%s mismatch" % lbl)


# The r9 kernel grid: both Pallas backward structures (two-pass dq/dkv
# and the one kernel that keeps dq of a head in VMEM) across block shapes, causal
# on/off, and a lane-padded vs exact head dim — the interpret-mode
# numerics net under any kernel restructure.  Block shapes are driven
# through the HVD_TPU_FLASH_BLOCK_Q/K hooks, exactly how an A/B or the
# autotune sweep drives them.
@pytest.mark.parametrize(
    "variant,block_q,block_k,causal",
    # full causal coverage over the block pairs; non-causal once per
    # variant (the masking branch is the only causal-sensitive code,
    # and interpret-mode grads are the expensive part of tier-1)
    [(v, bq, bk, True) for v in ("pallas", "pallas_onepass")
     for bq, bk in ((64, 128), (128, 64), (128, 128))]
    + [(v, 128, 128, False) for v in ("pallas", "pallas_onepass")])
def test_flash_bwd_grid(monkeypatch, variant, block_q, block_k, causal):
    monkeypatch.setenv("HVD_TPU_FLASH_BWD", variant)
    monkeypatch.setenv("HVD_TPU_FLASH_BLOCK_Q", str(block_q))
    monkeypatch.setenv("HVD_TPU_FLASH_BLOCK_K", str(block_k))
    q, k, v = _qkv(b=1, s=128, h=1, d=32, seed=11)

    def loss_flash(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=causal) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(_reference_attention(q_, k_, v_, causal) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, lbl in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4,
                                   err_msg="d%s mismatch" % lbl)


@pytest.mark.parametrize("variant", ["pallas", "pallas_onepass"])
def test_flash_bwd_grid_exact_lane_dim(monkeypatch, variant):
    # d=128: no lane padding (d_pad == d) — the zero-column path of the
    # d=32 grid above must not be the only covered layout.
    monkeypatch.setenv("HVD_TPU_FLASH_BWD", variant)
    q, k, v = _qkv(b=1, s=128, h=1, d=128, seed=12)

    def loss_flash(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(_reference_attention(q_, k_, v_, True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, lbl in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4,
                                   err_msg="d%s mismatch" % lbl)


def test_flash_bwd_onepass_multiblock_grid(monkeypatch):
    # s=192 -> 3x3 grid of 64-blocks: the one kernel's dk/dv scratch and
    # its whole-head dq accumulator across a grid where causal skipping
    # actually fires (a dead tile adds nothing to its query block's rows).
    monkeypatch.setenv("HVD_TPU_FLASH_BWD", "pallas_onepass")
    q, k, v = _qkv(b=1, s=192, h=2, d=32, seed=13)

    def loss_flash(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(_reference_attention(q_, k_, v_, True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, lbl in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4,
                                   err_msg="d%s mismatch" % lbl)


def _backward_kernels(q, k, v, **kw):
    traced = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(flash_attention(*a, **kw) ** 2),
        argnums=(0, 1, 2)))(q, k, v))
    return {name for name in ("hvd_flash_dq", "hvd_flash_dkv",
                              "hvd_flash_bwd_onepass", "hvd_flash_window_dq",
                              "hvd_flash_window_dkv") if name in traced}


def _backward_calls():
    series = metrics.metrics_snapshot().get(
        "hvd_flash_backward_calls_total", {}).get("series", ())
    return {(row["labels"]["form"], row["labels"]["window"]): row["value"]
            for row in series}


@pytest.mark.parametrize("window", [None, 64])
def test_flash_bwd_form_follows_the_shape_when_unset(monkeypatch, window):
    """With ``HVD_TPU_FLASH_BWD`` unset the one kernel runs where dq of a
    grid step's heads fits the VMEM budget and the two kernels where it
    does not; ``pallas`` is the two kernels whatever the shape,
    ``pallas_onepass`` refuses a shape that does not fit, and every traced
    call is counted by its form."""
    monkeypatch.delenv("HVD_TPU_FLASH_BWD", raising=False)
    metrics.reset()
    q, k, v = _qkv(b=1, s=128, h=2, d=32, seed=16)
    banded = str(int(window is not None))
    one = {"hvd_flash_window_dkv"} if window else {"hvd_flash_bwd_onepass"}
    two = ({"hvd_flash_window_dq", "hvd_flash_window_dkv"} if window
           else {"hvd_flash_dq", "hvd_flash_dkv"})
    assert _backward_kernels(q, k, v, window=window) == one
    monkeypatch.setenv("HVD_TPU_FLASH_BWD", "pallas")
    assert _backward_kernels(q, k, v, window=window) == two
    monkeypatch.setenv("HVD_TPU_FLASH_BWD", "chunked")
    assert _backward_kernels(q, k, v, window=window) == set()
    # dq of one head: 128 x 128 float32 and its float32 output block twice
    monkeypatch.setattr(pk, "_ONEPASS_VMEM_BUDGET", 128 * 128 * 12 - 1)
    monkeypatch.delenv("HVD_TPU_FLASH_BWD")
    assert _backward_kernels(q, k, v, window=window) == two
    monkeypatch.setenv("HVD_TPU_FLASH_BWD", "pallas_onepass")
    with pytest.raises(ValueError, match="does not fit"):
        _backward_kernels(q, k, v, window=window)
    assert _backward_calls() == {("onepass", banded): 1,
                                 ("two_kernel", banded): 2,
                                 ("chunked", banded): 1}


def test_flash_bwd_heads_a_step_shrink_to_the_budget(monkeypatch):
    """Under a window the one kernel takes as many heads a grid step as
    the other kernels of the call where their dq fits, fewer where it does
    not, and a ragged sequence's XLA backward is counted as such."""
    a_head = 8192 * 128 * (4 + 2 * 2)           # bfloat16: 8 MiB
    assert pk._ONEPASS_VMEM_BUDGET >= 4 * a_head
    assert pk._backward_form(128, 8192, 128, 2, 512) == ("onepass", 4)
    assert pk._backward_form(96, 8192, 128, 2, None) == ("onepass", 1)
    assert pk._backward_form(128, 8192, 128, 4, 512) == ("onepass", 2)
    monkeypatch.setattr(pk, "_ONEPASS_VMEM_BUDGET", a_head)
    assert pk._backward_form(128, 8192, 128, 2, 512) == ("onepass", 1)
    assert pk._backward_form(128, 16384, 128, 2, 512) == ("two_kernel", 4)
    assert pk._backward_form(96, 16384, 128, 2, None) == ("two_kernel", 1)
    metrics.reset()
    q, k, v = _qkv(b=1, s=96, h=1, d=16, seed=17)
    assert _backward_kernels(q, k, v) == set()
    assert _backward_calls() == {("xla", "0"): 1}


def test_flash_plan_info_tells_the_backward_form(monkeypatch):
    for name in ("HVD_TPU_FLASH_BWD", "HVD_TPU_FLASH_BLOCK_Q",
                 "HVD_TPU_FLASH_BLOCK_K"):
        monkeypatch.delenv(name, raising=False)
    assert pk.flash_plan_info(8192, 128)["bwd"] == "onepass"
    assert pk.flash_plan_info(512, 64)["bwd"] == "onepass"
    assert pk.flash_plan_info(100, 64)["bwd"] == "xla"
    monkeypatch.setattr(pk, "_ONEPASS_VMEM_BUDGET", 1 << 20)
    assert pk.flash_plan_info(8192, 128)["bwd"] == "two_kernel"
    monkeypatch.setenv("HVD_TPU_FLASH_BWD", "chunked")
    assert pk.flash_plan_info(8192, 128)["bwd"] == "chunked"
    monkeypatch.setenv("HVD_TPU_FLASH_BWD", "pallas")
    assert pk.flash_plan_info(8192, 128)["bwd"] == "two_kernel"
    monkeypatch.setenv("HVD_TPU_FLASH_BWD", "onepass")
    with pytest.raises(ValueError, match="HVD_TPU_FLASH_BWD"):
        pk.flash_plan_info(8192, 128)


def test_flash_bwd_unknown_variant_fails_loudly(monkeypatch):
    monkeypatch.setenv("HVD_TPU_FLASH_BWD", "onepass")  # typo'd value
    q, k, v = _qkv(b=1, s=128, h=1, d=32, seed=14)
    with pytest.raises(ValueError, match="HVD_TPU_FLASH_BWD"):
        jax.grad(lambda q_: jnp.sum(
            flash_attention(q_, k, v, causal=True) ** 2))(q)


def test_autotune_flash_blocks_pins_plan(monkeypatch):
    # The sweep measures each candidate and PINS the winner into the
    # plan registry: _plan must consult it, and env overrides must win
    # over (and suppress) the pin.
    monkeypatch.delenv("HVD_TPU_FLASH_BLOCK_Q", raising=False)
    monkeypatch.delenv("HVD_TPU_FLASH_BLOCK_K", raising=False)
    try:
        info = pk.autotune_flash_blocks(
            128, 32, batch_heads=1, iters=1, include_bwd=False,
            candidates=[(64, 64), (128, 128)], report_core=False)
        assert info["pinned"], info
        assert info["best"] in info["candidates"]
        assert pk._TUNED_BLOCKS[(128, 128)] == info["best"]
        plan = pk.flash_plan_info(128, 32)
        assert plan["source"] == "autotuned"
        assert (plan["block_q"], plan["block_k"]) == info["best"]
        # tuned blocks still produce oracle-exact attention
        q, k, v = _qkv(b=1, s=128, h=1, d=32, seed=15)
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v, causal=True)),
            np.asarray(_reference_attention(q, k, v, True)),
            atol=2e-5, rtol=2e-5)
        # an explicit env A/B wins over the tuner and suppresses pinning
        monkeypatch.setenv("HVD_TPU_FLASH_BLOCK_Q", "64")
        assert pk.flash_plan_info(128, 32)["source"] == "env"
        info2 = pk.autotune_flash_blocks(
            128, 32, batch_heads=1, iters=1, include_bwd=False,
            candidates=[(64, 64)], report_core=False)
        assert not info2["pinned"]
    finally:
        pk._TUNED_BLOCKS.clear()


def test_kernel_tuner_native_mirror():
    # The C++ KernelTuner (core/src/parameter_manager.cc) must agree
    # with the Python KernelBlockTuner on argmax-by-mean.
    pytest.importorskip("ctypes")
    from horovod_tpu.core.client import (core_library_available,
                                         load_library)
    if not core_library_available():
        pytest.skip("native core not buildable here")
    lib = load_library()
    base = lib.hvd_tcp_kernel_tune_samples()
    # Huge scores so this test's choices dominate any samples another
    # in-process test may have recorded into the singleton tuner.
    lib.hvd_tcp_kernel_tune_record(7, 1.0e18)
    lib.hvd_tcp_kernel_tune_record(9, 3.0e18)
    lib.hvd_tcp_kernel_tune_record(9, 5.0e18)
    lib.hvd_tcp_kernel_tune_record(7, 10.0e18)  # mean 5.5e18 beats 4e18
    assert lib.hvd_tcp_kernel_tune_best() == 7
    assert lib.hvd_tcp_kernel_tune_samples() == base + 4


def test_flash_attention_grad_chunked_escape_hatch(monkeypatch):
    # HVD_TPU_FLASH_BWD=chunked selects the XLA chunked backward; both
    # paths must match the oracle.
    monkeypatch.setenv("HVD_TPU_FLASH_BWD", "chunked")
    q, k, v = _qkv(b=1, s=128, h=1, d=32, seed=5)

    def loss_flash(q_):
        return jnp.sum(flash_attention(q_, k, v, causal=True) ** 2)

    def loss_ref(q_):
        return jnp.sum(_reference_attention(q_, k, v, True) ** 2)

    g1 = jax.grad(loss_flash)(q)
    g2 = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               atol=2e-4, rtol=2e-4)


def test_fused_scale_sum():
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(3, 50), jnp.float32)  # non-lane-aligned
    b = jnp.asarray(rng.randn(3, 50), jnp.float32)
    got = fused_scale_sum(a, b, alpha=0.5, beta=2.0)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(0.5 * a + 2.0 * b),
                               atol=1e-6)


def test_flash_attention_gqa():
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 128, 4, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    got = flash_attention(q, k, v, causal=True)
    want = _reference_attention(q, jnp.repeat(k, 2, 2),
                                jnp.repeat(v, 2, 2), True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
