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
    # accumulation, the rows' first and last pairs, and a causal
    # schedule shorter than the rectangle in BOTH backward kernels (s=128
    # is one pair where those paths degenerate).
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
# numerics net under any kernel restructure.  Block shapes and the form are
# pinned by the ``flash_blocks`` fixture (tests/conftest.py).
@pytest.mark.parametrize(
    "variant,block_q,block_k,causal",
    # full causal coverage over the block pairs; non-causal once per
    # variant (the masking branch is the only causal-sensitive code,
    # and interpret-mode grads are the expensive part of tier-1)
    [(v, bq, bk, True) for v in ("pallas", "pallas_onepass")
     for bq, bk in ((64, 128), (128, 64), (128, 128))]
    + [(v, 128, 128, False) for v in ("pallas", "pallas_onepass")])
def test_flash_bwd_grid(flash_blocks, variant, block_q, block_k, causal):
    flash_blocks(block_q, block_k, variant)
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
def test_flash_bwd_grid_exact_lane_dim(flash_blocks, variant):
    # d=128: no lane padding (d_pad == d) — the zero-column path of the
    # d=32 grid above must not be the only covered layout.
    flash_blocks(backward=variant)
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


def test_flash_bwd_onepass_multiblock_grid():
    # s=192 -> 3x3 grid of 64-blocks: the one kernel's dk/dv scratch and
    # its whole-head dq accumulator across a schedule that leaves out the
    # pairs above the diagonal (nothing is added to their query blocks' rows).
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


# -- a full call's grid is a schedule of block pairs ---------------------------

# the cells' plan; square blocks; a query block wider than its key block; one
# pair a head (BERT); short sequences in the shapes the numerics below run
SCHEDULES = [(8192, 512, 1024), (8192, 512, 512), (8192, 1024, 512),
             (512, 512, 512), (256, 64, 128), (256, 128, 64), (192, 64, 64)]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq, block_q, block_k", SCHEDULES)
def test_the_schedule_visits_every_live_pair_once_in_both_orders(
        seq, block_q, block_k, causal):
    """By query block and by key block: the pairs that hold a seen score,
    each once, a row's pairs together and ascending, the row's first and
    last flagged, and ``masked`` on the pairs that also hold a hidden one."""
    rows, cols = np.arange(seq)[:, None], np.arange(seq)[None, :]
    seen = (cols <= rows) if causal else np.ones((seq, seq), bool)
    blocks = seen.reshape(seq // block_q, block_q, seq // block_k, block_k)
    live = {(j, t): not blocks[j, :, t].all()
            for j in range(seq // block_q) for t in range(seq // block_k)
            if blocks[j, :, t].any()}
    by_query, by_key = pk._block_schedule(seq, block_q, block_k, causal)
    for pairs, row_of, in_row in ((by_query, by_query.q, by_query.k),
                                  (by_key, by_key.k, by_key.q)):
        visited = list(zip(pairs.q.tolist(), pairs.k.tolist()))
        assert len(visited) == len(set(visited)) and set(visited) == set(live)
        assert [live[pair] for pair in visited] == pairs.masked.tolist()
        # rows in order and unbroken, each row's partners ascending
        assert row_of.tolist() == sorted(row_of.tolist())
        starts = np.flatnonzero(np.diff(row_of, prepend=-1))
        ends = np.flatnonzero(np.diff(row_of, append=-1))
        assert pairs.first.tolist() == np.isin(
            np.arange(len(visited)), starts).tolist()
        assert pairs.last.tolist() == np.isin(
            np.arange(len(visited)), ends).tolist()
        for a, b in zip(starts, ends):
            assert (np.diff(in_row[a:b + 1]) == 1).all()
    if not causal:
        assert len(by_query.q) == (seq // block_q) * (seq // block_k)
        assert not by_query.masked.any() and not by_key.masked.any()


def test_the_schedule_at_the_cells_shape():
    """8192 positions in blocks of 512 x 1024: 72 of the rectangle's 128
    pairs, 16 of them on the diagonal; BERT's 512 without a mask: one."""
    for pairs in pk._block_schedule(8192, *pk._plan(8192, 128)[:2], True):
        assert (len(pairs.q), int(pairs.masked.sum())) == (72, 16)
    for pairs in pk._block_schedule(512, *pk._plan(512, 64)[:2], False):
        assert (len(pairs.q), int(pairs.masked.sum())) == (1, 0)
        assert pairs.first.all() and pairs.last.all()


# Every cell's call shape and the blocks its plan takes, from the shape
# alone: BERT's (mlm512), the full softmax layers at 8192 (pt8k, laguna,
# nemotron, olmo), laguna's sliding layers, kanana's latent attention (192
# over 128, the rotary key shared) and a full call read in float32.
@pytest.mark.parametrize("shape, blocks", [
    (dict(s=512, d=64), (512, 512)),
    (dict(s=8192, d=128), (512, 1024)),
    (dict(s=8192, d=128, window=512), (512, 512)),
    (dict(s=8192, d=192, d_v=128, two_part_key=True), (1024, 1024)),
    (dict(s=8192, d=128, itemsize=4), (512, 1024))],
    ids=["mlm512", "full-8192", "window-512", "kanana", "float32"])
def test_the_plan_at_each_cells_call_shape(shape, blocks):
    assert pk._plan(**shape)[:2] == blocks


def _lse(q, k, causal):
    """log-sum-exp of the scaled, masked scores, ``[batch * heads, seq, 1]``."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        s = q.shape[1]
        scores = jnp.where(jnp.arange(s)[None, :] <= jnp.arange(s)[:, None],
                           scores, -jnp.inf)
    return jax.nn.logsumexp(scores, -1).reshape(-1, q.shape[1], 1)


# Output, log-sum-exp and the three gradients of a causal call whose
# schedule has interior and diagonal pairs, rows of unequal length and (at
# 128 x 64) two diagonal pairs a query block; the one backward kernel and
# the two; once without the mask.
@pytest.mark.parametrize("variant", ["pallas", "pallas_onepass"])
@pytest.mark.parametrize("block_q, block_k, causal", [
    (64, 128, True), (128, 64, True), (64, 64, True), (256, 256, True),
    (64, 128, False)])
def test_a_scheduled_call_matches_the_reference(flash_blocks, variant,
                                                block_q, block_k, causal):
    flash_blocks(block_q, block_k, variant)
    q, k, v = _qkv(b=1, s=256, h=2, d=32, seed=21)
    weight = _qkv(b=1, s=256, h=2, d=32, seed=22)[0]
    got, (*_, lse) = pk._flash_fwd(q, k, v, causal, None)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_reference_attention(q, k, v, causal)),
        atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(_lse(q, k, causal)),
                               atol=2e-5, rtol=2e-5)
    g1 = jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, causal=causal) * weight), argnums=(0, 1, 2))(
            q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(
        _reference_attention(*a, causal) * weight), argnums=(0, 1, 2))(
            q, k, v)
    for a, b, lbl in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4,
                                   err_msg="d%s mismatch" % lbl)


def test_a_full_call_of_several_heads_a_step_is_the_heads_one_by_one():
    """``heads`` flat heads a grid step under a schedule: the tables are
    every head's, the numbers the same to the bit."""
    rng = np.random.RandomState(23)
    q, k, v, g = (jnp.asarray(rng.randn(4, 256, 32), jnp.float32)
                  for _ in range(4))

    def all_three(heads):
        at = dict(causal=True, block_q=64, block_k=128, interpret=True,
                  heads=heads)
        o, lse = pk._flash_attention_fwd_flat(q, k, v, **at)
        delta = jnp.sum(g * o, -1, keepdims=True)
        return (o, lse) + tuple(pk._flash_attention_bwd_flat(
            q, k, v, g, lse, delta, **at)) + tuple(
                pk._flash_attention_bwd_onepass_flat(
                    q, k, v, g, lse, delta, **at))

    for got, want in zip(all_three(2), all_three(1)):
        assert jnp.array_equal(got, want)
    traced = str(jax.make_jaxpr(lambda *a: pk._flash_attention_fwd_flat(
        *a, causal=True, block_q=64, block_k=128, interpret=True,
        heads=2))(q, k, v))
    assert "grid=(2, 6)" in traced         # two heads a step, six live pairs


def _block_pairs():
    series = metrics.metrics_snapshot().get(
        "hvd_flash_block_pairs_total", {}).get("series", ())
    return {(row["labels"]["kernel"], row["labels"]["kind"]): row["value"]
            for row in series}


def test_the_visited_pairs_are_counted_by_kernel_and_kind(flash_blocks):
    """``hvd_flash_block_pairs_total``: what a flat head's grid visits, as a
    call's kernels are traced, the pairs no mask cuts and those it does; a
    banded call counts its band's."""
    x = jax.ShapeDtypeStruct((1, 8192, 1, 128), jnp.bfloat16)

    def trace(**kw):
        metrics.reset()
        jax.eval_shape(jax.grad(lambda *a: jnp.sum(
            flash_attention(*a, **kw).astype(jnp.float32)),
            argnums=(0, 1, 2)), x, x, x)
        return _block_pairs()

    # the cells' full calls: the rectangle's grid would read 128
    assert trace() == {("fwd", "interior"): 56, ("fwd", "masked"): 16,
                       ("onepass", "interior"): 56,
                       ("onepass", "masked"): 16}
    assert trace(causal=False) == {("fwd", "interior"): 128,
                                   ("fwd", "masked"): 0,
                                   ("onepass", "interior"): 128,
                                   ("onepass", "masked"): 0}
    # laguna's sliding layers, 512 x 512 under 512 keys: every pair of the
    # band is cut, by the diagonal or by the band's far edge
    assert trace(window=512) == {("fwd", "interior"): 0, ("fwd", "masked"): 31,
                                 ("onepass", "interior"): 0,
                                 ("onepass", "masked"): 31}
    flash_blocks(backward="pallas")
    assert trace() == {(kernel, kind): n for kernel in ("fwd", "dq", "dkv")
                       for kind, n in (("interior", 56), ("masked", 16))}


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
    """The one kernel runs where dq of a grid step's heads fits the VMEM
    budget and the two kernels where one head's does not, and every traced
    call is counted by its form."""
    metrics.reset()
    q, k, v = _qkv(b=1, s=128, h=2, d=32, seed=16)
    banded = str(int(window is not None))
    one = {"hvd_flash_window_dkv"} if window else {"hvd_flash_bwd_onepass"}
    two = ({"hvd_flash_window_dq", "hvd_flash_window_dkv"} if window
           else {"hvd_flash_dq", "hvd_flash_dkv"})
    assert _backward_kernels(q, k, v, window=window) == one
    # dq of one head: 128 x 128 float32 and its float32 output block twice
    monkeypatch.setattr(pk, "_ONEPASS_VMEM_BUDGET", 128 * 128 * 12 - 1)
    assert _backward_kernels(q, k, v, window=window) == two
    monkeypatch.setattr(pk, "_ONEPASS_VMEM_BUDGET", 128 * 128 * 12)
    assert _backward_kernels(q, k, v, window=window) == one
    assert _backward_calls() == {("onepass", banded): 2,
                                 ("two_kernel", banded): 1}


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


def test_kernel_tuner_native_mirror():
    # The C++ KernelTuner (core/src/parameter_manager.cc) picks the choice
    # of the best mean score.
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
