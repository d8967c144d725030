"""Sliding-window attention: the XLA form (``parallel/ring_attention.py:
local_attention``) and the interpreted flash kernels
(``ops/pallas_kernels.py: flash_attention(..., window=w)``) against a plain
masked softmax, forward and every gradient; which blocks the banded grids
visit; and that ``window=None`` is the program it was."""

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common import scopes
from horovod_tpu.ops import pallas_kernels as pk
from horovod_tpu.parallel.ring_attention import local_attention

SEQ = 256


def plain(q, k, v, window):
    """softmax(q k^T / sqrt(d)) v over the keys ``i - window < j <= i``,
    nothing blocked, every query head on key/value head ``h // group``."""
    s, d = q.shape[1], q.shape[-1]
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window is not None:
        seen &= j > i - window
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def inputs(seq=SEQ, heads=2, kv_heads=1, d=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (1, seq, heads, d)),
            jax.random.normal(ks[1], (1, seq, kv_heads, d)),
            jax.random.normal(ks[2], (1, seq, kv_heads, d)),
            jax.random.normal(ks[3], (1, seq, heads, d)))


def out_and_grads(attention, q, k, v, weight):
    return jax.value_and_grad(
        lambda *qkv: (attention(*qkv) * weight).sum(), argnums=(0, 1, 2))(
            q, k, v)


def off(got, want):
    """Largest difference of two trees' leaves, each against its leaf's
    largest entry (or 1: a window of one key has gradients of zero)."""
    return max(float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1.0))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


@pytest.fixture
def blocks(monkeypatch):
    def pin(block_q, block_k, backward="pallas"):
        monkeypatch.setenv("HVD_TPU_FLASH_BLOCK_Q", str(block_q))
        monkeypatch.setenv("HVD_TPU_FLASH_BLOCK_K", str(block_k))
        monkeypatch.setenv("HVD_TPU_FLASH_BWD", backward)
    return pin


@pytest.mark.parametrize("window", [1, 17, 64, 100, SEQ, 400])
def test_the_xla_form_matches_a_plain_masked_softmax(window):
    q, k, v, weight = inputs()
    got = out_and_grads(lambda *a: local_attention(*a, causal=True,
                                                   window=window),
                        q, k, v, weight)
    assert off(got, out_and_grads(lambda *a: plain(*a, window),
                                  q, k, v, weight)) < 2e-6


# Windows smaller than a block, a block, between blocks, several blocks,
# the whole sequence and more; square and oblong blocks; grouped queries.
@pytest.mark.parametrize("window, block_q, block_k", [
    (1, 64, 64), (32, 64, 64), (64, 64, 64), (65, 64, 128), (64, 128, 64),
    (100, 64, 64), (96, 128, 128), (200, 64, 64), (SEQ - 1, 64, 64),
    (SEQ, 64, 64), (400, 64, 64)])
def test_the_kernels_match_forward_and_every_gradient(blocks, window,
                                                      block_q, block_k):
    blocks(block_q, block_k)
    q, k, v, weight = inputs()
    got = out_and_grads(lambda *a: pk.flash_attention(*a, causal=True,
                                                      window=window),
                        q, k, v, weight)
    assert off(got, out_and_grads(lambda *a: plain(*a, window),
                                  q, k, v, weight)) < 1e-5


@pytest.mark.parametrize("heads, kv_heads", [(6, 2), (8, 2)])
def test_grouped_queries_under_a_window(blocks, heads, kv_heads):
    """Three and four query heads a key/value head, as a model's full and
    sliding layers have them."""
    blocks(64, 64)
    q, k, v, weight = inputs(seq=128, heads=heads, kv_heads=kv_heads, d=16)
    got = out_and_grads(lambda *a: pk.flash_attention(*a, window=48),
                        q, k, v, weight)
    assert off(got, out_and_grads(lambda *a: plain(*a, 48),
                                  q, k, v, weight)) < 1e-5


@pytest.mark.parametrize("backward", ["pallas_onepass", "chunked"])
def test_the_other_backward_forms_under_a_window(blocks, backward):
    """The chunked form carries the mask; the one kernel carries the band
    and sits where the banded dk/dv kernel sat, so nothing runs under the
    unbanded names or the banded dq's."""
    blocks(64, 64, backward)
    q, k, v, weight = inputs()
    fn = lambda *a: pk.flash_attention(*a, window=100)     # noqa: E731
    got = out_and_grads(fn, q, k, v, weight)
    assert off(got, out_and_grads(lambda *a: plain(*a, 100),
                                  q, k, v, weight)) < 1e-5
    traced = str(jax.make_jaxpr(jax.grad(
        lambda *a: (fn(*a) * weight).sum(), argnums=(0, 1, 2)))(q, k, v))
    assert "hvd_flash_bwd_onepass" not in traced
    assert "hvd_flash_window_dq" not in traced
    assert ("hvd_flash_window_dkv" in traced) == (backward != "chunked")


# The one backward kernel (dq of a whole head in VMEM): not causal, causal,
# windows narrower and wider than a block and of laguna's 512; grids with
# nq != nk; one, two and four heads a grid step (``step``: what the plan
# makes of three, two or six, and four or eight flat heads under a window).
@pytest.mark.parametrize(
    "seq, heads, step, window, causal, block_q, block_k", [
        (256, 2, 1, None, False, 64, 128), (256, 2, 1, None, True, 128, 64),
        (256, 2, 1, None, True, 64, 64), (256, 2, 2, 48, True, 64, 64),
        (256, 2, 2, 48, True, 128, 64), (256, 3, 1, 48, True, 64, 64),
        (256, 4, 4, 100, True, 64, 128), (256, 6, 2, 100, True, 64, 64),
        (256, 8, 4, 100, True, 128, 128), (1024, 2, 2, 512, True, 256, 512),
        (1024, 8, 4, 512, True, 512, 256)])
def test_the_one_backward_kernel_matches_every_gradient(
        blocks, seq, heads, step, window, causal, block_q, block_k):
    blocks(block_q, block_k, "pallas_onepass")
    q, k, v, weight = inputs(seq=seq, heads=heads, kv_heads=heads, d=16)
    assert pk._backward_form(heads, seq, 128, 4, window) == ("onepass", step)
    traced = str(jax.make_jaxpr(jax.grad(lambda *a: (pk.flash_attention(
        *a, causal=causal, window=window) * weight).sum(),
        argnums=(0, 1, 2)))(q, k, v))
    assert ("hvd_flash_bwd_onepass" in traced) == (window is None)
    assert ("hvd_flash_window_dkv" in traced) == (window is not None)
    assert "hvd_flash_dq" not in traced and "_window_dq" not in traced
    got = out_and_grads(lambda *a: pk.flash_attention(
        *a, causal=causal, window=window), q, k, v, weight)
    want = out_and_grads(
        (lambda *a: plain(*a, window)) if causal
        else (lambda *a: local_attention(*a, causal=False)), q, k, v, weight)
    assert off(got, want) < 1e-5


@pytest.mark.parametrize("heads, kv_heads", [(6, 1), (8, 1)])
def test_the_one_backward_kernel_under_grouped_queries(blocks, heads,
                                                       kv_heads):
    """Six and eight query heads a key/value head, as laguna's full and
    sliding layers have them, with and without the band."""
    blocks(64, 64, "pallas_onepass")
    q, k, v, weight = inputs(seq=128, heads=heads, kv_heads=kv_heads, d=16)
    for window in (None, 48):
        got = out_and_grads(lambda *a: pk.flash_attention(*a, window=window),
                            q, k, v, weight)
        assert off(got, out_and_grads(lambda *a: plain(*a, window),
                                      q, k, v, weight)) < 1e-5


def test_a_ragged_sequence_takes_the_xla_form_with_its_window():
    q, k, v, weight = inputs(seq=100)
    got = out_and_grads(lambda *a: pk.flash_attention(*a, window=30),
                        q, k, v, weight)
    assert off(got, out_and_grads(lambda *a: plain(*a, 30),
                                  q, k, v, weight)) < 2e-6


def test_no_window_is_the_program_it_was(blocks):
    """``window=None`` (and a window that holds the whole triangle) traces
    to the call of before: the same kernels, names and grids, and the same
    numbers to the bit."""
    blocks(64, 128)
    q, k, v, weight = inputs()

    def traced(**window):
        return str(jax.make_jaxpr(jax.grad(
            lambda *a: (pk.flash_attention(*a, causal=True, **window)
                        * weight).sum(), argnums=(0, 1, 2)))(q, k, v))

    before = traced()
    assert traced(window=None) == traced(window=SEQ) == before
    assert "hvd_flash_window" not in before and "hvd_flash_fwd" in before
    # the schedule's six live pairs of the eight a rectangle of 4 x 2 has
    assert "grid=(2, 6)" in before
    a = out_and_grads(lambda *x: pk.flash_attention(*x), q, k, v, weight)
    b = out_and_grads(lambda *x: pk.flash_attention(*x, window=SEQ + 7),
                      q, k, v, weight)
    assert off(a, b) == 0.0


@pytest.mark.parametrize("seq, block_q, block_k, window, steps", [
    (8192, 512, 512, 512, (2, 2)), (8192, 512, 1024, 512, (2, 3)),
    (8192, 256, 256, 512, (3, 3)), (8192, 128, 128, 512, (5, 5)),
    (256, 64, 64, 1, (1, 1)), (256, 64, 128, 65, (2, 3)),
    (256, 64, 64, 255, (4, 4))])
def test_the_banded_grids_visit_the_band_alone(seq, block_q, block_k, window,
                                               steps):
    """The inner axes are as long as the widest block's band, every block
    the band touches is visited once, and no other: a dead block costs
    neither a product nor a copy (a step past the band holds the index of
    the step before, so the pipeline fetches nothing)."""
    assert pk._band_steps(seq, block_q, block_k, window) == steps
    nq, nk = seq // block_q, seq // block_k
    touched = {(j, t) for j in range(nq) for t in range(nk)
               if t * block_k <= j * block_q + block_q - 1
               and t * block_k + block_k - 1 > j * block_q - window}
    index = pk._k_spec(block_q, block_k, 128, window).index_map
    by_query = [[int(index(0, j, u)[1]) for u in range(steps[0])]
                for j in range(nq)]
    assert {(j, t) for j, row in enumerate(by_query) for t in row} == touched
    for row in by_query:        # ascending, the last one held
        assert row == sorted(row) and len(set(row)) >= 1
    by_key = {(j, t) for t in range(nk) for j in range(nq)
              if int(pk._band_first_q(t, block_q, block_k)) <= j
              <= int(pk._band_last_q(t, block_q, block_k, window, nq))}
    assert by_key == touched
    assert max(sum(1 for j, t in touched if t == key)
               for key in range(nk)) == steps[1]


@pytest.mark.parametrize("heads", [2, 4])
def test_several_heads_a_grid_step_are_the_heads_one_by_one(heads):
    """A banded call's kernels take blocks of several flat heads and walk
    them in the step: the same numbers to the bit, a grid that many times
    shorter.  A full call keeps one head a step."""
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q, k, v, g = (jax.random.normal(key, (4, SEQ, 32)) for key in ks)

    def both(heads):
        at = dict(causal=True, block_q=64, block_k=64, interpret=True,
                  window=100, heads=heads)
        o, lse = pk._flash_attention_fwd_flat(q, k, v, **at)
        delta = jnp.sum(g * o, -1, keepdims=True)
        return (o, lse) + tuple(pk._flash_attention_bwd_flat(
            q, k, v, g, lse, delta, **at))

    for got, want in zip(both(heads), both(1)):
        assert jnp.array_equal(got, want)
    # the one backward kernel: the two kernels' numbers to the bit
    o, lse, *two = both(heads)
    one = pk._flash_attention_bwd_onepass_flat(
        q, k, v, g, lse, jnp.sum(g * o, -1, keepdims=True), causal=True,
        block_q=64, block_k=64, interpret=True, window=100, heads=heads)
    for got, want in zip(one, two):
        assert jnp.array_equal(got, want)
    traced = str(jax.make_jaxpr(lambda *a: pk._flash_attention_fwd_flat(
        *a, causal=True, block_q=64, block_k=64, interpret=True, window=100,
        heads=heads))(q, k, v))
    assert "grid=(%d, 4, 3)" % (4 // heads) in traced
    assert [pk._heads_of(n, 512) for n in (128, 96, 6, 7)] == [4, 4, 2, 1]
    assert pk._heads_of(128, None) == 1


# sha256 of a banded call's lowered text (forward and the three gradients,
# interpreted, ``as_text()``) at PR 35 (commit 836838e), before a full call's
# grid became a schedule of block pairs: the banded kernels, their grids and
# their index maps are the parent's to the letter.  ``(block_q, block_k,
# HVD_TPU_FLASH_BWD, query heads)``; a PR that changes the banded path on
# purpose computes these again.
BANDED_PARENTS = {
    (64, 64, "pallas", 2):
        "201696d4cb0f5dff5f6a626610b6622715c3f82aa2c3ac588256a7d75bed3765",
    (64, 128, "pallas_onepass", 4):
        "32404b745495adfd9cb91c083ab0df63f89ea195e8e8cfecd349f15f0b648f68",
    (128, 64, "pallas_onepass", 4):
        "1c33044caf9d50a84d135904aea74814c0b81168874c8982627c035783077f5a",
}


@pytest.mark.parametrize("block_q, block_k, backward, heads",
                         sorted(BANDED_PARENTS))
def test_a_banded_call_lowers_to_the_parents_text(blocks, block_q, block_k,
                                                  backward, heads):
    blocks(block_q, block_k, backward)
    q, k, v, weight = inputs(heads=heads)
    text = jax.jit(jax.grad(
        lambda *a: (pk.flash_attention(*a, window=100) * weight).sum(),
        argnums=(0, 1, 2))).lower(q, k, v).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == BANDED_PARENTS[block_q, block_k, backward, heads]


def test_the_banded_calls_carry_their_own_scopes_and_names(blocks):
    blocks(64, 64)
    q, k, v, weight = inputs()

    def names(**window):
        traced = str(jax.make_jaxpr(jax.grad(
            lambda *a: (pk.flash_attention(*a, **window) * weight).sum(),
            argnums=(0, 1, 2)))(q, k, v))
        return {scope for scope in (
            scopes.FLASH_FWD, scopes.FLASH_DQ, scopes.FLASH_DKV,
            scopes.FLASH_WINDOW_FWD, scopes.FLASH_WINDOW_DQ,
            scopes.FLASH_WINDOW_DKV) if scopes.kernel_name(scope) in traced}

    assert names(window=64) == {scopes.FLASH_WINDOW_FWD,
                                scopes.FLASH_WINDOW_DQ,
                                scopes.FLASH_WINDOW_DKV}
    assert names() == {scopes.FLASH_FWD, scopes.FLASH_DQ, scopes.FLASH_DKV}
    lowered = jax.jit(jax.grad(
        lambda *a: (pk.flash_attention(*a, window=64) * weight).sum(),
        argnums=(0, 1, 2))).lower(q, k, v).as_text(debug_info=True)
    for scope in (scopes.FLASH_WINDOW_FWD, scopes.FLASH_WINDOW_DQ,
                  scopes.FLASH_WINDOW_DKV):
        assert scope in lowered


def test_the_plan_takes_the_window_as_a_third_key(monkeypatch):
    for name in ("HVD_TPU_FLASH_BLOCK_Q", "HVD_TPU_FLASH_BLOCK_K"):
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setattr(pk, "_TUNED_BLOCKS", {})
    assert pk._plan(8192, 128)[:2] == pk._plan(8192, 128, None)[:2] \
        == (512, 1024)
    # no block wider than the band: a 1024-wide key block over 512 keys
    # would do twice the work
    assert pk._plan(8192, 128, 512)[:2] == (512, 512)
    assert pk._plan(8192, 128, 100)[:2] == (64, 64)
    pk._TUNED_BLOCKS[(8192, 128)] = (256, 2048)
    pk._TUNED_BLOCKS[(8192, 128, 512)] = (256, 256)
    assert pk._plan(8192, 128)[:2] == (256, 2048)
    assert pk._plan(8192, 128, 512)[:2] == (256, 256)
    assert pk._plan(8192, 128, 1024)[:2] == (512, 1024)
    saved = pk.export_tuned_blocks()
    assert saved == {"8192x128": [256, 2048], "8192x128x512": [256, 256]}
    pk._TUNED_BLOCKS.clear()
    pk.seed_tuned_blocks(dict(saved, **{"8192x128x512x1": [64, 64]}))
    assert pk.export_tuned_blocks() == saved


@pytest.mark.parametrize("causal, window", [(False, 64), (True, 0),
                                            (True, -3)])
def test_a_window_refuses_what_it_cannot_mean(causal, window):
    q, k, v, _ = inputs(seq=64)
    with pytest.raises(ValueError, match="window"):
        pk.flash_attention(q, k, v, causal=causal, window=window)


def test_bfloat16_under_a_window_stays_near_float32(blocks):
    blocks(64, 64)
    q, k, v, weight = inputs()
    want = plain(q, k, v, 100)
    got = pk.flash_attention(*(x.astype(jnp.bfloat16) for x in (q, k, v)),
                             window=100)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < 0.05
    assert np.isfinite(np.asarray(got, np.float32)).all()
