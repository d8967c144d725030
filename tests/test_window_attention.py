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
def test_the_kernels_match_forward_and_every_gradient(flash_blocks, window,
                                                      block_q, block_k):
    flash_blocks(block_q, block_k, "pallas")
    q, k, v, weight = inputs()
    got = out_and_grads(lambda *a: pk.flash_attention(*a, causal=True,
                                                      window=window),
                        q, k, v, weight)
    assert off(got, out_and_grads(lambda *a: plain(*a, window),
                                  q, k, v, weight)) < 1e-5


@pytest.mark.parametrize("heads, kv_heads", [(6, 2), (8, 2)])
def test_grouped_queries_under_a_window(flash_blocks, heads, kv_heads):
    """Three and four query heads a key/value head, as a model's full and
    sliding layers have them."""
    flash_blocks(64, 64, "pallas")
    q, k, v, weight = inputs(seq=128, heads=heads, kv_heads=kv_heads, d=16)
    got = out_and_grads(lambda *a: pk.flash_attention(*a, window=48),
                        q, k, v, weight)
    assert off(got, out_and_grads(lambda *a: plain(*a, 48),
                                  q, k, v, weight)) < 1e-5


@pytest.mark.parametrize("backward", ["pallas_onepass"])
def test_the_other_backward_forms_under_a_window(flash_blocks, backward):
    """The one kernel carries the band and sits where the banded dk/dv
    kernel sat, so nothing runs under the unbanded names or the banded
    dq's."""
    flash_blocks(64, 64, backward)
    q, k, v, weight = inputs()
    fn = lambda *a: pk.flash_attention(*a, window=100)     # noqa: E731
    got = out_and_grads(fn, q, k, v, weight)
    assert off(got, out_and_grads(lambda *a: plain(*a, 100),
                                  q, k, v, weight)) < 1e-5
    traced = str(jax.make_jaxpr(jax.grad(
        lambda *a: (fn(*a) * weight).sum(), argnums=(0, 1, 2)))(q, k, v))
    assert "hvd_flash_bwd_onepass" not in traced
    assert "hvd_flash_window_dq" not in traced
    assert "hvd_flash_window_dkv" in traced


@pytest.mark.parametrize("block_q, block_k", [(64, 128), (128, 64)])
def test_the_two_backward_kernels_under_a_window_at_oblong_blocks(
        flash_blocks, block_q, block_k):
    """The two kernels where the one kernel's dq does not fit, on a banded
    schedule whose key blocks are wider and narrower than its query blocks,
    four flat heads a grid step."""
    flash_blocks(block_q, block_k, "pallas")
    q, k, v, weight = inputs(heads=4, kv_heads=4, d=16)
    fn = lambda *a: pk.flash_attention(*a, window=100)     # noqa: E731
    traced = str(jax.make_jaxpr(jax.grad(
        lambda *a: (fn(*a) * weight).sum(), argnums=(0, 1, 2)))(q, k, v))
    assert "hvd_flash_window_dq" in traced and "hvd_flash_window_dkv" in traced
    assert "grid=(1, " in traced            # four heads a grid step
    assert off(out_and_grads(fn, q, k, v, weight),
               out_and_grads(lambda *a: plain(*a, 100), q, k, v,
                             weight)) < 1e-5


@pytest.mark.parametrize("backward", ["pallas", "pallas_onepass"])
def test_a_window_wider_than_a_block_leaves_pairs_no_mask_cuts(flash_blocks,
                                                               backward):
    """A band of 300 keys over blocks of 64: the pairs between its far edge
    and the diagonal are seen whole, and the forward kernel takes its body
    without the mask there; the numbers are still the plain attention's."""
    flash_blocks(64, 64, backward)
    by_query = pk._block_schedule(512, 64, 64, True, 300)[0]
    assert 0 < int(by_query.masked.sum()) < len(by_query.q)
    q, k, v, weight = inputs(seq=512, heads=2, kv_heads=2, d=16, seed=3)
    fn = lambda *a: pk.flash_attention(*a, window=300)     # noqa: E731
    assert off(out_and_grads(fn, q, k, v, weight),
               out_and_grads(lambda *a: plain(*a, 300), q, k, v,
                             weight)) < 1e-5


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
        flash_blocks, seq, heads, step, window, causal, block_q, block_k):
    flash_blocks(block_q, block_k, "pallas_onepass")
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
def test_the_one_backward_kernel_under_grouped_queries(flash_blocks, heads,
                                                       kv_heads):
    """Six and eight query heads a key/value head, as laguna's full and
    sliding layers have them, with and without the band."""
    flash_blocks(64, 64, "pallas_onepass")
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


def test_no_window_is_the_program_it_was(flash_blocks):
    """``window=None`` (and a window that holds the whole triangle) traces
    to the call of before: the same kernels, names and grids, and the same
    numbers to the bit."""
    flash_blocks(64, 128, "pallas")
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


# (seq, block_q, block_k, window, pairs a head): laguna's plan, oblong and
# narrower blocks at its window, a window of one key, one just wider than a
# query block, one a key short of the sequence.
@pytest.mark.parametrize("seq, block_q, block_k, window, pairs", [
    (8192, 512, 512, 512, 31), (8192, 512, 1024, 512, 23),
    (8192, 256, 256, 512, 93), (8192, 128, 128, 512, 310),
    (256, 64, 64, 1, 4), (256, 64, 128, 65, 5), (256, 64, 64, 255, 10)])
def test_the_banded_grids_visit_the_band_alone(seq, block_q, block_k, window,
                                               pairs):
    """A banded call's schedule visits every block pair the band touches
    once and no other, in both orders, a row's partners together and
    ascending; a pair is masked where the diagonal or the band's far edge
    cuts it: a dead block costs neither a step, a product nor a copy."""
    rows, cols = np.arange(seq)[:, None], np.arange(seq)[None, :]
    seen = (cols <= rows) & (cols > rows - window)
    blocks = seen.reshape(seq // block_q, block_q, seq // block_k, block_k)
    touched = {(j, t): not blocks[j, :, t].all()
               for j in range(seq // block_q) for t in range(seq // block_k)
               if blocks[j, :, t].any()}
    assert len(touched) == pairs
    by_query, by_key = pk._block_schedule(seq, block_q, block_k, True, window)
    for sweep, row_of, in_row in ((by_query, by_query.q, by_query.k),
                                  (by_key, by_key.k, by_key.q)):
        visited = list(zip(sweep.q.tolist(), sweep.k.tolist()))
        assert len(visited) == len(set(visited))
        assert set(visited) == set(touched)
        assert [touched[pair] for pair in visited] == sweep.masked.tolist()
        assert row_of.tolist() == sorted(row_of.tolist())
        starts = np.flatnonzero(np.diff(row_of, prepend=-1))
        ends = np.flatnonzero(np.diff(row_of, append=-1))
        assert sweep.first.tolist() == np.isin(np.arange(len(visited)),
                                               starts).tolist()
        assert sweep.last.tolist() == np.isin(np.arange(len(visited)),
                                              ends).tolist()
        for a, b in zip(starts, ends):
            assert (np.diff(in_row[a:b + 1]) == 1).all()


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
    assert "grid=(%d, 9)" % (4 // heads) in traced     # nine live pairs
    assert [pk._heads_of(n, 512) for n in (128, 96, 6, 7)] == [4, 4, 2, 1]
    assert pk._heads_of(128, None) == 1


# sha256 of a banded call's lowered text (forward and the three gradients,
# interpreted, ``as_text()``) since the banded calls run on the full calls'
# schedule of block pairs (computed again on purpose then: the kernels,
# grids and index maps changed, the numbers did not).  ``(block_q,
# block_k, backward form: "pallas" the two kernels, "pallas_onepass" the
# one, query heads)``; a PR that changes the banded path on purpose
# computes these again.
BANDED_PARENTS = {
    (64, 64, "pallas", 2):
        "042b02d132470ed3709b8a5161276c6a9f20a76aa06603609cb72e8c8bf7fb13",
    (64, 128, "pallas_onepass", 4):
        "3a1322cec06265304c364b3b5496b9a2616d5517d8b030e5715aba45b88082b5",
    (128, 64, "pallas_onepass", 4):
        "8319e0f0de1a78a956c2cdf95db081f5ca019f6fa26653a7ab2ea6b0a8c725f5",
}


@pytest.mark.parametrize("block_q, block_k, backward, heads",
                         sorted(BANDED_PARENTS))
def test_a_banded_call_lowers_to_the_parents_text(flash_blocks, block_q,
                                                  block_k, backward, heads):
    flash_blocks(block_q, block_k, backward)
    q, k, v, weight = inputs(heads=heads)
    text = jax.jit(jax.grad(
        lambda *a: (pk.flash_attention(*a, window=100) * weight).sum(),
        argnums=(0, 1, 2))).lower(q, k, v).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == BANDED_PARENTS[block_q, block_k, backward, heads]


def test_the_banded_calls_carry_their_own_scopes_and_names(flash_blocks):
    flash_blocks(64, 64, "pallas")
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


def test_the_plan_takes_the_window_as_a_third_key():
    assert pk._plan(8192, 128)[:2] == pk._plan(8192, 128, None)[:2] \
        == (512, 1024)
    # no block wider than the band: a 1024-wide key block over 512 keys
    # would do twice the work
    assert pk._plan(8192, 128, 512)[:2] == (512, 512)
    assert pk._plan(8192, 128, 100)[:2] == (64, 64)
    assert pk._plan(8192, 128, 1024)[:2] == (512, 1024)
    assert pk._plan(8192, 128, 16)[:2] == (64, 64)


@pytest.mark.parametrize("causal, window", [(False, 64), (True, 0),
                                            (True, -3)])
def test_a_window_refuses_what_it_cannot_mean(causal, window):
    q, k, v, _ = inputs(seq=64)
    with pytest.raises(ValueError, match="window"):
        pk.flash_attention(q, k, v, causal=causal, window=window)


def test_bfloat16_under_a_window_stays_near_float32(flash_blocks):
    flash_blocks(64, 64, "pallas")
    q, k, v, weight = inputs()
    want = plain(q, k, v, 100)
    got = pk.flash_attention(*(x.astype(jnp.bfloat16) for x in (q, k, v)),
                             window=100)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < 0.05
    assert np.isfinite(np.asarray(got, np.float32)).all()
