"""Multi-head latent attention: the flash kernels at a query/key head size
that differs from the value's (interpreted) against the plain attention in
values and every gradient; the same kernels with the key's shared part as an
operand of its own against the key joined in HBM; the block of ``models/transformer.py`` against
the benchmark's plain reference (``yardstick/builders/deepseek_v3.py``);
the rotation's layout; the scale; the shares of heads and of experts; the
six-layer tiny decoder against the reference; scopes, counter, refusals."""

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.common import metrics, scopes
from horovod_tpu.models import transformer as T
from horovod_tpu.ops import pallas_kernels as pk
from horovod_tpu.parallel.moe import ExpertShare
from yardstick import manifest as mf
from yardstick.builders import deepseek_v3 as builder

CELL = "kanana-2-30b-a3b.dp1-pt8k"
SEQ = 256


def inputs(d_qk, d_v, seq=SEQ, batch=1, heads=2, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(k, (batch, seq, heads, d)).astype(dtype)
                 for k, d in zip(ks, (d_qk, d_qk, d_v, d_v)))


def out_and_grads(attention, q, k, v, weight):
    f32 = jnp.float32
    return jax.value_and_grad(
        lambda *qkv: (attention(*qkv).astype(f32) * weight.astype(f32)).sum(),
        argnums=(0, 1, 2))(q, k, v)


def off(got, want):
    """Largest difference of two trees' leaves, each against its leaf's
    largest entry."""
    f32 = jnp.float32
    return max(float(jnp.abs(a.astype(f32) - b.astype(f32)).max()
                     / jnp.abs(b.astype(f32)).max())
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


# -- the kernels at two head sizes ------------------------------------------

# The one backward kernel (what a call takes unasked) and the two kernels;
# square and oblong blocks; the cell's sizes and a pair under a lane tile.
@pytest.mark.parametrize("d_qk, d_v, block_q, block_k, backward", [
    (48, 32, 64, 64, None), (48, 32, 64, 128, None),
    (192, 128, 64, 64, None), (192, 128, 128, 64, None),
    (48, 32, 64, 128, "pallas"), (192, 128, 64, 64, "pallas"),
    (64, 128, 64, 64, None)])
def test_the_kernels_take_a_value_size_of_their_own(flash_blocks, d_qk, d_v,
                                                    block_q, block_k,
                                                    backward):
    flash_blocks(block_q, block_k, backward)
    q, k, v, weight = inputs(d_qk, d_v)
    got = out_and_grads(lambda *a: pk.flash_attention(*a, causal=True),
                        q, k, v, weight)
    want = out_and_grads(lambda *a: pk._reference_attention(*a, True),
                         q, k, v, weight)
    assert got[0].shape == () and [g.shape for g in got[1]] \
        == [q.shape, k.shape, v.shape]
    assert off(got, want) < 1e-5


def test_the_kernels_in_bfloat16_and_without_a_mask(flash_blocks):
    flash_blocks(64, 128)
    q, k, v, weight = inputs(192, 128, dtype=jnp.bfloat16, heads=3)
    for causal in (True, False):
        got = out_and_grads(lambda *a: pk.flash_attention(*a, causal=causal),
                            q, k, v, weight)
        want = out_and_grads(
            lambda *a: pk._reference_attention(
                *(x.astype(jnp.float32) for x in a), causal),
            q, k, v, weight)
        assert pk.flash_attention(q, k, v, causal=causal).shape \
            == (1, SEQ, 3, 128)
        assert off(got, want) < 3e-2, causal


def test_the_scale_is_of_the_query_key_size(flash_blocks):
    flash_blocks(64, 64)
    q, k, v, _ = inputs(48, 32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    seen = jnp.tril(jnp.ones((SEQ, SEQ), bool))

    def plain(size):
        probs = jax.nn.softmax(
            jnp.where(seen, scores / math.sqrt(size), -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    got = pk.flash_attention(q, k, v, causal=True)
    assert off(got, plain(48)) < 1e-5
    assert off(got, plain(32)) > 1e-2


def test_a_call_at_two_sizes_names_them_and_keeps_its_schedule(flash_blocks):
    flash_blocks(64, 64)
    q, k, v, weight = inputs(192, 128)
    before = metrics.snapshot().get("hvd_flash_block_pairs_total",
                                    {"series": []})["series"]
    traced = str(jax.make_jaxpr(jax.grad(
        lambda *a: (pk.flash_attention(*a) * weight).sum(),
        argnums=(0, 1, 2)))(q, k, v))
    assert "hvd_flash_fwd_192x128" in traced
    assert "hvd_flash_bwd_onepass_192x128" in traced
    after = metrics.snapshot()["hvd_flash_block_pairs_total"]["series"]

    def count(series, kernel):
        return sum(row["value"] for row in series
                   if row["labels"]["kernel"] == kernel)

    # 4 blocks of 64: 10 pairs meet the triangle, in both sweeps
    assert count(after, "fwd") - count(before, "fwd") == 10
    assert count(after, "onepass") - count(before, "onepass") == 10
    # equal sizes keep the plain names
    q, k, v, weight = inputs(32, 32)
    traced = str(jax.make_jaxpr(lambda *a: pk.flash_attention(*a))(q, k, v))
    assert "hvd_flash_fwd" in traced and "hvd_flash_fwd_" not in traced


@pytest.mark.parametrize("backward", ["pallas_onepass", "pallas"])
def test_a_window_takes_two_sizes(flash_blocks, backward):
    """The banded grids share the full calls' plumbing: the one backward
    kernel and the two, under their own names with the sizes."""
    flash_blocks(64, 64, backward)
    q, k, v, weight = inputs(48, 32, heads=4)
    got = out_and_grads(lambda *a: pk.flash_attention(*a, window=100),
                        q, k, v, weight)
    want = out_and_grads(lambda *a: pk._reference_attention(*a, True, 100),
                         q, k, v, weight)
    assert off(got, want) < 1e-5
    traced = str(jax.make_jaxpr(
        lambda *a: pk.flash_attention(*a, window=100))(q, k, v))
    assert "hvd_flash_window_fwd_48x128" in traced


def test_the_plan_of_two_sizes_pads_nothing_and_keeps_the_pins():
    assert pk._plan(8192, 128)[:3] == (512, 1024, 128)
    assert pk._plan(8192, 128, None, 128)[:3] == (512, 1024, 128)
    block_q, block_k, d_pad, scale = pk._plan(8192, 192, None, 128)
    assert (block_q, block_k, d_pad) == (1024, 1024, 192)
    assert math.isclose(scale, 192 ** -0.5)
    # the measured blocks are of that one shape: another takes the chains
    assert pk._plan(4096, 192, None, 128)[:3] == (512, 1024, 192)
    assert pk._plan(8192, 96, None, 64)[:3] == (512, 1024, 96)
    assert pk._plan(8192, 192, 512, 128)[:3] == (512, 512, 192)
    assert pk._plan(8192, 192, None, 128, 4)[:3] == (512, 1024, 192)
    # dq of a head of 192 lies in 256 lanes: 16 MiB of the 32 it may take
    assert pk._onepass_vmem_bytes(1, 8192, 192, 2) == 16 << 20
    assert pk._backward_form(64, 8192, 192, 2, None) == ("onepass", 1)


# -- the key's shared part as an operand of its own ---------------------------

def shared_inputs(d_k, d_s, d_v, dtype, batch=2, heads=3, seq=SEQ):
    """q at ``d_k + d_s``, every head's own key at ``d_k``, the part the
    heads of a batch entry share ``[B, S, d_s]``, v and the output's weight."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    return tuple(
        jax.random.normal(key, shape).astype(dtype) for key, shape in zip(ks, (
            (batch, seq, heads, d_k + d_s), (batch, seq, heads, d_k),
            (batch, seq, d_s), (batch, seq, heads, d_v),
            (batch, seq, heads, d_v))))


def shared_out_and_grads(attention, q, k, shared, v, weight):
    f32 = jnp.float32
    return jax.jit(jax.value_and_grad(
        lambda *a: (attention(*a).astype(f32) * weight.astype(f32)).sum(),
        argnums=(0, 1, 2, 3)))(q, k, shared, v)


# Square and oblong blocks (a key block wider and narrower than the query
# block), the one backward kernel and the two, both precisions; the tiny
# decoder's sizes (all under a lane tile) and the cell's 128 + 64 over 128.
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("backward", ["pallas_onepass", "pallas"])
@pytest.mark.parametrize("d_k, d_s, d_v, block_q, block_k", [
    (16, 8, 12, 64, 64), (16, 8, 12, 64, 128), (128, 64, 128, 128, 64)])
def test_a_shared_key_part_equals_the_key_joined_in_hbm(flash_blocks, d_k, d_s, d_v,
                                                        block_q, block_k,
                                                        backward, dtype):
    flash_blocks(block_q, block_k, backward)
    q, k, shared, v, weight = shared_inputs(d_k, d_s, d_v, dtype)
    got = shared_out_and_grads(
        lambda q, k, shared, v: pk.flash_attention(q, k, v, k_shared=shared),
        q, k, shared, v, weight)
    want = shared_out_and_grads(
        lambda q, k, shared, v: pk.flash_attention(
            q, pk.whole_key(k, shared), v), q, k, shared, v, weight)
    assert [g.shape for g in got[1]] \
        == [q.shape, k.shape, shared.shape, v.shape]
    (dq, dk, dshared, dv), (dq_w, dk_w, dshared_w, dv_w) = got[1], want[1]
    # the same products on the same numbers: to the bit
    for a, b in ((got[0], want[0]), (dq, dq_w), (dk, dk_w), (dv, dv_w)):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    # the three heads' shares are added up in float32 here and by the
    # repeat's transpose, in the operands' precision, there
    assert off(dshared, dshared_w) < (1e-6 if dtype == jnp.float32 else 8e-3)


def test_a_shared_key_part_against_the_plain_attention(flash_blocks):
    """Not only equal to the joined call: right (grouped key/value heads,
    each repeated for its group, by the one backward kernel and the two)."""
    q, k, shared, v, weight = shared_inputs(16, 8, 12, jnp.float32, heads=4)
    k, v = k[:, :, :2], v[:, :, :2]
    want = shared_out_and_grads(
        lambda q, k, shared, v: pk._reference_attention(
            q, pk.whole_key(jnp.repeat(k, 2, 2), shared),
            jnp.repeat(v, 2, 2), True), q, k, shared, v, weight)
    for backward in ("pallas_onepass", "pallas"):
        flash_blocks(64, 128, backward)
        got = shared_out_and_grads(
            lambda q, k, shared, v: pk.flash_attention(q, k, v,
                                                       k_shared=shared),
            q, k, shared, v, weight)
        assert off(got, want) < 1e-5, backward


def test_a_shared_key_part_under_a_window_is_refused_by_name(flash_blocks):
    flash_blocks(64, 64)
    q, k, shared, v, _ = shared_inputs(16, 8, 12, jnp.float32)
    with pytest.raises(ValueError, match="window of 100 keys.*shared part"):
        pk.flash_attention(q, k, v, window=100, k_shared=shared)
    with pytest.raises(ValueError, match=r"not \[B, S, d_qk - d_k\]"):
        pk.flash_attention(q, k, v, k_shared=shared[..., :4])
    # a window as long as the sequence is no band: the call is a full one
    assert pk.flash_attention(q, k, v, window=SEQ, k_shared=shared).shape \
        == v.shape


def shared_key_calls():
    rows = metrics.snapshot().get("hvd_flash_shared_key_calls_total",
                                  {"series": []})["series"]
    return {r["labels"]["kernel"]: r["value"] for r in rows}


# sha256 of a full call's lowered text (forward and the three gradients,
# interpreted, ``as_text()``) at PR 36 (commit d259f55), before the kernels
# took a value size of their own: where ``d_qk == d_v`` the call is the
# parent's to the letter.  ``(head size, block_q, block_k, backward form:
# "pallas" the two kernels, "pallas_onepass" the one)``; a PR that changes
# the full calls on purpose computes these again.
FULL_PARENTS = {
    (32, 64, 64, "pallas"):
        "f85757f723c81aca92e78e427479eaf395c54bee57cba40bc07e6768c6893f54",
    (128, 64, 128, "pallas_onepass"):
        "c73c119c9f589f1dc240673ab75676953036275bcf921757cb5f1de6360a433a",
    (64, 128, 64, "pallas_onepass"):
        "c32343c42ced8dd56aa06e85486d73a753eafc62976a4bccb538f63cbf27f4b8",
}


@pytest.mark.parametrize("d, block_q, block_k, backward",
                         sorted(FULL_PARENTS))
def test_a_call_at_one_size_lowers_to_the_parents_text(flash_blocks, d, block_q,
                                                       block_k, backward):
    flash_blocks(block_q, block_k, backward)
    q, k, v, weight = inputs(d, d)
    text = jax.jit(jax.grad(
        lambda *a: (pk.flash_attention(*a) * weight).sum(),
        argnums=(0, 1, 2))).lower(q, k, v).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == FULL_PARENTS[d, block_q, block_k, backward]


# -- the block ----------------------------------------------------------------

KIND = T.LatentAttention(n_heads=4, kv_rank=32, nope=16, rope_dim=8,
                         v_dim=12, rope=T.Rope(theta=1e6))
# The reference reads a configuration's keys.
KEYS = {"num_attention_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 12, "rope_theta": 1e6,
        "rms_norm_eps": 1e-6}


def block_config(**over):
    return T.TransformerConfig(
        vocab_size=128, d_model=64, n_layers=1, n_heads=4, n_kv_heads=4,
        d_ff=96, norm_eps=1e-6, dtype="float32",
        layer_pattern=((KIND, "dense"),), **over)


def block_params(seed=0):
    cfg = block_config()
    lp = jax.tree.map(lambda w: w[0], T._init_layers(
        jax.random.PRNGKey(seed), cfg, KIND, "dense", 1))
    # a norm scale that is not 1, so that leaving it out shows
    lp["kv_norm"] = 1.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(7),
                                                  lp["kv_norm"].shape)
    return cfg, lp


def mesh_of(shape):
    return Mesh(np.asarray(jax.devices()[:int(np.prod(shape))]).reshape(shape),
                ("dp", "sp", "tp"))


def run_block(cfg, lp, x, mesh=None, specs=None):
    """The block alone inside a shard_map, as the decoder calls it."""
    mesh = mesh or mesh_of((1, 1, 1))
    specs = specs or jax.tree.map(lambda _: P(), lp)
    tables = {KIND: T.rope_tables(jnp.arange(x.shape[1]), KIND.rope_dim,
                                  KIND.rope, cfg.act_dtype)}
    return jax.jit(jax.shard_map(
        lambda lp, x: T._latent_attention_block(x, lp, cfg, KIND, tables),
        mesh=mesh, in_specs=(specs, P()), out_specs=P(),
        check_vma=True))(lp, x)


def reference_block(lp, x, wrong=()):
    return jax.jit(jax.vmap(lambda h: builder.reference_latent_attention(
        h, lp, KEYS, wrong=wrong)))(x)


def test_the_block_matches_the_reference_in_values_and_gradients():
    cfg, lp = block_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64))
    weight = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(
            lambda lp, x: (run_block(cfg, lp, x) * weight).sum(),
            argnums=(0, 1)))(lp, x)
        want = jax.jit(jax.value_and_grad(
            lambda lp, x: (reference_block(lp, x) * weight).sum(),
            argnums=(0, 1)))(lp, x)
    mixer = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")
    assert off(got[0], want[0]) < 1e-5
    assert off([got[1][0][n] for n in mixer] + [got[1][1]],
               [want[1][0][n] for n in mixer] + [want[1][1]]) < 2e-5
    # the one rotary key's gradient is every head's, summed: the rotary
    # columns of W_kv_a get it from all four heads
    rope_columns = got[1][0]["wkv_a"][:, KIND.kv_rank:]
    assert float(jnp.abs(rope_columns).max()) > 0


@pytest.mark.parametrize("wrong", ["no_latent_norm", "scale_by_value_size",
                                   "no_rope", "rope_per_head_key",
                                   "rope_layout"])
def test_the_reference_tells_each_wrong_part_of_the_block(wrong):
    cfg, lp = block_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64))
    with jax.default_matmul_precision("highest"):
        got = run_block(cfg, lp, x)
        assert off(got, reference_block(lp, x)) < 1e-5
        assert off(got, reference_block(lp, x, (wrong,))) > 1e-2


def test_interleaved_pairs_equal_halves_under_the_column_permutation():
    """``_rope`` turns halves; the source turns neighbouring pairs.  A
    vector whose published layout is ``y`` lies in the program as
    ``y[inverse]``; turned there and read back through the permutation it
    is the published turn of ``y``."""
    rope_dim, seq = 8, 16
    columns = builder.published_rope_columns(rope_dim)
    assert sorted(columns) == list(range(rope_dim))
    assert list(columns[:4]) == [0, 4, 1, 5]
    y = jax.random.normal(jax.random.PRNGKey(0), (1, seq, 3, rope_dim))
    i = np.arange(rope_dim // 2)
    angle = np.arange(seq)[:, None] * 1e6 ** (-2.0 * i / rope_dim)
    cos, sin = np.cos(angle)[None, :, None], np.sin(angle)[None, :, None]
    a, b = y[..., 0::2], y[..., 1::2]
    published = jnp.stack([a * cos - b * sin, a * sin + b * cos],
                          -1).reshape(y.shape)
    tables = T.rope_tables(jnp.arange(seq), rope_dim, T.Rope(theta=1e6),
                           jnp.float32)
    program = T._rope(*tables, y[..., np.argsort(columns)])
    assert off(program[..., columns], published) < 1e-6
    # the halves' turn of the published layout as it lies is another
    assert off(T._rope(*tables, y), published) > 1e-2


def test_two_tp_shares_of_the_heads_add_up_to_the_uncut_block():
    cfg, lp = block_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64))
    specs = {name: T._layer_specs(cfg, KIND, "dense")[name]
             for name in lp}
    specs = jax.tree.map(lambda s: P(*s[1:]), specs)     # one layer
    with jax.default_matmul_precision("highest"):
        whole = run_block(cfg, lp, x)
        shared = run_block(cfg, lp, x, mesh_of((1, 1, 2)), specs)
    assert specs["wkv_a"] == P(None, None) and specs["kv_norm"] == P(None)
    assert specs["wq"] == specs["wkv_b"] == P(None, "tp")
    assert off(np.asarray(shared), np.asarray(whole)) < 1e-5


# -- the share of the experts --------------------------------------------------

def test_eight_shares_of_sixteen_experts_add_up_to_the_uncut_layer():
    """The guide's share test at this configuration's form: 128 experts, 6
    a token, 16 held by each of 8 chips, the shared experts on every chip
    and counted once."""
    from horovod_tpu.parallel.moe import (expert_share_ffn,
                                          init_expert_share_params)
    d, width, shared, tokens = 32, 16, 24, 96
    whole = ExpertShare(128, 0, 128, 6, d, width, shared,
                        routed_scaling=2.448, block_rows=8)
    lp = jax.tree.map(lambda w: w[0], init_expert_share_params(
        jax.random.PRNGKey(0), whole, 1))
    lp["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                                lp["router_bias"].shape)
    x = jax.random.normal(jax.random.PRNGKey(2), (tokens, d))
    config = {"held": {"first_expert": 0}, "num_experts_per_tok": 6,
              "routed_scaling_factor": 2.448}
    with jax.default_matmul_precision("highest"):
        want, loads, _ = jax.jit(
            lambda x, lp: builder.reference_expert_layer(x, lp, config))(
                x, lp)
        shared_only = jax.nn.silu(x @ lp["ws1"]) * (x @ lp["ws3"]) \
            @ lp["ws2"]
        total = shared_only
        for chip in range(8):
            share = ExpertShare(128, 16 * chip, 16, 6, d, width, shared,
                                routed_scaling=2.448, block_rows=8)
            held = dict(lp, **{n: lp[n][16 * chip:16 * chip + 16]
                               for n in ("we1", "we3", "we2")})
            y, counts = jax.jit(
                lambda held, x: expert_share_ffn(held, x, share))(held, x)
            total = total + (y - shared_only)
            # every share routes over all 128 and counts them all alike
            assert np.array_equal(np.asarray(counts).reshape(-1), loads)
    assert int(loads.sum()) == 6 * tokens
    assert off(total, want) < 1e-5


# -- the decoder ----------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    """The cell's tiny configuration in float32, its parameters prepared
    (routers fitted, head fitted) and its batch."""
    cell = mf.load().cell(CELL, tiny=True)
    cell["config"]["activation_dtype"] = "float32"
    cfg = builder._model_config(cell)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = builder.make_batch(cell, 0, 2)
    params, loss, _ = builder.prepare(params, batch["tokens"],
                                      batch["targets"], cell)
    return cell, cfg, params, batch, loss


def program_loss(cfg, mesh=None, check_vma=True):
    """The decoder's loss as ``make_train_step`` maps it.  The interpreted
    flash kernels read their schedule's tables (constants) beside blocks
    that vary over the mesh, which the interpreter's slices refuse under
    ``check_vma``; the tests that run them interpreted turn it off."""
    mesh = mesh or mesh_of((1, 1, 1))
    rows = {k: P(cfg.dp_axis, cfg.sp_axis) for k in ("tokens", "targets")}
    return jax.shard_map(lambda p, b: T.loss_fn(p, b, cfg), mesh=mesh,
                         in_specs=(T.param_specs(cfg), rows), out_specs=P(),
                         check_vma=check_vma)


def test_the_tiny_decoder_is_six_layers_of_the_cells_form(tiny):
    _, cfg, params, _, _ = tiny
    assert cfg.n_layers == 6 and len(cfg.leading_layers) == 1
    (mixer, ffn), = cfg.layer_pattern
    assert isinstance(mixer, T.LatentAttention) and ffn == "expert_share"
    assert cfg.leading_layers[0] == (mixer, "dense")
    assert not cfg.tie_embeddings and cfg.remat
    assert params["layers"][0]["wq"].shape == (5, 64, 4 * 24)
    assert params["leading"][0]["wkv_a"].shape == (1, 64, 32 + 8)


def test_the_decoders_loss_and_every_gradient_match_the_reference(tiny):
    cell, cfg, params, batch, loss = tiny
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(program_loss(cfg)))(params, batch)
        want = jax.jit(jax.value_and_grad(
            lambda p: builder.reference_loss_fn(
                p, batch["tokens"], batch["targets"], cell["config"])))(
                    params)
    assert abs(float(got[0]) - loss) < 2e-4 * loss
    assert abs(float(got[0]) - float(want[0])) < 2e-4 * loss
    flat_got = jax.tree_util.tree_leaves_with_path(got[1])
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want[1]))
    for path, leaf in flat_got:
        if "router_bias" in jax.tree_util.keystr(path):
            continue            # chosen under it, not weighted by it
        assert off(leaf, flat_want[path]) < 2e-4, jax.tree_util.keystr(path)


@pytest.mark.parametrize("wrong", builder.WRONG)
def test_the_fitted_head_tells_each_wrong_part(tiny, wrong):
    cell, _, params, batch, loss = tiny
    with jax.default_matmul_precision("highest"):
        reads = float(builder.reference_loss_fn(
            params, batch["tokens"], batch["targets"], cell["config"],
            wrong=(wrong,)))
    assert abs(reads - loss) > 0.1 * loss, (reads, loss)


def test_the_configuration_holds_the_published_values():
    cell = mf.load().cell(CELL)
    c = cell["config"]
    assert (c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["moe_intermediate_size"], c["intermediate_size"],
            c["n_shared_experts"], c["num_experts_per_tok"],
            c["routed_scaling_factor"], c["rope_theta"]) \
        == (2048, 32, 512, 128, 64, 128, 768, 6144, 2, 6, 2.448, 1000000)
    cfg = builder._model_config(cell)
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == c["parameters"] == 687502976
    assert cfg.experts == ExpertShare(128, 0, 16, 6, 2048, 768, 1536,
                                      routed_scaling=2.448, block_rows=512,
                                      form="swiglu")
    assert cfg.head_block == 4096 and cfg.remat_policy == "full"


# -- scopes, counter, refusals ---------------------------------------------------

def test_the_block_carries_its_scope_in_both_passes_and_is_counted(tiny,
                                                                  monkeypatch):
    _, cfg, params, batch, _ = tiny

    def series():
        rows = metrics.snapshot().get("hvd_latent_attention_calls_total",
                                      {"series": []})["series"]
        return {r["labels"]["form"]: r["value"] for r in rows}

    before = series()
    text = jax.jit(jax.grad(program_loss(cfg))).lower(params, batch) \
        .as_text(debug_info=True)
    lines = [ln for ln in text.splitlines() if scopes.LATENT_ATTENTION in ln]
    assert lines and all(scopes.ATTENTION in ln for ln in lines)
    assert any("transpose(" in ln for ln in lines) \
        and any("transpose(" not in ln for ln in lines)
    # on the CPU the block takes the XLA form; the leading layer and the
    # scanned one are traced once each
    assert series().get("xla", 0) \
        - before.get("xla", 0) == 2
    # where the models take the kernels they run under the flash scopes,
    # inside the block's, at the two sizes
    monkeypatch.setattr(pk, "use_flash_attention", lambda: True)
    text = jax.jit(jax.grad(program_loss(cfg, check_vma=False))).lower(
        params, batch).as_text(debug_info=True)
    for scope in (scopes.FLASH_FWD, scopes.FLASH_BWD_ONEPASS):
        found = [ln for ln in text.splitlines() if scope in ln]
        assert found and all(scopes.LATENT_ATTENTION in ln for ln in found)
    assert "hvd_flash_fwd_16s8x128" in text
    assert series().get("kernel", 0) \
        - before.get("kernel", 0) == 2


def test_the_block_under_the_kernels_builds_no_key_of_the_whole_size(
        monkeypatch):
    """Traced where the models take the kernels, both passes, the block
    hands them the one rotary key as it is: nothing repeats it over the
    heads and one concatenation alone makes ``[B, S, H, nope + rope]``, q's
    (the XLA form has the key's too); each kernel that reads the part is
    counted once a traced call, and a call without the part adds nothing."""
    cfg, lp = block_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64))
    tables = {KIND: T.rope_tables(jnp.arange(64), KIND.rope_dim, KIND.rope,
                                  cfg.act_dtype)}
    heads = (2, 64, KIND.n_heads)

    def eqns_of(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns_of(sub)

    def traced():
        return jax.make_jaxpr(jax.shard_map(
            jax.grad(lambda lp, x: T._latent_attention_block(
                x, lp, cfg, KIND, tables).sum()),
            mesh=mesh_of((1, 1, 1)),
            in_specs=(jax.tree.map(lambda _: P(), lp), P()),
            out_specs=jax.tree.map(lambda _: P(), lp), check_vma=False))(
                lp, x)

    def made(closed, primitive, width):
        return sum(eqn.primitive.name == primitive
                   and eqn.outvars[0].aval.shape == heads + (width,)
                   for eqn in eqns_of(closed.jaxpr))

    whole = KIND.nope + KIND.rope_dim
    on_cpu = traced()
    assert made(on_cpu, "broadcast_in_dim", KIND.rope_dim) == 1
    assert made(on_cpu, "concatenate", whole) == 2
    before = shared_key_calls()
    monkeypatch.setattr(pk, "use_flash_attention", lambda: True)
    with_kernels = traced()
    assert made(with_kernels, "broadcast_in_dim", KIND.rope_dim) == 0
    assert made(with_kernels, "concatenate", whole) == 1
    assert "hvd_flash_fwd_16s8x128" in str(with_kernels) \
        and "hvd_flash_bwd_onepass_16s8x128" in str(with_kernels)
    after = shared_key_calls()
    assert {k: after[k] - before.get(k, 0) for k in ("fwd", "onepass")} \
        == {"fwd": 1, "onepass": 1}
    q, k, v, weight = inputs(24, 12, seq=64, heads=4)
    jax.make_jaxpr(jax.grad(lambda *a: pk.flash_attention(*a).sum(),
                            argnums=(0, 1, 2)))(q, k, v)
    assert shared_key_calls() == after
    # a call inside a recomputed layer traces its forward kernel twice (the
    # call itself into the layer's program, then the rule that keeps the
    # residuals when that is differentiated): a built step whose two traced
    # layers are recomputed reads fwd 4, onepass 2, as the block pairs of
    # ``hvd_flash_block_pairs_total{fwd}`` are four calls' there
    jax.make_jaxpr(jax.grad(jax.checkpoint(
        lambda q, k, v, shared: pk.flash_attention(
            q, k, v, k_shared=shared).sum())))(
                q, k[..., :16], v, q[:, :, 0, 16:])
    recomputed = shared_key_calls()
    assert {k: recomputed[k] - after[k] for k in ("fwd", "onepass")} \
        == {"fwd": 2, "onepass": 1}


def test_the_kernel_form_of_the_decoder_matches_the_reference(tiny,
                                                             monkeypatch):
    cell, cfg, params, batch, loss = tiny
    monkeypatch.setattr(pk, "use_flash_attention", lambda: True)
    with jax.default_matmul_precision("highest"):
        got = float(jax.jit(program_loss(cfg, check_vma=False))(params,
                                                                batch))
    assert abs(got - loss) < 2e-4 * loss


@pytest.mark.parametrize("build, match", [
    (lambda: T.LatentAttention(4, 32, 16, 7, 12), "even rotary size"),
    (lambda: T.LatentAttention(4, 0, 16, 8, 12), "latent"),
    (lambda: T.LatentAttention(4, 32, 16, 8, 12, T.Rope(share=0.5)),
     "turns whole"),
])
def test_a_latent_kind_refuses_what_it_cannot_be(build, match):
    with pytest.raises(ValueError, match=match):
        build()


def test_a_split_sequence_is_refused_by_name():
    cfg = block_config()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = {k: jnp.zeros((2, 64), jnp.int32) for k in ("tokens", "targets")}
    with pytest.raises(ValueError, match="ring_attention.py.*ulysses.py"):
        jax.jit(program_loss(cfg, mesh_of((1, 2, 1)))).lower(params, batch)
