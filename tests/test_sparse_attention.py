"""Learned sparse attention: the choice kernel (interpreted) against the
benchmark's plain reference (``yardstick/builders/keye_vl2.py``) to the bit,
ties included; the attention kernels given the reference's own choice in
values and every gradient; the indexer loss, its gradients and where they
go; the block, the tiny decoder and its kernel form against the reference;
the softmax router's shares; the per-head QK-norm split over tp; the
configuration's count; the counters against ``yardstick/flops_sparse.py``;
scopes and refusals."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.common import metrics, scopes
from horovod_tpu.models import transformer as T
from horovod_tpu.ops import pallas_kernels as pk
from horovod_tpu.ops import sparse_kernels as sk
from horovod_tpu.parallel.moe import ExpertShare
from yardstick import flops_sparse as fs
from yardstick import manifest as mf
from yardstick.builders import keye_vl2 as builder

CELL = "keye-vl2-30b-a3b.dp1-pt32k"


def off(got, want):
    """Largest difference of two trees' leaves, each against its leaf's
    largest entry."""
    f32 = jnp.float32
    return max(float(jnp.abs(a.astype(f32) - b.astype(f32)).max()
                     / jnp.abs(b.astype(f32)).max())
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def mesh_of(shape):
    devices = np.asarray(jax.devices()[:int(np.prod(shape))]).reshape(shape)
    return Mesh(devices, ("dp", "sp", "tp"))


def attention_inputs(seq=64, heads=4, kv=2, d=16, heads_i=2, di=16, seed=0,
                     ties=False):
    """q, k, v ``[1, S, H|G, d]``, qI ``[1, S, HI, DI]``, kI ``[1, S, DI]``,
    w ``[1, S, HI]``; with ``ties`` the indexer's inputs are small whole
    numbers, so that many scores are equal."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (1, seq, heads, d))
    k = jax.random.normal(ks[1], (1, seq, kv, d))
    v = jax.random.normal(ks[2], (1, seq, kv, d))
    qi = jax.random.normal(ks[3], (1, seq, heads_i, di))
    ki = jax.random.normal(ks[4], (1, seq, di))
    w = 0.3 * jax.random.normal(ks[5], (1, seq, heads_i))
    if ties:
        qi, ki, w = jnp.round(qi), jnp.round(ki), jnp.round(4 * w) / 4
    return q, k, v, qi, ki, w


def reference_choice(qi, ki, w, top_k):
    """The reference's choice ``[1, S, S]`` from the kernels' inputs."""
    seq = qi.shape[1]
    scores = builder.reference_scores(qi[0], ki[0], w[0])
    return builder.reference_kept(scores, jnp.arange(seq), top_k)[None], \
        scores[None]


def pack(kept, pack_rows):
    """``[B, S, S]`` bool -> the kernels' words ``[B, S / 32, S]``."""
    b, seq, _ = kept.shape
    r = pack_rows // 32
    bits = kept.reshape(b, seq // pack_rows, 32, r, seq).astype(jnp.int32)
    words = jnp.zeros((b, seq // pack_rows, r, seq), jnp.int32)
    for bit in range(32):
        words = words | (bits[:, :, bit] << bit)
    return words.reshape(b, seq // 32, seq)


def unpack(words, pack_rows):
    """The kernels' words ``[B, S / 32, S]`` -> ``[B, S, S]`` bool."""
    b, _, seq = words.shape
    r = pack_rows // 32
    words = words.reshape(b, seq // pack_rows, r, seq)
    bits = (words[:, :, None] >> jnp.arange(32)[None, None, :, None, None]) & 1
    return bits.reshape(b, seq, seq) == 1


def masked_attention(q, k, v, qi, ki, w, kept):
    """Attention and the indexer loss over a given choice, in plain XLA."""
    b, seq, heads, d = q.shape
    group = heads // k.shape[2]
    kk, vv = (jnp.repeat(x, group, axis=2) for x in (k, v))
    logits = jnp.einsum("bthd,bshd->bhts", q, kk) / math.sqrt(d)
    probs = jax.nn.softmax(jnp.where(kept[:, None], logits, -jnp.inf), -1)
    o = jnp.einsum("bhts,bshd->bthd", probs, vv)
    scores = builder.reference_scores(qi[0], ki[0], w[0])[None]
    p = jax.lax.stop_gradient(probs.mean(1))
    log_q = jax.nn.log_softmax(jnp.where(kept, scores, -jnp.inf), -1)
    kl = jnp.sum(jnp.where(kept, p * (jnp.log(jnp.where(kept, p, 1.0))
                                      - jnp.where(kept, log_q, 0.0)), 0.0),
                 -1)
    return o, kl


# -- the choice ---------------------------------------------------------------

@pytest.mark.parametrize("block, pack_rows, ties", [
    (32, 32, True), (64, 32, False)])
def test_the_choice_is_the_references_to_the_bit(monkeypatch, block,
                                                 pack_rows, ties):
    """In float32 the kernel keeps exactly the keys the reference's
    ``lax.top_k`` keeps, ties to the lower position included (whole-number
    inputs make many scores equal), whatever the blocks; and the
    log-sum-exp of the kept scores is the reference's."""
    monkeypatch.setattr(sk, "PACK", pack_rows)
    _, _, _, qi, ki, w = attention_inputs(ties=ties)
    kept, scores = reference_choice(qi, ki, w, 8)
    mask, lse = jax.jit(lambda *a: sk.select(*a, 8, block, interpret=True))(
        jnp.swapaxes(qi, 1, 2), ki, jnp.swapaxes(w, 1, 2)[..., None])
    got = unpack(mask, pack_rows)
    assert np.array_equal(np.asarray(got), np.asarray(kept))
    assert int(got.sum()) == fs.kept_pairs(64, 8)
    if ties:
        # the rounding made ties at the threshold, and they were broken
        sorted_scores = -np.sort(-np.asarray(scores[0]), axis=-1)
        assert np.any(sorted_scores[8:, 7] == sorted_scores[8:, 8])
    want = jax.scipy.special.logsumexp(jnp.where(kept, scores, -jnp.inf), -1)
    assert np.allclose(lse[..., 0], want, rtol=1e-6, atol=1e-6)


def test_the_order_key_orders_floats_and_takes_minus_zero_as_zero():
    x = jnp.asarray([-jnp.inf, -2.5, -1e-30, -0.0, 0.0, 1e-30, 3.0, jnp.inf])
    keys = np.asarray(sk.order_key(x))
    assert keys[3] == keys[4] == 0
    assert np.all(np.diff(np.delete(keys, 3)) > 0)
    assert np.array_equal(np.asarray(sk.from_key(jnp.asarray(keys)))[4:],
                          np.asarray(x)[4:])


# -- the attention kernels over a given choice --------------------------------

def test_the_kernels_match_over_the_references_own_choice(block=32):
    """Handed the reference's choice (so that a kernel's error cannot hide
    behind a flipped key), the kernels' output, indexer loss and every
    gradient are those of plain masked attention: the output's reach q, k,
    v, the loss's qI, kI, w.  float32, interpreted; the tolerance is
    float32 rounding of sums over 64 keys."""
    q, k, v, qi, ki, w = attention_inputs(heads=2, kv=1, seed=1)
    kept, scores = reference_choice(qi, ki, w, 8)
    lse_i = jax.scipy.special.logsumexp(
        jnp.where(kept, scores, -jnp.inf), -1)[..., None]
    blocks = (block, block)
    weights = jax.random.normal(jax.random.PRNGKey(7), q.shape)
    weights_kl = jax.random.normal(jax.random.PRNGKey(8), (1, 64))

    def core(q, k, v, qi, ki, w):
        def heads_first(x):
            return jnp.swapaxes(x, 1, 2)
        o, kl = sk._core(heads_first(q / math.sqrt(q.shape[-1])),
                         heads_first(k), heads_first(v), heads_first(qi), ki,
                         heads_first(w)[..., None], pack(kept, block), lse_i,
                         blocks, True)
        return heads_first(o), kl[..., 0]

    def plain(*args):
        return masked_attention(*args, kept)

    def scalar(f):
        def run(*args):
            o, kl = f(*args)
            return jnp.sum(o * weights) + jnp.sum(kl * weights_kl)
        return run

    args = (q, k, v, qi, ki, w)
    got, want = jax.jit(core)(*args), plain(*args)
    assert off(got, want) < 1e-5
    grads = jax.jit(jax.grad(scalar(core), argnums=range(6)))(*args)
    grads_want = jax.grad(scalar(plain), argnums=range(6))(*args)
    for name, a, b in zip(("q", "k", "v", "qi", "ki", "w"), grads,
                          grads_want):
        assert off(a, b) < 1e-5, name


def test_the_kernel_form_equals_the_xla_form_with_its_own_choice():
    """The whole call, choice included, kernels against the XLA form the
    model takes on the CPU: the same output and indexer loss, to float32
    rounding of sums over 64 keys (1e-5)."""
    args = attention_inputs(seed=2)
    kernels = jax.jit(lambda *a: sk.sparse_attention(*a, 8, 32, True))
    xla = jax.jit(lambda *a: sk.sparse_attention_xla(*a, 8))
    assert off(kernels(*args), xla(*args)) < 1e-5


def test_a_call_refuses_blocks_that_do_not_divide():
    with pytest.raises(ValueError, match="blocks of"):
        sk.plan(96, 64)
    assert sk.plan(32768) == (256, 256)
    assert sk.plan(128) == (128, 128)


# -- the block against the reference ------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    """The cell's tiny configuration in float32, its parameters prepared
    (routers and head fitted) and its batch."""
    cell = mf.load().cell(CELL, tiny=True)
    cell["config"]["activation_dtype"] = "float32"
    cfg = builder._model_config(cell)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = builder.make_batch(cell, 0, 2)
    params, loss, _ = builder.prepare(params, batch["tokens"],
                                      batch["targets"], cell)
    return cell, cfg, params, batch, loss


def block_call(cfg, lp, x, mesh=None, specs=None):
    """``_sparse_attention_block`` over a mesh, one layer's parameters."""
    (mixer, _), = cfg.layer_pattern
    seq = x.shape[1]
    tables = {mixer.rope: T.rope_tables(jnp.arange(seq), cfg.head_dim,
                                        mixer.rope, cfg.act_dtype),
              ("indexer", mixer): T.rope_tables(
                  jnp.arange(seq), mixer.index_dim, mixer.index_rope,
                  cfg.act_dtype)}
    mesh = mesh or mesh_of((1, 1, 1))
    specs = specs or jax.tree.map(lambda _: P(), lp)
    return jax.shard_map(
        lambda lp, x: T._sparse_attention_block(x, lp, cfg, mixer, tables,
                                                1),
        mesh=mesh, in_specs=(specs, P()), out_specs=(P(), P()),
        check_vma=False)(lp, x)


def one_layer(params):
    return jax.tree.map(lambda w: w[0], params["layers"][0])


def test_the_block_and_its_loss_match_the_reference_and_go_where_they_should(
        tiny):
    """The block's output and indexer loss equal the reference's, and so do
    their gradients; the output's gradient reaches no indexer parameter,
    the loss's none of the attention's and not the block's input (the
    indexer reads it detached).  float32 at the highest precision: 1e-5 is
    rounding of sums over 128 keys; the indexer weights' gradients pass
    through a softmax's difference of two near-equal terms, 1e-4."""
    cell, cfg, params, batch, _ = tiny
    lp = one_layer(params)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 128, 64))
    indexer = {"w_iq", "w_ik", "ik_norm", "ik_bias", "w_iw"}
    weights = jax.random.normal(jax.random.PRNGKey(5), (1, 128, 64))
    config = cell["config"]

    def program(lp, x):
        out, kl = block_call(cfg, lp, x)
        return jnp.sum(out * weights), kl

    def reference(lp, x):
        out, kl = builder.reference_sparse_attention(x[0], lp, config)
        return jnp.sum(out * weights[0]), kl

    def both(f):
        return jax.jit(lambda lp, x: (
            f(lp, x), jax.grad(lambda *a: f(*a)[0], argnums=(0, 1))(lp, x),
            jax.grad(lambda *a: f(*a)[1], argnums=(0, 1))(lp, x)))

    with jax.default_matmul_precision("highest"):
        (out, kl), d_out, d_kl = both(program)(lp, x)
        (want, want_kl), want_out, want_d_kl = both(reference)(lp, x)
    assert abs(float(out) - float(want)) < 1e-5 * abs(float(want))
    assert abs(float(kl) - float(want_kl)) < 1e-5 * float(want_kl)
    want_out, want_kl = want_out[0], want_d_kl[0]
    for name in lp:
        if name in indexer:
            assert not np.any(np.asarray(d_out[0][name])), name
            assert off(d_kl[0][name], want_kl[name]) < 1e-4, name
        elif name in ("wq", "wk", "wv", "wo", "q_norm", "k_norm"):
            assert not np.any(np.asarray(d_kl[0][name])), name
            assert off(d_out[0][name], want_out[name]) < 1e-5, name
    assert not np.any(np.asarray(d_kl[1]))
    assert np.any(np.asarray(d_out[1]))


def test_the_first_mixers_readings_pass_the_program_and_catch_the_faults(
        tiny, monkeypatch):
    """The cell's readings of its first mixer (``mixer_readings``) on the
    tiny configuration, the kernels interpreted: the choice kernel keeps
    every query's keys, the kernels' gradients are the reference's to
    float32 rounding of sums over 128 keys (1e-5), nothing crossed; a key
    fewer a query changes the set of every query past ``top_k - 1``, and an
    indexer reading its input with its gradient crosses."""
    cell, cfg, params, batch, _ = tiny
    config = cell["config"]
    monkeypatch.setattr(pk, "use_flash_attention", lambda: True)
    readings = builder.mixer_readings(cfg, mesh_of((1, 1, 1)), params,
                                      batch["tokens"], config)
    assert readings["choice"] == 0 and readings["crossed"] == 0
    assert readings["gradient"] < 1e-5
    assert set(readings["by_input"]) == {*builder.ATTENTION_INPUTS,
                                         *builder.INDEXER_INPUTS}
    lp, x = builder.first_mixer_input(params, batch["tokens"], config)
    top_k, seq = config["sa_config"]["topk"], x.shape[0]
    with jax.default_matmul_precision("highest"):
        qi, ki, w = builder.reference_indexer(x, lp, config)
        one_fewer = builder.choice_differs(
            lambda j: builder.reference_kept(
                builder.reference_scores(qi, ki, w), jnp.arange(seq),
                top_k - 1), qi, ki, w, top_k, seq)
    assert float(one_fewer) == (seq - top_k + 1) / seq
    attached = jax.jit(lambda lp, x: builder.gradients(
        lambda x, **lp: builder.reference_sparse_attention(
            x, lp, config, wrong=builder.WRONG_GRADIENTS),
        dict(lp, x=x)))(lp, x)
    assert builder.crossed(attached, builder.MIXER_LEAVES + ("x",),
                           builder.INDEXER_LEAVES) > 0


@pytest.mark.parametrize("wrong", builder.WRONG)
def test_the_fitted_head_tells_each_wrong_part(tiny, wrong):
    """Each wrong part moves the reference's loss by more than 1e-3 of it,
    a hundred times the program's float32 distance above."""
    cell, _, params, batch, loss = tiny
    with jax.default_matmul_precision("highest"):
        reads = float(jax.jit(lambda p, tokens, targets:
                              builder.reference_loss_fn(
                                  p, tokens, targets, cell["config"],
                                  wrong=(wrong,)))(
            params, batch["tokens"], batch["targets"]))
    assert abs(reads - loss) > 1e-3 * loss, (reads, loss)


# -- the decoder --------------------------------------------------------------

def program_loss(cfg, mesh=None, check_vma=True):
    mesh = mesh or mesh_of((1, 1, 1))
    rows = {k: P(cfg.dp_axis, cfg.sp_axis) for k in ("tokens", "targets")}
    return jax.shard_map(lambda p, b: T.loss_fn(p, b, cfg), mesh=mesh,
                         in_specs=(T.param_specs(cfg), rows), out_specs=P(),
                         check_vma=check_vma)


def test_the_tiny_decoder_is_four_layers_of_the_cells_form(tiny):
    _, cfg, params, _, _ = tiny
    (mixer, ffn), = cfg.layer_pattern
    assert cfg.n_layers == 4 and not cfg.leading_layers and cfg.sparse
    assert isinstance(mixer, T.SparseAttention) and ffn == "expert_share"
    assert (mixer.n_heads, mixer.n_kv_heads, mixer.index_heads,
            mixer.index_dim, mixer.top_k, mixer.index_rope.share) \
        == (4, 2, 2, 16, 16, 0.5)
    assert cfg.experts.scoring == "softmax" and cfg.experts.d_shared == 0
    assert "router_bias" not in params["layers"][0]
    assert params["layers"][0]["w_iq"].shape == (4, 64, 32)


def test_the_decoders_loss_and_every_gradient_match_the_reference(tiny):
    """A whole step's loss (cross entropy plus the four indexer losses)
    and every gradient, the XLA form against the plain reference, float32:
    1e-5 of the loss is rounding; 2e-4 of a gradient leaf's largest entry
    is float32 sums over 256 tokens through four layers, the latent
    decoder's tests' allowance."""
    cell, cfg, params, batch, loss = tiny
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(program_loss(cfg)))(params, batch)
        want = jax.jit(jax.value_and_grad(
            lambda p: builder.reference_loss_fn(
                p, batch["tokens"], batch["targets"], cell["config"])))(
                    params)
    assert abs(float(got[0]) - loss) < 1e-5 * loss
    assert abs(float(got[0]) - float(want[0])) < 1e-5 * loss
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want[1]))
    for path, leaf in jax.tree_util.tree_leaves_with_path(got[1]):
        assert off(leaf, flat_want[path]) < 2e-4, jax.tree_util.keystr(path)


def test_the_kernel_form_of_the_decoder_matches_the_reference(tiny,
                                                             monkeypatch):
    """The kernels interpreted in the whole decoder: its loss is the
    reference's to float32 rounding (1e-5)."""
    cell, cfg, params, batch, loss = tiny
    monkeypatch.setattr(pk, "use_flash_attention", lambda: True)
    with jax.default_matmul_precision("highest"):
        got = float(jax.jit(program_loss(cfg, check_vma=False))(params,
                                                                batch))
    assert abs(got - loss) < 1e-5 * loss


def test_the_configuration_holds_the_published_values():
    cell = mf.load().cell(CELL)
    c = cell["config"]
    sa = c["sa_config"]
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["rope_theta"], sa["indexer_num_heads"], sa["indexer_head_dim"],
            sa["topk"]) \
        == (2048, 32, 4, 128, 768, 8, 10000000, 16, 64, 2048)
    cfg = builder._model_config(cell)
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == c["parameters"] == 465391104
    assert cfg.experts == ExpertShare(128, 0, 16, 8, 2048, 768, 0,
                                      block_rows=512, form="swiglu",
                                      scoring="softmax")
    assert cfg.head_block == 4096 and cfg.remat_policy == "full"


# -- shares -------------------------------------------------------------------

def test_eight_shares_of_sixteen_softmax_experts_add_up_to_the_uncut_layer():
    """The guide's share test with the softmax router: 128 experts, 8 a
    token, 16 held by each of 8 chips, no shared expert; 1e-5 is float32
    rounding of eight partial sums."""
    from horovod_tpu.parallel.moe import (expert_share_ffn,
                                          init_expert_share_params)
    d, width, tokens = 32, 16, 96
    whole = ExpertShare(128, 0, 128, 8, d, width, 0, block_rows=8,
                        scoring="softmax")
    lp = jax.tree.map(lambda w: w[0], init_expert_share_params(
        jax.random.PRNGKey(0), whole, 1))
    assert "router_bias" not in lp
    x = jax.random.normal(jax.random.PRNGKey(2), (tokens, d))
    config = {"held": {"first_expert": 0}, "num_experts_per_tok": 8}
    with jax.default_matmul_precision("highest"):
        want, loads, _ = jax.jit(
            lambda x, lp: builder.reference_expert_layer(x, lp, config))(
                x, lp)
        total = 0.0
        for chip in range(8):
            share = ExpertShare(128, 16 * chip, 16, 8, d, width, 0,
                                block_rows=8, scoring="softmax")
            held = dict(lp, **{n: lp[n][16 * chip:16 * chip + 16]
                               for n in ("we1", "we3", "we2")})
            y, counts = jax.jit(
                lambda held, x: expert_share_ffn(held, x, share))(held, x)
            total = total + y
            assert np.array_equal(np.asarray(counts).reshape(-1), loads)
    assert int(loads.sum()) == 8 * tokens
    assert off(total, want) < 1e-5


def test_a_router_scores_by_sigmoid_or_softmax_alone():
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        ExpertShare(8, 0, 4, 2, 8, 8, 0, scoring="relu")
    assert ExpertShare(8, 0, 4, 2, 8, 8, 0).scoring == "sigmoid"


def test_the_per_head_qk_norm_split_over_tp_is_the_whole(tiny):
    """Each head's norm needs no other head: q, k and v of two tp shards of
    the heads (the norms' scales whole on each) are those of one, to
    float32 rounding (1e-6)."""
    _, cfg, params, _, _ = tiny
    (mixer, _), = cfg.layer_pattern
    lp = one_layer(params)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, 64))
    tables = {mixer.rope: T.rope_tables(jnp.arange(16), cfg.head_dim,
                                        mixer.rope, cfg.act_dtype)}
    specs = jax.tree.map(lambda s: P(*s[1:]),
                         T._layer_specs(cfg, mixer, "expert_share"))
    specs = {name: specs[name] for name in lp}
    assert specs["wq"] == P(None, "tp") and specs["q_norm"] == P(None)

    def run(mesh, specs):
        return jax.device_get(jax.jit(jax.shard_map(
            lambda lp, x: T.head_qk_norm(x, lp, cfg, mixer, tables),
            mesh=mesh, in_specs=(specs, P()),
            out_specs=(P(None, None, "tp"),) * 3, check_vma=False))(lp, x))

    with jax.default_matmul_precision("highest"):
        whole = run(mesh_of((1, 1, 1)), jax.tree.map(lambda _: P(), lp))
        split = run(mesh_of((1, 1, 2)), specs)
    assert off(split, whole) < 1e-6


# -- counters, scopes, refusals -----------------------------------------------

def test_the_counters_agree_with_the_benchmarks_count(tiny):
    _, cfg, params, batch, _ = tiny

    def reading():
        snap = metrics.snapshot()
        return tuple(sum(r["value"] for r in snap.get(name, {"series": []})
                         ["series"])
                     for name in ("hvd_indexer_scored_pairs_total",
                                  "hvd_sparse_attention_pairs_total"))

    before = reading()
    jax.make_jaxpr(jax.grad(program_loss(cfg)))(params, batch)
    after = reading()
    batch_size, seq = batch["tokens"].shape
    assert after[0] - before[0] == 4 * batch_size * fs.scored_pairs(seq)
    assert after[1] - before[1] == 4 * batch_size * fs.kept_pairs(seq, 16)
    assert fs.kept_pairs(32768, 2048) == 65012736


def test_the_block_carries_its_scopes_in_both_passes(tiny, monkeypatch):
    _, cfg, params, batch, _ = tiny
    text = jax.jit(jax.grad(program_loss(cfg))).lower(params, batch) \
        .as_text(debug_info=True)
    for scope in (scopes.SPARSE_ATTENTION, scopes.INDEXER,
                  scopes.INDEXER_TOPK):
        lines = [ln for ln in text.splitlines() if scope in ln]
        assert lines and all(scopes.ATTENTION in ln for ln in lines), scope
    # the recomputed layer of the backward pass carries them as well
    assert any("rematted_computation" in ln and scopes.INDEXER in ln
               for ln in text.splitlines())
    monkeypatch.setattr(pk, "use_flash_attention", lambda: True)
    text = jax.jit(jax.value_and_grad(program_loss(cfg, check_vma=False))
                   ).lower(params, batch).as_text(debug_info=True)
    for scope in (scopes.SPARSE_FLASH_FWD, scopes.SPARSE_FLASH_BWD,
                  scopes.INDEXER_TOPK):
        found = [ln for ln in text.splitlines() if scope in ln]
        assert found and all(scopes.SPARSE_ATTENTION in ln
                             for ln in found), scope
    for name in ("hvd_sparse_flash_fwd", "hvd_sparse_flash_bwd_dq",
                 "hvd_sparse_flash_bwd_dkv", "hvd_indexer_topk",
                 "hvd_indexer_kl"):
        assert name in text, name


def test_a_split_sequence_or_split_heads_are_refused_by_name(tiny):
    _, cfg, params, batch, _ = tiny
    for shape in ((1, 2, 1), (1, 1, 2)):
        with pytest.raises(ValueError, match="neither the sequence"):
            jax.jit(program_loss(cfg, mesh_of(shape))).lower(params, batch)


@pytest.mark.parametrize("build, match", [
    (lambda: T.SparseAttention(4, 3, 2, 16, 8), "do not divide"),
    (lambda: T.SparseAttention(4, 2, 2, 14, 8), "multiple of 4"),
    (lambda: T.TransformerConfig(
        layer_pattern=((T.SparseAttention(8, 8, 2, 16, 8), "dense"),),
        post_norm=True), "no post_norm"),
])
def test_a_sparse_kind_refuses_what_it_cannot_be(build, match):
    with pytest.raises(ValueError, match=match):
        build()
