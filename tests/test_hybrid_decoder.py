"""The hybrid decoder (``models/transformer.py`` with a layer pattern:
gated NoPE softmax layers, delta-rule linear-attention layers, a chip's
share of a dropless expert layer) against the plain float32 reference kept
with the benchmark (``yardstick/builders/solar_open2.py``), at a small size
on the CPU with seeded weights."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import transformer
from horovod_tpu.models.linear_attention import (KdaConfig, init_kda_params,
                                                 kda_chunked,
                                                 kda_chunked_xla,
                                                 linear_attention_block)
from horovod_tpu.common import scopes
from horovod_tpu.ops import kda_kernels, moe_kernels
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.moe import (ExpertShare, expert_share_ffn,
                                      init_expert_share_params)
from yardstick.builders import solar_open2 as reference


def small_cell(heads=2, kv_heads=1, first_expert=3, dtype="float32"):
    """hidden 64, heads of 16, 8 experts, 2 a token, 2 held, one period of
    4 layers, sequences of 128: the cell's files in small."""
    config = {
        "hidden_size": 64, "head_dim": 16, "vocab_size": 256,
        "num_hidden_layers": 4, "num_attention_heads": heads,
        "num_key_value_heads": kv_heads, "intermediate_size": 128,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                               "num_heads": heads, "num_kv_heads": None},
        "moe_intermediate_size": 32, "n_routed_experts": 2,
        "published": {"n_routed_experts": 8}, "num_experts_per_tok": 2,
        "n_shared_experts": 1, "routed_scaling_factor": 1,
        "rms_norm_eps": 1e-5, "gqa_interval": 3, "gqa_layers": [0, 4, 8],
        "first_k_dense_replace": 0, "tie_word_embeddings": False,
        "activation_dtype": dtype, "param_dtype": "float32",
        "assumed_sizes": {"gate_rank": 16},
        "held": {"first_expert": first_expert},
    }
    spec = {"seq_len": 128, "batch_per_chip": 2, "delta_rule_chunk": 16,
            "expert_block_rows": 16, "head_block": 64}
    return {"name": "small", "config": config, "spec": spec}


def program_loss_and_grads(cfg, params, batch, mesh_shape=(1, 1, 1)):
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:math.prod(mesh_shape)]).reshape(mesh_shape),
        (cfg.dp_axis, cfg.sp_axis, cfg.tp_axis))
    specs = transformer.param_specs(cfg)
    rows = {k: P(cfg.dp_axis, cfg.sp_axis) for k in batch}
    fn = jax.jit(jax.shard_map(
        jax.value_and_grad(lambda p, b: transformer.loss_fn(p, b, cfg)),
        mesh=mesh, in_specs=(specs, rows), out_specs=(P(), specs),
        check_vma=True))
    return fn(params, batch)


def worst(a, b):
    """Largest difference between two trees' leaves, each against the
    largest entry of the second's leaf."""
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(np.abs(x - y).max() / (np.abs(y).max() + 1e-30)),
        jax.device_get(a), jax.device_get(b))))


# -- the whole model ---------------------------------------------------------

def test_program_matches_reference_loss_and_every_gradient():
    cell = small_cell()
    cfg = reference._model_config(cell)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    batch = reference.make_batch(cell, 1, 2)
    loss, grads = program_loss_and_grads(cfg, params, batch)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.reference_loss_fn(
            p, batch["tokens"], batch["targets"], cell["config"])))(params)
    assert abs(float(loss) - float(ref_loss)) < 2e-5 * float(ref_loss)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref_flat = jax.tree.leaves(ref_grads)
    assert len(flat) == len(ref_flat) == 3 + 4 * 10 + 3 * 15 + 5
    for (path, g), r in zip(flat, ref_flat):
        if path[-1].key == "router_bias":     # chooses experts, no gradient
            assert float(jnp.abs(g).max()) == float(jnp.abs(r).max()) == 0
            continue
        assert float(jnp.abs(r).max()) > 0, path      # every leaf is used
        assert float(jnp.abs(g - r).max()) \
            < 2e-3 * float(jnp.abs(r).max()), path


def test_bf16_activations_stay_near_the_reference():
    cell = small_cell(dtype="bfloat16")
    cfg = reference._model_config(cell)
    params = transformer.init_params(jax.random.PRNGKey(2), cfg)
    batch = reference.make_batch(cell, 3, 2)
    loss, _ = program_loss_and_grads(cfg, params, batch)
    ref_loss = reference.reference_loss(params, batch, cell["config"])
    assert abs(float(loss) - ref_loss) < 5e-3 * ref_loss


def test_head_shares_over_tp_add_up_to_the_uncut_model():
    """Two tensor-parallel shards hold half the heads of every mixer and
    half the vocabulary each; their parts add up (psum over tp) to what
    one shard with all of them gives: loss and every gradient."""
    cell = small_cell(heads=4, kv_heads=2)
    cfg = reference._model_config(cell)
    params = transformer.init_params(jax.random.PRNGKey(4), cfg)
    batch = reference.make_batch(cell, 5, 2)
    whole = program_loss_and_grads(cfg, params, batch, (1, 1, 1))
    halves = program_loss_and_grads(cfg, params, batch, (1, 1, 2))
    assert abs(float(whole[0]) - float(halves[0])) < 1e-5 * float(whole[0])
    assert worst(halves[1], whole[1]) < 1e-3


@pytest.fixture(scope="module")
def prepared():
    """The small cell as the builder sets it before the first step: the
    balancing buffers fitted to a load profile and the head fitted to the
    batch, both by the reference (``prepare``)."""
    cell = small_cell()
    cell["spec"]["expert_load_profile"] = [1.5, 0.5]
    cfg = reference._model_config(cell)
    batch = reference.make_batch(cell, 9, 2)
    params, loss_ref, loads = reference.prepare(
        transformer.init_params(jax.random.PRNGKey(8), cfg),
        batch["tokens"], batch["targets"], cell)
    return cell, cfg, batch, params, loss_ref, loads


def test_the_fitted_buffers_give_the_program_the_cells_loads(prepared):
    """The loads are the profile's (an expert of the small cell spans six
    blocks of 16 rows, another two), in the reference that fitted the
    buffers and in the program that only reads them, and the program's
    loss is the reference's."""
    cell, cfg, batch, params, loss_ref, loads = prepared
    goal = reference.load_targets(cell, batch["tokens"].size)
    assert goal[3:5].tolist() == [96.0, 32.0] and goal.sum() == 2 * 256
    assert np.abs(loads - goal).max() <= 2
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                             (cfg.dp_axis, cfg.sp_axis, cfg.tp_axis))
    counts = jax.jit(jax.shard_map(
        lambda p, t: jax.tree.map(
            lambda c: lax.psum(c, (cfg.dp_axis, cfg.sp_axis)),
            transformer.hidden(p, t, cfg)[2]), mesh=mesh,
        in_specs=(transformer.param_specs(cfg), P(cfg.dp_axis, cfg.sp_axis)),
        out_specs=P(), check_vma=True))(params, batch["tokens"])
    assert np.abs(np.concatenate(counts) - loads).max() <= 1
    loss, _ = program_loss_and_grads(cfg, params, batch)
    assert abs(float(loss) - loss_ref) < 2e-5 * loss_ref


@pytest.mark.parametrize("part", reference.WITHOUT)
def test_the_fitted_head_tells_a_left_out_part(prepared, part):
    """Under the head fitted to its batch the loss reads the mean squared
    angle between the hidden states compared: the reference without one
    part is far outside the tolerance the benchmark's cell is held to."""
    cell, cfg, batch, params, loss_ref, _ = prepared
    without = reference.reference_loss_fn(
        params, batch["tokens"], batch["targets"], cell["config"],
        without=(part,))
    assert float(without) - loss_ref > 10 * reference.LOSS_RTOL * loss_ref


def test_train_step_runs_the_pattern_and_the_loss_falls():
    import optax

    import horovod_tpu.jax as hvd
    hvd.init()
    try:
        cell = small_cell(dtype="bfloat16")
        cfg = reference._model_config(cell)
        mesh = hvd.create_mesh((2, 1, 1), ("dp", "sp", "tp"),
                               jax.devices()[:2])
        build, shard = transformer.make_train_step(cfg, mesh,
                                                   optax.adamw(1e-3))
        step, params, opt = build(transformer.init_params(
            jax.random.PRNGKey(6), cfg))
        batch = shard(reference.make_batch(cell, 7, 4))
        losses = []
        for _ in range(4):
            params, opt, loss = step(params, opt, batch)
            losses.append(float(loss))
        assert all(map(math.isfinite, losses)) and losses[-1] < losses[0]
    finally:
        hvd.shutdown()


def test_default_config_is_the_program_it_was():
    """``TransformerConfig()`` is a pattern of one pair: a tuple of one
    stacked dict of attention + dense layers under one scan, with no loop
    over the head and nothing recomputed in it."""
    cfg = transformer.TransformerConfig()
    assert cfg.layer_pattern == (("attention", "dense"),)
    assert cfg.head_block == 0 and not cfg.remat
    small = transformer.TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128)
    params = transformer.init_params(jax.random.PRNGKey(0), small)
    assert sorted(params) == ["embed", "layers", "ln_f"]
    layers, = params["layers"]
    assert sorted(layers) == ["ln1", "ln2", "w1", "w2", "w3",
                              "wk", "wo", "wq", "wv"]
    tokens = np.random.default_rng(0).integers(0, 256, (4, 32), np.int32)
    batch = {"tokens": tokens, "targets": np.roll(tokens, -1, 1)}
    loss, _ = program_loss_and_grads(small, params, batch, (2, 2, 2))
    # The default's layers are drawn from fold_in(key, 0) as every
    # pattern's are, and the head multiplies bfloat16 operands on the CPU as
    # on the chip (5.919988632202148 before both); the dense SwiGLU makes
    # silu(a) * g in float32 and rounds it once (6.235895156860352 with it
    # rounded in bfloat16 on the way; 6.2355070 in float32 throughout).
    assert abs(float(loss) - 6.236451625823975) < 1e-5
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                             ("dp", "sp", "tp"))
    specs = transformer.param_specs(small)

    def traced(fn, out_specs):
        return str(jax.make_jaxpr(jax.shard_map(
            fn, mesh=mesh,
            in_specs=(specs, {k: P("dp", "sp") for k in batch}),
            out_specs=out_specs))(params, batch))

    logits = traced(lambda p, b: transformer.forward(p, b["tokens"], small)[0],
                    P("dp", "sp", "tp"))
    grads = traced(jax.grad(lambda p, b: transformer.loss_fn(p, b, small)),
                   specs)
    # one scan forward, one more for the backward pass, nothing recomputed
    assert logits.count("scan[") == 1 and grads.count("scan[") == 2
    assert "checkpoint" not in logits + grads


def test_a_pattern_refuses_what_it_cannot_run():
    with pytest.raises(ValueError, match="layer_pattern"):
        transformer.TransformerConfig(layer_pattern=(("rope", "dense"),))
    with pytest.raises(ValueError, match="needs its configuration"):
        transformer.TransformerConfig(
            layer_pattern=(("linear_attention", "dense"),))
    with pytest.raises(ValueError, match="whole number of periods"):
        transformer.TransformerConfig(
            n_layers=3, layer_pattern=(("attention", "dense"),) * 2)


# -- the delta rule ------------------------------------------------------------

def delta_rule_inputs(key, decay, b=2, s=64, h=3, d=16):
    ks = jax.random.split(key, 5)
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in
            (jax.random.normal(kk, (b, s, h, d)) for kk in ks[:2]))
    v = jax.random.normal(ks[2], (b, s, h, d))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, s, h, d)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)) + 1.0)
    return q, k, v, g, beta


# (heads, head size, chunk): heads of 16 take the XLA form; heads of 128
# take the kernels (interpreted here), two heads a grid step or one.
FORMS = {"xla": (3, 16, 16), "kernels": (2, 128, 16),
         "kernels, one head a step": (1, 128, 8)}


@pytest.mark.parametrize("segment", [2, 16])
@pytest.mark.parametrize("decay", [0.05, 3.0, 40.0])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_chunked_delta_rule_matches_the_recurrence(form, decay, segment):
    """Four chunks or more, in segments of two chunks or in one, beta up
    to 2, decays from next to none to e^-40 a step (``exp(-cumsum)`` would
    overflow inside one chunk): output and the gradient of every input,
    in the XLA form and through the kernels."""
    h, d, chunk = FORMS[form]
    args = delta_rule_inputs(jax.random.PRNGKey(int(decay)), decay, h=h, d=d)
    assert float(args[4].max()) > 1.5
    assert kda_kernels.takes(d, d, chunk) == (form != "xla")

    def plain(*a):
        return jax.vmap(reference.kda_recurrence)(*a)

    def chunked(*a):
        return kda_chunked(*a, chunk, segment=segment)

    out, want = chunked(*args), plain(*args)
    assert float(jnp.abs(out - want).max()) < 1e-5
    weight = jax.random.normal(jax.random.PRNGKey(9), out.shape)
    grads = jax.grad(lambda *a: (chunked(*a) * weight).sum(),
                     argnums=range(5))(*args)
    wants = jax.grad(lambda *a: (plain(*a) * weight).sum(),
                     argnums=range(5))(*args)
    for g, w in zip(grads, wants):
        assert bool(jnp.isfinite(g).all())
        assert float(jnp.abs(g - w).max()) < 1e-4 * float(jnp.abs(w).max())


@pytest.mark.parametrize("decay", [0.05, 3.0, 40.0])
def test_delta_rule_kernels_match_the_xla_form(decay):
    """The same inputs down both forms, bfloat16 values as the mixer
    hands them over: output and gradients agree to float32 rounding."""
    q, k, v, g, beta = delta_rule_inputs(jax.random.PRNGKey(7), decay, b=1,
                                         h=2, d=128)
    args = (q, k, v.astype(jnp.bfloat16), g, beta)
    weight = jax.random.normal(jax.random.PRNGKey(8), q.shape)

    def both(form):
        def loss(*a):
            out = form(*a, 16, segment=2)
            return (out * weight).sum(), out
        grads, out = jax.grad(loss, argnums=range(5), has_aux=True)(*args)
        return (out,) + grads

    for got, want in zip(both(kda_chunked), both(kda_chunked_xla)):
        assert got.dtype == want.dtype
        close = 1e-5 if want.dtype == jnp.float32 else 1e-2    # v's: bf16
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        assert float(jnp.abs(got - want).max()) \
            < close * float(jnp.abs(want).max())


@pytest.mark.parametrize("head,chunk,kernels", [
    (16, 16, False), (128, 16, True), (256, 64, True), (128, 4, False),
    (128, 24, False), (64, 64, False)])
def test_shapes_choose_the_delta_rules_form(head, chunk, kernels):
    """Heads that fill the 128 lanes and a chunk that halves down to
    single rows take the kernels; anything else the XLA form.  No option
    chooses."""
    assert kda_kernels.takes(head, head, chunk) == kernels
    args = delta_rule_inputs(jax.random.PRNGKey(0), 1.0, b=1, s=2 * chunk,
                             h=1, d=head)
    jaxpr = str(jax.make_jaxpr(lambda *a: kda_chunked(*a, chunk))(*args))
    assert ("hvd_kda_fwd" in jaxpr) == kernels


@pytest.mark.parametrize("head", [16, 128])
def test_delta_rule_refuses_a_ragged_sequence(head):
    args = delta_rule_inputs(jax.random.PRNGKey(0), 1.0, s=40, h=1, d=head)
    with pytest.raises(ValueError, match="not a multiple"):
        kda_chunked(*args, 16)


def test_linear_mixer_head_shares_add_up_to_the_uncut_mixer():
    cfg = KdaConfig(n_heads=4, head_size=16, gate_rank=16, chunk=16)
    lp = jax.tree.map(lambda w: w[0], init_kda_params(
        jax.random.PRNGKey(1), 64, cfg, 1, jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64))
    whole = linear_attention_block(x, lp, cfg)
    half = KdaConfig(n_heads=2, head_size=16, gate_rank=16, chunk=16)

    def share(at):
        cols = slice(at * half.width, (at + 1) * half.width)
        heads = slice(at * 2, (at + 1) * 2)
        part = {name: w[..., cols] for name, w in lp.items()
                if name in ("wq", "wk", "wv", "conv_q", "conv_k", "conv_v",
                            "w_fb", "w_gb", "decay_bias")}
        part.update(w_fa=lp["w_fa"], w_ga=lp["w_ga"], o_norm=lp["o_norm"],
                    w_beta=lp["w_beta"][:, heads], a_log=lp["a_log"][heads],
                    wo=lp["wo"][cols])
        return linear_attention_block(x, part, half)

    assert float(jnp.abs(share(0) + share(1) - whole).max()) \
        < 1e-5 * float(jnp.abs(whole).max())


# -- the expert layer ------------------------------------------------------------

def masked_loop(params, x, share, shared=True):
    """The share's part of the layer with no sort and no blocks: every
    held expert over every token under a mask."""
    hi = lax.Precision.HIGHEST
    scores = jax.nn.sigmoid(jnp.dot(x, params["router"], precision=hi))
    _, ids = lax.top_k(scores + params["router_bias"], share.top_k)
    top = jnp.take_along_axis(scores, ids, -1)
    weights = top / top.sum(-1, keepdims=True) * share.routed_scaling

    def swiglu(w1, w3, w2):
        return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2

    y = swiglu(params["ws1"], params["ws3"], params["ws2"]) if shared \
        else jnp.zeros_like(x)
    for j in range(share.count):
        w_j = jnp.where(ids == share.first + j, weights, 0.0).sum(-1)
        y = y + w_j[:, None] * swiglu(params["we1"][j], params["we3"][j],
                                      params["we2"][j])
    return y, (ids[..., None] == jnp.arange(share.n_experts)).sum((0, 1))


def expert_layer(routing, tokens=96):
    share = ExpertShare(n_experts=8, first=2, count=3, top_k=3, d_model=32,
                        d_ff=24, d_shared=24, block_rows=16)
    params = jax.tree.map(lambda w: w[0], init_expert_share_params(
        jax.random.PRNGKey(3), share, 1))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (tokens, 32)))
    pull = jnp.zeros((8,))
    if routing == "every token picks only held experts":
        pull = pull.at[2:5].set(1.0)
    elif routing == "one expert gets every token":
        pull = pull.at[3].set(1.0)
    elif routing == "the balancing bias chooses":
        # scores lie in (0, 1): a bias of 2 wins whatever the scores are,
        # and the chosen experts' weights are still their scores'
        params["router_bias"] = jnp.zeros((8,)).at[jnp.array([0, 4, 7])].set(
            2.0)
    elif routing == "part-empty blocks beside full ones, the last pair held":
        # Expert 3 takes every token (six full blocks of 16), expert 4 full
        # blocks and a part-empty one, and the last token's last choice is
        # expert 4: the flattened pairs end in a held one, which is where a
        # padded row aimed at the nearest pair in range would land.
        pull = pull.at[3].set(1.0)
        x = x.at[-1].set(0.0).at[-1, 0].set(1.0)
        params["router"] = params["router"].at[0].set(
            jnp.array([-9.0, -9.0, -9.0, 0.0, -3.0, -9.0, -9.0, 2.0]))
    # x is positive, so a column raised by a constant wins every token.
    params["router"] = params["router"] + pull[None, :]
    return share, params, x


@pytest.mark.parametrize("routing", [
    "uniform", "every token picks only held experts",
    "one expert gets every token", "the balancing bias chooses",
    "part-empty blocks beside full ones, the last pair held"])
def test_expert_share_matches_the_masked_loop(routing):
    share, params, x = expert_layer(routing)
    y, loads = jax.jit(lambda p, x: expert_share_ffn(p, x, share))(params, x)
    want, want_loads = masked_loop(params, x, share)
    assert loads.tolist() == want_loads.tolist()
    assert int(loads.sum()) == share.top_k * x.shape[0]
    held = loads[share.first:share.first + share.count]
    if routing == "every token picks only held experts":
        assert int(held.sum()) == share.top_k * x.shape[0]     # no drop
    elif routing.startswith("part-empty blocks"):
        _, ids = moe.route(x, params["router"], params["router_bias"], share)
        assert int(ids[-1, -1]) == 4
        rows = share.block_rows
        assert int(held[1]) == x.shape[0] and x.shape[0] % rows == 0
        assert int(held[2]) > rows and int(held[2]) % rows
    elif routing == "one expert gets every token":
        assert int(held[1]) == x.shape[0]
    elif routing == "the balancing bias chooses":
        assert loads.tolist() == [x.shape[0] if e in (0, 4, 7) else 0
                                  for e in range(8)]
    else:
        assert 0 < int(held.sum()) < share.top_k * x.shape[0]
    assert float(jnp.abs(y - want).max()) < 1e-5 * float(jnp.abs(want).max())
    weight = jax.random.normal(jax.random.PRNGKey(5), y.shape)
    grads = jax.grad(lambda p, x: (expert_share_ffn(p, x, share)[0]
                                   * weight).sum(), argnums=(0, 1))(params, x)
    wants = jax.grad(lambda p, x: (masked_loop(p, x, share)[0]
                                   * weight).sum(), argnums=(0, 1))(params, x)
    assert worst(grads, wants) < 1e-4


@pytest.mark.parametrize("ties", [
    "no ties", "repeated columns", "every column the same",
    "a bias that ties two experts"])
def test_the_choice_is_top_ks_in_its_order(ties):
    """Eight maxima instead of a sort: ``lax.top_k``'s ids in its order,
    the first index on a tie."""
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(11),
                                              (64, 20)))
    bias = jnp.zeros((20,))
    if ties == "repeated columns":
        scores = scores.at[:, 7].set(scores[:, 2]).at[:, 19].set(scores[:, 2])
    elif ties == "every column the same":
        scores = jnp.broadcast_to(scores[:, :1], scores.shape)
    elif ties == "a bias that ties two experts":
        scores = scores.at[:, 5].set(0.25).at[:, 13].set(0.75)
        bias = bias.at[5].set(2.5).at[13].set(2.0)
    want = lax.top_k(scores + bias, 6)[1]
    got = jax.jit(lambda v: moe._top_k_ids(v, 6))(scores + bias)
    assert got.dtype == jnp.int32 and got.tolist() == want.tolist()
    if ties == "a bias that ties two experts":
        assert got[:, :2].tolist() == [[5, 13]] * 64
    # ... and through the router: the same experts with the same weights.
    share = ExpertShare(n_experts=20, first=0, count=20, top_k=6, d_model=20,
                        d_ff=8, d_shared=0)
    logits = jnp.log(scores) - jnp.log1p(-scores)
    weights, ids = moe.route(logits, jnp.eye(20), bias, share)
    top = jnp.take_along_axis(jax.nn.sigmoid(logits), ids, -1)
    assert float(jnp.abs(weights - top / top.sum(-1, keepdims=True)).max()) \
        < 1e-6
    if ties != "a bias that ties two experts":     # sigmoid(logit) rounds
        assert ids.tolist() == want.tolist()


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_router_product_is_float32_at_highest(dtype):
    """Whatever the tokens' dtype, the scores are one float32 product at
    ``Precision.HIGHEST`` (of a bfloat16 ``x`` the TPU's compiler drops the
    passes that multiply zeros by itself; ``PERF.md``, PR 30): as exact as
    float64 to 1e-6, and bfloat16 tokens choose the experts and the weights
    their float32 copy would."""
    hi = lax.Precision.HIGHEST
    x = jax.random.normal(jax.random.PRNGKey(12), (256, 96)).astype(dtype)
    router = jax.random.normal(jax.random.PRNGKey(13), (96, 40)) / 9.0
    share = ExpertShare(n_experts=40, first=0, count=4, top_k=4, d_model=96,
                        d_ff=8, d_shared=0)
    bias = jnp.zeros((40,))
    jaxpr = jax.make_jaxpr(lambda x, w: moe.route(x, w, bias, share))(
        x, router).jaxpr
    dots = [e for e in _equations(jaxpr) if e[0] == "dot_general"]
    assert len(dots) == 1
    _, _, params, dtypes = dots[0]
    assert dtypes == [jnp.float32, jnp.float32]
    assert params["precision"] == (hi, hi)
    weights, ids = moe.route(x, router, bias, share)
    assert weights.dtype == jnp.float32
    exact = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                                  @ np.asarray(router, np.float64))))
    want = np.take_along_axis(exact, np.asarray(ids), -1)
    want = want / want.sum(-1, keepdims=True)
    assert np.abs(np.asarray(weights) - want).max() < 1e-6
    again, ids_again = moe.route(x.astype(jnp.float32), router, bias, share)
    assert ids.tolist() == ids_again.tolist()
    assert weights.tolist() == again.tolist()
    # ... and the gradients of both operands are the float32 product's.
    cot = jax.random.normal(jax.random.PRNGKey(14), weights.shape)
    grads = jax.grad(lambda x, w: (moe.route(x, w, bias, share)[0]
                                   * cot).sum(), argnums=(0, 1))(x, router)
    wants = jax.grad(
        lambda x, w: (moe.route(x.astype(jnp.float32), w, bias, share)[0]
                      * cot).sum(), argnums=(0, 1))(x, router)
    assert grads[0].dtype == x.dtype and grads[1].dtype == jnp.float32
    assert worst(jax.tree.map(lambda g: g.astype(jnp.float32), grads),
                 jax.tree.map(lambda g: g.astype(jnp.float32), wants)) < 1e-5


def _equations(jaxpr, recomputed=False, under="", found=None):
    """(primitive, name stack, params, input dtypes) of every equation,
    nested jaxprs included (an inner equation's name stack starts at the
    equation that holds it); with ``recomputed``, of those alone that sit
    inside a ``jax.checkpoint``'s equation: a layer run again in the
    backward pass."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        stack = "%s/%s" % (under, eqn.source_info.name_stack)
        if not recomputed:
            found.append((eqn.primitive.name, stack, eqn.params,
                          [v.aval.dtype for v in eqn.invars
                           if hasattr(v.aval, "dtype")]))
        inside = recomputed and eqn.primitive.name != "remat2"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _equations(inner, inside, stack, found)
    return found


def test_the_router_tells_the_compiler_what_it_knows():
    """The form of a pattern step, read from its jaxpr: the blocks' rows
    are written by the combine kernel, every scatter-add left under the
    router says that its indices are distinct (a block's pairs, an expert's
    slab), and a layer run again in the backward pass neither sorts nor
    chooses again (it kept the ids, the order and the loads)."""
    cell = small_cell(dtype="bfloat16")
    cfg = reference._model_config(cell)
    params = transformer.init_params(jax.random.PRNGKey(15), cfg)
    batch = reference.make_batch(cell, 16, 2)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                             (cfg.dp_axis, cfg.sp_axis, cfg.tp_axis))
    rows = {k: P(cfg.dp_axis, cfg.sp_axis) for k in batch}
    specs = transformer.param_specs(cfg)
    step = jax.shard_map(
        jax.value_and_grad(lambda p, b: transformer.loss_fn(p, b, cfg)),
        mesh=mesh, in_specs=(specs, rows), out_specs=(P(), specs))
    jaxpr = jax.make_jaxpr(step)(params, batch).jaxpr
    router = [e for e in _equations(jaxpr) if scopes.ROUTER in e[1]]
    adds = [e for e in router if e[0] == "scatter-add"]
    # The weights' gradient and the three weight slabs, backward.
    assert len(adds) >= 4
    assert all(e[2]["unique_indices"] for e in adds)
    # y's rows forward, dx's rows backward.
    writes = [e for e in router if e[0] == "pallas_call"]
    assert len(writes) >= 2
    assert all(scopes.ROUTER_ROWS in e[1] and moe_kernels.COMBINE in e[1]
               for e in writes)
    chosen = {"sort", "argmax", "top_k"}
    assert {"sort", "argmax"} <= {e[0] for e in router}
    again = [e for e in _equations(jaxpr, recomputed=True)
             if scopes.ROUTER in e[1]]
    assert any(e[0] == "dot_general" for e in again)    # the scores, again
    assert not [e for e in again if e[0] in chosen]


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four shares of two experts, the shared expert counted once, against
    all eight experts under one mask loop."""
    whole = ExpertShare(n_experts=8, first=0, count=8, top_k=2, d_model=32,
                        d_ff=24, d_shared=24, block_rows=16)
    params = jax.tree.map(lambda w: w[0], init_expert_share_params(
        jax.random.PRNGKey(6), whole, 1))
    x = jax.random.normal(jax.random.PRNGKey(7), (64, 32))
    want, _ = masked_loop(params, x, whole)
    total, seen = 0.0, 0
    for first in range(0, 8, 2):
        part = ExpertShare(n_experts=8, first=first, count=2, top_k=2,
                           d_model=32, d_ff=24,
                           d_shared=24 if first == 0 else 0, block_rows=16)
        held = dict(params, **{name: params[name][first:first + 2]
                               for name in ("we1", "we3", "we2")})
        y, loads = expert_share_ffn(held, x, part)
        total, seen = total + y, seen + int(loads[first:first + 2].sum())
    assert seen == 2 * x.shape[0]
    assert float(jnp.abs(total - want).max()) \
        < 1e-5 * float(jnp.abs(want).max())


def test_expert_share_refuses_ids_outside_the_layer():
    with pytest.raises(ValueError, match="not among"):
        ExpertShare(n_experts=8, first=7, count=2, top_k=2, d_model=8,
                    d_ff=8, d_shared=0)


def test_expert_share_refuses_more_choices_than_experts():
    with pytest.raises(ValueError, match="cannot choose 9 of 8"):
        ExpertShare(n_experts=8, first=0, count=2, top_k=9, d_model=8,
                    d_ff=8, d_shared=0)
