"""Nemotron-3-Nano's decoder (``models/transformer.py`` with a pattern of
blocks of ONE sub-layer: Mamba-2 state-space mixers, a NoPE softmax block,
a chip's share of two-matrix ``relu^2`` experts) against the plain float32
reference kept with the benchmark (``yardstick/builders/nemotron_h.py``:
the token-by-token recurrence, a dense loop over experts), at the cell's
tiny size on the CPU with seeded weights."""

import copy
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.common import scopes
from horovod_tpu.models import transformer
from horovod_tpu.models.state_space import (SsmConfig, init_ssm_params,
                                            ssd_chunked, state_space_block)
from horovod_tpu.models.transformer import SoftmaxAttention
from horovod_tpu.parallel.moe import (ExpertShare, expert_share_ffn,
                                      init_expert_share_params)
from tests.test_hybrid_decoder import program_loss_and_grads, worst
from yardstick import manifest as mf
from yardstick.builders import nemotron_h as reference

CELL = "nemotron-3-nano-30b-a3b.dp1-pt8k"


def small_cell(dtype="float32"):
    """The cell's files at their tiny size: hidden 64, nine blocks (M E M E
    M * E M E), 4 state-space heads of 16 in 2 groups with state 16 and
    chunks of 16, 4 query heads of 16 over 2 key/value heads, 4 of 8
    experts of 32 (2 a token) beside a shared one of 64, sequences of
    128."""
    cell = copy.deepcopy(mf.load().cell(CELL, tiny=True))
    cell["config"]["activation_dtype"] = dtype
    return cell


# -- the scan ----------------------------------------------------------------

def scan_inputs(seq, heads=4, head=16, groups=2, state=16):
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    return (jax.random.normal(ks[0], (2, seq, heads, head)),
            jax.nn.softplus(jax.random.normal(ks[1], (2, seq, heads))),
            -jnp.exp(jax.random.uniform(ks[2], (heads,), maxval=2.7)),
            jax.random.normal(ks[3], (2, seq, groups, state)),
            jax.random.normal(ks[4], (2, seq, groups, state)),
            jax.random.normal(ks[5], (heads,))), \
        jax.random.normal(ks[6], (2, seq, heads, head))


@pytest.mark.parametrize("chunk", [16, 32])
def test_the_chunked_scan_is_the_recurrence_values_and_gradients(chunk):
    """96 steps are six or three chunks: the chunk's own part, the carried
    state and the decay between chunks all take part.  float32 on the CPU:
    what is left is the order of the sums (the recurrence multiplies 96
    decays, the chunked form exponentiates their summed logarithms)."""
    args, weight = scan_inputs(96)

    def recurrence(*a):
        x, dt, a_, b, c, d = a
        return jax.vmap(reference.ssm_recurrence,
                        in_axes=(0, 0, None, 0, 0, None))(x, dt, a_, b, c, d)

    chunked = jax.jit(lambda *a: ssd_chunked(*a, chunk))
    want = jax.jit(recurrence)(*args)
    got = chunked(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(jnp.abs(want).max())
    every = tuple(range(6))             # x, dt, A, B, C, D
    grads = jax.jit(jax.grad(lambda *a: (chunked(*a) * weight).sum(),
                             every))(*args)
    ref_grads = jax.jit(jax.grad(lambda *a: (recurrence(*a) * weight).sum(),
                                 every))(*args)
    assert worst(grads, ref_grads) < 2e-5


def test_a_strong_decay_neither_overflows_nor_loses_the_near_steps():
    """``dt A`` of -40 a step: ``exp(-cum_s)`` alone would overflow within
    three steps; every exponent here is a difference taken first."""
    (x, dt, _, b, c, d), _ = scan_inputs(64)
    a = jnp.full((4,), -40.0)
    got = ssd_chunked(x, jnp.ones_like(dt), a, b, c, d, 16)
    assert bool(jnp.isfinite(got).all())
    # the state is gone after a step: y_t = x_t (B_t . C_t) + D x_t
    per = 2
    bc = jnp.repeat(jnp.sum(b * c, -1), per, axis=2)[..., None]
    want = x * bc + d[:, None] * x
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(jnp.abs(want).max())
    with pytest.raises(ValueError, match="chunks of 24"):
        ssd_chunked(x, dt, a, b, c, d, 24)


def test_the_mixer_is_the_references():
    cfg = SsmConfig(n_heads=4, head_size=16, n_groups=2, state_size=16,
                    chunk=16)
    lp = jax.tree.map(lambda w: w[0], init_ssm_params(
        jax.random.PRNGKey(1), 64, cfg, 1, jnp.float32))
    assert {k: v.shape for k, v in lp.items()} == {
        "in_proj": (64, 64 + 128 + 4), "conv_w": (4, 128), "conv_b": (128,),
        "dt_bias": (4,), "a_log": (4,), "d_skip": (4,), "ssm_norm": (64,),
        "out_proj": (64, 64)}
    # the published start: A in [-16, -1], dt in [1e-3, 1e-1], D = 1
    assert 0 <= float(lp["a_log"].min()) and float(lp["a_log"].max()) <= 2.78
    dt = jax.nn.softplus(lp["dt_bias"])
    assert 1e-3 <= float(dt.min()) and float(dt.max()) <= 1e-1 + 1e-6
    # a start that makes every part count
    lp = dict(lp, d_skip=lp["d_skip"] * 0.7, dt_bias=lp["dt_bias"] + 3.0,
              ssm_norm=1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                                     (64,)))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 96, 64))
    config = {"mamba_num_heads": 4, "mamba_head_dim": 16, "n_groups": 2,
              "ssm_state_size": 16, "layer_norm_epsilon": 1e-5}
    want = jax.vmap(lambda h: reference.reference_mixer(h, lp, config))(x)
    got = state_space_block(x, lp, cfg)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())
    for part in ("no_d_skip", "no_conv", "no_dt_bias", "norm_over_all"):
        wrong = jax.vmap(lambda h: reference.reference_mixer(
            h, lp, config, wrong=(part,)))(x)
        assert float(jnp.abs(got - wrong).max()) \
            > 1e-2 * float(jnp.abs(want).max()), part


# -- the expert layer --------------------------------------------------------

def relu2_share(n_experts=8, first=0, count=8, top_k=2, d_shared=64):
    return ExpertShare(n_experts=n_experts, first=first, count=count,
                       top_k=top_k, d_model=64, d_ff=32, d_shared=d_shared,
                       routed_scaling=2.5, block_rows=16, form="relu2")


def layer_config(share, first=0):
    return {"held": {"first_expert": first}, "mlp_hidden_act": "relu2",
            "n_group": 1, "norm_topk_prob": True,
            "num_experts_per_tok": share.top_k,
            "routed_scaling_factor": share.routed_scaling}


def test_two_matrix_experts_against_a_dense_loop_values_and_gradients():
    """4 of 8 experts held from id 2; the correction bias sends every token
    to expert 3 (96 rows: six blocks of 16) and none to expert 4."""
    share = relu2_share(first=2, count=4)
    params = jax.tree.map(lambda w: w[0], init_expert_share_params(
        jax.random.PRNGKey(6), share, 1))
    assert sorted(params) == ["router", "router_bias", "we1", "we2", "ws1",
                              "ws2"]
    assert params["we1"].shape == (4, 64, 32) \
        and params["ws2"].shape == (64, 64)
    params["router_bias"] = params["router_bias"].at[3].set(10.0) \
        .at[4].set(-10.0)
    x = jax.random.normal(jax.random.PRNGKey(7), (96, 64))
    config = layer_config(share, first=2)
    y, loads = expert_share_ffn(params, x, share)
    want, want_loads, _ = reference.reference_expert_layer(x, params, config)
    assert jnp.array_equal(loads, want_loads)
    assert int(loads[3]) == 96 and int(loads[4]) == 0
    assert float(jnp.abs(y - want).max()) < 1e-5 * float(jnp.abs(want).max())
    weight = jax.random.normal(jax.random.PRNGKey(8), y.shape)
    grads = jax.jit(jax.grad(lambda p, x: (expert_share_ffn(p, x, share)[0]
                                           * weight).sum(), (0, 1)))(params, x)
    ref_grads = jax.jit(jax.grad(lambda p, x: (
        reference.reference_expert_layer(x, p, config)[0] * weight).sum(),
        (0, 1)))(params, x)
    assert float(jnp.abs(grads[0]["router_bias"]).max()) == 0
    assert float(jnp.abs(grads[0]["we1"][2]).max()) == 0    # expert 4: idle
    assert float(jnp.abs(grads[0]["we1"][1]).max()) > 0
    assert worst(grads, ref_grads) < 1e-4
    # relu for relu^2 is another layer
    wrong = reference.reference_expert_layer(x, params, config,
                                             wrong=("relu",))[0]
    assert float(jnp.abs(wrong - want).max()) \
        > 0.1 * float(jnp.abs(want).max())


@pytest.mark.parametrize("n_experts, count, top_k", [(8, 4, 2), (128, 8, 6)])
def test_expert_shares_add_up_to_the_uncut_references_layer(n_experts, count,
                                                            top_k):
    """The 2 (tiny) or 16 (the deployment's) shares' routed parts, the
    shared expert counted once, against the reference's layer with every
    expert held."""
    whole = relu2_share(n_experts, 0, n_experts, top_k)
    params = jax.tree.map(lambda w: w[0], init_expert_share_params(
        jax.random.PRNGKey(6), whole, 1))
    x = jax.random.normal(jax.random.PRNGKey(7), (96, 64))
    want, want_loads, _ = reference.reference_expert_layer(
        x, params, layer_config(whole))
    total, seen = 0.0, 0
    for first in range(0, n_experts, count):
        part = relu2_share(n_experts, first, count, top_k,
                           d_shared=64 if first == 0 else 0)
        held = dict(params, **{name: params[name][first:first + count]
                               for name in ("we1", "we2")})
        y, loads = expert_share_ffn(held, x, part)
        assert jnp.array_equal(loads, want_loads)
        total, seen = total + y, seen + int(loads[first:first + count].sum())
    assert seen == top_k * x.shape[0]
    assert float(jnp.abs(total - want).max()) \
        < 1e-5 * float(jnp.abs(want).max())


# -- the whole model ---------------------------------------------------------

def test_the_builder_reads_the_nine_letters_off_the_source():
    cell = small_cell()
    assert mf.load().cell(CELL)["config"]["hybrid_override_pattern"] \
        == "MEMEM*EME" == cell["config"]["hybrid_override_pattern"]
    cfg = reference._model_config(cell)
    star = SoftmaxAttention(4, 2, None, None, False)
    assert cfg.layer_pattern == tuple(
        {"M": ("state_space", None), "E": (None, "expert_share"),
         "*": (star, None)}[kind] for kind in "MEMEM*EME")
    assert cfg.n_layers == 9 and not cfg.leading_layers
    assert cfg.experts.form == "relu2" and cfg.experts.names("ws") \
        == ("ws1", "ws2")
    assert (cfg.state_space.n_heads, cfg.state_space.n_groups,
            cfg.state_space.chunk) == (4, 2, 16)
    full = reference._model_config(mf.load().cell(CELL))
    assert (full.state_space.width, full.state_space.conv_width,
            full.state_space.chunk) == (4096, 6144, 128)
    with pytest.raises(ValueError, match="does not name 9 blocks"):
        reference._pattern(dict(cell["config"],
                                hybrid_override_pattern="MEMEM*EM"))
    with pytest.raises(ValueError, match="does not name 9 blocks"):
        reference._pattern(dict(cell["config"],
                                hybrid_override_pattern="MEMEM-EME"))


def test_a_block_of_one_sub_layer_has_one_norm():
    cfg = reference._model_config(small_cell())
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    specs = transformer.param_specs(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda s: isinstance(s, P))
    for kind, lp in zip("MEMEM*EME", params["layers"]):
        norms = sorted(name for name in lp if name.startswith("ln"))
        assert norms == (["ln2"] if kind == "E" else ["ln1"]), kind
        assert ("in_proj" in lp, "wq" in lp, "router" in lp) \
            == (kind == "M", kind == "*", kind == "E")
    assert sorted(params["layers"][5]) == ["ln1", "wk", "wo", "wq", "wv"]
    assert "we3" not in params["layers"][1] \
        and "ws3" not in params["layers"][1]
    # a pair stays a pair
    pair = transformer._init_layers(jax.random.PRNGKey(0),
                                    transformer.TransformerConfig(),
                                    "attention", "dense", 2)
    assert sorted(pair) == ["ln1", "ln2", "w1", "w2", "w3", "wk", "wo", "wq",
                            "wv"]


def test_program_matches_reference_loss_and_every_gradient():
    """float32 on the CPU: the differences left are the order of the sums
    (the chunked scan against 128 steps of the recurrence, the sorted
    blocks against the dense loop)."""
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
    # embed, head, ln_f; four M blocks of 9 leaves, four E blocks of 7, 5
    assert len(flat) == len(ref_flat) == 3 + 4 * 9 + 4 * 7 + 5
    for (path, g), r in zip(flat, ref_flat):
        if path[-1].key == "router_bias":     # chooses experts, no gradient
            assert float(jnp.abs(g).max()) == float(jnp.abs(r).max()) == 0
            continue
        assert float(jnp.abs(r).max()) > 0, path      # every leaf is used
        assert float(jnp.abs(g - r).max()) \
            < 2e-4 * float(jnp.abs(r).max()), path


def test_bf16_activations_stay_near_the_reference():
    cell = small_cell("bfloat16")
    cfg = reference._model_config(cell)
    params = transformer.init_params(jax.random.PRNGKey(2), cfg)
    batch = reference.make_batch(cell, 3, 2)
    loss, _ = program_loss_and_grads(cfg, params, batch)
    ref_loss = reference.reference_loss(params, batch, cell["config"])
    assert abs(float(loss) - ref_loss) < 5e-3 * ref_loss


@pytest.fixture(scope="module")
def prepared():
    """The tiny cell as the builder sets it before the first step: the
    correction biases fitted to the cell's load profile and the head fitted
    to the batch, both by the reference (``prepare``)."""
    cell = small_cell()
    cfg = reference._model_config(cell)
    batch = reference.make_batch(cell, 9, 2)
    params, loss_ref, loads = reference.prepare(
        transformer.init_params(jax.random.PRNGKey(8), cfg),
        batch["tokens"], batch["targets"], cell)
    return cell, cfg, batch, params, loss_ref, loads


def test_the_fitted_state_is_the_cells_and_the_program_reads_it(prepared):
    cell, cfg, batch, params, loss_ref, loads = prepared
    goal = reference.load_targets(cell, batch["tokens"].size)
    assert goal[:4].round(1).tolist() == [96.0, 76.8, 51.2, 32.0] \
        and goal.sum() == pytest.approx(2 * 256)
    assert loads.shape == (4, 8) and np.abs(loads - goal).max() <= 2.6
    for kind, lp in zip("MEMEM*EME", params["layers"]):
        assert ("router_bias" in lp) == (kind == "E")
        if kind == "E":
            assert lp["router_bias"].shape == (1, 8) \
                and float(jnp.abs(lp["router_bias"]).max()) > 0
    loss, _ = program_loss_and_grads(cfg, params, batch)
    assert abs(float(loss) - loss_ref) < 2e-5 * loss_ref


@pytest.mark.parametrize("part", reference.WRONG)
def test_the_fitted_head_tells_a_part_left_out_or_wrong(prepared, part):
    """Under the head fitted to its batch the loss reads the mean squared
    angle between the hidden states compared: the reference with one part
    left out or wrong is far outside the tolerance the cell is held to."""
    cell, _, batch, params, loss_ref, _ = prepared
    wrong = reference.reference_loss_fn(
        params, batch["tokens"], batch["targets"], cell["config"],
        wrong=(part,))
    assert float(wrong) - loss_ref > 10 * reference.LOSS_RTOL * loss_ref


def test_nine_blocks_train_and_the_loss_falls():
    import optax

    import horovod_tpu.jax as hvd
    hvd.init()
    try:
        cell = small_cell("bfloat16")
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


# -- the scopes, and the programs that must not change ------------------------

def lowered(cell_name, builder, debug_info=False):
    """The StableHLO of the loss and its gradients at a cell's tiny size
    over one device."""
    import importlib
    cell = mf.load().cell(cell_name, tiny=True)
    cfg = importlib.import_module(
        "yardstick.builders." + builder)._model_config(cell)
    params = jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                             (cfg.dp_axis, cfg.sp_axis, cfg.tp_axis))
    batch = {k: jax.ShapeDtypeStruct((2, cell["spec"]["seq_len"]), jnp.int32)
             for k in ("tokens", "targets")}
    rows = {k: P(cfg.dp_axis, cfg.sp_axis) for k in batch}
    specs = transformer.param_specs(cfg)
    fn = jax.jit(jax.shard_map(
        jax.value_and_grad(lambda p, b: transformer.loss_fn(p, b, cfg)),
        mesh=mesh, in_specs=(specs, rows), out_specs=(P(), specs),
        check_vma=True))
    return fn.lower(params, batch).as_text(debug_info=debug_info)


def test_the_scan_and_the_mixer_carry_their_scopes():
    lines = lowered(CELL, "nemotron_h", debug_info=True).splitlines()
    core = [ln for ln in lines if scopes.SSD_CORE in ln]
    assert core and all(scopes.STATE_SPACE in ln for ln in core)
    # projections, convolution and gated norm: the mixer's, not the scan's
    assert any(scopes.STATE_SPACE in ln and scopes.SSD_CORE not in ln
               and "dot_general" in ln for ln in lines)
    assert any("/exp\"" in ln for ln in core) \
        and any("(cumsum)" in ln for ln in core)
    # heads of 16 and a state of 16 take the XLA form: no kernel's scope
    # (tests/test_ssd_kernels.py has the shapes that take the kernels)
    assert not any(scopes.SSD_FWD in ln or scopes.SSD_BWD in ln
                   for ln in lines)
    # the other blocks keep theirs
    for scope in (scopes.ATTENTION, scopes.MOE, scopes.ROUTER,
                  scopes.ROUTER_ROWS, scopes.EXPERTS, scopes.SHARED_EXPERT,
                  scopes.HEAD):
        assert any(scope in ln for ln in lines), scope
        assert not any(scope in ln and scopes.STATE_SPACE in ln
                       for ln in lines), scope


# sha256 of ``lowered(...)`` at PR 32 (commit dea7093), before the expert's
# form, the blocks of one sub-layer and the state-space mixer: under
# ``form="swiglu"`` and patterns of pairs the traced programs are the
# parent's to the letter.  A PR that changes those programs on purpose
# computes these again (``hashlib.sha256(lowered(cell, builder).encode())``).
# ``nemotron``'s own is of PR 36 (commit d259f55), before the latent-attention
# mixer and the flash kernels' second head size.  ``laguna``'s is of the dense
# SwiGLU's split form (``models/transformer.py: _dense_ffn``), which its
# leading layer runs.
PARENTS = {
    ("nemotron-3-nano-30b-a3b.dp1-pt8k", "nemotron_h"):
        "e2652c33ba96cd0fbf2e58291c393e635ba99544018c9dbaffc82b9148a0f361",
    ("solar-open2-250b.dp1-pt8k", "solar_open2"):
        "5fcd1a630d4b705efe90181c0306daf9c57773270250aaadb55cdeaef2911a1a",
    ("laguna-xs2.dp1-pt8k", "laguna"):
        "2d7db3af3af4a262235e09b5010631c5227acc2ad26f732b72a80472ad768194",
}


@pytest.mark.parametrize("cell, builder", sorted(PARENTS))
def test_a_swiglu_pair_pattern_lowers_to_the_parents_text(cell, builder):
    text = lowered(cell, builder)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PARENTS[cell, builder]


# -- what cannot run ---------------------------------------------------------

@pytest.mark.parametrize("build, match", [
    (lambda: transformer.TransformerConfig(layer_pattern=((None, None),)),
     "layer_pattern"),
    (lambda: transformer.TransformerConfig(
        layer_pattern=(("state_space", None),)), "needs its configuration"),
    (lambda: transformer.TransformerConfig(
        layer_pattern=((None, "expert_share"),)), "needs its configuration"),
    (lambda: transformer.TransformerConfig(
        layer_pattern=((None, "swiglu"),)), "layer_pattern"),
    (lambda: transformer.TransformerConfig(
        n_layers=4, layer_pattern=(("attention", None), (None, "dense"),
                                   ("attention", None))), "whole number"),
    (lambda: ExpertShare(8, 0, 8, 2, 64, 32, 64, form="gelu"),
     "an expert is one of"),
    (lambda: SsmConfig(n_heads=6, n_groups=4), "do not divide"),
    (lambda: SsmConfig(n_heads=4, n_groups=0), "do not divide"),
])
def test_a_pattern_refuses_what_it_cannot_run(build, match):
    with pytest.raises(ValueError, match=match):
        build()


@pytest.mark.parametrize("shape, match", [
    ((1, 2, 1), "state-space layer keeps a state"),
    ((1, 1, 2), "heads are not split"),
])
def test_a_state_space_layer_refuses_a_split_it_cannot_carry(shape, match):
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=4, n_kv_heads=2,
        layer_pattern=(("state_space", None),),
        state_space=SsmConfig(n_heads=4, head_size=8, n_groups=2,
                              state_size=8, chunk=8))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]).reshape(shape),
                             ("dp", "sp", "tp"))
    fn = jax.shard_map(
        lambda p, t: transformer.hidden(p, t, cfg)[0], mesh=mesh,
        in_specs=(transformer.param_specs(cfg), P("dp", "sp")),
        out_specs=P("dp", "sp", None))
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(fn, params, jnp.zeros((2, 32), jnp.int32))
