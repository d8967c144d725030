"""Laguna-XS.2's decoder (``models/transformer.py`` with a leading layer
and a pattern of ``SoftmaxAttention`` kinds: full and sliding-window layers
with their own head counts and rotary tables, an output gate, a dense layer
ahead of a chip's share of small experts) against the plain float32
reference kept with the benchmark (``yardstick/builders/laguna.py``), at
the cell's tiny size on the CPU with seeded weights."""

import copy
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.common import scopes
from horovod_tpu.models import transformer
from horovod_tpu.models.transformer import Rope, SoftmaxAttention
from horovod_tpu.parallel.moe import ExpertShare, expert_share_ffn
from tests.test_hybrid_decoder import program_loss_and_grads
from yardstick import manifest as mf
from yardstick.builders import laguna as reference

CELL = "laguna-xs2.dp1-pt8k"


def small_cell(dtype="float32"):
    """The cell's files at their tiny size: hidden 64, heads of 16 (6 in a
    full layer, 8 in a sliding one, over 2 key/value heads), window 32 in
    sequences of 128, a dense layer of 128 and then four sparse ones with
    4 of 8 experts of 32, 2 a token."""
    cell = copy.deepcopy(mf.load().cell(CELL, tiny=True))
    cell["config"]["activation_dtype"] = dtype
    return cell


# -- the whole model ---------------------------------------------------------

def test_the_builder_reads_the_pattern_off_the_source():
    cfg = reference._model_config(small_cell())
    (lead, lead_ffn), = cfg.leading_layers
    assert lead_ffn == "dense" and lead.window is None and lead.n_heads == 6
    assert [ffn for _, ffn in cfg.layer_pattern] == ["expert_share"] * 4
    assert [(k.n_heads, k.n_kv_heads, k.window, k.gate)
            for k, _ in cfg.layer_pattern] \
        == [(8, 2, 32, True)] * 3 + [(6, 2, None, True)]
    sliding, full = cfg.layer_pattern[0][0].rope, cfg.layer_pattern[3][0].rope
    assert (sliding.theta, sliding.share, sliding.factor) == (10000, 1, 1.0)
    assert (full.theta, full.share, full.factor) == (500000, 0.5, 4)
    assert lead.rope == full and cfg.n_layers == 5
    # the published lists: a dense layer, then whole periods of four
    published = dict(small_cell()["config"], num_hidden_layers=9)
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        published[key] = published[key] + published[key][1:]
    assert [len(part) for part in reference._split(published)[:2]] == [1, 4]
    assert reference._split(published)[2] == 2


def test_program_matches_reference_loss_and_every_gradient():
    cell = small_cell()
    cfg = reference._model_config(cell)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    assert sorted(params) == ["embed", "head", "layers", "leading", "ln_f"]
    batch = reference.make_batch(cell, 1, 2)
    loss, grads = program_loss_and_grads(cfg, params, batch)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.reference_loss_fn(
            p, batch["tokens"], batch["targets"], cell["config"])))(params)
    assert abs(float(loss) - float(ref_loss)) < 2e-5 * float(ref_loss)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref_flat = jax.tree.leaves(ref_grads)
    # embed, head, ln_f; four sparse layers of 15 leaves; the dense one's 10
    assert len(flat) == len(ref_flat) == 3 + 4 * 15 + 10
    for (path, g), r in zip(flat, ref_flat):
        if path[-1].key == "router_bias":     # chooses experts, no gradient
            assert float(jnp.abs(g).max()) == float(jnp.abs(r).max()) == 0
            continue
        assert float(jnp.abs(r).max()) > 0, path      # every leaf is used
        assert float(jnp.abs(g - r).max()) \
            < 1e-4 * float(jnp.abs(r).max()), path


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
    balancing buffers fitted to the cell's load profile and the head
    fitted to the batch, both by the reference (``prepare``)."""
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
    assert goal[:4].tolist() == [96.0, 32.0, 96.0, 32.0] \
        and goal.sum() == 2 * 256
    assert loads.shape == (4, 8) and np.abs(loads - goal).max() <= 2
    # the dense layer has no buffer; the four sparse layers got theirs
    assert "router_bias" not in params["leading"][0]
    assert all(float(jnp.abs(lp["router_bias"]).max()) > 0
               for lp in params["layers"])
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


def test_a_leading_layer_and_a_period_train_and_the_loss_falls():
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
        host = transformer.init_params(jax.random.PRNGKey(6), cfg)
        before = jax.device_get(host["leading"])    # the step donates
        step, params, opt = build(host)
        batch = shard(reference.make_batch(cell, 7, 4))
        losses = []
        for _ in range(4):
            params, opt, loss = step(params, opt, batch)
            losses.append(float(loss))
        assert all(map(math.isfinite, losses)) and losses[-1] < losses[0]
        # the leading layer's own parameters moved with the rest
        moved = jax.tree.map(lambda a, b: float(np.abs(a - b).max()),
                             before, jax.device_get(params["leading"]))
        assert min(jax.tree.leaves(moved)) > 0
    finally:
        hvd.shutdown()


# -- the rotary tables -------------------------------------------------------

def by_hand(position, pair, rotary, theta, yarn=None):
    """cos and sin of one rotary pair at one position, from the published
    formulas in plain Python."""
    inv_freq = theta ** (-2.0 * pair / rotary)
    scale = 1.0
    if yarn:
        factor, span, fast, slow, scale = yarn

        def dimension(turns):
            return rotary * math.log(span / (turns * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(dimension(fast)), 0)
        high = min(math.ceil(dimension(slow)), rotary - 1)
        ramp = min(max((pair - low) / (high - low), 0.0), 1.0)
        inv_freq = inv_freq / factor * ramp + inv_freq * (1 - ramp)
    return (math.cos(position * inv_freq) * scale,
            math.sin(position * inv_freq) * scale)


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_the_rotary_tables_against_values_by_hand(kind):
    """The published tables at a few positions and pairs: a full layer's
    YaRN over the first 64 of 128 dimensions (correction dimensions 5 and
    16: 64 ln(4096 / (64 x 2 pi)) / (2 ln 500,000) = 5.66 and, for one
    turn, 15.8; pairs up to 5 keep theta 500,000's frequency, pairs from
    16 on a 64th of it), a sliding layer's plain table over all 128."""
    group = mf.load().cell(CELL)["config"]["rope_parameters"][kind]
    yarn = None
    if kind == "full_attention":
        rope = Rope(theta=500000, share=0.5, factor=64, original_max_seq=4096,
                    beta_fast=64, beta_slow=1,
                    attention_factor=1.4158883083359672)
        yarn = (64, 4096, 64, 1, 0.1 * math.log(64) + 1)
        ramp = transformer.yarn_ramp(rope, 64)
        assert ramp[:6].max() == 0 and ramp[16:].min() == 1
        assert 0 < ramp[6] < ramp[15] < 1
    else:
        rope = Rope(theta=10000, share=1)
    rotary = int(128 * rope.share)
    positions = np.array([0, 1, 2, 511, 512, 4095, 8191])
    cos, sin = transformer.rope_tables(jnp.asarray(positions), 128, rope,
                                       jnp.float32)
    ref_cos, ref_sin = reference.rotary_table(group, 128, positions)
    assert cos.shape == sin.shape == (1, 7, 1, rotary // 2)
    for at, position in enumerate(positions):
        for pair in (0, 1, 5, 6, 10, 15, 16, 31, rotary // 2 - 1):
            want = by_hand(int(position), pair, rotary, rope.theta, yarn)
            # float32 angles of up to 8191 radians: 1e-3 of a turn
            for got in ((cos[0, at, 0, pair], sin[0, at, 0, pair]),
                        (ref_cos[at, pair], ref_sin[at, pair])):
                assert abs(float(got[0]) - want[0]) < 2e-3, (position, pair)
                assert abs(float(got[1]) - want[1]) < 2e-3, (position, pair)


def test_half_a_head_turns_and_the_rest_passes():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 3, 16))
    cos, sin = transformer.rope_tables(jnp.arange(4), 16,
                                       Rope(theta=100.0, share=0.5),
                                       jnp.float32)
    out = transformer._rope(cos, sin, x)
    assert cos.shape[-1] == 4
    assert jnp.array_equal(out[..., 8:], x[..., 8:])
    assert jnp.array_equal(out[:, 0], x[:, 0])        # position 0: no turn
    # pair i is dimensions (i, i + 4) of the first eight
    turned = transformer._rope(cos, sin, x[..., :8])
    assert jnp.array_equal(out[..., :8], turned)
    assert float(jnp.abs(jnp.linalg.norm(out, axis=-1)
                         - jnp.linalg.norm(x, axis=-1)).max()) < 1e-5


def test_one_table_a_distinct_kind_not_one_a_layer():
    """Five layers of two kinds: two cosines in the whole forward pass,
    and the sliding layers' blocks alone under ``hvd.window_attention``."""
    cell = small_cell()
    cfg = reference._model_config(cell)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                             ("dp", "sp", "tp"))
    tokens = reference.make_batch(cell, 0, 2)["tokens"]
    fn = jax.shard_map(
        lambda p, t: transformer.hidden(p, t, cfg)[0], mesh=mesh,
        in_specs=(transformer.param_specs(cfg), P("dp", "sp")),
        out_specs=P("dp", "sp", None))
    lowered = jax.jit(fn).lower(params, tokens).as_text(debug_info=True)
    assert lowered.count("stablehlo.cosine") == 2
    marked = [line for line in lowered.splitlines()
              if scopes.WINDOW_ATTENTION in line]
    assert marked and all(scopes.ATTENTION in line for line in marked)
    # the full layers' blocks carry the one scope and not the other
    assert any(scopes.ATTENTION in line
               and scopes.WINDOW_ATTENTION not in line
               for line in lowered.splitlines())


# -- the expert layer ----------------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_references_layer():
    """Two shares of four small experts, the shared expert counted once,
    scaling 2.5, against the reference's layer with all eight held."""
    config = dict(small_cell()["config"], num_experts=8,
                  held={"first_expert": 0})
    whole = ExpertShare(n_experts=8, first=0, count=8, top_k=2, d_model=64,
                        d_ff=32, d_shared=32, routed_scaling=2.5,
                        block_rows=16)
    from horovod_tpu.parallel.moe import init_expert_share_params
    params = jax.tree.map(lambda w: w[0], init_expert_share_params(
        jax.random.PRNGKey(6), whole, 1))
    x = jax.random.normal(jax.random.PRNGKey(7), (96, 64))
    want, want_loads, _ = reference.reference_sparse_layer(x, params, config)
    total, seen = 0.0, 0
    for first in (0, 4):
        part = ExpertShare(n_experts=8, first=first, count=4, top_k=2,
                           d_model=64, d_ff=32,
                           d_shared=32 if first == 0 else 0,
                           routed_scaling=2.5, block_rows=16)
        held = dict(params, **{name: params[name][first:first + 4]
                               for name in ("we1", "we3", "we2")})
        y, loads = expert_share_ffn(held, x, part)
        assert jnp.array_equal(loads, want_loads)
        total, seen = total + y, seen + int(loads[first:first + 4].sum())
    assert seen == 2 * x.shape[0]
    assert float(jnp.abs(total - want).max()) \
        < 1e-5 * float(jnp.abs(want).max())
    # the routed weights sum to the scaling factor: without the shared
    # expert the layer is 2.5 times what scaling 1 gives
    routed = want - reference.reference_sparse_layer(
        x, dict(params, we1=params["we1"][:0], we3=params["we3"][:0],
                we2=params["we2"][:0]), config)[0]
    plain = reference.reference_sparse_layer(
        x, params, config, wrong=("scaling_1",))[0] - (want - routed)
    assert float(jnp.abs(routed - 2.5 * plain).max()) \
        < 1e-5 * float(jnp.abs(routed).max())


# -- what cannot run -----------------------------------------------------------

@pytest.mark.parametrize("build, match", [
    (lambda: SoftmaxAttention(n_heads=6, n_kv_heads=4), "do not divide"),
    (lambda: SoftmaxAttention(n_heads=4, n_kv_heads=0), "do not divide"),
    (lambda: SoftmaxAttention(n_heads=4, n_kv_heads=2, window=0),
     "not even the query"),
    (lambda: Rope(share=0.0), "share"),
    (lambda: Rope(share=1.5), "share"),
    (lambda: Rope(factor=64.0), "stretched from"),
    (lambda: transformer.TransformerConfig(
        n_layers=4, leading_layers=(("attention", "dense"),),
        layer_pattern=(("attention", "dense"),) * 2), "whole number"),
    (lambda: transformer.TransformerConfig(
        n_layers=1, leading_layers=(("attention", "dense"),) * 2),
     "whole number"),
    (lambda: transformer.TransformerConfig(
        leading_layers=(("attention", "expert_share"),)),
     "needs its configuration"),
    (lambda: transformer.TransformerConfig(
        layer_pattern=((("window", 64), "dense"),)), "layer_pattern"),
])
def test_a_kind_refuses_what_it_cannot_run(build, match):
    with pytest.raises(ValueError, match=match):
        build()


@pytest.mark.parametrize("sp_mode", ["ring", "ulysses"])
def test_a_window_refuses_a_sequence_split_over_chips(sp_mode):
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=4, n_kv_heads=2,
        d_ff=64, sp_mode=sp_mode, layer_pattern=((SoftmaxAttention(
            n_heads=4, n_kv_heads=2, window=8), "dense"),))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2, 1),
                             ("dp", "sp", "tp"))
    fn = jax.shard_map(
        lambda p, t: transformer.hidden(p, t, cfg)[0], mesh=mesh,
        in_specs=(transformer.param_specs(cfg), P("dp", "sp")),
        out_specs=P("dp", "sp", None))
    with pytest.raises(ValueError, match="window of 8 keys"):
        jax.eval_shape(fn, params, jnp.zeros((2, 32), jnp.int32))


def test_the_two_names_are_two_settings_of_the_one_block():
    cfg = transformer.TransformerConfig(n_heads=8, n_kv_heads=2,
                                        rope_theta=5000.0)
    assert cfg.softmax_kind("attention") == SoftmaxAttention(
        8, 2, window=None, rope=Rope(theta=5000.0), gate=False)
    assert cfg.softmax_kind("gated_nope_attention") == SoftmaxAttention(
        8, 2, window=None, rope=None, gate=True)
    assert cfg.softmax_kind("linear_attention") is None
    kind = SoftmaxAttention(n_heads=2, n_kv_heads=1, window=4)
    assert cfg.softmax_kind(kind) is kind
