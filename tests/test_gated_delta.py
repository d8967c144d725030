"""The gated delta rule with one decay a head and keys narrower than values
(Gated DeltaNet, ``olmo-hybrid-7b``): ``kda_chunked`` in its XLA form and
through the kernels that take a decay a head (interpreted) against the
token-by-token recurrence and against each other in values and every
gradient; which shapes take which kernels and the counter that says so;
the block with its norms after the sub-layers and the QK-norm against
their written-out equations; the tiny decoder against the
benchmark's plain reference (``yardstick/builders/olmo_hybrid.py``); scopes
and refusals."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.common import metrics, scopes
from horovod_tpu.models import transformer as T
from horovod_tpu.models.linear_attention import (KdaConfig, kda_chunked,
                                                 kda_chunked_xla)
from horovod_tpu.ops import kda_kernels
from yardstick import manifest as mf
from yardstick.builders import olmo_hybrid as builder

CELL = "olmo-hybrid-7b.dp1-pt8k"


def off(got, want):
    """Largest difference of two trees' leaves, each against its leaf's
    largest entry."""
    return max(float(jnp.abs(a - b).max() / jnp.abs(b).max())
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def inputs(key, h, dk, dv, decay, b=1, s=64):
    """Unit keys and queries, a decay a head from next to none to
    ``e^-decay`` a step, beta up to 2."""
    ks = jax.random.split(key, 5)
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in
            (jax.random.normal(kk, (b, s, h, dk)) for kk in ks[:2]))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)) + 1.0)
    return q, k, v, g, beta


def plain(*args):
    return jax.vmap(builder.gated_delta_recurrence)(*args)


# (heads, keys, values): 24 / 48 take the XLA form; 72 / 136 the kernels,
# padded to 128 / 256; the cell's 96 / 192, keys padded to 128 and two heads'
# values three lane tiles, the second head's sliced at lane 64; one head a
# grid step at the cell's sizes, values padded to 256.
FORMS = {"xla": (2, 24, 48), "kernels": (2, 72, 136),
         "kernels, the cell's sizes": (2, 96, 192),
         "kernels, one head a step": (1, 96, 192)}


@pytest.mark.parametrize("form,decay", [
    ("xla", 0.05), ("xla", 4.0), ("kernels", 0.05), ("kernels", 4.0),
    ("kernels, the cell's sizes", 0.05), ("kernels, the cell's sizes", 4.0),
    ("kernels, one head a step", 0.05), ("kernels, one head a step", 4.0)])
def test_a_decay_a_head_over_narrow_keys_is_the_recurrence(form, decay):
    """Output and the gradient of every input, keys not a lane multiple,
    beta above 1, a weak and a strong decay: against the recurrence, and
    the kernels (which take the decay a head as it is) against the XLA
    form (which broadcasts it over the key channels)."""
    h, dk, dv = FORMS[form]
    args = inputs(jax.random.PRNGKey(int(decay)), h, dk, dv, decay)
    assert float(args[4].max()) > 1.5
    assert kda_kernels.takes(dk, dv, 16) == (form != "xla")
    weight = jax.random.normal(jax.random.PRNGKey(9), (1, 64, h, dv))

    def both(f):
        def loss(*a):
            out = f(*a)
            return (out * weight).sum(), out
        grads, out = jax.grad(loss, argnums=range(5), has_aux=True)(*args)
        return (out,) + grads

    got = both(lambda *a: kda_chunked(*a, 16, segment=2))
    oracles = [plain] + ([] if form == "xla" else
                         [lambda *a: kda_chunked_xla(*a, 16, segment=2)])
    for oracle in oracles:
        want = both(oracle)
        assert got[0].shape == want[0].shape == (1, 64, h, dv)
        assert float(jnp.abs(got[0] - want[0]).max()) < 1e-5
        for g, w in zip(got[1:], want[1:]):
            assert g.shape == w.shape and bool(jnp.isfinite(g).all())
            assert float(jnp.abs(g - w).max()) \
                < 1e-4 * float(jnp.abs(w).max())


@pytest.mark.parametrize("dk,dv,chunk,kernels,decay", [
    (96, 192, 64, True, "head"), (128, 128, 64, True, "head"),
    (72, 136, 16, True, "head"), (64, 128, 64, False, "head"),
    (96, 64, 64, False, "head"), (24, 48, 16, False, "head"),
    (96, 192, 24, False, "head"), (96, 192, 64, True, "channel"),
    (64, 128, 64, False, "channel")])
def test_shapes_choose_the_form_and_the_counter_says_which(dk, dv, chunk,
                                                          kernels, decay):
    """Keys and values that fill more than half of their lane tiles, and a
    chunk that halves down to single rows, take the kernels: a decay a head
    its own pair (``hvd_kda_fwd_head``), a decay for every channel the
    other; the core counts its form and its decay as it is traced."""
    assert kda_kernels.takes(dk, dv, chunk) == kernels
    assert kda_kernels.padded(192, 30) == 192 == kda_kernels.padded(192, 2)
    assert kda_kernels.padded(192, 1) == 256 == kda_kernels.padded(136, 2)
    assert kda_kernels.padded(96, 30) == 128 == kda_kernels.padded(128, 1)

    def series():
        rows = metrics.snapshot().get("hvd_delta_rule_calls_total",
                                      {"series": []})["series"]
        return {(r["labels"]["form"], r["labels"]["decay"]): r["value"]
                for r in rows}

    before = series()
    q, k, v, g, beta = inputs(jax.random.PRNGKey(0), 1, dk, dv, 1.0,
                              s=2 * chunk)
    if decay == "channel":
        g = g[..., None] * jnp.linspace(0.5, 1.5, dk)
    jaxpr = str(jax.make_jaxpr(lambda *a: kda_chunked(*a, chunk))(
        q, k, v, g, beta))
    assert ("hvd_kda_fwd" in jaxpr) == kernels
    assert ("hvd_kda_fwd_head" in jaxpr) == (kernels and decay == "head")
    form = ("head_kernel" if decay == "head" else "kernel") if kernels \
        else "xla"
    changed = {key: n - before.get(key, 0) for key, n in series().items()
               if n != before.get(key, 0)}
    assert changed == {(form, decay): 1}


# -- the block ----------------------------------------------------------------

KIND = T.SoftmaxAttention(4, 4, rope=None, qk_norm=True)


def block_config(**over):
    return T.TransformerConfig(**dict(dict(
        vocab_size=64, d_model=32, n_layers=1, n_heads=4, n_kv_heads=4,
        d_ff=48, norm_eps=1e-6, dtype="float32", post_norm=True,
        layer_pattern=((KIND, "dense"),)), **over))


def mesh_of(shape):
    return Mesh(np.asarray(jax.devices()[:int(np.prod(shape))]).reshape(shape),
                ("dp", "sp", "tp"))


def one_layer(cfg, seed=0):
    """Parameters of one layer with every norm's scale away from 1, so that
    a scale left out shows."""
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    (lp,) = params["layers"]
    lp = {name: (1.0 + 0.3 * jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(7), at), w.shape))
        if name.endswith("norm") or name.startswith("ln") else w
        for at, (name, w) in enumerate(sorted(lp.items()))}
    return dict(params, layers=(lp,))


def program_hidden(cfg, mesh=None):
    mesh = mesh or mesh_of((1, 1, 1))
    return jax.jit(jax.shard_map(
        lambda p, t: T.hidden(p, t, cfg)[0], mesh=mesh,
        in_specs=(T.param_specs(cfg), P(cfg.dp_axis, cfg.sp_axis)),
        out_specs=P(cfg.dp_axis, cfg.sp_axis), check_vma=True))


def written_out(params, tokens, eps=1e-6, per_head=False, pre_norm=False):
    """One block as the equations have it: ``h = x + rms(attn(x))``, ``out
    = h + rms(swiglu(h))``, the final norm; q and k each under one RMSNorm
    over the whole projection; causal softmax over 4 heads of 8, no
    positions."""
    hi = jax.lax.Precision.HIGHEST
    (lp,) = jax.tree.map(lambda w: w[0], params["layers"])

    def rms(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def attn(x):
        s = x.shape[1]
        q, k, v = (jnp.einsum("bsd,de->bse", x, lp[n], precision=hi)
                   for n in ("wq", "wk", "wv"))
        if per_head:
            q, k = (rms(y.reshape(1, s, 4, 8), 1.0).reshape(1, s, 32) * w
                    for y, w in ((q, lp["q_norm"]), (k, lp["k_norm"])))
        else:
            q, k = rms(q, lp["q_norm"]), rms(k, lp["k_norm"])
        q, k, v = (y.reshape(1, s, 4, 8) for y in (q, k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            precision=hi) / math.sqrt(8)
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v,
                       precision=hi).reshape(1, s, 32)
        return jnp.einsum("bse,ed->bsd", o, lp["wo"], precision=hi)

    def swiglu(x):
        a = jnp.einsum("bsd,df->bsf", x, lp["w1"], precision=hi)
        g = jnp.einsum("bsd,df->bsf", x, lp["w3"], precision=hi)
        return jnp.einsum("bsf,fd->bsd", jax.nn.silu(a) * g, lp["w2"],
                          precision=hi)

    x = params["embed"][tokens]
    if pre_norm:
        x = x + attn(rms(x, lp["ln1"]))
        x = x + swiglu(rms(x, lp["ln2"]))
    else:
        x = x + rms(attn(x), lp["ln1"])
        x = x + rms(swiglu(x), lp["ln2"])
    return rms(x, params["ln_f"])


def test_norms_after_the_sub_layers_and_the_qk_norm_are_the_equations():
    cfg = block_config()
    params = one_layer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, 64)
    assert params["layers"][0]["q_norm"].shape == (1, 32)
    with jax.default_matmul_precision("highest"):
        got = program_hidden(cfg)(params, tokens)
        want = written_out(params, tokens)
        assert off(got, want) < 1e-5
        # the norm over each head alone and the norms before the sub-layers
        # are other functions
        for wrong in ({"per_head": True}, {"pre_norm": True}):
            assert off(got, written_out(params, tokens, **wrong)) > 1e-2, wrong
        # the same parameters under the pre-norm block are that other one
        pre = program_hidden(block_config(post_norm=False))(params, tokens)
        assert off(pre, written_out(params, tokens, pre_norm=True)) < 1e-5


def test_a_qk_norm_refuses_heads_split_over_tp():
    cfg = block_config()
    params = one_layer(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="QK-norm.*'tp'"):
        program_hidden(cfg, mesh_of((1, 1, 2)))(params, tokens)
    # without it the same heads split
    plain_kind = T.SoftmaxAttention(4, 4, rope=None)
    cfg = block_config(layer_pattern=((plain_kind, "dense"),))
    program_hidden(cfg, mesh_of((1, 1, 2)))(T.init_params(
        jax.random.PRNGKey(0), cfg), tokens)


def test_a_delta_rule_refuses_a_decay_it_does_not_know():
    with pytest.raises(ValueError, match="decays by one of"):
        KdaConfig(n_heads=2, decay="token")


# -- the decoder --------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    """The cell's tiny configuration in float32, its head fitted, and its
    batch."""
    cell = mf.load().cell(CELL, tiny=True)
    cell["config"]["activation_dtype"] = "float32"
    cfg = builder._model_config(cell)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = builder.make_batch(cell, 0, 2)
    params, loss = builder.prepare(params, batch["tokens"], batch["targets"],
                                   cell)
    return cell, cfg, params, batch, loss


def program_loss(cfg):
    rows = {k: P(cfg.dp_axis, cfg.sp_axis) for k in ("tokens", "targets")}
    return jax.shard_map(lambda p, b: T.loss_fn(p, b, cfg),
                         mesh=mesh_of((1, 1, 1)),
                         in_specs=(T.param_specs(cfg), rows), out_specs=P(),
                         check_vma=True)


def test_the_tiny_decoder_is_one_period_of_the_cells_form(tiny):
    _, cfg, params, _, _ = tiny
    mixers = [m for m, _ in cfg.layer_pattern]
    assert mixers[:3] == ["linear_attention"] * 3
    assert mixers[3] == T.SoftmaxAttention(2, 2, rope=None, qk_norm=True)
    assert {f for _, f in cfg.layer_pattern} == {"dense"}
    assert cfg.post_norm and cfg.remat and not cfg.tie_embeddings
    lin = cfg.linear_attention
    assert (lin.head_size, lin.values, lin.decay) == (24, 48, "head")
    lp = params["layers"][0]
    assert lp["w_a"].shape == (1, 64, 2) and lp["dt_bias"].shape == (1, 2)
    assert lp["w_g"].shape == (1, 64, 96) and lp["o_norm"].shape == (1, 48)
    assert "w_fa" not in lp and "decay_bias" not in lp


def test_the_decoders_loss_and_every_gradient_match_the_reference(tiny):
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
        assert off(leaf, flat_want[path]) < 1e-4, jax.tree_util.keystr(path)


@pytest.mark.parametrize("wrong", builder.WRONG)
def test_the_fitted_head_tells_each_wrong_part(tiny, wrong):
    cell, _, params, batch, loss = tiny
    with jax.default_matmul_precision("highest"):
        reads = float(builder.reference_loss_fn(
            params, batch["tokens"], batch["targets"], cell["config"],
            wrong=(wrong,)))
    # the QK-norm over each of the tiny model's two heads moves the loss
    # least (4.8e-3); every other part by twice the loss or more
    assert abs(reads - loss) > 2e-3 * loss, (reads, loss)


def test_the_configuration_holds_the_published_widths_and_count():
    cell = mf.load().cell(CELL)
    c = cell["config"]
    assert (c["hidden_size"], c["intermediate_size"], c["num_attention_heads"],
            c["linear_num_key_heads"], c["linear_key_head_dim"],
            c["linear_value_head_dim"], c["linear_conv_kernel_dim"]) \
        == (3840, 11008, 30, 30, 96, 192, 4)
    cfg = builder._model_config(cell)
    assert cfg.head_dim == 128 and cfg.linear_attention.chunk == 64
    assert kda_kernels.takes(96, 192, 64)
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == c["parameters"] == 928862196


def test_the_core_and_the_feed_forward_carry_their_scopes(tiny):
    _, cfg, params, batch, _ = tiny
    lines = jax.jit(jax.grad(program_loss(cfg))).lower(params, batch) \
        .as_text(debug_info=True).splitlines()
    core = [ln for ln in lines if scopes.GATED_DELTA_CORE in ln]
    assert core and all(scopes.LINEAR_ATTENTION in ln for ln in core)
    assert any("/while/body/" in ln for ln in core)     # the segments' walk
    assert not any(scopes.KDA_CORE in ln for ln in lines)
    dense = [ln for ln in lines if scopes.DENSE_FFN in ln]
    assert dense and any("dot_general" in ln for ln in dense)
    assert not any(scopes.DENSE_FFN in ln
                   and (scopes.ATTENTION in ln
                        or scopes.LINEAR_ATTENTION in ln) for ln in lines)
