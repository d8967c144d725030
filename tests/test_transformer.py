"""Flagship transformer tests: dense dp/sp/tp training, MoE variant,
single-device equivalence."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from horovod_tpu.models import bert, transformer
from horovod_tpu.models.linear_attention import KdaConfig
from horovod_tpu.models.state_space import SsmConfig
from horovod_tpu.models.transformer import (FEED_FORWARDS, MIXERS,
                                            TransformerConfig, init_params,
                                            loss_fn, make_train_step,
                                            param_specs)
from horovod_tpu.ops import pallas_kernels
from horovod_tpu.parallel.moe import ExpertShare

VOCAB = 64


def _cfg(**kw):
    base = dict(vocab_size=VOCAB, d_model=32, n_layers=2, n_heads=4,
                n_kv_heads=2, d_ff=64, max_seq=64, dtype="float32")
    base.update(kw)
    return TransformerConfig(**base)


def _mesh(shape, names):
    devs = np.asarray(jax.devices()[:math.prod(shape)]).reshape(shape)
    return Mesh(devs, names)


def _batch(rng, b, s):
    tokens = rng.randint(0, VOCAB, size=(b, s)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    return {"tokens": tokens, "targets": targets}


def test_dense_transformer_trains_dp_sp_tp(hvd_world):
    cfg = _cfg()
    mesh = _mesh((2, 2, 2), ("dp", "sp", "tp"))
    build, shard_batch = make_train_step(cfg, mesh, optax.adam(1e-2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    step, params, opt_state = build(params)
    rng = np.random.RandomState(0)
    batch = shard_batch(_batch(rng, 4, 32))
    losses = []
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.7  # memorizing a fixed batch


def test_moe_transformer_trains(hvd_world):
    cfg = _cfg(layer_pattern=(("attention", "moe"),), n_experts=4, top_k=2,
               capacity_factor=2.0, d_ff=32)
    mesh = _mesh((2, 2, 2), ("dp", "sp", "tp"))
    build, shard_batch = make_train_step(cfg, mesh, optax.adam(1e-2))
    params = init_params(jax.random.PRNGKey(1), cfg)
    step, params, opt_state = build(params)
    rng = np.random.RandomState(1)
    batch = shard_batch(_batch(rng, 4, 32))
    losses = []
    for _ in range(20):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_sharded_loss_matches_single_device(hvd_world):
    """Same params/batch: (2,2,2) mesh loss == (1,1,1) mesh loss."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(2), cfg)
    rng = np.random.RandomState(2)
    batch = _batch(rng, 4, 32)

    def run(mesh_shape, names, devices):
        mesh = Mesh(np.asarray(devices).reshape(mesh_shape), names)
        from jax.sharding import PartitionSpec as P
        from horovod_tpu.models.transformer import param_specs
        import jax as _jax
        f = _jax.jit(_jax.shard_map(
            lambda p, b: loss_fn(p, b, cfg), mesh=mesh,
            in_specs=(param_specs(cfg),
                      {"tokens": P("dp", "sp"), "targets": P("dp", "sp")}),
            out_specs=P(), check_vma=False))
        return float(f(params, batch))

    l_multi = run((2, 2, 2), ("dp", "sp", "tp"), jax.devices())
    l_single = run((1, 1, 1), ("dp", "sp", "tp"), jax.devices()[:1])
    assert l_multi == pytest.approx(l_single, rel=2e-4)


def test_remat_matches_no_remat(hvd_world):
    cfg = _cfg(remat=True)
    cfg_plain = _cfg(remat=False)
    params = init_params(jax.random.PRNGKey(3), cfg_plain)
    rng = np.random.RandomState(3)
    batch = _batch(rng, 2, 16)
    mesh = _mesh((2, 2, 2), ("dp", "sp", "tp"))
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models.transformer import param_specs

    def gradnorm(c):
        f = jax.jit(jax.shard_map(
            jax.grad(lambda p, b: loss_fn(p, b, c)), mesh=mesh,
            in_specs=(param_specs(c),
                      {"tokens": P("dp", "sp"), "targets": P("dp", "sp")}),
            out_specs=param_specs(c), check_vma=False))
        g = f(params, batch)
        return float(optax.global_norm(
            jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), g)))

    np.testing.assert_allclose(gradnorm(cfg), gradnorm(cfg_plain),
                               rtol=1e-4)


def test_collective_matmul_matches_psum(hvd_world):
    """The latency-hiding TP matmul ring (collective_matmul=True wires
    parallel/collective_matmul.py into the wo / w2 row-parallel
    products) must be numerically exact vs the plain psum form, for
    loss AND gradients, on a real tp>1 mesh (VERDICT r4 Next #3: the
    component stops being dead inventory)."""
    cfg = _cfg(collective_matmul=True)
    cfg_plain = _cfg(collective_matmul=False)
    params = init_params(jax.random.PRNGKey(7), cfg_plain)
    rng = np.random.RandomState(7)
    batch = _batch(rng, 4, 16)
    mesh = _mesh((2, 2, 2), ("dp", "sp", "tp"))
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models.transformer import param_specs

    def loss_and_gradnorm(c):
        bspec = {"tokens": P("dp", "sp"), "targets": P("dp", "sp")}
        f = jax.jit(jax.shard_map(
            jax.value_and_grad(lambda p, b: loss_fn(p, b, c)),
            mesh=mesh, in_specs=(param_specs(c), bspec),
            out_specs=(P(), param_specs(c)), check_vma=True))
        loss, g = f(params, batch)
        return float(loss), float(optax.global_norm(
            jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), g)))

    l_cm, g_cm = loss_and_gradnorm(cfg)
    l_ps, g_ps = loss_and_gradnorm(cfg_plain)
    np.testing.assert_allclose(l_cm, l_ps, rtol=1e-5)
    np.testing.assert_allclose(g_cm, g_ps, rtol=1e-4)


def test_sharded_gradients_match_single_device(hvd_world):
    """Loss AND gradients must be mesh-invariant under the vma-tracked
    step (r4: the previous check_vma=False form psum'ed grads over
    (dp, sp) on top of already-combined cotangents, scaling updates by
    dp*sp — this is the regression guard)."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.RandomState(5)
    batch = _batch(rng, 4, 16)
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models.transformer import param_specs

    def loss_and_gradnorm(mesh):
        bspec = {"tokens": P("dp", "sp"), "targets": P("dp", "sp")}
        f = jax.jit(jax.shard_map(
            jax.value_and_grad(lambda p, b: loss_fn(p, b, cfg)),
            mesh=mesh, in_specs=(param_specs(cfg), bspec),
            out_specs=(P(), param_specs(cfg)), check_vma=True))
        loss, g = f(params, batch)
        return float(loss), float(optax.global_norm(
            jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), g)))

    l1, g1 = loss_and_gradnorm(
        Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
             ("dp", "sp", "tp")))
    l8, g8 = loss_and_gradnorm(_mesh((2, 2, 2), ("dp", "sp", "tp")))
    np.testing.assert_allclose(l8, l1, rtol=1e-5)
    np.testing.assert_allclose(g8, g1, rtol=1e-4)


def test_ulysses_sp_matches_ring(hvd_world):
    # same model, same batch: ulysses (alltoall head exchange) must
    # produce the same loss surface as ring SP. heads=4 % sp=2 == 0.
    rng = np.random.RandomState(3)
    batch_host = _batch(rng, 4, 32)
    losses = {}
    for mode in ("ring", "ulysses"):
        cfg = _cfg(n_kv_heads=4, sp_mode=mode)
        mesh = _mesh((2, 2, 2), ("dp", "sp", "tp"))
        build, shard_batch = make_train_step(cfg, mesh,
                                             optax.sgd(1e-2))
        params = init_params(jax.random.PRNGKey(0), cfg)
        step, params, opt_state = build(params)
        batch = shard_batch(batch_host)
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, batch)
        losses[mode] = float(loss)
    assert np.isclose(losses["ring"], losses["ulysses"],
                      rtol=1e-4), losses


@pytest.mark.parametrize("ffn", FEED_FORWARDS)
@pytest.mark.parametrize("mixer", MIXERS)
def test_every_pair_of_the_pattern_builds_and_steps(mixer, ffn):
    """Every (mixer, feed-forward) pair is an entry of the pattern and
    nothing else: parameters and their specs are tuples of one structure,
    and one step of the one program moves every leaf by a finite
    gradient."""
    cfg = _cfg(
        layer_pattern=((mixer, ffn),), n_experts=4, top_k=2,
        capacity_factor=2.0,
        linear_attention=KdaConfig(n_heads=2, head_size=16, gate_rank=8,
                                   chunk=8),
        state_space=SsmConfig(n_heads=4, head_size=8, n_groups=2,
                              state_size=8, chunk=8),
        experts=ExpertShare(n_experts=4, first=1, count=2, top_k=2,
                            d_model=32, d_ff=16, d_shared=16,
                            block_rows=8))
    _one_step_moves_every_leaf(cfg)


def _one_step_moves_every_leaf(cfg):
    params = init_params(jax.random.PRNGKey(0), cfg)
    specs = param_specs(cfg)
    assert isinstance(params["layers"], tuple) and len(params["layers"]) == 1
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    build, shard_batch = make_train_step(
        cfg, _mesh((1, 1, 1), ("dp", "sp", "tp")), optax.sgd(1.0),
        donate=False)
    step, placed, opt_state = build(params)
    stepped, _, loss = step(placed, opt_state,
                            shard_batch(_batch(np.random.RandomState(0),
                                               2, 16)))
    assert np.isfinite(float(loss))
    # sgd at rate 1: a leaf's gradient is what the step took from it.
    grads = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                         params, jax.device_get(stepped))
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for path, g in flat:
        assert np.isfinite(g).all(), path
        if path[-1].key != "router_bias":   # chooses experts, no gradient
            assert np.abs(g).max() > 0, path


LATENT = transformer.LatentAttention(n_heads=4, kv_rank=16, nope=8,
                                     rope_dim=4, v_dim=6)


@pytest.mark.parametrize("leading, pattern", [
    (((LATENT, "dense"),), (("attention", "dense"),)),
    ((), ((LATENT, "expert_share"),)),
])
def test_a_latent_attention_entry_builds_and_steps(leading, pattern):
    """A ``LatentAttention`` stands where a mixer stands, in a leading
    layer and in the scanned pattern: its five leaves (``wq``, ``wkv_a``,
    ``kv_norm``, ``wkv_b``, ``wo``) have specs and gradients."""
    cfg = _cfg(
        n_layers=2 + len(leading), leading_layers=leading,
        layer_pattern=pattern,
        experts=ExpertShare(n_experts=4, first=1, count=2, top_k=2,
                            d_model=32, d_ff=16, d_shared=16,
                            block_rows=8))
    layers = init_params(jax.random.PRNGKey(0), cfg)[
        "leading" if leading else "layers"][0]
    assert {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"} <= set(layers)
    assert layers["wkv_a"].shape[1:] == (32, 16 + 4)
    assert layers["wkv_b"].shape[1:] == (16, 4 * (8 + 6))
    _one_step_moves_every_leaf(cfg)


def test_a_latent_attention_entry_refuses_a_split_sequence():
    cfg = _cfg(layer_pattern=((LATENT, "dense"),))
    build, _ = make_train_step(cfg, _mesh((1, 2, 1), ("dp", "sp", "tp")),
                               optax.sgd(1.0))
    step, placed, opt_state = build(init_params(jax.random.PRNGKey(0), cfg))
    with pytest.raises(ValueError, match="latent-attention.*cannot be split"):
        step(placed, opt_state, {k: jnp.zeros((2, 16), jnp.int32)
                                 for k in ("tokens", "targets")})


def test_both_models_take_the_kernel_choice_from_ops(monkeypatch):
    """``ops/pallas_kernels.use_flash_attention`` is the one place that
    says whether a model calls the flash kernel: turned round, the decoder
    and BERT both follow."""
    calls = []

    def flash(q, k, v, causal=True, window=None):
        calls.append(causal)
        return jnp.zeros_like(q)

    monkeypatch.setattr(pallas_kernels, "flash_attention", flash)
    cfg = _cfg()
    bcfg = bert.BertConfig(vocab_size=VOCAB, d_model=32, n_layers=1,
                           n_heads=4, d_ff=64, max_seq=16, dtype="float32")
    tokens = _batch(np.random.RandomState(0), 2, 16)["tokens"]
    mesh = _mesh((1, 1, 1), ("dp", "sp", "tp"))

    def trace(model):
        from jax.sharding import PartitionSpec as P
        del calls[:]
        if model is transformer:
            jax.eval_shape(jax.shard_map(
                lambda p, t: transformer.forward(p, t, cfg)[0], mesh=mesh,
                in_specs=(param_specs(cfg), P("dp", "sp")),
                out_specs=P("dp", "sp", "tp")),
                init_params(jax.random.PRNGKey(0), cfg), tokens)
        else:
            jax.eval_shape(jax.shard_map(
                lambda p, t: bert.encode(p, t, bcfg), mesh=mesh,
                in_specs=(bert.param_specs(bcfg), P("dp", None)),
                out_specs=P("dp", None, None)),
                bert.init_params(jax.random.PRNGKey(0), bcfg), tokens)
        return list(calls)

    assert not pallas_kernels.use_flash_attention()    # the CPU test world
    assert trace(transformer) == trace(bert) == []
    monkeypatch.setattr(pallas_kernels, "use_flash_attention", lambda: True)
    causal = trace(transformer)
    assert causal and all(causal)
    bidirectional = trace(bert)
    assert bidirectional and not any(bidirectional)
