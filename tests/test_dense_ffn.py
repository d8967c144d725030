"""The dense SwiGLU (``models/transformer.py: _dense_ffn``) against its
plain formula ``(silu(h w1) * (h w3)) w2``: alone, under the recomputation
``remat="full"`` wraps a layer in, and through a whole decoder with the
norm on each sub-layer's input and on its output.  The split form hands its
products every operand as a buffer of its own; its arithmetic is the
formula's, in float32 to rounding, and in bfloat16 no further from float32
than the formula's own bfloat16 form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from horovod_tpu.common import metrics
from horovod_tpu.models import transformer as T

D, F, VOCAB = 32, 96, 64


def plain_ffn(h, lp, cfg):
    """The plain formula: one expression, left whole to autodiff and to
    XLA."""
    w1, w3, w2 = (lp[name].astype(h.dtype) for name in ("w1", "w3", "w2"))
    return jax.lax.psum((jax.nn.silu(h @ w1) * (h @ w3)) @ w2, cfg.tp_axis)


def _mesh():
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                ("dp", "sp", "tp"))


def _full_policy():
    return jax.checkpoint_policies.save_only_these_names(
        *T.kda_saved_names, *T.ssm_saved_names, *T.moe_saved_names)


def _alone(ffn, dtype, remat):
    """(h, w1, w3, w2) -> the SwiGLU's output and every gradient, on a
    mesh of one device, ``dy`` drawn once."""
    cfg = T.TransformerConfig(vocab_size=VOCAB, d_model=D, d_ff=F,
                              dtype=dtype)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    h = jax.random.normal(keys[0], (2, 64, D)).astype(dtype)
    lp = {"w1": jax.random.normal(keys[1], (D, F)) / D ** 0.5,
          "w3": jax.random.normal(keys[2], (D, F)) / D ** 0.5,
          "w2": jax.random.normal(keys[3], (F, D)) / F ** 0.5}
    dy = jax.random.normal(keys[4], h.shape).astype(dtype)

    def f(h, lp):
        return ffn(h, lp, cfg)

    if remat:
        f = jax.checkpoint(f, policy=_full_policy())

    def run(h, lp, dy):
        y, vjp = jax.vjp(f, h, lp)
        dh, dlp = vjp(dy)
        return [y, dh, dlp["w1"], dlp["w3"], dlp["w2"]]

    spec = {"w1": P(), "w3": P(), "w2": P()}
    return jax.jit(jax.shard_map(
        run, mesh=_mesh(), in_specs=(P(), spec, P()),
        out_specs=[P(), P(), P(), P(), P()], check_vma=True))(h, lp, dy)


def _decoder(ffn, dtype, post_norm, monkeypatch):
    """A two-layer decoder's loss and every gradient, its feed-forward
    ``ffn``, each layer recomputed (``remat="full"``)."""
    monkeypatch.setattr(T, "_dense_ffn", ffn)
    cfg = T.TransformerConfig(vocab_size=VOCAB, d_model=D, n_layers=2,
                              n_heads=4, n_kv_heads=2, d_ff=F, max_seq=64,
                              dtype=dtype, post_norm=post_norm, remat=True)
    params = T.init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.RandomState(1)
    tokens = rng.randint(0, VOCAB, size=(2, 32)).astype(np.int32)
    batch = {"tokens": tokens, "targets": np.roll(tokens, -1, axis=1)}
    rows = {k: P("dp", "sp") for k in batch}
    specs = T.param_specs(cfg)
    loss, grads = jax.jit(jax.shard_map(
        jax.value_and_grad(lambda p, b: T.loss_fn(p, b, cfg)), mesh=_mesh(),
        in_specs=(specs, rows), out_specs=(P(), specs), check_vma=True))(
            params, batch)
    return [loss] + jax.tree.leaves(grads)


def _off(got, want):
    """|got - want| over |want|, by the Frobenius norm."""
    got, want = (jnp.asarray(x, jnp.float32) for x in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("where, dtype", [
    ("alone", "float32"), ("alone", "bfloat16"),
    ("recomputed", "float32"), ("recomputed", "bfloat16"),
    ("post_norm", "float32"), ("pre_norm", "float32")])
def test_the_split_swiglu_is_the_plain_formula(where, dtype, monkeypatch):
    """The output and the gradients of ``h``, ``w1``, ``w2``, ``w3`` (and
    in a decoder the loss and every gradient): float32 to 1e-6 of the plain
    formula; bfloat16 no further from the formula in float32 than the
    formula's own bfloat16 form is.  (In a whole decoder the other layers'
    bfloat16 rounding sets the gradients' distance: float32 alone tells
    there that the split form is wired in right.)"""
    split = T._dense_ffn

    def run(ffn, dt):
        with jax.default_matmul_precision("highest"):
            if where in ("alone", "recomputed"):
                return _alone(ffn, dt, where == "recomputed")
            return _decoder(ffn, dt, where == "post_norm", monkeypatch)

    want = run(plain_ffn, "float32")
    got = run(split, dtype)
    assert len(got) == len(want)
    if dtype == "float32":
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and _off(g, w) < 1e-6
        return
    parent = run(plain_ffn, dtype)
    for g, p, w in zip(got, parent, want):
        assert g.dtype == p.dtype and bool(jnp.isfinite(g).all())
        assert _off(g, w) <= _off(p, w), (_off(g, w), _off(p, w))


def test_the_counter_reads_one_for_each_traced_call():
    """``hvd_dense_ffn_calls_total{form="split"}`` counts a layer as it is
    traced: once a call, however many periods the scan stands for."""
    def split():
        rows = metrics.snapshot().get("hvd_dense_ffn_calls_total",
                                      {"series": []})["series"]
        return {r["labels"]["form"]: r["value"] for r in rows}.get("split", 0)

    before = split()
    _alone(T._dense_ffn, "float32", remat=True)
    assert split() - before == 1
    cfg = T.TransformerConfig(vocab_size=VOCAB, d_model=D, n_layers=3,
                              n_heads=4, n_kv_heads=2, d_ff=F, max_seq=64,
                              dtype="float32", remat=True,
                              leading_layers=(("attention", "dense"),))
    params = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
             for k in ("tokens", "targets")}
    specs = T.param_specs(cfg)
    before = split()
    jax.jit(jax.shard_map(
        jax.grad(lambda p, b: T.loss_fn(p, b, cfg)), mesh=_mesh(),
        in_specs=(specs, {k: P("dp", "sp") for k in batch}),
        out_specs=specs, check_vma=True)).lower(params, batch)
    # the leading layer and the scanned one; two periods, one trace
    assert split() - before == 2
