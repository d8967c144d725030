"""The compiled step names its own parts (``common/scopes.py``).

Every step builder's lowered and compiled text holds operations under
``jvp(hvd.model)`` (forward), ``transpose(jvp(hvd.model))`` (backward) and
``hvd.optimizer``; the in-program gradient all-reduce sits under
``hvd.exchange``; the models' attention and head carry their scopes in
both passes; each flash ``pallas_call`` sits under its kernel scope and
carries its ``name``, and so do the delta rule's two under the core's and
the expert layer's row writes under the router's.
The optimized HLO of the executable is where the benchmark reads the scopes
(``yardstick/scopes.py``), so that text is what is checked here.  CPU
world, tiny sizes.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

import horovod_tpu.jax as hvd
from horovod_tpu.common import scopes
from horovod_tpu.models import bert, transformer
from horovod_tpu.ops import moe_kernels, pallas_bn, pallas_kernels

FORWARD = "jvp(%s)" % scopes.MODEL
BACKWARD = "transpose(jvp(%s))" % scopes.MODEL
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _mesh(shape, names):
    return Mesh(np.asarray(jax.devices()).reshape(shape), names)


def _linear_loss(params, batch):
    return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)


def _linear(make):
    step, init = make(_linear_loss, optax.sgd(0.1, momentum=0.9))
    params = {"w": jnp.ones((8, 4))}
    batch = {"x": jnp.ones((16, 8)), "y": jnp.zeros((16, 4))}
    return step, (params, init(params), batch)


def _bert(objective):
    cfg = bert.BertConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                          d_ff=64, max_seq=32, n_classes=3, dtype="float32")
    build, shard_batch = bert.make_finetune_step(
        cfg, _mesh((4, 2), ("dp", "tp")), optax.adamw(1e-2),
        objective=objective)
    step, params, opt_state = build(
        bert.init_params(jax.random.PRNGKey(0), cfg))
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 64, size=(8, 16)).astype(np.int32)
    if objective == "mlm":
        batch = {"tokens": tokens, "targets": tokens,
                 "mlm_mask": np.ones((8, 16), np.int32)}
    else:
        batch = {"tokens": tokens, "mask": np.ones((8, 16), np.int32),
                 "labels": rng.randint(0, 3, size=(8,)).astype(np.int32)}
    # ``step`` picks its jitted program by the batch's keys; jitting round
    # it gives one text that holds that program whole.
    return jax.jit(step), (params, opt_state, shard_batch(batch))


def _transformer():
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq=64, dtype="float32")
    build, shard_batch = transformer.make_train_step(
        cfg, _mesh((2, 2, 2), ("dp", "sp", "tp")), optax.adam(1e-2))
    step, params, opt_state = build(
        transformer.init_params(jax.random.PRNGKey(0), cfg))
    tokens = np.random.RandomState(0).randint(
        0, 64, size=(4, 32)).astype(np.int32)
    batch = shard_batch({"tokens": tokens,
                         "targets": np.roll(tokens, -1, axis=1)})
    return step, (params, opt_state, batch)


def _pattern():
    """A period of a delta-rule layer (heads of 128, which take the
    kernels) with a share of an expert layer and a softmax layer, every
    layer recomputed."""
    from horovod_tpu.models.linear_attention import KdaConfig
    from horovod_tpu.parallel.moe import ExpertShare
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq=64, dtype="float32", remat=True,
        layer_pattern=(("linear_attention", "expert_share"),
                       ("attention", "dense")),
        linear_attention=KdaConfig(n_heads=2, head_size=128, gate_rank=8,
                                   chunk=8),
        experts=ExpertShare(n_experts=8, first=2, count=3, top_k=2,
                            d_model=32, d_ff=16, d_shared=16, block_rows=8))
    build, shard_batch = transformer.make_train_step(
        cfg, _mesh((4, 1, 2), ("dp", "sp", "tp")), optax.adam(1e-2))
    step, params, opt_state = build(
        transformer.init_params(jax.random.PRNGKey(0), cfg))
    tokens = np.random.RandomState(0).randint(
        0, 64, size=(4, 32)).astype(np.int32)
    batch = shard_batch({"tokens": tokens,
                         "targets": np.roll(tokens, -1, axis=1)})
    return step, (params, opt_state, batch)


def _state_space():
    """A Mamba-2 block whose shapes take the scan's kernels (heads of 64 in
    groups that fill a lane tile, a state of 128, chunks of 128) before a
    softmax layer, every block recomputed."""
    from horovod_tpu.models.state_space import SsmConfig
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq=128, dtype="float32", remat=True,
        layer_pattern=(("state_space", None), ("attention", "dense")),
        state_space=SsmConfig(n_heads=4, head_size=64, n_groups=2,
                              state_size=128, chunk=128))
    build, shard_batch = transformer.make_train_step(
        cfg, _mesh((8, 1, 1), ("dp", "sp", "tp")), optax.adam(1e-2))
    step, params, opt_state = build(
        transformer.init_params(jax.random.PRNGKey(0), cfg))
    tokens = np.random.RandomState(0).randint(
        0, 64, size=(8, 128)).astype(np.int32)
    batch = shard_batch({"tokens": tokens,
                         "targets": np.roll(tokens, -1, axis=1)})
    return step, (params, opt_state, batch)


BUILDERS = {
    "make_data_parallel_step":
        lambda: _linear(hvd.make_data_parallel_step),
    "make_sharded_jit_step": lambda: _linear(hvd.make_sharded_jit_step),
    "make_finetune_step-classification": lambda: _bert("classification"),
    "make_finetune_step-mlm": lambda: _bert("mlm"),
    "transformer.make_train_step": _transformer,
    "transformer.make_train_step-pattern": _pattern,
    "transformer.make_train_step-state_space": _state_space,
}


@pytest.fixture(scope="module")
def texts():
    """(lowered text, compiled text) of each builder's step, made once:
    one world over the 8 CPU devices for the whole module."""
    hvd.init()
    made = {}

    def get(name):
        if name not in made:
            step, args = BUILDERS[name]()
            lowered = step.lower(*args)
            made[name] = (lowered.as_text(debug_info=True),
                          lowered.compile().as_text())
        return made[name]

    yield get
    hvd.shutdown()


def _op_names(compiled_text):
    return OP_NAME.findall(compiled_text)


@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_every_step_builder_names_its_phases(texts, builder):
    lowered, compiled = texts(builder)
    names = _op_names(compiled)
    forward = [n for n in names if FORWARD in n and BACKWARD not in n]
    backward = [n for n in names if BACKWARD in n]
    update = [n for n in names if scopes.OPTIMIZER in n]
    assert forward and backward and update
    # Nothing of the update is inside the model, and the other way round.
    assert not any(scopes.MODEL in n for n in update)
    for phase in (FORWARD, BACKWARD, scopes.OPTIMIZER):
        assert phase in lowered


def test_the_in_program_allreduce_sits_under_exchange(texts):
    _, compiled = texts("make_data_parallel_step")
    reduces = [line for line in compiled.splitlines()
               if re.search(r" all-reduce(-start)?\(", line)]
    assert reduces
    grads = [line for line in reduces if scopes.EXCHANGE in line]
    # The gradients' all-reduce, and nested in the optimizer's scope; the
    # only other one is the loss's pmean, outside every scope.
    assert grads and len(reduces) - len(grads) <= 1
    assert all("%s/%s" % (scopes.OPTIMIZER, scopes.EXCHANGE) in line
               for line in grads)
    # make_finetune_step's dp reduction is the transpose of the loss's
    # pmean: part of the backward pass, nothing under hvd.exchange.
    _, compiled = texts("make_finetune_step-mlm")
    assert scopes.EXCHANGE not in compiled


@pytest.mark.parametrize("block", [scopes.ATTENTION, scopes.HEAD])
@pytest.mark.parametrize("builder", [
    "make_finetune_step-classification", "make_finetune_step-mlm",
    "transformer.make_train_step"])
def test_model_blocks_carry_their_scope_in_both_passes(texts, builder,
                                                       block):
    names = [n for n in _op_names(texts(builder)[1]) if block in n]
    assert any(BACKWARD in n for n in names)
    assert any(FORWARD in n and BACKWARD not in n for n in names)


@pytest.mark.parametrize("kernel,backward", [(scopes.KDA_FWD, False),
                                             (scopes.KDA_BWD, True)])
def test_delta_rule_kernels_sit_under_the_core_in_their_pass(texts, kernel,
                                                             backward):
    """Each of the two kernels under ``hvd.linear_attention/hvd.kda_core``
    with its ``name=``, in the lowered and in the compiled text: the
    forward one in the forward pass alone (a layer recomputed in the
    backward pass keeps the core's output and does not run it again), the
    backward one in the backward pass alone."""
    path = "/".join([scopes.LINEAR_ATTENTION, scopes.KDA_CORE, kernel,
                     scopes.kernel_name(kernel)])
    lowered, compiled = texts("transformer.make_train_step-pattern")
    assert path + "/pallas_call" in lowered
    names = [n for n in _op_names(compiled) if path in n]
    phased = [n for n in names if FORWARD in n]
    assert phased
    assert all((BACKWARD in n) == backward for n in phased)
    assert backward or not any("checkpoint" in n for n in names)


@pytest.mark.parametrize("kernel,backward", [(scopes.SSD_FWD, False),
                                             (scopes.SSD_BWD, True)])
def test_state_space_kernels_sit_under_the_core_in_their_pass(texts, kernel,
                                                              backward):
    """Each of the scan's two kernels under ``hvd.state_space/hvd.ssd_core``
    with its ``name=``, in the lowered and in the compiled text.  The
    backward one runs in the backward pass alone; the forward one in both,
    because a recomputed block keeps nothing of its scan
    (``state_space.SAVED``) and needs ``y`` again."""
    path = "/".join([scopes.STATE_SPACE, scopes.SSD_CORE, kernel,
                     scopes.kernel_name(kernel)])
    lowered, compiled = texts("transformer.make_train_step-state_space")
    assert path + "/pallas_call" in lowered
    phased = [n for n in _op_names(compiled) if path in n and FORWARD in n]
    assert phased
    if backward:
        assert all(BACKWARD in n for n in phased)
    else:
        assert any(BACKWARD not in n for n in phased)
        assert any(BACKWARD in n and "rematted_computation" in n
                   for n in phased)


@pytest.mark.parametrize("backward", [False, True])
def test_the_blocks_rows_sit_under_the_router_in_both_passes(texts, backward):
    """The dispatch gathers and the combine kernel of an expert layer's
    blocks under ``hvd.moe/hvd.router/hvd.router_rows``, the kernel with
    its ``name=``, in the forward and in the backward pass."""
    lowered, compiled = texts("transformer.make_train_step-pattern")
    # hvd.moe/<the blocks' loop>/hvd.router/hvd.router_rows/...
    path = re.compile("%s/[^;]*%s/%s/" % tuple(map(re.escape, (
        scopes.MOE, scopes.ROUTER, scopes.ROUTER_ROWS))))
    kernel = "%s/%s/" % (scopes.ROUTER_ROWS, moe_kernels.COMBINE)
    assert kernel in lowered
    rows = [n for n in _op_names(compiled) if scopes.ROUTER_ROWS in n]
    assert rows and all(path.search(n) for n in rows)
    mine = [n for n in rows if FORWARD in n and (BACKWARD in n) == backward]
    assert any(kernel in n for n in mine)
    assert any(n.endswith(scopes.ROUTER_ROWS + "/gather") for n in mine)


def _pallas_calls(jaxpr, found):
    """(name stack, ``name=``) of every ``pallas_call`` in a jaxpr,
    nested jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((str(eqn.source_info.name_stack),
                          eqn.params["name"]))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _pallas_calls(inner, found)
    return found


@pytest.mark.parametrize("variant,window,want", [
    ("pallas", None, [scopes.FLASH_FWD, scopes.FLASH_DQ, scopes.FLASH_DKV]),
    ("pallas_onepass", None, [scopes.FLASH_FWD, scopes.FLASH_BWD_ONEPASS]),
    (None, None, [scopes.FLASH_FWD, scopes.FLASH_BWD_ONEPASS]),
    ("pallas", 64, [scopes.FLASH_WINDOW_FWD, scopes.FLASH_WINDOW_DQ,
                    scopes.FLASH_WINDOW_DKV]),
    (None, 64, [scopes.FLASH_WINDOW_FWD, scopes.FLASH_WINDOW_DKV])])
def test_each_flash_kernel_sits_under_its_scope(flash_blocks, variant,
                                                window, want):
    """Where dq fits, the backward is the one kernel: under its own scope
    for a full call, under the banded dk/dv's for a call with a window (the
    scope the benchmark reads the banded backward from)."""
    flash_blocks(backward=variant)
    q = jnp.ones((1, 256, 2, 64), jnp.float32)

    def loss(q, k, v):
        return pallas_kernels.flash_attention(
            q, k, v, causal=window is not None, window=window).sum()

    calls = _pallas_calls(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q).jaxpr, [])
    assert [name for _, name in calls] == [
        scopes.kernel_name(s) for s in want]
    for (stack, _), scope in zip(calls, want):
        assert scope in stack
        assert all(other not in stack for other in want if other != scope)


def test_batch_norm_kernels_carry_a_name():
    x = jnp.ones((2, 8, 8, 128), jnp.float32)
    g = jnp.ones((128,), jnp.float32)

    def loss(x, g, b):
        return pallas_bn.batch_norm_act(x, g, b)[0].sum()

    calls = _pallas_calls(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, g, g).jaxpr, [])
    assert {name for _, name in calls} == {
        "hvd_bn_stats", "hvd_bn_apply", "hvd_bn_bwd_reductions",
        "hvd_bn_bwd_dx"}


def test_make_data_parallel_step_returns_the_jit_object(hvd_world):
    step, _ = hvd.make_data_parallel_step(_linear_loss, optax.sgd(0.1))
    assert isinstance(step, type(jax.jit(lambda x: x)))
    assert callable(step.lower)


def test_one_vocabulary():
    """Every ``jax.named_scope`` and every ``metrics.span`` in the program
    takes its name from ``common/scopes.py``, a device scope the one and a
    host span the other, and every name there is ``hvd.<word>``."""
    root = os.path.dirname(os.path.abspath(hvd.__file__))
    root = os.path.dirname(root)
    uses, host_uses = [], []
    for dirpath, _, files in os.walk(root):
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(dirpath, name)) as f:
                text = f.read()
            uses += re.findall(r"named_scope\(([^)]*)\)", text)
            if name != "metrics.py":        # the primitive's own module
                host_uses += re.findall(
                    r"metrics\.(?:span|record_span)\(\s*([^,)]*)", text)
    constants = {k: v for k, v in vars(scopes).items()
                 if k.isupper() and isinstance(v, str)}
    host = {k: v for k, v in constants.items() if v in scopes.host_spans}
    device = {k: v for k, v in constants.items() if k not in host}
    assert len(uses) >= 10
    for use in uses:
        assert use.startswith("scopes.") and use[7:] in device, use
    # The one use by a variable: common/device.py keeps JAX's compile
    # stages as spans through its table of their names.
    from horovod_tpu.common import device as device_module
    assert host_uses.count("name") == 1
    host_uses.remove("name")
    stages = set(device_module._COMPILE_STAGES.values())
    assert len(host_uses) >= 14
    for use in host_uses:
        assert use.startswith("scopes.") and use[7:] in host, use
    # Every host name is opened somewhere.
    assert {host[use[7:]] for use in host_uses} | stages == scopes.host_spans
    assert all(re.fullmatch(r"hvd\.[a-z_]+", v) for v in constants.values())
    assert len(set(constants.values())) == len(constants)
    assert len(device) == 34 and len(host) == len(scopes.host_spans) == 14
