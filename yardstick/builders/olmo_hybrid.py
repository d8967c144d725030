"""Olmo-Hybrid-7B's decoder (``model_type: olmo_hybrid``) through
``horovod_tpu/models/transformer.py``: a pre-training step of one chip's
share of an 8-chip group (the configuration's file says how it was cut),
with the plain float32 reference written from the layer equations beside
it.

A period of the model is three gated-DeltaNet layers and one full softmax
layer, every layer with a dense SwiGLU of 11008 and OLMo 2's reordered
norm (``x + rms_norm(sub(x))``).  A linear layer's 30 heads carry a state of
keys of 96 over values of 192 under one decay a head; a full layer's 30
heads of 128 see no rotary turn, their q and k projections each under one
RMSNorm over all heads.  The chip holds the four layers whole and a slice
of the vocabulary.
"""

import dataclasses
import math
import sys

import numpy as np

from yardstick import flops
from yardstick import flops_gated_delta as fg
from yardstick import measure
from yardstick.builders.deepseek_v3 import float32_loss
from yardstick.builders.solar_open2 import (_optimizer, make_batch,
                                            reference_nll_sum)

# Two comparisons decide ``correct``, both against the float32 reference at
# the timed sizes under a head fitted to its batch (``HEAD_FIT``,
# ``prepare``; ``solar_open2.py`` says why that gives them their teeth), as
# in ``kanana-2-30b-a3b`` (``deepseek_v3.py`` has the reasoning).
#
# ``FLOAT32_RTOL``: the program read ONCE in float32 before the first step
# (``float32_loss``: ``transformer.loss_fn`` over the step's mesh, kernels and
# parameters, activations float32, products at the highest precision).
# Found on the chip (my chip runs, PR 39; 14 seeds): 0 to 1.1e-6 off the
# reference; the same reference a precision below (bfloat16 weights and
# activations, default products) 7.3e-3 to 4.9e-2 over three; the QK-norm
# over each head alone 6.4e-4, every other ``WRONG`` part 0.57 or more.  The
# limit lies near the geometric middle of the program's largest and the lower
# precision's smallest reading: 55 times of room over the one, 120 under the
# other, and ten times under the part that moves the loss least.
#
# ``LOSS_RTOL``: the timed step's own step-0 loss (bf16 activations; float32
# norms, delta-rule core and softmax statistics).  Found on the chip (my chip
# runs, PR 39; eleven seeds): 5.4e-4 to 7.9e-4 off the reference (mean
# 6.5e-4), always above it.  The limit lies between that and the lower
# precision's 7.3e-3, near their geometric middle: 3.2 times over the
# step's largest reading, 2.9 under the lower precision's smallest.  It does
# not see the QK-norm over each head (6.4e-4); the float32 read does.
FLOAT32_RTOL = 6e-5
LOSS_RTOL = 0.0025
# The head's random start plus ``HEAD_FIT / hidden`` times, in column ``j``,
# the sum of the reference's final hidden states of the tokens whose target
# is ``j``: a target logit of about ``HEAD_FIT`` before the step.
HEAD_FIT = 8.0

REFERENCE_QUERY_BLOCK = 512     # queries the reference's softmax holds at once
# What the reference can get wrong on purpose, for the readings that show
# what the tolerances catch: the norms before the sub-layers and not after;
# the QK-norm over each head's 128 and not over the whole projection; no
# decay; beta up to 1 (no negative eigenvalues); a sigmoid output gate in
# the linear layers where the family's is silu.
WRONG = ("pre_norm", "qk_norm_per_head", "no_decay", "beta_below_one",
         "sigmoid_gate")


def _layer_types(c):
    """One period of ``layer_types``; refuses what the builder cannot
    run."""
    kinds = c["layer_types"]
    if set(kinds) - {"linear_attention", "full_attention"} \
            or c["num_hidden_layers"] % len(kinds) \
            or c["hidden_act"] != "silu" or c["attention_bias"] \
            or c["rope_parameters"].get("rope_theta") is not None \
            or not c["linear_allow_neg_eigval"] \
            or c["linear_num_key_heads"] != c["linear_num_value_heads"]:
        raise ValueError(
            "the builder runs whole periods of gated-DeltaNet and full "
            "layers, SwiGLUs, no rotary turn, no attention bias, beta up to "
            "2 and one key head a value head; the configuration says "
            "otherwise")
    return kinds


def _model_config(cell):
    from horovod_tpu.models import transformer
    if "post_norm" not in {f.name for f in dataclasses.fields(
            transformer.TransformerConfig)}:
        raise measure.Refused(
            "this horovod_tpu has no norm after the sub-layers, no QK-norm "
            "and no delta rule with a decay a head in models/: it cannot "
            "run %s" % cell["name"])
    from horovod_tpu.models.linear_attention import KdaConfig
    c, spec = cell["config"], cell["spec"]
    heads = c["num_attention_heads"]
    full = transformer.SoftmaxAttention(heads, c["num_key_value_heads"],
                                        rope=None, qk_norm=True)
    return transformer.TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=heads,
        n_kv_heads=c["num_key_value_heads"],
        head_size=c["hidden_size"] // heads, d_ff=c["intermediate_size"],
        max_seq=spec["seq_len"], norm_eps=c["rms_norm_eps"],
        dtype=c["activation_dtype"], param_dtype=c["param_dtype"],
        remat=True, post_norm=True,
        layer_pattern=tuple(
            (full if kind == "full_attention" else "linear_attention",
             "dense") for kind in _layer_types(c)),
        linear_attention=KdaConfig(
            n_heads=c["linear_num_key_heads"],
            head_size=c["linear_key_head_dim"],
            value_size=c["linear_value_head_dim"],
            conv_size=c["linear_conv_kernel_dim"],
            chunk=spec["delta_rule_chunk"], norm_eps=c["rms_norm_eps"],
            decay="head"),
        tie_embeddings=c["tie_word_embeddings"],
        head_block=spec["head_block"])


def prepare(params, tokens, targets, cell):
    """The head fitted to the batch (``HEAD_FIT``) in one pass of the plain
    reference, and the reference's loss under it.  Returns (the parameters,
    the loss)."""
    import jax
    import jax.numpy as jnp
    config = cell["config"]

    def one_pass(params, tokens, targets):
        x = reference_hidden(params, tokens, config)
        hidden = x.shape[-1]
        fit = jnp.zeros((params["head"].shape[1], hidden), jnp.float32) \
            .at[targets.reshape(-1)].add(x.reshape(-1, hidden))
        head = params["head"] + (HEAD_FIT / hidden) * fit.T
        loss = sum(reference_nll_sum(x[i], head, targets[i])
                   for i in range(x.shape[0])) / targets.size
        return head.astype(params["head"].dtype), loss

    head, loss = jax.jit(one_pass)(params, tokens, targets)
    return dict(params, head=head), float(loss)


def _shapes(cell):
    c, spec = cell["config"], cell["spec"]
    heads = c["num_attention_heads"]
    return dict(
        seq=spec["seq_len"], hidden=c["hidden_size"], vocab=c["vocab_size"],
        mixers=list(_layer_types(c)) * (c["num_hidden_layers"]
                                        // len(c["layer_types"])),
        heads=heads, head=c["hidden_size"] // heads,
        lin_heads=c["linear_num_key_heads"], key=c["linear_key_head_dim"],
        value=c["linear_value_head_dim"], conv=c["linear_conv_kernel_dim"],
        chunk=spec["delta_rule_chunk"], dense_width=c["intermediate_size"])


def _kernels(cell, samples):
    """The delta-rule cores and the flash kernels' calls of one step, for
    ``readers/scope_roofline.py`` and ``readers/kernel_roofline.py``.  The
    cores' floor is the chunked form's own work at keys of 96 over values
    of 192 with a scalar decay (``flops_gated_delta.py``), not the lanes
    the kernels pad to nor their per-channel transition."""
    sh = _shapes(cell)
    return [
        {"kernel": "gated_delta_core",
         "calls_per_step": sh["mixers"].count("linear_attention"),
         "per_call": fg.gated_delta_cost(samples, sh["seq"], sh["lin_heads"],
                                         sh["key"], sh["value"],
                                         sh["chunk"])},
        {"kernel": "flash",
         "calls_per_step": sh["mixers"].count("full_attention"),
         "per_call": flops.flash_attention_cost(
             samples, sh["heads"], sh["seq"], sh["head"], causal=True)},
    ]


def jit_step(cell, seed, hvd, devices):
    """``make_train_step`` over a (dp, sp, tp) = (chips, 1, 1) mesh."""
    import jax

    cfg = _model_config(cell)
    from horovod_tpu.models.transformer import init_params, make_train_step
    spec = cell["spec"]
    mesh = hvd.create_mesh((len(devices), 1, 1),
                           (cfg.dp_axis, cfg.sp_axis, cfg.tp_axis), devices)
    build, shard_batch = make_train_step(cfg, mesh, _optimizer(cell))
    # Weights on the device in one jitted call from the seed.
    params = jax.jit(lambda key: init_params(key, cfg))(
        jax.random.PRNGKey(seed))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    want = cell["config"].get("parameters")
    if want is not None and n_params != want:
        raise ValueError("the share has %d parameters, the configuration "
                         "says %d" % (n_params, want))
    n = spec["batch_per_chip"] * len(devices)
    host_batch = make_batch(cell, seed, n)
    # The reference's one pass comes before the optimizer's state is on the
    # device: it fits the head, and its loss is the one the job asks for.
    params, loss_ref = prepare(params, host_batch["tokens"],
                               host_batch["targets"], cell)
    # The finer comparison first, before the optimizer's state is on the
    # device too; a program whose float32 read is off the reference is
    # handed no room in the job's one check (``deepseek_v3.py``).
    batch = shard_batch(host_batch)
    loss_32 = float32_loss(cfg, mesh, params, batch)
    off = abs(loss_32 - loss_ref) / abs(loss_ref)
    print("yardstick: %s: the program read in float32 %.8g, %.3g off the "
          "plain reference %.8g (limit %g)"
          % (cell["name"], loss_32, off, loss_ref, FLOAT32_RTOL),
          file=sys.stderr)
    step, params, opt_state = build(params)

    def run_step(state, batch):
        params, opt_state, loss = step(state[0], state[1], batch)
        return (params, opt_state), loss

    return {
        "samples_per_step": n,
        "flops_per_sample": fg.train_flops_per_sequence(**_shapes(cell)),
        "grad_bytes": sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(params)),
        "kernels": _kernels(cell, n),
        "loss_rtol": spec.get("loss_rtol", LOSS_RTOL)
        if off <= FLOAT32_RTOL else 0.0,
        "step": run_step, "state": (params, opt_state),
        "batch": batch,
        "reference": lambda state: loss_ref,
        "probe": lambda state: state[0]["ln_f"],
    }


# -- the plain reference ---------------------------------------------------

def gated_delta_recurrence(q, k, v, g, beta, precision="highest"):
    """The gated delta rule with one decay a head, one time step after
    another, for one sequence: ``q, k`` ``[S, H, Dk]``, ``v`` ``[S, H, Dv]``,
    ``g, beta`` ``[S, H]``.  State ``[H, Dk, Dv]`` from zero:

        S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
        o_t = Dk^-1/2 S_t^T q_t
    """
    import jax.numpy as jnp
    from jax import lax
    hi = lax.Precision(precision)
    heads, dk = q.shape[1:]

    def step(state, now):
        q_t, k_t, v_t, g_t, b_t = now
        state = jnp.exp(g_t)[:, None, None] * state
        seen = jnp.einsum("hd,hde->he", k_t, state, precision=hi)
        state = state + (b_t[:, None] * k_t)[:, :, None] \
            * (v_t - seen)[:, None, :]
        return state, jnp.einsum("hde,hd->he", state, q_t, precision=hi)

    _, out = lax.scan(step, jnp.zeros((heads, dk, v.shape[-1]), q.dtype),
                      (q, k, v, g, beta))
    return out / math.sqrt(dk)


def reference_hidden(params, tokens, config, dtype="float32",
                     precision="highest", wrong=()):
    """The share's decoder in float32 at the highest matmul precision, from
    the layer equations (ISSUE 39; the assumed parts are the configuration
    file's ``assumed``): ``tokens`` ``[B, S]`` -> the hidden states after
    the final RMSNorm ``[B, S, hidden]``.  Blocks under OLMo 2's reordered
    norm: ``h = x + rms(mixer(x))``, then ``h + rms(swiglu(h))``, RMSNorm
    eps 1e-6.  Linear layers: ``q, k = l2norm(silu(conv4(x W)))``,
    ``v = silu(conv4(x W_v))``, ``g = -exp(A_log) softplus(x W_a +
    dt_bias)`` a head, ``beta = 2 sigmoid(x W_b)``,
    ``gated_delta_recurrence``, an RMSNorm over each head's 192 values times
    ``silu(x W_g)``, ``W_o``.  Full layers: q, k, v projections, an RMSNorm
    over the whole q and over the whole k, 30 heads of 128, causal softmax
    over ``q k^T / sqrt(128)`` with no positional encoding, a block of
    queries at a time, ``W_o``.  No kernels, no chunks, no sharding; a layer
    at a time, its mixer a sequence at a time.  It reads the parameter tree
    and nothing else of the program.  ``dtype``, ``precision`` and
    ``wrong`` are for the readings that show what the tolerances catch
    (PERF.md): the same arithmetic a precision below the configuration's
    (bfloat16, default), and with a part wrong (``WRONG``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    assert set(wrong) <= set(WRONG), wrong
    hi = lax.Precision(precision)
    eps = config["rms_norm_eps"]
    heads = config["num_attention_heads"]
    head = config["hidden_size"] // heads
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]

    def dot(a, b):
        return jnp.dot(a, b, precision=hi)

    def rms(x, w):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def conv(x, taps):          # causal, depthwise: [S, W], [n, W]
        n = taps.shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((n - 1,) + x.shape[1:], x.dtype), x])
        return sum(taps[j] * padded[j:j + x.shape[0]] for j in range(n))

    def l2norm(x):
        return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def linear_mixer(x, p):     # one sequence, [S, hidden]
        s = x.shape[0]

        def branch(name, size):
            return jax.nn.silu(conv(dot(x, p["w" + name]),
                                    p["conv_" + name])).reshape(s, -1, size)

        q, k, v = l2norm(branch("q", dk)), l2norm(branch("k", dk)), \
            branch("v", dv)
        g = -jnp.exp(p["a_log"]) * jax.nn.softplus(dot(x, p["w_a"])
                                                   + p["dt_bias"])
        if "no_decay" in wrong:
            g = jnp.zeros_like(g)
        beta = jax.nn.sigmoid(dot(x, p["w_beta"]))
        if "beta_below_one" not in wrong:
            beta = 2.0 * beta
        o = rms(gated_delta_recurrence(q, k, v, g, beta, precision),
                p["o_norm"])
        gate = (jax.nn.sigmoid if "sigmoid_gate" in wrong
                else jax.nn.silu)(dot(x, p["w_g"]))
        return dot(o.reshape(s, -1) * gate, p["wo"])

    def full_mixer(x, p):       # one sequence, [S, hidden]
        s = x.shape[0]
        q, k, v = dot(x, p["wq"]), dot(x, p["wk"]), dot(x, p["wv"])
        if "qk_norm_per_head" in wrong:
            q, k = (rms(y.reshape(s, heads, head), 1.0).reshape(s, -1) * w
                    for y, w in ((q, p["q_norm"]), (k, p["k_norm"])))
        else:
            q, k = rms(q, p["q_norm"]), rms(k, p["k_norm"])
        q, k, v = (y.reshape(s, -1, head) for y in (q, k, v))
        block = math.gcd(s, REFERENCE_QUERY_BLOCK)

        def rows(at):
            q_b = lax.dynamic_slice_in_dim(q, at * block, block)
            scores = jnp.einsum("qhd,khd->hqk", q_b, k, precision=hi) \
                / math.sqrt(head)
            seen = jnp.arange(s)[None, :] \
                <= (at * block + jnp.arange(block))[:, None]
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                                   -1)
            return jnp.einsum("hqk,khd->qhd", probs, v, precision=hi)

        attn = lax.map(rows, jnp.arange(s // block)).reshape(s, -1)
        return dot(attn, p["wo"])

    def swiglu(x, p):
        return dot(jax.nn.silu(dot(x, p["w1"])) * dot(x, p["w3"]), p["w2"])

    mixers = {"linear_attention": linear_mixer, "full_attention": full_mixer}
    kinds = _layer_types(config)
    with jax.default_matmul_precision(precision):
        p = jax.tree.map(lambda w: w.astype(dtype), params)
        x = p["embed"][tokens]
        for period in range(config["num_hidden_layers"] // len(kinds)):
            for kind, stacked in zip(kinds, p["layers"]):
                lp = jax.tree.map(lambda w: w[period], stacked)

                def mix(h):
                    return jax.vmap(lambda seq: mixers[kind](seq, lp))(h)

                if "pre_norm" in wrong:
                    x = x + mix(rms(x, lp["ln1"]))
                    x = x + swiglu(rms(x, lp["ln2"]), lp)
                else:
                    x = x + rms(mix(x), lp["ln1"])
                    x = x + rms(swiglu(x, lp), lp["ln2"])
        return rms(x, p["ln_f"])


def reference_loss_fn(params, tokens, targets, config, **reading):
    """Mean next-token cross entropy over the vocabulary slice of
    ``tokens``, ``targets`` ``[B, S]``.  A reading a precision below rounds
    the hidden states and the head as it rounds everything; the logits'
    sums and the cross entropy stay float32, as the program's do."""
    import jax.numpy as jnp
    x = reference_hidden(params, tokens, config, **reading)
    head = params["head"].astype(x.dtype).astype(jnp.float32)
    return sum(reference_nll_sum(x[i].astype(jnp.float32), head, targets[i])
               for i in range(x.shape[0])) / targets.size


# -- compiled for a chip that is not attached (rehearse.py compile) --------

def aot_step(cell, devices):
    """[(label, jitted, abstract arguments)] of the cell's step over
    described ``devices``, assembled from the public pieces of
    ``make_train_step`` as ``builders/solar_open2.py: aot_step`` does and
    for its reason, then the builder's float32 read of the loss (a second
    program the run makes on the chip).  They stand for the programs only as
    a rehearsal."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.common import scopes
    from horovod_tpu.models import transformer
    cfg, spec = _model_config(cell), cell["spec"]
    mesh = jax.sharding.Mesh(
        np.asarray(devices).reshape(len(devices), 1, 1),
        (cfg.dp_axis, cfg.sp_axis, cfg.tp_axis))
    optimizer = _optimizer(cell)
    specs = transformer.param_specs(cfg)
    params = jax.eval_shape(lambda key: transformer.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    o_specs = transformer.opt_spec_tree(opt_state, params, specs)
    n = spec["batch_per_chip"] * len(devices)
    rows = {k: P(cfg.dp_axis, cfg.sp_axis) for k in ("tokens", "targets")}

    def local_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(jax.named_scope(scopes.MODEL)(
            lambda p: transformer.loss_fn(p, batch, cfg)))(params)
        with jax.named_scope(scopes.OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

    step = jax.jit(jax.shard_map(
        local_step, mesh=mesh, in_specs=(specs, o_specs, rows),
        out_specs=(specs, o_specs, P()), check_vma=True),
        donate_argnums=(0, 1))
    cfg_32 = dataclasses.replace(cfg, dtype="float32")

    def loss_32(p, b):
        with jax.default_matmul_precision("highest"):
            return transformer.loss_fn(p, b, cfg_32)

    read = jax.jit(jax.shard_map(loss_32, mesh=mesh, in_specs=(specs, rows),
                                 out_specs=P(), check_vma=True))

    def on(tree, spec_tree):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            tree, spec_tree)

    batch = {k: jax.ShapeDtypeStruct((n, spec["seq_len"]), jnp.int32,
                                     sharding=NamedSharding(mesh, rows[k]))
             for k in rows}
    return [("make_train_step(%s)" % cell["name"], step,
             (on(params, specs), on(opt_state, o_specs), batch)),
            ("float32_loss(%s)" % cell["name"], read,
             (on(params, specs), batch))]
