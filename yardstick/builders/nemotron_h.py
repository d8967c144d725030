"""Nemotron-3-Nano-30B-A3B's decoder (``model_type: nemotron_h``) through
``horovod_tpu/models/transformer.py``: a pre-training step of one chip's
share of a 16-chip layer group (the configuration's file says how it was
cut), with the plain float32 reference written from the layer equations
beside it.

Every block of the model is ONE sub-layer under one pre-norm, ``x = x +
sub(rms_norm(x))``, and ``hybrid_override_pattern`` says which: ``M`` a
Mamba-2 state-space mixer (64 heads of 64 channels, 8 groups, state 128,
conv 4), ``E`` 128 routed two-matrix ``relu^2`` experts (6 a token) beside
a shared one, ``*`` causal attention of 32 query heads over 2 key/value
heads of 128 with no positional encoding.  The chip holds every mixer
whole, 8 experts of every ``E`` block and a slice of the vocabulary; what
the absent experts would add is left out, in the program and in the
reference alike.
"""

import math

import numpy as np

from yardstick import flops
from yardstick import flops_ssm as fs
from yardstick import measure
from yardstick.builders.laguna import _layer_params
from yardstick.builders.solar_open2 import (ROUTER_FIT_WITHIN, _optimizer,
                                            fit_router_bias, load_targets,
                                            make_batch, reference_nll_sum)

# Step-0 loss of the program (bf16 activations; float32 router, dt, decays
# and states) against the float32 reference at the timed sizes, under a
# head fitted to its batch (``HEAD_FIT``, ``prepare``; ``solar_open2.py``
# says why that gives the check its teeth).  Found on the chip (my chip
# runs, PR 33; PERF.md section 6): the program 2.12e-3 to 2.70e-3 over 13
# seeds (mean 2.39e-3, deviation 0.18e-3); the same reference a precision
# below (bfloat16 weights, activations, decays and states, default
# products) 3.22e-3 to 4.80e-3 over eight of them; the ``D x`` skip left
# out 1.31, the convolution 1.52, ``dt_bias`` 0.55, the gated norm over all
# 4096 channels 0.11, ``relu`` for ``relu^2`` 0.47.  The limit lies between
# the first two, 1.11 times over the one and 1.07 under the other (they
# lie close, it seems, because most of either is tokens whose sixth expert
# changes when a score moves in its fourth digit, which the program's
# bfloat16 hidden states and the lower reading's share; not measured).  A rotary turn put into the one attention block
# reads 1.25e-3 in the reference (random queries and keys weigh 8192 values
# nearly evenly, turned or not): under the program's own rounding, no limit
# that holds the seeds can see it, and the tier-1 tests hold it instead
# (``tests/test_nemotron_decoder.py``: 10 % at the tiny size).
LOSS_RTOL = 0.003
# The head's random start plus ``HEAD_FIT / hidden`` times, in column ``j``,
# the sum of the reference's final hidden states of the tokens whose target
# is ``j``: a target logit of about ``HEAD_FIT`` before the step.
HEAD_FIT = 8.0

REFERENCE_QUERY_BLOCK = 512     # queries the reference's softmax holds at once
# What the reference can get wrong on purpose, for the readings that show
# what the tolerance catches: the scan's ``D x`` skip left out, the
# convolution left out (``silu`` of the projection alone), ``dt_bias`` left
# out, the gated norm over all 4096 channels at once and not over groups of
# 512, ``relu`` for ``relu^2`` in the experts, a rotary turn put into the
# attention block (``rope_theta``, the whole head).
WRONG = ("no_d_skip", "no_conv", "no_dt_bias", "norm_over_all", "relu",
         "rotary")

BLOCKS = "ME*"       # a state-space mixer, an expert layer, attention


def _pattern(c):
    """The blocks held, as the source's ``hybrid_override_pattern`` names
    them."""
    pattern = c["hybrid_override_pattern"]
    if len(pattern) != c["num_hidden_layers"] or set(pattern) - set(BLOCKS):
        raise ValueError("%r does not name %d blocks out of %s"
                         % (pattern, c["num_hidden_layers"], BLOCKS))
    return pattern


def _model_config(cell):
    from horovod_tpu.models import transformer
    if "state_space" not in transformer.MIXERS:
        raise measure.Refused(
            "this horovod_tpu has no state-space mixer and no block of one "
            "sub-layer in models/transformer.py: it cannot run %s"
            % cell["name"])
    from horovod_tpu.models.state_space import SsmConfig
    from horovod_tpu.parallel.moe import ExpertShare
    c, spec = cell["config"], cell["spec"]
    attention = transformer.SoftmaxAttention(
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        window=c["sliding_window"], rope=None, gate=False)
    entry = {"M": ("state_space", None), "E": (None, "expert_share"),
             "*": (attention, None)}
    return transformer.TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_size=c["head_dim"],
        d_ff=c["intermediate_size"], max_seq=spec["seq_len"],
        norm_eps=c["layer_norm_epsilon"], dtype=c["activation_dtype"],
        param_dtype=c["param_dtype"], remat=True,
        layer_pattern=tuple(entry[kind] for kind in _pattern(c)),
        state_space=SsmConfig(
            n_heads=c["mamba_num_heads"], head_size=c["mamba_head_dim"],
            n_groups=c["n_groups"], state_size=c["ssm_state_size"],
            conv_size=c["conv_kernel"], chunk=c["chunk_size"],
            norm_eps=c["layer_norm_epsilon"], dt_min=c["time_step_min"],
            dt_max=c["time_step_max"], dt_floor=c["time_step_floor"]),
        experts=ExpertShare(
            n_experts=c["published"]["n_routed_experts"],
            first=c["held"]["first_expert"], count=c["n_routed_experts"],
            top_k=c["num_experts_per_tok"], d_model=c["hidden_size"],
            d_ff=c["moe_intermediate_size"],
            d_shared=c["n_shared_experts"]
            * c["moe_shared_expert_intermediate_size"],
            routed_scaling=float(c["routed_scaling_factor"]),
            block_rows=spec["expert_block_rows"],
            form=c["mlp_hidden_act"]),
        tie_embeddings=c["tie_word_embeddings"],
        head_block=spec["head_block"])


def prepare(params, tokens, targets, cell):
    """What the builder sets before the first step, in one pass of the
    plain reference over the batch: every ``E`` block's ``router_bias``
    (``fit_router_bias`` on that block's reference scores, so the loads
    follow the cell's profile), the head fitted to the batch
    (``HEAD_FIT``), and the reference's loss of the state so set.  Returns
    (the parameters, the loss, the loads ``[E blocks, experts]``)."""
    import jax
    import jax.numpy as jnp
    config = cell["config"]
    goal = load_targets(cell, tokens.size)

    def one_pass(params, tokens, targets):
        x, loads, biases = reference_hidden(
            params, tokens, config,
            router_bias=lambda scores: fit_router_bias(
                scores, goal, config["num_experts_per_tok"])[0])
        hidden = x.shape[-1]
        fit = jnp.zeros((params["head"].shape[1], hidden), jnp.float32) \
            .at[targets.reshape(-1)].add(x.reshape(-1, hidden))
        head = params["head"] + (HEAD_FIT / hidden) * fit.T
        loss = sum(reference_nll_sum(x[i], head, targets[i])
                   for i in range(x.shape[0])) / targets.size
        return biases, head.astype(params["head"].dtype), loads, loss

    biases, head, loads, loss = jax.jit(one_pass)(params, tokens, targets)
    # One period holds every block; the biases come in the blocks' order.
    sparse = [at for at, kind in enumerate(_pattern(config)) if kind == "E"]
    layers = tuple(
        dict(lp, router_bias=biases[sparse.index(at)][None].astype(
            lp["router_bias"].dtype)) if at in sparse else lp
        for at, lp in enumerate(params["layers"]))
    return (dict(params, head=head, layers=layers), float(loss),
            np.asarray(loads))


def _shapes(cell):
    c, spec = cell["config"], cell["spec"]
    return dict(
        seq=spec["seq_len"], hidden=c["hidden_size"], vocab=c["vocab_size"],
        pattern=_pattern(c), ssm_heads=c["mamba_num_heads"],
        ssm_head=c["mamba_head_dim"], groups=c["n_groups"],
        state=c["ssm_state_size"], conv=c["conv_kernel"],
        chunk=c["chunk_size"], q_heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"], head=c["head_dim"],
        experts=c["published"]["n_routed_experts"],
        held=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        shared_width=c["n_shared_experts"]
        * c["moe_shared_expert_intermediate_size"])


def _kernels(cell, samples):
    """The state-space scans, the routed experts' products and the flash
    kernels' calls of one step, for ``readers/scope_roofline.py`` and
    ``readers/kernel_roofline.py``."""
    sh = _shapes(cell)
    return [
        {"kernel": "ssd_core", "calls_per_step": sh["pattern"].count("M"),
         "per_call": fs.ssd_cost(samples, sh["seq"], sh["ssm_heads"],
                                 sh["ssm_head"], sh["groups"], sh["state"],
                                 sh["chunk"])},
        {"kernel": "experts", "calls_per_step": sh["pattern"].count("E"),
         "per_call": fs.expert_products_cost(
             fs.expected_pairs(samples * sh["seq"], sh["top_k"], sh["held"],
                               sh["experts"]),
             sh["held"], sh["hidden"], sh["expert_width"])},
        # Every query head's own pass over its (repeated) key/value head.
        {"kernel": "flash", "calls_per_step": sh["pattern"].count("*"),
         "per_call": flops.flash_attention_cost(
             samples, sh["q_heads"], sh["seq"], sh["head"], causal=True)},
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
    # device: it sets the balancing buffers and the head, and its loss is
    # the one the job asks for below.
    params, loss_ref, loads = prepare(
        params, host_batch["tokens"], host_batch["targets"], cell)
    goal = load_targets(cell, host_batch["tokens"].size)
    if np.abs(loads - goal).max() > 2 * ROUTER_FIT_WITHIN * goal.mean():
        raise ValueError("the routers' loads are not the cell's profile: "
                         "%s against %s" % (loads.tolist(), goal.tolist()))
    step, params, opt_state = build(params)

    def run_step(state, batch):
        params, opt_state, loss = step(state[0], state[1], batch)
        return (params, opt_state), loss

    return {
        "samples_per_step": n,
        "flops_per_sample": fs.train_flops_per_sequence(**_shapes(cell)),
        "grad_bytes": sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(params)),
        "kernels": _kernels(cell, n),
        "loss_rtol": spec.get("loss_rtol", LOSS_RTOL),
        "step": run_step, "state": (params, opt_state),
        "batch": shard_batch(host_batch),
        "reference": lambda state: loss_ref,
        "probe": lambda state: state[0]["ln_f"],
    }


# -- the plain reference ---------------------------------------------------

def ssm_recurrence(x, dt, a, b, c, d_skip):
    """The state-space layer one time step after another, for one sequence:
    ``x`` ``[S, H, P]``, ``dt`` ``[S, H]``, ``a``, ``d_skip`` ``[H]``, ``b``,
    ``c`` ``[S, G, N]``; head ``h`` reads group ``h // (H / G)``.  State
    ``[H, P, N]`` from zero:

        H_t = exp(dt_t a) H_{t-1} + dt_t x_t B_t^T
        y_t = H_t C_t + D x_t
    """
    import jax.numpy as jnp
    from jax import lax
    heads, p = x.shape[1:]
    per = heads // b.shape[1]

    def step(state, now):
        x_t, dt_t, b_t, c_t = now
        b_t, c_t = jnp.repeat(b_t, per, 0), jnp.repeat(c_t, per, 0)
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], -1) \
            + d_skip[:, None] * x_t

    _, y = lax.scan(step, jnp.zeros((heads, p, b.shape[-1]), x.dtype),
                    (x, dt, b, c))
    return y


def reference_mixer(x, p, config, precision="highest", wrong=()):
    """An ``M`` block's mixer over one sequence, ``x`` ``[S, hidden]``
    normed: ``in_proj`` to ``z`` (4096), ``xBC`` (4096 + 2 x 8 x 128) and
    ``dt`` (64); ``xBC = silu(conv4(xBC) + bias)``, causal and depthwise;
    ``dt = softplus(dt + dt_bias)`` with no clamp; ``A = -exp(A_log)``;
    ``ssm_recurrence``; ``y silu(z)``, then RMSNorm over each group of
    ``4096 / 8`` channels under a 4096-wide scale; ``out_proj``."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    hi = lax.Precision(precision)
    seq = x.shape[0]
    heads, head = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, state = config["n_groups"], config["ssm_state_size"]
    inner, gn = heads * head, groups * state

    def dot(a, b):
        return jnp.dot(a, b, precision=hi)

    def conv(y, taps):          # causal, depthwise: [S, W], [n, W]
        n = taps.shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((n - 1,) + y.shape[1:], y.dtype), y])
        return sum(taps[j] * padded[j:j + seq] for j in range(n))

    zxbcdt = dot(x, p["in_proj"])
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * gn],
                  zxbcdt[:, 2 * inner + 2 * gn:])
    if "no_conv" not in wrong:
        xbc = conv(xbc, p["conv_w"]) + p["conv_b"]
    xbc = jax.nn.silu(xbc)
    if "no_dt_bias" not in wrong:
        dt = dt + p["dt_bias"]
    d_skip = jnp.zeros_like(p["d_skip"]) if "no_d_skip" in wrong \
        else p["d_skip"]
    y = ssm_recurrence(
        xbc[:, :inner].reshape(seq, heads, head), jax.nn.softplus(dt),
        -jnp.exp(p["a_log"]),
        xbc[:, inner:inner + gn].reshape(seq, groups, state),
        xbc[:, inner + gn:].reshape(seq, groups, state), d_skip)
    y = y.reshape(seq, inner) * jax.nn.silu(z)
    y = y.reshape(seq, 1 if "norm_over_all" in wrong else groups, -1)
    y = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                      + config["layer_norm_epsilon"])
    return dot(y.reshape(seq, inner) * p["ssm_norm"], p["out_proj"])


def reference_attention(x, p, config, precision="highest", wrong=()):
    """A ``*`` block's mixer over one sequence: 32 query heads of 128 over
    2 key/value heads, query head ``h`` on key/value head ``h // 16``,
    scores over ``sqrt(128)``, causal, no positional encoding; the textbook
    softmax a block of queries at a time; ``W_o``."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    hi = lax.Precision(precision)
    seq, head = x.shape[0], config["head_dim"]
    q = jnp.dot(x, p["wq"], precision=hi).reshape(seq, -1, head)
    k = jnp.dot(x, p["wk"], precision=hi).reshape(seq, -1, head)
    v = jnp.dot(x, p["wv"], precision=hi).reshape(seq, -1, head)
    if "rotary" in wrong:
        i = np.arange(head // 2, dtype=np.float64)
        angle = np.arange(seq, dtype=np.float64)[:, None] \
            * float(config["rope_theta"]) ** (-2.0 * i / head)
        cos, sin = (jnp.asarray(t, x.dtype)[:, None, :]
                    for t in (np.cos(angle), np.sin(angle)))

        def turn(y):
            y1, y2 = y[..., :head // 2], y[..., head // 2:]
            return jnp.concatenate([y1 * cos - y2 * sin,
                                    y1 * sin + y2 * cos], -1)

        q, k = turn(q), turn(k)
    reads = jnp.arange(q.shape[1]) // (q.shape[1] // k.shape[1])
    k, v = k[:, reads], v[:, reads]
    block = math.gcd(seq, REFERENCE_QUERY_BLOCK)

    def rows(at):
        q_b = lax.dynamic_slice_in_dim(q, at * block, block)
        scores = jnp.einsum("qhd,khd->hqk", q_b, k, precision=hi) \
            / math.sqrt(head)
        seen = jnp.arange(seq)[None, :] \
            <= (at * block + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=hi)

    attn = lax.map(rows, jnp.arange(seq // block)).reshape(seq, -1)
    return jnp.dot(attn, p["wo"], precision=hi)


def reference_expert_layer(x, p, config, precision="highest", wrong=(),
                           router_bias=None):
    """An ``E`` block's sub-layer over every token of the step, ``x`` ``[T,
    hidden]`` normed: (its output, the tokens every expert got, the
    balancing bias the experts were chosen under).  Sigmoid scores over
    every expert of the layer; the ``num_experts_per_tok`` with the largest
    score + bias (one group: a plain top-k); their scores (without it)
    renormalised to sum to the scaling factor; the held experts' ``relu(x
    W_1)^2 W_2`` one after another, each over every token under its weight
    (0 where the token did not choose it); plus the shared expert's."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    hi = lax.Precision(precision)
    first = config["held"]["first_expert"]
    assert config["mlp_hidden_act"] == "relu2" and config["n_group"] == 1 \
        and config["norm_topk_prob"], config

    def dot(a, b):
        return jnp.dot(a, b, precision=hi)

    def expert(w1, w2):
        up = jax.nn.relu(dot(x, w1))
        return dot(up if "relu" in wrong else up * up, w2)

    scores = jax.nn.sigmoid(dot(x, p["router"]))
    bias = p["router_bias"] if router_bias is None \
        else router_bias(scores.astype(jnp.float32)).astype(scores.dtype)
    _, ids = lax.top_k(scores + bias, config["num_experts_per_tok"])
    loads = jnp.sum(ids[:, :, None] == jnp.arange(scores.shape[-1]), (0, 1))
    top = jnp.take_along_axis(scores, ids, -1)
    weights = top / top.sum(-1, keepdims=True) \
        * config["routed_scaling_factor"]

    def add(y, held):
        j, w1, w2 = held
        w_j = jnp.sum(jnp.where(ids == first + j, weights, 0.0), -1)
        return y + w_j[:, None] * expert(w1, w2), None

    y, _ = lax.scan(add, expert(p["ws1"], p["ws2"]),
                    (jnp.arange(p["we1"].shape[0]), p["we1"], p["we2"]))
    return y, loads, bias


def reference_hidden(params, tokens, config, dtype="float32",
                     precision="highest", wrong=(), router_bias=None):
    """The share's decoder in float32 at the highest matmul precision, from
    the layer equations (ISSUE 33; the assumed parts are the configuration
    file's ``assumed``): ``tokens`` ``[B, S]`` -> (the hidden states after
    the final RMSNorm ``[B, S, hidden]``, the tokens every expert of every
    ``E`` block got ``[E blocks, experts]``, the balancing bias each chose
    its experts under, the same shape).  Every block is ``x + sub(rms(x))``
    with one norm (``ln1`` of a mixer, ``ln2`` of an expert layer) and
    ``sub`` one of ``reference_mixer`` (the token-by-token recurrence),
    ``reference_attention`` and ``reference_expert_layer``.  No kernels, no
    chunks, no sort, no sharding; a block at a time, a mixer a sequence at a
    time.  It reads the parameter tree and nothing else of the program.
    ``router_bias`` (scores ``[T, experts]`` -> bias) replaces the
    parameters' buffer: ``prepare`` fits it there.  ``dtype``,
    ``precision`` and ``wrong`` are for the readings that show what the
    loss tolerance catches (PERF.md): the same arithmetic a precision below
    the configuration's (bfloat16 throughout, the recurrence's state and
    decays too, default products), and with a part left out or wrong
    (``WRONG``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    assert set(wrong) <= set(WRONG), wrong
    eps = config["layer_norm_epsilon"]

    def rms(x, w):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    with jax.default_matmul_precision(precision):
        p = jax.tree.map(lambda w: w.astype(dtype), params)
        x, loads, biases = p["embed"][tokens], [], []
        for kind, lp in zip(_pattern(config), _layer_params(p)):
            if kind == "E":
                y, load, bias = reference_expert_layer(
                    rms(x, lp["ln2"]).reshape(-1, x.shape[-1]), lp, config,
                    precision, wrong, router_bias)
                x = x + y.reshape(x.shape)
                loads.append(load)
                biases.append(bias)
                continue
            mixer = reference_mixer if kind == "M" else reference_attention
            x = x + lax.map(
                lambda h: mixer(h, lp, config, precision, wrong),
                rms(x, lp["ln1"]))
        return rms(x, p["ln_f"]), jnp.stack(loads), jnp.stack(biases)


def reference_loss_fn(params, tokens, targets, config, **reading):
    """Mean next-token cross entropy over the vocabulary slice of
    ``tokens``, ``targets`` ``[B, S]``.  A reading a precision below rounds
    the hidden states and the head as it rounds everything; the logits'
    sums and the cross entropy stay float32, as the program's do."""
    import jax.numpy as jnp
    x = reference_hidden(params, tokens, config, **reading)[0]
    head = params["head"].astype(x.dtype).astype(jnp.float32)
    return sum(reference_nll_sum(x[i].astype(jnp.float32), head, targets[i])
               for i in range(x.shape[0])) / targets.size


def reference_loss(params, host_batch, config):
    import jax
    device = sorted(jax.tree.leaves(params)[0].devices(),
                    key=lambda d: d.id)[0]
    tokens, targets = (jax.device_put(host_batch[k], device)
                       for k in ("tokens", "targets"))
    return float(jax.jit(
        lambda p, t, y: reference_loss_fn(p, t, y, config))(
            jax.device_put(params, device), tokens, targets))


# -- compiled for a chip that is not attached (rehearse.py compile) --------

def aot_step(cell, devices):
    """[(label, jitted, abstract arguments)] of the cell's step over
    described ``devices``, assembled from the public pieces of
    ``make_train_step`` as ``builders/solar_open2.py: aot_step`` does and
    for its reason.  It stands for the program only as a rehearsal."""
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

    def on(tree, spec_tree):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            tree, spec_tree)

    batch = {k: jax.ShapeDtypeStruct((n, spec["seq_len"]), jnp.int32,
                                     sharding=NamedSharding(mesh, rows[k]))
             for k in rows}
    return [("make_train_step(%s)" % cell["name"], step,
             (on(params, specs), on(opt_state, o_specs), batch))]
