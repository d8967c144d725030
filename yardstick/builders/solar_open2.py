"""Solar-Open2's hybrid decoder through ``horovod_tpu/models/transformer.py``:
a pre-training step of one chip's share of a 40-chip layer group (the
configuration's file says how it was cut), with the plain float32
reference written from the layer equations beside it.

A period of the model is one softmax layer (no positional encoding, grouped
queries, an output gate) and three linear-attention layers (gated delta
rule with a decay for every channel), every layer with a mixture of routed
SwiGLU experts beside a shared expert.  The chip holds some heads of every
mixer, some experts of every layer and a slice of the vocabulary; what the
absent heads and experts would add is left out, in the program and in the
reference alike.
"""

import math

import numpy as np

from yardstick import flops
from yardstick import flops_hybrid as fh
from yardstick import measure

# Step-0 loss of the program (bf16 activations, float32 state in the delta
# rule, float32 router) against the float32 reference at the timed sizes.
# Under a random head the mean of 16,384 cross entropies hardly tells a
# wrong hidden state from a right one (each token's error has its own sign:
# a left-out linear gate moved it 4e-4, rounding 3e-5), so the head starts
# fitted to its batch (``HEAD_FIT``, ``prepare``): column ``j`` holds the
# reference's final hidden states of the tokens whose target is ``j``.
# Every token's target logit then falls with the square of the angle between
# the program's hidden state and the reference's, all with one sign, and the
# loss reads their mean.  Found on the chip (my chip runs, PR 27; PERF.md
# section 6): the program 1.07e-3 to 1.54e-3 over 20 runs (mean 1.36e-3,
# deviation 0.12e-3); the same reference a precision below (bfloat16 weights
# and activations, default products) 2.35e-3 to 2.57e-3 over 3; the routed
# experts left out 2.9e-2, a gate 0.9 to 1.2, the decay 1.3.  The limit lies
# between the first two, 1.3 times over the one and 1.2 under the other: the
# program is itself bfloat16 but for its float32 islands, and they are all
# the lower precision takes away.
LOSS_RTOL = 0.002
# Scale of the fit: the head's random start plus ``HEAD_FIT / hidden`` times
# the sum of those hidden states (a target logit of about ``HEAD_FIT``
# before the step, a loss near 3 of ln 24576 = 10.1: far from both ends).
HEAD_FIT = 8.0
# ``fit_router_bias`` stops when every expert's load is within this share
# of the mean load of its target, or after so many rounds.
ROUTER_FIT_WITHIN = 0.02
ROUTER_FIT_ROUNDS = 400

REFERENCE_QUERY_BLOCK = 1024    # queries the reference's softmax holds at once
REFERENCE_HEAD_BLOCK = 1024     # tokens whose logits it holds at once
# Parts the reference can leave out, for the readings that show what the
# tolerance catches: the routed experts' output, the decay exp(g), the
# linear mixer's output gate, the softmax mixer's.
WITHOUT = ("routed", "decay", "linear_gate", "gqa_gate")


def _pattern(c):
    """One period of (mixer, feed-forward) pairs, from the source's
    ``gqa_layers`` and ``first_k_dense_replace``."""
    softmax = set(c["gqa_layers"])
    period = c["gqa_interval"] + 1
    if c["first_k_dense_replace"] or c["num_hidden_layers"] % period:
        raise ValueError("the builder runs whole periods of %d expert layers"
                         % period)
    return tuple(("gated_nope_attention" if at in softmax
                  else "linear_attention", "expert_share")
                 for at in range(period))


def _model_config(cell):
    from horovod_tpu.models import transformer
    if not hasattr(transformer.TransformerConfig, "layer_pattern"):
        raise measure.Refused(
            "this horovod_tpu has no per-layer pattern in "
            "models/transformer.py: it cannot run %s" % cell["name"])
    from horovod_tpu.models.linear_attention import KdaConfig
    from horovod_tpu.parallel.moe import ExpertShare
    c, spec = cell["config"], cell["spec"]
    lin, held = c["linear_attn_config"], c["held"]
    return transformer.TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_size=c["head_dim"],
        d_ff=c["intermediate_size"], max_seq=spec["seq_len"],
        norm_eps=c["rms_norm_eps"], dtype=c["activation_dtype"],
        param_dtype=c["param_dtype"], remat=True, layer_pattern=_pattern(c),
        linear_attention=KdaConfig(
            n_heads=lin["num_heads"], head_size=lin["head_dim"],
            conv_size=lin["short_conv_kernel_size"],
            gate_rank=c["assumed_sizes"]["gate_rank"],
            chunk=spec["delta_rule_chunk"], norm_eps=c["rms_norm_eps"]),
        experts=ExpertShare(
            n_experts=c["published"]["n_routed_experts"],
            first=held["first_expert"], count=c["n_routed_experts"],
            top_k=c["num_experts_per_tok"], d_model=c["hidden_size"],
            d_ff=c["moe_intermediate_size"],
            d_shared=c["n_shared_experts"] * c["moe_intermediate_size"],
            routed_scaling=float(c["routed_scaling_factor"]),
            block_rows=spec["expert_block_rows"]),
        tie_embeddings=c["tie_word_embeddings"],
        head_block=spec["head_block"])


def make_batch(cell, seed, samples):
    """Pre-training sequences: ids uniform over the vocabulary slice,
    documents run together with no mask, targets the ids shifted by one
    (the last position predicts one more drawn id)."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cell["config"]["vocab_size"],
                       (samples, cell["spec"]["seq_len"] + 1), np.int32)
    return {"tokens": ids[:, :-1].copy(), "targets": ids[:, 1:].copy()}


def _optimizer(cell):
    """AdamW with the linear warm-up a pre-training run starts with (2000
    steps, as DeepSeek-V3's report gives for the family these routing keys
    come from).  The job repeats one batch: at the full rate from step 0
    twenty steps learn it by heart and the routing moves with every step
    inside the window (PERF.md, PR 27)."""
    import optax
    opt = cell["config"]["optimizer"]
    assert opt["name"] == "adamw", opt
    return optax.adamw(
        optax.linear_schedule(0.0, opt["learning_rate"], opt["warmup_steps"]),
        weight_decay=opt["weight_decay"])


def load_targets(cell, tokens):
    """Tokens every expert of a layer is to get in a step of ``tokens``
    tokens, ``[experts]``: the held experts the cell's
    ``expert_load_profile`` times the mean load, the others the rest in
    equal parts."""
    c = cell["config"]
    n, held, first = (c["published"]["n_routed_experts"],
                      c["n_routed_experts"], c["held"]["first_expert"])
    profile = np.asarray(cell["spec"]["expert_load_profile"], np.float64)
    assert profile.shape == (held,), profile
    shares = np.full(n, (n - profile.sum()) / (n - held))
    shares[first:first + held] = profile
    return shares * tokens * c["num_experts_per_tok"] / n


def fit_router_bias(scores, targets, top_k):
    """The balancing buffer under which the experts' loads over ``scores``
    ``[T, experts]`` are ``targets``: every round counts the loads of
    ``top_k(scores + bias)`` and moves an expert's bias against the
    logarithm of its load over its target, by a fifteenth of it to begin
    with (near the choice's threshold a load answers its bias by about
    ``exp(15 b)``: the hazard of a normal logit two deviations out, 2.4,
    over the sigmoid's slope there, about 0.16); an expert whose load has
    crossed its target halves its step.  Plain arithmetic on the
    reference's scores; nothing of the program.  Returns the bias and the
    rounds it took."""
    import jax.numpy as jnp
    from jax import lax
    experts = scores.shape[-1]
    targets = jnp.asarray(targets, jnp.float32)
    within = ROUTER_FIT_WITHIN * targets.mean()

    def off_under(bias):
        _, ids = lax.top_k(scores + bias, top_k)
        loads = jnp.zeros(experts, jnp.float32).at[ids.reshape(-1)].add(1.0)
        return loads - targets, jnp.log((loads + 0.5) / (targets + 0.5))

    def unfinished(state):
        return (jnp.abs(state["off"]).max() > within) \
            & (state["rounds"] < ROUTER_FIT_ROUNDS)

    def one_round(state):
        bias = state["bias"] - state["step"] * state["log_off"]
        off, log_off = off_under(bias)
        crossed = log_off * state["log_off"] < 0
        return {"bias": bias, "off": off, "log_off": log_off,
                "step": jnp.where(crossed, state["step"] / 2, state["step"]),
                "rounds": state["rounds"] + 1}

    bias = jnp.zeros(experts, jnp.float32)
    off, log_off = off_under(bias)
    fitted = lax.while_loop(unfinished, one_round, {
        "bias": bias, "off": off, "log_off": log_off,
        "step": jnp.full(experts, 1 / 15.0, jnp.float32),
        "rounds": 0})
    return fitted["bias"], fitted["rounds"]


def prepare(params, tokens, targets, cell):
    """What the builder sets before the first step, in one pass of the
    plain reference over the batch: every expert layer's ``router_bias``
    (``fit_router_bias`` on that layer's reference scores, so the loads
    follow the cell's profile), the head fitted to the batch
    (``HEAD_FIT``), and the reference's loss of the state so set.  Returns
    (the parameters, the loss, the loads ``[layers, experts]``)."""
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
    period = len(params["layers"])
    layers = tuple(
        dict(lp, router_bias=biases[at::period].astype(
            lp["router_bias"].dtype))
        for at, lp in enumerate(params["layers"]))
    return (dict(params, head=head, layers=layers), float(loss),
            np.asarray(loads))


def _shapes(cell):
    c, spec = cell["config"], cell["spec"]
    lin = c["linear_attn_config"]
    return dict(
        seq=spec["seq_len"], hidden=c["hidden_size"],
        vocab=c["vocab_size"], pattern=_pattern(c),
        periods=c["num_hidden_layers"] // len(_pattern(c)),
        q_heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head=c["head_dim"], lin_heads=lin["num_heads"],
        lin_head=lin["head_dim"], conv=lin["short_conv_kernel_size"],
        gate_rank=c["assumed_sizes"]["gate_rank"],
        chunk=spec["delta_rule_chunk"],
        experts=c["published"]["n_routed_experts"],
        held=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        shared_width=c["n_shared_experts"] * c["moe_intermediate_size"])


def _kernels(cell, samples):
    """The delta-rule cores, the routed experts' products and the flash
    kernels' calls of one step, for ``readers/scope_roofline.py`` and
    ``readers/kernel_roofline.py``."""
    sh = _shapes(cell)
    linear = sum(m == "linear_attention" for m, _ in sh["pattern"])
    return [
        {"kernel": "kda_core", "calls_per_step": linear * sh["periods"],
         "per_call": fh.delta_rule_cost(samples, sh["seq"], sh["lin_heads"],
                                        sh["lin_head"], sh["chunk"])},
        {"kernel": "experts",
         "calls_per_step": len(sh["pattern"]) * sh["periods"],
         "per_call": fh.expert_products_cost(
             fh.expected_pairs(samples * sh["seq"], sh["top_k"], sh["held"],
                               sh["experts"]),
             sh["held"], sh["hidden"], sh["expert_width"])},
        # Every query head's own pass over its (repeated) key/value head.
        {"kernel": "flash",
         "calls_per_step": (len(sh["pattern"]) - linear) * sh["periods"],
         "per_call": flops.flash_attention_cost(
             samples, sh["q_heads"], sh["seq"], sh["head"], causal=True)},
    ]


def jit_step(cell, seed, hvd, devices):
    """``make_train_step`` over a (dp, sp, tp) = (chips, 1, 1) mesh."""
    import jax

    from horovod_tpu.models.transformer import init_params, make_train_step
    cfg = _model_config(cell)
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
        "flops_per_sample": fh.train_flops_per_sequence(**_shapes(cell)),
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

def kda_recurrence(q, k, v, g, beta, precision="highest"):
    """The gated delta rule with a decay for every channel, one time step
    after another, for one sequence: ``q, k, v, g`` ``[S, H, D]``, ``beta``
    ``[S, H]``.  State ``[H, D, D]`` from zero:

        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        o_t = D^-1/2 S_t^T q_t
    """
    import jax.numpy as jnp
    from jax import lax
    hi = lax.Precision(precision)
    heads, d = q.shape[1:]

    def step(state, now):
        q_t, k_t, v_t, g_t, b_t = now
        state = jnp.exp(g_t)[:, :, None] * state
        seen = jnp.einsum("hd,hde->he", k_t, state, precision=hi)
        state = state + (b_t[:, None] * k_t)[:, :, None] \
            * (v_t - seen)[:, None, :]
        return state, jnp.einsum("hde,hd->he", state, q_t, precision=hi)

    _, out = lax.scan(step, jnp.zeros((heads, d, d), jnp.float32),
                      (q, k, v, g, beta))
    return out / math.sqrt(d)


def reference_hidden(params, tokens, config, dtype="float32",
                     precision="highest", without=(), router_bias=None):
    """The share's decoder in float32 at the highest matmul precision, from
    the layer equations (ISSUE 27; the assumed parts are the configuration
    file's ``assumed``): ``tokens`` ``[B, S]`` -> (the hidden states after
    the final RMSNorm ``[B, S, hidden]``, the tokens every expert of every
    layer got ``[layers, experts]``, the balancing bias every layer chose
    its experts under ``[layers, experts]``).  Pre-norm blocks, RMSNorm.
    Softmax layers: causal attention with no positional encoding, every
    query head on the one key/value head, the heads' output times
    ``sigmoid(x W_g)`` before ``W_o``; the textbook formula a block of
    queries at a time.  Linear layers: ``q, k = l2norm(silu(conv4(x W)))``,
    ``v = silu(conv4(x W_v))``, log-decay ``-exp(A) softplus(x W_fa W_fb +
    b)``, ``beta = 2 sigmoid(x W_beta)``, ``kda_recurrence``, RMSNorm over
    each head times ``sigmoid(x W_ga W_gb)``, ``W_o``.  Experts: sigmoid
    scores over every expert, the 8 with the largest score + balancing
    bias, their scores renormalised, the held experts one after another
    under a mask, plus the shared expert.  No kernels, no chunks, no sort,
    no sharding; a layer at a time, its mixer a sequence at a time.  It
    reads the parameter tree and nothing else of the program.
    ``router_bias`` (scores ``[T, experts]`` -> bias) replaces the
    parameters' buffer: ``prepare`` fits it there.  ``dtype``,
    ``precision`` and ``without`` are for the readings that show what the
    loss tolerance catches (PERF.md): the same arithmetic a precision below
    the configuration's (bfloat16, default), and with a part left out
    (``WITHOUT``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    assert set(without) <= set(WITHOUT), without
    hi = lax.Precision(precision)
    eps = config["rms_norm_eps"]
    head = config["head_dim"]
    lin = config["linear_attn_config"]
    top_k = config["num_experts_per_tok"]
    first = config["held"]["first_expert"]
    scaling = config["routed_scaling_factor"]

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
        s, d = x.shape[0], lin["head_dim"]

        def branch(name):
            return jax.nn.silu(conv(dot(x, p["w" + name]),
                                    p["conv_" + name])).reshape(s, -1, d)

        q, k, v = l2norm(branch("q")), l2norm(branch("k")), branch("v")
        g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
            dot(dot(x, p["w_fa"]), p["w_fb"]) + p["decay_bias"]
        ).reshape(s, -1, d)
        if "decay" in without:
            g = jnp.zeros_like(g)
        beta = 2.0 * jax.nn.sigmoid(dot(x, p["w_beta"]))
        o = rms(kda_recurrence(q, k, v, g, beta, precision), p["o_norm"])
        gate = jax.nn.sigmoid(dot(dot(x, p["w_ga"]), p["w_gb"]))
        if "linear_gate" in without:
            gate = jnp.ones_like(gate)
        return dot(o.reshape(s, -1) * gate, p["wo"])

    def softmax_mixer(x, p):    # one sequence, [S, hidden]
        s = x.shape[0]
        q = dot(x, p["wq"]).reshape(s, -1, head)
        k = dot(x, p["wk"]).reshape(s, -1, head)
        v = dot(x, p["wv"]).reshape(s, -1, head)
        rep = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
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
        gate = jax.nn.sigmoid(dot(x, p["wg"]))
        if "gqa_gate" in without:
            gate = jnp.ones_like(gate)
        return dot(attn * gate, p["wo"])

    def swiglu(x, w1, w3, w2):
        return dot(jax.nn.silu(dot(x, w1)) * dot(x, w3), w2)

    def experts(x, p):          # every token of the step, [T, hidden]
        scores = jax.nn.sigmoid(dot(x, p["router"]))
        bias = p["router_bias"] if router_bias is None \
            else router_bias(scores.astype(jnp.float32)).astype(scores.dtype)
        _, ids = lax.top_k(scores + bias, top_k)
        loads = jnp.sum(ids[:, :, None] == jnp.arange(scores.shape[-1]),
                        (0, 1))
        top = jnp.take_along_axis(scores, ids, -1)
        weights = top / top.sum(-1, keepdims=True) * scaling
        y = swiglu(x, p["ws1"], p["ws3"], p["ws2"])
        for j in range(0 if "routed" in without else p["we1"].shape[0]):
            w_j = jnp.sum(jnp.where(ids == first + j, weights, 0.0), -1)
            y = y + w_j[:, None] * swiglu(x, p["we1"][j], p["we3"][j],
                                          p["we2"][j])
        return y, loads, bias

    mixers = {"gated_nope_attention": softmax_mixer,
              "linear_attention": linear_mixer}
    pattern = _pattern(config)
    with jax.default_matmul_precision(precision):
        p = jax.tree.map(lambda w: w.astype(dtype), params)
        x, loads, biases = p["embed"][tokens], [], []
        for period in range(config["num_hidden_layers"] // len(pattern)):
            for (mixer, _), stacked in zip(pattern, p["layers"]):
                lp = jax.tree.map(lambda w: w[period], stacked)
                x = x + jax.vmap(lambda seq: mixers[mixer](seq, lp))(
                    rms(x, lp["ln1"]))
                y, load, bias = experts(
                    rms(x, lp["ln2"]).reshape(-1, x.shape[-1]), lp)
                x = x + y.reshape(x.shape)
                loads.append(load)
                biases.append(bias)
        return rms(x, p["ln_f"]), jnp.stack(loads), jnp.stack(biases)


def reference_nll_sum(x, head, targets, precision="highest"):
    """Sum of the next-token cross entropy of ``x`` ``[S, hidden]`` under
    the untied ``head`` ``[hidden, V]``, logits a block of tokens at a
    time."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    block = math.gcd(x.shape[0], REFERENCE_HEAD_BLOCK)

    def rows(xs):
        x_b, t_b = xs
        logp = jax.nn.log_softmax(
            jnp.dot(x_b, head.astype(x.dtype),
                    precision=lax.Precision(precision)))
        return -jnp.take_along_axis(logp, t_b[:, None], -1).sum()

    return lax.map(rows, (x.reshape(-1, block, x.shape[-1]),
                          targets.reshape(-1, block))).sum()


def reference_loss_fn(params, tokens, targets, config, **reading):
    """Mean next-token cross entropy over the vocabulary slice of
    ``tokens``, ``targets`` ``[B, S]``."""
    x = reference_hidden(params, tokens, config, **reading)[0]
    return sum(reference_nll_sum(x[i], params["head"], targets[i],
                                 reading.get("precision", "highest"))
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
    described ``devices``: ``make_train_step``'s own ``local_step`` cannot
    be reached without placing real parameters, so this assembles the same
    step from the same public pieces (``loss_fn``, ``param_specs``,
    ``opt_spec_tree``, the scopes) in the same way, as ``builders/bert.py``
    does.  It stands for the program only as a rehearsal."""
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
