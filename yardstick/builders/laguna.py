"""Laguna-XS.2's decoder through ``horovod_tpu/models/transformer.py``: a
pre-training step of one chip's share of an 8-chip layer group (the
configuration's file says how it was cut), with the plain float32 reference
written from the layer equations beside it.

The model leads with one layer of full attention and a dense SwiGLU; every
later layer has 256 routed SwiGLU experts (8 a token) beside a shared one,
and the layers' mixers repeat (sliding, sliding, sliding, full): a sliding
layer has 64 query heads that see the last 512 positions under plain RoPE on
the whole head, a full layer 48 that see every earlier position under YaRN
on half of it, both over 8 key/value heads of 128 and with a sigmoid gate on
the heads' output.  The chip holds every mixer whole, 32 experts of every
sparse layer and a slice of the vocabulary; what the absent experts would
add is left out, in the program and in the reference alike.
"""

import math

import numpy as np

from yardstick import flops
from yardstick import flops_hybrid as fh
from yardstick import flops_window as fw
from yardstick import measure
from yardstick.builders.solar_open2 import (ROUTER_FIT_WITHIN, _optimizer,
                                            fit_router_bias,
                                            reference_nll_sum)

# Step-0 loss of the program (bf16 activations, float32 router) against the
# float32 reference at the timed sizes, under a head fitted to its batch
# (``HEAD_FIT``, ``prepare``; ``solar_open2.py`` says why that gives the
# check its teeth).  Found on the chip (my chip runs, PR 31; PERF.md section
# 6): the program 1.28e-2 to 1.38e-2 over ten seeds, ten times
# ``solar-open2-250b``'s because five attention-heavy layers of hidden 2048
# round more into the hidden state than four of 4096 (the same program in
# float32 at the highest precision reads 1.4e-5: it is all rounding); the
# same reference a precision below (bfloat16 weights and activations,
# default products) 2.19e-2 to 2.23e-2 over three; full causal attention in
# the sliding layers 9.8e-2, scaling 1 for 2.5 1.1e-1, the full layers' head
# grouping in the sliding layers 2.3e-1, no gate, no YaRN factor or one
# rotary table 1.7 to 3.2.  The limit lies between the first two, 1.27
# times over the one and 1.25 under the other.  A window off by one key
# reads 3.0e-3 in the reference and moves the program's reading by 4e-4:
# under the program's own rounding, no limit that holds the seeds can see
# it, and the tier-1 tests hold the window's edge instead
# (``tests/test_window_attention.py``, exact).
LOSS_RTOL = 0.0175
# The head's random start plus ``HEAD_FIT / hidden`` times, in column ``j``,
# the sum of the reference's final hidden states of the tokens whose target
# is ``j``: a target logit of about ``HEAD_FIT`` before the step.
HEAD_FIT = 8.0

REFERENCE_QUERY_BLOCK = 512     # queries the reference's softmax holds at once
# What the reference can get wrong on purpose, for the readings that show
# what the tolerance catches: full causal attention in the sliding layers,
# a window of one key fewer, the sliding layers' rotary table in the full
# layers too, YaRN's factor on cos and sin left out, no output gate, the
# routed weights summing to 1 and not to the scaling factor, the sliding
# layers' query heads grouped over the key/value heads as the full layers'
# are.
WRONG = ("no_window", "window_off_by_one", "one_rotary_table",
         "no_yarn_factor", "no_gate", "scaling_1", "full_grouping")


def _layers(c):
    """[(layer type, feed-forward type, query heads)] of the layers held."""
    n = c["num_hidden_layers"]
    lists = (c["layer_types"], c["mlp_layer_types"],
             c["num_attention_heads_per_layer"])
    if any(len(entries) != n for entries in lists):
        raise ValueError("the per-layer lists do not have %d entries" % n)
    return list(zip(*lists))


def _split(c):
    """(leading layers, one period, periods): the dense layers the model
    leads with run once; what follows is whole periods of its shortest
    repeating pattern."""
    layers = _layers(c)
    lead = 0
    while lead < len(layers) and layers[lead][1] == "dense":
        lead += 1
    rest = layers[lead:]
    for size in range(1, len(rest) + 1):
        if len(rest) % size == 0 \
                and rest == rest[:size] * (len(rest) // size):
            return layers[:lead], rest[:size], len(rest) // size
    raise ValueError("no layer follows the leading dense ones")


def _model_config(cell):
    from horovod_tpu.models import transformer
    if not hasattr(transformer, "SoftmaxAttention"):
        raise measure.Refused(
            "this horovod_tpu has no per-kind softmax attention (window, "
            "head counts, rotary table) in models/transformer.py: it "
            "cannot run %s" % cell["name"])
    from horovod_tpu.parallel.moe import ExpertShare
    c, spec = cell["config"], cell["spec"]

    def rope(group):
        if group["rope_type"] == "default":
            return transformer.Rope(theta=group["rope_theta"],
                                    share=group["partial_rotary_factor"])
        assert group["rope_type"] == "yarn", group
        return transformer.Rope(
            theta=group["rope_theta"], share=group["partial_rotary_factor"],
            factor=group["factor"],
            original_max_seq=group["original_max_position_embeddings"],
            beta_fast=group["beta_fast"], beta_slow=group["beta_slow"],
            attention_factor=group["attention_factor"])

    def pair(layer_type, mlp_type, heads):
        return (transformer.SoftmaxAttention(
            n_heads=heads, n_kv_heads=c["num_key_value_heads"],
            window=c["sliding_window"]
            if layer_type == "sliding_attention" else None,
            rope=rope(c["rope_parameters"][layer_type]),
            gate=bool(c["gating"])),
            {"dense": "dense", "sparse": "expert_share"}[mlp_type])

    leading, period, _ = _split(c)
    return transformer.TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_size=c["head_dim"],
        d_ff=c["intermediate_size"], max_seq=spec["seq_len"],
        norm_eps=c["rms_norm_eps"], dtype=c["activation_dtype"],
        param_dtype=c["param_dtype"], remat=True,
        layer_pattern=tuple(pair(*layer) for layer in period),
        leading_layers=tuple(pair(*layer) for layer in leading),
        experts=ExpertShare(
            n_experts=c["published"]["num_experts"],
            first=c["held"]["first_expert"], count=c["num_experts"],
            top_k=c["num_experts_per_tok"], d_model=c["hidden_size"],
            d_ff=c["moe_intermediate_size"],
            d_shared=c["shared_expert_intermediate_size"],
            routed_scaling=float(c["moe_routed_scaling_factor"]),
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


def load_targets(cell, tokens):
    """Tokens every expert of a sparse layer is to get in a step of
    ``tokens`` tokens, ``[experts]``: the held experts the cell's
    ``expert_load_profile``, over and over, times the mean load; the others
    the rest in equal parts."""
    c = cell["config"]
    n, held, first = (c["published"]["num_experts"], c["num_experts"],
                      c["held"]["first_expert"])
    profile = np.asarray(cell["spec"]["expert_load_profile"], np.float64)
    assert held % len(profile) == 0, (held, profile)
    profile = np.tile(profile, held // len(profile))
    shares = np.full(n, (n - profile.sum()) / (n - held))
    shares[first:first + held] = profile
    return shares * tokens * c["num_experts_per_tok"] / n


def _layer_params(params):
    """The layers' parameters in the model's order, one dict a layer."""
    import jax
    lead = [jax.tree.map(lambda w: w[0], lp)
            for lp in params.get("leading", ())]
    periods = jax.tree.leaves(params["layers"])[0].shape[0]
    return lead + [jax.tree.map(lambda w: w[at], lp)
                   for at in range(periods) for lp in params["layers"]]


def prepare(params, tokens, targets, cell):
    """What the builder sets before the first step, in one pass of the
    plain reference over the batch: every sparse layer's ``router_bias``
    (``fit_router_bias`` on that layer's reference scores, so the loads
    follow the cell's profile), the head fitted to the batch
    (``HEAD_FIT``), and the reference's loss of the state so set.  Returns
    (the parameters, the loss, the loads ``[sparse layers, experts]``)."""
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
    # The leading layers are dense (``_split``); the biases come in the
    # model's order, period by period, the period's sparse layers in turn.
    _, period, periods = _split(config)
    sparse = [at for at, (_, mlp, _) in enumerate(period) if mlp == "sparse"]
    biases = biases.reshape(periods, len(sparse), -1)
    layers = tuple(
        dict(lp, router_bias=biases[:, sparse.index(at)].astype(
            lp["router_bias"].dtype)) if at in sparse else lp
        for at, lp in enumerate(params["layers"]))
    return (dict(params, head=head, layers=layers), float(loss),
            np.asarray(loads))


def _shapes(cell):
    c, spec = cell["config"], cell["spec"]
    return dict(
        seq=spec["seq_len"], hidden=c["hidden_size"], vocab=c["vocab_size"],
        layers=[(heads, c["sliding_window"]
                 if kind == "sliding_attention" else None, mlp)
                for kind, mlp, heads in _layers(c)],
        head=c["head_dim"], kv_heads=c["num_key_value_heads"],
        dense_width=c["intermediate_size"],
        experts=c["published"]["num_experts"], held=c["num_experts"],
        top_k=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        shared_width=c["shared_expert_intermediate_size"])


def _kernels(cell, samples):
    """The routed experts' products and the flash kernels' calls of one
    step, for ``readers/scope_roofline.py`` and
    ``readers/kernel_roofline.py``.  ``flash`` is two entries, the full
    layers' calls and the sliding layers' banded ones, each at its own
    cost, so that ``flash_roofline``'s floor counts both; ``flash_window``
    is the banded ones again under the name ``window_flash_roofline``
    asks for."""
    sh = _shapes(cell)
    window = cell["config"]["sliding_window"]
    # Every query head's own pass over its (repeated) key/value head; the
    # layers of one kind share one head count.
    full = [heads for heads, w, _ in sh["layers"] if w is None]
    banded = [heads for heads, w, _ in sh["layers"] if w is not None]
    assert len(set(full)) == len(set(banded)) == 1, (full, banded)
    band = fw.window_flash_cost(samples, banded[0], sh["seq"], sh["head"],
                                window)
    return [
        {"kernel": "experts",
         "calls_per_step": sum(mlp == "sparse" for _, _, mlp in sh["layers"]),
         "per_call": fh.expert_products_cost(
             fh.expected_pairs(samples * sh["seq"], sh["top_k"], sh["held"],
                               sh["experts"]),
             sh["held"], sh["hidden"], sh["expert_width"])},
        {"kernel": "flash", "calls_per_step": len(full),
         "per_call": flops.flash_attention_cost(
             samples, full[0], sh["seq"], sh["head"], causal=True)},
        {"kernel": "flash", "calls_per_step": len(banded), "per_call": band},
        {"kernel": "flash_window", "calls_per_step": len(banded),
         "per_call": band},
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
        "flops_per_sample": fw.train_flops_per_sequence(**_shapes(cell)),
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

def rotary_table(group, head_dim, positions, wrong=()):
    """cos and sin ``[positions, rotary / 2]`` of one entry of the
    source's ``rope_parameters``: frequencies ``theta^(-2i / rotary)`` over
    the ``rotary = head_dim x partial_rotary_factor`` dimensions that
    turn; under YaRN those divided by ``factor`` where a frequency turns
    fewer than ``beta_slow`` times over the original positions, kept where
    it turns more than ``beta_fast`` times, a linear ramp over the
    dimensions between, and both tables times ``attention_factor``."""
    rotary = int(head_dim * group["partial_rotary_factor"])
    i = np.arange(rotary // 2, dtype=np.float64)
    theta = float(group["rope_theta"])
    inv_freq, scale = theta ** (-2.0 * i / rotary), 1.0
    if group["rope_type"] == "yarn":
        span = group["original_max_position_embeddings"]

        def dimension(turns):
            return rotary * math.log(span / (turns * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(dimension(group["beta_fast"])), 0)
        high = min(math.ceil(dimension(group["beta_slow"])), rotary - 1)
        ramp = np.clip((i - low) / max(high - low, 0.001), 0, 1)
        inv_freq = inv_freq / group["factor"] * ramp + inv_freq * (1 - ramp)
        if "no_yarn_factor" not in wrong:
            scale = group["attention_factor"]
    angle = np.asarray(positions, np.float64)[:, None] * inv_freq[None, :]
    return ((np.cos(angle) * scale).astype(np.float32),
            (np.sin(angle) * scale).astype(np.float32))


def reference_sparse_layer(x, p, config, precision="highest", wrong=(),
                           router_bias=None):
    """The feed-forward of a sparse layer over every token of the step,
    ``x`` ``[T, hidden]``: (its output, the tokens every expert got, the
    balancing bias the experts were chosen under).  Sigmoid scores over
    every expert of the layer; the ``num_experts_per_tok`` with the largest
    score + bias; their scores (without it) renormalised to sum to the
    scaling factor; the held experts' SwiGLUs one after another, each over
    every token under its weight (0 where the token did not choose it);
    plus the shared expert's SwiGLU."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    hi = lax.Precision(precision)
    first = config["held"]["first_expert"]
    scaling = 1.0 if "scaling_1" in wrong \
        else config["moe_routed_scaling_factor"]

    def dot(a, b):
        return jnp.dot(a, b, precision=hi)

    def swiglu(w1, w3, w2):
        return dot(jax.nn.silu(dot(x, w1)) * dot(x, w3), w2)

    scores = jax.nn.sigmoid(dot(x, p["router"]))
    bias = p["router_bias"] if router_bias is None \
        else router_bias(scores.astype(jnp.float32)).astype(scores.dtype)
    _, ids = lax.top_k(scores + bias, config["num_experts_per_tok"])
    loads = jnp.sum(ids[:, :, None] == jnp.arange(scores.shape[-1]), (0, 1))
    top = jnp.take_along_axis(scores, ids, -1)
    weights = top / top.sum(-1, keepdims=True) * scaling

    def add(y, held):
        j, w1, w3, w2 = held
        w_j = jnp.sum(jnp.where(ids == first + j, weights, 0.0), -1)
        return y + w_j[:, None] * swiglu(w1, w3, w2), None

    y, _ = lax.scan(add, swiglu(p["ws1"], p["ws3"], p["ws2"]),
                    (jnp.arange(p["we1"].shape[0]), p["we1"], p["we3"],
                     p["we2"]))
    return y, loads, bias


def reference_hidden(params, tokens, config, dtype="float32",
                     precision="highest", wrong=(), router_bias=None):
    """The share's decoder in float32 at the highest matmul precision, from
    the layer equations (ISSUE 31; the assumed parts are the configuration
    file's ``assumed``): ``tokens`` ``[B, S]`` -> (the hidden states after
    the final RMSNorm ``[B, S, hidden]``, the tokens every expert of every
    sparse layer got ``[sparse layers, experts]``, the balancing bias each
    chose its experts under, the same shape).  Pre-norm blocks, RMSNorm.
    Mixer: ``q`` as the layer's own count of heads of 128, ``k, v`` as 8;
    query head ``h`` reads key/value head ``h // (heads / 8)``; q and k
    turned by the layer kind's rotary table (``rotary_table``; the first
    dimensions of a head turn, the rest pass); scores over ``sqrt(128)``,
    key ``j`` seen by query ``i`` when ``j <= i`` and, in a sliding layer,
    ``j > i - window``; the textbook softmax a block of queries at a time;
    the heads' output times ``sigmoid(x W_g)``; ``W_o``.  Dense layer:
    SwiGLU.  Sparse layer: ``reference_sparse_layer``.  No kernels, no
    bands, no sort, no sharding; a layer
    at a time, its mixer a sequence at a time.  It reads the parameter tree
    and nothing else of the program.  ``router_bias`` (scores ``[T,
    experts]`` -> bias) replaces the parameters' buffer: ``prepare`` fits
    it there.  ``dtype``, ``precision`` and ``wrong`` are for the readings
    that show what the loss tolerance catches (PERF.md): the same
    arithmetic a precision below the configuration's (bfloat16, default),
    and with a part left out or wrong (``WRONG``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    assert set(wrong) <= set(WRONG), wrong
    hi = lax.Precision(precision)
    eps = config["rms_norm_eps"]
    head = config["head_dim"]
    kv_heads = config["num_key_value_heads"]
    seq = tokens.shape[1]
    full_heads = max(h for kind, _, h in _layers(config)
                     if kind == "full_attention")

    def dot(a, b):
        return jnp.dot(a, b, precision=hi)

    def rms(x, w):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def turn(x, cos, sin):      # [S, heads, head], tables [S, rotary / 2]
        half = cos.shape[-1]
        x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
        cos, sin = cos[:, None, :], sin[:, None, :]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                                rest], -1)

    def mixer(kind, x, p):      # one sequence, [S, hidden]
        q = dot(x, p["wq"]).reshape(seq, -1, head)
        k = dot(x, p["wk"]).reshape(seq, kv_heads, head)
        v = dot(x, p["wv"]).reshape(seq, kv_heads, head)
        sliding = kind == "sliding_attention"
        table = "sliding_attention" if "one_rotary_table" in wrong else kind
        cos, sin = (jnp.asarray(t, x.dtype) for t in rotary_table(
            config["rope_parameters"][table], head, np.arange(seq), wrong))
        q, k = turn(q, cos, sin), turn(k, cos, sin)
        group = q.shape[1] // kv_heads
        if sliding and "full_grouping" in wrong:
            group = full_heads // kv_heads
        reads = jnp.minimum(jnp.arange(q.shape[1]) // group, kv_heads - 1)
        k, v = k[:, reads], v[:, reads]
        window = config["sliding_window"] \
            if sliding and "no_window" not in wrong else None
        if window and "window_off_by_one" in wrong:
            window -= 1
        block = math.gcd(seq, REFERENCE_QUERY_BLOCK)

        def rows(at):
            q_b = lax.dynamic_slice_in_dim(q, at * block, block)
            scores = jnp.einsum("qhd,khd->hqk", q_b, k, precision=hi) \
                / math.sqrt(head)
            i = (at * block + jnp.arange(block))[:, None]
            j = jnp.arange(seq)[None, :]
            seen = j <= i
            if window:
                seen &= j > i - window
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                                   -1)
            return jnp.einsum("hqk,khd->qhd", probs, v, precision=hi)

        attn = lax.map(rows, jnp.arange(seq // block)).reshape(seq, -1)
        if config["gating"] and "no_gate" not in wrong:
            attn = attn * jax.nn.sigmoid(dot(x, p["wg"]))
        return dot(attn, p["wo"])

    def swiglu(x, w1, w3, w2):
        return dot(jax.nn.silu(dot(x, w1)) * dot(x, w3), w2)

    with jax.default_matmul_precision(precision):
        p = jax.tree.map(lambda w: w.astype(dtype), params)
        x, loads, biases = p["embed"][tokens], [], []
        for (kind, mlp, _), lp in zip(_layers(config), _layer_params(p)):
            x = x + lax.map(lambda h: mixer(kind, h, lp), rms(x, lp["ln1"]))
            h = rms(x, lp["ln2"])
            if mlp == "dense":
                x = x + swiglu(h, lp["w1"], lp["w3"], lp["w2"])
                continue
            y, load, bias = reference_sparse_layer(
                h.reshape(-1, x.shape[-1]), lp, config, precision, wrong,
                router_bias)
            x = x + y.reshape(x.shape)
            loads.append(load)
            biases.append(bias)
        return rms(x, p["ln_f"]), jnp.stack(loads), jnp.stack(biases)


def reference_loss_fn(params, tokens, targets, config, **reading):
    """Mean next-token cross entropy over the vocabulary slice of
    ``tokens``, ``targets`` ``[B, S]``.  A reading a precision below rounds
    the hidden states and the head as it rounds everything; the logits'
    sums and the cross entropy stay float32, as the program's do (a
    bfloat16 mean of 16,384 cross entropies would come in steps of 0.7 %)."""
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
