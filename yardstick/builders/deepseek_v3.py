"""Kanana-2-30B-A3B's decoder (``model_type: deepseek_v3``) through
``horovod_tpu/models/transformer.py``: a pre-training step of one chip's
share of an 8-chip layer group (the configuration's file says how it was
cut), with the plain float32 reference written from the layer equations
beside it.

Every layer's mixer is multi-head latent attention with no query latent:
32 query heads of 192 (128 that no rotation touches, then 64 that the rotary
table turns); keys and values out of one 512-wide latent a position (an
RMSNorm, then a head's 128 key dimensions and 128 value dimensions) beside
ONE 64-wide rotary key a position that every head's key ends in; scores over
``sqrt(192)``.  Layer 0 has a dense SwiGLU of 6144; every later layer 128
routed SwiGLU experts of 768 (6 a token) beside two shared experts, one
SwiGLU of 1536.  The chip holds every mixer whole, 16 experts of every sparse
layer and a slice of the vocabulary; what the absent experts would add is
left out, in the program and in the reference alike.
"""

import math
import sys

import numpy as np

from yardstick import flops_hybrid as fh
from yardstick import flops_mla as fm
from yardstick import measure
from yardstick.builders.laguna import _layer_params, reference_sparse_layer
from yardstick.builders.solar_open2 import (ROUTER_FIT_WITHIN, _optimizer,
                                            fit_router_bias, load_targets,
                                            make_batch, reference_nll_sum)

# Two comparisons decide ``correct``, both against the float32 reference at the
# timed sizes under a head fitted to its batch (``HEAD_FIT``, ``prepare``;
# ``solar_open2.py`` says why that gives them their teeth).
#
# ``FLOAT32_RTOL``: the program read ONCE in float32 before the first step
# (``float32_loss``: ``transformer.loss_fn`` over the step's mesh, kernels and
# parameters, activations float32, products at the highest precision).
# Found on the chip (my chip runs, PR 37; ten seeds, each reading paired with
# the two below on the same weights and batch): 6.3e-8 to 5.4e-5 off the
# reference, mean 9.7e-6 (what sets the larger ones was not looked into; an
# expert chosen the other way at a near-tie would); the same reference a precision below (bfloat16
# weights, activations, norms and softmax, default products) 5.0e-3 to 6.7e-3;
# every ``WRONG`` part 4.8e-2 or more.  The limit is the geometric middle of
# the program's largest and the lower precision's smallest reading: 9 times of
# room over the one, 10 under the other.
#
# ``LOSS_RTOL``: the timed step's own step-0 loss (bf16 activations; float32
# norms, router and softmax statistics).  Found on the chip (my chip runs,
# PR 37; PERF.md section 6): 2.8e-3 to 7.1e-3 over 15 seeds (mean 5.6e-3,
# deviation 1.1e-3; the same to the last digit under both block plans tried);
# every ``WRONG`` part far over it: the latent's norm left out 4.8e-2, the
# scale of the value's size 1.2e-1, the routed weights summing to 1 1.4e-1, no
# rotary turn, the rotary key in one head only and the interleaved turn on
# unpermuted columns 6.4e-1 to 7.1e-1.  The reference a precision below passes
# THIS limit: on the ten paired seeds it reads 1.04 to 1.38 times the step's
# own 4.1e-3 to 6.0e-3 (this program's float32 islands are few and small: its
# error is the bfloat16 products', which the lower reading shares, and the
# seeds spread wider than the two differ), which is why the cell has the
# limit above; this one is 1.8 times the step's largest reading and 6
# deviations over the mean (a run that reads `correct` false refuses a PR,
# this one or a later one) and catches every part above by 3.8 times or more
# in the step that is timed.
FLOAT32_RTOL = 5e-4
LOSS_RTOL = 0.0125
# The head's random start plus ``HEAD_FIT / hidden`` times, in column ``j``,
# the sum of the reference's final hidden states of the tokens whose target
# is ``j``: a target logit of about ``HEAD_FIT`` before the step.
HEAD_FIT = 8.0

REFERENCE_QUERY_BLOCK = 512     # queries the reference's softmax holds at once
# What the reference can get wrong on purpose, for the readings that show
# what the tolerance catches: the latent's RMSNorm left out; the scores over
# the square root of the value's size (128) and not of the query/key size
# (192); no rotary turn at all; the rotary key in head 0's key alone (the
# one key not handed to every head); the routed weights summing to 1 and not
# to the scaling factor; the interleaved pairs turned on the program's
# columns as they lie, without the permutation that makes the program's
# halves the published pairs.
WRONG = ("no_latent_norm", "scale_by_value_size", "no_rope",
         "rope_per_head_key", "no_routed_scaling", "rope_layout")


def _feed_forwards(c):
    """The layers held, ``dense`` or ``sparse`` each: the source's
    ``first_k_dense_replace`` leading dense layers, then sparse ones
    (``moe_layer_freq`` 1)."""
    if c["moe_layer_freq"] != 1 or c["q_lora_rank"] is not None \
            or c["rope_scaling"] is not None or c["n_group"] != 1 \
            or c["scoring_func"] != "sigmoid" or not c["norm_topk_prob"] \
            or c["hidden_act"] != "silu" or c["qk_head_dim"] \
            != c["qk_nope_head_dim"] + c["qk_rope_head_dim"]:
        raise ValueError(
            "the builder runs a sparse layer after every leading dense one, "
            "no query latent, plain RoPE on the last qk_rope_head_dim of a "
            "query/key head, SwiGLU experts and a plain top-k over "
            "renormalised sigmoid scores; the configuration says otherwise")
    lead = c["first_k_dense_replace"]
    return ["dense"] * lead + ["sparse"] * (c["num_hidden_layers"] - lead)


def _model_config(cell):
    from horovod_tpu.models import transformer
    if not hasattr(transformer, "LatentAttention"):
        raise measure.Refused(
            "this horovod_tpu has no latent-attention mixer in "
            "models/transformer.py and its flash kernels take one head size:"
            " it cannot run %s" % cell["name"])
    from horovod_tpu.parallel.moe import ExpertShare
    c, spec = cell["config"], cell["spec"]
    mixer = transformer.LatentAttention(
        n_heads=c["num_attention_heads"], kv_rank=c["kv_lora_rank"],
        nope=c["qk_nope_head_dim"], rope_dim=c["qk_rope_head_dim"],
        v_dim=c["v_head_dim"],
        rope=transformer.Rope(theta=float(c["rope_theta"])))
    kinds = {"dense": "dense", "sparse": "expert_share"}
    layers = _feed_forwards(c)
    lead = c["first_k_dense_replace"]
    return transformer.TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_size=c["qk_head_dim"],
        d_ff=c["intermediate_size"], max_seq=spec["seq_len"],
        norm_eps=c["rms_norm_eps"], dtype=c["activation_dtype"],
        param_dtype=c["param_dtype"], remat=True,
        leading_layers=tuple((mixer, kinds[kind]) for kind in layers[:lead]),
        layer_pattern=((mixer, kinds["sparse"]),),
        experts=ExpertShare(
            n_experts=c["published"]["n_routed_experts"],
            first=c["held"]["first_expert"], count=c["n_routed_experts"],
            top_k=c["num_experts_per_tok"], d_model=c["hidden_size"],
            d_ff=c["moe_intermediate_size"],
            d_shared=c["n_shared_experts"] * c["moe_intermediate_size"],
            routed_scaling=float(c["routed_scaling_factor"]),
            block_rows=spec["expert_block_rows"], form="swiglu"),
        tie_embeddings=c["tie_word_embeddings"],
        head_block=spec["head_block"])


def prepare(params, tokens, targets, cell):
    """What the builder sets before the first step, in one pass of the
    plain reference over the batch: every sparse layer's ``router_bias``
    (the family's ``e_score_correction_bias``; ``fit_router_bias`` on that
    layer's reference scores, so the loads follow the cell's profile), the
    head fitted to the batch (``HEAD_FIT``), and the reference's loss of
    the state so set.  Returns (the parameters, the loss, the loads
    ``[sparse layers, experts]``)."""
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
    # The leading layers are dense; the one scanned kind is sparse, and its
    # biases come in the layers' order.
    (lp,) = params["layers"]
    layers = (dict(lp, router_bias=biases.astype(lp["router_bias"].dtype)),)
    return (dict(params, head=head, layers=layers), float(loss),
            np.asarray(loads))


def _shapes(cell):
    c, spec = cell["config"], cell["spec"]
    return dict(
        seq=spec["seq_len"], hidden=c["hidden_size"], vocab=c["vocab_size"],
        feed_forwards=_feed_forwards(c), heads=c["num_attention_heads"],
        kv_rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
        rope_dim=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
        dense_width=c["intermediate_size"],
        experts=c["published"]["n_routed_experts"],
        held=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"],
        shared_width=c["n_shared_experts"] * c["moe_intermediate_size"])


def _kernels(cell, samples):
    """The routed experts' products and the flash kernels' calls of one
    step, for ``readers/scope_roofline.py`` and
    ``readers/kernel_roofline.py``.  Every layer makes one latent flash
    call; ``flash`` and ``latent_flash`` are the same calls under the names
    ``flash_roofline`` and ``latent_flash_roofline`` ask for.  The floor is
    the algorithm's: 192 and 128 as published, the one rotary key read once
    a batch entry, whatever the program repeats or pads."""
    sh = _shapes(cell)
    call = fm.latent_flash_cost(
        samples, sh["heads"], sh["seq"], sh["nope"] + sh["rope_dim"],
        sh["v_dim"], causal=True, shared_rope=True, rope_dim=sh["rope_dim"])
    layers = len(sh["feed_forwards"])
    return [
        {"kernel": "experts",
         "calls_per_step": sh["feed_forwards"].count("sparse"),
         "per_call": fh.expert_products_cost(
             fh.expected_pairs(samples * sh["seq"], sh["top_k"], sh["held"],
                               sh["experts"]),
             sh["held"], sh["hidden"], sh["expert_width"])},
        {"kernel": "flash", "calls_per_step": layers, "per_call": call},
        {"kernel": "latent_flash", "calls_per_step": layers,
         "per_call": call},
    ]


def float32_loss(cfg, mesh, params, batch):
    """The program's loss of ``batch`` read in float32: ``loss_fn`` as the
    step differentiates it, over the step's mesh, with the step's kernels
    and parameters; the activations' dtype is the one thing changed, and the
    products run at the highest precision.  A forward pass outside the
    window, once a run."""
    import dataclasses

    import jax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.models import transformer
    cfg = dataclasses.replace(cfg, dtype="float32")
    rows = P(cfg.dp_axis, cfg.sp_axis)
    read = jax.jit(jax.shard_map(
        lambda p, b: transformer.loss_fn(p, b, cfg), mesh=mesh,
        in_specs=(transformer.param_specs(cfg),
                  {"tokens": rows, "targets": rows}),
        out_specs=P(), check_vma=True))
    with jax.default_matmul_precision("highest"):
        return float(read(params, batch))


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
    # device: it sets the correction biases and the head, and its loss is
    # the one the job asks for below.
    params, loss_ref, loads = prepare(
        params, host_batch["tokens"], host_batch["targets"], cell)
    goal = load_targets(cell, host_batch["tokens"].size)
    if np.abs(loads - goal).max() > 2 * ROUTER_FIT_WITHIN * goal.mean():
        raise ValueError("the routers' loads are not the cell's profile: "
                         "%s against %s" % (loads.tolist(), goal.tolist()))
    # The finer comparison comes first, and before the optimizer's state is
    # on the device too.  The job makes one comparison, the step's loss
    # against the reference within ``loss_rtol`` (``jobs/jit_step.py``): a
    # program whose float32 read is off the reference is handed no room
    # there, so that the job's own check reads not correct.
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
        "flops_per_sample": fm.train_flops_per_sequence(**_shapes(cell)),
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

def published_rope_columns(rope_dim):
    """Where the published layout's rotary column ``j`` lies among the
    program's: the source turns interleaved pairs, dimensions ``(2i, 2i +
    1)`` at frequency ``i`` (``rope_interleave: true``); the program turns
    halves, ``(i, i + rope_dim / 2)`` (``models/transformer.py: _rope``).
    The two are one function of the input under this fixed permutation of
    the rotary columns of ``W_q`` and ``W_kv_a`` (a dot product of a turned
    query and a turned key does not care where a pair lies, only that both
    keep it in the same place): published column ``2i`` is the program's
    ``i``, ``2i + 1`` its ``i + rope_dim / 2``.  Weights are random from
    the seed; a checkpoint's loader would apply the inverse."""
    half = rope_dim // 2
    return np.stack([np.arange(half), half + np.arange(half)], 1).reshape(-1)


def reference_latent_attention(x, p, config, precision="highest", wrong=()):
    """A layer's mixer over one sequence, ``x`` ``[S, hidden]`` normed, as
    the public ``deepseek_v3`` code computes it with ``q_lora_rank`` null:

        q            = x W_q                     32 heads of [nope 128 | rope 64]
        [c | k_rope] = x W_kv_a                  latent 512, ONE rotary key 64
        [k_nope | v] = rms_norm(c; g_kv) W_kv_b  a head's 128 and 128
        q_rope, k_rope turned, interleaved pairs (2i, 2i + 1), theta 1e6
        k            = [k_nope | k_rope], k_rope the same for every head
        o_h          = softmax(q_h k_h^T / sqrt(192) + causal) v_h
        y            = concat_h(o_h) W_o

    No bias, no gate.  The textbook softmax a block of queries at a time.
    The rotary columns are read from the program's tree through
    ``published_rope_columns`` (the one departure, and it changes no
    value)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    hi = lax.Precision(precision)
    seq = x.shape[0]
    heads, rank = config["num_attention_heads"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v_dim = config["v_head_dim"]
    columns = np.arange(rope) if "rope_layout" in wrong \
        else published_rope_columns(rope)

    def dot(a, b):
        return jnp.dot(a, b, precision=hi)

    def turn(y):        # [S, heads, rope]: pairs (2i, 2i + 1) at frequency i
        if "no_rope" in wrong:
            return y
        i = np.arange(rope // 2, dtype=np.float64)
        angle = np.arange(seq, dtype=np.float64)[:, None] \
            * float(config["rope_theta"]) ** (-2.0 * i / rope)
        cos, sin = (jnp.asarray(t, y.dtype)[:, None, :]
                    for t in (np.cos(angle), np.sin(angle)))
        a, b = y[..., 0::2], y[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         -1).reshape(y.shape)

    q = dot(x, p["wq"]).reshape(seq, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], turn(q[..., nope:][..., columns])
    latent = dot(x, p["wkv_a"])
    c = latent[:, :rank]
    if "no_latent_norm" not in wrong:
        c = c * lax.rsqrt(jnp.mean(c * c, -1, keepdims=True)
                          + config["rms_norm_eps"]) * p["kv_norm"]
    kv = dot(c, p["wkv_b"]).reshape(seq, heads, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_rope = turn(latent[:, None, rank:][..., columns])
    k_rope = jnp.broadcast_to(k_rope, (seq, heads, rope))
    if "rope_per_head_key" in wrong:
        k_rope = k_rope * (jnp.arange(heads) == 0)[None, :, None]
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, k_rope], -1)
    scale = math.sqrt(v_dim if "scale_by_value_size" in wrong
                      else nope + rope)
    block = math.gcd(seq, REFERENCE_QUERY_BLOCK)

    def rows(at):
        q_b = lax.dynamic_slice_in_dim(q, at * block, block)
        scores = jnp.einsum("qhd,khd->hqk", q_b, k, precision=hi) / scale
        seen = jnp.arange(seq)[None, :] \
            <= (at * block + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=hi)

    attn = lax.map(rows, jnp.arange(seq // block)).reshape(seq, -1)
    return dot(attn, p["wo"])


def reference_expert_layer(x, p, config, precision="highest", wrong=(),
                           router_bias=None):
    """A sparse layer's feed-forward over every token of the step, ``x``
    ``[T, hidden]`` normed: ``builders/laguna.py: reference_sparse_layer``,
    the same mathematics under this source's keys (float32 sigmoid scores
    over all 128 experts; the 6 with the largest score +
    ``e_score_correction_bias``, ``n_group`` 1 and ``topk_group`` 1 making
    the grouped choice a plain top-6; their scores without the bias
    renormalised and multiplied by ``routed_scaling_factor``; the held
    experts' SwiGLUs as a dense loop; plus the two shared experts as one
    SwiGLU of 1536)."""
    return reference_sparse_layer(
        x, p, dict(config,
                   moe_routed_scaling_factor=config["routed_scaling_factor"]),
        precision, ("scaling_1",) if "no_routed_scaling" in wrong else (),
        router_bias)


def reference_hidden(params, tokens, config, dtype="float32",
                     precision="highest", wrong=(), router_bias=None):
    """The share's decoder in float32 at the highest matmul precision, from
    the layer equations (ISSUE 37; the assumed parts are the configuration
    file's ``assumed``): ``tokens`` ``[B, S]`` -> (the hidden states after
    the final RMSNorm ``[B, S, hidden]``, the tokens every expert of every
    sparse layer got ``[sparse layers, experts]``, the correction bias each
    chose its experts under, the same shape).  Pre-norm layers, RMSNorm
    (eps 1e-6): ``x + mixer(rms(x))`` (``reference_latent_attention``), then
    ``x + ffn(rms(x))``, a dense SwiGLU of 6144 in the leading layer and
    ``reference_expert_layer`` after it.  No kernels, no sort, no sharding;
    a layer at a time, its mixer a sequence at a time.  It reads the
    parameter tree and nothing else of the program.  ``router_bias``
    (scores ``[T, experts]`` -> bias) replaces the parameters' buffer:
    ``prepare`` fits it there.  ``dtype``, ``precision`` and ``wrong`` are
    for the readings that show what the loss tolerance catches (PERF.md):
    the same arithmetic a precision below the configuration's (bfloat16
    throughout, default products), and with a part left out or wrong
    (``WRONG``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    assert set(wrong) <= set(WRONG), wrong
    hi = lax.Precision(precision)
    eps = config["rms_norm_eps"]

    def dot(a, b):
        return jnp.dot(a, b, precision=hi)

    def rms(x, w):
        return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    with jax.default_matmul_precision(precision):
        p = jax.tree.map(lambda w: w.astype(dtype), params)
        x, loads, biases = p["embed"][tokens], [], []
        for kind, lp in zip(_feed_forwards(config), _layer_params(p)):
            x = x + lax.map(
                lambda h: reference_latent_attention(h, lp, config,
                                                     precision, wrong),
                rms(x, lp["ln1"]))
            h = rms(x, lp["ln2"])
            if kind == "dense":
                x = x + dot(jax.nn.silu(dot(h, lp["w1"])) * dot(h, lp["w3"]),
                            lp["w2"])
                continue
            y, load, bias = reference_expert_layer(
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
    sums and the cross entropy stay float32, as the program's do."""
    import jax.numpy as jnp
    x = reference_hidden(params, tokens, config, **reading)[0]
    head = params["head"].astype(x.dtype).astype(jnp.float32)
    return sum(reference_nll_sum(x[i].astype(jnp.float32), head, targets[i])
               for i in range(x.shape[0])) / targets.size


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
