"""Keye-VL-2.0-30B-A3B's language model (``model_type: KeyeVL2``) through
``horovod_tpu/models/transformer.py``: a pre-training step of one chip's
share of an 8-chip layer group (the configuration's file says how it was cut
and what was assumed), with the plain float32 reference written from the
layer equations beside it.

Every layer is learned sparse attention over GQA and a layer of 128 routed
SwiGLU experts of 768 (8 a token, softmax scores, no shared expert).  The
attention: 32 query heads over 4 key/value heads of 128, an RMSNorm over each
head's channels of q and k, RoPE at theta 1e7; a lightning indexer (16 heads
of 64 over one key, on the layer's normed input with no gradient) scores
every causal key of a query and the query attends over its 2048 keys of the
largest score alone; the indexer learns from the KL divergence of its softmax
over those keys from the attention's probabilities there, which the step adds
to the cross entropy.  The chip holds every mixer whole, 16 experts of every
layer and an eighth of the vocabulary; what the absent experts would add is
left out, in the program and in the reference alike.
"""

import math
import sys

import numpy as np

from yardstick import flops_hybrid as fh
from yardstick import flops_sparse as fs
from yardstick import measure
from yardstick.builders.laguna import _layer_params
from yardstick.builders.solar_open2 import (ROUTER_FIT_ROUNDS,
                                            ROUTER_FIT_WITHIN, _optimizer,
                                            make_batch, reference_nll_sum)

# Two comparisons of the loss decide ``correct``, with the first mixer's
# readings below, all against the float32 reference at
# the timed sizes under a head fitted to its batch (``HEAD_FIT``,
# ``prepare``; ``solar_open2.py`` says why that gives them their teeth).
#
# ``FLOAT32_RTOL``: the program read ONCE in float32 before the first step
# (``float32_loss``: ``transformer.loss_fn`` over the step's mesh, kernels and
# parameters, activations float32, products at the highest precision).
# Found on a TPU v5e (PERF.md, section 4): 3.5e-5 to 1.41e-4 off the
# reference over eight seeds (mean 8.7e-5; the kernels' float32 products and
# XLA's differ in their last bits, and a key or an expert at a near-tie is
# chosen the other way); the same reference a precision below (bfloat16
# weights, activations, norms and softmax, default products) 4.9e-4 and
# 1.28e-3 on two of those seeds; every causal key in place of the chosen
# ones 4.1e-3 to 1.34e-2, the indexer loss left out 4.3e-3 to 5.6e-3, the
# top 2047 in place of 2048 2.65e-4 and 3.40e-4.  The limit lies between the
# program's largest reading and the lower precision's smallest, 1.8 times
# over the one and 1.9 under the other; a key fewer a query fails it by
# 1.06 and 1.36 times only, which is no margin: ``CHOICE_LIMIT`` is the one
# that catches it.
#
# ``LOSS_RTOL``: the timed step's own step-0 loss (bf16 activations and
# products; float32 norms, router, softmax statistics and indexer sums).
# Found on a TPU v5e (PERF.md, section 4): 2.8e-4 to 1.14e-3 over the eight
# seeds (mean 7.6e-4).  The reference a precision below reads as much (4.9e-4
# and 1.28e-3): this program's error is its bfloat16 products', which that
# reading shares, so this limit cannot tell the two apart and the float32
# limit above does.  This one is 2.2 times the step's largest reading and
# catches every causal key (1.6 times or more) and the indexer loss left out
# (1.7 times or more) in the step that is timed.
FLOAT32_RTOL = 2.5e-4
LOSS_RTOL = 2.5e-3
# The first layer's mixer, read on its own at the timed size before the
# first step (``mixer_readings``): the choice and the attention kernels,
# backward included, which the loss sees only through a collapsed head or
# not at all.  Both are handed the reference's own float32 inputs of the
# layer (``reference_projections``), so that the projections' and rotary
# tables' rounding, which differs at 32768 positions, is not read as the
# kernels'.
#
# ``CHOICE_LIMIT``: the share of the queries whose kept keys differ
# between the choice kernel and ``reference_kept``.  Found on a TPU v5e
# (PERF.md, section 4; three seeds): 0, every one of the 32768 queries
# keeps the reference's 2048 keys; the reference's indexer a precision
# below 0.930, a key fewer a query 0.9375 (every query past 2047).  A fault
# in one block of 256 queries alone reads 0.0078.
#
# ``GRADIENT_RTOL``: the largest ``|g - g_ref| / |g_ref|`` over q, k and v
# from the output's term and qI, kI and w from the indexer loss's, the
# kernels (``sparse_kernels.sparse_attention``) against the reference's
# ``reference_attention`` under ``jax.vjp``.  Found on a TPU v5e (the same
# seeds): 3.50e-4 to 3.52e-4, from w (q, k, v 2.7e-5; qI 1.75e-4; kI
# 2.1e-5); the reference a precision below 0.0675.  The limit is 5.7 times
# the one and 34 times under the other.
#
# ``crossed``, held to 0: the gradient a term sends where it must send
# none, in the kernels and in the whole block (the indexer loss's to q, k,
# v, the attention's weights and the block's input; the output's to the
# indexer's inputs and weights).  Zero by construction: the choice and
# ``p`` take no gradient, and the indexer reads its input detached.
CHOICE_LIMIT = 1e-3
GRADIENT_RTOL = 2e-3
ATTENTION_INPUTS = ("q", "k", "v")
INDEXER_INPUTS = ("qi", "ki", "w")
MIXER_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
INDEXER_LEAVES = ("w_iq", "w_ik", "ik_norm", "ik_bias", "w_iw")
# The head's random start plus ``HEAD_FIT / hidden`` times, in column ``j``,
# the sum of the reference's final hidden states of the tokens whose target
# is ``j`` (the sibling cells' fit).  At 32768 positions the four layers'
# attention, which averages over 2048 keys a query, leaves every token's
# final state near one common direction (a mean cosine of 0.87 between two
# tokens' at 1024 positions, on the CPU), so a column's logit grows with the
# count of its target in the batch and the loss reads 40-50 where the fit
# would give about 3 to independent states; a token's target logit still
# moves with the angle between the program's state and the reference's.
# Centring the states and scaling the fit to what is left of them was tried
# and dropped: the loss then read 19-28 with the float32 program 1.2e-3 to
# 1.8e-3 off, a fifth to a half of what a key fewer a query read (3.4e-3 to
# 7.1e-3; on a TPU v5e).
HEAD_FIT = 8.0

REFERENCE_QUERY_BLOCK = 256     # queries the reference's softmax holds at once
# What the reference can get wrong on purpose, for the readings that show
# what the limits catch: every causal key in place of the chosen ones; the
# top 2047 in place of 2048; the indexer loss left out of the step's loss;
# no QK-norm; the router's scores by sigmoid and not softmax.  (An indexer
# whose input is not detached reads the same loss: only the gradients tell
# it, which the tests do.)
WRONG = ("dense_attention", "top_k_less_one", "no_indexer_loss",
         "no_qk_norm", "sigmoid_router")
# What only the mixer's readings can tell: the indexer reading its input
# with its gradient.
WRONG_GRADIENTS = ("indexer_not_detached",)


def _check(c):
    sa = c["sa_config"]
    if c["decoder_sparse_step"] != 1 or c["mlp_only_layers"] \
            or not c["norm_topk_prob"] or c["hidden_act"] != "silu" \
            or sa["indexer_num_kv_heads"] != 1 or c["use_sliding_window"] \
            or c["attention_bias"] \
            or c["rope_scaling"]["rope_type"] != "default":
        raise ValueError(
            "builders/keye_vl2.py runs an expert layer in every layer, one "
            "indexer "
            "key a position, no window, no bias, plain RoPE and SwiGLU "
            "experts over renormalised softmax scores; the configuration "
            "says otherwise")


def _mixer(c):
    from horovod_tpu.models import transformer
    sa = c["sa_config"]
    return transformer.SparseAttention(
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        top_k=sa["topk"], rope=transformer.Rope(theta=float(c["rope_theta"])))


def _model_config(cell):
    from horovod_tpu.models import transformer
    from horovod_tpu.parallel import moe
    if not hasattr(transformer, "SparseAttention") or "scoring" not in \
            getattr(moe.ExpertShare, "__dataclass_fields__", {}):
        raise measure.Refused(
            "this horovod_tpu has no learned-sparse-attention mixer in "
            "models/transformer.py and no softmax router in "
            "parallel/moe.py: it cannot run %s" % cell["name"])
    c, spec = cell["config"], cell["spec"]
    _check(c)
    return transformer.TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_size=c["head_dim"],
        d_ff=c["intermediate_size"], max_seq=spec["seq_len"],
        norm_eps=c["rms_norm_eps"], dtype=c["activation_dtype"],
        param_dtype=c["param_dtype"], remat=True,
        layer_pattern=((_mixer(c), "expert_share"),),
        experts=moe.ExpertShare(
            n_experts=c["published"]["num_experts"],
            first=c["held"]["first_expert"], count=c["num_experts"],
            top_k=c["num_experts_per_tok"], d_model=c["hidden_size"],
            d_ff=c["moe_intermediate_size"], d_shared=0,
            block_rows=spec["expert_block_rows"], form="swiglu",
            scoring="softmax"),
        tie_embeddings=c["tie_word_embeddings"],
        head_block=spec["head_block"])


def load_targets(cell, tokens):
    """Tokens every expert of a layer is to get in a step of ``tokens``
    tokens, ``[experts]``: the held experts the cell's
    ``expert_load_profile`` times the mean load, the others the rest in
    equal parts."""
    c = cell["config"]
    n, held, first = (c["published"]["num_experts"], c["num_experts"],
                      c["held"]["first_expert"])
    profile = np.asarray(cell["spec"]["expert_load_profile"], np.float64)
    assert profile.shape == (held,), profile
    shares = np.full(n, (n - profile.sum()) / (n - held))
    shares[first:first + held] = profile
    return shares * tokens * c["num_experts_per_tok"] / n


def fit_router(x, router, targets, top_k):
    """The router's start fitted to ``targets`` on the tokens ``x`` ``[T,
    hidden]`` of the step's own batch: every column first loses its part
    along the tokens' mean (so that each expert's logits average 0 over the
    batch, and the choice's threshold lies above their mean), then is
    scaled by ``fit_router_scale``.  Returns the fitted router."""
    import jax.numpy as jnp
    mean = jnp.mean(x, 0)
    router = router - jnp.outer(mean, mean @ router) / jnp.dot(mean, mean)
    scale, _ = fit_router_scale(jnp.dot(x, router), targets, top_k)
    return router * scale


def fit_router_scale(logits, targets, top_k):
    """The scale of each router column under which the experts' loads over
    ``logits`` ``[T, experts]`` are ``targets``: a softmax router chooses by
    its logits, and an expert whose column is scaled by ``s`` has every
    logit scaled by ``s``, so that above the choice's threshold (positive:
    the top 8 of 128) its load grows with ``s``, by about ``s^3`` there (the
    hazard of a normal logit 1.5 deviations out, times 1.5).  Every round
    moves an expert's log-scale against the logarithm of its load over its
    target, by a third of it to begin with; an expert whose load has crossed
    its target halves its step (``solar_open2.py: fit_router_bias``'s rule).
    Plain arithmetic on the reference's logits; nothing of the program.
    Returns the scales and the rounds it took."""
    import jax.numpy as jnp
    from jax import lax
    experts = logits.shape[-1]
    targets = jnp.asarray(targets, jnp.float32)
    within = ROUTER_FIT_WITHIN * targets.mean()

    def off_under(log_scale):
        _, ids = lax.top_k(logits * jnp.exp(log_scale), top_k)
        loads = jnp.zeros(experts, jnp.float32).at[ids.reshape(-1)].add(1.0)
        return loads - targets, jnp.log((loads + 0.5) / (targets + 0.5))

    def unfinished(state):
        return (jnp.abs(state["off"]).max() > within) \
            & (state["rounds"] < ROUTER_FIT_ROUNDS)

    def one_round(state):
        log_scale = state["log_scale"] - state["step"] * state["log_off"]
        off, log_off = off_under(log_scale)
        crossed = log_off * state["log_off"] < 0
        return {"log_scale": log_scale, "off": off, "log_off": log_off,
                "step": jnp.where(crossed, state["step"] / 2, state["step"]),
                "rounds": state["rounds"] + 1}

    log_scale = jnp.zeros(experts, jnp.float32)
    off, log_off = off_under(log_scale)
    fitted = lax.while_loop(unfinished, one_round, {
        "log_scale": log_scale, "off": off, "log_off": log_off,
        "step": jnp.full(experts, 1 / 3.0, jnp.float32), "rounds": 0})
    return jnp.exp(fitted["log_scale"]), fitted["rounds"]


def prepare(params, tokens, targets, cell):
    """What is set before the first step, in one pass of the
    plain reference over the batch: every layer's router fitted
    (``fit_router`` on that layer's reference inputs, so the loads follow
    the cell's profile), the head fitted to the batch
    (``HEAD_FIT``), and the reference's loss of the state so set.  Returns
    (the parameters, the loss, the loads ``[layers, experts]``)."""
    import jax
    import jax.numpy as jnp
    config = cell["config"]
    goal = load_targets(cell, tokens.size)

    def one_pass(params, tokens, targets):
        x, loads, routers, indexer = reference_hidden(
            params, tokens, config,
            router_fit=lambda h, router: fit_router(
                h, router, goal, config["num_experts_per_tok"]))
        hidden = x.shape[-1]
        fit = jnp.zeros((params["head"].shape[1], hidden), jnp.float32) \
            .at[targets.reshape(-1)].add(x.reshape(-1, hidden))
        head = params["head"] + (HEAD_FIT / hidden) * fit.T
        loss = sum(reference_nll_sum(x[i], head, targets[i])
                   for i in range(x.shape[0])) / targets.size + indexer
        return routers, head.astype(params["head"].dtype), loads, loss

    routers, head, loads, loss = jax.jit(one_pass)(params, tokens, targets)
    (lp,) = params["layers"]
    layers = (dict(lp, router=routers.astype(lp["router"].dtype)),)
    return (dict(params, head=head, layers=layers), float(loss),
            np.asarray(loads))


def _shapes(cell):
    c, spec = cell["config"], cell["spec"]
    sa = c["sa_config"]
    return dict(
        seq=spec["seq_len"], hidden=c["hidden_size"], vocab=c["vocab_size"],
        layers=c["num_hidden_layers"], heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"], head=c["head_dim"],
        index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"], top_k=sa["topk"],
        experts=c["published"]["num_experts"], held=c["num_experts"],
        expert_top_k=c["num_experts_per_tok"],
        expert_width=c["moe_intermediate_size"])


def _kernels(cell, samples):
    """The routed experts' products and the sparse attention's kernels of
    one step, for ``readers/scope_roofline.py``: the attention counts the
    pairs a query keeps (``flops_sparse.sparse_flash_cost``), whatever the
    kernels compute under the mask of the choice."""
    sh = _shapes(cell)
    return [
        {"kernel": "experts", "calls_per_step": sh["layers"],
         "per_call": fh.expert_products_cost(
             fh.expected_pairs(samples * sh["seq"], sh["expert_top_k"],
                               sh["held"], sh["experts"]),
             sh["held"], sh["hidden"], sh["expert_width"])},
        {"kernel": "sparse_flash", "calls_per_step": sh["layers"],
         "per_call": fs.sparse_flash_cost(
             samples, sh["heads"], sh["kv_heads"], sh["seq"], sh["head"],
             sh["top_k"])},
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


def first_mixer_input(params, tokens, config):
    """The first layer's mixer parameters (``MIXER_LEAVES`` and
    ``INDEXER_LEAVES``) and its normed input of the first sequence of
    ``tokens``, ``[S, hidden]``, in float32."""
    import jax.numpy as jnp
    lp = {name: params["layers"][0][name][0].astype(jnp.float32)
          for name in MIXER_LEAVES + INDEXER_LEAVES + ("ln1",)}
    x = params["embed"][tokens[0]].astype(jnp.float32)
    return lp, _rms(x, lp.pop("ln1"), config["rms_norm_eps"])


def program_mixer(cfg, mesh):
    """(lp, x ``[S, hidden]``) -> (output ``[S, hidden]``, indexer loss) of
    the program's block in float32 over the step's mesh, with the step's
    kernels."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.models import transformer
    cfg = dataclasses.replace(cfg, dtype="float32")
    (kind, _), = cfg.layer_pattern

    def local(lp, x):
        pos = jnp.arange(x.shape[0])
        tables = {kind.rope: transformer.rope_tables(
                      pos, cfg.head_dim, kind.rope, jnp.float32),
                  ("indexer", kind): transformer.rope_tables(
                      pos, kind.index_dim, kind.index_rope, jnp.float32)}
        out, kl = transformer._sparse_attention_block(x[None], lp, cfg, kind,
                                                      tables, 1)
        return out[0], kl

    return jax.shard_map(local, mesh=mesh, in_specs=(P(), P()),
                         out_specs=(P(), P()), check_vma=False)


def program_attention(top_k):
    """``reference_attention``'s signature over the program's kernels
    (``sparse_kernels.sparse_attention``, the choice included), the
    indexer loss the mean over the queries."""
    import jax.numpy as jnp

    from horovod_tpu.ops import sparse_kernels

    def attention(q, k, v, qi, ki, w):
        o, kl = sparse_kernels.sparse_attention(
            *(a[None] for a in (q, k, v, qi, ki, w)), top_k)
        return o[0].reshape(q.shape[0], -1), jnp.mean(kl)

    return attention


def gradients(f, args):
    """(the output's term's gradient, the indexer loss's), each a dict like
    ``args``, of ``f(**args)`` -> (output, indexer loss); the output's term
    is ``sum(out * c)`` under a fixed random ``c``.  The highest
    precision, the kernels' products included."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        (out, kl), pull = jax.vjp(lambda a: f(**a), args)
        c = jax.random.normal(jax.random.PRNGKey(0), out.shape, out.dtype)
        return (pull((c, jnp.zeros_like(kl)))[0],
                pull((jnp.zeros_like(out), jnp.ones_like(kl)))[0])


def _norm(a):
    import jax.numpy as jnp
    return float(jnp.linalg.norm(jnp.asarray(a, jnp.float32)))


def gradient_readings(got, want):
    """(``GRADIENT_RTOL``'s reading, {input: distance}) of the kernels'
    gradients ``got`` against ``want`` (``gradients``)."""
    off = {n: _norm(got[0][n] - want[0][n]) / _norm(want[0][n])
           for n in ATTENTION_INPUTS}
    off.update((n, _norm(got[1][n] - want[1][n]) / _norm(want[1][n]))
               for n in INDEXER_INPUTS)
    return max(off.values()), off


def crossed(grads, attention_names, indexer_names):
    """The largest gradient a term of ``grads`` (``gradients``) sends where
    it must send none: the indexer loss's to ``attention_names``, the
    output's to ``indexer_names``."""
    out, kl = grads
    return max([_norm(kl[n]) for n in attention_names]
               + [_norm(out[n]) for n in indexer_names])


def choice_differs(chosen, qi, ki, w, top_k, block):
    """The share of queries whose kept keys differ between ``chosen(j)``
    (block ``j``'s ``[block, S]`` bool) and ``reference_kept`` of the
    reference's scores of ``qi`` ``[S, HI, DI]``, ``ki`` ``[S, DI]``, ``w``
    ``[S, HI]``, a block of queries at a time."""
    import jax.numpy as jnp
    from jax import lax
    seq = ki.shape[0]

    def differ(j):
        rows = (lax.dynamic_slice_in_dim(a, j * block, block)
                for a in (qi, w))
        kept = reference_kept(reference_scores(next(rows), ki, next(rows)),
                              j * block + jnp.arange(block), top_k)
        return jnp.sum(jnp.any(kept != chosen(j), -1))

    return jnp.sum(lax.map(differ, jnp.arange(seq // block))) / seq


def program_choice(qi, ki, w, top_k):
    """(``chosen`` for ``choice_differs``, its block) of the program's
    choice kernel on ``qi`` ``[S, HI, DI]``, ``ki`` ``[S, DI]``, ``w`` ``[S,
    HI]``: its bits ``[S / 32, S]``, bit ``b`` of word row ``j r + i`` query
    ``j block + b r + i``'s (``ops/sparse_kernels.py``)."""
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.ops import sparse_kernels
    seq = ki.shape[0]
    block = sparse_kernels.plan(seq)[1]
    bits, _ = sparse_kernels.select(jnp.swapaxes(qi, 0, 1)[None], ki[None],
                                    jnp.swapaxes(w, 0, 1)[None, ..., None],
                                    top_k, block)
    r = block // 32

    def chosen(j):
        words = lax.dynamic_slice_in_dim(bits[0], j * r, r)
        return ((words[None] >> jnp.arange(32)[:, None, None]) & 1) \
            .reshape(block, seq) == 1

    return chosen, block


def mixer_readings(cfg, mesh, params, tokens, config):
    """The first layer's mixer against the reference at the timed size, in
    float32 at the highest precision: {"choice": ``CHOICE_LIMIT``'s
    reading, "gradient": ``GRADIENT_RTOL``'s, "crossed", "by_input"}.
    Outside the window, once a run."""
    import jax
    top_k = config["sa_config"]["topk"]

    def choice(qi, ki, w):
        chosen, block = program_choice(qi, ki, w, top_k)
        return choice_differs(chosen, qi, ki, w, top_k, block)

    def block(lp, x):
        return gradients(lambda x, **lp: program_mixer(cfg, mesh)(lp, x),
                         dict(lp, x=x))

    # One gradient at a time, its inputs let go before the next: the
    # reference's and the whole block's hold about 7 GiB of temporaries,
    # and the readings stay under the step's own peak.
    with jax.default_matmul_precision("highest"):
        lp, x = jax.jit(lambda p, t: first_mixer_input(p, t, config))(
            params, tokens)
        inputs = jax.jit(lambda lp, x: reference_projections(x, lp, config))(
            lp, x)
        share = float(jax.jit(choice)(inputs["qi"], inputs["ki"],
                                      inputs["w"]))
        want = jax.jit(lambda a: gradients(
            lambda **a: reference_attention(**a, config=config), a))(inputs)
        got = jax.jit(lambda a: gradients(program_attention(top_k), a))(
            inputs)
        del inputs
        gradient, by_input = gradient_readings(got, want)
        through = crossed(got, ATTENTION_INPUTS, INDEXER_INPUTS)
        del got, want
        whole = crossed(jax.jit(block)(lp, x), MIXER_LEAVES + ("x",),
                        INDEXER_LEAVES)
    return {"choice": share, "gradient": gradient,
            "crossed": max(through, whole), "by_input": by_input}


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
    # device: it sets the routers' columns and the head, and its loss is the
    # one the job asks for below.
    params, loss_ref, loads = prepare(
        params, host_batch["tokens"], host_batch["targets"], cell)
    goal = load_targets(cell, host_batch["tokens"].size)
    if np.abs(loads - goal).max() > 2 * ROUTER_FIT_WITHIN * goal.mean():
        raise ValueError("the routers' loads are not the cell's profile: "
                         "%s against %s" % (loads.tolist(), goal.tolist()))
    # The finer comparisons come first, and before the optimizer's state is
    # on the device too.  The job makes one comparison, the step's loss
    # against the reference within ``loss_rtol`` (``jobs/jit_step.py``): a
    # program whose float32 read or first mixer is off the reference is
    # handed no room there, so that the job's own check reads not correct.
    batch = shard_batch(host_batch)
    loss_32 = float32_loss(cfg, mesh, params, batch)
    off = abs(loss_32 - loss_ref) / abs(loss_ref)
    print("yardstick: %s: the program read in float32 %.8g, %.3g off the "
          "plain reference %.8g (limit %g)"
          % (cell["name"], loss_32, off, loss_ref, FLOAT32_RTOL),
          file=sys.stderr)
    mixer = mixer_readings(cfg, mesh, params, host_batch["tokens"],
                           cell["config"])
    print("yardstick: %s: the first mixer in float32: %.3g of the queries "
          "keep other keys than the reference's (limit %g); the kernels' "
          "gradients %.3g off (limit %g; %s); crossed %.3g (limit 0)"
          % (cell["name"], mixer["choice"], CHOICE_LIMIT, mixer["gradient"],
             GRADIENT_RTOL, " ".join("%s %.3g" % kv
                                     for kv in mixer["by_input"].items()),
             mixer["crossed"]), file=sys.stderr)
    sound = off <= FLOAT32_RTOL and mixer["choice"] <= CHOICE_LIMIT \
        and mixer["gradient"] <= GRADIENT_RTOL and mixer["crossed"] == 0
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
        "loss_rtol": spec.get("loss_rtol", LOSS_RTOL) if sound else 0.0,
        "step": run_step, "state": (params, opt_state),
        "batch": batch,
        "reference": lambda state: loss_ref,
        "probe": lambda state: state[0]["ln_f"],
    }


# -- the plain reference ---------------------------------------------------

def _rms(x, w, eps):
    import jax.numpy as jnp
    from jax import lax
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _turn(y, rotary, theta):
    """RoPE on the first ``rotary`` dimensions of ``y`` ``[S, heads, d]``,
    halves ``(i, i + rotary / 2)`` at frequency ``theta^(-2i / rotary)``;
    the rest passes."""
    import jax.numpy as jnp
    seq = y.shape[0]
    i = np.arange(rotary // 2, dtype=np.float64)
    angle = np.arange(seq, dtype=np.float64)[:, None] \
        * float(theta) ** (-2.0 * i / rotary)
    cos, sin = (jnp.asarray(t, y.dtype)[:, None, :]
                for t in (np.cos(angle), np.sin(angle)))
    a, b, rest = (y[..., :rotary // 2], y[..., rotary // 2:rotary],
                  y[..., rotary:])
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, rest], -1)


def reference_indexer(x, p, config, precision="highest"):
    """The lightning indexer's inputs of one sequence, ``x`` ``[S, hidden]``
    normed (the caller detaches it): (``qI = x W_iq`` ``[S, 16,
    64]``, ``kI = LayerNorm(x W_ik)`` ``[S, 64]``, both turned on their
    first 32 dimensions, ``w = x W_iw / sqrt(16 x 64)`` ``[S, 16]``)."""
    import jax.numpy as jnp
    from jax import lax
    hi = lax.Precision(precision)
    seq = x.shape[0]
    sa = config["sa_config"]
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    theta, eps = config["rope_theta"], config["rms_norm_eps"]
    qi = _turn(jnp.dot(x, p["w_iq"], precision=hi).reshape(seq, heads, dim),
               dim // 2, theta)
    ki = jnp.dot(x, p["w_ik"], precision=hi)
    mean = jnp.mean(ki, -1, keepdims=True)
    var = jnp.mean(jnp.square(ki - mean), -1, keepdims=True)
    ki = (ki - mean) * lax.rsqrt(var + eps) * p["ik_norm"] + p["ik_bias"]
    ki = _turn(ki[:, None], dim // 2, theta)[:, 0]
    w = jnp.dot(x, p["w_iw"], precision=hi) / math.sqrt(heads * dim)
    return qi, ki, w


def reference_scores(qi, ki, w, precision="highest"):
    """``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` of the queries
    ``qi`` ``[Q, 16, 64]``, ``w`` ``[Q, 16]`` over every key ``ki`` ``[S,
    64]``: ``[Q, S]``, the heads summed from the first."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    scores = None
    for j in range(qi.shape[1]):
        term = w[:, j, None] * jax.nn.relu(
            jnp.dot(qi[:, j], ki.T, precision=lax.Precision(precision)))
        scores = term if scores is None else scores + term
    return scores


def reference_kept(scores, t, top_k):
    """The keys queries at positions ``t`` ``[Q]`` keep, ``[Q, S]`` bool:
    the ``min(top_k, t + 1)`` causal keys of the largest score, by
    ``lax.top_k`` (a tie to the lower index; -0 taken as +0)."""
    import jax.numpy as jnp
    from jax import lax
    seq = scores.shape[-1]
    causal = jnp.arange(seq)[None, :] <= t[:, None]
    _, ids = lax.top_k(jnp.where(causal, jnp.where(scores == 0, 0.0, scores),
                                 -jnp.inf), min(top_k, seq))
    return jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], ids].set(True) & causal


def reference_projections(x, p, config, precision="highest", wrong=()):
    """A layer's mixer's inputs to its attention from one sequence, ``x``
    ``[S, hidden]`` normed: {"q": ``[S, 32, 128]``, "k", "v": ``[S, 4,
    128]``, "qi", "ki", "w": ``reference_indexer``'s}, where

        q, k, v = x W_q, x W_k, x W_v       32 and 4 heads of 128
        q, k    = rms(q; g_q), rms(k; g_k)  each head's 128 channels
        q, k turned over the whole head, theta 1e7
        qI, kI, w = reference_indexer(x)     no gradient reaches x"""
    import jax.numpy as jnp
    from jax import lax
    seq = x.shape[0]
    eps = config["rms_norm_eps"]
    heads, kv, hd = (config["num_attention_heads"],
                     config["num_key_value_heads"], config["head_dim"])

    def dot(a, b):
        return jnp.dot(a, b, precision=lax.Precision(precision))

    q = dot(x, p["wq"]).reshape(seq, heads, hd)
    k = dot(x, p["wk"]).reshape(seq, kv, hd)
    v = dot(x, p["wv"]).reshape(seq, kv, hd)
    if "no_qk_norm" not in wrong:
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    theta = config["rope_theta"]
    qi, ki, w = reference_indexer(
        x if "indexer_not_detached" in wrong else lax.stop_gradient(x), p,
        config, precision)
    return {"q": _turn(q, hd, theta), "k": _turn(k, hd, theta), "v": v,
            "qi": qi, "ki": ki, "w": w}


def reference_attention(q, k, v, qi, ki, w, config, precision="highest",
                        wrong=()):
    """Attention over each query's kept keys -> (``[S, 32 x 128]``, the
    indexer loss, the mean over the queries of ``KL(p || softmax(I))`` over
    their kept keys):

        I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
        S_t     = the min(2048, t + 1) causal keys of the largest I[t, s]
        o_h     = softmax over S_t of (q_h k_{h // 8}^T / sqrt(128)) v
        p[t]    = the heads' probabilities over S_t summed, over 32, and
                  under stop_gradient

    The textbook softmax a block of queries at a time, every score of the
    block's rows held."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    hi = lax.Precision(precision)
    seq, heads, hd = q.shape
    kv = k.shape[1]
    top_k = config["sa_config"]["topk"] - ("top_k_less_one" in wrong)
    block = math.gcd(seq, REFERENCE_QUERY_BLOCK)
    group = heads // kv

    def rows(at):
        t = at * block + jnp.arange(block)
        scores = reference_scores(
            lax.dynamic_slice_in_dim(qi, at * block, block), ki,
            lax.dynamic_slice_in_dim(w, at * block, block), precision)
        kept = reference_kept(scores, t, top_k)
        if "dense_attention" in wrong:
            kept = jnp.arange(seq)[None, :] <= t[:, None]
        q_b = lax.dynamic_slice_in_dim(q, at * block, block)
        outs, p_sum = [], 0.0
        for g in range(kv):
            logits = jnp.einsum("qhd,kd->hqk",
                                q_b[:, g * group:(g + 1) * group], k[:, g],
                                precision=hi) / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(kept[None], logits, -jnp.inf),
                                   -1)
            outs.append(jnp.einsum("hqk,kd->qhd", probs, v[:, g],
                                   precision=hi))
            p_sum = p_sum + probs.sum(0)
        attn = lax.stop_gradient(p_sum / heads)
        log_q = jax.nn.log_softmax(jnp.where(kept, scores, -jnp.inf), -1)
        held = kept & (attn > 0)
        kl = jnp.sum(jnp.where(held, attn * (jnp.log(jnp.where(held, attn,
                                                                1.0))
                                             - jnp.where(held, log_q, 0.0)),
                               0.0), -1)
        return jnp.concatenate(outs, 1).reshape(block, -1), kl

    # Under a gradient a block of queries is computed again, not kept.
    attn, kl = lax.map(jax.checkpoint(rows, prevent_cse=False),
                       jnp.arange(seq // block))
    return attn.reshape(seq, -1), jnp.mean(kl)


def reference_sparse_attention(x, p, config, precision="highest", wrong=()):
    """A layer's mixer over one sequence, ``x`` ``[S, hidden]`` normed, as
    the configuration's ``assumed`` writes it -> (its output ``[S,
    hidden]``, the indexer loss): ``reference_attention`` of
    ``reference_projections``, then ``W_o``."""
    import jax.numpy as jnp
    from jax import lax
    attn, kl = reference_attention(
        **reference_projections(x, p, config, precision, wrong),
        config=config, precision=precision, wrong=wrong)
    return jnp.dot(attn, p["wo"], precision=lax.Precision(precision)), kl


def reference_expert_layer(x, p, config, precision="highest", wrong=(),
                           router_fit=None):
    """A layer's feed-forward over every token of the step, ``x`` ``[T,
    hidden]`` normed: (its output, the tokens every expert got, the router
    it chose them by).  One softmax over the 128 router logits in float32
    (``router_fit``, (x, router) -> router, replaces the router first);
    the 8 largest renormalised to sum to 1; the held experts' SwiGLUs one
    after another, each over every token under its weight (0 where the
    token did not choose it).  No shared expert."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    hi = lax.Precision(precision)
    first = config["held"]["first_expert"]

    def dot(a, b):
        return jnp.dot(a, b, precision=hi)

    router = p["router"] if router_fit is None \
        else router_fit(x, p["router"]).astype(x.dtype)
    logits = dot(x, router).astype(jnp.float32)
    scores = jax.nn.sigmoid(logits) if "sigmoid_router" in wrong \
        else jax.nn.softmax(logits, -1)
    _, ids = lax.top_k(scores, config["num_experts_per_tok"])
    loads = jnp.sum(ids[:, :, None] == jnp.arange(scores.shape[-1]), (0, 1))
    top = jnp.take_along_axis(scores, ids, -1)
    weights = (top / top.sum(-1, keepdims=True)).astype(x.dtype)

    def add(y, held):
        j, w1, w3, w2 = held
        w_j = jnp.sum(jnp.where(ids == first + j, weights, 0.0), -1)
        return y + w_j[:, None] * dot(jax.nn.silu(dot(x, w1)) * dot(x, w3),
                                      w2), None

    y, _ = lax.scan(add, jnp.zeros_like(x),
                    (jnp.arange(p["we1"].shape[0]), p["we1"], p["we3"],
                     p["we2"]))
    return y, loads, router


def reference_hidden(params, tokens, config, dtype="float32",
                     precision="highest", wrong=(), router_fit=None):
    """The share's decoder in float32 at the highest matmul precision, from
    the layer equations (the assumed parts are the configuration
    file's ``assumed``): ``tokens`` ``[B, S]`` -> (the hidden states after
    the final RMSNorm ``[B, S, hidden]``, the tokens every expert of every
    layer got ``[layers, experts]``, the routers they were chosen by, the
    layers' indexer losses summed).  Pre-norm layers,
    RMSNorm (eps 1e-6): ``x + reference_sparse_attention(rms(x))``, then
    ``x + reference_expert_layer(rms(x))``.  No kernels, no sort, no
    sharding; a layer at a time, its mixer a sequence at a time.  It reads
    the parameter tree and nothing else of the program.  ``dtype``,
    ``precision`` and ``wrong`` are for the readings that show what the loss
    limits catch (PERF.md): the same arithmetic a precision below the
    configuration's (bfloat16 throughout, default products), and with a part
    left out or wrong (``WRONG``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    assert set(wrong) <= set(WRONG), wrong
    eps = config["rms_norm_eps"]
    with jax.default_matmul_precision(precision):
        p = jax.tree.map(lambda w: w.astype(dtype), params)
        x, loads, routers, indexer = p["embed"][tokens], [], [], 0.0
        for lp in _layer_params(p):
            y, kl = lax.map(
                lambda h: reference_sparse_attention(h, lp, config,
                                                     precision, wrong),
                _rms(x, lp["ln1"], eps))
            x = x + y
            indexer = indexer + jnp.mean(kl.astype(jnp.float32))
            y, load, router = reference_expert_layer(
                _rms(x, lp["ln2"], eps).reshape(-1, x.shape[-1]), lp, config,
                precision, wrong, router_fit)
            x = x + y.reshape(x.shape)
            loads.append(load)
            routers.append(router)
        if "no_indexer_loss" in wrong:
            indexer = 0.0
        return (_rms(x, p["ln_f"], eps), jnp.stack(loads),
                jnp.stack(routers), indexer)


def reference_loss_fn(params, tokens, targets, config, **reading):
    """Mean next-token cross entropy over the vocabulary slice of
    ``tokens``, ``targets`` ``[B, S]``, plus the layers' indexer losses.  A
    reading a precision below rounds the hidden states and the head as it
    rounds everything; the logits' sums and the cross entropy stay float32,
    as the program's do."""
    import jax.numpy as jnp
    x, _, _, indexer = reference_hidden(params, tokens, config, **reading)
    head = params["head"].astype(x.dtype).astype(jnp.float32)
    return sum(reference_nll_sum(x[i].astype(jnp.float32), head, targets[i])
               for i in range(x.shape[0])) / targets.size + indexer


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
