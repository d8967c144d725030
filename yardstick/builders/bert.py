"""BERT through ``horovod_tpu/models/bert.py``: masked-LM pre-training and
sequence-classification fine-tuning steps, with a plain float32
``jax.numpy`` forward pass written from the paper beside them.

The cell's file chooses the objective, the sequence length and, for
fine-tuning, the distribution of real lengths under the padding mask.
"""

import math

import numpy as np

from yardstick import flops

MASK_ID, PAD_ID = 103, 0        # bert-large-uncased's [MASK] and [PAD]
REFERENCE_MICROBATCH = 4        # sequences the reference holds at once

# bf16 activations through 24 post-LN layers against a float32 reference
# with the exact GELU.  The masked-LM loss averages 308 positions of a
# 30522-way softmax: on the chip the two differ by 1e-4 of the loss or less
# (my chip runs, PR 22), and 2e-3 leaves no room for activations kept
# below bf16.  The classification loss averages 8 two-way decisions read
# off one token through a tanh: the same rounding shows whole, at most
# 1.6 % of the loss over 40 seeds on the chip (root mean square 0.5 %; my
# chip runs, PR 22); 4 % is 2.5 times the largest, and a dropped padding
# mask, layer or bias moves the loss by tens of percent.
LOSS_RTOL = {"mlm": 0.002, "classification": 0.04}


def _bert_config(cell):
    from horovod_tpu.models.bert import BertConfig
    c = cell["config"]
    return BertConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        d_ff=c["intermediate_size"], max_seq=c["max_position_embeddings"],
        type_vocab=c["type_vocab_size"], n_classes=2,
        norm_eps=c["layer_norm_eps"], dtype=c["activation_dtype"],
        param_dtype=c["param_dtype"])


def make_batch(cell, seed, samples):
    """One synthetic batch on the host, and the real token count of each
    sequence."""
    c, spec = cell["config"], cell["spec"]
    rng = np.random.default_rng(seed)
    seq = spec["seq_len"]
    # Ordinary word pieces: the first ~1000 ids of the vocabulary are
    # [PAD], [unused..], [CLS], [SEP], [MASK] and single characters.
    ids = rng.integers(min(1000, c["vocab_size"] // 2), c["vocab_size"],
                       (samples, seq), np.int32)
    if spec["objective"] == "mlm":
        # Full sequences, no padding mask; exactly round(rate x seq)
        # positions of each are masked and predicted.
        k = int(round(spec["mlm_rate"] * seq))
        mlm_mask = np.zeros((samples, seq), np.int32)
        for row in mlm_mask:
            row[rng.choice(seq, k, replace=False)] = 1
        tokens = np.where(mlm_mask == 1, MASK_ID, ids).astype(np.int32)
        return ({"tokens": tokens, "targets": ids, "mlm_mask": mlm_mask},
                [seq] * samples)
    dist = spec["lengths"]
    assert dist["distribution"] == "lognormal", dist
    lengths = np.clip(np.rint(rng.lognormal(
        math.log(dist["median"]), dist["sigma"], samples)),
        dist["min"], min(dist["max"], seq)).astype(int)
    mask = (np.arange(seq)[None, :] < lengths[:, None]).astype(np.int32)
    return ({"tokens": np.where(mask == 1, ids, PAD_ID).astype(np.int32),
             "mask": mask,
             "labels": rng.integers(0, 2, samples, np.int32)},
            [int(n) for n in lengths])


def _optimizer(cell):
    import optax
    opt = cell["config"]["optimizer"]
    assert opt["name"] == "adamw", opt
    return optax.adamw(opt["learning_rate"], weight_decay=opt["weight_decay"])


def _flops_per_sample(cell, token_counts):
    c, spec = cell["config"], cell["spec"]
    if spec["objective"] == "mlm":
        head = flops.bert_mlm_head_macs(
            int(round(spec["mlm_rate"] * spec["seq_len"])),
            c["hidden_size"], c["vocab_size"])
    else:
        head = flops.bert_cls_head_macs(c["hidden_size"], 2)
    return flops.bert_train_flops(
        token_counts, c["hidden_size"], c["num_hidden_layers"],
        c["intermediate_size"], head)


def _kernels(cell, samples):
    """The flash-attention calls of one step.  ``models/bert.py`` takes
    the kernel only where the batch has no padding mask."""
    c, spec = cell["config"], cell["spec"]
    if spec["objective"] != "mlm":
        return []
    cost = flops.flash_attention_cost(
        samples, c["num_attention_heads"], spec["seq_len"],
        c["hidden_size"] // c["num_attention_heads"], causal=False)
    return [{"kernel": "flash", "calls_per_step": c["num_hidden_layers"],
             "per_call": cost}]


def jit_step(cell, seed, hvd, devices):
    """``make_finetune_step`` over a (dp, tp) = (chips, 1) mesh."""
    import jax

    from horovod_tpu.models.bert import init_params, make_finetune_step
    cfg = _bert_config(cell)
    spec = cell["spec"]
    mesh = hvd.create_mesh((len(devices), 1), (cfg.dp_axis, cfg.tp_axis),
                           devices)
    build, shard_batch = make_finetune_step(
        cfg, mesh, _optimizer(cell), objective=spec["objective"])
    # Weights on the device in one jitted call from the seed.
    params = jax.jit(lambda key: init_params(key, cfg))(
        jax.random.PRNGKey(seed))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    want = cell["config"].get("parameters")
    if want is not None and n_params != want:
        raise ValueError("BERT has %d parameters, the configuration says %d"
                         % (n_params, want))
    n = spec["batch_per_chip"] * len(devices)
    host_batch, token_counts = make_batch(cell, seed, n)
    step, params, opt_state = build(params)
    batch = shard_batch(host_batch)

    def run_step(state, batch):
        params, opt_state, loss = step(state[0], state[1], batch)
        return (params, opt_state), loss

    return {
        "samples_per_step": n,
        "flops_per_sample": _flops_per_sample(cell, token_counts),
        "grad_bytes": sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(params)),
        "kernels": _kernels(cell, n),
        "loss_rtol": LOSS_RTOL[spec["objective"]],
        "step": run_step, "state": (params, opt_state), "batch": batch,
        "reference": lambda state: reference_loss(
            state[0], host_batch, cell["config"], spec["objective"]),
        "probe": lambda state: state[0]["cls_b"],
    }


# -- the plain reference ---------------------------------------------------

def reference_loss(params, host_batch, config, objective):
    """BERT's loss in float32 at the highest matmul precision, written
    from Devlin et al.: token + position + segment embeddings, LayerNorm,
    then post-LN layers of multi-head self-attention (softmax of
    q.k / sqrt(head size), padding keys masked out) and a GELU
    feed-forward, exact GELU as published.  Masked LM: transform, GELU,
    LayerNorm, decoder tied to the word embeddings, mean cross entropy
    over the masked positions.  Classification: tanh pooler on the first
    token, linear layer, mean cross entropy.  No kernels, no sharding; it
    reads the parameter tree and nothing else of the program.  A few
    sequences at a time, to keep its memory small beside the step's."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    heads, eps = config["num_attention_heads"], config["layer_norm_eps"]
    hi = lax.Precision.HIGHEST

    def ln(x, g, b):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + eps) * g + b

    def gelu(x):
        return jax.nn.gelu(x, approximate=False)

    def encode(p, tokens, mask):
        b, s = tokens.shape
        x = p["word_embed"][tokens] + p["pos_embed"][:s][None] \
            + p["type_embed"][0][None, None]
        x = ln(x, p["ln_embed_g"], p["ln_embed_b"])
        bias = jnp.where(mask[:, None, None, :] > 0, 0.0, -1e9)

        def layer(x, lp):
            def proj(w, bb):
                return (jnp.dot(x, w, precision=hi) + bb).reshape(
                    b, s, heads, -1)
            q, k, v = (proj(lp["w" + n], lp["b" + n]) for n in "qkv")
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) \
                / math.sqrt(q.shape[-1]) + bias
            attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                              v, precision=hi).reshape(b, s, -1)
            x = ln(x + jnp.dot(attn, lp["wo"], precision=hi) + lp["bo"],
                   lp["ln1_g"], lp["ln1_b"])
            ff = jnp.dot(gelu(jnp.dot(x, lp["w_in"], precision=hi)
                              + lp["b_in"]), lp["w_out"], precision=hi)
            return ln(x + ff + lp["b_out"], lp["ln2_g"], lp["ln2_b"]), None

        return lax.scan(layer, x, p["layers"])[0]

    def mlm_sums(p, mb):
        h = encode(p, mb["tokens"], jnp.ones_like(mb["tokens"]))
        h = ln(gelu(jnp.dot(h, p["mlm_w"], precision=hi) + p["mlm_b"]),
               p["mlm_ln_g"], p["mlm_ln_b"])
        logits = jnp.dot(h, p["word_embed"].T, precision=hi) + p["mlm_bias"]
        nll = -jnp.take_along_axis(jax.nn.log_softmax(logits),
                                   mb["targets"][..., None], -1)[..., 0]
        m = mb["mlm_mask"].astype(jnp.float32)
        return (nll * m).sum(), m.sum()

    def cls_sums(p, mb):
        h = encode(p, mb["tokens"], mb["mask"])
        pooled = jnp.tanh(jnp.dot(h[:, 0], p["pooler_w"], precision=hi)
                          + p["pooler_b"])
        logits = jnp.dot(pooled, p["cls_w"], precision=hi) + p["cls_b"]
        nll = -jnp.take_along_axis(jax.nn.log_softmax(logits),
                                   mb["labels"][:, None], -1)[:, 0]
        return nll.sum(), jnp.float32(nll.shape[0])

    sums = mlm_sums if objective == "mlm" else cls_sums

    def loss(p, batch):
        p = jax.tree.map(lambda x: x.astype(jnp.float32), p)
        num, den = lax.map(lambda mb: sums(p, mb), batch)
        return num.sum() / den.sum()

    n = len(host_batch["tokens"])
    mb = math.gcd(n, REFERENCE_MICROBATCH)
    device = sorted(jax.tree.leaves(params)[0].devices(),
                    key=lambda d: d.id)[0]
    batch = {k: jax.device_put(v.reshape((n // mb, mb) + v.shape[1:]), device)
             for k, v in host_batch.items()}
    return float(jax.jit(loss)(jax.device_put(params, device), batch))


# -- compiled for a chip that is not attached (rehearse.py compile) --------

def aot_step(cell, devices):
    """[(label, jitted, abstract arguments)] of the cell's step over
    described ``devices``.  ``make_finetune_step`` builds its jitted step
    only after placing real parameters, which a described device cannot
    hold; so this assembles the same step from the same public pieces
    (the loss, ``param_specs``, ``opt_spec_tree``) in the same way.  It
    stands for the program only as a rehearsal: what is measured is the
    program's own."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import bert
    from horovod_tpu.models.transformer import opt_spec_tree
    cfg, spec = _bert_config(cell), cell["spec"]
    mesh = jax.sharding.Mesh(np.asarray(devices).reshape(len(devices), 1),
                             (cfg.dp_axis, cfg.tp_axis))
    optimizer = _optimizer(cell)
    loss_fn = (bert.mlm_loss if spec["objective"] == "mlm"
               else bert.classification_loss)
    specs = bert.param_specs(cfg)
    params = jax.eval_shape(lambda key: bert.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    o_specs = opt_spec_tree(opt_state, params, specs)
    n = spec["batch_per_chip"] * len(devices)
    host_batch, _ = make_batch(cell, 0, n)
    rows = {k: P(cfg.dp_axis, *([None] * (v.ndim - 1)))
            for k, v in host_batch.items()}

    def local_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg))(params)
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

    batch = {k: jax.ShapeDtypeStruct(v.shape, jnp.int32,
                                     sharding=NamedSharding(mesh, rows[k]))
             for k, v in host_batch.items()}
    return [("make_finetune_step(%s)" % spec["objective"], step,
             (on(params, specs), on(opt_state, o_specs), batch))]
