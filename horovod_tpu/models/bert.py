"""BERT-style bidirectional encoder, TPU-first SPMD.

Covers the reference's "PyTorch BERT-large fine-tune" flagship config
(BASELINE.json configs[2]) as a native model family: a pure-function
encoder over a params pytree with layer-stacked ``[L, ...]`` leaves
consumed by ``lax.scan`` (single-layer trace, static shapes, bf16
activations on the MXU), sharded Megatron-style over a (dp, tp) mesh:

* **dp** — batch sharding; gradient psum fused into the step.
* **tp** — attention heads / FFN columns column-row sharded (one psum
  after ``wo`` and one after ``w_out``); vocab-sharded word embedding
  and vocab-parallel MLM cross entropy (never materializes the full
  vocab on one shard).

Architectural choices vs the decoder flagship (``transformer.py``):
bidirectional attention (the Pallas flash kernel with ``causal=False``
when no padding mask is given; masked attention falls back to the XLA
path with an additive bias), learned position + token-type embeddings,
post-LN residual blocks and GELU — the original BERT recipe.  The
attention-mask contract matches ``transformers``' ``attention_mask``
(1 = attend, 0 = padding).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..common import metrics, scopes
from ..ops import pallas_kernels
from .transformer import (_sharded_embed_lookup, opt_spec_tree,
                          vocab_parallel_cross_entropy)


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    max_seq: int = 512
    type_vocab: int = 2
    n_classes: int = 2            # sequence-classification head width
    norm_eps: float = 1e-12
    dtype: str = "bfloat16"       # activation dtype (MXU-native)
    param_dtype: str = "float32"
    remat: bool = False
    dp_axis: str = "dp"
    tp_axis: str = "tp"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def act_dtype(self):
        return jnp.dtype(self.dtype)


def init_params(key, cfg: BertConfig):
    """Layer-stacked parameter pytree (host-side, full/unsharded)."""
    pd = jnp.dtype(cfg.param_dtype)
    d, f, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    ks = jax.random.split(key, 12)

    def norm(k, shape, fan_in):
        return (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(pd)

    return {
        "word_embed": norm(ks[0], (cfg.vocab_size, d), d),
        "pos_embed": norm(ks[1], (cfg.max_seq, d), d),
        "type_embed": norm(ks[2], (cfg.type_vocab, d), d),
        "ln_embed_g": jnp.ones((d,), pd),
        "ln_embed_b": jnp.zeros((d,), pd),
        "layers": {
            # Separate projections: a fused [d, 3d] param sharded
            # P(..., tp) would hand shard 0 all of Q plus part of K
            # (contiguous column slices cross the q/k/v boundary); the
            # per-shard compute below concatenates the LOCAL slices,
            # which is exact for any tp.
            "wq": norm(ks[3], (L, d, d), d),
            "wk": norm(ks[10], (L, d, d), d),
            "wv": norm(ks[11], (L, d, d), d),
            "bq": jnp.zeros((L, d), pd),
            "bk": jnp.zeros((L, d), pd),
            "bv": jnp.zeros((L, d), pd),
            "wo": norm(ks[4], (L, d, d), d),
            "bo": jnp.zeros((L, d), pd),
            "ln1_g": jnp.ones((L, d), pd),
            "ln1_b": jnp.zeros((L, d), pd),
            "w_in": norm(ks[5], (L, d, f), d),
            "b_in": jnp.zeros((L, f), pd),
            "w_out": norm(ks[6], (L, f, d), f),
            "b_out": jnp.zeros((L, d), pd),
            "ln2_g": jnp.ones((L, d), pd),
            "ln2_b": jnp.zeros((L, d), pd),
        },
        "pooler_w": norm(ks[7], (d, d), d),
        "pooler_b": jnp.zeros((d,), pd),
        "cls_w": norm(ks[8], (d, cfg.n_classes), d),
        "cls_b": jnp.zeros((cfg.n_classes,), pd),
        # MLM head: transform + layernorm; decoder weight is TIED to
        # word_embed (the BERT recipe), only a vocab bias is stored.
        "mlm_w": norm(ks[9], (d, d), d),
        "mlm_b": jnp.zeros((d,), pd),
        "mlm_ln_g": jnp.ones((d,), pd),
        "mlm_ln_b": jnp.zeros((d,), pd),
        "mlm_bias": jnp.zeros((cfg.vocab_size,), pd),
    }


def param_specs(cfg: BertConfig):
    """Megatron (dp, tp) sharding: vocab-sharded word embedding +
    MLM bias, column/row-sharded attention and FFN, everything else
    replicated."""
    from jax.sharding import PartitionSpec as P
    tp = cfg.tp_axis
    rep1, rep2 = P(None), P(None, None)
    return {
        "word_embed": P(tp, None),
        "pos_embed": rep2,
        "type_embed": rep2,
        "ln_embed_g": rep1, "ln_embed_b": rep1,
        "layers": {
            "wq": P(None, None, tp), "wk": P(None, None, tp),
            "wv": P(None, None, tp),
            "bq": P(None, tp), "bk": P(None, tp), "bv": P(None, tp),
            "wo": P(None, tp, None), "bo": P(None, None),
            "ln1_g": rep2, "ln1_b": rep2,
            "w_in": P(None, None, tp), "b_in": P(None, tp),
            "w_out": P(None, tp, None), "b_out": P(None, None),
            "ln2_g": rep2, "ln2_b": rep2,
        },
        "pooler_w": rep2, "pooler_b": rep1,
        "cls_w": rep2, "cls_b": rep1,
        "mlm_w": rep2, "mlm_b": rep1,
        "mlm_ln_g": rep1, "mlm_ln_b": rep1,
        "mlm_bias": P(tp),
    }


def layer_norm(x, g, b, eps):
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y.astype(x.dtype) * g.astype(x.dtype)
            + b.astype(x.dtype))


@jax.named_scope(scopes.ATTENTION)
def _attention(h, lp, cfg: BertConfig, mask):
    """Bidirectional self-attention; per-shard code (tp slice of the
    heads).  ``mask`` is [B, S] with 1 = attend (transformers
    contract) or None for dense sequences."""
    b, s, _ = h.shape
    hd = cfg.head_dim
    q = (h @ lp["wq"].astype(h.dtype)
         + lp["bq"].astype(h.dtype)).reshape(b, s, -1, hd)
    k = (h @ lp["wk"].astype(h.dtype)
         + lp["bk"].astype(h.dtype)).reshape(b, s, -1, hd)
    v = (h @ lp["wv"].astype(h.dtype)
         + lp["bv"].astype(h.dtype)).reshape(b, s, -1, hd)
    if mask is None and pallas_kernels.use_flash_attention():
        attn = pallas_kernels.flash_attention(q, k, v, causal=False)
    else:
        # XLA path with an additive bias for padding keys.
        qf = q.astype(jnp.float32) / math.sqrt(hd)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf,
                            k.astype(jnp.float32))
        if mask is not None:
            bias = jnp.where(mask[:, None, None, :] > 0, 0.0, -1e9)
            scores = scores + bias
        p = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", p,
                          v.astype(jnp.float32)).astype(h.dtype)
    out = attn.reshape(b, s, -1) @ lp["wo"].astype(h.dtype)
    # Row-sharded wo: partial sums live on each tp shard; the bias is
    # replicated, so add it AFTER the psum exactly once.
    return lax.psum(out, cfg.tp_axis) + lp["bo"].astype(h.dtype)


def _ffn(h, lp, cfg: BertConfig):
    a = jax.nn.gelu(h @ lp["w_in"].astype(h.dtype)
                    + lp["b_in"].astype(h.dtype))
    out = a @ lp["w_out"].astype(h.dtype)
    return lax.psum(out, cfg.tp_axis) + lp["b_out"].astype(h.dtype)


def encode(params, tokens, cfg: BertConfig, token_type=None, mask=None):
    """Per-shard encoder: tokens [B_loc, S] -> hidden [B_loc, S, d].
    Must run inside a shard_map over a mesh containing (dp, tp)."""
    s = tokens.shape[1]
    x = _sharded_embed_lookup(params["word_embed"], tokens, cfg.tp_axis)
    x = x + params["pos_embed"][:s][None]
    tt = (token_type if token_type is not None
          else jnp.zeros_like(tokens))
    x = x + jnp.take(params["type_embed"], tt, axis=0)
    x = layer_norm(x, params["ln_embed_g"], params["ln_embed_b"],
                   cfg.norm_eps).astype(cfg.act_dtype)

    def layer(x, lp):
        # Post-LN residual blocks (original BERT).
        x = layer_norm(x + _attention(x, lp, cfg, mask),
                       lp["ln1_g"], lp["ln1_b"], cfg.norm_eps)
        x = layer_norm(x + _ffn(x, lp, cfg),
                       lp["ln2_g"], lp["ln2_b"], cfg.norm_eps)
        return x, None

    layer_fn = jax.checkpoint(layer) if cfg.remat else layer
    x, _ = lax.scan(layer_fn, x, params["layers"])
    return x


def mlm_logits_local(params, hidden, cfg: BertConfig):
    """Vocab-parallel MLM head: [B, S, d] -> [B, S, V/tp] f32 (tied
    decoder = the word-embedding shard, so the matmul stays
    vocab-sharded like the lookup)."""
    h = jax.nn.gelu(hidden.astype(jnp.float32)
                    @ params["mlm_w"].astype(jnp.float32)
                    + params["mlm_b"].astype(jnp.float32))
    h = layer_norm(h, params["mlm_ln_g"].astype(jnp.float32),
                   params["mlm_ln_b"].astype(jnp.float32), cfg.norm_eps)
    return (h @ params["word_embed"].astype(jnp.float32).T
            + params["mlm_bias"].astype(jnp.float32))


def cls_logits(params, hidden):
    """[CLS] pooled sequence-classification head: [B, S, d] -> [B, C]."""
    pooled = jnp.tanh(hidden[:, 0].astype(jnp.float32)
                      @ params["pooler_w"].astype(jnp.float32)
                      + params["pooler_b"].astype(jnp.float32))
    return pooled @ params["cls_w"].astype(jnp.float32) \
        + params["cls_b"].astype(jnp.float32)


def mlm_loss(params, batch, cfg: BertConfig):
    """Masked-LM loss: mean nll over GLOBAL masked positions.

    Numerator and denominator are psum'ed over dp separately before
    the division — a per-shard masked mean then pmean'ed would weight
    shards with few masked positions as heavily as full ones (uneven
    ~15% masking makes per-shard counts differ every batch), breaking
    mesh invariance of the loss and gradients."""
    hidden = encode(params, batch["tokens"], cfg,
                    batch.get("token_type"), batch.get("mask"))
    with jax.named_scope(scopes.HEAD):
        logits = mlm_logits_local(params, hidden, cfg)
        nll = vocab_parallel_cross_entropy(logits, batch["targets"],
                                           cfg.tp_axis)
    m = batch["mlm_mask"].astype(jnp.float32)
    num = lax.psum((nll * m).sum(), cfg.dp_axis)
    den = lax.psum(m.sum(), cfg.dp_axis)
    return num / jnp.maximum(den, 1.0)


def classification_loss(params, batch, cfg: BertConfig):
    """Per-shard [CLS] cross entropy (fine-tune objective)."""
    hidden = encode(params, batch["tokens"], cfg,
                    batch.get("token_type"), batch.get("mask"))
    with jax.named_scope(scopes.HEAD):
        logits = cls_logits(params, hidden)
        nll = -jax.nn.log_softmax(logits)[
            jnp.arange(logits.shape[0]), batch["labels"]]
    return lax.pmean(nll.mean(), cfg.dp_axis)


def make_finetune_step(cfg: BertConfig, mesh, optimizer,
                       objective: str = "classification",
                       donate: bool = True):
    """Jitted SPMD fine-tune step over a (dp, tp) mesh.

    Returns ``(build, shard_batch)``;
    ``build(params_host) -> (step, params, opt_state)`` with
    ``step(params, opt_state, batch) -> (params, opt_state, loss)``.
    Gradients are psum'ed over dp inside the compiled program (the
    framework's DP story fused into the step — what the reference's
    DistributedOptimizer does from the outside)."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    loss_fn = (classification_loss if objective == "classification"
               else mlm_loss)
    specs = param_specs(cfg)
    dp = cfg.dp_axis
    batch_specs = {"tokens": P(dp, None), "targets": P(dp, None),
                   "token_type": P(dp, None), "mask": P(dp, None),
                   "mlm_mask": P(dp, None), "labels": P(dp)}

    def local_step(params, opt_state, batch):
        # vma-tracked AD (check_vma=True below) differentiates the dp
        # pmean in the loss with exact collective transposes, so the
        # per-shard grads ARE the global-batch gradient — no manual
        # combine (verified by the sharded-vs-single gradient test).
        # So the dp reduction is part of the backward pass
        # (transpose(jvp(hvd.model))) and nothing here is hvd.exchange.
        loss, grads = jax.value_and_grad(jax.named_scope(scopes.MODEL)(
            lambda p: loss_fn(p, batch, cfg)))(params)
        with jax.named_scope(scopes.OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    def build(params_host):
        with metrics.span(scopes.BUILD_STATE) as placed:
            params = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                params_host, specs)
            with metrics.span(scopes.OPTIMIZER_INIT):
                opt_state = optimizer.init(params)
            # Optimizer subtrees isomorphic to params inherit param specs.
            o_specs = opt_spec_tree(opt_state, params_host, specs)
            opt_state = jax.tree.map(
                lambda x, s: jax.device_put(jnp.asarray(x),
                                            NamedSharding(mesh, s))
                if hasattr(x, "shape") else x, opt_state, o_specs)
            placed.attributes["leaves"] = len(
                jax.tree.leaves((params, opt_state)))

        def make(batch_keys):
            bspec = {k: batch_specs[k] for k in batch_keys}
            mapped = jax.shard_map(
                local_step, mesh=mesh,
                in_specs=(specs, o_specs, bspec),
                out_specs=(specs, o_specs, P()),
                check_vma=True)
            return jax.jit(mapped,
                           donate_argnums=(0, 1) if donate else ())

        compiled = {}

        def step(params, opt_state, batch):
            key = tuple(sorted(batch))
            if key not in compiled:
                compiled[key] = make(key)
            return compiled[key](params, opt_state, batch)

        return step, params, opt_state

    @metrics.span(scopes.SHARD_BATCH)
    def shard_batch(batch):
        from jax.sharding import NamedSharding
        return {k: jax.device_put(jnp.asarray(v),
                                  NamedSharding(mesh, batch_specs[k]))
                for k, v in batch.items()}

    return build, shard_batch
