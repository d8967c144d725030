"""Flagship model: llama-style decoder-only transformer, TPU-first SPMD.

Plays the role of the reference's flagship workloads (BASELINE.json
configs: "PyTorch BERT-large fine-tune", "Elastic Llama-3-8B",
"horovod.jax adapter: Llama-3-70B data-parallel") — but built natively for
a TPU mesh rather than wrapped around a torch model:

* **dp** — batch sharding; gradient psum (what the reference's
  DistributedOptimizer did) fused into the step.
* **tp** — Megatron-style tensor parallelism: attention heads and FFN
  columns sharded, one psum after wo and one after w2; vocab-sharded
  embedding + vocab-parallel cross entropy (max/psum over tp).
* **sp** — ring attention over the sequence axis
  (``horovod_tpu.parallel.ring_attention``): KV blocks rotate on the ICI
  ring; activations stay sequence-sharded everywhere else.
* **ep** — optional MoE FFN with all-to-all expert dispatch
  (``horovod_tpu.parallel.moe``); the sequence axis doubles as the expert
  axis (sequence-sharded MoE layout).

Everything is a pure function over a params pytree with layer-stacked
leaves ``[L, ...]`` consumed by ``lax.scan`` (single-layer trace, static
shapes, bf16 activations on the MXU, optional ``jax.checkpoint`` remat).

What a layer is made of is said in one place, ``layer_pattern``: one
period of (mixer, feed-forward) entries out of ``MIXERS`` and
``FEED_FORWARDS``.  An entry with both is a pair, each sub-layer with a
norm of its own (``ln1``, ``ln2``) and its own residual add: the norm on
the sub-layer's input, ``x + sub(rms_norm(x))``, or, where the
configuration says ``post_norm`` (OLMo 2's reordered norm), on its output,
``x + rms_norm(sub(x))``; an entry
whose mixer or whose feed-forward is None is a block of the one sub-layer
that is there, under its one norm (a model whose blocks alternate instead
of pairing).  The scan runs over periods, ``params["layers"]`` is a
tuple with one stacked dict for each layer of the period, and each layer is
recomputed on its own.  ``leading_layers`` are entries that run once ahead
of the scan (``params["leading"]``: a model whose first layers differ from
its periods).  The block functions of the kinds that need more
than a few lines live beside their mechanism (``models/linear_attention.py``,
``models/state_space.py``, ``parallel/moe.py``); a new architecture is one
more kind there and an entry of the pattern here.  Softmax attention is one
block whatever the kind: what a kind of layer fixes of it (head counts,
window, rotary table, gate) is a ``SoftmaxAttention``, which a pattern's
entry may hold in the mixer's place; ``attention`` and
``gated_nope_attention`` name two of its settings.  Multi-head latent
attention (keys and values out of one low-rank latent, query/key heads of
another size than the values', one rotary key for all heads) is a block of
its own: a ``LatentAttention`` in the mixer's place.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..common import metrics, scopes
from ..ops import pallas_kernels
from ..parallel.moe import SAVED as moe_saved_names
from ..parallel.moe import (ExpertShare, MoeConfig, expert_share_ffn,
                            init_expert_share_params, moe_ffn)
from ..parallel.ring_attention import (local_attention, pvary_missing,
                                       ring_attention)
from .linear_attention import SAVED as kda_saved_names
from .linear_attention import (KdaConfig, init_kda_params, kda_param_specs,
                               linear_attention_block)
from .state_space import SAVED as ssm_saved_names
from .state_space import (SsmConfig, init_ssm_params, ssm_param_specs,
                          state_space_block)

# Kinds a layer is made of.  ``attention``: RoPE softmax attention (GQA);
# ``gated_nope_attention``: the same with no positional encoding and an
# element-wise sigmoid gate on the heads' output (both at the model's
# ``n_heads`` / ``n_kv_heads``; a ``SoftmaxAttention`` in the mixer's place
# says its own); ``linear_attention``: the gated delta rule
# (``cfg.linear_attention``); ``state_space``: a Mamba-2 mixer
# (``cfg.state_space``).  ``dense``: SwiGLU; ``moe``:
# capacity-factor experts with an all-to-all over ``sp``;
# ``expert_share``: this chip's share of a dropless expert layer beside a
# shared expert (``cfg.experts``).  None in either place: the block has no
# such sub-layer.
MIXERS = ("attention", "gated_nope_attention", "linear_attention",
          "state_space")
FEED_FORWARDS = ("dense", "moe", "expert_share")


@dataclasses.dataclass(frozen=True)
class Rope:
    """One rotary table.  ``share`` of every head rotates, its first
    dimensions (``partial_rotary_factor``); the rest passes through.
    ``factor`` other than 1 is YaRN: the frequencies that turn fewer than
    ``beta_slow`` times over ``original_max_seq`` positions are divided by
    ``factor``, those that turn more than ``beta_fast`` times stay, a
    linear ramp between; cos and sin are scaled by ``attention_factor``."""
    theta: float = 10000.0
    share: float = 1.0
    factor: float = 1.0
    original_max_seq: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def __post_init__(self):
        if not 0 < self.share <= 1:
            raise ValueError("a rotary table covers a share of (0, 1] of a "
                             "head, not %r" % (self.share,))
        if self.factor != 1.0 and self.original_max_seq <= 0:
            raise ValueError("YaRN by %r needs the positions it was "
                             "stretched from" % (self.factor,))


@dataclasses.dataclass(frozen=True)
class SoftmaxAttention:
    """What a kind of layer fixes of softmax attention: query and
    key/value heads on this tp shard group, the ``window`` of keys a query
    sees (None: every key before it), its rotary table (None: no
    positional encoding), whether a sigmoid gate from a projection of
    its own multiplies the heads' output before ``wo``, and whether an
    RMSNorm runs over the whole q and the whole k projection before the
    heads are split (``qk_norm``, OLMo 2's: one mean square over every
    head, so the heads cannot be split over ``tp``)."""
    n_heads: int
    n_kv_heads: int
    window: Optional[int] = None
    rope: Optional[Rope] = Rope()
    gate: bool = False
    qk_norm: bool = False

    def __post_init__(self):
        if self.n_kv_heads < 1 or self.n_heads % self.n_kv_heads:
            raise ValueError("%d key/value heads do not divide %d query "
                             "heads" % (self.n_kv_heads, self.n_heads))
        if self.window is not None and self.window < 1:
            raise ValueError("a window of %r keys holds not even the query "
                             "itself" % (self.window,))


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    """Multi-head latent attention (the ``deepseek_v3`` family's, without a
    query latent): ``n_heads`` query heads of ``nope + rope_dim``, whose
    last ``rope_dim`` dimensions the rotary table turns; keys and values
    from one ``kv_rank``-wide latent a position (``wkv_a``, an RMSNorm,
    ``wkv_b``: ``nope`` key dimensions and ``v_dim`` value dimensions a
    head) beside ONE ``rope_dim``-wide rotary key a position that every
    head shares.  Scores are scaled by ``(nope + rope_dim)^-1/2``, the
    query/key size and not the value's."""
    n_heads: int
    kv_rank: int
    nope: int
    rope_dim: int
    v_dim: int
    rope: Rope = Rope()

    def __post_init__(self):
        if min(self.n_heads, self.kv_rank, self.nope, self.v_dim) < 1 \
                or self.rope_dim < 2 or self.rope_dim % 2:
            raise ValueError("a latent-attention block needs heads, a latent,"
                             " key and value sizes and an even rotary size, "
                             "not %r" % (self,))
        if self.rope.share != 1.0:
            raise ValueError("the rotary key of a latent-attention block "
                             "turns whole (rope_dim says how wide it is), "
                             "not a share of %r" % (self.rope.share,))

    @property
    def qk_dim(self) -> int:
        return self.nope + self.rope_dim


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    d_ff: int = 1344
    max_seq: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"          # activation dtype (MXU-native)
    param_dtype: str = "float32"
    remat: bool = False
    # Remat policy when remat=True: "full" recomputes everything in
    # the backward (default jax.checkpoint), "dots" saves matmul
    # outputs and recomputes only elementwise work, "dots_no_batch"
    # saves only no-batch-dim dots (weights-side products).  Measured
    # per-policy on the flagship config in docs/benchmarks.md.
    remat_policy: str = "full"
    # Size of the ``moe`` feed-forward, for a pattern that holds one.
    n_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    # Mesh axis names (mesh must contain all of them; size 1 is fine).
    dp_axis: str = "dp"
    sp_axis: str = "sp"
    tp_axis: str = "tp"
    # sequence-parallel strategy when sp>1: "ring" (ppermute KV
    # rotation, any head count) or "ulysses" (alltoall head/sequence
    # exchange; the PER-TP-SHARD head counts — n_heads/tp and
    # n_kv_heads/tp — must both divide by sp; composes with flash
    # attention)
    sp_mode: str = "ring"
    # Latency-hiding TP matmuls (parallel/collective_matmul.py): the
    # row-parallel wo / w2 products run as an overlapped
    # matmul+reduce-scatter ring followed by a tiled all_gather (same
    # bytes as the plain psum, but the reduce leg hides behind MXU
    # work).  No-op at tp=1, so single-chip programs are unchanged.
    collective_matmul: bool = False
    # One period of the layer pattern, ((mixer, feed-forward), ...) out of
    # MIXERS (or a SoftmaxAttention, or a LatentAttention) x FEED_FORWARDS,
    # either of which may be None (not both); n_layers less the leading
    # layers is a multiple of its length.
    layer_pattern: Tuple[Tuple[object, Optional[str]], ...] = (
        ("attention", "dense"),)
    # Entries of the same kinds that run once, each with parameters of its
    # own, ahead of the scanned periods.
    leading_layers: Tuple[Tuple[object, Optional[str]], ...] = ()
    # Size of an attention head where it is not d_model / n_heads (a
    # chip's share of the heads keeps the model's head size).
    head_size: Optional[int] = None
    linear_attention: Optional[KdaConfig] = None
    state_space: Optional[SsmConfig] = None
    experts: Optional[ExpertShare] = None
    # An output head of its own, ``params["head"]`` [d, V], instead of the
    # embedding's transpose.
    tie_embeddings: bool = True
    # Tokens a block of the loss's head: logits and cross entropy are
    # computed (and recomputed in the backward pass) a block at a time and
    # [tokens, V] never exists.  0 = all at once.
    head_block: int = 0
    # Where a block's norms act: on each sub-layer's input (False,
    # ``x + sub(rms_norm(x))``) or on its output (True, OLMo 2's reordered
    # norm, ``x + rms_norm(sub(x))``).
    post_norm: bool = False

    def __post_init__(self):
        if self.sp_mode not in ("ring", "ulysses"):
            raise ValueError("sp_mode must be 'ring' or 'ulysses', "
                             "got %r" % (self.sp_mode,))
        if self.remat_policy not in ("full", "dots", "dots_no_batch"):
            raise ValueError("remat_policy must be 'full', 'dots' or "
                             "'dots_no_batch', got %r"
                             % (self.remat_policy,))
        for mixer, ffn in self.pairs:
            if not (isinstance(mixer, (SoftmaxAttention, LatentAttention))
                    or mixer in MIXERS + (None,)) \
                    or ffn not in FEED_FORWARDS + (None,) \
                    or (mixer is None and ffn is None):
                raise ValueError("layer_pattern pairs a mixer of %s (or a "
                                 "SoftmaxAttention or a LatentAttention) "
                                 "with a feed-forward of "
                                 "%s, or holds one of them alone, not %r"
                                 % (MIXERS, FEED_FORWARDS, (mixer, ffn)))
            if (mixer == "linear_attention" and not self.linear_attention) \
                    or (mixer == "state_space" and not self.state_space) \
                    or (ffn == "expert_share" and not self.experts):
                raise ValueError("%r needs its configuration"
                                 % ((mixer, ffn),))
        periodic = self.n_layers - len(self.leading_layers)
        if periodic < 0 or periodic % len(self.layer_pattern):
            raise ValueError("%d layers are no whole number of periods of %d"
                             " after %d leading ones"
                             % (self.n_layers, len(self.layer_pattern),
                                len(self.leading_layers)))

    @property
    def head_dim(self) -> int:
        return self.head_size or self.d_model // self.n_heads

    @property
    def pairs(self):
        """The (mixer, feed-forward) entries of the leading layers, then
        of one period."""
        return self.leading_layers + self.layer_pattern

    def softmax_kind(self, mixer) -> Optional[SoftmaxAttention]:
        """The setting of the softmax block a mixer stands for; None for a
        mixer that is no softmax attention."""
        if isinstance(mixer, SoftmaxAttention):
            return mixer
        if mixer == "attention":
            return SoftmaxAttention(self.n_heads, self.n_kv_heads,
                                    rope=Rope(theta=self.rope_theta))
        if mixer == "gated_nope_attention":
            return SoftmaxAttention(self.n_heads, self.n_kv_heads,
                                    rope=None, gate=True)
        return None

    @property
    def act_dtype(self):
        return jnp.dtype(self.dtype)

    def moe_config(self) -> MoeConfig:
        return MoeConfig(n_experts=self.n_experts, d_model=self.d_model,
                         d_ff=self.d_ff, top_k=self.top_k,
                         capacity_factor=self.capacity_factor)


# --------------------------------------------------------------------------
# Parameters
# --------------------------------------------------------------------------

def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)


def _init_layers(key, cfg: TransformerConfig, mixer, ffn, n: int):
    """``n`` stacked layers of one (mixer, feed-forward) kind: ``ln1`` and
    the mixer's parameters where it has a mixer, ``ln2`` and the
    feed-forward's where it has one."""
    pd = jnp.dtype(cfg.param_dtype)
    d, hd, f = cfg.d_model, cfg.head_dim, cfg.d_ff
    keys = jax.random.split(key, 12)
    norm = partial(_normal, dtype=pd)

    layers = {name: jnp.ones((n, d), pd)
              for name, part in (("ln1", mixer), ("ln2", ffn))
              if part is not None}
    kind = cfg.softmax_kind(mixer)
    if mixer == "linear_attention":
        layers.update(init_kda_params(keys[1], d, cfg.linear_attention, n,
                                      pd))
    elif mixer == "state_space":
        layers.update(init_ssm_params(keys[1], d, cfg.state_space, n, pd))
    elif isinstance(mixer, LatentAttention):
        h, rank = mixer.n_heads, mixer.kv_rank
        layers.update({
            "wq": norm(keys[1], (n, d, h * mixer.qk_dim), d),
            "wkv_a": norm(keys[2], (n, d, rank + mixer.rope_dim), d),
            "kv_norm": jnp.ones((n, rank), pd),
            "wkv_b": norm(keys[3], (n, rank, h * (mixer.nope + mixer.v_dim)),
                          rank),
            "wo": norm(keys[4], (n, h * mixer.v_dim, d), h * mixer.v_dim),
        })
    elif kind is not None:
        qh, kvh = kind.n_heads, kind.n_kv_heads
        layers.update({
            "wq": norm(keys[1], (n, d, qh * hd), d),
            "wk": norm(keys[2], (n, d, kvh * hd), d),
            "wv": norm(keys[3], (n, d, kvh * hd), d),
            "wo": norm(keys[4], (n, qh * hd, d), qh * hd),
        })
        if kind.gate:
            layers["wg"] = norm(jax.random.fold_in(key, 12),
                                (n, d, qh * hd), d)
        if kind.qk_norm:
            layers["q_norm"] = jnp.ones((n, qh * hd), pd)
            layers["k_norm"] = jnp.ones((n, kvh * hd), pd)
    if ffn == "dense":
        layers.update({
            "w1": norm(keys[5], (n, d, f), d),
            "w3": norm(keys[6], (n, d, f), d),
            "w2": norm(keys[7], (n, f, d), f),
        })
    elif ffn == "moe":
        e = cfg.n_experts
        layers.update({
            "router": norm(keys[8], (n, d, e), d),
            "we1": norm(keys[9], (n, e, d, f), d),
            "we3": norm(keys[10], (n, e, d, f), d),
            "we2": norm(keys[11], (n, e, f, d), f),
        })
    elif ffn == "expert_share":
        layers.update(init_expert_share_params(keys[8], cfg.experts, n, pd))
    return layers


def init_params(key, cfg: TransformerConfig):
    """Layer-stacked parameter pytree (host-side, full/unsharded)."""
    pd = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    params = {
        "embed": _normal(jax.random.split(key, 12)[0], (cfg.vocab_size, d),
                         d, pd),
        "ln_f": jnp.ones((d,), pd),
    }
    if not cfg.tie_embeddings:
        params["head"] = _normal(jax.random.fold_in(key, 12),
                                 (d, cfg.vocab_size), d, pd)
    n = (cfg.n_layers - len(cfg.leading_layers)) // len(cfg.layer_pattern)
    params["layers"] = tuple(
        _init_layers(jax.random.fold_in(key, at), cfg, mixer, ffn, n)
        for at, (mixer, ffn) in enumerate(cfg.layer_pattern))
    if cfg.leading_layers:
        # Stacks of one layer: the same leaves, specs and block functions.
        lead = jax.random.fold_in(key, 13)
        params["leading"] = tuple(
            _init_layers(jax.random.fold_in(lead, at), cfg, mixer, ffn, 1)
            for at, (mixer, ffn) in enumerate(cfg.leading_layers))
    return params


def _layer_specs(cfg: TransformerConfig, mixer, ffn):
    from jax.sharding import PartitionSpec as P
    tp, sp = cfg.tp_axis, cfg.sp_axis
    specs = {name: P(None, None)
             for name, part in (("ln1", mixer), ("ln2", ffn))
             if part is not None}
    kind = cfg.softmax_kind(mixer)
    if mixer == "linear_attention":
        specs.update(kda_param_specs(cfg.linear_attention, tp))
    elif mixer == "state_space":
        specs.update(ssm_param_specs())
    elif isinstance(mixer, LatentAttention):
        # Heads over tp: wq and wkv_b by columns, wo by rows; the latent's
        # projection and its norm are every shard's, whole.
        specs.update({
            "wq": P(None, None, tp),
            "wkv_a": P(None, None, None),
            "kv_norm": P(None, None),
            "wkv_b": P(None, None, tp),
            "wo": P(None, tp, None),
        })
    elif kind is not None:
        specs.update({
            "wq": P(None, None, tp),
            "wk": P(None, None, tp),
            "wv": P(None, None, tp),
            "wo": P(None, tp, None),
        })
        if kind.gate:
            specs["wg"] = P(None, None, tp)
        if kind.qk_norm:
            specs["q_norm"] = specs["k_norm"] = P(None, tp)
    if ffn == "dense":
        specs.update({
            "w1": P(None, None, tp),
            "w3": P(None, None, tp),
            "w2": P(None, tp, None),
        })
    elif ffn == "moe":
        specs.update({
            "router": P(None, None, None),
            "we1": P(None, sp, None, None),
            "we3": P(None, sp, None, None),
            "we2": P(None, sp, None, None),
        })
    elif ffn == "expert_share":
        # The share is what this chip holds: nothing of it is split again.
        specs.update({name: P(None, None, None, None)
                      for name in cfg.experts.names("we")})
        specs["router"] = P(None, None, None)
        specs["router_bias"] = P(None, None)
        if cfg.experts.d_shared:
            specs.update({name: P(None, None, None)
                          for name in cfg.experts.names("ws")})
    return specs


def param_specs(cfg: TransformerConfig):
    """PartitionSpec pytree: Megatron TP sharding + expert sharding.

    Vocab-sharded embedding over tp; attention/FFN column-row sharded over
    tp; experts sharded over the sequence/expert axis; norms replicated.
    """
    from jax.sharding import PartitionSpec as P
    tp = cfg.tp_axis
    specs = {"embed": P(tp, None), "ln_f": P(None)}
    if not cfg.tie_embeddings:
        specs["head"] = P(None, tp)
    specs["layers"] = tuple(_layer_specs(cfg, mixer, ffn)
                            for mixer, ffn in cfg.layer_pattern)
    if cfg.leading_layers:
        specs["leading"] = tuple(_layer_specs(cfg, mixer, ffn)
                                 for mixer, ffn in cfg.leading_layers)
    return specs


# --------------------------------------------------------------------------
# Building blocks (run inside the shard_map body; shapes are per-shard)
# --------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * inv).astype(x.dtype) * scale.astype(x.dtype)


def _rope(cos, sin, x):
    """Rotate pairs (x interleaved as [..., 2*k]); a table narrower than
    the head rotates the head's first dimensions and passes the rest."""
    rotary = 2 * cos.shape[-1]
    if rotary < x.shape[-1]:
        return jnp.concatenate([_rope(cos, sin, x[..., :rotary]),
                                x[..., rotary:]], axis=-1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1)


def yarn_ramp(rope: Rope, rotary: int):
    """How far each of the ``rotary / 2`` frequencies is interpolated
    (divided by ``rope.factor``): 0 up to the dimension that turns
    ``beta_fast`` times over the original positions, 1 from the one that
    turns ``beta_slow`` times, linear between."""
    import numpy as np

    def dimension(turns):
        return rotary * math.log(rope.original_max_seq
                                 / (turns * 2 * math.pi)) \
            / (2 * math.log(rope.theta))

    low = max(math.floor(dimension(rope.beta_fast)), 0)
    high = min(math.ceil(dimension(rope.beta_slow)), rotary - 1)
    if low == high:
        high += 0.001
    return np.clip((np.arange(rotary // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)


def rope_tables(positions, head_dim: int, rope: Rope, dtype):
    rotary = int(head_dim * rope.share)
    inv_freq = 1.0 / (rope.theta ** (jnp.arange(0, rotary, 2,
                                                dtype=jnp.float32) / rotary))
    if rope.factor != 1.0:
        ramp = yarn_ramp(rope, rotary)
        inv_freq = inv_freq / rope.factor * ramp + inv_freq * (1 - ramp)
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]

    def table(fn):
        values = fn(ang)
        if rope.attention_factor != 1.0:
            values = values * rope.attention_factor
        return values[None, :, None, :].astype(dtype)

    return table(jnp.cos), table(jnp.sin)


def _sharded_embed_lookup(embed_local, tokens, tp_axis: str):
    """Vocab-sharded embedding gather: local lookup + psum over tp."""
    v_local = embed_local.shape[0]
    start = lax.axis_index(tp_axis) * v_local
    adj = tokens - start
    valid = (adj >= 0) & (adj < v_local)
    adj = jnp.clip(adj, 0, v_local - 1)
    out = jnp.take(embed_local, adj, axis=0)
    out = jnp.where(valid[..., None], out, 0)
    return lax.psum(out, tp_axis)


def vocab_parallel_cross_entropy(logits_local, targets, tp_axis: str):
    """Cross entropy with the vocab axis sharded over tp.

    logits_local: [B, S, V/tp] f32; targets: [B, S] global vocab ids.
    One pmax + two psums over tp — never materializes the full vocab.
    """
    v_local = logits_local.shape[-1]
    start = lax.axis_index(tp_axis) * v_local
    # Per-shard logsumexp FIRST, then combine across tp.  Never write
    # `logits - logits.max(-1)[..., None]` here: XLA fuses the row-max
    # broadcast back into the consumer reduction and recomputes the max
    # per element — measured 24.8 ms vs 2.3 ms for builtin logsumexp on
    # a [4, 2048, 8192] f32 block (v5e).  stop_gradient on the shift:
    # numerical-stability only, its gradient contribution cancels
    # exactly (and pmax has no AD rule).
    lse_local = jax.scipy.special.logsumexp(logits_local, axis=-1)
    m = lax.pmax(lax.stop_gradient(lse_local), tp_axis)
    lse = jnp.log(lax.psum(jnp.exp(lse_local - m), tp_axis)) + m
    adj = targets - start
    valid = (adj >= 0) & (adj < v_local)
    adj = jnp.clip(adj, 0, v_local - 1)
    tgt = jnp.take_along_axis(logits_local, adj[..., None], axis=-1)[..., 0]
    tgt = lax.psum(jnp.where(valid, tgt, 0.0), tp_axis)
    return lse - tgt  # [B, S] per-token nll


@jax.named_scope(scopes.ATTENTION)
def _softmax_attention_block(x, lp, cfg: TransformerConfig,
                             kind: SoftmaxAttention, tables, sp_size):
    """Softmax attention with its projections, as ``kind`` sets it: the
    heads are what the weights hold; a rotary table turns q and k, or none
    does (the causal mask is then all the order it sees); a gate is a
    sigmoid, from a projection of its own, on every element of the heads'
    output before ``wo``; a QK-norm is an RMSNorm over the whole q and the
    whole k projection, every head together, before the heads are split.
    A layer with a window sits under ``hvd.window_attention`` as well."""
    with jax.named_scope(scopes.WINDOW_ATTENTION) if kind.window \
            else contextlib.nullcontext():
        b, s, _ = x.shape
        hd = cfg.head_dim
        if kind.qk_norm:
            if lax.axis_size(cfg.tp_axis) > 1:
                raise ValueError(
                    "a QK-norm's mean square spans every head of the q and "
                    "k projections: the heads cannot be split over %r"
                    % cfg.tp_axis)
            q, k, v = (x @ lp[name].astype(x.dtype)
                       for name in ("wq", "wk", "wv"))
            q, k, v = (y.reshape(b, s, -1, hd) for y in (
                rms_norm(q, lp["q_norm"], cfg.norm_eps),
                rms_norm(k, lp["k_norm"], cfg.norm_eps), v))
        else:
            q, k, v = ((x @ lp[name].astype(x.dtype)).reshape(b, s, -1, hd)
                       for name in ("wq", "wk", "wv"))
        if kind.rope is not None:
            cos, sin = tables[kind.rope]
            q = _rope(cos, sin, q)
            k = _rope(cos, sin, k)
        attn = _causal_attention(q, k, v, cfg, kind.window,
                                 sp_size).reshape(b, s, -1)
        if kind.gate:
            gate = jax.nn.sigmoid(
                (x @ lp["wg"].astype(x.dtype)).astype(jnp.float32))
            attn = (attn * gate).astype(x.dtype)
        # Row-sharded wo: partial sums live on each tp shard.
        return _row_parallel_product(attn, lp["wo"].astype(x.dtype), cfg)


@jax.named_scope(scopes.ATTENTION)
def _latent_attention_block(x, lp, cfg: TransformerConfig,
                            kind: LatentAttention, tables):
    """Multi-head latent attention with its projections: ``q = x W_q``, a
    head's ``[nope | rope]``; ``[c | k_rope] = x W_kv_a``; ``[k_nope | v] =
    rms_norm(c) W_kv_b`` a head; the rotary table turns every head's
    ``q_rope`` and the one ``k_rope``, which every head's key ends in; the
    scores' scale is the query/key size's (``flash_attention`` and
    ``local_attention`` take it from q).  The flash kernels take ``k_rope``
    as it is, ``[B, S, rope_dim]`` (``k_shared``), and no key of the whole
    size is built; ``local_attention`` gets it repeated behind every head's
    ``k_nope``.  The heads are what the weights hold on this tp shard; the
    sequence is whole (``_mix`` refuses a split one)."""
    with jax.named_scope(scopes.LATENT_ATTENTION):
        b, s, _ = x.shape
        nope, rank = kind.nope, kind.kv_rank
        metrics.counter(
            "hvd_latent_attention_calls_total",
            form="kernel" if pallas_kernels.use_flash_attention()
            else "xla").inc()
        q = (x @ lp["wq"].astype(x.dtype)).reshape(b, s, -1, kind.qk_dim)
        latent = x @ lp["wkv_a"].astype(x.dtype)
        kv = (rms_norm(latent[..., :rank], lp["kv_norm"], cfg.norm_eps)
              @ lp["wkv_b"].astype(x.dtype)).reshape(
                  b, s, -1, nope + kind.v_dim)
        cos, sin = tables[kind]
        q = jnp.concatenate([q[..., :nope],
                             _rope(cos, sin, q[..., nope:])], axis=-1)
        k_rope = _rope(cos, sin, latent[:, :, None, rank:])[:, :, 0]
        if pallas_kernels.use_flash_attention():
            attn = pallas_kernels.flash_attention(
                q, kv[..., :nope], kv[..., nope:], causal=True,
                k_shared=k_rope)
        else:
            attn = local_attention(
                q, pallas_kernels.whole_key(kv[..., :nope], k_rope),
                kv[..., nope:], causal=True)
        attn = attn.reshape(b, s, -1)
        # Row-sharded wo: partial sums live on each tp shard.
        return _row_parallel_product(attn, lp["wo"].astype(x.dtype), cfg)


def _causal_attention(q, k, v, cfg: TransformerConfig, window, sp_size):
    """Causal softmax attention over ``[B, S, heads, head_dim]`` by
    whichever form the layout calls for."""
    if sp_size > 1 and window is not None:
        raise ValueError("a window of %d keys crosses the shards of a "
                         "sequence split over %r: neither the ring nor the "
                         "head exchange carries it" % (window, cfg.sp_axis))
    if sp_size > 1 and cfg.sp_mode == "ulysses":
        from ..parallel.ulysses import ulysses_attention
        attn_fn = (pallas_kernels.flash_attention
                   if pallas_kernels.use_flash_attention() else None)
        return ulysses_attention(q, k, v, axis_name=cfg.sp_axis,
                                 causal=True, attn_fn=attn_fn)
    if sp_size > 1:
        return ring_attention(q, k, v, axis_name=cfg.sp_axis, causal=True)
    if pallas_kernels.use_flash_attention():
        # Pallas fused attention on TPU (ops/pallas_kernels.py):
        # O(seq) HBM forward + Pallas backward kernels (dq, dk/dv);
        # measured ~5x over XLA autodiff at seq 8192 on one chip
        # (docs/benchmarks.md)
        return pallas_kernels.flash_attention(q, k, v, causal=True,
                                              window=window)
    return local_attention(q, k, v, causal=True, window=window)


def _row_parallel_product(x, w, cfg: TransformerConfig):
    """``psum(x @ w, tp)`` for a row-sharded weight, optionally as the
    latency-hiding matmul+reduce-scatter ring + tiled all_gather
    (``cfg.collective_matmul``): identical math and total bytes, but
    the reduce leg overlaps the MXU work instead of serializing after
    it.  Plain psum at tp=1 or when rows do not divide the axis."""
    b, s, _ = x.shape
    tp = lax.axis_size(cfg.tp_axis)
    if cfg.collective_matmul and tp > 1 and (b * s) % tp == 0:
        from ..parallel.collective_matmul import matmul_reduce_scatter
        flat = x.reshape(b * s, x.shape[-1])
        part = matmul_reduce_scatter(flat, w, cfg.tp_axis)
        full = lax.all_gather(part, cfg.tp_axis, tiled=True)
        return full.reshape(b, s, w.shape[-1])
    return lax.psum(x @ w, cfg.tp_axis)


@jax.named_scope(scopes.DENSE_FFN)
def _dense_ffn(h, lp, cfg: TransformerConfig):
    """``(silu(h w1) * (h w3)) w2``, every operand of its products a buffer
    made once: the weights' casts, ``h``, ``silu(a) * g``, ``(d_a, d_g)``
    and the incoming gradient.  Left to itself XLA fuses those passes (and
    the neighbouring norm's) into the products' operands and computes them
    again for every tile of the product."""
    metrics.counter("hvd_dense_ffn_calls_total", form="split").inc()
    w1, w3, w2 = _made_once(tuple(lp[name].astype(h.dtype)
                                  for name in ("w1", "w3", "w2")))
    # One varying type for the gate's operands: the sums over the axes
    # one of them lacks are then its cotangent's, outside the gate.
    vma = tuple(sorted(set().union(*(jax.typeof(x).vma
                                     for x in (h, w1, w3)))))
    u = _swiglu_gate(*(pvary_missing(x, vma) for x in (h, w1, w3)))
    return _cotangent_once(_row_parallel_product(u, w2, cfg))


@jax.custom_vjp
def _made_once(x):
    """``x`` as a buffer of its own; its cotangent passes as it is."""
    return lax.optimization_barrier(x)


_made_once.defvjp(lambda x: (_made_once(x), None), lambda _, dx: (dx,))


@jax.custom_vjp
def _cotangent_once(y):
    """``y``, whose cotangent reaches the products behind it as a buffer of
    its own: a post-norm's gradient is made once."""
    return y


_cotangent_once.defvjp(lambda y: (y, None),
                       lambda _, dy: (lax.optimization_barrier(dy),))


def _gate(a, g):
    """``silu(a) * g`` in float32, rounded once to the products' dtype."""
    return (jax.nn.silu(a.astype(jnp.float32))
            * g.astype(jnp.float32)).astype(a.dtype)


def _tokens_product(x, dy):
    """``x^T dy`` over every token: ``[.., m]``, ``[.., n]`` -> ``[m, n]``,
    a weight's gradient in the operands' dtype, as autodiff gives it."""
    lead = tuple(range(x.ndim - 1))
    return lax.dot_general(x, dy, ((lead, lead), ((), ())))


@jax.custom_vjp
def _swiglu_gate(h, w1, w3):
    """``u = silu(h w1) * (h w3)``, kept ``h``, ``a = h w1`` and ``g = h
    w3``.  The way back makes ``(d_a, d_g)`` in one float32 pass, the last
    reader of ``du``, ``a`` and ``g``, and hands the products ``h``, ``d_a``
    and ``d_g`` as buffers."""
    return _swiglu_gate_fwd(h, w1, w3)[0]


def _swiglu_gate_fwd(h, w1, w3):
    h = lax.optimization_barrier(h)
    a, g = h @ w1, h @ w3
    return lax.optimization_barrier(_gate(a, g)), (h, a, g, w1, w3)


def _swiglu_gate_bwd(res, du):
    h, a, g, w1, w3 = res
    f32 = jnp.float32
    a32, g32, du32 = a.astype(f32), g.astype(f32), du.astype(f32)
    s = jax.nn.sigmoid(a32)
    d_a, d_g = lax.optimization_barrier(
        ((du32 * g32 * s * (1 + a32 * (1 - s))).astype(a.dtype),
         (du32 * a32 * s).astype(g.dtype)))
    dh = d_a @ w1.T + d_g @ w3.T
    return dh, _tokens_product(h, d_a), _tokens_product(h, d_g)


_swiglu_gate.defvjp(_swiglu_gate_fwd, _swiglu_gate_bwd)


def _moe_block(h, lp, cfg: TransformerConfig, sp_size):
    b, s, d = h.shape
    flat = h.reshape(b * s, d)
    moe_params = {"router": lp["router"], "w1": lp["we1"],
                  "w3": lp["we3"], "w2": lp["we2"]}
    axis = cfg.sp_axis if sp_size > 1 else None
    y, aux = moe_ffn(moe_params, flat, cfg.moe_config(), axis_name=axis)
    return y.reshape(b, s, d), aux


def _mix(h, lp, cfg: TransformerConfig, mixer, tables, sp_size):
    kind = cfg.softmax_kind(mixer)
    if kind is not None:
        return _softmax_attention_block(h, lp, cfg, kind, tables, sp_size)
    if isinstance(mixer, LatentAttention):
        if sp_size > 1:
            raise ValueError(
                "a latent-attention layer's query/key heads are of another "
                "size than its values': neither parallel/ring_attention.py "
                "nor parallel/ulysses.py carries that, so the sequence "
                "cannot be split over %r" % cfg.sp_axis)
        return _latent_attention_block(h, lp, cfg, mixer, tables)
    if sp_size > 1:
        raise ValueError("a %s layer keeps a state along the "
                         "sequence: the sequence cannot be split over %r"
                         % (mixer.replace("_", "-"), cfg.sp_axis))
    if mixer == "state_space":
        if lax.axis_size(cfg.tp_axis) > 1:
            raise ValueError("a state-space layer's heads are not split: "
                             "%r has to be 1 wide" % cfg.tp_axis)
        return state_space_block(h, lp, cfg.state_space)
    return lax.psum(linear_attention_block(h, lp, cfg.linear_attention),
                    cfg.tp_axis)


def _feed_forward(h, lp, cfg: TransformerConfig, ffn, sp_size):
    """(output, auxiliary loss or None, tokens of each expert)."""
    if ffn == "dense":
        return _dense_ffn(h, lp, cfg), None, ()
    if ffn == "moe":
        y, aux = _moe_block(h, lp, cfg, sp_size)
        return y, aux, ()
    b, s, d = h.shape
    y, counts = expert_share_ffn(lp, h.reshape(b * s, d), cfg.experts)
    return y.reshape(b, s, d), None, (counts,)


def hidden(params, tokens, cfg: TransformerConfig):
    """Per-shard decoder up to the final norm: tokens [B_loc, S_loc] ->
    (x [B, S, d], aux, expert counts).  ``aux`` is the ``moe`` layers'
    load-balancing losses summed; the counts are one ``[1, n_experts]``
    array for each leading ``expert_share`` layer, then one ``[periods,
    n_experts]`` array for each ``expert_share`` layer of the period."""
    sp_size = lax.axis_size(cfg.sp_axis)
    s_loc = tokens.shape[1]
    pos = lax.axis_index(cfg.sp_axis) * s_loc + jnp.arange(s_loc)
    # One table for each distinct one among the kinds, not one a layer.
    kinds = [cfg.softmax_kind(mixer) for mixer, _ in cfg.pairs]
    tables = {rope: rope_tables(pos, cfg.head_dim, rope, cfg.act_dtype)
              for rope in dict.fromkeys(kind.rope for kind in kinds
                                        if kind and kind.rope)}
    # A latent-attention kind's table is as wide as its rotary key.
    tables.update(
        (mixer, rope_tables(pos, mixer.rope_dim, mixer.rope, cfg.act_dtype))
        for mixer in dict.fromkeys(m for m, _ in cfg.pairs
                                   if isinstance(m, LatentAttention)))

    x = _sharded_embed_lookup(params["embed"], tokens, cfg.tp_axis)
    x = x.astype(cfg.act_dtype)
    if cfg.collective_matmul:
        # The RS+AG ring's all_gather output is vma-varying over tp
        # (identical values, but the tracker cannot prove it); the
        # scan carry must enter with the same varying axes.
        x = pvary_missing(x, (cfg.tp_axis,))

    def layer(mixer, ffn, carry, lp):
        x, aux = carry
        counts = ()
        if mixer is not None:
            if cfg.post_norm:
                x = x + rms_norm(_mix(x, lp, cfg, mixer, tables, sp_size),
                                 lp["ln1"], cfg.norm_eps)
            else:
                h = rms_norm(x, lp["ln1"], cfg.norm_eps)
                x = x + _mix(h, lp, cfg, mixer, tables, sp_size)
        if ffn is not None:
            if cfg.post_norm:
                y, a, counts = _feed_forward(x, lp, cfg, ffn, sp_size)
                y = rms_norm(y, lp["ln2"], cfg.norm_eps)
            else:
                h = rms_norm(x, lp["ln2"], cfg.norm_eps)
                y, a, counts = _feed_forward(h, lp, cfg, ffn, sp_size)
            x, aux = x + y, aux if a is None else aux + a
        return (x, aux), counts

    layer_fns = [partial(layer, mixer, ffn) for mixer, ffn in cfg.pairs]
    if cfg.remat:
        # "full" keeps nothing but what a block names as dearer to compute
        # again than to keep (the delta rule's walk along the sequence, the
        # state-space scan's chunk states, an expert layer's choice and
        # sort).
        pol = {"full": jax.checkpoint_policies.save_only_these_names(
                   *kda_saved_names, *ssm_saved_names, *moe_saved_names),
               "dots": jax.checkpoint_policies.dots_saveable,
               "dots_no_batch":
                   jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
               }[cfg.remat_policy]
        layer_fns = [jax.checkpoint(fn, policy=pol) for fn in layer_fns]

    def run(fns, carry, lps):
        counts = ()
        for fn, lp in zip(fns, lps):
            carry, c = fn(carry, lp)
            counts += c
        return carry, counts

    # The MoE aux accumulator acquires V:(dp, sp) from the routed
    # tokens; the carry must enter with the same varying axes under
    # vma tracking (guarded no-op in untracked traces).
    aux0 = jnp.zeros((), jnp.float32)
    if any(ffn == "moe" for _, ffn in cfg.pairs):
        aux0 = pvary_missing(aux0, (cfg.dp_axis, cfg.sp_axis))
    n_lead = len(cfg.leading_layers)
    carry, lead_counts = run(
        layer_fns[:n_lead], (x, aux0),
        jax.tree.map(lambda w: w[0], params.get("leading", ())))
    (x, aux), counts = lax.scan(partial(run, layer_fns[n_lead:]), carry,
                                params["layers"])
    return (rms_norm(x, params["ln_f"], cfg.norm_eps), aux,
            tuple(c[None] for c in lead_counts) + counts)


def _logits(x, params, cfg: TransformerConfig):
    """[.., d] -> [.., V/tp] float32: operands in the activations' dtype,
    accumulated in float32."""
    head = (params["embed"].astype(cfg.act_dtype).T if cfg.tie_embeddings
            else params["head"].astype(cfg.act_dtype))
    return jnp.matmul(x.astype(cfg.act_dtype), head,
                      preferred_element_type=jnp.float32)


def forward(params, tokens, cfg: TransformerConfig):
    """Per-shard forward: tokens [B_loc, S_loc] -> (logits_local, aux).

    Must run inside a shard_map over a mesh containing
    (dp_axis, sp_axis, tp_axis).  logits are [B, S, V/tp] in f32.
    """
    x, aux, _ = hidden(params, tokens, cfg)
    with jax.named_scope(scopes.HEAD):
        logits = _logits(x, params, cfg)
    return logits, aux / cfg.n_layers


@jax.named_scope(scopes.HEAD)
def _nll_sum(x, targets, params, cfg: TransformerConfig):
    """Sum of the tokens' nll, ``cfg.head_block`` tokens at a time: a
    block's logits live from its product to its cross entropy, in the
    forward pass and again in the backward pass.  0 is one block of all
    the tokens, with no loop and nothing computed again."""
    d = x.shape[-1]
    x, targets = x.reshape(-1, d), targets.reshape(-1)

    def block(xs):
        x_b, t_b = xs
        return vocab_parallel_cross_entropy(
            _logits(x_b, params, cfg), t_b, cfg.tp_axis).sum()

    if not cfg.head_block:
        return block((x, targets))
    if x.shape[0] % cfg.head_block:
        raise ValueError("%d tokens are no whole number of head blocks of "
                         "%d" % (x.shape[0], cfg.head_block))
    return lax.map(jax.checkpoint(block),
                   (x.reshape(-1, cfg.head_block, d),
                    targets.reshape(-1, cfg.head_block))).sum()


def loss_fn(params, batch, cfg: TransformerConfig):
    """Per-shard mean nll (+ MoE aux); psum-averaged over dp and sp."""
    tokens, targets = batch["tokens"], batch["targets"]
    x, aux, _ = hidden(params, tokens, cfg)
    aux = aux / cfg.n_layers
    nll_mean = _nll_sum(x, targets, params, cfg) / targets.size
    loss = nll_mean + cfg.aux_loss_weight * aux
    return lax.pmean(loss, (cfg.dp_axis, cfg.sp_axis))


# --------------------------------------------------------------------------
# Train step over the mesh
# --------------------------------------------------------------------------

def opt_spec_tree(opt_state, params_host, specs):
    """Sharding specs for optimizer state: any subtree isomorphic to
    the params tree (adam mu/nu, etc.) inherits the param ``specs``;
    everything else (step counters...) is replicated.  Shared by every
    model family's step builder."""
    from jax.sharding import PartitionSpec as P
    pdef = jax.tree.structure(params_host)

    def rec(node):
        try:
            if jax.tree.structure(node) == pdef:
                return specs
        except Exception:  # noqa: BLE001 - non-pytree leaves
            pass
        if isinstance(node, tuple) and hasattr(node, "_fields"):
            return type(node)(*[rec(c) for c in node])
        if isinstance(node, tuple):
            return tuple(rec(c) for c in node)
        if isinstance(node, list):
            return [rec(c) for c in node]
        if isinstance(node, dict):
            return {k: rec(v) for k, v in node.items()}
        return P()

    return rec(opt_state)


def make_train_step(cfg: TransformerConfig, mesh, optimizer,
                    donate: bool = True):
    """Jitted SPMD train step over ``mesh`` (axes dp/sp/tp as configured).

    Returns ``(build, shard_batch)``; ``build(params_host)`` returns
    ``(step, params, opt_state)`` with
    ``step(params, opt_state, batch) -> (params, opt_state, loss)``.
    Gradients are psum'ed over (dp, sp) — tp/ep-sharded leaves stay
    sharded, the framework's DP story fused into the compiled program;
    the optimizer's update is part of the same program, so XLA overlaps
    it with the tail of the backward pass.
    """
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    specs = param_specs(cfg)
    batch_spec = {"tokens": P(cfg.dp_axis, cfg.sp_axis),
                  "targets": P(cfg.dp_axis, cfg.sp_axis)}

    def local_grad(params, batch):
        # vma-tracked AD (check_vma=True below) differentiates the
        # dp/sp pmean in loss_fn with the exact collective transposes,
        # so the per-shard grads ARE the global-batch gradient — no
        # manual combine.  (The previous check_vma=False form psum'ed
        # grads over (dp, sp) on top of already-combined cotangents,
        # scaling the update by dp*sp: r4 correctness fix, verified by
        # the sharded-vs-single-device gradient test.)
        return jax.value_and_grad(jax.named_scope(scopes.MODEL)(
            lambda p: loss_fn(p, batch, cfg)))(params)

    def local_update(params, opt_state, grads):
        with jax.named_scope(scopes.OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

    def local_step(params, opt_state, batch):
        loss, grads = local_grad(params, batch)
        params, opt_state = local_update(params, opt_state, grads)
        return params, opt_state, loss

    def build(params_host):
        with metrics.span(scopes.BUILD_STATE) as placed:
            params = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                params_host, specs)
            with metrics.span(scopes.OPTIMIZER_INIT):
                opt_state = optimizer.init(params_host)
            o_specs = opt_spec_tree(opt_state, params_host, specs)
            opt_state = jax.tree.map(
                lambda x, s: jax.device_put(jnp.asarray(x),
                                            NamedSharding(mesh, s))
                if hasattr(x, "shape") else x,
                opt_state, o_specs)
            placed.attributes["leaves"] = len(
                jax.tree.leaves((params, opt_state)))
        mapped = jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(specs, o_specs, batch_spec),
            out_specs=(specs, o_specs, P()),
            check_vma=True)
        step = jax.jit(mapped, donate_argnums=(0, 1) if donate else ())
        return step, params, opt_state

    @metrics.span(scopes.SHARD_BATCH)
    def shard_batch(batch):
        return jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x),
                                        NamedSharding(mesh, s)),
            batch, batch_spec)

    return build, shard_batch
