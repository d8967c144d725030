"""Expert parallelism: Mixture-of-Experts with all-to-all dispatch.

Beyond-reference extension (SURVEY.md §2.5: the reference ships the
``alltoall`` collective but no MoE strategy; this module is the strategy).
Switch/GShard-style top-k routing with capacity: tokens are dispatched to
experts sharded over the 'ep' mesh axis via XLA ``all-to-all`` — the exact
use case the reference's AlltoallOp existed to serve, here fused into the
compiled step.

All functions run inside a shard_map body.  Shapes per shard:
tokens ``x: [T, d]``; experts_per_shard local experts; global expert count
E = ep_size * experts_per_shard.

``expert_share_ffn`` (second half of this file) is the layer a chip runs
when it holds some of a layer's experts: told which ids it holds, it
routes over all of them, drops nothing and computes its own experts' part
by dense products over blocks of the (token, expert) pairs sorted by
expert, beside a shared expert.  It has no exchange yet: the other
experts' part is left out, as on one chip of an expert-parallel group
whose tokens all stay at home.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..common import scopes
from ..ops import moe_kernels
from .ring_attention import pvary_missing


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    n_experts: int
    d_model: int
    d_ff: int
    top_k: int = 2
    capacity_factor: float = 1.25


def init_moe_params(key, cfg: MoeConfig, experts_per_shard: int,
                    dtype=jnp.float32):
    """Per-shard expert weights (swiglu FFN per expert) + replicated router.

    In the ep-sharded world each shard holds ``experts_per_shard`` experts;
    stacking over shards yields the full expert set.
    """
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d, f = cfg.d_model, cfg.d_ff
    s = 1.0 / math.sqrt(d)
    return {
        "router": (jax.random.normal(k1, (d, cfg.n_experts)) * s
                   ).astype(dtype),
        "w1": (jax.random.normal(k2, (experts_per_shard, d, f)) * s
               ).astype(dtype),
        "w3": (jax.random.normal(k3, (experts_per_shard, d, f)) * s
               ).astype(dtype),
        "w2": (jax.random.normal(k4, (experts_per_shard, f, d)) *
               (1.0 / math.sqrt(f))).astype(dtype),
    }


def _dispatch_tensors(gates, top_k: int, n_experts: int, capacity: int):
    """Build dispatch/combine tensors (GShard-style cumsum position slots).

    gates: [T, E] softmax router probabilities.
    Returns dispatch [T, E, C] (bool) and combine [T, E, C] (weights).
    """
    t = gates.shape[0]
    topk_w, topk_e = lax.top_k(gates, top_k)
    # Renormalize selected weights.
    topk_w = topk_w / jnp.maximum(topk_w.sum(-1, keepdims=True), 1e-9)
    dispatch = jnp.zeros((t, n_experts, capacity), bool)
    combine = jnp.zeros((t, n_experts, capacity), gates.dtype)
    # Fill expert slots choice-by-choice so earlier choices get priority,
    # mirroring the reference MoE implementations' greedy capacity rule.
    used = jnp.zeros((n_experts,), jnp.int32)
    for j in range(top_k):
        e = topk_e[:, j]
        onehot = jax.nn.one_hot(e, n_experts, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - 1) + used[None, :]
        pos_t = (pos * onehot).sum(-1)
        keep = pos_t < capacity
        slot = jax.nn.one_hot(pos_t, capacity, dtype=jnp.bool_)
        d_j = (onehot.astype(bool)[:, :, None] & slot[:, None, :]
               & keep[:, None, None])
        dispatch = dispatch | d_j
        combine = combine + d_j.astype(combine.dtype) * \
            topk_w[:, j][:, None, None]
        used = used + onehot.sum(0)
    return dispatch, combine


def moe_ffn(params, x, cfg: MoeConfig, axis_name: Optional[str] = "ep"):
    """Top-k routed swiglu FFN with expert parallelism.

    ``x: [T, d]`` per shard.  When ``axis_name`` is None (or ep=1) the
    all-to-alls drop out and this is a dense-local MoE.
    """
    n_shards = lax.axis_size(axis_name) if axis_name else 1
    t, d = x.shape
    e_total = cfg.n_experts
    e_local = params["w1"].shape[0]
    assert e_local * n_shards == e_total, (e_local, n_shards, e_total)
    capacity = max(1, int(math.ceil(
        t * cfg.top_k * cfg.capacity_factor / e_total)))

    logits = x @ params["router"].astype(x.dtype)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    dispatch, combine = _dispatch_tensors(gates, cfg.top_k, e_total, capacity)

    # [T, E, C] x [T, d] -> [E, C, d]
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)

    if n_shards > 1:
        # [E, C, d] -> [ep, E_local, C, d]; shard i keeps its experts,
        # receiving one [E_local, C, d] slab from every source shard.
        expert_in = expert_in.reshape(n_shards, e_local, capacity, d)
        expert_in = lax.all_to_all(expert_in, axis_name, split_axis=0,
                                   concat_axis=0, tiled=True)
        expert_in = expert_in.reshape(n_shards, e_local, capacity, d)
        # -> [E_local, ep*C, d]: fold source shards into the slot axis.
        expert_in = expert_in.transpose(1, 0, 2, 3).reshape(
            e_local, n_shards * capacity, d)
    else:
        expert_in = expert_in.reshape(e_local, capacity, d)

    # Per-expert swiglu, batched over local experts on the MXU.
    h = jnp.einsum("esd,edf->esf", expert_in, params["w1"].astype(x.dtype))
    g = jnp.einsum("esd,edf->esf", expert_in, params["w3"].astype(x.dtype))
    act = jax.nn.silu(h) * g
    expert_out = jnp.einsum("esf,efd->esd", act,
                            params["w2"].astype(x.dtype))

    if n_shards > 1:
        expert_out = expert_out.reshape(
            e_local, n_shards, capacity, d).transpose(1, 0, 2, 3)
        expert_out = expert_out.reshape(n_shards * e_local, capacity, d)
        expert_out = lax.all_to_all(expert_out, axis_name, split_axis=0,
                                    concat_axis=0, tiled=True)
        expert_out = expert_out.reshape(e_total, capacity, d)
    else:
        expert_out = expert_out.reshape(e_total, capacity, d)

    # Weighted return to token positions: [T, E, C] x [E, C, d] -> [T, d]
    y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), expert_out)
    return y, aux_load_balance_loss(gates, dispatch)


def aux_load_balance_loss(gates, dispatch):
    """Switch-transformer load-balancing auxiliary loss."""
    e = gates.shape[1]
    frac_tokens = dispatch.any(-1).astype(jnp.float32).mean(0)
    frac_gates = gates.mean(0)
    return e * jnp.sum(frac_tokens * frac_gates)


# --------------------------------------------------------------------------
# A chip's share of an expert layer, dropless
# --------------------------------------------------------------------------

def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _relu2(x, w1, w2):
    return jnp.square(jax.nn.relu(x @ w1)) @ w2


# What an expert computes, by form: which matrices it has (the digits of
# their names, in the order its function takes them) and the function.
_FORMS = {"swiglu": ("132", _swiglu), "relu2": ("12", _relu2)}


@dataclasses.dataclass(frozen=True)
class ExpertShare:
    """Which experts of a layer live here.  The router is ``n_experts``
    wide whatever ``count`` is."""
    n_experts: int
    first: int                  # id of the first expert held here
    count: int                  # experts held here, ids first..first+count-1
    top_k: int
    d_model: int
    d_ff: int                   # width of a routed expert
    d_shared: int               # width of the shared expert (0 = none)
    routed_scaling: float = 1.0
    # Rows of one block of sorted pairs: what one pass of the expert
    # products takes.  A block never spans two experts.
    block_rows: int = 512
    # What an expert (routed or shared) computes, the model's published
    # activation: ``swiglu`` is three matrices, ``(silu(x w1) * (x w3))
    # w2``; ``relu2`` two, ``relu(x w1)^2 w2``, and the tree then has no
    # ``we3`` / ``ws3``.
    form: str = "swiglu"
    # How the router scores the experts: ``sigmoid`` (a score each, chosen
    # under the ``router_bias`` balancing buffer) or ``softmax`` (one
    # softmax over all of them and no buffer: the tree has no
    # ``router_bias``).
    scoring: str = "sigmoid"

    def __post_init__(self):
        if self.form not in _FORMS:
            raise ValueError("an expert is one of %s, not %r"
                             % (sorted(_FORMS), self.form))
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError("a router scores by sigmoid or softmax, not %r"
                             % (self.scoring,))
        if not 0 <= self.first <= self.first + self.count <= self.n_experts:
            raise ValueError("experts %d..%d are not among %d"
                             % (self.first, self.first + self.count - 1,
                                self.n_experts))
        if not 0 < self.top_k <= self.n_experts:
            # A token's choices are distinct: the row writes rest on it.
            raise ValueError("a token cannot choose %d of %d experts"
                             % (self.top_k, self.n_experts))

    def names(self, prefix: str):
        """The parameters of an expert of this form: ``we..`` the held
        routed experts', ``ws..`` the shared expert's."""
        return tuple(prefix + digit for digit in _FORMS[self.form][0])

    @property
    def expert(self):
        """``f(x, *matrices)``: what one expert computes."""
        return _FORMS[self.form][1]


def init_expert_share_params(key, share: ExpertShare, n: int,
                             dtype=jnp.float32):
    """``n`` stacked layers of the held experts, the router and the
    shared expert (each of ``share.form``: ``we1, we3, we2`` / ``ws1, ws3,
    ws2``, without the ``3`` where the form has two matrices).
    ``router_bias`` is the family's
    load-balancing buffer: added to the scores where the experts are
    chosen and nowhere else, so no gradient reaches it; it starts at zero
    and is set from outside (a checkpoint, or a calibration).  A softmax
    router has none."""
    ks = jax.random.split(key, 7)
    d, f, fs = share.d_model, share.d_ff, share.d_shared

    def norm(k, shape, fan_in):
        return (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(dtype)

    def expert(prefix, keys, lead, width):
        """The matrices of experts ``width`` wide: ``..1`` and ``..3`` up,
        ``..2`` down, each with the key it has whatever the form."""
        keys = dict(zip("132", keys))
        shapes = {"1": (d, width), "3": (d, width), "2": (width, d)}
        return {name: norm(keys[name[-1]], lead + shapes[name[-1]],
                           shapes[name[-1]][0])
                for name in share.names(prefix)}

    params = {"router": norm(ks[0], (n, d, share.n_experts), d),
              **expert("we", ks[1:4], (n, share.count), f)}
    if share.scoring == "sigmoid":
        params["router_bias"] = jnp.zeros((n, share.n_experts), dtype)
    if fs:
        params.update(expert("ws", ks[4:7], (n,), fs))
    return params


# ``checkpoint_name``s of what a recomputed layer keeps of its routing: the
# chosen ids, their sort by expert and the loads are integers (1 MiB a
# layer at 16384 tokens choosing 8), so a layer recomputed in the backward
# pass neither chooses nor sorts again.
SAVED = ("moe_ids", "moe_order", "moe_loads")


def _top_k_ids(values, k: int):
    """``lax.top_k(values, k)[1]`` as ``k`` rounds of arg-max and mask
    instead of a sort of every row: the largest first, the first index on
    a tie."""
    cols = lax.broadcasted_iota(jnp.int32, values.shape, values.ndim - 1)
    ids = []
    for _ in range(k):
        best = jnp.argmax(values, axis=-1).astype(jnp.int32)[..., None]
        ids.append(best)
        values = jnp.where(cols == best, -jnp.inf, values)
    return jnp.concatenate(ids, axis=-1)


def _take(scores, ids):
    """``scores[t, ids[t, j]]`` as a sum over the experts under a mask (one
    term is not zero, so it is exact): its gradient is the same mask, where
    a gather's is a scatter-add of ``k T`` scalars."""
    cols = lax.broadcasted_iota(jnp.int32, (1,) * ids.ndim + scores.shape[-1:],
                                ids.ndim)
    return jnp.sum(jnp.where(ids[..., None] == cols, scores[..., None, :],
                             0.0), axis=-1)


def route(x, router, bias, share: ExpertShare):
    """Sigmoid scores in float32 over every expert of the layer; the
    ``top_k`` experts with the largest score + bias; their scores (without
    the bias) renormalised to sum to ``routed_scaling``.  Returns (weights
    ``[T, k]`` float32, ids ``[T, k]``; the ids carry a ``checkpoint_name``,
    ``SAVED``).  A ``softmax`` router scores by one softmax over the logits
    of every expert and chooses by the scores alone (``bias`` is None).

    The product is float32 at ``Precision.HIGHEST`` whatever ``x`` is.  Of
    its six bfloat16 passes the TPU's compiler drops the three that
    multiply the middle and low terms of an ``x`` that arrives in bfloat16,
    which are zero (0.69 ms for ``[16384, 4096] x [4096, 320]``, the MXU's
    peak for three passes): a three-pass form written out by hand took as
    long and 60 MiB more (``PERF.md``, PR 30)."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if share.scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        ids = checkpoint_name(_top_k_ids(lax.stop_gradient(scores),
                                         share.top_k), SAVED[0])
    else:
        scores = jax.nn.sigmoid(logits)
        ids = checkpoint_name(_top_k_ids(
            lax.stop_gradient(scores) + bias.astype(jnp.float32),
            share.top_k), SAVED[0])
    top = _take(scores, ids)
    return top / top.sum(-1, keepdims=True) * share.routed_scaling, ids


def _sorted_pairs(ids, share: ExpertShare):
    """The (token, choice) pairs that chose a held expert, sorted by
    expert: ``order`` (indices into the flattened ``[T * k]`` pairs, held
    ones first), and how many pairs every expert of the layer got.  The
    sort is stable, so one expert's pairs keep their order: ascending."""
    flat = ids.reshape(-1)
    local = flat - share.first
    local = jnp.where((local >= 0) & (local < share.count), local,
                      share.count)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    loads = jnp.sum(flat[:, None] == jnp.arange(share.n_experts)[None, :],
                    axis=0, dtype=jnp.int32)
    return checkpoint_name(order, SAVED[1]), checkpoint_name(loads, SAVED[2])


def _zeros(shape, dtype, like):
    """Zeros that vary over the mesh axes ``like`` varies over: what a
    loop's carry has to start as under ``check_vma``."""
    return pvary_missing(jnp.zeros(shape, dtype), tuple(jax.typeof(like).vma))


def _plan(sizes, rows: int):
    """How the sorted pairs fall into blocks of ``rows`` rows of one expert
    each: an expert with ``n`` pairs takes ``ceil(n / rows)`` blocks, so a
    block never spans two experts and every product is dense.  Returns the
    running count of pairs and of blocks after each expert."""
    return jnp.cumsum(sizes), jnp.cumsum((sizes + rows - 1) // rows)


def _block(i, order, sizes, plan, rows: int, top_k: int):
    """Block ``i`` of the sorted pairs.  Returns its expert, its rows'
    places among the flattened ``[T * k]`` pairs and the tokens they are
    read from (in range for every row), which rows are real (an expert's
    last block is part empty), and the token and the pair each row is
    written to.

    The real rows' tokens are distinct and strictly ascending: the sort by
    expert is stable, so an expert's pairs come in the order of the
    flattened pairs, and a token chooses an expert at most once.  A padded
    row is written nowhere: its token ``T + row`` and its pair ``T k + row``
    are out of range, so the block's indices stay distinct and ascending,
    and what writes by them (``moe_kernels.combine``, the weights'
    gradient's scatter) drops it."""
    ends, block_ends = plan
    expert = jnp.sum(i >= block_ends)
    nth = i - jnp.where(expert > 0, block_ends[expert - 1], 0)
    row = jnp.arange(rows, dtype=jnp.int32)
    start = ends[expert] - sizes[expert] + nth * rows
    real = start + row < ends[expert]
    pairs = order[jnp.minimum(start + row, order.shape[0] - 1)]
    source = pairs // top_k
    return (expert, pairs, source, real,
            jnp.where(real, source, order.shape[0] // top_k + row),
            jnp.where(real, pairs, order.shape[0] + row))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _routed(top_k, rows, expert, x, held, weights, order, sizes):
    """``y[t] = sum over t's held choices of weight * expert(x[t])``
    (``held`` the experts' stacked matrices, in ``expert``'s order),
    block by block over the sorted pairs: a step pays for the blocks
    that hold pairs (``0.2 T`` rows where routing is even), and ``k T``
    rows are still right.  A block gathers its rows of ``x``, multiplies,
    and adds its rows into ``y`` at their tokens: no two rows of a block
    share a token (``_block``), so the adds are conflict-free writes, one
    DMA a row in any order (``ops/moe_kernels.py``)."""
    return _routed_fwd(top_k, rows, expert, x, held, weights, order,
                       sizes)[0]


def _routed_fwd(top_k, rows, expert, x, held, weights, order, sizes):
    flat_w = weights.reshape(-1)
    plan = _plan(sizes, rows)

    def block(i, y):
        with jax.named_scope(scopes.ROUTER):
            e, pairs, source, real, token, _ = _block(
                i, order, sizes, plan, rows, top_k)
            w_rows = jnp.where(real, flat_w[pairs], 0.0)
            with jax.named_scope(scopes.ROUTER_ROWS):
                x_rows = x[source]
        with jax.named_scope(scopes.EXPERTS):
            out = expert(x_rows, *(w[e].astype(x.dtype) for w in held))
        with jax.named_scope(scopes.ROUTER):
            out = (out.astype(jnp.float32) * w_rows[:, None]).astype(y.dtype)
            with jax.named_scope(scopes.ROUTER_ROWS):
                return moe_kernels.combine(y, out, token)

    # A token's sum has at most top_k terms: it is kept at x's precision.
    y = lax.fori_loop(0, plan[1][-1], block,
                      _zeros(moe_kernels.as_rows(x.shape), x.dtype, x))
    with jax.named_scope(scopes.ROUTER), jax.named_scope(scopes.ROUTER_ROWS):
        y = moe_kernels.from_rows(y, x.shape)
    return y, (x, held, weights, order, sizes)


def _routed_bwd(top_k, rows, expert, res, dy):
    """Each block's products are computed anew and differentiated on the
    spot, so nothing of a block outlives it."""
    x, held, weights, order, sizes = res
    flat_w = weights.reshape(-1)
    plan = _plan(sizes, rows)

    def expert_of(x_rows, *ws):
        return expert(x_rows, *(w.astype(x.dtype) for w in ws))

    def block(i, carry):
        dx, d_held, dw = carry
        with jax.named_scope(scopes.ROUTER):
            e, pairs, source, real, token, pair = _block(
                i, order, sizes, plan, rows, top_k)
            w_rows = jnp.where(real, flat_w[pairs], 0.0)
            with jax.named_scope(scopes.ROUTER_ROWS):
                x_rows = x[source]
                dy_rows = dy[source].astype(jnp.float32)
        with jax.named_scope(scopes.EXPERTS):
            out, vjp = jax.vjp(expert_of, x_rows, *(w[e] for w in held))
            dx_rows, *grads = vjp(
                (dy_rows * w_rows[:, None]).astype(out.dtype))
        with jax.named_scope(scopes.ROUTER):
            dw_rows = jnp.where(
                real, jnp.sum(out.astype(jnp.float32) * dy_rows, -1), 0.0)
            with jax.named_scope(scopes.ROUTER_ROWS):
                dx = moe_kernels.combine(dx, dx_rows.astype(dx.dtype), token)
            # What the compiler cannot see of these indices and ``_block``
            # says.  (``indices_are_sorted`` is as true of ``pair`` and is
            # left unsaid: XLA's TPU scatter takes 13 times as long when
            # told, ``PERF.md`` PR 30.)
            return (dx, tuple(d.at[e].add(g, unique_indices=True)
                              for d, g in zip(d_held, grads)),
                    dw.at[pair].add(dw_rows, mode="drop",
                                    unique_indices=True))

    init = (_zeros(moe_kernels.as_rows(x.shape), x.dtype, x),
            tuple(_zeros(w.shape, w.dtype, x) for w in held),
            _zeros(flat_w.shape, jnp.float32, x))
    dx, d_held, dw = lax.fori_loop(0, plan[1][-1], block, init)
    with jax.named_scope(scopes.ROUTER), jax.named_scope(scopes.ROUTER_ROWS):
        dx = moe_kernels.from_rows(dx, x.shape)
    return (dx, d_held,
            dw.reshape(weights.shape).astype(weights.dtype), None, None)


_routed.defvjp(_routed_fwd, _routed_bwd)


@jax.named_scope(scopes.MOE)
def expert_share_ffn(params, x, share: ExpertShare):
    """The held experts' and the shared expert's part of a
    mixture-of-experts layer over ``x`` ``[T, d]``:

        y = sum_{e held, e among t's top_k} w_e * Expert_e(x) + Shared(x)

    with the experts of ``share.form``.
    Nothing is dropped for any routing, and no tensor has an expert or a
    capacity axis.  Returns ``y`` and how many tokens every expert of the
    layer got (``[n_experts]``, a device value; the held experts' are
    ``[first:first + count]``)."""
    with jax.named_scope(scopes.ROUTER):
        weights, ids = route(x, params["router"], params.get("router_bias"),
                             share)
        order, loads = _sorted_pairs(ids, share)
        sizes = lax.dynamic_slice_in_dim(loads, share.first, share.count)
        vma = tuple(jax.typeof(x).vma)
        held = tuple(pvary_missing(params[name], vma)
                     for name in share.names("we"))
    y = _routed(share.top_k, share.block_rows, share.expert, x, held,
                weights, order, sizes)
    if share.d_shared:
        with jax.named_scope(scopes.SHARED_EXPERT):
            y = y + share.expert(x, *(params[name].astype(x.dtype)
                                      for name in share.names("ws")))
    return y, loads
