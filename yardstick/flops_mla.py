"""Operations and bytes of a decoder whose mixers are multi-head latent
attention (keys and values out of one low-rank latent a position, query/key
heads of another size than the values', one rotary key shared by all heads)
ahead of dense and sparse feed-forwards, computed from shapes, whatever
implements them.  Same conventions as ``flops.py``: a multiply-add is 2
operations, a training step is the forward pass times
``flops.TRAIN_FLOP_MULT``, recomputation is not counted.
"""

from yardstick.flops import TRAIN_FLOP_MULT
from yardstick.flops_hybrid import expected_pairs


def latent_flash_cost(batch, heads, seq, d_qk, d_v, causal, shared_rope,
                      rope_dim=0, itemsize=2):
    """(operations, bytes) of one flash-attention call whose queries and
    keys are ``d_qk`` wide and whose values ``d_v``, forward and backward
    apart, at the model's own sizes (lanes a kernel pads to are not useful
    work).  Forward: the scores (``d_qk``) and the weighted sum (``d_v``) of
    every (query, key) pair a head.  Backward: the scores again, dQ and dK
    at ``d_qk``, dV and dP at ``d_v``: ``3 d_qk + 2 d_v`` a pair.  A causal
    mask halves the pairs.  Bytes: the forward reads q, k, v and writes o;
    the backward reads q, k, v, o, do and writes dq, dk, dv; q and k (and
    their gradients) are ``d_qk`` wide a head, v, o (and theirs) ``d_v``.
    ``shared_rope``: the last ``rope_dim`` of a key are one rotary key a
    position that the call reads (and whose gradient it writes) once a
    batch entry and not once a head."""
    pairs = float(batch) * heads * seq * seq
    if causal:
        pairs /= 2
    rows = batch * heads * seq * itemsize
    key = d_qk * rows
    if shared_rope:
        key = (d_qk - rope_dim) * rows + rope_dim * batch * seq * itemsize
    return {"fwd": {"flops": 2 * pairs * (d_qk + d_v),
                    "bytes": d_qk * rows + key + 2 * d_v * rows},
            "bwd": {"flops": 2 * pairs * (3 * d_qk + 2 * d_v),
                    "bytes": 2 * d_qk * rows + 2 * key + 4 * d_v * rows}}


def layer_macs(seq, hidden, heads, kv_rank, nope, rope_dim, v_dim,
               feed_forward, dense_width, experts, held, top_k, expert_width,
               shared_width):
    """Forward multiply-adds ONE token needs in one layer, by part.  The
    latent block's projections: ``W_q`` to every head's ``nope + rope_dim``,
    ``W_kv_a`` to the latent and the one rotary key, ``W_kv_b`` from the
    latent to every head's ``nope + v_dim``, ``W_o`` from the heads'
    values.  A causal query meets ``(seq + 1) / 2`` keys on average, at
    ``nope + rope_dim`` for the scores and ``v_dim`` for the weighted
    sum."""
    parts = {"projections": hidden * heads * (nope + rope_dim)
             + hidden * (kv_rank + rope_dim)
             + kv_rank * heads * (nope + v_dim) + heads * v_dim * hidden,
             "latent_softmax": (seq + 1) / 2.0 * heads
             * (nope + rope_dim + v_dim)}
    if feed_forward == "dense":
        parts["dense"] = 3 * hidden * dense_width
    else:
        parts.update(router=hidden * experts,
                     shared_expert=3 * hidden * shared_width,
                     routed_experts=expected_pairs(1, top_k, held, experts)
                     * 3 * hidden * expert_width)
    return parts


def forward_macs_per_token(seq, hidden, vocab, feed_forwards, **shape):
    """``{part: multiply-adds}`` one token needs in the forward pass of the
    layers (``feed_forwards``: ``dense`` or ``sparse`` each, every one under
    a latent-attention mixer) and the output head."""
    parts = {"head": float(hidden * vocab)}
    for feed_forward in feed_forwards:
        for part, macs in layer_macs(seq, hidden, feed_forward=feed_forward,
                                     **shape).items():
            parts[part] = parts.get(part, 0.0) + macs
    return parts


def train_flops_per_sequence(seq, **shape):
    """Operations one sequence of ``seq`` tokens needs in a training
    step."""
    return 2.0 * TRAIN_FLOP_MULT * seq * sum(
        forward_macs_per_token(seq, **shape).values())
