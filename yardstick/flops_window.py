"""Operations and bytes of a decoder whose softmax layers come in two kinds,
full causal and sliding-window, each with its own head count, ahead of dense
and sparse feed-forwards, computed from shapes, whatever implements them.
Same conventions as ``flops.py``: a multiply-add is 2 operations, a training
step is the forward pass times ``flops.TRAIN_FLOP_MULT``, recomputation is
not counted.
"""

from yardstick.flops import TRAIN_FLOP_MULT
from yardstick.flops_hybrid import expected_pairs


def band_pairs(seq, window):
    """(query, key) pairs of one head over one sequence under a causal
    window: query ``i`` meets ``min(i + 1, window)`` keys, itself among
    them."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def window_flash_cost(batch, heads, seq, head_dim, window, itemsize=2):
    """(operations, bytes) of one flash-attention call under a causal
    window, forward and backward apart, as ``flops.flash_attention_cost``
    counts a full call: 2 products of ``pairs x head_dim`` a head forward
    (scores, weighted sum), 5 backward (the scores again, dV, dP, dQ, dK);
    the forward reads q, k, v and writes o, the backward reads q, k, v, o,
    do and writes dq, dk, dv.  The band, not the triangle: blocks a kernel
    computes outside it are its cost and not the algorithm's."""
    product = 2.0 * batch * heads * band_pairs(seq, window) * head_dim
    tensor = batch * heads * seq * head_dim * itemsize
    return {"fwd": {"flops": 2 * product, "bytes": 4 * tensor},
            "bwd": {"flops": 5 * product, "bytes": 8 * tensor}}


def layer_macs(seq, hidden, head, kv_heads, q_heads, window, feed_forward,
               dense_width, experts, held, top_k, expert_width,
               shared_width):
    """Forward multiply-adds ONE token needs in one layer, by part.
    ``window`` None is a full causal layer: a query meets ``(seq + 1) / 2``
    keys on average, twice (scores, then the weighted sum of values); under
    a window ``band_pairs / seq``."""
    keys = (seq + 1) / 2.0 if window is None \
        else band_pairs(seq, window) / float(seq)
    # q, the output gate and the output at the query heads' width, k and v
    # at the key heads'.
    parts = {"projections": 3 * hidden * q_heads * head
             + 2 * hidden * kv_heads * head,
             ("softmax" if window is None else "window_softmax"):
                 2 * keys * q_heads * head}
    if feed_forward == "dense":
        parts["dense"] = 3 * hidden * dense_width
    else:
        parts.update(router=hidden * experts,
                     shared_expert=3 * hidden * shared_width,
                     routed_experts=expected_pairs(1, top_k, held, experts)
                     * 3 * hidden * expert_width)
    return parts


def forward_macs_per_token(seq, hidden, vocab, layers, **shape):
    """``{part: multiply-adds}`` one token needs in the forward pass of
    ``layers`` (``(query heads, window or None, feed-forward)`` each) and
    the output head."""
    parts = {"head": float(hidden * vocab)}
    for q_heads, window, feed_forward in layers:
        for part, macs in layer_macs(seq, hidden, q_heads=q_heads,
                                     window=window,
                                     feed_forward=feed_forward,
                                     **shape).items():
            parts[part] = parts.get(part, 0.0) + macs
    return parts


def train_flops_per_sequence(seq, **shape):
    """Operations one sequence of ``seq`` tokens needs in a training
    step."""
    return 2.0 * TRAIN_FLOP_MULT * seq * sum(
        forward_macs_per_token(seq, **shape).values())
