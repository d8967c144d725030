"""Operations and bytes of a decoder whose mixers are learned sparse
attention (a lightning indexer scores every causal key of a query, the query
attends over its ``top_k`` keys of the largest score alone) ahead of sparse
feed-forwards, computed from shapes, whatever implements them.  Same
conventions as ``flops.py``: a multiply-add is 2 operations, a training step
is the forward pass times ``flops.TRAIN_FLOP_MULT``, recomputation is not
counted.  Attention counts the pairs a query keeps, not the causal ones.
"""

from yardstick.flops import TRAIN_FLOP_MULT
from yardstick.flops_hybrid import expected_pairs


def scored_pairs(seq):
    """(query, key) pairs an indexer scores in a sequence: every causal
    one."""
    return seq * (seq + 1) // 2


def kept_pairs(seq, top_k):
    """(query, key) pairs the attention reads in a sequence: query ``t``
    keeps ``min(top_k, t + 1)`` keys."""
    k = min(top_k, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def sparse_flash_cost(batch, heads, kv_heads, seq, head, top_k,
                      itemsize=2):
    """(operations, bytes) of one layer's attention over the kept keys,
    forward and backward apart.  Forward: the scores and the weighted sum,
    ``4 head`` operations a kept pair a query head; backward: the scores
    again and the four gradient products, ``10 head`` (the flash kernels'
    count, ``flops.flash_attention_cost``).  The least bytes: q and o at the
    query heads, k and v at the key/value heads, the choice as one bit a
    causal pair and ``p`` (a float32 a kept pair) once; the backward reads
    do as well and writes dq, dk and dv."""
    pairs = float(batch) * heads * kept_pairs(seq, top_k)
    q = batch * seq * heads * head * itemsize
    kv = batch * seq * kv_heads * head * itemsize
    choice = batch * scored_pairs(seq) / 8.0 \
        + 4.0 * batch * kept_pairs(seq, top_k)
    return {"fwd": {"flops": 4 * pairs * head,
                    "bytes": 2 * q + 2 * kv + choice},
            "bwd": {"flops": 10 * pairs * head,
                    "bytes": 4 * q + 4 * kv + choice}}


def layer_macs(seq, hidden, heads, kv_heads, head, index_heads, index_dim,
               top_k, experts, held, expert_top_k, expert_width):
    """Forward multiply-adds ONE token needs in one layer, by part.  The
    projections: ``W_q``, ``W_k``, ``W_v``, ``W_o`` and the indexer's
    ``W_iq``, ``W_ik``, ``W_iw``; the indexer's products over a query's
    ``(seq + 1) / 2`` causal keys on average; the attention's scores and
    weighted sum over the keys it keeps, on average ``kept_pairs / seq``;
    the router and the held experts' share of the token's choices."""
    return {
        "projections": 2 * hidden * heads * head
        + 2 * hidden * kv_heads * head
        + hidden * (index_heads * index_dim + index_dim + index_heads),
        "indexer": (seq + 1) / 2.0 * index_heads * index_dim,
        "sparse_softmax": kept_pairs(seq, top_k) / float(seq)
        * 2 * heads * head,
        "router": hidden * experts,
        "routed_experts": expected_pairs(1, expert_top_k, held, experts)
        * 3 * hidden * expert_width}


def forward_macs_per_token(seq, hidden, vocab, layers, **shape):
    """``{part: multiply-adds}`` one token needs in the forward pass of
    ``layers`` layers and the output head."""
    parts = {"head": float(hidden * vocab)}
    for part, macs in layer_macs(seq, hidden, **shape).items():
        parts[part] = layers * macs
    return parts


def train_flops_per_sequence(seq, **shape):
    """Operations one sequence of ``seq`` tokens needs in a training
    step."""
    return 2.0 * TRAIN_FLOP_MULT * seq * sum(
        forward_macs_per_token(seq, **shape).values())
