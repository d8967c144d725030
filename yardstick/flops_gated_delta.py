"""Operations and bytes of a hybrid decoder whose linear layers run the
gated delta rule with one decay a head (Gated DeltaNet) beside softmax
layers, every layer with a dense SwiGLU, computed from shapes, whatever
implements them.  Same conventions as ``flops.py``: a multiply-add is 2
operations, a training step is the forward pass times
``flops.TRAIN_FLOP_MULT``, recomputation is not counted.
"""

from yardstick.flops import TRAIN_FLOP_MULT


def gated_delta_macs(chunk, key, value):
    """Forward multiply-adds of ONE chunk of ONE head of the gated delta
    rule with a scalar decay in its chunked form (``C`` steps, keys of
    ``Dk``, values of ``Dv``), at the model's own sizes (no padded lanes):
    the two decayed ``C x C`` products of k against k and of q against k
    (2 C^2 Dk: a scalar decay a step scales them after the product), the
    unit-triangular solve against ``[v | k]`` (C^2 (Dv + Dk) / 2), the
    pseudo-values' ``w_k S`` (C Dk Dv), the output's ``q S`` and ``P w``
    (C Dk Dv + C^2 Dv) and the state's update ``k^T w`` (C Dk Dv; the
    state's decay is one scalar a chunk, no product)."""
    c, dk, dv = chunk, key, value
    return 2 * c * c * dk + c * c * (dv + dk) / 2.0 + c * c * dv \
        + 3 * c * dk * dv


def gated_delta_cost(batch, seq, heads, key, value, chunk, itemsize=4):
    """(operations, bytes) of the delta-rule core of one layer, forward
    and backward apart.  The backward pass is two products for each of
    the forward's.  Bytes: the forward reads q, k (``Dk`` a head), v
    (``Dv``), the decay and ``beta`` (one a head) and writes o (``Dv``);
    the backward reads those five and o's gradient and writes five
    gradients.  Float32 throughout."""
    chunks = batch * (seq // chunk) * heads
    product = 2.0 * chunks * gated_delta_macs(chunk, key, value)
    rows = batch * seq * heads * itemsize
    keys, values = key * rows, value * rows
    return {"fwd": {"flops": product,
                    "bytes": 2 * keys + 2 * values + 2 * rows},
            "bwd": {"flops": 2 * product,
                    "bytes": 4 * keys + 3 * values + 4 * rows}}


def layer_macs(mixer, seq, hidden, heads, head, lin_heads, key, value, conv,
               chunk, dense_width):
    """Forward multiply-adds ONE token needs in one layer, by part."""
    if mixer == "linear_attention":
        # W_q, W_k to the keys; W_v, the gate's W_g to the values; W_a and
        # W_b a scalar a head; W_o back from the values; the convolution's
        # taps over q, k and v.
        parts = {"projections": hidden * lin_heads * (2 * key + 3 * value + 2)
                 + conv * lin_heads * (2 * key + value),
                 "delta_rule": lin_heads * gated_delta_macs(chunk, key, value)
                 / float(chunk)}
    else:
        # q, k, v and the output at the heads' width; a causal query meets
        # (seq + 1) / 2 keys twice (scores, then the weighted sum).
        parts = {"projections": 4 * hidden * heads * head,
                 "softmax": (seq + 1) * heads * head}
    parts["dense"] = 3 * hidden * dense_width
    return parts


def forward_macs_per_token(seq, hidden, vocab, mixers, **shape):
    """``{part: multiply-adds}`` one token needs in the forward pass of the
    layers (``mixers``: one a layer) and the output head."""
    parts = {"head": float(hidden * vocab)}
    for mixer in mixers:
        for part, macs in layer_macs(mixer, seq, hidden, **shape).items():
            parts[part] = parts.get(part, 0.0) + macs
    return parts


def train_flops_per_sequence(seq, **shape):
    """Operations one sequence of ``seq`` tokens needs in a training
    step."""
    return 2.0 * TRAIN_FLOP_MULT * seq * sum(
        forward_macs_per_token(seq, **shape).values())
