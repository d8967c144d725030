"""Operations and bytes of a hybrid decoder (softmax layers, delta-rule
linear-attention layers, sparse experts) computed from shapes, whatever
implements them.  Same conventions as ``flops.py``: a multiply-add is 2
operations, a training step is the forward pass times
``flops.TRAIN_FLOP_MULT``, recomputation is not counted.
"""

from yardstick.flops import TRAIN_FLOP_MULT


def expected_pairs(tokens, top_k, held, experts):
    """(token, expert) pairs that fall on ``held`` of ``experts`` experts
    when every token picks ``top_k`` and routing is even."""
    return tokens * top_k * held / float(experts)


def delta_rule_macs(chunk, head):
    """Forward multiply-adds of ONE chunk of ONE head of the gated delta
    rule in its chunked form (``C`` steps, keys and values of size ``D``):
    the two decayed ``C x C`` products of k against k and of q against k
    (2 C^2 D), the unit-triangular solve against ``[v | k]`` (C^2 D), the
    chunk's transition and input ``k^T [w_k | u]`` (2 C D^2), the state
    times the transition (D^3), the pseudo-values' ``w_k S`` (C D^2) and
    the output's ``q S`` and ``P w`` (C D^2 + C^2 D)."""
    c, d = chunk, head
    return 4 * c * c * d + 4 * c * d * d + d * d * d


def delta_rule_cost(batch, seq, heads, head, chunk, itemsize=4):
    """(operations, bytes) of the delta-rule core of one layer, forward
    and backward apart.  The backward pass is two products for each of
    the forward's.  Bytes: the forward reads q, k, v and the log-decay
    (``[tokens, heads, head]`` each) and ``beta`` and writes o; the
    backward reads those five and o's gradient and writes five
    gradients.  State and decays are float32."""
    chunks = batch * (seq // chunk) * heads
    product = 2.0 * chunks * delta_rule_macs(chunk, head)
    tensor = batch * seq * heads * head * itemsize
    small = batch * seq * heads * itemsize
    return {"fwd": {"flops": product, "bytes": 5 * tensor + small},
            "bwd": {"flops": 2 * product, "bytes": 9 * tensor + 2 * small}}


def expert_products_cost(pairs, held, hidden, width, weight_itemsize=4,
                         row_itemsize=2):
    """(operations, bytes) of the routed experts' three products over
    ``pairs`` rows, forward and backward apart: 3 products of
    ``hidden x width`` a row, twice that again backward.  Bytes: the held
    experts' weights once a pass (float32 parameters; their gradients
    written once backward), each row read and written at ``hidden``."""
    product = 2.0 * 3 * pairs * hidden * width
    weights = 3 * held * hidden * width * weight_itemsize
    rows = pairs * hidden * row_itemsize
    return {"fwd": {"flops": product, "bytes": weights + 2 * rows},
            "bwd": {"flops": 2 * product, "bytes": 2 * weights + 4 * rows}}


def layer_macs(mixer, seq, hidden, q_heads, kv_heads, head, lin_heads,
               lin_head, conv, gate_rank, chunk, experts, held, top_k,
               expert_width, shared_width):
    """Forward multiply-adds ONE token needs in one layer of the share, by
    part."""
    if mixer == "linear_attention":
        w = lin_heads * lin_head
        mix = {"projections": 4 * hidden * w + 2 * (hidden * gate_rank
                                                    + gate_rank * w)
               + hidden * lin_heads + 3 * conv * w,
               "delta_rule": lin_heads * delta_rule_macs(chunk, lin_head)
               / float(chunk)}
    else:
        # q, the output gate and the output at the query heads' width, k
        # and v at the key heads'; a causal query meets (seq + 1) / 2 keys
        # twice (scores, then the weighted sum of values).
        mix = {"projections": 3 * hidden * q_heads * head
               + 2 * hidden * kv_heads * head,
               "softmax": (seq + 1) * q_heads * head}
    return dict(mix, router=hidden * experts,
                shared_expert=3 * hidden * shared_width,
                routed_experts=expected_pairs(1, top_k, held, experts)
                * 3 * hidden * expert_width)


def forward_macs_per_token(seq, hidden, vocab, pattern, periods, **shape):
    """``{part: multiply-adds}`` one token needs in the forward pass of
    ``periods`` periods of ``pattern`` and the output head."""
    parts = {"head": float(hidden * vocab)}
    for mixer, _ in pattern:
        for part, macs in layer_macs(mixer, seq, hidden, **shape).items():
            parts[part] = parts.get(part, 0.0) + periods * macs
    return parts


def train_flops_per_sequence(seq, **shape):
    """Operations one sequence of ``seq`` tokens needs in a training
    step."""
    return 2.0 * TRAIN_FLOP_MULT * seq * sum(
        forward_macs_per_token(seq, **shape).values())
