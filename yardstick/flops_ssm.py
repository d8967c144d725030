"""Operations and bytes of a decoder whose blocks are one sub-layer each:
a Mamba-2 state-space mixer, a full causal softmax mixer, or a sparse layer
of two-matrix experts beside a shared one; computed from shapes, whatever
implements them.  Same conventions as ``flops.py``: a multiply-add is 2
operations, a training step is the forward pass times
``flops.TRAIN_FLOP_MULT``, recomputation is not counted.
"""

from yardstick.flops import TRAIN_FLOP_MULT
from yardstick.flops_hybrid import expected_pairs


def ssd_macs(chunk, heads, head, groups, state):
    """Forward multiply-adds of ONE chunk of the state-space scan in its
    chunked form over all ``heads`` (``Q`` steps, heads of ``P`` channels,
    ``groups`` of heads sharing ``B`` and ``C`` of size ``N``): ``C B^T``
    once a group (Q^2 N), the masked product with ``dt . X`` (Q^2 P a
    head), the chunk's own state ``X^T B`` (Q P N a head) and the carried
    state's part of the output ``H C`` (Q P N a head).  The carry from
    chunk to chunk is P N multiply-adds a head and chunk, a 128th of those,
    and is left out."""
    q, p, n = chunk, head, state
    return groups * q * q * n + heads * (q * q * p + 2 * q * p * n)


def ssd_cost(batch, seq, heads, head, groups, state, chunk, itemsize=2,
             dt_itemsize=4):
    """(operations, bytes) of the scan of one layer, forward and backward
    apart.  The backward pass is two products for each of the forward's.
    Bytes: the forward reads x, B, C (the activations' dtype) and dt
    (float32) and writes y; the backward reads those four and y's gradient
    and writes four gradients."""
    product = 2.0 * batch * (seq // chunk) * ssd_macs(chunk, heads, head,
                                                      groups, state)
    tokens = batch * seq
    x = tokens * heads * head * itemsize
    bc = 2 * tokens * groups * state * itemsize
    dt = tokens * heads * dt_itemsize
    return {"fwd": {"flops": product, "bytes": 2 * x + bc + dt},
            "bwd": {"flops": 2 * product, "bytes": 3 * x + 2 * (bc + dt)}}


def expert_products_cost(pairs, held, hidden, width, weight_itemsize=4,
                         row_itemsize=2):
    """(operations, bytes) of two-matrix routed experts over ``pairs``
    rows, forward and backward apart: 2 products of ``hidden x width`` a
    row, twice that again backward.  Bytes as
    ``flops_hybrid.expert_products_cost`` counts them: the held experts'
    weights once a pass (float32 parameters; their gradients written once
    backward), each row read and written at ``hidden``."""
    product = 2.0 * 2 * pairs * hidden * width
    weights = 2 * held * hidden * width * weight_itemsize
    rows = pairs * hidden * row_itemsize
    return {"fwd": {"flops": product, "bytes": weights + 2 * rows},
            "bwd": {"flops": 2 * product, "bytes": 2 * weights + 4 * rows}}


def block_macs(kind, seq, hidden, ssm_heads, ssm_head, groups, state, conv,
               chunk, q_heads, kv_heads, head, experts, held, top_k,
               expert_width, shared_width):
    """Forward multiply-adds ONE token needs in one block of ``kind``
    (``M`` state-space, ``*`` attention, ``E`` experts), by part."""
    if kind == "M":
        inner = ssm_heads * ssm_head
        conv_width = inner + 2 * groups * state
        return {"ssm_projections": hidden * (inner + conv_width + ssm_heads)
                + inner * hidden + conv * conv_width,
                "scan": ssd_macs(chunk, ssm_heads, ssm_head, groups, state)
                / float(chunk)}
    if kind == "*":
        # q and the output at the query heads' width, k and v at the key
        # heads'; a causal query meets (seq + 1) / 2 keys twice.
        return {"attention_projections": 2 * hidden * q_heads * head
                + 2 * hidden * kv_heads * head,
                "softmax": (seq + 1) * q_heads * head}
    assert kind == "E", kind
    return {"router": hidden * experts,
            "shared_expert": 2 * hidden * shared_width,
            "routed_experts": expected_pairs(1, top_k, held, experts)
            * 2 * hidden * expert_width}


def forward_macs_per_token(seq, hidden, vocab, pattern, **shape):
    """``{part: multiply-adds}`` one token needs in the forward pass of the
    blocks ``pattern`` names (a string of ``M``, ``E``, ``*``) and the
    output head."""
    parts = {"head": float(hidden * vocab)}
    for kind in pattern:
        for part, macs in block_macs(kind, seq, hidden, **shape).items():
            parts[part] = parts.get(part, 0.0) + macs
    return parts


def train_flops_per_sequence(seq, **shape):
    """Operations one sequence of ``seq`` tokens needs in a training
    step."""
    return 2.0 * TRAIN_FLOP_MULT * seq * sum(
        forward_macs_per_token(seq, **shape).values())
