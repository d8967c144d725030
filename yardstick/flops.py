"""Operations and bytes the algorithms need, computed from shapes.

A multiply-add counts as 2 operations.  A training step needs the forward
pass once and, for every matrix product in it, two more of the same size
in the backward pass (the gradient for the input and the one for the
weight): ``TRAIN_FLOP_MULT`` = 3.  Recomputed operations do not count, so
utilisation computed from these numbers is the model's, not the
hardware's.  ``RESNET50_GFLOPS_FWD`` and the ``x 3`` rule are
``bench.py``'s (``MODEL_GFLOPS_FWD``, ``TRAIN_FLOP_MULT``); BERT's count
follows the same convention as ``bench.py``'s transformer count.
"""

TRAIN_FLOP_MULT = 3.0

# Forward pass of ResNet-50 on one 224x224 image: 4.089 G operations
# (2 x 2.04 G multiply-adds, convolutions and the final dense layer).
RESNET50_GFLOPS_FWD = 4.089


def resnet50_train_flops(image_size=224):
    """Operations one image needs in a training step.  Convolution work
    scales with the image's area."""
    return (RESNET50_GFLOPS_FWD * 1e9 * TRAIN_FLOP_MULT
            * (image_size / 224.0) ** 2)


def bert_encoder_macs(tokens, hidden, layers, intermediate):
    """Forward multiply-adds of the encoder for ONE sequence of ``tokens``
    real tokens.  Per layer and token: the four attention projections
    (4 h^2), the two feed-forward products (2 h f), and attention itself,
    where every token meets every token of its sequence twice (scores,
    then the weighted sum of values): 2 x tokens x h."""
    per_token = 4 * hidden * hidden + 2 * hidden * intermediate \
        + 2 * tokens * hidden
    return layers * tokens * per_token


def bert_mlm_head_macs(predictions, hidden, vocab):
    """Forward multiply-adds of the masked-LM head at ``predictions``
    positions: the h x h transform and the tied h x vocab decoder.  Only
    masked positions enter the loss, so only they are needed; a program
    that projects every position does more than this counts."""
    return predictions * (hidden * hidden + hidden * vocab)


def bert_cls_head_macs(hidden, labels):
    """Pooler (h x h on the first token) and the classifier."""
    return hidden * hidden + hidden * labels


def bert_train_flops(token_counts, hidden, layers, intermediate,
                     head_macs_per_sequence):
    """Mean operations a sequence needs in a training step, over
    sequences with ``token_counts`` real tokens each (padding is not
    work the model needs)."""
    macs = sum(bert_encoder_macs(t, hidden, layers, intermediate)
               + head_macs_per_sequence for t in token_counts)
    return 2.0 * macs * TRAIN_FLOP_MULT / len(token_counts)


def flash_attention_cost(batch, heads, seq, head_dim, causal, itemsize=2):
    """(operations, bytes) of one flash-attention call, forward and
    backward apart, at the model's own head size: lanes a kernel pads to
    are not useful work.

    Forward: scores and the weighted sum, 2 products of
    ``seq x seq x head_dim`` a head.  Backward: the score recomputation
    and the four gradient products (dV, dP, dQ, dK), 5 such products;
    a kernel split into a dq and a dk/dv pass recomputes more, which is
    its cost and not the algorithm's.  A causal mask halves the work.
    Bytes: the forward reads q, k, v and writes o; the backward reads
    q, k, v, o, do and writes dq, dk, dv.  The row statistics are small
    beside them and left out."""
    product = 2.0 * batch * heads * seq * seq * head_dim
    if causal:
        product /= 2
    tensor = batch * heads * seq * head_dim * itemsize
    return {"fwd": {"flops": 2 * product, "bytes": 4 * tensor},
            "bwd": {"flops": 5 * product, "bytes": 8 * tensor}}


def roofline_seconds(flops, nbytes, peak):
    """The least time the chip could take, and which peak sets it."""
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bytes")


def allreduce_bus_bytes(payload_bytes, ranks):
    """Bytes every link of a ring carries to all-reduce ``payload_bytes``
    over ``ranks`` members: NCCL's bus-bandwidth convention
    (``benchmarks/allreduce_bw.py``)."""
    return 2.0 * (ranks - 1) / ranks * payload_bytes
