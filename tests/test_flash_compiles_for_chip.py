"""The flash kernels compiled for a described v5e at the shapes
the benchmark's cells run (no chip: ``jax.experimental.topologies``).  What
Mosaic refuses (a slice off the tiling, more VMEM than the limit) it
refuses here, which interpret mode cannot show.  A compile, not a speed."""

import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # Such a compile can be written to the persistent cache and not read
    # back without a chip: keep it out.
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache)


# laguna's full and sliding layers, pt8k's and nemotron's softmax layers,
# BERT's (head 64 padded), and float32 inputs, whose output block halves the
# heads a step.
@pytest.mark.parametrize("flat_heads, seq, window, causal, dtype", [
    (96, 8192, None, True, jnp.bfloat16),
    (128, 8192, 512, True, jnp.bfloat16),
    (16, 8192, None, True, jnp.bfloat16),
    (64, 8192, None, True, jnp.bfloat16),
    (64, 512, None, False, jnp.bfloat16),
    (8, 8192, 512, True, jnp.float32)])
def test_the_one_backward_kernel_compiles_for_v5e(one_chip, flat_heads, seq,
                                                  window, causal, dtype):
    block_q, block_k, d_pad, _ = pk._plan(seq, 128, window)
    form, heads = pk._backward_form(flat_heads, seq, d_pad,
                                    jnp.dtype(dtype).itemsize, window)
    assert form == "onepass"
    x = jax.ShapeDtypeStruct((flat_heads, seq, d_pad), dtype,
                             sharding=one_chip)
    rows = jax.ShapeDtypeStruct((flat_heads, seq, 1), jnp.float32,
                                sharding=one_chip)
    compiled = jax.jit(functools.partial(
        pk._flash_attention_bwd_onepass_flat, causal=causal, block_q=block_q,
        block_k=block_k, interpret=False, window=window,
        heads=heads)).lower(x, x, x, x, rows, rows).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    # dq, dk, dv in the inputs' dtype and nothing else: no partial in HBM
    assert [(o.shape, o.dtype) for o in compiled.out_info] \
        == [((flat_heads, seq, d_pad), jnp.dtype(dtype))] * 3


# The full calls' grids are schedules of block pairs whose tables go in as
# scalar prefetch and whose index maps read a block's index from them: the
# forward kernel at the cells' shapes (laguna's full layers, nemotron's,
# pt8k's, BERT's without a mask: one pair a head), square blocks and a query
# block wider than the key block.
@pytest.mark.parametrize("flat_heads, seq, causal, blocks", [
    (96, 8192, True, None), (64, 8192, True, None), (16, 8192, True, None),
    (64, 512, False, None), (16, 8192, True, (512, 512)),
    (16, 8192, True, (1024, 512))])
def test_the_scheduled_forward_kernel_compiles_for_v5e(one_chip, flat_heads,
                                                       seq, causal, blocks):
    block_q, block_k = blocks or pk._plan(seq, 128)[:2]
    x = jax.ShapeDtypeStruct((flat_heads, seq, 128), jnp.bfloat16,
                             sharding=one_chip)
    compiled = jax.jit(functools.partial(
        pk._flash_attention_fwd_flat, causal=causal, block_q=block_q,
        block_k=block_k, interpret=False)).lower(x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert [(o.shape, o.dtype) for o in compiled.out_info] \
        == [((flat_heads, seq, 128), jnp.dtype(jnp.bfloat16)),
            ((flat_heads, seq, 1), jnp.dtype(jnp.float32))]


# The two backward kernels, which a call past ``_ONEPASS_VMEM_BUDGET`` takes
# (a sequence of 16384) and ``HVD_TPU_FLASH_BWD=pallas`` at any shape.
@pytest.mark.parametrize("flat_heads, seq, causal", [
    (96, 8192, True), (8, 16384, True), (64, 512, False)])
def test_the_scheduled_two_backward_kernels_compile_for_v5e(one_chip,
                                                            flat_heads, seq,
                                                            causal):
    block_q, block_k = pk._plan(seq, 128)[:2]
    x = jax.ShapeDtypeStruct((flat_heads, seq, 128), jnp.bfloat16,
                             sharding=one_chip)
    rows = jax.ShapeDtypeStruct((flat_heads, seq, 1), jnp.float32,
                                sharding=one_chip)
    compiled = jax.jit(functools.partial(
        pk._flash_attention_bwd_flat, causal=causal, block_q=block_q,
        block_k=block_k, interpret=False)).lower(
            x, x, x, x, rows, rows).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    assert [(o.shape, o.dtype) for o in compiled.out_info] \
        == [((flat_heads, seq, 128), jnp.dtype(jnp.bfloat16))] * 3


# kanana's latent-attention calls: [2, 8192, 32 heads] as 64 flat heads,
# query/key heads of 192 over values of 128, neither padded; the forward
# kernel, the one backward kernel (dq of a head in 256 lanes: 16 MiB) and the
# two kernels ``HVD_TPU_FLASH_BWD=pallas`` would take; and the forward kernel
# in float32, which the cell's builder runs once before the first step (the
# measured 1024 x 1024 blocks are bfloat16's: in float32 they pass the
# kernel's VMEM, and the plan gives the chains' 512 x 1024).
@pytest.mark.parametrize("kernel, calls, dtype", [
    ("forward", 1, jnp.bfloat16), ("onepass", 1, jnp.bfloat16),
    ("two_kernel", 2, jnp.bfloat16), ("forward", 1, jnp.float32)])
def test_the_kernels_at_two_head_sizes_compile_for_v5e(one_chip, kernel,
                                                       calls, dtype):
    flat_heads, seq, d_qk, d_v = 64, 8192, 192, 128
    block_q, block_k, d_pad, _ = pk._plan(seq, d_qk, None, d_v,
                                          jnp.dtype(dtype).itemsize)
    assert (block_q, block_k) == ((1024, 1024) if dtype == jnp.bfloat16
                                  else (512, 1024))
    assert (d_pad, pk._d_pad(d_v)) == (192, 128)
    assert pk._backward_form(flat_heads, seq, d_pad, 2, None) \
        == ("onepass", 1)

    def shape(width, dtype=dtype):
        return jax.ShapeDtypeStruct((flat_heads, seq, width), dtype,
                                    sharding=one_chip)

    qk, v, rows = shape(d_qk), shape(d_v), shape(1, jnp.float32)
    plan = dict(causal=True, block_q=block_q, block_k=block_k,
                interpret=False)
    if kernel == "forward":
        compiled = jax.jit(functools.partial(
            pk._flash_attention_fwd_flat, **plan)).lower(qk, qk, v).compile()
        want = [(v.shape, v.dtype), (rows.shape, rows.dtype)]
    else:
        flat = (pk._flash_attention_bwd_onepass_flat if kernel == "onepass"
                else pk._flash_attention_bwd_flat)
        compiled = jax.jit(functools.partial(flat, **plan)).lower(
            qk, qk, v, v, rows, rows).compile()
        # dq and dk at the query/key size, dv at the value's
        want = [(qk.shape, qk.dtype)] * 2 + [(v.shape, v.dtype)]
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == calls
    assert "192x128" in text
    assert [(o.shape, o.dtype) for o in compiled.out_info] == want


# The same calls with the one rotary key as an operand of its own: q at 192,
# every head's own key at 128, the shared part [2, 8192, 64] read by flat
# head i from batch entry i // 32; dk comes back at 128 and the part's
# gradient a flat head's share at 64.  bfloat16 at the measured 1024 x 1024
# and float32 (the builder's read) at the chains' 512 x 1024, the forward
# kernel and both backward forms.
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("kernel, calls", [
    ("forward", 1), ("onepass", 1), ("two_kernel", 2)])
def test_the_kernels_with_a_shared_key_part_compile_for_v5e(one_chip, kernel,
                                                            calls, dtype):
    batch, heads, seq, d_k, d_s, d_v = 2, 32, 8192, 128, 64, 128
    itemsize = jnp.dtype(dtype).itemsize
    block_q, block_k, d_pad, _ = pk._plan(seq, d_k + d_s, None, d_v, itemsize,
                                          True)
    assert (block_q, block_k, d_pad) == (
        (1024, 1024, 192) if dtype == jnp.bfloat16 else (512, 1024, 192))
    assert pk._backward_form(batch * heads, seq, d_pad, itemsize, None) \
        == ("onepass", 1)

    def shape(width, dtype=dtype, rows=batch * heads):
        return jax.ShapeDtypeStruct((rows, seq, width), dtype,
                                    sharding=one_chip)

    q, k, v, rows = shape(d_k + d_s), shape(d_k), shape(d_v), \
        shape(1, jnp.float32)
    shared = shape(d_s, rows=batch)
    flat = {"forward": pk._flash_attention_fwd_flat,
            "onepass": pk._flash_attention_bwd_onepass_flat,
            "two_kernel": pk._flash_attention_bwd_flat}[kernel]
    rest = (v,) if kernel == "forward" else (v, v, rows, rows)
    compiled = jax.jit(
        lambda shared, *operands: flat(
            *operands, causal=True, block_q=block_q, block_k=block_k,
            interpret=False, k_shared=shared)).lower(
                shared, q, k, *rest).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == calls
    assert "128s64x128" in text
    assert [(o.shape, o.dtype) for o in compiled.out_info] == (
        [(v.shape, v.dtype), (rows.shape, rows.dtype)] if kernel == "forward"
        else [(x.shape, x.dtype) for x in (q, k, shape(d_s), v)])


def test_the_banded_kernels_at_two_head_sizes_compile_for_v5e(one_chip):
    """No cell runs a window over two head sizes; the banded grids share the
    full calls' plumbing, and Mosaic takes them at 192 over 128 (two heads a
    step in the one backward kernel: dq of four would pass its budget)."""
    flat_heads, seq, window = 64, 8192, 512
    block_q, block_k, d_pad, _ = pk._plan(seq, 192, window, 128)
    assert pk._backward_form(flat_heads, seq, d_pad, 2, window) \
        == ("onepass", 2)

    def shape(width, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((flat_heads, seq, width), dtype,
                                    sharding=one_chip)

    qk, v, rows = shape(192), shape(128), shape(1, jnp.float32)
    plan = dict(causal=True, block_q=block_q, block_k=block_k,
                interpret=False, window=window)
    fwd = jax.jit(functools.partial(
        pk._flash_attention_fwd_flat, heads=pk._heads_of(flat_heads, window),
        **plan)).lower(qk, qk, v).compile()
    bwd = jax.jit(functools.partial(
        pk._flash_attention_bwd_onepass_flat, heads=2, **plan)).lower(
            qk, qk, v, v, rows, rows).compile()
    assert "hvd_flash_window_fwd_192x128" in fwd.as_text()
    assert [(o.shape, o.dtype) for o in bwd.out_info] \
        == [(qk.shape, qk.dtype)] * 2 + [(v.shape, v.dtype)]


# nemotron's scan (2 x 8192 tokens, 64 heads of 64 in 8 groups, state 128,
# chunk 128, bfloat16) first; then what else ``ssd_kernels.takes`` says yes
# to: a chunk of 256, float32 activations, a head a lane tile, four heads a
# lane tile, a group of two heads (its scalars filled up to 8 rows), and the
# widest group taken (1024 channels).
@pytest.mark.parametrize("heads, head, groups, chunk, dtype", [
    (64, 64, 8, 128, jnp.bfloat16),
    (64, 64, 8, 256, jnp.bfloat16),
    (64, 64, 8, 128, jnp.float32),
    (32, 128, 8, 128, jnp.bfloat16),
    (32, 32, 2, 128, jnp.bfloat16),
    (16, 64, 8, 128, jnp.bfloat16),
    (32, 64, 2, 128, jnp.bfloat16)])
def test_the_state_space_kernels_compile_for_v5e(one_chip, monkeypatch,
                                                 heads, head, groups, chunk,
                                                 dtype):
    from horovod_tpu.common import device
    from horovod_tpu.ops import ssd_kernels as sk
    bsz, seq, state = 2, 8192, 128
    per = heads // groups
    assert sk.takes(head, per, state, chunk)
    # the kernels ask ``device.on_tpu()``, as ``rehearse.py compile`` patches
    monkeypatch.setattr(device, "on_tpu", lambda: True)

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    x = shape((bsz, seq, heads * head), dtype)
    scal = shape((bsz, groups, seq // chunk, sk.scalar_rows(per), chunk),
                 jnp.float32)
    bc = shape((bsz, seq, groups * state), dtype)
    skip = shape((1, heads * head), jnp.float32)
    starts = shape((bsz, groups, seq // chunk, per * head, state),
                   jnp.float32)
    fwd = jax.jit(functools.partial(sk.forward, p=head)).lower(
        x, scal, bc, bc, skip).compile()
    assert fwd.as_text().count("tpu_custom_call") == 1
    # y in float32 and the chunks' start states, nothing else
    assert [(o.shape, o.dtype) for o in fwd.out_info] \
        == [(x.shape, jnp.float32), (starts.shape, jnp.float32)]
    bwd = jax.jit(functools.partial(sk.backward, p=head)).lower(
        x, scal, bc, bc, skip, shape(x.shape, jnp.float32), starts).compile()
    assert bwd.as_text().count("tpu_custom_call") == 1
    # each gradient in its input's shape and dtype, and D's a channel as
    # eight rows a (sequence, group): nothing of a step's size is partial
    assert [(o.shape, o.dtype) for o in bwd.out_info] \
        == [(v.shape, v.dtype) for v in (x, scal, bc, bc)] \
        + [((bsz, groups, 8, per * head), jnp.float32)]


def _delta_rule_grad(one_chip, batch, heads, key, value, per_head):
    """The lowered gradient of ``kda_chunked`` at a cell's shapes, chunk 64,
    and its arguments."""
    from horovod_tpu.models import linear_attention as la
    from horovod_tpu.ops import kda_kernels
    seq, chunk = 8192, 64
    assert kda_kernels.takes(key, value, chunk)

    def shape(*dims, dt=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    args = (shape(batch, seq, heads, key), shape(batch, seq, heads, key),
            shape(batch, seq, heads, value, dt=jnp.bfloat16),
            shape(batch, seq, heads) if per_head
            else shape(batch, seq, heads, key), shape(batch, seq, heads))
    return jax.jit(jax.grad(
        lambda *a: la.kda_chunked(*a, chunk).sum(), argnums=range(5))).lower(
            *args), args


def _custom_call(text, name):
    """(results, operands) of the one ``tpu_custom_call`` named ``name`` in
    a compiled text: their shapes as the text writes them, ``f32[1,8]``."""
    (line,) = [ln for ln in text.splitlines()
               if ln.lstrip().startswith("%%%s." % name)
               and 'custom_call_target="tpu_custom_call"' in ln]
    results = line.split(" = ", 1)[1].split(" custom-call(", 1)[0]
    operands = line.split("operand_layout_constraints={", 1)[1].split(
        "}, frontend_attributes", 1)[0]
    return tuple(re.findall(r"\w+\[[\d,]*\]", x) for x in (results, operands))


# The delta rule's two kernels at the cells' shapes: olmo's 30 heads of
# 96 over 192 (keys padded to 128 lanes, two heads' values three lane tiles,
# the second head's sliced at lane 64, a decay a head as one value a step in
# a pair of kernels of its own), pt8k's 8 heads of 128 over 128 (a decay for
# every channel), and one head a grid step at olmo's sizes (values padded to
# 256).
@pytest.mark.parametrize("batch, heads, key, value, per_head", [
    (1, 30, 96, 192, True), (2, 8, 128, 128, False), (1, 3, 96, 192, True)])
def test_the_delta_rule_kernels_compile_for_v5e(one_chip, monkeypatch, batch,
                                                heads, key, value, per_head):
    from horovod_tpu.ops import kda_kernels
    monkeypatch.setattr(kda_kernels, "on_tpu", lambda: True)
    seq = 8192
    lowered, args = _delta_rule_grad(one_chip, batch, heads, key, value,
                                     per_head)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    width = heads * kda_kernels.padded(value, heads)
    assert "f32[%d,%d,%d]" % (batch, seq, width) in text
    assert [(o.shape, o.dtype) for o in compiled.out_info] \
        == [(a.shape, a.dtype) for a in args]
    # q, k, kb, vb, g in and their gradients out: a decay for every channel
    # at the keys' width (olmo's would be f32[1,8192,3840]), a decay a head
    # as a row of 64 steps a head and chunk (2 MB at olmo's shape).
    def rows(size):
        return "f32[%d,%d,%d]" % (batch, seq,
                                  heads * kda_kernels.padded(size, heads))

    keys, values = rows(key), rows(value)
    steps = kda_kernels._heads_a_step(heads)
    decay = "f32[%d,%d,%d,%d,64]" % (batch, heads // steps, seq // 64,
                                     steps) if per_head else keys
    name = "_head" if per_head else ""
    _, fwd = _custom_call(text, "hvd_kda_fwd" + name)
    grads, bwd = _custom_call(text, "hvd_kda_bwd" + name)
    inputs = [keys, keys, keys, values, decay]
    assert fwd[2:] == inputs and bwd[2:8] == inputs + [values]
    assert grads == inputs


# sha256 of the Mosaic modules (no locations) of pt8k's two delta-rule
# kernels at PR 39 (commit 04437dc), before a decay a head took a pair of
# kernels of its own: a decay for every channel runs the parent's kernels
# to the letter.  A PR that changes them on purpose computes these again.
CHANNEL_PARENTS = {
    "hvd_kda_fwd":
        "573d0e1751f5d78d736286ebe36f5e68810712e97b64c5be1787e2ea28f17aae",
    "hvd_kda_bwd":
        "8f443ad29885b2bef4f230bcab8567c0c9ae701e9b6625aa770bf7f05dc57af8"}


def test_the_channel_delta_rule_kernels_lower_to_the_parents_text(
        one_chip, monkeypatch):
    from jax._src import tpu_custom_call
    from horovod_tpu.ops import kda_kernels
    monkeypatch.setattr(kda_kernels, "on_tpu", lambda: True)
    bodies = {}
    serialize = tpu_custom_call._lower_mosaic_module_to_asm

    def keep(module, **kw):
        text = module.operation.get_asm(enable_debug_info=False)
        name = text.split("module @", 1)[1].split(" ", 1)[0]
        bodies[name] = hashlib.sha256(text.encode()).hexdigest()
        return serialize(module, **kw)

    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm", keep)
    _delta_rule_grad(one_chip, 2, 8, 128, 128, False)
    assert bodies == CHANNEL_PARENTS


def _fusions(text):
    """{computation: {instruction: (opcode, operands, called computations,
    op_name)}} of a compiled HLO text."""
    comps, body = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            body = comps[head.group(1)] = {}
            continue
        ins = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .*? ([\w\-]+)\((.*)$",
                       line)
        if body is not None and ins:
            name, opcode, rest = ins.groups()
            operands = rest.split(")", 1)[0]
            op_name = re.search(r'op_name="([^"]*)"', rest)
            body[name] = (opcode, re.findall(r"%([\w.\-]+)", operands),
                          re.findall(r"calls=%([\w.\-]+)", rest),
                          op_name.group(1) if op_name else "")
    return comps


def _logistic_in_product_operands(text, scope):
    """The fusions under ``scope`` whose product (a ``convolution``) reads
    an operand that a ``logistic`` (on the chip: an ``exponential``) is
    computed into, inside the fusion: rebuilt for every tile."""
    comps = _fusions(text)

    def opcodes(comp):
        for opcode, _, calls, _ in comps[comp].values():
            yield opcode
            for called in calls:
                yield from opcodes(called)

    def feeds(comp):
        body = comps[comp]
        for opcode, operands, calls, _ in body.values():
            if opcode == "convolution":
                todo, seen = list(operands), set()
                while todo:
                    name = todo.pop()
                    if name in seen or name not in body:
                        continue
                    seen.add(name)
                    op, more, called, _ = body[name]
                    inner = {op}.union(*(set(opcodes(c)) for c in called))
                    if inner & {"logistic", "exponential"}:
                        return True
                    todo += more
            if any(feeds(c) for c in calls):
                return True
        return False

    found, products = [], 0
    for body in comps.values():
        for name, (opcode, _, calls, op_name) in body.items():
            if opcode == "fusion" and scope in op_name \
                    and "convolution" in set(opcodes(calls[0])):
                products += 1
                if feeds(calls[0]):
                    found.append(name)
    return found, products


# A post-norm SwiGLU layer's train step with AdamW after it, small: the
# dense feed-forward's products, forward and backward, read silu(a) * g, its
# derivative and the norm's gradient as buffers made once; the plain
# formula, one expression left to XLA, has them rebuilt inside a weight
# gradient's product for each of its tiles.
@pytest.mark.parametrize("split", [True, False])
def test_no_swiglu_product_rebuilds_its_operands_on_v5e(one_chip, split):
    import numpy as np
    import optax
    from jax.sharding import Mesh, PartitionSpec as P
    from horovod_tpu.common import scopes
    from horovod_tpu.models import transformer as T
    tokens, width, inner = 1024, 256, 1024
    cfg = T.TransformerConfig(d_model=width, d_ff=inner, dtype="bfloat16",
                              post_norm=True)

    @jax.named_scope(scopes.DENSE_FFN)
    def formula(h, lp, cfg):
        w1, w3, w2 = (lp[k].astype(h.dtype) for k in ("w1", "w3", "w2"))
        return jax.lax.psum((jax.nn.silu(h @ w1) * (h @ w3)) @ w2,
                            cfg.tp_axis)

    ffn = T._dense_ffn if split else formula

    def loss(lp, x):
        y = x + T.rms_norm(ffn(x, lp, cfg), lp["ln2"], cfg.norm_eps)
        return jnp.mean(jnp.square(y.astype(jnp.float32)))

    opt = optax.adamw(1e-3)
    mesh = Mesh(np.asarray(list(one_chip.device_set)).reshape(1, 1, 1),
                ("dp", "sp", "tp"))

    def step(lp, state, x):
        grads = jax.shard_map(
            jax.grad(jax.checkpoint(loss)), mesh=mesh, in_specs=(P(), P()),
            out_specs=P(), check_vma=False)(lp, x)
        updates, state = opt.update(grads, state, lp)
        return optax.apply_updates(lp, updates), state

    def shape(*dims, dt=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    lp = {"w1": shape(width, inner), "w3": shape(width, inner),
          "w2": shape(inner, width), "ln2": shape(width)}
    state = jax.eval_shape(opt.init, lp)
    state = jax.tree.map(lambda s: shape(*s.shape, dt=s.dtype), state)
    text = jax.jit(step).lower(
        lp, state, shape(1, tokens, width, dt=jnp.bfloat16)).compile() \
        .as_text()
    found, products = _logistic_in_product_operands(text, scopes.DENSE_FFN)
    assert products >= 6
    assert bool(found) != split, found
