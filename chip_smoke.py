#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py            # every leg the machine can run
    python3 chip_smoke.py LEG ...    # only the named legs (debugging)

Drives the training path once through the entry points a user calls, at
the full width of the models ``bench.py`` tracks, with random weights
made from a seed and a few steps each:

* ``resnet50``     ResNet-50, 224x224, 1000 classes, bf16, batch 128 per
                   chip, SGD-momentum: ``hvd.init`` ->
                   ``hvd.make_data_parallel_step`` ->
                   ``hvd.broadcast_parameters`` -> ``hvd.shard_batch``.
* ``transformer``  ``d1024_L12_hd128_seq2048_b4``, vocab 8192, through
                   ``models.transformer.make_train_step``; the lowered
                   step must hold the Mosaic kernels and no interpreted
                   one, and the flash forward and backward must agree
                   with ``_reference_attention`` at the flagship shape,
                   and again with query/key heads of 192 over its values,
                   64 of them a key part all heads share (a
                   latent-attention call).
* ``collectives``  the eager ``horovod_tpu.ops.api`` surface in
                   ``inprocess`` mode at a small and a large
                   (64 MiB per rank) size, every result against numpy.

A machine with four chips or more runs those on one chip and again on
four (``@4``: one process driving all four, every array spread over the
four devices), then ``layouts@4`` (the in-process layouts of
``__graft_entry__.run_layouts``: ring attention, Ulysses, MoE, pipeline)
and ``launcher@4`` (``python -m horovod_tpu.runner -np 4 --multihost``,
four processes of one chip each).

This process never imports jax: a parent that has touched JAX holds the
chip, and a child that needs it then fails or hangs.  A probe child
reports what JAX finds, then each leg runs as a child of its own, one
after another, under a time limit.  Every process started here carries
a tag in its environment and is killed by that tag when its leg ends,
whatever session it moved to.  Any leg's non-zero exit or timeout, or a
probe that does not report ``tpu``, ends the run non-zero, naming it.
Times printed are the host clock around ``block_until_ready``, for
information only.

Last line of standard output on success, and only then:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
TAG = "CHIP_SMOKE_RUN"

# Seconds a leg may take, compilation included; the one-chip legs sum
# to less than the 1200 s the whole run is allowed.
LEG_TIMEOUT_S = {"resnet50": 420, "transformer": 360, "collectives": 300,
                 "layouts": 420, "launcher": 420}
ONE_CHIP_LEGS = ("resnet50", "transformer", "collectives")
FOUR_CHIP_LEGS = ONE_CHIP_LEGS + ("layouts", "launcher")

MIB = 1 << 20

# What the legs run, at full width (bench.py's two tracked models).
PLATFORM = "tpu"
RESNET = {"image": 224, "classes": 1000, "batch_per_chip": 128, "steps": 6}
TRANSFORMER = {"vocab_size": 8192, "d_model": 1024, "n_layers": 12,
               "n_heads": 8, "n_kv_heads": 8, "d_ff": 3072, "max_seq": 2048}
TRANSFORMER_BATCH, TRANSFORMER_STEPS = 4, 5      # sequences per dp replica
# float32 elements per rank: 4 KiB, and 64 MiB (a real model's fused
# gradient buffer).
COLLECTIVE_SIZES = (1024, 64 * MIB // 4)


# -- parent: stays off jax --------------------------------------------------

def tagged(tag_value):
    """PIDs of the processes carrying this tag, ourselves excepted."""
    needle = ("%s=%s" % (TAG, tag_value)).encode()
    pids = []
    for name in os.listdir("/proc"):
        if not name.isdigit() or int(name) == os.getpid():
            continue
        try:
            with open("/proc/%s/environ" % name, "rb") as f:
                if needle in f.read().split(b"\0"):
                    pids.append(int(name))
        except OSError:
            pass  # gone already, or not ours to read
    return pids


def reap(tag_value):
    for pid in tagged(tag_value):
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass


def run_child(name, argv, timeout_s):
    """Run one child to its end or its time limit, passing its output
    through; returns (exit code or "timeout", its output lines).  The
    child and everything it started are dead when this returns: a
    process left alive would hold the chip."""
    tag_value = "%d.%s" % (os.getpid(), name)
    env = dict(os.environ, **{TAG: tag_value, "PYTHONUNBUFFERED": "1"})
    proc = subprocess.Popen(argv, cwd=HERE, env=env, text=True,
                            errors="replace", stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT)
    timed_out = threading.Event()

    def expire():
        timed_out.set()
        reap(tag_value)

    timer = threading.Timer(timeout_s, expire)
    timer.start()
    lines = []
    try:
        for line in proc.stdout:
            lines.append(line)
            sys.stdout.write("[%s] %s" % (name, line))
            sys.stdout.flush()
        rc = proc.wait()
    finally:
        timer.cancel()
        reap(tag_value)
        proc.wait()
    return ("timeout" if timed_out.is_set() else rc), lines


def child_argv(leg, chips):
    return [sys.executable, os.path.join(HERE, "chip_smoke.py"),
            "--child", leg, str(chips)]


def probe():
    """What JAX finds, as a child reports it; None if it reports
    nothing."""
    rc, lines = run_child("probe", child_argv("probe", 0), 120)
    for line in reversed(lines):
        if line.startswith("{"):
            return json.loads(line) if rc == 0 else None
    return None


def plan(count, wanted):
    """[(leg, chips)] for a machine with ``count`` chips."""
    legs = [(leg, 1) for leg in ONE_CHIP_LEGS]
    if count >= 4:
        legs += [(leg, 4) for leg in FOUR_CHIP_LEGS]
    if wanted:
        names = {"%s@%d" % lc for lc in legs} | {leg for leg, _ in legs}
        unknown = [w for w in wanted if w not in names]
        if unknown:
            raise SystemExit("chip_smoke: no leg %s on %d chip(s); legs: %s"
                             % (unknown, count, sorted(names)))
        legs = [lc for lc in legs
                if lc[0] in wanted or "%s@%d" % lc in wanted]
    return legs


def main(wanted):
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    found = probe()
    if found is None:
        print("chip_smoke: FAILED: the probe reported no device")
        return 1
    print("chip_smoke: jax %(jax)s, jaxlib %(jaxlib)s, libtpu %(libtpu)s; "
          "platform=%(platform)s device_kind=%(kind)s count=%(count)d"
          % found)
    if found["platform"] != PLATFORM:
        print("chip_smoke: FAILED: JAX found platform %r, not %r; "
              "nothing was compiled" % (found["platform"], PLATFORM))
        return 1
    legs = plan(found["count"], wanted)
    print("chip_smoke: legs: %s"
          % " ".join("%s@%d" % lc for lc in legs))
    results, failed = [], []
    for leg, chips in legs:
        name = "%s@%d" % (leg, chips)
        t0 = time.time()
        rc, lines = run_child(name, child_argv(leg, chips),
                              LEG_TIMEOUT_S[leg])
        done = [ln for ln in lines if ln.startswith("LEG_OK ")]
        if rc == 0 and done:
            results.append((name, time.time() - t0,
                            json.loads(done[-1][len("LEG_OK "):])))
            continue
        failed.append(name)
        print("chip_smoke: FAILED: leg %s (%s after %.0f s)"
              % (name, "timeout" if rc == "timeout"
                 else "exit code %s" % rc, time.time() - t0))
        if rc == "timeout":
            break           # the chip may be wedged: do not queue behind it
    for name, secs, info in results:
        print("chip_smoke: ok %-14s %5.0f s  %s"
              % (name, secs, json.dumps(info, sort_keys=True)))
    if failed:
        print("chip_smoke: FAILED: %s" % " ".join(failed))
        return 1
    cached = [info["first_step_from_cache"] for _, _, info in results
              if "first_step_from_cache" in info]
    print("chip_smoke: step executables read from the compile cache: "
          "%d of %d" % (sum(cached), len(cached)))
    print(json.dumps({"ok": True, "device": {
        "platform": found["platform"], "kind": found["kind"],
        "count": found["count"]}}))
    return 0


# -- children: each one owns the chip(s) while it lives ---------------------

def child_probe():
    import jax
    import jaxlib
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    devices = jax.devices()
    print(json.dumps({
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "jax": jax.__version__,
        "jaxlib": jaxlib.__version__, "libtpu": libtpu_version}))


class CacheCounter:
    """Counts JAX's own compile-cache events in this process."""

    REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
    HITS = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import collections

        import jax.monitoring
        self.counts = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda event, **_: self.counts.update([event]))

    def snapshot(self):
        return self.counts[self.REQUESTS], self.counts[self.HITS]


def start_leg(name, chips):
    """Common head of a leg that computes: place the compile cache
    before the first compile, refuse anything but a TPU, say what the
    leg runs on, bring the runtime up over ``chips`` devices."""
    import jax

    import horovod_tpu.jax as hvd
    from horovod_tpu.common.device import place_compile_cache
    cache_dir = place_compile_cache()
    counter = CacheCounter()
    devices = jax.devices()
    if devices[0].platform != PLATFORM:
        raise SystemExit("leg %s: platform %r, not %r"
                         % (name, devices[0].platform, PLATFORM))
    if len(devices) < chips:
        raise SystemExit("leg %s: needs %d chips, JAX finds %d"
                         % (name, chips, len(devices)))
    print("platform=%s device_kind=%s count=%d, using %d; compile cache %s"
          % (devices[0].platform, devices[0].device_kind, len(devices),
             chips, cache_dir))
    # With no launcher env, init() takes every device it is not told
    # to leave alone.
    hvd.init(devices=None if chips == len(devices) else devices[:chips])
    assert hvd.size() == chips, (hvd.size(), chips)
    return hvd, devices[:chips], counter


def device_bytes(device, key):
    return device.memory_stats()[key]


def train(step, state, batch, n_steps, devices, counter):
    """``n_steps`` of ``step(*state, batch) -> (*state, loss)``.  The
    loss must be finite and fall; on several chips every array must be
    spread over all of them, every chip must hold bytes after a step,
    and no step after the first may grow chip 0."""
    import jax
    import numpy as np
    losses, times, chip0 = [], [], []
    r0, h0 = counter.snapshot()
    for i in range(n_steps):
        t0 = time.time()
        *state, loss = step(*state, batch)
        loss = float(jax.block_until_ready(loss))
        times.append(time.time() - t0)
        losses.append(loss)
        chip0.append(device_bytes(devices[0], "bytes_in_use"))
        if i == 0:
            requests, hits = (a - b for a, b in
                              zip(counter.snapshot(), (r0, h0)))
        print("step %d loss %.4f  %.3f s" % (i, loss, times[-1]))
    assert np.all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], "loss did not fall: %s" % losses
    if len(devices) > 1:
        for leaf in jax.tree.leaves((state, batch)):
            assert leaf.sharding.device_set == set(devices), (
                leaf.shape, leaf.sharding)
        in_use = [device_bytes(d, "bytes_in_use") for d in devices]
        print("bytes in use per chip after the last step: %s; chip 0 "
              "after each step: %s" % (in_use, chip0))
        assert all(b > 0 for b in in_use), in_use
        assert max(chip0[1:]) <= chip0[0] + MIB, chip0
    print("first step: %d cacheable compile request(s), %d read from "
          "the cache" % (requests, hits))
    return {"loss": [round(losses[0], 4), round(losses[-1], 4)],
            "first_step_s": round(times[0], 1),
            "step_s": round(min(times[1:]), 4),
            "first_step_from_cache": bool(requests and hits == requests)}


def leg_resnet50(chips):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu.models.resnet import create_resnet50, resnet_loss_fn
    hvd, devices, counter = start_leg("resnet50", chips)
    image = RESNET["image"]
    model = create_resnet50(num_classes=RESNET["classes"],
                            dtype=jnp.bfloat16)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, image, image, 3), jnp.bfloat16))

    def loss_fn(variables, batch):
        # As examples/jax_synthetic_benchmark.py: the loss-only step.
        return resnet_loss_fn(model, variables, batch, train=True)[0]

    step, opt_init = hvd.make_data_parallel_step(
        loss_fn, optax.sgd(0.01, momentum=0.9))
    variables = hvd.broadcast_parameters(variables, root_rank=0)
    opt_state = hvd.broadcast_optimizer_state(opt_init(variables))
    rng = np.random.default_rng(0)
    n = RESNET["batch_per_chip"] * chips
    batch = hvd.shard_batch({
        "x": rng.standard_normal((n, image, image, 3), np.float32
                                 ).astype(jnp.bfloat16),
        "y": rng.integers(0, RESNET["classes"], n, np.int32)})
    info = train(step, (variables, opt_state), batch, RESNET["steps"],
                 devices, counter)
    hvd.shutdown()
    return dict(info, global_batch=n)


def count_pallas_calls(jaxpr):
    """(pallas_call equations, those in interpret mode) in a jaxpr and
    everything nested in it."""
    import jax.extend.core as jex
    total = interpreted = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            total += 1
            interpreted += bool(eqn.params["interpret"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                if isinstance(sub, jex.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jex.Jaxpr):
                    t, i = count_pallas_calls(sub)
                    total, interpreted = total + t, interpreted + i
    return total, interpreted


def check_kernels_compiled(traced):
    """The step holds the flash forward and its backward (one kernel where
    dq of a head fits in VMEM, else two), every one lowered to a Mosaic
    custom call and none interpreted."""
    n_pallas, n_interpreted = count_pallas_calls(traced.jaxpr.jaxpr)
    n_mosaic = traced.lower().as_text().count("tpu_custom_call")
    print("lowered step: %d pallas_call(s), %d interpreted, %d Mosaic "
          "custom call(s)" % (n_pallas, n_interpreted, n_mosaic))
    assert n_interpreted == 0 and n_mosaic == n_pallas >= 2, (
        n_pallas, n_interpreted, n_mosaic)
    return n_mosaic


def check_flash_against_reference():
    """Flash forward and both Pallas backward forms against
    ``_reference_attention`` at the flagship per-step shape, on the
    chip.  Tolerance: 2^-5 of the reference's largest magnitude, a
    handful of bf16 roundings (eps 2^-8) of an output held in bf16; a
    wrong kernel is off by the magnitude itself."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops import pallas_kernels as pk
    rng = np.random.default_rng(1)
    cfg = TRANSFORMER
    shape = (TRANSFORMER_BATCH, cfg["max_seq"], cfg["n_heads"],
             cfg["d_model"] // cfg["n_heads"])
    q, k, v, g = (jnp.asarray(rng.standard_normal(shape, np.float32) * 0.5,
                              jnp.bfloat16) for _ in range(4))

    def weighted(attn):
        def f(q, k, v):
            out = attn(q, k, v, True)
            return (out.astype(jnp.float32) * g.astype(jnp.float32)
                    ).sum(), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    def close(name, got, want):
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        err, top = np.abs(got - want).max(), np.abs(want).max()
        print("flash %-22s max|err| %.5f of max|ref| %.4f"
              % (name, err, top))
        return bool(np.isfinite(got).all() and err <= top * 2.0 ** -5)

    (_, want_out), want_grads = weighted(pk._reference_attention)(q, k, v)
    (_, out), grads = weighted(pk.flash_attention)(q, k, v)
    ok = close("forward", out, want_out)
    batch, seq, heads, d = shape
    block_q, block_k, d_pad, scale = pk._plan(seq, d)
    form = pk._backward_form(batch * heads, seq, d_pad, 2, None)[0]
    for name, got, want in zip("qkv", grads, want_grads):
        ok &= close("backward (%s) d%s" % (form, name), got, want)

    # The two kernels, which a call takes where the one kernel's dq does
    # not fit, on the flat operands the call hands the kernels.
    @jax.jit
    def two_kernels(q, k, v, g):
        f32 = jnp.float32
        q, k, v, g = (pk._to_flat(x, d_pad) for x in (q * scale, k, v, g))
        plan = dict(causal=True, block_q=block_q, block_k=block_k,
                    interpret=False)
        o, lse = pk._flash_attention_fwd_flat(q, k, v, **plan)
        delta = jnp.sum(g.astype(f32) * o.astype(f32), -1, keepdims=True)
        dq, dk, dv = pk._flash_attention_bwd_flat(q, k, v, g, lse, delta,
                                                  **plan)
        return (pk._from_flat(dq.astype(f32) * scale, batch, heads, d, q),
                pk._from_flat(dk, batch, heads, d, k),
                pk._from_flat(dv, batch, heads, d, v))

    for name, got, want in zip("qkv", two_kernels(q, k, v, g), want_grads):
        ok &= close("backward (two_kernel) d" + name, got, want)

    # One latent-attention call: query/key heads of 192 over the same
    # values, the last 64 of every head's key one rotary key a position,
    # which the kernels read as an operand of its own (names of their own).
    q, shared = (jnp.asarray(
        rng.standard_normal(dims, np.float32) * 0.5, jnp.bfloat16)
        for dims in (shape[:3] + (shape[3] + 64,), shape[:2] + (64,)))

    def in_two_parts(attn):
        return weighted(lambda q, parts, v, causal: attn(
            q, parts[0], v, causal, k_shared=parts[1]))

    (_, want_out), want_grads = in_two_parts(
        lambda q, k, v, causal, k_shared: pk._reference_attention(
            q, pk.whole_key(k, k_shared), v, causal))(q, (k, shared), v)
    (_, out), grads = in_two_parts(pk.flash_attention)(q, (k, shared), v)
    sizes = "%ds64x%d" % (shape[3], shape[3])
    ok &= close("forward " + sizes, out, want_out)
    for name, got, want in zip(
            ("q", "k", "k_shared", "v"), jax.tree.leaves(grads),
            jax.tree.leaves(want_grads)):
        ok &= close("backward %s d%s" % (sizes, name), got, want)
    assert ok, "flash attention disagrees with the reference on the chip"
    return form


def leg_transformer(chips):
    import jax
    import numpy as np
    import optax

    from horovod_tpu.models.transformer import (TransformerConfig,
                                                init_params,
                                                make_train_step)
    hvd, devices, counter = start_leg("transformer", chips)
    # Four chips: tp puts the flash kernel under shard_map with half the
    # heads, and the tp psum and vocab-parallel CE on real ICI.
    dp, sp, tp = (1, 1, 1) if chips == 1 else (chips // 2, 1, 2)
    mesh = hvd.create_mesh((dp, sp, tp), ("dp", "sp", "tp"), devices)
    cfg = TransformerConfig(**TRANSFORMER)
    build, shard_batch = make_train_step(cfg, mesh, optax.adam(1e-3))
    step, params, opt_state = build(
        init_params(jax.random.PRNGKey(0), cfg))
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (TRANSFORMER_BATCH * dp, cfg.max_seq), np.int32)
    batch = shard_batch({"tokens": tokens,
                         "targets": np.roll(tokens, -1, axis=1)})

    n_mosaic = check_kernels_compiled(step.trace(params, opt_state, batch))
    info = train(step, (params, opt_state), batch, TRANSFORMER_STEPS,
                 devices, counter)
    del params, opt_state
    flash_backward = check_flash_against_reference()
    hvd.shutdown()
    return dict(info, mesh=[dp, sp, tp], mosaic_calls=n_mosaic,
                flash_backward=flash_backward)


def leg_collectives(chips):
    import jax
    import numpy as np

    from horovod_tpu.ops import api
    hvd, devices, _ = start_leg("collectives", chips)
    n = chips
    rng = np.random.default_rng(0)
    weights = np.arange(1, n + 1, dtype=np.float32)

    def timed(name, size, fn, *args, **kw):
        t0 = time.time()
        out = jax.block_until_ready(fn(*args, **kw))
        print("%-28s %9d B/rank  %.4f s"
              % (name, size * 4, time.time() - t0))
        return out

    def same(out, want, sharded=False):
        """Per rank: every chip's replica (or its own row) is right."""
        assert out.sharding.device_set == set(devices), out.sharding
        assert n == 1 or out.is_fully_replicated == (not sharded)
        for shard in out.addressable_shards:
            np.testing.assert_allclose(
                np.asarray(shard.data), want[shard.index],
                rtol=1e-5, atol=1e-6)

    for size in COLLECTIVE_SIZES:
        per = size // n * n          # divisible for alltoall/reducescatter
        base = rng.standard_normal(per, np.float32)
        x = base[None, :] * weights[:, None]       # x[r] = (r+1) * base
        total = base * weights.sum()
        same(timed("allreduce Sum", per, api.allreduce, x, op=api.SUM),
             total)
        same(timed("allreduce Average pre/post", per, api.allreduce, x,
                   op=api.AVERAGE, prescale_factor=0.5,
                   postscale_factor=4.0), total / n * 2.0)
        a, b = timed("grouped_allreduce x2", per, api.grouped_allreduce,
                     [x, -x], op=api.SUM)
        same(a, total)
        same(b, -total)
        same(timed("allgather", per, api.allgather, x[:, None, :]), x)
        same(timed("broadcast root=%d" % (n - 1), per, api.broadcast, x,
                   root_rank=n - 1), x[n - 1])
        k = per // n
        same(timed("alltoall", per, api.alltoall, x),
             x.reshape(n, n, k).transpose(1, 0, 2).reshape(n, per),
             sharded=True)
        same(timed("reducescatter Sum", per, api.reducescatter, x,
                   op=api.SUM), total.reshape(n, k), sharded=True)
        api.barrier()
    peaks = [device_bytes(d, "peak_bytes_in_use") for d in devices]
    print("peak bytes per chip: %s" % peaks)
    if n > 1:
        # A rank-major stack staged whole on chip 0 before it is
        # sharded would show as n x 64 MiB more there than anywhere.
        assert peaks[0] <= max(peaks[1:]) + 16 * MIB, peaks
    hvd.shutdown()
    return {"size": n, "peak_bytes": peaks}


def leg_layouts(chips):
    import __graft_entry__ as entry
    hvd, devices, _ = start_leg("layouts", chips)
    entry.run_layouts(devices)
    hvd.shutdown()
    return {"layouts": ["dense ring", "ulysses", "moe", "hierarchical",
                        "bert", "pipeline"]}


def leg_launcher(chips):
    """``-np chips --multihost`` on this host: one process for each chip,
    to DONE on every rank.  This child stays off jax; its workers own
    the chips."""
    from horovod_tpu.core.client import build_library
    print("native core rebuilt from core/src: %s" % build_library(force=True))
    cmd = [sys.executable, "-m", "horovod_tpu.runner", "-np", str(chips),
           "--multihost", sys.executable,
           os.path.join("examples", "multihost_pod_training.py")]
    print("$ " + " ".join(cmd))
    proc = subprocess.Popen(cmd, cwd=HERE, text=True,
                            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        out, _ = proc.communicate(timeout=LEG_TIMEOUT_S["launcher"] - 60)
    except subprocess.TimeoutExpired:
        # SIGINT lets the launcher tear its workers down itself.
        proc.send_signal(signal.SIGINT)
        out, _ = proc.communicate(timeout=30)
        print(out)
        raise SystemExit("launcher did not finish")
    print(out)
    assert proc.returncode == 0, "launcher exit code %d" % proc.returncode
    for rank in range(chips):
        for want in ("rank %d/%d: 1 local of %d global devices, %d "
                     "processes" % (rank, chips, chips, chips),
                     "DONE rank=%d size=%d" % (rank, chips)):
            assert want in out, "missing from the workers' output: " + want
    return {"processes": chips, "local_devices_each": 1}


def child(leg, chips):
    if leg == "probe":
        return child_probe()
    info = {"resnet50": leg_resnet50, "transformer": leg_transformer,
            "collectives": leg_collectives, "layouts": leg_layouts,
            "launcher": leg_launcher}[leg](chips)
    print("LEG_OK " + json.dumps(info))


if __name__ == "__main__":
    if sys.argv[1:2] == ["--child"]:
        child(sys.argv[2], int(sys.argv[3]))
    else:
        sys.exit(main(sys.argv[1:]))
