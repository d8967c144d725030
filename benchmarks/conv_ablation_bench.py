"""In-situ conv cost attribution by whole-model ablation.

Isolated per-conv microbenchmarks timed from the host are dominated
by per-call dispatch and fetch cost (three estimators gave three
answers).  What is stable is the full training step, so this harness
attributes conv cost the way the round-3 BN ablation did: replace the
3x3 convs with 1x1 convs of the same channel plan — inside the real
fwd+bwd+SGD step — and read the delta.

Variants: full model; 3x3->1x1 everywhere; early stages only
(filters 64/128, the 56^2/28^2 MXU-unfriendly shapes); late stages
only (256/512).  The replacement 1x1 carries 1/9 of the tap FLOPs, so
``delta ~= in-situ cost of the ablated 3x3s - 1/9``.

    python benchmarks/conv_ablation_bench.py [--batch 128] [--steps 10]

Prints one JSON line per variant.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--variants", default="full,all,early,late")
    ap.add_argument("--ab", default=None,
                    help="two comma-separated variants: build both "
                         "steps once, ALTERNATE timing windows many "
                         "times in one process (tightest drift "
                         "control), report per-round pairs + medians")
    ap.add_argument("--rounds", type=int, default=6)
    args = ap.parse_args()

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models import resnet as R

    class AblatedBottleneck(nn.Module):
        """BottleneckBlock with the 3x3 conv optionally ablated to a
        1x1 of the same channels/stride (keeps every other op, BN
        plan, and residual identical)."""
        filters: int
        strides: tuple
        norm: object
        dtype: object = jnp.bfloat16
        ablate: str = "all"  # all | early | late

        def _ablated(self):
            if self.ablate == "all":
                return True
            if self.ablate == "early":
                return self.filters <= 128
            return self.filters >= 256

        @nn.compact
        def __call__(self, x):
            residual = x
            y = nn.Conv(self.filters, (1, 1), use_bias=False,
                        dtype=self.dtype)(x)
            y = self.norm()(y)
            k = (1, 1) if self._ablated() else (3, 3)
            y = nn.Conv(self.filters, k, self.strides, use_bias=False,
                        dtype=self.dtype)(y)
            y = self.norm()(y)
            y = nn.Conv(self.filters * 4, (1, 1), use_bias=False,
                        dtype=self.dtype)(y)
            if residual.shape[-1] != self.filters * 4 or \
                    self.strides != (1, 1):
                residual = nn.Conv(self.filters * 4, (1, 1),
                                   self.strides, use_bias=False,
                                   dtype=self.dtype)(residual)
                residual = self.norm(relu=False)(residual)
            return self.norm(scale_init=nn.initializers.zeros)(
                y, residual)

    def block_factory(variant):
        if variant == "full":
            return R.BottleneckBlock
        from functools import partial
        return partial(AblatedBottleneck, ablate=variant)

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(args.batch, 224, 224, 3), jnp.bfloat16)
    y = jnp.asarray(rng.randint(0, 1000, size=(args.batch,)), jnp.int32)
    batch_data = {"x": x, "y": y}
    fetch = jax.jit(lambda v: v.astype(jnp.float32))

    def build_variant(variant):
        orig = R.BottleneckBlock
        R.BottleneckBlock = block_factory(variant)
        model = R.create_resnet50(num_classes=1000, dtype=jnp.bfloat16)
        variables = model.init(
            jax.random.PRNGKey(0),
            np.zeros((1, 224, 224, 3), np.float32), train=True)
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        tx = optax.sgd(0.1, momentum=0.9)
        opt_state = tx.init(params)

        def train_step(params, batch_stats, opt_state, batch):
            def loss(p):
                nll, new_state = R.resnet_loss_fn(
                    model, {"params": p, "batch_stats": batch_stats},
                    batch)
                return nll, new_state.get("batch_stats", batch_stats)
            (nll, new_stats), grads = jax.value_and_grad(
                loss, has_aux=True)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), new_stats,
                    opt_state, nll)

        step = jax.jit(train_step, donate_argnums=(0, 1, 2))
        arm = {"step": step, "state": [params, batch_stats, opt_state]}
        # Trace+compile happens at the first CALL, and ResNet resolves
        # the (patched) block class at trace time — warm while patched.
        p, bs, os_ = arm["state"]
        nll = None
        for _ in range(5):
            p, bs, os_, nll = step(p, bs, os_, batch_data)
        float(np.asarray(fetch(nll)))
        arm["state"] = [p, bs, os_]
        R.BottleneckBlock = orig
        return arm

    def window(arm, n):
        p, bs, os_ = arm["state"]
        step = arm["step"]
        t0 = time.perf_counter()
        nll = None
        for _ in range(n):
            p, bs, os_, nll = step(p, bs, os_, batch_data)
        float(np.asarray(fetch(nll)))
        arm["state"] = [p, bs, os_]
        return time.perf_counter() - t0

    if args.ab:
        va, vb = args.ab.split(",")
        arms = {v: build_variant(v) for v in (va, vb)}
        pairs = []
        for _ in range(args.rounds):
            ms = {}
            for v in (va, vb):
                t1 = window(arms[v], args.steps)
                t2 = window(arms[v], 2 * args.steps)
                ms[v] = max(t2 - t1, 1e-9) / args.steps * 1e3
            pairs.append((ms[va], ms[vb]))
            print(json.dumps({"round": len(pairs), va: round(ms[va], 2),
                              vb: round(ms[vb], 2)}), flush=True)
        med = lambda xs: float(np.median(xs))
        ma, mb = med([p[0] for p in pairs]), med([p[1] for p in pairs])
        print(json.dumps({
            "ab": args.ab, "median_" + va: round(ma, 2),
            "median_" + vb: round(mb, 2),
            "delta_ms": round(ma - mb, 2)}))
        return

    results = {}
    for variant in args.variants.split(","):
        arm = build_variant(variant)
        t1s, t2s = [], []
        for _ in range(args.windows):
            t1s.append(window(arm, args.steps))
            t2s.append(window(arm, 2 * args.steps))
        step_ms = max(min(t2s) - min(t1s), 1e-9) / args.steps * 1e3
        results[variant] = step_ms
        print(json.dumps({
            "variant": variant, "step_ms": round(step_ms, 2),
            "img_per_sec": round(args.batch / step_ms * 1e3, 1)}),
            flush=True)

    if "full" in results:
        base = results["full"]
        for v, t in results.items():
            if v != "full":
                print(json.dumps({
                    "delta_vs_full_ms": round(base - t, 2),
                    "variant": v,
                    "note": "in-situ fwd+bwd cost of the ablated "
                            "3x3 taps (minus the 1/9 1x1 remnant)"}))


if __name__ == "__main__":
    main()
