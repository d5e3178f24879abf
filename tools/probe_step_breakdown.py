"""Ablation ledger: where does the ResNet-50 step time actually go?

Round-4 probe data (tools/probe_lowbit_conv.py, median-slope method)
shows isolated bf16 convs sustaining ~170 TFLOP/s on this chip — far
above the ~30 TFLOP/s the full train step averages and above the round-2
"73 TF practical peak" (which the same flawed min-timing produced). So
the step is NOT conv-bound: this probe re-times the real bench under
op-registry ablations to attribute the gap.

Variants (each rerun of bench.run/run_inference under a patched op):
  base          unmodified
  bn_affine     BatchNorm uses running stats even in training (removes
                the batch-stats reduction passes, keeps normalize math)
  bn_off        BatchNorm = identity (removes ALL BN cost)
  relu_off      Activation = identity
  bn_relu_off   both off: the pure conv+add skeleton

Run on the TPU (slow: each variant is a fresh XLA compile; the
persistent compile cache makes REruns free):
    python tools/probe_step_breakdown.py [train|infer|both]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import contextlib


@contextlib.contextmanager
def patched(name, fn):
    from mxnet_tpu.ops.registry import get_op
    op = get_op(name)
    orig = op.fn
    op.fn = fn
    try:
        yield
    finally:
        op.fn = orig


def _variant(tag):
    import jax.numpy as jnp
    from mxnet_tpu.ops.registry import get_op
    orig_bn = get_op("BatchNorm").fn
    orig_act = get_op("Activation").fn

    def bn_affine(data, gamma, beta, mm, mv, **kw):
        kw["_training"] = False
        return orig_bn(data, gamma, beta, mm, mv, **kw)

    def bn_off(data, gamma, beta, mm, mv, **kw):
        return data, mm.astype(jnp.float32), mv.astype(jnp.float32)

    def act_off(data, act_type="relu"):
        return data

    stack = contextlib.ExitStack()
    if tag in ("bn_affine",):
        stack.enter_context(patched("BatchNorm", bn_affine))
    if tag in ("bn_off", "bn_relu_off"):
        stack.enter_context(patched("BatchNorm", bn_off))
    if tag in ("relu_off", "bn_relu_off"):
        stack.enter_context(patched("Activation", act_off))
    return stack


def main():
    what = sys.argv[1] if len(sys.argv) > 1 else "both"
    import bench
    bench._enable_compile_cache()
    variants = ["base", "bn_affine", "bn_off", "relu_off", "bn_relu_off"]
    results = {}
    for tag in variants:
        if what in ("train", "both"):
            with _variant(tag):
                try:
                    ips = bench.run(batch=256, k_steps=8)
                except Exception as e:
                    ips = None
                    print(f"train[{tag}] FAILED: {str(e)[:140]}")
            if ips:
                results[f"train_{tag}"] = ips
                print(f"RESULT train[{tag}]: {ips:.1f} img/s")
        if what in ("infer", "both"):
            with _variant(tag):
                try:
                    ips = bench.run_inference(batch=256)
                except Exception as e:
                    ips = None
                    print(f"infer[{tag}] FAILED: {str(e)[:140]}")
            if ips:
                results[f"infer_{tag}"] = ips
                print(f"RESULT infer[{tag}]: {ips:.1f} img/s")
    print("SUMMARY", results)


if __name__ == "__main__":
    main()
