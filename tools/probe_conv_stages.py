"""Localize the ResNet-50 conv gap: isolated convs sustain ~190 TFLOP/s
(probe_lowbit_conv) but the conv-only model skeleton still takes the full
~104 ms/step (BN/ReLU ablations change nothing).

This probe times each ResNet-50 STAGE as a pure-conv chain — forward and
forward+backward — by the methodology of probe_lowbit_conv:
K-scan with a FETCHED scalar, slope between two K values, median reps.

Run on the TPU:  python tools/probe_conv_stages.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

B = 256
K_LO, K_HI = 2, 10

# ResNet-50 v1 NHWC: (H_in, C_in, kernel, stride, C_out) per conv,
# grouped by stage. Bottleneck: 1x1 -> 3x3(stride) -> 1x1x4 (+ 1x1
# projection on the first block of each stage).
def bottleneck(h, cin, mid, stride):
    out = []
    out.append((h, cin, 1, 1, mid))
    out.append((h, mid, 3, stride, mid))
    out.append((h // stride, mid, 1, 1, mid * 4))
    out.append((h, cin, 1, stride, mid * 4))  # projection
    return out


def stage(h, cin, mid, blocks, stride):
    convs = bottleneck(h, cin, mid, stride)
    for _ in range(blocks - 1):
        convs += bottleneck(h // stride, mid * 4, mid, 1)[:3]
    return convs


STAGES = {
    "stem": [(224, 3, 7, 2, 64)],
    "s1": stage(56, 64, 64, 3, 1),
    "s2": stage(56, 256, 128, 4, 2),
    "s3": stage(28, 512, 256, 6, 2),
    "s4": stage(14, 1024, 512, 3, 2),
}


def conv(x, w, stride):
    dn = lax.conv_dimension_numbers(x.shape, w.shape, ("NHWC", "HWIO", "NHWC"))
    k = w.shape[0]
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=[(k // 2, k // 2)] * 2, dimension_numbers=dn)


def fetch_time(f, *args):
    float(f(*args))  # compile + sync
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        float(f(*args))
        ts.append(time.perf_counter() - t0)
    ts = sorted(ts)[1:-1]
    return sum(ts) / len(ts)


def time_stage(name, convs, grad):
    rs = np.random.RandomState(0)
    h0, c0 = convs[0][0], convs[0][1]
    x0 = jnp.asarray(rs.rand(B, h0, h0, c0).astype(np.float32),
                     dtype=jnp.bfloat16)
    ws = [jnp.asarray(((rs.rand(k, k, cin, cout) - 0.5) * 0.1)
                      .astype(np.float32), dtype=jnp.bfloat16)
          for (_h, cin, k, s, cout) in convs]
    flops = sum(2.0 * B * (h // s) * (h // s) * k * k * cin * cout
                for (h, cin, k, s, cout) in convs)

    # rebuilding the exact bottleneck wiring is overkill for a TIMING
    # probe: what matters is executing exactly these conv shapes (and
    # their dX/dW counterparts). Run them as independent applications.
    xs = [jnp.asarray(rs.rand(B, h, h, cin).astype(np.float32),
                      dtype=jnp.bfloat16)
          for (h, cin, k, s, cout) in convs]

    def run_all(xs, ws, seed):
        acc = jnp.float32(0)
        for (spec, x, w) in zip(convs, xs, ws):
            y = conv(x + seed.astype(x.dtype), w, spec[3])
            y32 = y.astype(jnp.float32)
            acc = acc + (y32 * y32).mean()
        return acc

    if grad:
        def loss(ws, xs, seed):
            return run_all(xs, ws, seed)

        def body(carry, seed):
            gw, gx = jax.grad(loss, argnums=(0, 1))(ws, xs, seed)
            leaf = sum(g.astype(jnp.float32).mean() for g in gw) \
                + sum(g.astype(jnp.float32).mean() for g in gx)
            return carry + leaf, None
    else:
        def body(carry, seed):
            return carry + run_all(xs, ws, seed), None

    def scan_k(seeds):
        return lax.scan(body, jnp.float32(0), seeds)[0]

    f = jax.jit(scan_k)
    seeds = jnp.arange(K_HI, dtype=jnp.float32) * 1e-6
    t_hi = fetch_time(f, seeds)
    t_lo = fetch_time(f, seeds[:K_LO])
    ms = (t_hi - t_lo) / (K_HI - K_LO) * 1e3
    eff_flops = flops * (3.0 if grad else 1.0)
    tf = eff_flops / (ms * 1e-3) / 1e12 if ms > 0 else float("nan")
    print(f"  {name:5s} {'fwd+bwd' if grad else 'fwd    '} "
          f"{ms:8.2f} ms  {eff_flops/1e9:7.1f} GFLOP  {tf:6.1f} TFLOP/s",
          flush=True)
    return ms


def main():
    print("devices:", jax.devices(), flush=True)
    total_f, total_g = 0.0, 0.0
    for name, convs in STAGES.items():
        total_f += time_stage(name, convs, grad=False)
        total_g += time_stage(name, convs, grad=True)
    print(f"TOTAL fwd {total_f:.1f} ms, fwd+bwd {total_g:.1f} ms "
          f"(train step measures ~104 ms)")


if __name__ == "__main__":
    main()
