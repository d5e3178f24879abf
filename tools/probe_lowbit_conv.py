"""Probe: do fp8/int8 convolutions run faster than bf16 on this chip for
representative ResNet-50 layer shapes?

HBM-traffic hypothesis (round-4): the train step is bandwidth-bound on
activation bytes (round-3 ledger), so halving the bytes the convs READ
(fp8/int8 inputs) should cut wall time even though this v5e has no faster
fp8 MXU path (round-2 finding: fp8 matmul == bf16 speed).

Methodology:
- the conv is scanned over K DISTINCT weight tensors so XLA cannot hoist
  it out of the loop (a scan body with loop-invariant operands gets
  LICM'd and you measure nothing);
- per-conv time is the SLOPE between a K_hi and K_lo dispatch, which
  cancels the fixed dispatch overhead;
- a "read x" row (scalar-scaled reduction of x per iteration) gives the
  pure-bandwidth roofline for each input size.

Run on the TPU:  python tools/probe_lowbit_conv.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

# the slope must rise above per-dispatch jitter: 192 extra conv
# applications at ~0.3-2 ms each gives a 60-400 ms signal
K_LO, K_HI = 8, 200

# (N, H, W, Cin, kernel, Cout, stride) — the three ResNet-50 traffic hogs
# plus a stride-2 3x3 (NHWC).
SHAPES = [
    (256, 56, 56, 64, 1, 64, 1),
    (256, 56, 56, 256, 1, 64, 1),
    (256, 28, 28, 128, 3, 128, 1),
    (256, 14, 14, 256, 3, 256, 1),
    (256, 28, 28, 256, 3, 256, 2),
]


def conv(x, w, stride):
    dn = lax.conv_dimension_numbers(x.shape, w.shape, ("NHWC", "HWIO", "NHWC"))
    k = w.shape[0]
    pet = jnp.int32 if jnp.issubdtype(x.dtype, jnp.integer) else jnp.float32
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=[(k // 2, k // 2)] * 2, dimension_numbers=dn,
        preferred_element_type=pet)


def dispatch_time(fn, *args):
    """Median wall time of dispatch + SCALAR FETCH.

    The sync is fetching the result to host; its fixed cost is
    cancelled by the K-slope."""
    f = jax.jit(fn)
    float(f(*args))  # compile + sync
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        float(f(*args))
        ts.append(time.perf_counter() - t0)
    ts = sorted(ts)
    return ts[len(ts) // 2]


def bench(name, x, ws, stride, flops):
    def run(x, ws):
        def body(acc, w):
            y = conv(x, w, stride)
            # NONLINEAR consumer: a linear reduction (mean/sum) of a conv
            # is algebraically factored through the conv by XLA's
            # simplifier (reduce(conv(x,w)) -> dot(reduce-window(x),
            # reduce(w))) and the conv never executes; squaring blocks
            # the rewrite
            y32 = y.astype(jnp.float32)
            return acc + (y32 * y32).mean(), None
        return lax.scan(body, jnp.float32(0), ws)[0]

    try:
        t_hi = dispatch_time(run, x, ws)
        t_lo = dispatch_time(run, x, ws[:K_LO])
    except Exception as e:
        print(f"  {name:10s} FAILED: {str(e)[:110]}")
        return
    ms = (t_hi - t_lo) / (K_HI - K_LO) * 1e3
    mb = x.size * x.dtype.itemsize / 1e6
    tf = flops / (ms * 1e-3) / 1e12 if ms > 0 else float("nan")
    print(f"  {name:10s} {ms:7.3f} ms/conv  x-bytes {mb:7.1f} MB  "
          f"{tf:6.1f} TFLOP/s")


def bench_read(x):
    """Pure x-read roofline: per-iteration scalar-weighted reduction."""
    scal = jnp.arange(1.0, K_HI + 1, dtype=jnp.float32)

    def run(x, scal):
        def body(acc, s):
            v = x.astype(jnp.float32) + s  # +s defeats hoisting,
            return acc + (v * v).mean(), None  # squaring defeats factoring
        return lax.scan(body, jnp.float32(0), scal)[0]

    t_hi = dispatch_time(run, x, scal)
    t_lo = dispatch_time(run, x, scal[:K_LO])
    ms = (t_hi - t_lo) / (K_HI - K_LO) * 1e3
    mb = x.size * x.dtype.itemsize / 1e6
    bw = mb / 1e3 / (ms * 1e-3) if ms > 0 else float("nan")
    print(f"  {'read-x':10s} {ms:7.3f} ms/iter  x-bytes {mb:7.1f} MB  "
          f"{bw:6.0f} GB/s")


def main():
    print("devices:", jax.devices())
    for (n, h, w, cin, k, cout, stride) in SHAPES:
        rs = np.random.RandomState(0)
        xf = rs.rand(n, h, w, cin).astype(np.float32)
        wf = (rs.rand(K_HI, k, k, cin, cout) - 0.5).astype(np.float32) * 0.1
        flops = 2.0 * n * (h // stride) * (w // stride) * k * k * cin * cout
        print(f"conv N{n} {h}x{w}x{cin} -> k{k}s{stride} -> {cout} "
              f"({flops/1e9:.1f} GFLOP)")
        x16, w16 = jnp.asarray(xf, jnp.bfloat16), jnp.asarray(wf, jnp.bfloat16)
        bench("bf16", x16, w16, stride, flops)
        bench_read(x16)
        bench("fp8e4m3", jnp.asarray(xf).astype(jnp.float8_e4m3fn),
              jnp.asarray(wf * 20).astype(jnp.float8_e4m3fn), stride, flops)
        bench("int8", jnp.asarray(xf * 100).astype(jnp.int8),
              jnp.asarray(wf * 500).astype(jnp.int8), stride, flops)


if __name__ == "__main__":
    main()
