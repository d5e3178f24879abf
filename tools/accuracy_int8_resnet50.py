"""Model-scale int8 accuracy evidence on the REAL chip: train the bench
ResNet-50 (bf16 NHWC b256x16 — byte-identical program shapes to
bench.py, so the XLA compile cache is hot) to convergence on a 10-class
texture task, quantize it with the calibrated int8 flow (quantize_net:
BN fold -> per-channel int8 weights -> entropy-calibrated activation
scales), and report held-out top-1 of bf16 vs int8 plus their
prediction-agreement rate — the accuracy row that makes the int8
throughput rows in BENCH/README meaningful (VERDICT r4 directive #4;
ref: python/mxnet/contrib/quantization.py + the accuracy comparison in
example/quantization/imagenet_inference.py).

Data: oriented-grating textures (see examples/quantization/
quantize_resnet.py — class-specific orientation/frequency/color with
phase/contrast jitter and noise), the zero-egress ImageNet stand-in;
labels use classes 0-9 of the 1000-way head so every program shape
matches the bench exactly.

Run on the TPU:  python tools/accuracy_int8_resnet50.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "examples", "quantization"))
from quantize_resnet import make_batch as _texture_batch  # noqa: E402

CLASSES = 10
IMG = 224


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def make_batch(rs, n):
    # the SAME task definition as examples/quantization/quantize_resnet
    # .py, at ImageNet scale
    return _texture_batch(rs, n, size=IMG, classes=CLASSES)


def main():
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    import mxnet_tpu as mx
    from mxnet_tpu.cached_op import make_scan_forward
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    from mxnet_tpu.parallel import SPMDTrainer
    from mxnet_tpu.util import enable_compile_cache

    enable_compile_cache()
    log(f"devices: {jax.devices()}")
    mx.random.seed(0)
    net = resnet50_v1(layout="NHWC", stem_s2d=True)
    net.initialize(mx.init.Xavier())
    trainer = SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                          mesh=None, optimizer="sgd",
                          optimizer_params={"learning_rate": 0.05,
                                            "momentum": 0.9},
                          dtype=jnp.bfloat16)

    rs = np.random.RandomState(0)
    k, batch = 16, 256
    accel = jax.devices()[0]
    cpu = jax.devices("cpu")[0]
    ckpt = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "_resnet50_textures_params.npz")
    if os.path.exists(ckpt):
        # trained-params checkpoint from a previous run: skip the train
        loaded = dict(np.load(ckpt))
        with jax.default_device(cpu):
            net(mx.nd.from_jax(jnp.asarray(
                np.zeros((1, IMG, IMG, 3), np.float32), device=cpu)))
        dst = sorted(net.collect_params().items())
        assert len(dst) == len(loaded), \
            (f"stale checkpoint {ckpt}: {len(loaded)} arrays vs "
             f"{len(dst)} params — delete it and re-train")
        for (name, p), key in zip(dst, sorted(loaded)):
            a = loaded[key]
            assert tuple(p.shape) == a.shape, \
                (f"stale checkpoint {ckpt}: {name} {p.shape} vs "
                 f"{a.shape} — delete it and re-train")
            p._data._rebind(jax.device_put(jnp.asarray(a), cpu))
        log(f"loaded trained params from {ckpt}")
    else:
        xs, ys = make_batch(rs, k * batch)
        data = jnp.asarray(xs.reshape(k, batch, IMG, IMG, 3))
        label = jnp.asarray(ys.reshape(k, batch).astype(np.float32))
        t0 = time.time()
        losses = np.asarray(trainer.run_steps(data, label))
        log(f"first dispatch (compile) {time.time() - t0:.0f}s "
            f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
        for rep in range(14):
            losses = trainer.run_steps(data, label)
        losses = np.asarray(losses)
        log(f"trained 240 steps; final loss {losses[-1]:.4f}")

    f32_params = {}
    for name, p in net.collect_params().items():
        a = p._data._data
        f32_params[name] = np.asarray(jax.device_put(a, cpu),
                                      np.float32)
    if not os.path.exists(ckpt):
        np.savez(ckpt, **{f"{i:03d}": f32_params[k2] for i, k2 in
                          enumerate(sorted(f32_params))})
        log(f"saved trained params to {ckpt}")

    # ---- bf16 eval (the bench inference program: scanned 8x256) -------

    def place_on_accel(block):
        """bench.py's placement policy: quantized blocks keep int8
        weights + f32 scales/biases; every other f32 param goes bf16."""
        from mxnet_tpu.contrib.quantization import (_QuantizedLayer,
                                                    _walk_blocks)
        qids = set()
        for _, _, blk in _walk_blocks(block):
            if isinstance(blk, _QuantizedLayer):
                qids.update(id(p) for _, p in
                            blk.collect_params().items())
        for _, p in block.collect_params().items():
            if p._data is not None:
                a = p._data._data
                if a.dtype == jnp.float32 and id(p) not in qids:
                    a = a.astype(jnp.bfloat16)
                p._data._rebind(jax.device_put(a, accel))

    test_rs = np.random.RandomState(777)
    xte, yte = make_batch(test_rs, 8 * 256)
    host = xte.reshape(8, 256, IMG, IMG, 3).astype(ml_dtypes.bfloat16)
    xs_dev = jax.device_put(jnp.asarray(host), accel)

    place_on_accel(net)
    fwd = make_scan_forward(net)
    t0 = time.time()
    out_f = np.asarray(fwd(xs_dev)._data, np.float32)
    log(f"bf16 eval (incl compile) {time.time() - t0:.0f}s")
    pred_f = out_f.reshape(-1, out_f.shape[-1]).argmax(axis=1)
    top1_f = float((pred_f == yte).mean())

    # ---- quantize ON HOST (eager per-block calib on the chip would
    # pay a dispatch and a compile per op) then eval int8 on the chip. Sweep the
    # calibration configurations so a collapse localizes ---------------
    from mxnet_tpu.contrib.quantization import quantize_net

    def restore_f32():
        """Fresh net carrying the TRAINED f32 params (fresh because
        quantize_net mutates in place). Parameter names differ only by
        the per-instance name prefix, so align by sorted order."""
        fresh = resnet50_v1(layout="NHWC", stem_s2d=True)
        fresh.initialize(mx.init.Xavier())
        with jax.default_device(cpu):
            fresh(mx.nd.from_jax(jnp.asarray(
                np.zeros((1, IMG, IMG, 3), np.float32), device=cpu)))
        src = [f32_params[k] for k in sorted(f32_params)]
        dst = [p for _, p in sorted(fresh.collect_params().items())]
        assert len(src) == len(dst)
        for a, p in zip(src, dst):
            assert tuple(p.shape) == a.shape, (p.name, p.shape, a.shape)
            p._data._rebind(jax.device_put(jnp.asarray(a), cpu))
        return fresh

    configs = [
        ("entropy", (), 4, 2),
        ("naive", (), 4, 2),
        ("naive", ("dense",), 4, 2),
        # conv2d0 is the (space-to-depth) stem conv — the reference's
        # standard first-conv exclusion
        ("naive", ("dense", "conv2d0"), 4, 2),
        ("naive", (), 16, 8),
    ]
    class ScaleLog:
        """Captures quantize_net's per-layer 'quantized <name>
        (in_scale=...)' lines so calibration modes can be diffed."""

        def __init__(self):
            self.scales = {}

        def info(self, fmt, *args):
            if "in_scale" in fmt and len(args) == 2:
                # strip the per-instance net prefix for cross-net diffs
                self.scales[str(args[0]).split("_", 2)[-1]] = \
                    float(args[1])

    results = []
    mode_scales = {}
    for mode, exclude, n_batches, bsz in configs:
        fresh = restore_f32()
        calib_rs = np.random.RandomState(555)
        slog = ScaleLog()
        with jax.default_device(cpu):
            calib = [mx.nd.from_jax(jnp.asarray(
                make_batch(calib_rs, bsz)[0], device=cpu))
                for _ in range(n_batches)]
            t0 = time.time()
            qnet = quantize_net(fresh, calib, calib_mode=mode,
                                exclude=exclude, logger=slog)
            log(f"quantize_net {mode} exclude={exclude} "
                f"({n_batches}x{bsz}) {time.time() - t0:.0f}s")
        mode_scales[(mode, exclude, n_batches * bsz)] = slog.scales
        place_on_accel(qnet)
        fwd_q = make_scan_forward(qnet)
        t0 = time.time()
        out_q = np.asarray(fwd_q(xs_dev)._data, np.float32)
        pred_q = out_q.reshape(-1, out_q.shape[-1]).argmax(axis=1)
        top1_q = float((pred_q == yte).mean())
        agree = float((pred_q == pred_f).mean())
        log(f"  -> top1 {top1_q:.4f} agree {agree:.4f} "
            f"({time.time() - t0:.0f}s)")
        results.append((mode, exclude, n_batches * bsz, top1_q, agree))

    # scale diff: where does entropy clip relative to naive-absmax?
    ent = mode_scales.get(("entropy", (), 8))
    nai = mode_scales.get(("naive", (), 8))
    if ent and nai:
        ratios = sorted(((nai[k] / max(ent[k], 1e-12), k)
                         for k in ent if k in nai), reverse=True)
        log("largest naive/entropy scale ratios (entropy clips here):")
        for r, k in ratios[:12]:
            log(f"  {k:28s} naive {nai[k]:10.5g} entropy {ent[k]:10.5g} "
                f"ratio {r:6.2f}")

    best = max(results, key=lambda r: r[3])
    for mode, exclude, n, t1, ag in results:
        print(f"CONFIG {mode} exclude={','.join(exclude) or '-'} "
              f"calib_n={n} top1_int8 {t1:.4f} agree {ag:.4f}")
    print(f"RESNET50_INT8_ACCURACY top1_bf16 {top1_f:.4f} "
          f"top1_int8 {best[3]:.4f} delta {top1_f - best[3]:.4f} "
          f"agreement {best[4]:.4f} n {len(yte)} "
          f"best_config {best[0]}/{','.join(best[1]) or '-'}/{best[2]}")


if __name__ == "__main__":
    main()
