"""The TPU fast-training recipe, end to end.

Puts the round-2 performance machinery together on a ResNet-style
workload (ref: example/image-classification/train_imagenet.py, rebuilt
around what actually makes a TPU busy):

1. NHWC model layout (channels-last is the TPU conv layout),
2. ``SPMDTrainer.run_steps`` — K training steps fused into ONE XLA
   dispatch (lax.scan), amortizing per-dispatch host overhead and letting
   XLA overlap the optimizer update of step i with the forward of i+1,
3. ``io.DeviceStagingIter`` — async host->device staging one batch ahead,
4. optional activation remat (``remat=True`` / MXNET_BACKWARD_DO_MIRROR)
   for models that don't fit otherwise,
5. async checkpoints (``fault.CheckpointManager(async_write=True)``).

Run (any backend; on a virtual mesh use JAX_PLATFORMS=cpu with
--xla_force_host_platform_device_count=8):

    python examples/tpu_fast_training.py --batch-size 64 --fused-steps 4
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import fault, gluon, nd
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon.model_zoo.vision import get_model
from mxnet_tpu.io import DeviceStagingIter, NDArrayIter
from mxnet_tpu.parallel import SPMDTrainer


def synthetic_imagenet(n, image_size, classes, layout, seed=0):
    rs = np.random.RandomState(seed)
    shape = (n, image_size, image_size, 3) if layout == "NHWC" \
        else (n, 3, image_size, image_size)
    return (rs.rand(*shape).astype(np.float32),
            rs.randint(0, classes, n).astype(np.float32))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet18_v1")
    ap.add_argument("--layout", default="NHWC")
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--fused-steps", type=int, default=4)
    ap.add_argument("--image-size", type=int, default=64)
    ap.add_argument("--num-batches", type=int, default=16)
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--remat", action="store_true",
                    help="recompute activations in backward "
                         "(MXNET_BACKWARD_DO_MIRROR)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=8,
                    help="checkpoint every N outer batches")
    args = ap.parse_args()

    import jax.numpy as jnp
    mx.random.seed(0)
    net = get_model(args.model, layout=args.layout, classes=100)
    net.initialize(mx.init.Xavier())
    trainer = SPMDTrainer(
        net, gloss.SoftmaxCrossEntropyLoss(), mesh=None, optimizer="sgd",
        optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
        dtype=jnp.bfloat16 if args.dtype == "bfloat16" else None,
        remat=args.remat)

    K, B = args.fused_steps, args.batch_size
    X, Y = synthetic_imagenet(args.num_batches * K * B, args.image_size,
                              100, args.layout)
    # host iter -> async device staging one batch ahead
    it = DeviceStagingIter(NDArrayIter(X, Y, batch_size=K * B))

    cm = fault.CheckpointManager(args.ckpt_dir, async_write=True) \
        if args.ckpt_dir else None

    t0 = time.time()
    nstep = 0
    for i, batch in enumerate(it):
        data = batch.data[0].reshape((K, B) + batch.data[0].shape[1:])
        label = batch.label[0].reshape((K, B))
        losses = trainer.run_steps(data, label)  # ONE dispatch, K steps
        nstep += K
        if i % 4 == 0:
            print(f"batch {i}: loss {float(np.asarray(losses)[-1]):.3f}",
                  flush=True)
        if cm is not None and i % args.ckpt_every == \
                args.ckpt_every - 1:
            cm.save(nstep, net=net)  # file IO overlaps training
    dt = time.time() - t0
    print(f"{nstep} steps, {nstep * B / dt:.0f} img/s "
          f"({dt / nstep * 1000:.1f} ms/step incl. first compile)")
    if cm is not None:
        cm.wait()
        print("checkpoints:", cm.steps())


if __name__ == "__main__":
    main()
