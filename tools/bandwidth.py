#!/usr/bin/env python
"""Allreduce bandwidth benchmark (ref: tools/bandwidth/measure.py).

Measures KVStore/collective bandwidth over the mesh with the reference's
formula ``2(n-1)/n * size / t`` (measure.py:138).

Modes:
- flat tensor sweep (``--size-mb``, possibly comma-separated)
- model-gradient-shaped workload (``--model resnet50_v1|alexnet|...``):
  allreduces one buffer per parameter with that model's REAL gradient
  shapes in one fused program — the reference's measure.py drives the
  kvstore with the model's actual param list likewise, which exposes
  small-tensor overheads a single big buffer hides.

Run with JAX_PLATFORMS=cpu and --xla_force_host_platform_device_count for
a virtual mesh, or on real chips for ICI numbers.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _model_grad_shapes(name):
    """Parameter shapes of a model-zoo network (gradient workload)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd
    from mxnet_tpu.gluon import model_zoo
    net = model_zoo.vision.get_model(name)
    net.initialize(mx.init.Xavier())
    with autograd.pause():
        net(nd.ones((1, 3, 224, 224)))
    return [tuple(p.data().shape)
            for _, p in sorted(net.collect_params().items())
            if p.grad_req != "null"]


def _measure_shapes(mesh, axis, shapes, iters):
    """Gradient-shaped sweep via the library harness; returns
    (GB/s/device, total_mb)."""
    import numpy as np
    from mxnet_tpu.parallel import measure_allreduce_bandwidth
    bw = measure_allreduce_bandwidth(mesh, axis=axis, iters=iters,
                                     shapes=shapes)
    total_mb = sum(4 * int(np.prod(s)) for s in shapes) / 1e6
    return bw, total_mb


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-mb", default="64",
                    help="flat tensor size(s), comma separated")
    ap.add_argument("--model", default=None,
                    help="use this model-zoo net's gradient shapes "
                         "instead of a flat tensor")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--axis", default="dp")
    ap.add_argument("--num-devices", type=int, default=0,
                    help="0 = all visible")
    args = ap.parse_args()

    import jax
    from mxnet_tpu.parallel import make_mesh, measure_allreduce_bandwidth

    n = args.num_devices or len(jax.devices())
    if n < 2:
        print(json.dumps({"metric": "allreduce_bandwidth", "value": 0.0,
                          "unit": "GB/s/device",
                          "note": "needs >=2 devices"}))
        return
    mesh = make_mesh({args.axis: n})

    if args.model:
        shapes = _model_grad_shapes(args.model)
        bw, mb = _measure_shapes(mesh, args.axis, shapes, args.iters)
        print(json.dumps({"metric": "allreduce_bandwidth",
                          "value": round(bw, 3), "unit": "GB/s/device",
                          "devices": n, "model": args.model,
                          "num_tensors": len(shapes),
                          "total_mb": round(mb, 2)}))
        return

    for size_mb in (float(s) for s in str(args.size_mb).split(",")):
        bw = measure_allreduce_bandwidth(mesh, size_mb=size_mb,
                                         axis=args.axis,
                                         iters=args.iters)
        print(json.dumps({"metric": "allreduce_bandwidth",
                          "value": round(bw, 3), "unit": "GB/s/device",
                          "devices": n, "size_mb": size_mb}))


if __name__ == "__main__":
    main()
