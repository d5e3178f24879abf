"""Model zoo tests (ref: tests/python/unittest/test_gluon_model_zoo.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon.model_zoo.vision import get_model


def test_get_model_names():
    for name in ["resnet18_v1", "vgg11", "squeezenet1.0", "mobilenet0.25",
                 "densenet121", "inceptionv3", "alexnet"]:
        net = get_model(name, classes=10)
        assert net is not None


@pytest.mark.heavy
def test_inception_v3_forward():
    net = get_model("inceptionv3", classes=10)
    net.initialize()
    x = mx.nd.array(np.random.rand(1, 3, 299, 299).astype(np.float32))
    assert net(x).shape == (1, 10)


@pytest.mark.heavy
def test_mobilenet_v2_width_variants():
    """All four MobileNetV2 width multipliers (reference zoo parity);
    the multiplier must actually shrink the stem conv channels."""
    for name, mult in [("mobilenetv2_1.0", 1.0), ("mobilenetv2_0.75", .75),
                       ("mobilenetv2_0.5", 0.5), ("mobilenetv2_0.25", .25)]:
        net = get_model(name, classes=10)
        net.initialize()
        x = mx.nd.array(np.random.rand(1, 3, 32, 32).astype(np.float32))
        assert net(x).shape == (1, 10), name
        stem = [p for n, p in sorted(net.collect_params().items())
                if "weight" in n][0]
        assert stem.data().shape[0] == int(32 * mult), (name, stem.shape)


def test_space_to_depth_stem_exact_reparametrization():
    """SpaceToDepthStem == 7x7/2 pad-3 conv with the kernel embedded in
    the rearranged basis (the MLPerf stem trick; see resnet.py docstring).
    Accuracy-neutral by construction: verified numerically here."""
    from mxnet_tpu.gluon.model_zoo.vision.resnet import SpaceToDepthStem
    rs = np.random.RandomState(0)
    B, H, W, O = 2, 32, 32, 5
    x = rs.rand(B, H, W, 3).astype(np.float32)
    w7 = rs.randn(O, 7, 7, 3).astype(np.float32)
    ref = nd.op.Convolution(nd.array(x), nd.array(w7), kernel=(7, 7),
                            stride=(2, 2), pad=(3, 3), num_filter=O,
                            no_bias=True, layout="NHWC").asnumpy()
    # embed into 8x8 (zero row/col at top/left: window [2i-4, 2i+3]) and
    # pack kernel position (2a+dy, 2b+dx, c) -> (a, b, dy*6+dx*3+c)
    w8 = np.zeros((O, 8, 8, 3), np.float32)
    w8[:, 1:, 1:, :] = w7
    w4 = np.zeros((O, 4, 4, 12), np.float32)
    for a in range(4):
        for b in range(4):
            for dy in range(2):
                for dx in range(2):
                    w4[:, a, b, dy * 6 + dx * 3:dy * 6 + dx * 3 + 3] = \
                        w8[:, 2 * a + dy, 2 * b + dx, :]
    stem = SpaceToDepthStem(O, layout="NHWC")
    stem.initialize()
    stem.conv.weight.data()._rebind(nd.array(w4)._data)
    out = stem(nd.array(x)).asnumpy()
    np.testing.assert_allclose(out, ref, atol=2e-4)


@pytest.mark.heavy
def test_resnet50_s2d_trains():
    from mxnet_tpu import autograd
    # s2d variant builds, runs forward/backward at thumbnail-free shape
    from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1
    net = resnet18_v1(layout="NHWC", stem_s2d=True)
    net.initialize(mx.init.Xavier())
    x = nd.array(np.random.rand(2, 64, 64, 3).astype(np.float32))
    with autograd.record():
        out = net(x)
        out.sum().backward()
    assert out.shape == (2, 1000)


def test_make_scan_forward_matches_eager():
    """K-batch scanned inference (mxnet_tpu.cached_op.make_scan_forward)
    equals per-batch eager forwards — the serving-pattern API bench.py
    measures with."""
    import jax.numpy as jnp
    from mxnet_tpu.cached_op import make_scan_forward
    from mxnet_tpu.gluon import nn as gnn
    net = gnn.HybridSequential()
    net.add(gnn.Dense(8, activation="relu"), gnn.Dense(3))
    net.initialize(mx.init.Xavier())
    xs = np.random.RandomState(0).rand(4, 5, 6).astype(np.float32)
    net(nd.array(xs[0]))  # materialize
    fwd_k = make_scan_forward(net)
    out = fwd_k(jnp.asarray(xs))
    assert out.shape == (4, 5, 3)
    for k in range(4):
        ref = net(nd.array(xs[k])).asnumpy()
        np.testing.assert_allclose(out.asnumpy()[k], ref, rtol=1e-5,
                                   atol=1e-5)
