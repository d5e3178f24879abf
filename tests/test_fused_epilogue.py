"""Fused conv-epilogue kernels: BN(+add)+ReLU Pallas path
(ops/pallas_kernels.py fused_bn_act, dispatched via
_contrib_fused_bn_relu / _contrib_fused_bn_add_relu and the gluon
FusedBatchNormReLU / FusedBatchNormAddReLU blocks).

Numeric contract proven here (interpret mode on CPU — the SAME kernel
code path the TPU compiles):
  - forward + full gradient parity (dx, dresidual, dgamma, dbeta) vs
    the composed BatchNorm -> add -> ReLU lowering, f32 tight and bf16
    at bf16 tolerance;
  - MXTPU_FUSED_EPILOGUE=0 falls back to the composed lowering and the
    flag lives in the jit-cache key (toggling takes effect);
  - the channel-last model-zoo ResNet uses the fused blocks, trains,
    and int8 BN-folding (quantize_net) still folds THROUGH them,
    preserving the relu / add+relu tails.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import registry as reg

RS = np.random.RandomState(7)
EPS = 1e-5


def _composed(x, res, g, b):
    """Reference: plain batch-stats BN -> add -> relu in f32."""
    import jax
    import jax.numpy as jnp
    c = x.shape[-1]
    x32 = x.astype(jnp.float32).reshape(-1, c)
    mean = x32.mean(axis=0)
    var = x32.var(axis=0)
    out = (x.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + EPS) * g + b
    if res is not None:
        out = out + res.astype(jnp.float32)
    return jnp.maximum(out, 0.0).astype(x.dtype), mean, var


@pytest.mark.parametrize("has_res", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_bn_act_forward_and_grad_parity(has_res, dtype):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import fused_bn_act

    n, h, w, c = 2, 7, 5, 9   # deliberately non-aligned shapes
    dt = jnp.dtype(dtype)
    x = jnp.asarray(RS.randn(n, h, w, c).astype(np.float32)).astype(dt)
    res = jnp.asarray(RS.randn(n, h, w, c).astype(np.float32)).astype(dt) \
        if has_res else None
    g = jnp.asarray((RS.rand(c) + 0.5).astype(np.float32))
    b = jnp.asarray(RS.randn(c).astype(np.float32))
    tol = dict(rtol=2e-5, atol=2e-5) if dtype == "float32" \
        else dict(rtol=2e-2, atol=2e-2)

    def fused(*args):
        if has_res:
            x_, r_, g_, b_ = args
            return fused_bn_act(x_, r_, g_, b_, EPS)
        x_, g_, b_ = args
        return fused_bn_act(x_, None, g_, b_, EPS)

    def ref(*args):
        if has_res:
            x_, r_, g_, b_ = args
            return _composed(x_, r_, g_, b_)
        x_, g_, b_ = args
        return _composed(x_, None, g_, b_)

    args = (x, res, g, b) if has_res else (x, g, b)
    of, mf, vf = fused(*args)
    orr, mr, vr = ref(*args)
    assert of.dtype == dt
    np.testing.assert_allclose(np.asarray(of, np.float32),
                               np.asarray(orr, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(mf), np.asarray(mr), **tol)
    np.testing.assert_allclose(np.asarray(vf), np.asarray(vr), **tol)

    dy = jnp.asarray(RS.randn(n, h, w, c).astype(np.float32)).astype(dt)
    _, vjp_f = jax.vjp(lambda *a: fused(*a)[0], *args)
    _, vjp_r = jax.vjp(lambda *a: ref(*a)[0], *args)
    names = ("dx", "dres", "dgamma", "dbeta") if has_res \
        else ("dx", "dgamma", "dbeta")
    for name, gf, gr in zip(names, vjp_f(dy), vjp_r(dy)):
        np.testing.assert_allclose(
            np.asarray(gf, np.float32), np.asarray(gr, np.float32),
            err_msg=name, **tol)


def test_fused_op_nonlast_axis_falls_back_and_matches():
    """axis != last (NCHW) can't use the Pallas tiling — the op must
    fall back to the composed lowering, same numerics."""
    opdef = reg.get_op("_contrib_fused_bn_relu")
    x = RS.randn(2, 5, 4, 4).astype(np.float32)
    g = (RS.rand(5) + 0.5).astype(np.float32)
    b = RS.randn(5).astype(np.float32)
    import jax.numpy as jnp
    out, mean, var = opdef.fn(
        jnp.asarray(x), jnp.asarray(g), jnp.asarray(b),
        jnp.zeros(5), jnp.ones(5), eps=EPS, axis=1, _training=True)
    xt = np.transpose(x, (0, 2, 3, 1))
    want, _, _ = _composed(jnp.asarray(xt), None, jnp.asarray(g),
                           jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out),
                               np.transpose(np.asarray(want), (0, 3, 1, 2)),
                               rtol=2e-5, atol=2e-5)


def test_flag_off_composed_fallback_matches(monkeypatch):
    """MXTPU_FUSED_EPILOGUE must actually switch lowerings (the flag
    is in the jit-cache key) and keep identical semantics; unset means
    the composed lowering."""
    opdef = reg.get_op("_contrib_fused_bn_add_relu")
    x = nd.array(RS.randn(2, 6, 6, 4).astype(np.float32))
    r = nd.array(RS.randn(2, 6, 6, 4).astype(np.float32))
    g = nd.array((RS.rand(4) + 0.5).astype(np.float32))
    b = nd.array(RS.randn(4).astype(np.float32))
    mm, mv = nd.zeros((4,)), nd.ones((4,))

    def run():
        with autograd.record():
            out = nd.contrib.fused_bn_add_relu(x, r, g, b, mm, mv,
                                               eps=EPS, axis=-1)
        return out[0].asnumpy()

    opdef._jit_cache.clear()
    monkeypatch.setenv("MXTPU_FUSED_EPILOGUE", "1")
    on = run()
    n_on = len(opdef._jit_cache)
    monkeypatch.setenv("MXTPU_FUSED_EPILOGUE", "0")
    off = run()
    assert len(opdef._jit_cache) > n_on, \
        "flag toggle did not create a new jit-cache entry (stale program)"
    np.testing.assert_allclose(on, off, rtol=2e-5, atol=2e-5)
    monkeypatch.delenv("MXTPU_FUSED_EPILOGUE")
    n_off = len(opdef._jit_cache)
    np.testing.assert_array_equal(run(), off)
    assert len(opdef._jit_cache) == n_off, "unset must mean composed"


@pytest.mark.parametrize("flag", ["0", "1"])
def test_gluon_fused_blocks_match_composed_blocks(flag, monkeypatch):
    monkeypatch.setenv("MXTPU_FUSED_EPILOGUE", flag)
    x = RS.randn(3, 8, 8, 6).astype(np.float32)
    res = RS.randn(3, 8, 8, 6).astype(np.float32)
    mx.random.seed(0)
    fused = nn.FusedBatchNormAddReLU(axis=-1)
    fused.initialize()
    bn = nn.BatchNorm(axis=-1)
    bn.initialize()
    xa, ra = nd.array(x), nd.array(res)
    xb, rb = nd.array(x), nd.array(res)
    xa.attach_grad(); ra.attach_grad()
    xb.attach_grad(); rb.attach_grad()
    with autograd.record():
        y1 = fused(xa, ra)
    y1.backward()
    with autograd.record():
        y2 = nd.Activation(bn(xb) + rb, act_type="relu")
    y2.backward()
    np.testing.assert_allclose(y1.asnumpy(), y2.asnumpy(), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(xa.grad.asnumpy(), xb.grad.asnumpy(),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(ra.grad.asnumpy(), rb.grad.asnumpy(),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(fused.gamma.grad().asnumpy(),
                               bn.gamma.grad().asnumpy(),
                               rtol=1e-4, atol=1e-4)
    # running stats updated identically
    np.testing.assert_allclose(fused.running_mean.data().asnumpy(),
                               bn.running_mean.data().asnumpy(),
                               rtol=1e-6, atol=1e-6)
    # inference mode (moving stats) parity
    y3 = fused(nd.array(x), nd.array(res))
    y4 = nd.Activation(bn(nd.array(x)) + nd.array(res), act_type="relu")
    np.testing.assert_allclose(y3.asnumpy(), y4.asnumpy(), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.heavy
def test_resnet_channel_last_uses_fused_blocks_and_trains(monkeypatch):
    """The bench model family adopts the fused epilogue blocks
    channel-last, here with their op lowered to the Pallas kernels (the
    composed default trains in tests/test_chip_smoke.py's rehearsal);
    channel-first keeps the composed structure (and the kernels' NHWC
    requirement never sees an NCHW tensor)."""
    monkeypatch.setenv("MXTPU_FUSED_EPILOGUE", "1")
    from mxnet_tpu.gluon.model_zoo.vision.resnet import (BottleneckV1,
                                                         get_resnet)
    from mxnet_tpu import gluon
    net = get_resnet(1, 50, layout="NHWC", classes=10)
    blocks = [b for _, _, b in _walk(net) if isinstance(b, BottleneckV1)]
    assert blocks and all(b._fused for b in blocks)
    n_fused = sum(isinstance(b, (nn.FusedBatchNormReLU,
                                 nn.FusedBatchNormAddReLU))
                  for _, _, b in _walk(net))
    assert n_fused == 3 * 16, n_fused  # 3 per bottleneck, 16 bottlenecks
    nchw = get_resnet(1, 50, layout="NCHW", classes=10)
    assert not any(isinstance(b, (nn.FusedBatchNormReLU,
                                  nn.FusedBatchNormAddReLU))
                   for _, _, b in _walk(nchw))
    # and it trains
    net.initialize(mx.init.Xavier())
    x = nd.array(RS.randn(2, 32, 32, 3).astype(np.float32))
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.01})
    with autograd.record():
        loss = (net(x) ** 2).mean()
    loss.backward()
    tr.step(2)
    assert np.isfinite(float(loss.asscalar()))


def _walk(block):
    for key, child in list(block._children.items()):
        yield block, key, child
        yield from _walk(child)


def test_int8_fold_preserves_fused_epilogues():
    """fold_batchnorm folds the fused blocks into the preceding conv and
    leaves the relu / add+relu tail behind — quantize_net keeps working
    on the fused channel-last ResNet (the bench int8-inference path)."""
    from mxnet_tpu.contrib.quantization import fold_batchnorm
    from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1
    mx.random.seed(0)
    net = resnet18_v1(layout="NHWC", classes=10)
    net.initialize(mx.init.Xavier())
    x = nd.array(RS.rand(2, 32, 32, 3).astype(np.float32))
    with autograd.pause():
        before = net(x).asnumpy()
    n = fold_batchnorm(net)
    assert n > 0
    with autograd.pause():
        after = net(x).asnumpy()
    # folding is exact at inference; tails (relu/add+relu) preserved
    np.testing.assert_allclose(before, after, rtol=1e-4, atol=1e-5)
