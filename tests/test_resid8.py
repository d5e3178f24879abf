"""8-bit activation-residual training mode (MXNET_RESID_DTYPE, ops/resid8.py).

The mode stores backward residuals fp8: dx must stay EXACT for convs
(backward-input needs only weights), dW and BN param grads see only small
zero-mean rounding noise, and toggling the env flag must actually change
the compiled kernels (trace-time flags are part of every jit-cache key).
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.gluon import nn, loss as gloss

RS = np.random.RandomState(7)


@pytest.fixture
def fp8_mode():
    os.environ["MXNET_RESID_DTYPE"] = "fp8"
    try:
        yield
    finally:
        os.environ["MXNET_RESID_DTYPE"] = ""


def _convnet():
    np.random.seed(0)
    mx.random.seed(0)
    net = nn.HybridSequential(prefix="")
    net.add(nn.Conv2D(8, 3, padding=1, use_bias=False, in_channels=3,
                      layout="NHWC"))
    net.add(nn.BatchNorm(axis=-1))
    net.add(nn.Activation("relu"))
    net.add(nn.Conv2D(16, 3, padding=1, use_bias=False, in_channels=8,
                      layout="NHWC"))
    net.add(nn.BatchNorm(axis=-1))
    net.add(nn.Activation("relu"))
    net.add(nn.GlobalAvgPool2D(layout="NHWC"))
    net.add(nn.Dense(5))
    net.initialize(mx.init.Xavier())
    return net


def _grads(hybridize=False):
    x = np.random.RandomState(1).rand(8, 12, 12, 3).astype(np.float32)
    y = np.random.RandomState(2).randint(0, 5, 8).astype(np.float32)
    net = _convnet()
    if hybridize:
        net.hybridize()
    lossfn = gloss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = lossfn(net(mx.nd.array(x)), mx.nd.array(y))
    loss.backward()
    # by declaration, not by name: names carry a process-wide counter
    # ("conv10" sorts before "conv9"), so two nets can sort differently
    grads = [p.grad().asnumpy() for p in net.collect_params().values()
             if p.grad_req != "null"]
    return float(loss.mean().asnumpy()), grads


def test_conv_dx_exact_dw_noisy():
    """dx needs only weights (exact); dW reads the fp8 input (small,
    nonzero rounding error) — the defining property of the mode."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import resid8

    x = jnp.asarray(RS.rand(2, 6, 6, 3).astype(np.float32))
    w = jnp.asarray((RS.rand(4, 3, 3, 3) - 0.5).astype(np.float32))
    dy = jnp.asarray(RS.rand(2, 6, 6, 4).astype(np.float32))

    def plain(d, ww):
        dn = jax.lax.conv_dimension_numbers(
            d.shape, ww.shape, ("NHWC", "OHWI", "NHWC"))
        return jax.lax.conv_general_dilated(
            d, ww, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn)

    def r8(d, ww):
        return resid8.conv_resid8(d, ww, (1, 1), (1, 1), (1, 1),
                                  ("NHWC", "OHWI", "NHWC"), 1,
                                  "float8_e4m3fn")

    _, vjp0 = jax.vjp(plain, x, w)
    _, vjp8 = jax.vjp(r8, x, w)
    (dx0, dw0), (dx8, dw8) = vjp0(dy), vjp8(dy)
    assert float(jnp.abs(dx0 - dx8).max()) == 0.0
    rel = float(jnp.abs(dw0 - dw8).max() / jnp.abs(dw0).max())
    assert 1e-5 < rel < 0.05, rel


def test_outlier_activations_saturate_not_nan():
    """|x| > fp8-max (448 for e4m3) must clamp, not overflow: XLA's
    f32->fp8 cast rounds out-of-range values to NaN (e4m3fn) / inf
    (e5m2), and one NaN residual poisons dW for the whole layer and
    zeroes relu grads (NaN > 0 is False). Regression for the round-4
    advisor finding."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import resid8

    for rdt in ("float8_e4m3fn", "float8_e5m2"):
        big = float(jnp.finfo(jnp.dtype(rdt)).max) * 4.0
        x = jnp.asarray(RS.rand(2, 6, 6, 3).astype(np.float32)) * big
        w = jnp.asarray((RS.rand(4, 3, 3, 3) - 0.5).astype(np.float32))
        dy = jnp.ones((2, 6, 6, 4), np.float32)

        # conv residual: dW must be finite and ~match the exact dW
        _, vjp8 = jax.vjp(
            lambda d, ww: resid8.conv_resid8(
                d, ww, (1, 1), (1, 1), (1, 1),
                ("NHWC", "OHWI", "NHWC"), 1, rdt), x, w)
        dx8, dw8 = vjp8(dy)
        assert np.isfinite(np.asarray(dw8)).all(), rdt
        assert np.isfinite(np.asarray(dx8)).all(), rdt

        # relu residual: grads where y > fp8-max must pass dy, not zero
        _, vr = jax.vjp(lambda v: resid8.relu_resid8(v, rdt),
                        jnp.full((8,), big, jnp.float32))
        assert np.asarray(vr(jnp.ones(8, np.float32))[0]).min() == 1.0

        # BN xhat residual (ops/nn.py fwd): xhat is normalized so its
        # max is ~sqrt(N) for a lone spike among N elements — use
        # N > fp8_max^2 per channel so the spike's xhat overflows fp8
        if rdt == "float8_e4m3fn":  # e5m2 max is 57344: N would be 3e9
            from mxnet_tpu.ops.nn import _make_bn_core
            core = _make_bn_core(rdt)
            xnp = np.zeros((1, 500, 500, 2), np.float32)  # N=250k > 448^2
            xnp[0, 0, 0, :] = 1e6
            xb = jnp.asarray(xnp)

            def f(d):
                out, _, _ = core(d, jnp.ones(2, jnp.float32),
                                 jnp.zeros(2, jnp.float32), 3, 1e-5)
                return out
            out, vb = jax.vjp(f, xb)
            # confirm the construction actually exceeds the fp8 range
            assert float(jnp.abs(out).max()) > 448.0
            assert np.isfinite(
                np.asarray(vb(jnp.ones_like(xb))[0])).all(), rdt


def test_relu_mask_from_fp8_copy():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import resid8

    x = jnp.asarray((RS.rand(64) - 0.5).astype(np.float32))
    dy = jnp.asarray(RS.rand(64).astype(np.float32))
    _, v0 = jax.vjp(lambda v: jnp.maximum(v, 0), x)
    _, v8 = jax.vjp(lambda v: resid8.relu_resid8(v, "float8_e4m3fn"), x)
    # mask survives the fp8 round-trip bit-exactly away from denormals
    assert float(jnp.abs(v0(dy)[0] - v8(dy)[0]).max()) == 0.0


def test_bn_core_fp8_residual_close():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.nn import _make_bn_core

    # a stream of its own: the module's RS stands wherever the tests that
    # ran before this one in the same worker left it, and dgamma is a sum
    # that nearly cancels, so its relative error swings with the draw
    rs = np.random.RandomState(0)
    xb = jnp.asarray(rs.rand(8, 6, 6, 5).astype(np.float32) * 3 + 1)
    g32 = jnp.asarray(rs.rand(5).astype(np.float32) + 0.5)
    b32 = jnp.asarray(rs.rand(5).astype(np.float32))
    dyb = jnp.asarray((rs.rand(8, 6, 6, 5) - 0.5).astype(np.float32))

    def run(core):
        def f(d, g, b):
            out, _, _ = core(d, g, b, 3, 1e-5)
            return out
        _, vjp = jax.vjp(f, xb, g32, b32)
        return vjp(dyb)

    exact = run(_make_bn_core(None))
    quant = run(_make_bn_core("float8_e4m3fn"))
    for a, b in zip(exact, quant):
        rel = float(jnp.abs(a - b).max() / (jnp.abs(a).max() + 1e-9))
        assert rel < 0.05, rel


def test_net_grads_close_and_env_actually_switches(fp8_mode):
    """Whole-net grads under fp8 residuals stay within a few percent of
    exact AND genuinely differ (regression: trace-time env flags must be
    in the op/vjp jit-cache keys, else toggling is a silent no-op)."""
    os.environ["MXNET_RESID_DTYPE"] = ""
    l0, g0 = _grads()
    os.environ["MXNET_RESID_DTYPE"] = "fp8"
    l8, g8 = _grads()
    assert abs(l0 - l8) < 1e-4  # forward is untouched
    diffs = [np.abs(a - b).max() / max(np.abs(a).max(), 1e-6)
             for a, b in zip(g0, g8)]
    assert max(diffs) > 1e-5, "fp8 mode did not engage (stale jit cache?)"
    # compare only params with non-degenerate gradients: exact-zero
    # cancellation grads (e.g. conv bias feeding BN) have no meaningful
    # relative error
    for a, b in zip(g0, g8):
        if np.abs(a).max() > 1e-4:
            rel = np.abs(a - b).max() / np.abs(a).max()
            assert rel < 0.1, rel


def test_eager_hybrid_agree_under_fp8(fp8_mode):
    l_e, g_e = _grads(hybridize=False)
    l_h, g_h = _grads(hybridize=True)
    assert abs(l_e - l_h) < 1e-4
    for a, b in zip(g_e, g_h):
        assert np.abs(a - b).max() / max(np.abs(a).max(), 1e-6) < 2e-2


def test_training_converges_under_fp8(fp8_mode):
    from mxnet_tpu import gluon
    net = _convnet()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.3, "momentum": 0.9})
    lossfn = gloss.SoftmaxCrossEntropyLoss()

    def make_data(n):
        y = np.random.randint(0, 3, n)
        x = np.random.rand(n, 8, 8, 3).astype(np.float32) * 0.3
        for i, c in enumerate(y):
            x[i, :, :, c] += 1.0
        return x, y.astype(np.float32)

    first = last = None
    for _ in range(25):
        x, y = make_data(64)
        with autograd.record():
            loss = lossfn(net(mx.nd.array(x)), mx.nd.array(y))
        loss.backward()
        tr.step(64)
        last = float(loss.mean().asnumpy())
        first = first if first is not None else last
    assert last < first * 0.5, (first, last)


def test_spmd_trainer_under_fp8(fp8_mode):
    """The bench path: SPMDTrainer fused step with fp8 residuals."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import SPMDTrainer
    net = _convnet()
    tr = SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": 0.1},
                     dtype=jnp.bfloat16)
    x = jnp.asarray(RS.rand(2, 8, 12, 12, 3).astype(np.float32))
    y = jnp.asarray(RS.randint(0, 5, (2, 8)).astype(np.float32))
    losses = tr.run_steps(x, y)
    assert np.isfinite(np.asarray(losses)).all()
