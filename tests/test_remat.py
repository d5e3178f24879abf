"""MXNET_BACKWARD_DO_MIRROR (activation remat) tests.

The reference's mirror pass (src/nnvm/gradient.cc:271 mirror_fun) re-runs
cheap forward nodes inside backward instead of keeping their outputs live.
The TPU-native analog wraps the traced forward in jax.checkpoint, so the
fused fwd+bwd XLA program stores only the inputs across the boundary and
rematerializes activations. Gradients must be bit-identical; the compiled
program must actually contain a remat region; peak memory must not grow.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.cached_op import CachedOp
from mxnet_tpu.util import apply_mirror, mirror_enabled


def _deep_mlp(width=64, depth=6):
    mx.random.seed(0)
    net = gluon.nn.HybridSequential()
    for _ in range(depth):
        net.add(gluon.nn.Dense(width, activation="relu"))
    net.add(gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    with autograd.pause():
        net(nd.ones((2, 8)))
    return net


def _grads_via_cached_op(net, x, mirror):
    op = CachedOp(net, mirror=mirror)
    with autograd.record():
        out = op(x)
        loss = (out * out).sum()
    loss.backward()
    return {k: p.grad().asnumpy()
            for k, p in sorted(net.collect_params().items())}


def test_apply_mirror_inserts_remat():
    import jax
    import jax.numpy as jnp

    def f(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((4, 4))
    plain = str(jax.make_jaxpr(jax.grad(f))(x))
    assert "remat" not in plain
    os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1"
    try:
        wrapped = str(jax.make_jaxpr(jax.grad(apply_mirror(f)))(x))
    finally:
        del os.environ["MXNET_BACKWARD_DO_MIRROR"]
    assert "remat" in wrapped or "checkpoint" in wrapped


def test_mirror_enabled_resolution():
    assert not mirror_enabled()
    assert mirror_enabled(True)
    os.environ["MXNET_BACKWARD_DO_MIRROR"] = "1"
    try:
        assert mirror_enabled()
        assert not mirror_enabled(False)  # explicit arg wins over env
    finally:
        del os.environ["MXNET_BACKWARD_DO_MIRROR"]


def test_bad_policy_raises():
    from mxnet_tpu.base import MXNetError
    os.environ["MXNET_BACKWARD_MIRROR_POLICY"] = "bogus"
    try:
        with pytest.raises(MXNetError):
            apply_mirror(lambda x: x, True)
    finally:
        del os.environ["MXNET_BACKWARD_MIRROR_POLICY"]


def test_cached_op_mirror_same_grads():
    net = _deep_mlp()
    x = nd.array(np.random.RandomState(0).randn(8, 8).astype(np.float32))
    g_plain = _grads_via_cached_op(net, x, mirror=False)
    g_remat = _grads_via_cached_op(net, x, mirror=True)
    assert g_plain.keys() == g_remat.keys()
    for k in g_plain:
        np.testing.assert_array_equal(g_plain[k], g_remat[k])


def test_cached_op_mirror_dots_policy_same_grads():
    net = _deep_mlp()
    x = nd.array(np.random.RandomState(1).randn(8, 8).astype(np.float32))
    g_plain = _grads_via_cached_op(net, x, mirror=False)
    os.environ["MXNET_BACKWARD_MIRROR_POLICY"] = "dots"
    try:
        g_remat = _grads_via_cached_op(net, x, mirror=True)
    finally:
        del os.environ["MXNET_BACKWARD_MIRROR_POLICY"]
    for k in g_plain:
        np.testing.assert_array_equal(g_plain[k], g_remat[k])


def _executor_grads(monkeypatch_env):
    import mxnet_tpu.symbol as sym_mod
    sym = mx.sym
    x = sym.Variable("x")
    w1 = sym.Variable("w1")
    w2 = sym.Variable("w2")
    h = sym.Activation(sym.dot(x, w1), act_type="relu")
    out = sym.dot(h, w2)
    rs = np.random.RandomState(0)
    args = {"x": nd.array(rs.randn(4, 8).astype(np.float32)),
            "w1": nd.array(rs.randn(8, 16).astype(np.float32)),
            "w2": nd.array(rs.randn(16, 2).astype(np.float32))}
    grads = {k: nd.zeros(v.shape) for k, v in args.items()}
    for k, v in monkeypatch_env.items():
        os.environ[k] = v
    try:
        ex = out.bind(mx.cpu(), args=args, args_grad=grads)
        ex.forward(is_train=True)
        ex.backward(out_grads=nd.ones((4, 2)))
    finally:
        for k in monkeypatch_env:
            del os.environ[k]
    return {k: g.asnumpy() for k, g in grads.items()}


def test_executor_mirror_same_grads():
    g_plain = _executor_grads({})
    g_remat = _executor_grads({"MXNET_BACKWARD_DO_MIRROR": "1"})
    for k in g_plain:
        np.testing.assert_array_equal(g_plain[k], g_remat[k])


def test_hybridize_mirror_kwarg():
    """net.hybridize(mirror=True) plumbs through to the CachedOp."""
    net = _deep_mlp()
    x = nd.array(np.random.RandomState(2).randn(4, 8).astype(np.float32))
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    g_plain = {k: p.grad().asnumpy()
               for k, p in sorted(net.collect_params().items())}
    net.hybridize(mirror=True)
    assert net._cached_op_kwargs == {"mirror": True}
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    assert net._cached_op.mirror is True
    g_remat = {k: p.grad().asnumpy()
               for k, p in sorted(net.collect_params().items())}
    for k in g_plain:
        np.testing.assert_allclose(g_plain[k], g_remat[k],
                                   rtol=1e-5, atol=1e-6)


def test_spmd_remat_same_trajectory():
    from mxnet_tpu.parallel.spmd import SPMDTrainer
    from mxnet_tpu.gluon import loss as gloss

    def run(remat):
        net = _deep_mlp()
        tr = SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                         optimizer="sgd",
                         optimizer_params={"learning_rate": 0.1},
                         remat=remat)
        rs = np.random.RandomState(0)
        datas = rs.randn(3, 8, 8).astype(np.float32)
        labels = rs.randint(0, 4, (3, 8)).astype(np.float32)
        return np.asarray(tr.run_steps(datas, labels))

    np.testing.assert_allclose(run(False), run(True), rtol=1e-6)


def test_remat_memory_not_worse():
    """The checkpointed fused fwd+bwd program must not allocate MORE
    temp memory than the plain one (on backends that report it)."""
    import jax
    import jax.numpy as jnp

    def loss(params, x):
        h = x
        for w in params:
            h = jnp.tanh(h @ w)
        return (h * h).sum()

    rs = np.random.RandomState(0)
    params = tuple(jnp.asarray(rs.randn(256, 256).astype(np.float32))
                   for _ in range(8))
    x = jnp.asarray(rs.randn(512, 256).astype(np.float32))

    def temp_bytes(fn):
        c = jax.jit(jax.grad(fn)).lower(params, x).compile()
        m = c.memory_analysis()
        if m is None or not hasattr(m, "temp_size_in_bytes"):
            pytest.skip("backend reports no memory analysis")
        return m.temp_size_in_bytes

    plain = temp_bytes(loss)
    remat = temp_bytes(apply_mirror(loss, True))
    assert remat <= plain, f"remat temp {remat} > plain {plain}"


# ---------------------------------------------------------------------------
# what a recorded hybridized forward keeps for its backward (cached_op.py):
# mirroring off saves convolution and matmul outputs and the reductions;
# mirror=True / MXNET_BACKWARD_DO_MIRROR keep their meaning, under
# MXNET_BACKWARD_MIRROR_POLICY

def _plan(net, x, env=None):
    """The recorded entry's plan after one forward/backward of ``net``."""
    for k, v in (env or {}).items():
        os.environ[k] = v
    try:
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
    finally:
        for k in env or {}:
            del os.environ[k]
    return _recorded_entry(net).linear


def _recorded_entry(net):
    return [e for _, e in net._cached_op._cache.snapshot_items()
            if e.linear is not None][-1]


def _owned_shapes(plan):
    return sorted(a.shape for a in plan.arena_avals)


@pytest.mark.parametrize("how,policy,owned", [
    ("default", "elementwise", [(8, 64)] * 6),
    ("mirror_kwarg", "full", []),
    ("env_full", "full", []),
    ("env_dots", "dots", [(8, 64)] * 6),
])
def test_recorded_forward_keeps_what_the_policy_says(how, policy, owned):
    """``full`` leaves only pass-through leaves (parameters, the batch, the
    key) as residuals, the former behaviour and footprint; the default
    keeps each hidden layer's product."""
    net = _deep_mlp()
    env = {}
    if how == "mirror_kwarg":
        net.hybridize(mirror=True)
    else:
        net.hybridize()
        if how.startswith("env_"):
            env = {"MXNET_BACKWARD_DO_MIRROR": "1",
                   "MXNET_BACKWARD_MIRROR_POLICY": how[4:]}
    x = nd.array(np.random.RandomState(3).randn(8, 8).astype(np.float32))
    plan = _plan(net, x, env)
    assert plan.policy == policy
    assert _owned_shapes(plan) == owned
    assert plan.residual_bytes == sum(4 * a * b for a, b in owned)
    if not owned:
        assert all(src < 0 for src in plan.res_src)


def test_default_policy_keeps_reductions_not_batched_matmuls():
    """LayerNorm's per-row sums are kept (they are small beside their
    input); a matmul with batch dimensions (attention's scores, T x T a
    head) is recomputed."""
    class Block(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.norm = gluon.nn.LayerNorm()
                self.proj = gluon.nn.Dense(16, flatten=False)

        def hybrid_forward(self, F, x):
            h = self.proj(self.norm(x))          # (2, 6, 16)
            scores = F.batch_dot(h, h, transpose_b=True)  # (2, 6, 6)
            return F.batch_dot(F.softmax(scores), h)

    net = Block()
    net.initialize(mx.init.Xavier())
    x = nd.array(np.random.RandomState(4).randn(2, 6, 8).astype(np.float32))
    net(x)
    net.hybridize()
    shapes = _owned_shapes(_plan(net, x))
    assert (2, 6, 16) in shapes          # the projection's product
    assert (2, 6, 6) not in shapes       # no scores, no softmax output
    assert any(s in ((2, 6), (2, 6, 1)) for s in shapes)  # the sums


def test_default_policy_keeps_batchnorm_statistics():
    """BatchNorm's training core is a ``jax.custom_vjp``; its forward rule
    calls the plain function, so the policy sees the per-channel sums
    and keeps them: the backward program reduces only over cotangents,
    it does not read the convolution's output again for the batch
    statistics (two reductions a layer in place of four)."""
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(4, 3, padding=1, use_bias=False),
            gluon.nn.BatchNorm(), gluon.nn.Activation("relu"))
    net.initialize(mx.init.Xavier())
    x = nd.array(np.random.RandomState(6).rand(2, 3, 5, 5).astype(np.float32))
    with autograd.pause():
        net(x)
    net.hybridize()
    # the product in whichever dimension order the compiler keeps it (XLA's
    # CPU backend channels-last: handed over as (2, 5, 5, 4)), and the sums
    assert [tuple(sorted(s)) for s in _owned_shapes(_plan(net, x))] == \
        [(2, 4, 5, 5), (4,), (4,)]
    entry = _recorded_entry(net)
    bwd = entry.vjp_jitted.lower(*entry.vjp_abstract).as_text()
    assert bwd.count("stablehlo.reduce(") == 2   # sum_dy, sum_dy_xhat


def test_mirror_flag_is_part_of_the_recorded_key():
    """The knobs are read on the host at every call and join the cache
    key: setting the flag after the first step builds the other program,
    it does not replay the first."""
    net = _deep_mlp()
    net.hybridize()
    x = nd.array(np.random.RandomState(5).randn(8, 8).astype(np.float32))
    assert _plan(net, x).policy == "elementwise"
    assert _plan(net, x, {"MXNET_BACKWARD_DO_MIRROR": "1"}).policy == "full"
    keys = [k.record[0] for k, _ in net._cached_op._cache.snapshot_items()]
    assert sorted(keys) == ["elementwise", "full"]
