"""Mesh/collectives/ring-attention/SPMD tests on the 8-device CPU mesh
(model: the reference's local multi-process dist tests,
tests/nightly/dist_sync_kvstore.py run via launch.py local)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu import parallel as par


def _mesh(**axes):
    return par.make_mesh(axes)


def test_make_mesh():
    import jax
    assert len(jax.devices()) == 8, "conftest must provide 8 cpu devices"
    mesh = _mesh(dp=2, tp=4)
    assert mesh.axis_names == ("dp", "tp")
    mesh2 = par.make_mesh({"dp": -1, "tp": 2})
    assert dict(zip(mesh2.axis_names, mesh2.devices.shape))["dp"] == 4


def test_allreduce_and_broadcast():
    import jax.numpy as jnp
    mesh = _mesh(dp=8)
    x = jnp.ones((16,))
    out = par.allreduce(x, mesh, axis="dp")
    assert np.allclose(np.asarray(out), 8.0)
    out = par.allreduce(x, mesh, axis="dp", op="mean")
    assert np.allclose(np.asarray(out), 1.0)


def test_allgather_reduce_scatter():
    import jax
    import jax.numpy as jnp
    mesh = _mesh(dp=8)
    from jax.sharding import NamedSharding, PartitionSpec as P
    x = jax.device_put(jnp.arange(32.0), NamedSharding(mesh, P("dp")))
    full = par.allgather(x, mesh, axis="dp")
    assert np.allclose(np.asarray(full), np.arange(32.0))
    rs = par.reduce_scatter(jnp.ones((32,)), mesh, axis="dp")
    assert rs.shape == (32,)
    assert np.allclose(np.asarray(rs), 8.0)


def test_ring_attention_matches_plain():
    import jax
    import jax.numpy as jnp
    mesh = _mesh(sp=8)
    rs = np.random.RandomState(0)
    B, T, H, D = 2, 32, 4, 8
    q = jnp.asarray(rs.randn(B, T, H, D).astype(np.float32))
    k = jnp.asarray(rs.randn(B, T, H, D).astype(np.float32))
    v = jnp.asarray(rs.randn(B, T, H, D).astype(np.float32))
    ref = par.attention(q, k, v, causal=False)
    out = par.ring_attention(q, k, v, mesh, axis="sp", causal=False)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_ring_attention_causal():
    import jax.numpy as jnp
    mesh = _mesh(sp=4)
    rs = np.random.RandomState(1)
    B, T, H, D = 1, 16, 2, 4
    q = jnp.asarray(rs.randn(B, T, H, D).astype(np.float32))
    k = jnp.asarray(rs.randn(B, T, H, D).astype(np.float32))
    v = jnp.asarray(rs.randn(B, T, H, D).astype(np.float32))
    ref = par.attention(q, k, v, causal=True)
    out = par.ring_attention(q, k, v, mesh, axis="sp", causal=True)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_spmd_trainer_data_parallel():
    from mxnet_tpu.gluon import nn, loss as gloss
    mesh = _mesh(dp=8)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(10))
    net.initialize(mx.init.Xavier())
    trainer = par.SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                              mesh=mesh, optimizer="sgd",
                              optimizer_params={"learning_rate": 0.5,
                                                "momentum": 0.9})
    rs = np.random.RandomState(0)
    centers = rs.randn(10, 16).astype(np.float32) * 2
    losses = []
    for i in range(30):
        labels = rs.randint(0, 10, 64)
        data = centers[labels] + 0.1 * rs.randn(64, 16).astype(np.float32)
        loss = trainer.step(nd.array(data), nd.array(labels.astype(np.float32)))
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.2, f"{losses[0]} -> {losses[-1]}"


def test_spmd_run_steps_matches_sequential():
    """The fused K-step scan driver (one XLA dispatch) must be bit-for-bit
    the same training trajectory as K individual step() calls."""
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu import autograd

    def make():
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
        net.initialize(mx.init.Xavier())
        with autograd.pause():
            net(nd.ones((2, 8)))
        return net

    rs = np.random.RandomState(1)
    K, B = 4, 8
    datas = rs.randn(K, B, 8).astype(np.float32)
    labels = rs.randint(0, 4, (K, B)).astype(np.float32)
    loss = gloss.SoftmaxCrossEntropyLoss()
    opt = {"learning_rate": 0.1, "momentum": 0.9}

    net_a = make()
    tr_a = par.SPMDTrainer(net_a, loss, optimizer="sgd",
                           optimizer_params=opt)
    la = [float(np.asarray(tr_a.step(datas[i], labels[i])))
          for i in range(K)]
    net_b = make()
    tr_b = par.SPMDTrainer(net_b, loss, optimizer="sgd",
                           optimizer_params=opt)
    lb = np.asarray(tr_b.run_steps(datas, labels))
    np.testing.assert_allclose(la, lb, rtol=1e-5)
    for (_, pa), (_, pb) in zip(sorted(net_a.collect_params().items()),
                                sorted(net_b.collect_params().items())):
        np.testing.assert_allclose(pa.data().asnumpy(),
                                   pb.data().asnumpy(),
                                   rtol=1e-5, atol=1e-6)


def test_spmd_run_steps_matches_sequential_with_dropout():
    """Stochastic layers too: both paths fold the trainer's base key with
    the step index, so dropout masks — hence trajectories — match."""
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu import autograd

    def make():
        mx.random.seed(3)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dropout(0.5),
                nn.Dense(4))
        net.initialize(mx.init.Xavier())
        with autograd.pause():
            net(nd.ones((2, 8)))
        return net

    rs = np.random.RandomState(4)
    K, B = 3, 8
    datas = rs.randn(K, B, 8).astype(np.float32)
    labels = rs.randint(0, 4, (K, B)).astype(np.float32)
    loss = gloss.SoftmaxCrossEntropyLoss()

    net_a = make()
    mx.random.seed(11)
    tr_a = par.SPMDTrainer(net_a, loss, optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
    la = [float(np.asarray(tr_a.step(datas[i], labels[i])))
          for i in range(K)]
    net_b = make()
    mx.random.seed(11)
    tr_b = par.SPMDTrainer(net_b, loss, optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
    lb = np.asarray(tr_b.run_steps(datas, labels))
    np.testing.assert_allclose(la, lb, rtol=1e-5)


def test_spmd_run_steps_on_mesh():
    """run_steps shards the batch axis (axis 1) over dp and trains."""
    from mxnet_tpu.gluon import nn, loss as gloss
    mesh = _mesh(dp=8)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(10))
    net.initialize(mx.init.Xavier())
    trainer = par.SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                              mesh=mesh, optimizer="sgd",
                              optimizer_params={"learning_rate": 0.5,
                                                "momentum": 0.9})
    rs = np.random.RandomState(0)
    centers = rs.randn(10, 16).astype(np.float32) * 2
    K, B = 6, 64
    labels = rs.randint(0, 10, (K, B))
    data = centers[labels] + 0.1 * rs.randn(K, B, 16).astype(np.float32)
    losses = np.asarray(trainer.run_steps(
        nd.array(data), nd.array(labels.astype(np.float32))))
    losses2 = np.asarray(trainer.run_steps(
        nd.array(data), nd.array(labels.astype(np.float32))))
    assert losses2[-1] < losses[0], f"{losses[0]} -> {losses2[-1]}"


def test_transformer_sharded_train_step():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as tfm
    mesh = _mesh(dp=2, tp=2, sp=2)
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_seq_len=32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    step, shard = tfm.make_train_step(cfg, mesh, lr=0.1)
    params = shard(params)
    rs = np.random.RandomState(0)
    toks = jnp.asarray(rs.randint(0, 64, (4, 16)).astype(np.int32))
    tgts = jnp.asarray(rs.randint(0, 64, (4, 16)).astype(np.int32))
    loss0, params = step(params, toks, tgts)
    for _ in range(10):
        loss, params = step(params, toks, tgts)
    assert float(loss) < float(loss0), f"{float(loss0)} -> {float(loss)}"


def test_transformer_ring_matches_dense():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                                n_layers=1, d_ff=32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.arange(16, dtype=np.int32)[None] % 32)
    logits_plain = tfm.forward(params, toks, cfg, mesh=None)
    mesh = _mesh(sp=8)
    logits_ring = tfm.forward(params, toks, cfg, mesh=mesh)
    assert np.allclose(np.asarray(logits_plain), np.asarray(logits_ring),
                       atol=1e-3)


def test_bandwidth_measure_runs():
    mesh = _mesh(dp=8)
    bw = par.measure_allreduce_bandwidth(mesh, size_mb=1.0, iters=2)
    assert bw > 0


@pytest.mark.heavy
def test_pipeline_matches_sequential():
    """GPipe pipeline over pp must be numerically identical to running
    the stages back-to-back (fwd and bwd)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _mesh(pp=4)
    rs = np.random.RandomState(0)
    W = jnp.asarray(rs.randn(4, 8, 8).astype(np.float32)) * 0.5
    b = jnp.asarray(rs.randn(4, 8).astype(np.float32)) * 0.1
    x = jnp.asarray(rs.randn(16, 8).astype(np.float32))

    def stage_fn(p, xm):
        w, bb = p
        return jnp.tanh(xm @ w + bb)

    params = (jax.device_put(W, NamedSharding(mesh, P("pp"))),
              jax.device_put(b, NamedSharding(mesh, P("pp"))))
    y = par.pipeline_apply(stage_fn, params, x, mesh, "pp",
                           n_microbatches=8)
    y_ref = x
    for i in range(4):
        y_ref = jnp.tanh(y_ref @ W[i] + b[i])
    assert np.allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)

    def lf(p):
        out = par.pipeline_apply(stage_fn, p, x, mesh, "pp",
                                 n_microbatches=4)
        return jnp.sum(out ** 2)

    def lf_ref(p):
        w, bb = p
        yy = x
        for i in range(4):
            yy = jnp.tanh(yy @ w[i] + bb[i])
        return jnp.sum(yy ** 2)

    g = jax.grad(lf)(params)
    g_ref = jax.grad(lf_ref)((W, b))
    assert np.allclose(np.asarray(g[0]), np.asarray(g_ref[0]), atol=1e-4)


def test_moe_ffn_shapes_and_balance():
    """Top-1 routed MoE: output finite, aux loss ~1 for balanced router."""
    import jax
    import jax.numpy as jnp
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(2, 8, 16).astype(np.float32))
    params = par.init_moe_params(jax.random.PRNGKey(0), 16, 32, 4)
    out, aux = par.moe_ffn(x, params, 4)
    assert out.shape == x.shape
    assert np.all(np.isfinite(np.asarray(out)))
    # near-uniform router at init => aux close to 1 (its minimum)
    assert 0.9 < float(aux) < 2.0


def test_moe_transformer_ep_sharded_step():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as tfm
    mesh = _mesh(dp=2, ep=4)
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_seq_len=32,
                                n_experts=4, moe_every=1)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    step, shard = tfm.make_train_step(cfg, mesh, lr=0.1)
    params = shard(params)
    rs = np.random.RandomState(0)
    toks = jnp.asarray(rs.randint(0, 64, (4, 16)).astype(np.int32))
    loss0, params = step(params, toks, toks)
    for _ in range(10):
        loss, params = step(params, toks, toks)
    assert float(loss) < float(loss0)


def test_pipeline_transformer_step():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as tfm
    mesh = _mesh(dp=2, pp=2, ep=2)
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_seq_len=32,
                                n_experts=4, moe_every=1)
    step, prepare = tfm.make_pipeline_train_step(cfg, mesh, lr=0.1,
                                                 n_microbatches=4)
    pparams = prepare(tfm.init_params(jax.random.PRNGKey(0), cfg))
    rs = np.random.RandomState(0)
    toks = jnp.asarray(rs.randint(0, 64, (4, 16)).astype(np.int32))
    loss0, pparams = step(pparams, toks, toks)
    for _ in range(5):
        loss, pparams = step(pparams, toks, toks)
    assert float(loss) < float(loss0)


def test_spmd_mesh_trainer_compiles_its_step_once():
    """Optimizer state is created where its parameter lives — on every
    device of the mesh, not on the first — so the second step finds the
    arguments laid out as the first did and reuses its program."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import SPMDTrainer
    mesh = _mesh(dp=4)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    tr = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), mesh=mesh,
                     optimizer="adam",
                     optimizer_params={"learning_rate": 1e-2})
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(8, 12).astype(np.float32))
    y = jnp.asarray(rs.randint(0, 4, (8,)).astype(np.float32))
    for _ in range(3):
        tr.step(x, y)
    (fn,) = tr._step_fns.values()
    assert fn._cache_size() == 1
    for a in jax.tree_util.tree_leaves(tr._opt_state):
        assert len(a.sharding.device_set) == 4
