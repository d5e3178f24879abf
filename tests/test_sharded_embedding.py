"""Sharded embedding tables on the 8-device CPU mesh (VERDICT r1 item 4:
the reference's server-side row-sparse sharding,
kvstore_dist_server.h:331, redesigned as mesh-sharded jax Arrays).
"""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu import parallel as par
from mxnet_tpu.parallel.sharded_embedding import (
    ShardedEmbedding, shard_table, sharded_lookup, sharded_scatter_add)

VOCAB, DIM = 64, 8


def _mesh():
    return par.make_mesh({"mp": 8})


def test_table_provably_sharded():
    mesh = _mesh()
    emb = ShardedEmbedding(VOCAB, DIM, mesh, axis="mp", seed=1)
    shards = emb.shards
    assert len(shards) == 8
    # each device holds a DISTINCT block of vocab/8 rows
    assert all(s.data.shape == (VOCAB // 8, DIM) for s in shards)
    datas = [np.asarray(s.data) for s in shards]
    full = np.asarray(emb.weight)
    for i, d in enumerate(datas):
        np.testing.assert_array_equal(d, full[i * 8:(i + 1) * 8])
    assert len({d.tobytes() for d in datas}) == 8, "shards are copies!"


def test_lookup_matches_replicated_take():
    import jax.numpy as jnp
    mesh = _mesh()
    rs = np.random.RandomState(0)
    table = jnp.asarray(rs.randn(VOCAB, DIM).astype(np.float32))
    sharded = shard_table(table, mesh, "mp")
    ids = jnp.asarray(rs.randint(0, VOCAB, (17,)).astype(np.int32))
    out = sharded_lookup(sharded, ids, mesh, "mp")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(table)[np.asarray(ids)],
                               rtol=1e-6)


def test_lookup_gradient_is_row_sparse_scatter():
    import jax
    import jax.numpy as jnp
    mesh = _mesh()
    rs = np.random.RandomState(1)
    table = shard_table(
        jnp.asarray(rs.randn(VOCAB, DIM).astype(np.float32)), mesh, "mp")
    ids = jnp.asarray(np.array([3, 3, 60, 10], np.int32))
    cot = jnp.asarray(rs.randn(4, DIM).astype(np.float32))

    def f(t):
        return (sharded_lookup(t, ids, mesh, "mp") * cot).sum()

    g = jax.grad(f)(table)
    want = np.zeros((VOCAB, DIM), np.float32)
    for i, r in enumerate(np.asarray(ids)):
        want[r] += np.asarray(cot)[i]
    np.testing.assert_allclose(np.asarray(g), want, rtol=1e-5, atol=1e-6)


def test_scatter_add_updates_owned_rows_only():
    import jax.numpy as jnp
    mesh = _mesh()
    table = shard_table(jnp.zeros((VOCAB, DIM), jnp.float32), mesh, "mp")
    ids = jnp.asarray(np.array([0, 8, 63, 8], np.int32))
    rows = jnp.ones((4, DIM), jnp.float32)
    new = sharded_scatter_add(table, ids, rows, mesh, "mp")
    out = np.asarray(new)
    want = np.zeros((VOCAB, DIM), np.float32)
    want[0] += 1
    want[8] += 2  # duplicate id accumulates
    want[63] += 1
    np.testing.assert_array_equal(out, want)
    # still sharded after the update
    assert len(new.addressable_shards) == 8
    assert new.addressable_shards[0].data.shape == (VOCAB // 8, DIM)


def test_sharded_training_matches_replicated():
    """Convergence parity: an embedding classifier trained with the
    sharded table equals the same model trained with a replicated dense
    table (same data, same updates)."""
    import jax
    import jax.numpy as jnp
    mesh = _mesh()
    rs = np.random.RandomState(2)
    w0 = rs.randn(VOCAB, DIM).astype(np.float32) * 0.1
    proj = jnp.asarray(rs.randn(DIM, 1).astype(np.float32))
    emb = ShardedEmbedding(VOCAB, DIM, mesh, axis="mp")
    emb.weight = shard_table(jnp.asarray(w0), mesh, "mp")
    dense = jnp.asarray(w0)

    lr = 0.5
    losses_s, losses_d = [], []
    # fixed batch: the fit is learnable, so loss must drop
    ids = jnp.asarray(rs.randint(0, VOCAB, (16,)).astype(np.int32))
    y = jnp.asarray(rs.randn(16, 1).astype(np.float32))
    for step in range(10):

        def loss_sharded(t):
            out = sharded_lookup(t, ids, mesh, "mp") @ proj
            return ((out - y) ** 2).mean()

        def loss_dense(t):
            out = jnp.take(t, ids, axis=0) @ proj
            return ((out - y) ** 2).mean()

        ls, gs = jax.value_and_grad(loss_sharded)(emb.weight)
        ld, gd = jax.value_and_grad(loss_dense)(dense)
        # row-sparse apply on the sharded table; dense SGD on the other
        grad_rows = jnp.take(np.asarray(gd), ids, axis=0)  # rows of grad
        emb.weight = emb.weight - lr * gs
        dense = dense - lr * gd
        losses_s.append(float(ls))
        losses_d.append(float(ld))
    np.testing.assert_allclose(losses_s, losses_d, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(emb.weight), np.asarray(dense),
                               rtol=1e-4, atol=1e-5)
    assert losses_s[-1] < losses_s[0]


def test_kvstore_shards_big_tables_and_row_sparse_pull(monkeypatch):
    """kv.init above MXNET_KVSTORE_BIGARRAY_BOUND stores the value SHARDED
    across local devices; row_sparse_pull gathers across shards; pushes
    through the updater keep the table sharded."""
    monkeypatch.setenv("MXNET_KVSTORE_BIGARRAY_BOUND", "256")
    from mxnet_tpu import kvstore as kv_mod
    from jax.sharding import NamedSharding
    kv = kv_mod.create("device")
    rs = np.random.RandomState(3)
    table = rs.randn(VOCAB, DIM).astype(np.float32)  # 512 elems >= bound
    kv.init("emb", nd.array(table))
    stored = kv._store["emb"]
    assert isinstance(stored._data.sharding, NamedSharding)
    assert len(stored._data.addressable_shards) == 8
    assert stored._data.addressable_shards[0].data.shape == (VOCAB // 8,
                                                             DIM)
    # row_sparse_pull returns exactly the requested rows
    rid = nd.array(np.array([1, 9, 33, 63]), dtype="int64")
    out = nd.zeros((4, DIM))
    kv.row_sparse_pull("emb", out=out, row_ids=rid)
    np.testing.assert_allclose(out.asnumpy(), table[[1, 9, 33, 63]],
                               rtol=1e-6)
    # additive push keeps the table sharded
    kv.set_updater(lambda k, delta, stored:
                   stored._rebind((stored + delta)._data))
    delta = np.zeros_like(table)
    delta[9] = 1.0
    kv.push("emb", nd.array(delta))
    stored = kv._store["emb"]
    assert isinstance(stored._data.sharding, NamedSharding), \
        "push dropped the sharding"
    out2 = nd.zeros((4, DIM))
    kv.row_sparse_pull("emb", out=out2, row_ids=rid)
    np.testing.assert_allclose(out2.asnumpy()[1], table[9] + 1.0,
                               rtol=1e-6)
    # small values stay unsharded
    kv.init("small", nd.zeros((4, 4)))
    assert not isinstance(kv._store["small"]._data.sharding,
                          NamedSharding) or \
        len(kv._store["small"]._data.sharding.mesh.devices.ravel()) == 1
