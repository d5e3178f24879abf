"""Sparse embedding plane (parallel/embedding_plane.py): row-wise table
sharding across a simulated world, fixed-shape mask-packed row-sparse
gradients through the row-gathered grouped update (optimizer/grouped.py
sparse_rows_update), lazily materialized 1/world per-rank optimizer
state pinned ledger-exact, kv_flake no-double-apply, sentinel skip +
rollback, and the registry lookup-serving tier (serving/lookup.py).

Marker ``sparse_plane`` (tier-1-safe: CPU, simulated worlds in-process;
the ledger is exact by construction there)."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu import optimizer as opt_mod
from mxnet_tpu.base import MXNetError
from mxnet_tpu.contrib import chaos
from mxnet_tpu.optimizer import grouped as grouped_mod
from mxnet_tpu.parallel import embedding_plane as ep
from mxnet_tpu.telemetry import memory as mem

pytestmark = pytest.mark.sparse_plane


@pytest.fixture
def plane_on(monkeypatch):
    monkeypatch.setenv("MXTPU_SPARSE_PLANE", "on")


def _plane(name, rows=32, dim=4, world=4, opt=None, seed=0, **kw):
    opt = opt or opt_mod.Adam(learning_rate=0.05)
    return ep.EmbeddingPlane(name, rows=rows, dim=dim, world=world,
                             optimizer=opt, seed=seed, **kw), opt


def _steps(plane, n=4, rows=32, batch=6, dim=4, seed=1, ids_list=None):
    rs = np.random.RandomState(seed)
    for s in range(n):
        ids = (ids_list[s] if ids_list is not None
               else rs.randint(0, rows, size=batch))
        g = rs.randn(len(ids), dim).astype(np.float32)
        plane.step(ids, nd.array(g))


# ---------------------------------------------------------------------------
# env flags + pure partition/bucket helpers
# ---------------------------------------------------------------------------

def test_sparse_plane_flag_strict_parse(monkeypatch):
    for raw, want in (("on", True), ("1", True), ("true", True),
                      ("off", False), ("0", False), ("", False)):
        monkeypatch.setenv("MXTPU_SPARSE_PLANE", raw)
        assert ep.sparse_plane_requested() is want
    monkeypatch.delenv("MXTPU_SPARSE_PLANE", raising=False)
    assert ep.sparse_plane_requested() is False
    monkeypatch.setenv("MXTPU_SPARSE_PLANE", "yess")
    with pytest.raises(MXNetError, match="MXTPU_SPARSE_PLANE"):
        ep.sparse_plane_requested()


def test_sparse_max_rows_strict_parse(monkeypatch):
    monkeypatch.delenv("MXTPU_SPARSE_MAX_ROWS", raising=False)
    assert ep.sparse_max_rows() == 4096
    monkeypatch.setenv("MXTPU_SPARSE_MAX_ROWS", "64")
    assert ep.sparse_max_rows() == 64
    monkeypatch.setenv("MXTPU_SPARSE_MAX_ROWS", "four")
    with pytest.raises(MXNetError, match="MXTPU_SPARSE_MAX_ROWS"):
        ep.sparse_max_rows()
    monkeypatch.setenv("MXTPU_SPARSE_MAX_ROWS", "0")
    with pytest.raises(MXNetError, match="MXTPU_SPARSE_MAX_ROWS"):
        ep.sparse_max_rows()


def test_plane_requires_explicit_opt_in(monkeypatch):
    monkeypatch.delenv("MXTPU_SPARSE_PLANE", raising=False)
    with pytest.raises(MXNetError, match="MXTPU_SPARSE_PLANE"):
        ep.EmbeddingPlane("t", rows=8, dim=2, world=2,
                          optimizer=opt_mod.Adam())


def test_row_partition_contiguous_and_strict():
    assert ep.row_partition(12, 3) == [(0, 4), (4, 8), (8, 12)]
    assert ep.row_partition(8, 1) == [(0, 8)]
    with pytest.raises(MXNetError, match="divide the world"):
        ep.row_partition(10, 4)
    with pytest.raises(MXNetError):
        ep.row_partition(8, 0)


def test_row_bucket_policy(monkeypatch):
    monkeypatch.setenv("MXTPU_SPARSE_MAX_ROWS", "64")
    assert ep.row_bucket(1) == 8      # floor
    assert ep.row_bucket(8) == 8
    assert ep.row_bucket(9) == 16     # next pow2
    assert ep.row_bucket(33) == 64    # capped exactly at the ceiling
    with pytest.raises(MXNetError, match="MXTPU_SPARSE_MAX_ROWS"):
        ep.row_bucket(65)


# ---------------------------------------------------------------------------
# lookup + sharding invariants
# ---------------------------------------------------------------------------

def test_lookup_matches_todense(plane_on):
    plane, _ = _plane("t_lk", rows=64, dim=8, world=4)
    try:
        ids = np.array([0, 5, 5, 17, 63, 32, 16])  # dupes + shard edges
        out = plane.lookup(ids).asnumpy()
        np.testing.assert_array_equal(out, plane.todense()[ids])
        with pytest.raises(MXNetError, match="lookup ids outside"):
            plane.lookup(np.array([64]))
        with pytest.raises(MXNetError, match="lookup ids outside"):
            plane.lookup(np.array([-1]))
    finally:
        plane.close()


def test_init_is_world_invariant(plane_on):
    """The deterministic full-table init + pure contiguous split: every
    world size derives the SAME table bitwise (topology-portable)."""
    tables = []
    for world in (1, 2, 4):
        plane, _ = _plane(f"t_init{world}", rows=32, dim=4, world=world)
        tables.append(plane.todense())
        plane.close()
    np.testing.assert_array_equal(tables[0], tables[1])
    np.testing.assert_array_equal(tables[0], tables[2])


@pytest.mark.parametrize("mkopt", [
    lambda: opt_mod.Adam(learning_rate=0.05, wd=0.01),
    lambda: opt_mod.SGD(learning_rate=0.1, momentum=0.9),
    lambda: opt_mod.SGD(learning_rate=0.1, wd=0.01),
], ids=["adam", "sgd-mom", "sgd"])
def test_training_is_world_invariant_bitwise(plane_on, mkopt):
    """Tentpole acceptance: the sharded trajectory is BITWISE identical
    across world sizes — the shard update is the same rule-kernel math,
    only row ownership changes."""
    tables = []
    for world in (1, 2, 4):
        plane, _ = _plane(f"t_tw{world}", rows=32, dim=4, world=world,
                          opt=mkopt())
        _steps(plane, n=4)
        tables.append(plane.todense())
        plane.close()
    np.testing.assert_array_equal(tables[0], tables[1])
    np.testing.assert_array_equal(tables[0], tables[2])


def test_parity_vs_dense_gather_reference(plane_on):
    """Bitwise parity against an independent dense-gather reference: the
    full unsharded table stepped by the SAME grouped rule kernel on the
    gathered touched rows (gather -> kernel -> scatter, no plane, no
    sharding, no mask-pack)."""
    rows, dim, batch = 32, 4, 6
    plane, opt = _plane("t_par", rows=rows, dim=dim, world=4)
    ref_opt = opt_mod.Adam(learning_rate=0.05)
    kernel = grouped_mod._with_cast(
        grouped_mod._rule_for(ref_opt).make_kernel(ref_opt, True), False)
    kfn = jax.jit(kernel)
    ref = jnp.asarray(plane.todense())
    ref_state = (jnp.zeros((rows, dim), jnp.float32),
                 jnp.zeros((rows, dim), jnp.float32))
    try:
        rs = np.random.RandomState(1)
        import math
        for s in range(4):
            ids = rs.randint(0, rows, size=batch)
            g = rs.randn(batch, dim).astype(np.float32)
            plane.step(ids, nd.array(g))
            # the reference: same dedup + same segment-summed rows
            uids, inv = np.unique(ids, return_inverse=True)
            bucket = ep.row_bucket(len(uids))
            packed = ep._pack_fn(batch, bucket)(
                jnp.asarray(g), jnp.asarray(inv.astype(np.int32)))
            ref_opt._update_count(0)
            t = ref_opt._index_update_count[0]
            lr = ref_opt._get_lr(0) * math.sqrt(
                1 - ref_opt.beta2 ** t) / (1 - ref_opt.beta1 ** t)
            u = jnp.asarray(uids.astype(np.int32))
            gw = jnp.take(ref, u, axis=0)
            gs = tuple(jnp.take(a, u, axis=0) for a in ref_state)
            nw, ns = kfn(gw, packed[:len(uids)], gs,
                         jnp.asarray(lr, jnp.float32),
                         jnp.asarray(ref_opt._get_wd(0), jnp.float32),
                         jnp.asarray(ref_opt.rescale_grad, jnp.float32))
            ref = ref.at[u].set(nw)
            ref_state = tuple(a.at[u].set(b)
                              for a, b in zip(ref_state, ns))
        np.testing.assert_array_equal(plane.todense(), np.asarray(ref))
    finally:
        plane.close()


def test_warm_rows_update_is_one_launch_and_bitwise_the_kernel(tmp_path):
    """A warm ``sparse_rows_update`` launches its rows program and nothing
    else: lr, wd and ``rescale_grad`` travel with that launch as host
    float32 arrays, not as three puts and cast programs before it. The
    values are what the device scalars gave: bitwise the rule kernel on the
    gathered rows."""
    from test_step_spans import _host_spans, _launches_inside, \
        _profiler_session
    rows, dim, bucket = 16, 4, 8
    opt = opt_mod.Adam(learning_rate=0.05, wd=0.01, rescale_grad=0.5)
    rs = np.random.RandomState(3)
    w0 = rs.randn(rows, dim).astype(np.float32)
    g = jnp.asarray(rs.randn(bucket, dim).astype(np.float32))
    idx = jnp.asarray(np.array([1, 4, 5, 9, 15, 0, 0, 0], np.int32))
    valid = jnp.asarray(np.arange(bucket) < 5)
    lr, wd = 0.0123, 0.01

    def fresh():
        return jnp.asarray(w0), (jnp.zeros((rows, dim), jnp.float32),
                                 jnp.zeros((rows, dim), jnp.float32))

    grouped_mod.sparse_rows_update(opt, *fresh(), g, idx, valid, lr, wd)
    weight, states = fresh()
    with _profiler_session(tmp_path), \
            jax.profiler.TraceAnnotation("test.rows_update"):
        nw, ns = grouped_mod.sparse_rows_update(
            opt, weight, states, g, idx, valid, lr, wd)
    assert _launches_inside(_host_spans(tmp_path),
                            "test.rows_update") == ["fn"]

    kernel = jax.jit(grouped_mod._with_cast(
        grouped_mod._rule_for(opt).make_kernel(opt, True), False))
    ref_w, ref_s = fresh()
    u = idx[:5]
    kw, ks = kernel(jnp.take(ref_w, u, axis=0), g[:5],
                    tuple(jnp.take(a, u, axis=0) for a in ref_s),
                    jnp.asarray(lr, jnp.float32),
                    jnp.asarray(wd, jnp.float32),
                    jnp.asarray(opt.rescale_grad, jnp.float32))
    np.testing.assert_array_equal(np.asarray(nw),
                                  np.asarray(ref_w.at[u].set(kw)))
    for got, a, b in zip(ns, ref_s, ks):
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(a.at[u].set(b)))


def test_step_touches_only_touched_rows(plane_on):
    plane, _ = _plane("t_touch", rows=32, dim=4, world=4)
    try:
        before = plane.todense().copy()
        ids = np.array([3, 17, 30])
        plane.step(ids, nd.array(np.ones((3, 4), np.float32)))
        after = plane.todense()
        untouched = [i for i in range(32) if i not in set(ids.tolist())]
        np.testing.assert_array_equal(after[untouched], before[untouched])
        assert not np.allclose(after[ids], before[ids])
    finally:
        plane.close()


# ---------------------------------------------------------------------------
# the ZeRO analog: 1/world ledger-exact per-rank bytes, lazy state
# ---------------------------------------------------------------------------

def test_rank_bytes_exactly_one_world(plane_on):
    """Acceptance bar: with every rank touched, each rank's params+state
    ledger bytes land at EXACTLY 1/world of the unsharded plane's."""
    rows, dim, world = 64, 8, 4
    cover = [np.arange(i, rows, 4) for i in range(4)]  # hits all rows
    p1, _ = _plane("t_b1", rows=rows, dim=dim, world=1)
    _steps(p1, n=4, rows=rows, dim=dim, ids_list=cover)
    unsharded = p1.rank_bytes(0)
    p1.close()
    # Adam on f32: params rows*dim*4, state (mean+var) twice that
    assert unsharded == 3 * rows * dim * 4
    p4, _ = _plane("t_b4", rows=rows, dim=dim, world=world)
    try:
        _steps(p4, n=4, rows=rows, dim=dim, ids_list=cover)
        per_rank = [p4.rank_bytes(r) for r in range(world)]
        assert per_rank == [unsharded // world] * world
        assert sum(per_rank) == unsharded
    finally:
        p4.close()


def test_state_is_lazy_per_rank(plane_on):
    """A rank whose rows were never touched holds params only — the
    reference's lazy row-sparse update discipline at shard granularity."""
    plane, _ = _plane("t_lazy", rows=64, dim=8, world=4)
    try:
        shard_bytes = 64 // 4 * 8 * 4
        assert [plane.rank_bytes(r) for r in range(4)] == [shard_bytes] * 4
        plane.step(np.array([0, 40]),  # ranks 0 and 2 only
                   nd.array(np.ones((2, 8), np.float32)))
        assert plane.rank_bytes(0) == 3 * shard_bytes
        assert plane.rank_bytes(2) == 3 * shard_bytes
        assert plane.rank_bytes(1) == shard_bytes  # untouched: no state
        assert plane.rank_bytes(3) == shard_bytes
        assert plane.describe()["ranks_with_state"] == 2
    finally:
        plane.close()


# ---------------------------------------------------------------------------
# retrace contract: warm steps replay, never recompile
# ---------------------------------------------------------------------------

def test_warm_steps_never_retrace_within_bucket(plane_on):
    plane, _ = _plane("t_warm", rows=32, dim=4, world=4)
    try:
        rs = np.random.RandomState(3)
        batch = 6
        ids = rs.randint(0, 32, size=batch)
        plane.step(ids, nd.array(rs.randn(batch, 4).astype(np.float32)))
        plane.lookup(ids)
        grouped_misses = grouped_mod._cache().cache_info().misses
        pack_size = ep._pack_fn.cache_info().currsize
        gather_size = ep._gather_fn.cache_info().currsize
        # warm steps: varying touched-row counts and rank subsets, same
        # batch size, all within the bucket -> zero new programs
        for n_unique in (1, 3, 6, 2, 5, 4):
            ids = np.resize(rs.choice(32, size=n_unique, replace=False),
                            batch)  # repeat ids up to the fixed batch
            plane.step(ids,
                       nd.array(rs.randn(batch, 4).astype(np.float32)))
            plane.lookup(ids)
        assert grouped_mod._cache().cache_info().misses == grouped_misses
        assert ep._pack_fn.cache_info().currsize == pack_size
        assert ep._gather_fn.cache_info().currsize == gather_size
    finally:
        plane.close()


# ---------------------------------------------------------------------------
# chaos: retried kv_flake never double-applies
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_kv_flake_retry_never_double_applies(plane_on, monkeypatch):
    monkeypatch.setenv("MXNET_KV_RETRY_BASE_MS", "1")

    def run(name, spec):
        plan = None
        if spec:
            plan = chaos.ChaosPlan(spec, seed=7)
            chaos.install(plan)
        try:
            plane, _ = _plane(name, rows=32, dim=4, world=4)
            _steps(plane, n=4)
            out = plane.todense()
            plane.close()
        finally:
            if spec:
                chaos.uninstall()
        return out, plan

    clean, _ = run("t_cl", "")
    flaky, plan = run("t_fl", "kv_flake:0.3")
    assert plan.injected["kv_flake"] > 0
    np.testing.assert_array_equal(clean, flaky)


# ---------------------------------------------------------------------------
# sentinel skip + rollback
# ---------------------------------------------------------------------------

def test_sentinel_false_leaves_device_state_bitwise(plane_on):
    plane, opt = _plane("t_sent", rows=32, dim=4, world=4)
    led = mem.ledger()
    try:
        w0 = plane.todense().copy()
        base = led.live_bytes("optimizer", owner_prefix="state:emb")
        plane.step(np.array([1, 20]),
                   nd.array(np.ones((2, 4), np.float32)),
                   flag=jnp.asarray(False))
        # device half untouched; host half (count + lazily created
        # state arrays with their ledger bytes) pending rollback
        np.testing.assert_array_equal(plane.todense(), w0)
        assert led.live_bytes("optimizer",
                              owner_prefix="state:emb") > base
        assert opt._index_update_count[0] == 1
        plane.rollback_step()
        assert led.live_bytes("optimizer",
                              owner_prefix="state:emb") == base
        assert opt._index_update_count[0] == 0
        # the retried step is step 1 again (Adam bias correction replays)
        plane.step(np.array([1, 20]),
                   nd.array(np.ones((2, 4), np.float32)))
        assert opt._index_update_count[0] == 1
        assert not np.allclose(plane.todense(), w0)
    finally:
        plane.close()


def test_sentinel_true_applies(plane_on):
    plane, _ = _plane("t_sentok", rows=32, dim=4, world=2)
    try:
        w0 = plane.todense().copy()
        plane.step(np.array([1, 20]),
                   nd.array(np.ones((2, 4), np.float32)),
                   flag=jnp.asarray(True))
        assert not np.allclose(plane.todense(), w0)
    finally:
        plane.close()


def test_skipped_then_clean_matches_never_skipped(plane_on):
    """A sentinel-skipped + rolled-back step is indistinguishable from
    one that never ran: the subsequent trajectory is bitwise identical
    (the Trainer.rollback_step contract, row-sharded)."""
    def run(name, skip):
        plane, _ = _plane(name, rows=32, dim=4, world=4)
        rs = np.random.RandomState(5)
        for s in range(3):
            ids = rs.randint(0, 32, size=6)
            g = rs.randn(6, 4).astype(np.float32)
            if skip and s == 1:
                plane.step(ids, nd.array(g), flag=jnp.asarray(False))
                plane.rollback_step()
                continue
            if not skip and s == 1:
                continue  # the clean run never sees step 1's batch
            plane.step(ids, nd.array(g))
        out = plane.todense()
        plane.close()
        return out
    np.testing.assert_array_equal(run("t_sk", True), run("t_nk", False))


# ---------------------------------------------------------------------------
# grouped/zero dispatch seams (satellites 1 + 2)
# ---------------------------------------------------------------------------

def test_grouped_dense_raise_names_sparse_plane():
    """ONE documented raise for sparse storage in the fused dense path,
    and it names the MXTPU_SPARSE_PLANE opt-in (the doorway into
    sparse_rows_update)."""
    p = gluon.Parameter("emb_sp", shape=(8, 2), grad_stype="row_sparse")
    p.initialize(mx.init.One())

    class U:
        optimizer = opt_mod.Adam()
        states = {}

    with pytest.raises(MXNetError, match="MXTPU_SPARSE_PLANE"):
        grouped_mod.prepare_update(U(), [(0, p)])


def test_sparse_rows_update_rejects_unruled_optimizer(plane_on):
    class Weird(opt_mod.Optimizer):
        def create_state(self, index, weight):
            return None

        def update(self, index, weight, grad, state):
            pass

    with pytest.raises(MXNetError, match="no grouped-update rule"):
        ep.EmbeddingPlane("t_weird", rows=8, dim=2, world=2,
                          optimizer=Weird())


def test_zero_raise_names_embedding_plane(monkeypatch):
    """MXTPU_ZERO=1 with a sparse table in the Trainer: the creation
    raise points at the row-wise plane composition."""
    from mxnet_tpu import kvstore as kvs
    monkeypatch.setenv("MXTPU_ZERO", "1")
    monkeypatch.setenv("MXTPU_ZERO_WORLD", "2")
    p = gluon.Parameter("emb_z", shape=(8, 2), grad_stype="row_sparse")
    p.initialize(mx.init.One())
    tr = gluon.Trainer([p], "adam", {"learning_rate": 0.01},
                       kvstore=kvs.create("local"))
    from mxnet_tpu import autograd
    with autograd.record():
        e = nd.Embedding(nd.array(np.array([1.0])), p.data(), input_dim=8,
                         output_dim=2, sparse_grad=True)
        e.sum().backward()
    p._fresh_grad = True
    with pytest.raises(MXNetError, match="embedding_plane.EmbeddingPlane"):
        tr.step(1)


def test_dense_zero_composes_with_plane_in_one_loop(plane_on,
                                                    monkeypatch):
    """Satellite-2 regression: dense params ZeRO-sharded through the
    Trainer while the embedding table trains through the plane — one
    loop, two planes, both sharded, and the dense trajectory is bitwise
    the ZeRO-off trajectory."""
    from mxnet_tpu import kvstore as kvs

    def run(zero):
        if zero:
            monkeypatch.setenv("MXTPU_ZERO", "1")
            monkeypatch.setenv("MXTPU_ZERO_WORLD", "2")
        else:
            monkeypatch.delenv("MXTPU_ZERO", raising=False)
            monkeypatch.delenv("MXTPU_ZERO_WORLD", raising=False)
        tag = "zc" if zero else "nc"
        rs = np.random.RandomState(0)
        params = []
        for j in range(4):
            p = gluon.Parameter(f"{tag}{j}", shape=(4, 4))
            p.initialize(mx.init.Constant(0.0))
            p.set_data(nd.array(rs.randn(4, 4).astype(np.float32)))
            params.append(p)
        tr = gluon.Trainer(params, "adam", {"learning_rate": 0.01},
                           kvstore=kvs.create("local"))
        plane, _ = _plane(f"t_comp_{tag}", rows=16, dim=4, world=2)
        for _ in range(3):
            for p in params:
                g = nd.array(rs.randn(4, 4).astype(np.float32))
                p._grad._rebind(g._data)
                p._fresh_grad = True
            ids = rs.randint(0, 16, size=5)
            ge = rs.randn(5, 4).astype(np.float32)
            tr.step(4)
            plane.step(ids, nd.array(ge))
        dense = [p.data().asnumpy() for p in params]
        table = plane.todense()
        zero_on = bool(tr._zero)
        per_rank = [plane.rank_bytes(r) for r in range(2)]
        plane.close()
        return dense, table, zero_on, per_rank

    d_z, t_z, zon, per_rank = run(True)
    d_n, t_n, noff, _ = run(False)
    assert zon and not noff
    for a, b in zip(d_z, d_n):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(t_z, t_n)
    # both ranks touched (16 rows, 15 random draws): state everywhere
    assert per_rank[0] == per_rank[1] == 3 * 8 * 4 * 4


# ---------------------------------------------------------------------------
# serving: the registry lookup tier (serving/lookup.py)
# ---------------------------------------------------------------------------

def _tower(dim=8, seed=0):
    mx.random.seed(seed)
    net = gluon.nn.Dense(2, in_units=dim)
    net.initialize(mx.init.Xavier())
    with mx.autograd.pause():
        net(nd.ones((1, dim)))
    return net


@pytest.mark.serving
def test_lookup_serving_roundtrip(plane_on, tmp_path):
    from mxnet_tpu.serving import (LookupFleet, LookupReplica,
                                   ModelRegistry, publish_embedding)
    plane, _ = _plane("t_serve", rows=64, dim=8, world=4)
    try:
        _steps(plane, n=2, rows=64, dim=8)
        reg = ModelRegistry(str(tmp_path / "registry"))
        sig = {"bucket_shapes": [[8]], "dtype": "float32"}
        version = publish_embedding(reg, "two_tower", plane, _tower(),
                                    signature=sig)
        table = plane.todense()
        replica = LookupReplica(reg, "two_tower", version=version)
        assert (replica.rows, replica.dim, replica.world) == (64, 8, 4)
        ids = np.array([0, 17, 63, 17])
        np.testing.assert_array_equal(replica.lookup(ids), table[ids])
        # dense-tower + the combined recommend request
        out = replica.recommend(ids)
        assert out.shape == (4, 2)
        ref = replica.dense_tower(table[ids])
        np.testing.assert_array_equal(out, ref)
        # the fleet tier: round-robin spreads requests, metrics count
        fleet = LookupFleet(reg, "two_tower", replicas=2, version=version)
        for _ in range(6):
            fleet.lookup(ids)
        m = fleet.metrics_json()
        assert m["replicas"] == 2 and m["requests"] == 6
        assert m["lookup_qps"] > 0
        assert sorted(m["per_replica"].values()) == [3, 3]
        # the plane's metadata rode along in the manifest
        emb_meta = replica.resolved.manifest["metadata"]["embedding"]
        assert emb_meta["rows"] == 64 and emb_meta["world"] == 4
    finally:
        plane.close()


@pytest.mark.serving
def test_lookup_replica_requires_sidecar(plane_on, tmp_path):
    from mxnet_tpu.serving import LookupReplica, ModelRegistry
    reg = ModelRegistry(str(tmp_path / "registry"))
    reg.publish("plain", net=_tower(),
                signature={"bucket_shapes": [[8]], "dtype": "float32"})
    with pytest.raises(MXNetError, match="sidecar"):
        LookupReplica(reg, "plain")


# ---------------------------------------------------------------------------
# misc plane hygiene
# ---------------------------------------------------------------------------

def test_step_shape_mismatch_raises(plane_on):
    plane, _ = _plane("t_shape", rows=16, dim=4, world=2)
    try:
        with pytest.raises(MXNetError, match="gradient rows"):
            plane.step(np.array([1, 2, 3]),
                       nd.array(np.ones((2, 4), np.float32)))
    finally:
        plane.close()


def test_close_drops_ledger(plane_on):
    led = mem.ledger()
    plane, _ = _plane("t_close", rows=16, dim=4, world=2)
    plane.step(np.array([1]), nd.array(np.ones((1, 4), np.float32)))
    own = mem.plane_owner(0, 2, "t_close")
    assert led.live_bytes("params", owner_prefix=own) > 0
    plane.close()
    assert led.live_bytes("params", owner_prefix=own) == 0
    assert led.live_bytes(
        "optimizer",
        owner_prefix=mem.plane_owner(0, 2, "t_close", state=True)) == 0
