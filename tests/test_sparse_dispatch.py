"""Storage-type dispatch: the op layer actually speaks sparse.

Covers the FComputeEx analog (ops/sparse_ops.py + registry.stype_dispatch):
on-device csr dot kernels, row_sparse autograd gradients
(Embedding(sparse_grad=True), dot(csr, dense)), lazy optimizer updates,
kvstore row_sparse push, and the principled dense fallback.
Reference: src/operator/tensor/dot-inl.h, src/operator/tensor/indexing_op.cc,
src/operator/optimizer_op.cc row_sparse variants,
tests/python/unittest/test_sparse_operator.py.
"""
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd
from mxnet_tpu import optimizer as opt
from mxnet_tpu.ndarray import sparse


def _random_csr(m, k, density=0.25, seed=0):
    rs = np.random.RandomState(seed)
    dense = rs.randn(m, k).astype(np.float32) * (rs.rand(m, k) < density)
    return sparse.csr_matrix(dense), dense


# ---------------------------------------------------------------------------
# csr dot kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n,density",
                         [(4, 7, 3, 0.3), (16, 33, 8, 0.1),
                          (8, 12, 1, 0.5), (5, 9, 4, 0.0),
                          (1, 64, 16, 0.9)])
def test_dot_csr_dense(m, k, n, density):
    csr, dense = _random_csr(m, k, density, seed=m + k)
    rhs = np.random.RandomState(1).randn(k, n).astype(np.float32)
    out = nd.dot(csr, nd.array(rhs))
    assert out.shape == (m, n)
    np.testing.assert_allclose(out.asnumpy(), dense @ rhs,
                               rtol=1e-5, atol=1e-5)


def test_dot_csr_dense_vector_rhs():
    csr, dense = _random_csr(6, 10, 0.3)
    rhs = np.random.randn(10).astype(np.float32)
    out = nd.dot(csr, nd.array(rhs))
    assert out.shape == (6,)
    np.testing.assert_allclose(out.asnumpy(), dense @ rhs, rtol=1e-5,
                               atol=1e-5)


def test_dot_csr_transpose_returns_row_sparse():
    csr, dense = _random_csr(6, 50, 0.1, seed=3)
    rhs = np.random.RandomState(2).randn(6, 4).astype(np.float32)
    out = nd.dot(csr, nd.array(rhs), transpose_a=True)
    assert out.stype == "row_sparse"
    # only touched columns appear as stored rows
    touched = np.unique(np.asarray(csr._indices))
    assert set(np.asarray(out._indices)) <= set(touched)
    np.testing.assert_allclose(out.todense().asnumpy(), dense.T @ rhs,
                               rtol=1e-5, atol=1e-5)


def test_sparse_dot_namespace():
    csr, dense = _random_csr(5, 8, 0.4)
    rhs = np.random.randn(8, 2).astype(np.float32)
    out = sparse.dot(csr, nd.array(rhs))
    np.testing.assert_allclose(out.asnumpy(), dense @ rhs, rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# autograd: row_sparse gradients
# ---------------------------------------------------------------------------

def test_dot_csr_backward_row_sparse_grad():
    csr, dense = _random_csr(6, 30, 0.15, seed=5)
    w = nd.array(np.random.RandomState(3).randn(30, 4).astype(np.float32))
    w.attach_grad(stype="row_sparse")
    with autograd.record():
        y = nd.dot(csr, w)
        loss = (y * y).sum()
    loss.backward()
    g = w.grad
    assert isinstance(g, sparse.RowSparseNDArray)
    cot = 2 * (dense @ np.asarray(w._data))
    ref = dense.T @ cot
    np.testing.assert_allclose(g.todense().asnumpy(), ref, rtol=1e-4,
                               atol=1e-4)
    # untouched feature rows are not stored
    touched = np.unique(np.asarray(csr._indices))
    assert set(np.asarray(g._indices)) <= set(touched)


def test_dot_csr_backward_vector_rhs():
    # 1-D rhs: backward must mirror the squeeze (regression: (nnz, nnz) cot)
    csr, dense = _random_csr(8, 20, 0.2, seed=11)
    w = nd.array(np.random.RandomState(6).randn(20).astype(np.float32))
    w.attach_grad(stype="row_sparse")
    with autograd.record():
        y = nd.dot(csr, w)
        y.sum().backward()
    g = w.grad
    ref = dense.T @ np.ones(8, np.float32)
    np.testing.assert_allclose(g.todense().asnumpy(), ref, rtol=1e-5,
                               atol=1e-5)
    # no spurious padded row 0 with zero data in the compact grad
    touched = set(np.unique(np.asarray(csr._indices)))
    assert set(np.asarray(g._indices)) <= touched


def test_dot_csr_transpose_backward():
    # y = csr.T @ h: grad wrt h = csr @ cot (regression: silent zero grad)
    csr, dense = _random_csr(6, 15, 0.25, seed=12)
    h = nd.array(np.random.RandomState(7).randn(6, 3).astype(np.float32))
    h.attach_grad()
    with autograd.record():
        y = nd.dot(csr, h, transpose_a=True)
        loss = y.todense().sum()
    loss.backward()
    ref = dense @ np.ones((15, 3), np.float32)
    np.testing.assert_allclose(h.grad.asnumpy(), ref, rtol=1e-4, atol=1e-4)


def test_sparse_dispatch_out_kwarg():
    csr, dense = _random_csr(5, 9, 0.4, seed=13)
    rhs = np.random.randn(9, 2).astype(np.float32)
    buf = nd.zeros((5, 2))
    res = nd.op.dot(csr, nd.array(rhs), out=buf)
    assert res is buf
    np.testing.assert_allclose(buf.asnumpy(), dense @ rhs, rtol=1e-5,
                               atol=1e-5)
    # row_sparse result into a row_sparse out buffer
    rhs2 = np.random.randn(5, 2).astype(np.float32)
    rsp_buf = sparse.zeros("row_sparse", (9, 2))
    nd.op.dot(csr, nd.array(rhs2), transpose_a=True, out=rsp_buf)
    np.testing.assert_allclose(rsp_buf.todense().asnumpy(), dense.T @ rhs2,
                               rtol=1e-5, atol=1e-5)


def test_embedding_sparse_grad_row_sparse():
    W = np.random.RandomState(0).randn(40, 6).astype(np.float32)
    w = nd.array(W)
    w.attach_grad(stype="row_sparse")
    ids = np.array([[5, 9, 5], [17, 9, 0]], np.float32)
    with autograd.record():
        e = nd.Embedding(nd.array(ids), w, input_dim=40, output_dim=6,
                         sparse_grad=True)
        loss = e.sum()
    loss.backward()
    g = w.grad
    assert isinstance(g, sparse.RowSparseNDArray)
    assert sorted(np.asarray(g._indices)) == [0, 5, 9, 17]
    ref = np.zeros_like(W)
    for i in ids.reshape(-1).astype(int):
        ref[i] += 1.0
    np.testing.assert_allclose(g.todense().asnumpy(), ref, atol=1e-6)


def test_embedding_sparse_grad_dense_buffer_densifies():
    # dense grad buffer still receives the correct (densified) gradient
    w = nd.array(np.random.randn(20, 3).astype(np.float32))
    w.attach_grad()
    with autograd.record():
        e = nd.Embedding(nd.array(np.array([1.0, 3.0])), w, input_dim=20,
                         output_dim=3, sparse_grad=True)
        e.sum().backward()
    g = w.grad.asnumpy()
    assert g[1].sum() == pytest.approx(3.0)
    assert g[3].sum() == pytest.approx(3.0)
    assert np.abs(g[[0, 2, 4]]).sum() == 0.0


def test_grad_accumulation_sparse_plus_sparse():
    w = nd.array(np.zeros((30, 2), np.float32))
    w.attach_grad(stype="row_sparse")
    with autograd.record():
        e1 = nd.Embedding(nd.array(np.array([2.0])), w, input_dim=30,
                          output_dim=2, sparse_grad=True)
        e2 = nd.Embedding(nd.array(np.array([2.0, 7.0])), w, input_dim=30,
                          output_dim=2, sparse_grad=True)
        (e1.sum() + e2.sum()).backward()
    g = w.grad
    assert sorted(np.asarray(g._indices)) == [2, 7]
    dense = g.todense().asnumpy()
    assert dense[2].sum() == pytest.approx(4.0)  # 2 + 2
    assert dense[7].sum() == pytest.approx(2.0)


def test_grad_accumulation_sparse_plus_dense_densifies():
    w = nd.array(np.ones((10, 2), np.float32))
    w.attach_grad()  # dense buffer
    with autograd.record():
        e = nd.Embedding(nd.array(np.array([4.0])), w, input_dim=10,
                         output_dim=2, sparse_grad=True)
        dense_path = (w * 2.0).sum()
        (e.sum() + dense_path).backward()
    g = w.grad.asnumpy()
    assert g[4].sum() == pytest.approx(2 * 2 + 2)  # 2 from dense, 1+1 embed
    assert g[0].sum() == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# lazy optimizer updates
# ---------------------------------------------------------------------------

def _rsp_grad(shape, rows, seed=0):
    rs = np.random.RandomState(seed)
    data = rs.randn(len(rows), *shape[1:]).astype(np.float32)
    import jax.numpy as jnp
    return sparse.RowSparseNDArray(jnp.asarray(data),
                                   np.asarray(rows, np.int32), shape)


@pytest.mark.parametrize("name,kwargs", [
    ("sgd", dict(learning_rate=0.1)),
    ("sgd", dict(learning_rate=0.1, momentum=0.9)),
    ("adam", dict(learning_rate=0.01)),
])
def test_lazy_update_touches_only_grad_rows(name, kwargs):
    w = nd.array(np.random.RandomState(1).randn(25, 4).astype(np.float32))
    o = opt.create(name, wd=0.01, **kwargs)
    state = o.create_state(0, w)
    g = _rsp_grad((25, 4), [3, 11, 19], seed=2)
    before = w.asnumpy().copy()
    o.update(0, w, g, state)
    after = w.asnumpy()
    untouched = [i for i in range(25) if i not in (3, 11, 19)]
    np.testing.assert_array_equal(after[untouched], before[untouched])
    assert not np.allclose(after[[3, 11, 19]], before[[3, 11, 19]])


def test_lazy_sgd_matches_dense_on_touched_rows():
    rows = [1, 6, 7]
    w1 = nd.array(np.random.RandomState(4).randn(10, 3).astype(np.float32))
    w2 = nd.array(w1.asnumpy())
    g = _rsp_grad((10, 3), rows, seed=5)
    o = opt.create("sgd", learning_rate=0.2, wd=0.1)
    o.update(0, w1, g, None)
    o2 = opt.create("sgd", learning_rate=0.2, wd=0.1, lazy_update=False)
    o2.update(0, w2, g, None)  # densified standard update
    np.testing.assert_allclose(w1.asnumpy()[rows], w2.asnumpy()[rows],
                               rtol=1e-5, atol=1e-6)


def test_lazy_update_convergence_logistic():
    """Sparse logistic regression with Adam lazy updates converges
    (the VERDICT 'done' criterion for the lazy_update path)."""
    rs = np.random.RandomState(0)
    n, d, nnz = 512, 400, 12
    w_true = rs.randn(d).astype(np.float32)
    cols = np.stack([rs.choice(d, nnz, replace=False) for _ in range(n)])
    vals = rs.randn(n, nnz).astype(np.float32)
    y = ((w_true[cols] * vals).sum(1) > 0).astype(np.float32)

    w = nd.zeros((d, 1))
    adam = opt.create("adam", learning_rate=0.05)
    state = adam.create_state(0, w)
    import jax.numpy as jnp
    bs = 64
    for epoch in range(6):
        correct = 0
        for b0 in range(0, n, bs):
            sl = slice(b0, b0 + bs)
            indptr = np.arange(bs + 1, dtype=np.int32) * nnz
            X = sparse.CSRNDArray(jnp.asarray(vals[sl].reshape(-1)),
                                  cols[sl].reshape(-1).astype(np.int32),
                                  indptr, (bs, d))
            yn = nd.array(y[sl])
            w.attach_grad(stype="row_sparse")
            with autograd.record():
                logits = sparse.dot(X, w).reshape((-1,))
                loss = (nd.op.relu(logits) - logits * yn +
                        nd.op.Activation(-nd.op.abs(logits),
                                         act_type="softrelu")).mean()
            loss.backward()
            adam.update(0, w, w.grad, state)
            correct += int(((logits.asnumpy() > 0) == y[sl]).sum())
    assert correct / n > 0.9


# ---------------------------------------------------------------------------
# kvstore row_sparse push
# ---------------------------------------------------------------------------

def test_kvstore_push_row_sparse():
    from mxnet_tpu import kvstore as kv_mod
    kv = kv_mod.create("local")
    kv.init("w", nd.zeros((12, 2)))
    kv.set_updater(lambda key, grad, stored:
                   stored.__setitem__(slice(None), (stored + grad.todense())
                                      if isinstance(grad,
                                                    sparse.RowSparseNDArray)
                                      else (stored + grad)))
    g1 = _rsp_grad((12, 2), [1, 5], seed=1)
    g2 = _rsp_grad((12, 2), [5, 9], seed=2)
    kv.push("w", [g1, g2])
    out = nd.zeros((12, 2))
    kv.pull("w", out=out)
    ref = g1.todense().asnumpy() + g2.todense().asnumpy()
    np.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-5, atol=1e-6)


def test_kvstore_push_row_sparse_no_updater_replaces():
    # replace semantics, like the dense push path: the store becomes the
    # pushed value (untouched rows zero), not a mix with stale contents
    from mxnet_tpu import kvstore as kv_mod
    kv = kv_mod.create("local")
    kv.init("w", nd.array(np.ones((8, 2), np.float32)))
    g = _rsp_grad((8, 2), [2, 6], seed=3)
    kv.push("w", g)
    out = nd.zeros((8, 2))
    kv.pull("w", out=out)
    res = out.asnumpy()
    np.testing.assert_array_equal(res[[0, 1, 3, 4, 5, 7]],
                                  np.zeros((6, 2), np.float32))
    np.testing.assert_allclose(res[[2, 6]], np.asarray(g._data), atol=1e-6)


# ---------------------------------------------------------------------------
# other sparse kernels + fallback discipline
# ---------------------------------------------------------------------------

def test_elemwise_add_rsp_rsp():
    a = _rsp_grad((9, 3), [0, 4], seed=6)
    b = _rsp_grad((9, 3), [4, 8], seed=7)
    out = sparse.add(a, b)
    assert out.stype == "row_sparse"
    assert sorted(np.asarray(out._indices)) == [0, 4, 8]
    np.testing.assert_allclose(out.todense().asnumpy(),
                               a.todense().asnumpy() + b.todense().asnumpy(),
                               rtol=1e-6)


def test_mask_pack_roundtrip_preserves_zero_rows():
    # a pushed row whose gradient is exactly zero must survive the packed
    # reduce (lazy updates still apply wd/momentum to it)
    import jax.numpy as jnp
    data = np.array([[0.0, 0.0], [1.5, -2.0]], np.float32)
    rsp = sparse.RowSparseNDArray(jnp.asarray(data),
                                  np.array([3, 7], np.int32), (10, 2))
    packed = sparse.mask_pack(rsp)
    assert packed.shape == (10, 3)
    back = sparse.mask_unpack(packed, (10, 2))
    assert sorted(np.asarray(back._indices)) == [3, 7]
    np.testing.assert_allclose(back.todense().asnumpy(),
                               rsp.todense().asnumpy(), atol=1e-6)


@pytest.mark.parametrize("axis,keepdims", [(None, False), (0, False),
                                           (1, False), (1, True),
                                           ((0, 1), False)])
def test_sum_csr(axis, keepdims):
    csr, dense = _random_csr(7, 11, 0.3, seed=9)
    out = nd.op.sum(csr, axis=axis, keepdims=keepdims)
    ref = dense.sum(axis=axis, keepdims=keepdims)
    np.testing.assert_allclose(np.asarray(out.asnumpy()).reshape(ref.shape)
                               if hasattr(ref, "shape") else out.asnumpy(),
                               ref, rtol=1e-5, atol=1e-5)


def test_dense_fallback_warns_and_computes():
    from mxnet_tpu.ops import registry as reg
    reg._FALLBACK_WARNED.clear()
    csr, dense = _random_csr(5, 6, 0.4, seed=10)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = nd.op.tanh(csr)
    assert any("falling back to dense" in str(w.message) for w in caught)
    np.testing.assert_allclose(out.asnumpy(), np.tanh(dense), rtol=1e-5,
                               atol=1e-5)
    # warned once only
    with warnings.catch_warnings(record=True) as caught2:
        warnings.simplefilter("always")
        nd.op.tanh(csr)
    assert not any("falling back" in str(w.message) for w in caught2)


def test_gluon_embedding_sparse_grad_end_to_end():
    from mxnet_tpu import gluon
    layer = gluon.nn.Embedding(30, 4, sparse_grad=True)
    layer.initialize()
    x = nd.array(np.array([[1.0, 2.0], [2.0, 9.0]]))
    with autograd.record():
        out = layer(x)
        out.sum().backward()
    g = layer.weight.grad()
    assert isinstance(g, sparse.RowSparseNDArray)
    assert sorted(np.asarray(g._indices)) == [1, 2, 9]
    # trainer step consumes the sparse grad through the lazy path
    trainer = gluon.Trainer(layer.collect_params(), "sgd",
                            {"learning_rate": 0.5})
    before = layer.weight.data().asnumpy().copy()
    trainer.step(1)
    after = layer.weight.data().asnumpy()
    untouched = [i for i in range(30) if i not in (1, 2, 9)]
    np.testing.assert_array_equal(after[untouched], before[untouched])
    assert not np.allclose(after[[1, 2, 9]], before[[1, 2, 9]])


# ---------------------------------------------------------------------------
# jit trace-path round-trips (row_sparse crosses
# into a jitted program as a FIXED-SHAPE dense packed buffer; nnz varies
# per step, the compiled program does not)
# ---------------------------------------------------------------------------

def test_mask_pack_is_a_fixed_shape_jit_boundary():
    """mask_pack -> jitted dense reduce -> mask_unpack: the row_sparse ->
    dense boundary inside a jitted program. The program traces ONCE for
    the dense packed shape while nnz varies per call, and the round-trip
    reassembles the union row set bitwise."""
    import jax
    import jax.numpy as jnp
    traces = []

    @jax.jit
    def reduce_two(a, b):
        traces.append(1)
        summed = a + b  # the dense cross-worker reduce
        mask = (summed[:, -1:] > 0).astype(a.dtype)
        return jnp.concatenate([summed[:, :-1], mask], axis=1)

    shape = (10, 3)
    for seed, (r1, r2) in enumerate([([1, 4], [4, 7]),
                                     ([0, 2, 9], [2]),
                                     ([5], [5])]):
        g1 = _rsp_grad(shape, r1, seed=20 + seed)
        g2 = _rsp_grad(shape, r2, seed=40 + seed)
        packed = reduce_two(sparse.mask_pack(g1)._data,
                            sparse.mask_pack(g2)._data)
        back = sparse.mask_unpack(nd.from_jax(packed), shape)
        assert sorted(np.asarray(back._indices)) == \
            sorted(set(r1) | set(r2))
        np.testing.assert_array_equal(
            back.todense().asnumpy(),
            g1.todense().asnumpy() + g2.todense().asnumpy())
    assert len(traces) == 1  # nnz varied three ways, the program replayed


def test_mask_pack_jit_reduce_keeps_cancelled_rows():
    """A row whose reduced gradient sums to exactly zero must survive the
    jitted reduce via the mask column (lazy updates still apply wd /
    momentum to every pushed row — dropping it would silently skip them)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def reduce_two(a, b):
        summed = a + b
        mask = (summed[:, -1:] > 0).astype(a.dtype)
        return jnp.concatenate([summed[:, :-1], mask], axis=1)

    shape = (8, 2)
    g1 = _rsp_grad(shape, [4], seed=3)
    g2 = sparse.RowSparseNDArray(-g1._data, np.array([4], np.int32), shape)
    packed = reduce_two(sparse.mask_pack(g1)._data,
                        sparse.mask_pack(g2)._data)
    back = sparse.mask_unpack(nd.from_jax(packed), shape)
    assert list(np.asarray(back._indices)) == [4]
    np.testing.assert_array_equal(np.asarray(back._data),
                                  np.zeros((1, 2), np.float32))


def test_autograd_row_sparse_grad_through_jitted_program_matches_eager():
    """End-to-end over the real autograd product: an Embedding
    sparse_grad backward's row_sparse gradient rides mask_pack through a
    jitted dense transform and unpacks to the same rows and values the
    eager dense path computes."""
    import jax

    W = np.random.RandomState(8).randn(20, 4).astype(np.float32)
    w = nd.array(W)
    w.attach_grad(stype="row_sparse")
    with autograd.record():
        e = nd.Embedding(nd.array(np.array([3.0, 11.0, 3.0])), w,
                         input_dim=20, output_dim=4, sparse_grad=True)
        (e * e).sum().backward()
    g = w.grad
    assert isinstance(g, sparse.RowSparseNDArray)

    @jax.jit
    def halve(packed):
        return packed.at[:, :-1].multiply(0.5)  # data halved, mask kept

    back = sparse.mask_unpack(
        nd.from_jax(halve(sparse.mask_pack(g)._data)), g.shape)
    assert sorted(np.asarray(back._indices)) == [3, 11]
    np.testing.assert_array_equal(back.todense().asnumpy(),
                                  g.todense().asnumpy() * 0.5)


def test_hybridize_sparse_grad_warns_but_correct():
    from mxnet_tpu import gluon
    layer = gluon.nn.Embedding(20, 3, sparse_grad=True)
    layer.initialize()
    layer.hybridize()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with autograd.record():
            out = layer(nd.array(np.array([4.0, 4.0])))
            out.sum().backward()
    assert any("row_sparse" in str(w.message) for w in caught)
    g = layer.weight.grad()
    assert isinstance(g, sparse.RowSparseNDArray)
    assert g.todense().asnumpy()[4].sum() == pytest.approx(6.0)
