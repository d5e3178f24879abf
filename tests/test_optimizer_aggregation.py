"""Aggregated (multi-tensor) optimizer step: parity with the per-param
path, dispatch-count regression, sparse bypass, bucketed allreduce
(ref: optimizer_op.cc multi_sgd_update + MXNET_OPTIMIZER_AGGREGATION_SIZE;
DDP-style gradient bucketing for the allreduce side)."""
import functools

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import nd, gluon
from mxnet_tpu.base import MXNetError
from mxnet_tpu.optimizer import grouped as grouped_mod


def _make_params(rs, n=6, dtype="float32", shapes=None):
    """``dtype`` is one name, or a tuple of names the parameters cycle
    through (mixed-precision nets: two bucket keys)."""
    dtypes = (dtype,) if isinstance(dtype, str) else dtype
    params = []
    for j in range(n):
        shape = shapes[j] if shapes else (3, j + 2)
        p = gluon.Parameter(f"p{j}", shape=shape,
                            dtype=dtypes[j % len(dtypes)])
        p.initialize(mx.init.Constant(0.0))
        p.set_data(nd.array(rs.randn(*shape).astype(np.float32)))
        params.append(p)
    return params


def _set_grads(params, rs, poison_at=None):
    for k, p in enumerate(params):
        g = rs.randn(*p.shape).astype(np.float32)
        if poison_at is not None and k == poison_at:
            g[0, 0] = np.nan
        garr = nd.array(g)
        if str(p.data().dtype) != "float32":
            garr = garr.astype(p.data().dtype)
        p._grad._rebind(garr._data)
        p._fresh_grad = True


OPTS = [
    ("sgd", {"learning_rate": 0.1, "wd": 0.01}),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 0.01}),
    ("nag", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.01, "wd": 0.001}),
    ("rmsprop", {"learning_rate": 0.01}),
    ("rmsprop", {"learning_rate": 0.01, "centered": True}),
]


def _set_agg(monkeypatch, agg):
    """``None`` leaves the variable unset: the default, one program a
    bucket key."""
    if agg is None:
        monkeypatch.delenv("MXTPU_OPTIMIZER_AGGREGATION", raising=False)
    else:
        monkeypatch.setenv("MXTPU_OPTIMIZER_AGGREGATION", str(agg))


def _run_steps(opt, kw, agg, monkeypatch, steps=3, dtype="float32", n=6,
               seed=0):
    _set_agg(monkeypatch, agg)
    rs = np.random.RandomState(seed)
    params = _make_params(rs, n=n, dtype=dtype)
    tr = gluon.Trainer(params, opt, dict(kw), kvstore=None)
    for _ in range(steps):
        _set_grads(params, rs)
        tr.step(4)
    return params, tr


@pytest.mark.parametrize("opt,kw", OPTS,
                         ids=[f"{o}-{'-'.join(k)}" for o, k in
                              [(o, list(kw)) for o, kw in OPTS]])
def test_aggregated_matches_per_param(opt, kw, monkeypatch):
    """Tentpole acceptance: 3 aggregated steps == 3 per-param steps to
    fp32 tolerance, for every grouped optimizer."""
    ref, tr_ref = _run_steps(opt, kw, 0, monkeypatch)
    got, tr_got = _run_steps(opt, kw, 4, monkeypatch)
    assert tr_ref.last_update_dispatches == len(ref)
    assert tr_got.last_update_dispatches == 2  # ceil(6/4) buckets
    for pr, pg in zip(ref, got):
        np.testing.assert_allclose(pr.data().asnumpy(), pg.data().asnumpy(),
                                   rtol=1e-5, atol=1e-6)
    # optimizer state must agree too (momentum/mean/var trajectories)
    for i in tr_ref._updaters[0].states:
        sr, sg = tr_ref._updaters[0].states[i], tr_got._updaters[0].states[i]
        flat_r = grouped_mod._flatten_inner(sr)
        flat_g = grouped_mod._flatten_inner(sg)
        for a, b in zip(flat_r, flat_g):
            np.testing.assert_allclose(a.asnumpy(), b.asnumpy(),
                                       rtol=1e-5, atol=1e-6)


def test_aggregated_multi_precision_parity(monkeypatch):
    """bf16 weights + multi_precision: the fused path must route through
    the same f32 master-weight math as Optimizer.update_multi_precision —
    master copies match to fp32 tolerance, weights bitwise as bf16."""
    kw = {"learning_rate": 0.05, "momentum": 0.9, "multi_precision": True}
    ref, tr_ref = _run_steps("sgd", kw, 0, monkeypatch, dtype="bfloat16")
    got, tr_got = _run_steps("sgd", kw, 3, monkeypatch, dtype="bfloat16")
    for i in range(len(ref)):
        w32_ref = tr_ref._updaters[0].states[i][1].asnumpy()
        w32_got = tr_got._updaters[0].states[i][1].asnumpy()
        np.testing.assert_allclose(w32_ref, w32_got, rtol=1e-6)
        np.testing.assert_array_equal(
            ref[i].data().astype("float32").asnumpy(),
            got[i].data().astype("float32").asnumpy())


def test_loss_scale_skip_step_parity(monkeypatch):
    """A non-finite step must be a perfect no-op under BOTH flows: the
    per-param path (host check, update never called) and the fused path
    (where-guard + rollback). Trajectories including a poisoned middle
    step stay identical — Adam's bias-correction counter included."""
    kw = {"learning_rate": 0.01}

    def run(agg):
        monkeypatch.setenv("MXTPU_OPTIMIZER_AGGREGATION", str(agg))
        rs = np.random.RandomState(3)
        params = _make_params(rs, n=5)
        tr = gluon.Trainer(params, "adam", dict(kw), kvstore=None)
        for step in range(3):
            _set_grads(params, rs, poison_at=2 if step == 1 else None)
            flag = tr.update_with_sentinel(4)
            if flag is not None:          # fused flow
                if not bool(jax.device_get(flag)):
                    tr.rollback_step()
                    for p in params:
                        p.zero_grad()
            else:                         # classic FitLoop flow
                finite = all(np.isfinite(p.grad().asnumpy()).all()
                             for p in params)
                if finite:
                    tr.update(4)
                else:
                    for p in params:
                        p.zero_grad()
        return params, tr

    ref, tr_ref = run(0)
    got, tr_got = run(4)
    assert tr_got._optimizer.num_update == tr_ref._optimizer.num_update == 2
    for pr, pg in zip(ref, got):
        np.testing.assert_allclose(pr.data().asnumpy(), pg.data().asnumpy(),
                                   rtol=1e-5, atol=1e-7)


def test_skipped_fused_step_creates_no_state(monkeypatch):
    """State creation is an observable side effect: when the FIRST step is
    skipped, rollback must also remove the freshly-created optimizer
    state, matching the per-param path where update never ran."""
    monkeypatch.setenv("MXTPU_OPTIMIZER_AGGREGATION", "4")
    rs = np.random.RandomState(0)
    params = _make_params(rs, n=3)
    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.1,
                                       "momentum": 0.9}, kvstore=None)
    _set_grads(params, rs, poison_at=0)
    flag = tr.update_with_sentinel(2)
    assert flag is not None and not bool(jax.device_get(flag))
    tr.rollback_step()
    assert not tr._updaters[0].states
    assert tr._optimizer.num_update == 0


@pytest.mark.parametrize("agg,launches", [
    (0, 50),                              # O(params)
    (4, 13),                              # ceil(50/4) buckets
    (64, 1),                              # one bucket covers everything
    (1, 50),                              # degenerate cap still works
    (None, 1),                            # unset: one program a bucket key
], ids=["off", "cap4", "cap64", "cap1", "unset"])
def test_dispatch_count_regression(agg, launches, monkeypatch):
    """Acceptance: a 50-param model steps in ONE compiled-call launch by
    default, O(buckets) under an explicit cap, O(params) with
    MXTPU_OPTIMIZER_AGGREGATION=0."""
    _set_agg(monkeypatch, agg)
    rs = np.random.RandomState(0)
    params = _make_params(rs, n=50, shapes=[(4, 4)] * 50)
    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.1,
                                       "momentum": 0.9}, kvstore=None)
    _set_grads(params, rs)
    tr.step(8)
    assert tr.last_update_dispatches == launches


def _state_arrays(tr):
    """Every optimizer-state array of ``tr`` as numpy, by parameter index
    (the f32 master weight of a multi-precision parameter included)."""
    out = {}
    for i, st in sorted(tr._updaters[0].states.items()):
        flat = []
        for part in (st if isinstance(st, (tuple, list)) else [st]):
            flat += grouped_mod._flatten_inner(part)
        out[i] = [a.asnumpy() for a in flat]
    return out


@pytest.mark.parametrize("opt,kw,dtype,per_param_exact", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9,
             "multi_precision": True}, "bfloat16", True),
    ("nag", {"learning_rate": 0.1, "momentum": 0.9}, "float32", True),
    ("adam", {"learning_rate": 0.01, "wd": 0.001}, "float32", True),
    ("rmsprop", {"learning_rate": 0.01, "centered": True}, "float32", True),
    # the per-parameter SGD ops bake ``wd`` into their program as a
    # constant and the grouped ones take it as an argument, so XLA rounds
    # ``rescale * g + wd * w`` differently there (the last bit, at any
    # cap): the groupings still agree with each other to the byte
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 0.01,
             "multi_precision": True}, "bfloat16", False),
], ids=["sgd-mom-mp-bf16", "nag", "adam-wd", "rmsprop-centered",
        "sgd-mom-wd-mp-bf16"])
def test_uncapped_default_is_bitwise_a_cap_of_four_and_per_param(
        opt, kw, dtype, per_param_exact, monkeypatch):
    """The grouping changes how many programs carry the update, never its
    arithmetic: over three steps the unset default (one program), a cap of
    4 (three programs) and ``0`` (the per-parameter loop) leave the same
    bytes in every weight and every state array."""
    runs = {agg: _run_steps(opt, kw, agg, monkeypatch, dtype=dtype, n=10)
            for agg in (None, 4, 0)}
    assert [runs[a][1].last_update_dispatches for a in (None, 4, 0)] \
        == [1, 3, 10]
    ref_params, ref_tr = runs[None]
    ref_states = _state_arrays(ref_tr)
    assert ref_states and all(ref_states.values())
    for agg in (4, 0):
        if agg or per_param_exact:
            same = np.testing.assert_array_equal
        else:
            same = functools.partial(np.testing.assert_allclose, rtol=2e-6)
        params, tr = runs[agg]
        for pr, pg in zip(ref_params, params):
            same(pr.data().astype("float32").asnumpy(),
                 pg.data().astype("float32").asnumpy())
        states = _state_arrays(tr)
        assert states.keys() == ref_states.keys()
        for i in states:
            assert len(states[i]) == len(ref_states[i])
            for a, b in zip(ref_states[i], states[i]):
                same(a, b)


_SGD_MP = {"learning_rate": 0.1, "momentum": 0.9, "multi_precision": True}


@pytest.mark.parametrize("opt,kw,dtype,agg,programs", [
    ("sgd", _SGD_MP, "bfloat16", 4, 3),   # ceil(10 / 4) buckets
    ("adam", {"learning_rate": 0.01, "wd": 0.001}, "float32", 4, 3),
    ("sgd", _SGD_MP, "bfloat16", None, 1),  # unset: one a bucket key
    # bfloat16 parameters keep a float32 master and float32 ones do not:
    # two keys, two programs
    ("sgd", _SGD_MP, ("bfloat16", "float32"), None, 2),
], ids=["sgd-mom-mp-bf16", "adam", "unset-one-dtype", "unset-two-dtypes"])
def test_update_launches_only_its_bucket_programs(opt, kw, dtype, agg,
                                                  programs, monkeypatch,
                                                  tmp_path):
    """What a warm ``Trainer.step`` launches, read from the profiler's host
    plane and not from the framework's own counter: one program a bucket,
    and no ``convert_element_type`` for a learning rate, a weight decay or
    ``rescale_grad`` (as ``jnp.asarray(list, float32)`` each of those was a
    put and a cast program: two a bucket and one a step). With the variable
    unset a bucket is a whole bucket key, however many parameters."""
    from test_step_spans import _host_spans, _launches_inside, \
        _profiler_session
    params, tr = _run_steps(opt, kw, agg, monkeypatch, steps=2, dtype=dtype,
                            n=10)
    _set_grads(params, np.random.RandomState(1))
    with _profiler_session(tmp_path):
        tr.step(4)
    launched = _launches_inside(_host_spans(tmp_path), "mx.trainer.update")
    assert tr.last_update_dispatches == programs
    assert len(launched) == tr.last_update_dispatches, launched
    assert not [n for n in launched if "convert_element_type" in n]


@pytest.mark.parametrize("good_steps", [0, 2], ids=["first-step", "warm"])
def test_sentinel_skip_with_one_bucket_leaves_every_byte(good_steps,
                                                         monkeypatch):
    """The sentinel's skip is all or nothing, and with the variable unset
    "all" is one program: a poisoned step leaves every weight and every
    state array byte for byte, and a poisoned FIRST step creates no
    state."""
    _set_agg(monkeypatch, None)
    rs = np.random.RandomState(0)
    params = _make_params(rs, n=7)
    tr = gluon.Trainer(params, "adam", {"learning_rate": 0.01, "wd": 0.001},
                       kvstore=None)
    for _ in range(good_steps):
        _set_grads(params, rs)
        flag = tr.update_with_sentinel(4)
        assert bool(jax.device_get(flag))
        assert tr.last_update_dispatches == 2  # the flag and ONE bucket
    weights = [p.data().asnumpy().copy() for p in params]
    states = _state_arrays(tr)
    counts = dict(tr._optimizer._index_update_count)
    _set_grads(params, rs, poison_at=5)
    flag = tr.update_with_sentinel(4)
    assert flag is not None and not bool(jax.device_get(flag))
    assert tr.last_update_dispatches == 2
    tr.rollback_step()
    for p, w in zip(params, weights):
        np.testing.assert_array_equal(p.data().asnumpy(), w)
    after = _state_arrays(tr)
    assert after.keys() == states.keys()
    assert bool(after) == bool(good_steps)  # no state out of a skipped step
    for i in states:
        for a, b in zip(states[i], after[i]):
            np.testing.assert_array_equal(a, b)
    assert {i: c for i, c in tr._optimizer._index_update_count.items()
            if c} == counts
    assert tr._optimizer.num_update == good_steps


@pytest.mark.parametrize("agg,n", [(8, 6), (None, 50)],
                         ids=["cap8", "unset"])
def test_signature_cache_no_per_step_recompile(agg, n, monkeypatch):
    """Steady-state steps must HIT the signature cache (the CachedOp
    discipline): changing lr / rescale between steps may not mint new
    compiled programs. With the variable unset one program of 50 bundles
    is one signature."""
    _set_agg(monkeypatch, agg)
    grouped_mod.clear_cache()
    rs = np.random.RandomState(0)
    params = _make_params(rs, n=n)
    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.1,
                                       "momentum": 0.9}, kvstore=None)
    _set_grads(params, rs)
    tr.step(4)
    misses0 = grouped_mod.cache_info().misses
    assert misses0 == 1
    for step in range(4):
        tr.set_learning_rate(0.1 / (step + 2))  # scheduled-lr churn
        _set_grads(params, rs)
        tr.step(4 + step)                        # batch-size churn too
    info = grouped_mod.cache_info()
    assert info.misses == misses0, \
        "per-step lr/batch churn recompiled the bucket program"
    assert info.hits >= 4


def test_adam_bias_corrected_lr_mints_no_program(monkeypatch):
    """Adam's bias-corrected learning rate is another float every step: it
    travels as a value of the same ``float32[n]`` argument, so steps two and
    three find the signature cache and the compiler where step one left
    them."""
    from mxnet_tpu import telemetry
    monkeypatch.setenv("MXTPU_OPTIMIZER_AGGREGATION", "4")
    grouped_mod.clear_cache()
    rs = np.random.RandomState(0)
    params = _make_params(rs, n=6)
    tr = gluon.Trainer(params, "adam", {"learning_rate": 0.01, "wd": 0.001},
                       kvstore=None)
    _set_grads(params, rs)
    tr.step(4)
    reg = telemetry.default_registry()
    misses0 = grouped_mod.cache_info().misses
    compiles0 = reg.render_json().get("mxtpu_xla_compile_total", 0)
    assert misses0 >= 1
    for _ in range(2):
        _set_grads(params, rs)
        tr.step(4)
    assert grouped_mod.cache_info().misses == misses0
    assert reg.render_json().get("mxtpu_xla_compile_total", 0) == compiles0


def test_sparse_params_bypass_aggregation(monkeypatch):
    """Satellite: row_sparse-grad params must fall back to the per-param
    loop while dense neighbors still aggregate; _contains_sparse trainers
    work unchanged."""
    monkeypatch.setenv("MXTPU_OPTIMIZER_AGGREGATION", "4")
    rs = np.random.RandomState(0)
    dense = _make_params(rs, n=4)
    emb = gluon.Parameter("emb", shape=(10, 3), grad_stype="row_sparse")
    emb.initialize(mx.init.Constant(0.0))
    emb.set_data(nd.array(rs.randn(10, 3).astype(np.float32)))
    params = dense + [emb]
    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.1}, kvstore=None)
    _set_grads(dense, rs)
    from mxnet_tpu.ndarray import sparse as _sp
    rows = np.array([1, 4], dtype=np.int32)
    vals = rs.randn(2, 3).astype(np.float32)
    emb._grad._update(nd.array(vals)._data, nd.array(rows)._data)
    emb._fresh_grad = True
    w_emb = emb.data().asnumpy().copy()
    tr.step(2)
    # dense riders: 1 aggregated launch; sparse straggler: 1 per-param
    assert tr.last_update_dispatches == 2
    expect = w_emb.copy()
    expect[rows] -= 0.1 * (vals / 2.0)
    np.testing.assert_allclose(emb.data().asnumpy(), expect,
                               rtol=1e-5, atol=1e-6)


def test_grouped_update_asserts_dense_inputs(monkeypatch):
    """The grouped path refuses sparse inputs loudly instead of
    densifying them behind the caller's back."""
    rs = np.random.RandomState(0)
    emb = gluon.Parameter("emb", shape=(6, 2), grad_stype="row_sparse")
    emb.initialize(mx.init.Constant(0.0))
    tr = gluon.Trainer([emb], "sgd", {"learning_rate": 0.1}, kvstore=None)
    with pytest.raises(MXNetError, match="dense"):
        grouped_mod.grouped_update(tr._updaters[0], [(0, emb)], 4)
    # and the sentinel path reports ineligibility instead of raising
    assert not grouped_mod.eligible(tr._updaters[0], [(0, emb)])


def test_sentinel_unavailable_for_ungrouped_optimizer(monkeypatch):
    """update_with_sentinel returns None (caller falls back) for
    optimizers without a grouping rule — and applies nothing."""
    monkeypatch.setenv("MXTPU_OPTIMIZER_AGGREGATION", "4")
    rs = np.random.RandomState(0)
    params = _make_params(rs, n=2)
    tr = gluon.Trainer(params, "ftrl", {"learning_rate": 0.1}, kvstore=None)
    _set_grads(params, rs)
    before = [p.data().asnumpy().copy() for p in params]
    assert tr.update_with_sentinel(2) is None
    for p, w in zip(params, before):
        np.testing.assert_array_equal(p.data().asnumpy(), w)
    assert all(p._fresh_grad for p in params), \
        "a declined sentinel call must leave the step fully pending"


def test_sentinel_declines_on_stale_without_raising(monkeypatch):
    """skip_nonfinite + a stale param + overflowing grads: the classic
    flow checks finiteness first and skips WITHOUT reaching the stale
    pre-scan, so the fused path must decline (None) rather than raise —
    the caller's fallback then reproduces the old ordering exactly."""
    monkeypatch.setenv("MXTPU_OPTIMIZER_AGGREGATION", "4")
    rs = np.random.RandomState(0)
    params = _make_params(rs, n=3)
    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.1}, kvstore=None)
    _set_grads(params, rs, poison_at=0)
    params[1]._fresh_grad = False  # unused-in-loss straggler
    before = [p.data().asnumpy().copy() for p in params]
    assert tr.update_with_sentinel(2) is None  # declined, nothing touched
    for p, w in zip(params, before):
        np.testing.assert_array_equal(p.data().asnumpy(), w)
    # the classic flow the caller falls back to: host check -> skip
    finite = all(np.isfinite(p.grad().asnumpy()).all() for p in params)
    assert not finite


def test_sentinel_covers_stale_grads(monkeypatch):
    """The fused flag must cover EVERY live grad — a stale NaN grad
    skipped under ignore_stale_grad still poisons the classic host check
    (FitLoop._grads_finite_flag iterates all non-null grads), so the
    fused path must skip the step identically."""
    monkeypatch.setenv("MXTPU_OPTIMIZER_AGGREGATION", "4")
    rs = np.random.RandomState(0)
    params = _make_params(rs, n=3)
    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.1}, kvstore=None)
    _set_grads(params, rs)
    # params[2] goes stale-with-NaN: fresh flag cleared, buffer poisoned
    bad = np.full(params[2].shape, np.nan, np.float32)
    params[2]._grad._rebind(nd.array(bad)._data)
    params[2]._fresh_grad = False
    before = [p.data().asnumpy().copy() for p in params[:2]]
    flag = tr.update_with_sentinel(2, ignore_stale_grad=True)
    assert flag is not None and not bool(jax.device_get(flag)), \
        "stale NaN grad must poison the fused flag like the host check"
    tr.rollback_step()
    for p, w in zip(params[:2], before):
        np.testing.assert_array_equal(p.data().asnumpy(), w)


def test_bucketed_allreduce_values_and_collective_count(monkeypatch):
    """Satellite: allreduce_grads issues one kvstore collective per
    bucket, values bit-preserved through flatten -> reduce -> split."""
    rs = np.random.RandomState(0)
    params = _make_params(rs, n=7, shapes=[(8, j + 1) for j in range(7)])
    grads = [rs.randn(*p.shape).astype(np.float32) for p in params]

    def setg():
        for p, g in zip(params, grads):
            p._grad._rebind(nd.array(g)._data)
            p._fresh_grad = True

    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                       kvstore="device")
    setg()
    tr.allreduce_grads()
    if tr._kvstore is None:
        pytest.skip("single-device backend: kvstore degraded to local")
    assert tr.last_allreduce_collectives == 1  # everything fits one bucket
    for p, g in zip(params, grads):
        np.testing.assert_allclose(p.grad().asnumpy(), g, rtol=1e-6)

    monkeypatch.setenv("MXTPU_GRAD_BUCKET_MB", "0")  # per-key fallback
    setg()
    tr.allreduce_grads()
    assert tr.last_allreduce_collectives == 7
    for p, g in zip(params, grads):
        np.testing.assert_allclose(p.grad().asnumpy(), g, rtol=1e-6)

    monkeypatch.setenv("MXTPU_GRAD_BUCKET_MB", "0.0001")  # ~100B buckets
    tr2 = gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                        kvstore="device")
    setg()
    tr2.allreduce_grads()
    assert 1 < tr2.last_allreduce_collectives < 7
    for p, g in zip(params, grads):
        np.testing.assert_allclose(p.grad().asnumpy(), g, rtol=1e-6)


def test_bucketed_allreduce_mixed_dtype_and_sparse(monkeypatch):
    """dtype boundaries split buckets; row_sparse grads keep their
    per-key path alongside the bucketed dense ones."""
    rs = np.random.RandomState(1)
    p32 = _make_params(rs, n=2, shapes=[(4, 4), (4, 4)])
    p16 = []
    for j in range(2):
        p = gluon.Parameter(f"h{j}", shape=(4, 4), dtype="bfloat16")
        p.initialize(mx.init.Constant(0.0))
        p.set_data(nd.array(rs.randn(4, 4).astype(np.float32)))
        p16.append(p)
    params = [p32[0], p16[0], p32[1], p16[1]]  # interleave dtypes
    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                       kvstore="device")
    for p in params:
        g = nd.array(rs.randn(4, 4).astype(np.float32))
        if str(p.data().dtype) != "float32":
            g = g.astype(p.data().dtype)
        p._grad._rebind(g._data)
        p._fresh_grad = True
    tr.allreduce_grads()
    if tr._kvstore is None:
        pytest.skip("single-device backend: kvstore degraded to local")
    # interleaved dtypes force a bucket break at every boundary
    assert tr.last_allreduce_collectives == 4


@pytest.mark.parametrize("op,group,n_state", [
    ("multi_adam_update", 4, 2),
    ("multi_nag_mom_update", 3, 1),
    ("multi_rmsprop_update", 3, 1),
])
def test_multi_tensor_ops_match_singles(op, group, n_state):
    """The registered multi-tensor op surface (reference: the
    optimizer_op.cc multi_sgd family, extended beyond SGD) computes the
    same values as N single-tensor invocations."""
    single = {"multi_adam_update": "adam_update",
              "multi_nag_mom_update": "nag_mom_update",
              "multi_rmsprop_update": "rmsprop_update"}[op]
    rs = np.random.RandomState(0)
    n = 3
    packs = []
    for _ in range(n):
        w = nd.array(rs.randn(4).astype(np.float32))
        g = nd.array(rs.randn(4).astype(np.float32))
        states = [nd.zeros((4,)) for _ in range(n_state)]
        packs.append([w, g] + states)
    lrs = tuple(0.1 * (i + 1) for i in range(n))
    wds = tuple(0.01 * i for i in range(n))
    flat = [t.copy() for pack in packs for t in pack]
    outs = nd.imperative_invoke(op, tuple(flat),
                                {"lrs": lrs, "wds": wds, "num_weights": n})
    for i, pack in enumerate(packs):
        ref = nd.imperative_invoke(single, tuple(pack),
                                   {"lr": lrs[i], "wd": wds[i]})
        ref_w = ref[0] if isinstance(ref, (tuple, list)) else ref
        np.testing.assert_allclose(outs[i].asnumpy(), ref_w.asnumpy(),
                                   rtol=1e-6)


def test_fused_sentinel_through_fitloop(monkeypatch):
    """End to end: FitLoop rides the fused sentinel (one flag fetch, no
    per-grad host check) and still skips poisoned steps exactly."""
    monkeypatch.setenv("MXTPU_OPTIMIZER_AGGREGATION", "8")
    from mxnet_tpu import fit as fit_mod
    from mxnet_tpu.contrib import chaos
    from mxnet_tpu.io import NDArrayIter

    def build():
        mx.random.seed(0)
        net = gluon.nn.Dense(2, in_units=3)
        net.initialize(mx.init.Constant(0.5))
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05}, kvstore=None)
        rs = np.random.RandomState(0)
        it = NDArrayIter(rs.rand(16, 3).astype(np.float32),
                         rs.rand(16, 2).astype(np.float32), batch_size=4)
        loss = lambda out, y: ((out - y) ** 2).mean()
        return net, fit_mod.FitLoop(net, tr, loss, it, ckpt_dir=None)

    chaos.install("nan_grad@1")
    net_a, loop_a = build()
    res = loop_a.fit(epochs=1)
    chaos.uninstall() if hasattr(chaos, "uninstall") else chaos.install("")
    assert res.skipped_steps == [1]
    assert np.isfinite(net_a.weight.data().asnumpy()).all()

    # the same run per-param must land on the identical trajectory
    monkeypatch.setenv("MXTPU_OPTIMIZER_AGGREGATION", "0")
    chaos.install("nan_grad@1")
    net_b, loop_b = build()
    res_b = loop_b.fit(epochs=1)
    chaos.install("")
    assert res_b.skipped_steps == [1]
    np.testing.assert_allclose(net_a.weight.data().asnumpy(),
                               net_b.weight.data().asnumpy(), rtol=1e-6)
    np.testing.assert_allclose(res.losses, res_b.losses, rtol=1e-6)


@pytest.mark.parametrize("agg", [4, 0])
def test_adam_resume_bitwise_matches_uninterrupted(monkeypatch, tmp_path,
                                                   agg):
    """Kill/resume parity for Adam (graftcheck-adjacent state audit, PR 9
    note): the bias-correction counter ``t`` rides the state pickle via
    Updater.COUNTS_KEY, so a restore continues the t sequence. Pre-fix,
    t restarted at 1 after load_states and the resumed trajectory
    diverged from the uninterrupted one on the very first step."""
    kw = {"learning_rate": 0.01, "wd": 0.001}
    steps_total, steps_before = 6, 3

    # uninterrupted reference: 6 steps, one trainer
    params_a, _ = _run_steps("adam", kw, agg, monkeypatch,
                             steps=steps_total, seed=7)

    # interrupted: 3 steps, save, then a FRESH trainer (fresh optimizer,
    # fresh updater — the process-restart stand-in) restores and resumes
    # on the same gradient stream
    monkeypatch.setenv("MXTPU_OPTIMIZER_AGGREGATION", str(agg))
    rs = np.random.RandomState(7)
    params_b = _make_params(rs, n=6)
    tr = gluon.Trainer(params_b, "adam", dict(kw), kvstore=None)
    for _ in range(steps_before):
        _set_grads(params_b, rs)
        tr.step(4)
    fname = str(tmp_path / "trainer.states")
    tr.save_states(fname)
    saved_weights = [p.data().asnumpy().copy() for p in params_b]

    params_c = _make_params(np.random.RandomState(7), n=6)
    for p, w in zip(params_c, saved_weights):
        p.set_data(nd.array(w))
    tr2 = gluon.Trainer(params_c, "adam", dict(kw), kvstore=None)
    tr2.load_states(fname)
    # the counter must have resumed, not reset
    assert tr2._updaters[0].optimizer._index_update_count
    assert all(c == steps_before for c in
               tr2._updaters[0].optimizer._index_update_count.values())
    for _ in range(steps_total - steps_before):
        _set_grads(params_c, rs)
        tr2.step(4)

    for pa, pc in zip(params_a, params_c):
        assert np.array_equal(pa.data().asnumpy(), pc.data().asnumpy()), \
            f"{pa.name}: resumed Adam trajectory diverged (t not restored)"


def test_updater_states_roundtrip_accepts_legacy_pickle():
    """A pre-fix checkpoint (no reserved counter keys) must still load:
    counters then stay at their defaults exactly as before the fix."""
    import pickle
    from mxnet_tpu import optimizer as opt_mod
    up = opt_mod.get_updater(opt_mod.create("adam"))
    legacy = pickle.dumps({0: None, 1: None})
    up.set_states(legacy)
    assert set(up.states) == {0, 1}
    assert up.optimizer._index_update_count == {}
