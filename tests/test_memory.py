"""Device-memory observability plane (telemetry/memory.py).

The live-byte ledger must be EXACT on CPU for every tracked category —
that is the property that lets tier-1 enforce memory accounting on a
backend that reports no ``memory_stats`` at all — and the surfaces built
on it (per-step watermarks in FitResult, the chrome-trace memory counter
track, OOM forensics dumps, serving per-model bytes) must agree with it
byte-for-byte.
"""
import gc
import glob
import json
import os

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import nd, gluon, io as mxio, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.contrib import chaos
from mxnet_tpu.fit import FitLoop
from mxnet_tpu.io.staging import DeviceStagingIter
from mxnet_tpu.optimizer import grouped as grouped_mod
from mxnet_tpu.telemetry import dump_chrome_trace, validate_chrome_trace
from mxnet_tpu.telemetry import memory as mem

pytestmark = pytest.mark.memory

LED = mem.ledger()


def _flush():
    """Collect pending garbage BEFORE baselining, so an earlier test's
    dying net can't subtract its bytes between our snapshots."""
    gc.collect()
    return {c: LED.live_bytes(c) for c in mem.CATEGORIES}


def _param_bytes(params):
    return sum(p.data().size * p.data()._data.dtype.itemsize
               for p in params)


def _make_params(rs, n=4, dtype="float32", size=16):
    params = []
    for j in range(n):
        p = gluon.Parameter(f"memtest{j}", shape=(size, j + 2), dtype=dtype)
        p.initialize(mx.init.Constant(0.0))
        p.set_data(nd.array(rs.randn(size, j + 2).astype(np.float32)))
        params.append(p)
    return params


def _set_grads(params, rs, poison=False):
    for p in params:
        g = rs.randn(*p.shape).astype(np.float32)
        if poison:
            g.flat[0] = np.nan
        garr = nd.array(g)
        if str(p.data().dtype) != "float32":
            garr = garr.astype(p.data().dtype)
        p._grad._rebind(garr._data)
        p._fresh_grad = True


def _mlp(width=32, out=8, materialize=False):
    net = gluon.nn.HybridSequential()  # CachedOp needs a HybridBlock
    net.add(gluon.nn.Dense(width, activation="relu"),
            gluon.nn.Dense(out))
    net.initialize(mx.init.Xavier())
    if materialize:  # CachedOp needs shapes known up front
        net(nd.array(np.zeros((1, 16), np.float32)))
    return net


def _fit(steps=4, batch=8, staging=True, tracer=False, **fit_kw):
    rs = np.random.RandomState(0)
    net = _mlp()
    data = rs.randn(steps * batch, 16).astype(np.float32)
    label = rs.randint(0, 8, (steps * batch,)).astype(np.float32)
    it = mxio.NDArrayIter(data, label, batch_size=batch)
    if staging:
        it = DeviceStagingIter(it)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    was_on = telemetry.tracer.enabled
    if tracer:
        telemetry.tracer.clear()
        telemetry.enable()
    try:
        result = FitLoop(net, trainer, loss_fn, it,
                         ckpt_dir=None).fit(epochs=1, **fit_kw)
    finally:
        if tracer and not was_on:
            telemetry.disable()
    return result, net


# ---------------------------------------------------------------------------
# Ledger exactness per category
# ---------------------------------------------------------------------------

def test_params_and_grads_exact_then_freed():
    base = _flush()
    net = _mlp()
    x = nd.array(np.random.RandomState(0).randn(4, 16).astype(np.float32))
    net(x)  # deferred shapes materialize
    params = list(net.collect_params().values())
    expect = _param_bytes(params)
    assert LED.live_bytes("params") - base["params"] == expect
    assert LED.live_bytes("grads") - base["grads"] == expect
    del net, params
    gc.collect()
    assert LED.live_bytes("params") == base["params"]
    assert LED.live_bytes("grads") == base["grads"]


def test_grad_req_null_frees_grad_bytes():
    base = _flush()
    p = gluon.Parameter("memnull", shape=(32, 4))
    p.initialize(mx.init.One())
    nbytes = 32 * 4 * 4
    assert LED.live_bytes("grads") - base["grads"] == nbytes
    p.grad_req = "null"
    assert LED.live_bytes("grads") == base["grads"]
    assert LED.live_bytes("params") - base["params"] == nbytes


def test_cast_retracks_bytes():
    base = _flush()
    p = gluon.Parameter("memcast", shape=(64, 4))
    p.initialize(mx.init.One())
    assert LED.live_bytes("params") - base["params"] == 64 * 4 * 4
    p.cast("float16")
    assert LED.live_bytes("params") - base["params"] == 64 * 4 * 2
    assert LED.live_bytes("grads") - base["grads"] == 64 * 4 * 2


def test_optimizer_state_exact_and_rollback_frees():
    base = _flush()
    rs = np.random.RandomState(0)
    params = _make_params(rs, n=3)
    tr = gluon.Trainer(params, "adam", {"learning_rate": 0.01},
                       kvstore=None)
    # poisoned first step: the fused sentinel declines the update and
    # rollback must also release the state objects it just materialized
    _set_grads(params, rs, poison=True)
    flag = tr.update_with_sentinel(1)
    assert flag is not None and not bool(jax.device_get(flag))
    tr.rollback_step()
    gc.collect()
    assert LED.live_bytes("optimizer") == base["optimizer"], \
        "sentinel-skipped step leaked optimizer-state accounting"
    # clean step: adam m+v, both f32 like the weights -> exactly 2x
    _set_grads(params, rs)
    flag = tr.update_with_sentinel(1)
    assert bool(jax.device_get(flag))
    assert LED.live_bytes("optimizer") - base["optimizer"] == \
        2 * _param_bytes(params)
    assert LED.live_bytes("masters") == base["masters"]  # f32: no masters


def test_masters_split_out_for_multi_precision():
    base = _flush()
    rs = np.random.RandomState(1)
    params = _make_params(rs, n=3, dtype="bfloat16")
    tr = gluon.Trainer(params, "sgd",
                       {"learning_rate": 0.01, "momentum": 0.9,
                        "multi_precision": True}, kvstore=None)
    _set_grads(params, rs)
    tr.update(1)
    n_elems = sum(int(np.prod(p.shape)) for p in params)
    # f32 master copy per param; momentum rides the master dtype (f32)
    assert LED.live_bytes("masters") - base["masters"] == 4 * n_elems
    assert LED.live_bytes("optimizer") - base["optimizer"] == 4 * n_elems
    del tr, params
    gc.collect()
    assert LED.live_bytes("masters") == base["masters"]
    assert LED.live_bytes("optimizer") == base["optimizer"]


def test_masters_split_survives_kvstore_updater_path():
    """The optimizer pickle round-trip (kvstore.set_optimizer) drops
    param_dict, so the kvstore updater calls with param unresolvable —
    the masters split must come from the WEIGHT the updater holds."""
    import pickle
    base = _flush()
    from mxnet_tpu import optimizer as opt_mod
    opt = opt_mod.create("sgd", learning_rate=0.01, momentum=0.9,
                         multi_precision=True)
    opt = pickle.loads(pickle.dumps(opt))  # param_dict pickles away
    up = opt_mod.get_updater(opt)
    w = nd.array(np.ones((16, 4), np.float32)).astype("bfloat16")
    g = nd.array(np.ones((16, 4), np.float32)).astype("bfloat16")
    up(0, g, w)
    n = 16 * 4
    assert LED.live_bytes("masters") - base["masters"] == 4 * n, \
        "masters split lost on the kvstore-updater (param-less) path"
    assert LED.live_bytes("optimizer") - base["optimizer"] == 4 * n


def test_set_states_drops_stale_indices():
    """Checkpoint restore replaces the state dict wholesale; an index the
    restored dict lacks must not keep phantom optimizer bytes."""
    import pickle
    base = _flush()
    rs = np.random.RandomState(7)
    params = _make_params(rs, n=3)
    tr = gluon.Trainer(params, "adam", {"learning_rate": 0.01},
                       kvstore=None)
    _set_grads(params, rs)
    tr.step(1)
    up = tr._updaters[0]
    assert LED.live_bytes("optimizer") - base["optimizer"] == \
        2 * _param_bytes(params)
    partial = {i: s for i, s in up.states.items() if i != 2}
    up.set_states(pickle.dumps(partial))
    assert LED.live_bytes("optimizer") - base["optimizer"] == \
        2 * _param_bytes(params[:2]), \
        "stale index 2 kept phantom optimizer bytes after restore"


def test_grouped_donation_does_not_double_count():
    """Repeated fused (donated-buffer) steps must leave every category
    flat: donation rebinds outputs over the same logical params/states,
    so the ledger totals may not creep."""
    base = _flush()
    rs = np.random.RandomState(2)
    params = _make_params(rs, n=5)
    tr = gluon.Trainer(params, "adam", {"learning_rate": 0.01},
                       kvstore=None)
    _set_grads(params, rs)
    tr.step(1)
    after_one = {c: LED.live_bytes(c) for c in ("params", "grads",
                                                "optimizer", "masters")}
    for _ in range(4):
        _set_grads(params, rs)
        tr.step(1)
    for cat, val in after_one.items():
        assert LED.live_bytes(cat) == val, \
            f"{cat} grew across donated steps"
    assert after_one["optimizer"] - base["optimizer"] == \
        2 * _param_bytes(params)


def test_grad_bucket_bytes_tracked_and_stable():
    base = _flush()
    from mxnet_tpu import kvstore as kvs
    rs = np.random.RandomState(3)
    params = _make_params(rs, n=4)
    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.01},
                       kvstore=kvs.create("device"))
    for _ in range(3):
        _set_grads(params, rs)
        tr.step(1)
        gc.collect()
    # all 4 f32 grads fit one 25MB bucket -> ONE flat wire buffer stays
    # resident in the store; transients freed with each split
    flat_bytes = sum(int(np.prod(p.shape)) * 4 for p in params)
    assert LED.live_bytes("grad_buckets") - base["grad_buckets"] == \
        flat_bytes
    del tr
    gc.collect()
    assert LED.live_bytes("grad_buckets") == base["grad_buckets"]


def test_staging_bytes_rise_and_fall():
    base = _flush()
    rs = np.random.RandomState(4)
    data = rs.randn(6 * 4, 8).astype(np.float32)
    label = rs.randint(0, 2, (6 * 4,)).astype(np.float32)
    it = DeviceStagingIter(mxio.NDArrayIter(data, label, batch_size=4),
                           depth=2)
    batch_bytes = 4 * 8 * 4 + 4 * 4  # data + label per batch
    first = it.next()
    # depth=2: after serving one batch, 3 are staged ahead (depth+1)
    assert LED.live_bytes("staging") - base["staging"] == 3 * batch_bytes
    for _ in range(5):
        it.next()
    with pytest.raises(StopIteration):
        it.next()
    assert LED.live_bytes("staging") == base["staging"]
    # abandoned mid-epoch: reset + GC must not leak either
    it.reset()
    it.next()
    assert LED.live_bytes("staging") > base["staging"]
    del it, first
    gc.collect()
    assert LED.live_bytes("staging") == base["staging"]


def test_staging_finalizer_never_reenters_ledger_accessor(monkeypatch):
    """The abandoned-iterator finalizer must run against the MemoryLedger
    captured at construction, NOT re-resolve it through memory.ledger():
    that accessor runs first-use metrics installation under plain
    (non-reentrant) locks, and weakref.finalize can fire synchronously on
    a thread holding them — self-deadlock (graftcheck GC-L03, the PR 8
    ledger-bug class generalized). Simulated here by making the accessor
    explosive after construction: the finalizer must still free the
    staged bytes without ever calling it."""
    base = _flush()
    rs = np.random.RandomState(5)
    data = rs.randn(4 * 4, 8).astype(np.float32)
    label = rs.randint(0, 2, (4 * 4,)).astype(np.float32)
    it = DeviceStagingIter(mxio.NDArrayIter(data, label, batch_size=4),
                           depth=1)
    it.next()
    assert LED.live_bytes("staging") > base["staging"]

    def boom():
        raise AssertionError("finalizer re-entered memory.ledger()")

    monkeypatch.setattr(mem, "ledger", boom)
    del it
    gc.collect()
    assert LED.live_bytes("staging") == base["staging"]


# ---------------------------------------------------------------------------
# FitResult + trace counter track (the acceptance criterion)
# ---------------------------------------------------------------------------

def test_fit_memory_summary_matches_trace_counters(tmp_path):
    result, _net = _fit(steps=4, tracer=True)
    payload = dump_chrome_trace(str(tmp_path / "mem_trace.json"))
    validate_chrome_trace(payload)  # counter events are validator-clean
    peaks = [int(e["args"]["value"]) for e in payload["traceEvents"]
             if e.get("ph") == "C" and e["name"] == "device_memory_peak"]
    assert result.memory is not None
    per_step = result.memory["per_step"]
    assert len(per_step) == 4 and len(peaks) == 4
    assert peaks == [r["peak_bytes"] for r in per_step], \
        "trace memory track disagrees with FitResult memory summary"
    assert result.memory["peak_bytes"] == max(peaks)
    # the stacked track carries real categories with real bytes
    mem_events = [e for e in payload["traceEvents"]
                  if e.get("ph") == "C" and e["name"] == "device_memory"]
    assert mem_events
    cats = set().union(*(e["args"].keys() for e in mem_events))
    assert cats <= set(mem.CATEGORIES)
    assert {"params", "grads"} <= cats
    assert result.memory["by_category"]["params"] > 0
    # every per-step record carries the watermark pair
    for rec in per_step:
        assert rec["peak_bytes"] >= rec["live_bytes"] - max(
            rec["delta_bytes"], 0)
        assert "delta_bytes" in rec


def test_fit_memory_ledger_is_exact_on_cpu():
    result, net = _fit(steps=3, staging=False)
    params = list(net.collect_params().values())
    expect = _param_bytes(params)
    by_cat = result.memory["by_category"]
    assert by_cat["params"] >= expect
    # cross-check against the backend where it reports (CPU: it doesn't,
    # and reconcile must say so instead of inventing numbers)
    rec = mem.reconcile()
    assert rec["ledger_bytes"] == LED.live_bytes()
    if rec["backend_bytes_in_use"] is None:
        assert rec["consistent"] is None
    else:
        assert rec["consistent"]


# ---------------------------------------------------------------------------
# Forensics: chaos mem_pressure, budget watermark, OOM guard
# ---------------------------------------------------------------------------

def _dumps_in(d):
    return sorted(glob.glob(os.path.join(str(d), "mem_forensics_*.json")))


def test_mem_pressure_chaos_dump_parses(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_MEM_DUMP_DIR", str(tmp_path))
    plan = chaos.install("mem_pressure@2")
    try:
        _fit(steps=4)
    finally:
        chaos.uninstall()
    assert plan.injected["mem_pressure"] == 1
    dumps = _dumps_in(tmp_path)
    assert len(dumps) == 1, "mem_pressure@2 must fire exactly once"
    blob = json.load(open(dumps[0]))
    assert blob["reason"] == "chaos_mem_pressure"
    assert blob["step"] == 2
    assert blob["live_bytes"] > 0
    ranked = [c["category"] for c in blob["categories"]]
    assert "params" in ranked and "grads" in ranked
    shares = [c["bytes"] for c in blob["categories"]]
    assert shares == sorted(shares, reverse=True), "categories not ranked"
    owners = [b["owner"] for b in blob["top_buffers"]]
    assert any("dense" in o for o in owners), \
        f"top buffers must name their owners, got {owners[:5]}"


def test_mem_pressure_explicit_bytes_no_fire_when_under(tmp_path,
                                                        monkeypatch):
    monkeypatch.setenv("MXTPU_MEM_DUMP_DIR", str(tmp_path))
    plan = chaos.install(f"mem_pressure@1:{1 << 40}")  # 1 TiB: never over
    try:
        _fit(steps=3)
    finally:
        chaos.uninstall()
    assert plan.injected["mem_pressure"] == 1  # consumed...
    assert _dumps_in(tmp_path) == []           # ...but under budget


def test_budget_watermark_dumps_once(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_MEM_DUMP_DIR", str(tmp_path))
    monkeypatch.setenv("MXTPU_MEM_BUDGET", "1")
    _fit(steps=4)
    dumps = _dumps_in(tmp_path)
    assert len(dumps) == 1, \
        "budget breach must dump on the rising edge only, not per step"
    blob = json.load(open(dumps[0]))
    assert blob["reason"] == "budget_exceeded"
    assert blob["budget_bytes"] == 1


def test_oom_guard_dumps_and_reraises(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_MEM_DUMP_DIR", str(tmp_path))
    with pytest.raises(MXNetError, match="RESOURCE_EXHAUSTED"):
        with mem.oom_guard():
            raise MXNetError(
                "RESOURCE_EXHAUSTED: Out of memory allocating 2GB")
    dumps = _dumps_in(tmp_path)
    assert len(dumps) == 1
    blob = json.load(open(dumps[0]))
    assert blob["reason"] == "resource_exhausted"
    assert "RESOURCE_EXHAUSTED" in blob["error"]
    # a benign error must NOT dump
    with pytest.raises(ValueError):
        with mem.oom_guard():
            raise ValueError("nope")
    assert len(_dumps_in(tmp_path)) == 1


def test_mem_pressure_grammar_errors():
    with pytest.raises(MXNetError):
        chaos.ChaosPlan("mem_pressure")  # no target
    with pytest.raises(MXNetError):
        chaos.ChaosPlan("mem_pressure:0.5@3")  # no probability allowed
    with pytest.raises(MXNetError):
        chaos.ChaosPlan("mem_pressure@x:y")  # bad ints


# ---------------------------------------------------------------------------
# Static per-program attribution
# ---------------------------------------------------------------------------

def test_cached_op_memory_analysis():
    from mxnet_tpu.cached_op import CachedOp
    net = _mlp(materialize=True)
    op = CachedOp(net)
    x = nd.array(np.random.RandomState(0).randn(4, 16).astype(np.float32))
    op(x)
    report = op.memory_analysis()
    assert len(report) == 1
    stats = next(iter(report.values()))
    assert stats["argument_bytes"] > 0
    assert stats["output_bytes"] > 0
    assert stats["temp_bytes"] >= 0
    # cached: second call returns the recorded stats without re-lowering
    assert op.memory_analysis() == report
    # recorded into the shared program registry -> registry gauges
    from mxnet_tpu.telemetry import default_registry
    g = default_registry().get("mxtpu_program_argument_bytes")
    assert g is not None and g.value > 0


@pytest.mark.heavy
def test_grouped_program_memory():
    rs = np.random.RandomState(5)
    params = _make_params(rs, n=3)
    tr = gluon.Trainer(params, "adam", {"learning_rate": 0.01},
                       kvstore=None)
    _set_grads(params, rs)
    tr.step(1)
    report = grouped_mod.program_memory()
    assert report, "fused bucket programs must be attributable"
    for stats in report.values():
        assert stats["argument_bytes"] > 0
        assert stats["temp_bytes"] >= 0
    ranked = mem.program_report()
    assert any(r["kind"] == "optimizer" for r in ranked)


# ---------------------------------------------------------------------------
# Registry gauges + serving bytes
# ---------------------------------------------------------------------------

def test_device_gauges_fall_back_to_ledger_on_cpu():
    from mxnet_tpu.telemetry import default_registry
    _flush()
    p = gluon.Parameter("memgauge", shape=(128, 8))
    p.initialize(mx.init.One())
    reg = default_registry()
    assert reg.get("mxtpu_device_bytes_in_use").value > 0, \
        "gauge still reads 0 on CPU — ledger fallback not wired"
    assert reg.get("mxtpu_device_peak_bytes").value >= \
        reg.get("mxtpu_device_bytes_in_use").value
    assert reg.get("mxtpu_mem_params_bytes").value >= 128 * 8 * 4


def test_serving_cache_bytes_rise_and_fall():
    from mxnet_tpu.serving import ModelServer
    base = _flush()
    net = _mlp(width=16, out=4, materialize=True)
    server = ModelServer(net, bucket_shapes=[(16,)], max_batch_size=2,
                        workers=1)
    try:
        cache = server._active.cache
        cache.warmup([(16,)], [1])
        assert server.metrics.render_json()["model_bytes"] == 0  # unrecorded
        report = cache.program_memory()
        assert report
        bytes_now = cache.memory_bytes()
        assert bytes_now > 0
        assert LED.live_bytes("serving_cache") - base["serving_cache"] == \
            bytes_now
        blob = server.metrics.render_json()
        assert blob["model_bytes"] == bytes_now
        text = server.metrics.render_prometheus()
        assert f"mxtpu_serve_model_bytes {bytes_now}" in text
    finally:
        server.stop(drain=False)
    del server, cache, net
    gc.collect()
    assert LED.live_bytes("serving_cache") == base["serving_cache"], \
        "drained model's cache bytes must fall with the cache"


def test_storage_memory_summary_bridges_ledger_and_backend():
    from mxnet_tpu import storage
    s = storage.memory_summary()
    assert s["ledger"]["live_bytes"] == LED.live_bytes()
    assert "by_category" in s["ledger"]
    assert isinstance(s["backend"], dict)
    assert set(s["reconcile"]) >= {"ledger_bytes", "backend_bytes_in_use",
                                   "consistent"}


# ---------------------------------------------------------------------------
# Offline trace report renders the memory track
# ---------------------------------------------------------------------------

def test_trace_report_memory_columns(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "trace_report.py"))
    trace_report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_report)

    result, _net = _fit(steps=3, tracer=True)
    path = str(tmp_path / "live.json")
    dump_chrome_trace(path)
    rows = trace_report.step_table(trace_report.load_events(path))
    mem_rows = [r for r in rows if "mem_peak_bytes" in r]
    assert len(mem_rows) >= 3
    expected = {r["step"]: r["peak_bytes"]
                for r in result.memory["per_step"]}
    for i, r in enumerate(mem_rows):
        if r["step"] in (str(k) for k in expected):
            assert r["mem_peak_bytes"] == expected[int(r["step"])]
        assert "mem_live_bytes" in r
        if i > 0:  # the first sampled window has no offline baseline
            assert "mem_delta_bytes" in r
    # table mode shows the columns; --json round-trips
    lines = trace_report._fmt_table(rows, 8)
    assert any("mem_peak_MB" in line for line in lines)
    assert trace_report.main([path, "--json"]) == 0


def test_trace_report_peak_only_window_has_no_bogus_delta():
    """A step window holding only a peak event (ring-drop boundary) must
    report the peak alone — not live=0 with a huge negative delta."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "trace_report2", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "trace_report.py"))
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)
    events = [
        {"name": "step:0", "ph": "i", "cat": "step", "ts": 0.0,
         "pid": 0, "tid": 0},
        {"name": "compute", "ph": "X", "cat": "compute", "ts": 1.0,
         "dur": 5.0, "pid": 0, "tid": 0},
        {"name": "device_memory", "ph": "C", "ts": 6.0, "pid": 0,
         "tid": 0, "args": {"params": 1000.0}},
        {"name": "device_memory_peak", "ph": "C", "ts": 6.5, "pid": 0,
         "tid": 0, "args": {"value": 1200.0}},
        {"name": "step:1", "ph": "i", "cat": "step", "ts": 10.0,
         "pid": 0, "tid": 0},
        {"name": "compute", "ph": "X", "cat": "compute", "ts": 11.0,
         "dur": 5.0, "pid": 0, "tid": 0},
        # ring drop ate step 1's device_memory sample; only peak survives
        {"name": "device_memory_peak", "ph": "C", "ts": 16.0, "pid": 0,
         "tid": 0, "args": {"value": 1300.0}},
    ]
    rows = tr.step_table(events)
    assert rows[0]["mem_peak_bytes"] == 1200
    assert rows[0]["mem_live_bytes"] == 1000
    assert rows[1]["mem_peak_bytes"] == 1300
    assert "mem_live_bytes" not in rows[1]
    assert "mem_delta_bytes" not in rows[1]
    # the table renderer handles the partial row
    assert any("mem_peak_MB" in line for line in tr._fmt_table(rows, 8))


def test_aot_bundle_bytes_ledgered(tmp_path):
    try:
        from jax.experimental.serialize_executable import serialize  # noqa
    except ImportError:
        pytest.skip("serialize_executable unavailable")
    from mxnet_tpu.cached_op import CachedOp
    base = _flush()
    net = _mlp(width=8, out=4, materialize=True)
    op = CachedOp(net)
    x = nd.array(np.zeros((2, 16), np.float32))
    op(x)
    path = str(tmp_path / "bundle.aot")
    assert op.aot_export(path) == 1
    op2 = CachedOp(net)
    assert op2.aot_load(path) == 1
    assert LED.live_bytes("aot_bundles") - base["aot_bundles"] > 0
    # the loaded executable itself attributes (Compiled stage) or is
    # skipped cleanly — either way memory_analysis must not raise
    op2.memory_analysis()
    del op, op2, net
    gc.collect()
    assert LED.live_bytes("aot_bundles") == base["aot_bundles"]
