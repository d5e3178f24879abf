"""Inference serving subsystem: batcher, signature cache, admission
control, deadlines, drain, and the metrics plane.

Everything here is tier-1-safe: CPU, in-process transport (no sockets),
deterministic chaos injection for the failure paths. The e2e acceptance
tests are at the bottom: concurrent heterogeneous clients get the direct
model call's results (to 4 ulp of the largest output: the served batch
sizes are not the direct call's) with a closed compile budget, saturation
sheds load with QueueFull, and the metrics endpoint emits valid
Prometheus text exposition.
"""
import json
import re
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, serving
from mxnet_tpu.base import MXNetError
from mxnet_tpu.cached_op import CachedOp
from mxnet_tpu.contrib import chaos
from mxnet_tpu.serving import (BucketTable, DeadlineExceeded, ModelServer,
                               NoBucket, QueueFull, ServerClosed,
                               batch_buckets, pad_rows)

pytestmark = pytest.mark.serving


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _dense_net(out=5, in_units=8, seed=0):
    mx.random.seed(seed)
    net = gluon.nn.Dense(out, in_units=in_units)
    net.initialize(mx.init.Xavier())
    with mx.autograd.pause():
        net(nd.ones((1, in_units)))
    return net


class _CountingModel:
    """Plain-callable model that records every dispatched batch size."""

    def __init__(self, delay_s=0.0):
        self.batches = []
        self.delay_s = delay_s
        self.lock = threading.Lock()

    def __call__(self, x):
        with self.lock:
            self.batches.append(int(x.shape[0]))
        if self.delay_s:
            time.sleep(self.delay_s)
        return x * 2


@pytest.fixture(autouse=True)
def _no_leftover_chaos():
    yield
    chaos.uninstall()


# ---------------------------------------------------------------------------
# policy layer (pure)
# ---------------------------------------------------------------------------

def test_batch_buckets_closed_set():
    assert batch_buckets(32) == (1, 2, 4, 8, 16, 32)
    assert batch_buckets(1) == (1,)
    # a non-power-of-two max is always included as the top bucket
    assert batch_buckets(48) == (1, 2, 4, 8, 16, 32, 48)


def test_pad_rows_zero_tail():
    rows = [np.full((3,), i, np.float32) for i in range(3)]
    out = pad_rows(rows, 8)
    assert out.shape == (8, 3)
    np.testing.assert_array_equal(out[:3], np.stack(rows))
    np.testing.assert_array_equal(out[3:], np.zeros((5, 3), np.float32))


def test_bucket_table_flush_policy():
    t = BucketTable(max_batch_size=4, max_queue_latency_ms=20,
                    bucket_shapes=[(3,), (5,)])
    with pytest.raises(NoBucket):
        t.key_for((7,), "float32")
    key = t.key_for((3,), "float32")

    def req():
        return serving.Request(np.zeros((3,), np.float32), key, None)

    # size-triggered flush at max_batch_size
    batches = [t.add(req()) for _ in range(4)]
    assert batches[:3] == [None, None, None]
    assert batches[3] is not None and len(batches[3].requests) == 4
    assert t.pending_count == 0
    # age-triggered flush after max_queue_latency_ms
    t.add(req())
    assert t.due() == []
    time.sleep(0.03)
    due = t.due()
    assert len(due) == 1 and len(due[0].requests) == 1
    # drain flush ignores age
    t.add(req())
    assert [len(b.requests) for b in t.flush_all()] == [1]
    assert t.pad_to(3) == 4 and t.pad_to(1) == 1 and t.pad_to(2) == 2


def test_chaos_serve_slow_grammar():
    plan = chaos.ChaosPlan("serve_slow:0.5@20")
    assert plan.serve_slow_p == 0.5 and plan.serve_slow_ms == 20.0
    plan = chaos.ChaosPlan("serve_slow@7")
    assert plan.serve_slow_p == 1.0
    assert plan.serve_delay_s() == 0.007
    assert plan.injected["serve_slow"] == 1
    with pytest.raises(MXNetError, match="delay target"):
        chaos.ChaosPlan("serve_slow:0.5")
    with pytest.raises(MXNetError, match="probability"):
        chaos.ChaosPlan("serve_slow:1.5@20")


# ---------------------------------------------------------------------------
# CachedOp signature-cache bound (satellite)
# ---------------------------------------------------------------------------

def test_cached_op_lru_eviction_keeps_hot_signature():
    net = _dense_net()
    op = CachedOp(net, cache_size=2)

    def run(batch):
        with mx.autograd.pause():
            return op(nd.ones((batch, 8)))

    run(1)                       # A: miss
    run(2)                       # B: miss
    run(1)                       # A: hit -> A is now most-recent
    info = op.cache_info()
    assert (info.hits, info.misses, info.evictions) == (1, 2, 0)
    assert info.currsize == 2 and info.maxsize == 2
    run(4)                       # C: miss -> evicts B (LRU), NOT hot A
    assert op.cache_info().evictions == 1
    run(1)                       # A must still be resident
    info = op.cache_info()
    assert info.misses == 3, "hot signature was evicted!"
    assert info.hits == 2
    run(2)                       # B was the eviction victim: recompiles
    assert op.cache_info().misses == 4


def test_cached_op_unbounded_when_zero():
    net = _dense_net()
    op = CachedOp(net, cache_size=0)
    with mx.autograd.pause():
        for b in (1, 2, 3, 4, 5):
            op(nd.ones((b, 8)))
    info = op.cache_info()
    assert info.currsize == 5 and info.evictions == 0 and info.maxsize is None


# ---------------------------------------------------------------------------
# server behaviors
# ---------------------------------------------------------------------------

def test_mixed_shape_clients_land_in_correct_buckets():
    model = _CountingModel()
    srv = ModelServer(model, bucket_shapes=[(3,), (6,)], max_batch_size=8,
                      max_queue_latency_ms=5, queue_depth=64)
    try:
        futs3 = [srv.submit(np.full((3,), i, np.float32)) for i in range(5)]
        futs6 = [srv.submit(np.full((6,), i, np.float32)) for i in range(3)]
        out3 = [f.result(timeout=5) for f in futs3]
        out6 = [f.result(timeout=5) for f in futs6]
    finally:
        srv.stop()
    # correct bucket => correct arithmetic AND correct shape back
    for i, o in enumerate(out3):
        np.testing.assert_array_equal(o, np.full((3,), 2.0 * i, np.float32))
    for i, o in enumerate(out6):
        np.testing.assert_array_equal(o, np.full((6,), 2.0 * i, np.float32))
    # padding only ever to a batch bucket (5 -> 8, 3 -> 4) or smaller
    # flushes; every dispatched size is a configured bucket
    assert set(model.batches) <= set(batch_buckets(8))


def test_no_bucket_and_closed_rejections():
    srv = ModelServer(_CountingModel(), bucket_shapes=[(3,)],
                      max_batch_size=2, max_queue_latency_ms=1)
    srv.start()
    with pytest.raises(NoBucket):
        srv.submit(np.zeros((4,), np.float32))
    srv.stop()
    with pytest.raises(ServerClosed):
        srv.submit(np.zeros((3,), np.float32))
    rejected = srv.metrics.rejected_total.by_label()
    assert rejected.get("no_bucket") == 1 and rejected.get("closed") == 1


def test_queue_full_is_raised_not_deadlocked():
    """Saturation sheds load with a typed QueueFull at submit — the client
    thread is never blocked and admitted work still completes."""
    model = _CountingModel(delay_s=0.05)
    srv = ModelServer(model, bucket_shapes=[(2,)], max_batch_size=4,
                      max_queue_latency_ms=1, queue_depth=8, workers=1)
    try:
        futs, nfull = [], 0
        t0 = time.perf_counter()
        for i in range(64):
            try:
                futs.append(srv.submit(np.zeros((2,), np.float32)))
            except QueueFull:
                nfull += 1
        submit_time = time.perf_counter() - t0
        assert submit_time < 2.0, "submit must never block on a full queue"
        assert nfull > 0, "64 fast submits vs depth 8 must shed load"
        # everything admitted completes (drain) — no deadlock, no loss
        for f in futs:
            f.result(timeout=10)
    finally:
        srv.stop()
    m = srv.metrics.render_json()
    assert m["rejected"].get("queue_full") == nfull
    assert m["responses_total"] == len(futs)
    assert m["requests_total"] == 64


def test_saturation_queue_depth_metric_is_monotone():
    """While the single worker is pinned by a slow batch, every accepted
    admission must raise the queue-depth gauge monotonically up to its
    bound; the peak equals the configured depth when QueueFull fires."""
    chaos.install("serve_slow@200")   # first batch pins the worker 200ms
    model = _CountingModel()
    depth = 6
    srv = ModelServer(model, bucket_shapes=[(2,)], max_batch_size=2,
                      max_queue_latency_ms=1, queue_depth=depth, workers=1)
    try:
        srv.submit(np.zeros((2,), np.float32))
        time.sleep(0.05)          # batch formed + picked up, worker asleep
        samples, nfull = [], 0
        for i in range(2 * depth):
            try:
                srv.submit(np.zeros((2,), np.float32))
            except QueueFull:
                nfull += 1
            samples.append(srv.metrics.queue_depth.value)
        assert nfull > 0
        assert samples == sorted(samples), \
            f"queue depth not monotone during saturation: {samples}"
        assert srv.metrics.queue_depth.peak == depth
    finally:
        srv.stop()
        chaos.uninstall()


def test_deadline_expired_requests_never_dispatched():
    """chaos serve_slow pins the worker; requests whose deadline expires
    while queued are rejected with DeadlineExceeded BEFORE dispatch — the
    model never sees their rows."""
    chaos.install("serve_slow@80")
    model = _CountingModel()
    srv = ModelServer(model, bucket_shapes=[(2,)], max_batch_size=4,
                      max_queue_latency_ms=1, queue_depth=64, workers=1)
    try:
        first = srv.submit(np.zeros((2,), np.float32))   # occupies worker
        time.sleep(0.03)                                 # now in its sleep
        doomed = [srv.submit(np.zeros((2,), np.float32), deadline_ms=10)
                  for _ in range(5)]
        first.result(timeout=5)
        for f in doomed:
            with pytest.raises(DeadlineExceeded, match="never dispatched"):
                f.result(timeout=5)
    finally:
        srv.stop()
        plan = chaos.active()
        assert plan is not None and plan.injected["serve_slow"] >= 1
        chaos.uninstall()
    # the model saw ONLY the first request's batch: expired rows were
    # dropped before padding/dispatch, not computed-and-discarded
    assert sum(model.batches) == 1, model.batches
    m = srv.metrics.render_json()
    assert m["rejected"].get("deadline") == 5
    assert m["responses_total"] == 1


def test_stop_drain_completes_pending_work():
    model = _CountingModel(delay_s=0.01)
    srv = ModelServer(model, bucket_shapes=[(2,)], max_batch_size=8,
                      max_queue_latency_ms=500, queue_depth=64)
    futs = [srv.submit(np.full((2,), i, np.float32)) for i in range(6)]
    # requests are still waiting out the 500ms batching window; drain must
    # flush them immediately and finish them
    srv.stop(drain=True)
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(f.result(timeout=1),
                                      np.full((2,), 2.0 * i, np.float32))


def test_sigterm_drain_exits_resumable():
    """SIGTERM -> serve_forever drains in-flight work, then exits with the
    resumable code shared with FitLoop (subprocess; real signal)."""
    code = r"""
import atexit, signal, threading, time
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.serving import ModelServer

class Slow:
    def __call__(self, x):
        time.sleep(0.01)
        return x * 2

srv = ModelServer(Slow(), bucket_shapes=[(2,)], max_batch_size=4,
                  max_queue_latency_ms=1, queue_depth=64)
futs = [srv.submit(np.full((2,), i, np.float32)) for i in range(12)]

@atexit.register
def report():
    ok = 0
    for i, f in enumerate(futs):
        if f.done():
            try:
                r = f.result(0)
                ok += int(r[0] == 2.0 * i)
            except Exception:
                pass
    print(f"COMPLETED {ok}/{len(futs)}", flush=True)

threading.Timer(0.05, signal.raise_signal, (signal.SIGTERM,)).start()
srv.serve_forever()
"""
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env={**__import__("os").environ,
                              "JAX_PLATFORMS": "cpu"})
    assert res.returncode == 75, (res.returncode, res.stderr[-500:])
    assert "COMPLETED 12/12" in res.stdout, (res.stdout, res.stderr[-500:])


# ---------------------------------------------------------------------------
# metrics plane
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'
    r' (NaN|[-+]?(?:[0-9]*\.)?[0-9]+(?:[eE][-+]?[0-9]+)?|[-+]?Inf)$')


def _validate_prometheus(text):
    """Strict-enough validator for the text exposition format: every line
    is a HELP/TYPE comment or a sample; TYPE precedes its samples;
    histogram buckets are cumulative with le="+Inf" == _count."""
    types, samples = {}, []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            assert len(line.split(" ", 3)) == 4, f"bad HELP: {line!r}"
            continue
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split(" ", 3)
            assert typ in ("counter", "gauge", "histogram", "summary"), line
            types[name] = typ
            continue
        assert not line.startswith("#"), f"unknown comment: {line!r}"
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        samples.append((m.group(1), m.group(2), float(m.group(4))))
    by_family = {}
    for name, labels, value in samples:
        family = re.sub(r"_(bucket|sum|count)$", "", name)
        family = family if family in types else name
        assert family in types, f"sample {name} has no TYPE"
        by_family.setdefault(family, []).append((name, labels, value))
    for family, typ in types.items():
        rows = by_family.get(family, [])
        assert rows, f"TYPE {family} declared but no samples"
        if typ == "histogram":
            buckets = [(l, v) for n, l, v in rows if n.endswith("_bucket")]
            count = [v for n, _, v in rows if n.endswith("_count")]
            assert buckets and len(count) == 1
            vals = [v for _, v in buckets]
            assert vals == sorted(vals), f"{family} buckets not cumulative"
            inf = [v for l, v in buckets if '+Inf' in (l or "")]
            assert inf == [count[0]], f"{family} +Inf != count"
    return types, samples


def test_metrics_prometheus_and_json(tmp_path):
    srv = ModelServer(_CountingModel(), bucket_shapes=[(4,)],
                      max_batch_size=4, max_queue_latency_ms=2,
                      queue_depth=32)
    try:
        futs = [srv.submit(np.zeros((4,), np.float32)) for _ in range(9)]
        for f in futs:
            f.result(timeout=5)
        with pytest.raises(NoBucket):
            srv.submit(np.zeros((9,), np.float32))
    finally:
        srv.stop()
    text = srv.metrics_text()
    types, samples = _validate_prometheus(text)
    # the full surface is present
    for fam in ("mxtpu_serve_requests_total", "mxtpu_serve_responses_total",
                "mxtpu_serve_rejected_total", "mxtpu_serve_batches_total",
                "mxtpu_serve_queue_depth", "mxtpu_serve_queue_latency_ms",
                "mxtpu_serve_batch_latency_ms",
                "mxtpu_serve_compute_latency_ms",
                "mxtpu_serve_total_latency_ms", "mxtpu_serve_batch_size",
                "mxtpu_serve_cache_misses_total",
                "mxtpu_serve_uptime_seconds"):
        assert fam in types, f"{fam} missing from exposition"
    j = json.loads(srv.metrics.render_json_text())
    assert j["responses_total"] == 9 and j["requests_total"] == 10
    assert j["latency_ms"]["total"]["count"] == 9
    assert j["latency_ms"]["total"]["p99"] >= j["latency_ms"]["total"]["p50"]
    assert j["rejected"] == {"no_bucket": 1}
    assert j["cache"]["misses"] >= 1
    assert j["throughput_rps"] > 0


def test_batch_dispatch_emits_profiler_span():
    from mxnet_tpu import profiler
    srv = ModelServer(_CountingModel(), bucket_shapes=[(2,)],
                      max_batch_size=2, max_queue_latency_ms=1)
    profiler.set_state("run")
    try:
        futs = [srv.submit(np.zeros((2,), np.float32)) for _ in range(4)]
        for f in futs:
            f.result(timeout=5)
    finally:
        srv.stop()
        profiler.set_state("stop")
    spans = [e for e in profiler.events("serving")
             if e["name"].startswith("serve_batch")]
    assert spans, "batch dispatch must land in the chrome trace"
    assert spans[0]["args"]["rows"] >= 1
    assert spans[0]["args"]["padded_to"] in batch_buckets(2)


# ---------------------------------------------------------------------------
# e2e acceptance: heterogeneous concurrent clients, the direct call's
# results, closed compile budget
# ---------------------------------------------------------------------------

def _pool_net(seed=0):
    """Shape-polymorphic net: conv -> global average pool -> dense, so the
    SAME weights serve multiple image sizes (distinct XLA signatures)."""
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(4, kernel_size=3, padding=1, in_channels=3))
    net.add(gluon.nn.GlobalAvgPool2D())
    net.add(gluon.nn.Flatten())
    net.add(gluon.nn.Dense(3, in_units=4))
    net.initialize(mx.init.Xavier())
    with mx.autograd.pause():
        net(nd.ones((1, 3, 8, 8)))
    return net


def test_e2e_concurrent_heterogeneous_clients_match_direct_call():
    shapes = [(3, 8, 8), (3, 12, 12)]
    net = _pool_net()
    srv = ModelServer(net, bucket_shapes=shapes, max_batch_size=4,
                      max_queue_latency_ms=5, queue_depth=256, workers=2)
    srv.start()
    compiles = srv.warmup()
    assert compiles == len(shapes) * len(batch_buckets(4))  # closed set

    rs = np.random.RandomState(0)
    inputs = {s: [rs.rand(*s).astype(np.float32) for _ in range(10)]
              for s in shapes}
    results = {s: [None] * 10 for s in shapes}
    errors = []

    def client(shape, i):
        try:
            results[shape][i] = srv.submit(inputs[shape][i]).result(timeout=30)
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append((shape, i, e))

    threads = [threading.Thread(target=client, args=(s, i))
               for s in shapes for i in range(10)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    try:
        assert not errors, errors
        info = srv.cache.cache_info()
        # acceptance: total XLA compiles <= configured bucket combinations
        assert info.misses == compiles, \
            f"traffic caused {info.misses - compiles} extra compiles"
        # hybridized reference at a batch of its own (10). How the timing
        # cut the requests into padded batches of 1, 2 or 4 is not known
        # here, and another batch size is another XLA program whose rows
        # can differ in the last ulp of the terms it sums (1.5 ulp of the
        # largest output here); the bitwise claim, at the SAME padded
        # batch, is test_padding_never_contaminates_rows_matched_batch's
        net.hybridize()
        for s in shapes:
            direct = net(nd.array(np.stack(inputs[s]))).asnumpy()
            served = np.stack(results[s])
            np.testing.assert_allclose(
                served, direct, rtol=0,
                atol=4 * np.spacing(np.abs(direct).max()))
    finally:
        srv.stop()


def test_model_server_load_serves_exported_checkpoint(tmp_path):
    """ModelServer.load serves a HybridBlock.export checkpoint (the
    deployment format) through SymbolBlock.imports, bit-exact with the
    original block."""
    net = _dense_net(seed=3)
    prefix = str(tmp_path / "m")
    net.export(prefix)
    srv = ModelServer.load(prefix, bucket_shapes=[(8,)], max_batch_size=4,
                           max_queue_latency_ms=2)
    try:
        rs = np.random.RandomState(1)
        xs = [rs.randn(8).astype(np.float32) for _ in range(6)]
        futs = [srv.submit(x) for x in xs]
        served = np.stack([f.result(timeout=10) for f in futs])
    finally:
        srv.stop()
    direct = net(nd.array(np.stack(xs))).asnumpy()
    np.testing.assert_allclose(served, direct, rtol=1e-6, atol=1e-6)


def _run_bench_serve(cold_start):
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py"), "serve"],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "MXTPU_BENCH_SERVE_SECONDS": "1",
             # small bucket set: the sweep (and the cold-start children)
             # warm 3 signatures instead of 6 — same assertions, less
             # wall-clock
             "MXTPU_SERVE_MAX_BATCH": "4",
             "MXTPU_BENCH_SERVE_COLD_START": "1" if cold_start else "0",
             "MXTPU_BENCH_DEADLINE_S": "300"})
    assert res.returncode == 0, res.stderr[-800:]
    rows = [json.loads(l) for l in res.stdout.splitlines()
            if l.startswith("{")]
    assert rows, res.stdout
    for row in rows:  # every emission must be complete on its own
        assert row["metric"] == "serve_p99_latency_ms" and row["unit"] == "ms"
        assert row["value"] > 0 and row["imgs_per_sec"] > 0
        assert len(row["points"]) >= 2
        for pt in row["points"]:
            assert 0 < pt["p50_ms"] <= pt["p95_ms"] <= pt["p99_ms"]
            assert pt["throughput_rps"] > 0 and pt["batches"] > 0
        # compile budget holds in the bench too: one shape x pow2 buckets
        assert row["compiled_signatures"] == \
            len(batch_buckets(row["max_batch"]))
    return rows, res


def test_bench_serve_emits_load_sweep_row():
    """`bench.py serve` must emit a JSON row with p50/p95/p99 latency and
    achieved throughput at >= 2 offered-load points, inside the deadline
    budget. (Cold-start probe exercised by the slow-tier companion test;
    its mechanism — fresh-process zero-compile restart — is tier-1-
    covered by test_serving_fleet.py's subprocess acceptance test.)"""
    _run_bench_serve(cold_start=False)


@pytest.mark.slow
def test_bench_serve_cold_start_probe_extends_row():
    """With the probe on, the serve row is re-emitted extended with
    cold_start_s / warm_start_s (newest complete line wins, same
    incremental convention as the train rows): a fresh process against
    the populated persistent compile cache must spend (near) zero
    seconds in actual XLA compilation — retrievals are counted apart."""
    rows, res = _run_bench_serve(cold_start=True)
    row = rows[-1]
    assert "cold_start_s" in row and "warm_start_s" in row, \
        ("cold-start probe did not complete inside the (ample) deadline "
         "budget — bench stderr: %s; row: %r" % (res.stderr[-500:], row))
    assert row["cold_start_s"] > 0 and row["warm_start_s"] > 0, row
    assert row["cold_start_compile_s"] > 0, row
    assert row["warm_start_compile_s"] <= row["cold_start_compile_s"] / 4, \
        row


def test_padding_never_contaminates_rows_matched_batch():
    """The precise padding invariant: with ONE deterministic batch
    (flush window >> submit time) of 7 requests padded to bucket 8, the
    served rows are bit-exact equal to the hybridized model called on the
    same zero-padded batch — the pad rows change nothing."""
    net = _pool_net(seed=7)
    srv = ModelServer(net, bucket_shapes=[(3, 8, 8)], max_batch_size=8,
                      max_queue_latency_ms=300, queue_depth=32)
    try:
        rs = np.random.RandomState(2)
        items = [rs.rand(3, 8, 8).astype(np.float32) for _ in range(7)]
        futs = [srv.submit(x) for x in items]
        served = np.stack([f.result(timeout=10) for f in futs])
        assert srv.metrics.batches_total.value == 1, "must be ONE batch"
        assert srv.metrics.padded_rows_total.value == 1  # 7 -> bucket 8
    finally:
        srv.stop()
    padded = np.concatenate(
        [np.stack(items), np.zeros((1, 3, 8, 8), np.float32)])
    net.hybridize()
    reference = net(nd.array(padded)).asnumpy()[:7]
    np.testing.assert_array_equal(served, reference)


def test_e2e_saturation_and_shed_load_metrics():
    chaos.install("serve_slow@100")
    net = _dense_net()
    srv = ModelServer(net, bucket_shapes=[(8,)], max_batch_size=4,
                      max_queue_latency_ms=1, queue_depth=8, workers=1)
    try:
        srv.warmup()
        ok, full = 0, 0
        futs = []
        for i in range(48):
            try:
                futs.append(srv.submit(np.zeros((8,), np.float32)))
            except QueueFull:
                full += 1
        for f in futs:
            f.result(timeout=30)
            ok += 1
        assert full > 0 and ok == len(futs)
        depth_samples = srv.metrics.queue_depth
        assert depth_samples.peak == 8
    finally:
        srv.stop()
        chaos.uninstall()
    _validate_prometheus(srv.metrics_text())
