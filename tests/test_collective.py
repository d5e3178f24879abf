"""Fleet-wide comm observability (telemetry/collective.py): collective
ledger at every kvstore/ZeRO entry point, desync + straggler-skew
detection, the hung-collective flight recorder driven by the kv_hang
chaos grammar, the wall-clock trace anchor and the fleet trace merge
(tools/fleet_trace.py), and the plane's numeric inertness.

Marker ``comm_health`` (tier-1-safe: CPU, in-process simulated worlds;
the one real-group test is a 2-process subprocess on the
coordination-service fallback, same harness as test_dist_kvstore)."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, gluon
from mxnet_tpu import kvstore as kvs
from mxnet_tpu.base import MXNetError
from mxnet_tpu.contrib import chaos
from mxnet_tpu.telemetry import collective as coll

pytestmark = pytest.mark.comm_health

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_plane():
    """Each test sees an empty ring, a zero watchdog count and no stale
    chaos plan (the registry counters stay monotone — only the ledger's
    test-facing state resets)."""
    coll.ledger.clear()
    coll.ledger.watchdog_fired = 0
    coll.ledger.flight_records.clear()
    coll.reset_health()
    chaos.uninstall()
    yield
    chaos.uninstall()
    coll.ledger.force(None)
    coll.ledger.clear()
    coll.ledger.flight_records.clear()


def _step_params(n=4, shape=(8, 8), prefix="cp", store="device"):
    params = []
    for i in range(n):
        p = gluon.Parameter(f"{prefix}{i}", shape=shape)
        p.initialize(mx.init.One())
        params.append(p)
    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                       kvstore=kvs.create(store))
    return params, tr


def _one_step(params, tr, batch=4):
    for p in params:
        p._grad._rebind(nd.array(
            np.ones(p.shape, np.float32))._data)
        p._fresh_grad = True
    tr.step(batch)


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------

def test_ledger_off_by_default_records_nothing(monkeypatch):
    monkeypatch.delenv("MXTPU_COLL_HEALTH", raising=False)
    monkeypatch.delenv("MXTPU_COLL_TIMEOUT_S", raising=False)
    assert not coll.enabled()
    params, tr = _step_params(prefix="off")
    _one_step(params, tr)
    assert coll.ledger.depth() == 0


def test_ledger_records_push_pull_with_bytes_and_monotone_seq(monkeypatch):
    monkeypatch.setenv("MXTPU_COLL_HEALTH", "1")
    params, tr = _step_params(prefix="led")
    for _ in range(3):
        _one_step(params, tr)
    recs = coll.ledger.records()
    assert recs, "enabled plane recorded nothing"
    kinds = {r["kind"] for r in recs}
    assert {"push", "pull"} <= kinds, kinds
    # one flat bucket of 4 f32 8x8 grads = 1024 wire bytes each way
    assert all(r["bytes"] == 4 * 8 * 8 * 4 for r in recs), recs
    assert all(r["t_exit"] is not None and
               r["t_exit"] >= r["t_enter"] for r in recs)
    assert all(r["rank"] == 0 for r in recs)
    # per-(kind, key) monotone seq — the identity ranks compare
    last = {}
    for r in recs:
        ident = (r["kind"], r["key"])
        assert r["seq"] == last.get(ident, -1) + 1, (ident, r["seq"])
        last[ident] = r["seq"]


def test_ledger_covers_zero_collectives_and_sentinel(monkeypatch):
    monkeypatch.setenv("MXTPU_COLL_HEALTH", "1")
    monkeypatch.setenv("MXTPU_ZERO", "1")
    monkeypatch.setenv("MXTPU_ZERO_WORLD", "2")
    params, tr = _step_params(prefix="zc")
    _one_step(params, tr)
    kinds = {r["kind"] for r in coll.ledger.records()}
    assert {"reduce_scatter", "allgather"} <= kinds, kinds


def test_ring_bounded_and_drop_counted(monkeypatch):
    monkeypatch.setenv("MXTPU_COLL_HEALTH", "1")
    monkeypatch.setenv("MXTPU_COLL_RING", "4")
    params, tr = _step_params(prefix="rg")
    for _ in range(5):
        _one_step(params, tr)  # 10 records into a 4-slot ring
    assert coll.ledger.depth() == 4
    assert coll.ledger.dropped >= 6


def test_seq_map_bounded_by_unique_tagged_collectives(monkeypatch):
    """Byte-channel collectives carry a counter in the KEY (exchange /
    barrier / health tags), so each is a fresh (kind, key) identity —
    the seq map must evict longest-idle identities instead of growing
    one entry per collective forever, and a LIVE identity must keep its
    monotone seq across the churn."""
    monkeypatch.setenv("MXTPU_COLL_RING", "8")
    coll.ledger.force(True)
    for i in range(100):
        coll.exit_(coll.enter("exchange", f"tag{i}", 0, 0))
        coll.exit_(coll.enter("push", "hot", 0, 0))  # re-inserted: live
    assert len(coll.ledger._seq) <= 4 * 8
    # the hot identity survived every eviction round with seq intact
    tok = coll.enter("push", "hot", 0, 0)
    coll.exit_(tok)
    assert coll.ledger.records(1)[0]["seq"] == 100


def test_comm_health_summary_resets_per_run(monkeypatch):
    """A second fit() in the same process must not inherit the previous
    run's comparison, check count or watchdog firings."""
    coll.ledger.watchdog_fired = 3  # pretend an earlier run hung
    coll.health_check(None)
    assert coll.health_summary()["checks"] == 1
    coll.reset_health()
    s = coll.health_summary()
    assert s["checks"] == 0
    assert s["watchdog_fired"] == 0
    assert s["flight_records"] == []


def test_env_grammar_strict():
    for var, fn in (("MXTPU_COLL_TIMEOUT_S", coll.timeout_s),
                    ("MXTPU_COLL_RING", coll.ring_capacity),
                    ("MXTPU_COLL_HEALTH", coll.health_interval)):
        os.environ[var] = "wat"
        try:
            with pytest.raises(MXNetError, match=var):
                fn()
        finally:
            os.environ.pop(var)
    os.environ["MXTPU_COLL_RING"] = "0"
    try:
        with pytest.raises(MXNetError, match="MXTPU_COLL_RING"):
            coll.ring_capacity()
    finally:
        os.environ.pop("MXTPU_COLL_RING")


# ---------------------------------------------------------------------------
# desync / straggler detection
# ---------------------------------------------------------------------------

def _digest(entries, t0=1000.0):
    return [{"kind": k, "key": key, "seq": s, "bytes": 0,
             "t_enter_epoch": t0 + dt}
            for (k, key, s, dt) in entries]


def test_compare_digests_clean():
    d = _digest([("push", "a", 0, 0.0), ("pull", "a", 0, 0.01),
                 ("push", "a", 1, 0.02)])
    cmp = coll.compare_digests({0: d, 1: d})
    assert cmp["desync"] is None
    assert cmp["max_skew_ms"] == 0.0
    assert cmp["straggler_rank"] is None
    assert cmp["compared"] == 3 and cmp["world"] == 2


def test_compare_digests_detects_desynced_order():
    a = _digest([("push", "a", 0, 0.0), ("push", "b", 0, 0.01)])
    b = _digest([("push", "b", 0, 0.0), ("push", "a", 0, 0.01)])
    cmp = coll.compare_digests({0: a, 1: b})
    assert cmp["desync"] is not None
    assert cmp["desync"]["ranks"] == [0, 1]
    assert cmp["desync"]["position"] == 0
    assert cmp["desync"]["expected"] == ["push", "a", 0]
    assert cmp["desync"]["got"] == ["push", "b", 0]


def test_compare_digests_attributes_straggler_skew():
    mk = lambda lag: _digest([("push", "a", 0, 0.0 + lag),
                              ("pull", "a", 0, 0.010 + lag),
                              ("push", "a", 1, 0.020 + lag)])
    cmp = coll.compare_digests({0: mk(0.0), 1: mk(0.050), 2: mk(0.002)})
    assert cmp["straggler_rank"] == 1
    assert abs(cmp["max_skew_ms"] - 50.0) < 1e-6
    assert abs(cmp["skew_ms_by_rank"][1]["mean_ms"] - 50.0) < 1e-6
    assert cmp["skew_ms_by_rank"][0]["mean_ms"] == 0.0
    assert abs(cmp["skew_ms_by_rank"][2]["mean_ms"] - 2.0) < 1e-6


def test_compare_ignores_extra_tail_only_common_ids():
    """Ranks caught at different ring positions: only the identities all
    ranks saw are compared — a longer tail is not a desync."""
    a = _digest([("push", "a", 0, 0.0), ("push", "a", 1, 0.01),
                 ("push", "a", 2, 0.02)])
    b = _digest([("push", "a", 0, 0.0), ("push", "a", 1, 0.01)])
    cmp = coll.compare_digests({0: a, 1: b})
    assert cmp["desync"] is None and cmp["compared"] == 2


def test_health_check_strict_raises_on_desync(monkeypatch):
    monkeypatch.setattr(coll, "compare_digests", lambda pr: {
        "world": 2, "compared": 1,
        "desync": {"ranks": [0, 1], "position": 0,
                   "expected": ["push", "a", 0],
                   "got": ["push", "b", 0]},
        "skew_ms_by_rank": {}, "max_skew_ms": 0.0,
        "straggler_rank": None})
    with pytest.raises(MXNetError, match="desync"):
        coll.health_check(None, strict=True)
    from mxnet_tpu.telemetry import default_registry
    c = default_registry().get("mxtpu_coll_desync_total")
    assert c is not None and c.value >= 1


def test_health_check_sets_gauges_and_breakdown_note(monkeypatch):
    from mxnet_tpu.telemetry import default_registry
    from mxnet_tpu.telemetry.step_breakdown import StepBreakdown
    monkeypatch.setattr(coll, "compare_digests", lambda pr: {
        "world": 4, "compared": 9, "desync": None,
        "skew_ms_by_rank": {2: {"mean_ms": 41.0, "max_ms": 44.0}},
        "max_skew_ms": 44.0, "straggler_rank": 2})
    bd = StepBreakdown()
    cmp = coll.health_check(None, breakdown=bd)
    assert cmp["straggler_rank"] == 2
    reg = default_registry()
    assert reg.get("mxtpu_coll_skew_ms").value == 44.0
    assert reg.get("mxtpu_coll_straggler_rank").value == 2
    assert bd._comm_health["straggler_rank"] == 2


def test_straggler_bound_diagnosis_variant(caplog):
    """A comm-bound step with a known straggler re-aims the detector at
    the straggler rank instead of the comm knobs."""
    import logging
    from mxnet_tpu.telemetry.step_breakdown import StepBreakdown, segment
    bd = StepBreakdown(bound_frac=0.3).install()
    try:
        bd.note_comm_health({"straggler_rank": 3, "max_skew_ms": 37.5})
        bd.begin_step(0)
        with segment("comm"):
            time.sleep(0.02)
        with caplog.at_level(logging.WARNING,
                             logger="mxnet_tpu.telemetry"):
            bd.end_step()
    finally:
        bd.uninstall()
    assert bd.diagnoses, "comm-bound step produced no diagnosis"
    assert "straggler-bound: rank 3" in bd.diagnoses[0]
    assert "37.5ms" in bd.diagnoses[0]
    # without the note, the same shape of step gives the comm advice
    bd2 = StepBreakdown(bound_frac=0.3).install()
    try:
        bd2.begin_step(0)
        with segment("comm"):
            time.sleep(0.02)
        bd2.end_step()
    finally:
        bd2.uninstall()
    assert "straggler" not in bd2.diagnoses[0]
    assert "MXTPU_COMM_OVERLAP" in bd2.diagnoses[0]


# ---------------------------------------------------------------------------
# FitLoop wiring (simulated world)
# ---------------------------------------------------------------------------

def _fit(monkeypatch, n_steps=4, seed=0, **env):
    from mxnet_tpu.fit import FitLoop
    from mxnet_tpu.io import NDArrayIter
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    mx.random.seed(seed)
    net = gluon.nn.Dense(2, in_units=3)
    net.initialize(mx.init.Constant(0.5))
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05},
                       kvstore=kvs.create("local"))
    rs = np.random.RandomState(seed)
    it = NDArrayIter(rs.rand(4 * n_steps, 3).astype(np.float32),
                     rs.rand(4 * n_steps, 2).astype(np.float32),
                     batch_size=4)
    loss = lambda out, y: ((out - y) ** 2).mean()
    res = FitLoop(net, tr, loss, it, ckpt_dir=None).fit(epochs=1)
    return net, res


def test_fitloop_comm_health_summary_simulated_world(monkeypatch):
    _, res = _fit(monkeypatch, MXTPU_COLL_HEALTH="2",
                  MXTPU_ZERO="1", MXTPU_ZERO_WORLD="4",
                  MXTPU_OPTIMIZER_AGGREGATION="4")
    ch = res.comm_health
    assert ch is not None
    assert ch["checks"] >= 1
    assert ch["ledger_depth"] > 0
    assert ch["watchdog_fired"] == 0 and ch["flight_records"] == []
    assert ch["desync"] is None
    assert ch["max_skew_ms"] == 0.0  # one process, one clock
    assert ch["world"] == 1  # the kv group; the ZeRO world is simulated


def test_fitloop_no_health_no_summary(monkeypatch):
    monkeypatch.delenv("MXTPU_COLL_HEALTH", raising=False)
    monkeypatch.delenv("MXTPU_COLL_TIMEOUT_S", raising=False)
    _, res = _fit(monkeypatch)
    assert res.comm_health is None


def test_trajectory_bitwise_identical_plane_on_vs_off(monkeypatch):
    """The whole plane is numerically inert: ledger + health + armed
    watchdog change NOTHING about the training trajectory (the PR 6/9
    discipline)."""
    net_off, res_off = _fit(monkeypatch, n_steps=5)
    coll.ledger.clear()
    net_on, res_on = _fit(monkeypatch, n_steps=5,
                          MXTPU_COLL_HEALTH="1",
                          MXTPU_COLL_TIMEOUT_S="30")
    assert coll.ledger.depth() > 0  # the plane actually ran
    assert res_on.losses == res_off.losses  # bitwise, not allclose
    np.testing.assert_array_equal(net_on.weight.data().asnumpy(),
                                  net_off.weight.data().asnumpy())


# ---------------------------------------------------------------------------
# kv_hang chaos + the watchdog flight recorder
# ---------------------------------------------------------------------------

def test_kv_hang_grammar():
    p = chaos.ChaosPlan("kv_hang:1@3:500")
    assert p._kv_hang == {3: (1, 500.0)}
    p = chaos.ChaosPlan("kv_hang:0@7")
    assert p._kv_hang == {7: (0, 60000.0)}  # default: withhold
    for bad in ("kv_hang@3", "kv_hang:x@3", "kv_hang:1",
                "kv_hang:1@x", "kv_hang:1@3:x", "kv_hang:-1@3",
                "kv_hang:1@3:-5"):
        with pytest.raises(MXNetError):
            chaos.ChaosPlan(bad)


def test_kv_hang_consume_once_and_rank_gated():
    p = chaos.ChaosPlan("kv_hang:1@2:100")
    p.begin_step(1)
    assert p.kv_hang_delay_s(1) == 0.0  # wrong step
    p.begin_step(2)
    assert p.kv_hang_delay_s(0) == 0.0  # wrong rank: not consumed
    assert p.kv_hang_delay_s(1) == 0.1
    assert p.kv_hang_delay_s(1) == 0.0  # consumed
    assert p.injected["kv_hang"] == 1


def test_watchdog_dumps_flight_record_on_kv_hang(monkeypatch, tmp_path):
    """The in-process watchdog drill: kv_hang holds this rank's push
    inside the armed collective past MXTPU_COLL_TIMEOUT_S, so the
    watchdog dumps a flight record naming the hung (kind, key, seq) with
    all-thread stacks — the CPU-testable half of the 2-process proof."""
    monkeypatch.setenv("MXTPU_COLL_TIMEOUT_S", "0.1")
    monkeypatch.setenv("MXTPU_MEM_DUMP_DIR", str(tmp_path))
    fired0 = coll.ledger.watchdog_fired
    params, tr = _step_params(prefix="wd")
    chaos.install("kv_hang:0@1:400")  # trainer drives the step clock
    _one_step(params, tr)  # step 0: clean
    _one_step(params, tr)  # step 1: the push is held 400ms > 100ms
    chaos.uninstall()
    deadline = time.time() + 2.0
    while coll.ledger.watchdog_fired == fired0 and time.time() < deadline:
        time.sleep(0.02)
    assert coll.ledger.watchdog_fired == fired0 + 1
    assert coll.ledger.flight_records
    path = coll.ledger.flight_records[-1]
    assert os.path.dirname(path) == str(tmp_path)
    with open(path) as f:
        rec = json.load(f)
    assert rec["reason"] == "hung_collective"
    assert rec["timeout_s"] == 0.1
    hung = rec["hung"][0]
    assert hung["kind"] == "push" and hung["key"].startswith("_gbkt")
    assert hung["seq"] == 1  # step 0's push was seq 0
    assert hung["elapsed_s"] >= 0.1
    assert rec["ring"], "flight record shipped no ledger ring"
    assert rec["thread_stacks"], "flight record missing thread stacks"
    # the hung thread's stack names the chaos sleep it is parked in
    joined = "".join(s for st in rec["thread_stacks"].values()
                     for s in st)
    assert "kv_hang_delay_s" in joined or "sleep" in joined
    from mxnet_tpu.telemetry import default_registry
    c = default_registry().get("mxtpu_coll_watchdog_fired_total")
    assert c is not None and c.value >= 1


def test_flight_dump_failure_logs_and_retries(monkeypatch, tmp_path):
    """A dump that cannot be written (full/unwritable disk) must not
    silently lose the one record the recorder exists for: the hang is
    named in an ERROR log and the dump retries on the next wake."""
    monkeypatch.setenv("MXTPU_COLL_TIMEOUT_S", "0.1")
    monkeypatch.setenv("MXTPU_MEM_DUMP_DIR", str(tmp_path))
    calls = {"n": 0}
    real = coll.CollectiveLedger._dump_flight

    def flaky(self, overdue, t):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("disk full")
        return real(self, overdue, t)

    monkeypatch.setattr(coll.CollectiveLedger, "_dump_flight", flaky)
    params, tr = _step_params(prefix="rf")
    chaos.install("kv_hang:0@0:600")
    _one_step(params, tr)
    chaos.uninstall()
    deadline = time.time() + 2.0
    while not coll.ledger.flight_records and time.time() < deadline:
        time.sleep(0.02)
    assert calls["n"] >= 2, "failed dump was not retried"
    assert coll.ledger.flight_records, "retry never landed the record"


def test_watchdog_thread_exits_when_disarmed(monkeypatch):
    """A brief arming (the bench probe pattern) must not leave a 4Hz
    poller for the process lifetime: disarmed + idle, the thread exits;
    the next armed collective re-spawns it."""
    monkeypatch.setenv("MXTPU_COLL_TIMEOUT_S", "5")
    params, tr = _step_params(prefix="wx")
    _one_step(params, tr)
    th = coll.ledger._watchdog
    assert th is not None and th.is_alive()
    monkeypatch.delenv("MXTPU_COLL_TIMEOUT_S")
    deadline = time.time() + 3.0
    while time.time() < deadline and \
            coll.ledger._watchdog is th and th.is_alive():
        time.sleep(0.05)
    assert coll.ledger._watchdog is not th or not th.is_alive()
    # re-arming spawns a fresh watchdog
    monkeypatch.setenv("MXTPU_COLL_TIMEOUT_S", "5")
    _one_step(params, tr)
    assert coll.ledger._watchdog is not None
    assert coll.ledger._watchdog.is_alive()


def test_clean_armed_run_fires_zero_watchdogs(monkeypatch, tmp_path):
    monkeypatch.setenv("MXTPU_COLL_TIMEOUT_S", "5")
    monkeypatch.setenv("MXTPU_MEM_DUMP_DIR", str(tmp_path))
    fired0 = coll.ledger.watchdog_fired
    params, tr = _step_params(prefix="cl")
    for _ in range(3):
        _one_step(params, tr)
    time.sleep(0.1)
    assert coll.ledger.watchdog_fired == fired0
    assert list(tmp_path.glob("coll_flight_*.json")) == []


# ---------------------------------------------------------------------------
# trace clock anchor + fleet merge
# ---------------------------------------------------------------------------

def _synthetic_rank_trace(path, rank, delay_s):
    from mxnet_tpu.telemetry.tracer import Tracer
    from mxnet_tpu.telemetry.chrome_trace import dump_chrome_trace
    tr = Tracer(rank=rank)
    tr.enable()
    for step in range(3):
        tr.instant(f"step:{step}", "step")
        time.sleep(delay_s)
        with tr.span("kv_push:_gbkt0", "comm"):
            time.sleep(0.001)
        with tr.span("kv_pull:_gbkt0", "comm"):
            time.sleep(0.001)
    tr.disable()
    dump_chrome_trace(str(path), tracer=tr)
    return tr


def test_trace_carries_clock_anchor(tmp_path):
    from mxnet_tpu.telemetry.chrome_trace import validate_chrome_trace
    before = time.time()
    tr = _synthetic_rank_trace(tmp_path / "r0.json", 0, 0.0)
    after = time.time()
    with open(tmp_path / "r0.json") as f:
        payload = json.load(f)
    validate_chrome_trace(payload)
    sync = [e for e in payload["traceEvents"]
            if e.get("name") == "clock_sync"]
    assert len(sync) == 1
    args = sync[0]["args"]
    # the anchor is the epoch second at trace ts 0 = tracer birth
    assert abs(args["epoch_t0_s"] - tr.epoch_anchor) < 1e-9
    assert before <= args["epoch_t0_s"] <= after
    assert args["clock_offset_ms"] == 0.0


def test_fleet_trace_merge_validates_and_names_straggler(tmp_path):
    from mxnet_tpu.telemetry.chrome_trace import validate_chrome_trace
    _synthetic_rank_trace(tmp_path / "r0.json", 0, 0.0)
    _synthetic_rank_trace(tmp_path / "r1.json", 1, 0.03)
    merged = tmp_path / "merged.json"
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "fleet_trace.py"),
         str(tmp_path / "r0.json"), str(tmp_path / "r1.json"),
         "-o", str(merged), "--json"],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert r.returncode == 0, r.stderr
    with open(merged) as f:
        payload = json.load(f)
    validate_chrome_trace(payload)  # Perfetto-loadable, both pids kept
    pids = {e["pid"] for e in payload["traceEvents"]
            if e.get("ph") != "M"}
    assert pids == {0, 1}
    rep = json.loads(r.stdout)
    assert rep["ranks"] == [0, 1]
    assert rep["straggler_rank"] == 1
    assert rep["collective_skew_ms"]["1"]["mean_ms"] > \
        rep["collective_skew_ms"]["0"]["mean_ms"]
    # the per-step table reads per rank through trace_report
    r2 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_report.py"),
         str(merged), "--json"],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert r2.returncode == 0, r2.stderr
    out = json.loads(r2.stdout)
    assert set(out["ranks"]) == {"0", "1"}
    assert len(out["ranks"]["0"]["steps"]) == 3


def test_trace_report_single_rank_output_unchanged(tmp_path):
    """The multi-rank path must not engage for a single-rank trace: the
    top-level --json shape stays {steps, autotune} (the byte-identical
    single-rank contract)."""
    _synthetic_rank_trace(tmp_path / "r0.json", 0, 0.0)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_report.py"),
         str(tmp_path / "r0.json"), "--json"],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout)
    assert "steps" in out and "autotune" in out and "ranks" not in out


def test_fleet_trace_aligns_anchored_clocks(tmp_path):
    """Two traces whose anchors say rank 1's tracer was born 100ms after
    rank 0's merge with a 100ms shift; a claimed clock offset cancels
    back out."""
    def fake(path, pid, epoch0, offset_ms):
        ev = [{"name": "process_name", "ph": "M", "ts": 0.0, "pid": pid,
               "tid": 0, "args": {"name": f"rank{pid}"}},
              {"name": "clock_sync", "ph": "M", "ts": 0.0, "pid": pid,
               "tid": 0, "args": {"epoch_t0_s": epoch0,
                                  "clock_offset_ms": offset_ms}},
              {"name": "kv_push:w", "cat": "comm", "ph": "X", "ts": 10.0,
               "dur": 5.0, "pid": pid, "tid": 0}]
        with open(path, "w") as f:
            json.dump({"traceEvents": ev}, f)

    fake(tmp_path / "a.json", 0, 1000.0, 0.0)
    fake(tmp_path / "b.json", 1, 1000.1, 0.0)
    fake(tmp_path / "c.json", 2, 1000.1, 100.0)  # clock ran 100ms fast
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import importlib
        ft = importlib.import_module("fleet_trace")
    finally:
        sys.path.pop(0)
    merged = ft.merge([ft.load_trace(str(tmp_path / n))
                       for n in ("a.json", "b.json", "c.json")])
    ts = {e["pid"]: e["ts"] for e in merged if e.get("ph") == "X"}
    assert ts[0] == pytest.approx(10.0)
    assert ts[1] == pytest.approx(10.0 + 100e3)  # born 100ms later
    assert ts[2] == pytest.approx(10.0)  # the offset cancels the anchor


# ---------------------------------------------------------------------------
# the 2-process proof: surviving rank's flight record names the absentee
# ---------------------------------------------------------------------------

@pytest.mark.heavy
def test_two_process_kv_hang_flight_record_and_fleet_skew(tmp_path):
    """tools/launch.py forks 2 workers; rank 1 straggles then withholds
    one exchange (chaos kv_hang). Every surviving rank must write a
    flight record naming the hung (kind, key, seq) and the absent rank
    within MXTPU_COLL_TIMEOUT_S, and the merged 2-rank trace's skew
    report must agree with the live FitResult-shaped comm_health."""
    out_dir = tmp_path / "fleet"
    out_dir.mkdir()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # one cpu device per process
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_COLL_HEALTH"] = "1"
    env["MXTPU_COLL_TIMEOUT_S"] = "1"
    env["MXTPU_MEM_DUMP_DIR"] = str(out_dir)
    env["KV_HANG_OUT_DIR"] = str(out_dir)
    env["KV_HANG_MS"] = "6000"
    env["KV_HANG_COORD_TIMEOUT_MS"] = "4000"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
         "-n", "2", "--launcher", "local",
         "--coordinator", "127.0.0.1:12457",
         sys.executable,
         os.path.join(ROOT, "tests", "dist", "kv_hang_worker.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    for r in range(2):
        assert f"worker {r}/2: comm observability checks passed" in out, \
            out[-4000:]
    # the surviving rank's flight record names collective + absent rank
    flight = [l for l in out.splitlines()
              if l.startswith("FLIGHT_RECORD ")]
    assert len(flight) == 1, out[-4000:]
    rec = json.loads(flight[0][len("FLIGHT_RECORD "):])
    assert rec["absent_rank"] == 1
    assert {"kind": "push", "key": "w", "seq": 3} in rec["hung"]
    # live comm_health (printed by rank 0) vs the offline fleet report
    health_line = [l for l in out.splitlines()
                   if l.startswith("COMM_HEALTH ")]
    assert health_line, out[-4000:]
    health = json.loads(health_line[0][len("COMM_HEALTH "):])
    assert health["straggler_rank"] == 1
    merged = out_dir / "merged.json"
    r2 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "fleet_trace.py"),
         str(out_dir / "rank0.json"), str(out_dir / "rank1.json"),
         "-o", str(merged), "--json"],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert r2.returncode == 0, r2.stderr
    rep = json.loads(r2.stdout)
    assert rep["straggler_rank"] == 1
    from mxnet_tpu.telemetry.chrome_trace import validate_chrome_trace
    with open(merged) as f:
        validate_chrome_trace(json.load(f))
    # trace_report round-trips the LIVE 2-rank merge per rank
    r3 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_report.py"),
         str(merged), "--json"],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert r3.returncode == 0, r3.stderr
    ranks = json.loads(r3.stdout)["ranks"]
    assert set(ranks) == {"0", "1"}
    assert all(rank_rep["steps"] for rank_rep in ranks.values())
    # the two attributions measure the same entries: agree to within a
    # tenth of the injected 250ms straggle (clock + transport noise).
    # Rank 1 is late to 6 of the 14 compared entries, so its mean reads
    # about 105ms on an idle host and three quarters of that under load
    live = health["skew_ms_by_rank"]["1"]["mean_ms"]
    offline = rep["collective_skew_ms"]["1"]["mean_ms"]
    assert live > 40 and offline > 40, (live, offline)
    assert abs(live - offline) < 25 + 0.5 * max(live, offline), \
        (live, offline)
