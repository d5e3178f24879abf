"""Self-healing fleet supervisor (parallel/supervisor.py + tools/launch.py
--supervise): the table-driven escalation-ladder proofs over the pure
``decide`` function, capacity models, the supervisor-consumable
flight-record schema (stable ``absent_rank``/``hung_since`` + parse
helper, pinned against a PR 12-layout fixture AND a live ``_dump_flight``
round-trip), the launcher exit-code taxonomy, the crash-loop/budget
termination drill (jax-free stub workers — bounded, never an infinite
relaunch), and the acceptance chaos soak: a real supervised 2-worker
fleet surviving a scripted rank kill, hung collective and graceful
resize with zero human intervention, the union-of-trained-samples and
loss-trajectory contracts intact.

Marker ``supervisor``."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import supervisor as sv
from mxnet_tpu.telemetry import collective as coll

pytestmark = pytest.mark.supervisor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(ROOT, "tests", "data",
                       "coll_flight_pr12_fixture.json")

# every decide() call below pins the knobs explicitly so the table is
# hermetic to the environment
KNOBS = dict(max_restarts=8, crash_window_s=300.0, crash_limit=3)


def _ev(kind, rank=None, t=0.0, ranks=None):
    e = {"kind": kind, "rank": rank, "time": t}
    if ranks is not None:
        e["ranks"] = ranks
    return e


# ----------------------------------------------------------- env knobs

def test_supervise_knobs_strict_parse(monkeypatch):
    monkeypatch.setenv("MXTPU_SUPERVISE_MAX_RESTARTS", "5")
    assert sv.supervise_max_restarts() == 5
    monkeypatch.setenv("MXTPU_SUPERVISE_CRASH_WINDOW_S", "12.5")
    assert sv.supervise_crash_window_s() == 12.5
    monkeypatch.setenv("MXTPU_SUPERVISE_CRASH_LIMIT", "2")
    assert sv.supervise_crash_limit() == 2
    for name, fn in (
            ("MXTPU_SUPERVISE_MAX_RESTARTS", sv.supervise_max_restarts),
            ("MXTPU_SUPERVISE_CRASH_WINDOW_S",
             sv.supervise_crash_window_s),
            ("MXTPU_SUPERVISE_CRASH_LIMIT", sv.supervise_crash_limit)):
        monkeypatch.setenv(name, "yolo")
        with pytest.raises(MXNetError, match=name):
            fn()
        monkeypatch.delenv(name)
    monkeypatch.setenv("MXTPU_SUPERVISE_MAX_RESTARTS", "-1")
    with pytest.raises(MXNetError, match="MXTPU_SUPERVISE_MAX_RESTARTS"):
        sv.supervise_max_restarts()
    monkeypatch.setenv("MXTPU_SUPERVISE_CRASH_LIMIT", "0")
    with pytest.raises(MXNetError, match="MXTPU_SUPERVISE_CRASH_LIMIT"):
        sv.supervise_crash_limit()


def test_classify_exit_taxonomy():
    assert sv.classify_exit(0) == "ok"
    assert sv.classify_exit(75) == "resumable"
    assert sv.classify_exit(-9) == "signal"
    assert sv.classify_exit(-15) == "signal"
    assert sv.classify_exit(1) == "fatal"
    assert sv.classify_exit(137) == "fatal"
    with pytest.raises(MXNetError):
        sv.classify_exit(None)


# -------------------------------------- the escalation ladder, by table

LADDER = [
    # (id, events, world, knob overrides, expected action subset)
    ("flake_retries",
     [_ev("flake", 0)], 2, {}, {"op": "retry"}),
    ("flake_even_after_incidents",
     [_ev("crash", 1, 0.0), _ev("flake", 0, 1.0)], 2, {},
     {"op": "retry"}),
    ("single_crash_shrinks",
     [_ev("crash", 1, 0.0)], 2, {},
     {"op": "shrink", "world": 1, "lost": [1]}),
    ("hang_shrinks_absent_rank",
     [_ev("hang", 0, 0.0, ranks=[0])], 3, {},
     {"op": "shrink", "world": 2, "lost": [0]}),
    ("multi_rank_death_shrinks_by_all",
     [_ev("crash", 0, 0.0, ranks=[0, 2])], 4, {},
     {"op": "shrink", "world": 2, "lost": [0, 2]}),
    ("whole_group_death_relaunches_at_floor",
     [_ev("crash", 0, 0.0, ranks=[0, 1])], 2, {},
     {"op": "shrink", "world": 1}),
    ("resumable_resumes_same_world",
     [_ev("resumable")], 2, {}, {"op": "resume", "world": 2}),
    ("crash_loop_excludes_slot",
     [_ev("crash", 1, t) for t in (0.0, 10.0, 20.0)], 2,
     {"crash_limit": 3}, {"op": "exclude", "rank": 1, "world": 1}),
    ("crash_loop_window_expired_shrinks",
     [_ev("crash", 1, t) for t in (0.0, 10.0, 1000.0)], 2,
     {"crash_limit": 3, "crash_window_s": 300.0},
     {"op": "shrink", "world": 1}),
    ("crashes_of_different_ranks_shrink",
     [_ev("crash", 0, 0.0), _ev("crash", 1, 10.0)], 2,
     {"crash_limit": 2}, {"op": "shrink", "world": 1}),
    ("budget_exhausted_fails",
     [_ev("crash", 1, float(t)) for t in range(4)], 2,
     {"max_restarts": 3, "crash_limit": 99}, {"op": "fail"}),
    ("budget_counts_resumables",
     [_ev("resumable"), _ev("resumable"), _ev("resumable")], 2,
     {"max_restarts": 2}, {"op": "fail"}),
    ("budget_ignores_flakes",
     [_ev("flake", 0, float(t)) for t in range(10)] +
     [_ev("crash", 1, 11.0)], 2,
     {"max_restarts": 1}, {"op": "shrink", "world": 1}),
    ("budget_outranks_crash_loop",
     [_ev("crash", 1, float(t)) for t in range(5)], 3,
     {"max_restarts": 2, "crash_limit": 3}, {"op": "fail"}),
    ("exclude_below_floor_fails",
     [_ev("crash", 0, t) for t in (0.0, 1.0, 2.0)], 1,
     {"crash_limit": 3}, {"op": "fail"}),
]


@pytest.mark.parametrize("events,world,over,want",
                         [c[1:] for c in LADDER],
                         ids=[c[0] for c in LADDER])
def test_decide_ladder(events, world, over, want):
    got = sv.decide(events, world=world, floor=1, **{**KNOBS, **over})
    for k, v in want.items():
        assert got[k] == v, (got, want)


def test_decide_rejects_garbage():
    with pytest.raises(MXNetError, match="empty"):
        sv.decide([], world=2, **KNOBS)
    with pytest.raises(MXNetError, match="unknown event kind"):
        sv.decide([_ev("meteor", 0)], world=2, **KNOBS)


# ------------------------------------------------------ capacity models

def test_capacity_models():
    s = sv.StaticCapacity(4)
    assert s.available(0.0) == s.available(1e9) == 4
    m = sv.SpotCapacityModel(3, recovery_s=10.0)
    assert m.available(0.0) == 3
    m.note_lost(2, 100.0)
    assert m.available(105.0) == 1   # both slots still out
    assert m.available(110.0) == 3   # recovered
    m.note_lost(1, 200.0)
    assert m.available(205.0) == 2
    with pytest.raises(MXNetError):
        sv.SpotCapacityModel(0)


# ------------------------------- flight-record schema (supervisor view)

def test_parse_flight_record_pr12_fixture():
    """The PR 12 on-disk layout (no ``hung_since``) keeps parsing: old
    dumps on a crashed fleet's disk must stay supervisor-readable."""
    rec = coll.parse_flight_record(FIXTURE)
    assert rec["absent_rank"] == 0
    assert rec["rank"] == 1 and rec["pid"] == 41873
    assert rec["hung_since"] is None          # pre-PR-17 record
    assert rec["hung"][0]["seq"] == 7


def test_parse_flight_record_rejects_non_flight(tmp_path):
    p = tmp_path / "coll_flight_bogus.json"
    p.write_text(json.dumps({"reason": "oom"}))
    with pytest.raises(MXNetError, match="not 'hung_collective'"):
        coll.parse_flight_record(str(p))
    p.write_text("{not json")
    with pytest.raises(MXNetError, match="unreadable"):
        coll.parse_flight_record(str(p))


def test_live_dump_roundtrips_through_parser(monkeypatch, tmp_path):
    """Producer<->consumer pin: a record written by the REAL
    ``_dump_flight`` carries top-level ``absent_rank`` + ``hung_since``
    and round-trips through ``parse_flight_record`` — schema drift on
    either side fails here."""
    monkeypatch.setenv("MXTPU_MEM_DUMP_DIR", str(tmp_path))
    t = time.perf_counter() - 7.0
    path = coll.ledger._dump_flight(
        [{"kind": "push", "key": "_gbkt_0", "seq": 3, "bytes": 64,
          "rank": 1, "waiting_for": 0, "t_enter": t}], 5.0)
    rec = coll.parse_flight_record(path)
    assert rec["absent_rank"] == 0
    assert rec["hung_since"] == pytest.approx(coll.ledger.epoch_of(t))
    assert rec["hung"][0]["waiting_for_rank"] == 0

    seen = set()
    recs = coll.scan_flight_records(str(tmp_path), seen)
    assert [r["path"] for r in recs] == [path] and path in seen
    assert coll.scan_flight_records(str(tmp_path), seen) == []  # consumed


# --------------------------------------- launcher exit-code taxonomy

def _load_launch():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "launch_mod", os.path.join(ROOT, "tools", "launch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_launch_wait_group_taxonomy():
    """Satellite: _wait_group distinguishes resumable / fatal / signal.
    A resumable exit must NOT fail-fast-kill the draining peers; a
    fatal one must. The group verdict carries the distinction."""
    launch = _load_launch()
    assert launch._classify_exit(0) == "ok"
    assert launch._classify_exit(75) == "resumable"
    assert launch._classify_exit(-9) == "signal"
    assert launch._classify_exit(3) == "fatal"

    def popen(code, delay=0.0):
        return subprocess.Popen(
            [sys.executable, "-c",
             f"import time,sys; time.sleep({delay}); sys.exit({code})"])

    # all ok -> 0
    assert launch._wait_group([(0, popen(0)), (1, popen(0))]) == 0
    # one resumable + one slow-ok: peers NOT killed, verdict = 75
    slow = popen(0, delay=1.0)
    assert launch._wait_group([(0, popen(75)), (1, slow)]) == 75
    assert slow.returncode == 0, "draining peer was killed"
    # fatal kills the group and wins over a resumable
    hang = subprocess.Popen([sys.executable, "-c",
                             "import time; time.sleep(60)"])
    rc = launch._wait_group([(0, popen(3)), (1, popen(75)), (2, hang)])
    assert rc == 3
    assert hang.wait(timeout=10) != 0, "fatal death did not kill peers"


# ------------------------- crash loop + budget: bounded, loud, bundled

def test_supervisor_crash_loop_budget_terminates(tmp_path, capsys):
    """A fleet whose workers ALWAYS crash must terminate by the ladder
    (shrink -> crash-loop exclude/floor -> budget fail), never relaunch
    forever, and leave the forensic bundle. Stub workers are jax-free:
    the whole drill is seconds."""
    spawned = []

    def spawn(world, gen, extra):
        spawned.append((gen, world))
        return {r: subprocess.Popen([sys.executable, "-c",
                                     "import sys; sys.exit(3)"])
                for r in range(world)}

    from mxnet_tpu.telemetry import default_registry
    before = getattr(default_registry().get(
        "mxtpu_supervisor_restarts_total"), "value", 0)
    sup = sv.Supervisor(spawn, 2, state_dir=str(tmp_path),
                        dump_dir=str(tmp_path / "dumps"),
                        max_restarts=3, crash_window_s=300.0,
                        crash_limit=3, term_grace_s=0.5, floor=1)
    t0 = time.monotonic()
    rc = sup.run()
    assert rc == 1
    assert time.monotonic() - t0 < 60
    # bounded: every failure relaunch is budgeted and every grow needs
    # a preceding shrink, so generations <= 2*budget + 2 — never an
    # infinite relaunch loop
    assert len(spawned) <= 2 * 3 + 2
    assert sup.restarts <= 3
    after = default_registry().get("mxtpu_supervisor_restarts_total")
    assert after is not None and after.value - before == sup.restarts

    out = capsys.readouterr().out
    summary = json.loads(out.split("SUPERVISOR_SUMMARY ", 1)[1])
    assert summary["ok"] is False
    assert [e["kind"] for e in summary["events"]].count("crash") >= 2
    bundle = summary["forensics"]
    assert bundle and os.path.isdir(bundle)
    with open(os.path.join(bundle, "events.json")) as f:
        dumped = json.load(f)
    assert dumped["summary"]["reason"]
    assert os.path.exists(os.path.join(bundle, "manifest.json")) or \
        os.path.exists(os.path.join(bundle, "MANIFEST.txt"))


def test_supervisor_excludes_crash_looping_slot(tmp_path):
    """Rung 3 in-process: when one slot crash-loops while the rest of
    the fleet is healthy, the supervisor EXCLUDES it and continues
    smaller instead of burning the whole budget on it."""
    # slot 1 crashes whenever it exists (a bad host); every other rank
    # is healthy: drains resumable on SIGTERM, finishes clean otherwise.
    # With the default StaticCapacity the supervisor grows straight
    # back after the first shrink — putting the bad slot back in play,
    # which is exactly what the crash-loop rung must then stop.
    crash = "import time,sys; time.sleep(0.1); sys.exit(3)"
    healthy = ("import signal,sys,time;"
               "signal.signal(signal.SIGTERM, lambda *a: sys.exit(75));"
               "time.sleep(1.5); sys.exit(0)")

    def spawn(world, gen, extra):
        return {r: subprocess.Popen(
                    [sys.executable, "-c", crash if r == 1 else healthy])
                for r in range(world)}

    sup = sv.Supervisor(spawn, 2, state_dir=str(tmp_path),
                        dump_dir=str(tmp_path / "dumps"),
                        max_restarts=8, crash_window_s=300.0,
                        crash_limit=2, term_grace_s=2.0, floor=1)
    rc = sup.run()
    assert rc == 0
    kinds = [e["kind"] for e in sup.events]
    assert all(k == "crash" for k in kinds) and len(kinds) == 2
    assert sup.excluded == [1], (sup.excluded, sup.events)
    # after the exclusion the fleet ran (and finished) at world 1
    assert sup.generations[-1]["world"] == 1
    assert sup.generations[-1]["outcome"] == "done"
    assert sup.grows >= 1


# ----------------------------------------------- the chaos soak (tentpole)

@pytest.mark.heavy
def test_selfheal_chaos_soak(tmp_path):
    """Acceptance: a supervised 2-worker fleet survives three scripted
    chaos events — rank kill, hung collective (kv_hang + watchdog
    flight record), graceful resize — with ZERO human intervention:
    auto-shrink to the survivor, auto-grow back when the spot capacity
    model recovers, run to completion. The union of trained samples
    equals the no-failure stream exactly and the per-step summed loss
    trajectory matches a never-failed fixed-global-batch reference.
    ``restarts`` in the supervisor summary equals the injected event
    count (grows are free)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "selfheal_worker",
        os.path.join(ROOT, "tests", "dist", "selfheal_worker.py"))
    sw = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sw)

    out = str(tmp_path)
    dumps = os.path.join(out, "dumps")
    os.makedirs(dumps)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # one cpu device per process
    env.pop("MXTPU_CHAOS", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "MXTPU_ZERO": "1",
        "MXTPU_OPTIMIZER_AGGREGATION": "8",
        "MXTPU_COLL_TIMEOUT_S": "1",
        "MXTPU_MEM_DUMP_DIR": dumps,
        "MXTPU_COORD_TIMEOUT_MS": "20000",
        "MXTPU_SUPERVISE_MAX_RESTARTS": "6",
        "SELFHEAL_OUT_DIR": out,
        "SELFHEAL_TARGET": "2",
        "SELFHEAL_STEP_SLEEP_MS": "500",
        "SELFHEAL_EVENTS": json.dumps({
            "0": {"kind": "kill", "rank": 1, "offset": 2},
            "2": {"kind": "kv_hang", "rank": 0, "offset": 2},
            "4": {"kind": "resize", "world": 2, "offset": 2},
        }),
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
         "-n", "2", "--launcher", "local",
         "--coordinator", "127.0.0.1:12700",
         "--supervise",
         "--supervise-ckpt", os.path.join(out, "ckpt_r0"),
         "--supervise-dir", out,
         "--supervise-grace", "2", "--supervise-recovery", "2.5",
         sys.executable,
         os.path.join(ROOT, "tests", "dist", "selfheal_worker.py")],
        capture_output=True, text=True, timeout=240, env=env, cwd=ROOT)
    text = proc.stdout + proc.stderr
    assert proc.returncode == 0, text[-4000:]

    summary = json.loads(
        text.split("SUPERVISOR_SUMMARY ", 1)[1].split("\n", 1)[0])
    assert summary["ok"] is True
    # mxtpu_supervisor_restarts_total == injected chaos events
    assert summary["restarts"] == 3, summary
    assert [e["kind"] for e in summary["events"]] == \
        ["crash", "hang", "resumable"], summary["events"]
    # each shrink was followed by a capacity-driven grow back to target
    assert summary["grows"] == 2, summary
    assert summary["final_world"] == 2
    assert summary["excluded"] == []
    # the hang event named the withholding rank from the flight record
    hang = summary["events"][1]
    assert hang["ranks"] == [0], hang

    # ---- never-failed reference: world 1, same fixed global batch G
    # and sum loss -> world-independent trajectory
    import mxnet_tpu as mx
    from mxnet_tpu import fit, gluon, io
    for k in ("MXTPU_ZERO", "MXTPU_ZERO_WORLD", "MXTPU_ELASTIC"):
        os.environ.pop(k, None)
    X, Y = sw.make_data()
    mx.random.seed(0)
    net = gluon.nn.Dense(1, in_units=3)
    net.initialize(mx.init.Constant(0.25))
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9},
                       kvstore=None)
    it = io.NDArrayIter(X, Y, batch_size=sw.G, shuffle=True, seed=sw.SEED)
    loop = fit.FitLoop(net, tr, lambda o, y: ((o - y) ** 2).sum(), it,
                       ckpt_dir=None, heartbeat=False, seed=sw.SEED)
    ref = loop.fit(epochs=sw.EPOCHS, batch_size=sw.G)
    total_steps = (sw.N // sw.G) * sw.EPOCHS
    assert ref.step == total_steps

    ref_stream = []
    rit = io.NDArrayIter(X, Y, batch_size=sw.G, shuffle=True, seed=sw.SEED)
    for ep in range(sw.EPOCHS):
        rit.set_epoch(ep)
        for bt in rit:
            ref_stream += sw.batch_ids(bt.data[0].asnumpy())

    # ---- union proof: every step's ids, across all ranks of all
    # generations, equals the no-failure stream — zero dup, zero drop
    consumed = []
    per_step = {}
    logs = [n for n in os.listdir(out) if n.startswith("steps_r")]
    assert logs, text[-2000:]
    for name in logs:
        with open(os.path.join(out, name)) as f:
            for line in f:
                rec = json.loads(line)
                consumed += rec["ids"]
                per_step[rec["step"]] = \
                    per_step.get(rec["step"], 0.0) + rec["loss"]
    assert sorted(consumed) == sorted(ref_stream)
    assert len(consumed) == len(ref_stream) == sw.N * sw.EPOCHS

    # ---- trajectory contract: per-step summed loss across however
    # many ranks trained that step == the never-failed reference
    assert sorted(per_step) == list(range(total_steps))
    np.testing.assert_allclose(
        [per_step[s] for s in range(total_steps)], ref.losses,
        rtol=1e-4, atol=1e-6)

    # ---- final weights from the last generation agree with reference
    dec = json.JSONDecoder()
    done = [dec.raw_decode(chunk.lstrip())[0]
            for chunk in text.split("SELFHEAL_DONE ")[1:]]
    final_gen = max(d["gen"] for d in done)
    finals = [d for d in done if d["gen"] == final_gen]
    assert sorted(d["rank"] for d in finals) == [0, 1]
    for d in finals:
        np.testing.assert_allclose(
            np.asarray(d["weight"]),
            net.weight.data().asnumpy().ravel(), rtol=1e-5, atol=1e-7)

    # the hung-collective evidence is on disk: at least one flight
    # record in the dump dir names rank 0 absent
    recs = coll.scan_flight_records(dumps)
    assert any(r["absent_rank"] == 0 for r in recs), recs
