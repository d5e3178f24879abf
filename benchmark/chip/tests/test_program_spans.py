"""``program_spans`` on a hand-made event list, and the four span metrics in
a rehearsed traced run of the tiny Gluon and SPMD cells."""
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
import program_spans as ps  # noqa: E402
from test_rehearsal import run  # noqa: E402

US = 1e3  # a millisecond, in the ring's microseconds


def span(name, ts, dur, step, ident, parent=None, cat="step", tid=0, **args):
    args.update(id=ident, step=step)
    if parent is not None:
        args["parent"] = parent
    return {"name": name, "cat": cat, "ts": ts * US, "dur": dur * US,
            "pid": 0, "tid": tid, "args": args}


def gluon_step(n, missing=()):
    """Step ``n`` of a 100 ms loop: forward 10 ms, two eager ops of 1 ms,
    backward 30 ms (its vjp 20 of them, the delivery 5), trainer.step 40 ms
    (its update 35), so 18 ms of the period are no phase's."""
    t, i = 100.0 * n, 10 * n
    out = [
        span(ps.FORWARD, t, 10, n, i, programs=1),
        span("softmax", t + 11, 1, n, i + 1, cat="operator", programs=1),
        span("mean", t + 13, 1, n, i + 2, cat="operator", programs=1),
        span(ps.BACKWARD, t + 20, 30, n, i + 3, programs=2),
        span("mx.cached_op.vjp", t + 22, 20, n, i + 4, parent=i + 3,
             programs=1),
        span("mx.autograd.deliver", t + 44, 5, n, i + 5, parent=i + 3,
             programs=0),
        span(ps.UPDATE, t + 60, 40, n, i + 6),
        span("mx.trainer.update", t + 62, 35, n, i + 7, parent=i + 6,
             programs=4),
        # another thread's spans do not count
        span(ps.FORWARD, t, 50, n, i + 8, tid=1, programs=1),
    ]
    return [e for e in out if e["name"] not in missing]


def test_steps_are_grouped_and_the_ends_left_out():
    events = [e for n in range(3, 10) for e in gluon_step(n)]
    steps = ps.steps(events, skip=2)
    # seven steps closed; the first two and the last are left out
    assert [s["step"] for s in steps] == [5, 6, 7, 8]
    for s in steps:
        assert s["period_ms"] == pytest.approx(100)
        assert s["programs"] == 10
        assert s["root_ms"] == pytest.approx(
            {ps.FORWARD: 10, ps.BACKWARD: 30, ps.UPDATE: 40})
        assert s["total_ms"]["mx.cached_op.vjp"] == pytest.approx(20)
        assert s["self_ms"][ps.BACKWARD] == pytest.approx(5)
        assert s["self_ms"][ps.UPDATE] == pytest.approx(5)
        assert s["self_ms"][ps.FORWARD] == pytest.approx(10)
    assert ps.root_ms(ps.BACKWARD, events) == pytest.approx(30)


def test_a_missing_phase_is_none_not_zero():
    events = [e for n in range(6) for e in gluon_step(
        n, missing=(ps.BACKWARD, "mx.cached_op.vjp", "mx.autograd.deliver"))]
    steps = ps.steps(events, skip=1)
    assert len(steps) == 4
    assert all(ps.BACKWARD not in s["root_ms"] for s in steps)
    assert ps.root_ms(ps.BACKWARD, events) is None
    assert ps.root_ms(ps.FORWARD, events) == pytest.approx(10)
    assert ps.median(s["root_ms"].get(ps.BACKWARD) for s in steps) is None


def test_no_step_spans_no_steps():
    assert ps.steps([]) == []
    ops = [span("dot", 1, 1, 0, 1, cat="operator", programs=1)]
    assert ps.steps(ops) == []
    assert ps.median(s["programs"] for s in ps.steps(ops)) is None
    # too few closed steps to leave the ends out
    assert ps.steps([e for n in range(3) for e in gluon_step(n)], skip=2) == []


def test_spmd_steps():
    events = []
    for n in range(6):
        t, i = 10.0 * n, 10 * n
        events += [span("mx.spmd.step", t, 6, n, i, programs=0),
                   span("mx.spmd.launch", t + 1, 4, n, i + 1, parent=i,
                        programs=2)]
    steps = ps.steps(events, skip=2)
    assert [s["step"] for s in steps] == [2, 3, 4]
    assert all(s["programs"] == 2 for s in steps)
    assert steps[0]["self_ms"]["mx.spmd.step"] == pytest.approx(2)


@pytest.mark.parametrize("cell", ["resnet50_train_gluon",
                                  "resnet50_train_spmd"])
def test_span_metrics_in_a_rehearsed_traced_run(cell):
    done = run(["--rehearse", str(HERE / "rehearse"), "--workload", cell,
                "--seed", "7", "--seconds", "8", "--trace", "1"])
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["dispatches_per_step"] >= 2
    phases = ["host_forward_ms", "host_backward_ms", "host_update_ms"]
    if cell.endswith("gluon"):
        assert all(m[p] > 0 for p in phases)
        # what the cell reads on the chip since PR 30, one update program
        # a bucket key (ledger, PRs 30 - 34); 55 before it
        assert m["dispatches_per_step"] == 15
    else:
        assert not set(phases) & set(m)
        assert m["dispatches_per_step"] == 2
