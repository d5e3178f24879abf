"""The four readers of the replay's children and ``FitLoop``'s stretches
(``metrics/step_spans.py``) on a hand-made event list, and in rehearsed
traced runs of the tiny ``FitLoop`` and Gluon cells."""
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
import program_spans as ps  # noqa: E402
import run as harness  # noqa: E402
from test_program_spans import span  # noqa: E402
from test_rehearsal import run  # noqa: E402

NEW = ["cached_op_prepare_ms", "cached_op_launch_ms", "fit_between_steps_ms",
       "dispatches_per_step.fitloop"]


def replay(t, n, i, parent, prepare=2.0, launch=5.0, missing=()):
    """A replay of 8 ms that starts at ``t``: prepare, launch, finish 1."""
    out = [span(ps.FORWARD, t, 8, n, i, parent=parent, programs=0),
           span("mx.cached_op.prepare", t, prepare, n, i + 1, parent=i,
                programs=4),
           span("mx.cached_op.launch", t + prepare, launch, n, i + 2,
                parent=i, programs=1),
           span("mx.cached_op.finish", t + prepare + launch, 1, n, i + 3,
                parent=i, programs=0)]
    return [e for e in out if e["name"] not in missing]


def fit_step(n, replays=1, missing=(), tid=0):
    """Step ``n`` of a 100 ms ``FitLoop``: the iterator's next 0.25 ms, the
    forward's replay from 0.5 ms on (a second one, of a loss that is
    hybridized too, at 20 ms), backward, update, the fetch until 99.5 ms,
    the close 0.25 ms."""
    t, i = 100.0 * n, 100 * n
    out = [span("mx.fit.step", t, 100, n, i, programs=0, finite=True),
           span("data_wait", t, 0.25, n, i + 1, parent=i, cat="data_wait"),
           span("compute", t + 0.4, 60, n, i + 2, parent=i, cat="compute")]
    for r in range(replays):
        out += replay(t + 0.5 + 20 * r, n, i + 10 * (r + 1), i + 2,
                      missing=missing)
    out += [span("mean", t + 40, 1, n, i + 3, parent=i + 2, cat="operator",
                 programs=1),
            span(ps.BACKWARD, t + 45, 10, n, i + 4, parent=i + 2,
                 programs=3),
            span("optimizer", t + 61, 5, n, i + 5, parent=i,
                 cat="optimizer"),
            span("mx.trainer.update", t + 61.5, 4, n, i + 6, parent=i + 5,
                 programs=2),
            span("compute", t + 66, 33.6, n, i + 7, parent=i, cat="compute"),
            span("mx.fit.fetch", t + 66.1, 33.4, n, i + 8, parent=i + 7,
                 programs=0, blocking=True),
            span("mx.fit.close", t + 99.6, 0.25, n, i + 9, parent=i,
                 programs=0)]
    for e in out:
        e["tid"] = tid
    return [e for e in out if e["name"] not in missing]


def harness_thread(n):
    """The harness's thread beside it: spans that carry another thread's
    step numbers and no root of the loop's."""
    return [span("mx.cached_op.prepare", 100.0 * n, 50, n, 100 * n + 90,
                 tid=1, programs=4)]


@pytest.fixture
def reader(monkeypatch):
    """``read(run)`` of a metric's file, on the given events in the ring's
    place."""
    def read(name, events):
        monkeypatch.setattr(ps, "ring", lambda: events)
        return harness.load_module("metrics", name).read({})
    return read


def test_the_four_readers_on_a_fitloop(reader):
    events = [e for n in range(3, 10)
              for e in fit_step(n) + harness_thread(n)]
    assert reader("cached_op_prepare_ms", events) == pytest.approx(2)
    assert reader("cached_op_launch_ms", events) == pytest.approx(5)
    # fetch ends at 99.5, the next step's forward starts at 100.5
    assert reader("fit_between_steps_ms", events) == pytest.approx(1.0)
    assert reader("dispatches_per_step.fitloop", events) == 4 + 1 + 1 + 3 + 2


def test_the_ends_are_left_out_and_the_step_after_the_last_is_looked_at():
    step_spans = harness.load_module("metrics", "step_spans")
    events = [e for n in range(3, 10) for e in fit_step(n)]
    kept, by_step = step_spans.steps(events, skip=2)
    assert kept == [5, 6, 7, 8]
    assert 9 in by_step  # the step after the last kept: between_ms reads it
    # a longer replay in a step that is left out moves nothing
    slow = [dict(e, dur=40e3) if e["name"] == "mx.cached_op.launch"
            and e["args"]["step"] in (3, 4, 9) else e for e in events]
    assert step_spans.sum_ms("mx.cached_op.launch", slow) \
        == pytest.approx(5)


def test_a_step_with_two_replays_sums_them(reader):
    events = [e for n in range(6) for e in fit_step(n, replays=2)]
    assert reader("cached_op_prepare_ms", events) == pytest.approx(4)
    assert reader("cached_op_launch_ms", events) == pytest.approx(10)
    assert reader("dispatches_per_step.fitloop", events) == 16
    # to the start of the step's FIRST replay
    assert reader("fit_between_steps_ms", events) == pytest.approx(1.0)


def test_a_missing_child_is_none_not_zero(reader):
    events = [e for n in range(6)
              for e in fit_step(n, missing=("mx.cached_op.launch",))]
    assert reader("cached_op_launch_ms", events) is None
    assert reader("cached_op_prepare_ms", events) == pytest.approx(2)
    no_fetch = [e for n in range(6)
                for e in fit_step(n, missing=("mx.fit.fetch",))]
    assert reader("fit_between_steps_ms", no_fetch) is None


def test_an_iteration_that_trained_nothing_closes_no_step(reader):
    events = [e for n in range(6) for e in fit_step(n)]
    events.append(span("mx.fit.step", 600, 0.1, 6, 600, programs=0,
                       trained=False))
    step_spans = harness.load_module("metrics", "step_spans")
    assert step_spans.steps(events, skip=2)[0] == [2, 3, 4]


def test_the_gluon_loop_has_the_replays_two_and_not_fitloops(reader):
    events = []
    for n in range(6):
        t, i = 100.0 * n, 100 * n
        # the replay is a root here
        events += replay(t, n, i + 10, None) + [
            span(ps.BACKWARD, t + 20, 30, n, i + 1, programs=2),
            span(ps.UPDATE, t + 60, 40, n, i + 2)]
        events += harness_thread(n)
    assert reader("cached_op_prepare_ms", events) == pytest.approx(2)
    assert reader("cached_op_launch_ms", events) == pytest.approx(5)
    assert reader("fit_between_steps_ms", events) is None
    assert reader("dispatches_per_step.fitloop", events) is None


def test_a_program_without_the_spans_reads_nothing(reader):
    """The parent of the PR that added them: no ``mx.fit.step``, no children
    under the replay; and a program with no span at all."""
    old = [e for n in range(6) for e in fit_step(n, missing=(
        "mx.fit.step", "mx.fit.fetch", "mx.fit.close",
        "mx.cached_op.prepare", "mx.cached_op.launch",
        "mx.cached_op.finish"))]
    for events in (old, []):
        for name in NEW:
            assert reader(name, events) is None


def test_the_entries_in_benchmark_json():
    real = json.loads((HERE.parents[2] / "BENCHMARK.json").read_text())
    last = real["per_layer"][-4:]
    assert [m["name"] for m in last] == NEW
    fitloop, gluon = "resnet50_train_fitloop", "resnet50_train_gluon"
    assert [m["workloads"] for m in last] == [
        [fitloop, gluon], [fitloop, gluon], [fitloop], [fitloop]]
    for m in last:
        assert (m["layer"], m["moves"], m["better"]) == (
            "entry points and step engine", "samples_per_s", "lower")
    assert [(m["unit"], m["source"]) for m in last] == [
        ("ms", "program_span")] * 3 + [("count", "program_counter")]


def test_the_fitloop_toy_cell_reports_all_four():
    done = run(["--rehearse", str(HERE / "rehearse_35"), "--workload",
                "resnet50_train_fitloop", "--seed", str(2**31 + 38),
                "--seconds", "8", "--trace", "1"])
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line
    assert set(line["metrics"]) == {"host_dispatch_ms", *NEW}
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(m[name] > 0 for name in NEW)
    # the Gluon toy cell's 20 (below) and the sentinel's flag beside the
    # update; what the chip reads is in PERF.md
    assert m["dispatches_per_step.fitloop"] == 21


def test_the_gluon_toy_cell_reports_the_replays_two_and_every_launch():
    done = run(["--rehearse", str(HERE / "rehearse"), "--workload",
                "resnet50_train_gluon", "--seed", "38", "--seconds", "8",
                "--trace", "1"])
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert not {"fit_between_steps_ms", "dispatches_per_step.fitloop"} \
        & set(m)
    assert 0 < m["cached_op_prepare_ms"] and 0 < m["cached_op_launch_ms"]
    assert m["cached_op_prepare_ms"] + m["cached_op_launch_ms"] \
        <= m["host_forward_ms"]
    # 15 until PR 38: the random key's four programs and the second of the
    # head gradient's two now have an owner
    assert m["dispatches_per_step"] == 20
