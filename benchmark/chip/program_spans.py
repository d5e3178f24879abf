"""The program's own step spans, read for the host metrics of the step engine.

``mxnet_tpu.telemetry``'s tracer is on while a profiler session runs, so
after the traced steps of a ``--trace 1`` run its ring holds them: one span
per phase of a step (``mx.cached_op.forward``, ``mx.autograd.backward``,
``mx.trainer.step``, ``mx.spmd.step`` and their children) and one per eager
operator, each with ``id``, ``parent``, ``step`` and, where it launches
executables itself, ``programs`` in its ``args``. ``steps()`` groups them by
step and gives each step's period, total and self time per span name and the
launches the spans own; the readers under ``metrics/`` take medians over the
steps. A program without such spans (the parent of the PR that added them)
gives no steps, and the readers return nothing.

A session also writes the same spans into the trace's host plane, where
``trace_reduce`` lays them against the device's idle gaps;
``gaps_by_span(xplane_path)`` gives that for a kept trace.

    python3 benchmark/chip/program_spans.py <file.xplane.pb>

prints that as one JSON object.
"""
import json
import statistics
import sys
from collections import defaultdict

import trace_reduce

# the spans that end a step, and the phases of a Gluon step
CLOSERS = ("mx.trainer.step", "mx.spmd.step")
FORWARD, BACKWARD, UPDATE = \
    "mx.cached_op.forward", "mx.autograd.backward", "mx.trainer.step"


def ring():
    """The tracer's recorded spans that carry a step number."""
    try:
        from mxnet_tpu.telemetry import tracer
    except ImportError:
        return []
    return [e for e in tracer.events()
            if e.get("ph", "X") == "X" and "step" in e.get("args", {})]


def steps(events=None, skip=trace_reduce.SKIP):
    """One dict per whole step of the thread that stepped: ``step``,
    ``period_ms`` (end of the span that closed the step before to the end of
    the one that closed this), ``programs`` (sum over the step's spans),
    ``total_ms`` and ``self_ms`` by name over the spans of category ``step``,
    and ``root_ms`` by name over those of them that have no parent. Left out
    are the first ``skip`` steps (the pipeline refills after the profiler's
    start) and the last, as ``trace_reduce`` leaves them out of the window.
    """
    events = ring() if events is None else events
    closers = [e for e in events if e["name"] in CLOSERS
               and "parent" not in e["args"]]
    if not closers:
        return []
    tid = statistics.mode(e["tid"] for e in closers)
    end_of = {e["args"]["step"]: e["ts"] + e["dur"]
              for e in closers if e["tid"] == tid}
    by_step = defaultdict(list)
    for e in events:
        if e["tid"] == tid:
            by_step[e["args"]["step"]].append(e)
    out = []
    for n in sorted(end_of)[skip:-1]:
        if n - 1 not in end_of:
            continue
        children = defaultdict(float)
        for e in by_step[n]:
            if "parent" in e["args"]:
                children[e["args"]["parent"]] += e["dur"]
        total, own, root = \
            defaultdict(float), defaultdict(float), defaultdict(float)
        for e in by_step[n]:
            if e["cat"] != "step":
                continue
            total[e["name"]] += e["dur"] / 1e3
            own[e["name"]] += max(
                e["dur"] - children[e["args"]["id"]], 0.0) / 1e3
            if "parent" not in e["args"]:
                root[e["name"]] += e["dur"] / 1e3
        out.append({
            "step": n, "period_ms": (end_of[n] - end_of[n - 1]) / 1e3,
            "programs": sum(e["args"].get("programs", 0)
                            for e in by_step[n]),
            "total_ms": dict(total), "self_ms": dict(own),
            "root_ms": dict(root)})
    return out


def median(values):
    """Over the steps that have the value; None where none has."""
    values = [v for v in values if v is not None]
    if values:
        return statistics.median(values)


def root_ms(name, events=None):
    return median(s["root_ms"].get(name) for s in steps(events))


# ---------------------------------------------------------------------------
# a kept trace's idle gaps under the program's spans

def gaps_by_span(xplane_path):
    """What every traced run's ``breakdown.idle_gaps`` is cut from, in full,
    for a trace kept with ``--keep-trace``: the idle milliseconds a step of
    the first device by the host span ``trace_reduce`` puts each gap down
    to. None where the trace has no window or no device."""
    out = trace_reduce.reduce(trace_reduce.load(xplane_path))
    if out:
        by_span = {name: 1e3 * seconds / out["steps"] for name, seconds in
                   out["idle_seconds_by_span"].items()}
        return {"steps": out["steps"],
                "window_ms_per_step": 1e3 * out["window_s"] / out["steps"],
                "idle_ms_per_step": sum(by_span.values()),
                "idle_ms_per_step_by_span": dict(
                    sorted(by_span.items(), key=lambda p: -p[1])),
                "longest_ms": [[name, 1e3 * seconds]
                               for name, seconds in out["idle_gaps"]]}


if __name__ == "__main__":
    print(json.dumps(gaps_by_span(sys.argv[1])))
