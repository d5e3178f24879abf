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

``gaps_by_span(xplane_path)`` lays the same spans, which a session also
writes into the trace's host plane, against the device's idle gaps.

    python3 benchmark/chip/program_spans.py <file.xplane.pb>

prints that as one JSON object.
"""
import json
import pathlib
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


def other_ms(step):
    """What is left of a Gluon step's period once its forward, backward and
    update are taken out; None where one of them has no span."""
    phases = [step["root_ms"].get(name) for name in (FORWARD, BACKWARD, UPDATE)]
    if None not in phases:
        return step["period_ms"] - sum(phases)


def median(values):
    """Over the steps that have the value; None where none has."""
    values = [v for v in values if v is not None]
    if values:
        return statistics.median(values)


def root_ms(name, events=None):
    return median(s["root_ms"].get(name) for s in steps(events))


# ---------------------------------------------------------------------------
# the device's idle gaps under the program's spans

def load_host(path, prefixes=("mx.", "bench.")):
    """The host plane's spans whose names start with one of ``prefixes``,
    as ``(name, start_s, end_s)``."""
    import gzip
    from jax.profiler import ProfileData
    raw = pathlib.Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    host = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                         for e in line.events if e.name.startswith(prefixes)]
    return sorted(host, key=lambda s: s[1])


def innermost(spans, s, e):
    """The name of the shortest span that covers more than half of the gap
    (s, e): spans of one thread nest, so that is the innermost. Where none
    covers half, the one that covers most; "host.between" where none
    touches it."""
    best, key = "host.between", None
    for name, hs, he in spans:
        overlap = min(e, he) - max(s, hs)
        if overlap <= 0:
            continue
        k = (1, hs - he) if 2 * overlap > e - s else (0, overlap)
        if key is None or k > key:
            best, key = name, k
    return best


def gaps_by_span(xplane_path, skip=trace_reduce.SKIP, top=10):
    """The idle gaps of the first device in the traced window
    (``trace_reduce``'s window and interval arithmetic), each put down to
    the innermost ``mx.`` span that covers most of it (to the ``bench.``
    span where no ``mx.`` span touches it): the ``top`` longest, and the
    idle milliseconds per step by span. None where the trace has no window
    or no device."""
    trace = trace_reduce.load(xplane_path)
    win = trace_reduce.window(trace["host"], skip)
    if win is None or not trace["devices"]:
        return None
    dev = next(iter(trace["devices"].values()))
    lo = trace_reduce.snap(dev["modules"], win[0])
    hi = trace_reduce.snap(dev["modules"], win[1])
    busy = trace_reduce.union([(max(s, lo), min(e, hi))
                               for _, _, s, e in dev["ops"]
                               if min(e, hi) > max(s, lo)])
    host = load_host(xplane_path)
    ours = [h for h in host if h[0].startswith("mx.")]
    named = []
    for s, e in trace_reduce.subtract([(lo, hi)], busy):
        name = innermost(ours, s, e)
        if name == "host.between":
            name = innermost(host, s, e)
        named.append((name, e - s))
    per_step = defaultdict(float)
    for name, seconds in named:
        per_step[name] += 1e3 * seconds / win[2]
    return {
        "steps": win[2], "window_ms_per_step": 1e3 * (hi - lo) / win[2],
        "idle_ms_per_step": sum(per_step.values()),
        "longest_ms": [[name, 1e3 * seconds] for name, seconds in
                       sorted(named, key=lambda p: -p[1])[:top]],
        "idle_ms_per_step_by_span": dict(
            sorted(per_step.items(), key=lambda p: -p[1])),
        "host_spans": _host_spans(host, win),
    }


def _host_spans(host, win):
    """For each span name, over the spans that start inside the window: how
    many a step, their median milliseconds, and their milliseconds a step."""
    ms = defaultdict(list)
    for name, s, e in host:
        if win[0] <= s < win[1]:
            ms[name].append(1e3 * (e - s))
    return {name: {"per_step": len(v) / win[2],
                   "median_ms": statistics.median(v),
                   "ms_per_step": sum(v) / win[2]}
            for name, v in sorted(ms.items(), key=lambda p: -sum(p[1]))}


if __name__ == "__main__":
    print(json.dumps(gaps_by_span(sys.argv[1])))
