"""From a profiler trace to the numbers the layer metrics read.

``load(path)`` turns an ``.xplane.pb`` into plain data, ``reduce(trace)``
turns that into one dict. Everything below ``load`` works on plain tuples, so
tests/test_trace_reduce.py feeds it a hand-made event list.

A trace is ``{"devices": {name: {"ops": [...], "async": [...], "modules":
[...]}}, "host": [...]}``. An op is ``(name, kind, start_s, end_s)``, a module
launch ``(name, start_s, end_s)``, a host span ``(name, start_s, end_s)``, all
on the trace's one clock. ``ops`` is the device's "XLA Ops" line, one op at a
time; ``async`` its "Async XLA Ops" line, each event from an asynchronous
op's start to its done, running beside the others (copies, and across chips
the collectives).

The window that is reduced holds whole steps of device work, whichever way
the host runs ahead: a step's last program ends just before its
``bench.wait`` span returns (the host wakes a millisecond or so later), so on
each device the window runs from the end of the last program that ended
before one wait returned to the end of the last program that ended before a
later wait returned. The first ``SKIP`` waits are left out, while the
pipeline refills after the profiler's start, and so is the last: it drains
the pipeline with no dispatch before it, so where the host sets the pace it
returns at once and bounds no whole step.
"""
import pathlib
import re
import statistics
from collections import defaultdict

WAIT = "bench.wait"
SKIP = 2
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
# a TPU trace names an op by its HLO text: "%fusion.7 = bf16[256,56,56,64]{..}
# fusion(..), kind=kLoop, calls=.."
KIND = re.compile(r"kind=(k\w+)")
SHAPE = re.compile(r"=\s*\(*(\w+\[[\d,]*\])")


def load(path):
    """The device planes' op and module lines and the host plane's
    ``bench.*`` spans of one ``.xplane.pb`` (or ``.xplane.pb.gz``)."""
    import gzip
    from jax.profiler import ProfileData
    raw = pathlib.Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    lines = {"XLA Ops": "ops", "Async XLA Ops": "async",
             "XLA Modules": "modules"}
    devices, host = {}, []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith("/device:TPU:"):
            dev = devices.setdefault(
                plane.name, {"ops": [], "async": [], "modules": []})
            for line in plane.lines:
                if line.name in lines:
                    dev[lines[line.name]] += [
                        (_span if line.name == "XLA Modules" else _op)(e)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [_span(e) for e in line.events
                         if e.name.startswith("bench.")]
    host.sort(key=lambda s: s[1])
    return {"devices": devices, "host": host}


def _span(event):
    start = event.start_ns * 1e-9
    return (event.name, start, start + event.duration_ns * 1e-9)


def _op(event):
    """(name, kind, start, end). The kind is the fusion kind XLA gave the op
    (kLoop, kInput, kOutput, ..), else its opcode; the name is the HLO
    instruction's with kind and output shape, "fusion.7_kLoop_bf16_256_56_56_
    64", so that a breakdown's line says what the op is."""
    text, start, end = _span(event)
    ident = text.split(" = ")[0].lstrip("%")
    kind = KIND.search(text)
    kind = kind.group(1) if kind else re.sub(r"\.\d+$", "", ident)
    shape = SHAPE.search(text)
    label = "_".join([ident, kind] + ([shape.group(1)] if shape else [])) \
        if kind != ident else ident
    return (re.sub(r"[^\w.\-]+", "_", label).strip("_"), kind, start, end)


# ---------------------------------------------------------------------------
# interval arithmetic on lists of (start, end)

def union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(intervals, cover):
    """The parts of ``intervals`` (merged) that no interval of ``cover``
    (merged) overlaps."""
    out = []
    for s, e in intervals:
        for cs, ce in cover:
            if ce <= s:
                continue
            if cs >= e:
                break
            if cs > s:
                out.append((s, cs))
            s = max(s, ce)
        if e > s:
            out.append((s, e))
    return out


# ---------------------------------------------------------------------------

def window(host, skip=SKIP):
    """(start, end, steps) from the wait spans; None where there are too
    few."""
    ends = [e for name, _, e in host if name == WAIT][skip:-1]
    if len(ends) < 2:
        return None
    return ends[0], ends[-1], len(ends) - 1


def snap(modules, t):
    """The end of the last program that ended by ``t``; ``t`` where none
    did."""
    return max((e for _, _, e in modules if e <= t), default=t)


def reduce(trace, skip=SKIP):
    """One dict for the metric readers; None if the trace holds no whole
    window or no device op in it."""
    win = window(trace["host"], skip)
    if win is None or not trace["devices"]:
        return None
    n = len(trace["devices"])
    busy, launches, exposed, spans, gaps = [], [], [], [], []
    by_kind, by_name = defaultdict(float), defaultdict(float)
    for dev in trace["devices"].values():
        lo, hi = snap(dev["modules"], win[0]), snap(dev["modules"], win[1])
        steps = win[2]
        spans.append(hi - lo)
        ops = [(name, kind, max(s, lo), min(e, hi))
               for name, kind, s, e in dev["ops"] if min(e, hi) > max(s, lo)]
        for name, kind, s, e in ops:
            by_kind[kind] += (e - s) / n
            by_name[name] += (e - s) / n
        busy.append(total(union([(s, e) for _, _, s, e in ops])))
        launches.append(sum(1 for _, _, e in dev["modules"] if lo < e <= hi))
        coll = union([(max(s, lo), min(e, hi))
                      for name, _, s, e in ops + dev.get("async", [])
                      if COLLECTIVE.search(name) and min(e, hi) > max(s, lo)])
        rest = union([(s, e) for name, _, s, e in ops
                      if not COLLECTIVE.search(name)])
        exposed.append(total(subtract(coll, rest)))
        if not gaps:  # of the first device
            gaps = subtract([(lo, hi)], union([(s, e) for _, _, s, e in ops]))
    if not any(busy):
        return None
    return {
        "window_s": sum(spans) / n, "steps": steps, "devices": n,
        "busy_s": sum(busy) / n,
        "launches_per_step": sum(launches) / n / steps,
        "collective_exposed_s_per_step": statistics.median(exposed) / steps,
        "seconds_by_kind": dict(by_kind),
        "device_ops": _top(by_name.items()),
        "idle_gaps": _top((_doing(trace["host"], s, e), e - s)
                          for s, e in gaps),
    }


def _top(pairs, n=10):
    return [[name, seconds] for name, seconds in
            sorted(pairs, key=lambda p: -p[1])[:n]]


def _doing(host, s, e):
    """The host span that covers most of the gap (s, e); "host.between"
    where none does."""
    best, most = "host.between", 0.0
    for name, hs, he in host:
        overlap = min(e, he) - max(s, hs)
        if overlap > most:
            best, most = name, overlap
    return best
