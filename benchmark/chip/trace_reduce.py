"""From a profiler trace to the numbers the layer metrics read.

``load(path)`` turns an ``.xplane.pb`` into plain data, ``reduce(trace)``
turns that into one dict. Everything below ``load`` works on plain tuples, so
tests/test_trace_reduce.py feeds it a hand-made event list.

A trace is ``{"devices": {name: {"ops": [...], "async": [...], "modules":
[...]}}, "host": [...]}``. An op is ``(name, kind, start_s, end_s, scope)``,
a module launch ``(name, start_s, end_s)``, a host span ``(name, start_s,
end_s)``, all on the trace's one clock. ``ops`` is the device's "XLA Ops"
line, one op at a time but for a ``while``, whose body's ops lie inside it;
``async`` its "Async XLA Ops" line, each event from an asynchronous op's
start to its done, running beside the others (copies, and across chips the
collectives). The host spans are the harness's ``bench.*`` and the program's
own ``mx.*`` (``mxnet_tpu.telemetry`` writes them into the session's host
plane).

An op's scope is what the program called that part of itself: the
``jax.named_scope`` names that start with ``mx.`` in the op's ``op_name``,
outermost first and joined by ``/`` (``mx.mamba2/mx.ssd``), read through
``jvp(..)``, ``transpose(..)`` and ``checkpoint`` wrappers; ``""`` where
there is none, ``NO_NAME`` where the op has no ``op_name`` at all. The device
writes every HLO instruction's ``op_name`` into the trace as the ``tf_op``
stat of the event's metadata, which ``jax.profiler.ProfileData`` does not
hand out, so ``op_names`` reads that one table from the file's bytes. XLA
gives a fusion the metadata of one instruction in it (the root's, as a
rule): a fusion whose parts come from two scopes goes whole to the scope
that its metadata names.

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
SCOPE = re.compile(r"mx\.[a-z0-9_]+(?:\.[a-z0-9_]+)*")
NO_NAME = "(no op_name)"


def load(path):
    """The device planes' op and module lines and the host plane's
    ``bench.*`` and ``mx.*`` spans of one ``.xplane.pb`` (or
    ``.xplane.pb.gz``)."""
    import gzip
    from jax.profiler import ProfileData
    raw = pathlib.Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    names = op_names(raw)
    lines = {"XLA Ops": "ops", "Async XLA Ops": "async"}
    devices, host = {}, []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith("/device:TPU:"):
            dev = devices.setdefault(
                plane.name, {"ops": [], "async": [], "modules": []})
            of = names.get(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] += [_span(e) for e in line.events]
                elif line.name in lines:
                    dev[lines[line.name]] += [
                        _op(e, of.get(e.name)) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [_span(e) for e in line.events
                         if e.name.startswith(("bench.", "mx."))]
    host.sort(key=lambda s: s[1])
    return {"devices": devices, "host": host}


def _span(event):
    start = event.start_ns * 1e-9
    return (event.name, start, start + event.duration_ns * 1e-9)


def _op(event, op_name=None):
    """(name, kind, start, end, scope). The kind is the fusion kind XLA gave
    the op (kLoop, kInput, kOutput, ..), else its opcode; the name is the HLO
    instruction's with kind and output shape, "fusion.7_kLoop_bf16_256_56_56_
    64", so that a breakdown's line says what the op is."""
    text, start, end = _span(event)
    ident = text.split(" = ")[0].lstrip("%")
    kind = KIND.search(text)
    kind = kind.group(1) if kind else re.sub(r"\.\d+$", "", ident)
    shape = SHAPE.search(text)
    label = "_".join([ident, kind] + ([shape.group(1)] if shape else [])) \
        if kind != ident else ident
    return (re.sub(r"[^\w.\-]+", "_", label).strip("_"), kind, start, end,
            scope_of(op_name))


def scope_of(op_name):
    """``mx.mamba2/mx.ssd`` of ``jit(step)/transpose(jvp(mx.mamba2))/mx.ssd/
    mul``. A scope entered again inside itself (a recomputed layer's) counts
    once, and of the names a merged instruction carries (``a;b``) the first
    is read."""
    if op_name is None:
        return NO_NAME
    path = []
    for scope in SCOPE.findall(op_name.split(";")[0]):
        path = path[:path.index(scope)] if scope in path else path
        path.append(scope)
    return "/".join(path)


# ---------------------------------------------------------------------------
# the one table of an .xplane.pb that ProfileData keeps to itself

def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf):
    """(field number, value) over one protobuf message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            size, i = _varint(buf, i)
            i += size
            yield key >> 3, buf[i - size:i]
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")


def op_names(raw):
    """{device plane: {event name: op_name}} from the bytes of an XSpace:
    ``XSpace.planes = 1``; ``XPlane.name = 2``, ``.event_metadata = 4`` and
    ``.stat_metadata = 5`` (maps: key 1, value 2); ``XEventMetadata.name =
    2``, ``.stats = 5``; ``XStat.metadata_id = 1``, ``.str_value = 5``;
    ``XStatMetadata.name = 2`` (tsl/profiler/protobuf/xplane.proto). The
    ``tf_op`` stat is the HLO instruction's ``op_name`` with a colon and the
    framework's op type, which jax leaves empty, after it. Two programs' ops
    of one HLO text share a name here, and the later one's ``op_name``."""
    out = {}
    for field, plane in _fields(memoryview(raw)):
        if field != 1:
            continue
        name, events, tf_op = "", [], None
        for field, value in _fields(plane):
            if field == 2:
                name = bytes(value).decode()
            elif field == 4 and name.startswith("/device:TPU:"):
                events.append(dict(_fields(value))[2])
            elif field == 5 and name.startswith("/device:TPU:"):
                entry = dict(_fields(value))
                if bytes(dict(_fields(entry[2])).get(2, b"")) == b"tf_op":
                    tf_op = entry[1]
        if tf_op is None:
            continue
        table = out[name] = {}
        for event in events:
            text, op_name = None, None
            for field, value in _fields(event):
                if field == 2:
                    text = bytes(value).decode()
                elif field == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) == tf_op and 5 in stat:
                        op_name = bytes(stat[5]).decode().rsplit(":", 1)[0]
            if text is not None and op_name is not None:
                table[text] = op_name
    return out


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


def self_seconds(ops):
    """For each op of one line, the seconds in which it is the innermost op
    running: of the ops open at an instant, the one that started last (a
    ``while`` holds its body's ops). Every busy instant goes to one op, so
    the self times add up to the line's busy time."""
    own = [0.0] * len(ops)
    open_, now = [], 0.0

    def advance(to):
        nonlocal now
        while open_:
            end = ops[open_[-1]][3]
            if end <= now:
                open_.pop()
            elif now >= to:
                return
            else:
                own[open_[-1]] += min(to, end) - now
                now = min(to, end)
        now = to

    for i in sorted(range(len(ops)), key=lambda i: (ops[i][2], -ops[i][3])):
        advance(ops[i][2])
        open_.append(i)
    advance(float("inf"))
    return own


def reduce(trace, skip=SKIP):
    """One dict for the metric readers; None if the trace holds no whole
    window or no device op in it."""
    win = window(trace["host"], skip)
    if win is None or not trace["devices"]:
        return None
    n = len(trace["devices"])
    busy, launches, exposed, spans, gaps = [], [], [], [], []
    by_kind, by_name = defaultdict(float), defaultdict(float)
    by_scope, by_span = defaultdict(float), defaultdict(float)
    for dev in trace["devices"].values():
        lo, hi = snap(dev["modules"], win[0]), snap(dev["modules"], win[1])
        steps = win[2]
        spans.append(hi - lo)
        ops = [(name, kind, max(s, lo), min(e, hi), scope)
               for name, kind, s, e, scope in dev["ops"]
               if min(e, hi) > max(s, lo)]
        for (name, kind, s, e, scope), own in zip(ops, self_seconds(ops)):
            by_kind[kind] += (e - s) / n
            by_name[_labelled(scope, name)] += (e - s) / n
            by_scope[scope] += own / n
        busy.append(total(union([(s, e) for _, _, s, e, _ in ops])))
        launches.append(sum(1 for _, _, e in dev["modules"] if lo < e <= hi))
        coll = union([(max(s, lo), min(e, hi))
                      for name, _, s, e, _ in ops + dev.get("async", [])
                      if COLLECTIVE.search(name) and min(e, hi) > max(s, lo)])
        rest = union([(s, e) for name, _, s, e, _ in ops
                      if not COLLECTIVE.search(name)])
        exposed.append(total(subtract(coll, rest)))
        if not gaps:  # of the first device
            gaps = [(_doing(trace["host"], s, e), e - s) for s, e in subtract(
                [(lo, hi)], union([(s, e) for _, _, s, e, _ in ops]))]
            for name, seconds in gaps:
                by_span[name] += seconds
    if not any(busy):
        return None
    return {
        "window_s": sum(spans) / n, "steps": steps, "devices": n,
        "busy_s": sum(busy) / n,
        "launches_per_step": sum(launches) / n / steps,
        "collective_exposed_s_per_step": statistics.median(exposed) / steps,
        "seconds_by_kind": dict(by_kind),
        "seconds_by_scope": dict(by_scope),
        "idle_seconds_by_span": dict(by_span),
        "device_ops": _top(by_name.items()),
        "idle_gaps": _top(gaps),
    }


def _labelled(scope, name):
    """``mx.ssd:fusion.7_kLoop_..``: the op's name behind its innermost
    scope, for a breakdown's line."""
    inner = scope.rsplit("/", 1)[-1]
    return f"{inner}:{name}" if inner and inner != NO_NAME else name


def _top(pairs, n=10):
    return [[name, seconds] for name, seconds in
            sorted(pairs, key=lambda p: -p[1])[:n]]


def _doing(host, s, e):
    """What the host was doing in the gap (s, e): the name of the shortest
    span that covers more than half of it (spans of one thread nest, so that
    is the innermost: an ``mx.`` span of the program inside the harness's
    ``bench.`` one). Where none covers half, the one that covers most;
    "host.between" where none touches it."""
    best, key = "host.between", None
    for name, hs, he in host:
        overlap = min(e, he) - max(s, hs)
        if overlap <= 0:
            continue
        k = (1, hs - he) if 2 * overlap > e - s else (0, overlap)
        if key is None or k > key:
            best, key = name, k
    return best
