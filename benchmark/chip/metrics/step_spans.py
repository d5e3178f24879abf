"""The program's spans of one step of the loop, for the readers of what a
replay's three children and ``FitLoop``'s own stretches cost the host: the
ring's spans (``program_spans.ring()``) on the thread that holds the roots
that close a step, grouped by the step number they carry. A root is an
``mx.fit.step`` that trained (``FitLoop``; an iteration that only replayed a
batch or met the iterator's end says ``trained: false``) or, where there is
none, an ``mx.trainer.step`` with no parent (the Gluon loop). Left out, as
``program_spans.steps`` leaves them out: the first ``trace_reduce.SKIP``
steps, while the pipeline refills after the profiler's start, and the last.
A program without the spans (the parent of the PR that added them) gives no
steps, or steps without the children, and the readers return nothing."""
import statistics
from collections import defaultdict

import program_spans
import trace_reduce

FIT, TRAINER = "mx.fit.step", "mx.trainer.step"
PREPARE, LAUNCH, FINISH = \
    "mx.cached_op.prepare", "mx.cached_op.launch", "mx.cached_op.finish"
FETCH = "mx.fit.fetch"


def steps(events=None, roots=(FIT, TRAINER), skip=trace_reduce.SKIP):
    """(the numbers of the whole steps kept, {number: that step's spans in
    the order they started}) of the thread that holds the roots, which are
    the spans named ``roots[0]``, or where there are none ``roots[1]``, and
    so on. The dict holds the steps left out too: the step after the last
    one kept is there for a reader that looks across a step's end."""
    events = program_spans.ring() if events is None else events
    for name in roots:
        closed = [e for e in events if e["name"] == name
                  and "parent" not in e["args"]
                  and e["args"].get("trained", True)]
        if closed:
            break
    else:
        return [], {}
    tid = statistics.mode(e["tid"] for e in closed)
    by_step = defaultdict(list)
    for e in sorted(events, key=lambda e: e["ts"]):
        if e["tid"] == tid:
            by_step[e["args"]["step"]].append(e)
    kept = sorted({e["args"]["step"] for e in closed
                   if e["tid"] == tid})[skip:-1]
    return kept, by_step


def sum_ms(name, events=None):
    """A step's sum of the durations of the spans called ``name``: median
    over the steps that have one; None where none has."""
    kept, by_step = steps(events)
    sums = []
    for n in kept:
        durations = [e["dur"] for e in by_step[n] if e["name"] == name]
        sums.append(sum(durations) / 1e3 if durations else None)
    return program_spans.median(sums)


def between_ms(events=None):
    """From the end of a ``FitLoop`` step's ``mx.fit.fetch`` to the start
    of the next step's first ``mx.cached_op.forward``: median over the
    steps; None where no step has both ends."""
    kept, by_step = steps(events, roots=(FIT,))
    gaps = []
    for n in kept:
        ends = [e["ts"] + e["dur"] for e in by_step[n]
                if e["name"] == FETCH]
        starts = [e["ts"] for e in by_step.get(n + 1, ())
                  if e["name"] == program_spans.FORWARD]
        if ends and starts:
            gaps.append((min(starts) - max(ends)) / 1e3)
    return program_spans.median(gaps)


def programs(events=None):
    """The sum of ``programs`` over the spans of a ``FitLoop`` step: median
    over the steps."""
    kept, by_step = steps(events, roots=(FIT,))
    return program_spans.median(
        sum(e["args"].get("programs", 0) for e in by_step[n]) for n in kept)
