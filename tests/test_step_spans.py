"""Step-phase spans inside the Gluon step engine, ``SPMDTrainer`` and
``FitLoop``: what the tracer records for one training step, where a step
ends, that every launch has a span that owns it, that the tracer records
nothing and reads no clock while off, and that it follows a JAX profiler
session into the device trace.

Marker ``telemetry`` — tier-1-safe: CPU, in-process, tiny nets.
"""
import contextlib
import importlib
import time

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, telemetry
from mxnet_tpu import random as mx_random
from mxnet_tpu.fit import FitLoop
from mxnet_tpu.parallel import SPMDTrainer
from mxnet_tpu.telemetry import step_breakdown, validate_chrome_trace, \
    chrome_trace_events
from mxnet_tpu.telemetry.tracer import tracer, xplane_name, _NOOP

# the module: the package's ``tracer`` attribute is the Tracer it made
tracer_module = importlib.import_module("mxnet_tpu.telemetry.tracer")

pytestmark = pytest.mark.telemetry

BATCH = 4


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracer.disable()
    tracer.clear()
    yield
    tracer.disable()
    tracer.clear()


def _net():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(3))
    net.initialize(mx.init.Xavier())
    net(nd.zeros((1, 5)))
    return net


class Gluon:
    """The loop of benchmark/chip/paths/gluon.py on a two-layer net."""

    def __init__(self):
        self.net = _net()
        self.net.hybridize()
        self.trainer = gluon.Trainer(self.net.collect_params(), "sgd",
                                     {"learning_rate": 0.1, "momentum": 0.9})
        self.loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        rng = np.random.RandomState(0)
        self.data = nd.array(rng.rand(BATCH, 5).astype("float32"))
        self.label = nd.array(rng.randint(0, 3, BATCH).astype("float32"))
        self.step()  # trace and compile outside every test's window

    def step(self):
        with autograd.record():
            loss = self.loss_fn(self.net(self.data), self.label)
        loss.backward()
        self.trainer.step(BATCH)
        return loss.mean().asnumpy()


@pytest.fixture(scope="module")
def loop():
    return Gluon()


@pytest.fixture(scope="module")
def spmd():
    trainer = SPMDTrainer(_net(), gluon.loss.SoftmaxCrossEntropyLoss(),
                          optimizer="sgd",
                          optimizer_params={"learning_rate": 0.1})
    rng = np.random.RandomState(1)
    batch = (rng.rand(BATCH, 5).astype("float32"),
             rng.randint(0, 3, BATCH).astype("float32"))
    trainer.step(*batch)
    return trainer, batch


class Fit:
    """``FitLoop`` round the Gluon loop's net and trainer, three batches an
    epoch: ``run()`` is one ``fit()`` of three steps."""

    def __init__(self, loop, trainer=None, data=None):
        rng = np.random.RandomState(3)
        data = rng.rand(3 * BATCH, 5).astype("float32") \
            if data is None else data
        label = rng.randint(0, 3, 3 * BATCH).astype("float32")
        self.loop = FitLoop(
            loop.net, trainer or loop.trainer, loop.loss_fn,
            mx.io.NDArrayIter(data, label, batch_size=BATCH),
            ckpt_dir=None, heartbeat=False, scale_growth_interval=0)
        self.run()  # the sentinel's programs compile outside every window

    def run(self):
        return self.loop.fit(1, batch_size=BATCH)


@pytest.fixture(scope="module")
def fit(loop):
    return Fit(loop)


def _traced(fn, times=1):
    """The spans of category ``step`` and ``operator`` that ``fn`` leaves."""
    tracer.clear()
    tracer.enable()
    try:
        for _ in range(times):
            fn()
    finally:
        tracer.disable()
    return [e for e in tracer.events() if e["cat"] in ("step", "operator")]


def _named(events, name):
    return [e for e in events if e["name"] == name]


# ---------------------------------------------------------------------------
# off

def test_off_gluon_and_spmd_steps_leave_the_ring_empty(loop, spmd):
    assert not tracer.enabled
    loop.step()
    spmd[0].step(*spmd[1]).block_until_ready()
    assert tracer.events() == []


def _infer(loop):
    return loop.net(loop.data).asnumpy()


@pytest.mark.parametrize("what", ["gluon_step", "fitloop_steps",
                                  "inference_replay"])
def test_off_builds_no_span_and_reads_no_clock(what, loop, fit, monkeypatch):
    """The off path by counting, not by timing: with tracing off a Gluon
    step, ``FitLoop``'s steps and an inference replay pass every ``span()``
    site they have, and the tracer constructs no span, appends nothing to
    its ring and reads no clock."""
    run = {"gluon_step": loop.step, "fitloop_steps": fit.run,
           "inference_replay": lambda: _infer(loop)}[what]
    run()
    built, sites = [], []

    class Counted(tracer_module._Span):
        def __init__(self, *a):
            built.append(a[1])
            super().__init__(*a)

    class Clock:
        @staticmethod
        def perf_counter():
            raise AssertionError("the tracer read the clock while off")

        time = staticmethod(time.time)

    real_span = tracer_module.Tracer.span

    def counting_span(self, name, category, args=None):
        sites.append(name)
        return real_span(self, name, category, args)

    monkeypatch.setattr(tracer_module, "_Span", Counted)
    monkeypatch.setattr(tracer_module, "time", Clock)
    monkeypatch.setattr(tracer_module.Tracer, "span", counting_span)
    assert not tracer.enabled
    before = tracer._thread.step
    run()
    assert built == [] and tracer.events() == []
    # the sites were passed: the replay's and, in FitLoop, the step's own
    assert {"mx.cached_op.forward", "mx.cached_op.prepare",
            "mx.cached_op.launch", "mx.cached_op.finish"} <= set(sites)
    if what == "fitloop_steps":
        assert sites.count("mx.fit.step") == 4  # three steps and the end
        assert sites.count("mx.fit.fetch") == sites.count("mx.fit.close") == 3
        assert tracer._thread.step == before + 3
    if what == "gluon_step":
        assert tracer._thread.step == before + 1


def test_off_span_is_the_shared_noop():
    assert telemetry.span("mx.trainer.step", "step") is _NOOP
    assert telemetry.span("dot", "operator", {"programs": 1}) is _NOOP
    with telemetry.span("mx.trainer.update", "step") as sp:
        sp.set(programs=3)  # accepted and dropped
    assert tracer.events() == []


def test_segment_without_listener_reads_no_clock(monkeypatch):
    def no_clock():
        raise AssertionError("clock read with nothing listening")
    monkeypatch.setattr(time, "perf_counter", no_clock)
    assert step_breakdown.current_breakdown() is None
    with step_breakdown.segment("comm"):
        pass
    with telemetry.span("mx.spmd.step", "step"):
        pass


# ---------------------------------------------------------------------------
# on: one Gluon step

def test_gluon_step_has_one_root_of_each_phase_and_one_step_number(loop):
    events = _traced(loop.step)
    phases = [e for e in events if e["cat"] == "step"]
    roots = [e for e in phases if "parent" not in e["args"]]
    assert sorted(e["name"] for e in roots) == [
        "mx.autograd.backward", "mx.cached_op.forward", "mx.trainer.step"]
    assert len({e["args"]["step"] for e in phases}) == 1
    forward = _named(roots, "mx.cached_op.forward")[0]["args"]
    # the replay's children own what it launches
    assert forward["cache"] == "hit" and forward["programs"] == 0
    assert _named(events, "mx.cached_op.launch")[0]["args"]["programs"] == 1
    assert forward["block"] == "HybridSequential"
    assert _named(roots, "mx.trainer.step")[0]["args"]["params"] == 4
    # at most a dozen step spans a step, whatever the parameter count
    assert len(phases) <= 12


def test_parents_are_recorded_spans_of_the_same_thread(loop):
    events = _traced(loop.step)
    by_id = {e["args"]["id"]: e for e in events}
    assert len(by_id) == len(events)
    children = [e for e in events if "parent" in e["args"]]
    assert {"mx.cached_op.vjp", "mx.autograd.deliver", "mx.trainer.update",
            "mx.trainer.allreduce"} <= {e["name"] for e in children}
    for e in children:
        parent = by_id[e["args"]["parent"]]
        assert parent["tid"] == e["tid"]
        # children lie inside their parents
        assert parent["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1e-3
    nested = {e["name"]: by_id[e["args"]["parent"]]["name"] for e in children
              if e["cat"] == "step"}
    assert nested == {"mx.cached_op.prepare": "mx.cached_op.forward",
                      "mx.cached_op.launch": "mx.cached_op.forward",
                      "mx.cached_op.finish": "mx.cached_op.forward",
                      "mx.cached_op.vjp": "mx.autograd.backward",
                      "mx.autograd.deliver": "mx.autograd.backward",
                      "mx.trainer.allreduce": "mx.trainer.step",
                      "mx.trainer.update": "mx.trainer.step"}


def test_self_times_are_non_negative(loop):
    events = _traced(loop.step)
    covered = {}
    for e in events:
        if "parent" in e["args"]:
            covered[e["args"]["parent"]] = \
                covered.get(e["args"]["parent"], 0.0) + e["dur"]
    for e in events:
        assert e["dur"] - covered.get(e["args"]["id"], 0.0) >= -1e-3, e


def test_update_programs_equal_last_update_dispatches(loop):
    events = _traced(loop.step)
    update = _named(events, "mx.trainer.update")[0]["args"]
    assert loop.trainer.last_update_dispatches >= 1
    assert update["programs"] == loop.trainer.last_update_dispatches
    assert update["buckets"] == update["programs"]  # all four are grouped
    allreduce = _named(events, "mx.trainer.allreduce")[0]["args"]
    assert allreduce["collectives"] == loop.trainer.last_allreduce_collectives


def test_update_span_reports_one_bucket_by_default(loop, monkeypatch):
    """With ``MXTPU_OPTIMIZER_AGGREGATION`` unset the loop's four parameters
    (one dtype, one device, one state arity) are one bucket key and so one
    program, and the span says what the trainer counted."""
    monkeypatch.delenv("MXTPU_OPTIMIZER_AGGREGATION", raising=False)
    events = _traced(loop.step)
    update = _named(events, "mx.trainer.update")[0]["args"]
    assert update["buckets"] == 1
    assert update["programs"] == loop.trainer.last_update_dispatches == 1


def test_backward_counts_nodes_grads_and_its_own_launches(loop):
    events = _traced(loop.step)
    backward = _named(events, "mx.autograd.backward")[0]["args"]
    # the hybridized net is one tape node, the eager loss adds its own
    assert backward["nodes"] >= 2
    # a vjp program per eager node, and the head gradient
    assert backward["programs"] >= backward["nodes"] - 1
    deliver = _named(events, "mx.autograd.deliver")[0]["args"]
    assert deliver == dict(deliver, grads=4, programs=0)
    assert _named(events, "mx.cached_op.vjp")[0]["args"]["programs"] == 1


def test_eager_operator_spans_own_one_program_each(loop):
    events = _traced(loop.step)
    ops = [e for e in events if e["cat"] == "operator"]
    assert ops and all(e["args"]["programs"] == 1 for e in ops)
    assert all("step" in e["args"] and "id" in e["args"] for e in ops)


def test_two_steps_carry_consecutive_step_numbers(loop):
    events = _traced(loop.step, times=2)
    steps = [e["args"]["step"] for e in _named(events, "mx.trainer.step")]
    assert len(steps) == 2 and steps[1] == steps[0] + 1
    forwards = [e["args"]["step"]
                for e in _named(events, "mx.cached_op.forward")]
    assert forwards == steps


def test_update_alone_ends_a_step(loop):
    def step():
        with autograd.record():
            loss = loop.loss_fn(loop.net(loop.data), loop.label)
        loss.backward()
        loop.trainer.allreduce_grads()
        loop.trainer.update(BATCH)
    events = _traced(step, times=2)
    updates = _named(events, "mx.trainer.update")
    assert [u["args"]["step"] for u in updates] == [
        updates[0]["args"]["step"], updates[0]["args"]["step"] + 1]
    assert all("parent" not in u["args"] for u in updates)


def test_spmd_step_has_its_three_children(spmd):
    trainer, batch = spmd
    events = _traced(lambda: trainer.step(*batch).block_until_ready(),
                     times=2)
    steps = _named(events, "mx.spmd.step")
    assert len(steps) == 2
    assert steps[1]["args"]["step"] == steps[0]["args"]["step"] + 1
    for root in steps:
        kids = [e for e in events
                if e["args"].get("parent") == root["args"]["id"]]
        assert [e["name"] for e in sorted(kids, key=lambda e: e["ts"])] == [
            "mx.spmd.prepare", "mx.spmd.launch", "mx.spmd.finish"]
        assert sum(e["args"]["programs"] for e in kids + [root]) == 2


def test_spmd_run_steps_is_one_step_span(spmd):
    trainer, (data, label) = spmd
    events = _traced(lambda: trainer.run_steps(
        np.stack([data, data]), np.stack([label, label])).block_until_ready())
    assert len(_named(events, "mx.spmd.step")) == 1
    assert len(_named(events, "mx.spmd.launch")) == 1


# ---------------------------------------------------------------------------
# on: a hybridized block's replay

def _children(events, parent):
    return sorted((e for e in events
                   if e["args"].get("parent") == parent["args"]["id"]),
                  key=lambda e: e["ts"])


@pytest.mark.parametrize("recorded", [True, False])
def test_replay_has_three_children_that_tile_it(recorded, loop):
    events = _traced(loop.step if recorded else lambda: _infer(loop))
    forward, = _named(events, "mx.cached_op.forward")
    kids = _children(events, forward)
    assert [e["name"] for e in kids] == [
        "mx.cached_op.prepare", "mx.cached_op.launch", "mx.cached_op.finish"]
    assert [e["args"]["programs"] for e in kids] == [
        mx_random.NEXT_KEY_PROGRAMS, 1, 0]
    assert forward["args"]["programs"] == 0
    assert ("residual_bytes" in forward["args"]) == recorded
    # one after the other, inside the parent, and nothing of the replay
    # outside them: what is left to the parent is the three spans' own
    # bookkeeping (under a tenth of it, or a fifth of a millisecond on a
    # loaded machine)
    end = forward["ts"]
    for e in kids:
        assert end <= e["ts"]
        end = e["ts"] + e["dur"]
    assert end <= forward["ts"] + forward["dur"] + 1e-3
    own = forward["dur"] - sum(e["dur"] for e in kids)
    assert own <= max(0.1 * forward["dur"], 200.0), (own, forward["dur"])


def test_a_replay_that_allocates_its_residual_set_owns_that_launch(loop):
    """A second recorded call in one scope finds no residual set to recycle:
    ``_take_arena`` runs its allocation, and the launch span says two."""
    def twice():
        with autograd.record():
            loop.net(loop.data)
            loop.net(loop.data)
    loop.step()  # its backward hands one residual set back
    events = _traced(twice)
    forwards = _named(events, "mx.cached_op.forward")
    assert [f["args"]["recycled"] for f in forwards] == [True, False]
    assert [e["args"]["programs"]
            for e in _named(events, "mx.cached_op.launch")] == [1, 2]


# ---------------------------------------------------------------------------
# on: every launch has a span that owns it

def _vector_head(loop):
    with autograd.record():
        loss = loop.loss_fn(loop.net(loop.data), loop.label)
    loss.backward()
    loop.trainer.step(BATCH)


def _scalar_head(loop):
    with autograd.record():
        loss = loop.loss_fn(loop.net(loop.data), loop.label).mean()
    loss.backward()
    loop.trainer.step(BATCH)


@pytest.mark.parametrize("what", ["gluon_step", "gluon_step_scalar_head",
                                  "inference_replay", "fitloop_steps"])
def test_programs_of_the_spans_equal_the_launches_counted(
        what, loop, fit, tmp_path):
    """The sum of ``programs`` over the spans against the jitted calls the
    host made, counted from the profiler session's host plane. Left out on
    both sides: what ``mx.trainer.allreduce`` launches where the trainer has
    a store (the tests' eight devices; no cell of the benchmark): its span
    counts ``collectives``, and the bucket's flatten and split and whatever
    the store launches have no ``programs`` yet."""
    run = {"gluon_step": lambda: _vector_head(loop),
           "gluon_step_scalar_head": lambda: _scalar_head(loop),
           "inference_replay": lambda: loop.net(loop.data),
           "fitloop_steps": fit.run}[what]
    run()
    tracer.clear()
    with _profiler_session(tmp_path):
        with jax.profiler.TraceAnnotation("test.window"):
            run()
    spans = _host_spans(tmp_path)
    launches = _launches_inside(spans, "test.window")
    in_allreduce = [call for name, lo, hi in spans
                    if name == "mx.trainer.allreduce"
                    for call in _launches_between(spans, lo, hi)]
    owned = sum(e["args"].get("programs", 0) for e in tracer.events()
                if e.get("ph", "X") == "X")
    assert owned == len(launches) - len(in_allreduce), launches
    assert owned >= {"inference_replay": 5, "fitloop_steps": 3 * 17}.get(
        what, 17)
    if what == "fitloop_steps":
        assert owned % 3 == 0  # each of the three steps launches the same


def test_next_key_launches_what_its_constant_says(tmp_path):
    mx_random.next_key()
    with _profiler_session(tmp_path):
        with jax.profiler.TraceAnnotation("test.window"):
            mx_random.next_key()
    launches = _launches_inside(_host_spans(tmp_path), "test.window")
    assert len(launches) == mx_random.NEXT_KEY_PROGRAMS == 4, launches
    assert sorted(launches) == ["_threefry_fold_in", "_threefry_fold_in",
                                "convert_element_type",
                                "convert_element_type"]


# ---------------------------------------------------------------------------
# on: where a step ends

def test_end_step_inside_a_span_waits_for_the_outermost_exit():
    tracer.enable()
    start = tracer._thread.step
    with telemetry.span("user.step", "step"):
        with telemetry.span("user.update", "step"):
            telemetry.end_step()
        telemetry.end_step()  # a second call in one step ends it once
        with telemetry.span("user.after", "step"):
            pass
        assert tracer._thread.step == start
    assert tracer._thread.step == start + 1
    with telemetry.span("user.step", "step"):
        pass
    tracer.disable()
    spans = [e for e in tracer.events() if e["name"].startswith("user.")]
    assert [e["args"]["step"] for e in spans] == [start] * 3 + [start + 1]


def test_end_step_with_no_span_open_advances_at_once():
    for on in (False, True):
        tracer.enable() if on else tracer.disable()
        start = tracer._thread.step
        telemetry.end_step()
        assert tracer._thread.step == start + 1
    # a span of a category that is filtered out is not open
    tracer.set_categories({"comm"})
    try:
        with telemetry.span("user.step", "step") as sp:
            assert sp is _NOOP
            telemetry.end_step()
            assert tracer._thread.step == start + 2
    finally:
        tracer.set_categories(None)


# ---------------------------------------------------------------------------
# on: FitLoop

def _fit_roots(events):
    return [e for e in _named(events, "mx.fit.step")
            if e["args"].get("trained", True)]


def _step_events(fn):
    """Every span ``fn`` leaves, the segments too."""
    _traced(fn)
    return [e for e in tracer.events() if e.get("ph", "X") == "X"]


def test_fitloop_has_one_root_a_step_with_consecutive_numbers(fit):
    events = _step_events(fit.run)
    roots = _fit_roots(events)
    assert len(roots) == 3
    assert all("parent" not in r["args"] for r in roots)
    first = roots[0]["args"]["step"]
    assert [r["args"]["step"] for r in roots] == [first, first + 1, first + 2]
    assert all(r["args"] == dict(r["args"], programs=0, finite=True)
               for r in roots)
    # the iteration that met the iterator's end trained nothing, says so,
    # and ends no step
    ended, = [e for e in _named(events, "mx.fit.step") if e not in roots]
    assert ended["args"]["trained"] is False
    assert ended["args"]["step"] == first + 3


def test_every_span_of_a_fitloop_iteration_carries_its_roots_number(fit):
    events = _step_events(fit.run)
    by_id = {e["args"]["id"]: e for e in events}
    roots = _fit_roots(events)

    def root_of(e):
        while "parent" in e["args"]:
            e = by_id[e["args"]["parent"]]
        return e

    for root in roots:
        mine = [e for e in events if root_of(e) is root]
        names = {e["name"] for e in mine}
        assert {"mx.fit.step", "data_wait", "compute", "comm", "optimizer",
                "mx.cached_op.forward", "mx.cached_op.prepare",
                "mx.cached_op.launch", "mx.cached_op.finish",
                "mx.autograd.backward", "mx.cached_op.vjp",
                "mx.trainer.allreduce", "mx.trainer.update",
                "mx.fit.fetch", "mx.fit.close"} <= names
        assert {e["args"]["step"] for e in mine} == {root["args"]["step"]}
    # and no span of the thread is outside the four roots
    assert all(root_of(e)["name"] == "mx.fit.step" for e in events)


def test_fitloop_names_the_fetch_and_the_close(fit):
    events = _step_events(fit.run)
    by_id = {e["args"]["id"]: e for e in events}
    fetches = _named(events, "mx.fit.fetch")
    closes = _named(events, "mx.fit.close")
    assert len(fetches) == len(closes) == 3
    for fetch, close in zip(fetches, closes):
        assert fetch["args"] == dict(fetch["args"], programs=0, blocking=True)
        parent = by_id[fetch["args"]["parent"]]
        assert (parent["name"], parent["cat"]) == ("compute", "compute")
        assert by_id[close["args"]["parent"]]["name"] == "mx.fit.step"
        assert close["args"]["programs"] == 0
        # the update is over before the fetch, the fetch before the close
        assert fetch["ts"] + fetch["dur"] <= close["ts"]
    # the segments keep their category, and are the root's children
    for name in ("data_wait", "compute", "comm", "optimizer"):
        for e in _named(events, name):
            assert e["cat"] == name
            assert by_id[e["args"]["parent"]]["name"] == "mx.fit.step"


def test_fitloop_ends_the_step_its_trainer_did_not(loop):
    """The per-parameter path (a trainer without the fused sentinel) skips
    a non-finite step's update, so no trainer call ends that step: the loop
    does, and every ``mx.fit.step`` still has a number of its own."""
    class PerParameter:
        def __init__(self, trainer):
            self._trainer = trainer

        def __getattr__(self, name):
            if name == "update_with_sentinel":
                raise AttributeError(name)
            return getattr(self._trainer, name)

    data = np.random.RandomState(4).rand(3 * BATCH, 5).astype("float32")
    data[BATCH:2 * BATCH] = np.nan  # the second batch of three
    fit = Fit(loop, trainer=PerParameter(loop.trainer), data=data)
    done = []
    events = _step_events(lambda: done.append(fit.run()))
    assert done[0].skipped_steps == [1]
    roots = _fit_roots(events)
    first = roots[0]["args"]["step"]
    assert [r["args"]["step"] for r in roots] == [first, first + 1, first + 2]
    assert [r["args"]["finite"] for r in roots] == [True, False, True]
    assert [e["args"]["step"] for e in _named(events, "mx.trainer.update")] \
        == [first, first + 2]


def test_exported_chrome_trace_of_a_fitloop_run_validates(fit):
    tracer.enable()
    fit.run()
    tracer.disable()
    events = chrome_trace_events()
    validate_chrome_trace({"traceEvents": events})
    names = {e.get("name") for e in events}
    assert {"mx.fit.step", "mx.fit.fetch", "mx.fit.close", "compute",
            "mx.cached_op.launch"} <= names
    assert any(str(n).startswith("step:") for n in names)


def test_exported_chrome_trace_still_validates(loop, spmd):
    tracer.enable()
    loop.step()
    spmd[0].step(*spmd[1]).block_until_ready()
    tracer.disable()
    events = chrome_trace_events()
    validate_chrome_trace({"traceEvents": events})
    assert any(e.get("name") == "mx.trainer.step" for e in events)


def test_xplane_names():
    assert xplane_name("mx.trainer.step", "step") == "mx.trainer.step"
    assert xplane_name("Convolution", "operator") == "mx.op.Convolution"
    assert xplane_name("xla_cache_hit", "compile") == "mx.compile"
    assert xplane_name("kv_push:3", "comm") == "mx.comm.kv_push:3"


# ---------------------------------------------------------------------------
# following a profiler session

@contextlib.contextmanager
def _profiler_session(logdir):
    """A JAX profiler session that writes its trace under ``logdir``, with
    the Python tracer off (the host plane then holds TraceMe events only)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _host_spans(logdir):
    from jax.profiler import ProfileData
    files = sorted(logdir.rglob("*.xplane.pb"))
    assert files, "the profiler wrote no trace"
    spans = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events]
    return spans


def _launches_between(spans, lo, hi):
    """Names of the jitted calls the host made between ``lo`` and ``hi``:
    what is launched, whatever the framework's counters say. The runtime
    writes each call as two nested ``PjitFunction(...)`` events, so only the
    outermost count."""
    calls = sorted((s for s in spans if s[0].startswith("PjitFunction(")
                    and lo <= s[1] and s[2] <= hi),
                   key=lambda s: (s[1], -s[2]))
    outer, end = [], lo
    for call, start, stop in calls:
        if start >= end:
            outer.append(call[len("PjitFunction("):-1])
            end = stop
    return outer


def _launches_inside(spans, name):
    """``_launches_between`` the ends of the one span called ``name``."""
    (_, lo, hi), = [s for s in spans if s[0] == name]
    return _launches_between(spans, lo, hi)


def test_tracer_follows_a_profiler_session_into_the_xplane(
        loop, tmp_path, monkeypatch):
    monkeypatch.delenv("MXTPU_PROFILE", raising=False)
    loop.step()
    assert tracer.events() == [] and not tracer.enabled
    with _profiler_session(tmp_path):
        assert tracer.enabled
        with jax.profiler.TraceAnnotation("test.dispatch"):
            loop.step()
    assert not tracer.enabled, "the tracer is what it was before the session"
    recorded = tracer.events()
    assert len(_named(recorded, "mx.trainer.step")) == 1
    loop.step()
    assert len(tracer.events()) == len(recorded), \
        "the ring fills only between start and stop"

    spans = _host_spans(tmp_path)
    outer = [s for s in spans if s[0] == "test.dispatch"]
    assert len(outer) == 1
    _, lo, hi = outer[0]
    ours = {name: (s, e) for name, s, e in spans if name.startswith("mx.")}
    # the program's names, with nothing added; the eager ops as mx.op.*
    assert {"mx.cached_op.forward", "mx.autograd.backward",
            "mx.cached_op.vjp", "mx.autograd.deliver", "mx.trainer.step",
            "mx.trainer.allreduce", "mx.trainer.update"} <= set(ours)
    assert any(name.startswith("mx.op.") for name in ours)
    # on one clock: inside the annotation the test opened, and nested
    for s, e in ours.values():
        assert lo <= s and e <= hi
    step, update = ours["mx.trainer.step"], ours["mx.trainer.update"]
    assert step[0] <= update[0] and update[1] <= step[1]


# ---------------------------------------------------------------------------
# what the programs hold

def test_step_programs_hold_no_opt_barrier():
    """The convolutional cells live on XLA fusing BatchNorm and ReLU into the
    convolutions round them, so no program the operator layer traces may fence
    an op's outputs: not ``SPMDTrainer``'s one-program step, not a hybridized
    block's forward, not its VJP. Read from the lowered text, where a barrier
    still stands (the CPU compiler drops it from the compiled one)."""
    # in two halves: the word itself appears nowhere under mxnet_tpu/ or
    # tests/, so that a grep for it finds any use that comes back
    barrier = "optimization" "_barrier"
    fenced = jax.jit(lambda a: getattr(jax.lax, barrier)(a * 2) + 1)
    assert barrier in fenced.lower(
        jax.ShapeDtypeStruct((4,), np.float32)).as_text()

    def conv_net():
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Conv2D(4, 3, padding=1), gluon.nn.BatchNorm(),
                gluon.nn.Activation("relu"), gluon.nn.GlobalAvgPool2D(),
                gluon.nn.Flatten(), gluon.nn.Dense(3))
        net.initialize(mx.init.Xavier())
        net(nd.zeros((1, 2, 6, 6)))
        net.hybridize()
        return net

    rng = np.random.RandomState(2)
    data = rng.rand(BATCH, 2, 6, 6).astype("float32")
    label = rng.randint(0, 3, BATCH).astype("float32")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    trainer = SPMDTrainer(conv_net(), loss_fn, optimizer="sgd",
                          optimizer_params={"learning_rate": 0.1})
    trainer.step(data, label)
    step_fn, step_args = trainer._last_program
    texts = {"spmd step": step_fn.lower(*step_args).as_text()}

    net = conv_net()
    x = nd.array(data)
    with autograd.record():
        out = net(x)
        loss = loss_fn(out, nd.array(label))
    loss.backward()
    op = net._cached_op
    (key_sig, entry), = op._cache.snapshot_items()
    texts["forward"] = entry.jitted.lower(
        *op._abstract_args(key_sig, entry)).as_text()
    texts["vjp"] = entry.vjp_jitted.lower(*entry.vjp_abstract).as_text()
    for name, text in texts.items():
        assert "convolution" in text, name
        assert barrier not in text, name
