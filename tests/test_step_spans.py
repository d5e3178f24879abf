"""Step-phase spans inside the Gluon step engine and ``SPMDTrainer``: what
the tracer records for one training step, that it records nothing while off,
and that it follows a JAX profiler session into the device trace.

Marker ``telemetry`` — tier-1-safe: CPU, in-process, tiny nets.
"""
import contextlib
import time

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, telemetry
from mxnet_tpu.parallel import SPMDTrainer
from mxnet_tpu.telemetry import step_breakdown, validate_chrome_trace, \
    chrome_trace_events
from mxnet_tpu.telemetry.tracer import tracer, xplane_name, _NOOP

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
    assert forward["cache"] == "hit" and forward["programs"] == 1
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
    assert nested == {"mx.cached_op.vjp": "mx.autograd.backward",
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


def _launches_inside(spans, name):
    """Names of the jitted calls the host made inside the one span called
    ``name``: what is launched, whatever the framework's counters say. The
    runtime writes each call as two nested ``PjitFunction(...)`` events, so
    only the outermost count."""
    (_, lo, hi), = [s for s in spans if s[0] == name]
    calls = sorted((s for s in spans if s[0].startswith("PjitFunction(")
                    and lo <= s[1] and s[2] <= hi),
                   key=lambda s: (s[1], -s[2]))
    outer, end = [], lo
    for call, start, stop in calls:
        if start >= end:
            outer.append(call[len("PjitFunction("):-1])
            end = stop
    return outer


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


# ---------------------------------------------------------------------------
# cost when off

@pytest.mark.heavy
def test_tracing_off_overhead_under_one_percent_of_a_gluon_step(loop):
    """The twin of test_telemetry's bound, on a hybridized Gluon step: what
    the closed ``span()`` sites of one step cost against the step. A step
    passes one site per eager operator and a dozen at the step engine's
    boundaries; count them with the tracer on, time that many closed sites
    with it off."""
    sites = len(_traced(loop.step))
    assert 10 <= sites <= 40
    tracer.clear()

    def per_iter(body, n, reps=5):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(n):
                body()
            best = min(best, (time.perf_counter() - t0) / n)
        return best

    def closed_site():
        with telemetry.span("mx.trainer.update", "step") as sp:
            sp.set(programs=1)

    loop.step()
    assert tracer.events() == []
    site_cost = per_iter(closed_site, 20000)
    step_cost = per_iter(loop.step, 20)
    assert sites * site_cost < 0.01 * step_cost, (
        f"{sites} closed span sites cost {sites * site_cost * 1e6:.1f}us = "
        f"{sites * site_cost / step_cost:.3%} of a "
        f"{step_cost * 1e6:.0f}us step")
