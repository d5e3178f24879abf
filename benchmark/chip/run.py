#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of its standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (with
``--trace 1`` also ``breakdown``), and last in it, as on the last lines of
standard error, ``compared``: each number ``correct`` rests on beside its
limit. Every name in ``BENCHMARK.json`` resolves to a file beside this one,
and nothing else is imported by hand:

    configs/<config>.json   sizes of the model, source, what was assumed
    models/<config>.py      plain reference: loss(), flops_per_sample()
    traffic/<traffic>.json  path, batch, chips, mesh, dtype, optimizer, pool
    paths/<path>.py         Path(config, traffic, seed, devices) with
                            dispatch(i) -> handle, wait(handle) -> loss
    metrics/<metric>.py     read(run) -> number or None, for every metric

A cell is a closed loop that dispatches step i+1 before it waits for step i.
There are two kinds of path, and the traffic file says which (``"trains":
false``; absent, the path trains). ``correct`` follows what the path does:

    a path that trains hands the harness ``initial`` (its parameters before
    the first step) and ``pool``: the first step's loss is held against the
    reference's, during set-up, and the last pass over the pool has to lose
    less than the first (``loss_fell``).

    a path that does not train hands it ``pool`` and, once the window has
    closed and the peak of memory is read, ``produced()``: the float32
    weights, and what the window's last step on ``pool[0]`` produced at the
    timed sizes (the per-sequence losses and every head's logits), letting
    go of its net. The reference (``score()`` of models/<config>.py) then
    runs a sequence at a time: each head's logits by their relative L2
    error, and the worst sequence's loss against the reference's loss of
    the same logits (``outputs_match``, limits in the traffic file's
    ``limits``); every batch has to lose at its last completion in the
    window what it lost at its first (``same_every_pass``, limit 0).
    Nothing of it is counted in ``setup_s``.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` measures
most of the window the same way, then profiles a few steps, and reports the
cell's per-layer metrics. ``trace_reduce`` reads that trace under the
program's own names: every device op under the ``jax.named_scope`` path in
its ``op_name`` (the ``mx_*_ms`` and ``scope_rest_ms`` metrics;
``breakdown.device_ops`` and, in milliseconds a step, ``scopes``), every
kernel under its instruction's name (the ``*_roofline`` metrics), every idle
gap under the innermost ``bench.`` or ``mx.`` host span that covers it
(``breakdown.idle_gaps``, ``idle_ms_per_step``). Without a TPU the run exits
non-zero and prints no result. ``--rehearse DIR`` (the tests) takes the cells
from DIR's ``workloads.json`` and their ``configs/`` and ``traffic/`` from
DIR too, lets a CPU pass and stamps it as one.
"""
import time
T_START = time.perf_counter()

import argparse
import collections
import contextlib
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import statistics
import sys
import traceback

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACED_STEPS = 12  # the first trace_reduce.SKIP of them refill the pipeline


def load_module(kind, name):
    """``<kind>/<name>.py`` beside this file; siblings import each other by
    bare name."""
    path = HERE / kind / f"{name}.py"
    if str(path.parent) not in sys.path:
        sys.path.insert(0, str(path.parent))
    spec = importlib.util.spec_from_file_location(
        f"{kind}_{name}".replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Loop:
    """The closed loop. ``run`` keeps ``ahead`` steps in flight, stops
    dispatching at ``until`` (a ``perf_counter`` time) or after ``count``
    steps, and drains. All times are ``perf_counter`` seconds."""

    def __init__(self, path, ahead):
        self.path, self.ahead = path, ahead
        self.i = self.attempted = self.failed = 0
        self.losses = []

    def run(self, until=None, count=None, span=contextlib.nullcontext):
        done, dispatch_s = [], []
        pending = collections.deque()
        more = True
        while more or pending:
            more = more and self.failed == 0 \
                and (until is None or time.perf_counter() < until) \
                and (count is None or len(dispatch_s) < count)
            try:
                if more:
                    self.attempted += 1
                    t = time.perf_counter()
                    with span("bench.dispatch"):
                        pending.append(self.path.dispatch(self.i))
                    dispatch_s.append(time.perf_counter() - t)
                    self.i += 1
                    if len(pending) < self.ahead:
                        continue
                if not pending:  # ahead 1: the last wait left nothing
                    break
                with span("bench.wait"):
                    loss = float(self.path.wait(pending.popleft()))
                done.append(time.perf_counter())
                self.losses.append(loss)
                self.failed += not math.isfinite(loss)
            except Exception:
                traceback.print_exc()
                self.failed += 1 + len(pending)
                pending.clear()
        return done, dispatch_s


def outputs_gaps(reference, config, batch, params, losses, heads):
    """What a path that does not train produced for ``batch`` against the
    plain reference, a sequence at a time so that it fits: ({"logits_gap.
    head<i>": relative L2 error of head i's logits over the batch against
    ``score``'s, "sequence_loss_gap": the worst sequence's |loss - the
    reference's loss of the same logits| / (|the reference's| + 1)},
    ``score``'s loss of the batch). The first is the net's number and sees
    a precision; the second is the loss program's and sees what is left out
    of a mean. Every gap is None where the path produced other shapes than
    the reference."""
    import jax
    import jax.numpy as jnp
    tokens, labels = batch
    score = jax.jit(lambda p, t, l: reference.score(p, t, l, config))
    loss_of = jax.jit(lambda h, l: reference.loss_of_logits(h, l, config))
    parts = jax.jit(lambda got, ref: (
        jnp.sum((got.astype(jnp.float32) - ref) ** 2), jnp.sum(ref ** 2)))
    n = tokens.shape[0]
    ref_losses, loss_gaps = [], []
    for b in range(n):
        one = slice(b, b + 1)
        ref_loss, want = score(params, tokens[one], labels[one])
        ref_losses.append(float(ref_loss))
        if b == 0:
            sums = [[0.0, 0.0] for _ in want]
            fits = losses.shape == (n,) and [h.shape for h in heads] == [
                (n,) + ref.shape[1:] for ref in want]
        if not fits:
            continue
        for total, got, ref in zip(sums, heads, want):
            err, norm = parts(got[one], ref)
            total[0] += float(err)
            total[1] += float(norm)
        own = float(loss_of([h[one] for h in heads], labels[one]))
        loss_gaps.append(abs(float(losses[b]) - own) / (abs(own) + 1))
    gaps = {f"logits_gap.head{i}": math.sqrt(err / norm) if fits else None
            for i, (err, norm) in enumerate(sums)}
    gaps["sequence_loss_gap"] = max(loss_gaps) if fits else None
    return gaps, statistics.fmean(ref_losses)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true")
    ap.add_argument("--rehearse", type=pathlib.Path)
    args = ap.parse_args()

    data = args.rehearse or HERE
    bench = load_json(ROOT / "BENCHMARK.json")
    if args.rehearse:
        bench["workloads"] = load_json(data / "workloads.json")
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        sys.exit(f"no workload {args.workload!r} in BENCHMARK.json")
    config = load_json(data / "configs" / f"{cell['config']}.json")
    traffic = load_json(data / "traffic" / f"{cell['traffic']}.json")

    sys.path.insert(0, str(ROOT))
    try:
        import jax
        from mxnet_tpu.util import enable_compile_cache
    except ImportError as e:
        sys.exit(f"run.py needs jax and the repo's mxnet_tpu package: {e}")
    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse:
        sys.exit(f"JAX found no TPU: {devices}")
    if len(devices) < cell["chips"]:
        sys.exit(f"{cell['name']} needs {cell['chips']} chips, JAX has "
                 f"{len(devices)}")
    devices = devices[:cell["chips"]]
    enable_compile_cache()
    marks = {"imports_s": time.perf_counter()}  # where set-up goes
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **kw: "compile" in name and compiles.append(name))

    # ---- set-up: net, batches, reference, warm-up -------------------------
    path = load_module("paths", traffic["path"]).Path(
        config, traffic, args.seed, devices)
    marks["build_s"] = time.perf_counter()
    reference = load_module("models", cell["config"])
    trains = traffic.get("trains", True)
    if trains:
        ref_loss = float(jax.jit(
            lambda p, d, l: reference.loss(p, d, l, config))(
                path.initial, *path.pool[0]))
        del path.initial
    marks["reference_s"] = time.perf_counter()
    loop = Loop(path, traffic["ahead"])
    loop.run(count=1)
    first_loss = loop.losses[0] if loop.losses else math.nan
    loop.run(count=traffic["warmup_steps"] - 1)
    loop.i = loop.attempted = 0
    loop.losses = []
    gc.collect()

    # ---- the window -------------------------------------------------------
    compiled_before = len(compiles)
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    marks["warmup_s"] = t0
    trace = None
    if args.trace:
        import trace_reduce
        done, dispatch_s = loop.run(until=t0 + args.seconds - 5.0)
        trace_dir = ROOT / ".bench_trace" / cell["name"]
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        loop.run(count=TRACED_STEPS, span=jax.profiler.TraceAnnotation)
        jax.profiler.stop_trace()
        files = sorted(trace_dir.rglob("*.xplane.pb"))
        trace = trace_reduce.reduce(trace_reduce.load(files[-1])) \
            if files else None
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        done, dispatch_s = loop.run(until=t0 + args.seconds)
    compiled_in_window = len(compiles) - compiled_before
    t_closed = time.perf_counter()

    # ---- what was measured ------------------------------------------------
    kind = devices[0].device_kind
    peaks = load_json(HERE / "peaks.json")["device_kinds"].get(kind)
    # completions but the last: that wait drains the pipeline with no
    # dispatch before it, so where the host sets the pace it bounds no step
    done = done[:-1]
    run = {
        "cell": cell, "config": config, "traffic": traffic,
        "reference": reference, "peaks": peaks, "setup_s": setup_s,
        "done": done, "dispatch_s": dispatch_s, "trace": trace,
    }
    listed = [m for m in bench["per_layer" if args.trace else "end_to_end"]
              if cell["name"] in m.get("workloads", [cell["name"]])]
    # the cell's readers, for one that asks what the others read
    run["readers"] = {m["name"]: load_module("metrics", m["name"])
                      for m in listed}
    metrics = {}
    for m in listed:
        value = run["readers"][m["name"]].read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    placed = set(devices)
    on_device = all(a.sharding.device_set == placed for a in path.state())
    stats = [d.memory_stats() or {} for d in devices]

    # ---- correct ----------------------------------------------------------
    pool = traffic["pool"]
    first_pass = statistics.fmean(loop.losses[:pool] or [0])
    last_pass = statistics.fmean(loop.losses[-pool:] or [0])
    if trains:
        of_kind = {"last_pass_over_first_pass_loss": [
            last_pass / first_pass if first_pass else None, 1.0]}
        by_kind = {"loss_fell": len(loop.losses) >= 2 * pool
                   and last_pass < first_pass}
    else:
        # the steps complete in the order they were issued: step j of the
        # window ran batch j % pool
        last = {j % pool: loss for j, loss in enumerate(loop.losses)}
        change = max(abs(last[b] - loss)
                     for b, loss in enumerate(loop.losses[:pool])) \
            if loop.failed == 0 and len(loop.losses) >= 2 * pool else None
        gaps, ref_loss = outputs_gaps(
            reference, config, path.pool[0], *path.produced())
        of_kind = {"loss_change_between_passes": [change, 0],
                   **{name: [gap, traffic["limits"][name]]
                      for name, gap in gaps.items()}}
        by_kind = {"same_every_pass": change == 0,
                   "outputs_match": all(
                       gap is not None and gap <= traffic["limits"][name]
                       for name, gap in gaps.items())}
    compared = {  # name: [number, limit]
        "first_step_loss_gap": [
            abs(first_loss - ref_loss) / (abs(ref_loss) + 1),
            reference.TOLERANCE],
        "steps_failed": [loop.failed, 0],
        **of_kind,
        "compiled_in_window": [compiled_in_window, 0],
    }
    checks = {
        "reference": compared["first_step_loss_gap"][0]
        <= reference.TOLERANCE,
        "no_step_failed": loop.failed == 0 and loop.attempted > 0,
        **by_kind,
        "on_device": on_device,
        "no_compile_in_window": compiled_in_window == 0,
        "known_device": peaks is not None or bool(args.rehearse),
    }
    device = {
        "platform": devices[0].platform, "kind": kind,
        "count": len(jax.devices()),
        # live arrays at their peak plus what the runtime reserved for the
        # programs' temporaries, which it counts apart (PERF.md section 6)
        "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0)
                                 + s.get("peak_bytes_reserved", 0)
                                 for s in stats),
    }
    result = {
        "correct": all(checks.values()), "attempted": loop.attempted,
        "failed": loop.failed, "metrics": metrics, "device": device,
        "checks": checks,
        "losses": {"reference": ref_loss, "first_step": first_loss,
                   "first_pass": first_pass, "last_pass": last_pass},
        "compiled_in_window": compiled_in_window,
        "setup": {k: b - a for (k, b), a in zip(
            marks.items(), [T_START] + list(marks.values()))},
        # after the window: reading the trace, and the reference of a path
        # that does not train
        "closing_s": time.perf_counter() - t_closed,
    }
    if trace:
        device["busy_s"], device["window_s"] = \
            trace["busy_s"], trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
        for key, by in (("scopes", "seconds_by_scope"),
                        ("idle_ms_per_step", "idle_seconds_by_span")):
            result[key] = {name: 1e3 * seconds / trace["steps"]
                           for name, seconds in trace[by].items()}
    result["compared"] = compared
    for name, (number, limit) in compared.items():
        print(f"compared {name}: {number} limit {limit}", file=sys.stderr)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
