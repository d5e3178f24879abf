#!/usr/bin/env python3
"""The quickest proof that the framework still starts on the chip.

One process trains ResNet-50 v1 (NHWC, bf16 compute, f32 master weights,
batch 256, 224x224, 1000 classes, random weights and data made from
``--seed``) for a few steps on one TPU, through the entry points a user
calls, and checks what comes out:

  device       jax.devices(); anything but a TPU ends the run
  kernels      the Pallas BN(+add)+ReLU epilogue, forward and backward,
               COMPILED, against the composed XLA lowering of the same op
  train_spmd   parallel.SPMDTrainer: step x3, then one run_steps of K=4
  train_gluon  net.initialize(ctx=mx.tpu()) / hybridize / autograd.record /
               gluon.Trainer.step, batch 64, 3 steps

Each phase prints one JSON line when it ends; any failure ends the run with
a non-zero exit code. Times, rates and byte counts are information only,
stamped with the device they were read on. The last line of a run that
passed is

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--chips 4`` runs the multi-chip phase instead and nothing else: the same
model through SPMDTrainer over a dp=4 mesh against the one-device step on
the same batch (and against that step with the batch reordered, which is
how far bf16 lets two right answers lie apart), then
__graft_entry__.dryrun_multichip(4).

To rehearse the same code on the CPU at a tiny size (tests/test_chip_smoke.py
does), pass ``--allow-cpu`` with small ``--model/--batch/--image`` values:
the device phase then lets a CPU pass and the last line names it.
The smoke path needs none of the native libraries under src/.
"""
from __future__ import annotations

import argparse
import contextlib
import faulthandler
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# (HW, C) of the two residual epilogues the kernels phase runs, at --batch
KERNEL_SHAPES = ((56, 256), (7, 2048))
# bf16 tolerance of tests/test_fused_epilogue.py
RTOL = ATOL = 2e-2
# SGD with momentum 0.9, learning rate scaled linearly with the batch
LR_AT_256 = 0.01
# multi-chip against one device, bf16 compute: |dloss| / (|loss| + 1), and
# how many times the distance a reordered batch shows on one device
LOSS_TOL, NOISE_FACTOR = 5e-3, 3
# a hung chip cannot raise: after this many seconds the run kills itself
DEADLINE_S = 1150


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def env_var(name, value):
    """``name`` set to ``value`` inside the block."""
    old = os.environ.get(name)
    os.environ[name] = value
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = old


def device_stamp():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def check_on_device(what, arrays, devices):
    """Every array lives on exactly ``devices`` — not on the host CPU, and
    not on the first chip of several."""
    for a in arrays:
        check(a.devices() == devices,
              f"{what} lives on {sorted(map(str, a.devices()))}, "
              f"expected {sorted(map(str, devices))}")


def memory_row(compiled):
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k + "_size_in_bytes"))
            for k in ("temp", "argument", "output", "alias",
                      "generated_code")}


def n_kernels(compiled):
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def peak_device_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: int(stats[k]) for k in ("peak_bytes_in_use", "bytes_limit")
            if k in stats}


def default_lowering():
    from mxnet_tpu.base import env
    return "fused" if env.get("MXTPU_FUSED_EPILOGUE") else "composed"


def make_batch(args, batch):
    import numpy as np
    rs = np.random.RandomState(args.seed)
    data = rs.rand(batch, args.image, args.image, 3).astype(np.float32)
    label = rs.randint(0, args.classes, (batch,)).astype(np.float32)
    return data, label


def make_net(args, ctx=None):
    """The model-zoo net with every deferred shape resolved by one batch-1
    forward, as a Gluon user does before handing it to a trainer."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    mx.random.seed(args.seed)
    net = vision.get_model(args.model, classes=args.classes, layout="NHWC")
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net(mx.nd.zeros((1, args.image, args.image, 3), ctx=ctx))
    return net


def sgd_params(batch):
    return {"learning_rate": LR_AT_256 * batch / 256, "momentum": 0.9}


def make_spmd_trainer(net, batch, mesh=None):
    import jax.numpy as jnp
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import SPMDTrainer
    return SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), mesh=mesh,
                       optimizer="sgd", optimizer_params=sgd_params(batch),
                       dtype=jnp.bfloat16)


def check_losses(losses):
    import math
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(args):
    import importlib.metadata as md
    import jax
    stamp = device_stamp()
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = None
    check(stamp["platform"] == "tpu" or args.allow_cpu,
          f"JAX found no TPU: jax.devices() is {jax.devices()}")
    check(stamp["count"] >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, JAX has "
          f"{stamp['count']}")
    return {**stamp, **versions}


def phase_kernels(args):
    """fused_bn_act forward + backward, compiled, against the composed
    lowering of the same op (ops/nn.py, MXTPU_FUSED_EPILOGUE=0) on the same
    inputs."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.ops.nn import _fused_bn_act_impl

    on_tpu = jax.devices()[0].platform == "tpu"

    def compile_epilogue(fused, *args):
        """The op's forward + backward under one of its two lowerings. The
        flag is read at trace time, so each lowering is its own function
        object: jit would hand a second trace of the same one the first's."""
        def epilogue(x, res, g, b, dy):
            c = x.shape[-1]

            def fwd(x, res, g, b):
                out, mean, var = _fused_bn_act_impl(
                    x, res, g, b, jnp.zeros(c), jnp.ones(c), 1e-5, False,
                    False, -1, True)
                return out, (mean, var)

            out, vjp, (mean, var) = jax.vjp(fwd, x, res, g, b, has_aux=True)
            return (out, mean, var) + vjp(dy)

        with env_var("MXTPU_FUSED_EPILOGUE", "1" if fused else "0"):
            traced = jax.jit(epilogue).trace(*args)
        check(("pallas_call" in str(traced.jaxpr)) is fused,
              f"MXTPU_FUSED_EPILOGUE={int(fused)} did not pick the lowering")
        return traced.lower().compile()

    @jax.jit
    def compare(got, want):
        """Per output: largest |got - want|, the share of elements beyond
        atol + rtol*|want|, largest |want|; and whether all of got is
        finite."""
        rows = []
        for a, b in zip(got, want):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            err = jnp.abs(a - b)
            rows.append(jnp.stack([
                jnp.max(err), jnp.mean(err > ATOL + RTOL * jnp.abs(b)),
                jnp.max(jnp.abs(b)), jnp.all(jnp.isfinite(a))]))
        return jnp.stack(rows)

    names = ("out", "mean", "var", "dx", "dres", "dgamma", "dbeta")
    rows = []
    for hw, c in KERNEL_SHAPES:
        shape = (args.batch, hw, hw, c)
        k = jax.random.split(jax.random.PRNGKey(args.seed + c), 5)
        x, res, dy = (jax.random.normal(k[i], shape, jnp.float32)
                      .astype(jnp.bfloat16) for i in range(3))
        g = jax.random.uniform(k[3], (c,), jnp.float32, 0.5, 1.5)
        b = jax.random.normal(k[4], (c,), jnp.float32)
        interpret = pk._interpret_for(x)
        check(interpret is (not on_tpu),
              f"interpret resolved {interpret} for an array on "
              f"{sorted(map(str, x.devices()))}")
        fused = compile_epilogue(True, x, res, g, b, dy)
        if on_tpu:
            check(n_kernels(fused) == 4,
                  f"{n_kernels(fused)} tpu_custom_call in the fused "
                  "program, expected 4 compiled kernels")
        got = fused(x, res, g, b, dy)
        check(got[0].shape == shape and got[0].dtype == jnp.bfloat16
              and got[3].shape == shape and got[5].shape == (c,),
              "unexpected output shapes")
        # The reference is the composed lowering on the same values held
        # in float32. Run in bf16 it rounds the normalized activation
        # before the add, which moves the ReLU mask of about one element
        # in a thousand: too blurred to hold a kernel against.
        x32, res32, dy32 = (a.astype(jnp.float32) for a in (x, res, dy))
        composed = compile_epilogue(False, x32, res32, g, b, dy32)
        check(n_kernels(composed) == 0, "composed lowering holds a kernel")
        stats = compare(got, composed(x32, res32, g, b, dy32))
        report = {}
        for name, (max_err, beyond, max_ref, finite) in zip(
                names, stats.tolist()):
            check(finite == 1.0, f"non-finite {name} at {shape}")
            if name in ("dx", "dres"):
                # elementwise through the ReLU mask: an element whose
                # pre-activation is zero to float32 rounding may fall on
                # either side
                ok = beyond <= 1e-4
            elif name in ("dgamma", "dbeta"):
                # sums over all rows, such ties included: held to the
                # tolerance at the scale of the vector
                ok = max_err <= ATOL + RTOL * max_ref
            else:
                ok = beyond == 0.0
            check(ok, f"fused {name} against composed at {shape}: max |err| "
                  f"{max_err}, share beyond tolerance {beyond}, max |ref| "
                  f"{max_ref}")
            report[name] = {"max_abs_err": max_err, "beyond_tol": beyond}
        rows.append({"shape": list(shape), "residual": True,
                     "interpret": interpret,
                     "tpu_custom_calls": n_kernels(fused), "vs_composed": report})
        del got, stats, x, res, dy, x32, res32, dy32
    return {"dtype": "bfloat16", "rtol": RTOL, "atol": ATOL, "cases": rows}


def phase_train_spmd(args):
    import jax
    import jax.numpy as jnp
    dev = {jax.devices()[0]}
    data_np, label_np = make_batch(args, args.batch)
    net = make_net(args)
    tr = make_spmd_trainer(net, args.batch)
    data, label = jnp.asarray(data_np), jnp.asarray(label_np)

    losses, step_s = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        loss = tr.step(data, label)
        losses.append(float(loss))
        step_s.append(time.perf_counter() - t0)
    check_on_device("the step's loss", [loss], dev)
    step_prog = tr.last_compiled()

    k = 4
    datas = jnp.broadcast_to(data[None], (k,) + data.shape)
    labels = jnp.broadcast_to(label[None], (k,) + label.shape)
    t0 = time.perf_counter()
    ks = tr.run_steps(datas, labels)
    ks.block_until_ready()
    multi_first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ks2 = tr.run_steps(datas, labels)
    ks2.block_until_ready()
    multi_warm_s = time.perf_counter() - t0
    losses += [float(x) for x in ks] + [float(x) for x in ks2]
    check_on_device("run_steps' losses", [ks2], dev)

    check_losses(losses)
    params = [p.data()._data for p in net.collect_params().values()]
    check(len(params) == len(tr._trainable) + len(tr._aux), "param count")
    check_on_device("a parameter", params, dev)
    check_on_device("optimizer state",
                    jax.tree_util.tree_leaves(tr._opt_state), dev)
    kernels = n_kernels(step_prog)
    lowering = default_lowering()
    if lowering == "fused" and jax.devices()[0].platform == "tpu":
        check(kernels > 0, "fused lowering, but no tpu_custom_call in the "
              "step's HLO")
    warm = min(step_s[1:])
    return {
        "model": args.model, "batch": args.batch, "image": args.image,
        "lowering": lowering, "tpu_custom_calls": kernels,
        "n_params": len(tr._trainable), "n_aux": len(tr._aux),
        "losses": [round(x, 4) for x in losses],
        "step_memory_analysis": memory_row(step_prog),
        "peak_device": peak_device_bytes(),
        "info": {
            "device_kind": jax.devices()[0].device_kind,
            "first_step_s_compile_included": round(step_s[0], 2),
            "warm_step_ms": round(warm * 1e3, 2),
            "warm_step_img_per_s": round(args.batch / warm, 1),
            "run_steps_k": k,
            "run_steps_first_s_compile_included": round(multi_first_s, 2),
            "run_steps_warm_ms_per_step": round(multi_warm_s / k * 1e3, 2),
            "run_steps_warm_img_per_s": round(
                args.batch * k / multi_warm_s, 1),
        },
    }


def phase_train_gluon(args):
    """The path users write; float32, since that is what it gives them."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    dev = {jax.devices()[0]}
    ctx = mx.tpu()
    batch = args.gluon_batch
    data_np, label_np = make_batch(args, batch)
    net = make_net(args, ctx=ctx)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd", sgd_params(batch))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x = mx.nd.array(data_np, ctx=ctx)
    y = mx.nd.array(label_np, ctx=ctx)
    check_on_device("the batch", [x._data, y._data], dev)

    losses, step_s = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(batch)
        losses.append(float(loss.mean().asscalar()))
        step_s.append(time.perf_counter() - t0)
    check_losses(losses)
    check_on_device("the loss", [loss._data], dev)
    params = list(net.collect_params().values())
    check_on_device("a parameter", [p.data()._data for p in params], dev)
    check_on_device("a gradient", [p.grad()._data for p in params
                                   if p.grad_req != "null"], dev)
    states = [s._data for s in jax.tree_util.tree_leaves(
        [u.states for u in trainer._updaters])]
    check(states, "the trainer holds no optimizer state")
    check_on_device("optimizer state", states, dev)
    warm = min(step_s[1:])
    return {
        "model": args.model, "batch": batch, "image": args.image,
        "dtype": "float32", "lowering": default_lowering(),
        "n_params": len(params), "n_states": len(states),
        "losses": [round(x, 4) for x in losses],
        "peak_device": peak_device_bytes(),
        "info": {
            "device_kind": jax.devices()[0].device_kind,
            "first_step_s_compile_included": round(step_s[0], 2),
            "warm_step_ms": round(warm * 1e3, 2),
            "warm_step_img_per_s": round(batch / warm, 1),
        },
    }


def phase_multichip(args):
    """SPMDTrainer over a dp=N mesh against the one-device step on the same
    batch, then the five strategy meshes of __graft_entry__.

    The first loss must agree. The updated parameters cannot agree closely:
    at initialization this net's first bf16 gradient is so ill-conditioned
    that the same step on ONE device with the batch's rows in another order
    — the same mathematics — moves some weights as far from the reference
    as the update itself. So that reordered step is run too, and the mesh
    is held to NOISE_FACTOR times the distance it shows."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from __graft_entry__ import _tree_parity, dryrun_multichip
    from mxnet_tpu.parallel import make_mesh
    n = args.chips
    devices = jax.devices()[:n]
    check(args.batch % n == 0, f"--batch {args.batch} not divisible by {n}")
    data_np, label_np = make_batch(args, args.batch)

    def two_steps(mesh=None, order=slice(None)):
        """A net from the seed and two steps on the batch: the trainer, the
        net, both losses, the parameters after the first step (in the order
        the architecture declares them: the names carry process-wide block
        counters) and both steps' seconds."""
        net = make_net(args)
        tr = make_spmd_trainer(net, args.batch, mesh=mesh)
        data = jnp.asarray(data_np[order])
        label = jnp.asarray(label_np[order])
        losses, params, secs = [], None, []
        for _ in range(2):
            t0 = time.perf_counter()
            loss = tr.step(data, label)
            losses.append(float(loss))
            secs.append(time.perf_counter() - t0)
            if params is None:
                params = [np.asarray(p.data()._data)
                          for p in net.collect_params().values()]
        return tr, net, loss, losses, params, secs

    def distance(losses, params):
        """(first loss, parameters after it, second loss) from the
        reference: |dloss| / (|loss| + 1) and __graft_entry__._tree_parity."""
        dl = [abs(a - b) / (abs(b) + 1.0) for a, b in zip(losses, ref_losses)]
        return dl[0], _tree_parity(params, ref_params), dl[1]

    # one device first: these leave only host copies behind
    tr, net, _, ref_losses, ref_params, _ = two_steps()
    check_on_device("a reference parameter",
                    [p.data()._data for p in net.collect_params().values()],
                    {devices[0]})
    order = np.random.RandomState(args.seed + 1).permutation(args.batch)
    tr, net, _, noise_losses, noise_params, _ = two_steps(order=order)
    noise = distance(noise_losses, noise_params)

    tr, net, loss, losses, params, secs = two_steps(
        mesh=make_mesh({"dp": n}, devices=devices))
    dist = distance(losses, params)
    check(dist[0] < LOSS_TOL, f"dp={n} first-step loss {losses[0]} vs one "
          f"device {ref_losses[0]}: {dist[0]} >= {LOSS_TOL}")
    check(dist[1] <= NOISE_FACTOR * noise[1],
          f"dp={n} updated parameters vs one device: parity {dist[1]}, more "
          f"than {NOISE_FACTOR} x the {noise[1]} of a reordered batch")
    check(dist[2] <= max(LOSS_TOL, NOISE_FACTOR * noise[2]),
          f"dp={n} second-step loss {losses[1]} vs one device "
          f"{ref_losses[1]}: {dist[2]}, reordered batch {noise[2]}")
    check_losses(losses)

    all_devs = set(devices)
    live = [p.data()._data for p in net.collect_params().values()]
    for a in live + jax.tree_util.tree_leaves(tr._opt_state) + [loss]:
        check(a.sharding.device_set == all_devs,
              f"an array of the dp={n} trainer lives on "
              f"{len(a.sharding.device_set)} devices, expected {n}")
    batch_in = tr._last_program[1][5]
    check(batch_in.sharding.device_set == all_devs
          and batch_in.sharding.shard_shape(batch_in.shape)[0]
          == args.batch // n,
          f"the batch is not split {n} ways: {batch_in.sharding}")
    prog = tr.last_compiled()
    hlo = prog.as_text()
    keys = ("first_loss", "params_after_first_step", "second_loss")
    row = {
        "model": args.model, "global_batch": args.batch, "mesh": {"dp": n},
        "lowering": default_lowering(),
        "losses_dp": [round(x, 5) for x in losses],
        "losses_one_device": [round(x, 5) for x in ref_losses],
        "losses_one_device_reordered": [round(x, 5) for x in noise_losses],
        "dp_vs_one_device": dict(zip(keys, dist)),
        "reordered_vs_one_device": dict(zip(keys, noise)),
        "param_devices": n, "batch_rows_per_device": args.batch // n,
        "all_reduces": hlo.count(" all-reduce(") + hlo.count(
            " all-reduce-start("),
        "step_memory_analysis_per_device": memory_row(prog),
        "peak_device": peak_device_bytes(),
        "info": {
            "device_kind": jax.devices()[0].device_kind,
            "first_step_s_compile_included": round(secs[0], 2),
            "warm_step_ms": round(secs[1] * 1e3, 2),
            "warm_step_img_per_s": round(args.batch / secs[1], 1),
        },
    }
    del tr, net, live, loss
    dryrun_multichip(n)
    row["dryrun_multichip"] = "ok"
    return row


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="more than 1: run only the multi-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--model", default="resnet50_v1")
    ap.add_argument("--classes", type=int, default=1000)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--gluon-batch", type=int, default=64)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal: let the device phase pass without a TPU")
    args = ap.parse_args(argv)

    # dump every thread's stack and exit hard
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    try:
        from mxnet_tpu.util import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke.py needs the repo's mxnet_tpu package beside it: "
              f"{e}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()

    if args.chips > 1:
        phases = [("device", phase_device), ("multichip", phase_multichip)]
    else:
        phases = [("device", phase_device), ("kernels", phase_kernels),
                  ("train_spmd", phase_train_spmd),
                  ("train_gluon", phase_train_gluon)]
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            row = fn(args)
        except Exception as e:
            traceback.print_exc()
            print(json.dumps({"phase": name, "ok": False,
                              "error": f"{type(e).__name__}: {e}"[:2000]}),
                  flush=True)
            return 1
        if name == "device":
            row["compile_cache"] = cache
        print(json.dumps({"phase": name, "ok": True,
                          "seconds": round(time.perf_counter() - t0, 2),
                          **row}), flush=True)
    print(json.dumps({"ok": True, "device": device_stamp()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
