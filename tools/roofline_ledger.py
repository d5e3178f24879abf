"""Roofline ledger for the ResNet-50 train bench.

Two kinds of evidence, both written into docs/ROOFLINE.json with
provenance (source commit + date + where the measured numbers came
from):

1. **Program stats** (needs the TPU): per-mode XLA cost-model stats
   (flops, bytes accessed) of the EXACT fused 16-step program bench.py
   dispatches, plus a measured pure-HBM-stream bandwidth ceiling.
   Measured img/s is NEVER baked into this file anymore (the round-5
   advisor flagged the hardcoded table silently combined with freshly
   computed cost stats): pass it explicitly via
   ``--imgs-per-sec bf16=2490.77,int8=2550.28``, via the
   ``MXTPU_MEASURED_IPS`` env var (same format), or let ``--measure``
   re-run ``bench.py --train-only`` per mode. Without a source the
   ledger records the cost stats with ``imgs_per_sec_measured: null``.

2. **Per-op byte ledger** (``--per-op``, runs anywhere): an analytic
   decomposition of the train step's HBM bytes over the bench model's
   op instances (B=256 NHWC bf16 s2d ResNet-50), ranking the top byte
   movers and comparing the unfused epilogue lowering against the fused
   Pallas BN(+add)+ReLU path (ops/pallas_kernels.py) — the committed
   answer to "which bytes can fusion remove, and which are irreducible".

3. **From a run report** (``--from-report PATH``, runs anywhere): the
   live efficiency plane (``MXTPU_EFFICIENCY`` + ``MXTPU_RUN_REPORT_DIR``,
   telemetry/efficiency.py) already measured the run's per-step FLOPs,
   bytes and samples/s — a mode row is stamped straight from that
   artifact (same JSON schema, provenance names the report) instead of
   requiring a live re-measure on the TPU.

Run on the TPU:         python tools/roofline_ledger.py --measure
Anywhere (per-op only): python tools/roofline_ledger.py --per-op --skip-stream --modes ''
From a run report:      python tools/roofline_ledger.py --modes '' --from-report runs/run_123_456.json
"""
import argparse
import datetime
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np

BATCH, K = 256, 16
MODE_ENVS = {
    "bf16": {},
    "int8": {"MXNET_CONV_COMPUTE": "int8"},
    "int8+fp8": {"MXNET_CONV_COMPUTE": "int8", "MXNET_RESID_DTYPE": "fp8"},
}


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def provenance(measured_source):
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=ROOT,
            capture_output=True, text=True).stdout.strip() or "unknown"
    except OSError:
        commit = "unknown"
    return {
        "source_commit": commit,
        "generated": datetime.date.today().isoformat(),
        "measured_imgs_per_sec_source": measured_source,
    }


def parse_ips(spec):
    """'bf16=2490.77,int8=2550.28' -> {'bf16': 2490.77, ...}"""
    out = {}
    for part in filter(None, (spec or "").split(",")):
        mode, _, val = part.partition("=")
        mode = mode.strip()
        try:
            out[mode] = float(val)
        except ValueError:
            raise SystemExit(
                f"bad measured-ips entry {part!r}: expected "
                f"mode=imgs_per_sec (e.g. bf16=2490.77)")
        if mode not in MODE_ENVS:
            raise SystemExit(
                f"unknown mode {mode!r} in measured-ips spec; "
                f"known modes: {sorted(MODE_ENVS)}")
    return out


def measure_ips(modes):
    """Re-measure train img/s per mode via bench.py --train-only (the
    same child-process harness the bench uses)."""
    out = {}
    for mode in modes:
        env = dict(os.environ, **MODE_ENVS[mode])
        t0 = time.time()
        res = subprocess.run(
            [sys.executable, os.path.join(ROOT, "bench.py"),
             "--train-only", str(BATCH), str(K)],
            capture_output=True, text=True, env=env, cwd=ROOT)
        for line in res.stdout.splitlines():
            if line.startswith("TRAIN_IPS "):
                out[mode] = float(line.split()[1])
                log(f"  measured {mode}: {out[mode]:.1f} img/s "
                    f"({time.time() - t0:.0f}s)")
        if mode not in out:
            log(f"  measure {mode} FAILED: {(res.stderr or '')[-200:]}")
    return out


def stream_bandwidth_gbs():
    """Measured HBM stream ceiling: sum-reduce a resident 2 GiB bf16
    buffer k times inside one scanned program; the slope between two
    scan lengths cancels the fixed dispatch overhead. Every timed call
    carries a fresh scalar operand and syncs on a host FETCH of the
    scalar (bench.py's sync note)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = 1 << 30  # 1Gi elements of bf16 = 2 GiB
    x = jax.device_put(jnp.ones((n,), jnp.bfloat16))

    def reader(k):
        @jax.jit
        def f(xx, s):
            def body(c, _):
                # abs(x*s - c) cannot be factored into s*sum(x) - n*c by
                # the algebraic simplifier, and c changes per iteration,
                # so every iteration must re-read the full buffer
                return c + jnp.abs(xx * s - c.astype(jnp.bfloat16)) \
                    .sum(dtype=jnp.float32), None
            out, _ = lax.scan(body, jnp.zeros((), jnp.float32),
                              None, length=k)
            return out
        return f

    k_lo, k_hi = 2, 64   # 62-pass slope (~150 ms at nominal BW) so
    #                      dispatch jitter cannot drown it
    f_lo, f_hi = reader(k_lo), reader(k_hi)
    seed = [0]

    def timed(f):
        best = float("inf")
        for _ in range(3):
            seed[0] += 1
            s = jnp.asarray(1.0 + 1e-3 * seed[0], jnp.bfloat16)
            t0 = time.perf_counter()
            float(f(x, s))          # host fetch = real sync
            best = min(best, time.perf_counter() - t0)
        return best

    timed(f_lo); timed(f_hi)        # warm both executables
    for attempt in range(3):
        per_pass = (timed(f_hi) - timed(f_lo)) / (k_hi - k_lo)
        if per_pass > 0:
            return (2.0 * n) / per_pass / 1e9
        log(f"stream probe: non-positive slope (attempt {attempt}) — "
            "dispatch jitter; retrying")
    raise RuntimeError(
        "stream bandwidth probe: slope non-positive after 3 attempts — "
        "refusing to write a garbage bandwidth into the ledger")


def mode_stats(env_overrides):
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    from mxnet_tpu.parallel import SPMDTrainer

    for k, v in env_overrides.items():
        os.environ[k] = v
    try:
        mx.random.seed(0)
        net = resnet50_v1(layout="NHWC", stem_s2d=True)
        net.initialize(mx.init.Xavier())
        trainer = SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(),
                              mesh=None, optimizer="sgd",
                              optimizer_params={"learning_rate": 0.05,
                                                "momentum": 0.9},
                              dtype=jnp.bfloat16)
        # generate on DEVICE: the host-to-device copy of 2.5 GB is not
        # what this tool measures
        import jax
        kk = jax.random.PRNGKey(0)
        data = jax.random.uniform(kk, (K, BATCH, 224, 224, 3),
                                  jnp.float32)
        label = jax.random.randint(jax.random.PRNGKey(1), (K, BATCH),
                                   0, 1000).astype(jnp.float32)
        t0 = time.time()
        trainer.run_steps(data, label)
        log(f"  dispatch (compile-cached) {time.time() - t0:.0f}s")
        return trainer.program_stats()
    finally:
        for k in env_overrides:
            os.environ.pop(k, None)


# ---------------------------------------------------------------------------
# Per-op byte ledger (analytic; no accelerator needed)
# ---------------------------------------------------------------------------

def _resnet50_chains(batch=BATCH, img=224):
    """The bench model's conv->epilogue chains:
    (name, hw_in, hw_out, c_in, c_out, kernel_taps, kind). Spatial sizes
    follow the s2d stem + [3,4,6,3] bottleneck stages of
    resnet50_v1(layout='NHWC'); stage-boundary conv1/downsample convs
    read the PREVIOUS stage's (2x) spatial grid."""
    chains = []
    # s2d stem: 4x4/1 conv over (112,112,12) -> (112,112,64), BN+ReLU
    chains.append(("stem_conv4x4", 112, 112, 12, 64, 16, "relu"))
    stages = [(3, 64, 256, 56), (4, 128, 512, 28),
              (6, 256, 1024, 14), (3, 512, 2048, 7)]
    c_in = 64
    for si, (blocks, mid, out, hw) in enumerate(stages):
        for bi in range(blocks):
            p = f"stage{si + 1}b{bi + 1}"
            # stride-2 on the first block of stages 2-4 lives in conv1
            # (and the downsample), which read the previous stage's grid
            hw_in = hw * 2 if (si > 0 and bi == 0) else hw
            chains.append((f"{p}_conv1x1a", hw_in, hw, c_in, mid, 1,
                           "relu"))
            chains.append((f"{p}_conv3x3", hw, hw, mid, mid, 9, "relu"))
            chains.append((f"{p}_conv1x1b", hw, hw, mid, out, 1,
                           "add_relu"))
            if bi == 0:
                chains.append((f"{p}_downsample", hw_in, hw, c_in, out,
                               1, "bn_only"))
            c_in = out
    return chains


def per_op_ledger(batch=BATCH, img=224, act_bytes=2):
    """HBM bytes per train step, per op instance, under two lowerings:

    - ``unfused``: the composed BatchNorm/add/ReLU ops with XLA's
      elementwise fusion granted wherever it is legal (an OPTIMISTIC
      floor for the current lowering — the measured program moves more:
      the cost model counted 88.1 GB/step at round 5).
    - ``fused``: the Pallas fused-epilogue path
      (MXTPU_FUSED_EPILOGUE=1), where the ReLU-masked cotangent is
      re-derived in-kernel instead of materialized between the ReLU
      backward and the BN reductions.

    Byte model per chain with A = conv-output bytes, R = residual:
      fwd (both):    conv reads in+W, writes A; stats read A;
                     apply reads A (+R), writes A
      bwd unfused:   mask pass reads dy+out, WRITES g; BN sums read
                     g+x; BN apply reads g+x, writes dx; conv bwd reads
                     dy+W (dx) and dy+saved-in (dW)
      bwd fused:     stats read dy+out+x; apply reads dy+out+x, writes
                     dx (+dres); same conv bwd
      The fused path removes the g materialization (one A write + the
      differing read pattern nets to one A write) for relu epilogues;
      for add_relu epilogues g doubles as dres in both lowerings, so
      the delta is zero there. bn_only (downsample) chains have no mask
      and fuse identically either way.
    """
    rows = []
    for (name, hw_in, hw_out, cin, cout, ktaps, kind) in _resnet50_chains(
            batch, img):
        a = batch * hw_out * hw_out * cout * act_bytes   # conv output
        a_in = batch * hw_in * hw_in * cin * act_bytes   # conv input
        wbytes = ktaps * cin * cout * act_bytes    # bf16 weight replica
        conv = (a_in + wbytes + a) + (a + wbytes) + (a + a_in)
        #       fwd                 bwd dx           bwd dW
        if kind == "bn_only":
            epi_unfused = a + (a + a) + (3 * a + 2 * a + a)
            #             stats  apply   bwd: sums(dy,x)+apply(dy,x)+dx
            epi_fused = epi_unfused
        else:
            res = a if kind == "add_relu" else 0
            fwd = a + (a + res + a)                # stats + apply
            bwd_unf = (2 * a + a) + (2 * a) + (2 * a + a)
            #          mask(dy,out)+g  sums(g,x)  apply(g,x)+dx
            #          (the materialized g IS dres for add_relu)
            bwd_fus = (3 * a) + (3 * a + a) + res
            #          stats(dy,out,x) apply(dy,out,x)+dx (+dres)
            epi_unfused = fwd + bwd_unf
            epi_fused = fwd + bwd_fus
        rows.append({
            "op": name, "kind": kind,
            "conv_bytes": conv,
            "epilogue_bytes_unfused": epi_unfused,
            "epilogue_bytes_fused": epi_fused,
            "total_unfused": conv + epi_unfused,
            "total_fused": conv + epi_fused,
        })
    # non-conv traffic: input batch (f32), classifier, params/optimizer
    n_params = 25.6e6
    misc = {
        "op": "input+fc+params+optimizer", "kind": "misc",
        # input read f32 + global-pool/fc acts + per-param: read f32
        # master, write bf16 replica, write f32 grad, momentum r/w,
        # master write
        "conv_bytes": 0,
        "epilogue_bytes_unfused": 0, "epilogue_bytes_fused": 0,
        "total_unfused": int(batch * img * img * 3 * 4 + n_params * 22),
        "total_fused": int(batch * img * img * 3 * 4 + n_params * 22),
    }
    rows.append(misc)
    tot_u = sum(r["total_unfused"] for r in rows)
    tot_f = sum(r["total_fused"] for r in rows)
    top = sorted(rows, key=lambda r: -r["total_unfused"])[:15]
    return {
        "model": "analytic (optimistic-XLA floor; see docstring)",
        "batch": batch, "img": img, "act_dtype_bytes": act_bytes,
        "bytes_per_step_unfused": tot_u,
        "bytes_per_step_fused": tot_f,
        "fused_saving_bytes": tot_u - tot_f,
        "fused_saving_pct": round(100.0 * (tot_u - tot_f) / tot_u, 2),
        "irreducible_pct": round(100.0 * tot_f / tot_u, 2),
        "note": "irreducible = bytes that remain under the fused "
                "epilogue: conv activation I/O, autodiff-saved "
                "activations, weights/optimizer and input traffic. "
                "Shrinking those needs narrower ACTIVATION storage "
                "(quantized epilogue emission), not more fusion.",
        "top_movers": top,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--modes", default="bf16,int8,int8+fp8",
                    help="comma list of modes to lower+cost ('' = skip)")
    ap.add_argument("--imgs-per-sec", default=None,
                    help="measured img/s per mode: bf16=...,int8=...")
    ap.add_argument("--measure", action="store_true",
                    help="re-measure img/s via bench.py --train-only")
    ap.add_argument("--skip-stream", action="store_true",
                    help="skip the HBM stream-bandwidth probe")
    ap.add_argument("--per-op", action="store_true",
                    help="emit the analytic per-op byte ledger")
    ap.add_argument("--from-report", default=None, metavar="PATH",
                    help="stamp a mode row from a persistent run report "
                         "(MXTPU_RUN_REPORT_DIR artifact with the "
                         "efficiency plane on) instead of a live "
                         "re-measure; combine with --modes '' to skip "
                         "lowering entirely")
    ap.add_argument("--report-mode", default="bf16",
                    help="which mode row --from-report stamps "
                         "(default bf16)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="ledger file to update (default "
                         "docs/ROOFLINE.json; tests point this at a "
                         "scratch file)")
    args = ap.parse_args()

    path = args.out or os.path.join(ROOT, "docs", "ROOFLINE.json")
    out = {}
    if os.path.exists(path):
        with open(path) as f:
            out = json.load(f)

    modes = [m for m in args.modes.split(",") if m]
    ips_src = "absent"
    measured = {}
    if args.imgs_per_sec:
        measured, ips_src = parse_ips(args.imgs_per_sec), "cli"
    elif os.environ.get("MXTPU_MEASURED_IPS"):
        measured = parse_ips(os.environ["MXTPU_MEASURED_IPS"])
        ips_src = "env:MXTPU_MEASURED_IPS"
    elif args.measure and modes:
        measured, ips_src = measure_ips(modes), "bench.py --train-only"

    if modes:
        import jax
        from mxnet_tpu.util import enable_compile_cache
        enable_compile_cache()
        log(f"devices: {jax.devices()}")
        if not args.skip_stream:
            bw = stream_bandwidth_gbs()
            log(f"measured HBM stream bandwidth: {bw:.0f} GB/s")
            out["stream_bandwidth_gbs_measured"] = round(bw, 1)
        rows = {}
        for name in modes:
            log(f"mode {name}: lowering + compiling (cache)...")
            s = mode_stats(MODE_ENVS[name])
            # XLA's cost model counts a While/scan BODY once, not times
            # its trip count — the program totals ARE per-step numbers
            row = {
                "imgs_per_sec_measured": measured.get(name),
                "program_flops_per_step": s["flops"],
                "program_bytes_per_step": s["bytes_accessed"],
            }
            if measured.get(name):
                step_s = BATCH / measured[name]
                row["ms_per_step"] = round(1e3 * step_s, 2)
                row["achieved_tflops"] = round(
                    s["flops"] / step_s / 1e12, 1)
                row["achieved_hbm_gbs"] = round(
                    s["bytes_accessed"] / step_s / 1e9, 0)
            rows[name] = row
            log(f"  {name}: {s['flops'] / 1e12:.2f} TFLOP/step, "
                f"{s['bytes_accessed'] / 1e9:.2f} GB/step")
        # merge per mode: a subset --modes run must not wipe the other
        # modes' committed evidence rows from the artifact
        merged = dict(out.get("modes", {}))
        merged.update(rows)
        stamp = provenance(ips_src)
        stamp["regenerated_modes"] = sorted(rows)
        out.update({
            "note": "XLA cost-model stats of the exact fused 16-step "
                    "bench train program (scan body counted once = "
                    "per-step numbers); regenerate with "
                    "tools/roofline_ledger.py on the TPU",
            "batch": BATCH, "fused_steps": K,
            "modes": merged,
            # stamps THIS regeneration (regenerated_modes lists which
            # rows it refreshed; others keep their earlier stamp's story)
            "modes_provenance": stamp,
        })
    elif "modes" in out:
        # modes rows inherited untouched from the existing file: never
        # relabel them with this invocation's (absent) measurement source
        out.setdefault("modes_provenance", {
            "source_commit": "unknown",
            "generated": "unknown",
            "measured_imgs_per_sec_source":
                "file predates provenance stamping",
        })

    if args.from_report:
        # mode row straight from the run report's efficiency rollup —
        # the live plane already measured flops/bytes per step and
        # samples/s, so no accelerator (and no lowering) is needed
        try:
            with open(args.from_report) as f:
                rep = json.load(f)
        except (OSError, ValueError) as e:
            raise SystemExit(f"--from-report: {e}")
        if rep.get("kind") != "mxtpu_run_report":
            raise SystemExit(
                f"--from-report: {args.from_report} is not a run report "
                f"(kind={rep.get('kind')!r})")
        # same format guard as telemetry.run_report.load_run_report /
        # tools/run_compare.py (duplicated — this path stays
        # framework-import-free): a NEWER report with moved fields must
        # fail loudly, not stamp a row of nulls into the ledger
        try:
            fmt = int(rep.get("format", -1))
        except (TypeError, ValueError):
            fmt = -1
        if fmt > 1:
            raise SystemExit(
                f"--from-report: report format {rep.get('format')} is "
                "newer than this reader (1) — update the tool")
        eff = rep.get("efficiency") or {}
        if not eff:
            raise SystemExit(
                "--from-report: report has no efficiency rollup — run "
                "with MXTPU_EFFICIENCY=on to capture one")
        st = rep.get("step_time") or {}
        sps = eff.get("samples_per_s")
        row = {
            "imgs_per_sec_measured": round(sps, 2) if sps else None,
            "program_flops_per_step": eff.get("flops_per_step"),
            "program_bytes_per_step": eff.get("bytes_per_step"),
        }
        if st.get("p50_s"):
            row["ms_per_step"] = round(1e3 * float(st["p50_s"]), 2)
        if eff.get("achieved_flops_per_s"):
            row["achieved_tflops"] = round(
                float(eff["achieved_flops_per_s"]) / 1e12, 3)
        if eff.get("achieved_bytes_per_s"):
            row["achieved_hbm_gbs"] = round(
                float(eff["achieved_bytes_per_s"]) / 1e9, 1)
        if eff.get("mfu") is not None:
            row["mfu"] = round(float(eff["mfu"]), 5)
            row["mfu_estimate"] = bool(eff.get("estimate"))
        merged = dict(out.get("modes", {}))
        merged[args.report_mode] = row
        stamp = provenance(f"run report {args.from_report} "
                           "(efficiency plane samples_per_s)")
        stamp["regenerated_modes"] = [args.report_mode]
        out["modes"] = merged
        out["modes_provenance"] = stamp
        log(f"mode {args.report_mode}: stamped from {args.from_report} "
            f"({sps and round(sps, 1)} samples/s, "
            f"mfu={eff.get('mfu')})")

    if args.per_op:
        out["per_op_ledger"] = per_op_ledger()
        led = out["per_op_ledger"]
        led["provenance"] = provenance("n/a (analytic model)")
        log(f"per-op ledger: {led['bytes_per_step_unfused'] / 1e9:.1f} "
            f"GB/step unfused -> {led['bytes_per_step_fused'] / 1e9:.1f} "
            f"GB/step fused ({led['fused_saving_pct']}% removed, "
            f"{led['irreducible_pct']}% irreducible)")

    out.pop("provenance", None)  # superseded by per-section stamps
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
