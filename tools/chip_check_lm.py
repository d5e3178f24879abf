#!/usr/bin/env python3
"""One chip check of a benchmark language model beyond
``benchmark/chip/run.py``'s ``correct``: for one sequence of the cell's
traffic at the published widths, the logits of every head and the gradient of
the loss by parameter group, the framework in bfloat16 (``SPMDTrainer``'s
precision: float32 masters, bfloat16 replicas) against the model's plain
float32 reference (``benchmark/chip/models/<model>.py``), and the same in one
or more LOWER precisions, each of which must NOT pass:

    mantissa3      every weight matrix rounded to 3 mantissa bits first
                   (bfloat16 keeps 7): a further halving of the precision
    decay_bf16     (``nemotron_3_nano_30b_a3b``) the state-space scan's decay
                   and running sums kept in bfloat16, not float32

Relative L2 errors; prints one JSON object and exits 1 if bfloat16 exceeds a
limit or a lower precision stays under all of them.

    python3 tools/chip_check_lm.py [--seed N] [--rehearse]
        --model glm_4_7_flash|nemotron_3_nano_30b_a3b

``--rehearse`` takes the model's toy configuration under
``benchmark/chip/tests`` (any backend; checks the flow only).
"""
import argparse
import contextlib
import functools
import importlib.util
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmark" / "chip"


@contextlib.contextmanager
def decay_in_bfloat16():
    """``ops.lm_ops.ssd_chunked`` with its decays and running sums in
    bfloat16. The mixer is traced once an (op, attributes) pair and kept, so
    the kept traces go before and after."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import lm_ops
    plain = lm_ops.ssd_chunked
    lm_ops.ssd_chunked = functools.partial(plain, decay_dtype=jnp.bfloat16)
    jax.clear_caches()
    try:
        yield
    finally:
        lm_ops.ssd_chunked = plain
        jax.clear_caches()


# limits: bfloat16 must stay under each, every lower precision must lie over
# at least one (``all_over``: over each). ``routed``: the parameter groups
# whose gradients feel a token that changes experts, judged by
# ``routed_gradients``; ``decay``: those whose gradients come through the
# state-space recurrence alone, judged by ``decay_gradients``; the other
# groups by ``gradients``. ``lower``: {name: (mantissa bits of the weights or
# None, a context the system runs in)}.
MODELS = {
    # PR 29's chip run (PERF.md section 6): logits 0.034 against 0.117,
    # gradients 0.046 against 0.126, routed 0.21 against 0.44
    "glm_4_7_flash": dict(
        rehearse="rehearse_29", traffic="spmd_lm_*.json",
        heads=("main", "mtp"),
        limits={"logits": 0.065, "gradients": 0.08, "routed_gradients": 0.3},
        routed=("experts", "router"), decay=(), all_over=True,
        lower={"mantissa3": (3, contextlib.nullcontext)}),
    # PR 33's chip run (PERF.md section 6), bfloat16 / decay_bf16 /
    # mantissa3: logits 0.041 / 0.058 / 0.172; gradients at most 0.057 / at
    # least 0.055 / 0.169; routed at most 0.235 / 0.30 / at least 0.47;
    # A_log's and dt_bias's 0.058 / 0.166 / 0.243. Each limit is the
    # geometric mean of the bfloat16 reading and the nearest lower-precision
    # one that it has to catch; decay_bf16 is caught by decay_gradients alone
    "nemotron_3_nano_30b_a3b": dict(
        rehearse="rehearse_33", traffic="spmd_causal_lm_*.json",
        heads=("main",),
        limits={"logits": 0.08, "gradients": 0.1, "routed_gradients": 0.33,
                "decay_gradients": 0.1},
        routed=("experts", "router"), decay=("mamba_decay",), all_over=False,
        lower={"mantissa3": (3, contextlib.nullcontext),
               "decay_bf16": (None, decay_in_bfloat16)}),
}


def readings(result, spec):
    """{limit's name: the errors it judges}."""
    grads = result["gradients"]
    apart = spec["routed"] + spec["decay"]
    return {"logits": list(result["logits"].values()),
            "gradients": [e for g, (e, _) in grads.items() if g not in apart],
            "routed_gradients": [grads[g][0] for g in spec["routed"]],
            "decay_gradients": [grads[g][0] for g in spec["decay"]]}


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=sorted(MODELS), required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 29)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    spec = MODELS[args.model]
    sys.path[:0] = [str(ROOT), str(CHIP / "paths")]
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import autograd
    from mxnet_tpu.ndarray.ndarray import from_jax
    from mxnet_tpu.util import enable_compile_cache
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        sys.exit(f"JAX found no TPU: {jax.devices()}")
    enable_compile_cache()
    data = CHIP / "tests" / spec["rehearse"] if args.rehearse else CHIP
    config = json.loads((data / "configs" / f"{args.model}.json").read_text())
    traffic = json.loads(next((data / "traffic").glob(spec["traffic"]))
                         .read_text())
    reference = load(CHIP / "models" / f"{args.model}.py", "reference")
    path = load(CHIP / "paths" / f"{traffic['path']}.py", "path").Path(
        config, traffic, args.seed, jax.devices()[:1])
    params, (tokens, label) = path.initial, path.pool[0]
    objs = list(path.net.collect_params().values())
    loss_fn = path.trainer.loss_fn

    def system(params, tokens, label, mantissa_bits=None):
        """Loss and logits as SPMDTrainer's step computes them."""
        saved = [p._data._data for p in objs]
        for p, a in zip(objs, params):
            if mantissa_bits is not None and a.ndim > 1:
                # round the value (reduce_precision, which XLA does not
                # elide as it does a cast down and up again); the gradient
                # passes straight through
                a = a + jax.lax.stop_gradient(jax.lax.reduce_precision(
                    a, exponent_bits=8, mantissa_bits=mantissa_bits) - a)
            p._data._data = a.astype(jnp.bfloat16)
        try:
            with autograd.pause():
                out = path.net(from_jax(tokens))
                loss = jnp.mean(loss_fn(out, from_jax(label))._data
                                .astype(jnp.float32))
                out = out if isinstance(out, (list, tuple)) else (out,)
                return loss, tuple(o._data for o in out)
        finally:
            for p, a in zip(objs, saved):
                p._data._data = a

    plain_layer = reference.layer
    reference.layer = lambda x, p, c: jax.checkpoint(
        lambda x, p: plain_layer(x, p, c))(x, p)

    def plain(params, tokens, label):
        with jax.default_matmul_precision("highest"):
            logits = reference.forward(params, tokens, config)
            if len(spec["heads"]) == 1:
                return reference.cross_entropy(logits, label), (logits,)
            main, mtp = logits
            loss = reference.cross_entropy(main, label[..., 0]) \
                + config["mtp_loss_weight"] * reference.cross_entropy(
                    mtp, label[..., 1])
        return loss, (main, mtp)

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b ** 2))

    def compare(got, want):
        """{group: (relative L2 error, norm of got / norm of want)}."""
        out = {}
        got = reference.parameter_groups(got, config)
        want = reference.parameter_groups(want, config)
        for g in want:
            err = sum(jnp.sum((a - b) ** 2) for a, b in zip(got[g], want[g]))
            n_got = sum(jnp.sum(a ** 2) for a in got[g])
            n_want = sum(jnp.sum(b ** 2) for b in want[g])
            out[g] = (jnp.sqrt(err / n_want), jnp.sqrt(n_got / n_want))
        return out

    grad = lambda f: jax.jit(jax.value_and_grad(f, has_aux=True))  # noqa: E731
    (ref_loss, ref_logits), ref_grads = grad(plain)(params, tokens, label)
    result = {"model": args.model, "device": jax.devices()[0].device_kind,
              "seed": args.seed, "tokens": config["tokens_per_sample"],
              "reference_loss": float(ref_loss)}
    variants = {"bfloat16": (None, contextlib.nullcontext), **spec["lower"]}
    for name, (bits, context) in variants.items():
        with context():
            (loss, logits), grads = grad(
                lambda p, t, l: system(p, t, l, bits))(params, tokens, label)
        result[name] = {
            "loss": float(loss),
            "logits": {h: float(rel(a, b)) for h, a, b in
                       zip(spec["heads"], logits, ref_logits)},
            "gradients": {g: [float(e), float(r)] for g, (e, r) in
                          jax.jit(compare)(grads, ref_grads).items()}}
        del grads, logits
    limits = spec["limits"]
    low = readings(result["bfloat16"], spec)
    result["ok"] = all(max(low[k]) < limit for k, limit in limits.items())
    for name in spec["lower"]:
        lower = readings(result[name], spec)
        over = [limit < min(lower[k]) for k, limit in limits.items()]
        result["ok"] &= all(over) if spec["all_over"] else any(over)
    result["limits"] = limits
    print(json.dumps(result), flush=True)
    sys.exit(0 if result["ok"] or args.rehearse else 1)


if __name__ == "__main__":
    main()
