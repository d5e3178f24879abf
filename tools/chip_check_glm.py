#!/usr/bin/env python3
"""One chip check of GLM-4.7-Flash beyond ``benchmark/chip/run.py``'s
``correct``: for one 8,192-token sequence at the published widths, the
logits of both heads and the gradient of the loss by parameter group, the
framework in bfloat16 (``SPMDTrainer``'s precision: float32 masters,
bfloat16 replicas) against the plain float32 reference
(``benchmark/chip/models/glm_4_7_flash.py``), and the same with every weight
matrix rounded to 3 mantissa bits first (bfloat16 keeps 7): a further
halving of the precision, which must NOT pass. Relative L2 errors; prints
one JSON object and exits 1 if bfloat16 exceeds a limit or the rounded
weights do not.

    python3 tools/chip_check_glm.py [--seed N] [--rehearse]

``--rehearse`` takes the toy configuration of
``benchmark/chip/tests/rehearse_29`` (any backend; checks the flow only).
"""
import argparse
import importlib.util
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmark" / "chip"
# bfloat16 must stay under each, 3-bit weights must lie over each. Set from
# the two readings of PR 29's chip run (PERF.md section 6): logits 0.034
# against 0.117, gradients 0.046 against 0.126, and the gradients of the
# routed experts and the router, where a token between two experts changes
# sides, 0.21 against 0.44.
LIMITS = {"logits": 0.065, "gradients": 0.08, "routed_gradients": 0.3}
ROUTED = ("experts", "router")


def readings(result):
    """{limit's name: the errors it judges}."""
    grads = result["gradients"]
    return {"logits": list(result["logits"].values()),
            "gradients": [e for g, (e, _) in grads.items()
                          if g not in ROUTED],
            "routed_gradients": [grads[g][0] for g in ROUTED]}


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2**31 + 29)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(CHIP / "paths")]
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu import autograd
    from mxnet_tpu.ndarray.ndarray import from_jax
    from mxnet_tpu.util import enable_compile_cache
    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        sys.exit(f"JAX found no TPU: {jax.devices()}")
    enable_compile_cache()
    data = CHIP / "tests" / "rehearse_29" if args.rehearse else CHIP
    config = json.loads((data / "configs" / "glm_4_7_flash.json").read_text())
    traffic = json.loads(next((data / "traffic").glob("spmd_lm_*.json"))
                         .read_text())
    reference = load(CHIP / "models" / "glm_4_7_flash.py", "glm_reference")
    path = load(CHIP / "paths" / "spmd_lm.py", "spmd_lm").Path(
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
                return loss, tuple(o._data for o in out)
        finally:
            for p, a in zip(objs, saved):
                p._data._data = a

    plain_layer = reference.layer
    reference.layer = lambda x, p, c: jax.checkpoint(
        lambda x, p: plain_layer(x, p, c))(x, p)

    def plain(params, tokens, label):
        with jax.default_matmul_precision("highest"):
            main, mtp = reference.forward(params, tokens, config)
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
    result = {"device": jax.devices()[0].device_kind, "seed": args.seed,
              "tokens": int(tokens.shape[1]) - 1,
              "reference_loss": float(ref_loss)}
    for name, bits in (("bfloat16", None), ("mantissa3", 3)):
        (loss, logits), grads = grad(
            lambda p, t, l: system(p, t, l, bits))(params, tokens, label)
        result[name] = {
            "loss": float(loss),
            "logits": {h: float(rel(a, b)) for h, a, b in
                       zip(("main", "mtp"), logits, ref_logits)},
            "gradients": {g: [float(e), float(r)] for g, (e, r) in
                          jax.jit(compare)(grads, ref_grads).items()}}
        del grads, logits
    low, lower = readings(result["bfloat16"]), readings(result["mantissa3"])
    result["ok"] = all(max(low[k]) < limit < min(lower[k])
                       for k, limit in LIMITS.items())
    result["limits"] = LIMITS
    print(json.dumps(result), flush=True)
    sys.exit(0 if result["ok"] or args.rehearse else 1)


if __name__ == "__main__":
    main()
