"""Long-context transformer LM training with sequence parallelism.

The first-class long-context recipe: a decoder-only transformer whose
attention runs as RING ATTENTION over the mesh's `sp` axis
(mxnet_tpu.parallel.ring_attention — the blockwise k/v rotation over ICI;
per-device working set is T/n so sequences n× longer than one chip's
memory fit), composed with data parallelism on `dp`. The whole train
step is ONE pjit-compiled program: XLA inserts the gradient psum over
`dp` and the ring ppermutes over `sp`.

Run (virtual 8-device mesh on CPU):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/long_context/train_long_context.py

On a real TPU slice the same code scales across chips — only the mesh
shape changes (ref counterpart: example/gluon/word_language_model + the
reference's dist kvstore, re-designed SPMD-first).
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def build_params(rng, vocab, d_model, n_heads, d_ff, n_layers):
    import jax
    import jax.numpy as jnp
    keys = jax.random.split(rng, 2 + 4 * n_layers)
    s = 0.02
    params = {
        "embed": jax.random.normal(keys[0], (vocab, d_model)) * s,
        "out": jax.random.normal(keys[1], (d_model, vocab)) * s,
        "layers": [],
    }
    for i in range(n_layers):
        k = keys[2 + 4 * i: 6 + 4 * i]
        params["layers"].append({
            "qkv": jax.random.normal(k[0], (d_model, 3 * d_model)) * s,
            "proj": jax.random.normal(k[1], (d_model, d_model)) * s,
            "ff1": jax.random.normal(k[2], (d_model, d_ff)) * s,
            "ff2": jax.random.normal(k[3], (d_ff, d_model)) * s,
        })
    return params


def forward(params, tokens, mesh, n_heads, sp_axis="sp"):
    """tokens (B, T) int32 -> logits (B, T, V); attention over the ring."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import ring_attention

    x = params["embed"][tokens]  # (B, T, D)
    B, T, D = x.shape
    H, hd = n_heads, D // n_heads
    for layer in params["layers"]:
        # pre-norm
        h = x / (jnp.sqrt(jnp.mean(jnp.square(x), axis=-1,
                                   keepdims=True)) + 1e-6)
        qkv = h @ layer["qkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, H, hd)
        k = k.reshape(B, T, H, hd)
        v = v.reshape(B, T, H, hd)
        att = ring_attention(q, k, v, mesh, axis=sp_axis, causal=True)
        x = x + att.reshape(B, T, D) @ layer["proj"]
        h = x / (jnp.sqrt(jnp.mean(jnp.square(x), axis=-1,
                                   keepdims=True)) + 1e-6)
        x = x + jnp.maximum(h @ layer["ff1"], 0.0) @ layer["ff2"]
    return x @ params["out"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--sp", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--n-heads", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=257)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--lr", type=float, default=3e-3)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel import make_mesh

    mesh = make_mesh({"dp": args.dp, "sp": args.sp})
    print(f"mesh: dp={args.dp} x sp={args.sp} over "
          f"{args.dp * args.sp} devices; seq {args.seq_len} "
          f"({args.seq_len // args.sp}/device)")

    rng = jax.random.PRNGKey(0)
    params = build_params(rng, args.vocab, args.d_model, args.n_heads,
                          4 * args.d_model, args.layers)

    # synthetic LEARNABLE task: a FIXED set of period-P sequences — the
    # model memorizes the patterns' bigrams and long-range structure;
    # loss drops toward zero while every attention step runs as a ring
    # over `sp` (the long-range retrieval machinery under test)
    rs = np.random.RandomState(0)
    period = 16
    pat = rs.randint(1, args.vocab, (args.batch, period))
    reps = (args.seq_len + period) // period + 1
    fixed = np.tile(pat, (1, reps))[:, :args.seq_len + 1]

    def batch():
        return fixed[:, :-1].astype(np.int32), fixed[:, 1:].astype(np.int32)

    def loss_fn(p, tokens, targets):
        logits = forward(p, tokens, mesh, args.n_heads)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None],
                                   axis=-1)[..., 0]
        return jnp.mean(nll)

    @jax.jit
    def step(p, m, v, t, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(p, tokens, targets)
        # inline Adam — the update fuses into the same XLA program as the
        # ring-attention forward/backward (one dispatch per step)
        b1, b2, eps = 0.9, 0.999, 1e-8
        m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g,
                                   m, grads)
        v = jax.tree_util.tree_map(
            lambda a, g: b2 * a + (1 - b2) * jnp.square(g), v, grads)
        lr_t = args.lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        new_p = jax.tree_util.tree_map(
            lambda w, mi, vi: w - lr_t * mi / (jnp.sqrt(vi) + eps),
            p, m, v)
        return new_p, m, v, loss

    # shard: batch over dp, sequence over sp; params replicated
    data_sh = NamedSharding(mesh, P("dp", "sp"))
    params = jax.device_put(params, NamedSharding(mesh, P()))
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    m_state, v_state = zeros(), zeros()

    first = last = None
    for i in range(args.steps):
        toks, tgts = batch()
        toks = jax.device_put(jnp.asarray(toks), data_sh)
        tgts = jax.device_put(jnp.asarray(tgts), data_sh)
        params, m_state, v_state, loss = step(params, m_state, v_state,
                                              i + 1, toks, tgts)
        last = float(loss)
        first = first if first is not None else last
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i}: loss {last:.4f}")
    print(f"done (loss {first:.3f} -> {last:.3f})")


if __name__ == "__main__":
    main()
