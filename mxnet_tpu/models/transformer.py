"""Transformer LM: the long-context / distributed flagship.

The reference's sequence-model story is the fused cuDNN RNN + bucketing
(src/operator/rnn.cc, example/rnn/word_lm); the TPU-native framework adds a
transformer family designed for the mesh from day one:

- weights carry Megatron-style tp shardings (column/row parallel),
- activations are sharded (dp, sp, -) with explicit constraints,
- attention runs as ring attention over the 'sp' axis for long context
  (parallel/ring_attention.py) or plain attention when sp=1,
- the train step is ONE pjit'd program: loss, psum'd grads (inserted by
  GSPMD), and optimizer update fused.

Pure-jax parameter pytree (not Gluon Blocks) so every tensor can carry a
PartitionSpec; the Gluon layer zoo covers the eager/imperative use case.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

from ..base import check

__all__ = ["TransformerConfig", "init_params", "forward", "loss_fn",
           "make_train_step", "param_specs", "make_pipeline_train_step"]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_seq_len: int = 2048
    dtype: Any = None  # e.g. jnp.bfloat16 for MXU-friendly compute
    causal: bool = True
    remat: bool = False  # jax.checkpoint each layer (HBM <-> FLOPs trade)
    # Mixture-of-Experts (expert parallelism over the 'ep' mesh axis;
    # parallel/moe.py). n_experts=0 -> dense FFN everywhere.
    n_experts: int = 0
    moe_every: int = 1   # layer i uses MoE when (i+1) % moe_every == 0
    capacity_factor: float = 1.25
    router_k: int = 1    # top-k routing (1=Switch, 2=GShard)
    aux_loss_coef: float = 0.01

    def is_moe_layer(self, i: int) -> bool:
        return self.n_experts > 0 and (i + 1) % self.moe_every == 0


def _dt(config):
    import jax.numpy as jnp
    return config.dtype or jnp.float32


def init_params(key, config: TransformerConfig) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    k = jax.random.split(key, 2 + config.n_layers)
    d, h, f = config.d_model, config.n_heads, config.d_ff
    dt = _dt(config)
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(k[0], (config.vocab_size, d)) * 0.02
                  ).astype(dt),
        "ln_f_scale": jnp.ones((d,), dt),
        "ln_f_bias": jnp.zeros((d,), dt),
    }
    for i in range(config.n_layers):
        kk = jax.random.split(k[2 + i], 6)
        s = 0.02
        lp = {
            "ln1_scale": jnp.ones((d,), dt),
            "ln1_bias": jnp.zeros((d,), dt),
            "w_qkv": (jax.random.normal(kk[0], (d, 3 * d)) * s).astype(dt),
            "wo": (jax.random.normal(kk[1], (d, d)) * s /
                   math.sqrt(2 * config.n_layers)).astype(dt),
            "ln2_scale": jnp.ones((d,), dt),
            "ln2_bias": jnp.zeros((d,), dt),
        }
        if config.is_moe_layer(i):
            from ..parallel.moe import init_moe_params
            lp["moe"] = init_moe_params(kk[4], d, f, config.n_experts,
                                        dtype=dt)
        else:
            lp.update({
                "ffn_in": (jax.random.normal(kk[2], (d, f)) * s).astype(dt),
                "ffn_in_b": jnp.zeros((f,), dt),
                "ffn_out": (jax.random.normal(kk[3], (f, d)) * s /
                            math.sqrt(2 * config.n_layers)).astype(dt),
                "ffn_out_b": jnp.zeros((d,), dt),
            })
        params[f"layer{i}"] = lp
    return params


def _single_layer_specs(config: TransformerConfig, mesh, i: int):
    """Megatron-style tp shardings for one layer: qkv/ffn_in
    column-parallel, wo/ffn_out row-parallel; MoE layers delegate to
    moe_param_specs (ep x tp)."""
    from jax.sharding import PartitionSpec as P
    names = mesh.axis_names if mesh is not None else ()
    tp = "tp" if "tp" in names else None
    vec = P()
    lsp = {
        "ln1_scale": vec, "ln1_bias": vec,
        "w_qkv": P(None, tp),
        "wo": P(tp, None),
        "ln2_scale": vec, "ln2_bias": vec,
    }
    if config.is_moe_layer(i):
        from ..parallel.moe import moe_param_specs
        lsp["moe"] = moe_param_specs(mesh)
    else:
        lsp.update({"ffn_in": P(None, tp), "ffn_in_b": P(tp),
                    "ffn_out": P(tp, None), "ffn_out_b": vec})
    return lsp


def param_specs(config: TransformerConfig, mesh) -> Dict[str, Any]:
    """Full-model shardings: embedding sharded over vocab on tp, layers
    per _single_layer_specs."""
    from jax.sharding import PartitionSpec as P
    tp = "tp" if "tp" in mesh.axis_names else None
    specs: Dict[str, Any] = {
        "embed": P(tp, None),
        "ln_f_scale": P(), "ln_f_bias": P(),
    }
    for i in range(config.n_layers):
        specs[f"layer{i}"] = _single_layer_specs(config, mesh, i)
    return specs


def _pos_encode(tokens, d: int, dtype):
    """Stateless sinusoidal positional encoding, (1, T, d)."""
    import jax.numpy as jnp
    pos = jnp.arange(tokens.shape[1])[:, None]
    dim = jnp.arange(d // 2)[None, :]
    angle = pos / jnp.power(10000.0, 2 * dim / d)
    pe = jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)
    return pe[None].astype(dtype)


def _layernorm(x, scale, bias, eps=1e-5):
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) / jnp.sqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _block(x, lp, config: TransformerConfig, mesh, act_spec):
    import jax
    import jax.numpy as jnp
    b, t, d = x.shape
    h = config.n_heads
    hd = d // h

    y = _layernorm(x, lp["ln1_scale"], lp["ln1_bias"])
    qkv = jnp.einsum("btd,de->bte", y, lp["w_qkv"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, t, h, hd)
    k = k.reshape(b, t, h, hd)
    v = v.reshape(b, t, h, hd)
    from ..ops.pallas_kernels import _interpret_for, flash_attention
    from ..parallel.ring_attention import attention, ring_attention
    if mesh is not None and "sp" in mesh.axis_names and \
            dict(zip(mesh.axis_names, mesh.devices.shape))["sp"] > 1:
        attn = ring_attention(q, k, v, mesh, axis="sp", causal=config.causal)
    elif not _interpret_for(q) and t % 128 == 0 and hd >= 64:
        # single-chip hot path: fused Pallas attention (no (T,T) in HBM),
        # taken exactly where the kernel runs compiled — the one platform
        # rule of ops/pallas_kernels.py; interpret mode would only be slow
        attn = flash_attention(q, k, v, causal=config.causal)
    else:
        attn = attention(q, k, v, causal=config.causal)
    attn = attn.reshape(b, t, d)
    x = x + jnp.einsum("btd,de->bte", attn, lp["wo"])
    if act_spec is not None:
        x = jax.lax.with_sharding_constraint(x, act_spec)

    y = _layernorm(x, lp["ln2_scale"], lp["ln2_bias"])
    aux = jnp.zeros((), jnp.float32)
    if "moe" in lp:
        from ..parallel.moe import moe_ffn
        ff, aux = moe_ffn(y, lp["moe"], config.n_experts,
                          capacity_factor=config.capacity_factor,
                          k=config.router_k)
        x = x + ff
    else:
        hdn = jnp.einsum("btd,df->btf", y, lp["ffn_in"]) + lp["ffn_in_b"]
        hdn = jax.nn.gelu(hdn)
        x = x + jnp.einsum("btf,fd->btd", hdn, lp["ffn_out"]) \
            + lp["ffn_out_b"]
    if act_spec is not None:
        x = jax.lax.with_sharding_constraint(x, act_spec)
    return x, aux


def forward(params, tokens, config: TransformerConfig, mesh=None,
            return_aux: bool = False):
    """tokens (B, T) int32 -> logits (B, T, vocab).
    With return_aux=True also returns the summed MoE load-balance loss."""
    import jax
    import jax.numpy as jnp
    act_spec = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        act_spec = NamedSharding(
            mesh, P("dp" if "dp" in sizes else None,
                    "sp" if "sp" in sizes else None, None))
    x = params["embed"][tokens]  # (B, T, D)
    x = x + _pos_encode(tokens, config.d_model, x.dtype)
    if act_spec is not None:
        x = jax.lax.with_sharding_constraint(x, act_spec)

    blk = _block
    if config.remat:
        # config, Mesh and NamedSharding are all hashable non-array args
        blk = jax.checkpoint(_block, static_argnums=(2, 3, 4))

    aux = jnp.zeros((), jnp.float32)
    for i in range(config.n_layers):
        x, a = blk(x, params[f"layer{i}"], config, mesh, act_spec)
        aux = aux + a
    x = _layernorm(x, params["ln_f_scale"], params["ln_f_bias"])
    logits = jnp.einsum("btd,vd->btv", x, params["embed"])
    return (logits, aux) if return_aux else logits


def loss_fn(params, tokens, targets, config: TransformerConfig, mesh=None):
    import jax
    import jax.numpy as jnp
    logits, aux = forward(params, tokens, config, mesh, return_aux=True)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return jnp.mean(nll) + config.aux_loss_coef * aux


def make_train_step(config: TransformerConfig, mesh=None, lr: float = 1e-3):
    """Returns (jitted_step, shard_params_fn). step(params, tokens, targets)
    -> (loss, new_params). One XLA program: fwd+bwd+sgd, GSPMD collectives."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def step(params, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets,
                                                  config, mesh)
        new_params = jax.tree_util.tree_map(
            lambda w, g: w - lr * g.astype(w.dtype), params, grads)
        return loss, new_params

    if mesh is None:
        return jax.jit(step, donate_argnums=(0,)), lambda p: p

    specs = param_specs(config, mesh)
    param_shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P))
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tok_sharding = NamedSharding(
        mesh, P("dp" if "dp" in sizes else None,
                "sp" if "sp" in sizes else None))

    def shard_params(params):
        return jax.tree_util.tree_map(jax.device_put, params,
                                      param_shardings)

    jitted = jax.jit(step,
                     in_shardings=(param_shardings, tok_sharding,
                                   tok_sharding),
                     out_shardings=(NamedSharding(mesh, P()),
                                    param_shardings),
                     donate_argnums=(0,))
    return jitted, shard_params


# ----------------------------------------------------------------------
# Pipeline parallelism: layers stage-stacked over the 'pp' mesh axis.
# ----------------------------------------------------------------------

def make_pipeline_train_step(config: TransformerConfig, mesh,
                             lr: float = 1e-3,
                             n_microbatches: Optional[int] = None):
    """Pipelined train step over a mesh with a 'pp' axis.

    Layers are grouped into S = |pp| stages (config.n_layers % S == 0; all
    layers must share one structure, i.e. uniformly dense or uniformly
    MoE, so the stage stack is a single pytree). Returns
    (jitted_step, prepare): ``prepare(init_params(...))`` stacks per-layer
    params into {'embed', 'ln_f_*', 'stages'} with leaves (S, L/S, ...)
    sharded P('pp', ...), and ``step(pparams, tokens, targets)`` runs
    fwd (GPipe microbatch schedule, parallel/pipeline.py) + bwd + SGD as
    one XLA program. MoE aux loss is not threaded through the pipeline
    scan (load-balance term is omitted on this path).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..parallel.pipeline import pipeline_apply
    from ..parallel.mesh import axis_size

    S = axis_size(mesh, "pp")
    L = config.n_layers
    check(L % max(S, 1) == 0,
          f"n_layers={L} must divide over {S} pipeline stages")
    lps = L // max(S, 1)
    if config.n_experts > 0:
        moe_flags = [config.is_moe_layer(i) for i in range(L)]
        check(all(moe_flags) or not any(moe_flags),
              "pipeline stacking needs uniform layers (set moe_every=1)")

    names = mesh.axis_names
    sizes = dict(zip(names, mesh.devices.shape))
    dp = "dp" if "dp" in names and sizes.get("dp", 1) > 1 else None

    layer_specs = _single_layer_specs(config, mesh, 0)
    stage_specs = jax.tree_util.tree_map(
        lambda s: P("pp", None, *s), layer_specs,
        is_leaf=lambda s: isinstance(s, P))
    top_specs = {"embed": P(None, None), "ln_f_scale": P(), "ln_f_bias": P(),
                 "stages": stage_specs}
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), top_specs,
        is_leaf=lambda s: isinstance(s, P))

    def prepare(params):
        layers = [params[f"layer{i}"] for i in range(L)]
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs).reshape(S, lps, *xs[0].shape),
            *layers)
        pparams = {"embed": params["embed"],
                   "ln_f_scale": params["ln_f_scale"],
                   "ln_f_bias": params["ln_f_bias"],
                   "stages": stacked}
        return jax.tree_util.tree_map(jax.device_put, pparams, shardings)

    def stage_fn(lp_stack, xm):
        for j in range(lps):
            lp = jax.tree_util.tree_map(lambda a: a[j], lp_stack)
            xm, _ = _block(xm, lp, config, None, None)
        return xm

    def pipe_forward(pparams, tokens):
        x = pparams["embed"][tokens]
        x = x + _pos_encode(tokens, config.d_model, x.dtype)
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(dp, None, None)))
        x = pipeline_apply(stage_fn, pparams["stages"], x, mesh,
                           axis="pp", n_microbatches=n_microbatches)
        x = _layernorm(x, pparams["ln_f_scale"], pparams["ln_f_bias"])
        return jnp.einsum("btd,vd->btv", x, pparams["embed"])

    def loss_of(pparams, tokens, targets):
        logits = pipe_forward(pparams, tokens)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return jnp.mean(nll)

    tok_sharding = NamedSharding(mesh, P(dp, None))

    def step(pparams, tokens, targets):
        loss, grads = jax.value_and_grad(loss_of)(pparams, tokens, targets)
        new_params = jax.tree_util.tree_map(
            lambda w, g: w - lr * g.astype(w.dtype), pparams, grads)
        return loss, new_params

    jitted = jax.jit(step,
                     in_shardings=(shardings, tok_sharding, tok_sharding),
                     out_shardings=(NamedSharding(mesh, P()), shardings),
                     donate_argnums=(0,))
    return jitted, prepare
