"""Operators of a sparse latent-attention language model (the GLM-4.7-Flash
/ DeepSeek-V2 family): RMSNorm, rotary embedding, multi-head latent
attention (MLA), the gated FFN and the dropless expert layer.

Pure JAX functions, registered like every other op, so one definition
serves eager NDArray calls, the autograd tape, hybridized blocks and
``SPMDTrainer``'s one-program step. Weights multiply from the right
(``x @ w``: ``w`` is (in, out)). Attention runs through
``pallas_kernels.blocked_attention``; the expert layer is
``parallel.moe.dropless_moe_ffn``. The model's blocks are in
``gluon/model_zoo/text``.
"""
from __future__ import annotations

from .registry import register


def rms_norm(x, weight, eps=1e-5):
    """x * rsqrt(mean(x^2) + eps) * weight over the last axis; the
    statistics in float32 whatever ``x`` is."""
    import jax
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def rope(x, theta=10000.0):
    """Rotary embedding over the last axis of ``x`` (..., T, dim), position
    = index along T. Pairs are (i, i + dim/2) ("rotate half"); angles in
    float32."""
    import jax.numpy as jnp
    t, dim = x.shape[-2], x.shape[-1]
    half = dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def swiglu(h):
    """silu(gate) * up of ``h = [gate | up]`` along the last axis."""
    import jax
    f = h.shape[-1] // 2
    return jax.nn.silu(h[..., :f]) * h[..., f:]


def swiglu_ffn(x, w_in, w_out):
    """(silu(x @ w_gate) * (x @ w_up)) @ w_out with ``w_in = [w_gate |
    w_up]`` one (D, 2F) product."""
    import jax.numpy as jnp
    return jnp.dot(swiglu(jnp.dot(x, w_in)), w_out)


def mla_attention(x, w_qa, q_norm, w_qb, w_kva, kv_norm, w_kvb, w_o,
                  heads=1, nope=0, rope_dim=0, v_dim=0, theta=10000.0,
                  eps=1e-5):
    """Causal multi-head latent attention over x (B, T, D).

    ``c_q = RMSNorm(x w_qa)``; ``q = c_q w_qb`` -> heads x (nope + rope);
    ``[c_kv | k_r] = x w_kva``; ``[k_nope | v] = RMSNorm(c_kv) w_kvb`` ->
    heads x (nope + v_dim); rotary embedding on q's rope dimensions and on
    ``k_r``, ONE rope key that every head shares; softmax of
    ``q.k / sqrt(nope + rope)`` under the causal mask; ``w_o`` from heads x
    v_dim back to D. No biases. Attention is blocked over queries and keys
    in both passes (``blocked_attention``).
    """
    import jax
    import jax.numpy as jnp
    from .pallas_kernels import blocked_attention
    b, t, _ = x.shape
    rank = w_kva.shape[1] - rope_dim
    with jax.named_scope("mx.mla"):
        c_q = rms_norm(jnp.dot(x, w_qa), q_norm, eps)
        q = jnp.einsum("btr,rhk->bhtk", c_q,
                       w_qb.reshape(w_qb.shape[0], heads, nope + rope_dim))
        kva = jnp.dot(x, w_kva)
        c_kv = rms_norm(kva[..., :rank], kv_norm, eps)
        kv = jnp.einsum("btr,rhk->bhtk", c_kv,
                        w_kvb.reshape(rank, heads, nope + v_dim))
        k_rope = rope(kva[..., rank:], theta)                     # (B, T, r)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope[:, None], (b, heads, t, rope_dim))], -1)
        v = kv[..., nope:]
        o = blocked_attention(
            q.reshape(b * heads, t, -1), k.reshape(b * heads, t, -1),
            v.reshape(b * heads, t, v_dim), causal=True,
            scale=(nope + rope_dim) ** -0.5)
        return jnp.einsum("bhtv,hvd->btd", o.reshape(b, heads, t, v_dim),
                          w_o.reshape(heads, v_dim, w_o.shape[1]))


@register("_contrib_rms_norm", aliases=("rms_norm",))
def _rms_norm_op(x, weight, eps=1e-5):
    return rms_norm(x, weight, eps)


@register("_contrib_swiglu_ffn")
def _swiglu_ffn_op(x, w_in, w_out):
    return swiglu_ffn(x, w_in, w_out)


@register("_contrib_mla_attention")
def _mla_attention_op(x, w_qa, q_norm, w_qb, w_kva, kv_norm, w_kvb, w_o,
                      heads=1, nope=0, rope_dim=0, v_dim=0, theta=10000.0,
                      eps=1e-5):
    return mla_attention(x, w_qa, q_norm, w_qb, w_kva, kv_norm, w_kvb, w_o,
                         heads=heads, nope=nope, rope_dim=rope_dim,
                         v_dim=v_dim, theta=theta, eps=eps)


@register("_contrib_dropless_moe", num_outputs=3, aux_inputs=(2,))
def _dropless_moe_op(x, gate, bias, w_in, w_out, shared_in, shared_out,
                     k=1, experts_held=None, scaling=1.0):
    """``parallel.moe.dropless_moe_ffn``: (y, load over all experts, pairs
    computed here), the two counters as float32."""
    import jax.numpy as jnp
    from ..parallel.moe import dropless_moe_ffn
    y, stats = dropless_moe_ffn(
        x, {"gate": gate, "bias": bias, "w_in": w_in, "w_out": w_out,
            "shared_in": shared_in, "shared_out": shared_out},
        k, experts_held, scaling)
    return (y, stats["load"].astype(jnp.float32),
            stats["tokens_here"].astype(jnp.float32))
