"""Attention kernel ops: flash attention + interleaved self-attention
matmuls (ref: src/operator/contrib/transformer.cc MKL/interleaved helpers;
the flash kernel is the TPU-native replacement for fused attention).

Cross-checked against plain jnp einsum attention.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.test_utils import assert_almost_equal

RS = np.random.RandomState(5)


def _plain_attention(q, k, v, causal=False, scale=None):
    # q,k,v: (B, T, H, D)
    B, T, H, D = q.shape
    s = scale if scale is not None else 1.0 / np.sqrt(D)
    logits = np.einsum("bthd,bshd->bhts", q, k) * s
    if causal:
        mask = np.tril(np.ones((T, T), bool))
        logits = np.where(mask, logits, -1e30)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhts,bshd->bthd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_plain(causal):
    B, T, H, D = 2, 32, 2, 8
    q = RS.randn(B, T, H, D).astype(np.float32)
    k = RS.randn(B, T, H, D).astype(np.float32)
    v = RS.randn(B, T, H, D).astype(np.float32)
    out = nd.imperative_invoke(
        "_contrib_flash_attention",
        (nd.array(q), nd.array(k), nd.array(v)), {"causal": causal})
    want = _plain_attention(q, k, v, causal=causal)
    assert_almost_equal(out.asnumpy(), want, rtol=2e-3, atol=2e-3)


def test_flash_attention_gradients_match_plain():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import flash_attention as \
        _flash_attention

    B, T, H, D = 1, 16, 2, 4
    q = jnp.asarray(RS.randn(B, T, H, D).astype(np.float32))
    k = jnp.asarray(RS.randn(B, T, H, D).astype(np.float32))
    v = jnp.asarray(RS.randn(B, T, H, D).astype(np.float32))

    def plain(q, k, v):
        s = 1.0 / np.sqrt(D)
        logits = jnp.einsum("bthd,bshd->bhts", q, k) * s
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhts,bshd->bthd", p, v).sum()

    g_plain = jax.grad(plain, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(lambda q, k, v:
                       _flash_attention(q, k, v).sum(),
                       argnums=(0, 1, 2))(q, k, v)
    for gp, gf in zip(g_plain, g_flash):
        assert_almost_equal(np.asarray(gf), np.asarray(gp), rtol=5e-3,
                            atol=5e-3)


def test_interleaved_matmul_selfatt_roundtrip():
    """qk produces (H*B, T, T) attention logits from packed qkv; valatt
    applies attention weights to the packed values — together they form
    standard self-attention (ref: transformer.cc interleaved layout
    (T, B, 3*H*D))."""
    T, B, H, D = 8, 2, 2, 4
    qkv = RS.randn(T, B, 3 * H * D).astype(np.float32)
    att = nd.imperative_invoke(
        "_contrib_interleaved_matmul_selfatt_qk",
        (nd.array(qkv),), {"heads": H}).asnumpy()
    assert att.shape == (B * H, T, T)
    # reference computation from the packed layout
    proj = qkv.reshape(T, B, H, 3, D)
    q, k, v = proj[..., 0, :], proj[..., 1, :], proj[..., 2, :]
    scale = 1.0 / np.sqrt(D)
    want = np.einsum("tbhd,sbhd->bhts", q * scale, k).reshape(B * H, T, T)
    assert_almost_equal(att, want, rtol=1e-4, atol=1e-5)

    weights = np.exp(att) / np.exp(att).sum(-1, keepdims=True)
    out = nd.imperative_invoke(
        "_contrib_interleaved_matmul_selfatt_valatt",
        (nd.array(qkv), nd.array(weights.astype(np.float32))),
        {"heads": H}).asnumpy()
    want_out = np.einsum("bhts,sbhd->tbhd",
                         weights.reshape(B, H, T, T), v)
    assert_almost_equal(out, want_out.reshape(T, B, H * D), rtol=1e-4,
                        atol=1e-5)


# ---------------------------------------------------------------------------
# the interpret-mode decision (ops/pallas_kernels.py _interpret_for): the
# platform decides, alone and the same way every time
# ---------------------------------------------------------------------------

def test_interpret_decision_same_under_trace_and_eager():
    """The first call in a real program comes from inside a jitted step,
    where the operand is a tracer. It must answer as the eager call does
    and leave nothing behind that changes a later answer."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk

    x = jnp.zeros((8, 128), jnp.float32)
    seen = []

    @jax.jit
    def step(a):
        seen.append(pk._interpret_for(a))
        return a + 1

    step(x)
    eager = pk._interpret_for(x)
    assert seen == [eager]
    assert eager is (jax.default_backend() != "tpu")
    # a host array or no operand at all lowers for the default backend
    assert pk._interpret_for(np.zeros(3)) is eager
    assert pk._interpret_for(None) is eager
    step(x + 1)  # cached trace: the decision does not drift
    assert pk._interpret_for(x) is eager


def test_pallas_refusal_raises_and_is_not_retried_interpreted(monkeypatch):
    """A kernel the backend's compiler refuses must raise. Compiled
    Pallas on the CPU backend is such a refusal; it must neither fall
    back to interpret mode nor change what the decision says next."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_kernels as pk

    x = jnp.asarray(RS.randn(16, 128).astype(np.float32))
    g, b = jnp.ones(128), jnp.zeros(128)
    before = pk._interpret_for(x)
    assert before is True  # the suite runs on the CPU
    monkeypatch.setattr(pk, "_interpret_for", lambda _x: False)
    with pytest.raises(ValueError, match="interpret mode"):
        pk.fused_bn_act(x, None, g, b, 1e-5)
    with pytest.raises(ValueError, match="interpret mode"):
        pk.flash_attention(x.reshape(1, 16, 1, 128), x.reshape(1, 16, 1, 128),
                           x.reshape(1, 16, 1, 128))
    monkeypatch.undo()
    assert pk._interpret_for(x) is before
    out, _, _ = pk.fused_bn_act(x, None, g, b, 1e-5)
    assert np.isfinite(np.asarray(out)).all()
