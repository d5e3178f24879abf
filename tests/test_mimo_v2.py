"""The ``mimo_v2`` language model (MiMo-V2.5's) at a toy size on the CPU:
the blocked attention's window-and-sink variant against a plain masked
softmax and, with grouped KV rows, against the KV rows repeated, the Gluon
block against the plain reference of the benchmark
(``benchmark/chip/models/mimo_v2_5.py``), the expert layer without a shared
expert and its share over sixteen ranks, what the other models' attention
and expert layer keep, the benchmark's configuration, its FLOPs and its
kernel's costs, and a rehearsal of the benchmark's cell.

Ops exercised here (tests/op_cases.py COVERED_ELSEWHERE):
_contrib_fused_qkv_attention.
"""
import base64
import hashlib
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon.model_zoo import get_model
from mxnet_tpu.gluon.model_zoo.text import config_keys
from mxnet_tpu.ndarray.ndarray import from_jax
from mxnet_tpu.ops import lm_ops
from mxnet_tpu.ops.pallas_kernels import (_attention_walk, _Band,
                                          _build_blocked_attention,
                                          blocked_attention)
from mxnet_tpu.parallel import moe

ROOT = pathlib.Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmark" / "chip"
REHEARSE = CHIP / "tests" / "rehearse_44"
NAME = "mimo_v2_5"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load(CHIP / "models" / f"{NAME}.py", "mimo_v2_5_reference")
TOY = json.loads((REHEARSE / "configs" / f"{NAME}.json").read_text())
CONFIG = json.loads((CHIP / "configs" / f"{NAME}.json").read_text())
KEYS = config_keys("mimo_v2")
B, T = 2, 256       # two blocks of 128: the toy's window of 16 crosses one


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


# ---------------------------------------------------------------------------
# the window-and-sink kernel, interpret mode

def plain_attention(q, k, v, window=None, sink=None):
    """Softmax of q.k / sqrt(dk) over keys i - window < j <= i (j <= i
    without a window), with ``exp(sink)`` in each row's denominator."""
    t = q.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q, k) / jnp.sqrt(1.0 * q.shape[-1])
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    live = (j <= i) & (j > i - (t if window is None else window))
    s = jnp.where(live, s, -jnp.inf)
    if sink is not None:
        column = jnp.broadcast_to(sink[:, None, None], s.shape[:2] + (1,))
        return jnp.einsum("bqk,bkd->bqd",
                          jax.nn.softmax(jnp.concatenate([column, s], -1),
                                         -1)[..., 1:], v)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("bh,t,dk,dv,window", [
    (3, 256, 32, 32, 1),       # the query's own key alone
    (3, 256, 32, 16, 5),       # smaller than a block: two tiles touched
    (16, 384, 24, 16, 130),    # across one tile edge; 8 heads a grid step
    (3, 256, 16, 32, 128),     # the model's window, dk < dv
    (6, 48, 16, 8, 7),         # a T no block divides: one tile
])
def test_window_and_sink_match_a_masked_softmax(bh, t, dk, dv, window):
    ks = jax.random.split(jax.random.PRNGKey(bh + t + window), 5)
    q, k = (jax.random.normal(ks[i], (bh, t, dk)) for i in (0, 1))
    v, g = (jax.random.normal(ks[i], (bh, t, dv)) for i in (2, 3))
    sink = 1.0 + jax.random.normal(ks[4], (bh,))

    def ours(q, k, v, sink):
        return blocked_attention(q, k, v, window=window, sink=sink)

    def plain(q, k, v, sink):
        return plain_attention(q, k, v, window, sink)

    np.testing.assert_allclose(ours(q, k, v, sink), plain(q, k, v, sink),
                               atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * g), (0, 1, 2, 3))(
        q, k, v, sink)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * g), (0, 1, 2, 3))(
        q, k, v, sink)
    for name, a, b in zip(("q", "k", "v", "sink"), got, want):
        np.testing.assert_allclose(a, b, atol=5e-5, err_msg=name)
    assert float(jnp.abs(got[3]).max()) > 1e-2   # the sink does take mass


@pytest.mark.parametrize("kv_rows,rep,window", [
    (3, 1, 16),     # a KV row a query row
    (8, 2, 16),     # 16 query rows, a grid step 8 of them: four groups a step
    (2, 3, 130),    # 6 query rows, a step two groups of three
    (2, 8, 16),     # a step is one group, as in MiMo's window layers (64 on 8)
    (1, 16, 5),     # a group is two steps
])
def test_grouped_kv_rows_give_what_repeated_rows_give(kv_rows, rep, window):
    """The window's kernels with K and V at their KV rows against the same
    kernels handed each KV row repeated for its group: the output, the
    log-sum-exp and the gradients of q, k, v and the sink."""
    t, dk, dv = 256, 24, 16
    bh = kv_rows * rep
    ks = jax.random.split(jax.random.PRNGKey(rep), 5)
    q = jax.random.normal(ks[0], (bh, t, dk))
    k = jax.random.normal(ks[1], (kv_rows, t, dk))
    v = jax.random.normal(ks[2], (kv_rows, t, dv))
    g = jax.random.normal(ks[3], (bh, t, dv))
    sink = 1.0 + jax.random.normal(ks[4], (bh,))

    def repeated(z):
        return jnp.repeat(z, rep, axis=0)

    fwd, _ = _build_blocked_attention(t, dk, dv, True, dk ** -0.5,
                                      "float32", True, window, True)
    for got, want in zip(fwd(q, k, v, sink),
                         fwd(q, repeated(k), repeated(v), sink)):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    def loss(q, k, v, sink, kv=lambda z: z):
        return jnp.sum(g * blocked_attention(q, kv(k), kv(v), window=window,
                                             sink=sink))

    got = jax.grad(loss, (0, 1, 2, 3))(q, k, v, sink)
    want = jax.grad(loss, (0, 1, 2, 3))(q, k, v, sink, repeated)
    for name, a, b in zip(("q", "k", "v", "sink"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, err_msg=name)


def test_kv_rows_that_do_not_divide_the_query_rows_are_refused():
    q, k = jnp.zeros((6, 256, 16)), jnp.zeros((4, 256, 16))
    with pytest.raises(ValueError):
        blocked_attention(q, k, k)
    with pytest.raises(ValueError):
        blocked_attention(q, k[:3], k[:2])


@pytest.mark.parametrize("window", [256, 1000])
def test_a_window_as_long_as_the_sequence_is_causal_attention(window):
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k = (jax.random.normal(ks[i], (2, 256, 16)) for i in (0, 1))
    v, g = (jax.random.normal(ks[i], (2, 256, 32)) for i in (2, 3))
    np.testing.assert_allclose(blocked_attention(q, k, v, window=window),
                               blocked_attention(q, k, v), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(
        blocked_attention(*a, window=window) * g), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(blocked_attention(*a) * g),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_a_sink_comes_with_a_window_and_a_window_is_causal():
    q = jnp.zeros((2, 256, 16))
    with pytest.raises(ValueError):
        blocked_attention(q, q, q, sink=jnp.zeros(2))
    with pytest.raises(ValueError):
        blocked_attention(q, q, q, causal=False, window=4)


def test_the_window_walk_visits_the_band_alone():
    """Tiles of 128 rows up to a window of 128, two grid steps a block of
    queries at the model's shapes whatever T is, in all three passes."""
    for t in (8192, 32768):
        assert _attention_walk(t, 192, 128, 2, window=128) == _Band(128, 1)
        assert _attention_walk(t, 192, 128, 2, True, 128) == _Band(128, 1)
    # a wider window takes tiles of 256: 129 - 257 keys touch two, 258 three
    assert _attention_walk(32768, 192, 128, 2, window=129) \
        == _Band(256, 1)
    assert _attention_walk(32768, 192, 128, 2, window=258).reach == 2
    assert _attention_walk(32768, 192, 128, 2, window=512) \
        == _Band(256, 2)
    assert _attention_walk(256, 16, 16, 4, window=1).reach == 0
    assert _attention_walk(256, 16, 16, 4, window=10 ** 6) \
        == _Band(256, 0)
    assert _attention_walk(48, 16, 16, 4, window=7) == _Band(48, 0)


def _tpu_text(fn, *args):
    """What ``jax.jit(fn)`` lowers to for a TPU, each kernel's Mosaic module
    printed in place of its serialized body without source locations
    (those name this file's lines). Lowering for a TPU needs none: this
    runs on the CPU."""
    import re
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)) \
        .as_text()

    def printed(body):
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True
        with context:
            return ir.Module.parse(base64.b64decode(body.group(1))) \
                .operation.get_asm(enable_debug_info=False)

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]*)\\22', printed,
                  text)


# sha256 (first 16 digits) of ``_tpu_text`` of the forward and of the
# backward at a KV row a query row, as they were before the kernels took K
# and V at the KV heads: GLM's and Olmo's programs are those. A deliberate
# change to a kernel rewrites them.
TPU_TEXTS = {
    (256, None): ("bf890550fc01ddb5", "faf120f52962dfc5"),
    (256, 128): ("2b517ebc46c19eef", "c1ebaa58b9a20a74"),
    (128, None): ("ed3b6010efaae735", "ebad386fa3707ad5"),
    (128, 128): ("c5621467237e3867", "0fe2399558cfab2a"),
}


@pytest.mark.parametrize("t,dk,dv", [(8192, 256, 256), (8192, 128, 128)])
def test_without_a_window_the_walk_and_the_kernels_are_as_they_were(t, dk,
                                                                     dv):
    """The GLM (MLA, 256), Nemotron and Olmo (128) shapes: the forward's
    block of 512 and stretch of four tiles, one tile a step backward, the
    three kernels' names and, with as many KV rows as query rows, the
    causal and the window's passes as lowered for a TPU (``TPU_TEXTS``)."""
    bf = jnp.bfloat16
    for window in (None, 128):
        fwd, bwd = _build_blocked_attention(
            t, dk, dv, True, dk ** -0.5, "bfloat16", False, window,
            window is not None)
        q, v = (jax.ShapeDtypeStruct((4, t, d), bf) for d in (dk, dv))
        lse = jax.ShapeDtypeStruct((4, t), jnp.float32)
        sinks = (jax.ShapeDtypeStruct((4,), jnp.float32),) if window else ()
        texts = (_tpu_text(fwd, q, q, v, *sinks),
                 _tpu_text(bwd, q, q, v, v, lse, v))
        assert "mosaic" in texts[0] and "mosaic" in texts[1]
        assert tuple(hashlib.sha256(x.encode()).hexdigest()[:16]
                     for x in texts) == TPU_TEXTS[dk, window]
    assert _attention_walk(t, dk, dv, 2) == (512, 2048)
    assert _attention_walk(t, dk, dv, 2, backward=True) == (512, 512)
    q = jnp.zeros((1, 256, 16))

    def names(**kw):
        text = str(jax.make_jaxpr(jax.grad(
            lambda q: jnp.sum(blocked_attention(q, q, q, **kw))))(q))
        return sorted(set(
            w.split("=")[1] for w in text.split() if w.startswith("name=mx_")))

    assert names() == ["mx_attention_dkv", "mx_attention_dq",
                       "mx_attention_fwd"]
    assert names(window=8, sink=jnp.zeros(1)) == [
        "mx_attention_window_dkv", "mx_attention_window_dq",
        "mx_attention_window_fwd"]


# ---------------------------------------------------------------------------
# partial rotary embedding and the attention layer

def test_partial_rope_rotates_the_first_dims_alone():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 40, 24))
    got = lm_ops.rope(x, 1e4, dims=8)
    np.testing.assert_allclose(got[..., :8], lm_ops.rope(x[..., :8], 1e4),
                               atol=1e-6)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_array_equal(lm_ops.rope(x, 1e4, dims=24),
                                  lm_ops.rope(x, 1e4))
    assert rel(lm_ops.rope(x, 1e4, 8), lm_ops.rope(x, 1e7, 8)) > 1e-2


def _layer_inputs(window, seed=0, d=32, heads=4, kv=2, qk=24, v_dim=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (2, T, d))
    w_qkv = 0.2 * jax.random.normal(ks[1], (d, (heads + kv) * qk + kv * v_dim))
    w_o = 0.2 * jax.random.normal(ks[2], (heads * v_dim, d))
    sink = jnp.log(64 + 960 * jax.random.uniform(ks[3], (heads,)))
    attrs = dict(heads=heads, kv_heads=kv, qk_dim=qk, v_dim=v_dim,
                 rope_dim=8, theta=1e4 if window else 1e7, window=window,
                 value_scale=0.707)
    return x, w_qkv, w_o, sink, attrs


@pytest.mark.parametrize("window", [16, None])
def test_attention_layer_matches_the_reference(window):
    """``fused_qkv_attention`` (and its op through the nd namespace) against
    the reference's layer, a window layer with its sink and a full one."""
    x, w_qkv, w_o, sink, attrs = _layer_inputs(window)
    sinks = (sink,) if window else ()
    got = lm_ops.fused_qkv_attention(x, w_qkv, w_o, *sinks, **attrs)
    c = dict(TOY, swa_num_attention_heads=4, num_attention_heads=4,
             swa_num_key_value_heads=2, num_key_value_heads=2,
             sliding_window=window)
    p = {"w_qkv": w_qkv, "w_o": w_o, **({"sink": sink} if window else {})}
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.attention(x[b], p, c, bool(window))
                          for b in range(2)])
    assert rel(got, want) < 1e-5
    op = nd.contrib.fused_qkv_attention(
        *(from_jax(a) for a in (x, w_qkv, w_o) + sinks), **attrs)
    assert rel(op.asnumpy(), want) < 1e-5


def test_attention_layer_names_its_kind():
    x, w_qkv, w_o, sink, attrs = _layer_inputs(16)

    def text(*args, **changed):
        layer = jax.jit(lambda *a: lm_ops.fused_qkv_attention(
            *a, **dict(attrs, **changed)))
        return str(jax.make_jaxpr(layer)(*args)) + layer.lower(
            *args).as_text(debug_info=True)

    window, full = text(x, w_qkv, w_o, sink), text(x, w_qkv, w_o, window=None)
    assert "mx.swa" in window and "mx_attention_window_fwd" in window
    assert "mx.full_attn" in full and "name=mx_attention_fwd" in full
    assert "mx.full_attn" not in window and "mx.swa" not in full


# ---------------------------------------------------------------------------
# the expert layer: no shared expert, and a layer's share over 16 ranks

D, F, ROUTER, K = 16, 8, 32, 4


def _experts(seed=0, shared_ff=0, held=None):
    return moe.init_dropless_moe_params(jax.random.PRNGKey(seed), D, F,
                                        ROUTER, held, shared_ff=shared_ff)


def test_a_layer_without_a_shared_expert_has_none():
    from mxnet_tpu.gluon.model_zoo.text.glm_moe_lite import DroplessMoE
    assert not {"shared_in", "shared_out"} & set(_experts())
    block = DroplessMoE(D, F, ROUTER, tuple(range(4)), K, 1.0, 0.001,
                        shared_width=0)
    assert not any("shared" in name for name in block.collect_params())
    with_one = DroplessMoE(D, F, ROUTER, tuple(range(4)), K, 1.0, 0.001)
    assert sum("shared" in name for name in with_one.collect_params()) == 2


def test_a_shared_expert_adds_what_it_added_before():
    """With a shared expert the layer's output is the routed part plus the
    shared SwiGLU, as it was; without one, the routed part alone."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, D))
    p = _experts(shared_ff=12)
    routed = {k: v for k, v in p.items() if not k.startswith("shared")}
    y, stats = moe.dropless_moe_ffn(x, p, K, None, 1.5)
    alone, same = moe.dropless_moe_ffn(x, routed, K, None, 1.5)
    shared = lm_ops.swiglu_ffn(x, p["shared_in"], p["shared_out"])
    np.testing.assert_allclose(y, alone + shared, atol=1e-5)
    np.testing.assert_array_equal(stats["load"], same["load"])
    names = ("gate", "bias", "w_in", "w_out", "shared_in", "shared_out")
    for held, want in ((routed, alone), (p, y)):
        got = nd.contrib.dropless_moe(
            from_jax(x), *(from_jax(held[k]) for k in names if k in held),
            k=K, scaling=1.5)[0]
        np.testing.assert_allclose(got.asnumpy(), want, atol=1e-5)


def test_sixteen_ranks_add_up_to_the_whole_layer():
    """Expert parallelism over sixteen ranks of two experts each, no shared
    expert: each rank's layer routes over all 32 experts and computes its
    own experts' part; the parts add up to the uncut layer, the program's
    and the reference's alike."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 128, D))
    whole = _experts(seed=3)
    y, _ = moe.dropless_moe_ffn(x, whole, K)
    parts = []
    for rank in range(16):
        held = (2 * rank, 2 * rank + 1)
        mine = dict(whole, w_in=whole["w_in"][jnp.asarray(held)],
                    w_out=whole["w_out"][jnp.asarray(held)])
        parts.append(moe.dropless_moe_ffn(x, mine, K, held)[0])
    np.testing.assert_allclose(sum(parts), y, atol=1e-5)
    c = dict(num_experts_per_tok=K, routed_scaling_factor=None,
             experts_held=list(range(ROUTER)))
    with jax.default_matmul_precision("highest"):
        want = reference.experts(x[0], whole, c)
    assert rel(y[0], want) < 1e-5


# ---------------------------------------------------------------------------
# the whole model

def make_net(seed=5, std=0.1):
    mx.random.seed(seed)
    net = get_model("mimo_v2", **{k: TOY[k] for k in KEYS})
    net.initialize(mx.init.Normal(std))
    rs = np.random.RandomState(seed)
    for p in net.collect_params().values():
        if p.name.endswith("weight") and len(p.shape) == 1:
            p.set_data(mx.nd.array(1 + 0.3 * rs.randn(*p.shape)
                                   .astype(np.float32)))
    return net


def batch(seed=0):
    s = np.random.RandomState(seed).randint(0, TOY["vocab_size"], (B, T + 1))
    return jnp.asarray(s[:, :T], jnp.int32), jnp.asarray(s[:, 1:], jnp.float32)


@pytest.fixture(scope="module")
def compared():
    net = make_net()
    net.hybridize()
    params = [p.data()._data for p in net.collect_params().values()]
    tokens, label = batch()
    loss, (logits,) = jax.jit(lambda p, t, l: reference.score(p, t, l, TOY))(
        params, tokens, label)
    with autograd.pause(), jax.default_matmul_precision("highest"):
        got = net(from_jax(tokens))._data
    return {"net": net, "ref": logits, "loss": loss, "got": got,
            "label": label}


def test_logits_match_reference(compared):
    want = compared["ref"]
    assert want.shape == (B, T, TOY["vocab_size"])
    assert float(jnp.std(want)) > 0.3
    assert rel(compared["got"], want) < 1e-4


def test_the_sinks_and_the_window_change_the_logits(compared):
    """The comparison above can see each: without the sinks, or with full
    attention in the window layers, the reference's logits move by far more
    than the program's distance from them."""
    params = [p.data()._data for p in compared["net"].collect_params()
              .values()]
    tokens, label = batch()
    base = rel(compared["got"], compared["ref"])
    for changed in (dict(sliding_window=T), dict(attention_value_scale=1.0)):
        _, (other,) = reference.score(params, tokens, label,
                                      dict(TOY, **changed))
        assert rel(other, compared["ref"]) > 100 * base, changed
    low = [p - 20.0 if p.shape == (4,) else p for p in params]
    _, (other,) = reference.score(low, tokens, label, TOY)
    assert rel(other, compared["ref"]) > 100 * base


def test_sinks_are_drawn_as_the_model_declares():
    """exp(sink) uniform in [64, 1024], on the host and in the scoring
    path's draw."""
    net = make_net()
    sinks = [p for p in net.collect_params().values()
             if p.name.endswith("sink")]
    assert len(sinks) == TOY["hybrid_layer_pattern"].count(1)
    values = np.exp(np.concatenate([p.data().asnumpy() for p in sinks]))
    assert 64 <= values.min() and values.max() <= 1024
    sys.path.insert(0, str(CHIP / "paths"))
    try:
        path = _load(CHIP / "paths" / "score_causal_lm.py", "score_path")
    finally:
        sys.path.remove(str(CHIP / "paths"))
    drawn = np.exp(path.DRAWS[type(sinks[0].init).__name__](
        jax.random.PRNGKey(0), (4096,), sinks[0].init))
    assert 64 <= drawn.min() and drawn.max() <= 1024.001
    assert abs(drawn.mean() / 544 - 1) < 0.05


def test_causal_prefix_property(compared):
    net = compared["net"]
    tokens, _ = batch()
    other = tokens.at[:, 150:].set((tokens[:, 150:] + 7) % TOY["vocab_size"])
    with autograd.pause():
        a = net(from_jax(tokens))._data
        b = net(from_jax(other))._data
    np.testing.assert_allclose(a[:, :150], b[:, :150], atol=1e-5)
    assert float(jnp.abs(a[:, 150:] - b[:, 150:]).max()) > 1e-3


def test_loss_of_the_logits_is_the_log_softmax_at_the_label(compared):
    logits, label = compared["ref"], compared["label"]
    want = -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                         label.astype(jnp.int32)[..., None],
                                         -1))
    assert abs(float(compared["loss"]) - float(want)) < 1e-5


# ---------------------------------------------------------------------------
# the benchmark's files

def test_configuration_keeps_every_published_width():
    row = dict(hidden_size=4096, intermediate_size=16384,
               moe_intermediate_size=2048, num_attention_heads=64,
               num_key_value_heads=4, head_dim=192, v_head_dim=128,
               swa_num_attention_heads=64, swa_num_key_value_heads=8,
               swa_head_dim=192, swa_v_head_dim=128,
               partial_rotary_factor=0.334, rope_theta=10000000,
               swa_rope_theta=10000, sliding_window=128,
               sliding_window_size=128, attention_chunk_size=128,
               attention_value_scale=0.707, num_experts_per_tok=8,
               n_shared_experts=None, layernorm_epsilon=1e-5,
               max_position_embeddings=1048576, tie_word_embeddings=False,
               add_swa_attention_sink_bias=True,
               add_full_attention_sink_bias=False)
    assert {k: CONFIG[k] for k in row} == row
    assert CONFIG["reduced"] == ["num_hidden_layers", "hybrid_layer_pattern",
                                 "moe_layer_freq", "n_routed_experts",
                                 "vocab_size"]
    published = CONFIG["published"]
    assert published["num_hidden_layers"] == 48
    assert published["hybrid_layer_pattern"].count(1) == 39
    assert CONFIG["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1] \
        == published["hybrid_layer_pattern"][:7]
    assert CONFIG["moe_layer_freq"] == published["moe_layer_freq"][:7]
    assert (CONFIG["n_routed_experts"], CONFIG["router_experts"]) == (16, 256)
    assert CONFIG["experts_held"] == list(range(16))
    assert CONFIG["vocab_size"] * 8 == published["vocab_size"] == 152576
    assert int(192 * CONFIG["partial_rotary_factor"]) == 64
    assert {"deployment", "assumed", "source", "parameters",
            "reference_weights"} <= set(CONFIG)
    assert set(KEYS) <= set(CONFIG)


def test_parameter_count_of_the_cut():
    """3,429,892,096 in the matrices, 61,440 in the norms, 320 sinks (the
    configuration's table), counted from the shapes with nothing
    allocated: 6.86 GB in bf16."""
    net = get_model("mimo_v2", **{k: CONFIG[k] for k in KEYS})
    table, count = CONFIG["parameters"], {}
    for p in net.collect_params().values():
        n = int(np.prod(p.shape))
        kind = "matrices" if len(p.shape) > 1 else "norms" \
            if p.name.endswith("weight") else "sinks" \
            if p.name.endswith("sink") else "router bias and counters"
        count[kind] = count.get(kind, 0) + n
    assert count == {k: table[k] for k in count}
    assert count["matrices"] == 3429892096
    assert count["norms"] == 7 * 2 * 4096 + 4096
    assert count["sinks"] == 5 * 64
    layers = [sum(int(np.prod(p.shape)) for p in layer.collect_params()
                  .values() if len(p.shape) > 1) for layer in net.layers]
    assert layers == [table["layer 0 (dense, full attention)"]] \
        + [table["window expert layer"]] * 4 + [table["full expert layer"],
                                                table["window expert layer"]]
    assert sum(count.values()) == table["total"]
    assert 2 * table["total"] == table["bf16 bytes"]


def test_flops_per_sample():
    """A 32,768-token forward: the two full layers' attention 44.0 TFLOP,
    the window layers' projections 30.9 and band 0.86, the dense SwiGLU
    13.2, the held experts 4.95, the head 5.1."""
    parts = reference._macs_per_token(CONFIG, 32768)
    tflop = {k: 2 * 32768 * v / 1e12 for k, v in parts.items()}
    assert abs(tflop["full_core"] - 44.0) < 0.1
    assert tflop["window_projections"] == pytest.approx(
        5 * 2 * 32768 * 4096 * (64 * 192 + 8 * 320 + 64 * 128) / 1e12)
    assert abs(tflop["window_band"] - 5 * 0.172) < 0.01
    assert abs(tflop["dense_ffn"] - 13.2) < 0.1
    assert abs(tflop["routed_experts"] - 4.95) < 0.01
    assert abs(tflop["head"] - 5.12) < 0.01
    assert abs(reference.flops_per_sample(CONFIG) / 3 / 1e12
               - sum(tflop.values())) < 1e-6


def test_window_kernel_costs_by_hand():
    """The window kernel's least time: the band's 0.172 TFLOP and 1.52 GB a
    layer (q, o at 64 heads, k, v at 8, bf16; the log-sum-exp float32):
    bound by bandwidth, 1.85 ms a layer at 819 GB/s."""
    sys.path.insert(0, str(CHIP / "models"))    # it imports the reference
    try:
        costs = _load(CHIP / "models" / f"{NAME}_kernels.py",
                      "mimo_kernels").kernel_costs
    finally:
        sys.path.remove(str(CHIP / "models"))
    flops, nbytes = costs(CONFIG, 1)["mx_attention_window_fwd"]
    assert nbytes == 5 * 32768 * (2 * (64 * 320 + 8 * 320) + 4 * 64)
    assert abs(flops / 5 / 0.172e12 - 1) < 0.01
    assert abs(nbytes / 5 / 1.52e9 - 1) < 0.01
    assert flops / 197e12 < nbytes / 819e9
    assert costs(CONFIG, 2)["mx_attention_window_fwd"] == (2 * flops,
                                                          2 * nbytes)


def test_scope_readers_on_a_made_up_trace():
    saved = list(sys.path)            # the readers import their siblings
    try:
        run = _load(CHIP / "run.py", "chip_run")
        read = {m: run.load_module("metrics", m).read for m in (
            "mx_swa_ms", "mx_full_attn_ms", "mx_attention_window_roofline")}
        _read_a_made_up_trace(read, run.load_module("models", NAME))
    finally:
        sys.path[:] = saved


def _read_a_made_up_trace(read, model):
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = 5 * 32768 * (2 * 23040 + 256) / 819e9
    scopes = {"jit(program)/mx.swa/dot_general": 0.4,
              "jit(program)/mx.full_attn/dot_general": 0.8}
    out = {"reference": model, "config": CONFIG, "peaks": peaks,
           "traffic": {"batch": 1},
           "trace": {"steps": 4, "seconds_by_scope": scopes,
                     "seconds_by_kind": {"mx_attention_window_fwd":
                                         4 * 4 * least}}}
    assert read["mx_swa_ms"](out) == pytest.approx(100)
    assert read["mx_full_attn_ms"](out) == pytest.approx(200)
    assert read["mx_attention_window_roofline"](out) == pytest.approx(25)
    assert read["mx_attention_window_roofline"](dict(out, trace=dict(
        out["trace"], seconds_by_kind={}))) is None


def test_reference_imports_nothing_of_the_framework():
    for name in (NAME, f"{NAME}_kernels"):
        source = (CHIP / "models" / f"{name}.py").read_text()
        assert "mxnet_tpu" not in source.split('"""', 2)[2]


@pytest.mark.heavy
def test_cell_rehearsal():
    """The new path, traffic and metrics end to end on the CPU at a toy
    size, through ``run.py --rehearse`` from a directory of their own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--rehearse", str(REHEARSE),
         "--workload", "mimo_v2_5_score_s32k_b1",
         "--seed", str(2**31 + 44), "--seconds", "8", "--trace", "1"],
        env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] >= 16
    assert set(line["metrics"]) == {"host_dispatch_ms"}
