"""The ``nemotron_h`` language model (NVIDIA-Nemotron-3-Nano-30B-A3B) at a
toy size on the CPU: the Gluon block against the plain reference of the
benchmark (``benchmark/chip/models/nemotron_3_nano_30b_a3b.py``), the chunked
state-space recurrence against the step-by-step scan, the causal
convolution, grouped-KV attention, the squared-ReLU expert layer and its
share of an expert-parallel layer, and a rehearsal of the benchmark's cell.

Ops exercised here (tests/op_cases.py COVERED_ELSEWHERE):
_contrib_mamba2_mixer, _contrib_gqa_attention.
"""
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
from mxnet_tpu.gluon.model_zoo.text import LMLoss, config_keys
from mxnet_tpu.ndarray.ndarray import from_jax
from mxnet_tpu.ops import lm_ops
from mxnet_tpu.parallel import SPMDTrainer, moe
from mxnet_tpu.parallel.ring_attention import attention

ROOT = pathlib.Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmark" / "chip"
REHEARSE = CHIP / "tests" / "rehearse_33"
NAME = "nemotron_3_nano_30b_a3b"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load(CHIP / "models" / f"{NAME}.py", "nemotron_reference")
TOY = json.loads((REHEARSE / "configs" / f"{NAME}.json").read_text())
CONFIG = json.loads((CHIP / "configs" / f"{NAME}.json").read_text())
KEYS = config_keys("nemotron_h")
B, T = 2, 40        # two and a half chunks of the toy's 16


def make_net(seed=5, std=0.08, **over):
    """The toy model with weights large enough that logits are O(1) (at the
    published 0.02 every logit is near 0 and nothing could be told apart),
    a random selection bias and a random convolution bias."""
    mx.random.seed(seed)
    net = get_model("nemotron_h", **dict({k: TOY[k] for k in KEYS}, **over))
    net.initialize(mx.init.Normal(std))
    for p in net.collect_params().values():
        if p.name.endswith(("moe0_bias", "conv_bias")):
            p.set_data(nd.array(np.random.RandomState(seed).randn(
                *p.shape).astype(np.float32) * 0.3))
    return net


def params_of(net):
    return [p.data()._data for p in net.collect_params().values()]


def batch(seed=0, t=T):
    s = np.random.RandomState(seed).randint(0, TOY["vocab_size"], (B, t + 1))
    return jnp.asarray(s[:, :t], jnp.int32), jnp.asarray(s[:, 1:], jnp.float32)


def functional(net, dtype=None):
    """(params, tokens) -> logits through the Gluon block, parameters
    swapped in as ``SPMDTrainer`` swaps them; floating parameters in
    ``dtype`` when given."""
    objs = list(net.collect_params().values())

    def forward(params, tokens):
        saved = [p._data._data for p in objs]
        for p, a in zip(objs, params):
            p._data._data = a.astype(dtype) if dtype is not None else a
        try:
            with autograd.pause():
                return net(from_jax(tokens))._data.astype(jnp.float32)
        finally:
            for p, a in zip(objs, saved):
                p._data._data = a

    return forward


def system_loss(net, dtype=None):
    forward, loss_fn = functional(net, dtype), LMLoss()

    def loss(params, tokens, label):
        return jnp.mean(loss_fn(from_jax(forward(params, tokens)),
                                from_jax(label))._data)

    return loss


def by_group(grads):
    """{group: one flat vector} of a gradient list in the architecture's
    order."""
    return {g: np.concatenate([np.asarray(a).ravel() for a in arrays])
            for g, arrays in reference.parameter_groups(grads, TOY).items()}


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


# Relative L2 errors against the float32 reference. Float32 differs only in
# the order of its sums and in the chunked algebra (exp of a difference of
# running sums where the reference multiplies step by step): 1e-6 to 1e-5
# here. bfloat16 rounds every product's operands to 8 bits: 3e-3 to 5e-2
# here, and up to 0.2 where a token whose third and fourth scores are close
# changes experts (the routed gradients) or where a sum over every position
# cancels (the gradients of the 3 x heads scalars of a Mamba-2 layer:
# "mamba_decay", "mamba_skip"). TIGHT
# lies between the two, so a silent drop in precision fails it; LOOSE holds
# bfloat16 and a further halving of the mantissa passes it no more.
TIGHT, LOOSE = 2e-4, 8e-2
NOISY = ("router", "experts", "mamba_decay", "mamba_skip")


@pytest.fixture(scope="module")
def compared():
    """Logits, loss and gradients: reference, float32 system, bfloat16
    system, on one seeded net and batch."""
    net = make_net()
    params, (tokens, label) = params_of(net), batch()
    out = {"net": net}
    with jax.default_matmul_precision("highest"):
        out["ref_logits"] = reference.forward(params, tokens, TOY)
        out["ref"] = jax.value_and_grad(reference.loss)(
            params, tokens, label, TOY)
        for name, dtype in (("f32", None), ("bf16", jnp.bfloat16)):
            out[name + "_logits"] = functional(net, dtype)(params, tokens)
            out[name] = jax.value_and_grad(system_loss(net, dtype))(
                params, tokens, label)
    return out


def test_logits_match_reference(compared):
    want = compared["ref_logits"]
    assert want.shape == (B, T, TOY["vocab_size"])
    assert float(jnp.std(want)) > 0.3           # logits that mean something
    assert rel(compared["f32_logits"], want) < TIGHT
    assert TIGHT < rel(compared["bf16_logits"], want) < LOOSE


def test_loss_matches_reference(compared):
    want = float(compared["ref"][0])
    assert abs(float(compared["f32"][0]) - want) < 1e-4 * want
    assert abs(float(compared["bf16"][0]) - want) < 2e-2 * want


@pytest.mark.parametrize("group", reference.GROUPS)
def test_gradients_match_reference(compared, group):
    want = by_group(compared["ref"][1])[group]
    assert np.linalg.norm(want) > 0
    assert rel(by_group(compared["f32"][1])[group], want) < TIGHT
    assert rel(by_group(compared["bf16"][1])[group], want) < \
        (3 * LOOSE if group in NOISY else LOOSE)


def test_every_parameter_has_its_gradient(compared):
    """Parameter by parameter, not by group: each trainable array's
    gradient against the reference's, and none for bias and counters."""
    net = compared["net"]
    for p, got, want in zip(net.collect_params().values(),
                            compared["f32"][1], compared["ref"][1]):
        if p.grad_req == "null":
            assert not np.any(np.asarray(got)), p.name
        else:
            assert np.linalg.norm(want) > 0, p.name
            assert rel(got, want) < TIGHT, p.name


def test_causal_prefix_property():
    """Logits at positions < n do not depend on tokens from n on: through
    the convolution, the carried state and the attention mask alike."""
    net = make_net()
    forward = functional(net)
    tokens, _ = batch()
    other = tokens.at[:, 21:].set((tokens[:, 21:] + 7) % TOY["vocab_size"])
    a, b = forward(params_of(net), tokens), forward(params_of(net), other)
    np.testing.assert_allclose(a[:, :21], b[:, :21], atol=1e-5)
    assert float(jnp.abs(a[:, 21:] - b[:, 21:]).max()) > 1e-3


def test_remat_per_layer_same_loss_and_gradients_and_scopes():
    """``remat=True`` recomputes each layer in the backward pass and
    changes nothing else; the attention layer keeps its kernel's output; the
    program names its parts."""
    plain, remat = make_net(), make_net(remat=True)
    tokens, label = batch()
    a = jax.value_and_grad(system_loss(plain))(params_of(plain), tokens, label)
    b = jax.value_and_grad(system_loss(remat))(params_of(remat), tokens, label)
    assert abs(float(a[0]) - float(b[0])) < 1e-6
    for g, h in zip(a[1], b[1]):
        np.testing.assert_allclose(g, h, rtol=1e-5, atol=1e-6)
    with autograd.train_mode():
        remat(from_jax(tokens))
    k = TOY["num_experts_per_tok"]
    assert remat.layers[1].mixer.load.data().asnumpy().sum() == B * T * k
    grad = jax.grad(system_loss(remat))
    jaxpr = str(jax.make_jaxpr(grad)(params_of(remat), tokens, label))
    assert jaxpr.count("name=mx_attention_fwd") == 1
    assert jaxpr.count("name=mx_attention_dq") == 1
    hlo = jax.jit(grad).lower(params_of(remat), tokens, label).as_text(
        debug_info=True)
    for scope in ("mx.mamba2", "mx.ssd", "mx.gqa", "mx.moe.route",
                  "mx.moe.experts", "mx.lm_head"):
        assert scope in hlo, scope


def test_trains_through_spmd_trainer_in_bfloat16():
    net = make_net(std=0.05, remat=True)
    trainer = SPMDTrainer(net, LMLoss(), optimizer="adam",
                          optimizer_params={"learning_rate": 1e-3},
                          dtype=jnp.bfloat16)
    tokens, label = batch()
    layer = net.layers[1].mixer
    start = layer.bias.data().asnumpy().copy()
    losses = [float(trainer.step(tokens, label)) for _ in range(4)]
    assert losses[-1] < losses[0]
    load = layer.load.data().asnumpy()
    assert load.sum() == B * T * TOY["num_experts_per_tok"]
    assert int(layer.tokens_here.data().asnumpy()[0]) == \
        load[list(TOY["experts_held"])].sum()
    assert np.any(layer.bias.data().asnumpy() != start)
    assert all(not p.name.endswith("moe0_bias")
               for p in trainer._trainable)


def test_initialisation_of_the_state_space_scalars():
    mx.random.seed(11)
    net = get_model("nemotron_h", **dict({k: TOY[k] for k in KEYS},
                                         mamba_num_heads=64, n_groups=8,
                                         hybrid_override_pattern="M",
                                         num_hidden_layers=1))
    net.initialize(mx.init.Normal(0.02))
    m = net.layers[0].mixer
    a_log, d = m.a_log.data().asnumpy(), m.d.data().asnumpy()
    step = np.log1p(np.exp(m.dt_bias.data().asnumpy()))
    assert 0 <= a_log.min() and a_log.max() <= np.log(16) and a_log.std() > 0.3
    assert 0.001 * 0.99 <= step.min() and step.max() <= 0.1 * 1.01
    assert step.max() / step.min() > 5                       # log-uniform
    assert np.all(d == 1) and not np.any(m.conv_bias.data().asnumpy())
    assert abs(m.w_in.data().asnumpy().std() / 0.02 - 1) < 0.1


# ---------------------------------------------------------------------------
# the state-space recurrence

def _scan_inputs(t, h, p, g, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        u=jax.random.normal(ks[0], (2, t, h, p)),
        dt=jax.random.normal(ks[1], (2, t, h)) - 1.0,       # before softplus
        a_log=jnp.log(jax.random.uniform(ks[2], (h,), minval=1., maxval=16.)),
        b=jax.random.normal(ks[3], (2, t, g, n)),
        c=jax.random.normal(ks[4], (2, t, g, n)),
        d=jax.random.normal(ks[5], (h,)))


def _chunked(x, chunk, **kw):
    y = lm_ops.ssd_chunked(x["u"], jax.nn.softplus(x["dt"]),
                           -jnp.exp(x["a_log"]), x["b"], x["c"], chunk, **kw)
    return y + x["d"][:, None] * x["u"]


def _stepwise(x):
    y = jnp.stack([reference.recurrence(
        x["u"][i], jax.nn.softplus(x["dt"][i]), -jnp.exp(x["a_log"]),
        x["b"][i], x["c"][i]) for i in range(x["u"].shape[0])])
    return y + x["d"][:, None] * x["u"]


SCANS = {  # (T, heads, head size, groups, state, chunk)
    "one_group": (64, 4, 8, 1, 16, 16),
    "several_groups": (64, 6, 8, 3, 16, 16),
    "ragged_T": (50, 4, 8, 2, 16, 16),      # T is not a multiple of the chunk
    "one_chunk": (24, 4, 8, 2, 16, 128),    # T under the chunk
    "published_chunk": (256, 8, 16, 8, 32, 128),
}


@pytest.mark.parametrize("case", SCANS)
def test_chunked_scan_matches_the_step_by_step_scan(case):
    t, h, p, g, n, chunk = SCANS[case]
    x = _scan_inputs(t, h, p, g, n)
    with jax.default_matmul_precision("highest"):
        got, want = _chunked(x, chunk), _stepwise(x)
    assert got.shape == (2, t, h, p) and got.dtype == jnp.float32
    assert float(jnp.std(want)) > 0.1
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("wrt", ["u", "b", "c", "dt", "a_log", "d"])
@pytest.mark.parametrize("case", ["several_groups", "ragged_T"])
def test_chunked_scan_gradients(case, wrt):
    t, h, p, g, n, chunk = SCANS[case]
    x = _scan_inputs(t, h, p, g, n, seed=1)
    cot = jax.random.normal(jax.random.PRNGKey(9), (2, t, h, p))

    def through(f):
        return jax.grad(lambda v: jnp.sum(f(dict(x, **{wrt: v})) * cot))(
            x[wrt])

    with jax.default_matmul_precision("highest"):
        got = through(lambda x: _chunked(x, chunk))
        want = through(_stepwise)
    assert np.linalg.norm(want) > 0
    assert rel(got, want) < 2e-5


def test_scan_decay_in_bfloat16_is_told_from_float32():
    """What ``tools/chip_check_lm.py`` leans on: with the running sums of
    the decay kept in bfloat16 the result leaves the band float32 stays in
    by two orders of magnitude."""
    t, h, p, g, n, chunk = SCANS["published_chunk"]
    x = _scan_inputs(t, h, p, g, n, seed=2)
    with jax.default_matmul_precision("highest"):
        want = _stepwise(x)
        exact = rel(_chunked(x, chunk), want)
        low = rel(_chunked(x, chunk, decay_dtype=jnp.bfloat16), want)
    assert exact < 1e-5 and low > 1e-3


def test_state_is_kept_once_a_chunk_not_once_a_step():
    """No (T, T) array and no state for every step, forward or backward: the
    largest array of the differentiated scan is of the order of T x chunk x
    heads or chunks x heads x head size x state."""
    t, h, p, g, n, chunk = 512, 4, 8, 2, 16, 32
    x = _scan_inputs(t, h, p, g, n)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda u: jnp.sum(_chunked(dict(x, u=u), chunk))))(x["u"])
    largest = max(int(np.prod(v.aval.shape)) for eqn in jaxpr.eqns
                  for v in eqn.outvars if hasattr(v.aval, "shape"))
    per_step_state, square = 2 * t * h * p * n, 2 * h * t * t
    per_chunk = 2 * max(t * chunk * h, (t // chunk) * h * p * n, t * h * p)
    assert largest <= per_chunk < min(per_step_state, square)


def test_causal_convolution_matches_a_shifted_sum():
    rs = np.random.RandomState(0)
    x = rs.randn(2, 19, 6).astype(np.float32)
    w, b = rs.randn(6, 4).astype(np.float32), rs.randn(6).astype(np.float32)
    want = np.zeros_like(x) + b
    for t in range(19):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += w[:, j] * x[:, t - 3 + j]
    got = lm_ops.causal_conv1d(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        jnp.stack([reference.causal_conv(jnp.asarray(x[i]), w, b)
                   for i in range(2)]), want, rtol=1e-5, atol=1e-5)
    low = lm_ops.causal_conv1d(jnp.asarray(x, jnp.bfloat16), jnp.asarray(w),
                               jnp.asarray(b))
    assert low.dtype == jnp.bfloat16 and rel(low, want) < 1e-2


def test_gated_grouped_norm_normalises_within_each_group():
    rs = np.random.RandomState(1)
    y, z = rs.randn(3, 5, 24).astype(np.float32), rs.randn(3, 5, 24)
    w = rs.rand(24).astype(np.float32) + 0.5
    gated = y * (z / (1 + np.exp(-z)))
    grouped = gated.reshape(3, 5, 4, 6)
    want = (grouped / np.sqrt((grouped ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(3, 5, 24) * w
    got = lm_ops.gated_group_rms_norm(jnp.asarray(y), jnp.asarray(
        z, jnp.float32), jnp.asarray(w), 4, 1e-5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# grouped-KV attention

@pytest.mark.parametrize("heads,kv", [(4, 2), (8, 1), (3, 3)])
def test_grouped_kv_attention_matches_repeated_kv_heads(heads, kv):
    d, hidden, t = 16, 24, 48
    ks = jax.random.split(jax.random.PRNGKey(heads), 6)
    x = jax.random.normal(ks[0], (2, t, hidden))
    w_q, w_o = (jax.random.normal(ks[1], (hidden, heads * d)) * 0.3,
                jax.random.normal(ks[2], (heads * d, hidden)) * 0.3)
    w_k, w_v = (jax.random.normal(ks[i], (hidden, kv * d)) * 0.3
                for i in (3, 4))
    cot = jax.random.normal(ks[5], (2, t, hidden))

    def ours(x, w_q, w_k, w_v, w_o):
        return jnp.sum(cot * lm_ops.gqa_attention(
            x, w_q, w_k, w_v, w_o, heads=heads, kv_heads=kv, head_dim=d))

    def repeated(x, w_q, w_k, w_v, w_o):
        """Plain multi-head attention whose key and value projections hold
        each KV head's columns ``heads / kv`` times."""
        def wide(w):
            return jnp.repeat(w.reshape(hidden, kv, d), heads // kv,
                              axis=1).reshape(hidden, heads * d)
        q, k, v = ((x @ w).reshape(2, t, heads, d)
                   for w in (w_q, wide(w_k), wide(w_v)))
        o = attention(q, k, v, causal=True, scale=d ** -0.5)
        return jnp.sum(cot * (o.reshape(2, t, heads * d) @ w_o))

    args = (x, w_q, w_k, w_v, w_o)
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(ours, range(5))(*args)
        want = jax.value_and_grad(repeated, range(5))(*args)
    assert abs(float(got[0]) - float(want[0])) < 1e-3 * abs(float(want[0]))
    for a, b in zip(got[1], want[1]):
        assert a.shape == b.shape and rel(a, b) < 2e-5


@pytest.mark.parametrize("kv_rows,rep", [(3, 1), (2, 2), (2, 8), (1, 16)])
def test_causal_kernels_read_a_kv_row_for_its_whole_group(kv_rows, rep):
    """The causal kernels with K and V at their KV rows against the same
    kernels handed each KV row repeated for its group: the output, the
    log-sum-exp and the gradients of q, k and v, over two blocks of 512
    (the stretch that holds the diagonal, and the backward's steps above
    it, which copy nothing)."""
    from mxnet_tpu.ops.pallas_kernels import (_build_blocked_attention,
                                              blocked_attention)
    t, dk, dv = 1024, 16, 8
    bh = kv_rows * rep
    ks = jax.random.split(jax.random.PRNGKey(rep), 4)
    q = jax.random.normal(ks[0], (bh, t, dk))
    k = jax.random.normal(ks[1], (kv_rows, t, dk))
    v = jax.random.normal(ks[2], (kv_rows, t, dv))
    g = jax.random.normal(ks[3], (bh, t, dv))

    def repeated(z):
        return jnp.repeat(z, rep, axis=0)

    fwd, _ = _build_blocked_attention(t, dk, dv, True, dk ** -0.5,
                                      "float32", True)
    for got, want in zip(fwd(q, k, v), fwd(q, repeated(k), repeated(v))):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    def loss(q, k, v, kv=lambda z: z):
        return jnp.sum(g * blocked_attention(q, kv(k), kv(v)))

    got = jax.grad(loss, (0, 1, 2))(q, k, v)
    want = jax.grad(loss, (0, 1, 2))(q, k, v, repeated)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, err_msg=name)


# ---------------------------------------------------------------------------
# the expert layer with squared-ReLU experts

D, F, FS, E, K = 32, 24, 40, 128, 6


def _layer_params(held=None, seed=1, experts=E, d=D):
    p = moe.init_dropless_moe_params(jax.random.PRNGKey(seed), d, F, experts,
                                     held, activation="relu2", shared_ff=FS)
    p = {k: v * 8 if k != "bias" else v for k, v in p.items()}
    p["bias"] = jax.random.normal(jax.random.PRNGKey(seed + 1),
                                  (experts,)) * 0.3
    return p


def _plain_layer(x, p, held, k=K):
    config = {"experts_held": held, "num_experts_per_tok": k,
              "routed_scaling_factor": 2.5}
    return reference.moe(x.reshape(-1, x.shape[-1]), p, config).reshape(
        x.shape)


def _ours(x, p, held, k=K):
    return moe.dropless_moe_ffn(x, p, k, held, 2.5, tile=8,
                                activation="relu2")


def test_relu2_expert_shapes_and_shared_width():
    p = _layer_params((0, 1, 2))
    assert p["w_in"].shape == (3, D, F) and p["w_out"].shape == (3, F, D)
    assert p["shared_in"].shape == (D, FS)
    assert p["shared_out"].shape == (FS, D)
    gated = moe.init_dropless_moe_params(jax.random.PRNGKey(0), D, F, 4)
    assert gated["w_in"].shape == (4, D, 2 * F)         # SwiGLU as before
    assert gated["shared_in"].shape == (D, 2 * F)


def test_sixteen_shares_sum_to_the_uncut_layer():
    """The share test: sixteen chips hold two experts each of thirty-two.
    Their routed parts, with the shared expert (which every chip computes
    alike) counted once, add up to what the uncut reference gives for the
    whole layer."""
    experts = 32
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, D))
    whole = _layer_params(experts=experts)
    want = _plain_layer(x, whole, tuple(range(experts)))
    shared = reference.relu2_ffn(x, whole["shared_in"], whole["shared_out"])
    total, pairs = shared, 0
    for chip in range(16):
        held = (2 * chip, 2 * chip + 1)
        part = dict(whole, w_in=whole["w_in"][2 * chip:2 * chip + 2],
                    w_out=whole["w_out"][2 * chip:2 * chip + 2])
        y, stats = _ours(x, part, held)
        np.testing.assert_allclose(          # each share against its own
            y, _plain_layer(x, part, held), rtol=1e-5, atol=1e-4)
        total = total + (y - shared)
        pairs += int(stats["tokens_here"])
        assert int(stats["load"].sum()) == 80 * K  # routed over all of them
    assert pairs == 80 * K
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-3)
    y, _ = _ours(x, whole, None)                   # all held: the whole layer
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-4)


def test_eight_of_128_held_leaves_out_exactly_the_absent_terms():
    """The cell's share: the router is 128 wide and chooses six over all of
    them; the result is the shared expert plus the terms of experts 0-7 and
    nothing of the other 120."""
    held = tuple(range(8))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, D))
    p = _layer_params(held)
    p["bias"] = p["bias"].at[jnp.arange(8)].add(0.5)   # some tokens do come
    y, stats = _ours(x, p, held)
    xf = x.reshape(-1, D)
    s = jax.nn.sigmoid(xf @ p["gate"])
    _, chosen = jax.lax.top_k(s + p["bias"], K)
    w = jnp.take_along_axis(s, chosen, -1)
    w = 2.5 * w / jnp.sum(w, -1, keepdims=True)
    want = reference.relu2_ffn(xf, p["shared_in"], p["shared_out"])
    here = 0
    for e in range(E):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), -1, keepdims=True)
        if e in held:
            want = want + w_e * reference.relu2_ffn(
                xf, p["w_in"][e], p["w_out"][e])
            here += int(jnp.sum(chosen == e))
    assert 0 < here < 128 * K
    assert int(stats["tokens_here"]) == here
    assert int(stats["load"].sum()) == 128 * K and stats["load"].shape == (E,)
    np.testing.assert_allclose(y.reshape(-1, D), want, rtol=1e-5, atol=1e-4)


# As tests/test_glm_moe_lite.py's ROUTED_CASES, for squared-ReLU experts under
# a 16-wide router choosing four: held experts, selection bias by expert,
# tokens a sequence, tile.
ROUTED_CASES = {
    "all_held": (None, {}, 40, 8, D),
    "subset": ((0, 1, 2, 3), {}, 40, 8, D),
    "ragged_tiles": ((5, 9, 12), {}, 37, 16, D),
    "all_to_one_held_expert": ((2, 3), {3: 5.0}, 40, 8, D),
    "none_chosen": ((5, 9), {5: -5.0, 9: -5.0}, 40, 8, D),
    "kernel_combine": ((0, 1, 2, 3), {}, 37, 16, 128),
}


@pytest.mark.parametrize("case", ROUTED_CASES)
def test_relu2_routed_loop_is_the_plain_masked_sum(case):
    """The loop over the tiles in use and its backward with ``relu2``
    experts: output, counters and every gradient against the plain masked
    sum for everything held, a share, rows that fill no whole tile, every
    token on one expert, and no token here."""
    held, bias, t, tile, d = ROUTED_CASES[case]
    experts, k = 16, 4
    ids = held or tuple(range(experts))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, t, d))
    p = _layer_params(held, experts=experts, d=d)
    for e, b in bias.items():
        p["bias"] = p["bias"].at[e].set(b)

    def ours(x, p):
        y, stats = moe.dropless_moe_ffn(x, p, k, held, 2.5, tile=tile,
                                        activation="relu2")
        return jnp.sum(y ** 2), (y, stats)

    def plain(x, p):
        y = _plain_layer(x, p, ids, k)
        return jnp.sum(y ** 2), y

    (_, (y, stats)), got = jax.value_and_grad(ours, (0, 1), has_aux=True)(x, p)
    (_, want_y), want = jax.value_and_grad(plain, (0, 1), has_aux=True)(x, p)
    # rows of whole lanes: the kernel adds them, in the forward and the
    # backward loop; else XLA's scatter, in neither
    program = str(jax.make_jaxpr(jax.value_and_grad(
        ours, (0, 1), has_aux=True))(x, p))
    assert program.count("mx_moe_combine") == (2 if d % 128 == 0 else 0)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-4)
    here, tiles = int(stats["tokens_here"]), int(stats["tiles_run"])
    assert here == int(stats["load"][np.asarray(ids)].sum())
    assert 0 <= tiles * tile - here < len(ids) * tile
    assert rel(got[0], want[0]) < 1e-5
    for name in p:
        if name == "bias":
            assert not np.any(np.asarray(got[1][name]))
        else:
            assert rel(got[1][name], want[1][name]) < 1e-5, name
    if case == "all_to_one_held_expert":
        assert int(stats["load"][3]) == 2 * t <= here
    if case == "none_chosen":
        assert here == tiles == 0
        np.testing.assert_allclose(
            y, reference.relu2_ffn(x, p["shared_in"], p["shared_out"]),
            rtol=1e-5, atol=1e-4)
        assert not np.any(np.asarray(got[1]["w_in"]))
        assert not np.any(np.asarray(got[1]["w_out"]))
        assert not np.any(np.asarray(got[1]["gate"]))


@pytest.mark.parametrize("held", [None, (0, 1, 2, 3), (5, 9)])
def test_relu2_expert_layer_gradients(held):
    experts = 16
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 40, D))
    p = _layer_params(held, experts=experts)
    ids = held or tuple(range(experts))

    def ours(x, p):
        return jnp.sum(_ours(x, p, held, 4)[0] ** 2)

    def plain(x, p):
        return jnp.sum(_plain_layer(x, p, ids, 4) ** 2)

    got, want = jax.grad(ours, (0, 1))(x, p), jax.grad(plain, (0, 1))(x, p)
    assert rel(got[0], want[0]) < 1e-5
    for name in p:
        if name == "bias":
            assert not np.any(np.asarray(got[1][name]))
        else:
            assert rel(got[1][name], want[1][name]) < 1e-5, name


# ---------------------------------------------------------------------------
# the benchmark's files

def test_configuration_keeps_every_published_width():
    widths = dict(hidden_size=2688, mamba_num_heads=64, mamba_head_dim=64,
                  n_groups=8, ssm_state_size=128, conv_kernel=4,
                  chunk_size=128, expand=2, num_attention_heads=32,
                  num_key_value_heads=2, head_dim=128,
                  moe_intermediate_size=1856, intermediate_size=1856,
                  moe_shared_expert_intermediate_size=3712,
                  router_experts=128, num_experts_per_tok=6,
                  routed_scaling_factor=2.5, n_shared_experts=1,
                  layer_norm_epsilon=1e-5, time_step_min=0.001,
                  time_step_max=0.1, time_step_floor=1e-4)
    assert {k: CONFIG[k] for k in widths} == widths
    assert CONFIG["reduced"] == ["num_hidden_layers",
                                 "hybrid_override_pattern",
                                 "n_routed_experts", "vocab_size"]
    published = CONFIG["published"]
    assert published["num_hidden_layers"] == 52 == len(
        published["hybrid_override_pattern"])
    assert published["n_routed_experts"] == 128
    assert CONFIG["vocab_size"] * 8 == published["vocab_size"] == 131072
    assert CONFIG["hybrid_override_pattern"] == "MEMEM*EME" == \
        published["hybrid_override_pattern"][:CONFIG["num_hidden_layers"]]
    assert [published["hybrid_override_pattern"].count(k) for k in "ME*"] \
        == [23, 23, 6]
    assert CONFIG["n_routed_experts"] == len(CONFIG["experts_held"]) == 8
    assert {"deployment", "assumed", "source"} <= set(CONFIG)
    assert set(KEYS) <= set(CONFIG)


def test_flops_per_sample():
    parts = reference._macs_per_token(CONFIG, 8192)
    total = sum(parts.values())
    assert abs(2 * total / 0.7152e9 - 1) < 2e-3          # GFLOP a token
    assert abs(reference.flops_per_sample(CONFIG) / 17.576e12 - 1) < 2e-3
    share = {k: v / total for k, v in parts.items()}
    mamba = share["mamba_projections"] + share["mamba_scan"]
    assert 0.44 < mamba < 0.46 and 0.01 < share["mamba_scan"] < 0.025
    assert 0.26 < share["expert_layers"] < 0.28
    attn = share["attention_projections"] + share["attention_core"]
    assert 0.15 < attn < 0.17 and 0.12 < share["head"] < 0.13
    assert not hasattr(reference, "kernel_costs")     # no new Pallas kernel


def test_parameter_count_of_the_share():
    """667.0M parameters that train (ISSUE 33's table), counted from the
    shapes with nothing allocated."""
    net = get_model("nemotron_h", **{k: CONFIG[k] for k in KEYS})
    count = {}
    for layer in net.layers:
        n = sum(int(np.prod(p.shape)) for p in
                layer.mixer.collect_params().values() if p.grad_req != "null")
        count.setdefault(layer.kind, set()).add(n)
    assert count["M"] == {27697152 + 11010048 + 6144 * 5 + 4096 + 3 * 64}
    assert count["*"] == {2 * 2688 * 4096 + 2 * 2688 * 256}
    assert count["E"] == {8 * 2 * 2688 * 1856 + 2 * 2688 * 3712 + 2688 * 128}
    n = sum(int(np.prod(p.shape)) for p in net.collect_params().values()
            if p.grad_req != "null")
    assert n == 666962944


def test_reference_imports_nothing_of_the_framework():
    source = (CHIP / "models" / f"{NAME}.py").read_text()
    assert "mxnet_tpu" not in source.split('"""', 2)[2]


@pytest.mark.heavy
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(trace):
    """The new path, traffic and metrics end to end on the CPU at a toy
    size, through ``run.py --rehearse`` from a directory of their own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--rehearse", str(REHEARSE),
         "--workload", "nemotron_3_nano_train_spmd_s8k",
         "--seed", str(2**31 + 33), "--seconds", "8", "--trace", str(trace)],
        env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] >= 4
    assert line["checks"]["loss_fell"] and line["checks"]["reference"]
    assert set(line["metrics"]) == (
        {"host_dispatch_ms", "dispatches_per_step",
         "moe_load_max_over_mean.nemotron", "moe_tokens_here_share"}
        if trace else {"samples_per_s", "setup_s"})
    if trace:
        assert line["metrics"]["dispatches_per_step"]["value"] == 2
        assert 1.0 <= line["metrics"]["moe_load_max_over_mean.nemotron"][
            "value"] < 16
        assert 0.3 < line["metrics"]["moe_tokens_here_share"]["value"] < 3
