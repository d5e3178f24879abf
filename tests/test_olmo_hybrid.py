"""The ``olmo_hybrid`` language model (Olmo-Hybrid-7B) at a toy size on the
CPU: the Gluon block against the plain reference of the benchmark
(``benchmark/chip/models/olmo_hybrid_7b.py``), the chunked delta rule
against the step-by-step scan, the Gated DeltaNet mixer, attention with
QK-norm, the benchmark's configuration and its FLOPs, and a rehearsal of the
benchmark's cell.

Ops exercised here (tests/op_cases.py COVERED_ELSEWHERE):
_contrib_gated_deltanet_mixer, _contrib_qk_norm_attention.
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
from mxnet_tpu import autograd
from mxnet_tpu.gluon.model_zoo import get_model
from mxnet_tpu.gluon.model_zoo.text import config_keys
from mxnet_tpu.ndarray.ndarray import from_jax
from mxnet_tpu.ops import lm_ops

ROOT = pathlib.Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmark" / "chip"
REHEARSE = CHIP / "tests" / "rehearse_42"
NAME = "olmo_hybrid_7b"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load(CHIP / "models" / f"{NAME}.py", "olmo_hybrid_reference")
TOY = json.loads((REHEARSE / "configs" / f"{NAME}.json").read_text())
CONFIG = json.loads((CHIP / "configs" / f"{NAME}.json").read_text())
KEYS = config_keys("olmo_hybrid")
B, T = 2, 40        # two and a half chunks of the toy's 16


def make_net(seed=5, std=0.08, **over):
    """The toy model with weights large enough that logits are O(1), and
    norms that are not all ones."""
    mx.random.seed(seed)
    net = get_model("olmo_hybrid", **dict({k: TOY[k] for k in KEYS}, **over))
    net.initialize(mx.init.Normal(std))
    rs = np.random.RandomState(seed)
    for p in net.collect_params().values():
        if p.name.endswith("norm") or p.name.endswith("weight") \
                and len(p.shape) == 1:
            p.set_data(mx.nd.array(1 + 0.3 * rs.randn(*p.shape)
                                   .astype(np.float32)))
    return net


def params_of(net):
    return [p.data()._data for p in net.collect_params().values()]


def batch(seed=0, t=T):
    s = np.random.RandomState(seed).randint(0, TOY["vocab_size"], (B, t + 1))
    return jnp.asarray(s[:, :t], jnp.int32), jnp.asarray(s[:, 1:], jnp.float32)


def functional(net, dtype=None):
    """(params, tokens) -> logits through the Gluon block, parameters
    swapped in; floating parameters in ``dtype`` when given."""
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


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


# Relative L2 errors against the float32 reference. Float32 differs only in
# the order of its sums and in the chunked algebra (a triangular solve and
# exp of differences of running sums where the reference steps): 1e-7 to
# 3e-6 here. bfloat16 rounds every product's operands to 8 bits: 1e-2 to
# 4e-2 here. TIGHT lies between the two, so a silent drop in precision fails
# it; LOOSE holds bfloat16 with room.
TIGHT, LOOSE = 1e-4, 0.1


# ---------------------------------------------------------------------------
# the gated delta rule

def _rule_inputs(t, h, dk, dv, seed=0, beta_max=2.0, log_decay=-2.0):
    """Inputs as the mixer makes them: q and k L2-normed (q scaled), beta
    in (0, beta_max), the decay's log around ``-exp(log_decay)``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = lm_ops.l2_norm(jax.random.normal(ks[0], (2, t, h, dk))) * dk ** -0.5
    k = lm_ops.l2_norm(jax.random.normal(ks[1], (2, t, h, dk)))
    v = jax.random.normal(ks[2], (2, t, h, dv))
    g = -jnp.exp(jax.random.normal(ks[3], (2, t, h)) + log_decay)
    beta = beta_max * jax.nn.sigmoid(jax.random.normal(ks[4], (2, t, h)))
    return dict(q=q, k=k, v=v, g=g, beta=beta)


# the rule's forward: the kernel (what the model runs), and the XLA
# formulation its backward is differentiated through
PATHS = {"kernel": lm_ops.gated_delta_rule_chunked,
         "xla": lm_ops._delta_rule_xla}


def _chunked(x, chunk, path="kernel", **kw):
    """The rule over the (B, T, H) inputs, handed to it heads first."""
    first = (x[n].swapaxes(1, 2) for n in ("q", "k", "v", "g", "beta"))
    return PATHS[path](*first, chunk, **kw).swapaxes(1, 2)


def _eqns(jaxpr):
    """The equations of a jaxpr and of those it holds."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _kernel_calls(jaxpr, name):
    """The Pallas calls named ``name`` in a jaxpr and in those it holds (the
    printed jaxpr names a kernel shared by several calls once)."""
    return sum(eqn.primitive.name == "pallas_call"
               and eqn.params.get("name") == name for eqn in _eqns(jaxpr))


@jax.jit
def _stepwise(x):
    return jax.vmap(reference.delta_rule)(
        *(x[n] for n in ("q", "k", "v", "g", "beta")))


RULES = {  # (T, heads, dk, dv, chunk, input options)
    "chunk16": (64, 3, 16, 24, 16, {}),
    "chunk32": (96, 3, 16, 24, 32, {}),
    "chunk64": (128, 2, 32, 48, 64, {}),
    "ragged_T": (50, 3, 16, 24, 16, {}),       # T is not a multiple
    "one_short_chunk": (24, 2, 16, 24, 64, {}),
    "beta_above_1": (64, 3, 16, 24, 16, {"beta_max": 2.0, "seed": 3}),
    "decay_near_0": (64, 3, 16, 24, 16, {"log_decay": 2.0}),   # alpha ~ 0
    "decay_near_1": (64, 3, 16, 24, 16, {"log_decay": -7.0}),  # alpha ~ 1
}


@pytest.mark.parametrize("case, path", [
    pytest.param(case, path, id=case if path == "kernel" else f"{case}-{path}")
    for path in PATHS for case in RULES])
def test_chunked_rule_matches_the_step_by_step_scan(case, path):
    t, h, dk, dv, chunk, opts = RULES[case]
    x = _rule_inputs(t, h, dk, dv, **opts)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(_chunked, static_argnums=(1, 2))(x, chunk, path)
        want = _stepwise(x)
    assert got.shape == (2, t, h, dv) and got.dtype == jnp.float32
    assert float(jnp.std(want)) > 0.05
    assert rel(got, want) < 1e-5
    if case == "beta_above_1":
        assert float(x["beta"].max()) > 1.5            # eigenvalues below 0
    alpha = jnp.exp(x["g"])
    if case == "decay_near_0":
        assert float(jnp.median(alpha)) < 1e-2
    if case == "decay_near_1":
        assert float(jnp.median(alpha)) > 0.999


@pytest.mark.parametrize("wrt", ["q", "k", "v", "g", "beta"])
@pytest.mark.parametrize("case", ["chunk16", "ragged_T"])
def test_chunked_rule_gradients(case, wrt):
    t, h, dk, dv, chunk, opts = RULES[case]
    x = _rule_inputs(t, h, dk, dv, seed=1, **opts)
    cot = jax.random.normal(jax.random.PRNGKey(9), (2, t, h, dv))

    def through(f):
        return jax.jit(jax.grad(
            lambda a: jnp.sum(f(dict(x, **{wrt: a})) * cot)))(x[wrt])

    with jax.default_matmul_precision("highest"):
        got = through(lambda x: _chunked(x, chunk))
        want = through(_stepwise)
    assert np.linalg.norm(want) > 0
    assert rel(got, want) < 2e-5


def test_kernel_and_xla_formulation_agree_in_bfloat16():
    """On the same bf16 q, k and v the kernel casts where the XLA
    formulation does: the two agree far inside the band that bf16 puts
    both in against the float32 rule."""
    t, h, dk, dv, chunk, opts = RULES["chunk64"]
    x = _rule_inputs(t, h, dk, dv, seed=6)
    low = dict(x, **{n: x[n].astype(jnp.bfloat16) for n in ("q", "k", "v")})
    with jax.default_matmul_precision("highest"):
        want = _stepwise(x)
    kernel, xla = (jax.jit(_chunked, static_argnums=(1, 2))(low, chunk, path)
                   for path in PATHS)
    assert kernel.dtype == jnp.float32
    assert TIGHT < rel(kernel, want) < LOOSE
    assert TIGHT < rel(xla, want) < LOOSE
    assert rel(kernel, xla) < LOOSE / 100


def test_rule_with_bfloat16_decay_and_state_is_told_from_float32():
    """The running sums of the decay and the carried state in bfloat16 leave
    the band float32 stays in by two orders of magnitude."""
    t, h, dk, dv, chunk, opts = RULES["chunk64"]
    x = _rule_inputs(t, h, dk, dv, seed=2)
    with jax.default_matmul_precision("highest"):
        want = _stepwise(x)
        exact = rel(_chunked(x, chunk), want)
        low = rel(_chunked(x, chunk, "xla", decay_dtype=jnp.bfloat16), want)
    assert exact < 1e-5 and low > 1e-3


def test_negative_eigenvalues_change_the_result():
    """beta in (1, 2) is a transition with a negative eigenvalue; halving it
    is another rule, far outside the float32 band."""
    x = _rule_inputs(64, 3, 16, 24, seed=4)
    with jax.default_matmul_precision("highest"):
        full = _stepwise(x)
        half = _stepwise(dict(x, beta=x["beta"] / 2))
    assert rel(half, full) > 0.05


@pytest.mark.parametrize("path", PATHS)
def test_rule_keeps_no_step_by_step_state(path):
    """No state for every step and no (T, T) array, in the forward or the
    backward, the XLA formulation's loops and the kernel's body included:
    the largest array of the rule is of the order of T x chunk or chunks x
    dk x dv."""
    t, h, dk, dv, chunk = 512, 2, 16, 32, 32
    x = _rule_inputs(t, h, dk, dv)
    cot = jax.random.normal(jax.random.PRNGKey(9), (2, t, h, dv))
    jaxpr = jax.make_jaxpr(jax.value_and_grad(lambda v: jnp.sum(
        _chunked(dict(x, v=v), chunk, path) * cot)))(x["v"]).jaxpr
    sizes = [int(np.prod(v.aval.shape)) for eqn in _eqns(jaxpr)
             for v in eqn.outvars if hasattr(v.aval, "shape")]
    if path == "kernel":
        assert _kernel_calls(jaxpr, "mx_delta_rule") == 1
    assert any(eqn.primitive.name == "scan" for eqn in _eqns(jaxpr))
    per_step_state, square = 2 * t * h * dk * dv, 2 * h * t * t
    assert max(sizes) <= 2 * t * h * max(chunk, dv) < min(per_step_state,
                                                           square)


def test_a_chunk_that_is_not_a_power_of_two_is_refused():
    """The kernel's lane masks and its solve by doubling need a chunk that
    is a power of two; the XLA formulation takes any."""
    t, h, dk, dv = 48, 2, 16, 24
    x = _rule_inputs(t, h, dk, dv)
    with pytest.raises(ValueError, match="power of two"):
        _chunked(x, 24)
    with jax.default_matmul_precision("highest"):
        assert rel(_chunked(x, 24, "xla"), _stepwise(x)) < 1e-5


# ---------------------------------------------------------------------------
# the mixer's parts

def test_causal_convolution_without_bias():
    """No bias is a bias of zeros, and the path with a bias traces to the
    ops it traced to before the bias became optional."""
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(2, 19, 6).astype(np.float32))
    w = jnp.asarray(rs.randn(6, 4).astype(np.float32))
    zero = jnp.zeros(6)
    np.testing.assert_allclose(lm_ops.causal_conv1d(x, w),
                               lm_ops.causal_conv1d(x, w, zero), atol=1e-6)
    np.testing.assert_allclose(
        jnp.stack([reference.causal_conv(x[i], w) for i in range(2)]),
        lm_ops.causal_conv1d(x, w), rtol=1e-5, atol=1e-5)

    def before(x, weight, bias):            # the body it had, for the trace
        t, width = x.shape[1], weight.shape[1]
        padded = jnp.pad(x.astype(jnp.float32),
                         ((0, 0), (width - 1, 0), (0, 0)))
        y = bias.astype(jnp.float32)
        for j in range(width):
            y = y + padded[:, j:j + t] * weight.astype(jnp.float32)[:, j]
        return y.astype(x.dtype)

    def prims(f):
        return [e.primitive.name for e in jax.make_jaxpr(f)(x, w, zero).eqns]

    assert prims(lm_ops.causal_conv1d) == prims(before)


def test_norm_then_gate_and_l2_norm():
    rs = np.random.RandomState(1)
    y, z = rs.randn(3, 5, 2, 8).astype(np.float32), rs.randn(3, 5, 2, 8)
    w = rs.rand(8).astype(np.float32) + 0.5
    normed = y / np.sqrt((y ** 2).mean(-1, keepdims=True) + 1e-6) * w
    want = normed * (z / (1 + np.exp(-z)))
    got = lm_ops.norm_then_gate(jnp.asarray(y), jnp.asarray(z, jnp.float32),
                                jnp.asarray(w), 1e-6)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    unit = lm_ops.l2_norm(jnp.asarray(y))
    np.testing.assert_allclose(np.linalg.norm(unit, axis=-1), 1, atol=1e-5)
    np.testing.assert_allclose(unit, reference.l2_norm(jnp.asarray(y)),
                               rtol=1e-6, atol=1e-6)


def _layer(kind, seed=5, **over):
    """One toy layer's mixer and its parameters as the reference names
    them."""
    net = make_net(seed, num_hidden_layers=1, layer_types=[kind], **over)
    p = reference.unpack(params_of(net), dict(TOY, layer_types=[kind]))
    return net.layers[0].mixer, p["layers"][0]


@pytest.mark.parametrize("kind", ["linear_attention", "full_attention"])
def test_mixer_matches_the_reference(kind):
    mixer, p = _layer(kind)
    x = jax.random.normal(jax.random.PRNGKey(2), (B, T, TOY["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda x, p: reference.MIXERS[kind](x, p, TOY))(x, p)
        with autograd.pause():
            got = mixer(from_jax(x))._data
    assert float(jnp.std(want)) > 0.05
    assert rel(got, want) < TIGHT


@pytest.mark.parametrize("neg_eigval", [True, False])
def test_mixer_reads_allow_neg_eigval(neg_eigval):
    """``linear_allow_neg_eigval`` doubles beta in the program and in the
    reference alike; the two settings give different layers."""
    cfg = dict(TOY, linear_allow_neg_eigval=neg_eigval)
    mixer, p = _layer("linear_attention", linear_allow_neg_eigval=neg_eigval)
    x = jax.random.normal(jax.random.PRNGKey(3), (B, T, TOY["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        want, other = (jax.jit(lambda x, p: reference.gated_deltanet(
            x, p, dict(cfg, linear_allow_neg_eigval=flag)))(x, p)
            for flag in (neg_eigval, not neg_eigval))
        with autograd.pause():
            got = mixer(from_jax(x))._data
    assert rel(got, want) < TIGHT < 1e-2 < rel(other, want)


# ---------------------------------------------------------------------------
# the whole model

@pytest.fixture(scope="module")
def compared():
    """Logits of the reference's ``score`` (what the chip's comparison
    calls) and its loss, and the system's logits in float32 and bf16, on one
    seeded net and batch."""
    net = make_net()
    params, (tokens, label) = params_of(net), batch()
    loss, (logits,) = jax.jit(lambda p, t, l: reference.score(p, t, l, TOY))(
        params, tokens, label)
    out = {"net": net, "ref": logits, "ref_loss": loss}
    with jax.default_matmul_precision("highest"):
        for name, dtype in (("f32", None), ("bf16", jnp.bfloat16)):
            out[name] = functional(net, dtype)(params, tokens)
    return out


def test_logits_match_reference(compared):
    want = compared["ref"]
    assert want.shape == (B, T, TOY["vocab_size"])
    assert float(jnp.std(want)) > 0.3           # logits that mean something
    assert rel(compared["f32"], want) < TIGHT
    assert TIGHT < rel(compared["bf16"], want) < LOOSE


def test_loss_of_the_logits_is_the_log_softmax_at_the_label(compared):
    _, label = batch()
    logits = compared["ref"]
    want = -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                         label.astype(jnp.int32)[..., None],
                                         -1))
    assert abs(float(compared["ref_loss"]) - float(want)) < 1e-5
    assert abs(float(reference.loss_of_logits((logits,), label, TOY))
               - float(want)) < 1e-5
    low = logits.astype(jnp.bfloat16)
    assert abs(float(reference.loss_of_logits((low,), label, TOY))
               - float(want)) < 2e-2


def test_causal_prefix_property():
    """Logits at positions < n do not depend on tokens from n on: through
    the convolutions, the carried state and the attention mask alike."""
    net = make_net()
    forward = functional(net)
    tokens, _ = batch()
    other = tokens.at[:, 21:].set((tokens[:, 21:] + 7) % TOY["vocab_size"])
    a, b = forward(params_of(net), tokens), forward(params_of(net), other)
    np.testing.assert_allclose(a[:, :21], b[:, :21], atol=1e-5)
    assert float(jnp.abs(a[:, 21:] - b[:, 21:]).max()) > 1e-3


def test_forward_names_its_parts():
    """The hybridized forward outside ``autograd.record()`` runs the linear
    layers under ``mx.gdn`` with the rule under ``mx.delta_rule`` through
    the delta rule's kernel, and the full layer under ``mx.attention``
    through the attention kernel."""
    net = make_net()
    net.hybridize()
    tokens, _ = batch()
    with autograd.pause():
        first = net(from_jax(tokens))._data
    np.testing.assert_allclose(
        first, functional(make_net())(params_of(net), tokens), rtol=1e-5,
        atol=1e-5)
    forward = functional(net)
    closed = jax.make_jaxpr(forward)(params_of(net), tokens)
    assert str(closed).count("name=mx_attention_fwd") == TOY[
        "layer_types"].count("full_attention")
    assert _kernel_calls(closed.jaxpr, "mx_delta_rule") == TOY[
        "layer_types"].count("linear_attention")
    hlo = jax.jit(forward).lower(params_of(net), tokens).as_text(
        debug_info=True)
    for scope in ("mx.gdn", "mx.delta_rule", "mx.attention", "mx.lm_head"):
        assert scope in hlo, scope


def test_initialisation_of_the_recurrent_scalars():
    mx.random.seed(11)
    net = get_model("olmo_hybrid", **dict(
        {k: TOY[k] for k in KEYS}, linear_num_key_heads=64,
        linear_num_value_heads=64, num_hidden_layers=1,
        layer_types=["linear_attention"]))
    net.initialize(mx.init.Normal(0.02))
    m = net.layers[0].mixer
    a = np.exp(m.a_log.data().asnumpy())
    step = np.log1p(np.exp(m.dt_bias.data().asnumpy()))
    assert 0 < a.min() and a.max() <= 16 and a.std() > 2     # uniform 0..16
    assert 0.001 * 0.99 <= step.min() and step.max() <= 0.1 * 1.01
    assert step.max() / step.min() > 5                       # log-uniform
    conv = m.conv_v.data().asnumpy()
    assert np.abs(conv).max() <= 0.5 and conv.std() > 0.2
    assert np.all(m.norm.data().asnumpy() == 1)
    assert abs(m.w_q.data().asnumpy().std() / 0.02 - 1) < 0.1


def test_the_scoring_path_draws_every_declared_initialiser():
    """``paths/score_causal_lm.py`` makes the weights on the chip and has a
    draw for each initialiser class the model declares; each draw lies
    where the host's initialiser puts it."""
    sys.path.insert(0, str(CHIP / "paths"))
    try:
        path = _load(CHIP / "paths" / "score_causal_lm.py", "score_path")
    finally:
        sys.path.remove(str(CHIP / "paths"))
    net = get_model("olmo_hybrid", **{k: TOY[k] for k in KEYS})
    kinds = {type(p.init).__name__ for p in net.collect_params().values()
             if p.init is not None and not isinstance(p.init, str)}
    assert kinds == set(path.DRAWS) == {"Uniform", "LogUniform",
                                        "InverseSoftplusStep"}
    mixer = net.layers[0].mixer
    key = jax.random.PRNGKey(0)
    conv = path.DRAWS["Uniform"](key, (4096,), mixer.conv_q.init)
    assert float(jnp.abs(conv).max()) <= 0.5 and float(conv.std()) > 0.2
    a = jnp.exp(path.DRAWS["LogUniform"](key, (4096,), mixer.a_log.init))
    assert 0 < float(a.min()) and float(a.max()) <= 16
    assert abs(float(a.mean()) - 8) < 0.5
    step = jax.nn.softplus(path.DRAWS["InverseSoftplusStep"](
        key, (4096,), mixer.dt_bias.init))
    assert 0.001 * 0.99 <= float(step.min()) and float(step.max()) <= 0.101
    assert abs(float(jnp.log(step).mean()) - np.log(0.01)) < 0.1


# ---------------------------------------------------------------------------
# the benchmark's files

def test_configuration_keeps_every_published_width():
    widths = dict(hidden_size=3840, intermediate_size=11008,
                  num_attention_heads=30, num_key_value_heads=30,
                  linear_num_key_heads=30, linear_num_value_heads=30,
                  linear_key_head_dim=96, linear_value_head_dim=192,
                  linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
                  rms_norm_eps=1e-6, vocab_size=100352,
                  max_position_embeddings=65536, tie_word_embeddings=False)
    assert {k: CONFIG[k] for k in widths} == widths
    assert CONFIG["rope_parameters"] == {"rope_theta": None}
    assert CONFIG["reduced"] == ["num_hidden_layers", "layer_types"]
    published = CONFIG["published"]
    period = ["linear_attention"] * 3 + ["full_attention"]
    assert published["num_hidden_layers"] == 32
    assert published["layer_types"] == period * 8
    assert CONFIG["num_hidden_layers"] == 8
    assert CONFIG["layer_types"] == period * 2 == published["layer_types"][:8]
    assert {"deployment", "assumed", "source", "parameters"} <= set(CONFIG)
    assert {"rope", "qk_norm", "norm_placement", "chunk_size", "init_std",
            "conv_init"} <= set(CONFIG["assumed"])
    assert set(KEYS) <= set(CONFIG)


def test_parameter_count_of_the_cut():
    """2,435,748,072 parameters, 4.87 GB in bf16 (the configuration's
    table), counted from the shapes with nothing allocated."""
    net = get_model("olmo_hybrid", **{k: CONFIG[k] for k in KEYS})
    count = {}
    for layer in net.layers:
        n = sum(int(np.prod(p.shape))
                for p in layer.collect_params().values())
        count.setdefault(layer.kind, set()).add(n)
    table = CONFIG["parameters"]
    assert count == {"linear_attention": {215570172},
                     "full_attention": {185809920}}
    assert table["linear_attention layer"] == 215570172
    assert table["full_attention layer"] == 185809920
    assert 2 * net.embed.weight.shape[0] * net.embed.weight.shape[1] \
        == table["embedding and head"] == 770703360
    n = sum(int(np.prod(p.shape)) for p in net.collect_params().values())
    assert n == table["total"] == 2435748072
    assert 2 * n == table["bf16 bytes"] and 4 * n == table["float32 bytes"]


def test_flops_per_sample():
    """4.26 GFLOP a token forward: the linear layers'
    projections 1.07, their FFNs 1.52, the rule 0.03, full attention 0.36,
    the other FFNs 0.51, the head 0.77; 34.9 TFLOP a sequence forward."""
    parts = reference._macs_per_token(CONFIG, 8192)
    gflop = {k: 2 * v / 1e9 for k, v in parts.items()}
    assert abs(sum(gflop.values()) - 4.26) < 0.01
    assert abs(gflop["linear_projections"] - 1.07) < 0.01
    assert abs(gflop["linear_ffn"] - 1.52) < 0.01
    assert 0.02 < gflop["delta_rule"] < 0.04
    assert abs(gflop["attention_projections"] + gflop["attention_core"]
               - 0.36) < 0.01
    assert abs(gflop["full_ffn"] - 0.51) < 0.01
    assert abs(gflop["head"] - 0.77) < 0.01
    linear = gflop["linear_projections"] + gflop["linear_ffn"] \
        + gflop["delta_rule"]
    assert 0.61 < linear / sum(gflop.values()) < 0.63
    assert abs(reference.flops_per_sample(CONFIG) / 3 / 34.9e12 - 1) < 3e-3


def test_delta_rule_costs_by_hand():
    """The rule's least time: 285 MB a layer at 8k tokens (q, k, v, o bf16,
    the decay's log and beta float32), bound by bandwidth, 0.35 ms a layer
    at 819 GB/s."""
    sys.path.insert(0, str(CHIP / "models"))    # it imports the reference
    try:
        costs = _load(CHIP / "models" / f"{NAME}_kernels.py",
                      "olmo_kernels").kernel_costs
    finally:
        sys.path.remove(str(CHIP / "models"))
    flops, nbytes = costs(CONFIG, 1)["mx_delta_rule"]
    assert nbytes == 6 * 8192 * 30 * (2 * (96 + 96 + 192 + 192) + 8)
    assert abs(nbytes / 6 / 285e6 - 1) < 0.01
    assert flops == 6 * 8192 * 2 * reference.rule_macs_per_token(CONFIG)
    assert flops / 197e12 < nbytes / 819e9
    assert costs(CONFIG, 2)["mx_delta_rule"] == (2 * flops, 2 * nbytes)


def test_scope_readers_on_a_made_up_trace():
    """``mx_gdn_ms`` holds ``mx_delta_rule_ms``; the rule's roofline share
    is its least time over the scope's: a rule that took four times its
    least time a step reads 25%. Nothing to read, nothing read."""
    saved = list(sys.path)            # the readers import their siblings
    try:
        _scope_readers()
    finally:
        sys.path[:] = saved


def _scope_readers():
    run = _load(CHIP / "run.py", "chip_run")
    read = {m: run.load_module("metrics", m).read for m in (
        "mx_gdn_ms", "mx_delta_rule_ms", "mx_delta_rule_roofline")}
    model = run.load_module("models", NAME)
    costs = run.load_module("models", f"{NAME}_kernels").kernel_costs
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    flops, nbytes = costs(CONFIG, 1)["mx_delta_rule"]
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    scopes = {"jit(program)/mx.gdn/mx.delta_rule/while": 3 * 4 * least,
              "jit(program)/mx.gdn/mx.delta_rule/dot": 4 * least,
              "jit(program)/mx.gdn/dot_general": 0.040,
              "jit(program)/mx.attention/dot_general": 0.030}
    out = {"reference": model, "config": CONFIG, "peaks": peaks,
           "traffic": {"batch": 1},
           "trace": {"steps": 4, "seconds_by_scope": scopes}}
    assert read["mx_delta_rule_ms"](out) == pytest.approx(4e3 * least)
    assert read["mx_gdn_ms"](out) == pytest.approx(4e3 * least + 10)
    assert read["mx_delta_rule_roofline"](out) == pytest.approx(25)
    for nothing in (dict(out, trace=None), dict(out, peaks=None),
                    dict(out, trace={"steps": 4, "seconds_by_scope": {}})):
        assert read["mx_delta_rule_roofline"](nothing) is None


def test_reference_imports_nothing_of_the_framework():
    for name in (NAME, f"{NAME}_kernels"):
        source = (CHIP / "models" / f"{name}.py").read_text()
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
         "--workload", "olmo_hybrid_7b_score_s8k_b1",
         "--seed", str(2**31 + 42), "--seconds", "6", "--trace", str(trace)],
        env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] >= 16
    assert list(line["compared"]) == [
        "first_step_loss_gap", "steps_failed", "loss_change_between_passes",
        "logits_gap.head0", "sequence_loss_gap", "compiled_in_window"]
    number, limit = line["compared"]["logits_gap.head0"]
    assert 0 < number < limit
    assert set(line["metrics"]) == (
        {"host_dispatch_ms"} if trace else {"samples_per_s", "setup_s"})
