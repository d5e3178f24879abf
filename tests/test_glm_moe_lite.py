"""The ``glm4_moe_lite`` language model (GLM-4.7-Flash) at a toy size on the
CPU: the Gluon block against the plain reference of the benchmark
(``benchmark/chip/models/glm_4_7_flash.py``), the dropless expert layer and
its share of an expert-parallel layer, the blocked attention kernels
(interpret mode), integer inputs under a bfloat16 ``SPMDTrainer``, the
router's selection bias, and a rehearsal of the benchmark's cell.

Ops exercised here (tests/op_cases.py COVERED_ELSEWHERE): _contrib_rms_norm,
_contrib_swiglu_ffn, _contrib_mla_attention, _contrib_dropless_moe,
_contrib_blocked_attention.
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
from mxnet_tpu.gluon.model_zoo.text import CONFIG_KEYS, LMLoss
from mxnet_tpu.ndarray.ndarray import from_jax
from mxnet_tpu.ops.pallas_kernels import _attention_walk, blocked_attention
from mxnet_tpu.parallel import SPMDTrainer, moe
from mxnet_tpu.parallel.ring_attention import attention

ROOT = pathlib.Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmark" / "chip"
REHEARSE = CHIP / "tests" / "rehearse_29"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load(CHIP / "models" / "glm_4_7_flash.py", "glm_reference")
TOY = json.loads((REHEARSE / "configs" / "glm_4_7_flash.json").read_text())
B, T = 2, 48


def make_net(seed=5, std=0.15, **over):
    """The toy model with weights large enough that logits are O(1) (at the
    published 0.02 every logit is near 0 and nothing could be told apart),
    and a random selection bias."""
    mx.random.seed(seed)
    net = get_model("glm4_moe_lite",
                    **dict({k: TOY[k] for k in CONFIG_KEYS}, **over))
    net.initialize(mx.init.Normal(std))
    for p in net.collect_params().values():
        if p.name.endswith("bias"):
            p.set_data(nd.array(np.random.RandomState(seed).randn(
                *p.shape).astype(np.float32) * 0.3))
    return net


def params_of(net):
    return [p.data()._data for p in net.collect_params().values()]


def batch(seed=0, t=T):
    s = np.random.RandomState(seed).randint(0, TOY["vocab_size"], (B, t + 2))
    return jnp.asarray(s[:, :t + 1], jnp.int32), jnp.asarray(
        np.stack([s[:, 1:t + 1], s[:, 2:t + 2]], -1), jnp.float32)


def functional(net, dtype=None):
    """(params, tokens) -> (logits, MTP logits) through the Gluon block,
    parameters swapped in as ``SPMDTrainer`` swaps them; floating parameters
    in ``dtype`` when given."""
    objs = list(net.collect_params().values())

    def forward(params, tokens):
        saved = [p._data._data for p in objs]
        for p, a in zip(objs, params):
            p._data._data = a.astype(dtype) if dtype is not None else a
        try:
            with autograd.pause():
                return tuple(o._data.astype(jnp.float32)
                             for o in net(from_jax(tokens)))
        finally:
            for p, a in zip(objs, saved):
                p._data._data = a

    return forward


def system_loss(net, dtype=None):
    forward, loss_fn = functional(net, dtype), LMLoss(TOY["mtp_loss_weight"])

    def loss(params, tokens, label):
        main, mtp = forward(params, tokens)
        return jnp.mean(loss_fn((from_jax(main), from_jax(mtp)),
                                from_jax(label))._data)

    return loss


GROUPS = reference.GROUPS


def by_group(grads):
    """{group: one flat vector} of a gradient list in the architecture's
    order."""
    return {g: np.concatenate([np.asarray(a).ravel() for a in arrays])
            for g, arrays in reference.parameter_groups(grads, TOY).items()}


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


# What float32 and bfloat16 compute give against the float32 reference, as
# relative L2 errors. Float32 differs only in the order of its sums (1e-6 to
# 1e-5 here); bfloat16 rounds every product's operands to 8 bits (3e-3 to
# 3e-2 here). TIGHT lies between the two: float32 passes it and bfloat16
# does not, so it would catch a silent drop in precision; LOOSE holds
# bfloat16 and a further halving of the mantissa would pass it no more.
TIGHT, LOOSE = 2e-4, 8e-2


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


@pytest.mark.parametrize("head", [0, 1], ids=["main", "mtp"])
def test_logits_match_reference(compared, head):
    want = compared["ref_logits"][head]
    assert want.shape == (B, T, TOY["vocab_size"])
    assert float(jnp.std(want)) > 0.3           # logits that mean something
    assert rel(compared["f32_logits"][head], want) < TIGHT
    assert TIGHT < rel(compared["bf16_logits"][head], want) < LOOSE


def test_loss_matches_reference(compared):
    want = float(compared["ref"][0])
    assert abs(float(compared["f32"][0]) - want) < 1e-4 * want
    assert abs(float(compared["bf16"][0]) - want) < 2e-2 * want


@pytest.mark.parametrize("group", GROUPS)
def test_gradients_match_reference(compared, group):
    want = by_group(compared["ref"][1])[group]
    assert np.linalg.norm(want) > 0
    assert rel(by_group(compared["f32"][1])[group], want) < TIGHT
    # the router's gradient is the noisiest under bfloat16: a token whose
    # fourth and fifth scores are close changes experts
    assert rel(by_group(compared["bf16"][1])[group], want) < \
        (3 * LOOSE if group == "router" else LOOSE)


def test_no_gradient_for_bias_and_counters(compared):
    net = compared["net"]
    for p, g in zip(net.collect_params().values(), compared["f32"][1]):
        if p.grad_req == "null":
            assert not np.any(np.asarray(g)), p.name


def test_causal_prefix_property():
    """Logits at positions < n do not depend on tokens from n on (for the
    MTP head, whose input at i includes token i + 1: positions < n - 1)."""
    net = make_net()
    forward = functional(net)
    tokens, _ = batch()
    other = tokens.at[:, 30:].set((tokens[:, 30:] + 7) % TOY["vocab_size"])
    a, b = forward(params_of(net), tokens), forward(params_of(net), other)
    np.testing.assert_allclose(a[0][:, :30], b[0][:, :30], atol=1e-5)
    np.testing.assert_allclose(a[1][:, :29], b[1][:, :29], atol=1e-5)
    assert float(jnp.abs(a[0][:, 30:] - b[0][:, 30:]).max()) > 1e-3


# ---------------------------------------------------------------------------
# the expert layer

D, F, E, K = 32, 24, 16, 4


def _layer_params(held=None, seed=1, d=D):
    p = moe.init_dropless_moe_params(jax.random.PRNGKey(seed), d, F, E, held)
    return {k: v * 8 if k != "bias" else v for k, v in p.items()}


def _plain_layer(x, p, held):
    config = {"experts_held": held, "num_experts_per_tok": K,
              "routed_scaling_factor": 1.8}
    return reference.moe(x.reshape(-1, x.shape[-1]), p, config).reshape(
        x.shape)


def test_routing_drops_nothing_under_a_biased_router():
    """A router biased so that most tokens choose expert 3: capacity
    routing would drop most of them; here every (token, expert) pair is
    computed and the result is the plain masked sum."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, D))
    p = _layer_params()
    p["bias"] = jnp.zeros((E,)).at[3].set(5.0)
    y, stats = moe.dropless_moe_ffn(x, p, K, None, 1.8, tile=8)
    assert int(stats["load"][3]) == 128            # every token chose it
    assert int(stats["load"].sum()) == 128 * K == int(stats["tokens_here"])
    want = _plain_layer(x, p, tuple(range(E)))
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    # the same traffic through the capacity layer keeps 1.25 * 128 * 4 / 16
    # = 40 of expert 3's 128 tokens
    old = moe.init_moe_params(jax.random.PRNGKey(1), D, F, E)
    old["gate"] = old["gate"].at[:, 3].add(100.0 * jnp.ones((D,)))
    out, _ = moe.moe_ffn(jnp.abs(x), old, E, k=1)
    kept = int(jnp.sum(jnp.any(out.reshape(-1, D) != 0, axis=-1)))
    assert kept == 10                               # ceil(128 * 1.25 / 16)


def test_shares_sum_to_the_uncut_layer():
    """The share test: eight chips hold two experts each of sixteen. Their
    partial results, with the shared expert (which every chip computes
    alike) counted once, add up to what the uncut reference gives for the
    whole layer."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, D))
    whole = _layer_params()
    whole["bias"] = jax.random.normal(jax.random.PRNGKey(3), (E,)) * 0.3
    want = _plain_layer(x, whole, tuple(range(E)))
    shared = reference.swiglu(x, whole["shared_in"], whole["shared_out"])
    total, pairs = shared, 0
    for chip in range(8):
        held = (2 * chip, 2 * chip + 1)
        part = dict(whole, w_in=whole["w_in"][2 * chip:2 * chip + 2],
                    w_out=whole["w_out"][2 * chip:2 * chip + 2])
        y, stats = moe.dropless_moe_ffn(x, part, K, held, 1.8, tile=8)
        np.testing.assert_allclose(          # each share against its own
            y, _plain_layer(x, part, held), rtol=1e-5, atol=1e-5)
        total = total + (y - shared)
        pairs += int(stats["tokens_here"])
        assert int(stats["load"].sum()) == 80 * K  # routed over all sixteen
    assert pairs == 80 * K
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("held", [None, (0, 1, 2, 3), (5, 9)])
def test_expert_layer_gradients(held):
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 40, D))
    p = _layer_params(held)
    ids = held or tuple(range(E))

    def ours(x, p):
        return jnp.sum(moe.dropless_moe_ffn(x, p, K, held, 1.8, tile=8)[0] ** 2)

    def plain(x, p):
        return jnp.sum(_plain_layer(x, p, ids) ** 2)

    got, want = jax.grad(ours, (0, 1))(x, p), jax.grad(plain, (0, 1))(x, p)
    assert rel(got[0], want[0]) < 1e-5
    for name in p:
        if name == "bias":
            assert not np.any(np.asarray(got[1][name]))
        else:
            assert rel(got[1][name], want[1][name]) < 1e-5, name


# The routed path is one loop over the tiles in use, with its own backward:
# held experts, selection bias by expert, tokens a sequence (two sequences),
# tile. N k = 296 is no multiple of 16; a bias of 5 sends every token to an
# expert, one of -5 none.
ROUTED_CASES = {
    "all_held": (None, {}, 40, 8, D),
    "subset": ((0, 1, 2, 3), {}, 40, 8, D),
    "ragged_tiles": ((5, 9, 12), {}, 37, 16, D),
    "all_to_one_held_expert": ((2, 3), {3: 5.0}, 40, 8, D),
    "none_chosen": ((5, 9), {5: -5.0, 9: -5.0}, 40, 8, D),
    "kernel_combine": ((0, 1, 2, 3), {}, 37, 16, 128),
}


@pytest.mark.parametrize("case", ROUTED_CASES)
def test_routed_loop_is_the_plain_masked_sum(case):
    """Output, counters and every gradient of the layer against the plain
    masked sum, whatever the routing sends here: everything, a share, rows
    that fill no whole tile, every token on one expert, nothing."""
    held, bias, t, tile, d = ROUTED_CASES[case]
    ids = held or tuple(range(E))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, t, d))
    p = _layer_params(held, d=d)
    for e, b in bias.items():
        p["bias"] = p["bias"].at[e].set(b)

    def ours(x, p):
        y, stats = moe.dropless_moe_ffn(x, p, K, held, 1.8, tile=tile)
        return jnp.sum(y ** 2), (y, stats)

    def plain(x, p):
        y = _plain_layer(x, p, ids)
        return jnp.sum(y ** 2), y

    (_, (y, stats)), got = jax.value_and_grad(ours, (0, 1), has_aux=True)(x, p)
    (_, want_y), want = jax.value_and_grad(plain, (0, 1), has_aux=True)(x, p)
    # rows of whole lanes: the kernel adds them, in the forward and the
    # backward loop; else XLA's scatter, in neither
    program = str(jax.make_jaxpr(jax.value_and_grad(
        ours, (0, 1), has_aux=True))(x, p))
    assert program.count("mx_moe_combine") == (2 if d % 128 == 0 else 0)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    here, tiles = int(stats["tokens_here"]), int(stats["tiles_run"])
    assert here == int(stats["load"][np.asarray(ids)].sum())
    assert 0 <= tiles * tile - here < len(ids) * tile   # padding: under a tile an expert
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
            y, reference.swiglu(x, p["shared_in"], p["shared_out"]),
            rtol=1e-5, atol=1e-5)
        assert not np.any(np.asarray(got[1]["w_in"]))
        assert not np.any(np.asarray(got[1]["w_out"]))
        assert not np.any(np.asarray(got[1]["gate"]))


def test_bias_moves_toward_balance_and_takes_no_gradient():
    """Training steps move the selection bias of an overloaded expert down
    and of a starved one up, by gamma a step; the optimizer never touches
    it (it is aux state of the step, as BatchNorm's statistics are)."""
    net = make_net(std=0.05)
    layer = net.layers[1].ffn
    assert layer.bias.grad_req == "null" and layer.load.grad_req == "null"
    start = layer.bias.data().asnumpy().copy()
    trainer = SPMDTrainer(net, LMLoss(0.3), optimizer="adam",
                          optimizer_params={"learning_rate": 1e-3})
    assert all("bias" not in p.name for p in
               (trainer._collect() or trainer._trainable))
    tokens, label = batch()
    trainer.step(tokens, label)
    load = layer.load.data().asnumpy()
    moved = layer.bias.data().asnumpy() - start
    assert load.sum() == B * T * K
    assert int(layer.tokens_here.data().asnumpy()[0]) == \
        load[list(TOY["experts_held"])].sum()
    gamma = TOY["bias_update_speed"]
    np.testing.assert_allclose(
        moved, gamma * np.sign(load.mean() - load), atol=1e-7)
    assert moved[np.argmax(load)] < 0 < moved[np.argmin(load)]


def test_spmd_trainer_keeps_integer_inputs_under_bfloat16():
    """Token ids above 256 do not survive bfloat16: the trainer casts only
    floating inputs. Two nets that differ in one embedding row see the
    difference exactly when a token with a large id names that row."""
    vocab = 3000
    net = make_net(vocab_size=vocab, num_hidden_layers=2, std=0.05)
    trainer = SPMDTrainer(net, LMLoss(0.3), optimizer="sgd",
                          optimizer_params={"learning_rate": 0.0},
                          dtype=jnp.bfloat16)
    s = np.full((1, 18), 2049)     # bfloat16 would read 2049 as 2048
    tokens = jnp.asarray(s[:, :17], jnp.int32)
    label = jnp.asarray(np.stack([s[:, 1:17], s[:, 2:18]], -1), jnp.float32)
    before = float(trainer.step(tokens, label))
    weight = net.embed.weight
    weight.set_data(nd.array(np.asarray(
        weight.data()._data.at[2048].set(9.0))))
    trainer._param_objs = None          # collect the new array
    assert float(trainer.step(tokens, label)) == before
    weight.set_data(nd.array(np.asarray(
        weight.data()._data.at[2049].set(9.0))))
    trainer._param_objs = None
    assert float(trainer.step(tokens, label)) != before
    trainer.step(tokens.astype(jnp.float32), label)   # floats still cast


def test_remat_per_layer_same_loss_and_gradients():
    """``remat=True`` recomputes each layer in the backward pass and
    changes nothing else; the counters still leave the checkpoint."""
    plain, remat = make_net(), make_net(remat=True)
    tokens, label = batch()
    a = jax.value_and_grad(system_loss(plain))(params_of(plain), tokens, label)
    b = jax.value_and_grad(system_loss(remat))(params_of(remat), tokens, label)
    assert abs(float(a[0]) - float(b[0])) < 1e-6
    for g, h in zip(a[1], b[1]):
        np.testing.assert_allclose(g, h, rtol=1e-5, atol=1e-6)
    with autograd.train_mode():
        remat(from_jax(tokens))
    assert remat.layers[1].ffn.load.data().asnumpy().sum() == B * T * K
    # the recomputed layer keeps the attention output: no second forward
    # kernel in the backward pass
    jaxpr = str(jax.make_jaxpr(jax.grad(system_loss(remat)))(
        params_of(remat), tokens, label))
    layers = TOY["num_hidden_layers"] + 1
    assert jaxpr.count("name=mx_attention_fwd") == layers
    assert jaxpr.count("name=mx_attention_dq") == layers


# ---------------------------------------------------------------------------
# the blocked attention kernels, interpret mode

def _plain_attention(q, k, v, causal):
    def to(a):
        return a.transpose(1, 0, 2)[None]
    return attention(to(q), to(k), to(v), causal=causal,
                     scale=q.shape[-1] ** -0.5)[0].transpose(1, 0, 2)


@pytest.mark.parametrize("backward,keys_own", [
    (False, False), (True, False), (True, True)])  # fwd, dq, dkv
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t", [128, 256, 384, 640, 1024, 1152, 1536, 2048,
                               3584, 4096, 8192, 16384, 32768])
def test_attention_walk_visits_every_live_tile_once(t, causal, backward,
                                                    keys_own):
    """The walk the three kernels share, with no kernel run: over all grid
    steps every (query tile, key tile) pair at or below the diagonal is
    visited exactly once and none above it, exactly the diagonal tiles are
    masked, a dead step is one whose stretch lies wholly above the
    diagonal, and at T = 8,192 a head of the forward takes 64 grid steps,
    24 of them dead (256 and 120 with one grid step a tile, which the
    backward kernels keep)."""
    walk = _attention_walk(t, 256, 256, 2, backward)
    blk, places = walk.block, walk.stretch // walk.block
    assert t % walk.stretch == 0 and walk.stretch % blk == 0 and places <= 4
    n, n_s = t // blk, t // walk.stretch
    seen, dead = {}, 0
    for i in range(n):
        for s in range(n_s):
            place = None
            if causal:
                after = walk.after_diagonal(i, s, keys_own)
                if after > 0:
                    dead += 1
                    continue
                if after == 0:
                    assert walk.diagonal(i)[0] == s
                    place = walk.diagonal(i)[1]
            for j, on_diagonal in walk.visits(place, keys_own):
                assert 0 <= j < places
                other = s * places + j
                pair = (other, i) if keys_own else (i, other)
                assert pair not in seen
                seen[pair] = on_diagonal
    assert seen == {(q, k): causal and q == k for q in range(n)
                    for k in range(n) if k <= q or not causal}
    if causal:
        assert dead == sum(i // places if keys_own else
                           n_s - 1 - i // places for i in range(n))
    if t == 8192:
        steps, dead_steps = (256, 120) if backward else (64, 24)
        assert (blk, n * n_s) == (512, steps)
        assert dead == (dead_steps if causal else 0)


def test_attention_walk_fits_its_stretch_to_the_operands():
    """Two buffers of a stretch of both walked operands stay inside
    ``_ATTENTION_STRETCH_VMEM`` whatever T is; wide float32 operands get a
    shorter stretch, a T with a prime count of tiles one tile a step."""
    for t in (8192, 32768):
        assert _attention_walk(t, 256, 256, 2) == (512, 2048)
        assert _attention_walk(t, 128, 128, 2) == (512, 2048)
    assert _attention_walk(4096, 192, 128, 4) == (512, 1024)
    assert _attention_walk(3584, 128, 128, 2) == (512, 512)
    assert _attention_walk(48, 16, 16, 4) == (48, 48)
    assert _attention_walk(8192, 256, 256, 2, backward=True) == (512, 512)


@pytest.mark.parametrize("t,dk,dv,causal", [
    (256, 256, 256, True),      # MLA's head sizes, one block
    (384, 64, 32, True), (256, 32, 64, False), (48, 16, 16, True),
    # three stretches of three tiles of 128: the diagonal at every place of
    # a stretch, wholly live stretches before (after, in dK/dV) it
    (1152, 32, 16, True), (1152, 16, 32, False),
    # a count of tiles (five) that no stretch divides: one tile a step
    (640, 16, 32, True),
    # tiles of 512; 128 lanes of running maximum and sum a row (dv = 128)
    (1536, 64, 128, True), (2048, 128, 64, True)])
def test_blocked_attention_forward_and_backward(t, dk, dv, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(ks[i], (3, t, dk)) for i in (0, 1))
    v, g = (jax.random.normal(ks[i], (3, t, dv)) for i in (2, 3))
    np.testing.assert_allclose(blocked_attention(q, k, v, causal),
                               _plain_attention(q, k, v, causal), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(blocked_attention(*a, causal) * g),
                   (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_plain_attention(*a, causal) * g),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_blocked_attention_backward_walks_a_stretch_too(monkeypatch):
    """dQ and dK/dV are written for the forward's walk and handed one tile a
    step (``_attention_walk``); handed its stretches of three tiles they
    give the same gradients."""
    from mxnet_tpu.ops import pallas_kernels as pk
    walk = pk._attention_walk
    monkeypatch.setattr(pk, "_attention_walk",
                        lambda *shape, backward=False: walk(*shape[:4]))
    pk._build_blocked_attention.cache_clear()
    try:
        test_blocked_attention_forward_and_backward(1152, 32, 16, True)
    finally:
        pk._build_blocked_attention.cache_clear()


def test_blocked_attention_op_and_bfloat16():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(ks[i], (2, 256, 128)) for i in range(3))
    out = nd.contrib.blocked_attention(from_jax(q), from_jax(k), from_jax(v))
    want = _plain_attention(q, k, v, True)
    np.testing.assert_allclose(out.asnumpy(), want, atol=2e-5)
    low = blocked_attention(*(a.astype(jnp.bfloat16) for a in (q, k, v)))
    assert low.dtype == jnp.bfloat16
    assert rel(low.astype(jnp.float32), want) < 2e-2


def test_ops_through_the_nd_namespace():
    x = nd.array(np.random.RandomState(0).randn(2, 8, 16).astype(np.float32))
    w = nd.array(np.full((16,), 2.0, np.float32))
    got = nd.contrib.rms_norm(x, w, eps=1e-5).asnumpy()
    want = x.asnumpy() / np.sqrt((x.asnumpy() ** 2).mean(-1, keepdims=True)
                                 + 1e-5) * 2.0
    np.testing.assert_allclose(got, want, rtol=1e-5)
    w_in = nd.array(np.random.RandomState(1).randn(16, 12).astype(np.float32))
    w_out = nd.array(np.random.RandomState(2).randn(6, 16).astype(np.float32))
    h = x.asnumpy() @ w_in.asnumpy()
    want = (h[..., :6] / (1 + np.exp(-h[..., :6])) * h[..., 6:]) \
        @ w_out.asnumpy()
    np.testing.assert_allclose(
        nd.contrib.swiglu_ffn(x, w_in, w_out).asnumpy(), want, rtol=1e-4,
        atol=1e-5)


# ---------------------------------------------------------------------------
# the benchmark's files

def test_configuration_keeps_every_published_width():
    config = json.loads((CHIP / "configs" / "glm_4_7_flash.json").read_text())
    widths = dict(hidden_size=2048, num_attention_heads=20, q_lora_rank=768,
                  kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
                  v_head_dim=256, intermediate_size=10240,
                  moe_intermediate_size=1536, router_experts=64,
                  num_experts_per_tok=4, routed_scaling_factor=1.8,
                  n_shared_experts=1, rope_theta=1000000, rms_norm_eps=1e-5)
    assert {k: config[k] for k in widths} == widths
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 47,
                                   "n_routed_experts": 64,
                                   "vocab_size": 154880}
    assert config["n_routed_experts"] == len(config["experts_held"]) == 8
    assert config["vocab_size"] * 8 == 154880
    assert {"deployment", "assumed", "source"} <= set(config)


def test_flops_and_kernel_costs():
    config = json.loads((CHIP / "configs" / "glm_4_7_flash.json").read_text())
    parts = reference._macs_per_token(config, 8192)
    per_token = 2 * sum(parts.values())
    assert abs(per_token / 1.208e9 - 1) < 2e-3          # GFLOP a token
    assert abs(reference.flops_per_sample(config) / 29.69e12 - 1) < 2e-3
    share = {k: v / sum(parts.values()) for k, v in parts.items()}
    assert 0.62 < share["mla_projections"] + share["attention_core"] < 0.64
    assert 0.12 < share["heads"] < 0.14
    costs = reference.kernel_costs(config, 1)
    assert set(costs) == {"mx_attention_fwd", "mx_attention_dq",
                          "mx_attention_dkv"}
    # six call sites, 20 heads, half of 8192^2, 512 = 256 + 256 wide
    assert costs["mx_attention_fwd"][0] == 6 * 20 * 8192 ** 2 * 512
    assert costs["mx_attention_dkv"][0] == 2 * costs["mx_attention_fwd"][0]


def test_parameter_count_of_the_share():
    """706.5M parameters that train (ISSUE 29's table), counted from the
    shapes with nothing allocated."""
    config = json.loads((CHIP / "configs" / "glm_4_7_flash.json").read_text())
    net = get_model("glm4_moe_lite", **{k: config[k] for k in CONFIG_KEYS})
    n = sum(int(np.prod(p.shape)) for p in net.collect_params().values()
            if p.grad_req != "null")
    assert abs(n / 706.5e6 - 1) < 1e-3


@pytest.mark.heavy
@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell,traced", [
    ("glm_4_7_flash_train_spmd_s8k",
     {"host_dispatch_ms", "dispatches_per_step", "moe_load_max_over_mean"}),
    # FitLoop closes its steps under its own root span, mx.fit.step, which
    # the readers of PR 38 know (metrics/step_spans.py) and those that go
    # by program_spans.CLOSERS do not
    ("resnet50_train_fitloop",
     {"host_dispatch_ms", "cached_op_prepare_ms", "cached_op_launch_ms",
      "fit_between_steps_ms", "dispatches_per_step.fitloop"})])
def test_cell_rehearsal(cell, traced, trace):
    """The new paths end to end on the CPU at a toy size, through ``run.py
    --rehearse`` from a directory of their own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--rehearse", str(REHEARSE),
         "--workload", cell, "--seed", str(2**31 + 29), "--seconds", "8",
         "--trace", str(trace)],
        env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] >= 4
    assert line["checks"]["loss_fell"] and line["checks"]["reference"]
    assert set(line["metrics"]) == (
        traced if trace else {"samples_per_s", "setup_s"})
    if "moe_load_max_over_mean" in line["metrics"]:
        assert 1.0 <= line["metrics"]["moe_load_max_over_mean"]["value"] < 16
