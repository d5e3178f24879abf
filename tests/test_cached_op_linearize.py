"""A recorded hybridized forward runs once (cached_op.py): under
``autograd.record`` the block's program linearises, hands its residuals
to the tape node, whose backward program only applies the transpose;
only what is on the tape is differentiated; the residuals the program
wrote itself are recycled through the entry's arena by donation, and
die with the graph, not with the loss.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn, rnn
from mxnet_tpu.telemetry import tracer


# ---------------------------------------------------------------------------
# the nets

class _Residual(gluon.HybridBlock):
    """conv-BN-ReLU twice, the second with the residual add of a ResNet
    block: BatchNorm's moving statistics are the mutated state."""

    def __init__(self):
        super().__init__()
        with self.name_scope():
            self.c1, self.b1 = nn.Conv2D(4, 3, padding=1), nn.BatchNorm()
            self.c2, self.b2 = nn.Conv2D(4, 3, padding=1), nn.BatchNorm()
            self.out = nn.Dense(3)

    def hybrid_forward(self, F, x):
        h = F.relu(self.b1(self.c1(x)))
        h = F.relu(self.b2(self.c2(h)) + h)
        return self.out(h)


class _TwoHeads(gluon.HybridBlock):
    def __init__(self):
        super().__init__()
        with self.name_scope():
            self.body = nn.Dense(8, activation="relu")
            self.a, self.b = nn.Dense(3), nn.Dense(2)

    def hybrid_forward(self, F, x):
        h = self.body(x)
        return self.a(h), self.b(h)


class _Nested(gluon.HybridBlock):
    """Takes ``x`` and a list ``[y, z]``."""

    def __init__(self):
        super().__init__()
        with self.name_scope():
            self.fx, self.fy = nn.Dense(4), nn.Dense(4)

    def hybrid_forward(self, F, x, yz):
        return self.fx(x) * self.fy(yz[0]) + yz[1]


def _rs(seed=0):
    return np.random.RandomState(seed)


def _span_args(fn, name):
    """The ``args`` of the spans called ``name`` that ``fn`` leaves."""
    tracer.clear()
    tracer.enable()
    try:
        fn()
    finally:
        tracer.disable()
    args = [e["args"] for e in tracer.events() if e["name"] == name]
    tracer.clear()
    return args


def _residual():
    return _Residual(), (nd.array(_rs().rand(4, 2, 6, 6).astype("float32")),)


def _dense():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="tanh"), nn.Dense(3))
    return net, (nd.array(_rs(1).randn(5, 6).astype("float32")),)


def _rnn_layer():
    return rnn.LSTM(6, input_size=4), \
        (nd.array(_rs(2).randn(5, 3, 4).astype("float32")),)


def _two_heads():
    return _TwoHeads(), (nd.array(_rs(3).randn(4, 5).astype("float32")),)


def _nested():
    r = _rs(4)
    return _Nested(), (nd.array(r.randn(3, 5).astype("float32")),
                       [nd.array(r.randn(3, 6).astype("float32")),
                        nd.array(r.randn(3, 4).astype("float32"))])


NETS = {"conv_bn_relu_residual": _residual, "dense": _dense,
        "rnn_layer": _rnn_layer, "multi_output": _two_heads,
        "nested_input": _nested}


def _run(make, hybridize, steps=2):
    """Parameter gradients, state (``grad_req`` null) and outputs after
    ``steps`` recorded forward/backward passes, by declaration order."""
    mx.random.seed(7)
    net, args = make()
    net.initialize(mx.init.Xavier())
    with autograd.pause():
        net(*args)
    if hybridize:
        net.hybridize()
    for _ in range(steps):
        with autograd.record():
            out = net(*args)
            flat = out if isinstance(out, (tuple, list)) else [out]
            loss = sum((o * o).sum() for o in flat)
        loss.backward()
    params = list(net.collect_params().values())
    return ([o.asnumpy() for o in flat],
            [p.grad().asnumpy() if p.grad_req != "null"
             else p.data().asnumpy() for p in params], net)


@pytest.mark.parametrize("name", sorted(NETS))
def test_recorded_forward_matches_the_unhybridized_net(name):
    """Outputs, gradients and moving statistics of the linearising
    program and its transpose are the eager tape's."""
    want_out, want, _ = _run(NETS[name], False)
    got_out, got, net = _run(NETS[name], True)
    assert len(want) == len(got) and len(want_out) == len(got_out)
    for a, b in zip(want_out + want, got_out + got):
        np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-5)
    (_, entry), = net._cached_op._cache.snapshot_items()
    assert entry.linear is not None


def test_dropout_draws_one_mask_for_forward_and_backward():
    """The backward recomputes the mask from the call's key, a residual:
    for an input of ones the output and the input gradient are the same
    array, mask / keep."""
    net = nn.HybridSequential()
    net.add(nn.Dropout(0.5))
    net.initialize()
    net.hybridize()
    x = nd.ones((64, 32))
    x.attach_grad()
    with autograd.record():
        out = net(x)
    out.backward()
    kept = out.asnumpy()
    assert 0.2 < (kept > 0).mean() < 0.8
    np.testing.assert_array_equal(x.grad.asnumpy(), kept)


# ---------------------------------------------------------------------------
# only what is on the tape is differentiated

def _conv_stack(n):
    """Each layer its own width: the lowered text shares one function
    between calls that are equal to the letter."""
    net = nn.HybridSequential()
    for i in range(n):
        net.add(nn.Conv2D(4 + i, 3, padding=1, use_bias=False),
                nn.Activation("relu"))
    net.initialize(mx.init.Xavier())
    net(nd.zeros((1, 3, 5, 5)))
    return net


@pytest.mark.parametrize("input_on_tape", [False, True])
def test_backward_program_differentiates_only_the_tape(input_on_tape):
    """N convolutions: the backward program holds N weight gradients and
    N - 1 data gradients, and the Nth (the input's) only when the input
    carries a tape entry; none of the forward's convolutions is in it."""
    n = 3
    net = _conv_stack(n)
    data = _rs(5).rand(2, 3, 5, 5).astype("float32")

    def grads(net, x):
        if input_on_tape:
            x.attach_grad()
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        return [p.grad().asnumpy() for p in net.collect_params().values()] \
            + ([x.grad.asnumpy()] if input_on_tape else [])

    want = grads(net, nd.array(data))
    net.hybridize()
    got = grads(net, nd.array(data))
    for a, b in zip(want, got):
        np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-5)
    (_, entry), = net._cached_op._cache.snapshot_items()
    text = entry.vjp_jitted.lower(*entry.vjp_abstract).as_text()
    assert text.count("stablehlo.convolution") == \
        (2 * n if input_on_tape else 2 * n - 1)
    assert len(entry.linear.diff_pos) == n + input_on_tape


def test_frozen_parameter_and_integer_input_get_no_gradient():
    """A ``grad_req='null'`` weight and an int32 input (token ids) are
    closed over, not differentiated: the node returns None for them."""
    net = nn.HybridSequential()
    net.add(nn.Embedding(11, 4), nn.Dense(3, flatten=False))
    net.initialize()
    ids = nd.array(_rs(6).randint(0, 11, (2, 5)), dtype="int32")
    net(ids)
    frozen = net[1].bias
    frozen.grad_req = "null"
    net.hybridize()
    with autograd.record():
        loss = net(ids).sum()
    loss.backward()
    (_, entry), = net._cached_op._cache.snapshot_items()
    params = [p for _, p in sorted(net.collect_params().items())]
    assert entry.linear.diff_pos == tuple(
        i for i, p in enumerate(params) if p is not frozen)
    assert float(np.abs(net[0].weight.grad().asnumpy()).sum()) > 0


# ---------------------------------------------------------------------------
# a training loop

def test_five_trainer_steps_follow_the_unhybridized_trajectory():
    """Donation never touches a pass-through leaf: after five steps no
    parameter is deleted and the weights are the eager loop's."""
    def train(hybridize):
        mx.random.seed(3)
        net, (x,) = _residual()
        net.initialize(mx.init.Xavier())
        net(x)
        if hybridize:
            net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.05, "momentum": 0.9})
        label = nd.array(_rs(8).randint(0, 3, 4).astype("float32"))
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        for _ in range(5):
            with autograd.record():
                loss = loss_fn(net(x), label)
            loss.backward()
            trainer.step(4)
        return net

    want, got = train(False), train(True)
    for a, b in zip(want.collect_params().values(),
                    got.collect_params().values()):
        assert not b.data()._data.is_deleted()
        np.testing.assert_allclose(b.data().asnumpy(), a.data().asnumpy(),
                                   rtol=2e-4, atol=2e-5)
    assert len(got._cached_op._arena) == 1  # one set for the loop's life


# ---------------------------------------------------------------------------
# the residuals' life

def _recorded(net, x):
    """A recorded loss over ``net(x)`` and the block's tape node."""
    with autograd.record():
        out = net(x)
        node = out._tape_entry.node.custom
        loss = (out * out).sum()
    return loss, node


def _hybrid_residual():
    mx.random.seed(5)
    net, (x,) = _residual()
    net.initialize(mx.init.Xavier())
    net(x)
    net.hybridize()
    return net, x


def test_residuals_are_released_with_the_graph_not_the_loss():
    """``backward()`` frees the graph: the node drops its residuals and
    the set goes to the arena although the loss is still referenced; the
    next recorded forward donates that set."""
    net, x = _hybrid_residual()
    loss, node = _recorded(net, x)
    owned = node.owned
    assert owned and node.closure is not None
    assert sum(a.nbytes for a in owned) == node.entry.linear.residual_bytes
    loss.backward()
    assert node.closure is None and node.owned is None
    assert [s for _, s in node.op._arena] == [owned]
    assert np.isfinite(loss.asscalar())  # the loss outlived its graph
    loss2, node2 = _recorded(net, x)
    assert node.op._arena == []
    assert all(a.is_deleted() for a in owned)  # donated, not copied
    assert not any(a.is_deleted() for a in node2.owned)
    loss2.backward()


def test_retain_graph_keeps_the_set_out_of_the_arena():
    net, x = _hybrid_residual()
    loss, node = _recorded(net, x)
    loss.backward(retain_graph=True)
    first = [p.grad().asnumpy() for p in net.collect_params().values()
             if p.grad_req != "null"]
    assert node.closure is not None and node.op._arena == []
    loss.backward()
    second = [p.grad().asnumpy() for p in net.collect_params().values()
              if p.grad_req != "null"]
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a, b)
    assert node.closure is None and len(node.op._arena) == 1


def test_backward_after_release_raises():
    net, x = _hybrid_residual()
    loss, node = _recorded(net, x)
    loss.backward()
    with pytest.raises(MXNetError, match="already been freed"):
        loss.backward()
    with pytest.raises(MXNetError, match="already been freed"):
        node._run_backward([nd.ones((4, 3))._data])


def test_one_block_called_twice_in_one_record_scope():
    """The second call finds no set to recycle and allocates; both
    backward passes give their sets back, and the next scope's two calls
    recycle both."""
    net, x = _hybrid_residual()
    y = nd.array(_rs(9).rand(4, 2, 6, 6).astype("float32"))

    def both():
        with autograd.record():
            loss = (net(x) ** 2).sum() + (net(y) ** 2).sum()
        loss.backward()

    def recycled():
        return [a["recycled"]
                for a in _span_args(both, "mx.cached_op.forward")]

    assert recycled() == [False, False]
    assert len(net._cached_op._arena) == 2
    assert recycled() == [True, True]
    got = [p.grad().asnumpy() for p in net.collect_params().values()
           if p.grad_req != "null"]

    net.hybridize(False)
    with autograd.record():
        loss = (net(x) ** 2).sum() + (net(y) ** 2).sum()
    loss.backward()
    want = [p.grad().asnumpy() for p in net.collect_params().values()
            if p.grad_req != "null"]
    for a, b in zip(want, got):
        np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-5)


def test_signatures_with_equal_residuals_share_one_set():
    """The arena is the op's: a cast net's first step runs under another
    cache key than every later one (the moving statistics turn float32),
    and both recycle the same set; a set of another shape is dropped when
    a forward finds none that fits."""
    net, x = _hybrid_residual()
    net.cast("bfloat16")
    x = x.astype("bfloat16")
    for _ in range(3):
        _recorded(net, x)[0].backward()
    op = net._cached_op
    assert len(op._cache) == 2 and len(op._arena) == 1
    (_, kept), = op._arena
    half = nd.array(x.asnumpy()[:2]).astype("bfloat16")
    _recorded(net, half)[0].backward()
    assert len(op._arena) == 1 and op._arena[0][1][0].shape[0] == 2
    assert not any(a.is_deleted() for a in kept)  # freed, not donated


# ---------------------------------------------------------------------------
# the programs and what is said of them

def test_inference_keeps_the_plain_program():
    """Recording joins the cache key: the call outside ``record`` runs the
    plain forward, which has no residual outputs, and AOT export takes
    only that one."""
    net, x = _hybrid_residual()
    net(x)
    loss, _ = _recorded(net, x)
    loss.backward()
    entries = dict(net._cached_op._cache.snapshot_items())
    assert sorted(k.record is not None for k in entries) == [False, True]
    for key_sig, entry in entries.items():
        assert (entry.linear is None) == (key_sig.record is None)
        if key_sig.record is not None:
            assert key_sig.record[0] == "elementwise"


def test_spans_say_what_was_kept_and_recomputed():
    net, x = _hybrid_residual()

    def two_steps():
        for _ in range(2):
            _recorded(net, x)[0].backward()

    tracer.clear()
    tracer.enable()
    try:
        two_steps()
    finally:
        tracer.disable()
    fwd, vjp, launch = [
        [e["args"] for e in tracer.events() if e["name"] == name]
        for name in ("mx.cached_op.forward", "mx.cached_op.vjp",
                     "mx.cached_op.launch")]
    tracer.clear()
    assert [a["recycled"] for a in fwd] == [False, True]
    assert len(vjp) == 2
    assert all(a["programs"] == 1 for a in vjp)
    # the replay's children own its launches: the program, and on the first
    # step the allocation of the residual set it donates
    assert all(a["programs"] == 0 for a in fwd)
    assert [a["programs"] for a in launch] == [2, 1]
    # the two convolution outputs and each BatchNorm's two per-channel
    # sums; nothing after the Dense layer's product needs it
    want = 2 * 4 * 4 * 6 * 6 * 4 + 2 * 2 * 4 * 4
    assert [a["residual_bytes"] for a in fwd] == [want, want]
    assert [a["recompute"] for a in vjp] == ["elementwise"] * 2


def test_cost_and_memory_analysis_still_resolve():
    net, x = _hybrid_residual()
    loss, node = _recorded(net, x)
    loss.backward()
    op = net._cached_op
    (key_sig, entry), = op._cache.snapshot_items()
    (mem,) = op.memory_analysis().values()
    assert mem["alias_bytes"] == entry.linear.residual_bytes
    assert op.entry_cost_stats(key_sig, entry)["flops"] > 0
    assert op.entry_vjp_cost_stats(entry)["flops"] > 0


# ---------------------------------------------------------------------------
# the residual set is handed over in the dimension order the compiler keeps

def _formats(tree):
    import jax
    return [a.format for a in jax.tree_util.tree_leaves(tree)]


def _fresh_formats(avals):
    import jax.numpy as jnp
    return [jnp.zeros(a.shape, a.dtype).format for a in avals]


def test_the_set_is_handed_over_in_the_order_the_forward_compiler_keeps():
    """XLA's CPU backend keeps an NCHW convolution's product channels-last,
    so this net's two products cross to the backward as (N, H, W, C); every
    end of the hand-over agrees on that: the donated arguments, the
    residual outputs, the set ``_take_arena`` allocates, the leaves the
    closure holds and the backward program's inputs, all in the default
    layout of the turned shape; the backward names them back."""
    import jax
    net, x = _hybrid_residual()
    loss, node = _recorded(net, x)
    op, entry, lin = node.op, node.entry, node.entry.linear
    assert sorted(a.shape for a in lin.arena_avals) == \
        [(4,)] * 4 + [(4, 6, 6, 4)] * 2
    want = _fresh_formats(lin.arena_avals)
    (key_sig, _), = op._cache.snapshot_items()
    fwd = op._lower_signature(key_sig, entry)
    n = len(lin.arena_avals)
    assert list(fwd.input_formats[0][3]) == want
    assert list(fwd.output_formats[:n]) == want
    assert _formats(node.owned) == want
    assert [(a.shape, a.dtype) for a in node.owned] == \
        [(a.shape, a.dtype) for a in lin.arena_avals]
    fresh, recycled = op._take_arena(entry, x._data)
    assert not recycled and _formats(fresh) == want

    leaves = jax.tree_util.tree_leaves(node.closure)
    owned = {id(a) for a in node.owned}
    assert sum(id(a) in owned for a in leaves) == n  # the set itself
    assert len(lin.turn_back) == len(leaves)
    turned = [(a, t) for a, t in zip(leaves, lin.turn_back) if t is not None]
    assert len(turned) == lin.relaid == 2
    for a, t in turned:
        assert id(a) in owned and a.shape == (4, 6, 6, 4)
        assert a.transpose(t).shape == (4, 4, 6, 6)
    loss.backward()
    bwd = entry.vjp_jitted.lower(*entry.vjp_abstract).compile()
    assert jax.tree_util.tree_leaves(bwd.input_formats[0][0]) == \
        _formats(leaves)


def test_the_next_signature_with_the_same_residuals_compiles_once(
        monkeypatch):
    """A cast net's first step runs under another cache key than every
    later one; the second key finds the first's reading of the layouts on
    the op, hands its residuals over the same way (so that the two go on
    sharing one set) and compiles its forward once, not twice."""
    import jax
    from jax import stages
    net, x = _hybrid_residual()
    net.cast("bfloat16")
    x = x.astype("bfloat16")
    compiles = []
    real = stages.Lowered.compile

    def counted(self, *a, **kw):
        compiles.append(self)
        return real(self, *a, **kw)

    monkeypatch.setattr(stages.Lowered, "compile", counted)
    for _ in range(3):
        _recorded(net, x)[0].backward()
    op = net._cached_op
    first, second = [e.linear for _, e in op._cache.snapshot_items()]
    assert len(compiles) == 1  # the first key's, with the layouts left free
    assert first.arena_avals == second.arena_avals and first.relaid == 2
    assert op._turned[1] == tuple(
        (0, 2, 3, 1) if len(a.shape) == 4 else None
        for a in first.arena_avals)
    assert len(op._arena) == 1


def test_a_set_in_another_order_is_not_written_over():
    """A recycled set is matched on its abstract values, and those carry
    the order each buffer is handed over in: a set an entry laid out
    otherwise (planted: the same buffers under the products' own shapes)
    is not donated to this entry's program."""
    import jax
    net, x = _hybrid_residual()
    loss, node = _recorded(net, x)
    loss.backward()
    op, entry = node.op, node.entry
    (avals, kept), = op._arena
    assert avals == entry.linear.arena_avals
    planted = tuple(
        jax.ShapeDtypeStruct((4, 4, 6, 6), a.dtype) if len(a.shape) == 4
        else a for a in avals)
    assert planted != avals
    op._arena[:] = [(planted, kept)]
    fresh, recycled = op._take_arena(entry, x._data)
    assert not recycled and fresh is not kept
    assert op._arena == []  # dropped, as a set of other shapes is
    assert not any(a.is_deleted() for a in kept)
    op._arena.append((avals, kept))
    assert op._take_arena(entry, x._data) == (kept, True)


def test_five_trainer_steps_write_over_one_set(recwarn):
    """The set of step n is the donated argument of step n + 1: recycled
    from the second step on, consumed by the program (no "donated buffers
    were not usable"), one set for the loop's life."""
    mx.random.seed(3)
    net, (x,) = _residual()
    net.initialize(mx.init.Xavier())
    net(x)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9})
    label = nd.array(_rs(8).randint(0, 3, 4).astype("float32"))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    sets = []

    def step():
        with autograd.record():
            out = net(x)
            sets.append(out._tape_entry.node.custom.owned)
            loss = loss_fn(out, label)
        loss.backward()
        trainer.step(4)

    def five():
        for _ in range(5):
            step()

    fwd = _span_args(five, "mx.cached_op.forward")
    assert [a["recycled"] for a in fwd] == [False] + [True] * 4
    for donated in sets[:-1]:
        assert all(a.is_deleted() for a in donated)
    assert not any(a.is_deleted() for a in sets[-1])
    assert len(net._cached_op._arena) == 1
    assert not [w for w in recwarn.list if "donated" in str(w.message)]


@pytest.mark.parametrize("make", ["conv", "dense"])
def test_the_forward_span_counts_the_residuals_handed_over_turned(make):
    """``residuals_relaid`` and its bytes are set once from the plan, like
    ``residual_bytes``: the convolutional net's two products here (XLA's
    CPU backend keeps them channels-last), none of a net of matrix
    products."""
    if make == "conv":
        net, x = _hybrid_residual()
    else:
        net, (x,) = _dense()
        net.initialize(mx.init.Xavier())
        net.hybridize()

    def two_steps():
        for _ in range(2):
            _recorded(net, x)[0].backward()

    fwd = _span_args(two_steps, "mx.cached_op.forward")
    count, nbytes = (2, 2 * 4 * 4 * 6 * 6 * 4) if make == "conv" else (0, 0)
    assert [a["residuals_relaid"] for a in fwd] == [count] * 2
    assert [a["residuals_relaid_bytes"] for a in fwd] == [nbytes] * 2
    (_, entry), = net._cached_op._cache.snapshot_items()
    assert (entry.linear.relaid, entry.linear.relaid_bytes) == (count, nbytes)


def test_a_buffer_is_turned_only_where_that_says_what_the_compiler_chose():
    import jax
    import jax.numpy as jnp
    from jax.experimental.layout import Format, Layout
    from mxnet_tpu.cached_op import _kept_order
    a = jnp.zeros((2, 3, 4, 5), jnp.float32)
    aval = jax.ShapeDtypeStruct(a.shape, a.dtype)
    tiling = a.format.layout.tiling

    def chose(order, tiling=tiling):
        return Format(Layout(order, tiling), a.sharding)

    assert _kept_order(aval, a.format) is None          # the default
    assert _kept_order(aval, Format(None, a.sharding)) is None
    assert _kept_order(aval, chose((0, 2, 3, 1))) == (0, 2, 3, 1)
    # tiles that the turned shape's default layout does not have: a
    # transpose could not say it, so the buffer keeps its own shape
    assert _kept_order(aval, chose((0, 2, 3, 1), ((2, 5),))) is None


def test_arguments_over_several_devices_are_handed_over_the_same_way():
    """Nothing in the mechanism is one device's: the layouts are read from
    a compile over the arguments' own sharding, the program that runs is a
    jit, and its gradients are the one-device call's."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    one, x = _hybrid_residual()
    _recorded(one, x)[0].backward()
    want = [p.grad().asnumpy() for p in one.collect_params().values()
            if p.grad_req != "null"]

    net, x = _hybrid_residual()  # the same seed, the same parameters
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    for p in net.collect_params().values():
        p._data._rebind(jax.device_put(p._data._data,
                                       NamedSharding(mesh, P())))
        if p.grad_req != "null":
            p._grad._rebind(jax.device_put(p._grad._data,
                                           NamedSharding(mesh, P())))
    xs = nd.from_jax(jax.device_put(x._data, NamedSharding(mesh, P("dp"))))
    for _ in range(2):
        loss, node = _recorded(net, xs)
        loss.backward()
    assert node.op.memory_analysis()
    got = [p.grad().asnumpy() for p in net.collect_params().values()
           if p.grad_req != "null"]
    for a, b in zip(want, got):
        np.testing.assert_allclose(b, a, rtol=2e-4, atol=2e-5)
