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
