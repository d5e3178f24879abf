"""Imperative autograd: tape recording + reverse pass.

Reference: src/imperative/imperative.cc (RecordOp :191, Backward :278,
MarkVariables :130) and python/mxnet/autograd.py (record/pause/train_mode/
backward/grad/Function).

TPU-native redesign: instead of building an NNVM backward graph and executing
node-by-node through the engine, every recorded op keeps (a) a snapshot of its
input ``jax.Array`` values (immutable, so "snapshot" is just a reference —
versioned-mutation on NDArray cannot corrupt the tape) and (b) its pure op
function. The reverse pass walks the tape topologically and calls ``jax.vjp``
on each op — XLA jit-compiles each (op, params, shapes) vjp once and replays
it. A hybridized block is ONE node of this tape (CachedOp: its recorded
forward linearises, the node's backward applies the transpose — see
cached_op.py).
"""
from __future__ import annotations

import threading
import weakref
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as _np

from .base import MXNetError, check, hashable_params
from .telemetry.tracer import span as _span

__all__ = ["record", "pause", "train_mode", "predict_mode", "is_recording",
           "is_training", "set_recording", "set_training", "mark_variables",
           "backward", "grad", "grad_ready_scope", "Function", "get_symbol"]


class _State(threading.local):
    def __init__(self):
        self.recording = False
        self.training = False
        self.capture_stack = []
        self.grad_ready_hook = None


_state = _State()


class _CaptureScope:
    """Discovers grad-relevant free NDArrays used inside a traced construct
    (the analog of NNVM subgraph free-variable capture in
    src/operator/subgraph_op_common.cc)."""

    def __init__(self):
        self.order: list = []
        self._seen = set()
        self._internal = set()

    def observe(self, inputs, outputs) -> None:
        for x in inputs:
            if getattr(x, "_tape_entry", None) is not None and \
                    id(x) not in self._internal and id(x) not in self._seen:
                self._seen.add(id(x))
                self.order.append(x)
        for o in outputs:
            self._internal.add(id(o))


class capture:
    """Context manager collecting captured free variables."""

    def __enter__(self) -> _CaptureScope:
        scope = _CaptureScope()
        _state.capture_stack.append(scope)
        return scope

    def __exit__(self, *a):
        _state.capture_stack.pop()


def _observe_capture(inputs, outputs) -> None:
    if _state.capture_stack:
        _state.capture_stack[-1].observe(inputs, outputs)


def is_recording() -> bool:
    return _state.recording


def is_training() -> bool:
    return _state.training


def set_recording(is_rec: bool) -> bool:
    prev, _state.recording = _state.recording, is_rec
    return prev


def set_training(train: bool) -> bool:
    prev, _state.training = _state.training, train
    return prev


class _RecordingStateScope:
    """(ref: python/mxnet/autograd.py _RecordingStateScope)"""

    def __init__(self, is_record: Optional[bool], train_mode: Optional[bool]):
        self._enter_is_record = is_record
        self._enter_train_mode = train_mode
        self._prev_is_record = None
        self._prev_train_mode = None

    def __enter__(self):
        if self._enter_is_record is not None:
            self._prev_is_record = set_recording(self._enter_is_record)
        if self._enter_train_mode is not None:
            self._prev_train_mode = set_training(self._enter_train_mode)

    def __exit__(self, *args):
        if self._enter_is_record is not None:
            set_recording(self._prev_is_record)
        if self._enter_train_mode is not None:
            set_training(self._prev_train_mode)


def record(train_mode: bool = True) -> _RecordingStateScope:
    return _RecordingStateScope(True, train_mode)


def pause(train_mode: bool = False) -> _RecordingStateScope:
    return _RecordingStateScope(False, train_mode)


def train_mode() -> _RecordingStateScope:
    return _RecordingStateScope(None, True)


def predict_mode() -> _RecordingStateScope:
    return _RecordingStateScope(None, False)


# ---------------------------------------------------------------------------
# tape structures
# ---------------------------------------------------------------------------

class _RspGrad:
    """A row-sparse cotangent traveling down the tape: (data, indices) with
    duplicate indices allowed; unique-row compaction happens once at grad
    delivery. This is how Embedding(sparse_grad=True) and dot(csr, dense)
    gradients avoid ever materializing a dense (vocab, dim) array
    (ref: src/operator/tensor/indexing_op.cc SparseEmbeddingOpBackwardRspImpl)."""

    __slots__ = ("data", "indices", "shape")

    def __init__(self, data, indices, shape):
        self.data = data          # (n, ...) jax array, n rows (dupes ok)
        self.indices = indices    # (n,) int row ids
        self.shape = tuple(shape)

    def densify(self):
        import jax.numpy as jnp
        out = jnp.zeros(self.shape, self.data.dtype)
        return out.at[jnp.asarray(self.indices)].add(self.data)

    def compact(self):
        """→ (data, unique_sorted_indices): duplicate rows segment-summed."""
        import jax.numpy as jnp
        import numpy as np
        idx = np.asarray(self.indices)
        uniq, inv = np.unique(idx, return_inverse=True)
        data = jnp.zeros((len(uniq),) + self.shape[1:], self.data.dtype)
        data = data.at[jnp.asarray(inv)].add(self.data)
        return data, uniq.astype(np.int32)


class _TapeIdentity:
    """Backward hook that passes cotangents straight through — used to keep
    the tape connected across container conversions (rsp.todense())."""

    def _run_backward(self, cotangents):
        return list(cotangents)


def _grad_sum(a, b):
    """Accumulate two cotangents, either of which may be row-sparse."""
    a_rsp, b_rsp = isinstance(a, _RspGrad), isinstance(b, _RspGrad)
    if a_rsp and b_rsp:
        import jax.numpy as jnp
        import numpy as np
        return _RspGrad(jnp.concatenate([a.data, b.data]),
                        np.concatenate([np.asarray(a.indices),
                                        np.asarray(b.indices)]), a.shape)
    if a_rsp:
        return a.densify() + b
    if b_rsp:
        return a + b.densify()
    return a + b


class _VariableEntry:
    """Leaf marked by mark_variables/attach_grad (ref AGInfo for variables)."""

    __slots__ = ("array_ref", "grad_ref", "grad_req")

    def __init__(self, array, grad, grad_req: str):
        self.array_ref = weakref.ref(array)
        self.grad_ref = weakref.ref(grad) if grad is not None else None
        self.grad_req = grad_req

    @property
    def node(self):
        return None


class _TapeNode:
    """One recorded op application (ref: nnvm node + AGInfo per output)."""

    __slots__ = ("opdef", "params_key", "input_vals", "input_entries",
                 "out_avals", "custom", "train_mode")

    def __init__(self, opdef, params_key, input_vals, input_entries,
                 out_avals, custom=None, train=False):
        self.opdef = opdef
        self.params_key = params_key
        self.input_vals = input_vals        # tuple of jax arrays (immutable)
        self.input_entries = input_entries  # per-input: _OutputEntry | _VariableEntry | None
        self.out_avals = out_avals          # [(shape, dtype)]
        self.custom = custom                # Function instance for custom grads
        self.train_mode = train


class _OutputEntry:
    __slots__ = ("node", "index")

    def __init__(self, node: _TapeNode, index: int):
        self.node = node
        self.index = index


class grad_ready_scope:
    """Install a gradient-finality hook for backward passes on this thread.

    ``fn(grad_buffer)`` is called DURING the reverse pass, the moment a
    marked variable's gradient buffer receives its final contribution (no
    remaining tape node can add to it). This is the dependency-resolution
    signal the reference engine schedules kvstore pushes on (PAPER.md
    §engine): a consumer can start communicating a gradient while backward
    is still producing the earlier layers' gradients. The hook runs on the
    backward thread; delivery order is reverse-creation order (later
    layers' grads finalize first). Whole-graph (CachedOp) backward bypasses
    the tape and fires no hooks — consumers must treat the hook as an
    optimization signal, not a completeness guarantee."""

    def __init__(self, fn):
        self._fn = fn
        self._prev = None

    def __enter__(self):
        self._prev = _state.grad_ready_hook
        _state.grad_ready_hook = self._fn
        return self

    def __exit__(self, *a):
        _state.grad_ready_hook = self._prev
        return False


def mark_variables(variables: Sequence, gradients: Sequence,
                   grad_reqs="write") -> None:
    """Associate gradient buffers with arrays
    (ref: MXAutogradMarkVariables -> Imperative::MarkVariables)."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for var, g, req in zip(variables, gradients, grad_reqs):
        var._tape_entry = _VariableEntry(var, g, req)
        var._grad = g
        var._grad_req = req


def _record_op(opdef, params, nd_inputs, arrays, out_nds) -> None:
    """Append one op to the tape (ref Imperative::RecordOp)."""
    from .ops.registry import normalize_params
    entries = [getattr(x, "_tape_entry", None) for x in nd_inputs]
    if not any(e is not None for e in entries):
        return  # nothing upstream requires grad: keep the tape sparse
    node = _TapeNode(opdef, hashable_params(normalize_params(params)),
                     tuple(arrays), entries,
                     [(o.shape, o._data.dtype) for o in out_nds],
                     train=is_training())
    for i, o in enumerate(out_nds):
        o._tape_entry = _OutputEntry(node, i)


def _record_custom(function, nd_inputs, out_nds) -> None:
    entries = [getattr(x, "_tape_entry", None) for x in nd_inputs]
    node = _TapeNode(None, (), tuple(x._data for x in nd_inputs), entries,
                     [(o.shape, o._data.dtype) for o in out_nds],
                     custom=function, train=is_training())
    for i, o in enumerate(out_nds):
        o._tape_entry = _OutputEntry(node, i)


# ---------------------------------------------------------------------------
# reverse pass
# ---------------------------------------------------------------------------

_VJP_CACHE: Dict[Tuple, Any] = {}


def _vjp_call(node: _TapeNode, cotangents: Tuple):
    """jit-cached vjp of one op (the FGradient analog, compiled)."""
    import jax
    from .ops.registry import _trace_time_flags
    key = (node.opdef.name, node.params_key, node.train_mode,
           _trace_time_flags())
    fn = _VJP_CACHE.get(key)
    if fn is None:
        opdef = node.opdef
        kwargs = dict(node.params_key)

        def fwd(*ins):
            out = opdef.fn(*ins, **kwargs)
            return out if isinstance(out, tuple) else (out,)

        def run(inputs, cots):
            _, vjp = jax.vjp(fwd, *inputs)
            return vjp(tuple(cots))

        try:
            fn = jax.jit(run)
            _VJP_CACHE[key] = fn
        except Exception:
            fn = run
    return fn(node.input_vals, cotangents)


def _toposort(root_nodes: List[_TapeNode]) -> List[_TapeNode]:
    order: List[_TapeNode] = []
    seen = set()
    stack = [(n, False) for n in root_nodes]
    while stack:
        node, processed = stack.pop()
        if processed:
            order.append(node)
            continue
        if id(node) in seen:
            continue
        seen.add(id(node))
        stack.append((node, True))
        for e in node.input_entries:
            if e is not None and getattr(e, "node", None) is not None \
                    and id(e.node) not in seen:
                stack.append((e.node, False))
    return order


def backward(heads: Sequence, head_grads: Optional[Sequence] = None,
             retain_graph: bool = False, train_mode: bool = True) -> None:
    """Run the reverse pass, accumulating into attached grad buffers
    (ref: MXAutogradBackwardEx -> Imperative::Backward, imperative.cc:278)."""
    _backward_impl(heads, head_grads, retain_graph, train_mode,
                   variables=None)


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """Return gradients of heads w.r.t. variables
    (ref: python/mxnet/autograd.py:270)."""
    check(not create_graph, "create_graph=True (higher-order autograd) is "
                            "not supported yet on the eager tape")
    if retain_graph is None:
        retain_graph = create_graph
    from .ndarray.ndarray import NDArray
    if isinstance(heads, NDArray):
        heads = [heads]
    if isinstance(variables, NDArray):
        variables = [variables]
    if head_grads is not None and isinstance(head_grads, NDArray):
        head_grads = [head_grads]
    if head_grads is not None:
        check(len(head_grads) == len(heads),
              f"len(head_grads) ({len(head_grads)}) must equal "
              f"len(heads) ({len(heads)})")
    return _backward_impl(heads, head_grads, retain_graph, train_mode,
                          variables=variables)


def _deliver_grad(e: "_VariableEntry", g):
    """Write one accumulated cotangent into a variable's attached grad
    buffer (honoring grad_req and row_sparse buffers). Returns the buffer
    written, or None when the variable has no live buffer, and the number
    of programs that took (the add of grad_req='add', a cast to the
    buffer's dtype)."""
    var = e.array_ref()
    if var is None or e.grad_ref is None:
        return None, 0
    gbuf = e.grad_ref()
    if gbuf is None or e.grad_req == "null":
        return None, 0
    from .ndarray.sparse import RowSparseNDArray
    if isinstance(gbuf, RowSparseNDArray):
        # row_sparse grad buffer (attach_grad(stype='row_sparse') /
        # Parameter grad_stype): store only the touched rows
        if not isinstance(g, _RspGrad):
            g = _RspGrad(g, _np.arange(g.shape[0], dtype=_np.int64),
                         g.shape)
        if e.grad_req == "add" and gbuf._data.shape[0]:
            g = _grad_sum(_RspGrad(gbuf._data,
                                   _np.asarray(gbuf._indices),
                                   g.shape), g)
        data, uniq = g.compact()
        gbuf._update(data.astype(gbuf._data.dtype), uniq)
        gbuf._fresh_grad = True
        return gbuf, 0  # host-side packing: its launches are not counted
    if isinstance(g, _RspGrad):
        g = g.densify()
    if e.grad_req == "add":
        gbuf._rebind(gbuf._data + g)
        launched = 1
    else:
        launched = int(g.dtype != gbuf._data.dtype)
        gbuf._rebind(g.astype(gbuf._data.dtype))
    gbuf._fresh_grad = True
    return gbuf, launched


def _backward_impl(heads, head_grads, retain_graph, train_mode_flag,
                   variables=None):
    with _span("mx.autograd.backward", "step") as sp:
        return _backward_walk(heads, head_grads, retain_graph, variables, sp)


def _backward_walk(heads, head_grads, retain_graph, variables, sp):
    """The reverse pass. ``sp`` is its span: it learns how many tape nodes
    were walked and how many programs the walk itself launched (head
    gradients, zero cotangents, the eager ops' vjps, sums of cotangents;
    not a CachedOp's backward, which has its own span)."""
    import jax.numpy as jnp
    from .ndarray.ndarray import NDArray

    heads = list(heads)
    for h in heads:
        check(h._tape_entry is not None,
              "cannot differentiate: output is not part of the recorded graph "
              "(was it computed under autograd.record()?)")

    if head_grads is None:
        head_grads = [None] * len(heads)

    # grad accumulator keyed by tape entry identity
    acc: Dict[int, Any] = {}
    entry_of: Dict[int, Any] = {}

    launched = 0

    def add_grad(entry, g):
        nonlocal launched
        k = id(entry)
        entry_of[k] = entry
        if k in acc:
            acc[k] = _grad_sum(acc[k], g)
            launched += 1
        else:
            acc[k] = g

    root_nodes = []
    for h, hg in zip(heads, head_grads):
        e = h._tape_entry
        if hg is not None:
            g = hg._data
        else:
            # the cast of 1 to the dtype, and its broadcast where the head
            # has a shape to fill
            g = jnp.ones(h.shape, h._data.dtype)
            launched += 1 + bool(h.shape)
        add_grad(e, g)
        if isinstance(e, _OutputEntry):
            root_nodes.append(e.node)

    order = _toposort(root_nodes)

    # grad-ready scheduling (overlap consumers): count, per marked
    # variable, how many tape nodes can still contribute to its gradient;
    # when the count hits zero during the reverse pass the grad is FINAL
    # and can be delivered + announced immediately, while backward keeps
    # running. Zero-cost when no hook is installed.
    hook = _state.grad_ready_hook
    pending: Dict[int, int] = {}
    delivered = set()
    if hook is not None:
        for node in order:
            for e in node.input_entries:
                if isinstance(e, _VariableEntry):
                    pending[id(e)] = pending.get(id(e), 0) + 1

    for node in reversed(order):
        # gather cotangents for this node's outputs
        cots = []
        has_any = False
        for i, (shape, dtype) in enumerate(node.out_avals):
            found = None
            for k, e in list(entry_of.items()):
                if isinstance(e, _OutputEntry) and e.node is node and e.index == i:
                    found = acc.get(k)
                    break
            if found is not None:
                has_any = True
                cots.append(found.densify() if isinstance(found, _RspGrad)
                            else found)
            else:
                cots.append(jnp.zeros(shape, dtype))
                launched += 1
        if has_any:
            if node.input_vals is None:
                raise MXNetError("graph has already been freed; pass "
                                 "retain_graph=True to backward() to reuse "
                                 "it")
            if node.custom is not None:
                in_grads = node.custom._run_backward(cots)
            elif node.opdef.name == "Embedding" \
                    and dict(node.params_key).get("sparse_grad"):
                # row_sparse weight gradient: ship (cot rows, ids) without
                # the dense (vocab, dim) scatter (ref: indexing_op.cc
                # SparseEmbeddingOpBackwardRspImpl)
                data_in, weight_in = node.input_vals[0], node.input_vals[1]
                cot = cots[0]
                dim = weight_in.shape[-1]
                in_grads = (None, _RspGrad(cot.reshape(-1, dim),
                                           _np.asarray(data_in).reshape(-1)
                                           .astype(_np.int64),
                                           weight_in.shape))
            else:
                in_grads = _vjp_call(node, tuple(cots))
                launched += 1
            for e, g in zip(node.input_entries, in_grads):
                if e is not None and g is not None:
                    add_grad(e, g)
        if hook is None:
            continue
        # a node consumed (whether or not it contributed a cotangent) can
        # no longer add to its input variables' grads — decrement, and on
        # zero deliver into the attached buffer + fire the hook
        for e in node.input_entries:
            if not isinstance(e, _VariableEntry):
                continue
            k = id(e)
            pending[k] -= 1
            if pending[k] == 0 and k in acc and k not in delivered:
                delivered.add(k)
                gbuf, n = _deliver_grad(e, acc[k])
                launched += n
                if gbuf is not None:
                    hook(gbuf)

    # deliver to variables
    results = None
    if variables is not None:
        results = []
        for v in variables:
            e = v._tape_entry
            check(e is not None, "one of the variables was not marked "
                                 "(call attach_grad())")
            g = acc.get(id(e))
            if g is None:
                g = jnp.zeros(v.shape, v._data.dtype)
            elif isinstance(g, _RspGrad):
                from .ndarray import sparse as _sp
                data, uniq = g.compact()
                results.append(_sp.RowSparseNDArray(data, uniq, g.shape,
                                                    v._ctx))
                continue
            results.append(NDArray(g, ctx=v._ctx))
    # accumulate into attached grad buffers (entries already delivered
    # early by the grad-ready path are skipped — delivering twice would
    # double-accumulate a grad_req='add' buffer)
    sp.set(nodes=len(order), programs=launched)
    with _span("mx.autograd.deliver", "step") as deliver:
        grads = programs = 0
        for k, e in entry_of.items():
            if isinstance(e, _VariableEntry) and k not in delivered:
                gbuf, n = _deliver_grad(e, acc[k])
                grads += gbuf is not None
                programs += n
        deliver.set(grads=grads, programs=programs)

    if not retain_graph:
        for node in order:
            node.input_vals = None
            # a custom node may hold more than its inputs (a CachedOp's
            # residuals): it must not outlive the graph because a loss
            # kept for logging keeps the node
            release = getattr(node.custom, "_release_graph", None)
            if release is not None:
                release()

    return results


def get_symbol(x):
    """Trace the tape that produced ``x`` into a Symbol
    (ref: MXAutogradGetSymbol). Minimal: returns a symbol listing the op
    chain; full graph export lives on the Symbol/CachedOp path."""
    raise NotImplementedError("get_symbol on the eager tape is not supported; "
                              "use HybridBlock.export / symbol tracing")


class Function:
    """User-defined differentiable function
    (ref: python/mxnet/autograd.py:365 Function + src/c_api/c_api_function.cc).

    Subclass and implement ``forward(self, *inputs)`` and
    ``backward(self, *output_grads)`` operating on NDArrays.
    """

    def __init__(self):
        self._saved: Tuple = ()

    def save_for_backward(self, *arrays):
        self._saved = arrays

    @property
    def saved_tensors(self):
        return self._saved

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def _run_backward(self, cotangents):
        from .ndarray.ndarray import NDArray, from_jax
        with pause():
            grads = self.backward(*[from_jax(c) for c in cotangents])
        if not isinstance(grads, (tuple, list)):
            grads = (grads,)
        return [g._data if isinstance(g, NDArray) else g for g in grads]

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray
        with pause():
            outputs = self.forward(*inputs)
        single = not isinstance(outputs, (tuple, list))
        out_t = (outputs,) if single else tuple(outputs)
        if is_recording():
            _record_custom(self, inputs, out_t)
        return outputs
