"""Gluon Block / HybridBlock / SymbolBlock.

Reference: python/mxnet/gluon/block.py (Block.__call__:535,
HybridBlock.hybridize:504, _build_cache:748 -> CachedOp:785, export:868,
SymbolBlock:1082).

TPU-native: hybridize() swaps the imperative per-op path for a CachedOp that
jit-compiles the whole forward into one XLA module (cached_op.py). The
`F`-namespace convention of ``hybrid_forward(F, x, ...)`` is preserved —
``F`` is always the nd namespace here because tracing happens at the jax
level, not via symbol proxies.
"""
from __future__ import annotations

import re
import threading
from collections import OrderedDict
from typing import Optional

from ..base import MXNetError, check
from ..context import current_context
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock", "nn_block_scope"]


class _NameManager(threading.local):
    def __init__(self):
        self.counters = {}
        self.prefix_stack = [""]

    def next_prefix(self, hint: str) -> str:
        scope = self.prefix_stack[-1]
        key = (scope, hint)
        n = self.counters.get(key, 0)
        self.counters[key] = n + 1
        return f"{scope}{hint}{n}_"


_names = _NameManager()


class nn_block_scope:
    """Prefix scope for child block naming (ref: _BlockScope)."""

    def __init__(self, prefix: str):
        self.prefix = prefix

    def __enter__(self):
        _names.prefix_stack.append(self.prefix)
        return self

    def __exit__(self, *a):
        _names.prefix_stack.pop()


class Block:
    """Base imperative building block (ref: gluon/block.py Block)."""

    def __init__(self, prefix: Optional[str] = None, params=None):
        hint = re.sub("(.)([A-Z][a-z]+)", r"\1_\2", type(self).__name__)
        hint = re.sub("([a-z0-9])([A-Z])", r"\1\2", hint).lower()
        self._prefix = prefix if prefix is not None else _names.next_prefix(hint)
        self._params = ParameterDict(self._prefix, shared=params)
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._scope = nn_block_scope(self._prefix)

    # -- naming ---------------------------------------------------------
    @property
    def prefix(self) -> str:
        return self._prefix

    @property
    def name(self) -> str:
        return self._prefix[:-1] if self._prefix.endswith("_") else self._prefix

    def name_scope(self):
        return self._scope

    @property
    def params(self) -> ParameterDict:
        return self._params

    # -- child registration --------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            pd = self.__dict__.get("_params")
            if pd is not None and value.name not in pd:
                pd._params[value.name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None) -> None:
        self._children[name or str(len(self._children))] = block

    def collect_params(self, select: Optional[str] = None) -> ParameterDict:
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self._params)
        else:
            pattern = re.compile(select)
            ret._params.update({k: v for k, v in self._params.items()
                                if pattern.match(k)})
        for child in self._children.values():
            ret.update(child.collect_params(select))
        return ret

    def _collect_params_with_prefix(self, prefix: str = "") -> dict:
        if prefix:
            prefix += "."
        ret = {prefix + k[len(self._prefix):]: v
               for k, v in self._params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    # -- lifecycle ------------------------------------------------------
    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False) -> None:
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active: bool = True, **kwargs) -> None:
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def cast(self, dtype) -> None:
        for child in self._children.values():
            child.cast(dtype)
        for _, p in self._params.items():
            p.cast(dtype)

    def apply(self, fn) -> "Block":
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # -- persistence (ref: save_parameters/load_parameters) -------------
    def save_parameters(self, filename: str) -> None:
        from ..ndarray import utils as nd_utils
        params = self._collect_params_with_prefix()
        nd_utils.save(filename, {k: v.data() for k, v in params.items()})

    def load_parameters(self, filename: str, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False) -> None:
        from ..ndarray import utils as nd_utils
        loaded = nd_utils.load(filename)
        params = self._collect_params_with_prefix()
        if not allow_missing:
            for name in params:
                check(name in loaded, f"parameter {name} missing in file")
        for name, data in loaded.items():
            if name not in params:
                if ignore_extra:
                    continue
                raise MXNetError(f"parameter {name} not present in Block")
            params[name].set_data(data if ctx is None
                                  else data.as_in_context(ctx))

    # compat aliases (ref: deprecated save_params/load_params)
    save_params = save_parameters

    def load_params(self, filename, ctx=None, **kw):
        self.load_parameters(filename, ctx=ctx, **kw)

    # -- execution ------------------------------------------------------
    def __call__(self, *args):
        return self.forward(*args)

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs) -> None:
        outputs = self(*inputs)
        n_params = sum(int(p.data().size) for p in
                       self.collect_params().values() if p._data is not None)
        print(f"{type(self).__name__}: {n_params} parameters")

    def __repr__(self):
        lines = [f"{type(self).__name__}("]
        for name, child in self._children.items():
            child_repr = repr(child).replace("\n", "\n  ")
            lines.append(f"  ({name}): {child_repr}")
        lines.append(")")
        return "\n".join(lines)


class HybridBlock(Block):
    """Block that can be compiled to a single XLA program
    (ref: gluon/block.py HybridBlock)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op = None
        self._cached_op_kwargs = {}

    def hybridize(self, active: bool = True, static_alloc: bool = False,
                  static_shape: bool = False, mirror=None, **kwargs) -> None:
        self._active = active
        self._cached_op = None
        # mirror: rematerialize activations in backward (None = follow the
        # MXNET_BACKWARD_DO_MIRROR env flag)
        self._cached_op_kwargs = {"mirror": mirror}
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def infer_shape(self, *args) -> None:
        """Resolve deferred parameter shapes from input shapes.

        Layers override _infer_shape_impl; containers recurse by running the
        forward in shape-inference mode (cheap: runs eagerly once).
        """
        self._deferred_infer(*args)

    def _deferred_infer(self, *args) -> None:
        # run the imperative forward; layers hitting deferred params will
        # resolve them from the concrete inputs they see.
        self._imperative_call(*args)

    def _resolved_params(self) -> dict:
        out = {}
        for k, p in self._params.items():
            short = k[len(self._prefix):]
            out[short] = p.data()
        return out

    def _imperative_call(self, *args):
        """Un-jitted forward: hybrid_forward(F=nd, ...) with own params."""
        from .. import ndarray as F
        try:
            params = self._resolved_params()
        except DeferredInitializationError:
            self._shape_hint_from(*args)
            params = self._resolved_params()
        return self.hybrid_forward(F, *args, **params)

    def _shape_hint_from(self, *args) -> None:
        """Give each deferred param a shape using layer-specific logic."""
        self.infer_shape_from_inputs(*args)
        for _, p in self._params.items():
            if p._deferred_init is not None:
                p._finish_deferred_init()

    def infer_shape_from_inputs(self, *args) -> None:
        raise DeferredInitializationError(
            f"{type(self).__name__} has uninitialized-shape parameters and "
            "no shape inference rule; initialize with explicit shapes")

    def _symbolic_call(self, *args):
        """Trace hybrid_forward with Symbol proxies: params become named
        vars, the return is a Symbol graph (ref: block.py:748 _build_cache
        tracing with symbol inputs)."""
        from .. import symbol as F
        params = {}
        for k, p in self._params.items():
            short = k[len(self._prefix):]
            v = F.var(p.name)
            if not getattr(p, "_differentiable", True):
                v._outputs[0][0].extra["aux"] = True
            params[short] = v
        return self.hybrid_forward(F, *args, **params)

    def forward(self, *args):
        from ..symbol.symbol import Symbol
        if any(isinstance(a, Symbol) for a in args):
            return self._symbolic_call(*args)
        if self._active:
            if self._cached_op is None:
                from ..cached_op import CachedOp
                # make sure deferred params are resolved before tracing
                try:
                    self._collect_deferred_check()
                except DeferredInitializationError:
                    self._imperative_call(*args)
                self._cached_op = CachedOp(
                    self, **getattr(self, "_cached_op_kwargs", {}))
            return self._cached_op(*args)
        return self._imperative_call(*args)

    def remat_call(self, *args):
        """Call this block so that the backward pass recomputes it
        (``jax.checkpoint``): of what the block computes only its inputs,
        and arrays named "mx.attention" (an attention kernel's output and
        log-sum-exp), are kept. For a child called inside a traced whole
        (``SPMDTrainer``'s step, a hybridized parent). The block's
        parameters enter as arguments, and what the forward rebinds (a
        BatchNorm's running statistics, a router's bias) leaves as results
        and is rebound outside, so no tracer escapes the checkpoint."""
        import jax
        from ..ndarray.ndarray import NDArray, from_jax
        params = [p for p in self.collect_params().values()
                  if p._data is not None]
        aux = [p for p in params if p.grad_req == "null"]

        def pure(arrays, xs):
            saved = [p._data._data for p in params]
            for p, a in zip(params, arrays):
                p._data._data = a
            try:
                out = self(*[from_jax(x) for x in xs])
                return (jax.tree_util.tree_map(
                    lambda o: o._data, out,
                    is_leaf=lambda o: isinstance(o, NDArray)),
                    [p._data._data for p in aux])
            finally:
                for p, a in zip(params, saved):
                    p._data._data = a

        policy = jax.checkpoint_policies.save_only_these_names("mx.attention")
        out, rebound = jax.checkpoint(pure, policy=policy)(
            [p._data._data for p in params], [a._data for a in args])
        for p, a in zip(aux, rebound):
            p._data._data = a
        return jax.tree_util.tree_map(from_jax, out)

    def _collect_deferred_check(self) -> None:
        for _, p in self.collect_params().items():
            if p._data is None:
                raise DeferredInitializationError(p.name)

    def hybrid_forward(self, F, *args, **kwargs):
        raise NotImplementedError

    def export(self, path: str, epoch: int = 0, input_names=("data",)):
        """Serialize for deployment (ref: block.py:868 export): traces the
        block symbolically and writes ``path-symbol.json`` +
        ``path-{epoch:04d}.params`` with ``arg:``/``aux:`` keyed entries —
        the reference checkpoint layout, reloadable by SymbolBlock.imports,
        Module, the C predict API, and contrib.onnx.export_model."""
        from .. import symbol as F
        from ..symbol import symbol as sym_mod
        from ..ndarray import utils as nd_utils
        self._collect_deferred_check()
        sym = self._symbolic_call(*[F.var(n) for n in input_names])
        if isinstance(sym, (list, tuple)):
            sym = sym_mod.Group(list(sym))
        sym.save(f"{path}-symbol.json")
        aux_names = set(sym.list_auxiliary_states())
        payload = {}
        for _, p in sorted(self.collect_params().items()):
            kind = "aux" if p.name in aux_names else "arg"
            payload[f"{kind}:{p.name}"] = p.data()
        nd_utils.save(f"{path}-{epoch:04d}.params", payload)
        return sym


class SymbolBlock(HybridBlock):
    """Run a loaded symbolic graph as a block (ref: block.py:1082)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        from ..symbol.symbol import Symbol
        if isinstance(inputs, Symbol):
            inputs = [inputs]
        self._sym_outputs = outputs
        self._sym_inputs = [i.name if isinstance(i, Symbol) else i
                            for i in inputs]
        # every non-input variable becomes a Parameter of this block
        aux = set(outputs.list_auxiliary_states())
        for name in outputs.list_inputs():
            if name in self._sym_inputs:
                continue
            self.params.get(name, grad_req="null" if name in aux else "write",
                            allow_deferred_init=True,
                            differentiable=name not in aux)

    def _symbolic_call(self, *args):
        # splice the stored graph into the outer symbolic trace by
        # composing input vars with the caller's symbols (params stay as
        # named vars, so a parent block's export sees them)
        subs = {name: a for name, a in zip(self._sym_inputs, args)}
        return self._sym_outputs(**subs)

    @classmethod
    def imports(cls, symbol_file: str, input_names, param_file=None,
                ctx=None):
        """Load an exported model (ref: block.py SymbolBlock.imports)."""
        from ..symbol import symbol as sym_mod
        from ..ndarray import utils as nd_utils
        if isinstance(input_names, str):
            input_names = [input_names]
        sym = sym_mod.load(symbol_file)
        net = cls(sym, list(input_names))
        if param_file is not None:
            loaded = nd_utils.load(param_file)
            for k, v in loaded.items():
                name = k.split(":", 1)[1] if ":" in k else k
                if name in net._params:
                    net._params.get(name).set_data(
                        v if ctx is None else v.as_in_context(ctx))
        return net

    def hybrid_forward(self, F, *args, **params):
        from ..symbol.executor import eval_symbol
        return eval_symbol(self._sym_outputs, self._sym_inputs, args, params)
