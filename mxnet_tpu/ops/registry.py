"""Operator registry + eager compile-and-cache executor.

This is the TPU-native replacement for the reference's NNVM op registry and
imperative dispatch chain (ref: include/mxnet/op_attr_types.h FCompute/
FComputeEx; src/imperative/imperative.cc:87 Imperative::Invoke ->
src/engine/threaded_engine.cc:315 PushAsync -> worker kernels).

Design:
- Every operator is a **pure JAX function** ``fn(*inputs, **params)`` over
  ``jax.Array`` values. This single definition serves all four consumers:
  1. eager NDArray execution (this module: per-(op, params) ``jax.jit``
     with XLA's shape/dtype-keyed compile cache = the reference's
     per-op kernel dispatch, but compiled),
  2. the autograd tape (``jax.vjp`` on the same fn = ref FGradient),
  3. symbolic/CachedOp whole-graph lowering (fns composed then jitted as a
     single HLO module = ref GraphExecutor bulking taken to its limit),
  4. shape/type inference (``jax.eval_shape`` = ref FInferShape/FInferType).
- The "async engine" contract (frontend never blocks, exceptions surface at
  sync points) is inherited from JAX's async dispatch; NaiveEngine debug mode
  (MXNET_ENGINE_TYPE=NaiveEngine, ref src/engine/engine.cc:33-46) is honored
  by blocking after every eager op.

Registered names mirror the reference's op names (elemwise_add, dot,
Convolution, ...) so generated frontend namespaces have the same surface
(ref: python/mxnet/ndarray/register.py codegen).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as _np

from ..base import MXNetError, env, hashable_params, coerce_param
from ..telemetry.tracer import span as _span

__all__ = ["OpDef", "register", "get_op", "list_ops", "invoke_jax",
           "eval_shape", "alias", "register_sparse", "stype_dispatch",
           "storage_fallback_warn"]

_OPS: Dict[str, "OpDef"] = {}


# storage-type dispatch table (the FComputeEx + FInferStorageType analog,
# ref: include/mxnet/op_attr_types.h:122,282): (op name, input stypes) →
# kernel over sparse/dense NDArray objects. "*" matches any stype tuple.
_SPARSE_IMPLS: Dict[Tuple[str, Tuple[str, ...]], Callable] = {}
_FALLBACK_WARNED: set = set()


class OpDef:
    """A registered operator.

    Attributes
    ----------
    name : canonical op name (reference-compatible).
    fn : pure function ``fn(*inputs, **params) -> array | tuple``.
    num_outputs : static int, or callable ``(n_inputs, params) -> int``.
    differentiable : participates in autograd recording.
    creation : takes no array inputs (zeros/ones/random...); receives
        ``shape/dtype/ctx`` handling in the frontend wrapper.
    """

    __slots__ = ("name", "fn", "num_outputs", "differentiable", "creation",
                 "namespaces", "_jit_cache", "doc", "variadic", "backward_fn",
                 "rng", "aux_inputs", "dynamic_params")

    def __init__(self, name: str, fn: Callable, num_outputs=1,
                 differentiable: bool = True, creation: bool = False,
                 namespaces: Sequence[str] = ("op",), variadic: bool = False,
                 backward_fn: Optional[Callable] = None, doc: str = "",
                 rng: bool = False, aux_inputs: Sequence[int] = (),
                 dynamic_params: Sequence[str] = ()):
        # float params traced as device scalars instead of baked into the
        # compiled program: a per-step value (Adam's bias-corrected lr_t, a
        # scheduled lr) must NOT key the jit cache, or every step
        # recompiles (measured: eager Adam recompiled 15x/step before this)
        self.dynamic_params = tuple(dynamic_params)
        self.rng = rng
        # input slots that are auxiliary states in symbolic graphs
        # (ref: OperatorProperty::ListAuxiliaryStates — e.g. BatchNorm's
        # moving_mean/moving_var)
        self.aux_inputs = tuple(aux_inputs)
        self.name = name
        self.fn = fn
        self.num_outputs = num_outputs
        self.differentiable = differentiable
        self.creation = creation
        self.namespaces = tuple(namespaces)
        self.variadic = variadic
        self.backward_fn = backward_fn
        self.doc = doc or (fn.__doc__ or "")
        self._jit_cache: Dict[Tuple, Callable] = {}

    # -- eager execution ------------------------------------------------
    def jitted(self, params_key: Tuple, dyn_names: Tuple = ()) -> Callable:
        """One ``jax.jit`` per (op, params); XLA caches per shape/dtype.

        This is the eager hot path: the analog of the reference's per-op
        engine push, except each (op, params, shape, dtype) combination is
        compiled once into a fused XLA executable and then replayed
        (SURVEY.md §7 stage 4: "compile-and-cache tiny HLO modules").

        ``dyn_names``: declared dynamic params bound on this call — their
        VALUES arrive as a traced tuple argument, not in the cache key.
        """
        cache_key = (params_key, dyn_names)
        cached = self._jit_cache.get(cache_key)
        if cached is None:
            import jax
            # strip the trace-time flag suffix (booleans) — only real
            # (name, value) param pairs become kwargs
            kwargs = dict(kv for kv in params_key
                          if isinstance(kv, tuple) and len(kv) == 2)
            fn = self.fn

            def call(dyn_vals, *arrays):
                return fn(*arrays, **kwargs,
                          **dict(zip(dyn_names, dyn_vals)))

            cached = jax.jit(call)
            self._jit_cache[cache_key] = cached
        return cached

    def __call__(self, *inputs, **params):
        return invoke_jax(self, inputs, params)

    def n_out(self, n_inputs: int, params: Dict[str, Any]) -> int:
        if callable(self.num_outputs):
            return self.num_outputs(n_inputs, params)
        return self.num_outputs

    def __repr__(self):
        return f"<OpDef {self.name}>"


def register(name: str, aliases: Sequence[str] = (), **kw) -> Callable:
    """Decorator registering a pure-jax op implementation under ``name``."""

    def deco(fn: Callable) -> Callable:
        opdef = OpDef(name, fn, **kw)
        if name in _OPS:
            raise MXNetError(f"op {name} already registered")
        _OPS[name] = opdef
        for a in aliases:
            _OPS.setdefault(a, opdef)
        return fn

    return deco


def alias(name: str, target: str) -> None:
    _OPS[name] = _OPS[target]


def register_sparse(name: str, stypes: Sequence[str]) -> Callable:
    """Register an FComputeEx kernel for ``name`` with the given input
    storage-type signature, e.g. ``("csr", "default")``. The kernel receives
    the frontend NDArray/sparse objects directly (it owns device dispatch
    and tape recording) and returns NDArray or sparse NDArray outputs
    (ref: op_attr_types.h:282 FComputeEx; DispatchMode::kFComputeEx)."""

    def deco(fn: Callable) -> Callable:
        _SPARSE_IMPLS[(name, tuple(stypes))] = fn
        return fn

    return deco


def stype_dispatch(name: str, stypes: Sequence[str]) -> Optional[Callable]:
    """FInferStorageType analog: pick the FComputeEx kernel for this input
    stype combination, or None → dense fallback
    (DispatchMode::kFComputeFallback). Signature matching: exact tuple,
    then signatures whose tail is "*" (any remaining inputs), then the
    full wildcard ("*",)."""
    stypes = tuple(stypes)
    impl = _SPARSE_IMPLS.get((name, stypes))
    if impl is not None:
        return impl
    for (n, sig), fn in _SPARSE_IMPLS.items():
        if n != name or not sig or sig[-1] != "*":
            continue
        head = sig[:-1]
        if stypes[:len(head)] == head:
            return fn
    return _SPARSE_IMPLS.get((name, ("*",)))


def storage_fallback_warn(name: str, stypes: Sequence[str]) -> None:
    """Log the sparse→dense fallback once per (op, stypes), like the
    reference's LogStorageFallback (src/common/utils.h); silenced by
    MXNET_STORAGE_FALLBACK_LOG_VERBOSE=0 (ref: docs/faq/env_var.md)."""
    key = (name, tuple(stypes))
    if key in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(key)
    if not env.get("MXNET_STORAGE_FALLBACK_LOG_VERBOSE"):
        return
    import warnings
    warnings.warn(
        f"operator {name} has no sparse kernel for input storage types "
        f"{tuple(stypes)}: falling back to dense compute (inputs densified). "
        "Set MXNET_STORAGE_FALLBACK_LOG_VERBOSE=0 to silence.",
        stacklevel=3)


def get_op(name: str) -> OpDef:
    try:
        return _OPS[name]
    except KeyError:
        raise MXNetError(f"operator {name!r} is not registered") from None


def list_ops() -> List[str]:
    return sorted(_OPS)


def _naive_engine() -> bool:
    return env.get("MXNET_ENGINE_TYPE") == "NaiveEngine"


def normalize_params(params: Dict[str, Any]) -> Dict[str, Any]:
    return {k: coerce_param(v) for k, v in params.items() if v is not None}


def _trace_time_flags() -> Tuple:
    """Env flags read INSIDE op impls at trace time (they change the
    compiled program, so they must be part of the jit-cache key —
    otherwise toggling the flag after first compile is a silent no-op)."""
    return (bool(env.get("MXNET_SAFE_ACCUMULATION")),
            env.get("MXNET_RESID_DTYPE") or "",
            env.get("MXNET_CONV_COMPUTE") or "",
            float(env.get("MXNET_CONV_INT8_RANGE")),
            bool(env.get("MXTPU_FUSED_EPILOGUE")))


#: span args of an eager op: the one executable it launches (shared: the
#: tracer copies)
_ONE_PROGRAM = {"programs": 1}


def invoke_jax(opdef: OpDef, arrays: Sequence, params: Dict[str, Any]):
    """Execute an op on raw jax arrays through the jit cache.

    Returns whatever the impl returns (array or tuple). Equivalent position in
    the stack to Imperative::InvokeOp (ref src/imperative/imperative.cc:38),
    with the engine push replaced by XLA async dispatch.
    """
    params = normalize_params(params)
    dyn = {}
    if opdef.dynamic_params:
        import numbers
        for n in opdef.dynamic_params:
            # numbers.Real (not just int/float): an lr computed by a
            # numpy-based LRScheduler arrives as np.float32, which is not
            # a python float — missing it would bake the value into the
            # jit-cache key and recompile every step
            if n in params and isinstance(params[n], numbers.Real) \
                    and not isinstance(params[n], bool):
                # plain python float: jit traces it as a WEAK-typed scalar,
                # so `weight - lr * g` keeps the weight's (bf16) dtype —
                # a strong f32 scalar would silently promote the update
                dyn[n] = float(params.pop(n))
    key = hashable_params(params) + _trace_time_flags()
    with _span(opdef.name, "operator", _ONE_PROGRAM):
        try:
            out = opdef.jitted(key, tuple(dyn))(tuple(dyn.values()), *arrays)
        except TypeError:
            # Non-jittable param combination (e.g. python callable param):
            # fall back to direct tracing-free eval.
            out = opdef.fn(*arrays, **params, **dyn)
        if _naive_engine():
            import jax
            jax.block_until_ready(out)
    return out


def eval_shape(opdef: OpDef, in_shapes: Sequence[Tuple[int, ...]],
               in_dtypes: Sequence[Any], params: Dict[str, Any]):
    """Shape/dtype inference via abstract evaluation (ref: FInferShape /
    FInferType attr functions, src/executor/infer_graph_attr_pass.cc)."""
    import jax
    import jax.numpy as jnp
    params = normalize_params(params)
    specs = [jax.ShapeDtypeStruct(tuple(s), jnp.dtype(d))
             for s, d in zip(in_shapes, in_dtypes)]
    out = jax.eval_shape(lambda *xs: opdef.fn(*xs, **params), *specs)
    if not isinstance(out, (tuple, list)):
        out = (out,)
    return [tuple(o.shape) for o in out], [o.dtype for o in out]


def as_tuple_outputs(out) -> Tuple:
    if isinstance(out, (tuple, list)):
        return tuple(out)
    return (out,)
