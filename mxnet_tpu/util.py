"""Misc utilities (ref: python/mxnet/util.py)."""
from __future__ import annotations

import functools
import inspect
import threading

__all__ = ["makedirs", "use_np_shape", "is_np_shape", "set_np_shape",
           "wrap_ctx_to_device_func", "getenv", "setenv"]

import os


def makedirs(d):
    os.makedirs(os.path.expanduser(d), exist_ok=True)


_np_shape = threading.local()


def is_np_shape() -> bool:
    return getattr(_np_shape, "value", True)


def set_np_shape(active: bool) -> bool:
    prev = is_np_shape()
    _np_shape.value = active
    return prev


def use_np_shape(func):
    @functools.wraps(func)
    def wrapper(*args, **kwargs):
        prev = set_np_shape(True)
        try:
            return func(*args, **kwargs)
        finally:
            set_np_shape(prev)
    return wrapper


def wrap_ctx_to_device_func(func):
    return func


def mirror_enabled(explicit=None) -> bool:
    """Whether backward rematerialization is on: an explicit argument wins,
    else the MXNET_BACKWARD_DO_MIRROR env flag (ref: the mirror_fun path of
    src/nnvm/gradient.cc:271 — the reference's only memory-for-compute
    lever; on TPU this maps to jax.checkpoint)."""
    if explicit is not None:
        return bool(explicit)
    from .base import env
    return bool(env.get("MXNET_BACKWARD_DO_MIRROR"))


def _mirror_policy(name):
    """The ``jax.checkpoint`` policy a MXNET_BACKWARD_MIRROR_POLICY name
    stands for (None: save nothing)."""
    import jax
    if name in ("full", ""):
        return None
    if name == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    if name == "convs":
        def policy(prim, *_args, **_params):
            return prim.name in ("conv_general_dilated", "dot_general")
        return policy
    from .base import MXNetError
    raise MXNetError(
        f"unknown MXNET_BACKWARD_MIRROR_POLICY {name!r} "
        "(expected 'full', 'dots' or 'convs')")


def mirror_wrapper(explicit=None):
    """Resolve the mirror decision NOW and return the wrapper to apply.

    Program builders must call THIS on the host side (outside the traced
    function) and apply the returned wrapper inside the trace: the
    MXNET_BACKWARD_DO_MIRROR / MXNET_BACKWARD_MIRROR_POLICY knobs are
    then read at program-BUILD time — a defined, observable moment —
    instead of being baked invisibly into the first trace (graftcheck
    GC-T03; the MXNET_SAFE_ACCUMULATION cache-key discipline's sibling).
    """
    if not mirror_enabled(explicit):
        return lambda fn: fn
    import jax
    from .base import env
    policy = _mirror_policy(env.get("MXNET_BACKWARD_MIRROR_POLICY") or "full")
    return lambda fn: jax.checkpoint(fn, policy=policy)


# What a recorded hybridized forward keeps when mirroring is off: the
# outputs that are expensive to make again (convolutions, matmuls) and the
# reductions, whose outputs are small beside their inputs (BatchNorm's
# per-channel sums). The elementwise chain between them (normalise, scale,
# shift, activation) is recomputed inside the backward program.
RESIDUAL_DEFAULT = "elementwise"


def _save_convs_dots_reductions(prim, *avals, **params):
    import jax
    if prim.name in ("conv_general_dilated", "reduce_sum", "reduce_max",
                     "reduce_min"):
        return True
    # a matmul with batch dimensions is attention's T x T scores a head
    return jax.checkpoint_policies.dots_with_no_batch_dims_saveable(
        prim, *avals, **params)


def residual_policy_name(explicit=None) -> str:
    """Name of what a recorded CachedOp forward recomputes in its backward
    (read on the host, once a call: it is part of the program's cache
    key): the MXNET_BACKWARD_MIRROR_POLICY in force when mirroring is on,
    else :data:`RESIDUAL_DEFAULT`."""
    if not mirror_enabled(explicit):
        return RESIDUAL_DEFAULT
    from .base import env
    return env.get("MXNET_BACKWARD_MIRROR_POLICY") or "full"


def residual_policy(name: str):
    """The ``jax.checkpoint`` policy for a :func:`residual_policy_name`."""
    if name == RESIDUAL_DEFAULT:
        return _save_convs_dots_reductions
    return _mirror_policy(name)


def apply_mirror(fn, explicit=None):
    """Wrap a traceable function in jax.checkpoint when mirroring is on.

    The backward pass then stores only the function's inputs (plus
    whatever the MXNET_BACKWARD_MIRROR_POLICY keeps) and recomputes
    intermediate activations — XLA fuses the recompute into the backward
    program. Policies:
      full (default) - save nothing, recompute everything (max savings)
      dots           - save matmul/einsum results, recompute elementwise
                       (closest to the reference's mirror of cheap ops)
      convs          - save conv AND matmul results, recompute elementwise
                       (the conv-net sweet spot: halves saved-activation
                       HBM traffic — each layer stores one tensor, the
                       conv output, instead of conv output + post-BN/ReLU
                       activation — at the cost of re-running the cheap
                       normalize/activation chain inside backward)
    Eager convenience over :func:`mirror_wrapper` — fine host-side (the
    remat tests, one-shot wraps); code that BUILDS jitted programs must
    resolve ``mirror_wrapper()`` outside the trace instead.
    """
    return mirror_wrapper(explicit)(fn)


def getenv(name):
    from .base import env
    return env.raw(name)


def setenv(name, value):
    os.environ[name] = value


def enable_compile_cache(cache_dir=None):
    """Persistent XLA compilation cache for scripts (chip_smoke.py, bench.py,
    __graft_entry__.py, tools/): whole-graph compiles for the chip take
    tens of seconds and reruns hit the cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache is placed from
    outside: JAX reads the variable and no directory is set in code.
    Otherwise explicit ``cache_dir`` > ``MXTPU_COMPILE_CACHE`` >
    ``<checkout>/.jax_cache``. A CPU-only process skips that last default
    (its compiles are fast) and returns ``"skipped-cpu"``. Returns the
    directory in effect, or None when switched off."""
    import jax
    from .base import env
    from .serving.aot import enable_compile_cache as _wire
    if cache_dir is None and not env.get("MXTPU_COMPILE_CACHE") \
            and not env.raw("JAX_COMPILATION_CACHE_DIR"):
        # nothing placed the cache: ask which platform this process runs
        # on. An explicit JAX_PLATFORMS request is read from the config
        # without initializing a backend.
        plat = jax.config.jax_platforms or jax.default_backend()
        if plat.split(",")[0].strip() == "cpu":
            return "skipped-cpu"  # truthy: intentional skip, not a failure
        cache_dir = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
    return _wire(cache_dir)
