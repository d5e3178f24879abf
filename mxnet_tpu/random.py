"""Global functional PRNG state.

Reference: python/mxnet/random.py (mx.random.seed) over per-device PRNG
resources (include/mxnet/resource.h kRandom). TPU-native: one root
``jax.random`` key per process; every random op consumes a fresh split.
``seed(n)`` makes the whole program reproducible (the reference needed
per-device seeding; XLA's threefry is deterministic per key regardless of
partitioning).
"""
from __future__ import annotations

import threading
from typing import Optional

__all__ = ["seed", "next_key", "uniform", "normal", "randint", "gamma",
           "exponential", "poisson", "negative_binomial",
           "generalized_negative_binomial", "multinomial", "shuffle"]

_lock = threading.Lock()
_key = None
_counter = 0
# None until the user calls seed() explicitly (MXNET_ENFORCE_DETERMINISM
# uses this to detect unseeded host-side sampling)
_seed_value = None

# While tracing a CachedOp/jitted graph, random ops must derive their keys
# from a *traced* key input (otherwise the trace would bake one fixed mask
# into the compiled program). push_trace_key installs that traced key; each
# next_key() call folds in a counter so every op in the graph gets a distinct,
# per-invocation-fresh stream.
_trace_stack = threading.local()

#: executables one eager ``next_key()`` launches: its two ``fold_in``s, each
#: behind the cast of its Python int to uint32. The span round a caller
#: (``mx.cached_op.prepare``) owns them by this number; a test holds it to
#: the launches it counts.
NEXT_KEY_PROGRAMS = 4


def _jr():
    import jax.random as jr
    return jr


def seed(seed_state: int, ctx=None) -> None:
    """Reset the root key (ref: python/mxnet/random.py seed)."""
    global _key, _seed_value, _counter
    with _lock:
        _seed_value = int(seed_state)
        _key = _jr().PRNGKey(_seed_value)
        _counter = 0


def push_trace_key(key) -> None:
    if not hasattr(_trace_stack, "stack"):
        _trace_stack.stack = []
    _trace_stack.stack.append([key, 0])


def pop_trace_key() -> None:
    _trace_stack.stack.pop()


def next_key():
    """Derive a fresh subkey for one sampling op.

    The root key is NEVER mutated with the result of a jax op: splitting
    under an active jit trace would store a tracer into module state
    (UnexpectedTracerError on the next eager call). Instead subkeys are
    fold_in(root, counter) — the counter is plain python state, safe to
    advance during tracing.
    """
    stack = getattr(_trace_stack, "stack", None)
    if stack:
        entry = stack[-1]
        entry[1] += 1
        return _jr().fold_in(entry[0], entry[1])
    global _key, _counter
    with _lock:
        if _key is None:
            import jax
            # force eager creation even if the first next_key() happens
            # inside a jit trace — a staged PRNGKey would be a tracer
            with jax.ensure_compile_time_eval():
                _key = _jr().PRNGKey(0)
        _counter += 1
        # distinguished fold so the eager stream cannot collide with a
        # trace-key stream even when a caller pushes the root key itself
        # (NEXT_KEY_PROGRAMS counts what these two calls launch)
        return _jr().fold_in(_jr().fold_in(_key, 0xEA6E4), _counter)


def np_rng() -> "_numpy.random.Generator":
    """Numpy Generator seeded from the mx.random key stream.

    Host-side samplers (e.g. the DGL neighbor samplers, which are numpy
    graph algorithms) draw from this instead of the global numpy RNG so
    that `mx.random.seed()` makes them reproducible like every
    device-side random op.

    Under MXNET_ENFORCE_DETERMINISM, using a host-side sampler without an
    explicit mx.random.seed() is an error (the run would not be
    reproducible across restarts)."""
    import numpy as _numpy
    from .base import MXNetError, env
    if env.get("MXNET_ENFORCE_DETERMINISM") and _seed_value is None:
        raise MXNetError(
            "MXNET_ENFORCE_DETERMINISM is set but mx.random.seed() was "
            "never called — host-side sampling would be irreproducible")
    k = next_key()
    try:
        raw = _jr().key_data(k)  # typed keys (jax >= 0.4.16)
    except Exception:
        raw = k  # raw uint32 key arrays
    seed_words = _numpy.asarray(raw).astype(_numpy.uint32).reshape(-1)
    return _numpy.random.default_rng(_numpy.random.SeedSequence(seed_words))


def _nd():
    from .ndarray import register as ndreg
    return ndreg.registry_namespace()


def uniform(low=0, high=1, shape=(1,), dtype=None, ctx=None, out=None):
    from .ndarray import op as _op
    return _op._random_uniform(low=low, high=high, shape=shape, dtype=dtype,
                               ctx=ctx, out=out)


def normal(loc=0, scale=1, shape=(1,), dtype=None, ctx=None, out=None):
    from .ndarray import op as _op
    return _op._random_normal(loc=loc, scale=scale, shape=shape, dtype=dtype,
                              ctx=ctx, out=out)


def randint(low, high, shape=(1,), dtype="int32", ctx=None, out=None):
    from .ndarray import op as _op
    return _op._random_randint(low=low, high=high, shape=shape, dtype=dtype,
                               ctx=ctx, out=out)


def gamma(alpha=1, beta=1, shape=(1,), dtype=None, ctx=None, out=None):
    from .ndarray import op as _op
    return _op._random_gamma(alpha=alpha, beta=beta, shape=shape, dtype=dtype,
                             ctx=ctx, out=out)


def exponential(scale=1, shape=(1,), dtype=None, ctx=None, out=None):
    from .ndarray import op as _op
    return _op._random_exponential(lam=1.0 / scale, shape=shape, dtype=dtype,
                                   ctx=ctx, out=out)


def poisson(lam=1, shape=(1,), dtype=None, ctx=None, out=None):
    from .ndarray import op as _op
    return _op._random_poisson(lam=lam, shape=shape, dtype=dtype, ctx=ctx,
                               out=out)


def negative_binomial(k=1, p=1, shape=(1,), dtype=None, ctx=None, out=None):
    from .ndarray import op as _op
    return _op._random_negative_binomial(k=k, p=p, shape=shape, dtype=dtype,
                                         ctx=ctx, out=out)


def generalized_negative_binomial(mu=1, alpha=1, shape=(1,), dtype=None,
                                  ctx=None, out=None):
    from .ndarray import op as _op
    return _op._random_generalized_negative_binomial(
        mu=mu, alpha=alpha, shape=shape, dtype=dtype, ctx=ctx, out=out)


def multinomial(data, shape=(), get_prob=False, out=None, dtype="int32"):
    from .ndarray import op as _op
    return _op._sample_multinomial(data, shape=shape, get_prob=get_prob,
                                   dtype=dtype, out=out)


def shuffle(data, out=None):
    from .ndarray import op as _op
    return _op._shuffle(data, out=out)
