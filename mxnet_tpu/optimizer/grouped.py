"""Aggregated (multi-tensor) optimizer updates.

Reference: src/operator/optimizer_op.cc:654 multi_sgd_update and the
``MXNET_OPTIMIZER_AGGREGATION_SIZE`` knob — the reference fuses groups of
small parameters into one kernel launch because per-parameter dispatch
dominates step time on models with hundreds of tensors.

TPU-native version: a whole dtype/device bucket of parameters is stepped by
ONE jitted pytree-level program per (optimizer, bucket signature), cached by
signature the way :class:`~mxnet_tpu.cached_op.CachedOp` caches compiled
graphs, so regrouping/resharding re-uses programs instead of recompiling
every step. Weight and optimizer-state buffers are **donated** into the
program (``donate_argnums``) so the update stops double-buffering optimizer
memory; gradients are NOT donated (they stay readable for the sentinel,
chaos hooks and user inspection, exactly like the per-parameter path).

The reference's cap of 4 parameters a launch does NOT carry over: it is the
argument-list limit of a CUDA kernel, and a jitted XLA program has none
(``SPMDTrainer.step`` updates every tensor inside one program). With
``MXTPU_OPTIMIZER_AGGREGATION`` unset a bucket key is ONE program however
many parameters it holds, so the count of update launches does not grow
with the model: ResNet-50's 161 tensors were 41 launches at a cap of 4,
more than the 32 programs the TPU client keeps in flight, and the host
stalled behind the backward program every step. Nothing overlaps with a
split update either: every gradient comes out of one backward program, and
the ZeRO/overlap plane cuts its items by communication bucket before it
calls :func:`grouped_update`. An explicit positive value still caps a
program; ``0`` disables the grouped path. What the cap bought and the
default gives up: under ``ignore_stale_grad=True`` a fresh set that changes
from step to step is a new signature for the whole key and not for one
bucket of four; the signature LRU (``MXTPU_CACHEDOP_CACHE_SIZE``) bounds
what that keeps.

The per-parameter update math is the SAME pure function the per-parameter
ops use (``ops/optimizer_ops.py``), so the aggregated step is numerically
the per-parameter step minus the dispatch overhead. The FitLoop
global-finiteness sentinel folds in: one fused reduction over every
gradient produces a device flag, and each bucket program guards its
updates with ``where(ok, new, old)`` — a non-finite step costs zero
parameter bytes and the host only fetches one scalar.
"""
from __future__ import annotations

import functools
import math as _math
import operator
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as _np

from ..base import MXNetError, check, env
from ..telemetry import efficiency as _efficiency
from ..telemetry import memory as _memory

__all__ = ["aggregation_size", "eligible", "grouped_update",
           "sparse_rows_update", "prepare_update", "chunk_prepared",
           "apply_chunk", "global_finite_flag", "rollback_counts",
           "cache_info", "clear_cache", "program_memory"]


def _jnp():
    import jax.numpy as jnp
    return jnp


def aggregation_size() -> int:
    """Parameters-a-program cap from ``MXTPU_OPTIMIZER_AGGREGATION``: 0 =
    grouped path off; unset = the declared default ``sys.maxsize``, which
    no bucket reaches, so a bucket key is one program."""
    try:
        return int(env.get("MXTPU_OPTIMIZER_AGGREGATION"))
    except (TypeError, ValueError):
        return 0


# ---------------------------------------------------------------------------
# Per-optimizer grouping rules.
#
# A rule maps one parameter's (weight, grad, state arrays, lr, wd, rescale)
# to (new weight, new state arrays) using the SAME kernel function the
# per-parameter path invokes. ``statics`` is every hyper-parameter baked
# into the traced program — part of the cache key.
# ---------------------------------------------------------------------------

_RULES: Dict[str, Any] = {}


class _Rule:
    __slots__ = ("name", "statics", "make_kernel")

    def __init__(self, name, statics, make_kernel):
        self.name = name
        self.statics = statics          # opt -> hashable tuple
        self.make_kernel = make_kernel  # (opt, has_state) -> kernel fn


def _rule(cls_name, statics, make_kernel):
    _RULES[cls_name] = _Rule(cls_name, statics, make_kernel)


def _clipv(cg):
    return -1.0 if cg is None else float(cg)


def _sgd_statics(opt):
    return (float(opt.momentum), _clipv(opt.clip_gradient))


def _sgd_kernel(opt, has_state):
    from ..ops.optimizer_ops import _sgd_update, _sgd_mom_update
    mom, clip = float(opt.momentum), _clipv(opt.clip_gradient)
    if not has_state:
        def k(w, g, states, lr, wd, rs):
            return _sgd_update(w, g, lr=lr, wd=wd, rescale_grad=rs,
                               clip_gradient=clip), ()
    else:
        def k(w, g, states, lr, wd, rs):
            nw, nm = _sgd_mom_update(w, g, states[0], lr=lr, momentum=mom,
                                     wd=wd, rescale_grad=rs,
                                     clip_gradient=clip)
            return nw, (nm,)
    return k


def _nag_statics(opt):
    return (float(opt.momentum), _clipv(opt.clip_gradient))


def _nag_kernel(opt, has_state):
    from ..ops.optimizer_ops import _sgd_update, _nag_mom_update
    mom, clip = float(opt.momentum), _clipv(opt.clip_gradient)
    if not has_state:
        # NAG without momentum degenerates to plain SGD (ref: NAG.update)
        def k(w, g, states, lr, wd, rs):
            return _sgd_update(w, g, lr=lr, wd=wd, rescale_grad=rs,
                               clip_gradient=clip), ()
    else:
        def k(w, g, states, lr, wd, rs):
            nw, nm = _nag_mom_update(w, g, states[0], lr=lr, momentum=mom,
                                     wd=wd, rescale_grad=rs,
                                     clip_gradient=clip)
            return nw, (nm,)
    return k


def _adam_statics(opt):
    return (float(opt.beta1), float(opt.beta2), float(opt.epsilon),
            _clipv(opt.clip_gradient))


def _adam_kernel(opt, has_state):
    from ..ops.optimizer_ops import _adam_update
    b1, b2, eps = float(opt.beta1), float(opt.beta2), float(opt.epsilon)
    clip = _clipv(opt.clip_gradient)

    def k(w, g, states, lr, wd, rs):
        # lr arrives already bias-corrected (lr_t), exactly like the
        # per-parameter path computes it host-side from the update count
        nw, nm, nv = _adam_update(w, g, states[0], states[1], lr=lr,
                                  beta1=b1, beta2=b2, epsilon=eps, wd=wd,
                                  rescale_grad=rs, clip_gradient=clip)
        return nw, (nm, nv)
    return k


def _rmsprop_statics(opt):
    return (float(opt.gamma1), float(opt.gamma2), float(opt.epsilon),
            bool(opt.centered), _clipv(opt.clip_gradient),
            _clipv(opt.clip_weights))


def _rmsprop_kernel(opt, has_state):
    from ..ops.optimizer_ops import _rmsprop_update, _rmspropalex_update
    g1, g2, eps = float(opt.gamma1), float(opt.gamma2), float(opt.epsilon)
    clip, clipw = _clipv(opt.clip_gradient), _clipv(opt.clip_weights)
    if not opt.centered:
        def k(w, g, states, lr, wd, rs):
            nw, nn = _rmsprop_update(w, g, states[0], lr=lr, gamma1=g1,
                                     epsilon=eps, wd=wd, rescale_grad=rs,
                                     clip_gradient=clip, clip_weights=clipw)
            return nw, (nn,)
    else:
        def k(w, g, states, lr, wd, rs):
            nw, nn, ng, nd = _rmspropalex_update(
                w, g, states[0], states[1], states[2], lr=lr, gamma1=g1,
                gamma2=g2, epsilon=eps, wd=wd, rescale_grad=rs,
                clip_gradient=clip, clip_weights=clipw)
            return nw, (nn, ng, nd)
    return k


_rule("SGD", _sgd_statics, _sgd_kernel)
_rule("NAG", _nag_statics, _nag_kernel)
_rule("Adam", _adam_statics, _adam_kernel)
_rule("RMSProp", _rmsprop_statics, _rmsprop_kernel)


def _rule_for(opt):
    """Exact-type match only: a subclass may override ``update`` with
    different math, so it must NOT silently inherit the parent's fused
    kernel (LBSGD is whitelisted — it does not override SGD.update)."""
    from . import optimizer as _opt
    t = type(opt)
    if t is _opt.SGD or t is _opt.LBSGD:
        return _RULES["SGD"]
    if t is _opt.NAG:
        return _RULES["NAG"]
    if t is _opt.Adam:
        return _RULES["Adam"]
    if t is _opt.RMSProp:
        return _RULES["RMSProp"]
    return None


# ---------------------------------------------------------------------------
# State flattening. ``create_state_multi_precision`` yields, per parameter:
#   non-mp: None | NDArray | tuple[NDArray, ...]
#   mp    : (inner_state, w32)   (active iff multi_precision and w != f32)
# ---------------------------------------------------------------------------

def _mp_active(opt, weight) -> bool:
    return bool(opt.multi_precision) and \
        weight._data.dtype != _np.float32


def _flatten_inner(inner) -> List:
    if inner is None:
        return []
    if isinstance(inner, (tuple, list)):
        return [s for s in inner if s is not None]
    return [inner]


def _state_handles(opt, weight, state) -> Tuple[List, bool]:
    """NDArray handles of one param's state in kernel order; last slot is
    the f32 master weight when multi-precision is active."""
    if _mp_active(opt, weight):
        inner, w32 = state
        return _flatten_inner(inner) + [w32], True
    return _flatten_inner(state), False


def _wrap_mp(base_kernel):
    """Generic multi-precision wrapper, mirroring
    ``Optimizer.update_multi_precision``: cast the grad to f32, update the
    f32 master copy, cast the result back into the working weight."""
    def k(w, g, states, lr, wd, rs):
        w32 = states[-1]
        nw32, ns = base_kernel(w32, g.astype(w32.dtype), states[:-1],
                               lr, wd, rs)
        return nw32.astype(w.dtype), ns + (nw32,)
    return k


def _with_cast(kernel, mp: bool):
    """Cast the dynamic f32 scalars to the kernel's compute dtype so
    low-precision params see the same arithmetic as the per-param path's
    weak-typed python floats (a strong f32 scalar would silently promote
    a bf16 update to f32)."""
    def k(w, g, states, lr, wd, rs):
        cdt = states[-1].dtype if mp else w.dtype
        return kernel(w, g, states, lr.astype(cdt), wd.astype(cdt),
                      rs.astype(cdt))
    return k


# ---------------------------------------------------------------------------
# Signature-keyed compiled-program cache: the CachedOp discipline, shared
# via cached_op.SignatureLRU (LRU-bounded by MXTPU_CACHEDOP_CACHE_SIZE,
# hit/miss/eviction counters).
# ---------------------------------------------------------------------------

def _cache():
    global _CACHE
    if _CACHE is None:
        from ..cached_op import SignatureLRU
        _CACHE = SignatureLRU()
    return _CACHE


_CACHE = None


def cache_info():
    return _cache().cache_info()


def clear_cache():
    _cache().clear()


def _sig_fields(sig) -> Optional[Tuple]:
    """(rule_name, sentinel, donated_sig, grads_sig) of one cache key, or
    None for a foreign entry (the shared-LRU discipline)."""
    try:
        if len(sig) == 6:
            # stats-emitting variant (MXTPU_NUMERICS sampled steps)
            rule_name, _statics, sentinel, _stats, donated_sig, \
                grads_sig = sig
        else:
            rule_name, _statics, sentinel, donated_sig, grads_sig = sig
        return rule_name, sentinel, donated_sig, grads_sig
    except (TypeError, ValueError):
        return None


def _lower_sig(sig, fn):
    """Re-lower one cached bucket program from its signature-key's
    abstract arguments to a jax ``Compiled`` (one trace; a disk read,
    not a recompile, under a persistent compile cache) — the CachedOp
    discipline ``spmd.program_stats`` established. None for foreign or
    un-lowerable entries."""
    import jax
    import numpy as _np2
    fields = _sig_fields(sig)
    if fields is None:
        return None
    _rule_name, sentinel, donated_sig, grads_sig = fields
    f32 = _np2.dtype("float32")
    n = len(donated_sig)
    vec = jax.ShapeDtypeStruct((n,), f32)
    scalar = jax.ShapeDtypeStruct((), f32)
    try:
        donated = tuple(
            tuple(jax.ShapeDtypeStruct(tuple(s), _np2.dtype(dt))
                  for s, dt in bundle) for bundle in donated_sig)
        grads = tuple(jax.ShapeDtypeStruct(tuple(s), _np2.dtype(dt))
                      for s, dt in grads_sig)
        if sentinel:
            ok = jax.ShapeDtypeStruct((), _np2.dtype(bool))
            return fn.lower(vec, vec, scalar, ok, donated,
                            grads).compile()
        return fn.lower(vec, vec, scalar, donated, grads).compile()
    except Exception:
        return None  # un-lowerable entry must not break the report


def _analyze_sig(sig, fn, refresh: bool = False,
                 need_cost: bool = False) -> Optional[dict]:
    """Combined cost+memory analysis of one cached bucket program, via
    the ONE shared extraction helper, recorded in the telemetry program
    registry (kind ``optimizer``) and cached there until ``refresh`` (or
    until ``need_cost`` finds a memory-only record to upgrade). A
    FAILED resolution is cached too (``unavailable``/``cost_unavailable``
    markers): a backend whose analyses are missing must cost one lower,
    not one per step — ``refresh=True`` is the retry path."""
    import hashlib
    fields = _sig_fields(sig)
    if fields is None:
        return None
    rule_name = fields[0]
    digest = hashlib.md5(repr(sig).encode()).hexdigest()[:12]
    label = f"{rule_name}:{digest}"
    cached = _memory.get_program("optimizer", label)
    if cached is not None and not refresh and \
            (not need_cost or "flops" in cached or
             cached.get("unavailable") or cached.get("cost_unavailable")):
        return cached
    compiled = _lower_sig(sig, fn)
    stats = _efficiency.compiled_program_stats(compiled)
    if stats is None:
        stats = {"unavailable": True}
    stats = dict(stats, signature=digest, params=len(fields[2]))
    if "flops" not in stats:
        stats["cost_unavailable"] = True
    _memory.record_program("optimizer", label, stats)
    return stats


def program_memory(refresh: bool = False) -> Dict[str, dict]:
    """Static memory attribution of every cached bucket program:
    ``{signature_digest: {argument_bytes, output_bytes, temp_bytes, ...}}``
    from ``compiled.memory_analysis()``. The abstract argument signature
    is reconstructed from the cache key, so this re-lowers (one trace; a
    disk read, not a recompile, under a persistent compile cache) — the
    CachedOp discipline ``spmd.program_stats`` established. Results are
    recorded in the telemetry program registry (kind ``optimizer``) and
    cached until ``refresh``. Records may additionally carry the
    cost-model fields (``flops`` / ``bytes_accessed``) when the
    efficiency plane resolved this program."""
    out: Dict[str, dict] = {}
    for sig, fn in _cache().snapshot_items():
        stats = _analyze_sig(sig, fn, refresh=refresh)
        if stats is None or "argument_bytes" not in stats:
            continue
        out[stats["signature"]] = stats
    return out


def _build_bucket_fn(kernels, guarded: bool, stats: bool = False):
    """One jitted program stepping a whole bucket.

    Arguments: (lrs, wds, rescale[, ok], donated, grads) where ``donated``
    is a tuple of per-param (weight, *state_arrays) tuples — donated to the
    program so XLA writes updates into the same buffers — and ``grads`` is
    the matching tuple of gradient arrays (NOT donated).

    With ``stats`` (a numerics-plane sampled step, ``MXTPU_NUMERICS``),
    the program additionally returns one ``(n_params, 6)`` f32 matrix of
    per-parameter tensor statistics in :data:`telemetry.numerics
    .RAW_FIELDS` order — computed from the SAME traced values the update
    consumes (grads pre-guard, weights pre-update, the would-be update
    delta), so a sampled step costs extra outputs, not extra dispatches,
    and the update math itself is untouched (bitwise-parity pinned).
    """
    import jax
    jnp = _jnp()

    def step(lrs, wds, rescale, ok, donated, grads):
        outs, stat_rows = [], []
        for i, (bundle, g) in enumerate(zip(donated, grads)):
            w, states = bundle[0], tuple(bundle[1:])
            nw, ns = kernels[i](w, g, states, lrs[i], wds[i], rescale)
            if stats:
                gf = g.astype(jnp.float32)
                wf = w.astype(jnp.float32)
                dwf = nw.astype(jnp.float32) - wf
                zero = jnp.zeros((), jnp.float32)
                stat_rows.append(jnp.stack([
                    jnp.sum(gf * gf),
                    jnp.sum(wf * wf),
                    jnp.sum(dwf * dwf),
                    # guard the empty-array reductions (a 0-dim shape):
                    # max raises and mean NaNs on zero elements
                    jnp.max(jnp.abs(gf)) if g.size else zero,
                    jnp.mean(gf) if g.size else zero,
                    jnp.sum(~jnp.isfinite(g)).astype(jnp.float32),
                ]))
            if ok is not None:
                nw = jnp.where(ok, nw, w)
                ns = tuple(jnp.where(ok, a, b) for a, b in zip(ns, states))
            outs.append((nw,) + tuple(ns))
        if stats:
            return tuple(outs), jnp.stack(stat_rows)
        return tuple(outs)

    if guarded:
        def fn(lrs, wds, rescale, ok, donated, grads):
            return step(lrs, wds, rescale, ok, donated, grads)
        return jax.jit(fn, donate_argnums=(4,))

    def fn(lrs, wds, rescale, donated, grads):
        return step(lrs, wds, rescale, None, donated, grads)
    return jax.jit(fn, donate_argnums=(3,))


@functools.lru_cache(maxsize=256)
def _finite_fn(n: int):
    """One fused reduction: every gradient's finiteness AND-ed into a
    single device scalar (replaces FitLoop's per-grad host check)."""
    import jax
    jnp = _jnp()

    def fn(*grads):
        flags = [jnp.isfinite(g).all() for g in grads]
        return functools.reduce(operator.and_, flags)
    return jax.jit(fn)


def _finite_cost(n: int, sig) -> Optional[dict]:
    """Efficiency-plane resolver for the fused finiteness reduction.
    Failed resolutions are cached (``cost_unavailable``) like
    ``_analyze_sig`` — one lower per signature, never one per step."""
    import hashlib

    import jax
    import numpy as _np2
    label = f"finite_flag:{n}:" + hashlib.md5(
        repr(sig).encode()).hexdigest()[:12]
    cached = _memory.get_program("optimizer", label)
    if cached is not None and ("flops" in cached or
                               cached.get("cost_unavailable")):
        return cached
    try:
        avals = tuple(jax.ShapeDtypeStruct(tuple(s), _np2.dtype(dt))
                      for s, dt in sig)
        compiled = _finite_fn(n).lower(*avals).compile()
        stats = _efficiency.compiled_program_stats(compiled)
    except Exception:
        stats = None
    if stats is None:
        stats = {"unavailable": True}
    if "flops" not in stats:
        stats = dict(stats, cost_unavailable=True)
    _memory.record_program("optimizer", label, dict(stats))
    return stats


def global_finite_flag(grads):
    """Device-resident all-finite scalar over raw jax arrays (no host
    sync; the caller fetches it together with the loss)."""
    fn = _finite_fn(len(grads))
    if _efficiency.enabled():
        sig = tuple((tuple(g.shape), str(g.dtype)) for g in grads)
        _efficiency.note_dispatch(
            ("finite", sig), "optimizer", f"finite_flag:{len(grads)}",
            functools.partial(_finite_cost, len(grads), sig))
    return fn(*grads)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def _is_dense(p) -> bool:
    from ..ndarray.sparse import BaseSparseNDArray
    if p.stype != "default":
        return False
    g = p._grad
    return g is not None and not isinstance(g, BaseSparseNDArray)


def eligible(updater, items) -> bool:
    """True when EVERY (index, Parameter) item can ride the grouped path:
    a grouping rule exists for the optimizer and all params/grads are
    dense. All-or-nothing by design — the fused sentinel's skip decision
    must cover the complete parameter set or none of it."""
    if not items:
        return False
    if _rule_for(updater.optimizer) is None:
        return False
    return all(_is_dense(p) for _, p in items)


def _devices_key(arr) -> Tuple:
    devs = getattr(arr, "devices", None)
    if devs is None:
        return ()
    try:
        return tuple(sorted(d.id for d in arr.devices()))
    except Exception:
        return ()


def prepare_update(updater, items):
    """HOST half of one aggregated step over ``items``: state creation
    (ledger-tracked), update-count bumps, and lr/wd resolution — every
    count bumps before any lr is resolved within the step, identical to
    the per-param loop's order. Pure host bookkeeping, no device work.
    Returns ``(prepared, created)`` where ``prepared`` entries are
    ``(index, Parameter, state_handles, mp, lr, wd)`` and ``created``
    lists indices whose optimizer state this call first materialized
    (rollback must delete them again)."""
    opt = updater.optimizer
    rule = _rule_for(opt)
    check(rule is not None,
          f"optimizer {type(opt).__name__} has no grouped-update rule")
    for _, p in items:
        if not _is_dense(p):
            raise MXNetError(
                f"grouped optimizer update requires dense parameters and "
                f"gradients; {p.name!r} (stype={p.stype!r}, grad_stype="
                f"{getattr(p, 'grad_stype', 'default')!r}) has no fused "
                "dense bucket. Sparse tables opt into the row-gathered "
                "grouped path with MXTPU_SPARSE_PLANE=on + "
                "parallel.embedding_plane.EmbeddingPlane (which calls "
                "sparse_rows_update); outside the plane, sparse "
                "parameters take the per-parameter lazy-update loop "
                "(Trainer routes them there automatically).")

    is_adam = rule.name == "Adam"
    created = []
    for i, p in items:
        if i not in updater.states:
            updater.states[i] = opt.create_state_multi_precision(i, p.data())
            created.append(i)
            _memory.track_optimizer_state(updater, i, updater.states[i],
                                          param=p)
        opt._update_count(i)

    prepared = []
    for i, p in items:
        lr, wd = opt._get_lr(i), opt._get_wd(i)
        if is_adam:
            t = opt._index_update_count[i]
            lr = lr * _math.sqrt(1 - opt.beta2 ** t) / (1 - opt.beta1 ** t)
        handles, mp = _state_handles(opt, p, updater.states[i])
        prepared.append((i, p, handles, mp, float(lr), float(wd)))
    return prepared, created


def chunk_prepared(prepared, agg_size: int):
    """Bucket ``prepared`` entries by (weight dtype, device placement,
    mp-ness, state arity), preserving parameter order within a bucket;
    a bucket longer than ``agg_size`` (only an explicit
    ``MXTPU_OPTIMIZER_AGGREGATION`` is ever that small) is cut into
    chunks of that many. Pure function of the prepared structure."""
    buckets: "OrderedDict[Tuple, List]" = OrderedDict()
    for ent in prepared:
        i, p, handles, mp = ent[0], ent[1], ent[2], ent[3]
        bkey = (str(p._data._data.dtype), _devices_key(p._data._data), mp,
                len(handles))
        buckets.setdefault(bkey, []).append(ent)

    chunks = []
    for ents in buckets.values():
        for s in range(0, len(ents), max(1, agg_size)):
            chunks.append(ents[s:s + max(1, agg_size)])
    return chunks


def apply_chunk(updater, rule, chunk, lrs, wds, rescale,
                sentinel: bool = False, flag=None, stats_out=None):
    """DEVICE half for ONE chunk: signature → cached jitted bucket
    program → call → rebind weights/states. ``lrs``/``wds``/``rescale``
    are dynamic inputs of the program (f32 vectors over the chunk / an
    f32 scalar): Adam's bias-corrected lr changes every step, and a
    baked value would retrace. :func:`grouped_update` hands them over as
    host float32 numpy arrays, which travel with this launch
    (``jnp.asarray`` of a Python list is a put plus a cast program of
    its own). Returns the handled indices."""
    opt = updater.optimizer
    collect = stats_out is not None
    statics_key = rule.statics(opt)
    donated, grads = [], []
    for (_i, p, handles, _mp, _lr, _wd) in chunk:
        donated.append((p._data._data,) +
                       tuple(h._data for h in handles))
        grads.append(p._grad._data)
    donated = tuple(donated)
    grads = tuple(grads)
    # the stats variant inserts one True element; the stats-free
    # signature stays the historical 5-tuple, so warm caches (and
    # program_memory consumers) are untouched
    sig = ((rule.name, statics_key, bool(sentinel)) +
           ((True,) if collect else ()) +
           (tuple(tuple((tuple(a.shape), str(a.dtype))
                        for a in bundle) for bundle in donated),
            tuple((tuple(g.shape), str(g.dtype)) for g in grads)))

    def _build(chunk=chunk, s=sentinel, c=collect):
        # kernel closures are built ONLY on a signature-cache miss —
        # the warm path (every step after the first) pays a key
        # lookup, not O(params) closure allocations
        kernels = []
        for (_i2, _p2, handles2, mp2, _lr2, _wd2) in chunk:
            n_inner = len(handles2) - (1 if mp2 else 0)
            k = rule.make_kernel(opt, n_inner > 0)
            if mp2:
                k = _wrap_mp(k)
            kernels.append(_with_cast(k, mp2))
        return _build_bucket_fn(tuple(kernels), s, stats=c)

    fn = _cache().get_or_build(sig, _build)
    # efficiency plane (MXTPU_EFFICIENCY): one launch of this bucket
    # program into the current step window — the cost resolves
    # lazily at step end through the SAME registry record
    # program_memory fills. One cached env check when off.
    if _efficiency.enabled():
        _efficiency.note_dispatch(
            ("opt", sig), "optimizer",
            f"{rule.name}:bucket{len(chunk)}",
            functools.partial(_analyze_sig, sig, fn, need_cost=True))
    if sentinel:
        outs = fn(lrs, wds, rescale, flag, donated, grads)
    else:
        outs = fn(lrs, wds, rescale, donated, grads)
    if collect:
        outs, srows = outs
        stats_out.append(
            (tuple(e[1].name for e in chunk), srows))
    handled = []
    for (i, p, handles, _mp, _lr, _wd), bundle_out in zip(chunk, outs):
        p._data._rebind(bundle_out[0])
        for h, arr in zip(handles, bundle_out[1:]):
            h._rebind(arr)
        handled.append(i)
    return handled


def grouped_update(updater, items, agg_size: int, sentinel: bool = False,
                   sentinel_grads=None, sentinel_flag=None,
                   stats_out=None):
    """Apply one aggregated optimizer step to ``items`` ([(index, Parameter)]
    with fresh dense gradients).

    ``sentinel_grads``: the raw grad arrays the finiteness flag must cover
    — the CALLER's full live set, which may be wider than ``items`` (a
    stale param skipped under ``ignore_stale_grad`` still poisons the
    classic host check, so it must poison the fused flag identically).
    Defaults to the items' own grads.

    ``sentinel_flag``: a precomputed all-finite verdict that REPLACES the
    local fused reduction — the ZeRO-1 path passes the cross-rank
    AND-reduced global flag here, so every rank's shard update is guarded
    by the same verdict (a NaN anywhere skips the step everywhere).

    ``stats_out``: a list to collect per-bucket numerics stats into (the
    MXTPU_NUMERICS sampled-step hook): each bucket program then emits one
    extra ``(n_params, 6)`` f32 matrix (``telemetry.numerics.RAW_FIELDS``
    order) and ``(param_names, device_matrix)`` is appended per bucket —
    device arrays, NOT fetched here: the caller rides them on its
    existing flag+loss transfer. None (default) = the stats-free
    programs, bit-for-bit the historical behavior.

    Each bucket's learning rates and weight decays and the step's
    ``rescale_grad`` travel as host float32 arrays with the bucket's own
    launch (``jnp.asarray`` of a Python list is a put plus a cast program
    of its own, a launch that ``n_dispatches`` does not count).

    Returns ``(handled_indices, n_dispatches, finite_flag, created)``
    where ``finite_flag`` is a device scalar when ``sentinel`` and None
    otherwise, and ``created`` lists the indices whose optimizer state was
    first materialized by THIS call (a sentinel-skipped step must delete
    them again — state creation is an observable side effect the
    per-param skip path never has). Raises :class:`MXNetError` if any
    input is sparse — ONE documented behavior for every config: the
    fused dense buckets never accept sparse storage. The raise names
    ``MXTPU_SPARSE_PLANE`` as the opt-in: sparse tables ride the
    row-gathered variant (:func:`sparse_rows_update`) through
    ``parallel.embedding_plane.EmbeddingPlane``; everything else routes
    sparse parameters through the per-parameter lazy-update loop (the
    Trainer's ``eligible()`` gate does this automatically, so callers
    only see this raise when they bypass the gate).
    """
    opt = updater.optimizer
    rule = _rule_for(opt)
    jnp = _jnp()

    prepared, created = prepare_update(updater, items)
    chunks = chunk_prepared(prepared, agg_size)

    flag = None
    if sentinel:
        if sentinel_flag is not None:
            flag = jnp.asarray(sentinel_flag)
        else:
            if sentinel_grads is None:
                sentinel_grads = tuple(p._grad._data for _, p in items)
            flag = global_finite_flag(tuple(sentinel_grads))

    rescale = _np.asarray(float(opt.rescale_grad), dtype=_np.float32)
    n_dispatch = 0
    handled = []
    for chunk in chunks:
        lrs = _np.asarray([e[4] for e in chunk], dtype=_np.float32)
        wds = _np.asarray([e[5] for e in chunk], dtype=_np.float32)
        handled += apply_chunk(updater, rule, chunk, lrs, wds, rescale,
                               sentinel=sentinel, flag=flag,
                               stats_out=stats_out)
        n_dispatch += 1
    return handled, n_dispatch, flag, created


def _build_rows_fn(kernel, guarded: bool):
    """One jitted program stepping the TOUCHED rows of one row-sharded
    table: gather ``(max_rows, dim)`` slices of weight + optimizer state
    at ``idx``, run the SAME per-parameter rule kernel on the gathered
    rows, scatter the results back under the validity mask. The program
    shape depends only on (shard shape, bucket) — never on how many rows
    a step actually touched — so warm steps with varying touched-row
    counts replay, not retrace.

    Scatter discipline: deduped valid indices are unique by construction
    (the plane's host-side ``np.unique``); invalid lanes (padding +
    rows another shard owns) are routed to an out-of-range pad row and
    sliced off, the ``sharded_scatter_add`` drop idiom — ``at[].set``
    with colliding lanes would race old row bytes against new ones.
    With ``guarded`` the sentinel verdict ANDs into the mask, so a
    non-finite step leaves every row's weight AND lazily-touched state
    bytes exactly as they were (MASKED writes, not idempotent ones:
    Adam/AdaGrad state accumulates, a replayed write would double-decay).
    """
    import jax
    jnp = _jnp()

    def step(lr, wd, rescale, ok, donated, grad_rows, idx, valid):
        w, states = donated[0], tuple(donated[1:])
        nloc = w.shape[0]
        safe = jnp.clip(idx, 0, nloc - 1)
        gw = jnp.take(w, safe, axis=0)
        gs = tuple(jnp.take(s, safe, axis=0) for s in states)
        nw, ns = kernel(gw, grad_rows, gs, lr, wd, rescale)
        keep = valid if ok is None else valid & ok
        dump = jnp.where(keep, safe, nloc)

        def scat(full, rows):
            padded = jnp.concatenate(
                [full, jnp.zeros((1,) + full.shape[1:], full.dtype)])
            return padded.at[dump].set(rows.astype(full.dtype))[:nloc]

        return (scat(w, nw),) + tuple(
            scat(s, a) for s, a in zip(states, ns))

    if guarded:
        def fn(lr, wd, rescale, ok, donated, grad_rows, idx, valid):
            return step(lr, wd, rescale, ok, donated, grad_rows, idx,
                        valid)
        return jax.jit(fn, donate_argnums=(4,))

    def fn(lr, wd, rescale, donated, grad_rows, idx, valid):
        return step(lr, wd, rescale, None, donated, grad_rows, idx, valid)
    return jax.jit(fn, donate_argnums=(3,))


def sparse_rows_update(opt, weight, states, grad_rows, idx, valid, lr, wd,
                       flag=None):
    """Row-gathered grouped update for ONE shard of a row-sharded table —
    the sparse plane's device half (``parallel/embedding_plane.py``),
    and the variant :func:`grouped_update`'s dense buckets raise toward.

    All tensor arguments are raw jax arrays: ``weight`` is the
    ``(rows_local, dim)`` shard (donated, with its state arrays — XLA
    updates in place), ``grad_rows`` the deduped ``(max_rows, dim)``
    mask-packed gradient rows (NOT donated — they stay readable for the
    sentinel and chaos hooks), ``idx``/``valid`` the shard-local row ids
    and their in-shard+non-padding mask, ``lr``/``wd`` dynamic f32
    scalars (Adam's bias-corrected lr changes every step; baking it
    would retrace; Python floats here, handed to the program as host
    float32 arrays with its launch, since ``jnp.asarray`` of one is a put
    plus a cast program) and ``flag`` an optional device all-finite verdict
    (the global sentinel). The update math is the SAME
    :data:`_RULES` kernel the dense buckets trace, applied to gathered
    rows — so a plane step is bitwise the dense-gather reference update
    on the touched rows. Returns ``(new_weight, new_states)``.
    """
    jnp = _jnp()
    rule = _rule_for(opt)
    check(rule is not None,
          f"sparse_rows_update: optimizer {type(opt).__name__} has no "
          "grouped-update rule")
    states = tuple(states)
    donated = (weight,) + states
    guarded = flag is not None
    # 7-tuple, deliberately foreign to _sig_fields (the shared-LRU
    # discipline: program_memory skips entries it cannot re-lower)
    sig = ("sparse_rows", rule.name, rule.statics(opt), guarded,
           tuple((tuple(a.shape), str(a.dtype)) for a in donated),
           (tuple(grad_rows.shape), str(grad_rows.dtype)),
           (tuple(idx.shape), str(idx.dtype)))

    def _build(n_states=len(states), g=guarded):
        k = rule.make_kernel(opt, n_states > 0)
        return _build_rows_fn(_with_cast(k, False), g)

    fn = _cache().get_or_build(sig, _build)
    lr = _np.asarray(float(lr), dtype=_np.float32)
    wd = _np.asarray(float(wd), dtype=_np.float32)
    rescale = _np.asarray(float(opt.rescale_grad), dtype=_np.float32)
    if guarded:
        outs = fn(lr, wd, rescale, jnp.asarray(flag), donated, grad_rows,
                  idx, valid)
    else:
        outs = fn(lr, wd, rescale, donated, grad_rows, idx, valid)
    return outs[0], tuple(outs[1:])


def rollback_counts(opt, indices: Sequence[int]) -> None:
    """Undo the host-side update counters after a sentinel-skipped fused
    step, so Adam's bias correction (and any lr scheduler) sees the same
    ``t`` the per-parameter skip path would."""
    for i in indices:
        if i in opt._index_update_count:
            opt._index_update_count[i] -= 1
    counts = list(opt._index_update_count.values())
    opt.num_update = max(counts + [opt.begin_num_update])
