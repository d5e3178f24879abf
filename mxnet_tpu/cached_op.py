"""CachedOp: whole-graph compilation of hybridized blocks.

Reference: src/imperative/cached_op.{h,cc} — a traced NNVM graph executed
with static allocation/bulking, registered on the tape as ONE node with its
own backward (cached_op.cc:889 Forward, :1112 Backward).

TPU-native redesign (SURVEY.md §7 stage 7): "hybridize" == trace the block's
imperative python once per (shapes, dtypes, train-mode) key and compile the
WHOLE graph to a single XLA executable with ``jax.jit``. This subsumes the
reference's static_alloc/static_shape/bulking machinery — XLA buffer
assignment does the memory planning, and op fusion replaces engine bulking.

Mutable layer state (BatchNorm moving stats) is captured functionally: the
trace detects which Parameters were rebound during the traced call and turns
them into extra outputs that are written back after execution — the
flax-style state story replacing the reference's in-place aux-state mutation.

Randomness: a fresh PRNG key is passed as a real input each invocation and
installed as the trace key, so Dropout masks differ per call while the
compiled program stays cached.
"""
from __future__ import annotations

import os
import threading
from collections import OrderedDict, namedtuple
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .base import MXNetError, env
from .random import NEXT_KEY_PROGRAMS
from .telemetry.tracer import span as _span

__all__ = ["CachedOp", "CacheInfo", "SignatureLRU", "make_scan_forward",
           "scan_forward"]

# ``args`` of the replay's child spans, shared: a span copies what it is
# given, and a closed site builds nothing
_PREPARE_ARGS = {"programs": NEXT_KEY_PROGRAMS}
_ONE_PROGRAM = {"programs": 1}
_NO_PROGRAMS = {"programs": 0}

CacheInfo = namedtuple("CacheInfo",
                       ["hits", "misses", "evictions", "currsize", "maxsize"])

# Every live SignatureLRU (CachedOp signature caches, grouped-optimizer
# program caches, serving signature caches) reports into the shared
# telemetry registry as polled gauges — zero hot-path cost: the counters
# are summed at export time, not on every lookup. Counters of DEAD caches
# are folded into a retired accumulator by a weakref.finalize, so the
# exported totals are MONOTONE: a cyclic-GC pass collecting an old
# hybridized net between two reads must never make hits/misses go down
# (the exact mechanism behind the test_env_flags+test_telemetry
# pair-order flake this replaces — the gauge used to sum live caches
# only, so a cache dying mid-test subtracted its whole history).
_all_caches: "weakref.WeakSet" = None  # type: ignore[assignment]
_retired_counts = {"hits": 0, "misses": 0, "evictions": 0}
# RLock, not Lock: the retire callback runs from weakref.finalize, which
# cyclic GC may fire synchronously on THIS thread while it already holds
# the lock (list() below allocates, allocation can trigger collection of
# a dead cycle holding a SignatureLRU) — a plain Lock would self-deadlock
_track_lock = threading.RLock()


def _retire_cache_counts(stats: dict) -> None:
    with _track_lock:
        for field in _retired_counts:
            _retired_counts[field] += stats[field]


def _tracked_cache_total(field: str) -> int:
    """Monotone process-wide total for hits/misses/evictions; live-only
    occupancy for currsize (a dead cache holds no entries)."""
    with _track_lock:
        live = list(_all_caches) if _all_caches is not None else []
        base = _retired_counts.get(field, 0)
    return base + sum(getattr(c.cache_info(), field) for c in live)


def _track_cache(cache: "SignatureLRU") -> None:
    global _all_caches
    import weakref
    with _track_lock:
        if _all_caches is None:
            _all_caches = weakref.WeakSet()
            try:
                from .telemetry import default_registry
                reg = default_registry()
                for field in ("hits", "misses", "evictions", "currsize"):
                    reg.callback_gauge(
                        f"mxtpu_cachedop_cache_{field}",
                        (lambda f=field: _tracked_cache_total(f)),
                        f"Signature-cache {field} over all compiled-"
                        "program caches (monotone: retired caches keep "
                        "their counts, except currsize which is live "
                        "occupancy).")
            except Exception:
                pass
        _all_caches.add(cache)
    weakref.finalize(cache, _retire_cache_counts, cache._stats)


class SignatureLRU:
    """Thread-safe signature-keyed LRU of compiled programs — the caching
    discipline CachedOp applies to whole-graph executables, reusable by
    any subsystem that compiles per-signature (optimizer/grouped.py's
    bucket programs). Bounded by ``MXTPU_CACHEDOP_CACHE_SIZE`` unless an
    explicit ``maxsize`` is given; 0 = unbounded."""

    def __init__(self, maxsize: Optional[int] = None):
        self._explicit_maxsize = maxsize
        self._cache: "OrderedDict[Any, Any]" = OrderedDict()
        self._lock = threading.Lock()
        # counters live in a plain dict so the telemetry finalizer can
        # fold them into the retired accumulator after this cache dies
        self._stats = {"hits": 0, "misses": 0, "evictions": 0}
        _track_cache(self)

    def _bound(self) -> int:
        if self._explicit_maxsize is not None:
            return int(self._explicit_maxsize)
        return int(env.get("MXTPU_CACHEDOP_CACHE_SIZE"))

    def get_or_build(self, key, build):
        """Return the cached value for ``key``, building (outside the
        lock — ``build`` may trace/compile) and inserting on miss."""
        with self._lock:
            val = self._cache.get(key)
            if val is not None:
                self._stats["hits"] += 1
                self._cache.move_to_end(key)
                return val
        val = build()
        with self._lock:
            self._stats["misses"] += 1
            self._cache[key] = val
            self._evict_locked()
        return val

    def get_or_insert(self, key, factory):
        """Lock-held get-or-create for CHEAP factories (a jit wrapper, an
        entry object — never a trace/compile): exactly one caller creates
        the value for a key, so concurrent cold lookups cannot race two
        half-initialized entries into existence (CachedOp's requirement)."""
        with self._lock:
            val = self._cache.get(key)
            if val is not None:
                self._stats["hits"] += 1
                self._cache.move_to_end(key)
                return val
            self._stats["misses"] += 1
            val = factory()
            self._cache[key] = val
            self._evict_locked()
            return val

    def _evict_locked(self) -> None:
        bound = self._bound()
        if bound > 0:
            while len(self._cache) > bound:
                self._cache.popitem(last=False)
                self._stats["evictions"] += 1

    def cache_info(self) -> CacheInfo:
        bound = self._bound()
        return CacheInfo(self._stats["hits"], self._stats["misses"],
                         self._stats["evictions"], len(self._cache),
                         bound if bound > 0 else None)

    def insert(self, key, val) -> bool:
        """Install a prebuilt value (AOT-loaded executables) without
        counting a hit or a miss; returns False when the key was already
        resident (the resident entry wins — it may already be warm)."""
        with self._lock:
            if key in self._cache:
                return False
            self._cache[key] = val
            self._evict_locked()
            return True

    def snapshot_items(self):
        """(key, value) pairs at this instant (export iteration)."""
        with self._lock:
            return list(self._cache.items())

    def __len__(self) -> int:
        # truthiness == occupancy, like the plain dict this replaced
        # (callers probe `not op._cache` for "no entries were built")
        return len(self._cache)

    def clear(self) -> None:
        with self._lock:
            self._cache.clear()
            # retire, don't erase: the telemetry totals promise
            # monotonicity, so a clear() folds this history into the
            # retired accumulator exactly like cache death would
            _retire_cache_counts(self._stats)
            for k in self._stats:
                self._stats[k] = 0


def _jax():
    import jax
    return jax


_EFFICIENCY_MOD = None


def _eff():
    """Lazy module accessor for the efficiency plane (one global check
    per call after the first import — the off path stays one cached env
    check inside ``efficiency.enabled``)."""
    global _EFFICIENCY_MOD
    if _EFFICIENCY_MOD is None:
        from .telemetry import efficiency
        _EFFICIENCY_MOD = efficiency
    return _EFFICIENCY_MOD


# A compiled program's cache key. ``flags`` are the env flags read inside op
# impls (they change the traced program: toggling them must re-trace, not
# replay); ``record`` is None for the plain forward that inference and
# serving run, else (residual policy name, which params, which inputs are
# differentiated): a recorded call runs another program.
_Signature = namedtuple("_Signature", [
    "inputs", "params", "in_treedef", "training", "flags", "record"])


# What a recorded entry's forward program hands to its backward, worked
# out once from the traced program (CachedOp._linearize):
#   n_outs, n_state   how the program's flat outputs split: the residuals
#                     the program itself wrote (the set CachedOp._arena
#                     recycles), then the block's outputs, the mutated state
#   res_src           one int a leaf of the vjp closure: >= 0 a position in
#                     the program's outputs, < 0 the ~position of a flat
#                     argument (params, key, inputs) that is passed through
#   closure_treedef   treedef of the closure jax.vjp returned
#   n_inputs, diff_pos  how many inputs the node has (params + inputs), and
#                     the positions among them of what the closure
#                     differentiates with respect to
#   arena_avals       abstract values of the recycled set, as handed over:
#                     a buffer the forward's compiler keeps in another
#                     dimension order than the default is handed over
#                     transposed to that order
#   turn_back         one entry a leaf of the closure: None, or the
#                     permutation that gives a handed-over buffer its own
#                     shape again (the backward program applies it)
#   residual_bytes    bytes of that set
#   relaid, relaid_bytes  how many of its buffers are handed over
#                     transposed, and their bytes
#   policy            residual_policy_name the program was built under
_Linearized = namedtuple("_Linearized", [
    "n_outs", "n_state", "res_src", "closure_treedef", "n_inputs",
    "diff_pos", "arena_avals", "turn_back", "residual_bytes", "relaid",
    "relaid_bytes", "policy"])


class _CacheEntry:
    __slots__ = ("jitted", "mutated_idx", "out_treedef", "vjp_jitted",
                 "n_outputs", "warm", "mem_stats", "cost_stats",
                 "vjp_abstract", "vjp_cost_stats", "linear", "alloc",
                 "__weakref__")

    def __init__(self):
        self.jitted = None
        self.mutated_idx: Tuple[int, ...] = ()
        self.out_treedef = None
        self.vjp_jitted = None
        self.n_outputs = 0
        # static memory_analysis of the compiled program, filled lazily
        # by CachedOp.memory_analysis()
        self.mem_stats: Optional[dict] = None
        # cost_analysis (flops / bytes accessed) of the forward program,
        # filled lazily by entry_cost_stats ({} = resolution failed, so
        # the efficiency plane does not retry every step)
        self.cost_stats: Optional[dict] = None
        # abstract (closure, cots) signature of the backward program,
        # captured at its first dispatch so entry_vjp_cost_stats (and a
        # test) can lower it
        self.vjp_abstract: Optional[tuple] = None
        self.vjp_cost_stats: Optional[dict] = None
        # recorded entries only: the _Linearized plan, and the program
        # that allocates a residual set when the op's arena has none
        self.linear: Optional[_Linearized] = None
        self.alloc = None
        # False until the first execution (which runs the python trace)
        # has completed — concurrent callers must treat a cold entry like
        # a miss and take the exclusive trace path
        self.warm = False


class _RWLock:
    """Many concurrent replays, exclusive traces. Tracing a cold
    signature swaps every Parameter's storage to jax Tracers for the
    duration of the trace (_make_pure_fn), so a concurrent reader could
    capture a Tracer into its param tuple; replays of warm entries only
    read, and may overlap freely (serving workers). The lock is shared
    per BLOCK (stashed on it), not per CachedOp — two executors over the
    same net mutate the same Parameter objects. Threads that bypass
    CachedOp entirely (direct un-hybridized calls, checkpoint saves)
    during another thread's trace remain outside this guard — don't mix
    those with concurrent serving traffic over the same net."""

    def __init__(self):
        self._cond = threading.Condition()
        self._readers = 0
        self._writing = False
        self._writers_waiting = 0

    def acquire_read(self):
        with self._cond:
            # writer preference: back-to-back warm replays must not
            # starve a cold signature's one-time trace forever
            while self._writing or self._writers_waiting:
                self._cond.wait()
            self._readers += 1

    def release_read(self):
        with self._cond:
            self._readers -= 1
            if not self._readers:
                self._cond.notify_all()

    def acquire_write(self):
        with self._cond:
            self._writers_waiting += 1
            try:
                while self._writing or self._readers:
                    self._cond.wait()
            finally:
                self._writers_waiting -= 1
            self._writing = True

    def release_write(self):
        with self._cond:
            self._writing = False
            self._cond.notify_all()


def trace_rw_for(block) -> "_RWLock":
    """The block's shared trace lock, creating and stashing it on first
    use — the SAME instance every CachedOp wrapping ``block`` guards its
    storage-swapping traces with, so two CachedOps of one block never
    trace over the same Parameters at once. Falls back to a fresh private
    lock for slotted/exotic blocks that refuse the attribute stash."""
    rw = getattr(block, "_mxtpu_trace_rw", None)
    if rw is None:
        rw = _RWLock()
        try:
            block._mxtpu_trace_rw = rw
        except AttributeError:
            pass  # slotted/exotic block: fall back to a private lock
    return rw


def _backward_program(turn_back):
    """A recorded entry's backward program: the transpose ``closure``
    applied to ``cots``, once the leaves that were handed over in the
    forward compiler's dimension order (``_Linearized.turn_back``) have
    their own shape again. The compiler reads such a leaf where it lies:
    the transpose of an argument is a change of name, not a pass over it."""
    import jax

    def _apply_closure(closure, cots):
        leaves, treedef = jax.tree_util.tree_flatten(closure)
        closure = jax.tree_util.tree_unflatten(
            treedef, [a if t is None else a.transpose(t)
                      for a, t in zip(leaves, turn_back)])
        return closure(cots)

    return jax.jit(_apply_closure)


def _kept_order(aval, fmt) -> Optional[Tuple[int, ...]]:
    """The dimension order, major to minor, that a compiler left free chose
    for a buffer of ``aval``, where handing the buffer over transposed to
    that order says the same thing: the order is not the one its device
    gives an array of that shape anyway (on a TPU the default depends on
    the shape: not every default is row-major), and the transposed array's
    default layout is the chosen one, tiles and all. None otherwise."""
    from jax.experimental.layout import Layout
    if fmt.layout is None:  # a backend that reports no layouts
        return None
    d = next(iter(fmt.sharding.device_set))

    def default(shape):
        return Layout.from_pjrt_layout(
            d.client.get_default_layout(aval.dtype, shape, d))

    order = fmt.layout.major_to_minor
    if order == default(aval.shape).major_to_minor:
        return None
    turned = default(tuple(aval.shape[i] for i in order))
    if turned.major_to_minor != tuple(range(len(order))) \
            or turned.tiling != fmt.layout.tiling:
        return None
    return order


def _on_tape(x) -> bool:
    """Whether NDArray ``x`` can receive a gradient: it carries a tape
    entry (a marked variable or a recorded op's output) and is real or
    complex."""
    import jax.numpy as jnp
    return x._tape_entry is not None and \
        jnp.issubdtype(x._data.dtype, jnp.inexact)


class _CachedOpGrad:
    """Per-call backward closure recorded as a single tape node
    (ref: CachedOp::Backward, src/imperative/cached_op.cc:1112). It holds
    what the recorded forward handed over: the ``jax.vjp`` closure, whose
    leaves are the residuals, and of those the set the forward program
    wrote itself (``owned``), which goes back to the op's arena when the
    graph is freed."""

    def __init__(self, op: "CachedOp", entry: _CacheEntry, closure, owned):
        self.op = op
        self.entry = entry
        self.closure = closure
        self.owned = owned

    def _release_graph(self) -> None:
        """The walk freed the graph (``retain_graph`` false): drop the
        residuals even if an output NDArray keeps this node alive, and
        hand the recycled set to the next recorded forward, which
        donates it. The backward that read it is already enqueued; the
        device's queue orders the two."""
        owned, self.owned, self.closure = self.owned, None, None
        if owned:
            with self.op._arena_lock:
                self.op._arena.append((self.entry.linear.arena_avals, owned))

    def _note_efficiency(self) -> None:
        """Efficiency-plane hook: note this launch (callers gate on
        ``enabled()`` — plane-off steps never reach here)."""
        try:
            op, entry = self.op, self.entry
            _eff().note_dispatch(
                ("co_bwd", id(entry)), "cached_op",
                f"{type(op.block).__name__}:bwd",
                lambda op=op, e=entry: op.entry_vjp_cost_stats(e))
        except Exception:
            pass  # observability must not take down the backward

    def _run_backward(self, cotangents):
        with _span("mx.cached_op.vjp", "step",
                   {"block": type(self.op.block).__name__, "programs": 1,
                    "recompute": self.entry.linear.policy}):
            return self._vjp(cotangents)

    def _vjp(self, cotangents):
        import jax
        entry, closure = self.entry, self.closure
        if closure is None:
            raise MXNetError("graph has already been freed; pass "
                             "retain_graph=True to backward() to reuse it")
        cotangents = tuple(cotangents)
        if entry.vjp_jitted is None:
            # the program only applies the transpose: tracing it runs none
            # of the block's Python, so it needs no trace lock
            entry.vjp_jitted = _backward_program(entry.linear.turn_back)
            entry.vjp_abstract = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (closure, cotangents))
        if _eff().enabled():
            self._note_efficiency()
        param_grads, in_grads = entry.vjp_jitted(closure, cotangents)
        grads = [None] * entry.linear.n_inputs
        for pos, g in zip(entry.linear.diff_pos, param_grads + in_grads):
            grads[pos] = g
        return grads


class CachedOp:
    """Compile-and-replay executor for a HybridBlock.

    ``__call__(args)`` returns output NDArrays; parameters and mutable state
    are read from / written back to the block's Parameters.
    """

    def __init__(self, block, static_alloc: bool = False,
                 static_shape: bool = False, inline_limit: int = 2,
                 flags: Sequence = (), mirror: Optional[bool] = None,
                 cache_size: Optional[int] = None):
        # static_alloc/static_shape are implied by XLA compilation; kept for
        # API compat (ref: CachedOpConfig, cached_op.h:32-53). ``mirror``
        # (default: the MXNET_BACKWARD_DO_MIRROR env flag) rematerializes
        # activations in backward instead of storing them (ref: the
        # mirror_fun path of src/nnvm/gradient.cc:271).
        self.block = block
        self.mirror = mirror
        # LRU-bounded signature cache: every distinct (shapes, dtypes,
        # train-mode, trace flags) key holds a full compiled executable, so
        # shape-churny workloads (variable batch/seq) otherwise grow
        # without bound. 0 = unbounded. Bookkeeping lives in SignatureLRU
        # (shared with optimizer/grouped.py); execution runs outside its
        # lock under _trace_rw: warm replays share a read lock (serving
        # workers overlap), cold first executions take the write lock
        # because the trace mutates shared Parameter storage.
        if cache_size is None:
            cache_size = int(env.get("MXTPU_CACHEDOP_CACHE_SIZE"))
        self._cache_size = int(cache_size)
        self._cache = SignatureLRU(maxsize=self._cache_size)
        self._trace_rw = trace_rw_for(block)
        self._param_objs: Optional[List] = None
        # residual sets of recorded calls whose graph was freed, each
        # with its abstract values, for the next recorded forward to
        # write over (as many as calls were live at once). The op's, not
        # an entry's: two signatures whose residuals have the same shapes
        # (the first step's, before the moving statistics turn float32,
        # and every later one's) share one set.
        self._arena: List[tuple] = []
        self._arena_lock = threading.Lock()
        # the last residual set whose layouts were read from a compile,
        # and the order each buffer is handed over in: the next signature
        # with the same residuals (again the first step's and every later
        # one's) takes it as read, keeps sharing the set, and compiles its
        # forward once
        self._turned: Tuple[Optional[tuple], tuple] = (None, ())

    def cache_info(self) -> CacheInfo:
        """Hit/miss/eviction counters + occupancy of the signature cache
        (shape of :func:`functools.lru_cache`'s ``cache_info``)."""
        return self._cache.cache_info()

    @staticmethod
    def _entry_digest(key_sig) -> str:
        import hashlib
        return hashlib.md5(repr(key_sig).encode()).hexdigest()[:12]

    def _lower_signature(self, key_sig, entry: _CacheEntry):
        """Re-lower one warm entry's forward program from its recorded
        abstract signature to a jax ``Compiled`` (AOT-loaded entries ARE
        executables and are returned as-is; cold or stale-flag-regime
        entries return None). Re-lowering retraces the pure fn —
        Parameter storage is swapped to tracers for the duration — so it
        runs under the trace write lock, the aot_export discipline. The
        one lowering site behind :meth:`memory_analysis` AND the
        efficiency plane's cost resolution."""
        if not entry.warm:
            return None
        if not hasattr(entry.jitted, "lower"):
            return entry.jitted  # AOT-loaded: already a Compiled stage
        from .ops.registry import _trace_time_flags
        if key_sig.flags != _trace_time_flags():
            return None  # stale entry from a different flag regime
        self._trace_rw.acquire_write()
        try:
            self._in_treedef = key_sig.in_treedef
            return entry.jitted.lower(
                *self._abstract_args(key_sig, entry)).compile()
        finally:
            self._trace_rw.release_write()

    @staticmethod
    def _abstract_args(key_sig, entry: _CacheEntry) -> tuple:
        """The abstract arguments ``entry.jitted`` was traced with, from
        the entry's cache key: ``(params, key, *inputs)`` for a plain
        forward, ``(params, key, inputs, arena)`` for a recorded one."""
        import jax
        import numpy as np

        def sds(sig):
            return tuple(jax.ShapeDtypeStruct(tuple(shape), np.dtype(dt))
                         for shape, dt in sig)

        probe_key = jax.random.PRNGKey(0)
        key_aval = jax.ShapeDtypeStruct(probe_key.shape, probe_key.dtype)
        if entry.linear is None:
            return (sds(key_sig.params), key_aval, *sds(key_sig.inputs))
        return (sds(key_sig.params), key_aval, sds(key_sig.inputs),
                entry.linear.arena_avals)

    def memory_analysis(self, refresh: bool = False) -> Dict[str, dict]:
        """Static per-program memory attribution, keyed by signature
        digest: each warm entry's compiled ``memory_analysis()``
        (argument/output/temp/alias bytes — the activation/workspace
        footprint the live ledger cannot see). Re-lowers from the
        recorded abstract signature like :meth:`aot_export` (one trace;
        with the persistent compile cache this is a disk read, not a
        recompile) and caches the result on the entry until ``refresh``.
        Results are also recorded in the telemetry program registry
        (kind ``cached_op``) for the registry gauges and OOM forensics."""
        from .telemetry import memory as _memory

        label_base = type(self.block).__name__
        out: Dict[str, dict] = {}
        for key_sig, entry in self._cache.snapshot_items():
            if not entry.warm:
                continue
            digest = self._entry_digest(key_sig)
            if entry.mem_stats is not None and not refresh:
                out[digest] = entry.mem_stats
                continue
            compiled = self._lower_signature(key_sig, entry)
            if compiled is None:
                continue
            stats = _memory.compiled_memory_stats(compiled)
            if stats is None:
                continue
            stats = dict(stats, signature=digest)
            entry.mem_stats = stats
            self._record_program(f"{label_base}:{digest}", stats)
            out[digest] = stats
        return out

    @staticmethod
    def _record_program(label: str, stats: dict) -> None:
        """Merge one program's stats into the telemetry registry record
        (memory and cost halves may resolve at different times on
        different threads — the merge is atomic under the registry
        lock, so neither clobbers the other's fields)."""
        from .telemetry import memory as _memory
        _memory.merge_program("cached_op", label, stats)

    def entry_cost_stats(self, key_sig, entry: _CacheEntry
                         ) -> Optional[dict]:
        """Cost-model stats (flops / bytes accessed) of one warm entry's
        forward program — the efficiency plane's resolver. Re-lowers
        once under the trace write lock (the :meth:`memory_analysis`
        discipline), caches on the entry (a failed resolution caches an
        empty dict so the plane never retries every step), and records
        the combined cost+memory stats in the program registry."""
        cached = entry.cost_stats
        if cached is not None:
            return cached or None
        from .telemetry.efficiency import (COST_FIELDS, MEMORY_FIELDS,
                                           compiled_program_stats)
        try:
            stats = compiled_program_stats(
                self._lower_signature(key_sig, entry))
        except Exception:
            stats = None
        if not stats or "flops" not in stats:
            entry.cost_stats = {}
            return None
        digest = self._entry_digest(key_sig)
        cost = {k: stats[k] for k in COST_FIELDS if k in stats}
        entry.cost_stats = cost
        if entry.mem_stats is None and "argument_bytes" in stats:
            entry.mem_stats = dict(
                {k: stats[k] for k in MEMORY_FIELDS}, signature=digest)
        self._record_program(f"{type(self.block).__name__}:{digest}",
                             dict(stats, signature=digest))
        return cost

    def entry_vjp_cost_stats(self, entry: _CacheEntry) -> Optional[dict]:
        """Cost-model stats of one entry's backward (vjp) program, from
        the abstract signature captured at its first dispatch. Same
        re-lower/cache discipline as :meth:`entry_cost_stats`."""
        cached = entry.vjp_cost_stats
        if cached is not None:
            return cached or None
        ab = entry.vjp_abstract
        if ab is None:
            return None
        from .telemetry.efficiency import (COST_FIELDS,
                                           compiled_program_stats)
        try:
            stats = compiled_program_stats(
                entry.vjp_jitted.lower(*ab).compile())
        except Exception:
            stats = None
        if not stats or "flops" not in stats:
            entry.vjp_cost_stats = {}
            return None
        cost = {k: stats[k] for k in COST_FIELDS if k in stats}
        entry.vjp_cost_stats = cost
        import hashlib
        import jax
        digest = hashlib.md5(
            repr(jax.tree_util.tree_leaves(ab)).encode()
        ).hexdigest()[:12]
        self._record_program(
            f"{type(self.block).__name__}:bwd:{digest}",
            dict(stats, signature=digest))
        return cost

    # -- AOT executable slot -------------------------------------------
    # A new replica of an already-published model should reach first byte
    # with ZERO compiles and ZERO traces: aot_export serializes every warm
    # signature's compiled XLA executable (jax.experimental.
    # serialize_executable) next to its cache key; aot_load deserializes
    # them into pre-warmed cache entries on a fingerprint-matched runtime.
    AOT_FORMAT = 3  # 3: the cache key says whether the call was recorded

    def aot_export(self, path: str) -> int:
        """Serialize the warm, inference-facing signature entries to
        ``path``. Returns the number of executables exported. Entries are
        re-lowered from their recorded (shapes, dtypes) signature and
        compiled — with the persistent compile cache enabled this is a
        disk read, not a recompile. Backward programs (vjp) are not
        exported: AOT bundles are a serving artifact."""
        import pickle

        from .ops.registry import _trace_time_flags
        from .serving.aot import runtime_fingerprint
        try:
            from jax.experimental.serialize_executable import serialize
        except ImportError as e:
            raise MXNetError(f"AOT export unavailable on this jax: {e}")
        records = []
        for key_sig, entry in self._cache.snapshot_items():
            if not entry.warm or not hasattr(entry.jitted, "lower"):
                continue  # cold, or itself an AOT-loaded executable
            if entry.linear is not None:
                continue  # a recorded forward serves a backward, not a replica
            if key_sig.flags != _trace_time_flags():
                continue  # stale entry from a different flag regime
            # re-lowering retraces the pure fn, which temporarily swaps
            # Parameter storage to tracers — same exclusivity as a cold
            # trace (the treedef is restored per call by __call__; set it
            # under the lock so the retrace can't see a concurrent
            # caller's)
            self._trace_rw.acquire_write()
            try:
                self._in_treedef = key_sig.in_treedef
                lowered = entry.jitted.lower(
                    *self._abstract_args(key_sig, entry))
            finally:
                self._trace_rw.release_write()
            compiled = lowered.compile()
            payload, in_tree, out_tree = serialize(compiled)
            records.append({
                "key": pickle.dumps(key_sig),
                "payload": payload,
                # the devices the executable was compiled for: loading it
                # over every local device of the replica (the loader's
                # default) makes each call expect one shard a device
                "device_ids": [d.id for d in compiled.runtime_executable()
                               .local_devices()],
                "in_tree": pickle.dumps(in_tree),
                "out_tree": pickle.dumps(out_tree),
                "mutated_idx": entry.mutated_idx,
                "out_treedef": pickle.dumps(entry.out_treedef),
                "n_outputs": entry.n_outputs,
            })
        bundle = {"format": self.AOT_FORMAT,
                  "fingerprint": runtime_fingerprint(),
                  "entries": records}
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump(bundle, f)
        os.replace(tmp, path)
        return len(records)

    def aot_load(self, path: str) -> int:
        """Install AOT-exported executables as warm cache entries; returns
        how many were loaded. Zero (with a log line) when the bundle was
        built on a different jaxlib/backend or fails to deserialize —
        callers fall back to warmup through the persistent compile cache,
        never crash the replica."""
        import pickle

        import jax
        from .log import get_logger
        from .serving.aot import runtime_fingerprint
        log = get_logger("mxnet_tpu.cached_op")
        try:
            from jax.experimental.serialize_executable import \
                deserialize_and_load
        except ImportError:
            log.warning("aot_load: serialize_executable unavailable")
            return 0
        try:
            with open(path, "rb") as f:
                bundle = pickle.load(f)
        except Exception as e:
            log.warning("aot_load: unreadable bundle %s: %s", path, e)
            return 0
        if bundle.get("format") != self.AOT_FORMAT:
            log.warning("aot_load: bundle format %s != %s, skipping",
                        bundle.get("format"), self.AOT_FORMAT)
            return 0
        fp = runtime_fingerprint()
        if bundle.get("fingerprint") != fp:
            log.warning("aot_load: fingerprint mismatch (bundle %s, "
                        "runtime %s) — executables not portable, falling "
                        "back to compile-cache warmup",
                        bundle.get("fingerprint"), fp)
            return 0
        loaded = 0
        by_id = {d.id: d for d in jax.devices()}
        for rec in bundle.get("entries", ()):
            try:
                key_sig = pickle.loads(rec["key"])
                exe = deserialize_and_load(
                    rec["payload"], pickle.loads(rec["in_tree"]),
                    pickle.loads(rec["out_tree"]),
                    execution_devices=[by_id[i] for i in rec["device_ids"]])
                entry = _CacheEntry()
                entry.jitted = exe
                entry.mutated_idx = tuple(rec["mutated_idx"])
                entry.out_treedef = pickle.loads(rec["out_treedef"])
                entry.n_outputs = int(rec["n_outputs"])
                entry.warm = True
                if self._cache.insert(key_sig, entry):
                    loaded += 1
                    # ledger the deserialized executable under
                    # 'aot_bundles' (serialized-payload bytes as the
                    # footprint proxy), freed when the entry dies
                    from .telemetry import memory as _memory
                    _memory.ledger().attach(
                        "aot_bundles", len(rec["payload"]),
                        f"aot:{os.path.basename(path)}", entry)
            except Exception as e:
                log.warning("aot_load: skipping one entry: %s", e)
        return loaded

    # -----------------------------------------------------------------
    def _params(self) -> List:
        if self._param_objs is None:
            self._param_objs = [p for _, p in
                                sorted(self.block.collect_params().items())]
            sparse = [p.name for p in self._param_objs
                      if getattr(p, "grad_stype", "default") != "default"]
            if sparse:
                import warnings
                warnings.warn(
                    f"hybridize(): parameters {sparse} request row_sparse "
                    "gradients, but the whole-graph XLA backward produces "
                    "dense gradients (they are still delivered correctly "
                    "to the row_sparse buffers). Run the block un-hybridized "
                    "to keep gradients compact.", stacklevel=3)
        return self._param_objs

    def _make_pure_fn(self, training: bool, entry: _CacheEntry):
        """Build the pure (params, key, *inputs) -> (outputs, state) fn."""
        from . import autograd, random as _random
        from .ndarray.ndarray import NDArray, from_jax
        import jax

        block = self.block
        params = self._params()

        def fn(param_arrays, key, *input_arrays):
            originals = []
            for p, a in zip(params, param_arrays):
                originals.append(p._data._data)
                p._data._data = a
            _random.push_trace_key(key)
            prev_rec = autograd.set_recording(False)
            prev_train = autograd.set_training(training)
            try:
                nd_args = [from_jax(a) for a in input_arrays]
                args = jax.tree_util.tree_unflatten(self._in_treedef, nd_args)
                out = block._imperative_call(*args)
                flat_out, out_treedef = jax.tree_util.tree_flatten(
                    out, is_leaf=lambda x: isinstance(x, NDArray))
                out_arrays = tuple(o._data for o in flat_out)
                mutated, state = [], []
                for i, (p, orig) in enumerate(zip(params, param_arrays)):
                    if p._data._data is not orig:
                        mutated.append(i)
                        state.append(p._data._data)
                entry.mutated_idx = tuple(mutated)
                entry.out_treedef = out_treedef
                entry.n_outputs = len(out_arrays)
                return out_arrays, tuple(state)
            finally:
                autograd.set_training(prev_train)
                autograd.set_recording(prev_rec)
                _random.pop_trace_key()
                for p, orig in zip(params, originals):
                    p._data._data = orig

        return fn

    def _linearize(self, entry: _CacheEntry, training: bool, record,
                   param_arrays, rng_key, in_arrays) -> None:
        """Build a recorded entry's forward program: it runs the block
        once and returns, beside outputs and mutated state, the residuals
        of ``jax.vjp`` taken with respect to what is on the tape, under
        the ``jax.checkpoint`` policy ``record`` names. The node's
        backward only applies the transpose (``_apply_closure``).

        The function is traced to a jaxpr first, because what the
        program takes as donated arguments (the arena) has the shapes of
        what it returns: residuals that are the program's own arguments
        (parameters, the batch) or its outputs are taken from there on
        the host and never donated; the rest is the set the arena
        recycles, each buffer in the dimension order the compiler keeps
        it in (below). Runs under the trace write lock (the trace swaps
        Parameter storage)."""
        import jax
        from jax.extend.core import Literal, jaxpr_as_fun
        from .util import residual_policy

        policy_name, param_mask, in_mask = record
        policy = residual_policy(policy_name)
        fn = self._make_pure_fn(training, entry)

        def pick(arrays, mask):
            return tuple(a for a, m in zip(arrays, mask) if m)

        def merge(arrays, mask, picked):
            picked = iter(picked)
            return tuple(next(picked) if m else a
                         for a, m in zip(arrays, mask))

        def linear(params, key, ins):
            def f(diff_params, diff_ins):
                return fn(merge(params, param_mask, diff_params), key,
                          *merge(ins, in_mask, diff_ins))

            # prevent_cse=False: the backward is another program, there
            # is nothing to CSE with, and optimization barriers keep XLA
            # from fusing BatchNorm and ReLU into the convolutions
            outs, closure, state = jax.vjp(
                jax.checkpoint(f, policy=policy, prevent_cse=False),
                pick(params, param_mask), pick(ins, in_mask), has_aux=True)
            return outs, state, closure

        closed, shapes = jax.make_jaxpr(linear, return_shape=True)(
            param_arrays, rng_key, tuple(in_arrays))
        jaxpr = closed.jaxpr
        n_outs, n_state = len(shapes[0]), len(shapes[1])
        arg_pos = {v: i for i, v in enumerate(jaxpr.invars)}
        emit = list(range(n_outs + n_state))  # outvars the program returns
        out_pos = {}
        for j in emit:
            if not isinstance(jaxpr.outvars[j], Literal):
                out_pos.setdefault(jaxpr.outvars[j], j)
        res_src = []
        for j in range(n_outs + n_state, len(jaxpr.outvars)):
            v = jaxpr.outvars[j]
            if isinstance(v, Literal):
                res_src.append(len(emit))
                emit.append(j)
            elif v in arg_pos:
                res_src.append(~arg_pos[v])
            else:
                if v not in out_pos:
                    out_pos[v] = len(emit)
                    emit.append(j)
                res_src.append(out_pos[v])
        n = n_outs + n_state
        n_arena = len(emit) - n
        # the program returns the set first: jax pairs a donated argument
        # with the first output of its shape and type, and that has to be
        # the buffer written over it, not an output or a moving statistic
        # that happens to look like it
        emit = emit[n:] + emit[:n]
        res_src = [src if src < 0 else src - n if src >= n else src + n_arena
                   for src in res_src]
        run = jaxpr_as_fun(closed)

        def build(turns, **layouts):
            def program(params, key, ins, arena):
                # ``arena`` is donated and otherwise unused: XLA writes
                # this call's residuals over the set a freed graph gave
                # back
                del arena
                out = run(*params, key, *ins)
                return [out[j] if t is None else out[j].transpose(t)
                        for j, t in zip(emit, turns)]

            return jax.jit(program, donate_argnums=(3,), keep_unused=True,
                           **layouts)

        def avals(turns):
            shapes = [jaxpr.outvars[j].aval for j in emit[:n_arena]]
            return tuple(jax.ShapeDtypeStruct(
                a.shape if t is None else tuple(a.shape[i] for i in t),
                a.dtype) for a, t in zip(shapes, turns))

        # What the program writes for its backward alone need not have the
        # default layout, which a program's outputs have: where the fusion
        # that makes a buffer writes another dimension order, the forward
        # would copy it into the default and the backward re-lay it as it
        # reads. So the program is compiled once with the set's layouts
        # left to the compiler, on the donated arguments and the outputs
        # alike, only to read what it chose; the program that runs hands
        # over, transposed to the chosen order, each buffer the compiler
        # kept out of the default, and the backward names it back
        # (``_backward_program``): in the default layout of the transposed
        # shape the bytes lie as the fusion writes them, and neither side
        # copies. Every array keeps a default layout, so nothing else has
        # to know: the set is matched by its abstract values, and whatever
        # lowers either program again lowers the same one. (The executable
        # compiled with free layouts cannot itself be the one that runs:
        # loaded from the persistent compile cache it returns its results
        # under the default layout's name.)
        asis = (None,) * len(emit)
        own, kept = avals(asis), asis[:n_arena]
        if self._turned[0] == own:
            kept = self._turned[1]
        elif any(len(a.shape) > 1 for a in own):  # something can be turned
            from jax.experimental.layout import Format, Layout
            free = Format(Layout.AUTO)
            chosen = build(
                asis, in_shardings=(None, None, None, (free,) * n_arena),
                out_shardings=[free] * n_arena + [None] * n,
            ).lower(param_arrays, rng_key, tuple(in_arrays),
                    own).compile().input_formats[0][3]
            kept = tuple(map(_kept_order, own, chosen))
            self._turned = own, kept
        turns = kept + asis[n_arena:]
        arena_avals = avals(turns)
        entry.jitted = build(turns)

        def back(src):
            t = turns[src] if src >= 0 else None
            return t and tuple(sorted(range(len(t)), key=t.__getitem__))

        def nbytes(some):
            return sum(a.size * a.dtype.itemsize for a in some)

        relaid = [a for a, t in zip(arena_avals, turns) if t is not None]
        n_params = len(param_arrays)
        entry.linear = _Linearized(
            n_outs, n_state, tuple(res_src),
            jax.tree_util.tree_structure(shapes[2]),
            n_params + len(in_arrays),
            tuple([i for i, m in enumerate(param_mask) if m]
                  + [n_params + i for i, m in enumerate(in_mask) if m]),
            arena_avals, tuple(back(src) for src in res_src),
            nbytes(arena_avals), len(relaid), nbytes(relaid), policy_name)

    def _take_arena(self, entry: _CacheEntry, like) -> Tuple[tuple, bool]:
        """A residual set for ``entry``'s forward to donate, and whether
        it was recycled. With none that fits (first step, a second call
        in one record scope, a forward whose backward never ran, another
        input shape) one is allocated on ``like``'s device, so that the
        forward program has one compiled variant, not a donating and an
        allocating one; sets of other shapes are dropped then, since the
        loop has moved on from them."""
        avals = entry.linear.arena_avals
        with self._arena_lock:
            for i in range(len(self._arena) - 1, -1, -1):
                if self._arena[i][0] == avals:
                    return self._arena.pop(i)[1], True
            self._arena.clear()
        import contextlib
        import jax
        if entry.alloc is None:
            import jax.numpy as jnp
            entry.alloc = jax.jit(lambda: tuple(
                jnp.zeros(a.shape, a.dtype) for a in avals))
        devices = like.devices()
        where = jax.default_device(next(iter(devices))) \
            if len(devices) == 1 else contextlib.nullcontext()
        with where:
            return entry.alloc(), False

    # -----------------------------------------------------------------
    def __call__(self, *args):
        import jax
        from . import autograd
        from .ndarray.ndarray import NDArray

        flat_in, in_treedef = jax.tree_util.tree_flatten(
            args, is_leaf=lambda x: isinstance(x, NDArray))
        in_arrays = [x._data for x in flat_in]

        # nested trace (this CachedOp called inside another jit trace):
        # execute imperatively and let the outer trace inline us.
        if any(isinstance(a, jax.core.Tracer) for a in in_arrays):
            return self.block._imperative_call(*args)

        # bulk-exec knobs: when disabled, run op-by-op imperatively
        # instead of one fused program (ref: MXNET_EXEC_BULK_EXEC_TRAIN /
        # _INFERENCE gating engine bulking, graph_executor.cc)
        from .base import env
        if autograd.is_training():
            if not env.get("MXNET_EXEC_BULK_EXEC_TRAIN"):
                return self.block._imperative_call(*args)
        elif not env.get("MXNET_EXEC_BULK_EXEC_INFERENCE"):
            return self.block._imperative_call(*args)

        with _span("mx.cached_op.forward", "step") as sp:
            return self._replay(flat_in, in_treedef, in_arrays, sp)

    def _replay(self, flat_in, in_treedef, in_arrays, sp):
        """Run the block's compiled program for these inputs (tracing and
        compiling it first on a new signature). ``sp`` is the call's span;
        its three children tile it and own what the call launches:
        ``mx.cached_op.prepare`` (parameters, the random key, signature and
        cache lookup), ``.launch`` (the residual set, the jitted call that
        donates it, letting go of its handles) and ``.finish`` (write-back,
        wraps, the tape record)."""
        import jax
        from . import autograd, random as _random
        from .ndarray.ndarray import NDArray
        from .ops.registry import _trace_time_flags

        mode = None
        try:
            with _span("mx.cached_op.prepare", "step", _PREPARE_ARGS):
                params = self._params()
                for p in params:
                    if p._data is None:
                        raise MXNetError(
                            f"parameter {p.name} not initialized")
                training = autograd.is_training()
                rng_key = _random.next_key()
                # a recorded call runs another program than the plain
                # forward that inference and serving keep: it linearises,
                # with respect to the arguments that carry a tape entry
                # (only those can receive a gradient), and keeps what the
                # mirror policy in force says
                record = None
                if autograd.is_recording():
                    from .util import residual_policy_name
                    record = (residual_policy_name(self.mirror),
                              tuple(_on_tape(p._data) for p in params),
                              tuple(_on_tape(x) for x in flat_in))

                self._trace_rw.acquire_read()
                mode = "read"
                # treedef is read by the pure fn at TRACE time only (traces
                # hold the write lock); assigning inside the lock — and
                # re-asserting under write exclusivity below — keeps a
                # concurrent caller's different input structure (or a
                # memory_analysis/aot_export re-lower) from being traced
                # against the wrong treedef
                self._in_treedef = in_treedef
                param_arrays = tuple(p._data._data for p in params)
                key_sig = _Signature(
                    tuple((tuple(a.shape), str(a.dtype)) for a in in_arrays),
                    tuple((tuple(a.shape), str(a.dtype))
                          for a in param_arrays),
                    in_treedef, training, _trace_time_flags(), record)

                def _new_entry():
                    # cheap: builds the entry + jit WRAPPER only (no trace/
                    # compile happens until the first execution below; a
                    # recorded entry's wrapper is built there too)
                    e = _CacheEntry()
                    if record is None:
                        e.jitted = jax.jit(self._make_pure_fn(training, e))
                    return e

                entry = self._cache.get_or_insert(key_sig, _new_entry)
                # programs 0: the children own what the call launches
                sp.set(block=type(self.block).__name__, programs=0,
                       cache="hit" if entry.warm else "miss")
                if not entry.warm:
                    # cold entry (ours or a concurrent thread's): the first
                    # execution runs the python trace, which swaps Parameter
                    # storage to Tracers — upgrade to the exclusive lock and
                    # re-read the params after no reader/trace is in flight
                    self._trace_rw.release_read()
                    mode = None
                    self._trace_rw.acquire_write()
                    mode = "write"
                    self._in_treedef = in_treedef  # no clobber possible now
                    param_arrays = tuple(p._data._data for p in params)
                    if entry.jitted is None:
                        self._linearize(entry, training, record,
                                        param_arrays, rng_key, in_arrays)
            with _span("mx.cached_op.launch", "step",
                       _ONE_PROGRAM) as launch:
                if record is None:
                    out_arrays, state = entry.jitted(param_arrays, rng_key,
                                                     *in_arrays)
                else:
                    lin = entry.linear
                    flat_args = param_arrays + (rng_key,) + tuple(in_arrays)
                    arena, recycled = self._take_arena(entry, flat_args[0])
                    flat_out = entry.jitted(param_arrays, rng_key,
                                            tuple(in_arrays), arena)
                    # the donated set's handles die here, inside the span
                    # of the call that consumed them, not as the frame goes
                    del arena
                    n = len(lin.arena_avals)  # the set comes first
                    out_arrays = flat_out[n:n + lin.n_outs]
                    state = flat_out[n + lin.n_outs:]
                    sp.set(residual_bytes=lin.residual_bytes,
                           recycled=recycled,
                           residuals_relaid=lin.relaid,
                           residuals_relaid_bytes=lin.relaid_bytes)
                    if not recycled:  # _take_arena ran its allocation
                        launch.set(programs=2)
                entry.warm = True
        finally:
            if mode == "read":
                self._trace_rw.release_read()
            elif mode == "write":
                self._trace_rw.release_write()

        with _span("mx.cached_op.finish", "step", _NO_PROGRAMS):
            # write back mutable state (moving stats) — versioned-var
            # rebind, exclusive: a concurrent replay must not capture a torn
            # set of params (only training-mode calls mutate, so serving
            # never pays)
            if entry.mutated_idx:
                self._trace_rw.acquire_write()
                try:
                    for i, s in zip(entry.mutated_idx, state):
                        params[i]._data._rebind(s)
                finally:
                    self._trace_rw.release_write()

            # efficiency plane (MXTPU_EFFICIENCY): one launch of this warm
            # program into the current step window — a list append; the cost
            # itself resolves lazily (entry_cost_stats) at step end. One
            # cached env check when the plane is off.
            if _eff().enabled():
                _eff().note_dispatch(
                    ("co_fwd", id(entry)), "cached_op",
                    f"{type(self.block).__name__}:fwd",
                    lambda op=self, k=key_sig, e=entry:
                    op.entry_cost_stats(k, e))

            ctx = flat_in[0]._ctx if flat_in else params[0]._data._ctx
            out_nds = [NDArray(a, ctx=ctx) for a in out_arrays]

            if record is not None:
                closure = jax.tree_util.tree_unflatten(
                    lin.closure_treedef,
                    [flat_out[src] if src >= 0 else flat_args[~src]
                     for src in lin.res_src])
                autograd._record_custom(
                    _CachedOpGrad(self, entry, closure,
                                  tuple(flat_out[:n])),
                    [p._data for p in params] + list(flat_in),
                    tuple(out_nds))

            return jax.tree_util.tree_unflatten(entry.out_treedef, out_nds)


def make_scan_forward(block, training: bool = False):
    """Build a reusable K-batch scanned forward for a hybridizable block:
    returns ``fn(xs)`` mapping (K, batch, ...) stacked inputs to
    (K, batch, ...) stacked outputs in ONE jitted program per call.

    The inference-side analog of SPMDTrainer.run_steps: lax.scan replays
    the compiled forward K times per dispatch, amortizing per-dispatch
    host overhead — the serving pattern for batch scoring
    (ref: the engine's bulk-exec of inference graphs,
    MXNET_EXEC_BULK_EXEC_INFERENCE). The returned callable holds the
    compiled program; build it ONCE and reuse it (rebuilding re-traces).
    """
    import jax
    from jax import lax
    from .ndarray.ndarray import NDArray, from_jax

    co = CachedOp(block)
    entry = _CacheEntry()
    co._in_treedef = jax.tree_util.tree_flatten(
        (from_jax(jax.numpy.zeros((1,))),),
        is_leaf=lambda v: isinstance(v, NDArray))[1]
    fwd = co._make_pure_fn(training, entry)

    def multi(params_t, k, stacked):
        def body(carry, x):
            outs, _state = fwd(params_t, k, x)
            return carry, outs[0]
        _, ys = lax.scan(body, 0, stacked)
        return ys

    jitted = jax.jit(multi)
    base_key = jax.random.PRNGKey(0)

    def run(xs, key=None):
        params = tuple(p._data._data for p in co._params())
        xs_arr = xs._data if isinstance(xs, NDArray) else xs
        return from_jax(jitted(params, key if key is not None else base_key,
                               xs_arr))

    return run


def scan_forward(block, xs, key=None, training: bool = False):
    """One-shot convenience over :func:`make_scan_forward` (traces per
    call — hot loops should build the callable once)."""
    return make_scan_forward(block, training)(xs, key=key)
