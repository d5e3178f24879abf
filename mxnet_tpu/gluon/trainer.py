"""Gluon Trainer (ref: python/mxnet/gluon/trainer.py:27).

Applies an Optimizer to a set of Parameters after backward. With a kvstore,
gradients ride the communication layer (XLA collectives over the mesh — see
kvstore.py) exactly like the reference's push/pull flow (trainer.py:327
allreduce_grads); without one, updates are local fused ops.

Aggregated hot path (ref: optimizer_op.cc:654 multi_sgd_update +
MXNET_OPTIMIZER_AGGREGATION_SIZE): dense parameters are grouped into
dtype/device buckets (whole by default; of up to
``MXTPU_OPTIMIZER_AGGREGATION`` params where that is set) and
each bucket is stepped by ONE jitted program with donated weight/state
buffers (optimizer/grouped.py), so a step costs O(buckets) compiled-call
launches instead of O(params). ``allreduce_grads`` likewise concatenates
same-dtype gradients into flat buckets (``MXTPU_GRAD_BUCKET_MB``) and
issues one kvstore push/pull — one collective — per bucket instead of one
per key; the kvstore's retry/chaos hooks wrap each bucketed call, so fault
semantics are preserved per bucket. Sparse (row_sparse) parameters and
gradients always take the original per-key/per-param paths.

ZeRO-1 sharded optimizer state (``MXTPU_ZERO=1``, parallel/zero.py): the
same ``_gbkt`` flat buckets are **reduce-scattered** instead of
allreduced, the grouped donated-buffer update steps only this rank's
parameter shard (optimizer state + f32 masters materialize 1/N per
rank), and the updated weights ride a per-bucket **allgather** back.
The fused finiteness sentinel is AND-reduced across ranks before any
shard applies, so a NaN anywhere skips the step everywhere and
``rollback_step`` stays shard-local. See the plane's module docstring
for partition/portability invariants.

Comm/backward overlap (ref: the dependency engine scheduling each key's
push as soon as its write dependency resolves — PAPER.md §engine,
§KVStore): with ``MXTPU_COMM_OVERLAP=on`` the loop owner brackets
``backward()`` in :meth:`Trainer.overlap_scope`, which installs the
autograd grad-ready hook and launches each bucket's collective the moment
its constituent gradients receive their final contribution DURING the
reverse pass. Buckets use the SAME forward-order layout (and so the same
``_gbkt`` keys) as the barrier path, but *launch* in finalization order —
backward finalizes later layers' grads first, so the last buckets are in
flight while backward is still producing the early layers' gradients;
``allreduce_grads`` then only flushes stragglers and splits the flat wire
buffers back. Numerically identical to the barrier path — the same
buckets, the same sums, launched earlier. Overlapped communication is charged to
the step-breakdown segment ``comm_overlapped`` (exclusive time, nested
inside ``compute``).

The overlap composes with ZeRO-1: under ``MXTPU_ZERO=1`` the same
grad-ready hook launches each bucket's **reduce-scatter** at grad
finality (rebinds deferred to finalization — autograd may still read
the live buffers), and the update path launches each bucket's weight
**allgather** as soon as that bucket's shard updates land, while the
tail buckets are still updating. Same buckets, same sums, same
collective count as the barrier plane; only the launch points move,
into ``comm_overlapped``. See parallel/zero.py for the prefetch
completion contract on distributed groups.
"""
from __future__ import annotations

import functools
import re
from typing import Dict, List, Optional

from ..base import MXNetError, check, env
from .. import optimizer as opt_mod
from ..optimizer import grouped as _grouped
from ..telemetry import memory as _memory
from ..telemetry import numerics as _numerics
from ..telemetry.step_breakdown import segment as _bd_segment
from ..telemetry.tracer import span as _span, tracer as _tracer
from .parameter import Parameter, ParameterDict

__all__ = ["Trainer"]


def _overlap_requested() -> bool:
    """Strict MXTPU_COMM_OVERLAP parse — a typo'd request to overlap must
    not silently train with the barrier path."""
    raw = str(env.get("MXTPU_COMM_OVERLAP") or "").strip().lower()
    if raw in ("", "0", "off", "false"):
        return False
    if raw in ("1", "on", "true"):
        return True
    raise MXNetError(
        f"MXTPU_COMM_OVERLAP: unknown value {raw!r} (known: on, off)")


@functools.lru_cache(maxsize=1)
def _flatten_fn():
    """One jitted concat of a gradient bucket into a flat wire buffer
    (jit's own trace cache specializes per input shapes/dtypes, so a
    single wrapper serves every bucket signature)."""
    import jax
    import jax.numpy as jnp

    def fn(*gs):
        return jnp.concatenate([g.ravel() for g in gs])
    return jax.jit(fn)


@functools.lru_cache(maxsize=256)
def _split_fn(sig):
    """Inverse of :func:`_flatten_fn`. The split outputs are rebound over
    the old per-param grad buffers (which then free), so steady-state
    grad memory stays one copy; XLA cannot alias one flat buffer into
    many differently-shaped outputs, so ``donate_argnums`` would only
    warn, not help."""
    import jax

    def fn(flat):
        out, off = [], 0
        for shape, _ in sig:
            n = 1
            for s in shape:
                n *= s
            out.append(flat[off:off + n].reshape(shape))
            off += n
        return tuple(out)
    return jax.jit(fn)


@functools.lru_cache(maxsize=1)
def _update_dispatch_counter():
    from ..telemetry import default_registry
    return default_registry().counter(
        "mxtpu_update_dispatches_total",
        "Compiled-program launches per optimizer update "
        "(aggregated: one per dtype/device bucket).")


@functools.lru_cache(maxsize=1)
def _allreduce_counter():
    from ..telemetry import default_registry
    return default_registry().counter(
        "mxtpu_allreduce_collectives_total",
        "kvstore collectives issued by Trainer.allreduce_grads "
        "(bucketed: one per gradient bucket).")


def _natural_key(name: str):
    """Numeric-aware sort key: ``dense9_weight`` < ``dense10_weight``.

    Positional parameter indices (kvstore keys, checkpointed optimizer
    state slots) derive from this order, and gluon block names embed a
    process-global counter — a plain lexicographic sort flips the order
    of structurally identical nets created at different counter values
    (``dense10_*`` < ``dense8_*``), so a resumed run would bind restored
    optimizer state to the wrong parameters."""
    return [(1, int(t)) if t.isdigit() else (0, t)
            for t in re.split(r"(\d+)", name)]


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore="device", compression_params=None,
                 update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = [params[k] for k in sorted(params.keys(),
                                                key=_natural_key)]
        if not isinstance(params, (list, tuple)):
            raise MXNetError("params must be a ParameterDict/list of Parameter")
        self._params: List[Parameter] = []
        self._param2idx: Dict[str, int] = {}
        for i, p in enumerate(params):
            if not isinstance(p, Parameter):
                raise MXNetError(f"invalid parameter {p!r}")
            self._param2idx[p.name] = i
            self._params.append(p)
        self._compression_params = compression_params
        self._contains_sparse = any(p.stype != "default" for p in self._params)
        optimizer_params = optimizer_params or {}
        self._scale = float(optimizer_params.get("rescale_grad", 1.0))
        self._init_optimizer(optimizer, optimizer_params)
        self._kvstore_arg = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._kvstore = None
        self._kv_initialized = False
        self._params_synced = False
        self._chaos_step = 0  # step clock for env-driven chaos plans
        # per-call observability for the aggregated paths (bench + the
        # dispatch-count regression test read these)
        self.last_update_dispatches = 0
        self._update_buckets = 0
        self.last_allreduce_collectives = 0
        self.last_reduce_scatter_collectives = 0
        self.last_allgather_collectives = 0
        # numerics plane (MXTPU_NUMERICS): device stat arrays of the last
        # sampled update — [(param_names, (n,6) matrix)] per bucket, left
        # UN-fetched so FitLoop rides them on its flag+loss transfer
        self.last_numerics_stats = None
        # ZeRO-1 plane: None = not yet resolved, False = off, else the
        # live parallel.zero.ZeroPlane; _zero_step carries the plane from
        # allreduce_grads (reduce-scatter ran) to the following _update;
        # _zero_declined marks a sentinel decline whose classic fallback
        # update() is the ONE sanctioned unsharded update under ZeRO
        self._zero = None
        self._zero_step = None
        self._zero_declined = False
        self._last_fused_indices: List[int] = []
        self._last_fused_created: List[int] = []
        # bucket keys already init'ed on the kvstore (keyed by the full
        # shape-signature string, so a layout change mints a fresh key)
        self._bucket_keys: Dict[str, bool] = {}
        # live comm/backward overlap scope (set on scope entry, consumed
        # by the next allreduce_grads)
        self._overlap_state: Optional["_OverlapScope"] = None

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: p for i, p in enumerate(self._params)}
        if isinstance(optimizer, opt_mod.Optimizer):
            check(not optimizer_params,
                  "optimizer_params must be empty when an Optimizer instance "
                  "is passed")
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt_mod.create(optimizer,
                                             param_dict=param_dict,
                                             **optimizer_params)
        self._updaters = [opt_mod.get_updater(self._optimizer)]

    def _init_kvstore(self):
        """Lazy kvstore creation (ref: trainer.py:169)."""
        if self._kvstore_arg and not isinstance(self._kvstore_arg, str):
            self._kvstore = self._kvstore_arg
        elif self._kvstore_arg:
            from .. import kvstore as kv_mod
            arg = str(self._kvstore_arg).lower()
            try:
                kv = kv_mod.create(self._kvstore_arg)
            except Exception as e:
                # Only the benign default local/device store may degrade to
                # direct updates; a dist or explicitly-requested exotic
                # store failing to come up must NOT silently turn a
                # multi-worker run into single-device training.
                if arg not in ("local", "device"):
                    raise MXNetError(
                        f"failed to create kvstore {self._kvstore_arg!r} "
                        "(refusing to fall back to local updates — a "
                        "misconfigured dist run would silently train "
                        f"single-device): {e}") from e
                self._kvstore = None
            else:
                # a 1-device single-worker store adds nothing over direct
                # update
                self._kvstore = kv if (kv.num_devices > 1 or
                                       kv.num_workers > 1) else None
        self._kv_initialized = True
        if self._kvstore is not None:
            for i, p in enumerate(self._params):
                self._kvstore.init(i, p.data())

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    @property
    def optimizer(self):
        return self._optimizer

    @staticmethod
    def _bucket_mb() -> float:
        try:
            return float(env.get("MXTPU_GRAD_BUCKET_MB"))
        except (TypeError, ValueError):
            return 0.0

    def overlap_scope(self, chaos_step: Optional[int] = None):
        """Context manager for one backward pass that overlaps gradient
        communication with the reverse pass (``MXTPU_COMM_OVERLAP=on``):
        the autograd grad-ready hook launches each dense bucket's kvstore
        push/pull — or, under ``MXTPU_ZERO=1``, its reduce-scatter — as
        soon as its constituent grads are final, and the following
        :meth:`allreduce_grads` call only flushes stragglers + completes
        the deferred rebinds. Returns an inactive no-op scope when
        overlap is off or there is no kvstore argument — the caller can
        always write ``with trainer.overlap_scope(): loss.backward()``.

        ``chaos_step``: the chaos clock index the upcoming step will run
        under (defaults to this trainer's own ``step()`` clock; FitLoop
        passes its step counter). A step whose grads the chaos plan will
        poison AFTER backward gets an inactive scope: overlapped
        collectives would ship the clean grads during backward — and,
        through a compressing store, advance per-key error-feedback
        residuals a second push on the same keys would then corrupt."""
        # parse FIRST: a typo'd MXTPU_COMM_OVERLAP must raise even when
        # there is no store (short-circuiting the parse away would let
        # the typo silently train with the barrier path)
        active = _overlap_requested() and bool(self._kvstore_arg)
        if active:
            from ..contrib import chaos
            plan = chaos.active()
            if plan is not None and plan.poisons_step(
                    self._chaos_step if chaos_step is None else chaos_step):
                active = False
        return _OverlapScope(self, active)

    def allreduce_grads(self):
        """Sum gradients across devices (ref: trainer.py:327). With the SPMD
        mesh backend this is an XLA psum ridden through the kvstore.

        Dense gradients are bucketed: same-dtype grads are concatenated
        into flat buffers capped at ``MXTPU_GRAD_BUCKET_MB`` and reduced
        with ONE push/pull (one collective) per bucket (ref: kvstore key
        flattening / DDP gradient bucketing), then split back over the old
        per-param grad buffers (which then free) — the flat wire buffer is
        transient, see :func:`_split_fn`. Row-sparse grads keep the
        per-key mask-pack path. Under an active :meth:`overlap_scope` the
        collectives were already launched during backward; this call
        flushes the remainder and completes the splits."""
        with _span("mx.trainer.allreduce", "step") as sp:
            self._allreduce_grads()
            sp.set(collectives=self.last_allreduce_collectives)

    def _allreduce_grads(self):
        st = self._overlap_state
        if st is not None:
            self._overlap_state = None
            st.finalize()
            return
        if not self._kv_initialized:
            self._init_kvstore()
        self.last_allreduce_collectives = 0
        self.last_reduce_scatter_collectives = 0
        self._zero_step = None
        # a fresh comm round supersedes a stale un-consumed decline (the
        # caller skipped that step's update): without this, the stale
        # flag would sanction one later bare unsharded update()
        self._zero_declined = False
        plane = self._zero_plane()
        if plane is not None:
            # ZeRO-1: reduce-scatter the same buckets instead of
            # allreduce; the following _update consumes the plane (shard
            # update + weight allgather)
            plane.reduce_scatter_grads(self)
            self._zero_step = plane
            return
        if self._kvstore is None:
            return
        from ..ndarray import sparse as _sp
        bucket_mb = self._bucket_mb()
        flat_items = []
        for i, p in enumerate(self._params):
            if p.grad_req == "null":
                continue
            g = p.grad()
            if isinstance(g, _sp.RowSparseNDArray):
                self._allreduce_rowsparse(i, g)
                continue
            if bucket_mb > 0:
                flat_items.append((i, g))
            else:
                self._kvstore.push(i, g)
                self._kvstore.pull(i, g)
                self.last_allreduce_collectives += 1
        if flat_items:
            self._allreduce_bucketed(flat_items, bucket_mb)
        if self.last_allreduce_collectives:
            _allreduce_counter().inc(self.last_allreduce_collectives)

    def _zero_plane(self):
        """The live ZeRO-1 plane, or None. Resolved once: ``MXTPU_ZERO``
        is parsed strictly (typos raise), and a non-composable
        configuration (no store, compression, ungrouped optimizer,
        sparse params, aggregation off) raises at first use instead of
        silently training unsharded."""
        if self._zero is None:
            from ..parallel import zero as _zero
            if not _zero.zero_requested():
                self._zero = False
            else:
                if not self._kv_initialized:
                    self._init_kvstore()
                self._zero = _zero.ZeroPlane(self)
        return self._zero or None

    def _allreduce_rowsparse(self, i, g):
        """Cross-worker reduce of one row_sparse gradient. Single-process
        grads are already complete (the tape saw every device's batch); a
        cross-worker reduce would need the dist store's sparse wire path —
        densify for it (ref: trainer.py requires update_on_kvstore for
        row_sparse params for the same reason)."""
        from ..ndarray import sparse as _sp
        if self._kvstore.num_workers > 1:
            # dense [grad | row-mask] reduce: the mask column makes
            # the rebuilt row set the union across workers, even
            # for rows whose reduced gradient is exactly zero
            packed = _sp.mask_pack(g)
            self._kvstore.push(i, packed)
            self._kvstore.pull(i, packed)
            reduced = _sp.mask_unpack(packed, g.shape)
            g._update(reduced._data, reduced._indices)
            self.last_allreduce_collectives += 1

    def _grad_buckets(self, items, bucket_mb):
        """Deterministic same-dtype runs capped at ``bucket_mb`` MB — the
        layout is a pure function of (param order, dtypes, cap), so the
        kvstore keys stay stable across steps."""
        cap = max(1, int(bucket_mb * (1 << 20)))
        buckets, cur, cur_bytes, cur_dtype = [], [], 0, None
        for i, g in items:
            nbytes = g.size * g._data.dtype.itemsize
            dt = str(g._data.dtype)
            if cur and (dt != cur_dtype or cur_bytes + nbytes > cap):
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append((i, g))
            cur_bytes += nbytes
            cur_dtype = dt
        if cur:
            buckets.append(cur)
        return buckets

    def _allreduce_bucketed(self, items, bucket_mb):
        for bid, bucket in enumerate(self._grad_buckets(items, bucket_mb)):
            flat = self._launch_bucket(bid, bucket)
            if flat is not None:
                self._split_bucket(bucket, *flat)

    def _launch_bucket(self, bid, bucket):
        """Push+pull one dense bucket. A flattened multi-grad bucket
        returns ``(sig, flat_nd)`` with the split DEFERRED to the caller
        (overlap launches split after backward finishes); a singleton
        rides its per-param key, pulled in place, and returns None."""
        if len(bucket) == 1:
            # a lone grad (or one larger than the cap) rides its own
            # already-initialized per-param key — no copy overhead
            i, g = bucket[0]
            self._kvstore.push(i, g)
            self._kvstore.pull(i, g)
            self.last_allreduce_collectives += 1
            return None
        sig, key = self._bucket_sig_key(bid, bucket)
        flat_nd = self._bucket_wire(key, bucket)
        if key not in self._bucket_keys:
            try:
                # the flat wire buffer must NOT be row-sharded by the
                # big-array bound — it is split back immediately
                self._kvstore.init(key, flat_nd, shard=False)
            except TypeError:  # user-supplied store without shard=
                self._kvstore.init(key, flat_nd)
            self._bucket_keys[key] = True
        # retry/chaos hooks (TransientKVError backoff, kv_flake) wrap
        # these calls per BUCKET key inside the kvstore, preserving
        # the fault semantics of the per-key path
        self._kvstore.push(key, flat_nd)
        self._kvstore.pull(key, out=flat_nd)
        self.last_allreduce_collectives += 1
        return sig, flat_nd

    @staticmethod
    def _bucket_sig_key(bid, bucket):
        """(signature, stable store key) of one dense gradient bucket.
        The key encodes the bucket's FULL shape signature (digest): if
        the layout changes mid-run (a param frozen, the MB cap changed) a
        fresh key gets a fresh store buffer and a fresh compressor
        error-feedback residual — a stale key would push a
        differently-laid-out flat into old state. Shared by the allreduce
        path and the ZeRO-1 reduce-scatter/allgather plane, so BOTH comm
        modes see one ``_gbkt*`` layout per step."""
        import hashlib
        sig = tuple((g.shape, str(g._data.dtype)) for _, g in bucket)
        total = sum(int(g.size) for _, g in bucket)
        digest = hashlib.md5(repr(sig).encode()).hexdigest()[:10]
        return sig, (f"_gbkt{bid}:{sig[0][1]}:{total}"
                     f":n{len(bucket)}:{digest}")

    @staticmethod
    def _bucket_wire(key, bucket):
        """Flatten one dense bucket into its transient flat wire buffer.
        The NDArray is ledgered under ``grad_buckets`` and lives until
        the split (or reduce-scatter slicing) rebinds the per-param grads
        and it dies — freed by the NDArray's death, so donation/free
        accounting is automatic. Shared by the allreduce push path and
        the ZeRO-1 reduce-scatter, so both comm modes' memory attribution
        stays identical."""
        from ..ndarray import ndarray as _nd
        flat = _flatten_fn()(*[g._data for _, g in bucket])
        flat_nd = _nd.NDArray(flat, ctx=bucket[0][1]._ctx)
        _memory.track_ndarray("grad_buckets", flat_nd,
                              owner=f"{key.split(':')[0]}:wire")
        return flat_nd

    @staticmethod
    def _split_bucket(bucket, sig, flat_nd):
        parts = _split_fn(sig)(flat_nd._data)
        for (_, g), arr in zip(bucket, parts):
            g._rebind(arr)

    def step(self, batch_size, ignore_stale_grad=False):
        """One optimization step: rescale by 1/batch_size, allreduce, update
        (ref: trainer.py:298)."""
        with _span("mx.trainer.step", "step", {"params": len(self._params)}):
            self._step(batch_size, ignore_stale_grad)
        _tracer.end_step()

    def _step(self, batch_size, ignore_stale_grad):
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        from ..contrib import chaos
        plan = chaos.active()
        if plan is not None:
            # drive the plan's step clock for classic backward+step loops
            # (FitLoop drives it itself and never calls step())
            plan.begin_step(self._chaos_step)
            self._chaos_step += 1
            if self._overlap_state is not None and \
                    plan.poisons_step(self._chaos_step - 1):
                # late defense for a plan installed AFTER the scope was
                # entered (overlap_scope() returns an inactive scope for
                # steps it KNOWS will be poisoned): collectives already
                # shipped the CLEAN grads during backward; consuming the
                # state would let the deferred splits overwrite the
                # poison injected below. Abandon it — allreduce re-runs
                # on the poisoned buffers and the fault bites
                self._overlap_state = None
            plan.poison_grads(self._params)
        self.allreduce_grads()
        self._update(ignore_stale_grad)

    def update(self, batch_size, ignore_stale_grad=False):
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)
        _tracer.end_step()

    def update_with_sentinel(self, batch_size, ignore_stale_grad=False):
        """Aggregated update with the global-finiteness sentinel folded
        into the compiled bucket programs: every update is guarded by one
        fused all-grads-finite reduction, applied as ``where(ok, new,
        old)`` on device. Returns the device-resident flag (fetch it with
        the loss in one transfer; on False call :meth:`rollback_step`), or
        None when the fused path cannot cover the whole parameter set —
        the caller must then use the classic check-then-update flow."""
        self._optimizer.rescale_grad = self._scale / batch_size
        flag = self._update(ignore_stale_grad, sentinel=True)
        _tracer.end_step()
        return flag

    def rollback_step(self):
        """Undo the host-side effects of the last fused sentinel step (the
        device state was already left untouched by the ``where`` guard):
        update counters, and optimizer-state objects first materialized by
        that step — so a skipped step is indistinguishable from the
        per-param path's never-applied update."""
        _grouped.rollback_counts(self._optimizer, self._last_fused_indices)
        for i in self._last_fused_created:
            self._updaters[0].states.pop(i, None)
            self._updaters[0].states_synced.pop(i, None)
            # the state objects die with the pop: release their ledger
            # bytes too, or a skipped first step would leak phantom
            # optimizer/masters accounting forever
            _memory.drop_optimizer_state(self._updaters[0], i)
        self._last_fused_indices = []
        self._last_fused_created = []

    def _update(self, ignore_stale_grad=False, sentinel=False):
        with _span("mx.trainer.update", "step") as sp:
            flag = self._apply_update(ignore_stale_grad, sentinel)
            sp.set(programs=self.last_update_dispatches,
                   buckets=self._update_buckets)
        return flag

    def _apply_update(self, ignore_stale_grad, sentinel):
        # stale sampled stats must not outlive their step: FitLoop reads
        # this attribute right after the update call
        self.last_numerics_stats = None
        self._update_buckets = 0  # grouped bucket programs, for the span
        plane = self._zero_step
        self._zero_step = None
        if plane is not None:
            return self._update_zero(plane, ignore_stale_grad, sentinel)
        declined = self._zero_declined
        self._zero_declined = False
        if not declined and self._zero_plane() is not None:
            # MXTPU_ZERO=1 but no reduce-scatter preceded this update:
            # stepping every parameter here would silently materialize
            # FULL optimizer state (and, in a worker group, consume
            # unreduced local gradients) — the exact degradation the
            # plane's strictness contract forbids. The one sanctioned
            # classic fallback is the sentinel's simulated-world decline,
            # flagged above.
            raise MXNetError(
                "MXTPU_ZERO=1: update() without a preceding "
                "allreduce_grads() reduce-scatter would apply an "
                "unsharded update. Call step(), or allreduce_grads() "
                "before update(), or unset MXTPU_ZERO.")
        updater = self._updaters[0]
        live = [(i, p) for i, p in enumerate(self._params)
                if p.grad_req != "null"]
        if not ignore_stale_grad:
            # pre-scan BEFORE applying any update: raising mid-loop would
            # leave a half-stepped model behind a supposedly recoverable
            # error (ref: trainer.py _fresh_grad check)
            stale = [p.name for _, p in live if not p._fresh_grad]
            if stale and sentinel:
                # decline instead of raising: the classic flow checks
                # finiteness FIRST and skips a non-finite step without
                # ever reaching this pre-scan — the fused path must not
                # turn that survivable skip into a crash. The fallback
                # reproduces the old ordering exactly (skip silently on
                # non-finite, raise on the next finite step).
                return None
            if stale:
                raise MXNetError(
                    f"gradient of parameter(s) {stale[:4]} is stale (not "
                    "updated by backward since the last step). This "
                    "usually means the parameter was unused in the loss, "
                    "or step() ran twice per backward. Call backward "
                    "first, or pass ignore_stale_grad=True to skip stale "
                    "parameters. No update was applied.")
        todo = [(i, p) for i, p in live if p._fresh_grad]
        self.last_update_dispatches = 0
        agg = _grouped.aggregation_size()
        if sentinel and (agg <= 0 or not todo or
                         not _grouped.eligible(updater, todo)):
            # all-or-nothing: the sentinel's single skip decision must
            # cover the complete parameter set, so aggregation off or any
            # ineligible param (sparse, un-grouped optimizer) declines
            # the fused path WITHOUT touching a single parameter — the
            # caller falls back to check-then-update
            return None
        handled, flag = set(), None
        stats_out = None
        if agg > 0 and todo:
            if sentinel:
                # numerics plane: one consume-once sampling decision per
                # step; when sampled the bucket programs emit the extra
                # stats output (same dispatch count — cost is outputs,
                # not launches). One cached flag check when off. Consumed
                # only when a grouped call actually runs — a per-param
                # step leaves the sample for the caller's fallback.
                nspec = _numerics.collect_spec()
                stats_out = [] if nspec is not None else None
                # the flag must cover EVERY live grad — including stale
                # ones skipped under ignore_stale_grad — exactly like the
                # classic host check (FitLoop._grads_finite_flag), or the
                # two paths would diverge on whether a step is skipped
                sentinel_grads = tuple(p._grad._data for _, p in live
                                       if p._grad is not None)
                idxs, n, flag, created = _grouped.grouped_update(
                    updater, todo, agg, sentinel=True,
                    sentinel_grads=sentinel_grads, stats_out=stats_out)
                handled = set(idxs)
                self._last_fused_indices = idxs
                self._last_fused_created = created
                self.last_update_dispatches += n + 1  # + finite reduction
                self._update_buckets += n
            else:
                dense = [(i, p) for i, p in todo
                         if _grouped.eligible(updater, [(i, p)])]
                if dense:
                    # collect only when the grouped call covers EVERY
                    # live param — a mixed dense/ineligible set would
                    # publish a silently under-counted "global" grad
                    # norm; leaving the sample unconsumed lets the
                    # caller's fallback cover the full set instead
                    if len(dense) == len(todo):
                        nspec = _numerics.collect_spec()
                        stats_out = [] if nspec is not None else None
                    idxs, n, _, _ = _grouped.grouped_update(
                        updater, dense, agg, stats_out=stats_out)
                    handled = set(idxs)
                    self.last_update_dispatches += n
                    self._update_buckets += n
        if stats_out:
            self.last_numerics_stats = stats_out
        for i, p in todo:
            if i in handled:
                p._fresh_grad = False
                continue
            updater(i, p.grad(), p.data())
            p._fresh_grad = False
            self.last_update_dispatches += 1
        if self.last_update_dispatches:
            _update_dispatch_counter().inc(self.last_update_dispatches)
        return flag

    def _update_zero(self, plane, ignore_stale_grad, sentinel):
        """ZeRO-1 back half (the reduce-scatter already ran in
        allreduce_grads): shard-local grouped update guarded by the
        GLOBAL finiteness verdict, then the per-bucket weight allgather
        — as one barrier after all updates, or, with
        ``MXTPU_COMM_OVERLAP=on``, launched per bucket the moment that
        bucket's shard updates land (charged to ``comm_overlapped``).
        Only this rank's parameters touch optimizer state; everyone
        else's updated weights arrive through the allgather."""
        import jax
        updater = self._updaters[0]
        self.last_update_dispatches = 0
        self.last_allgather_collectives = 0
        self._last_fused_indices = []
        self._last_fused_created = []
        live = [(i, p) for i, p in enumerate(self._params)
                if p.grad_req != "null"]
        stale = [] if ignore_stale_grad else \
            [p.name for _, p in live if not p._fresh_grad]
        if stale and sentinel and not plane.distributed:
            # decline exactly like the unsharded fused path
            # (Trainer._update's stale pre-scan): the caller's classic
            # fallback host-checks the locally-complete reduced grads
            # and reproduces the old skip-before-stale-raise ordering
            self._zero_declined = True
            return None
        flag = plane.global_finite_flag(live) if sentinel else None
        if stale:
            if sentinel:
                # distributed: the flag is already global — reproduce the
                # classic ordering (a non-finite step skips silently, a
                # finite one surfaces the stale error on every rank)
                if not bool(jax.device_get(flag)):
                    return flag
            raise MXNetError(
                f"gradient of parameter(s) {stale[:4]} is stale (not "
                "updated by backward since the last step). This "
                "usually means the parameter was unused in the loss, "
                "or step() ran twice per backward. Call backward "
                "first, or pass ignore_stale_grad=True to skip stale "
                "parameters. No update was applied.")
        todo = [(i, p) for i, p in live if p._fresh_grad]
        if not todo:
            if sentinel and not plane.distributed:
                # decline: the caller's classic fallback is sanctioned —
                # ONLY here; arming the flag on a non-sentinel call would
                # hand a later buggy bare update() an unsharded bypass
                self._zero_declined = True
                return None
            return flag
        agg = max(1, _grouped.aggregation_size())
        # numerics plane: one sampling decision covers every shard's
        # grouped call this step (simulated worlds step all ranks here,
        # so the stats matrix spans the full parameter set; a real group
        # merges shard-local stats over the byte channel at record time)
        nspec = _numerics.collect_spec()
        stats_out = [] if nspec is not None else None
        handled, created, n_disp = [], [], 0
        overlap = plane.overlap_active(self)
        if overlap:
            # overlapped allgather: walk the comm round's buckets in
            # layout order, update each bucket's shards, and launch that
            # bucket's weight allgather IMMEDIATELY — in flight while the
            # tail buckets still update (the DeviceStagingIter staging
            # idiom applied to weights). Per-param update math is
            # grouping-independent (grouped.py advances per-index
            # counters), so splitting the per-rank grouped calls per
            # bucket is bitwise-neutral vs the barrier plane.
            layout = plane.take_step_layout(self)
            todo_idx = dict(todo)
            seen = set()
            for key, bucket in layout:
                bitems = [(i, todo_idx[i]) for i, _g in bucket
                          if i in todo_idx]
                seen.update(i for i, _p in bitems)
                for r in plane.my_ranks:
                    items = [(i, p) for i, p in bitems
                             if plane.owner(i) == r]
                    if not items:
                        continue
                    idxs, n, _f, cr = _grouped.grouped_update(
                        updater, items, agg, sentinel=sentinel,
                        sentinel_flag=flag, stats_out=stats_out)
                    handled += idxs
                    created += cr
                    n_disp += n
                    self._update_buckets += n
                with _bd_segment("comm_overlapped"):
                    plane.launch_allgather_bucket(self, key, bucket)
            plane.seal_allgather(self)
            # safety net: a fresh grad outside the round's layout cannot
            # exist (the layout covers every grad), but if one ever did
            # its update must not be dropped — it just misses the wire,
            # exactly like a stale-declined param
            leftovers = [(i, p) for i, p in todo if i not in seen]
        else:
            leftovers = todo
        for r in plane.my_ranks:
            items = [(i, p) for i, p in leftovers if plane.owner(i) == r]
            if not items:
                continue
            idxs, n, _f, cr = _grouped.grouped_update(
                updater, items, agg, sentinel=sentinel,
                sentinel_flag=flag, stats_out=stats_out)
            handled += idxs
            created += cr
            n_disp += n
            self._update_buckets += n
        if stats_out is not None:
            # park even an EMPTY list (a distributed rank owning zero
            # params this step): record_step's cross-rank stats merge is
            # a collective, and a rank that silently skipped it would
            # deadlock every peer on the first sampled step
            self.last_numerics_stats = stats_out
        if sentinel:
            n_disp += 1  # the fused finite reduction
            self._last_fused_indices = handled
            self._last_fused_created = created
        if not overlap:
            # barrier allgather of the (where-guarded) updated weights:
            # wire time is charged to 'comm' so StepBreakdown/
            # trace_report attribute it, even though the call runs
            # inside the optimizer phase
            with _bd_segment("comm"):
                plane.allgather_weights(self)
        for _i, p in todo:
            p._fresh_grad = False
        self.last_update_dispatches = n_disp
        if n_disp:
            _update_dispatch_counter().inc(n_disp)
        return flag

    def get_states_bytes(self) -> bytes:
        """Serialized optimizer state in the TOPOLOGY-PORTABLE unsharded
        format: under ZeRO-1 the shards are gathered back into one full
        state dict (gather-on-save), so the bytes restore into any world
        size — including an unsharded run. CheckpointManager routes
        through here."""
        plane = self._zero_plane()
        if plane is not None:
            return plane.gather_states_bytes(self._updaters[0])
        return self._updaters[0].get_states(dump_optimizer=False)

    def set_states_bytes(self, data: bytes) -> None:
        """Restore from the unsharded format; under distributed ZeRO-1
        the local shard view is re-derived (non-local slots pruned before
        they ever touch device memory or the ledger)."""
        plane = self._zero_plane()
        keep = None
        if plane is not None and plane.distributed:
            keep = plane.local_indices()
        self._updaters[0].set_states(data, keep=keep)

    def save_states(self, fname):
        with open(fname, "wb") as f:
            f.write(self.get_states_bytes())

    def load_states(self, fname):
        with open(fname, "rb") as f:
            self.set_states_bytes(f.read())


class _OverlapScope:
    """One backward pass's comm/backward overlap state.

    Entering installs the autograd grad-ready hook; while backward runs,
    each dense bucket whose constituent grads have ALL received their
    final contribution is pushed/pulled immediately (the barrier path's
    forward-order layout, launched in finalization order: later layers'
    buckets finalize first and go out while backward still computes the
    early layers). The flat-buffer splits are deferred to
    :meth:`finalize` (called by the trainer's next ``allreduce_grads``),
    so the collectives stay in flight behind the remaining backward
    compute.

    The bucket layout is built lazily at the first hook firing: deferred-
    init parameters only materialize shapes during the first forward, and
    the kvstore itself initializes lazily. A backward that announces no
    grads (whole-graph CachedOp bypasses the tape) degrades gracefully:
    finalize launches every bucket, which is exactly the barrier path.

    Under ``MXTPU_ZERO=1`` the scope drives the plane's reduce-scatter
    instead of push/pull: the same buckets launch at grad finality
    through ``ZeroPlane.launch_bucket_rs`` (the collective is pure; only
    the launch moves), and the grad-onto-reduced-slice rebinds are
    deferred to :meth:`finalize` exactly like the dense splits — autograd
    may still read the live grad buffers mid-backward. finalize then
    hands the round's layout to the plane and arms ``_zero_step``, so
    the following update consumes the plane as if the barrier
    ``reduce_scatter_grads`` had run.

    Contract: each entered scope is paired with the following
    ``allreduce_grads``/``step`` call, which consumes it. A scope whose
    backward raised is abandoned on exit (its launched buckets hold a
    partial step's grads); a scope abandoned any other way (the caller
    skipped the update entirely) is superseded wholesale by the next
    scope's entry — interleaving an un-consumed scope with a scopeless
    ``allreduce_grads`` is caller error.
    """

    def __init__(self, trainer: Trainer, active: bool):
        self._trainer = trainer
        self.active = active
        self._cm = None
        self._buckets = None        # list of [(param_idx, grad_nd), ...]
        self._sparse = None         # [(param_idx, grad_nd), ...]
        self._owner: Dict[int, int] = {}   # id(grad) -> bucket index
        self._pending: List[int] = []
        self._launched: List = []   # per bucket: None | True | (sig, flat)
        self._nostore = False
        self._zplane = None         # ZeroPlane when MXTPU_ZERO=1

    # -- context management ---------------------------------------------
    def __enter__(self):
        # any stale state from an aborted step is superseded wholesale —
        # by INACTIVE entries too: a caller that skipped an update and
        # then entered a poisoned-step/off scope must not leave the old
        # scope's launched buckets for the next allreduce_grads to split
        # over fresh gradients
        self._trainer._overlap_state = None
        if not self.active:
            return self
        from .. import autograd
        self._cm = autograd.grad_ready_scope(self._on_ready)
        self._cm.__enter__()
        self._trainer._overlap_state = self
        self._trainer.last_allreduce_collectives = 0
        self._trainer.last_reduce_scatter_collectives = 0
        return self

    def __exit__(self, *exc):
        if self._cm is not None:
            self._cm.__exit__(*exc)
            self._cm = None
        if exc and exc[0] is not None and \
                self._trainer._overlap_state is self:
            # backward died mid-pass: buckets already launched hold a
            # partial step's grads. A later allreduce_grads (next step,
            # or a caller that catches and continues) must NOT consume
            # them — the deferred splits would overwrite fresh gradients
            # with this aborted step's values. Abandon wholesale.
            self._trainer._overlap_state = None
        return False

    # -- layout ---------------------------------------------------------
    def _ensure_ready(self) -> bool:
        """Lazy kvstore + bucket layout; returns False when there is no
        store to communicate through (overlap degrades to a no-op and
        allreduce_grads' normal no-store semantics)."""
        if self._nostore:
            return False
        if self._buckets is not None:
            return True
        t = self._trainer
        if not t._kv_initialized:
            t._init_kvstore()
        if t._kvstore is None:
            self._nostore = True
            return False
        plane = t._zero_plane()
        if plane is not None:
            # ZeRO mode: drive the plane's reduce-scatter from the hook.
            # Same per-round checks and pending-allgather drain the
            # barrier reduce_scatter_grads runs, then the SAME bucket
            # layout below (the plane guarantees dense params only)
            plane.check_comm_round()
            plane.flush_pending()
            self._zplane = plane
        from ..ndarray import sparse as _sp
        items, sparse = [], []
        # the SAME forward-order layout as the barrier path: identical
        # bucket contents and _gbkt keys whichever path runs (a store
        # compressor's per-key error-feedback residual sees one layout,
        # and toggling overlap mid-run — the tuner probes it — can't mint
        # a parallel key set). Launch order still follows FINALIZATION
        # order naturally: backward finalizes later layers' grads first,
        # so the later buckets complete — and ship — while backward is
        # still computing the early layers.
        for i, p in enumerate(t._params):
            if p.grad_req == "null" or p._grad is None:
                continue
            g = p.grad()
            if isinstance(g, _sp.RowSparseNDArray):
                sparse.append((i, g))
                continue
            items.append((i, g))
        bucket_mb = t._bucket_mb()
        if bucket_mb > 0:
            self._buckets = t._grad_buckets(items, bucket_mb)
        else:
            # per-key scheduling: every grad launches the moment it is
            # final — the reference engine's exact behavior
            self._buckets = [[it] for it in items]
        self._sparse = sparse
        self._pending = [len(b) for b in self._buckets]
        self._launched = [None] * len(self._buckets)
        for b, bucket in enumerate(self._buckets):
            for _, g in bucket:
                self._owner[id(g)] = b
        return True

    # -- the grad-ready hook (runs on the backward thread) --------------
    def _on_ready(self, gbuf) -> None:
        if not self._ensure_ready():
            return
        b = self._owner.get(id(gbuf))
        if b is None or self._launched[b] is not None:
            return
        self._pending[b] -= 1
        if self._pending[b] > 0:
            return
        # the whole bucket is final: launch its collective NOW, while
        # backward still runs. Exclusive time lands in 'comm_overlapped'
        # (nested inside the loop owner's 'compute' segment).
        with _bd_segment("comm_overlapped"):
            if self._zplane is not None:
                self._launched[b] = self._launch_zero_bucket(b)
            else:
                self._launched[b] = \
                    self._trainer._launch_bucket(b, self._buckets[b]) or True

    def _launch_zero_bucket(self, b):
        """Reduce-scatter one finalized bucket from the backward thread:
        the same ``_gbkt`` key and wire layout as the barrier plane,
        launched at grad finality. Grad rebinds wait for finalize()."""
        t = self._trainer
        bucket = self._buckets[b]
        key = t._bucket_sig_key(b, bucket)[1]
        parts, slices = self._zplane.launch_bucket_rs(t, key, bucket)
        t.last_reduce_scatter_collectives += 1
        return parts, slices

    # -- completion (from Trainer.allreduce_grads) ----------------------
    def finalize(self) -> None:
        if not self._ensure_ready():
            from ..parallel import zero as _zero
            if not self._nostore or not _zero.zero_requested():
                return
            # no-store semantics diverge under ZeRO: the barrier path
            # raises the plane's no-kvstore error rather than silently
            # training unsharded — reproduce it, don't swallow it
            self._trainer._zero_plane()
            return
        t = self._trainer
        if self._zplane is not None:
            self._finalize_zero()
            return
        # stragglers: grads that never announced (tape bypassed, stale
        # grads under ignore_stale_grad) ride the barrier path now
        for b, bucket in enumerate(self._buckets):
            if self._launched[b] is None:
                self._launched[b] = t._launch_bucket(b, bucket) or True
        for b, bucket in enumerate(self._buckets):
            r = self._launched[b]
            if r is not True:
                t._split_bucket(bucket, *r)
        for i, g in self._sparse:
            t._allreduce_rowsparse(i, g)
        if t.last_allreduce_collectives:
            _allreduce_counter().inc(t.last_allreduce_collectives)

    def _finalize_zero(self) -> None:
        """Complete the overlapped ZeRO comm round: reduce-scatter the
        stragglers (grads that never announced ride the barrier path —
        inside the caller's exposed 'comm' segment, truthfully), rebind
        this rank's grads onto the reduced slices, and hand the round's
        (key, bucket) layout to the plane so the allgather half sees the
        identical layout. Arms ``_zero_step`` like the barrier
        ``allreduce_grads`` branch does."""
        t = self._trainer
        plane = self._zplane
        # a fresh comm round supersedes a stale un-consumed decline (the
        # same contract as the barrier allreduce_grads entry)
        t._zero_declined = False
        for b in range(len(self._buckets)):
            if self._launched[b] is None:
                self._launched[b] = self._launch_zero_bucket(b)
        for parts, slices in self._launched:
            plane.finish_bucket_rs(parts, slices)
        plane._step_layout = [
            (t._bucket_sig_key(b, bucket)[1], bucket)
            for b, bucket in enumerate(self._buckets)]
        t._zero_step = plane
        if t.last_reduce_scatter_collectives:
            from ..parallel.zero import _rs_counter
            _rs_counter().inc(t.last_reduce_scatter_collectives)
