"""SPMDTrainer: one fused, sharded XLA program per training step.

This is the TPU-native replacement for the reference's whole training-loop
machinery: DataParallelExecutorGroup batch slicing + per-device executors +
kvstore push/pull + per-param optimizer ops
(python/mxnet/module/executor_group.py, src/kvstore/comm.h) become ONE
jit-compiled step over a Mesh:

    loss+grads+optimizer-update = single HLO module,
    batch sharded on 'dp', params replicated (or sharded by a ShardingPlan),
    gradient reduction = the psum GSPMD inserts because the loss averages
    over a dp-sharded batch. Buffer donation recycles parameter memory.

Works with any Gluon HybridBlock + loss Block.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..base import MXNetError, check
from ..telemetry.tracer import span as _span, tracer as _tracer

__all__ = ["SPMDTrainer"]

# ``programs`` of the step's spans (shared: the tracer copies). A launch is
# two: the cast that makes the step counter a device scalar, and the step.
_NO_PROGRAM = {"programs": 0}
_TWO_PROGRAMS = {"programs": 2}


class SPMDTrainer:
    def __init__(self, block, loss_fn, mesh=None, optimizer: str = "sgd",
                 optimizer_params: Optional[dict] = None,
                 plan=None, dtype=None, remat: Optional[bool] = None):
        import jax
        self.block = block
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.plan = plan
        # remat=True (or MXNET_BACKWARD_DO_MIRROR) recomputes activations
        # in backward instead of storing them — the memory-for-compute
        # lever for big models / long sequences
        self.remat = remat
        opt_params = dict(optimizer_params or {})
        self.lr = float(opt_params.get("learning_rate", 0.01))
        self.momentum = float(opt_params.get("momentum", 0.0))
        self.wd = float(opt_params.get("wd", 0.0))
        self.optimizer = optimizer
        check(optimizer in ("sgd", "adam"),
              "SPMDTrainer supports sgd/adam (use gluon.Trainer otherwise)")
        self.beta1 = float(opt_params.get("beta1", 0.9))
        self.beta2 = float(opt_params.get("beta2", 0.999))
        self.epsilon = float(opt_params.get("epsilon", 1e-8))

        self._param_objs: Optional[list] = None
        self._trainable: list = []
        self._aux: list = []
        self._compute_dtype = dtype
        self._step_fns: Dict[Tuple, Any] = {}
        self._opt_state = None
        self._t = 0
        self._base_key = None
        self._last_program = None

    def _collect(self, sample_data=None):
        """Resolve deferred-init params (probe forward) then place on mesh."""
        items = sorted(self.block.collect_params().items())
        if any(p._data is None for _, p in items) and sample_data is not None:
            from ..ndarray.ndarray import from_jax
            from .. import autograd
            import jax.numpy as jnp
            import numpy as _np
            # the probe runs EAGERLY against freshly initialized (default-
            # context) float32 parameters: detach the sample from any
            # device commitment (a staged accelerator batch would clash
            # with CPU-committed params) and cast low precision up so
            # conv dtype checks don't trip
            probe = jnp.asarray(_np.asarray(sample_data))
            if jnp.issubdtype(probe.dtype, jnp.floating):
                probe = probe.astype(jnp.float32)
            with autograd.pause():
                self.block._imperative_call(from_jax(probe))
            items = sorted(self.block.collect_params().items())
        self._param_objs = [p for _, p in items]
        self._trainable = [p for p in self._param_objs if p.grad_req != "null"]
        self._aux = [p for p in self._param_objs if p.grad_req == "null"]
        if self.mesh is not None:
            # _place_params shards directly onto the mesh; staging through
            # a single device first would double the transfer and could
            # OOM device 0 for models that only fit sharded
            self._place_params()
        else:
            self._consolidate_params()

    def _consolidate_params(self):
        """Move all parameter buffers onto the default backend's first
        device (the chip, where there is one) before the training loop.
        Eager initialization places parameters on the default *context*
        (mx.cpu() -> the CPU backend device, committed); a jit whose
        arguments are committed to the CPU backend runs the whole step ON
        HOST CPU. One explicit device_put here pins everything to the
        accelerator; the step's own outputs then stay there."""
        import jax
        arrays = [p._data._data for p in self._param_objs]
        if not arrays:
            return
        dev = jax.devices()[0]
        if all(next(iter(a.devices())) == dev for a in arrays):
            return
        outs = jax.device_put(arrays, dev)
        for p, a in zip(self._param_objs, outs):
            p._data._rebind(a)

    # ------------------------------------------------------------------
    def _place_params(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        repl = NamedSharding(self.mesh, PartitionSpec())
        for p in self._param_objs:
            arr = p._data._data
            if self.plan is not None:
                spec = self.plan.spec_for(p.name, arr.shape)
                sh = NamedSharding(self.mesh, spec)
            else:
                sh = repl
            p._data._rebind(jax.device_put(arr, sh))

    def _init_opt_state(self, train_arrays):
        # one fused program for ALL state buffers, not one dispatch each,
        # and each buffer placed like its parameter: a program of zeros has
        # no input to follow, and state left on the first device would cost
        # a mesh trainer a second compile of the whole step
        import jax
        import jax.numpy as jnp
        if self.optimizer == "sgd" and self.momentum == 0.0:
            return ()
        like = tuple(a.sharding for a in train_arrays)
        n_copies = 1 if self.optimizer == "sgd" else 2  # adam: means, vars
        zeros = jax.jit(
            lambda *xs: tuple(tuple(jnp.zeros_like(a) for a in xs)
                              for _ in range(n_copies)),
            out_shardings=(like,) * n_copies)(*train_arrays)
        return zeros[0] if n_copies == 1 else zeros

    def _build_step_fn(self):
        """The raw (un-jitted) single-step function
        (train, aux, opt, key, t, data, label) ->
        (loss, new_train, new_aux, new_opt)."""
        import jax
        import jax.numpy as jnp
        from ..ndarray.ndarray import NDArray, from_jax
        from .. import autograd, random as _random

        block = self.block
        loss_fn = self.loss_fn
        trainable = self._trainable
        aux = self._aux
        lr, momentum, wd = self.lr, self.momentum, self.wd
        optimizer = self.optimizer
        beta1, beta2, eps = self.beta1, self.beta2, self.epsilon
        compute_dtype = self._compute_dtype
        # remat knob read at build time, not trace time (graftcheck GC-T03)
        from ..util import mirror_wrapper
        mirror = mirror_wrapper(self.remat)

        def step(train_arrays, aux_arrays, opt_state, key, t, data, label):
            # per-step stream derived on-device from the trainer's base key:
            # fold_in(base, t) makes step() and run_steps() draw IDENTICAL
            # dropout masks for the same step index t
            step_key = jax.random.fold_in(key, t)

            def loss_of(params):
                originals = []
                for p, a in zip(trainable, params):
                    originals.append(p._data._data)
                    # mixed precision: master f32 weights, compute-dtype
                    # replicas inside the graph (grads come back f32)
                    if compute_dtype is not None and \
                            a.dtype == jnp.float32:
                        a = a.astype(compute_dtype)
                    p._data._data = a
                aux_orig = []
                for p, a in zip(aux, aux_arrays):
                    aux_orig.append(p._data._data)
                    p._data._data = a
                _random.push_trace_key(step_key)
                prev_r = autograd.set_recording(False)
                prev_t = autograd.set_training(True)
                try:
                    # only floating inputs take the compute dtype: token
                    # ids do not survive bfloat16
                    x = from_jax(data.astype(compute_dtype)
                                 if compute_dtype is not None and
                                 jnp.issubdtype(data.dtype, jnp.floating)
                                 else data)
                    out = block._imperative_call(x)
                    loss = loss_fn(out, from_jax(label))
                    loss_val = jnp.mean(loss._data.astype(jnp.float32))
                    # BatchNorm & friends rebind running stats during the
                    # forward; surface them as a has_aux output so the
                    # tracers stay inside the value_and_grad scope.
                    new_aux = tuple(p._data._data for p in aux)
                    return loss_val, new_aux
                finally:
                    autograd.set_training(prev_t)
                    autograd.set_recording(prev_r)
                    _random.pop_trace_key()
                    for p, o in zip(trainable, originals):
                        p._data._data = o
                    for p, o in zip(aux, aux_orig):
                        p._data._data = o

            (loss, new_aux), grads = jax.value_and_grad(
                mirror(loss_of),
                has_aux=True)(tuple(train_arrays))

            new_params = []
            if optimizer == "sgd":
                if momentum == 0.0:
                    for w, g in zip(train_arrays, grads):
                        gw = g.astype(w.dtype)
                        new_params.append(w - lr * (gw + wd * w))
                    new_opt = opt_state
                else:
                    new_mom = []
                    for w, g, m in zip(train_arrays, grads, opt_state):
                        gw = g.astype(w.dtype) + wd * w
                        nm = momentum * m - lr * gw
                        new_mom.append(nm)
                        new_params.append(w + nm)
                    new_opt = tuple(new_mom)
            else:  # adam
                means, vars_ = opt_state
                bc1 = 1 - beta1 ** t
                bc2 = 1 - beta2 ** t
                lr_t = lr * jnp.sqrt(bc2) / bc1
                new_m, new_v = [], []
                for w, g, m, v in zip(train_arrays, grads, means, vars_):
                    gw = g.astype(w.dtype) + wd * w
                    nm = beta1 * m + (1 - beta1) * gw
                    nv = beta2 * v + (1 - beta2) * jnp.square(gw)
                    new_m.append(nm)
                    new_v.append(nv)
                    new_params.append(w - lr_t * nm / (jnp.sqrt(nv) + eps))
                new_opt = (tuple(new_m), tuple(new_v))

            return loss, tuple(new_params), new_aux, new_opt

        return step

    def last_compiled(self):
        """The compiled executable of the most recently dispatched step
        program (``step`` or ``run_steps``), for ``memory_analysis()``,
        ``cost_analysis()`` and ``as_text()``. Re-lowers from the recorded
        ABSTRACT signature, shardings included (donated buffers die with
        each call), so with a persistent compile cache this costs one
        trace, not a recompile."""
        if self._last_program is None:
            raise MXNetError(
                "no step program dispatched yet — call step() or "
                "run_steps() first")
        fn, abstract_args = self._last_program
        return fn.lower(*abstract_args).compile()

    def _record_program(self, fn, args):
        import jax

        def abstract(a):
            # an uncommitted array (the step counter, a host batch) goes
            # wherever the committed ones are, so it carries no sharding
            sharding = a.sharding if getattr(a, "committed", False) else None
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

        self._last_program = (fn, jax.tree_util.tree_map(abstract, args))

    def program_stats(self):
        """XLA cost-model stats of the most recently dispatched step
        program: ``{"flops", "bytes_accessed", "argument_bytes",
        "temp_bytes"}``.

        The compiler's own accounting of what the compiled program
        touches — the honest numerator/denominator pair for roofline
        analysis (tools/roofline_ledger.py): achieved FLOP/s vs achieved
        HBM bandwidth."""
        import hashlib

        from ..telemetry import memory as _memory
        from ..telemetry.efficiency import compiled_program_stats
        comp = self.last_compiled()
        abstract_args = self._last_program[1]
        # ONE shared cost/memory extraction (telemetry/efficiency.py) —
        # the same parser CachedOp and the grouped optimizer use; the
        # combined stats land in the program registry (kind "spmd") so
        # the fused step ranks in forensics and the cost gauges too
        stats = compiled_program_stats(comp) or {}
        if "flops" not in stats or "argument_bytes" not in stats:
            # the historical behavior failed LOUDLY when a backend
            # reported no analyses — a silent all-zero row would read
            # as "this program is free", the exact opposite of a
            # broken diagnostic
            raise MXNetError(
                "program_stats: this backend reports no "
                f"cost/memory analysis for the compiled step program "
                f"(got fields {sorted(stats)})")
        digest = hashlib.md5(repr(abstract_args).encode()).hexdigest()[:12]
        _memory.record_program(
            "spmd", f"{type(self.block).__name__}:{digest}", dict(stats))
        return {
            "flops": float(stats.get("flops", 0.0)),
            "bytes_accessed": float(stats.get("bytes_accessed", 0.0)),
            "argument_bytes": int(stats.get("argument_bytes", 0)),
            "temp_bytes": int(stats.get("temp_bytes", 0)),
        }

    def _make_step(self, treedef_key):
        import jax
        return jax.jit(self._build_step_fn(), donate_argnums=(0, 1, 2))

    def _make_multi_step(self, treedef_key):
        """K steps fused into ONE XLA program via lax.scan.

        One dispatch per K steps amortizes the per-execution host
        overhead, and lets XLA pipeline the weight-update of step i with
        the forward of step i+1. Each microstep folds the trainer's
        base key with its step index — the same stream step() uses, so the
        trajectories (dropout masks included) are identical."""
        import jax
        from jax import lax
        step = self._build_step_fn()

        def multi(train_arrays, aux_arrays, opt_state, key, t0, datas,
                  labels):
            def body(carry, xs):
                train, aux, opt, t = carry
                d, l = xs
                loss, ntrain, naux, nopt = step(train, aux, opt, key, t,
                                                d, l)
                return (ntrain, naux, nopt, t + 1), loss

            (train, aux, opt, _), losses = lax.scan(
                body, (train_arrays, aux_arrays, opt_state, t0),
                (datas, labels))
            return losses, train, aux, opt

        return jax.jit(multi, donate_argnums=(0, 1, 2))

    # ------------------------------------------------------------------
    def _prepare(self, data, label, batch_dim=0):
        """Shared step preamble: unwrap NDArrays, resolve deferred params,
        align device commitments, shard the batch, gather param/opt arrays
        and the base RNG key. Returns (data, label, train, aux, key)."""
        import jax
        import jax.numpy as jnp
        from .. import random as _random
        from ..ndarray.ndarray import NDArray

        data = data._data if isinstance(data, NDArray) else data
        label = label._data if isinstance(label, NDArray) else label
        if self._param_objs is None:
            self._collect(sample_data=data if batch_dim == 0 else data[0])
        if self.mesh is None:
            # NDArray inputs arrive committed to the default *context*
            # device (CPU); with parameters pinned to the accelerator
            # (_consolidate_params) mixed commitments would error — move
            # batch inputs to the same device. Raw numpy arrays have no
            # commitment yet and are accepted as-is (jit coerces them).
            dev = jax.devices()[0]
            if isinstance(data, jax.Array) and dev not in data.devices():
                data = jax.device_put(data, dev)
            if isinstance(label, jax.Array) and dev not in label.devices():
                label = jax.device_put(label, dev)
        else:
            from .sharding import shard_batch
            data = shard_batch(data, self.mesh, batch_dim=batch_dim)
            label = shard_batch(label, self.mesh, batch_dim=batch_dim)

        train_arrays = tuple(p._data._data for p in self._trainable)
        aux_arrays = tuple(p._data._data for p in self._aux)
        if self._opt_state is None:
            self._opt_state = self._init_opt_state(train_arrays)
        if self._base_key is None:
            # one base key per trainer; every step folds it with its step
            # index t on device. Fetched to host because the eager RNG
            # stream lives on the default *context* (CPU) — a
            # CPU-committed argument would drag the whole jit onto the
            # host backend (see _consolidate_params).
            key = _random.next_key()
            if isinstance(key, jax.Array):
                import numpy as _np
                key = jnp.asarray(_np.asarray(key))
            self._base_key = key
        return data, label, train_arrays, aux_arrays, self._base_key

    def _finish(self, new_params, new_aux, new_opt):
        for p, a in zip(self._trainable, new_params):
            p._data._rebind(a)
        for p, a in zip(self._aux, new_aux):
            p._data._rebind(a)
        self._opt_state = new_opt

    def step(self, data, label):
        """Run one training step; returns the (device) scalar loss."""
        import jax.numpy as jnp
        with _span("mx.spmd.step", "step", _NO_PROGRAM):
            with _span("mx.spmd.prepare", "step", _NO_PROGRAM):
                data, label, train_arrays, aux_arrays, key = self._prepare(
                    data, label)
            self._t += 1
            sig = (tuple((a.shape, str(a.dtype)) for a in (data, label)),)
            fn = self._step_fns.get(sig)
            if fn is None:
                fn = self._step_fns[sig] = self._make_step(sig)
            with _span("mx.spmd.launch", "step", _TWO_PROGRAMS):
                args = (train_arrays, aux_arrays, self._opt_state, key,
                        jnp.asarray(self._t, jnp.int32), data, label)
                self._record_program(fn, args)
                loss, new_params, new_aux, new_opt = fn(*args)
            with _span("mx.spmd.finish", "step", _NO_PROGRAM):
                self._finish(new_params, new_aux, new_opt)
        _tracer.end_step()
        return loss

    def run_steps(self, data, label):
        """Run ``K = data.shape[0]`` training steps in ONE fused XLA
        dispatch (lax.scan over microbatches).

        ``data``/``label`` carry a leading steps axis: ``(K, batch, ...)``.
        Returns the ``(K,)`` per-step loss array (still on device — only
        fetch it when you need the values). Produces the same trajectory
        as K calls to :meth:`step` (per-step RNG keys are fold_in(base, t)
        in both paths, so even dropout masks match). Use it when
        per-dispatch host overhead matters or to let XLA overlap the
        optimizer update of step i with the forward of step i+1."""
        import jax.numpy as jnp
        with _span("mx.spmd.step", "step", _NO_PROGRAM):
            with _span("mx.spmd.prepare", "step", _NO_PROGRAM):
                data, label, train_arrays, aux_arrays, key = self._prepare(
                    data, label, batch_dim=1)
            k_steps = data.shape[0]
            sig = ("multi", tuple((a.shape, str(a.dtype))
                                  for a in (data, label)))
            fn = self._step_fns.get(sig)
            if fn is None:
                fn = self._step_fns[sig] = self._make_multi_step(sig)
            with _span("mx.spmd.launch", "step", _TWO_PROGRAMS):
                t0 = jnp.asarray(self._t + 1, jnp.int32)
                args = (train_arrays, aux_arrays, self._opt_state, key, t0,
                        data, label)
                self._record_program(fn, args)
                losses, new_params, new_aux, new_opt = fn(*args)
            self._t += int(k_steps)
            with _span("mx.spmd.finish", "step", _NO_PROGRAM):
                self._finish(new_params, new_aux, new_opt)
        _tracer.end_step()
        return losses
