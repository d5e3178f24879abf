"""Host->device staging (the pinned-memory transfer lane analog).

Reference: src/storage/pinned_memory_storage.h + iter_prefetcher.h — the
reference stages batches through pinned host buffers so H2D DMA overlaps
compute. The TPU-native analog: start the (async) `jax.device_put` of
batch k+1 while the trainer computes on batch k, so the PCIe
transfer hides behind the step instead of serializing in front of it.

``DeviceStagingIter`` wraps any DataIter; batches come out as NDArrays
whose buffers are already device-resident (committed to the accelerator),
which also avoids the committed-to-CPU jit pitfall (see
SPMDTrainer._consolidate_params).
"""
from __future__ import annotations

from typing import Optional

import itertools

from ..base import check
from ..telemetry import memory as _memory
from ..telemetry.step_breakdown import segment as _segment
from .io import DataBatch, DataIter

__all__ = ["DeviceStagingIter"]

_STAGE_KEYS = itertools.count(1)


def _drop_keys(led, keys):
    # ``led`` is the MemoryLedger captured at construction — NOT fetched
    # via _memory.ledger() here: this runs from weakref.finalize, which
    # cyclic GC can fire synchronously on a thread mid-way through
    # ledger()'s first-use metrics installation (plain _install_lock and
    # the registry locks held) — calling back into that path from the
    # finalizer would self-deadlock. MemoryLedger.drop itself is
    # finalizer-safe by contract (RLock). Surfaced by graftcheck GC-L03.
    try:
        for key in keys:
            led.drop("staging", key)
    except Exception:
        pass  # interpreter shutdown


class DeviceStagingIter(DataIter):
    """Stage batches onto the device one step ahead of consumption.

    >>> it = DeviceStagingIter(ImageRecordIter(...))
    >>> for batch in it:           # batch.data already on the accelerator
    ...     trainer.step(batch.data[0], batch.label[0])
    """

    def __init__(self, base_iter: DataIter, device=None, depth: int = 1):
        super().__init__(base_iter.batch_size)
        check(depth >= 1, "staging depth must be >= 1")
        self._base = base_iter
        self._depth = depth
        import jax
        self._device = device or jax.devices()[0]
        # staged NDArrays must carry a Context matching where the data
        # actually lives — keeping the source (cpu) ctx would poison
        # ctx-driven placement of scalars/copies downstream
        from ..context import Context, cpu, tpu, gpu
        platform = getattr(self._device, "platform", "cpu")
        if platform == "cpu":
            self._ctx = cpu(self._device.id)
        elif platform == "gpu":
            self._ctx = gpu(self._device.id)
        else:
            self._ctx = tpu(self._device.id)
        self._staged: list = []
        self._staged_keys: list = []  # parallel memory-ledger keys
        self._exhausted = False
        # an iterator abandoned mid-epoch must not leak its staged bytes
        # (ledger resolved NOW, outside any finalizer context)
        self._ledger = _memory.ledger()
        import weakref
        weakref.finalize(self, _drop_keys, self._ledger, self._staged_keys)

    @property
    def depth(self) -> int:
        """Staging depth: batches kept in flight ahead of consumption."""
        return self._depth

    def set_depth(self, depth: int) -> None:
        """Retarget the staging depth mid-run (the autotuner's prefetch
        knob). Deepening takes effect on the next ``next()`` (it stages
        further ahead); shallowing drains naturally — already-staged
        batches are served, never dropped."""
        check(depth >= 1, "staging depth must be >= 1")
        self._depth = int(depth)

    @property
    def provide_data(self):
        return self._base.provide_data

    @property
    def provide_label(self):
        return self._base.provide_label

    def reset(self):
        self._base.reset()
        self._staged.clear()
        self._drop_staged_keys()
        self._exhausted = False

    def _drop_staged_keys(self):
        led = _memory.ledger()
        for key in self._staged_keys:
            led.drop("staging", key)
        self._staged_keys.clear()

    def _stage_one(self) -> bool:
        """Kick off the async H2D transfer of the next host batch."""
        import jax
        from ..ndarray.ndarray import NDArray
        try:
            batch = self._base.next()
        except StopIteration:
            return False

        def put(nd_arr):
            # device_put dispatches asynchronously: the DMA overlaps
            # whatever the caller does until the array is first used
            return NDArray(jax.device_put(nd_arr._data, self._device),
                           ctx=self._ctx)

        with _segment("h2d"):
            staged = DataBatch(
                [put(d) for d in (batch.data or [])],
                [put(l) for l in (batch.label or [])],
                pad=batch.pad, index=getattr(batch, "index", None),
                bucket_key=getattr(batch, "bucket_key", None))
            self._staged.append(staged)
            # ledger the staged-ahead device bytes (category 'staging'):
            # live from the device_put here until the consumer pops the
            # batch — the prefetch depth is visible memory, and the one
            # knob (set_depth) the autotuner moves it with
            key = ("stage", next(_STAGE_KEYS))
            self._staged_keys.append(key)
            _memory.ledger().set(
                "staging", key,
                sum(_memory.nd_bytes(a) for a in
                    (staged.data or []) + (staged.label or [])),
                owner=f"staging:{type(self._base).__name__}")
        return True

    def next(self) -> DataBatch:
        while not self._exhausted and len(self._staged) <= self._depth:
            if not self._stage_one():
                self._exhausted = True
        if not self._staged:
            raise StopIteration
        out = self._staged.pop(0)
        if self._staged_keys:
            _memory.ledger().drop("staging", self._staged_keys.pop(0))
        # refill the pipeline: start the next transfer before returning
        if not self._exhausted and len(self._staged) <= self._depth \
                and not self._stage_one():
            self._exhausted = True
        return out
