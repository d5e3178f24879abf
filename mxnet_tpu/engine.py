"""Engine surface: the async-execution control API.

Reference: include/mxnet/engine.h + src/engine/ — the dependency engine that
schedules every op by var read/write sets on per-device worker threads, with
NaiveEngine as the serialize-everything debug mode (engine.cc:33-46) and
bulk scopes batching sync ops (python/mxnet/engine.py).

TPU-native mapping: XLA's async dispatch queue IS the engine — ops return
futures, program order per device is preserved, and data dependencies are
explicit in the dataflow. What remains at this layer:
- NaiveEngine debug semantics (block after every op) via MXNET_ENGINE_TYPE,
- bulk scopes (no-op: whole-graph jit supersedes engine bulking),
- WaitForAll / WaitForVar fences,
- exception propagation to sync points (JAX raises device errors at
  block_until_ready — the exception_ptr rethrow analog,
  ref threaded_engine.h:449-456).
"""
from __future__ import annotations

import contextlib
import os

from .base import env
from .telemetry.tracer import span as _span

__all__ = ["set_bulk_size", "bulk", "wait_for_all", "engine_type",
           "set_engine_type", "NativeEngine", "shared_engine"]

_bulk_size = 15
_shared_engine = None


def shared_engine(num_workers: int = None):
    """Process-wide NativeEngine for host-side pipelines (IO prefetch,
    async checkpoint writes). Returns None when the native library is
    unavailable — callers fall back to synchronous execution."""
    global _shared_engine
    if _shared_engine is None:
        try:
            workers = num_workers or int(
                env.get("MXNET_CPU_WORKER_NTHREADS") or 1) * 4
            _shared_engine = NativeEngine(num_workers=max(2, workers))
        except Exception:
            _shared_engine = False
    return _shared_engine or None


def set_bulk_size(size: int) -> int:
    """(ref: MXEngineSetBulkSize; python/mxnet/engine.py) — retained for
    API compat; graph compilation replaces engine-level bulking."""
    global _bulk_size
    prev, _bulk_size = _bulk_size, size
    return prev


@contextlib.contextmanager
def bulk(size: int):
    prev = set_bulk_size(size)
    try:
        yield
    finally:
        set_bulk_size(prev)


def wait_for_all() -> None:
    """Engine::WaitForAll (ref: engine.h:232)."""
    from .ndarray import waitall
    waitall()


def engine_type() -> str:
    return env.get("MXNET_ENGINE_TYPE")


def set_engine_type(name: str) -> None:
    """Switch scheduling mode. 'NaiveEngine' blocks after every eager op —
    the standard way to localize async failures (ref: engine.cc:33-46)."""
    os.environ["MXNET_ENGINE_TYPE"] = name


class NativeEngine:
    """The native host-task dependency engine (src/engine.cc).

    Same contract as the reference core engine (include/mxnet/engine.h):
    ``new_var()``, ``push(fn, read_vars, write_vars)``, ``wait_for_var``,
    ``wait_all``; vars carry version counters bumped per write. Schedules
    host-side work (IO, batch assembly, checkpoint writes) on C++ worker
    threads — device-side ordering belongs to XLA's async dispatch.
    """

    def __init__(self, num_workers: int = 4):
        import ctypes
        from .io.record_io import _load_lib
        lib = _load_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._configure(lib)
        self._h = lib.mxtpu_engine_create(num_workers)
        self._keepalive = []  # trampoline refs (freed on wait_all)
        self._cb_type = ctypes.CFUNCTYPE(None, ctypes.c_void_p)

    @staticmethod
    def _configure(lib):
        import ctypes
        if getattr(lib, "_engine_configured", False):
            return
        lib.mxtpu_engine_create.restype = ctypes.c_void_p
        lib.mxtpu_engine_create.argtypes = [ctypes.c_int]
        lib.mxtpu_engine_destroy.argtypes = [ctypes.c_void_p]
        lib.mxtpu_engine_new_var.restype = ctypes.c_void_p
        lib.mxtpu_engine_new_var.argtypes = [ctypes.c_void_p]
        lib.mxtpu_engine_push.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int]
        lib.mxtpu_engine_wait_var.argtypes = [ctypes.c_void_p,
                                              ctypes.c_void_p,
                                              ctypes.c_uint64]
        lib.mxtpu_engine_wait_all.argtypes = [ctypes.c_void_p]
        lib.mxtpu_engine_var_version.restype = ctypes.c_uint64
        lib.mxtpu_engine_var_version.argtypes = [ctypes.c_void_p,
                                                 ctypes.c_void_p]
        lib._engine_configured = True

    def new_var(self):
        return self._lib.mxtpu_engine_new_var(self._h)

    def push(self, fn, read_vars=(), write_vars=(), name="engine_task"):
        """Schedule ``fn()`` after its dependencies
        (ref: Engine::PushAsync, engine.h:115).

        Returns the ctypes trampoline keeping the task callable alive;
        callers managing many short-lived tasks may hold it themselves and
        drop it once the task is known complete (e.g. after
        wait_for_var on a var the task wrote) instead of letting it
        accumulate until wait_all."""
        import ctypes

        def tramp(_):
            with _span(name, "engine"):
                fn()

        cb = self._cb_type(tramp)
        self._keepalive.append(cb)
        reads = (ctypes.c_void_p * max(1, len(read_vars)))(*read_vars)
        writes = (ctypes.c_void_p * max(1, len(write_vars)))(*write_vars)
        self._lib.mxtpu_engine_push(
            self._h, ctypes.cast(cb, ctypes.c_void_p), None,
            reads, len(read_vars), writes, len(write_vars))
        return cb

    def release(self, cbs) -> None:
        """Drop trampoline refs for tasks known to be complete."""
        for cb in cbs:
            try:
                self._keepalive.remove(cb)
            except ValueError:
                pass

    def wait_for_var(self, var, version: int = 0) -> None:
        # a closed engine (interpreter-shutdown teardown order) has
        # nothing left to wait on; blocking would hang process exit
        if self._h:
            self._lib.mxtpu_engine_wait_var(self._h, var, version)

    def wait_all(self) -> None:
        if self._h:
            self._lib.mxtpu_engine_wait_all(self._h)
        self._keepalive.clear()

    def var_version(self, var) -> int:
        return self._lib.mxtpu_engine_var_version(self._h, var)

    def close(self) -> None:
        if self._h:
            self._lib.mxtpu_engine_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
