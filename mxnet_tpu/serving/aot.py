"""Zero-compile cold start: persistent compile cache, AOT bundles, replay.

PR 5's telemetry showed XLA compilation dominating replica cold start (the
``mxtpu_xla_compile_seconds_total`` counter); this module is the three-layer
answer, so a new replica of an already-published version reaches first byte
without compiling anything:

1. **Persistent compile cache** (:func:`enable_compile_cache`): jax's
   on-disk compilation cache, keyed by (program, jaxlib version, backend) —
   a recompile of a signature any previous process compiled is a disk read.
2. **AOT executable bundles**: ``CachedOp.aot_export`` serializes the
   compiled executables of the closed ``bucket_shapes x batch-bucket``
   signature set (``jax.experimental.serialize_executable``); published
   alongside the version (``aot.bin``), ``CachedOp.aot_load`` installs them
   on a new replica with zero traces AND zero compiles. Fingerprint-gated:
   a mismatched runtime falls back to layer 1.
3. **Signature replay** (:class:`ReplayLog`): production shape traffic is
   recorded (one line per distinct signature) and new replicas prewarm
   from it — the signatures real traffic exercises, not just the
   configured closure.
"""
from __future__ import annotations

import json
import os
import threading
from typing import List, Optional, Sequence, Tuple

from ..base import env
from ..log import get_logger

__all__ = ["enable_compile_cache", "runtime_fingerprint", "ReplayLog",
           "warm_from_replay"]

_LOG = get_logger("mxnet_tpu.serving.aot")


def runtime_fingerprint() -> dict:
    """The runtime identity compiled artifacts are only valid within."""
    try:
        import jax
        import jaxlib
        backend = "unknown"
        try:
            backend = jax.default_backend()
        except Exception:
            pass
        return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "backend": backend}
    except Exception:
        return {"jax": "none", "jaxlib": "none", "backend": "none"}


def enable_compile_cache(cache_dir: Optional[str] = None) -> Optional[str]:
    """Wire jax's persistent on-disk compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache is placed from
    outside: JAX reads the variable itself and no directory is set here.
    Otherwise explicit ``cache_dir`` > ``MXTPU_COMPILE_CACHE``, and with
    neither the cache stays off. An explicit ``0``/``off`` switches this
    call off either way. Returns the directory in effect, or None.

    Every compile-time knob is forced to cache-everything (min compile
    time / entry size 0): a serving replica's goal is zero compile seconds
    on restart, not disk thrift. This is the ONE wiring implementation:
    ``util.enable_compile_cache`` (scripts) delegates here after applying
    its own policy (default ``<checkout>/.jax_cache``, CPU skipped unless a
    directory is named); the serving path honors a configured cache on
    every backend — the cold-start contract must be testable on CPU CI.
    The directory is used as given: jax's cache key already holds the
    jaxlib version, the platform and the device kind.
    """
    import jax
    cache_dir = str(cache_dir or env.get("MXTPU_COMPILE_CACHE") or "")
    if cache_dir.lower() in ("0", "off", "disabled", "none"):
        return None
    if not env.raw("JAX_COMPILATION_CACHE_DIR"):
        if not cache_dir:
            return None
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    effective = jax.config.jax_compilation_cache_dir
    _LOG.info("persistent compile cache at %s", effective)
    return effective


class ReplayLog:
    """Append-only record of the serving signatures real traffic hit.

    One JSON line per *distinct* (item shape, dtype, padded batch)
    signature — the file is a set, not a stream, so it stays tiny and a
    prewarm replays each signature once. Thread-safe (serving workers
    record concurrently); recording an already-seen signature is one set
    lookup, no IO.
    """

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._seen: set = set()
        # resume the dedup set from an existing file so restarts append
        # only genuinely new signatures
        for shape, dtype, batch in self.signatures(path):
            self._seen.add((shape, dtype, batch))

    def record(self, item_shape: Sequence[int], dtype: str,
               batch: int) -> bool:
        """Record one dispatched signature; returns True when it was new
        (and therefore appended to the file)."""
        key = (tuple(int(s) for s in item_shape), str(dtype), int(batch))
        with self._lock:
            if key in self._seen:
                return False
            self._seen.add(key)
            try:
                os.makedirs(os.path.dirname(os.path.abspath(self.path)),
                            exist_ok=True)
                with open(self.path, "a") as f:
                    f.write(json.dumps({"shape": list(key[0]),
                                        "dtype": key[1],
                                        "batch": key[2]}) + "\n")
            except OSError as e:
                _LOG.warning("replay log %s unwritable: %s", self.path, e)
        return True

    @staticmethod
    def signatures(path: str) -> List[Tuple[Tuple[int, ...], str, int]]:
        """Parse a replay file into (item_shape, dtype, batch) tuples
        (deduplicated, file order). Unparseable lines are skipped — a
        torn tail write must not take down a prewarm."""
        out: List[Tuple[Tuple[int, ...], str, int]] = []
        seen = set()
        try:
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                        key = (tuple(int(s) for s in rec["shape"]),
                               str(rec["dtype"]), int(rec["batch"]))
                    except (ValueError, KeyError, TypeError):
                        continue
                    if key not in seen:
                        seen.add(key)
                        out.append(key)
        except OSError:
            pass
        return out


def warm_from_replay(cache, path: str, signatures=None) -> int:
    """Prewarm a :class:`~mxnet_tpu.serving.cache.SignatureCache` from a
    replay file: every recorded (shape, dtype, batch) signature is driven
    once. Returns the number of fresh compiles performed (0 when the AOT
    bundle / compile cache already covered the traffic). Pass
    ``signatures`` when the caller already parsed the file."""
    import numpy as np
    from ..ndarray import ndarray as _nd
    before = cache.cache_info().misses
    if signatures is None:
        signatures = ReplayLog.signatures(path)
    for shape, dtype, batch in signatures:
        x = _nd.array(np.zeros((batch,) + shape, np.dtype(dtype)))
        out = cache(x)
        outs = out if isinstance(out, (list, tuple)) else (out,)
        for o in outs:
            o.asnumpy()
    return cache.cache_info().misses - before
