"""Collective operations over the device mesh.

This is the communication backend that replaces the reference's entire
kvstore comm stack: CommCPU/CommDevice reduction (src/kvstore/comm.h),
NCCL reduce/broadcast (src/kvstore/kvstore_nccl.h), and the ps-lite
push/pull transport (src/kvstore/kvstore_dist.h) all map to XLA collectives
(psum / all_gather / reduce_scatter / ppermute / all_to_all) laid onto the
ICI mesh by GSPMD. DCN between slices is handled by the same primitives via
jax.distributed process groups — same API, different links.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

from ..base import MXNetError, check

__all__ = ["allreduce", "allgather", "reduce_scatter", "broadcast",
           "ppermute_ring", "all_to_all", "barrier", "device_allreduce",
           "measure_allreduce_bandwidth", "cross_process_reduce_scatter",
           "cross_process_exchange_bytes", "cross_process_allgather_object",
           "cross_process_reform"]


def _jax():
    import jax
    return jax


# -- CPU-backend cross-process fallback ------------------------------------
# This jaxlib build cannot run multiprocess XLA computations on the CPU
# backend ("Multiprocess computations aren't implemented on the CPU
# backend"), which took out every dist_tpu_sync collective in CPU CI. The
# fallback rides the jax.distributed *coordination service* key-value store
# (the same service the processes already rendezvoused through): each rank
# publishes its buffer, reads its peers', reduces on host, and passes a
# barrier. Functional parity, not bandwidth — the XLA path stays the one
# and only transport on real accelerator backends.

import itertools as _itertools

_coord_seq = _itertools.count()


def _coord_timeout_ms() -> int:
    """``MXTPU_COORD_TIMEOUT_MS``: bound on each blocking coordination-
    service get/barrier hop. A rank whose peer died blocks at most this
    long before the hop raises — under the fleet supervisor this is what
    turns "survivor wedged behind a dead peer" into a bounded, visible
    failure it can act on. Strict parse: an unparseable bound must not
    silently become an unbounded wait."""
    from ..base import env
    try:
        t = int(env.get("MXTPU_COORD_TIMEOUT_MS"))
    except (TypeError, ValueError) as e:
        raise MXNetError(
            f"MXTPU_COORD_TIMEOUT_MS: not an integer: "
            f"{env.raw('MXTPU_COORD_TIMEOUT_MS')!r}") from e
    check(t > 0, f"MXTPU_COORD_TIMEOUT_MS must be > 0, got {t}")
    return t


def _coord_client():
    from jax._src import distributed
    client = distributed.global_state.client
    check(client is not None,
          "cross-process collective without jax.distributed initialized")
    return client


def _use_coord_fallback() -> bool:
    import jax
    return jax.process_count() > 1 and jax.default_backend() == "cpu"


def _coord_exchange(arr, tag: str):
    """Publish this rank's array under ``tag`` and fetch every rank's;
    returns the list indexed by rank. All ranks must call with the SAME
    tag sequence (the usual SPMD collective contract).

    Comm observability: the whole exchange is one collective-ledger
    record, and the peer rank each blocking get is waiting on is stamped
    into it (``note_waiting``) — when a peer never publishes, the hung-
    collective flight recorder names that rank as the absent one."""
    import jax
    import numpy as np
    from ..telemetry import collective as _coll
    client = _coord_client()
    rank, nproc = jax.process_index(), jax.process_count()
    prefix = f"mxtpu_coll/{tag}"
    arr = np.ascontiguousarray(arr)
    tok = _coll.enter("exchange", tag, arr.nbytes, rank) \
        if _coll.enabled() else None
    try:
        client.key_value_set_bytes(f"{prefix}/{rank}", arr.tobytes())
        parts = []
        for r in range(nproc):
            if r == rank:
                parts.append(arr)
                continue
            if tok is not None:
                _coll.note_waiting(tok, r)
            buf = client.blocking_key_value_get_bytes(f"{prefix}/{r}",
                                                      _coord_timeout_ms())
            parts.append(np.frombuffer(bytearray(buf),
                                       arr.dtype).reshape(arr.shape))
        if tok is not None:
            # still a hang point: a peer that dies between publishing
            # and the done-barrier strands us HERE — keep the record
            # truthful instead of clearing the waiting stamp
            _coll.note_waiting(tok, "barrier")
        # everyone has read everything before rank 0 garbage-collects
        # the keys
        client.wait_at_barrier(f"{prefix}/done", _coord_timeout_ms())
        if rank == 0:
            for r in range(nproc):
                try:
                    client.key_value_delete(f"{prefix}/{r}")
                except Exception:
                    pass
        return parts
    finally:
        if tok is not None:
            _coll.exit_(tok)


def allreduce(x, mesh, axis: str = "dp", op: str = "sum"):
    """AllReduce a replicated-per-shard array along a mesh axis using a
    shard_map psum (ref: the kvstore push+pull round trip)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from .compat import shard_map

    def f(v):
        if op == "sum":
            return jax.lax.psum(v, axis)
        if op == "mean":
            return jax.lax.pmean(v, axis)
        if op == "max":
            return jax.lax.pmax(v, axis)
        raise MXNetError(f"unknown reduce op {op}")

    spec = P(*(None,) * x.ndim)
    return shard_map(f, mesh=mesh, in_specs=(spec,), out_specs=spec,
                     check_vma=False)(x)


def make_host_mesh():
    """A 1-D "hosts" mesh with exactly ONE device per process — the
    communication domain for per-process values (dist kvstore). Using all
    devices would make psum overcount by devices-per-process."""
    import jax
    import numpy as _np2
    from jax.sharding import Mesh
    per_proc = {}
    for d in jax.devices():
        per_proc.setdefault(d.process_index, d)
    devs = [per_proc[i] for i in sorted(per_proc)]
    return Mesh(_np2.asarray(devs), ("hosts",))


@functools.lru_cache(maxsize=None)
def _cross_process_fn(mesh, axis, op, ndim):
    """Compiled psum-over-hosts program, cached per (mesh, axis, op,
    rank) so the per-key, per-iteration kvstore push path does not
    re-trace (shapes vary per key but jit caches per shape under one
    function object)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from .compat import shard_map

    def f(v):
        red = {"sum": jax.lax.psum, "mean": jax.lax.pmean,
               "max": jax.lax.pmax}[op]
        return red(v[0], axis)

    # multi-host shard_map must run under jit (eager mode tries to copy
    # the operand to non-addressable devices)
    return jax.jit(shard_map(f, mesh=mesh, in_specs=(P(axis),),
                             out_specs=P(*([None] * ndim)),
                             check_vma=False))


def cross_process_allreduce(local, mesh, axis: str = "hosts",
                            op: str = "sum"):
    """AllReduce of per-PROCESS local values over a one-device-per-process
    mesh (make_host_mesh): the dist kvstore push path — each worker holds
    its own merged gradient; the result is the sum, replicated to every
    worker.

    The local array is lifted into a global array with one shard per
    process on `axis` (jax.make_array_from_process_local_data), psum'd
    with shard_map, and the replicated result is returned as host numpy.
    """
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    nproc = mesh.devices.size
    check(nproc == jax.process_count(),
          f"cross_process_allreduce needs a one-device-per-process mesh "
          f"(make_host_mesh); got {nproc} devices for "
          f"{jax.process_count()} processes")
    if _use_coord_fallback():
        parts = _coord_exchange(np.asarray(local),
                                f"ar{next(_coord_seq)}")
        if op == "sum":
            return sum(parts[1:], parts[0].copy())
        if op == "mean":
            return sum(parts[1:], parts[0].copy()) / len(parts)
        if op == "max":
            return np.maximum.reduce(parts)
        raise MXNetError(f"unknown reduce op {op}")
    local = np.asarray(local)[None]
    gshape = (nproc,) + local.shape[1:]
    garr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(axis)), local, gshape)
    out = _cross_process_fn(mesh, axis, op, local.ndim - 1)(garr)
    # fully replicated -> every process can materialize it
    return np.asarray(out)


def cross_process_allgather(local, mesh, axis: str = "hosts"):
    """AllGather of per-PROCESS local values over a one-device-per-process
    mesh: every worker receives the (nproc, ...) stack. This is the wire
    hop for compressed-gradient push — the payload that crosses DCN is
    whatever dtype/size `local` has (e.g. packed 2-bit codes)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    nproc = mesh.devices.size
    check(nproc == jax.process_count(),
          f"cross_process_allgather needs a one-device-per-process mesh; "
          f"got {nproc} devices for {jax.process_count()} processes")
    if _use_coord_fallback():
        return np.stack(_coord_exchange(np.asarray(local),
                                        f"ag{next(_coord_seq)}"))
    local = np.asarray(local)[None]
    gshape = (nproc,) + local.shape[1:]
    garr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P(axis)), local, gshape)
    out = _cross_process_gather_fn(mesh, axis, local.ndim - 1)(garr)
    return np.asarray(out)


@functools.lru_cache(maxsize=None)
def _cross_process_gather_fn(mesh, axis, ndim):
    import jax
    from jax.sharding import PartitionSpec as P
    from .compat import shard_map

    def f(v):
        return jax.lax.all_gather(v[0], axis)

    return jax.jit(shard_map(f, mesh=mesh, in_specs=(P(axis),),
                             out_specs=P(*([None] * (ndim + 1))),
                             check_vma=False))


def _tile_layout(all_parts, n: int):
    """Rank-major tiled permutation for a ragged reduce-scatter.

    ``all_parts[r]`` is rank r's ``[lo, hi)`` segments of an ``n``-element
    flat buffer (parameter-granular, so per-rank totals differ). A tiled
    ``psum_scatter`` needs EQUAL tiles, so: tile size ``T`` is the max
    per-rank element count, and output slot ``r*T + k`` holds the k-th
    element of rank r's concatenated segments — pad slots point at index
    ``n``, a zero appended by the caller. Returns ``(counts, T, perm)``
    with ``perm`` an int64 index vector of length ``world*T``.

    The padding rule callers gate on: tiled wire cost is ``world*T``
    elements vs the allreduce fallback's ``~2n``; take the tiled path
    only when ``world*T < 2n`` (a single rank owning nearly everything
    would otherwise pad every other rank's tile up to its size and ship
    more bytes than the allreduce it replaces)."""
    import numpy as np
    counts = [sum(hi - lo for lo, hi in ap) for ap in all_parts]
    T = max(counts) if counts else 0
    perm = np.full(len(all_parts) * T, n, dtype=np.int64)
    for r, ap in enumerate(all_parts):
        off = r * T
        for lo, hi in ap:
            perm[off:off + (hi - lo)] = np.arange(lo, hi, dtype=np.int64)
            off += hi - lo
    return counts, T, perm


@functools.lru_cache(maxsize=None)
def _rs_tile_fn(mesh, axis):
    """Compiled tiled ``psum_scatter`` over the hosts mesh: every process
    contributes its rank-major padded wire buffer and keeps ONLY its own
    reduced tile. The input is DONATED — the padded wire buffer is
    transient by construction and dies inside the collective instead of
    living on until the caller's slicing."""
    import jax
    from jax.sharding import PartitionSpec as P
    from .compat import shard_map

    def f(v):
        return jax.lax.psum_scatter(v[0], axis, scatter_dimension=0,
                                    tiled=True)

    return jax.jit(shard_map(f, mesh=mesh, in_specs=(P(axis),),
                             out_specs=P(axis), check_vma=False),
                   donate_argnums=(0,))


def _coord_segment_reduce(local, all_parts, tag: str):
    """Coordination-service reduce-scatter: each rank publishes, per
    PEER, only the segments that peer owns (one ``{src}to{dst}`` blob per
    pair), then sums the ``{peer}to{me}`` blobs with its own contribution
    — ``~n`` elements cross the wire per rank instead of the full-buffer
    exchange's ``world*n``. Ledger kind is ``reduce_scatter`` (this IS
    one, unlike the allreduce-shaped ``exchange``), with the same
    per-peer waiting stamps and done-barrier as ``_coord_exchange``.
    Returns rank's reduced segments in ``all_parts[rank]`` order."""
    import jax
    import numpy as np
    from ..telemetry import collective as _coll
    client = _coord_client()
    rank, nproc = jax.process_index(), jax.process_count()
    prefix = f"mxtpu_coll/{tag}"
    local = np.ascontiguousarray(local)
    blobs = {d: np.concatenate(
        [local[lo:hi] for lo, hi in all_parts[d]] or
        [local[:0]]) for d in range(nproc)}
    sent = sum(b.nbytes for d, b in blobs.items() if d != rank)
    tok = _coll.enter("reduce_scatter", tag, sent, rank) \
        if _coll.enabled() else None
    try:
        # a rank that owns NOTHING in this bucket has zero-length blobs
        # in both directions — never ship those: a zero-length value
        # through the coordination-service KV hard-crashes the client
        # (observed SIGSEGV in blocking get), and there is nothing to
        # sum anyway. The done-barrier below still syncs every rank.
        for d in range(nproc):
            if d != rank and blobs[d].size:
                client.key_value_set_bytes(f"{prefix}/{rank}to{d}",
                                           blobs[d].tobytes())
        total = blobs[rank].copy()
        if total.size:
            for s in range(nproc):
                if s == rank:
                    continue
                if tok is not None:
                    _coll.note_waiting(tok, s)
                buf = client.blocking_key_value_get_bytes(
                    f"{prefix}/{s}to{rank}", _coord_timeout_ms())
                total = total + np.frombuffer(bytearray(buf), local.dtype)
        if tok is not None:
            _coll.note_waiting(tok, "barrier")  # see _coord_exchange
        client.wait_at_barrier(f"{prefix}/done", _coord_timeout_ms())
        if rank == 0:
            for s in range(nproc):
                for d in range(nproc):
                    if s != d and blobs[d].size:
                        try:
                            client.key_value_delete(f"{prefix}/{s}to{d}")
                        except Exception:
                            pass
        out, off = [], 0
        for lo, hi in all_parts[rank]:
            out.append(total[off:off + (hi - lo)])
            off += hi - lo
        return out
    finally:
        if tok is not None:
            _coll.exit_(tok)


def cross_process_reduce_scatter(local, mesh, parts, axis: str = "hosts",
                                 op: str = "sum", all_parts=None):
    """Reduce per-PROCESS flat buffers element-wise and return only the
    ``[lo, hi)`` slices named by ``parts`` — the ZeRO-1 gradient plane:
    each rank keeps exactly the reduced segments its optimizer shard
    consumes. All ranks must call per the usual SPMD collective contract
    (same buffer shape, each with its own ``parts``).

    ``all_parts`` (rank-indexed list of every rank's segments, identical
    on all callers) unlocks the true reduce-scatter wire cost: the XLA
    path pads each rank's ragged segments to equal ``T``-element tiles
    (rank-major permutation, :func:`_tile_layout`) and runs one tiled
    ``psum_scatter`` whenever ``world*T < 2n`` — below that the padding
    would out-ship the psum+slice fallback, which then still applies.
    The coord fallback (multiprocess CPU) sends each peer only the
    segments it owns (:func:`_coord_segment_reduce`). Without
    ``all_parts`` both paths degrade to the full-buffer form:
    exchange+sum+slice on CPU, psum+slice on XLA."""
    import jax
    import numpy as np
    nproc = mesh.devices.size
    check(nproc == jax.process_count(),
          f"cross_process_reduce_scatter needs a one-device-per-process "
          f"mesh (make_host_mesh); got {nproc} devices for "
          f"{jax.process_count()} processes")
    check(op == "sum", f"unsupported reduce-scatter op {op!r}")
    local = np.asarray(local)
    n = int(local.size)
    if all_parts is not None:
        check(len(all_parts) == nproc,
              f"all_parts covers {len(all_parts)} ranks, world is {nproc}")
        rank = jax.process_index()
        check([tuple(p) for p in parts] ==
              [tuple(p) for p in all_parts[rank]],
              "cross_process_reduce_scatter: parts != all_parts[rank] — "
              "the caller's own segments must match the shared layout")
    if _use_coord_fallback():
        if all_parts is not None:
            return _coord_segment_reduce(local, all_parts,
                                         f"rs{next(_coord_seq)}")
        bufs = _coord_exchange(local, f"rs{next(_coord_seq)}")
        total = bufs[0].copy()
        for b in bufs[1:]:
            total = total + b
        return [total[lo:hi] for lo, hi in parts]
    if all_parts is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        counts, T, perm = _tile_layout(all_parts, n)
        if T > 0 and nproc * T < 2 * n:
            padded = np.concatenate([local, np.zeros(1, local.dtype)])
            wire = np.ascontiguousarray(padded[perm])[None]
            garr = jax.make_array_from_process_local_data(
                NamedSharding(mesh, P(axis)), wire, (nproc, nproc * T))
            out = _rs_tile_fn(mesh, axis)(garr)
            tile = np.asarray(out.addressable_shards[0].data)
            rank = jax.process_index()
            res, off = [], 0
            for lo, hi in parts:
                res.append(tile[off:off + (hi - lo)])
                off += hi - lo
            return res
    full = cross_process_allreduce(local, mesh, axis=axis, op=op)
    return [np.asarray(full[lo:hi]) for lo, hi in parts]


def cross_process_exchange_bytes(payload: bytes, tag: str):
    """Publish this rank's byte payload under ``tag`` and fetch every
    rank's (rank-indexed list). Rides the jax.distributed coordination-
    service KV store — the transport for RAGGED payloads (pickled
    optimizer-state shards, per-rank weight segments) that the
    fixed-shape array collectives cannot carry. Same contract as
    :func:`_coord_exchange`: all ranks call with the same tag sequence.
    Records into the collective ledger with per-peer waiting notes, like
    ``_coord_exchange`` — this hop is where a surviving rank blocks when
    a peer dies, so the flight recorder must see it."""
    import jax
    from ..telemetry import collective as _coll
    client = _coord_client()
    rank, nproc = jax.process_index(), jax.process_count()
    prefix = f"mxtpu_coll/{tag}"
    tok = _coll.enter("exchange_bytes", tag, len(payload), rank) \
        if _coll.enabled() else None
    try:
        client.key_value_set_bytes(f"{prefix}/{rank}", payload)
        outs = []
        for r in range(nproc):
            if r == rank:
                outs.append(payload)
                continue
            if tok is not None:
                _coll.note_waiting(tok, r)
            outs.append(bytes(client.blocking_key_value_get_bytes(
                f"{prefix}/{r}", _coord_timeout_ms())))
        if tok is not None:
            _coll.note_waiting(tok, "barrier")  # see _coord_exchange
        client.wait_at_barrier(f"{prefix}/done", _coord_timeout_ms())
        if rank == 0:
            for r in range(nproc):
                try:
                    client.key_value_delete(f"{prefix}/{r}")
                except Exception:
                    pass
        return outs
    finally:
        if tok is not None:
            _coll.exit_(tok)


def cross_process_allgather_object(obj, tag_prefix: str = "obj"):
    """Ragged allgather of one picklable object per rank (rank-indexed
    list) over the coordination-service byte channel — the ZeRO-1 weight
    allgather hop (per-rank segment sizes differ, so the tiled XLA
    all_gather cannot carry them)."""
    import pickle
    blobs = cross_process_exchange_bytes(
        pickle.dumps(obj), f"{tag_prefix}{next(_coord_seq)}")
    return [pickle.loads(b) for b in blobs]


def cross_process_reform(tag: str, expect: Optional[int] = None):
    """Membership rendezvous for elastic resume (``parallel/elastic.py``):
    every process publishes a ``{rank, pid, host}`` record through the
    jax.distributed coordination-service KV store and reads the full
    roster back — the exchange's barrier IS the group re-formation, the
    same KV-store path every CPU-backend collective already rides (and
    the ps-lite elastic-membership analog, PAPER.md §KVStore). Returns
    the roster sorted by rank. A member that never launched blocks the
    exchange until its bounded get times out — that is the transport's
    own failure mode, and ranks are ``jax.process_index()`` over
    ``process_count()``, so a completed exchange is contiguous by
    construction. What this call ADDS is the ``expect`` validation: a
    group re-formed at the wrong size (checkpoint world vs live process
    count drift) must fail loudly at resume, not at the first training
    collective."""
    import os
    import socket
    import jax
    rec = {"rank": int(jax.process_index()), "pid": os.getpid(),
           "host": socket.gethostname()}
    roster = cross_process_allgather_object(rec, tag_prefix=f"rf_{tag}_")
    if expect is not None:
        check(len(roster) == int(expect),
              f"cross_process_reform: {len(roster)} member(s) joined but "
              f"the resume expects world {expect}")
    return sorted(roster, key=lambda m: int(m["rank"]))


def device_allreduce(arrays, mesh, axis: str = "dp", op: str = "sum"):
    """Fused allreduce of a list of arrays (one compiled program for the
    whole gradient bucket, like the reference's grouped NCCL launches,
    kvstore_nccl.h:270-296)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from .compat import shard_map

    specs = tuple(P(*(None,) * a.ndim) for a in arrays)

    def f(*vs):
        red = jax.lax.psum if op == "sum" else jax.lax.pmean
        return tuple(red(v, axis) for v in vs)

    return shard_map(f, mesh=mesh, in_specs=specs, out_specs=specs,
                     check_vma=False)(*arrays)


def allgather(x, mesh, axis: str = "dp", tiled_axis: int = 0):
    import jax
    from jax.sharding import PartitionSpec as P
    from .compat import shard_map

    in_spec = [None] * x.ndim
    in_spec[tiled_axis] = axis
    def f(v):
        return jax.lax.all_gather(v, axis, axis=tiled_axis, tiled=True)
    return shard_map(f, mesh=mesh, in_specs=(P(*in_spec),),
                     out_specs=P(*([None] * x.ndim)), check_vma=False)(x)


def reduce_scatter(x, mesh, axis: str = "dp", scatter_axis: int = 0):
    import jax
    from jax.sharding import PartitionSpec as P
    from .compat import shard_map

    out_spec = [None] * x.ndim
    out_spec[scatter_axis] = axis
    def f(v):
        return jax.lax.psum_scatter(v, axis, scatter_dimension=scatter_axis,
                                    tiled=True)
    return shard_map(f, mesh=mesh, in_specs=(P(*([None] * x.ndim)),),
                     out_specs=P(*out_spec), check_vma=False)(x)


def broadcast(x, mesh, axis: str = "dp", root: int = 0):
    """Broadcast shard `root`'s value to all (ref: kvstore pull)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from .compat import shard_map

    def f(v):
        idx = jax.lax.axis_index(axis)
        masked = jnp.where(idx == root, v, jnp.zeros_like(v))
        return jax.lax.psum(masked, axis)

    spec = P(*(None,) * x.ndim)
    return shard_map(f, mesh=mesh, in_specs=(spec,), out_specs=spec,
                     check_vma=False)(x)


def ppermute_ring(x, mesh, axis: str = "sp", shift: int = 1):
    """Ring rotation along an axis — the building block of ring attention."""
    import jax
    from jax.sharding import PartitionSpec as P
    from .compat import shard_map

    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    perm = [(i, (i + shift) % n) for i in range(n)]
    in_spec = [axis] + [None] * (x.ndim - 1)

    def f(v):
        return jax.lax.ppermute(v, axis, perm)

    return shard_map(f, mesh=mesh, in_specs=(P(*in_spec),),
                     out_specs=P(*in_spec), check_vma=False)(x)


def all_to_all(x, mesh, axis: str = "sp", split_axis: int = 1,
               concat_axis: int = 0):
    """DeepSpeed-Ulysses style axis exchange for sequence parallelism."""
    import jax
    from jax.sharding import PartitionSpec as P
    from .compat import shard_map

    in_spec = [None] * x.ndim
    in_spec[concat_axis] = axis
    out_spec = [None] * x.ndim
    out_spec[split_axis] = axis

    def f(v):
        return jax.lax.all_to_all(v, axis, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)

    return shard_map(f, mesh=mesh, in_specs=(P(*in_spec),),
                     out_specs=P(*out_spec), check_vma=False)(x)


def barrier(mesh=None) -> None:
    """Global sync point (ref: ps::Postoffice::Barrier). Single-process:
    drain the dispatch queue."""
    import jax
    if mesh is None:
        (jax.device_put(0) + 0).block_until_ready()
        return
    if jax.process_count() > 1:
        if _use_coord_fallback():
            from ..telemetry import collective as _coll
            tag = f"bar{next(_coord_seq)}"
            tok = _coll.enter("barrier", tag, 0, jax.process_index()) \
                if _coll.enabled() else None
            try:
                if tok is not None:
                    _coll.note_waiting(tok, "all")
                _coord_client().wait_at_barrier(
                    f"mxtpu_coll/{tag}", _coord_timeout_ms())
            finally:
                if tok is not None:
                    _coll.exit_(tok)
            return
        import numpy as np
        # the collective itself is the rendezvous
        cross_process_allreduce(np.zeros((), np.float32), mesh,
                                axis=mesh.axis_names[0])
        return
    import jax.numpy as jnp
    allreduce(jnp.zeros(()), mesh, axis=mesh.axis_names[0]).block_until_ready()


def measure_allreduce_bandwidth(mesh, size_mb: float = 64.0,
                                axis: str = "dp", iters: int = 10,
                                shapes=None):
    """Allreduce bandwidth in GB/s/device with the reference's formula
    ``2(n-1)/n * size / t`` (ref: tools/bandwidth/measure.py:138).

    ``shapes``: allreduce one buffer per shape in a single fused program
    (the model-gradient-shaped workload of measure.py's real-model mode)
    instead of one flat ``size_mb`` tensor."""
    import time
    import jax
    import jax.numpy as jnp

    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    if shapes is None:
        arrays = [jnp.ones((int(size_mb * 1e6 / 4),), jnp.float32)]
    else:
        arrays = [jnp.ones(s, jnp.float32) for s in shapes]
    total_bytes = sum(a.nbytes for a in arrays)
    f = jax.jit(lambda *vs: device_allreduce(list(vs), mesh, axis=axis))
    jax.block_until_ready(f(*arrays))  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*arrays)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    bw = 2 * (n - 1) / n * total_bytes / dt / 1e9
    return bw
