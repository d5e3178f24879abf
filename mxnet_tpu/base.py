"""Foundation utilities: errors, logging, env-config registry, typed params.

TPU-native replacement for the dmlc-core substrate the reference is built on
(ref: include/mxnet/base.h, dmlc/logging.h, dmlc/parameter.h). Instead of
C++ CHECK macros and DMLC_DECLARE_PARAMETER structs, we provide:

- :class:`MXNetError` — the framework exception (ref: python/mxnet/base.py).
- ``check(cond, msg)`` — CHECK() analog raising MXNetError.
- :class:`EnvRegistry` — central registry of ``MXNET_*`` environment
  variables with typed defaults (ref: docs/faq/env_var.md lists ~72 vars;
  the reference reads them ad-hoc via dmlc::GetEnv).
- parameter coercion helpers used by the op registry to accept both python
  values and the string forms found in serialized symbol JSON
  (ref: dmlc::Parameter string kwargs -> struct parsing).
"""
from __future__ import annotations

import ast
import logging
import os
import sys
import threading
from typing import Any, Callable, Dict, Optional, Tuple

__all__ = [
    "MXNetError",
    "check",
    "env",
    "EnvRegistry",
    "numeric_types",
    "string_types",
    "classproperty",
]

numeric_types = (float, int)
string_types = (str,)

logger = logging.getLogger("mxnet_tpu")


class MXNetError(RuntimeError):
    """Framework-level error (ref: python/mxnet/base.py MXNetError)."""


def check(cond: bool, msg: str = "check failed") -> None:
    """CHECK() analog: raise :class:`MXNetError` when ``cond`` is false."""
    if not cond:
        raise MXNetError(msg)


class EnvRegistry:
    """Typed registry of MXNET_* environment variables.

    The reference scatters ``dmlc::GetEnv("MXNET_FOO", default)`` reads across
    the codebase; here every knob is declared once so ``mx.runtime`` can
    enumerate them (ref: docs/faq/env_var.md).
    """

    def __init__(self) -> None:
        self._defaults: Dict[str, Tuple[type, Any, str]] = {}

    def declare(self, name: str, typ: type, default: Any, doc: str = "") -> None:
        self._defaults[name] = (typ, default, doc)

    def get(self, name: str, default: Any = None) -> Any:
        if name in self._defaults:
            typ, decl_default, _ = self._defaults[name]
            raw = os.environ.get(name)
            if raw is None:
                return decl_default if default is None else default
            if typ is bool:
                return raw not in ("0", "false", "False", "")
            return typ(raw)
        raw = os.environ.get(name)
        return raw if raw is not None else default

    def raw(self, name: str) -> Optional[str]:
        """Uncoerced read: the raw environment string, or None when unset.

        For save/restore plumbing (the autotuner snapshots knobs it is
        about to vary), error messages that must echo the un-parseable
        original, and third-party variables (``JAX_PLATFORMS``) that are
        not ours to declare or coerce. This — not ``os.environ`` — is the
        sanctioned escape hatch: graftcheck's env-discipline rule flags
        every direct environ read outside this module.
        """
        return os.environ.get(name)

    def default_for(self, name: str) -> Any:
        """The DECLARED default of a registered variable (None when the
        name is undeclared) — lets consumers tell 'set to the default'
        from 'overridden' (the run-report fingerprint)."""
        decl = self._defaults.get(name)
        return decl[1] if decl is not None else None

    def items(self):
        for name, (typ, default, doc) in sorted(self._defaults.items()):
            yield name, typ, self.get(name), doc


env = EnvRegistry()

# Engine/executor knobs kept for API parity; on TPU most map to XLA behavior.
env.declare("MXNET_ENGINE_TYPE", str, "ThreadedEnginePerDevice",
            "Engine flavor: ThreadedEnginePerDevice|ThreadedEngine|NaiveEngine. "
            "NaiveEngine synchronizes after every op (debug).")
env.declare("MXNET_EXEC_BULK_EXEC_INFERENCE", bool, True,
            "Fuse inference graphs into single XLA programs.")
env.declare("MXNET_EXEC_BULK_EXEC_TRAIN", bool, True,
            "Fuse training graphs into single XLA programs.")
env.declare("MXNET_BACKWARD_DO_MIRROR", bool, False,
            "Trade compute for memory in backward (jax.checkpoint remat). "
            "Off, a recorded hybridized forward keeps convolution and "
            "matmul outputs and recomputes the elementwise chain.")
env.declare("MXNET_BACKWARD_MIRROR_POLICY", str, "full",
            "Remat policy when mirroring: full (save nothing) | dots "
            "(save matmul results, recompute elementwise ops) | convs "
            "(save convolution and matmul results).")
env.declare("MXNET_UPDATE_ON_KVSTORE", bool, True,
            "Run optimizer update inside the kvstore when supported.")
env.declare("MXNET_KVSTORE_BIGARRAY_BOUND", int, 1000000,
            "Threshold above which arrays are sharded across servers/devices.")
env.declare("MXNET_ENFORCE_DETERMINISM", bool, False,
            "Restrict to deterministic algorithms.")
env.declare("MXNET_PROFILER_AUTOSTART", bool, False,
            "Start the profiler at import time.")
env.declare("MXNET_CPU_WORKER_NTHREADS", int, 1,
            "Host-side worker threads (IO pipeline).")
env.declare("MXNET_DEFAULT_DTYPE", str, "float32",
            "Default dtype for created arrays.")
env.declare("MXNET_TPU_MATMUL_PRECISION", str, "default",
            "jax matmul precision: default|high|highest.")
env.declare("MXNET_SAFE_ACCUMULATION", bool, False,
            "Accumulate f16/bf16 reductions (sum/mean/prod/norm) in f32.")
env.declare("MXNET_IS_RECOVERY", bool, False,
            "Set by the relauncher on restarted nodes; read by "
            "mx.fault.is_recovery().")
env.declare("MXTPU_CHAOS", str, "",
            "Deterministic fault-injection plan for resilience testing, "
            "e.g. 'nan_grad@12,kill@40,ckpt_corrupt@latest,kv_flake:0.2' "
            "(contrib/chaos.py grammar; hooks in trainer/kvstore/fault).")
env.declare("MXTPU_CHAOS_SEED", int, 0,
            "Seed for the chaos plan's RNG (kv_flake rolls) so injected "
            "failure sequences replay identically.")
env.declare("MXNET_KV_RETRY_MAX", int, 3,
            "Bounded retries (exponential backoff) around kvstore "
            "push/pull on TransientKVError before giving up.")
env.declare("MXNET_KV_RETRY_BASE_MS", float, 50.0,
            "Backoff base for kvstore push/pull retries: attempt n sleeps "
            "base * 2**(n-1) milliseconds.")
env.declare("MXTPU_RESUMABLE_EXIT_CODE", int, 75,
            "Exit code FitLoop uses after a SIGTERM/SIGINT-triggered final "
            "checkpoint (default 75 = EX_TEMPFAIL), so the relauncher can "
            "tell 'resume me' from a real failure.")
env.declare("MXTPU_SUPERVISE_MAX_RESTARTS", int, 8,
            "Fleet supervisor (parallel/supervisor.py, launch.py "
            "--supervise) restart budget: failure-driven relaunches "
            "(shrink/resume after a crash, hang, or resumable exit) "
            "beyond this fail the job loudly with a forensic bundle. "
            "Capacity-driven grow relaunches do not count.")
env.declare("MXTPU_SUPERVISE_CRASH_WINDOW_S", float, 300.0,
            "Fleet supervisor crash-loop window: crashes of the SAME "
            "rank slot within this many seconds count toward "
            "MXTPU_SUPERVISE_CRASH_LIMIT.")
env.declare("MXTPU_SUPERVISE_CRASH_LIMIT", int, 3,
            "Fleet supervisor crash-loop threshold: this many "
            "crash/signal deaths of the same rank slot within the "
            "window exclude the slot (the fleet continues smaller) "
            "instead of another same-size relaunch.")
env.declare("MXTPU_COORD_TIMEOUT_MS", int, 120000,
            "Bound on each blocking coordination-service KV get/barrier "
            "hop (parallel/collectives.py CPU-backend transport). A rank "
            "whose peer died blocks at most this long before the hop "
            "raises — the self-healing fleet wants survivors to fail "
            "fast, not hang for the scheduler's whole grace period.")
env.declare("MXNET_STORAGE_FALLBACK_LOG_VERBOSE", bool, True,
            "Warn when an op without a sparse kernel densifies its inputs "
            "(storage fallback).")
env.declare("MXNET_RESID_DTYPE", str, "",
            "Store backward activation residuals 8-bit (fp8|e4m3|e5m2). "
            "Conv dx stays exact (needs only weights); conv dW, BN "
            "grads/dx (via fp8 xhat) and ReLU masks see small zero-mean "
            "rounding (ops/resid8.py).")
env.declare("MXNET_CONV_COMPUTE", str, "",
            "Set to 'int8' to run training convolutions int8 on the MXU "
            "(static activation range + per-channel weight scales; "
            "~1.5x the bf16 conv rate and half the conv-input HBM reads; "
            "ops/resid8.py conv_int8_train).")
env.declare("MXNET_CONV_INT8_RANGE", float, 8.0,
            "Symmetric activation clip range for MXNET_CONV_COMPUTE=int8 "
            "(post-BN/ReLU activations are O(1); widen if a model clips).")
env.declare("MXTPU_FUSED_EPILOGUE", bool, False,
            "Set 1 to route the fused conv-epilogue ops "
            "(_contrib_fused_bn_relu / _contrib_fused_bn_add_relu) through "
            "the Pallas BN(+add)+ReLU kernels (compiled on TPU, interpret "
            "mode elsewhere) instead of the composed XLA lowering. Off by "
            "default: a Mosaic kernel cannot be partitioned over a mesh "
            "(SPMDTrainer(mesh=...) is refused by the compiler) and the "
            "b256 ResNet-50 step needs 15.0 GB of temporaries against "
            "8.5 GB composed on a 16 GB chip. Read at trace time — part "
            "of every op jit-cache key.")
env.declare("MXTPU_CACHEDOP_CACHE_SIZE", int, 256,
            "LRU bound on CachedOp's per-signature compiled-program cache "
            "(each entry is a full XLA executable). 0 = unbounded. "
            "CachedOp.cache_info() reports hits/misses/evictions.")
env.declare("MXTPU_SERVE_MAX_BATCH", int, 32,
            "serving.ModelServer: maximum coalesced batch size per "
            "dispatch; also the largest batch-padding bucket.")
env.declare("MXTPU_SERVE_MAX_LATENCY_MS", float, 5.0,
            "serving.ModelServer: maximum time a request may wait in its "
            "shape bucket before the batch is flushed partially full.")
env.declare("MXTPU_SERVE_QUEUE_DEPTH", int, 256,
            "serving.ModelServer: bounded admission-queue depth; a full "
            "queue sheds load with a typed QueueFull rejection "
            "(backpressure) instead of buffering without bound.")
env.declare("MXTPU_SERVE_REGISTRY", str, "",
            "Root directory of the versioned model registry "
            "(serving.ModelRegistry): registry/<model>/<version>/ holding "
            "exported artifacts + SHA-256 manifests + an atomic CURRENT "
            "pointer. Empty = <cwd>/registry.")
env.declare("MXTPU_COMPILE_CACHE", str, "",
            "Persistent on-disk XLA compilation cache directory. "
            "serving.enable_compile_cache honors it on every backend so a "
            "replica restart recompiles nothing; util.enable_compile_cache "
            "(scripts) defaults to <checkout>/.jax_cache and skips CPU "
            "unless a directory is named. Where JAX_COMPILATION_CACHE_DIR "
            "is set, JAX reads that and no directory is set in code. "
            "'0'/'off' disables.")
env.declare("MXTPU_SERVE_REPLAY", str, "",
            "Signature-replay file: when set, ModelServer appends one "
            "JSON line per DISTINCT dispatched (item shape, dtype, "
            "padded batch) signature; new replicas prewarm from it "
            "(serving.warm_from_replay / FleetServer deploy). Empty = "
            "recording off.")
env.declare("MXTPU_FLEET_MIN", int, 1,
            "serving fleet (serving/autoscale.py): lower bound on live "
            "replica processes; the autoscaler never drains below it. "
            ">= 1.")
env.declare("MXTPU_FLEET_MAX", int, 4,
            "serving fleet: upper bound on replica processes; sustained "
            "queue pressure scales up to (never past) it. >= "
            "MXTPU_FLEET_MIN.")
env.declare("MXTPU_FLEET_TARGET_QUEUE", int, 16,
            "serving fleet: per-replica queue-depth target; mean depth "
            "above it for consecutive autoscaler ticks is scale-up "
            "pressure (serving.autoscale.decide).")
env.declare("MXTPU_FLEET_HEARTBEAT_MS", float, 200.0,
            "serving fleet router: interval between metrics-heartbeat "
            "polls of each replica (queue depth / p95 / active version "
            "drive least-loaded routing and the version floor).")
env.declare("MXNET_HOME", str, "",
            "Root directory for datasets and model artifacts "
            "(default ~/.mxnet; ref: docs/faq/env_var.md MXNET_HOME).")
env.declare("MXTPU_OPTIMIZER_AGGREGATION", int, sys.maxsize,
            "Multi-tensor optimizer aggregation: dense parameters are "
            "grouped by bucket key (weight dtype, device placement, "
            "multi-precision, state arity) and each key is stepped by "
            "ONE jitted program with donated weight/state buffers, so "
            "the count of update programs does not grow with the count "
            "of parameters. Unset = no cap (the declared default is "
            "sys.maxsize, a cap no model reaches). A positive value caps "
            "a program at that many params (ref: the reference's "
            "MXNET_OPTIMIZER_AGGREGATION_SIZE, whose default of 4 is a "
            "CUDA kernel's argument-list limit and does not carry over "
            "to a jitted XLA program). 0 disables (per-parameter "
            "updates).")
env.declare("MXTPU_GRAD_BUCKET_MB", float, 25.0,
            "Gradient-allreduce bucketing: Trainer.allreduce_grads "
            "concatenates same-dtype dense gradients into flat buffers "
            "capped at this many MB and issues one kvstore push/pull "
            "(one collective) per bucket instead of one per key "
            "(ref: DDP gradient bucketing). 0 disables (per-key "
            "push/pull).")
env.declare("MXTPU_COMM_OVERLAP", str, "off",
            "Overlap gradient communication with backward: 'on' launches "
            "each gradient bucket's kvstore push/pull the moment its "
            "constituent grads receive their final contribution during "
            "the reverse pass (reverse-creation-order bucket scheduling, "
            "ref: the reference engine ordering kvstore pushes on write "
            "dependencies), instead of one barrier after backward. "
            "Numerically identical to 'off' (same bucket sums, earlier "
            "launch). Driven per step by fit.FitLoop; overlapped time is "
            "charged to the step-breakdown segment 'comm_overlapped'. "
            "Unknown values raise.")
env.declare("MXTPU_AUTOTUNE", str, "off",
            "Telemetry-driven knob autotuner (telemetry/autotune.py): "
            "'on' makes FitLoop spend a few instrumented probe steps per "
            "candidate varying MXTPU_GRAD_BUCKET_MB, "
            "MXTPU_OPTIMIZER_AGGREGATION, DeviceStagingIter prefetch "
            "depth and MXTPU_COMM_OVERLAP, score each candidate with the "
            "step-breakdown exclusive-time data, lock the best config and "
            "record the decision (trace category 'autotune', metrics "
            "registry, FitResult.tuning_report). Grammar: "
            "'on[,probe=N][,warmup=N][,knobs=a|b][,bucket_mb=v|v]"
            "[,agg=v|v][,prefetch=v|v][,overlap=0|1]'; typos raise. "
            "'off' (default) reproduces untuned behavior exactly.")
env.declare("MXTPU_PROFILE", str, "",
            "Telemetry tracer spec, applied at import: comma-separated "
            "tokens 'on'|'off'|'ring=N'|'cat=a|b'|'file=PATH' (see "
            "telemetry.tracer). Empty = tracing off (near-zero overhead: "
            "one flag check per span site).")
env.declare("MXTPU_MEM_BUDGET", int, 0,
            "Device-memory budget in bytes for the live-byte ledger "
            "(telemetry/memory.py). When > 0, fit.FitLoop checks the "
            "per-step ledger watermark against it and writes a ranked "
            "memory-forensics dump (categories, top owners, per-program "
            "temp bytes, recent trace window) on the first step that "
            "exceeds it. 0 (default) disables the budget check; the "
            "RESOURCE_EXHAUSTED and mem_pressure chaos triggers stay "
            "active regardless.")
env.declare("MXTPU_MEM_DUMP_DIR", str, "",
            "Directory memory-forensics dumps are written to "
            "(mem_forensics_<pid>_<n>.json). Empty (default) = the "
            "current working directory.")
env.declare("MXTPU_ZERO", str, "off",
            "ZeRO-1 sharded optimizer state (parallel/zero.py): 'on' "
            "replaces the bucketed gradient allreduce with a per-bucket "
            "reduce-scatter (same _gbkt flat layout), steps only this "
            "rank's parameter shard through the grouped donated-buffer "
            "update (optimizer state + f32 multi_precision masters "
            "materialize ~1/N per rank), and allgathers the updated "
            "weights back per bucket. The fused finiteness sentinel is "
            "AND-reduced across ranks before any shard applies; "
            "checkpoints gather-on-save into the ordinary unsharded "
            "format (topology-portable). Requires a kvstore and the "
            "grouped update path (dense params, grouped-capable "
            "optimizer, MXTPU_OPTIMIZER_AGGREGATION > 0) — anything else "
            "raises rather than silently training unsharded. Unknown "
            "values raise.")
env.declare("MXTPU_ZERO_WORLD", int, 0,
            "Simulated ZeRO-1 world size for single-worker runs: this "
            "process plays all N ranks in sequence (same partition, "
            "shard-aware ledger attribution, collective call pattern and "
            "trajectory as a real N-rank group), so the parity/memory/"
            "chaos suites run the N-rank protocol on one CPU process. "
            "0/1 = no simulation; ignored when kvstore.num_workers > 1.")
env.declare("MXTPU_ELASTIC", str, "off",
            "Elastic world-size training (parallel/elastic.py): 'on' "
            "lets fit.FitLoop resume a checkpoint whose recorded "
            "topology names a DIFFERENT world size — the collective "
            "group is re-formed through the coordination-service KV "
            "store, the ZeRO-1 partition map is re-derived at the new "
            "world (zero.partition is a pure function of order/shapes/"
            "world), the seeded data-iterator position is re-split "
            "across the new rank count from the checkpoint's global "
            "sample position (no duplicated, no dropped sample), and "
            "the per-fit comm-health/clock-sync state is reset so skew "
            "tables never blend topologies. 'off' (default) makes a "
            "cross-world resume raise elastic.TopologyMismatchError "
            "instead of silently resuming mis-split; checkpoints whose "
            "trainer states are NOT in the gather-on-save portable "
            "format always raise across a world change. Unknown values "
            "raise. Chaos 'resize@N[:M]' drives the kill half.")
env.declare("MXTPU_COORDINATOR", str, "",
            "host:port of the jax.distributed coordinator; set per worker "
            "by tools/launch.py. Empty = single-process run "
            "(kvstore_server.init_distributed is a no-op).")
env.declare("MXTPU_NUM_WORKERS", int, 1,
            "Process count of the distributed group (tools/launch.py).")
env.declare("MXTPU_WORKER_ID", int, 0,
            "This process's rank in the distributed group "
            "(tools/launch.py); also stamps telemetry trace events.")
env.declare("MXTPU_WORKER_HOSTS", str, "",
            "Comma-separated worker hostnames in rank order "
            "(tools/launch.py placement); resolves each rank's "
            "command-channel endpoint. Empty = loopback.")
env.declare("MXTPU_CMD_PORT_BASE", int, 0,
            "Base TCP port of the per-worker command channel (port = "
            "base + rank). 0 = derive from the coordinator port + 100.")
env.declare("MXTPU_CMD_TOKEN", str, "",
            "Shared job token every worker command must carry "
            "(tools/launch.py generates one per job). Empty = command "
            "endpoints bind loopback only.")
env.declare("MXTPU_LIBRARY_PATH", str, "",
            "Explicit path to the native engine shared library "
            "(libinfo.find_lib_path); empty = search the package dirs.")
env.declare("MXNET_ENGINE_BULK_SIZE", int, 15,
            "Engine bulk-execution window size (ref: the reference's "
            "MXNET_ENGINE_BULK_SIZE); read/written through the C API "
            "bridge's MXEngineSetBulkSize.")
env.declare("DMLC_ROLE", str, "worker",
            "Launcher-assigned process role (worker|server|scheduler), "
            "reference ps-lite parity; read by the C API role queries.")
env.declare("DMLC_RANK", int, 0,
            "Launcher-assigned rank (reference ps-lite parity); used to "
            "tag per-rank checkpoint state in mx.fault.")
env.declare("MXTPU_COLL_TIMEOUT_S", float, 0.0,
            "Hung-collective watchdog (telemetry/collective.py): when "
            "> 0, a watchdog thread is armed at every collective entry "
            "(kvstore push/pull, ZeRO reduce-scatter/allgather/"
            "all-finite, coordination-service exchange/barrier); a "
            "collective still in flight past this many seconds dumps a "
            "flight record — the collective ledger ring, the hung "
            "(kind, key, seq), the peer rank the transport is blocked "
            "on, and all-thread stacks — to MXTPU_MEM_DUMP_DIR "
            "(tmp+rename). 0 (default) disarms; arming also turns the "
            "collective ledger on. Unparseable values raise.")
env.declare("MXTPU_COLL_RING", int, 4096,
            "Collective-ledger ring capacity (telemetry/collective.py): "
            "bounded per-process ring of (seq, kind, key, bytes, rank, "
            "t_enter, t_exit) records, one per collective; evictions "
            "are counted, never silent. Must be >= 1.")
env.declare("MXTPU_COLL_HEALTH", int, 0,
            "Cross-rank comm-health cadence (telemetry/collective.py): "
            "when N > 0, fit.FitLoop exchanges each rank's recent "
            "collective-ledger digest over the coordination-service "
            "byte channel every N steps, diagnoses desynced collective "
            "order (mxtpu_coll_desync_total), attributes per-rank "
            "entry-time skew (mxtpu_coll_skew_ms / "
            "mxtpu_coll_straggler_rank, FitResult.comm_health, the "
            "step-breakdown straggler-bound diagnosis), and the "
            "collective ledger records every collective. Distributed "
            "runs: the exchange is itself a collective — every rank "
            "must run the same cadence. 0 (default) = off; unparseable "
            "values raise.")
env.declare("MXTPU_NUMERICS", str, "",
            "In-graph numerics observability plane (telemetry/"
            "numerics.py): 'on[,every=N][,stats=l2|absmax|mean|nonfinite|"
            "update_ratio][,pattern=RE]' makes every Nth (default every "
            "1) grouped optimizer update emit per-parameter tensor "
            "statistics — grad/weight L2, abs-max, mean, non-finite "
            "counts, update/weight ratio — as extra outputs of the SAME "
            "compiled bucket programs (zero extra dispatches; the stats "
            "ride fit.FitLoop's existing flag+loss transfer). A sentinel-"
            "skipped step additionally runs a non-finite provenance pass "
            "naming the first offending parameter in an ERROR log and a "
            "numerics_<pid>_<n>.json forensics dump (MXTPU_MEM_DUMP_DIR). "
            "Surfaces: FitResult.numerics, mxtpu_numerics_* gauges, "
            "Perfetto 'C' counters (category 'numerics'), "
            "tools/trace_report.py columns, Monitor.install_numerics. "
            "Numerically inert (bitwise on-vs-off parity); 'pattern' "
            "filters which parameters get per-param records (no commas "
            "in the regex). Empty/off (default) = one cached flag check "
            "per step; unknown tokens raise.")
env.declare("MXTPU_EFFICIENCY", str, "",
            "Efficiency/goodput plane (telemetry/efficiency.py): 'on' "
            "makes fit.FitLoop sum the XLA cost-model FLOPs/bytes of "
            "the compiled programs dispatched each step (warm CachedOp "
            "forward + backward, grouped optimizer buckets, the fused "
            "finiteness reduction; costs re-lowered once per signature "
            "under the trace write-lock, cached) and divide by the "
            "measured step wall and the MXTPU_DEVICE_PEAK table into "
            "live MFU, achieved FLOP/s / bytes/s, roofline position "
            "and samples/s (+ tokens/s via FitLoop's tokens_per_sample "
            "knob). Surfaces: FitResult.efficiency, mxtpu_mfu / "
            "mxtpu_goodput_samples gauges, Perfetto counters (category "
            "'efficiency'), the trace_report mfu column. Numerically "
            "inert (bitwise on-vs-off parity); off (default) costs one "
            "cached env check per hook. Unknown tokens raise.")
env.declare("MXTPU_DEVICE_PEAK", str, "",
            "Device peak table for the efficiency plane: "
            "'flops=<FLOP/s>,bw=<bytes/s>' (e.g. flops=197e12,bw=819e9). "
            "Strict parse — typos/partial tables raise at fit() start. "
            "Empty = the published peaks of the device's device_kind "
            "(telemetry/efficiency.py DEVICE_PEAKS); a kind that is not "
            "in that table, the CPU included, gets no MFU.")
env.declare("MXTPU_RUN_REPORT_DIR", str, "",
            "Directory fit.FitLoop writes one persistent run report "
            "into at fit end (run_<pid>_<ts>.json, tmp+rename, shared "
            "SHA-256 manifest via fault.write_manifest): config/env "
            "fingerprint, step-time distribution, loss-trajectory "
            "digest and every measurement-plane axis summary. "
            "tools/run_compare.py diffs two reports into per-metric "
            "regression verdicts (CI exit codes). Empty (default) = "
            "no report.")
env.declare("MXTPU_PROFILE_BOUND_FRAC", float, 0.4,
            "Step-breakdown detector threshold: any non-compute segment "
            "(data_wait/h2d/comm/optimizer/checkpoint) whose share of "
            "wall-clock step time reaches this fraction logs a one-line "
            "input-bound/comm-bound diagnosis. <=0 disables the "
            "detector.")
env.declare("MXTPU_SPARSE_PLANE", str, "off",
            "Sparse embedding plane (parallel/embedding_plane.py): '1'/"
            "'on' opts a row-sparse embedding table into the sharded "
            "sparse subsystem — the table is partitioned row-wise "
            "across the (simulated or real) world, row-sparse "
            "gradients travel dedup'd + mask-packed into fixed-shape "
            "(max_rows, dim) gather/scatter update programs (no warm-"
            "step retrace on varying touched-row counts), and per-row "
            "optimizer state lives only on the rank owning the row "
            "(1/world state bytes, ledger-exact). Off (default): sparse "
            "parameters raise out of the grouped update path with a "
            "message naming this flag. Unknown values raise.")
env.declare("MXTPU_SPARSE_MAX_ROWS", int, 4096,
            "Sparse-plane bucket ceiling: touched-row counts are padded "
            "up to the next power of two, capped at this many rows per "
            "fixed-shape update program. A minibatch touching more "
            "unique rows than the cap raises (the cap IS the retrace "
            "contract — raising it recompiles). Must be >= 1; "
            "unparseable values raise.")
env.declare("MXTPU_BENCH_RECSYS", str, "1",
            "bench.py: run the recsys probe child (two-tower training "
            "over a sharded embedding table at simulated world 4 + "
            "registry-served lookup QPS) and fold the 'recsys' row into "
            "the headline artifact. '0' skips the child.")


def data_dir() -> str:
    """Dataset/model root: $MXNET_HOME or ~/.mxnet
    (ref: python/mxnet/base.py data_dir)."""
    return env.get("MXNET_HOME") or os.path.join(
        os.path.expanduser("~"), ".mxnet")


class classproperty:  # noqa: N801 - decorator style
    def __init__(self, fget: Callable) -> None:
        self.fget = fget

    def __get__(self, obj, owner):
        return self.fget(owner)


# ---------------------------------------------------------------------------
# Parameter coercion: accept python values or their string serialization, the
# way dmlc::Parameter parses kwargs shipped through symbol JSON / C API.
# ---------------------------------------------------------------------------

_BOOL_STRINGS = {"true": True, "True": True, "1": True,
                 "false": False, "False": False, "0": False}


def coerce_param(value: Any) -> Any:
    """Best-effort conversion of string-serialized op params to python values.

    Symbol JSON stores every attr as a string (``"(2, 2)"``, ``"True"``,
    ``"float32"``); imperative python passes real values. Both funnel through
    here so op impls always see typed values (ref: dmlc parameter parsing +
    legacy JSON loader src/nnvm/legacy_json_util.cc:222).
    """
    if not isinstance(value, str):
        if isinstance(value, list):
            return tuple(coerce_param(v) for v in value)
        return value
    s = value.strip()
    if s in _BOOL_STRINGS:
        return _BOOL_STRINGS[s]
    if s in ("None", "none", "null"):
        return None
    try:
        v = ast.literal_eval(s)
        if isinstance(v, list):
            v = tuple(v)
        return v
    except (ValueError, SyntaxError):
        return s


def hashable_params(params: Dict[str, Any]) -> Tuple[Tuple[str, Any], ...]:
    """Normalize an op's kwargs into a hashable, jit-cache-friendly key."""
    out = []
    for k in sorted(params):
        v = coerce_param(params[k])
        if isinstance(v, list):
            v = tuple(v)
        elif isinstance(v, dict):
            v = tuple(sorted(v.items()))
        out.append((k, v))
    return tuple(out)


class _TLocal(threading.local):
    pass


tlocal = _TLocal()


def getenv_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default
