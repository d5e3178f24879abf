"""Unified telemetry subsystem: tracer, metrics registry, step breakdown.

The reference dedicates a whole subsystem to observability — ``src/profiler/``
with aggregate stats, chrome trace-event dumps and a process-profiler C API
(``MXSetProcessProfilerConfig`` / ``MXDumpProfile``), plus remote profiler
commands shipped over the kvstore command channel
(``KVStoreServerProfilerCommand``, include/mxnet/kvstore.h:49). This package
is the TPU-native generalization; the whole stack reports into it:

- :mod:`.tracer` — thread-safe structured span tracer with a bounded ring
  buffer, category filtering and the ``MXTPU_PROFILE`` env grammar. Near-zero
  overhead when off (one flag check per span). Spans carry their parent and
  step number; while a JAX profiler session runs the tracer is on and the
  spans are in the device trace too, as ``mx.*`` annotations.
- :mod:`.chrome_trace` — strict Chrome trace-event JSON exporter (loadable in
  Perfetto / chrome://tracing) plus the validator the test-suite enforces it
  with.
- :mod:`.registry` — shared metrics registry (counters / gauges /
  histograms). ``serving/metrics.py`` is built on these types; CachedOp cache
  traffic, kvstore retries, chaos injections, Trainer dispatch counts, XLA
  compile events and device-memory watermarks all land in the default
  registry.
- :mod:`.step_breakdown` — per-step time accounting (data_wait / h2d /
  compute / optimizer / comm / checkpoint) with the input-bound / comm-bound
  detector. ``fit.FitLoop`` drives it; ``bench.py`` ships the segment shares
  as the ``step_breakdown`` headline row.
- :mod:`.memory` — the memory axis: a live-byte ledger attributing device
  bytes by owner (params / grads / optimizer / masters / staging /
  buckets / serving caches; exact by construction on CPU), static
  per-program ``memory_analysis`` attribution, per-step watermarks in the
  step breakdown + a Perfetto counter track, and ranked OOM-forensics
  dumps (``RESOURCE_EXHAUSTED`` / ``MXTPU_MEM_BUDGET`` / ``mem_pressure``
  chaos).

- :mod:`.collective` — the cross-rank comm axis: a bounded collective
  ledger at every kvstore/ZeRO/byte-channel entry point, the
  desync/straggler health exchange (``MXTPU_COLL_HEALTH``,
  ``mxtpu_coll_skew_ms``/``mxtpu_coll_straggler_rank``), and the
  hung-collective flight recorder (``MXTPU_COLL_TIMEOUT_S``) that names
  the hung ``(kind, key, seq)`` and the absent rank on every surviving
  rank. ``tools/fleet_trace.py`` merges per-rank chrome traces onto one
  clock via the tracer's wall-clock anchor + offset handshake.

- :mod:`.numerics` — the numbers axis: in-graph per-parameter tensor
  statistics emitted by the grouped-update bucket programs themselves
  (``MXTPU_NUMERICS``; zero extra dispatches, stats ride the step's
  existing flag+loss transfer), non-finite provenance naming the exact
  parameter a sentinel-skipped step blew up in (ERROR log +
  ``numerics_<pid>_<n>.json`` forensics), and the dynamic loss-scale
  timeline (``FitResult.numerics["loss_scale_events"]``,
  ``mxtpu_loss_scale``). The legacy ``mxnet_tpu.monitor.Monitor`` is a
  facade over it (``Monitor.install_numerics``).

- :mod:`.efficiency` — the efficiency axis: the ONE shared
  ``cost_analysis``/``memory_analysis`` extraction helper behind
  ``spmd.program_stats`` / ``CachedOp.memory_analysis`` /
  ``grouped.program_memory``, a per-program FLOP/byte cost registry
  (recorded alongside the program-memory registry,
  ``mxtpu_program_{flops,bytes_accessed}``), and the live MFU/goodput
  rollup (``MXTPU_EFFICIENCY``, ``MXTPU_DEVICE_PEAK`` peak table):
  ``FitResult.efficiency``, ``mxtpu_mfu``/``mxtpu_goodput_samples``,
  Perfetto counters (category ``efficiency``), the ``mfu`` column of
  ``tools/trace_report.py``.

- :mod:`.run_report` — the persistent per-run verdict: a versioned
  ``run_<pid>_<ts>.json`` artifact written at fit end
  (``MXTPU_RUN_REPORT_DIR``, tmp+rename + shared ``fault.write_manifest``)
  capturing the config fingerprint, step-time distribution and every
  axis's summary; ``tools/run_compare.py`` diffs two of them into
  per-metric regression verdicts with CI exit codes.

``mxnet_tpu.profiler`` remains the MXNet-compatible facade over this
package, and the kvstore remote profiler command channel
(``KVStore.send_profiler_command``) is served by it, so the controller can
collect per-rank chrome traces without a shared filesystem.
"""
from __future__ import annotations

from .tracer import (Tracer, tracer, span, instant, counter_event, enabled,
                     configure, enable, disable, end_step)
from .chrome_trace import (chrome_trace_events, dump_chrome_trace,
                           validate_chrome_trace)
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       default_registry)
from .step_breakdown import (StepBreakdown, segment, current_breakdown,
                             SEGMENTS)
from . import memory
from .memory import (MemoryLedger, ledger as memory_ledger, dump_forensics)
from . import collective
from .collective import (CollectiveLedger,
                         ledger as collective_ledger)
from . import numerics
from .numerics import NumericsPlane, plane as numerics_plane
from . import efficiency
from .efficiency import (EfficiencyRollup, compiled_program_stats,
                         rollup as efficiency_rollup)
from . import run_report
from .run_report import write_run_report, load_run_report

__all__ = [
    "Tracer", "tracer", "span", "instant", "counter_event", "enabled",
    "configure", "enable", "disable", "end_step",
    "chrome_trace_events", "dump_chrome_trace", "validate_chrome_trace",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "default_registry",
    "StepBreakdown", "segment", "current_breakdown", "SEGMENTS",
    "memory", "MemoryLedger", "memory_ledger", "dump_forensics",
    "collective", "CollectiveLedger", "collective_ledger",
    "numerics", "NumericsPlane", "numerics_plane",
    "efficiency", "EfficiencyRollup", "compiled_program_stats",
    "efficiency_rollup",
    "run_report", "write_run_report", "load_run_report",
]
