"""Efficiency & goodput: per-program FLOP/byte costs, live MFU/roofline.

The measurement plane answers WHERE time goes (tracer / step breakdown),
where BYTES live (memory ledger), whether the FLEET agrees (collective
ledger) and whether the NUMBERS are sane (numerics plane). This module is
the axis the north star is graded on — *is this run as fast as the
hardware allows*: it divides what the hardware DID (XLA cost-model FLOPs
and bytes of the programs actually dispatched each step) by what the
hardware COULD do (a device peak table) and publishes the quotient live.

Three layers:

**Shared program analysis** (:func:`compiled_program_stats`): the ONE
extraction of a jax ``Compiled`` object's ``cost_analysis()`` (flops,
bytes accessed) and ``memory_analysis()`` (argument/output/temp/alias
bytes). ``spmd.program_stats``, ``memory.compiled_memory_stats`` (and
through it ``CachedOp.memory_analysis`` / ``grouped.program_memory``)
all route here — one parser for the backend's two analysis surfaces
instead of three hand-rolled copies. Per-program costs are recorded
alongside the program-memory registry (``memory.record_program``) so
forensics dumps and the ``mxtpu_program_{flops,bytes_accessed}`` gauges
rank programs by compute as well as by workspace.

**Live MFU/goodput rollup** (``MXTPU_EFFICIENCY=on``): dispatch sites
that launch attributable compiled programs — warm :class:`CachedOp`
forward replays, their vjp backward programs, the grouped-optimizer
bucket programs and the fused finiteness reduction — drop a
:func:`note_dispatch` per launch (a list append; with the plane off the
whole hook is one cached env check, the tracer discipline).
``fit.FitLoop`` brackets each step with :func:`begin_step` /
:func:`end_step` the way ``StepBreakdown`` opens its ledger window; at
step end every noted program's cost is resolved — re-lowered ON DEMAND
under the owning trace write-lock exactly like ``memory_analysis``,
cached per signature, so the hot path never lowers — and the step's FLOP
and byte sums divide by the measured wall and the device peak table
(:func:`device_peak`, ``MXTPU_DEVICE_PEAK=flops=F,bw=B``) into MFU,
achieved FLOP/s and bytes/s, the roofline position (compute- vs
bandwidth-bound) and samples/s goodput (non-finite skipped steps produce
no useful samples). Surfaces: ``FitResult.efficiency``, ``mxtpu_mfu`` /
``mxtpu_goodput_samples`` gauges, Perfetto ``"C"`` counters (category
``efficiency``) and the ``mfu`` column of ``tools/trace_report.py``.

Coverage contract: only whole-graph programs are attributed. An
un-hybridized net's per-op dispatches (and the tiny numerics fallback
programs) are invisible to the plane — they are never noted, so they
appear in no counter (``unattributed_dispatches`` counts only NOTED
launches whose cost failed to resolve) and MFU is a silent LOWER bound
there — hybridize the net for full attribution. The plane is
numerically inert:
notes are host-side bookkeeping and resolution is a re-lower (a trace,
never an execute) — bitwise on-vs-off trajectory parity is test-pinned,
as are warm-step dispatch/launch counts.

**Honest peaks**: the peak table comes from ``MXTPU_DEVICE_PEAK``
(strict parse — a typo'd peak raises before step 0, never silently
grades against garbage). Without it, the published peaks of the device's
``device_kind`` apply (:data:`DEVICE_PEAKS`, each entry with its
source); a kind that is not in the table — the CPU included — gets no
MFU at all, only achieved FLOP/s and bytes/s.
"""
from __future__ import annotations

import math
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..base import MXNetError, env

__all__ = ["compiled_program_stats", "COST_FIELDS", "MEMORY_FIELDS",
           "spec", "enabled", "device_peak", "note_dispatch",
           "begin_step", "end_step", "reset_run", "summary", "rollup",
           "cost_report"]

#: fields :func:`compiled_program_stats` extracts from ``cost_analysis``
COST_FIELDS = ("flops", "bytes_accessed")
#: fields it extracts from ``memory_analysis`` (the historical
#: ``memory.compiled_memory_stats`` layout, byte-identical)
MEMORY_FIELDS = ("argument_bytes", "output_bytes", "temp_bytes",
                 "alias_bytes", "generated_code_bytes")

#: per-step efficiency records retained (the FitResult window)
RECENT = 64


# ---------------------------------------------------------------------------
# Shared program analysis — the one cost/memory extraction site
# ---------------------------------------------------------------------------

def compiled_program_stats(compiled) -> Optional[Dict[str, Any]]:
    """Extract XLA's ``cost_analysis()`` + ``memory_analysis()`` from a
    jax ``Compiled`` object into one plain dict (:data:`COST_FIELDS` as
    floats, :data:`MEMORY_FIELDS` as ints). Either analysis may be
    absent on a backend — missing halves are simply omitted; None when
    the program reports neither."""
    out: Dict[str, Any] = {}
    if compiled is None:
        return None
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else dict(ca or {})
    except Exception:
        ca = {}
    if ca:
        out["flops"] = float(ca.get("flops", 0.0))
        out["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    if mem is not None:
        def g(name):
            try:
                return int(getattr(mem, name, 0) or 0)
            except Exception:
                return 0

        memd = {"argument_bytes": g("argument_size_in_bytes"),
                "output_bytes": g("output_size_in_bytes"),
                "temp_bytes": g("temp_size_in_bytes"),
                "alias_bytes": g("alias_size_in_bytes"),
                "generated_code_bytes": g("generated_code_size_in_bytes")}
        if any(memd.values()) or hasattr(mem, "temp_size_in_bytes"):
            out.update(memd)
    return out or None


# ---------------------------------------------------------------------------
# MXTPU_EFFICIENCY grammar (strict, cached against the raw string)
# ---------------------------------------------------------------------------

def _parse(raw: Optional[str]) -> bool:
    s = (raw or "").strip()
    if not s:
        return False
    on = False
    for tok in s.split(","):
        tok = tok.strip()
        if not tok:
            continue
        low = tok.lower()
        if low in ("on", "1", "true", "all"):
            on = True
        elif low in ("off", "0", "false"):
            on = False
        else:
            raise MXNetError(
                f"MXTPU_EFFICIENCY: unknown token {tok!r} "
                "(known: on, off)")
    return on


_spec_lock = threading.Lock()
_spec_cached: Optional[Tuple[Optional[str], bool]] = None


def spec() -> bool:
    """True when the plane is armed. Cached against the raw env string —
    the off path is one environ lookup + a compare (the tracer
    discipline); a typo'd value raises on every call."""
    global _spec_cached
    raw = env.raw("MXTPU_EFFICIENCY")
    c = _spec_cached
    if c is not None and c[0] == raw:
        return c[1]
    parsed = _parse(raw)
    with _spec_lock:
        _spec_cached = (raw, parsed)
    return parsed


def enabled() -> bool:
    return spec()


# ---------------------------------------------------------------------------
# Device peak table (MXTPU_DEVICE_PEAK=flops=F,bw=B)
# ---------------------------------------------------------------------------

#: Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``,
#: each with its source. A kind that is not here has no peak: its steps
#: get achieved FLOP/s and bytes/s but no MFU — never a guess.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "flops": 197.0e12,      # bf16
        "bw": 819.0e9,          # HBM bytes/s
        "hbm_bytes": 16.0e9,
        "source": "Google Cloud documentation, \"TPU v5e\": 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def _parse_peak(raw: Optional[str]) -> Optional[Tuple[float, float]]:
    s = (raw or "").strip()
    if not s:
        return None
    vals: Dict[str, float] = {}
    for tok in s.split(","):
        tok = tok.strip()
        if not tok:
            continue
        key, sep, val = tok.partition("=")
        key = key.strip().lower()
        if not sep or key not in ("flops", "bw"):
            raise MXNetError(
                f"MXTPU_DEVICE_PEAK: unknown token {tok!r} (grammar: "
                "flops=<FLOP/s>,bw=<bytes/s>, e.g. flops=73e12,bw=9e11)")
        try:
            vals[key] = float(val)
        except ValueError:
            raise MXNetError(
                f"MXTPU_DEVICE_PEAK: {key}={val.strip()!r} is not a "
                "number")
        if vals[key] <= 0:
            raise MXNetError(
                f"MXTPU_DEVICE_PEAK: {key} must be > 0, got {vals[key]}")
    missing = [k for k in ("flops", "bw") if k not in vals]
    if missing:
        raise MXNetError(
            f"MXTPU_DEVICE_PEAK: missing {missing} — both flops= and "
            "bw= are required (MFU against half a peak table grades "
            "against garbage)")
    return vals["flops"], vals["bw"]


_peak_lock = threading.Lock()
_peak_cached: Optional[Tuple[Optional[str],
                             Optional[Tuple[float, float]]]] = None


def device_peak() -> Dict[str, Any]:
    """The active peak table: ``{"flops", "bw", "source", "estimate"}``.
    ``MXTPU_DEVICE_PEAK`` wins (strict parse); otherwise the published
    peaks of the default device's ``device_kind`` (:data:`DEVICE_PEAKS`).
    A kind with no entry gives ``flops``/``bw`` None and ``estimate``
    True: the rollup then reports no MFU at all."""
    global _peak_cached
    raw = env.raw("MXTPU_DEVICE_PEAK")
    c = _peak_cached
    if c is not None and c[0] == raw:
        parsed = c[1]
    else:
        parsed = _parse_peak(raw)
        with _peak_lock:
            _peak_cached = (raw, parsed)
    if parsed is not None:
        return {"flops": parsed[0], "bw": parsed[1], "source": "env",
                "estimate": False}
    import jax
    kind = jax.devices()[0].device_kind
    row = DEVICE_PEAKS.get(kind)
    if row is None:
        return {"flops": None, "bw": None, "source": f"unknown:{kind}",
                "estimate": True}
    return {"flops": row["flops"], "bw": row["bw"],
            "source": f"table:{kind}", "estimate": False}


def _utilization(flops: float, byts: float, wall_s: float,
                 peak: Dict[str, Any]) -> Dict[str, float]:
    """``{"mfu", "bw_util"}`` against ``peak`` — empty when the device
    has no known peak."""
    if peak["flops"] is None:
        return {}
    if wall_s <= 0:
        return {"mfu": 0.0, "bw_util": 0.0}
    return {"mfu": flops / wall_s / peak["flops"],
            "bw_util": byts / wall_s / peak["bw"]}


# ---------------------------------------------------------------------------
# The rollup
# ---------------------------------------------------------------------------

def _gauges():
    from .registry import default_registry
    reg = default_registry()
    return (
        reg.gauge("mxtpu_mfu",
                  "Model FLOP utilization of the last efficiency-plane "
                  "step: attributed program FLOPs / wall / device peak "
                  "(MXTPU_EFFICIENCY, MXTPU_DEVICE_PEAK)."),
        reg.gauge("mxtpu_goodput_samples",
                  "Useful samples/s of the last efficiency-plane step "
                  "(non-finite skipped steps produce no useful "
                  "samples)."),
    )


def _install_program_gauges() -> None:
    try:
        from . import memory as _memory
        from .registry import default_registry
        reg = default_registry()
        reg.callback_gauge(
            "mxtpu_program_flops",
            lambda: _memory.program_total("flops"),
            "XLA cost-model FLOPs over recorded compiled programs "
            "(one execution each; the efficiency plane's cost registry).")
        reg.callback_gauge(
            "mxtpu_program_bytes_accessed",
            lambda: _memory.program_total("bytes_accessed"),
            "XLA cost-model bytes accessed over recorded compiled "
            "programs (one execution each).")
    except Exception:
        pass


class EfficiencyRollup:
    """Per-process rollup state: the current step's dispatch notes, the
    resolved per-program cost table, run totals and the bounded recent
    window. ``reset_run`` re-arms it per fit (the
    ``reset_pressure_state`` discipline)."""

    def __init__(self):
        self._lock = threading.Lock()
        # current step: token -> [count, kind, label, resolver]
        self._notes: Dict[Any, list] = {}
        self._step_t0: Optional[float] = None
        # run-lifetime per-program table: token -> dict
        self.programs: Dict[Any, Dict[str, Any]] = {}
        self.recent: deque = deque(maxlen=RECENT)
        self.steps = 0
        self.flops_total = 0.0
        self.bytes_total = 0.0
        self.wall_total = 0.0
        self.samples_total = 0
        self.useful_samples_total = 0
        self.skipped_steps = 0
        self.unresolved_dispatches = 0

    # -- run lifecycle --------------------------------------------------
    def reset(self) -> None:
        with self._lock:
            self._notes = {}
            self._step_t0 = None
            self.programs = {}
            self.recent.clear()
            self.steps = 0
            self.flops_total = 0.0
            self.bytes_total = 0.0
            self.wall_total = 0.0
            self.samples_total = 0
            self.useful_samples_total = 0
            self.skipped_steps = 0
            self.unresolved_dispatches = 0

    # -- per-step -------------------------------------------------------
    def note(self, token, kind: str, label: str,
             resolver: Callable[[], Optional[dict]]) -> None:
        with self._lock:
            if self._step_t0 is None:
                # no open step window (bare Trainer loop / serving
                # process with the plane armed): DROP the note — each
                # resolver closure pins a compiled-program cache entry,
                # so accumulating them with nothing ever closing the
                # window would defeat the LRU bound and grow without end
                return
            ent = self._notes.get(token)
            if ent is None:
                self._notes[token] = [1, kind, label, resolver]
            else:
                ent[0] += 1

    def begin_step(self) -> None:
        with self._lock:
            self._notes = {}
            self._step_t0 = time.perf_counter()

    def end_step(self, step: Optional[int] = None, samples: int = 0,
                 useful: bool = True,
                 tokens_per_sample: Optional[float] = None,
                 wall_s: Optional[float] = None) -> Optional[dict]:
        """Close the step window: resolve every noted program's cost
        (cached per signature — only a first-seen program pays the
        re-lower), divide by the wall and the peak table, publish the
        gauges/counters, and append the step record."""
        with self._lock:
            if self._step_t0 is None:
                return None
            notes = self._notes
            self._notes = {}
            t0 = self._step_t0
            self._step_t0 = None
        if wall_s is None:
            wall_s = time.perf_counter() - t0
        # resolution OUTSIDE the rollup lock: resolvers may take the
        # owning CachedOp's trace write-lock (lock-order discipline)
        flops = byts = 0.0
        dispatches = unresolved = 0
        resolved_rows = []
        for token, (count, kind, label, resolver) in notes.items():
            dispatches += count
            stats = None
            try:
                stats = resolver()
            except Exception:
                stats = None
            if not stats or "flops" not in stats:
                unresolved += count
                continue
            f = float(stats.get("flops", 0.0))
            b = float(stats.get("bytes_accessed", 0.0))
            flops += count * f
            byts += count * b
            resolved_rows.append((token, kind, label, count, f, b))
        util = _utilization(flops, byts, wall_s, device_peak())
        sps = (samples / wall_s) if (wall_s > 0 and useful) else 0.0
        rec = {
            "step": step,
            "wall_s": wall_s,
            "flops": flops,
            "bytes_accessed": byts,
            **util,
            "achieved_flops_per_s": flops / wall_s if wall_s > 0 else 0.0,
            "achieved_bytes_per_s": byts / wall_s if wall_s > 0 else 0.0,
            "samples_per_s": sps,
            "useful": bool(useful),
            "dispatches": dispatches,
            "unattributed_dispatches": unresolved,
        }
        if tokens_per_sample is not None:
            rec["tokens_per_s"] = sps * float(tokens_per_sample)
        with self._lock:
            for _token, kind, label, count, f, b in resolved_rows:
                # run-lifetime table keyed by (identity, cost), NOT the
                # per-step note token: a token built on id(entry) could
                # alias a later entry after the first is evicted and
                # collected — two indistinguishable (label, cost) rows
                # merging is fine, two different programs merging is not
                pkey = (kind, label, f, b)
                prog = self.programs.get(pkey)
                if prog is None:
                    prog = self.programs[pkey] = {
                        "kind": kind, "label": label, "flops": f,
                        "bytes_accessed": b, "dispatches": 0}
                prog["dispatches"] += count
            self.recent.append(rec)
            self.steps += 1
            self.flops_total += flops
            self.bytes_total += byts
            self.wall_total += wall_s
            self.samples_total += samples
            if useful:
                self.useful_samples_total += samples
            else:
                self.skipped_steps += 1
            self.unresolved_dispatches += unresolved
        try:
            g_mfu, g_sps = _gauges()
            if util:
                g_mfu.set(util["mfu"])
            g_sps.set(sps)
        except Exception:
            pass
        try:
            from .tracer import tracer as _tr
            if _tr.enabled:
                if util:
                    _tr.counter_event("mfu", util["mfu"],
                                      category="efficiency")
                _tr.counter_event("samples_per_s", sps,
                                  category="efficiency")
        except Exception:
            pass
        return rec

    # -- aggregate ------------------------------------------------------
    def summary(self, tokens_per_sample: Optional[float] = None
                ) -> Optional[dict]:
        peak = device_peak()
        with self._lock:
            if not self.steps:
                return None
            wall = self.wall_total
            sps = (self.useful_samples_total / wall) if wall > 0 else 0.0
            util = _utilization(self.flops_total, self.bytes_total, wall,
                                peak)
            progs = sorted(
                (dict(p) for p in self.programs.values()),
                key=lambda p: -(p["flops"] * p["dispatches"]))
            out = {
                "enabled": True,
                "steps": self.steps,
                "wall_s": round(wall, 6),
                "flops_total": self.flops_total,
                "bytes_total": self.bytes_total,
                "flops_per_step": self.flops_total / self.steps,
                "bytes_per_step": self.bytes_total / self.steps,
                "achieved_flops_per_s": self.flops_total / wall
                if wall > 0 else 0.0,
                "achieved_bytes_per_s": self.bytes_total / wall
                if wall > 0 else 0.0,
                **util,
                # which ceiling is the run actually pressed against —
                # the standard roofline verdict (whichever utilization
                # is higher is the binding constraint). With NOTHING
                # attributed there is no verdict to give: a definitive
                # "compute_bound" over zero measured FLOPs would be a
                # lie (the un-hybridized-net case)
                "roofline": "unattributed"
                if not (self.flops_total > 0 or self.bytes_total > 0)
                else "no_peak" if not util
                else "compute_bound" if util["mfu"] >= util["bw_util"]
                else "bandwidth_bound",
                "samples_per_s": sps,
                "samples_total": self.samples_total,
                "useful_samples_total": self.useful_samples_total,
                "skipped_steps": self.skipped_steps,
                "unattributed_dispatches": self.unresolved_dispatches,
                "peak": dict(peak),
                "estimate": bool(peak["estimate"]),
                "per_program": progs[:20],
                "recent": [dict(r) for r in self.recent],
            }
        if tokens_per_sample is not None:
            out["tokens_per_s"] = sps * float(tokens_per_sample)
            out["tokens_per_sample"] = float(tokens_per_sample)
        return out


_ROLLUP = EfficiencyRollup()
_gauges_installed = [False]


def rollup() -> EfficiencyRollup:
    return _ROLLUP


def reset_run() -> None:
    """Re-arm the rollup for a fresh run (``fit.FitLoop`` calls this at
    fit start). Also the strict-parse checkpoint: a typo'd
    ``MXTPU_EFFICIENCY`` or ``MXTPU_DEVICE_PEAK`` raises HERE, before
    any step runs."""
    on = spec()
    if on:
        device_peak()  # strict-parse the peak table before step 0
        if not _gauges_installed[0]:
            _gauges_installed[0] = True
            _install_program_gauges()
    _ROLLUP.reset()


def note_dispatch(token, kind: str, label: str,
                  resolver: Callable[[], Optional[dict]]) -> None:
    """Record one launch of an attributable compiled program into the
    current step window. ``token`` dedupes repeat launches of the same
    program within a step; ``resolver`` returns the program's cost dict
    (it re-lowers on first call and must cache on its own side — the
    rollup calls it once per step at most). Callers gate on
    :func:`enabled` so the off path never builds the closure."""
    if not spec():
        return
    _ROLLUP.note(token, kind, label, resolver)


def begin_step() -> None:
    if not spec():
        return
    _ROLLUP.begin_step()


def end_step(step: Optional[int] = None, samples: int = 0,
             useful: bool = True,
             tokens_per_sample: Optional[float] = None,
             wall_s: Optional[float] = None) -> Optional[dict]:
    if not spec():
        return None
    return _ROLLUP.end_step(step=step, samples=samples, useful=useful,
                            tokens_per_sample=tokens_per_sample,
                            wall_s=wall_s)


def summary(tokens_per_sample: Optional[float] = None) -> Optional[dict]:
    """The ``FitResult.efficiency`` payload; None when the plane is off
    or no step closed."""
    if not spec():
        return None
    return _ROLLUP.summary(tokens_per_sample=tokens_per_sample)


def cost_report(limit: Optional[int] = None) -> List[Dict[str, Any]]:
    """Recorded programs ranked by cost-model FLOPs (the compute-side
    twin of ``memory.program_report``)."""
    from . import memory as _memory
    rows = [r for r in _memory.program_report(None)
            if float(r.get("flops", 0.0) or 0.0) > 0]
    rows.sort(key=lambda r: -float(r.get("flops", 0.0)))
    return rows[:limit] if limit else rows
