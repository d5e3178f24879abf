"""Bench hygiene (ROADMAP carry-item): the headline artifact must parse.

BENCH r05 shipped rc:124 with an EMPTY artifact — the failure mode was
only caught post-hoc, in the bench review. These subprocess tests pin the
two structural guarantees in-repo:

- a tiny ``MXTPU_BENCH_DEADLINE_S`` run (the ``smoke`` model: 2-layer
  MLP, compiles in seconds on CPU) still emits a headline JSON line that
  parses and carries the train + step_breakdown + autotune rows;
- a deadline too small for ANY child still exits 0 with a parseable
  error row, never silence.

Marker ``autotune`` (this PR's subsystem marker; tier-1-safe).
"""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.autotune

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(deadline_s, timeout, extra_env=None):
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "MXTPU_BENCH_DEADLINE_S": str(deadline_s),
           "MXTPU_BENCH_CONFIGS": "8x2",
           "MXTPU_BENCH_MODEL": "smoke",
           "MXTPU_BENCH_DTYPE": "float32",
           "MXTPU_BENCH_INFERENCE": "0",
           "MXTPU_BENCH_LOWBIT": "0",
           **(extra_env or {})}
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=ROOT)


@pytest.mark.heavy
def test_bench_tiny_deadline_emits_full_headline_json():
    res = _run_bench(deadline_s=300, timeout=360)
    assert res.returncode == 0, res.stderr[-1000:]
    rows = [json.loads(l) for l in res.stdout.splitlines()
            if l.startswith("{")]
    assert rows, f"no JSON on stdout:\n{res.stdout}\n{res.stderr[-500:]}"
    # incremental re-emission: the LAST line is the most complete payload
    payload = rows[-1]
    assert payload["metric"] == "resnet50_train_imgs_per_sec"
    assert "error" not in payload, payload
    assert payload["value"] > 0
    # the r05 class of outage: rows present, not silently missing
    bd = payload["step_breakdown"]
    assert bd["steps"] > 0 and 0.8 <= bd["accounted_frac"] <= 1.0 + 1e-6
    assert "compute" in bd["shares"]
    at = payload["autotune"]
    assert at["status"] == "locked"
    assert at["probe_candidates"] >= 2
    assert set(at["chosen"]) == set(at["baseline"]) != set()
    # the tuner's needle on the comm-heavy probe config: exposed comm
    # share shrinks, and the hidden time stays visible
    assert at["comm_share_after"] < at["comm_share_before"]
    assert at["comm_overlapped_share_after"] > 0
    # the memory row: the device-byte attribution ZeRO-1 will be graded
    # on must ship with the headline, not as a separate artifact
    mrow = payload["memory"]
    assert mrow["params_bytes"] > 0 and mrow["grads_bytes"] > 0
    assert mrow["optimizer_bytes"] > 0 and mrow["masters_bytes"] > 0
    assert mrow["grad_bucket_bytes"] > 0
    assert mrow["step_peak_bytes"] >= mrow["params_bytes"]
    assert mrow["programs"] > 0
    # the zero row: per-rank optimizer+masters bytes must land at 1/world
    # of the unsharded mp-Adam baseline (equal-sized params, ledger-exact)
    zrow = payload["zero"]
    assert zrow["world"] == 4
    assert zrow["unsharded_opt_masters_bytes"] > 0
    assert zrow["zero_rank0_opt_masters_bytes"] == \
        zrow["unsharded_opt_masters_bytes"] // zrow["world"]
    assert zrow["zero_total_opt_masters_bytes"] == \
        zrow["unsharded_opt_masters_bytes"]
    assert abs(zrow["rank0_share"] - 1.0 / zrow["world"]) < 0.01
    assert zrow["step_ms_zero"] > 0 and zrow["step_ms_unsharded"] > 0
    assert zrow["zero_collectives_per_step"] >= 2  # rs + ag per bucket
    # the zero_overlap row: with MXTPU_COMM_OVERLAP=on the grad-finality
    # reduce-scatter + allgather prefetch move the launches under
    # comm_overlapped, so the EXPOSED comm share strictly drops vs the
    # barrier plane on the same workload (CPU child: the CPU is in no
    # peaks table, so the row's MFU fields are bench.py's 0.0 default —
    # the attribution move is the pin)
    zorow = payload["zero_overlap"]
    assert zorow["world"] == 2
    assert zorow["step_ms_barrier"] > 0 and zorow["step_ms_overlap"] > 0
    assert zorow["comm_overlapped_share"] > 0
    assert zorow["exposed_comm_share_overlap"] < \
        zorow["exposed_comm_share_barrier"]
    assert zorow["total_comm_share_overlap"] >= \
        zorow["comm_overlapped_share"]
    assert zorow["mfu_barrier"] == zorow["mfu_overlap"] == 0
    assert zorow["collectives_per_step"] >= 2  # rs + ag per bucket
    # the comm_health row: the collective-observability plane over a
    # clean simulated ZeRO run — ledger populated, no skew (one process,
    # one clock), and ZERO watchdog firings with the watchdog armed
    crow = payload["comm_health"]
    assert crow["world"] == 4
    assert crow["ledger_depth"] > 0
    assert crow["watchdog_fired"] == 0
    assert crow["max_coll_skew_ms"] == 0.0
    assert crow["desync"] is None
    assert crow["collectives_per_step"] >= 2
    # the numerics row: in-graph grad norm from a clean instrumented
    # FitLoop, and the provenance drill firing EXACTLY once under an
    # injected nan_grad — naming the poisoned parameter
    nrow = payload["numerics"]
    assert nrow["samples"] > 0
    assert nrow["grad_norm"] > 0
    assert nrow["update_ratio"] > 0
    assert "sampled_overhead_pct" in nrow
    assert nrow["provenance_dumps"] == 1
    assert nrow["nonfinite_steps"] == [2]
    assert nrow["culprit"]
    assert nrow["loss_scale_events"] == 1
    # the efficiency row: cost-model FLOPs of the dispatched programs
    # (no MFU: the CPU has no peak), full attribution on the hybridized
    # smoke MLP,
    # and the persistent run-report round-trip (parse + manifest verify)
    # — the carried hygiene item: the first artifact reflecting
    # PRs 6-14 parses with every plane's row present
    erow = payload["efficiency"]
    assert erow["mfu"] == 0 and erow["roofline"] == "no_peak"
    assert erow["samples_per_s"] > 0
    assert erow["flops_per_step"] > 0
    assert erow["unattributed_dispatches"] == 0
    assert 1 <= len(erow["top_programs"]) <= 3
    assert all(f > 0 for _lbl, f in erow["top_programs"])
    assert erow["estimate"] is True  # CPU child, defaulted peak table
    assert erow["report_ok"] is True
    assert erow["report_steps"] > 0
    # the elastic row: a simulated mid-run resize (chaos resize@K,
    # resumable exit 75) resumed at a different world must reproduce the
    # always-at-new-size trajectory — the ROADMAP acceptance bar,
    # re-measured with every artifact
    elrow = payload["elastic"]
    assert elrow["from_world"] == 2 and elrow["to_world"] == 3
    assert elrow["resumable_exit"] is True
    assert elrow["resume_s"] > 0
    assert elrow["post_resize_steps"] > 0
    assert elrow["trajectory_match"] is True
    # the selfheal row: a REAL supervised 2-worker run with one injected
    # rank kill must auto-shrink, auto-grow back, and finish with the
    # no-failure trajectory — the self-healing acceptance bar, measured
    # as wall-clock detect->shrink and capacity->grow latencies
    srow = payload["selfheal"]
    assert srow["restarts"] == 1
    assert srow["grows"] == 1
    assert srow["final_world"] == 2
    assert srow["generations"] == 3
    assert srow["shrink_s"] > 0
    assert srow["grow_s"] > 0
    assert srow["union_ok"] is True
    assert srow["trajectory_match"] is True
    # the fleet row: a REAL 2-process serving fleet behind the
    # least-loaded router with a chaos replica_kill mid closed-loop —
    # zero dropped requests (the router retried the corpse's un-acked
    # in-flight on the survivor) and a ZERO-compile scale-up from the
    # published AOT bundle + shared compile cache
    frow = payload["fleet"]
    assert frow["replicas"] == 2
    assert frow["aggregate_qps"] > 0 and frow["requests"] > 0
    assert frow["p99_ms"] > 0
    assert frow["killed"] == 1
    assert frow["dropped_requests"] == 0
    assert frow["scaleup_s"] > 0
    assert frow["scaleup_compiles"] == 0
    assert frow["scaleup_aot_loaded"] > 0
    assert frow["dense_qps"] > 0 and frow["int8_qps"] > 0
    # the recsys row: the sparse embedding plane's numbers — warm
    # mask-packed row-sparse examples/s, closed-loop lookup_qps from the
    # 2-replica LookupFleet, and the ledger pin: EVERY rank's bytes at
    # exactly 1/world of the world=1 baseline trained the same way
    # (Adam state lazy per rank; the probe touches all rows first)
    rrow = payload["recsys"]
    assert rrow["world"] == 4
    assert rrow["examples_per_s"] > 0
    assert rrow["unsharded_embedding_bytes"] > 0
    assert len(rrow["per_rank_embedding_bytes"]) == rrow["world"]
    assert all(
        b == rrow["unsharded_embedding_bytes"] // rrow["world"]
        for b in rrow["per_rank_embedding_bytes"])
    assert rrow["replicas"] == 2
    assert rrow["lookup_requests"] > 0 and rrow["lookup_qps"] > 0


def test_bench_exhausted_deadline_still_emits_parseable_row():
    """Deadline too small for any child: bench must exit 0 with an error
    row that parses — never rc:124 with an empty artifact."""
    res = _run_bench(deadline_s=5, timeout=120)
    assert res.returncode == 0, res.stderr[-500:]
    rows = [json.loads(l) for l in res.stdout.splitlines()
            if l.startswith("{")]
    assert len(rows) == 1
    assert rows[0]["metric"] == "resnet50_train_imgs_per_sec"
    assert rows[0]["value"] == 0.0
    assert "error" in rows[0]
