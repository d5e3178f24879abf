"""Efficiency & goodput plane (telemetry/efficiency.py, MXTPU_EFFICIENCY):
shared cost/memory extraction, per-program FLOPs vs hand-computed matmul
counts, MFU arithmetic vs a known peak table, off-path inertness, bitwise
on-vs-off trajectory parity, dispatch/launch-count invariance, the
persistent run report round-trip (incl. manifest verify), the
tools/run_compare.py fence/exit-code matrix (incl. the kv_slow slowed-run
acceptance pair), and the trace_report mfu-column round-trip.

Tier-1-safe: tiny models, CPU (where the XLA cost model is exact),
in-process, seeded everything.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import fault, fit, gluon, io, nd
from mxnet_tpu import kvstore as kvs
from mxnet_tpu.base import MXNetError, env
from mxnet_tpu.contrib import chaos
from mxnet_tpu.optimizer import grouped as grouped_mod
from mxnet_tpu.telemetry import efficiency as eff
from mxnet_tpu.telemetry import memory as mem
from mxnet_tpu.telemetry import run_report as rrmod

pytestmark = pytest.mark.efficiency

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_plane(monkeypatch):
    monkeypatch.delenv("MXTPU_EFFICIENCY", raising=False)
    monkeypatch.delenv("MXTPU_DEVICE_PEAK", raising=False)
    monkeypatch.delenv("MXTPU_RUN_REPORT_DIR", raising=False)
    chaos.uninstall()
    eff.reset_run()
    yield
    chaos.uninstall()
    monkeypatch.delenv("MXTPU_EFFICIENCY", raising=False)
    monkeypatch.delenv("MXTPU_DEVICE_PEAK", raising=False)
    eff.reset_run()


def _mlp(width=32, out=8, in_units=16, hybridize=True, seed=0):
    mx.random.seed(seed)
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(width, activation="relu", in_units=in_units),
            gluon.nn.Dense(out, in_units=width))
    net.initialize(mx.init.Xavier(rnd_type="gaussian", magnitude=1.0))
    if hybridize:
        net.hybridize()
    return net


def _fit(net, steps=4, batch=16, in_units=16, classes=8, seed=0,
         kvstore=None, loss_scale=1.0, **loop_kw):
    rs = np.random.RandomState(seed)
    data = rs.randn(steps * batch, in_units).astype(np.float32)
    label = rs.randint(0, classes, (steps * batch,)).astype(np.float32)
    it = io.NDArrayIter(data, label, batch_size=batch)
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 1e-3}, kvstore=kvstore)
    loop = fit.FitLoop(net, tr, gluon.loss.SoftmaxCrossEntropyLoss(),
                       it, ckpt_dir=None, loss_scale=loss_scale,
                       **loop_kw)
    return loop.fit(epochs=1), tr


# --------------------------------------------------------- grammar

def test_grammar():
    assert eff._parse(None) is False
    assert eff._parse("") is False
    for on in ("on", "1", "true", "all"):
        assert eff._parse(on) is True
    for off in ("off", "0", "false"):
        assert eff._parse(off) is False
    with pytest.raises(MXNetError):
        eff._parse("bogus")


def test_peak_grammar():
    assert eff._parse_peak("flops=73e12,bw=9e11") == (73e12, 9e11)
    assert eff._parse_peak("") is None
    for bad in ("flops=1e12",            # missing bw
                "bw=1e12",               # missing flops
                "flops=x,bw=1",          # not a number
                "flops=0,bw=1",          # non-positive
                "flops=1,bw=1,hz=2",     # unknown key
                "73e12"):                # no key at all
        with pytest.raises(MXNetError):
            eff._parse_peak(bad)


def test_typo_raises_at_fit_start(monkeypatch):
    monkeypatch.setenv("MXTPU_EFFICIENCY", "on")
    monkeypatch.setenv("MXTPU_DEVICE_PEAK", "flops=garbage")
    net = _mlp()
    with pytest.raises(MXNetError, match="MXTPU_DEVICE_PEAK"):
        _fit(net, steps=1)


# ------------------------------------------- shared extraction helper

def test_shared_helper_matches_hand_rolled_extraction():
    """Dedup satellite pin: the ONE shared extraction helper returns
    byte-identical numbers to hand-rolled cost_analysis /
    memory_analysis reads of the same Compiled object."""
    import jax.numpy as jnp
    f = jax.jit(lambda a, b: a @ b)
    a = jax.ShapeDtypeStruct((32, 64), np.float32)
    b = jax.ShapeDtypeStruct((64, 8), np.float32)
    comp = f.lower(a, b).compile()
    stats = eff.compiled_program_stats(comp)
    ca = comp.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else dict(ca)
    m = comp.memory_analysis()
    assert stats["flops"] == float(ca.get("flops", 0.0))
    assert stats["bytes_accessed"] == float(ca.get("bytes accessed", 0.0))
    assert stats["argument_bytes"] == int(m.argument_size_in_bytes)
    assert stats["output_bytes"] == int(m.output_size_in_bytes)
    assert stats["temp_bytes"] == int(m.temp_size_in_bytes)
    # memory.compiled_memory_stats (the historical surface CachedOp /
    # grouped route through) stays the exact 5-field layout
    ms = mem.compiled_memory_stats(comp)
    assert set(ms) == set(eff.MEMORY_FIELDS)
    assert ms["argument_bytes"] == stats["argument_bytes"]


def test_spmd_program_stats_shape_unchanged():
    """spmd.program_stats keeps its historical 4-key layout through the
    shared helper, and the program lands in the cost registry."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import SPMDTrainer
    net = gluon.nn.Dense(4, in_units=8)
    net.initialize(mx.init.One())
    tr = SPMDTrainer(net, gluon.loss.L2Loss(), mesh=None,
                     optimizer="sgd")
    data = np.ones((2, 4, 8), np.float32)
    label = np.zeros((2, 4, 4), np.float32)
    tr.run_steps(data, label)
    stats = tr.program_stats()
    assert set(stats) == {"flops", "bytes_accessed", "argument_bytes",
                          "temp_bytes"}
    assert stats["flops"] > 0
    assert any(r["kind"] == "spmd" for r in mem.program_report(None))


# ------------------------------------------------- FLOPs correctness

def test_cached_op_flops_match_hand_computed_matmul(monkeypatch):
    """Acceptance: per-program FLOPs equal hand-computed matmul counts.
    A bias-free Dense forward is one (b, i) x (i, o) matmul — the XLA
    cost model counts exactly 2*b*i*o FLOPs for it on CPU."""
    monkeypatch.setenv("MXTPU_EFFICIENCY", "on")
    b, i, o = 16, 32, 8
    net = gluon.nn.Dense(o, in_units=i, use_bias=False)
    net.initialize(mx.init.One())
    net.hybridize()
    x = nd.array(np.random.RandomState(0).randn(b, i).astype(np.float32))
    eff.reset_run()
    eff.begin_step()
    net(x)
    rec = eff.rollup().end_step(step=0, samples=b)
    assert rec["dispatches"] == 1
    assert rec["unattributed_dispatches"] == 0
    assert rec["flops"] == 2.0 * b * i * o


def test_fitloop_mfu_nonzero_and_programs_attributed(monkeypatch):
    """Acceptance: a smoke-MLP FitLoop with the plane on reports nonzero
    MFU, and the per-program table carries forward + backward + the
    grouped optimizer bucket + the finiteness reduction."""
    monkeypatch.setenv("MXTPU_EFFICIENCY", "on")
    monkeypatch.setenv("MXTPU_DEVICE_PEAK", "flops=1e12,bw=1e12")
    res, _tr = _fit(_mlp(), steps=4)
    e = res.efficiency
    assert e is not None and e["enabled"]
    assert e["steps"] == 4
    assert e["mfu"] > 0
    assert e["samples_per_s"] > 0
    assert e["estimate"] is False
    assert e["peak"]["source"] == "env"
    assert e["roofline"] in ("compute_bound", "bandwidth_bound")
    kinds = {(p["kind"], p["label"].split(":")[-1][:3])
             for p in e["per_program"]}
    labels = " ".join(p["label"] for p in e["per_program"])
    assert any(p["kind"] == "cached_op" and "fwd" in p["label"]
               for p in e["per_program"]), labels
    assert any(p["kind"] == "cached_op" and "bwd" in p["label"]
               for p in e["per_program"]), labels
    assert any(p["kind"] == "optimizer" and "bucket" in p["label"]
               for p in e["per_program"]), labels
    assert any("finite_flag" in p["label"] for p in e["per_program"]), \
        labels
    assert e["unattributed_dispatches"] == 0
    # every attributed program launched once per step
    assert all(p["dispatches"] == 4 for p in e["per_program"])
    # the forward matmul FLOPs are in the table: hand-computable Dense
    # (16x16 -> 32, with bias+relu: 2*b*i*w + 2*b*w elementwise)
    flops = sorted(p["flops"] for p in e["per_program"])
    assert all(f > 0 for f in flops)


def test_mfu_arithmetic_vs_known_peak(monkeypatch):
    """MFU/roofline arithmetic pinned against a hand-set peak table and
    a hand-fed program cost with a controlled wall."""
    monkeypatch.setenv("MXTPU_EFFICIENCY", "on")
    monkeypatch.setenv("MXTPU_DEVICE_PEAK", "flops=1e9,bw=2e9")
    eff.reset_run()
    r = eff.rollup()
    r.begin_step()
    eff.note_dispatch(("t", 1), "test", "fake",
                      lambda: {"flops": 4e6, "bytes_accessed": 1e6})
    rec = r.end_step(step=0, samples=10, wall_s=0.01)
    assert rec["flops"] == 4e6
    assert rec["mfu"] == pytest.approx(4e6 / 0.01 / 1e9)
    assert rec["bw_util"] == pytest.approx(1e6 / 0.01 / 2e9)
    assert rec["samples_per_s"] == pytest.approx(1000.0)
    s = r.summary()
    assert s["mfu"] == pytest.approx(rec["mfu"])
    # flops utilization (0.4) > bw utilization (0.05): compute-bound
    assert s["roofline"] == "compute_bound"
    assert s["estimate"] is False
    # goodput: a non-useful (sentinel-skipped) step's samples don't count
    r.begin_step()
    eff.note_dispatch(("t", 1), "test", "fake",
                      lambda: {"flops": 4e6, "bytes_accessed": 1e6})
    rec2 = r.end_step(step=1, samples=10, useful=False, wall_s=0.01)
    assert rec2["samples_per_s"] == 0.0
    s2 = r.summary()
    assert s2["useful_samples_total"] == 10
    assert s2["samples_total"] == 20
    assert s2["skipped_steps"] == 1


def test_tokens_per_s(monkeypatch):
    monkeypatch.setenv("MXTPU_EFFICIENCY", "on")
    res, _ = _fit(_mlp(), steps=2, tokens_per_sample=128)
    e = res.efficiency
    assert e["tokens_per_s"] == pytest.approx(
        e["samples_per_s"] * 128.0)


def test_unknown_device_kind_gives_no_mfu(monkeypatch):
    """The CPU is in no peaks table: achieved rates, no MFU, no guess."""
    monkeypatch.setenv("MXTPU_EFFICIENCY", "on")
    res, _ = _fit(_mlp(), steps=2)
    e = res.efficiency
    assert e["estimate"] is True
    assert e["peak"] == {"flops": None, "bw": None,
                         "source": "unknown:cpu", "estimate": True}
    assert "mfu" not in e and "bw_util" not in e
    assert e["roofline"] == "no_peak"
    assert e["achieved_flops_per_s"] > 0
    assert all("mfu" not in r for r in e["recent"])


def test_peaks_table_keyed_by_device_kind(monkeypatch):
    """A kind in DEVICE_PEAKS is graded against its published peaks,
    each entry naming its source."""
    import jax
    for row in eff.DEVICE_PEAKS.values():
        assert row["flops"] > 0 and row["bw"] > 0 and row["source"]
    v5e = eff.DEVICE_PEAKS["TPU v5 lite"]
    assert (v5e["flops"], v5e["bw"], v5e["hbm_bytes"]) == \
        (197e12, 819e9, 16e9)

    class _Dev:
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    assert eff.device_peak() == {"flops": 197e12, "bw": 819e9,
                                 "source": "table:TPU v5 lite",
                                 "estimate": False}


def test_zero_attribution_reports_unattributed_not_compute_bound(
        monkeypatch):
    """An un-hybridized net with the per-param update path attributes
    NOTHING — the roofline verdict must say so, not claim a definitive
    'compute_bound' over zero measured FLOPs."""
    monkeypatch.setenv("MXTPU_EFFICIENCY", "on")
    monkeypatch.setenv("MXTPU_DEVICE_PEAK", "flops=1e12,bw=1e12")
    monkeypatch.setenv("MXTPU_OPTIMIZER_AGGREGATION", "0")
    res, _ = _fit(_mlp(hybridize=False), steps=2)
    e = res.efficiency
    assert e["flops_total"] == 0
    assert e["mfu"] == 0
    assert e["roofline"] == "unattributed"


def test_env_default_valued_var_is_not_an_override(monkeypatch,
                                                   tmp_path):
    monkeypatch.setenv("MXTPU_EFFICIENCY", "on")
    monkeypatch.setenv("MXTPU_RUN_REPORT_DIR", str(tmp_path))
    # SET to the declared default: not a configuration difference
    monkeypatch.setenv("MXTPU_OPTIMIZER_AGGREGATION",
                       str(env.default_for("MXTPU_OPTIMIZER_AGGREGATION")))
    res, _ = _fit(_mlp(), steps=2)
    fp = rrmod.load_run_report(res.run_report)["fingerprint"]
    assert "MXTPU_OPTIMIZER_AGGREGATION" not in fp["env_overrides"]


def test_spmd_program_stats_raises_loudly_without_analyses(monkeypatch):
    """A backend reporting no cost/memory analyses must fail the
    diagnostic loudly — an all-zero row would read as 'this program is
    free'."""
    from mxnet_tpu.parallel import SPMDTrainer
    net = gluon.nn.Dense(4, in_units=8)
    net.initialize(mx.init.One())
    tr = SPMDTrainer(net, gluon.loss.L2Loss(), mesh=None,
                     optimizer="sgd")
    tr.run_steps(np.ones((2, 4, 8), np.float32),
                 np.zeros((2, 4, 4), np.float32))
    monkeypatch.setattr(
        "mxnet_tpu.telemetry.efficiency.compiled_program_stats",
        lambda compiled: None)
    with pytest.raises(MXNetError, match="no\\s+cost/memory analysis"):
        tr.program_stats()


# ------------------------------------------------- inertness contracts

def test_off_path_inert():
    res, tr = _fit(_mlp(), steps=2)
    assert res.efficiency is None
    assert eff.summary() is None
    assert res.run_report is None
    # no step windows accumulated
    assert eff.rollup().steps == 0


def test_bitwise_on_vs_off_parity(monkeypatch, tmp_path):
    """The plane (and the run report write) is numerically inert: the
    weight trajectory is bitwise identical with it on or off."""
    def weights(plane_on):
        if plane_on:
            monkeypatch.setenv("MXTPU_EFFICIENCY", "on")
            monkeypatch.setenv("MXTPU_RUN_REPORT_DIR",
                               str(tmp_path / "rr"))
        else:
            monkeypatch.delenv("MXTPU_EFFICIENCY", raising=False)
            monkeypatch.delenv("MXTPU_RUN_REPORT_DIR", raising=False)
        net = _mlp(seed=7)
        res, _ = _fit(net, steps=4, seed=7)
        # by position: the auto-numbered names (dense9_, dense10_) sort
        # differently from one net to the next
        return res, [p.data().asnumpy().tobytes()
                     for p in net.collect_params().values()]

    res_off, w_off = weights(False)
    res_on, w_on = weights(True)
    assert w_on == w_off
    assert res_off.losses == res_on.losses
    assert res_on.efficiency is not None


def test_warm_dispatch_counts_equal_plane_off(monkeypatch):
    """Acceptance: warm-step dispatch/launch counts are test-pinned
    equal to plane-off — cost resolution is a re-lower (a trace), never
    an extra launch, and never a new compiled-program cache entry."""
    def run(plane_on):
        if plane_on:
            monkeypatch.setenv("MXTPU_EFFICIENCY", "on")
        else:
            monkeypatch.delenv("MXTPU_EFFICIENCY", raising=False)
        net = _mlp(seed=3)
        before = grouped_mod.cache_info()
        res, tr = _fit(net, steps=4, seed=3)
        after = grouped_mod.cache_info()
        return (tr.last_update_dispatches,
                after.misses - before.misses)

    d_off, m_off = run(False)
    d_on, m_on = run(True)
    assert d_on == d_off > 0
    assert m_on == m_off


# ------------------------------------------------- run report + diff

def test_run_report_round_trip_with_manifest(monkeypatch, tmp_path):
    rdir = tmp_path / "reports"
    monkeypatch.setenv("MXTPU_EFFICIENCY", "on")
    monkeypatch.setenv("MXTPU_DEVICE_PEAK", "flops=1e12,bw=1e12")
    monkeypatch.setenv("MXTPU_RUN_REPORT_DIR", str(rdir))
    res, _ = _fit(_mlp(), steps=4)
    assert res.run_report and os.path.exists(res.run_report)
    rep = rrmod.load_run_report(res.run_report)
    assert rep["format"] == rrmod.REPORT_FORMAT
    assert rep["run"]["steps"] == 4
    assert rep["step_time"]["p50_s"] > 0
    assert rep["step_time"]["p95_s"] >= rep["step_time"]["p50_s"]
    assert rep["loss"]["n"] == 4
    assert len(rep["loss"]["sha256_16"]) == 16
    assert rep["efficiency"]["mfu"] > 0
    assert "recent" not in rep["efficiency"]  # verdict, not a trace
    assert rep["memory"]["peak_bytes"] > 0
    fp = rep["fingerprint"]["env_overrides"]
    assert fp["MXTPU_EFFICIENCY"] == "on"
    # the report dir itself is NOT config, and a var set to its declared
    # default is NOT an override — two clean runs reporting into
    # different directories must not read as "configured differently"
    assert "MXTPU_RUN_REPORT_DIR" not in fp
    # the shared-manifest discipline: the directory verifies
    fault.verify_manifest(str(rdir), required=True)
    # a second fit in the same second must not clobber the first
    res2, _ = _fit(_mlp(seed=1), steps=2, seed=1)
    assert res2.run_report != res.run_report
    fault.verify_manifest(str(rdir), required=True)
    # identical trajectories hash identical; different ones differ
    assert rrmod.load_run_report(res2.run_report)["loss"]["sha256_16"] \
        != rep["loss"]["sha256_16"]


def _synth_report(path, step_p50=0.01, mfu=0.5, sps=1000.0,
                  mem_peak=1000, skipped=0, **over):
    payload = {
        "format": 1, "kind": "mxtpu_run_report", "time_unix": 0,
        "pid": 1,
        "fingerprint": {"env_overrides": over.pop("env", {})},
        "run": {"steps": 8, "skipped_steps": skipped},
        "step_time": {"p50_s": step_p50, "p95_s": step_p50 * 1.2,
                      "max_s": step_p50 * 2},
        "loss": {"last": 1.0},
        "memory": {"peak_bytes": mem_peak},
        "efficiency": {"mfu": mfu, "samples_per_s": sps,
                       "achieved_flops_per_s": mfu * 1e12,
                       "estimate": False},
    }
    payload.update(over)
    with open(path, "w") as f:
        json.dump(payload, f)
    return str(path)


def test_run_compare_matrix(tmp_path, capsys):
    from tools import run_compare as rc
    a = _synth_report(tmp_path / "a.json")
    # within the 5% fence: ok, exit 0
    b_ok = _synth_report(tmp_path / "b_ok.json", step_p50=0.0102,
                         mfu=0.49, sps=980.0)
    assert rc.main([a, b_ok]) == 0
    # step time +50%, mfu -40%: regression, exit 1, both named
    b_bad = _synth_report(tmp_path / "b_bad.json", step_p50=0.015,
                          mfu=0.3, sps=660.0)
    capsys.readouterr()  # flush the text-mode output before --json
    assert rc.main([a, b_bad, "--json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert "step_time_p50_s" in out["regressed"]
    assert "mfu" in out["regressed"]
    assert "samples_per_s" in out["regressed"]
    assert out["verdict"] == "regression"
    # an IMPROVEMENT never fails the gate
    b_fast = _synth_report(tmp_path / "b_fast.json", step_p50=0.005,
                           mfu=0.9, sps=2000.0)
    assert rc.main([a, b_fast]) == 0
    # a wider fence swallows the regression
    assert rc.main([a, b_bad, "--fence", "60"]) == 0
    # zero-baseline count: ANY skipped step regresses
    b_skip = _synth_report(tmp_path / "b_skip.json", skipped=3)
    capsys.readouterr()
    assert rc.main([a, b_skip, "--json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["regressed"] == ["skipped_steps"]
    # missing plane (no efficiency block) never regresses
    b_noeff = _synth_report(tmp_path / "b_noeff.json")
    with open(b_noeff) as f:
        p = json.load(f)
    del p["efficiency"]
    with open(b_noeff, "w") as f:
        json.dump(p, f)
    capsys.readouterr()
    assert rc.main([a, b_noeff, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    mrow = {r["metric"]: r["verdict"] for r in out["metrics"]}
    assert mrow["mfu"] == "missing"
    # fingerprint diff is surfaced
    b_env = _synth_report(tmp_path / "b_env.json",
                          env={"MXTPU_ZERO": "on"})
    capsys.readouterr()
    assert rc.main([a, b_env, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["fingerprint_diff"] == ["MXTPU_ZERO"]
    # bad inputs: exit 2
    assert rc.main([str(tmp_path / "nope.json"), a]) == 2
    notrep = tmp_path / "notrep.json"
    notrep.write_text("{}")
    assert rc.main([str(notrep), a]) == 2
    # a NEWER-format report must be rejected (exit 2), not silently
    # degrade every metric to 'missing' and pass the gate blind
    newer = _synth_report(tmp_path / "newer.json")
    with open(newer) as f:
        p = json.load(f)
    p["format"] = 99
    with open(newer, "w") as f:
        json.dump(p, f)
    assert rc.main([a, newer]) == 2


def test_run_compare_grades_recsys_row(tmp_path, capsys):
    """The recsys bench row's rates (sparse embedding plane: train
    examples/s + LookupFleet lookup_qps) gate directionally like any
    other rate; a report without the row stays 'missing', never a
    false regression."""
    from tools import run_compare as rc
    a = _synth_report(tmp_path / "a.json",
                      recsys={"examples_per_s": 40000.0,
                              "lookup_qps": 3000.0})
    good = _synth_report(tmp_path / "good.json",
                         recsys={"examples_per_s": 41000.0,
                                 "lookup_qps": 3050.0})
    assert rc.main([a, good]) == 0
    bad = _synth_report(tmp_path / "bad.json",
                        recsys={"examples_per_s": 20000.0,
                                "lookup_qps": 1000.0})
    capsys.readouterr()
    assert rc.main([a, bad, "--json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert "recsys_examples_per_s" in out["regressed"]
    assert "lookup_qps" in out["regressed"]
    plain = _synth_report(tmp_path / "plain.json")
    capsys.readouterr()
    assert rc.main([a, plain, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    mrow = {r["metric"]: r["verdict"] for r in out["metrics"]}
    assert mrow["lookup_qps"] == "missing"
    assert mrow["recsys_examples_per_s"] == "missing"


def test_run_compare_cli_and_kv_slow_acceptance(monkeypatch, tmp_path):
    """Acceptance: two run reports from an intentionally-slowed run pair
    (chaos kv_slow wire delay) make tools/run_compare.py exit nonzero
    naming step-time and MFU as the regressed metrics."""
    rdir = tmp_path / "rr"
    monkeypatch.setenv("MXTPU_EFFICIENCY", "on")
    monkeypatch.setenv("MXTPU_DEVICE_PEAK", "flops=1e12,bw=1e12")
    monkeypatch.setenv("MXTPU_RUN_REPORT_DIR", str(rdir))

    def run(slow):
        if slow:
            # every kv attempt sleeps 600ms: the delay is injected, so it
            # is sized to stand clear of a loaded host's own step times
            chaos.install("kv_slow@600")
        try:
            net = _mlp(seed=11)
            res, _ = _fit(net, steps=4, seed=11,
                          kvstore=kvs.create("device"))
        finally:
            chaos.uninstall()
        return res.run_report

    run(False)                      # warm every compiled program
    fast = run(False)
    slow = run(True)
    assert fast and slow
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "run_compare.py"),
         fast, slow, "--json"],
        capture_output=True, text=True, cwd=ROOT)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    assert "step_time_p50_s" in out["regressed"]
    assert "mfu" in out["regressed"]
    # and a pair inside the fence passes the gate: the fast report
    # against itself with every step time a tenth longer (two real runs
    # of a 4-step fit differ by what else the host is doing)
    near_rep = rrmod.load_run_report(fast)
    for k in ("p50_s", "p95_s"):
        near_rep["step_time"][k] *= 1.1
    near = tmp_path / "near.json"
    near.write_text(json.dumps(near_rep))
    proc2 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "run_compare.py"),
         fast, str(near), "--fence", "25", "--json"],
        capture_output=True, text=True, cwd=ROOT)
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr
    assert json.loads(proc2.stdout)["regressed"] == []


def test_roofline_from_report(monkeypatch, tmp_path):
    """tools/roofline_ledger.py --from-report stamps a mode row (same
    JSON schema) from a run report instead of a live re-measure."""
    rdir = tmp_path / "rr"
    monkeypatch.setenv("MXTPU_EFFICIENCY", "on")
    monkeypatch.setenv("MXTPU_RUN_REPORT_DIR", str(rdir))
    res, _ = _fit(_mlp(), steps=4)
    out_path = tmp_path / "ROOFLINE.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "roofline_ledger.py"),
         "--modes", "", "--from-report", res.run_report,
         "--out", str(out_path)],
        capture_output=True, text=True, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-500:]
    ledger = json.loads(out_path.read_text())
    row = ledger["modes"]["bf16"]
    rep = rrmod.load_run_report(res.run_report)
    assert row["imgs_per_sec_measured"] == pytest.approx(
        rep["efficiency"]["samples_per_s"], rel=0.01)
    assert row["program_flops_per_step"] == \
        rep["efficiency"]["flops_per_step"]
    assert "mfu" not in row  # the CPU has no peak to grade against
    assert "run report" in \
        ledger["modes_provenance"]["measured_imgs_per_sec_source"]
    # a NEWER-format report is rejected, not stamped as a null row
    newer = tmp_path / "newer.json"
    rep2 = dict(rep, format=99)
    newer.write_text(json.dumps(rep2))
    proc_new = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "roofline_ledger.py"),
         "--modes", "", "--from-report", str(newer),
         "--out", str(tmp_path / "R2.json")],
        capture_output=True, text=True, cwd=ROOT)
    assert proc_new.returncode != 0
    assert "newer" in proc_new.stderr


# ------------------------------------------------- trace integration

def test_trace_report_mfu_column_round_trip(monkeypatch, tmp_path):
    """Live-dump round trip: with the plane + tracer on, the chrome
    trace carries category-'efficiency' mfu counters and trace_report
    renders the mfu column (text + --json); a plane-off trace omits the
    column and the key entirely."""
    from mxnet_tpu import telemetry
    from tools import trace_report as tre

    monkeypatch.setenv("MXTPU_DEVICE_PEAK", "flops=1e12,bw=1e12")

    def dump(plane_on, name):
        if plane_on:
            monkeypatch.setenv("MXTPU_EFFICIENCY", "on")
        else:
            monkeypatch.delenv("MXTPU_EFFICIENCY", raising=False)
        telemetry.tracer.clear()
        telemetry.tracer.enable()
        try:
            _fit(_mlp(seed=5), steps=3, seed=5)
            path = str(tmp_path / name)
            telemetry.dump_chrome_trace(path)
        finally:
            telemetry.tracer.disable()
            telemetry.tracer.clear()
        with open(path) as f:
            telemetry.validate_chrome_trace(json.load(f))
        return path

    on_path = dump(True, "on.json")
    rows = tre.step_table(tre.load_events(on_path))
    mfu_rows = [r for r in rows if "mfu" in r]
    assert mfu_rows, "no mfu column in plane-on trace"
    assert all(r["mfu"] > 0 for r in mfu_rows)
    off_path = dump(False, "off.json")
    rows_off = tre.step_table(tre.load_events(off_path))
    assert all("mfu" not in r for r in rows_off)
    # text mode renders the column header only when the plane was on
    def header(stdout):
        return next(l for l in stdout.splitlines() if "wall_ms" in l)

    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_report.py"),
         on_path], capture_output=True, text=True, cwd=ROOT)
    assert proc.returncode == 0 and "mfu" in header(proc.stdout)
    proc_off = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_report.py"),
         off_path], capture_output=True, text=True, cwd=ROOT)
    assert proc_off.returncode == 0
    assert "mfu" not in header(proc_off.stdout)


# ------------------------------------------------- cost registry

def test_cost_registry_and_gauges(monkeypatch):
    monkeypatch.setenv("MXTPU_EFFICIENCY", "on")
    monkeypatch.setenv("MXTPU_DEVICE_PEAK", "flops=1e12,bw=1e12")
    _fit(_mlp(), steps=2)
    rows = eff.cost_report()
    assert rows and all(r["flops"] > 0 for r in rows)
    assert rows == sorted(rows, key=lambda r: -r["flops"])
    from mxnet_tpu.telemetry import default_registry
    g = default_registry().get("mxtpu_program_flops")
    assert g is not None and g.value > 0
    gm = default_registry().get("mxtpu_mfu")
    assert gm is not None and gm.value > 0


def test_run_compare_nan_candidate_regresses(tmp_path, capsys):
    """A candidate whose final loss diverged to NaN must FAIL the gate
    (NaN comparisons are all-False, which used to verdict 'ok'), and
    the text report must render it instead of crashing on int(NaN)."""
    from tools import run_compare as rc
    a = _synth_report(tmp_path / "a.json")
    b = _synth_report(tmp_path / "b.json",
                      loss={"last": float("nan")})
    capsys.readouterr()
    assert rc.main([a, b, "--json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["regressed"] == ["loss_last"]
    assert rc.main([a, b]) == 1  # text mode must not crash either
    # both-diverged is not a REGRESSION (baseline was already broken)
    a_nan = _synth_report(tmp_path / "a_nan.json",
                          loss={"last": float("nan")})
    assert rc.main([a_nan, b]) == 0


def test_note_without_open_step_window_is_dropped(monkeypatch):
    """A process that never opens a step window (bare Trainer loop /
    serving with the plane armed) must not accumulate notes — each one
    pins a compiled-program cache entry via its resolver closure."""
    monkeypatch.setenv("MXTPU_EFFICIENCY", "on")
    eff.reset_run()
    net = _mlp(seed=9)
    x = nd.array(np.random.RandomState(0).randn(4, 16).astype(np.float32))
    net(x)
    net(x)  # warm replays, no begin_step anywhere
    assert not eff.rollup()._notes


def test_run_report_valid_json_on_diverged_run(monkeypatch, tmp_path):
    """A diverged run (NaN losses — the exact case the artifact exists
    to catch) must still write RFC-valid JSON: no bare NaN tokens, the
    non-finite count surfaced, extrema over finite values only."""
    monkeypatch.setenv("MXTPU_RUN_REPORT_DIR", str(tmp_path))

    class R:
        status = "done"
        step = 3
        epoch = 1
        resumed_from = None
        skipped_steps = [1]
        loss_scale = 0.5
        losses = [2.0, float("nan"), float("inf")]
        step_breakdown = None
        memory = None
        comm_health = None
        numerics = {"grad_norm": float("nan"), "samples": 1,
                    "update_ratio": None, "nonfinite_steps": [1],
                    "loss_scale_events": []}
        efficiency = None

    path = rrmod.write_run_report(R())
    text = open(path).read()
    assert "NaN" not in text and "Infinity" not in text
    json.loads(text)  # strict-parses
    rep = rrmod.load_run_report(path)
    assert rep["loss"]["nonfinite"] == 2
    assert rep["loss"]["min"] == rep["loss"]["max"] == 2.0
    assert rep["loss"]["last"] is None  # was inf
    assert rep["numerics"]["grad_norm"] is None


def test_failed_resolution_cached_not_retried(monkeypatch):
    """A backend whose analyses are unavailable must cost ONE lower per
    signature, never one per step: _analyze_sig caches the failure
    (unavailable markers) and the resolver stops re-lowering."""
    calls = []
    real = grouped_mod._lower_sig

    def counting(sig, fn):
        calls.append(sig)
        return None  # analyses unavailable on this 'backend'

    monkeypatch.setattr(grouped_mod, "_lower_sig", counting)
    sig = ("SGD", (0.0, -1.0), True,
           ((( (3, 2), "float32"),),),
           (((3, 2), "float32"),))
    assert grouped_mod._analyze_sig(sig, None, need_cost=True) \
        .get("unavailable") is True
    assert grouped_mod._analyze_sig(sig, None, need_cost=True) \
        .get("unavailable") is True
    assert len(calls) == 1, "failed resolution re-lowered on retry"
    monkeypatch.setattr(grouped_mod, "_lower_sig", real)


def test_grouped_program_memory_gains_cost_fields(monkeypatch):
    """The grouped bucket record carries BOTH halves after the plane
    resolved it — one registry record, two analysis surfaces."""
    monkeypatch.setenv("MXTPU_EFFICIENCY", "on")
    _fit(_mlp(), steps=2)
    report = grouped_mod.program_memory()
    assert report
    assert any("flops" in st and st["argument_bytes"] > 0
               for st in report.values())
