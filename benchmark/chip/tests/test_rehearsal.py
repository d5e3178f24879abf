"""Every (path, configuration) pair end to end on the CPU at a tiny size,
through ``run.py --rehearse``: the control flow, the contract of the last
line, and the refusal to measure without a TPU. No timing read here means
anything."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[2]
RUN = [sys.executable, str(HERE.parent / "run.py")]
# (rehearsal directory, cell): every path on a configuration of its own kind
CELLS = [(d, cell) for d in ("rehearse", "rehearse_36")
         for cell in json.loads((HERE / d / "workloads.json").read_text())
         if "mantissa3" not in cell["name"]]  # the control: test_correct.py
REAL = json.loads((ROOT / "BENCHMARK.json").read_text())
# what correct rests on, by kind of path: names and order
CHECKS = {
    True: ["reference", "no_step_failed", "loss_fell", "on_device",
           "no_compile_in_window", "known_device"],
    False: ["reference", "no_step_failed", "same_every_pass",
            "outputs_match", "on_device", "no_compile_in_window",
            "known_device"]}
COMPARED = {
    True: ["first_step_loss_gap", "steps_failed",
           "last_pass_over_first_pass_loss", "compiled_in_window"],
    False: ["first_step_loss_gap", "steps_failed",
            "loss_change_between_passes", "logits_gap.head0",
            "logits_gap.head1", "sequence_loss_gap", "compiled_in_window"]}


def run(args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(RUN + args, env=env, capture_output=True, text=True,
                          timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("where, cell", CELLS, ids=[c["name"] for _, c in CELLS])
def test_cell_end_to_end(where, cell, trace):
    traffic = json.loads((HERE / where / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    trains = traffic.get("trains", True)
    if trace and trains and cell["config"] != "resnet50_v1":
        pytest.skip("the traced flow is the same for every configuration")
    done = run(["--rehearse", str(HERE / where), "--workload",
                cell["name"], "--seed", str(2**31 + 11), "--seconds", "8",
                "--trace", str(trace)], devices=cell["chips"])
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True, line
    # a path that trains is held to what it was held to before there was
    # another kind, under the same names; one that does not, to its own
    assert list(line["checks"]) == CHECKS[trains]
    assert list(line["compared"]) == COMPARED[trains]
    assert line["failed"] == 0 and line["attempted"] >= 4
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == cell["chips"]
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    wanted = {m["name"] for m in REAL["per_layer" if trace else "end_to_end"]
              if cell["name"] in m.get("workloads", [cell["name"]])}
    # a CPU has no device plane in its trace and no peak in the table: the
    # readers of those return nothing and the line leaves them out
    assert set(line["metrics"]) <= wanted
    assert set(line["metrics"]) >= (
        {"host_dispatch_ms"} if trace else wanted)
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] > 0, (name, m)
    assert not (ROOT / ".bench_trace" / cell["name"]).exists()


@pytest.mark.parametrize("trace", [0, 1])
def test_fitloop_with_one_step_in_flight(trace):
    """``FitLoop`` on its own thread, the harness one batch ahead and no
    more: the last wait leaves nothing in flight, and the loop drains with
    no failure. The loss scale stays 1, so nothing compiles in the window."""
    done = run(["--rehearse", str(HERE / "rehearse_35"), "--workload",
                "resnet50_train_fitloop", "--seed", str(2**31 + 35),
                "--seconds", "8", "--trace", str(trace)])
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] >= 4
    assert line["compiled_in_window"] == 0
    assert set(line["metrics"]) == (
        {"host_dispatch_ms"} if trace else {"samples_per_s", "setup_s"})
    # the numbers correct rests on, each beside its limit: last in the
    # line, and the last lines of standard error
    assert list(line)[-1] == "compared"
    said = [l for l in done.stderr.splitlines() if l.startswith("compared ")]
    assert done.stderr.splitlines()[-len(said):] == said
    assert [l.split()[1].rstrip(":") for l in said] == list(line["compared"])
    for (number, limit), l in zip(line["compared"].values(), said):
        assert l.split()[2:] == [str(number), "limit", str(limit)]


def test_no_tpu_no_result():
    done = run(["--workload", REAL["workloads"][0]["name"], "--seed", "1",
                "--seconds", "1", "--trace", "0"])
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "no TPU" in done.stderr


def test_benchmark_json_resolves_to_files():
    """Every name in BENCHMARK.json is a file under benchmark/chip. (A file
    may wait there for its cell: PERF.md section 7.)"""
    chip = HERE.parent
    assert REAL["paths"] == [str(chip.relative_to(ROOT))]
    for c in REAL["configs"]:
        assert c["file"] == f"{REAL['paths'][0]}/configs/{c['name']}.json"
        config = json.loads((ROOT / c["file"]).read_text())
        assert config["reduced"] == c["reduced"]
        assert (chip / "models" / f"{c['name']}.py").exists()
    for w in REAL["workloads"]:
        traffic = json.loads(
            (chip / "traffic" / f"{w['traffic']}.json").read_text())
        assert traffic["chips"] == w["chips"]
        assert (chip / "paths" / f"{traffic['path']}.py").exists()
    assert {m["name"] for m in REAL["per_layer"] + REAL["end_to_end"]} <= {
        p.name[:-3] for p in (chip / "metrics").glob("*.py")}
    e2e = {m["name"] for m in REAL["end_to_end"]}
    assert all(m["moves"] in e2e for m in REAL["per_layer"])
    # a metric lists cells that exist, and every cell reports a layer metric
    cells = [w["name"] for w in REAL["workloads"]]
    for m in REAL["per_layer"] + REAL["end_to_end"]:
        assert set(m.get("workloads", cells)) <= set(cells), m["name"]
    assert all(any(c in m.get("workloads", cells) for m in REAL["per_layer"])
               for c in cells)


def test_the_cells_and_metrics_of_pr_35():
    cells = {w["name"]: w for w in REAL["workloads"]}
    scored = cells.pop("glm_4_7_flash_score_s8k_b4")  # PR 36: the next test
    assert len(cells) == 7
    assert [w["name"] for w in cells.values() if w["chips"] == 4] \
        == ["resnet50_train_dp4"]
    fitloop = cells["resnet50_train_fitloop"]
    assert (fitloop["config"], fitloop["traffic"], fitloop["chips"]) \
        == ("resnet50_v1", "fitloop_b256", 1)
    layer = {m["name"]: m for m in REAL["per_layer"]}
    assert not {"step_ms_p95.gluon", "host_other_ms"} & set(layer)
    lang = ["glm_4_7_flash_train_spmd_s8k", "nemotron_3_nano_train_spmd_s8k"]
    scopes = {"mx_mamba2_ms": lang[1:], "mx_ssd_ms": lang[1:],
              "mx_gqa_ms": lang[1:], "mx_mla_ms": lang[:1],
              "mx_moe_ms": lang, "scope_rest_ms": lang}
    for name, where in scopes.items():
        m = layer[name]
        listed = [w for w in m["workloads"] if w != scored["name"]]
        assert (listed, m["unit"], m["better"], m["source"]) \
            == (where, "ms", "lower", "device_trace"), name
    for kernel in ("fwd", "dq", "dkv"):
        assert [w for w in layer[f"mx_attention_{kernel}_roofline"][
            "workloads"] if w != scored["name"]] == lang
    tail = next(m for m in REAL["end_to_end"] if m["name"] == "step_ms_p95")
    # the device-paced cells; not the one the host paces, nor a language cell
    assert tail["workloads"] == [
        "resnet50_train_spmd", "inception_v3_train_spmd",
        "resnet50_train_gluon", "resnet50_train_dp4"]
    # FitLoop closes its steps without Trainer.step: no span says how many
    # programs it launched, and a metric with no list must read everywhere
    assert "resnet50_train_fitloop" not in layer[
        "dispatches_per_step"]["workloads"]


def test_the_cell_of_pr_36():
    """One cell more, forward only, on lists that existed: no configuration,
    no end-to-end metric and no bound is new."""
    cells = {w["name"]: w for w in REAL["workloads"]}
    assert len(cells) == 8
    assert [w["name"] for w in cells.values() if w["chips"] == 4] \
        == ["resnet50_train_dp4"]
    scored = cells["glm_4_7_flash_score_s8k_b4"]
    assert (scored["config"], scored["traffic"], scored["chips"]) \
        == ("glm_4_7_flash", "score_lm_s8192_b4", 1)
    assert len(scored["why"]) <= 200
    traffic = json.loads((HERE.parent / "traffic"
                          / "score_lm_s8192_b4.json").read_text())
    assert traffic["trains"] is False and traffic["path"] == "score_lm"
    assert set(traffic["limits"]) == {
        "logits_gap.head0", "logits_gap.head1", "sequence_loss_gap"}
    # every other traffic file trains, and says so by saying nothing
    assert all("trains" not in json.loads(f.read_text())
               for f in (HERE.parent / "traffic").glob("*.json")
               if f.stem != "score_lm_s8192_b4")
    listing = {m["name"] for m in REAL["per_layer"]
               if scored["name"] in m.get("workloads", [scored["name"]])}
    assert listing == {
        "host_dispatch_ms", "programs_per_step", "loop_fusion_share",
        "device_idle", "mfu", "mx_attention_fwd_roofline", "mx_mla_ms",
        "mx_moe_ms", "scope_rest_ms"}
    assert {m["name"]: m["bound"] for m in REAL["end_to_end"]} == {
        "samples_per_s": 0.025, "step_ms_p95": 0.01, "setup_s": 0.1}
    tail = next(m for m in REAL["end_to_end"] if m["name"] == "step_ms_p95")
    assert scored["name"] not in tail["workloads"]
    assert [c["name"] for c in REAL["configs"]] == [
        "resnet50_v1", "inception_v3", "glm_4_7_flash",
        "nemotron_3_nano_30b_a3b"]
