"""``correct`` of the one-head scoring path (``paths/score_causal_lm.py``) on
the ``olmo_hybrid_7b`` configuration, driven through ``run.main`` on the CPU
at the toy size of ``tests/rehearse_42``: the sound path reads true; the
control (every weight matrix rounded to 3 mantissa bits) and two faults
planted in the delta rule itself read false, by ``outputs_match``. The
chip's control is ``tests/control_42``: the published configuration and the
cell's traffic but for the rounding."""
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
import run  # noqa: E402

SCORED = "olmo_hybrid_7b_score_s8k_b1"
CHECKS = {"reference", "no_step_failed", "same_every_pass", "outputs_match",
          "on_device", "no_compile_in_window", "known_device"}


def drive(monkeypatch, capsys, workload=SCORED, broken=None):
    """One run of ``run.main`` on the CPU; the result line."""
    real = run.load_module

    def load(kind, name):
        module = real(kind, name)
        if kind == "paths" and broken:
            module.Path = broken(module.Path)
        return module

    monkeypatch.setattr(run, "load_module", load)
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--rehearse", str(HERE / "rehearse_42"), "--workload",
        workload, "--seed", str(2**31 + 42), "--seconds", "4", "--trace",
        "0"])
    run.main()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line["checks"]) == CHECKS
    assert line["correct"] is all(line["checks"].values())
    return line


def failed_checks(line):
    return {name for name, ok in line["checks"].items() if not ok}


def test_the_sound_path_is_correct(monkeypatch, capsys):
    line = drive(monkeypatch, capsys)
    assert line["correct"] is True and failed_checks(line) == set()
    numbers = line["compared"]
    assert list(numbers) == [
        "first_step_loss_gap", "steps_failed", "loss_change_between_passes",
        "logits_gap.head0", "sequence_loss_gap", "compiled_in_window"]
    number, limit = numbers["logits_gap.head0"]
    assert 0 < number < limit / 2
    assert numbers["sequence_loss_gap"][0] < 1e-6


def beta_not_doubled(Path):
    """``linear_allow_neg_eigval`` ignored: beta in (0, 1), a transition
    without negative eigenvalues. The reference still doubles it."""
    class Broken(Path):
        def __init__(self, config, traffic, seed, devices):
            super().__init__(dict(config, linear_allow_neg_eigval=False),
                             traffic, seed, devices)
    return Broken


def decay_left_out(monkeypatch, request):
    """The delta rule with alpha = 1: the decay's log set to 0 where the
    mixer hands it to the rule. The programs traced before and after the
    fault are dropped, so that none is taken from the other's trace."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import lm_ops
    whole = lm_ops.gated_delta_rule_chunked
    monkeypatch.setattr(
        lm_ops, "gated_delta_rule_chunked",
        lambda q, k, v, g, beta, *args, **kw: whole(
            q, k, v, jnp.zeros_like(g), beta, *args, **kw))
    jax.clear_caches()
    request.addfinalizer(jax.clear_caches)


# the first step's loss moves by more than the reference check's 5e-3 at
# the toy size on some seeds: ``reference`` may fail besides
@pytest.mark.parametrize("fault", ["beta_not_doubled", "decay_left_out"])
def test_a_fault_in_the_rule_reads_not_correct(monkeypatch, capsys, request,
                                              fault):
    if fault == "decay_left_out":
        decay_left_out(monkeypatch, request)
        line = drive(monkeypatch, capsys)
    else:
        line = drive(monkeypatch, capsys, broken=beta_not_doubled)
    assert line["correct"] is False, line["compared"]
    assert "outputs_match" in failed_checks(line) <= {"outputs_match",
                                                       "reference"}
    number, limit = line["compared"]["logits_gap.head0"]
    assert number > 1.5 * limit, (number, limit)
    assert line["failed"] == 0 and line["attempted"] >= 16


def test_the_control_reads_not_correct(monkeypatch, capsys):
    line = drive(monkeypatch, capsys,
                 workload="olmo_hybrid_7b_score_mantissa3")
    assert line["correct"] is False
    assert "outputs_match" in failed_checks(line) <= {"outputs_match",
                                                       "reference"}
    number, limit = line["compared"]["logits_gap.head0"]
    assert number > 1.5 * limit, (number, limit)


def test_the_control_on_the_chip_is_the_cell_with_rounded_weights():
    chip, control = HERE.parent, HERE / "control_42"
    name = "olmo_hybrid_7b"
    assert (control / "configs" / f"{name}.json").read_text() \
        == (chip / "configs" / f"{name}.json").read_text()
    cell, = json.loads((control / "workloads.json").read_text())
    real = next(w for w in json.loads(
        (chip.parents[1] / "BENCHMARK.json").read_text())["workloads"]
        if w["name"] == SCORED)
    assert (cell["config"], cell["chips"]) == (real["config"], real["chips"])
    rounded = json.loads(
        (control / "traffic" / f"{cell['traffic']}.json").read_text())
    plain = json.loads(
        (chip / "traffic" / f"{real['traffic']}.json").read_text())
    assert rounded.pop("weights_mantissa_bits") == 3
    for key in ("name", "notes"):
        rounded.pop(key), plain.pop(key)
    assert rounded == plain and plain["trains"] is False
    assert set(plain["limits"]) == {"logits_gap.head0", "sequence_loss_gap"}
