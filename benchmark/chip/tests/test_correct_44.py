"""``correct`` of the scoring path whose reference is handed the served
weights (``paths/score_causal_lm_rounded_ref.py``) on the ``mimo_v2_5``
configuration, driven through ``run.main`` on the CPU at the toy size of
``tests/rehearse_44``: the sound path reads true in bf16 and in float32; the
control (every weight matrix rounded to 3 mantissa bits) reads false; and
five faults planted in the attention layers read false by
``outputs_match`` in float32, where the program and the reference agree to
rounding. The chip's control is ``tests/control_44``: the published
configuration and the cell's traffic but for the rounding."""
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
import run  # noqa: E402

SCORED = "mimo_v2_5_score_s32k_b1"
EXACT = "mimo_v2_5_score_f32"
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
        "run.py", "--rehearse", str(HERE / "rehearse_44"), "--workload",
        workload, "--seed", str(2**31 + 44), "--seconds", "3", "--trace",
        "0"])
    run.main()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line["checks"]) == CHECKS
    assert line["correct"] is all(line["checks"].values())
    return line


def failed_checks(line):
    return {name for name, ok in line["checks"].items() if not ok}


@pytest.mark.parametrize("workload", [SCORED, EXACT])
def test_the_sound_path_is_correct(monkeypatch, capsys, workload):
    line = drive(monkeypatch, capsys, workload)
    assert line["correct"] is True and failed_checks(line) == set()
    numbers = line["compared"]
    assert list(numbers) == [
        "first_step_loss_gap", "steps_failed", "loss_change_between_passes",
        "logits_gap.head0", "sequence_loss_gap", "compiled_in_window"]
    number, limit = numbers["logits_gap.head0"]
    # bf16 at the toy size reads 0.02 - 0.04: a routing choice that flips
    # on rounding moves a token's whole expert output
    assert 0 < number < (limit if workload == SCORED else limit / 100)
    assert numbers["sequence_loss_gap"][0] < 1e-6


def with_config(**changed):
    """A path whose net is built from the configuration with ``changed``
    keys; the reference still reads the configuration as it is."""
    def broken(Path):
        class Broken(Path):
            def __init__(self, config, traffic, seed, devices):
                super().__init__(dict(config, **{
                    key: value(config) for key, value in changed.items()}),
                    traffic, seed, devices)
        return Broken
    return broken


FAULTS = {
    "window_off_by_one": with_config(
        sliding_window=lambda c: c["sliding_window"] + 1),
    "rotary_over_the_whole_head": with_config(
        partial_rotary_factor=lambda c: 1.0),
    "thetas_swapped": with_config(rope_theta=lambda c: c["swa_rope_theta"],
                                  swa_rope_theta=lambda c: c["rope_theta"]),
    "value_scale_left_out": with_config(
        attention_value_scale=lambda c: 1.0),
}


def sink_left_out(monkeypatch, request):
    """The window layers' sink dropped where the layer hands it to the
    attention: the parameters are there, the softmax never sees them. The
    programs traced before and after the fault are dropped, so that none is
    taken from the other's trace."""
    import jax
    from mxnet_tpu.ops import lm_ops
    whole = lm_ops.fused_qkv_attention
    monkeypatch.setattr(
        lm_ops, "fused_qkv_attention",
        lambda x, w_qkv, w_o, *sink, **kw: whole(x, w_qkv, w_o, **kw))
    jax.clear_caches()
    request.addfinalizer(jax.clear_caches)


# the first step's loss may move by more than the reference check's 5e-3
# at the toy size: ``reference`` may fail besides
@pytest.mark.parametrize("fault", ["sink_left_out"] + list(FAULTS))
def test_a_fault_in_attention_reads_not_correct(monkeypatch, capsys, request,
                                                fault):
    if fault == "sink_left_out":
        sink_left_out(monkeypatch, request)
        line = drive(monkeypatch, capsys, EXACT)
    else:
        line = drive(monkeypatch, capsys, EXACT, broken=FAULTS[fault])
    assert line["correct"] is False, line["compared"]
    assert "outputs_match" in failed_checks(line) <= {"outputs_match",
                                                       "reference"}
    number, limit = line["compared"]["logits_gap.head0"]
    assert number > 10 * limit, (number, limit)
    assert line["failed"] == 0


def test_the_control_reads_not_correct(monkeypatch, capsys):
    line = drive(monkeypatch, capsys, workload="mimo_v2_5_score_mantissa3")
    assert line["correct"] is False
    assert "outputs_match" in failed_checks(line) <= {"outputs_match",
                                                       "reference"}
    number, limit = line["compared"]["logits_gap.head0"]
    assert number > 1.3 * limit, (number, limit)


def test_the_control_on_the_chip_is_the_cell_with_rounded_weights():
    chip, control = HERE.parent, HERE / "control_44"
    name = "mimo_v2_5"
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
    assert plain["path"] == "score_causal_lm_rounded_ref"
    assert set(plain["limits"]) == {"logits_gap.head0", "sequence_loss_gap"}
