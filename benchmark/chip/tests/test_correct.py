"""``correct`` of a path that does not train, driven through ``run.main``
itself on the CPU at the toy size (``--rehearse`` skips the look for a chip
and nothing else), with the timed path broken underneath: each fault the
cell can have reads ``correct: false``, by the check meant for it. The
control (every weight matrix rounded to 3 mantissa bits) is a traffic file
of the rehearsal directory, as it is on the chip at the published sizes
(``tests/control_36``)."""
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
import run  # noqa: E402

SCORED = "glm_4_7_flash_score_s8k_b4"
CHECKS = {"reference", "no_step_failed", "same_every_pass", "outputs_match",
          "on_device", "no_compile_in_window", "known_device"}


def bias_moves(Path):
    """The inference branch keeps state it should not: after the first pass
    over the pool the last expert layer's selection bias moves, as a
    training forward moves it."""
    from mxnet_tpu.gluon.model_zoo.text.glm_moe_lite import DroplessMoE

    class Broken(Path):
        def __init__(self, *args):
            super().__init__(*args)
            self.bias = max(DroplessMoE.instances,
                            key=lambda m: m.bias.name).bias
            # made here: nothing may compile in the window
            self.moved = nd(self.bias.data()._data.at[::2].add(0.02))

        def dispatch(self, i):
            if i == len(self.pool):
                self.bias.set_data(self.moved)
            return super().dispatch(i)
    return Broken


def breaks(name):
    """Makes of ``broken(whole, *args)`` a fault: a Path whose
    ``<name>(*args)`` is ``broken(<name>, *args)`` while a step is issued."""
    def fault(broken):
        def of(Path):
            class Broken(Path):
                def dispatch(self, i):
                    whole = getattr(self, name)
                    setattr(self, name, lambda *args: broken(whole, *args))
                    try:
                        return super().dispatch(i)
                    finally:
                        setattr(self, name, whole)
            return Broken
        of.__name__, of.__doc__ = broken.__name__, broken.__doc__
        return of
    return fault


def nd(array):
    from mxnet_tpu.ndarray.ndarray import from_jax
    return from_jax(array)


@breaks("loss")
def sequence_left_out(loss, heads, labels):
    """The last sequence's logits do not reach the loss: zeros do."""
    return loss([nd(h._data.at[-1].set(0)) for h in heads], labels)


@breaks("loss")
def positions_left_out(loss, heads, labels):
    """The loss takes its mean over the first half of the positions."""
    t = labels.shape[1] // 2
    return loss([h[:, :t] for h in heads], labels[:, :t])


@breaks("net")
def answer_altered(net, tokens):
    """One answer is altered where it is produced: the second head returns
    the first sequence's logits for the second sequence too."""
    main, mtp = net(tokens)
    return main, nd(mtp._data.at[1].set(mtp._data[0]))


def half_the_batch(Path):
    """Half of the batch is left out, the mean taken over the rest."""
    class Broken(Path):
        def __init__(self, config, traffic, seed, devices):
            super().__init__(config, traffic, seed, devices)
            half = traffic["batch"] // 2
            self._batches = [(t[:half], l[:half]) for t, l in self._batches]
    return Broken


def drive(monkeypatch, capsys, workload=SCORED, broken=None, trace=0):
    """One run of ``run.main`` on the CPU; the result line."""
    real = run.load_module

    def load(kind, name):
        module = real(kind, name)
        if kind == "paths" and broken:
            module.Path = broken(module.Path)
        return module

    monkeypatch.setattr(run, "load_module", load)
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--rehearse", str(HERE / "rehearse_36"), "--workload",
        workload, "--seed", str(2**31 + 36), "--seconds", "4", "--trace",
        str(trace)])
    run.main()
    said = capsys.readouterr()
    line = json.loads(said.out.strip().splitlines()[-1])
    # each number beside its limit: last in the line, last on standard error
    assert list(line)[-1] == "compared"
    assert [l.split()[1].rstrip(":") for l in said.err.splitlines()
            if l.startswith("compared ")] == list(line["compared"])
    return line


def failed_checks(line):
    assert set(line["checks"]) == CHECKS
    assert line["correct"] is all(line["checks"].values())
    return {name for name, ok in line["checks"].items() if not ok}


def test_the_sound_path_is_correct(monkeypatch, capsys):
    line = drive(monkeypatch, capsys)
    assert line["correct"] is True and failed_checks(line) == set()
    numbers = line["compared"]
    assert list(numbers) == [
        "first_step_loss_gap", "steps_failed", "loss_change_between_passes",
        "logits_gap.head0", "logits_gap.head1", "sequence_loss_gap",
        "compiled_in_window"]
    assert numbers["loss_change_between_passes"] == [0.0, 0]
    for name in ("logits_gap.head0", "logits_gap.head1"):
        number, limit = numbers[name]
        assert 0 < number < limit, (name, number, limit)
    # the same float32 arithmetic on the same logits, on a CPU
    assert numbers["sequence_loss_gap"][0] < 1e-6


# (the fault, the checks that have to fail, those that may besides: at the
# toy size a mean over 128 positions moves the first step's loss by more
# than the reference check's 5e-3 on some seeds)
FAULTS = [
    (bias_moves, {"same_every_pass"}, set()),
    (sequence_left_out, {"outputs_match"}, {"reference"}),
    (positions_left_out, {"outputs_match"}, {"reference"}),
    (half_the_batch, {"outputs_match"}, {"reference"}),
    (answer_altered, {"outputs_match"}, set()),
]


@pytest.mark.parametrize("broken, by, besides", FAULTS,
                         ids=[f[0].__name__ for f in FAULTS])
def test_a_planted_fault_reads_not_correct(monkeypatch, capsys, broken, by,
                                           besides):
    line = drive(monkeypatch, capsys, broken=broken)
    assert line["correct"] is False, line["compared"]
    assert by <= failed_checks(line) <= by | besides, line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 16
    if broken is half_the_batch:  # no shape of the reference's: no number
        assert line["compared"]["logits_gap.head0"][0] is None


def test_the_control_reads_not_correct_by_outputs_match_alone(
        monkeypatch, capsys):
    line = drive(monkeypatch, capsys, workload="glm_4_7_flash_score_mantissa3")
    assert line["correct"] is False
    assert failed_checks(line) == {"outputs_match"}
    for head in ("logits_gap.head0", "logits_gap.head1"):
        number, limit = line["compared"][head]
        assert number > 1.5 * limit, (head, number, limit)


def test_the_control_on_the_chip_is_the_cell_with_rounded_weights():
    """``tests/control_36`` is what ``run.py --rehearse`` takes on the chip:
    the published configuration, and the cell's traffic but for the
    rounding."""
    chip, control = HERE.parent, HERE / "control_36"
    name = "glm_4_7_flash"
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
