"""The FLOPs and bytes the attention kernels' rooflines and the step's MFU
rest on, by hand, and their readers on hand-made readings."""
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
CHIP = HERE.parent
sys.path.insert(0, str(CHIP))
import run  # noqa: E402

KERNELS = ("mx_attention_fwd", "mx_attention_dq", "mx_attention_dkv")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def costs_of(name):
    config = json.loads((CHIP / "configs" / f"{name}.json").read_text())
    reference = run.load_module("models", name)
    costs = getattr(reference, "kernel_costs", None) or run.load_module(
        "models", f"{name}_kernels").kernel_costs
    return config, reference, costs


def test_nemotron_kernel_costs_by_hand():
    config, reference, costs = costs_of("nemotron_3_nano_30b_a3b")
    # tests/test_nemotron_h.py holds the reference to having none
    assert not hasattr(reference, "kernel_costs")
    got = costs(config, 1)
    assert set(got) == set(KERNELS)
    t, q, kv = 8192, 32 * 8192, 2 * 8192
    # one call site, 32 heads, half of 8192^2, 128 + 128 wide
    assert got["mx_attention_fwd"][0] == 32 * t ** 2 * 256
    assert got["mx_attention_dq"][0] == 1.5 * got["mx_attention_fwd"][0]
    assert got["mx_attention_dkv"][0] == 2 * got["mx_attention_fwd"][0]
    # bf16 q and o of 32 heads, k and v of 2, a float32 log-sum-exp
    assert got["mx_attention_fwd"][1] == q * (2 * 256 + 4) + kv * 2 * 256
    # q, do and dq of 32 heads, k and v of 2, two float32 row vectors
    assert got["mx_attention_dq"][1] == q * (2 * 384 + 8) + kv * 2 * 256
    # q and do of 32 heads, k, v, dk and dv of 2
    assert got["mx_attention_dkv"][1] == q * (2 * 256 + 8) + kv * 2 * 512
    # twice the batch, twice the work
    assert costs(config, 2)["mx_attention_fwd"] == tuple(
        2 * x for x in got["mx_attention_fwd"])


def test_glm_kernel_costs_by_hand():
    config, _, costs = costs_of("glm_4_7_flash")
    got = costs(config, 1)
    # six call sites, 20 heads, half of 8192^2, 512 = 256 + 256 wide
    assert got["mx_attention_fwd"][0] == 6 * 20 * 8192 ** 2 * 512
    assert got["mx_attention_dkv"][0] == 2 * got["mx_attention_fwd"][0]


@pytest.mark.parametrize("name", ["nemotron_3_nano_30b_a3b", "glm_4_7_flash"])
@pytest.mark.parametrize("kernel", KERNELS)
def test_every_kernel_is_flop_bound_and_its_roofline_reads(name, kernel):
    config, reference, costs = costs_of(name)
    flops, nbytes = costs(config, 1)[kernel]
    least = flops / PEAKS["bf16_flops_per_s"]
    assert least > nbytes / PEAKS["hbm_bytes_per_s"]
    # a kernel that took three times its least time a step reads a third
    out = {"reference": reference, "config": config, "peaks": PEAKS,
           "traffic": {"batch": 1},
           "trace": {"steps": 4, "seconds_by_kind": {kernel: 12 * least}}}
    read = run.load_module("metrics", f"{kernel}_roofline").read
    assert read(out) == pytest.approx(100 / 3)
    # nothing to read: no such op, no trace, no peak
    assert read(dict(out, trace={"steps": 4, "seconds_by_kind": {}})) is None
    assert read(dict(out, trace=None)) is None
    assert read(dict(out, peaks=None)) is None


def test_a_reference_without_kernels_has_no_roofline():
    reference = run.load_module("models", "resnet50_v1")
    out = {"reference": reference, "config": {}, "peaks": PEAKS,
           "traffic": {"batch": 1},
           "trace": {"steps": 4, "seconds_by_kind": {"mx_attention_fwd": 1}}}
    assert run.load_module(
        "metrics", "mx_attention_fwd_roofline").read(out) is None


def test_glm_kernel_costs_are_of_a_step_trained_or_not():
    """The forward kernel runs once a step whether the step trains or
    scores, so its entry serves both kinds of path: the docstrings say "a
    step", and the reader asks the traffic for its batch alone."""
    _, _, costs = costs_of("glm_4_7_flash")
    assert "a step over all its call sites" in costs.__doc__
    assert "a training step" not in costs.__doc__
    header = run.load_module("metrics", "kernel_roofline").__doc__
    assert "in a step" in header and "training step" not in header
    # four sequences a step: four times one sequence's forward
    assert costs(json.loads((CHIP / "configs" / "glm_4_7_flash.json")
                            .read_text()), 4)["mx_attention_fwd"][0] \
        == 4 * 6 * 20 * 8192 ** 2 * 512


def test_mfu_counts_the_forward_once_where_the_path_does_not_train():
    """``flops_per_sample`` is forward x 3. A made-up run of the scoring
    traffic at 11 sequences a second reads 55% of the chip's peak; the same
    run read as training would read three times that, which no chip gives."""
    config, reference, _ = costs_of("glm_4_7_flash")
    mfu = run.load_module("metrics", "mfu")
    done = [i * 4 / 11.0 for i in range(20)]  # a batch of 4 every 364 ms
    scored = {"reference": reference, "config": config, "peaks": PEAKS,
              "cell": {"chips": 1}, "done": done,
              "traffic": json.loads((CHIP / "traffic"
                                     / "score_lm_s8192_b4.json").read_text())}
    assert scored["traffic"]["trains"] is False
    per_sample = reference.flops_per_sample(config)
    assert mfu.flops(scored) == per_sample / 3
    assert mfu.read(scored) == pytest.approx(
        100 * 11 * per_sample / 3 / 197e12)
    assert 50 < mfu.read(scored) < 100
    trained = dict(scored, traffic={"batch": 4})  # no "trains": it trains
    assert mfu.flops(trained) == per_sample
    assert mfu.read(trained) == pytest.approx(3 * mfu.read(scored))
    assert mfu.read(trained) > 100
    assert mfu.read(dict(scored, peaks=None)) is None
