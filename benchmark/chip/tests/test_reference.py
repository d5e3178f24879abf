"""The system against the plain references at a tiny size, on the CPU in
float32: the loss and every gradient agree; a reference built wrong on
purpose lies outside the tolerance the chip run holds the system to; and the
FLOP counts are the ones the papers quote."""
import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
CHIP = HERE.parent
sys.path[:0] = [str(CHIP), str(CHIP.parents[1])]
import run  # noqa: E402

# (configuration, image, batch): ResNet-50 needs 64 px for a last stage that
# is not 1x1, Inception-v3 its own 299 px for the 8x8 pooling
CASES = [("resnet50_v1", 64, 8), ("inception_v3", 299, 2)]


@pytest.fixture(scope="module", params=CASES, ids=[c[0] for c in CASES])
def case(request):
    """The zoo net's loss and gradients through autograd, and what the
    reference needs to compute the same."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    name, image, batch = request.param
    config = json.loads(
        (HERE / "rehearse" / "configs" / f"{name}.json").read_text())
    config["image"] = image
    common = run.load_module("paths", "common")
    net = common.make_net(config, seed=3)
    params = list(net.collect_params().values())
    initial = [p.data()._data for p in params]
    data, label = common.make_pool(
        config, {"pool": 1, "batch": batch}, 3, None, "float32")[0]
    with autograd.record():
        loss = gluon.loss.SoftmaxCrossEntropyLoss()(
            net(mx.nd.from_jax(data)), mx.nd.from_jax(label))
    loss.backward()  # of the sum over the batch
    grads = [np.asarray(p.grad()._data) / batch if p.grad_req != "null"
             else None for p in params]
    reference = run.load_module("models", name)
    return dict(config=config, reference=reference, initial=initial,
                data=data, label=label, grads=grads,
                loss=float(loss.mean().asscalar()), jax=jax)


def reference(case, fault=None):
    """The reference's loss, and for each trainable parameter the distance
    of the system's gradient from the reference's, |got - want| / |want|
    in the L2 norm."""
    loss, grads = case["jax"].jit(case["jax"].value_and_grad(
        lambda p: case["reference"].loss(
            p, case["data"], case["label"], case["config"], fault)))(
                case["initial"])
    errors = [np.linalg.norm(got - np.asarray(want))
              / (np.linalg.norm(np.asarray(want)) + 1e-30)
              for want, got in zip(grads, case["grads"]) if got is not None]
    return float(loss), np.array(errors)


def test_loss_and_every_gradient_agree(case):
    """The loss agrees to float32 rounding. The gradients cannot: float32
    through some fifty BatchNorms at random weights is ill-conditioned (each
    BatchNorm's backward pass is a difference of nearly equal terms).
    Against this reference run in float64 on ResNet-50 at this size, the
    reference in float32 lies 1.7% away at the median parameter and the
    system 4.8% (PR 25, CPU, values only), so two right float32 answers lie
    some 5% apart and a wrong model (next test) ten times that."""
    loss, errors = reference(case)
    assert loss == pytest.approx(case["loss"], rel=2e-4)
    assert len(errors) > 100
    assert np.median(errors) < 0.08 and errors.max() < 0.2, (
        np.median(errors), errors.max())


# the last residual add of ResNet-50's 16, the last concatenation of
# Inception-v3's 15 (the E blocks' inner fans count)
LAST_JOIN = {"resnet50_v1": 15, "inception_v3": 14}


@pytest.mark.parametrize("fault", ["running_stats", "drop_branch"])
def test_a_wrong_model_fails_the_comparison(case, fault):
    ref = case["reference"]
    if fault == "drop_branch":
        fault = (fault, LAST_JOIN[case["config"]["name"]])
    wrong, errors = reference(case, fault)
    distance = abs(wrong - case["loss"]) / (abs(case["loss"]) + 1)
    assert distance > 2 * ref.TOLERANCE, (wrong, case["loss"])
    assert np.median(errors) > 0.4, np.median(errors)


@pytest.mark.parametrize("name, gmacs", [("resnet50_v1", 3.86),
                                         ("inception_v3", 5.72)])
def test_flops_per_sample(name, gmacs):
    """He et al. give 3.8 G multiply-adds for ResNet-50 at 224 (the 4.1 G of
    other zoos is v1.5, stride on the 3x3), Szegedy et al. 5.7 G for
    Inception-v3 at 299. A training step is 2 FLOPs x 3 passes of that."""
    config = json.loads((CHIP / "configs" / f"{name}.json").read_text())
    flops = run.load_module("models", name).flops_per_sample(config)
    assert flops / 6 / 1e9 == pytest.approx(gmacs, rel=0.03)
