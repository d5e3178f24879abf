"""Smoke-run the detection examples end to end (ref: the reference CI runs
example trees via ci/docker/runtime_functions.sh tutorialtest)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, args, timeout=600):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, script)] + args,
        capture_output=True, text=True, timeout=timeout, env=env)


@pytest.mark.slow
def test_rcnn_example_learns():
    r = _run("examples/rcnn/train_rcnn.py",
             ["--iters", "6", "--batch-size", "4"])
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [l for l in r.stdout.splitlines() if l.startswith("iter")]
    assert len(lines) == 6
    first = float(lines[0].split("loss")[1].split()[0])
    last = float(lines[-1].split("loss")[1].split()[0])
    assert last < first, (first, last)


@pytest.mark.slow
def test_ssd_example_runs():
    r = _run("examples/ssd/train_ssd.py", ["--iters", "3"])
    assert r.returncode == 0, r.stderr[-2000:]


@pytest.mark.slow
def test_fleet_demo_example_smoke():
    """The fleet-serving walkthrough (examples/serving/fleet_demo.py):
    publish v1, serve, publish v2 + AOT bundle, hot-swap under load with
    a monotone version-tag timeline and zero errors, roll back. Slow
    tier: every invariant it asserts is also covered in-process by
    tests/test_serving_fleet.py (tier-1) — this run exercises the
    example script itself."""
    r = _run("examples/serving/fleet_demo.py",
             ["--smoke", "--requests", "120"], timeout=300)
    assert r.returncode == 0, (r.stdout + r.stderr)[-1500:]
    assert "SMOKE OK" in r.stdout


@pytest.mark.heavy
def test_tpu_fast_training_example(tmp_path):
    """The round-2 fast-training recipe (run_steps + DeviceStagingIter +
    async checkpoints + remat) runs end to end."""
    r = _run("examples/tpu_fast_training.py",
             ["--batch-size", "4", "--fused-steps", "2",
              "--image-size", "32", "--num-batches", "3", "--remat",
              "--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-every", "2"])
    out = r.stdout + r.stderr
    assert r.returncode == 0, out[-2000:]
    assert "img/s" in r.stdout
    # 3 outer batches of 2 fused steps, saving at i%2==1 -> exactly [4]
    assert "checkpoints: [4]" in r.stdout, r.stdout[-500:]


@pytest.mark.slow
def test_long_context_ring_attention_example_learns():
    """dp x sp mesh training with ring attention converges (the
    long-context recipe; examples/long_context/train_long_context.py)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "examples/long_context/train_long_context.py"),
         "--steps", "25", "--seq-len", "128"],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-500:]
    import re
    m = re.search(r"done \(loss ([\d.]+) -> ([\d.]+)\)", r.stdout)
    assert m, r.stdout[-300:]
    first, last = float(m.group(1)), float(m.group(2))
    assert last < first * 0.5, (first, last)


@pytest.mark.slow
def test_quantize_gluon_example_accuracy_delta():
    """The Gluon int8 flow example: trains to convergence, quantizes with
    calibration, asserts top-1 delta <=1% (VERDICT r3 item 2)."""
    r = _run("examples/quantization/quantize_gluon.py", ["--epochs", "30"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "quantize_gluon done" in r.stdout
    delta = [l for l in r.stdout.splitlines() if "delta" in l][0]
    assert abs(float(delta.split("delta")[1].strip(" )+"))) <= 0.01


@pytest.mark.slow
def test_ctc_example_learns():
    """CTC loss must collapse by >5x within a short run (full sequence
    accuracy needs ~400 iters; the smoke bar is learning, like rcnn's)."""
    r = _run("examples/ctc/lstm_ocr.py", ["--iters", "60"])
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [l for l in r.stdout.splitlines() if "ctc-loss" in l]
    first = float(lines[0].split("ctc-loss")[1])
    last = float(lines[-1].split("ctc-loss")[1])
    assert last < first / 5, (first, last)


@pytest.mark.slow
def test_nce_example_retrieves_pairs():
    r = _run("examples/nce_loss/wordvec_nce.py", ["--iters", "200"])
    assert r.returncode == 0, r.stderr[-2000:]
    acc = float(r.stdout.splitlines()[-1].split(":")[1])
    assert acc >= 0.8, acc


@pytest.mark.slow
def test_recommender_example_sparse_path_and_learns():
    # ~540 s standalone on this box: needs headroom over the default
    # 600 s budget when the suite loads all cores (it timed out flakily
    # at 600 in a full-suite run)
    r = _run("examples/recommenders/matrix_fact_sparse.py",
             ["--iters", "150", "--users", "800", "--items", "400",
              "--batch-size", "1024", "--lr", "0.02"], timeout=1200)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "grad stype=row_sparse" in r.stdout
    rmse = float(r.stdout.splitlines()[-1].split("RMSE:")[1].split()[0])
    assert rmse < 0.3, rmse  # planted-structure RMSE -> noise floor 0.1


@pytest.mark.slow
def test_text_cnn_example_learns():
    """Kim-style multi-width conv text classifier on planted-keyword
    sentences: must clearly beat chance on held-out data."""
    r = _run("examples/cnn_text_classification/text_cnn.py",
             ["--iters", "120"])
    assert r.returncode == 0, r.stderr[-2000:]
    acc = float(r.stdout.splitlines()[-1].split(":")[1])
    assert acc >= 0.8, acc


@pytest.mark.slow
def test_deepspeech_example_learns():
    """DeepSpeech-lite (conv stem + BiGRU + CTC over length buckets):
    CTC loss must collapse and held-out phoneme error rate go low."""
    r = _run("examples/speech_recognition/deepspeech.py", ["--iters", "40"])
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [l for l in r.stdout.splitlines() if "ctc-loss" in l]
    first = float(lines[0].split("ctc-loss")[1])
    last = float(lines[-1].split("ctc-loss")[1])
    assert last < first / 5, (first, last)
    per = float(r.stdout.splitlines()[-1].split(":")[1])
    assert per < 0.3, per


@pytest.mark.slow
def test_dqn_example_learns():
    """DQN on Catch (imperative rollouts + replay + target net): greedy
    policy must catch most balls; random play catches ~1/6."""
    r = _run("examples/reinforcement_learning/dqn.py",
             ["--episodes", "300"])
    assert r.returncode == 0, r.stderr[-2000:]
    rate = float(r.stdout.splitlines()[-1].split(":")[1])
    assert rate >= 0.7, rate


@pytest.mark.slow
def test_autoencoder_example_learns():
    """Conv autoencoder (NHWC Conv2DTranspose decoder): reconstruction
    error must fall well below input variance and the bottleneck must
    stay linearly class-separable (probe >> 10% chance)."""
    r = _run("examples/autoencoder/conv_autoencoder.py", ["--iters", "150"])
    assert r.returncode == 0, r.stderr[-2000:]
    tail = r.stdout.splitlines()[-1]
    mse = float(tail.split("recon-mse")[1].split()[0])
    var = float(tail.split("input-var")[1].split()[0])
    probe = float(tail.split("probe accuracy:")[1])
    assert mse < var / 4, (mse, var)
    assert probe >= 0.3, probe


@pytest.mark.slow
def test_ner_example_learns():
    """BiLSTM NER tagger: entity F1 on held-out sentences; the
    trigger-word construction makes context (the BiLSTM) mandatory."""
    r = _run("examples/named_entity_recognition/ner_bilstm.py",
             ["--iters", "120"])
    assert r.returncode == 0, r.stderr[-2000:]
    f1 = float(r.stdout.splitlines()[-1].split("entity F1:")[1])
    assert f1 >= 0.7, f1


@pytest.mark.slow
def test_fgsm_example_attacks_succeed():
    """FGSM (input-gradient attack): the model must be accurate on clean
    data and collapse under eps-sign perturbation — proves grads w.r.t.
    non-parameter inputs flow through the tape."""
    r = _run("examples/adversary/fgsm.py", ["--iters", "120"])
    assert r.returncode == 0, r.stderr[-2000:]
    tail = r.stdout.splitlines()[-1]
    clean = float(tail.split("clean accuracy")[1].split()[0])
    adv = float(tail.split("adversarial accuracy:")[1].split()[0])
    assert clean >= 0.8, clean
    assert adv < clean / 2, (clean, adv)


@pytest.mark.slow
def test_vae_example_learns():
    """VAE: ELBO collapses and prior samples emit sparse digit-like
    mass (reparameterized sampling under the autograd tape)."""
    r = _run("examples/vae/vae.py", ["--iters", "200"])
    assert r.returncode == 0, r.stderr[-2000:]
    tail = r.stdout.splitlines()[-1]
    first = float(tail.split("first-loss")[1].split()[0])
    final = float(tail.split("final-loss")[1].split()[0])
    on = float(tail.split("gen-on-fraction")[1])
    assert final < first / 3, (first, final)
    assert 0.03 < on < 0.6, on


@pytest.mark.slow
def test_fcn_segmentation_example_learns():
    """FCN-8s-style segmentation (NHWC deconv upsampling + skip fuse):
    mean foreground IoU is the task's metric."""
    r = _run("examples/fcn_xs/fcn_seg.py", ["--iters", "150"])
    assert r.returncode == 0, r.stderr[-2000:]
    iou = float(r.stdout.splitlines()[-1].split("mean IoU:")[1])
    assert iou >= 0.6, iou


@pytest.mark.slow
def test_capsnet_example_learns():
    """CapsNet dynamic routing (3 unrolled routing iterations, batch_dot
    capsule transform): classify by digit-capsule LENGTH."""
    r = _run("examples/capsnet/capsnet.py", ["--iters", "150"])
    assert r.returncode == 0, r.stderr[-2000:]
    acc = float(r.stdout.splitlines()[-1].split(":")[1])
    assert acc >= 0.8, acc


@pytest.mark.slow
def test_svm_example_learns():
    """SVMOutput head: the op's backward IS the squared-hinge gradient
    (no Gluon loss object in the loop)."""
    r = _run("examples/svm/svm_mnist.py", ["--iters", "200"])
    assert r.returncode == 0, r.stderr[-2000:]
    acc = float(r.stdout.splitlines()[-1].split(":")[1])
    assert acc >= 0.8, acc


@pytest.mark.slow
def test_stochastic_depth_example():
    """Stochastic depth: training forwards vary (blocks drop), inference
    forwards are bit-identical (every block kept), and the thinned net
    still learns."""
    r = _run("examples/stochastic_depth/stochastic_depth.py",
             ["--iters", "150"])
    assert r.returncode == 0, r.stderr[-2000:]
    tail = r.stdout.splitlines()[-1]
    train_var = float(tail.split("train-mode variation")[1].split()[0])
    infer_var = float(tail.split("infer-mode variation")[1].split()[0])
    acc = float(tail.split("accuracy:")[1])
    assert train_var > 0, "blocks never dropped in training mode"
    assert infer_var == 0.0, infer_var
    assert acc >= 0.6, acc


@pytest.mark.slow
def test_sgld_example_samples_posterior():
    """SGLD (Bayesian methods): the sgld optimizer's Langevin noise
    must give a genuinely spread posterior whose predictive mean still
    matches the data — a point optimizer would collapse the spread."""
    r = _run("examples/bayesian_methods/sgld_regression.py",
             ["--steps", "1200"])
    assert r.returncode == 0, r.stderr[-2000:]
    tail = r.stdout.splitlines()[-1]
    pred = float(tail.split("predictive mean")[1].split()[0])
    data_mean = float(tail.split("(data mean")[1].split(")")[0])
    spread = float(tail.split("posterior-spread")[1])
    assert abs(pred - data_mean) < 0.35, (pred, data_mean)
    assert spread > 0.1, spread


@pytest.mark.slow
def test_multi_task_example_both_heads_learn():
    r = _run("examples/multi_task/multi_task.py", ["--iters", "150"])
    assert r.returncode == 0, r.stderr[-2000:]
    tail = r.stdout.splitlines()[-1]
    digit = float(tail.split("digit accuracy:")[1].split()[0])
    parity = float(tail.split("parity accuracy:")[1].split()[0])
    assert digit > 0.7 and parity > 0.7, (digit, parity)


@pytest.mark.sparse_plane
def test_two_tower_example_trains_and_serves():
    """The graded recsys recipe (examples/recsys/two_tower.py --smoke):
    a 4-way row-sharded table trains through the plane's mask-packed
    row-sparse path, per-rank ledger bytes land at exactly 1/world, and
    a LookupFleet serves the published table bitwise. Non-slow: the
    smoke sizes finish in well under a minute on CPU."""
    r = _run("examples/recsys/two_tower.py", ["--smoke"], timeout=300)
    out = r.stdout + r.stderr
    assert r.returncode == 0, out[-2000:]
    assert "TWO_TOWER OK" in r.stdout
    # the eval bar: held-out loss fell decisively (the script asserts
    # < 0.6x; re-derive here so a silently weakened script still fails)
    ev = [l for l in r.stdout.splitlines() if l.startswith("eval loss")][0]
    first, last = (float(t) for t in
                   ev.replace("eval loss", "").split("->"))
    assert last < 0.6 * first, (first, last)
    # the ledger pin and the served-table parity, as printed
    bytes_line = [l for l in r.stdout.splitlines()
                  if l.startswith("per-rank embedding bytes:")][0]
    assert "True" in bytes_line, bytes_line
    assert "served-table parity: True" in r.stdout
    assert any(l.startswith("lookup QPS:") for l in r.stdout.splitlines())
