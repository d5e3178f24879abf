"""The trace reduction on a hand-made event list, and on a trace recorded
on the chip where one is kept beside this file."""
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
import trace_reduce as tr  # noqa: E402

MS = 1e-3


def hand_made():
    """Two devices, nine waits 10 ms apart (the last is left out), each returning half a
    millisecond after the step's last op on the second device ended: after
    SKIP=2 the window is five steps, [27, 77] ms on the first device (whose
    program ends at 7 ms of each 10) and [28, 78] on the second (whose second
    program ends at 8, with a copy outside any program after it). On each
    device every step runs a convolution
    fusion (4 ms), a loop fusion (3 ms, overlapping the convolution by 1 ms
    on another line), and an all-reduce (2 ms) of which the loop fusion
    hides the first half. The first device idles 3 ms a step under the
    dispatch span, the second has one more launch a step."""
    host, devices = [], {"/device:TPU:0": {"ops": [], "modules": []},
                         "/device:TPU:1": {"ops": [], "modules": []}}
    for i in range(9):
        t = 10 * i * MS
        host.append(("bench.dispatch", t + 6.5 * MS, t + 10 * MS))
        host.append(("bench.wait", t + 10 * MS, t + 10.5 * MS))
        for d, dev in enumerate(devices.values()):
            dev["modules"].append(("jit_step", t, t + 7 * MS))
            if d == 1:
                dev["modules"].append(("jit_extra", t + 7 * MS, t + 8 * MS))
            dev["ops"] += [
                ("fusion.1_kOutput", "kOutput", t, t + 4 * MS),
                ("fusion.2_kLoop", "kLoop", t + 3 * MS, t + 6 * MS),
                ("all-reduce.3", "all-reduce", t + 5 * MS, t + 7 * MS)]
            if d == 1:
                dev["ops"].append(("copy.4", "copy", t + 7 * MS, t + 10 * MS))
    host.sort(key=lambda s: s[1])
    return {"devices": devices, "host": host}


def test_interval_arithmetic():
    assert tr.union([(3, 5), (0, 2), (1, 4)]) == [[0, 5]]
    assert tr.total(tr.union([(0, 1), (2, 3), (2.5, 4)])) == 3
    assert tr.subtract([(0, 10)], [[2, 3], [5, 11]]) == [(0, 2), (3, 5)]


def test_hand_made_trace():
    out = tr.reduce(hand_made())
    assert out["steps"] == 5 and out["devices"] == 2
    assert out["window_s"] == pytest.approx(50 * MS)
    # device 0 is busy 7 of every 10 ms, device 1 all 10
    assert out["busy_s"] == pytest.approx((35 + 50) / 2 * MS)
    assert out["launches_per_step"] == pytest.approx(1.5)
    # the all-reduce's second millisecond runs alone, on both devices
    assert out["collective_exposed_s_per_step"] == pytest.approx(1 * MS)
    kinds = out["seconds_by_kind"]
    assert kinds["kLoop"] == pytest.approx(15 * MS)
    assert kinds["kOutput"] == pytest.approx(20 * MS)
    assert out["device_ops"][0] == ["fusion.1_kOutput", pytest.approx(20 * MS)]
    # device 0's gaps are 3 ms, under the dispatch span for 2.5 of them
    assert len(out["idle_gaps"]) == 5
    assert all(name == "bench.dispatch" and s == pytest.approx(3 * MS)
               for name, s in out["idle_gaps"])


def test_too_short_a_trace_gives_nothing():
    trace = hand_made()
    trace["host"] = trace["host"][:6]
    assert tr.reduce(trace) is None
    assert tr.reduce({"devices": {}, "host": hand_made()["host"]}) is None


def test_metric_readers_on_the_hand_made_trace():
    sys.path.insert(0, str(HERE.parent))
    import run
    out = {"trace": tr.reduce(hand_made())}
    read = lambda name: run.load_module("metrics", name).read(out)
    assert read("device_idle") == pytest.approx(15.0)
    assert read("programs_per_step") == pytest.approx(1.5)
    assert read("allreduce_exposed_ms") == pytest.approx(1.0)
    # loop fusions 15 ms of 15 + 20 + 10 (all-reduce) + 15/2 (copy)
    assert read("loop_fusion_share") == pytest.approx(100 * 15 / 52.5)
    assert all(run.load_module("metrics", n).read({"trace": None}) is None
               for n in ("device_idle", "programs_per_step",
                         "allreduce_exposed_ms", "loop_fusion_share"))


def test_recorded_chip_trace():
    """Eight traced steps of resnet50_train_spmd on one TPU v5 lite (PR 25's
    first traced chip run; the device's op and module lines and the host's
    bench.* spans, nothing else kept). Each step is a 0.6 us program that
    makes the step counter and the 102.5 ms step program."""
    out = tr.reduce(tr.load(HERE / "resnet50_train_spmd.xplane.pb.gz"))
    assert out["devices"] == 1 and out["steps"] == 4
    assert out["window_s"] / 4 == pytest.approx(0.1025, rel=1e-3)
    assert out["launches_per_step"] == pytest.approx(2.0)
    assert 0.999 < out["busy_s"] / out["window_s"] <= 1.0
    kinds = out["seconds_by_kind"]
    assert kinds["kOutput"] > 3 * kinds["kLoop"] > 0.25 * out["busy_s"]
    assert out["collective_exposed_s_per_step"] == 0
    assert len(out["device_ops"]) == 10
    assert out["device_ops"][0][0] == "fusion.60_kOutput_f32_256"
    assert all(name == "bench.wait" and s < 1e-4
               for name, s in out["idle_gaps"])
