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
    dispatch span, the second has one more launch a step. The convolution is
    under the scope ``mx.a``, the loop fusion under ``mx.b`` inside it, the
    all-reduce under none, and the copy has no ``op_name`` at all."""
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
                ("fusion.1_kOutput", "kOutput", t, t + 4 * MS, "mx.a"),
                ("fusion.2_kLoop", "kLoop", t + 3 * MS, t + 6 * MS,
                 "mx.a/mx.b"),
                ("all-reduce.3", "all-reduce", t + 5 * MS, t + 7 * MS, "")]
            if d == 1:
                dev["ops"].append(("copy.4", "copy", t + 7 * MS, t + 10 * MS,
                                   tr.NO_NAME))
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
    # a breakdown's line carries the innermost scope
    assert out["device_ops"][0] == ["mx.a:fusion.1_kOutput",
                                    pytest.approx(20 * MS)]
    assert {name for name, _ in out["device_ops"]} == {
        "mx.a:fusion.1_kOutput", "mx.b:fusion.2_kLoop", "all-reduce.3",
        "copy.4"}
    # device 0's gaps are 3 ms, under the dispatch span for 2.5 of them
    assert len(out["idle_gaps"]) == 5
    assert all(name == "bench.dispatch" and s == pytest.approx(3 * MS)
               for name, s in out["idle_gaps"])
    assert out["idle_seconds_by_span"] == pytest.approx(
        {"bench.dispatch": 15 * MS})


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step)/jit(main)/mx.a/dot_general", "mx.a"),
    ("jit(step)/mx.a/mx.b/add", "mx.a/mx.b"),
    ("jit(step)/transpose(jvp(mx.a))/mul", "mx.a"),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "jit(call)/mx.mamba2/mx.ssd/mul", "mx.mamba2/mx.ssd"),
    # entered again inside itself; the first of a merged instruction's names
    ("jit(step)/jvp(mx.a)/mx.b/mx.a/mx.b/reshape;mx.c/reshape", "mx.a/mx.b"),
    ("jit(step)/mx.moe.experts/while", "mx.moe.experts"),
    ("jit(step)/jvp()/convert_element_type", ""),
    ("", ""),
    (None, tr.NO_NAME)])
def test_scope_of_an_op_name(op_name, scope):
    assert tr.scope_of(op_name) == scope


def test_seconds_by_scope_are_self_times_that_add_up_to_busy():
    """Per device and step the convolution keeps the 3 ms before the loop
    fusion starts inside it, the loop fusion the 2 ms before the all-reduce
    does, the all-reduce its 2; the second device's copy is 15 ms of the
    window. Halved over two devices they add up to ``busy_s``."""
    out = tr.reduce(hand_made())
    assert out["seconds_by_scope"] == pytest.approx({
        "mx.a": 15 * MS, "mx.a/mx.b": 10 * MS, "": 10 * MS,
        tr.NO_NAME: 7.5 * MS})
    assert sum(out["seconds_by_scope"].values()) == pytest.approx(
        out["busy_s"])


def test_self_seconds_of_a_while_and_its_body():
    ops = [("while.1", "while", 0.0, 10.0, tr.NO_NAME),
           ("fusion.2", "kLoop", 1.0, 4.0, "mx.a"),
           ("fusion.3", "kLoop", 4.0, 9.0, "mx.a"),
           ("fusion.4", "kLoop", 10.0, 12.0, "")]
    assert tr.self_seconds(ops) == pytest.approx([2.0, 3.0, 5.0, 2.0])
    # two ops of the body that overlap, the second outliving the while
    ops = [("while.1", "while", 0.0, 10.0, ""), ("a", "kLoop", 2.0, 5.0, ""),
           ("b", "kLoop", 4.0, 12.0, ""), ("c", "kLoop", 4.5, 4.7, "")]
    own = tr.self_seconds(ops)
    assert own == pytest.approx([2.0, 2.0, 7.8, 0.2])
    assert sum(own) == pytest.approx(12.0)


def test_a_gap_goes_to_the_innermost_span_that_covers_it():
    spans = [("bench.dispatch", 0.0, 10.0), ("mx.trainer.step", 4.0, 10.0),
             ("mx.trainer.update", 5.0, 9.0), ("mx.cached_op.forward", 0.5, 2)]
    assert tr._doing(spans, 6.0, 7.0) == "mx.trainer.update"
    assert tr._doing(spans, 4.2, 5.2) == "mx.trainer.step"
    assert tr._doing(spans, 2.5, 3.5) == "bench.dispatch"
    # none covers half: the one that covers most
    assert tr._doing(spans[1:], 1.5, 4.4) == "mx.cached_op.forward"
    assert tr._doing(spans, 11.0, 12.0) == "host.between"
    # another thread's program span inside the harness's wait (FitLoop)
    spans = [("bench.wait", 0.0, 100.0), ("mx.autograd.backward", 20.0, 60.0)]
    assert tr._doing(spans, 30.0, 40.0) == "mx.autograd.backward"
    assert tr._doing(spans, 70.0, 80.0) == "bench.wait"


def _varint(n):
    out = b""
    while n >= 0x80:
        out += bytes([n & 0x7F | 0x80])
        n >>= 7
    return out + bytes([n])


def _message(*fields):
    """A protobuf message of (number, int or bytes) fields."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += bytes([number << 3]) + _varint(value)
        else:
            out += bytes([number << 3 | 2]) + _varint(len(value)) + value
    return out


def test_op_names_from_the_bytes_of_an_xspace():
    def plane(name):
        def event(key, text, *stats):
            return _message((1, key), (2, _message(
                (1, key), (2, text), *[(5, _message(*s)) for s in stats])))
        return _message(
            (2, name),
            (3, b"a line, skipped"),
            (5, _message((1, 7), (2, _message((1, 7), (2, b"flops"))))),
            (5, _message((1, 9), (2, _message((1, 9), (2, b"tf_op"))))),
            (4, event(1, b"%fusion.1 = f32[8] fusion()", ((1, 7), (4, 640)),
                      ((1, 9), (5, b"jit(step)/mx.a/mul:")))),
            (4, event(2, b"%copy.2 = f32[8] copy()", ((1, 7), (4, 0)))))
    raw = _message((1, plane(b"/device:TPU:0")), (1, plane(b"/host:CPU")),
                   (2, b"an error string"))
    assert tr.op_names(raw) == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion()": "jit(step)/mx.a/mul"}}


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
    # the scope metrics: device ms a step under a prefix and inside it; the
    # outermost of them and the rest add up to the busy time a step
    load = lambda name: run.load_module("metrics", name)
    assert load("scope_ms").read(out, "mx.a") == pytest.approx(5.0)
    assert load("scope_ms").read(out, "mx.b") == pytest.approx(2.0)
    assert load("scope_ms").read(out, "mx.c") is None
    import types
    out["readers"] = {"a": types.SimpleNamespace(PREFIX="mx.a"),
                      "b": types.SimpleNamespace(PREFIX="mx.b"),
                      "other": load("device_idle")}
    assert read("scope_rest_ms") == pytest.approx(3.5)
    assert 5.0 + 3.5 == pytest.approx(
        1e3 * out["trace"]["busy_s"] / out["trace"]["steps"])
    assert load("scope_ms").rest(dict(out, readers={})) is None
    assert read("device_idle") == pytest.approx(15.0)
    assert read("programs_per_step") == pytest.approx(1.5)
    assert read("allreduce_exposed_ms") == pytest.approx(1.0)
    # loop fusions 15 ms of 15 + 20 + 10 (all-reduce) + 15/2 (copy)
    assert read("loop_fusion_share") == pytest.approx(100 * 15 / 52.5)
    assert all(run.load_module("metrics", n).read({"trace": None}) is None
               for n in ("device_idle", "programs_per_step",
                         "allreduce_exposed_ms", "loop_fusion_share",
                         "mx_mamba2_ms", "mx_moe_ms"))


@pytest.mark.parametrize("name, prefix", [
    ("mx_mamba2_ms", "mx.mamba2"), ("mx_ssd_ms", "mx.ssd"),
    ("mx_gqa_ms", "mx.gqa"), ("mx_mla_ms", "mx.mla"), ("mx_moe_ms", "mx.moe")])
def test_a_scope_metric_reads_its_prefix(name, prefix):
    sys.path.insert(0, str(HERE.parent))
    import run
    module = run.load_module("metrics", name)
    assert module.PREFIX == prefix
    trace = {"steps": 2, "seconds_by_scope": {
        prefix: 4 * MS, f"mx.outer/{prefix}.part": 2 * MS,
        prefix + "x": 16 * MS, "": 8 * MS}}
    assert module.read({"trace": trace}) == pytest.approx(3.0)


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
