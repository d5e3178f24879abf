"""Native dependency-engine tests (model: tests/cpp/engine/
threaded_engine_test.cc — randomized dependency workloads verified against
expected ordering)."""
import random
import threading
import time

import pytest

from mxnet_tpu.engine import NativeEngine
from mxnet_tpu.io.record_io import native_available

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="native lib not built")


def test_write_write_ordering():
    eng = NativeEngine(num_workers=4)
    v = eng.new_var()
    log = []
    lock = threading.Lock()
    for i in range(50):
        def fn(i=i):
            with lock:
                log.append(i)
        eng.push(fn, write_vars=[v])
    eng.wait_all()
    assert log == list(range(50)), "writes on one var must serialize in order"
    assert eng.var_version(v) == 50
    eng.close()


def test_readers_between_writes():
    eng = NativeEngine(num_workers=4)
    v = eng.new_var()
    state = {"x": 0}
    seen = []
    lock = threading.Lock()

    def writer(val):
        def fn():
            time.sleep(0.001)
            state["x"] = val
        return fn

    def reader():
        def fn():
            with lock:
                seen.append(state["x"])
        return fn

    eng.push(writer(1), write_vars=[v])
    for _ in range(8):
        eng.push(reader(), read_vars=[v])
    eng.push(writer(2), write_vars=[v])
    for _ in range(8):
        eng.push(reader(), read_vars=[v])
    eng.wait_all()
    assert seen[:8] == [1] * 8
    assert seen[8:] == [2] * 8
    eng.close()


def test_independent_vars_run_concurrently():
    eng = NativeEngine(num_workers=4)
    vs = [eng.new_var() for _ in range(4)]
    barrier = threading.Barrier(4, timeout=5)
    ok = []

    def fn():
        barrier.wait()  # passes only if 4 tasks run concurrently
        ok.append(1)

    for v in vs:
        eng.push(fn, write_vars=[v])
    eng.wait_all()
    assert len(ok) == 4
    eng.close()


def test_randomized_dependency_chains():
    """Random ops over random var subsets; verify per-var write order and
    read-after-write visibility (the threaded_engine_test.cc pattern)."""
    eng = NativeEngine(num_workers=8)
    rng = random.Random(0)
    n_vars = 6
    vars_ = [eng.new_var() for _ in range(n_vars)]
    counters = [0] * n_vars
    observed = []
    lock = threading.Lock()

    expected = [0] * n_vars
    for _ in range(200):
        k = rng.randint(1, 3)
        targets = rng.sample(range(n_vars), k)
        if rng.random() < 0.5:
            def fn(ts=tuple(targets)):
                with lock:
                    for t in ts:
                        counters[t] += 1
            eng.push(fn, write_vars=[vars_[t] for t in targets])
            for t in targets:
                expected[t] += 1
        else:
            def fn(ts=tuple(targets)):
                with lock:
                    observed.append(tuple(counters[t] for t in ts))
            eng.push(fn, read_vars=[vars_[t] for t in targets])
    eng.wait_all()
    assert counters == expected
    eng.close()


def test_wait_for_var_version():
    eng = NativeEngine(num_workers=2)
    v = eng.new_var()
    for i in range(10):
        eng.push(lambda: time.sleep(0.001), write_vars=[v])
    eng.wait_for_var(v, version=10)
    assert eng.var_version(v) == 10
    eng.close()


def test_image_record_iter_uses_engine_and_overlaps(tmp_path):
    """The iterator decodes batch k+1 while the consumer works on batch k
    (ref: iter_prefetcher.h:47). Proof: when ``next()`` hands out batch k,
    batch k+1 is already scheduled on the engine, and the engine's own
    threads decode and assemble it while the consumer stays out of the
    iterator."""
    import time
    import numpy as np
    from mxnet_tpu import io as mxio, recordio

    rec = tmp_path / "d.rec"
    rs = np.random.RandomState(0)
    writer = recordio.MXRecordIO(str(rec), "w")
    for i in range(24):
        img = rs.randint(0, 255, (64, 64, 3), np.uint8)
        writer.write(recordio.pack_img(
            recordio.IRHeader(0, float(i % 10), i, 0), img, quality=95))
    writer.close()

    it = mxio.ImageRecordIter(path_imgrec=str(rec), data_shape=(3, 32, 32),
                              batch_size=8, resize=32,
                              preprocess_threads=4)
    assert it._engine is not None, "native engine must drive the iterator"
    n = prefetched = 0
    for b in it:
        n += 1
        kind, state, _ = it._pending  # what next() scheduled on its way out
        if kind == "eof":
            continue
        # the training step: the consumer never enters the iterator, so
        # only the engine can finish batch k+1. The limit is no timing
        # fence: it ends a run in which the batch never arrives
        limit = time.monotonic() + 60
        while "batch" not in state and time.monotonic() < limit:
            time.sleep(0.005)
        assert "batch" in state and state["batch"] is not b, \
            f"batch {n + 1} was not decoded while the consumer held {n}"
        prefetched += 1
    assert n == 3 and prefetched == 2


def test_async_checkpoint_write(tmp_path):
    """CheckpointManager(async_write=True): save() returns before the
    files exist; wait()/steps() fence; contents match a sync write; the
    snapshot is taken at save() time (later mutations don't leak in)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import fault, nd

    cm = fault.CheckpointManager(str(tmp_path), max_keep=2,
                                 async_write=True)
    assert cm._engine is not None
    w = nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    params = {"w": w}
    cm.save(1, params)
    # mutate AFTER scheduling: the checkpoint must hold the old value
    w += 100.0
    cm.save(2, params)
    assert cm.steps() == [1, 2]  # steps() waits for the writes
    step, loaded, meta = cm.restore(1)
    np.testing.assert_array_equal(
        loaded["w"].asnumpy(),
        np.arange(6, dtype=np.float32).reshape(2, 3))
    step2, loaded2, _ = cm.restore(2)
    np.testing.assert_array_equal(
        loaded2["w"].asnumpy(),
        np.arange(6, dtype=np.float32).reshape(2, 3) + 100.0)


def test_async_checkpoint_resume_with_trainer(tmp_path):
    """Async checkpoints restore bit-exactly including optimizer state."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, fault, gluon, nd

    def make():
        net = gluon.nn.Dense(2, use_bias=False)
        net.initialize(mx.init.Constant(1.0))
        with autograd.pause():
            net(nd.ones((1, 3)))
        return net

    def step(net, tr, x, y):
        with autograd.record():
            loss = ((net(x) - y) ** 2).mean()
        loss.backward()
        tr.step(x.shape[0])

    rs = np.random.RandomState(0)
    x = nd.array(rs.randn(8, 3).astype(np.float32))
    y = nd.array(rs.randn(8, 2).astype(np.float32))
    net_a = make()
    tr_a = gluon.Trainer(net_a.collect_params(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9})
    for _ in range(2):
        step(net_a, tr_a, x, y)
    cm = fault.CheckpointManager(str(tmp_path), async_write=True)
    cm.save(2, net=net_a, trainer=tr_a)
    for _ in range(2):
        step(net_a, tr_a, x, y)  # keep training while the write lands

    net_b = make()
    tr_b = gluon.Trainer(net_b.collect_params(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9})
    resumed = cm.restore_latest(net=net_b, trainer=tr_b)
    assert resumed is not None and resumed[0] == 2
    for _ in range(2):
        step(net_b, tr_b, x, y)
    for (_, pa), (_, pb) in zip(sorted(net_a.collect_params().items()),
                                sorted(net_b.collect_params().items())):
        np.testing.assert_allclose(pa.data().asnumpy(),
                                   pb.data().asnumpy(), rtol=1e-6)


def test_cpp_native_unit_tests():
    """The tests/cpp analog: build and run the assert-based C++ unit
    tests over the engine + recordio C ABIs (make -C src test)."""
    import os
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(["make", "-C", os.path.join(root, "src"), "test"],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ALL NATIVE TESTS PASSED" in r.stdout
