"""Test harness: force an 8-device virtual CPU mesh.

Mirrors the reference's test strategy of running the same suite against
different backends by switching the default context
(ref: tests/python/gpu/test_operator_gpu.py imports the CPU suite).
Multi-device tests use the 8 virtual CPU devices as the stand-in TPU mesh.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_collection_modifyitems(config, items):
    """Tier-1 runs under a wall-clock limit, and on a slow machine the
    limit cuts it: whatever has not run by then counts for nothing. The
    tests marked ``heavy`` (ten seconds to a minute each) therefore run
    after all the others, so that a cut costs a dozen tests and not the
    hundreds behind them. The sort is stable: nothing else moves."""
    items.sort(key=lambda item: item.get_closest_marker("heavy") is not None)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running end-to-end example tests")
    config.addinivalue_line(
        "markers", "heavy: part of tier-1, but tens of seconds long: run "
        "after every other test (tests/conftest.py "
        "pytest_collection_modifyitems)")
    config.addinivalue_line(
        "markers", "chaos: fault-injection resilience tests "
        "(contrib/chaos.py plans; the unmarked-slow subset is a "
        "tier-1-safe fast smoke)")
    config.addinivalue_line(
        "markers", "serving: inference-serving subsystem tests "
        "(mxnet_tpu/serving: batcher, signature cache, admission, "
        "metrics, fleet router/autoscaler). Tier-1-safe: CPU; loopback "
        "sockets only (the fleet tests), never the network.")
    config.addinivalue_line(
        "markers", "telemetry: unified telemetry subsystem tests "
        "(mxnet_tpu/telemetry: tracer, chrome-trace export, metrics "
        "registry, step breakdown). Tier-1-safe: CPU, in-process.")
    config.addinivalue_line(
        "markers", "autotune: self-tuning runtime tests "
        "(telemetry/autotune.py probe-then-lock controller, "
        "comm/backward overlap, bench hygiene). Tier-1-safe: CPU, "
        "in-process, deterministic kv_slow chaos for comm-heavy steps.")
    config.addinivalue_line(
        "markers", "zero: ZeRO-1 sharded-optimizer-state tests "
        "(parallel/zero.py reduce-scatter / shard-update / allgather "
        "plane, global sentinel, topology-portable checkpoints). "
        "Tier-1-safe: CPU, simulated worlds in-process plus one "
        "2-process coordination-service subprocess test.")
    config.addinivalue_line(
        "markers", "comm_health: fleet-wide comm observability tests "
        "(telemetry/collective.py collective ledger, desync/straggler "
        "detection, hung-collective flight recorder, fleet trace "
        "merge). Tier-1-safe: CPU, in-process simulated worlds plus "
        "one 2-process kv_hang subprocess test.")
    config.addinivalue_line(
        "markers", "memory: device-memory observability tests "
        "(telemetry/memory.py live-byte ledger, per-program "
        "attribution, trace memory track, OOM forensics). Tier-1-safe: "
        "CPU — the ledger is exact by construction there.")
    config.addinivalue_line(
        "markers", "numerics: in-graph numerics observability tests "
        "(telemetry/numerics.py tensor-stat plane riding the grouped "
        "bucket programs, non-finite provenance, loss-scale timeline, "
        "Monitor facade). Tier-1-safe: CPU, in-process, bitwise "
        "on-vs-off parity pinned.")
    config.addinivalue_line(
        "markers", "elastic: elastic world-size training tests "
        "(parallel/elastic.py topology records, resize@N[:M] chaos, "
        "cross-world resume with re-formed group + re-split data, "
        "NDArrayIter num_parts sharding union proofs). Tier-1-safe: "
        "CPU, simulated worlds in-process; the real 2->3-process drill "
        "is a subprocess on the coordination-service fallback, same "
        "harness as test_dist_kvstore.")
    config.addinivalue_line(
        "markers", "supervisor: self-healing fleet supervisor tests "
        "(parallel/supervisor.py decide ladder, capacity models, "
        "flight-record parsing, tools/launch.py --supervise). "
        "Tier-1-safe: CPU — the escalation ladder is a pure function, "
        "the crash-loop/budget drill uses jax-free stub workers, and "
        "the chaos soak is a subprocess drill on the "
        "coordination-service fallback, same harness as test_elastic.")
    config.addinivalue_line(
        "markers", "efficiency: efficiency/goodput plane tests "
        "(telemetry/efficiency.py per-program FLOP/byte cost registry "
        "+ live MFU/roofline rollup, telemetry/run_report.py run "
        "reports, tools/run_compare.py regression diff). Tier-1-safe: "
        "CPU — the XLA cost model is exact there, so hand-computed "
        "matmul FLOPs pin the numbers.")
    config.addinivalue_line(
        "markers", "sparse_plane: sparse embedding-plane tests "
        "(parallel/embedding_plane.py row-wise sharded tables, "
        "optimizer/grouped.py sparse_rows_update row-gathered updates, "
        "serving/lookup.py registry lookup tier). Tier-1-safe: CPU, "
        "simulated worlds in-process; 1/world per-rank byte pins are "
        "ledger-exact by construction there.")
