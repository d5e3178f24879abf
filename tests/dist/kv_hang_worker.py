"""2-process comm-observability worker (run via tools/launch.py local):

Phase A (healthy straggler): rank 1 sleeps between collectives, both
ranks run the clock handshake + a few kvstore pushes + the comm-health
digest exchange — rank 0 prints the ``FitResult.comm_health``-shaped
diagnosis (straggler must be rank 1) and each rank dumps its chrome
trace for the controller's ``fleet_trace`` merge.

Phase B (hung collective): the chaos plan ``kv_hang:1@0:<MS>`` makes
rank 1 withhold its exchange; rank 0 blocks inside the collective, its
``MXTPU_COLL_TIMEOUT_S`` watchdog fires, and the surviving rank's
flight record must name the hung ``(kind, key, seq)`` and absent rank 1.
The coordination-service get timeout is shortened so both ranks exit
bounded after the diagnosis is on disk.
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np


def main():
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "..", ".."))
    from mxnet_tpu.kvstore_server import init_distributed
    assert init_distributed(), "MXTPU_* env missing (run via tools/launch.py)"
    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu import nd
    from mxnet_tpu import kvstore as kvs
    from mxnet_tpu import telemetry
    from mxnet_tpu.contrib import chaos
    from mxnet_tpu.telemetry import collective as coll
    from mxnet_tpu.telemetry.chrome_trace import dump_chrome_trace

    out_dir = os.environ["KV_HANG_OUT_DIR"]
    hang_ms = float(os.environ.get("KV_HANG_MS", "6000"))
    # bound phase B: the blocked get must give up soon after the flight
    # record lands, so the test finishes in seconds, not 120s
    os.environ["MXTPU_COORD_TIMEOUT_MS"] = \
        os.environ.get("KV_HANG_COORD_TIMEOUT_MS", "4000")

    kv = kvs.create("dist_sync")
    rank, nw = kv.rank, kv.num_workers
    telemetry.enable()

    # -- clock handshake: anchors ledger digests + trace onto rank 0 ----
    off = coll.sync_clocks()
    assert abs(off) < 1000.0, f"same-host clock offset {off}ms"
    if rank == 0:
        # rank 0 IS the reference: a nonzero self-offset would fabricate
        # skew on every digest
        assert off == 0.0, off

    # -- phase A: rank 1 straggles BETWEEN collectives ------------------
    import time
    straggle_s = 0.25  # large beside a loaded host's scheduling noise
    kv.init("w", nd.array(np.zeros((4, 4), np.float32)))
    for step in range(3):
        if rank == 1:
            time.sleep(straggle_s)  # slow host/input on this rank
        g = nd.array(np.ones((4, 4), np.float32))
        kv.push("w", g)
        kv.pull("w", out=g)
    health = coll.health_check(kv)
    assert health["world"] == nw, health
    assert health["desync"] is None, health
    assert health["straggler_rank"] == 1, health
    assert health["max_skew_ms"] > straggle_s * 1e3 * 0.5, health
    if rank == 0:
        print("COMM_HEALTH " + json.dumps(health), flush=True)
    dump_chrome_trace(os.path.join(out_dir, f"rank{rank}.json"))
    kv.barrier()
    # clean traffic under an armed watchdog fires nothing
    assert coll.ledger.watchdog_fired == 0

    # -- phase B: kv_hang -> surviving rank's flight record -------------
    plan = chaos.install(f"kv_hang:1@0:{hang_ms:.0f}")
    plan.begin_step(0)
    g = nd.array(np.ones((4, 4), np.float32))
    try:
        kv.push("w", g)
        survived_error = None
    except Exception as e:  # rank 0: the bounded coord get gave up
        survived_error = e
    chaos.uninstall()
    if rank == 1:
        # the faulty rank slept through the collective; its own record
        # (if any) is not the one under test
        assert plan.injected["kv_hang"] == 1, plan.injected
    else:
        assert survived_error is not None, \
            "rank 0 should have timed out waiting for the withheld rank"
        # the watchdog fired while we were blocked and wrote the flight
        # record naming the hung collective and the absent rank
        assert coll.ledger.watchdog_fired >= 1
        assert coll.ledger.flight_records, "no flight record written"
        with open(coll.ledger.flight_records[0]) as f:
            rec = json.load(f)
        assert rec["reason"] == "hung_collective"
        assert rec["absent_rank"] == 1, rec.get("absent_rank")
        hung = rec["hung"]
        kinds = {h["kind"] for h in hung}
        assert "push" in kinds, kinds
        push = next(h for h in hung if h["kind"] == "push")
        assert push["key"] == "w" and push["seq"] >= 0, push
        assert rec["thread_stacks"], "flight record missing thread stacks"
        print("FLIGHT_RECORD " + json.dumps(
            {"path": coll.ledger.flight_records[0],
             "absent_rank": rec["absent_rank"],
             "hung": [{k: h[k] for k in ("kind", "key", "seq")}
                      for h in hung]}), flush=True)
        # this rank may host the coordination service: stay alive until
        # the withheld rank has woken, finished its exchange attempt and
        # hit its own bounded timeout — dying first would turn rank 1's
        # clean exit into a coordinator-connection error
        time.sleep(hang_ms / 1000.0 + 1.5)

    print(f"worker {rank}/{nw}: comm observability checks passed",
          flush=True)


if __name__ == "__main__":
    main()
