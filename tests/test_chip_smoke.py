"""chip_smoke.py off the chip: its CPU rehearsal passes, its plain run
refuses a machine without a TPU at the first phase, and the compile cache
it switches on first can be placed from outside.

Every case is a child process: chip_smoke.py and enable_compile_cache set
process-wide jax configuration.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(argv, extra_env=None, timeout=600, cwd=ROOT):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "MXTPU_COMPILE_CACHE",
                        "MXTPU_FUSED_EPILOGUE")}
    env.update({"JAX_PLATFORMS": "cpu", **(extra_env or {})})
    return subprocess.run([sys.executable, *argv], capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=cwd)


def _json_lines(stdout):
    return [json.loads(l) for l in stdout.splitlines() if l.startswith("{")]


@pytest.mark.heavy
def test_cpu_rehearsal_passes_every_phase():
    """The same code at a tiny size: ResNet-18, 64 px, batch 8, Pallas
    kernels interpreted. The last line is the result, and names the CPU."""
    res = _run([SMOKE, "--allow-cpu", "--model", "resnet18_v1", "--batch", "8",
                "--gluon-batch", "8", "--image", "64"],
               {"XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    rows = _json_lines(res.stdout)
    assert [r.get("phase") for r in rows] == \
        ["device", "kernels", "train_spmd", "train_gluon", None]
    assert all(r["ok"] for r in rows)
    assert json.loads(res.stdout.splitlines()[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    device, kernels, spmd, gluon = rows[:4]
    assert device["compile_cache"] == "skipped-cpu"
    assert [c["shape"] for c in kernels["cases"]] == \
        [[8, 56, 56, 256], [8, 7, 7, 2048]]
    assert all(c["interpret"] for c in kernels["cases"])  # not a TPU
    for row in (spmd, gluon):
        assert row["lowering"] == "composed"
        assert row["losses"][-1] < row["losses"][0]
        assert row["info"]["device_kind"] == "cpu"
    assert len(spmd["losses"]) == 3 + 4 + 4 and len(gluon["losses"]) == 3


def test_plain_run_refuses_a_machine_without_tpu():
    """What the driver runs, where there is no chip: non-zero at the
    device phase, no result line."""
    res = _run([SMOKE])
    assert res.returncode != 0
    rows = _json_lines(res.stdout)
    assert len(rows) == 1
    assert rows[0]["phase"] == "device" and rows[0]["ok"] is False
    assert "no TPU" in rows[0]["error"]


def test_fails_alone_in_a_directory(tmp_path):
    """Without the repo beside it the script cannot pass."""
    import shutil
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    res = _run([str(tmp_path / "chip_smoke.py"), "--allow-cpu"],
               {"PYTHONPATH": ""}, cwd=str(tmp_path))
    assert res.returncode != 0
    assert not _json_lines(res.stdout)
    assert "mxnet_tpu" in res.stderr


_CACHE_PROBE = """
import json, os, sys
sys.path.insert(0, {root!r})
import jax
from mxnet_tpu import util
_update = jax.config.update
dirs_set_in_code = []
def spy(name, value):
    if name == "jax_compilation_cache_dir":
        dirs_set_in_code.append(value)
    _update(name, value)
jax.config.update = spy
def call():
    return {{"returned": util.enable_compile_cache(),
             "dir": jax.config.jax_compilation_cache_dir,
             "set_in_code": list(dirs_set_in_code),
             "min_secs":
             jax.config.jax_persistent_cache_min_compile_time_secs}}
rows = []
{calls}
print(json.dumps(rows))
"""


def _cache_probe(calls, extra_env=None):
    """``calls``: statements that append ``call()`` results to ``rows``,
    run in one child (enable_compile_cache sets process-wide jax config)."""
    res = _run(["-c", _CACHE_PROBE.format(root=ROOT, calls=calls)], extra_env)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.splitlines()[-1])


def test_compile_cache_placed_from_outside(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it, the program sets no
    directory of its own (and no sub-directory), only the thresholds —
    on the CPU too, since someone placed it. MXTPU_COMPILE_CACHE does not
    override a cache placed this way."""
    where = str(tmp_path / "placed")
    first, second = _cache_probe(
        "rows.append(call())\n"
        f"os.environ['MXTPU_COMPILE_CACHE'] = {str(tmp_path / 'x')!r}\n"
        "rows.append(call())",
        {"JAX_COMPILATION_CACHE_DIR": where})
    want = {"returned": where, "dir": where, "set_in_code": [],
            "min_secs": 0.0}
    assert first == want and second == want


def test_compile_cache_default_is_fixed_in_the_checkout():
    """Unset: a CPU-only process skips the cache; a process on the chip
    (steered here, since this machine has none) keeps it at the fixed
    <checkout>/.jax_cache."""
    on_cpu, on_chip = _cache_probe(
        "rows.append(call())\n"
        "_update('jax_platforms', 'tpu')\n"
        "rows.append(call())")
    assert on_cpu["returned"] == "skipped-cpu" and on_cpu["dir"] is None
    fixed = os.path.join(ROOT, ".jax_cache")
    assert on_chip == {"returned": fixed, "dir": fixed,
                       "set_in_code": [fixed], "min_secs": 0.0}
