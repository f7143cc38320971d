"""The harness end to end at a tiny size on the CPU (test-only override,
``tiny.py``): every cell, untraced and traced, and the four-replica fleet
on four virtual devices. And the refusals: no TPU, an unknown chip, no
program next to the benchmark."""
import json
import os
import shutil
import subprocess
import sys
import time
from types import SimpleNamespace

import jax
import pytest

from bench import manifest
from bench import run as bench_run
from bench.tests.tiny import tiny_cell

ROOT = manifest.ROOT
CELLS = [w["name"] for w in manifest.load_manifest()["workloads"]]
PEAKS = json.loads((ROOT / "bench" / "peaks.json").read_text())["TPU v5 lite"]


def _run(cell, trace, seed=2**31 + 99, seconds=2.0):
    return bench_run.run(cell, seed, seconds, trace,
                         jax.devices()[:cell.chips], PEAKS,
                         t_start=time.perf_counter())


@pytest.mark.parametrize("cell_name", [c for c in CELLS
                                       if manifest.find_cell(c).chips == 1])
def test_untraced_run_reports_end_to_end(cell_name):
    cell = tiny_cell(cell_name)
    res = _run(cell, False)
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == want
    for v in res["metrics"].values():
        assert v["value"] > 0
    assert res["device"]["count"] == 1


@pytest.mark.parametrize("cell_name", [c for c in CELLS
                                       if manifest.find_cell(c).chips == 1])
def test_traced_run_reports_per_layer(cell_name):
    cell = tiny_cell(cell_name)
    res = _run(cell, True)
    assert res["correct"] is True
    names = {m["name"] for m in cell.per_layer}
    # the CPU trace has no TPU plane: device-trace metrics stay silent
    device = {m["name"] for m in cell.per_layer
              if m["source"] == "device_trace"}
    assert set(res["metrics"]) == names - device
    assert "busy_s" not in res["device"]


def test_four_replica_fleet_on_virtual_devices():
    code = (
        "import json, sys, time\n"
        "sys.path[:0] = [%r, %r]\n"
        "import jax\n"
        "from bench import manifest, run\n"
        "from bench.tests.tiny import kept_mix, tiny_cell\n"
        "man = manifest.load_manifest()\n"
        "man['workloads'].append({'name': 'fleet', 'config': 'olmo-1b',"
        " 'traffic': 'chat', 'chips': 4, 'why': 'test'})\n"
        "for m in man['end_to_end']:\n"
        "    if m['name'] == 'tokens_per_s':\n"
        "        m['workloads'].append('fleet')\n"
        "cell = tiny_cell('fleet', man, traffic=kept_mix('chat-closed'))\n"
        "res = run.run(cell, 5, 2.0, False, jax.devices()[:4], None,"
        " t_start=time.perf_counter())\n"
        "print(json.dumps(res))\n" % (str(ROOT), str(ROOT / "src")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["device"]["count"] == 4
    assert res["metrics"]["tokens_per_s"]["value"] > 0


def test_gc_passes_are_recorded():
    pauses = bench_run.GcPauses()
    t0 = time.perf_counter()
    import gc
    gc.collect()
    pauses.close()
    gc.collect()
    assert [p[2] for p in pauses.inside(t0, time.perf_counter())] == [2]


def _bench_cli(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    out = _bench_cli(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_refuses_in_a_directory_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = _bench_cli(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_refuses_unknown_chip_and_too_few():
    tpu = SimpleNamespace(platform="tpu", device_kind="TPU v9 imaginary")
    fake = SimpleNamespace(devices=lambda: [tpu])
    with pytest.raises(bench_run.Refused, match="peaks.json"):
        bench_run.chips_for(fake, 1, {"TPU v5 lite": {}})
    known = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    with pytest.raises(bench_run.Refused, match="needs 4 chips"):
        bench_run.chips_for(SimpleNamespace(devices=lambda: [known]), 4,
                            {"TPU v5 lite": {}})
    devs, peak = bench_run.chips_for(
        SimpleNamespace(devices=lambda: [known]), 1, {"TPU v5 lite": {"x": 1}})
    assert devs == [known] and peak == {"x": 1}
