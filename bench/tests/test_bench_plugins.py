"""The command's refusals, and that a configuration, a cell and a metric
are added as new files plus entries, without editing a file that exists."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from bench import harness
from bench.tests import tiny


def test_command_refuses_a_cpu_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for x64 in ("0", "1"):
        p = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", tiny.cells()[0], "--seed",
             str(2**31 + 5), "--seconds", "1", "--trace", "0"],
            cwd=harness.REPO, env={**env, "JAX_ENABLE_X64": x64},
            capture_output=True, text=True, timeout=300)
        assert p.returncode == 2, p.stderr
        assert p.stdout == ""
        assert "no TPU" in p.stderr


def test_device_check_refuses_float64_and_too_few_chips(monkeypatch):
    class FakeTPU:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTPU()])
    assert harness.check_device(1)["kind"] == "TPU v5 lite"
    with pytest.raises(harness.NoDevice, match="4 chips"):
        harness.check_device(4)
    jax.config.update("jax_enable_x64", True)
    with pytest.raises(harness.NoDevice, match="x64"):
        harness.check_device(1)


def _digest(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_new_config_cell_and_metric_are_new_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(harness.REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("_out", "_dev", "__pycache__", "tests"))
    shutil.copy(harness.REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _digest(root)
    (root / "bench" / "configs" / "tiny_cloud.json").write_text(json.dumps({
        "source": "a test", "reduced": {}, "assumed": [],
        "params": {**harness.read_json(root / "bench" / "configs" / "pointcloud_c1.json")["params"],
                   "n": 256}}))
    (root / "bench" / "workloads" / "tiny_cloud.ot.json").write_text(json.dumps({
        "config": "tiny_cloud", "traffic": "solve_stream", "params": {"lam": None, "max_iter": 20},
        "check": {"solves": 1}, "limits": {"value_rel_err": 1.0}}))
    (root / "bench" / "metrics" / "solves_done.py").write_text(
        "def read(run):\n    return len(run.record['calls'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_cloud", "source": "a test",
                             "file": "bench/configs/tiny_cloud.json", "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "tiny_cloud.ot", "config": "tiny_cloud", "traffic": "ot",
                               "chips": 1, "why": "a test"})
    bench["end_to_end"].append({"name": "solves_done", "unit": "solves", "better": "higher",
                                "bound": 0.25, "source": "host_clock", "workloads": ["tiny_cloud.ot"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    res = tiny.run("tiny_cloud.ot", root=root, n=256)
    assert res["correct"] is True
    assert res["metrics"]["solves_done"]["value"] >= 1
    assert set(res["metrics"]) == {"setup_s", "solves_done"}
    after = _digest(root)
    assert {p: d for p, d in after.items() if p in before} == before
