"""The command's refusals; that a configuration, a problem family, a cell
and a metric are added as new files plus entries, without editing a file
that exists; and that the existing cells draw and check the problems they
drew before the families."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.batch.solvers as batch_solvers
import repro.core.api.solvers as api_solvers
from bench import check, control, harness
from bench.tests import test_bench_faults as faults
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


def _add(root, section, entry):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench[section].append(entry)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_config_cell_and_metric_are_new_files(tmp_path, monkeypatch):
    """A configuration of the default family and one of a family of its own,
    a cell each and a metric, added as new files plus entries: both cells
    run correct, and the new family's cell is checked as every cell is: its
    bfloat16 control and two faults of the timed path come out not
    correct."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("_out", "_dev", "__pycache__", "tests"))
    shutil.copy(harness.REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _digest(root)
    params = harness.read_json(root / "bench" / "configs" / "pointcloud_c1.json")["params"]
    (root / "bench" / "configs" / "tiny_cloud.json").write_text(json.dumps({
        "source": "a test", "reduced": {}, "assumed": [], "params": {**params, "n": 256}}))
    (root / "bench" / "workloads" / "tiny_cloud.ot.json").write_text(json.dumps({
        "config": "tiny_cloud", "traffic": "solve_stream", "params": {"lam": None, "max_iter": 20},
        "check": {"solves": 1}, "limits": {"value_rel_err": 1.0}}))
    (root / "bench" / "metrics" / "solves_done.py").write_text(
        "def read(run):\n    return len(run.record['calls'])\n")
    # WFR between echocardiogram frames on a pixel grid, many pixels of no
    # mass; limits from CPU runs at its tiny size (PERF.md)
    shutil.copy(harness.REPO / "bench" / "tests" / "families" / "grid_wfr.py",
                root / "bench" / "families" / "grid_wfr.py")
    (root / "bench" / "configs" / "tiny_echo.json").write_text(json.dumps({
        "source": "a test", "reduced": {}, "assumed": [],
        "params": {"family": "grid_wfr", "side": 112, "eta": 0.1, "eps": 0.01, "lam": 0.5,
                   "s_mult": 4.0, "method": "spar_sink_mf", "stabilize": True, "tol": 1e-6,
                   "dtype": "float32", "pool": 8}}))
    (root / "bench" / "workloads" / "tiny_echo.uot.json").write_text(json.dumps({
        "config": "tiny_echo", "traffic": "solve_stream", "params": {"max_iter": 200},
        "check": {"solves": 2, "draw": True},
        "limits": {"draw_z": 12.0, "entry_log_err": 0.05, "value_rel_err": 1e-4}}))
    _add(root, "configs", {"name": "tiny_cloud", "source": "a test",
                           "file": "bench/configs/tiny_cloud.json", "reduced": [], "why": "a test"})
    _add(root, "configs", {"name": "tiny_echo", "source": "a test",
                           "file": "bench/configs/tiny_echo.json", "reduced": [], "why": "a test"})
    _add(root, "workloads", {"name": "tiny_cloud.ot", "config": "tiny_cloud", "traffic": "ot",
                             "chips": 1, "why": "a test"})
    _add(root, "workloads", {"name": "tiny_echo.uot", "config": "tiny_echo", "traffic": "uot",
                             "chips": 1, "why": "a test"})
    _add(root, "end_to_end", {"name": "solves_done", "unit": "solves", "better": "higher",
                              "bound": 0.25, "source": "host_clock",
                              "workloads": ["tiny_cloud.ot", "tiny_echo.uot"]})

    res = tiny.run("tiny_cloud.ot", root=root, n=256)
    assert res["correct"] is True
    assert res["metrics"]["solves_done"]["value"] >= 1
    assert set(res["metrics"]) == {"setup_s", "solves_done"}

    echo = "tiny_echo.uot"
    ms = harness.traffic("solve_stream", root).pool(
        {**harness.load_cell(echo, root)[1], **tiny.overrides(echo, root)}, tiny.SEED, root)
    assert all(min((m.a == 0).mean(), (m.b == 0).mean()) > 0.2 for m in ms)
    blocked = np.isinf(ms[0].cost(np, ms[0].x[:, None], ms[0].x[None]))
    assert 0.2 < blocked.mean() < 1.0
    res = tiny.run(echo, root=root)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"setup_s", "solves_done"}
    limits = harness.load_cell(echo, root)[0]["limits"]
    over = tiny.overrides(echo, root)
    low = control.readings(echo, tiny.SEED, dtype=jnp.bfloat16, root=root, overrides=over)
    same = control.readings(echo, tiny.SEED, dtype=jnp.float32, root=root, overrides=over)
    assert not check.passed(check.compare(low, limits)), low
    assert check.passed(check.compare(same, limits)), same
    with monkeypatch.context() as mp:
        mp.setattr(batch_solvers, "sparse_log_potentials", faults.unchanged_state)
        assert tiny.run(echo, root=root)["correct"] is False
    real = api_solvers._coo_log_value
    with monkeypatch.context() as mp:
        mp.setattr(api_solvers, "_coo_log_value", lambda *a, **k: real(*a, **k) * 1.01)
        assert tiny.run(echo, root=root)["correct"] is False

    after = _digest(root)
    assert {p: d for p, d in after.items() if p in before} == before


#: the parent tree's pool digest, budget ``s`` and float32 control
#: readings of each cell at its tiny size, seed `tiny.SEED`: the families
#: must draw the same problems and check them alike
PINNED = {
    "pointcloud_c1.ot": (
        "c5373c72076755669b1025372a8b24984e85f15d557314e2c9c60559f4c0ab54", 3101.714599533794,
        {"entry_log_err": 2.2018845399429665e-05, "value_rel_err": 3.289083689142107e-07,
         "draw_z": 1.3102780491680837, "draw_grid_z": -0.04822551023139608,
         "draw_dup_z": -1.3102780491680837}),
    "pointcloud_c1.uot": (
        "78bc75ef7b6b97580803d91ddfe4971929f6f7e401a0fdf73b16421daa4ef12d", 3101.714599533794,
        {"entry_log_err": 1.8313015459625603e-05, "value_rel_err": 5.829339178143212e-08,
         "draw_z": 0.38442455105919354, "draw_grid_z": -0.43234823897720737,
         "draw_dup_z": 0.38442455105919354}),
}


def _pool_digest(pool) -> str:
    h = hashlib.sha256()
    for m in pool:
        for arr in (m.x, m.a, m.b):
            h.update(np.ascontiguousarray(arr, np.float64).tobytes())
        h.update(repr((m.eps, m.lam)).encode())
    return h.hexdigest()


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_existing_cells_get_the_same_problems_and_readings(cell):
    digest, s, readings = PINNED[cell]
    spec, params = harness.load_cell(cell)
    over = tiny.overrides(cell)
    traffic = harness.traffic(spec["traffic"])
    pool = traffic.pool({**params, **over}, tiny.SEED)
    assert _pool_digest(pool) == digest
    assert traffic.budget({**params, **over}, pool) == s
    assert control.readings(cell, tiny.SEED, dtype=jnp.float32, overrides=over) == readings
