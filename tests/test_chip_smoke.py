"""``chip_smoke.py`` on the CPU: its phases run at small sizes (so the
script cannot rot between chip runs), and the script itself refuses to
report success without a TPU or without the repository around it."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import s0
from repro.launch.compile_cache import use_compile_cache

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

N_SMALL = 512


def _run(args, cwd, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
    )


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_script_fails_without_tpu_or_repo(where, tmp_path):
    """No TPU, or a directory holding only the script: non-zero exit and no
    success line."""
    cwd = ROOT
    if where == "alone":
        shutil.copy(ROOT / "chip_smoke.py", tmp_path)
        cwd = tmp_path
    proc = _run(["chip_smoke.py"], cwd, PYTHONPATH="")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_phase_large_log_cpu():
    out = chip_smoke.phase_large_log(N_SMALL)
    assert len(out) == 2 and all(r["nnz"] > 0 for r in out)


def test_phase_large_kernel_cpu():
    res = chip_smoke.phase_large_kernel(N_SMALL)
    assert res["tpu_custom_call"] is False  # no TPU: impl="auto" is jnp


def test_phase_host_parity_cpu():
    out = chip_smoke.phase_host_parity(N_SMALL)
    for r in out:  # one device here
        assert r["dense_rel_err"] == r["mf_rel_err"] == 0.0
        assert r["counts_rows_differ"] == r["cols_differ"] == 0


def _with_counts(d, counts):
    rows = np.searchsorted(np.cumsum(counts), np.arange(d["rows"].size), side="right")
    return dict(d, counts=counts, rows=np.minimum(rows, counts.size - 1).astype(np.int32))


def _count_far_off(d):
    """Row 0 draws more entries than any proposal of its rate; rows still
    follow the counts."""
    counts = d["counts"].copy()
    counts[0] += 2 + int(6 * np.sqrt(d["lam"][0]))
    return _with_counts(d, counts)


def _counts_off_by_one(d):
    """Every row draws one more entry."""
    return _with_counts(d, d["counts"] + 1)


def _move_col(d):
    """One slot's column moves to a bin far from its uniform."""
    cols = d["cols"].copy()
    cols[0] = (cols[0] + d["rb"].size // 2) % d["rb"].size
    return dict(d, cols=cols)


def _drop_row(d):
    """Rows stop following the counts."""
    rows = d["rows"].copy()
    rows[0] = rows[-1]
    return dict(d, rows=rows)


def _flip_uniform(d):
    """One column uniform moves by an ulp."""
    u = d["u"].copy()
    u[0] = np.nextafter(u[0], np.float32(1.0))
    return dict(d, u=u)


@pytest.mark.parametrize(
    "fault", [_count_far_off, _counts_off_by_one, _move_col, _drop_row, _flip_uniform]
)
def test_sampler_check_catches_a_faulty_draw(fault):
    """`compare_draws` passes a draw against itself and fails one that a
    sampler fault on the chip would produce."""
    cpu = jax.devices("cpu")[0]
    problem = chip_smoke._problem("OT", N_SMALL, 0, chip_smoke.EPS_LOG)
    key, s = jax.random.PRNGKey(0), chip_smoke.S_MULT * s0(N_SMALL)
    draw = chip_smoke.draw_on(cpu, problem, key, s)
    assert chip_smoke.compare_draws(draw, draw)["cols_differ"] == 0
    with pytest.raises(chip_smoke.CheckFailed, match="sampler"):
        chip_smoke.compare_draws(fault(draw), draw)


def test_phase_serving_cpu():
    res = chip_smoke.phase_serving(16)
    assert res["requests"] == 16 and res["max_rel_err"] <= 1e-5


def test_phase_four_chips_on_four_cpu_devices():
    """The --four-chips phase on 4 virtual CPU devices (in a child process:
    this one must keep seeing a single device)."""
    code = (f"import sys; sys.path.insert(0, {str(ROOT)!r}); import chip_smoke; "
            "chip_smoke.phase_four_chips(8, 64)")
    proc = _run(["-c", code], ROOT,
                XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "[four_chips] batch=8 n=64 devices=4" in proc.stdout


def test_compile_cache_defaults_to_the_repo(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_entries_go_to_the_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled entries land there and
    nowhere in the repository."""
    repo_cache = ROOT / ".jax_cache"
    before = sorted(repo_cache.iterdir()) if repo_cache.exists() else []
    code = ("from repro.launch.compile_cache import use_compile_cache; "
            "import jax, jax.numpy as jnp; print(use_compile_cache()); "
            "jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()")
    proc = _run(["-c", code], ROOT, PYTHONPATH=str(ROOT / "src"),
                JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == str(tmp_path / "cache")
    assert any((tmp_path / "cache").iterdir())
    after = sorted(repo_cache.iterdir()) if repo_cache.exists() else []
    assert after == before
