"""Sizes at which a test run holds a cell, by traffic kind, and a device
record that lets a test drive a run on the CPU."""
import time

from bench import harness

CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SECONDS = 1.5
SEED = 2**31 + 11

_TINY = {
    "solve_stream": {"n": 512, "pool": 2},
}


def overrides(name: str, root=harness.REPO, **more) -> dict:
    cell, _ = harness.load_cell(name, root)
    return {**_TINY[cell["traffic"]], **more}


def run(name: str, *, trace: bool = False, root=harness.REPO, seed: int = SEED,
        seconds: float = SECONDS, **more) -> dict:
    """One run of ``name`` on the CPU at its tiny size."""
    return harness.run_cell(name, seed, seconds, trace, t_start=time.perf_counter(),
                            root=root, device=dict(CPU),
                            overrides=overrides(name, root, **more), log=lambda msg: None)


def cells(root=harness.REPO) -> list[str]:
    """Every cell of ``BENCHMARK.json``."""
    return [w["name"] for w in harness.read_json(root / "BENCHMARK.json")["workloads"]]
