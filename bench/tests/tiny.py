"""Sizes at which a test run holds a cell, and a device record that lets a
test drive a run on the CPU. The sizes come from the cell's own plugins:
the pool from its traffic kind's ``TINY``, the problem size from its
family's ``TINY`` (or ``TINY_MERGE``, the size with enough duplicate
draws)."""
import time

from bench import harness

CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SECONDS = 1.5
SEED = 2**31 + 11


def overrides(name: str, root=harness.REPO, *, merge: bool = False, **more) -> dict:
    cell, params = harness.load_cell(name, root)
    fam = harness.family(params, root)
    return {**harness.traffic(cell["traffic"], root).TINY,
            **(fam.TINY_MERGE if merge else fam.TINY), **more}


def run(name: str, *, trace: bool = False, root=harness.REPO, seed: int = SEED,
        seconds: float = SECONDS, merge: bool = False, **more) -> dict:
    """One run of ``name`` on the CPU at its tiny size."""
    return harness.run_cell(name, seed, seconds, trace, t_start=time.perf_counter(),
                            root=root, device=dict(CPU),
                            overrides=overrides(name, root, merge=merge, **more),
                            log=lambda msg: None)


def cells(root=harness.REPO) -> list[str]:
    """Every cell of ``BENCHMARK.json``."""
    return [w["name"] for w in harness.read_json(root / "BENCHMARK.json")["workloads"]]
