"""The control of a cell's check: the plain reference put in the program's
place, computed in bfloat16, the precision below the configurations'
float32. Its answers go through the same check as the program's, and
must come out not correct.

    python3 bench/control.py --workload pointcloud_c1.ot --seeds 1,2,3

prints, per seed, the numbers the cell compares, read on the control's
answers, one JSON line each. The benchmark's own runs never run it.
"""
from __future__ import annotations

import json
import math
import sys
from pathlib import Path

if __name__ == "__main__":
    REPO = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(REPO), str(REPO / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import check, generator, harness, reference  # noqa: E402


def answer(m, key, s: float, dtype, tol: float, max_iter: int) -> check.Answer:
    """The reference's own answer to problem ``m`` by the configuration's
    stopping rule, every step in ``dtype``."""
    rows, cols, lv = reference.draw(key, m, s, dtype)
    nnz = rows.size
    pad = check.padded(nnz)
    rp, cp, lp = np.zeros(pad, np.int64), np.zeros(pad, np.int64), np.full(pad, -np.inf)
    rp[:nnz], cp[:nnz], lp[:nnz] = rows, cols, lv
    f, g, _ = reference.solve_support(m, rp, cp, lp, dtype=dtype, tol=tol, max_iter=max_iter)
    rnd = lambda v: np.asarray(jnp.asarray(v, dtype), np.float64)  # noqa: E731
    with np.errstate(invalid="ignore", over="ignore"):
        t = rnd(np.exp(rnd(lv + (f[rows] + g[cols]) / m.eps)))
    c_e = rnd(reference.entry_cost(m, rows, cols))
    value = reference.objective(m, rows, cols, rnd(lv), rnd(f), rnd(g), c_e)
    return check.Answer(rows, cols, np.nan_to_num(t, nan=0.0), f, g, float(rnd(value)), nnz)


def readings(name: str, seed: int, *, dtype=jnp.bfloat16, root=harness.REPO,
             overrides: dict | None = None) -> dict:
    """The cell's compared numbers, read on the control's answers to as
    many problems of the seed's pool as a run checks."""
    cell, params = harness.load_cell(name, root)
    params.update(overrides or {})
    traffic = harness.traffic(cell["traffic"], root)
    ms = traffic.pool(params, seed, root)[: cell["check"]["solves"]]
    s = traffic.budget(params, ms)
    base = jax.random.PRNGKey(generator.key_seed(seed, 9))
    samples = [(m, answer(m, jax.random.fold_in(base, i), s, dtype, params["tol"],
                          params["max_iter"]))
               for i, m in enumerate(ms)]
    return check.check_all(samples, s, tol=params["tol"], max_iter=params["max_iter"],
                           draw=cell["check"].get("draw", False))


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    cell, _ = harness.load_cell(args.workload)
    for seed in (int(v) for v in args.seeds.split(",")):
        r = readings(args.workload, seed)
        compared = check.compare(r, cell["limits"])
        print(json.dumps({"seed": seed, "correct": check.passed(compared),
                          **{k: (v if math.isfinite(v) else 1e308) for k, v in r.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
