"""Solves back to back: one caller, one large problem at a time.

The cell's pool of problems (`pool`: ``pool`` problems that the
configuration's family draws from the seed) is built in set-up and held on
the device. Solve ``i`` takes pool problem ``i mod pool`` with a key of its
own, ``fold_in(key, i)``, so every solve draws a fresh sketch. Every solve
runs the cell's ``max_iter`` iterations, so every seed does the same work.
Each solve is timed from the call to its value on the host; no solve starts
after the window's last second. After the window, `describe` gives each
phase's host seconds over the window's solves (the ``spar_sink.`` spans'
histograms, reset after the warm-up), so a far-off solve shows its phase.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from bench import generator, harness
from bench.check import Answer, check_all

#: the pool a CPU test holds
TINY = {"pool": 2}
PHASES = "spar_sink."


@dataclass
class State:
    params: dict
    pool: list  # generator.Measures
    problems: list  # the program's problem objects, on the device
    base_key: jax.Array
    s: float
    solutions: list = field(default_factory=list)


def pool(params: dict, seed: int, root=harness.REPO) -> list:
    """The cell's pool of `generator.Measures`, drawn by its family."""
    fam = harness.family(params, root)
    return [dataclasses.replace(m, family=fam) for m in fam.pool(params, seed)]


def budget(params: dict, pool: list) -> float:
    """The sketch budget ``s = s_mult s0(n)``."""
    return params["s_mult"] * generator.s0(pool[0].n)


def _program_problem(m):
    from repro.core import OTProblem, PointCloudGeometry, UOTProblem

    geom = PointCloudGeometry(jnp.asarray(m.x, jnp.float32), **m.geometry())
    a, b = jnp.asarray(m.a, jnp.float32), jnp.asarray(m.b, jnp.float32)
    if m.lam is None:
        return OTProblem(geom, a, b, m.eps)
    return UOTProblem(geom, a, b, m.eps, lam=m.lam)


def _solve(state: State, problem, key):
    from repro.core import solve

    p = state.params
    return solve(problem, method=p["method"], stabilize=p["stabilize"], key=key, s=state.s,
                 tol=p["tol"], max_iter=p["max_iter"])


def setup(params: dict, seed: int, seconds: float, log, root=harness.REPO) -> State:
    from repro.obs import default_registry

    t0 = time.perf_counter()
    ms = pool(params, seed, root)
    problems = [_program_problem(m) for m in ms]
    jax.block_until_ready([(q.a, q.b, q.geom.x) for q in problems])
    base = jax.random.PRNGKey(generator.key_seed(seed, 1))
    state = State(params, ms, problems, base, budget(params, ms))
    t1 = time.perf_counter()
    # warm-up: the cell's one shape, pool problem 0 with a key of its own
    warm_key = jax.random.fold_in(jax.random.PRNGKey(generator.key_seed(seed, 2)), 0)
    warm = _solve(state, problems[0], warm_key)
    float(warm.value)
    default_registry.reset(PHASES)
    t2 = time.perf_counter()
    log(f"setup_parts data_s={t1 - t0:.6f} warm_solve_s={t2 - t1:.6f}")
    return state


def run(state: State, seconds: float, tracer) -> dict:
    """The window. Returns the record the metric readers see."""
    calls = []
    start = time.perf_counter()
    end = start + seconds
    i = 0
    while time.perf_counter() < end or i == 0:
        key = jax.random.fold_in(state.base_key, i)
        problem = state.problems[i % len(state.problems)]
        t0 = time.perf_counter()
        with tracer.span("bench.solve"):
            sol = _solve(state, problem, key)
            value = float(sol.value)
        t1 = time.perf_counter()
        state.solutions.append(sol)
        calls.append({"start": t0 - start, "end": t1 - start, "value": value})
        i += 1
    for c, sol in zip(calls, state.solutions):
        c.update(n_iter=int(sol.n_iter), status=sol.status_label, nnz=int(sol.nnz),
                 overflowed=bool(sol.overflowed), cap=int(sol.plan().cap))
    window = calls[-1]["end"]
    return {"kind": "solve_stream", "window_s": window, "calls": calls}


def describe(record: dict) -> str:
    """Each solve's seconds, iterations, status and nnz; then, one line a
    phase, the count, mean and p99 of its host seconds over the window."""
    from repro.obs import default_registry

    calls = record["calls"]
    lines = ["solves " + " ".join(
        f"[{c['end'] - c['start']:.6f}s it={c['n_iter']} {c['status']} nnz={c['nnz']}]"
        for c in calls)]
    for name, h in sorted(default_registry.snapshot()["histograms"].items()):
        if name.startswith(PHASES):
            lines.append(f"phase {name} count={h['count']} mean_s={h['mean']!r} p99_s={h['p99']!r}")
    return "\n".join(lines)


def failed(record: dict, state: State) -> int:
    return sum(c["status"] in ("non_finite", "degenerate") or c["overflowed"]
               for c in record["calls"])


def collect(state: State, record: dict, seed: int, check: dict) -> list:
    """The answers to check, on the host: ``check["solves"]`` of the
    window's solves, drawn from the seed (all of them where fewer). The
    program's arrays are dropped afterwards."""
    n = len(state.solutions)
    pick = sorted(generator.rng(seed, 5).permutation(n)[: check["solves"]].tolist())
    out = [(state.pool[i % len(state.pool)], Answer.from_solution(state.solutions[i]))
           for i in pick]
    state.solutions.clear()
    state.problems.clear()
    return out


def verify(state: State, samples: list, check: dict) -> dict:
    p = state.params
    return check_all(samples, state.s, tol=p["tol"], max_iter=p["max_iter"],
                     draw=check.get("draw", False))
