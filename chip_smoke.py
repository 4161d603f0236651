"""Chip smoke test: the main Spar-Sink paths, end to end, on one TPU.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # four chips: the batch-sharded
                                       # executor vs one device, nothing else

Each phase prints what it measured, then ``[phase] PASS`` or ``[phase] FAIL``;
any failed check exits non-zero. Phases:

* ``large_log``     — matrix-free ``spar_sink_mf`` (``stabilize=True``) at
  n = 2^17, d = 3, s = 4 s0(n), eps = 1e-2: OT, and UOT with lam = 0.1;
* ``large_kernel``  — the scaling-domain ``spar_sink_mf`` at the same size
  and eps = 0.1 with ``impl="auto"``: the compiled entry evaluation must
  hold the Pallas kernel (``tpu_custom_call``) and agree with ``impl="jnp"``
  on the solve's sampled pairs;
* ``host_parity``   — at n = 2048, chip against the host CPU device: the
  dense ``log`` value, the sketch sampler under one key (array by array),
  and the log-domain ``spar_sink_mf`` iteration on one sketch;
* ``serving``       — 64 mixed OT/UOT requests through `OTServer` over a
  `BucketedExecutor` (a warm-up and a measured stream): no recompiles, and
  every value matches a per-problem ``solve()``.

Runs float32 (``jax_enable_x64`` off), in one process that starts no
children. The last line of stdout is ``{"ok": true, "device": {...}}`` and
is printed only when every phase passed on a TPU.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.batch import BucketedExecutor  # noqa: E402
from repro.batch.problems import BatchedProblem, bucket_shape  # noqa: E402
from repro.batch.solvers import (  # noqa: E402
    build_batched_log_sketch,
    build_batched_mf_log_sketch,
    get_batched_solver,
)
from repro.core import (  # noqa: E402
    OTProblem, PointCloudGeometry, UOTProblem, default_cap, s0, solve,
)
from repro.core.api.solvers import mf_sampling_factors  # noqa: E402
from repro.core.sparsify import factor_draw  # noqa: E402
from repro.data import make_measures  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.launch.serve_ot import OTServer, _make_request_problems  # noqa: E402

S_MULT = 4.0  # sketch budget s = S_MULT * s0(n)
EPS_LOG, EPS_KERNEL = 1e-2, 0.1  # log-domain phases; scaling-domain kernel phase
LAM = 0.1  # benchmarks/bench_rmae_uot.py
SERVE_SIZES = (96, 128, 200, 256)  # the serving CLI's default --sizes
BAD_STATUS = ("non_finite", "degenerate")


class CheckFailed(AssertionError):
    """A smoke check did not hold."""


def _check(cond, what: str) -> None:
    if not bool(cond):
        raise CheckFailed(what)


def _report(phase: str, **fields) -> dict:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)
    return fields


def _problem(kind: str, n: int, seed: int, eps: float) -> OTProblem:
    """C1 point cloud (d = 3) in float32: OT, or UOT with masses 5 and 3."""
    a, b, x = make_measures("C1", n, 3, seed)
    geom = PointCloudGeometry(jnp.asarray(x, jnp.float32))
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    if kind == "OT":
        return OTProblem(geom, a, b, eps)
    return UOTProblem(geom, 5.0 * a, 3.0 * b, eps, lam=LAM)


def _peak_bytes():
    """Device peak memory so far, where the backend reports it."""
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def _check_sketch_solve(sol, what: str) -> dict:
    """The pass rule of a large sketched solve: finite value, potentials
    never NaN or +inf, a non-empty sketch that did not overflow, and a
    status that is neither non_finite nor degenerate. Also counts the live
    atoms (mass > 0, touched by the sketch) and those whose potential is
    -inf (``dead_live``)."""
    status = sol.status_label
    _check(np.isfinite(float(sol.value)), f"{what}: value {float(sol.value)}")
    _check(int(sol.nnz) > 0, f"{what}: empty sketch")
    _check(not bool(sol.overflowed), f"{what}: sketch overflowed its cap")
    _check(status not in BAD_STATUS, f"{what}: status {status}")
    plan = sol.plan()
    used = (jnp.arange(plan.cap) < plan.nnz).astype(jnp.int32)
    f, g = sol.potentials
    live, dead = 0, 0
    for pot, idx, mass in ((f, plan.rows, sol.problem.a), (g, plan.cols, sol.problem.b)):
        _check(not jnp.any(jnp.isnan(pot) | (pot == jnp.inf)), f"{what}: NaN/+inf potential")
        touched = jax.ops.segment_sum(used, idx, num_segments=pot.shape[0]) > 0
        on = touched & (mass > 0)
        live += int(jnp.sum(on))
        dead += int(jnp.sum(on & jnp.isneginf(pot)))
    return dict(status=status, n_iter=int(sol.n_iter), nnz=int(sol.nnz),
                cap=plan.cap, value=float(sol.value), live=live, dead_live=dead)


def phase_large_log(n: int, *, seed: int = 0) -> list[dict]:
    """Matrix-free log-domain Spar-Sink at size n, OT and UOT."""
    s = S_MULT * s0(n)
    out = []
    for kind in ("OT", "UOT"):
        problem = _problem(kind, n, seed, EPS_LOG)
        t0 = time.perf_counter()
        sol = solve(problem, method="spar_sink_mf", stabilize=True,
                    key=jax.random.PRNGKey(seed), s=s).block_until_ready()
        dt = time.perf_counter() - t0
        res = _check_sketch_solve(sol, f"large_log/{kind}")
        _check(res["dead_live"] == 0, f"large_log/{kind}: -inf potentials on live atoms {res}")
        out.append(_report(f"large_log/{kind}", n=n, s=round(s), eps=EPS_LOG, **res,
                           seconds=round(dt, 2), peak_bytes=_peak_bytes()))
    return out


def phase_large_kernel(n: int, *, seed: int = 0) -> dict:
    """Scaling-domain matrix-free Spar-Sink with ``impl="auto"``; reports
    whether its entry evaluation compiles to the Pallas kernel
    (``tpu_custom_call``) and checks it against the jnp path on the solve's
    own sampled pairs. Live atoms may
    end with zero scalings here (``dead_live`` is reported, not checked):
    at n = 2^17 the float32 scalings span ~1e-38..1e28 and a few underflow,
    on the CPU as on the chip — the reason the log domain exists."""
    eps = EPS_KERNEL
    problem = _problem("OT", n, seed, eps)
    geom = problem.geom
    t0 = time.perf_counter()
    sol = solve(problem, method="spar_sink_mf", impl="auto",
                key=jax.random.PRNGKey(seed), s=S_MULT * s0(n)).block_until_ready()
    dt = time.perf_counter() - t0
    res = _check_sketch_solve(sol, "large_kernel")
    plan = sol.plan()
    rows, cols = plan.rows, plan.cols
    auto = jax.jit(lambda r, c: geom.entries(r, c, eps, impl="auto"))
    has_kernel = "tpu_custom_call" in auto.lower(rows, cols).compile().as_text()
    k_p, c_p = auto(rows, cols)
    k_j, c_j = geom.entries(rows, cols, eps, impl="jnp")
    err_c = float(jnp.max(jnp.abs(c_p - c_j)))
    err_k = float(jnp.max(jnp.abs(k_p - k_j) / jnp.maximum(k_j, 1e-30)))
    np.testing.assert_allclose(np.asarray(c_p), np.asarray(c_j), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(k_p), np.asarray(k_j), rtol=2e-4, atol=1e-6)
    return _report("large_kernel", n=n, eps=eps, **res,
                   tpu_custom_call=has_kernel, max_abs_err_C=err_c,
                   max_rel_err_K=err_k, seconds=round(dt, 2),
                   peak_bytes=_peak_bytes())


_draw = jax.jit(factor_draw, static_argnums=(3, 4))


def draw_on(dev, problem, key, s: float) -> dict:
    """`factor_draw`, the matrix-free sampler, for ``problem`` on ``dev``:
    its arrays, the Poisson rates ``lam = s ra``, the column factor ``rb``
    and its CDF, on the host."""
    with jax.default_device(dev):
        ra, rb, _ = mf_sampling_factors(problem)
        d = _draw(key, ra, rb, s, default_cap(s))
        out = {f: np.asarray(getattr(d, f)) for f in ("counts", "rows", "cols", "u")}
        out["lam"] = s * np.asarray(ra, np.float64)
        out["rb"] = np.asarray(rb, np.float64)
        out["cdf"] = np.asarray(jax.jit(jnp.cumsum)(rb), np.float64)
    return out


def compare_draws(t: dict, c: dict) -> dict:
    """One key's draw on the chip (``t``) against the CPU's (``c``), array
    by array (`draw_on`).

    The column uniforms are threefry bits and must agree exactly; each
    device's rows must follow its own counts exactly. The float steps may
    round differently per backend, so:

    * counts may differ in at most max(1, n/100) rows. ``jax.random.poisson``
      draws rates below 10 by Knuth's product of uniforms, where a test at
      a rounding boundary moves the count by 1; above 10 by transformed
      rejection, whose acceptance test compares float32 terms that cancel
      (``k log lam - lgamma(k + 1)``, about 324 - 261 at lam = 68), and
      one flipped test returns another proposal for that row: a count
      within 6 sqrt(lam) of the other;
    * a column may differ, in at most 1e-3 of the slots, only where its
      uniform lies within ``edge_bound`` (twice the two devices' CDF
      rounding, as measured) of the exact CDF's edge between the two
      columns."""
    n, cap = t["counts"].shape[0], t["u"].shape[0]
    _check(np.array_equal(t["u"], c["u"]), "sampler: column uniforms differ")
    for who, d in (("chip", t), ("cpu", c)):
        rows = np.searchsorted(np.cumsum(d["counts"]), np.arange(cap), side="right")
        _check(np.array_equal(d["rows"], np.minimum(rows, n - 1)),
               f"sampler: rows do not follow the counts on the {who}")
    dcount = np.abs(t["counts"].astype(np.int64) - c["counts"])
    count_bound = np.where(c["lam"] < 10, 1, 6 * np.sqrt(c["lam"]))
    edges = np.cumsum(c["rb"])  # the exact CDF of the CPU's factor
    rounding = np.max(np.abs(t["cdf"] - edges)) + np.max(np.abs(c["cdf"] - edges))
    slots = np.flatnonzero(t["cols"] != c["cols"])
    lo = np.minimum(t["cols"], c["cols"])[slots]
    hi = np.maximum(t["cols"], c["cols"])[slots]
    edge_dist = max((float(np.max(np.abs(c["u"][i] - edges[a:b])))
                     for i, a, b in zip(slots, lo, hi)), default=0.0)
    res = dict(counts_rows_differ=int(np.count_nonzero(dcount)),
               counts_max_diff=int(np.max(dcount)),
               total_chip=int(t["counts"].sum()), total_cpu=int(c["counts"].sum()),
               cols_differ=int(slots.size), slots=cap, cols_edge_dist=edge_dist,
               edge_bound=float(2 * rounding))
    _check(res["counts_rows_differ"] <= max(1, n // 100) and np.all(dcount <= count_bound),
           f"sampler: counts {res}")
    _check(slots.size <= 1e-3 * cap and edge_dist <= res["edge_bound"],
           f"sampler: columns {res}")
    return res


def phase_host_parity(n: int, *, seed: int = 0) -> list[dict]:
    """Chip vs host CPU device on the same problem, OT and UOT:

    * the dense ``log`` value (``highest`` matmul precision) within 1e-4
      relative;
    * the sampler under one key, array by array (`compare_draws`);
    * the log-domain ``spar_sink_mf`` iteration on one sketch within 1e-3:
      the sketch is drawn on the CPU and iterated on each device
      (`build_batched_mf_log_sketch` + the batched solver at B = 1, the
      kernel ``solve()`` runs).

    ``solve()`` on each device with the same key is reported
    (``mf_solve_*``), not checked: the few draws that round differently
    move a balanced sketched value by up to about 1%. Sketched solves run
    a fixed 100 iterations: balanced OT on a sketch stalls instead of
    converging, and where the stall rule fires depends on rounding."""
    chip, cpu = jax.devices()[0], jax.devices("cpu")[0]
    key, s = jax.random.PRNGKey(seed), S_MULT * s0(n)
    sketch_opts = dict(stabilize=True, tol=0.0, max_iter=100)
    iterate = jax.jit(functools.partial(get_batched_solver("spar_sink_mf"), **sketch_opts))
    out = []
    for kind in ("OT", "UOT"):
        problems, dense, solved, same = {}, {}, {}, {}
        for dev in (chip, cpu):
            with jax.default_device(dev):
                problems[dev] = problem = _problem(kind, n, seed, EPS_LOG)
                with jax.default_matmul_precision("highest"):
                    dense[dev] = float(solve(problem, method="log").value)
                solved[dev] = solve(problem, method="spar_sink_mf", key=key, s=s,
                                    **sketch_opts)
        sampler = compare_draws(*(draw_on(dev, problems[dev], key, s) for dev in (chip, cpu)))
        with jax.default_device(cpu):
            batch = (BatchedProblem.from_problems([problems[cpu]], materialize_cost=False),
                     build_batched_mf_log_sketch([problems[cpu]], [key], s))
        for dev in (chip, cpu):
            same[dev] = float(iterate(*jax.device_put(batch, dev)).value[0])
        value = {dev: float(sol.value) for dev, sol in solved.items()}
        rel = lambda d: abs(d[chip] - d[cpu]) / abs(d[cpu])  # noqa: E731
        fields = dict(
            n=n, dense_chip=dense[chip], dense_cpu=dense[cpu],
            dense_rel_err=rel(dense), dense_bound=1e-4, **sampler,
            mf_chip=same[chip], mf_cpu=same[cpu], mf_rel_err=rel(same),
            mf_bound=1e-3, mf_iters=sketch_opts["max_iter"], nnz=int(batch[1].nnz[0]),
            mf_solve_chip=value[chip], mf_solve_cpu=value[cpu],
            mf_solve_rel_err=rel(value), mf_solve_nnz_chip=int(solved[chip].nnz),
            mf_solve_nnz_cpu=int(solved[cpu].nnz),
        )
        _check(fields["dense_rel_err"] <= 1e-4 and fields["mf_rel_err"] <= 1e-3,
               f"host_parity/{kind}: {fields}")
        out.append(_report(f"host_parity/{kind}", **fields))
    return out


def _serving_opts(sizes) -> dict:
    """The serving CLI's options for a keyed method (s = 8 s0(max size))."""
    return {"s": 8.0 * s0(max(sizes)), "max_iter": 2000}


def phase_serving(n_requests: int = 64, *, seed: int = 0) -> dict:
    """`OTServer` over a `BucketedExecutor` on the CLI's mixed OT/UOT
    traffic with ``spar_sink_log``: a warm-up stream, then a measured one
    that compiles nothing, every value matching a per-problem ``solve()``."""
    problems = _make_request_problems(n_requests, SERVE_SIZES, seed)
    keys = [jax.random.PRNGKey(i) for i in range(n_requests)]
    opts = _serving_opts(SERVE_SIZES)
    executor = BucketedExecutor()

    def stream(server):
        t0 = time.perf_counter()
        futures = [server.submit(p, method="spar_sink_log", key=k, **opts)
                   for p, k in zip(problems, keys)]
        values = [float(f.result().value) for f in futures]
        return values, time.perf_counter() - t0

    with OTServer(executor, max_batch=16, robust=False) as server:
        _, t_warm = stream(server)
        compiles = executor.compile_count
        server.reset_stats()
        values, t_run = stream(server)
        stats = server.stats()
    _check(executor.compile_count == compiles,
           f"serving: {executor.compile_count - compiles} compiles in the measured stream")
    ref = np.asarray([
        float(solve(p, method="spar_sink_log", key=k, **opts).value)
        for p, k in zip(problems, keys)
    ])
    err = float(np.max(np.abs(np.asarray(values) - ref) / np.abs(ref)))
    _check(err <= 1e-5, f"serving: batched vs per-problem rel err {err} > 1e-5")
    return _report("serving", requests=n_requests, batches=stats["batches"],
                   compiles=compiles, max_rel_err=err, bound=1e-5,
                   warmup_seconds=round(t_warm, 2), seconds=round(t_run, 3))


def phase_four_chips(batch: int = 64, n: int = 256, *, seed: int = 0) -> dict:
    """The batch-sharded executor on a 4-device ``data`` mesh against one
    device: same values, and every batch leaf split over the 4 devices."""
    mesh = make_test_mesh(4, 1)
    problems = _make_request_problems(batch, (n,), seed)
    keys = [jax.random.PRNGKey(i) for i in range(batch)]
    opts = _serving_opts((n,))
    sharded = BucketedExecutor(mesh=mesh)
    # the executor's own placement of this batch: each leaf's leading axis
    # must be split 4 ways, not replicated (`leading_axis_specs` replicates
    # a leading dim the data axes do not divide)
    bp = BatchedProblem.from_problems(problems, bucket=bucket_shape(n, n),
                                      materialize_cost=False)
    aux = build_batched_log_sketch(problems, keys, opts["s"])
    for leaf in jax.tree_util.tree_leaves(sharded._place(bp, aux)):
        _check(len(leaf.sharding.device_set) == 4,
               f"four_chips: leaf {leaf.shape} on {len(leaf.sharding.device_set)} devices")
        shard_rows = {s.data.shape[0] for s in leaf.addressable_shards}
        _check(shard_rows == {batch // 4},
               f"four_chips: leaf {leaf.shape} shards have {shard_rows} rows")
    t0 = time.perf_counter()
    got = sharded.solve_batch(problems, method="spar_sink_log", keys=keys, **opts)
    v_sharded = np.asarray([float(s.value) for s in got])
    dt = time.perf_counter() - t0
    ref = BucketedExecutor().solve_batch(problems, method="spar_sink_log",
                                         keys=keys, **opts)
    v_one = np.asarray([float(s.value) for s in ref])
    err = float(np.max(np.abs(v_sharded - v_one) / np.abs(v_one)))
    _check(err <= 1e-5, f"four_chips: sharded vs one device rel err {err} > 1e-5")
    return _report("four_chips", batch=batch, n=n, devices=4, max_rel_err=err,
                   bound=1e-5, seconds=round(dt, 2))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip batch-sharding phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (found {dev.platform}); nothing run", file=sys.stderr)
        return 2
    if jax.config.jax_enable_x64:
        print("chip_smoke: jax_enable_x64 is on; the chip path runs float32",
              file=sys.stderr)
        return 2
    count = len(jax.devices())
    print(f"[device] platform={dev.platform} kind={dev.device_kind!r} count={count} "
          f"jax={jax.__version__} compile_cache={use_compile_cache()}", flush=True)

    if args.four_chips:
        if count < 4:
            print(f"chip_smoke: --four-chips needs 4 devices, found {count}",
                  file=sys.stderr)
            return 2
        phases = [("four_chips", lambda: phase_four_chips(seed=args.seed))]
    else:
        def large_kernel():
            res = phase_large_kernel(2 ** 17, seed=args.seed)
            _check(res["tpu_custom_call"], "no tpu_custom_call in the impl='auto' entries")

        phases = [
            ("large_log", lambda: phase_large_log(2 ** 17, seed=args.seed)),
            ("large_kernel", large_kernel),
            ("host_parity", lambda: phase_host_parity(2048, seed=args.seed)),
            ("serving", lambda: phase_serving(seed=args.seed)),
        ]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception as e:  # noqa: BLE001 — report every phase, then fail
            failed.append(name)
            print(f"[{name}] FAIL after {time.perf_counter() - t0:.1f}s: "
                  f"{type(e).__name__}: {e}", flush=True)
        else:
            print(f"[{name}] PASS after {time.perf_counter() - t0:.1f}s", flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
