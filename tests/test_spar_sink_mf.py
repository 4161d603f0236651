"""Matrix-free Spar-Sink: PointCloudGeometry guard + gathered entries,
factorized-sampler parity (shared-variate bitwise vs the dense-sketch path),
production-mode consistency, the no-O(n^2)-allocation trace guard, overflow
flags, and sorted-COO invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr

from repro.core import (
    Geometry,
    OTProblem,
    PointCloudGeometry,
    UOTProblem,
    build_coo_sketch,
    build_mf_sketch,
    s0,
    solve,
)
from repro.core import sparsify
from repro.core.sinkhorn import generic_scaling_loop
from repro.core.spar_sink import coo_objective_ot_entries, default_cap

EPS = 0.1
N = 256


def _points(n, d=4, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.uniform(size=(n, d)))
    a = jnp.asarray(rng.dirichlet(np.ones(n)))
    b = jnp.asarray(rng.dirichlet(np.ones(n)))
    return x, a, b


@pytest.fixture(scope="module")
def mf_problem():
    x, a, b = _points(N)
    return OTProblem(PointCloudGeometry(x), a, b, EPS)


@pytest.fixture(scope="module")
def dense_problem():
    x, a, b = _points(N)
    return OTProblem(Geometry.from_points(x), a, b, EPS)


# --------------------------------------------------------------------------
# PointCloudGeometry: guard + dense parity + gathered entries
# --------------------------------------------------------------------------


def test_pointcloud_dense_access_bitwise_below_guard():
    x, _, _ = _points(64)
    pc = PointCloudGeometry(x)
    dense = Geometry.from_points(x)
    np.testing.assert_array_equal(np.asarray(pc.cost), np.asarray(dense.cost))
    np.testing.assert_array_equal(
        np.asarray(pc.kernel(EPS)), np.asarray(dense.kernel(EPS))
    )
    pcw = PointCloudGeometry(x, cost="wfr", eta=0.2)
    densew = Geometry.wfr(x, eta=0.2)
    np.testing.assert_array_equal(np.asarray(pcw.cost), np.asarray(densew.cost))


def test_pointcloud_classmethod_ctors_build_point_clouds():
    """Geometry's classmethods would hand a dense cost matrix to
    PointCloudGeometry.__init__ as support points — the overrides must
    build real point-cloud geometries (or refuse where no matrix-free
    form exists)."""
    x, _, _ = _points(64)
    pc = PointCloudGeometry.from_points(x)
    assert isinstance(pc, PointCloudGeometry) and pc.shape == (64, 64)
    np.testing.assert_array_equal(
        np.asarray(pc.cost), np.asarray(Geometry.from_points(x).cost)
    )
    pcw = PointCloudGeometry.wfr(x, eta=0.3)
    assert pcw.cost_name == "wfr" and pcw.eta == 0.3
    pcg = PointCloudGeometry.from_grid(8, 8, eta=0.5)
    assert isinstance(pcg, PointCloudGeometry) and pcg.shape == (64, 64)
    np.testing.assert_array_equal(
        np.asarray(pcg.cost), np.asarray(Geometry.from_grid(8, 8, eta=0.5).cost)
    )
    with pytest.raises(TypeError):
        PointCloudGeometry.from_cost(jnp.eye(4))
    with pytest.raises(TypeError):
        PointCloudGeometry.wfr(x, d=jnp.zeros((64, 64)))
    # normalize goes through the (guarded) dense escape hatch, like the base
    assert not isinstance(PointCloudGeometry.from_points(x, normalize=True),
                          PointCloudGeometry)


def test_mf_sketch_nnz_prefix_and_no_duplicates():
    """The first nnz entries are exactly the realized sketch (no zero holes,
    no trailing mass) and kept pairs are unique — incl. the thinned UOT
    path, whose rejections would otherwise leave holes."""
    x, a, b = _points(N, seed=4)
    for problem in (
        OTProblem(PointCloudGeometry(x), a, b, EPS),
        UOTProblem(PointCloudGeometry(x, cost="wfr", eta=0.5), a * 5, b * 3,
                   EPS, lam=0.5),
    ):
        sk, c_e = build_mf_sketch(problem, jax.random.PRNGKey(1), 8 * s0(N))
        nnz = int(sk.nnz)
        vals = np.asarray(sk.vals)
        assert (vals[:nnz] != 0).all()
        assert (vals[nnz:] == 0).all()
        pairs = list(zip(np.asarray(sk.rows)[:nnz], np.asarray(sk.cols)[:nnz]))
        assert len(pairs) == len(set(pairs))  # duplicates merged
        assert c_e.shape == sk.vals.shape  # costs stay index-aligned


def test_pointcloud_refuses_dense_above_guard():
    x, _, _ = _points(64)
    pc = PointCloudGeometry(x, dense_guard=32)
    with pytest.raises(ValueError, match="refuses dense"):
        pc.cost
    with pytest.raises(ValueError, match="refuses dense"):
        pc.kernel(EPS)
    with pytest.raises(ValueError, match="refuses dense"):
        pc.log_kernel(EPS)
    # entry-wise and tile access stay available
    k_e, c_e = pc.entries(jnp.arange(8), jnp.arange(8), EPS)
    assert k_e.shape == (8,) and c_e.shape == (8,)
    assert pc.cost_block(0, 16, 0, 16).shape == (16, 16)
    with pytest.raises(KeyError):
        PointCloudGeometry(x, cost="euclidean")  # not matrix-free-supported


def test_gathered_entries_match_dense():
    x, _, _ = _points(96, seed=3)
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.integers(0, 96, 500), jnp.int32)
    cols = jnp.asarray(rng.integers(0, 96, 500), jnp.int32)
    for kwargs, geom in (
        (dict(), Geometry.from_points(x)),
        (dict(cost="wfr", eta=0.15), Geometry.wfr(x, eta=0.15)),
    ):
        pc = PointCloudGeometry(x, **kwargs)
        c_ref = geom.cost[rows, cols]
        k_ref = geom.kernel(EPS)[rows, cols]
        k_e, c_e = pc.entries(rows, cols, EPS, impl="jnp")
        np.testing.assert_allclose(np.asarray(c_e), np.asarray(c_ref), rtol=1e-9)
        np.testing.assert_allclose(np.asarray(k_e), np.asarray(k_ref), rtol=1e-9)
        np.testing.assert_allclose(
            np.asarray(pc.cost_block(8, 40, 16, 56)),
            np.asarray(geom.cost[8:40, 16:56]),
            rtol=1e-12,
        )
    # WFR blocked pairs: kernel exactly 0, cost +inf
    pcw = PointCloudGeometry(x, cost="wfr", eta=0.15)
    k_e, c_e = pcw.entries(rows, cols, EPS, impl="jnp")
    blocked = np.isinf(np.asarray(Geometry.wfr(x, eta=0.15).cost))[rows, cols]
    assert blocked.any()
    np.testing.assert_array_equal(np.asarray(k_e)[blocked], 0.0)
    assert np.all(np.isinf(np.asarray(c_e)[blocked]))


# --------------------------------------------------------------------------
# Shared-variate parity: bitwise-identical scalings vs spar_sink_coo
# --------------------------------------------------------------------------


def test_shared_variates_bitwise_matches_coo(mf_problem, dense_problem):
    key = jax.random.PRNGKey(7)
    s = 8 * s0(N)
    ref = solve(dense_problem, method="spar_sink_coo", key=key, s=s,
                tol=1e-9, max_iter=5000)
    sol = solve(mf_problem, method="spar_sink_mf", key=key, s=s,
                shared_variates=True, tol=1e-9, max_iter=5000)
    assert bool(jnp.all(sol.result.u == ref.result.u))
    assert bool(jnp.all(sol.result.v == ref.result.v))
    assert int(sol.result.n_iter) == int(ref.result.n_iter)
    assert int(sol.nnz) == int(ref.nnz)
    # the objective runs on gathered costs: equal up to rounding only
    np.testing.assert_allclose(float(sol.value), float(ref.value), rtol=1e-9)


def test_shared_variates_bitwise_matches_coo_uot():
    x, a, b = _points(N, seed=5)
    key = jax.random.PRNGKey(11)
    s = 8 * s0(N)
    ref = solve(UOTProblem(Geometry.wfr(x, eta=0.5), a * 5, b * 3, EPS, lam=0.5),
                method="spar_sink_coo", key=key, s=s, tol=1e-9, max_iter=5000)
    sol = solve(
        UOTProblem(PointCloudGeometry(x, cost="wfr", eta=0.5), a * 5, b * 3,
                   EPS, lam=0.5),
        method="spar_sink_mf", key=key, s=s, shared_variates=True,
        tol=1e-9, max_iter=5000,
    )
    assert bool(jnp.all(sol.result.u == ref.result.u))
    assert bool(jnp.all(sol.result.v == ref.result.v))
    np.testing.assert_allclose(float(sol.value), float(ref.value), rtol=1e-9)


# --------------------------------------------------------------------------
# Production mode: consistency within sampling noise
# --------------------------------------------------------------------------


def test_mf_value_within_sampling_noise(mf_problem, dense_problem):
    truth = float(solve(dense_problem, method="dense", tol=1e-9,
                        max_iter=20_000).value)
    s = 16 * s0(N)
    vals_mf = [
        float(solve(mf_problem, method="spar_sink_mf",
                    key=jax.random.PRNGKey(i), s=s,
                    tol=1e-9, max_iter=20_000).value)
        for i in range(6)
    ]
    vals_coo = [
        float(solve(dense_problem, method="spar_sink_coo",
                    key=jax.random.PRNGKey(i), s=s,
                    tol=1e-9, max_iter=20_000).value)
        for i in range(6)
    ]
    err_mf = np.mean([abs(v - truth) / abs(truth) for v in vals_mf])
    err_coo = np.mean([abs(v - truth) / abs(truth) for v in vals_coo])
    # same estimand, same budget: the Poissonized draw tracks the Bernoulli
    # sketch's accuracy (not a tighter claim — both are Monte Carlo)
    assert err_mf < max(2.0 * err_coo, 0.25), (err_mf, err_coo)


def test_mf_uot_thinning_consistent():
    x, a, b = _points(N, seed=9)
    a, b = a * 5, b * 3
    lam = 0.5
    dense = UOTProblem(Geometry.wfr(x, eta=0.5), a, b, EPS, lam=lam)
    mf = UOTProblem(PointCloudGeometry(x, cost="wfr", eta=0.5), a, b, EPS, lam=lam)
    truth = float(solve(dense, method="dense", tol=1e-9, max_iter=20_000).value)
    vals = [
        float(solve(mf, method="spar_sink_mf", key=jax.random.PRNGKey(i),
                    s=32 * s0(N), tol=1e-9, max_iter=20_000).value)
        for i in range(6)
    ]
    err = np.mean([abs(v - truth) / abs(truth) for v in vals])
    assert err < 0.5, (err, vals, truth)
    # the acceptance-thinning branch genuinely fires: the same proposal
    # stream with thinning keeps strictly fewer entries than without
    s = 32 * s0(N)
    cap = default_cap(s)
    c_ab = lam / (2.0 * lam + EPS)
    qa, qb = a ** c_ab, b ** c_ab
    qa, qb = qa / jnp.sum(qa), qb / jnp.sum(qb)
    entries = lambda r, c: mf.geom.entries(r, c, EPS, impl="jnp")
    key = jax.random.PRNGKey(0)
    sk_thin, _ = sparsify.sparsify_coo_mf(
        key, qa, qb, s, cap, entries, thin_scale=1.0 / (2.0 * lam + EPS)
    )
    sk_all, _ = sparsify.sparsify_coo_mf(key, qa, qb, s, cap, entries)
    assert int(sk_thin.nnz) < int(sk_all.nnz)


def test_mf_unbiased_sketch_small():
    """E[K~] = K entry-wise for the Poissonized factorized draw."""
    x, a, b = _points(48, seed=2)
    pc = PointCloudGeometry(x)
    problem = OTProblem(pc, a, b, EPS)
    K = Geometry.from_points(x).kernel(EPS)
    acc = jnp.zeros((48, 48))
    n_rep = 300
    for i in range(n_rep):
        sk, _ = build_mf_sketch(problem, jax.random.PRNGKey(i), 400.0)
        acc = acc.at[sk.rows, sk.cols].add(sk.vals)
    mean = np.asarray(acc / n_rep)
    assert np.abs(mean - np.asarray(K)).mean() < 0.05 * np.asarray(K).mean() + 0.02


# --------------------------------------------------------------------------
# The Õ(n) guarantee: no (n, m) array in the traced computation
# --------------------------------------------------------------------------


def _max_aval_elems(jaxpr) -> int:
    biggest = 1

    def walk(jp):
        nonlocal biggest
        for eqn in jp.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                shape = getattr(aval, "shape", None)
                if shape:
                    biggest = max(biggest, int(np.prod(shape)))
            for param in eqn.params.values():
                for sub in jax.tree_util.tree_leaves(
                    param, is_leaf=lambda p: isinstance(p, ClosedJaxpr)
                ):
                    if isinstance(sub, ClosedJaxpr):
                        walk(sub.jaxpr)

    walk(jaxpr.jaxpr if isinstance(jaxpr, ClosedJaxpr) else jaxpr)
    return biggest


def test_mf_solve_never_allocates_n_squared():
    """Trace the full matrix-free pipeline (sketch + iteration + objective)
    at n = 2^17 and assert every intermediate stays far below n*m."""
    n = 2 ** 17
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(size=(n, 2)), jnp.float32)
    a = jnp.asarray(rng.dirichlet(np.ones(n)))
    b = jnp.asarray(rng.dirichlet(np.ones(n)))
    problem = OTProblem(PointCloudGeometry(x), a, b, EPS)
    s = 100_000.0
    cap = default_cap(s)

    def mf_core(key):
        sk, c_e = build_mf_sketch(problem, key, s, cap=cap)
        res = generic_scaling_loop(
            lambda v: sparsify.coo_matvec(sk, v),
            lambda u: sparsify.coo_rmatvec(sk, u),
            a, b, 1.0, tol=1e-3, max_iter=20,
        )
        return res.u, res.v, coo_objective_ot_entries(sk, c_e, res, EPS), sk.nnz

    jaxpr = jax.make_jaxpr(mf_core)(jax.random.PRNGKey(0))
    biggest = _max_aval_elems(jaxpr)
    assert biggest < 100 * n, biggest  # O(n + cap); n*m would be 1.7e10


def test_mf_traced_solve_never_allocates_n_squared():
    """Telemetry keeps the Õ(n) guarantee: the trace=True matrix-free
    pipeline at n = 2^17 adds only the O(trace_len) ring buffer, never an
    (n, m) intermediate."""
    n = 2 ** 17
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(size=(n, 2)), jnp.float32)
    a = jnp.asarray(rng.dirichlet(np.ones(n)))
    b = jnp.asarray(rng.dirichlet(np.ones(n)))
    problem = OTProblem(PointCloudGeometry(x), a, b, EPS)
    s = 100_000.0
    cap = default_cap(s)

    def mf_traced_core(key):
        sk, c_e = build_mf_sketch(problem, key, s, cap=cap)
        res = generic_scaling_loop(
            lambda v: sparsify.coo_matvec(sk, v),
            lambda u: sparsify.coo_rmatvec(sk, u),
            a, b, 1.0, tol=1e-3, max_iter=20, trace=True,
        )
        return res.u, res.v, res.trace, coo_objective_ot_entries(sk, c_e, res, EPS)

    jaxpr = jax.make_jaxpr(mf_traced_core)(jax.random.PRNGKey(0))
    biggest = _max_aval_elems(jaxpr)
    assert biggest < 100 * n, biggest  # O(n + cap + trace_len)


def test_mf_stabilized_log_solve_never_allocates_n_squared():
    """Acceptance: the log-domain matrix-free path (spar_sink_mf with
    stabilize=True) keeps the Õ(n) guarantee — trace sketch + potential
    iteration + objective at n = 2^17 and assert nothing is near n*m."""
    from repro.batch.solvers import sparse_log_potentials
    from repro.core import build_mf_log_sketch
    from repro.core.sinkhorn import _masked_log
    from repro.core.spar_sink import coo_objective_ot_log_entries

    n = 2 ** 17
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(size=(n, 2)), jnp.float32)
    a = jnp.asarray(rng.dirichlet(np.ones(n)))
    b = jnp.asarray(rng.dirichlet(np.ones(n)))
    problem = OTProblem(PointCloudGeometry(x), a, b, 1e-3)
    s = 100_000.0
    cap = default_cap(s)

    def mf_log_core(key):
        sk, c_e = build_mf_log_sketch(problem, key, s, cap=cap)
        f, g, t, err, status = sparse_log_potentials(
            sk.rows[None], sk.cols[None], sk.logvals[None], sk.csort[None],
            _masked_log(a)[None], _masked_log(b)[None],
            jnp.asarray([1e-3], a.dtype), jnp.asarray([1.0], a.dtype),
            n=n, m=n, tol=1e-3, max_iter=20,
        )
        from repro.core.sinkhorn import SinkhornResult

        res = SinkhornResult(f[0], g[0], t[0], err[0], status[0])
        return res.u, res.v, coo_objective_ot_log_entries(sk, c_e, res, 1e-3)

    jaxpr = jax.make_jaxpr(mf_log_core)(jax.random.PRNGKey(0))
    biggest = _max_aval_elems(jaxpr)
    assert biggest < 100 * n, biggest  # O(n + cap); n*m would be 1.7e10


def test_mf_certified_solve_never_allocates_n_squared():
    """Acceptance: certify=True keeps the Õ(n) guarantee — the certificate
    is O(cap + n) math, so the full spar_sink_mf solve (scaling and
    stabilized-log domains) still traces without any (n, m) intermediate
    at n = 2^17."""
    n = 2 ** 17
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(size=(n, 2)), jnp.float32)
    a = jnp.asarray(rng.dirichlet(np.ones(n)))
    b = jnp.asarray(rng.dirichlet(np.ones(n)))
    s = 100_000.0

    for eps, stabilize in ((EPS, False), (1e-3, True)):
        problem = OTProblem(PointCloudGeometry(x), a, b, eps)

        def certified_core(key, problem=problem, stabilize=stabilize):
            sol = solve(problem, method="spar_sink_mf", key=key, s=s,
                        tol=1e-3, max_iter=20, stabilize=stabilize,
                        certify=True)
            return sol.value, sol.certificate

        jaxpr = jax.make_jaxpr(certified_core)(jax.random.PRNGKey(0))
        biggest = _max_aval_elems(jaxpr)
        assert biggest < 100 * n, (stabilize, biggest)  # O(n + cap)


def test_mf_end_to_end_2e17_completes():
    """Acceptance: solve(problem, method='spar_sink_mf') at n = 2^17 on CPU
    completes (the geometry guard makes any dense fallback raise)."""
    n = 2 ** 17
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.uniform(size=(n, 2)), jnp.float32)
    a = jnp.asarray(rng.dirichlet(np.ones(n)))
    b = jnp.asarray(rng.dirichlet(np.ones(n)))
    problem = OTProblem(PointCloudGeometry(x), a, b, 0.05)
    sol = solve(problem, method="spar_sink_mf", key=jax.random.PRNGKey(0),
                s=150_000.0, tol=1e-3, max_iter=30)
    assert np.isfinite(float(sol.value))
    assert sol.result.u.shape == (n,)
    assert int(sol.nnz) > 0
    plan = sol.plan()
    assert plan.rows.shape == plan.vals.shape  # O(cap) plan, never dense


# --------------------------------------------------------------------------
# Overflow flag + sorted-COO invariants (satellites)
# --------------------------------------------------------------------------


def test_overflow_flag_on_truncation(mf_problem, dense_problem):
    s = 8 * s0(N)
    tiny_cap = 64
    sol = solve(dense_problem, method="spar_sink_coo",
                key=jax.random.PRNGKey(0), s=s, cap=tiny_cap,
                tol=1e-6, max_iter=500)
    assert bool(sol.overflowed)
    assert int(sol.nnz) == tiny_cap  # truncated to capacity
    assert np.isfinite(float(sol.value))
    assert sol.plan().rows.shape == (tiny_cap,)
    sol_mf = solve(mf_problem, method="spar_sink_mf",
                   key=jax.random.PRNGKey(0), s=s, cap=tiny_cap,
                   tol=1e-6, max_iter=500)
    assert bool(sol_mf.overflowed)
    # ample capacity: flag off
    ok = solve(dense_problem, method="spar_sink_coo",
               key=jax.random.PRNGKey(0), s=s, tol=1e-6, max_iter=500)
    assert not bool(ok.overflowed)


def test_coo_sketch_sorted_invariants(dense_problem):
    sk = build_coo_sketch(dense_problem, jax.random.PRNGKey(3), 8 * s0(N))
    rows, cols = np.asarray(sk.rows), np.asarray(sk.cols)
    assert (np.diff(rows) >= 0).all()  # sorted by row, padding at the end
    assert (np.diff(cols[np.asarray(sk.csort)]) >= 0).all()
    x, a, b = _points(N)
    sk_mf, _ = build_mf_sketch(
        OTProblem(PointCloudGeometry(x), a, b, EPS),
        jax.random.PRNGKey(3), 8 * s0(N),
    )
    assert (np.diff(np.asarray(sk_mf.rows)) >= 0).all()
    assert (np.diff(np.asarray(sk_mf.cols)[np.asarray(sk_mf.csort)]) >= 0).all()


def test_rand_sink_factorized_uniform_matches_dense_probs(dense_problem):
    """Factorized uniform factors reproduce the dense uniform_probs draw
    bitwise (n, m powers of two -> exact products)."""
    key = jax.random.PRNGKey(5)
    s = 8 * s0(N)
    ref = solve(dense_problem, method="spar_sink_coo", key=key, s=s,
                probs=sparsify.uniform_probs(N, N, dense_problem.geom.dtype),
                tol=1e-9, max_iter=5000)
    sol = solve(dense_problem, method="rand_sink", key=key, s=s,
                tol=1e-9, max_iter=5000)
    assert float(sol.value) == float(ref.value)
    assert bool(jnp.all(sol.result.u == ref.result.u))


def test_batched_mf_bitwise_matches_per_problem():
    from repro.batch import BucketedExecutor

    problems, keys = [], []
    for i, (n, seed) in enumerate(((128, 0), (96, 1), (128, 2))):
        x, a, b = _points(n, seed=seed)
        geom = PointCloudGeometry(x)
        if i == 1:
            problems.append(UOTProblem(geom, a * 2, b * 3, EPS, lam=0.5))
        else:
            problems.append(OTProblem(geom, a, b, EPS))
        keys.append(jax.random.PRNGKey(40 + i))
    s = 8 * s0(128)
    sols = BucketedExecutor().solve_batch(
        problems, method="spar_sink_mf", keys=keys, s=s, tol=1e-9, max_iter=3000
    )
    for p, k, sol in zip(problems, keys, sols):
        ref = solve(p, method="spar_sink_mf", key=k, s=s, tol=1e-9, max_iter=3000)
        assert bool(jnp.all(sol.result.u == ref.result.u))
        assert bool(jnp.all(sol.result.v == ref.result.v))
        np.testing.assert_allclose(float(sol.value), float(ref.value), rtol=1e-9)
        assert sol.overflowed is not None and not bool(sol.overflowed)
