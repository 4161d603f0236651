"""Log-domain sparse Spar-Sink: the small-eps regression suite.

Covers the tentpole and its acceptance criteria:

* ``spar_sink_log`` / ``spar_sink_mf(stabilize=True)`` stay finite and
  RMAE-comparable to the dense ``log`` oracle at ``eps`` down to 1e-3
  (OT and UOT), where the scaling-domain sketch underflows;
* the old failure mode is pinned: a scaling-domain sparse solve whose
  kernel underflowed now reports ``degenerate`` via the new ``converged``/
  ``status`` flag instead of silently returning an all-zero plan;
* batched ``spar_sink_log`` (and stabilized mf) is bitwise the per-problem
  solver per element;
* convergence statuses (tol / max_iter / stall / non-finite / degenerate)
  and the unified ``tol`` default across registered methods.
"""
import inspect
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hlo import has_scatter, while_body_text
from repro.batch import solvers as batch_solvers
from repro.core import (
    Geometry,
    OTProblem,
    PointCloudGeometry,
    STATUS_CONVERGED,
    STATUS_DEGENERATE,
    STATUS_MAX_ITER,
    STATUS_NONFINITE,
    STATUS_STALL,
    UOTProblem,
    available_methods,
    build_coo_log_sketch,
    build_coo_sketch,
    build_mf_log_sketch,
    s0,
    solve,
)
from repro.core import sparsify
from repro.core.api.registry import get_solver
from repro.core.api.solvers import DEFAULT_TOL
from repro.core.sinkhorn import (
    generic_scaling_loop,
    generic_sparse_log_loop,
    sinkhorn_log,
)

N = 128
S = 16 * s0(N)


def _measures(n=N, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.uniform(size=(n, 4)))
    a = jnp.asarray(rng.dirichlet(np.ones(n)))
    b = jnp.asarray(rng.dirichlet(np.ones(n)))
    return x, a, b


@pytest.fixture(scope="module")
def separated():
    """Two separated clouds (costs bounded below ~0.1): the objective stays
    O(1) across the whole eps sweep, so RMAE vs the oracle is meaningful."""
    x, a, b = _measures()
    perm = np.asarray(jax.random.permutation(jax.random.PRNGKey(9), N))
    y = x[perm] + 0.5
    return x, y, a, b


def _rmae(problem, method, s, n_rep=3, **kw):
    truth = float(solve(problem, method="log", tol=1e-10, max_iter=50_000).value)
    vals = [
        float(
            solve(problem, method=method, key=jax.random.PRNGKey(i), s=s,
                  tol=1e-9, max_iter=3000, **kw).value
        )
        for i in range(n_rep)
    ]
    assert all(np.isfinite(v) for v in vals), (method, vals)
    return float(np.mean([abs(v - truth) / abs(truth) for v in vals]))


# --------------------------------------------------------------------------
# Acceptance: small-eps RMAE vs the dense log oracle
# --------------------------------------------------------------------------


def test_small_eps_rmae_within_2x_of_coo_baseline_ot(separated):
    """RMAE of the log-domain sparse solvers at eps = 1e-3 must be within
    2x what spar_sink_coo achieves at eps = 0.1, at matched s (the
    acceptance criterion: today the scaling path returns garbage there)."""
    x, y, a, b = separated
    geom, pc = Geometry.from_points(x, y), PointCloudGeometry(x, y)
    base = _rmae(OTProblem(geom, a, b, 0.1), "spar_sink_coo", S)
    r_log = _rmae(OTProblem(geom, a, b, 1e-3), "spar_sink_log", S)
    r_mf = _rmae(OTProblem(pc, a, b, 1e-3), "spar_sink_mf", S, stabilize=True)
    assert r_log <= 2.0 * base, (r_log, base)
    assert r_mf <= 2.0 * base, (r_mf, base)


def test_small_eps_rmae_within_2x_of_coo_baseline_uot(separated):
    x, y, a, b = separated
    geom, pc = Geometry.from_points(x, y), PointCloudGeometry(x, y)
    aw, bw = a * 5.0, b * 3.0
    base = _rmae(UOTProblem(geom, aw, bw, 0.1, lam=0.5), "spar_sink_coo", 2 * S)
    r_log = _rmae(UOTProblem(geom, aw, bw, 1e-3, lam=0.5), "spar_sink_log", 2 * S)
    r_mf = _rmae(
        UOTProblem(pc, aw, bw, 1e-3, lam=0.5), "spar_sink_mf", 2 * S,
        stabilize=True,
    )
    assert r_log <= 2.0 * base, (r_log, base)
    assert r_mf <= 2.0 * base, (r_mf, base)


@pytest.mark.parametrize("eps", [1e-1, 1e-2, 1e-3])
def test_log_sparse_finite_across_eps_sweep(separated, eps):
    """Every log-domain sparse path stays finite (and sane) over the paper's
    eps sweep; the Solution is domain="log" with a potential-based plan."""
    x, y, a, b = separated
    problem = OTProblem(Geometry.from_points(x, y), a, b, eps)
    sol = solve(problem, method="spar_sink_log", key=jax.random.PRNGKey(0),
                s=S, tol=1e-9, max_iter=3000)
    truth = float(solve(problem, method="log", tol=1e-10, max_iter=50_000).value)
    assert sol.domain == "log"
    assert np.isfinite(float(sol.value))
    # single-key Monte Carlo estimate: a loose sanity band (the tight RMAE
    # claim is the averaged acceptance test above)
    assert abs(float(sol.value) - truth) / abs(truth) < 2.5
    plan = sol.plan()
    vals = np.asarray(plan.vals)
    assert np.isfinite(vals).all()
    assert abs(float(plan.total_mass()) - 1.0) < 0.15
    mf = solve(OTProblem(PointCloudGeometry(x, y), a, b, eps),
               method="spar_sink_mf", key=jax.random.PRNGKey(0), s=S,
               stabilize=True, tol=1e-9, max_iter=3000)
    assert np.isfinite(float(mf.value))
    assert mf.domain == "log"


# --------------------------------------------------------------------------
# Pinned regression: the old silent-zero failure now reports loudly
# --------------------------------------------------------------------------


def test_scaling_sparse_at_small_eps_reports_degenerate():
    """eps = 1e-3 with costs >= ~4 underflows every exp(-C/eps) to an exact
    zero in f64: the scaling-domain sketch used to 'converge' to all-zero
    scalings silently. It must now flag STATUS_DEGENERATE — and the
    log-domain solver must actually solve the same problem."""
    x, a, b = _measures(seed=3)
    problem = OTProblem(Geometry.from_points(x, x + 2.0), a, b, 1e-3)
    key = jax.random.PRNGKey(0)
    coo = solve(problem, method="spar_sink_coo", key=key, s=S,
                tol=1e-9, max_iter=2000)
    assert int(coo.status) == STATUS_DEGENERATE
    assert bool(coo.converged) is False
    assert float(coo.value) == 0.0  # the degenerate all-zero plan
    assert np.all(np.asarray(coo.plan().vals) == 0.0)
    # the log-domain sketch on the same key solves it
    lg = solve(problem, method="spar_sink_log", key=key, s=S,
               tol=1e-9, max_iter=3000)
    truth = float(solve(problem, method="log", tol=1e-10, max_iter=50_000).value)
    assert np.isfinite(float(lg.value))
    assert abs(float(lg.value) - truth) / abs(truth) < 0.5
    assert float(lg.plan().total_mass()) > 0.5


# --------------------------------------------------------------------------
# Convergence statuses (satellite: silent NaN / degenerate exits)
# --------------------------------------------------------------------------


def test_status_converged_and_max_iter():
    x, a, b = _measures(seed=1)
    problem = OTProblem(Geometry.from_points(x), a, b, 0.1)
    ok = solve(problem, method="dense", tol=1e-6, max_iter=5000)
    assert int(ok.status) == STATUS_CONVERGED and bool(ok.converged)
    short = solve(problem, method="dense", tol=1e-12, max_iter=3)
    assert int(short.status) == STATUS_MAX_ITER and not bool(short.converged)
    lg = solve(problem, method="log", tol=1e-9, max_iter=5000)
    assert int(lg.status) == STATUS_CONVERGED
    lg_short = solve(problem, method="log", tol=1e-13, max_iter=2)
    assert int(lg_short.status) == STATUS_MAX_ITER


def test_status_stall_on_pinched_kernel():
    """K = [[1, 0], [0, 0]] with a1 != b1: the scalings drift forever while
    the marginal violation is constant — stall detection must fire."""
    K = jnp.asarray([[1.0, 0.0], [0.0, 0.0]])
    a = jnp.asarray([0.5, 0.5])
    b = jnp.asarray([0.25, 0.75])
    res = generic_scaling_loop(
        lambda v: K @ v, lambda u: K.T @ u, a, b, 1.0,
        tol=1e-12, max_iter=100_000,
    )
    assert int(res.status) == STATUS_STALL
    assert int(res.n_iter) < 100_000


def test_status_nonfinite_on_nan_kernel_log_domain():
    """A NaN in logK makes err NaN, which silently exits the loop (NaN > tol
    is False); the status must surface it instead of passing for converged."""
    logK = jnp.full((8, 8), jnp.nan)
    a = jnp.ones(8) / 8
    res = sinkhorn_log(logK, a, a, 0.1, tol=1e-9, max_iter=100)
    assert int(res.status) == STATUS_NONFINITE
    assert res.converged is not None and not bool(res.converged)


def test_status_degenerate_all_zero_scalings():
    K = jnp.zeros((6, 6))
    a = jnp.ones(6) / 6
    res = generic_scaling_loop(lambda v: K @ v, lambda u: K.T @ u, a, a, 1.0,
                               tol=1e-9, max_iter=100)
    assert int(res.status) == STATUS_DEGENERATE


def test_status_threaded_through_batched_solvers():
    from repro.batch import BucketedExecutor

    x, a, b = _measures(96, seed=5)
    problems = [OTProblem(Geometry.from_points(x), a, b, 0.1)] * 2
    keys = [jax.random.PRNGKey(i) for i in range(2)]
    for method, kw in (("dense", {}), ("log", {}),
                       ("spar_sink_coo", dict(keys=keys, s=8 * s0(96)))):
        sols = BucketedExecutor().solve_batch(problems, method=method,
                                              tol=1e-6, max_iter=5000, **kw)
        for sol in sols:
            assert sol.status is not None
            assert sol.status_label in ("converged", "stall")


# --------------------------------------------------------------------------
# Unified tol default + every method honors a passed tol (satellite)
# --------------------------------------------------------------------------


def test_registered_tol_defaults_are_unified():
    """`log` used to register 1e-9 while everything else registered 1e-6;
    every method that accepts tol must now default to DEFAULT_TOL."""
    for method in available_methods():
        params = inspect.signature(get_solver(method)).parameters
        if "tol" in params:
            assert params["tol"].default == DEFAULT_TOL, method


def test_every_method_honors_passed_tol():
    x, a, b = _measures(seed=2)
    # normalized cost: err decays through the loose threshold well before
    # the sketched methods' stall detection can fire, so a looser tol must
    # stop strictly earlier for every method
    problem = OTProblem(Geometry.from_points(x, normalize=True), a, b, 0.1)
    key = jax.random.PRNGKey(0)
    # the loose tol must sit above each method's scaling-domain err plateau
    # (sketched iterations stall near err ~1-50 and would not separate a
    # barely-loose tol from a tight one), so it is per-method
    per_method = {
        "dense": ({}, 10.0), "log": ({}, 10.0),
        "spar_sink_coo": (dict(key=key, s=S), 10.0),
        "spar_sink_log": (dict(key=key, s=S), 10.0),
        "spar_sink_dense": (dict(key=key, s=S), 10.0),
        "spar_sink_block_ell": (dict(key=key, s=S, block=32), 100.0),
        "rand_sink": (dict(key=key, s=S), 1e3),  # uniform sketch: err ~1e2 at iter 1
        "nys_sink": (dict(key=key, rank=40), 10.0),
        "screenkhorn_lite": ({}, 10.0),
    }
    pc_problem = OTProblem(PointCloudGeometry(x), a, b, 0.1)
    for method, (kw, loose_tol) in per_method.items():
        loose = solve(problem, method=method, tol=loose_tol, max_iter=5000, **kw)
        tight = solve(problem, method=method, tol=1e-8, max_iter=5000, **kw)
        assert int(loose.n_iter) < int(tight.n_iter), method
    mf_loose = solve(pc_problem, method="spar_sink_mf", key=key, s=S,
                     tol=1e3, max_iter=5000)  # raw-cost scalings: err ~1e3 early
    mf_tight = solve(pc_problem, method="spar_sink_mf", key=key, s=S,
                     tol=1e-8, max_iter=5000)
    assert int(mf_loose.n_iter) < int(mf_tight.n_iter)


# --------------------------------------------------------------------------
# Log-space sketch construction invariants
# --------------------------------------------------------------------------


def test_log_sketch_support_bitwise_matches_coo_sketch():
    """OT path: same PRNG key => the log sketch samples exactly the
    spar_sink_coo support, with logvals = log(vals)."""
    x, a, b = _measures(seed=4)
    problem = OTProblem(Geometry.from_points(x), a, b, 0.1)
    key = jax.random.PRNGKey(7)
    sk_lin = build_coo_sketch(problem, key, S)
    sk_log, c_e = build_coo_log_sketch(problem, key, S)
    np.testing.assert_array_equal(np.asarray(sk_lin.rows), np.asarray(sk_log.rows))
    np.testing.assert_array_equal(np.asarray(sk_lin.cols), np.asarray(sk_log.cols))
    assert int(sk_lin.nnz) == int(sk_log.nnz)
    nnz = int(sk_log.nnz)
    np.testing.assert_allclose(
        np.exp(np.asarray(sk_log.logvals[:nnz])), np.asarray(sk_lin.vals[:nnz]),
        rtol=1e-12,
    )
    assert np.all(np.isneginf(np.asarray(sk_log.logvals[nnz:])))
    # gathered costs are index-aligned
    C = np.asarray(problem.geom.cost)
    np.testing.assert_allclose(
        np.asarray(c_e[:nnz]),
        C[np.asarray(sk_log.rows[:nnz]), np.asarray(sk_log.cols[:nnz])],
        rtol=1e-12,
    )


def test_log_sketch_survives_small_eps_where_linear_collapses():
    """At eps = 1e-3 on separated supports the linear sketch's values are
    exact zeros while the log sketch keeps the same support, finite."""
    x, a, b = _measures(seed=6)
    problem = OTProblem(Geometry.from_points(x, x + 2.0), a, b, 1e-3)
    key = jax.random.PRNGKey(1)
    sk_lin = build_coo_sketch(problem, key, S)
    sk_log, _ = build_coo_log_sketch(problem, key, S)
    assert int(sk_lin.nnz) > 0
    assert float(jnp.max(sk_lin.vals)) == 0.0  # underflowed to nothing
    lv = np.asarray(sk_log.logvals[: int(sk_log.nnz)])
    assert int(sk_log.nnz) == int(sk_lin.nnz)
    assert np.isfinite(lv).all()


def test_uot_logprobs_match_linear_and_survive_small_eps():
    x, a, b = _measures(seed=8)
    C = Geometry.wfr(x, eta=0.5).cost
    lam, eps = 0.5, 0.1
    logp = sparsify.uot_sampling_logprobs(a * 5, b * 3, C, lam, eps)
    logK = jnp.where(jnp.isinf(C), -jnp.inf, -C / eps)
    p = sparsify.uot_sampling_probs(a * 5, b * 3, logK, lam, eps)
    np.testing.assert_allclose(np.exp(np.asarray(logp)), np.asarray(p),
                               rtol=1e-9, atol=1e-300)
    # blocked entries are -inf, and the distribution stays normalized at
    # eps where the linear path would round it
    lp_small = sparsify.uot_sampling_logprobs(a * 5, b * 3, C, 1e-3, 1e-3)
    assert np.isneginf(np.asarray(lp_small))[np.isinf(np.asarray(C))].all()
    z = jax.scipy.special.logsumexp(jnp.where(jnp.isneginf(lp_small), -jnp.inf, lp_small))
    np.testing.assert_allclose(float(z), 0.0, atol=1e-9)


def test_mf_log_sketch_invariants_and_uot_thinning():
    """The matrix-free log sketch keeps the compaction/merge invariants of
    the linear mf sketch, and its UOT thinning keeps a nonempty, finite
    support at small eps."""
    x, a, b = _measures(seed=10)
    pc = PointCloudGeometry(x)
    for problem in (
        OTProblem(pc, a, b, 1e-3),
        UOTProblem(PointCloudGeometry(x, cost="wfr", eta=0.5), a * 5, b * 3,
                   1e-3, lam=0.5),
    ):
        sk, c_e = build_mf_log_sketch(problem, jax.random.PRNGKey(2), S)
        nnz = int(sk.nnz)
        assert nnz > 0
        lv = np.asarray(sk.logvals)
        assert np.isfinite(lv[:nnz]).all()
        assert np.isneginf(lv[nnz:]).all()
        rows, cols = np.asarray(sk.rows), np.asarray(sk.cols)
        assert (np.diff(rows) >= 0).all()  # row-sorted, padding at the end
        assert (np.diff(cols[np.asarray(sk.csort)]) >= 0).all()
        pairs = list(zip(rows[:nnz], cols[:nnz]))
        assert len(pairs) == len(set(pairs))  # duplicates merged
        assert c_e.shape == sk.logvals.shape


# --------------------------------------------------------------------------
# Batched bitwise parity (acceptance)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("eps", [1e-1, 1e-3])
def test_batched_spar_sink_log_bitwise_matches_per_problem(eps):
    from repro.batch import BucketedExecutor

    problems, keys = [], []
    for i, (n, seed) in enumerate(((128, 0), (96, 1), (128, 2))):
        x, a, b = _measures(n, seed=seed)
        geom = Geometry.from_points(x)
        if i == 1:
            problems.append(UOTProblem(geom, a * 2, b * 3, eps, lam=0.5))
        else:
            problems.append(OTProblem(geom, a, b, eps))
        keys.append(jax.random.PRNGKey(40 + i))
    s = 8 * s0(128)
    sols = BucketedExecutor().solve_batch(
        problems, method="spar_sink_log", keys=keys, s=s, tol=1e-9,
        max_iter=3000,
    )
    for p, k, sol in zip(problems, keys, sols):
        ref = solve(p, method="spar_sink_log", key=k, s=s, tol=1e-9,
                    max_iter=3000)
        assert bool(jnp.all(sol.result.u == ref.result.u))
        assert bool(jnp.all(sol.result.v == ref.result.v))
        assert int(sol.n_iter) == int(ref.n_iter)
        assert int(sol.status) == int(ref.status)
        assert sol.domain == "log"
        np.testing.assert_allclose(float(sol.value), float(ref.value), rtol=1e-9)
        np.testing.assert_allclose(np.asarray(sol.plan().vals),
                                   np.asarray(ref.plan().vals), rtol=1e-12)


def test_batched_mf_stabilized_bitwise_matches_per_problem():
    from repro.batch import BucketedExecutor

    problems, keys = [], []
    for i, (n, seed) in enumerate(((128, 0), (96, 1), (128, 2))):
        x, a, b = _measures(n, seed=seed)
        geom = PointCloudGeometry(x)
        if i == 1:
            problems.append(UOTProblem(geom, a * 2, b * 3, 1e-3, lam=0.5))
        else:
            problems.append(OTProblem(geom, a, b, 1e-3))
        keys.append(jax.random.PRNGKey(70 + i))
    s = 8 * s0(128)
    sols = BucketedExecutor().solve_batch(
        problems, method="spar_sink_mf", keys=keys, s=s, stabilize=True,
        tol=1e-9, max_iter=3000,
    )
    for p, k, sol in zip(problems, keys, sols):
        ref = solve(p, method="spar_sink_mf", key=k, s=s, stabilize=True,
                    tol=1e-9, max_iter=3000)
        assert bool(jnp.all(sol.result.u == ref.result.u))
        assert bool(jnp.all(sol.result.v == ref.result.v))
        assert int(sol.status) == int(ref.status)
        np.testing.assert_allclose(float(sol.value), float(ref.value), rtol=1e-9)


# --------------------------------------------------------------------------
# The generic closure-based loop is the same iteration
# --------------------------------------------------------------------------


def test_generic_sparse_log_loop_matches_solver_trajectory():
    """`generic_sparse_log_loop` (the closure-based reference) agrees with
    the B=1 batched kernel the registry actually runs — same iteration
    counts and status, potentials equal to fp tolerance (XLA may fuse the
    two programs' transcendentals differently, hence not bitwise)."""
    from repro.core.sinkhorn import _masked_log

    x, a, b = _measures(seed=11)
    problem = OTProblem(Geometry.from_points(x), a, b, 0.05)
    sk, _ = build_coo_log_sketch(problem, jax.random.PRNGKey(3), S)
    eps = 0.05
    res = generic_sparse_log_loop(
        lambda g: sparsify.coo_lse_row(sk, g / eps),
        lambda f: sparsify.coo_lse_col(sk, f / eps),
        _masked_log(a), _masked_log(b), eps, 1.0, tol=1e-9, max_iter=3000,
    )
    sol = solve(problem, method="spar_sink_log", key=jax.random.PRNGKey(3),
                s=S, tol=1e-9, max_iter=3000)
    assert int(res.n_iter) == int(sol.n_iter)
    assert int(res.status) == int(sol.status)
    f_ref, f_sol = np.asarray(res.u), np.asarray(sol.result.u)
    alive = ~np.isneginf(f_ref)
    np.testing.assert_allclose(f_sol[alive], f_ref[alive], rtol=1e-12, atol=1e-12)


# --------------------------------------------------------------------------
# The sorted segmented logsumexp of the iteration loop
# --------------------------------------------------------------------------


def _sorted_case(name):
    """``(idx, z, n)``: (B, cap) sorted segment ids and float32 summands."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    n, cap = 40, 400

    def draw(k):
        return np.sort(rng.integers(0, n, size=k)), rng.normal(size=k) * 30 - 300

    if name in ("random_b1", "random_b3"):
        rows = [draw(cap) for _ in range(1 if name == "random_b1" else 3)]
    elif name == "empty_segments":  # ids from a few segments only
        idx = np.sort(rng.choice([2, 3, 9, 30], size=cap))
        rows = [(idx, rng.normal(size=cap) * 30 - 300)]
    elif name == "all_neginf_segments":
        idx, z = draw(cap)
        z[np.isin(idx, [0, 5, 6, n - 1])] = -np.inf
        rows = [(idx, z)]
    elif name == "single_entry_segments":
        idx = np.arange(n)[rng.random(n) < 0.7]
        rows = [(idx, rng.normal(size=idx.size) * 30 - 300)]
    elif name == "dead_tail":  # the mf sketch parks dead slots at row n - 1
        idx, z = draw(cap // 4)
        rows = [(np.concatenate([idx, np.full(cap - cap // 4, n - 1)]),
                 np.concatenate([z, np.full(cap - cap // 4, -np.inf)]))]
    else:  # "starts_at_entry_0": one long first segment, then short ones
        idx = np.concatenate([np.zeros(300, int), np.sort(rng.integers(1, n, 100))])
        rows = [(idx, rng.normal(size=400) * 30 - 300)]
    for _, z in rows:
        z[rng.random(z.size) < 0.1] = -np.inf
    idx = jnp.asarray(np.stack([r[0] for r in rows]), jnp.int32)
    z = jnp.asarray(np.stack([r[1] for r in rows]), jnp.float32)
    return idx, z, n


@pytest.mark.parametrize("name", [
    "random_b1", "random_b3", "empty_segments", "all_neginf_segments",
    "single_entry_segments", "dead_tail", "starts_at_entry_0",
])
def test_sorted_segment_logsumexp_matches_scatter(name):
    """The loop's scan against the scatter `segment_logsumexp`, per row of
    the batch: a few float32 ulps, and ``-inf`` exactly where it gives it."""
    idx, z, n = _sorted_case(name)
    got = np.asarray(sparsify.sorted_segment_logsumexp(z, sparsify.sorted_segments(idx, n)))
    for b in range(idx.shape[0]):
        ref = np.asarray(sparsify.segment_logsumexp(z[b], idx[b], n))
        assert got[b].dtype == np.float32
        np.testing.assert_array_equal(np.isneginf(got[b]), np.isneginf(ref))
        fin = np.isfinite(ref)
        assert fin.any()
        ulps = np.abs(got[b][fin] - ref[fin]) / np.spacing(np.abs(ref[fin]))
        assert ulps.max() <= 4, ulps.max()


def _loop_hlo(sorted_: bool) -> str:
    n, cap = 32, 256
    ids = jax.ShapeDtypeStruct((1, cap), jnp.int32)
    vec = jax.ShapeDtypeStruct((1, n), jnp.float32)
    one = jax.ShapeDtypeStruct((1,), jnp.float32)
    loop = jax.jit(batch_solvers.sparse_log_potentials,
                   static_argnames=("n", "m", "tol", "max_iter"))
    return loop.lower(
        ids, ids, jax.ShapeDtypeStruct((1, cap), jnp.float32), ids if sorted_ else None,
        vec, vec, one, one, n=n, m=n, tol=1e-6, max_iter=10,
    ).compile().as_text()


def test_sorted_loop_body_holds_no_scatter():
    """With ``csort`` the loop reduces by the scan; without it, by scatters."""
    assert not has_scatter(while_body_text(_loop_hlo(True)))
    assert has_scatter(while_body_text(_loop_hlo(False)))
