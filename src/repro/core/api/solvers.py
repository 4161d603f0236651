"""The built-in solver registry entries behind ``solve(problem, method=...)``.

Eleven methods, one `Solution` contract:

===================== ========================================================
``dense``             Algorithm 1/2 on the dense Gibbs kernel (scaling domain)
``log``               log-domain Algorithm 1/2 (small-``eps`` safe)
``spar_sink_coo``     paper Algorithms 3/4 — importance sketch, padded COO,
                      O(s) per iteration and O(cap) plan (scaling domain:
                      needs ``eps`` large enough that ``exp(-C/eps) > 0``)
``spar_sink_log``     **log-domain** Algorithms 3/4 — the same importance
                      sketch carried as ``logvals = -C_e/eps - log p*_e``,
                      iterated by sorted-COO segment-logsumexp; safe for
                      ``eps`` down to 1e-3 and below (paper Sec. 5 sweep)
``spar_sink_mf``      **matrix-free** Algorithms 3/4 on a `PointCloudGeometry`
                      — factorized O(s log n) sampler + gathered-kernel
                      evaluation, no (n, m) array anywhere (Õ(n) end to end);
                      ``stabilize=True`` runs it in the log domain (small-eps
                      safe, still matrix-free)
``spar_sink_block_ell`` tile-granular TPU sketch (DESIGN §3)
``spar_sink_dense``   exact eq.(7) sketch as a dense masked array (reference)
``rand_sink``         Spar-Sink with uniform probabilities (baseline)
``greenkhorn``        greedy single-row/col updates (Altschuler et al. 2017)
``nys_sink``          Nyström low-rank kernel + Sinkhorn (Altschuler 2019)
``screenkhorn_lite``  static active-set screening (simplified Alaya 2019)
===================== ========================================================

Every solver accepts both `OTProblem` and `UOTProblem`; the unbalanced
exponent ``fe = lam/(lam+eps)`` comes from the problem object, and
``lam = inf`` degenerates each method to its balanced form.

Every iterative method defaults to the **same** stopping tolerance
``DEFAULT_TOL = 1e-6`` (the ``log`` method used to register ``1e-9`` while
everything else registered ``1e-6``, so swapping methods silently changed
the stopping rule). The scaling-domain rule is the paper's
``||du||_1 + ||dv||_1 <= tol``; the log-domain rule is its potential
analogue ``max|df| + max|dg| <= tol``; pass ``tol=`` to tighten either.

The sketching solvers here are **the** implementation — the legacy
``spar_sink_ot``/``spar_sink_uot`` free functions are deprecation shims
over this module, so results agree bitwise for a given PRNG key.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import sparsify
from repro.core.api.geometry import PointCloudGeometry
from repro.core.api.problems import OTProblem, UOTProblem
from repro.core.api.registry import register_solver
from repro.core.api.solution import SparsePlan, Solution
from repro.core.baselines import greenkhorn, nys_sink, screenkhorn_lite
from repro.core.sinkhorn import (
    _masked_log,
    generic_scaling_loop,
    plan_from_potentials,
    plan_from_scalings,
    sinkhorn,
    sinkhorn_log,
    sinkhorn_uot,
    sinkhorn_uot_log,
)
from repro.core.spar_sink import (
    coo_objective_ot,
    coo_objective_ot_entries,
    coo_objective_ot_log_entries,
    coo_objective_uot,
    coo_objective_uot_entries,
    coo_objective_uot_log_entries,
    default_cap,
    default_max_blocks,
    log_plan_entries,
)
from repro.obs.certify import dense_certificate, importance_ess, sparse_certificate
from repro.obs.spans import span
from repro.obs.trace import SolverTrace, sketch_diagnostics

__all__ = [
    "DEFAULT_TOL",
    "build_coo_log_sketch",
    "build_coo_sketch",
    "build_mf_log_sketch",
    "build_mf_sketch",
    "mf_sampling_factors",
    "mix_uniform",
    "sampling_probs",
]

#: shared stopping-tolerance default of every registered iterative method
#: (documented in the module table above)
DEFAULT_TOL = 1e-6


# --------------------------------------------------------------------------
# Shared sketching helpers (used by the registry and the benchmarks)
# --------------------------------------------------------------------------


def mix_uniform(probs, shrinkage: float):
    """Thm 1 condition (ii): keep ``p*_ij >= c3 s / n^2`` by uniform mixing.

    ``probs`` may be an ``(fr, fc)`` factor pair (rank-1 probabilities);
    mixing breaks the rank-1 structure, so factored probs only pass through
    unmixed."""
    if shrinkage <= 0.0:
        return probs
    if isinstance(probs, tuple):
        raise ValueError(
            "uniform mixing (shrinkage > 0) is rank-2 and cannot be applied "
            "to factored probabilities; pass a dense probs array instead"
        )
    n, m = probs.shape
    return (1.0 - shrinkage) * probs + shrinkage / (n * m)


def sampling_probs(problem: OTProblem) -> jax.Array:
    """Paper eq. (9) for OT, eq. (11) for UOT (degenerates to (9) at lam=inf)."""
    if isinstance(problem, UOTProblem) and not problem.is_balanced:
        return sparsify.uot_sampling_probs(
            problem.a, problem.b, problem.log_kernel(), problem.lam, problem.eps
        )
    return sparsify.ot_sampling_probs(problem.a, problem.b)


def _resolve_probs(
    problem: OTProblem, probs: jax.Array | None, shrinkage: float
) -> jax.Array:
    """One place for the Thm-1 probability rule shared by every sketch path:
    explicit override, else eq.(9)/(11) by problem type, then uniform mixing."""
    return mix_uniform(probs if probs is not None else sampling_probs(problem), shrinkage)


def build_coo_sketch(
    problem: OTProblem,
    key: jax.Array,
    s: float,
    *,
    cap: int | None = None,
    probs: jax.Array | None = None,
    shrinkage: float = 0.0,
) -> sparsify.SparseKernelCOO:
    """Importance-sparsified COO sketch of the problem's Gibbs kernel."""
    probs = _resolve_probs(problem, probs, shrinkage)
    cap = default_cap(s) if cap is None else cap
    return sparsify.sparsify_coo(key, problem.kernel(), probs, s, cap)


def _mf_geometry(problem: OTProblem) -> PointCloudGeometry:
    geom = problem.geom
    if not isinstance(geom, PointCloudGeometry):
        raise TypeError(
            "the matrix-free path needs support points: build the problem on "
            "a PointCloudGeometry(x, y, cost=...) instead of a dense-cost "
            f"Geometry (got {type(geom).__name__})"
        )
    return geom


def mf_sampling_factors(problem: OTProblem):
    """``(ra, rb, thin_scale)`` of the matrix-free draw: the eq. (9)
    factors for OT (``thin_scale = None``); for UOT the normalized rank-1
    ``(a_i b_j)^{lam/(2lam+eps)}`` proposal of eq. (11), thinned by
    ``exp(-C thin_scale)`` with ``thin_scale = 1/(2lam+eps)``."""
    if isinstance(problem, UOTProblem) and not problem.is_balanced:
        lam, eps = float(problem.lam), float(problem.eps)
        c_ab = lam / (2.0 * lam + eps)
        qa, qb = problem.a ** c_ab, problem.b ** c_ab
        return qa / jnp.sum(qa), qb / jnp.sum(qb), 1.0 / (2.0 * lam + eps)
    ra, rb = sparsify.ot_sampling_prob_factors(problem.a, problem.b)
    return ra, rb, None


def build_mf_sketch(
    problem: OTProblem,
    key: jax.Array,
    s: float,
    *,
    cap: int | None = None,
    impl: str = "auto",
) -> tuple[sparsify.SparseKernelCOO, jax.Array]:
    """Matrix-free importance sketch in O(n + s log n) — no (n, m) array.

    OT: the eq. (9) probabilities are rank-1, so the factorized sampler
    draws them exactly (`sparsify.sparsify_coo_mf`). UOT: proposes from the
    rank-1 ``(a_i b_j)^{lam/(2lam+eps)}`` part of eq. (11) and thins with
    the on-the-fly ``K^{eps/(2lam+eps)}`` acceptance; ``s`` is then the
    proposal budget. Returns ``(sketch, C_e)`` with the gathered raw costs.
    """
    geom = _mf_geometry(problem)
    eps = float(problem.eps)
    cap = default_cap(s) if cap is None else cap
    entries = lambda r, c: geom.entries(r, c, eps, impl=impl)
    ra, rb, thin_scale = mf_sampling_factors(problem)
    return sparsify.sparsify_coo_mf(key, ra, rb, s, cap, entries, thin_scale=thin_scale)


def build_coo_log_sketch(
    problem: OTProblem,
    key: jax.Array,
    s: float,
    *,
    cap: int | None = None,
    probs: jax.Array | None = None,
    shrinkage: float = 0.0,
) -> tuple[sparsify.LogSparseKernelCOO, jax.Array]:
    """Log-space importance sketch (+ index-aligned gathered costs).

    OT (and explicit ``probs`` overrides): the same eq. (7) draw as
    `build_coo_sketch` — same uniform variates, so the sampled support is
    bitwise identical for the same PRNG key — with values stored as
    ``logvals = -C_e/eps - log p*_e``. UOT: the eq. (11) probabilities are
    computed, normalized, *and drawn* in log space
    (`sparsify.uot_sampling_logprobs`), so a sharply-concentrated
    small-``eps`` distribution cannot flush the sampled support to zero.
    """
    cap = default_cap(s) if cap is None else cap
    cost = problem.geom.cost
    eps = float(problem.eps)
    if probs is None and isinstance(problem, UOTProblem) and not problem.is_balanced:
        logp = sparsify.uot_sampling_logprobs(
            problem.a, problem.b, cost, float(problem.lam), eps
        )
        if shrinkage > 0.0:  # log-space mix_uniform (Thm 1 condition (ii))
            n, m = problem.shape
            logp = jnp.logaddexp(
                jnp.log1p(-shrinkage) + logp,
                jnp.log(shrinkage) - jnp.log(float(n * m)),
            )
        return sparsify.sparsify_coo_log(key, cost, None, eps, s, cap, logprobs=logp)
    probs = _resolve_probs(problem, probs, shrinkage)
    return sparsify.sparsify_coo_log(key, cost, probs, eps, s, cap)


def build_mf_log_sketch(
    problem: OTProblem,
    key: jax.Array,
    s: float,
    *,
    cap: int | None = None,
) -> tuple[sparsify.LogSparseKernelCOO, jax.Array]:
    """Matrix-free **log-space** importance sketch in O(n + s log n).

    `build_mf_sketch`'s factorized Poissonized draw with entry values kept
    as ``logvals = -C_e/eps - log rate_e`` from gathered raw costs
    (`PointCloudGeometry.cost_entries`) — ``exp(-C/eps)`` is never
    evaluated, so the sketch survives arbitrarily small ``eps`` and still
    touches no (n, m) array. UOT acceptance thinning runs in log space.
    """
    geom = _mf_geometry(problem)
    eps = float(problem.eps)
    cap = default_cap(s) if cap is None else cap
    ra, rb, thin_scale = mf_sampling_factors(problem)
    return sparsify.sparsify_coo_mf_log(
        key, ra, rb, s, cap, geom.cost_entries, eps, thin_scale=thin_scale
    )


def _coo_value(problem: OTProblem, sk, res) -> jax.Array:
    """O(cap) entropic objective on the sketch plan."""
    if isinstance(problem, UOTProblem) and not problem.is_balanced:
        return coo_objective_uot(
            sk, problem.geom.cost, res, problem.a, problem.b, problem.lam, problem.eps
        )
    return coo_objective_ot(sk, problem.geom.cost, res, problem.eps)


def _sketch_stats(sk, trace):
    """Sketch diagnostics, computed only when telemetry was requested (the
    ``trace=False`` fast path does zero extra work)."""
    return sketch_diagnostics(sk) if trace else None


def _problem_lam(problem: OTProblem) -> float:
    """Marginal penalty as a plain float; ``inf`` selects the balanced dual."""
    if isinstance(problem, UOTProblem):
        return float(problem.lam)
    return float("inf")


def _scaling_potentials(res, eps: float):
    """(f, g) = eps log(u, v) with dead atoms (zero scalings) at ``-inf``."""
    u, v = res.u, res.v
    f = jnp.where(u > 0, eps * jnp.log(jnp.where(u > 0, u, 1.0)), -jnp.inf)
    g = jnp.where(v > 0, eps * jnp.log(jnp.where(v > 0, v, 1.0)), -jnp.inf)
    return f, g


def _kernel_cost(Kt: jax.Array, eps: float) -> jax.Array:
    """Effective cost ``-eps log Kt`` of a (sketched) dense kernel, with
    zeroed/negative entries mapped to ``+inf`` (outside the support)."""
    pos = Kt > 0
    return jnp.where(pos, -eps * jnp.log(jnp.where(pos, Kt, 1.0)), jnp.inf)


def _sparse_cert(problem: OTProblem, sk, res, value, c_e, *, log_domain: bool):
    """Certificate of a sketched solve in O(cap + n): dense-anchored duality
    gap via the Horvitz-Thompson kernel entries ``k_e`` plus the
    delta-method CI from the recovered inclusion probabilities
    (``p*_e = K_e / vals_e``). ``c_e`` are the raw gathered costs.

    Only called behind ``certify=True`` — everything here is post-loop
    array math, so ``certify=False`` jaxprs carry zero extra equations.
    """
    eps = float(problem.eps)
    lam = _problem_lam(problem)
    n, m = problem.shape
    if log_domain:
        t_e = log_plan_entries(sk, res, eps)
        f, g = res.u, res.v
        fh = jnp.where(jnp.isfinite(f), f, 0.0)
        gh = jnp.where(jnp.isfinite(g), g, 0.0)
        # HT dual kernel entries at the masked potentials (== t_e if none died)
        logk = sk.logvals + (fh[sk.rows] + gh[sk.cols]) / eps
        k_e = jnp.where(jnp.isneginf(logk), 0.0, jnp.exp(logk))
        # logvals = -C_e/eps - log p*_e  =>  log p*_e = -C_e/eps - logvals
        logp = jnp.minimum(-c_e / eps - sk.logvals, 0.0)
        p_e = jnp.where(jnp.isneginf(sk.logvals), 1.0, jnp.exp(logp))
        ess = importance_ess(sk.logvals, log_space=True)
    else:
        vals = sk.vals
        alive = vals > 0
        t_e = res.u[sk.rows] * vals * res.v[sk.cols]
        f, g = _scaling_potentials(res, eps)
        uh = jnp.where(res.u > 0, res.u, 1.0)
        vh = jnp.where(res.v > 0, res.v, 1.0)
        k_e = uh[sk.rows] * vals * vh[sk.cols]
        # vals = K_e / p*_e  =>  p*_e = exp(-C_e/eps) / vals
        K_e = jnp.where(jnp.isfinite(c_e), jnp.exp(-c_e / eps), 0.0)
        p_e = jnp.where(alive, jnp.clip(K_e / jnp.where(alive, vals, 1.0), 0.0, 1.0), 1.0)
        ess = importance_ess(vals)
    return sparse_certificate(
        t_e=t_e,
        c_e=c_e,
        rows=sk.rows,
        cols=sk.cols,
        n=n,
        m=m,
        a=problem.a,
        b=problem.b,
        f=f,
        g=g,
        eps=eps,
        lam=lam,
        value=value,
        k_e=k_e,
        p_e=p_e,
        ess=ess,
    )


def _dense_solution(
    problem: OTProblem,
    method: str,
    res,
    Kt: jax.Array,
    *,
    nnz=None,
    certify: bool = False,
    cost: jax.Array | None = None,
) -> Solution:
    """Assemble a `Solution` whose plan is a dense ``diag(u) Kt diag(v)``.

    The plan array is *recomputed* by the lazy thunk rather than captured:
    a long-lived Solution then pins only ``Kt`` (for the dense/greenkhorn/
    screenkhorn paths that is the Geometry-cached kernel, already alive),
    not a second n x m array. ``certify=True`` evaluates the duality-gap
    certificate on the transient plan; ``cost`` overrides the certified
    cost matrix for solvers whose kernel is itself sketched."""
    T = plan_from_scalings(res.u, Kt, res.v)
    value = problem.objective(T)
    cert = None
    if certify:
        eps = float(problem.eps)
        f, g = _scaling_potentials(res, eps)
        cert = dense_certificate(
            plan=T,
            cost=problem.geom.cost if cost is None else cost,
            a=problem.a,
            b=problem.b,
            f=f,
            g=g,
            eps=eps,
            lam=_problem_lam(problem),
            value=value,
        )
    del T
    return Solution(
        method=method,
        problem=problem,
        value=value,
        result=res,
        domain="scaling",
        nnz=nnz,
        certificate=cert,
        _plan_thunk=lambda: plan_from_scalings(res.u, Kt, res.v),
    )


# --------------------------------------------------------------------------
# Dense-kernel solvers
# --------------------------------------------------------------------------


@register_solver("dense")
def _solve_dense(
    problem: OTProblem,
    *,
    tol: float = DEFAULT_TOL,
    max_iter: int = 1000,
    trace: bool | int = False,
    certify: bool = False,
) -> Solution:
    """Scaling-domain Sinkhorn on the dense Gibbs kernel (Alg. 1 / Alg. 2)."""
    K = problem.kernel()
    if problem.fe == 1.0:
        res = sinkhorn(K, problem.a, problem.b, tol=tol, max_iter=max_iter, trace=trace)
    else:
        res = sinkhorn_uot(
            K, problem.a, problem.b, problem.lam, problem.eps, tol=tol,
            max_iter=max_iter, trace=trace,
        )
    return _dense_solution(problem, "dense", res, K, certify=certify)


@register_solver("log")
def _solve_log(
    problem: OTProblem,
    *,
    tol: float = DEFAULT_TOL,
    max_iter: int = 1000,
    trace: bool | int = False,
    certify: bool = False,
    init: tuple[jax.Array, jax.Array] | None = None,
) -> Solution:
    """Log-domain Sinkhorn on dual potentials (survives ``eps`` down to 1e-3).

    ``init=(f0, g0)`` warm-starts the potentials — e.g. re-tightening at
    the original ``eps`` from an eps-bumped solve (the escalation ladder's
    stall recovery); ``init=None`` (default) is the cold start and changes
    nothing in the compiled program.
    """
    logK = problem.log_kernel()
    eps = float(problem.eps)
    if problem.fe == 1.0:
        res = sinkhorn_log(
            logK, problem.a, problem.b, eps, tol=tol, max_iter=max_iter,
            trace=trace, init=init,
        )
    else:
        res = sinkhorn_uot_log(
            logK, problem.a, problem.b, float(problem.lam), eps, tol=tol,
            max_iter=max_iter, trace=trace, init=init,
        )
    T = plan_from_potentials(res.u, logK, res.v, eps)
    value = problem.objective(T)
    cert = None
    if certify:
        cert = dense_certificate(
            plan=T,
            cost=problem.geom.cost,
            a=problem.a,
            b=problem.b,
            f=res.u,
            g=res.v,
            eps=eps,
            lam=_problem_lam(problem),
            value=value,
        )
    del T
    return Solution(
        method="log",
        problem=problem,
        value=value,
        result=res,
        domain="log",
        certificate=cert,
        _plan_thunk=lambda: plan_from_potentials(res.u, logK, res.v, eps),
    )


# --------------------------------------------------------------------------
# Sketching solvers (paper Algorithms 3 & 4 + baselines)
# --------------------------------------------------------------------------


@register_solver("spar_sink_coo")
def _solve_spar_sink_coo(
    problem: OTProblem,
    *,
    key: jax.Array,
    s: float,
    cap: int | None = None,
    shrinkage: float = 0.0,
    probs: jax.Array | None = None,
    tol: float = DEFAULT_TOL,
    max_iter: int = 1000,
    trace: bool | int = False,
    certify: bool = False,
) -> Solution:
    """Spar-Sink on the padded-COO sketch: O(s) iterations, O(cap) plan.

    **Scaling domain**: needs ``eps`` large enough that ``exp(-C/eps)``
    stays representable — at the paper's small-``eps`` floor the sketch
    underflows and the solve reports ``STATUS_DEGENERATE``; use
    ``spar_sink_log`` there.
    """
    sk = build_coo_sketch(problem, key, s, cap=cap, probs=probs, shrinkage=shrinkage)
    res = _coo_scaling_loop(problem, sk, tol, max_iter, trace)
    value = _coo_value(problem, sk, res)
    cert = None
    if certify:
        c_e = problem.geom.cost[sk.rows, sk.cols]
        cert = _sparse_cert(problem, sk, res, value, c_e, log_domain=False)
    return _coo_solution(
        "spar_sink_coo", problem, sk, res, value,
        sketch_stats=_sketch_stats(sk, trace), certificate=cert,
    )


def _coo_scaling_loop(
    problem: OTProblem, sk, tol: float, max_iter: int, trace: bool | int = False
):
    return generic_scaling_loop(
        lambda v: sparsify.coo_matvec(sk, v),
        lambda u: sparsify.coo_rmatvec(sk, u),
        problem.a,
        problem.b,
        problem.fe,
        tol=tol,
        max_iter=max_iter,
        trace=trace,
    )


def _coo_solution(
    method: str, problem: OTProblem, sk, res, value, sketch_stats=None, certificate=None
) -> Solution:
    def sparse_plan() -> SparsePlan:
        # T~ restricted to kept entries; padded slots carry vals == 0.
        return SparsePlan(
            sk.rows, sk.cols, res.u[sk.rows] * sk.vals * res.v[sk.cols], sk.nnz, sk.n, sk.m
        )

    return Solution(
        method=method,
        problem=problem,
        value=value,
        result=res,
        domain="scaling",
        nnz=sk.nnz,
        overflowed=sk.overflowed,
        sketch_stats=sketch_stats,
        certificate=certificate,
        _plan_thunk=sparse_plan,
    )


def _sparse_log_loop(
    problem: OTProblem, sk, tol: float, max_iter: int,
    trace: bool | int = False,
    init: tuple[jax.Array, jax.Array] | None = None,
):
    """Run the sorted-COO segment-logsumexp iteration on a log-space sketch.

    Dispatches to `repro.batch.solvers.sparse_log_potentials` at B = 1 —
    the same compiled kernel the batched executor runs — so batched
    ``spar_sink_log`` results are **bitwise** the per-problem ones (two
    differently-shaped XLA programs may legally differ by a ulp in the
    fused exp/log of the logsumexp; one shared B-invariant program cannot).
    `repro.core.sinkhorn.generic_sparse_log_loop` remains the generic
    closure-based reference of the same iteration.
    """
    from repro.batch.solvers import sparse_log_potentials  # local: avoids cycle
    from repro.core.sinkhorn import SinkhornResult

    eps = float(problem.eps)
    n, m = problem.shape
    csort = sk.csort[None] if sk.csort is not None else None
    res = sparse_log_potentials(
        sk.rows[None],
        sk.cols[None],
        sk.logvals[None],
        csort,
        _masked_log(problem.a)[None],
        _masked_log(problem.b)[None],
        jnp.asarray([eps], problem.a.dtype),
        jnp.asarray([problem.fe], problem.a.dtype),
        n=n,
        m=m,
        tol=tol,
        max_iter=max_iter,
        trace=trace,
        init=(init[0][None], init[1][None]) if init is not None else None,
    )
    f, g, t, err, status = res[:5]
    tr = None
    if trace:  # slice the B = 1 batched trace down to the per-problem shape
        btr = res[5]
        tr = SolverTrace(btr.err[0], btr.marg[0], btr.n_matvec[0])
    return SinkhornResult(f[0], g[0], t[0], err[0], status[0], tr)


def _coo_log_value(problem: OTProblem, sk, c_e, res) -> jax.Array:
    """O(cap) entropic objective of a log-domain sparse solve, evaluated
    from potentials and gathered costs."""
    if isinstance(problem, UOTProblem) and not problem.is_balanced:
        return coo_objective_uot_log_entries(
            sk, c_e, res, problem.a, problem.b, float(problem.lam), problem.eps
        )
    return coo_objective_ot_log_entries(sk, c_e, res, problem.eps)


def _coo_log_solution(
    method: str, problem: OTProblem, sk, res, value, sketch_stats=None, certificate=None
) -> Solution:
    eps = float(problem.eps)

    def sparse_plan() -> SparsePlan:
        # t_e = exp((f_i + g_j - C_e)/eps - log p*_e); padded slots exact 0
        return SparsePlan(
            sk.rows, sk.cols, log_plan_entries(sk, res, eps), sk.nnz, sk.n, sk.m
        )

    return Solution(
        method=method,
        problem=problem,
        value=value,
        result=res,
        domain="log",
        nnz=sk.nnz,
        overflowed=sk.overflowed,
        sketch_stats=sketch_stats,
        certificate=certificate,
        _plan_thunk=sparse_plan,
    )


@register_solver("spar_sink_log")
def _solve_spar_sink_log(
    problem: OTProblem,
    *,
    key: jax.Array,
    s: float,
    cap: int | None = None,
    shrinkage: float = 0.0,
    probs: jax.Array | None = None,
    tol: float = DEFAULT_TOL,
    max_iter: int = 1000,
    trace: bool | int = False,
    certify: bool = False,
    init: tuple[jax.Array, jax.Array] | None = None,
) -> Solution:
    """**Log-domain** Spar-Sink (paper Alg. 3/4), safe for small ``eps``.

    Same importance sketch as ``spar_sink_coo`` (bitwise-identical sampled
    support for the same PRNG key on OT problems), but the sketch carries
    ``logvals = -C_e/eps - log p*_e`` and the iteration runs sorted-COO
    segment-logsumexp on dual potentials — nothing ever evaluates
    ``exp(-C/eps)``, so ``eps`` down to 1e-3 and below (the paper's Sec. 5
    sweep) cannot underflow the solve the way the scaling-domain sketch
    does. Returns a ``domain="log"`` `Solution`; plan and objective are
    evaluated from the potentials.

    The solve's phases are host spans (`repro.obs.span`, recorded into
    `repro.obs.default_registry`): ``spar_sink.solve`` around
    ``spar_sink.sketch``, ``spar_sink.loop``, ``spar_sink.objective`` and,
    with ``certify``, ``spar_sink.certify``.
    """
    with span("spar_sink.solve"):
        with span("spar_sink.sketch"):
            sk, c_e = build_coo_log_sketch(
                problem, key, s, cap=cap, probs=probs, shrinkage=shrinkage
            )
        with span("spar_sink.loop"):
            res = _sparse_log_loop(problem, sk, tol, max_iter, trace, init=init)
        with span("spar_sink.objective"):
            value = _coo_log_value(problem, sk, c_e, res)
        cert = None
        if certify:
            with span("spar_sink.certify"):
                cert = _sparse_cert(problem, sk, res, value, c_e, log_domain=True)
        return _coo_log_solution(
            "spar_sink_log", problem, sk, res, value,
            sketch_stats=_sketch_stats(sk, trace), certificate=cert,
        )


@register_solver("spar_sink_mf")
def _solve_spar_sink_mf(
    problem: OTProblem,
    *,
    key: jax.Array,
    s: float,
    cap: int | None = None,
    impl: str = "auto",
    shared_variates: bool = False,
    stabilize: bool = False,
    tol: float = DEFAULT_TOL,
    max_iter: int = 1000,
    trace: bool | int = False,
    certify: bool = False,
    init: tuple[jax.Array, jax.Array] | None = None,
) -> Solution:
    """Matrix-free Spar-Sink: Õ(n) end to end, no (n, m) array anywhere.

    Requires a `PointCloudGeometry` problem. Sketch construction is the
    factorized O(s log n) sampler (`build_mf_sketch`), the iteration runs
    sorted-COO segment-sums, and the objective uses gathered costs — so
    memory stays O(n + s) and n >= 2^17 fits on a laptop.

    ``stabilize=True`` runs the whole pipeline in the **log domain**
    (`build_mf_log_sketch` + segment-logsumexp on potentials): still
    matrix-free, but safe for small ``eps`` where the default
    scaling-domain sketch underflows ``exp(-C/eps)`` to an all-zero (and
    now loudly ``degenerate``-flagged) solve. Returns a ``domain="log"``
    `Solution` in that mode. ``impl`` only affects the scaling-domain
    path: the stabilized sketch gathers raw costs (there is no kernel
    exponential to fuse), so the Pallas gathered-kernel backend does not
    apply to it.

    ``shared_variates=True`` is the small-n **test mode**: it draws the
    exact Bernoulli bits of the dense-sketch ``spar_sink_coo`` path (which
    materializes O(n m), hence only below the geometry's ``dense_guard``),
    making scalings bitwise-identical to ``spar_sink_coo`` for the same
    PRNG key; only the objective differs (gathered vs dense-indexed costs,
    equal up to rounding). Combined with ``stabilize=True`` it draws the
    ``spar_sink_log`` support instead.

    Both domains record the phase spans of ``spar_sink_log``.
    """
    geom = _mf_geometry(problem)
    if init is not None and not stabilize:
        raise ValueError(
            "init= (warm-started potentials) requires the log-domain "
            "stabilize=True path"
        )
    with span("spar_sink.solve"):
        if stabilize:
            with span("spar_sink.sketch"):
                if shared_variates:
                    sk, c_e = build_coo_log_sketch(problem, key, s, cap=cap)
                else:
                    sk, c_e = build_mf_log_sketch(problem, key, s, cap=cap)
            with span("spar_sink.loop"):
                res = _sparse_log_loop(problem, sk, tol, max_iter, trace, init=init)
            with span("spar_sink.objective"):
                value = _coo_log_value(problem, sk, c_e, res)
            cert = None
            if certify:
                with span("spar_sink.certify"):
                    cert = _sparse_cert(problem, sk, res, value, c_e, log_domain=True)
            return _coo_log_solution(
                "spar_sink_mf", problem, sk, res, value,
                sketch_stats=_sketch_stats(sk, trace), certificate=cert,
            )
        with span("spar_sink.sketch"):
            if shared_variates:
                sk = build_coo_sketch(problem, key, s, cap=cap)  # guarded dense draw
                c_e = geom.cost_entries(sk.rows, sk.cols)
            else:
                sk, c_e = build_mf_sketch(problem, key, s, cap=cap, impl=impl)
        with span("spar_sink.loop"):
            res = _coo_scaling_loop(problem, sk, tol, max_iter, trace)
        with span("spar_sink.objective"):
            if isinstance(problem, UOTProblem) and not problem.is_balanced:
                value = coo_objective_uot_entries(
                    sk, c_e, res, problem.a, problem.b, float(problem.lam), problem.eps
                )
            else:
                value = coo_objective_ot_entries(sk, c_e, res, problem.eps)
        cert = None
        if certify:
            with span("spar_sink.certify"):
                cert = _sparse_cert(problem, sk, res, value, c_e, log_domain=False)
        return _coo_solution(
            "spar_sink_mf", problem, sk, res, value,
            sketch_stats=_sketch_stats(sk, trace), certificate=cert,
        )


@register_solver("rand_sink")
def _solve_rand_sink(
    problem: OTProblem,
    *,
    key: jax.Array,
    s: float,
    cap: int | None = None,
    tol: float = DEFAULT_TOL,
    max_iter: int = 1000,
    trace: bool | int = False,
    certify: bool = False,
) -> Solution:
    """Spar-Sink with uniform probabilities (the paper's Rand-Sink baseline).

    The uniform probabilities are passed as O(n)+O(m) row/col factors
    (`sparsify.uniform_prob_factors`) — the baseline no longer materializes
    an (n, m) probability array (same keep-probabilities, same draws)."""
    n, m = problem.shape
    sol = _solve_spar_sink_coo(
        problem,
        key=key,
        s=s,
        cap=cap,
        probs=sparsify.uniform_prob_factors(n, m, problem.geom.dtype),
        tol=tol,
        max_iter=max_iter,
        trace=trace,
        certify=certify,
    )
    sol.method = "rand_sink"
    return sol


@register_solver("spar_sink_dense")
def _solve_spar_sink_dense(
    problem: OTProblem,
    *,
    key: jax.Array,
    s: float,
    shrinkage: float = 0.0,
    probs: jax.Array | None = None,
    tol: float = DEFAULT_TOL,
    max_iter: int = 1000,
    trace: bool | int = False,
    certify: bool = False,
) -> Solution:
    """Exact eq.(7) sketch held as a dense masked array (O(n^2) reference;
    scaling domain — same small-``eps`` caveat as ``spar_sink_coo``)."""
    K = problem.kernel()
    probs = _resolve_probs(problem, probs, shrinkage)
    Kt = sparsify.sparsify_dense(key, K, probs, s)
    res = generic_scaling_loop(
        lambda v: Kt @ v,
        lambda u: Kt.T @ u,
        problem.a,
        problem.b,
        problem.fe,
        tol=tol,
        max_iter=max_iter,
        trace=trace,
    )
    return _dense_solution(
        problem, "spar_sink_dense", res, Kt, nnz=jnp.sum(Kt > 0), certify=certify,
        cost=_kernel_cost(Kt, float(problem.eps)) if certify else None,
    )


@register_solver("spar_sink_block_ell")
def _solve_spar_sink_block_ell(
    problem: OTProblem,
    *,
    key: jax.Array,
    s: float,
    block: int = 128,
    max_blocks: int | None = None,
    shrinkage: float = 0.0,
    probs: jax.Array | None = None,
    tol: float = DEFAULT_TOL,
    max_iter: int = 1000,
    trace: bool | int = False,
    certify: bool = False,
) -> Solution:
    """Tile-granular sketch in block-ELL layout (dense MXU work per tile;
    scaling domain — same small-``eps`` caveat as ``spar_sink_coo``)."""
    K = problem.kernel()
    probs = _resolve_probs(problem, probs, shrinkage)
    tile_p = sparsify.tile_probs_from_elem(probs, block)
    n = problem.a.shape[0]
    if max_blocks is None:
        max_blocks = default_max_blocks(n, s, block)
    sk = sparsify.sparsify_block_ell(key, K, tile_p, s, block, max_blocks)
    res = generic_scaling_loop(
        lambda v: sparsify.block_ell_matvec(sk, v),
        lambda u: sparsify.block_ell_rmatvec(sk, u),
        problem.a,
        problem.b,
        problem.fe,
        tol=tol,
        max_iter=max_iter,
        trace=trace,
    )
    # Transient densification for the objective (legacy behavior); the
    # Solution itself retains only the O(s*Bk) block-ELL tiles.
    Kt = sparsify.block_ell_to_dense(sk)
    T = plan_from_scalings(res.u, Kt, res.v)
    value = problem.objective(T)
    nnz = jnp.sum(Kt > 0)
    cert = None
    if certify:
        eps = float(problem.eps)
        f, g = _scaling_potentials(res, eps)
        cert = dense_certificate(
            plan=T,
            cost=_kernel_cost(Kt, eps),
            a=problem.a,
            b=problem.b,
            f=f,
            g=g,
            eps=eps,
            lam=_problem_lam(problem),
            value=value,
        )
    del T, Kt
    return Solution(
        method="spar_sink_block_ell",
        problem=problem,
        value=value,
        result=res,
        domain="scaling",
        nnz=nnz,
        certificate=cert,
        _plan_thunk=lambda: plan_from_scalings(
            res.u, sparsify.block_ell_to_dense(sk), res.v
        ),
    )


# --------------------------------------------------------------------------
# Competitor solvers (paper Section 5 baselines)
# --------------------------------------------------------------------------


@register_solver("greenkhorn")
def _solve_greenkhorn(
    problem: OTProblem, *, n_updates: int | None = None, certify: bool = False
) -> Solution:
    """Greedy single-coordinate scalings; ``n_updates`` defaults to 5(n+m)."""
    n, m = problem.shape
    if n_updates is None:
        n_updates = 5 * (n + m)
    res = greenkhorn(
        # fe is a static (hashable) jit argument in greenkhorn
        problem.kernel(), problem.a, problem.b, n_updates, fe=float(problem.fe)
    )
    return _dense_solution(problem, "greenkhorn", res, problem.kernel(), certify=certify)


@register_solver("nys_sink")
def _solve_nys_sink(
    problem: OTProblem,
    *,
    key: jax.Array,
    rank: int | None = None,
    tol: float = DEFAULT_TOL,
    max_iter: int = 1000,
    certify: bool = False,
) -> Solution:
    """Nyström low-rank kernel + Sinkhorn. Needs near-PSD K (fails on WFR)."""
    n, m = problem.shape
    if rank is None:
        rank = max(2, min(n, m) // 20)
    res, nk = nys_sink(
        key,
        problem.kernel(),
        problem.a,
        problem.b,
        rank,
        tol=tol,
        max_iter=max_iter,
        fe=problem.fe,
    )
    # Evaluate the objective on a transient dense plan; the Solution keeps
    # only the O(nr) factors until .plan()/.marginals() is first accessed
    # (which re-densifies and caches, per the Solution contract).
    T = plan_from_scalings(res.u, nk.dense(), res.v)
    value = problem.objective(T)
    cert = None
    if certify:
        # certify against the low-rank kernel the solver optimized; Nyström
        # entries can go negative — those fall outside the certified support
        eps = float(problem.eps)
        f, g = _scaling_potentials(res, eps)
        cert = dense_certificate(
            plan=T,
            cost=_kernel_cost(nk.dense(), eps),
            a=problem.a,
            b=problem.b,
            f=f,
            g=g,
            eps=eps,
            lam=_problem_lam(problem),
            value=value,
        )
    del T
    return Solution(
        method="nys_sink",
        problem=problem,
        value=value,
        result=res,
        domain="scaling",
        certificate=cert,
        _plan_thunk=lambda: plan_from_scalings(res.u, nk.dense(), res.v),
    )


@register_solver("screenkhorn_lite")
def _solve_screenkhorn_lite(
    problem: OTProblem,
    *,
    decimation: int = 3,
    tol: float = DEFAULT_TOL,
    max_iter: int = 1000,
    certify: bool = False,
) -> Solution:
    """Static active-set screening; screened-out atoms keep zero scalings."""
    res, _, _ = screenkhorn_lite(
        problem.kernel(),
        problem.a,
        problem.b,
        decimation=decimation,
        tol=tol,
        max_iter=max_iter,
        fe=problem.fe,
        renormalize=problem.is_balanced,
    )
    return _dense_solution(
        problem, "screenkhorn_lite", res, problem.kernel(), certify=certify
    )
