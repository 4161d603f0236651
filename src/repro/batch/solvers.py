"""Batched solver kernels: whole-batch jit programs over `BatchedProblem`.

Five registered batched methods mirror the per-problem registry paths:

* ``dense``         — scaling-domain Sinkhorn on the (B, n, m) Gibbs kernels
* ``log``           — log-domain Sinkhorn on the (B, n, m) log-kernels
* ``spar_sink_coo`` — paper Alg. 3/4 on a fixed-cap batched COO sketch:
                      one ``(B, cap)`` index/value array, per-problem PRNG
                      keys, one segment-sum mat-vec pair per iteration
* ``spar_sink_log`` — the same sketch carried in **log space** (``vals`` =
                      logvals), iterated by batched segment-logsumexp on
                      potentials: small-``eps`` safe (`sparse_log_potentials`
                      is also the per-problem kernel, so results are bitwise)
* ``spar_sink_mf``  — matrix-free sketches; ``stabilize=True`` switches it
                      to the log-domain iteration too

The iteration loops are *per-element frozen* versions of
:func:`repro.core.sinkhorn.generic_scaling_loop` /
:func:`~repro.core.sinkhorn.generic_log_loop`: one `lax.while_loop` runs
until every element has met its own stopping rule, and converged elements
stop updating (their trajectories are exactly the per-problem ones — same
iteration counts, same stall detection — so batched results match
per-problem ``solve()``).

Sketch construction is split so Monte Carlo draws stay *bitwise identical*
to per-problem ``build_coo_sketch``:

* `build_batched_sketch` (the executor's default) draws each element's
  sketch at its **true** ``(n_i, m_i)`` shape host-side — the exact bits of
  the per-problem path for the same PRNG key — and stacks the padded COO
  triples into one ``(B, cap)`` array; only the solve remains to jit.
* `batched_coo_sketch` is the fully-fused in-jit variant (`lax.map` over
  the batch): same bits *when a problem exactly fills its bucket* (draw
  shapes match), otherwise an equally-distributed but different draw on the
  padded support (padding has probability 0 either way).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.batch.problems import BatchedProblem
from repro.core import sparsify
from repro.core.sinkhorn import (
    _masked_log,
    _status_code,
    kl_divergence,
    ot_cost_from_plan,
    uot_cost_from_plan,
)
from repro.core.spar_sink import default_cap
from repro.obs.certify import (
    Certificate,
    dense_certificate,
    importance_ess,
    sparse_certificate,
)
from repro.obs.metrics import default_registry
from repro.obs.trace import (
    SolverTrace,
    empty_trace,
    record_iteration,
    resolve_trace_len,
)

__all__ = [
    "BatchedResult",
    "BatchedSketch",
    "batchable_methods",
    "batched_coo_sketch",
    "batched_log_loop",
    "batched_scaling_loop",
    "batched_sparse_log_loop",
    "build_batched_log_sketch",
    "build_batched_mf_log_sketch",
    "build_batched_mf_sketch",
    "build_batched_sketch",
    "get_batched_solver",
    "register_batched_solver",
    "sparse_log_potentials",
]


class BatchedSketch(NamedTuple):
    """B fixed-cap padded-COO kernel sketches as one array set (the batched
    `repro.core.sparsify.SparseKernelCOO`; padded slots carry vals == 0).

    ``csort`` is the per-element col-sorted permutation (rows are sorted by
    construction), so both batched segment-sums run with
    ``indices_are_sorted=True``. ``cost_e`` carries the gathered raw costs
    on the matrix-free path (None for dense-sketch builds, which gather
    from the batched cost instead)."""

    rows: jax.Array  # (B, cap) int32, per-element ascending
    cols: jax.Array  # (B, cap) int32
    vals: jax.Array  # (B, cap)
    nnz: jax.Array  # (B,) int32
    csort: jax.Array | None = None  # (B, cap) int32
    overflowed: jax.Array | None = None  # (B,) bool
    cost_e: jax.Array | None = None  # (B, cap) gathered costs (mf path)

    @property
    def cap(self) -> int:
        return self.rows.shape[1]


class BatchedResult(NamedTuple):
    """Per-element solver outputs; sketch fields are ``None`` off the
    spar_sink path (None is an empty pytree node, so jit passes it through)."""

    u: jax.Array  # (B, n) scalings (or potentials f in the log domain)
    v: jax.Array  # (B, m)
    n_iter: jax.Array  # (B,) int32
    err: jax.Array  # (B,)
    value: jax.Array  # (B,) entropic objective estimates
    rows: jax.Array | None = None  # (B, cap) int32
    cols: jax.Array | None = None  # (B, cap) int32
    vals: jax.Array | None = None  # (B, cap) sketch kernel values (logvals
    #                                on the spar_sink_log / stabilized path)
    nnz: jax.Array | None = None  # (B,) int32
    overflowed: jax.Array | None = None  # (B,) bool — sketch draw truncated
    status: jax.Array | None = None  # (B,) int32 STATUS_* convergence codes
    #: batched per-iteration ring-buffer telemetry ((B, L) buffers + (B,)
    #: matvec counter); ``None`` unless the solve ran with ``trace=True``
    trace: SolverTrace | None = None
    #: batched quality certificate ((B,) fields, sliced per element by the
    #: executor); ``None`` unless the solve ran with ``certify=True``
    certificate: Certificate | None = None


# --------------------------------------------------------------------------
# Batched iteration loops (per-element freezing)
# --------------------------------------------------------------------------


def _l1(x: jax.Array) -> jax.Array:
    return jnp.sum(jnp.abs(x), axis=-1)


def _safe_div(num: jax.Array, den: jax.Array) -> jax.Array:
    return jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0), 0.0)


def batched_scaling_loop(
    matvec: Callable[[jax.Array], jax.Array],
    rmatvec: Callable[[jax.Array], jax.Array],
    a: jax.Array,
    b: jax.Array,
    fe: jax.Array,
    *,
    tol: float = 1e-6,
    max_iter: int = 1000,
    patience: int = 100,
    trace: bool | int = False,
):
    """Scaling-domain Sinkhorn over a batch; ``matvec: (B, m) -> (B, n)``.

    Each element follows exactly the per-problem loop (stopping rule,
    stall detection, non-finite exit) and is frozen once it stops; the
    while_loop exits when the whole batch is done. Extra wall-clock cost vs
    the slowest element is zero — frozen elements' updates are computed but
    discarded. Returns ``(u, v, n_iter, err, status)`` with per-element
    ``STATUS_*`` codes, like the per-problem `generic_scaling_loop`.

    ``trace`` (static) appends a batched `repro.obs.SolverTrace` to the
    return tuple — frozen elements stop recording, so each element's trace
    is exactly its per-problem one; the default ``False`` adds no loop
    state and no ops.
    """
    B, n = a.shape
    m = b.shape[1]
    u0 = jnp.ones((B, n), a.dtype)
    v0 = jnp.ones((B, m), b.dtype)
    big = jnp.full((B,), jnp.finfo(a.dtype).max, a.dtype)
    fe_col = fe[:, None]

    def cond(state):
        return jnp.any(state[-1])

    def body(state):
        u, v, t, err, best, since = state[:6]
        active = state[-1]
        Kv = matvec(v)
        u_new = _safe_div(a, Kv) ** fe_col
        KTu = rmatvec(u_new)
        v_new = _safe_div(b, KTu) ** fe_col
        err_new = _l1(u_new - u) + _l1(v_new - v)
        marg = _l1(v * KTu - b)
        improved = marg < best * (1.0 - 1e-4)
        best_new = jnp.minimum(best, marg)
        since_new = jnp.where(improved, 0, since + 1)
        # freeze finished elements at their final state
        keep = active[:, None]
        u = jnp.where(keep, u_new, u)
        v = jnp.where(keep, v_new, v)
        err = jnp.where(active, err_new, err)
        best = jnp.where(active, best_new, best)
        since = jnp.where(active, since_new, since)
        out = (u, v, jnp.where(active, t + 1, t), err, best, since)
        if trace:
            out += (record_iteration(state[6], t, err_new, marg, active=active),)
        t = out[2]
        active = (
            active
            & (err > tol)
            & jnp.isfinite(err)
            & (t < max_iter)
            & (since < patience)
        )
        return out + (active,)

    state = (
        u0,
        v0,
        jnp.zeros((B,), jnp.int32),
        big,
        big,
        jnp.zeros((B,), jnp.int32),
    )
    if trace:
        state += (empty_trace(resolve_trace_len(trace), a.dtype, batch=B),)
    final = jax.lax.while_loop(cond, body, state + (jnp.ones((B,), bool),))
    u, v, t, err, _, since = final[:6]
    bad = ~(
        jnp.isfinite(err)
        & jnp.all(jnp.isfinite(u), axis=-1)
        & jnp.all(jnp.isfinite(v), axis=-1)
    )
    degenerate = (jnp.max(u, axis=-1) <= 0.0) | (jnp.max(v, axis=-1) <= 0.0)
    out = (u, v, t, err, _status_code(bad, degenerate, err, tol, since >= patience))
    return out + (final[6],) if trace else out


def batched_log_loop(
    lse_row: Callable[[jax.Array], jax.Array],
    lse_col: Callable[[jax.Array], jax.Array],
    loga: jax.Array,
    logb: jax.Array,
    eps: jax.Array,
    fe: jax.Array,
    *,
    tol: float = 1e-9,
    max_iter: int = 1000,
    trace: bool | int = False,
):
    """Log-domain Sinkhorn over a batch on potentials; per-element freezing.
    ``lse_row(g): (B, m) -> (B, n)`` and vice versa; ``eps``/``fe`` are (B,).
    Returns ``(f, g, n_iter, err, status)`` with per-element ``STATUS_*``.
    ``trace`` (static) appends a batched `repro.obs.SolverTrace` — the
    column-marginal violation is computed only on the traced path (the
    stopping rule here doesn't need it)."""
    B, n = loga.shape
    m = logb.shape[1]
    f0 = jnp.zeros((B, n), loga.dtype)
    g0 = jnp.zeros((B, m), logb.dtype)
    neg_inf_a = jnp.isneginf(loga)
    neg_inf_b = jnp.isneginf(logb)
    scale = (fe * eps)[:, None]
    if trace:
        b_lin = jnp.exp(logb)
        eps_col = eps[:, None]

    def cond(state):
        return jnp.any(state[-1])

    def body(state):
        f, g, t, err = state[:4]
        active = state[-1]
        f_new = scale * (loga - lse_row(g))
        f_new = jnp.where(neg_inf_a, -jnp.inf, f_new)
        lc = lse_col(f_new)
        g_new = scale * (logb - lc)
        g_new = jnp.where(neg_inf_b, -jnp.inf, g_new)
        df = jnp.where(neg_inf_a, 0.0, jnp.abs(f_new - f))
        dg = jnp.where(neg_inf_b, 0.0, jnp.abs(g_new - g))
        err_new = jnp.max(df, axis=-1) + jnp.max(dg, axis=-1)
        if trace:
            # pre-update g: the column marginal of the plan after the
            # f-update, mirroring the sparse loops' stall metric
            col_marg = jnp.where(
                jnp.isneginf(g) | jnp.isneginf(lc), 0.0, jnp.exp(g / eps_col + lc)
            )
            marg = jnp.sum(jnp.abs(col_marg - b_lin), axis=-1)
        keep = active[:, None]
        f = jnp.where(keep, f_new, f)
        g = jnp.where(keep, g_new, g)
        err = jnp.where(active, err_new, err)
        out = (f, g, jnp.where(active, t + 1, t), err)
        if trace:
            out += (record_iteration(state[4], t, err_new, marg, active=active),)
        t = out[2]
        active = active & (err > tol) & (t < max_iter)
        return out + (active,)

    state = (
        f0,
        g0,
        jnp.zeros((B,), jnp.int32),
        jnp.full((B,), jnp.inf, loga.dtype),
    )
    if trace:
        state += (empty_trace(resolve_trace_len(trace), loga.dtype, batch=B),)
    final = jax.lax.while_loop(cond, body, state + (jnp.ones((B,), bool),))
    f, g, t, err = final[:4]
    out = (f, g, t, err, _batched_log_status(f, g, err, tol))
    return out + (final[4],) if trace else out


def _batched_log_status(
    f: jax.Array,
    g: jax.Array,
    err: jax.Array,
    tol: float,
    stalled: jax.Array | bool = False,
) -> jax.Array:
    """Per-element mirror of `repro.core.sinkhorn._log_domain_status`."""
    bad = (
        jnp.isnan(err)
        | jnp.any(jnp.isnan(f) | (f == jnp.inf), axis=-1)
        | jnp.any(jnp.isnan(g) | (g == jnp.inf), axis=-1)
    )
    degenerate = jnp.all(jnp.isneginf(f), axis=-1) | jnp.all(
        jnp.isneginf(g), axis=-1
    )
    return _status_code(bad, degenerate, err, tol, stalled)


def batched_sparse_log_loop(
    lse_row: Callable[[jax.Array], jax.Array],
    lse_col: Callable[[jax.Array], jax.Array],
    loga: jax.Array,
    logb: jax.Array,
    eps: jax.Array,
    fe: jax.Array,
    *,
    tol: float = 1e-6,
    max_iter: int = 1000,
    patience: int = 100,
    trace: bool | int = False,
    init: tuple[jax.Array, jax.Array] | None = None,
):
    """Per-element-frozen mirror of
    :func:`repro.core.sinkhorn.generic_sparse_log_loop`: log-domain
    Sinkhorn on B sparse (sketched) kernels, with the sketch conventions —
    atoms whose sparse logsumexp is ``-inf`` get pinned to ``-inf``
    (covers dead rows *and* inert bucket padding, which starts pinned), and
    the scaling loop's stall detection on the column-marginal violation.
    Each element reproduces the per-problem trajectory exactly.
    ``init=(f0, g0)`` — both (B, ·) — warm-starts the potentials exactly
    like `generic_sparse_log_loop`'s ``init`` (non-finite entries -> 0,
    then dead-atom pinning); the default ``None`` leaves the jaxpr
    untouched. Returns ``(f, g, n_iter, err, status)``; ``trace`` (static)
    appends a batched `repro.obs.SolverTrace`.
    """
    B, n = loga.shape
    m = logb.shape[1]
    neg_inf_a = jnp.isneginf(loga)
    neg_inf_b = jnp.isneginf(logb)
    if init is None:
        f0 = jnp.where(neg_inf_a, -jnp.inf, jnp.zeros((B, n), loga.dtype))
        g0 = jnp.where(neg_inf_b, -jnp.inf, jnp.zeros((B, m), logb.dtype))
    else:  # warm start: non-finite entries -> 0, then dead-atom pinning
        f0 = jnp.asarray(init[0], loga.dtype)
        g0 = jnp.asarray(init[1], logb.dtype)
        f0 = jnp.where(neg_inf_a, -jnp.inf, jnp.where(jnp.isfinite(f0), f0, 0.0))
        g0 = jnp.where(neg_inf_b, -jnp.inf, jnp.where(jnp.isfinite(g0), g0, 0.0))
    big = jnp.full((B,), jnp.finfo(loga.dtype).max, loga.dtype)
    scale = (fe * eps)[:, None]
    eps_col = eps[:, None]
    b_lin = jnp.exp(logb)

    def cond(state):
        return jnp.any(state[-1])

    def body(state):
        f, g, t, err, best, since = state[:6]
        active = state[-1]
        lr = lse_row(g)
        f_new = scale * (loga - lr)
        f_new = jnp.where(neg_inf_a | jnp.isneginf(lr), -jnp.inf, f_new)
        lc = lse_col(f_new)
        g_new = scale * (logb - lc)
        g_new = jnp.where(neg_inf_b | jnp.isneginf(lc), -jnp.inf, g_new)
        df = jnp.where(
            jnp.isneginf(f_new) & jnp.isneginf(f), 0.0, jnp.abs(f_new - f)
        )
        dg = jnp.where(
            jnp.isneginf(g_new) & jnp.isneginf(g), 0.0, jnp.abs(g_new - g)
        )
        err_new = jnp.max(df, axis=-1) + jnp.max(dg, axis=-1)
        col_marg = jnp.where(
            jnp.isneginf(g) | jnp.isneginf(lc), 0.0, jnp.exp(g / eps_col + lc)
        )
        marg = jnp.sum(jnp.abs(col_marg - b_lin), axis=-1)
        improved = marg < best * (1.0 - 1e-4)
        best_new = jnp.minimum(best, marg)
        since_new = jnp.where(improved, 0, since + 1)
        keep = active[:, None]
        f = jnp.where(keep, f_new, f)
        g = jnp.where(keep, g_new, g)
        err = jnp.where(active, err_new, err)
        best = jnp.where(active, best_new, best)
        since = jnp.where(active, since_new, since)
        out = (f, g, jnp.where(active, t + 1, t), err, best, since)
        if trace:
            out += (record_iteration(state[6], t, err_new, marg, active=active),)
        t = out[2]
        active = active & (err > tol) & (t < max_iter) & (since < patience)
        return out + (active,)

    state = (
        f0,
        g0,
        jnp.zeros((B,), jnp.int32),
        big,
        big,
        jnp.zeros((B,), jnp.int32),
    )
    if trace:
        state += (empty_trace(resolve_trace_len(trace), loga.dtype, batch=B),)
    final = jax.lax.while_loop(cond, body, state + (jnp.ones((B,), bool),))
    f, g, t, err, _, since = final[:6]
    out = (f, g, t, err, _batched_log_status(f, g, err, tol, since >= patience))
    return out + (final[6],) if trace else out


# --------------------------------------------------------------------------
# Shared batched pieces
# --------------------------------------------------------------------------


# (_masked_log is imported from repro.core.sinkhorn: one masked-log
# implementation repo-wide, so loga/logb bits match between serving modes)


def _batched_value_from_plan(bp: BatchedProblem, T: jax.Array) -> jax.Array:
    """Per-element entropic objective of dense plans, OT/UOT selected per
    element (the lam=inf branch of the UOT formula is inf/nan and discarded
    by the where — exactly `UOTProblem.objective`'s balanced branch)."""
    v_ot = jax.vmap(ot_cost_from_plan)(T, bp.cost, bp.eps)
    v_uot = jax.vmap(uot_cost_from_plan)(T, bp.cost, bp.a, bp.b, bp.lam, bp.eps)
    return jnp.where(bp.is_balanced, v_ot, v_uot)


def _batched_lam(bp: BatchedProblem) -> jax.Array:
    """Per-element marginal penalty with balanced elements pinned to ``inf``
    (selects the balanced dual branch inside the certificate math)."""
    return jnp.where(bp.is_balanced, jnp.inf, bp.lam)


def _batched_potentials(u: jax.Array, v: jax.Array, eps: jax.Array):
    """Batched ``(f, g) = eps log(u, v)`` with dead atoms at ``-inf``."""
    eps_col = eps[:, None]
    f = jnp.where(u > 0, eps_col * jnp.log(jnp.where(u > 0, u, 1.0)), -jnp.inf)
    g = jnp.where(v > 0, eps_col * jnp.log(jnp.where(v > 0, v, 1.0)), -jnp.inf)
    return f, g


def _batched_dense_cert(
    bp: BatchedProblem, T: jax.Array, f: jax.Array, g: jax.Array, value: jax.Array
) -> Certificate:
    """vmapped `repro.obs.certify.dense_certificate` over the batch."""

    def one(T_i, cost_i, a_i, b_i, f_i, g_i, eps_i, lam_i, value_i):
        return dense_certificate(
            plan=T_i, cost=cost_i, a=a_i, b=b_i, f=f_i, g=g_i,
            eps=eps_i, lam=lam_i, value=value_i,
        )

    return jax.vmap(one)(
        T, bp.cost, bp.a, bp.b, f, g, bp.eps, _batched_lam(bp), value
    )


def _batched_sparse_cert(
    bp: BatchedProblem,
    t_e: jax.Array,
    c_e: jax.Array,
    rows: jax.Array,
    cols: jax.Array,
    f: jax.Array,
    g: jax.Array,
    k_e: jax.Array,
    p_e: jax.Array,
    ess: jax.Array,
    value: jax.Array,
    n: int,
    m: int,
) -> Certificate:
    """vmapped `repro.obs.certify.sparse_certificate` over the batch."""

    def one(t_i, c_i, r_i, co_i, a_i, b_i, f_i, g_i, eps_i, lam_i, v_i, k_i, p_i, e_i):
        return sparse_certificate(
            t_e=t_i, c_e=c_i, rows=r_i, cols=co_i, n=n, m=m, a=a_i, b=b_i,
            f=f_i, g=g_i, eps=eps_i, lam=lam_i, value=v_i, k_e=k_i, p_e=p_i,
            ess=e_i,
        )

    return jax.vmap(one)(
        t_e, c_e, rows, cols, bp.a, bp.b, f, g, bp.eps, _batched_lam(bp),
        value, k_e, p_e, ess,
    )


def _element_probs(cost_i, a_i, b_i, eps_i, lam_i) -> jax.Array:
    """Per-element sampling probabilities: eq. (9) where balanced, eq. (11)
    otherwise — the batched mirror of `repro.core.api.solvers.sampling_probs`."""
    p_ot = sparsify.ot_sampling_probs(a_i, b_i)
    logK_i = jnp.where(jnp.isinf(cost_i), -jnp.inf, -cost_i / eps_i)
    p_uot = sparsify.uot_sampling_probs(a_i, b_i, logK_i, lam_i, eps_i)
    return jnp.where(jnp.isinf(lam_i), p_ot, p_uot)


# --------------------------------------------------------------------------
# Batched solver registry
# --------------------------------------------------------------------------

BatchedSolverFn = Callable[..., BatchedResult]

_BATCH_REGISTRY: dict[str, BatchedSolverFn] = {}


def register_batched_solver(name: str) -> Callable[[BatchedSolverFn], BatchedSolverFn]:
    """Decorator: register a batched kernel under the per-problem method name."""

    def deco(fn: BatchedSolverFn) -> BatchedSolverFn:
        if name in _BATCH_REGISTRY:
            raise ValueError(f"batched solver {name!r} already registered")
        _BATCH_REGISTRY[name] = fn
        return fn

    return deco


def batchable_methods() -> list[str]:
    """Method names `BucketedExecutor` can dispatch (a subset of
    `repro.core.api.available_methods()`)."""
    return sorted(_BATCH_REGISTRY)


def get_batched_solver(method: str) -> BatchedSolverFn:
    try:
        return _BATCH_REGISTRY[method]
    except KeyError:
        raise KeyError(
            f"method {method!r} has no batched kernel; batchable: "
            f"{', '.join(sorted(_BATCH_REGISTRY))}"
        ) from None


@register_batched_solver("dense")
def batched_solve_dense(
    bp: BatchedProblem,
    keys: jax.Array | None = None,
    *,
    tol: float = 1e-6,
    max_iter: int = 1000,
    trace: bool | int = False,
    certify: bool = False,
) -> BatchedResult:
    """Scaling-domain Sinkhorn on B dense Gibbs kernels at once."""
    del keys
    K = bp.kernel()
    res = batched_scaling_loop(
        lambda vv: jnp.einsum("bnm,bm->bn", K, vv),
        lambda uu: jnp.einsum("bnm,bn->bm", K, uu),
        bp.a,
        bp.b,
        bp.fe,
        tol=tol,
        max_iter=max_iter,
        trace=trace,
    )
    u, v, t, err, status = res[:5]
    T = u[:, :, None] * K * v[:, None, :]
    value = _batched_value_from_plan(bp, T)
    cert = None
    if certify:
        f, g = _batched_potentials(u, v, bp.eps)
        cert = _batched_dense_cert(bp, T, f, g, value)
    return BatchedResult(
        u, v, t, err, value, status=status,
        trace=res[5] if trace else None, certificate=cert,
    )


@register_batched_solver("log")
def batched_solve_log(
    bp: BatchedProblem,
    keys: jax.Array | None = None,
    *,
    tol: float = 1e-9,
    max_iter: int = 1000,
    trace: bool | int = False,
    certify: bool = False,
) -> BatchedResult:
    """Log-domain Sinkhorn on B log-kernels; returns potentials ``(f, g)``."""
    del keys
    logK = bp.log_kernel()
    res = batched_log_loop(
        lambda gg: jax.scipy.special.logsumexp(
            logK + gg[:, None, :] / bp.eps[:, None, None], axis=2
        ),
        lambda ff: jax.scipy.special.logsumexp(
            logK + ff[:, :, None] / bp.eps[:, None, None], axis=1
        ),
        _masked_log(bp.a),
        _masked_log(bp.b),
        bp.eps,
        bp.fe,
        tol=tol,
        max_iter=max_iter,
        trace=trace,
    )
    f, g, t, err, status = res[:5]
    logT = logK + f[:, :, None] / bp.eps[:, None, None] + g[:, None, :] / bp.eps[:, None, None]
    T = jnp.where(jnp.isneginf(logT), 0.0, jnp.exp(logT))
    value = _batched_value_from_plan(bp, T)
    cert = None
    if certify:
        cert = _batched_dense_cert(bp, T, f, g, value)
    return BatchedResult(
        f, g, t, err, value, status=status,
        trace=res[5] if trace else None, certificate=cert,
    )


def build_batched_sketch(
    problems, keys, s: float, cap: int | None = None
) -> BatchedSketch:
    """Stack per-problem importance sketches into one fixed-cap array set.

    Each element's draw happens at its *true* support shape through
    `repro.core.api.build_coo_sketch` — bitwise the sketch the per-problem
    ``solve(..., method="spar_sink_coo")`` builds from the same PRNG key —
    so batched results are exactly reproducible against per-problem runs.
    Indices need no offsetting: padded bucket rows/cols have probability 0.
    """
    from repro.core.api.solvers import build_coo_sketch

    cap = default_cap(s) if cap is None else cap
    sks = [build_coo_sketch(p, k, s, cap=cap) for p, k in zip(problems, keys)]
    return BatchedSketch(
        rows=jnp.stack([sk.rows for sk in sks]),
        cols=jnp.stack([sk.cols for sk in sks]),
        vals=jnp.stack([sk.vals for sk in sks]),
        nnz=jnp.stack([sk.nnz for sk in sks]),
        csort=jnp.stack([sk.csort for sk in sks]),
        overflowed=jnp.stack([sk.overflowed for sk in sks]),
    )


def build_batched_mf_sketch(
    problems, keys, s: float, cap: int | None = None
) -> BatchedSketch:
    """Stack per-problem **matrix-free** sketches (`build_mf_sketch`): every
    element's geometry must be a `PointCloudGeometry`, the draw is the
    factorized O(s log n) sampler at the element's true support shape —
    bitwise the per-problem ``solve(..., method="spar_sink_mf")`` sketch
    for the same PRNG key — and the gathered raw costs ride along in
    ``cost_e`` so the batched solve never touches an (n, m) cost."""
    from repro.core.api.solvers import build_mf_sketch

    cap = default_cap(s) if cap is None else cap
    built = [build_mf_sketch(p, k, s, cap=cap) for p, k in zip(problems, keys)]
    sks = [sk for sk, _ in built]
    return BatchedSketch(
        rows=jnp.stack([sk.rows for sk in sks]),
        cols=jnp.stack([sk.cols for sk in sks]),
        vals=jnp.stack([sk.vals for sk in sks]),
        nnz=jnp.stack([sk.nnz for sk in sks]),
        csort=jnp.stack([sk.csort for sk in sks]),
        overflowed=jnp.stack([sk.overflowed for sk in sks]),
        cost_e=jnp.stack([c_e for _, c_e in built]),
    )


def build_batched_log_sketch(
    problems, keys, s: float, cap: int | None = None
) -> BatchedSketch:
    """Stack per-problem **log-space** sketches (`build_coo_log_sketch`):
    the ``vals`` field carries ``logvals`` (padding ``-inf``) and the
    gathered raw costs ride along in ``cost_e``, so the batched
    ``spar_sink_log`` solve never exponentiates ``-C/eps`` nor touches a
    (B, n, m) kernel. Each element's draw is bitwise the per-problem
    ``solve(..., method="spar_sink_log")`` sketch for the same PRNG key."""
    from repro.core.api.solvers import build_coo_log_sketch

    cap = default_cap(s) if cap is None else cap
    built = [build_coo_log_sketch(p, k, s, cap=cap) for p, k in zip(problems, keys)]
    return _stack_log_sketches(built)


def build_batched_mf_log_sketch(
    problems, keys, s: float, cap: int | None = None
) -> BatchedSketch:
    """Stack per-problem **matrix-free log-space** sketches
    (`build_mf_log_sketch`): `build_batched_mf_sketch`'s contract (pure
    `PointCloudGeometry` gathered evaluation, nothing O(n m) anywhere) with
    ``vals`` carrying ``logvals`` — the batched ``spar_sink_mf`` path with
    ``stabilize=True``. Bitwise the per-problem sketch per PRNG key."""
    from repro.core.api.solvers import build_mf_log_sketch

    cap = default_cap(s) if cap is None else cap
    built = [build_mf_log_sketch(p, k, s, cap=cap) for p, k in zip(problems, keys)]
    return _stack_log_sketches(built)


def _stack_log_sketches(built) -> BatchedSketch:
    sks = [sk for sk, _ in built]
    return BatchedSketch(
        rows=jnp.stack([sk.rows for sk in sks]),
        cols=jnp.stack([sk.cols for sk in sks]),
        vals=jnp.stack([sk.logvals for sk in sks]),
        nnz=jnp.stack([sk.nnz for sk in sks]),
        csort=jnp.stack([sk.csort for sk in sks]),
        overflowed=jnp.stack([sk.overflowed for sk in sks]),
        cost_e=jnp.stack([c_e for _, c_e in built]),
    )


def batched_coo_sketch(
    bp: BatchedProblem, keys: jax.Array, s: float, cap: int | None = None
) -> BatchedSketch:
    """Fully in-jit sketch construction (`lax.map` over the batch) at the
    bucket shape. Bitwise-equal to `build_batched_sketch` for elements that
    exactly fill the bucket; padded elements get an equally-distributed but
    different draw (see module docstring). Use inside a jit'd pipeline when
    the eager per-problem build would dominate dispatch latency."""
    cap = default_cap(s) if cap is None else cap

    def build_one(args):
        cost_i, a_i, b_i, eps_i, lam_i, key_i = args
        K_i = jnp.where(jnp.isinf(cost_i), 0.0, jnp.exp(-cost_i / eps_i))
        probs = _element_probs(cost_i, a_i, b_i, eps_i, lam_i)
        sk = sparsify.sparsify_coo(key_i, K_i, probs, s, cap)
        return sk.rows, sk.cols, sk.vals, sk.nnz, sk.csort, sk.overflowed

    rows, cols, vals, nnz, csort, overflowed = jax.lax.map(
        build_one, (bp.cost, bp.a, bp.b, bp.eps, bp.lam, keys)
    )
    return BatchedSketch(rows, cols, vals, nnz, csort, overflowed)


def _batched_sketch_solve(
    bp: BatchedProblem,
    sketch: BatchedSketch,
    c_e: jax.Array,
    tol: float,
    max_iter: int,
    trace: bool | int = False,
    certify: bool = False,
) -> BatchedResult:
    """Shared Spar-Sink core (paper Alg. 3/4) on a fixed-cap batched COO
    sketch: two batched **sorted** segment-sum mat-vecs per iteration
    (rows are construction-sorted; the transpose direction permutes through
    ``csort``), O(cap) objective per element from the gathered costs ``c_e``
    (the batched mirror of ``coo_objective_*_entries``)."""
    _, n, m = bp.shape
    rows, cols, vals = sketch.rows, sketch.cols, sketch.vals
    sorted_ = sketch.csort is not None
    # The flat-segment reduction lives in repro.kernels (one implementation,
    # also the TPU entry point); it is bitwise B per-problem `coo_matvec`s.
    from repro.kernels.ops import batched_coo_matvec, batched_coo_rmatvec

    if sorted_:
        cols_sorted = jnp.take_along_axis(cols, sketch.csort, axis=1)
        vals_sorted = jnp.take_along_axis(vals, sketch.csort, axis=1)

    def coo_matvec(v):  # (B, m) -> (B, n)
        return batched_coo_matvec(
            rows, vals, jnp.take_along_axis(v, cols, axis=1), n=n,
            indices_are_sorted=sorted_,
        )

    def coo_rmatvec(u):  # (B, n) -> (B, m)
        ug = jnp.take_along_axis(u, rows, axis=1)
        if not sorted_:
            return batched_coo_rmatvec(cols, vals, ug, m=m)
        return batched_coo_rmatvec(
            cols_sorted,
            vals_sorted,
            jnp.take_along_axis(ug, sketch.csort, axis=1),
            m=m,
            indices_are_sorted=True,
        )

    res = batched_scaling_loop(
        coo_matvec, coo_rmatvec, bp.a, bp.b, bp.fe, tol=tol, max_iter=max_iter,
        trace=trace,
    )
    u, v, t, err, status = res[:5]

    t_e = (
        jnp.take_along_axis(u, rows, axis=1)
        * vals
        * jnp.take_along_axis(v, cols, axis=1)
    )
    value = _batched_value_from_te(bp, t_e, c_e, rows, cols, n, m)
    cert = None
    if certify:
        eps_col = bp.eps[:, None]
        f, g = _batched_potentials(u, v, bp.eps)
        uh = jnp.where(u > 0, u, 1.0)
        vh = jnp.where(v > 0, v, 1.0)
        k_e = (
            jnp.take_along_axis(uh, rows, axis=1)
            * vals
            * jnp.take_along_axis(vh, cols, axis=1)
        )
        alive = vals > 0
        K_e = jnp.where(jnp.isinf(c_e), 0.0, jnp.exp(-c_e / eps_col))
        p_e = jnp.where(
            alive, jnp.clip(K_e / jnp.where(alive, vals, 1.0), 0.0, 1.0), 1.0
        )
        ess = jax.vmap(importance_ess)(vals)
        cert = _batched_sparse_cert(
            bp, t_e, c_e, rows, cols, f, g, k_e, p_e, ess, value, n, m
        )
    return BatchedResult(
        u, v, t, err, value, rows, cols, vals, sketch.nnz, sketch.overflowed,
        status, res[5] if trace else None, cert,
    )


def _batched_value_from_te(
    bp: BatchedProblem,
    t_e: jax.Array,
    c_e: jax.Array,
    rows: jax.Array,
    cols: jax.Array,
    n: int,
    m: int,
) -> jax.Array:
    """Per-element entropic objective from (B, cap) plan entries + gathered
    costs — the batched mirror of ``coo_objective_*_entries``, shared by
    the scaling-domain and log-domain sketch solvers."""
    logt = jnp.log(jnp.where(t_e > 0, t_e, 1.0))
    ent = jnp.sum(jnp.where(t_e > 0, -t_e * (logt - 1.0), 0.0), axis=1)
    tc = jnp.sum(
        jnp.where(t_e > 0, t_e * jnp.where(jnp.isinf(c_e), 0.0, c_e), 0.0), axis=1
    )
    v_ot = tc - bp.eps * ent
    row_m = jax.vmap(lambda x, r: jax.ops.segment_sum(x, r, num_segments=n))(t_e, rows)
    col_m = jax.vmap(lambda x, c: jax.ops.segment_sum(x, c, num_segments=m))(t_e, cols)
    kl_r = jax.vmap(kl_divergence)(row_m, bp.a)
    kl_c = jax.vmap(kl_divergence)(col_m, bp.b)
    v_uot = tc + bp.lam * (kl_r + kl_c) - bp.eps * ent
    return jnp.where(bp.is_balanced, v_ot, v_uot)


@register_batched_solver("spar_sink_coo")
def batched_solve_spar_sink(
    bp: BatchedProblem,
    sketch: BatchedSketch,
    *,
    tol: float = 1e-6,
    max_iter: int = 1000,
    trace: bool | int = False,
    certify: bool = False,
) -> BatchedResult:
    """Spar-Sink on a dense-built batched sketch; costs for the objective
    are gathered from the batched cost matrices."""
    c_e = jax.vmap(lambda C, r, c: C[r, c])(bp.cost, sketch.rows, sketch.cols)
    return _batched_sketch_solve(bp, sketch, c_e, tol, max_iter, trace, certify)


@register_batched_solver("spar_sink_mf")
def batched_solve_spar_sink_mf(
    bp: BatchedProblem,
    sketch: BatchedSketch,
    *,
    stabilize: bool = False,
    tol: float = 1e-6,
    max_iter: int = 1000,
    trace: bool | int = False,
    certify: bool = False,
) -> BatchedResult:
    """Matrix-free batched Spar-Sink: the sketch (from
    `build_batched_mf_sketch`) carries its own gathered costs, so
    ``bp.cost`` may be ``None`` (`BatchedProblem.from_problems` with
    ``materialize_cost=False``) and nothing O(n m) exists anywhere.
    ``stabilize=True`` expects a **log-space** sketch
    (`build_batched_mf_log_sketch`) and runs the log-domain iteration —
    the batched mirror of ``solve(..., method="spar_sink_mf",
    stabilize=True)``, safe at small ``eps``."""
    if sketch.cost_e is None:
        raise ValueError(
            "spar_sink_mf needs a matrix-free sketch with gathered costs; "
            "build it with build_batched_mf_sketch()"
        )
    if stabilize:
        return _batched_sketch_log_solve(bp, sketch, tol, max_iter, trace, certify)
    return _batched_sketch_solve(
        bp, sketch, sketch.cost_e, tol, max_iter, trace, certify
    )


@register_batched_solver("spar_sink_log")
def batched_solve_spar_sink_log(
    bp: BatchedProblem,
    sketch: BatchedSketch,
    *,
    tol: float = 1e-6,
    max_iter: int = 1000,
    trace: bool | int = False,
    certify: bool = False,
) -> BatchedResult:
    """Log-domain batched Spar-Sink on a log-space sketch
    (`build_batched_log_sketch`): potential updates through batched sorted
    segment-logsumexp, bitwise the per-problem ``spar_sink_log`` per
    element; small-``eps`` safe. ``bp.cost`` is never read (the sketch
    carries gathered costs), so no (B, n, m) array is materialized."""
    if sketch.cost_e is None:
        raise ValueError(
            "spar_sink_log needs a log-space sketch with gathered costs; "
            "build it with build_batched_log_sketch()"
        )
    return _batched_sketch_log_solve(bp, sketch, tol, max_iter, trace, certify)


class _SortedLogLayout(NamedTuple):
    """A sorted sketch's column order, laid out once per solve: the rows
    and log-values of ``csort``'s permutation, and where the segments of
    the row order and the column order lie (`sparsify.SortedSegments`)."""

    rows_cs: jax.Array  # (B, cap) int32
    logvals_cs: jax.Array  # (B, cap)
    row_seg: sparsify.SortedSegments
    col_seg: sparsify.SortedSegments


@functools.partial(jax.jit, static_argnames=("n", "m"))
def _sorted_log_layout(rows, cols, logvals, csort, *, n: int, m: int):
    """One program, before the loop: O(cap) gathers and O((n + m) log cap)
    searches, so no iteration permutes its summands into column order."""
    take = functools.partial(jnp.take_along_axis, indices=csort, axis=1)
    return _SortedLogLayout(
        take(rows),
        take(logvals),
        sparsify.sorted_segments(rows, n),
        sparsify.sorted_segments(take(cols), m),
    )


def sparse_log_potentials(
    rows: jax.Array,
    cols: jax.Array,
    logvals: jax.Array,
    csort: jax.Array | None,
    loga: jax.Array,
    logb: jax.Array,
    eps: jax.Array,
    fe: jax.Array,
    *,
    n: int,
    m: int,
    tol: float,
    max_iter: int,
    trace: bool | int = False,
    init: tuple[jax.Array, jax.Array] | None = None,
):
    """Log-domain potentials of B sketched problems — the ONE iteration
    kernel behind both the per-problem ``spar_sink_log`` /
    ``spar_sink_mf(stabilize=True)`` solvers (called at B = 1) and the
    batched executor path.

    Sharing the exact computation matters: the segment-logsumexp contains
    ``exp``/``log`` whose fused codegen XLA may legally vary by a ulp
    between differently-shaped programs, while this batched reduction is
    B-invariant — so per-problem and batched results agree **bitwise**
    per element. Returns ``(f, g, n_iter, err, status)``, all (B, ·);
    ``trace`` (static) appends a batched `repro.obs.SolverTrace`.

    Sketches sorted by construction (``csort`` given) are laid out in
    column order once (`_sorted_log_layout`), and each half-step is one
    segmented scan (`sparsify.sorted_segment_logsumexp`), with no scatter;
    ``csort=None`` falls back to the scatter `batched_coo_logsumexp`. Each
    call adds 1 to the counter ``spar_sink.loop_sorted_scan`` or
    ``spar_sink.loop_scatter`` of `repro.obs.default_registry`: once per
    eager call, once per trace under an outer jit.
    """
    if csort is None:
        from repro.kernels.ops import batched_coo_logsumexp

        default_registry.counter("spar_sink.loop_scatter")
        rows_c, logvals_c = rows, logvals
        reduce_row = functools.partial(batched_coo_logsumexp, rows, n=n)
        reduce_col = functools.partial(batched_coo_logsumexp, cols, n=m)
    else:
        default_registry.counter("spar_sink.loop_sorted_scan")
        lay = _sorted_log_layout(rows, cols, logvals, csort, n=n, m=m)
        rows_c, logvals_c = lay.rows_cs, lay.logvals_cs
        reduce_row = functools.partial(sparsify.sorted_segment_logsumexp, seg=lay.row_seg)
        reduce_col = functools.partial(sparsify.sorted_segment_logsumexp, seg=lay.col_seg)
    eps_col = eps[:, None]

    def lse_row(g):  # (B, m) -> (B, n)
        return reduce_row(logvals + jnp.take_along_axis(g / eps_col, cols, axis=1))

    def lse_col(f):  # (B, n) -> (B, m), summands in column order
        return reduce_col(logvals_c + jnp.take_along_axis(f / eps_col, rows_c, axis=1))

    return batched_sparse_log_loop(
        lse_row, lse_col, loga, logb, eps, fe, tol=tol, max_iter=max_iter,
        trace=trace, init=init,
    )


def _batched_sketch_log_solve(
    bp: BatchedProblem,
    sketch: BatchedSketch,
    tol: float,
    max_iter: int,
    trace: bool | int = False,
    certify: bool = False,
) -> BatchedResult:
    """Shared log-domain Spar-Sink core on a fixed-cap batched COO sketch
    whose ``vals`` carry ``logvals``: two batched **sorted**
    segment-logsumexps per iteration (`sparse_log_potentials`), O(cap)
    potential-based objective per element."""
    _, n, m = bp.shape
    rows, cols, logvals = sketch.rows, sketch.cols, sketch.vals
    res = sparse_log_potentials(
        rows,
        cols,
        logvals,
        sketch.csort,
        _masked_log(bp.a),
        _masked_log(bp.b),
        bp.eps,
        bp.fe,
        n=n,
        m=m,
        tol=tol,
        max_iter=max_iter,
        trace=trace,
    )
    f, g, t, err, status = res[:5]
    eps_col = bp.eps[:, None]
    logt = (
        logvals
        + jnp.take_along_axis(f, rows, axis=1) / eps_col
        + jnp.take_along_axis(g, cols, axis=1) / eps_col
    )
    t_e = jnp.where(jnp.isneginf(logt) | jnp.isnan(logt), 0.0, jnp.exp(logt))
    value = _batched_value_from_te(bp, t_e, sketch.cost_e, rows, cols, n, m)
    cert = None
    if certify:
        c_e = sketch.cost_e
        fh = jnp.where(jnp.isfinite(f), f, 0.0)
        gh = jnp.where(jnp.isfinite(g), g, 0.0)
        logk = (
            logvals
            + jnp.take_along_axis(fh, rows, axis=1) / eps_col
            + jnp.take_along_axis(gh, cols, axis=1) / eps_col
        )
        k_e = jnp.where(jnp.isneginf(logk), 0.0, jnp.exp(logk))
        logp = jnp.minimum(-c_e / eps_col - logvals, 0.0)
        p_e = jnp.where(jnp.isneginf(logvals), 1.0, jnp.exp(logp))
        ess = jax.vmap(lambda lv: importance_ess(lv, log_space=True))(logvals)
        cert = _batched_sparse_cert(
            bp, t_e, c_e, rows, cols, f, g, k_e, p_e, ess, value, n, m
        )
    return BatchedResult(
        f, g, t, err, value, rows, cols, logvals, sketch.nnz, sketch.overflowed,
        status, res[5] if trace else None, cert,
    )
