"""Importance sparsification of the Gibbs kernel (paper Section 3).

Four faithful-to-eq.(7) representations of the sketch ``K~``:

* ``sparsify_dense``      — dense array with zeros (exact reference; O(n^2) compute)
* ``sparsify_coo``        — padded COO + segment-sum mat-vecs (O(s) compute; the
                            paper's algorithm verbatim, with static shapes for jit)
* ``sparsify_coo_mf``     — **matrix-free** COO: the Poissonized draw of eq. (7)
                            for rank-1 probabilities, O(n + s log n) with entry
                            values gathered from support points — no (n, m)
                            array anywhere
* ``sparsify_block_ell``  — **TPU adaptation**: Poisson sampling at 128x128 *tile*
                            granularity, stored in block-ELL layout so the
                            Spar-Sink iteration is dense MXU work (see DESIGN §3)

The first three Bernoulli paths draw inclusion decisions from the same uniform
variates, so given the same PRNG key the COO sketch equals the dense sketch
exactly (tested). COO sketches come out sorted by row (with a col-sorted
permutation ``csort``), so both segment-sum mat-vecs run with
``indices_are_sorted=True``, and they flag capacity ``overflowed`` instead of
truncating silently.

Sampling probabilities:

* OT  (eq. 9):  p_ij ∝ sqrt(a_i b_j)                       — factorizes, O(n)
* UOT (eq. 11): p_ij ∝ (a_i b_j)^{λ/(2λ+ε)} K_ij^{ε/(2λ+ε)} — computed in log space
* uniform                                                    — Rand-Sink baseline

Small ``eps`` (the paper sweeps down to 1e-3) underflows every *value*
above: ``K = exp(-C/eps)`` flushes to exact zeros, so a scaling-domain
sketch degenerates before the solver runs. The **log-space sketches**
(`LogSparseKernelCOO` via `sparsify_coo_log` / `sparsify_coo_mf_log`)
carry ``logvals = -C_e/eps - log p*_e`` instead — built from gathered raw
costs, never exponentiating — and iterate through segment-logsumexp
(`coo_lse_row` / `coo_lse_col`), which is what ``spar_sink_log`` and
``spar_sink_mf(stabilize=True)`` run on.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "BlockEllKernel",
    "FactorDraw",
    "LogSparseKernelCOO",
    "SparseKernelCOO",
    "block_ell_matvec",
    "block_ell_rmatvec",
    "block_ell_to_dense",
    "coo_lse_col",
    "coo_lse_row",
    "coo_matvec",
    "coo_rmatvec",
    "factor_draw",
    "ot_sampling_prob_factors",
    "ot_sampling_probs",
    "ot_tile_probs",
    "poisson_keep_probs",
    "segment_logsumexp",
    "sparsify_block_ell",
    "sparsify_coo",
    "sparsify_coo_log",
    "sparsify_coo_mf",
    "sparsify_coo_mf_log",
    "sparsify_dense",
    "tile_probs_from_elem",
    "uniform_prob_factors",
    "uniform_probs",
    "uot_sampling_logprobs",
    "uot_sampling_probs",
]


# --------------------------------------------------------------------------
# Sampling probabilities
# --------------------------------------------------------------------------


def ot_sampling_prob_factors(a: jax.Array, b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Row/col factors ``(ra, rb)`` with ``p_ij = ra_i * rb_j`` (eq. 9)."""
    sa = jnp.sqrt(a)
    sb = jnp.sqrt(b)
    return sa / jnp.sum(sa), sb / jnp.sum(sb)


def ot_sampling_probs(a: jax.Array, b: jax.Array) -> jax.Array:
    ra, rb = ot_sampling_prob_factors(a, b)
    return ra[:, None] * rb[None, :]


def uot_sampling_probs(
    a: jax.Array, b: jax.Array, logK: jax.Array, lam: float, eps: float
) -> jax.Array:
    """Eq. (11), evaluated in log space. ``logK = -C/eps`` (``-inf`` = blocked).

    Degenerates to eq. (9) as ``lam -> inf`` (the K exponent vanishes).
    """
    c_ab = lam / (2.0 * lam + eps)
    c_k = eps / (2.0 * lam + eps)
    loga = jnp.where(a > 0, jnp.log(jnp.where(a > 0, a, 1.0)), -jnp.inf)
    logb = jnp.where(b > 0, jnp.log(jnp.where(b > 0, b, 1.0)), -jnp.inf)
    logp = c_ab * (loga[:, None] + logb[None, :]) + c_k * logK
    logz = jax.scipy.special.logsumexp(jnp.where(jnp.isneginf(logp), -jnp.inf, logp))
    p = jnp.exp(logp - logz)
    return jnp.where(jnp.isneginf(logp), 0.0, p)


def uot_sampling_logprobs(
    a: jax.Array, b: jax.Array, cost: jax.Array, lam: float, eps: float
) -> jax.Array:
    """Eq. (11) as *normalized log-probabilities*, entirely in log space.

    Works from the raw cost (``+inf`` = blocked): the kernel factor
    ``K_ij^{eps/(2lam+eps)} = exp(-C_ij/(2lam+eps))`` is kept as the single
    exponent ``-C/(2lam+eps)`` instead of being exponentiated and
    re-powered, so small ``eps`` (or small ``lam``) never flushes a
    probability to an exact zero before the solver even samples. Consumed
    by the log-domain sketch builders; `uot_sampling_probs` is its
    ``exp``."""
    from repro.core.sinkhorn import _masked_log

    c_ab = lam / (2.0 * lam + eps)
    logk_part = jnp.where(jnp.isinf(cost), -jnp.inf, -cost / (2.0 * lam + eps))
    logp = c_ab * (_masked_log(a)[:, None] + _masked_log(b)[None, :]) + logk_part
    return logp - jax.scipy.special.logsumexp(logp)


def uniform_probs(n: int, m: int, dtype=jnp.float32) -> jax.Array:
    """Rand-Sink: every element equally likely."""
    return jnp.full((n, m), 1.0 / (n * m), dtype=dtype)


def uniform_prob_factors(n: int, m: int, dtype=jnp.float32) -> tuple[jax.Array, jax.Array]:
    """Rand-Sink probabilities as O(n)+O(m) row/col factors: every
    probability-consuming path broadcasts ``fr_i * fc_j`` on the fly, so
    the uniform baseline never materializes an (n, m) probability array."""
    return (
        jnp.full((n,), 1.0 / n, dtype=dtype),
        jnp.full((m,), 1.0 / m, dtype=dtype),
    )


def poisson_keep_probs(probs, s: float) -> jax.Array:
    """``p*_ij = min(1, s p_ij)`` — inclusion probabilities of eq. (7).

    ``probs`` is either an (n, m) array or an ``(fr, fc)`` factor pair
    (``p_ij = fr_i * fc_j``, e.g. `uniform_prob_factors`), broadcast here
    instead of being materialized by the caller."""
    if isinstance(probs, tuple):
        fr, fc = probs
        return jnp.minimum(1.0, s * (fr[:, None] * fc[None, :]))
    return jnp.minimum(1.0, s * probs)


# --------------------------------------------------------------------------
# Dense reference sketch (exact eq. 7)
# --------------------------------------------------------------------------


def _keep_mask(key: jax.Array, p_star: jax.Array) -> jax.Array:
    return jax.random.uniform(key, p_star.shape, dtype=p_star.dtype) < p_star


def sparsify_dense(key: jax.Array, K: jax.Array, probs: jax.Array, s: float) -> jax.Array:
    """Dense ``K~``: ``K_ij / p*_ij`` w.p. ``p*_ij``, else 0."""
    p_star = poisson_keep_probs(probs, s)
    keep = _keep_mask(key, p_star)
    return jnp.where(keep, K / jnp.maximum(p_star, 1e-300), 0.0)


# --------------------------------------------------------------------------
# Padded-COO sketch (O(s) compute path; static shapes)
# --------------------------------------------------------------------------


class SparseKernelCOO(NamedTuple):
    """Padded COO sketch, **sorted by row** at construction; padded slots
    carry ``vals == 0`` and sort to the end (row ``n-1``)."""

    rows: jax.Array  # (cap,) int32, ascending; padding parks at n-1
    cols: jax.Array  # (cap,) int32
    vals: jax.Array  # (cap,)       padded with 0.0
    nnz: jax.Array  # () int32 realized count (truncated to cap on overflow)
    n: int
    m: int
    # col-sorted permutation: cols[csort] is ascending, so K~^T u runs a
    # sorted segment-sum too. None only on hand-built sketches (then the
    # mat-vecs fall back to the unsorted scatter).
    csort: jax.Array | None = None  # (cap,) int32
    overflowed: jax.Array | None = None  # () bool — realized nnz exceeded cap
    # draw accounting for `repro.obs.sketch_diagnostics` (None on hand-built
    # sketches): proposals drawn by the sampler (Bernoulli keeps / Poisson
    # total, *before* capacity truncation) and entries alive after
    # evaluation+thinning but *before* duplicate merge
    n_proposed: jax.Array | None = None  # () int32
    n_accepted: jax.Array | None = None  # () int32

    @property
    def cap(self) -> int:
        return self.rows.shape[0]


def sparsify_coo(
    key: jax.Array, K: jax.Array, probs, s: float, cap: int
) -> SparseKernelCOO:
    """Padded COO sketch. ``cap`` is a static capacity (>= realized nnz w.h.p.;
    E[nnz] <= s, so ``cap ~ s + 5 sqrt(s)`` is comfortable). If the draw
    exceeds ``cap`` anyway, the trailing entries (row-major order) are
    dropped and ``overflowed`` is set. ``probs`` may be an (n, m) array or
    an ``(fr, fc)`` factor pair (see `poisson_keep_probs`)."""
    n, m = K.shape
    p_star = poisson_keep_probs(probs, s)
    keep = _keep_mask(key, p_star)
    true_nnz = jnp.sum(keep).astype(jnp.int32)
    # fill with the last flat index: padding parks at (n-1, m-1), keeping
    # the row ids ascending for the sorted segment-sum in coo_matvec
    flat_idx = jnp.nonzero(keep.ravel(), size=cap, fill_value=n * m - 1)[0]
    valid = jnp.arange(cap) < true_nnz
    vals_dense = jnp.where(keep, K / jnp.maximum(p_star, 1e-300), 0.0).ravel()
    vals = jnp.where(valid, vals_dense[flat_idx], 0.0)
    rows = (flat_idx // m).astype(jnp.int32)
    cols = (flat_idx % m).astype(jnp.int32)
    return SparseKernelCOO(
        rows,
        cols,
        vals,
        jnp.minimum(true_nnz, cap),
        n,
        m,
        csort=jnp.argsort(cols).astype(jnp.int32),
        overflowed=true_nnz > cap,
        n_proposed=true_nnz,
        n_accepted=jnp.minimum(true_nnz, cap),
    )


class LogSparseKernelCOO(NamedTuple):
    """Log-space padded COO sketch: `SparseKernelCOO`'s layout, but carrying
    ``logvals = -C_e/eps - log p*_e`` (= ``log(K_e/p*_e)``) so the sketch
    stays finite when ``exp(-C/eps)`` underflows (eps down to 1e-3 and
    below). Padded slots carry ``logvals == -inf`` and park at row n-1."""

    rows: jax.Array  # (cap,) int32, ascending; padding parks at n-1
    cols: jax.Array  # (cap,) int32
    logvals: jax.Array  # (cap,)   padded with -inf
    nnz: jax.Array  # () int32 realized count (truncated to cap on overflow)
    n: int
    m: int
    csort: jax.Array | None = None  # (cap,) int32 col-sorted permutation
    overflowed: jax.Array | None = None  # () bool — realized nnz exceeded cap
    # draw accounting for `repro.obs.sketch_diagnostics`; see SparseKernelCOO
    n_proposed: jax.Array | None = None  # () int32
    n_accepted: jax.Array | None = None  # () int32

    @property
    def cap(self) -> int:
        return self.rows.shape[0]


def sparsify_coo_log(
    key: jax.Array,
    cost: jax.Array,
    probs,
    eps: float,
    s: float,
    cap: int,
    *,
    logprobs: jax.Array | None = None,
) -> tuple[LogSparseKernelCOO, jax.Array]:
    """Log-space padded COO sketch built from the raw *cost* matrix.

    Same eq. (7) draw as `sparsify_coo` — with linear ``probs`` the keep
    mask is drawn from the same uniform variates, so the sampled support is
    bitwise the `sparsify_coo` support for the same PRNG key — but entry
    values are stored as ``logvals = -C_e/eps - log p*_e`` without ever
    materializing ``exp(-C/eps)``. With ``logprobs`` (normalized log-space
    probabilities, e.g. `uot_sampling_logprobs`) the keep probabilities
    ``log p* = min(0, log s + log p)`` and the inclusion draw
    ``log U < log p*`` also stay in log space, so a sharply-concentrated
    eq. (11) distribution cannot flush its support to zero first.

    Returns ``(sketch, C_e)`` — gathered raw costs, index-aligned with the
    sketch (``+inf`` on padded slots), for potential-based objectives.
    """
    n, m = cost.shape
    if logprobs is None:
        p_star = poisson_keep_probs(probs, s)
        keep = _keep_mask(key, p_star)
        log_pstar = jnp.log(jnp.maximum(p_star, 1e-300))
    else:
        log_pstar = jnp.minimum(0.0, jnp.log(s) + logprobs)
        u = jax.random.uniform(key, log_pstar.shape, dtype=log_pstar.dtype)
        keep = jnp.log(u) < log_pstar
    true_nnz = jnp.sum(keep).astype(jnp.int32)
    # same padding convention as sparsify_coo: park at the last flat index
    flat_idx = jnp.nonzero(keep.ravel(), size=cap, fill_value=n * m - 1)[0]
    valid = jnp.arange(cap) < true_nnz
    c_e = jnp.where(valid, cost.ravel()[flat_idx], jnp.inf)
    logvals = jnp.where(valid, -c_e / eps - log_pstar.ravel()[flat_idx], -jnp.inf)
    rows = (flat_idx // m).astype(jnp.int32)
    cols = (flat_idx % m).astype(jnp.int32)
    sk = LogSparseKernelCOO(
        rows,
        cols,
        logvals,
        jnp.minimum(true_nnz, cap),
        n,
        m,
        csort=jnp.argsort(cols).astype(jnp.int32),
        overflowed=true_nnz > cap,
        n_proposed=true_nnz,
        n_accepted=jnp.minimum(true_nnz, cap),
    )
    return sk, c_e


class FactorDraw(NamedTuple):
    """One Poissonized factorized draw (`factor_draw`)."""

    counts: jax.Array  # (n,) per-row totals
    rows: jax.Array  # (cap,) int32, sorted
    cols: jax.Array  # (cap,) int32
    u: jax.Array  # (cap,) the uniforms ``cols`` were inverted from
    k_acc: jax.Array  # key left for UOT acceptance thinning


def factor_draw(key: jax.Array, ra: jax.Array, rb: jax.Array, s: float, cap: int) -> FactorDraw:
    """The Poissonized factorized draw of the matrix-free sketches.

    Per-row totals ``counts ~ Poisson(s ra)``; slot ``i`` takes its row
    from their cumulative sum and its column by inverse CDF on ``rb``.
    Slots at or past ``sum(counts)`` are padding and park at row ``n - 1``.
    """
    n, m = ra.shape[0], rb.shape[0]
    k_counts, k_cols, k_acc = jax.random.split(key, 3)
    counts = jax.random.poisson(k_counts, s * ra)
    rows = jnp.searchsorted(jnp.cumsum(counts), jnp.arange(cap), side="right")
    rows = jnp.minimum(rows, n - 1).astype(jnp.int32)
    u = jax.random.uniform(k_cols, (cap,), dtype=rb.dtype)
    cols = jnp.searchsorted(jnp.cumsum(rb), u, side="right")
    cols = jnp.minimum(cols, m - 1).astype(jnp.int32)
    return FactorDraw(counts, rows, cols, u, k_acc)


def sparsify_coo_mf(
    key: jax.Array,
    ra: jax.Array,
    rb: jax.Array,
    s: float,
    cap: int,
    entries_fn,
    *,
    thin_scale: float | None = None,
) -> tuple[SparseKernelCOO, jax.Array]:
    """Matrix-free COO sketch from rank-1 probabilities in O(n + cap log n).

    The Poissonized form of eq. (7) for factorized ``p_ij = ra_i rb_j``
    (eq. 9): entry multiplicities ``N_ij ~ Poisson(s ra_i rb_j)`` are drawn
    by splitting — per-row totals ``N_i ~ Poisson(s ra_i)`` (the factorized
    row marginals), then each draw's column by inverse-CDF on ``rb`` — and
    every drawn copy contributes ``K_ij / (s ra_i rb_j)``, so
    ``E[K~_ij] = K_ij`` exactly, entry-wise, like the Bernoulli sketch.
    No (n, m) array is ever touched: kernel/cost values come from
    ``entries_fn(rows, cols) -> (K_e, C_e)`` (gathered evaluation).

    With ``thin_scale = 1/(2 lam + eps)`` the draw covers eq. (11): the
    rank-1 ``(a_i b_j)^{lam/(2lam+eps)}`` part is the proposal (pass its
    normalized factors as ``ra``/``rb``) and each proposal is thinned by
    the on-the-fly acceptance ``K_ij^{eps/(2lam+eps)} = exp(-C_ij *
    thin_scale)``; accepted copies are reweighted by the *known* rate
    ``s ra_i rb_j acc_ij``, so the sketch stays exactly unbiased without
    ever computing eq. (11)'s O(n^2) normalizer. ``s`` is then the
    proposal budget (expected kept count is ``s * E_q[acc] <= s``).

    Returns ``(sketch, C_e)`` — the gathered raw costs ride along so the
    sparse objective never re-gathers (``C_e`` stays index-aligned with the
    sketch arrays). Rows come out sorted; duplicate draws (multiplicity
    >= 2) are merged into one entry carrying the summed weight, and all
    zero slots are compacted to the tail so the first ``nnz`` entries are
    exactly the realized sketch.
    """
    n, m = ra.shape[0], rb.shape[0]
    counts, rows, cols, _, k_acc = factor_draw(key, ra, rb, s, cap)
    total = jnp.sum(counts).astype(jnp.int32)
    valid = jnp.arange(cap) < jnp.minimum(total, cap)
    k_e, c_e = entries_fn(rows, cols)
    rate = s * ra[rows] * rb[cols]  # E[multiplicity] per drawn entry
    # floor for rate: a literal 1e-300 is 0 in float32
    tiny = jnp.finfo(rate.dtype).tiny
    if thin_scale is not None:
        # acceptance K^{eps/(2lam+eps)} entirely in log space: the test
        # log U < -C thin_scale cannot flush to an always-False `U < 0`
        # when exp(-C thin_scale) underflows, and the accepted weight
        # K/(rate*acc) is one exponential of the summed logs instead of a
        # division by a product that underflows long before K does
        log_acc = -c_e * thin_scale  # blocked (C = +inf) -> -inf, rejected
        u_acc = jax.random.uniform(k_acc, (cap,), dtype=rb.dtype)
        valid = valid & (jnp.log(u_acc) < log_acc)
        alive = valid & (k_e > 0)
        logw = (
            jnp.log(jnp.where(alive, k_e, 1.0))
            - jnp.log(jnp.maximum(rate, tiny))
            - log_acc
        )
        vals = jnp.where(alive, jnp.exp(logw), 0.0)
    else:
        vals = jnp.where(valid, k_e / jnp.maximum(rate, tiny), 0.0)
    n_accepted = jnp.sum(vals != 0).astype(jnp.int32)  # pre-merge alive count
    # Merge duplicate draws (multiplicity >= 2 of one pair) so the sparse
    # objective's entry-wise entropy sees the summed plan mass, then compact
    # every zero slot (rejected proposals, blocked pairs, overflow, merged
    # copies) to the tail: "entries beyond nnz are padding" stays true.
    order = jnp.lexsort((cols, rows))  # rows primary: stays row-sorted
    rows, cols, vals, c_e = rows[order], cols[order], vals[order], c_e[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1])]
    )
    grp = jnp.cumsum(first) - 1
    merged = jax.ops.segment_sum(vals, grp, num_segments=cap, indices_are_sorted=True)
    vals = jnp.where(first, merged[grp], 0.0)
    compact = jnp.argsort(vals == 0)  # stable: nonzero first, row order kept
    rows, cols, vals, c_e = (
        rows[compact], cols[compact], vals[compact], c_e[compact]
    )
    nz = vals != 0
    sk = SparseKernelCOO(
        jnp.where(nz, rows, n - 1).astype(jnp.int32),
        jnp.where(nz, cols, m - 1).astype(jnp.int32),
        vals,
        jnp.sum(nz).astype(jnp.int32),
        n,
        m,
        csort=jnp.argsort(jnp.where(nz, cols, m - 1)).astype(jnp.int32),
        overflowed=total > cap,
        n_proposed=total,
        n_accepted=n_accepted,
    )
    return sk, c_e


def sparsify_coo_mf_log(
    key: jax.Array,
    ra: jax.Array,
    rb: jax.Array,
    s: float,
    cap: int,
    cost_entries_fn,
    eps: float,
    *,
    thin_scale: float | None = None,
) -> tuple[LogSparseKernelCOO, jax.Array]:
    """Matrix-free **log-space** COO sketch: `sparsify_coo_mf`'s Poissonized
    factorized draw, carrying ``logvals = -C_e/eps - log rate_e`` built from
    gathered raw costs only (``cost_entries_fn(rows, cols) -> C_e``) — the
    Gibbs kernel is never exponentiated, so the sketch survives ``eps``
    where ``exp(-C/eps)`` flushes to zero.

    UOT (``thin_scale = 1/(2 lam + eps)``): the eq. (11) acceptance
    thinning runs in log space too (``log U < -C_e thin_scale``; rate
    ``+= log acc``), so neither the sampled support nor the reweighting
    collapses at small ``eps``/``lam``. Duplicate draws are merged by
    segment-**logsumexp** instead of segment-sum. Returns ``(sketch, C_e)``
    with the gathered costs index-aligned to the sketch arrays.
    """
    n, m = ra.shape[0], rb.shape[0]
    counts, rows, cols, _, k_acc = factor_draw(key, ra, rb, s, cap)
    total = jnp.sum(counts).astype(jnp.int32)
    valid = jnp.arange(cap) < jnp.minimum(total, cap)
    c_e = cost_entries_fn(rows, cols)
    tiny = jnp.finfo(rb.dtype).tiny  # a literal 1e-300 is 0 in float32
    lograte = (
        jnp.log(jnp.asarray(s, rb.dtype))
        + jnp.log(jnp.maximum(ra[rows], tiny))
        + jnp.log(jnp.maximum(rb[cols], tiny))
    )
    if thin_scale is not None:
        log_acc = -c_e * thin_scale  # blocked (C = +inf) -> -inf, rejected
        valid = valid & (
            jnp.log(jax.random.uniform(k_acc, (cap,), dtype=rb.dtype)) < log_acc
        )
        lograte = lograte + log_acc
    logvals = jnp.where(valid, -c_e / eps - lograte, -jnp.inf)
    n_accepted = jnp.sum(~jnp.isneginf(logvals)).astype(jnp.int32)  # pre-merge
    # Merge duplicate draws by logsumexp of their weights, then compact all
    # dead slots (rejected proposals, blocked pairs, overflow, merged
    # copies) to the tail — same invariants as sparsify_coo_mf with
    # "vals == 0" replaced by "logvals == -inf".
    order = jnp.lexsort((cols, rows))  # rows primary: stays row-sorted
    rows, cols, logvals, c_e = rows[order], cols[order], logvals[order], c_e[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1])]
    )
    grp = jnp.cumsum(first) - 1
    merged = segment_logsumexp(logvals, grp, num_segments=cap, indices_are_sorted=True)
    logvals = jnp.where(first, merged[grp], -jnp.inf)
    compact = jnp.argsort(jnp.isneginf(logvals))  # stable: alive first
    rows, cols, logvals, c_e = (
        rows[compact], cols[compact], logvals[compact], c_e[compact]
    )
    nz = ~jnp.isneginf(logvals)
    sk = LogSparseKernelCOO(
        jnp.where(nz, rows, n - 1).astype(jnp.int32),
        jnp.where(nz, cols, m - 1).astype(jnp.int32),
        logvals,
        jnp.sum(nz).astype(jnp.int32),
        n,
        m,
        csort=jnp.argsort(jnp.where(nz, cols, m - 1)).astype(jnp.int32),
        overflowed=total > cap,
        n_proposed=total,
        n_accepted=n_accepted,
    )
    return sk, c_e


def coo_matvec(sk: SparseKernelCOO, v: jax.Array) -> jax.Array:
    """``K~ v`` in O(cap); sorted scatter on construction-sorted sketches."""
    return jax.ops.segment_sum(
        sk.vals * v[sk.cols],
        sk.rows,
        num_segments=sk.n,
        indices_are_sorted=sk.csort is not None,
    )


def coo_rmatvec(sk: SparseKernelCOO, u: jax.Array) -> jax.Array:
    """``K~^T u`` in O(cap); runs the col-sorted permutation when available."""
    data = sk.vals * u[sk.rows]
    if sk.csort is None:
        return jax.ops.segment_sum(data, sk.cols, num_segments=sk.m)
    return jax.ops.segment_sum(
        data[sk.csort],
        sk.cols[sk.csort],
        num_segments=sk.m,
        indices_are_sorted=True,
    )


def segment_logsumexp(
    z: jax.Array,
    seg: jax.Array,
    num_segments: int,
    indices_are_sorted: bool = False,
) -> jax.Array:
    """Per-segment ``logsumexp`` via segment-max + segment-sum.

    ``-inf`` entries are inert (their ``exp`` shift is masked to 0, so no
    ``-inf - -inf = nan``), and empty / all-dead segments come out exactly
    ``-inf`` — the log-domain mirror of `coo_matvec`'s zero rows. This is
    the one implementation behind both the per-problem `coo_lse_row` /
    `coo_lse_col` and the batched flat reduction in ``repro.kernels.ops``
    (disjoint per-element segments), keeping batched results bitwise equal
    to per-problem ones.
    """
    mx = jax.ops.segment_max(
        z, seg, num_segments=num_segments, indices_are_sorted=indices_are_sorted
    )
    e = jnp.where(jnp.isneginf(z), 0.0, jnp.exp(z - mx[seg]))
    tot = jax.ops.segment_sum(
        e, seg, num_segments=num_segments, indices_are_sorted=indices_are_sorted
    )
    return jnp.where(jnp.isneginf(mx), -jnp.inf, mx + jnp.log(tot))


def coo_lse_row(sk: LogSparseKernelCOO, y: jax.Array) -> jax.Array:
    """``logsumexp_j(logvals_e + y[cols_e])`` per row in O(cap) — the
    log-domain `coo_matvec` (callers pass ``y = g/eps``)."""
    return segment_logsumexp(
        sk.logvals + y[sk.cols],
        sk.rows,
        num_segments=sk.n,
        indices_are_sorted=sk.csort is not None,
    )


def coo_lse_col(sk: LogSparseKernelCOO, y: jax.Array) -> jax.Array:
    """``logsumexp_i(logvals_e + y[rows_e])`` per column in O(cap) — the
    log-domain `coo_rmatvec`; runs the col-sorted permutation when available."""
    z = sk.logvals + y[sk.rows]
    if sk.csort is None:
        return segment_logsumexp(z, sk.cols, num_segments=sk.m)
    return segment_logsumexp(
        z[sk.csort],
        sk.cols[sk.csort],
        num_segments=sk.m,
        indices_are_sorted=True,
    )


# --------------------------------------------------------------------------
# Block-ELL sketch (TPU path; tile-granular Poisson sampling)
# --------------------------------------------------------------------------


class BlockEllKernel(NamedTuple):
    vals: jax.Array  # (nrb, max_blocks, Bk, Bk) rescaled kernel tiles (0-padded)
    col_idx: jax.Array  # (nrb, max_blocks) int32 column-block ids (0-padded)
    nblocks: jax.Array  # (nrb,) int32 valid blocks per row-block
    n: int
    m: int

    @property
    def block(self) -> int:
        return self.vals.shape[-1]

    @property
    def max_blocks(self) -> int:
        return self.vals.shape[1]


def ot_tile_probs(a: jax.Array, b: jax.Array, bk: int) -> jax.Array:
    """Tile-aggregated eq.(9) probabilities — exact, because eq.(9) factorizes:

        p_T = (sum_{i in rowblk} ra_i) * (sum_{j in colblk} rb_j)

    Computable in O(n) without touching K.
    """
    ra, rb = ot_sampling_prob_factors(a, b)
    ta = jnp.sum(ra.reshape(-1, bk), axis=1)
    tb = jnp.sum(rb.reshape(-1, bk), axis=1)
    return ta[:, None] * tb[None, :]


def tile_probs_from_elem(probs: jax.Array, bk: int) -> jax.Array:
    """Tile aggregation of arbitrary element probabilities (UOT eq. 11 path)."""
    n, m = probs.shape
    return probs.reshape(n // bk, bk, m // bk, bk).sum(axis=(1, 3))


def _tile_keep_probs(tile_probs: jax.Array, s: float, bk: int, ensure: bool):
    """``p*_T = min(1, (s/Bk^2) p_T)``; with ``ensure``, the heaviest tile of
    every row-block and column-block gets ``p*_T = 1`` (deterministic
    inclusion, rescale 1/1) — still exactly unbiased, and the sketch never
    has an empty row/column block (Sinkhorn would oscillate otherwise)."""
    s_tiles = s / float(bk * bk)
    p_star = jnp.minimum(1.0, s_tiles * tile_probs)
    if ensure:
        nrb, ncb = tile_probs.shape
        # rows: force each row-block's heaviest tile.
        row_top = jnp.argmax(tile_probs, axis=1)
        p_star = p_star.at[jnp.arange(nrb), row_top].set(1.0)
        # columns: eq.(9) tile probs are rank-1, so the per-column argmax is
        # one single row — forcing it would overload that row's ELL slots.
        # Spread instead: match the k-th heaviest column with the k-th
        # heaviest row (cyclically), one forced tile per (row, col) pair.
        row_mass = jnp.sum(tile_probs, axis=1)
        col_mass = jnp.sum(tile_probs, axis=0)
        row_order = jnp.argsort(-row_mass)
        col_order = jnp.argsort(-col_mass)
        r_for_c = row_order[jnp.arange(ncb) % nrb]
        p_star = p_star.at[r_for_c, col_order].set(1.0)
    return p_star


def sparsify_block_ell(
    key: jax.Array,
    K: jax.Array,
    tile_probs: jax.Array,
    s: float,
    bk: int,
    max_blocks: int,
    ensure_rows: bool = True,
) -> BlockEllKernel:
    """Poisson-sample tiles with ``p*_T = min(1, (s/Bk^2) p_T)`` and rescale by
    ``1/p*_T`` — the tile-granular analogue of eq. (7); unbiased for the same
    reason (every kept tile is divided by its own inclusion probability).

    ``s`` is the element budget; ``s/Bk^2`` is the tile budget.
    """
    n, m = K.shape
    nrb, ncb = n // bk, m // bk
    p_star = _tile_keep_probs(tile_probs, s, bk, ensure_rows)
    keep = jax.random.uniform(key, p_star.shape, dtype=p_star.dtype) < p_star

    nblocks = jnp.sum(keep, axis=1).astype(jnp.int32)
    # Per-row-block compaction (static width); if a row overflows max_blocks,
    # the *least important* tiles are dropped (importance-ordered).
    score = jnp.where(keep, tile_probs, -1.0)
    order = jnp.argsort(-score, axis=1, stable=True)
    col_idx = order[:, :max_blocks].astype(jnp.int32)
    valid = jnp.arange(max_blocks)[None, :] < jnp.minimum(nblocks, max_blocks)[:, None]
    col_idx = jnp.where(valid, col_idx, 0)

    Ktiles = K.reshape(nrb, bk, ncb, bk).transpose(0, 2, 1, 3)  # (nrb, ncb, Bk, Bk)
    scale = 1.0 / jnp.maximum(p_star, 1e-300)
    gathered = jnp.take_along_axis(Ktiles, col_idx[:, :, None, None], axis=1)
    gscale = jnp.take_along_axis(scale, col_idx, axis=1)
    vals = jnp.where(valid[:, :, None, None], gathered * gscale[:, :, None, None], 0.0)
    return BlockEllKernel(vals, col_idx, jnp.minimum(nblocks, max_blocks), n, m)


def sparsify_block_ell_pair(
    key: jax.Array,
    K: jax.Array,
    tile_probs: jax.Array,
    s: float,
    bk: int,
    max_blocks: int,
    ensure_rows: bool = True,
) -> tuple[BlockEllKernel, BlockEllKernel]:
    """Sample once, return the sketch in BOTH row-major and transposed
    (column-major) block-ELL layouts. ``K~^T u`` then runs the *same* gather
    mat-vec kernel on the transposed layout — TPUs prefer a second laid-out
    copy over random scatter (see DESIGN §3)."""
    n, m = K.shape
    nrb, ncb = n // bk, m // bk
    p_star = _tile_keep_probs(tile_probs, s, bk, ensure_rows)
    keep = jax.random.uniform(key, p_star.shape, dtype=p_star.dtype) < p_star
    scale = 1.0 / jnp.maximum(p_star, 1e-300)
    Ktiles = K.reshape(nrb, bk, ncb, bk).transpose(0, 2, 1, 3)

    def ell_from_mask(mask, probs, tiles, sc):
        nb = jnp.sum(mask, axis=1).astype(jnp.int32)
        score = jnp.where(mask, probs, -1.0)
        order = jnp.argsort(-score, axis=1, stable=True)
        ci = order[:, :max_blocks].astype(jnp.int32)
        valid = jnp.arange(max_blocks)[None, :] < jnp.minimum(nb, max_blocks)[:, None]
        ci = jnp.where(valid, ci, 0)
        g = jnp.take_along_axis(tiles, ci[:, :, None, None], axis=1)
        gs = jnp.take_along_axis(sc, ci, axis=1)
        vals = jnp.where(valid[:, :, None, None], g * gs[:, :, None, None], 0.0)
        return vals, ci, jnp.minimum(nb, max_blocks)

    vals, ci, nb = ell_from_mask(keep, tile_probs, Ktiles, scale)
    valsT, ciT, nbT = ell_from_mask(
        keep.T, tile_probs.T, Ktiles.transpose(1, 0, 3, 2), scale.T
    )
    return (
        BlockEllKernel(vals, ci, nb, n, m),
        BlockEllKernel(valsT, ciT, nbT, m, n),
    )


def block_ell_matvec(sk: BlockEllKernel, v: jax.Array) -> jax.Array:
    """``K~ v``: gather v-blocks by column id, dense (Bk x Bk) @ (Bk,) per tile."""
    bk = sk.block
    vblocks = v.reshape(sk.m // bk, bk)
    gathered = vblocks[sk.col_idx]  # (nrb, max_blocks, Bk)
    out = jnp.einsum("rkij,rkj->ri", sk.vals, gathered)
    return out.reshape(sk.n)


def block_ell_rmatvec(sk: BlockEllKernel, u: jax.Array) -> jax.Array:
    """``K~^T u``: per-tile (Bk,) @ (Bk x Bk), scatter-added into column blocks."""
    bk = sk.block
    ublocks = u.reshape(sk.n // bk, bk)
    contrib = jnp.einsum("rkij,ri->rkj", sk.vals, ublocks)  # (nrb, max_blocks, Bk)
    ncb = sk.m // bk
    out = jax.ops.segment_sum(
        contrib.reshape(-1, bk), sk.col_idx.reshape(-1), num_segments=ncb
    )
    return out.reshape(sk.m)


def block_ell_to_dense(sk: BlockEllKernel) -> jax.Array:
    """Densify (tests / small problems only)."""
    bk = sk.block
    nrb, ncb = sk.n // bk, sk.m // bk
    dense_tiles = jnp.zeros((nrb, ncb, bk, bk), sk.vals.dtype)
    r = jnp.arange(nrb)[:, None].repeat(sk.max_blocks, 1)
    valid = jnp.arange(sk.max_blocks)[None, :] < sk.nblocks[:, None]
    # scatter-add so padded (0) column ids with zero vals are harmless
    dense_tiles = dense_tiles.at[r.ravel(), sk.col_idx.ravel()].add(
        jnp.where(valid[..., None, None], sk.vals, 0.0).reshape(-1, bk, bk)
    )
    return dense_tiles.transpose(0, 2, 1, 3).reshape(sk.n, sk.m)
