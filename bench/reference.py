"""Plain reference of the configurations: entropic OT and UOT on an
importance sketch, written from the paper (arXiv:2306.06581, eqs. 6, 7, 9,
10, 11) in straightforward ``jax.numpy``. It imports nothing of the
program. The ground cost is the problem's family's
(``bench/families/<family>.py``, `bench.generator.Measures.cost`); a cost
of ``+inf`` blocks a pair: its rate is 0 and it is never kept.

* `row_factors`, `single_logvals`, `draw_chi2`, `dup_moments`: what the
  matrix-free Poissonized sketch of ``spar_sink_mf`` should be. Pair ij is
  drawn ``k_ij ~ Poisson(r_ij)`` times, ``r_ij = s p_ij`` (OT, eq. 9) or the
  UOT proposal's rate thinned by ``exp(-C_ij/(2 lam + eps))`` (eq. 11), and
  a pair drawn ``k`` times weighs ``k`` single draws. Support points of
  zero mass have rate 0 in their row or column.
* `solve_support`: log-domain Sinkhorn (OT) or its UOT form on a given
  support, dense and masked, so it runs fast on a TPU; `objective`: eq. 6 /
  eq. 10 on that support.
* `draw`: the reference sampler. Only the control uses it: the reference
  put in the program's place at a lower precision.

The precision is the ``dtype`` argument; float32 is the configuration's
own, bfloat16 the control's.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -np.inf


# --------------------------------------------------------------------------
# cost and sampling rates
# --------------------------------------------------------------------------


def block_cost(family, cost_kw: tuple, xr: jax.Array, xc: jax.Array) -> jax.Array:
    """The family's cost of row points ``xr`` (k, d) against column points
    ``xc`` (l, d) as a (k, l) block, in their dtype."""
    return family.cost(jnp, xr[:, None, :], xc[None, :, :], **dict(cost_kw))


def entry_cost(m, rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
    """The cost of the pairs (rows, cols) of problem ``m``, float64, host."""
    return m.cost(np, m.x[rows], m.x[cols])


def _lograte(lrb, lc, thin, c):
    """``log r`` of a block with cost ``c``: ``-inf`` where the pair is
    blocked (``c = +inf``) or a marginal is zero, with no ``inf - inf``."""
    blocked = jnp.isinf(c)
    return jnp.where(blocked, -jnp.inf,
                     lrb[:, None] + lc[None, :] - thin * jnp.where(blocked, 0.0, c))


def _log(v: np.ndarray) -> np.ndarray:
    with np.errstate(divide="ignore"):
        return np.log(v)


def row_factors(m, s: float):
    """The sampling rate, ``log r_ij = lr_i + lc_j - thin C_ij``, float64:
    ``(lr, lc, thin)``.

    OT, eq. (9): ``r_ij = s sqrt(a_i b_j) / (sum sqrt a)(sum sqrt b)``.
    UOT: proposal ``s (a_i b_j)^c / (sum a^c)(sum b^c)`` with ``c = lam/(2
    lam + eps)``, thinned by ``exp(-C/(2 lam + eps))``.
    """
    if m.lam is None:
        ra, rb = np.sqrt(m.a), np.sqrt(m.b)
        return math.log(s) + _log(ra / ra.sum()), _log(rb / rb.sum()), 0.0
    c = m.lam / (2.0 * m.lam + m.eps)
    qa, qb = m.a ** c, m.b ** c
    return math.log(s) + _log(qa / qa.sum()), _log(qb / qb.sum()), 1.0 / (2.0 * m.lam + m.eps)


def _block(n: int) -> int:
    b = max(1, min(n, (1 << 24) // n))
    while n % b:
        b -= 1
    return b


def _f32(v):
    return jnp.asarray(v, jnp.float32)


@functools.partial(jax.jit, static_argnames=("block", "groups", "family", "cost_kw"))
def _blocked_sums(x, lr, lc, thin, rg, cg, *, block, groups, family, cost_kw):
    """Per-(row group, column group) sums of the probability ``P = 1 -
    exp(-r)`` that a pair is drawn at least once and of ``P (1 - P)``, plus
    the row and column sums of P, over the dense (n, n) pair grid in row
    blocks of ``block``."""
    n = x.shape[0]
    nb = n // block

    def one(i, acc):
        cell, var, rsum, csum = acc
        r0 = i * block
        xr = jax.lax.dynamic_slice_in_dim(x, r0, block)
        lrb = jax.lax.dynamic_slice_in_dim(lr, r0, block)
        p = -jnp.expm1(-jnp.exp(_lograte(lrb, lc, thin, block_cost(family, cost_kw, xr, x))))
        rgb = jax.lax.dynamic_slice_in_dim(rg, r0, block)
        ohr = jax.nn.one_hot(rgb, groups, dtype=p.dtype)
        ohc = jax.nn.one_hot(cg, groups, dtype=p.dtype)
        hp = jax.lax.Precision.HIGHEST
        cell = cell + jnp.dot(ohr.T, jnp.dot(p, ohc, precision=hp), precision=hp)
        var = var + jnp.dot(ohr.T, jnp.dot(p * (1.0 - p), ohc, precision=hp), precision=hp)
        rsum = jax.lax.dynamic_update_slice_in_dim(rsum, jnp.sum(p, axis=1), r0, 0)
        return cell, var, rsum, csum + jnp.sum(p, axis=0)

    z = jnp.zeros((groups, groups), x.dtype)
    init = (z, z, jnp.zeros((n,), x.dtype), jnp.zeros((n,), x.dtype))
    return jax.lax.fori_loop(0, nb, one, init)


def draw_chi2(m, s: float, rows, cols, max_groups=16):
    """Pearson's statistic of a drawn support against the reference's
    probabilities that each pair is drawn, over a grid of row groups by
    column groups of about equal expected count: ``(chi2, cells)``.

    ``rows``/``cols`` are the distinct pairs that the draw kept. The
    expected count of a cell is the sum of its pairs' ``P = 1 - exp(-r)``,
    its variance the sum of ``P (1 - P)``.
    """
    n = m.n
    lr, lc, thin = row_factors(m, s)
    args = (_f32(m.x), _f32(lr), _f32(lc), _f32(thin))
    static = dict(block=_block(n), family=m.family, cost_kw=m.cost_kw)
    zeros = jnp.zeros((n,), jnp.int32)
    _, _, rsum, csum = _blocked_sums(*args, zeros, zeros, groups=1, **static)
    total = float(jnp.sum(rsum))
    groups = int(max(1, min(max_groups, math.isqrt(int(total / 50.0)))))
    rg = _groups(np.asarray(rsum, np.float64), groups)
    cg = _groups(np.asarray(csum, np.float64), groups)
    groups = int(max(rg.max(), cg.max())) + 1
    cell, var, _, _ = _blocked_sums(*args, jnp.asarray(rg), jnp.asarray(cg), groups=groups,
                                    **static)
    obs = np.zeros((groups, groups))
    np.add.at(obs, (rg[rows], cg[cols]), 1.0)
    e, v = np.asarray(cell, np.float64), np.asarray(var, np.float64)
    keep = v > 0
    chi2 = float(np.sum((obs[keep] - e[keep]) ** 2 / v[keep]))
    stray = float(np.sum(obs[~keep]))  # draws where the reference allows none
    return chi2 + 1e6 * stray, int(keep.sum())


def _groups(expect: np.ndarray, groups: int) -> np.ndarray:
    """Contiguous index groups of about equal total ``expect``, at most
    ``groups``, numbered from 0 with none empty. An index of zero
    ``expect`` joins the group of the nearest index before it with a
    positive one (after it, at the start), so no group's total is zero."""
    c = np.cumsum(expect)
    g = np.minimum((c - 0.5 * expect) * groups // max(c[-1], 1e-300), groups - 1)
    live = expect > 0
    if live.any():
        prev = np.maximum.accumulate(np.where(live, np.arange(expect.size), -1))
        g = g[np.where(prev >= 0, prev, np.argmax(live))]
    return np.unique(g, return_inverse=True)[1].astype(np.int32)


def dup_pair_moments(r):
    """Mean and variance of the extra draws ``D = max(k - 1, 0)`` of a pair
    drawn ``k ~ Poisson(r)`` times. ``E D = r - P`` and ``Var D = r (2P - 1)
    + P (1 - P)`` with ``P = 1 - exp(-r)``; below ``r = 1e-2`` their series,
    ``r^2/2 - r^3/6 + r^4/24`` and ``r^2/2 + r^3/6 - 7 r^4/24``, which the
    differences lose to rounding."""
    p = -jnp.expm1(-r)
    small = r < 1e-2
    mean = jnp.where(small, r * r * (0.5 - r / 6.0 + r * r / 24.0), r - p)
    var = jnp.where(small, r * r * (0.5 + r / 6.0 - 7.0 * r * r / 24.0),
                    r * (2.0 * p - 1.0) + p * (1.0 - p))
    return mean, var


@functools.partial(jax.jit, static_argnames=("block", "family", "cost_kw"))
def _dup_sums(x, lr, lc, thin, inv_eps, fe, ge, logfloor, *, block, family, cost_kw):
    n = x.shape[0]

    def one(i, acc):
        r0 = i * block
        xr = jax.lax.dynamic_slice_in_dim(x, r0, block)
        c = block_cost(family, cost_kw, xr, x)
        logr = _lograte(jax.lax.dynamic_slice_in_dim(lr, r0, block), lc, thin, c)
        live = jnp.isfinite(logr)  # a pair of rate 0 is never drawn
        single = -jnp.where(live, c, 0.0) * inv_eps - jnp.where(live, logr, 0.0)
        feb = jax.lax.dynamic_slice_in_dim(fe, r0, block)
        readable = live & (single + feb[:, None] + ge[None, :] >= logfloor)
        mean, var = dup_pair_moments(jnp.exp(logr))
        return (acc[0] + jnp.sum(jnp.where(readable, mean, 0.0)),
                acc[1] + jnp.sum(jnp.where(readable, var, 0.0)))

    zero = jnp.zeros((), x.dtype)
    return jax.lax.fori_loop(0, n // block, one, (zero, zero))


def dup_moments(m, s: float, f: np.ndarray, g: np.ndarray, logfloor: float):
    """``(mean, var)`` of the extra draws summed over the pairs whose single
    draw gives a readable plan entry, ``-C/eps - log r + (f_i + g_j)/eps >=
    logfloor`` under the answer's potentials ``f``, ``g``."""
    lr, lc, thin = row_factors(m, s)
    with np.errstate(invalid="ignore"):
        fe, ge = f / m.eps, g / m.eps
    mean, var = _dup_sums(_f32(m.x), _f32(lr), _f32(lc), _f32(thin), _f32(1.0 / m.eps),
                          _f32(fe), _f32(ge), _f32(logfloor), block=_block(m.n),
                          family=m.family, cost_kw=m.cost_kw)
    return float(mean), float(var)


def single_logvals(m, s: float, rows, cols):
    """Reference log-value of one draw of each pair, ``-C/eps - log r``,
    float64, host; ``-inf`` (no weight) for a pair of rate 0, which no sound
    draw keeps."""
    lr, lc, thin = row_factors(m, s)
    c = entry_cost(m, rows, cols)
    out = np.full(c.shape, NEG_INF)
    live = np.isfinite(c) & np.isfinite(lr[rows]) & np.isfinite(lc[cols])
    c, rows, cols = c[live], rows[live], cols[live]
    out[live] = -c / m.eps - (lr[rows] + lc[cols] - thin * c)
    return out


# --------------------------------------------------------------------------
# Sinkhorn on a given support
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n", "max_iter"))
def _support_sinkhorn(rows, cols, logvals, loga, logb, eps, fe, tol, *, n, max_iter):
    """Log-domain Sinkhorn on the masked dense kernel ``L`` (``-inf`` off
    the support): ``f = fe eps (log a - lse_j(L + g/eps))`` and the same
    for g; dead atoms are pinned to ``-inf``. Stops when
    ``max|df| + max|dg| <= tol`` or after ``max_iter`` iterations."""
    dt = logvals.dtype
    L = jnp.full((n, n), -jnp.inf, dt).at[rows, cols].max(logvals)  # pads are -inf
    scale = (fe * eps).astype(dt)

    def half(L, pot, logw, axis):
        lse = jax.nn.logsumexp(L + (pot / eps).astype(dt), axis=axis)
        return jnp.where(jnp.isneginf(logw) | jnp.isneginf(lse), -jnp.inf, scale * (logw - lse))

    def delta(new, old):
        both = jnp.isneginf(new) & jnp.isneginf(old)
        return jnp.max(jnp.where(both, 0.0, jnp.abs(new - old)))

    def body(st):
        f, g, it, _ = st
        f2 = half(L, g[None, :], loga, 1)
        g2 = half(L, f2[:, None], logb, 0)
        return f2, g2, it + 1, delta(f2, f) + delta(g2, g)

    def cond(st):
        return (st[2] < max_iter) & (st[3] > tol)

    f0 = jnp.where(jnp.isneginf(loga), -jnp.inf, 0.0).astype(dt)
    g0 = jnp.where(jnp.isneginf(logb), -jnp.inf, 0.0).astype(dt)
    f, g, it, err = jax.lax.while_loop(cond, body, (f0, g0, jnp.int32(0), jnp.asarray(jnp.inf, dt)))
    return f, g, it, err


def objective(m, rows, cols, logvals, f, g, c_e) -> float:
    """Eq. 6 (OT) or eq. 10 (UOT) on the support, float64, host:
    ``<T, C> - eps H(T)`` with ``t_e = exp(logval_e + (f_i + g_j)/eps)``,
    plus ``lam (KL(T1 | a) + KL(T'1 | b))`` for UOT."""
    with np.errstate(invalid="ignore", over="ignore"):
        logt = logvals + (f[rows] + g[cols]) / m.eps
    live = np.isfinite(logt)
    logt = np.where(live, logt, NEG_INF)
    t = np.exp(logt)
    tl = np.where(live, t * (logt - 1.0), 0.0)
    value = float(np.sum(t * np.where(live, c_e, 0.0)) + m.eps * np.sum(tl))
    if m.lam is None:
        return value
    row = np.bincount(rows, weights=t, minlength=m.n)
    col = np.bincount(cols, weights=t, minlength=m.n)
    return value + m.lam * (_kl(row, m.a) + _kl(col, m.b))


def _kl(x: np.ndarray, y: np.ndarray) -> float:
    """``KL(x | y) = sum x log(x / y) - x + y``; ``+inf`` where ``x > 0 = y``."""
    if np.any((x > 0) & (y <= 0)):
        return math.inf
    with np.errstate(divide="ignore", invalid="ignore"):
        xl = np.where(x > 0, x * (_log(np.where(x > 0, x, 1.0)) - _log(np.where(y > 0, y, 1.0))), 0.0)
    return float(np.sum(xl - x + y))


def solve_support(m, rows, cols, logvals, *, tol: float, max_iter: int, dtype=jnp.float32):
    """The reference's solution on the support ``(rows, cols)`` with entry
    log-values ``logvals``, by the configuration's stopping rule (``tol``,
    ``max_iter``): ``(f, g, n_iter)`` as float64 host arrays. ``dtype`` is
    the precision the iteration runs in."""
    fe = 1.0 if m.lam is None else m.lam / (m.lam + m.eps)
    with np.errstate(divide="ignore"):
        loga, logb = np.log(m.a), np.log(m.b)
    f, g, it, _ = _support_sinkhorn(
        jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32),
        jnp.asarray(logvals, dtype), jnp.asarray(loga, dtype), jnp.asarray(logb, dtype),
        jnp.asarray(m.eps, dtype), jnp.asarray(fe, dtype), jnp.asarray(tol, dtype),
        n=m.n, max_iter=max_iter)
    return np.asarray(f, np.float64), np.asarray(g, np.float64), int(it)


# --------------------------------------------------------------------------
# the reference sampler (the control only)
# --------------------------------------------------------------------------


def draw(key, m, s: float, dtype):
    """Draw a support by the reference's own sampler, computing every rate,
    CDF and entry value in ``dtype``: per-row Poisson totals, columns by
    inverse CDF, then the UOT thinning; ``(rows, cols, logvals)``, host
    arrays, distinct pairs, logvals float64 of the ``dtype`` values."""
    lr, lc, thin = row_factors(m, s)
    x = jnp.asarray(m.x, dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    ra = jnp.exp(jnp.asarray(lr - math.log(s), dtype))
    rb = jnp.exp(jnp.asarray(lc, dtype))
    counts = np.asarray(jax.random.poisson(k1, (jnp.asarray(s, dtype) * ra).astype(jnp.float32)))
    rows = np.repeat(np.arange(m.n), counts)
    u = jax.random.uniform(k2, (rows.size,), jnp.float32).astype(dtype)
    cdf = jnp.cumsum(rb.astype(dtype))
    cols = np.minimum(np.asarray(jnp.searchsorted(cdf, u, side="right")), m.n - 1)
    c = m.cost(jnp, x[rows], x[cols])
    blocked = np.asarray(jnp.isinf(c))
    c = jnp.where(blocked, 0.0, c)  # a blocked draw is never kept
    lograte = (jnp.log(jnp.asarray(s, dtype)) + jnp.log(ra)[rows] + jnp.log(rb)[cols]
               - jnp.asarray(thin, dtype) * c)
    keep = ~blocked & np.asarray(jnp.isfinite(lograte))
    if thin:
        keep &= np.asarray(jnp.log(jax.random.uniform(k3, (rows.size,), jnp.float32)).astype(dtype)
                           < -jnp.asarray(thin, dtype) * c)
    lv = np.asarray(-c / jnp.asarray(m.eps, dtype) - lograte, np.float64)[keep]
    rows, cols = rows[keep], cols[keep]
    pair = rows.astype(np.int64) * m.n + cols
    uniq, first, mult = np.unique(pair, return_index=True, return_counts=True)
    return (uniq // m.n).astype(np.int64), (uniq % m.n).astype(np.int64), lv[first] + np.log(mult)
