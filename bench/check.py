"""What decides ``correct``: the timed path's answers against the plain
reference (`bench/reference.py`).

An answer is what one solve returned: the support of its sketch, its plan
entries there, its potentials and its value. Per answer the reference

1. recomputes the log-value of one draw of every kept pair from the
   problem's points and weights, and reads the program's own log-value
   back from the plan entry and potentials, ``log t_e - (f_i + g_j)/eps``.
   A pair drawn ``k`` times carries ``log k`` more; the gap in log space,
   net of that whole ``k``, is ``entry_log_err``: it covers the gathered
   costs and the sampling rates;
2. iterates its own Sinkhorn on that support with its own log-values (and
   the ``k`` read in 1), by the configuration's stopping rule, evaluates
   eq. 6 / eq. 10, and compares the value: ``value_rel_err``, the gap over
   the reference's value or, where that is smaller, over ``eps`` times the
   lesser marginal mass (the scale of the entropy term), so that a value
   near 0 still has a scale. It covers the iteration loop, its iteration
   count and the objective;
3. where the cell asks for it, tests the draw itself, pooled over the
   answers checked: ``draw_z``, the larger of two standardized statistics.
   One is Pearson's, of the support against the reference's probability
   that each pair is drawn, over a grid of row and column groups
   (`reference.draw_chi2`): the Poisson counts, the columns, the UOT
   thinning, the budget ``s``. The other is the sum of the extra draws
   ``k - 1`` read in 1 against its mean and variance under the reference's
   rates (`reference.dup_moments`): the merge of duplicate draws, which 1
   and 2 cannot see, since they take ``k`` from the program.

Each number is compared with the cell's limit for it; the readings and
limits are documented in PERF.md.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

from bench import reference

#: a plan entry below this is not read back (float32 underflows near 1e-38)
T_FLOOR = 1e-30


@dataclass
class Answer:
    """One answer of the timed path, on the host, support cut to ``nnz``."""

    rows: np.ndarray
    cols: np.ndarray
    t: np.ndarray  # plan entries on the support
    f: np.ndarray
    g: np.ndarray
    value: float
    nnz: int

    @classmethod
    def from_solution(cls, sol) -> "Answer":
        plan = sol.plan()
        nnz = int(plan.nnz)
        f, g = sol.potentials
        return cls(np.asarray(plan.rows)[:nnz].astype(np.int64),
                   np.asarray(plan.cols)[:nnz].astype(np.int64),
                   np.asarray(plan.vals, np.float64)[:nnz],
                   np.asarray(f, np.float64), np.asarray(g, np.float64),
                   float(sol.value), nnz)


def entry_gap(m, ans: Answer, ref_lv: np.ndarray):
    """``(err, k)``: the largest gap between the program's and the
    reference's log-values net of a whole multiplicity ``k``, and ``k`` per
    entry (1 where the plan entry is too small to read back)."""
    readable = np.isfinite(ans.t) & (ans.t >= T_FLOOR)
    k = np.ones(ans.nnz)
    if not readable.any():
        return math.inf, k
    r = readable
    with np.errstate(invalid="ignore", over="ignore"):
        prog = np.log(ans.t[r]) - (ans.f[ans.rows[r]] + ans.g[ans.cols[r]]) / m.eps
        d = prog - ref_lv[r]
        k[r] = np.maximum(1.0, np.rint(np.exp(np.minimum(d, 60.0))))
    gap = np.abs(d - np.log(k[r]))
    return float(np.max(np.where(np.isfinite(gap), gap, math.inf))), k


def extra_draws(m, ans: Answer, ref_lv: np.ndarray, k: np.ndarray) -> float:
    """The extra draws ``sum (k - 1)`` over the entries whose single draw
    gives a readable plan entry (the pairs `reference.dup_moments` sums)."""
    with np.errstate(invalid="ignore"):
        single = ref_lv + (ans.f[ans.rows] + ans.g[ans.cols]) / m.eps
    return float(np.sum(np.where(single >= math.log(T_FLOOR), k - 1.0, 0.0)))


def check_answer(m, ans: Answer, s: float, *, tol: float, max_iter: int) -> dict:
    """``entry_log_err``, ``value_rel_err`` and the extra draws of one
    answer. An answer whose support or potentials do not fit the problem is
    wrong outright."""
    n = m.n
    if (ans.f.shape != (n,) or ans.g.shape != (n,) or ans.rows.size != ans.nnz
            or (ans.nnz and (min(ans.rows.min(), ans.cols.min()) < 0
                             or max(ans.rows.max(), ans.cols.max()) >= n))):
        return {"entry_log_err": math.inf, "value_rel_err": math.inf, "ref_value": math.nan,
                "extra_draws": math.inf}
    ref_lv = reference.single_logvals(m, s, ans.rows, ans.cols)
    err, k = entry_gap(m, ans, ref_lv)
    lv = ref_lv + np.log(k)
    pad = padded(ans.nnz)
    rows, cols, lvp = (np.zeros(pad, np.int64), np.zeros(pad, np.int64),
                       np.full(pad, -np.inf))
    rows[:ans.nnz], cols[:ans.nnz], lvp[:ans.nnz] = ans.rows, ans.cols, lv
    f, g, _ = reference.solve_support(m, rows, cols, lvp, tol=tol, max_iter=max_iter)
    c_e = reference.entry_cost(m, ans.rows, ans.cols)
    ref_value = reference.objective(m, ans.rows, ans.cols, lv, f, g, c_e)
    floor = m.eps * float(min(m.a.sum(), m.b.sum()))
    rel = abs(ans.value - ref_value) / max(abs(ref_value), floor)
    return {"entry_log_err": err, "value_rel_err": rel if math.isfinite(rel) else math.inf,
            "ref_value": ref_value, "extra_draws": extra_draws(m, ans, ref_lv, k)}


def padded(nnz: int) -> int:
    """A fixed support length per problem size, so one compiled reference
    serves every answer of that size: the next power of two."""
    return max(1024, 1 << max(0, nnz - 1).bit_length())


def draw_z(chi, dup) -> tuple[float, float]:
    """``(grid_z, dup_z)``: the standardized Pearson statistic over the
    pooled cells of ``chi = [(chi2, cells), ...]``, ``(sum chi2 - K) /
    sqrt(2 K)``, and the pooled extra draws of ``dup = [(observed, mean,
    var), ...]``, ``sum (observed - mean) / sqrt(sum var)``."""
    chi2 = sum(c for c, _ in chi)
    cells = sum(k for _, k in chi)
    grid = (chi2 - cells) / math.sqrt(2.0 * max(cells, 1))
    off = sum(o - mu for o, mu, _ in dup)
    var = sum(v for _, _, v in dup)
    if not math.isfinite(off):
        return grid, math.inf
    return grid, off / math.sqrt(var) if var > 0 else (0.0 if off == 0 else math.inf)


def compare(readings: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}`` for every limited number, in the
    cell's order; a number that was not read counts as infinite."""
    out = {}
    for name, limit in limits.items():
        v = readings.get(name, math.inf)
        out[name] = {"value": v if math.isfinite(v) else 1e308, "limit": limit}
    return out


def passed(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())


def check_all(samples, s: float, *, tol: float, max_iter: int, draw: bool) -> dict:
    """Worst reading of every number over ``samples = [(Measures, Answer)]``;
    with ``draw`` also ``draw_z`` and its two parts, ``draw_grid_z`` and
    ``draw_dup_z``."""
    worst = {"entry_log_err": 0.0, "value_rel_err": 0.0}
    chi, dup = [], []
    for m, ans in samples:
        r = check_answer(m, ans, s, tol=tol, max_iter=max_iter)
        for name in worst:
            worst[name] = max(worst[name], r[name])
        if draw and not math.isfinite(r["extra_draws"]):  # the answer does not fit
            chi.append((math.inf, 1))
            dup.append((math.inf, 0.0, 1.0))
        elif draw:
            chi.append(reference.draw_chi2(m, s, ans.rows, ans.cols))
            dup.append((r["extra_draws"],
                        *reference.dup_moments(m, s, ans.f, ans.g, math.log(T_FLOOR))))
    if draw:
        grid, dz = draw_z(chi, dup)
        worst.update(draw_z=max(grid, abs(dz)), draw_grid_z=grid, draw_dup_z=dz)
    return worst
