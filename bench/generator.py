"""The one traffic generator: problems and arrival times from a cell's
parameters and ``--seed``.

Everything a run offers the system is drawn here, so that a new traffic mix
is a new data file under ``bench/workloads/`` and never new code. Every seed
gets the same amount of work: problems of the same sizes and kinds, with
data and sketch keys of its own.

The point-cloud distribution is a copy of the program's own
(`repro.data.make_measures` pattern C1), kept here so that no change to the
program can change the yardstick.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np


def s0(n: int) -> float:
    """The paper's pilot sketch size ``s0(n) = 1e-3 n log^4 n`` (Sec. 5.1)."""
    return 1e-3 * n * math.log(n) ** 4


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of ``seed`` (any whole number)."""
    return np.random.default_rng([seed % 2**64, *stream])


def key_seed(seed: int, *stream: int) -> int:
    """A 31-bit integer for ``jax.random.PRNGKey``, drawn from ``seed``."""
    return int(rng(seed, 7, *stream).integers(2**31 - 1))


@dataclass(frozen=True)
class Measures:
    """One problem's data, float64 on the host: points ``x`` (shared by both
    marginals), weights ``a`` and ``b``, and its parameters."""

    x: np.ndarray
    a: np.ndarray
    b: np.ndarray
    eps: float
    lam: float | None  # None: balanced OT

    @property
    def n(self) -> int:
        return self.x.shape[0]


def _gauss_hist(n: int, loc: float, scale: float) -> np.ndarray:
    """The paper's C1 histogram over the index grid (a Gaussian shape, so
    weights span orders of magnitude)."""
    t = (np.arange(n) + 0.5) / n
    w = np.exp(-0.5 * ((t - loc) / scale) ** 2) + 1e-12
    return w / w.sum()


def pointcloud_c1(n: int, d: int, r: np.random.Generator, *, eps: float,
                  lam: float | None, mass_a: float, mass_b: float) -> Measures:
    """Pattern C1 (arXiv:2306.06581 Sec. 5.1): x ~ U(0,1)^d, a and b the
    N(1/3, 1/20) and N(1/2, 1/20) shapes; scaled to the UOT masses."""
    x = r.uniform(0.0, 1.0, size=(n, d))
    a = _gauss_hist(n, 1.0 / 3.0, 1.0 / 20.0)
    b = _gauss_hist(n, 1.0 / 2.0, 1.0 / 20.0)
    if lam is not None:
        a, b = mass_a * a, mass_b * b
    return Measures(x, a, b, eps, lam)


def solve_pool(params: dict, seed: int) -> list[Measures]:
    """The point-cloud cells' pool: ``pool`` problems of one configuration,
    each with points of its own drawn from ``seed``. Every seed gets the
    same amount of work: the cells fix the solver's iteration count
    (``max_iter``), so what differs from seed to seed is the data, not how
    long a solve runs."""
    return [
        pointcloud_c1(params["n"], params["d"], rng(seed, 1, i), eps=params["eps"],
                      lam=params.get("lam"), mass_a=params.get("mass_a", 1.0),
                      mass_b=params.get("mass_b", 1.0))
        for i in range(params["pool"])
    ]
