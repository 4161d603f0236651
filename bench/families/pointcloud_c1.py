"""Problem family ``pointcloud_c1``: pattern C1 of arXiv:2306.06581 Sec. 5.1
under the squared Euclidean cost.

A copy of the program's own distribution (`repro.data.make_measures`
pattern C1), kept here so that no change to the program can change the
yardstick. The configurations that name no ``family`` use this one.
"""
from __future__ import annotations

import numpy as np

from bench.generator import Measures, rng

#: sizes at which a CPU test holds a cell: a run, and a run with enough
#: duplicate draws for a lost merge to show (their count grows about as
#: log^8 n, so at the run size it reads near noise)
TINY = {"n": 512}
TINY_MERGE = {"n": 2048}


def cost(xp, u, v):
    """Squared Euclidean cost of the points ``u`` against ``v`` (broadcast
    over their leading axes), from coordinate differences; ``xp`` is
    ``numpy`` or ``jax.numpy``."""
    d = u - v
    return xp.sum(d * d, axis=-1)


def geometry() -> dict:
    """The program's `PointCloudGeometry` arguments besides the points: its
    default, the squared Euclidean cost."""
    return {}


def _gauss_hist(n: int, loc: float, scale: float) -> np.ndarray:
    """The paper's C1 histogram over the index grid (a Gaussian shape, so
    weights span orders of magnitude)."""
    t = (np.arange(n) + 0.5) / n
    w = np.exp(-0.5 * ((t - loc) / scale) ** 2) + 1e-12
    return w / w.sum()


def _c1(n: int, d: int, r: np.random.Generator, *, eps: float, lam: float | None,
        mass_a: float, mass_b: float) -> Measures:
    """x ~ U(0,1)^d, a and b the N(1/3, 1/20) and N(1/2, 1/20) shapes;
    scaled to the UOT masses."""
    x = r.uniform(0.0, 1.0, size=(n, d))
    a = _gauss_hist(n, 1.0 / 3.0, 1.0 / 20.0)
    b = _gauss_hist(n, 1.0 / 2.0, 1.0 / 20.0)
    if lam is not None:
        a, b = mass_a * a, mass_b * b
    return Measures(x, a, b, eps, lam)


def pool(params: dict, seed: int) -> list[Measures]:
    """``pool`` problems of one configuration, each with points of its own
    drawn from ``seed``."""
    return [
        _c1(params["n"], params["d"], rng(seed, 1, i), eps=params["eps"],
            lam=params.get("lam"), mass_a=params.get("mass_a", 1.0),
            mass_b=params.get("mass_b", 1.0))
        for i in range(params["pool"])
    ]
