"""What a run offers the system is drawn from a cell's parameters and
``--seed`` by the helpers here and by the problem family that the cell's
configuration names (``bench/families/<family>.py``; ``pointcloud_c1``
where it names none).

So a new traffic mix is a new data file under ``bench/workloads/``, and a
new kind of problem (its data and its ground cost) a new family file; never
an edit of code the benchmark has. Every seed gets the same amount of work:
problems of the same sizes and kinds, with data and sketch keys of its own.
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
    marginals), weights ``a`` and ``b`` (zero at points of no mass), its
    parameters, and the family that drew it with its cost's parameters."""

    x: np.ndarray
    a: np.ndarray
    b: np.ndarray
    eps: float
    lam: float | None  # None: balanced OT
    cost_kw: tuple = ()  # the family's cost parameters, as (name, value) pairs
    family: object = None  # the module of bench/families/<family>.py

    @property
    def n(self) -> int:
        return self.x.shape[0]

    def cost(self, xp, u, v):
        """The family's ground cost of points ``u`` against ``v``, computed
        by the array module ``xp`` (``numpy`` or ``jax.numpy``) in their
        dtype; ``+inf`` for a blocked pair."""
        return self.family.cost(xp, u, v, **dict(self.cost_kw))

    def geometry(self) -> dict:
        """The program's `PointCloudGeometry` arguments besides the points."""
        return self.family.geometry(**dict(self.cost_kw))
