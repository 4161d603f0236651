"""Ground costs, Gibbs kernels and support-point utilities.

Everything here is pure ``jnp`` and jit-safe. Cost matrices are the *inputs*
of the paper's algorithms; the Gibbs kernel is ``K = exp(-C / eps)``.

The Wasserstein-Fisher-Rao (WFR) cost of the paper (Section 2.2) is

    C_ij = -log( cos_+^2( d_ij / (2 eta) ) ),   cos_+(z) = cos(min(z, pi/2))

so that ``d_ij >= pi * eta  =>  C_ij = +inf  =>  K_ij = 0`` — transport is
blocked beyond range ``pi * eta`` and the kernel is *sparse and nearly
full-rank* (the regime where Nyström-style low-rank methods fail and
importance sparsification shines).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

__all__ = [
    "squared_euclidean_cost",
    "euclidean_cost",
    "wfr_cost",
    "wfr_log_kernel",
    "gathered_cost",
    "gibbs_kernel",
    "wfr_from_dist",
    "log_gibbs_kernel",
    "grid_support_2d",
    "normalize_cost",
]


def _pairwise_sqdist(x: jax.Array, y: jax.Array) -> jax.Array:
    """``(n,d),(m,d) -> (n,m)`` squared euclidean distances, numerically safe."""
    x2 = jnp.sum(x * x, axis=-1)[:, None]
    y2 = jnp.sum(y * y, axis=-1)[None, :]
    xy = x @ y.T
    return jnp.maximum(x2 + y2 - 2.0 * xy, 0.0)


def squared_euclidean_cost(x: jax.Array, y: jax.Array | None = None) -> jax.Array:
    """``C_ij = ||x_i - y_j||_2^2`` (paper Section 5.1)."""
    y = x if y is None else y
    return _pairwise_sqdist(x, y)


def euclidean_cost(x: jax.Array, y: jax.Array | None = None) -> jax.Array:
    y = x if y is None else y
    return jnp.sqrt(_pairwise_sqdist(x, y) + 1e-30)


def wfr_from_dist(
    d: jax.Array, eta: float, cos_floor: float = 1e-300
) -> tuple[jax.Array, jax.Array]:
    """Distances -> (WFR cost ``-2 log cos_+(d/2eta)``, blocked mask).

    The single implementation of the paper's Sec. 2.2 formula, shared by
    `wfr_cost`, `gathered_cost`, and the Pallas kernels' cost switch
    (which pass ``cos_floor=1e-30``, the f32-safe clamp)."""
    z = d / (2.0 * eta)
    blocked = z >= (math.pi / 2.0)
    cosz = jnp.cos(jnp.minimum(z, math.pi / 2.0))
    # -log(cos^2) = -2 log cos ; callers put +inf on the blocked set.
    return -2.0 * jnp.log(jnp.maximum(cosz, cos_floor)), blocked


def wfr_cost(
    x: jax.Array,
    y: jax.Array | None = None,
    *,
    eta: float = 1.0,
    d: jax.Array | None = None,
) -> jax.Array:
    """WFR ground cost. Blocked entries (``d >= pi*eta``) come out ``+inf``.

    ``d`` may be passed directly (precomputed distances), otherwise euclidean
    distances between ``x`` and ``y`` are used.
    """
    if d is None:
        d = euclidean_cost(x, y)
    c, blocked = wfr_from_dist(d, eta)
    return jnp.where(blocked, jnp.inf, c)


def wfr_log_kernel(
    x: jax.Array,
    y: jax.Array | None = None,
    *,
    eta: float = 1.0,
    eps: float = 1.0,
    d: jax.Array | None = None,
) -> jax.Array:
    """``log K`` for the WFR cost: ``(2/eps) * log cos_+(d/2eta)`` with -inf blocks."""
    if d is None:
        d = euclidean_cost(x, y)
    z = d / (2.0 * eta)
    blocked = z >= (math.pi / 2.0)
    cosz = jnp.cos(jnp.minimum(z, math.pi / 2.0))
    logk = (2.0 / eps) * jnp.log(jnp.maximum(cosz, 1e-300))
    return jnp.where(blocked, -jnp.inf, logk)


def gather_points(pts: jax.Array, idx: jax.Array) -> list[jax.Array]:
    """``pts[idx]`` as d arrays of shape (k,), one 1-D gather per
    coordinate: a single (k, d) gather pads d to 128 lanes on the TPU
    (~5 GB at k = 1e7)."""
    return [pts[:, j][idx] for j in range(pts.shape[1])]


def gathered_cost(
    x: jax.Array,
    y: jax.Array,
    rows: jax.Array,
    cols: jax.Array,
    *,
    cost: str = "sqeuclidean",
    eta: float = 1.0,
) -> jax.Array:
    """Entry-wise ground cost ``C[rows, cols]`` straight from support points.

    The matrix-free evaluation of the paper's costs: O(k d) compute and
    memory for k index pairs, never touching an (n, m) array. Blocked WFR
    entries (``d >= pi * eta``) come out ``+inf``, exactly as `wfr_cost`.
    """
    xx = yy = xy = 0.0
    for xj, yj in zip(gather_points(x, rows), gather_points(y, cols)):
        xx, yy, xy = xx + xj * xj, yy + yj * yj, xy + xj * yj
    sq = jnp.maximum(xx + yy - 2.0 * xy, 0.0)
    if cost == "sqeuclidean":
        return sq
    if cost == "euclidean":
        return jnp.sqrt(sq + 1e-30)
    if cost == "wfr":
        c, blocked = wfr_from_dist(jnp.sqrt(sq + 1e-30), eta)
        return jnp.where(blocked, jnp.inf, c)
    raise ValueError(f"unknown cost {cost!r}")


def gibbs_kernel(cost: jax.Array, eps: float) -> jax.Array:
    """``K = exp(-C/eps)``; ``C = +inf`` rows map to exactly 0."""
    return jnp.where(jnp.isinf(cost), 0.0, jnp.exp(-cost / eps))


def log_gibbs_kernel(cost: jax.Array, eps: float) -> jax.Array:
    """``log K = -C/eps`` with ``-inf`` for blocked entries (jit-safe)."""
    return jnp.where(jnp.isinf(cost), -jnp.inf, -cost / eps)


def normalize_cost(cost: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Scale a (finite part of a) cost matrix to [0, 1]; returns (C', scale).

    The paper assumes bounded costs ``C_ij <= c_0``; in practice (e.g. POT)
    ``eps`` is interpreted relative to the cost scale. Dividing by the max
    makes ``eps`` grids comparable across data patterns C1-C3.
    """
    finite = jnp.where(jnp.isinf(cost), 0.0, cost)
    scale = jnp.maximum(jnp.max(finite), 1e-30)
    return cost / scale, scale


def grid_support_2d(h: int, w: int, dtype=jnp.float32) -> jax.Array:
    """Pixel-grid support points in [0,1]^2, row-major — used by image OT."""
    ys = (jnp.arange(h, dtype=dtype) + 0.5) / h
    xs = (jnp.arange(w, dtype=dtype) + 0.5) / w
    yy, xx = jnp.meshgrid(ys, xs, indexing="ij")
    return jnp.stack([yy.ravel(), xx.ravel()], axis=-1)


@partial(jax.jit, static_argnames=("eps",))
def kernel_from_points(x: jax.Array, y: jax.Array, eps: float) -> jax.Array:
    """Convenience: squared-euclidean Gibbs kernel straight from supports."""
    return gibbs_kernel(squared_euclidean_cost(x, y), eps)
