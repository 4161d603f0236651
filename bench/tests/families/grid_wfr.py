"""A problem family for the tests: WFR unbalanced transport between two
frames of a small synthetic echocardiogram (arXiv:2306.06581 Sec. 6).

The support is the pixel grid, ``side`` x ``side`` points in the unit
square; each marginal is one frame's intensities, a bright ring round a
darker chamber whose radius follows the cardiac phase, with noise clipped
at 0, so that many pixels have no mass. The cost is the paper's WFR cost
(Sec. 2.2), ``-2 log cos(d / 2 eta)``, blocked (``+inf``) from ``d >= pi
eta``. Problem ``i`` of a pool is the end-systolic frame against frame
``i + 1`` of its cycle, each frame with noise of its own from the seed.

The tests copy this file into a checkout as ``bench/families/grid_wfr.py``,
to show that a family is added as a new file.
"""
from __future__ import annotations

import math

import numpy as np

from bench.generator import Measures, rng

TINY = {"side": 24}
TINY_MERGE = {"side": 64}

#: frames a cycle; the ring's mean radius, swing and width; the chamber
CYCLE, RADIUS, SWING, WALL, CHAMBER, NOISE = 20, 0.22, 0.08, 0.03, 0.15, 0.03


def cost(xp, u, v, *, eta):
    """WFR cost of the points ``u`` against ``v`` (broadcast over their
    leading axes) with range ``eta``; ``+inf`` where blocked."""
    d = u - v
    z = xp.sqrt(xp.sum(d * d, axis=-1)) / (2.0 * eta)
    blocked = z >= math.pi / 2.0
    c = -2.0 * xp.log(xp.cos(xp.where(blocked, 0.0, z)))
    return xp.where(blocked, xp.inf, c)


def geometry(*, eta) -> dict:
    return {"cost": "wfr", "eta": eta}


def _frame(side: int, phase: float, r: np.random.Generator) -> np.ndarray:
    yy, xx = np.mgrid[0:side, 0:side]
    rr = np.hypot(yy - side / 2.0, xx - side / 2.0) / side
    radius = RADIUS + SWING * math.cos(phase)
    img = np.exp(-((rr - radius) ** 2) / (2.0 * WALL**2)) + CHAMBER * (rr < radius - 0.05)
    return np.clip(img + NOISE * r.standard_normal(rr.shape), 0.0, 1.0).ravel()


def pool(params: dict, seed: int) -> list[Measures]:
    side = params["side"]
    ys, xs = np.mgrid[0:side, 0:side]
    x = np.stack([ys.ravel() / side, xs.ravel() / side], -1)
    out = []
    for i in range(params["pool"]):
        r = rng(seed, 1, i)
        a = _frame(side, math.pi, r)
        b = _frame(side, math.pi + 2.0 * math.pi * (i + 1) / CYCLE, r)
        out.append(Measures(x, a / a.sum(), b / b.sum(), params["eps"], params["lam"],
                            cost_kw=(("eta", params["eta"]),)))
    return out
