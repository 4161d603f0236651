"""Gathered Gibbs-kernel evaluation Pallas kernel (TPU target).

The matrix-free Spar-Sink path works on an O(s) list of ``(row, col)``
index pairs instead of an (n, m) array. Given the two support-point blocks
*already gathered* at those pairs (XLA owns the gather; see
``repro.kernels.ops.gathered_kernel``), this kernel streams chunks of
entries through VMEM and emits, per pair,

* ``K_e = exp(-C(x_i, y_j) / eps)``   — the sketch's kernel values, and
* ``C_e = C(x_i, y_j)``               — the raw cost (sparse objective),

in O(s d) HBM traffic. Cost functions are the static switch shared with
``fused_sinkhorn._cost_tile`` (squared euclidean / WFR); WFR blocked pairs
(``d >= pi * eta``) map to exactly ``K_e = 0`` and ``C_e = +inf``.

Layout: the entry axis is folded onto the (8, 128) vreg tiling. Gathered
points arrive as ``(d, S/128, 128)`` — one dense (sublane, lane) plane per
coordinate, d on the untiled leading axis — and both outputs are dense
``(S/128, 128)`` planes. A ``(S, d)`` layout would pad d up to 128 lanes and
``(S, 1)`` outputs 128x as well. Everything is VPU element-wise work, no
MXU involved.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_sinkhorn import _cost_from_sq

__all__ = ["gathered_kernel_call"]

LANES = 128


def _gathered_kernel(x_ref, y_ref, k_ref, c_ref, *, eps: float, cost: str, eta: float):
    xx = yy = xy = 0.0
    for j in range(x_ref.shape[0]):  # static d: one (Br, 128) plane each
        xj, yj = x_ref[j], y_ref[j]
        xx, yy, xy = xx + xj * xj, yy + yj * yj, xy + xj * yj
    sq = jnp.maximum(xx + yy - 2.0 * xy, 0.0)  # (Br, 128) squared distances
    c, blocked = _cost_from_sq(sq, cost, eta)
    k = jnp.exp(-c / eps)
    if blocked is not None:
        k = jnp.where(blocked, 0.0, k)
        c = jnp.where(blocked, jnp.inf, c)
    k_ref[...] = k
    c_ref[...] = c


def gathered_kernel_call(
    xg: jax.Array,
    yg: jax.Array,
    *,
    eps: float,
    cost: str = "sqeuclidean",
    eta: float = 1.0,
    block_s: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw pallas_call on pre-gathered, pre-padded inputs: ``xg``/``yg`` are
    the (d, S/128, 128) coordinate planes of the support points at the
    sampled pairs, with ``S % block_s == 0`` and ``block_s`` a multiple of
    1024 (8 sublanes x 128 lanes). Returns ``(K_e, C_e)``, each
    (S/128, 128) in entry order. Use ``repro.kernels.ops.gathered_kernel``
    for the gather + padding."""
    d, r, lanes = xg.shape
    if lanes != LANES or block_s % (8 * LANES) or (r * LANES) % block_s:
        raise ValueError(
            f"gathered_kernel_call needs (d, S/128, 128) inputs with S a "
            f"multiple of block_s and block_s a multiple of 1024; got "
            f"{xg.shape}, block_s={block_s}"
        )
    br = block_s // LANES
    kern = functools.partial(_gathered_kernel, eps=eps, cost=cost, eta=eta)
    plane = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        kern,
        grid=(r // br,),
        in_specs=[
            pl.BlockSpec((d, br, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((d, br, LANES), lambda i: (0, i, 0)),
        ],
        out_specs=[plane, plane],
        out_shape=[
            jax.ShapeDtypeStruct((r, LANES), jnp.float32),
            jax.ShapeDtypeStruct((r, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(xg, yg)
