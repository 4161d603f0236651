"""Public jit'd wrappers around the Pallas kernels.

These take arbitrary (n, m, d) problems, pad to block-aligned shapes with
mass-neutral padding (v=0 / g=-inf / duplicate support points), call the
kernels, and slice the padding away. On non-TPU backends they run in
interpret mode automatically, so the whole library is testable on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import fused_sinkhorn as _fs
from repro.kernels import block_ell as _be
from repro.core.geometry import gather_points
from repro.core.sinkhorn import SinkhornResult, generic_scaling_loop

__all__ = [
    "batched_block_ell_matvec",
    "batched_coo_logsumexp",
    "batched_coo_matvec",
    "batched_coo_rmatvec",
    "block_ell_matvec",
    "fused_sinkhorn_solve",
    "gathered_kernel",
    "lru_scan",
    "online_lse",
    "online_matvec",
]


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, size: int, axis: int, value=0.0) -> jax.Array:
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _round_up(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


@functools.partial(
    jax.jit, static_argnames=("eps", "cost", "eta", "block_n", "block_m", "interpret")
)
def online_matvec(
    x: jax.Array,
    y: jax.Array,
    v: jax.Array,
    *,
    eps: float,
    cost: str = "sqeuclidean",
    eta: float = 1.0,
    block_n: int = 256,
    block_m: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """``K(x, y) @ v`` without materializing K. Shapes: (n,d),(m,d),(m,) -> (n,)."""
    interpret = _interpret_default() if interpret is None else interpret
    n, m = x.shape[0], y.shape[0]
    dp = _round_up(x.shape[1], 128)
    np_, mp = _round_up(n, block_n), _round_up(m, block_m)
    xp = _pad_to(_pad_to(x.astype(jnp.float32), dp, 1), np_, 0)
    yp = _pad_to(_pad_to(y.astype(jnp.float32), dp, 1), mp, 0)
    vp = _pad_to(v.astype(jnp.float32)[:, None], mp, 0)
    out = _fs.online_matvec_call(
        xp, yp, vp, eps=eps, cost=cost, eta=eta,
        block_n=block_n, block_m=block_m, interpret=interpret,
    )
    return out[:n, 0]


@functools.partial(
    jax.jit, static_argnames=("eps", "cost", "eta", "block_n", "block_m", "interpret")
)
def online_lse(
    x: jax.Array,
    y: jax.Array,
    g: jax.Array,
    *,
    eps: float,
    cost: str = "sqeuclidean",
    eta: float = 1.0,
    block_n: int = 256,
    block_m: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """``logsumexp_j(-C_ij/eps + g_j/eps)`` streamed. (n,d),(m,d),(m,) -> (n,)."""
    interpret = _interpret_default() if interpret is None else interpret
    n, m = x.shape[0], y.shape[0]
    dp = _round_up(x.shape[1], 128)
    np_, mp = _round_up(n, block_n), _round_up(m, block_m)
    xp = _pad_to(_pad_to(x.astype(jnp.float32), dp, 1), np_, 0)
    yp = _pad_to(_pad_to(y.astype(jnp.float32), dp, 1), mp, 0)
    gp = _pad_to(g.astype(jnp.float32)[:, None], mp, 0, value=-1e30)
    out = _fs.online_lse_call(
        xp, yp, gp, eps=eps, cost=cost, eta=eta,
        block_n=block_n, block_m=block_m, interpret=interpret,
    )
    return out[:n, 0]


@functools.partial(
    jax.jit, static_argnames=("eps", "cost", "eta", "block_s", "interpret")
)
def gathered_kernel(
    x: jax.Array,
    y: jax.Array,
    rows: jax.Array,
    cols: jax.Array,
    *,
    eps: float,
    cost: str = "sqeuclidean",
    eta: float = 1.0,
    block_s: int = 32768,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``(K_e, C_e) = (exp(-C(x_i,y_j)/eps), C(x_i,y_j))`` at k index pairs.

    The matrix-free sketch's kernel evaluation: XLA gathers the two
    support-point blocks (O(k d) HBM traffic), the Pallas kernel fuses the
    cost + exponential per chunk of ``block_s`` entries (shrunk for small
    k or large d). WFR blocked pairs map to exactly ``(0, +inf)``.
    Shapes: (n,d),(m,d),(k,),(k,) -> ((k,),(k,)).
    """
    from repro.kernels.gather_kernel import LANES, gathered_kernel_call

    interpret = _interpret_default() if interpret is None else interpret
    k, d = rows.shape[0], x.shape[1]
    tile = 8 * LANES
    # at most ~2 MiB of VMEM per input block, at least one (8, 128) tile
    block_s = max(tile, min(block_s, (2 ** 19 // d) // tile * tile))
    block_s = min(block_s, _round_up(max(k, 1), tile))
    kp = _round_up(max(k, 1), block_s)
    # padded pairs gather point 0 (a valid index); sliced away below
    rows, cols = _pad_to(rows, kp, 0, value=0), _pad_to(cols, kp, 0, value=0)
    # (d, k/128, 128) coordinate planes: entries on lanes
    xg = jnp.stack(gather_points(x.astype(jnp.float32), rows)).reshape(d, -1, LANES)
    yg = jnp.stack(gather_points(y.astype(jnp.float32), cols)).reshape(d, -1, LANES)
    k_e, c_e = gathered_kernel_call(
        xg, yg, eps=eps, cost=cost, eta=eta, block_s=block_s, interpret=interpret
    )
    return k_e.reshape(-1)[:k], c_e.reshape(-1)[:k]


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_ell_matvec(
    vals: jax.Array,
    col_idx: jax.Array,
    v: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Sparse sketch mat-vec: (nrb,maxb,Bk,Bk),(nrb,maxb),(n_cols,) -> (n_rows,)."""
    interpret = _interpret_default() if interpret is None else interpret
    bk = vals.shape[-1]
    out = _be.block_ell_matvec_call(
        vals, col_idx, v.astype(jnp.float32).reshape(-1, bk), interpret=interpret
    )
    return out.reshape(-1)


# ---------------------------------------------------------------------------
# Batched sparse mat-vec entry points (the repro.batch execution engine)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("interpret",))
def batched_block_ell_matvec(
    vals: jax.Array,  # (B, nrb, maxb, Bk, Bk)
    col_idx: jax.Array,  # (B, nrb, maxb) int32
    v: jax.Array,  # (B, n_cols)
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """B independent block-ELL sketch mat-vecs in ONE pallas_call.

    The batch axis is folded into the row-block grid dimension (column ids
    get a per-element block offset), so the single-sketch kernel serves the
    whole batch without a vmap-of-pallas lowering. Returns (B, n_rows).
    """
    interpret = _interpret_default() if interpret is None else interpret
    bsz, nrb, maxb, bk, _ = vals.shape
    ncb = v.shape[-1] // bk
    offs = (jnp.arange(bsz, dtype=jnp.int32) * ncb)[:, None, None]
    ci = (col_idx.astype(jnp.int32) + offs).reshape(bsz * nrb, maxb)
    out = _be.block_ell_matvec_call(
        vals.reshape(bsz * nrb, maxb, bk, bk),
        ci,
        v.astype(jnp.float32).reshape(bsz * ncb, bk),
        interpret=interpret,
    )
    return out.reshape(bsz, nrb * bk)


@functools.partial(jax.jit, static_argnames=("n", "indices_are_sorted"))
def batched_coo_matvec(
    rows: jax.Array,
    vals: jax.Array,
    v_gathered: jax.Array,
    *,
    n: int | None = None,
    indices_are_sorted: bool = False,
) -> jax.Array:
    """B independent padded-COO mat-vec reductions as one flat segment-sum.

    ``rows`` is (B, cap) per-element row ids; ``v_gathered`` is the already
    gathered right factor ``take_along_axis(v, cols, 1)`` (callers own the
    gather so the transpose direction reuses this same reduction). Disjoint
    per-element segments keep results bitwise those of B separate
    `repro.core.sparsify.coo_matvec` calls. With per-element-sorted ids
    (the `sparsify_coo` construction invariant) the flat concatenation is
    sorted too, so pass ``indices_are_sorted=True`` for the faster scatter.
    Returns (B, n).
    """
    bsz, _ = rows.shape
    if n is None:
        raise TypeError("batched_coo_matvec requires n (static output width)")
    seg = (rows + (jnp.arange(bsz, dtype=jnp.int32) * n)[:, None]).ravel()
    out = jax.ops.segment_sum(
        (vals * v_gathered).ravel(),
        seg,
        num_segments=bsz * n,
        indices_are_sorted=indices_are_sorted,
    )
    return out.reshape(bsz, n)


def batched_coo_rmatvec(
    cols: jax.Array,
    vals: jax.Array,
    u_gathered: jax.Array,
    *,
    m: int | None = None,
    indices_are_sorted: bool = False,
) -> jax.Array:
    """Transpose counterpart of `batched_coo_matvec` (segment over columns).
    For sorted scatter, callers pass the col-sorted permutation of all three
    arrays (``take_along_axis(., sketch.csort, 1)``)."""
    return batched_coo_matvec(
        cols, vals, u_gathered, n=m, indices_are_sorted=indices_are_sorted
    )


@functools.partial(jax.jit, static_argnames=("n", "indices_are_sorted"))
def batched_coo_logsumexp(
    idx: jax.Array,
    z: jax.Array,
    *,
    n: int | None = None,
    indices_are_sorted: bool = False,
) -> jax.Array:
    """B independent padded-COO segment-logsumexps as one flat reduction.

    The log-domain `batched_coo_matvec` (the hot op of the batched
    ``spar_sink_log`` solver): ``z`` is the per-entry summand
    ``logvals + take_along_axis(y, cols, 1)`` — callers own the gather so
    the transpose direction reuses this same reduction — and ``idx`` the
    (B, cap) per-element segment ids. Disjoint per-element segments run the
    single `repro.core.sparsify.segment_logsumexp` implementation, so
    results are bitwise those of B separate per-problem calls; ``-inf``
    entries (padding / dead sketch slots) are inert and empty segments come
    out exactly ``-inf``. Returns (B, n).
    """
    from repro.core.sparsify import segment_logsumexp

    bsz, _ = idx.shape
    if n is None:
        raise TypeError("batched_coo_logsumexp requires n (static output width)")
    seg = (idx + (jnp.arange(bsz, dtype=jnp.int32) * n)[:, None]).ravel()
    out = segment_logsumexp(
        z.ravel(),
        seg,
        num_segments=bsz * n,
        indices_are_sorted=indices_are_sorted,
    )
    return out.reshape(bsz, n)


# ---------------------------------------------------------------------------
# Fused LRU scan (h_t = a_t h_{t-1} + b_t) with a custom VJP — both directions
# are single-pass Pallas kernels (see kernels/lru_scan.py).
# ---------------------------------------------------------------------------


def _lru_pad(x, s_pad, w_pad):
    return _pad_to(_pad_to(x, w_pad, 2), s_pad, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def lru_scan(a: jax.Array, b: jax.Array, interpret: bool | None = None) -> jax.Array:
    """(B,S,W) f32 linear recurrence h_t = a_t h_{t-1} + b_t, fused on TPU."""
    return _lru_fwd(a, b, interpret)[0]


def _lru_fwd(a, b, interpret):
    from repro.kernels import lru_scan as _lk

    interpret = _interpret_default() if interpret is None else interpret
    bsz, s, w = a.shape
    sp, wp = _round_up(s, 256), _round_up(w, 128)
    ap = _lru_pad(a.astype(jnp.float32), sp, wp)
    bp = _lru_pad(b.astype(jnp.float32), sp, wp)
    h = _lk.lru_scan_fwd_call(ap, bp, seq_chunk=min(1024, sp), interpret=interpret)
    h = h[:, :s, :w]
    return h, (a, h)


def _lru_bwd(interpret, res, g):
    from repro.kernels import lru_scan as _lk

    interpret = _interpret_default() if interpret is None else interpret
    a, h = res
    bsz, s, w = a.shape
    sp, wp = _round_up(s, 256), _round_up(w, 128)
    a_next = jnp.concatenate([a[:, 1:, :], jnp.zeros_like(a[:, :1, :])], axis=1)
    anp = _lru_pad(a_next.astype(jnp.float32), sp, wp)
    gp = _lru_pad(g.astype(jnp.float32), sp, wp)
    lam = _lk.lru_scan_bwd_call(anp, gp, seq_chunk=min(1024, sp), interpret=interpret)
    lam = lam[:, :s, :w]
    h_prev = jnp.concatenate([jnp.zeros_like(h[:, :1, :]), h[:, :-1, :]], axis=1)
    return (lam * h_prev).astype(a.dtype), lam.astype(a.dtype)


lru_scan.defvjp(_lru_fwd, _lru_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("eps", "fe", "cost", "eta", "tol", "max_iter", "block_n", "block_m", "interpret"),
)
def fused_sinkhorn_solve(
    x: jax.Array,
    y: jax.Array,
    a: jax.Array,
    b: jax.Array,
    *,
    eps: float,
    fe: float = 1.0,
    cost: str = "sqeuclidean",
    eta: float = 1.0,
    tol: float = 1e-6,
    max_iter: int = 1000,
    block_n: int = 256,
    block_m: int = 512,
    interpret: bool | None = None,
) -> SinkhornResult:
    """Dense Sinkhorn (OT: fe=1; UOT: fe=lam/(lam+eps)) with the fused online
    mat-vec — the beyond-paper O(n d)-memory baseline (DESIGN §3.2)."""
    mv = lambda v: online_matvec(
        x, y, v, eps=eps, cost=cost, eta=eta,
        block_n=block_n, block_m=block_m, interpret=interpret,
    )
    rmv = lambda u: online_matvec(
        y, x, u, eps=eps, cost=cost, eta=eta,
        block_n=block_n, block_m=block_m, interpret=interpret,
    )
    return generic_scaling_loop(mv, rmv, a, b, fe, tol=tol, max_iter=max_iter)
