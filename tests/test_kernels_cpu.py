"""Raw Pallas ``*_call`` coverage on CPU (interpret mode): `online_lse_call`
and `block_ell_matvec_call` against the pure-jnp oracles in
`repro.kernels.ref`, including the WFR blocked-entry (zero-mass) branch,
plus the batched sparse mat-vec entry points of `repro.kernels.ops`.

Unlike tests/test_kernels.py (which exercises the padded public wrappers),
these call the kernels directly on pre-padded block-aligned shapes — the
contract the TPU lowering sees."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gibbs_kernel, squared_euclidean_cost, wfr_cost
from repro.core import sparsify
from repro.kernels import (
    batched_block_ell_matvec,
    batched_coo_matvec,
    batched_coo_rmatvec,
    gathered_kernel,
)
from repro.kernels.block_ell import block_ell_matvec_call
from repro.kernels.fused_sinkhorn import online_lse_call
from repro.kernels.gather_kernel import gathered_kernel_call
from repro.kernels.ref import (
    block_ell_matvec_ref,
    gathered_kernel_ref,
    online_lse_ref,
)

NEG_INF = -1e30


def _points(key, n, d, lo=0.0, hi=1.0):
    return jax.random.uniform(key, (n, d), jnp.float32, lo, hi)


def _planes(pts):
    """(S, d) gathered points -> the kernel's (d, S/128, 128) layout."""
    return pts.T.reshape(pts.shape[1], -1, 128)


# --------------------------------------------------------------------------
# online_lse_call (raw, pre-padded shapes)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(256, 512, 128), (512, 1024, 256)])
def test_online_lse_call_sqeuclidean(shape):
    n, m, d = shape
    kx, ky, kg = jax.random.split(jax.random.PRNGKey(n + m), 3)
    x, y = _points(kx, n, d), _points(ky, m, d)
    g = 0.1 * jax.random.normal(kg, (m,), jnp.float32)
    out = online_lse_call(x, y, g[:, None], eps=0.05, interpret=True)
    ref = online_lse_ref(x, y, g, eps=0.05)
    np.testing.assert_allclose(
        np.asarray(out[:, 0]), np.asarray(ref), rtol=2e-4, atol=5e-4
    )


def test_online_lse_call_wfr_blocked_entries():
    """WFR cost with eta small enough that many pairs sit beyond range
    pi*eta: blocked entries contribute exactly zero mass to the LSE."""
    n, m, d = 256, 512, 128
    kx, ky, kg = jax.random.split(jax.random.PRNGKey(0), 3)
    x, y = _points(kx, n, d), _points(ky, m, d)
    g = 0.05 * jax.random.normal(kg, (m,), jnp.float32)
    d_xy = jnp.sqrt(jnp.maximum(squared_euclidean_cost(x, y), 0.0))
    eta = float(jnp.median(d_xy)) / math.pi  # range pi*eta = median distance
    frac_blocked = float(jnp.mean(d_xy / (2 * eta) >= math.pi / 2))
    assert 0.05 < frac_blocked < 0.95, frac_blocked  # branch genuinely taken
    out = online_lse_call(x, y, g[:, None], eps=0.1, cost="wfr", eta=eta,
                          interpret=True)
    ref = online_lse_ref(x, y, g, eps=0.1, cost="wfr", eta=eta)
    np.testing.assert_allclose(
        np.asarray(out[:, 0]), np.asarray(ref), rtol=2e-4, atol=5e-4
    )


def test_online_lse_call_wfr_fully_blocked_row_stays_neg_inf():
    """A support point out of range of *every* target: its row LSE must come
    out as the -1e30 sentinel (zero total mass), not nan/garbage."""
    n, m, d = 256, 512, 128
    ky, kg = jax.random.split(jax.random.PRNGKey(1), 2)
    y = _points(ky, m, d, 0.0, 0.05)
    x = jnp.zeros((n, d), jnp.float32).at[0, 0].set(100.0)  # row 0 far away
    x = x.at[1:, :].set(_points(jax.random.PRNGKey(2), n - 1, d, 0.0, 0.05))
    g = jnp.zeros((m,), jnp.float32)
    out = online_lse_call(x, y, g[:, None], eps=0.1, cost="wfr", eta=0.3,
                          interpret=True)
    out = np.asarray(out[:, 0])
    assert out[0] <= NEG_INF / 2  # fully blocked row: -inf sentinel
    assert np.all(np.isfinite(out[1:])) and np.all(out[1:] > NEG_INF / 2)


# --------------------------------------------------------------------------
# gathered_kernel_call (raw) — the matrix-free (K_e, C_e) evaluation
# --------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(1024, 128), (2048, 256)])
def test_gathered_kernel_call_sqeuclidean(shape):
    s, d = shape
    n, m = 300, 200
    kx, ky = jax.random.split(jax.random.PRNGKey(s), 2)
    x, y = _points(kx, n, d), _points(ky, m, d)
    rng = np.random.default_rng(s)
    rows = jnp.asarray(rng.integers(0, n, s), jnp.int32)
    cols = jnp.asarray(rng.integers(0, m, s), jnp.int32)
    k_out, c_out = gathered_kernel_call(
        _planes(x[rows]), _planes(y[cols]), eps=0.05, block_s=1024,
        interpret=True,
    )
    assert k_out.shape == c_out.shape == (s // 128, 128)
    k_ref, c_ref = gathered_kernel_ref(x, y, rows, cols, eps=0.05)
    np.testing.assert_allclose(np.asarray(c_out).ravel(), np.asarray(c_ref),
                               rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(k_out).ravel(), np.asarray(k_ref),
                               rtol=2e-3, atol=1e-6)


def test_gathered_kernel_call_wfr_blocked_is_exactly_zero():
    """WFR pairs beyond range pi*eta must come out K_e = 0 exactly and
    C_e = +inf (the blocked branch of the matrix-free sketch)."""
    s, d = 1024, 128
    rng = np.random.default_rng(3)
    # two clusters further apart than the transport range
    x = np.zeros((256, d), np.float32)
    x[:128, 0] = rng.uniform(0.0, 0.2, 128)
    x[128:, 0] = rng.uniform(1.8, 2.0, 128)
    x = jnp.asarray(x)
    eta = 0.2
    rows = jnp.asarray(rng.integers(0, 256, s), jnp.int32)
    cols = jnp.asarray(rng.integers(0, 256, s), jnp.int32)
    k_out, c_out = gathered_kernel_call(
        _planes(x[rows]), _planes(x[cols]), eps=0.1, cost="wfr", eta=eta,
        block_s=1024, interpret=True,
    )
    k_out, c_out = np.asarray(k_out).ravel(), np.asarray(c_out).ravel()
    k_ref, c_ref = gathered_kernel_ref(x, x, rows, cols, eps=0.1, cost="wfr",
                                       eta=eta)
    blocked = np.isinf(np.asarray(c_ref))
    assert 0.1 < blocked.mean() < 0.9  # branch genuinely taken
    np.testing.assert_array_equal(k_out[blocked], 0.0)
    assert np.all(np.isinf(c_out[blocked]))
    ok = ~blocked
    np.testing.assert_allclose(k_out[ok], np.asarray(k_ref)[ok], rtol=2e-3,
                               atol=1e-6)
    np.testing.assert_allclose(c_out[ok], np.asarray(c_ref)[ok], rtol=2e-3,
                               atol=1e-5)


def test_gathered_kernel_wrapper_pads_and_slices():
    """The public wrapper handles arbitrary (k, d): pads to block-aligned
    shapes, gathers, and slices the padding away."""
    n, m, d, k = 100, 80, 5, 777  # nothing aligned
    kx, ky = jax.random.split(jax.random.PRNGKey(0), 2)
    x, y = _points(kx, n, d), _points(ky, m, d)
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.integers(0, n, k), jnp.int32)
    cols = jnp.asarray(rng.integers(0, m, k), jnp.int32)
    k_e, c_e = gathered_kernel(x, y, rows, cols, eps=0.1, interpret=True)
    assert k_e.shape == (k,) and c_e.shape == (k,)
    k_ref, c_ref = gathered_kernel_ref(x, y, rows, cols, eps=0.1)
    np.testing.assert_allclose(np.asarray(k_e), np.asarray(k_ref), rtol=2e-3,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(c_e), np.asarray(c_ref), rtol=2e-4,
                               atol=1e-5)


# --------------------------------------------------------------------------
# block_ell_matvec_call (raw) — including zero-mass (blocked) WFR tiles
# --------------------------------------------------------------------------


@pytest.mark.parametrize("bk,maxb,nrb", [(8, 2, 4), (16, 4, 8), (32, 3, 4)])
def test_block_ell_matvec_call_random(bk, maxb, nrb):
    ncb = nrb
    key = jax.random.PRNGKey(bk * maxb)
    kv, ki, kx = jax.random.split(key, 3)
    vals = jax.random.uniform(kv, (nrb, maxb, bk, bk), jnp.float32)
    col_idx = jax.random.randint(ki, (nrb, maxb), 0, ncb, jnp.int32)
    v = jax.random.uniform(kx, (ncb, bk), jnp.float32)
    out = block_ell_matvec_call(vals, col_idx, v, interpret=True)
    ref = block_ell_matvec_ref(vals, col_idx, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=1e-6)


def test_block_ell_matvec_call_wfr_zero_mass_tiles():
    """Sketch a WFR kernel whose blocked entries are exactly 0: tiles that
    straddle the transport range carry zero-mass entries, and fully-blocked
    kept tiles must contribute exactly 0 to the mat-vec."""
    n, bk, maxb = 128, 16, 4
    rng = np.random.default_rng(7)
    # two spatial clusters further apart than pi*eta: cross-cluster blocked
    x = np.concatenate([rng.uniform(0.0, 0.2, (n // 2, 2)),
                        rng.uniform(1.8, 2.0, (n // 2, 2))])
    x = jnp.asarray(x, jnp.float32)
    eta = 0.2
    K = gibbs_kernel(wfr_cost(x, eta=eta), 0.1).astype(jnp.float32)
    assert float(jnp.mean(K == 0.0)) > 0.4  # blocked branch well-populated
    a = jnp.asarray(rng.dirichlet(np.ones(n)), jnp.float32)
    tp = sparsify.ot_tile_probs(a, a, bk).astype(jnp.float32)
    sk = sparsify.sparsify_block_ell(
        jax.random.PRNGKey(3), K, tp, float(n * 8), bk, maxb
    )
    v = jnp.asarray(rng.uniform(size=(n,)), jnp.float32)
    out = block_ell_matvec_call(
        sk.vals, sk.col_idx, v.reshape(-1, bk), interpret=True
    )
    ref = block_ell_matvec_ref(sk.vals, sk.col_idx, v.reshape(-1, bk))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=1e-6)
    # rows whose kept tiles are all in the blocked region get exactly 0
    dense = sparsify.block_ell_to_dense(sk)
    dead_rows = np.asarray(jnp.sum(dense, axis=1) == 0.0)
    np.testing.assert_array_equal(np.asarray(out).reshape(-1)[dead_rows], 0.0)


# --------------------------------------------------------------------------
# Batched entry points (repro.kernels.ops)
# --------------------------------------------------------------------------


def test_batched_block_ell_matvec_matches_per_element():
    B, bk, maxb, nrb = 3, 16, 2, 4
    key = jax.random.PRNGKey(0)
    kv, ki, kx = jax.random.split(key, 3)
    vals = jax.random.uniform(kv, (B, nrb, maxb, bk, bk), jnp.float32)
    col_idx = jax.random.randint(ki, (B, nrb, maxb), 0, nrb, jnp.int32)
    v = jax.random.uniform(kx, (B, nrb * bk), jnp.float32)
    out = batched_block_ell_matvec(vals, col_idx, v, interpret=True)
    assert out.shape == (B, nrb * bk)
    for i in range(B):
        ref = block_ell_matvec_ref(
            vals[i], col_idx[i], v[i].reshape(-1, bk)
        ).reshape(-1)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref),
                                   rtol=2e-4, atol=1e-6)


def test_batched_coo_matvec_bitwise_matches_per_element():
    """The flat-segment batched COO mat-vec is bitwise B separate
    `sparsify.coo_matvec` / `coo_rmatvec` calls (disjoint segments)."""
    B, n, m, cap = 4, 64, 48, 300
    rng = np.random.default_rng(11)
    sks = []
    for i in range(B):
        K = jnp.asarray(rng.uniform(size=(n, m)))
        probs = jnp.full((n, m), 1.0 / (n * m))
        sks.append(sparsify.sparsify_coo(jax.random.PRNGKey(i), K, probs,
                                         float(cap) / 2, cap))
    rows = jnp.stack([sk.rows for sk in sks])
    cols = jnp.stack([sk.cols for sk in sks])
    vals = jnp.stack([sk.vals for sk in sks])
    v = jnp.asarray(rng.uniform(size=(B, m)))
    u = jnp.asarray(rng.uniform(size=(B, n)))
    out = batched_coo_matvec(rows, vals, jnp.take_along_axis(v, cols, axis=1), n=n)
    out_t = batched_coo_rmatvec(cols, vals, jnp.take_along_axis(u, rows, axis=1), m=m)
    for i, sk in enumerate(sks):
        assert bool(jnp.all(out[i] == sparsify.coo_matvec(sk, v[i])))
        assert bool(jnp.all(out_t[i] == sparsify.coo_rmatvec(sk, u[i])))
