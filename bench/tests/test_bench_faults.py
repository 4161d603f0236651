"""The rest of a run, with the timed path broken underneath, must come out
not correct: once for each fault a cell can have. The cells run on one chip,
so there is no exchange between chips to leave out."""
import math

import jax
import jax.numpy as jnp
import pytest

import repro.batch.solvers as batch_solvers
import repro.core.api.solvers as api_solvers
import repro.core.sparsify as sparsify
from bench.tests import tiny

CELLS = tiny.cells()


def unchanged_state(rows, cols, logvals, csort, loga, logb, eps, fe, **kw):
    """The iteration returns its initial potentials, as if no step ran."""
    f = jnp.where(jnp.isneginf(loga), -jnp.inf, 0.0).astype(loga.dtype)
    g = jnp.where(jnp.isneginf(logb), -jnp.inf, 0.0).astype(logb.dtype)
    b = loga.shape[0]
    return (f, g, jnp.zeros((b,), jnp.int32), jnp.zeros((b,), loga.dtype),
            jnp.zeros((b,), jnp.int32))


def _assert_caught(res):
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_state_left_unchanged(cell, monkeypatch):
    monkeypatch.setattr(batch_solvers, "sparse_log_potentials", unchanged_state)
    _assert_caught(tiny.run(cell))


@pytest.mark.parametrize("cell", CELLS)
def test_solve_value_altered(cell, monkeypatch):
    real = api_solvers._coo_log_value
    monkeypatch.setattr(api_solvers, "_coo_log_value",
                        lambda *a, **k: real(*a, **k) * 1.01)
    _assert_caught(tiny.run(cell))


def _merge_fault(monkeypatch, merge):
    """The sketch's duplicate draws merged by ``merge`` in place of their
    logsumexp; the iteration keeps the true segment logsumexp."""
    real_build, real_lse = sparsify.sparsify_coo_mf_log, sparsify.segment_logsumexp

    def build(*a, **k):
        monkeypatch.setattr(sparsify, "segment_logsumexp", merge)
        try:
            return real_build(*a, **k)
        finally:
            monkeypatch.setattr(sparsify, "segment_logsumexp", real_lse)

    monkeypatch.setattr(sparsify, "sparsify_coo_mf_log", build)


@pytest.mark.parametrize("cell", CELLS)
def test_duplicate_draws_lose_their_multiplicity(cell, monkeypatch):
    """A pair drawn k times weighs one draw."""
    _merge_fault(monkeypatch, lambda z, seg, num_segments, indices_are_sorted=False:
                 jax.ops.segment_max(z, seg, num_segments=num_segments,
                                     indices_are_sorted=indices_are_sorted))
    res = tiny.run(cell, merge=True)
    _assert_caught(res)
    assert res["checks"]["entry_log_err"]["value"] <= res["checks"]["entry_log_err"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_every_multiplicity_doubled(cell, monkeypatch):
    """A pair drawn k times weighs 2k draws."""
    real = sparsify.segment_logsumexp
    _merge_fault(monkeypatch, lambda *a, **k: real(*a, **k) + math.log(2.0))
    _assert_caught(tiny.run(cell, merge=True))
