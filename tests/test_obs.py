"""repro.obs: jit-safe solver telemetry + runtime metrics.

Covers the PR-7 observability contract:

* **zero overhead when disabled** — the ``trace=False`` jaxpr of every
  generic loop is *string-identical* to a frozen pre-telemetry copy of the
  loop kept in this file, and traced/untraced solves agree bitwise;
* trace correctness: matvec accounting, ring-buffer wrap, chronological
  unroll, batched slicing;
* sketch diagnostics (nnz/fill/ESS/acceptance/merge-rate);
* the Spar-Sink solves' phase spans: one registry observation a phase, and
  spans nested in a profiler trace that leave the result bitwise unchanged;
* `MetricsRegistry` semantics (quantiles, windowing, atomicity, export
  formats) and the executor/serving instrumentation built on it;
* status propagation through composite paths (divergence, barycenters,
  screenkhorn's restricted solve).
"""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Geometry, OTProblem, PointCloudGeometry, solve
from repro.core.sinkhorn import (
    STATUS_CONVERGED,
    STATUS_MAX_ITER,
    SinkhornResult,
    _l1,
    _log_domain_status,
    _masked_log,
    _safe_div,
    _status_code,
    generic_log_loop,
    generic_scaling_loop,
    generic_sparse_log_loop,
)
from repro.obs import (
    DEFAULT_TRACE_LEN,
    MetricsRegistry,
    SolverTrace,
    export,
    sketch_diagnostics,
    trim_trace,
)

EPS = 0.5


def _problem(n=48, m=40, seed=0, eps=EPS):
    rng = np.random.default_rng(seed)
    C = rng.random((n, m))
    a = np.abs(rng.normal(size=n)) + 0.1
    b = np.abs(rng.normal(size=m)) + 0.1
    return OTProblem(
        Geometry(jnp.asarray(C)),
        jnp.asarray(a / a.sum()),
        jnp.asarray(b / b.sum()),
        eps,
    )


# --------------------------------------------------------------------------
# Zero-overhead contract: trace=False jaxprs == frozen pre-telemetry loops
# --------------------------------------------------------------------------
# These are literal copies of the three generic loops as they stood before
# the trace option existed (reusing the module's own helpers, so helper
# changes don't spuriously fail the guard). If a refactor legitimately
# changes the untraced op sequence, update the frozen copy in the same PR.


def _frozen_scaling_loop(matvec, rmatvec, a, b, fe=1.0, *, tol=1e-6,
                         max_iter=1000, patience=100):
    n, m = a.shape[0], b.shape[0]
    u0 = jnp.ones((n,), dtype=a.dtype)
    v0 = jnp.ones((m,), dtype=b.dtype)
    big = jnp.array(jnp.finfo(a.dtype).max, a.dtype)

    def cond(state):
        t, err, since = state[2], state[3], state[5]
        return (
            (err > tol) & jnp.isfinite(err) & (t < max_iter) & (since < patience)
        )

    def body(state):
        u, v, t, _, best, since = state[:6]
        Kv = matvec(v)
        u_new = _safe_div(a, Kv) ** fe
        KTu = rmatvec(u_new)
        v_new = _safe_div(b, KTu) ** fe
        err = _l1(u_new - u) + _l1(v_new - v)
        marg = _l1(v * KTu - b)
        improved = marg < best * (1.0 - 1e-4)
        best = jnp.minimum(best, marg)
        since = jnp.where(improved, 0, since + 1)
        return (u_new, v_new, t + 1, err, best, since)

    init = (u0, v0, jnp.array(0, jnp.int32), big, big, jnp.array(0, jnp.int32))
    final = jax.lax.while_loop(cond, body, init)
    u, v, t, err, _, since = final[:6]
    bad = ~(
        jnp.isfinite(err) & jnp.all(jnp.isfinite(u)) & jnp.all(jnp.isfinite(v))
    )
    degenerate = (jnp.max(u) <= 0.0) | (jnp.max(v) <= 0.0)
    return SinkhornResult(
        u, v, t, err, _status_code(bad, degenerate, err, tol, since >= patience)
    )


def _frozen_log_loop(lse_row, lse_col, loga, logb, eps, fe=1.0, *, tol=1e-9,
                     max_iter=1000):
    n, m = loga.shape[0], logb.shape[0]
    f0 = jnp.zeros((n,), loga.dtype)
    g0 = jnp.zeros((m,), logb.dtype)
    neg_inf_a = jnp.isneginf(loga)
    neg_inf_b = jnp.isneginf(logb)

    def cond(state):
        t, err = state[2], state[3]
        return jnp.logical_and(err > tol, t < max_iter)

    def body(state):
        f, g, t, _ = state[:4]
        f_new = fe * eps * (loga - lse_row(g))
        f_new = jnp.where(neg_inf_a, -jnp.inf, f_new)
        lc = lse_col(f_new)
        g_new = fe * eps * (logb - lc)
        g_new = jnp.where(neg_inf_b, -jnp.inf, g_new)
        df = jnp.where(neg_inf_a, 0.0, jnp.abs(f_new - f))
        dg = jnp.where(neg_inf_b, 0.0, jnp.abs(g_new - g))
        err = jnp.max(df) + jnp.max(dg)
        return (f_new, g_new, t + 1, err)

    init = (f0, g0, jnp.array(0, jnp.int32), jnp.array(jnp.inf, loga.dtype))
    final = jax.lax.while_loop(cond, body, init)
    f, g, t, err = final[:4]
    return SinkhornResult(f, g, t, err, _log_domain_status(f, g, err, tol))


def _frozen_sparse_log_loop(lse_row, lse_col, loga, logb, eps, fe=1.0, *,
                            tol=1e-6, max_iter=1000, patience=100):
    n, m = loga.shape[0], logb.shape[0]
    neg_inf_a = jnp.isneginf(loga)
    neg_inf_b = jnp.isneginf(logb)
    f0 = jnp.where(neg_inf_a, -jnp.inf, jnp.zeros((n,), loga.dtype))
    g0 = jnp.where(neg_inf_b, -jnp.inf, jnp.zeros((m,), logb.dtype))
    big = jnp.array(jnp.finfo(loga.dtype).max, loga.dtype)
    b_lin = jnp.exp(logb)

    def cond(state):
        t, err, since = state[2], state[3], state[5]
        return (err > tol) & (t < max_iter) & (since < patience)

    def body(state):
        f, g, t, _, best, since = state[:6]
        lr = lse_row(g)
        f_new = fe * eps * (loga - lr)
        f_new = jnp.where(neg_inf_a | jnp.isneginf(lr), -jnp.inf, f_new)
        lc = lse_col(f_new)
        g_new = fe * eps * (logb - lc)
        g_new = jnp.where(neg_inf_b | jnp.isneginf(lc), -jnp.inf, g_new)
        df = jnp.where(
            jnp.isneginf(f_new) & jnp.isneginf(f), 0.0, jnp.abs(f_new - f)
        )
        dg = jnp.where(
            jnp.isneginf(g_new) & jnp.isneginf(g), 0.0, jnp.abs(g_new - g)
        )
        err = jnp.max(df) + jnp.max(dg)
        col_marg = jnp.where(
            jnp.isneginf(g) | jnp.isneginf(lc), 0.0, jnp.exp(g / eps + lc)
        )
        marg = jnp.sum(jnp.abs(col_marg - b_lin))
        improved = marg < best * (1.0 - 1e-4)
        best = jnp.minimum(best, marg)
        since = jnp.where(improved, 0, since + 1)
        return (f_new, g_new, t + 1, err, best, since)

    init = (f0, g0, jnp.array(0, jnp.int32), big, big, jnp.array(0, jnp.int32))
    final = jax.lax.while_loop(cond, body, init)
    f, g, t, err, _, since = final[:6]
    return SinkhornResult(
        f, g, t, err, _log_domain_status(f, g, err, tol, since >= patience)
    )


def test_untraced_scaling_loop_jaxpr_identical_to_pre_trace():
    p = _problem()
    K = p.kernel()

    def current(K, a, b):
        return generic_scaling_loop(
            lambda v: K @ v, lambda u: K.T @ u, a, b, 1.0
        )

    def frozen(K, a, b):
        return _frozen_scaling_loop(
            lambda v: K @ v, lambda u: K.T @ u, a, b, 1.0
        )

    cur = str(jax.make_jaxpr(current)(K, p.a, p.b))
    ref = str(jax.make_jaxpr(frozen)(K, p.a, p.b))
    assert cur == ref


def test_untraced_log_loop_jaxpr_identical_to_pre_trace():
    p = _problem()
    logK = p.log_kernel()
    eps = float(p.eps)

    def lse_row(logK, g):
        return jax.scipy.special.logsumexp(logK + g[None, :] / eps, axis=1)

    def lse_col(logK, f):
        return jax.scipy.special.logsumexp(logK + f[:, None] / eps, axis=0)

    def current(logK, a, b):
        return generic_log_loop(
            lambda g: lse_row(logK, g), lambda f: lse_col(logK, f),
            _masked_log(a), _masked_log(b), eps, 1.0,
        )

    def frozen(logK, a, b):
        return _frozen_log_loop(
            lambda g: lse_row(logK, g), lambda f: lse_col(logK, f),
            _masked_log(a), _masked_log(b), eps, 1.0,
        )

    cur = str(jax.make_jaxpr(current)(logK, p.a, p.b))
    ref = str(jax.make_jaxpr(frozen)(logK, p.a, p.b))
    assert cur == ref


def test_untraced_sparse_log_loop_jaxpr_identical_to_pre_trace():
    p = _problem()
    logK = p.log_kernel()
    eps = float(p.eps)

    def lse_row(logK, g):
        return jax.scipy.special.logsumexp(logK + g[None, :] / eps, axis=1)

    def lse_col(logK, f):
        return jax.scipy.special.logsumexp(logK + f[:, None] / eps, axis=0)

    def current(logK, a, b):
        return generic_sparse_log_loop(
            lambda g: lse_row(logK, g), lambda f: lse_col(logK, f),
            _masked_log(a), _masked_log(b), eps, 1.0,
        )

    def frozen(logK, a, b):
        return _frozen_sparse_log_loop(
            lambda g: lse_row(logK, g), lambda f: lse_col(logK, f),
            _masked_log(a), _masked_log(b), eps, 1.0,
        )

    cur = str(jax.make_jaxpr(current)(logK, p.a, p.b))
    ref = str(jax.make_jaxpr(frozen)(logK, p.a, p.b))
    assert cur == ref


def test_untraced_batched_loops_return_no_trace_outputs():
    """The batched loops' trace=False carry stays the pre-telemetry 5-tuple
    (no extra jaxpr outputs, BatchedResult.trace is None)."""
    from repro.batch.problems import BatchedProblem
    from repro.batch.solvers import get_batched_solver

    bp = BatchedProblem.from_problems(
        [_problem(seed=i) for i in range(2)], bucket=(64, 64)
    )
    br = get_batched_solver("dense")(bp, None)
    assert br.trace is None
    br_log = get_batched_solver("log")(bp, None)
    assert br_log.trace is None


# --------------------------------------------------------------------------
# Trace correctness
# --------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["dense", "log"])
def test_trace_on_off_bitwise_parity(method):
    p = _problem()
    off = solve(p, method=method)
    on = solve(p, method=method, trace=True)
    assert bool(jnp.all(off.result.u == on.result.u))
    assert bool(jnp.all(off.result.v == on.result.v))
    assert int(off.n_iter) == int(on.n_iter)
    assert float(off.err) == float(on.err)
    assert off.diagnostics is None
    assert on.diagnostics is not None


def test_trace_contents_and_matvec_accounting():
    p = _problem()
    sol = solve(p, method="dense", trace=True)
    d = sol.diagnostics
    n_iter = int(sol.n_iter)
    assert 0 < n_iter < DEFAULT_TRACE_LEN
    assert d.n_matvec == 2 * n_iter
    errs, margs, first = trim_trace(d.trace, n_iter)
    assert first == 0 and d.first_traced_iteration == 0
    assert len(errs) == len(margs) == n_iter
    assert np.all(np.isfinite(errs)) and np.all(np.isfinite(margs))
    # the last ring record is the loop's final stopping-rule error
    assert errs[-1] == float(sol.err)
    # untouched ring slots stay NaN (never returned by trim_trace)
    raw = np.asarray(d.trace.err)
    assert np.all(np.isnan(raw[n_iter:]))
    assert float(errs[-1]) <= float(p.eps)  # it did make progress


def test_trace_ring_wraps_to_last_records():
    p = _problem()
    L = 3
    sol = solve(p, method="dense", trace=L, tol=1e-12, max_iter=50)
    d = sol.diagnostics
    n_iter = int(sol.n_iter)
    assert n_iter > L  # ring must actually wrap
    assert d.trace.trace_len == L
    errs, _, first = trim_trace(d.trace, n_iter)
    assert len(errs) == L
    assert first == n_iter - L == d.first_traced_iteration
    assert errs[-1] == float(sol.err)
    # full solve's tail must match the wrapped ring record-for-record
    full = solve(p, method="dense", trace=True, tol=1e-12, max_iter=50)
    tail = trim_trace(full.diagnostics.trace, n_iter)[0][-L:]
    np.testing.assert_array_equal(errs, tail)


@pytest.mark.parametrize("method", ["spar_sink_coo", "spar_sink_log"])
def test_sparse_trace_and_sketch_diagnostics(method):
    p = _problem(eps=0.5)
    key = jax.random.PRNGKey(0)
    off = solve(p, method=method, key=key, s=8.0)
    on = solve(p, method=method, key=key, s=8.0, trace=True)
    assert bool(jnp.all(off.result.u == on.result.u))
    d = on.diagnostics
    assert d.n_matvec == 2 * int(on.n_iter)
    sk = d.sketch
    assert sk is not None
    assert int(sk.nnz) == int(on.nnz)
    assert float(sk.fill) == pytest.approx(int(sk.nnz) / sk.cap)
    assert 0.0 < float(sk.ess) <= int(sk.nnz) + 1e-6
    assert 0.0 < float(sk.ess_ratio) <= 1.0 + 1e-6
    assert not bool(sk.overflowed)
    # Bernoulli draw: every proposal is accepted, truncation-only merging
    assert float(sk.acceptance_rate) == pytest.approx(1.0)
    assert 0.0 <= float(sk.dup_merge_rate) < 1.0
    assert "sketch" in d.summary()


def test_sketch_diagnostics_direct_values():
    from repro.core.sparsify import SparseKernelCOO

    vals = jnp.asarray([2.0, 2.0, 2.0, 2.0, 0.0])  # equal weights: ESS = nnz
    sk = SparseKernelCOO(
        rows=jnp.asarray([0, 0, 1, 2, 2], jnp.int32),
        cols=jnp.asarray([0, 1, 0, 1, 0], jnp.int32),
        vals=vals,
        nnz=jnp.asarray(4, jnp.int32),
        n=3,
        m=2,
        overflowed=jnp.asarray(False),
        n_proposed=jnp.asarray(8, jnp.int32),
        n_accepted=jnp.asarray(5, jnp.int32),
    )
    st = sketch_diagnostics(sk)
    assert int(st.nnz) == 4 and st.cap == 5
    assert float(st.fill) == pytest.approx(4 / 5)
    assert float(st.ess) == pytest.approx(4.0)  # equal weights
    assert float(st.ess_ratio) == pytest.approx(1.0)
    assert float(st.acceptance_rate) == pytest.approx(1.0)  # 5 of min(8, cap=5)
    assert float(st.dup_merge_rate) == pytest.approx(1.0 - 4 / 5)


def test_batched_trace_sliced_per_problem():
    from repro.batch import BucketedExecutor

    problems = [_problem(seed=i) for i in range(3)]
    keys = list(jax.random.split(jax.random.PRNGKey(0), 3))
    ex = BucketedExecutor(metrics=MetricsRegistry())
    sols = ex.solve_batch(
        problems, method="spar_sink_log", keys=keys, s=8.0, trace=True
    )
    for sol in sols:
        d = sol.diagnostics
        assert d is not None and d.trace.err.ndim == 1
        assert d.n_matvec == 2 * int(sol.n_iter)
        errs = d.iteration_errors()
        assert len(errs) == min(int(sol.n_iter), DEFAULT_TRACE_LEN)
        assert errs[-1] == float(sol.err)
    # problems converge at different iteration counts -> per-element freeze
    # must give each its own counter (not the batch maximum)
    iters = [int(s.n_iter) for s in sols]
    matvecs = [s.diagnostics.n_matvec for s in sols]
    assert matvecs == [2 * t for t in iters]
    # and the untraced dispatch carries no diagnostics
    offs = ex.solve_batch(problems, method="spar_sink_log", keys=keys, s=8.0)
    assert all(s.diagnostics is None for s in offs)


def test_batched_vs_per_problem_trace_parity():
    """spar_sink_log runs the same B-invariant kernel per-problem and
    batched, so the *trace* rings agree bitwise too."""
    from repro.batch import BucketedExecutor

    p = _problem(n=64, m=64)  # bucket-sized: no padding difference
    key = jax.random.PRNGKey(3)
    single = solve(p, method="spar_sink_log", key=key, s=8.0, trace=True)
    ex = BucketedExecutor(metrics=MetricsRegistry(), min_bucket=64)
    batched = ex.solve_batch(
        [p], method="spar_sink_log", keys=[key], s=8.0, trace=True
    )[0]
    np.testing.assert_array_equal(
        np.asarray(single.diagnostics.trace.err),
        np.asarray(batched.diagnostics.trace.err),
    )
    assert single.diagnostics.n_matvec == batched.diagnostics.n_matvec


# --------------------------------------------------------------------------
# MetricsRegistry
# --------------------------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("c")
    reg.counter("c", 2.5)
    reg.gauge("g", 7.0)
    for v in range(1, 101):
        reg.observe("h", float(v))
    assert reg.get_counter("c") == 3.5
    assert reg.get_gauge("g") == 7.0
    h = reg.get_histogram("h")
    assert h["count"] == 100 and h["sum"] == pytest.approx(5050.0)
    assert h["mean"] == pytest.approx(50.5)
    # linear-interpolated quantiles over 1..100
    assert h["p50"] == pytest.approx(50.5)
    assert h["p95"] == pytest.approx(95.05)
    assert h["p99"] == pytest.approx(99.01)
    # unknown names read as empty, not KeyError
    assert reg.get_counter("nope") == 0.0
    assert reg.get_histogram("nope")["count"] == 0


def test_registry_histogram_window_bounded():
    from repro.obs import HISTOGRAM_WINDOW

    reg = MetricsRegistry()
    n = HISTOGRAM_WINDOW + 500
    for v in range(n):
        reg.observe("h", float(v))
    h = reg.get_histogram("h")
    assert h["count"] == n  # lifetime count keeps running
    assert h["sum"] == pytest.approx(n * (n - 1) / 2)
    # quantiles come from the last HISTOGRAM_WINDOW samples only
    assert h["p50"] >= 500.0


def test_registry_reset_prefix_and_locked():
    reg = MetricsRegistry()
    reg.counter("serve.requests", 5)
    reg.counter("executor.cache_hit", 2)
    reg.observe("serve.latency_seconds", 0.1)
    with reg.locked():
        reg.reset("serve.")
        assert reg.get_counter("serve.requests") == 0.0
    assert reg.get_counter("executor.cache_hit") == 2.0
    assert reg.get_histogram("serve.latency_seconds")["count"] == 0


def test_registry_thread_safety():
    reg = MetricsRegistry()

    def work(i):
        for j in range(1000):
            reg.counter("c")
            # alternate across two buckets so cumulative counts are exercised
            reg.observe("h", 0.001 if (i + j) % 2 else 0.3)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.get_counter("c") == 8000.0
    assert reg.get_histogram("h")["count"] == 8000
    # cumulative bucket counts must be monotone and account for every sample
    snap = reg.snapshot(include_buckets=True)
    buckets = dict(snap["histograms"]["h"]["buckets"])
    assert buckets[0.001] == 4000
    assert buckets[0.5] == 8000
    cum = [c for _, c in sorted(buckets.items())]
    assert cum == sorted(cum)


def test_export_json_and_prometheus():
    reg = MetricsRegistry()
    reg.counter("executor.cache_hit", 3)
    reg.gauge("serve.queue_depth", 2)
    reg.observe("serve.latency_seconds", 0.25)
    rows = json.loads(export("json", reg))
    by_name = {r["metric"]: r for r in rows}
    assert by_name["executor.cache_hit"] == {
        "metric": "executor.cache_hit", "type": "counter", "value": 3.0
    }
    assert by_name["serve.latency_seconds"]["type"] == "histogram"
    assert by_name["serve.latency_seconds"]["p99"] == pytest.approx(0.25)
    text = export("prometheus", reg)
    assert "# TYPE executor_cache_hit counter" in text
    # real histogram exposition: cumulative le-buckets + sum/count, with the
    # windowed-exact quantiles kept as a companion gauge family
    assert "# TYPE serve_latency_seconds histogram" in text
    assert 'serve_latency_seconds_bucket{le="0.25"} 1' in text
    assert 'serve_latency_seconds_bucket{le="0.1"} 0' in text
    assert 'serve_latency_seconds_bucket{le="+Inf"} 1' in text
    assert 'serve_latency_seconds_quantile{quantile="0.99"} 0.25' in text
    assert "serve_latency_seconds_count 1" in text
    # json rows keep the pre-bucket shape (no "buckets" key)
    assert "buckets" not in by_name["serve.latency_seconds"]
    with pytest.raises(ValueError):
        export("xml", reg)


# --------------------------------------------------------------------------
# Phase spans of the Spar-Sink solves
# --------------------------------------------------------------------------

PHASES = ("spar_sink.solve", "spar_sink.sketch", "spar_sink.loop", "spar_sink.objective")


def _cloud(n=64, seed=0):
    rng = np.random.default_rng(seed)
    a = jnp.full((n,), 1.0 / n)
    return OTProblem(PointCloudGeometry(jnp.asarray(rng.uniform(size=(n, 3)))), a, a, 0.1)


@pytest.mark.parametrize("certify", [False, True])
@pytest.mark.parametrize("method,opts", [("spar_sink_mf", {"stabilize": False}),
                                         ("spar_sink_mf", {"stabilize": True}),
                                         ("spar_sink_log", {})])
def test_spar_sink_phases_record_once_into_injected_registry(method, opts, certify,
                                                            monkeypatch):
    import repro.obs.spans

    reg = MetricsRegistry()
    monkeypatch.setattr(repro.obs.spans, "default_registry", reg)
    solve(_cloud(), method=method, key=jax.random.PRNGKey(0), s=2000.0, certify=certify,
          **opts)
    counts = {n: h["count"] for n, h in reg.snapshot()["histograms"].items()}
    want = PHASES + (("spar_sink.certify",) if certify else ())
    assert counts == {f"{p}_seconds": 1 for p in want}


def test_stabilized_solve_counts_the_sorted_scan_loop(monkeypatch):
    """A sorted sketch's loop takes the scan, and says so once a call."""
    import repro.batch.solvers

    reg = MetricsRegistry()
    monkeypatch.setattr(repro.batch.solvers, "default_registry", reg)
    solve(_cloud(), method="spar_sink_mf", stabilize=True, key=jax.random.PRNGKey(0),
          s=2000.0)
    assert reg.get_counter("spar_sink.loop_sorted_scan") == 1
    assert reg.get_counter("spar_sink.loop_scatter") == 0


def test_spar_sink_spans_change_no_result_and_nest_in_the_trace(tmp_path):
    from jax.profiler import ProfileData

    p = _cloud()

    def run():
        sol = solve(p, method="spar_sink_mf", stabilize=True, key=jax.random.PRNGKey(1),
                    s=2000.0)
        return jax.block_until_ready((sol.value, *sol.potentials))

    off = run()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        on = run()
    finally:
        jax.profiler.stop_trace()
    for x, y in zip(off, on):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    pd = ProfileData.from_file(str(next(tmp_path.glob("**/*.xplane.pb"))))
    spans = [(line.name, ev.start_ns, ev.end_ns, ev.name)
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("spar_sink.")]
    assert sorted(s[3] for s in spans) == sorted(PHASES)
    (outer,) = [s for s in spans if s[3] == "spar_sink.solve"]
    for line, t0, t1, _ in spans:
        assert line == outer[0] and outer[1] <= t0 <= t1 <= outer[2]


# --------------------------------------------------------------------------
# Executor + serving instrumentation
# --------------------------------------------------------------------------


def test_executor_metrics():
    from repro.batch import BucketedExecutor

    reg = MetricsRegistry()
    ex = BucketedExecutor(metrics=reg)
    problems = [_problem(seed=i) for i in range(3)]
    ex.solve_batch(problems, method="dense")
    assert reg.get_counter("executor.cache_miss") == 1.0
    assert reg.get_counter("executor.retrace") == 1.0
    assert reg.get_counter("executor.cache_hit") == 0.0
    ex.solve_batch(problems, method="dense")
    assert reg.get_counter("executor.cache_hit") == 1.0
    assert reg.get_counter("executor.cache_miss") == 1.0
    occ = reg.get_histogram("executor.bucket_occupancy")
    waste = reg.get_histogram("executor.padding_waste")
    assert occ["count"] == waste["count"] == 2
    # 3 problems pad to B=4 -> occupancy 0.75; waste strictly positive
    assert occ["p50"] == pytest.approx(0.75)
    assert 0.0 < waste["p50"] < 1.0
    assert reg.get_histogram("executor.dispatch_seconds")["count"] == 2
    assert reg.get_gauge("executor.cache_entries") == 1.0


def test_server_stats_quantiles_and_atomic_reset():
    from repro.launch.serve_ot import OTServer

    reg = MetricsRegistry()
    from repro.batch import BucketedExecutor

    server = OTServer(
        BucketedExecutor(metrics=reg), max_batch=4, deadline_s=0.005
    )
    problems = [_problem(seed=i) for i in range(6)]
    with server:
        futures = [server.submit(p, method="dense") for p in problems]
        sols = [f.result() for f in futures]
    assert all(s.value == s.value for s in sols)  # all resolved, no NaN
    st = server.stats()
    assert st["requests"] == 6 and server.requests_served == 6
    assert st["batches"] == server.batches_dispatched >= 2
    assert 0 < st["p50_latency_s"] <= st["p95_latency_s"] <= st["p99_latency_s"]
    assert reg.get_histogram("serve.latency_seconds")["count"] == 6
    assert reg.get_histogram("serve.batch_fill")["count"] == st["batches"]
    assert reg.get_counter("serve.requests") == 6.0
    server.reset_stats()
    st2 = server.stats()
    assert st2["requests"] == 0 and st2["batches"] == 0
    assert st2["p50_latency_s"] == 0.0
    # executor-side metrics survive a serving-stats reset
    assert reg.get_counter("executor.cache_miss") >= 1.0


# --------------------------------------------------------------------------
# Status propagation through composite paths
# --------------------------------------------------------------------------


def test_divergence_with_status():
    from repro.core.divergence import sinkhorn_divergence

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(24, 2)))
    y = jnp.asarray(rng.normal(size=(20, 2)))
    a = jnp.asarray(rng.dirichlet(np.ones(24)))
    b = jnp.asarray(rng.dirichlet(np.ones(20)))
    v, st = sinkhorn_divergence(x, y, a, b, 0.5, with_status=True)
    assert int(st) == STATUS_CONVERGED
    v_plain = sinkhorn_divergence(x, y, a, b, 0.5)
    assert float(v) == float(v_plain)
    # one starved term taints the whole divergence with the worst code
    _, st_bad = sinkhorn_divergence(
        x, y, a, b, 0.5, with_status=True, max_iter=2, tol=1e-13
    )
    assert int(st_bad) == STATUS_MAX_ITER


def test_barycenter_status():
    from repro.core.barycenter import ibp, solve_barycenter

    rng = np.random.default_rng(0)
    n, mm = 32, 3
    x = np.linspace(0.0, 1.0, n)[:, None]
    C = jnp.asarray((x - x.T) ** 2)
    K = jnp.exp(-C / 0.05)
    bs = jnp.asarray(rng.dirichlet(np.ones(n), size=mm))
    w = jnp.ones(mm) / mm
    res = ibp(K, bs, w, tol=1e-8, max_iter=5000)
    assert int(res.status) == STATUS_CONVERGED and bool(res.converged)
    capped = ibp(K, bs, w, tol=1e-13, max_iter=3)
    assert int(capped.status) == STATUS_MAX_ITER and not bool(capped.converged)
    front = solve_barycenter(C, bs, w, 0.05, tol=1e-8, max_iter=5000)
    assert int(front.status) == STATUS_CONVERGED


def test_screenkhorn_restricted_solve_status():
    p = _problem()
    sol = solve(p, method="screenkhorn_lite")
    assert sol.status is not None
    assert bool(sol.converged)
    assert sol.status_label == "converged"
    capped = solve(p, method="screenkhorn_lite", tol=1e-13, max_iter=2)
    assert not bool(capped.converged)
    assert int(capped.status) == STATUS_MAX_ITER
