"""The trace reduction: interval arithmetic on hand-made events, and the
reading of a real trace recorded on the CPU from a tiny solve()."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import trace_reduce as tr


def test_union_and_gaps_of_overlapping_intervals():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6), (6.0, 7.0)]
    assert list(zip(*tr.merge(iv))) == [(0.0, 2.0), (3.0, 4.0), (6.0, 7.0)]
    assert tr.union_length(iv) == pytest.approx(4.0)
    assert tr.union_length(iv, 1.0, 6.5) == pytest.approx(1.0 + 1.0 + 0.5)
    assert tr.gaps(iv, -1.0, 8.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 6.0), (7.0, 8.0)]


def test_summary_of_hand_made_trace():
    # two chips; the second is busy half as long; host spans name the gaps
    ops0 = [(10.0, 12.0, "fusion"), (11.0, 13.0, "scatter"), (15.0, 16.0, "fusion")]
    ops1 = [(10.0, 11.0, "fusion"), (15.0, 15.5, "fusion")]
    mods0 = [(10.0, 13.0, "jit_while"), (15.0, 16.0, "jit_objective")]
    mods1 = [(10.0, 11.0, "jit_while"), (15.0, 15.5, "jit_objective")]
    host = [(10.0, 16.0, "bench.solve"), (13.0, 15.0, "PjitFunction(sort)"),
            (9.0, 17.0, "outer thread event")]
    s = tr.summarize([{"ops": ops0, "modules": mods0}, {"ops": ops1, "modules": mods1}], host)
    assert s["window_s"] == pytest.approx(6.0)
    assert s["busy_s"] == pytest.approx((4.0 + 1.5) / 2)
    assert s["programs"]["jit_while"] == pytest.approx(2.0)
    assert s["program_counts"]["jit_while"] == 2
    assert s["top_ops"][0][0] == "fusion"
    assert s["idle_gaps"] == [["bench.solve: PjitFunction(sort)", pytest.approx(2.0)]]


def test_reads_a_trace_recorded_on_the_cpu(tmp_path):
    from repro.core import OTProblem, PointCloudGeometry, solve

    rng = np.random.default_rng(0)
    n = 64
    x = jnp.asarray(rng.uniform(size=(n, 3)), jnp.float32)
    a = jnp.full((n,), 1.0 / n, jnp.float32)
    problem = OTProblem(PointCloudGeometry(x), a, a, 0.1)
    run = lambda k: solve(problem, method="spar_sink_mf", stabilize=True,  # noqa: E731
                          key=jax.random.PRNGKey(k), s=2000.0)
    float(run(0).value)  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.solve"):
        float(run(1).value)
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    device_lines, host = tr.read(path)
    assert device_lines == []  # the CPU has no device plane
    spans = [ev for ev in host if ev[2] == "bench.solve"]
    assert len(spans) == 1 and spans[0][1] > spans[0][0]
    # on the CPU, XLA's thunks run on host threads: read them as one chip's ops
    ops = [ev for ev in host if ev[0] >= spans[0][0] and ev[1] <= spans[0][1]
           and not ev[2].startswith("bench.")]
    s = tr.summarize([{"ops": ops, "modules": []}], host)
    assert s["window_s"] == pytest.approx(spans[0][1] - spans[0][0])
    assert 0.0 < s["busy_s"] <= s["window_s"]
    assert s["top_ops"] and len(s["top_ops"]) <= 10
    assert sum(v for _, v in s["idle_gaps"]) == pytest.approx(s["window_s"] - s["busy_s"])
    assert tr.reduce(tmp_path)["spans"]["bench.solve"] == pytest.approx(s["window_s"])
