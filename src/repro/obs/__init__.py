"""repro.obs — observability: jit-safe solver traces + host-side metrics.

Four layers (see README "Observability"):

* `trace`: fixed-size ring-buffer iteration telemetry carried through the
  ``lax.while_loop`` solver cores (`SolverTrace`), sketch-quality stats
  (`SketchStats`), and the per-solve `Diagnostics` record surfaced as
  ``Solution.diagnostics``. Enable with ``solve(..., trace=True)``; the
  ``trace=False`` default is zero-overhead (identical jaxprs, guarded by
  tests).
* `certify`: a posteriori solution-quality certificates (`Certificate`) —
  duality gap, marginal-violation error bound, and importance-sampling
  confidence interval — computed in O(nnz + n) from converged potentials.
  Enable with ``solve(..., certify=True)``; the ``certify=False`` default
  is zero-overhead (identical jaxprs, guarded by tests).
* `metrics`: a thread-safe `MetricsRegistry` (counters / gauges /
  p50-p95-p99 histograms) instrumenting `BucketedExecutor` and
  ``serve_ot``'s `OTServer`; `export` renders JSON events or
  Prometheus text (cumulative ``_bucket`` histogram exposition).
* `spans`: `span`, a host span at a phase boundary of a solve. The
  Spar-Sink solvers (``spar_sink_mf``, ``spar_sink_log``) open
  ``spar_sink.solve`` and inside it ``spar_sink.sketch``,
  ``spar_sink.loop``, ``spar_sink.objective`` and, with ``certify=True``,
  ``spar_sink.certify``. Each is a ``jax.profiler.TraceAnnotation``, so a
  profiler trace puts device programs and device idle time on the phase
  that launched them, and each records its host seconds into
  `default_registry` as the histogram ``<name>_seconds``:
  ``spar_sink_sketch_seconds`` and so on in ``export("prometheus")``.
* profiling: ``tools/profile_solve.py`` compiles any registered method and
  reports XLA cost-analysis flops/bytes per iteration; the on-chip
  benchmark (``bench/``, ``BENCHMARK.json``) reduces a profiler trace of
  the solve to per-phase device time and idle time.
"""
from repro.obs.certify import (
    DEFAULT_Z,
    Certificate,
    dense_certificate,
    importance_ess,
    sparse_certificate,
)
from repro.obs.metrics import (
    DEFAULT_BUCKETS,
    HISTOGRAM_WINDOW,
    MetricsRegistry,
    default_registry,
    export,
)
from repro.obs.spans import span
from repro.obs.trace import (
    DEFAULT_TRACE_LEN,
    Diagnostics,
    SketchStats,
    SolverTrace,
    empty_trace,
    record_iteration,
    resolve_trace_len,
    sketch_diagnostics,
    trim_trace,
)

__all__ = [
    "Certificate",
    "DEFAULT_BUCKETS",
    "DEFAULT_TRACE_LEN",
    "DEFAULT_Z",
    "Diagnostics",
    "HISTOGRAM_WINDOW",
    "MetricsRegistry",
    "SketchStats",
    "SolverTrace",
    "default_registry",
    "dense_certificate",
    "empty_trace",
    "export",
    "importance_ess",
    "record_iteration",
    "resolve_trace_len",
    "sketch_diagnostics",
    "span",
    "sparse_certificate",
    "trim_trace",
]
