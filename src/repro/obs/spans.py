"""Host spans at the phase boundaries of a solve.

``with span("spar_sink.sketch"):`` does two things:

* it writes a ``jax.profiler.TraceAnnotation`` of that name, on the
  profiler's host plane and on the same clock as the device planes, so a
  trace can put the device programs launched inside it, and the device's
  idle time while it was open, on the phase (spans opened inside it on the
  same thread are its children);
* on exit it records the span's host seconds into a `MetricsRegistry` as
  the histogram ``<name>_seconds`` (`default_registry` unless one is
  injected), which ``export("prometheus")`` renders as
  ``spar_sink_sketch_seconds``.

There is no switch: with no profiler running a span costs one ``TraceMe``
and one registry observation. A span never reads a device value, so it
adds no device-to-host transfer and no wait: the host seconds it records
are dispatch time, not device time, wherever the phase does not itself
wait for the device.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Iterator

import jax

from repro.obs.metrics import MetricsRegistry, default_registry

__all__ = ["span"]


@contextmanager
def span(name: str, registry: MetricsRegistry | None = None) -> Iterator[None]:
    """Open a profiler host span ``name`` and record its host seconds into
    ``registry`` (default `default_registry`) as ``<name>_seconds``."""
    reg = default_registry if registry is None else registry
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        reg.observe(f"{name}_seconds", time.perf_counter() - t0)
