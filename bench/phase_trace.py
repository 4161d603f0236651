"""Device time, program count and device idle time of each of the
program's own phase spans, from the same profiler trace that
`bench.trace_reduce` reads.

The program opens host spans named ``spar_sink.*`` at the phases of a
solve (`repro.obs.span`): ``spar_sink.solve`` around ``spar_sink.sketch``,
``spar_sink.loop``, ``spar_sink.objective`` and ``spar_sink.certify``.

* A device program execution (an ``XLA Modules`` event) belongs to the
  innermost program span that *launched* it, not to the span open while it
  ran: the eager solve launches the objective's programs while the loop
  still runs, so they execute after the host has left the objective's span.
  The launch is found through the profiler's own correlation: the module
  carries ``run_id`` and a flow id ``_c``; host events carry the same
  ``run_id``, or the flow id as ``_p``. From those, flows are followed back
  through the host events that enclose them (``_c`` to the ``_p`` of the
  event that caused it) to the earliest: on the TPU the module's
  ``DoEnqueueProgram``, run on a worker thread when its inputs were not yet
  ready, leads back to the Python thread's ``PJRT_LoadedExecutable_Execute``
  call. Where some module has no such link, the k-th launch event
  (`LAUNCH_EVENT`) in the window is taken as the k-th module execution, on
  one chip and only if the two counts agree; otherwise nothing is
  attributed.
* An idle gap of the device belongs to the innermost program span that
  covers its midpoint, as `bench.trace_reduce` names gaps by ``bench.*``
  spans.

A trace of a program without such spans gives no reduction (None), and so
does a trace with no device plane.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from functools import lru_cache
from pathlib import Path

import numpy as np

from bench import trace_reduce

PROGRAM_PREFIX = "spar_sink."
#: the TPU client's host event that launches one program on one device
LAUNCH_EVENT = "CommonPjRtLoadedExecutable::ExecuteHelperOnSingleDevice"
#: stats that join host and device events: correlation id, flow out, flow in
STATS = ("run_id", "_p", "_c")
#: the most flows followed back from a module to its launch
HOPS = 4


class _Host:
    """Host events as columns, with the stats that join them to modules."""

    def __init__(self, events):
        """``events``: ``(start, end, name, line, stats)``; ``stats`` holds
        any of `STATS`."""
        self.s = np.asarray([ev[0] for ev in events], float)
        self.e = np.asarray([ev[1] for ev in events], float)
        self.line = np.asarray([ev[3] for ev in events], int)
        self.by_run, self.by_flow, flow_in = defaultdict(list), {}, defaultdict(list)
        for i, ev in enumerate(events):
            st = ev[4]
            if "run_id" in st:
                self.by_run[st["run_id"]].append(i)
            if "_p" in st:
                self.by_flow[st["_p"]] = i
            if "_c" in st:
                flow_in[ev[3]].append((i, st["_c"]))
        # per line, the events that carry an incoming flow
        self.flow_in = {line: (np.asarray([i for i, _ in v]), [c for _, c in v])
                        for line, v in flow_in.items()}

    def causes(self, i: int) -> list[int]:
        """The producers of the flows into ``i`` and into the events of its
        line that enclose it."""
        if self.line[i] not in self.flow_in:
            return []
        idx, flows = self.flow_in[self.line[i]]
        hit = np.flatnonzero((self.s[idx] <= self.s[i]) & (self.e[idx] >= self.e[i]))
        return [self.by_flow[flows[k]] for k in hit if flows[k] in self.by_flow]

    def launch(self, run_id, flow) -> float | None:
        """Start of the earliest host event reached back from a module with
        correlation ``run_id`` and incoming flow ``flow``."""
        seen = set(self.by_run.get(run_id, ()))
        if flow in self.by_flow:
            seen.add(self.by_flow[flow])
        frontier = list(seen)
        for _ in range(HOPS):
            frontier = [j for i in frontier for j in self.causes(i) if j not in seen]
            if not frontier:
                break
            seen.update(frontier)
        return float(min(self.s[i] for i in seen)) if seen else None


def summarize(device_lines, host_events) -> dict | None:
    """The reduction of already-read events, or None where the trace has no
    device plane or no program span.

    ``device_lines``: one ``{"ops": [(start, end, name)], "modules":
    [(start, end, name, run_id, flow)]}`` per chip, modules in device order.
    ``host_events``: ``(start, end, name, line, stats)``. Times in seconds.
    The window is `bench.trace_reduce`'s: the ``bench.*`` spans, where
    there are any. Device time and program counts are averaged over chips,
    as in `bench.trace_reduce`; they are None where no join holds."""
    spans = [h for h in host_events if h[2].startswith(PROGRAM_PREFIX)]
    if not device_lines or not spans:
        return None
    bench = [h for h in host_events if h[2].startswith(trace_reduce.SPAN_PREFIX)]
    edges = bench or [op for dev in device_lines for op in dev["ops"]]
    lo, hi = min(h[0] for h in edges), max(h[1] for h in edges)
    sp = trace_reduce._Events([h[:3] for h in spans])
    chips = len(device_lines)

    host = _Host(host_events)
    launch_starts = sorted(h[0] for h in host_events
                           if h[2] == LAUNCH_EVENT and lo <= h[0] <= hi)
    device_s, programs, join = defaultdict(float), defaultdict(float), None
    for dev in device_lines:
        mods = [m for m in dev["modules"] if m[1] > lo and m[0] < hi]
        times = [host.launch(m[3], m[4]) for m in mods]
        if all(t is not None for t in times):
            join = "correlation"
        elif chips == 1 and len(launch_starts) == len(mods):
            times, join = launch_starts, "launch order"
        else:
            device_s = programs = join = None
            break
        for m, t in zip(mods, times):
            name = sp.innermost(t, t)
            if name is not None:
                device_s[name] += (min(m[1], hi) - max(m[0], lo)) / chips
                programs[name] += 1 / chips
    idle_s = defaultdict(float)
    for g0, g1 in trace_reduce.gaps([op[:2] for op in device_lines[0]["ops"]], lo, hi):
        name = sp.innermost(0.5 * (g0 + g1), 0.5 * (g0 + g1))
        if name is not None:
            idle_s[name] += g1 - g0
    return {
        "spans": {n: sum(h[1] - h[0] for h in spans if h[2] == n) for n in {h[2] for h in spans}},
        "span_device_s": None if device_s is None else dict(device_s),
        "span_programs": None if programs is None else dict(programs),
        "span_idle_s": dict(idle_s),
        "join": join,
    }


def read(path: str | Path):
    """``(device_lines, host_events)`` of one ``.xplane.pb``, with the
    stats `summarize` joins on."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    device_lines, host_events, line_no = [], [], 0
    for plane in pd.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    dev["ops"] = [(ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                                  for ev in line.events]
                elif line.name == trace_reduce.MODULES_LINE:
                    for ev in line.events:
                        st = dict(ev.stats)
                        dev["modules"].append((ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name,
                                               st.get("run_id"), st.get("_c")))
            dev["modules"].sort(key=lambda m: m[0])
            device_lines.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    st = {k: v for k, v in ev.stats if k in STATS}
                    host_events.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name,
                                        line_no, st))
                line_no += 1
    return device_lines, host_events


@lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int) -> dict | None:
    return summarize(*read(path))


def of_run(run, reader_file: str) -> dict | None:
    """The reduction of a traced run's trace, or None where the run was not
    traced or completed no call. ``reader_file`` is the calling reader's
    ``__file__``: the trace lies under its checkout's ``bench/_out/trace``,
    where the harness wrote it."""
    if not run.trace or not run.record.get("calls"):
        return None
    trace_dir = Path(reader_file).resolve().parents[1] / "_out" / "trace"
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        return None
    return _reduce_file(files[-1], os.stat(files[-1]).st_mtime_ns)


def per_solve(run, reader_file: str, key: str, span: str, scale: float = 1.0):
    """``scale`` times the reduction's ``key`` of ``span``, over the run's
    solves; None where the trace holds no such span or no join held."""
    phases = of_run(run, reader_file)
    if phases is None or span not in phases["spans"] or phases[key] is None:
        return None
    return scale * phases[key].get(span, 0.0) / len(run.record["calls"])
