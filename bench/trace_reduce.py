"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

* device busy time: the union of the intervals in which an operation runs
  on a chip (its ``XLA Ops`` line), averaged over the chips;
* per-program device time: the durations of each compiled program's
  executions (its ``XLA Modules`` line), by program name;
* per-op device time, for ``breakdown``;
* idle gaps: the stretches of the traced window with no operation on the
  device, each named by the host spans that cover it: the benchmark's own
  ``bench.*`` annotation and the innermost host event around the gap.

The traced window runs from the first to the last ``bench.*`` host span
(the window's own work), or over the device events where there is none.
"""
from __future__ import annotations

import glob
from collections import defaultdict
from pathlib import Path

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
SHORT_GAP_S = 1e-4


def merge(intervals):
    """Sorted, non-overlapping union of ``(start, end)`` intervals, as two
    arrays."""
    iv = np.asarray(sorted(intervals), float).reshape(-1, 2)
    if not len(iv):
        return iv[:, 0], iv[:, 1]
    s, e = iv[:, 0], np.maximum.accumulate(iv[:, 1])
    new = np.concatenate([[True], s[1:] > e[:-1]])
    idx = np.flatnonzero(new)
    return s[idx], np.concatenate([e[idx[1:] - 1], e[-1:]])


def clip(starts, ends, lo, hi):
    keep = (ends > lo) & (starts < hi)
    return np.maximum(starts[keep], lo), np.minimum(ends[keep], hi)


def union_length(intervals, lo=None, hi=None) -> float:
    s, e = merge(intervals)
    if lo is not None:
        s, e = clip(s, e, lo, hi)
    return float(np.sum(e - s))


def gaps(busy, lo, hi):
    """Idle stretches of ``[lo, hi]`` between merged ``busy`` intervals."""
    s, e = clip(*merge(busy), lo, hi)
    g0 = np.concatenate([[lo], e])
    g1 = np.concatenate([s, [hi]])
    keep = g1 > g0
    return list(zip(g0[keep].tolist(), g1[keep].tolist()))


class _Events:
    """Host events as arrays, for labelling gaps."""

    def __init__(self, events):
        self.s = np.asarray([ev[0] for ev in events], float)
        self.e = np.asarray([ev[1] for ev in events], float)
        self.names = [ev[2] for ev in events]

    def innermost(self, t0: float, t1: float) -> str | None:
        """The shortest event that covers ``[t0, t1]``."""
        hit = np.flatnonzero((self.s <= t0) & (self.e >= t1))
        if not hit.size:
            return None
        return self.names[hit[np.argmin(self.e[hit] - self.s[hit])]]


def label(gap, spans: _Events, host: _Events) -> str:
    """What the host was doing in ``gap``: the innermost ``bench.*`` span
    covering its midpoint, and the innermost other host event that covers
    the whole gap."""
    mid = 0.5 * (gap[0] + gap[1])
    return f"{spans.innermost(mid, mid) or 'outside'}: {host.innermost(*gap) or 'idle'}"


def program_name(module: str) -> str:
    """A compiled program's name without its fingerprint:
    ``jit_while(1038...)`` -> ``jit_while``."""
    return module.split("(", 1)[0]


def op_name(op: str) -> str:
    """An operation's name without its HLO signature:
    ``%while = (f32[1,16384]...) while(...)`` -> ``%while``."""
    return op.split(" = ", 1)[0]


def summarize(device_lines, host_events, top: int = 10) -> dict:
    """The reduction of already-read events.

    ``device_lines``: one ``{"ops": [(start, end, name)], "modules": [...]}``
    per chip. ``host_events``: ``(start, end, name)`` of every host event.
    Times in seconds. Gaps shorter than ``SHORT_GAP_S`` are summed under one
    label instead of being named one by one."""
    spans = [ev for ev in host_events if ev[2].startswith(SPAN_PREFIX)]
    all_ops = [ev for dev in device_lines for ev in dev["ops"]]
    if spans:
        lo, hi = min(s for s, _, _ in spans), max(e for _, e, _ in spans)
    elif all_ops:
        lo, hi = min(s for s, _, _ in all_ops), max(e for _, e, _ in all_ops)
    else:
        lo = hi = 0.0
    chips = max(1, len(device_lines))
    busy = sum(union_length([(s, e) for s, e, _ in dev["ops"]], lo, hi)
               for dev in device_lines) / chips
    programs, counts, ops = defaultdict(float), defaultdict(int), defaultdict(float)
    for dev in device_lines:
        for s, e, name in dev["modules"]:
            if e > lo and s < hi:
                programs[program_name(name)] += (min(e, hi) - max(s, lo)) / chips
                counts[program_name(name)] += 1
        for s, e, name in dev["ops"]:
            ops[op_name(name)] += (e - s) / chips
    by_label = defaultdict(float)
    if device_lines:
        span_ev = _Events(spans)
        host_ev = _Events([ev for ev in host_events if not ev[2].startswith(SPAN_PREFIX)])
        for g in gaps([(s, e) for s, e, _ in device_lines[0]["ops"]], lo, hi):
            if g[1] - g[0] < SHORT_GAP_S:
                by_label[f"gaps under {SHORT_GAP_S * 1e6:g} us"] += g[1] - g[0]
            else:
                by_label[label(g, span_ev, host_ev)] += g[1] - g[0]
    return {
        "window_s": hi - lo,
        "busy_s": busy,
        "programs": dict(programs),
        "program_counts": dict(counts),
        "spans": {name: sum(e - s for s, e, n in spans if n == name)
                  for name in {n for _, _, n in spans}},
        "top_ops": sorted(([k, v] for k, v in ops.items()), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in by_label.items()), key=lambda kv: -kv[1])[:top],
    }


def read(path: str | Path):
    """``(device_lines, host_events)`` of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    device_lines, host_events = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [(ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                                for ev in line.events]
            device_lines.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_events.extend((ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                                   for ev in line.events)
    return device_lines, host_events


def reduce(trace_dir: str | Path) -> dict:
    """The reduction of the newest trace under ``trace_dir``."""
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return summarize(*read(files[-1]))
