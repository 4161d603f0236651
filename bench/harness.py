"""One run of one cell: set-up, the measured window, the check, the metrics.

Everything is found by name, with no registry to edit:

* ``BENCHMARK.json`` (at the root) lists the cells and which metrics each
  reports;
* ``bench/workloads/<cell>.json`` names the cell's configuration, its
  traffic kind, the mix's parameters, what to check and the limits;
* ``bench/configs/<config>.json`` holds the deployment's sizes, and under
  ``params["family"]`` the problem family it draws (none:
  ``pointcloud_c1``);
* ``bench/families/<family>.py`` draws the problems and gives their ground
  cost, to the traffic, the reference and the control (``pool``,
  ``cost``, ``geometry``, ``TINY``, ``TINY_MERGE``);
* ``bench/traffic/<kind>.py`` drives the system under test with the mix
  (``pool``, ``setup``, ``run``, ``describe``, ``failed``, ``collect``,
  ``verify``, ``TINY``);
* ``bench/metrics/<metric>.py`` reads one metric from the run (``read``),
  and returns None where it finds nothing to read.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent


class NoDevice(RuntimeError):
    """The run found no TPU, too few chips, or float64 switched on."""


@dataclass
class Run:
    """What a metric reader sees."""

    cell: str
    params: dict
    record: dict
    setup_s: float
    device_kind: str
    trace: dict | None  # `bench.trace_reduce.reduce` of the traced window


def read_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_cell(name: str, root: Path = REPO) -> tuple[dict, dict]:
    """``(cell, params)``: the cell's file and the configuration's
    parameters with the cell's own laid over them."""
    cell = read_json(root / "bench" / "workloads" / f"{name}.json")
    config = read_json(root / "bench" / "configs" / f"{cell['config']}.json")
    return cell, {**config["params"], **cell.get("params", {})}


def _module(path: Path):
    """The module of one plugin file, loaded by path (its name may hold dots)."""
    name = f"bench_plugin:{path}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def traffic(kind: str, root: Path = REPO):
    return _module(root / "bench" / "traffic" / f"{kind}.py")


def family(params: dict, root: Path = REPO):
    """The problem family that a cell's parameters name."""
    return _module(root / "bench" / "families" / f"{params.get('family', 'pointcloud_c1')}.py")


def reader(metric: str, root: Path = REPO):
    return _module(root / "bench" / "metrics" / f"{metric}.py")


def metric_specs(benchmark: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    group = benchmark["per_layer"] if trace else benchmark["end_to_end"]
    return [m for m in group if "workloads" not in m or cell in m["workloads"]]


def chips_of(benchmark: dict, cell: str) -> int:
    return next(w["chips"] for w in benchmark["workloads"] if w["name"] == cell)


def check_device(chips: int) -> dict:
    """The device as JAX reports it; raises `NoDevice` unless there are
    ``chips`` TPUs and float64 is off (the configurations run float32)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found {len(devices)}")
    if jax.config.jax_enable_x64:
        raise NoDevice("jax_enable_x64 is on; the configurations run float32")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


class Tracer:
    """The profiler over the window: ``span(name)`` writes a host span while
    the profiler runs."""

    def __init__(self, directory: Path | None):
        self.directory, self.active = directory, False

    def start(self) -> None:
        if self.directory is None:
            return
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.directory), profiler_options=opts)
        self.active = True

    def stop(self) -> None:
        if self.active:
            import jax

            jax.profiler.stop_trace()
            self.active = False

    def span(self, name: str):
        if not self.active:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


def _peak_bytes() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(name: str, seed: int, seconds: float, trace: bool, *, t_start: float,
             root: Path = REPO, device: dict | None = None, overrides: dict | None = None,
             log=None) -> dict:
    """One run of cell ``name``; returns the result line as a dict.

    ``device`` is the checked device (`check_device`); tests pass one of
    their own to drive the rest of a run on the CPU. ``overrides`` lays
    parameters over the cell's (tests shrink sizes with it)."""
    from bench import check, trace_reduce

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    benchmark = read_json(root / "BENCHMARK.json")
    cell, params = load_cell(name, root)
    params.update(overrides or {})
    if device is None:
        device = check_device(chips_of(benchmark, name))
    mod = traffic(cell["traffic"], root)
    state = mod.setup(params, seed, seconds, log, root)
    setup_s = time.perf_counter() - t_start

    trace_dir = root / "bench" / "_out" / "trace"
    tracer = Tracer(trace_dir if trace else None)
    tracer.start()
    try:
        with tracer.span("bench.window"):
            record = mod.run(state, seconds, tracer)
    finally:
        tracer.stop()
    log(mod.describe(record))
    summary = trace_reduce.reduce(trace_dir) if trace else None
    peak = _peak_bytes()
    attempted = len(record["calls"])
    failed = mod.failed(record, state)
    samples = mod.collect(state, record, seed, cell["check"])
    gc.collect()
    readings = mod.verify(state, samples, cell["check"])
    compared = check.compare(readings, cell["limits"])
    log("readings " + " ".join(f"{k}={v!r}" for k, v in readings.items() if k not in compared))

    run = Run(name, params, record, setup_s, device["kind"], summary)
    metrics = {}
    for spec in metric_specs(benchmark, name, trace):
        value = reader(spec["name"], root).read(run)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    dev = {**device, "memory_peak_bytes": peak}
    if summary is not None:
        dev.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
    out = {"correct": check.passed(compared), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": dev}
    if summary is not None:
        out["breakdown"] = {"device_ops": summary["top_ops"], "idle_gaps": summary["idle_gaps"]}
    out["checks"] = compared
    return out


def report(result: dict, log) -> None:
    """Print the result: the compared numbers last on stderr, then the
    result line last on stdout."""
    for name, c in result["checks"].items():
        log(f"check {name}={c['value']!r} limit={c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}")
    print(json.dumps(result, allow_nan=False), flush=True)

