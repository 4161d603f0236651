"""The reduction of a trace to the program's phase spans, on hand-made
events and on hand-made ``.xplane.pb`` files read through the metric
readers."""
import shutil

import pytest

from bench import harness
from bench import phase_trace as pt

READERS = ("sketch_ms.solve", "sketch_idle_ms.solve", "sketch_programs.solve",
           "objective_ms.solve")
EXEC = pt.LAUNCH_EVENT

# one solve, times in seconds: the phases nest in spar_sink.solve
SPANS = [(0.0, 100.0, "bench.window"), (1.0, 90.0, "bench.solve"),
         (2.0, 40.0, "spar_sink.solve"), (3.0, 10.0, "spar_sink.sketch"),
         (11.0, 20.0, "spar_sink.loop"), (21.0, 25.0, "spar_sink.objective")]


def host(*events, line=0):
    """Host events ``(start, end, name, stats)`` on one line."""
    return [(s, e, name, line, stats) for s, e, name, stats in events]


def spans(line=0):
    return host(*[(s, e, name, {}) for s, e, name in SPANS], line=line)


def test_objective_program_run_after_its_span_counts_to_the_objective():
    # the sketch's program is launched and runs inside its span; the loop's
    # runs far past it; the objective's is enqueued on a worker thread once
    # the loop is done (flow 99 from the module to DoEnqueueProgram, whose
    # enclosing event's flow 55 leads back to the Python thread's launch)
    mods = [(4.0, 6.0, "jit_gather(1)", 1, None), (12.0, 60.0, "jit_while(2)", 2, None),
            (60.5, 61.0, "jit_add(3)", None, 99)]
    ops = [(m[0], m[1], "op") for m in mods]
    events = spans() + host(
        (3.5, 3.6, "PjRtCpuExecutable::ExecuteHelper", {"run_id": 1}),
        (11.5, 11.6, "PjRtCpuExecutable::ExecuteHelper", {"run_id": 2}),
        (22.0, 22.1, "tpu::System::Execute", {"_p": 55}),
    ) + host(
        (60.1, 60.4, "tpu::System::Execute=>IssueSequencedEvent", {"_c": 55}),
        (60.2, 60.3, "DoEnqueueProgram", {"_p": 99, "run_id": 3}),
        line=1)
    s = pt.summarize([{"ops": ops, "modules": mods}], events)
    assert s["join"] == "correlation"
    assert s["span_device_s"] == {"spar_sink.sketch": pytest.approx(2.0),
                                  "spar_sink.loop": pytest.approx(48.0),
                                  "spar_sink.objective": pytest.approx(0.5)}
    assert s["span_programs"] == {"spar_sink.sketch": 1, "spar_sink.loop": 1,
                                  "spar_sink.objective": 1}
    assert s["spans"]["spar_sink.objective"] == pytest.approx(4.0)


@pytest.mark.parametrize("extra_launch", [False, True])
def test_launch_order_fallback_only_when_the_counts_agree(extra_launch):
    # no module carries a correlation stat: the k-th launch is the k-th module
    mods = [(4.0, 6.0, "jit_gather", None, None), (12.0, 60.0, "jit_while", None, None),
            (60.5, 61.0, "jit_add", None, None)]
    launches = [(3.5, 3.6, EXEC, {}), (11.5, 11.6, EXEC, {}), (22.0, 22.1, EXEC, {})]
    if extra_launch:
        launches.append((23.0, 23.1, EXEC, {}))
    s = pt.summarize([{"ops": [m[:3] for m in mods], "modules": mods}],
                     spans() + host(*launches))
    if extra_launch:
        assert s["join"] is None
        assert s["span_device_s"] is None and s["span_programs"] is None
    else:
        assert s["join"] == "launch order"
        assert s["span_device_s"] == {"spar_sink.sketch": pytest.approx(2.0),
                                      "spar_sink.loop": pytest.approx(48.0),
                                      "spar_sink.objective": pytest.approx(0.5)}


def _xspace(modules, ops, host_events) -> bytes:
    """A serialized XSpace: ``modules`` ``(start, end, name, stats)`` and
    ``ops`` ``(start, end, name)`` on one TPU plane, ``host_events`` as
    `host` gives them on the host plane. Times in seconds."""
    from jax.profiler import ProfileData

    def plane(pid, name, lines):
        meta, stat_meta, out = {}, {}, []
        for lid, (lname, events) in enumerate(lines, 1):
            evs = []
            for s, e, ename, stats in events:
                mid = meta.setdefault(ename, len(meta) + 1)
                st = " ".join(
                    f"stats {{ metadata_id: {stat_meta.setdefault(k, len(stat_meta) + 1)} "
                    f"int64_value: {v} }}" for k, v in stats.items())
                evs.append(f"events {{ metadata_id: {mid} offset_ps: {round(s * 1e12)} "
                           f"duration_ps: {round((e - s) * 1e12)} {st} }}")
            out.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 {" ".join(evs)} }}')
        out += [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                for n, i in meta.items()]
        out += [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                for n, i in stat_meta.items()]
        return f'planes {{ id: {pid} name: "{name}" {" ".join(out)} }}'

    by_line = {}
    for s, e, name, line, stats in host_events:
        by_line.setdefault(f"thread{line}", []).append((s, e, name, stats))
    text = "\n".join([
        plane(1, "/device:TPU:0", [("XLA Modules", modules),
                                   ("XLA Ops", [(s, e, n, {}) for s, e, n in ops])]),
        plane(2, "/host:CPU", list(by_line.items())),
    ])
    return ProfileData.text_proto_to_serialized_xspace(text)


def _reader_run(tmp_path, modules, ops, host_events, solves=2):
    """Write the trace where a checkout at ``tmp_path`` keeps it; return the
    checkout's readers and a traced run of ``solves`` solves."""
    trace = tmp_path / "bench" / "_out" / "trace" / "plugins" / "profile" / "t"
    trace.mkdir(parents=True)
    (trace / "vm.xplane.pb").write_bytes(_xspace(modules, ops, host_events))
    (tmp_path / "bench" / "metrics").mkdir()
    for name in READERS:
        shutil.copy(harness.REPO / "bench" / "metrics" / f"{name}.py",
                    tmp_path / "bench" / "metrics" / f"{name}.py")
    run = harness.Run("cell", {}, {"calls": [{}] * solves}, 1.0, "TPU v5 lite",
                      trace={"window_s": 100.0, "busy_s": 50.0, "programs": {}})
    return {name: harness.reader(name, tmp_path) for name in READERS}, run


# the device idles 3-4 and 6-9.5 in the sketch's span and 10.2-10.8
# between phases, in spar_sink.solve's alone
MODULES = [(4.0, 6.0, "jit_gather(1)", {"run_id": 1}), (12.0, 60.0, "jit_while(2)", {"run_id": 2}),
           (60.5, 61.0, "jit_add(3)", {"run_id": 3})]
OPS = [(0.0, 3.0, "warm"), (4.0, 6.0, "gather"), (9.5, 10.2, "copy"), (10.8, 60.0, "while"),
       (60.0, 100.0, "after")]
LAUNCHES = host((3.5, 3.6, EXEC, {"run_id": 1}), (11.5, 11.6, EXEC, {"run_id": 2}),
                (22.0, 22.1, EXEC, {"run_id": 3}))


def test_readers_read_the_sketch_and_objective_per_solve(tmp_path):
    readers, run = _reader_run(tmp_path, MODULES, OPS, spans() + LAUNCHES)
    got = {name: r.read(run) for name, r in readers.items()}
    assert got == {"sketch_ms.solve": pytest.approx(1e3 * 2.0 / 2),
                   "sketch_idle_ms.solve": pytest.approx(1e3 * (1.0 + 3.5) / 2),
                   "sketch_programs.solve": pytest.approx(1 / 2),
                   "objective_ms.solve": pytest.approx(1e3 * 0.5 / 2)}
    s = pt.summarize(*pt.read(next(tmp_path.rglob("*.xplane.pb"))))
    # the gap between the sketch and the loop is the solve's, not the sketch's
    assert s["span_idle_s"] == {"spar_sink.sketch": pytest.approx(4.5),
                                "spar_sink.solve": pytest.approx(0.6)}


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_program_spans(tmp_path, name):
    bench_only = [h for h in spans() if h[2].startswith("bench.")]
    readers, run = _reader_run(tmp_path, MODULES, OPS, bench_only + LAUNCHES)
    assert readers[name].read(run) is None
    untraced = harness.Run("cell", {}, run.record, 1.0, "TPU v5 lite", trace=None)
    assert readers[name].read(untraced) is None
