"""Every cell of BENCHMARK.json, rehearsed on the CPU at a tiny size through
the harness's own functions: its generator, its traffic code, its check and its
metric readers."""
import json

import pytest

from bench import harness
from bench.tests import tiny

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.mark.parametrize("cell", tiny.cells())
def test_cell_runs_and_is_correct(cell):
    res = tiny.run(cell)
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    bench = harness.read_json(harness.REPO / "BENCHMARK.json")
    want = {m["name"] for m in harness.metric_specs(bench, cell, trace=False)}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res["checks"]) == list(harness.load_cell(cell)[0]["limits"])
    json.dumps(res, allow_nan=False)


@pytest.mark.parametrize("kind", sorted({harness.load_cell(c)[0]["traffic"] for c in tiny.cells()}))
def test_traced_run_adds_breakdown_and_reads_per_layer_metrics(kind):
    bench = harness.read_json(harness.REPO / "BENCHMARK.json")
    for cell in [c for c in tiny.cells() if harness.load_cell(c)[0]["traffic"] == kind]:
        res = tiny.run(cell, trace=True)
        assert set(res) == KEYS | {"breakdown"}
        assert list(res)[-1] == "checks"
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        assert {"busy_s", "window_s"} <= set(res["device"])
        allowed = {m["name"] for m in harness.metric_specs(bench, cell, trace=True)}
        assert set(res["metrics"]) <= allowed
        # the CPU has no device plane: trace readers find nothing and say so
        assert "idle_pct.solve" not in res["metrics"]
