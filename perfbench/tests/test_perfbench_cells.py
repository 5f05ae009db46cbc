"""Each cell's run through the harness on the CPU at small sizes: the
result line keeps to the contract and the outputs match the reference."""
import json
import math

import pytest

from perfbench.harness import registry as reg
from perfbench.tests.conftest import bench, run_small

CELLS = [c["name"] for c in bench()["workloads"]]


@pytest.mark.parametrize("traced", [False, True], ids=["timed", "traced"])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_and_reports_its_metrics(small_root, cell, traced):
    full = bench()
    line = run_small(small_root, cell, traced=traced)
    json.dumps(line)                                # one JSON object
    assert list(line)[:3] == ["correct", "attempted", "failed"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["compiles_in_window"] == 0
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if traced:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        names = {m["name"] for m in reg.cell_metrics(full, cell,
                                                     "per_layer")}
        assert set(line["metrics"]) <= names
    else:
        names = {m["name"] for m in reg.cell_metrics(full, cell,
                                                     "end_to_end")}
        assert set(line["metrics"]) == names
        for m in line["metrics"].values():
            assert math.isfinite(m["value"]) and m["value"] > 0
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]
