"""Each cell's run through the harness on the CPU at small sizes, on as
many devices as the cell's chips: the result line keeps to the contract and
the outputs match the reference."""
import json
import math

import pytest

from perfbench.harness import registry as reg
from perfbench.tests.conftest import (NoTestSize, bench, missing_sizes,
                                      run_small)

#: unlisted cells whose program path compiles inside the window, so that
#: no run of theirs can keep to the contract: ``SearchSession`` runs the
#: sharded search's ``shard_map`` eagerly, op by op, with 29 compilations a
#: chunk on a mesh of one device or four (PERF.md, Open questions).  The
#: rest of what the harness does for them is tested below.
COMPILES_IN_WINDOW = {"search.dense768.mesh4"}
CELLS = [c["name"] for c in bench()["workloads"]
         if c["name"] not in COMPILES_IN_WINDOW]


def _reports_correct_answers_and_its_metrics(line, cell, traced):
    full = bench()
    json.dumps(line)                                # one JSON object
    assert list(line)[:3] == ["correct", "attempted", "failed"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    assert dev["count"] == reg.find_cell(full, cell)["chips"]
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


@pytest.mark.parametrize("traced", [False, True], ids=["timed", "traced"])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_and_reports_its_metrics(small_root, cell, traced):
    line = run_small(small_root, cell, traced=traced)
    _reports_correct_answers_and_its_metrics(line, cell, traced)
    assert line["compiles_in_window"] == 0


#: peak bytes each of the four CPU devices reports, by device id
PEAKS = {0: 5 << 20, 1: 9 << 20, 2: 7 << 20, 3: 1 << 20}


def four_devices_born_only():
    """Planted in the child run: each CPU device reports a peak of its own
    (the CPU reports none), and a corpus streamed in from the host fails,
    so the run shows that it reads the fullest device and searches the
    rows the maker made row-sharded."""
    import jax
    from repro.distributed.sharded_corpus import ShardedCorpus

    def streamed(*args, **kwargs):
        raise AssertionError("the corpus was streamed in from the host")

    type(jax.devices()[0]).memory_stats = \
        lambda self: {"peak_bytes_in_use": PEAKS[self.id]}
    ShardedCorpus.from_host = classmethod(streamed)


@pytest.mark.parametrize("traced", [False, True], ids=["timed", "traced"])
def test_a_cell_on_four_devices_runs_correct_on_its_born_rows(small_root,
                                                              traced):
    cell = "search.dense768.mesh4"
    line = run_small(small_root, cell, traced=traced,
                     plant=f"{__name__}:four_devices_born_only")
    _reports_correct_answers_and_its_metrics(line, cell, traced)
    assert line["device"]["memory_peak_bytes"] == max(PEAKS.values())


def test_every_configuration_and_traffic_has_a_test_size():
    assert missing_sizes(bench()["workloads"]) == []


def test_a_cell_without_a_test_size_is_refused_before_it_runs(tmp_path):
    with pytest.raises(NoTestSize, match="configs/msmarco-dense768-shard"):
        run_small(str(tmp_path / "absent"), "search.dense768.batch",
                  sizes=str(tmp_path))
