"""BENCHMARK.json against the contract's shape, and the registry that finds
a cell's files by name."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench.harness import registry as reg
from perfbench.tests.conftest import (CHECKOUT, SIZES, bench,
                                      make_small_root, run_small)

BENCH = reg.load_benchmark()
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(BENCH) == TOP_KEYS
    assert BENCH["paths"] == ["perfbench"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("unlisted", [False, True],
                         ids=["benchmark", "with_unlisted"])
def test_names_and_units_keep_to_the_allowed_characters(unlisted):
    assert reg.name_errors(bench() if unlisted else BENCH) == []


@pytest.mark.parametrize("bad", ["a b", "a,b", "a/b", "", "x" * 65, ".x",
                                 "muµ"])
def test_bad_names_are_refused(bad):
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"][0]["name"] = bad
    assert reg.name_errors(bench)


@pytest.mark.parametrize("unit", ["tokens per second", "µs", "",
                                  "x" * 17])
def test_bad_units_are_refused(unit):
    bench = json.loads(json.dumps(BENCH))
    bench["end_to_end"][0]["unit"] = unit
    assert reg.name_errors(bench)


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for cell in BENCH["workloads"]:
        e2e = {m["name"] for m in reg.cell_metrics(BENCH, cell["name"],
                                                   "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2, cell["name"]
        assert reg.cell_metrics(BENCH, cell["name"], "per_layer"), \
            cell["name"]


def test_metric_entries_keep_to_the_contract():
    cells = {c["name"] for c in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        for cell in m["workloads"]:   # the cell reports what it moves
            assert m["moves"] in {x["name"] for x in reg.cell_metrics(
                BENCH, cell, "end_to_end")}


def test_every_named_file_is_there():
    r = reg.Registry()
    full = bench()
    for cfg in BENCH["configs"]:
        assert cfg["file"] == f"perfbench/configs/{cfg['name']}.json"
        assert r.config(cfg["name"])["name"] == cfg["name"]
        assert hasattr(r.reference(cfg["name"]), "make_corpus") or \
            hasattr(r.reference(cfg["name"]), "make_inputs")
        assert callable(r.reference(cfg["name"]).control)
    for cell in full["workloads"]:
        assert cell["chips"] in (1, 4)
        kind = r.kind(r.traffic(cell["traffic"])["kind"])
        assert hasattr(kind, "Driver")
    fours = sum(cell["chips"] == 4 for cell in BENCH["workloads"])
    assert fours <= max(1, len(BENCH["workloads"]) // 2)
    for m in full["per_layer"]:
        assert callable(r.metric(m["name"]).read)


def test_new_files_are_found_by_name_with_no_edit(tmp_path):
    """A configuration, a traffic mix, a driver and a per-layer reader
    dropped into a directory are found by the names an entry gives; a
    configuration of four chips, added as files and a size file, runs
    through the harness with no edit to a file that is there."""
    root = tmp_path / "bench"
    for sub in ("configs", "traffic", "kinds", "metrics"):
        (root / sub).mkdir(parents=True)
    (root / "configs" / "toy-cfg.json").write_text('{"name": "toy-cfg"}')
    (root / "configs" / "toy-cfg_ref.py").write_text("ANSWER = 42\n")
    (root / "traffic" / "toy.json").write_text('{"kind": "toy_kind"}')
    (root / "kinds" / "toy_kind.py").write_text("class Driver:\n    pass\n")
    (root / "metrics" / "toy_metric.x.py").write_text(
        "def read(r):\n    return 7.0\n")
    bench = {"end_to_end": [{"name": "setup_s"}, {"name": "toy_s",
                                                  "workloads": ["toy.cell"]}],
             "per_layer": [{"name": "toy_metric.x", "moves": "toy_s",
                            "workloads": ["toy.cell"]},
                           {"name": "every", "moves": "toy_s"}],
             "workloads": [{"name": "toy.cell", "config": "toy-cfg",
                            "traffic": "toy", "chips": 1}]}
    r = reg.Registry(str(root))
    cell = reg.find_cell(bench, "toy.cell")
    assert r.config(cell["config"]) == {"name": "toy-cfg"}
    assert r.reference(cell["config"]).ANSWER == 42
    assert hasattr(r.kind(r.traffic(cell["traffic"])["kind"]), "Driver")
    assert r.metric("toy_metric.x").read(None) == 7.0
    (root / "metrics" / "shared.py").write_text(
        "def read(r):\n    return 3.0\n")
    assert r.metric("shared.a").read(None) == 3.0     # one reader, two names
    assert r.metric("shared.b") is r.metric("shared")
    assert [m["name"] for m in reg.cell_metrics(bench, "toy.cell",
                                                "per_layer")] == \
        ["toy_metric.x", "every"]
    with pytest.raises(reg.RegistryError):
        r.traffic("absent")
    with pytest.raises(reg.RegistryError):
        reg.find_cell(bench, "absent")

    bench_dir, sizes = tmp_path / "perfbench", tmp_path / "sizes"
    shutil.copytree(reg.BENCH_DIR, bench_dir, ignore=shutil.ignore_patterns(
        "__pycache__"))
    shutil.copytree(SIZES, sizes)
    cfg = json.loads((bench_dir / "configs" / "msmarco-dense768-shard.json")
                     .read_text())
    cfg.update(name="toy-dense96", rows=100003, dim=96)
    (bench_dir / "configs" / "toy-dense96.json").write_text(json.dumps(cfg))
    # its maker leaves the rows on one device: the harness streams them in
    (bench_dir / "configs" / "toy-dense96_ref.py").write_text(
        (bench_dir / "configs" / "msmarco-dense768-shard_ref.py").read_text()
        + "\n\ndef make_corpus(config, seed, devices):\n"
          "    with jax.default_device(devices[0]):\n"
          "        return _normal(_key(seed, 0), rows=config['rows'],\n"
          "                       dim=config['dim'])\n")
    (sizes / "configs" / "toy-dense96.json").write_text(
        '{"rows": 2001, "checked_answers": 32}')
    toy = {"end_to_end": [m for m in BENCH["end_to_end"]
                          if m["name"] in ("setup_s", "search_qps")],
           "per_layer": [],
           "workloads": [{"name": "toy.dense96.x4", "config": "toy-dense96",
                          "traffic": "batch", "chips": 4}]}
    toy["end_to_end"][1] = {**toy["end_to_end"][1],
                            "workloads": ["toy.dense96.x4"]}
    root = make_small_root(str(tmp_path / "small"), str(bench_dir),
                           str(sizes))
    line = run_small(root, "toy.dense96.x4", benchmark=toy, sizes=str(sizes))
    assert line["correct"] is True, line["checks"]
    assert line["device"]["count"] == 4
    assert set(line["metrics"]) == {"setup_s", "search_qps"}


def test_a_run_without_a_tpu_exits_non_zero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(CHECKOUT, "perfbench", "run.py"),
         "--workload", "sample.msmarco.job", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
