"""A cell, a configuration, a traffic mix and a per-layer metric are
found by name: adding one is adding files and entries, with no edit to
the harness. Shown by loading a cell from a fixture directory."""
import json
import os

import bench


def test_a_cell_from_a_fixture_directory(tmp_path):
    chip = tmp_path / "benchmarks" / "chip"
    for d in ("configs", "traffic", "metrics"):
        (chip / d).mkdir(parents=True)
    (chip / "configs" / "tiny.json").write_text(json.dumps(
        {"name": "tiny", "task": "gmres_ir"}))
    (chip / "traffic" / "burst.json").write_text(json.dumps(
        {"entry": "inproc", "outstanding": 2, "pool": 4}))
    (chip / "metrics" / "x.count.py").write_text(
        "def read(rec):\n    return len(rec['answers'])\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "tiny.burst", "config": "tiny",
                       "traffic": "burst", "chips": 1, "why": "w"}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "only_elsewhere", "unit": "s",
                        "workloads": ["other"]}],
        "per_layer": [{"name": "x.count", "unit": "1",
                       "workloads": ["tiny.burst"]}]}))
    cell = bench.workload("tiny.burst", root=str(tmp_path),
                          here=str(chip))
    assert cell["config_file"]["name"] == "tiny"
    assert cell["traffic_file"]["pool"] == 4
    assert [m["name"] for m in cell["end_to_end"]] == ["setup_s"]
    reader = bench.module("metrics", "x.count", here=str(chip))
    assert reader.read({"answers": [1, 2, 3]}) == 3


def test_every_cell_of_the_benchmark_loads():
    for w in bench.benchmark()["workloads"]:
        cell = bench.workload(w["name"])
        cfg = cell["config_file"]
        assert os.path.exists(os.path.join(bench.HERE, "tasks",
                                           cfg["task"] + ".py"))
        assert os.path.exists(os.path.join(bench.HERE, "references",
                                           cfg["task"] + ".py"))
        assert os.path.exists(os.path.join(
            bench.HERE, "generators", cfg["generator"]["kind"] + ".py"))
        assert os.path.exists(os.path.join(
            bench.HERE, "entries", cell["traffic_file"]["entry"] + ".py"))
        assert "setup_s" in [m["name"] for m in cell["end_to_end"]]
        assert cell["per_layer"]
