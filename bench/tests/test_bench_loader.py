"""The harness finds a cell, its configuration and its metrics by file
name alone; a new cell is new files, with no edit to a file already
there."""

import json
import os
import shutil

import pytest

from bench_tiny import BENCH, ROOT, harness

run = harness()
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("entry", SPEC["workloads"], ids=lambda w: w["name"])
def test_each_cell_loads_by_name(entry):
    cell = run.load_cell(entry["name"])
    assert cell["config"]["name"] == entry["config"]
    assert cell["chips"] == entry["chips"]
    assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) >= 2
    assert cell["per_layer"]
    for name in cell["per_layer"]:
        assert callable(run.load_metric(name).read)


def test_config_files_match_their_entries():
    for c in SPEC["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]


def test_a_new_cell_is_new_files(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("tests"))
    spec = dict(SPEC)
    spec["workloads"] = SPEC["workloads"] + [
        {"name": "paper_shhss.cgnr", "config": "paper_shhss",
         "traffic": "cgnr", "chips": 1, "why": "solves at the bf16 rung"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    config = dict(json.load(open(bench / "configs" / "paper_sssss.json")),
                  name="paper_shhss", precision="shhss")
    (bench / "configs" / "paper_shhss.json").write_text(json.dumps(config))
    traffic = dict(json.load(open(bench / "workloads" /
                                  "paper_sssss.cgnr.json")), config="paper_shhss")
    (bench / "workloads" / "paper_shhss.cgnr.json").write_text(
        json.dumps(traffic))
    (bench / "metrics" / "cgnr_only.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    cell = run.load_cell("paper_shhss.cgnr", bench_dir=str(bench))
    assert cell["traffic"]["call"] == "cgnr"
    assert cell["config"]["precision"] == "shhss"
    assert run.load_metric("cgnr_only", bench_dir=str(bench)).read({}) == 1.0
