"""BENCHMARK.json against the contract's letter, and every cell's files."""

import os
import re

import pytest

from harness import cells

from conftest import BENCH_DIR, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_names_units_and_keys(benchmark_json):
    b = benchmark_json
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    names = []
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells_ = {w["name"] for w in b["workloads"]}
    for m in b["end_to_end"] + b["per_layer"]:
        names.append(m["name"])
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells_)) <= cells_
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in b["per_layer"]:
        # the metric it moves is reported in every cell where this one is
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells_)) <= set(
            moved.get("workloads", cells_))
    assert len(names) == len(set(names)) and "setup_s" in e2e
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        assert c["file"].startswith(b["paths"][0] + "/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
    checks = 2 + 14 * 24
    assert checks * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_every_cell_resolves_and_reports(benchmark_json):
    for w in benchmark_json["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.traffic["kind"] in ("train", "open_loop", "closed_batch")
        assert os.path.exists(os.path.join(
            BENCH_DIR, "harness", "runners", cell.traffic["kind"] + ".py"))
        assert os.path.exists(os.path.join(
            BENCH_DIR, "families", cell.config["family"] + ".py"))
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            reader = cell.layer_files[m["name"]]["reader"]
            assert os.path.exists(os.path.join(BENCH_DIR, "readers",
                                               reader + ".py"))
        reduced = next(c for c in benchmark_json["configs"]
                       if c["name"] == w["config"])["reduced"]
        assert cell.config["reduced"] == reduced
        for key in reduced:       # never a width
            assert not re.search(r"(_dim|_rank|_size)$", key)


def test_files_under_paths_are_named_from_a_names_characters():
    for dirpath, dirs, files in os.walk(BENCH_DIR):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


def test_a_dummy_cell_is_added_by_files_and_entries_alone(tiny_tree):
    for name in ("tiny-gpt.tiny-train", "tiny-llama.tiny-chat",
                 "tiny-llama.tiny-batch"):
        cell = cells.load_cell(name, tiny_tree)
        assert cell.config["name"] == name.split(".")[0]
        assert cell.per_layer and cell.end_to_end
    with pytest.raises(SystemExit):
        cells.load_cell("no-such.cell", tiny_tree)
