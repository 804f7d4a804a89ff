"""BENCHMARK.json against the builder's contract, as far as a test can
hold it: names, units, files, and the arrows between metrics."""
import json
import os
import re

import pytest

from cellbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
M = harness.load_manifest()
CELLS = [w["name"] for w in M["workloads"]]


def test_top_level_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        < 64 * 1024
    for word in M["command"]:
        assert not word.startswith("/") and ".." not in word
    for path in M["paths"]:
        assert os.path.isdir(os.path.join(harness.ROOT, path))


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_names_are_well_formed_and_unique(section):
    names = [e["name"] for e in M[section]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n


def test_entries_have_exactly_the_contract_keys():
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"])
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert sum(w["chips"] == 4 for w in M["workloads"]) \
        <= max(1, len(M["workloads"]) // 2)


def test_setup_s_is_an_end_to_end_metric_of_every_cell():
    setup = [m for m in M["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.25


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    c = harness.Cell(cell)
    assert c.workload["config"] == c.row["config"]
    assert c.workload["traffic"] == c.row["traffic"]
    conf = [k for k in M["configs"] if k["name"] == c.row["config"]][0]
    with open(os.path.join(harness.ROOT, conf["file"])) as fh:
        on_disk = json.load(fh)
    assert on_disk["source"] == conf["source"]
    assert on_disk["reduced"] == conf["reduced"]
    assert all(k in on_disk for k in conf["reduced"])
    assert on_disk["guarantees"] and on_disk["assumed"]
    assert os.path.exists(os.path.join(
        harness.HERE, "drivers", on_disk["driver"] + ".py"))
    # set-up, another end-to-end metric, and a per-layer metric
    e2e = [m["name"] for m in c.end_to_end()]
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer()


@pytest.mark.parametrize("metric", [m["name"] for m in M["per_layer"]])
def test_per_layer_metric_has_a_reader_and_moves_what_its_cells_report(
        metric):
    m = [x for x in M["per_layer"] if x["name"] == metric][0]
    assert callable(harness.load_by_name("layer_metrics", metric).read)
    moved = [e for e in M["end_to_end"] if e["name"] == m["moves"]]
    assert len(moved) == 1
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        assert cell in moved[0].get("workloads", CELLS), (metric, cell)
    if "roofline" in metric:
        assert m["unit"] == "%" and m["source"] == "device_trace"


def test_each_config_is_used_and_each_pair_appears_once():
    used = {w["config"] for w in M["workloads"]}
    assert used == {c["name"] for c in M["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))
    assert all(f.startswith(tuple(p + "/" for p in M["paths"]))
               for f in files)


def test_full_check_fits_the_driver_s_limit_with_24_cells():
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
