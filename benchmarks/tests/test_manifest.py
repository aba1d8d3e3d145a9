"""``BENCHMARK.json`` against the limits of the contract it is checked by."""

import os
import re

import pytest

from benchmarks.lib.manifest import Bench

from conftest import REPO_DIR

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden_size", "intermediate", "latent", "state_size", "_proj",
               "head_dim", "experts_per", "expansion")


@pytest.fixture(scope="module")
def bench():
    return Bench()


def test_top_level_keys_and_sizes(bench):
    m = bench.manifest
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(bench.manifest_path) <= 64 * 1024
    assert m["command"] == ["python3", "benchmarks/run.py"]
    assert m["paths"] == ["benchmarks"]
    rs = m["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells must fit into 43,200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_text_fields(bench):
    m = bench.manifest
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[section]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    metric_names = [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in m["configs"] + m["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"] \
            and "\t" not in e["why"]


def test_configs_and_cells(bench):
    m = bench.manifest
    assert 1 <= len(m["configs"]) <= 24 and 2 <= len(m["workloads"]) <= 24
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in m["workloads"])
    assert len(four) <= max(1, len(m["workloads"]) // 4)
    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files))
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/")
        cfg = bench.config(c["name"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg
            assert not key.endswith(("_dim", "_rank")) and not any(
                w in key for w in WIDTH_WORDS), key
        assert os.path.isfile(bench.path(*cfg["reference"].split("/")))
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        cell = bench.cell(w["name"])              # checks the cell's file
        mix = bench.traffic(cell["traffic"])
        assert os.path.isfile(bench.path("drivers", mix["kind"] + ".py"))


def test_metrics(bench):
    m = bench.manifest
    cells = [w["name"] for w in m["workloads"]]
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
    layers = set()
    for e in m["per_layer"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert e["moves"] in e2e and e["moves"] != "setup_s"
        layers.add(e["layer"])
        bench.reader(e["name"])                   # the reader's file exists
        if e["name"].endswith("_roofline"):
            assert e["unit"] == "%"
    assert 1 <= len(m["per_layer"]) <= 128
    for cell in cells:
        mine = [e["name"] for e in bench.metrics_for("end_to_end", cell)]
        assert "setup_s" in mine and len(mine) >= 2
        reported = [e for e in bench.metrics_for("per_layer", cell)
                    if e["moves"] in mine]
        assert reported, cell
    for e in m["end_to_end"] + m["per_layer"]:
        assert set(e.get("workloads", cells)) <= set(cells)
    # PERF.md's list of layers carries the same names, letter for letter
    with open(os.path.join(REPO_DIR, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert f"**{layer}**" in perf, layer
