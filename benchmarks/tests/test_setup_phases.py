"""Set-up read from inside the program (ISSUE 55): the reduction of one
registry snapshot, the tool that prints a cell's set-up as one line, and the
reader of ``jit_host_ms_in_window``.  CPU, tiny cells: identities and
counts, never a time that is written down."""

import importlib.util
import json
import os

import pytest

from benchmarks.lib.manifest import Bench
from benchmarks.lib.setup_spans import (KEYS, jit_seconds, setup_parts,
                                        unattributed_s)
from benchmarks.run import run_cell

from conftest import BENCH_DIR, REPO_DIR

# a cold serve run's registry where set-up ended, as the tool snapshots it
RECORDED = {
    "ds_setup_import_seconds": 6.25,
    "ds_setup_serving_seconds_total": 21.5,
    "ds_setup_engine_seconds_total": 14.0,
    "ds_setup_inject_seconds_total": 9.5,
    "ds_setup_pool_seconds_total": 7.0,
    "ds_setup_seconds_total": 21.5,
    "ds_jit_trace_seconds_total": 30.0,
    "ds_jit_lower_seconds_total": 12.0,
    "ds_jit_compile_seconds_total": 80.0,
    "ds_jit_cache_read_seconds_total": 0.0,
    "ds_jit_in_setup_seconds_total": 10.0,
    "ds_jit_programs_total": 141,
    "ds_jit_cache_hits_total": 0,
    "ds_jit_cache_misses_total": 141,
    "ds_serve_steps_total": 57,
}
SERVE_CELLS = {
    "mistral-7b-L8.serve-chat", "olmoe-1b-7b-L8.serve-chat",
    "mistral-7b-L8.serve-chat-over", "evabyte-L6.serve-doc",
    "trinity-large-L5-ep8.serve-mixed-16k",
    "kimi-linear-L5-ep8.serve-reason-doc-tail",
    "axk1-L5-ep16.serve-mixed-16k", "dots3-note-L5-ep16.serve-doc-48k"}


def _tool():
    spec = importlib.util.spec_from_file_location(
        "bench_tool_setup_phases",
        os.path.join(BENCH_DIR, "tools", "setup_phases.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_setup_parts_of_a_recorded_snapshot():
    parts = setup_parts(RECORDED)
    assert set(parts) == set(KEYS)
    assert parts["import"] == 6.25
    assert (parts["engine"], parts["inject"], parts["pool"]) == \
        (14.0, 9.5, 7.0)
    # a serve run opens no training range: no seconds, not no value
    assert parts["params"] == 0.0 and parts["compile_steps"] == 0.0
    assert (parts["trace"], parts["lower"], parts["compile"]) == \
        (30.0, 12.0, 80.0)
    assert (parts["hits"], parts["misses"], parts["programs"]) == \
        (0.0, 141.0, 141.0)
    assert parts["phases"] == 21.5
    assert parts["jit_outside_phases"] == 122.0 - 10.0
    assert jit_seconds(RECORDED) == 122.0
    # setup_s = import + ranges + jit outside them + the rest
    assert unattributed_s(160.0, parts) == \
        pytest.approx(160.0 - 6.25 - 21.5 - 112.0)


@pytest.mark.parametrize("counters", [
    None, {}, {"ds_serve_steps_total": 57,
               "ds_serve_step_seconds_total": 3.5}])
def test_a_program_without_the_counters_reads_none_throughout(counters):
    parts = setup_parts(counters)
    assert set(parts) == set(KEYS) and set(parts.values()) == {None}
    assert jit_seconds(counters) is None
    assert unattributed_s(100.0, parts) is None


@pytest.mark.parametrize("cell,opened", [
    ("tiny-gpt2.serve", ("engine", "inject", "pool")),
    ("tiny-gpt2.train", ("params", "compile_steps"))])
def test_the_tool_accounts_for_a_tiny_cells_set_up(tiny_bench, cell, opened):
    out = _tool().setup_phases(cell, 3, 1.0, manifest_path=tiny_bench,
                               allow_cpu=True)
    json.dumps(out)
    parts = out["setup_parts"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["setup_s"] == out["metrics"]["setup_s"]
    for name in opened:
        assert parts[name] > 0, name
    assert parts["inject"] <= parts["engine"]
    assert parts["compile_steps"] <= parts["params"]
    assert parts["phases"] >= sum(parts[p] for p in ("engine", "pool"))
    jit = parts["trace"] + parts["lower"] + parts["compile"]
    assert 0 < parts["jit_outside_phases"] <= jit <= out["setup_s"]
    assert parts["cache_read"] <= parts["compile"]
    assert parts["programs"] >= parts["hits"] >= 0
    assert 0 <= out["backend_init_s"] <= out["unattributed_s"]
    assert (parts["import"] + parts["phases"] + parts["jit_outside_phases"]
            + out["unattributed_s"]) == pytest.approx(out["setup_s"])
    rows = out["programs"]
    assert 0 < len(rows) <= 20 <= out["programs_named"]
    assert [r["self_s"] for r in rows] == \
        sorted((r["self_s"] for r in rows), reverse=True)
    assert any(r["phase"] is not None for r in rows) or \
        parts["jit_outside_phases"] == pytest.approx(jit)
    assert set(out["checks"]["after_window_s"]) >= {"reference"}
    assert out["checks"]["compiles_in_window"] == 0
    assert out["process_s"] >= out["setup_s"] + 1.0


def test_the_reader_reads_zero_on_a_traced_run_and_none_without(tiny_bench):
    line = run_cell("tiny-gpt2.serve", 5, 1.5, True,
                    manifest_path=tiny_bench, allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert line["metrics"]["jit_host_ms_in_window"] == \
        {"value": 0.0, "unit": "ms"}
    read = Bench(tiny_bench).reader("jit_host_ms_in_window").read
    snap = dict(RECORDED)
    later = dict(snap, ds_jit_lower_seconds_total=13.5,
                 ds_jit_compile_seconds_total=80.25)
    assert read({"counters": {"begin": snap, "end": later}}) == \
        pytest.approx(1750.0)
    # the train driver keeps no counters; a parent has no ledger
    assert read({"counters": {}}) is None
    old = {"ds_serve_steps_total": 57}
    assert read({"counters": {"begin": old, "end": old}}) is None
    assert read({"counters": {"begin": old, "end": later}}) is None
    train = run_cell("tiny-gpt2.train", 5, 1.0, True,
                     manifest_path=tiny_bench, allow_cpu=True)
    assert "jit_host_ms_in_window" not in train["metrics"]


def test_the_entry_is_listed_for_the_serve_cells():
    bench = Bench(os.path.join(REPO_DIR, "BENCHMARK.json"))
    entries = [e for e in bench.manifest["per_layer"]
               if e["name"] == "jit_host_ms_in_window"]
    assert len(entries) == 1
    entry = entries[0]
    assert entry == {"name": "jit_host_ms_in_window", "unit": "ms",
                     "better": "lower", "source": "program_counter",
                     "layer": "Entry", "moves": "tokens_per_s",
                     "workloads": entry["workloads"]}
    assert set(entry["workloads"]) == SERVE_CELLS
    assert len(entry["workloads"]) == len(SERVE_CELLS)
    for cell in SERVE_CELLS:
        assert entry in bench.metrics_for("per_layer", cell)
    assert entry not in bench.metrics_for("per_layer", "gpt2-xl.train-zero3")
    # nothing the program measures of set-up is given a `moves` it does
    # not move (the benchmark issue that lifts test_manifest's rule adds
    # the setup_* entries from lib/setup_spans.py)
    assert not [e for e in bench.manifest["per_layer"]
                if e["moves"] == "setup_s"]
