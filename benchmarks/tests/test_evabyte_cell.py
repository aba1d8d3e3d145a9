"""The EvaByte cell's files through the runner and through
``tools/evabyte_agreement.py`` at a tiny size on the CPU (window 32, chunk
4, page 8, 2 layers, 4 heads): the configuration's ``model_config`` builds,
the driver's ``correct`` holds on a mix whose every request crosses a window
boundary in DECODE (a stale window page or a lost summary would sit far
below the reference's best logit), nothing compiles inside the window, the
program's counters reach the new readers, and the agreement tool's
bookkeeping yields every generated position."""

import importlib.util
import os
import shutil

import pytest

from benchmarks.run import run_cell

from conftest import BENCH_DIR, _dump, _load

CELL = "tiny-evabyte.serve"


@pytest.fixture
def eva_bench(tiny_bench):
    root = os.path.join(os.path.dirname(tiny_bench), "tinybench")
    cfg = _load(os.path.join(BENCH_DIR, "configs", "evabyte-L6.json"))
    cfg.update(hidden_size=64, intermediate_size=96, num_attention_heads=4,
               num_key_value_heads=4, num_hidden_layers=2, vocab_size=40,
               window_size=32, chunk_size=4)
    cfg["model_config"].update(
        vocab_size=40, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=4, num_kv_heads=4, head_dim=16, max_seq_len=256,
        eva_window=32, eva_chunk=4)
    _dump(cfg, os.path.join(root, "configs", "tiny-evabyte.json"))
    shutil.copy(os.path.join(BENCH_DIR, "reference", "evabyte.py"),
                os.path.join(root, "reference", "evabyte.py"))
    mix = _load(os.path.join(BENCH_DIR, "traffic", "bytes-doc-8k.json"))
    # 34 tokens or more of output: every request leaves a window in decode
    mix["prompt_tokens"].update(median=40, min=20, max=60)
    mix["output_tokens"].update(median=40, min=34, max=48)
    mix["max_total_tokens"] = 112
    _dump(mix, os.path.join(root, "traffic", "bytes-tiny.json"))
    cell = _load(os.path.join(BENCH_DIR, "workloads",
                              "evabyte-L6.serve-doc.json"))
    cell.update(name=CELL, config="tiny-evabyte", traffic="bytes-tiny",
                rate_rps=4.0, trace_seconds=0.5)
    cell["engine"].update(num_slots=4, prefill_chunk=16, max_out_tokens=128,
                          kv_pool_tokens=256, kv_page_tokens=8,
                          decode_block_tokens=4)
    _dump(cell, os.path.join(root, "workloads", CELL + ".json"))
    m = _load(tiny_bench)
    real = _load(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json"))
    m["configs"].append({"name": "tiny-evabyte", "source": "test",
                         "reduced": [], "why": "test",
                         "file": "tinybench/configs/tiny-evabyte.json"})
    m["workloads"].append({"name": CELL, "config": "tiny-evabyte",
                           "traffic": "bytes-tiny", "chips": 1,
                           "why": "test"})
    # the cell reports what the real one reports
    lists = {e["name"]: e.get("workloads")
             for s in ("end_to_end", "per_layer") for e in real[s]}
    for e in m["end_to_end"] + m["per_layer"]:
        if "evabyte-L6.serve-doc" in (lists[e["name"]] or ()):
            e["workloads"] = [w for w in e["workloads"]
                              if w != "evabyte-L6.serve-doc"] + [CELL]
    _dump(m, tiny_bench)
    return tiny_bench


def test_the_cell_runs_and_is_correct(eva_bench):
    line = run_cell(CELL, 2**31 + 9, 1.5, True, manifest_path=eva_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["compiles_in_window"] == 0
    assert line["checks"]["reference"]["requests_checked"] > 0
    # no device plane on the CPU: the trace's metrics are left out ...
    assert "eva_decode_roofline" not in line["metrics"]
    assert "eva_decode_share" not in line["metrics"]
    # ... the counter's is there: every request attends summaries
    assert 0 < line["metrics"]["eva_summary_row_share"]["value"] < 100
    assert "flash_decode_paged_roofline" not in line["metrics"]
    assert {"sched_occupancy_mean", "gen_late_p99_ms"} <= set(line["metrics"])


def test_untraced_run_reports_the_end_to_end_metrics(eva_bench):
    line = run_cell(CELL, 77, 1.5, False, manifest_path=eva_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert {"tokens_per_s", "tpot_p50_ms", "setup_s"} <= set(line["metrics"])


def test_readers_return_none_for_a_program_without_eva(tiny_bench):
    """What the parent commit gives the new readers: no such kernel in the
    trace, no such counter in the registry."""
    from benchmarks.lib.manifest import Bench

    bench = Bench(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json"))
    ctx = {"trace": {"kernels": {"flash_decode_paged": {"seconds": 1.0}},
                     "programs": {"jit_body": {"busy_s": 2.0}}},
           "trace_window": (0.0, 1.0), "loop": {"records": [], "schedule": []},
           "counters": {"begin": {"ds_serve_steps_total": 0},
                        "end": {"ds_serve_steps_total": 9}},
           "config": bench.config("evabyte-L6"), "peaks": None}
    for name in ("eva_decode_roofline", "eva_decode_share",
                 "eva_summary_row_share"):
        assert bench.reader(name).read(ctx) is None
        assert bench.reader(name).read({**ctx, "trace": None}) is None


def test_agreement_tool_reads_every_generated_position(eva_bench):
    spec = importlib.util.spec_from_file_location(
        "_evabyte_agreement", os.path.join(BENCH_DIR, "tools",
                                           "evabyte_agreement.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    # a prompt that closes two windows in prefill; one that ends 12 short of
    # the boundary at 96 and decodes across it
    line = tool.agreement(CELL, 11, manifest_path=eva_bench, allow_cpu=True,
                          lengths=((70, 6), (12, 30)))
    assert line["requests"] == [{"prompt": 70, "new": 6},
                                {"prompt": 84, "new": 30}]
    assert line["generated_positions"] == 36
    # bf16 program against the float32 reference: within the tolerance, and
    # both broken attentions far outside it (the bf16 residual is told apart
    # at the published depth and widths, on the chip: PERF.md)
    assert line["worst_steps"] <= line["tolerance_steps"], line
    assert line["worst_steps_all_heads_decode"] <= line["tolerance_steps"]
    assert line["caught"]["no_summaries"] and line["caught"]["stale_window"]
