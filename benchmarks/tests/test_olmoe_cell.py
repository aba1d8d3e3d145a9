"""The OLMoE cell's files through the runner and through
``tools/olmoe_agreement.py`` at a tiny size on the CPU: the configuration's
``model_config`` builds, the driver's ``correct`` holds for a
mixture-of-experts model (dropless: a dropped token would sit hundreds of
steps below the reference's best logit), and the agreement tool's
bookkeeping yields every generated position."""

import importlib.util
import os
import shutil

import pytest

from benchmarks.run import run_cell

from conftest import BENCH_DIR, _dump, _load

CELL = "tiny-olmoe.serve"


@pytest.fixture
def olmoe_bench(tiny_bench):
    root = os.path.join(os.path.dirname(tiny_bench), "tinybench")
    cfg = _load(os.path.join(BENCH_DIR, "configs", "olmoe-1b-7b-L8.json"))
    cfg.update(hidden_size=64, intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=4, num_hidden_layers=2, vocab_size=503,
               num_experts=8, num_experts_per_tok=2)
    cfg["model_config"].update(
        vocab_size=503, hidden_size=64, intermediate_size=32, num_layers=2,
        num_heads=4, num_kv_heads=4, head_dim=16, max_seq_len=256,
        num_experts=8, num_experts_per_tok=2)
    _dump(cfg, os.path.join(root, "configs", "tiny-olmoe.json"))
    shutil.copy(os.path.join(BENCH_DIR, "reference", "olmoe.py"),
                os.path.join(root, "reference", "olmoe.py"))
    cell = _load(os.path.join(root, "workloads", "tiny-gpt2.serve.json"))
    cell.update(name=CELL, config="tiny-olmoe")
    _dump(cell, os.path.join(root, "workloads", CELL + ".json"))
    m = _load(tiny_bench)
    m["configs"].append({"name": "tiny-olmoe", "source": "test",
                         "reduced": [], "why": "test",
                         "file": "tinybench/configs/tiny-olmoe.json"})
    m["workloads"].append({"name": CELL, "config": "tiny-olmoe",
                           "traffic": "chat-tiny", "chips": 1, "why": "test"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "tiny-gpt2.serve" in e.get("workloads", ()):
            e["workloads"].append(CELL)
    _dump(m, tiny_bench)
    return tiny_bench


def test_the_cell_runs_and_is_correct(olmoe_bench):
    line = run_cell(CELL, 2**31 + 5, 1.5, True, manifest_path=olmoe_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["compiles_in_window"] == 0
    # no device plane on the CPU: the trace's metrics are left out
    assert "fused_moe_mlp_roofline" not in line["metrics"]
    assert {"sched_occupancy_mean", "gen_late_p99_ms"} <= set(line["metrics"])


def test_agreement_tool_reads_every_generated_position(olmoe_bench):
    spec = importlib.util.spec_from_file_location(
        "_olmoe_agreement", os.path.join(BENCH_DIR, "tools",
                                         "olmoe_agreement.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    line = tool.agreement(CELL, 11, 3, manifest_path=olmoe_bench,
                          allow_cpu=True)
    assert line["requests"] == 3 and line["generated_positions"] >= 3 * 4
    assert line["routing_sets"] > 0
    # bf16 program against the float32 reference: within the tolerance, and
    # nearer still where the reference routes as the program did
    assert line["ok"], line
    assert line["worst_steps_given_routing"] <= \
        line["worst_steps_free_running"] + 1e-6
