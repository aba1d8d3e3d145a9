"""The runner end to end at a tiny size on the CPU (permitted by a function
argument, never by a flag), and the data-driven contract: a cell, a mix, a
configuration and a per-layer metric are added as FILES."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.run import run_cell

from conftest import BENCH_DIR, REPO_DIR, _dump, _load

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def check_line(line, want_metrics):
    assert CONTRACT_KEYS <= set(line)
    json.dumps(line)                                 # one JSON object
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is True, line["checks"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])
    assert want_metrics <= set(line["metrics"]), line["metrics"]
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)


def test_serve_cell_prints_its_end_to_end_metrics(tiny_bench):
    line = run_cell("tiny-gpt2.serve", 3, 1.5, False,
                    manifest_path=tiny_bench, allow_cpu=True)
    check_line(line, {"tokens_per_s", "ttft_p50_ms", "tpot_p50_ms",
                      "setup_s"})
    assert line["checks"]["compiles_in_window"] == 0
    assert set(line["checks"]["after_window_s"]) == {"drain", "reference"}
    assert line["checks"]["reference"]["requests_checked"] > 0


def test_train_cell_on_four_devices_prints_per_layer_metrics(tiny_bench):
    line = run_cell("tiny-gpt2.train", 3, 1.5, True,
                    manifest_path=tiny_bench, allow_cpu=True)
    # a CPU trace has no TPU plane: the trace's metrics are left out, not
    # faked from host time
    check_line(line, {"compiles_in_window", "train_step_p50_ms"})
    assert "train_device_idle_share" not in line["metrics"]
    assert "busy_s" not in line["device"]
    assert line["device"]["count"] == 4
    ref = line["checks"]["reference"]
    assert ref["ok"] and ref["weights"] == "initial"
    # the probe ran before any step: its loss is the untrained model's
    assert abs(ref["engine_loss"] - line["checks"]["first_loss"]) < 0.2
    assert ref["engine_loss"] > max(line["checks"]["last_losses"])


def test_cell_mix_configuration_and_metric_are_added_as_files(tiny_bench):
    """What a later PR does: new files, new manifest entries, no edit."""
    root = os.path.join(os.path.dirname(tiny_bench), "tinybench")
    before = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            before[p] = os.path.getmtime(p), os.path.getsize(p)

    cfg = _load(os.path.join(BENCH_DIR, "configs", "mistral-7b-L8.json"))
    cfg.update(hidden_size=64, intermediate_size=160, num_attention_heads=4,
               num_key_value_heads=2, num_hidden_layers=2, vocab_size=503)
    cfg["model_config"].update(
        vocab_size=503, hidden_size=64, intermediate_size=160, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=256)
    cfg["reference"] = "reference/new-model.py"
    _dump(cfg, os.path.join(root, "configs", "new-model.json"))
    shutil.copy(os.path.join(BENCH_DIR, "reference", "mistral.py"),
                os.path.join(root, "reference", "new-model.py"))
    mix = _load(os.path.join(root, "traffic", "chat-tiny.json"))
    mix.update(pattern_seed=7, max_total_tokens=48)
    mix["prompt_tokens"].update(median=20, sigma=0.5)
    _dump(mix, os.path.join(root, "traffic", "new-mix.json"))
    cell = _load(os.path.join(root, "workloads", "tiny-gpt2.serve.json"))
    cell.update(name="new-model.new-mix", config="new-model",
                traffic="new-mix")
    _dump(cell, os.path.join(root, "workloads", "new-model.new-mix.json"))
    with open(os.path.join(root, "layer_metrics", "new_iterations.py"),
              "w") as f:
        f.write('"""Serve engine: scheduler iterations run."""\n\n\n'
                "def read(ctx):\n"
                "    return len(ctx['loop']['iters']) or None\n")
    m = _load(tiny_bench)
    m["configs"].append({"name": "new-model", "source": "test",
                         "reduced": [], "why": "test",
                         "file": "tinybench/configs/new-model.json"})
    m["workloads"].append({"name": "new-model.new-mix", "config": "new-model",
                           "traffic": "new-mix", "chips": 1, "why": "test"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "tiny-gpt2.serve" in e.get("workloads", ()):
            e["workloads"].append("new-model.new-mix")
    m["per_layer"].append({"name": "new_iterations", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "Serve engine", "moves": "tpot_p50_ms",
                           "workloads": ["new-model.new-mix"]})
    _dump(m, tiny_bench)

    line = run_cell("new-model.new-mix", 5, 1.5, True,
                    manifest_path=tiny_bench, allow_cpu=True)
    check_line(line, {"new_iterations", "sched_occupancy_mean",
                      "sched_prefill_token_share", "gen_late_p99_ms"})
    # a dozen requests support no 90th percentile: left out, not reported
    assert line["attempted"] < 100 and "ttft_p90_ms" not in line["metrics"]
    for p, stamp in before.items():
        assert (os.path.getmtime(p), os.path.getsize(p)) == stamp, p


def test_ttft_p90_is_read_from_the_loop_where_ten_samples_lie_beyond():
    from types import SimpleNamespace as NS

    from benchmarks.lib.manifest import Bench
    from benchmarks.lib.stats import percentile

    read = Bench().reader("ttft_p90_ms").read
    due = [0.1 * i for i in range(150)]                   # 15 s of requests
    recs = [NS(t_first=d + 0.05 + 0.001 * i) for i, d in enumerate(due)]
    recs[3] = None                                        # one was refused
    loop = {"records": recs, "schedule": [NS(due_s=d) for d in due],
            "until_s": 15.0}
    want = [50.0 + i for i in range(150) if i != 3]
    assert read({"loop": loop, "trace_window": None}) == \
        pytest.approx(percentile(want, 90))
    # traced: only requests due a second before the profiler started
    loop["until_s"] = 14.0
    assert read({"loop": loop, "trace_window": (14.0, 15.0)}) == \
        pytest.approx(percentile([w for w in want if w < 180.0], 90))
    loop["until_s"] = 9.0                                 # 89 samples left
    assert read({"loop": loop, "trace_window": None}) is None


def test_run_py_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         "mistral-7b-L8.serve-chat", "--seed", "0", "--seconds", "1",
         "--trace",
         "0"], cwd=REPO_DIR, env=env, capture_output=True, text=True,
        timeout=120)
    assert r.returncode != 0
    assert "metrics" not in r.stdout and "{" not in r.stdout
    assert "needs a TPU" in r.stderr


def test_unknown_cell_is_an_error(tiny_bench):
    from benchmarks.lib.manifest import ManifestError

    with pytest.raises(ManifestError, match="no workload"):
        run_cell("no-such.cell", 0, 1.0, False, manifest_path=tiny_bench,
                 allow_cpu=True)
