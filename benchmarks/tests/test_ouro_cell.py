"""The Ouro cell's files: the manifest's new entries resolve; the
configuration against the catalog's row; the cell through the runner and
``tools/ouro_agreement.py`` at a tiny size on the CPU (hidden 64, four heads
of 16, two layers run four times, page 16, chunks of 16); the three new
readers on a recorded trace and recorded counters."""

import importlib.util
import json
import os
import shutil

import pytest

from benchmarks.lib import trace_reduce
from benchmarks.lib.manifest import Bench
from benchmarks.run import run_cell

from conftest import BENCH_DIR, TESTS_DIR, _dump, _load

CELL = "tiny-ouro.serve"
REAL = "ouro-2.6b-L12.serve-reason-768"
CONFIG = "ouro-2.6b-L12"
NEW = ("loop_passes_traced", "loop_attn_decode_roofline",
       "loop_kv_bytes_per_token_kb")
TINY = dict(vocab_size=503, hidden_size=64, intermediate_size=160,
            num_layers=2, num_heads=4, num_kv_heads=4, head_dim=16,
            max_seq_len=256)


@pytest.fixture
def ouro_bench(tiny_bench):
    root = os.path.join(os.path.dirname(tiny_bench), "tinybench")
    cfg = _load(os.path.join(BENCH_DIR, "configs", CONFIG + ".json"))
    cfg.update(hidden_size=64, intermediate_size=160, num_attention_heads=4,
               num_key_value_heads=4, num_hidden_layers=2, vocab_size=503)
    cfg["model_config"].update(TINY)
    _dump(cfg, os.path.join(root, "configs", "tiny-ouro.json"))
    shutil.copy(os.path.join(BENCH_DIR, "reference", "ouro.py"),
                os.path.join(root, "reference", "ouro.py"))
    mix = _load(os.path.join(BENCH_DIR, "traffic", "reason-768.json"))
    # chunks of 16: prompts inside one bucket and past several chunks
    mix["prompt_tokens"].update(median=14, min=4, max=40)
    mix["output_tokens"].update(median=12, min=4, max=20)
    mix["max_total_tokens"] = 64
    _dump(mix, os.path.join(root, "traffic", "reason-tiny.json"))
    cell = _load(os.path.join(BENCH_DIR, "workloads", REAL + ".json"))
    cell.update(name=CELL, config="tiny-ouro", traffic="reason-tiny",
                rate_rps=6.0, trace_seconds=0.5)
    cell["engine"].update(num_slots=4, prefill_chunk=16, max_out_tokens=64,
                          kv_pool_tokens=256, kv_page_tokens=16,
                          decode_block_tokens=4,
                          # at hidden 64 a bf16 stream alone moves the logits
                          # by more steps than ``correct`` allows: the tiny
                          # cell checks paths, in float32
                          dtype="fp32")
    _dump(cell, os.path.join(root, "workloads", CELL + ".json"))
    m = _load(tiny_bench)
    real = _load(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json"))
    m["configs"].append({"name": "tiny-ouro", "source": "test",
                         "reduced": [], "why": "test",
                         "file": "tinybench/configs/tiny-ouro.json"})
    m["workloads"].append({"name": CELL, "config": "tiny-ouro",
                           "traffic": "reason-tiny", "chips": 1,
                           "why": "test"})
    # the cell reports what the real one reports
    lists = {e["name"]: e.get("workloads")
             for s in ("end_to_end", "per_layer") for e in real[s]}
    for e in m["end_to_end"] + m["per_layer"]:
        if REAL in (lists[e["name"]] or ()):
            e["workloads"] = [w for w in e["workloads"] if w != REAL] + [CELL]
    _dump(m, tiny_bench)
    return tiny_bench


# --------------------------------------------------------------- manifest
def test_the_manifests_new_entries_resolve():
    bench = Bench()
    m = bench.manifest
    entry = bench.workload_entry(REAL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "reason-768", 1)
    assert len(entry["why"]) <= 200
    assert bench.config_entry(CONFIG)["reduced"] == ["num_hidden_layers"]
    cell = bench.cell(REAL)
    engine = cell["engine"]
    # every slot can hold the longest request: no preemption
    assert engine["kv_pool_tokens"] == engine["num_slots"] \
        * engine["max_out_tokens"]
    assert (engine["prefill_chunk"], engine["max_prefill_chunks"],
            engine["decode_block_tokens"], engine["max_out_tokens"],
            engine["kv_page_tokens"]) == (256, 2, 8, 1280, 256)
    assert engine["num_slots"] in (16, 12)      # 12: the named fallback
    mix = bench.traffic(cell["traffic"])
    assert (mix["pattern_seed"], mix["kind"], mix["max_total_tokens"]) == (
        57, "serve_open_loop", 1280)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 128,
                                    "sigma": 0.8, "min": 16, "max": 512}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 384,
                                    "sigma": 0.5, "min": 64, "max": 768}
    assert hasattr(bench.reference(CONFIG), "logits_rows")
    # the three new metrics sit together, in this order, behind PR 55's
    # entry and listed for this cell.  Their distance from the list's end is
    # NOT asserted, nor that no other cell is listed: a later PR has to be
    # able to add its own
    names = [e["name"] for e in m["per_layer"]]
    first = names.index(NEW[0])
    assert names[first:first + 3] == list(NEW)
    assert first > names.index("jit_host_ms_in_window")
    layers = {e["name"]: (e["layer"], e["moves"], e["source"], e["unit"])
              for e in m["per_layer"][first:first + 3]}
    assert layers == {
        "loop_passes_traced": ("Model", "tpot_p50_ms", "device_trace",
                               "count"),
        "loop_attn_decode_roofline": ("Kernels", "tpot_p50_ms",
                                      "device_trace", "%"),
        "loop_kv_bytes_per_token_kb": ("Serve engine", "tokens_per_s",
                                       "program_counter", "KB")}
    for e in m["per_layer"][first:first + 3]:
        assert REAL in e["workloads"]
        assert callable(bench.reader(e["name"]).read)
    reported = {e["name"] for e in bench.metrics_for("end_to_end", REAL)}
    # ttft_p50_ms spread by 10% over twelve seeds (PERF.md section 4)
    assert reported == {"tokens_per_s", "tpot_p50_ms", "setup_s"}
    per_layer = {e["name"] for e in bench.metrics_for("per_layer", REAL)}
    assert {"sched_occupancy_mean", "decode_step_device_ms",
            "kernel_time_share", "device_idle_share", "host_work_share",
            "idle_host_work_share", "idle_fetch_share",
            "decode_rows_live_share", "attn_decode_share",
            "compiles_in_window", "peak_hbm_gb", *NEW} <= per_layer
    # its reader counts num_layers calls a step: a quarter of this model's
    assert "flash_decode_paged_roofline" not in per_layer
    # an accepted test holds its list to the nine cells before this one
    # (benchmarks/tests/test_setup_phases.py: a ``benchmark`` PR's to lift)
    assert "jit_host_ms_in_window" not in per_layer
    # a metric that moves an end-to-end metric the cell does not report is
    # not listed for it
    moves = {e["name"]: e["moves"] for e in m["per_layer"]}
    assert {moves[n] for n in per_layer} <= reported


def test_the_configuration_file_states_the_published_widths_and_the_cut():
    cfg = _load(os.path.join(BENCH_DIR, "configs", CONFIG + ".json"))
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ouro-2.6B")
        assert cfg["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == set(cfg["reduced"])
    assert set(cfg["reduced"]) == {"num_hidden_layers"}
    assert (cfg["num_hidden_layers"], cfg["total_ut_steps"],
            cfg["early_exit_threshold"]) == (12, 4, 1)
    mc = cfg["model_config"]
    assert (mc["hidden_size"], mc["intermediate_size"], mc["num_heads"],
            mc["num_kv_heads"], mc["head_dim"], mc["vocab_size"],
            mc["num_layers"], mc["total_ut_steps"]) == (
                2048, 5632, 16, 16, 128, 49152, 12, 4)
    assert (mc["norm_eps"], mc["rope_theta"], mc["sandwich_norm"],
            mc["loop_exit_gate"], mc["early_exit_threshold"],
            mc["tie_embeddings"], mc["fp32_residual"]) == (
                1e-6, 1e6, True, True, 1.0, False, True)
    assert {"norms", "final_norm", "cache_index", "rotary_positions",
            "exit_gate", "early_exit_threshold", "weights",
            "residual_dtype"} <= set(cfg["assumed"])
    assert {"early_exit", "training", "checkpoint_import", "parallelism",
            "cache_sharing"} <= set(cfg["left_out"])
    assert "ring" in cfg["deployment"] and "384 KB" in cfg["bytes"]["cache"]
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert cfg["parameters"] == 12 * layer + 2 * 49152 * 2048 + 2048 + 2049

    from deepspeed_tpu.models import ModelConfig
    built = ModelConfig(**mc)
    assert built.cache_layers == 48 and built.is_looped


# ------------------------------------------------------- the tiny cell runs
def test_the_cell_runs_and_is_correct(ouro_bench):
    line = run_cell(CELL, 2**31 + 57, 1.5, True, manifest_path=ouro_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["compiles_in_window"] == 0
    assert line["checks"]["preemptions"] == 0
    assert line["checks"]["reference"]["requests_checked"] > 0
    m = line["metrics"]
    # no device plane on the CPU: the trace's metrics are left out ...
    assert not {"loop_passes_traced", "loop_attn_decode_roofline",
                "attn_decode_share", "decode_step_device_ms"} & set(m)
    # ... the gauge's is there: K and V of 4 heads x 16 in float32, in
    # 4 x 2 cache layers
    assert m["loop_kv_bytes_per_token_kb"]["value"] == \
        2 * 8 * 4 * 16 * 4 / 1024
    assert 0 < m["decode_rows_live_share"]["value"] <= 100
    assert {"sched_occupancy_mean", "compiles_in_window"} <= set(m)


def test_untraced_run_reports_the_end_to_end_metrics(ouro_bench):
    line = run_cell(CELL, 57, 1.5, False, manifest_path=ouro_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert {"tokens_per_s", "tpot_p50_ms", "setup_s"} <= set(line["metrics"])


@pytest.mark.parametrize("control", [None, "shared_cache_layer",
                                     "three_passes"])
def test_agreement_tool_passes_the_program_and_fails_the_controls(
        ouro_bench, control):
    spec = importlib.util.spec_from_file_location(
        "_ouro_agreement", os.path.join(BENCH_DIR, "tools",
                                        "ouro_agreement.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    line = tool.agreement(CELL, 11, 3, control, manifest_path=ouro_bench,
                          allow_cpu=True)
    assert line["requests"] == 3 and line["generated_positions"] >= 3 * 4
    assert line["control"] == control
    # float32 both sides here: far inside the limits, or far outside
    assert line["ok"] == (control is None), line
    if control is None:
        assert line["worst_steps"] < 0.1
    else:
        assert line["worst_steps"] > 2 * line["limit_worst_steps"]


# ------------------------------------------------------------- the readers
SERVE_FIXTURE = os.path.join(TESTS_DIR, "fixtures",
                             "v5e_serve_mistral_40ms.json.gz")


def reader_ctx(summary, mc, **over):
    ctx = {"trace": summary, "bench": Bench(),
           "cell": {"engine": {"decode_block_tokens": 8}},
           "config": {"model_config": mc},
           "trace_window": (0.0, 1.0),
           # (begin, end, occupied, queued, context tokens) an iteration
           "loop": {"iters": [(0.1, 0.2, 4, 0, 1800), (2.0, 2.1, 4, 0, 900)]},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "counters": {"begin": {}, "end": {}}}
    ctx.update(over)
    return ctx


def test_the_trace_readers_on_a_recorded_trace():
    """Mistral's recorded 40 ms: ONE decode block of 8 steps of which 3 lie
    inside, 24 calls of the attention kernel over 8 layers.  Read as a model
    of 8 layers that is 24 / (1 x 8 x 8) calls a layer a step (the window
    cuts the block: an edge the docstring names); read as a model whose 2
    layers run 4 times, the same calls are 4 x that."""
    bench = Bench()
    s = trace_reduce.summarize(trace_reduce.load_events(SERVE_FIXTURE))
    passes = bench.reader("loop_passes_traced").read
    roof = bench.reader("loop_attn_decode_roofline").read
    once = dict(num_layers=8, num_heads=32, num_kv_heads=8, head_dim=128,
                hidden_size=4096)
    looped = dict(once, num_layers=2, total_ut_steps=4)
    assert passes(reader_ctx(s, once)) == pytest.approx(24 / 64)
    assert passes(reader_ctx(s, looped)) == pytest.approx(4 * 24 / 64)
    # the roofline: K and V of 1,800 context tokens x 8 steps, in 8 cache
    # layers either way, over the kernel's 17.08 ms
    want = 100 * (2 * 1800 * 8 * 8 * 128 * 2 * 8 / 819e9) / 17084545e-9
    assert roof(reader_ctx(s, once)) == pytest.approx(want)
    assert roof(reader_ctx(s, looped)) == pytest.approx(want)
    # ... and is the accepted metric's own number where the stack runs once
    assert roof(reader_ctx(s, once)) == pytest.approx(
        bench.reader("flash_decode_paged_roofline").read(
            reader_ctx(s, once)))
    # None without the kernel, the program, a trace or live rows
    bare = dict(s, kernels={k: v for k, v in s["kernels"].items()
                            if k != "flash_decode_paged"})
    for read in (passes, roof):
        assert read(reader_ctx(bare, once)) is None
        assert read(reader_ctx(None, once)) is None
    assert passes(reader_ctx(dict(s, programs={}), once)) is None
    assert roof(reader_ctx(s, once, trace_window=None)) is None
    assert roof(reader_ctx(s, once, trace_window=(5.0, 6.0))) is None


def test_the_gauge_reader_on_recorded_counters():
    read = Bench().reader("loop_kv_bytes_per_token_kb").read
    ctx = lambda end: reader_ctx(None, {}, counters={"begin": {}, "end": end})
    assert read(ctx({"ds_serve_kv_bytes_per_token": 393216})) == 384.0
    assert read(ctx({"ds_serve_kv_bytes_per_token": 32768})) == 32.0
    # a parent without the gauge, or a registry that was never on
    assert read(ctx({"ds_serve_steps_total": 5})) is None
    assert read(reader_ctx(None, {}, counters={})) is None
