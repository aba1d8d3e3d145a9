"""The Jamba2-3B cell's files through the runner and through
``tools/jamba2_agreement.py`` at a tiny size on the CPU (hidden 32, 64
channels over a state of 16, a ``dt`` bottleneck of 6, four query heads over
ONE key-value head of 8, page 8, 14 layers with attention at layer 7: the
published pattern's period): the configuration's ``model_config`` builds, the
driver's ``correct`` holds on a mix whose prompts end on a chunk, inside a
padded bucket and past several chunks, nothing compiles inside the window,
the program's counters reach the readers; the file's numbers are the catalog
row's key by key and NOTHING is reduced; the parameter count; the manifest
lists the cell by MEMBERSHIP."""

import importlib.util
import json
import os
import shutil

import pytest

from benchmarks.run import run_cell

from conftest import BENCH_DIR, _dump, _load

CELL = "tiny-jamba2.serve"
REAL = "jamba2-3b.serve-reason-768"
CONFIG = "jamba2-3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's ``config`` (AI21-Jamba2-3B), kept here because the
# catalog is not part of a checkout
ROW = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
    "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}


def real_config():
    return _load(os.path.join(BENCH_DIR, "configs", CONFIG + ".json"))


def pattern(layers, period, offset):
    out = []
    for i in range(layers):
        out += ["full_attention" if i % period == offset else "mamba1", "mlp"]
    return out


def tiny_config():
    cfg = real_config()
    cfg.update(hidden_size=32, intermediate_size=48, num_attention_heads=4,
               num_hidden_layers=14, vocab_size=96, mamba_dt_rank=6)
    cfg["model_config"].update(
        vocab_size=96, hidden_size=32, intermediate_size=48, num_layers=28,
        num_heads=4, head_dim=8, max_seq_len=256, ssm_inner_size=64,
        ssm_dt_rank=6, ssm_chunk=8, layer_types=pattern(14, 14, 7))
    return cfg


@pytest.fixture
def jamba_bench(tiny_bench):
    root = os.path.join(os.path.dirname(tiny_bench), "tinybench")
    _dump(tiny_config(), os.path.join(root, "configs", "tiny-jamba2.json"))
    shutil.copy(os.path.join(BENCH_DIR, "reference", "jamba2.py"),
                os.path.join(root, "reference", "jamba2.py"))
    mix = _load(os.path.join(BENCH_DIR, "traffic", "reason-768.json"))
    # chunks of 16: prompts inside one bucket and past several chunks
    mix["prompt_tokens"].update(median=20, sigma=0.8, min=4, max=70)
    mix["output_tokens"].update(median=20, min=8, max=40)
    mix["max_total_tokens"] = 112
    _dump(mix, os.path.join(root, "traffic", "reason-tiny.json"))
    cell = _load(os.path.join(BENCH_DIR, "workloads", REAL + ".json"))
    cell.update(name=CELL, config="tiny-jamba2", traffic="reason-tiny",
                rate_rps=8.0, trace_seconds=0.5)
    cell["engine"].update(num_slots=4, prefill_chunk=16, max_out_tokens=128,
                          kv_pool_tokens=512, kv_page_tokens=8,
                          decode_block_tokens=4,
                          # at hidden 32 a bf16 stream alone moves the logits
                          # by more steps than ``correct`` allows: the tiny
                          # cell checks paths, in float32
                          dtype="fp32")
    _dump(cell, os.path.join(root, "workloads", CELL + ".json"))
    m = _load(tiny_bench)
    real = _load(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json"))
    m["configs"].append({"name": "tiny-jamba2", "source": "test",
                         "reduced": [], "why": "test",
                         "file": "tinybench/configs/tiny-jamba2.json"})
    m["workloads"].append({"name": CELL, "config": "tiny-jamba2",
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


def test_the_cell_runs_and_is_correct(jamba_bench):
    line = run_cell(CELL, 2**31 + 66, 1.5, True, manifest_path=jamba_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["compiles_in_window"] == 0
    assert line["checks"]["reference"]["requests_checked"] > 0
    # no device plane on the CPU: the trace's metrics are left out ...
    m = line["metrics"]
    assert not {"mamba1_decode_share", "mamba1_decode_roofline",
                "selective_scan_share", "selective_scan_roofline",
                "attn_decode_share"} & set(m)
    # ... the counters' are there: the XLA form of the state update visits
    # every slot
    assert 0 < m["state_rows_live_share"]["value"] <= 100
    assert m["state_rows_live_share"]["value"] == pytest.approx(
        m["decode_rows_live_share"]["value"], rel=0.05)
    assert {"sched_occupancy_mean", "host_work_share"} <= set(m)


def test_untraced_run_reports_the_end_to_end_metrics(jamba_bench):
    line = run_cell(CELL, 66, 1.5, False, manifest_path=jamba_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert {"tokens_per_s", "tpot_p50_ms", "setup_s"} <= set(line["metrics"])


def test_the_manifest_lists_the_cell_where_its_readers_read():
    """By MEMBERSHIP: on the lists of the generic readers that read true for
    it and of its own four, NOT on those that key on ``mamba2``, on
    ``ssm_decode_step`` or on experts, nor on the attention kernel's
    rooflines (two layers at 1 KB a token: ``attn_decode_share`` says what
    they take)."""
    from benchmarks.lib.manifest import Bench

    bench = Bench()
    names = {m["name"] for m in bench.metrics_for("per_layer", REAL)}
    assert {"mamba1_decode_share", "mamba1_decode_roofline",
            "selective_scan_share", "selective_scan_roofline",
            "attn_decode_share", "state_rows_live_share",
            "decode_rows_live_share", "sched_occupancy_mean",
            "decode_step_device_ms", "kernel_time_share",
            "device_idle_share", "host_work_share", "idle_host_work_share",
            "idle_fetch_share", "peak_hbm_gb", "compiles_in_window"} <= names
    assert not {"ssm_decode_share", "ssm_decode_roofline",
                "mixer_attn_decode_roofline", "flash_decode_paged_roofline",
                "fused_moe_mlp_roofline", "moe_decode_share",
                "moe_experts_hit_share", "kda_decode_share"} & names
    assert {"tokens_per_s", "tpot_p50_ms", "setup_s"} == {
        m["name"] for m in bench.metrics_for("end_to_end", REAL)}
    entry = bench.workload_entry(REAL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert entry["config"] == CONFIG and entry["traffic"] == "reason-768"
    assert bench.config_entry(CONFIG)["reduced"] == []
    # the new metrics' entries, wherever they stand (never a position: a
    # later PR appends behind them)
    by_name = {m["name"]: m for m in bench.manifest["per_layer"]}
    for name, layer, moves in (
            ("mamba1_decode_share", "Model", "tpot_p50_ms"),
            ("mamba1_decode_roofline", "Kernels", "tpot_p50_ms"),
            ("selective_scan_share", "Model", "tokens_per_s"),
            ("selective_scan_roofline", "Kernels", "tokens_per_s")):
        e = by_name[name]
        assert (e["unit"], e["source"], e["layer"], e["moves"]) == (
            "%", "device_trace", layer, moves)
        assert REAL in e["workloads"]


def test_the_files_numbers_are_the_catalog_rows_key_by_key():
    """Every key of the row's ``config`` is in the file under the same name
    with the same value: nothing is reduced."""
    cfg = real_config()
    if os.path.isfile(CATALOG):        # the copy above is the row's
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "AI21-Jamba2-3B")
        assert row["config"] == ROW and row["source_url"] == cfg["source"]
    assert {k for k, v in ROW.items() if cfg[k] != v} == set()
    assert cfg["reduced"] == {}
    # ... and what the program is built from says the same
    mc = cfg["model_config"]
    assert mc["layer_types"] == pattern(28, 14, 7)
    assert (mc["hidden_size"], mc["ssm_inner_size"], mc["ssm_dt_rank"],
            mc["ssm_state_size"], mc["ssm_conv_kernel"], mc["num_heads"],
            mc["num_kv_heads"], mc["head_dim"], mc["intermediate_size"],
            mc["vocab_size"], mc["norm_eps"], mc["max_seq_len"]) == (
        2560, 5120, 160, 16, 4, 20, 1, 128, 8192, 65536, 1e-6, 262144)
    assert mc["tie_embeddings"] and mc["glu"] and mc["activation"] == "silu"
    assert mc["ssm_inner_norms"] is True
    assert mc["num_layers"] == 2 * cfg["num_hidden_layers"] == 56


def test_the_published_widths_give_the_stated_parameter_count():
    """From the file's published keys alone; and the program's own arrays
    hold exactly that many (nothing is padded)."""
    import math

    import jax

    from deepspeed_tpu.models import CausalLM, ModelConfig

    cfg = real_config()
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    di, N = cfg["mamba_expand"] * D, cfg["mamba_d_state"]
    R, K = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    mixer = D * 2 * di + di * K + di + di * (R + 2 * N) + R * di + di \
        + di * N + di + di * D + (R + 2 * N)
    mlp = 3 * D * F
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = D // heads
    attention = 2 * D * heads * dh + 2 * D * kv * dh
    layers = cfg["num_hidden_layers"]
    n_attn = sum(i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
                 for i in range(layers))
    total = (layers - n_attn) * (mixer + mlp + 2 * D) \
        + n_attn * (attention + mlp + 2 * D) + cfg["vocab_size"] * D + D
    assert (mixer, mlp, attention, n_attn) == (41241792, 62914560, 13762560,
                                               2)
    assert mixer + mlp + 2 * D == 104161472
    assert total == cfg["parameters"] == 3029337472
    shapes = jax.eval_shape(CausalLM(ModelConfig(**cfg["model_config"]),
                                     None).init, jax.random.PRNGKey(0))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(shapes)) == total


def test_agreement_tool_reads_every_generated_position(jamba_bench):
    spec = importlib.util.spec_from_file_location(
        "_jamba2_agreement", os.path.join(BENCH_DIR, "tools",
                                          "jamba2_agreement.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    # inside one padded bucket; exactly two chunks; 16 + 16 + 16 + 2
    line = tool.agreement(CELL, 11, manifest_path=jamba_bench,
                          allow_cpu=True,
                          lengths=((9, 5), (32, 6), (50, 20)),
                          wrong=tool.WRONG + ("bf16_a",))
    assert line["requests"] == [{"prompt": 9, "new": 5},
                                {"prompt": 32, "new": 6},
                                {"prompt": 50, "new": 20}]
    assert line["generated_positions"] == 31
    # a float32 program within reach of the float32 reference, the broken
    # references far from it (the limits are set at the published widths,
    # on the chip: PERF.md)
    far = line["worst_steps_against_wrong_reference"]
    assert line["worst_steps"] < min(far[k] for k in ("no_dt_norm",
                                                      "no_dt_bias")), line
    # the slots' states after the last token fed, against the reference's
    # recurrence: float32 noise, and a state or an A kept in bf16 far from it
    wrong_state = line["state_difference_against_wrong_reference"]
    assert set(wrong_state) == set(tool.WRONG) | {"bf16_a"}
    assert line["state_difference"] < 1e-4 < 10 * 1e-4 < min(
        wrong_state.values()), line
    assert [r["prompt"] for r in line["worst_rms_and_state_by_request"]] == [
        9, 32, 50]
    assert line["served_token_steps_below_best"] == 0.0
