"""The Nemotron-3-Nano cell's files through the runner and through
``tools/nemotron3_nano_agreement.py`` at a tiny size on the CPU (hidden 64,
four Mamba-2 heads of 16 in two groups over a state of 16, blocks of 8 rows,
four query heads over two key-value heads of 16, page 8, a router of 8
experts of which 4 are held, the published pattern ``MEMEM*EME``): the
configuration's ``model_config`` builds, the driver's ``correct`` holds on a
mix whose prompts end on a chunk, inside a padded bucket and past several
chunks (a state not carried, or K/V rows read through a wrong page, would
sit far below the reference's best logit), nothing compiles inside the
window, the program's counters reach the readers; the file's numbers are the
catalog row's key by key and ``reduced`` names every key that is not; the
parameter count; the manifest lists the cell by MEMBERSHIP."""

import importlib.util
import json
import os
import shutil

import pytest

from benchmarks.run import run_cell

from conftest import BENCH_DIR, _dump, _load

CELL = "tiny-nemotron.serve"
REAL = "nemotron3-nano-L9-ep2.serve-reason-4k"
CONFIG = "nemotron3-nano-L9-ep2"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=48, num_heads=4,
            num_kv_heads=2, head_dim=16, max_seq_len=256, num_experts=4,
            moe_router_experts=8, num_experts_per_tok=2, ssm_num_heads=4,
            ssm_head_dim=16, ssm_groups=2, ssm_state_size=16, ssm_chunk=8,
            shared_intermediate_size=96)
# the catalog row's ``config`` (NVIDIA-Nemotron-3-Nano-30B-A3B-BF16), kept
# here because the catalog is not part of a checkout
ROW = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}


def real_config():
    return _load(os.path.join(BENCH_DIR, "configs", CONFIG + ".json"))


def tiny_config():
    cfg = real_config()
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, n_routed_experts=4, vocab_size=96,
               mamba_num_heads=4, mamba_head_dim=16, n_groups=2,
               ssm_state_size=16, num_experts_per_tok=2)
    cfg["expert_parallel"].update(router_experts=8)
    cfg["model_config"].update(TINY)
    return cfg


@pytest.fixture
def nemotron_bench(tiny_bench):
    root = os.path.join(os.path.dirname(tiny_bench), "tinybench")
    _dump(tiny_config(), os.path.join(root, "configs", "tiny-nemotron.json"))
    shutil.copy(os.path.join(BENCH_DIR, "reference", "nemotron3_nano.py"),
                os.path.join(root, "reference", "nemotron3_nano.py"))
    mix = _load(os.path.join(BENCH_DIR, "traffic", "reason-4k.json"))
    # chunks of 16: prompts inside one bucket and past several chunks
    mix["prompt_tokens"].update(median=20, sigma=0.8, min=4, max=70)
    mix["output_tokens"].update(median=20, min=8, max=40)
    mix["max_total_tokens"] = 112
    _dump(mix, os.path.join(root, "traffic", "reason-tiny.json"))
    cell = _load(os.path.join(BENCH_DIR, "workloads", REAL + ".json"))
    cell.update(name=CELL, config="tiny-nemotron", traffic="reason-tiny",
                rate_rps=4.0, trace_seconds=0.5)
    cell["engine"].update(num_slots=4, prefill_chunk=16, max_out_tokens=128,
                          kv_pool_tokens=512, kv_page_tokens=8,
                          decode_block_tokens=4,
                          # at hidden 64 a bf16 stream alone moves the logits
                          # (all near 0) by more steps than ``correct``
                          # allows: the tiny cell checks paths, in float32
                          dtype="fp32")
    _dump(cell, os.path.join(root, "workloads", CELL + ".json"))
    m = _load(tiny_bench)
    real = _load(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json"))
    m["configs"].append({"name": "tiny-nemotron", "source": "test",
                         "reduced": [], "why": "test",
                         "file": "tinybench/configs/tiny-nemotron.json"})
    m["workloads"].append({"name": CELL, "config": "tiny-nemotron",
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


def test_the_cell_runs_and_is_correct(nemotron_bench):
    line = run_cell(CELL, 2**31 + 63, 1.5, True, manifest_path=nemotron_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["compiles_in_window"] == 0
    assert line["checks"]["reference"]["requests_checked"] > 0
    # no device plane on the CPU: the trace's metrics are left out ...
    assert not {"ssm_decode_share", "ssm_decode_roofline",
                "attn_decode_share"} & set(line["metrics"])
    # ... the counters' are there: the reference form of the state kernel
    # visits every slot, and four of the router's eight experts are held
    m = line["metrics"]
    assert 0 < m["state_rows_live_share"]["value"] <= 100
    assert m["state_rows_live_share"]["value"] == pytest.approx(
        m["decode_rows_live_share"]["value"], rel=0.05)
    assert 0 < m["moe_local_assignment_share"]["value"] < 100
    assert 0 < m["moe_experts_hit_share"]["value"] <= 100
    assert {"sched_occupancy_mean", "host_work_share"} <= set(m)


def test_untraced_run_reports_the_end_to_end_metrics(nemotron_bench):
    line = run_cell(CELL, 63, 1.5, False, manifest_path=nemotron_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert {"tokens_per_s", "tpot_p50_ms", "setup_s"} <= set(line["metrics"])


def test_the_manifest_lists_the_cell_where_its_readers_read():
    """By MEMBERSHIP (never a position, never a whole list): the cell is on
    the lists of the readers that find something in it and NOT on those that
    would count what it has not (``flash_decode_paged_roofline`` a call in
    every layer; ``hybrid_attn_decode_roofline`` and the KDA readers a
    ``linear_attention`` layer: ``hybrid_costs.full_layers`` reads 0 for
    this pattern, so the attention kernel's roofline is a reader of this
    form's own, ``mixer_attn_decode_roofline``;
    ``attn_keys_fetched_fill_share``, whose counters this cell
    does move, because ``test_attn_keys_fetched_fill.py:52`` holds that list
    whole)."""
    from benchmarks.lib.manifest import Bench

    bench = Bench()
    names = {m["name"] for m in bench.metrics_for("per_layer", REAL)}
    assert {"ssm_decode_share", "ssm_decode_roofline",
            "mixer_attn_decode_roofline",
            "attn_decode_share", "state_rows_live_share",
            "fused_moe_mlp_roofline", "moe_decode_share",
            "moe_experts_hit_share", "moe_local_assignment_share",
            "decode_rows_live_share", "sched_occupancy_mean", "decode_step_device_ms",
            "kernel_time_share", "device_idle_share", "host_work_share",
            "idle_host_work_share", "idle_fetch_share", "peak_hbm_gb",
            "compiles_in_window"} <= names
    assert not {"flash_decode_paged_roofline", "hybrid_attn_decode_roofline",
                "kda_decode_share", "kda_decode_roofline", "mla_decode_share",
                "jit_host_ms_in_window",
                "attn_keys_fetched_fill_share"} & names
    assert {"tokens_per_s", "tpot_p50_ms", "setup_s"} <= {
        m["name"] for m in bench.metrics_for("end_to_end", REAL)}
    entry = bench.workload_entry(REAL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert entry["config"] == CONFIG and entry["traffic"] == "reason-4k"
    assert bench.config_entry(CONFIG)["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    # the new metrics' entries, wherever they stand
    by_name = {m["name"]: m for m in bench.manifest["per_layer"]}
    for name, layer in (("ssm_decode_share", "Model"),
                        ("ssm_decode_roofline", "Kernels"),
                        ("mixer_attn_decode_roofline", "Kernels")):
        e = by_name[name]
        assert (e["unit"], e["better"], e["source"], e["layer"],
                e["moves"]) == ("%", "higher", "device_trace", layer,
                                "tpot_p50_ms")
        assert REAL in e["workloads"]


def test_the_files_numbers_are_the_catalog_rows_key_by_key():
    """Every key of the row's ``config`` is in the file under the same name;
    a number (or any other value) that differs is named in ``reduced``, with
    the published value kept beside it; the widths are all as published."""
    cfg = real_config()
    if os.path.isfile(CATALOG):        # the copy above is the row's
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert row["config"] == ROW and row["source_url"] == cfg["source"]
    changed = {k for k, v in ROW.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"}
    for k in changed:
        assert cfg[k + "_published"] == ROW[k]
    assert cfg["hybrid_override_pattern"] == \
        ROW["hybrid_override_pattern"][:cfg["num_hidden_layers"]] == \
        "MEMEM*EME"
    # ... and what the program is built from says the same
    mc = cfg["model_config"]
    letters = {"mamba2": "M", "experts": "E", "full_attention": "*"}
    assert "".join(letters[t] for t in mc["layer_types"]) == \
        cfg["hybrid_override_pattern"]
    assert (mc["hidden_size"], mc["ssm_num_heads"], mc["ssm_head_dim"],
            mc["ssm_groups"], mc["ssm_state_size"], mc["ssm_conv_kernel"],
            mc["ssm_chunk"], mc["num_heads"], mc["num_kv_heads"],
            mc["head_dim"], mc["intermediate_size"],
            mc["shared_intermediate_size"], mc["num_experts_per_tok"],
            mc["moe_router_experts"], mc["moe_route_scale"],
            mc["norm_eps"]) == (
        2688, 64, 64, 8, 128, 4, 128, 32, 2, 128, 1856, 3712, 6, 128, 2.5,
        1e-5)
    assert (mc["num_experts"], mc["vocab_size"], mc["num_layers"]) == (
        cfg["n_routed_experts"], cfg["vocab_size"],
        cfg["num_hidden_layers"]) == (64, 65536, 9)
    assert mc["activation"] == "relu2" and mc["glu"] is False
    ep = cfg["expert_parallel"]
    assert (ep["ranks"], ep["rank"], ep["first_expert"],
            ep["router_experts"], ep["vocabulary_rows"]) == (
        2, 0, 0, 128, [0, 65536])


def test_the_published_widths_give_the_stated_parameter_count():
    """From the file's published keys alone; and the program's own arrays
    hold that many beside the zero columns the experts are padded with."""
    import jax

    from deepspeed_tpu.models import CausalLM, ModelConfig

    cfg = real_config()
    D, H, P = cfg["hidden_size"], cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    di, conv = H * P, H * P + 2 * G * N
    mamba = D * (2 * di + 2 * G * N + H) + conv * K + conv + di * D + di \
        + 3 * H + D
    heads, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    attention = 2 * D * heads * dh + 2 * D * kv * dh + D
    expert = 2 * D * cfg["moe_intermediate_size"]
    router = cfg["expert_parallel"]["router_experts"]
    experts = cfg["n_routed_experts"] * expert \
        + 2 * D * cfg["moe_shared_expert_intermediate_size"] \
        + D * router + router + D
    outer = 2 * cfg["vocab_size"] * D + D
    letters = cfg["hybrid_override_pattern"]
    total = letters.count("M") * mamba + letters.count("*") * attention \
        + letters.count("E") * experts + outer
    assert (mamba, attention, expert, experts, outer) == (
        38744896, 23399040, 9977856, 658885376, 352324224)
    assert total == cfg["parameters"] == 3166244352
    shapes = jax.eval_shape(CausalLM(ModelConfig(**cfg["model_config"]),
                                     None).init, jax.random.PRNGKey(0))
    held = sum(int(__import__("math").prod(a.shape))
               for a in jax.tree.leaves(shapes))
    pad = letters.count("E") * 2 * D * (
        cfg["n_routed_experts"] * (2048 - 1856) + (4096 - 3712))
    assert held == total + pad


def test_agreement_tool_reads_every_generated_position(nemotron_bench):
    spec = importlib.util.spec_from_file_location(
        "_nemotron_agreement", os.path.join(BENCH_DIR, "tools",
                                            "nemotron3_nano_agreement.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    # inside one padded bucket; exactly two chunks; 16 + 16 + 16 + 2
    line = tool.agreement(CELL, 11, manifest_path=nemotron_bench,
                          allow_cpu=True,
                          lengths=((9, 5), (32, 6), (50, 20)),
                          wrong=("bf16_state", "gate_after_norm", "no_skip",
                                 "bias_weighs"))
    assert line["requests"] == [{"prompt": 9, "new": 5},
                                {"prompt": 32, "new": 6},
                                {"prompt": 50, "new": 20}]
    assert line["generated_positions"] == 31
    # routed as the program routed: a float32 program within reach of the
    # float32 reference, the broken references far from it (the limits are
    # set at the published widths, on the chip: PERF.md)
    far = line["worst_steps_against_wrong_reference"]
    assert line["worst_steps"] < min(far[k] for k in (
        "gate_after_norm", "no_skip", "bias_weighs")), line
    assert line["worst_steps"] <= far["bf16_state"]
    # the slots' states after the last token fed, against the reference's
    # recurrence: float32 noise, and a state kept in bf16 far from it
    wrong_state = line["state_difference_against_wrong_reference"]
    assert set(wrong_state) == {"bf16_state"}
    assert line["state_difference"] < 1e-4 < 10 * 1e-4 < wrong_state[
        "bf16_state"], line
    assert all(len(r["exact"]) == len(r["bf16_state"]) == 3
               and len(r["no_skip"]) == 2
               for r in line["worst_rms_and_state_by_request"])
    assert 0 <= line["routing_sets_flipped_share"] < 1
    assert [r["prompt"] for r in line["worst_rms_and_state_by_request"]] == [
        9, 32, 50]
    # the driver's own check, read against a router whose bias weighs as
    # well (the near-tie search runs under that control)
    below = line["served_token_steps_below_best"]
    assert below["free_running_bias_weighs"] >= below["free_running"]


@pytest.mark.parametrize("first", [0, 4])
def test_reference_agrees_with_the_programs_forward(first):
    """``test_reference.py``'s case for this reference (which has no loss to
    compare): float32 both sides, every gain and bias moved off its seeded
    value (the experts' pad columns too: both sides then use them), the
    share taken at rank 0 and at rank 1 of 2."""
    import jax
    import numpy as np

    from benchmarks.lib.manifest import Bench
    from deepspeed_tpu.models import CausalLM, ModelConfig

    cfg = tiny_config()
    mc = dict(cfg["model_config"], moe_first_expert=first)
    cfg["expert_parallel"]["first_expert"] = first
    ref = Bench().reference(CONFIG)
    model = CausalLM(ModelConfig(**mc), None)
    params = model.init(jax.random.PRNGKey(0))
    noise = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    params = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(next(noise), a.shape), params)
    tokens = np.random.default_rng(0).integers(0, 96, 83, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(model.apply(params, tokens[None]))[0]
    device = jax.devices()[0]
    _, _, own = ref.hidden_states(params, cfg, tokens, device,
                                  return_routing=True)
    got = np.asarray(ref.logits_rows(params, cfg, tokens, list(range(83)),
                                     device, routing=list(own)))
    np.testing.assert_allclose(got, logits, atol=5e-5)
