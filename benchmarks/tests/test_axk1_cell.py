"""The A.X-K1 cell's files: the manifest's new entries resolve; the cell
through the runner and ``tools/axk1_agreement.py`` at a tiny size on the CPU
(hidden 64, four query heads of 16 + 8 behind a bottleneck of 24, a latent
of 32 + 8, YaRN over an original length of 16, page 8, chunks of 16, a
router of 16 experts in 4 groups of which 2 are kept, 2 experts held,
top-4); the two new readers on recorded counters; ``kda_costs`` and
``moe_costs`` on the configuration's file."""

import importlib.util
import os
import shutil

import pytest

from benchmarks.lib.manifest import Bench
from benchmarks.run import run_cell

from conftest import BENCH_DIR, REPO_DIR, _dump, _load

CELL = "tiny-axk1.serve"
REAL = "axk1-L5-ep16.serve-mixed-16k"
CONFIG = "axk1-L5-ep16"
NEW = ("mla_rows_expanded_per_written", "moe_group_kept_share")
TINY_ROPE = {"theta": 10000, "factor": 32,
             "original_max_position_embeddings": 16, "beta_fast": 32,
             "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=48, num_heads=4,
            max_seq_len=256, dense_intermediate_size=128, num_experts=2,
            moe_router_experts=16, moe_n_group=4, moe_topk_group=2,
            num_experts_per_tok=4, mla_kv_rank=32, mla_nope_dim=16,
            mla_rot_dim=8, mla_v_dim=16, mla_q_rank=24, mla_rope=TINY_ROPE)


def tiny_config():
    cfg = _load(os.path.join(BENCH_DIR, "configs", CONFIG + ".json"))
    cfg.update(hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
               q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, n_routed_experts=2, vocab_size=96, n_group=4,
               topk_group=2, num_experts_per_tok=4)
    cfg["rope_scaling"].update(original_max_position_embeddings=16)
    cfg["expert_parallel"].update(router_experts=16)
    cfg["model_config"].update(TINY)
    return cfg


@pytest.fixture
def axk1_bench(tiny_bench):
    root = os.path.join(os.path.dirname(tiny_bench), "tinybench")
    _dump(tiny_config(), os.path.join(root, "configs", "tiny-axk1.json"))
    for name in ("axk1.py", "trinity.py"):
        shutil.copy(os.path.join(BENCH_DIR, "reference", name),
                    os.path.join(root, "reference", name))
    mix = _load(os.path.join(BENCH_DIR, "traffic", "mixed-16k.json"))
    # chunks of 16: prompts inside one bucket and past several chunks
    mix["prompt_tokens"].update(median=20, sigma=0.8, min=4, max=70)
    mix["output_tokens"].update(median=20, min=8, max=40)
    mix["max_total_tokens"] = 112
    _dump(mix, os.path.join(root, "traffic", "mixed-tiny.json"))
    cell = _load(os.path.join(BENCH_DIR, "workloads", REAL + ".json"))
    cell.update(name=CELL, config="tiny-axk1", traffic="mixed-tiny",
                rate_rps=4.0, trace_seconds=0.5)
    cell["engine"].update(num_slots=4, prefill_chunk=16, max_out_tokens=128,
                          kv_pool_tokens=512, kv_page_tokens=8,
                          decode_block_tokens=4,
                          # at hidden 64 a bf16 stream alone moves the logits
                          # by more steps than ``correct`` allows: the tiny
                          # cell checks paths, in float32
                          dtype="fp32")
    _dump(cell, os.path.join(root, "workloads", CELL + ".json"))
    m = _load(tiny_bench)
    real = _load(os.path.join(REPO_DIR, "BENCHMARK.json"))
    m["configs"].append({"name": "tiny-axk1", "source": "test",
                         "reduced": [], "why": "test",
                         "file": "tinybench/configs/tiny-axk1.json"})
    m["workloads"].append({"name": CELL, "config": "tiny-axk1",
                           "traffic": "mixed-tiny", "chips": 1,
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
        CONFIG, "mixed-16k", 1)
    assert len(entry["why"]) <= 200
    assert bench.config_entry(CONFIG)["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    cell = bench.cell(REAL)
    assert cell["engine"] == {
        "num_slots": 32, "prefill_chunk": 1024, "max_prefill_chunks": 2,
        "decode_block_tokens": 8, "max_out_tokens": 16384,
        "kv_pool_tokens": 32 * 16384, "kv_page_tokens": 256}
    # the mix is Trinity's own file, not a copy
    assert bench.traffic(cell["traffic"])["pattern_seed"] == 36
    assert hasattr(bench.reference(CONFIG), "logits_rows")
    # the two new metrics sit together behind PR 45's entry, listed for this
    # cell.  Their distance from the list's end is NOT asserted, nor that no
    # other cell is listed: a later PR has to be able to add its own
    names = [e["name"] for e in m["per_layer"]]
    first = names.index(NEW[0])
    assert names[first:first + 2] == list(NEW)
    assert first > names.index("prefill_chunks_extra_share")
    for e in m["per_layer"][first:first + 2]:
        assert REAL in e["workloads"] and e["moves"] == "tpot_p50_ms"
        assert e["layer"] == "Serve engine"
        assert callable(bench.reader(e["name"]).read)
    reported = {e["name"] for e in bench.metrics_for("end_to_end", REAL)}
    assert reported == {"tokens_per_s", "ttft_p50_ms", "tpot_p50_ms",
                        "setup_s"}
    per_layer = {e["name"] for e in bench.metrics_for("per_layer", REAL)}
    assert {"mla_decode_roofline", "mla_decode_share",
            "fused_moe_mlp_roofline", "moe_decode_share",
            "moe_local_assignment_share", "decode_rows_live_share",
            *NEW} <= per_layer
    assert not {"kda_decode_share", "kda_decode_roofline",
                "state_rows_live_share", "swa_decode_roofline"} & per_layer


def test_the_configuration_file_states_the_published_widths_and_the_cut():
    cfg = _load(os.path.join(BENCH_DIR, "configs", CONFIG + ".json"))
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        import json

        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "A.X-K1")
        assert cfg["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == set(cfg["reduced"])
    assert set(cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                   "vocab_size"}
    mc = cfg["model_config"]
    assert (mc["hidden_size"], mc["num_heads"], mc["mla_nope_dim"],
            mc["mla_rot_dim"], mc["mla_v_dim"], mc["mla_kv_rank"],
            mc["mla_q_rank"], mc["dense_intermediate_size"],
            mc["intermediate_size"]) == (7168, 64, 128, 64, 128, 512, 1536,
                                         18432, 2048)
    assert (mc["num_experts_per_tok"], mc["moe_router_experts"],
            mc["num_experts"], mc["moe_n_group"], mc["moe_topk_group"],
            mc["moe_route_scale"]) == (8, 192, 12, 8, 4, 2.5)
    assert mc["mla_rope"] == {
        "theta": 10000, "factor": 32,
        "original_max_position_embeddings": 4096, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
    assert mc["layer_types"] == ["latent_attention"] * 5
    assert not any(k.startswith("kda_") for k in mc)
    ep = cfg["expert_parallel"]
    assert (ep["ranks"], ep["rank"], ep["first_expert"],
            ep["router_experts"]) == (16, 0, 0, 192)
    assert "sixteen chips" in cfg["deployment"]
    assert {"topk_method", "group_score", "norms", "rotation", "yarn",
            "embedding_init"} <= set(cfg["assumed"])


# ------------------------------------------------------- the tiny cell runs
def test_the_cell_runs_and_is_correct(axk1_bench):
    line = run_cell(CELL, 2**31 + 48, 1.5, True, manifest_path=axk1_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["compiles_in_window"] == 0
    assert line["checks"]["reference"]["requests_checked"] > 0
    m = line["metrics"]
    # no device plane on the CPU: the trace's metrics are left out ...
    assert not {"mla_decode_share", "mla_decode_roofline",
                "fused_moe_mlp_roofline", "moe_decode_share"} & set(m)
    # ... the counters' are there: 2 of 4 groups kept and the chip's two
    # experts in one of them; a prompt of several chunks expands its
    # earlier rows again
    assert 0 < m["moe_group_kept_share"]["value"] < 100
    assert m["mla_rows_expanded_per_written"]["value"] >= 1.0
    assert 0 < m["moe_local_assignment_share"]["value"] < 100
    assert 0 < m["decode_rows_live_share"]["value"] <= 100
    assert {"sched_occupancy_mean", "host_work_share",
            "moe_experts_hit_share",
            "sched_prefill_token_share", "prefill_chunks_extra_share"} <= set(m)


def test_untraced_run_reports_the_end_to_end_metrics(axk1_bench):
    line = run_cell(CELL, 48, 1.5, False, manifest_path=axk1_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"tokens_per_s", "ttft_p50_ms",
                                    "tpot_p50_ms", "setup_s"}


def test_agreement_tool_reads_every_generated_position(axk1_bench):
    spec = importlib.util.spec_from_file_location(
        "_axk1_agreement", os.path.join(BENCH_DIR, "tools",
                                        "axk1_agreement.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    # decoding across a page of 8; exactly two chunks; past three chunks
    line = tool.agreement(CELL, 11, manifest_path=axk1_bench, allow_cpu=True,
                          lengths=((6, 5), (32, 6), (50, 20)),
                          wrong=("no_rope", "no_mscale", "no_q_norm",
                                 "unrotated_cache_key"))
    assert line["requests"] == [{"prompt": 6, "new": 5},
                                {"prompt": 32, "new": 6},
                                {"prompt": 50, "new": 20}]
    assert line["generated_positions"] == 31
    # routed as the program routed: a float32 program on the float32
    # reference, the broken references far from it (the limits are set at
    # the published widths, on the chip: PERF.md)
    assert line["worst_steps"] < 0.05 * min(
        line["worst_steps_against_wrong_reference"].values()), line
    assert 0 <= line["routing_sets_flipped_share"] < 0.2
    assert [r["prompt"] for r in line["worst_and_rms_by_request"]] == [
        6, 32, 50]
    # the driver's own check, read against the router without its group
    # limit as well (the near-tie search runs under a routing control)
    below = line["served_token_steps_below_best"]
    assert below["free_running"] <= line["served_tie_steps"]
    assert below["free_running_no_group_limit"] > below["free_running"]


# ------------------------------------------------------------ the readers
def _counter_ctx(begin, end, config=CONFIG):
    return {"counters": {"begin": begin, "trace_start": end, "end": {}},
            "config": Bench().config(config)}


def test_readers_on_recorded_counters():
    """A recorded window: 3,584 live (row, step) pairs over four expert
    layers = 14,336 triples x 8 choices; the group with this chip's experts
    kept for 7,202 of them; six chunk programs of one 6,144-token prompt
    over five latent layers."""
    bench = Bench()
    begin = {"ds_serve_moe_assignments_total": 1000,
             "ds_serve_moe_group_kept_total": 70,
             "ds_serve_mla_rows_expanded_total": 5 * 2048,
             "ds_serve_mla_rows_written_total": 5 * 1500}
    end = {"ds_serve_moe_assignments_total": 1000 + 14_336 * 8,
           "ds_serve_moe_group_kept_total": 70 + 7_202,
           "ds_serve_mla_rows_expanded_total":
               5 * (2048 + 1024 * (1 + 2 + 3 + 4 + 5 + 6)),
           "ds_serve_mla_rows_written_total": 5 * (1500 + 6144)}
    ctx = _counter_ctx(begin, end)
    assert bench.reader("moe_group_kept_share").read(ctx) == pytest.approx(
        100 * 7_202 / 14_336)
    assert bench.reader("mla_rows_expanded_per_written").read(ctx) == \
        pytest.approx(21_504 / 6_144) == pytest.approx(3.5)


@pytest.mark.parametrize("name", NEW)
def test_readers_return_none_for_a_program_without_the_counters(name):
    """What the parent commit gives the new readers (no such counter), what
    a window without a chunk or a decode step gives them, and what a model
    with one group gives the group reader."""
    bench = Bench()
    other = {"ds_serve_steps_total": 9, "ds_serve_moe_assignments_total": 64}
    assert bench.reader(name).read(_counter_ctx({}, other)) is None
    still = {"ds_serve_moe_assignments_total": 0,
             "ds_serve_moe_group_kept_total": 0,
             "ds_serve_mla_rows_expanded_total": 0,
             "ds_serve_mla_rows_written_total": 0}
    assert bench.reader(name).read(_counter_ctx(still, still)) is None
    assert bench.reader(name).read({"counters": {}, "config":
                                    bench.config(CONFIG)}) is None
    moved = {k: 8 for k in still}
    one_group = _counter_ctx(still, moved, "kimi-linear-L5-ep8")
    assert bench.reader("moe_group_kept_share").read(one_group) is None


# --------------------------------------------------- the accepted cost libs
def test_kda_costs_and_moe_costs_read_this_configurations_file():
    """``lib/kda_costs.py`` and ``lib/moe_costs.py`` on a file with 64
    heads, five latent layers, no linear layer and 12 of 192 experts: the
    numerators of ``mla_decode_roofline`` and ``fused_moe_mlp_roofline``."""
    from benchmarks.lib import kda_costs, moe_costs

    mc = Bench().config(CONFIG)["model_config"]
    assert kda_costs.kind_counts(mc) == (0, 5)
    # 512 latent + 64 rotated key values in bf16, once for all 64 heads
    assert kda_costs.latent_row_bytes(mc) == 1_152
    # 12 held experts x 3 matrices x 7,168 x 2,048 in bf16: 1.06 GB a call
    assert moe_costs.expert_weight_bytes(mc) == 12 * 3 * 7168 * 2048 * 2 \
        == 1_056_964_608
    assert moe_costs.routed_flops(mc, 1) == 2 * 8 * 3 * 7168 * 2048


def test_the_accepted_readers_take_this_configuration():
    """``mla_decode_roofline`` / ``mla_decode_share`` / ``fused_moe_mlp_
    roofline`` on a hand-made trace summary with this file's sizes: one
    request at position 6,143 that decodes 8 steps inside the traced
    window."""
    import types

    from benchmarks.lib.peaks import peaks

    bench = Bench()
    lv = types.SimpleNamespace(marks=[(0.5, 1), (1.5, 9)])
    arrival = types.SimpleNamespace(prompt=[0] * 6144)
    rows = sum(6144 + i + 1 for i in range(8))
    ctx = {"trace": {"kernels": {
               "mla_decode_paged": {"seconds": 1e-3, "count": 40},
               "fused_moe_mlp": {"seconds": 0.05, "count": 32}},
               "programs": {"jit_body": {"busy_s": 0.1}}},
           "trace_window": (1.0, 2.0),
           "loop": {"records": [lv], "schedule": [arrival]},
           "counters": {}, "config": bench.config(CONFIG),
           "peaks": peaks("TPU v5 lite")}
    bw = ctx["peaks"]["hbm_bytes_per_s"]
    assert bench.reader("mla_decode_roofline").read(ctx) == pytest.approx(
        100 * 5 * rows * 1152 / bw / 1e-3)
    assert bench.reader("mla_decode_share").read(ctx) == pytest.approx(1.0)
    assert bench.reader("fused_moe_mlp_roofline").read(ctx) == pytest.approx(
        100 * 32 * 1_056_964_608 / bw / 0.05)


@pytest.mark.parametrize("first", [0, 2])
def test_reference_agrees_with_the_programs_forward(first):
    """``test_reference.py``'s case for this reference (which has no loss to
    compare): float32 both sides, every gain moved off its seeded value (a
    gain of exactly 1 would hide a dropped norm), the share taken at rank 0
    and at rank 1 of 8 (both in group 0)."""
    import jax
    import numpy as np

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
