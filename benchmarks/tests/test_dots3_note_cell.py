"""The dots3-note cell's files: the manifest's new entries resolve; the cell
through the runner and ``tools/dots3_agreement.py`` at a tiny size on the CPU
(hidden 64; full layers of four heads of 16 + 8 behind a bottleneck of 24, a
latent of 32, sixteen index heads of 16 that select 16 keys; sliding layers
of two heads of 24 + 8, a latent of 48, a window of 13; page 8, chunks of 16,
a router of 16 experts of which 2 are held, top-4); the new readers on
recorded counters and on a hand-made trace summary; ``dsa_costs`` on the
configuration's file."""

import importlib.util
import os
import shutil

import pytest

from benchmarks.lib.manifest import Bench
from benchmarks.run import run_cell

from conftest import BENCH_DIR, REPO_DIR, _dump, _load

CELL = "tiny-dots3.serve"
REAL = "dots3-note-L5-ep16.serve-doc-48k"
CONFIG = "dots3-note-L5-ep16"
NEW = ("dsa_index_share", "dsa_keys_attended_share",
       "dsa_index_scores_paged_roofline", "dsa_decode_selected_roofline",
       "dsa_index_scores_chunk_roofline", "dsa_chunk_attention_roofline")
ROPE = lambda theta: {"theta": theta, "factor": 1,
                      "original_max_position_embeddings": 64, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 0}
TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=48, num_heads=4,
            max_seq_len=256, dense_intermediate_size=128, num_experts=2,
            moe_router_experts=16, num_experts_per_tok=4, mla_kv_rank=32,
            mla_nope_dim=16, mla_rot_dim=8, mla_v_dim=16, mla_q_rank=24,
            mla_rope=ROPE(8e7), sliding_window=13,
            mla_sliding={"num_heads": 2, "kv_rank": 48, "nope_dim": 24,
                         "rot_dim": 8, "v_dim": 16, "q_rank": 24,
                         "rope": ROPE(5e4)},
            mla_index_heads=16, mla_index_dim=16, mla_index_topk=16)


def tiny_config():
    cfg = _load(os.path.join(BENCH_DIR, "configs", CONFIG + ".json"))
    cfg.update(hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
               q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, swa_num_attention_heads=2, swa_kv_lora_rank=48,
               swa_q_lora_rank=24, swa_qk_nope_head_dim=24,
               swa_qk_rope_head_dim=8, swa_v_head_dim=16,
               sliding_window_size=13, index_n_heads=16, index_head_dim=16,
               index_topk=16, n_routed_experts=2, vocab_size=96,
               num_experts_per_tok=4)
    cfg["expert_parallel"].update(router_experts=16)
    cfg["model_config"].update(TINY)
    return cfg


@pytest.fixture
def dots3_bench(tiny_bench):
    root = os.path.join(os.path.dirname(tiny_bench), "tinybench")
    _dump(tiny_config(), os.path.join(root, "configs", "tiny-dots3.json"))
    for name in ("dots3_note.py", "trinity.py"):
        shutil.copy(os.path.join(BENCH_DIR, "reference", name),
                    os.path.join(root, "reference", name))
    mix = _load(os.path.join(BENCH_DIR, "traffic", "doc-48k.json"))
    # chunks of 16, a selection of 16, a window of 13: prompts on both sides
    mix["prompt_tokens"].update(median=24, sigma=0.8, min=4, max=70)
    mix["output_tokens"].update(median=20, min=8, max=40)
    mix["max_total_tokens"] = 112
    _dump(mix, os.path.join(root, "traffic", "doc-tiny.json"))
    cell = _load(os.path.join(BENCH_DIR, "workloads", REAL + ".json"))
    cell.update(name=CELL, config="tiny-dots3", traffic="doc-tiny",
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
    m["configs"].append({"name": "tiny-dots3", "source": "test",
                         "reduced": [], "why": "test",
                         "file": "tinybench/configs/tiny-dots3.json"})
    m["workloads"].append({"name": CELL, "config": "tiny-dots3",
                           "traffic": "doc-tiny", "chips": 1, "why": "test"})
    # the cell reports what the real one reports (the tiny manifest may be
    # older than an entry: it then gains it)
    have = {e["name"] for s in ("end_to_end", "per_layer") for e in m[s]}
    for s in ("end_to_end", "per_layer"):
        m[s] += [dict(e) for e in real[s] if e["name"] not in have
                 and REAL in (e.get("workloads") or ())]
    lists = {e["name"]: e.get("workloads")
             for s in ("end_to_end", "per_layer") for e in real[s]}
    for e in m["end_to_end"] + m["per_layer"]:
        if REAL in (lists.get(e["name"]) or ()):
            e["workloads"] = [w for w in e.get("workloads", ())
                              if w != REAL] + [CELL]
    _dump(m, tiny_bench)
    return tiny_bench


# --------------------------------------------------------------- manifest
def test_the_manifests_new_entries_resolve():
    bench = Bench()
    m = bench.manifest
    entry = bench.workload_entry(REAL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "doc-48k", 1)
    assert len(entry["why"]) <= 200
    assert bench.config_entry(CONFIG)["reduced"] == [
        "num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size"]
    cell = bench.cell(REAL)
    assert cell["engine"] == {
        "num_slots": 16, "prefill_chunk": 1024, "max_prefill_chunks": 2,
        "decode_block_tokens": 8, "max_out_tokens": 32768,
        "kv_pool_tokens": 16 * 32768, "kv_page_tokens": 256}
    # 3 s: the pattern's last prompt is due at 37.19 s of 40, and a traced
    # second would hold no chunk program (PERF.md section 4)
    assert cell["trace_seconds"] == 3.0 and cell["rate_rps"] == 1.0
    mix = bench.traffic(cell["traffic"])
    # ISSUE 52's named fallback (the reference check of 8 sequences of
    # 49,152 took 291.6 s after the window: PERF.md section 4)
    assert (mix["pattern_seed"], mix["max_total_tokens"]) == (52, 32768)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 8192,
                                    "sigma": 0.7, "min": 2048, "max": 31744}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 320,
                                    "sigma": 0.5, "min": 64, "max": 1024}
    assert hasattr(bench.reference(CONFIG), "logits_rows")
    # the six new metrics sit together behind PR 48's entries, listed for
    # this cell.  Their distance from the list's end is NOT asserted, nor
    # that no other cell is listed: a later PR has to be able to add its own
    names = [e["name"] for e in m["per_layer"]]
    first = names.index(NEW[0])
    assert names[first:first + len(NEW)] == list(NEW)
    assert first > names.index("moe_group_kept_share")
    for e in m["per_layer"][first:first + len(NEW)]:
        assert REAL in e["workloads"] and e["moves"] == "tpot_p50_ms"
        assert e["unit"] == "%"
        assert callable(bench.reader(e["name"]).read)
    assert [e["layer"] for e in m["per_layer"][first:first + len(NEW)]] == [
        "Model", "Serve engine"] + ["Kernels"] * 4
    reported = {e["name"] for e in bench.metrics_for("end_to_end", REAL)}
    assert {"tokens_per_s", "tpot_p50_ms", "setup_s"} <= reported
    per_layer = {e["name"] for e in bench.metrics_for("per_layer", REAL)}
    assert {"fused_moe_mlp_roofline", "moe_decode_share",
            "moe_local_assignment_share", "decode_rows_live_share",
            "peak_hbm_gb", "compiles_in_window", *NEW} <= per_layer
    # readers that count another kernel's bytes are not listed
    assert not {"mla_decode_roofline", "mla_decode_share",
                "swa_decode_roofline", "kv_pages_saved_share",
                "kda_decode_share"} & per_layer


def test_the_configuration_file_states_the_published_widths_and_the_cut():
    cfg = _load(os.path.join(BENCH_DIR, "configs", CONFIG + ".json"))
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        import json

        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "dots3-note-prev")
        assert cfg["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differs == set(cfg["reduced"])
    assert set(cfg["reduced"]) == {"num_hidden_layers", "layer_types",
                                   "n_routed_experts", "vocab_size"}
    mc = cfg["model_config"]
    assert (mc["hidden_size"], mc["num_heads"], mc["mla_nope_dim"],
            mc["mla_rot_dim"], mc["mla_v_dim"], mc["mla_kv_rank"],
            mc["mla_q_rank"], mc["dense_intermediate_size"],
            mc["intermediate_size"]) == (5120, 128, 128, 64, 128, 512, 1024,
                                         13824, 1536)
    assert mc["mla_sliding"] == {
        "num_heads": 64, "kv_rank": 1024, "nope_dim": 192, "rot_dim": 64,
        "v_dim": 128, "q_rank": 1024, "rope": dict(mc["mla_rope"],
                                                   theta=50000)}
    assert mc["mla_rope"]["theta"] == 80000000 and \
        mc["mla_rope"]["factor"] == 1
    assert (mc["mla_index_heads"], mc["mla_index_dim"], mc["mla_index_topk"],
            mc["sliding_window"], mc["mla_head_gate"],
            mc["mla_lora_rescale"]) == (64, 128, 2048, 513, True, True)
    assert (mc["num_experts_per_tok"], mc["moe_router_experts"],
            mc["num_experts"], mc["moe_route_scale"],
            mc["moe_select_bias"]) == (8, 256, 16, 1.0, True)
    assert mc["layer_types"] == ["latent_attention"] * 2 + [
        "latent_sliding_attention"] * 3
    assert cfg["layer_types"] == ["full_attention"] * 2 + [
        "sliding_attention"] * 3
    ep = cfg["expert_parallel"]
    assert (ep["ranks"], ep["rank"], ep["first_expert"],
            ep["router_experts"], ep["vocabulary_rows"]) == (
                16, 0, 0, 256, [0, 19008])
    assert "sixteen chips" in cfg["deployment"]
    assert {"apply_mla_qkv_lora_rescale", "attention_gate_type", "indexer",
            "window", "rotation", "router", "embedding_init"} <= set(
                cfg["assumed"])
    assert "vision tower" in cfg["left_out"]
    assert cfg["parameters"] == 2_577_204_736


# ------------------------------------------------------- the tiny cell runs
def test_the_cell_runs_and_is_correct(dots3_bench):
    line = run_cell(CELL, 2**31 + 52, 1.5, True, manifest_path=dots3_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["compiles_in_window"] == 0
    assert line["checks"]["reference"]["requests_checked"] > 0
    m = line["metrics"]
    # no device plane on the CPU: the trace's metrics are left out ...
    assert not {n for n in NEW if n != "dsa_keys_attended_share"} & set(m)
    # ... the counters' are there: past 16 keys a row attends 16 of them
    assert 0 < m["dsa_keys_attended_share"]["value"] < 100
    assert 0 < m["moe_local_assignment_share"]["value"] < 100
    assert 0 < m["decode_rows_live_share"]["value"] <= 100
    assert {"sched_occupancy_mean", "host_work_share",
            "moe_experts_hit_share"} <= set(m)


def test_untraced_run_reports_the_end_to_end_metrics(dots3_bench):
    line = run_cell(CELL, 52, 1.5, False, manifest_path=dots3_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert {"tokens_per_s", "tpot_p50_ms", "setup_s"} <= set(line["metrics"])


def test_agreement_tool_reads_every_generated_position(dots3_bench):
    spec = importlib.util.spec_from_file_location(
        "_dots3_agreement", os.path.join(BENCH_DIR, "tools",
                                         "dots3_agreement.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    # under the selection's 16 keys; across them in the second chunk; past
    # three chunks, the rings round twice
    line = tool.agreement(CELL, 11, manifest_path=dots3_bench, allow_cpu=True,
                          lengths=((6, 5), (20, 6), (50, 20)),
                          wrong=("no_selection", "no_rescale", "no_gate",
                                 "window_512", "unrotated_cache_key"),
                          wrong_selectors=("top_1024", "no_relu"))
    assert line["requests"] == [{"prompt": 6, "new": 5},
                                {"prompt": 20, "new": 6},
                                {"prompt": 50, "new": 20}]
    assert line["generated_positions"] == 31
    # routed and selecting as the program did: a float32 program on the
    # float32 reference, the broken references far from it (the limits are
    # set at the published widths, on the chip: PERF.md)
    assert line["worst_steps"] < 0.05 * min(
        line["worst_steps_against_wrong_reference"].values()), line
    assert 0 <= line["routing_sets_flipped_share"] < 0.2
    # float32 on both sides: the program selects the reference's own keys
    assert line["selection_sets_differ_share"] == 0.0
    assert line["selection_farthest_differing_key_from_edge"] == 0.0
    assert [r["prompt"] for r in line["worst_and_rms_by_request"]] == [
        6, 20, 50]
    below = line["served_token_steps_below_best"]
    assert below["free_running"] <= line["served_tie_steps"]


# ------------------------------------------------------------ the readers
def _ctx(start, end, kernels=None, busy=1.0):
    from benchmarks.lib.peaks import peaks

    trace = None if kernels is None else {
        "kernels": {k: {"seconds": s, "count": 1} for k, s in kernels.items()},
        "busy_s_chip0": busy, "programs": {}}
    return {"counters": {"begin": {}, "trace_start": start, "end": end},
            "trace": trace, "config": Bench().config(CONFIG),
            "peaks": peaks("TPU v5 lite")}


def test_readers_on_a_recorded_window():
    """A traced second: one row at position 16,383 decodes 8 steps; one
    chunk of 1,024 real tokens at 8,192; two full layers."""
    bench = Bench()
    p = [16384 + i for i in range(1, 9)]
    t = [8192 + i for i in range(1, 1025)]
    end = {"ds_serve_dsa_keys_scored_total": sum(p),
           "ds_serve_dsa_keys_attended_total": 8 * 2048,
           "ds_serve_dsa_chunk_keys_scored_total": sum(t),
           "ds_serve_dsa_chunk_keys_attended_total": 1024 * 2048}
    ctx = _ctx({"ds_serve_steps_total": 3}, end,
               {"dsa_index_scores_paged": 2e-4,
                "dsa_decode_selected": 1e-4, "dsa_index_scores_chunk": 5e-3,
                "dsa_chunk_attention": 4e-2}, busy=0.5)
    pk = ctx["peaks"]
    bw, fl = pk["hbm_bytes_per_s"], pk["bf16_flops_per_s"]
    read = lambda name: bench.reader(name).read(ctx)
    # 256 B and 16,384 FLOPs a key: the bytes bound
    assert read("dsa_index_scores_paged_roofline") == pytest.approx(
        100 * 2 * sum(p) * 256 / bw / 2e-4)
    # 1,152 B and 2 x 128 x 1,088 FLOPs a row: at the ridge, the larger
    assert read("dsa_decode_selected_roofline") == pytest.approx(
        100 * 2 * 8 * 2048 * max(1152 / bw, 2 * 128 * 1088 / fl) / 1e-4)
    assert read("dsa_index_scores_chunk_roofline") == pytest.approx(
        100 * 2 * sum(t) * 16384 / fl / 5e-3)
    assert read("dsa_chunk_attention_roofline") == pytest.approx(
        100 * 2 * 1024 * 2048 * 2 * 128 * 320 / fl / 4e-2)
    assert read("dsa_index_share") == pytest.approx(
        100 * (2e-4 + 5e-3) / 0.5)
    for name in NEW[2:]:
        assert 0 < read(name) < 100, name
    # begin -> profiler start, as the other counter readers take it
    grown = {"counters": {"begin": {"ds_serve_steps_total": 0},
                          "trace_start": end, "end": {}},
             "config": ctx["config"]}
    assert bench.reader("dsa_keys_attended_share").read(grown) == \
        pytest.approx(100 * 8 * 2048 / sum(p))


@pytest.mark.parametrize("name", NEW)
def test_readers_return_none_for_a_program_without_what_they_read(name):
    """What the parent commit gives the new readers (no such counter, no
    such kernel), what a window without a chunk or a step gives them, what a
    run without a trace gives them, and what a configuration without an
    indexer gives them."""
    bench = Bench()
    read = bench.reader(name).read
    other = {"ds_serve_steps_total": 9}
    assert read(_ctx(other, other, {"mla_decode_paged": 1e-3})) is None
    assert read(_ctx(other, other)) is None
    still = dict.fromkeys(("ds_serve_dsa_keys_scored_total",
                           "ds_serve_dsa_keys_attended_total",
                           "ds_serve_dsa_chunk_keys_scored_total",
                           "ds_serve_dsa_chunk_keys_attended_total"), 0)
    kernels = dict.fromkeys(("dsa_index_scores_paged", "dsa_decode_selected",
                             "dsa_index_scores_chunk",
                             "dsa_chunk_attention"), 1e-3)
    if name != "dsa_index_share":
        quiet = _ctx(still, still, kernels)
        quiet["counters"]["begin"] = still
        assert read(quiet) is None
    moved = {k: 8 for k in still}
    axk1 = dict(_ctx(still, moved, kernels),
                config=bench.config("axk1-L5-ep16"))
    if name.endswith("_roofline"):
        assert read(axk1) is None
    assert read({"counters": {}, "trace": None,
                 "config": bench.config(CONFIG)}) is None


def test_dsa_costs_read_this_configurations_file():
    from benchmarks.lib import dsa_costs

    mc = Bench().config(CONFIG)["model_config"]
    assert dsa_costs.indexed_layers(mc) == 2
    assert dsa_costs.index_pair(mc) == (2 * 64 * 128, 256)
    assert dsa_costs.selected_pair(mc) == (2 * 128 * (576 + 512), 1152)
    assert dsa_costs.chunk_pair_flops(mc) == 2 * 128 * (192 + 128)
    axk1 = Bench().config("axk1-L5-ep16")["model_config"]
    assert dsa_costs.indexed_layers(axk1) == 0


@pytest.mark.parametrize("first", [0, 2])
def test_reference_agrees_with_the_programs_forward(first):
    """``test_reference.py``'s case for this reference (which has no loss to
    compare): float32 both sides, every gain moved off its seeded value, the
    share taken at rank 0 and at rank 1 of 8."""
    import jax
    import numpy as np

    from deepspeed_tpu.models import CausalLM, ModelConfig

    cfg = tiny_config()
    mc = dict(cfg["model_config"], moe_first_expert=first)
    cfg["expert_parallel"]["first_expert"] = first
    ref = Bench().reference(CONFIG)
    model = CausalLM(ModelConfig(**mc), None)
    params = model.init(jax.random.PRNGKey(0))
    noise = iter(jax.random.split(jax.random.PRNGKey(1), 96))
    params = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(next(noise), a.shape), params)
    tokens = np.random.default_rng(0).integers(0, 96, 83, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(model.apply(params, tokens[None]))[0]
    device = jax.devices()[0]
    _, _, own, selected = ref.hidden_states(params, cfg, tokens, device,
                                            return_choices=True)
    got = np.asarray(ref.logits_rows(params, cfg, tokens, list(range(83)),
                                     device, routing=list(own),
                                     selection=selected))
    np.testing.assert_allclose(got, logits, atol=5e-5)
