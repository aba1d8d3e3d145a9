"""The Solar-Open2 cell's files through the runner and through
``tools/solar_open2_agreement.py`` at a tiny size on the CPU (hidden 64, four
KDA heads of 16 with ``beta`` to 2, four query heads over two key-value
heads of 16 with the gate, page 8, a router of 16 experts of which 4 are
held, the published pattern ``[f, k, k, k]``): the configuration's
``model_config`` builds, the driver's ``correct`` holds on a mix whose
prompts end on a chunk, inside a padded bucket and past several chunks (a
state not carried, or K/V rows read through a wrong page, would sit far below
the reference's best logit), nothing compiles inside the window, the
program's counters reach the readers, the new reader and its costs count
the full layers only, and the agreement tool's bookkeeping yields every
generated position."""

import importlib.util
import os
import shutil

import pytest

from benchmarks.run import run_cell

from conftest import BENCH_DIR, _dump, _load

CELL = "tiny-solar.serve"
REAL = "solar-open2-L4-ep8.serve-reason-4k"
CONFIG = "solar-open2-L4-ep8"
TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=48, num_heads=4,
            num_kv_heads=2, head_dim=16, max_seq_len=256, num_experts=4,
            moe_router_experts=16, kda_num_heads=4, kda_head_dim=16,
            kda_gate_rank=16)


def tiny_config():
    cfg = _load(os.path.join(BENCH_DIR, "configs", CONFIG + ".json"))
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, n_routed_experts=4, vocab_size=96)
    cfg["linear_attn_config"].update(num_heads=4, head_dim=16)
    cfg["expert_parallel"].update(router_experts=16)
    cfg["model_config"].update(TINY)
    return cfg


@pytest.fixture
def solar_bench(tiny_bench):
    root = os.path.join(os.path.dirname(tiny_bench), "tinybench")
    _dump(tiny_config(), os.path.join(root, "configs", "tiny-solar.json"))
    shutil.copy(os.path.join(BENCH_DIR, "reference", "solar_open2.py"),
                os.path.join(root, "reference", "solar_open2.py"))
    mix = _load(os.path.join(BENCH_DIR, "traffic", "reason-4k.json"))
    # chunks of 16: prompts inside one bucket and past several chunks
    mix["prompt_tokens"].update(median=20, sigma=0.8, min=4, max=70)
    mix["output_tokens"].update(median=20, min=8, max=40)
    mix["max_total_tokens"] = 112
    _dump(mix, os.path.join(root, "traffic", "reason-tiny.json"))
    cell = _load(os.path.join(BENCH_DIR, "workloads", REAL + ".json"))
    cell.update(name=CELL, config="tiny-solar", traffic="reason-tiny",
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
    m["configs"].append({"name": "tiny-solar", "source": "test",
                         "reduced": [], "why": "test",
                         "file": "tinybench/configs/tiny-solar.json"})
    m["workloads"].append({"name": CELL, "config": "tiny-solar",
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


def test_the_cell_runs_and_is_correct(solar_bench):
    line = run_cell(CELL, 2**31 + 59, 1.5, True, manifest_path=solar_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["compiles_in_window"] == 0
    assert line["checks"]["reference"]["requests_checked"] > 0
    # no device plane on the CPU: the trace's metrics are left out ...
    assert not {"kda_decode_share", "attn_decode_share", "kda_decode_roofline",
                "hybrid_attn_decode_roofline"} & set(line["metrics"])
    # ... the counters' are there: the reference form of the state kernel
    # visits every slot, and four of the router's sixteen experts are held
    m = line["metrics"]
    assert 0 < m["state_rows_live_share"]["value"] <= 100
    assert m["state_rows_live_share"]["value"] == pytest.approx(
        m["decode_rows_live_share"]["value"], rel=0.05)
    assert 0 < m["moe_local_assignment_share"]["value"] < 100
    assert 0 < m["moe_experts_hit_share"]["value"] <= 100
    assert {"sched_occupancy_mean", "host_work_share"} <= set(m)


def test_untraced_run_reports_the_end_to_end_metrics(solar_bench):
    line = run_cell(CELL, 59, 1.5, False, manifest_path=solar_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert {"tokens_per_s", "tpot_p50_ms", "setup_s"} <= set(line["metrics"])
    assert "ttft_p50_ms" not in line["metrics"]


def test_the_manifest_lists_the_cell_where_its_readers_read():
    """The cell is on the lists of the readers that find something in it
    (the state's, the experts', the attention kernel's share, its own
    roofline) and NOT on ``flash_decode_paged_roofline``'s, which would count
    a call in every layer."""
    from benchmarks.lib.manifest import Bench

    bench = Bench()
    names = {m["name"] for m in bench.metrics_for("per_layer", REAL)}
    assert {"hybrid_attn_decode_roofline", "kda_decode_roofline",
            "kda_decode_share", "attn_decode_share", "state_rows_live_share",
            "fused_moe_mlp_roofline", "moe_decode_share",
            "decode_rows_live_share", "peak_hbm_gb"} <= names
    assert not {"flash_decode_paged_roofline", "mla_decode_roofline",
                "mla_decode_share", "loop_attn_decode_roofline"} & names
    assert {m["name"] for m in bench.metrics_for("end_to_end", REAL)} == {
        "tokens_per_s", "tpot_p50_ms", "setup_s"}
    entry = bench.workload_entry(REAL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert bench.config_entry(CONFIG)["reduced"] == [
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]


def test_costs_count_the_full_layers_and_the_state_at_64_heads():
    from benchmarks.lib import hybrid_costs, kda_costs

    mc = _load(os.path.join(BENCH_DIR, "configs",
                            CONFIG + ".json"))["model_config"]
    assert hybrid_costs.full_layers(mc) == 1
    assert kda_costs.kind_counts(mc) == (3, 0)
    # a token: K and V of 8 heads of 128 in ONE layer, bf16
    assert hybrid_costs.kv_bytes_per_token(mc) == 4096
    # a live row's state step: 64 heads of 128 x 128 float32 read and written
    assert kda_costs.state_step_bytes(mc) == 4 * (
        2 * 64 * 128 * 128 + 5 * 64 * 128 + 64)
    # no linear layer, no such pages; Trinity's global layers are not these
    for other in ("mistral-7b-L8", "trinity-large-L5-ep8",
                  "kimi-linear-L5-ep8"):
        mc = _load(os.path.join(BENCH_DIR, "configs",
                                other + ".json"))["model_config"]
        assert hybrid_costs.full_layers(mc) == 0


def test_the_roofline_counts_one_call_a_full_layer():
    """A hand-made trace: 8 steps over 1,000 context tokens, the kernel
    traced for the time 819 GB/s needs for ONE layer's K and V: 100%, where
    ``flash_decode_paged_roofline`` would read 400."""
    from benchmarks.lib.manifest import Bench
    from benchmarks.lib.peaks import peaks

    bench = Bench()
    pk = peaks("TPU v5 lite")
    nbytes = 8 * 1000 * 4096
    ctx = {"bench": bench, "peaks": pk, "trace_window": (0.0, 1.0),
           "cell": bench.cell(REAL), "config": bench.config(CONFIG),
           "trace": {"kernels": {"flash_decode_paged": {
               "seconds": nbytes / pk["hbm_bytes_per_s"]}}},
           "loop": {"iters": [(0.5, 0.6, 3, 0, 1000)]}}
    assert bench.reader("hybrid_attn_decode_roofline").read(ctx) == \
        pytest.approx(100.0)
    assert bench.reader("flash_decode_paged_roofline").read(ctx) == \
        pytest.approx(400.0)


@pytest.mark.parametrize("config", ["mistral-7b-L8", "kimi-linear-L5-ep8"])
def test_the_reader_returns_none_for_a_program_without_the_form(config):
    """What the parent commit, and a cell of another configuration, give the
    new reader: no such layers in the configuration, or no trace."""
    from benchmarks.lib.manifest import Bench

    bench = Bench()
    ctx = {"bench": bench, "peaks": None, "trace_window": (0.0, 1.0),
           "trace": {"kernels": {"flash_decode_paged": {"seconds": 1.0}}},
           "loop": {"iters": []}, "config": bench.config(config),
           "cell": bench.cell(REAL)}
    reader = bench.reader("hybrid_attn_decode_roofline")
    assert reader.read(ctx) is None
    assert reader.read({**ctx, "config": bench.config(CONFIG),
                        "trace": None}) is None


def test_agreement_tool_reads_every_generated_position(solar_bench):
    spec = importlib.util.spec_from_file_location(
        "_solar_agreement", os.path.join(BENCH_DIR, "tools",
                                         "solar_open2_agreement.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    # inside one padded bucket; exactly two chunks; 16 + 16 + 16 + 2
    line = tool.agreement(CELL, 11, manifest_path=solar_bench, allow_cpu=True,
                          lengths=((9, 5), (32, 6), (50, 20)),
                          wrong=("beta_sigmoid", "bf16_state", "no_gqa_gate",
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
        "beta_sigmoid", "no_gqa_gate", "bias_weighs")), line
    assert 0 <= line["routing_sets_flipped_share"] < 1
    assert [r["prompt"] for r in line["worst_and_rms_by_request"]] == [
        9, 32, 50]
    # the driver's own check, read against a wrong beta as well (the
    # near-tie search runs under that control)
    below = line["served_token_steps_below_best"]
    assert below["free_running_beta_sigmoid"] >= below["free_running"]


@pytest.mark.parametrize("first", [0, 4])
def test_reference_agrees_with_the_programs_forward(first):
    """``test_reference.py``'s case for this reference (which has no loss to
    compare): float32 both sides, every gain and bias moved off its seeded
    value, the share taken at rank 0 and at rank 1 of 4."""
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
