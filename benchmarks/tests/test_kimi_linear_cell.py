"""The Kimi-Linear cell's files through the runner and through
``tools/kimi_linear_agreement.py`` at a tiny size on the CPU (hidden 64, four
KDA heads of 16, a latent of 32 + 8, four query heads of 16 + 8, page 8, a
router of 16 experts of which 4 are held, pattern ``[k | k, k, m, k]``): the
configuration's ``model_config`` builds, the driver's ``correct`` holds on a
mix whose prompts end on a chunk, inside a padded bucket and past several
chunks (a state moved by a pad row, not carried or not zeroed would sit far
below the reference's best logit), nothing compiles inside the window, the
program's counters reach the new readers, and the agreement tool's
bookkeeping yields every generated position."""

import importlib.util
import os
import shutil

import pytest

from benchmarks.run import run_cell

from conftest import BENCH_DIR, _dump, _load

CELL = "tiny-kimi.serve"
REAL = "kimi-linear-L5-ep8.serve-reason-doc-tail"
NEW = ("kda_decode_share", "mla_decode_share", "kda_decode_roofline",
       "mla_decode_roofline", "state_rows_live_share")
TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=48, num_heads=4,
            max_seq_len=256, dense_intermediate_size=128, num_experts=4,
            moe_router_experts=16, kda_num_heads=4, kda_head_dim=16,
            kda_gate_rank=16, mla_kv_rank=32, mla_nope_dim=16, mla_rot_dim=8,
            mla_v_dim=16)


def tiny_config():
    cfg = _load(os.path.join(BENCH_DIR, "configs", "kimi-linear-L5-ep8.json"))
    cfg.update(hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               num_experts=4, vocab_size=96)
    cfg["linear_attn_config"].update(num_heads=4, head_dim=16)
    cfg["expert_parallel"].update(router_experts=16)
    cfg["model_config"].update(TINY)
    return cfg


@pytest.fixture
def kimi_bench(tiny_bench):
    root = os.path.join(os.path.dirname(tiny_bench), "tinybench")
    _dump(tiny_config(), os.path.join(root, "configs", "tiny-kimi.json"))
    shutil.copy(os.path.join(BENCH_DIR, "reference", "kimi_linear.py"),
                os.path.join(root, "reference", "kimi_linear.py"))
    mix = _load(os.path.join(BENCH_DIR, "traffic", "reason-1k-doc-tail.json"))
    # chunks of 16: prompts inside one bucket and past several chunks
    mix["prompt_tokens"].update(median=20, sigma=0.8, min=4, max=70)
    mix["output_tokens"].update(median=20, min=8, max=40)
    mix["max_total_tokens"] = 112
    _dump(mix, os.path.join(root, "traffic", "reason-tiny.json"))
    cell = _load(os.path.join(BENCH_DIR, "workloads", REAL + ".json"))
    cell.update(name=CELL, config="tiny-kimi", traffic="reason-tiny",
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
    m["configs"].append({"name": "tiny-kimi", "source": "test",
                         "reduced": [], "why": "test",
                         "file": "tinybench/configs/tiny-kimi.json"})
    m["workloads"].append({"name": CELL, "config": "tiny-kimi",
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


def test_the_cell_runs_and_is_correct(kimi_bench):
    line = run_cell(CELL, 2**31 + 44, 1.5, True, manifest_path=kimi_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["compiles_in_window"] == 0
    assert line["checks"]["reference"]["requests_checked"] > 0
    # no device plane on the CPU: the trace's metrics are left out ...
    assert not {"kda_decode_share", "mla_decode_share", "kda_decode_roofline",
                "mla_decode_roofline"} & set(line["metrics"])
    # ... the counters' are there: the reference form of the state kernel
    # visits every slot, and four of the router's sixteen experts are held
    m = line["metrics"]
    assert 0 < m["state_rows_live_share"]["value"] <= 100
    assert m["state_rows_live_share"]["value"] == pytest.approx(
        m["decode_rows_live_share"]["value"], rel=0.05)
    assert 0 < m["moe_local_assignment_share"]["value"] < 100
    assert 0 < m["moe_experts_hit_share"]["value"] <= 100
    assert {"sched_occupancy_mean", "host_work_share"} <= set(m)


def test_untraced_run_reports_the_end_to_end_metrics(kimi_bench):
    line = run_cell(CELL, 44, 1.5, False, manifest_path=kimi_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert {"tokens_per_s", "tpot_p50_ms", "setup_s"} <= set(line["metrics"])


@pytest.mark.parametrize("name", NEW)
def test_readers_return_none_for_a_program_without_the_form(name):
    """What the parent commit, and a cell of another configuration, give
    the new readers: no such kernel in the trace, no such counter in the
    registry, no such layers in the configuration."""
    from benchmarks.lib.manifest import Bench

    bench = Bench(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json"))
    ctx = {"trace": {"kernels": {"fused_mlp": {"seconds": 1.0}},
                     "programs": {"jit_body": {"busy_s": 2.0}}},
           "trace_window": (0.0, 1.0), "loop": {"records": [], "schedule": []},
           "counters": {"begin": {"ds_serve_steps_total": 0},
                        "end": {"ds_serve_steps_total": 9}},
           "config": bench.config("mistral-7b-L8"), "peaks": None}
    assert bench.reader(name).read(ctx) is None
    assert bench.reader(name).read({**ctx, "trace": None}) is None


def test_agreement_tool_reads_every_generated_position(kimi_bench):
    spec = importlib.util.spec_from_file_location(
        "_kimi_agreement", os.path.join(BENCH_DIR, "tools",
                                        "kimi_linear_agreement.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    # inside one padded bucket; exactly two chunks; past three chunks
    line = tool.agreement(CELL, 11, manifest_path=kimi_bench, allow_cpu=True,
                          lengths=((9, 5), (32, 6), (50, 20)),
                          wrong=("no_decay", "no_k_rot", "stale_state",
                                 "pad_rows", "no_route_scale"))
    assert line["requests"] == [{"prompt": 9, "new": 5, "pad_rows": 7},
                                {"prompt": 32, "new": 6, "pad_rows": 0},
                                {"prompt": 50, "new": 20, "pad_rows": 6}]
    assert line["generated_positions"] == 31
    # routed as the program routed: a bf16 program within reach of the
    # float32 reference, the broken references far from it (the limits are
    # set at the published widths, on the chip: PERF.md)
    assert line["worst_steps"] < min(
        line["worst_steps_against_wrong_reference"].values()), line
    assert 0 <= line["routing_sets_flipped_share"] < 1
    assert [r["prompt"] for r in line["worst_and_rms_by_request"]] == [
        9, 32, 50]
    # the driver's own check, read against a wrong router as well (the
    # near-tie search runs under a routing control)
    below = line["served_token_steps_below_best"]
    assert below["free_running_no_route_scale"] >= below["free_running"]


@pytest.mark.parametrize("first", [0, 4])
def test_reference_agrees_with_the_programs_forward(first):
    """``test_reference.py``'s case for this reference (which has no loss to
    compare): float32 both sides, every gain and bias moved off its seeded
    value (a gain of exactly 1 would hide a dropped norm), the share taken
    at rank 0 and at rank 1 of 2."""
    import jax
    import numpy as np

    from benchmarks.lib.manifest import Bench
    from deepspeed_tpu.models import CausalLM, ModelConfig

    cfg = tiny_config()
    mc = dict(cfg["model_config"], moe_first_expert=first)
    cfg["expert_parallel"]["first_expert"] = first
    ref = Bench().reference("kimi-linear-L5-ep8")
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
