"""The Trinity cell's files through the runner and through
``tools/trinity_agreement.py`` at a tiny size on the CPU (hidden 64, 4 / 2
heads x 16, window 16, page 8, a router of 8 experts of which 4 are held,
pattern ``[s | s, s, s, f]``): the configuration's ``model_config`` builds,
the driver's ``correct`` holds on a mix whose requests lie inside the window,
cross it in prefill and cross it in decode (a stale ring row or a dropped
mask would sit far below the reference's best logit), nothing compiles inside
the window, the program's counters reach the new readers, and the agreement
tool's bookkeeping yields every generated position."""

import importlib.util
import os
import shutil

import pytest

from benchmarks.run import run_cell

from conftest import BENCH_DIR, _dump, _load

CELL = "tiny-trinity.serve"
REAL = "trinity-large-L5-ep8.serve-mixed-16k"
NEW = ("swa_decode_roofline", "attn_decode_share", "kv_pages_saved_share",
       "moe_local_assignment_share")


@pytest.fixture
def trinity_bench(tiny_bench):
    root = os.path.join(os.path.dirname(tiny_bench), "tinybench")
    cfg = _load(os.path.join(BENCH_DIR, "configs",
                             "trinity-large-L5-ep8.json"))
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, sliding_window=16, num_experts=4, vocab_size=96)
    cfg["expert_parallel"].update(router_experts=8)
    cfg["model_config"].update(
        vocab_size=96, hidden_size=64, intermediate_size=48, num_heads=4,
        num_kv_heads=2, head_dim=16, max_seq_len=256, sliding_window=16,
        dense_intermediate_size=128, embed_scale=8.0, num_experts=4,
        moe_router_experts=8)
    _dump(cfg, os.path.join(root, "configs", "tiny-trinity.json"))
    shutil.copy(os.path.join(BENCH_DIR, "reference", "trinity.py"),
                os.path.join(root, "reference", "trinity.py"))
    mix = _load(os.path.join(BENCH_DIR, "traffic", "mixed-16k.json"))
    # a window of 16: prompts inside it and past it, answers that cross it
    mix["prompt_tokens"].update(median=24, sigma=0.8, min=4, max=70)
    mix["output_tokens"].update(median=20, min=8, max=40)
    mix["max_total_tokens"] = 112
    _dump(mix, os.path.join(root, "traffic", "mixed-tiny.json"))
    cell = _load(os.path.join(BENCH_DIR, "workloads", REAL + ".json"))
    cell.update(name=CELL, config="tiny-trinity", traffic="mixed-tiny",
                rate_rps=4.0, trace_seconds=0.5)
    cell["engine"].update(num_slots=4, prefill_chunk=16, max_out_tokens=128,
                          kv_pool_tokens=384, kv_page_tokens=8,
                          decode_block_tokens=4)
    _dump(cell, os.path.join(root, "workloads", CELL + ".json"))
    m = _load(tiny_bench)
    real = _load(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json"))
    m["configs"].append({"name": "tiny-trinity", "source": "test",
                         "reduced": [], "why": "test",
                         "file": "tinybench/configs/tiny-trinity.json"})
    m["workloads"].append({"name": CELL, "config": "tiny-trinity",
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


def test_the_cell_runs_and_is_correct(trinity_bench):
    line = run_cell(CELL, 2**31 + 36, 1.5, True, manifest_path=trinity_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["compiles_in_window"] == 0
    assert line["checks"]["reference"]["requests_checked"] > 0
    # no device plane on the CPU: the trace's metrics are left out ...
    assert "swa_decode_roofline" not in line["metrics"]
    assert "attn_decode_share" not in line["metrics"]
    assert "flash_decode_paged_roofline" not in line["metrics"]
    # ... the counters' are there: a ring saves pages past the window, and
    # four of the router's eight experts are held
    assert 0 < line["metrics"]["kv_pages_saved_share"]["value"] < 100
    assert 0 < line["metrics"]["moe_local_assignment_share"]["value"] < 100
    assert 0 < line["metrics"]["moe_experts_hit_share"]["value"] <= 100
    assert {"sched_occupancy_mean", "host_work_share"} <= set(line["metrics"])


def test_untraced_run_reports_the_end_to_end_metrics(trinity_bench):
    line = run_cell(CELL, 36, 1.5, False, manifest_path=trinity_bench,
                    allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert {"tokens_per_s", "tpot_p50_ms", "setup_s"} <= set(line["metrics"])


@pytest.mark.parametrize("name", NEW)
def test_readers_return_none_for_a_program_without_the_form(name):
    """What the parent commit, and a cell of another configuration, give
    the new readers: no such counter in the registry, layers all alike."""
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


@pytest.mark.parametrize("pos,n", [(0, 5), (10, 3), (14, 4), (15, 1),
                                   (16, 8), (40, 8), (4090, 16)])
def test_attended_rows_are_counted_in_closed_form(pos, n):
    from benchmarks.lib.window_costs import (attended_rows_span,
                                             decode_attention_bytes,
                                             kind_counts)

    W = 16 if pos < 100 else 4096
    mc = {"sliding_window": W, "num_heads": 48, "num_kv_heads": 8,
          "head_dim": 128,
          "layer_types": ["sliding_attention"] * 4 + ["full_attention"]}
    win, full = attended_rows_span(mc, pos, n)
    steps = range(pos, pos + n)
    assert win == sum(min(p + 1, W) for p in steps)
    assert full == sum(p + 1 for p in steps)
    assert kind_counts(mc) == (4, 1)
    assert decode_attention_bytes(mc, win, full) == (4 * win + full) * 4096


def test_agreement_tool_reads_every_generated_position(trinity_bench):
    spec = importlib.util.spec_from_file_location(
        "_trinity_agreement", os.path.join(BENCH_DIR, "tools",
                                           "trinity_agreement.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    # inside the window of 16; chunks of 16 across it; six short of it and
    # decoding across
    line = tool.agreement(CELL, 11, manifest_path=trinity_bench,
                          allow_cpu=True,
                          lengths=((9, 5), (50, 6), (10, 20)),
                          wrong=("no_window", "no_gate", "stale_ring"))
    assert line["requests"] == [{"prompt": 9, "new": 5},
                                {"prompt": 50, "new": 6},
                                {"prompt": 10, "new": 20}]
    assert line["generated_positions"] == 31
    # routed as the program routed: a bf16 program within reach of the
    # float32 reference, the broken references far from it (the limits are
    # set at the published widths, on the chip: PERF.md)
    assert line["worst_steps"] < min(
        line["worst_steps_against_wrong_reference"].values()), line
    assert 0 <= line["routing_sets_flipped_share"] < 1


@pytest.mark.parametrize("first", [0, 4])
def test_reference_agrees_with_the_programs_forward(first):
    """``test_reference.py``'s case for this reference (which has no loss to
    compare): float32 both sides, every gain and the selection bias moved
    off its seeded value (a gain of exactly 1 would hide a dropped norm), the
    share taken at rank 0 and at rank 1 of 2."""
    import jax
    import numpy as np

    from benchmarks.lib.manifest import Bench
    from deepspeed_tpu.models import CausalLM, ModelConfig

    mc = dict(_load(os.path.join(BENCH_DIR, "configs",
                                 "trinity-large-L5-ep8.json"))["model_config"],
              vocab_size=96, hidden_size=64, intermediate_size=48,
              num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=256,
              sliding_window=16, dense_intermediate_size=128, embed_scale=8.0,
              num_experts=4, moe_router_experts=8, moe_first_expert=first)
    ref_config = {
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-5, "rope_theta": 10000,
        "sliding_window": 16, "layer_types": mc["layer_types"],
        "num_dense_layers": 1, "num_experts_per_tok": 4, "route_scale": 2.448,
        "route_norm": True, "expert_parallel": {"first_expert": first}}
    ref = Bench().reference("trinity-large-L5-ep8")
    model = CausalLM(ModelConfig(**mc), None)
    params = model.init(jax.random.PRNGKey(0))
    noise = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    params = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(next(noise), a.shape), params)
    tokens = np.random.default_rng(0).integers(0, 96, 53, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(model.apply(params, tokens[None]))[0]
    device = jax.devices()[0]
    _, _, own = ref.hidden_states(params, ref_config, tokens, device,
                                  return_routing=True)
    got = np.asarray(ref.logits_rows(params, ref_config, tokens,
                                     list(range(53)), device,
                                     routing=list(own)))
    np.testing.assert_allclose(got, logits, atol=5e-5)
