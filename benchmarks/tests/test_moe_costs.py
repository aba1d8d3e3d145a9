"""``moe_costs`` against hand arithmetic at the OLMoE cell's shape, and the
MoE readers: right on a hand-made summary, and ``None`` — never a wrong
value — on a trace or a registry without their kernel or counters."""

import os

import pytest

from benchmarks.lib import moe_costs
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.manifest import Bench
from benchmarks.lib.peaks import peaks

from conftest import BENCH_DIR, TESTS_DIR, _load

READERS = ("fused_moe_mlp_roofline", "moe_decode_share",
           "moe_experts_hit_share", "moe_max_load_ratio")
P = "ds_serve_moe_"


def config(name):
    return _load(os.path.join(BENCH_DIR, "configs", name + ".json"))


def test_bytes_and_flops_at_the_cells_shape():
    mc = config("olmoe-1b-7b-L8")["model_config"]
    # 64 experts x 3 matrices x 2048 x 1024 x 2 bytes
    assert moe_costs.expert_weight_bytes(mc) == 805_306_368
    assert moe_costs.expert_weight_bytes(mc) * mc["num_layers"] == \
        pytest.approx(6.44e9, rel=1e-3)
    # a token: 8 experts x 3 matrices x 2048 x 1024 multiply-adds
    assert moe_costs.routed_flops(mc, 1) == 2 * 8 * 3 * 2048 * 1024
    # 64 rows against all 64 experts: 51.5 GFLOP a layer
    assert moe_costs.dense_flops(mc, 64) == 2 * 64 * 64 * 3 * 2048 * 1024
    assert moe_costs.dense_flops(mc, 64) == pytest.approx(51.5e9, rel=1e-2)
    # not gated: two matrices an expert
    assert moe_costs.expert_weight_bytes(dict(mc, glu=False)) == \
        805_306_368 * 2 / 3


def test_parameter_count_of_the_configuration():
    c = config("olmoe-1b-7b-L8")
    mc = c["model_config"]
    D, F, E, V = (mc[k] for k in ("hidden_size", "intermediate_size",
                                  "num_experts", "vocab_size"))
    layer = 4 * D * D + 2 * D + 2 * D + D * E + E * 3 * D * F
    assert layer == 419_569_664
    assert c["parameters"] == mc["num_layers"] * layer + 2 * V * D + D
    # no width differs from the published configuration
    assert (c["hidden_size"], c["intermediate_size"], c["num_experts"],
            c["num_experts_per_tok"], c["num_attention_heads"],
            c["vocab_size"]) == (2048, 1024, 64, 8, 16, 50304)
    assert list(c["reduced"]) == ["num_hidden_layers"]
    assert mc["moe_drop_tokens"] is False and mc["qk_norm"] is True \
        and mc["moe_norm_topk_prob"] is False


def moe_ctx():
    c = config("olmoe-1b-7b-L8")
    summary = {"kernels": {"fused_moe_mlp": {"count": 16, "seconds": 0.02},
                           "fused_norm_qkv": {"count": 16, "seconds": 0.001}},
               "programs": {"jit_body": {"count": 1, "busy_s": 0.025,
                                         "span_s": 0.026}}}
    grow = {P + "assignments_total": 1000.0, P + "expert_hits_total": 450.0,
            P + "expert_slots_total": 512.0, P + "max_load_total": 31.25}
    return {"trace": summary, "config": c, "peaks": peaks("TPU v5 lite"),
            "counters": {"begin": {k: 7.0 for k in grow},
                         "trace_start": {k: 7.0 + v for k, v in grow.items()}}}


def test_readers_on_a_hand_made_summary():
    read = {n: Bench().reader(n).read for n in READERS}
    ctx = moe_ctx()
    # 16 calls x 805,306,368 B at 819 GB/s = 15.73 ms of the 20 traced
    assert read["fused_moe_mlp_roofline"](ctx) == pytest.approx(
        100 * 16 * 805_306_368 / 819e9 / 0.02)
    assert read["moe_decode_share"](ctx) == pytest.approx(80.0)
    assert read["moe_experts_hit_share"](ctx) == pytest.approx(
        100 * 450 / 512)
    assert read["moe_max_load_ratio"](ctx) == pytest.approx(
        31.25 * 64 / 1000)


def test_readers_return_none_without_their_kernel_or_counters():
    """The parent's program, or a dense model: no ``fused_moe_mlp`` in the
    trace (the recorded Mistral window) and no ``ds_serve_moe_*``."""
    summary = tr.summarize(
        tr.load_events(os.path.join(TESTS_DIR, "fixtures",
                                    "v5e_serve_mistral_40ms.json.gz")),
        host_scopes=("ds_serve_admit", "ds_serve_prefill",
                     "ds_serve_decode"))
    assert "fused_mlp" in summary["kernels"]
    other = {"ds_serve_steps_total": 3.0}
    for cfg in ("mistral-7b-L8", "olmoe-1b-7b-L8"):
        ctx = {"trace": summary, "config": config(cfg),
               "peaks": peaks("TPU v5 lite"),
               "counters": {"begin": dict(other), "trace_start": dict(other)}}
        for name in READERS:
            assert Bench().reader(name).read(ctx) is None, (cfg, name)
    # no trace at all (a CPU run), no snapshots at all (registry off)
    ctx = dict(moe_ctx(), trace=None, counters={})
    for name in READERS:
        assert Bench().reader(name).read(ctx) is None, name
    # counters that never moved give no ratio
    still = moe_ctx()
    still["counters"]["trace_start"] = dict(still["counters"]["begin"])
    assert Bench().reader("moe_experts_hit_share").read(still) is None
    assert Bench().reader("moe_max_load_ratio").read(still) is None
