"""``kda_costs`` against hand arithmetic at the Kimi-Linear cell's shape, and
the readers of the recurrent-state and latent-attention kernels: right on a
hand-made summary, and ``None`` — never a wrong value — on a trace or a
registry without their kernel or counters."""

import os
import types

import pytest

from benchmarks.lib import kda_costs
from benchmarks.lib.manifest import Bench
from benchmarks.lib.peaks import peaks

from conftest import BENCH_DIR, _load

CELL = "kimi-linear-L5-ep8.serve-reason-doc-tail"


def config(name="kimi-linear-L5-ep8"):
    return _load(os.path.join(BENCH_DIR, "configs", name + ".json"))


def test_bytes_of_one_row_one_layer_one_step_by_hand():
    mc = config()["model_config"]
    assert kda_costs.kind_counts(mc) == (4, 1)
    # 32 heads x 128 x 128 float32 read and written: 2 x 2,097,152 B; q, k,
    # v and the decay 4 x 32 x 128 x 4 B; beta 32 x 4 B; o 32 x 128 x 4 B
    assert kda_costs.state_step_bytes(mc) == \
        4_194_304 + 65_536 + 128 + 16_384 == 4_276_352
    # 512 latent + 64 shared key values in bf16, stored once for all heads
    assert kda_costs.latent_row_bytes(mc) == 1_152
    # 60 live rows, four layers: 1.03 GB a step, 1.25 ms at 819 GB/s
    assert 60 * 4 * kda_costs.state_step_bytes(mc) / 819e9 == \
        pytest.approx(1.253e-3, rel=1e-3)
    assert kda_costs.kind_counts(config("mistral-7b-L8")["model_config"]) \
        == (0, 0)


@pytest.mark.parametrize("pos,n", [(0, 1), (10, 3), (1023, 8), (12_000, 8)])
def test_latent_rows_are_counted_in_closed_form(pos, n):
    assert kda_costs.latent_rows_span(pos, n) == \
        sum(p + 1 for p in range(pos, pos + n))


def test_moe_costs_read_this_configurations_share():
    """``lib/moe_costs.py`` on this ``model_config``: 32 held experts of
    width 1,024 under a hidden size of 2,304, eight a token."""
    from benchmarks.lib import moe_costs

    mc = config()["model_config"]
    assert (mc["num_experts"], mc["moe_router_experts"],
            mc["intermediate_size"], mc["num_experts_per_tok"]) == \
        (32, 256, 1024, 8)
    assert moe_costs.expert_weight_bytes(mc) == 32 * 3 * 2304 * 1024 * 2 \
        == 452_984_832


def _ctx(mc_name="kimi-linear-L5-ep8", kernels=None, counters=None):
    # one request of a 100-token prompt: 1 token visible at 0.5 s (outside
    # the traced window's count), 9 at 1.2 s, 17 at 1.8 s
    lv = types.SimpleNamespace(marks=[(0.5, 1), (1.2, 9), (1.8, 17)])
    arrival = types.SimpleNamespace(prompt=list(range(100)))
    return {
        "trace": {"kernels": kernels if kernels is not None else {
            "kda_decode_step": {"seconds": 0.5e-3, "count": 64},
            "mla_decode_paged": {"seconds": 0.02e-3, "count": 16}},
            "programs": {"jit_body": {"busy_s": 2e-3}}},
        "trace_window": (1.0, 2.0),
        "loop": {"records": [lv, None], "schedule": [arrival, arrival]},
        "counters": counters or {
            "begin": {"ds_serve_state_row_steps_total": 10,
                      "ds_serve_state_row_steps_visited_total": 20},
            "trace_start": {"ds_serve_state_row_steps_total": 110,
                            "ds_serve_state_row_steps_visited_total": 220}},
        "config": config(mc_name), "peaks": peaks("TPU v5 lite")}


def test_readers_on_a_hand_made_summary():
    bench = Bench()
    ctx = _ctx()
    read = lambda name: bench.reader(name).read(ctx)
    assert read("kda_decode_share") == pytest.approx(25.0)
    assert read("mla_decode_share") == pytest.approx(1.0)
    assert read("state_rows_live_share") == pytest.approx(50.0)
    # 16 decode steps in the window (tokens 2..17), four linear layers
    steps, rows = kda_costs.traced_steps(ctx)
    assert steps == 16
    # their queries sat at positions 100 .. 115: rows 101 + ... + 116
    assert rows == sum(range(101, 117))
    assert read("kda_decode_roofline") == pytest.approx(
        100 * 16 * 4 * 4_276_352 / 819e9 / 0.5e-3)
    assert read("mla_decode_roofline") == pytest.approx(
        100 * rows * 1_152 / 819e9 / 0.02e-3)
    assert 0 < read("kda_decode_roofline") <= 100
    assert 0 < read("mla_decode_roofline") <= 100


@pytest.mark.parametrize("name", [
    "kda_decode_share", "mla_decode_share", "kda_decode_roofline",
    "mla_decode_roofline", "state_rows_live_share"])
def test_readers_give_none_without_their_kernel_or_counter(name):
    bench = Bench()
    other = {"fused_mlp": {"seconds": 1.0, "count": 3}}
    plain = {"begin": {"ds_serve_steps_total": 0},
             "end": {"ds_serve_steps_total": 9}}
    read = bench.reader(name).read
    assert read(_ctx(kernels=other, counters=plain)) is None
    assert read({**_ctx(kernels=other, counters=plain), "trace": None}) is None
    if name.endswith("_roofline"):    # the kernel's name on another model
        assert read(_ctx("mistral-7b-L8")) is None


def test_the_manifest_lists_the_cell_where_its_readers_read():
    m = Bench().manifest
    lists = {e["name"]: e.get("workloads") for e in m["per_layer"]}
    for name in ("kda_decode_share", "mla_decode_share", "kda_decode_roofline",
                 "mla_decode_roofline", "state_rows_live_share"):
        assert lists[name] == [CELL]
    for name in ("fused_moe_mlp_roofline", "moe_decode_share",
                 "moe_local_assignment_share", "decode_rows_live_share"):
        assert CELL in lists[name]
    assert CELL not in lists["flash_decode_paged_roofline"]
    assert CELL not in lists["moe_max_load_ratio"]


def test_the_five_metrics_sit_together():
    """Additions only: the five entries in one run, appended.  Their distance
    from the list's end is NOT asserted: a later PR has to be able to add its
    own after them."""
    names = [e["name"] for e in Bench().manifest["per_layer"]]
    first = names.index("kda_decode_share")
    assert names[first:first + 5] == [
        "kda_decode_share", "mla_decode_share", "kda_decode_roofline",
        "mla_decode_roofline", "state_rows_live_share"]
