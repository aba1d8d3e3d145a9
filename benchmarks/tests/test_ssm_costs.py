"""``lib/ssm_costs.py`` and the three readers it feeds: the state's bytes a
live row a layer a step at the published widths; ``ssm_decode_roofline`` and ``mixer_attn_decode_roofline`` on a
hand-made trace; the readers return
nothing, and do not raise, for a program without the kernels (the parent
commit) and for a configuration without mamba2 layers."""

import os

import pytest

from conftest import BENCH_DIR, _load

REAL = "nemotron3-nano-L9-ep2.serve-reason-4k"
CONFIG = "nemotron3-nano-L9-ep2"


def _mc(name=CONFIG):
    return _load(os.path.join(BENCH_DIR, "configs",
                              name + ".json"))["model_config"]


def test_state_bytes_at_the_published_widths():
    from benchmarks.lib import ssm_costs

    mc = _mc()
    assert ssm_costs.mamba_layers(mc) == 4
    # 64 heads of 64 x 128 float32 read and written, the step's vectors
    assert ssm_costs.state_step_bytes(mc) == 4 * (
        2 * 64 * 64 * 128 + 2 * 64 * 64 + 64 + 2 * 8 * 128)
    # 16.8 MB a live row a step over the four layers (ISSUE 63's reckoning)
    assert 4 * ssm_costs.state_step_bytes(mc) == pytest.approx(16.8e6,
                                                               rel=0.01)
    # by the state, not by the step's vectors: a state twice as deep is
    # twice the bytes to within the vectors' 0.5%
    deep = dict(mc, ssm_state_size=256)
    assert ssm_costs.state_step_bytes(deep) == pytest.approx(
        2 * ssm_costs.state_step_bytes(mc), rel=0.005)


@pytest.mark.parametrize("other", ["mistral-7b-L8", "solar-open2-L4-ep8",
                                   "kimi-linear-L5-ep8"])
def test_no_other_configuration_has_such_layers(other):
    from benchmarks.lib import ssm_costs

    assert ssm_costs.mamba_layers(_mc(other)) == 0
    assert ssm_costs.attention_layers(_mc(other)) == 0


def _ctx(bench, kernels, config=CONFIG, programs=None, marks=True):
    from benchmarks.lib.peaks import peaks

    class Lv:
        # a request that shows 1 token after the iteration at 0.4 s and 9
        # after the one at 0.5 s: eight decode steps inside the window
        marks = [(0.4, 1), (0.5, 9)]

    class Arrival:
        prompt = list(range(100))

    return {"bench": bench, "peaks": peaks("TPU v5 lite"),
            "trace_window": (0.45, 1.0), "cell": bench.cell(REAL),
            "config": bench.config(config),
            "trace": None if kernels is None else {
                "kernels": kernels, "programs": programs or {}},
            "loop": {"records": [Lv] if marks else [],
                     "schedule": [Arrival] if marks else []}}


def test_the_roofline_counts_the_live_rows_states_once_a_layer():
    """Eight steps of one live row, the kernel traced for exactly the time
    819 GB/s needs for the four layers' states: 100%; traced for twice that
    (a kernel that moved a parked row's state beside it): 50%."""
    from benchmarks.lib import ssm_costs
    from benchmarks.lib.manifest import Bench
    from benchmarks.lib.peaks import peaks

    bench = Bench()
    nbytes = 8 * 4 * ssm_costs.state_step_bytes(_mc())
    least = nbytes / peaks("TPU v5 lite")["hbm_bytes_per_s"]
    reader = bench.reader("ssm_decode_roofline")
    ctx = _ctx(bench, {"ssm_decode_step": {"seconds": least, "count": 32}})
    assert reader.read(ctx) == pytest.approx(100.0)
    ctx = _ctx(bench, {"ssm_decode_step": {"seconds": 2 * least,
                                           "count": 32}})
    assert reader.read(ctx) == pytest.approx(50.0)


def test_the_attention_roofline_counts_the_one_layer_that_keeps_pages():
    """One iteration of the traced window with 1,000 context tokens under
    its decode rows, a block of 8 steps: the kernel must read K and V of
    2 heads of 128 in bf16 (1 KB a token) in the ONE ``full_attention``
    layer of nine, not in nine."""
    from benchmarks.lib import ssm_costs
    from benchmarks.lib.manifest import Bench
    from benchmarks.lib.peaks import peaks

    bench = Bench()
    assert ssm_costs.attention_layers(_mc()) == 1
    nbytes = 8 * 1000 * 2 * 2 * 128 * 2
    least = nbytes / peaks("TPU v5 lite")["hbm_bytes_per_s"]
    ctx = _ctx(bench, {"flash_decode_paged": {"seconds": 2 * least,
                                              "count": 8}})
    ctx["loop"]["iters"] = [(0.3, 0.4, 1, 0, 500), (0.5, 0.6, 1, 0, 1000)]
    assert bench.reader("mixer_attn_decode_roofline").read(ctx) == \
        pytest.approx(50.0)
    # the reader it stands beside counts a call in every layer: nine times
    assert bench.reader("flash_decode_paged_roofline").read(ctx) == \
        pytest.approx(450.0)


def test_the_share_is_kernel_time_over_the_decode_blocks_busy_time():
    from benchmarks.lib.manifest import Bench

    bench = Bench()
    ctx = _ctx(bench, {"ssm_decode_step": {"seconds": 0.03, "count": 32}},
               programs={"jit_body": {"busy_s": 0.1},
                         "jit_prefill": {"busy_s": 0.06}})
    assert bench.reader("ssm_decode_share").read(ctx) == pytest.approx(30.0)


@pytest.mark.parametrize("metric", ["ssm_decode_share",
                                    "ssm_decode_roofline",
                                    "mixer_attn_decode_roofline"])
def test_the_readers_return_none_for_a_program_without_the_kernels(metric):
    """What the parent commit, an untraced run and a cell of another
    configuration give the new readers."""
    from benchmarks.lib.manifest import Bench

    bench = Bench()
    reader = bench.reader(metric)
    busy = {"jit_body": {"busy_s": 0.1}, "jit_prefill": {"busy_s": 0.1}}
    other = {"kda_decode_step": {"seconds": 1.0, "count": 3},
             "fused_moe_mlp": {"seconds": 1.0, "count": 3}}
    assert reader.read(_ctx(bench, other, programs=busy)) is None
    assert reader.read(_ctx(bench, None)) is None
    assert reader.read(_ctx(bench, other, config="solar-open2-L4-ep8",
                            programs=busy)) is None
    assert reader.read(_ctx(bench, {}, programs={}, marks=False)) is None
