"""``eva_prefill_attn_share``: right on a made-up reduced trace and on a
hand-made trace through the reduction, ``None`` — never a wrong value — for
a program without the kernel (the parent, whose chunks run the dense XLA
attention) or a window without a chunk program, and listed for the one cell
whose chunk programs hold the kernel."""

import pytest

from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.manifest import Bench
from benchmarks.lib.trace_reduce import Ev

NAME = "eva_prefill_attn_share"
KERNEL, PROGRAM = "eva_chunk_attention", "jit_prefill"


def reduced(kernels, programs):
    return {"trace": {"kernels": kernels, "programs": programs}}


@pytest.mark.parametrize("ctx,want", [
    # 318 layer-chunks of 0.25 ms in 53 chunk programs of 25 ms
    (reduced({KERNEL: {"count": 318, "seconds": 0.0795},
              "eva_decode_paged": {"count": 1284, "seconds": 0.63}},
             {PROGRAM: {"count": 53, "span_s": 1.33, "busy_s": 1.325},
              "jit_body": {"count": 26, "span_s": 1.4, "busy_s": 1.35}}),
     6.0),
    # the parent: chunk programs without the kernel
    (reduced({"eva_decode_paged": {"count": 1284, "seconds": 0.63}},
             {PROGRAM: {"count": 53, "span_s": 1.7, "busy_s": 1.66}}),
     None),
    # no chunk program inside the traced window
    (reduced({KERNEL: {"count": 6, "seconds": 0.0015}},
             {"jit_body": {"count": 26, "span_s": 1.4, "busy_s": 1.35}}),
     None),
    (reduced({KERNEL: {"count": 6, "seconds": 0.0015}},
             {PROGRAM: {"count": 1, "span_s": 0.0, "busy_s": 0.0}}), None),
    # an untraced run
    ({"trace": None}, None),
], ids=["kernel", "parent", "no_jit_prefill", "no_busy_time", "no_trace"])
def test_reader_on_a_made_up_reduced_trace(ctx, want):
    got = Bench().reader(NAME).read(ctx)
    assert got == (want if want is None else pytest.approx(want))


def test_reader_through_the_reduction():
    """A chunk program of 1,000 ns holding two calls of the kernel (a
    ``tpu_custom_call`` named by its ``pallas_call``) of 100 ns each, and a
    decode block whose kernels do not count."""
    call = ('%{}.{} = bf16[32,1024,128]{{2,1,0}} custom-call(s32[1]{{0}} %s, '
            'bf16[32,1024,128]{{2,1,0}} %q), '
            'custom_call_target="tpu_custom_call"')
    chip = {tr.OPS_LINE: [
        Ev("%fusion.130 = bf16[1024,4096]{1,0} fusion(%x)", 0, 800, {}),
        Ev(call.format(KERNEL, 1), 800, 100, {}),
        Ev(call.format(KERNEL, 2), 900, 100, {}),
        Ev(call.format("eva_decode_paged", 7), 1000, 500, {})],
        tr.MODULES_LINE: [Ev("jit_prefill(11)", 0, 1000, {}),
                          Ev("jit_body(12)", 1000, 500, {})]}
    s = tr.summarize({"/device:TPU:0": chip})
    assert s["kernels"][KERNEL]["count"] == 2
    assert Bench().reader(NAME).read({"trace": s}) == pytest.approx(20.0)


def test_listed_for_the_evabyte_cell():
    bench = Bench()
    (entry,) = [m for m in bench.manifest["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "device_trace", "layer": "Kernels",
                     "moves": "ttft_p50_ms",
                     "workloads": ["evabyte-L6.serve-doc"]}
    assert bench.manifest["per_layer"][-1] == entry      # appended, last
