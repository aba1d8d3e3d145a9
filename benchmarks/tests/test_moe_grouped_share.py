"""``moe_grouped_prefill_share`` / ``moe_grouped_kernel_share``: right on a
made-up reduced trace of a program on the Pallas kernel, of one on XLA's
``ragged-dot`` (the parent of PR 64: the first reads the same quantity under
the same name, the second 0) and of one on both, through the reduction on a
hand-made trace, ``None`` — never a wrong value — without a trace, without a
chunk program in the window or without a grouped matmul, and listed for the
seven cells whose chunk programs hold an expert layer."""

import pytest

from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.manifest import Bench
from benchmarks.lib.trace_reduce import Ev

SHARE, KERNEL_SHARE = "moe_grouped_prefill_share", "moe_grouped_kernel_share"
KERNEL, PROGRAM = "moe_grouped_matmul", "jit_prefill"
CELLS = {"olmoe-1b-7b-L8.serve-chat", "trinity-large-L5-ep8.serve-mixed-16k",
         "kimi-linear-L5-ep8.serve-reason-doc-tail",
         "axk1-L5-ep16.serve-mixed-16k", "dots3-note-L5-ep16.serve-doc-48k",
         "solar-open2-L4-ep8.serve-reason-4k",
         "nemotron3-nano-L9-ep2.serve-reason-4k"}


def reduced(kernels, ops, programs):
    return {"bench": Bench(), "trace": {
        "kernels": kernels, "programs": programs,
        "ops": dict(ops, **kernels)}}


CHUNKS = {PROGRAM: {"count": 11, "span_s": 0.45, "busy_s": 0.44},
          "jit_body": {"count": 7, "span_s": 0.56, "busy_s": 0.55}}
DECODE = {"fused_moe_mlp": {"count": 280, "seconds": 0.346}}

CASES = {
    # the ledger's line of PR 63: 88 calls of XLA's grouped matmul
    "ragged_dot": (reduced(DECODE, {
        "ragged-dot-none": {"count": 88, "seconds": 0.263},
        "ragged-dot-metadata": {"count": 88, "seconds": 0.001},
        "fusion.12": {"count": 44, "seconds": 0.1}}, CHUNKS), 60.0, 0.0),
    "kernel": (reduced(dict(DECODE, **{
        KERNEL: {"count": 88, "seconds": 0.088}}),
        {"fusion.12": {"count": 44, "seconds": 0.1}}, CHUNKS), 20.0, 100.0),
    # a shape rule keeps some calls on XLA's
    "both": (reduced({KERNEL: {"count": 44, "seconds": 0.033}},
                     {"ragged-dot-none.3": {"count": 44, "seconds": 0.011}},
                     CHUNKS), 10.0, 75.0),
    "no_expert_layer": (reduced({"fused_mlp": {"count": 9, "seconds": 0.2}},
                                {"fusion.12": {"count": 44, "seconds": 0.1}},
                                CHUNKS), None, None),
    "no_jit_prefill": (reduced({KERNEL: {"count": 2, "seconds": 0.002}}, {},
                               {"jit_body": CHUNKS["jit_body"]}), None, 100.0),
    "no_busy_time": (reduced({KERNEL: {"count": 2, "seconds": 0.002}}, {}, {
        PROGRAM: {"count": 1, "span_s": 0.0, "busy_s": 0.0}}), None, 100.0),
    "no_trace": ({"bench": Bench(), "trace": None}, None, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_readers_on_a_made_up_reduced_trace(case):
    ctx, share, kernel_share = CASES[case]
    approx = lambda v: v if v is None else pytest.approx(v)
    assert Bench().reader(SHARE).read(ctx) == approx(share)
    assert Bench().reader(KERNEL_SHARE).read(ctx) == approx(kernel_share)


@pytest.mark.parametrize("on_kernel", [True, False],
                         ids=["kernel", "ragged_dot"])
def test_readers_through_the_reduction(on_kernel):
    """A chunk program of 1,000 ns holding two grouped matmuls of 100 ns
    each, as the kernel (a ``tpu_custom_call`` named by its
    ``pallas_call``) or as XLA's instruction, and a decode block whose
    expert kernel does not count."""
    call = ('%{}.{} = bf16[6144,2048]{{1,0}} custom-call(s32[1]{{0}} %b, '
            'bf16[6144,2688]{{1,0}} %rows), '
            'custom_call_target="tpu_custom_call"')
    xla = ("%ragged-dot-none{} = bf16[6272,2048]{{1,0}} custom-call("
           "bf16[6272,2688]{{1,0}} %rows), "
           'custom_call_target="RaggedDot"')
    two = [Ev(call.format(KERNEL, 1), 800, 100, {}),
           Ev(call.format(KERNEL, 2), 900, 100, {})] if on_kernel else [
        Ev(xla.format(""), 800, 100, {}), Ev(xla.format(".1"), 900, 100, {})]
    chip = {tr.OPS_LINE: [
        Ev("%fusion.130 = bf16[1024,2688]{1,0} fusion(%x)", 0, 800, {}),
        *two, Ev(call.format("fused_moe_mlp", 7), 1000, 500, {})],
        tr.MODULES_LINE: [Ev("jit_prefill(11)", 0, 1000, {}),
                          Ev("jit_body(12)", 1000, 500, {})]}
    ctx = {"bench": Bench(), "trace": tr.summarize({"/device:TPU:0": chip})}
    assert Bench().reader(SHARE).read(ctx) == pytest.approx(20.0)
    assert Bench().reader(KERNEL_SHARE).read(ctx) == pytest.approx(
        100.0 if on_kernel else 0.0)


@pytest.mark.parametrize("name,better", [(SHARE, "lower"),
                                         (KERNEL_SHARE, "higher")])
def test_listed_for_the_cells_with_expert_layers(name, better):
    bench = Bench()
    (entry,) = [m for m in bench.manifest["per_layer"] if m["name"] == name]
    assert entry == dict(entry, unit="%", better=better,
                         source="device_trace", layer="Kernels",
                         moves="tpot_p50_ms")
    assert CELLS <= set(entry["workloads"])
    for cell in CELLS:       # every listed cell reports what the metric moves
        assert entry in bench.metrics_for("per_layer", cell)
        assert "tpot_p50_ms" in {m["name"] for m in
                                 bench.metrics_for("end_to_end", cell)}
