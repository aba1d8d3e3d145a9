"""The reduction from a trace to numbers: interval arithmetic and the
summary on a hand-made trace whose every number can be checked by eye, and
on a small trace recorded on a v5e (``fixtures/``)."""

import os

import pytest

from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.trace_reduce import Ev

from conftest import TESTS_DIR


def test_interval_arithmetic():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.total([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]
    assert tr.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]


def op(name, start, dur):
    """An instruction's event as a v5e trace names it: by its whole text."""
    return Ev(name, start, dur, {})


def hand_made():
    """Two chips, a 1,000 ns window.  Chip 0:

        0-100   fusion.1                  (in program A, 0-300)
        100-250 custom-call, kernel k1    (program A)
        200-300 all-gather.1              (overlaps the kernel 200-250)
        300-400 idle                      (host: ds_serve_prefill)
        400-600 an all-reduce by opcode   (alone: exposed)
        600-700 fusion.2                  (program B, 400-700)
        0-700   while.3                   (only encloses: not counted)
        700-1000 idle                     (host: nothing -> between steps)
    """
    chip0 = {
        tr.OPS_LINE: [
            op("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop", 0,
               100),
            op('%k1.7 = bf16[8]{0} custom-call(bf16[8]{0} %p), '
               'custom_call_target="tpu_custom_call"', 100, 150),
            op("%all-gather.1 = bf16[32]{0} all-gather(bf16[8]{0} %p)", 200,
               100),
            op("%fusion.9 = f32[8]{0} all-reduce(f32[8]{0} %g), "
               "to_apply=%add", 400, 200),
            op("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %g)", 600, 100),
            op("%while.3 = (s32[], bf16[8]{0}) while(%t), body=%b", 0, 700)],
        tr.MODULES_LINE: [Ev("jit_A(123)", 0, 300, {}),
                          Ev("jit_B(456)", 400, 300, {})]}
    chip1 = {tr.OPS_LINE: [op("%fusion.1", 0, 500)]}
    host = {"main": [Ev(tr.WINDOW_MARK, 0, 1000, {}),
                     Ev(tr.STEP_MARK, 0, 720, {}),
                     Ev("ds_serve_prefill", 290, 120, {})]}
    return {"/device:TPU:0": chip0, "/device:TPU:1": chip1,
            "/host:CPU": host}


def test_summary_of_the_hand_made_trace():
    s = tr.summarize(hand_made(), host_scopes=("ds_serve_prefill",))
    ns = 1e-9
    assert s["window_s"] == pytest.approx(1000 * ns)
    assert s["busy_s_chip0"] == pytest.approx(600 * ns)     # 0-300, 400-700
    assert s["busy_s"] == pytest.approx((600 + 500) / 2 * ns)
    assert s["chips"] == 2
    assert s["programs"]["jit_A"] == {
        "count": 1, "span_s": pytest.approx(300 * ns),
        "busy_s": pytest.approx(300 * ns)}
    assert s["programs"]["jit_B"]["busy_s"] == pytest.approx(300 * ns)
    assert s["kernels"] == {"k1": {"count": 1,
                                   "seconds": pytest.approx(150 * ns)}}
    assert s["comm_s"] == pytest.approx(300 * ns)           # 200-300, 400-600
    assert s["comm_exposed_s"] == pytest.approx(250 * ns)   # 250-300, 400-600
    assert s["idle_gaps"] == {
        "ds_serve_prefill": pytest.approx(100 * ns),
        "between steps": pytest.approx(300 * ns)}
    b = tr.breakdown(s)
    assert b["device_ops"][0] == ["fusion.9", pytest.approx(200 * ns)]
    assert ["k1", pytest.approx(150 * ns)] in b["device_ops"]
    assert b["idle_gaps"][0][0] == "between steps"


def test_a_trace_without_a_device_plane_is_refused():
    with pytest.raises(tr.NoDeviceTrace):
        tr.summarize({"/host:CPU": {"main": [Ev("x", 0, 1, {})]}})


def test_fixture_round_trip(tmp_path):
    path = str(tmp_path / "t.json.gz")
    tr.save_events(hand_made(), path, 0, 10 ** 9)
    back = tr.load_events(path)
    assert tr.summarize(back, ("ds_serve_prefill",))["comm_exposed_s"] == \
        pytest.approx(250e-9)


# ---------------------------------------------------------------------------
# recorded on a v5e (my chip run, PR 24): 40 ms from the middle of a traced
# optimizer step of gpt2-xl under ZeRO-3 on four chips (8 x 1,024 a chip),
# cut by benchmarks/tools/cut_fixture.py
# ---------------------------------------------------------------------------

TRAIN_FIXTURE = os.path.join(TESTS_DIR, "fixtures",
                             "v5e_train_zero3_40ms.json.gz")


def raster(trace, plane="/device:TPU:0"):
    """Busy, collective and other-instruction time of one chip, nanosecond
    by nanosecond: slow, obvious, and sharing no code with the reduction."""
    import re

    import numpy as np

    coll = r"(all-gather|all-reduce|reduce-scatter|all-to-all|" \
           r"collective-permute)"
    lo, hi = tr.window_of(trace)
    n = int(hi - lo)
    busy, comm, other = (np.zeros(n, bool) for _ in range(3))
    for line in (tr.OPS_LINE, tr.ASYNC_LINE):
        for e in trace[plane].get(line, ()):
            name = e.name.split(" = ")[0].lstrip("%")
            if re.match(r"(while|conditional|call)[.\d]", name) or \
                    not lo <= e.start < hi:
                continue
            a = int(e.start - lo)
            b = min(int(e.start + e.dur - lo), n)
            collective = bool(re.match(coll, name) or re.search(
                r"\s" + coll + r"(-start|-done)?\(", e.name))
            if line == tr.OPS_LINE:
                busy[a:b] = True
                (comm if collective else other)[a:b] = True
            elif collective:
                comm[a:b] = True
    return n, busy.sum(), comm.sum(), (comm & ~other).sum()


def test_recorded_v5e_train_trace():
    trace = tr.load_events(TRAIN_FIXTURE)
    assert tr.device_planes(trace) == [f"/device:TPU:{i}" for i in range(4)]
    s = tr.summarize(trace, host_scopes=("ds_fwd_bwd",))
    n, busy, comm, exposed = raster(trace)
    # the values, worked out once by the raster and by eye from the events
    assert (n, busy, comm, exposed) == (40000000, 39998030, 5260820, 388775)
    assert s["window_s"] == pytest.approx(n * 1e-9)
    assert s["busy_s_chip0"] == pytest.approx(busy * 1e-9)
    assert s["comm_s"] == pytest.approx(comm * 1e-9)
    assert s["comm_exposed_s"] == pytest.approx(exposed * 1e-9)
    assert s["chips"] == 4
    # the average over the chips: every chip was busy all but ~2 us
    assert s["busy_s"] == pytest.approx(0.03999802875)
    # one program, the fused train step, busy for all of its span
    assert list(s["programs"]) == ["jit_fused1"]
    assert s["programs"]["jit_fused1"]["count"] == 1
    assert s["programs"]["jit_fused1"]["busy_s"] == pytest.approx(busy * 1e-9)
    # three layers' worth of backward: the kernels by their pallas names
    k = s["kernels"]
    assert {n: r["count"] for n, r in k.items()} == {
        "layer_norm_bwd": 6, "layer_norm_fwd": 6, "flash_attention_fwd": 3,
        "flash_attention_bwd_dkv": 3, "flash_attention_bwd_dq": 3}
    assert k["flash_attention_bwd_dkv"]["seconds"] == pytest.approx(4543082e-9)
    assert k["flash_attention_fwd"]["seconds"] == pytest.approx(4102267e-9)
    assert tr.breakdown(s)["device_ops"][0][0] == "flash_attention_bwd_dkv"
    # an event carries its instruction's text and its times, nothing else:
    # a jax.named_scope does not reach a v5e trace
    assert all(set(e.stats) <= {"hlo_op", "run_id"}
               for e in trace["/device:TPU:0"][tr.OPS_LINE])


# 40 ms from a traced window of mistral-7b-L8.serve-chat on one chip (my
# chip run, PR 24): the end of one scheduler iteration and most of the
# decode block of the next, with the host's ranges
SERVE_FIXTURE = os.path.join(TESTS_DIR, "fixtures",
                             "v5e_serve_mistral_40ms.json.gz")


def test_recorded_v5e_serve_trace():
    trace = tr.load_events(SERVE_FIXTURE)
    s = tr.summarize(trace, host_scopes=("ds_serve_admit",
                                         "ds_serve_prefill",
                                         "ds_serve_decode"))
    n, busy, comm, _ = raster(trace)
    assert (n, busy, comm) == (40000000, 36219556, 0)
    assert s["busy_s_chip0"] == pytest.approx(busy * 1e-9)
    assert s["busy_s"] == s["busy_s_chip0"] and s["chips"] == 1
    # the decode block, by its program's name, and the kernels inside it:
    # three decode steps of eight layers
    body = s["programs"]["jit_body"]
    assert body["count"] == 1
    assert body["busy_s"] == pytest.approx(36216738e-9)
    assert body["busy_s"] <= body["span_s"]
    assert {k: r["count"] for k, r in s["kernels"].items()} == {
        "fused_norm_qkv": 25, "paged_kv_append": 24, "flash_decode_paged": 24,
        "fused_proj_norm": 24, "fused_mlp": 24, "rms_norm_fwd": 3}
    assert s["kernels"]["flash_decode_paged"]["seconds"] == \
        pytest.approx(17084545e-9)
    in_kernels = sum(r["seconds"] for r in s["kernels"].values())
    assert in_kernels / s["busy_s_chip0"] == pytest.approx(0.9653, abs=1e-4)
    # the idle time lies where the host was inside ds_serve_prefill (the
    # first token's sync), hardly any inside ds_serve_decode
    gaps = s["idle_gaps"]
    assert sum(gaps.values()) == pytest.approx((n - busy) * 1e-9)
    assert gaps["ds_serve_prefill"] == pytest.approx(3773256e-9)
    assert gaps["ds_serve_decode"] == pytest.approx(7188e-9)
