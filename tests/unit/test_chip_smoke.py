"""``chip_smoke.py`` rehearsed without the chip, and the rules it rests on.

The phases run here at a tiny size on the virtual CPU mesh, with the Pallas
kernels in interpret mode: a TEST-side choice (``default_impl`` patched),
not an option of the program.  ``main()`` is what asserts the platform, and
the phases are plain functions, so the tests call them directly with small
sizes and no kernel expectations (an interpreted kernel leaves no
``tpu_custom_call``).  What only the chip can show — that the kernels
compile, fit and run — is ``test_tpu_compile.py`` and the chip run itself.
"""

import json
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from deepspeed_tpu.ops.pallas import common  # noqa: E402

TINY = dict(num_layers=2, hidden_size=128, intermediate_size=512,
            num_heads=2, vocab_size=1024)


@pytest.fixture()
def interpret_kernels(monkeypatch):
    monkeypatch.setattr(common, "default_impl", lambda: "interpret")
    monkeypatch.setattr(common, "_REFERENCE_SELECTED", {})


def test_train_phase_tiny(devices, interpret_kernels):
    rec = chip_smoke.train_phase(devices, overrides=dict(TINY, max_seq_len=128),
                                 seq=128, micro=2, steps=5, want_kernels=())
    assert rec["phase"] == "train" and rec["steps"] == 5
    assert rec["losses"][-1] < rec["losses"][0]
    # seq 128 takes the flash kernel: no shape rule put a reference in
    assert rec["reference_in_place_of_kernel"] == []
    assert set(rec["wall_s"]) == {"total", "trace", "lower", "compile",
                                  "steady", "note"}
    json.dumps(rec)


def test_a_missing_kernel_fails_the_phase():
    chip_smoke.check_has("train: compiled step", ("a",), ["a", "b"])
    with pytest.raises(chip_smoke.SmokeFailure, match="lacks .'c'.; has"):
        chip_smoke.check_has("train: compiled step", ("a", "c"), ["a", "b"])


def test_serve_phase_tiny(devices, interpret_kernels):
    rec = chip_smoke.serve_phase(
        devices, overrides=dict(TINY, max_seq_len=512),
        config={"dtype": "float32", "max_out_tokens": 512},
        prompts=(40, 300), shared=(280, 40), new_tokens=5,
        want_kernels=())
    # float32 on the CPU: token for token what generate() says
    assert rec["requests"] == 3 and rec["equal_generate"] == "3 of 3 requests"
    assert rec["near_ties"] == [] and rec["worst_steps_below_best"] == 0
    # max_out_tokens 512 resolves kv_page_tokens: 0 to the kernel's page
    assert rec["kv_page_tokens"] == 256 and rec["prefix_hit_tokens"] == 256
    assert rec["reference_in_place_of_kernel"] == []


def test_sharded_phase_on_four_virtual_devices(devices, interpret_kernels):
    rec = chip_smoke.sharded_phase(
        devices[:4], overrides=dict(TINY, max_seq_len=128), seq=128, batch=8,
        steps=3, max_share=0.6,
        # XLA's CPU backend leaves the gradient reduction an all-reduce; the
        # reduce-scatter is asked of the TPU compile
        want_collectives=("all-gather",))
    assert rec["chips"] == 4
    assert rec["loss_rtol"]["worst"] <= chip_smoke.SHARDED_LOSS_RTOL
    assert len(rec["param_shards"]["device_share"]) == 4
    assert rec["params"] > 0
    assert rec["embedding"]["shard_shape"] == [1024, 32]


def test_final_line_is_the_contract(devices):
    line = json.loads(chip_smoke.final_line(devices[:1]))
    assert line == {"ok": True, "device": {
        "platform": "cpu", "kind": devices[0].device_kind, "count": 1}}
    assert list(line) == ["ok", "device"]
    assert list(line["device"]) == ["platform", "kind", "count"]


def test_main_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_kernel_names_come_from_the_custom_calls():
    text = ('  %a = f32[8] custom-call(%x), custom_call_target="tpu_custom_'
            'call", metadata={op_name="jit(f)/while/body/fused_mlp/pallas_'
            'call"}\n  %b = f32[8] custom-call(%x), custom_call_target='
            '"Sharding", metadata={op_name="jit(f)/not_a_kernel/pallas_call"}')
    assert chip_smoke.kernels_in(text) == ["fused_mlp"]


def test_collectives_include_the_tpu_fused_reduce_scatter():
    text = ("  %ag = bf16[8] all-gather-start(%x), dimensions={0}\n"
            "  %ar = f32[] all-reduce(%y), to_apply=%add\n"
            "%all-reduce-scatter.4.clone (input: bf16[8]) -> bf16[2] {\n"
            "  %f = bf16[2] fusion(%g), calls=%all-reduce-scatter.4.clone\n")
    assert chip_smoke.collectives_in(text) == [
        "all-gather", "all-reduce", "reduce-scatter"]
    assert chip_smoke.collectives_in("%all-reduce.1 = f32[] add(%a)") == []


def test_near_tie_measure_counts_bf16_steps():
    import numpy as np

    # best 3.03125 lies in [2, 4): bf16 steps there are 2^-6 wide
    rows = np.asarray([[3.0, 3.03125, 1.0]], np.float32)
    assert chip_smoke._ulps_below_best(rows, [1])[0] == 0
    assert chip_smoke._ulps_below_best(rows, [0])[0] == 2
    assert chip_smoke._ulps_below_best(rows, [2])[0] == 130


def test_shape_rules_select_the_reference_loudly(interpret_kernels):
    from deepspeed_tpu.models.layers import flash_reference_reason
    from deepspeed_tpu.ops.pallas.decode import (decode_reference_reason,
                                                 paged_decode_reference_reason)

    assert paged_decode_reference_reason(256) is None
    assert "16" in paged_decode_reference_reason(16)
    assert decode_reference_reason(1024, 256) is None
    assert decode_reference_reason(145, 256)
    assert flash_reference_reason(8, 12, 1024, nb=4) is None
    assert flash_reference_reason(8, 12, 100)
    assert flash_reference_reason(6, 12, 1024, nb=4)
    assert common.kernel_or_reference("op", "pallas", None) == "pallas"
    assert common.kernel_or_reference("op", "pallas", "why") == "xla"
    assert common.kernel_or_reference("op", "interpret", "why") == "xla"
    assert common.reference_selections() == [("op", "why")]


def test_peak_flops_raises_on_an_unknown_device(devices):
    from deepspeed_tpu.profiling.flops import peak_flops

    class V5e:
        device_kind = "TPU v5 lite"

    assert peak_flops(V5e()) == 197e12
    with pytest.raises(KeyError, match="no peak"):
        peak_flops(devices[0])        # the CPU is not in the table


def test_accelerator_is_tpu_or_cpu_only(monkeypatch):
    from deepspeed_tpu.accelerator import real_accelerator

    monkeypatch.setenv("DS_ACCELERATOR", "gpu")
    with pytest.raises(ValueError, match="not supported"):
        real_accelerator._detect()
    monkeypatch.setenv("DS_ACCELERATOR", "cpu")
    assert real_accelerator._detect().name() == "cpu"


def test_compile_cache_rule(monkeypatch):
    from deepspeed_tpu.utils.compile_cache import place_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert place_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert place_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
