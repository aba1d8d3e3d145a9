#!/usr/bin/env python3
"""Agreement of a served looped-stack cell with its plain reference at the
published widths, on the chip, outside any timed window (the
``model-configs`` guide, section 3, item 3).  Not part of a run; its numbers
go into PERF.md.

    python3 benchmarks/tools/ouro_agreement.py \
        [--workload ouro-2.6b-L12.serve-reason-768] [--seed 7] \
        [--requests 4] [--control shared_cache_layer|three_passes|bf16_stream]

A seeded sample of the cell's own requests is served by the cell's engine
(``init_serving`` with the cell's settings: prefill through the chunk
programs, decode through the paged pool on the fused path, the pass loop
rolled, all requests in flight together), with ``lib/serve_taps.py`` copying
out the program's logits at every generated position.  Each served sequence
then goes through ``reference/ouro.py`` in one float32 forward under
``jax.default_matmul_precision("highest")``.

Two numbers, each with a limit (and ``below_best_steps``, the harness's own
measure over EVERY generated position of the sample: how far below the
reference's best logit the served token's sits, of which a run's ``correct``
allows ``correctness.SERVE_TIE_STEPS``):

1. ``worst_steps``: the worst |program - reference| over every logit of
   every generated position, in bf16 steps at that position's best logit
   (``LIMIT_WORST_STEPS``).
2. ``rms_steps``: the root mean square of the same differences
   (``LIMIT_RMS_STEPS``): a worst case is one logit of fifty thousand times
   some thousand positions and moves with the sample; the mean does not.

``--control`` serves a WRONG program, which must fail the limits:
``shared_cache_layer`` (every pass reads and writes cache layer ``l`` in
place of ``t * L + l``: the decode block's earlier keys are then the last
pass's), ``three_passes`` (``total_ut_steps - 1``), ``bf16_stream`` (the
residual stream in bf16 where the configuration states float32: the nearest
precision below the one the configuration states).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)
sys.path.insert(0, REPO_DIR)

# The program keeps weights, matmul inputs and the K/V cache in bf16, accumulates
# in float32 and carries the residual stream in float32 (the configuration's
# ``fp32_residual``); the reference is float32 throughout.  A logit is a
# 2,048-term dot product of a stream that 48 layer applications have each
# added two bf16-rounded sub-block outputs to.  The limits stand between the
# two readings of PERF.md section 4 (my chip runs, PR 57): the program's
# largest over its seeds under them, the same program with its stream in bf16
# (``--control bf16_stream``) over them, by either limit; a wrong cache layer
# or a pass too few misses them by two orders of magnitude.
LIMIT_WORST_STEPS = 3.3
LIMIT_RMS_STEPS = 0.33
CONTROLS = ("shared_cache_layer", "three_passes", "bf16_stream")


def bf16_step(best: np.ndarray) -> np.ndarray:
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(best), 1e-30))) - 7)


def _share_cache_layers(num_layers: int):
    """Patch the fused decode step so that every pass uses cache layer
    ``l``; returns the undo."""
    from deepspeed_tpu.models import fused_decode

    append, attend = fused_decode.paged_kv_append, fused_decode.flash_decode
    fused_decode.paged_kv_append = lambda *a, layer, **kw: append(
        *a, layer=layer % num_layers, **kw)
    fused_decode.flash_decode = lambda *a, layer, **kw: attend(
        *a, layer=layer % num_layers, **kw)

    def undo():
        fused_decode.paged_kv_append, fused_decode.flash_decode = \
            append, attend
    return undo


def agreement(workload: str, seed: int, requests: int, control=None, *,
              manifest_path=None, allow_cpu: bool = False) -> dict:
    """``allow_cpu`` exists for the benchmark's own tests (a tiny size, the
    paths and the bookkeeping) and has no command-line flag."""
    import jax

    from benchmarks.lib.correctness import bf16_steps_below_best
    from benchmarks.lib.device import place_compile_cache, require_devices
    from benchmarks.lib.manifest import Bench
    from benchmarks.lib.serve_taps import ServeTaps, serve_and_read
    from benchmarks.lib.traffic import serve_schedule

    bench = Bench(manifest_path)
    cell = bench.cell(workload)
    config = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    driver = bench.driver(mix["kind"])
    devices = require_devices(int(cell["chips"]), allow_cpu=allow_cpu)
    place_compile_cache()
    mc = dict(config["model_config"])      # the SERVED model's fields
    if control == "three_passes":
        mc["total_ut_steps"] -= 1
    if control == "bf16_stream":
        mc["fp32_residual"] = False
    served_config = dict(config, model_config=mc)
    ctx = {"bench": bench, "cell": cell, "config": served_config, "mix": mix,
           "seed": seed, "devices": devices}
    undo = (_share_cache_layers(config["model_config"]["num_layers"])
            if control == "shared_cache_layer" else lambda: None)
    try:
        with ServeTaps() as taps:
            built = driver.build(ctx)
            serve, params, vocab = (built["serve"], built["params"],
                                    built["vocab"])
            schedule = serve_schedule(mix, float(cell["rate_rps"]), 40.0,
                                      seed, vocab)
            rng = np.random.default_rng([seed, 0x0020])
            pick = [schedule[i] for i in
                    rng.permutation(len(schedule))[:requests]]
            served = serve_and_read(taps, serve, [a.prompt for a in pick],
                                    [a.max_new_tokens for a in pick])
            serve.pool.check_no_leak()
            serve.close()
    finally:
        undo()
    del serve, built, taps.events[:]

    ref = bench.reference(cell["config"])
    pad = int(cell["engine"]["max_out_tokens"])       # one compiled reference
    if pad > 1024:
        pad = -(-pad // 512) * 512
    n_rows = int(mix["output_tokens"]["max"])
    worst = worst_abs = square = count = below = 0.0
    positions = 0
    for rec, a in zip(served, pick):
        out = np.asarray(rec["tokens"], np.int32)
        n = len(a.prompt) + len(out)
        seq = np.zeros(pad, np.int32)        # right padding: causal, so it
        seq[:n] = np.concatenate([a.prompt, out])    # cannot reach a row read
        rows = list(range(len(a.prompt) - 1, n - 1))
        rows += rows[-1:] * (n_rows - len(out))
        want = np.asarray(ref.logits_rows(params, config, seq, rows,
                                          devices[0]))[: len(out)]
        diff = np.abs(rec["logits"] - want)
        steps = diff / bf16_step(want.max(-1))[:, None]
        below = max(below, float(bf16_steps_below_best(want, out).max()))
        worst = max(worst, float(steps.max()))
        worst_abs = max(worst_abs, float(diff.max()))
        square += float((steps.astype(np.float64) ** 2).sum())
        count += steps.size
        positions += len(out)
    rms = (square / count) ** 0.5
    return {
        "workload": workload, "seed": seed, "control": control,
        "requests": len(pick),
        "generated_positions": positions,
        "device": jax.devices()[0].device_kind,
        "worst_steps": worst, "worst_abs": worst_abs, "rms_steps": rms,
        "below_best_steps": below,
        "limit_worst_steps": LIMIT_WORST_STEPS,
        "limit_rms_steps": LIMIT_RMS_STEPS,
        "ok": worst <= LIMIT_WORST_STEPS and rms <= LIMIT_RMS_STEPS}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="ouro-2.6b-L12.serve-reason-768")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--control", choices=CONTROLS, default=None)
    args = ap.parse_args()
    line = agreement(args.workload, args.seed, args.requests, args.control)
    print(json.dumps(line), flush=True)
    out_dir = os.path.join(REPO_DIR, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "agreement.jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")
    # a control passes its run by FAILING the limits
    return 0 if line["ok"] == (args.control is None) else 1


if __name__ == "__main__":
    sys.exit(main())
