#!/usr/bin/env python3
"""Agreement of the served Trinity cell with its plain reference at the
published widths, on the chip, outside any timed window (the
``model-configs`` guide, section 3, item 3).  Not part of a run; its numbers
go into PERF.md.

    python3 benchmarks/tools/trinity_agreement.py \\
        [--workload trinity-large-L5-ep8.serve-mixed-16k] [--seed 7]

Three requests are served by the cell's own engine (``init_serving`` with
the cell's settings: prefill through the chunk programs, decode through the
two page budgets on the fused path, all in flight together), with
``lib/serve_taps.py`` copying out the program's logits and its routers'
choices at every generated position:

- one whose prompt lies INSIDE the window (every layer acts as full
  attention; no ring row is ever overwritten);
- one whose prompt's chunks CROSS position ``sliding_window`` in prefill (a
  chunk attends ring rows it is about to overwrite);
- one whose prompt ends ``DECODE_BEFORE`` short of the window and crosses it
  while DECODING (the ring wraps under the decode kernels).

Each served sequence then goes through ``reference/trinity.py`` in ONE
float32 forward, routed as the program routed (``routing=``: bf16 flips a
few per cent of top-k sets between near-tied experts, which is not an
arithmetic error), and the worst |program - reference| over the logits of
every generated position is reported in bf16 steps at that position's best
logit, with the root mean square of the same differences.

That the tolerances are tight enough is shown in the same run by wrong
references the program must FAIL against, by at least one of the two limits
(the difference is symmetric: a right program against a wrong reference
differs as a wrong program against the right one): ``no_window`` (a sliding
layer attends everything), ``rope_global`` (RoPE in the global layer),
``no_gate``, ``no_post_norm``, ``bias_weighs`` (the selection bias added to
the weights), ``no_route_scale``, ``stale_ring`` (one ring page a window
old).  The published configuration keeps no float32 stream, so there is no
precision below the program's own to read the reference in.
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

# Largest |program - reference| allowed at any compared logit, in bf16 steps
# at that position's best logit, and the root mean square of the same over
# every compared logit.  The program keeps weights, matmul inputs, cache
# rows AND the residual stream in bf16 and accumulates in float32; the
# reference is float32 throughout.  Settled on the chip between the readings
# PERF.md gives (section 4, trinity-large-L5-ep8; my chip runs, PR 36): the
# largest the program showed over its seeds (worst 2.65-2.88, root mean
# square 0.407-0.424) and the smallest any wrong reference showed (the bias
# used as a weight: worst 5.38-6.10, root mean square 0.570-0.717; every
# other one 14 steps or more).
TOLERANCE_STEPS = 4.0
RMS_TOLERANCE_STEPS = 0.5

WRONG = ("no_window", "rope_global", "no_gate", "no_post_norm",
         "bias_weighs", "no_route_scale", "stale_ring")

INSIDE = (1500, 24)       # prompt, new tokens: inside one window
CROSS_PREFILL = (6000, 24)  # chunks cross position 4,096
DECODE_BEFORE, DECODE_NEW = 6, 40   # prompt ends 6 short; decodes across


def bf16_step(best: np.ndarray) -> np.ndarray:
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(best), 1e-30))) - 7)


def agreement(workload: str, seed: int, *, manifest_path=None,
              allow_cpu: bool = False, lengths=None, wrong=WRONG) -> dict:
    """``allow_cpu``, ``lengths`` and ``wrong`` exist for the benchmark's
    own tests (a tiny size: the paths and the bookkeeping) and have no
    command-line flag."""
    import jax

    from benchmarks.lib.device import place_compile_cache, require_devices
    from benchmarks.lib.manifest import Bench
    from benchmarks.lib.serve_taps import ServeTaps, serve_and_read

    bench = Bench(manifest_path)
    cell = bench.cell(workload)
    config = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    driver = bench.driver(mix["kind"])
    devices = require_devices(int(cell["chips"]), allow_cpu=allow_cpu)
    place_compile_cache()
    W = int(config["sliding_window"])
    shapes = lengths or (INSIDE, CROSS_PREFILL,
                         (W - DECODE_BEFORE, DECODE_NEW))
    ctx = {"bench": bench, "cell": cell, "config": config, "mix": mix,
           "seed": seed, "devices": devices}
    with ServeTaps() as taps:
        built = driver.build(ctx)
        serve, params, vocab = built["serve"], built["params"], built["vocab"]
        rng = np.random.default_rng([seed, 0x7A1])
        prompts = [rng.integers(0, vocab, n, dtype=np.int32)
                   for n, _ in shapes]
        served = serve_and_read(taps, serve, prompts, [n for _, n in shapes])
        serve.pool.check_no_leak()
        serve.close()
    del serve, built, taps.events[:], taps.chunks[:]
    import gc
    gc.collect()              # the two budgets leave the chip

    ref = bench.reference(cell["config"])
    # one padded length, so one compiled reference a variant
    pad = max(len(p) + n for p, (_, n) in zip(prompts, shapes))
    pad = -(-pad // ref.QUERY_BLOCK) * ref.QUERY_BLOCK
    names = ("exact",) + tuple(wrong)
    worst = {k: 0.0 for k in names}
    squares = {k: 0.0 for k in names}
    positions = flips = choices = 0
    below = {"given_routing": 0.0, "free_running": 0.0}
    for rec, p in zip(served, prompts):
        out = np.asarray(rec["tokens"], np.int32)
        seq = np.zeros(pad, np.int32)
        n = len(p) + len(out)
        seq[:n] = np.concatenate([p, out])
        rows = list(range(len(p) - 1, n - 1))
        for name in names:
            want = np.asarray(ref.logits_rows(
                params, config, seq, rows, devices[0],
                routing=rec["routing"],
                variant=() if name == "exact" else (name,)))
            step = bf16_step(want.max(-1))
            steps = np.abs(rec["logits"] - want) / step[:, None]
            worst[name] = max(worst[name], float(steps.max()))
            squares[name] += float((steps ** 2).sum())
            if name == "exact":
                below["given_routing"] = max(below["given_routing"], float(
                    ((want.max(-1) - want[np.arange(len(out)), out])
                     / step).max()))
        # what the driver's ``verify`` measures: the served tokens against
        # the reference left to route by itself (its near-ties admitted)
        free = np.asarray(ref.logits_rows(params, config, seq, rows,
                                          devices[0]))
        below["free_running"] = max(below["free_running"], float(
            ((free.max(-1) - free[np.arange(len(out)), out])
             / bf16_step(free.max(-1))).max()))
        # how often the program's top-k set differs from the reference's own
        _, _, own = ref.hidden_states(params, config, seq, devices[0],
                                      return_routing=True, n_live=n)
        own = np.sort(np.asarray(own)[:, : n - 1], axis=-1)
        got = np.sort(np.stack(rec["routing"]), axis=-1)
        flips += int((own != got).any(-1).sum())
        choices += own.shape[0] * own.shape[1]
        positions += len(out)
    rms = {k: (v / (positions * vocab)) ** 0.5 for k, v in squares.items()}
    caught = {k: worst[k] > TOLERANCE_STEPS or rms[k] > RMS_TOLERANCE_STEPS
              for k in wrong}
    return {
        "workload": workload, "seed": seed,
        "device": jax.devices()[0].device_kind,
        "requests": [{"prompt": len(p), "new": len(r["tokens"])}
                     for p, r in zip(prompts, served)],
        "generated_positions": positions,
        "worst_steps": worst["exact"], "rms_steps": rms["exact"],
        "routing_sets_flipped_share": flips / max(choices, 1),
        "served_token_steps_below_best": below,
        "worst_steps_against_wrong_reference": {k: worst[k] for k in wrong},
        "rms_steps_against_wrong_reference": {k: rms[k] for k in wrong},
        "caught": caught,
        "tolerance_steps": TOLERANCE_STEPS,
        "rms_tolerance_steps": RMS_TOLERANCE_STEPS,
        "ok": worst["exact"] <= TOLERANCE_STEPS
        and rms["exact"] <= RMS_TOLERANCE_STEPS and all(caught.values())}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    default="trinity-large-L5-ep8.serve-mixed-16k")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    line = agreement(args.workload, args.seed)
    print(json.dumps(line), flush=True)
    out_dir = os.path.join(REPO_DIR, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "agreement.jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")
    return 0 if line["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
