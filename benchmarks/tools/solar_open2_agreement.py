#!/usr/bin/env python3
"""Agreement of the served Solar-Open2 cell with its plain reference at the
published widths, on the chip, outside any timed window (the
``model-configs`` guide, section 3, item 3).  Not part of a run; its numbers
go into PERF.md.

    python3 benchmarks/tools/solar_open2_agreement.py \\
        [--workload solar-open2-L4-ep8.serve-reason-4k] [--seed 7 ...]

Four requests are served by the cell's own engine (``init_serving`` with
the cell's settings: prefill through the chunk programs with the state
carried from chunk to chunk beside the full layer's K/V pages, decode through
the state kernel and the paged attention kernel on the fused path, all in
flight together), with ``lib/serve_taps.py`` copying out the program's logits
and its routers' choices at every generated position: prompts of 64 tokens
(the mix's shortest), 700 (one chunk, a padded bucket), 1,500 (two chunks of
UNEQUAL size: 1,024 and 476 in a bucket of 512) and 4,000 (four chunks), each
decoded ``NEW`` tokens through the cache.

Each served sequence then goes through ``reference/solar_open2.py`` in ONE
float32 forward (the recurrence one step a token, plain softmax), routed as
the program routed (``routing=``: bf16 flips a few per cent of top-8 sets
between near-tied experts, which is not an arithmetic error), and the worst
|program - reference| over the logits of every generated position is reported
in bf16 steps at that position's best logit, with the root mean square of
the same differences.  Every request is padded to ONE length, so each piece
of the reference compiles once a variant.

That the tolerances are tight enough is shown in the same run by wrong
references the program must FAIL against, by at least one of the two limits
(the difference is symmetric: a right program against a wrong reference
differs as a wrong program against the right one): :data:`WRONG`.  Among
them ``beta_sigmoid``, the delta rule's ``beta`` without its 2 (Kimi's form:
what a program that ignored ``kda_allow_neg_eigval`` computes), and the
precision control ``bf16_state``: the reference with its recurrent state
rounded to bf16 after every token, the nearest precision below the float32
the configuration states for it.
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
# every compared logit, each the LARGEST of the four requests' own readings.
# The program keeps weights, matmul inputs, K/V rows, convolution tails AND
# the residual stream in bf16, the recurrent state in float32, and
# accumulates in float32; the reference is float32 throughout.  Set between
# the chip's readings on three seeds (7, 8, 9; my chip runs, PR 59; PERF.md
# section 6).  The program: worst 2.29, 2.20, 2.23; root mean square 0.3259,
# 0.3173, 0.3261.  The nearest wrong references: in the worst difference
# RoPE wrongly on the full layer (2.99, 2.99, 3.08, on the shortest prompt)
# and the full layer's gate left out (10.0, 9.5, 9.8); in the root mean
# square the state in bf16 (0.3642, 0.3538, 0.3638: it fails that limit
# only, its worst 2.45-2.51 is the program's own) and RoPE on the full layer
# (0.4265, 0.4255, 0.4248).  The root mean square's room is 4% on either
# side (0.3261 | 0.340 | 0.3538): a seed's own noise moves the sound reading
# and the bf16 state's together (the control reads 1.115-1.117 x its seed's
# sound reading on all three), so a fresh seed that reads 0.34 sound would be
# a finding, not a fault.  ``beta`` without its 2 reads 43-46 and 6.1-6.2.
TOLERANCE_STEPS = 2.7
RMS_TOLERANCE_STEPS = 0.340

# what each breaks: beta without its 2, the state kept in bf16, the short
# convolution, the full layer's gate, RoPE wrongly on the full layer, the
# KDA output gate.  NOT among them: the router's bias used as a weight
# (``--wrong ... bias_weighs`` reads it): at this model's route scale of 1
# and a bias seeded x 0.05 it moves the logits by 2% of the bf16 noise
# (root mean square 0.3323, 0.3244, 0.3333 beside the program's 0.3259,
# 0.3173, 0.3261: under Kimi's scale of 2.446 it read 1.10 x), so no limit
# on the chip can tell it; the float32 CPU tests do, at 5e-5
# (``benchmarks/tests/test_solar_open2_cell.py``)
WRONG = ("beta_sigmoid", "bf16_state", "no_conv", "no_gqa_gate",
         "rope_on_gqa", "no_out_gate")

PROMPTS = (64, 700, 1500, 4000)
NEW = 256
# the wrong form the driver's own check is read against as well (the
# reference left to route by itself, its near-ties admitted)
FREE_RUNNING = ("beta_sigmoid",)


def agreement(workload: str, seed: int, *, manifest_path=None,
              allow_cpu: bool = False, lengths=None, wrong=WRONG) -> dict:
    """``allow_cpu``, ``lengths`` and ``wrong`` exist for the benchmark's
    own tests (a tiny size: the paths and the bookkeeping) and have no
    command-line flag."""
    import gc

    import jax

    from benchmarks.lib.device import place_compile_cache, require_devices
    from benchmarks.lib.manifest import Bench
    from benchmarks.lib.serve_taps import ServeTaps, serve_and_read
    from benchmarks.tools.trinity_agreement import bf16_step

    bench = Bench(manifest_path)
    cell = bench.cell(workload)
    config = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    driver = bench.driver(mix["kind"])
    devices = require_devices(int(cell["chips"]), allow_cpu=allow_cpu)
    place_compile_cache()
    shapes = lengths or tuple((n, NEW) for n in PROMPTS)
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
    gc.collect()              # pool and state leave the chip

    ref = bench.reference(cell["config"])
    # one padded length and one number of rows read, so one compiled
    # reference a variant
    pad = max(len(p) + n for p, (_, n) in zip(prompts, shapes))
    pad = -(-pad // ref.QUERY_BLOCK) * ref.QUERY_BLOCK
    n_rows = max(n for _, n in shapes)
    names = ("exact",) + tuple(wrong)
    worst = {k: 0.0 for k in names}
    rms = {k: 0.0 for k in names}
    by_request = []
    positions = flips = choices = 0
    free_runs = [("free_running", {})] + [
        ("free_running_" + v, {"variant": (v,)})
        for v in FREE_RUNNING if v in wrong]
    below = dict.fromkeys(["given_routing"] + [k for k, _ in free_runs], 0.0)

    def steps_below(logits, out):
        return float(((logits.max(-1) - logits[np.arange(len(out)), out])
                      / bf16_step(logits.max(-1))).max())

    for rec, p in zip(served, prompts):
        out = np.asarray(rec["tokens"], np.int32)
        n = len(p) + len(out)
        seq = np.zeros(pad, np.int32)
        seq[:n] = np.concatenate([p, out])
        rows = list(range(len(p) - 1, n - 1))
        rows += rows[-1:] * (n_rows - len(out))
        one = {"prompt": len(p)}
        for name in names:
            kw = {} if name == "exact" else {"variant": (name,)}
            want = np.asarray(ref.logits_rows(
                params, config, seq, rows, devices[0],
                routing=rec["routing"], **kw))[:len(out)]
            step = bf16_step(want.max(-1))
            steps = np.abs(rec["logits"] - want) / step[:, None]
            one[name] = (float(steps.max()),
                         float((steps ** 2).mean() ** 0.5))
            # the LARGEST of the requests' own readings: a fault that shows
            # on one kind of request is not thinned by the others' positions
            worst[name] = max(worst[name], one[name][0])
            rms[name] = max(rms[name], one[name][1])
            print(f"seed {seed} prompt {len(p)} {name}: {one[name]}",
                  file=sys.stderr, flush=True)
            if name == "exact":
                below["given_routing"] = max(below["given_routing"],
                                             steps_below(want, out))
        by_request.append(one)
        # what the driver's ``verify`` measures: the served tokens against
        # the reference left to route by itself (its near-ties admitted);
        # and the same against a wrong beta
        for key, kw in free_runs:
            free = np.asarray(ref.logits_rows(
                params, config, seq, rows, devices[0], **kw))[:len(out)]
            below[key] = max(below[key], steps_below(free, out))
        # how often the program's top-k set differs from the reference's own
        _, _, own = ref.hidden_states(params, config, seq, devices[0],
                                      return_routing=True, n_live=n)
        own = np.sort(np.asarray(own)[:, : n - 1], axis=-1)
        got = np.sort(np.stack(rec["routing"]), axis=-1)
        flips += int((own != got).any(-1).sum())
        choices += own.shape[0] * own.shape[1]
        positions += len(out)
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
        "worst_and_rms_by_request": by_request,
        "caught": caught,
        "tolerance_steps": TOLERANCE_STEPS,
        "rms_tolerance_steps": RMS_TOLERANCE_STEPS,
        "ok": worst["exact"] <= TOLERANCE_STEPS
        and rms["exact"] <= RMS_TOLERANCE_STEPS and all(caught.values())}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    default="solar-open2-L4-ep8.serve-reason-4k")
    ap.add_argument("--seed", type=int, nargs="+", default=[7])
    ap.add_argument("--wrong", nargs="*", default=list(WRONG),
                    help="the controls to read (default: all of them)")
    args = ap.parse_args()
    out_dir = os.path.join(REPO_DIR, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    ok = True
    for seed in args.seed:      # one process: the reference compiles once
        line = agreement(args.workload, seed, wrong=tuple(args.wrong))
        print(json.dumps(line), flush=True)
        with open(os.path.join(out_dir, "agreement.jsonl"), "a") as f:
            f.write(json.dumps(line) + "\n")
        ok = ok and line["ok"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
