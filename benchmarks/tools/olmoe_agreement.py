#!/usr/bin/env python3
"""Agreement of a served mixture-of-experts cell with its plain reference at
the published widths, on the chip, outside any timed window (the
``model-configs`` guide, section 3, item 3).  Not part of a run; its numbers
go into PERF.md.

    python3 benchmarks/tools/olmoe_agreement.py \
        [--workload olmoe-1b-7b-L8.serve-chat] [--seed 7] [--requests 8]

A seeded sample of the cell's own requests is served by the cell's engine
(``init_serving`` with the cell's settings; prefill through the chunk
programs, decode through the paged pool on the fused path, all of them in
flight together), with ``lib/serve_taps.py`` copying out the program's
logits at every generated position and every router's choice.  Each served
sequence then goes through ``reference/olmoe.py`` in one float32 forward,
twice: free-running, and with the program's expert choices handed in.

Three numbers.  With seeded routers a token's eighth and ninth expert can
sit within a bf16 rounding of each other, and a flip swaps one expert's
whole contribution: not an arithmetic error, and not rounding-small.

1. ``routing_sets_differing``: share of (token, layer) top-k SETS that
   differ from the free-running reference's.  Reported, not bounded.
2. ``worst_steps_given_routing``: the worst |program - reference| over every
   logit of every generated position, the reference using the program's
   choices, in bf16 steps at that position's best logit.  THE TOLERANCE
   (``TOLERANCE_STEPS``) guards this one.
3. ``worst_steps_free_running``: the same against the free-running
   reference, for the record.
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

# Largest |program - reference| allowed at any logit of any generated
# position, in bf16 steps at that position's best logit (a step is 2^-7 of
# the best logit's power of two: bf16 keeps 8 significant bits), the
# reference routed as the program routed.  The program keeps activations,
# weights and the KV cache in bf16 and accumulates in float32; the reference
# is float32 throughout.  A logit is a 2,048-term dot product of a bf16
# hidden state that has been rounded at some forty places on its way through
# eight layers, so the two differ by a few roundings of the LARGEST logit at
# every logit, not of each logit's own size.  4 steps is 3% of the best
# logit's power of two, the bound `correctness.SERVE_TIE_STEPS` puts on the
# served token.  What it must catch sits far above: an expert matmul in a
# lower precision than bf16, a dropped token (its whole MoE output missing
# from the residual), a wrong page or a missing q/k norm move logits by tens
# to hundreds of steps.
TOLERANCE_STEPS = 4.0


def bf16_step(best: np.ndarray) -> np.ndarray:
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(best), 1e-30))) - 7)


def agreement(workload: str, seed: int, requests: int, *,
              manifest_path=None, allow_cpu: bool = False) -> dict:
    """``allow_cpu`` exists for the benchmark's own tests (a tiny size, the
    paths and the bookkeeping) and has no command-line flag."""
    import jax

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
    ctx = {"bench": bench, "cell": cell, "config": config, "mix": mix,
           "seed": seed, "devices": devices}
    with ServeTaps() as taps:
        built = driver.build(ctx)
        serve, params, vocab = built["serve"], built["params"], built["vocab"]
        schedule = serve_schedule(mix, float(cell["rate_rps"]), 40.0,
                                  seed, vocab)
        rng = np.random.default_rng([seed, 0xA62EE])
        pick = [schedule[i] for i in
                rng.permutation(len(schedule))[:requests]]
        served = serve_and_read(taps, serve, [a.prompt for a in pick],
                                [a.max_new_tokens for a in pick])
        serve.pool.check_no_leak()
        serve.close()
    del serve, built, taps.events[:]

    ref = bench.reference(cell["config"])
    k = config["num_experts_per_tok"]
    pad = int(cell["engine"]["max_out_tokens"])       # one compiled reference
    n_rows = int(mix["output_tokens"]["max"])
    sets = flipped = 0
    worst = {"given": 0.0, "free": 0.0}
    worst_abs = {"given": 0.0, "free": 0.0}
    positions = 0
    for rec, a in zip(served, pick):
        out = np.asarray(rec["tokens"], np.int32)
        n = len(a.prompt) + len(out)
        seq = np.zeros(pad, np.int32)        # right padding: causal, so it
        seq[:n] = np.concatenate([a.prompt, out])    # cannot reach a row read
        rows = list(range(len(a.prompt) - 1, n - 1))
        rows += rows[-1:] * (n_rows - len(out))
        x, outer, chosen = ref.hidden_states(params, config, seq, devices[0],
                                             return_routing=True)
        chosen = np.asarray(chosen)[:, : n - 1]                  # [L, n-1, k]
        mine = np.stack(rec["routing"])                          # [L, n-1, k]
        same = (np.sort(mine, -1) == np.sort(chosen, -1)).all(-1)
        sets += same.size
        flipped += int((~same).sum())
        # padded positions take any k distinct experts: no row read sees them
        routing = np.broadcast_to(np.arange(k, dtype=np.int32),
                                  (len(mine), pad, k)).copy()
        routing[:, : n - 1] = mine
        got = rec["logits"]
        for tag, r in (("free", None), ("given", list(routing))):
            want = np.asarray(ref.logits_rows(params, config, seq, rows,
                                              devices[0], routing=r)
                              )[: len(out)]
            diff = np.abs(got - want).max(-1)
            steps = diff / bf16_step(want.max(-1))
            worst[tag] = max(worst[tag], float(steps.max()))
            worst_abs[tag] = max(worst_abs[tag], float(diff.max()))
        positions += len(out)
    return {
        "workload": workload, "seed": seed,
        "requests": len(pick), "generated_positions": positions,
        "device": jax.devices()[0].device_kind,
        "routing_sets": sets, "routing_sets_differing": flipped,
        "routing_sets_differing_share": flipped / sets,
        "worst_steps_given_routing": worst["given"],
        "worst_abs_given_routing": worst_abs["given"],
        "worst_steps_free_running": worst["free"],
        "worst_abs_free_running": worst_abs["free"],
        "tolerance_steps": TOLERANCE_STEPS,
        "ok": worst["given"] <= TOLERANCE_STEPS}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="olmoe-1b-7b-L8.serve-chat")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--requests", type=int, default=8)
    args = ap.parse_args()
    line = agreement(args.workload, args.seed, args.requests)
    print(json.dumps(line), flush=True)
    out_dir = os.path.join(REPO_DIR, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "agreement.jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")
    return 0 if line["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
