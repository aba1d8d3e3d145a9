#!/usr/bin/env python3
"""Agreement of the served A.X-K1 cell with its plain reference at the
published widths, on the chip, outside any timed window (the
``model-configs`` guide, section 3, item 3).  Not part of a run; its numbers
go into PERF.md.

    python3 benchmarks/tools/axk1_agreement.py \\
        [--workload axk1-L5-ep16.serve-mixed-16k] [--seed 7]

Four requests are served by the cell's own engine (``init_serving`` with
the cell's settings: prefill through the chunk programs, decode through the
latent pages on the fused path, all in flight together), with
``lib/serve_taps.py`` copying out the program's logits and its routers'
choices at every generated position: prompts of 250 tokens (decoding crosses
the first 256-row page: the appended rows land on a second page, and every
position lies on the part of YaRN's ramp that ``original_max_position_
embeddings`` covers), 700 (one chunk, a padded bucket), 3,000 (three chunks)
and 5,000 (five chunks, past position 4,096, where the slowed pairs and the
kept pairs have turned apart), each decoded ``NEW`` tokens through the cache.

Each served sequence then goes through ``reference/axk1.py`` in ONE float32
forward (MLA decompressed, no cache), routed as the program routed
(``routing=``: bf16 flips a few per cent of top-8 sets between near-tied
experts or groups, which is not an arithmetic error), and the worst |program
- reference| over the logits of every generated position is reported in bf16
steps at that position's best logit, with the root mean square of the same
differences.

That the tolerances are tight enough is shown in the same run by wrong
references the program must FAIL against (the difference is symmetric: a
right program against a wrong reference differs as a wrong program against
the right one): :data:`WRONG`, each by at least one of the two limits on the
logits, and :data:`WRONG_ROUTERS`, which routing given by the program would
hide, by the driver's own limit on the served tokens with the reference left
to route by itself (``free_running``).  Among the first the precision
control, ``bf16_angles``: the rotation's angles rounded to bf16, the nearest
precision below the float32 they are computed in.
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
# every compared logit.  The program keeps weights, matmul inputs, latent
# rows AND the residual stream in bf16 and accumulates in float32 (router
# scores, norms, softmax statistics and the rotation's angles in float32);
# the reference is float32 throughout.  Both are read as the LARGEST of the
# four requests' own readings and set between the chip's readings on four
# seeds (7, 8, 9, 10; my chip runs, PR 48; PERF.md section 4).  The program:
# worst 2.34, 2.05, 2.03, 2.02; root mean square 0.3210, 0.3077, 0.3213,
# 0.3125.  The nearest wrong references: in the worst difference the base's
# frequencies without YaRN's blend (3.48, 3.66, 3.86, 3.45; every other
# control but the next 5.4 or more); in the root mean square the rotation's
# angles in bf16 (0.384, 0.367, 0.376, 0.364: the precision control, which
# fails this limit only, its worst 2.29-2.65), then the plain frequencies
# (0.533-0.582; the rest 0.82 or more).  The root mean square's room is 6%
# on either side (0.3213 | 0.342 | 0.364): a seed's own noise moves the
# sound reading and this control together (it reads 1.17-1.20 x its seed's
# sound reading), so a fresh seed that reads 0.342 sound would be a finding,
# not a fault.
TOLERANCE_STEPS = 2.9
RMS_TOLERANCE_STEPS = 0.342
# Share of (position, expert layer) pairs whose top-8 SET may differ between
# the program and the reference's own routing: bf16 flips 4.2-4.4% of them
# between near-tied experts or groups (four seeds); a router that chooses
# otherwise than by the published rule differs in most rows.
ROUTING_FLIP_LIMIT = 0.15

# what each breaks: the rotation left out (q_r and k_r unrotated); the
# base's frequencies without YaRN's blend; m^2 = 1 on the softmax scale; N_q
# left out; the cache row holding the UNROTATED key part under rotated
# queries; the rotation's angles in bf16
WRONG = ("no_rope", "plain_freq", "no_mscale", "no_q_norm",
         "unrotated_cache_key", "bf16_angles")
# ... and the routers: plain top-8 without the group limit
WRONG_ROUTERS = ("no_group_limit",)

PROMPTS = (250, 700, 3000, 5000)
NEW = 128


def agreement(workload: str, seed: int, *, manifest_path=None,
              allow_cpu: bool = False, lengths=None, wrong=WRONG,
              wrong_routers=WRONG_ROUTERS) -> dict:
    """``allow_cpu``, ``lengths``, ``wrong`` and ``wrong_routers`` exist for
    the benchmark's own tests (a tiny size: the paths and the bookkeeping)
    and have no command-line flag."""
    import gc

    import jax

    from benchmarks.lib.correctness import SERVE_TIE_STEPS
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
        rng = np.random.default_rng([seed, 0xA0C1])
        prompts = [rng.integers(0, vocab, n, dtype=np.int32)
                   for n, _ in shapes]
        served = serve_and_read(taps, serve, prompts, [n for _, n in shapes])
        serve.pool.check_no_leak()
        serve.close()
    del serve, built, taps.events[:], taps.chunks[:]
    gc.collect()              # the pool leaves the chip

    ref = bench.reference(cell["config"])
    # one padded length, so one compiled reference a variant
    pad = max(len(p) + n for p, (_, n) in zip(prompts, shapes))
    pad = -(-pad // ref.QUERY_BLOCK) * ref.QUERY_BLOCK
    names = ("exact",) + tuple(wrong)
    worst = {k: 0.0 for k in names}
    rms = {k: 0.0 for k in names}
    by_request = []
    positions = flips = choices = 0
    wrong_flips = dict.fromkeys(wrong_routers, 0)
    free_runs = [("free_running", {})] + [
        ("free_running_" + v, {"variant": (v,)}) for v in wrong_routers]
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
        one = {"prompt": len(p)}
        for name in names:
            kw = {} if name == "exact" else {"variant": (name,)}
            want = np.asarray(ref.logits_rows(
                params, config, seq, rows, devices[0],
                routing=rec["routing"], **kw))
            step = bf16_step(want.max(-1))
            steps = np.abs(rec["logits"] - want) / step[:, None]
            one[name] = (float(steps.max()),
                         float((steps ** 2).mean() ** 0.5))
            # the LARGEST of the requests' own readings: a fault that shows
            # on one kind of request (the slowed pairs past 4,096 positions)
            # is not thinned by the others' positions
            worst[name] = max(worst[name], one[name][0])
            rms[name] = max(rms[name], one[name][1])
            print(f"prompt {len(p)} {name}: {one[name]}", file=sys.stderr,
                  flush=True)
            if name == "exact":
                below["given_routing"] = max(below["given_routing"],
                                             steps_below(want, out))
        by_request.append(one)
        # what the driver's ``verify`` measures: the served tokens against
        # the reference left to route by itself (its near-ties admitted);
        # and the same against a wrong router
        for key, kw in free_runs:
            free = np.asarray(ref.logits_rows(params, config, seq, rows,
                                              devices[0], **kw))
            below[key] = max(below[key], steps_below(free, out))
        # how often the program's top-k set differs from the reference's own
        _, _, own = ref.hidden_states(params, config, seq, devices[0],
                                      return_routing=True, n_live=n)
        own = np.sort(np.asarray(own)[:, : n - 1], axis=-1)
        got = np.sort(np.stack(rec["routing"]), axis=-1)
        flips += int((own != got).any(-1).sum())
        choices += own.shape[0] * own.shape[1]
        positions += len(out)
        # ... and from a wrong router's own: a router without the group limit
        # chooses from other groups in most rows, which no near-tie explains
        for v in wrong_routers:
            _, _, theirs = ref.hidden_states(
                params, config, seq, devices[0], return_routing=True,
                n_live=n, variant=(v,))
            theirs = np.sort(np.asarray(theirs)[:, : n - 1], axis=-1)
            wrong_flips[v] += int((theirs != got).any(-1).sum())
    caught = {k: worst[k] > TOLERANCE_STEPS or rms[k] > RMS_TOLERANCE_STEPS
              for k in wrong}
    caught.update({k: below["free_running_" + k] > SERVE_TIE_STEPS
                   or wrong_flips[k] / max(choices, 1) > ROUTING_FLIP_LIMIT
                   for k in wrong_routers})
    return {
        "workload": workload, "seed": seed,
        "device": jax.devices()[0].device_kind,
        "requests": [{"prompt": len(p), "new": len(r["tokens"])}
                     for p, r in zip(prompts, served)],
        "generated_positions": positions,
        "worst_steps": worst["exact"], "rms_steps": rms["exact"],
        "routing_sets_flipped_share": flips / max(choices, 1),
        "routing_sets_flipped_share_against_wrong_router": {
            k: v / max(choices, 1) for k, v in wrong_flips.items()},
        "served_token_steps_below_best": below,
        "worst_steps_against_wrong_reference": {k: worst[k] for k in wrong},
        "rms_steps_against_wrong_reference": {k: rms[k] for k in wrong},
        "worst_and_rms_by_request": by_request,
        "caught": caught,
        "tolerance_steps": TOLERANCE_STEPS,
        "rms_tolerance_steps": RMS_TOLERANCE_STEPS,
        "served_tie_steps": SERVE_TIE_STEPS,
        "routing_flip_limit": ROUTING_FLIP_LIMIT,
        "ok": worst["exact"] <= TOLERANCE_STEPS
        and rms["exact"] <= RMS_TOLERANCE_STEPS
        and below["free_running"] <= SERVE_TIE_STEPS
        and flips / max(choices, 1) <= ROUTING_FLIP_LIMIT
        and all(caught.values())}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="axk1-L5-ep16.serve-mixed-16k")
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
