#!/usr/bin/env python3
"""Agreement of the served Kimi-Linear cell with its plain reference at the
published widths, on the chip, outside any timed window (the
``model-configs`` guide, section 3, item 3).  Not part of a run; its numbers
go into PERF.md.

    python3 benchmarks/tools/kimi_linear_agreement.py \\
        [--workload kimi-linear-L5-ep8.serve-reason-doc-tail] [--seed 7]

Four requests are served by the cell's own engine (``init_serving`` with
the cell's settings: prefill through the chunk programs with the state
carried from chunk to chunk, decode through the state kernel and the latent
pages on the fused path, all in flight together), with ``lib/serve_taps.py``
copying out the program's logits and its routers' choices at every generated
position: prompts of 128 tokens (the mix's shortest: what a previous
occupant's state would reach furthest into), 700 (one chunk, a padded
bucket), 3,000 (three chunks, a padded last bucket) and 12,000 (a document:
twelve chunks), each decoded ``NEW`` tokens through the cache.

Each served sequence then goes through ``reference/kimi_linear.py`` in ONE
float32 forward (the recurrence one step a token, MLA decompressed), routed
as the program routed (``routing=``: bf16 flips a few per cent of top-8 sets
between near-tied experts, which is not an arithmetic error), and the worst
|program - reference| over the logits of every generated position is reported
in bf16 steps at that position's best logit, with the root mean square of
the same differences.

That the tolerances are tight enough is shown in the same run by wrong
references the program must FAIL against, by at least one of the two limits
(the difference is symmetric: a right program against a wrong reference
differs as a wrong program against the right one): :data:`WRONG`.  Among
them the precision control, ``bf16_state``: the reference with its recurrent
state rounded to bf16 after every token, which is the nearest precision
below the float32 the configuration states for it.
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
# rows, convolution tails AND the residual stream in bf16, the recurrent
# state in float32, and accumulates in float32; the reference is float32
# throughout.  Both are read as the LARGEST of the four requests' own
# readings, and set between the chip's readings on three seeds (7, 8, 9; my
# chip runs, PR 44; PERF.md section 6).  The program: worst 2.41, 2.22, 2.26;
# root mean square 0.3375, 0.3445, 0.3490.  The nearest wrong references:
# in the worst difference ``N_kv`` dropped (5.42, 5.99, 5.46) and a state
# not zeroed under the 128-token prompt (20.3, 17.6, 18.6); in the root mean
# square the bias used as a weight (0.3715, 0.3764, 0.3819) and the state in
# bf16 (0.3752, 0.3787, 0.3874), which fail that limit only, as do ``k_r``
# left out of the scores and RoPE on the 64 (0.405-0.425, on the shortest
# prompt).  The root mean square's room is 3% on either side (0.3490 | 0.360
# | 0.3715): a seed's own noise moves the sound reading and its controls
# together (each control reads 1.09-1.11 x its seed's sound reading), so a
# fresh seed that reads 0.36 sound would be a finding, not a fault.
TOLERANCE_STEPS = 3.2
RMS_TOLERANCE_STEPS = 0.360

# what each breaks: the decay (alpha = 1), beta = 1, the short convolution,
# l2norm of q and k, k_r left out of the MLA scores, N_kv, RoPE wrongly on
# the 64, the output gate, route_scale, the bias used as a weight, a slot's
# state not zeroed at admission, pad rows of a bucket allowed to move the
# state, the state kept in bf16
WRONG = ("no_decay", "beta_one", "no_conv", "no_l2norm", "no_k_rot",
         "no_kv_norm", "rope_on_rot", "no_out_gate", "no_route_scale",
         "bias_weighs", "stale_state", "pad_rows", "bf16_state")

PROMPTS = (128, 700, 3000, 12000)
NEW = 256
# the wrong routers the driver's own check is read against as well (the
# reference left to route by itself, its near-ties admitted)
FREE_RUNNING = ("no_route_scale", "bias_weighs")


def pad_rows_of(serve, prompt_len: int):
    """(first pad position, pad rows) of the prompt's last chunk's bucket."""
    from deepspeed_tpu.inference.engine import pow2_bucket

    off = (prompt_len - 1) // serve.prefill_chunk * serve.prefill_chunk
    c = prompt_len - off
    return prompt_len, pow2_bucket(c, lo=8, cap=serve.cache_len - off) - c


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
        pads = [pad_rows_of(serve, len(p)) for p in prompts]
        serve.close()
    del serve, built, taps.events[:], taps.chunks[:]
    gc.collect()              # pool and state leave the chip

    ref = bench.reference(cell["config"])
    # one padded length, so one compiled reference a variant (a length of
    # its own a request compiled every piece four times over: a call of an
    # hour, my chip run, PR 44)
    pad = max(len(p) + n for p, (_, n) in zip(prompts, shapes))
    pad = -(-pad // ref.QUERY_BLOCK) * ref.QUERY_BLOCK
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

    for rec, p, pads_p in zip(served, prompts, pads):
        out = np.asarray(rec["tokens"], np.int32)
        n = len(p) + len(out)
        seq = np.zeros(pad, np.int32)
        seq[:n] = np.concatenate([p, out])
        rows = list(range(len(p) - 1, n - 1))
        one = {"prompt": len(p)}
        for name in names:
            kw = {"variant": (name,)}
            if name == "exact":
                kw = {}
            elif name == "stale_state":
                # the state a twin of this request left in the slot at its
                # end, not zeroed when this one took it
                kw = {"stale_rows": n - 1}
            elif name == "pad_rows":
                kw = {"pad_rows": pads_p}
            want = np.asarray(ref.logits_rows(
                params, config, seq, rows, devices[0],
                routing=rec["routing"], **kw))
            step = bf16_step(want.max(-1))
            steps = np.abs(rec["logits"] - want) / step[:, None]
            one[name] = (float(steps.max()),
                         float((steps ** 2).mean() ** 0.5))
            # the LARGEST of the requests' own readings: a fault that shows
            # on one kind of request (a stale state under a short prompt,
            # the 64 shared key values over a short context) is not thinned
            # by the others' positions
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
    caught = {k: worst[k] > TOLERANCE_STEPS or rms[k] > RMS_TOLERANCE_STEPS
              for k in wrong}
    return {
        "workload": workload, "seed": seed,
        "device": jax.devices()[0].device_kind,
        "requests": [{"prompt": len(p), "new": len(r["tokens"]),
                      "pad_rows": int(pd[1])}
                     for p, r, pd in zip(prompts, served, pads)],
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
                    default="kimi-linear-L5-ep8.serve-reason-doc-tail")
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
