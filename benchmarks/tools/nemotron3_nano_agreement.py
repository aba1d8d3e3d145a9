#!/usr/bin/env python3
"""Agreement of the served Nemotron-3-Nano cell with its plain reference at
the published widths, on the chip, outside any timed window (the
``model-configs`` guide, section 3, item 3).  Not part of a run; its numbers
go into PERF.md.

    python3 benchmarks/tools/nemotron3_nano_agreement.py \\
        [--workload nemotron3-nano-L9-ep2.serve-reason-4k] [--seed 7 ...]

Four requests are served by the cell's own engine (``init_serving`` with
the cell's settings: prefill through the chunk programs, the chunked scan
carrying state and convolution tail from chunk to chunk beside the attention
layer's K/V pages, decode through ``ssm_decode_step`` and the paged attention
kernel on the fused path, all in flight together), with
``lib/serve_taps.py`` copying out the program's logits and its routers'
choices at every generated position: prompts of 64 tokens (the mix's
shortest), 700 (one chunk, a padded bucket), 1,500 (two chunks of UNEQUAL
size: 1,024 and 476 in a bucket of 512) and 4,000 (four chunks), each
decoded ``NEW`` tokens through the cache.

Each served sequence then goes through ``reference/nemotron3_nano.py`` in
ONE float32 forward (the recurrence one step a token, never the chunked
form; plain softmax), routed as the program routed (``routing=``: bf16 flips
a few per cent of top-6 sets between near-tied experts, which is not an
arithmetic error), and the worst |program - reference| over the logits of
every generated position is reported in bf16 steps at that position's best
logit, with the root mean square of the same differences.  Every request is
padded to ONE length, so each piece of the reference compiles once a
variant.  Each request's SLOT STATE is read out of the engine after the run
(a slot's state is never freed: it is as of the last token the program fed)
and held against the state the reference's recurrence has after the same
token, a mamba2 layer at a time.

That the tolerances are tight enough is shown in the same run by wrong
references the program must FAIL against, by at least one of the three
limits (the difference is symmetric: a right program against a wrong
reference differs as a wrong program against the right one): :data:`WRONG`.
Among them the precision control ``bf16_state``: the reference with its
state rounded to bf16 after every token, the nearest precision below the
float32 the configuration states for it, which the logits cannot tell from
the sound reference and the state can.
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

# Three limits, each the LARGEST of the four requests' own readings.  The
# program keeps weights, matmul inputs, K/V rows, convolution tails AND the
# residual stream in bf16, the state in float32, and accumulates in float32;
# the reference is float32 throughout.  Read on seeds 7 | 8 | 9 on the chip
# (call 9; my chip runs, PR 63, the fourth build: the selection bias seeded
# x 0.01; the third build's readings, bias x 0.05, seeds 7-10: PERF.md
# section 6).  The root mean square's limit was set anew between these
# readings; the other two stand as call 7 set them.
#
# LOGITS: the largest |program - reference| at any compared logit, in bf16
# steps at that position's best logit, and the root mean square of the same
# over every compared logit.  The program: worst 2.50 | 2.25 | 2.28, root
# mean square 0.2472 | 0.2471 | 0.2445.  The nearest wrong reference: RoPE
# wrongly on the attention layer (2.55 | 2.42 | 2.41 and 0.2622 | 0.2671 |
# 0.2677, on the shortest prompt: ONE layer of nine, its out-projection
# over sqrt(9); it fails the root mean square only, with 3% of room on
# either side: 0.2472 | 0.255 | 0.2622); the Mamba layers' other controls
# read 42-148 and 3.8-15.6, SiLU in the experts 31.1 and 3.49 (seed 7).
# The state in bf16 reads 2.40 | 2.25 | 2.34 and 0.2483 | 0.2485 | 0.2492:
# the program's own readings to 0-2%.  NO limit on the logits can hold it,
# which is why the third limit is there.
TOLERANCE_STEPS = 3.0
RMS_TOLERANCE_STEPS = 0.255
# THE STATE: |program's state - reference's state| over |reference's state|
# of one mamba2 layer of one request (all heads, the Frobenius norm; the
# largest over the layers), after the last token the program fed: the
# engine's own slot state, read out after the run, against the recurrence
# one step a token.  The program: 0.0037 | 0.0052 | 0.0038, the same at
# every length (the inputs' bf16 rounding, which a float32 sum over a head's
# memory averages down).  The state rounded to bf16 after every token:
# 0.0703 | 0.0305 | 0.0854, and by the request (seed 7) 0.0107 after 320
# tokens, 0.0294 after 956, 0.0497 after 1,756, 0.0703 after 4,256 (a head
# whose decay is 0.999 a step carries the roundings of its last thousand
# steps).  The limit lies a factor of 1.9 over the largest sound reading and
# 3 under the smallest of the control.
STATE_TOLERANCE = 0.01
# the controls whose state is read as well (the others' states are the sound
# reference's or differ grossly in the logits)
STATE_WRONG = ("bf16_state",)

# what each breaks: the state kept in bf16, the gate applied AFTER the
# grouped norm, the skip D x, RoPE wrongly on the attention layer, SiLU in
# the experts' place of relu^2, the convolution's bias, dt_bias.  NOT among
# them since the selection bias is seeded x 0.01: the router's bias used as
# a weight (``--wrong ... bias_weighs`` reads it).  Under a bias x 0.05 and
# this model's route scale of 2.5 it read 3.74 and 0.3634 (seed 7, the
# third build); under x 0.01 it moves a kept score by a hundredth and reads
# 2.44 and 0.2532 beside the program's 2.50 and 0.2472 (call 9), so no
# limit on the chip can tell it, as in Solar's cell; the float32 CPU tests
# do (``benchmarks/tests/test_nemotron3_nano_cell.py``)
WRONG = ("bf16_state", "gate_after_norm", "no_skip", "rope_on_attn",
         "silu_experts", "no_conv_bias", "no_dt_bias")

PROMPTS = (64, 700, 1500, 4000)
NEW = 256
# the wrong form the driver's own check is read against as well (the
# reference left to route by itself, its near-ties admitted: the search runs
# under a control that breaks the router's weights alone)
FREE_RUNNING = ("bias_weighs",)


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
    from deepspeed_tpu.models import ssm_moe
    from deepspeed_tpu.ops.pallas.decode import ssm_state_unpack

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
        reqs = []              # ``serve_and_read`` keeps its requests

        def submit(*a, **kw):
            reqs.append(type(serve).submit(serve, *a, **kw))
            return reqs[-1]

        serve.submit = submit
        served = serve_and_read(taps, serve, prompts, [n for _, n in shapes])
        # off again: through it the engine would outlive ``del serve`` and
        # its cache stay on the chip under the reference
        del serve.submit, submit
        # a slot's state is never freed: each request's is still as of the
        # last token the program fed (its last output but one), [mamba2
        # layers, H, P, N] as the recurrence writes it
        pk = ssm_moe.ssm_sizes(serve.kind.cfg)[-1]
        kept = [np.asarray(ssm_state_unpack(serve._cache["state"][:, r.slot],
                                            pk)) for r in reqs]
        serve.pool.check_no_leak()
        serve.close()
    del serve, built, reqs, taps.events[:], taps.chunks[:]
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
    state = {k: 0.0 for k in ("exact",) + STATE_WRONG if k in names}
    by_request = []
    positions = flips = choices = 0
    free_runs = [("free_running", {})] + [
        ("free_running_" + v, {"variant": (v,)})
        for v in FREE_RUNNING if v in wrong]
    below = dict.fromkeys(["given_routing"] + [k for k, _ in free_runs], 0.0)

    def steps_below(logits, out):
        return float(((logits.max(-1) - logits[np.arange(len(out)), out])
                      / bf16_step(logits.max(-1))).max())

    for rec, p, S_kept in zip(served, prompts, kept):
        out = np.asarray(rec["tokens"], np.int32)
        n = len(p) + len(out)
        seq = np.zeros(pad, np.int32)
        seq[:n] = np.concatenate([p, out])
        rows = list(range(len(p) - 1, n - 1))
        rows += rows[-1:] * (n_rows - len(out))
        one = {"prompt": len(p)}
        for name in names:
            kw = {} if name == "exact" else {"variant": (name,)}
            S_ref = {} if name in state else None
            want = np.asarray(ref.logits_rows(
                params, config, seq, rows, devices[0],
                routing=rec["routing"], states=S_ref, **kw))[:len(out)]
            step = bf16_step(want.max(-1))
            steps = np.abs(rec["logits"] - want) / step[:, None]
            one[name] = (float(steps.max()),
                         float((steps ** 2).mean() ** 0.5))
            # the LARGEST of the requests' own readings: a fault that shows
            # on one kind of request is not thinned by the others' positions
            worst[name] = max(worst[name], one[name][0])
            rms[name] = max(rms[name], one[name][1])
            if name in state:
                # the reference's states are as of the last of ``rows``,
                # the last token the program fed; the LARGEST over the
                # mamba2 layers of a layer's |difference| over its |state|
                far = max(float(np.linalg.norm(got - np.asarray(S_ref[l]))
                                / np.linalg.norm(np.asarray(S_ref[l])))
                          for got, l in zip(S_kept, sorted(S_ref)))
                one[name] += (far,)
                state[name] = max(state[name], far)
            print(f"seed {seed} prompt {len(p)} {name}: {one[name]}",
                  file=sys.stderr, flush=True)
            if name == "exact":
                below["given_routing"] = max(below["given_routing"],
                                             steps_below(want, out))
        by_request.append(one)
        # what the driver's ``verify`` measures: the served tokens against
        # the reference left to route by itself (its near-ties admitted);
        # and the same against a router whose bias weighs
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
              or state.get(k, 0.0) > STATE_TOLERANCE for k in wrong}
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
        "state_difference": state["exact"],
        "state_difference_against_wrong_reference":
            {k: state[k] for k in wrong if k in state},
        "worst_rms_and_state_by_request": by_request,
        "caught": caught,
        "tolerance_steps": TOLERANCE_STEPS,
        "rms_tolerance_steps": RMS_TOLERANCE_STEPS,
        "state_tolerance": STATE_TOLERANCE,
        "ok": worst["exact"] <= TOLERANCE_STEPS
        and rms["exact"] <= RMS_TOLERANCE_STEPS
        and state["exact"] <= STATE_TOLERANCE and all(caught.values())}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    default="nemotron3-nano-L9-ep2.serve-reason-4k")
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
