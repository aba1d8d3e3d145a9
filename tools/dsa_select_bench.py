"""Time an indexed latent layer's decode-step selection alone on the chip.

The two stages between ``dsa_index_scores_paged`` and the layer's output
(``models/kda_mla.py:fused_layers``): ``select_positions`` (the top-k of
every row's index scores) and ``dsa_decode_selected`` (page lookup, gather of
the selected latent rows, attention over them), at the
``dots3-note-L5-ep16.serve-doc-48k`` cell's shape (16 slots, 32,768 key
columns, the 2,048 best, 128 heads against rows of 640 values, pages of 256,
a pool of 2,048 pages in two layers, bf16), for a list of live-row counts
and, where the tree has them, a list of values of each loop's group size
(``kda_mla.SORT_GROUP``, ``decode.GATHER_GROUP``; 16 = one group = every
slot).  One row of JSON a case, appended to
``chiprun_out/dsa_select_bench.jsonl``.

    python3 tools/dsa_select_bench.py [--tree <checkout>] [--label parent]
        [--live 0,4,7,8,11,16] [--sort-groups 2,4,8,16]
        [--gather-groups 2,4,8,16]

``--tree`` imports ``deepspeed_tpu`` from another checkout (the parent
commit, unpacked beside this one), so one call times both on one chip; a
tree without the constants (its selection works every slot) is timed once a
live count.  The live rows are scattered over the slots, hold 2,048 to
31,744 positions (the cell's lengths), and the others' scores are
``NEG_INF`` as ``dsa_index_scores_paged`` leaves them.  The time of a stage
is the host clock around ``--reps`` programs of ``--steps`` calls each (a
scan whose carry feeds the next call), ending in ``block_until_ready``.
TPU only (``--allow-cpu`` rehearses the control flow at a tiny size).
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=None)
    ap.add_argument("--label", default="change")
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--keys", type=int, default=32768)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=128)
    ap.add_argument("--width", type=int, default=640)
    ap.add_argument("--page", type=int, default=256)
    ap.add_argument("--pool-pages", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--live", default="0,4,7,8,11,16")
    ap.add_argument("--sort-groups", default="2,4,8,16")
    ap.add_argument("--gather-groups", default="2,4,8,16")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.abspath(args.tree) if args.tree else repo)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import kda_mla
    from deepspeed_tpu.ops.pallas import decode

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"no TPU here ({dev.platform}): a time from it is no device "
              "number", file=sys.stderr)
        return 2
    impl = None if dev.platform == "tpu" else "interpret"
    B, S, K, H, W = args.slots, args.keys, args.topk, args.heads, args.width
    page, P, L = args.page, args.pool_pages, args.layers
    cols = S // page
    rng = np.random.default_rng(args.seed)
    grouped = hasattr(kda_mla, "SORT_GROUP")
    takes_live = "live" in inspect.signature(
        decode.dsa_decode_selected).parameters
    cache = jnp.asarray(rng.normal(size=(L, P, 1, page, W)), jnp.bfloat16)
    table = jnp.asarray(
        np.stack([rng.permutation(P - 1)[:cols] + 1 for _ in range(B)]),
        jnp.int32)
    base = rng.normal(size=(B, S)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(B, H, W)), jnp.bfloat16)

    def timed(fn, *xs):
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*xs))
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = fn(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / (args.reps * args.steps) * 1e6

    def case(n_live):
        live = np.zeros(B, bool)
        live[rng.permutation(B)[:n_live]] = True
        lo = min(K, S - 1)
        pos = rng.integers(lo, max(S - 1024, lo + 1), size=B)
        scores = np.where((np.arange(S)[None] <= pos[:, None])
                          & live[:, None], base, decode.NEG_INF)
        return (jnp.asarray(live), jnp.asarray(pos, jnp.int32),
                jnp.asarray(scores, jnp.float32))

    def sort_us(live, pos, scores):
        masked = jnp.where(live, pos, -1) if grouped else pos

        def program(scores):
            def step(c, _):
                sel, n = kda_mla.select_positions(scores + c, masked, K)
                return sel[0, 0].astype(jnp.float32) * 1e-30, (sel[:, 0], n)
            return jax.lax.scan(step, jnp.float32(0), None,
                                length=args.steps)
        return timed(program, scores)

    def gather_us(live, pos, scores):
        masked = jnp.where(live, pos, -1) if grouped else pos
        sel, n = jax.jit(kda_mla.select_positions, static_argnums=2)(
            scores, masked, K)
        kw = {"live": live} if takes_live else {}

        def program(q, cache, sel, n, table):
            def step(c, _):
                # ``c`` is 0, which the compiler cannot know: neither the
                # lookup nor the gather can leave the scan
                o = decode.dsa_decode_selected(
                    q, cache, sel + c, n, table, layer=L - 1, sm_scale=0.07,
                    impl=impl, **kw)
                return (o[0, 0, 0] != o[0, 0, 0]).astype(sel.dtype), \
                    o[:, 0, 0]
            return jax.lax.scan(step, jnp.zeros((), sel.dtype), None,
                                length=args.steps)
        return timed(program, q, cache, sel, n, table)

    ints = lambda text: [int(x) for x in text.split(",") if x]
    os.makedirs(os.path.join(repo, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(repo, "chiprun_out", "dsa_select_bench.jsonl"),
               "a")
    for n_live in ints(args.live):
        arrays = case(n_live)
        for stage, fn, module, name, groups in (
                ("sort", sort_us, kda_mla, "SORT_GROUP", args.sort_groups),
                ("gather", gather_us, decode, "GATHER_GROUP",
                 args.gather_groups)):
            for G in ints(groups) if grouped else [None]:
                if grouped:
                    setattr(module, name, G)
                row = {"label": args.label, "stage": stage, "group": G,
                       "live": n_live, "slots": B, "keys": S, "topk": K,
                       "us_a_call": round(fn(*arrays), 2),
                       "device": dev.device_kind}
                print(json.dumps(row), flush=True)
                out.write(json.dumps(row) + "\n")
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
