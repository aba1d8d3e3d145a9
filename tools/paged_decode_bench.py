"""Time the paged decode kernels alone on the chip, at one shape.

``flash_decode`` over the paged pool and ``paged_kv_append``, each called
once per layer of a stacked pool as a decode step calls them, for a list of
position mixes.  The default shape is the benchmark's
``mistral-7b-L8.serve-chat`` cell (64 slots, 32 heads over 8 KV heads of
128, pages of 256, 4 pages a row, 8 layers x 129 pages, bf16); the
``ouro-2.6b-L12.serve-reason-768`` cell's is ``--heads 16 --kv-heads 16
--head-dim 128 --slots 16 --page 256 --maxp 5 --layers 48 --mixes
reason8,1023``.  One row of JSON per mix, appended to
``chiprun_out/paged_decode_bench.jsonl``.

    python3 tools/paged_decode_bench.py [--tree <checkout>] [--label parent]
        [--heads 32 --kv-heads 8 --head-dim 128 --slots 64 --page 256
         --maxp 4 --layers 8] [--mixes 0,255,300,1023,cell,cell16,reason8]
        [--window 2048 --chunk 16]

``--window`` times ``eva_decode_paged`` instead (EVA: a row's ``window /
page`` window pages, then its summary pages; no append is timed, the rows'
K/V land by the window's rule).  The ``evabyte-L6.serve-doc`` cell's shape
is ``--heads 32 --kv-heads 32 --head-dim 128 --slots 32 --page 256 --maxp 12
--layers 6 --window 2048 --chunk 16 --mixes whole,doc5,doc12``: ``whole`` has
every row at ``4 W + W / 2 - 1``, where both segments end on a page boundary
(4 window pages and 2 summary pages, nothing fetched that is not attended:
the time of a call over its pages is the kernel's whole-page rate);
``doc<n>`` has ``n`` live rows where ``bytes-doc-8k`` leaves them (a prompt
lognormal median 6,144, 2,560-14,336, an answer median 512, 128-1,024, met
as ``reason<n>`` meets its requests).  Such a row carries what the call
attended (``window_rows``, ``summary_rows``), the pages that hold it
(``live_pages``), the rows the page walk's rule fetches for it
(``fetched_rows_in_pieces``: every page before a segment's last whole, the
last in 64-row pieces) and the grid steps a page-a-step schedule spends on
table columns the row does not attend (``dead_steps``).

``--tree`` imports ``deepspeed_tpu`` from another checkout (the parent
commit, unpacked beside this one), so one call times both on one chip.  A
mix is a position shared by every row (``1023``; ``1023x8``: by 8 live rows,
the others parked), or ``cell``: 33 rows at 150-500 and
31 parked (position 0 on the junk page 0), the cell's mean occupancy when
PR 26 took it; ``cell<n>`` has ``n`` such rows; ``reason<n>`` has ``n`` live
rows where the ``reason-768`` mix leaves them (a prompt lognormal median 128,
16-512, an answer median 384, 64-768, the request met at a random moment of
its decode: answers weighted by their length).  Such a mix hands the
kernel its live mask where the tree's kernel takes one (its grid then
visits the live rows only, ISSUE 39; ``masked`` in the row says so).  The
time of a call is the host clock around ``--reps`` programs of ``layers``
x ``--rounds`` kernel calls each, ending in ``block_until_ready``.  TPU only.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=None)
    ap.add_argument("--label", default="change")
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--page", type=int, default=256)
    ap.add_argument("--maxp", type=int, default=4)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--mixes", default="0,255,300,1023,cell")
    ap.add_argument("--window", type=int, default=0,
                    help="EVA: time eva_decode_paged at this window")
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=4,
                    help="passes over the layers inside one program")
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse the control flow (interpret mode)")
    args = ap.parse_args()

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.abspath(args.tree) if args.tree else repo)
    import jax
    import jax.numpy as jnp
    import numpy as np

    import inspect

    from deepspeed_tpu.ops.pallas.decode import (_kv_heads_per_step,
                                                 eva_decode_paged,
                                                 flash_decode,
                                                 paged_kv_append)

    W, C = args.window, args.chunk
    kernel = eva_decode_paged if W else flash_decode
    masked = "live" in inspect.signature(kernel).parameters

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"paged_decode_bench: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    impl = "pallas" if dev.platform == "tpu" else "interpret"
    B, H, Hkv, Dh = args.slots, args.heads, args.kv_heads, args.head_dim
    page, maxp, L = args.page, args.maxp, args.layers
    P = B * maxp // 2 + 1             # the cell's pool: half the slots' reach
    hb = _kv_heads_per_step(Hkv, page, Dh, 2)     # KV heads of a grid step
    rng = np.random.RandomState(args.seed)
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 5)
    pool = (L, P, Hkv, page, Dh)
    kc = jax.random.normal(keys[0], pool, jnp.bfloat16)
    vc = jax.random.normal(keys[1], pool, jnp.bfloat16)
    q = jax.random.normal(keys[2], (B, H, Dh), jnp.bfloat16)
    kn = jax.random.normal(keys[3], (B, Hkv, Dh), jnp.bfloat16)
    vn = jax.random.normal(keys[4], (B, Hkv, Dh), jnp.bfloat16)

    def met_positions(n, prompt, answer):
        """Where ``n`` rows of a mix of lognormal (median, sigma, least,
        most) prompts and answers stand at a random moment: a request is
        met with a chance that grows with its answer's length, anywhere in
        it."""
        def cut(median, sigma, lo, hi):
            return np.clip(np.exp(np.log(median) + sigma
                                  * rng.standard_normal(64 * n)), lo, hi)
        prompt, out = cut(*prompt), cut(*answer)
        at = rng.choice(out.size, n, p=out / out.sum())
        return (prompt[at] + rng.uniform(0, 1, n) * out[at]).astype(np.int64)

    wp, per = (W // page, W // C) if W else (0, 0)
    # the deepest position a row's table reaches
    reach = ((maxp - wp) * page // per * W if W else maxp * page) - 1

    def segments(p):
        """(first table column, rows that count) of each run of pages the
        row at ``p`` attends."""
        if not W:
            return [(0, p + 1)]
        return [(0, p % W + 1), (wp, p // W * per)]

    def columns(p):
        return [c + j for c, n in segments(p) for j in range(-(-n // page))]

    def fetched_in_pieces(p):
        return sum((n - 1) // page * page + ((n - 1) % page // 64 + 1) * 64
                   for _, n in segments(p) if n)

    def mix(name):
        """(pos [B], page_table [B, maxp], live [B]) of one mix; live rows
        own distinct shuffled pages as far as the pool goes, parked rows sit
        on the junk page."""
        if name.startswith("cell"):
            n, at = name[4:] or B * 33 // 64, lambda: rng.randint(150, 501, B)
        elif name.startswith("reason"):
            n, at = name[6:], lambda: met_positions(
                B, (128, 0.8, 16, 512), (384, 0.5, 64, 768))
        elif name.startswith("doc"):
            n, at = name[3:], lambda: met_positions(
                B, (6144, 0.5, 2560, 14336), (512, 0.5, 128, 1024))
        elif name == "whole":
            n, at = B, lambda: 4 * W + W // 2 - 1
        else:
            shared, _, n = name.partition("x")
            n, at = n or B, lambda: int(shared)
        live = np.zeros(B, bool)
        live[rng.permutation(B)[:int(n)]] = True
        pos = np.where(live, np.minimum(at(), reach), 0)
        pt = np.zeros((B, maxp), np.int32)
        free = list(rng.permutation(P - 1) + 1)
        for b in np.flatnonzero(live):
            for j in columns(pos[b]):
                pt[b, j] = free.pop() if free else 1 + (b * maxp + j) % (P - 1)
        return jnp.asarray(pos, jnp.int32), jnp.asarray(pt), jnp.asarray(live)

    layers = list(range(L)) * args.rounds

    def attention(q, kc, vc, pos, pt, live, layer, impl):
        mask = {"live": live} if masked else {}
        if W:
            return eva_decode_paged(q, kc, vc, pos, pt, layer=layer, window=W,
                                    chunk=C, impl=impl, **mask)
        return flash_decode(q, kc, vc, pos, layer=layer, page_table=pt,
                            impl=impl, **mask)

    @jax.jit
    def attend(q, kc, vc, pos, pt, live):
        for l in layers:              # each call feeds the next: in order
            q = attention(q, kc, vc, pos, pt, live, l, impl)
        return q

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def append(kc, vc, kn, vn, pos, pt):
        for l in layers:
            kc, vc = paged_kv_append(kc, vc, kn, vn, pos, pt, layer=l,
                                     impl=impl)
        return kc, vc

    def worst_error(pos, pt, live):
        """Largest |kernel - jnp reference| of one layer's attention over
        the live rows, and whether the append wrote what the scatter writes
        off the junk page (parked rows race there)."""
        l = L - 1
        got = attention(q, kc, vc, pos, pt, live, l, impl)
        want = attention(q, kc, vc, pos, pt, live, l, "xla")
        err = jnp.max(jnp.abs(got.astype(jnp.float32)
                              - want.astype(jnp.float32))[live])
        if W:             # a window's rows land by another rule: not timed
            return float(err), None
        ka, _ = paged_kv_append(kc, vc, kn, vn, pos, pt, layer=l, impl=impl)
        kx, _ = paged_kv_append(kc, vc, kn, vn, pos, pt, layer=l, impl="xla")
        return float(err), bool(jnp.array_equal(ka[:, 1:], kx[:, 1:]))

    def timed(fn, reps):
        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps / len(layers) * 1e6

    out_dir = os.path.join(repo, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    shape = dict(slots=B, heads=H, kv_heads=Hkv, head_dim=Dh, page=page,
                 maxp=maxp, layers=L, pool_pages=P)
    if W:
        shape.update(window=W, chunk=C)
    name_us = f"{'eva' if W else 'flash'}_decode_paged_us_per_call"
    reps = args.reps if dev.platform == "tpu" else 1
    for name in args.mixes.split(","):
        pos, pt, live = mix(name)
        err, same = worst_error(pos, pt, live)
        attn_us = timed(lambda: attend(q, kc, vc, pos, pt, live), reps)

        def step():
            nonlocal kc, vc
            kc, vc = append(kc, vc, kn, vn, pos, pt)
            return kc
        app_us = None if W else timed(step, reps)
        at = [int(p) for p in np.asarray(pos)[np.asarray(live)]]
        pages = sum(len(columns(p)) for p in at)
        eva = {} if not W else {
            "window_rows": sum(segments(p)[0][1] for p in at),
            "summary_rows": sum(segments(p)[1][1] for p in at),
            "fetched_rows_in_pieces": sum(map(fetched_in_pieces, at)),
            "dead_steps": (len(at) * maxp - pages) * (Hkv // hb)}
        row = {"label": args.label, "device": dev.device_kind, "mix": name,
               "masked": masked and not bool(jnp.all(live)),
               "positions": sorted(at) if len(at) <= 16 else None,
               "live_rows": len(at), "live_pages": pages,
               "context_tokens": sum(p + 1 for p in at), **eva,
               name_us: attn_us, "paged_kv_append_us_per_call": app_us,
               "attention_max_abs_err": err, "append_matches_scatter": same,
               **shape}
        print(json.dumps(row), flush=True)
        with open(os.path.join(out_dir, "paged_decode_bench.jsonl"),
                  "a") as f:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
