"""Time the latent-attention prefill-chunk kernel alone on the chip.

``mla_chunk_attention`` (bf16) at A.X-K1's widths (``--heads 64``: ``q [s,
64, 192]`` over one layer of a slot's view, 16,384 cache rows of 640, ``W_kvb``
``[512, 64, 256]``; ``--heads 32 --max-tokens 13312`` is Kimi-Linear's latent
layer)
for each ``--blocks`` entry (the number of live 1,024-row key blocks: the
chunk starts at ``(blocks - 1) * 1024``) and each ``--chunks`` entry ``s``,
profiled for ``--reps`` calls: the kernel's device time is read from the
trace by name, as the benchmark's readers do, and ``afmoe.attend(expand=)``
(the key-block loop the chunk programs ran before, and the kernel's
reference) is timed beside it as the whole of its jitted program.  One row of
JSON per case, appended to ``chiprun_out/mla_chunk_attention_bench.jsonl``,
with ``mla_chunk_schedule``'s visited rows, both forms' share of the MXU
peak on the operations the chunk NEEDS (decompression of the ``start + s``
rows its queries see, ``2 x rows x kv x H x (n + v)``; scores and ``p v`` of
the pairs the mask keeps, ``2 x H x (s x start + s (s + 1) / 2) x (n + r +
v)``: 60 GFLOP a block under the chunk at 64 heads and ``s`` = 1,024), and
the kernel's worst difference from the loop in units of the loop's largest
magnitude.

    python3 tools/mla_chunk_attention_bench.py [--blocks 1 4 8 15]
        [--chunks 1024] [--heads 64] [--set _STRIP=128]

``--set`` overrides a module constant of ``flash_attention.py`` for this run
only (exploration; nothing reads it in the program).  ``--allow-cpu``
rehearses the control flow in interpret mode (tiny shapes only).  TPU only
otherwise.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import sys
import tempfile

KERNEL, LOOP = "mla_chunk_attention", "jit_loop"
PEAK_FLOPS = 197e12          # one v5e, bf16 (benchmarks/lib/peaks.py)
KEY_BLOCK = 1024             # afmoe.KEY_BLOCK: what a "live block" is


def device_ms(trace_dir: str) -> dict:
    """{name: [events, ms an event]} on chip 0 for the kernel (an ``XLA
    Ops`` event named by its instruction) and the loop's program (an ``XLA
    Modules`` event)."""
    import jax

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    out = {KERNEL: [0, 0.0], LOOP: [0, 0.0]}
    for plane in data.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            want = {"XLA Ops": KERNEL, "XLA Modules": LOOP}.get(line.name)
            if want is None:
                continue
            for ev in line.events:
                if want in ev.name.split(" = ", 1)[0]:
                    out[want][0] += 1
                    out[want][1] += ev.duration_ns / 1e6
    return {k: [n, (ms / n if n else None)] for k, (n, ms) in out.items()}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--label", default="change")
    ap.add_argument("--blocks", nargs="+", type=int, default=[1, 4, 8, 15])
    ap.add_argument("--chunks", nargs="+", type=int, default=[1024])
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--kv-rank", type=int, default=512)
    ap.add_argument("--nope", type=int, default=128)
    ap.add_argument("--rot", type=int, default=64)
    ap.add_argument("--v-dim", type=int, default=128)
    ap.add_argument("--max-tokens", type=int, default=16384)
    ap.add_argument("--set", nargs="*", default=[])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    import jax
    import jax.numpy as jnp

    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print(f"mla_chunk_attention_bench: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    for item in args.set:
        name, value = item.split("=")
        setattr(fa, name, int(value))

    H, kv, n, r, v = (args.heads, args.kv_rank, args.nope, args.rot,
                      args.v_dim)
    P, W = args.max_tokens, -(-(kv + r) // 128) * 128
    kw = dict(nope=n, scale=(n + r) ** -0.5)
    kernel = jax.jit(lambda q, rows, w, start: fa.mla_chunk_attention(
        q, rows, w, start, impl="pallas" if on_chip else "interpret", **kw))

    def loop(q, rows, w, start):
        return fa.mla_chunk_attention(q, rows, w, start, impl="xla", **kw)

    loop = jax.jit(loop)
    out_dir = os.path.join(repo, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    rows = jax.random.normal(keys[0], (1, P, W), jnp.bfloat16)
    w = (jax.random.normal(keys[1], (kv, H, n + v), jnp.float32)
         * kv ** -0.5).astype(jnp.bfloat16)
    for s in args.chunks:
        q = jax.random.normal(keys[2], (s, H, n + r), jnp.bfloat16)
        for blocks in args.blocks:
            start = (blocks - 1) * KEY_BLOCK
            if start + s > P:
                continue
            at = jnp.int32(start)
            sch = fa.mla_chunk_schedule(
                start, s, P, heads=H, nope=n, rot=r, v_dim=v, row_width=W,
                kv=kv, impl="pallas")
            kept = s * start + s * (s + 1) // 2
            flops = 2.0 * H * ((start + s) * kv * (n + v)
                               + kept * (n + r + v))
            row = {"label": args.label, "q": [s, H, n + r], "rows": [P, W],
                   "wkvb": [kv, H, n + v], "start": start, "blocks": blocks,
                   "set": args.set, "device": dev.device_kind,
                   "schedule": sch, "gflop": flops / 1e9}
            try:
                got, want = kernel(q, rows, w, at), loop(q, rows, w, at)
                f32 = lambda a: a.astype(jnp.float32)
                row["worst"] = float(jnp.max(jnp.abs(f32(got) - f32(want)))
                                     / jnp.max(jnp.abs(f32(want))))
                with tempfile.TemporaryDirectory() as td:
                    with jax.profiler.trace(td):
                        for _ in range(args.reps):
                            out = (kernel(q, rows, w, at),
                                   loop(q, rows, w, at))
                        jax.block_until_ready(out)
                    ms = device_ms(td) if on_chip else {}
                if ms:
                    row["calls"] = [ms[KERNEL][0], ms[LOOP][0]]
                    for name, key in ((KERNEL, "kernel"), (LOOP, "loop")):
                        if ms[name][1]:
                            row[key + "_us"] = ms[name][1] * 1e3
                            row[key + "_us_a_block"] = \
                                ms[name][1] * 1e3 / blocks
                            row[key + "_flop_bound_share"] = 100.0 * (
                                flops / (ms[name][1] / 1e3) / PEAK_FLOPS)
            except Exception as e:  # a variant the compiler refuses: say so
                row["error"] = str(e)[:600]
            print(json.dumps(row), flush=True)
            with open(os.path.join(out_dir, "mla_chunk_attention_bench.jsonl"),
                      "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
