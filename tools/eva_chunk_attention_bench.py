"""Time the EVA prefill-chunk attention kernel alone on the chip.

``eva_chunk_attention`` (bf16) at the ``evabyte-L6.serve-doc`` cell's shape
(``q [1, 32, s, 128]`` over a view of ``window`` + summary rows) for each
``--starts`` entry (the chunk's first absolute position) and each
``--chunks`` entry ``s``, profiled for ``--reps`` calls: the kernel's device
time is read from the trace by name, as the benchmark's reader does, and the
dense form (``eva.cached_attention``, what the chunk programs ran before) is
timed beside it as the whole of its jitted program.  One row of JSON per
case, appended to ``chiprun_out/eva_chunk_attention_bench.jsonl``, with the
schedule's visited and kept scores, the kernel's share of the MXU peak on
the scores it KEEPS (4 x head dim x kept x heads operations), and its worst
difference from the dense form in units of the dense form's largest
magnitude.

    python3 tools/eva_chunk_attention_bench.py [--starts 0 1024 2048 5120]
        [--chunks 128 1024] [--set _STRIP=128]

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

KERNEL, DENSE = "eva_chunk_attention", "jit_dense"
PEAK_FLOPS = 197e12          # one v5e, bf16 (benchmarks/lib/peaks.py)


def device_ms(trace_dir: str) -> dict:
    """{name: [events, ms an event]} on chip 0 for the kernel (an ``XLA
    Ops`` event named by its instruction) and the dense program (an ``XLA
    Modules`` event)."""
    import jax

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    out = {KERNEL: [0, 0.0], DENSE: [0, 0.0]}
    for plane in data.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            want = {"XLA Ops": KERNEL, "XLA Modules": DENSE}.get(line.name)
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
    ap.add_argument("--starts", nargs="+", type=int,
                    default=[0, 1024, 2048, 5120])
    ap.add_argument("--chunks", nargs="+", type=int, default=[128, 1024])
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--window", type=int, default=2048)
    ap.add_argument("--eva-chunk", type=int, default=16)
    ap.add_argument("--max-tokens", type=int, default=15360)
    ap.add_argument("--set", nargs="*", default=[])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import eva

    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print(f"eva_chunk_attention_bench: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    for item in args.set:
        name, value = item.split("=")
        setattr(fa, name, int(value))

    W, C, H, D = args.window, args.eva_chunk, args.heads, args.head_dim
    rows = W + eva.summary_rows(args.max_tokens, W, C)
    kw = dict(window=W, chunk=C)
    kernel = jax.jit(lambda q, k, v, start: fa.eva_chunk_attention(
        q, k, v, start, impl="pallas" if on_chip else "interpret", **kw))

    def dense(q, k, v, start):
        return eva.cached_attention(q, k, v, start + jnp.arange(q.shape[2]),
                                    scale=D ** -0.5, **kw)

    dense = jax.jit(dense)
    out_dir = os.path.join(repo, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    for s in args.chunks:
        keys = jax.random.split(jax.random.PRNGKey(args.seed + s), 3)
        q = jax.random.normal(keys[0], (1, H, s, D), jnp.bfloat16)
        k, v = (jax.random.normal(kk, (1, H, rows, D), jnp.bfloat16)
                for kk in keys[1:])
        for start in args.starts:
            at = jnp.int32(start)
            sch = fa.eva_chunk_schedule(start, s, rows=rows, head_dim=D,
                                        impl="pallas", **kw)
            row = {"label": args.label, "q": [1, H, s, D], "rows": rows,
                   "start": start, "set": args.set,
                   "device": dev.device_kind, "schedule": sch}
            try:
                got, want = kernel(q, k, v, at), dense(q, k, v, at)
                f32 = lambda a: a.astype(jnp.float32)
                row["worst"] = float(jnp.max(jnp.abs(f32(got) - f32(want)))
                                     / jnp.max(jnp.abs(f32(want))))
                with tempfile.TemporaryDirectory() as td:
                    with jax.profiler.trace(td):
                        for _ in range(args.reps):
                            r = kernel(q, k, v, at), dense(q, k, v, at)
                        jax.block_until_ready(r)
                    ms = device_ms(td) if on_chip else {}
                if ms:
                    row["calls"] = [ms[KERNEL][0], ms[DENSE][0]]
                    row["kernel_ms"], row["dense_ms"] = \
                        ms[KERNEL][1], ms[DENSE][1]
                    if row["kernel_ms"]:
                        row["kept_mxu_share"] = 100.0 * (
                            4.0 * D * sch["kept"] * H
                            / (row["kernel_ms"] / 1e3) / PEAK_FLOPS)
            except Exception as e:  # a variant the compiler refuses: say so
                row["error"] = str(e)[:600]
            print(json.dumps(row), flush=True)
            with open(os.path.join(out_dir, "eva_chunk_attention_bench.jsonl"),
                      "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
