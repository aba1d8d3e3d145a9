"""Time the three training attention kernels alone on the chip.

``jax.grad`` of ``flash_attention`` (causal, bf16) at each ``--shapes`` entry
``B,H,S,D`` and each ``--blocks`` entry ``QxK`` (the upper limits handed to
``block_q`` / ``block_k``), profiled for ``--reps`` calls; the device time
of ``flash_attention_fwd`` / ``_bwd_dq`` / ``_bwd_dkv`` is read from the
trace by name, as the benchmark's reader does.  One row of JSON per case,
appended to ``chiprun_out/flash_attention_bench.jsonl``, with the worst
difference of the output and the three gradients from ``mha_reference`` on
the first batch row, in units of the reference's largest magnitude.

    python3 tools/flash_attention_bench.py [--tree <checkout>] [--label parent]
        [--shapes 16,25,1024,64 8,32,1024,128 2,32,4096,128]
        [--blocks 512x512] [--set _VMEM_BLOCK_BYTES=16777216]

``--tree`` imports ``deepspeed_tpu`` from another checkout (the parent
commit, unpacked under the gitignored ``smoke_tree/``), so one call times
both on one chip.  ``--set`` overrides a module constant of
``flash_attention.py`` for this run only (exploration; nothing reads it in
the program).  TPU only.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import sys
import tempfile

KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
           "flash_attention_bwd_dkv")


def kernel_ms(trace_dir: str) -> dict:
    """{kernel: [calls, ms a call]} on chip 0, from the xplane file."""
    import jax

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    out = {k: [0, 0.0] for k in KERNELS}
    for plane in data.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                # an event is named by its instruction's whole text; the
                # instruction's own name comes first
                short = ev.name.split(" = ", 1)[0]
                for k in KERNELS:
                    if k in short:
                        out[k][0] += 1
                        out[k][1] += ev.duration_ns / 1e6
    return {k: [n, (ms / n if n else None)] for k, (n, ms) in out.items()}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=None)
    ap.add_argument("--label", default="change")
    ap.add_argument("--shapes", nargs="+",
                    default=["16,25,1024,64", "8,32,1024,128", "2,32,4096,128"])
    ap.add_argument("--blocks", nargs="+", default=["512x512"])
    ap.add_argument("--set", nargs="*", default=[])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.abspath(args.tree) if args.tree else repo)
    import jax
    import jax.numpy as jnp

    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"flash_attention_bench: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    for item in args.set:
        name, value = item.split("=")
        setattr(fa, name, int(value))

    out_dir = os.path.join(repo, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    for shape in args.shapes:
        B, H, S, D = (int(x) for x in shape.split(","))
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        q, k, v, do = (jax.random.normal(kk, (B, H, S, D), jnp.bfloat16)
                       for kk in keys)
        for blocks in args.blocks:
            bq, bk = (int(x) for x in blocks.split("x"))

            def loss(q, k, v, do, impl="pallas"):
                o = fa.flash_attention(q, k, v, True, None, bq, bk, impl)
                return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32)), o

            grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))
            row = {"label": args.label, "shape": [B, H, S, D],
                   "blocks": blocks, "set": args.set,
                   "device": dev.device_kind}
            try:
                jax.block_until_ready(grad(q, k, v, do))
                with tempfile.TemporaryDirectory() as td:
                    with jax.profiler.trace(td):
                        for _ in range(args.reps):
                            r = grad(q, k, v, do)
                        jax.block_until_ready(r)
                    ms = kernel_ms(td)
                row.update({k.replace("flash_attention_", "") + "_ms": ms[k][1]
                            for k in KERNELS})
                row["calls"] = [ms[k][0] for k in KERNELS]
                row["sum_ms"] = sum(ms[k][1] or 0.0 for k in KERNELS)
                (gq, gk, gv), o = grad(q[:1], k[:1], v[:1], do[:1])
                # the same loss through the jnp reference (float32
                # probabilities)
                ref = jax.jit(jax.grad(
                    lambda *a: loss(*a, impl="xla"), argnums=(0, 1, 2),
                    has_aux=True))
                (rq, rk, rv), ro = ref(q[:1], k[:1], v[:1], do[:1])
                row["worst"] = [float(jnp.max(jnp.abs(
                    a.astype(jnp.float32) - b.astype(jnp.float32)))
                    / jnp.max(jnp.abs(b.astype(jnp.float32))))
                    for a, b in ((o, ro), (gq, rq), (gk, rk), (gv, rv))]
                if hasattr(fa, "tile_schedule"):
                    sch = fa.tile_schedule(S, S, bq, bk, True, head_dim=D,
                                           heads=B * H)
                    row["schedule"] = {k: sch[k] for k in (
                        "block_q", "block_k", "tiles", "visited", "masked",
                        "heads_per_step", "grid_steps")}
            except Exception as e:  # a variant the compiler refuses: say so
                row["error"] = str(e)[:600]
            print(json.dumps(row), flush=True)
            with open(os.path.join(out_dir, "flash_attention_bench.jsonl"),
                      "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
