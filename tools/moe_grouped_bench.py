#!/usr/bin/env python3
"""Time the dropless expert block's grouped matmuls on the chip: the three
projections of ``moe/sharded_moe.py:_moe_grouped`` over ``N*k`` sorted rows
with ``group_sizes [E]``, as ``jax.lax.ragged_dot`` (what the program runs)
and as the Pallas grouped matmul JAX ships
(``jax.experimental.pallas.ops.tpu.megablox.gmm``), beside the whole
``moe_mlp`` block and the bytes and FLOPs the shapes ask for.  The choice
between the two is made by this measurement (PERF.md, Findings, PR 27).

    python3 tools/moe_grouped_bench.py [--tokens 256,1024]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(fn, *args, calls=20):
    import jax

    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", default="256,1024")
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--stacked-layers", type=int, default=0)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from types import SimpleNamespace

    from deepspeed_tpu.moe.sharded_moe import moe_mlp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("tools/moe_grouped_bench.py needs a TPU", file=sys.stderr)
        return 1
    E, k, D, F = args.experts, args.top_k, args.hidden, args.width
    cfg = SimpleNamespace(num_experts=E, num_experts_per_tok=k,
                          activation="silu", glu=True, moe_drop_tokens=False,
                          moe_norm_topk_prob=False)
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    bf = jnp.bfloat16
    params = {"gate_w": jax.random.normal(keys[0], (D, E), bf) * D ** -0.5,
              "w_up": jax.random.normal(keys[1], (E, D, F), bf) * D ** -0.5,
              "w_gate": jax.random.normal(keys[2], (E, D, F), bf) * D ** -0.5,
              "w_down": jax.random.normal(keys[3], (E, F, D), bf) * F ** -0.5}
    try:
        from jax.experimental.pallas.ops.tpu.megablox import gmm
    except ImportError:
        gmm = None
    for N in (int(n) for n in args.tokens.split(",")):
        x = jax.random.normal(keys[4], (1, N, D), bf)
        rows = jax.random.normal(keys[5], (N * k, D), bf)
        # near-uniform routing, as a seeded router gives
        flat = jax.random.randint(keys[5], (N * k,), 0, E)
        sizes = jnp.bincount(flat, length=E).astype(jnp.int32)

        def three(dot):
            def f(rows, p, sizes):
                up = dot(rows, p["w_up"], sizes)
                h = jax.nn.silu(dot(rows, p["w_gate"], sizes)) * up
                return dot(h.astype(rows.dtype), p["w_down"], sizes)
            return jax.jit(f)

        row = {"tokens": N, "rows": N * k, "device": dev.device_kind,
               "weight_bytes_ms": 3 * E * D * F * 2 / 819e9 * 1e3,
               "routed_flops_ms": 2 * N * k * 3 * D * F / 197e12 * 1e3,
               "ragged_dot_ms": timed(three(jax.lax.ragged_dot), rows,
                                      params, sizes),
               "moe_mlp_block_ms": timed(
                   jax.jit(lambda p, x: moe_mlp(p, x, cfg)[0]), params, x)}
        if gmm is not None:
            try:
                row["megablox_gmm_ms"] = timed(
                    three(lambda a, w, s: gmm(a, w, s,
                                              preferred_element_type=bf)),
                    rows, params, sizes)
            except Exception as e:       # a tiling it refuses, at this shape
                row["megablox_gmm_error"] = f"{type(e).__name__}: {e}"[:300]
        if args.stacked_layers:
            # how a layer's experts are best taken out of the model's STACKED
            # [L, E, ...] arrays: a dynamic slice (a scan over layers), a
            # static one (an unrolled loop), or not at all (one ragged_dot
            # over all L*E groups, the other layers' groups empty)
            L = args.stacked_layers
            stack = {n: jnp.broadcast_to(params[n], (L,) + params[n].shape)
                     + jnp.zeros((L, 1, 1, 1), bf)
                     for n in ("w_up", "w_gate", "w_down")}
            layer = jnp.asarray(L - 3, jnp.int32)
            f3 = three(jax.lax.ragged_dot)
            row["stacked_dynamic_slice_ms"] = timed(jax.jit(
                lambda r, w, s, l: f3(r, jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, l, keepdims=False), w), s)),
                rows, stack, sizes, layer)
            row["stacked_static_slice_ms"] = timed(jax.jit(
                lambda r, w, s: f3(r, jax.tree.map(lambda a: a[L - 3], w),
                                   s)), rows, stack, sizes)
            row["stacked_all_groups_ms"] = timed(jax.jit(
                lambda r, w, s, l: f3(
                    r, jax.tree.map(
                        lambda a: a.reshape((-1,) + a.shape[2:]), w),
                    jax.lax.dynamic_update_slice(
                        jnp.zeros((L * E,), jnp.int32), s, (l * E,)))),
                rows, stack, sizes, layer)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
