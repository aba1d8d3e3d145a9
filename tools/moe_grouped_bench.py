#!/usr/bin/env python3
"""Time the dropless expert block's grouped matmuls on the chip: the three
projections of ``moe/sharded_moe.py:_moe_grouped`` over ``N*k`` sorted rows
with ``group_sizes [E]``, as ``jax.lax.ragged_dot`` (what a gradient flows
through, and what a program runs off the chip) and as the repo's Pallas
grouped matmul (``ops/pallas/grouped_matmul.py``: weight blocks of the whole
contraction, the (row tile, group) pairs that hold rows; what the chunk
programs run over the stacked arrays since PR 64), beside the whole
``moe_mlp`` block and the bytes and FLOPs the shapes ask for.  The choice
between the two is made by the ``--held-share --stacked`` readings below, at
each cell's widths (PERF.md, Findings, PR 64; PR 27 had timed JAX's own
``megablox.gmm`` at its default (128, 128, 128) tiling, a k loop over 32 KB
weight blocks, 10.16 ms a layer against 2.78: that column is gone).

    python3 tools/moe_grouped_bench.py [--tokens 256,1024]

``--held-share 8 --stacked`` times the block as the held-share cells' chunk
programs run it (``models/afmoe.py:mlp``; PERF.md, Findings, PR 46): the
``--experts`` held here are one in ``--held-share`` of those the router
chooses among, the arrays are the model's stacked ``[L, E, ...]`` ones and
every expert layer's block runs inside ONE jitted function, one after the
other, as a chunk program's do.  A row gives, per expert layer: the three
grouped matmuls alone over the ``N*k`` sorted rows and over those and one
``sharded_moe.ROW_TILE`` of pad (``matmuls_all_rows_us``,
``matmuls_padded_rows_us``: a third of each is the ``us a call`` that decided
PR 46), the whole block over ``N*k`` rows (``block_one_call_us``: what the
parent ran) and as the program runs it, padded (``block_padded_us``), with
the worst difference between the two; ``--lhs-rows`` gives the matmuls alone
over other lengths of the sorted rows' prefix (``matmuls_<n>_rows_us``: the
chip's row-tile rule is read from these, 384 against 512).  ``--all-held``
routes every choice to a held expert, as a rank that received its peers' rows
would find them, and ``--held-share 1`` holds every expert.  These readings
ARE what the cells' programs pay, a traced window's ``ragged-dot`` time over
its calls says the same; the stand-alone readings above (one un-stacked call
a jit) read about twice the cell's per-call time and price nothing in a cell
(ROADMAP D8).

    python3 tools/moe_grouped_bench.py --held-share 8 --stacked \
        --experts 32 --top-k 8 --hidden 2304 --width 1024 --tokens 1024
    python3 tools/moe_grouped_bench.py --held-share 2 --stacked --two-matrix \
        --experts 64 --top-k 6 --hidden 2688 --width 1920 --tokens 1024

``--width`` is the STORED width of an expert (Nemotron's 1,856 published
columns: 1,920 since PR 67, ``--width 2048`` re-reads what they cost before).
"""

from __future__ import annotations

import argparse
import functools
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


def held_share(args) -> int:
    """``--held-share``: one JSON row a token count (module docstring)."""
    import jax
    import jax.numpy as jnp
    from types import SimpleNamespace

    from deepspeed_tpu.models.layers import activation_fn
    from deepspeed_tpu.moe import sharded_moe
    from deepspeed_tpu.ops.pallas import common
    from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul

    dev = jax.devices()[0]
    E, k, D, F = args.experts, args.top_k, args.hidden, args.width
    ER, L = E * args.held_share, args.stacked_layers or 4
    glu = not args.two_matrix
    cfg = SimpleNamespace(num_experts=E, num_experts_per_tok=k,
                          activation="silu" if glu else "relu2", glu=glu)
    act = activation_fn(cfg.activation)
    pallas = "pallas" if dev.platform == "tpu" else "interpret"
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    bf = jnp.bfloat16
    stack = {n: jax.random.normal(key, (L, E) + shape, bf) * shape[0] ** -0.5
             for n, key, shape in (("w_up", keys[0], (D, F)),
                                   ("w_gate", keys[1], (D, F)),
                                   ("w_down", keys[2], (F, D)))
             if glu or n != "w_gate"}
    layers = jnp.arange(L, dtype=jnp.int32)
    tile = sharded_moe.ROW_TILE

    def matmuls(kernel, rows, w, sizes, layers):
        """The projections of every expert layer over ``rows``, as the
        kernel or as ``ragged_dot`` over the stack's ``L * E`` groups."""
        flat = {n: a.reshape((L * E,) + a.shape[2:]) for n, a in w.items()}
        out = rows
        for l in range(L):
            s = jax.lax.dynamic_update_slice(
                jnp.zeros((L * E,), jnp.int32), sizes, (layers[l] * E,))
            if kernel:
                dot = lambda a, n: grouped_matmul(a, w[n], sizes,
                                                  layer=layers[l], impl=pallas)
            else:
                dot = lambda a, n: jax.lax.ragged_dot(a, flat[n], s)
            up = dot(out, "w_up")
            h = act(dot(out, "w_gate")) * up if glu else act(up)
            out = out + dot(h, "w_down")
        return out

    def blocks(row_tile, impl, *args):
        """Every expert layer's whole block, traced under ``row_tile`` with
        ``impl`` for the process's kernels (``_moe_grouped`` takes what
        ``common.default_impl`` gives: no argument reaches its choice)."""
        def f(x, w, weight, local, layers):
            for l in range(L):
                y, _ = sharded_moe._moe_grouped(
                    w, x, None, cfg, False, layer=layers[l],
                    assign=(weight, local))
                x = x + y
            return x
        sharded_moe.ROW_TILE, was = row_tile, common.default_impl
        common.default_impl = lambda: impl
        try:
            return jax.jit(f).lower(*args).compile()
        finally:
            sharded_moe.ROW_TILE, common.default_impl = tile, was

    for N in (int(n) for n in args.tokens.split(",")):
        x = jax.random.normal(keys[3], (N, D), bf)
        # k distinct experts a token, even over the router's: the first E
        # are held here
        _, idx = jax.lax.top_k(jax.random.uniform(
            keys[4], (N, E if args.all_held else ER)), k)
        weight = jnp.where(idx < E, 1.0 / k, 0.0)
        local = jnp.where(idx < E, idx, E).astype(jnp.int32)
        sizes = jnp.bincount(local.reshape(-1), length=E).astype(jnp.int32)
        rows = jax.random.normal(keys[5], (N * k, D), bf)
        held = int(sizes.sum())
        per_layer_us = lambda fn, *a: timed(fn, *a) / L * 1e3
        mm = jax.jit(functools.partial(matmuls, False))
        mk = jax.jit(functools.partial(matmuls, True))
        args_ = (x, stack, weight, local, layers)
        # a tile no row count is a multiple of: the form before PR 46
        one_call = blocks(1 << 30, "xla", *args_)
        padded = blocks(tile, "xla", *args_)
        kernel = blocks(tile, pallas, *args_)
        pad_rows = jnp.pad(rows, ((0, tile), (0, 0)))
        n_mats = 3 if glu else 2
        diff = lambda a, b: float(jnp.abs(
            a(*args_).astype(jnp.float32) - b(*args_)).max())
        row = {"tokens": N, "rows": N * k, "held_rows": held, "tile": tile,
               "groups_hit": int((sizes > 0).sum()),
               "layers": L, "matrices": n_mats, "device": dev.device_kind,
               "weight_bytes_us": n_mats * E * D * F * 2 / 819e9 * 1e6,
               "held_flops_us": 2 * held * n_mats * D * F / 197e12 * 1e6,
               "matmuls_all_rows_us": per_layer_us(mm, rows, stack, sizes,
                                                   layers),
               "matmuls_padded_rows_us": per_layer_us(
                   mm, pad_rows, stack, sizes, layers),
               "matmuls_kernel_us": per_layer_us(mk, rows, stack, sizes,
                                                 layers),
               "matmuls_kernel_padded_us": per_layer_us(
                   mk, pad_rows, stack, sizes, layers),
               "block_one_call_us": per_layer_us(one_call, *args_),
               "block_padded_us": per_layer_us(padded, *args_),
               "block_kernel_us": per_layer_us(kernel, *args_),
               "padded_max_abs_diff": diff(one_call, padded),
               "kernel_max_abs_diff": diff(kernel, padded)}
        for n in (int(n) for n in args.lhs_rows.split(",") if n):
            if held <= n <= N * k:      # the prefix holds every held row
                row[f"matmuls_{n}_rows_us"] = per_layer_us(
                    mm, rows[:n], stack, sizes, layers)
        print(json.dumps(row), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", default="256,1024")
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--stacked-layers", type=int, default=0)
    ap.add_argument("--held-share", type=int, default=0,
                    help="the --experts held here are one in this many of "
                         "the router's (with --stacked)")
    ap.add_argument("--stacked", action="store_true")
    ap.add_argument("--two-matrix", action="store_true",
                    help="--held-share: relu2 experts of an up and a down "
                         "projection, no gate (models/ssm_moe.py)")
    ap.add_argument("--lhs-rows", default="",
                    help="--held-share: other lengths of the sorted rows' "
                         "prefix to run the matmuls alone over")
    ap.add_argument("--all-held", action="store_true",
                    help="--held-share: the router chooses held experts "
                         "only (a rank that received its peers' rows)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse the control flow (tiny shapes only)")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from types import SimpleNamespace

    from deepspeed_tpu.moe.sharded_moe import moe_mlp
    from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print("tools/moe_grouped_bench.py needs a TPU", file=sys.stderr)
        return 1
    if args.held_share:
        if not args.stacked:
            ap.error("--held-share times the cells' form: add --stacked")
        return held_share(args)
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
        if dev.platform == "tpu":
            row["grouped_matmul_ms"] = timed(
                three(lambda a, w, s: grouped_matmul(a, w, s, impl="pallas")),
                rows, params, sizes)
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
