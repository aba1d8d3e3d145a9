#!/usr/bin/env python3
"""Time ``fused_moe_mlp`` alone on the chip, at a benchmark cell's shapes.

The decode path's expert kernel (``ops/pallas/decode.py``) over the model's
STACKED ``[L, E, D, F]`` arrays with ``layer=`` as the models pass it, on the
cell's ``num_slots`` rows: the configuration and the engine are read from
``benchmarks/workloads/<--workload>.json`` and the ``benchmarks/configs`` file
it names, the weights are the model's own seeded init in bf16 (so the stored
width is the model's: a padded one where the model pads).  Two settings:

- back to back (the default): every expert layer's call in one jitted
  program, ``--rounds`` times over, each call's residual the call before's
  output, nothing between them;
- ``--in-layer``: the decode layer's other instructions between the calls.
  A model of ``fused_decode.decode_step``'s own layer loop (OLMoE) runs
  ``--steps`` whole decode steps in one scanned program, as the serve
  engine's decode block does, over a paged pool of the cell's size with
  about a third of the rows live; a ``layer_types`` model (``models/afmoe.py``
  and its forms) runs every expert layer from its attention's output on
  (``afmoe.fused_close``: projection, norms, shared expert, router, counts),
  or, where the layer is only its experts (``models/ssm_moe.py``), its norm
  and ``afmoe.fused_experts``.

A row of JSON a case (appended to ``--out``, by default
``chiprun_out/moe_decode_bench.jsonl``): the kernel's
device time a call read from the profiler's trace by the kernel's name, as
the benchmark's reader takes it (``kernel_us`` the mean, ``kernel_us_p50``),
the host clock around the program over its calls (``host_us_a_call``, back to
back only: PR 27's number), the block of FFN columns the call took
(``block_cols``, read from the grid the kernel asked for), the held experts'
bytes over 819 GB/s as a share of the kernel's time (``bytes_share``: what
``fused_moe_mlp_roofline`` reads), the MXU's time at 197 TF/s for every
row against every expert (``flop_us``: at 256 rows it passes the bytes') and
the program's ``--ops`` heaviest instructions, us a call of the kernel's
(``ops_us_a_call``: what the layer spends around the kernel).

    python3 tools/moe_decode_bench.py --workload olmoe-1b-7b-L8.serve-chat
        [--rows 8,64,128] [--block rule,256,512,1024] [--in-layer]
        [--width 1920] [--live-rows all,64] [--row-tile 128]
        [--tree <checkout>] [--label parent]

``--block`` forces a block width by patching the kernel module's budget
constants for this process (``_TILE_BYTES``, and the whole-expert share where
the tree has one; a block past the budget the module itself has gets the
VMEM limit ``_moe_step_bytes`` reckons, where the tree has that): no argument
of the kernel selects it.  ``rule`` is the module's own choice.  ``--width``
stores the routed experts that wide instead of the model's own stored width
(zero columns and rows past it, or the model's zero pad cut: 2,048 | 1,920
for Nemotron's 1,856).  ``--live-rows`` hands the kernel a ``live`` mask of
that many rows scattered over the call's rows (``all``: no mask, every row;
back to back only: the in-layer programs bring their own mask), and
``--row-tile`` patches the rows a MXU pass of the kernel's row cut works
(``_MOE_ROW_TILE``).  ``--tree`` imports ``deepspeed_tpu`` from another
checkout (the parent commit, unpacked beside this one), so one call times
both on one chip.  ``--allow-cpu`` rehearses the control flow in interpret
mode on a tiny ``--model-config`` (JSON of ``ModelConfig`` fields).  TPU only
otherwise.
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import re
import statistics
import sys
import tempfile
import time

KERNEL = "fused_moe_mlp"
PEAK_BYTES, PEAK_FLOPS = 819e9, 197e12      # one v5e (benchmarks/lib/peaks.py)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def kernel_events_us(trace_dir: str):
    """Device durations (us) of the kernel's events on chip 0, of the
    programs (``XLA Modules``) that held them, and every instruction's
    summed time by name."""
    import jax

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    kernel, programs, ops = [], [], {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    name = ev.name.split(" = ", 1)[0].lstrip("%")
                    kind = re.sub(r"[.\d]+$", "", name)   # call sites apart
                    ops[kind] = ops.get(kind, 0.0) + ev.duration_ns / 1e3
                    if KERNEL in name:
                        kernel.append(ev.duration_ns / 1e3)
            elif line.name == "XLA Modules":
                programs += [ev.duration_ns / 1e3 for ev in line.events
                             if "bench_program" in ev.name]
    return kernel, programs, ops


def read_cell(workload: str):
    """(model_config fields, engine settings) of a benchmark cell."""
    with open(os.path.join(REPO, "benchmarks", "workloads",
                           workload + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(REPO, "benchmarks", "configs",
                           cell["config"] + ".json")) as f:
        return json.load(f)["model_config"], cell["engine"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="olmoe-1b-7b-L8.serve-chat")
    ap.add_argument("--model-config", default=None,
                    help="JSON of ModelConfig fields instead of a cell's "
                         "(with --allow-cpu: tiny widths)")
    ap.add_argument("--rows", default="",
                    help="rows a call, comma separated (default: the "
                         "cell's num_slots)")
    ap.add_argument("--block", default="rule",
                    help="FFN columns a grid step, comma separated; 'rule' "
                         "is the module's own choice")
    ap.add_argument("--width", type=int, default=0,
                    help="stored width of the routed experts (default: the "
                         "model's own)")
    ap.add_argument("--live-rows", default="all",
                    help="rows of a call that decode, comma separated; "
                         "'all' passes no mask")
    ap.add_argument("--row-tile", type=int, default=0,
                    help="rows a pass of the kernel's row cut works "
                         "(default: the module's)")
    ap.add_argument("--ops", type=int, default=12,
                    help="instructions of the program a row lists by time")
    ap.add_argument("--in-layer", action="store_true")
    ap.add_argument("--steps", type=int, default=8,
                    help="--in-layer: decode steps (or passes over the "
                         "expert layers) inside one program")
    ap.add_argument("--rounds", type=int, default=4,
                    help="back to back: passes over the layers in a program")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--tree", default=None)
    ap.add_argument("--label", default="change")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "moe_decode_bench.jsonl"))
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.tree) if args.tree else REPO)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.comm.mesh import build_mesh
    from deepspeed_tpu.models import CausalLM, ModelConfig, afmoe
    from deepspeed_tpu.models import fused_decode
    from deepspeed_tpu.ops.pallas import decode

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print(f"moe_decode_bench: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    impl = "pallas" if on_chip else "interpret"
    if args.model_config:
        fields, engine = json.loads(args.model_config), {"num_slots": 8}
    else:
        fields, engine = read_cell(args.workload)
    model = CausalLM(ModelConfig(**fields),
                     build_mesh(devices=jax.devices()[:1]))
    cfg = model.config
    params = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), model.init(key)))(
            jax.random.PRNGKey(args.seed))
    stack = params["layers"]["mlp"]
    if args.width:
        def stored(w, axis):
            """``w`` cut, or padded with zeros, to ``--width`` along
            ``axis``."""
            w = jax.lax.slice_in_dim(w, 0, min(args.width, w.shape[axis]),
                                     axis=axis)
            pad = [(0, 0)] * w.ndim
            pad[axis] = (0, args.width - w.shape[axis])
            return jnp.pad(w, pad)
        stack.update({k: stored(stack[k], 2 if k == "w_down" else 3)
                      for k in ("w_up", "w_gate", "w_down") if k in stack})
    L, E, D, F = stack["w_up"].shape
    glu = "w_gate" in stack
    mats = 3 if glu else 2
    item = stack["w_up"].dtype.itemsize
    k = cfg.num_experts_per_tok

    # the grid the kernel asks for says which block it took
    seen = {}
    real_call = decode.pl.pallas_call

    def recording_call(kernel, *a, **kw):
        if kw.get("name") == KERNEL:
            seen["grid"] = tuple(kw.get("grid") or kw["grid_spec"].grid)
        return real_call(kernel, *a, **kw)
    decode.pl.pallas_call = recording_call

    constants = {n: getattr(decode, n)
                 for n in ("_TILE_BYTES", "_WHOLE_EXPERT_VMEM_SHARE",
                           "_TILE_OVER", "moe_expert_block")
                 if hasattr(decode, n)}
    row_tile = getattr(decode, "_MOE_ROW_TILE", None)
    if args.row_tile:
        decode._MOE_ROW_TILE = args.row_tile

    def force_block(block: str):
        """Patch the module's budgets so that its rule takes ``block``
        columns ('rule': leave them as they are)."""
        for n, v in constants.items():
            setattr(decode, n, v)
        if hasattr(decode, "_moe_mlp_live_rows"):
            # its own jit: a cached trace would keep the last case's grid
            decode._moe_mlp_live_rows.clear_cache()
        if block == "rule":
            return
        whole = int(block) >= F
        if "_WHOLE_EXPERT_VMEM_SHARE" in constants:
            decode._WHOLE_EXPERT_VMEM_SHARE = 1.0 if whole else 0.0
        if "_TILE_OVER" in constants:
            decode._TILE_OVER = 0.0
        over = int(block) * D * mats * item > decode._TILE_BYTES
        decode._TILE_BYTES = int(block) * D * mats * item
        if over and not whole and hasattr(decode, "_moe_step_bytes"):
            # past the module's own budget: the compiler's scoped limit
            # would refuse it, so the call sets what a step holds
            rule = decode.moe_expert_block

            def limited(rows, d, f, **kw):
                cols, limit = rule(rows, d, f, **kw)
                return cols, limit or -(-decode._moe_step_bytes(
                    rows, d, cols, kw.get("matrices", 3),
                    kw.get("itemsize", 2),
                    kw.get("row_itemsize", 2)) // 2**20) * 2**20
            decode.moe_expert_block = limited

    keys = jax.random.split(jax.random.PRNGKey(args.seed + 1), 6)

    def combine_of(B):
        """[B, E] float32: k experts a row at 1 / k, as a router gives."""
        _, idx = jax.lax.top_k(jax.random.uniform(keys[0], (B, E)),
                               min(k, E))
        return jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), 1) / k

    def alone(B, live):
        h = jax.random.normal(keys[1], (B, D), jnp.bfloat16)
        c = combine_of(B)
        mask = None
        if live != "all":
            mask = np.zeros(B, bool)
            mask[np.random.RandomState(args.seed).permutation(B)[
                :int(live)]] = True
            mask = jnp.asarray(mask)

        def bench_program(h, c, stack, mask):
            x = h
            for l in list(range(L)) * args.rounds:
                x = decode.fused_moe_mlp(
                    h, x, c, stack["w_up"], stack["w_down"],
                    stack.get("w_gate"), layer=l, act=cfg.activation,
                    impl=impl, **({} if mask is None else {"live": mask}))
            return x
        return (jax.jit(bench_program), (h, c, stack, mask), L * args.rounds)

    def in_layer_steps(B):
        """``--steps`` decode steps of ``fused_decode.decode_step`` in one
        scan, greedy, over a paged pool of the cell's size."""
        dparams = fused_decode.inject_decode_params(params, cfg)
        page = engine.get("kv_page_tokens", 16)
        pool = engine.get("kv_pool_tokens", 4 * page * B) // page + 1
        maxp = max(2, min(4, (pool - 1) // max(1, B // 2)))
        rng = np.random.RandomState(args.seed)
        live = np.zeros(B, bool)
        live[rng.permutation(B)[:max(1, B * 34 // 100)]] = True
        pos = np.where(live, rng.randint(min(150, page), min(
            500, maxp * page - args.steps - 1), B), 0)
        table = np.zeros((B, maxp), np.int32)
        free = list(rng.permutation(pool - 1) + 1)
        for b in np.flatnonzero(live):
            for j in range(maxp):
                table[b, j] = free.pop()
        shape = (cfg.num_layers, pool, cfg.num_kv_heads, page, cfg.head_dim)
        cache = {"k": jnp.zeros(shape, jnp.bfloat16),
                 "v": jnp.zeros(shape, jnp.bfloat16)}
        tok = jnp.ones((B,), jnp.int32)

        def bench_program(dparams, cache, tok, pos, table, live):
            def sub(carry, _):
                cache, tok, pos = carry
                logits, cache, _ = fused_decode.decode_step(
                    cfg, dparams, tok[:, None], cache, pos,
                    page_table=table, moe_live=live, impl=impl)
                tok = jnp.where(live, jnp.argmax(logits, -1).astype(
                    tok.dtype), tok)
                return (cache, tok, pos + live.astype(pos.dtype)), None
            (cache, tok, pos), _ = jax.lax.scan(
                sub, (cache, tok, pos), None, length=args.steps)
            return tok
        return (jax.jit(bench_program),
                (dparams, cache, tok, jnp.asarray(pos, jnp.int32),
                 jnp.asarray(table), jnp.asarray(live)),
                cfg.num_layers * args.steps)

    def in_layer_close(B):
        """Every expert layer from its attention's output on, ``--steps``
        times over, the stream carried from layer to layer."""
        dparams = afmoe.form(cfg).inject(cfg, params)
        layers = [(l, lp) for l, lp in enumerate(dparams["layers"])
                  if "gate_w" in lp]
        x = jax.random.normal(keys[2], (B, D), jnp.bfloat16)
        M = max([lp["wo"].shape[0] for _, lp in layers if "wo" in lp] or [D])
        ctx = jax.random.normal(keys[3], (B, M), jnp.bfloat16)
        live = jnp.arange(B) % 3 == 0

        def bench_program(dparams, x, ctx, live):
            stats = afmoe.moe_counts_zero(cfg)
            for _ in range(args.steps):
                for i, (l, _) in enumerate(layers):
                    lp = dparams["layers"][l]
                    if "wo" in lp:
                        x, stats = afmoe.fused_close(
                            cfg, dparams, lp, l, ctx[:, :lp["wo"].shape[0]],
                            x, stats, live, impl)
                    else:
                        h = afmoe.rms(x, lp["norm"], cfg.norm_eps)
                        x, stats = afmoe.fused_experts(
                            cfg, dparams, lp, i, h, x, stats, live, impl)
            return x, stats
        return (jax.jit(bench_program), (dparams, x, ctx, live),
                len(layers) * args.steps)

    expert_bytes = mats * E * D * F * item
    reps = args.reps if on_chip else 1

    def timed(B, live, row):
        """Build, warm and time one case into ``row``."""
        build = (functools.partial(alone, live=live) if not args.in_layer
                 else in_layer_close if cfg.is_afmoe else in_layer_steps)
        fn, operands, calls = build(B)
        jax.block_until_ready(fn(*operands))
        jax.block_until_ready(fn(*operands))
        grid = seen.get("grid", ())
        row["block_cols"] = F // grid[1] if len(grid) > 1 else F
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*operands)
        jax.block_until_ready(out)
        if not args.in_layer:
            row["host_us_a_call"] = ((time.perf_counter() - t0)
                                     / reps / calls * 1e6)
        with tempfile.TemporaryDirectory() as td:
            with jax.profiler.trace(td):
                for _ in range(reps):
                    out = fn(*operands)
                jax.block_until_ready(out)
            kernel, programs, ops = (kernel_events_us(td) if on_chip
                                     else ([], [], {}))
        if kernel:
            us = statistics.fmean(kernel)
            row.update(calls=len(kernel), kernel_us=us,
                       kernel_us_p50=statistics.median(kernel),
                       bytes_share=100.0 * row["expert_bytes_us"] / us)
        if programs:
            row["program_us_a_call"] = statistics.fmean(programs) / calls
            # the program's instructions by time, us a call of the kernel's
            row["ops_us_a_call"] = {
                name: round(us / len(programs) / calls, 2)
                for name, us in sorted(ops.items(), key=lambda kv: -kv[1])[
                    :args.ops]}

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    cases = [(B, block, live)
             for B in ([int(b) for b in args.rows.split(",") if b]
                       or [engine["num_slots"]])
             for block in args.block.split(",")
             for live in (["all"] if args.in_layer
                          else args.live_rows.split(","))]
    try:
        for B, block, live in cases:
            row = {"label": args.label, "device": dev.device_kind,
                   "workload": None if args.model_config else args.workload,
                   "mode": "in_layer" if args.in_layer else "alone",
                   "rows": B, "live_rows": live, "block": block,
                   "row_tile": getattr(decode, "_MOE_ROW_TILE", None),
                   "layers": L, "experts": E,
                   "hidden": D, "width": F, "matrices": mats,
                   "expert_bytes_us": expert_bytes / PEAK_BYTES * 1e6,
                   "flop_us": 2.0 * B * mats * D * F * E / PEAK_FLOPS * 1e6}
            force_block(block)
            seen.clear()
            try:
                timed(B, live, row)
            except Exception as e:   # a block the compiler refuses: say so
                row["error"] = f"{type(e).__name__}: {e}"[:600]
            print(json.dumps(row), flush=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    finally:
        decode.pl.pallas_call = real_call
        force_block("rule")
        if args.row_tile:
            decode._MOE_ROW_TILE = row_tile
    return 0


if __name__ == "__main__":
    sys.exit(main())
