"""Kernels: share of their roofline the training attention kernels reached
(``flash_attention_fwd`` + ``bwd_dq`` + ``bwd_dkv``, FLOP-bound at
sequence 1024).  Least time for the traced calls, from
``costs.flash_attention_flops`` at the cell's per-chip shapes, over their
traced time on chip 0."""

from benchmarks.lib.costs import (FLASH_MATMULS, flash_attention_flops,
                                  least_seconds)


def read(ctx):
    tr, loop = ctx["trace"], ctx["loop"]
    if tr is None:
        return None
    mc = ctx["config"]["model_config"]
    heads = mc["num_heads"]
    dh = mc.get("head_dim") or mc["hidden_size"] // heads
    rows = loop["rows"] // loop["chips"]
    least = spent = 0.0
    for name in FLASH_MATMULS:
        rec = tr["kernels"].get(name)
        if rec is None:
            continue
        flops = flash_attention_flops(name, rows, heads, loop["seq"], dh)
        least += rec["count"] * least_seconds(flops, 0.0, ctx["peaks"])[0]
        spent += rec["seconds"]
    return 100.0 * least / spent if spent else None
