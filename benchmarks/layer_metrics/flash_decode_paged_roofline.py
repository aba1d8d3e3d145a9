"""Kernels: share of its roofline the decode attention kernel reached.
Least time for the traced calls of ``flash_decode_paged``: each call (one
layer of one decode step) must read K and V of every context token of
every running row, ``costs.decode_attention_bytes`` over the chip's
bytes/s (the kernel is byte-bound: two FLOPs a byte).  Context tokens per
step come from the loop's records of the traced iterations."""

from benchmarks.lib.costs import decode_attention_bytes, least_seconds

KERNEL = "flash_decode_paged"


def read(ctx):
    tr, loop = ctx["trace"], ctx["loop"]
    if tr is None or KERNEL not in tr["kernels"] or not ctx["trace_window"]:
        return None
    lo, hi = ctx["trace_window"]
    k = int(ctx["cell"]["engine"]["decode_block_tokens"])
    mc = ctx["config"]["model_config"]
    # context tokens summed over the decode steps of the traced iterations
    ctx_steps = sum(it[4] * k for it in loop["iters"] if lo <= it[0] < hi)
    if not ctx_steps:
        return None
    nbytes = decode_attention_bytes(mc, ctx_steps) * mc["num_layers"]
    least, _ = least_seconds(0.0, nbytes, ctx["peaks"])
    return 100.0 * least / tr["kernels"][KERNEL]["seconds"]
