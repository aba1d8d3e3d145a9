"""Kernels: share of its roofline the EVA decode attention kernel reached.
Least time for the traced calls of ``eva_decode_paged``: each call (one
layer of one decode step) must read K and V of every row its live queries
attend, window rows and summary rows (``eva_costs.decode_attention_bytes``
over the chip's bytes/s: the kernel is byte-bound, two FLOPs a byte).  The
rows come from the loop's own records: a request that shows ``seen`` tokens
after an iteration sits at position ``prompt + seen - 1``, and the next
block's steps attend from there (``iters[i][4]`` counts ``prompt + seen``
rows a request, which is not what EVA attends).  None for a program without
the kernel."""

from benchmarks.lib.costs import least_seconds
from benchmarks.lib.eva_costs import (attended_rows_span,
                                      decode_attention_bytes)

KERNEL = "eva_decode_paged"


def traced_rows(ctx):
    """Rows attended by the decode steps of the traced iterations, summed
    over requests: for each mark ``(t, k)`` of a request inside the trace
    window after its first, the ``k - k_prev`` steps that produced it."""
    lo, hi = ctx["trace_window"]
    mc = ctx["config"]["model_config"]
    rows = 0
    for lv, a in zip(ctx["loop"]["records"], ctx["loop"]["schedule"]):
        if lv is None:
            continue
        prev = None
        for t, k in lv.marks:
            if prev is not None and lo <= t < hi:
                # tokens prev+1 .. k came from steps whose queries sat at
                # positions prompt + prev - 1 .. prompt + k - 2
                win, summ = attended_rows_span(
                    mc, len(a.prompt) + prev - 1, k - prev)
                rows += win + summ
            prev = k
    return rows


def read(ctx):
    tr = ctx["trace"]
    if tr is None or KERNEL not in tr["kernels"] or not ctx["trace_window"]:
        return None
    mc = ctx["config"]["model_config"]
    rows = traced_rows(ctx)
    if not rows:
        return None
    nbytes = decode_attention_bytes(mc, rows) * mc["num_layers"]
    least, _ = least_seconds(0.0, nbytes, ctx["peaks"])
    return 100.0 * least / tr["kernels"][KERNEL]["seconds"]
