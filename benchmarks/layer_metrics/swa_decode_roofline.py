"""Kernels: share of its roofline decode attention reached where layers are
of two kinds.  Least time for the traced calls of ``flash_decode_paged`` (one
call a layer a decode step: over a ring of ``sliding_window`` rows in a
sliding layer, over every position in a global one): K and V of the rows the
live queries ATTEND, ``window_costs.decode_attention_bytes`` over the chip's
bytes/s (the kernel is byte-bound, two FLOPs a byte).  The rows come from
the loop's own marks, as ``eva_decode_roofline`` takes them: a request that
shows ``seen`` tokens after an iteration sits at position ``prompt + seen -
1``, and the next block's steps attend from there.  None for a program
without the kernel and for a configuration whose layers are all alike."""

from benchmarks.lib.costs import least_seconds
from benchmarks.lib.window_costs import (attended_rows_span,
                                         decode_attention_bytes)

KERNEL = "flash_decode_paged"


def traced_rows(ctx):
    """(rows in one sliding layer, rows in one global layer) attended by the
    decode steps of the traced iterations, summed over requests."""
    lo, hi = ctx["trace_window"]
    mc = ctx["config"]["model_config"]
    win = full = 0
    for lv, a in zip(ctx["loop"]["records"], ctx["loop"]["schedule"]):
        if lv is None:
            continue
        prev = None
        for t, k in lv.marks:
            if prev is not None and lo <= t < hi:
                # tokens prev+1 .. k came from steps whose queries sat at
                # positions prompt + prev - 1 .. prompt + k - 2
                w, f = attended_rows_span(mc, len(a.prompt) + prev - 1,
                                          k - prev)
                win, full = win + w, full + f
            prev = k
    return win, full


def read(ctx):
    tr = ctx["trace"]
    mc = ctx["config"]["model_config"]
    if tr is None or KERNEL not in tr["kernels"] or not ctx["trace_window"] \
            or not mc.get("layer_types"):
        return None
    win, full = traced_rows(ctx)
    if not win + full:
        return None
    least, _ = least_seconds(0.0, decode_attention_bytes(mc, win, full),
                             ctx["peaks"])
    return 100.0 * least / tr["kernels"][KERNEL]["seconds"]
