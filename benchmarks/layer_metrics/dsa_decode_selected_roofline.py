"""Kernels: share of its roofline the decode steps' attention over the
SELECTED rows reached.  Least time for the traced calls of
``dsa_decode_selected``: every selected row of every live query read once a
full layer a step (576 values, 1,152 B) and attended by 128 heads in the
absorbed form (2 x 128 x (576 + 512) FLOPs): 242 FLOPs a byte, AT the v5e's
ridge of 240, so the larger of the two times bounds; the rows from the
cache kind's counter between the profiler's start and the window's end.
The gather of the rows out of the pool runs before the kernel and is not in
its time; the kernel reads the gathered copy, the same bytes."""

from benchmarks.lib.dsa_costs import ATTENDED, roofline_share, selected_pair


def read(ctx):
    return roofline_share(ctx, "dsa_decode_selected", ATTENDED,
                          selected_pair)
