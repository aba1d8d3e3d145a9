"""Kernels: share of its roofline the decode steps' index scoring reached.
Least time for the traced calls of ``dsa_index_scores_paged``: every index
key of every live row's context read once a full layer a step (256 B) and
scored by 64 heads of 128 (16.4 kFLOP): 64 FLOPs a byte, under the v5e's
ridge of 240, so the byte time bounds; the keys from the cache kind's
counter between the profiler's start and the window's end."""

from benchmarks.lib.dsa_costs import SCORED, index_pair, roofline_share


def read(ctx):
    return roofline_share(ctx, "dsa_index_scores_paged", SCORED, index_pair)
