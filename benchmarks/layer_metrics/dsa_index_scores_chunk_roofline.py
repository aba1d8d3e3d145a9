"""Kernels: share of its roofline the chunk programs' index scoring reached.
Least time for the traced calls of ``dsa_index_scores_chunk``: every (real
token of a chunk, index key at or before it) pair scored by 64 heads of 128
(16.4 kFLOP); a key is read once for a block of queries, so the FLOP time
bounds; the pairs from the cache kind's counter between the profiler's start
and the window's end."""

from benchmarks.lib.dsa_costs import CHUNK_SCORED, index_pair, roofline_share


def read(ctx):
    # a key is read once for a block of queries: the FLOPs alone
    return roofline_share(ctx, "dsa_index_scores_chunk", CHUNK_SCORED,
                          lambda mc: index_pair(mc)[0])
