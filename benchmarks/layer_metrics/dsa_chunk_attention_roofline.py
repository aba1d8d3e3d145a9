"""Kernels: share of its roofline the chunk programs' attention under the
selection reached.  Least time for the traced calls of
``dsa_chunk_attention``: every (real token of a chunk, SELECTED key) pair
attended by 128 heads in the decompressed form (2 x 128 x (192 + 128)
FLOPs), at the chip's peak: the work the equations ask.  The kernel
decompresses and scores every row up to the chunk's last query and masks by
selection (the selection differs by query), so past 2,048 keys this share
falls as 2,048 over the context: that is what a kernel that skips unselected
keys would win.  The pairs from the cache kind's counter between the
profiler's start and the window's end."""

from benchmarks.lib.dsa_costs import (CHUNK_ATTENDED, chunk_pair_flops,
                                      roofline_share)


def read(ctx):
    return roofline_share(ctx, "dsa_chunk_attention", CHUNK_ATTENDED,
                          chunk_pair_flops)
