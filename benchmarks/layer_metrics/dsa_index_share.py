"""Model: traced time of the index-score kernels (``dsa_index_scores_paged``
in the decode blocks, ``dsa_index_scores_chunk`` in the chunk programs) over
the chip's busy time in the traced window, chip 0: what scoring every key
costs before a single one is attended.  None for a program without either
kernel."""

from benchmarks.lib.dsa_costs import INDEX_KERNELS


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.get("busy_s_chip0") \
            or not any(k in tr["kernels"] for k in INDEX_KERNELS):
        return None
    return 100.0 * sum(tr["kernels"].get(k, {}).get("seconds", 0.0)
                       for k in INDEX_KERNELS) / tr["busy_s_chip0"]
