"""Serve engine: median over requests of ``Request.t_first_token -
t_admit``: from the slot to the first token's value on the host, which is
the prompt's chunks, the wait behind the decode block already on the chip,
and the blocking fetch.  (``lib/host_spans.ttft_parts``.)"""

from benchmarks.lib.host_spans import ttft_part_p50_ms


def read(ctx):
    return ttft_part_p50_ms(ctx, "prefill")
