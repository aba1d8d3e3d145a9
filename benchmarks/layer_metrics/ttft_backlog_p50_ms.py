"""Serve engine: median over requests of ``Request.t_first_token -
t_last_chunk``: from the enqueue of the last chunk's program to the first
token's value on the host: the chip working off what was queued ahead of
the chunk, the chunk itself, the fetch.  What ran there:
``ttft_backlog_decode_p50_ms``, ``ttft_backlog_chunks_p50_ms``,
``ttft_own_chunk_p50_ms``.  (``lib/request_spans.stamp_parts``.)"""

from benchmarks.lib.request_spans import stamp_part_p50_ms


def read(ctx):
    return stamp_part_p50_ms(ctx, "backlog")
