"""Serve engine: median over requests of ``Request.t_last_chunk -
t_first_chunk``: from the request's first turn to the enqueue of its last
chunk's program: a wait for pages, the host's work on each chunk, and, for
a prompt of several chunks, the whole iterations between them (one chunk a
request an iteration).  (``lib/request_spans.stamp_parts``.)"""

from benchmarks.lib.request_spans import stamp_part_p50_ms


def read(ctx):
    return stamp_part_p50_ms(ctx, "chunks")
