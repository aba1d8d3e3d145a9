"""Serve engine: over the requests whose last chunk was enqueued inside the
traced window behind a decode block (``ttft_backlog_decode_p50_ms``' own),
median chip time in OTHER chunk programs between the end of that enqueue
and the end of the chunk's own program on chip 0: chunks of other requests
queued ahead of it.  What batching chunks across requests could win.
(``lib/request_spans.backlog_split``; left out under 8 such requests.)"""

from benchmarks.lib.request_spans import run_backlog_p50_ms


def read(ctx):
    return run_backlog_p50_ms(ctx, "chunks", behind_only=True)
