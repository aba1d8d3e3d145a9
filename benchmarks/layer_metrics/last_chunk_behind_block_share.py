"""Serve engine: of the requests whose last chunk was enqueued inside the
traced window, the share whose chunk program waited on chip 0 behind a
decode block (``lib/request_spans.behind_a_block``).  The rest met a chip
with no block left to run, and their first token cost the chunk's own
program and little else.  ``ttft_backlog_decode_p50_ms`` and
``ttft_backlog_chunks_p50_ms`` are medians over this share alone; left out
under 8 requests."""

from benchmarks.lib.request_spans import behind_block_share, split_of_run


def read(ctx):
    return behind_block_share(split_of_run(ctx))
