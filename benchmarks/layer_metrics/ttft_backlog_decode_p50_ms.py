"""Serve engine: over the requests whose last chunk was enqueued inside the
traced window BEHIND a decode block, median chip time in decode blocks
between the end of that enqueue and the end of the chunk's own program on
chip 0: the block a first token waits behind, which shorter blocks while a
first token is owed could win.  The other requests met a chip with no block
left to run and read 0; ``last_chunk_behind_block_share`` says how many are
of which kind.  The dispatch ranges' launch numbers joined to the ``XLA
Modules`` line (``lib/request_spans.backlog_split``); left out under 8 such
requests."""

from benchmarks.lib.request_spans import run_backlog_p50_ms


def read(ctx):
    return run_backlog_p50_ms(ctx, "decode", behind_only=True)
