"""Serve engine: ``ds_serve_first_token_overlapped_total`` over
``ds_serve_first_tokens_total``, window begin to profiler start: of the
first tokens fetched right behind their chunk, the share fetched with a
decode block already enqueued behind it, so that the wait for the value
cost the chip no gap (PR 28).  The rest waited with nothing queued."""

from benchmarks.lib.request_spans import FIRSTS, counter_share


def read(ctx):
    return counter_share(ctx, *FIRSTS)
