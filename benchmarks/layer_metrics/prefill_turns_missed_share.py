"""Serve engine: ``ds_serve_prefill_turns_missed_total`` over
``ds_serve_prefill_turns_total``, window begin to profiler start: of the
iterations a request spent in a slot with prompt left to compute, the share
in which it was given no chunk (beyond ``max_prefill_chunks``, or refused
pages).  What more chunks an iteration could win."""

from benchmarks.lib.request_spans import TURNS, counter_share


def read(ctx):
    return counter_share(ctx, *TURNS)
