"""Serve engine: ``ds_serve_prefill_chunks_extra_total`` over
``ds_serve_prefill_chunks_total``, window begin to profiler start: of the
chunk programs enqueued, the share given to a request that already had a
chunk in the same iteration (places of ``max_prefill_chunks`` the other
requests left).  0 where every iteration's places go to distinct requests;
nothing for a program without the counter."""

from benchmarks.lib.request_spans import counter_share


def read(ctx):
    return counter_share(ctx, "ds_serve_prefill_chunks_extra_total",
                         "ds_serve_prefill_chunks_total")
