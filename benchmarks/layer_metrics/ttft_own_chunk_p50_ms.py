"""Serve engine: over the requests whose last chunk was enqueued inside the
traced window, median length of that chunk's OWN program on chip 0, start
to end: the only part of the wait for a first token that is the request's
own work.  (``lib/request_spans.backlog_split``' ``program``; left out
under 8 such requests.)"""

from benchmarks.lib.request_spans import run_backlog_p50_ms


def read(ctx):
    return run_backlog_p50_ms(ctx, "program")
