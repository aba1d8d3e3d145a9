"""Serve engine: median over requests of ``Request.t_admit - t_submit``,
the wait for a slot: the part of ``ttft_p50_ms`` that more slots or faster
turnover would shorten.  The program's own stamps; which requests, and the
other three parts, in ``lib/host_spans.ttft_parts``."""

from benchmarks.lib.host_spans import ttft_part_p50_ms


def read(ctx):
    return ttft_part_p50_ms(ctx, "queue")
