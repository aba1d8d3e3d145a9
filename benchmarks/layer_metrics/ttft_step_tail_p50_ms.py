"""Serve engine: median over requests of (first token visible to the
benchmark, when ``step()`` returned) - ``Request.t_first_token``: the rest
of the iteration the token sat on the host through, other requests'
chunks, the decode dispatch and the lag-1 drain.  A server that streams
from inside the iteration would not pay it.
(``lib/host_spans.ttft_parts``.)"""

from benchmarks.lib.host_spans import ttft_part_p50_ms


def read(ctx):
    return ttft_part_p50_ms(ctx, "tail")
