"""Serve engine: share of wall time the engine thread spent inside
``step()`` NOT blocked on the chip: ``ds_serve_step_seconds_total`` less
the two fetch counters, window begin to profiler start (registry on,
profiler off).  Host work the chip may or may not be covered through;
``idle_host_work_share`` says how much of it the chip sat out.  The loop
is always inside ``step()`` while it has work, so 100 less this is the
share it spent blocked on the chip."""

from benchmarks.lib.host_spans import host_work_share


def read(ctx):
    return host_work_share(ctx)
