"""Device: chip 0's idle time whose middle lies in a ``ds_serve_*`` range
that does not block on the chip (a child, or a parent's self time), over
the traced window: the part of ``device_idle_share`` that shorter host work
inside ``step()`` can win.  ``python -m benchmarks.lib.host_spans <trace
dir>`` prints it range by range."""

from benchmarks.lib.host_spans import idle_share


def read(ctx):
    return idle_share(ctx, "host_work_s")
