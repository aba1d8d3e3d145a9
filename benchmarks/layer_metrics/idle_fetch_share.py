"""Device: chip 0's idle time whose middle lies in
``ds_serve_first_token_fetch`` or ``ds_serve_block_fetch``, over the traced
window: the host is waiting for the chip and the chip has nothing queued,
so only dispatching further ahead can win it.  What remains of
``device_idle_share`` after this and ``idle_host_work_share`` is idle
outside ``step()``: the load generator's."""

from benchmarks.lib.host_spans import idle_share


def read(ctx):
    return idle_share(ctx, "fetch_s")
