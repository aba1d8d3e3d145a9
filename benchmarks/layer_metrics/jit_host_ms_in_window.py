"""Entry: milliseconds jax spent tracing, lowering and compiling (or reading
the persistent cache in a compile's place) inside the measured window:
``ds_jit_trace_`` + ``_lower_`` + ``_compile_seconds_total``, window end
less window begin.  ``compiles_in_window`` in seconds and from inside the
program: that one counts backend events and times nothing, so a retrace
that ends in a cache read costs a second or two of lowering which it shows
as "1".  Must be 0.0; nothing for a program without the ledger and for a
driver that keeps no counters."""

from benchmarks.lib.setup_spans import jit_seconds


def read(ctx):
    begin = jit_seconds(ctx["counters"].get("begin"))
    end = jit_seconds(ctx["counters"].get("end"))
    if begin is None or end is None:
        return None
    return 1e3 * (end - begin)
