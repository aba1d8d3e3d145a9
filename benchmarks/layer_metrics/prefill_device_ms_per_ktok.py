"""Model: device busy time inside the prefill programs (``jit_prefill``,
one per chunk bucket) per 1,000 prompt tokens prefilled in the traced
window (``ds_serve_prefill_tokens_total`` between profiler start and
end)."""

PROGRAM = "jit_prefill"
P = "ds_serve_prefill_tokens_total"


def read(ctx):
    tr, c = ctx["trace"], ctx["counters"]
    if tr is None or PROGRAM not in tr["programs"]:
        return None
    a, b = c.get("trace_start"), c.get("end")
    if not a or not b or not b.get(P, 0) - a.get(P, 0):
        return None
    return tr["programs"][PROGRAM]["busy_s"] * 1e3 / (
        (b[P] - a[P]) / 1e3)
