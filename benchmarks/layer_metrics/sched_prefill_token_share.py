"""Serve engine: ``ds_serve_prefill_tokens_total`` over prefill plus
``ds_serve_decode_tokens_total``, window only (the registry is enabled in
the traced run; counters from the window's start to the profiler's)."""

P, D = "ds_serve_prefill_tokens_total", "ds_serve_decode_tokens_total"


def read(ctx):
    c = ctx["counters"]
    a, b = c.get("begin"), c.get("trace_start") or c.get("end")
    if not a or not b or P not in b:
        return None
    p, d = b[P] - a.get(P, 0), b[D] - a.get(D, 0)
    return 100.0 * p / (p + d) if p + d else None
