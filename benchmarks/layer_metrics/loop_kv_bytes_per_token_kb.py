"""Serve engine: the gauge ``ds_serve_kv_bytes_per_token`` in KB (1,024 B):
what one position holds in the page pool over all its cache layers, as the
engine built it (384.0 for Ouro's 48 cache layers of 16 x 128 bf16 K and V
rows).  What a cache-layout change would move.  None for a program without
the gauge."""

GAUGE = "ds_serve_kv_bytes_per_token"


def read(ctx):
    snap = ctx["counters"].get("end") or {}
    return snap[GAUGE] / 1024.0 if snap.get(GAUGE) else None
