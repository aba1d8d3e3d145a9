"""Growth of the program's ``ds_serve_moe_*`` counters over the window (the
registry is enabled in the traced run only; the window's begin to the
profiler's start, as the other counter readers take it)."""

from __future__ import annotations

from typing import Any, Dict, Optional


def grown(ctx: Dict[str, Any], *names: str) -> Optional[Dict[str, float]]:
    """``{name: end - begin}``, or None where the program has no such
    counter (a parent without them, a dense model's registry)."""
    c = ctx["counters"]
    a, b = c.get("begin"), c.get("trace_start") or c.get("end")
    if not a or not b or any(n not in b for n in names):
        return None
    return {n: b[n] - a.get(n, 0) for n in names}
