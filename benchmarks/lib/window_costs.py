"""Rows and bytes decode attention needs where layers are of two kinds
(sliding-window layers over a ring, global layers over every position; the
program's ``models/afmoe.py``), from shapes and positions: the numerator of
``swa_decode_roofline``.  Kept with the benchmark so that no PR that claims a
gain can change it (``costs.py``'s rule; this file is beside it and not in it
because a PR may only add files).

``mc`` is a configuration file's ``model_config`` group: ``layer_types``,
``sliding_window`` W.  The query at position ``p`` attends ``min(p + 1, W)``
rows in each sliding layer and ``p + 1`` in each global one: not ``p + 1``
rows in every layer, which is what ``costs.decode_attention_bytes`` would be
given.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def kind_counts(mc: Dict[str, Any]) -> Tuple[int, int]:
    """(sliding layers, global layers)."""
    kinds = list(mc["layer_types"])
    n = kinds.count("sliding_attention")
    return n, len(kinds) - n


def attended_rows_span(mc: Dict[str, Any], pos: int, n: int
                       ) -> Tuple[int, int]:
    """(rows in ONE sliding layer, rows in ONE global layer) attended by the
    ``n`` queries at ``pos .. pos + n - 1`` (one row's decode steps of a
    block), in closed form."""
    W = int(mc["sliding_window"])
    full = n * (pos + 1) + n * (n - 1) // 2          # (pos+1) + ... + (pos+n)
    ramp = max(0, min(n, W - 1 - pos))               # steps with p + 1 < W
    win = ramp * (pos + 1) + ramp * (ramp - 1) // 2 + (n - ramp) * W
    return win, full


def row_bytes(mc: Dict[str, Any], bytes_per_element: int = 2) -> int:
    """K and V of one position in one layer."""
    Hkv = mc.get("num_kv_heads") or mc["num_heads"]
    Dh = mc.get("head_dim") or mc["hidden_size"] // mc["num_heads"]
    return 2 * Hkv * Dh * bytes_per_element


def decode_attention_bytes(mc: Dict[str, Any], window_rows: float,
                           full_rows: float, bytes_per_element: int = 2
                           ) -> float:
    """What the decode attention kernels of ALL layers must read for queries
    that attend ``window_rows`` in each sliding layer and ``full_rows`` in
    each global one."""
    n_win, n_full = kind_counts(mc)
    return float(window_rows * n_win + full_rows * n_full) \
        * row_bytes(mc, bytes_per_element)
