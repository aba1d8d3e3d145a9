"""Rows, bytes and operations EVA attention needs (EvaByte; the program's
``models/eva.py``), from shapes and positions: the numerators of the EVA
kernels' roofline shares.  Kept with the benchmark so that no PR that claims
a gain can change them (``costs.py``'s rule; this file is beside it and not
in it because a PR may only add files).

``mc`` is a configuration file's ``model_config`` group: ``eva_window`` W,
``eva_chunk`` C.  The query at position ``p`` attends the ``p % W + 1``
rows of its own window and one summary row per ``C`` positions of every
EARLIER window, ``(p // W) * (W / C)``: not ``p + 1`` rows, which is what
``costs.decode_attention_bytes`` would be given.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def attended_rows(mc: Dict[str, Any], pos: int) -> Tuple[int, int]:
    """(window rows, summary rows) of the query at position ``pos``."""
    W, C = int(mc["eva_window"]), int(mc["eva_chunk"])
    return pos % W + 1, (pos // W) * (W // C)


def attended_rows_span(mc: Dict[str, Any], pos: int, n: int
                       ) -> Tuple[int, int]:
    """The same summed over the ``n`` queries at ``pos .. pos + n - 1``
    (one row's decode steps of a block), in closed form a window at a
    time."""
    W, C = int(mc["eva_window"]), int(mc["eva_chunk"])
    win = summ = 0
    while n > 0:
        r = pos % W
        k = min(n, W - r)                       # steps left in this window
        win += k * r + k * (k + 1) // 2         # (r+1) + ... + (r+k)
        summ += k * (pos // W) * (W // C)
        pos, n = pos + k, n - k
    return win, summ


def row_bytes(mc: Dict[str, Any], bytes_per_element: int = 2) -> int:
    """K and V of one row (a token's, or a chunk summary's) in one layer."""
    H = mc["num_heads"]
    Dh = mc.get("head_dim") or mc["hidden_size"] // H
    return 2 * H * Dh * bytes_per_element


def decode_attention_bytes(mc: Dict[str, Any], rows: float,
                           bytes_per_element: int = 2) -> float:
    """One call of the EVA decode kernel (one layer, one step) must read K
    and V of every row its queries attend, ``rows`` summed over the batch
    (window rows plus summary rows)."""
    return float(rows) * row_bytes(mc, bytes_per_element)


def summarize_bytes(mc: Dict[str, Any], bytes_per_element: int = 2) -> float:
    """Pooling ONE window of one row in one layer: read its ``W`` rows of K
    and V, write its ``W / C`` summaries."""
    W, C = int(mc["eva_window"]), int(mc["eva_chunk"])
    return float(W + W // C) * row_bytes(mc, bytes_per_element)


def summarize_flops(mc: Dict[str, Any]) -> float:
    """The same in operations: per row and head, two pooling logits (``k .
    mu``, ``k . phi``: 2 x 2 Dh) and two weighted sums (2 x 2 Dh); the
    chunk softmaxes are lower order."""
    H = mc["num_heads"]
    Dh = mc.get("head_dim") or mc["hidden_size"] // H
    return 8.0 * int(mc["eva_window"]) * H * Dh
