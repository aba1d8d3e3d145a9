"""Bytes the decode attention of a model of linear-attention layers beside
per-head ``full_attention`` layers must read (the program's
``models/kda_mla.py``, ``serving/cache_kind.py:FullPagesAndState``), from
shapes and positions: the numerator of ``hybrid_attn_decode_roofline``.
Kept with the benchmark so that no PR that claims a gain can change it
(``costs.py``'s rule; beside it and not in it because a PR may only add
files).

``mc`` is a configuration file's ``model_config`` group: ``layer_types``,
``num_heads``, ``num_kv_heads``, ``head_dim``.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.lib.costs import decode_attention_bytes


def full_layers(mc: Dict[str, Any]) -> int:
    """Layers that keep K and V rows in pages: the ``full_attention`` ones
    of a pattern that also has linear-attention layers (0 for any other
    model: its pages are not this kind's)."""
    kinds = list(mc.get("layer_types") or ())
    if "linear_attention" not in kinds:
        return 0
    return kinds.count("full_attention")


def kv_bytes_per_token(mc: Dict[str, Any], bytes_per_element: int = 2) -> int:
    """What one position holds in the page pool: a K and a V row of every
    key-value head in the FULL layers only."""
    return int(decode_attention_bytes(mc, 1, bytes_per_element)) \
        * full_layers(mc)

