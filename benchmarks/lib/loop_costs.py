"""Bytes a looped stack's cache and weights cost, from shapes: the
numerators of the ``loop_*`` per-layer metrics.  Kept with the benchmark so
that no PR that claims a gain can change them.

``mc`` is a configuration file's ``model_config`` group.  A model without
``total_ut_steps`` runs its stack once.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.lib.costs import decode_attention_bytes


def cache_layers(mc: Dict[str, Any]) -> int:
    """Layers of the K/V cache: one a (pass, layer) pair."""
    return int(mc.get("total_ut_steps", 1)) * int(mc["num_layers"])


def kv_bytes_per_token(mc: Dict[str, Any], bytes_per_element: int = 2) -> int:
    """What one position holds in the cache: a K and a V row of every KV
    head in every cache layer."""
    return int(decode_attention_bytes(mc, 1, bytes_per_element)) \
        * cache_layers(mc)


def layer_weight_bytes(mc: Dict[str, Any], bytes_per_element: int = 2) -> int:
    """One layer's weights: the four attention projections, the MLP's two
    or three matrices and its norms' gains (two, or four with a norm on both
    sides of each sub-block).  A decode step reads them once a pass."""
    D, F, H = mc["hidden_size"], mc["intermediate_size"], mc["num_heads"]
    Hkv = mc.get("num_kv_heads") or H
    Dh = mc.get("head_dim") or D // H
    attn = 2 * D * H * Dh + 2 * D * Hkv * Dh
    mlp = D * F * (3 if mc.get("glu", True) else 2)
    norms = D * (4 if mc.get("sandwich_norm") else 2)
    return (attn + mlp + norms) * bytes_per_element


def decode_step_weight_bytes(mc: Dict[str, Any],
                             bytes_per_element: int = 2) -> int:
    """The layers' weights a decode step reads whatever its batch: every
    layer's, once a pass."""
    return cache_layers(mc) * layer_weight_bytes(mc, bytes_per_element)
