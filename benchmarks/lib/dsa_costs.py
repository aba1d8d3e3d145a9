"""Operations and bytes the learned selection of keys asks for (the
program's ``models/kda_mla.py`` indexer and its four kernels), from shapes
and from the counters the cache kind moves on the host
(``serving/cache_kind.py:IndexedLatentPagesAndRing``): the numerators of the
four ``dsa_*_roofline`` metrics.  Kept with the benchmark so that no PR that
claims a gain can change them (``costs.py``'s rule; beside it because a PR
may only add files).  They count the work the EQUATIONS ask (index keys
scored, keys selected and attended), not what an implementation visits.

``mc`` is a configuration file's ``model_config`` group: ``layer_types``,
``mla_index_heads`` G, ``mla_index_dim`` d, ``mla_index_topk``, ``num_heads``
H, ``mla_kv_rank``, ``mla_nope_dim``, ``mla_rot_dim``, ``mla_v_dim``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

SCORED = "ds_serve_dsa_keys_scored_total"
ATTENDED = "ds_serve_dsa_keys_attended_total"
CHUNK_SCORED = "ds_serve_dsa_chunk_keys_scored_total"
CHUNK_ATTENDED = "ds_serve_dsa_chunk_keys_attended_total"
INDEX_KERNELS = ("dsa_index_scores_paged", "dsa_index_scores_chunk")


def indexed_layers(mc: Dict[str, Any]) -> int:
    """Layers that select their keys: the latent_attention layers of a
    model with an indexer."""
    if not mc.get("mla_index_topk"):
        return 0
    return list(mc.get("layer_types") or ()).count("latent_attention")


def index_pair(mc: Dict[str, Any], bytes_per_element: int = 2
               ) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE (query, index key) pair in one layer: G heads'
    dot products of d values (2 G d; the ReLU and the weighted sum are not
    counted), and the key read once."""
    G, d = mc["mla_index_heads"], mc["mla_index_dim"]
    return 2.0 * G * d, float(d * bytes_per_element)


def selected_pair(mc: Dict[str, Any], bytes_per_element: int = 2
                  ) -> Tuple[float, float]:
    """(FLOPs, bytes) of ONE (decode query, selected row) pair in one layer
    in the absorbed form: H heads score the row's ``kv_rank + rot`` values
    and sum its ``kv_rank``; the row (stored once for all heads; its lane
    padding is not counted) read once."""
    H, kv, r = mc["num_heads"], mc["mla_kv_rank"], mc["mla_rot_dim"]
    return 2.0 * H * (kv + r + kv), float((kv + r) * bytes_per_element)


def chunk_pair_flops(mc: Dict[str, Any]) -> float:
    """FLOPs of ONE (chunk query, selected key) pair in one layer in the
    decompressed form: H heads' scores over ``nope + rot`` and sums over
    ``v`` (the decompression of a row, shared by a chunk's queries, is not
    counted)."""
    H = mc["num_heads"]
    return 2.0 * H * (mc["mla_nope_dim"] + mc["mla_rot_dim"]
                      + mc["mla_v_dim"])


def traced_growth(ctx, name: str) -> Optional[float]:
    """A counter's growth between the profiler's start and the window's
    end; None where the program has no such counter."""
    c = ctx["counters"]
    a, b = c.get("trace_start"), c.get("end")
    if not a or not b or name not in b:
        return None
    return b[name] - a.get(name, 0)


def roofline_share(ctx, kernel: str, counter: str, pair):
    """The larger of the FLOP time and the byte time of ``counter``'s traced
    growth (pairs in ONE indexed layer; ``pair(mc)`` gives one pair's FLOPs,
    or its (FLOPs, bytes)) times the indexed layers, over ``kernel``'s
    traced time, in percent; None for a trace without the kernel, a program
    without the counter, a configuration without an indexer, or no pairs."""
    from benchmarks.lib.costs import least_seconds

    tr = ctx["trace"]
    mc = ctx["config"]["model_config"]
    layers = indexed_layers(mc)
    if tr is None or kernel not in tr["kernels"] or not layers:
        return None
    pairs = traced_growth(ctx, counter)
    if not pairs:
        return None
    cost = pair(mc)
    flops, nbytes = cost if isinstance(cost, tuple) else (cost, 0.0)
    least, _ = least_seconds(layers * pairs * flops, layers * pairs * nbytes,
                             ctx["peaks"])
    return 100.0 * least / tr["kernels"][kernel]["seconds"]
