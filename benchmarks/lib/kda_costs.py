"""Bytes a decode step of a model of linear-attention and latent-attention
layers needs (the program's ``models/kda_mla.py``), from shapes and
positions: the numerators of ``kda_decode_roofline`` and
``mla_decode_roofline``.  Kept with the benchmark so that no PR that claims
a gain can change them (``costs.py``'s rule; this file is beside it and not
in it because a PR may only add files).

``mc`` is a configuration file's ``model_config`` group: ``layer_types``,
``kda_num_heads`` H, ``kda_head_dim`` d, ``mla_kv_rank``, ``mla_rot_dim``.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

STATE_BYTES = 4          # the recurrent state and its step's vectors: float32


def kind_counts(mc: Dict[str, Any]) -> Tuple[int, int]:
    """(linear-attention layers, latent-attention layers)."""
    kinds = list(mc.get("layer_types") or ())
    return (kinds.count("linear_attention"), kinds.count("latent_attention"))


def state_step_bytes(mc: Dict[str, Any]) -> int:
    """One live row, one linear layer, one decode step: the head states
    [H, d, d] read and written once, q, k, v and the log-decay [H, d] and
    beta [H] in, o [H, d] out, all float32."""
    H, d = mc["kda_num_heads"], mc["kda_head_dim"]
    return STATE_BYTES * (2 * H * d * d + 4 * H * d + H + H * d)


def latent_row_bytes(mc: Dict[str, Any], bytes_per_element: int = 2) -> int:
    """The cache row of one position in one latent layer: the normed latent
    and the shared key values, stored once for all heads (the zeros that pad
    it to a lane tile are not counted)."""
    return (mc["mla_kv_rank"] + mc["mla_rot_dim"]) * bytes_per_element


def latent_rows_span(pos: int, n: int) -> int:
    """Rows attended in ONE latent layer by the ``n`` queries at ``pos ..
    pos + n - 1``: ``(pos + 1) + ... + (pos + n)``."""
    return n * (pos + 1) + n * (n - 1) // 2


def traced_steps(ctx) -> Tuple[int, int]:
    """(decode steps of live rows, latent rows they attended in one latent
    layer) in the traced iterations, summed over requests, from the loop's
    own marks: a request that shows ``seen`` tokens after an iteration sits
    at position ``prompt + seen - 1``, and the next block's steps run from
    there (``swa_decode_roofline``'s way)."""
    lo, hi = ctx["trace_window"]
    steps = rows = 0
    for lv, a in zip(ctx["loop"]["records"], ctx["loop"]["schedule"]):
        if lv is None:
            continue
        prev = None
        for t, k in lv.marks:
            if prev is not None and lo <= t < hi:
                steps += k - prev
                rows += latent_rows_span(len(a.prompt) + prev - 1, k - prev)
            prev = k
    return steps, rows


def decode_share(ctx, kernel: str, program: str = "jit_body"):
    """Traced time of ``kernel`` over the busy time of the decode-block
    programs, in percent; None for a trace without either."""
    tr = ctx["trace"]
    if tr is None or kernel not in tr["kernels"] \
            or not tr["programs"].get(program, {}).get("busy_s"):
        return None
    return 100.0 * tr["kernels"][kernel]["seconds"] \
        / tr["programs"][program]["busy_s"]


def byte_roofline_share(ctx, kernel: str, layers: int, nbytes: float):
    """Least time for ``nbytes`` in each of ``layers`` layers at the chip's
    bytes/s over ``kernel``'s traced time, in percent; None for a trace
    without the kernel, a configuration without such layers, or no bytes."""
    from benchmarks.lib.costs import least_seconds

    tr = ctx["trace"]
    if tr is None or kernel not in tr["kernels"] or not layers or not nbytes:
        return None
    least, _ = least_seconds(0.0, layers * nbytes, ctx["peaks"])
    return 100.0 * least / tr["kernels"][kernel]["seconds"]
