"""Bytes the Mamba-2 layers of a one-mixer model must move in a decode step
(the program's ``models/ssm_moe.py``), from shapes: the numerator of
``ssm_decode_roofline``.  Kept with the benchmark so that no PR that claims a
gain can change them (``costs.py``'s rule; this file is beside it and not in
it because a PR may only add files).

``mc`` is a configuration file's ``model_config`` group: ``layer_types``,
``ssm_num_heads`` H, ``ssm_head_dim`` P, ``ssm_groups`` G,
``ssm_state_size`` N.
"""

from __future__ import annotations

from typing import Any, Dict

STATE_BYTES = 4          # the state and its step's vectors: float32


def mamba_layers(mc: Dict[str, Any]) -> int:
    """Layers that keep a selective state space's state a slot."""
    return list(mc.get("layer_types") or ()).count("mamba2")


def state_step_bytes(mc: Dict[str, Any]) -> int:
    """One live row, one mamba2 layer, one decode step: the head states [H,
    P, N] read and written once, x [H, P], dt [H], B and C [G, N] in, y [H,
    P] out, all float32 (the kernel takes the decay and ``dt x`` broadcast
    to [H, P] rows and B and C as columns of a lane tile: more bytes than
    these, which reads low, and that is the truth)."""
    H, P, G, N = (mc["ssm_num_heads"], mc["ssm_head_dim"], mc["ssm_groups"],
                  mc["ssm_state_size"])
    return STATE_BYTES * (2 * H * P * N + 2 * H * P + H + 2 * G * N)



def attention_layers(mc: Dict[str, Any]) -> int:
    """Layers that keep K and V rows in pages beside the mamba2 layers'
    states: the ``full_attention`` ones of a pattern that has mamba2 layers
    (0 for any other model: ``hybrid_costs.full_layers`` or ``num_layers``
    count its pages' layers)."""
    return list(mc["layer_types"]).count("full_attention") \
        if mamba_layers(mc) else 0
