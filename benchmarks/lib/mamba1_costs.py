"""Bytes the Mamba-1 layers of a one-mixer model must move (the program's
``models/ssm_moe.py``, kind ``mamba1``; ``ops/pallas/selective_scan.py``),
from shapes: the numerators of ``mamba1_decode_roofline`` and
``selective_scan_roofline``.  Kept with the benchmark so that no PR that
claims a gain can change them (``costs.py``'s rule; this file is beside it
and not in it because a PR may only add files).

``mc`` is a configuration file's ``model_config`` group: ``layer_types``,
``ssm_inner_size`` (d_inner channels), ``ssm_state_size`` N.
"""

from __future__ import annotations

from typing import Any, Dict

STATE_BYTES = 4          # the state and its step's vectors: float32


def mamba1_layers(mc: Dict[str, Any]) -> int:
    """Layers that keep a Mamba-1 state a slot."""
    return list(mc.get("layer_types") or ()).count("mamba1")


def row_bytes(mc: Dict[str, Any]) -> int:
    """One row (a token) of one mamba1 layer through the recurrence: u and
    dt [d_inner] in, y [d_inner] out, B and C [N] in, all float32.  The skip
    and the gate are outside the kernels, so ``z`` and ``D`` are not its."""
    return STATE_BYTES * (3 * mc["ssm_inner_size"] + 2 * mc["ssm_state_size"])


def state_bytes(mc: Dict[str, Any]) -> int:
    """One mamba1 layer's state [d_inner, N] float32, read and written
    once."""
    return STATE_BYTES * 2 * mc["ssm_inner_size"] * mc["ssm_state_size"]


def state_step_bytes(mc: Dict[str, Any]) -> int:
    """One live row, one mamba1 layer, one decode step: the state read and
    written once and the step's vectors (716,928 at 5,120 x 16).  ``A``
    [d_inner, N] stays resident across a call's rows and is not counted;
    the kernel takes B and C as columns of a lane tile, more bytes than
    these, which reads low, and that is the truth."""
    return state_bytes(mc) + row_bytes(mc)


def scan_chunk_bytes(mc: Dict[str, Any], real_rows: float,
                     calls: float) -> float:
    """What ``calls`` calls of the chunk scan (one a mamba1 layer a chunk
    program) cannot avoid for ``real_rows`` REAL prompt rows in each layer:
    every real row's vectors once a layer, the state in and out once a
    call.  Pad rows of a bucket are work the kernel does and bytes nobody
    needs: they read low here."""
    return real_rows * mamba1_layers(mc) * row_bytes(mc) \
        + calls * state_bytes(mc)
