"""Operations and bytes a mixture-of-experts block needs, from shapes: the
numerators of the MoE kernels' roofline shares.  Kept with the benchmark so
that no PR that claims a gain can change them (``costs.py``'s rule; this
file is beside it and not in it because a PR may only add files).

``mc`` is a configuration file's ``model_config`` group.
"""

from __future__ import annotations

from typing import Any, Dict


def expert_matrices(mc: Dict[str, Any]) -> int:
    """Matrices of one expert: up and down, and the gate of a gated MLP."""
    return 3 if mc.get("glu", True) else 2


def expert_weight_bytes(mc: Dict[str, Any], bytes_per_element: int = 2
                        ) -> float:
    """Bytes of ALL experts' weights of one layer: what a decode step must
    read once per layer when its rows, between them, hit every expert (64
    rows x 8 choices over 64 experts: they nearly always do; the program's
    ``ds_serve_moe_expert_hits_total`` says how nearly)."""
    return float(mc["num_experts"] * expert_matrices(mc) * mc["hidden_size"]
                 * mc["intermediate_size"] * bytes_per_element)


def routed_flops(mc: Dict[str, Any], tokens: float) -> float:
    """Multiply-adds x 2 of the expert matrices for ``tokens`` tokens of one
    layer, each through its ``num_experts_per_tok`` experts: the work the
    routing asked for, whatever a kernel computes beyond it."""
    return 2.0 * tokens * mc["num_experts_per_tok"] * expert_matrices(mc) \
        * mc["hidden_size"] * mc["intermediate_size"]


def dense_flops(mc: Dict[str, Any], rows: float) -> float:
    """The same with every row through EVERY expert, as ``fused_moe_mlp``
    computes it (an unchosen expert's product is weighed by zero)."""
    return routed_flops(mc, rows) * mc["num_experts"] \
        / mc["num_experts_per_tok"]
