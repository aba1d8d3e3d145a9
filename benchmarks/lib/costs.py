"""Operations and bytes the algorithm needs, from shapes: the numerators
of MFU and of the roofline shares.  Kept with the benchmark so that no PR
that claims a gain can change them.

``mc`` is a configuration file's ``model_config`` group.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def matmul_params(mc: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication for every token:
    the layers' projections and the output head (the tied embedding counts
    once, as the head; embedding lookups and norms cost no matmul)."""
    D, F, L = mc["hidden_size"], mc["intermediate_size"], mc["num_layers"]
    H = mc["num_heads"]
    Hkv = mc.get("num_kv_heads") or H
    Dh = mc.get("head_dim") or D // H
    attn = D * H * Dh * 2 + D * Hkv * Dh * 2
    mlp = D * F * (3 if mc.get("glu", True) else 2)
    return L * (attn + mlp) + mc["vocab_size"] * D


def train_flops_per_token(mc: Dict[str, Any], seq: int) -> float:
    """Forward and backward of one token: 6 per matmul parameter, plus
    causal attention's 6 * L * (H * Dh) * S (QK^T and PV are 4*S*H*Dh a
    token forward; three times that with the backward; halved by the
    mask).  Recomputed operations are not counted."""
    H = mc["num_heads"]
    Dh = mc.get("head_dim") or mc["hidden_size"] // H
    return 6.0 * matmul_params(mc) + 6.0 * mc["num_layers"] * H * Dh * seq


# Matrix multiplications of [S, Dh] x [Dh, S] size each flash-attention
# kernel of the training path must do per (batch, head), given that the
# backward is split in two kernels which each rebuild the probabilities:
# forward QK^T, PV; dq: QK^T, dO V^T, dS K; dkv: QK^T, dO V^T, P^T dO,
# dS^T Q.
FLASH_MATMULS = {"flash_attention_fwd": 2, "flash_attention_bwd_dq": 3,
                 "flash_attention_bwd_dkv": 4}


def flash_attention_flops(kernel: str, batch: int, heads: int, seq: int,
                          head_dim: int) -> float:
    """One call of a training attention kernel on ``[batch, heads, seq,
    head_dim]``, causal (half of the square is masked out)."""
    return FLASH_MATMULS[kernel] * 2.0 * batch * heads * seq * seq \
        * head_dim * 0.5


def decode_attention_bytes(mc: Dict[str, Any], context_tokens: float,
                           bytes_per_element: int = 2) -> float:
    """One call of the decode attention kernel (one layer, one step): it
    must read K and V of every context token of every row it serves."""
    H = mc["num_heads"]
    Hkv = mc.get("num_kv_heads") or H
    Dh = mc.get("head_dim") or mc["hidden_size"] // H
    return 2.0 * context_tokens * Hkv * Dh * bytes_per_element


def least_seconds(flops: float, nbytes: float, pk: Dict[str, float]
                  ) -> Tuple[float, str]:
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak bytes/s, and which of the two bounds."""
    tf, tb = flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
