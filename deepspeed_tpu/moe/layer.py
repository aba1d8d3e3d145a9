"""Reference-parity MoE layer API.

The reference's ``deepspeed.moe.layer.MoE`` wraps a user torch expert module
with a ``TopKGate`` + ``MOELayer`` (SURVEY.md §2.1).  The functional analog is
a standalone block with ``init``/``apply`` usable inside any jax model, plus
the expert/non-expert param split helper (reference ``moe/utils.py``)
reworked as a pytree mask for optax (partition-by-mask replaces torch param
groups).
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.moe.sharded_moe import moe_mlp
from deepspeed_tpu.utils.logging import logger


class MoE:
    """Standalone top-k MoE feed-forward block.

    Mirrors the reference constructor surface.  ``ep_size`` is informational
    on TPU: expert placement is governed by the mesh's ``ep`` axis (a mismatch
    logs a warning rather than resizing process groups).  ``drop_tokens=False``
    takes ``moe_mlp``'s dropless path (tokens sorted by expert, grouped
    matmuls; no capacity, so the capacity factors and ``use_rts`` have
    nothing to act on); it is not built under ``ep > 1`` and raises there.
    """

    def __init__(self, hidden_size: int, num_experts: int = 1, k: int = 1,
                 intermediate_size: Optional[int] = None, ep_size: int = 1,
                 capacity_factor: float = 1.0, eval_capacity_factor: float = 1.0,
                 min_capacity: int = 4, activation: str = "silu", glu: bool = True,
                 use_residual: bool = False, drop_tokens: bool = True,
                 use_rts: bool = False, mesh=None):
        self.hidden_size = hidden_size
        self.num_experts = num_experts
        self.mesh = mesh
        if mesh is not None and ep_size > 1 and mesh.shape.get("ep", 1) != ep_size:
            logger.warning("MoE ep_size=%d ignored: mesh ep axis is %d (TPU expert "
                           "placement follows the mesh)", ep_size, mesh.shape.get("ep", 1))
        self.cfg = SimpleNamespace(
            num_experts=num_experts, num_experts_per_tok=k,
            moe_capacity_factor=capacity_factor,
            moe_eval_capacity_factor=eval_capacity_factor,
            moe_min_capacity=min_capacity, activation=activation, glu=glu,
            moe_drop_tokens=drop_tokens, moe_use_rts=use_rts)
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.use_residual = use_residual

    def init(self, rng, x=None) -> Any:
        D, F, E = self.hidden_size, self.intermediate_size, self.num_experts
        k1, k2, k3, k4, k5, k6, k7 = jax.random.split(rng, 7)
        s_in, s_ff = D ** -0.5, F ** -0.5
        params = {
            "gate_w": jax.random.uniform(k1, (D, E), jnp.float32, -s_in, s_in),
            "w_up": jax.random.uniform(k2, (E, D, F), jnp.float32, -s_in, s_in),
            "w_down": jax.random.uniform(k3, (E, F, D), jnp.float32, -s_ff, s_ff),
        }
        if self.cfg.glu:
            params["w_gate"] = jax.random.uniform(k4, (E, D, F), jnp.float32, -s_in, s_in)
        if self.use_residual:
            params["res_up"] = jax.random.uniform(k5, (D, F), jnp.float32, -s_in, s_in)
            params["res_down"] = jax.random.uniform(k6, (F, D), jnp.float32, -s_ff, s_ff)
            params["res_coef"] = jnp.zeros((D, 2), jnp.float32)
        return params

    def apply(self, params, x, training: bool = True, rng=None):
        """x: [B, S, D] -> (y, aux_loss).  ``training`` selects
        capacity_factor vs eval_capacity_factor (reference TopKGate arg);
        ``rng`` feeds random token selection when ``use_rts``.
        (Reference MoE.forward also returns exp_counts, a profiling detail.)"""
        cfg = self.cfg
        factor = cfg.moe_capacity_factor if training else cfg.moe_eval_capacity_factor
        eff = SimpleNamespace(**{**vars(cfg), "moe_capacity_factor": factor})
        y, aux = moe_mlp(params, x, eff, self.mesh, rng=rng)
        if self.use_residual:
            from deepspeed_tpu.models.layers import activation_fn
            act = activation_fn(cfg.activation)
            res = act(x @ params["res_up"]) @ params["res_down"]
            coef = jax.nn.softmax(x @ params["res_coef"], axis=-1)
            y = y * coef[..., 0:1] + res * coef[..., 1:2]
        return y, aux


def split_params_into_moe_groups(params) -> Any:
    """Boolean mask pytree: True where a leaf is an expert-parallel weight.

    Expert weights are identified *structurally*: any dict that contains a
    ``gate_w`` router alongside ``w_up``/``w_down`` is an MoE block (the
    built-in models' dense MLPs use the same leaf names but have no router).
    The router itself is dense/replicated, like the reference's gate (it sits
    in the non-expert group).  Use with ``optax.masked`` to give expert params
    their own schedule/decay — the functional replacement for the reference's
    optimizer param groups (``moe/utils.py``).
    """
    expert_keys = {"w_up", "w_down", "w_gate"}

    def walk(node, in_moe):
        if isinstance(node, dict):
            is_moe_block = "gate_w" in node and expert_keys & set(node)
            return {k: walk(v, in_moe or (is_moe_block and k in expert_keys))
                    for k, v in node.items()}
        return jax.tree.map(lambda _: in_moe, node)

    return walk(params, False)


def is_moe_param(params, path_or_mask=None) -> Any:
    """Convenience: the mask tree itself (see split_params_into_moe_groups)."""
    return split_params_into_moe_groups(params)
