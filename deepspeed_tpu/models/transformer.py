"""Decoder-only transformer (Llama / GPT-2 / Mixtral families), TPU-native.

The reference has no model zoo — users hand torch modules to
``deepspeed.initialize`` and the kernel-injection policies recognize the
architecture (``deepspeed/module_inject/containers/``: GPT2, LLaMA, Mixtral…,
SURVEY.md §2.1).  Here the same families are implemented directly as a
functional jax model designed for the compiler:

- **Stacked layers + ``lax.scan``**: all layer params carry a leading [L]
  dim and one compiled layer body is scanned — O(1) compile time in depth,
  and XLA pipelines the per-layer collectives.
- **Remat per layer** (``jax.checkpoint``) is the activation-checkpointing
  equivalent of the reference's ``runtime/activation_checkpointing`` —
  recompute-in-backward as a compiler transform instead of autograd hooks.
- **Logical TP specs** (``logical_pspecs``) mark Megatron column/row splits
  over the ``tp`` mesh axis (the AutoTP classification, auto_tp.py) and
  expert splits over ``ep``; the engine merges these with the ZeRO ``fsdp``
  sharding (runtime/zero/partition.py).
- Fused kernels: RMSNorm/LayerNorm, RoPE, flash attention from
  ``deepspeed_tpu/ops/pallas`` (the csrc kernel equivalents).

API shape follows the flax convention the engine expects
(``init(rng, batch)`` / ``apply(params, batch, rngs=...)``): with ``labels``
the model returns the scalar LM loss (fp32 accumulation), else logits.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.comm.mesh import axis_size, get_global_mesh
from deepspeed_tpu.models.afmoe import close
from deepspeed_tpu.models.config import ModelConfig, get_model_config
from deepspeed_tpu.models.layers import (activation_fn, apply_partial_rope,
                                         attention_core, constrain,
                                         _mesh_spec, model_norm, norm, qk_norm,
                                         _repeat_kv, rope_cache, rope_dim)
from deepspeed_tpu.ops.pallas import apply_rotary_pos_emb


EVA_INIT_STD = 0.01275     # EvaByte's published ``init_std``


def _uniform(rng, shape, scale, dtype):
    return jax.random.uniform(rng, shape, dtype, -scale, scale)


# the mesh axes a batch is split over (the sequence goes over "sp")
_BATCH_AX = ("dp", "fsdp", "ep")


def _axes(entry) -> tuple:
    """One PartitionSpec entry as a tuple of axis names."""
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


class CausalLM:
    """Functional causal language model over a device mesh."""

    def __init__(self, config: ModelConfig, mesh: Optional[Mesh] = None):
        self.config = config
        self._mesh = mesh

    @property
    def mesh(self) -> Optional[Mesh]:
        return self._mesh if self._mesh is not None else get_global_mesh(create_default=False)

    def set_param_offload_specs(self, specs) -> None:
        """Engine hook: runtime PartitionSpecs for the param tree, needed so
        the per-layer host->device streaming moves carry explicit shardings
        (ZeRO-Infinity param tiering)."""
        self._offload_specs = specs

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------
    def init(self, rng, tokens=None, labels=None) -> Dict[str, Any]:
        cfg = self.config
        dtype = jnp.float32  # master params fp32; engine casts for compute
        if cfg.is_afmoe:     # two stacks of layers (models/afmoe.py)
            from deepspeed_tpu.models import afmoe
            return afmoe.form(cfg).init_params(cfg, rng, dtype)
        D, F, V, L = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.num_layers
        H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        E = cfg.num_experts
        keys = iter(jax.random.split(rng, 32))
        s_in = D ** -0.5
        s_ff = F ** -0.5

        def linit(key, shape, scale):
            # Layer weights always carry the stacked [L] leading dim; scan vs
            # python-loop is a forward-pass choice, not a layout choice.
            return _uniform(key, (L,) + shape, scale, dtype)

        # a unit-offset norm multiplies by 1 + gain: its gain starts at 0
        gain = jnp.zeros if cfg.norm_add_unit_offset else jnp.ones
        norm_p = {"scale": gain((L, D), dtype)}
        if cfg.norm == "layernorm":
            norm_p["bias"] = jnp.zeros((L, D), dtype)
        attn = {
            "wq": linit(next(keys), (D, H * Dh), s_in),
            "wk": linit(next(keys), (D, Hkv * Dh), s_in),
            "wv": linit(next(keys), (D, Hkv * Dh), s_in),
            "wo": linit(next(keys), (H * Dh, D), (H * Dh) ** -0.5),
        }
        if cfg.use_bias or cfg.qkv_bias:
            attn.update(bq=jnp.zeros((L, H * Dh), dtype),
                        bk=jnp.zeros((L, Hkv * Dh), dtype),
                        bv=jnp.zeros((L, Hkv * Dh), dtype))
        if cfg.use_bias:
            attn.update(bo=jnp.zeros((L, D), dtype))
        if cfg.qk_norm:
            attn.update(q_norm={"scale": jnp.ones((L, H * Dh), dtype)},
                        k_norm={"scale": jnp.ones((L, Hkv * Dh), dtype)})
        if cfg.is_eva:
            # the release's adaptive_mu_k / adaptive_phi, initialised as its
            # other weights are (normal, the published init_std)
            attn.update(
                eva_mu=jax.random.normal(next(keys), (L, H, Dh), dtype)
                * EVA_INIT_STD,
                eva_phi=jax.random.normal(next(keys), (L, H, Dh), dtype)
                * EVA_INIT_STD)
        if cfg.is_moe:
            mlp = {
                "gate_w": _uniform(next(keys), (L, D, E), s_in, dtype),
                "w_up": _uniform(next(keys), (L, E, D, F), s_in, dtype),
                "w_down": _uniform(next(keys), (L, E, F, D), s_ff, dtype),
            }
            if cfg.glu:
                mlp["w_gate"] = _uniform(next(keys), (L, E, D, F), s_in, dtype)
        else:
            mlp = {
                "w_up": linit(next(keys), (D, F), s_in),
                "w_down": linit(next(keys), (F, D), s_ff),
            }
            if cfg.glu:
                mlp["w_gate"] = linit(next(keys), (D, F), s_in)
            if cfg.has_mlp_bias:
                mlp.update(b_up=jnp.zeros((L, F), dtype),
                           b_down=jnp.zeros((L, D), dtype))
                if cfg.glu:
                    mlp["b_gate"] = jnp.zeros((L, F), dtype)
        layers = {"attn_norm": norm_p,
                  "mlp_norm": jax.tree.map(jnp.copy, norm_p),
                  "attn": attn, "mlp": mlp}
        if cfg.sandwich_norm:
            # a norm on each sub-block's OUTPUT too.  Its gain starts at
            # (2 L)^-0.5: a normed output has unit scale whatever the stream's,
            # so with gains of 1 every sub-block would REPLACE a stream of
            # unit scale (the embedding below, a looped stack's normed pass)
            # rather than refine it, and nothing would damp a rounding on its
            # way through the depth; at (2 L)^-0.5 the 2 L outputs of one
            # pass through the stack together carry the stream's own energy
            post = jnp.full((L, D), (2 * L) ** -0.5, dtype)
            layers.update(attn_post_norm={"scale": post},
                          mlp_post_norm={"scale": jnp.copy(post)})
        fnorm = {"scale": gain((D,), dtype)}
        if cfg.norm == "layernorm":
            fnorm["bias"] = jnp.zeros((D,), dtype)
        # the token embedding at 0.02 is the start of a stream that the
        # sub-blocks' raw outputs, of like scale, add to; where every output
        # is NORMED to unit scale first it would be no part of the stream,
        # which then were the first sub-blocks' outputs alone (PERF.md,
        # PR 44's finding), so there it starts at unit scale too
        embed_std = 1.0 if cfg.sandwich_norm else 0.02
        params = {
            "embed": {"tok": jax.random.normal(next(keys), (V, D), dtype)
                      * embed_std},
            "layers": layers,
            "final_norm": fnorm,
        }
        if cfg.position == "learned":
            params["embed"]["pos"] = jax.random.normal(
                next(keys), (cfg.max_seq_len, D), dtype) * 0.02
        if cfg.embed_norm:  # bloom: layernorm right after the token embed
            params["embed"]["norm"] = {"scale": jnp.ones((D,), dtype),
                                       "bias": jnp.zeros((D,), dtype)}
        if not cfg.tie_embeddings:
            # head p's V columns predict token i + 1 + p (num_pred_heads)
            params["lm_head"] = jax.random.normal(
                next(keys), (D, cfg.num_pred_heads * V), dtype) * s_in
        if cfg.lm_head_bias:
            params["lm_head_bias"] = jnp.zeros((V,), dtype)
        if cfg.loop_exit_gate:  # a Linear with bias on each pass's stream
            params["exit_gate"] = {
                "w": jax.random.normal(next(keys), (D, 1), dtype) * s_in,
                "b": jnp.zeros((1,), dtype)}
        return params

    def logical_pspecs(self) -> Dict[str, Any]:
        """Tensor/expert-parallel logical specs (the AutoTP column/row map).

        Layer weights have a leading stacked [L] dim (never sharded here —
        ``fsdp`` may claim it later for ZeRO-3).
        """
        cfg = self.config
        if cfg.is_afmoe:
            from deepspeed_tpu.models import afmoe
            return afmoe.logical_pspecs(cfg, jax.eval_shape(
                lambda: afmoe.form(cfg).init_params(cfg,
                                                    jax.random.PRNGKey(0))))
        col = P(None, None, "tp")       # [L, D, H*Dh] / [L, D, F] — column split
        row = P(None, "tp", None)       # [L, F, D] / [L, H*Dh, D] — row split
        norm_spec = {"scale": P(None, None)}
        if cfg.norm == "layernorm":
            norm_spec["bias"] = P(None, None)
        attn = {"wq": col, "wk": col, "wv": col, "wo": row}
        if cfg.use_bias or cfg.qkv_bias:
            # column-split outputs carry tp-split biases; row outputs are
            # reduced across tp, so their bias stays replicated
            attn.update(bq=P(None, "tp"), bk=P(None, "tp"), bv=P(None, "tp"))
        if cfg.use_bias:
            attn.update(bo=P(None, None))
        if cfg.qk_norm:  # replicated; layers.qk_norm refuses tp > 1
            attn.update(q_norm={"scale": P(None, None)},
                        k_norm={"scale": P(None, None)})
        if cfg.is_eva:  # replicated; _attn_out refuses tp > 1 and sp > 1
            attn.update(eva_mu=P(None, None, None),
                        eva_phi=P(None, None, None))
        if cfg.is_moe:
            mlp = {"gate_w": P(None, None, None),
                   "w_up": P(None, "ep", None, "tp"),
                   "w_down": P(None, "ep", "tp", None)}
            if cfg.glu:
                mlp["w_gate"] = P(None, "ep", None, "tp")
        else:
            mlp = {"w_up": col, "w_down": row}
            if cfg.glu:
                mlp["w_gate"] = col
            if cfg.has_mlp_bias:
                mlp.update(b_up=P(None, "tp"), b_down=P(None, None))
                if cfg.glu:
                    mlp["b_gate"] = P(None, "tp")
        fnorm = {"scale": P(None)}
        if cfg.norm == "layernorm":
            fnorm["bias"] = P(None)
        specs = {
            "embed": {"tok": P("tp", None)},
            "layers": {"attn_norm": norm_spec,
                       "mlp_norm": dict(norm_spec),
                       "attn": attn, "mlp": mlp},
            "final_norm": fnorm,
        }
        if cfg.position == "learned":
            specs["embed"]["pos"] = P(None, None)
        if cfg.embed_norm:
            specs["embed"]["norm"] = {"scale": P(None), "bias": P(None)}
        if not cfg.tie_embeddings:
            specs["lm_head"] = P(None, "tp")
        if cfg.lm_head_bias:
            specs["lm_head_bias"] = P("tp")
        if cfg.sandwich_norm:
            specs["layers"].update(attn_post_norm={"scale": P(None, None)},
                                   mlp_post_norm={"scale": P(None, None)})
        if cfg.loop_exit_gate:
            specs["exit_gate"] = {"w": P(None, None), "b": P(None)}
        mesh = self.mesh
        if mesh is not None and not mesh.empty:
            # pipeline parallelism: stage ownership = stacked-layer-dim shard
            from deepspeed_tpu.runtime.pipe.spmd import pp_layer_pspecs
            specs["layers"] = pp_layer_pspecs(specs["layers"], mesh)
        return specs

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def _attn_out(self, lp, x, k_attn, cos, sin, batch_ax, use_drop):
        """Attention sub-block OUTPUT (residual not added)."""
        cfg = self.config
        mesh = self.mesh
        B, S, D = x.shape
        H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        h = model_norm(cfg, x, lp["attn_norm"], mesh)
        a = lp["attn"]
        if cfg.fp32_residual:     # matmul inputs in the weights' dtype
            h = h.astype(a["wq"].dtype)
        q = h @ a["wq"]
        k = h @ a["wk"]
        v = h @ a["wv"]
        if cfg.use_bias or cfg.qkv_bias:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        if cfg.qk_norm:
            q, k = qk_norm(q, k, a["q_norm"]["scale"], a["k_norm"]["scale"],
                           cfg.norm_eps, mesh)
        q = q.reshape(B, S, H, Dh).transpose(0, 2, 1, 3)
        k = k.reshape(B, S, Hkv, Dh).transpose(0, 2, 1, 3)
        v = v.reshape(B, S, Hkv, Dh).transpose(0, 2, 1, 3)
        if cfg.position == "rope":  # [B, H, S, Dh] is the kernel's layout
            q = apply_partial_rope(q, cos, sin)
            k = apply_partial_rope(k, cos, sin)
        k = _repeat_kv(k, H // Hkv)
        v = _repeat_kv(v, H // Hkv)
        if cfg.is_eva:
            if mesh is not None and not mesh.empty and (
                    axis_size(mesh, "tp") > 1 or axis_size(mesh, "sp") > 1):
                raise NotImplementedError(
                    "attention='eva' with tp > 1 or sp > 1: eva_mu/eva_phi "
                    "are replicated per head and the windows are not split "
                    "over chips")
            from deepspeed_tpu.models.eva import eva_attention
            o = eva_attention(q, k, v, a["eva_mu"], a["eva_phi"],
                              window=cfg.eva_window, chunk=cfg.eva_chunk,
                              scale=Dh ** -0.5)
        else:
            o = attention_core(q, k, v, mesh, causal=True,
                               sp_mode=cfg.sp_mode,
                               alibi=cfg.position == "alibi",
                               ring_q=getattr(cfg, "seq_ring_q", False),
                               ring_q_block=getattr(cfg, "comm_quant_block",
                                                    256))
        o = o.transpose(0, 2, 1, 3).reshape(B, S, H * Dh)
        o = o @ a["wo"]
        if cfg.use_bias:
            o = o + a["bo"]
        o = o.astype(x.dtype)
        if use_drop:
            o = _dropout(o, k_attn, cfg.dropout)
        return o

    def _attn_block(self, lp, x, k_attn, cos, sin, batch_ax, use_drop):
        x = close(self.config, x,
                  self._attn_out(lp, x, k_attn, cos, sin, batch_ax, use_drop),
                  lp.get("attn_post_norm", {}).get("scale"))
        return constrain(x, self.mesh, batch_ax, "sp", None)

    def _mlp_block(self, lp, x, k_mlp, batch_ax, use_drop):
        cfg = self.config
        mesh = self.mesh
        h = model_norm(cfg, x, lp["mlp_norm"], mesh)
        if cfg.fp32_residual:
            h = h.astype(jax.tree.leaves(lp["mlp"])[0].dtype)
        if cfg.is_moe:
            from deepspeed_tpu.moe.sharded_moe import moe_mlp
            # split: the RTS permutation and the dropout mask below must not
            # consume the same key
            k_rts = None
            if k_mlp is not None:
                k_rts, k_mlp = jax.random.split(k_mlp)
            mlp_out, aux = moe_mlp(lp["mlp"], h, cfg, mesh, rng=k_rts)
        else:
            act = activation_fn(cfg.activation)
            m = lp["mlp"]
            up = h @ m["w_up"]
            if cfg.has_mlp_bias:
                up = up + m["b_up"]
            if cfg.glu:
                gate = h @ m["w_gate"]
                if cfg.has_mlp_bias:
                    gate = gate + m["b_gate"]
                gated = act(gate) * up
            else:
                gated = act(up)
            mlp_out = gated @ m["w_down"]
            if cfg.has_mlp_bias:
                mlp_out = mlp_out + m["b_down"]
            aux = jnp.zeros((), jnp.float32)
        mlp_out = mlp_out.astype(x.dtype)
        if use_drop:
            mlp_out = _dropout(mlp_out, k_mlp, cfg.dropout)
        x = close(cfg, x, mlp_out, lp.get("mlp_post_norm", {}).get("scale"))
        return constrain(x, mesh, batch_ax, "sp", None), aux

    def _layer(self, lp, x, key, cos, sin, batch_ax, use_drop):
        k_attn, k_mlp = (jax.random.split(key) if use_drop else (None, None))
        if self.config.parallel_residual:
            # gpt-neox/pythia: both sub-blocks read the LAYER INPUT
            attn_o = self._attn_out(lp, x, k_attn, cos, sin, batch_ax,
                                    use_drop)
            mlp_y, aux = self._mlp_block(lp, x, k_mlp, batch_ax, use_drop)
            # _mlp_block returns x + mlp(ln2(x)); add the attention branch
            x = mlp_y + attn_o
            return constrain(x, self.mesh, batch_ax, "sp", None), aux
        x = self._attn_block(lp, x, k_attn, cos, sin, batch_ax, use_drop)
        return self._mlp_block(lp, x, k_mlp, batch_ax, use_drop)

    def apply(self, params, tokens, labels=None, rngs=None, loss_mask=None,
              exit_distribution: bool = False):
        """``exit_distribution`` (a looped stack with ``loop_exit_gate``):
        return (logits, p [B, S, total_ut_steps]), the distribution over the
        pass at which each token would leave (:func:`exit_probabilities`)."""
        cfg = self.config
        mesh = self.mesh
        batch_ax = _BATCH_AX
        if cfg.is_afmoe:
            return self._apply_afmoe(params, tokens, labels)
        if exit_distribution and not cfg.loop_exit_gate:
            raise ValueError("exit_distribution needs loop_exit_gate")
        if cfg.is_looped and (labels is not None or cfg.param_offload
                              or not cfg.scan_layers):
            raise NotImplementedError(
                "a looped stack (total_ut_steps > 1) is served only: its "
                "training loss (an expected loss over the exit distribution "
                "with an entropy term) is not built, and its pass loop is a "
                "scan around the scan over stacked layers (scan_layers, no "
                "param_offload)")
        if cfg.param_offload:
            # ZeRO-Infinity param tiering: non-layer params come over once
            # here; scanned layer weights stream per-layer inside the scan
            # body (bounded device window; XLA's latency-hiding scheduler
            # overlaps the copies with the previous layer's compute).  The
            # engine injects the runtime PartitionSpecs (set_param_offload_specs)
            # because the SPMD partitioner requires memory-space moves to
            # carry explicit shardings on multi-device meshes.
            specs = getattr(self, "_offload_specs", None)
            from deepspeed_tpu.accelerator.real_accelerator import \
                supports_pinned_host

            if supports_pinned_host():
                def to_dev(t, spec_t):
                    def put(a, s):
                        if s is None or mesh is None or mesh.empty:
                            return jax.device_put(a, jax.memory.Space.Device)
                        from jax.sharding import NamedSharding
                        return jax.device_put(
                            a, NamedSharding(mesh, s, memory_kind="device"))
                    if spec_t is None:
                        return jax.tree.map(lambda a: put(a, None), t)
                    return jax.tree.map(put, t, spec_t)
            else:
                # capability-gated fallback: one memory space on this
                # backend (CPU advertises only unpinned_host), so there is
                # nothing to stream across — the in-jit memory-space move
                # would be rejected at lowering
                def to_dev(t, spec_t):
                    return t

            self._offload_to_dev = to_dev
            params = {**params,
                      "embed": to_dev(params["embed"],
                                      specs["embed"] if specs else None),
                      "final_norm": to_dev(params["final_norm"],
                                           specs["final_norm"] if specs else None)}
            if "lm_head" in params:
                params["lm_head"] = to_dev(params["lm_head"],
                                           specs["lm_head"] if specs else None)
        tokens = constrain(tokens, mesh, batch_ax, "sp")
        x = jnp.take(params["embed"]["tok"], tokens, axis=0)
        if cfg.position == "learned":
            S = tokens.shape[1]
            x = x + params["embed"]["pos"][:S][None]
        if cfg.embed_norm:  # bloom word_embeddings_layernorm
            x = norm(x, params["embed"]["norm"], "layernorm", cfg.norm_eps,
                     mesh)
        x = constrain(x, mesh, batch_ax, "sp", None)
        w_dtype = x.dtype
        if cfg.fp32_residual:
            x = x.astype(jnp.float32)

        if cfg.position == "rope":
            cos, sin = rope_cache(tokens.shape[1], rope_dim(cfg), cfg.rope_theta)
            cos, sin = cos.astype(w_dtype), sin.astype(w_dtype)
        else:
            cos = sin = jnp.zeros((), x.dtype)

        drop_rng = rngs.get("dropout") if isinstance(rngs, dict) else rngs
        use_drop = cfg.dropout > 0 and drop_rng is not None
        keys = (jax.random.split(drop_rng, cfg.num_layers) if use_drop
                else jnp.zeros((cfg.num_layers,), jnp.uint32))

        body = functools.partial(self._layer, cos=cos, sin=sin, batch_ax=batch_ax,
                                 use_drop=use_drop)
        if cfg.remat:
            # "dots" saves matmul outputs and recomputes only the cheap
            # elementwise chain — a middle point between full remat (+1/3
            # FLOPs) and no remat (full activation residency).  Measured on
            # v5e: also saving the flash-attention output does NOT pay — the
            # custom_vjp still recomputes its forward for the lse residual,
            # so the extra residency only adds memory pressure.
            # "mlp_only" leaves the attention sub-block out of the remat
            # region entirely (its residuals persist; the flash kernel never
            # re-runs) and fully remats the MLP half — the fastest policy on
            # v5e when activations fit.
            if (cfg.remat_policy in ("mlp_only", "mlp_dots")
                    and not cfg.parallel_residual):
                # (parallel-residual layers have no post-attention stream to
                # split the remat around; they fall through to whole-layer
                # policies below)
                mlp_policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                              if cfg.remat_policy == "mlp_dots" else None)

                def body(lp, x, key, _self=self):
                    k_attn, k_mlp = (jax.random.split(key) if use_drop
                                     else (None, None))
                    x = _self._attn_block(lp, x, k_attn, cos, sin, batch_ax,
                                          use_drop)
                    mlp = jax.checkpoint(
                        functools.partial(_self._mlp_block, batch_ax=batch_ax,
                                          use_drop=use_drop),
                        prevent_cse=False, policy=mlp_policy)
                    return mlp(lp, x, k_mlp)
            else:
                if cfg.remat_policy == "offload_dots":
                    # cpu_checkpointing: saved matmul outputs page to pinned
                    # host memory and stream back in backward — activations
                    # stop occupying HBM between fwd and bwd (reference
                    # activation_checkpointing cpu_checkpointing semantics).
                    # The CPU backend cannot execute the placement custom
                    # call inside sharded programs; residuals stay saved
                    # on-"device" there (same memory on CPU anyway).
                    if jax.default_backend() == "cpu":
                        from deepspeed_tpu.utils.logging import logger as _lg

                        _lg.warning("cpu_checkpointing: offloaded residuals "
                                    "unsupported on the CPU backend; saving "
                                    "dots without the host memory-space move")
                        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                    else:
                        policy = jax.checkpoint_policies.offload_dot_with_no_batch_dims(
                            "device", "pinned_host")
                elif cfg.remat_policy == "dots":
                    policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                elif (cfg.remat_policy in ("mlp_only", "mlp_dots")
                      and cfg.parallel_residual):
                    # no post-attention stream to split around: degrade to
                    # whole-layer saved-dots, and say so
                    from deepspeed_tpu.utils.logging import logger as _lg

                    _lg.warning(
                        "remat_policy=%r has no mlp-scoped form for parallel-"
                        "residual layers; using whole-layer 'dots' instead",
                        cfg.remat_policy)
                    policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                else:
                    policy = None
                body = jax.checkpoint(body, prevent_cse=False, policy=policy)
        pp = axis_size(mesh, "pp") if mesh is not None and not mesh.empty else 1

        if cfg.param_offload:
            specs = getattr(self, "_offload_specs", None)
            layer_specs = (jax.tree.map(lambda s: P(*tuple(s)[1:]),
                                        specs["layers"]) if specs else None)

        def scan_body(carry, xs):
            lp, key = xs
            if cfg.param_offload:  # stream this layer's weights to device
                lp = self._offload_to_dev(lp, layer_specs)
            y, aux = body(lp, carry, key)
            return y, aux

        if pp > 1:
            if cfg.is_looped:
                raise NotImplementedError(
                    "a looped stack (total_ut_steps > 1) with pp > 1: the "
                    "stream would pass the ring of stages once a pass, which "
                    "runtime/pipe/spmd.py does not build")
            if not cfg.scan_layers:
                raise ValueError("pipeline parallelism requires scan_layers=True "
                                 "(stacked layer params)")
            from deepspeed_tpu.runtime.pipe.spmd import spmd_pipeline

            def stage_fn(wl, xmb, keys_l, cos, sin):
                y, auxes = jax.lax.scan(
                    lambda c, xs: scan_body(c, xs), xmb, (wl, keys_l))
                return y, jnp.sum(auxes)

            if labels is not None:
                # loss-in-pipeline: the last stage folds each finished
                # microbatch straight into CE sums — the O(global-batch)
                # replicated hidden-state buffer never exists
                head_pp = (params["embed"]["tok"].T if cfg.tie_embeddings
                           else params["lm_head"])
                # the consts tuple only grows a bias entry when the model
                # has one (static): no zeros-add over the fp32 logits —
                # the largest loss-tail tensor — for bias-free models
                hb_pp = ((params["lm_head_bias"],) if cfg.lm_head_bias
                         else ())
                mask_arg = (loss_mask if loss_mask is not None
                            else jnp.ones(labels.shape, jnp.int32))
                has_mask = loss_mask is not None

                # Uneven global batch: the loss-in-pipeline schedules need
                # B % M == 0, so pad to the next multiple with rows the CE
                # mask drops (label -1, mask 0, zero embedding) — exact
                # loss and gradients, because pad rows contribute zero to
                # both the nll sum and the token count.
                M_eff = cfg.pp_microbatches or pp
                pad_rows = (-x.shape[0]) % M_eff
                if pad_rows:
                    x = jnp.concatenate(
                        [x, jnp.zeros((pad_rows,) + x.shape[1:], x.dtype)])
                    labels = jnp.concatenate(
                        [labels, jnp.full((pad_rows,) + labels.shape[1:],
                                          -1, labels.dtype)])
                    mask_arg = jnp.concatenate(
                        [mask_arg,
                         jnp.zeros((pad_rows,) + mask_arg.shape[1:],
                                   mask_arg.dtype)])

                def reduce_mb(y_mb, r_xs, consts):
                    # dense CE over one microbatch (small by construction);
                    # blockwise CE's checkpoint+scan trips XLA CHECKs under
                    # the partial-manual region on CPU (jax 0.9)
                    lab_mb, m_mb = r_xs
                    fnorm_c, head_c, *hb_c = consts
                    h = norm(y_mb, fnorm_c, cfg.norm, cfg.norm_eps)
                    logits = (h[:, :-1] @ head_c.astype(h.dtype)
                              ).astype(jnp.float32)
                    if hb_c:
                        logits = logits + hb_c[0].astype(jnp.float32)
                    lab = lab_mb[:, 1:]
                    lse = jax.scipy.special.logsumexp(logits, axis=-1)
                    # one-hot contraction, not take_along_axis: XLA's SPMD
                    # partitioner CHECK-crashes partitioning that gather
                    # under the partial-manual pp region (jax 0.9)
                    gold = jnp.einsum(
                        "bsv,bsv->bs", logits,
                        jax.nn.one_hot(jnp.maximum(lab, 0), logits.shape[-1],
                                       dtype=logits.dtype))
                    nll = lse - gold
                    if cfg.z_loss:
                        nll = nll + cfg.z_loss * lse ** 2
                    valid = lab >= 0
                    if has_mask:
                        valid = valid & (m_mb[:, 1:] > 0)
                    return {"nll": jnp.where(valid, nll, 0.0).sum(),
                            "cnt": valid.sum().astype(jnp.float32)}

                if cfg.pp_schedule == "1f1b":
                    # token count is data-only, so it can divide each
                    # microbatch's contribution BEFORE the pipeline — the
                    # fused schedule needs additive per-microbatch scalars
                    from deepspeed_tpu.runtime.pipe.spmd import \
                        spmd_pipeline_1f1b

                    valid_all = labels[:, 1:] >= 0
                    if has_mask:
                        valid_all = valid_all & (mask_arg[:, 1:] > 0)
                    cnt = jnp.maximum(valid_all.sum().astype(jnp.float32),
                                      1.0)

                    def loss_mb(y_mb, r_xs, consts):
                        *red_c, cnt_c = consts
                        d = reduce_mb(y_mb, r_xs, tuple(red_c))
                        return d["nll"] / cnt_c

                    return spmd_pipeline_1f1b(
                        stage_fn, loss_mb, params["layers"], x, mesh,
                        num_microbatches=cfg.pp_microbatches,
                        broadcast_args=(cos, sin), scan_args=keys,
                        loss_xs=(labels, mask_arg),
                        loss_consts=(params["final_norm"], head_pp) + hb_pp
                        + (cnt,),
                        aux_coef=(cfg.moe_aux_loss_coef if cfg.is_moe
                                  else 0.0),
                        quantize_boundary=cfg.pp_boundary_q,
                        quant_block=cfg.comm_quant_block,
                        comm_record=cfg.pp_comm_record)

                # When the model remats per layer (cfg.remat), the scan's
                # per-step residuals are already bounded by the tuned layer
                # policy — an outer save-nothing wrap would override it.
                # Only un-rematted models take the pipeline's own stage remat.
                red, aux_loss = spmd_pipeline(
                    stage_fn, params["layers"], x, mesh,
                    num_microbatches=cfg.pp_microbatches,
                    broadcast_args=(cos, sin), scan_args=keys,
                    reduce_fn=reduce_mb, reduce_xs=(labels, mask_arg),
                    reduce_consts=(params["final_norm"], head_pp) + hb_pp,
                    remat_stage=not bool(cfg.remat),
                    quantize_boundary=cfg.pp_boundary_q,
                    quant_block=cfg.comm_quant_block,
                    comm_record=cfg.pp_comm_record)
                loss = red["nll"] / jnp.maximum(red["cnt"], 1.0)
                return (loss + cfg.moe_aux_loss_coef * aux_loss
                        if cfg.is_moe else loss)

            x, aux_loss = spmd_pipeline(stage_fn, params["layers"], x, mesh,
                                        num_microbatches=cfg.pp_microbatches,
                                        broadcast_args=(cos, sin), scan_args=keys,
                                        remat_stage=not bool(cfg.remat),
                                        quantize_boundary=cfg.pp_boundary_q,
                                        quant_block=cfg.comm_quant_block,
                                        comm_record=cfg.pp_comm_record)
        elif cfg.is_looped:
            # the SAME stacked weights in every pass; the final norm closes
            # each pass and feeds the next, and the gate reads what it left
            def one_pass(x, _):
                x, _ = jax.lax.scan(scan_body, x, (params["layers"], keys))
                x = model_norm(cfg, x, params["final_norm"], mesh)
                return x, (_exit_gate(params["exit_gate"], x)
                           if exit_distribution else None)

            x, gates = jax.lax.scan(one_pass, x, None,
                                    length=cfg.total_ut_steps)
        elif cfg.scan_layers:
            x, auxes = jax.lax.scan(scan_body, x, (params["layers"], keys))
            aux_loss = jnp.sum(auxes)
        elif cfg.param_offload:
            # unrolled layers with host-tiered params: to_dev IS the
            # prefetch hook — layer i+1's host->device move is emitted
            # tied (optimization_barrier) to layer i's INPUT, so XLA may
            # run the copy concurrent with layer i's matmuls but cannot
            # hoist the whole stacked tree to the program head (the PR 6
            # barrier-tied bucket idiom applied to the memory tier;
            # double-buffered: at most two layers' params are in flight)
            aux_loss = jnp.zeros((), jnp.float32)
            lspecs = (jax.tree.map(lambda s: P(*tuple(s)[1:]),
                                   getattr(self, "_offload_specs",
                                           {}).get("layers"))
                      if getattr(self, "_offload_specs", None) else None)
            nxt = self._offload_to_dev(
                jax.tree.map(lambda a: a[0], params["layers"]), lspecs)
            for i in range(cfg.num_layers):
                lp = nxt
                if i + 1 < cfg.num_layers:
                    sl = jax.tree.map(lambda a: a[i + 1], params["layers"])
                    x, sl = jax.lax.optimization_barrier((x, sl))
                    nxt = self._offload_to_dev(sl, lspecs)
                x, aux = body(lp, x, keys[i])
                aux_loss = aux_loss + aux
        else:
            aux_loss = jnp.zeros((), jnp.float32)
            for i in range(cfg.num_layers):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                x, aux = body(lp, x, keys[i])
                aux_loss = aux_loss + aux

        if labels is None:
            if not cfg.is_looped:     # a pass loop has normed its last pass
                x = model_norm(cfg, x, params["final_norm"], mesh)
            head = (params["embed"]["tok"].T if cfg.tie_embeddings
                    else params["lm_head"]).astype(x.dtype)
            logits = x @ head
            if cfg.lm_head_bias:
                logits = logits + params["lm_head_bias"].astype(logits.dtype)
            logits = constrain(logits, mesh, batch_ax, "sp", "tp")
            if exit_distribution:
                return logits, exit_probabilities(gates)
            return logits
        head = (params["embed"]["tok"].T if cfg.tie_embeddings
                else params["lm_head"])
        loss = self._loss_tail(params["final_norm"], head, x, labels, loss_mask,
                               head_bias=params.get("lm_head_bias"))
        return loss + cfg.moe_aux_loss_coef * aux_loss if cfg.is_moe else loss

    def _apply_afmoe(self, params, tokens, labels):
        """``apply`` for ``layer_types`` (models/afmoe.py, or its sibling
        models/kda_mla.py): logits only.  A chip's share of the experts and
        of the vocabulary has no loss to train on (the other ranks' logits
        are absent from its softmax)."""
        from deepspeed_tpu.models import afmoe

        cfg = self.config
        if labels is not None:
            raise NotImplementedError(
                "the training loss of a layer_types model (models/afmoe.py, "
                "models/kda_mla.py) is not built: the chip holds a share of "
                "the experts and of the vocabulary, and CausalLM._loss_tail "
                "knows neither; such a model is served only")
        x = afmoe.embed(cfg, params["embed"]["tok"], tokens,
                        params["embed"]["tok"].dtype)
        x = afmoe.form(cfg).apply_layers(cfg, params, x, self.mesh)
        x = model_norm(cfg, x, params["final_norm"], self.mesh)
        head = (params["embed"]["tok"].T if cfg.tie_embeddings
                else params["lm_head"])
        return x @ head.astype(x.dtype)

    def _loss_tail(self, fnorm, head, x, labels, loss_mask, head_bias=None):
        """Final norm + LM cross-entropy — the single implementation behind
        both ``apply`` and the streamed head segment (their numerical parity
        is load-bearing for the offload tests).  ``head`` is [D, V].

        Next-token objective (HF CausalLM convention: shift inside when
        labels == input_ids): logits[t] predicts labels[t+1].  Every one of
        the S positions stays (the last takes the ignore label), so each
        shard of the data axes keeps its own B_local x S rows.

        The head is a gathered weight like any layer's: whatever dimension
        ZeRO-3 sharded it on over the data axes, it is replicated over them
        here (after the cast, so the gather moves the compute dtype) and the
        tokens stay where they are.  Only ``tp`` keeps its split of the
        vocabulary."""
        cfg = self.config
        mesh = self.mesh
        if cfg.num_pred_heads > 1:
            raise NotImplementedError(
                f"the training loss over num_pred_heads={cfg.num_pred_heads} "
                "output heads (head p against the token p + 1 ahead) is not "
                "built: CausalLM._loss_tail knows one next-token head; such "
                "a model is served only")
        h = model_norm(cfg, x, fnorm, mesh)
        head = constrain(head.astype(h.dtype), mesh, None, "tp")
        if loss_mask is not None:
            labels = jnp.where(loss_mask > 0, labels, -100)
        # the shift crosses the shards of "sp": it is done on whole rows of
        # ints, which is how a batch arrives (split over the data axes only)
        labels = constrain(labels, mesh, _BATCH_AX, None)
        targets = constrain(jnp.concatenate(
            [labels[:, 1:], jnp.full_like(labels[:, :1], -100)], axis=1),
            mesh, _BATCH_AX, None)
        B, S, _ = h.shape
        chunk = cfg.ce_chunk
        if chunk is None:  # auto: chunk when the fp32 logits would be >2^28 elts
            chunk = 2048 if B * S * cfg.vocab_size > (1 << 28) else 0
        if chunk:
            return blockwise_cross_entropy(h, head, targets, chunk=chunk,
                                           z_loss=cfg.z_loss,
                                           head_bias=head_bias, mesh=mesh)
        logits = h @ head
        if head_bias is not None:
            logits = logits + head_bias.astype(logits.dtype)
        logits = constrain(logits, mesh, _BATCH_AX, "sp", "tp")
        return cross_entropy(logits, targets, z_loss=cfg.z_loss)

    # flax-style call-through so `model.apply(params, batch...)` also accepts
    # dict batches via engine's kwargs path
    def __call__(self, params, *args, **kwargs):
        return self.apply(params, *args, **kwargs)

    # ------------------------------------------------------------------
    # streamed per-layer segments (ZeRO-Infinity grad streaming)
    # ------------------------------------------------------------------
    def stream_segments(self):
        """Pure per-segment functions for the engine's streamed fwd/bwd driver
        (``runtime/zero/stream_grad.py``).  The reference's ZeRO-Infinity
        streams params *and* grads per layer (``(R)
        runtime/swap_tensor/partitioned_param_swapper.py`` role); these
        segments let the engine run one layer at a time so no [model]-sized
        buffer — params or grads — ever exists on device.

        Returns None when the model cannot be segment-streamed (pipeline
        parallelism owns the layer loop there).
        """
        cfg = self.config
        mesh = self.mesh
        if mesh is not None and not mesh.empty and axis_size(mesh, "pp") > 1:
            return None
        batch_ax = _BATCH_AX

        def embed_fwd(embed, tokens):
            toks = constrain(tokens, mesh, batch_ax, "sp")
            x = jnp.take(embed["tok"], toks, axis=0)
            if cfg.position == "learned":
                x = x + embed["pos"][: toks.shape[1]][None]
            if cfg.embed_norm:
                x = norm(x, embed["norm"], "layernorm", cfg.norm_eps, mesh)
            return constrain(x, mesh, batch_ax, "sp", None)

        def layer_fwd(lp, x, key, cos, sin, use_drop):
            return self._layer(lp, x, key, cos, sin, batch_ax, use_drop)

        def head_loss(head_tree, x, labels, loss_mask):
            head = head_tree["head"]
            if cfg.tie_embeddings:  # head passed as the [V, D] tok table
                head = head.T
            return self._loss_tail(head_tree["final_norm"], head, x, labels,
                                   loss_mask,
                                   head_bias=head_tree.get("head_bias"))

        def rope(S, dtype):
            if cfg.position != "rope":
                return jnp.zeros((), dtype), jnp.zeros((), dtype)
            cos, sin = rope_cache(S, rope_dim(cfg), cfg.rope_theta)
            return cos.astype(dtype), sin.astype(dtype)

        return {
            "num_layers": cfg.num_layers,
            "dropout": cfg.dropout,
            "moe_coef": cfg.moe_aux_loss_coef if cfg.is_moe else 0.0,
            "tied": cfg.tie_embeddings,
            "embed_fwd": embed_fwd,
            "layer_fwd": layer_fwd,
            "head_loss": head_loss,
            "rope": rope,
        }


def _exit_gate(gate, x):
    """sigmoid(x w + b) in float32: [..., D] -> [...]."""
    return jax.nn.sigmoid(
        jnp.dot(x.astype(jnp.float32), gate["w"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)[..., 0]
        + gate["b"].astype(jnp.float32)[0])


def exit_probabilities(gates):
    """The passes' gates ``g`` [T, ...] -> p [..., T], the probability that a
    token leaves after pass t: ``p_t = g_t prod_{s<t} (1 - g_s)`` for
    ``t < T - 1`` and the rest of the mass at ``T - 1``.  A token leaves at
    the first pass whose cumulated p reaches ``early_exit_threshold``; at
    the only threshold built, 1, that is the last."""
    stay = jnp.cumprod(1.0 - gates[:-1], axis=0)           # prod_{s<=t}
    before = jnp.concatenate([jnp.ones_like(gates[:1]), stay[:-1]], axis=0)
    p = jnp.concatenate([gates[:-1] * before, stay[-1:]], axis=0)
    return jnp.moveaxis(p, 0, -1)


def _dropout(x, key, rate: float):
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), jnp.zeros((), x.dtype))


def cross_entropy(logits, labels, z_loss: float = 0.0, mask=None):
    """Token-level CE in fp32; ignore_index=-100 (HF convention)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                               axis=-1).squeeze(-1)
    nll = lse - gold
    if z_loss:
        nll = nll + z_loss * lse ** 2
    valid = labels >= 0
    if mask is not None:
        valid = valid & (mask > 0)
    nll = jnp.where(valid, nll, 0.0)
    return nll.sum() / jnp.maximum(valid.sum(), 1)


def blockwise_cross_entropy(x, head, labels, chunk: int, z_loss: float = 0.0,
                            mask=None, return_sums: bool = False,
                            head_bias=None, mesh: Optional[Mesh] = None):
    """LM loss without materializing the full [B, S, V] logits.

    The reference's fused-softmax CUDA kernels attack the same bandwidth
    problem from below (SURVEY.md §2.2 "Transformer training kernels"); on TPU
    the winning shape is blockwise: scan over token chunks, each producing a
    [chunk, V] logits block (one MXU matmul) reduced to per-token nll in fp32,
    with ``jax.checkpoint`` so the backward pass recomputes the block instead
    of saving it.  Peak logits memory drops from O(B·S·V) to O(chunk·V) while
    the matmuls stay MXU-sized.

    Under ``mesh`` every shard of the activation layout (batch over the data
    axes, sequence over ``sp``) chunks its OWN rows: a scan step takes
    ``chunk`` rows from each shard (``[shards, chunk, ...]``), so ``chunk`` is
    the rows of logits one chip holds at a time, the loop dimension is
    unsharded and no block of rows or logits ever crosses chips.  ``head``
    arrives as the caller placed it (``CausalLM._loss_tail`` gathers it over
    the data axes)."""
    B, S, D = x.shape
    if mask is not None:
        labels = jnp.where(mask > 0, labels, -100)
    spec = _mesh_spec(x, mesh, _BATCH_AX, "sp", None)
    b_ax, s_ax = (_axes(e) for e in (spec or (None, None))[:2])
    nb, ns = (math.prod(axis_size(mesh, a) for a in ax) for ax in (b_ax, s_ax))
    rows, shards = b_ax + s_ax, nb * ns
    n_local = (B // nb) * (S // ns)
    n_blocks = -(-n_local // chunk)

    def lay_out(a, fill):
        """[B, S, ...] -> [n_blocks, shards, chunk, ...]: each shard's rows
        flattened and padded apart from the others', the loop dimension
        first, the shard dimension split over ``rows`` as ``a`` was."""
        tail = a.shape[2:]
        none = (None,) * len(tail)
        a = a.reshape(nb, B // nb, ns, S // ns, *tail)
        a = constrain(a, mesh, b_ax, None, s_ax, None, *none)
        a = jnp.moveaxis(a, 2, 1).reshape(shards, n_local, *tail)
        a = jnp.pad(a, ((0, 0), (0, n_blocks * chunk - n_local))
                    + ((0, 0),) * len(tail), constant_values=fill)
        a = jnp.moveaxis(a.reshape(shards, n_blocks, chunk, *tail), 1, 0)
        return constrain(a, mesh, None, rows, None, *none)

    # One copy of the head a shard, as the batch dimension of the block's
    # matmul: its gradient then accumulates where it is computed, and the
    # chips sum it once after the loop (the transpose of this broadcast), not
    # in every step.
    head = constrain(jnp.broadcast_to(head, (shards, *head.shape)), mesh,
                     rows, None, "tp")

    @jax.checkpoint
    def block(carry, args):
        xc, lc = args                                  # [shards, chunk, ...]
        # the logits as ONE [rows, V] block (a chip's [chunk, V]): with the
        # shard dimension kept, XLA leaves the float32 cast and the row max
        # out of the matmul's fusion (+6 ms a step in gpt2-xl.train-zero3)
        logits = jnp.einsum("rcd,rdv->rcv", xc, head).astype(jnp.float32)
        logits = logits.reshape(shards * chunk, -1)
        if head_bias is not None:
            logits = logits + head_bias.astype(jnp.float32)
        logits = constrain(logits, mesh, rows, "tp")
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lc, 0).reshape(-1, 1), axis=-1).squeeze(-1)
        nll = lse - gold
        if z_loss:
            nll = nll + z_loss * lse ** 2
        nll = jnp.where(lc >= 0, nll.reshape(shards, chunk), 0.0)
        tot, cnt = carry  # one partial sum a shard: the chips meet once, below
        return (tot + nll.sum(1), cnt + (lc >= 0).sum(1)), None

    init = (constrain(jnp.zeros((shards,), jnp.float32), mesh, rows),
            constrain(jnp.zeros((shards,), jnp.int32), mesh, rows))
    (tot, cnt), _ = jax.lax.scan(block, init,
                                 (lay_out(x, 0), lay_out(labels, -100)))
    tot, cnt = tot.sum(), cnt.sum()
    if return_sums:
        return tot, cnt
    return tot / jnp.maximum(cnt, 1)


def causal_lm(preset: str, mesh: Optional[Mesh] = None, **overrides) -> CausalLM:
    return CausalLM(get_model_config(preset, **overrides), mesh=mesh)
