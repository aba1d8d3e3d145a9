"""Kernel-injected decode path: fused per-layer Pallas kernels at s=1.

The TPU-native form of the reference's ``replace_with_kernel_inject``
machinery (``(R) module_inject/replace_module.py`` swapping HF blocks for
``DeepSpeedTransformerInference`` with fused QKV weights and the
``csrc/transformer/inference`` kernels; SURVEY.md §3.5): instead of swapping
modules, :func:`inject_decode_params` re-lays the weights for the fused
kernels (QKV concatenated into one [D, N] matmul per layer — the reference's
fused-QKV transform), and :func:`decode_step` runs a single token through
four kernel launches per layer (``ops/pallas/decode.py``) instead of the
~25-op unfused HLO chain.  A mixture-of-experts model keeps four of the five
kernels and swaps ``fused_mlp`` for ``fused_moe_mlp``, which reads the
model's own stacked expert arrays: those are never laid out a second time.

Prefill keeps the standard :func:`~deepspeed_tpu.models.decoding.
forward_with_cache` path (it is matmul-bound, already MXU-shaped); only the
launch-bound s=1 loop uses the injected weights.  Both share the same KV
cache layout, so a generation prefills on the plain tree and decodes on the
injected one.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import afmoe
from deepspeed_tpu.models.decoding import output_logits
from deepspeed_tpu.models.layers import model_norm, norm, qk_norm, rope_dim
from deepspeed_tpu.ops.pallas import rope_angles
from deepspeed_tpu.ops.pallas.decode import (eva_decode_paged,
                                             eva_summarize_paged,
                                             flash_decode, fused_mlp,
                                             fused_moe_mlp, fused_norm_qkv,
                                             fused_proj_norm, paged_kv_append)

EXPERT_WEIGHTS = ("w_up", "w_gate", "w_down")


def supports_fused_decode(cfg, *, quantized_kv: bool = False,
                          tp: int = 1) -> bool:
    """The fused path covers the model zoo, dense and mixture-of-experts,
    including int8 weights (dequant in-kernel; expert weights are never
    quantized, ``models/quant.py``); int8 KV caches and tp>1 fall back to
    the reference-shaped loop.

    What a cache page holds on this path depends on the attention form.
    ``attention="full"``: the K and V rows of ``page`` consecutive positions,
    for ever (``flash_decode`` masks by one length a row).  ``"eva"``, paged
    pool only: a WINDOW page holds rows ``p % W`` of the current window and
    is overwritten in place by the next one; a SUMMARY page holds ``page``
    pooled chunk summaries, ``ktilde`` in K and ``vtilde`` in V
    (``eva_decode_paged`` masks by two lengths a row, ``eva_summarize_paged``
    fills the summary rows when a step fills a window)."""
    return (not quantized_kv
            and tp == 1 and cfg.position in ("rope", "learned", "alibi"))


def inject_decode_params(params: Any, cfg) -> Dict[str, Any]:
    """Build the kernel-injected weight view from a model param tree.

    Layers are UNSTACKED into a tuple of per-layer dicts (``num_layers`` of
    them: a looped stack's passes share them) with their own device
    buffers: the decode step's static layer loop then feeds each
    Pallas kernel a whole array — profiling showed that slicing a stacked
    [L, ...] weight per layer inside the program re-materializes the
    slice (a full per-layer weight copy per token).  The QKV concat is the
    reference's fused-QKV injection transform.

    Expert weights are the exception: ``fused_moe_mlp`` indexes the STACKED
    [L, E, ...] arrays at a static layer offset inside its index maps (as
    ``flash_decode`` indexes the stacked cache), so ``out["experts"]`` holds
    the caller's own arrays by reference and they stay resident once."""
    from deepspeed_tpu.models.quant import QTensor, is_qtensor

    if cfg.is_afmoe:      # two stacks of layers, its own view
        return afmoe.form(cfg).inject(cfg, params)
    ly = params["layers"]
    attn, mlp = ly["attn"], ly["mlp"]
    if cfg.sandwich_norm and is_qtensor(attn["wq"]):
        raise NotImplementedError(
            "int8 weights with sandwich_norm: the post-norm close of the "
            "fused path (afmoe.fused_close) hands its kernels no scales")
    if is_qtensor(attn["wq"]):  # int8 serving: concat payloads AND scales
        wqkv = QTensor(
            jnp.concatenate([attn["wq"].q, attn["wk"].q, attn["wv"].q], -1),
            jnp.concatenate([attn["wq"].scale, attn["wk"].scale,
                             attn["wv"].scale], -1))
    else:
        wqkv = jnp.concatenate([attn["wq"], attn["wk"], attn["wv"]], axis=-1)
    # a unit-offset norm's gain is 1 + scale, taken in float32 once, here
    gain = ((lambda g: 1.0 + g.astype(jnp.float32))
            if cfg.norm_add_unit_offset else (lambda g: g))
    stacked: Dict[str, Any] = {
        "wqkv": wqkv,
        "wo": attn["wo"],
        "n1_scale": gain(ly["attn_norm"]["scale"]),
        "n2_scale": gain(ly["mlp_norm"]["scale"]),
    }
    if cfg.sandwich_norm:
        stacked["n1_post"] = ly["attn_post_norm"]["scale"]
        stacked["n2_post"] = ly["mlp_post_norm"]["scale"]
    if cfg.is_eva:
        stacked["eva_mu"] = attn["eva_mu"]
        stacked["eva_phi"] = attn["eva_phi"]
    if cfg.is_moe:
        stacked["gate_w"] = mlp["gate_w"]
    else:
        stacked.update({k: mlp[k] for k in EXPERT_WEIGHTS if k in mlp})
    if cfg.qk_norm:
        stacked["q_norm"] = attn["q_norm"]["scale"]
        stacked["k_norm"] = attn["k_norm"]["scale"]
    if cfg.norm == "layernorm":
        stacked["n1_bias"] = ly["attn_norm"]["bias"]
        stacked["n2_bias"] = ly["mlp_norm"]["bias"]
    if cfg.use_bias or cfg.qkv_bias:
        stacked["bqkv"] = jnp.concatenate([attn["bq"], attn["bk"], attn["bv"]],
                                          axis=-1)
    if cfg.use_bias:
        stacked["bo"] = attn["bo"]
    if cfg.has_mlp_bias:
        stacked["b_up"] = mlp["b_up"]
        stacked["b_down"] = mlp["b_down"]
        if cfg.glu:
            stacked["b_gate"] = mlp["b_gate"]
    def unstack(v, l):
        if is_qtensor(v):
            return QTensor(v.q[l], v.scale[l])
        return v[l]

    layers = tuple(
        {k: unstack(v, l) for k, v in stacked.items()}
        for l in range(cfg.num_layers))
    out = {"embed": params["embed"], "final_norm": params["final_norm"],
           "layers": layers}
    if cfg.is_moe:
        out["experts"] = {k: mlp[k] for k in EXPERT_WEIGHTS if k in mlp}
    if not cfg.tie_embeddings:
        out["lm_head"] = params["lm_head"]
    if cfg.lm_head_bias:
        out["lm_head_bias"] = params["lm_head_bias"]
    return out


def moe_combine(h, gate_w, cfg):
    """Router of one layer on rows ``h`` [B, D]: float32 logits, softmax,
    top-k -> (combine [B, E] float32, each row's weight per expert and 0
    where not chosen; chosen [B, E] bool).  ``fused_moe_mlp``'s input."""
    from deepspeed_tpu.moe.sharded_moe import topk_weights

    gates = jax.nn.softmax(
        h.astype(jnp.float32) @ gate_w.astype(jnp.float32), axis=-1)
    weight, idx = topk_weights(gates, cfg.num_experts_per_tok,
                               cfg.moe_norm_topk_prob)
    onehot = jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.float32)
    return (jnp.sum(onehot * weight[..., None], axis=1),
            jnp.sum(onehot, axis=1) > 0)


def moe_counts_zero(cfg):
    """Zeros of ``decode_step``'s routing counts (its ``moe_live`` result)."""
    if cfg.is_afmoe:      # a fourth count: the assignments offered
        return afmoe.form(cfg).moe_counts_zero(cfg)
    return (jnp.zeros((cfg.num_experts,), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))


def decode_step(cfg, dparams, tokens, cache, pos, *,
                page_table=None, moe_live=None, impl: Optional[str] = None):
    """One generation step: ``tokens`` [B, 1] at absolute position ``pos``
    -> (logits [B, num_pred_heads * V] fp32, cache).

    ``pos`` is a traced scalar (static batch: every row at the same depth)
    or an int32 [B] vector of per-row positions (continuous batching: each
    slot sits at its own depth in the paged pool, so ``page_table`` comes
    with it; the flash-decode kernel masks per row).

    ``page_table`` [B, maxp] switches the cache to the paged pool layout
    ([L, num_pages, Hkv, page, Dh], ``serving/paged_kv.py``): appends
    scatter through the table and the flash-decode kernel indirects its
    DMA index map through it.

    ``moe_live`` [B] bool (the rows that are really decoding; one mask, two
    uses) is handed to the attention kernels, whose grid then visits those
    rows only (a row that does not decode gets its ``q`` back and costs no
    page fetch: ``ops/pallas/decode.py:_decode_attention``), and adds a
    third result: a mixture-of-experts model's routing of this step over
    those rows, summed over layers — (assignments per expert [E], (layer,
    expert) pairs with at least one row, the fullest expert's rows summed
    over layers), int32, what ``ds_serve_moe_*`` count — and None for a
    dense model.  Without it every row is visited.

    Four kernel launches per layer: norm+QKV, flash-decode attention,
    out-proj+residual+norm, MLP+residual (ops/pallas/decode.py); the cache
    row appends stay XLA in-place updates (on the donated cache)."""
    B = tokens.shape[0]
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    M, Mkv = H * Dh, Hkv * Dh
    kind, eps = cfg.norm, cfg.norm_eps
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1                  # [B] per-slot depths
    if (page_table is not None) != per_row:
        raise ValueError("per-row positions are the paged KV decode's: pass "
                         "both pos [B] and page_table, or neither")
    if cfg.is_eva and page_table is None:
        raise NotImplementedError(
            "attention='eva' decodes through the paged pool "
            "(serving/paged_kv.py); the contiguous caches hold no window "
            "and summary rows on the fused path")
    if cfg.is_afmoe:
        if page_table is None:
            raise NotImplementedError(
                "a layer_types model (models/afmoe.py) decodes through the "
                "paged pool's two budgets (serving/paged_kv.py)")
        form = afmoe.form(cfg)      # afmoe itself, or models/kda_mla.py
        x = afmoe.embed(cfg, dparams["embed"]["tok"], tokens[:, 0],
                        cache[form.cache_key(cfg)].dtype)
        x, new_cache, moe_stats = form.fused_layers(
            cfg, dparams, x, cache, pos, page_table, moe_live=moe_live,
            impl=impl)
        logits = output_logits(cfg, dparams, x)
        if moe_live is not None:
            return logits, new_cache, moe_stats
        return logits, new_cache
    x = jnp.take(dparams["embed"]["tok"], tokens[:, 0], axis=0)
    if cfg.position == "learned":
        x = x + jnp.take(dparams["embed"]["pos"],
                         pos if per_row else pos[None], axis=0)
    if cfg.embed_norm:  # bloom word_embeddings_layernorm
        x = norm(x, dparams["embed"]["norm"], "layernorm", cfg.norm_eps)
    # the stream between the kernels: the cache's dtype, or float32 where
    # the model keeps its residual there (evabyte); the kernels hand the
    # matmuls the weights' dtype either way
    x = x.astype(jnp.float32 if cfg.fp32_residual else cache["k"].dtype)

    if cfg.position == "rope":
        rd = rope_dim(cfg)
        # scalar: [1, rd/2] broadcast over the batch; per-row: [B, rd/2]
        cos, sin = rope_angles(pos if per_row else pos[None], rd,
                               theta=cfg.rope_theta)
    else:
        cos = sin = None

    def rope_rows(t):
        """[B, Hx, Dh] -> rotate the first rd dims of each head."""
        if cos is None:
            return t
        half = rd // 2
        if per_row:
            c = cos[:, None].astype(jnp.float32)     # [B, 1, rd/2]
            s = sin[:, None].astype(jnp.float32)
        else:
            c = cos[0].astype(jnp.float32)
            s = sin[0].astype(jnp.float32)
        x1 = t[..., :half].astype(jnp.float32)
        x2 = t[..., half:rd].astype(jnp.float32)
        rot = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
        return jnp.concatenate([rot.astype(t.dtype), t[..., rd:]], axis=-1) \
            if rd < t.shape[-1] else rot.astype(t.dtype)

    scale = 1.0 / (Dh ** 0.5)

    # Statically unrolled layer loop over UNSTACKED per-layer weights: a
    # lax.scan (or per-layer slicing of stacked weights) re-materializes a
    # full per-layer weight copy per token — profiled at ~40% of the decode
    # step.  Cache rows update in place on the stacked [L, ...] buffers
    # (donated through the generation loop); flash_decode indexes the
    # stacked cache with a static layer offset, so no cache slice
    # materializes either.
    kc_all, vc_all = cache["k"], cache["v"]
    pos0 = jnp.zeros((), jnp.int32)
    from deepspeed_tpu.models.quant import is_qtensor

    def wq_pair(w):
        """(payload, per-out-channel scale | None) for dense or int8."""
        if is_qtensor(w):
            return w.q, w.scale
        return w, None

    count_moe = cfg.is_moe and moe_live is not None
    moe_stats = moe_counts_zero(cfg) if count_moe else None

    def one_pass(x, kc_all, vc_all, moe_stats, first):
        """The layer bodies once, layer ``l``'s weights over cache layer
        ``first + l``: ``first`` is 0, or a looped stack's traced ``pass *
        num_layers``."""
        for l, lp in enumerate(dparams["layers"]):
            cl = first + l
            wqkv, s_qkv = wq_pair(lp["wqkv"])
            qkv = fused_norm_qkv(x, lp["n1_scale"], lp.get("n1_bias"),
                                 wqkv, lp.get("bqkv"), kind=kind, eps=eps,
                                 wscale=s_qkv, impl=impl)
            q, k = qkv[:, :M], qkv[:, M:M + Mkv]
            if cfg.qk_norm:
                q, k = qk_norm(q, k, lp["q_norm"], lp["k_norm"], eps)
            q = rope_rows(q.reshape(B, H, Dh))
            k = rope_rows(k.reshape(B, Hkv, Dh))
            v = qkv[:, M + Mkv:].reshape(B, Hkv, Dh)
            if cfg.is_eva:
                # window rows are reused in place: position p lives at row
                # p % W of the row's window pages (the table's first W / page)
                W, C = cfg.eva_window, cfg.eva_chunk
                kc_all, vc_all = paged_kv_append(
                    kc_all, vc_all, k, v, pos % W, page_table, layer=cl,
                    impl=impl)
            elif page_table is not None:
                # paged append: row b writes at row pos[b] % page of physical
                # page page_table[b, pos[b] // page] (parked rows' tables
                # point at the junk page 0 — their writes land where no live
                # slot reads)
                kc_all, vc_all = paged_kv_append(
                    kc_all, vc_all, k, v, pos, page_table, layer=cl,
                    impl=impl)
            else:
                kc_all = jax.lax.dynamic_update_slice(
                    kc_all, k[None, :, :, None, :].astype(kc_all.dtype),
                    (cl, pos0, pos0, pos, pos0))
                vc_all = jax.lax.dynamic_update_slice(
                    vc_all, v[None, :, :, None, :].astype(vc_all.dtype),
                    (cl, pos0, pos0, pos, pos0))
            if cfg.is_eva:
                ctx = eva_decode_paged(
                    q, kc_all, vc_all, pos, page_table, layer=cl, window=W,
                    chunk=C, sm_scale=scale, live=moe_live, impl=impl)
                # rows whose step filled their window leave its summaries
                kc_all, vc_all = eva_summarize_paged(
                    kc_all, vc_all, lp["eva_mu"], lp["eva_phi"], pos,
                    page_table, layer=cl, window=W, chunk=C, impl=impl)
            else:
                ctx = flash_decode(q, kc_all, vc_all, pos, sm_scale=scale,
                                   layer=cl, alibi=cfg.position == "alibi",
                                   page_table=page_table, live=moe_live,
                                   impl=impl)
            if cfg.sandwich_norm:
                # a norm between each sub-block and its residual add: the
                # layer form's close, the dense branch of it
                x, _ = afmoe.fused_close(cfg, dparams, lp, l,
                                         ctx.reshape(B, M), x, None, None,
                                         impl)
                continue
            wo, s_wo = wq_pair(lp["wo"])
            r, h = fused_proj_norm(
                ctx.reshape(B, M), x, wo, lp.get("bo"), lp["n2_scale"],
                lp.get("n2_bias"), kind=kind, eps=eps,
                parallel=cfg.parallel_residual, wscale=s_wo, impl=impl)
            if cfg.is_moe:
                combine, chosen = moe_combine(h, lp["gate_w"], cfg)
                ex = dparams["experts"]
                x = fused_moe_mlp(h, r, combine, ex["w_up"], ex["w_down"],
                                  ex.get("w_gate"), layer=l,
                                  act=cfg.activation, live=moe_live,
                                  impl=impl)
                if count_moe:
                    load = jnp.sum(chosen & moe_live[:, None], axis=0,
                                   dtype=jnp.int32)
                    moe_stats = (
                        moe_stats[0] + load,
                        moe_stats[1] + jnp.sum(load > 0, dtype=jnp.int32),
                        moe_stats[2] + jnp.max(load))
            else:
                wu, su = wq_pair(lp["w_up"])
                wd, sd = wq_pair(lp["w_down"])
                wg, sg = (wq_pair(lp["w_gate"]) if "w_gate" in lp
                          else (None, None))
                wscales = (su, sg, sd) if su is not None else None
                x = fused_mlp(h, r, wu, wd, wg, lp.get("b_up"),
                              lp.get("b_gate"), lp.get("b_down"),
                              act=cfg.activation, wscales=wscales, impl=impl)
        return x, kc_all, vc_all, moe_stats

    if cfg.is_looped:
        # ONE trace of the layer bodies inside a rolled loop over the passes:
        # the per-layer weights are loop-invariant operands (never a slice of
        # a stacked array, see above), the pool is the carry, updated in
        # place, and the final norm closes every pass
        def loop_pass(t, carry):
            with jax.named_scope("ds_loop_pass"):
                x, kc, vc, _ = one_pass(*carry, None, t * cfg.num_layers)
                return model_norm(cfg, x, dparams["final_norm"]), kc, vc

        x, kc_all, vc_all = jax.lax.fori_loop(
            0, cfg.total_ut_steps, loop_pass, (x, kc_all, vc_all))
    else:
        x, kc_all, vc_all, moe_stats = one_pass(x, kc_all, vc_all,
                                                moe_stats, 0)
    new_cache = {"k": kc_all, "v": vc_all}
    logits = output_logits(cfg, dparams, x, normed=cfg.is_looped)
    if cfg.lm_head_bias:
        logits = logits + dparams["lm_head_bias"].astype(jnp.float32)
    if moe_live is not None:
        return logits, new_cache, moe_stats
    return logits, new_cache
