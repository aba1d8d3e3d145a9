"""KV-cache prefill/decode paths for the built-in models.

TPU-native counterpart of the reference's inference kernel path
(``csrc/transformer/inference/``: preallocated KV-cache workspace in
``inference_context.h`` sized by ``max_out_tokens``, fused decode kernels;
SURVEY.md §2.2, §3.5).  The cache is a functional pytree of static-shape
[L, B, Hkv, Smax, Dh] buffers updated with ``dynamic_update_slice`` and
donated across steps by the engine — the jax equivalent of the reference's
global inference workspace arena.

Prefill attends densely under a position mask; decode (s=1) runs a
length-aware flash-decode: online softmax over cache blocks inside a
``lax.while_loop`` bounded by the current position, so per-token attention
work tracks the sequence actually generated instead of ``Smax`` — while the
traced program stays static-shape (one compiled step).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.comm.mesh import axis_size
from deepspeed_tpu.models import eva
from deepspeed_tpu.models.afmoe import close
from deepspeed_tpu.models.layers import (activation_fn, apply_partial_rope,
                                         constrain, model_norm, norm, qk_norm,
                                         _repeat_kv, rope_dim)
from deepspeed_tpu.ops.pallas import rope_angles
from deepspeed_tpu.ops.pallas.flash_attention import eva_chunk_attention

NEG_INF = -1e30


def init_kv_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
                  quantized: bool = False) -> Dict[str, Any]:
    """``quantized=True`` stores int8 K/V with a per-(position, head) fp32
    scale over the head dim — ~1.03 bytes/element vs 2 for bf16 (reference
    int8 KV role, ``(R) inference_context.h`` workspace + dequant kernels).

    Caches longer than one decode block are rounded UP to a block multiple
    so the length-aware flash-decode path always applies (the padding rows
    cost memory only; they are never visited).

    One cache layer a (pass, layer) pair: ``cfg.cache_layers`` of them, the
    model's ``num_layers`` unless its stack is looped."""
    L, Hkv, Dh = cfg.cache_layers, cfg.num_kv_heads, cfg.head_dim
    if max_len > DECODE_BLOCK and max_len % DECODE_BLOCK:
        rounded = -(-max_len // DECODE_BLOCK) * DECODE_BLOCK
        # callers sizing masks/position buffers must read cache['k'].shape[-2]
        # rather than their requested max_len — say so, once
        global _WARNED_ROUNDED_CACHE
        if not _WARNED_ROUNDED_CACHE:
            _WARNED_ROUNDED_CACHE = True
            from deepspeed_tpu.utils.logging import logger

            logger.info(
                "init_kv_cache: max_len %d rounded up to %d (a %d-multiple) "
                "for the flash-decode path; size position buffers from "
                "cache['k'].shape[-2]", max_len, rounded, DECODE_BLOCK)
        max_len = rounded
    if quantized:
        return {
            "k": jnp.zeros((L, batch, Hkv, max_len, Dh), jnp.int8),
            "v": jnp.zeros((L, batch, Hkv, max_len, Dh), jnp.int8),
            "k_scale": jnp.zeros((L, batch, Hkv, max_len, 1), jnp.float32),
            "v_scale": jnp.zeros((L, batch, Hkv, max_len, 1), jnp.float32),
            # decode activations still need a dtype anchor (cache dtype is
            # int8); keep it alongside the buffers
            "x_dtype": jnp.zeros((), dtype),
        }
    return {
        "k": jnp.zeros((L, batch, Hkv, max_len, Dh), dtype),
        "v": jnp.zeros((L, batch, Hkv, max_len, Dh), dtype),
    }


def _quantize_kv_rows(x):
    """[B, Hkv, s, Dh] -> (int8 payload, fp32 [B, Hkv, s, 1] scale)."""
    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    scale = jnp.where(absmax == 0, 1.0, absmax / 127.0)
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


DECODE_BLOCK = 256  # flash-decode cache block (power of two, MXU-friendly)
_WARNED_ODD_CACHE = False
_WARNED_ROUNDED_CACHE = False


def _as_row_pos(q_pos):
    """Normalize query positions to [Bq, s]: a [s] vector is shared across
    the batch (Bq=1 broadcasts); a [B, s] matrix is per-row (continuous
    batching, where every sequence sits at its own depth)."""
    q_pos = jnp.asarray(q_pos)
    return q_pos[None] if q_pos.ndim == 1 else q_pos


def _cached_attention_dense(q, kcache, vcache, q_pos, scale, k_scale=None,
                            v_scale=None, slopes=None):
    """Masked attention over the whole static cache (prefill path, s > 1);
    int8 caches are dequantized on the fly (fused into the einsum reads);
    ``slopes`` [H] adds the ALiBi per-head linear position bias.  ``q_pos``
    is [s] (batch-shared) or [B, s] (per-row positions)."""
    B, H, s, Dh = q.shape
    Hkv = kcache.shape[1]
    q_pos = _as_row_pos(q_pos)                         # [Bq, s]
    kf = kcache.astype(jnp.float32)
    vf = vcache.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale
    if v_scale is not None:
        vf = vf * v_scale
    k = _repeat_kv(kf, H // Hkv)
    v = _repeat_kv(vf, H // Hkv)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k) * scale
    key_pos = jnp.arange(k.shape[-2])
    if slopes is not None:
        rel = (key_pos[None, None, :] - q_pos[:, :, None]).astype(jnp.float32)
        logits = logits + slopes[None, :, None, None] * rel[:, None]
    mask = key_pos[None, None, :] <= q_pos[:, :, None]  # causal vs absolute pos
    logits = jnp.where(mask[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return out.astype(q.dtype)


def _cached_attention_flash_decode(q, kcache, vcache, q_pos, scale,
                                   k_scale=None, v_scale=None, slopes=None,
                                   block: int = DECODE_BLOCK):
    """Length-aware decode attention (VERDICT r3 weak #10): online-softmax
    over cache blocks, visiting only blocks up to the current position — a
    ``lax.while_loop`` flash-decode whose per-token compute is
    O(cur_len rounded up to ``block``), not O(Smax).  The dense path scans
    the whole static cache every token, which at Smax=8k and cur_len=100 is
    ~80x wasted attention FLOPs/bandwidth."""
    B, H, s, Dh = q.shape
    Hkv = kcache.shape[1]
    Smax = kcache.shape[2]
    rep = H // Hkv
    q_pos = _as_row_pos(q_pos)                         # [Bq, s]
    qf = q.astype(jnp.float32)
    # visit blocks [0, n_blocks): everything at or before the newest query
    # (per-row positions: the deepest row bounds the loop; shallower rows'
    # extra blocks are fully masked, and exp(NEG_INF - m) underflows to an
    # exact 0 contribution, so per-row outputs match a per-row-bounded scan)
    n_blocks = jnp.max(q_pos) // block + 1

    def body(carry):
        i, m, l, acc = carry
        start = i * block
        kb = jax.lax.dynamic_slice_in_dim(kcache, start, block, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vcache, start, block, axis=2)
        kb = kb.astype(jnp.float32)
        vb = vb.astype(jnp.float32)
        if k_scale is not None:
            ksb = jax.lax.dynamic_slice_in_dim(k_scale, start, block, axis=2)
            kb = kb * ksb
        if v_scale is not None:
            vsb = jax.lax.dynamic_slice_in_dim(v_scale, start, block, axis=2)
            vb = vb * vsb
        kb = _repeat_kv(kb, rep)
        vb = _repeat_kv(vb, rep)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kb) * scale
        key_pos = start + jnp.arange(block)
        if slopes is not None:
            rel = (key_pos[None, None, :]
                   - q_pos[:, :, None]).astype(jnp.float32)
            logits = logits + slopes[None, :, None, None] * rel[:, None]
        mask = key_pos[None, None, :] <= q_pos[:, :, None]  # [Bq, s, block]
        logits = jnp.where(mask[:, None], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        correction = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * correction + p.sum(axis=-1)
        acc_new = (acc * correction[..., None]
                   + jnp.einsum("bhqk,bhkd->bhqd", p, vb))
        return i + 1, m_new, l_new, acc_new

    init = (jnp.zeros((), jnp.int32),
            jnp.full((B, H, s), NEG_INF, jnp.float32),
            jnp.zeros((B, H, s), jnp.float32),
            jnp.zeros((B, H, s, Dh), jnp.float32))
    _, m, l, acc = jax.lax.while_loop(lambda c: c[0] < n_blocks, body, init)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _cached_attention(q, kcache, vcache, q_pos, scale, k_scale=None,
                      v_scale=None, slopes=None):
    """q: [B, H, s, Dh]; caches: [B, Hkv, Smax, Dh]; q_pos: absolute
    positions of the queries — [s] (batch-shared) or [B, s] (per-row, the
    continuous-batching decode where every sequence is at its own depth).
    Decode (s == 1, cache larger than one block) takes the length-aware
    flash-decode path; prefill stays dense.  ``slopes`` [H] = ALiBi bias."""
    s = q.shape[2]
    Smax = kcache.shape[2]
    if s == 1 and Smax > DECODE_BLOCK:
        if Smax % DECODE_BLOCK == 0:
            return _cached_attention_flash_decode(q, kcache, vcache, q_pos,
                                                  scale, k_scale, v_scale,
                                                  slopes)
        # init_kv_cache rounds lengths up; an externally-built odd cache
        # falls back to the dense scan — say so, once
        global _WARNED_ODD_CACHE
        if not _WARNED_ODD_CACHE:
            _WARNED_ODD_CACHE = True
            from deepspeed_tpu.utils.logging import logger

            logger.warning(
                "decode: cache length %d is not a multiple of %d; the "
                "length-aware flash-decode is disabled and every token "
                "re-scans the full cache (build caches via init_kv_cache)",
                Smax, DECODE_BLOCK)
    return _cached_attention_dense(q, kcache, vcache, q_pos, scale,
                                   k_scale, v_scale, slopes)


def _rope_rows(t, cos, sin):
    """Per-row partial RoPE: t [B, Hx, s, Dh]; cos/sin [B, s, half] carry
    each row's own absolute positions (continuous-batching decode)."""
    rot = 2 * cos.shape[-1]
    half = cos.shape[-1]
    c = cos[:, None].astype(jnp.float32)               # [B, 1, s, half]
    sn = sin[:, None].astype(jnp.float32)
    x1 = t[..., :half].astype(jnp.float32)
    x2 = t[..., half:rot].astype(jnp.float32)
    r = jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn],
                        axis=-1).astype(t.dtype)
    return (jnp.concatenate([r, t[..., rot:]], axis=-1)
            if rot < t.shape[-1] else r)


def paged_logical_view(buf, page_table):
    """Gather a slot-contiguous LOGICAL cache view out of the paged pool:
    ``buf`` [P, Hkv, page, D] physical pages, ``page_table`` [B, maxp]
    int32 -> [B, Hkv, maxp*page, D].  The XLA fallback/reference read path
    for the paged cache (the Pallas flash-decode kernel instead indirects
    its DMA index map through the table, so no gather materializes);
    unallocated table entries gather the junk page, whose rows sit beyond
    every live position and are masked like any other padding."""
    g = buf[page_table]                                 # [B, maxp, page...]
    B, mp, Hkv, pg, D = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, mp * pg, D)


def _scatter_paged_rows(buf, rows, pos, page_table):
    """Per-row single-token append through the page table: ``rows``
    [B, Hkv, 1, D] written at logical positions ``pos`` [B] into the
    physical pool ``buf`` [P, Hkv, page, D] (row b lands at row
    ``pos[b] % page`` of page ``page_table[b, pos[b] // page]``).  One
    batched scatter, same aliasing argument as :func:`_scatter_rows`;
    parked rows (table pointing at junk page 0) scatter junk harmlessly."""
    B = rows.shape[0]
    page = buf.shape[2]
    pp = page_table[jnp.arange(B), pos // page]         # physical page [B]
    po = pos % page
    return buf.at[pp, :, po, :].set(rows[:, :, 0, :].astype(buf.dtype))


def _scatter_view(buf, view, page_table):
    """Inverse of :func:`paged_logical_view`: write each row's logical view
    [B, Hkv, maxp*page, D] back through its pages.  Unallocated entries all
    name the junk page, which takes whichever write lands last."""
    B, Hkv, _, D = view.shape
    mp, pg = page_table.shape[1], buf.shape[2]
    return buf.at[page_table].set(
        view.reshape(B, Hkv, mp, pg, D).transpose(0, 2, 1, 3, 4))


def _scatter_rows(buf, rows, start_pos):
    """Write ``rows`` [B, Hx, s, D] into ``buf`` [B, Hx, Smax, D] at
    per-row start positions ``start_pos`` [B] (each batch row lands at its
    own cache depth) as ONE batched scatter — measured much faster than a
    per-row dynamic_update_slice loop, whose per-row dynamic start index
    defeats XLA's in-place aliasing and copies the buffer per write."""
    B, _, s, _ = rows.shape
    bidx = jnp.arange(B)[:, None]                      # [B, 1]
    pidx = start_pos[:, None] + jnp.arange(s)[None, :]  # [B, s]
    return buf.at[bidx, :, pidx, :].set(
        rows.transpose(0, 2, 1, 3).astype(buf.dtype))


def forward_with_cache(model, params, tokens, cache, start_pos,
                       page_table=None, valid_len=None):
    """Run the model over ``tokens`` [B, s] starting at absolute position
    ``start_pos``, reading/updating the KV cache.

    ``start_pos`` is a scalar (the whole batch at one depth — static-batch
    prefill/decode) or an int32 [B] vector of per-row positions (the
    continuous-batching decode, where every slot sits at its own depth: the
    paged layout's, so it comes with a ``page_table``).

    ``page_table`` [B, maxp] switches the cache to the PAGED layout
    (``serving/paged_kv.py``: [L, num_pages, Hkv, page, Dh] pools shared
    by all slots): appends scatter through the table and reads gather a
    logical view per layer — the dense XLA fallback/reference for the
    Pallas paged kernel.  Paged mode is decode-only (``s == 1``, per-row
    positions); serving prefill gathers the slot's pages around this
    function instead.

    Under ``attention="eva"`` the cache (or the paged pool's logical view)
    has the layout of ``models/eva.py``: ``eva_window`` window rows, position
    ``p`` at row ``p % W``, then the summary rows.  The ``s`` tokens of a
    call must lie in one window (the serving engine's chunks do:
    ``prefill_chunk`` divides ``W``); each call also pools its window's rows
    into that window's summary rows, so the call that fills a window leaves
    its summaries behind.  The paged form gathers each row's pages, works on
    the view and scatters them back: the reference for the fused path.

    Under ``layer_types`` (``models/afmoe.py``) the cache is the two-kind
    view ``afmoe.cached_layers`` takes (rings of ``sliding_window`` rows for the
    sliding layers, every position for the global ones) and the call is a
    prefill chunk of ONE slot at a scalar ``start_pos``, of which the first
    ``valid_len`` tokens are real: a ring takes no pad row.  Decode runs on
    the fused path only.  Where the kinds are linear and latent attention
    (``models/kda_mla.py``) the cache is that module's view of one slot:
    latent rows of every position, and the recurrent state and convolution
    tail the chunk carries in and out as of its last real row.

    Returns (logits [B, s, num_pred_heads * V], new_cache).  Used for prefill
    (s = prompt length, start_pos=0), decode (s = 1), and chunked per-slot
    prefill (s = chunk, scalar start_pos = chunk offset).
    """
    cfg = model.config
    mesh = model.mesh
    batch_ax = ("dp", "fsdp", "ep")
    B, s = tokens.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if cfg.is_afmoe:
        from deepspeed_tpu.models import afmoe
        if page_table is not None or jnp.ndim(start_pos) != 0:
            raise NotImplementedError(
                "a layer_types model (models/afmoe.py) decodes through the "
                "fused path (fused_decode.decode_step); forward_with_cache "
                "prefills one slot's chunk at a scalar start_pos")
        afmoe.refuse_parallel(cfg, mesh, "forward_with_cache")
        form = afmoe.form(cfg)      # afmoe itself, or models/kda_mla.py
        x = afmoe.embed(cfg, params["embed"]["tok"], tokens,
                        cache[form.cache_key(cfg)].dtype)
        x, new_cache = form.cached_layers(
            cfg, params, x, cache, start_pos,
            s if valid_len is None else valid_len)
        return output_logits(cfg, params, x), new_cache
    quant_kv = "k_scale" in cache
    start_pos = jnp.asarray(start_pos, jnp.int32)
    per_row = start_pos.ndim == 1                      # [B] vector of depths
    paged = page_table is not None
    if paged != per_row or (paged and s != 1):
        raise ValueError("per-row positions are the paged KV decode's, with "
                         "a page_table and s == 1 (prefill runs on a "
                         "gathered slot view at a scalar start_pos)")
    if cfg.is_eva and quant_kv:
        raise NotImplementedError(
            "attention='eva' with an int8 KV cache: the summary rows are "
            "pooled from the window rows and have no scales of their own")
    x = jnp.take(params["embed"]["tok"], tokens, axis=0)
    if cfg.position == "learned":
        if per_row:
            pos_idx = start_pos[:, None] + jnp.arange(s)       # [B, s]
            x = x + jnp.take(params["embed"]["pos"], pos_idx, axis=0)
        else:
            pos_idx = start_pos + jnp.arange(s)
            x = x + jnp.take(params["embed"]["pos"], pos_idx, axis=0)[None]
    if cfg.embed_norm:  # bloom word_embeddings_layernorm
        x = norm(x, params["embed"]["norm"], "layernorm", cfg.norm_eps)
    cdt = cache["x_dtype"].dtype if quant_kv else cache["k"].dtype
    # matmul inputs in the cache's dtype; the stream itself in float32
    # where the model says so (evabyte's fp32_skip_add)
    x = x.astype(jnp.float32 if cfg.fp32_residual else cdt)
    x = constrain(x, mesh, batch_ax, None, None)
    if per_row:
        q_pos = start_pos[:, None] + jnp.arange(s)             # [B, s]
    else:
        q_pos = start_pos + jnp.arange(s)                      # [s]
    if cfg.position == "alibi":
        from deepspeed_tpu.models.layers import alibi_slopes
        slopes = alibi_slopes(H)
    else:
        slopes = None

    # logical sequence capacity: the paged pool's per-slot window is the
    # page table width x page depth, not the physical buffer's last dim
    s_max = cache["k"].shape[-2] * (page_table.shape[1] if paged else 1)
    if cfg.is_eva:
        # positions are not cache rows here: angles straight from q_pos
        cos, sin = (t.reshape(q_pos.shape + t.shape[-1:]).astype(cdt)
                    for t in rope_angles(q_pos.reshape(-1), rope_dim(cfg),
                                         theta=cfg.rope_theta))
    elif cfg.position == "rope":
        # angles for the whole cache window once; gather the query slice
        cos_all, sin_all = rope_angles(jnp.arange(s_max),
                                       rope_dim(cfg), theta=cfg.rope_theta)
        if per_row:
            cos = cos_all[q_pos].astype(x.dtype)               # [B, s, half]
            sin = sin_all[q_pos].astype(x.dtype)
        else:
            cos = jax.lax.dynamic_slice_in_dim(cos_all, start_pos,
                                               s).astype(x.dtype)
            sin = jax.lax.dynamic_slice_in_dim(sin_all, start_pos,
                                               s).astype(x.dtype)
    else:
        cos = sin = jnp.zeros((), x.dtype)
    scale = 1.0 / (Dh ** 0.5)
    W = cfg.eva_window
    # EVA: several queries at shared positions whose heads all sit on this
    # chip (GSPMD cannot split a Pallas kernel over tp or sp)
    flash_chunk = s > 1 and not per_row and (
        mesh is None or mesh.empty
        or axis_size(mesh, "tp") == axis_size(mesh, "sp") == 1)

    # A dropless MoE block takes the STACKED expert arrays whole and its own
    # layer's index (moe/sharded_moe.py:_moe_grouped): scanned like the other
    # weights, each layer's 0.8 GB would be copied out in front of every
    # grouped matmul.  So they stay out of the scan's slices.
    layers = params["layers"]
    experts = {}
    if cfg.is_moe and not cfg.moe_drop_tokens:
        experts = {k: v for k, v in layers["mlp"].items() if k != "gate_w"}
        layers = {**layers, "mlp": {"gate_w": layers["mlp"]["gate_w"]}}
    layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)

    def layer_step(carry, xs):
        h_in = carry
        if quant_kv:
            lp, l, kc, vc, ksc, vsc = xs
        else:
            lp, l, kc, vc = xs
            ksc = vsc = None
        x0 = h_in  # layer input (parallel residual reads it twice)
        h = model_norm(cfg, h_in, lp["attn_norm"]).astype(cdt)
        a = lp["attn"]
        q = h @ a["wq"].astype(h.dtype)
        k = h @ a["wk"].astype(h.dtype)
        v = h @ a["wv"].astype(h.dtype)
        if cfg.use_bias or cfg.qkv_bias:
            q = q + a["bq"].astype(h.dtype)
            k = k + a["bk"].astype(h.dtype)
            v = v + a["bv"].astype(h.dtype)
        if cfg.qk_norm:
            q, k = qk_norm(q, k, a["q_norm"]["scale"], a["k_norm"]["scale"],
                           cfg.norm_eps, mesh)
        q = q.reshape(B, s, H, Dh).transpose(0, 2, 1, 3)
        k = k.reshape(B, s, Hkv, Dh).transpose(0, 2, 1, 3)
        v = v.reshape(B, s, Hkv, Dh).transpose(0, 2, 1, 3)
        if cfg.position == "rope":
            if per_row:
                q = _rope_rows(q, cos, sin)
                k = _rope_rows(k, cos, sin)
            else:
                q = apply_partial_rope(q, cos, sin)
                k = apply_partial_rope(k, cos, sin)
        if cfg.is_eva:
            win_pos = start_pos % W
            kv_view, vv_view = ((paged_logical_view(kc, page_table),
                                 paged_logical_view(vc, page_table))
                                if paged else (kc, vc))
            if per_row:
                kv_view = _scatter_rows(kv_view, k, win_pos)
                vv_view = _scatter_rows(vv_view, v, win_pos)
            else:
                kv_view = jax.lax.dynamic_update_slice(
                    kv_view, k.astype(kc.dtype), (0, 0, win_pos, 0))
                vv_view = jax.lax.dynamic_update_slice(
                    vv_view, v.astype(vc.dtype), (0, 0, win_pos, 0))
            if flash_chunk:
                # a chunk at shared positions: the flash form where its
                # sizes allow (the scores stay in VMEM), else the dense one
                o = eva_chunk_attention(q, kv_view, vv_view, start_pos,
                                        window=W, chunk=cfg.eva_chunk,
                                        sm_scale=scale)
            else:
                o = eva.cached_attention(q, kv_view, vv_view, q_pos, window=W,
                                         chunk=cfg.eva_chunk, scale=scale)
            kv_view, vv_view = eva.write_window_summaries(
                kv_view, vv_view, a["eva_mu"], a["eva_phi"], start_pos // W,
                window=W, chunk=cfg.eva_chunk)
            if paged:
                kc = _scatter_view(kc, kv_view, page_table)
                vc = _scatter_view(vc, vv_view, page_table)
            else:
                kc, vc = kv_view, vv_view
        elif quant_kv:
            kq, ks = _quantize_kv_rows(k)
            vq, vs = _quantize_kv_rows(v)
            if paged:
                kc = _scatter_paged_rows(kc, kq, start_pos, page_table)
                vc = _scatter_paged_rows(vc, vq, start_pos, page_table)
                ksc = _scatter_paged_rows(ksc, ks, start_pos, page_table)
                vsc = _scatter_paged_rows(vsc, vs, start_pos, page_table)
            else:
                kc = jax.lax.dynamic_update_slice(kc, kq, (0, 0, start_pos, 0))
                vc = jax.lax.dynamic_update_slice(vc, vq, (0, 0, start_pos, 0))
                ksc = jax.lax.dynamic_update_slice(ksc, ks,
                                                   (0, 0, start_pos, 0))
                vsc = jax.lax.dynamic_update_slice(vsc, vs,
                                                   (0, 0, start_pos, 0))
        elif paged:
            kc = _scatter_paged_rows(kc, k, start_pos, page_table)
            vc = _scatter_paged_rows(vc, v, start_pos, page_table)
        else:
            kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                              (0, 0, start_pos, 0))
            vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                              (0, 0, start_pos, 0))
        if cfg.is_eva:
            pass                       # attended above, on the view
        elif paged:
            # XLA fallback read: gather the logical per-slot view through
            # the table (junk-page rows sit past every live position and
            # mask out); the Pallas kernel path never materializes this
            o = _cached_attention(
                q, paged_logical_view(kc, page_table),
                paged_logical_view(vc, page_table), q_pos, scale,
                paged_logical_view(ksc, page_table) if quant_kv else None,
                paged_logical_view(vsc, page_table) if quant_kv else None,
                slopes)
        else:
            o = _cached_attention(q, kc, vc, q_pos, scale, ksc, vsc, slopes)
        o = o.transpose(0, 2, 1, 3).reshape(B, s, H * Dh)
        o = o @ a["wo"].astype(h.dtype)
        if cfg.use_bias:
            o = o + a["bo"].astype(h.dtype)
        o = o.astype(h_in.dtype)
        if cfg.parallel_residual:
            # gpt-neox: MLP reads the LAYER INPUT; both branches add at once
            mlp_src = x0
        else:
            h_in = close(cfg, h_in, o,
                         lp.get("attn_post_norm", {}).get("scale"))
            mlp_src = h_in

        h = model_norm(cfg, mlp_src, lp["mlp_norm"]).astype(cdt)
        if cfg.is_moe:
            from deepspeed_tpu.moe.sharded_moe import moe_mlp
            mlp_out, _ = moe_mlp(
                jax.tree.map(lambda a: a.astype(h.dtype),
                             {**lp["mlp"], **experts}),
                h, cfg, mesh, layer=l if experts else None)
        else:
            act = activation_fn(cfg.activation)
            m = lp["mlp"]
            up = h @ m["w_up"].astype(h.dtype)
            if cfg.has_mlp_bias:
                up = up + m["b_up"].astype(h.dtype)
            if cfg.glu:
                gate = h @ m["w_gate"].astype(h.dtype)
                if cfg.has_mlp_bias:
                    gate = gate + m["b_gate"].astype(h.dtype)
                gated = act(gate) * up
            else:
                gated = act(up)
            mlp_out = gated @ m["w_down"].astype(h.dtype)
            if cfg.has_mlp_bias:
                mlp_out = mlp_out + m["b_down"].astype(h.dtype)
        mlp_out = mlp_out.astype(h_in.dtype)
        h_in = (x0 + o + mlp_out) if cfg.parallel_residual else close(
            cfg, h_in, mlp_out, lp.get("mlp_post_norm", {}).get("scale"))
        if quant_kv:
            return h_in, (kc, vc, ksc, vsc)
        return h_in, (kc, vc)

    names = ("k", "v", "k_scale", "v_scale") if quant_kv else ("k", "v")
    arrays = tuple(cache[n] for n in names)
    if cfg.is_looped:
        # the passes scan AROUND the scan over layers, the same stacked
        # weights in each; the cache's ``cache_layers`` leading entries are
        # [pass, layer] (a free reshape), so pass t's layer l reads and
        # writes entry t * L + l; the final norm closes every pass
        T, L = cfg.total_ut_steps, cfg.num_layers

        def one_pass(x, arrays_t):
            with jax.named_scope("ds_loop_pass"):
                x, out = jax.lax.scan(layer_step, x,
                                      (layers, layer_ids) + arrays_t)
                return model_norm(cfg, x, params["final_norm"]), out

        x, arrays = jax.lax.scan(
            one_pass, x,
            tuple(a.reshape((T, L) + a.shape[1:]) for a in arrays))
        arrays = tuple(a.reshape((T * L,) + a.shape[2:]) for a in arrays)
    else:
        x, arrays = jax.lax.scan(layer_step, x, (layers, layer_ids) + arrays)
    new_cache = dict(zip(names, arrays))
    if quant_kv:
        new_cache["x_dtype"] = cache["x_dtype"]
    logits = output_logits(cfg, params, x, normed=cfg.is_looped)
    if cfg.lm_head_bias:
        logits = logits + params["lm_head_bias"].astype(jnp.float32)
    return logits, new_cache


def output_logits(cfg, params, x, normed: bool = False):
    """Final norm and output head of the cached forwards (``params`` the
    plain or the kernel-injected tree): float32 logits [..., num_pred_heads
    * V].  A float32 residual stream (evabyte's fp32_logits) is normed in
    float32, meets the head in the head's dtype and is accumulated in
    float32; otherwise the product is rounded to ``x``'s dtype first.
    ``normed``: the final norm has closed the last pass of a looped stack
    already."""
    if not normed:
        x = model_norm(cfg, x, params["final_norm"])
    head = (params["embed"]["tok"].T if cfg.tie_embeddings
            else params["lm_head"])
    if cfg.fp32_residual:
        return jnp.dot(x.astype(head.dtype), head,
                       preferred_element_type=jnp.float32)
    return (x @ head.astype(x.dtype)).astype(jnp.float32)  # QTensor-aware


def next_token_logits(cfg, logits):
    """The columns the next token is sampled from: head 0's ``vocab_size``
    of a model with several prediction heads."""
    return logits[..., :cfg.vocab_size] if cfg.num_pred_heads > 1 else logits


def sample_token(logits, rng, temperature: float = 1.0, top_k: int = 0,
                 top_p: float = 1.0, do_sample: bool = True):
    """logits: [B, V] -> token ids [B] (greedy when do_sample=False)."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1)
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, NEG_INF, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)            # first idx past mass
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff, NEG_INF, logits)
    return jax.random.categorical(rng, logits, axis=-1)
