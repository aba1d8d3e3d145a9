"""The AFMoE layer form (arcee-ai Trinity; ``ModelConfig.layer_types``):
layers of two attention kinds and two MLP kinds from a static pattern,
gated attention, a norm on both sides of each sub-block, a sigmoid
bias-corrected router and ONE CHIP'S SHARE of the routed experts.

With ``N(.)`` RMSNorm (own gain, ``norm_eps``) and ``x`` the stream:

    x = E[tokens] * embed_scale
    layer l, attention:
        h = N_in(x); q, k, v, g = h Wq, h Wk, h Wv, h Wg
        q, k = N_q(q), N_k(k)          per head, gains [head_dim]
        "sliding_attention": RoPE on q and k, keys j with 0 <= i - j < W
        "full_attention":    NO position encoding, every j <= i
        a = (softmax(q k^T / sqrt(d)) v * sigmoid(g)) Wo
        x = x + N_post_attn(a)
    layer l, MLP:  h = N_pre_mlp(x)
        l < num_dense_layers:  m = (silu(h Wgate) * (h Wup)) Wdown
        else: s = sigmoid(h Wr) over the router's experts, float32
              sel = top-k of (s + b)   (the bias picks, it does not weigh),
                    inside the moe_topk_group best of moe_n_group groups of
                    consecutive experts where the model has more than one
              w = s[sel] / (sum s[sel] + 1e-20) * route_scale
              m = shared(h) + sum over e in sel HELD HERE of w_e expert_e(h)
        x = x + N_post_mlp(m)
    logits = N_f(x) W_head

Each unusual piece is ONE function here (:func:`head_norm_rope`,
:func:`gated`, :func:`close`, :func:`route` / :func:`held`, :func:`mlp`,
:func:`attend`) and the three forwards call them: ``CausalLM.apply``
(:func:`apply_layers`, no cache), ``forward_with_cache`` (:func:`cached_layers`,
a prefill chunk on a slot's gathered views) and ``decode_step``
(:func:`fused_layers`, the Pallas kernels of ``ops/pallas/decode.py`` for the
matmuls and the paged attention).  What differs between a sliding and a
global layer is data of equal shape, so the pattern is a static Python loop
over the layers; the parameters are two stacks because the MLP shapes differ:
``dense_layers`` ``[num_dense_layers, ...]`` and ``layers`` ``[rest, ...]``.

Cache: a sliding layer keeps a RING of exactly ``W = sliding_window`` rows,
position ``p`` at row ``p % W`` (``serving/paged_kv.py``: window pages); a
global layer keeps every position (full pages).  Decode appends its row and
then attends the ring whole (query ``p`` overwrote ``p - W``, which it no
longer sees).  A prefill chunk of ``c`` tokens would overwrite rows its own
first queries still attend, so it ATTENDS BEFORE IT APPENDS: the ring as the
previous chunks left it beside the chunk's own K and V, every key masked by
its position, and only then the chunk's ``valid_len`` real rows go into the
ring (a pad row written there would destroy a live one).

The share: ``num_experts`` is the number HELD here, ``[moe_first_expert,
+ num_experts)`` of the router's ``moe_router_experts``.  A token's choice of
an expert another chip holds is dropped, not imitated: the eight ranks'
routed parts plus the shared expert once sum to the whole layer
(tests/unit/test_trinity.py).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.pallas import rope_angles

NEG_INF = -1e30
F32 = jnp.float32
KEY_BLOCK = 1024          # keys a step of :func:`attend`'s online softmax


def form(cfg):
    """The module that holds a ``layer_types`` model's three forwards: this
    one (sliding and global layers), or its sibling for every pattern with a
    linear-attention or a latent-attention layer in it (a global layer
    beside linear ones is the sibling's per-head kind), which calls this
    one's pieces."""
    if cfg.is_kda_mla:
        from deepspeed_tpu.models import kda_mla
        return kda_mla
    if cfg.is_mixer:
        from deepspeed_tpu.models import ssm_moe
        return ssm_moe
    import sys
    return sys.modules[__name__]


def cache_key(cfg) -> str:
    """The cache entry whose dtype the stream takes."""
    return "k_full"


def is_sliding(cfg, l: int) -> bool:
    return cfg.layer_types[l] == "sliding_attention"


def kind_layers(cfg):
    """(indices of the sliding layers, indices of the global layers)."""
    L = range(cfg.num_layers)
    return ([l for l in L if is_sliding(cfg, l)],
            [l for l in L if not is_sliding(cfg, l)])


def refuse_parallel(cfg, mesh, what: str) -> None:
    if mesh is not None and not mesh.empty and any(
            dict(mesh.shape).get(a, 1) > 1 for a in ("tp", "ep", "sp", "pp")):
        raise NotImplementedError(
            f"{what} with layer_types (models/afmoe.py) under tp, ep, sp or "
            "pp > 1: the chip's share of the experts runs WITHOUT its "
            "exchange, and the head norms, gate and ring cache are not split")


# ----------------------------------------------------------------------
# parameters
# ----------------------------------------------------------------------
def init_params(cfg, rng, dtype=F32, attn: bool = True) -> Dict[str, Any]:
    """Seeded weights (``attn=False``: without the attention projections,
    for a form whose attention kinds bring their own stacks,
    ``models/kda_mla.py``): the repo's uniform init (not the release's
    depth-scaled one), norm gains 1, the selection bias normal x 0.05 (the
    release starts it at zero and trains it by its balancing rule; zeros
    would leave the bias path untested, and at x 0.01 a bias wrongly used
    as a weight moved the logits no further than bf16 does: PERF.md section
    4, trinity-large-L5-ep8)."""
    D, V = cfg.hidden_size, cfg.vocab_size
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Ld, Le = cfg.num_dense_layers, cfg.num_layers - cfg.num_dense_layers
    E, R, F = cfg.num_experts, cfg.moe_router_experts, cfg.intermediate_size
    keys = iter(jax.random.split(rng, 40))
    uni = lambda shape, fan_in: jax.random.uniform(
        next(keys), shape, dtype, -fan_in ** -0.5, fan_in ** -0.5)
    ones = lambda *shape: {"scale": jnp.ones(shape, dtype)}

    def stack(L, mlp):
        out = {"attn_norm": ones(L, D), "mlp_norm": ones(L, D), "mlp": mlp}
        if attn:
            a = {"wq": uni((L, D, H * Dh), D), "wk": uni((L, D, Hkv * Dh), D),
                 "wv": uni((L, D, Hkv * Dh), D),
                 "wo": uni((L, H * Dh, D), H * Dh)}
            if cfg.attn_output_gate:
                a["wg"] = uni((L, D, H * Dh), D)
            if cfg.qk_norm_per_head:
                a.update(q_norm=ones(L, Dh), k_norm=ones(L, Dh))
            out["attn"] = a
        if cfg.sandwich_norm:
            out.update(attn_post_norm=ones(L, D), mlp_post_norm=ones(L, D))
        return out

    def glu(lead, width):
        return {"w_up": uni(lead + (D, width), D),
                "w_gate": uni(lead + (D, width), D),
                "w_down": uni(lead + (width, D), width)}

    params = {"embed": {"tok": jax.random.normal(next(keys), (V, D), dtype)
                        * 0.02},
              "final_norm": {"scale": jnp.ones((D,), dtype)},
              "lm_head": jax.random.normal(next(keys), (D, V), dtype)
              * D ** -0.5}
    if Ld:
        params["dense_layers"] = stack(
            Ld, glu((Ld,), cfg.dense_intermediate_size))
    if Le:
        mlp = {"gate_w": uni((Le, D, R), D), **glu((Le, E), F)}
        if cfg.moe_select_bias:
            mlp["gate_bias"] = jax.random.normal(next(keys), (Le, R),
                                                 dtype) * 0.05
        if cfg.num_shared_experts:
            mlp["shared"] = glu((Le,), F * cfg.num_shared_experts)
        params["layers"] = stack(Le, mlp)
    return params


def logical_pspecs(cfg, params_like) -> Dict[str, Any]:
    """Every leaf whole on its chip (:func:`refuse_parallel`)."""
    return jax.tree.map(lambda a: P(*([None] * a.ndim)), params_like)


def layer_params(cfg, params, l: int):
    """Layer ``l``'s slice of its stack, and (expert layers) its index in
    the stacked expert arrays, which stay whole."""
    Ld = cfg.num_dense_layers
    if l < Ld:
        return jax.tree.map(lambda a: a[l], params["dense_layers"]), None
    ly = params["layers"]
    thin = {**ly, "mlp": {k: v for k, v in ly["mlp"].items()
                          if k not in ("w_up", "w_gate", "w_down")}}
    return jax.tree.map(lambda a: a[l - Ld], thin), l - Ld


# ----------------------------------------------------------------------
# the block forms
# ----------------------------------------------------------------------
def rms(x, scale, eps: float):
    """RMSNorm over the last axis in float32, back in ``x``'s dtype."""
    x32 = x.astype(F32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(F32)).astype(x.dtype)


def rope(t, cos, sin):
    """Rotate-half RoPE over the whole head: ``t`` [..., H, Dh], ``cos`` /
    ``sin`` broadcastable [..., 1, Dh/2]."""
    half = t.shape[-1] // 2
    t1, t2 = t[..., :half].astype(F32), t[..., half:].astype(F32)
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                           axis=-1).astype(t.dtype)


def angles(cfg, positions):
    """cos, sin [..., 1, Dh/2] float32 for integer ``positions`` [...]."""
    cos, sin = rope_angles(positions.reshape(-1), cfg.head_dim,
                           theta=cfg.rope_theta)
    shape = positions.shape + (1, cfg.head_dim // 2)
    return cos.reshape(shape), sin.reshape(shape)


def head_norm_rope(cfg, q_norm, k_norm, q, k, cos, sin, sliding: bool):
    """q [..., H, Dh], k [..., Hkv, Dh]: the per-head norms, then RoPE on a
    sliding layer and NOTHING on a global one."""
    if cfg.qk_norm_per_head:
        q, k = rms(q, q_norm, cfg.norm_eps), rms(k, k_norm, cfg.norm_eps)
    if sliding:
        q, k = rope(q, cos, sin), rope(k, cos, sin)
    return q, k


def gated(cfg, o, g):
    """Attention output ``o`` times sigmoid of the gate projection."""
    if not cfg.attn_output_gate:
        return o
    return (o.astype(F32) * jax.nn.sigmoid(g.astype(F32))).astype(o.dtype)


def close(cfg, x, y, post_scale):
    """A sub-block's output ``y`` into the stream: through its post-norm
    where the model has one (the sandwich)."""
    if cfg.sandwich_norm:
        y = rms(y, post_scale, cfg.norm_eps)
    return x + y.astype(x.dtype)


def kept_groups(cfg, sel):
    """The group limit on selection scores ``sel`` [N, R] >= 0: a group of
    ``R / moe_n_group`` consecutive experts scores the sum of its two best,
    and the ``moe_topk_group`` best groups are kept.  Returns kept [N, G]
    bool."""
    N, R = sel.shape
    G = cfg.moe_n_group
    score = jax.lax.top_k(sel.reshape(N, G, R // G), 2)[0].sum(-1)
    _, best = jax.lax.top_k(score, cfg.moe_topk_group)
    return jnp.zeros((N, G), bool).at[jnp.arange(N)[:, None], best].set(True)


def route(cfg, h, gate_w, gate_bias=None):
    """Router of one layer on rows ``h`` [N, D] -> (weight [N, k] float32,
    idx [N, k] over the ROUTER's experts, the groups kept [N, G] bool | None
    with one group).  Scores in float32 at full precision; the bias joins
    the selection only; with more than one group the selection is limited
    to the kept groups (:func:`kept_groups`); the kept scores are normalised
    over the k (``moe_norm_topk_prob``) and scaled."""
    from deepspeed_tpu.moe import sharded_moe

    logits = jnp.dot(h.astype(F32), gate_w.astype(F32),
                     precision=jax.lax.Precision.HIGHEST)
    s = (jax.nn.sigmoid(logits) if cfg.moe_score_func == "sigmoid"
         else jax.nn.softmax(logits, axis=-1))
    sel = s + gate_bias.astype(F32) if cfg.moe_select_bias else s
    kept = None
    if cfg.moe_n_group > 1:
        kept = kept_groups(cfg, sel)
        sel = jnp.where(jnp.repeat(kept, sel.shape[1] // cfg.moe_n_group,
                                   axis=1), sel, 0.0)
    # through the module, so that benchmarks/lib/serve_taps.py sees the choice
    _, idx = sharded_moe.topk_weights(sel, cfg.num_experts_per_tok, False)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.moe_norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg.moe_route_scale, idx, kept


def held_group_kept(cfg, kept):
    """Rows [N] bool whose kept groups include a group with an expert held
    here."""
    size = cfg.moe_router_experts // cfg.moe_n_group
    first = cfg.moe_first_expert // size
    last = (cfg.moe_first_expert + cfg.num_experts - 1) // size
    return kept[:, first:last + 1].any(-1)


def held(cfg, weight, idx):
    """The assignments to experts held here: (weight, 0 elsewhere; index
    among the held, ``num_experts`` elsewhere)."""
    local = idx - cfg.moe_first_expert
    here = (local >= 0) & (local < cfg.num_experts)
    return (jnp.where(here, weight, 0.0),
            jnp.where(here, local, cfg.num_experts))


def glu_mlp(h, m, act: str = "silu"):
    """The gated MLP ``(silu(h Wgate) * (h Wup)) Wdown``; where ``m`` has no
    ``w_gate`` the two-matrix form ``act(h Wup) Wdown`` (models/ssm_moe.py's
    relu2 experts)."""
    if "w_gate" not in m:
        from deepspeed_tpu.models.layers import activation_fn
        return activation_fn(act)(h @ m["w_up"].astype(h.dtype)) \
            @ m["w_down"].astype(h.dtype)
    return (jax.nn.silu(h @ m["w_gate"].astype(h.dtype))
            * (h @ m["w_up"].astype(h.dtype))) @ m["w_down"].astype(h.dtype)


def mlp(cfg, lp, h, experts=None, layer=None):
    """The MLP of one layer on ``h`` [B, s, D] (normed): dense, or shared
    experts plus this chip's share of the routed ones (``experts``: the
    STACKED expert arrays, ``layer`` this layer's index in them)."""
    from deepspeed_tpu.moe.sharded_moe import _moe_grouped

    m = lp["mlp"]
    if experts is None:
        return glu_mlp(h, m)
    B, s, D = h.shape
    ht = h.reshape(B * s, D)
    weight, idx, _ = route(cfg, ht, m["gate_w"], m.get("gate_bias"))
    weight, local = held(cfg, weight, idx)
    y, _ = _moe_grouped(experts, ht, None, cfg, False,
                        layer=jnp.asarray(layer, jnp.int32),
                        assign=(weight, local))
    if cfg.num_shared_experts:
        y = y + glu_mlp(ht, m["shared"], cfg.activation)
    return y.reshape(B, s, D)


def attend(q, segments, q_pos, *, window: int, scale: float,
           live_keys=None, expand=None):
    """Causal (and, with ``window`` > 0, sliding-window) attention by an
    online softmax over key blocks: q [B, H, s, Dh] at positions ``q_pos``
    [s]; ``segments`` a list of (k, v [B, Hkv, Sk, Dh], k_pos [Sk]), each
    key masked by ITS position (negative: an empty row), so a ring of rows
    and a chunk's own keys sit side by side.  ``live_keys`` (traced) bounds
    the LAST segment's loop: its keys at or past it are not visited (the
    global layer's view of ``max_out_tokens`` rows, of which a prompt fills
    a part).  Float32 scores, never more than [B, H, s, KEY_BLOCK] of them.
    The values' width is their own.  ``expand`` (a latent layer,
    ``models/kda_mla.py``): a segment is (rows [B, 1, Sk, W], None, k_pos),
    and a key block's per-head keys and values ``expand(rows of the block)``
    [B, Hkv, kb, .] are made when the block is visited."""
    B, H, s, Dh = q.shape
    if expand is None:
        Hkv, Dv = segments[0][0].shape[1], segments[0][1].shape[-1]
    else:
        ks, vs = jax.eval_shape(expand, segments[0][0][:, :, :1])
        Hkv, Dv = ks.shape[1], vs.shape[-1]
    qg = q.reshape(B, Hkv, H // Hkv, s, Dh)
    qp = q_pos[:, None]                                    # [s, 1]

    def block(carry, kb, vb, kp):
        m, l, acc = carry
        if expand is not None:
            kb, vb = expand(kb)
        sc = jnp.einsum("bgrqd,bgkd->bgrqk", qg, kb.astype(q.dtype),
                        preferred_element_type=F32) * scale
        ok = (kp[None, :] <= qp) & (kp[None, :] >= 0)
        if window:
            ok = ok & (qp - kp[None, :] < window)
        sc = jnp.where(ok, sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(-1))
        p = jnp.where(ok, jnp.exp(sc - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bgrqk,bgkd->bgrqd", p.astype(vb.dtype), vb,
            preferred_element_type=F32)
        return m_new, alpha * l + p.sum(-1), acc

    carry = (jnp.full(qg.shape[:-1], NEG_INF, F32),
             jnp.zeros(qg.shape[:-1], F32),
             jnp.zeros(qg.shape[:-1] + (Dv,), F32))
    for i, (k, v, k_pos) in enumerate(segments):
        Sk = k.shape[2]
        kb = min(KEY_BLOCK, Sk)
        pad = (-Sk) % kb
        if pad:
            k, v = (t if t is None else
                    jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0)))
                    for t in (k, v))
            k_pos = jnp.pad(k_pos, (0, pad), constant_values=-1)
        n = (Sk + pad) // kb
        if n == 1:
            carry = block(carry, k, v, k_pos)
            continue

        def step(j, carry, k=k, v=v, k_pos=k_pos, kb=kb):
            take = lambda t, ax: t if t is None else \
                jax.lax.dynamic_slice_in_dim(t, j * kb, kb, axis=ax)
            return block(carry, take(k, 2), take(v, 2), take(k_pos, 0))

        if live_keys is not None and i == len(segments) - 1:
            n = jnp.minimum(n, (live_keys + kb - 1) // kb)
        carry = jax.lax.fori_loop(0, n, step, carry)
    _, l, acc = carry
    o = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return o.reshape(B, H, s, Dv).astype(q.dtype)


def keys_visited(rows: int, live_keys: int) -> int:
    """Keys of a LAST segment of ``rows`` keys that :func:`attend` visits
    under ``live_keys``: whole key blocks up to the one that holds key
    ``live_keys - 1`` (the host's copy of the loop bound above, for the
    counters of ``serving/cache_kind.py``)."""
    kb = min(KEY_BLOCK, rows)
    return min(-(-rows // kb), -(-live_keys // kb)) * kb


def _project(cfg, lp, x, cos, sin, sliding):
    """Norm, the four projections, head norms and RoPE: q [B, s, H, Dh],
    k, v [B, s, Hkv, Dh], g [B, s, H * Dh] | None, all in ``x``'s dtype."""
    B, s, _ = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    a = lp["attn"]
    h = rms(x, lp["attn_norm"]["scale"], cfg.norm_eps)
    w = lambda n: a[n].astype(h.dtype)
    q = (h @ w("wq")).reshape(B, s, H, Dh)
    k = (h @ w("wk")).reshape(B, s, Hkv, Dh)
    v = (h @ w("wv")).reshape(B, s, Hkv, Dh)
    g = h @ w("wg") if cfg.attn_output_gate else None
    q, k = head_norm_rope(
        cfg, a.get("q_norm", {}).get("scale"), a.get("k_norm", {}).get("scale"),
        q, k, cos, sin, sliding)
    return q, k, v, g


def mlp_block(cfg, lp, x, a, experts, layer):
    """The attention sub-block's output ``a`` [B, s, D] into the stream,
    then the MLP sub-block: the layer's end, whatever kind attended."""
    x = close(cfg, x, a, lp.get("attn_post_norm", {}).get("scale"))
    h = rms(x, lp["mlp_norm"]["scale"], cfg.norm_eps)
    return close(cfg, x, mlp(cfg, lp, h, experts, layer),
                 lp.get("mlp_post_norm", {}).get("scale"))


def _finish_layer(cfg, lp, x, o, g, experts, layer):
    """From the attention output o [B, s, H * Dh] to the layer's end."""
    a = gated(cfg, o, g) @ lp["attn"]["wo"].astype(o.dtype)
    return mlp_block(cfg, lp, x, a, experts, layer)


def _experts(params):
    ly = params.get("layers")
    return None if ly is None else {k: ly["mlp"][k]
                                    for k in ("w_up", "w_gate", "w_down")
                                    if k in ly["mlp"]}


def embed(cfg, table, tokens, dtype):
    x = jnp.take(table, tokens, axis=0).astype(F32) * cfg.embed_scale
    return x.astype(dtype)


# ----------------------------------------------------------------------
# forward 1: no cache (CausalLM.apply)
# ----------------------------------------------------------------------
def apply_layers(cfg, params, x, mesh=None):
    """The layer stack on ``x`` [B, S, D] (embedded, scaled), positions
    ``0 .. S - 1``: a static loop over the pattern."""
    refuse_parallel(cfg, mesh, "CausalLM.apply")
    B, S, _ = x.shape
    pos = jnp.arange(S)
    cos, sin = angles(cfg, pos)
    heads = lambda t: t.transpose(0, 2, 1, 3)
    for l in range(cfg.num_layers):
        lp, le = layer_params(cfg, params, l)
        sliding = is_sliding(cfg, l)
        q, k, v, g = _project(cfg, lp, x, cos, sin, sliding)
        o = attend(heads(q), [(heads(k), heads(v), pos)], pos,
                   window=cfg.sliding_window if sliding else 0,
                   scale=cfg.head_dim ** -0.5)
        o = heads(o).reshape(B, S, -1)
        x = _finish_layer(cfg, lp, x, o, g,
                          None if le is None else _experts(params), le)
    return x


# ----------------------------------------------------------------------
# forward 2: a prefill chunk on one slot's gathered views
# ----------------------------------------------------------------------
def ring_positions(start, window: int):
    """The position each ring row holds before a chunk that starts at
    ``start``: the largest ``p < start`` with ``p % W == r`` (negative: the
    row was never written)."""
    r = jnp.arange(window)
    return start - 1 - ((start - 1 - r) % window)


def cached_layers(cfg, params, x, cache, start, valid_len):
    """The layer stack on a chunk ``x`` [1, s, D] at positions ``start ..``
    over the slot's views (``k_win`` / ``v_win`` [sliding layers, 1, Hkv, W, Dh]
    rings, ``k_full`` / ``v_full`` [global layers, 1, Hkv, positions, Dh]:
    what ``cache_kind.TwoBudgets.view`` gathers); only the first
    ``valid_len`` rows are real (the rest pad the bucket).  Returns
    (x, views).  Whether the chunk lies before, across or past the window,
    and whether the ring has wrapped, is data: one program a bucket."""
    B, s, _ = x.shape
    assert B == 1, "a chunk program prefills one slot"
    W = cfg.sliding_window
    assert s <= W or not any(is_sliding(cfg, l) for l in
                             range(cfg.num_layers)), (s, W)
    start = jnp.asarray(start, jnp.int32)
    pos = start + jnp.arange(s)
    cos, sin = angles(cfg, pos)
    heads = lambda t: t.transpose(0, 2, 1, 3)
    k_win, v_win = cache["k_win"], cache["v_win"]
    k_full, v_full = cache["k_full"], cache["v_full"]
    # ring rows of the real tokens; a pad row goes nowhere (mode="drop")
    ring_row = jnp.where(jnp.arange(s) < valid_len, pos % max(W, 1), W)
    held_pos = ring_positions(start, W) if W else None
    i_win = i_full = 0
    for l in range(cfg.num_layers):
        lp, le = layer_params(cfg, params, l)
        sliding = is_sliding(cfg, l)
        q, k, v, g = _project(cfg, lp, x, cos, sin, sliding)
        kh, vh = heads(k), heads(v)                    # [1, Hkv, s, Dh]
        if sliding:
            # attend BEFORE appending: the ring as the earlier chunks left
            # it, beside this chunk's own keys
            o = attend(heads(q),
                       [(k_win[i_win], v_win[i_win], held_pos),
                        (kh, vh, pos)],
                       pos, window=W, scale=cfg.head_dim ** -0.5)
            put = lambda buf, t: buf.at[i_win, 0, :, ring_row, :].set(
                t[0].transpose(1, 0, 2).astype(buf.dtype), mode="drop")
            k_win, v_win = put(k_win, kh), put(v_win, vh)
            i_win += 1
        else:
            at = (i_full, 0, 0, start, 0)
            k_full = jax.lax.dynamic_update_slice(
                k_full, kh[None].astype(k_full.dtype), at)
            v_full = jax.lax.dynamic_update_slice(
                v_full, vh[None].astype(v_full.dtype), at)
            o = attend(heads(q),
                       [(k_full[i_full], v_full[i_full],
                         jnp.arange(k_full.shape[3]))],
                       pos, window=0, scale=cfg.head_dim ** -0.5,
                       live_keys=start + s)
            i_full += 1
        o = heads(o).reshape(B, s, -1)
        x = _finish_layer(cfg, lp, x, o, g,
                          None if le is None else _experts(params), le)
    return x, {"k_win": k_win, "v_win": v_win,
               "k_full": k_full, "v_full": v_full}


# ----------------------------------------------------------------------
# forward 3: one decode step through the fused kernels and the paged pool
# ----------------------------------------------------------------------
def inject(cfg, params) -> Dict[str, Any]:
    """The kernel-injected view (``fused_decode.inject_decode_params``):
    per-layer dicts with their own buffers, q, k, v AND the gate projection
    in one ``[D, N]`` matrix; the stacked routed experts by reference."""
    layers = []
    for l in range(cfg.num_layers):
        lp, le = layer_params(cfg, params, l)
        a = lp["attn"]
        cols = [a["wq"], a["wk"], a["wv"]] + (
            [a["wg"]] if cfg.attn_output_gate else [])
        d = {"wqkv": jnp.concatenate(cols, axis=-1), "wo": a["wo"],
             **inject_rest(cfg, lp, le)}
        if cfg.qk_norm_per_head:
            d["q_norm"], d["k_norm"] = a["q_norm"]["scale"], a["k_norm"]["scale"]
        layers.append(d)
    return inject_outer(params, layers)


def inject_rest(cfg, lp, le) -> Dict[str, Any]:
    """A layer's injected entries beside its attention's: the norms and the
    MLP (dense), or the router and the shared expert (expert layer)."""
    m = lp["mlp"]
    d = {"n1_scale": lp["attn_norm"]["scale"],
         "n2_scale": lp["mlp_norm"]["scale"]}
    if cfg.sandwich_norm:
        d["n1_post"] = lp["attn_post_norm"]["scale"]
        d["n2_post"] = lp["mlp_post_norm"]["scale"]
    if le is None:
        d.update({k: m[k] for k in ("w_up", "w_gate", "w_down")})
    else:
        d["gate_w"] = m["gate_w"]
        if cfg.moe_select_bias:
            d["gate_bias"] = m["gate_bias"]
        if cfg.num_shared_experts:
            d["shared"] = m["shared"]
    return d


def inject_outer(params, layers) -> Dict[str, Any]:
    out = {"embed": params["embed"], "final_norm": params["final_norm"],
           "layers": tuple(layers)}
    if "lm_head" in params:       # absent: the head is the embedding's
        out["lm_head"] = params["lm_head"]
    if _experts(params) is not None:
        out["experts"] = _experts(params)
    return out


def moe_counts_zero(cfg):
    """Zeros of :func:`fused_layers`' routing counts: assignments per HELD
    expert [E], (layer, expert) pairs hit, the fullest expert's rows, and
    the assignments the live rows OFFERED (k a row and expert layer, held
    here or not); under a group limit a fifth, the (live row, expert layer)
    pairs whose kept groups include one with an expert held here."""
    z = jnp.zeros((), jnp.int32)
    return (jnp.zeros((cfg.num_experts,), jnp.int32), z, z, z) \
        + ((z,) if cfg.moe_n_group > 1 else ())


def fused_close(cfg, dparams, lp, l: int, ctx, x, stats, moe_live, impl):
    """Layer ``l`` of the fused path from its attention's output ``ctx``
    [B, M] (gated, before ``wo``) to its end, whatever kind attended: the
    output projection, the norms, the dense MLP or the expert block, and the
    routing counts.  Returns (x, stats)."""
    from deepspeed_tpu.ops.pallas.decode import fused_mlp, fused_proj_norm

    eps = cfg.norm_eps
    zeros = jnp.zeros_like(x)
    if cfg.sandwich_norm:
        # the post-norm sits between the projection and its residual
        # add: the kernel projects onto a zero stream and norms that
        _, a = fused_proj_norm(ctx, zeros, lp["wo"], None, lp["n1_post"],
                               None, kind="rmsnorm", eps=eps, impl=impl)
        x = x + a.astype(x.dtype)
        h = rms(x, lp["n2_scale"], eps)
    else:
        x, h = fused_proj_norm(ctx, x, lp["wo"], None, lp["n2_scale"],
                               None, kind="rmsnorm", eps=eps, impl=impl)
    base = zeros if cfg.sandwich_norm else x
    if "gate_w" not in lp:
        y = fused_mlp(h, base, lp["w_up"], lp["w_down"], lp["w_gate"],
                      act=cfg.activation, impl=impl)
    else:
        y, stats = fused_experts(cfg, dparams, lp, l - cfg.num_dense_layers,
                                 h, base, stats, moe_live, impl)
    x = close(cfg, x, y, lp.get("n2_post")) if cfg.sandwich_norm else y
    return x, stats


def fused_experts(cfg, dparams, lp, le: int, h, base, stats, moe_live, impl):
    """The expert block of the fused path on normed rows ``h`` [B, D]:
    ``base`` plus the shared expert plus this chip's share of the routed
    ones (expert layer ``le`` of the stacked arrays; experts of three
    matrices, or of two where the stack has no ``w_gate``), and the routing
    counts.  Returns (y, stats)."""
    from deepspeed_tpu.ops.pallas.decode import fused_mlp, fused_moe_mlp

    if cfg.num_shared_experts:
        sh = lp["shared"]
        base = fused_mlp(h, base, sh["w_up"], sh["w_down"],
                         sh.get("w_gate"), act=cfg.activation, impl=impl)
    weight, idx, kept = route(cfg, h, lp["gate_w"], lp.get("gate_bias"))
    weight, local = held(cfg, weight, idx)
    onehot = jax.nn.one_hot(local, cfg.num_experts, dtype=F32)
    combine = jnp.sum(onehot * weight[..., None], axis=1)
    ex = dparams["experts"]
    y = fused_moe_mlp(h, base, combine, ex["w_up"], ex["w_down"],
                      ex.get("w_gate"), layer=le, act=cfg.activation,
                      live=moe_live, impl=impl)
    if stats is not None:
        load = jnp.sum((jnp.sum(onehot, axis=1) > 0)
                       & moe_live[:, None], axis=0, dtype=jnp.int32)
        rest = stats[4:]
        if kept is not None:      # the group limit's count leads them
            rest = (rest[0] + jnp.sum(
                held_group_kept(cfg, kept) & moe_live,
                dtype=jnp.int32),) + rest[1:]
        stats = (stats[0] + load,
                 stats[1] + jnp.sum(load > 0, dtype=jnp.int32),
                 stats[2] + jnp.max(load),
                 stats[3] + jnp.sum(moe_live, dtype=jnp.int32)
                 * cfg.num_experts_per_tok) + rest
    return y, stats


def fused_layers(cfg, dparams, x, cache, pos, page_table, *, moe_live=None,
                 impl: Optional[str] = None):
    """The layer stack for one token a row: ``x`` [B, D] at per-row
    positions ``pos`` [B] over the two paged budgets (``cache``: ``k_win`` /
    ``v_win`` [sliding layers, window pages, Hkv, page, Dh], ``k_full`` /
    ``v_full`` [global layers, full pages, ...]; ``page_table`` [B, window
    columns + full columns], ``serving/paged_kv.py``).  A sliding layer
    writes row ``pos % W`` of the ring and attends rows ``<= min(pos, W - 1)``:
    once the ring has wrapped that is all of it, which is exactly the window.
    ``moe_live`` [B] bool: the rows that decode, which the attention kernels
    visit and the routing counts cover (``fused_decode.decode_step``).
    Returns (x, cache, routing counts | None)."""
    from deepspeed_tpu.ops.pallas.decode import (flash_decode, fused_norm_qkv,
                                                 paged_kv_append)

    B = x.shape[0]
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    M, Mkv, W = H * Dh, Hkv * Dh, cfg.sliding_window
    eps, scale = cfg.norm_eps, Dh ** -0.5
    k_win, v_win = cache["k_win"], cache["v_win"]
    k_full, v_full = cache["k_full"], cache["v_full"]
    wp = W // k_win.shape[3] if k_win.shape[0] else 0   # window columns
    win_table, full_table = page_table[:, :wp], page_table[:, wp:]
    cos, sin = angles(cfg, pos)                       # [B, 1, Dh/2]
    ring_row, ring_len = pos % max(W, 1), jnp.minimum(pos, W - 1)
    stats = moe_counts_zero(cfg) if moe_live is not None else None
    i_win = i_full = 0
    for l, lp in enumerate(dparams["layers"]):
        sliding = is_sliding(cfg, l)
        qkv = fused_norm_qkv(x, lp["n1_scale"], None, lp["wqkv"], None,
                             kind="rmsnorm", eps=eps, impl=impl)
        q = qkv[:, :M].reshape(B, H, Dh)
        k = qkv[:, M:M + Mkv].reshape(B, Hkv, Dh)
        v = qkv[:, M + Mkv:M + 2 * Mkv].reshape(B, Hkv, Dh)
        g = qkv[:, M + 2 * Mkv:] if cfg.attn_output_gate else None
        q, k = head_norm_rope(cfg, lp.get("q_norm"), lp.get("k_norm"), q, k,
                              cos, sin, sliding)
        if sliding:
            k_win, v_win = paged_kv_append(k_win, v_win, k, v, ring_row,
                                           win_table, layer=i_win, impl=impl)
            ctx = flash_decode(q, k_win, v_win, ring_len, sm_scale=scale,
                               layer=i_win, page_table=win_table,
                               live=moe_live, impl=impl)
            i_win += 1
        else:
            k_full, v_full = paged_kv_append(k_full, v_full, k, v, pos,
                                             full_table, layer=i_full,
                                             impl=impl)
            ctx = flash_decode(q, k_full, v_full, pos, sm_scale=scale,
                               layer=i_full, page_table=full_table,
                               live=moe_live, impl=impl)
            i_full += 1
        x, stats = fused_close(cfg, dparams, lp, l,
                               gated(cfg, ctx.reshape(B, M), g), x, stats,
                               moe_live, impl)
    return x, {"k_win": k_win, "v_win": v_win,
               "k_full": k_full, "v_full": v_full}, stats

