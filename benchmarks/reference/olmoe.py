"""Plain reference forward of OLMoE-1B-7B (Muennighoff et al. 2024,
arXiv:2409.02060), the layer equations as in HF ``OlmoeForCausalLM``
(``transformers/models/olmoe/modeling_olmoe.py``: ``OlmoeAttention``
lines 286-307 for QK-norm, ``OlmoeSparseMoeBlock`` lines 581-600 for the
router, ``OlmoeMLP`` for an expert): float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching, no capacity, independent of ``deepspeed_tpu.models``,
``deepspeed_tpu.moe`` and ``deepspeed_tpu.ops``.

    x = embed[tokens]
    per layer:
        h = RMSNorm(x; input_layernorm)
        q, k, v = h Wq, h Wk, h Wv       (16 query heads = 16 KV heads, d 128)
        q, k = RMSNorm(q; q_norm), RMSNorm(k; k_norm)    over the WHOLE
                                          projection (all heads at once),
                                          before the head split (qk_norm)
        q, k = RoPE(q), RoPE(k)          (rotate-half form, theta 1e4)
        x = x + softmax(causal(q k^T / sqrt(d))) v Wo
        h = RMSNorm(x; post_attention_layernorm)
        p = softmax(h W_router)          (float32, over the 64 experts)
        w, e = top-8 of p                (w / sum(w) only if norm_topk_prob)
        x = x + sum_j w_j * (silu(h W_gate[e_j]) * (h W_up[e_j])) W_down[e_j]
    logits = RMSNorm(x; norm) lm_head    (untied)

The expert block is the published loop: every token runs its eight experts
and no other, whatever the others chose.  It is written as HF writes it, one
expert at a time over all tokens, each expert computed densely and its
output multiplied by the token's weight for it, which is zero where the
token did not choose it.  ``routing=`` (one ``[S, k]`` array of expert
indices per layer) replaces the reference's own top-k choice by the
program's and keeps the reference's weights for those experts: with seeded
routers a token's eighth and ninth expert can sit within a bf16 rounding,
and the tool that measures agreement on the chip uses this to tell a
routing flip from an arithmetic error.  Nothing else uses it.

Departures from the HF file: float32 throughout (HF casts the router
weights to the activations' dtype, bf16 in the released checkpoints);
``clip_qkv`` is null in the published configuration and is not written.

Like ``mistral.py`` it runs layer by layer on weights cast up to float32
one layer at a time, and knows of the program only the NAMES in its weight
tree.  The depth is the configuration file's.
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512


def _up(a, device):
    return jax.device_put(a, device).astype(F32)


def outer_weights(params, device):
    return {"embed": _up(params["embed"]["tok"], device),
            "norm": _up(params["final_norm"]["scale"], device),
            "lm_head": _up(params["lm_head"], device)}


def layer_weights(params, l, device, qk_norm=True):
    ly = params["layers"]
    g = lambda a, b: _up(ly[a][b][l], device)
    w = {"input_layernorm": g("attn_norm", "scale"),
         "post_attention_layernorm": g("mlp_norm", "scale"),
         "wq": g("attn", "wq"), "wk": g("attn", "wk"),
         "wv": g("attn", "wv"), "wo": g("attn", "wo"),
         "router": g("mlp", "gate_w"),
         "w_gate": g("mlp", "w_gate"), "w_up": g("mlp", "w_up"),
         "w_down": g("mlp", "w_down")}
    if qk_norm:
        w["q_norm"] = _up(ly["attn"]["q_norm"]["scale"][l], device)
        w["k_norm"] = _up(ly["attn"]["k_norm"]["scale"][l], device)
    return w


def rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def rope(t, theta):
    """t [H, S, d]: rotate (t[..., :d/2], t[..., d/2:]) pairs by the angle
    pos * theta^(-2i/d)."""
    H, S, d = t.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]      # [S, d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    t1, t2 = t[..., : d // 2], t[..., d // 2:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


def causal_attention(q, k, v):
    """q [H, S, d]; k, v [Hkv, S, d], each KV head shared by H/Hkv query
    heads; queries in blocks so the [H, block, S] scores fit."""
    H, S, d = q.shape
    rep = H // k.shape[0]
    k = jnp.repeat(k, rep, axis=0)
    v = jnp.repeat(v, rep, axis=0)
    block = min(S, QUERY_BLOCK)
    key_pos = jnp.arange(S)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        s = jnp.einsum("hqd,hkd->hqk", qb, k) / jnp.sqrt(F32(d))
        ok = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p, v)

    out = jax.lax.map(one, jnp.arange(0, S, block))    # [nb, H, block, d]
    return out.transpose(1, 0, 2, 3).reshape(H, S, d)


def top_k_choice(p, k):
    """Indices of each row's k largest probabilities, best first."""
    return jax.lax.top_k(p, k)[1]


def expert_block(h, w, *, top_k, norm_topk, chosen=None):
    """h [S, D] -> (sum over each token's top-k experts of weight x expert
    output, the expert indices [S, k] used)."""
    p = jax.nn.softmax(h @ w["router"], axis=-1)                # [S, E]
    if chosen is None:
        chosen = top_k_choice(p, top_k)
    weight = jnp.take_along_axis(p, chosen, axis=-1)            # [S, k]
    if norm_topk:
        weight = weight / weight.sum(-1, keepdims=True)
    # [S, E]: the token's weight for each expert, 0 where not chosen
    per_expert = jnp.zeros_like(p).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(weight)

    def one(acc, ew):
        wg, wu, wd, col = ew
        return acc + col[:, None] * ((jax.nn.silu(h @ wg) * (h @ wu)) @ wd), \
            None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (w["w_gate"], w["w_up"], w["w_down"], per_expert.T))
    return y, chosen


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv", "eps", "theta", "top_k", "norm_topk", "qk_norm"))
def layer(x, w, chosen=None, *, n_head, n_kv, eps, theta, top_k, norm_topk,
          qk_norm):
    S, D = x.shape
    d = w["wq"].shape[1] // n_head
    h = rms_norm(x, w["input_layernorm"], eps)
    q, k = h @ w["wq"], h @ w["wk"]
    if qk_norm:
        q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    heads = lambda t, n: t.reshape(S, n, d).transpose(1, 0, 2)
    a = causal_attention(rope(heads(q, n_head), theta),
                         rope(heads(k, n_kv), theta),
                         heads(h @ w["wv"], n_kv))
    x = x + a.transpose(1, 0, 2).reshape(S, n_head * d) @ w["wo"]
    h = rms_norm(x, w["post_attention_layernorm"], eps)
    y, chosen = expert_block(h, w, top_k=top_k, norm_topk=norm_topk,
                             chosen=chosen)
    return x + y, chosen


def _qk_norm(config):
    """QK-norm is in the HF code, not behind a key of the published
    configuration; the configuration file states it under ``qk_norm``."""
    return bool(config.get("qk_norm", True))


def hidden_states(params, config, tokens, device, routing=None,
                  return_routing=False):
    """Final hidden states [S, D] and the outer weights; with
    ``return_routing`` also the expert indices used, [L, S, k]."""
    with jax.default_matmul_precision("highest"):
        outer = outer_weights(params, device)
        tokens = jax.device_put(jnp.asarray(tokens, jnp.int32), device)
        x = outer["embed"][tokens]
        used = []
        for l in range(config["num_hidden_layers"]):
            x, chosen = layer(
                x, layer_weights(params, l, device, _qk_norm(config)),
                None if routing is None else jnp.asarray(routing[l]),
                n_head=config["num_attention_heads"],
                n_kv=config["num_key_value_heads"],
                eps=config["rms_norm_eps"], theta=float(config["rope_theta"]),
                top_k=config["num_experts_per_tok"],
                norm_topk=bool(config["norm_topk_prob"]),
                qk_norm=_qk_norm(config))
            used.append(chosen)
        if return_routing:
            return x, outer, jnp.stack(used)
        return x, outer


def logits_rows(params, config, tokens, rows, device, routing=None):
    """Reference logits [len(rows), V] at positions ``rows`` of ``tokens``."""
    x, outer = hidden_states(params, config, tokens, device, routing)
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x[jnp.asarray(rows)], outer["norm"],
                     config["rms_norm_eps"])
        return h @ outer["lm_head"]
