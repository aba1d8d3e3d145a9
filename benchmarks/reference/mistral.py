"""Plain reference forward of Mistral-7B (Jiang et al. 2023,
arXiv:2310.06825; the layer equations as in HF ``MistralForCausalLM``
v0.3, which has no sliding window): float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching, independent of ``deepspeed_tpu.models``.

    x = embed[tokens]
    per layer:
        h = RMSNorm(x; input_layernorm)
        q, k, v = h Wq, h Wk, h Wv       (32 query heads, 8 KV heads, d 128)
        q, k = RoPE(q), RoPE(k)          (rotate-half form, theta 1e6)
        x = x + softmax(causal(q k^T / sqrt(d))) v Wo   (each KV head
                                          serves 4 query heads)
        h = RMSNorm(x; post_attention_layernorm)
        x = x + (silu(h W_gate) * (h W_up)) W_down
    logits = RMSNorm(x; norm) lm_head    (untied)

It runs layer by layer on weights cast up to float32 one layer at a time,
so it never holds a second copy of the model.  The only thing it knows of
the program is the NAMES in its weight tree (:func:`layer_weights`,
:func:`outer_weights`).  The depth is the configuration file's.
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


def layer_weights(params, l, device):
    ly = params["layers"]
    g = lambda a, b: _up(ly[a][b][l], device)
    return {"input_layernorm": g("attn_norm", "scale"),
            "post_attention_layernorm": g("mlp_norm", "scale"),
            "wq": g("attn", "wq"), "wk": g("attn", "wk"),
            "wv": g("attn", "wv"), "wo": g("attn", "wo"),
            "w_gate": g("mlp", "w_gate"), "w_up": g("mlp", "w_up"),
            "w_down": g("mlp", "w_down")}


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
    heads; queries in blocks so the [H, block, S] scores fit at 8k."""
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


@functools.partial(jax.jit,
                   static_argnames=("n_head", "n_kv", "eps", "theta"))
def layer(x, w, *, n_head, n_kv, eps, theta):
    S, D = x.shape
    d = w["wq"].shape[1] // n_head
    h = rms_norm(x, w["input_layernorm"], eps)
    heads = lambda t, n: t.reshape(S, n, d).transpose(1, 0, 2)
    a = causal_attention(rope(heads(h @ w["wq"], n_head), theta),
                         rope(heads(h @ w["wk"], n_kv), theta),
                         heads(h @ w["wv"], n_kv))
    x = x + a.transpose(1, 0, 2).reshape(S, n_head * d) @ w["wo"]
    h = rms_norm(x, w["post_attention_layernorm"], eps)
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def hidden_states(params, config, tokens, device):
    with jax.default_matmul_precision("highest"):
        outer = outer_weights(params, device)
        tokens = jax.device_put(jnp.asarray(tokens, jnp.int32), device)
        x = outer["embed"][tokens]
        for l in range(config["num_hidden_layers"]):
            x = layer(x, layer_weights(params, l, device),
                      n_head=config["num_attention_heads"],
                      n_kv=config["num_key_value_heads"],
                      eps=config["rms_norm_eps"], theta=config["rope_theta"])
        return x, outer


def logits_rows(params, config, tokens, rows, device):
    """Reference logits [len(rows), V] at positions ``rows`` of ``tokens``."""
    x, outer = hidden_states(params, config, tokens, device)
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x[jnp.asarray(rows)], outer["norm"],
                     config["rms_norm_eps"])
        return h @ outer["lm_head"]

