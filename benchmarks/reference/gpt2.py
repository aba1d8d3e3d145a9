"""Plain reference forward of GPT-2 (Radford et al. 2019; the layer
equations as in HF ``GPT2LMHeadModel``): float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching, independent of ``deepspeed_tpu.models``.

    x   = wte[tokens] + wpe[positions]
    per layer:
        h = LayerNorm(x; ln_1)
        q, k, v = h Wq + bq, h Wk + bk, h Wv + bv      (n_head heads)
        x = x + softmax(causal(q k^T / sqrt(d_head))) v Wo + bo
        h = LayerNorm(x; ln_2)
        x = x + gelu_new(h W_fc + b_fc) W_proj + b_proj
    logits = LayerNorm(x; ln_f) wte^T                  (tied head)

It runs layer by layer on weights cast up to float32 one layer at a time
(:func:`layer_weights`), so it never holds a second copy of the model.
The only thing it knows of the program is the NAMES in its weight tree,
in :func:`layer_weights` and :func:`outer_weights`.  Departure from the
source: dropout is off (as in the configuration file).
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512


def _up(a, device):
    return jax.device_put(a, device).astype(F32)


def outer_weights(params, device):
    """Embeddings and the final norm, float32 on ``device``."""
    return {"wte": _up(params["embed"]["tok"], device),
            "wpe": _up(params["embed"]["pos"], device),
            "ln_f": (_up(params["final_norm"]["scale"], device),
                     _up(params["final_norm"]["bias"], device))}


def layer_weights(params, l, device):
    """Layer ``l`` of the program's stacked tree, float32 on ``device``."""
    ly = params["layers"]
    g = lambda *path: _up(functools.reduce(lambda t, k: t[k], path, ly)[l],
                          device)
    zeros = lambda like: jnp.zeros(like.shape[-1:], F32)
    w = {"ln_1": (g("attn_norm", "scale"), g("attn_norm", "bias")),
         "ln_2": (g("mlp_norm", "scale"), g("mlp_norm", "bias")),
         "wq": g("attn", "wq"), "wk": g("attn", "wk"), "wv": g("attn", "wv"),
         "wo": g("attn", "wo"), "w_fc": g("mlp", "w_up"),
         "w_proj": g("mlp", "w_down")}
    for name, path, like in (("bq", ("attn", "bq"), "wq"),
                             ("bk", ("attn", "bk"), "wk"),
                             ("bv", ("attn", "bv"), "wv"),
                             ("bo", ("attn", "bo"), "wo"),
                             ("b_fc", ("mlp", "b_up"), "w_fc"),
                             ("b_proj", ("mlp", "b_down"), "w_proj")):
        w[name] = g(*path) if path[1] in ly[path[0]] else zeros(w[like])
    return w


def layer_norm(x, scale_bias, eps):
    scale, bias = scale_bias
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def causal_attention(q, k, v):
    """q [H, S, d], k and v [H, S, d] -> [H, S, d]; queries in blocks so
    that the [H, block, S] scores fit for long contexts."""
    H, S, d = q.shape
    block = min(S, QUERY_BLOCK)
    key_pos = jnp.arange(S)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        s = jnp.einsum("hqd,hkd->hqk", qb, k) / jnp.sqrt(F32(d))
        ok = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p, v)

    starts = jnp.arange(0, S, block)
    out = jax.lax.map(one, starts)                     # [nb, H, block, d]
    return out.transpose(1, 0, 2, 3).reshape(H, S, d)


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def layer(x, w, *, n_head, eps):
    S, D = x.shape
    h = layer_norm(x, w["ln_1"], eps)
    heads = lambda t: t.reshape(S, n_head, D // n_head).transpose(1, 0, 2)
    a = causal_attention(heads(h @ w["wq"] + w["bq"]),
                         heads(h @ w["wk"] + w["bk"]),
                         heads(h @ w["wv"] + w["bv"]))
    x = x + a.transpose(1, 0, 2).reshape(S, D) @ w["wo"] + w["bo"]
    h = layer_norm(x, w["ln_2"], eps)
    return x + gelu_new(h @ w["w_fc"] + w["b_fc"]) @ w["w_proj"] + w["b_proj"]


def hidden_states(params, config, tokens, device):
    """[S] token ids -> the last layer's output [S, D], float32."""
    with jax.default_matmul_precision("highest"):
        outer = outer_weights(params, device)
        tokens = jax.device_put(jnp.asarray(tokens, jnp.int32), device)
        x = outer["wte"][tokens] + outer["wpe"][: tokens.shape[0]]
        for l in range(config["n_layer"]):
            x = layer(x, layer_weights(params, l, device),
                      n_head=config["n_head"],
                      eps=config["layer_norm_epsilon"])
        return x, outer


def logits_rows(params, config, tokens, rows, device):
    """Reference logits [len(rows), V] at positions ``rows`` of ``tokens``."""
    x, outer = hidden_states(params, config, tokens, device)
    with jax.default_matmul_precision("highest"):
        h = layer_norm(x[jnp.asarray(rows)], outer["ln_f"],
                       config["layer_norm_epsilon"])
        return h @ outer["wte"].T

