"""Plain reference forward of Ouro-2.6B (ByteDance, "Ouro 1.4B/2.6B LoopLM";
``config.json`` ``model_type`` ``ouro``): float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching, independent of ``deepspeed_tpu.models``.

ONE stack of ``L`` layers runs ``T = total_ut_steps`` times with the same
weights.  ``N`` = RMSNorm with its own gain, eps 1e-6:

    x = E[tokens]                                         E [49152, 2048]; head untied
    for t in 0 .. T-1:                                    T = total_ut_steps = 4
        for l in 0 .. L-1:                                THE SAME weights in every pass
            h = N1_l(x);  q, k, v = h Wq_l, h Wk_l, h Wv_l        16 heads of 128, 16 KV heads, no bias
            q, k <- RoPE(q, k; position p, theta 1e6)             the same p in every pass; the Llama
                                                                  backbone's half-split pairs
            cache[t * L + l][p] <- (k, v)                         a cache layer a (pass, layer) pair
            a = softmax(q K^T / sqrt(128)) V   over j <= p of cache layer t * L + l ONLY
            x = x + N2_l(a Wo_l)                                  post-norm on the sub-block's OUTPUT
            h = N3_l(x);  m = (silu(h Wg_l) * (h Wu_l)) Wd_l      width 5,632
            x = x + N4_l(m)
        x = N_f(x)                                        closes EVERY pass and feeds the next
        g_t = sigmoid(x w_e + b_e)                        the exit gate, w_e [2048, 1]
    logits = x W_head                                     from the last pass
    exit distribution: p_t = g_t prod_{s<t} (1 - g_s) for t < T-1, p_{T-1} = prod_{s<T-1} (1 - g_s);
    a token leaves at the first t whose cumulated p reaches early_exit_threshold; at 1 that is T-1.

There is no cache here: in pass ``t`` a layer attends the keys and values
that SAME pass made at the earlier positions, which is what "cache layer
``t * L + l`` only" means.  ASSUMED, as the configuration's file lists
(neither the release's modeling code nor arXiv:2510.25741 is on this
machine): the order of the four norms a layer (``input_layernorm``,
``input_layernorm_2`` on the attention output, ``post_attention_layernorm``,
``post_attention_layernorm_2`` on the MLP output), the final norm inside the
pass loop, the same rotary positions in every pass, the gate a Linear with
bias on the normed stream, threshold 1 read as "the last pass's logits for
every token".

It runs pass by pass and layer by layer on weights cast up to float32 one
layer at a time.  The only thing it knows of the program is the NAMES in its
weight tree (:func:`layer_weights`, :func:`outer_weights`).  Depth and the
number of passes are the configuration file's.
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512


def _up(a, device):
    return jax.device_put(a, device).astype(F32)


def outer_weights(params, device):
    out = {"embed": _up(params["embed"]["tok"], device),
           "norm": _up(params["final_norm"]["scale"], device),
           "lm_head": _up(params["lm_head"], device)}
    if "exit_gate" in params:
        out["gate_w"] = _up(params["exit_gate"]["w"], device)
        out["gate_b"] = _up(params["exit_gate"]["b"], device)
    return out


def layer_weights(params, l, device):
    ly = params["layers"]
    g = lambda a, b: _up(ly[a][b][l], device)
    return {"input_layernorm": g("attn_norm", "scale"),
            "input_layernorm_2": g("attn_post_norm", "scale"),
            "post_attention_layernorm": g("mlp_norm", "scale"),
            "post_attention_layernorm_2": g("mlp_post_norm", "scale"),
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
    a = a.transpose(1, 0, 2).reshape(S, n_head * d) @ w["wo"]
    x = x + rms_norm(a, w["input_layernorm_2"], eps)
    h = rms_norm(x, w["post_attention_layernorm"], eps)
    m = (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
    return x + rms_norm(m, w["post_attention_layernorm_2"], eps)


def hidden_states(params, config, tokens, device):
    """(the last pass's normed stream [S, D], the outer weights, the exit
    gates g [T, S] or None where the weights have no gate)."""
    with jax.default_matmul_precision("highest"):
        outer = outer_weights(params, device)
        tokens = jax.device_put(jnp.asarray(tokens, jnp.int32), device)
        x = outer["embed"][tokens]
        gates = []
        for _ in range(config["total_ut_steps"]):
            for l in range(config["num_hidden_layers"]):
                x = layer(x, layer_weights(params, l, device),
                          n_head=config["num_attention_heads"],
                          n_kv=config["num_key_value_heads"],
                          eps=config["rms_norm_eps"],
                          theta=config["rope_theta"])
            x = rms_norm(x, outer["norm"], config["rms_norm_eps"])
            if "gate_w" in outer:
                gates.append(jax.nn.sigmoid(
                    (x @ outer["gate_w"])[:, 0] + outer["gate_b"][0]))
        return x, outer, (jnp.stack(gates) if gates else None)


def exit_distribution(gates):
    """g [T, S] -> p [S, T]: the probability that a token leaves after pass
    t, the rest of the mass on the last pass."""
    T = gates.shape[0]
    p, stay = [], jnp.ones_like(gates[0])
    for t in range(T - 1):
        p.append(gates[t] * stay)
        stay = stay * (1.0 - gates[t])
    return jnp.stack(p + [stay], axis=-1)


def exit_pass(p, threshold):
    """The first pass at which the cumulated ``p`` [S, T] reaches
    ``threshold``: [S].  At 1 that is T - 1 for every token (the cumulated
    mass of T - 1 passes stays under 1 while a gate is under 1)."""
    T = p.shape[-1]
    reached = jnp.cumsum(p[:, :-1], axis=-1) >= threshold
    return jnp.where(reached.any(-1), reached.argmax(-1), T - 1)


def logits_rows(params, config, tokens, rows, device):
    """Reference logits [len(rows), V] at positions ``rows`` of ``tokens``:
    the last pass's (``early_exit_threshold`` 1)."""
    x, outer, _ = hidden_states(params, config, tokens, device)
    with jax.default_matmul_precision("highest"):
        return x[jnp.asarray(rows)] @ outer["lm_head"]
