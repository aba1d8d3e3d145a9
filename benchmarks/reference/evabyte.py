"""Plain reference forward of EvaByte (EvaByte/EvaByte, config.json: EVA
attention, arXiv:2302.04542, in the deterministic per-head form of the
release): float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching, independent of ``deepspeed_tpu.models``.  Written from the
equations of ISSUE 32, not from the package's code.

    d = 128, W = window_size (2048), C = chunk_size (16), 32 heads
    x = embed[bytes]                                  (float32 throughout)
    per layer:
        a = RMSNorm(x) * (1 + g1)                     [norm_add_unit_offset]
        q, k, v = a Wq, a Wk, a Wv ; q, k = RoPE(q), RoPE(k)   (theta 1e5,
                                    absolute positions, rotate-half)
        per head h, chunk c = positions 16c .. 16c+15, roped keys:
            ktilde_c = sum_j softmax_j(k_j . mu_h)  k_j
            vtilde_c = sum_j softmax_j(k_j . phi_h) v_j
        query i, w = i // W: ONE softmax over
            d^-1/2 q_i . k_j       for  wW <= j <= i      (its own window)
            d^-1/2 q_i . ktilde_c  for  c < w * W/C       (earlier windows)
        o_i = sum_j p_j v_j + sum_c p_c vtilde_c ;  x = x + o Wo
        m = RMSNorm(x) * (1 + g2) ;  x = x + (silu(m Wg) * (m Wu)) Wd
    logits = (RMSNorm(x) * (1 + g)) Whead             Whead [D, 8 * 320]
    head p = columns [320p, 320(p+1)) predicts byte i + 1 + p; the served
    token is head 0's argmax.

``assumed`` (the configuration file lists them with their reasons): (a) no
constant factor on the two pooling logits ``k . mu`` and ``k . phi`` (a
positive constant is absorbed by the learned vector); (b) the order of the
head's columns as above; (c) seeded weights: ``mu``, ``phi`` normal with
the published ``init_std``, norm gains 0.

Attention runs a window of queries at a time, the window in query blocks,
so that 15,360 positions fit: the scores held are [H, block, W + S/C].
It runs layer by layer on weights cast up to float32 one layer at a time.
The only thing it knows of the program is the NAMES in its weight tree.
:func:`attention_bruteforce` is the same step 3 as one masked O(T^2)
softmax, for the tests.
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
    return {"g1": g("attn_norm", "scale"), "g2": g("mlp_norm", "scale"),
            "wq": g("attn", "wq"), "wk": g("attn", "wk"),
            "wv": g("attn", "wv"), "wo": g("attn", "wo"),
            "mu": g("attn", "eva_mu"), "phi": g("attn", "eva_phi"),
            "w_gate": g("mlp", "w_gate"), "w_up": g("mlp", "w_up"),
            "w_down": g("mlp", "w_down")}


def rms_norm(x, gain, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + gain)


def rope(t, theta):
    """t [H, S, d]: rotate (t[..., :d/2], t[..., d/2:]) pairs by the angle
    pos * theta^(-2i/d)."""
    H, S, d = t.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    t1, t2 = t[..., : d // 2], t[..., d // 2:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


def chunk_summaries(k, v, mu, phi, chunk):
    """Step 2.  k, v [H, S, d] (S a multiple of ``chunk``), mu, phi [H, d]
    -> ktilde, vtilde [H, S/chunk, d]."""
    H, S, d = k.shape
    kc = k.reshape(H, S // chunk, chunk, d)
    vc = v.reshape(H, S // chunk, chunk, d)
    wk = jax.nn.softmax(jnp.einsum("hcjd,hd->hcj", kc, mu), axis=-1)
    wv = jax.nn.softmax(jnp.einsum("hcjd,hd->hcj", kc, phi), axis=-1)
    return (jnp.einsum("hcj,hcjd->hcd", wk, kc),
            jnp.einsum("hcj,hcjd->hcd", wv, vc))


def eva_attention(q, k, v, mu, phi, window, chunk):
    """Steps 2 and 3.  q, k, v [H, S, d], roped; S a multiple of ``window``
    or shorter than one (the caller pads: causal, so right padding cannot
    reach a real row)."""
    H, S, d = q.shape
    W = min(window, S)
    per = window // chunk
    ks, vs = chunk_summaries(k, v, mu, phi, chunk)      # [H, S/chunk, d]
    block = min(W, QUERY_BLOCK)
    chunk_id = jnp.arange(ks.shape[1])
    sqrt_d = jnp.sqrt(F32(d))

    def one(start):
        w = start // window
        w0 = w * window
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        kw = jax.lax.dynamic_slice_in_dim(k, w0, W, axis=1)
        vw = jax.lax.dynamic_slice_in_dim(v, w0, W, axis=1)
        s_win = jnp.einsum("hqd,hkd->hqk", qb, kw) / sqrt_d
        s_sum = jnp.einsum("hqd,hcd->hqc", qb, ks) / sqrt_d
        q_pos = start + jnp.arange(block)
        ok_win = (w0 + jnp.arange(W))[None, :] <= q_pos[:, None]
        ok_sum = jnp.broadcast_to(chunk_id[None, :] < w * per,
                                  (block, len(chunk_id)))
        s = jnp.concatenate([jnp.where(ok_win[None], s_win, -jnp.inf),
                             jnp.where(ok_sum[None], s_sum, -jnp.inf)], -1)
        p = jax.nn.softmax(s, axis=-1)
        return (jnp.einsum("hqk,hkd->hqd", p[..., :W], vw)
                + jnp.einsum("hqc,hcd->hqd", p[..., W:], vs))

    out = jax.lax.map(one, jnp.arange(0, S, block))     # [nb, H, block, d]
    return out.transpose(1, 0, 2, 3).reshape(H, S, d)


def attention_bruteforce(q, k, v, mu, phi, window, chunk):
    """Step 3 as ONE masked softmax over all S keys and all S/chunk
    summaries, [H, S, S + S/chunk] scores: the tests' check of the windowed
    form above."""
    H, S, d = q.shape
    ks, vs = chunk_summaries(k, v, mu, phi, chunk)
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    c = jnp.arange(S // chunk)[None, :]
    ok = jnp.concatenate(
        [(j >= (i // window) * window) & (j <= i),
         c < (i // window) * (window // chunk)], axis=-1)
    s = jnp.einsum("hqd,hkd->hqk", q, jnp.concatenate([k, ks], 1)) \
        / jnp.sqrt(F32(d))
    p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p, jnp.concatenate([v, vs], 1))


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "theta",
                                             "window", "chunk", "attention",
                                             "stream_dtype"))
def layer(x, w, *, n_head, eps, theta, window, chunk,
          attention=eva_attention, stream_dtype=None):
    """``stream_dtype`` rounds the residual stream to that dtype after each
    of the two adds: the reading in the precision below ``fp32_skip_add``
    that the comparisons' tolerances are set against.  None: float32."""
    stream = (lambda t: t) if stream_dtype is None else \
        (lambda t: t.astype(stream_dtype).astype(F32))
    S, D = x.shape
    d = w["wq"].shape[1] // n_head
    a = rms_norm(x, w["g1"], eps)
    heads = lambda t: t.reshape(S, n_head, d).transpose(1, 0, 2)
    o = attention(rope(heads(a @ w["wq"]), theta),
                  rope(heads(a @ w["wk"]), theta), heads(a @ w["wv"]),
                  w["mu"], w["phi"], window, chunk)
    x = stream(x + o.transpose(1, 0, 2).reshape(S, n_head * d) @ w["wo"])
    m = rms_norm(x, w["g2"], eps)
    return stream(
        x + (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"])


def hidden_states(params, config, tokens, device, attention=eva_attention,
                  stream_dtype=None):
    W, C = config["window_size"], config["chunk_size"]
    tokens = jnp.asarray(tokens, jnp.int32)
    n = len(tokens)
    # whole windows, else whole query blocks, else whole chunks: causal, so
    # right padding cannot reach a real row
    unit = W if n > W else QUERY_BLOCK if n > QUERY_BLOCK else C
    tokens = jnp.pad(tokens, (0, -n % unit))
    with jax.default_matmul_precision("highest"):
        outer = outer_weights(params, device)
        x = outer["embed"][jax.device_put(tokens, device)]
        for l in range(config["num_hidden_layers"]):
            x = layer(x, layer_weights(params, l, device),
                      n_head=config["num_attention_heads"],
                      eps=config["rms_norm_eps"],
                      theta=float(config["rope_theta"]),
                      window=W, chunk=C, attention=attention,
                      stream_dtype=stream_dtype)
        return x[:n], outer


def logits_rows(params, config, tokens, rows, device, all_heads=False,
                attention=eva_attention, stream_dtype=None):
    """Reference logits at positions ``rows`` of ``tokens``: head 0's
    [len(rows), vocab_size], what the served token is the argmax of; with
    ``all_heads`` every head's, [len(rows), num_pred_heads * vocab_size]."""
    x, outer = hidden_states(params, config, tokens, device, attention,
                             stream_dtype)
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x[jnp.asarray(rows)], outer["norm"],
                     config["rms_norm_eps"])
        logits = h @ outer["lm_head"]
    return logits if all_heads else logits[:, : config["vocab_size"]]
