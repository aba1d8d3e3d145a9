"""Plain reference forward of AI21-Jamba2-3B (``model_type: jamba``;
config.json; Lieber et al. 2024, arXiv:2403.19887; the mixer of Gu & Dao
2023, arXiv:2312.00752), WHOLE: float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching, independent of ``deepspeed_tpu``.  Written from the equations of
ISSUE 66, not from the package's code.  ``N(.)`` is RMSNorm with its own
gain, eps ``rms_norm_eps``; no bias but where named; NO position encoding:

    x = embed[tokens]
    layer i:  x = x + mixer_i(N1_i(x));   x = x + mlp_i(N2_i(x))
    logits = N_f(x) embed^T                              (tie_word_embeddings)

    mlp(h) = (silu(h W_gate) * (h W_up)) W_down

    layer i attends iff i % attn_layer_period == attn_layer_offset; else
    Mamba-1 (d_inner = mamba_expand x hidden_size, N = mamba_d_state, R =
    mamba_dt_rank, K = mamba_d_conv), h = N1(x)_t:
        [u | z] = h W_in
        u[t] = silu(sum_{i=0..K-1} conv[:, i] * u[t - (K - 1) + i] + b_conv)
            zeros before t = 0
        [r | B | C] = u W_x                              R | N | N
        r = N_dt(r);  B = N_b(B);  C = N_c(C)
        dt = softplus(r W_dt + b_dt);  A = -exp(A_log)   [d_inner, N]
        per channel c, S [d_inner, N], S_0 = 0:
            S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] u_t[c] B_t[n]
            y_t[c]    = sum_n S_t[c, n] C_t[n] + D[c] u_t[c]
        mixer = (y * silu(z)) W_out
      ONE STEP A TOKEN (``lax.scan``): the recurrence has no other form.

    attention (20 query heads over ONE key-value head of 128), h = N1(x):
        score(t, j) = q_head(t) . k(j) / sqrt(128),  every j <= t
        mixer = softmax_j(score) v W_o

There is no discrete choice in this model (no router, no top-k): no
near-tie rule.  What the catalog's ``config`` does not carry is listed in
the configuration file under ``assumed``.  Departures from the published
description: float32 throughout; seeded weights.

``variant=`` breaks one equation on purpose, for
``tools/jamba2_agreement.py``'s negative controls; nothing else uses it:
``bf16_state`` (the state rounded to bfloat16 after every token),
``bf16_a`` (``A`` rounded to bfloat16), ``no_dt_norm`` (``N_dt`` dropped),
``no_dt_bias`` (``b_dt`` dropped).

Layer by layer on weights cast up to float32 one layer at a time, attention
in query blocks; of the program it knows only the NAMES in its weight tree.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 512
VARIANTS = ("bf16_state", "bf16_a", "no_dt_norm", "no_dt_bias")


def _up(a, device):
    return jax.device_put(a, device).astype(F32)


def _bf16(t):
    """``t`` rounded to bfloat16's 8 bits of mantissa, kept float32 (the
    TPU folds a convert pair away)."""
    return jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)


def attends(config, i: int) -> bool:
    return i % config["attn_layer_period"] == config["attn_layer_offset"]


def outer_weights(params, device):
    return {"embed": _up(params["embed"]["tok"], device),
            "norm": _up(params["final_norm"]["scale"], device)}


def layer_weights(params, config, i, device):
    """Published layer ``i``: its two norms, its mixer (the ``j``-th of its
    kind) and its MLP."""
    kind = "gqa" if attends(config, i) else "ssm1"
    j = sum(attends(config, k) == attends(config, i) for k in range(i))
    w = {k: _up(v[j], device) for k, v in params[kind].items()}
    w.update({k: _up(v[i], device) for k, v in params["mlp"].items()})
    w["n1"] = _up(params["norms"]["scale"][2 * i], device)
    w["n2"] = _up(params["norms"]["scale"][2 * i + 1], device)
    return w


def rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def mlp(x, w, eps):
    h = rms_norm(x, w["n2"], eps)
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def recurrence(u, dt, Bm, Cm, A, n_live, bf16_state=False):
    """The selective scan over ``S`` tokens, one step a token: u, dt [S,
    d_inner]; Bm, Cm [S, N]; A [d_inner, N].  Returns (y [S, d_inner]
    WITHOUT the skip, the state after token ``n_live - 1``)."""
    def step(S, xs):
        t, u_t, dt_t, b_t, c_t = xs
        new = jnp.exp(dt_t[:, None] * A) * S \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        if bf16_state:
            new = _bf16(new)
        return jnp.where(t < n_live, new, S), new @ c_t

    S0 = jnp.zeros(A.shape, F32)
    S, y = jax.lax.scan(step, S0, (jnp.arange(u.shape[0]), u, dt, Bm, Cm))
    return y, S


@functools.partial(jax.jit, static_argnames=("R", "eps", "variant"))
def mamba_layer(x, w, n_live, *, R, eps, variant=()):
    S, _ = x.shape
    di, K = w["conv"].shape
    N = w["a_log"].shape[1]
    uz = rms_norm(x, w["n1"], eps) @ w["w_in"]
    u, z = uz[:, :di], uz[:, di:]
    past = jnp.concatenate([jnp.zeros((K - 1, di), F32), u])
    u = jax.nn.silu(sum(past[i:i + S] * w["conv"][:, i] for i in range(K))
                    + w["conv_b"])
    rbc = u @ w["w_x"]
    r, Bm, Cm = rbc[:, :R], rbc[:, R:R + N], rbc[:, R + N:R + 2 * N]
    if "no_dt_norm" not in variant:
        r = rms_norm(r, w["dt_norm"], eps)
    Bm, Cm = rms_norm(Bm, w["b_norm"], eps), rms_norm(Cm, w["c_norm"], eps)
    dt = r @ w["w_dt"]
    if "no_dt_bias" not in variant:
        dt = dt + w["dt_bias"]
    dt = jax.nn.softplus(dt)
    A = -jnp.exp(w["a_log"])
    if "bf16_a" in variant:
        A = _bf16(A)
    y, state = recurrence(u, dt, Bm, Cm, A, n_live,
                          bf16_state="bf16_state" in variant)
    y = (y + w["d_skip"] * u) * jax.nn.silu(z)
    return mlp(x + y @ w["wo"], w, eps), state


def causal_attention(q, k, v):
    """q [H, S, d]; k, v [S, d], ONE key-value head under every query head;
    queries in blocks."""
    H, S, d = q.shape
    block = min(S, QUERY_BLOCK)
    key_pos = jnp.arange(S)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        s = jnp.einsum("hqd,kd->hqk", qb, k) / jnp.sqrt(F32(d))
        ok = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,kd->hqd", p, v)

    out = jax.lax.map(one, jnp.arange(0, S, block))    # [nb, H, block, d]
    return out.transpose(1, 0, 2, 3).reshape(H, S, d)


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def attention_layer(x, w, *, n_head, eps):
    S, _ = x.shape
    h = rms_norm(x, w["n1"], eps)
    d = w["wq"].shape[1] // n_head
    q = (h @ w["wq"]).reshape(S, n_head, d).transpose(1, 0, 2)
    a = causal_attention(q, h @ w["wk"], h @ w["wv"])
    x = x + a.transpose(1, 0, 2).reshape(S, n_head * d) @ w["wo"]
    return mlp(x, w, eps)


def hidden_states(params, config, tokens, device, variant=(), n_live=None,
                  states=None):
    """The final stream [S, D] of ``tokens`` and the outer weights;
    ``states`` (a dict) is filled with each Mamba layer's state [d_inner, N]
    after token ``n_live - 1`` (None: the last)."""
    assert set(variant) <= set(VARIANTS), variant
    tokens = np.asarray(tokens)
    S = len(tokens)
    if S > QUERY_BLOCK and S % QUERY_BLOCK:      # whole query blocks
        tokens = np.pad(tokens, (0, -S % QUERY_BLOCK))
    n_live = jnp.asarray(S if n_live is None else n_live, jnp.int32)
    with jax.default_matmul_precision("highest"):
        outer = outer_weights(params, device)
        x = outer["embed"][jax.device_put(jnp.asarray(tokens, jnp.int32),
                                          device)]
        for i in range(config["num_hidden_layers"]):
            w = layer_weights(params, config, i, device)
            if attends(config, i):
                x = attention_layer(x, w, n_head=config["num_attention_heads"],
                                    eps=config["rms_norm_eps"])
                continue
            x, state = mamba_layer(x, w, n_live, R=config["mamba_dt_rank"],
                                   eps=config["rms_norm_eps"],
                                   variant=tuple(variant))
            if states is not None:
                states[i] = state
        return x, outer


def logits_rows(params, config, tokens, rows, device, variant=(),
                states=None):
    """Reference logits [len(rows), V] at positions ``rows`` of ``tokens``;
    ``states`` (a dict) is filled with each Mamba layer's state after the
    last of ``rows``."""
    rows = np.asarray(rows)
    x, outer = hidden_states(params, config, tokens, device, variant,
                             n_live=int(rows.max()) + 1, states=states)
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x[jnp.asarray(rows)], outer["norm"],
                     config["rms_norm_eps"])
        return h @ outer["embed"].T
