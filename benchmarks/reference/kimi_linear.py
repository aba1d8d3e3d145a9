"""Plain reference forward of Kimi-Linear-48B-A3B-Instruct (moonshotai,
``model_type: kimi_linear``; config.json), ONE CHIP'S SHARE of it as the
configuration file states: float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching, independent of ``deepspeed_tpu.models``, ``deepspeed_tpu.moe`` and
``deepspeed_tpu.ops``.  Written from the equations of ISSUE 44, not from the
package's code.  ``N(.)`` is RMSNorm with its own gain, eps ``rms_norm_eps``;
published layers are numbered from 1 (``linear_attn_config.kda_layers`` /
``full_attn_layers``); layer l's MLP is dense where l <=
``first_k_dense_replace``:

    x = embed[tokens]
    per layer:  x = x + attn(N_in(x));  x = x + mlp(N_post(x))
    logits = N_f(x) W_head                           the chip's vocabulary rows

    KDA (H = 32 heads of d = 128), h = N_in(x)_t:
        u = h [Wq | Wk | Wv]
        c[t] = silu(sum_{i=0..3} conv[:, i] * u[t - 3 + i])   zeros before t = 0
        q = l2norm_head(c_q) * d^-0.5;  k = l2norm_head(c_k);  v = c_v
        g = -exp(A_log[head]) * softplus((h Wf_down) Wf_up + dt_bias)
        beta = sigmoid(h Wb)
        per head, S [d, d], S_0 = 0:   S' = diag(exp(g_t)) S_{t-1}
            S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;   o_t = S_t^T q_t
        a = (N_o(o_t) * sigmoid((h Wg_down) Wg_up + b_g)) Wo
      ONE STEP A TOKEN, never a chunk form.

    MLA (H = 32, query heads 128 + 64, values 128, latent 512), NO RoPE:
        q = h Wq;  [c_raw | k_r] = h Wkva;  c = N_kv(c_raw)
        [k_n,h | v_h] = c Wkvb
        score_h(t, j) = (q_n,h(t) . k_n,h(j) + q_r,h(t) . k_r(j)) / sqrt(192)
        a = concat_h(softmax_j<=t(score_h) v_h) Wo
      DECOMPRESSED (per-head keys and values), never the absorbed form.

    mlp: dense SwiGLU, or the expert block of ``reference/trinity.py`` (the
    same equations with this model's numbers: sigmoid scores over the
    router's 256 in float32, the bias in the top-8 selection only, the kept
    scores normalised and times ``routed_scaling_factor``, the shared expert
    plus the chosen experts HELD HERE), without that model's post-norm.  The
    router and the experts' closes are imported from that file, which is
    benchmark code and not the package's.

What the catalog's ``config`` does not carry (the gates' rank and biases,
no convolution bias, l2norm's eps, ``A_log`` a head and ``dt_bias`` a
channel, the d^-0.5 on q) is listed in the configuration file under
``assumed``.  Departures from the published description: float32
throughout; seeded weights.

``routing=`` replaces the reference's own top-8 by the program's;
``variant=`` breaks one equation on purpose, for ``tools/
kimi_linear_agreement.py``'s negative controls (``pad_rows=`` and
``stale_rows=`` belong to two of them); nothing else uses them.

Near-ties of the router are admitted by ``reference/trinity.py``'s rule,
UNCHANGED (its ``route``, ``SWAPS`` and ``NEAR_TIE`` are imported: the last
two chosen against the first two not chosen, singly or both, within 0.005).
Top-8 of 256 puts the scores at the edge of the selection closer together
than Trinity's top-4 (about 0.006 against 0.012), but what the rule has to
cover is the bf16 stream's noise, and with the embedding seeded at unit
scale that is Trinity's (4.7% of top-8 sets flip against this file's own,
Trinity's 4-5%): PERF.md section 6, PR 44, gives the cell's readings under
no rule, under these five exchanges and under 114 within 0.02.  A row is
re-evaluated with one exchange by :func:`replay`: for that it needs the
recurrent state BEFORE the row in every KDA layer after the first expert
layer, so the main pass keeps the states of the rows read (they are one
contiguous span: a request's generated positions) and nothing else of the
recurrence.

Layer by layer on weights cast up to float32 one layer at a time, MLA in
query blocks, so that 13,312 positions fit beside nothing else; of the
program it knows only the NAMES in its weight tree.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.trinity import (NEAR_TIE, REPLAY_ROWS, _below_best,
                                          _capacity, _up, expert_close,
                                          outer_weights, rms_norm, rope,
                                          route, swiglu)

F32 = jnp.float32
QUERY_BLOCK = 512
L2_EPS = 1e-6
NO_POST_NORM = ("no_post_norm",)      # trinity's closes, its post-norm out
# the controls that break the router's weights alone: the near-tie search
# runs under them too (a wrong router must fail WITH its near-ties admitted)
ROUTE_VARIANTS = frozenset({"bias_weighs", "no_route_scale"})


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def _layers(config):
    """(layer index from 0, dense MLP?, "kda" | "mla", index among its
    kind) for each layer."""
    kda = set(config["linear_attn_config"]["kda_layers"])
    seen = {"kda": 0, "mla": 0}
    for l in range(config["num_hidden_layers"]):
        kind = "kda" if l + 1 in kda else "mla"
        yield l, l < config["first_k_dense_replace"], kind, seen[kind]
        seen[kind] += 1


def layer_weights(params, config, l, device):
    _, dense, kind, j = list(_layers(config))[l]
    n_dense = config["first_k_dense_replace"]
    ly = params["dense_layers" if dense else "layers"]
    i = l if dense else l - n_dense
    g = lambda *path: _up(functools.reduce(lambda t, k: t[k], path, ly)[i],
                          device)
    w = {"n_in": g("attn_norm", "scale"), "n_post": g("mlp_norm", "scale")}
    w.update({k: _up(v[j], device) for k, v in params[kind].items()})
    if dense:
        w.update({k: g("mlp", k) for k in ("w_gate", "w_up", "w_down")})
    else:
        # the held experts' matrices stay as stored (bf16) and are cast up
        # one expert at a time inside expert_close
        raw = lambda k: jax.device_put(ly["mlp"][k], device)[i]
        w.update(router=g("mlp", "gate_w"), bias=g("mlp", "gate_bias"),
                 e_gate=raw("w_gate"), e_up=raw("w_up"), e_down=raw("w_down"),
                 s_gate=g("mlp", "shared", "w_gate"),
                 s_up=g("mlp", "shared", "w_up"),
                 s_down=g("mlp", "shared", "w_down"))
    return w


# ---------------------------------------------------------------------------
# KDA
# ---------------------------------------------------------------------------
def qkv_rows(h, w):
    """u = h [Wq | Wk | Wv]: q, k and v before the convolution."""
    return jnp.concatenate([h @ w["wq"], h @ w["wk"], h @ w["wv"]], -1)


def kda_inputs(h, taps, w, *, heads, variant=()):
    """From ``h`` [n, D] (normed) and the convolution's inputs ``taps`` (a
    list of [n, 3 H d]: ``u`` (:func:`qkv_rows`) of the rows ``taps - 1``
    .. 1 positions before each row, then of the rows themselves) to the
    recurrence's inputs: q, k, v, g [n, H, d], beta [n, H], and the output
    gate before its sigmoid [n, H d]."""
    n = h.shape[0]
    if "no_conv" in variant:
        c = taps[-1]
    else:
        c = sum(t * w["conv"][:, i] for i, t in enumerate(taps))
    c = jax.nn.silu(c)
    q, k, v = (t.reshape(n, heads, -1) for t in jnp.split(c, 3, -1))
    d = q.shape[-1]
    if "no_l2norm" not in variant:
        l2 = lambda t: t / jnp.sqrt((t * t).sum(-1, keepdims=True) + L2_EPS)
        q, k = l2(q), l2(k)
    q = q * d ** -0.5
    f = (h @ w["wf_down"]) @ w["wf_up"] + w["dt_bias"]
    g = -jnp.exp(w["a_log"])[:, None] * jax.nn.softplus(
        f.reshape(n, heads, d))
    if "no_decay" in variant:
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(h @ w["wb"])
    if "beta_one" in variant:
        beta = jnp.ones_like(beta)
    gate = (h @ w["wg_down"]) @ w["wg_up"] + w["b_g"]
    return q, k, v, g, beta, gate


def delta_step(S, q, k, v, g, beta, bf16_state=False):
    """One token of the delta rule on S [..., H, d, d] (key axis, value
    axis); elementwise float32.  ``bf16_state``: the state rounded to bf16
    after every token (the precision control)."""
    S = S * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - (S * k[..., None]).sum(-2))
    S = S + k[..., None] * u[..., None, :]
    if bf16_state:      # (a convert pair is folded away on the TPU)
        S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
    return (S * q[..., None]).sum(-2), S


@functools.partial(jax.jit, static_argnames=("n_span", "emit", "bf16_state"))
def recurrence(S0, q, k, v, g, beta, lo, *, n_span, emit, bf16_state=False):
    """The delta rule over positions ``0 .. lo + n_span - 1``, one step a
    token: a loop to ``lo`` (traced), then a scan over the next ``n_span``
    that, with ``emit``, also returns the state BEFORE each of them.  Later
    positions get o = 0 (no row that is read can see them).  Returns (o
    [T, H, d], states [n_span, H, d, d] | None)."""
    T = q.shape[0]
    # idle rows behind the sequence, for a span that overhangs its end
    xs = tuple(jnp.pad(t, ((0, n_span),) + ((0, 0),) * (t.ndim - 1))
               for t in (q, k, v, g, beta))
    one = functools.partial(delta_step, bf16_state=bf16_state)

    def body(t, carry):
        S, out = carry
        o, S = one(S, *(a[t] for a in xs))
        return S, out.at[t].set(o)

    S, out = jax.lax.fori_loop(0, lo, body,
                               (S0, jnp.zeros((T + n_span,) + q.shape[1:], F32)))
    before = None
    if n_span:
        def step(S, x):
            o, S2 = one(S, *x)
            return S2, (o, S if emit else None)

        span = tuple(jax.lax.dynamic_slice_in_dim(a, lo, n_span) for a in xs)
        _, (o_span, before) = jax.lax.scan(step, S, span)
        out = jax.lax.dynamic_update_slice_in_dim(out, o_span, lo, 0)
    return out[:T], before


def kda_close(x, o, gate, w, eps, variant=()):
    """x + (N_o(o) * sigmoid(gate)) Wo, and N_post of that."""
    y = rms_norm(o, w["o_norm"], eps).reshape(gate.shape)
    if "no_out_gate" not in variant:
        y = y * jax.nn.sigmoid(gate)
    x = x + y @ w["wo"]
    return x, rms_norm(x, w["n_post"], eps)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "variant"))
def kda_front(x, w, *, heads, eps, variant=()):
    h = rms_norm(x, w["n_in"], eps)
    u = qkv_rows(h, w)
    # u of the taps - 1 positions before each row: zeros before position 0
    taps = [jnp.pad(u, ((i, 0), (0, 0)))[:u.shape[0]]
            for i in range(w["conv"].shape[1] - 1, -1, -1)]
    return kda_inputs(h, taps, w, heads=heads, variant=variant)


kda_back = jax.jit(kda_close, static_argnames=("eps", "variant"))


# ---------------------------------------------------------------------------
# MLA, decompressed
# ---------------------------------------------------------------------------
def mla_qkv(h, w, *, heads, nope, kv_rank, eps, variant=()):
    """q [n, H, nope + rot], per-head keys [n, H, nope + rot] and values
    [n, H, v] of rows ``h`` [n, D]: the latent normed, decompressed, the
    shared ``k_r`` beside each head's ``k_n``."""
    n = h.shape[0]
    q = (h @ w["wq"]).reshape(n, heads, -1)
    cr = h @ w["wkva"]
    c = cr[:, :kv_rank]
    if "no_kv_norm" not in variant:
        c = rms_norm(c, w["kv_norm"], eps)
    kvb = (c @ w["wkvb"]).reshape(n, heads, -1)
    k_r = jnp.broadcast_to(cr[:, None, kv_rank:],
                           (n, heads, cr.shape[1] - kv_rank))
    if "no_k_rot" in variant:       # k_r left out of the scores
        k_r = jnp.zeros_like(k_r)
    return q, jnp.concatenate([kvb[..., :nope], k_r], -1), kvb[..., nope:]


def _rot(t, nope, fn):
    return jnp.concatenate([t[..., :nope], fn(t[..., nope:])], -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "kv_rank", "eps", "theta", "variant", "skip"))
def mla_block(x, w, *, heads, nope, kv_rank, eps, theta, variant=(),
              skip=None):
    """x -> (x + attention, N_post of that, keys [H, S, 192], values
    [H, S, 128]).  ``skip`` = (first, count): keys at those positions are
    seen only by the queries among them (the pad-row control)."""
    S = x.shape[0]
    h = rms_norm(x, w["n_in"], eps)
    q, k, v = (t.transpose(1, 0, 2) for t in mla_qkv(
        h, w, heads=heads, nope=nope, kv_rank=kv_rank, eps=eps,
        variant=variant))
    if "rope_on_rot" in variant:    # RoPE wrongly applied to the 64
        q, k = (_rot(t, nope, lambda r: rope(r, theta)) for t in (q, k))
    block = min(S, QUERY_BLOCK)
    j = jnp.arange(S)[None, :]

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        i = (start + jnp.arange(block))[:, None]
        ok = j <= i
        if skip is not None:
            a, n = skip
            ok = ok & ~((j >= a) & (j < a + n) & (i >= a + n))
        s = jnp.einsum("hqd,hkd->hqk", qb, k) / jnp.sqrt(F32(q.shape[-1]))
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p, v)

    a = jax.lax.map(one, jnp.arange(0, S, block))      # [nb, H, block, v]
    a = a.transpose(1, 0, 2, 3).reshape(heads, S, -1)
    x = x + a.transpose(1, 0, 2).reshape(S, -1) @ w["wo"]
    return x, rms_norm(x, w["n_post"], eps), k, v


@jax.jit
def dense_close(x, h, w):
    return x + swiglu(h, w["w_gate"], w["w_up"], w["w_down"])


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------
def _kda_kw(config):
    return dict(heads=config["linear_attn_config"]["num_heads"])


def _mla_kw(config):
    return dict(heads=config["num_attention_heads"],
                nope=config["qk_nope_head_dim"],
                kv_rank=config["kv_lora_rank"])


def _route_kw(config):
    return dict(top_k=config["num_experts_per_token"],
                first=config["expert_parallel"]["first_expert"],
                route_scale=float(config["routed_scaling_factor"]),
                route_norm=bool(config["moe_renormalize"]))


def _state0(w, heads):
    d = w["wq"].shape[1] // heads
    return jnp.zeros((heads, d, d), F32)


def hidden_states(params, config, tokens, device, routing=None,
                  return_routing=False, variant=(), n_live=None, keep=None,
                  span=None, stale_rows=0, skip=None):
    """Final hidden states [S, D] and the outer weights; with
    ``return_routing`` also the router indices used, [expert layers, S, k].
    ``span`` = (lo, n): the recurrence runs to ``lo + n`` (every row that is
    read lies before it).  ``keep`` (a dict) is filled with what
    :func:`replay` needs.  ``stale_rows`` (the control of a state not
    zeroed): every KDA layer starts from the state its first that many rows
    leave.  ``skip``: :func:`mla_block`'s."""
    variant = tuple(sorted(variant))
    eps = config["rms_norm_eps"]
    n_dense = config["first_k_dense_replace"]
    with jax.default_matmul_precision("highest"):
        outer = outer_weights(params, device)
        tokens = jax.device_put(jnp.asarray(tokens, jnp.int32), device)
        S = tokens.shape[0]
        n_live = S if n_live is None else n_live
        lo, n_span = (n_live, 0) if span is None else span
        x = outer["embed"][tokens]
        used = []
        if keep is not None:
            keep.update(x_in={}, states={}, kv={}, ties=[], lo=lo,
                        variant=variant)
        for l, dense, kind, _ in _layers(config):
            w = layer_weights(params, config, l, device)
            after = l > n_dense           # attends after the first router
            if kind == "kda":
                if keep is not None and after:
                    keep["x_in"][l] = x
                q, k, v, g, beta, gate = kda_front(
                    x, w, eps=eps, variant=variant, **_kda_kw(config))
                S0 = _state0(w, q.shape[1])
                run = functools.partial(
                    recurrence, bf16_state="bf16_state" in variant)
                if stale_rows:      # the state before row ``stale_rows``
                    S0 = run(S0, q, k, v, g, beta, stale_rows, n_span=1,
                             emit=True)[1][0]
                emit = keep is not None and after
                o, states = run(S0, q, k, v, g, beta, lo,
                                n_span=n_span, emit=emit)
                if emit:
                    keep["states"][l] = states
                x, h = kda_back(x, o, gate, w, eps, variant)
            else:
                x, h, k, v = mla_block(
                    x, w, eps=eps, variant=variant, skip=skip,
                    theta=float(config["rope_theta"]), **_mla_kw(config))
                if keep is not None:
                    keep["kv"][l] = (k, v)
            if dense:
                x = dense_close(x, h, w)
                continue
            if keep is not None and l == n_dense:
                keep["start"] = (x, h)
            chosen = None if routing is None else jnp.asarray(
                routing[l - n_dense])
            chosen, local, fullest, tie = route(
                h, w, chosen, n_live, variant=variant, **_route_kw(config))
            x = expert_close(x, h, w, local, eps=eps, cap=_capacity(fullest, S),
                             variant=NO_POST_NORM)
            used.append(chosen)
            if keep is not None:
                keep["ties"].append(tuple(np.asarray(t) for t in tie))
        if return_routing:
            return x, outer, jnp.stack(used)
        return x, outer


# ---------------------------------------------------------------------------
# one row again, with an exchange at the edge of its top-8
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def kda_one(x, pos, w, x_in, states, lo, *, heads, eps):
    """:func:`kda_front`, one step and :func:`kda_close` for single
    positions: ``x`` [n, D] the streams of positions ``pos`` [n] on top of
    the sequence's own earlier rows (``x_in``: the layer's inputs of the
    main pass, for the convolution's three rows before) and the state the
    main pass had BEFORE each (``states[pos - lo]``)."""
    taps = w["conv"].shape[1]
    norm = lambda t: rms_norm(t, w["n_in"], eps)
    back = pos[:, None] - jnp.arange(taps - 1, 0, -1)[None, :]    # [n, 3]
    ub = jnp.where((back >= 0)[..., None],
                   qkv_rows(norm(x_in[jnp.maximum(back, 0)]), w), 0.0)
    h = norm(x)
    q, k, v, g, beta, gate = kda_inputs(
        h, [ub[:, i] for i in range(taps - 1)] + [qkv_rows(h, w)], w,
        heads=heads)
    o, _ = delta_step(states[pos - lo], q, k, v, g, beta)
    return kda_close(x, o, gate, w, eps)


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "kv_rank", "eps"))
def mla_one(x, pos, w, k_all, v_all, *, heads, nope, kv_rank, eps):
    """:func:`mla_block` for single positions: the sequence's own keys and
    values of the EARLIER positions, and their own of this evaluation."""
    S = k_all.shape[1]
    h = rms_norm(x, w["n_in"], eps)
    q, k, v = mla_qkv(h, w, heads=heads, nope=nope, kv_rank=kv_rank, eps=eps)
    s_all = jnp.einsum("nhd,hkd->nhk", q, k_all)
    ok = jnp.arange(S)[None, :] < pos[:, None]
    s = jnp.concatenate([jnp.where(ok[:, None], s_all, -jnp.inf),
                         (q * k).sum(-1)[..., None]], -1) \
        / jnp.sqrt(F32(q.shape[-1]))
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("nhk,hkd->nhd", p[..., :S], v_all) + p[..., S:] * v
    x = x + a.reshape(x.shape[0], -1) @ w["wo"]
    return x, rms_norm(x, w["n_post"], eps)


def replay(params, config, pos, swaps, keep, device):
    """Final hidden states [n, D] of positions ``pos`` [n] with the exchange
    ``swaps`` [n, expert layers] names (an entry of SWAPS, 1-based; 0:
    none) made at each expert layer, every other position as the main
    pass left it; and each expert layer's near-ties ON THAT STREAM."""
    n_dense = config["first_k_dense_replace"]
    eps = config["rms_norm_eps"]
    n = len(pos)
    # to a power of two of whole blocks, so that few shapes compile
    pad = REPLAY_ROWS * (1 << int(np.ceil(np.log2(-(-n // REPLAY_ROWS))))) - n
    pos = jnp.asarray(np.pad(pos, (0, pad), mode="edge"), jnp.int32)
    swaps = jnp.asarray(np.pad(swaps, ((0, pad), (0, 0))))
    blocks = range(0, n + pad, REPLAY_ROWS)
    cut = lambda t, a: t[a:a + REPLAY_ROWS]
    with jax.default_matmul_precision("highest"):
        # up to the first router a row is what the main pass made of it
        x, h = (t[pos] for t in keep["start"])
        ties = []
        for l, dense, kind, _ in _layers(config):
            if l < n_dense:
                continue
            w = layer_weights(params, config, l, device)
            if l > n_dense and kind == "kda":
                x, h = (jnp.concatenate(parts) for parts in zip(*(
                    kda_one(cut(x, a), cut(pos, a), w, keep["x_in"][l],
                            keep["states"][l], keep["lo"], eps=eps,
                            **_kda_kw(config)) for a in blocks)))
            elif l > n_dense:
                x, h = (jnp.concatenate(parts) for parts in zip(*(
                    mla_one(cut(x, a), cut(pos, a), w, *keep["kv"][l],
                            eps=eps, **_mla_kw(config)) for a in blocks)))
            _, local, _, tie = route(
                h, w, None, n + pad, swap=swaps[:, l - n_dense],
                variant=keep["variant"], **_route_kw(config))
            x = expert_close(x, h, w, local, eps=eps, cap=n + pad,
                             variant=NO_POST_NORM)
            ties.append(tuple(np.asarray(t)[:n] for t in tie))
    return x[:n], ties


def admissible_rows(params, config, tokens, rows, device, logits, keep,
                    outer):
    """``logits`` [len(rows), V] with each row whose next token is not its
    best replaced by its admissible evaluation under which that token sits
    highest (``reference/trinity.py``: the rule and its search)."""
    n_exp = len(keep["ties"])
    n_tok = len(tokens)
    logits = np.array(logits)
    first, places = {}, {}
    for at, r in enumerate(rows):
        first.setdefault(int(r), at)
        places.setdefault(int(r), []).append(at)
    front = [(r, (0,) * n_exp, [(m[r], h[r]) for m, h in keep["ties"]])
             for r, at in first.items() if r + 1 < n_tok
             and _below_best(logits[at], tokens[r + 1]) > 0.0]
    best = {r: _below_best(logits[first[r]], tokens[r + 1])
            for r, _, _ in front}
    while front:
        tries = []
        for r, swaps, ties in front:
            last = max((e for e in range(n_exp) if swaps[e]), default=-1)
            for e in range(last + 1, n_exp):
                for n, (margin, held) in enumerate(zip(*ties[e]), start=1):
                    if held and margin < NEAR_TIE:
                        tries.append((r, swaps[:e] + (n,) + swaps[e + 1:]))
        if not tries:
            break
        x, ties = replay(params, config, np.asarray([r for r, _ in tries]),
                         np.asarray([sw for _, sw in tries]), keep, device)
        with jax.default_matmul_precision("highest"):
            got = np.asarray(rms_norm(x, outer["norm"],
                                      config["rms_norm_eps"])
                             @ outer["lm_head"])
        front = []
        for t, (r, swaps) in enumerate(tries):
            below = _below_best(got[t], tokens[r + 1])
            if below < best[r]:
                best[r] = below
                logits[places[r]] = got[t]
            front.append((r, swaps, [(m[t], h[t]) for m, h in ties]))
    return logits


def logits_rows(params, config, tokens, rows, device, routing=None,
                variant=(), pad_rows=None, stale_rows=0):
    """Reference logits [len(rows), V] at positions ``rows`` of ``tokens``
    (V the chip's share of the vocabulary).  Without ``routing`` a row at a
    near-tie of the router is the admissible evaluation its next token fits
    best (:func:`admissible_rows`), also under a ``variant`` that breaks the
    router's weights alone (:data:`ROUTE_VARIANTS`); with ``routing``, or
    under any other control, the one evaluation stands.

    ``pad_rows`` = (first, count) (the control of pad rows allowed to move
    the state): that many rows of token 0 are put into the sequence at
    ``first``; the recurrences run over them, the MLA queries behind them do
    not see them, and ``rows`` and ``routing`` mean the sequence without
    them."""
    tokens = np.asarray(tokens)
    rows = np.asarray(rows)
    S = len(tokens)
    if pad_rows is not None:
        a, n = pad_rows
        tokens = np.concatenate([tokens[:a], np.zeros(n, tokens.dtype),
                                 tokens[a:]])
        rows = np.where(rows >= a, rows + n, rows)
        if routing is not None:
            fill = lambda r: np.concatenate(
                [r[:a], np.repeat(r[a - 1:a], n, 0), r[a:]])
            routing = [fill(np.asarray(r)) for r in routing]
    if len(tokens) > QUERY_BLOCK and len(tokens) % QUERY_BLOCK:
        tokens = np.pad(tokens, (0, -len(tokens) % QUERY_BLOCK))
    if routing is not None:       # [S or fewer, k] a layer: rows to the end
        routing = [np.pad(np.asarray(r), ((0, len(tokens) - len(r)), (0, 0)))
                   for r in routing]
    n_live = int(rows.max()) + 1
    keep = {} if routing is None and ROUTE_VARIANTS.issuperset(variant) \
        and not stale_rows and pad_rows is None else None
    # the rows read are one span (a request's generated positions, the last
    # again where the answer is shorter): the states before them are kept
    lo = int(rows.min())
    span = (lo, len(rows)) if keep is not None else (n_live, 0)
    assert keep is None or lo + len(rows) >= n_live, "rows are not one span"
    x, outer = hidden_states(params, config, tokens, device, routing,
                             variant=variant, n_live=n_live, keep=keep,
                             span=span, stale_rows=stale_rows,
                             skip=None if pad_rows is None
                             else tuple(int(i) for i in pad_rows))
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x[jnp.asarray(rows)], outer["norm"],
                     config["rms_norm_eps"])
        logits = h @ outer["lm_head"]
    if keep is None:
        return logits
    return admissible_rows(params, config, tokens[:S], rows, device, logits,
                           keep, outer)
