"""Plain reference forward of Trinity-Large-Preview (arcee-ai,
``model_type: afmoe``; config.json), ONE CHIP'S SHARE of it as the
configuration file states: float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching, independent of ``deepspeed_tpu.models``, ``deepspeed_tpu.moe`` and
``deepspeed_tpu.ops``.  Written from the equations of ISSUE 36, not from the
package's code.  ``N(.)`` is RMSNorm with its own gain, eps ``rms_norm_eps``:

    x = embed[tokens] * sqrt(hidden_size)            [mup_enabled]
    per layer l (kind = layer_types[l]):
        h = N_in(x); q, k, v, g = h Wq, h Wk, h Wv, h Wg
        q, k = N_q(q), N_k(k)                        per head, gains [128]
        sliding_attention: q, k = RoPE(q), RoPE(k)   (theta 1e4, whole head,
            rotate-half); query i sees keys j with 0 <= i - j < sliding_window
        full_attention:    NO position encoding; query i sees every j <= i
        a = (softmax(q k^T / sqrt(128)) v * sigmoid(g)) Wo
        x = x + N_post_attn(a)
        h = N_pre_mlp(x)
        l < num_dense_layers:  m = (silu(h Wgate) * (h Wup)) Wdown
        else:  s = sigmoid(h Wr)                     over the router's 256
               sel = top-4 of (s + b)                the bias picks only
               w = s[sel] / (sum s[sel] + 1e-20) * route_scale   [route_norm]
               m = shared(h) + sum_{e in sel, first <= e < first + held}
                       w_e (silu(h Wgate_e) * (h Wup_e)) Wdown_e
        x = x + N_post_mlp(m)
    logits = N_f(x) W_head                           the chip's vocabulary rows

The share (``expert_parallel`` in the configuration file): the chip holds
experts ``[first_expert, first_expert + num_experts)`` of the router's, and a
token's choice of any other expert adds nothing HERE (another chip adds it);
the shared expert, attention, router and norms are whole.  The held count
and the router's width are read off the weights' shapes.

What the configuration's keys prove and what is ``assumed`` (the four norms'
places, per-head QK-norm, the gate before ``Wo``, RoPE on sliding layers
only, ``sqrt(hidden_size)`` as the muP multiplier, the bias in the selection
only: the issue author's recollection of HF's ``modeling_afmoe.py``, which
this machine's ``transformers`` does not carry) is listed in the
configuration file.  Departures from the published description, at their
lines below: float32 throughout; seeded weights.

The expert block runs each token's chosen HELD experts and no other: per
held expert, the rows that chose it are gathered (a capacity read off the
routing on the host), run densely and added back under their weights.  Rows
past the last row read are not run through the experts: causal attention
lets them reach no row that is read (the driver pads every sequence to one
length).  ``routing=`` (one ``[S, k]`` array of ROUTER indices per expert
layer) replaces the reference's own top-k choice by the program's and keeps
the reference's weights for them (``reference/olmoe.py``'s device).
``variant=`` breaks one equation on purpose, for ``tools/
trinity_agreement.py``'s negative controls; nothing else uses it.

Attention runs in query blocks so that 16,384 positions fit: a sliding
layer's block sees a slice of ``sliding_window + block`` keys, the global
layer's all of them.  Layer by layer on weights cast up to float32 one layer
at a time; of the program it knows only the NAMES in its weight tree.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 512
# exchanges at the edge of a top-k, as (how many places above the k-th go
# out, how many places below it come in): k-th <-> (k+1)-th, k-th <-> (k+2)-th,
# (k-1)-th <-> (k+1)-th, (k-1)-th <-> (k+2)-th, and both pairs at once
SWAPS = (((0,), (0,)), ((0,), (1,)), ((1,), (0,)), ((1,), (1,)),
         ((1, 0), (0, 1)))


def _up(a, device):
    return jax.device_put(a, device).astype(F32)


def outer_weights(params, device):
    return {"embed": _up(params["embed"]["tok"], device),
            "norm": _up(params["final_norm"]["scale"], device),
            "lm_head": _up(params["lm_head"], device)}


def layer_weights(params, l, n_dense, device):
    dense = l < n_dense
    ly = params["dense_layers" if dense else "layers"]
    i = l if dense else l - n_dense
    g = lambda *path: _up(functools.reduce(lambda t, k: t[k], path, ly)[i],
                          device)
    w = {"n_in": g("attn_norm", "scale"),
         "n_post_attn": g("attn_post_norm", "scale"),
         "n_pre_mlp": g("mlp_norm", "scale"),
         "n_post_mlp": g("mlp_post_norm", "scale"),
         "wq": g("attn", "wq"), "wk": g("attn", "wk"), "wv": g("attn", "wv"),
         "wg": g("attn", "wg"), "wo": g("attn", "wo"),
         "q_norm": g("attn", "q_norm", "scale"),
         "k_norm": g("attn", "k_norm", "scale")}
    if dense:
        w.update({k: g("mlp", k) for k in ("w_gate", "w_up", "w_down")})
    else:
        # the held experts' three matrices stay as they are stored (bf16) and
        # are cast up one expert at a time inside expert_close: exact, and
        # 3.6 GB of float32 a layer never exist
        raw = lambda k: jax.device_put(ly["mlp"][k], device)[i]
        w.update(router=g("mlp", "gate_w"), bias=g("mlp", "gate_bias"),
                 e_gate=raw("w_gate"), e_up=raw("w_up"), e_down=raw("w_down"),
                 s_gate=g("mlp", "shared", "w_gate"),
                 s_up=g("mlp", "shared", "w_up"),
                 s_down=g("mlp", "shared", "w_down"))
    return w


def rms_norm(x, gain, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * gain


def rope(t, theta):
    """t [H, S, d]: rotate (t[..., :d/2], t[..., d/2:]) pairs by the angle
    pos * theta^(-2i/d)."""
    H, S, d = t.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    t1, t2 = t[..., : d // 2], t[..., d // 2:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


def attention(q, k, v, window):
    """q [H, S, d]; k, v [Hkv, S, d], each KV head shared by H/Hkv query
    heads.  ``window`` 0: every j <= i.  Else keys with 0 <= i - j < window,
    and a query block reads only the ``window + block`` keys it can see."""
    H, S, d = q.shape
    rep = H // k.shape[0]
    k, v = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
    block = min(S, QUERY_BLOCK)
    span = S if not window else min(S, window) + block
    if window:                 # keys before position 0: never seen
        front = ((0, 0), (span - block, 0), (0, 0))
        k, v = jnp.pad(k, front), jnp.pad(v, front)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        q_pos = (start + jnp.arange(block))[:, None]
        if window:
            kb = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
            k_pos = (start - (span - block) + jnp.arange(span))[None, :]
            ok = (k_pos >= 0) & (k_pos <= q_pos) & (q_pos - k_pos < window)
        else:
            kb, vb = k, v
            ok = jnp.arange(S)[None, :] <= q_pos
        s = jnp.einsum("hqd,hkd->hqk", qb, kb) / jnp.sqrt(F32(d))
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p, vb)

    out = jax.lax.map(one, jnp.arange(0, S, block))    # [nb, H, block, d]
    return out.transpose(1, 0, 2, 3).reshape(H, S, d)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv", "eps", "theta", "window", "variant"))
def attention_block(x, w, *, n_head, n_kv, eps, theta, window, variant=()):
    """x -> (x + N_post_attn(attention), N_pre_mlp of that, and the keys
    and values [Hkv, S, d] the layer attended)."""
    S, D = x.shape
    d = w["wq"].shape[1] // n_head
    h = rms_norm(x, w["n_in"], eps)
    heads = lambda t, n: t.reshape(S, n, d).transpose(1, 0, 2)
    q = rms_norm(heads(h @ w["wq"], n_head), w["q_norm"], eps)
    k = rms_norm(heads(h @ w["wk"], n_kv), w["k_norm"], eps)
    v = heads(h @ w["wv"], n_kv)
    if window or "rope_global" in variant:
        q, k = rope(q, theta), rope(k, theta)
    if window and "stale_ring" in variant:
        # one ring page never overwritten: the fourth sixteenth of the
        # ring (a 256-row page of 4,096) still holds the position one
        # window earlier
        j = jnp.arange(S)
        stale = (j >= window) & ((j % window) * 16 // window == 3)
        back = lambda t: jnp.where(stale[None, :, None],
                                   jnp.roll(t, window, axis=1), t)
        k, v = back(k), back(v)
    a = attention(q, k, v, 0 if "no_window" in variant else window)
    a = a.transpose(1, 0, 2).reshape(S, n_head * d)
    if "no_gate" not in variant:
        a = a * jax.nn.sigmoid(h @ w["wg"])
    a = a @ w["wo"]
    x = x + (a if "no_post_norm" in variant
             else rms_norm(a, w["n_post_attn"], eps))
    return x, rms_norm(x, w["n_pre_mlp"], eps), k, v


def swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


@functools.partial(jax.jit, static_argnames=("eps", "variant"))
def dense_close(x, h, w, *, eps, variant=()):
    m = swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
    return x + (m if "no_post_norm" in variant
                else rms_norm(m, w["n_post_mlp"], eps))


@functools.partial(jax.jit, static_argnames=(
    "top_k", "first", "route_scale", "route_norm", "variant"))
def route(h, w, chosen, n_live, *, top_k, first, route_scale, route_norm,
          variant=(), swap=None):
    """(router indices chosen [S, k]; the token's weight for each HELD
    expert [S, E], 0 where not chosen; the fullest held expert's rows; the
    near-ties of the selection: for each exchange of :data:`SWAPS`, how far
    the scores it exchanges lie apart [S, len(SWAPS)] and whether a held
    expert is among them).  ``swap`` [S] int makes exchange ``swap`` (1-based;
    0: none)."""
    S = h.shape[0]
    E = w["e_up"].shape[0]
    s = jax.nn.sigmoid(h @ w["router"])                 # [S, R] float32
    val, idx = jax.lax.top_k(s + w["bias"], top_k + 2)
    here = lambda e: (e >= first) & (e < first + E)
    # the exchanges at the edge of the top-k (SWAPS: ranks out, ranks in),
    # how far apart their scores lie, and whether a held expert is in them
    ranks = [(tuple(top_k - 1 - o for o in outs), tuple(top_k + i for i in ins))
             for outs, ins in SWAPS]
    tie = (jnp.stack([val[:, min(o)] - val[:, max(i)] for o, i in ranks], 1),
           jnp.stack([functools.reduce(jnp.logical_or,
                                       [here(idx[:, r]) for r in o + i])
                      for o, i in ranks], 1))
    if chosen is None:
        chosen = idx[:, :top_k]
        if swap is not None:
            for n, (outs, ins) in enumerate(ranks, start=1):
                for o, i in zip(outs, ins):
                    chosen = chosen.at[:, o].set(
                        jnp.where(swap == n, idx[:, i], chosen[:, o]))
    pick = s + w["bias"] if "bias_weighs" in variant else s
    weight = jnp.take_along_axis(pick, chosen, axis=-1)
    if route_norm:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    if "no_route_scale" not in variant:
        weight = weight * route_scale
    per_expert = jnp.zeros_like(s).at[
        jnp.arange(S)[:, None], chosen].set(weight)
    local = jax.lax.dynamic_slice_in_dim(per_expert, first, E, axis=1)
    # rows past the last row read reach no row that is read (causal)
    local = jnp.where(jnp.arange(S)[:, None] < n_live, local, 0.0)
    return chosen, local, (local != 0).sum(0).max(), tie


@functools.partial(jax.jit, static_argnames=("eps", "cap", "variant"))
def expert_close(x, h, w, local, *, eps, cap, variant=()):
    """x + N_post_mlp(shared(h) + the held experts' weighed outputs): per
    held expert, the (at most ``cap``) rows that chose it, gathered, run
    densely, added back."""
    S, D = h.shape
    h_pad = jnp.concatenate([h, jnp.zeros((1, D), F32)])

    def one(acc, ew):
        wg, wu, wd, col = ew
        rows = jnp.nonzero(col != 0, size=cap, fill_value=S)[0]
        out = swiglu(h_pad[rows], wg.astype(F32), wu.astype(F32),
                     wd.astype(F32))
        col_pad = jnp.concatenate([col, jnp.zeros((1,), F32)])
        return acc.at[rows].add(col_pad[rows][:, None] * out,
                                mode="drop"), None

    m, _ = jax.lax.scan(one, swiglu(h, w["s_gate"], w["s_up"], w["s_down"]),
                        (w["e_gate"], w["e_up"], w["e_down"], local.T))
    return x + (m if "no_post_norm" in variant
                else rms_norm(m, w["n_post_mlp"], eps))


def _layers(config):
    n_dense = config["num_dense_layers"]
    for l, kind in enumerate(config["layer_types"]):
        yield l, l < n_dense, (config["sliding_window"]
                               if kind == "sliding_attention" else 0)


def _attn_kw(config):
    return dict(n_head=config["num_attention_heads"],
                n_kv=config["num_key_value_heads"],
                theta=float(config["rope_theta"]))


def _route_kw(config):
    return dict(top_k=config["num_experts_per_tok"],
                first=config["expert_parallel"]["first_expert"],
                route_scale=float(config["route_scale"]),
                route_norm=bool(config["route_norm"]))


def _capacity(fullest, rows):
    """Rows an expert's gather holds: at least the fullest expert's, from
    three sizes (so that few shapes compile)."""
    return next(c for c in (min(rows, 512), min(rows, 2048), rows)
                if c >= int(fullest))


def hidden_states(params, config, tokens, device, routing=None,
                  return_routing=False, variant=(), n_live=None, keep=None):
    """Final hidden states [S, D] and the outer weights; with
    ``return_routing`` also the router indices used, [expert layers, S, k].
    ``keep`` (a dict) is filled with what :func:`replay` needs: each layer's
    keys and values and each expert layer's near-ties."""
    variant = tuple(sorted(variant))
    n_dense = config["num_dense_layers"]
    kw = dict(eps=config["rms_norm_eps"], variant=variant)
    with jax.default_matmul_precision("highest"):
        outer = outer_weights(params, device)
        tokens = jax.device_put(jnp.asarray(tokens, jnp.int32), device)
        S = tokens.shape[0]
        n_live = S if n_live is None else n_live
        # mup_enabled: the embedding times sqrt(hidden_size) (assumed)
        x = outer["embed"][tokens] * np.sqrt(config["hidden_size"])
        used = []
        if keep is not None:
            keep.update(kv=[], ties=[])
        for l, dense, window in _layers(config):
            w = layer_weights(params, l, n_dense, device)
            x, h, k, v = attention_block(x, w, window=window,
                                         **_attn_kw(config), **kw)
            if keep is not None:
                keep["kv"].append((k, v))
            if dense:
                x = dense_close(x, h, w, **kw)
                continue
            chosen = None if routing is None else jnp.asarray(
                routing[l - n_dense])
            chosen, local, fullest, tie = route(
                h, w, chosen, n_live, variant=variant, **_route_kw(config))
            x = expert_close(x, h, w, local, cap=_capacity(fullest, S), **kw)
            used.append(chosen)
            if keep is not None:
                keep["ties"].append(tuple(np.asarray(t) for t in tie))
        if return_routing:
            return x, outer, jnp.stack(used)
        return x, outer


# ---------------------------------------------------------------------------
# Near-ties of the router.  Top-k is a discontinuous function of the
# selection scores: where the k-th and the (k+1)-th lie within NEAR_TIE of
# each other, the published equations evaluated in bf16 (as the release and
# the program run them) and in float32 (this file) may each pick either, and
# NEITHER is wrong.  At these widths a bf16 stream moves a selection score
# by up to about 0.0025 (PERF.md section 4, trinity-large-L5-ep8): 4-9% of
# (token, layer) pairs flip, a quarter of those involve a held expert, and
# such a row's logits then move by tens of bf16 steps.  So a row whose served
# token is not this file's own best is ALSO evaluated with the experts at the
# edge of the top-k exchanged (SWAPS: the last two chosen against the first
# two not chosen, singly or both) at each layer where the exchanged scores
# are near-tied and a held expert is among them (and, on the stream that
# exchange leaves, at the later layers likewise), and the row reports the
# admissible evaluation under which
# the served token (the next token of the teacher-forced sequence) sits
# highest.  Rows without a near-tie, and every row of a program whose fault
# is not a tie (a stale page, a dropped mask: hundreds of steps on most
# rows), are judged against this file's own routing as before.
# ---------------------------------------------------------------------------
NEAR_TIE = 0.005
REPLAY_ROWS = 64


def rope_at(t, pos, theta):
    """t [n, H, d] at positions ``pos`` [n]."""
    d = t.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    t1, t2 = t[..., : d // 2], t[..., d // 2:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv", "eps", "theta", "window"))
def attention_one(x, pos, w, k_all, v_all, *, n_head, n_kv, eps, theta,
                  window):
    """:func:`attention_block` for single positions: ``x`` [n, D] the
    streams of positions ``pos`` [n], attending the sequence's own keys and
    values of the EARLIER positions and their own of this evaluation."""
    n, D = x.shape
    S = k_all.shape[1]
    d = w["wq"].shape[1] // n_head
    rep = n_head // n_kv
    h = rms_norm(x, w["n_in"], eps)
    q = rms_norm((h @ w["wq"]).reshape(n, n_head, d), w["q_norm"], eps)
    k = rms_norm((h @ w["wk"]).reshape(n, n_kv, d), w["k_norm"], eps)
    v = (h @ w["wv"]).reshape(n, n_kv, d)
    if window:
        q, k = rope_at(q, pos, theta), rope_at(k, pos, theta)
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s_all = jnp.einsum("nhd,hkd->nhk", q, jnp.repeat(k_all, rep, axis=0))
    j = jnp.arange(S)[None, :]
    ok = j < pos[:, None]
    if window:
        ok = ok & (pos[:, None] - j < window)
    s = jnp.concatenate([jnp.where(ok[:, None], s_all, -jnp.inf),
                         (q * k).sum(-1)[..., None]], -1) / jnp.sqrt(F32(d))
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("nhk,hkd->nhd", p[..., :S],
                   jnp.repeat(v_all, rep, axis=0)) + p[..., S:] * v
    a = a.reshape(n, n_head * d) * jax.nn.sigmoid(h @ w["wg"])
    x = x + rms_norm(a @ w["wo"], w["n_post_attn"], eps)
    return x, rms_norm(x, w["n_pre_mlp"], eps)


def replay(params, config, tokens, pos, swaps, kv, outer, device):
    """Final hidden states [n, D] of positions ``pos`` [n] with the exchange
    ``swaps`` [n, expert layers] names (an entry of SWAPS, 1-based; 0: none)
    made at each expert layer, every other position as the main pass left
    it; and each expert layer's near-ties ON THAT STREAM (:func:`route`)."""
    n_dense = config["num_dense_layers"]
    eps = config["rms_norm_eps"]
    n = len(pos)
    # to a power of two of whole blocks, so that few shapes compile
    pad = REPLAY_ROWS * (1 << int(np.ceil(np.log2(-(-n // REPLAY_ROWS))))) - n
    pos = jnp.asarray(np.pad(pos, (0, pad)), jnp.int32)
    swaps = jnp.asarray(np.pad(swaps, ((0, pad), (0, 0))))
    with jax.default_matmul_precision("highest"):
        x = outer["embed"][jnp.asarray(tokens)[pos]] \
            * np.sqrt(config["hidden_size"])
        ties = []
        for l, dense, window in _layers(config):
            w = layer_weights(params, l, n_dense, device)
            x, h = (jnp.concatenate(parts) for parts in zip(*(
                attention_one(x[a:a + REPLAY_ROWS], pos[a:a + REPLAY_ROWS],
                              w, *kv[l], eps=eps, window=window,
                              **_attn_kw(config))
                for a in range(0, n + pad, REPLAY_ROWS))))
            if dense:
                x = dense_close(x, h, w, eps=eps)
                continue
            _, local, _, tie = route(
                h, w, None, n + pad, swap=swaps[:, l - n_dense],
                **_route_kw(config))
            x = expert_close(x, h, w, local, eps=eps, cap=n + pad)
            ties.append(tuple(np.asarray(t)[:n] for t in tie))
    return x[:n], ties


def _below_best(logits, token):
    return float(logits.max() - logits[token])


def admissible_rows(params, config, tokens, rows, device, logits, keep,
                    outer):
    """``logits`` [len(rows), V] with each row whose next token is not its
    best replaced by its admissible evaluation (the comment above) under
    which that token sits highest."""
    n_exp = len(keep["ties"])
    n_tok = len(tokens)
    logits = np.array(logits)
    first, places = {}, {}
    for at, r in enumerate(rows):
        first.setdefault(int(r), at)
        places.setdefault(int(r), []).append(at)
    # (row, swaps so far, the near-ties on that stream): rows to try
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
        x, ties = replay(params, config, tokens,
                         np.asarray([r for r, _ in tries]),
                         np.asarray([sw for _, sw in tries]), keep["kv"],
                         outer, device)
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
                variant=()):
    """Reference logits [len(rows), V] at positions ``rows`` of ``tokens``
    (V the chip's share of the vocabulary).  Without ``routing`` a row at a
    near-tie of the router is the admissible evaluation its next token fits
    best (:func:`admissible_rows`); with it, the given choice stands."""
    tokens = np.asarray(tokens)
    S = len(tokens)
    if S > QUERY_BLOCK and S % QUERY_BLOCK:      # whole query blocks
        tokens = np.pad(tokens, (0, -S % QUERY_BLOCK))
    if routing is not None:       # [S or fewer, k] a layer: rows to the end
        routing = [np.pad(np.asarray(r), ((0, len(tokens) - len(r)), (0, 0)))
                   for r in routing]
    keep = {} if routing is None and not variant else None
    x, outer = hidden_states(params, config, tokens, device, routing,
                             variant=variant, n_live=max(rows) + 1, keep=keep)
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x[jnp.asarray(rows)], outer["norm"],
                     config["rms_norm_eps"])
        logits = h @ outer["lm_head"]
    if keep is None:
        return logits
    return admissible_rows(params, config, tokens[:S], rows, device, logits,
                           keep, outer)
