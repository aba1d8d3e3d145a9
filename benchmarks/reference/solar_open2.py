"""Plain reference forward of Solar-Open2-250B (upstage, ``model_type:
solar_open2``; config.json), ONE CHIP'S SHARE of it as the configuration
file states: float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching, independent of ``deepspeed_tpu.models``, ``deepspeed_tpu.moe`` and
``deepspeed_tpu.ops``.  Written from the equations of ISSUE 59, not from the
package's code.  ``N(.)`` is RMSNorm with its own gain, eps ``rms_norm_eps``;
layers are numbered from 0 as ``gqa_layers`` numbers them; EVERY layer's MLP
is the expert block (``first_k_dense_replace`` 0):

    x = embed[tokens]
    per layer:  x = x + attn(N_in(x));  x = x + mlp(N_post(x))
    logits = N_f(x) W_head                           the chip's vocabulary rows

    l in gqa_layers (softmax attention WITHOUT positions: ``use_rope`` false;
    64 query heads over 8 key-value heads of 128), h = N_in(x):
        q = h Wq;  k = h Wk;  v = h Wv               no head norm, no rotation
        score(t, j) = q_head(t) . k_group(j) / sqrt(128),  every j <= t, a
            key-value head serving its 8 query heads
        a = (softmax_j(score) v * sigmoid(h Wg)) Wo  the gate elementwise
            over the 8,192 values (``use_gqa_gate``)

    otherwise KDA (H = 64 heads of d = 128), h = N_in(x)_t:
        u = h [Wq | Wk | Wv]
        c[t] = silu(sum_{i=0..3} conv[:, i] * u[t - 3 + i])   zeros before t = 0
        q = l2norm_head(c_q) * d^-0.5;  k = l2norm_head(c_k);  v = c_v
        g = -exp(A_log[head]) * softplus((h Wf_down) Wf_up + dt_bias)
        beta = 2 sigmoid(h Wb)        ``kda_allow_neg_eigval``: in (0, 2), so
            that ``I - beta k k^T`` has its moving eigenvalue in (-1, 1)
        per head, S [d, d], S_0 = 0:   S' = diag(exp(g_t)) S_{t-1}
            S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;   o_t = S_t^T q_t
        a = (N_o(o_t) * sigmoid((h Wg_down) Wg_up + b_g)) Wo
      ONE STEP A TOKEN, never a chunk form.

    mlp: s = sigmoid(h Wr) over the router's 320 in float32; the 8 highest of
    s + b (one group; the bias picks, it does not weigh); w = s_e / (sum s +
    1e-20) over the eight, times ``routed_scaling_factor`` = 1; shared(h) +
    sum over the chosen experts HELD HERE of w_e expert_e(h), each a SwiGLU
    of width 1280.  The router and the experts' closes are
    ``reference/trinity.py``'s (benchmark code, not the package's), without
    that model's post-norm; the delta rule's one step, its loop and the
    gated output norm are ``reference/kimi_linear.py``'s, which write the
    same equations; the recurrence's INPUTS (``beta`` above all) and both
    attention blocks are written here.

What the catalog's ``config`` does not carry (sigmoid scores and the
selection-only bias, the shared expert's width, the gates' rank and biases,
no convolution bias, l2norm's eps, ``A_log`` a head and ``dt_bias`` a channel,
the d^-0.5 on q, the gate elementwise, no q / k head norm) is listed in the
configuration file under ``assumed``.  Keys of the row that no layer uses:
``intermediate_size`` (no dense layer), ``rope_theta`` and
``partial_rotary_factor`` (``use_rope`` false), ``kda_use_full_proj`` (false:
the two gates stay low-rank).  Departures from the published description:
float32 throughout; seeded weights.

``routing=`` replaces the reference's own top-8 by the program's;
``variant=`` breaks one equation on purpose, for ``tools/
solar_open2_agreement.py``'s negative controls; nothing else uses them.

Near-ties of the router are admitted by ``reference/trinity.py``'s rule,
UNCHANGED (its ``route``, ``SWAPS`` and ``NEAR_TIE``).  A row is re-evaluated
with one exchange by :func:`replay`: for that it needs the recurrent state
BEFORE the row in every KDA layer after the first expert layer.  A state is
4 MB a layer here (twice Kimi's), so the states before all 640 rows read
would be 8 GB beside 6.6 GB of weights: the main pass keeps each layer's
INPUT instead (84 MB a layer) and, once the logits say which rows have to
be tried, :func:`states_before` runs those layers' recurrences again and
keeps the state before each of those rows only.

Layer by layer on weights cast up to float32 one layer at a time, attention
in query blocks; of the program it knows only the NAMES in its weight tree.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.kimi_linear import (L2_EPS, NO_POST_NORM,
                                              ROUTE_VARIANTS, delta_step,
                                              kda_back, kda_close, qkv_rows,
                                              recurrence)
from benchmarks.reference.trinity import (NEAR_TIE, QUERY_BLOCK, REPLAY_ROWS,
                                          _below_best, _capacity, _up,
                                          attention, expert_close,
                                          outer_weights, rms_norm, rope,
                                          route)

F32 = jnp.float32
# the controls under which the near-tie search still runs (a wrong router or
# a wrong beta must fail WITH its near-ties admitted); under any other the
# one evaluation stands
REPLAYED = ROUTE_VARIANTS | {"beta_sigmoid"}
# rows of one sequence that are tried at their near-ties, at most: the state
# before each is 4 MB a layer.  A served sequence has a few dozen rows whose
# token is not the reference's own best; one with more than this has a fault
# that no exchange explains, and its later rows stand as evaluated
MAX_TRIED = 256


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def _layers(config):
    """(layer index, "gqa" | "kda", index among its kind) for each layer."""
    gqa = set(config["gqa_layers"])
    seen = {"gqa": 0, "kda": 0}
    for l in range(config["num_hidden_layers"]):
        kind = "gqa" if l in gqa else "kda"
        yield l, kind, seen[kind]
        seen[kind] += 1


def layer_weights(params, config, l, device):
    _, kind, j = list(_layers(config))[l]
    ly = params["layers"]
    g = lambda *path: _up(functools.reduce(lambda t, k: t[k], path, ly)[l],
                          device)
    w = {"n_in": g("attn_norm", "scale"), "n_post": g("mlp_norm", "scale")}
    w.update({k: _up(v[j], device) for k, v in params[kind].items()})
    # the held experts' matrices stay as stored (bf16) and are cast up one
    # expert at a time inside expert_close
    raw = lambda k: jax.device_put(ly["mlp"][k], device)[l]
    w.update(router=g("mlp", "gate_w"), bias=g("mlp", "gate_bias"),
             e_gate=raw("w_gate"), e_up=raw("w_up"), e_down=raw("w_down"),
             s_gate=g("mlp", "shared", "w_gate"),
             s_up=g("mlp", "shared", "w_up"),
             s_down=g("mlp", "shared", "w_down"))
    return w


# ---------------------------------------------------------------------------
# KDA: the recurrence's inputs (beta in (0, 2))
# ---------------------------------------------------------------------------
def kda_inputs(h, taps, w, *, heads, variant=()):
    """From ``h`` [n, D] (normed) and the convolution's inputs ``taps`` (a
    list of [n, 3 H d]: ``u`` = h [Wq | Wk | Wv] of the rows ``taps - 1`` ..
    1 positions before each row, then of the rows themselves) to the
    recurrence's inputs: q, k, v, g [n, H, d], beta [n, H] in (0, 2), and
    the output gate before its sigmoid [n, H d]."""
    n = h.shape[0]
    c = taps[-1] if "no_conv" in variant else sum(
        t * w["conv"][:, i] for i, t in enumerate(taps))
    c = jax.nn.silu(c)
    q, k, v = (t.reshape(n, heads, -1) for t in jnp.split(c, 3, -1))
    d = q.shape[-1]
    l2 = lambda t: t / jnp.sqrt((t * t).sum(-1, keepdims=True) + L2_EPS)
    q, k = l2(q) * d ** -0.5, l2(k)
    f = (h @ w["wf_down"]) @ w["wf_up"] + w["dt_bias"]
    g = -jnp.exp(w["a_log"])[:, None] * jax.nn.softplus(
        f.reshape(n, heads, d))
    # kda_allow_neg_eigval: twice the sigmoid ("beta_sigmoid": the control
    # that leaves the 2 out, Kimi's form)
    beta = jax.nn.sigmoid(h @ w["wb"])
    if "beta_sigmoid" not in variant:
        beta = 2.0 * beta
    gate = (h @ w["wg_down"]) @ w["wg_up"] + w["b_g"]
    return q, k, v, g, beta, gate


@functools.partial(jax.jit, static_argnames=("heads", "eps", "variant"))
def kda_front(x, w, *, heads, eps, variant=()):
    h = rms_norm(x, w["n_in"], eps)
    u = qkv_rows(h, w)
    # u of the taps - 1 positions before each row: zeros before position 0
    taps = [jnp.pad(u, ((i, 0), (0, 0)))[:u.shape[0]]
            for i in range(w["conv"].shape[1] - 1, -1, -1)]
    return kda_inputs(h, taps, w, heads=heads, variant=variant)


@jax.jit
def states_before(S0, q, k, v, g, beta, at):
    """The state BEFORE each position of ``at`` [n] (ascending; entries past
    the sequence never come) under the one-step recurrence from ``S0``: [n,
    H, d, d].  The loop runs to the last of them and keeps nothing else."""
    n = at.shape[0]
    hi = jnp.max(jnp.where(at < q.shape[0], at, -1)) + 1

    def body(t, carry):
        S, out, j = carry
        # slot j holds the newest state until position at[j] has come;
        # slot n is scratch once every position has
        out = jax.lax.dynamic_update_index_in_dim(out, S, j, 0)
        j = j + (at[jnp.minimum(j, n - 1)] == t) * (j < n)
        _, S = delta_step(S, q[t], k[t], v[t], g[t], beta[t])
        return S, out, j

    out = jnp.zeros((n + 1,) + S0.shape, F32)
    return jax.lax.fori_loop(0, hi, body, (S0, out, jnp.int32(0)))[1][:n]


# ---------------------------------------------------------------------------
# the per-head softmax layers: no positions, gated
# ---------------------------------------------------------------------------
def gqa_qkvg(h, w, *, n_head, n_kv):
    """q [n, H, d], k, v [n, Hkv, d] and the gate's logits [n, H d] of rows
    ``h`` [n, D]: four projections, nothing else."""
    n = h.shape[0]
    d = w["wq"].shape[1] // n_head
    return ((h @ w["wq"]).reshape(n, n_head, d),
            (h @ w["wk"]).reshape(n, n_kv, d),
            (h @ w["wv"]).reshape(n, n_kv, d), h @ w["wg"])


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv", "eps", "theta", "variant"))
def gqa_block(x, w, *, n_head, n_kv, eps, theta, variant=()):
    """x -> (x + attention, N_post of that, keys and values [Hkv, S, d])."""
    S = x.shape[0]
    h = rms_norm(x, w["n_in"], eps)
    q, k, v, gate = gqa_qkvg(h, w, n_head=n_head, n_kv=n_kv)
    q, k, v = (t.transpose(1, 0, 2) for t in (q, k, v))
    if "rope_on_gqa" in variant:        # RoPE wrongly applied (use_rope)
        q, k = rope(q, theta), rope(k, theta)
    a = attention(q, k, v, 0).transpose(1, 0, 2).reshape(S, -1)
    if "no_gqa_gate" not in variant:
        a = a * jax.nn.sigmoid(gate)
    x = x + a @ w["wo"]
    return x, rms_norm(x, w["n_post"], eps), k, v


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------
def _kda_kw(config):
    return dict(heads=config["linear_attn_config"]["num_heads"])


def _gqa_kw(config):
    return dict(n_head=config["num_attention_heads"],
                n_kv=config["num_key_value_heads"])


def _route_kw(config):
    return dict(top_k=config["num_experts_per_tok"],
                first=config["expert_parallel"]["first_expert"],
                route_scale=float(config["routed_scaling_factor"]),
                route_norm=bool(config["norm_topk_prob"]))


def _state0(w, heads):
    d = w["wq"].shape[1] // heads
    return jnp.zeros((heads, d, d), F32)


def hidden_states(params, config, tokens, device, routing=None,
                  return_routing=False, variant=(), n_live=None, keep=None):
    """Final hidden states [S, D] and the outer weights; with
    ``return_routing`` also the router indices used, [layers, S, k].  Rows
    at and past ``n_live`` reach no row that is read: the recurrences stop
    there and the experts skip them.  ``keep`` (a dict) is filled with what
    :func:`replay` needs."""
    variant = tuple(sorted(variant))
    eps = config["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        outer = outer_weights(params, device)
        tokens = jax.device_put(jnp.asarray(tokens, jnp.int32), device)
        S = tokens.shape[0]
        n_live = S if n_live is None else n_live
        x = outer["embed"][tokens]
        used = []
        if keep is not None:
            keep.update(x_in={}, kv={}, ties=[], variant=variant)
        for l, kind, _ in _layers(config):
            w = layer_weights(params, config, l, device)
            if kind == "kda":
                if keep is not None and l:  # attends after the first router
                    keep["x_in"][l] = x
                q, k, v, g, beta, gate = kda_front(
                    x, w, eps=eps, variant=variant, **_kda_kw(config))
                o, _ = recurrence(_state0(w, q.shape[1]), q, k, v, g, beta,
                                  n_live, n_span=0, emit=False,
                                  bf16_state="bf16_state" in variant)
                x, h = kda_back(x, o, gate, w, eps, variant)
            else:
                x, h, k, v = gqa_block(
                    x, w, eps=eps, variant=variant,
                    theta=float(config["rope_theta"]), **_gqa_kw(config))
                if keep is not None:
                    keep["kv"][l] = (k, v)
            if keep is not None and l == 0:
                keep["start"] = (x, h)
            chosen = None if routing is None else jnp.asarray(routing[l])
            chosen, local, fullest, tie = route(
                h, w, chosen, n_live, variant=variant, **_route_kw(config))
            x = expert_close(x, h, w, local, eps=eps,
                             cap=_capacity(fullest, S), variant=NO_POST_NORM)
            used.append(chosen)
            if keep is not None:
                keep["ties"].append(tuple(np.asarray(t) for t in tie))
        if return_routing:
            return x, outer, jnp.stack(used)
        return x, outer


# ---------------------------------------------------------------------------
# one row again, with an exchange at the edge of its top-8
# ---------------------------------------------------------------------------
def _pow2_blocks(n: int) -> int:
    """``n`` rounded up to a power of two of whole blocks of REPLAY_ROWS,
    so that few shapes compile."""
    return REPLAY_ROWS * (1 << int(np.ceil(np.log2(-(-n // REPLAY_ROWS)))))


def keep_states(params, config, rows, keep, device):
    """The second pass over the KDA layers after the first router: the
    state before each of ``rows`` (the positions :func:`replay` will be
    asked for), from the layer inputs the main pass kept."""
    rows = sorted(int(r) for r in rows)
    T = next(iter(keep["x_in"].values())).shape[0]
    at = jnp.asarray(rows + [T] * (_pow2_blocks(len(rows)) - len(rows)),
                     jnp.int32)
    keep["slot"] = {r: i for i, r in enumerate(rows)}
    keep["states"] = {}
    with jax.default_matmul_precision("highest"):
        for l, kind, _ in _layers(config):
            if kind != "kda" or not l:
                continue
            w = layer_weights(params, config, l, device)
            q, k, v, g, beta, _ = kda_front(
                keep["x_in"][l], w, eps=config["rms_norm_eps"],
                variant=keep["variant"], **_kda_kw(config))
            keep["states"][l] = states_before(
                _state0(w, q.shape[1]), q, k, v, g, beta, at)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "variant"))
def kda_one(x, pos, w, x_in, states, *, heads, eps, variant=()):
    """:func:`kda_front`, one step and ``kda_close`` for single positions:
    ``x`` [n, D] the streams of positions ``pos`` [n] on top of the
    sequence's own earlier rows (``x_in``: the layer's inputs of the main
    pass, for the convolution's three rows before) and the state the main
    pass had BEFORE each (``states`` [n, H, d, d])."""
    taps = w["conv"].shape[1]
    norm = lambda t: rms_norm(t, w["n_in"], eps)
    back = pos[:, None] - jnp.arange(taps - 1, 0, -1)[None, :]    # [n, 3]
    ub = jnp.where((back >= 0)[..., None],
                   qkv_rows(norm(x_in[jnp.maximum(back, 0)]), w), 0.0)
    h = norm(x)
    q, k, v, g, beta, gate = kda_inputs(
        h, [ub[:, i] for i in range(taps - 1)] + [qkv_rows(h, w)], w,
        heads=heads, variant=variant)
    o, _ = delta_step(states, q, k, v, g, beta)
    return kda_close(x, o, gate, w, eps)


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv", "eps"))
def gqa_one(x, pos, w, k_all, v_all, *, n_head, n_kv, eps):
    """:func:`gqa_block` for single positions: the sequence's own keys and
    values of the EARLIER positions, and their own of this evaluation."""
    n = x.shape[0]
    S = k_all.shape[1]
    rep = n_head // n_kv
    h = rms_norm(x, w["n_in"], eps)
    q, k, v, gate = gqa_qkvg(h, w, n_head=n_head, n_kv=n_kv)
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s_all = jnp.einsum("nhd,hkd->nhk", q, jnp.repeat(k_all, rep, axis=0))
    ok = jnp.arange(S)[None, :] < pos[:, None]
    s = jnp.concatenate([jnp.where(ok[:, None], s_all, -jnp.inf),
                         (q * k).sum(-1)[..., None]], -1) \
        / jnp.sqrt(F32(q.shape[-1]))
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("nhk,hkd->nhd", p[..., :S],
                   jnp.repeat(v_all, rep, axis=0)) + p[..., S:] * v
    x = x + (a.reshape(n, -1) * jax.nn.sigmoid(gate)) @ w["wo"]
    return x, rms_norm(x, w["n_post"], eps)


def replay(params, config, pos, swaps, keep, device):
    """Final hidden states [n, D] of positions ``pos`` [n] with the exchange
    ``swaps`` [n, layers] names (an entry of SWAPS, 1-based; 0: none) made
    at each layer's router, every other position as the main pass left it;
    and each layer's near-ties ON THAT STREAM."""
    eps = config["rms_norm_eps"]
    n = len(pos)
    pad = _pow2_blocks(n) - n
    slot = jnp.asarray(np.pad([keep["slot"][int(r)] for r in pos], (0, pad),
                              mode="edge"), jnp.int32)
    pos = jnp.asarray(np.pad(pos, (0, pad), mode="edge"), jnp.int32)
    swaps = jnp.asarray(np.pad(swaps, ((0, pad), (0, 0))))
    blocks = range(0, n + pad, REPLAY_ROWS)
    cut = lambda t, a: t[a:a + REPLAY_ROWS]
    kda_variant = tuple(v for v in keep["variant"] if v not in ROUTE_VARIANTS)
    with jax.default_matmul_precision("highest"):
        # up to the first router a row is what the main pass made of it
        x, h = (t[pos] for t in keep["start"])
        ties = []
        for l, kind, _ in _layers(config):
            w = layer_weights(params, config, l, device)
            if l and kind == "kda":
                x, h = (jnp.concatenate(parts) for parts in zip(*(
                    kda_one(cut(x, a), cut(pos, a), w, keep["x_in"][l],
                            keep["states"][l][cut(slot, a)], eps=eps,
                            variant=kda_variant, **_kda_kw(config))
                    for a in blocks)))
            elif l:
                x, h = (jnp.concatenate(parts) for parts in zip(*(
                    gqa_one(cut(x, a), cut(pos, a), w, *keep["kv"][l],
                            eps=eps, **_gqa_kw(config)) for a in blocks)))
            _, local, _, tie = route(
                h, w, None, n + pad, swap=swaps[:, l],
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
    front = front[:MAX_TRIED]
    if front:
        keep_states(params, config, [r for r, _, _ in front], keep, device)
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
                variant=()):
    """Reference logits [len(rows), V] at positions ``rows`` of ``tokens``
    (V the chip's share of the vocabulary).  Without ``routing`` a row at a
    near-tie of the router is the admissible evaluation its next token fits
    best (:func:`admissible_rows`), also under a ``variant`` that breaks the
    router's weights or ``beta`` alone (:data:`REPLAYED`);
    with ``routing``, or under any other control, the one evaluation stands."""
    tokens = np.asarray(tokens)
    rows = np.asarray(rows)
    S = len(tokens)
    if S > QUERY_BLOCK and S % QUERY_BLOCK:      # whole query blocks
        tokens = np.pad(tokens, (0, -S % QUERY_BLOCK))
    if routing is not None:       # [S or fewer, k] a layer: rows to the end
        routing = [np.pad(np.asarray(r), ((0, len(tokens) - len(r)), (0, 0)))
                   for r in routing]
    keep = {} if routing is None and REPLAYED.issuperset(variant) else None
    x, outer = hidden_states(params, config, tokens, device, routing,
                             variant=variant, n_live=int(rows.max()) + 1,
                             keep=keep)
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x[jnp.asarray(rows)], outer["norm"],
                     config["rms_norm_eps"])
        logits = h @ outer["lm_head"]
    if keep is None:
        return logits
    return admissible_rows(params, config, tokens[:S], rows, device, logits,
                           keep, outer)
