"""Plain reference forward of NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type:
nemotron_h``; config.json), ONE CHIP'S SHARE of it as the configuration file
states: float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching, independent of ``deepspeed_tpu.models``, ``deepspeed_tpu.moe`` and
``deepspeed_tpu.ops``.  Written from the equations of ISSUE 63, not from the
package's code.  ``N(.)`` is RMSNorm with its own gain, eps
``layer_norm_epsilon``; a layer is ONE mixer, its letter in
``hybrid_override_pattern``:

    x = embed[tokens]
    per layer l:  x = x + mixer_l(N_l(x))
    logits = N_f(x) W_head                           the chip's vocabulary rows

    M (Mamba-2: H = mamba_num_heads heads of P = mamba_head_dim, G = n_groups
    groups, N = ssm_state_size), h = N_l(x)_t:
        [z | xBC | dt] = h W_in           widths H P | H P + 2 G N | H
        c[t] = silu(sum_{i=0..3} conv[:, i] * xBC[t - 3 + i] + b_conv)
            zeros before t = 0
        x [H, P] | B [G, N] | C [G, N] = c;  head i reads group i // (H / G)
        dt = softplus(dt + dt_bias);  a = -exp(A_log)        one a head
        per head, S [P, N], S_0 = 0:
            S_t = exp(dt_t a) S_{t-1} + (dt_t x_t) B_t^T
            y_t = S_t C_t + D x_t
        o = N_groups(y * silu(z)) * w     the gate BEFORE the norm, the norm
            over each of the G groups of H P / G channels
        mixer = o W_out
      ONE STEP A TOKEN, never a chunked form (the program's prefill runs the
      chunked one: the two must agree).

    * (softmax attention WITHOUT positions; 32 query heads over 2 key-value
    heads of 128), h = N_l(x):
        q = h Wq;  k = h Wk;  v = h Wv     no head norm, no rotation, no gate
        score(t, j) = q_head(t) . k_group(j) / sqrt(128),  every j <= t
        mixer = softmax_j(score) v Wo

    E: s = sigmoid(h Wr) over the router's 128 in float32; the 6 highest of
    s + b (one group; the bias picks, it does not weigh); w = s_e / (sum s +
    1e-20) over the six, times ``routed_scaling_factor`` = 2.5;
    mixer = shared(h) + sum over the chosen experts HELD HERE of w_e
    expert_e(h), an expert ``relu(h W_up)^2 W_down`` (two matrices), the
    shared one the same at ``moe_shared_expert_intermediate_size``.  The
    router is ``reference/trinity.py``'s (benchmark code, not the package's).

The stored expert matrices may carry zero columns (``W_up``) and zero rows
(``W_down``) beyond the published width (the program pads 1,856 to whole
lane tiles): ``relu(0)^2 = 0``, so they are used as stored.

What the catalog's ``config`` does not carry (no position encoding in the
attention layers, the dtype of the state) is listed in the configuration
file under ``assumed``.  Departures from the published description: float32
throughout; seeded weights.

``routing=`` replaces the reference's own top-6 by the program's;
``variant=`` breaks one equation on purpose, for ``tools/
nemotron3_nano_agreement.py``'s negative controls; nothing else uses them.

Near-ties of the router are admitted by ``reference/trinity.py``'s rule,
UNCHANGED (its ``route``, ``SWAPS`` and ``NEAR_TIE``).  A row is re-evaluated
with one exchange by :func:`replay`: for that it needs, in every Mamba layer
after the first router, the state BEFORE the row (2 MB a layer) and the
layer's inputs of the three rows before it (the convolution's taps).  The
main pass keeps each such layer's INPUT and, once the logits say which rows
have to be tried, :func:`keep_states` runs those layers' recurrences again
and keeps the state before each of those rows only
(``reference/solar_open2.py``'s device).

Layer by layer on weights cast up to float32 one layer at a time, attention
in query blocks; of the program it knows only the NAMES in its weight tree.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.kimi_linear import ROUTE_VARIANTS
from benchmarks.reference.trinity import (NEAR_TIE, QUERY_BLOCK, REPLAY_ROWS,
                                          _below_best, _capacity, _up,
                                          attention, outer_weights, rms_norm,
                                          rope, route)

F32 = jnp.float32
KINDS = {"M": "ssm", "*": "gqa", "E": "moe"}
# rows of one sequence that are tried at their near-ties, at most: the state
# before each is 2 MB a Mamba layer
MAX_TRIED = 256


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def _layers(config):
    """(layer index, "ssm" | "gqa" | "moe", index among its kind)."""
    seen = {k: 0 for k in KINDS.values()}
    for l, letter in enumerate(config["hybrid_override_pattern"]):
        kind = KINDS[letter]
        yield l, kind, seen[kind]
        seen[kind] += 1


def layer_weights(params, config, l, device):
    _, kind, j = list(_layers(config))[l]
    w = {"norm": _up(params["norms"]["scale"][l], device)}
    if kind != "moe":
        w.update({k: _up(v[j], device) for k, v in params[kind].items()})
        return w
    m = params["layers"]["mlp"]
    # the held experts' matrices stay as stored (bf16) and are cast up one
    # expert at a time inside expert_close
    raw = lambda k: jax.device_put(m[k], device)[j]
    w.update(router=_up(m["gate_w"][j], device),
             bias=_up(m["gate_bias"][j], device),
             e_up=raw("w_up"), e_down=raw("w_down"),
             s_up=_up(m["shared"]["w_up"][j], device),
             s_down=_up(m["shared"]["w_down"][j], device))
    return w


# ---------------------------------------------------------------------------
# Mamba-2
# ---------------------------------------------------------------------------
def _ssm_kw(config):
    return dict(heads=config["mamba_num_heads"], groups=config["n_groups"],
                eps=config["layer_norm_epsilon"])


def ssm_inputs(zxd, taps, w, *, heads, groups, variant=()):
    """From the rows' projections ``zxd`` = h W_in [n, 2 H P + 2 G N + H] and
    the convolution's inputs ``taps`` (a list of [n, H P + 2 G N]: ``xBC`` of
    the rows ``taps - 1`` .. 1 positions before each row, then of the rows
    themselves) to the recurrence's inputs: x [n, H, P], B and C [n, G, N],
    dt [n, H], and the gate z [n, H P]."""
    n = zxd.shape[0]
    c = sum(t * w["conv"][:, i] for i, t in enumerate(taps))
    if "no_conv_bias" not in variant:
        c = c + w["conv_b"]
    c = jax.nn.silu(c)
    di = w["wo"].shape[0]
    gn = (c.shape[1] - di) // 2
    x = c[:, :di].reshape(n, heads, -1)
    Bm = c[:, di:di + gn].reshape(n, groups, -1)
    Cm = c[:, di + gn:].reshape(n, groups, -1)
    dt = zxd[:, di + c.shape[1]:di + c.shape[1] + heads]
    if "no_dt_bias" not in variant:
        dt = dt + w["dt_bias"]
    return x, Bm, Cm, jax.nn.softplus(dt), zxd[:, :di]


def xbc_rows(zxd, w):
    """The convolution's input rows: the middle of ``h W_in``."""
    di = w["wo"].shape[0]
    return zxd[:, di:di + w["conv"].shape[0]]


def ssm_step(S, x, dt, Bm, Cm, w, bf16_state=False):
    """ONE token: S [..., H, P, N], x [..., H, P], dt [..., H], B and C
    [..., G, N] -> (y [..., H, P] with the skip, S)."""
    rep = x.shape[-2] // Bm.shape[-2]
    Bh, Ch = jnp.repeat(Bm, rep, axis=-2), jnp.repeat(Cm, rep, axis=-2)
    a = -jnp.exp(w["a_log"])
    S = jnp.exp(dt * a)[..., None, None] * S \
        + (dt[..., None] * x)[..., :, None] * Bh[..., None, :]
    if bf16_state:      # the control: a state kept in a lower precision
        # (a convert pair is folded away on the TPU)
        S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
    y = (S * Ch[..., None, :]).sum(-1)
    return y + w["d_skip"][:, None] * x, S


@functools.partial(jax.jit, static_argnames=("heads", "groups", "eps",
                                             "variant"))
def ssm_front(x, w, *, heads, groups, eps, variant=()):
    zxd = rms_norm(x, w["norm"], eps) @ w["w_in"]
    u = xbc_rows(zxd, w)
    # xBC of the taps - 1 positions before each row: zeros before position 0
    taps = [jnp.pad(u, ((i, 0), (0, 0)))[:u.shape[0]]
            for i in range(w["conv"].shape[1] - 1, -1, -1)]
    return ssm_inputs(zxd, taps, w, heads=heads, groups=groups,
                      variant=variant)


@functools.partial(jax.jit, static_argnames=("bf16_state",))
def recurrence(S0, x, dt, Bm, Cm, w, n_live, bf16_state=False):
    """The recurrence over positions ``0 .. n_live - 1``, one step a token:
    (the state after position ``n_live - 1``, y); later positions get y = 0
    (no row that is read can see them)."""
    def body(t, carry):
        S, out = carry
        y, S = ssm_step(S, x[t], dt[t], Bm[t], Cm[t], w, bf16_state)
        return S, out.at[t].set(y)

    return jax.lax.fori_loop(0, n_live, body, (S0, jnp.zeros_like(x)))


@functools.partial(jax.jit, static_argnames=("groups", "eps", "variant"))
def ssm_close(x, y, z, w, *, groups, eps, variant=()):
    """x + (N_groups(y * silu(z)) * w) W_out."""
    n = y.shape[0]
    y = y.reshape(n, -1)
    grouped = lambda t: rms_norm(t.reshape(n, groups, -1), 1.0,
                                 eps).reshape(n, -1)
    if "gate_after_norm" in variant:       # the control: norm, THEN gate
        o = grouped(y) * w["o_norm"] * jax.nn.silu(z)
    else:
        o = grouped(y * jax.nn.silu(z)) * w["o_norm"]
    return x + o @ w["wo"]


@jax.jit
def states_before(S0, x, dt, Bm, Cm, w, at):
    """The state BEFORE each position of ``at`` [n] (ascending; entries past
    the sequence never come) under the one-step recurrence from ``S0``: [n,
    H, P, N].  The loop runs to the last of them and keeps nothing else."""
    n = at.shape[0]
    hi = jnp.max(jnp.where(at < x.shape[0], at, -1)) + 1

    def body(t, carry):
        S, out, j = carry
        # slot j holds the newest state until position at[j] has come;
        # slot n is scratch once every position has
        out = jax.lax.dynamic_update_index_in_dim(out, S, j, 0)
        j = j + (at[jnp.minimum(j, n - 1)] == t) * (j < n)
        _, S = ssm_step(S, x[t], dt[t], Bm[t], Cm[t], w)
        return S, out, j

    out = jnp.zeros((n + 1,) + S0.shape, F32)
    return jax.lax.fori_loop(0, hi, body, (S0, out, jnp.int32(0)))[1][:n]


def _state0(x, Bm):
    return jnp.zeros(x.shape[1:] + Bm.shape[2:], F32)


# ---------------------------------------------------------------------------
# attention: no positions, no gate
# ---------------------------------------------------------------------------
def _gqa_kw(config):
    return dict(n_head=config["num_attention_heads"],
                n_kv=config["num_key_value_heads"],
                eps=config["layer_norm_epsilon"])


def gqa_qkv(h, w, *, n_head, n_kv):
    n = h.shape[0]
    d = w["wq"].shape[1] // n_head
    return ((h @ w["wq"]).reshape(n, n_head, d),
            (h @ w["wk"]).reshape(n, n_kv, d),
            (h @ w["wv"]).reshape(n, n_kv, d))


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv", "eps",
                                             "theta", "variant"))
def gqa_block(x, w, *, n_head, n_kv, eps, theta, variant=()):
    """x -> (x + attention, keys and values [Hkv, S, d])."""
    S = x.shape[0]
    q, k, v = (t.transpose(1, 0, 2) for t in gqa_qkv(
        rms_norm(x, w["norm"], eps), w, n_head=n_head, n_kv=n_kv))
    if "rope_on_attn" in variant:       # RoPE wrongly applied
        q, k = rope(q, theta), rope(k, theta)
    a = attention(q, k, v, 0).transpose(1, 0, 2).reshape(S, -1)
    return x + a @ w["wo"], k, v


# ---------------------------------------------------------------------------
# experts: two matrices and relu^2
# ---------------------------------------------------------------------------
def relu2_mlp(h, wu, wd, variant=()):
    u = h @ wu
    if "silu_experts" in variant:       # the control: the wrong activation
        return jax.nn.silu(u) @ wd
    return jnp.square(jax.nn.relu(u)) @ wd


@functools.partial(jax.jit, static_argnames=("cap", "variant"))
def expert_close(x, h, w, local, *, cap, variant=()):
    """x + shared(h) + the held experts' weighed outputs: per held expert,
    the (at most ``cap``) rows that chose it, gathered, run densely, added
    back (``reference/trinity.py``'s way)."""
    S, D = h.shape
    h_pad = jnp.concatenate([h, jnp.zeros((1, D), F32)])

    def one(acc, ew):
        wu, wd, col = ew
        rows = jnp.nonzero(col != 0, size=cap, fill_value=S)[0]
        out = relu2_mlp(h_pad[rows], wu.astype(F32), wd.astype(F32), variant)
        col_pad = jnp.concatenate([col, jnp.zeros((1,), F32)])
        return acc.at[rows].add(col_pad[rows][:, None] * out,
                                mode="drop"), None

    m, _ = jax.lax.scan(one, relu2_mlp(h, w["s_up"], w["s_down"], variant),
                        (w["e_up"], w["e_down"], local.T))
    return x + m


def _route_kw(config):
    return dict(top_k=config["num_experts_per_tok"],
                first=config["expert_parallel"]["first_expert"],
                route_scale=float(config["routed_scaling_factor"]),
                route_norm=bool(config["norm_topk_prob"]))


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------
def hidden_states(params, config, tokens, device, routing=None,
                  return_routing=False, variant=(), n_live=None, keep=None,
                  states=None):
    """Final hidden states [S, D] and the outer weights; with
    ``return_routing`` also the router indices used, [expert layers, S, k].
    Rows at and past ``n_live`` reach no row that is read: the recurrences
    stop there and the experts skip them.  ``keep`` (a dict) is filled with
    what :func:`replay` needs, ``states`` (a dict) with each Mamba layer's
    state [H, P, N] after position ``n_live - 1``, by layer."""
    variant = tuple(sorted(variant))
    eps = config["layer_norm_epsilon"]
    with jax.default_matmul_precision("highest"):
        outer = outer_weights(params, device)
        tokens = jax.device_put(jnp.asarray(tokens, jnp.int32), device)
        S = tokens.shape[0]
        n_live = S if n_live is None else n_live
        x = outer["embed"][tokens]
        used = []
        if keep is not None:
            keep.update(x_in={}, kv={}, ties=[], variant=variant, start=None)
        for l, kind, j in _layers(config):
            w = layer_weights(params, config, l, device)
            routed = keep is not None and keep["start"] is not None
            if kind == "ssm":
                if routed:                 # attends after the first router
                    keep["x_in"][l] = x
                xs, Bm, Cm, dt, z = ssm_front(x, w, variant=variant,
                                              **_ssm_kw(config))
                S_l, y = recurrence(_state0(xs, Bm), xs, dt, Bm, Cm, w,
                                    n_live,
                                    bf16_state="bf16_state" in variant)
                if states is not None:
                    states[l] = S_l
                if "no_skip" in variant:   # the control: D x left out
                    y = y - w["d_skip"][:, None] * xs
                x = ssm_close(x, y, z, w, groups=config["n_groups"], eps=eps,
                              variant=variant)
            elif kind == "gqa":
                x, k, v = gqa_block(x, w, variant=variant,
                                    theta=float(config["rope_theta"]),
                                    **_gqa_kw(config))
                if routed:
                    keep["kv"][l] = (k, v)
            else:
                if keep is not None and keep["start"] is None:
                    keep["start"] = (l, x)
                h = rms_norm(x, w["norm"], eps)
                chosen = None if routing is None else jnp.asarray(routing[j])
                chosen, local, fullest, tie = route(
                    h, w, chosen, n_live, variant=variant,
                    **_route_kw(config))
                x = expert_close(x, h, w, local, cap=_capacity(fullest, S),
                                 variant=variant)
                used.append(chosen)
                if keep is not None:
                    keep["ties"].append(tuple(np.asarray(t) for t in tie))
        if return_routing:
            return x, outer, jnp.stack(used)
        return x, outer


# ---------------------------------------------------------------------------
# one row again, with an exchange at the edge of its top-6
# ---------------------------------------------------------------------------
def _pow2_blocks(n: int) -> int:
    """``n`` rounded up to a power of two of whole blocks of REPLAY_ROWS,
    so that few shapes compile."""
    return REPLAY_ROWS * (1 << int(np.ceil(np.log2(-(-n // REPLAY_ROWS)))))


def keep_states(params, config, rows, keep, device):
    """The second pass over the Mamba layers after the first router: the
    state before each of ``rows`` (the positions :func:`replay` will be
    asked for), from the layer inputs the main pass kept."""
    rows = sorted(int(r) for r in rows)
    keep["slot"] = {r: i for i, r in enumerate(rows)}
    keep["states"] = {}
    with jax.default_matmul_precision("highest"):
        for l, x_in in keep["x_in"].items():
            at = jnp.asarray(rows + [x_in.shape[0]]
                             * (_pow2_blocks(len(rows)) - len(rows)),
                             jnp.int32)
            w = layer_weights(params, config, l, device)
            xs, Bm, Cm, dt, _ = ssm_front(x_in, w, variant=keep["variant"],
                                          **_ssm_kw(config))
            keep["states"][l] = states_before(_state0(xs, Bm), xs, dt, Bm,
                                              Cm, w, at)


@functools.partial(jax.jit, static_argnames=("heads", "groups", "eps",
                                             "variant"))
def ssm_one(x, pos, w, x_in, states, *, heads, groups, eps, variant=()):
    """:func:`ssm_front`, one step and :func:`ssm_close` for single
    positions: ``x`` [n, D] the streams of positions ``pos`` [n] on top of
    the sequence's own earlier rows (``x_in``: the layer's inputs of the main
    pass, for the convolution's three rows before) and the state the main
    pass had BEFORE each (``states`` [n, H, P, N])."""
    taps = w["conv"].shape[1]
    proj = lambda t: rms_norm(t, w["norm"], eps) @ w["w_in"]
    back = pos[:, None] - jnp.arange(taps - 1, 0, -1)[None, :]    # [n, 3]
    n = x.shape[0]
    ub = jnp.where((back >= 0)[..., None], xbc_rows(
        proj(x_in[jnp.maximum(back, 0)].reshape(n * (taps - 1), -1)),
        w).reshape(n, taps - 1, -1), 0.0)
    zxd = proj(x)
    xs, Bm, Cm, dt, z = ssm_inputs(
        zxd, [ub[:, i] for i in range(taps - 1)] + [xbc_rows(zxd, w)], w,
        heads=heads, groups=groups, variant=variant)
    y, _ = ssm_step(states, xs, dt, Bm, Cm, w)
    return ssm_close(x, y, z, w, groups=groups, eps=eps, variant=variant)


@functools.partial(jax.jit, static_argnames=("n_head", "n_kv", "eps"))
def gqa_one(x, pos, w, k_all, v_all, *, n_head, n_kv, eps):
    """:func:`gqa_block` for single positions: the sequence's own keys and
    values of the EARLIER positions, and their own of this evaluation."""
    n = x.shape[0]
    S = k_all.shape[1]
    rep = n_head // n_kv
    q, k, v = gqa_qkv(rms_norm(x, w["norm"], eps), w, n_head=n_head,
                      n_kv=n_kv)
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s_all = jnp.einsum("nhd,hkd->nhk", q, jnp.repeat(k_all, rep, axis=0))
    ok = jnp.arange(S)[None, :] < pos[:, None]
    s = jnp.concatenate([jnp.where(ok[:, None], s_all, -jnp.inf),
                         (q * k).sum(-1)[..., None]], -1) \
        / jnp.sqrt(F32(q.shape[-1]))
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("nhk,hkd->nhd", p[..., :S],
                   jnp.repeat(v_all, rep, axis=0)) + p[..., S:] * v
    return x + a.reshape(n, -1) @ w["wo"]


def replay(params, config, pos, swaps, keep, device):
    """Final hidden states [n, D] of positions ``pos`` [n] with the exchange
    ``swaps`` [n, expert layers] names (an entry of SWAPS, 1-based; 0: none)
    made at each router, every other position as the main pass left it; and
    each router's near-ties ON THAT STREAM."""
    eps = config["layer_norm_epsilon"]
    n = len(pos)
    pad = _pow2_blocks(n) - n
    slot = jnp.asarray(np.pad([keep["slot"][int(r)] for r in pos], (0, pad),
                              mode="edge"), jnp.int32)
    pos = jnp.asarray(np.pad(pos, (0, pad), mode="edge"), jnp.int32)
    swaps = jnp.asarray(np.pad(swaps, ((0, pad), (0, 0))))
    blocks = range(0, n + pad, REPLAY_ROWS)
    cut = lambda t, a: t[a:a + REPLAY_ROWS]
    ssm_variant = tuple(v for v in keep["variant"] if v not in ROUTE_VARIANTS)
    first, x0 = keep["start"]
    with jax.default_matmul_precision("highest"):
        # up to the first router a row is what the main pass made of it
        x = x0[pos]
        ties = []
        for l, kind, j in _layers(config):
            if l < first:
                continue
            w = layer_weights(params, config, l, device)
            if kind == "ssm":
                x = jnp.concatenate([
                    ssm_one(cut(x, a), cut(pos, a), w, keep["x_in"][l],
                            keep["states"][l][cut(slot, a)],
                            variant=ssm_variant, **_ssm_kw(config))
                    for a in blocks])
            elif kind == "gqa":
                x = jnp.concatenate([
                    gqa_one(cut(x, a), cut(pos, a), w, *keep["kv"][l],
                            **_gqa_kw(config)) for a in blocks])
            else:
                h = rms_norm(x, w["norm"], eps)
                _, local, _, tie = route(
                    h, w, None, n + pad, swap=swaps[:, j],
                    variant=keep["variant"], **_route_kw(config))
                x = expert_close(x, h, w, local, cap=n + pad,
                                 variant=ssm_variant)
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
                                      config["layer_norm_epsilon"])
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
                variant=(), states=None):
    """Reference logits [len(rows), V] at positions ``rows`` of ``tokens``
    (V the chip's share of the vocabulary).  Without ``routing`` a row at a
    near-tie of the router is the admissible evaluation its next token fits
    best (:func:`admissible_rows`), also under a ``variant`` that breaks the
    router's weights alone (``ROUTE_VARIANTS``); with ``routing``, or under
    any other control, the one evaluation stands.  ``states`` (a dict) is
    filled with each Mamba layer's state after the last of ``rows``."""
    tokens = np.asarray(tokens)
    rows = np.asarray(rows)
    S = len(tokens)
    if S > QUERY_BLOCK and S % QUERY_BLOCK:      # whole query blocks
        tokens = np.pad(tokens, (0, -S % QUERY_BLOCK))
    if routing is not None:       # [S or fewer, k] a layer: rows to the end
        routing = [np.pad(np.asarray(r), ((0, len(tokens) - len(r)), (0, 0)))
                   for r in routing]
    keep = {} if routing is None and ROUTE_VARIANTS.issuperset(variant) \
        else None
    x, outer = hidden_states(params, config, tokens, device, routing,
                             variant=variant, n_live=int(rows.max()) + 1,
                             keep=keep, states=states)
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x[jnp.asarray(rows)], outer["norm"],
                     config["layer_norm_epsilon"])
        logits = h @ outer["lm_head"]
    if keep is None:
        return logits
    return admissible_rows(params, config, tokens[:S], rows, device, logits,
                           keep, outer)
