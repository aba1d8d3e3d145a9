"""Plain reference forward of A.X-K1 (skt, ``model_type: axk1``;
config.json), ONE CHIP'S SHARE of it as the configuration file states:
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no batching, independent of ``deepspeed_tpu.models``,
``deepspeed_tpu.moe`` and ``deepspeed_tpu.ops``.  Written from the equations
of ISSUE 48, not from the package's code.  ``N(.)`` is RMSNorm with its own
gain, eps ``rms_norm_eps``; layer l's MLP is dense where l <
``first_k_dense_replace``; every layer attends by latent attention (MLA):

    x = embed[tokens]                                no multiplier
    per layer:  x = x + attn(N_in(x));  x = x + mlp(N_post(x))
    logits = N_f(x) W_head                           the chip's vocabulary rows

    attn, h = N_in(x)_t (H = 64 heads of n + r = 128 + 64, values 128):
        c_q = N_q(h W_qa)                            [q_lora_rank]
        q_h = c_q W_qb -> [q_n,h | q_r,h]
        [c_raw | k_raw] = h W_kva;  c = N_kv(c_raw)  [kv_lora_rank | r]
        q_r,h <- R_t q_r,h;  k_r = R_t k_raw         pairs (2i, 2i + 1)
        [k_n,h | v_h] = c W_kvb
        score_h(t, j) = (q_n,h(t) . k_n,h(j) + q_r,h(t) . k_r(j))
                        * m^2 / sqrt(n + r),   j <= t
        a = concat_h(softmax_j(score_h) v_h) W_o
      DECOMPRESSED (per-head keys and values), never the absorbed form.

    R_t, YaRN (``rope_scaling``; dim r, base ``rope_theta``, s = factor, L0
    = original_max_position_embeddings):
        f_i = base^(-2i / r)
        lo = floor(r ln(L0 / (beta_fast 2 pi)) / (2 ln base))
        hi = ceil(r ln(L0 / (beta_slow 2 pi)) / (2 ln base))
        ramp_i = clip((i - lo) / (hi - lo), 0, 1)
        inv_freq_i = f_i (1 - ramp_i) + (f_i / s) ramp_i;  angle = t inv_freq_i
        y(a) = 0.1 a ln s + 1;  cos and sin times y(mscale) / y(mscale_all_dim)
        (= 1 in the published config);  m = y(mscale_all_dim)

    mlp: dense SwiGLU, or shared(h) + the routed part:
        s = sigmoid(h W_g)                           over the router's 192
        group score = the sum of the two largest s in each of ``n_group``
            groups of consecutive experts; the ``topk_group`` best are kept
        idx = top-8 of s inside the kept groups      NO selection bias
        w = s[idx] / (sum s[idx] + 1e-20) * routed_scaling_factor
        routed = sum over idx HELD HERE of w_e expert_e(h)
      (``reference/trinity.py``'s ``expert_close`` under "no_post_norm": that
      file is benchmark code and not the package's.)

What the catalog's ``config`` does not carry (``topk_method: "none"`` read
as no correction bias WITH the group limit, the group score a top-2 sum,
``N_q`` and ``N_kv``, the pair layout, m^2 on the softmax scale) is listed in
the configuration file under ``assumed``.  Departures from the published
description: float32 throughout; seeded weights.

``routing=`` replaces the reference's own top-8 by the program's;
``variant=`` breaks one equation on purpose, for ``tools/axk1_agreement.py``'s
negative controls; nothing else uses them.

Near-ties of the router are admitted by ``reference/trinity.py``'s rule
(``SWAPS``, ``NEAR_TIE`` imported: the last two chosen against the first two
not chosen, singly or both, within 0.005, where a held expert is among
them), extended to the discrete choice this router adds: where the
``topk_group``-th and the next group's scores lie within ``NEAR_TIE`` and a
group with a held expert is one of the two OR one of the groups kept beside
them, the other outcome is admissible as well (exchange ``GROUP_FLIP``: the
two groups change places and the top-8 is taken anew).  Every held expert
of rank 0 lives in group 0: where group 0 is one of the two, the flip moves
every held choice of the row at once; where it is kept beside them, the
flip changes whom its experts compete with for the eight places (my chip
run, PR 48, seed 2148000103, position 5,201: groups 1 and 6 0.00016 apart,
the program kept 6, the reference 1, and held expert 4 fell out of the
program's top-8 behind three of group 6's: 5.8 bf16 steps under the rule
that asked for a held group among the two, 0.0 under the program's routing).
An exchange of groups and an exchange of experts are not both made in ONE
layer.  A row is re-evaluated by :func:`replay` against the sequence's own
latent rows, decompressed anew.

Memory: layer by layer on weights cast up to float32 one layer at a time;
attention in groups of ``HEAD_GROUP`` heads and query blocks of
``QUERY_BLOCK`` rows (64 heads x 16,384 keys of scores would be 4 GB a
block of 256), the dense MLP in row blocks, so that 16,384 positions fit
beside 7 GB of bf16 weights; of the program it knows only the NAMES in its
weight tree.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.trinity import (NEAR_TIE, REPLAY_ROWS, SWAPS,
                                          _below_best, _capacity, _up,
                                          expert_close, outer_weights,
                                          rms_norm, swiglu)

F32 = jnp.float32
QUERY_BLOCK = 256
HEAD_GROUP = 16
MLP_ROWS = 2048
NO_POST_NORM = ("no_post_norm",)      # trinity's close, its post-norm out
GROUP_FLIP = len(SWAPS) + 1           # the exchange of two groups (1-based)
# the controls that break the router alone: the near-tie search runs under
# them too (a wrong router must fail WITH its near-ties admitted)
ROUTE_VARIANTS = frozenset({"no_group_limit", "no_route_scale"})


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def layer_weights(params, config, l, device):
    n_dense = config["first_k_dense_replace"]
    dense = l < n_dense
    ly = params["dense_layers" if dense else "layers"]
    i = l if dense else l - n_dense
    g = lambda *path: _up(functools.reduce(lambda t, k: t[k], path, ly)[i],
                          device)
    w = {"n_in": g("attn_norm", "scale"), "n_post": g("mlp_norm", "scale")}
    w.update({k: _up(v[l], device) for k, v in params["mla"].items()})
    if dense:
        w.update({k: g("mlp", k) for k in ("w_gate", "w_up", "w_down")})
    else:
        # the held experts' matrices stay as stored (bf16) and are cast up
        # one expert at a time inside expert_close
        raw = lambda k: jax.device_put(ly["mlp"][k], device)[i]
        w.update(router=g("mlp", "gate_w"),
                 e_gate=raw("w_gate"), e_up=raw("w_up"), e_down=raw("w_down"),
                 s_gate=g("mlp", "shared", "w_gate"),
                 s_up=g("mlp", "shared", "w_up"),
                 s_down=g("mlp", "shared", "w_down"))
    return w


# ---------------------------------------------------------------------------
# the rotation
# ---------------------------------------------------------------------------
def yarn_frequencies(config, variant=()):
    """(inv_freq [r / 2] as a tuple, the factor on cos and sin, m) of the
    equations above, in float64 on the host (hashable: a static argument of
    the jitted blocks)."""
    rs = config["rope_scaling"]
    r, base = config["qk_rope_head_dim"], float(config["rope_theta"])
    s, L0 = float(rs["factor"]), float(rs["original_max_position_embeddings"])
    i = np.arange(r // 2)
    f = base ** (-2.0 * i / r)
    edge = lambda beta: r * math.log(L0 / (beta * 2 * math.pi)) \
        / (2 * math.log(base))
    lo = max(math.floor(edge(rs["beta_fast"])), 0)
    hi = min(math.ceil(edge(rs["beta_slow"])), r - 1)
    ramp = np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    inv_freq = f * (1 - ramp) + f / s * ramp
    if "plain_freq" in variant:       # no YaRN blend: the base's frequencies
        inv_freq = f
    y = lambda a: 0.1 * a * math.log(s) + 1.0 if s > 1 else 1.0
    m = y(rs["mscale_all_dim"]) if rs["mscale_all_dim"] else 1.0
    if "no_mscale" in variant:        # m^2 = 1 on the softmax scale
        m = 1.0
    return (tuple(float(f) for f in inv_freq),
            y(rs["mscale"]) / y(rs["mscale_all_dim"] or 0.0), m)


def rotate(t, pos, inv_freq, on_cos_sin, bf16_angles=False):
    """``t`` [n, ..., r] at positions ``pos`` [n]: each pair (t[2i], t[2i +
    1]) turned by ``pos * inv_freq_i``."""
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv_freq, F32)[None, :]
    if bf16_angles:         # the precision control: angles rounded to bf16
        ang = jax.lax.reduce_precision(ang, exponent_bits=8, mantissa_bits=7)
    ang = ang.reshape((t.shape[0],) + (1,) * (t.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang) * on_cos_sin, jnp.sin(ang) * on_cos_sin
    pairs = t.reshape(t.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(t.shape)


# ---------------------------------------------------------------------------
# latent attention, decompressed
# ---------------------------------------------------------------------------
def latent_rows(h, pos, w, *, kv_rank, eps, rot, variant=()):
    """(c [n, kv_rank] normed, k_r [n, r] rotated, c_q [n, q_rank] normed)
    of rows ``h`` [n, D] at positions ``pos``: what every head shares."""
    inv_freq, on_cos_sin, _ = rot
    cr = h @ w["wkva"]
    c = rms_norm(cr[:, :kv_rank], w["kv_norm"], eps)
    k_r = cr[:, kv_rank:]
    if not {"no_rope", "unrotated_cache_key"} & set(variant):
        k_r = rotate(k_r, pos, inv_freq, on_cos_sin,
                     "bf16_angles" in variant)
    cq = h @ w["wqa"]
    if "no_q_norm" not in variant:
        cq = rms_norm(cq, w["q_norm"], eps)
    return c, k_r, cq


def heads_of(cq, c, k_r, pos, wqb, wkvb, *, nope, rot, variant=()):
    """Per-head q [n, G, nope + r], k [n, G, nope + r], v [n, G, v] of a
    group of G heads (``wqb`` [q_rank, G (nope + r)], ``wkvb`` [kv_rank, G
    (nope + v)])."""
    inv_freq, on_cos_sin, _ = rot
    n = cq.shape[0]
    q = (cq @ wqb).reshape(n, -1, nope + k_r.shape[1])
    if "no_rope" not in variant:
        q = jnp.concatenate(
            [q[..., :nope], rotate(q[..., nope:], pos, inv_freq, on_cos_sin,
                                   "bf16_angles" in variant)], -1)
    G = q.shape[1]
    kvb = (c @ wkvb).reshape(n, G, -1)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_r[:, None], (n, G, k_r.shape[1]))],
        -1)
    return q, k, kvb[..., nope:]


def _grouped(w, heads, per_head):
    """[in, H per_head] -> [H / HEAD_GROUP, in, HEAD_GROUP per_head]."""
    g = min(HEAD_GROUP, heads)
    return w.reshape(w.shape[0], heads // g, g * per_head).transpose(1, 0, 2)


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "v_dim", "kv_rank", "eps", "rot", "variant"))
def mla_block(x, w, *, heads, nope, v_dim, kv_rank, eps, rot, variant=()):
    """x [S, D] -> (x + attention, N_post of that, the rows' shared parts (c,
    k_r) for :func:`replay`)."""
    S = x.shape[0]
    pos = jnp.arange(S)
    h = rms_norm(x, w["n_in"], eps)
    c, k_r, cq = latent_rows(h, pos, w, kv_rank=kv_rank, eps=eps, rot=rot,
                             variant=variant)
    r = k_r.shape[1]
    scale = rot[2] ** 2 / math.sqrt(nope + r)
    block = min(S, QUERY_BLOCK)
    j = jnp.arange(S)[None, :]

    def group(ws):
        q, k, v = (t.transpose(1, 0, 2) for t in heads_of(
            cq, c, k_r, pos, *ws, nope=nope, rot=rot, variant=variant))

        def one(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
            ok = j <= (start + jnp.arange(block))[:, None]
            s = jnp.einsum("hqd,hkd->hqk", qb, k) * scale
            p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,hkd->hqd", p, v)

        a = jax.lax.map(one, jnp.arange(0, S, block))  # [nb, G, block, v]
        return a.transpose(0, 2, 1, 3).reshape(S, -1)  # [S, G v]

    a = jax.lax.map(group, (_grouped(w["wqb"], heads, nope + r),
                            _grouped(w["wkvb"], heads, nope + v_dim)))
    x = x + a.transpose(1, 0, 2).reshape(S, -1) @ w["wo"]
    return x, rms_norm(x, w["n_post"], eps), (c, k_r)


@jax.jit
def dense_close(x, h, w):
    """x + SwiGLU(h), in blocks of ``MLP_ROWS`` rows (18,432 columns of
    float32 for 16,384 rows at once would be 1.2 GB an intermediate)."""
    S, D = h.shape
    rows = min(MLP_ROWS, S)
    m = jax.lax.map(lambda hb: swiglu(hb, w["w_gate"], w["w_up"],
                                      w["w_down"]),
                    jnp.pad(h, ((0, -S % rows), (0, 0))).reshape(-1, rows, D))
    return x + m.reshape(-1, D)[:S]


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=(
    "top_k", "first", "n_group", "topk_group", "route_scale", "route_norm",
    "variant"))
def route(h, w, chosen, n_live, *, top_k, first, n_group, topk_group,
          route_scale, route_norm, variant=(), swap=None):
    """``reference/trinity.py:route`` with this model's selection: (router
    indices chosen [S, k]; the token's weight for each HELD expert [S, E];
    the fullest held expert's rows; the near-ties: for each exchange of
    SWAPS and then for GROUP_FLIP, how far apart the scores it exchanges lie
    [S, len(SWAPS) + 1] and whether a held expert is among them (for the
    groups: a group with one among the kept groups or the next).  ``swap`` [S] int makes that exchange (1-based; 0: none)."""
    S = h.shape[0]
    E = w["e_up"].shape[0]
    s = jax.nn.sigmoid(h @ w["router"])                 # [S, R] float32
    R = s.shape[1]
    size = R // n_group
    here = lambda e: (e >= first) & (e < first + E)
    sel = s
    group_tie = (jnp.full((S,), jnp.inf, F32), jnp.zeros((S,), bool))
    if n_group > 1 and "no_group_limit" not in variant:
        gscore = jax.lax.top_k(s.reshape(S, n_group, size), 2)[0].sum(-1)
        gval, gidx = jax.lax.top_k(gscore, min(topk_group + 1, n_group))
        last, nxt = gidx[:, topk_group - 1], gidx[:, -1]
        kept = gidx[:, :topk_group]
        if swap is not None:
            kept = kept.at[:, topk_group - 1].set(
                jnp.where(swap == GROUP_FLIP, nxt, last))
        mask = jnp.zeros((S, n_group), bool).at[
            jnp.arange(S)[:, None], kept].set(True)
        sel = jnp.where(jnp.repeat(mask, size, axis=1), s, 0.0)
        held_group = lambda g: (g * size < first + E) & ((g + 1) * size > first)
        if topk_group < n_group:
            # a held group among the kept ones or the next: the flip can
            # move a held choice
            group_tie = (gval[:, topk_group - 1] - gval[:, topk_group],
                         functools.reduce(jnp.logical_or, [
                             held_group(gidx[:, j])
                             for j in range(topk_group + 1)]))
    val, idx = jax.lax.top_k(sel, top_k + 2)
    ranks = [(tuple(top_k - 1 - o for o in outs), tuple(top_k + i for i in ins))
             for outs, ins in SWAPS]
    tie = (jnp.stack([val[:, min(o)] - val[:, max(i)] for o, i in ranks]
                     + [group_tie[0]], 1),
           jnp.stack([functools.reduce(jnp.logical_or,
                                       [here(idx[:, r]) for r in o + i])
                      for o, i in ranks] + [group_tie[1]], 1))
    if chosen is None:
        chosen = idx[:, :top_k]
        if swap is not None:
            for n, (outs, ins) in enumerate(ranks, start=1):
                for o, i in zip(outs, ins):
                    chosen = chosen.at[:, o].set(
                        jnp.where(swap == n, idx[:, i], chosen[:, o]))
    weight = jnp.take_along_axis(s, chosen, axis=-1)
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


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------
def _mla_kw(config, variant=()):
    return dict(heads=config["num_attention_heads"],
                nope=config["qk_nope_head_dim"], v_dim=config["v_head_dim"],
                kv_rank=config["kv_lora_rank"], eps=config["rms_norm_eps"],
                rot=yarn_frequencies(config, variant))


def _route_kw(config):
    return dict(top_k=config["num_experts_per_tok"],
                first=config["expert_parallel"]["first_expert"],
                n_group=config["n_group"], topk_group=config["topk_group"],
                route_scale=float(config["routed_scaling_factor"]),
                route_norm=bool(config["norm_topk_prob"]))


def hidden_states(params, config, tokens, device, routing=None,
                  return_routing=False, variant=(), n_live=None, keep=None):
    """Final hidden states [S, D] and the outer weights; with
    ``return_routing`` also the router indices used, [expert layers, S, k].
    ``keep`` (a dict) is filled with what :func:`replay` needs."""
    variant = tuple(sorted(variant))
    eps = config["rms_norm_eps"]
    n_dense = config["first_k_dense_replace"]
    with jax.default_matmul_precision("highest"):
        outer = outer_weights(params, device)
        tokens = jax.device_put(jnp.asarray(tokens, jnp.int32), device)
        S = tokens.shape[0]
        n_live = S if n_live is None else n_live
        x = outer["embed"][tokens]
        used = []
        if keep is not None:
            keep.update(rows={}, ties=[], variant=variant)
        for l in range(config["num_hidden_layers"]):
            w = layer_weights(params, config, l, device)
            x, h, rows = mla_block(x, w, variant=variant,
                                   **_mla_kw(config, variant))
            if keep is not None and l > n_dense:
                keep["rows"][l] = rows
            if l < n_dense:
                x = dense_close(x, h, w)
                continue
            if keep is not None and l == n_dense:
                keep["start"] = (x, h)
            chosen = None if routing is None else jnp.asarray(
                routing[l - n_dense])
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
# one row again, with an exchange at the edge of its selection
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "v_dim", "kv_rank", "eps", "rot"))
def mla_one(x, pos, w, c_all, kr_all, *, heads, nope, v_dim, kv_rank, eps,
            rot):
    """:func:`mla_block` for single positions: ``x`` [n, D] the streams of
    positions ``pos`` [n] over the sequence's own rows (``c_all``,
    ``kr_all``: the main pass's, decompressed here) of the EARLIER
    positions, and their own of this evaluation."""
    S = c_all.shape[0]
    h = rms_norm(x, w["n_in"], eps)
    c, k_r, cq = latent_rows(h, pos, w, kv_rank=kv_rank, eps=eps, rot=rot)
    r = k_r.shape[1]
    scale = rot[2] ** 2 / math.sqrt(nope + r)
    ok = jnp.arange(S)[None, :] < pos[:, None]

    def group(ws):
        wqb, wkvb = ws
        q, k, v = heads_of(cq, c, k_r, pos, wqb, wkvb, nope=nope, rot=rot)
        kvb = (c_all @ wkvb).reshape(S, q.shape[1], -1)
        s_all = (jnp.einsum("ngd,sgd->ngs", q[..., :nope], kvb[..., :nope])
                 + jnp.einsum("ngd,sd->ngs", q[..., nope:], kr_all))
        s = jnp.concatenate([jnp.where(ok[:, None], s_all, -jnp.inf),
                             (q * k).sum(-1)[..., None]], -1) * scale
        p = jax.nn.softmax(s, axis=-1)
        a = jnp.einsum("ngs,sgd->ngd", p[..., :S], kvb[..., nope:]) \
            + p[..., S:] * v
        return a.reshape(a.shape[0], -1)

    a = jax.lax.map(group, (_grouped(w["wqb"], heads, nope + r),
                            _grouped(w["wkvb"], heads, nope + v_dim)))
    x = x + a.transpose(1, 0, 2).reshape(x.shape[0], -1) @ w["wo"]
    return x, rms_norm(x, w["n_post"], eps)


def replay(params, config, pos, swaps, keep, device):
    """Final hidden states [n, D] of positions ``pos`` [n] with the exchange
    ``swaps`` [n, expert layers] names (an entry of SWAPS or GROUP_FLIP,
    1-based; 0: none) made at each expert layer, every other position as
    the main pass left it; and each expert layer's near-ties ON THAT
    STREAM."""
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
        for l in range(n_dense, config["num_hidden_layers"]):
            w = layer_weights(params, config, l, device)
            if l > n_dense:
                x, h = (jnp.concatenate(parts) for parts in zip(*(
                    mla_one(cut(x, a), cut(pos, a), w, *keep["rows"][l],
                            **_mla_kw(config)) for a in blocks)))
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
    highest (``reference/trinity.py``: the rule and its search, over this
    file's exchanges)."""
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
                variant=()):
    """Reference logits [len(rows), V] at positions ``rows`` of ``tokens``
    (V the chip's share of the vocabulary).  Without ``routing`` a row at a
    near-tie of the router is the admissible evaluation its next token fits
    best (:func:`admissible_rows`), also under a ``variant`` that breaks the
    router alone (:data:`ROUTE_VARIANTS`); with ``routing``, or under any
    other control, the one evaluation stands."""
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
                             keep=keep)
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x[jnp.asarray(rows)], outer["norm"],
                     config["rms_norm_eps"])
        logits = h @ outer["lm_head"]
    if keep is None:
        return logits
    return admissible_rows(params, config, tokens[:S], rows, device, logits,
                           keep, outer)
