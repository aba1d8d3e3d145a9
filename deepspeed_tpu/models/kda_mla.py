"""Linear-attention and latent-attention layers (moonshotai Kimi-Linear:
both kinds, the latent one without a position encoding; skt A.X-K1: latent
layers only, rotated, with a low-rank query; dots-studio dots3-note-prev:
latent layers of TWO kinds, one under a learned selection of its keys, one
under a window; upstage Solar-Open2: linear layers whose ``beta`` runs to 2
beside PER-HEAD ``full_attention`` layers, gated, without a position
encoding, over K/V pages; ``ModelConfig.layer_types``): the layer form of
``models/afmoe.py`` (a static pattern, leading dense layers, the sigmoid
router over ONE CHIP'S SHARE of the experts) over further attention kinds,
plain pre-norm.

With ``N(.)`` RMSNorm (own gain, ``norm_eps``), ``x`` the stream, ``h =
N_in(x)``:

    layer:  x = x + attn(N_in(x));  x = x + mlp(N_post(x))   (afmoe.mlp_block)

    "linear_attention" (KDA: H = kda_num_heads heads of d = kda_head_dim):
        u = h [Wq | Wk | Wv]                                   [3 H d]
        c[t] = silu(sum_i conv[:, i] * u[t - K + 1 + i])       depthwise causal
            convolution of K = kda_conv_kernel taps, zeros before t = 0
        q = l2norm(c_q) * d^-0.5;  k = l2norm(c_k);  v = c_v   per head
        g = -exp(a_log[head]) * softplus((h Wf_down) Wf_up + dt_bias)   <= 0
        beta = sigmoid(h Wb)                                   [H]
            times 2 with ``kda_neg_eigval``: beta in (0, 2), so that ``I -
            beta k k^T`` (k of unit length) has its moving eigenvalue in
            (-1, 1) and a step may reflect the state along k, not only
            shrink it
        per head, S [d, d] float32, S_0 = 0:
            S' = diag(exp(g_t)) S_{t-1}
            S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
            o_t = S_t^T q_t
        a = (N_o(o_t) * sigmoid((h Wg_down) Wg_up + b_g)) Wo   N_o over d

    "latent_attention" (MLA: H = num_heads query heads of n + r = mla_nope_dim
    + mla_rot_dim, values of mla_v_dim, latent mla_kv_rank):
        q = h Wq   or, with mla_q_rank,   q = N_q(h Wqa) Wqb
        [c_raw | k_r] = h Wkva;  c = N_kv(c_raw)
        mla_rope None: the r values of q_h and k_r are used UNROTATED (no
            position encoding), m = 1
        mla_rope set:  q_r,h <- R_t q_r,h;  k_r <- R_t k_r   at the token's
            position t, pairs (2i, 2i + 1), YaRN frequencies; m = YaRN's
            factor on the softmax scale (:func:`yarn`)
        [k_n,h | v_h] = c Wkvb        per head
        score_h(t, j) = (q_n,h(t) . k_n,h(j) + q_r,h(t) . k_r(j))
                        * m^2 / sqrt(n + r)
        a = concat_h(softmax(score_h) v_h) Wo
        cache row of token j: [c(j) | k_r(j)] (k_r as the scores use it:
            rotated where the model rotates), shared by all heads

    "full_attention" beside linear layers (per-head softmax attention, H =
    num_heads query heads over num_kv_heads key-value heads of head_dim, NO
    position encoding, no head norms; ``models/afmoe.py``'s global layer
    without its sandwich):
        q, k, v, g = h Wq, h Wk, h Wv, h Wg
        a = (softmax(q k^T / sqrt(head_dim)) v * sigmoid(g)) Wo   every j <= t;
            the gate with ``attn_output_gate``, elementwise (afmoe.gated)
        cache rows of token j: k(j), v(j) per key-value head, in pages

    A latent layer's sizes, head count and base are its KIND's
    (``ModelConfig.mla_kind(layer type)``, an ``MlaKind``), and a kind may add:
        rescale:  c_q and c times sqrt(hidden / rank) after their norms
        a gate:   a = concat_h(sigmoid(h Wg)_h o_h) Wo,  Wg [D, H]
        an indexer ("latent_attention" with ``mla_index_*``; G index heads
        of d, their first ``rot`` values rotated as k_r is):
            qI_g = c_q W_Iq;  kI = LN(h W_Ik) (gain and bias);
            w = h W_Iw G^-0.5 d^-0.5
            I(t, j) = sum_g w_g(t) relu(qI_g(t) . kI(j)),  j <= t
            the softmax runs over the ``topk`` keys of largest I(t, j) only
            (all of them while t + 1 <= topk); kI(j) is cached beside the row
        a window ("latent_sliding_attention"): keys 0 <= t - j < window

Each piece is ONE function here (:func:`short_conv`, :func:`kda_activate`,
:func:`kda_step` / :func:`kda_chunk`, :func:`kda_out`; :func:`gqa_split`
(``afmoe.attend`` / ``flash_decode`` and ``afmoe.gated`` do the rest);
:func:`mla_project`,
:func:`mla_query`, :func:`mla_row`, :func:`rotate`, :func:`mla_decompress`,
:func:`mla_absorb` / :func:`mla_unabsorb`,
:func:`head_gate`; :func:`index_key`, :func:`index_query`,
:func:`select_keys` / :func:`select_positions`; :func:`ring_before`,
:func:`ring_after`, :func:`window_attention`, :func:`ring_decode`) and the
three forwards call them, as ``afmoe.py``'s do; the expert block, the pattern
loop's parameter stacks and the fused path's layer end are ``afmoe``'s own
functions.  The attention parameters are stacks by KIND (``params["kda"]``
``[linear layers, ...]``, ``params["mla"]`` ``[latent layers, ...]``,
``params["gqa"]`` ``[per-head full layers, ...]``): the kinds interleave
inside ``afmoe``'s two MLP stacks.  A model without linear layers has no
``kda`` stack, and its cache no ``state`` and no ``tail``; a model without
latent layers has no ``latent`` pages, and one with per-head full layers
keeps ``k`` and ``v`` pages ``[full layers, pages, Hkv, page, Dh]`` for THOSE
layers only (``serving/cache_kind.py:FullPagesAndState``).

Cache (``serving/paged_kv.py``): a linear layer keeps, for each SLOT, its
state ``[H, d, d]`` float32 and the convolution's tail, the last ``K - 1``
rows of ``u``: fixed, never paged, zeroed when a request starts (a chunk at
position 0 reads zeros whatever the slot held).  A latent layer keeps one
row a position in LATENT PAGES ``[pages, 1, page, row_width]``: no head axis
to speak of, no V array, keys and values read from the same row (padded from
``mla_kv_rank + mla_rot_dim`` to whole 128-lane tiles, zeros).  A prefill
chunk carries the state: pad rows of its bucket get ``beta = 0`` and ``g =
0`` and leave state and tail as of the last REAL row.  It runs the
recurrence in its chunkwise (UT transform) form over sub-chunks of ``SUB``
tokens; decays are differences of cumulative log-decays inside a sub-chunk,
masked before the exponential, so that no quotient of decays is formed.  A
chunk's latent layers attend DECOMPRESSED keys and values, a strip of rows
at a time inside ``ops/pallas/flash_attention.py:mla_chunk_attention`` (the
per-head keys, values and scores stay in VMEM; ``afmoe.attend(expand=)``,
its reference, where the kernel does not run: the CPU, widths that are not
lane tiles).  A decode step attends in the absorbed form: ``q'_h = [q_n,h
Wkvb_k,h^T | q_r,h]`` against the rows, the context ``sum_j p_j c(j)``
through ``Wkvb_v,h``.  Under an indexer the latent pages carry ``index``
pages of index keys under the same table: a chunk scores every key up to
each of its queries (``dsa_index_scores_chunk``), finds each query's
``topk``-th score by bisection and attends under the mask
(``dsa_chunk_attention``: every row up to the chunk's last query is
decompressed and scored, because the selection differs by query); a decode
step scores its row's pages (``dsa_index_scores_paged``), takes a top-k and
attends the rows at those POSITIONS, gathered out of the pool
(``dsa_decode_selected``); the sort and the gather, like the kernels, work
the rows that decode and no others, a group of them an iteration of a loop
whose trip count is read at run time.  A sliding latent layer keeps a
per-slot ``ring``
``[sliding layers, slots, ring rows, row width]``: the row of position p at
``p % ring rows``, attended where the position a ring row holds lies in the
window; a chunk attends the ring's rows before it and its own, then writes
its last real rows.  An exact tie at the edge of a top-k is kept by a chunk
and cut by index order in a decode step; neither happens with 64 index heads
of float32 sums.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import afmoe
from deepspeed_tpu.models.afmoe import F32, refuse_parallel, rms
from deepspeed_tpu.ops.pallas.flash_attention import (
    dsa_chunk_attention, dsa_index_scores_chunk, mla_chunk_attention)

SUB = 64                  # tokens a sub-chunk of the chunkwise delta rule
HI = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6


def cache_key(cfg) -> str:
    """The cache entry whose dtype the stream takes."""
    return "k" if full_layers(cfg) else "latent"


def is_linear(cfg, l: int) -> bool:
    return cfg.layer_types[l] == "linear_attention"


def kind_layers(cfg):
    """(indices of the linear layers, indices of the latent layers whose
    rows live in pages: "latent_attention")."""
    L = range(cfg.num_layers)
    return ([l for l in L if is_linear(cfg, l)],
            [l for l in L if cfg.layer_types[l] == "latent_attention"])


def full_layers(cfg):
    """Indices of the per-head "full_attention" layers (K and V rows in
    pages)."""
    return [l for l in range(cfg.num_layers)
            if cfg.layer_types[l] == "full_attention"]


def sliding_layers(cfg):
    """Indices of the "latent_sliding_attention" layers (rows in a ring)."""
    return [l for l in range(cfg.num_layers)
            if cfg.layer_types[l] == "latent_sliding_attention"]


def row_width(cfg) -> int:
    """A latent page's row (``MlaKind.row_width`` of the paged kind)."""
    return cfg.mla_kind("latent_attention").row_width


def ring_rows(cfg, page: int) -> int:
    """Rows of a sliding latent layer's ring: the whole pages that hold a
    window (a row lands at ``position % rows`` and is masked by the position
    it holds: 513 is no multiple of a page)."""
    return -(-cfg.sliding_window // page) * page


def chunk_rows(cfg) -> int:
    """Rows under which a prefill chunk's bucket saves no work beside a
    slot state and per-head pages: one sub-chunk of the recurrence."""
    return SUB


def state_shapes(cfg, num_slots: int):
    """Per-slot state of the linear layers: ``state`` float32 and ``tail``
    (the stream's dtype) shapes."""
    n = len(kind_layers(cfg)[0])
    H, d = cfg.kda_num_heads, cfg.kda_head_dim
    return ((n, num_slots, H, d, d),
            (n, num_slots, cfg.kda_conv_kernel - 1, 3 * H * d))


def slot_state_bytes(cfg, dtype) -> int:
    """Bytes of :func:`state_shapes` for ONE slot, the tail in ``dtype``."""
    state, tail = state_shapes(cfg, 1)
    return math.prod(state) * 4 + math.prod(tail) * jnp.dtype(dtype).itemsize


# ----------------------------------------------------------------------
# parameters
# ----------------------------------------------------------------------
def init_params(cfg, rng, dtype=F32) -> Dict[str, Any]:
    """``afmoe.init_params``' two MLP stacks and the two attention stacks.
    Seeded so that the mechanism is alive: ``a_log`` = log U(1, 16) and
    ``dt_bias`` with ``softplus(dt_bias)`` log-uniform in [1e-3, 1e-1], so a
    step's decay runs from 0.999 (a channel that remembers thousands of
    tokens) to about 0.2 (one that forgets in three); gate bias normal x
    0.1; norm gains 1; the token embedding normal x 1, not ``afmoe``'s x
    0.02: this form has no ``embed_scale``, and under an embedding of 0.02
    the stream IS the first sub-blocks' outputs, whose bf16 rounding nothing
    then dilutes (0.64% of relative error after one layer and 3.1% after
    five at the published widths, a sixth of all top-8 sets flipped against
    the float32 forward: PERF.md section 6, PR 44)."""
    params = afmoe.init_params(cfg, rng, dtype, attn=False)
    D = cfg.hidden_size
    lin, lat = kind_layers(cfg)
    keys = iter(jax.random.split(jax.random.fold_in(rng, 0x4B4441), 24))
    params["embed"] = {"tok": jax.random.normal(
        next(keys), (cfg.vocab_size, D), dtype)}
    uni = lambda shape, fan_in: jax.random.uniform(
        next(keys), shape, dtype, -fan_in ** -0.5, fan_in ** -0.5)
    if lin:
        L, Hk, d = len(lin), cfg.kda_num_heads, cfg.kda_head_dim
        K, r, C = cfg.kda_conv_kernel, cfg.kda_gate_rank, Hk * d
        dt = jnp.exp(jax.random.uniform(next(keys), (L, C), F32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        params["kda"] = {
            "wq": uni((L, D, C), D), "wk": uni((L, D, C), D),
            "wv": uni((L, D, C), D), "conv": uni((L, 3 * C, K), K),
            "wf_down": uni((L, D, r), D), "wf_up": uni((L, r, C), r),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "a_log": jnp.log(jax.random.uniform(
                next(keys), (L, Hk), F32, 1.0, 16.0)).astype(dtype),
            "wb": uni((L, D, Hk), D),
            "wg_down": uni((L, D, r), D), "wg_up": uni((L, r, C), r),
            "b_g": jax.random.normal(next(keys), (L, C), dtype) * 0.1,
            "o_norm": jnp.ones((L, d), dtype), "wo": uni((L, C, D), C)}
    def latent_stack(kd, L):
        H, n, r, kv, v, rq = kd.heads, kd.nope, kd.rot, kd.kv, kd.v, kd.q_rank
        wq = {"wq": uni((L, D, H * (n + r)), D)} if not rq else {
            "wqa": uni((L, D, rq), D), "q_norm": jnp.ones((L, rq), dtype),
            "wqb": uni((L, rq, H * (n + r)), rq)}
        return {**wq, "wkva": uni((L, D, kv + r), D),
                "kv_norm": jnp.ones((L, kv), dtype),
                "wkvb": uni((L, kv, H * (n + v)), kv),
                "wo": uni((L, H * v, D), H * v)}

    def more(kd, L):
        """The gate and the indexer, drawn after every kind's stack so that
        a model without them keeps its weights."""
        a = {"wg": uni((L, D, kd.heads), D)} if kd.gate else {}
        if kd.index:
            Hi, di, _ = kd.index
            a.update(wiq=uni((L, kd.q_rank, Hi * di), kd.q_rank),
                     wik=uni((L, D, di), D), wiw=uni((L, D, Hi), D),
                     ik_norm=jnp.ones((L, di), dtype),
                     ik_bias=jax.random.normal(next(keys), (L, di), dtype)
                     * 0.1)
        return a

    if full_layers(cfg):
        # drawn after the linear stack, so that a model without them keeps
        # its weights
        L = len(full_layers(cfg))
        M, Mkv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
        params["gqa"] = {
            "wq": uni((L, D, M), D), "wk": uni((L, D, Mkv), D),
            "wv": uni((L, D, Mkv), D), "wo": uni((L, M, D), M),
            **({"wg": uni((L, D, M), D)} if cfg.attn_output_gate else {})}
    kinds = [(cfg.mla_kind(t), len(ls)) for t, ls in (
        ("latent_attention", lat),
        ("latent_sliding_attention", sliding_layers(cfg))) if ls]
    for kd, L in kinds:
        params[kd.stack] = latent_stack(kd, L)
    for kd, L in kinds:
        params[kd.stack].update(more(kd, L))
    return params


def layer_params(cfg, params, l: int):
    """``afmoe.layer_params`` with layer ``l``'s slice of its KIND's
    attention stack as ``attn``."""
    lp, le = afmoe.layer_params(cfg, params, l)
    t = cfg.layer_types[l]
    kind = ("kda" if is_linear(cfg, l) else
            "gqa" if t == "full_attention" else cfg.mla_kind(t).stack)
    i = sum(cfg.layer_types[j] == t for j in range(l))
    return {**lp, "attn": jax.tree.map(lambda a: a[i], params[kind])}, le


# ----------------------------------------------------------------------
# the linear-attention (KDA) pieces
# ----------------------------------------------------------------------
def kda_project(a, h):
    """The projections of ``h`` [..., D]: (u [..., 3 H d] = q | k | v before
    the convolution, the two gates' low-rank rows, the beta logits)."""
    w = lambda n: a[n].astype(h.dtype)
    u = jnp.concatenate([h @ w("wq"), h @ w("wk"), h @ w("wv")], axis=-1)
    return u, h @ w("wf_down"), h @ w("wg_down"), h @ w("wb")


def short_conv(u, tail, w, valid_len=None, bias=None):
    """The depthwise causal convolution and its SiLU: ``u`` [B, s, C] after
    ``tail`` [B, K - 1, C] (the K - 1 rows before them; zeros at a
    sequence's start), ``w`` [C, K], ``bias`` [C] | None inside the SiLU.
    Returns (float32 [B, s, C], the tail after the first ``valid_len`` rows
    (None: all ``s``): the last K - 1 REAL rows, so pad rows do not move
    it)."""
    K, s = w.shape[-1], u.shape[1]
    full = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    w32 = w.astype(F32)
    y = sum(full[:, i:i + s].astype(F32) * w32[:, i] for i in range(K))
    if bias is not None:
        y = y + bias.astype(F32)
    new_tail = jax.lax.dynamic_slice_in_dim(
        full, s if valid_len is None else valid_len, K - 1, axis=1)
    return jax.nn.silu(y), new_tail.astype(tail.dtype)


def kda_activate(cfg, a, c, f_low, g_low, b_raw):
    """From the convolved rows ``c`` [..., 3 H d] float32 and the gates'
    low-rank rows to the recurrence's inputs, float32: q, k, v [..., H, d]
    (q and k l2-normed per head, q scaled), the log-decay g [..., H, d] <=
    0, beta [..., H] (a sigmoid, times 2 with ``kda_neg_eigval``), and the
    output gate before its sigmoid [..., H d]."""
    H, d = cfg.kda_num_heads, cfg.kda_head_dim
    lead = c.shape[:-1]
    q, k, v = (t.reshape(lead + (H, d)) for t in jnp.split(c, 3, axis=-1))
    l2 = lambda t: t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + L2_EPS)
    up = lambda low, w, b: jnp.dot(
        low, a[w].astype(low.dtype), preferred_element_type=F32) \
        + a[b].astype(F32)
    f = up(f_low, "wf_up", "dt_bias").reshape(lead + (H, d))
    g = -jnp.exp(a["a_log"].astype(F32))[:, None] * jax.nn.softplus(f)
    beta = lambda: jax.nn.sigmoid(b_raw[..., :H].astype(F32))
    return (l2(q) * d ** -0.5, l2(k), v, g,
            2.0 * beta() if cfg.kda_neg_eigval else beta(),
            up(g_low, "wg_up", "b_g"))


def kda_step(S, q, k, v, g, beta):
    """The delta rule for ONE token: state ``S`` [..., H, d, d] float32 (key
    axis, value axis), q, k, v, g [..., H, d], beta [..., H].  Returns (o
    [..., H, d], S).  Elementwise float32: the same on every backend."""
    S = S * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - (S * k[..., None]).sum(-2))
    S = S + k[..., None] * u[..., None, :]
    return (S * q[..., None]).sum(-2), S


def kda_chunk(S, q, k, v, g, beta):
    """The delta rule for ``s`` tokens of one sequence, chunkwise: ``S``
    [H, d, d]; q, k, v, g [s, H, d]; beta [s, H]; ``s`` a multiple of
    :data:`SUB` or less than it.  Returns (S, o [s, H, d]).

    Inside a sub-chunk, with ``G_t`` the cumulative log-decay and ``S_0``
    the state before it,

        S_t = diag(e^{G_t}) S_0 + sum_{i <= t} diag(e^{G_t - G_i}) k_i u_i^T
        (I + A) u = beta (v - (k e^G) S_0),
            A[t, i] = beta_t sum_c k_t[c] k_i[c] e^{G_t[c] - G_i[c]}, i < t
        o = (q e^G) S_0 + B u,  B[t, i] = sum_c q_t[c] k_i[c] e^{...}, i <= t

    ``T = (I + A)^-1`` (the UT transform) is made by forward substitution
    for all sub-chunks at once, the states then follow one sub-chunk after
    another.  Every exponent is a difference ``G_t - G_i`` with ``i <= t``,
    masked BEFORE the exponential: at most 0, so a channel that decays by
    e^-100 over a sub-chunk neither overflows nor flushes its row."""
    s, H, d = q.shape
    C = min(SUB, s)
    n = s // C
    assert s == n * C, (s, C)
    sub = lambda t: t.reshape((n, C) + t.shape[1:]).swapaxes(1, 2)
    q, k, v, g, beta = (sub(t) for t in (q, k, v, g, beta))   # [n, H, C, .]
    G = jnp.cumsum(g, axis=2)
    t_i = jnp.arange(C)
    low = t_i[:, None] >= t_i[None, :]                        # i <= t

    def pairs(xs):
        q, k, G, beta = xs                                    # one sub-chunk
        E = jnp.exp(jnp.where(low[None, :, :, None],
                              G[:, :, None, :] - G[:, None, :, :], -jnp.inf))
        kE = k[:, None, :, :] * E                             # [H, t, i, d]
        A = (k[:, :, None, :] * kE).sum(-1) * beta[:, :, None]
        return (jnp.where(low & ~jnp.eye(C, dtype=bool), A, 0.0),
                (q[:, :, None, :] * kE).sum(-1))

    A, B = jax.lax.map(pairs, (q, k, G, beta))                # [n, H, C, C]

    def row(t, T):
        # row t of (I + A)^-1: e_t - sum_{i < t} A[t, i] T[i, :]
        new = (t_i == t).astype(F32) - (A[:, :, t, :, None] * T).sum(-2)
        return jax.lax.dynamic_update_index_in_dim(T, new, t, axis=2)

    T = jax.lax.fori_loop(0, C, row, jnp.zeros_like(A))
    dot = lambda spec, x, y: jnp.einsum(spec, x, y, precision=HI)

    def step(S, xs):
        q, k, v, G, beta, T, B = xs
        eG = jnp.exp(G)
        u = dot("hti,hid->htd", T,
                beta[..., None] * (v - dot("htc,hcd->htd", k * eG, S)))
        o = dot("htc,hcd->htd", q * eG, S) + dot("hti,hid->htd", B, u)
        Gc = G[:, -1:, :]
        S = S * jnp.exp(Gc).swapaxes(1, 2) \
            + dot("hic,hid->hcd", k * jnp.exp(Gc - G), u)
        return S, o

    S, o = jax.lax.scan(step, S, (q, k, v, G, beta, T, B))
    return S, o.swapaxes(1, 2).reshape(s, H, d)


def kda_out(cfg, a, o, gate):
    """The gated output norm: ``N_o(o)`` over each head's values times the
    sigmoid of ``gate``; o [..., H, d] float32 -> [..., H d] in the weights'
    dtype (the input of ``wo``)."""
    y = rms(o, a["o_norm"], cfg.norm_eps).reshape(gate.shape)
    return (y * jax.nn.sigmoid(gate)).astype(a["wo"].dtype)


# ----------------------------------------------------------------------
# the per-head full-attention piece (beside linear layers)
# ----------------------------------------------------------------------
GQA_IN = ("wq", "wk", "wv", "wg")     # a full layer's projections of ``h``


def gqa_split(cfg, y):
    """The projections ``y`` [..., H Dh + 2 Hkv Dh (+ H Dh)] of a per-head
    full layer (``q | k | v | gate``, :data:`GQA_IN`'s order) as q [..., H,
    Dh], k, v [..., Hkv, Dh] and the gate's logits [..., H Dh] | None.  No
    rotation and no head norm: the layer has no position encoding."""
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    M, Mkv = H * Dh, Hkv * Dh
    heads = lambda t, n: t.reshape(t.shape[:-1] + (n, Dh))
    return (heads(y[..., :M], H), heads(y[..., M:M + Mkv], Hkv),
            heads(y[..., M + Mkv:M + 2 * Mkv], Hkv),
            y[..., M + 2 * Mkv:2 * M + 2 * Mkv]
            if cfg.attn_output_gate else None)


# ----------------------------------------------------------------------
# the latent-attention (MLA) pieces
# ----------------------------------------------------------------------
def yarn(rope, dim: int):
    """(inv_freq [dim / 2] float32, the factor on cos and sin, the factor
    ``m`` whose SQUARE multiplies the softmax scale) of a ``mla_rope`` group:
    with ``f_i = theta^(-2i / dim)``, ``s = factor`` and ``L0`` the original
    length, ``lo = floor(dim ln(L0 / (2 pi beta_fast)) / (2 ln theta))``,
    ``hi = ceil(the same with beta_slow)`` (both inside [0, dim - 1]), the
    ramp ``r_i = clip((i - lo) / (hi - lo), 0, 1)`` and

        inv_freq_i = f_i (1 - r_i) + (f_i / s) r_i

    (pairs that turn more than ``beta_fast`` times in ``L0`` keep their
    frequency, pairs that turn less than ``beta_slow`` times are slowed
    ``s``-fold).  With ``y(a) = 0.1 a ln s + 1`` (1 for ``s <= 1``), cos and
    sin are scaled by ``y(mscale) / y(mscale_all_dim)`` and ``m =
    y(mscale_all_dim)`` where ``mscale_all_dim`` is set (else 1).  Computed
    once, on the host, from the configuration."""
    s, theta = float(rope["factor"]), float(rope["theta"])
    L0 = float(rope["original_max_position_embeddings"])
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    turns = lambda b: dim * math.log(L0 / (b * 2 * math.pi)) \
        / (2 * math.log(theta))
    lo = max(math.floor(turns(rope["beta_fast"])), 0)
    hi = min(math.ceil(turns(rope["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    y = lambda a: 0.1 * a * math.log(s) + 1.0 if s > 1 else 1.0
    all_dim = float(rope["mscale_all_dim"])
    return ((f * (1 - ramp) + f / s * ramp).astype(np.float32),
            y(float(rope["mscale"])) / y(all_dim),
            y(all_dim) if all_dim else 1.0)


def rotate(kd, t, pos):
    """``t`` [..., r] (the rotary values of the query heads, of the shared
    key or of the index heads) at integer positions ``pos``, broadcastable
    to ``t``'s leading axes: pairs (2i, 2i + 1) turned by ``pos *
    inv_freq_i``, float32 angles.  Without a ``rope`` group the values pass
    unrotated."""
    if kd.rope is None:
        return t
    inv_freq, on_cos_sin, _ = yarn(kd.rope, t.shape[-1])
    ang = jnp.asarray(pos, F32)[..., None] * jnp.repeat(inv_freq, 2)
    cos, sin = jnp.cos(ang) * on_cos_sin, jnp.sin(ang) * on_cos_sin
    t32 = t.astype(F32)
    # the pair's other value: -t[2i + 1] at 2i, t[2i] at 2i + 1
    other = jnp.where(jnp.arange(t.shape[-1]) % 2 == 0,
                      -jnp.roll(t32, -1, axis=-1), jnp.roll(t32, 1, axis=-1))
    return (t32 * cos + other * sin).astype(t.dtype)


def _gain(scale, by: float):
    """A norm's gain times a kind's rescale factor (1: the gain itself)."""
    return scale if by == 1.0 else scale.astype(F32) * by


def mla_row(kd, a, cr, pos):
    """The cache row from ``[c_raw | k_r]`` [..., kv + r] at positions
    ``pos`` [...]: the latent normed (times the kind's ``kv_scale``), the
    shared key values (rotated where the model rotates), zeros up to the row
    width."""
    kv = kd.kv
    c = rms(cr[..., :kv], _gain(a["kv_norm"], kd.kv_scale), kd.eps)
    pad = jnp.zeros(cr.shape[:-1] + (kd.row_width - cr.shape[-1],),
                    cr.dtype)
    return jnp.concatenate([c, rotate(kd, cr[..., kv:], pos), pad], axis=-1)


def mla_query(kd, q, pos):
    """The projected queries ``q`` [..., H (n + r)] at positions ``pos``
    [...] as heads [..., H, n + r], each head's rotary part rotated."""
    n = kd.nope
    q = q.reshape(q.shape[:-1] + (kd.heads, -1))
    if kd.rope is None:
        return q
    return jnp.concatenate(
        [q[..., :n], rotate(kd, q[..., n:], jnp.asarray(pos)[..., None])],
        axis=-1)


def mla_cq(kd, a, cq_raw):
    """The query's bottleneck ``N_q(h W_qa)`` (times the kind's
    ``q_scale``)."""
    return rms(cq_raw, _gain(a["q_norm"], kd.q_scale), kd.eps)


def mla_project(kd, a, h, pos):
    """(q [..., H, n + r], the cache row [..., row_width], the query's
    bottleneck ``c_q`` | None) of ``h`` [..., D] at positions ``pos`` [...];
    the query full-rank, or ``N_q(h Wqa) Wqb`` where the kind has
    ``q_rank``."""
    w = lambda n: a[n].astype(h.dtype)
    cq = None
    if kd.q_rank:
        cq = mla_cq(kd, a, h @ w("wqa"))
        q = cq @ w("wqb")
    else:
        q = h @ w("wq")
    return mla_query(kd, q, pos), mla_row(kd, a, h @ w("wkva"), pos), cq


def _wkvb(kd, a):
    """``Wkvb`` as (keys [kv, H, n], values [kv, H, v])."""
    w = a["wkvb"].reshape(kd.kv, kd.heads, -1)
    return w[..., :kd.nope], w[..., kd.nope:]


def mla_decompress(rows, wk, wv, r: int):
    """Per-head keys [..., H, n + r] and values [..., H, v] of cache rows
    [..., row_width] through ``Wkvb`` as :func:`_wkvb` splits it: float32
    sums rounded to the rows' dtype, the shared ``k_r`` (``r`` values) as
    the row has it."""
    kv = wk.shape[0]
    c = rows[..., :kv]
    k_n = jnp.einsum("...c,chn->...hn", c, wk.astype(c.dtype))
    k_r = jnp.broadcast_to(rows[..., None, kv:kv + r],
                           k_n.shape[:-1] + (r,))
    return (jnp.concatenate([k_n, k_r], axis=-1),
            jnp.einsum("...c,chv->...hv", c, wv.astype(c.dtype)))


def mla_absorb(kd, a, q):
    """A decode step's queries against the ROWS: q [B, H, n + r] ->
    ``[q_n Wkvb_k^T | q_r | 0]`` [B, H, row_width]."""
    n = kd.nope
    wk, _ = _wkvb(kd, a)
    qc = jnp.einsum("bhn,chn->bhc", q[..., :n], wk.astype(q.dtype))
    pad = jnp.zeros(q.shape[:-1] + (kd.row_width - kd.kv - kd.rot,), q.dtype)
    return jnp.concatenate([qc, q[..., n:], pad], axis=-1)


def mla_unabsorb(kd, a, o):
    """The rows' weighted sum o [B, H, row_width] (its first ``kv`` values:
    ``sum_j p_j c(j)``) through ``Wkvb_v``: [B, H, v]."""
    _, wv = _wkvb(kd, a)
    return jnp.einsum("bhc,chv->bhv", o[..., :kd.kv], wv.astype(o.dtype))


def _mla_scale(kd) -> float:
    m = 1.0 if kd.rope is None else yarn(kd.rope, kd.rot)[2]
    return (kd.nope + kd.rot) ** -0.5 * m * m


def head_gate(kd, o, g, lead):
    """The headwise gate: head outputs ``o`` [..., H, v] times the sigmoid
    of the head's gate logit ``g`` [..., H] (shaped alike); returns ``lead +
    (H v,)``, what ``W_o`` takes, gated or not."""
    if kd.gate:
        g = jax.nn.sigmoid(g.astype(F32)).reshape(o.shape[:-1] + (1,))
        o = (o.astype(F32) * g).astype(o.dtype)
    return o.reshape(lead + (-1,))


# ----------------------------------------------------------------------
# the learned selection of a latent layer's keys (the indexer)
# ----------------------------------------------------------------------
def index_key(kd, a, ik_raw, pos):
    """The index key of a token from ``h W_Ik`` [..., di] at positions
    ``pos`` [...]: LayerNorm (gain and bias), then its first ``rot`` values
    rotated as the shared key's are.  What the index cache holds."""
    x = ik_raw.astype(F32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    k = ((x - mu) * jax.lax.rsqrt(var + kd.eps) * a["ik_norm"].astype(F32)
         + a["ik_bias"].astype(F32)).astype(ik_raw.dtype)
    r = kd.rot
    return jnp.concatenate([rotate(kd, k[..., :r], pos), k[..., r:]], -1)


def index_query(kd, qi, w_raw, pos):
    """(index queries [..., Hi, di], their first ``rot`` values rotated;
    head weights [..., Hi] float32 = ``h W_Iw`` times ``Hi^-0.5 di^-0.5``)
    from ``c_q W_Iq`` [..., Hi di] and ``h W_Iw`` [..., Hi]."""
    Hi, di, _ = kd.index
    r = kd.rot
    q = qi.reshape(qi.shape[:-1] + (Hi, di))
    q = jnp.concatenate(
        [rotate(kd, q[..., :r], jnp.asarray(pos)[..., None]), q[..., r:]], -1)
    return q, w_raw.astype(F32) * (Hi ** -0.5 * di ** -0.5)


def _sortable(x):
    """float32 -> uint32 that orders as the floats do."""
    b = jax.lax.bitcast_convert_type(x.astype(F32), jnp.int32)
    b = b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(b, jnp.uint32) ^ jnp.uint32(1 << 31)


def select_keys(scores_t, q_pos, k: int):
    """A chunk's selection as an additive bias: ``scores_t`` [keys, s]
    float32 (TRANSPOSED: a key a row; ``NEG_INF`` where the key is past the
    query) -> [keys, s] bfloat16, 0 where key j is among the ``k`` largest
    of query t's scores (all of ``j <= t`` while ``t + 1 <= k``; exact ties
    at the k-th all kept) and ``NEG_INF`` elsewhere.  The k-th largest is
    found by bisection on the scores' bits, 32 counting passes and no sort,
    over the shortest of eight prefixes of the keys that holds the chunk."""
    P, s = scores_t.shape
    u = _sortable(scores_t)
    causal = jnp.arange(P)[:, None] <= q_pos[None, :]

    def kth(n):
        def run(u):
            un = u[:n]

            def bit(i, t):
                cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
                cnt = jnp.sum(un >= cand[None, :], axis=0, dtype=jnp.int32)
                return jnp.where(cnt >= k, cand, t)

            return jax.lax.fori_loop(0, 32, bit, jnp.zeros((s,), jnp.uint32))
        return run

    if P <= k:
        keep = causal
    else:
        step = -(-P // 8 // 128) * 128 if P >= 8 * 1024 else P
        cuts = list(range(step, P, step)) + [P]
        last = q_pos[-1] + 1                       # keys the chunk can see
        branch = jnp.sum(jnp.asarray(cuts) < last)
        thr = jax.lax.switch(branch, [kth(n) for n in cuts], u)
        keep = causal & (u >= thr[None, :])
    return jnp.where(keep, 0.0, afmoe.NEG_INF).astype(jnp.bfloat16)


# rows of one group of :func:`select_positions`' loop (one sort a group).
# Eight: the chip sorts [2, 32,768], [4, .] and [8, .] float32 scores in the
# same 193-204 us and [16, .] in 387 (a vreg's eight sublanes hold eight
# batch rows), so a smaller group sorts no faster and pads no less (my chip
# runs, PR 58, tools/dsa_select_bench.py; the whole table: PERF.md section
# 5, ``dots3-note-L5-ep16.serve-doc-48k``)
SORT_GROUP = 8


def select_positions(scores, pos, k: int):
    """A decode step's selection as positions: ``scores`` [B, keys] float32
    (``NEG_INF`` past ``pos``) -> (positions [B, k'] int32 of the ``k' =
    min(k, keys)`` largest, best first, BY SLOT; how many of them are real,
    ``min(pos + 1, k')`` [B]).

    A NEGATIVE ``pos`` says that the row does not decode
    (:func:`fused_layers` hands ``where(live, pos, -1)``): its positions are
    zeros and none of them is real.  The rows that decode are sorted
    :data:`SORT_GROUP` at a time, live rows first
    (``ops/pallas/decode.py:over_live_groups``), in a loop whose trip count is
    read at run time: one ``jax.lax.top_k`` over a group's score rows an
    iteration, written at those slots, so a parked slot costs no sort unless
    it pads the last group, and no live row at all is a loop of no
    iterations.  A row's selection is ``jax.lax.top_k``'s on that row alone,
    ties at the k-th score by index order included."""
    from deepspeed_tpu.ops.pallas.decode import over_live_groups

    B = scores.shape[0]
    k = min(k, scores.shape[1])
    live = pos >= 0

    def group(at):
        _, idx = jax.lax.top_k(jnp.take(scores, at, axis=0), k)
        return jnp.where(jnp.take(live, at)[:, None], idx.astype(jnp.int32),
                         0)

    sel = over_live_groups(live, B, SORT_GROUP, group,
                           jnp.zeros((B, k), jnp.int32))
    return sel, jnp.minimum(pos + 1, k).astype(jnp.int32)


def selection_rows(live):
    """(row, step) pairs one indexed layer's selection works in a decode
    step with the live mask ``live`` [B], group padding included: [rows
    :func:`select_positions` sorts, rows ``dsa_decode_selected`` looks up,
    gathers and attends] int32."""
    from deepspeed_tpu.ops.pallas.decode import GATHER_GROUP, live_groups

    def worked(group):
        _, G, groups = live_groups(live, live.shape[0], group)
        return G * groups

    return jnp.stack([worked(SORT_GROUP), worked(GATHER_GROUP)])


# ----------------------------------------------------------------------
# the window over latent rows (a per-slot ring)
# ----------------------------------------------------------------------
def ring_before(ring, start, window: int):
    """The ``window - 1`` rows before position ``start`` out of a ring
    [R, W] (row of position p at ``p % R``), oldest first, and their
    positions (negative: before the sequence's start, to be masked)."""
    R = ring.shape[0]
    p = start - (window - 1) + jnp.arange(window - 1)
    return jnp.take(ring, p % R, axis=0), p


def ring_after(ring, rows, start, valid_len):
    """The ring after a chunk's first ``valid_len`` rows ``rows`` [s, W] at
    positions ``start ..``: each ring row takes the LAST real position that
    lands on it, the others stay."""
    R, s = ring.shape[0], rows.shape[0]
    last = start + valid_len - 1
    i = jnp.arange(R)
    p = last - (last - i) % R                 # the last position on row i
    new = jnp.take(rows, jnp.clip(p - start, 0, s - 1), axis=0)
    return jnp.where((p >= start)[:, None], new.astype(ring.dtype), ring)


def window_attention(kd, a, q, rows, q_pos, k_pos):
    """A chunk's attention over a window of latent rows, decompressed: q
    [s, H, n + r] at positions ``q_pos`` [s]; ``rows`` [S, W] at positions
    ``k_pos`` [S] (the ring's rows before the chunk, then the chunk's own);
    keys ``0 <= t - j < window``, ``j >= 0``.  Query blocks of 256 against
    the keys their windows can hold.  Returns [s, H, v]."""
    s, S = q.shape[0], rows.shape[0]
    wk, wv = _wkvb(kd, a)
    k, v = mla_decompress(rows, wk, wv, kd.rot)          # [S, H, .]
    scale, W = _mla_scale(kd), kd.window
    bq = min(s, 256)
    span = min(S, bq + W - 1)
    out = []
    for q0 in range(0, s, bq):
        # the block's keys: from its first query's window to its last query
        k0 = q0                                # rows[q0 + W - 1] is query q0
        kb, vb = k[k0:k0 + span], v[k0:k0 + span]
        d = q_pos[q0:q0 + bq, None] - k_pos[None, k0:k0 + span]
        ok = (d >= 0) & (d < W) & (k_pos[None, k0:k0 + span] >= 0)
        sc = jnp.einsum("qhd,khd->hqk", q[q0:q0 + bq], kb,
                        preferred_element_type=F32) * scale
        p = jax.nn.softmax(jnp.where(ok[None], sc, afmoe.NEG_INF), axis=-1)
        out.append(jnp.einsum("hqk,khv->qhv", p.astype(vb.dtype), vb,
                              preferred_element_type=F32).astype(q.dtype))
    return jnp.concatenate(out, axis=0)


def ring_decode(kd, q, ring, pos):
    """A decode step's absorbed attention over the rings: q [B, H, W]
    (:func:`mla_absorb`), ``ring`` [B, R, W] with this step's row written at
    ``pos % R``; ring row i holds position ``pos - (pos - i) % R``, attended
    where it is not negative and within the window.  Returns [B, H, W]."""
    R = ring.shape[1]
    back = (pos[:, None] - jnp.arange(R)[None, :]) % R        # t - j
    ok = (back < kd.window) & (back <= pos[:, None])
    sc = jnp.einsum("bhw,brw->bhr", q, ring,
                    preferred_element_type=F32) * _mla_scale(kd)
    p = jax.nn.softmax(jnp.where(ok[:, None], sc, afmoe.NEG_INF), axis=-1)
    return jnp.einsum("bhr,brw->bhw", p.astype(ring.dtype), ring,
                      preferred_element_type=F32).astype(q.dtype)


# ----------------------------------------------------------------------
# forwards 1 and 2: no cache (CausalLM.apply), and a prefill chunk on one
# slot's views (state carried in and out)
# ----------------------------------------------------------------------
def apply_layers(cfg, params, x, mesh=None):
    """The layer stack on ``x`` [B, S, D], positions ``0 .. S - 1``: every
    sequence a chunk at position 0 on an empty cache of its own."""
    refuse_parallel(cfg, mesh, "CausalLM.apply")
    B, S, _ = x.shape
    pad = -S % SUB if S > SUB else 0       # whole sub-chunks (pad rows idle)
    lin, lat = kind_layers(cfg)
    state, tail = state_shapes(cfg, 1)

    def one(xb):
        xb = jnp.pad(xb, ((0, pad), (0, 0)))[None]
        cache = {}
        if lat:
            cache["latent"] = jnp.zeros(
                (len(lat), 1, 1, S + pad, row_width(cfg)), x.dtype)
        if lin:
            cache.update(state=jnp.zeros(state, F32),
                         tail=jnp.zeros(tail, x.dtype))
        cache.update(_empty_extras(cfg, S + pad, x.dtype))
        return cached_layers(cfg, params, xb, cache, 0, S)[0][0, :S]

    return jax.lax.map(one, x)


def _empty_extras(cfg, positions: int, dtype):
    """The cache entries beside ``latent`` that one sequence of
    ``positions`` rows needs where the model has an indexer (``index``),
    sliding latent layers (``ring``) or per-head full layers (``k``, ``v``):
    zeros."""
    out = {}
    if full_layers(cfg):
        kv = jnp.zeros((len(full_layers(cfg)), 1, cfg.num_kv_heads,
                        positions, cfg.head_dim), dtype)
        out.update(k=kv, v=kv)
    kd = cfg.mla_kind("latent_attention") if kind_layers(cfg)[1] else None
    if kd is not None and kd.index:
        out["index"] = jnp.zeros((len(kind_layers(cfg)[1]), 1, 1, positions,
                                  kd.index[1]), dtype)
    sl = sliding_layers(cfg)
    if sl:
        kd = cfg.mla_kind("latent_sliding_attention")
        out["ring"] = jnp.zeros((len(sl), 1, kd.window, kd.row_width), dtype)
    return out


def cached_layers(cfg, params, x, cache, start, valid_len,
                  impl: Optional[str] = None):
    """The layer stack on a chunk ``x`` [1, s, D] at positions ``start ..``
    over ONE slot's views (``latent`` [latent layers, 1, 1, positions,
    row_width]; with linear layers also ``state`` [linear layers, 1, H, d, d]
    float32 and ``tail`` [linear layers, 1, K - 1, 3 H d]; with per-head
    full layers ``k`` and ``v`` [full layers, 1, Hkv, positions, Dh] in
    ``latent``'s place: what ``cache_kind.LatentPages.view`` /
    ``LatentPagesAndState.view`` / ``FullPagesAndState.view`` slice out);
    only the first ``valid_len`` rows are real.  A chunk at position 0
    starts from a zero state whatever the slot held (the state's reset at
    admission).  ``impl``: the latent layers' chunk attention
    (``ops/pallas/common.py``'s three names; None: by the device).  Returns
    (x, views)."""
    B, s, _ = x.shape
    assert B == 1, "a chunk program prefills one slot"
    start = jnp.asarray(start, jnp.int32)
    pos = start + jnp.arange(s)
    real = jnp.arange(s) < valid_len
    latent, state, tail = (cache.get(k) for k in ("latent", "state", "tail"))
    index, ring = cache.get("index"), cache.get("ring")
    k_full, v_full = cache.get("k"), cache.get("v")
    kept = (start != 0)
    experts = afmoe._experts(params)
    i_lin = i_lat = i_sw = i_full = 0
    for l in range(cfg.num_layers):
        lp, le = layer_params(cfg, params, l)
        a = lp["attn"]
        h = rms(x, lp["attn_norm"]["scale"], cfg.norm_eps)
        if is_linear(cfg, l):
            u, f_low, g_low, b_raw = kda_project(a, h)
            c, t1 = short_conv(u, jnp.where(kept, tail[i_lin], 0),
                               a["conv"], valid_len)
            q, k, v, g, beta, gate = kda_activate(cfg, a, c, f_low, g_low,
                                                  b_raw)
            # a pad row: beta = 0 and no decay, so the state does not move
            g = jnp.where(real[None, :, None, None], g, 0.0)
            beta = jnp.where(real[None, :, None], beta, 0.0)
            S1, o = kda_chunk(jnp.where(kept, state[i_lin, 0], 0.0), q[0],
                              k[0], v[0], g[0], beta[0])
            state = state.at[i_lin, 0].set(S1)
            tail = tail.at[i_lin].set(t1)
            ctx = kda_out(cfg, a, o[None], gate)
            i_lin += 1
        elif cfg.layer_types[l] == "full_attention":
            # per-head keys and values, unrotated: the chunk's rows join the
            # slot's view, and the queries attend every row up to their own
            q, k, v, g = gqa_split(cfg, jnp.concatenate(
                [h @ a[n].astype(h.dtype) for n in GQA_IN if n in a], -1))
            heads = lambda t: t.transpose(0, 2, 1, 3)
            at = (i_full, 0, 0, start, 0)
            k_full = jax.lax.dynamic_update_slice(
                k_full, heads(k)[None].astype(k_full.dtype), at)
            v_full = jax.lax.dynamic_update_slice(
                v_full, heads(v)[None].astype(v_full.dtype), at)
            o = afmoe.attend(
                heads(q), [(k_full[i_full], v_full[i_full],
                            jnp.arange(k_full.shape[3]))],
                pos, window=0, scale=cfg.head_dim ** -0.5,
                live_keys=start + s)
            ctx = afmoe.gated(cfg, heads(o).reshape(B, s, -1), g)
            i_full += 1
        else:
            kd = cfg.mla_kind(cfg.layer_types[l])
            q, row, cq = mla_project(kd, a, h, pos[None])
            if kd.window:
                # a window over latent rows: the ring's rows before the
                # chunk and the chunk's own, then the ring moves on
                before, p_before = ring_before(ring[i_sw, 0], start,
                                               kd.window)
                o = window_attention(
                    kd, a, q[0], jnp.concatenate(
                        [before, row[0].astype(ring.dtype)], axis=0),
                    pos, jnp.concatenate([p_before, pos]))
                ring = ring.at[i_sw, 0].set(
                    ring_after(ring[i_sw, 0], row[0], start, valid_len))
                i_sw += 1
            else:
                latent = jax.lax.dynamic_update_slice(
                    latent, row[None, :, None].astype(latent.dtype),
                    (i_lat, 0, 0, start, 0))
                if kd.index:
                    # the chunk's index keys join the cache, every query
                    # scores the keys up to itself, and the layer attends
                    # the selected ones only
                    w = lambda n: a[n].astype(h.dtype)
                    ik = index_key(kd, a, h @ w("wik"), pos[None])
                    index = jax.lax.dynamic_update_slice(
                        index, ik[None, :, None].astype(index.dtype),
                        (i_lat, 0, 0, start, 0))
                    qi, wi = index_query(kd, cq @ w("wiq"), h @ w("wiw"),
                                         pos[None])
                    scores_t = dsa_index_scores_chunk(
                        qi[0], wi[0], index[:, 0, 0], start, layer=i_lat,
                        impl=impl)
                    o = dsa_chunk_attention(
                        q[0], latent[:, 0, 0],
                        a["wkvb"].reshape(kd.kv, kd.heads, -1),
                        select_keys(scores_t, pos, kd.index[2]), start,
                        nope=kd.nope, scale=_mla_scale(kd), layer=i_lat,
                        impl=impl)
                else:
                    # this layer's cache is latent rows: the flash kernel
                    # where its sizes allow (keys, values and scores stay in
                    # VMEM), else afmoe.attend(expand=mla_decompress), its
                    # reference
                    o = mla_chunk_attention(
                        q[0], latent[:, 0, 0],
                        a["wkvb"].reshape(kd.kv, kd.heads, -1), start,
                        nope=kd.nope, scale=_mla_scale(kd), layer=i_lat,
                        impl=impl)
                i_lat += 1
            ctx = head_gate(kd, o, h @ a["wg"].astype(h.dtype)
                            if kd.gate else None, (B, s))
        x = afmoe.mlp_block(cfg, lp, x, ctx @ a["wo"].astype(ctx.dtype),
                            None if le is None else experts, le)
    return x, _views(latent=latent, state=state, tail=tail, index=index,
                     ring=ring, k=k_full, v=v_full)


def _views(**entries):
    """The cache a forward hands back: the entries it was given."""
    return {k: v for k, v in entries.items() if v is not None}


# ----------------------------------------------------------------------
# forward 3: one decode step through the fused kernels
# ----------------------------------------------------------------------
def _pad_cols(w, multiple: int = 512):
    """``fused_norm_qkv`` tiles its weight's columns in 128-lane blocks
    that divide their number: pad with zero columns to a count that has
    such divisors."""
    return jnp.pad(w, ((0, 0), (0, -w.shape[1] % multiple)))


def _w_in_names(kd):
    """A latent layer's projections of ``h``, in ``w_in``'s column order."""
    return (("wqa" if kd.q_rank else "wq", "wkva")
            + (("wg",) if kd.gate else ())
            + (("wik", "wiw") if kd.index else ()))


def inject(cfg, params) -> Dict[str, Any]:
    """The kernel-injected view (``afmoe.inject``'s shape): per-layer dicts,
    every projection of ``h`` in one ``[D, N]`` matrix ``w_in`` (linear: q |
    k | v | decay gate down | output gate down | beta; per-head full: q | k |
    v | gate; latent: q | [c_raw |
    k_r], or, with a low-rank query, Wqa | [c_raw | k_r] with ``wqb`` and
    ``q_norm`` beside it; then the head gate's and the indexer's key and
    head-weight columns where the kind has them), the small per-kind arrays
    under their own names,
    the stacked routed experts by reference."""
    layers = []
    for l in range(cfg.num_layers):
        lp, le = layer_params(cfg, params, l)
        a = lp["attn"]
        t = cfg.layer_types[l]
        names = (("wq", "wk", "wv", "wf_down", "wg_down", "wb")
                 if is_linear(cfg, l) else
                 tuple(n for n in GQA_IN if n in a)
                 if t == "full_attention" else _w_in_names(cfg.mla_kind(t)))
        d = {k: v for k, v in a.items() if k not in names}
        d["w_in"] = _pad_cols(jnp.concatenate([a[k] for k in names], axis=-1))
        layers.append({**d, **afmoe.inject_rest(cfg, lp, le)})
    return afmoe.inject_outer(params, layers)


def moe_counts_zero(cfg):
    """``afmoe.moe_counts_zero``; for a model with linear layers one more
    entry: (row, linear layer) pairs that were LIVE, and pairs whose state
    the decode kernel VISITED; for a model with an indexer a LAST entry:
    (row, step) pairs the selection of ONE indexed layer sorted, and pairs
    it gathered and attended (:func:`selection_rows`)."""
    pair = lambda has: (jnp.zeros((2,), jnp.int32),) if has else ()
    return (afmoe.moe_counts_zero(cfg) + pair(kind_layers(cfg)[0])
            + pair(cfg.mla_index_topk))


def _counted(stats, i: int, more):
    """The counts with ``more`` added to entry ``i``."""
    i %= len(stats)
    return stats[:i] + (stats[i] + more,) + stats[i + 1:]


def fused_layers(cfg, dparams, x, cache, pos, page_table, *, moe_live=None,
                 impl: Optional[str] = None):
    """The layer stack for one token a row: ``x`` [B, D] at per-row
    positions ``pos`` [B]; ``cache``: ``latent`` [latent layers, pages, 1,
    page, row_width] through ``page_table`` [B, columns] and, with linear
    layers, ``state`` [linear layers, B, H, d, d] and ``tail`` [linear
    layers, B, K - 1, 3 H d] by row (a row of the batch is a slot); with
    per-head full layers ``k`` and ``v`` [full layers, pages, Hkv, page, Dh]
    through the same table in ``latent``'s place.
    ``moe_live`` [B] bool: the rows that decode; only their state, tail and
    pages move, and the kernels visit only them.  In an indexed layer
    it governs the selection between the two kernels too: only the rows that
    decode are sorted (:func:`select_positions`, which is told by a negative
    position), looked up, gathered and attended (``dsa_decode_selected``),
    a group at a time, and the others' attention output is zeros.  Returns
    (x, cache, counts | None)."""
    from deepspeed_tpu.ops.pallas.decode import (dsa_decode_selected,
                                                 dsa_index_scores_paged,
                                                 flash_decode,
                                                 fused_norm_qkv,
                                                 kda_decode_step,
                                                 mla_decode_paged,
                                                 paged_kv_append,
                                                 paged_row_append)

    B = x.shape[0]
    Hk, d = cfg.kda_num_heads, cfg.kda_head_dim
    C3, r = 3 * Hk * d, cfg.kda_gate_rank
    latent, state, tail = (cache.get(k) for k in ("latent", "state", "tail"))
    index, ring = cache.get("index"), cache.get("ring")
    k_full, v_full = cache.get("k"), cache.get("v")
    stats = moe_counts_zero(cfg) if moe_live is not None else None
    # the indexer's counts are the last of them, the linear layers' the
    # last of the others
    i_state = -2 if cfg.mla_index_topk else -1
    i_lin = i_lat = i_sw = i_full = 0
    for l, lp in enumerate(dparams["layers"]):
        y = fused_norm_qkv(x, lp["n1_scale"], None, lp["w_in"], None,
                           kind="rmsnorm", eps=cfg.norm_eps, impl=impl)
        if is_linear(cfg, l):
            c, t1 = short_conv(y[:, None, :C3], tail[i_lin], lp["conv"])
            if moe_live is not None:
                t1 = jnp.where(moe_live[:, None, None], t1, tail[i_lin])
            tail = tail.at[i_lin].set(t1)
            q, k, v, g, beta, gate = kda_activate(
                cfg, lp, c[:, 0], y[:, C3:C3 + r], y[:, C3 + r:C3 + 2 * r],
                y[:, C3 + 2 * r:])
            o, state, visited = kda_decode_step(
                state, q, k, v, g, beta, layer=i_lin, live=moe_live,
                impl=impl)
            if stats is not None:
                stats = _counted(stats, i_state, jnp.stack(
                    [jnp.sum(moe_live, dtype=jnp.int32), visited]))
            ctx = kda_out(cfg, lp, o, gate)
            i_lin += 1
        elif cfg.layer_types[l] == "full_attention":
            # the row's K and V join its pages, then the paged kernel: every
            # position up to its own, a key-value head serving its group
            q, k, v, g = gqa_split(cfg, y)
            k_full, v_full = paged_kv_append(k_full, v_full, k, v, pos,
                                             page_table, layer=i_full,
                                             impl=impl)
            o = flash_decode(q, k_full, v_full, pos,
                             sm_scale=cfg.head_dim ** -0.5, layer=i_full,
                             page_table=page_table, live=moe_live, impl=impl)
            ctx = afmoe.gated(cfg, o.reshape(B, -1), g)
            i_full += 1
        else:
            kd = cfg.mla_kind(cfg.layer_types[l])
            # the query's columns of ``w_in``: the heads, or the bottleneck
            M = kd.q_rank or kd.heads * (kd.nope + kd.rot)
            R = M + kd.kv + kd.rot               # ... then [c_raw | k_r]
            q = cq = y[:, :M]
            if kd.q_rank and kd.q_scale == 1.0 and not kd.index:
                # N_q and Wqb: the kernel once more
                q = fused_norm_qkv(q, lp["q_norm"], None, lp["wqb"], None,
                                   kind="rmsnorm", eps=cfg.norm_eps,
                                   impl=impl)
            elif kd.q_rank:       # the bottleneck is the indexer's input too
                cq = mla_cq(kd, lp, q)
                q = cq @ lp["wqb"].astype(cq.dtype)
            q = mla_query(kd, q, pos)
            row = mla_row(kd, lp, y[:, M:R], pos)
            gate = y[:, R:R + kd.heads] if kd.gate else None
            if kd.window:
                # the ring: a live row's position lands at ``pos % rows``
                at = jax.nn.one_hot(pos % ring.shape[2], ring.shape[2],
                                    dtype=bool)
                if moe_live is not None:
                    at = at & moe_live[:, None]
                ring = ring.at[i_sw].set(jnp.where(
                    at[:, :, None], row[:, None].astype(ring.dtype),
                    ring[i_sw]))
                o = ring_decode(kd, mla_absorb(kd, lp, q), ring[i_sw], pos)
                i_sw += 1
            else:
                latent = paged_row_append(latent, row, pos, page_table,
                                          layer=i_lat, impl=impl)
                if kd.index:
                    # score the row's index keys, take the best, attend
                    # their rows only
                    G = R + kd.heads * kd.gate
                    Hi, di, topk = kd.index
                    index = paged_row_append(
                        index, index_key(kd, lp, y[:, G:G + di], pos), pos,
                        page_table, layer=i_lat, impl=impl)
                    qi, wi = index_query(
                        kd, cq @ lp["wiq"].astype(cq.dtype),
                        y[:, G + di:G + di + Hi], pos)
                    scores = dsa_index_scores_paged(
                        qi, wi, index, pos, page_table, layer=i_lat,
                        live=moe_live, impl=impl)
                    sel, n_sel = select_positions(
                        scores, pos if moe_live is None
                        else jnp.where(moe_live, pos, -1), topk)
                    o = dsa_decode_selected(
                        mla_absorb(kd, lp, q), latent, sel, n_sel,
                        page_table, layer=i_lat, sm_scale=_mla_scale(kd),
                        live=moe_live, impl=impl)
                    if stats is not None and i_lat == 0:
                        stats = _counted(stats, -1, selection_rows(moe_live))
                else:
                    o = mla_decode_paged(mla_absorb(kd, lp, q), latent, pos,
                                         page_table, layer=i_lat,
                                         sm_scale=_mla_scale(kd),
                                         live=moe_live, impl=impl)
                i_lat += 1
            ctx = head_gate(kd, mla_unabsorb(kd, lp, o), gate, (B,))
        x, stats = afmoe.fused_close(cfg, dparams, lp, l, ctx, x, stats,
                                     moe_live, impl)
    return x, _views(latent=latent, state=state, tail=tail, index=index,
                     ring=ring, k=k_full, v=v_full), stats
