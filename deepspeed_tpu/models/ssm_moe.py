"""Layers that are ONE mixer each (NVIDIA Nemotron-3-Nano, ``model_type:
nemotron_h``; ``ModelConfig.layer_types`` kinds ``mamba2``, ``experts`` and
``full_attention``): a third layer form beside ``models/afmoe.py`` and
``models/kda_mla.py``, whose layers are "attention of some kind, THEN an MLP
of some kind".  Here no layer has both:

    x = embed[tokens]
    layer l:  x = x + mixer_l(N_l(x))          N RMSNorm, own gain, norm_eps
    logits = N_f(x) W_head

    "mamba2" (a selective state space: H = ssm_num_heads heads of P =
    ssm_head_dim, G = ssm_groups groups of heads, N = ssm_state_size), h =
    N_l(x):
        [z | xBC | dt] = h W_in            widths H P | H P + 2 G N | H
        xBC = silu(conv_K(xBC) + b_conv)   depthwise causal convolution of K
            = ssm_conv_kernel taps, zeros before position 0
        x [H, P] | B [G, N] | C [G, N] = xBC        head i reads group i // (H / G)
        dt = softplus(dt + dt_bias);  a = -exp(a_log)     one a head
        per head, S [P, N] float32, S_0 = 0:
            S_t = exp(dt_t a) S_{t-1} + (dt_t x_t) B_t^T
            y_t = S_t C_t + D x_t
        o = N_groups(y * silu(z)) * w      the gate BEFORE the norm, the norm
            over each of the G groups of H P / G channels
        mixer = o W_out

    "full_attention" (num_heads query heads over num_kv_heads key-value
    heads of head_dim; NO position encoding, no head norm, no gate):
        mixer = softmax(q k^T / sqrt(head_dim)) v W_o,  every j <= t

    "experts": ``afmoe.route`` (sigmoid scores over the router's experts in
    float32, a bias that picks and does not weigh, the kept scores normalised
    and scaled) over ONE CHIP'S SHARE of the experts (``afmoe.held``), an
    expert ``relu(h W_up)^2 W_down`` (two matrices), the shared expert the
    same at ``shared_intermediate_size``, added unweighted.

    "mamba1" (AI21 Jamba: Mamba-1's selective scan over ``d_inner`` =
    ssm_inner_size channels, N = ssm_state_size, R = ssm_dt_rank), h = N_l(x):
        [u | z] = h W_in                   widths d_inner | d_inner
        u = silu(conv_K(u) + b_conv)       the same convolution, over u alone
        [r | B | C] = u W_x                widths R | N | N
        r, B, C = RMSNorm(r), RMSNorm(B), RMSNorm(C)    own gains
            (``ssm_inner_norms``; Mamba's own form has none)
        dt = softplus(r W_dt + b_dt);  A = -exp(a_log)    [d_inner, N]
        per channel c, S [d_inner, N] float32, S_0 = 0:
            S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] u_t[c] B_t[n]
            y_t[c]    = sum_n S_t[c, n] C_t[n] + D[c] u_t[c]
        mixer = (y * silu(z)) W_out        no gated norm
    A decay for every (channel, state dim) pair: no matrix form, so the
    recurrence is a KERNEL on the vector unit
    (``ops/pallas/selective_scan.py``: ``selective_scan_chunk`` walks a
    chunk's rows with a channel tile's state in registers,
    ``mamba1_decode_step`` is ``ssm_decode_step``'s sibling with the
    exponentials inside); the skip ``D u`` and the gate are XLA's, OUTSIDE
    both, fused into the out-projection's input.

    "mlp": the dense MLP as a layer of its own, ``(silu(h W_gate) * (h
    W_up)) W_down`` (``glu``) or ``act(h W_up) W_down``.  A published Jamba
    layer ``x + mixer(N1(x))``, ``x + mlp(N2(x))`` is TWO layers here.

Runs of ``[mamba1, mlp]`` pairs are ROLLED (:func:`runs`: a ``lax.scan`` a
run in the chunk programs and in the decode step alike), their weights
stacks by kind that BOTH read in place: the decode kernels take a stack and
the layer's index (``fused_norm_qkv(layer=)`` and its siblings), so a dense
layer's weights are resident ONCE and a program's size does not grow with
the depth.  The other kinds stay unrolled, with buffers of their own.

Each piece is ONE function here (:func:`ssm_split`, :func:`ssm_inputs`,
:func:`ssm_chunk_scan` / ``ops/pallas/decode.py:ssm_step_ref``,
:func:`gated_group_norm`; ``kda_mla.short_conv`` is the convolution,
``kda_mla.gqa_split``, ``afmoe.attend`` / ``flash_decode`` the attention
(the per-head kind of that module without its gate), ``afmoe.mlp`` /
``afmoe.fused_experts`` the expert block) and the three forwards call them.
The parameters are stacks by KIND (``params["ssm"]`` ``[mamba2 layers,
...]``, ``params["gqa"]`` ``[attention layers, ...]``, ``params["layers"]``
``[expert layers, ...]`` with the stacked routed experts under ``mlp``) and
one stack of the layers' norms (``params["norms"]`` ``[layers, D]``).

The experts' widths are stored PADDED with zero columns (``w_up``) and zero
rows (``w_down``), the ROUTED experts' to whole 128-lane tiles
(:data:`LANE_TILE`: 1,856 -> 1,920 = 15 of them; 1,856 is 14.5, and the
decode kernel and the grouped matmul tile a weight's width in 128-lane blocks
that divide it: ``ops/pallas/decode.py:moe_expert_block``), the SHARED
expert's to whole :data:`WIDTH_TILE`\\ s (3,712 -> 4,096: 3,712 is 29 lane
tiles, a prime, which ``fused_mlp`` could only take a tile at a time or
whole); the stacked experts are resident ONCE (a padded second copy for the
kernels would not fit).  ``relu(0)^2 = 0``: the pad adds nothing.  Until PR
67 the routed experts were stored 2,048 wide too, for the chip's own grouped
matmul (``jax.lax.ragged_dot`` works a width in the largest of 128 / 256 /
512 that divides it: 9.3 ms a call at 1,920 columns where 2,048 took 3.1);
no serving program runs ``ragged_dot`` since PR 64
(``ops/pallas/grouped_matmul.py``), and the callers that keep it
(``layer=None``, a mesh that splits ``tp`` / ``sp``, a gradient) are not
served for this model, so the 512 rule survives for the routed experts only
as this note: whoever brings that path back for a width of 15 lane tiles
pays three times the call.

Cache (``serving/cache_kind.py:FullPagesAndState``): a mamba2 layer keeps,
for each SLOT, its state and the convolution's tail (the last K - 1 rows of
``xBC``), fixed, never paged, read as zeros by a chunk at position 0.  The
state is kept as ``ops/pallas/decode.py:ssm_state_pack`` lays it out ([H /
pk, N, pk P] float32: the state dim down the sublanes, ``pk`` heads' values
across the 128 lanes); :func:`state_shapes` says so to the cache kind.  The
attention layers keep per-head K and V rows in pages, THOSE layers only
(``cfg.cache_layers``).  A prefill chunk runs the recurrence in its chunked
(SSD) form over blocks of ``ssm_chunk`` rows and carries state and tail; pad
rows of its bucket get ``dt = 0`` (no decay, no input) and leave both as of
the last REAL row.  A decode step updates the state of the LIVE rows in
place (``ssm_decode_step``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import afmoe
from deepspeed_tpu.models.afmoe import F32, refuse_parallel, rms
from deepspeed_tpu.models.kda_mla import _pad_cols, gqa_split, short_conv
from deepspeed_tpu.ops.pallas.decode import (ssm_heads_per_tile,
                                             ssm_state_pack,
                                             ssm_state_unpack)
from deepspeed_tpu.ops.pallas.selective_scan import (mamba1_decode_step,
                                                     mamba1_pack,
                                                     mamba1_tile,
                                                     selective_scan_chunk)

HI = jax.lax.Precision.HIGHEST
# the ROUTED experts' stored widths are whole multiples of the lane tile
# (module docstring): the least the decode kernel and the grouped matmul can
# tile, 6.25% fewer bytes and FLOPs than the 2,048 they were stored at
LANE_TILE = 128
# the SHARED expert's stored width is a whole multiple of this: 3,712 (29
# lane tiles) -> 4,096, which ``fused_mlp``'s tile divides.  (It was the
# routed experts' too while the chunk programs ran ``ragged_dot``, whose
# widest tile it is: 9.3 ms a call of a 1,024-row chunk's at 1,920 columns
# where 2,048 took 3.1, my chip run, PR 63, PERF.md section 6)
WIDTH_TILE = 512
GQA_IN = ("wq", "wk", "wv")       # an attention layer's projections of ``h``
# a kind's stack in the parameters (an experts layer: ``layers``/``mlp``)
STACK = {"mamba2": "ssm", "mamba1": "ssm1", "full_attention": "gqa",
         "mlp": "mlp"}
PAIR = ("mamba1", "mlp")          # the layers a rolled run repeats
# rows the fused kernels of a mamba1 / mlp layer keep resident in VMEM: a
# decode step's slots, or a chunk's bucket (the cell's 256 of 2,560)
KERNEL_ROWS = 256


def cache_key(cfg) -> str:
    """The cache entry whose dtype the stream takes."""
    return "k"


def kinds(cfg):
    """(kind, index among the layers of its kind) for each layer."""
    seen: Dict[str, int] = {}
    out = []
    for t in cfg.layer_types:
        out.append((t, seen.get(t, 0)))
        seen[t] = seen.get(t, 0) + 1
    return out


def count(cfg, kind: str) -> int:
    return cfg.layer_types.count(kind)


def ssm_kind(cfg) -> str:
    """The state-space kind of the model's layers (it has one)."""
    return "mamba1" if "mamba1" in cfg.layer_types else "mamba2"


def runs(cfg):
    """The layers in order as (first layer, pairs): ``pairs`` >= 2 a run of
    that many ``[mamba1, mlp]`` pairs, ROLLED; 0 one layer, unrolled."""
    out, l, L = [], 0, cfg.num_layers
    while l < L:
        n = 0
        while cfg.layer_types[l + 2 * n:l + 2 * n + 2] == PAIR:
            n += 1
        if n >= 2:
            out.append((l, n))
            l += 2 * n
        else:
            out.append((l, 0))
            l += 1
    return out


def padded_width(width: int, tile: int = LANE_TILE) -> int:
    """``width`` rounded up to whole ``tile``\\ s: a routed expert's stored
    width (:data:`LANE_TILE`), the shared expert's at :data:`WIDTH_TILE`."""
    return -(-width // tile) * tile


def shared_width(cfg) -> int:
    return cfg.shared_intermediate_size or (cfg.intermediate_size
                                            * cfg.num_shared_experts)


def ssm_sizes(cfg):
    """(H, P, G, N, inner width H P, convolved channels H P + 2 G N, heads a
    lane tile of the kept state)."""
    H, P, G, N = (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state_size)
    return H, P, G, N, H * P, H * P + 2 * G * N, ssm_heads_per_tile(H, P, G)


def chunk_rows(cfg) -> int:
    """Rows under which a prefill chunk's bucket saves no work: one block of
    the chunked scan."""
    return cfg.ssm_chunk


def state_shapes(cfg, num_slots: int):
    """Per-slot state of the mamba2 (or mamba1) layers: ``state`` float32
    (as ``ssm_state_pack`` lays it out; a mamba1 layer's ``d_inner``
    channels are that many heads of one value) and ``tail`` (the stream's
    dtype) shapes."""
    if ssm_kind(cfg) == "mamba1":
        di, W = cfg.ssm_inner_size, mamba1_tile(cfg.ssm_inner_size)
        n = count(cfg, "mamba1")
        return ((n, num_slots, di // W, cfg.ssm_state_size, W),
                (n, num_slots, cfg.ssm_conv_kernel - 1, di))
    n = count(cfg, "mamba2")
    H, P, _, N, _, C, pk = ssm_sizes(cfg)
    return ((n, num_slots, H // pk, N, pk * P),
            (n, num_slots, cfg.ssm_conv_kernel - 1, C))


def slot_state_bytes(cfg, dtype) -> int:
    """Bytes of :func:`state_shapes` for ONE slot, the tail in ``dtype``."""
    state, tail = state_shapes(cfg, 1)
    return math.prod(state) * 4 + math.prod(tail) * jnp.dtype(dtype).itemsize


# ----------------------------------------------------------------------
# parameters
# ----------------------------------------------------------------------
def init_params(cfg, rng, dtype=F32) -> Dict[str, Any]:
    """Seeded weights: the repo's uniform init (+- fan_in^-0.5) for every
    matrix, the convolution's filters and bias +- K^-0.5 (a depthwise
    filter's fan-in is its taps); norm gains 1; ``a_log`` = log U(1, 16) and
    ``dt_bias`` the inverse softplus of a log-uniform step in [1e-3, 1e-1]
    floored at 1e-4 (the release's ``time_step_min`` / ``_max`` /
    ``_floor``), so a step's decay ``exp(dt a)`` runs from 0.999 (a head that
    remembers thousands of tokens) to about 0.2 (one that forgets in three);
    ``D`` = 1; the selection bias normal x 0.01 (zeros would leave the bias
    path untested; the release's bias is what BALANCES its experts' load,
    and beside sigmoid scores that spread by 0.14 here the 0.05 of
    ``afmoe.init_params`` un-balances it: an expert's load from 0.0 to 5
    times the mean, a chip's share of the choices 45-55% a layer by the
    seed; at 0.01 the bias still changes the six chosen of every second
    token and the loads stay within 0.5-1.6); the token embedding normal x 1
    (``kda_mla.init_params``: this form has no embedding multiplier; a TIED
    one normal x ``D^-0.5``, the head's scale, which at 2,560 is the 0.02 of
    a release's ``initializer_range``: it is also the head).  A mamba1
    layer: ``a_log[c, n] = log(n + 1)`` (Mamba's S4D-real init), ``dt_bias``
    as above a CHANNEL, ``D`` = 1, the inner norms' gains 1.  EVERY
    projection that writes the residual stream (the Mamba and the attention
    out-projections, the experts' and the shared expert's down projections)
    is seeded at ``fan_in^-0.5 / sqrt(num_layers)``: the release's
    ``rescale_prenorm_residual`` (GPT-2's rule: a residual out-projection
    over the root of the residual layers, ONE a layer in this form), applied
    to all of them alike."""
    D, V = cfg.hidden_size, cfg.vocab_size
    N, K = cfg.ssm_state_size, cfg.ssm_conv_kernel
    keys = iter(jax.random.split(rng, 32))
    uni = lambda shape, fan_in: jax.random.uniform(
        next(keys), shape, dtype, -fan_in ** -0.5, fan_in ** -0.5)
    out = lambda shape, fan_in: uni(shape, fan_in * cfg.num_layers)
    params: Dict[str, Any] = {
        "embed": {"tok": jax.random.normal(next(keys), (V, D), dtype)},
        "norms": {"scale": jnp.ones((cfg.num_layers, D), dtype)},
        "final_norm": {"scale": jnp.ones((D,), dtype)}}
    if cfg.tie_embeddings:
        params["embed"]["tok"] = params["embed"]["tok"] * D ** -0.5
    else:
        params["lm_head"] = jax.random.normal(next(keys), (D, V),
                                              dtype) * D ** -0.5

    def dt_bias(shape):
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            next(keys), shape, F32, jnp.log(1e-3), jnp.log(1e-1))), 1e-4)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    L = count(cfg, "mamba2")
    if L:
        H, _, _, _, di, C, _ = ssm_sizes(cfg)
        params["ssm"] = {
            "w_in": uni((L, D, di + C + H), D),
            "conv": uni((L, C, K), K), "conv_b": uni((L, C), K),
            "dt_bias": dt_bias((L, H)),
            "a_log": jnp.log(jax.random.uniform(
                next(keys), (L, H), F32, 1.0, 16.0)).astype(dtype),
            "d_skip": jnp.ones((L, H), dtype),
            "o_norm": jnp.ones((L, di), dtype), "wo": out((L, di, D), di)}
    L = count(cfg, "mamba1")
    if L:
        di, R = cfg.ssm_inner_size, cfg.ssm_dt_rank
        params["ssm1"] = {
            "w_in": uni((L, D, 2 * di), D),
            "conv": uni((L, di, K), K), "conv_b": uni((L, di), K),
            "w_x": uni((L, di, R + 2 * N), di), "w_dt": uni((L, R, di), R),
            "dt_bias": dt_bias((L, di)),
            "a_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=F32)),
                                      (L, di, N)).astype(dtype),
            "d_skip": jnp.ones((L, di), dtype), "wo": out((L, di, D), di)}
        if cfg.ssm_inner_norms:
            params["ssm1"].update(dt_norm=jnp.ones((L, R), dtype),
                                  b_norm=jnp.ones((L, N), dtype),
                                  c_norm=jnp.ones((L, N), dtype))
    L = count(cfg, "mlp")
    if L:
        F = cfg.intermediate_size
        params["mlp"] = {"w_up": uni((L, D, F), D),
                         "w_down": out((L, F, D), F)}
        if cfg.glu:
            params["mlp"]["w_gate"] = uni((L, D, F), D)
    L = count(cfg, "full_attention")
    if L:
        M, Mkv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
        params["gqa"] = {"wq": uni((L, D, M), D), "wk": uni((L, D, Mkv), D),
                         "wv": uni((L, D, Mkv), D), "wo": out((L, M, D), M)}
    L = count(cfg, "experts")
    if L:
        E, R = cfg.num_experts, cfg.moe_router_experts

        def two(lead, width, tile=LANE_TILE):
            # drawn at the padded width and zeroed past the real one: no
            # unpadded copy beside the 5 GB of a real model's experts
            wide = padded_width(width, tile)
            real = jnp.arange(wide) < width
            return {"w_up": uni(lead + (D, wide), D) * real.astype(dtype),
                    "w_down": out(lead + (wide, D), width)
                    * real[:, None].astype(dtype)}

        mlp = {"gate_w": uni((L, D, R), D), **two((L, E),
                                                  cfg.intermediate_size)}
        if cfg.moe_select_bias:
            mlp["gate_bias"] = jax.random.normal(next(keys), (L, R),
                                                 dtype) * 0.01
        if cfg.num_shared_experts:
            mlp["shared"] = two((L,), shared_width(cfg), WIDTH_TILE)
        params["layers"] = {"mlp": mlp}
    return params


def layer_params(cfg, params, l: int):
    """(layer ``l``'s norm gain, its slice of its kind's stack (an experts
    layer: without the stacked routed experts, which stay whole), its kind,
    its index among that kind)."""
    kind, i = kinds(cfg)[l]
    if kind == "experts":
        stack = {k: v for k, v in params["layers"]["mlp"].items()
                 if k not in ("w_up", "w_down")}
    elif kind in PAIR:            # read in place, by the layer's index
        stack = {}
    else:
        stack = params[STACK[kind]]
    return (params["norms"]["scale"][l],
            jax.tree.map(lambda a: a[i], stack), kind, i)


# ----------------------------------------------------------------------
# the Mamba-2 pieces
# ----------------------------------------------------------------------
def ssm_split(cfg, y):
    """The in-projection's columns ``y`` [..., H P + (H P + 2 G N) + H (+
    pad)] as (z [..., H P], xBC [..., H P + 2 G N], dt [..., H])."""
    H, _, _, _, di, C, _ = ssm_sizes(cfg)
    return y[..., :di], y[..., di:di + C], y[..., di + C:di + C + H]


def ssm_inputs(cfg, a, c, dt_raw):
    """From the convolved rows ``c`` [..., H P + 2 G N] float32 and the step
    logits ``dt_raw`` [..., H] to the recurrence's inputs, float32: x [...,
    H, P], B and C [..., G, N], dt [..., H] > 0 (softplus of the logits plus
    ``dt_bias``, no clamp), the decay rates a [H] < 0."""
    H, P, G, N, di, _, _ = ssm_sizes(cfg)
    lead = c.shape[:-1]
    x = c[..., :di].reshape(lead + (H, P))
    Bm = c[..., di:di + G * N].reshape(lead + (G, N))
    Cm = c[..., di + G * N:].reshape(lead + (G, N))
    dt = jax.nn.softplus(dt_raw.astype(F32) + a["dt_bias"].astype(F32))
    return x, Bm, Cm, dt, -jnp.exp(a["a_log"].astype(F32))


def ssm_chunk_scan(S, x, dt, a, Bm, Cm, block: int):
    """The selective state space for ``s`` tokens of one sequence, chunked
    (the SSD form): ``S`` [H, P, N] float32; x [s, H, P]; dt [s, H]; a [H];
    Bm, Cm [s, G, N]; ``s`` a multiple of ``block`` or less than it.
    Returns (S, y [s, H, P]) WITHOUT the skip ``D x``.

    Inside a block, with ``L_t`` the cumulative log-decay ``sum_{j <= t}
    dt_j a`` and ``S_0`` the state before it,

        S_t = e^{L_t} S_0 + sum_{i <= t} e^{L_t - L_i} (dt_i x_i) B_i^T
        y_t = e^{L_t} S_0 C_t + sum_{i <= t} e^{L_t - L_i} (C_t . B_i) dt_i x_i

    so a block is three matrix products and the states follow one block
    after another.  Every exponent is a difference ``L_t - L_i`` with ``i <=
    t``, masked BEFORE the exponential: at most 0.  A row with ``dt = 0``
    (a pad row) neither decays the state nor adds to it."""
    s, H, P = x.shape
    G = Bm.shape[1]
    Q = min(block, s)
    n = s // Q
    assert s == n * Q, (s, Q)
    blk = lambda t: t.reshape((n, Q) + t.shape[1:])
    t_i = jnp.arange(Q)
    low = t_i[:, None] >= t_i[None, :]                           # i <= t
    dot = lambda spec, u, v: jnp.einsum(spec, u, v, precision=HI)
    heads = lambda t: jnp.repeat(t, H // G, axis=1)              # [Q, H, N]

    def step(S, xs):
        xd, la, Bq, Cq = xs
        Lc = jnp.cumsum(la, axis=0)                              # [Q, H]
        E = jnp.exp(jnp.where(low[None], Lc.T[:, :, None] - Lc.T[:, None, :],
                              -jnp.inf))                         # [H, t, i]
        W = jnp.repeat(dot("tgn,ign->gti", Cq, Bq), H // G, axis=0) * E
        y = dot("hti,ihp->thp", W, xd) \
            + dot("thn,hpn->thp", heads(Cq) * jnp.exp(Lc)[..., None], S)
        last = Lc[-1]                                            # [H]
        S = S * jnp.exp(last)[:, None, None] + dot(
            "ihp,ihn->hpn", xd * jnp.exp(last - Lc)[..., None], heads(Bq))
        return S, y

    S, y = jax.lax.scan(step, S, (blk(x * dt[..., None]), blk(dt * a),
                                  blk(Bm), blk(Cm)))
    return S, y.reshape(s, H, P)


def gated_group_norm(cfg, a, y, z):
    """The gated output norm: ``y * silu(z)`` normed over each of the G
    groups of H P / G channels (the gate BEFORE the norm), times the gain;
    y [..., H P] float32, z [..., H P] -> [..., H P] in the weights' dtype
    (the input of ``wo``)."""
    G = cfg.ssm_groups
    g = y * jax.nn.silu(z.astype(F32))
    g = g.reshape(g.shape[:-1] + (G, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + cfg.norm_eps)
    return (g.reshape(y.shape) * a["o_norm"].astype(F32)).astype(
        a["wo"].dtype)


# ----------------------------------------------------------------------
# the Mamba-1 pieces, each on layer ``i`` (an index, may be traced: a rolled
# run's counter) of the stacks ``a`` = ``params["ssm1"]``, read in place
# ----------------------------------------------------------------------
def _layer(t, i):
    return jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False)


def norm_after(cfg, params, norms, l):
    """The gain of the norm that FOLLOWS layer ``l`` (a traced ``l`` lies
    inside a rolled run, which an mlp layer ends): the next layer's, or the
    final one."""
    last = isinstance(l, int) and l + 1 >= cfg.num_layers
    return params["final_norm"]["scale"] if last else norms[l + 1]


def _at(a, name: str, i):
    return _layer(a[name], i)


def mamba1_rates(a_log, W: int):
    """``A = -exp(a_log)`` as the kernels read it: ``a_log`` [..., d_inner,
    N] (one layer's, or every layer's) -> [..., d_inner / W, N, W] float32."""
    return -jnp.exp(mamba1_pack(a_log.astype(F32), W))


def mamba1_inputs(cfg, a, i, c):
    """From the convolved rows ``c`` [..., d_inner] float32 to the
    recurrence's inputs, float32: dt [..., d_inner] > 0 (softplus of the
    bottleneck's projection plus ``dt_bias``, no clamp), B and C [..., N]."""
    R, N, eps = cfg.ssm_dt_rank, cfg.ssm_state_size, cfg.norm_eps
    w_x, w_dt = _at(a, "w_x", i), _at(a, "w_dt", i)
    rbc = jnp.dot(c.astype(w_x.dtype), w_x, preferred_element_type=F32)
    r, Bm, Cm = rbc[..., :R], rbc[..., R:R + N], rbc[..., R + N:R + 2 * N]
    if cfg.ssm_inner_norms:
        r, Bm, Cm = (rms(t, _at(a, n, i), eps) for t, n in (
            (r, "dt_norm"), (Bm, "b_norm"), (Cm, "c_norm")))
    dt = jnp.dot(r.astype(w_dt.dtype), w_dt, preferred_element_type=F32)
    return jax.nn.softplus(dt + _at(a, "dt_bias", i).astype(F32)), Bm, Cm


def mamba1_close(a, i, y, u, z):
    """The skip and the gate: ``(y + D u) * silu(z)`` in the weights' dtype
    (the input of ``wo``); y, u float32 [..., d_inner]."""
    g = (y + _at(a, "d_skip", i).astype(F32) * u) * jax.nn.silu(z.astype(F32))
    return g.astype(a["wo"].dtype)


def _row_impl(rows: int, impl: Optional[str]) -> Optional[str]:
    """The fused kernels keep their rows resident in VMEM: a chunk of more
    than :data:`KERNEL_ROWS` takes their XLA forms (which copy a layer's
    slice out of its stack in front of each product)."""
    return impl if rows <= KERNEL_ROWS else "xla"


def mamba1_layer(cfg, a, i, x, gain, next_gain, recur, impl=None):
    """Mamba1 layer ``i`` on the rows ``x`` [rows, D] (a chunk's tokens, or a
    decode step's batch): the norm and ``W_in`` (``fused_norm_qkv``), then
    ``recur(raw u [rows, d_inner]) -> (convolved u, y, aux)``, the forward's
    own convolution and recurrence over its cache, then skip, gate, ``W_out``,
    the residual add and the NEXT layer's norm (``fused_proj_norm``), the
    two matrices read in place at layer ``i`` of their stacks.  Returns (x,
    the stream normed by ``next_gain``, aux)."""
    from deepspeed_tpu.ops.pallas.decode import (fused_norm_qkv,
                                                 fused_proj_norm)

    di, kw = cfg.ssm_inner_size, dict(
        kind="rmsnorm", eps=cfg.norm_eps, layer=i,
        impl=_row_impl(x.shape[0], impl))
    uz = fused_norm_qkv(x, gain, None, a["w_in"], None, **kw)
    u, y, aux = recur(uz[:, :di])
    x, h = fused_proj_norm(mamba1_close(a, i, y, u, uz[:, di:]), x, a["wo"],
                           None, next_gain, None, **kw)
    return x, h, aux


def mlp_layer(cfg, m, i, x, h, impl=None):
    """Mlp layer ``i`` (of the stacks ``m`` = ``params["mlp"]``, read in
    place) on the rows ``x`` [rows, D] and their normed form ``h``: ``x +
    mlp(h)``."""
    from deepspeed_tpu.ops.pallas.decode import fused_mlp

    return fused_mlp(h, x, m["w_up"], m["w_down"], m.get("w_gate"),
                     act=cfg.activation, layer=i,
                     impl=_row_impl(x.shape[0], impl))


def chunk_recur(cfg, a, i, state, tail, kept, valid_len):
    """:func:`mamba1_layer`'s ``recur`` for a prefill chunk of one slot
    (``state`` [layers, 1, T, N, W], ``tail`` [layers, 1, K - 1, d_inner]):
    aux = (state, tail) as of the last REAL row."""
    def recur(raw):
        s, W = raw.shape[0], state.shape[-1]
        with jax.named_scope("ssm_conv"):
            c, t1 = short_conv(raw[None], jnp.where(kept, _layer(tail, i), 0),
                               _at(a, "conv", i), valid_len,
                               bias=_at(a, "conv_b", i))
        u = c[0]
        dt, Bm, Cm = mamba1_inputs(cfg, a, i, u)
        # a pad row: dt = 0, so the state neither decays nor takes it in
        dt = jnp.where((jnp.arange(s) < valid_len)[:, None], dt, 0.0)
        with jax.named_scope("selective_scan"):
            S1, y = selective_scan_chunk(
                jnp.where(kept, _layer(state, i)[0], 0.0), u, dt,
                mamba1_rates(_at(a, "a_log", i), W), Bm, Cm)
        return u, y, (jax.lax.dynamic_update_index_in_dim(state, S1[None],
                                                          i, 0),
                      jax.lax.dynamic_update_index_in_dim(tail, t1, i, 0))
    return recur


# ----------------------------------------------------------------------
# forwards 1 and 2: no cache (CausalLM.apply), and a prefill chunk on one
# slot's views (state carried in and out)
# ----------------------------------------------------------------------
def apply_layers(cfg, params, x, mesh=None):
    """The layer stack on ``x`` [B, S, D], positions ``0 .. S - 1``: every
    sequence a chunk at position 0 on an empty cache of its own."""
    refuse_parallel(cfg, mesh, "CausalLM.apply")
    B, S, _ = x.shape
    Q = cfg.ssm_chunk
    pad = -S % Q if S > Q else 0           # whole blocks (pad rows idle)
    if ssm_kind(cfg) == "mamba1":          # the scan kernel's blocks of rows
        pad = -S % 8
    state, tail = state_shapes(cfg, 1)
    kv = (count(cfg, "full_attention"), 1, cfg.num_kv_heads, S + pad,
          cfg.head_dim)

    def one(xb):
        xb = jnp.pad(xb, ((0, pad), (0, 0)))[None]
        cache = {"k": jnp.zeros(kv, x.dtype), "v": jnp.zeros(kv, x.dtype),
                 "state": jnp.zeros(state, F32),
                 "tail": jnp.zeros(tail, x.dtype)}
        return cached_layers(cfg, params, xb, cache, 0, S)[0][0, :S]

    return jax.lax.map(one, x)


def cached_layers(cfg, params, x, cache, start, valid_len):
    """The layer stack on a chunk ``x`` [1, s, D] at positions ``start ..``
    over ONE slot's views (``state`` [mamba2 layers, 1, H / pk, N, pk P]
    float32, ``tail`` [mamba2 layers, 1, K - 1, H P + 2 G N], ``k`` and ``v``
    [attention layers, 1, Hkv, positions, Dh]: what
    ``cache_kind.FullPagesAndState.view`` slices out); only the first
    ``valid_len`` rows are real.  A chunk at position 0 starts from a zero
    state whatever the slot held.  Returns (x, views)."""
    B, s, _ = x.shape
    assert B == 1, "a chunk program prefills one slot"
    start = jnp.asarray(start, jnp.int32)
    pos = start + jnp.arange(s)
    real = jnp.arange(s) < valid_len
    state, tail = cache["state"], cache["tail"]
    k_full, v_full = cache["k"], cache["v"]
    kept = (start != 0)
    experts = afmoe._experts(params)
    pk = ssm_sizes(cfg)[-1] if ssm_kind(cfg) == "mamba2" else 0
    norms, order = params["norms"]["scale"], kinds(cfg)

    def mamba1(l, i, x, state, tail):
        """Layer ``l`` = mamba1 layer ``i`` (either may be traced) on the
        chunk's rows ``x`` [s, D]."""
        a = params["ssm1"]
        return mamba1_layer(
            cfg, a, i, x, norms[l], norm_after(cfg, params, norms, l),
            chunk_recur(cfg, a, i, state, tail, kept, valid_len))

    for l, pairs in runs(cfg):
        if pairs:
            # a run of [mamba1, mlp] pairs, rolled: pair t is layers l + 2 t
            # and l + 2 t + 1, the stacks read in place by its indices
            i0, m0 = order[l][1], order[l + 1][1]

            def pair(carry, t, l=l, i0=i0, m0=m0):
                x, state, tail = carry
                x, h, (state, tail) = mamba1(l + 2 * t, i0 + t, x, state,
                                             tail)
                return (mlp_layer(cfg, params["mlp"], m0 + t, x, h), state,
                        tail), None

            (x2, state, tail), _ = jax.lax.scan(
                pair, (x[0], state, tail), jnp.arange(pairs, dtype=jnp.int32))
            x = x2[None]
            continue
        scale, a, kind, i = layer_params(cfg, params, l)
        if kind == "mamba1":
            x2, _, (state, tail) = mamba1(l, i, x[0], state, tail)
            x = x2[None]
            continue
        h = rms(x, scale, cfg.norm_eps)
        if kind == "mlp":
            x = mlp_layer(cfg, params["mlp"], i, x[0], h[0])[None]
            continue
        if kind == "mamba2":
            z, xBC, dt_raw = ssm_split(cfg, h @ a["w_in"].astype(h.dtype))
            with jax.named_scope("ssm_conv"):
                c, t1 = short_conv(xBC, jnp.where(kept, tail[i], 0),
                                   a["conv"], valid_len, bias=a["conv_b"])
            xs, Bm, Cm, dt, rate = ssm_inputs(cfg, a, c[0], dt_raw[0])
            # a pad row: dt = 0, so the state neither decays nor takes it in
            dt = jnp.where(real[:, None], dt, 0.0)
            with jax.named_scope("ssm_chunk_scan"):
                S1, y = ssm_chunk_scan(
                    jnp.where(kept, ssm_state_unpack(state[i, 0], pk), 0.0),
                    xs, dt, rate, Bm, Cm, cfg.ssm_chunk)
            state = state.at[i, 0].set(ssm_state_pack(S1, pk))
            tail = tail.at[i].set(t1)
            y = y + a["d_skip"].astype(F32)[:, None] * xs
            o = gated_group_norm(cfg, a, y.reshape(1, s, -1), z)
            out = o @ a["wo"].astype(o.dtype)
        elif kind == "full_attention":
            # per-head keys and values, unrotated: the chunk's rows join the
            # slot's view, and the queries attend every row up to their own
            q, k, v, _ = gqa_split(cfg, jnp.concatenate(
                [h @ a[n].astype(h.dtype) for n in GQA_IN], -1))
            heads = lambda t: t.transpose(0, 2, 1, 3)
            at = (i, 0, 0, start, 0)
            k_full = jax.lax.dynamic_update_slice(
                k_full, heads(k)[None].astype(k_full.dtype), at)
            v_full = jax.lax.dynamic_update_slice(
                v_full, heads(v)[None].astype(v_full.dtype), at)
            o = afmoe.attend(
                heads(q), [(k_full[i], v_full[i],
                            jnp.arange(k_full.shape[3]))],
                pos, window=0, scale=cfg.head_dim ** -0.5,
                live_keys=start + s)
            o = heads(o).reshape(B, s, -1)
            out = o @ a["wo"].astype(o.dtype)
        else:
            out = afmoe.mlp(cfg, {"mlp": a}, h, experts, i)
        x = x + out.astype(x.dtype)
    return x, {"k": k_full, "v": v_full, "state": state, "tail": tail}


# ----------------------------------------------------------------------
# forward 3: one decode step through the fused kernels
# ----------------------------------------------------------------------
def inject(cfg, params) -> Dict[str, Any]:
    """The kernel-injected view (``afmoe.inject``'s shape): per-layer dicts
    with their own buffers.  A mamba2 or attention layer: its norm gain, every
    projection of ``h`` in one ``[D, N]`` matrix ``w_in``, the small arrays
    under their own names, and ``next_norm``, the gain of the norm that
    FOLLOWS it (the next layer's, or the final one): its output projection's
    kernel norms the new stream for an experts layer behind it.  An experts
    layer: its norm gain, the router and the shared expert; the stacked
    routed experts by reference."""
    norms = params["norms"]["scale"]
    layers = []
    for l in range(cfg.num_layers):
        scale, a, kind, _ = layer_params(cfg, params, l)
        d = {"norm": scale}
        if kind == "experts":
            d.update(a)
        elif kind in PAIR:
            pass                  # its weights: the stacks below, in place
        else:
            names = ("w_in",) if kind == "mamba2" else GQA_IN
            d.update({k: v for k, v in a.items() if k not in names})
            d["w_in"] = _pad_cols(jnp.concatenate([a[k] for k in names], -1))
            d["next_norm"] = (norms[l + 1] if l + 1 < cfg.num_layers
                              else params["final_norm"]["scale"])
        layers.append(d)
    out = afmoe.inject_outer(params, layers)
    if count(cfg, "mamba1"):
        # the stacks themselves (no copy), and every layer's rates as the
        # decode kernel reads them (81,920 float32 a layer)
        out["ssm1"] = {**params["ssm1"], "rates": mamba1_rates(
            params["ssm1"]["a_log"], mamba1_tile(cfg.ssm_inner_size))}
    if count(cfg, "mlp"):
        out["mlp"] = params["mlp"]
    out["norms"] = norms
    return out


def moe_counts_zero(cfg):
    """``afmoe.moe_counts_zero`` and one more entry: (row, mamba2 or mamba1
    layer) pairs that were LIVE, and pairs whose state the decode kernel
    VISITED."""
    return afmoe.moe_counts_zero(cfg) + (jnp.zeros((2,), jnp.int32),)


def fused_layers(cfg, dparams, x, cache, pos, page_table, *, moe_live=None,
                 impl: Optional[str] = None):
    """The layer stack for one token a row: ``x`` [B, D] at per-row
    positions ``pos`` [B]; ``cache``: ``state`` [mamba2 layers, B, H / pk, N,
    pk P] and ``tail`` [mamba2 layers, B, K - 1, H P + 2 G N] by row (a row
    of the batch is a slot), ``k`` and ``v`` [attention layers, pages, Hkv,
    page, Dh] through ``page_table`` [B, columns].  ``moe_live`` [B] bool:
    the rows that decode; only their state, tail and pages move, and the
    kernels visit only them.  Returns (x, cache, counts | None)."""
    from deepspeed_tpu.ops.pallas.decode import (flash_decode,
                                                 fused_norm_qkv,
                                                 fused_proj_norm,
                                                 paged_kv_append,
                                                 ssm_decode_step)

    B = x.shape[0]
    eps = cfg.norm_eps
    state, tail = cache["state"], cache["tail"]
    k_full, v_full = cache["k"], cache["v"]
    stats = moe_counts_zero(cfg) if moe_live is not None else None
    h = None                      # the stream normed for the layer to come
    order, norms = kinds(cfg), dparams["norms"]

    def mamba1(l, i, x, state, tail, steps):
        """Layer ``l`` = mamba1 layer ``i`` (either may be traced) for one
        token a row: (x, the stream normed for layer l + 1, (state, tail,
        the (live, visited) counts))."""
        a = dparams["ssm1"]

        def recur(raw):
            old = _layer(tail, i)
            with jax.named_scope("ssm_conv"):
                c, t1 = short_conv(raw[:, None], old, _at(a, "conv", i),
                                   bias=_at(a, "conv_b", i))
            if moe_live is not None:
                t1 = jnp.where(moe_live[:, None, None], t1, old)
            u = c[:, 0]
            dt, Bm, Cm = mamba1_inputs(cfg, a, i, u)
            with jax.named_scope("selective_scan"):
                y, new, visited = mamba1_decode_step(
                    state, u, dt, a["rates"], Bm, Cm, layer=i, live=moe_live,
                    impl=impl)
            return u, y, (new, jax.lax.dynamic_update_index_in_dim(
                tail, t1, i, 0), None if steps is None else steps + jnp.stack(
                    [jnp.sum(moe_live, dtype=jnp.int32), visited]))

        return mamba1_layer(cfg, a, i, x, norms[l],
                            norm_after(cfg, dparams, norms, l), recur, impl)

    def dense(l, i, x, h):
        """Layer ``l`` = mlp layer ``i`` on the stream normed for it."""
        return mlp_layer(cfg, dparams["mlp"], i, x,
                         rms(x, norms[l], eps) if h is None else h, impl)

    for l, pairs in runs(cfg):
        kind, i = order[l]
        lp = dparams["layers"][l]
        steps = None if stats is None else stats[-1]
        if pairs:
            # a run of [mamba1, mlp] pairs, rolled: the kernels read the
            # stacks at the pair's indices
            m0 = order[l + 1][1]

            def pair(carry, t, l=l, i0=i, m0=m0):
                x, h, (state, tail, steps) = mamba1(l + 2 * t, i0 + t,
                                                    *carry)
                return (dense(l + 2 * t + 1, m0 + t, x, h), state, tail,
                        steps), None

            (x, state, tail, steps), _ = jax.lax.scan(
                pair, (x, state, tail, steps),
                jnp.arange(pairs, dtype=jnp.int32))
            h = None
        elif kind == "mamba1":
            x, h, (state, tail, steps) = mamba1(l, i, x, state, tail, steps)
        elif kind == "mlp":
            x, h = dense(l, i, x, h), None
        if kind in PAIR:
            stats = None if stats is None else stats[:-1] + (steps,)
            continue
        if kind == "experts":
            if h is None:
                h = rms(x, lp["norm"], eps)
            x, head = afmoe.fused_experts(
                cfg, dparams, lp, i, h, x, None if stats is None
                else stats[:-1], moe_live, impl)
            stats = None if stats is None else head + stats[-1:]
            h = None
            continue
        y = fused_norm_qkv(x, lp["norm"], None, lp["w_in"], None,
                           kind="rmsnorm", eps=eps, impl=impl)
        if kind == "mamba2":
            z, xBC, dt_raw = ssm_split(cfg, y)
            c, t1 = short_conv(xBC[:, None], tail[i], lp["conv"],
                               bias=lp["conv_b"])
            if moe_live is not None:
                t1 = jnp.where(moe_live[:, None, None], t1, tail[i])
            tail = tail.at[i].set(t1)
            xs, Bm, Cm, dt, rate = ssm_inputs(cfg, lp, c[:, 0], dt_raw)
            yk, state, visited = ssm_decode_step(
                state, xs, dt, rate, Bm, Cm, layer=i, live=moe_live,
                impl=impl)
            if stats is not None:
                stats = stats[:-1] + (stats[-1] + jnp.stack(
                    [jnp.sum(moe_live, dtype=jnp.int32), visited]),)
            yk = yk + lp["d_skip"].astype(F32)[:, None] * xs
            ctx = gated_group_norm(cfg, lp, yk.reshape(B, -1), z)
        else:
            # the row's K and V join its pages, then the paged kernel: every
            # position up to its own, a key-value head serving its group
            q, k, v, _ = gqa_split(cfg, y)
            k_full, v_full = paged_kv_append(k_full, v_full, k, v, pos,
                                             page_table, layer=i, impl=impl)
            ctx = flash_decode(q, k_full, v_full, pos,
                               sm_scale=cfg.head_dim ** -0.5, layer=i,
                               page_table=page_table, live=moe_live,
                               impl=impl).reshape(B, -1)
        x, h = fused_proj_norm(ctx, x, lp["wo"], None, lp["next_norm"], None,
                               kind="rmsnorm", eps=eps, impl=impl)
    return x, {"k": k_full, "v": v_full, "state": state, "tail": tail}, stats
