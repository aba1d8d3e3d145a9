"""Plain reference forward of dots3-note-prev (dots-studio, ``model_type:
dots3_note``; config.json), ONE CHIP'S SHARE of its language model as the
configuration file states: float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching, independent of ``deepspeed_tpu.models``, ``deepspeed_tpu.moe`` and
``deepspeed_tpu.ops``.  Written from the equations of ISSUE 52, not from the
package's code.  ``N(.)`` is RMSNorm with its own gain, eps ``rms_norm_eps``
(1e-5); layer l's MLP is dense where l < ``first_k_dense_replace``:

    x = embed[tokens]                                no multiplier
    per layer:  x = x + attn(N_in(x));  x = x + mlp(N_post(x))
    logits = N_f(x) W_head                           the chip's vocabulary rows

    ``full_attention`` (H = 128 heads of n + r = 128 + 64, values v = 128,
    latent 512, query rank 1,024, base 8e7), h = N_in(x)_t:
        c_q = a_q N_q(h W_qa)                 [1024]   a_q  = sqrt(5120 / 1024)
        q_h = c_q W_qb -> [q_n,h | q_r,h]
        [c_raw | k_r] = h W_kva;  c = a_kv N_kv(c_raw)  [512]
                                              a_kv = sqrt(5120 / 512)
        q_r,h <- R_t q_r,h;  k_r <- R_t k_r   plain RoPE: pairs (2i, 2i + 1)
                                              turned by t base^(-2i / r)
        [k_n,h | v_h] = c W_kvb
        the indexer (G = 64 heads of d = 128, the first r = 64 values of each
        rotated by the same R_t):
            qI_g = c_q W_Iq  [G x d];  kI = LN(h W_Ik) [d] (gain AND bias);
            w = h W_Iw G^-0.5 d^-0.5  [G]
            I(t, j) = sum_g w_g(t) relu(qI_g(t) . kI(j)),   j <= t
            S_t = the 2,048 keys j <= t of largest I(t, j); all of them while
                  t + 1 <= 2,048
        score_h(t, j) = (q_n,h . k_n,h(j) + q_r,h . k_r(j)) / sqrt(n + r),
            softmax over j in S_t ONLY
        a = concat_h(sigmoid(h W_g)_h sum_{j in S_t} p_h(t, j) v_h(j)) W_o
      DECOMPRESSED (per-head keys and values), never the absorbed form.

    ``sliding_attention`` (H = 64 heads of 192 + 64, values 128, latent
    1,024, query rank 1,024, base 5e4): the same without an indexer, a_q =
    a_kv = sqrt(5), keys 0 <= t - j < ``sliding_window_size`` (513: the
    token and its 512 predecessors), its own W_g [5120, 64].

    mlp: dense SwiGLU, or shared(h) + the routed part:
        s = sigmoid(h W_r)  over the router's 256;  idx = top-8 of s + b
        (the bias picks and does not weigh; no groups)
        w = s[idx] / (sum s[idx] + 1e-20) * routed_scaling_factor (1.0)
        routed = sum over idx HELD HERE of w_e expert_e(h)
      (``reference/trinity.py``'s ``route`` and ``expert_close`` under
      "no_post_norm": that file is benchmark code and not the package's.)

What the catalog's ``config`` does not carry is listed in the configuration
file under ``assumed``.  Departures from the published description: float32
throughout; seeded weights; the indexer's FP8 and Hadamard rotation left out
(an orthogonal rotation of both factors leaves the dot products as they are).

``routing=`` / ``selection=`` replace the reference's own top-8 / its own
selected keys by the program's; ``variant=`` breaks one equation on purpose,
for ``tools/dots3_agreement.py``'s negative controls.

Two discrete choices, two near-tie rules.  (1) The router's is
``reference/trinity.py``'s (``NEAR_TIE`` imported, its ``SWAPS`` applied by
its own ``route``, which this file calls: the last two
chosen against the first two not chosen, singly or both, within 0.005, where
a held expert is among them), through Kimi-Linear's router form (trinity's
``route`` with a selection bias): top-8 of 256 is discontinuous, and where
the 8th and 9th selection scores lie within what a bf16 stream moves them,
float32 and bf16 evaluations of the same equations may each pick either.
(2) The indexer's is new.  Top-2,048 of I(t, .) is discontinuous in the same
way, and the program scores in bfloat16 products summed in float32: a key
whose float32 score lies within ``INDEX_BAND`` of the 2,048th largest may be
in or out of S_t.  A row whose served token is not this file's own best is
ALSO evaluated with, at each full layer where such keys exist, every in-band
key left OUT (S_t shrinks to the keys above the band) and every in-band key
taken IN (S_t grows by the keys inside it): the two ends of what the band
admits (:data:`SELECT_MODES`); the row reports the admissible evaluation
under which the served token sits highest.  ``INDEX_BAND`` is absolute, in
units of I, whose spread over a row's keys is about 0.4 at the published
widths on seeded weights; ``tools/dots3_agreement.py`` measures on the chip
how far inside the reference's own ranking the program's selections differ
(PERF.md section 4, dots3-note-L5-ep16) and the band is that, not more.  A
selection alternative and a router exchange are not combined in one row.

Memory: layer by layer on weights cast up to float32 one layer at a time; a
sequence is cut to the shortest of :data:`LENGTHS` that holds its last row
read (rows past it reach no row that is read); the selection of a full layer
is a bit mask [S, S / 8]; attention in groups of ``HEAD_GROUP`` heads and
query blocks of ``QUERY_BLOCK`` rows (16 heads x 256 rows asked for 6.0 GB
of temporaries at 49,152 keys where 5.5 were free: my chip run, PR 52), the
index scores in groups of ``INDEX_GROUP`` heads (64 heads x 128 rows x
32,768 keys of float32 would be 1.1 GB), so that 32,768 positions (and
49,152: my chip run, PR 52) fit beside 5.2 GB of bf16 weights; of the program it knows only the NAMES in its weight
tree.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.trinity import (NEAR_TIE, REPLAY_ROWS, _below_best,
                                          _capacity, _up, expert_close,
                                          outer_weights, rms_norm, route,
                                          swiglu)

F32 = jnp.float32
QUERY_BLOCK = 128
HEAD_GROUP = 8
INDEX_GROUP = 8
MLP_ROWS = 2048
NO_POST_NORM = ("no_post_norm",)      # trinity's close, its post-norm out
# few, because every length compiles every block anew (five lengths: 500 s of
# a cold reference check of 8 sequences, of which 400 compiling; my chip run,
# PR 52)
LENGTHS = (4096, 16384, 32768)
# how far from the 2,048th largest float32 index score a key may lie and be
# in or out of the selection (module docstring; measured: PERF.md section 4)
INDEX_BAND = 0.03
# a full layer's selection in a replay: the reference's own, every in-band
# key out, every in-band key in
SELECT_MODES = (0, 1, 2)
ROUTE_VARIANTS = frozenset({"bias_weighs", "no_route_scale"})


# ---------------------------------------------------------------------------
# sizes and weights
# ---------------------------------------------------------------------------
def _layers(config):
    """(layer, dense MLP?, "full" | "sliding", index among its kind)."""
    seen = {"full": 0, "sliding": 0}
    for l, t in enumerate(config["layer_types"]):
        kind = "sliding" if t == "sliding_attention" else "full"
        yield l, l < config["first_k_dense_replace"], kind, seen[kind]
        seen[kind] += 1


def sizes(config, kind, variant=()):
    """The static sizes of one kind of layer, hashable."""
    p = "swa_" if kind == "sliding" else ""
    D = config["hidden_size"]
    kv, rq = config[p + "kv_lora_rank"], config[p + "q_lora_rank"]
    rescale = config["apply_mla_qkv_lora_rescale"] \
        and "no_rescale" not in variant
    theta = float(config[p + "rope_theta"])
    if kind == "sliding" and "full_base" in variant:
        theta = float(config["rope_theta"])
    window = 0
    if kind == "sliding":
        window = config["sliding_window_size"] - ("window_512" in variant)
    return (("heads", config[p + "num_attention_heads"]),
            ("nope", config[p + "qk_nope_head_dim"]),
            ("rot", config[p + "qk_rope_head_dim"]),
            ("v_dim", config[p + "v_head_dim"]), ("kv_rank", kv),
            ("theta", theta), ("window", window),
            ("a_q", math.sqrt(D / rq) if rescale else 1.0),
            ("a_kv", math.sqrt(D / kv) if rescale else 1.0),
            ("eps", config["rms_norm_eps"]),
            ("index", (config["index_n_heads"], config["index_head_dim"],
                       config["index_topk"]) if kind == "full" else None))


def layer_weights(params, config, l, device):
    _, dense, kind, j = list(_layers(config))[l]
    n_dense = config["first_k_dense_replace"]
    ly = params["dense_layers" if dense else "layers"]
    i = l if dense else l - n_dense
    g = lambda *path: _up(functools.reduce(lambda t, k: t[k], path, ly)[i],
                          device)
    w = {"n_in": g("attn_norm", "scale"), "n_post": g("mlp_norm", "scale")}
    stack = params["mla" if kind == "full" else "mla_sw"]
    w.update({k: _up(v[j], device) for k, v in stack.items()})
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
# the shared parts of a token's row
# ---------------------------------------------------------------------------
def rotate(t, pos, theta, bf16_angles=False):
    """``t`` [n, ..., r] at positions ``pos`` [n]: each pair (t[2i], t[2i +
    1]) turned by ``pos * theta^(-2i / r)``."""
    r = t.shape[-1]
    inv = jnp.asarray(theta ** (-np.arange(0, r, 2, dtype=np.float64) / r),
                      F32)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    if bf16_angles:         # the precision control: angles rounded to bf16
        ang = jax.lax.reduce_precision(ang, exponent_bits=8, mantissa_bits=7)
    ang = ang.reshape((t.shape[0],) + (1,) * (t.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = t.reshape(t.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(t.shape)


def shared_rows(h, pos, w, sz, variant=()):
    """(c [n, kv] normed and scaled, k_r [n, r] rotated, c_q [n, rq] normed
    and scaled) of rows ``h`` [n, D] at positions ``pos``: what every head
    shares."""
    kv, eps = sz["kv_rank"], sz["eps"]
    cr = h @ w["wkva"]
    c = sz["a_kv"] * rms_norm(cr[:, :kv], w["kv_norm"], eps)
    k_r = cr[:, kv:]
    if "unrotated_cache_key" not in variant:
        k_r = rotate(k_r, pos, sz["theta"], "bf16_angles" in variant)
    cq = sz["a_q"] * rms_norm(h @ w["wqa"], w["q_norm"], eps)
    return c, k_r, cq


def index_key(h, pos, w, sz, variant=()):
    """kI [n, d]: LayerNorm (gain and bias) of ``h W_Ik``, its first ``rot``
    values rotated."""
    x = h @ w["wik"]
    if "no_index_norm" not in variant:
        mu = x.mean(-1, keepdims=True)
        x = (x - mu) * jax.lax.rsqrt(((x - mu) ** 2).mean(-1, keepdims=True)
                                     + sz["eps"]) * w["ik_norm"] + w["ik_bias"]
    r = sz["rot"]
    if "unrotated_index_key" in variant:
        return x
    return jnp.concatenate([rotate(x[:, :r], pos, sz["theta"]), x[:, r:]], -1)


def index_scores(h, cq, pos, w, k_all, sz, variant=(), causal=True):
    """I [n, S]: rows ``h`` (their bottlenecks ``cq``) at ``pos`` against
    every index key ``k_all`` [S, d]; with ``causal``, -inf where the key
    (at position = its index) is past the row."""
    G, d, _ = sz["index"]
    r = sz["rot"]
    n = h.shape[0]
    q = (cq @ w["wiq"]).reshape(n, G, d)
    q = jnp.concatenate([rotate(q[..., :r], pos, sz["theta"]), q[..., r:]], -1)
    wt = (h @ w["wiw"]) * (G ** -0.5 * d ** -0.5)
    if "no_index_weight" in variant:
        wt = jnp.ones_like(wt) * (G ** -0.5 * d ** -0.5)
    bf16 = "bf16_index" in variant    # the precision control: bf16 factors
    if bf16:
        q, k_all = (jax.lax.reduce_precision(t, 8, 7) for t in (q, k_all))
    g = min(INDEX_GROUP, G)

    def group(acc, qw):
        qg, wg = qw                                         # [n, g, d], [n, g]
        s = jnp.einsum("ngd,sd->ngs", qg, k_all)
        if "no_relu" not in variant:
            s = jax.nn.relu(s)
        return acc + (s * wg[..., None]).sum(1), None

    acc, _ = jax.lax.scan(
        group, jnp.zeros((n, k_all.shape[0]), F32),
        (q.reshape(n, G // g, g, d).transpose(1, 0, 2, 3),
         wt.reshape(n, G // g, g).transpose(1, 0, 2)))
    if not causal:
        return acc
    ok = jnp.arange(k_all.shape[0])[None, :] <= pos[:, None]
    return jnp.where(ok, acc, -jnp.inf)


def select(I, pos, top_k, mode=None):
    """The selection of rows with scores ``I`` [n, S] (-inf past the row):
    bool [n, S], the ``top_k`` largest (every key ``j <= t`` while ``t + 1
    <= top_k``; an exact tie at the edge is kept), and how many keys lie
    within ``INDEX_BAND`` of the edge without being it.  ``mode`` [n] (a
    replay): 1 leaves every in-band key out, 2 takes every one in."""
    S = I.shape[1]
    causal = jnp.arange(S)[None, :] <= pos[:, None]
    if S <= top_k:
        return causal, jnp.zeros(I.shape[0], jnp.int32)
    edge = jax.lax.top_k(I, top_k)[0][:, -1:]
    full = (pos + 1 > top_k)[:, None]       # the row HAS more keys than k
    band = full & causal & (jnp.abs(I - edge) < INDEX_BAND) & (I != edge)
    keep = causal & ((I >= edge) | ~full)
    if mode is not None:
        keep = jnp.where((mode == 1)[:, None], keep & ~band, keep)
        keep = jnp.where((mode == 2)[:, None], keep | band, keep)
    return keep, band.sum(-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def _grouped(w, heads, per_head):
    """[in, H per_head] -> [H / HEAD_GROUP, in, HEAD_GROUP per_head]."""
    g = min(HEAD_GROUP, heads)
    return w.reshape(w.shape[0], heads // g, g * per_head).transpose(1, 0, 2)


def heads_of(cq, c, k_r, pos, wqb, wkvb, sz, variant=()):
    """Per-head q, k [n, G, nope + r] and v [n, G, v] of a group of heads."""
    nope = sz["nope"]
    n = cq.shape[0]
    q = (cq @ wqb).reshape(n, -1, nope + k_r.shape[1])
    q = jnp.concatenate([q[..., :nope], rotate(
        q[..., nope:], pos, sz["theta"], "bf16_angles" in variant)], -1)
    G = q.shape[1]
    kvb = (c @ wkvb).reshape(n, G, -1)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_r[:, None], (n, G, k_r.shape[1]))],
        -1)
    return q, k, kvb[..., nope:]


def _gate(a, h, w, heads, variant):
    """The headwise gate on head outputs ``a`` [n, H, v]."""
    if "no_gate" in variant:
        return a
    if "gate_wrong_head" in variant:
        # the stand-in for "the gate taken elementwise" (W_g has one column a
        # head: an elementwise gate has no weights here): head h's output
        # under head h - 1's gate
        return a * jnp.roll(jax.nn.sigmoid(h @ w["wg"]), 1, -1)[:, :, None]
    return a * jax.nn.sigmoid(h @ w["wg"])[:, :, None]


def _unpack(bits, S):
    """[n, S / 8] uint8 -> bool [n, S]."""
    b = (bits[:, :, None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
    return b.reshape(bits.shape[0], -1)[:, :S].astype(bool)


def _pack(mask):
    """bool [n, S] -> [n, ceil(S / 8)] uint8, bit i of byte j = key 8 j + i."""
    n, S = mask.shape
    m = jnp.pad(mask, ((0, 0), (0, -S % 8))).reshape(n, -1, 8)
    return (m.astype(jnp.uint8) << jnp.arange(8, dtype=jnp.uint8)).sum(
        -1).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("sz", "variant"))
def own_selection(h, cq, kI, w, n_live, *, sz, variant=()):
    """The reference's own selection of every row, packed [S, S / 8], and
    each row's count of in-band keys; a block of rows at or past ``n_live``
    (no row that is read lies there, nor sees them) attends itself."""
    sz = dict(sz)
    S = h.shape[0]
    block = min(S, QUERY_BLOCK)
    top_k = sz["index"][2] // (2 if "top_1024" in variant else 1)

    def one(start):
        pos = start + jnp.arange(block)

        def live():
            cut = lambda t: jax.lax.dynamic_slice_in_dim(t, start, block)
            I = index_scores(cut(h), cut(cq), pos, w, kI, sz, variant)
            keep, near = select(I, pos, top_k)
            return _pack(keep), near

        idle = lambda: (_pack(pos[:, None] == jnp.arange(S)[None, :]),
                        jnp.zeros((block,), jnp.int32))
        return jax.lax.cond(start < n_live, live, idle)

    bits, near = jax.lax.map(one, jnp.arange(0, S, block))
    return bits.reshape(S, -1), near.reshape(S)


@functools.partial(jax.jit, static_argnames=("sz",))
def selection_margins(h, cq, kI, w, bits, *, sz):
    """A given selection ``bits`` [S, S / 8] against the reference's own
    float32 scores: for every row, how many keys it takes that the own
    top-k does not (as many are left out), and how far the farthest of the
    keys that differ lies from the own edge (0 where none differs)."""
    sz = dict(sz)
    S = h.shape[0]
    block = min(S, QUERY_BLOCK)
    top_k = sz["index"][2]

    def one(start):
        cut = lambda t: jax.lax.dynamic_slice_in_dim(t, start, block)
        pos = start + jnp.arange(block)
        I = index_scores(cut(h), cut(cq), pos, w, kI, sz)
        keep, _ = select(I, pos, top_k)
        got = _unpack(cut(bits), S)
        if S <= top_k:
            return (got & ~keep).sum(-1), jnp.zeros((block,), F32)
        edge = jax.lax.top_k(I, top_k)[0][:, -1:]
        far = jnp.where(got != keep, jnp.abs(I - edge), 0.0)
        return (got & ~keep).sum(-1), jnp.where(jnp.isfinite(far), far,
                                                0.0).max(-1)

    n, far = jax.lax.map(one, jnp.arange(0, S, block))
    return n.reshape(S), far.reshape(S)


@functools.partial(jax.jit, static_argnames=("sz", "variant"))
def attention(h, c, k_r, cq, w, bits, n_live, *, sz, variant=()):
    """concat_h(gate_h * sum_j p_h(t, j) v_h(j)) W_o for every row before
    ``n_live`` (a block of rows at or past it is left zero: no row that is
    read sees it): ``bits`` [S, S / 8] the keys each row attends (a full
    layer), or None: the window (a sliding layer)."""
    sz = dict(sz)
    S = h.shape[0]
    H, nope, v_dim, W = sz["heads"], sz["nope"], sz["v_dim"], sz["window"]
    r = k_r.shape[1]
    pos = jnp.arange(S)
    scale = 1.0 / math.sqrt(nope + r)
    block = min(S, QUERY_BLOCK)
    span = min(S, block + W - 1) if W else S
    lead = span - block                   # keys before a block's first query

    def group(ws):
        q, k, v = (t.transpose(1, 0, 2) for t in heads_of(
            cq, c, k_r, pos, *ws, sz, variant))
        if W:                   # rows before the sequence: masked below
            k, v = (jnp.pad(t, ((0, 0), (lead, 0), (0, 0))) for t in (k, v))

        def one(start):
            return jax.lax.cond(
                start < n_live, attend,
                lambda _: jnp.zeros((q.shape[0], block, v_dim), F32), start)

        def attend(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
            t = (start + jnp.arange(block))[:, None]
            if W:
                kb, vb = (jax.lax.dynamic_slice_in_dim(x, start, span, axis=1)
                          for x in (k, v))
                j = (start - lead + jnp.arange(span))[None, :]
                ok = (j <= t) & (t - j < W) & (j >= 0)
            else:
                kb, vb = k, v
                ok = _unpack(jax.lax.dynamic_slice_in_dim(bits, start, block),
                             S)
            s = jnp.einsum("hqd,hkd->hqk", qb, kb) * scale
            p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,hkd->hqd", p, vb)

        a = jax.lax.map(one, jnp.arange(0, S, block))  # [nb, G, block, v]
        return a.transpose(0, 2, 1, 3).reshape(S, -1)  # [S, G v]

    a = jax.lax.map(group, (_grouped(w["wqb"], H, nope + r),
                            _grouped(w["wkvb"], H, nope + v_dim)))
    a = _gate(a.transpose(1, 0, 2).reshape(S, H, v_dim), h, w, H, variant)
    return a.reshape(S, -1) @ w["wo"]


@jax.jit
def dense_close(x, h, w):
    """x + SwiGLU(h), in blocks of ``MLP_ROWS`` rows."""
    S, D = h.shape
    rows = min(MLP_ROWS, S)
    m = jax.lax.map(lambda hb: swiglu(hb, w["w_gate"], w["w_up"],
                                      w["w_down"]),
                    jnp.pad(h, ((0, -S % rows), (0, 0))).reshape(-1, rows, D))
    return x + m.reshape(-1, D)[:S]


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------
def _route_kw(config):
    return dict(top_k=config["num_experts_per_tok"],
                first=config["expert_parallel"]["first_expert"],
                route_scale=float(config["routed_scaling_factor"]),
                route_norm=bool(config["norm_topk_prob"]))


def hidden_states(params, config, tokens, device, routing=None,
                  selection=None, return_choices=False, variant=(),
                  n_live=None, keep=None, margins=None):
    """Final hidden states [S, D] and the outer weights; with
    ``return_choices`` also the router indices used [expert layers, S, k]
    and the full layers' selections (packed, [full layers][S, S / 8]).
    ``selection``: the program's, in that form; ``margins`` (a list) then
    takes each full layer's :func:`selection_margins` of it.  ``keep`` (a
    dict) is filled with what :func:`replay` needs."""
    variant = tuple(sorted(variant))
    eps = config["rms_norm_eps"]
    n_dense = config["first_k_dense_replace"]
    with jax.default_matmul_precision("highest"):
        outer = outer_weights(params, device)
        tokens = jax.device_put(jnp.asarray(tokens, jnp.int32), device)
        S = tokens.shape[0]
        n_live = S if n_live is None else n_live
        pos = jnp.arange(S)
        x = outer["embed"][tokens]
        used, selected = [], []
        if keep is not None:
            keep.update(rows={}, ties=[], near=[], variant=variant)
        for l, dense, kind, j in _layers(config):
            w = layer_weights(params, config, l, device)
            sz = sizes(config, kind, variant)
            szd = dict(sz)
            h = rms_norm(x, w["n_in"], eps)
            c, k_r, cq = shared_rows(h, pos, w, szd, variant)
            bits = kI = None
            if kind == "full":
                kI = index_key(h, pos, w, szd, variant)
                if selection is not None:
                    bits = jnp.asarray(selection[j])
                    if margins is not None:
                        margins.append(tuple(np.asarray(t) for t in
                                             selection_margins(
                                                 h, cq, kI, w, bits, sz=sz)))
                elif "no_selection" in variant:
                    bits = _pack(pos[None, :] <= pos[:, None])
                else:
                    bits, near = own_selection(h, cq, kI, w, n_live, sz=sz,
                                               variant=variant)
                    if keep is not None:
                        keep["near"].append(np.asarray(near))
                selected.append(bits)
            x = x + attention(h, c, k_r, cq, w, bits, n_live, sz=sz,
                              variant=variant)
            if keep is not None:
                keep["rows"][l] = (c, k_r, kI)
            h = rms_norm(x, w["n_post"], eps)
            if dense:
                x = dense_close(x, h, w)
                continue
            chosen = None if routing is None else jnp.asarray(
                routing[l - n_dense])
            chosen, local, fullest, tie = route(
                h, w, chosen, n_live, variant=variant, **_route_kw(config))
            x = expert_close(x, h, w, local, eps=eps,
                             cap=_capacity(fullest, S), variant=NO_POST_NORM)
            used.append(chosen)
            if keep is not None:
                keep["ties"].append(tuple(np.asarray(t) for t in tie))
        if return_choices:
            return x, outer, jnp.stack(used), selected
        return x, outer


# ---------------------------------------------------------------------------
# one row again, with an exchange at the edge of one of its choices
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("sz",))
def attend_one(x, pos, w, c_all, kr_all, kI_all, mode, *, sz):
    """The attention sub-block for single positions: ``x`` [n, D] the
    streams of positions ``pos`` [n] over the sequence's own rows of the
    main pass (``c_all``, ``kr_all``, ``kI_all``, decompressed here) of the
    EARLIER positions, and their own of this evaluation; ``mode`` [n] the
    selection's (a full layer).  Returns (x + attention, its N_post)."""
    sz = dict(sz)
    S = c_all.shape[0]
    H, nope, v_dim, W = sz["heads"], sz["nope"], sz["v_dim"], sz["window"]
    h = rms_norm(x, w["n_in"], sz["eps"])
    c, k_r, cq = shared_rows(h, pos, w, sz)
    r = k_r.shape[1]
    j = jnp.arange(S)[None, :]
    if W:
        ok = (j < pos[:, None]) & (pos[:, None] - j < W)
        own = jnp.ones((x.shape[0],), bool)
    else:
        # the row's own index key takes its place among the sequence's
        kI = index_key(h, pos, w, sz)
        I = index_scores(h, cq, pos, w, kI_all, sz)
        I_own = index_scores(h, cq, pos, w, kI, sz, causal=False)   # [n, n]
        I = I.at[jnp.arange(x.shape[0]), pos].set(jnp.diagonal(I_own))
        keep, _ = select(I, pos, sz["index"][2], mode)
        own = keep[jnp.arange(x.shape[0]), pos]
        ok = keep & (j < pos[:, None])
    scale = 1.0 / math.sqrt(nope + r)

    def group(ws):
        wqb, wkvb = ws
        q, k, v = heads_of(cq, c, k_r, pos, wqb, wkvb, sz)
        kvb = (c_all @ wkvb).reshape(S, q.shape[1], -1)
        s_all = (jnp.einsum("ngd,sgd->ngs", q[..., :nope], kvb[..., :nope])
                 + jnp.einsum("ngd,sd->ngs", q[..., nope:], kr_all))
        s_own = jnp.where(own[:, None], (q * k).sum(-1), -jnp.inf)
        s = jnp.concatenate([jnp.where(ok[:, None], s_all, -jnp.inf),
                             s_own[..., None]], -1) * scale
        p = jax.nn.softmax(s, axis=-1)
        a = jnp.einsum("ngs,sgd->ngd", p[..., :S], kvb[..., nope:]) \
            + p[..., S:] * v
        return a.reshape(a.shape[0], -1)

    a = jax.lax.map(group, (_grouped(w["wqb"], H, nope + r),
                            _grouped(w["wkvb"], H, nope + v_dim)))
    a = _gate(a.transpose(1, 0, 2).reshape(-1, H, v_dim), h, w, H, ())
    x = x + a.reshape(x.shape[0], -1) @ w["wo"]
    return x, rms_norm(x, w["n_post"], sz["eps"])


def replay(params, config, tokens, pos, swaps, modes, keep, outer, device):
    """Final hidden states [n, D] of positions ``pos`` [n] with the router
    exchange ``swaps`` [n, expert layers] (an entry of SWAPS, 1-based; 0:
    none) made at each expert layer and the selection ``modes`` [n, full
    layers] (:data:`SELECT_MODES`) at each full layer, every other position
    as the main pass left it; and each expert layer's near-ties ON THAT
    STREAM."""
    n_dense = config["first_k_dense_replace"]
    eps = config["rms_norm_eps"]
    n = len(pos)
    # to a power of two of whole blocks, so that few shapes compile
    pad = REPLAY_ROWS * (1 << int(np.ceil(np.log2(-(-n // REPLAY_ROWS))))) - n
    pos = jnp.asarray(np.pad(pos, (0, pad), mode="edge"), jnp.int32)
    swaps = jnp.asarray(np.pad(swaps, ((0, pad), (0, 0))))
    modes = jnp.asarray(np.pad(modes, ((0, pad), (0, 0))))
    blocks = range(0, n + pad, REPLAY_ROWS)
    cut = lambda t, a: t[a:a + REPLAY_ROWS]
    with jax.default_matmul_precision("highest"):
        x = outer["embed"][jnp.asarray(tokens)[pos]]
        ties = []
        for l, dense, kind, j in _layers(config):
            w = layer_weights(params, config, l, device)
            sz = sizes(config, kind)
            c_all, kr_all, kI_all = keep["rows"][l]
            mode = modes[:, j] if kind == "full" else jnp.zeros_like(pos)
            x, h = (jnp.concatenate(parts) for parts in zip(*(
                attend_one(cut(x, a), cut(pos, a), w, c_all, kr_all,
                           kI_all if kind == "full" else c_all[:, :1],
                           cut(mode, a), sz=sz) for a in blocks)))
            if dense:
                x = dense_close(x, h, w)
                continue
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
    highest: the selection's two ends at the full layers where the row has
    in-band keys (one layer, or all of them, on the reference's own
    routing), then the router's exchanges (``reference/trinity.py``'s
    search, on the reference's own selection)."""
    n_exp, n_full = len(keep["ties"]), len(keep["near"])
    n_tok = len(tokens)
    logits = np.array(logits)
    first, places = {}, {}
    for at, r in enumerate(rows):
        first.setdefault(int(r), at)
        places.setdefault(int(r), []).append(at)
    no_swap, own = (0,) * n_exp, (0,) * n_full
    best = {r: _below_best(logits[at], tokens[r + 1])
            for r, at in first.items() if r + 1 < n_tok}
    best = {r: b for r, b in best.items() if b > 0.0}

    def evaluate(tries):
        """tries: (row, swaps, modes) -> each expert layer's near-ties on
        the replayed streams; ``best`` and ``logits`` move."""
        x, ties = replay(
            params, config, tokens, np.asarray([t[0] for t in tries]),
            np.asarray([t[1] for t in tries]).reshape(len(tries), n_exp),
            np.asarray([t[2] for t in tries]).reshape(len(tries), n_full),
            keep, outer, device)
        with jax.default_matmul_precision("highest"):
            got = np.asarray(rms_norm(x, outer["norm"],
                                      config["rms_norm_eps"])
                             @ outer["lm_head"])
        for t, (r, _, _) in enumerate(tries):
            below = _below_best(got[t], tokens[r + 1])
            if below < best[r]:
                best[r] = below
                logits[places[r]] = got[t]
        return ties

    tries = []
    for r in best:
        near = [keep["near"][f][r] > 0 for f in range(n_full)]
        for mode in SELECT_MODES[1:]:
            alts = {tuple(mode if near[f] and g in (None, f) else 0
                          for f in range(n_full))
                    for g in [None] + list(range(n_full))} - {own}
            tries += [(r, no_swap, alt) for alt in sorted(alts)]
    if tries:
        evaluate(tries)
    front = [(r, no_swap, [(m[r], h[r]) for m, h in keep["ties"]])
             for r in best]
    while front:
        tries = []
        for r, swaps, ties in front:
            last = max((e for e in range(n_exp) if swaps[e]), default=-1)
            for e in range(last + 1, n_exp):
                for n, (margin, held) in enumerate(zip(*ties[e]), start=1):
                    if held and margin < NEAR_TIE:
                        tries.append((r, swaps[:e] + (n,) + swaps[e + 1:],
                                      own))
        if not tries:
            break
        ties = evaluate(tries)
        front = [(r, swaps, [(m[t], h[t]) for m, h in ties])
                 for t, (r, swaps, _) in enumerate(tries)]
    return logits


def padded_routing(routing, S):
    """[S or fewer, k] a layer -> [S, k]: rows to the end."""
    return [np.pad(np.asarray(r)[:S], ((0, max(S - len(r), 0)), (0, 0)))
            for r in routing]


def _length(n_live, S):
    """The shortest of LENGTHS (or ``S`` itself) that holds ``n_live``
    rows."""
    return min([n for n in LENGTHS if n_live <= n < S] + [S])


def logits_rows(params, config, tokens, rows, device, routing=None,
                selection=None, variant=()):
    """Reference logits [len(rows), V] at positions ``rows`` of ``tokens``
    (V the chip's share of the vocabulary).  Without ``routing`` and
    ``selection`` a row at a near-tie of the router or of the indexer is the
    admissible evaluation its next token fits best (:func:`admissible_rows`),
    also under a ``variant`` that breaks the router alone; with them, or
    under any other control, the one evaluation stands."""
    tokens = np.asarray(tokens)
    rows = np.asarray(rows)
    S = len(tokens)
    n_live = int(rows.max()) + 1
    if S > QUERY_BLOCK:      # whole query blocks, no longer than it must be
        S = _length(n_live, -(-S // QUERY_BLOCK) * QUERY_BLOCK)
    cut = np.zeros(S, tokens.dtype)
    cut[:min(S, len(tokens))] = tokens[:S]
    if routing is not None:
        routing = padded_routing(routing, S)
    if selection is not None:     # [S or more, S / 8 or more] a full layer
        selection = [np.asarray(b)[:S, :-(-S // 8)] for b in selection]
    own = routing is None and selection is None \
        and ROUTE_VARIANTS.issuperset(variant)
    keep = {} if own else None
    x, outer = hidden_states(params, config, cut, device, routing, selection,
                             variant=variant, n_live=n_live, keep=keep)
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x[jnp.asarray(rows)], outer["norm"],
                     config["rms_norm_eps"])
        logits = h @ outer["lm_head"]
    if keep is None:
        return logits
    return admissible_rows(params, config, tokens[:S], rows, device, logits,
                           keep, outer)
