"""Blockwise (flash) attention Pallas kernels, forward + backward.

TPU-native replacement for the reference's fused attention path
(``csrc/transformer/softmax_kernels.cu`` + strided-batch GEMM attention in
``csrc/includes/strided_batch_gemm.h``, and the inference ``softmax.cu``;
SURVEY.md §2.2): instead of materializing the [S, S] score matrix between two
cuBLAS GEMMs, the kernel walks KV tiles with an online softmax, so memory is
O(S·D) and the MXU sees back-to-back matmuls.

Layout: q, k, v are [B, H, S, D].  Causal masking supported; ``alibi=True``
adds the per-head linear position bias in-kernel.  All softmax math in fp32
(matching the reference kernels' accumulation).

The tile schedule (:func:`tile_schedule`).  A grid step holds ``hb`` heads
(whatever divides B*H and fits the VMEM budget), one Q tile of each and the
heads' K and V for the whole sequence (a chunk of it where that does not
fit), and walks the KV axis inside the kernel, in strips of ``_STRIP`` rows:
a rolled loop over the strips under the diagonal, whose bound follows the Q
tile, then the tile the diagonal crosses, whose strips each take only the
queries from their own first row on.  So a strip the causal mask removes
costs no grid step, no DMA and no arithmetic; only the strips of a crossed
tile apply the mask; and of the scores computed and thrown away a strip's
width is left, not a tile's.  The running state lives in VMEM scratch, across
KV chunks too.

The forward and the dK/dV kernel work on the TRANSPOSED score tile ``K Qᵀ``
(KV positions on sublanes, queries on lanes).  The softmax's max and sum over
KV then run down the sublanes, elementwise, instead of across lanes through
the XLU once per eight rows per tile (what bound the forward at head dim
64), and m, l, LSE and delta are lane-dense rows: an [S, 1] column of float32
is padded to 128 lanes in HBM and in every block.  dK/dV's four matmuls are
plain or rhs-transposed in this form (no transpose of a score tile); the
forward pays one transpose of its [D, bq] accumulator per Q tile.

Backward follows the standard recompute scheme: saved LSE from forward;
``delta = rowsum(dO ∘ O)``; one kernel accumulates dQ over KV strips, another
accumulates dK/dV over Q strips.

:func:`eva_chunk_attention` (forward only) is the same schedule for a serving
prefill chunk of an EVA model (``models/eva.py``): the chunk's queries over
the slot's window rows up to each query and the summary rows of the windows
before, one online softmax over both runs of strips.

:func:`mla_chunk_attention` (forward only) is the schedule again for a
prefill chunk of a latent-attention layer (``models/kda_mla.py``): the cache
holds one compressed row a position, and each strip of rows is decompressed
to a head's keys and values on chip, right before its scores; the grid walks
blocks of rows only up to the chunk's last query (its extent is read at run
time).  Its jnp reference is ``models/afmoe.py:attend(expand=)``.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.common import (interpret_flag,
                                             kernel_or_reference, pick_block,
                                             resolve_impl, round_up)

# Upper limits of the Q and KV tile (the tiles are the largest divisors of S
# and Sk within them).  On one v5e, causal [16, 25, 1024, 64] bf16, forward +
# dQ + dK/dV of one call each (tools/flash_attention_bench.py, PR 34): 8.36 ms
# with a grid step per 512 x 512 tile (the schedule before); 4.19 ms with
# tiles of 1024 and strips of 256, 4.32 with strips of 128; with one block a
# tile and no strips 5.2 (512), 7.3 (256), 16.0 (128): a loop iteration costs
# some 500 cycles whatever the tile, so tiles are large and what the mask
# removes is cut out of them by strips.  [8, 32, 1024, 128]: 5.28 -> 2.55;
# [2, 32, 4096, 128]: 15.6 -> 9.2.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30
# What a grid step's double-buffered blocks and scratch may hold; it sets the
# heads a step takes and, for long sequences, the KV (dK/dV: Q) chunk.  The
# score-sized temporaries come on top, inside the compiler's 16 MiB default.
_VMEM_BLOCK_BYTES = 8 * 2**20
_LANES = 128
# Rows of a strip, the unit the in-kernel loops step by (see _strips).
_STRIP = 256


# ---------------------------------------------------------------------------
# jnp reference (parity target + CPU path)
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, causal: bool = True, sm_scale: Optional[float] = None,
                  bias=None, probs_transform=None, pv_dtype=None):
    """jnp attention; ``probs_transform`` hooks the post-softmax
    probabilities (e.g. attention dropout in the fused transformer layer);
    ``pv_dtype`` sets the probs@V matmul precision (default fp32 — the
    parity-reference contract; pass the compute dtype for MXU-rate serving
    of the masked path)."""
    *_, S, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / (D ** 0.5)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if bias is not None:
        logits = logits + bias
    if causal:
        Sk = k.shape[-2]
        mask = jnp.tril(jnp.ones((S, Sk), bool), k=Sk - S)
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if probs_transform is not None:
        probs = probs_transform(probs)
    pv = pv_dtype if pv_dtype is not None else jnp.float32
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(pv),
                      v.astype(pv)).astype(q.dtype)


# ---------------------------------------------------------------------------
# tile schedule
# ---------------------------------------------------------------------------

def _clip(x, lo, hi):
    """min(max(x, lo), hi) on Python ints and on traced scalars alike."""
    if all(isinstance(t, int) for t in (x, lo, hi)):
        return min(max(x, lo), hi)
    return jnp.minimum(jnp.maximum(x, lo), hi)


def _kv_bounds(i, bq: int, bk: int, nk: int, causal: bool):
    """KV tiles of Q tile ``i`` (rows ``[i bq, (i+1) bq)``; a row sees the
    columns up to its own index): tiles ``[0, plain)`` lie wholly under the
    diagonal, ``[plain, visit)`` are crossed by it, the rest is masked out."""
    if not causal:
        return nk, nk
    return (_clip((i * bq + 1) // bk, 0, nk),
            _clip(((i + 1) * bq + bk - 1) // bk, 0, nk))


def _q_bounds(j, bq: int, bk: int, nq: int, causal: bool):
    """The transpose of :func:`_kv_bounds`, for KV tile ``j``: Q tiles
    ``[first, plain)`` are crossed by the diagonal, ``[plain, nq)`` lie
    wholly under it, those before ``first`` see none of the tile."""
    if not causal:
        return 0, 0
    return (_clip(j * bk // bq, 0, nq),
            _clip(((j + 1) * bk + bq - 2) // bq, 0, nq))


class _Plan(NamedTuple):
    bq: int       # Q tile
    bk: int       # KV tile
    ck: int       # KV tokens a forward / dQ grid step holds (Sk if it fits)
    cq: int       # Q tokens a dK/dV grid step holds (S if it fits)
    hb_fwd: int   # heads a grid step, by kernel
    hb_dq: int
    hb_dkv: int


def _fit(n: int, tile: int, fixed: int, per_token: int) -> int:
    """Largest chunk of ``n`` tokens, in whole tiles and dividing ``n``,
    whose blocks fit :data:`_VMEM_BLOCK_BYTES` beside ``fixed`` bytes."""
    t = n // tile
    for parts in range(1, t + 1):
        if t % parts == 0 and fixed + (n // parts) * per_token <= _VMEM_BLOCK_BYTES:
            return n // parts
    return tile


def _heads(BH: int, per_head: int) -> int:
    """Heads a grid step: the largest divisor of ``BH`` (not of the heads
    of the model: 25 is odd) whose blocks fit the budget."""
    return max(d for d in range(1, BH + 1)
               if BH % d == 0 and (d == 1 or d * per_head <= _VMEM_BLOCK_BYTES))


def _token_bytes(D: int, itemsize: int):
    """VMEM bytes one token takes in a q / k / v block, in an f32
    accumulator and in a double-buffered [1, n] f32 row of statistics."""
    return round_up(D, _LANES) * itemsize, round_up(D, _LANES) * 4, 2 * 8 * 4


def _plan(BH: int, S: int, Sk: int, D: int, itemsize: int, block_q: int,
          block_k: int) -> _Plan:
    """Tiles, chunks and heads a step from the shapes alone.  Bytes are what
    the blocks take in VMEM: the head dim padded to the 128 lanes, inputs
    and outputs double-buffered, a [1, n] row of statistics 8 sublanes."""
    bq = pick_block(S, block_q, minimum=8)
    bk = pick_block(Sk, block_k, minimum=8)
    row, acc, stat = _token_bytes(D, itemsize)
    # forward: q, o tiles + lse row; m, l rows and acc scratch; K and V chunk
    fwd_fixed = bq * (2 * 2 * row + 2 * stat + acc)
    # dQ: q, dO, dq tiles + lse, delta rows; dq scratch; K and V chunk
    dq_fixed = bq * (2 * 3 * row + 2 * stat + acc)
    ck = _fit(Sk, bk, max(fwd_fixed, dq_fixed), 2 * 2 * row)
    # dK/dV: k, v, dk, dv tiles; dk, dv scratch; Q, dO chunk + lse, delta rows
    dkv_fixed = bk * (2 * 4 * row + 2 * acc)
    cq = _fit(S, bq, dkv_fixed, 2 * 2 * row + 2 * stat)
    return _Plan(bq, bk, ck, cq,
                 _heads(BH, fwd_fixed + ck * 2 * 2 * row),
                 _heads(BH, dq_fixed + ck * 2 * 2 * row),
                 _heads(BH, dkv_fixed + cq * (2 * 2 * row + 2 * stat)))


def tile_schedule(S: int, Sk: int, block_q: int = DEFAULT_BLOCK_Q,
                  block_k: int = DEFAULT_BLOCK_K, causal: bool = True, *,
                  head_dim: int = 64, heads: int = 1,
                  itemsize: int = 2) -> Dict[str, object]:
    """What the three kernels do on ``heads`` (= B*H) heads of [S, D] queries
    against [Sk, D] keys.  Pure arithmetic on shapes, from the same bounds
    and sub-blocks the kernels' loops use.

    ``visited`` / ``masked``: the (Q tile, KV tile) pairs of the ``tiles`` =
    nq * nk a head computes, and those of them whose body applies the mask
    (causal, S == Sk, square tiles: n (n + 1) / 2 and n; no visited pair lies
    wholly above the diagonal).  ``score_elements``: the scores a head
    computes, ``kept_elements`` those the mask keeps (what a diagonal tile's
    strips save shows here).  ``grid_steps``: of each kernel, all heads."""
    p = _plan(heads, S, Sk, head_dim, itemsize, block_q, block_k)
    nq, nk = S // p.bq, Sk // p.bk
    bounds = [_kv_bounds(i, p.bq, p.bk, nk, causal) for i in range(nq)]
    pairs = [(i, j) for i, (_, visit) in enumerate(bounds) for j in range(visit)]
    masked = [(i, j) for i, (plain, visit) in enumerate(bounds)
              for j in range(plain, visit)]
    crossed = sum(kv_n * q_n for _, kv_n, _, q_n in _strips(p.bq, p.bk, True))
    kept = sum(min(r + 1, Sk) for r in range(S)) if causal else S * Sk
    return {"block_q": p.bq, "block_k": p.bk, "strip": _strip(p.bk),
            "tiles": nq * nk, "visited": len(pairs), "masked": len(masked),
            "visited_pairs": pairs, "masked_pairs": masked,
            "score_elements": (len(pairs) - len(masked)) * p.bq * p.bk
            + len(masked) * crossed,
            "kept_elements": kept,
            "heads_per_step": {"fwd": p.hb_fwd, "bwd_dq": p.hb_dq,
                               "bwd_dkv": p.hb_dkv},
            "grid_steps": {"fwd": heads // p.hb_fwd * nq * (Sk // p.ck),
                           "bwd_dq": heads // p.hb_dq * nq * (Sk // p.ck),
                           "bwd_dkv": heads // p.hb_dkv * nk * (S // p.cq)}}


def _folds(scale: float) -> bool:
    """A power-of-two scale (64^-1/2 is) multiplies bf16 operands and f32
    sums exactly, so it moves off the [bq, bk] scores onto a [tile, D]
    operand or accumulator without changing a bit of the result."""
    return scale > 0 and math.frexp(scale)[0] == 0.5


def _strip(tile: int) -> int:
    """Rows of a strip of a ``tile``-token tile, the loops' own step: the
    largest divisor of the tile within :data:`_STRIP`, a whole number of
    lane tiles if there is one, of sublane tiles otherwise."""
    for unit in (_LANES, 8):
        fits = [d for d in range(unit, min(tile, _STRIP) + 1, unit)
                if tile % d == 0]
        if fits:
            return fits[-1]
    return tile


def _strips(bq: int, bk: int, masked: bool, by_q: bool = False):
    """The sub-blocks ``(kv_lo, kv_n, q_lo, q_n)`` of score tile [bq, bk]
    that one loop body computes.  Under the diagonal a body is ONE strip
    (of KV rows against the whole Q tile; ``by_q``: of Q rows against the
    whole KV tile, the dK/dV kernel's loop) and the loop runs over the
    strips.  A tile the diagonal crosses is one body: its KV strips, and on
    the diagonal of square tiles each strip against the queries from its
    own first row on only, since every query before that row has the whole
    strip masked out (the queries lie on lanes in two of the kernels, so
    that row has to be a multiple of the lane tile).  What is computed and
    thrown away of such a tile shrinks from a half to ``strip / (2 bk)`` of
    it; the strips differ in shape, so the body is unrolled over them."""
    sq, sk = _strip(bq), _strip(bk)
    if not masked:
        return [(0, bk, 0, sq)] if by_q else [(0, sk, 0, bq)]
    trimmed = bq == bk and sk % _LANES == 0
    return [(r, sk, r, bq - r) if trimmed else (r, sk, 0, bq)
            for r in range(0, bk, sk)]


def _rel(kv_n: int, q_n: int, kv_dim: int):
    """(KV index) - (Q index) inside a sub-block whose axis ``kv_dim`` runs
    over KV positions.  Built once a grid step, outside the loops: the mask
    of a tile is then one compare and one select, ALiBi one multiply-add."""
    shape = (kv_n, q_n) if kv_dim == 0 else (q_n, kv_n)
    return lax.sub(lax.broadcasted_iota(jnp.int32, shape, kv_dim),
                   lax.broadcasted_iota(jnp.int32, shape, 1 - kv_dim))


def _bodies(bq: int, bk: int, causal: bool, alibi: bool, kv_dim: int,
            by_q: bool = False):
    """{masked: [(kv_lo, kv_n, q_lo, q_n, rel or None)]} for the two loop
    bodies; ``rel`` only where the body masks or adds ALiBi."""
    return {masked: [blk + ((_rel(blk[1], blk[3], kv_dim)
                             if masked or alibi else None),)
                     for blk in _strips(bq, bk, masked, by_q)]
            for masked in ((False, True) if causal else (False,))}


# The kernel bodies below call ``lax`` where ``jnp`` would read better: a
# ``jnp`` function, or an operator on a traced array, is a jitted wrapper
# that takes most of a millisecond to trace, every time the enclosing program
# is lowered (nothing caches that: it is part of every process's set-up),
# against a tenth of that for the primitive.  The rare paths (ALiBi, a scale
# that does not fold) keep ``jnp``.

_NT = ((1,), (1,))    # a @ b.T
_NN = ((1,), (0,))    # a @ b
_TN = ((0,), (0,))    # a.T @ b


def _dot(a, b, contract):
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=jnp.float32)


def _bc(x, like):
    """A [1, n] row or an [n, 1] column broadcast over ``like``'s shape."""
    return lax.broadcast_in_dim(x, like.shape, (0, 1))


def _bias_and_mask(s, rel, slope, d, masked: bool):
    """``d`` = (first Q row) - (first KV column) of the sub-block.  ALiBi
    adds ``slope * (column - row)``; the mask keeps ``row >= column``."""
    if slope is not None:
        s = s + slope * (rel - d).astype(jnp.float32)
    if masked:
        s = lax.select(lax.le(rel, lax.broadcast(jnp.int32(d), rel.shape)), s,
                       lax.full_like(s, NEG_INF))
    return s


def _run(lo, hi, tile, masked: bool):
    """``tile(t, masked)`` for t in [lo, hi): a rolled loop (its body is
    traced once) whose bounds depend on the grid step."""
    def body(t, carry):
        tile(t, masked)
        return carry

    lax.fori_loop(lo, hi, body, 0)


def _when(pred):
    """``pl.when``, or a plain call where ``pred`` is known to hold."""
    return (lambda f: f()) if pred is True else pl.when(pred)


def _all(*preds):
    """Conjunction of traced conditions; True when there is none."""
    return functools.reduce(jnp.logical_and, preds) if preds else True


def _chunk(c, tiles: int, n: int):
    """Grid step ``c``'s chunk ``[lo, hi)`` of the ``n`` tiles its loops
    walk, and whether it starts / ends the accumulation: Python's 0, n,
    True, True where one chunk holds them all (the usual case, and then
    nothing of this is traced)."""
    if tiles == n:
        return 0, n, True, True
    return c * tiles, (c + 1) * tiles, c == 0, c == pl.num_programs(2) - 1


def _diagonal_here(i, mine: int, other: int, lo, hi):
    """Square tiles: does tile ``i`` (of ``mine``) have its diagonal tile
    among the ``other`` axis's tiles ``[lo, hi)`` of this grid step
    (:func:`_chunk`'s: Python ints where they are all of them)?"""
    return _all(*([i < other] if mine > other else [])
                + ([] if isinstance(lo, int) else [i >= lo, i < hi]))


def _part(x, axis: int, lo: int, n: int):
    """``x[lo:lo + n]`` along ``axis`` of a 2-D value (all of it: ``x``)."""
    if n == x.shape[axis]:
        return x
    start, stop = [0, 0], list(x.shape)
    start[axis], stop[axis] = lo, lo + n
    return lax.slice(x, start, stop)


def _to_col(row):
    """[1, n] -> [n, 1] (lanes to sublanes), through a whole-tile transpose."""
    n = row.shape[1]
    full = lax.broadcast_in_dim(row, (_LANES, n), (0, 1))
    return _part(lax.transpose(full, (1, 0)), 1, 0, 1)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _softmax_init(h, m_scr, l_scr, acc_scr):
    m_scr[h] = jnp.full(m_scr.shape[1:], NEG_INF, jnp.float32)
    l_scr[h] = jnp.zeros(l_scr.shape[1:], jnp.float32)
    acc_scr[h] = jnp.zeros(acc_scr.shape[1:], jnp.float32)


def _softmax_step(st, v, h, lanes, m_scr, l_scr, acc_scr):
    """One sub-block of the online softmax on transposed scores ``st``
    [kv_n, q_n] (masked already) and the strip's values ``v`` [kv_n, D]:
    head ``h``'s running max and sum (rows [1, bq]) and accumulator
    ([D, bq]) move on for the queries at ``lanes``."""
    m_prev = m_scr[h, :, lanes]                                # [1, q_n]
    m_new = lax.max(m_prev, lax.expand_dims(
        lax.reduce_max(st, (0,)), (0,)))
    pt = lax.exp(lax.sub(st, _bc(m_new, st)))                  # [kv_n, q_n]
    alpha = lax.exp(lax.sub(m_prev, m_new))
    l_scr[h, :, lanes] = lax.add(
        lax.mul(alpha, l_scr[h, :, lanes]),
        lax.expand_dims(lax.reduce_sum(pt, (0,)), (0,)))
    acc = acc_scr[h, :, lanes]                                 # [D, q_n]
    acc_scr[h, :, lanes] = lax.add(
        lax.mul(acc, _bc(alpha, acc)),
        _dot(v, lax.convert_element_type(pt, v.dtype), _TN))
    m_scr[h, :, lanes] = m_new


def _fwd_kernel(slope_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, alibi, bq, bk, nq,
                nk, tiles, hb):
    g, i, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    fold, square = _folds(scale), causal and bq == bk
    # this step's chunk of KV tiles, and what of it Q tile i visits (worked
    # out only where a loop needs it: scalar arithmetic is traced too)
    lo, hi, first, last = _chunk(c, tiles, nk)
    has_plain = not causal or _kv_bounds(nq - 1, bq, bk, nk, True)[0] > 0
    if has_plain or not square:
        plain, visit = _kv_bounds(i, bq, bk, nk, causal)
        if tiles < nk:
            plain, visit = _clip(plain, lo, hi), _clip(visit, lo, hi)
    bodies, sk = _bodies(bq, bk, causal, alibi, kv_dim=0), _strip(bk)

    def head(h, carry):
        _when(first)(lambda: _softmax_init(h, m_scr, l_scr, acc_scr))

        # MXU matmuls take the native (bf16) operands; only the accumulator
        # and softmax statistics are fp32 — fp32 MXU inputs would quarter
        # throughput for no accuracy gain over fp32 accumulation.
        q = q_ref[h]                                       # [bq, D]
        if fold:
            q = q * scale
        slope = slope_ref[g * hb + h] if alibi else None

        def tile(j, masked):
            # a masked body is a tile, a plain one a strip of a tile; on the
            # diagonal of square tiles the two first positions are equal
            step = bk if masked else sk
            ks = j * step - lo * bk
            d = 0 if masked and square else i * bq - j * step
            for kv_lo, kv_n, q_lo, q_n, rel in bodies[masked]:
                rows = pl.ds(pl.multiple_of(ks + kv_lo, kv_n), kv_n)
                lanes = slice(q_lo, q_lo + q_n)
                k, v = k_ref[h, rows, :], v_ref[h, rows, :]    # [kv_n, D]
                # the scores transposed (KV positions on sublanes, queries
                # on lanes): max and sum over KV run down the sublanes,
                # elementwise, and m, l are lane-dense rows
                st = _dot(k, _part(q, 0, q_lo, q_n), _NT)
                if not fold:
                    st = st * scale
                st = _bias_and_mask(st, rel, slope, d + q_lo - kv_lo, masked)
                _softmax_step(st, v, h, lanes, m_scr, l_scr, acc_scr)

        if has_plain:
            _run(lo * (bk // sk), plain * (bk // sk), tile, False)
        if square:
            _when(_diagonal_here(i, nq, nk, lo, hi))(lambda: tile(i, True))
        elif causal:
            _run(plain, visit, tile, True)

        @_when(last)
        def _finish():
            l = l_scr[h]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[h] = jnp.transpose(acc_scr[h] / safe_l).astype(o_ref.dtype)
            lse_ref[h, 0] = m_scr[h] + jnp.log(safe_l)

        return carry

    lax.fori_loop(0, hb, head, 0)


def _head_slopes(B: int, H: int, alibi: bool):
    """[B*H] per-head ALiBi slopes, read from SMEM by head index (zeros when
    off — the argument is static for the shared kernel signature)."""
    if not alibi:
        return jnp.zeros((B * H,), jnp.float32)
    from deepspeed_tpu.models.layers import alibi_slopes

    return jnp.tile(alibi_slopes(H), B).astype(jnp.float32)


_SLOPES = pl.BlockSpec(memory_space=pltpu.SMEM)


def _chunk_map(bound, tiles: int, n_chunks: int, last: bool):
    """Index map of the [hb, chunk, D] blocks a kernel's loops walk, for grid
    step (heads g, tile t, chunk c): chunk c, or, where the causal mask
    removes all of it, the nearest chunk tile t does need, so the block index
    repeats and nothing is fetched.  ``bound(t)`` is one past the last
    needed tile when ``last``, the first needed one otherwise."""
    def index(g, t, c):
        if n_chunks == 1:
            return g, 0, 0
        if last:
            return g, _clip(c, 0, _clip((bound(t) - 1) // tiles, 0,
                                        n_chunks - 1)), 0
        return g, _clip(c, _clip(bound(t) // tiles, 0, n_chunks - 1),
                        n_chunks - 1), 0

    return index


def _flash_fwd(q, k, v, causal, alibi, scale, block_q, block_k, interpret):
    B, H, S, D = q.shape
    Sk = k.shape[2]
    BH = B * H
    p = _plan(BH, S, Sk, D, q.dtype.itemsize, block_q, block_k)
    bq, bk, hb = p.bq, p.bk, p.hb_fwd
    nq, nk, tiles, nc = S // bq, Sk // bk, p.ck // bk, Sk // p.ck

    kv_chunk = _chunk_map(lambda i: _kv_bounds(i, bq, bk, nk, causal)[1],
                          tiles, nc, last=True)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               alibi=alibi, bq=bq, bk=bk, nq=nq, nk=nk,
                               tiles=tiles, hb=hb)
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH // hb, nq, nc),
        in_specs=[_SLOPES,
                  pl.BlockSpec((hb, bq, D), lambda g, i, c: (g, i, 0)),
                  pl.BlockSpec((hb, p.ck, D), kv_chunk),
                  pl.BlockSpec((hb, p.ck, D), kv_chunk)],
        out_specs=[pl.BlockSpec((hb, bq, D), lambda g, i, c: (g, i, 0)),
                   pl.BlockSpec((hb, 1, 1, bq), lambda g, i, c: (g, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((BH, S, D), q.dtype),
                   # lane-dense rows, a Q tile each: an [S, 1] column would
                   # be padded to 128 lanes in HBM and in every block
                   jax.ShapeDtypeStruct((BH, nq, 1, bq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, 1, bq), jnp.float32),
                        pltpu.VMEM((hb, 1, bq), jnp.float32),
                        pltpu.VMEM((hb, D, bq), jnp.float32)],
        interpret=interpret,
        name="flash_attention_fwd",
    )(_head_slopes(B, H, alibi), q.reshape(BH, S, D), k.reshape(BH, Sk, D),
      v.reshape(BH, Sk, D))
    return o.reshape(B, H, S, D), lse.reshape(B, H, S)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(slope_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, scale, causal, alibi, bq, bk, nq, nk,
                   tiles, hb):
    g, i, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    fold, square = _folds(scale), causal and bq == bk
    lo, hi, first, last = _chunk(c, tiles, nk)
    has_plain = not causal or _kv_bounds(nq - 1, bq, bk, nk, True)[0] > 0
    if has_plain or not square:
        plain, visit = _kv_bounds(i, bq, bk, nk, causal)
        if tiles < nk:
            plain, visit = _clip(plain, lo, hi), _clip(visit, lo, hi)
    bodies, sk = _bodies(bq, bk, causal, alibi, kv_dim=1), _strip(bk)

    def head(h, carry):
        @_when(first)
        def _init():
            dq_scr[h] = jnp.zeros(dq_scr.shape[1:], jnp.float32)

        q = q_ref[h]
        if fold:
            q = q * scale
        do = do_ref[h]
        lse = _to_col(lse_ref[h, 0])                       # [bq, 1]
        delta = _to_col(delta_ref[h, 0])
        slope = slope_ref[g * hb + h] if alibi else None

        def tile(j, masked):
            step = bk if masked else sk
            ks = j * step - lo * bk
            d = 0 if masked and square else i * bq - j * step
            for kv_lo, kv_n, q_lo, q_n, rel in bodies[masked]:
                cols = pl.ds(pl.multiple_of(ks + kv_lo, kv_n), kv_n)
                rows = slice(q_lo, q_lo + q_n)
                k, v = k_ref[h, cols, :], v_ref[h, cols, :]
                s = _dot(_part(q, 0, q_lo, q_n), k, _NT)       # [q_n, kv_n]
                if not fold:
                    s = s * scale
                s = _bias_and_mask(s, rel, slope, d + q_lo - kv_lo, masked)
                p = lax.exp(lax.sub(s, _bc(_part(lse, 0, q_lo, q_n), s)))
                dp = _dot(_part(do, 0, q_lo, q_n), v, _NT)
                ds = lax.mul(p, lax.sub(
                    dp, _bc(_part(delta, 0, q_lo, q_n), dp)))
                if not fold:
                    ds = ds * scale
                dq_scr[h, rows] = lax.add(
                    dq_scr[h, rows],
                    _dot(lax.convert_element_type(ds, k.dtype), k, _NN))

        if has_plain:
            _run(lo * (bk // sk), plain * (bk // sk), tile, False)
        if square:
            _when(_diagonal_here(i, nq, nk, lo, hi))(lambda: tile(i, True))
        elif causal:
            _run(plain, visit, tile, True)

        @_when(last)
        def _finish():
            dq = dq_scr[h]
            dq_ref[h] = (dq * scale if fold else dq).astype(dq_ref.dtype)

        return carry

    lax.fori_loop(0, hb, head, 0)


def _bwd_dkv_kernel(slope_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, alibi,
                    bq, bk, nq, nk, tiles, hb):
    g, j, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    fold, square = _folds(scale), causal and bq == bk
    # this step's chunk of Q tiles, and what of it KV tile j is seen by
    lo, hi, first, last = _chunk(c, tiles, nq)
    has_plain = not causal or _q_bounds(0, bq, bk, nq, True)[1] < nq
    if has_plain or not square:
        crossed, plain = _q_bounds(j, bq, bk, nq, causal)
        if tiles < nq:
            crossed, plain = _clip(crossed, lo, hi), _clip(plain, lo, hi)
    bodies = _bodies(bq, bk, causal, alibi, kv_dim=0, by_q=True)
    sq = _strip(bq)

    def head(h, carry):
        @_when(first)
        def _init():
            dk_scr[h] = jnp.zeros(dk_scr.shape[1:], jnp.float32)
            dv_scr[h] = jnp.zeros(dv_scr.shape[1:], jnp.float32)

        k = k_ref[h]                                       # [bk, D]
        if fold:
            k = k * scale
        v = v_ref[h]
        slope = slope_ref[g * hb + h] if alibi else None

        def tile(i, masked):
            # a masked body is a tile, a plain one a strip of a tile; lse
            # and delta come as one lane-dense row a strip
            step = bq if masked else sq
            qs = i * step - lo * bq
            d = 0 if masked and square else i * step - j * bk
            strip0 = i * (step // sq) - lo * (bq // sq)
            lse_row, delta_row = (
                ref[h, strip0] if step == sq else lax.concatenate(
                    [ref[h, strip0 + u] for u in range(step // sq)], 1)
                for ref in (lse_ref, delta_ref))
            for kv_lo, kv_n, q_lo, q_n, rel in bodies[masked]:
                at = pl.ds(pl.multiple_of(qs + q_lo, q_n), q_n)
                q, do = q_ref[h, at, :], do_ref[h, at, :]      # [q_n, D]
                rows = slice(kv_lo, kv_lo + kv_n)
                lse, delta = (_part(t, 1, q_lo, q_n)
                              for t in (lse_row, delta_row))   # [1, q_n]
                st = _dot(_part(k, 0, kv_lo, kv_n), q, _NT)    # [kv_n, q_n]
                if not fold:
                    st = st * scale
                st = _bias_and_mask(st, rel, slope, d + q_lo - kv_lo, masked)
                pt = lax.exp(lax.sub(st, _bc(lse, st)))
                dv_scr[h, rows] = lax.add(
                    dv_scr[h, rows],
                    _dot(lax.convert_element_type(pt, do.dtype), do, _NN))
                dpt = _dot(_part(v, 0, kv_lo, kv_n), do, _NT)
                dst = lax.mul(pt, lax.sub(dpt, _bc(delta, dpt)))
                if not fold:
                    dst = dst * scale
                dk_scr[h, rows] = lax.add(
                    dk_scr[h, rows],
                    _dot(lax.convert_element_type(dst, q.dtype), q, _NN))

        if square:
            _when(_diagonal_here(j, nk, nq, lo, hi))(lambda: tile(j, True))
        elif causal:
            _run(crossed, plain, tile, True)
        if has_plain:
            _run(plain * (bq // sq), hi * (bq // sq), tile, False)

        @_when(last)
        def _finish():
            dk = dk_scr[h]
            dk_ref[h] = (dk * scale if fold else dk).astype(dk_ref.dtype)
            dv_ref[h] = dv_scr[h].astype(dv_ref.dtype)

        return carry

    lax.fori_loop(0, hb, head, 0)


def _flash_bwd(res, g, causal, alibi, scale, block_q, block_k, interpret):
    q, k, v, o, lse = res
    B, H, S, D = q.shape
    Sk = k.shape[2]
    BH = B * H
    p = _plan(BH, S, Sk, D, q.dtype.itemsize, block_q, block_k)
    bq, bk = p.bq, p.bk
    nq, nk = S // bq, Sk // bk
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [B,H,S]
    q3, k3, v3 = (t.reshape(BH, -1, D) for t in (q, k, v))
    do3 = g.reshape(BH, S, D)
    # lane-dense rows of statistics: a Q tile each for the dQ kernel, a
    # strip each for the dK/dV kernel's loop (two views of the same bytes)
    sq = _strip(bq)
    lse4, delta4 = (t.reshape(BH, nq, 1, bq) for t in (lse, delta))
    lse_s, delta_s = (t.reshape(BH, S // sq, 1, sq) for t in (lse, delta))
    slopes = _head_slopes(B, H, alibi)

    hb, tiles, nc = p.hb_dq, p.ck // bk, Sk // p.ck

    kv_chunk = _chunk_map(lambda i: _kv_bounds(i, bq, bk, nk, causal)[1],
                          tiles, nc, last=True)

    q_tile = pl.BlockSpec((hb, bq, D), lambda g_, i, c: (g_, i, 0))
    stat_tile = pl.BlockSpec((hb, 1, 1, bq), lambda g_, i, c: (g_, i, 0, 0))
    dq_kernel = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                                  alibi=alibi, bq=bq, bk=bk, nq=nq, nk=nk,
                                  tiles=tiles, hb=hb)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(BH // hb, nq, nc),
        in_specs=[_SLOPES, q_tile,
                  pl.BlockSpec((hb, p.ck, D), kv_chunk),
                  pl.BlockSpec((hb, p.ck, D), kv_chunk),
                  q_tile, stat_tile, stat_tile],
        out_specs=q_tile,
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((hb, bq, D), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(slopes, q3, k3, v3, do3, lse4, delta4)

    hb, tiles, nc = p.hb_dkv, p.cq // bq, S // p.cq

    q_chunk = _chunk_map(lambda j: _q_bounds(j, bq, bk, nq, causal)[0],
                         tiles, nc, last=False)

    def stat_chunk(g_, j, c):
        return q_chunk(g_, j, c) + (0,)

    kv_tile = pl.BlockSpec((hb, bk, D), lambda g_, j, c: (g_, j, 0))
    dkv_kernel = functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                                   alibi=alibi, bq=bq, bk=bk, nq=nq, nk=nk,
                                   tiles=tiles, hb=hb)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(BH // hb, nk, nc),
        in_specs=[_SLOPES,
                  pl.BlockSpec((hb, p.cq, D), q_chunk), kv_tile, kv_tile,
                  pl.BlockSpec((hb, p.cq, D), q_chunk),
                  pl.BlockSpec((hb, p.cq // sq, 1, sq), stat_chunk),
                  pl.BlockSpec((hb, p.cq // sq, 1, sq), stat_chunk)],
        out_specs=[kv_tile, kv_tile],
        out_shape=[jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, Sk, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((hb, bk, D), jnp.float32),
                        pltpu.VMEM((hb, bk, D), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(slopes, q3, k3, v3, do3, lse_s, delta_s)
    return (dq.reshape(B, H, S, D), dk.reshape(B, H, Sk, D), dv.reshape(B, H, Sk, D))


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _alibi_ref_bias(q, k, alibi):
    if not alibi:
        return None
    from deepspeed_tpu.models.layers import alibi_bias

    H, S, Sk = q.shape[1], q.shape[2], k.shape[2]
    # cross-length calls: query i sits at absolute position i + (Sk - S),
    # matching mha_reference's offset causal mask convention
    return alibi_bias(H, jnp.arange(S) + (Sk - S), jnp.arange(Sk))[None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = True, sm_scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
                    impl: Optional[str] = None, alibi: bool = False):
    """Memory-efficient attention.  q/k/v: [B, H, S, D] -> [B, H, S, D].

    ``alibi=True`` adds the per-head linear position bias in-kernel
    (slopes derived from H; reference ``(R) softmax.cu`` alibi mask path)."""
    out, _ = _fa_fwd(q, k, v, causal, sm_scale, block_q, block_k, impl, alibi)
    return out


def _fa_fwd(q, k, v, causal, sm_scale, block_q, block_k, impl, alibi=False):
    impl = resolve_impl(impl)
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if impl == "xla":
        out = mha_reference(q, k, v, causal=causal, sm_scale=scale,
                            bias=_alibi_ref_bias(q, k, alibi))
        return out, (q, k, v, out, None)
    o, lse = _flash_fwd(q, k, v, causal, alibi, scale, block_q, block_k,
                        interpret_flag(impl))
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, sm_scale, block_q, block_k, impl, alibi, res, g):
    impl = resolve_impl(impl)
    q, k, v, o, lse = res
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if impl == "xla" or lse is None:
        # jnp autodiff of the reference
        def f(q_, k_, v_):
            return mha_reference(q_, k_, v_, causal=causal, sm_scale=scale,
                                 bias=_alibi_ref_bias(q_, k_, alibi))

        _, vjp = jax.vjp(f, q, k, v)
        return vjp(g)
    return _flash_bwd((q, k, v, o, lse), g, causal, alibi, scale, block_q,
                      block_k, interpret_flag(impl))


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# EVA prefill-chunk attention (models/eva.py:cached_attention), forward only
# ---------------------------------------------------------------------------

class _ChunkPlan(NamedTuple):
    bq: int       # Q tile (the whole chunk up to DEFAULT_BLOCK_Q)
    sk: int       # rows of a window strip
    ss: int       # rows of a summary strip
    per_head: int  # VMEM bytes of one head's blocks and scratch


def _chunk_plan(s: int, rows: int, window: int, D: int,
                itemsize: int) -> _ChunkPlan:
    bq = pick_block(s, DEFAULT_BLOCK_Q)
    row, acc, stat = _token_bytes(D, itemsize)
    # q, o tiles; m, l rows and the acc scratch; the whole K and V view
    per_head = bq * (2 * 2 * row + 2 * stat + acc) + rows * 2 * 2 * row
    return _ChunkPlan(bq, _strip(bq), _strip(max(rows - window, 1)), per_head)


def eva_chunk_reference_reason(s: int, window: int, rows: int, head_dim: int,
                               itemsize: int = 2) -> Optional[str]:
    """Why :func:`eva_chunk_attention` cannot take these sizes (None = it
    can): ``s`` queries over a view of ``rows`` = ``window`` + summary rows.
    Its strips are whole lane tiles of the chunk, of the window and of the
    summary rows, and a head's whole view waits in VMEM."""
    for what, n in (("chunk", s), ("window", window),
                    ("summary rows", rows - window), ("head dim", head_dim)):
        if n <= 0 or n % _LANES:
            return f"{what} of {n} is not a multiple of the 128-lane tile"
    p = _chunk_plan(s, rows, window, head_dim, itemsize)
    if window % p.sk:
        return f"window {window} is not whole strips of {p.sk} rows"
    if p.per_head > _VMEM_BLOCK_BYTES:
        return (f"a head's view of {rows} rows takes {p.per_head} bytes of "
                f"VMEM, over the {_VMEM_BLOCK_BYTES} of a grid step")
    return None


def _chunk_bounds(w0, n_sum, p: _ChunkPlan, window: int):
    """What a Q tile whose first query sits at window row ``w0``, with
    ``n_sum`` summary rows to attend, walks (Python ints or traced scalars):

    - window strips ``[0, plain)`` lie wholly under its first query;
    - ``aligned``: ``w0`` is a strip's first row, so the tile the diagonal
      crosses, rows ``[w0, w0 + bq)``, takes :func:`_strips`' trimmed form;
      otherwise strips ``[plain, crossed)`` each take every query, masked;
    - summary strips ``[0, full)`` whole, then ``part`` rows of one more."""
    plain = w0 // p.sk
    return (plain, w0 % p.sk == 0,
            _clip((w0 + p.bq + p.sk - 1) // p.sk, 0, window // p.sk),
            n_sum // p.ss, n_sum % p.ss)


def eva_chunk_schedule(start: int, s: int, *, window: int, chunk: int,
                       rows: int, head_dim: int = 128, itemsize: int = 2,
                       real: Optional[int] = None,
                       impl: Optional[str] = None) -> Dict[str, object]:
    """What one head of one layer computes for the ``s`` queries at
    positions ``start ..`` (all in one window) over a view of ``rows`` rows,
    from the bounds the kernel's loops take (:func:`tile_schedule`'s way of
    counting).  ``kept``: the scores the two masks keep for the first
    ``real`` queries (all ``s`` by default), in closed form; ``dense``:
    ``s * rows``, what :func:`eva.cached_attention` computes; ``visited``:
    what the kernel does, or ``dense`` where ``reason`` says why it does not
    run (its sizes, or ``impl``, resolved as the call resolves it)."""
    n = s if real is None else real
    per, w0 = window // chunk, start % window
    n_sum = min((start // window) * per, rows - window)
    reason = eva_chunk_reference_reason(s, window, rows, head_dim, itemsize)
    if reason is None and resolve_impl(impl) == "xla":
        reason = "impl is xla"
    out = {"kept": n * w0 + n * (n + 1) // 2 + n * n_sum, "dense": s * rows,
           "reason": reason}
    if reason is not None:
        return dict(out, visited=out["dense"])
    p = _chunk_plan(s, rows, window, head_dim, itemsize)
    trimmed = sum(kv_n * q_n for _, kv_n, _, q_n in _strips(p.bq, p.bq, True))
    visited = 0
    for i in range(s // p.bq):
        plain, aligned, crossed, full, part = _chunk_bounds(
            w0 + i * p.bq, n_sum, p, window)
        visited += (plain * p.sk * p.bq
                    + (trimmed if aligned else (crossed - plain) * p.sk * p.bq)
                    + (full + (part > 0)) * p.ss * p.bq)
    return dict(out, visited=visited, block_q=p.bq, strip=p.sk,
                summary_strip=p.ss)


def _eva_chunk_kernel(start_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                      acc_scr, *, scale, window, per, p, hb):
    bq, sk, ss = p.bq, p.sk, p.ss
    fold = _folds(scale)
    start = start_ref[0]
    w0 = start % window + pl.program_id(1) * bq
    n_sum = _clip((start // window) * per, 0, k_ref.shape[1] - window)
    plain, aligned, crossed, full, part = _chunk_bounds(w0, n_sum, p, window)
    diagonal = _bodies(bq, bq, True, False, kv_dim=0)[True]
    rel = _rel(sk, bq, 0)                       # (KV row) - (query), a strip
    kv_row = lax.broadcasted_iota(jnp.int32, (ss, bq), 0)

    def head(h, carry):
        _softmax_init(h, m_scr, l_scr, acc_scr)
        q = q_ref[h]                                       # [bq, D]
        if fold:
            q = q * scale

        def block(row0, kv_n, q_lo=0, q_n=bq, rel=None, d=0):
            """View rows ``[row0, row0 + kv_n)`` against the tile's queries
            ``[q_lo, q_lo + q_n)``, keeping ``rel <= d`` where masked."""
            rows = pl.ds(pl.multiple_of(row0, _LANES), kv_n)
            st = _dot(k_ref[h, rows, :], _part(q, 0, q_lo, q_n), _NT)
            if not fold:
                st = st * scale
            st = _bias_and_mask(st, rel, None, d, rel is not None)
            _softmax_step(st, v_ref[h, rows, :], h, slice(q_lo, q_lo + q_n),
                          m_scr, l_scr, acc_scr)

        # 1. the window's rows up to the tile's last query
        _run(0, plain, lambda t, _: block(t * sk, sk), False)

        @pl.when(aligned)
        def _trimmed():
            for kv_lo, kv_n, q_lo, q_n, tri in diagonal:
                block(w0 + kv_lo, kv_n, q_lo, q_n, tri, q_lo - kv_lo)

        @pl.when(jnp.logical_not(aligned))
        def _crossed():
            _run(plain, crossed,
                 lambda t, _: block(t * sk, sk, rel=rel, d=w0 - t * sk), True)

        # 2. the summaries of the windows before, the same for every query
        _run(0, full, lambda t, _: block(window + t * ss, ss), False)
        pl.when(part > 0)(lambda: block(window + full * ss, ss, rel=kv_row,
                                        d=part - 1))

        o_ref[h] = jnp.transpose(acc_scr[h] / l_scr[h]).astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, hb, head, 0)


def eva_chunk_attention(q, kview, vview, start, *, window: int, chunk: int,
                        sm_scale: Optional[float] = None,
                        impl: Optional[str] = None):
    """EVA attention of one prefill chunk (``models/eva.py``), the scores
    never leaving VMEM.  q [B, H, s, Dh] at the shared absolute positions
    ``start .. start + s - 1``, all in ONE window (``start`` a traced
    scalar); the logical views [B, H, W + summary rows, Dh] with the chunk's
    own rows already written.  One softmax over the window rows up to each
    query and the ``(start // W) * W/C`` summary rows of the windows before.

    A grid step holds ``hb`` heads' whole views and one Q tile and walks two
    runs of key strips (:func:`_chunk_bounds`), so a strip past either bound
    costs no arithmetic.  Sizes the kernel cannot take
    (:func:`eva_chunk_reference_reason`) run :func:`eva.cached_attention`."""
    from deepspeed_tpu.models import eva

    impl = resolve_impl(impl)
    B, H, s, D = q.shape
    rows = kview.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / (D ** 0.5)
    start = jnp.asarray(start, jnp.int32)
    impl = kernel_or_reference(
        "eva_chunk_attention", impl, eva_chunk_reference_reason(
            s, window, rows, D, kview.dtype.itemsize))
    if impl == "xla":
        return eva.cached_attention(q, kview, vview, start + jnp.arange(s),
                                    window=window, chunk=chunk, scale=scale)
    BH = B * H
    p = _chunk_plan(s, rows, window, D, kview.dtype.itemsize)
    hb = _heads(BH, p.per_head)
    q_tile = pl.BlockSpec((hb, p.bq, D), lambda g, i, start_ref: (g, i, 0))
    view = pl.BlockSpec((hb, rows, D), lambda g, i, start_ref: (g, 0, 0))
    o = pl.pallas_call(
        functools.partial(_eva_chunk_kernel, scale=scale, window=window,
                          per=window // chunk, p=p, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(BH // hb, s // p.bq),
            in_specs=[q_tile, view, view], out_specs=q_tile,
            scratch_shapes=[pltpu.VMEM((hb, 1, p.bq), jnp.float32),
                            pltpu.VMEM((hb, 1, p.bq), jnp.float32),
                            pltpu.VMEM((hb, D, p.bq), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((BH, s, D), q.dtype),
        interpret=interpret_flag(impl),
        name="eva_chunk_attention",
    )(start.reshape(1), q.astype(kview.dtype).reshape(BH, s, D),
      kview.reshape(BH, rows, D), vview.reshape(BH, rows, D))
    return o.reshape(B, H, s, D)


# ---------------------------------------------------------------------------
# latent-attention (MLA) prefill-chunk attention (models/kda_mla.py), forward
# only: afmoe.attend(expand=) with the scores and the decompressed keys and
# values never leaving VMEM
# ---------------------------------------------------------------------------

# float32 scores of ONE strip a body of the latent chunk kernel may make
# (2 MiB): it sets the rows of a strip under the chunk, which take no mask
# and are the longer the fewer the chunk's queries
_MLA_SCORES = 512 * 1024


class _MlaPlan(NamedTuple):
    bq: int       # the chunk's queries, padded to whole lane tiles: ONE tile
    sk: int       # rows of a strip of the chunk's own rows (the masked ones)
    sp: int       # rows of a strip under the chunk (whole ``sk`` strips)
    kb: int       # cache rows a grid step holds (whole ``sp`` strips)
    hb: int       # heads a grid step (0: not even one fits)


def _mla_plan(s: int, rows: int, H: int, kv: int, n: int, r: int, v: int,
              row_width: int, itemsize: int) -> _MlaPlan:
    bq = round_up(s, _LANES)
    sk = sp = _strip(bq)
    kb = pick_block(rows, max(DEFAULT_BLOCK_K, sk), minimum=sk)
    while kb % (2 * sp) == 0 and 2 * sp * bq <= _MLA_SCORES:
        sp *= 2
    # a head's q and o tiles and W_kvb columns (double-buffered), its m, l
    # rows and acc scratch; beside them the block of cache rows
    per_head = (bq * 2 * (round_up(n + r, 16) + v) * itemsize
                + bq * (v * 4 + 2 * 8 * 4) + 2 * kv * (n + v) * itemsize)
    room = _VMEM_BLOCK_BYTES - 2 * kb * row_width * itemsize
    return _MlaPlan(bq, sk, sp, kb,
                    max([d for d in range(1, H + 1)
                         if H % d == 0 and d * per_head <= room], default=0))


def mla_chunk_reference_reason(s: int, rows: int, H: int, kv: int, n: int,
                               r: int, v: int, row_width: int,
                               itemsize: int = 2) -> Optional[str]:
    """Why :func:`mla_chunk_attention` cannot take these sizes (None = it
    can): ``s`` queries of ``H`` heads of ``n + r`` over a view of ``rows``
    cache rows ``row_width`` wide, latent ``kv``, values of ``v``.  The
    latent, the heads' two parts of ``W_kvb``, the row and the view are
    whole 128-lane tiles (the rotary part is the tail of a tile), the chunk
    is one Q tile, and a head's blocks wait in VMEM beside a block of
    rows."""
    for what, d in (("latent rank", kv), ("nope dim", n), ("value dim", v),
                    ("row width", row_width), ("view", rows)):
        if d <= 0 or d % _LANES:
            return f"{what} of {d} is not a multiple of the 128-lane tile"
    if not 0 < r <= row_width - kv:
        return (f"rotary part of {r} does not fit the {row_width - kv} values "
                f"a row of {row_width} leaves beside the latent's {kv}")
    if not 0 < s <= DEFAULT_BLOCK_Q:
        return f"chunk of {s} queries is not one Q tile of {DEFAULT_BLOCK_Q}"
    if not _mla_plan(s, rows, H, kv, n, r, v, row_width, itemsize).hb:
        return (f"one head's blocks and a block of {row_width}-wide rows take "
                f"over the {_VMEM_BLOCK_BYTES} bytes of VMEM of a grid step")
    return None


def _mla_bounds(start, p: _MlaPlan, rows: int):
    """What the queries at positions ``start .. start + p.bq - 1`` walk of a
    view of ``rows`` cache rows (Python ints or traced scalars): ``long``
    strips of ``p.sp`` rows, then strips of ``p.sk`` rows up to strip
    ``plain``, lie wholly under the first query and take no mask;
    ``aligned``: ``start`` is a strip's first row, so the chunk's own rows
    are :func:`_strips`' trimmed tile (each ``p.sk`` strip against the
    queries from its own first row on); otherwise strips ``[plain, visit)``
    each take every query, masked.  No row at or past ``visit * p.sk`` is
    fetched or computed: the grid walks the first ``blocks`` blocks of
    ``p.kb`` rows."""
    visit = _clip((start + p.bq + p.sk - 1) // p.sk, 0, rows // p.sk)
    return (start // p.sp, start // p.sk, start % p.sk == 0, visit,
            (visit * p.sk + p.kb - 1) // p.kb)


def mla_chunk_schedule(start: int, s: int, rows: int, *, heads: int, kv: int,
                       nope: int, rot: int, v_dim: int, row_width: int,
                       itemsize: int = 2,
                       impl: Optional[str] = None) -> Dict[str, object]:
    """What one latent layer's chunk attention walks for the ``s`` queries
    at positions ``start ..`` over a view of ``rows`` cache rows.
    ``visited``: the rows decompressed to per-head keys and values, from the
    bounds the kernel's grid and loops take (whole strips up to the padded
    chunk's last query), or, where ``reason`` says why the kernel does not
    run (its sizes, or ``impl``, resolved as the call resolves it), what
    :func:`afmoe.attend` visits: whole key blocks (``afmoe.keys_visited``)."""
    from deepspeed_tpu.models import afmoe

    reason = mla_chunk_reference_reason(s, rows, heads, kv, nope, rot, v_dim,
                                        row_width, itemsize)
    if reason is None and resolve_impl(impl) == "xla":
        reason = "impl is xla"
    if reason is not None:
        return {"visited": afmoe.keys_visited(rows, start + s),
                "reason": reason}
    p = _mla_plan(s, rows, heads, kv, nope, rot, v_dim, row_width, itemsize)
    *_, visit, blocks = _mla_bounds(start, p, rows)
    return {"visited": visit * p.sk, "reason": None, "block_q": p.bq,
            "strip": p.sk, "strip_under_the_chunk": p.sp,
            "rows_per_step": p.kb, "heads_per_step": p.hb,
            "grid_steps": heads // p.hb * blocks}


def _mla_chunk_kernel(at_ref, q_ref, rows_ref, w_ref, o_ref, m_scr, l_scr,
                      acc_scr, *, scale, p, rows, kv, n, r, v):
    bq, sk, sp, kb, hb = p
    c, start = pl.program_id(1), at_ref[0]
    long, plain, aligned, visit, blocks = _mla_bounds(start, p, rows)
    lo, hi = c * (kb // sk), (c + 1) * (kb // sk)    # this step's strips
    diagonal = _bodies(bq, bq, True, False, kv_dim=0)[True]
    rel = _rel(sk, bq, 0)                        # (cache row) - (query)

    def head(h, carry):
        pl.when(c == 0)(lambda: _softmax_init(h, m_scr, l_scr, acc_scr))
        qt = q_ref[h]                                          # [n + r, bq]
        cols = pl.ds(pl.multiple_of(h * (n + v), _LANES), n + v)

        def block(row0, kv_n=sk, q_lo=0, q_n=bq, rel=None, d=0):
            """The ``kv_n`` rows from this step's row ``row0`` against the
            tile's queries ``[q_lo, q_lo + q_n)``, keeping ``rel <= d``
            where masked: decompressed as ``mla_decompress`` does it
            (float32 sums rounded to the cache's dtype), then ``attend``'s
            block."""
            at = pl.ds(pl.multiple_of(row0, _LANES), kv_n)
            kn_v = lax.convert_element_type(
                _dot(rows_ref[at, :kv], w_ref[:, cols], _NN), qt.dtype)
            k = lax.concatenate([_part(kn_v, 1, 0, n),
                                 rows_ref[at, kv:kv + r]], 1)  # [kv_n, n + r]
            st = _dot(k, _part(qt, 1, q_lo, q_n), _NN) * scale
            st = _bias_and_mask(st, rel, None, d, rel is not None)
            _softmax_step(st, _part(kn_v, 1, n, v), h,
                          slice(q_lo, q_lo + q_n), m_scr, l_scr, acc_scr)

        # 1. the rows under the chunk: long strips, then (where the chunk
        #    does not start at a long strip's first row) the rest by sk
        _run(c * (kb // sp), _clip(long, c * (kb // sp), (c + 1) * (kb // sp)),
             lambda u, _: block(u * sp - c * kb, sp), False)
        if sp != sk:
            _run(_clip(long * (sp // sk), lo, hi), _clip(plain, lo, hi),
                 lambda t, _: block((t - lo) * sk), False)
        # 2. the chunk's own rows: the trimmed tile, or masked strips
        for kv_lo, _, q_lo, q_n, tri in diagonal:
            row = start + kv_lo
            pl.when(aligned & (row // kb == c))(
                lambda row=row, q_lo=q_lo, q_n=q_n, tri=tri: block(
                    row - c * kb, sk, q_lo, q_n, tri, 0))
        _run(_clip(plain, lo, hi),
             _clip(jnp.where(aligned, plain, visit), lo, hi),
             lambda t, _: block((t - lo) * sk, rel=rel, d=start - t * sk),
             True)

        @pl.when(c == blocks - 1)
        def _finish():
            o_ref[:, pl.ds(pl.multiple_of(h * v, _LANES), v)] = jnp.transpose(
                acc_scr[h] / l_scr[h]).astype(o_ref.dtype)

        return carry

    lax.fori_loop(0, hb, head, 0)


@functools.partial(jax.jit, static_argnames=("nope", "scale", "interpret"))
def _mla_chunk_call(q, rows, wkvb, at, *, nope: int, scale: float,
                    interpret: bool):
    """The kernel on ``at`` = (start, layer), both traced: jitted of its own
    so that a chunk program's latent layers, which differ in ``layer``
    alone, are ONE traced and lowered function called five times (a call
    site costs 0.1-0.2 s of tracing and lowering that no cache keeps, and a
    serve process lowers every layer of every bucket at set-up)."""
    s, H, D = q.shape
    L, P, W = rows.shape
    kv, n, v = wkvb.shape[0], nope, wkvb.shape[2] - nope
    p = _mla_plan(s, P, H, kv, n, D - n, v, W, rows.dtype.itemsize)
    qt = q.astype(rows.dtype).transpose(1, 2, 0)           # [H, n + r, s]
    if p.bq != s:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, p.bq - s)))
    by_head = lambda g, c, at_ref: (g, 0, 0)
    head_cols = lambda g, c, at_ref: (0, g)
    o = pl.pallas_call(
        functools.partial(_mla_chunk_kernel, scale=scale, p=p, rows=P, kv=kv,
                          n=n, r=D - n, v=v),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H // p.hb, _mla_bounds(at[0], p, P)[4]),
            in_specs=[pl.BlockSpec((p.hb, D, p.bq), by_head),
                      pl.BlockSpec((p.kb, W), lambda g, c, at_ref: (
                          at_ref[1] * (P // p.kb) + c, 0)),
                      pl.BlockSpec((kv, p.hb * (n + v)), head_cols)],
            out_specs=pl.BlockSpec((p.bq, p.hb * v), head_cols),
            scratch_shapes=[pltpu.VMEM((p.hb, 1, p.bq), jnp.float32),
                            pltpu.VMEM((p.hb, 1, p.bq), jnp.float32),
                            pltpu.VMEM((p.hb, v, p.bq), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((p.bq, H * v), qt.dtype),
        interpret=interpret,
        name="mla_chunk_attention",
    )(at, qt, rows.reshape(L * P, W),
      wkvb.astype(rows.dtype).reshape(kv, -1))
    return o[:s].reshape(s, H, v)


def mla_chunk_attention(q, rows, wkvb, start, *, nope: int, scale: float,
                        layer: int = 0, impl: Optional[str] = None):
    """Latent attention of one prefill chunk (``models/kda_mla.py``): what
    ``afmoe.attend(expand=mla_decompress)`` computes, in one kernel that
    decompresses each strip of cache rows on chip, so that neither the
    per-head keys and values nor the scores leave VMEM.  q [s, H, n + r]
    (as ``mla_project`` returns it, rotated where the model rotates) at
    positions ``start .. start + s - 1`` (``start`` a traced scalar);
    ``rows`` [layers, positions, row_width] ONE slot's cache rows ``[c | k_r
    | 0]``, of which layer ``layer``'s are attended, with the chunk's own
    rows written (the whole view goes in and the index map picks the layer:
    a slice of it would be copied first, 21 MB a layer a chunk in A.X-K1's
    cell); ``wkvb`` [kv, H, n + v], a head's first ``nope`` = n columns its
    keys, the rest its values (``kda_mla._wkvb``'s split).  Returns [s, H,
    v], what ``W_o`` takes.

    A grid step holds ``hb`` heads' queries (the chunk padded to whole lane
    tiles: one Q tile; TRANSPOSED, ``[n + r, s]`` a head, which is how XLA
    lays the query projection out for the reference's own matmul, so the
    transposition costs nothing and the scores are a plain ``k q^T``), their
    columns of ``W_kvb`` and of the output, and one block of rows, and walks
    the block's strips (:func:`_mla_bounds`); the grid's second extent is
    read at run time, the blocks up to the one the chunk's last query sees,
    so rows past the prompt cost no grid step, no fetch and no arithmetic.
    Per head and strip, in ``attend``'s and ``mla_decompress``'s precisions
    and order of roundings: ``[k_n | v] = c W_kvb[h]`` summed in float32 and
    ROUNDED TO THE CACHE'S DTYPE; the scores ``[k_n | k_r] q^T`` in float32,
    times ``scale``, masked by cache row against query position in the
    strips that hold the chunk's own rows only; the online softmax in
    float32; ``p`` ROUNDED TO THE CACHE'S DTYPE for ``p v``, summed in
    float32; the quotient rounded once, to ``q``'s dtype.  What differs from
    ``attend`` is the step of the online softmax (a strip, not
    ``KEY_BLOCK``), so the running maximum at which ``p`` is rounded.

    ``afmoe.attend`` is the reference: it runs for ``impl="xla"`` (the CPU)
    and for the sizes :func:`mla_chunk_reference_reason` names."""
    from deepspeed_tpu.models import afmoe, kda_mla

    impl = resolve_impl(impl)
    s, H, D = q.shape
    _, P, W = rows.shape
    kv, n, v = wkvb.shape[0], nope, wkvb.shape[2] - nope
    start = jnp.asarray(start, jnp.int32)
    impl = kernel_or_reference(
        "mla_chunk_attention", impl, mla_chunk_reference_reason(
            s, P, H, kv, n, D - n, v, W, rows.dtype.itemsize))
    if impl == "xla":
        wk, wv = wkvb[..., :n], wkvb[..., n:]
        return afmoe.attend(
            q.transpose(1, 0, 2)[None],
            [(rows[layer][None, None], None, jnp.arange(P))],
            start + jnp.arange(s), window=0, scale=scale, live_keys=start + s,
            expand=lambda rb: tuple(
                t[:, 0].transpose(0, 2, 1, 3)
                for t in kda_mla.mla_decompress(rb, wk, wv, D - n))
        )[0].transpose(1, 0, 2)
    return _mla_chunk_call(q, rows, wkvb, jnp.stack([start, jnp.int32(layer)]),
                           nope=nope, scale=scale,
                           interpret=interpret_flag(impl))


# ---------------------------------------------------------------------------
# a learned selection of a latent layer's keys (models/kda_mla.py's indexer),
# forward only: a chunk's index scores, and its attention under the selection
# ---------------------------------------------------------------------------

_DSA_BLOCK_K = 512        # index keys / cache rows a grid step
_DSA_BLOCK_Q = 128        # queries a grid step of the index scores


def dsa_chunk_reference_reason(s: int, rows: int, *widths: int
                               ) -> Optional[str]:
    """Why the two kernels of a chunk under a selection cannot take these
    sizes (None = they can): every width whole 128-lane tiles, the view
    whole blocks of rows and no shorter than the chunk padded to a lane
    tile (the calls pad a shorter bucket's queries)."""
    for what, d in (("width", w) for w in widths):
        if d <= 0 or d % _LANES:
            return f"{what} of {d} is not a multiple of the 128-lane tile"
    if rows % _DSA_BLOCK_K or rows < round_up(s, _LANES):
        return (f"view of {rows} rows is not whole blocks of {_DSA_BLOCK_K} "
                f"that hold a chunk of {s}")
    return None


def _index_scores_chunk_ref(q, w, keys, start):
    """[P, s] float32: ``sum_g w[t, g] relu(q[t, g] . keys[j])`` at [j, t]
    for ``j <= start + t``, ``NEG_INF`` elsewhere; a block of keys at a
    time (the per-head products of ALL keys would be [G, s, P])."""
    s, G, d = q.shape
    P = keys.shape[0]
    kb = min(P, 1024)
    pad = -P % kb
    keys = jnp.pad(keys, ((0, pad), (0, 0)))

    def block(kblk):
        sc = jnp.einsum("kd,tgd->kgt", kblk, q.astype(kblk.dtype),
                        preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(sc) * w.T[None], axis=1)      # [kb, s]

    out = lax.map(block, keys.reshape(-1, kb, d)).reshape(-1, s)[:P]
    ok = jnp.arange(P)[:, None] <= start + jnp.arange(s)[None, :]
    return jnp.where(ok, out, NEG_INF)


def _dsa_index_kernel(at_ref, q_ref, w_ref, k_ref, old_ref, o_ref, *, G, bk,
                      bq):
    del old_ref                   # what the grid does not visit keeps it
    i, c = pl.program_id(0), pl.program_id(1)
    keys = k_ref[...]                                          # [bk, d]

    def head(g, acc):
        st = _dot(keys, q_ref[g], _NN)                         # [bk, bq]
        return acc + jnp.maximum(st, 0.0) * w_ref[g]

    acc = lax.fori_loop(0, G, head, jnp.zeros((bk, bq), jnp.float32))
    key = c * bk + lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    qry = at_ref[0] + i * bq + lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    o_ref[...] = jnp.where(key <= qry, acc, NEG_INF)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _dsa_index_call(q, w, keys, at, *, interpret: bool):
    s0, G, d = q.shape
    L, P, _ = keys.shape
    s = round_up(s0, _LANES)              # a short bucket: idle queries
    bk, bq = _DSA_BLOCK_K, _DSA_BLOCK_Q
    qt = jnp.pad(q.astype(keys.dtype).transpose(1, 2, 0),      # [G, d, s]
                 ((0, 0), (0, 0), (0, s - s0)))
    wt = jnp.pad(w.astype(jnp.float32).T[:, None, :],          # [G, 1, s]
                 ((0, 0), (0, 0), (0, s - s0)))
    blocks = jnp.minimum((at[0] + s + bk - 1) // bk, P // bk)
    out = pl.BlockSpec((bk, bq), lambda i, c, at_ref: (c, i))
    return pl.pallas_call(
        functools.partial(_dsa_index_kernel, G=G, bk=bk, bq=bq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(s // bq, blocks),
            in_specs=[pl.BlockSpec((G, d, bq), lambda i, c, at_ref: (0, 0, i)),
                      pl.BlockSpec((G, 1, bq), lambda i, c, at_ref: (0, 0, i)),
                      pl.BlockSpec((bk, d), lambda i, c, at_ref: (
                          at_ref[1] * (P // bk) + c, 0)),
                      out],
            out_specs=out),
        out_shape=jax.ShapeDtypeStruct((P, s), jnp.float32),
        input_output_aliases={4: 0},
        interpret=interpret,
        name="dsa_index_scores_chunk",
    )(at, qt, wt, keys.reshape(L * P, d),
      jnp.full((P, s), NEG_INF, jnp.float32))[:, :s0]


def dsa_index_scores_chunk(q, w, keys, start, *, layer: int = 0,
                           impl: Optional[str] = None):
    """The index scores of one prefill chunk, TRANSPOSED (a key a row, as
    the chunk kernels lay their scores): ``q`` [s, G, d] the chunk's index
    queries (rotated) at positions ``start ..``, ``w`` [s, G] float32 their
    head weights, ``keys`` [layers, positions, d] ONE slot's index keys with
    the chunk's own written, of which layer ``layer``'s are scored.  Returns
    [positions, s] float32: ``sum_g w[t, g] relu(q[t, g] . k(j))`` at [j,
    t] where ``j <= start + t``, ``NEG_INF`` elsewhere.  The per-head
    products ([G, s, positions]: 12.9 GB at 64 heads, a chunk of 1,024 and
    48 k positions) never exist: a grid step holds a block of keys and a
    block of queries and sums the heads in VMEM; the grid's second extent is
    read at run time, the blocks up to the chunk's last query, and what it
    does not visit keeps the ``NEG_INF`` the output is aliased onto."""
    impl = resolve_impl(impl)
    s, G, d = q.shape
    start = jnp.asarray(start, jnp.int32)
    impl = kernel_or_reference(
        "dsa_index_scores_chunk", impl,
        dsa_chunk_reference_reason(s, keys.shape[1], d))
    if impl == "xla":
        return _index_scores_chunk_ref(q, w, keys[layer], start)
    return _dsa_index_call(q, w, keys, jnp.stack([start, jnp.int32(layer)]),
                           interpret=interpret_flag(impl))


def _dsa_attention_ref(q, rows, wkvb, keep, *, nope: int, scale: float):
    """``afmoe.attend(expand=mla_decompress)``'s roundings under an additive
    bias ``keep`` [P, s]: a block of rows at a time, online softmax."""
    from deepspeed_tpu.models import kda_mla

    s, H, D = q.shape
    P = rows.shape[0]
    wk, wv = wkvb[..., :nope], wkvb[..., nope:]
    kb = min(P, 1024)
    pad = -P % kb
    rows = jnp.pad(rows, ((0, pad), (0, 0)))
    keep = jnp.pad(keep.astype(jnp.float32), ((0, pad), (0, 0)),
                   constant_values=NEG_INF)
    v_dim = wv.shape[-1]

    def block(carry, xs):
        m, l, acc = carry
        rb, bias = xs
        k, v = kda_mla.mla_decompress(rb, wk, wv, D - nope)    # [kb, H, .]
        sc = jnp.einsum("qhd,khd->hqk", q.astype(k.dtype), k,
                        preferred_element_type=jnp.float32) * scale
        sc = jnp.maximum(sc + bias.T[None], NEG_INF)
        ok = (bias.T > NEG_INF / 2)[None]
        m_new = jnp.maximum(m, sc.max(-1))
        p = jnp.where(ok, jnp.exp(sc - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hqk,khv->hqv", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return (m_new, alpha * l + p.sum(-1), acc), None

    init = (jnp.full((H, s), NEG_INF, jnp.float32),
            jnp.zeros((H, s), jnp.float32),
            jnp.zeros((H, s, v_dim), jnp.float32))
    (_, l, acc), _ = lax.scan(
        block, init, (rows.reshape(-1, kb, rows.shape[-1]),
                      keep.reshape(-1, kb, s)))
    o = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return o.transpose(1, 0, 2).astype(q.dtype)


def _dsa_heads_per_step(s: int, H: int, kv: int, n: int, r: int, v: int,
                        W: int, itemsize: int) -> int:
    per_head = (s * 2 * (round_up(n + r, 16) + v) * itemsize
                + s * (v * 4 + 2 * 8 * 4) + 2 * kv * (n + v) * itemsize)
    room = _VMEM_BLOCK_BYTES - 2 * _DSA_BLOCK_K * (W + s) * itemsize \
        - _DSA_BLOCK_K * s * 8
    return max([d for d in range(1, H + 1)
                if H % d == 0 and d * per_head <= room], default=1)


def _dsa_chunk_kernel(at_ref, q_ref, rows_ref, w_ref, keep_ref, o_ref, m_scr,
                      l_scr, acc_scr, *, scale, hb, kv, n, r, v, blocks_max):
    c = pl.program_id(1)
    bias = keep_ref[...].astype(jnp.float32)                   # [bk, s]
    bk, s = bias.shape

    def head(h, carry):
        pl.when(c == 0)(lambda: _softmax_init(h, m_scr, l_scr, acc_scr))
        qt = q_ref[h]                                          # [n + r, s]
        cols = pl.ds(pl.multiple_of(h * (n + v), _LANES), n + v)
        # decompressed as ``mla_decompress`` does it: float32 sums rounded
        # to the cache's dtype
        kn_v = lax.convert_element_type(
            _dot(rows_ref[:, :kv], w_ref[:, cols], _NN), qt.dtype)
        k = lax.concatenate([_part(kn_v, 1, 0, n), rows_ref[:, kv:kv + r]], 1)
        st = _dot(k, qt, _NN) * scale + bias                   # [bk, s]
        st = lax.max(st, lax.full_like(st, NEG_INF))
        _softmax_step(st, _part(kn_v, 1, n, v), h, slice(0, s), m_scr, l_scr,
                      acc_scr)

        # the grid's last block (its extent, as the call reads it)
        @pl.when(c == jnp.minimum((at_ref[0] + s + bk - 1) // bk,
                                  blocks_max) - 1)
        def _finish():
            o_ref[:, pl.ds(pl.multiple_of(h * v, _LANES), v)] = jnp.transpose(
                acc_scr[h] / l_scr[h]).astype(o_ref.dtype)

        return carry

    lax.fori_loop(0, hb, head, 0)


@functools.partial(jax.jit, static_argnames=("nope", "scale", "interpret"))
def _dsa_chunk_call(q, rows, wkvb, keep, at, *, nope: int, scale: float,
                    interpret: bool):
    s0, H, D = q.shape
    L, P, W = rows.shape
    kv, n, v = wkvb.shape[0], nope, wkvb.shape[2] - nope
    bk = _DSA_BLOCK_K
    s = round_up(s0, _LANES)      # a short bucket: idle queries, every key
    hb = _dsa_heads_per_step(s, H, kv, n, D - n, v, W, rows.dtype.itemsize)
    qt = jnp.pad(q.astype(rows.dtype).transpose(1, 2, 0),      # [H, n + r, s]
                 ((0, 0), (0, 0), (0, s - s0)))
    keep = jnp.pad(keep, ((0, 0), (0, s - s0)))
    by_head = lambda g, c, at_ref: (g, 0, 0)
    head_cols = lambda g, c, at_ref: (0, g)
    o = pl.pallas_call(
        functools.partial(_dsa_chunk_kernel, scale=scale, hb=hb, kv=kv, n=n,
                          r=D - n, v=v, blocks_max=P // bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H // hb, jnp.minimum((at[0] + s + bk - 1) // bk, P // bk)),
            in_specs=[pl.BlockSpec((hb, D, s), by_head),
                      pl.BlockSpec((bk, W), lambda g, c, at_ref: (
                          at_ref[1] * (P // bk) + c, 0)),
                      pl.BlockSpec((kv, hb * (n + v)), head_cols),
                      pl.BlockSpec((bk, s), lambda g, c, at_ref: (c, 0))],
            out_specs=pl.BlockSpec((s, hb * v), head_cols),
            scratch_shapes=[pltpu.VMEM((hb, 1, s), jnp.float32),
                            pltpu.VMEM((hb, 1, s), jnp.float32),
                            pltpu.VMEM((hb, v, s), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((s, H * v), qt.dtype),
        interpret=interpret,
        name="dsa_chunk_attention",
    )(at, qt, rows.reshape(L * P, W),
      wkvb.astype(rows.dtype).reshape(kv, -1), keep)
    return o[:s0].reshape(s0, H, v)


def dsa_chunk_attention(q, rows, wkvb, keep, start, *, nope: int,
                        scale: float, layer: int = 0,
                        impl: Optional[str] = None):
    """Latent attention of one prefill chunk over the SELECTED keys:
    :func:`mla_chunk_attention`'s operands and roundings (each block of
    cache rows decompressed on chip to per-head keys and values, the scores
    ``[k_n | k_r] q^T`` in float32, the online softmax, ``p`` rounded to the
    cache's dtype for ``p v``) under ``keep`` [positions, s] bfloat16, an
    additive bias a (key, query) pair: 0 where query t attends key j,
    ``NEG_INF`` where it does not (``kda_mla.select_keys``; it carries the
    causal mask, so every block is a masked one and the chunk's own rows
    take no path of their own).  The selection differs by query, so no key
    is skipped for the chunk as a whole: every row up to the chunk's last
    query is decompressed and scored, a block of ``_DSA_BLOCK_K`` rows a
    grid step, the second extent of the grid read at run time.  Returns [s,
    H, v].  Every query attends at least one key (itself, or 2,048)."""
    impl = resolve_impl(impl)
    s, H, D = q.shape
    _, P, W = rows.shape
    kv, n, v = wkvb.shape[0], nope, wkvb.shape[2] - nope
    start = jnp.asarray(start, jnp.int32)
    impl = kernel_or_reference(
        "dsa_chunk_attention", impl,
        dsa_chunk_reference_reason(s, P, kv, n, v, W))
    if impl == "xla":
        return _dsa_attention_ref(q, rows[layer], wkvb, keep, nope=nope,
                                  scale=scale)
    return _dsa_chunk_call(q, rows, wkvb, keep,
                           jnp.stack([start, jnp.int32(layer)]), nope=nope,
                           scale=scale, interpret=interpret_flag(impl))
