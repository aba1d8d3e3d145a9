"""Blockwise (flash) attention Pallas kernels, forward + backward.

TPU-native replacement for the reference's fused attention path
(``csrc/transformer/softmax_kernels.cu`` + strided-batch GEMM attention in
``csrc/includes/strided_batch_gemm.h``, and the inference ``softmax.cu``;
SURVEY.md §2.2): instead of materializing the [S, S] score matrix between two
cuBLAS GEMMs, the kernel streams KV blocks through VMEM with an online
softmax, so memory is O(S·D) and the MXU sees back-to-back matmuls.

Layout: q, k, v are [B, H, S, D].  Causal masking supported; optional
additive bias (e.g. ALiBi) can be folded by the caller via the bias arg of the
jnp reference for now.  All softmax math in fp32 (matching the reference
kernels' accumulation).

The TPU grid executes sequentially with the last axis fastest, so the KV-block
axis is the innermost grid dimension and the running (m, l, acc) state lives
in VMEM scratch across those grid steps — the Pallas-idiomatic form of the
flash-attention inner loop.

Backward follows the standard recompute scheme: saved LSE from forward;
``delta = rowsum(dO ∘ O)``; one kernel accumulates dQ over KV blocks, another
accumulates dK/dV over Q blocks.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.common import interpret_flag, pick_block, resolve_impl

# 512-token tiles: 8× fewer grid steps than 128 and MXU-shaped [512, 512]
# score matmuls; VMEM per step stays < 4MB at D=128. Measured 3× faster than
# 128-tiles on v5e at S=1024 (see bench notes in git history).
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


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
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, slope_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, alibi, block_q,
                block_k, nk):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    qb = pl.program_id(1)
    q_start = qb * block_q
    k_start = kb * block_k

    run = True
    if causal:
        # whole KV block strictly above the diagonal -> nothing to do
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        # MXU matmuls take the native (bf16) operands; only the accumulator
        # and softmax statistics are fp32 — fp32 MXU inputs would quarter
        # throughput for no accuracy gain over fp32 accumulation.
        q = q_ref[0]  # [BQ, D]
        k = k_ref[0]  # [BK, D]
        v = v_ref[0]  # [BK, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # [BQ, BK]
        if causal or alibi:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if alibi:
            s = s + slope_ref[0, 0, 0] * (cols - rows).astype(jnp.float32)
        if causal:
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_scr[:]                              # [BQ, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                         # [BQ, BK]
        alpha = jnp.exp(m_prev - m_new)                # [BQ, 1]
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(kb == nk - 1)
    def _finish():
        l = l_scr[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        # lse layout (BH, S, 1): the in-kernel block is the (bq, 1) column
        # vector itself — no relayout needed (see module docstring).
        lse_ref[0] = m_scr[:] + jnp.log(safe_l)


def _head_slopes(B: int, H: int, alibi: bool):
    """[B*H, 1, 1] per-grid-row ALiBi slopes (zeros when off — the argument
    shape must be static for the shared kernel signature).  3-D so the
    block's LAST TWO dims are full-size: Mosaic requires partial block dims
    in the last two positions to be (8, 128)-tile aligned."""
    if not alibi:
        return jnp.zeros((B * H, 1, 1), jnp.float32)
    from deepspeed_tpu.models.layers import alibi_slopes

    return jnp.tile(alibi_slopes(H), B).reshape(B * H, 1, 1)


def _flash_fwd(q, k, v, causal, alibi, scale, block_q, block_k, interpret):
    B, H, S, D = q.shape
    Sk = k.shape[2]
    bq = pick_block(S, block_q, minimum=8)
    bk = pick_block(Sk, block_k, minimum=8)
    nq, nk = S // bq, Sk // bk
    BH = B * H
    q3 = q.reshape(BH, S, D)
    k3 = k.reshape(BH, Sk, D)
    v3 = v.reshape(BH, Sk, D)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               alibi=alibi, block_q=bq, block_k=bk, nk=nk)
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
                  pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
                  pl.BlockSpec((1, 1, 1), lambda b, i, j: (b, 0, 0))],
        out_specs=[pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((BH, S, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, S, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q3, k3, v3, _head_slopes(B, H, alibi))
    return o.reshape(B, H, S, D), lse.reshape(B, H, S)


def _col(x_ref):
    """Read a (1, bq, 1) stat block as a (bq, 1) column."""
    return x_ref[0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, slope_ref,
                   dq_ref, dq_scr, *, scale, causal, alibi, block_q, block_k,
                   nk):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = pl.program_id(1) * block_q
    k_start = kb * block_k
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal or alibi:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if alibi:
            s = s + slope_ref[0, 0, 0] * (cols - rows).astype(jnp.float32)
        if causal:
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_scr[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    slope_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale,
                    causal, alibi, block_q, block_k, nq):
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = qb * block_q
    k_start = pl.program_id(1) * block_k
    run = True
    if causal:
        # whole Q block strictly left of the diagonal -> no grad flows here
        run = q_start + block_q - 1 >= k_start

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal or alibi:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if alibi:
            s = s + slope_ref[0, 0, 0] * (cols - rows).astype(jnp.float32)
        if causal:
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)                                     # [BQ, BK]
        dv_scr[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)          # [BQ, BK]
        dk_scr[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(qb == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(res, g, causal, alibi, scale, block_q, block_k, interpret):
    q, k, v, o, lse = res
    B, H, S, D = q.shape
    Sk = k.shape[2]
    bq = pick_block(S, block_q, minimum=8)
    bk = pick_block(Sk, block_k, minimum=8)
    nq, nk = S // bq, Sk // bk
    BH = B * H
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [B,H,S]
    q3, k3, v3 = (t.reshape(BH, -1, D) for t in (q, k, v))
    do3 = g.reshape(BH, S, D)
    lse3 = lse.reshape(BH, S, 1)
    delta3 = delta.reshape(BH, S, 1)
    slopes = _head_slopes(B, H, alibi)
    slope_spec = pl.BlockSpec((1, 1, 1), lambda b, i, j: (b, 0, 0))

    dq_kernel = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                                  alibi=alibi, block_q=bq, block_k=bk, nk=nk)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(BH, nq, nk),
        in_specs=[pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
                  pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
                  pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
                  slope_spec],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q3, k3, v3, do3, lse3, delta3, slopes)

    dkv_kernel = functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                                   alibi=alibi, block_q=bq, block_k=bk, nq=nq)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(BH, nk, nq),
        in_specs=[pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
                  pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
                  pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
                  pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
                  pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
                  pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
                  pl.BlockSpec((1, 1, 1), lambda b, j, i: (b, 0, 0))],
        out_specs=[pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
                   pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, Sk, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q3, k3, v3, do3, lse3, delta3, slopes)
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
