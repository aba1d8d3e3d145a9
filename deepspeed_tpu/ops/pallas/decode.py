"""Fused per-layer decode kernels (single-token generation fast path).

TPU-native counterpart of the reference's fused inference kernels
(``(R) csrc/transformer/inference/csrc/``: ``pt_binding.cpp`` dispatching
fused layer_norm/rms_norm, qkv_gemm, rotary, attention with the workspace KV
cache, residual+bias, and the MLP gemm chain; SURVEY.md §2.2 "Inference
kernels").  At s=1 the per-token cost is dominated not by FLOPs but by the
number of device kernel launches the unfused HLO chain emits (~25/layer);
these kernels collapse each layer to four launches:

- :func:`fused_norm_qkv`   — norm → QKV projection (one concatenated matmul)
- :func:`flash_decode`     — online-softmax attention over the KV cache in a
  single kernel, length-aware via scalar-prefetched position (the DMA index
  map clamps beyond ``pos`` so HBM traffic tracks the generated length)
- :func:`paged_kv_append`  — this step's K/V rows into the paged pool, in
  place (the paged serving path; the contiguous layouts append in XLA)
- :func:`eva_decode_paged` — the same online softmax over a row's window
  pages and its summary pages, two valid lengths a row (``models/eva.py``)
- :func:`eva_summarize_paged` — the rows whose step filled a window pool it
  into that window's summary rows, in place
- :func:`mla_decode_paged` — the same online softmax over LATENT pages
  (``models/kda_mla.py``): one row a position shared by all query heads, a
  page fetched once and used as keys and as values
- :func:`kda_decode_step`  — a linear-attention layer's delta-rule update of
  each live row's recurrent state, in place, and its read-out
- :func:`fused_proj_norm`  — attention out-projection → residual add → norm
- :func:`fused_mlp`        — (gated) MLP → residual add, blocked over the
  FFN dim so VMEM holds one weight tile at a time
- :func:`fused_moe_mlp`    — the same for a mixture of experts: every
  expert's weights stream through VMEM once, all rows run against each (at
  256 slots: the tiles of rows that decode), and a dense [B, E] combine
  matrix (zero where not chosen) weighs the sum

Each op keeps a pure-jnp reference (the CPU path and the parity target); the
Pallas kernels run in interpret mode on CPU for tests, matching the dispatch
policy in :mod:`deepspeed_tpu.ops.pallas.common`.

All softmax/norm/accumulation math is fp32; matmul operands stay in the
serving dtype (bf16) for MXU rate, accumulating fp32 — the same contract as
the training kernels in this package.  The residual stream may be wider than
the weights (evabyte keeps it float32): ``fused_norm_qkv`` then hands the
matmul the normed rows in the weights' dtype, and ``fused_proj_norm`` and
``fused_mlp`` return the stream in the dtype it came in.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.common import (interpret_flag,
                                             kernel_or_reference,
                                             resolve_impl, round_up)

NEG_INF = -1e30

# VMEM weight-tile budget per grid step (bytes). ~6MB leaves room for the
# double-buffered next tile + activations inside the ~16MB/core VMEM.
_TILE_BYTES = 6 * 2**20
# what a kernel's grid step may hold in VMEM in all (the compiler's scoped
# limit of 16 MB less 1.25 MB for output tiles, small operands and its own
# stack): two weight tiles and the ``resident`` activations
_VMEM_STEP_BYTES = 16 * 2**20 - 5 * 2**18


def _col_block(d_in: int, n_cols: int, itemsize: int = 2,
               resident: int = 0) -> int:
    """Largest 128-multiple block of the tiled weight dim (``n_cols`` long,
    ``d_in`` elements across) with d_in*block*itemsize under the tile
    budget, and dividing n_cols (falls back to n_cols for small ops).
    ``resident``: bytes of activations the kernel keeps in VMEM beside the
    double-buffered tile (whole-row blocks and scratch); the tile budget
    gives way where they leave less than two full tiles (128 rows of 4,096:
    3 MB, and the tile of a [4096, 18432] projection goes from 768 columns
    to 512; under 2.75 MB nothing changes)."""
    budget = min(_TILE_BYTES, (_VMEM_STEP_BYTES - resident) // 2)
    cap = max(128, budget // max(1, d_in * itemsize) // 128 * 128)
    if n_cols <= cap:
        return n_cols
    for b in range(cap, 127, -128):
        if n_cols % b == 0:
            return b
    return n_cols


# fused_moe_mlp alone asks the compiler for more than its scoped 16 MiB: a
# grid step takes a held expert WHOLE where two such blocks, the resident
# rows and the step's float32 intermediates fit this share of the chip's
# VMEM (40 MiB of a v5e's 128: OLMoE's 12.6 MB and Kimi-Linear's 14.2 MB
# experts pass whole, experts of 22 MB and more keep ``_col_block``'s tiles;
# PERF.md, Findings, PR 65)
_WHOLE_EXPERT_VMEM_SHARE = 5 / 16
# one v5e TensorCore's VMEM (jax/_src/pallas/mosaic/tpu_info.py, "TPU v5
# lite"): the chip this repo targets, for a process whose default device is
# no TPU (interpret mode, a compile for a described chip)
_VMEM_BYTES_V5E = 128 * 2**20


def _vmem_capacity_bytes() -> int:
    try:
        return int(pltpu.get_tpu_info().vmem_capacity_bytes)
    except ValueError:              # "Unsupported TPU device kind: cpu"
        return _VMEM_BYTES_V5E


# a dividing tile this share over ``_TILE_BYTES`` is still taken where it
# cuts an expert into fewer tiles (fewer step boundaries, about a microsecond
# of idle HBM each), under a limit of the call's own: 15 lane tiles of two
# [2,688, .] matrices go as 3 tiles of 640 columns (6.9 MB, 9% over) and not
# as 5 of 384 (PERF.md, Findings, PR 67)
_TILE_OVER = 1 / 10


def _moe_step_bytes(rows: int, d: int, cols: int, matrices: int,
                    itemsize: int, row_itemsize: int) -> int:
    """What a grid step of :func:`fused_moe_mlp` holds in VMEM with blocks of
    ``cols`` FFN columns: the expert's blocks twice (the one in use and the
    one on its way), ``h``, ``r`` and the output twice, the float32
    accumulator, the step's float32 up / gate / activation and down
    products, the combine column's lane tile, and what ``_VMEM_STEP_BYTES``
    leaves the compiler (1.25 MB)."""
    return (2 * matrices * d * cols * itemsize
            + rows * d * (3 * 2 * row_itemsize + 4)
            + 4 * rows * (matrices * cols + d) + 2 * 4 * rows * 128
            + 16 * 2**20 - _VMEM_STEP_BYTES)


def moe_expert_block(rows: int, d: int, f: int, *, matrices: int = 3,
                     itemsize: int = 2, row_itemsize: int = 2):
    """(FFN columns a grid step of :func:`fused_moe_mlp` takes, the VMEM
    limit its call sets | None) for ``rows`` of ``d`` against experts of
    ``matrices`` [d, f] / [f, d] arrays.  The whole expert (``f``) where
    ``_col_block`` gives it anyway (small ops: the compiler's own limit
    holds them) or where everything a step holds (:func:`_moe_step_bytes`)
    fits ``_WHOLE_EXPERT_VMEM_SHARE`` of the chip's VMEM: that sum, rounded
    up to a MiB, is then the call's limit.  Elsewhere ``_col_block``'s tile
    under the compiler's scoped limit, unless the next dividing tile up is
    within ``_TILE_OVER`` of the tile budget: then that one, under a limit
    of the call's own as well."""
    step = functools.partial(_moe_step_bytes, rows, d, matrices=matrices,
                             itemsize=itemsize, row_itemsize=row_itemsize)
    tile = _col_block(d * matrices, f, itemsize)
    if tile == f:
        return f, None
    if step(f) <= _WHOLE_EXPERT_VMEM_SHARE * _vmem_capacity_bytes():
        return f, round_up(step(f), 2**20)
    wider = next(b for b in range(tile + 128, f + 1, 128) if f % b == 0)
    if matrices * d * wider * itemsize <= (1 + _TILE_OVER) * _TILE_BYTES:
        return wider, round_up(step(wider), 2**20)
    return tile, None


def _normalize(x32, scale, bias, kind: str, eps: float):
    """fp32 norm over the last axis; ``bias`` ignored for rmsnorm."""
    if kind == "rmsnorm":
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + eps)
        return y * scale
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    xc = x32 - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    return y * scale + bias


def _act(name: str, x):
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if name == "gelu_exact":
        return jax.nn.gelu(x, approximate=False)
    if name == "relu":
        return jax.nn.relu(x)
    if name == "relu2":
        # ``maximum``, not ``jax.nn.relu``: behind the latter the CPU backend
        # turns ``_moe_mlp_ref``'s second product into a bf16 x bf16 = f32
        # dot it cannot run (jax 0.9.0)
        return jnp.square(jnp.maximum(x, 0.0))
    raise ValueError(f"unsupported activation {name}")


# ---------------------------------------------------------------------------
# fused_norm_qkv: x [B, D] -> norm -> @ wqkv [D, N] (+ bqkv) -> [B, N]
# ---------------------------------------------------------------------------

def _deq(w, ws, dtype):
    """int8 payload * per-out-channel scale -> compute dtype (the in-kernel
    form of ``QTensor.astype``; reference ``(R) dequantize.cu`` role)."""
    return (w.astype(jnp.float32) * ws).astype(dtype)


def _layer_of(w, layer):
    """Layer ``layer`` of a stack ``w`` [L, ...] (the XLA forms' slice);
    ``w`` itself where there is no layer or no array."""
    if layer is None or w is None:
        return w
    return jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)


def _layer_call(kernel, layer, stacked, *, grid, in_specs, out_specs, **kw):
    """``pl.pallas_call`` of a kernel whose weights may be LAYER ``layer`` of
    stacks ``[L, ...]``: the operands at positions ``stacked`` are then the
    whole stacks and the layer rides their index maps (one scalar-prefetch
    operand; ``layer`` may be traced, a ``lax.scan``'s counter), so no slice
    of a stack is copied out in front of the call.  ``layer`` None: the call
    as it is written, every operand its own array."""
    if layer is None:
        return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                              out_specs=out_specs, **kw)

    def lift(spec, stack):
        # the default argument freezes THIS spec's map in each lambda
        if stack:
            return pl.BlockSpec(
                (None,) + tuple(spec.block_shape),
                lambda *a, m=spec.index_map: (a[-1][0],) + tuple(m(*a[:-1])))
        return pl.BlockSpec(spec.block_shape,
                            lambda *a, m=spec.index_map: m(*a[:-1]))

    one = isinstance(out_specs, pl.BlockSpec)
    outs = [lift(o, False) for o in ([out_specs] if one else out_specs)]
    call = pl.pallas_call(
        lambda layer_ref, *refs: kernel(*refs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[lift(sp, i in stacked)
                      for i, sp in enumerate(in_specs)],
            out_specs=outs[0] if one else outs,
            scratch_shapes=kw.pop("scratch_shapes", ())), **kw)
    return functools.partial(call, jnp.asarray(layer, jnp.int32).reshape(1))


def _norm_qkv_ref(x, scale, bias, wqkv, bqkv, *, kind, eps, wscale=None):
    cd = x.dtype if wscale is not None else wqkv.dtype
    h = _normalize(x.astype(jnp.float32), scale.astype(jnp.float32),
                   bias.astype(jnp.float32), kind, eps).astype(cd)
    if wscale is not None:
        wqkv = _deq(wqkv, wscale.reshape(1, -1), cd)
    y = jax.lax.dot_general(h, wqkv, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if bqkv is not None:
        y = y + bqkv.astype(jnp.float32)
    return y.astype(cd)


def _norm_qkv_kernel(x_ref, s_ref, b_ref, w_ref, ws_ref, bq_ref, o_ref,
                     h_scr, *, kind, eps, has_bias, quant):
    @pl.when(pl.program_id(0) == 0)
    def _norm():
        x32 = x_ref[:].astype(jnp.float32)
        h = _normalize(x32, s_ref[:].astype(jnp.float32),
                       b_ref[:].astype(jnp.float32), kind, eps)
        h_scr[:] = h.astype(h_scr.dtype)

    w = _deq(w_ref[:], ws_ref[:], h_scr.dtype) if quant else w_ref[:]
    y = jax.lax.dot_general(h_scr[:], w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if has_bias:
        y = y + bq_ref[:].astype(jnp.float32)
    o_ref[:] = y.astype(o_ref.dtype)


def fused_norm_qkv(x, scale, bias, wqkv, bqkv=None, *, kind: str = "layernorm",
                   eps: float = 1e-5, wscale=None, layer=None,
                   impl: Optional[str] = None):
    """x: [B, D]; wqkv: [D, N]; returns [B, N] in the weights' dtype (the
    dtype the normed rows meet them in; ``x`` may be wider, a float32
    residual stream).  ``wscale`` [N]-broadcastable fp32 marks ``wqkv`` as
    int8 (dequant in-kernel; rows and result then keep ``x.dtype``).
    ``layer`` (an index, may be traced): ``wqkv`` is a stack [L, D, N] read
    in place (:func:`_layer_call`).

    Reference: fused ln/rmsnorm + qkv_gemm of ``(R)
    csrc/transformer/inference`` (one launch instead of norm + 3 GEMVs)."""
    impl = resolve_impl(impl)
    if bias is None:
        bias = jnp.zeros_like(scale)
    if impl == "xla":
        return _norm_qkv_ref(x, scale, bias, _layer_of(wqkv, layer), bqkv,
                             kind=kind, eps=eps, wscale=wscale)
    B, D = x.shape
    N = wqkv.shape[-1]
    quant = wscale is not None
    cd = x.dtype if quant else wqkv.dtype
    # quant sizing counts the in-kernel fp32 dequant intermediate, not the
    # int8 payload — a payload-sized block would overflow VMEM at 1B+ scale
    # beside the tile: the rows (double-buffered), their normed copy and the
    # float32 one the first step norms (256 rows of 2,688: 6.9 MB in all, and
    # a [2688, 10752] projection's tile goes from 896 columns to 512)
    bn = _col_block(D, N, 4 if quant else wqkv.dtype.itemsize,
                    resident=B * D * (2 * x.dtype.itemsize
                                      + jnp.dtype(cd).itemsize + 4))
    has_bias = bqkv is not None
    bq = (bqkv if has_bias else jnp.zeros((N,), cd)).reshape(1, N)
    ws = (wscale if quant else jnp.ones((N,), jnp.float32)).reshape(1, N)
    kernel = functools.partial(_norm_qkv_kernel, kind=kind, eps=eps,
                               has_bias=has_bias, quant=quant)
    return _layer_call(
        kernel, layer, (3,),
        grid=(N // bn,),
        in_specs=[pl.BlockSpec((B, D), lambda j: (0, 0)),
                  pl.BlockSpec((1, D), lambda j: (0, 0)),
                  pl.BlockSpec((1, D), lambda j: (0, 0)),
                  pl.BlockSpec((D, bn), lambda j: (0, j)),
                  pl.BlockSpec((1, bn), lambda j: (0, j)),
                  pl.BlockSpec((1, bn), lambda j: (0, j))],
        out_specs=pl.BlockSpec((B, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), cd),
        scratch_shapes=[pltpu.VMEM((B, D), cd)],
        interpret=interpret_flag(impl),
        name="fused_norm_qkv",
    )(x, scale.reshape(1, D), bias.reshape(1, D), wqkv, ws, bq)


# ---------------------------------------------------------------------------
# flash_decode: q [B, H, Dh] x cache [B, Hkv, Smax, Dh] -> [B, H, Dh]
# ---------------------------------------------------------------------------

def _flash_decode_ref(q, kcache, vcache, pos, *, scale, alibi=False):
    """Masked dense attention over the whole cache (parity target).
    ``pos`` is a scalar or a per-row [B] vector of depths."""
    B, H, Dh = q.shape
    Hkv, Smax = kcache.shape[1], kcache.shape[2]
    rep = H // Hkv
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    qf = q.astype(jnp.float32).reshape(B, Hkv, rep, Dh)
    kf = kcache.astype(jnp.float32)
    vf = vcache.astype(jnp.float32)
    s = jnp.einsum("bgrd,bgkd->bgrk", qf, kf) * scale
    key_pos = jnp.arange(Smax)
    if alibi:
        from deepspeed_tpu.models.layers import alibi_slopes

        rel = (key_pos[None, :] - pos[:, None]).astype(jnp.float32)
        s = s + (alibi_slopes(H).reshape(1, Hkv, rep, 1)
                 * rel[:, None, None, :])
    mask = key_pos[None, :] <= pos[:, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrk,bgkd->bgrd", p, vf)
    return o.reshape(B, H, Dh).astype(q.dtype)


def _flash_decode_kernel(*refs, scale, block, alibi):
    """One grid step = one batch row x ``hb`` KV heads x one key block (a
    page of the paged pool): online softmax with a leading head axis.  The
    scalar-prefetched refs lead (``rows``, the batch row of each step of
    the grid's first axis, then ``pos``; the paged layout adds its page
    table, which only the index maps read — it picks WHICH physical page
    the step's K and V blocks DMA; the math here is position-logical)."""
    rows_ref, pos_ref = refs[:2]
    q_ref, k_ref, v_ref, slope_ref, o_ref, m_scr, l_scr, acc_scr = refs[-8:]
    j = pl.program_id(2)

    pl.when(j == 0)(functools.partial(_softmax_init, m_scr, l_scr, acc_scr))

    # each batch row has its own position (continuous batching)
    pos = pos_ref[rows_ref[pl.program_id(0)]]

    @pl.when(j * block <= pos)
    def _compute():
        q, k = q_ref[0], k_ref[0]                   # [hb, rep | block, Dh]
        if q.dtype != k.dtype:
            q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        # operands as stored: bf16 products are exact in the float32 sum
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        key_pos = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        if alibi:
            s = s + slope_ref[:] * (key_pos - pos).astype(jnp.float32)
        s = jnp.where(key_pos <= pos, s, NEG_INF)   # [hb, rep, block]
        _softmax_block(s, v_ref, m_scr, l_scr, acc_scr)

    pl.when(j == pl.num_programs(2) - 1)(
        functools.partial(_softmax_finish, o_ref, l_scr, acc_scr))


def _softmax_init(m_scr, l_scr, acc_scr):
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)


def _softmax_finish(o_ref, l_scr, acc_scr):
    l = l_scr[:]
    o_ref[0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _softmax_block(s, v_ref, m_scr, l_scr, acc_scr, keep=None):
    """One key block of the online softmax: masked scores ``s`` [hb, rep,
    block] and the block's values into the running max, sum and weighted
    values.  ``keep`` [1, block, 1] bool: the value rows that were fetched
    and attended; the others are taken as 0, whatever the buffer holds
    there (their weight is exactly 0, but ``0 x NaN`` is not)."""
    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)

    def values():      # read where the parent's programs read them
        v = v_ref[0].astype(jnp.float32)
        return v if keep is None else jnp.where(keep, v, 0.0)

    acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
        p, values(), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    m_scr[:] = m_new


# VMEM the decode attention kernel may spend on its double-buffered K and V
# blocks (bytes): 4 buffers of [hb, block, Dh padded to 128 lanes].  Leaves
# the ~16MB scoped VMEM room for the f32 copy of V and the score tiles.
_DECODE_KV_VMEM_BYTES = 4 * 2**20


def _kv_heads_per_step(hkv: int, block: int, dh: int, itemsize: int) -> int:
    """Most KV heads one grid step of the decode attention kernel can take:
    the largest divisor of ``hkv`` whose K and V blocks, double-buffered,
    fit :data:`_DECODE_KV_VMEM_BYTES` (1 when even a single head does not)."""
    head = 4 * block * round_up(dh, 128) * itemsize
    return max((d for d in range(1, hkv + 1)
                if hkv % d == 0 and d * head <= _DECODE_KV_VMEM_BYTES),
               default=1)


def _live_rows(live, B: int):
    """(the batch rows that decode, in order, then the others; how many
    decode): the first axis of a grid that follows the live mask (None:
    every row)."""
    if live is None:
        return jnp.arange(B, dtype=jnp.int32), B
    return (jnp.argsort(~live, stable=True).astype(jnp.int32),
            jnp.sum(live, dtype=jnp.int32))


def live_groups(live, B: int, group: int):
    """:func:`_live_rows` cut into groups for a loop whose trip count is read
    at run time: (the rows live-first, the rows ``G = min(group, B)`` of a
    group, ``ceil(live rows / G)`` groups that hold a live row).  Group ``i``
    is ``rows[i G : (i + 1) G]`` (the last one of a batch that is no
    multiple of ``G`` starts at ``B - G``: ``dynamic_slice`` clamps it, and
    the rows it works twice come out the same); what pads the last group are
    rows that do not decode."""
    rows, n_live = _live_rows(live, B)
    G = min(group, B)
    return rows, G, (n_live + G - 1) // G


def over_live_groups(live, B: int, group: int, work, out):
    """``out`` [B, ...] with ``work(at)`` [G, ...] written at the rows ``at``
    [G] of every group of :func:`live_groups` that holds a live row: the
    loop, its trip count read at run time."""
    rows, G, groups = live_groups(live, B, group)

    def one(i, out):
        at = jax.lax.dynamic_slice_in_dim(rows, i * G, G)
        return out.at[at].set(work(at), unique_indices=True,
                              mode="promise_in_bounds")

    return jax.lax.fori_loop(0, groups, one, out)


def _alibi_slopes(H: int, hkv: int, alibi: bool):
    """The heads' ALiBi slopes [Hkv, rep, 1] float32 (zeros without)."""
    if not alibi:
        return jnp.zeros((hkv, H // hkv, 1), jnp.float32)
    from deepspeed_tpu.models.layers import alibi_slopes

    return alibi_slopes(H).reshape(hkv, H // hkv, 1)


def _decode_attention(q, kcache, vcache, pos, tables, kv_map, *, live, block,
                      nb, scale, alibi, impl, name):
    """The ``pallas_call`` behind every cache layout whose key blocks come a
    grid step each (the contiguous cache, latent pages, paged rows under
    the lane tile; per-head pages that fill the lanes are walked inside a
    step: :func:`_walk_pages`).  The caches are
    taken as ``[N, Hkv, S, Dh]`` views (N = stacked layers x batch rows, or
    x physical pages: a free reshape); ``kv_map(b, g, j, pos_ref,
    *table_refs)`` places the ``(1, hb, block, Dh)`` K and V blocks of batch
    row ``b``, head group ``g``, key block ``j``.

    The grid follows the batch, not the slot count: ``live`` [B] bool names
    the rows that decode (None: all of them), and the grid is ``(live rows,
    Hkv // hb, nb)`` with the first extent read at run time.  Step ``i`` of
    it works on row ``rows[i]``, the live rows' indices in order
    (scalar-prefetched ahead of ``pos``), so a row that does not decode costs
    no grid step and no page fetch; its output is its ``q``, which the
    output is aliased onto.  No live row at all is a grid of no steps.
    ``nb`` is a row's key blocks, one run up to ``pos``: the third extent
    stops at the deepest live row's last one, ``max(pos // block + 1)`` over
    them.

    ``vcache`` None (latent pages, :func:`mla_decode_paged`): the values
    are the key rows themselves, and the block is fetched ONCE: the body
    gets the one ref as its K and as its V."""
    B, H, Dh = q.shape
    view = (-1,) + kcache.shape[-3:]
    hkv = view[1]
    rep = H // hkv
    hb = _kv_heads_per_step(hkv, block, Dh, kcache.dtype.itemsize)
    slopes = _alibi_slopes(H, hkv, alibi)
    depth = pos // block + 1
    rows, n_live = _live_rows(live, B)
    if live is not None:
        depth = jnp.where(live, depth, 0)
    nb = jnp.minimum(nb, jnp.max(depth))
    kernel = functools.partial(_flash_decode_kernel, scale=scale,
                               block=block, alibi=alibi)
    caches = [kcache.reshape(view)]
    if vcache is None:
        body = kernel      # refs end q, k, slopes, o, m, l, acc: k again as v
        kernel = lambda *refs: body(*refs[:-5], refs[-6], *refs[-5:])
    else:
        caches.append(vcache.reshape(view))
    prefetch = (rows, pos) + tuple(tables)
    # index maps see the scalar-prefetch refs AFTER the grid indices (the
    # kernel body sees them first)
    heads = pl.BlockSpec((1, hb, rep, Dh),
                         lambda i, g, j, rows_ref, *_: (rows_ref[i], g, 0, 0))
    kv = pl.BlockSpec((1, hb, block, Dh),
                      lambda i, g, j, rows_ref, *refs:
                      kv_map(rows_ref[i], g, j, *refs))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(n_live, hkv // hb, nb),
        in_specs=[heads] + [kv] * len(caches) + [
            pl.BlockSpec((hb, rep, 1), lambda i, g, j, *_: (g, 0, 0))],
        out_specs=heads,
        scratch_shapes=[pltpu.VMEM((hb, rep, 1), jnp.float32),
                        pltpu.VMEM((hb, rep, 1), jnp.float32),
                        pltpu.VMEM((hb, rep, Dh), jnp.float32)],
    )
    o = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, hkv, rep, Dh), q.dtype),
        # operands count the scalar-prefetch arrays: q follows them
        input_output_aliases={len(prefetch): 0},
        interpret=interpret_flag(impl),
        name=name,
    )(*prefetch, q.reshape(B, hkv, rep, Dh), *caches, slopes)
    return o.reshape(B, H, Dh)


def paged_decode_reference_reason(page: int) -> Optional[str]:
    """Why the paged kernel cannot take this page size (None = it can):
    the page is the kernel's key block, which the score tile lays along
    the 128 lanes."""
    if page % 128:
        return f"page of {page} tokens is not a multiple of the 128-lane tile"
    return None


def decode_reference_reason(cache_len: int, block: int) -> Optional[str]:
    """Why the contiguous kernel cannot take this cache (None = it can)."""
    if cache_len % block:
        return (f"cache length {cache_len} is not a multiple of the "
                f"{block}-token block")
    return None


def _kv_append_kernel(pp_ref, po_ref, *refs, rows):
    """``refs``: the new rows, the old row groups and the groups out, one of
    each a pool."""
    del pp_ref                    # consumed by the index maps
    n = len(refs) // 3
    r = po_ref[pl.program_id(0)] % rows
    hit = jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1) == r
    for new, old, out in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
        out[0] = jnp.where(hit, new[0], old[0])


def paged_kv_append(kcache, vcache, k, v, pos, page_table, *, layer,
                    impl: Optional[str] = None):
    """Write this step's K/V rows into the stacked paged pool, in place:
    row b of ``k``/``v`` [B, Hkv, Dh] lands at row ``pos[b] % page`` of
    physical page ``page_table[b, pos[b] // page]`` of layer ``layer``, a
    Python int or a traced scalar (a looped stack's ``pass * layers +
    layer`` inside its rolled pass loop).

    The XLA form is one batched scatter.  On the chip that scatter wants
    its index dims minor-most while the flash-decode kernel takes the pool
    row-major, so XLA re-laid-out the WHOLE pool around every layer's
    kernel call (at gpt2-xl the decode block then asks for more HBM than
    the chip has).  The kernel form rewrites only the tile-aligned group of
    rows holding the new one, aliased onto the pool, so the pool keeps one
    layout from the first layer to the last."""
    return _paged_append((kcache, vcache), (k, v), pos, page_table, layer,
                         impl)


def paged_row_append(cache, row, pos, page_table, *, layer: int,
                     impl: Optional[str] = None):
    """:func:`paged_kv_append` for a pool of ONE array whose rows all heads
    share (latent pages ``[L, P, 1, page, W]``, ``models/kda_mla.py``): row
    b of ``row`` [B, W] lands at row ``pos[b] % page`` of its page."""
    return _paged_append((cache,), (row[:, None, :],), pos, page_table,
                         layer, impl)[0]


def _paged_append(pools, new_rows, pos, page_table, layer, impl):
    """The one scatter and the one ``pallas_call`` behind the appends: each
    of ``pools`` [L, P, Hkv, page, Dh] takes its rows [B, Hkv, Dh].  A
    static ``layer`` is folded into the index map; a traced one into the
    scalar-prefetched page numbers, which the index map then takes as they
    are (an index map may close over no traced value)."""
    impl = resolve_impl(impl)
    L, P, Hkv, page, Dh = pools[0].shape
    B = new_rows[0].shape[0]
    pp = page_table[jnp.arange(B), pos // page]
    po = pos % page
    impl = kernel_or_reference("paged_kv_append", impl,
                               paged_decode_reference_reason(page))
    if impl == "xla":
        return tuple(c.at[layer, pp, :, po, :].set(r.astype(c.dtype))
                     for c, r in zip(pools, new_rows))
    rows = 32 // pools[0].dtype.itemsize      # one (sublane x lane) tile
    kernel = functools.partial(_kv_append_kernel, rows=rows)
    n = len(pools)
    base = layer * P
    if not isinstance(layer, int):
        pp, base = pp + base, 0

    def group(b, pp_ref, po_ref):
        return base + pp_ref[b], 0, po_ref[b] // rows, 0

    new = pl.BlockSpec((1, Hkv, 1, Dh), lambda b, pp_ref, po_ref: (b, 0, 0, 0))
    old = pl.BlockSpec((1, Hkv, rows, Dh), group)
    pool = jax.ShapeDtypeStruct((L * P, Hkv, page, Dh), pools[0].dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[new] * n + [old] * n, out_specs=[old] * n),
        out_shape=[pool] * n,
        # operands count the two scalar-prefetch arrays and the new rows:
        # the pools follow them
        input_output_aliases={2 + n + i: i for i in range(n)},
        interpret=interpret_flag(impl),
        name="paged_kv_append",
    )(pp.astype(jnp.int32), po.astype(jnp.int32),
      *(r.astype(c.dtype).reshape(B, Hkv, 1, Dh)
        for c, r in zip(pools, new_rows)),
      *(c.reshape(pool.shape) for c in pools))
    return tuple(o.reshape(c.shape) for o, c in zip(out, pools))


# rows one copy of the LAST page of a run of pages fetches: that page comes
# in ``_last_page_pieces`` copies where every page before it comes in one, so
# a run fetches 32 rows it does not attend on average, not half a page.
# ``serving/cache_kind.py`` counts the fetched keys by this rule
FETCH_ROWS = 64


def walks_pages(head_dim: int) -> bool:
    """Whether :func:`_flash_decode_paged` walks a row's pages inside one grid
    step with copies of its own: where a row fills the 128 lanes.  Rows
    under the lane tile (GPT-2's 64) lie padded in HBM, a copy issued in the
    kernel can slice no such pool, and they keep a page a grid step
    (:func:`_decode_attention`)."""
    return head_dim % 128 == 0


def _page_runs(page: int, window: Optional[int] = None,
               chunk: Optional[int] = None):
    """The runs of table columns a decoding row attends, in the order its
    grid step walks them (:func:`_walk_pages`): ``(first column, rows that
    count at pos)`` each, the rows a function of the row's position (an int,
    an array or a scalar read from SMEM).  A run of ``n`` rows is its first
    ``ceil(n / page)`` columns, none at ``n = 0``; the first run is never
    empty.  Full pages are one run up to ``pos``; EVA (``window``,
    ``chunk``: ``models/eva.py``) attends the rows of its open window, then
    the ``window / chunk`` summary rows of every window that has closed."""
    if window is None:
        return ((0, lambda pos: pos + 1),)
    return ((0, lambda pos: pos % window + 1),
            (window // page, lambda pos: pos // window * (window // chunk)))


def _last_page_pieces(n, page: int):
    """Copies of :data:`FETCH_ROWS` rows that bring in the last page of a
    run of ``n > 0`` rows that count: the kernel's copies and the counters'
    keys both come from here."""
    return (n - 1) % page // FETCH_ROWS + 1


def _keys_fetched(pos, page: int, head_dim: int, **runs):
    """Keys the step at ``pos`` (an int or an array of them) brings into
    VMEM for one KV head of one cache layer: of each run of
    :func:`_page_runs` the pages before its last whole, the last in pieces
    up to the rows that count where the kernel walks the pages
    (:func:`walks_pages`), whole where it does not."""
    total = 0
    for _, rows in _page_runs(page, **runs):
        n = rows(pos)
        last = (_last_page_pieces(n, page) * FETCH_ROWS
                if walks_pages(head_dim) else page)
        total = total + (n > 0) * ((n - 1) // page * page + last)
    return total


def paged_keys_fetched(pos, page: int, head_dim: int):
    """:func:`_keys_fetched` of ``flash_decode`` over the paged pool: one
    run of pages up to ``pos``."""
    return _keys_fetched(pos, page, head_dim)


def eva_keys_fetched(pos, page: int, head_dim: int, window: int, chunk: int):
    """:func:`_keys_fetched` of :func:`eva_decode_paged`: the window pages
    up to row ``pos % window``, then the summary pages up to the closed
    windows' last row."""
    return _keys_fetched(pos, page, head_dim, window=window, chunk=chunk)


def _flash_decode_paged_kernel(rows_ref, pos_ref, base_ref, pt_ref, q_ref,
                               k_hbm, v_hbm, slope_ref, o_ref, m_scr, l_scr,
                               acc_scr, k_buf, v_buf, sems, slot_ref, *,
                               scale, alibi, runs):
    """One grid step = one LIVE batch row x ``hb`` KV heads, the row's pages
    walked inside it (:func:`_walk_pages`), run after run of ``runs``
    (:func:`_page_runs`).  ``k_hbm`` / ``v_hbm`` are the pools where they
    lie, ``k_buf`` / ``v_buf`` [2, hb, page, Dh] the two pages in VMEM,
    ``sems`` [K | V, slot] their copies' semaphores, ``base_ref`` [1] the
    layer's first page in the pools and ``slot_ref`` the slot the step's
    first page is on its way to (set by the step before it, which started
    that copy)."""
    i, g = pl.program_id(0), pl.program_id(1)
    _, hb, page, _ = k_buf.shape
    groups = k_hbm.shape[1] // hb
    whole = page // FETCH_ROWS
    b = rows_ref[i]
    pos = pos_ref[b]

    def locate(b, t):
        """(table column, rows that count from its first on) of page ``t``
        of row ``b``'s walk, and the pages the walk has."""
        p, total, at = pos_ref[b], 0, None
        for col0, rows in runs:
            n, j = rows(p), t - total
            here = (col0 + j, n - j * page)
            # a later run's page where ``t`` has come to it
            at = here if at is None else tuple(
                jnp.where(j >= 0, new, old) for new, old in zip(here, at))
            total = total + (n + page - 1) // page
        return (*at, total)

    def copies(b, g, col, slot, rows):
        at = base_ref[0] + pt_ref[b, col]
        return [pltpu.make_async_copy(pool.at[at, pl.ds(g * hb, hb), rows],
                                      buf.at[slot, :, rows], sems.at[n, slot])
                for n, (pool, buf) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf)))]

    def page_copies(b, g, t, slot, act):
        """``act`` ("start" or "wait") each copy of page ``t`` of row
        ``b``'s walk: one of the page, or of a run's last page the pieces up
        to its last row that counts.  Returns the page's rows that count."""
        col, cnt, _ = locate(b, t)
        n = _last_page_pieces(cnt, page)
        n = jnp.where((cnt > page) | (n == whole), 0, n)

        @pl.when(n == 0)
        def _whole():
            for c in copies(b, g, col, slot, slice(None)):
                getattr(c, act)()

        def piece(c, carry):
            at = pl.multiple_of(c * FETCH_ROWS, FETCH_ROWS)
            for d in copies(b, g, col, slot, pl.ds(at, FETCH_ROWS)):
                getattr(d, act)()
            return carry

        jax.lax.fori_loop(0, n, piece, 0)
        return cnt

    @pl.when((i == 0) & (g == 0))
    def _first():
        slot_ref[0] = 0
        page_copies(b, g, 0, 0, "start")

    slot0 = slot_ref[0]
    _softmax_init(m_scr, l_scr, acc_scr)
    # what follows this step's last page: the next grid step's first
    g1 = (g + 1) % groups
    i1 = jnp.where(g1 == 0, i + 1, i)
    more = i1 < pl.num_programs(0)
    b1 = rows_ref[jnp.where(more, i1, i)]
    rows = jax.lax.broadcasted_iota(jnp.int32, (1,) + v_buf.shape[2:], 1)
    pages = locate(b, 0)[2]

    def one_page(t, carry):
        slot = (slot0 + t) % 2
        inside = t + 1 < pages

        # the page after this one leaves before this one is scored
        @pl.when(inside | more)
        def _next():
            page_copies(jnp.where(inside, b, b1), jnp.where(inside, g, g1),
                        jnp.where(inside, t + 1, 0), 1 - slot, "start")

        cnt = page_copies(b, g, t, slot, "wait")
        q, k = q_ref[0], k_buf[slot]                # [hb, rep | page, Dh]
        if q.dtype != k.dtype:
            q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        key = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        if alibi:       # one run: page t holds positions t * page and on
            s = s + slope_ref[:] * (t * page + key - pos).astype(jnp.float32)
        s = jnp.where(key < cnt, s, NEG_INF)        # [hb, rep, page]
        # rows of the buffer past those that count hold what the pool holds
        # there (a window page: the window before's keys), or an earlier
        # page: they weigh exactly 0, and their values count as 0
        _softmax_block(s, v_buf.at[pl.ds(slot, 1)], m_scr, l_scr, acc_scr,
                       rows < cnt)
        return carry

    jax.lax.fori_loop(0, pages, one_page, 0)
    slot_ref[0] = (slot0 + pages) % 2
    _softmax_finish(o_ref, l_scr, acc_scr)


def _flash_decode_paged(q, kcache, vcache, pos, page_table, *, scale,
                        layer, alibi: bool, live, impl: str):
    """Decode attention over the PAGED pool (``serving/paged_kv.py``):
    caches [P, Hkv, page, Dh] (or stacked [L, P, Hkv, page, Dh] with
    ``layer=l``), ``page_table`` [B, maxp] int32 naming each row's
    physical page per logical block.  One grid step is one LIVE batch row
    x ``hb`` KV heads (``grid=(live rows, Hkv // hb)``, the first extent
    read at run time as :func:`_decode_attention`'s;
    :func:`_kv_heads_per_step` sizes ``hb`` from the shapes, all of ``Hkv``
    at GQA widths), and the step WALKS the row's ``pos // page + 1`` pages
    itself: the pools stay in HBM, a physical page holds its KV heads
    contiguously, so a page's K and V are ``[hb, page, Dh]`` slabs that the
    step copies into one of two VMEM buffers each, page ``j + 1`` on its way
    while page ``j`` is scored, the next grid step's first page while this
    one's last is.  A page before the row's last is one copy; the last is
    ``(pos % page) // FETCH_ROWS + 1`` copies of :data:`FETCH_ROWS` tokens,
    so what a row fetches past ``pos`` is under one piece.  Pages past a
    row's ``pos`` are neither fetched nor computed, a shallower row costs
    nothing for a deeper one's pages, a row that does not decode costs
    nothing and gets its ``q`` back.  The XLA path gathers
    the logical per-slot view and runs the dense reference (CPU tests, and
    the page sizes :func:`paged_decode_reference_reason` names).  The
    layer's offset in the pools, a Python int or a traced scalar
    (:func:`paged_kv_append`), is prefetched with the table
    (:func:`_walk_pages`, which :func:`eva_decode_paged` calls with its two
    runs of pages a row); at a head dim under the lane tile
    (:func:`walks_pages`) a traced one is added to the table's page
    numbers."""
    kc = kcache if layer is None else kcache[layer]
    vc = vcache if layer is None else vcache[layer]
    page = kc.shape[2]
    impl = kernel_or_reference("flash_decode_paged", impl,
                               paged_decode_reference_reason(page))
    if impl == "xla":
        from deepspeed_tpu.models.decoding import paged_logical_view

        return _flash_decode_ref(q, paged_logical_view(kc, page_table),
                                 paged_logical_view(vc, page_table), pos,
                                 scale=scale, alibi=alibi)
    base = 0 if layer is None else layer * kc.shape[0]
    page_table = page_table.astype(jnp.int32)
    B, H, Dh = q.shape
    if not walks_pages(Dh):
        if not isinstance(base, int):
            page_table, base = page_table + base, 0

        def page_map(b, g, j, pos_ref, pt_ref):
            jl = jnp.minimum(j, pos_ref[b] // page)     # per-row DMA clamp
            return base + pt_ref[b, jl], g, 0, 0

        return _decode_attention(
            q, kcache, vcache, pos, (page_table,), page_map,
            live=live, block=page, nb=page_table.shape[1], scale=scale,
            alibi=alibi, impl=impl, name="flash_decode_paged")
    view = (-1,) + kc.shape[-3:]
    hkv = view[1]
    rows, n_live = _live_rows(live, B)
    o = _walk_pages(
        rows, jnp.asarray(n_live, jnp.int32), pos,
        jnp.reshape(base, (1,)).astype(jnp.int32), page_table,
        q.reshape(B, hkv, H // hkv, Dh), kcache.reshape(view),
        vcache.reshape(view), _alibi_slopes(H, hkv, alibi), scale=scale,
        alibi=alibi, impl=impl, name="flash_decode_paged")
    return o.reshape(B, H, Dh)


@functools.partial(jax.jit, static_argnames=("scale", "alibi", "impl", "name",
                                             "window", "chunk"))
def _walk_pages(rows, n_live, pos, base, page_table, q, kcache, vcache,
                slopes, *, scale, alibi, impl, name, window=None, chunk=None):
    """The ``pallas_call`` that walks a live row's pages inside one grid
    step, :func:`_flash_decode_paged`'s and (``window``, ``chunk``)
    :func:`eva_decode_paged`'s: ``q`` [B, Hkv, rep, Dh], the pools [N, Hkv,
    page, Dh], ``base`` [1] the first page of the layer read, the runs of
    table columns a row attends by :func:`_page_runs`.  A function of its
    own under ``jit`` with the layer's offset an OPERAND, so the calls of a
    model's layers are one traced and lowered kernel, not one a layer: the
    decode block of a twelve-layer stack is traced and lowered in the time
    of one call's."""
    assert window is None or not alibi      # key positions: one run's
    B, hkv, rep, Dh = q.shape
    page = kcache.shape[2]
    hb = _kv_heads_per_step(hkv, page, Dh, kcache.dtype.itemsize)
    heads = pl.BlockSpec((1, hb, rep, Dh),
                         lambda i, g, rows_ref, *_: (rows_ref[i], g, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((2, hb, page, Dh), kcache.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_live, hkv // hb),
        in_specs=[heads, pool, pool,
                  pl.BlockSpec((hb, rep, 1), lambda i, g, *_: (g, 0, 0))],
        out_specs=heads,
        scratch_shapes=[pltpu.VMEM((hb, rep, 1), jnp.float32),
                        pltpu.VMEM((hb, rep, 1), jnp.float32),
                        pltpu.VMEM((hb, rep, Dh), jnp.float32),
                        buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_flash_decode_paged_kernel, scale=scale,
                          alibi=alibi,
                          runs=_page_runs(page, window, chunk)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # operands count the scalar-prefetch arrays: q follows them
        input_output_aliases={4: 0},
        interpret=interpret_flag(impl),
        name=name,
    )(rows, pos, base, page_table, q, kcache, vcache, slopes)


def flash_decode(q, kcache, vcache, pos, *, sm_scale: Optional[float] = None,
                 block: int = 256, layer: Optional[int] = None,
                 alibi: bool = False, impl: Optional[str] = None,
                 page_table=None, live=None):
    """Single-launch decode attention.  q: [B, H, Dh]; caches:
    [B, Hkv, Smax, Dh] — or, with ``layer=l``, stacked [L, B, Hkv, Smax, Dh]
    read at layer offset ``l`` (a Python int or a traced scalar) through
    the index map (no cache slice materializes); ``pos`` the (traced)
    absolute position of the query — a scalar shared by the batch, or an
    int32 [B] vector of per-row depths
    (continuous batching: each slot masks and clamps independently).
    ``page_table`` [B, maxp] switches to the paged pool layout
    ([P, Hkv, page, Dh] physical pages; see :func:`_flash_decode_paged`).

    ``live`` [B] bool names the rows that really decode (None: all): the
    kernel's grid visits those rows only and stops at the deepest one's
    last block (:func:`_decode_attention`); the others get their ``q`` back.

    The block index map clamps to the position's block PER ROW, so cache
    blocks past each row's ``pos`` are neither fetched nor computed — the
    single-kernel form of the length-aware flash-decode loop (reference:
    ``(R) softmax.cu`` + attention in the inference workspace)."""
    impl = resolve_impl(impl)
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1),
                           (q.shape[0],))
    if page_table is not None:
        return _flash_decode_paged(q, kcache, vcache, pos, page_table,
                                   scale=scale, layer=layer, alibi=alibi,
                                   live=live, impl=impl)
    # the xla path slices the stacked cache; the pallas path offsets the map
    kc = kcache if layer is None else kcache[layer]
    vc = vcache if layer is None else vcache[layer]
    Smax = kc.shape[2]
    # odd cache lengths (not a block multiple) would hand the kernel a
    # non-tile-aligned block — route them to the dense reference, the same
    # policy the unfused decode uses for small caches
    impl = kernel_or_reference("flash_decode", impl,
                               decode_reference_reason(Smax, block))
    if impl == "xla":
        return _flash_decode_ref(q, kc, vc, pos, scale=scale, alibi=alibi)
    base = 0 if layer is None else layer * q.shape[0]
    if isinstance(base, int):
        tables = ()

        def clamp(b, g, j, pos_ref):                # per-row DMA clamp
            return base + b, g, jnp.minimum(j, pos_ref[b] // block), 0
    else:       # a traced layer: its offset is prefetched, a table of one
        tables = (jnp.reshape(base, (1,)).astype(jnp.int32),)

        def clamp(b, g, j, pos_ref, base_ref):
            return base_ref[0] + b, g, jnp.minimum(j, pos_ref[b] // block), 0

    return _decode_attention(
        q, kcache, vcache, pos, tables, clamp, live=live, block=block,
        nb=Smax // block, scale=scale, alibi=alibi, impl=impl,
        name="flash_decode")


# ---------------------------------------------------------------------------
# Latent pages and recurrent state (models/kda_mla.py)
# ---------------------------------------------------------------------------

def mla_decode_paged(q, cache, pos, page_table, *, layer: int,
                     sm_scale: float, live=None, impl: Optional[str] = None):
    """Decode attention of a latent-attention layer in its ABSORBED form
    over LATENT PAGES: ``q`` [B, H, W] (``kda_mla.mla_absorb``: each head's
    query against the rows), ``cache`` [L, P, 1, page, W] (one row a
    position, shared by all heads: the normed latent, the shared key values,
    zeros to the lane tile).  :func:`_flash_decode_paged`'s schedule with
    one "KV head" of ``H`` query rows: a grid step is one LIVE batch row x
    one logical page, the page is fetched ONCE and serves as keys (the whole
    row) and as values, so the step's scores are one ``[H, W] x [W, page]``
    matmul.  Returns [B, H, W]: the rows' weighted sum, whose first
    ``mla_kv_rank`` values are the latent context (``kda_mla.mla_unabsorb``
    takes them through the value half of ``Wkvb``); a row that does not
    decode gets its ``q`` back."""
    impl = resolve_impl(impl)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1),
                           (q.shape[0],))
    P, page = cache.shape[1], cache.shape[3]
    impl = kernel_or_reference("mla_decode_paged", impl,
                               paged_decode_reference_reason(page))
    if impl == "xla":
        from deepspeed_tpu.models.decoding import paged_logical_view

        view = paged_logical_view(cache[layer], page_table)
        return _flash_decode_ref(q, view, view, pos, scale=sm_scale)

    def page_map(b, g, j, pos_ref, pt_ref):
        jl = jnp.minimum(j, pos_ref[b] // page)     # per-row DMA clamp
        return layer * P + pt_ref[b, jl], g, 0, 0

    return _decode_attention(
        q, cache, None, pos, (page_table.astype(jnp.int32),), page_map,
        live=live, block=page, nb=page_table.shape[1], scale=sm_scale,
        alibi=False, impl=impl, name="mla_decode_paged")


# ---------------------------------------------------------------------------
# A learned selection of keys (models/kda_mla.py: the indexer)
# ---------------------------------------------------------------------------

def _index_scores_ref(q, w, keys, pos):
    """``I(b, j) = sum_g w[b, g] relu(q[b, g] . keys[b, j])`` [B, S] float32
    for ``j <= pos[b]``, ``NEG_INF`` past it; q [B, G, d], w [B, G] float32,
    keys [B, S, d]."""
    s = jnp.einsum("bgd,bkd->bgk", q, keys.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    i = jnp.sum(jax.nn.relu(s) * w[:, :, None], axis=1)
    return jnp.where(jnp.arange(keys.shape[1])[None, :] <= pos[:, None], i,
                     NEG_INF)


def _index_scores_kernel(rows_ref, pos_ref, pt_ref, q_ref, w_ref, k_ref,
                         old_ref, o_ref, *, page):
    """One grid step = one LIVE batch row x one logical page of index keys:
    the page's keys against the row's ``G`` index queries, ReLU, the heads'
    weighted sum."""
    del pt_ref, old_ref           # the index maps'; the aliased output's
    j = pl.program_id(1)
    pos = pos_ref[rows_ref[pl.program_id(0)]]

    @pl.when(j * page <= pos)
    def _compute():
        s = jax.lax.dot_general(q_ref[0], k_ref[0, 0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        i = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0, keepdims=True)
        key_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, i.shape, 1)
        o_ref[0, 0] = jnp.where(key_pos <= pos, i, NEG_INF)

    @pl.when(j * page > pos)
    def _past():
        o_ref[0, 0] = jnp.full(o_ref.shape[2:], NEG_INF, jnp.float32)


def dsa_index_scores_paged(q, w, cache, pos, page_table, *, layer: int,
                           live=None, impl: Optional[str] = None):
    """Index scores of a decode step over the pages of INDEX KEYS: ``q`` [B,
    G, d] (the row's index queries, rotated), ``w`` [B, G] float32 (its head
    weights), ``cache`` [L, P, 1, page, d] (one index key a position, under
    the latent pages' table).  Returns [B, columns * page] float32: ``sum_g
    w_g relu(q_g . k(j))`` for ``j <= pos``, ``NEG_INF`` past it and in the
    rows that do not decode.  :func:`mla_decode_paged`'s schedule: a grid
    step is one LIVE row x one logical page, up to the deepest live row's
    last page; what the grid does not visit keeps the ``NEG_INF`` it is
    aliased onto."""
    impl = resolve_impl(impl)
    B, G, d = q.shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    P, page = cache.shape[1], cache.shape[3]
    cols = page_table.shape[1]
    impl = kernel_or_reference("dsa_index_scores_paged", impl,
                               paged_decode_reference_reason(page))
    if impl == "xla":
        from deepspeed_tpu.models.decoding import paged_logical_view

        view = paged_logical_view(cache[layer], page_table)[:, 0]
        out = _index_scores_ref(q.astype(cache.dtype), w, view, pos)
        return out if live is None else jnp.where(live[:, None], out, NEG_INF)
    rows, n_live = _live_rows(live, B)
    depth = pos // page + 1
    if live is not None:
        depth = jnp.where(live, depth, 0)
    nb = jnp.minimum(cols, jnp.max(depth))

    def page_map(i, j, rows_ref, pos_ref, pt_ref):
        b = rows_ref[i]
        return layer * P + pt_ref[b, jnp.minimum(j, pos_ref[b] // page)], 0, 0, 0

    by_row = lambda i, j, rows_ref, *_: (rows_ref[i], 0, 0)
    out = pl.BlockSpec((1, 1, 1, page),
                       lambda i, j, rows_ref, *_: (rows_ref[i], j, 0, 0))
    o = pl.pallas_call(
        functools.partial(_index_scores_kernel, page=page),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n_live, nb),
            in_specs=[pl.BlockSpec((1, G, d), by_row),
                      pl.BlockSpec((1, G, 1), by_row),
                      pl.BlockSpec((1, 1, page, d), page_map), out],
            out_specs=out),
        out_shape=jax.ShapeDtypeStruct((B, cols, 1, page), jnp.float32),
        input_output_aliases={6: 0},
        interpret=interpret_flag(impl),
        name="dsa_index_scores_paged",
    )(rows, pos, page_table.astype(jnp.int32), q.astype(cache.dtype),
      w.astype(jnp.float32)[:, :, None],
      cache.reshape((-1,) + cache.shape[2:]),
      jnp.full((B, cols, 1, page), NEG_INF, jnp.float32))
    return o.reshape(B, cols * page)


def _selected_ref(q, rows, n, *, scale):
    """Attention of q [B, H, W] over its own gathered rows [B, K, W], the
    first ``n[b]`` of them real; keys and values are the rows."""
    s = jnp.einsum("bhw,bkw->bhk", q, rows,
                   preferred_element_type=jnp.float32) * scale
    ok = jnp.arange(rows.shape[1])[None, :] < n[:, None]
    p = jax.nn.softmax(jnp.where(ok[:, None], s, NEG_INF), axis=-1)
    return jnp.einsum("bhk,bkw->bhw", p.astype(rows.dtype), rows,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _selected_kernel(n_ref, q_ref, rows_ref, o_ref, *, scale):
    """One grid step = one batch row: its ``H`` absorbed queries against its
    ``K`` selected rows, one softmax (no running maximum: the scores of one
    row fit VMEM whole)."""
    rows = rows_ref[0]                                          # [K, W]
    s = jax.lax.dot_general(q_ref[0], rows, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    ok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
        < n_ref[pl.program_id(0)]
    s = jnp.where(ok, s, NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(ok, p, 0.0)
    o = jax.lax.dot_general(p.astype(rows.dtype), rows,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0] = (o / jnp.sum(p, axis=-1, keepdims=True)).astype(o_ref.dtype)


def selected_reference_reason(K: int, W: int) -> Optional[str]:
    """Why :func:`dsa_decode_selected`'s kernel cannot take these sizes."""
    if K % 128 or W % 128:
        return (f"{K} selected rows of {W} values are not whole 128-lane "
                "tiles")
    return None


# rows of one group of :func:`dsa_decode_selected`'s loop (one page lookup,
# one gather and one kernel call a group).  One: at the cell's 16 slots of
# 2,048 selected rows of 640 values an iteration costs 37.7 us a row alone,
# 36.5 in pairs and 35 in fours (the parent's straight line over 16 rows:
# 31.8), so a group's pad rows cost more than its size saves: 266 | 292 | 281
# us a layer at 7 live rows, 302 | 293 | 282 at 8, 339 | 363 | 415 at 9 for
# groups of 1 | 2 | 4 (my chip runs, PR 58, tools/dsa_select_bench.py; the
# whole table: PERF.md section 5, ``dots3-note-L5-ep16.serve-doc-48k``)
GATHER_GROUP = 1


def dsa_decode_selected(q, cache, sel, n_sel, page_table, *, layer: int,
                        sm_scale: float, live=None,
                        impl: Optional[str] = None):
    """Decode attention of a latent layer in its absorbed form over the
    SELECTED rows: ``q`` [B, H, W] (``kda_mla.mla_absorb``), ``cache`` [L,
    P, 1, page, W] latent pages, ``sel`` [B, K] int32 the positions each row
    attends (best first; the first ``n_sel[b]`` are real), through
    ``page_table``.  Returns [B, H, W] as :func:`mla_decode_paged` does.

    The work follows the batch, not the slot count: ``live`` [B] bool names
    the rows that decode (None: all of them), and a loop whose trip count is
    read at run time takes them :data:`GATHER_GROUP` at a time, live rows
    first (:func:`over_live_groups`).  One iteration looks up its rows' pages,
    gathers their selected rows out of the pool ([G, K, W], one gather over
    the pool as a flat array of rows) and runs the kernel on them, one grid
    step a row (scores, one softmax, the weighted sum), then writes the G
    outputs at those slots.  A row that does not decode costs no lookup, no
    gather and no grid step unless it pads the last group, and its output is
    zeros either way; no live row at all is a loop of no iterations."""
    impl = resolve_impl(impl)
    B, H, W = q.shape
    L, P, _, page, _ = cache.shape
    K = sel.shape[1]
    impl = kernel_or_reference("dsa_decode_selected", impl,
                               selected_reference_reason(K, W))
    G = min(GATHER_GROUP, B)
    pool = cache.reshape(L * P * page, W)
    q, n_sel = q.astype(cache.dtype), n_sel.astype(jnp.int32)
    table = page_table.astype(jnp.float32)
    by_row = lambda b, n_ref: (b, 0, 0)

    def group(at):
        take = lambda a: jnp.take(a, at, axis=0)
        s, n = take(sel), take(n_sel)
        on = None if live is None else take(live)
        if on is not None:
            # a row that pads the group: K different rows, not one row K
            # times (2,048 reads of one address cost more than a live
            # row's: my chip run, PR 58)
            s = jnp.where(on[:, None], s, jnp.arange(K))
        # the page each position lies on, as a one-hot product with the
        # row's table (exact in float32; a gather of B x K scalars through
        # the table took 0.33 ms a layer a step on the v5e, as long as half
        # the sort that made the positions: my chip run, PR 52)
        phys = jnp.einsum(
            "bkp,bp->bk", jax.nn.one_hot(s // page, page_table.shape[1],
                                         dtype=jnp.float32),
            take(table), precision=jax.lax.Precision.HIGHEST
        ).astype(jnp.int32)
        flat = (layer * P + phys) * page + s % page
        rows = pool.at[flat].get(mode="promise_in_bounds")      # [G, K, W]
        if impl == "xla":
            o = _selected_ref(take(q), rows, n, scale=sm_scale)
        else:
            o = pl.pallas_call(
                functools.partial(_selected_kernel, scale=sm_scale),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1, grid=(G,),
                    in_specs=[pl.BlockSpec((1, H, W), by_row),
                              pl.BlockSpec((1, K, W), by_row)],
                    out_specs=pl.BlockSpec((1, H, W), by_row)),
                out_shape=jax.ShapeDtypeStruct((G, H, W), q.dtype),
                interpret=interpret_flag(impl),
                name="dsa_decode_selected",
            )(n, take(q), rows)
        return o if on is None else jnp.where(on[:, None, None], o, 0)

    return over_live_groups(live, B, GATHER_GROUP, group,
                            jnp.zeros((B, H, W), q.dtype))


# heads of one grid step of :func:`kda_decode_step`: their k, decay and q
# columns (3 x this many) share one 128-lane tile
_KDA_HEADS_PER_STEP = 16


def kda_reference_reason(heads: int, d: int) -> Optional[str]:
    """Why the state kernel cannot take these sizes (None = it can)."""
    if d % 128:
        return f"a head of {d} values is not a multiple of the 128-lane tile"
    if heads % min(heads, _KDA_HEADS_PER_STEP):
        return f"{heads} heads are not whole steps of {_KDA_HEADS_PER_STEP}"
    return None


def _kda_step_kernel(rows_ref, cols_ref, v_ref, beta_ref, s_ref, o_ref,
                     s_out, *, hb):
    """One grid step = one LIVE batch row x ``hb`` heads.  ``cols`` [d, 128]
    holds, as COLUMNS over the key axis, the heads' k (lanes [0, hb)), decay
    e^g ([hb, 2 hb)) and q ([2 hb, 3 hb)); v, beta (broadcast over the
    lanes) and o are rows over the value axis.  A head's state [d, d] comes
    into VMEM once, is decayed, corrected and read out, and goes back
    through the alias."""
    del rows_ref                  # consumed by the index maps
    cols = cols_ref[0, 0]
    for h in range(hb):
        col = lambda j: cols[:, j * hb + h:j * hb + h + 1]      # [d, 1]
        k = col(0)
        S = s_ref[0, h] * col(1)
        u = beta_ref[0, h:h + 1, :] * (
            v_ref[0, h:h + 1, :] - jnp.sum(S * k, axis=0, keepdims=True))
        S = S + k * u
        s_out[0, h] = S
        o_ref[0, h:h + 1, :] = jnp.sum(S * col(2), axis=0, keepdims=True)


def kda_decode_step(state, q, k, v, g, beta, *, layer: int, live=None,
                    impl: Optional[str] = None):
    """The delta rule of a linear-attention layer for one token a row
    (``kda_mla.kda_step``), on the stacked per-slot state in place:
    ``state`` [L, B, H, d, d] float32 (key axis, value axis), q, k, v, g
    [B, H, d] float32 (g the log-decay), beta [B, H].  Returns (o [B, H, d]
    float32, state, rows visited).

    The grid follows the batch as :func:`_decode_attention`'s does: ``live``
    [B] bool names the rows that decode (None: all), step ``i`` of the
    first axis works on row ``rows[i]`` of the live rows in order, so a
    parked row costs no grid step, its state is neither read nor written
    (it keeps it), and its ``o`` is its ``v`` (the output is aliased onto
    it).  The XLA form updates every row and keeps the old state where a
    row is not live: it visits all ``B``."""
    impl = resolve_impl(impl)
    L, B, H, d, _ = state.shape
    impl = kernel_or_reference("kda_decode_step", impl,
                               kda_reference_reason(H, d))
    if impl == "xla":
        from deepspeed_tpu.models.kda_mla import kda_step

        o, new = kda_step(state[layer], q, k, v, g, beta)
        if live is not None:
            new = jnp.where(live[:, None, None, None], new, state[layer])
        return o, state.at[layer].set(new), jnp.asarray(B, jnp.int32)
    hb = min(H, _KDA_HEADS_PER_STEP)
    rows, n_live = _live_rows(live, B)
    # k, decay and q as columns: [B, H / hb, d, 3 hb] padded to the tile
    cols = jnp.stack([k, jnp.exp(g), q], axis=1).reshape(B, 3, H // hb, hb, d)
    cols = cols.transpose(0, 2, 4, 1, 3).reshape(B, H // hb, d, 3 * hb)
    cols = jnp.pad(cols, ((0, 0),) * 3 + ((0, 128 - 3 * hb),))
    row_of = lambda i, j, rows_ref: (rows_ref[i], j, 0)
    vec = pl.BlockSpec((1, hb, d), row_of)
    mat = pl.BlockSpec((1, hb, d, d),
                       lambda i, j, rows_ref: (layer * B + rows_ref[i], j,
                                               0, 0))
    o, new = pl.pallas_call(
        functools.partial(_kda_step_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_live, H // hb),
            in_specs=[pl.BlockSpec((1, 1, d, 128),
                                   lambda i, j, rows_ref: (rows_ref[i], j,
                                                           0, 0)),
                      vec, vec, mat],
            out_specs=[vec, mat]),
        out_shape=[jax.ShapeDtypeStruct((B, H, d), jnp.float32),
                   jax.ShapeDtypeStruct((L * B, H, d, d), jnp.float32)],
        # operands count the scalar-prefetch array: v is 2, the state 4
        input_output_aliases={2: 0, 4: 1},
        interpret=interpret_flag(impl),
        name="kda_decode_step",
    )(rows, cols, v, jnp.broadcast_to(beta[..., None], (B, H, d)),
      state.reshape(L * B, H, d, d))
    return o, new.reshape(state.shape), jnp.asarray(n_live, jnp.int32)


# ---------------------------------------------------------------------------
# A selective state space (models/ssm_moe.py: Mamba-2), one token a row
# ---------------------------------------------------------------------------

# the state block of one grid step of :func:`ssm_decode_step` (read and
# written back, each double-buffered: four of these in VMEM)
_SSM_STEP_BYTES = 2 * 2**20


def ssm_heads_per_tile(heads: int, head_dim: int, groups: int) -> int:
    """Heads of ONE group whose ``head_dim`` values lie side by side across
    the lanes of a state tile (:func:`ssm_state_pack`): as many as fill 128
    lanes, a divisor of the heads a group has (1 at a head of 128 or more)."""
    per_group = heads // groups
    return max(d for d in range(1, per_group + 1)
               if per_group % d == 0 and d * head_dim <= max(128, head_dim))


def ssm_state_pack(S, pk: int):
    """A state [..., H, P, N] (head, value, state dim) as the cache keeps it:
    [..., H / pk, N, pk P], the state dim down the sublanes and ``pk`` heads'
    values across the lanes, so that a step's ``x``, decay and output are
    ROWS (their natural flat order) and only B and C are columns."""
    *lead, H, P, N = S.shape
    S = S.reshape(*lead, H // pk, pk, P, N)
    return jnp.moveaxis(S, -1, -3).reshape(*lead, H // pk, N, pk * P)


def ssm_state_unpack(S, pk: int):
    """:func:`ssm_state_pack`'s inverse: [..., H / pk, N, pk P] -> [..., H,
    P, N]."""
    *lead, T, N, W = S.shape
    S = jnp.moveaxis(S.reshape(*lead, T, N, pk, W // pk), -3, -1)
    return S.reshape(*lead, T * pk, W // pk, N)


def ssm_step_ref(S, x, dt, a, Bm, Cm):
    """One token of the selective state space on the LOGICAL state ``S``
    [..., H, P, N] float32: x [..., H, P], dt [..., H] (after its softplus),
    a [H] < 0, Bm and Cm [..., G, N] (head i reads group i // (H / G)):

        S <- exp(dt a) S + (dt x) B^T;   y = S C

    Returns (y [..., H, P], S).  Elementwise float32: the same on every
    backend."""
    rep = x.shape[-2] // Bm.shape[-2]
    Bh, Ch = (jnp.repeat(t, rep, axis=-2) for t in (Bm, Cm))
    S = S * jnp.exp(dt * a)[..., None, None] \
        + (dt[..., None] * x)[..., None] * Bh[..., None, :]
    return (S * Ch[..., None, :]).sum(-1), S


def ssm_reference_reason(tiles: int, N: int, W: int,
                         groups: int) -> Optional[str]:
    """Why the state kernel cannot take these sizes (None = it can)."""
    if W % 128 or N % 8:
        return (f"a state tile of {N} x {W} is not whole (8, 128) float32 "
                "tiles")
    if 2 * groups > 128:
        return f"B and C of {groups} groups do not fit one 128-lane tile"
    if tiles // groups * N * W * 4 > _SSM_STEP_BYTES:
        return "one group's state is more than a grid step holds"
    return None


def _ssm_step_kernel(rows_ref, cols_ref, da_ref, x_ref, s_ref, y_ref, s_out,
                     *, gs, per_group):
    """One grid step = one LIVE batch row x ``gs`` groups of ``per_group``
    state tiles [N, W].  ``cols`` [N, 128] holds, as COLUMNS down the state
    dim, the groups' B (lanes [0, gs)) and C ([gs, 2 gs)); the decay and ``dt
    x`` are rows across a tile's lanes.  A tile comes into VMEM once, is
    decayed, takes its outer product, is read out against C, and goes back
    through the alias."""
    del rows_ref                  # consumed by the index maps
    cols = cols_ref[0, 0]
    for g in range(gs):
        b_col, c_col = cols[:, g:g + 1], cols[:, gs + g:gs + g + 1]  # [N, 1]
        for t in range(g * per_group, (g + 1) * per_group):
            S = s_ref[0, t] * da_ref[0, t:t + 1, :] \
                + b_col * x_ref[0, t:t + 1, :]
            s_out[0, t] = S
            y_ref[0, t:t + 1, :] = jnp.sum(S * c_col, axis=0, keepdims=True)


def ssm_decode_step(state, x, dt, a, Bm, Cm, *, layer: int, live=None,
                    impl: Optional[str] = None):
    """The selective state space of a Mamba-2 layer for one token a row
    (:func:`ssm_step_ref`), on the stacked per-slot state in place:
    ``state`` [L, B, H / pk, N, pk P] float32 (:func:`ssm_state_pack`), x
    [B, H, P], dt [B, H], a [H], Bm and Cm [B, G, N], all float32.  Returns
    (y [B, H, P] float32, state, rows visited).

    The grid follows the batch as :func:`kda_decode_step`'s does: ``live``
    [B] bool names the rows that decode (None: all), a parked row costs no
    grid step, its state is neither read nor written, and its ``y`` is its
    ``dt x`` (the output is aliased onto it).  The XLA form updates every
    row and keeps the old state where a row is not live: it visits all
    ``B``."""
    impl = resolve_impl(impl)
    L, B, T, N, W = state.shape
    H, P = x.shape[1:]
    G = Bm.shape[1]
    pk = H // T
    impl = kernel_or_reference("ssm_decode_step", impl,
                               ssm_reference_reason(T, N, W, G))
    if impl == "xla":
        y, new = ssm_step_ref(ssm_state_unpack(state[layer], pk), x, dt, a,
                              Bm, Cm)
        new = ssm_state_pack(new, pk)
        if live is not None:
            new = jnp.where(live[:, None, None, None], new, state[layer])
        return y, state.at[layer].set(new), jnp.asarray(B, jnp.int32)
    per_group = T // G
    gs = max(d for d in range(1, G + 1) if G % d == 0
             and d * per_group * N * W * 4 <= _SSM_STEP_BYTES)
    rows, n_live = _live_rows(live, B)
    # B and C as columns: [B, G / gs, N, 2 gs] padded to the lane tile
    cols = jnp.concatenate([t.reshape(B, G // gs, gs, N).swapaxes(2, 3)
                            for t in (Bm, Cm)], axis=-1)
    cols = jnp.pad(cols, ((0, 0),) * 3 + ((0, 128 - 2 * gs),))
    flat = lambda t: t.reshape(B, T, W)
    da = flat(jnp.broadcast_to(jnp.exp(dt * a)[..., None], (B, H, P)))
    tb = gs * per_group
    vec = pl.BlockSpec((1, tb, W), lambda i, j, rows_ref: (rows_ref[i], j, 0))
    mat = pl.BlockSpec((1, tb, N, W),
                       lambda i, j, rows_ref: (layer * B + rows_ref[i], j,
                                               0, 0))
    y, new = pl.pallas_call(
        functools.partial(_ssm_step_kernel, gs=gs, per_group=per_group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_live, G // gs),
            in_specs=[pl.BlockSpec((1, 1, N, 128),
                                   lambda i, j, rows_ref: (rows_ref[i], j,
                                                           0, 0)),
                      vec, vec, mat],
            out_specs=[vec, mat]),
        out_shape=[jax.ShapeDtypeStruct((B, T, W), jnp.float32),
                   jax.ShapeDtypeStruct((L * B, T, N, W), jnp.float32)],
        # operands count the scalar-prefetch array: dt x is 3, the state 4
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret_flag(impl),
        name="ssm_decode_step",
    )(rows, cols, da, flat(dt[..., None] * x), state.reshape(L * B, T, N, W))
    return (y.reshape(B, H, P), new.reshape(state.shape),
            jnp.asarray(n_live, jnp.int32))


# ---------------------------------------------------------------------------
# EVA (models/eva.py): decode attention over window pages + summary pages,
# and the pooling of a filled window into its summary rows
# ---------------------------------------------------------------------------

def eva_reference_reason(page: int, window: int, chunk: int,
                         head_dim: Optional[int] = None) -> Optional[str]:
    """Why the EVA kernels cannot take these sizes (None = they can): a page
    is the decode kernel's key block along the 128 lanes, and the pooling
    kernel writes a page's ``page / chunk`` summaries as whole 16-row
    tiles; the decode kernel (``head_dim`` given) copies its pages itself,
    which takes rows that fill the lanes (:func:`walks_pages`)."""
    if page % 128:
        return f"page of {page} tokens is not a multiple of the 128-lane tile"
    if window % page or (page // chunk) % 16:
        return (f"window {window} / page {page} / chunk {chunk}: a page's "
                f"summaries are not whole 16-row tiles")
    if head_dim is not None and not walks_pages(head_dim):
        return f"head dim {head_dim} does not fill the 128 lanes"
    return None


def eva_decode_paged(q, kcache, vcache, pos, page_table, *, layer,
                     window: int, chunk: int,
                     sm_scale: Optional[float] = None, live=None,
                     impl: Optional[str] = None):
    """EVA decode attention over the paged pool.  q [B, H, Dh] at absolute
    positions ``pos`` [B]; caches stacked [L, P, H, page, Dh] read at layer
    ``layer`` (a Python int or a traced scalar); ``page_table`` [B, wp +
    sp]: a row's ``wp = W / page`` window pages, then its summary pages
    (``serving/paged_kv.py``).
    :func:`_flash_decode_paged`'s schedule with two runs of pages a row
    (:func:`_page_runs`): one grid step is one LIVE row (``live`` [B] bool,
    None: all) x ``hb`` heads and walks the window pages ``0 .. (pos % W) //
    page``, of which rows ``[0, pos % W]`` count, then the summary pages
    that hold the ``(pos // W) W / C`` rows of the closed windows (none
    before the first close), one online softmax over both.  A page before a
    run's last is one copy, the last :func:`_last_page_pieces` copies of
    :data:`FETCH_ROWS` rows; what a buffer holds past the rows that count (a
    window page: the window before's keys) weighs 0 and its values count as
    0.  Pages a row does not attend cost nothing, a row that does not decode
    costs nothing and gets its ``q`` back."""
    impl = resolve_impl(impl)
    B, H, Dh = q.shape
    L, P, hkv, page, _ = kcache.shape
    scale = sm_scale if sm_scale is not None else 1.0 / (Dh ** 0.5)
    pos = jnp.asarray(pos, jnp.int32)
    impl = kernel_or_reference("eva_decode_paged", impl,
                               eva_reference_reason(page, window, chunk, Dh))
    if impl == "xla":
        from deepspeed_tpu.models import eva
        from deepspeed_tpu.models.decoding import paged_logical_view

        return eva.cached_attention(
            q[:, :, None], paged_logical_view(kcache[layer], page_table),
            paged_logical_view(vcache[layer], page_table), pos[:, None],
            window=window, chunk=chunk, scale=scale)[:, :, 0]
    view = (L * P, hkv, page, Dh)
    rows, n_live = _live_rows(live, B)
    o = _walk_pages(
        rows, jnp.asarray(n_live, jnp.int32), pos,
        jnp.reshape(layer * P, (1,)).astype(jnp.int32),
        page_table.astype(jnp.int32), q.reshape(B, hkv, H // hkv, Dh),
        kcache.reshape(view), vcache.reshape(view),
        _alibi_slopes(H, hkv, False), scale=scale, alibi=False, impl=impl,
        name="eva_decode_paged", window=window, chunk=chunk)
    return o.reshape(B, H, Dh)


def _eva_summarize_kernel(pp_ref, sp_ref, k_ref, v_ref, mu_ref, phi_ref,
                          ks_out, vs_out, *, chunk):
    """One grid step = one closing row x ``hb`` heads x one window page: the
    page's ``page / chunk`` chunk summaries, written as one tile group of
    the row's summary page."""
    del pp_ref, sp_ref                # consumed by the index maps
    hb, page, Dh = k_ref.shape[1:]
    kc = k_ref[0].astype(jnp.float32).reshape(hb, page // chunk, chunk, Dh)
    vc = v_ref[0].astype(jnp.float32).reshape(hb, page // chunk, chunk, Dh)

    def weights(w_ref):
        z = jnp.sum(kc * w_ref[:].astype(jnp.float32)[:, None, None, :],
                    axis=-1, keepdims=True)            # [hb, n, chunk, 1]
        e = jnp.exp(z - jnp.max(z, axis=-2, keepdims=True))
        return e / jnp.sum(e, axis=-2, keepdims=True)

    ks_out[0] = jnp.sum(weights(mu_ref) * kc, axis=-2).astype(ks_out.dtype)
    vs_out[0] = jnp.sum(weights(phi_ref) * vc, axis=-2).astype(vs_out.dtype)


def eva_summarize_paged(kcache, vcache, mu, phi, pos, page_table, *,
                        layer: int, window: int, chunk: int,
                        impl: Optional[str] = None):
    """The window close of a decode step, in place on the stacked pool: for
    every row whose position ``pos[b]`` (already appended) is the last of its
    window, pool the ``W`` window rows of layer ``layer`` into the ``W/C``
    summary rows of window ``pos[b] // W`` (``models/eva.py:summarize``; mu,
    phi [H, Dh]).  Other rows cost nothing: the kernel's grid is (rows that
    close, head groups, window pages), its first bound read from the
    positions at run time, so a step in which no row closes a window (all
    but one in ``W`` a row) launches a kernel of no steps."""
    impl = resolve_impl(impl)
    L, P, H, page, Dh = kcache.shape
    pos = jnp.asarray(pos, jnp.int32)
    B = pos.shape[0]
    closing = (pos + 1) % window == 0
    wp, per = window // page, window // chunk
    impl = kernel_or_reference("eva_summarize_paged", impl,
                               eva_reference_reason(page, window, chunk))
    if impl == "xla":
        from deepspeed_tpu.models import eva
        from deepspeed_tpu.models.decoding import (_scatter_view,
                                                   paged_logical_view)

        def pool(kc, vc):
            kv, vv = (paged_logical_view(c[layer], page_table)
                      for c in (kc, vc))
            ks, vs = eva.write_window_summaries(
                kv, vv, mu, phi, pos // window, window=window, chunk=chunk)
            keep = closing[:, None, None, None]
            # rows that close nothing scatter back what they gathered
            return (kc.at[layer].set(_scatter_view(
                        kc[layer], jnp.where(keep, ks, kv), page_table)),
                    vc.at[layer].set(_scatter_view(
                        vc[layer], jnp.where(keep, vs, vv), page_table)))

        return jax.lax.cond(jnp.any(closing), pool,
                            lambda kc, vc: (kc, vc), kcache, vcache)
    n_out = page // chunk                       # summaries of one window page
    groups = page // n_out                      # tile groups of a page
    hb = max(d for d in range(1, 9) if H % d == 0)
    # the closing rows first; entries past their count are never visited
    rows = jnp.nonzero(closing, size=B, fill_value=0)[0]
    table = page_table[rows].astype(jnp.int32)
    # each (closing row, window page): its physical window page, and the
    # physical summary page and tile group its summaries go to
    srow = ((pos[rows] // window) * per)[:, None] \
        + jnp.arange(wp, dtype=jnp.int32)[None] * n_out
    sp = (jnp.take_along_axis(table, wp + srow // page, axis=1) * groups
          + (srow % page) // n_out)
    pool = jax.ShapeDtypeStruct((L * P, H, page, Dh), kcache.dtype)
    base = layer * P
    vec = pl.BlockSpec((hb, Dh), lambda i, g, j, *_: (g, 0))
    src = pl.BlockSpec(
        (1, hb, page, Dh),
        lambda i, g, j, pp_ref, sp_ref: (base + pp_ref[i, j], g, 0, 0))
    dst = pl.BlockSpec(
        (1, hb, n_out, Dh),
        lambda i, g, j, pp_ref, sp_ref: (base + sp_ref[i, j] // groups, g,
                                         sp_ref[i, j] % groups, 0))
    k4, v4 = pl.pallas_call(
        functools.partial(_eva_summarize_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(jnp.sum(closing, dtype=jnp.int32), H // hb, wp),
            in_specs=[src, src, vec, vec], out_specs=[dst, dst]),
        out_shape=[pool, pool],
        # operands count the two scalar-prefetch arrays: the pools, of which
        # window pages are read and summary groups written, are 2 and 3
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret_flag(impl),
        name="eva_summarize_paged",
    )(table[:, :wp], sp.astype(jnp.int32),
      kcache.reshape(pool.shape), vcache.reshape(pool.shape),
      mu.astype(jnp.float32), phi.astype(jnp.float32))
    return k4.reshape(kcache.shape), v4.reshape(vcache.shape)


# ---------------------------------------------------------------------------
# fused_proj_norm: ctx @ wo (+bo) + resid -> r; norm(r | resid) -> h
# ---------------------------------------------------------------------------

def _proj_norm_ref(ctx, resid, wo, bo, scale, bias, *, kind, eps, parallel,
                   wscale=None):
    if wscale is not None:
        wo = _deq(wo, wscale.reshape(1, -1), ctx.dtype)
    o = jax.lax.dot_general(ctx, wo, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if bo is not None:
        o = o + bo.astype(jnp.float32)
    r32 = resid.astype(jnp.float32) + o
    nsrc = resid.astype(jnp.float32) if parallel else r32
    h = _normalize(nsrc, scale.astype(jnp.float32),
                   bias.astype(jnp.float32), kind, eps)
    return r32.astype(resid.dtype), h.astype(ctx.dtype)


def _proj_norm_kernel(ctx_ref, res_ref, wo_ref, ws_ref, bo_ref, s_ref, b_ref,
                      r_ref, h_ref, acc_scr, *, kind, eps, parallel, has_bias,
                      quant, nm):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    wo = _deq(wo_ref[:], ws_ref[:], ctx_ref.dtype) if quant else wo_ref[:]
    acc_scr[:] += jax.lax.dot_general(ctx_ref[:], wo, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)

    @pl.when(j == nm - 1)
    def _finish():
        o = acc_scr[:]
        if has_bias:
            o = o + bo_ref[:].astype(jnp.float32)
        res32 = res_ref[:].astype(jnp.float32)
        r32 = res32 + o
        nsrc = res32 if parallel else r32
        h = _normalize(nsrc, s_ref[:].astype(jnp.float32),
                       b_ref[:].astype(jnp.float32), kind, eps)
        r_ref[:] = r32.astype(r_ref.dtype)
        h_ref[:] = h.astype(h_ref.dtype)


def fused_proj_norm(ctx, resid, wo, bo=None, scale=None, bias=None, *,
                    kind: str = "layernorm", eps: float = 1e-5,
                    parallel: bool = False, wscale=None, layer=None,
                    impl: Optional[str] = None):
    """ctx: [B, M]; wo: [M, D] (with ``layer``, an index that may be traced:
    a stack [L, M, D] read in place, :func:`_layer_call`); resid: [B, D].
    Returns (r, h): the updated
    residual stream (in ``resid.dtype``) and the normed MLP input (in
    ``ctx.dtype``) (``parallel=True`` norms the
    layer input instead — gpt-neox parallel residual).  ``wscale`` marks
    ``wo`` as int8 (dequant in-kernel).

    Reference: ``(R) pt_binding.cpp`` residual+bias fusion after the
    attention out-GEMM plus the next block's norm."""
    impl = resolve_impl(impl)
    if bias is None:
        bias = jnp.zeros_like(scale)
    if impl == "xla":
        return _proj_norm_ref(ctx, resid, _layer_of(wo, layer), bo, scale,
                              bias, kind=kind, eps=eps, parallel=parallel,
                              wscale=wscale)
    B, M = ctx.shape
    D = wo.shape[-1]
    quant = wscale is not None
    has_bias = bo is not None
    # blocked over the contraction dim (wo's rows): the norm needs whole
    # output rows, so grid step j accumulates ctx[:, j] @ wo[j, :] into an
    # fp32 scratch and the last step adds the residual and norms.  Sized
    # like fused_norm_qkv (quant counts the fp32 dequant intermediate).
    # beside the tile: the stream, the two results and the accumulator
    # (a layer of a stack: the two results counted twice, as the pipeline
    # holds them; at 256 rows of 2,560 against a [5120, 2560] layer the
    # single count's 640-row tile left the call 52 KB over the scoped 16 MB)
    twice = 1 if layer is None else 2
    bm = _col_block(D, M, 4 if quant else wo.dtype.itemsize,
                    resident=B * D * (resid.dtype.itemsize + twice * (
                        resid.dtype.itemsize + ctx.dtype.itemsize) + 4))
    bo2 = (bo if has_bias else jnp.zeros((D,), ctx.dtype)).reshape(1, D)
    ws = (wscale if quant else jnp.ones((D,), jnp.float32)).reshape(1, D)
    kernel = functools.partial(_proj_norm_kernel, kind=kind, eps=eps,
                               parallel=parallel, has_bias=has_bias,
                               quant=quant, nm=M // bm)
    row = pl.BlockSpec((1, D), lambda j: (0, 0))
    act = pl.BlockSpec((B, D), lambda j: (0, 0))
    r, h = _layer_call(
        kernel, layer, (2,),
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((B, bm), lambda j: (0, j)),
                  act,
                  pl.BlockSpec((bm, D), lambda j: (j, 0)),
                  row, row, row, row],
        out_specs=[act, act],
        out_shape=[jax.ShapeDtypeStruct((B, D), resid.dtype),
                   jax.ShapeDtypeStruct((B, D), ctx.dtype)],
        scratch_shapes=[pltpu.VMEM((B, D), jnp.float32)],
        interpret=interpret_flag(impl),
        name="fused_proj_norm",
    )(ctx, resid, wo, ws, bo2, scale.reshape(1, D), bias.reshape(1, D))
    return r, h


# ---------------------------------------------------------------------------
# fused_mlp: h @ w_up (* act(h @ w_gate)) @ w_down + r, blocked over FFN dim
# ---------------------------------------------------------------------------

def _mlp_ref(h, r, w_up, w_gate, w_down, b_up, b_gate, b_down, *, act,
             wscales=None):
    if wscales is not None:
        su, sg, sd = wscales
        w_up = _deq(w_up, su.reshape(1, -1), h.dtype)
        w_down = _deq(w_down, sd.reshape(1, -1), h.dtype)
        if w_gate is not None:
            w_gate = _deq(w_gate, sg.reshape(1, -1), h.dtype)
    up = jax.lax.dot_general(h, w_up, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if b_up is not None:
        up = up + b_up.astype(jnp.float32)
    if w_gate is not None:
        g = jax.lax.dot_general(h, w_gate, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if b_gate is not None:
            g = g + b_gate.astype(jnp.float32)
        a = _act(act, g) * up
    else:
        a = _act(act, up)
    y = jax.lax.dot_general(a.astype(h.dtype), w_down,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if b_down is not None:
        y = y + b_down.astype(jnp.float32)
    return (r.astype(jnp.float32) + y).astype(r.dtype)


def _mlp_kernel(h_ref, r_ref, wu_ref, wg_ref, wd_ref, su_ref, sg_ref,
                sd_ref, bu_ref, bg_ref, bd_ref, o_ref, acc_scr, *, act, glu,
                has_bias, nf, quant):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = r_ref[:].astype(jnp.float32)
        if has_bias:
            acc_scr[:] += bd_ref[:].astype(jnp.float32)

    h = h_ref[:]
    wu = _deq(wu_ref[:], su_ref[:], h.dtype) if quant else wu_ref[:]
    up = jax.lax.dot_general(h, wu, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if has_bias:
        up = up + bu_ref[:].astype(jnp.float32)
    if glu:
        wg = _deq(wg_ref[:], sg_ref[:], h.dtype) if quant else wg_ref[:]
        g = jax.lax.dot_general(h, wg, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if has_bias:
            g = g + bg_ref[:].astype(jnp.float32)
        a = _act(act, g) * up
    else:
        a = _act(act, up)
    wd = _deq(wd_ref[:], sd_ref[:], h.dtype) if quant else wd_ref[:]
    acc_scr[:] += jax.lax.dot_general(a.astype(h.dtype), wd,
                                      (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)

    @pl.when(j == nf - 1)
    def _finish():
        o_ref[:] = acc_scr[:].astype(o_ref.dtype)


def fused_mlp(h, r, w_up, w_down, w_gate=None, b_up=None, b_gate=None,
              b_down=None, *, act: str = "gelu", wscales=None, layer=None,
              impl: Optional[str] = None):
    """h: [B, D] (normed); r: [B, D] (residual).  Returns r + mlp(h) in
    ``r.dtype``.  ``layer`` (an index, may be traced): the three weights are
    stacks [L, ...] read in place (:func:`_layer_call`).
    ``wscales`` = (up, gate, down) per-out-channel fp32 scales marking the
    weights as int8 (dequant in-kernel; gate entry ignored when no GLU).

    Blocked over the FFN dim: grid step j computes the partial product of
    FFN slice j and accumulates the down-projection into a VMEM scratch, so
    the weight working set is one tile per matrix (reference: the inference
    MLP gemm chain with fused bias+activation epilogues)."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return _mlp_ref(h, r, _layer_of(w_up, layer),
                        _layer_of(w_gate, layer), _layer_of(w_down, layer),
                        b_up, b_gate, b_down, act=act, wscales=wscales)
    B, D = h.shape
    F = w_up.shape[-1]
    quant = wscales is not None
    per = 3 if w_gate is not None else 2
    # see fused_norm_qkv: quant blocks sized by the fp32 dequant intermediate
    bf = _col_block(D * per, F, 4 if quant else w_up.dtype.itemsize)
    glu = w_gate is not None
    has_bias = b_up is not None
    wdt = h.dtype if not quant else jnp.int8
    wg = w_gate if glu else jnp.zeros((D, bf), wdt)
    bu2 = (b_up if has_bias else jnp.zeros((F,), h.dtype)).reshape(1, F)
    bg2 = (b_gate if (glu and has_bias and b_gate is not None)
           else jnp.zeros((F,), h.dtype)).reshape(1, F)
    bd2 = (b_down if has_bias and b_down is not None
           else jnp.zeros((D,), h.dtype)).reshape(1, D)
    if quant:
        su, sg, sd = wscales
        su2 = su.reshape(1, F)
        sg2 = (sg.reshape(1, F) if glu else jnp.ones((1, bf), jnp.float32))
        sd2 = sd.reshape(1, D)
    else:
        su2 = jnp.ones((1, F), jnp.float32)
        sg2 = jnp.ones((1, F if glu else bf), jnp.float32)
        sd2 = jnp.ones((1, D), jnp.float32)
    kernel = functools.partial(_mlp_kernel, act=act, glu=glu,
                               has_bias=has_bias, nf=F // bf, quant=quant)
    gate_spec = (pl.BlockSpec((D, bf), lambda j: (0, j)) if glu
                 else pl.BlockSpec((D, bf), lambda j: (0, 0)))
    gate_s_spec = (pl.BlockSpec((1, bf), lambda j: (0, j)) if glu
                   else pl.BlockSpec((1, bf), lambda j: (0, 0)))
    return _layer_call(
        kernel, layer, (2, 3, 4) if glu else (2, 4),
        grid=(F // bf,),
        in_specs=[pl.BlockSpec((B, D), lambda j: (0, 0)),
                  pl.BlockSpec((B, D), lambda j: (0, 0)),
                  pl.BlockSpec((D, bf), lambda j: (0, j)),
                  gate_spec,
                  pl.BlockSpec((bf, D), lambda j: (j, 0)),
                  pl.BlockSpec((1, bf), lambda j: (0, j)),
                  gate_s_spec,
                  pl.BlockSpec((1, D), lambda j: (0, 0)),
                  pl.BlockSpec((1, bf), lambda j: (0, j)),
                  pl.BlockSpec((1, bf), lambda j: (0, j)),
                  pl.BlockSpec((1, D), lambda j: (0, 0))],
        out_specs=pl.BlockSpec((B, D), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, D), r.dtype),
        scratch_shapes=[pltpu.VMEM((B, D), jnp.float32)],
        interpret=interpret_flag(impl),
        name="fused_mlp",
    )(h, r, w_up, wg, w_down, su2, sg2, sd2, bu2, bg2, bd2)


# ---------------------------------------------------------------------------
# fused_moe_mlp: r + sum_e combine[:, e] * down_e(act(h gate_e) * (h up_e))
# ---------------------------------------------------------------------------

def _moe_mlp_ref(h, r, combine, w_up, w_gate, w_down, *, act):
    """Every expert on every row, weighed by ``combine`` (parity target)."""
    up = jnp.einsum("bd,edf->ebf", h, w_up,
                    preferred_element_type=jnp.float32)
    if w_gate is not None:
        a = _act(act, jnp.einsum("bd,edf->ebf", h, w_gate,
                                 preferred_element_type=jnp.float32)) * up
    else:
        a = _act(act, up)
    y = jnp.einsum("ebf,efd->ebd", a.astype(h.dtype), w_down,
                   preferred_element_type=jnp.float32)
    y = jnp.einsum("be,ebd->bd", combine.astype(jnp.float32), y)
    return (r.astype(jnp.float32) + y).astype(h.dtype)


# fused_moe_mlp's row cut.  A pass of the MXU over a weight tile costs the
# tile's load whatever rows stream behind it, up to the array's height: 128
# rows is the tile whose pass is never dearer than the rows it works (two
# passes of 64 cost what one of 128 does; ``grouped_matmul.ROW_TILE``)
_MOE_ROW_TILE = 128
# a v5e's MXU FLOP/s over its HBM bytes/s (197e12 / 819e9): a dense matmul
# of r rows spends 2 r FLOP a weight element, so at bf16 its MXU time passes
# its weights' stream time from r = 240 rows on
_FLOPS_PER_HBM_BYTE = 240


def moe_row_tile(rows: int, itemsize: int = 2) -> Optional[int]:
    """Rows a MXU pass of :func:`fused_moe_mlp` works where the call cuts
    its passes to the live rows' tiles, None where it keeps ONE pass over all
    ``rows``: the cut is taken where the static shapes say that the dense
    call's MXU time passes its weights' stream time (256 rows at bf16: yes;
    128 and fewer: no) and the rows are whole tiles."""
    if 2 * rows <= _FLOPS_PER_HBM_BYTE * itemsize or rows % _MOE_ROW_TILE:
        return None
    return _MOE_ROW_TILE


def moe_row_tiles_worked(live, tile: int):
    """Row tiles the cut call works: ``ceil(live rows / tile)`` (int32)."""
    return (jnp.sum(live, dtype=jnp.int32) + tile - 1) // tile


def _moe_mlp_kernel(*refs, act, glu, ne, nf, row_tile=None):
    """One grid step = one expert x one FFN tile (the whole expert where
    ``nf`` is 1): the rows against the tile, the down-projection weighed by
    this expert's combine column and added into the float32 accumulator
    (which starts at the residual).  ``refs``: under ``row_tile`` the
    scalar-prefetched (row tiles to work, the layer's first expert) first;
    the rows, the residual, the combine column, the up tile, the gate's
    where the experts have one, the down tile, the output and the
    accumulator.  Under ``row_tile`` the rows come live-first and only the
    first ``tiles`` tiles of them meet the weights: the others keep their
    residual."""
    if row_tile:
        tiles, refs = refs[0][0], refs[1:]
    h_ref, r_ref, c_ref, wu_ref = refs[:4]
    wg_ref, (wd_ref, o_ref, acc_scr) = (refs[4] if glu else None), refs[-3:]
    e, j = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (j == 0))
    def _init():
        acc_scr[:] = r_ref[:].astype(jnp.float32)

    dot = functools.partial(jax.lax.dot_general,
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    def work(at):
        h = h_ref[at]
        up = dot(h, wu_ref[:])
        a = _act(act, dot(h, wg_ref[:])) * up if glu else _act(act, up)
        acc_scr[at] += c_ref[at] * dot(a.astype(h.dtype), wd_ref[:])

    if row_tile:
        for t in range(h_ref.shape[0] // row_tile):
            pl.when(t < tiles)(functools.partial(
                work, pl.ds(t * row_tile, row_tile)))
    else:
        work(slice(None))

    @pl.when((e == ne - 1) & (j == nf - 1))
    def _finish():
        o_ref[:] = acc_scr[:].astype(o_ref.dtype)


def fused_moe_mlp(h, r, combine, w_up, w_down, w_gate=None, *,
                  layer: Optional[int] = None, act: str = "silu",
                  live=None, impl: Optional[str] = None):
    """h: [B, D] (normed); r: [B, D] (residual); ``combine`` [B, E] float32,
    each row's router weight per expert and 0 where the expert was not
    chosen.  Expert weights [E, D, F] / [E, F, D] — or, with ``layer=l``,
    the model's STACKED [L, E, D, F] / [L, E, F, D] arrays read at static
    layer offset ``l`` through the index maps, so the decode path holds no
    second copy of them.  Returns r + sum_e combine[:, e] * mlp_e(h).

    The grid walks (expert, FFN tile): each expert's matrices pass through
    VMEM once and ALL rows run against them, chosen or not — an unchosen
    expert adds ``0 *`` a finite number, so the result is the exact dropless
    mixture whatever the routing.  A small expert is ONE tile
    (:func:`moe_expert_block`: the grid is then an expert a step, the
    contraction over F one float32 accumulation, and the call sets its own
    VMEM limit); a step boundary costs the chip about a microsecond of idle
    HBM, which is why.  Up to ~128 rows the call is bound by those weight
    bytes (every expert is hit by some row almost every step), not by the
    E/k times more FLOPs than the rows asked for; from ~240 rows on the
    MXU's time for ALL rows passes the bytes' (256 rows of 2,688 against 64
    two-matrix experts 1,920 wide: 339 GFLOP = 1.72 ms at peak, 1.61 ms of
    bytes).  ``live`` [B] bool, the rows that decode (None: every row):
    where :func:`moe_row_tile` says the shapes are such, the rows go
    through the kernel live-first (:func:`_live_rows`) and the MXU passes
    run over the ``ceil(live / tile)`` row tiles that hold a live row, the
    count scalar-prefetched and read at run time; a row that does not
    decode then gets ``r`` back (elsewhere it gets its mixture like any
    row: the callers read live rows only).  Every held expert's blocks
    still pass through VMEM once a call (PERF.md, Findings, PRs 65, 67)."""
    impl = resolve_impl(impl)
    glu = w_gate is not None
    if impl == "xla":
        pick = (lambda w: w) if layer is None else (lambda w: w[layer])
        return _moe_mlp_ref(h, r, combine, pick(w_up),
                            pick(w_gate) if glu else None, pick(w_down),
                            act=act)
    B, D = h.shape
    E, _, F = w_up.shape[-3:]
    item = w_up.dtype.itemsize
    bf, vmem_limit = moe_expert_block(
        B, D, F, matrices=3 if glu else 2, itemsize=item,
        row_itemsize=h.dtype.itemsize)
    weights = (w_up.reshape(-1, D, F),
               *([w_gate.reshape(-1, D, F)] if glu else []),
               w_down.reshape(-1, F, D))
    base = 0 if layer is None else layer * E
    row_tile = None if live is None else moe_row_tile(B, item)
    if row_tile:
        return _moe_mlp_live_rows(
            h, r, combine, live, jnp.asarray(base, jnp.int32), *weights,
            act=act, bf=bf, vmem_limit=vmem_limit, row_tile=row_tile,
            impl=impl)
    return _moe_mlp_call(h, r, combine, weights, lambda e: base + e, act=act,
                         bf=bf, vmem_limit=vmem_limit, impl=impl)


def _moe_mlp_call(h, r, combine, weights, expert, *prefetch, act, bf,
                  vmem_limit, impl, row_tile=None):
    """The ``pallas_call`` of :func:`fused_moe_mlp` over ``weights`` [L * E,
    D, F] (up, the gate where the experts have one) and [L * E, F, D]:
    ``expert(e, *scalar refs)`` is grid expert ``e``'s place among them,
    ``prefetch`` the scalar-prefetched operands (the row cut's)."""
    B, D = h.shape
    E, F, glu = combine.shape[1], weights[0].shape[-1], len(weights) == 3
    rows = pl.BlockSpec((B, D), lambda e, j, *s: (0, 0))
    cols = pl.BlockSpec((None, D, bf),
                        lambda e, j, *s: (expert(e, *s), 0, j))
    grid = dict(
        grid=(E, F // bf),
        # (experts of two matrices stream two: no tile stands in for a gate)
        in_specs=[rows, rows,
                  pl.BlockSpec((None, B, 1), lambda e, j, *s: (e, 0, 0)),
                  cols, *([cols] if glu else []),
                  pl.BlockSpec((None, bf, D),
                               lambda e, j, *s: (expert(e, *s), j, 0))],
        out_specs=rows,
        scratch_shapes=[pltpu.VMEM((B, D), jnp.float32)])
    if prefetch:
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), **grid))
    return pl.pallas_call(
        functools.partial(_moe_mlp_kernel, act=act, glu=glu, ne=E,
                          nf=F // bf, row_tile=row_tile),
        **grid,
        out_shape=jax.ShapeDtypeStruct((B, D), h.dtype),
        compiler_params=vmem_limit and pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit),
        interpret=interpret_flag(impl),
        name="fused_moe_mlp",
    )(*prefetch, h, r, combine.astype(jnp.float32).T[:, :, None], *weights)


@functools.partial(jax.jit, static_argnames=(
    "act", "bf", "vmem_limit", "row_tile", "impl"))
def _moe_mlp_live_rows(h, r, combine, live, base, *weights, act, bf,
                       vmem_limit, row_tile, impl):
    """:func:`fused_moe_mlp`'s call with the MXU passes cut to the live
    rows' tiles, jitted with the layer's first expert ``base`` an operand: a
    program's expert layers are traced and lowered ONCE
    (``grouped_matmul._visit_groups``' lesson).  The rows are gathered
    live-first in front of the kernel and put back behind it."""
    B = h.shape[0]
    order, _ = _live_rows(live, B)
    back = jnp.zeros((B,), jnp.int32).at[order].set(
        jnp.arange(B, dtype=jnp.int32), unique_indices=True)
    # (a worked tile's rows that do not decode weigh nothing either)
    combine = jnp.where(live[:, None], combine, 0.0)
    out = _moe_mlp_call(
        h[order], r[order], combine[order], weights,
        lambda e, s: s[1] + e,
        jnp.stack([moe_row_tiles_worked(live, row_tile), base]),
        act=act, bf=bf, vmem_limit=vmem_limit, impl=impl, row_tile=row_tile)
    return out[back]
