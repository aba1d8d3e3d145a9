"""Fused LayerNorm and RMSNorm Pallas kernels with custom VJP.

TPU-native replacement for the reference's ``csrc/transformer/normalize_kernels.cu``
(training LayerNorm fwd/bwd) and ``csrc/transformer/inference/csrc/layer_norm.cu``
+ ``rms_norm.cu`` (SURVEY.md §2.2): one row-blocked kernel per pass instead of
warp-shuffle reductions — the VPU reduces across the feature (lane) dimension
natively.  The backward recomputes row statistics from x instead of saving
them (one extra VPU reduction over data already in VMEM, in exchange for no
1-D stat tensors in HBM — Mosaic wants ≥2-D tiles, and the memory saving is
the same trade the reference kernels make with their "stochastic mode").
Backward weight-gradients are produced as per-block partials and summed
outside the kernel (grid-parallel, no atomics).

Every entry point takes ``impl`` ∈ {None, "pallas", "interpret", "xla"}; the
jnp path is the numerics reference for parity tests (SURVEY.md §4(b)).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.ops.pallas.common import interpret_flag, pick_block, resolve_impl

# 512-row tiles: fewer grid steps than 256 while the bwd kernel's blocks and
# fp32 temporaries stay inside the scoped-VMEM budget even when fused into a
# large training program (1024 rows compiles standalone but trips the scoped
# limit inside the full step at n=768).  Wider features shrink the rows: the
# Mosaic compile hard-fails past ~512K elements per block there (measured on
# v5e: 256x4096 and 128x8192 die, 128x4096 and 64x8192 compile), so past
# n=2048 the cap is area-based.
_BLOCK_ROWS = 512
_WIDE_BLOCK_ELEMS = 512 * 1024


def _rows_blocks(rows: int, n: int, wide_at: int = 2048):
    """LayerNorm's backward carries more fp32 temporaries than RMSNorm's, so
    it switches to the area-based cap one width step earlier
    (``wide_at=1024``)."""
    cap = (_BLOCK_ROWS if n <= wide_at
           else max(8, (_WIDE_BLOCK_ELEMS // max(n, 1)) // 8 * 8))
    br = pick_block(rows, cap, minimum=8) if rows >= 8 else rows
    return br, rows // br if rows % br == 0 else 1


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, g_ref, b_ref, y_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    xhat = xc * jax.lax.rsqrt(var + eps)
    y = xhat * g_ref[0].astype(jnp.float32) + b_ref[0].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)


def _ln_bwd_kernel(x_ref, g_ref, dy_ref, dx_ref, dg_ref, db_ref, *, eps):
    # dg/db are a single (1, n) block shared across the (sequential) TPU grid:
    # zero on the first step, accumulate in VMEM on every step.
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dg_ref[:] = jnp.zeros_like(dg_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    wdy = dy * g
    c1 = jnp.mean(wdy, axis=-1, keepdims=True)
    c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
    dx_ref[:] = ((wdy - c1 - xhat * c2) * rstd).astype(dx_ref.dtype)
    dg_ref[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[:] += jnp.sum(dy, axis=0, keepdims=True)


def _rms_fwd_kernel(x_ref, g_ref, y_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    y_ref[:] = (x * rstd * g_ref[0].astype(jnp.float32)).astype(y_ref.dtype)


def _rms_bwd_kernel(x_ref, g_ref, dy_ref, dx_ref, dg_ref, *, eps):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dg_ref[:] = jnp.zeros_like(dg_ref)

    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    xhat = x * rstd
    wdy = dy * g
    c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
    dx_ref[:] = ((wdy - xhat * c2) * rstd).astype(dx_ref.dtype)
    dg_ref[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# LayerNorm public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def layer_norm(x, gamma, beta, eps: float = 1e-5, impl: Optional[str] = None):
    """Fused LayerNorm over the last dim.  fp32 statistics regardless of
    input dtype (matching the reference kernel's accumulation behavior)."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return _ln_xla(x, gamma, beta, eps)
    orig = x.shape
    n = orig[-1]
    x2 = x.reshape(-1, n)
    rows = x2.shape[0]
    br, grid = _rows_blocks(rows, n, wide_at=1024)
    y = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=(grid,),
        in_specs=[pl.BlockSpec((br, n), lambda i: (i, 0)),
                  pl.BlockSpec((1, n), lambda i: (0, 0)),
                  pl.BlockSpec((1, n), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        interpret=interpret_flag(impl),
        name="layer_norm_fwd",
    )(x2, gamma.reshape(1, n), beta.reshape(1, n))
    return y.reshape(orig)


def _ln_xla(x, gamma, beta, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(x.dtype)


def _layer_norm_fwd_vjp(x, gamma, beta, eps, impl):
    return layer_norm(x, gamma, beta, eps, impl), (x, gamma)


def _layer_norm_bwd_vjp(eps, impl, res, dy):
    x, gamma = res
    impl = resolve_impl(impl)
    orig = x.shape
    n = orig[-1]
    x2 = x.reshape(-1, n)
    dy2 = dy.reshape(-1, n)
    if impl == "xla":
        xf = x2.astype(jnp.float32)
        dyf = dy2.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        xc = xf - mean
        rstd = jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
        xhat = xc * rstd
        wdy = dyf * gamma.astype(jnp.float32)
        c1 = jnp.mean(wdy, axis=-1, keepdims=True)
        c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
        dx = ((wdy - c1 - xhat * c2) * rstd).astype(x.dtype)
        dg = jnp.sum(dyf * xhat, axis=0)
        db = jnp.sum(dyf, axis=0)
    else:
        rows = x2.shape[0]
        br, grid = _rows_blocks(rows, n, wide_at=1024)
        dx, dg_part, db_part = pl.pallas_call(
            functools.partial(_ln_bwd_kernel, eps=eps),
            grid=(grid,),
            in_specs=[pl.BlockSpec((br, n), lambda i: (i, 0)),
                      pl.BlockSpec((1, n), lambda i: (0, 0)),
                      pl.BlockSpec((br, n), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((br, n), lambda i: (i, 0)),
                       pl.BlockSpec((1, n), lambda i: (0, 0)),
                       pl.BlockSpec((1, n), lambda i: (0, 0))],
            out_shape=[jax.ShapeDtypeStruct((rows, n), x.dtype),
                       jax.ShapeDtypeStruct((1, n), jnp.float32),
                       jax.ShapeDtypeStruct((1, n), jnp.float32)],
            interpret=interpret_flag(impl),
            name="layer_norm_bwd",
        )(x2, gamma.reshape(1, n), dy2)
        dg, db = dg_part[0], db_part[0]
    return dx.reshape(orig), dg.astype(gamma.dtype), db.astype(gamma.dtype)


layer_norm.defvjp(_layer_norm_fwd_vjp, _layer_norm_bwd_vjp)


# ---------------------------------------------------------------------------
# RMSNorm public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rms_norm(x, gamma, eps: float = 1e-6, impl: Optional[str] = None):
    """Fused RMSNorm (reference: inference ``rms_norm.cu``; used by Llama)."""
    impl = resolve_impl(impl)
    if impl == "xla":
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        return (xf * jax.lax.rsqrt(ms + eps) * gamma.astype(jnp.float32)).astype(x.dtype)
    orig = x.shape
    n = orig[-1]
    x2 = x.reshape(-1, n)
    rows = x2.shape[0]
    br, grid = _rows_blocks(rows, n)
    y = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        grid=(grid,),
        in_specs=[pl.BlockSpec((br, n), lambda i: (i, 0)),
                  pl.BlockSpec((1, n), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        interpret=interpret_flag(impl),
        name="rms_norm_fwd",
    )(x2, gamma.reshape(1, n))
    return y.reshape(orig)


def _rms_norm_fwd_vjp(x, gamma, eps, impl):
    return rms_norm(x, gamma, eps, impl), (x, gamma)


def _rms_norm_bwd_vjp(eps, impl, res, dy):
    x, gamma = res
    impl = resolve_impl(impl)
    orig = x.shape
    n = orig[-1]
    x2 = x.reshape(-1, n)
    dy2 = dy.reshape(-1, n)
    if impl == "xla":
        xf = x2.astype(jnp.float32)
        dyf = dy2.astype(jnp.float32)
        rstd = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        xhat = xf * rstd
        wdy = dyf * gamma.astype(jnp.float32)
        c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
        dx = ((wdy - xhat * c2) * rstd).astype(x.dtype)
        dg = jnp.sum(dyf * xhat, axis=0)
    else:
        rows = x2.shape[0]
        br, grid = _rows_blocks(rows, n)
        dx, dg_part = pl.pallas_call(
            functools.partial(_rms_bwd_kernel, eps=eps),
            grid=(grid,),
            in_specs=[pl.BlockSpec((br, n), lambda i: (i, 0)),
                      pl.BlockSpec((1, n), lambda i: (0, 0)),
                      pl.BlockSpec((br, n), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((br, n), lambda i: (i, 0)),
                       pl.BlockSpec((1, n), lambda i: (0, 0))],
            out_shape=[jax.ShapeDtypeStruct((rows, n), x.dtype),
                       jax.ShapeDtypeStruct((1, n), jnp.float32)],
            interpret=interpret_flag(impl),
            name="rms_norm_bwd",
        )(x2, gamma.reshape(1, n), dy2)
        dg = dg_part[0]
    return dx.reshape(orig), dg.astype(gamma.dtype)


rms_norm.defvjp(_rms_norm_fwd_vjp, _rms_norm_bwd_vjp)
