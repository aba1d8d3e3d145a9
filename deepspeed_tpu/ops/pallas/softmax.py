"""Fused scaled-masked softmax + fused bias/activation epilogues.

TPU-native replacements for the reference's ``csrc/transformer/softmax_kernels.cu``
(fused scale+mask+softmax), ``gelu_kernels.cu`` (fused bias+GeLU) and the
inference ``gelu.cu`` bias+act variants (SURVEY.md §2.2).  On TPU most of
these fuse under XLA automatically; the Pallas forms exist for parity,
deterministic fusion, and as building blocks for the transformer layer op.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.ops.pallas.common import interpret_flag, pick_block, resolve_impl

NEG_INF = -1e30


def _softmax_kernel(x_ref, y_ref, *, scale):
    x = x_ref[:].astype(jnp.float32) * scale
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    y_ref[:] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(y_ref.dtype)


def _masked_softmax_kernel(x_ref, mask_ref, y_ref, *, scale):
    x = x_ref[:].astype(jnp.float32) * scale
    x = jnp.where(mask_ref[:] != 0, x, NEG_INF)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    y_ref[:] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(y_ref.dtype)


def scaled_masked_softmax(x, mask=None, scale: float = 1.0, impl: Optional[str] = None):
    """Softmax over the last dim with optional pre-scale and boolean keep-mask
    (1 = attend, 0 = masked out)."""
    impl = resolve_impl(impl)
    if impl == "xla":
        xf = x.astype(jnp.float32) * scale
        if mask is not None:
            xf = jnp.where(mask != 0, xf, NEG_INF)
        return jax.nn.softmax(xf, axis=-1).astype(x.dtype)
    orig = x.shape
    n = orig[-1]
    x2 = x.reshape(-1, n)
    rows = x2.shape[0]
    br = pick_block(rows, 256, minimum=8) if rows >= 8 else rows
    grid = rows // br if rows % br == 0 else 1
    if grid == 1:
        br = rows
    if mask is None:
        y = pl.pallas_call(
            functools.partial(_softmax_kernel, scale=scale),
            grid=(grid,),
            in_specs=[pl.BlockSpec((br, n), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((br, n), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
            interpret=interpret_flag(impl),
            name="scaled_softmax",
        )(x2)
    else:
        mask2 = jnp.broadcast_to(mask, orig).reshape(-1, n).astype(jnp.int32)
        y = pl.pallas_call(
            functools.partial(_masked_softmax_kernel, scale=scale),
            grid=(grid,),
            in_specs=[pl.BlockSpec((br, n), lambda i: (i, 0)),
                      pl.BlockSpec((br, n), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((br, n), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
            interpret=interpret_flag(impl),
            name="scaled_masked_softmax",
        )(x2, mask2)
    return y.reshape(orig)


def _bias_act_kernel(x_ref, b_ref, y_ref, *, act):
    x = x_ref[:].astype(jnp.float32) + b_ref[0].astype(jnp.float32)
    if act == "gelu":
        y = jax.nn.gelu(x, approximate=True)
    elif act == "relu":
        y = jnp.maximum(x, 0.0)
    elif act == "silu":
        y = x * jax.nn.sigmoid(x)
    else:
        y = x
    y_ref[:] = y.astype(y_ref.dtype)


def bias_act(x, bias, act: str = "gelu", impl: Optional[str] = None):
    """Fused bias-add + activation (reference: fused_bias_gelu/relu/silu)."""
    impl = resolve_impl(impl)
    if impl == "xla":
        xf = x.astype(jnp.float32) + bias.astype(jnp.float32)
        if act == "gelu":
            y = jax.nn.gelu(xf, approximate=True)
        elif act == "relu":
            y = jnp.maximum(xf, 0.0)
        elif act == "silu":
            y = xf * jax.nn.sigmoid(xf)
        else:
            y = xf
        return y.astype(x.dtype)
    orig = x.shape
    n = orig[-1]
    x2 = x.reshape(-1, n)
    rows = x2.shape[0]
    br = pick_block(rows, 256, minimum=8) if rows >= 8 else rows
    grid = rows // br if rows % br == 0 else 1
    if grid == 1:
        br = rows
    y = pl.pallas_call(
        functools.partial(_bias_act_kernel, act=act),
        grid=(grid,),
        in_specs=[pl.BlockSpec((br, n), lambda i: (i, 0)),
                  pl.BlockSpec((1, n), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        interpret=interpret_flag(impl),
        name="bias_act",
    )(x2, bias.reshape(1, n))
    return y.reshape(orig)
