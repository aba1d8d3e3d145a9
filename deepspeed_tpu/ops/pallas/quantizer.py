"""Block quantization kernels.

Reference: ``csrc/quantization/{quantize,dequantize,quant_reduce}.cu``
(SURVEY.md §2.2 "Quantizer kernels"): symmetric/asymmetric block int8/int4
quant + dequant.  The Pallas kernel computes the per-block absmax and the
quantized payload in ONE pass over the data (the fused form the CUDA
kernels exist for); dequant is a single scaled cast.  int4 packs two codes
per int8 byte.  The quantized-collective layer
(``runtime/comm/quantized.py``) and the compression QAT path are the
consumers.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.ops.pallas.common import (interpret_flag, resolve_impl,
                                             round_up)

_LANE = 128


# fp32 elements per grid step: 2MB in + 0.5MB out, double-buffered, stays
# well inside the 16MB scoped-VMEM default at every block width
_TILE_ELEMS = 512 * 1024


def _quant_kernel(x_ref, q_ref, scale_ref, *, qmax):
    x = x_ref[:].astype(jnp.float32)                 # [rows, block]
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(absmax == 0, 1.0, absmax / qmax)
    q_ref[:] = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int8)
    scale_ref[:] = scale


def quantize(x, bits: int = 8, block: int = 2048,
             impl: Optional[str] = None) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Symmetric per-block quantization in one fused pass.

    Returns (q int8 [nblocks, block], scale fp32 [nblocks], pad).  For
    ``bits=4`` the codes span [-7, 7] (packing to nibbles is the caller's
    transport concern; see :func:`pack_int4`).
    """
    assert bits in (8, 4), bits
    qmax = 127.0 if bits == 8 else 7.0
    impl = resolve_impl(impl)
    n = x.size
    block = min(block, 1 << 16)
    if impl != "xla":
        # the Pallas kernel tiles on 128 lanes; the XLA path honors any
        # caller granularity (quantized collectives use small blocks)
        block = max(_LANE, block)
    pad = (-n) % block
    nb = (n + pad) // block
    # a kernel grid step takes `rows` whole blocks: the TPU tiling wants
    # the second-to-last block dim a multiple of 8 (a (1, block) tile is
    # refused), so the block count is padded up to a row-tile multiple with
    # zero blocks that are sliced off again below
    rows = max(8, min(256, _TILE_ELEMS // block // 8 * 8, round_up(nb, 8)))
    nbp = nb if impl == "xla" else round_up(nb, rows)
    flat = x.reshape(-1).astype(jnp.float32)
    if nbp * block != n:
        flat = jnp.concatenate(
            [flat, jnp.zeros((nbp * block - n,), jnp.float32)])
    blocks = flat.reshape(nbp, block)
    if impl == "xla":
        absmax = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True)
        scale = jnp.where(absmax == 0, 1.0, absmax / qmax)
        q = jnp.clip(jnp.round(blocks / scale), -qmax, qmax).astype(jnp.int8)
        return q, scale[:, 0], pad
    q, scale = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=(nbp // rows,),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nbp, block), jnp.int8),
                   jax.ShapeDtypeStruct((nbp, 1), jnp.float32)],
        interpret=interpret_flag(impl),
        name="quantize",
    )(blocks)
    return q[:nb], scale[:nb, 0], pad


def dequantize(q, scale, pad: int, shape, dtype=jnp.float32):
    """Inverse of :func:`quantize` (scaled cast — XLA fuses it into the
    consumer, matching the reference's fused dequant epilogues)."""
    out = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    if pad:
        out = out[: out.size - pad]
    return out.reshape(shape).astype(dtype)


def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """int8 codes in [-7, 7] -> packed uint8 (two nibbles/byte)."""
    flat = q.reshape(-1)
    if flat.size % 2:
        flat = jnp.concatenate([flat, jnp.zeros((1,), jnp.int8)])
    pairs = (flat.astype(jnp.int32) + 8).reshape(-1, 2)
    return (pairs[:, 0] | (pairs[:, 1] << 4)).astype(jnp.uint8)


def unpack_int4(packed: jnp.ndarray, n: int) -> jnp.ndarray:
    lo = (packed.astype(jnp.int32) & 0xF) - 8
    hi = ((packed.astype(jnp.int32) >> 4) & 0xF) - 8
    return jnp.stack([lo, hi], axis=-1).reshape(-1)[:n].astype(jnp.int8)
