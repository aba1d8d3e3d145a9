"""Fused int8-state Adam update kernel (+ stochastic rounding).

The Adam8bit optimizer (ops/adam/adam8bit.py) stores m/v as int8 blocks
with per-block scales.  Composed as jnp ops, the dequant -> moment update
-> requant -> stochastic-round chain compiles to a slow many-pass program
(measured ~1000x below TPU capability at 1.3B params); this kernel does the
whole update in ONE VMEM pass per tile — the exact role the reference's
fused ``multi_tensor_adam.cu`` + quantization kernels play (SURVEY.md §2.2
rows "Fused Adam", "Quantizer kernels").

Per [rows, block] tile: dequant m/v (sqrt-space v), Adam moment update,
bias-corrected AdamW direction, per-row absmax requant, and — for bf16
params — stochastic rounding via the on-core PRNG (``pltpu.prng_seed`` /
``prng_random_bits``): add uniform bits below the truncated mantissa,
truncate, store bf16.  fp32 math throughout; int8/bf16 I/O only.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.common import interpret_flag, resolve_impl

ROW_MULT = 32  # int8 sublane tile; nb is padded to a multiple of this
XLA_CHUNK_ELEMS = 1 << 25  # fp32-temporary bound per chunk in the xla fallback


def _kernel(c1_ref, c2_ref, lr_ref, seed_ref, p_ref, g_ref, mq_ref, ms_ref,
            vq_ref, vs_ref, p_out, mq_out, ms_out, vq_out, vs_out, *,
            b1, b2, eps, wd, sr):
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    m = mq_ref[:].astype(jnp.float32) * ms_ref[:]
    rv = vq_ref[:].astype(jnp.float32) * vs_ref[:]
    v = rv * rv                               # sqrt-space storage
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    update = (m * c1_ref[0]) / (jnp.sqrt(v * c2_ref[0]) + eps) + wd * p
    new = p - lr_ref[0] * update

    def requant(x, q_out, s_out):
        absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        scale = jnp.where(absmax == 0, 1.0, absmax / 127.0)
        q_out[:] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        s_out[:] = scale

    requant(m, mq_out, ms_out)
    requant(jnp.sqrt(v), vq_out, vs_out)
    if sr:
        pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
        bits = pltpu.prng_random_bits(new.shape).astype(jnp.int32)
        u = jax.lax.bitcast_convert_type(new, jnp.int32)
        u = (u + (bits & 0xFFFF)) & jnp.int32(~0xFFFF)
        new = jax.lax.bitcast_convert_type(u, jnp.float32)
    p_out[:] = new.astype(p_out.dtype)


def fused_adam8bit_update(p2d, g2d, mq, ms, vq, vs, c1, c2, lr, seed, *,
                          b1: float, b2: float, eps: float, wd: float,
                          sr: bool, impl: Optional[str] = None):
    """One fused step over a [nb, block] view of a leaf.

    ``p2d``/``g2d``: [nb, block] param/grad views; ``mq``/``vq``: int8
    [nb, block]; ``ms``/``vs``: fp32 [nb, 1]; ``c1``/``c2``: bias-correction
    factors 1/(1-beta^t); ``seed``: i32 scalar for the SR stream.  Returns
    (new_p [nb, block] in p2d.dtype, mq', ms', vq', vs').
    """
    nb, block = p2d.shape
    assert nb % ROW_MULT == 0, (nb, ROW_MULT)
    impl = resolve_impl(impl)
    if impl == "xla":
        def xla_step(p_c, g_c, mq_c, ms_c, vq_c, vs_c, seed_c):
            m = mq_c.astype(jnp.float32) * ms_c
            v = jnp.square(vq_c.astype(jnp.float32) * vs_c)
            g = g_c.astype(jnp.float32)
            m = b1 * m + (1.0 - b1) * g
            v = b2 * v + (1.0 - b2) * g * g
            p = p_c.astype(jnp.float32)
            new = p - lr * ((m * c1) / (jnp.sqrt(v * c2) + eps) + wd * p)

            def requant(x):  # shared quantizer: same semantics as the kernel
                from deepspeed_tpu.ops.pallas.quantizer import quantize

                q, scale, _pad = quantize(x, bits=8, block=block, impl="xla")
                return q, scale[:, None]

            mq2, ms2 = requant(m)
            vq2, vs2 = requant(jnp.sqrt(v))
            if sr and p_c.dtype == jnp.bfloat16:
                from deepspeed_tpu.ops.adam.adam8bit import stochastic_round_bf16

                key = jax.random.fold_in(jax.random.PRNGKey(0), seed_c)
                new_p = stochastic_round_bf16(new, key)
            else:
                new_p = new.astype(p_c.dtype)
            return new_p, mq2, ms2, vq2, vs2

        # Bound fp32 temporaries to ~XLA_CHUNK_ELEMS per chunk: this debug
        # path must not reintroduce whole-leaf fp32 copies (a >1B model's
        # stacked-layers leaf is ~278M elements; ~6 fp32 temporaries of
        # that is ~7GB — an instant OOM on a 16GB chip).
        chunk_rows = max(ROW_MULT, XLA_CHUNK_ELEMS // block)
        if nb <= chunk_rows:
            return xla_step(p2d, g2d, mq, ms, vq, vs, seed)
        S = -(-nb // chunk_rows)
        pad_rows = S * chunk_rows - nb

        def padr(x):
            return jnp.pad(x, ((0, pad_rows), (0, 0))).reshape(
                S, chunk_rows, x.shape[1])

        xs = (padr(p2d), padr(g2d), padr(mq), padr(ms), padr(vq), padr(vs),
              seed + jnp.arange(S, dtype=jnp.int32) * jnp.int32(7919))
        outs = jax.lax.map(lambda t: xla_step(*t), xs)
        return tuple(o.reshape(S * chunk_rows, -1)[:nb] for o in outs)

    rows = min(256, nb)
    while nb % rows:
        rows //= 2
    grid = nb // rows
    tile = pl.BlockSpec((rows, block), lambda i, *_: (i, 0))
    stile = pl.BlockSpec((rows, 1), lambda i, *_: (i, 0))
    kernel = functools.partial(_kernel, b1=b1, b2=b2, eps=eps, wd=wd,
                               sr=bool(sr and p2d.dtype == jnp.bfloat16))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(grid,),
            in_specs=[tile, tile, tile, stile, tile, stile],
            out_specs=[tile, tile, stile, tile, stile],
        ),
        out_shape=[jax.ShapeDtypeStruct((nb, block), p2d.dtype),
                   jax.ShapeDtypeStruct((nb, block), jnp.int8),
                   jax.ShapeDtypeStruct((nb, 1), jnp.float32),
                   jax.ShapeDtypeStruct((nb, block), jnp.int8),
                   jax.ShapeDtypeStruct((nb, 1), jnp.float32)],
        interpret=interpret_flag(impl),
        name="fused_adam8bit",
    )(jnp.asarray([c1], jnp.float32), jnp.asarray([c2], jnp.float32),
      jnp.asarray([lr], jnp.float32), jnp.asarray([seed], jnp.int32),
      p2d, g2d, mq, ms, vq, vs)
