"""Rotary position embedding (RoPE).

TPU-native replacement for the reference's
``csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu`` (SURVEY.md §2.2,
named explicitly in the north star).  The rotation is pure VPU elementwise
work, so the Pallas kernel's value is fusing the sin/cos generation with the
rotation in VMEM; the jnp path is the parity reference and lets XLA fuse into
neighboring matmuls.

Convention: half-rotation (GPT-NeoX / Llama style) — the head dim is split in
halves [x1, x2] -> [x1*cos - x2*sin, x2*cos + x1*sin].
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.ops.pallas.common import interpret_flag, resolve_impl


def rope_angles(positions, head_dim: int, theta: float = 10000.0):
    """[S] int positions -> ([S, D/2] cos, [S, D/2] sin), fp32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _rope_ref(x, cos, sin):
    # x: [..., S, D]; cos/sin: [S, D/2]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    shape = (1,) * (x.ndim - 2) + cos.shape
    c = cos.reshape(shape).astype(jnp.float32)
    s = sin.reshape(shape).astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([x1f * c - x2f * s, x2f * c + x1f * s], axis=-1).astype(x.dtype)


def _rope_kernel(x_ref, cos_ref, sin_ref, y_ref):
    x = x_ref[0].astype(jnp.float32)  # [S, D]
    half = x.shape[-1] // 2
    c = cos_ref[:].astype(jnp.float32)
    s = sin_ref[:].astype(jnp.float32)
    x1, x2 = x[:, :half], x[:, half:]
    y = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def apply_rotary_pos_emb(x, cos, sin, impl: Optional[str] = None):
    """Apply RoPE.  ``x``: [..., S, D] (any leading batch/head dims); ``cos``/
    ``sin``: [S, D/2] from :func:`rope_angles`."""
    return _rope_fwd(x, cos, sin, impl)


def _rope_fwd(x, cos, sin, impl):
    impl = resolve_impl(impl)
    if impl == "xla":
        return _rope_ref(x, cos, sin)
    orig = x.shape
    S, D = orig[-2], orig[-1]
    lead = 1
    for d in orig[:-2]:
        lead *= d
    x3 = x.reshape(lead, S, D)
    y = pl.pallas_call(
        _rope_kernel,
        grid=(lead,),
        in_specs=[pl.BlockSpec((1, S, D), lambda i: (i, 0, 0)),
                  pl.BlockSpec((S, D // 2), lambda i: (0, 0)),
                  pl.BlockSpec((S, D // 2), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, S, D), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((lead, S, D), x.dtype),
        interpret=interpret_flag(impl),
        name="rope",
    )(x3, cos, sin)
    return y.reshape(orig)


def _rope_fwd_vjp(x, cos, sin, impl):
    return _rope_fwd(x, cos, sin, impl), (cos, sin)


def _rope_bwd_vjp(impl, res, dy):
    cos, sin = res
    # Rotation is orthogonal: the VJP is rotation by -angle.
    return _rope_fwd(dy, cos, -sin, impl), None, None


apply_rotary_pos_emb.defvjp(_rope_fwd_vjp, _rope_bwd_vjp)
