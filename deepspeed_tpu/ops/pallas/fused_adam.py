"""Fused Adam/AdamW update kernel.

TPU-native replacement for the reference's ``csrc/adam/multi_tensor_adam.cu``
(+ ``multi_tensor_apply.cuh``, SURVEY.md §2.2 "Fused Adam"): one Pallas kernel
applies the whole Adam update (moment updates + bias correction + weight decay
+ param update) in a single pass over each tensor, reading/writing VMEM tiles.
The multi-tensor-apply trick (batch many small tensors into few launches) is
unnecessary under XLA — the per-leaf kernels fuse into one program — but the
single-pass form still saves HBM round-trips versus naive composition of
elementwise ops, and pins fp32 math for the moments regardless of param dtype.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.common import interpret_flag, resolve_impl

_LANE = 128
_BLOCK = 64 * 1024  # elements per grid step


def _adam_kernel(c1_ref, c2_ref, lr_ref, p_ref, g_ref, m_ref, v_ref,
                 p_out, m_out, v_out, *, beta1, beta2, eps, weight_decay,
                 adam_w_mode):
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    m = m_ref[:]
    v = v_ref[:]
    c1 = c1_ref[0]  # 1/(1-beta1^t)
    c2 = c2_ref[0]  # 1/(1-beta2^t)
    lr = lr_ref[0]  # scalar-prefetch: may be schedule-driven (a traced value)
    if not adam_w_mode and weight_decay != 0.0:
        g = g + weight_decay * p  # L2 mode folds decay into the gradient
    m_new = beta1 * m + (1.0 - beta1) * g
    v_new = beta2 * v + (1.0 - beta2) * g * g
    m_hat = m_new * c1
    v_hat = v_new * c2
    update = m_hat / (jnp.sqrt(v_hat) + eps)
    if adam_w_mode and weight_decay != 0.0:
        update = update + weight_decay * p  # decoupled decay
    p_out[:] = (p - lr * update).astype(p_out.dtype)
    m_out[:] = m_new
    v_out[:] = v_new


def fused_adam_update(param, grad, m, v, step, *, lr: float, beta1: float = 0.9,
                      beta2: float = 0.999, eps: float = 1e-8,
                      weight_decay: float = 0.0, adam_w_mode: bool = True,
                      impl: Optional[str] = None):
    """Single-tensor fused Adam step.  ``m``/``v`` must be fp32; ``step`` is the
    1-based step count (scalar i32).  Returns (new_param, new_m, new_v)."""
    impl = resolve_impl(impl)
    stepf = step.astype(jnp.float32)
    c1 = 1.0 / (1.0 - beta1 ** stepf)
    c2 = 1.0 / (1.0 - beta2 ** stepf)
    if impl == "xla":
        p = param.astype(jnp.float32)
        g = grad.astype(jnp.float32)
        if not adam_w_mode and weight_decay != 0.0:
            g = g + weight_decay * p
        m_new = beta1 * m + (1 - beta1) * g
        v_new = beta2 * v + (1 - beta2) * g * g
        update = (m_new * c1) / (jnp.sqrt(v_new * c2) + eps)
        if adam_w_mode and weight_decay != 0.0:
            update = update + weight_decay * p
        return (p - lr * update).astype(param.dtype), m_new, v_new

    # Mosaic wants >=2-D tiles: view the flat tensor as [rows, 128] and block
    # over rows; the per-step scalars ride in as scalar-prefetch args.
    orig_shape = param.shape
    n = param.size
    pad = (-n) % _LANE
    def flat(x):
        xf = x.reshape(-1)
        if pad:
            xf = jnp.pad(xf, (0, pad))
        return xf.reshape(-1, _LANE)

    pf, gf, mf, vf = flat(param), flat(grad), flat(m), flat(v)
    rows = pf.shape[0]
    # a short tensor is one whole-array block (legal at any row count); a
    # long one runs 512-row blocks and leaves the last one ragged — the
    # update is elementwise, so what a ragged block reads past the end only
    # reaches lanes its masked write drops.  (Halving the block until it
    # divides the rows ends at a (2, 128) tile for the [50257, 768]
    # embedding, which the TPU tiling refuses.)
    block_rows = min(rows, _BLOCK // _LANE)
    grid = pl.cdiv(rows, block_rows)
    kernel = functools.partial(_adam_kernel, beta1=beta1, beta2=beta2, eps=eps,
                               weight_decay=weight_decay, adam_w_mode=adam_w_mode)
    c1a = jnp.asarray([c1], jnp.float32)
    c2a = jnp.asarray([c2], jnp.float32)
    # lr rides in as a scalar-prefetch arg (not a closure constant) so a
    # schedule-driven lr — a traced value inside the jitted train step —
    # doesn't end up baked into the kernel body.
    lra = jnp.asarray([lr], jnp.float32).reshape(1)
    # index_map receives (grid_idx, *scalar_prefetch_refs)
    bspec = pl.BlockSpec((block_rows, _LANE), lambda i, *_: (i, 0))
    p_new, m_new, v_new = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(grid,),
            in_specs=[bspec, bspec, bspec, bspec],
            out_specs=[bspec, bspec, bspec],
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, _LANE), param.dtype),
                   jax.ShapeDtypeStruct((rows, _LANE), jnp.float32),
                   jax.ShapeDtypeStruct((rows, _LANE), jnp.float32)],
        interpret=interpret_flag(impl),
        name="fused_adam",
    )(c1a, c2a, lra, pf, gf, mf, vf)
    unflat = lambda x: x.reshape(-1)[:n].reshape(orig_shape)
    return unflat(p_new), unflat(m_new), unflat(v_new)
