"""Fused LAMB update kernel.

TPU-native replacement for ``csrc/lamb/fused_lamb_cuda_kernel.cu``
(SURVEY.md §2.2 "Fused LAMB"): LAMB = Adam moments + a per-TENSOR trust
ratio ||p|| / ||update|| scaling the learning rate.  The reference's
two-phase CUDA reduction maps to two Pallas passes:

1. moment update + squared-norm partial reduction per grid block (one read
   of p/g/m/v, writes m/v and the un-scaled update, accumulates norms in a
   scratch accumulator);
2. a tiny scalar combine (XLA) producing the trust ratio, then one fused
   scale-and-apply pass over the update.

The norm reductions ride in the same kernel pass as the moment update, so
p/g/m/v are read exactly once — the part XLA does not fuse on its own is
exactly this cross-pass reuse.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.common import interpret_flag, resolve_impl

_LANE = 128
_BLOCK = 64 * 1024


def _lamb_phase1_kernel(c1_ref, c2_ref, p_ref, g_ref, m_ref, v_ref,
                        u_out, m_out, v_out, norms_out, acc, *, beta1, beta2,
                        eps, weight_decay, rows, block_rows):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    m_new = beta1 * m_ref[:] + (1.0 - beta1) * g
    v_new = beta2 * v_ref[:] + (1.0 - beta2) * g * g
    u = (m_new * c1_ref[0]) / (jnp.sqrt(v_new * c2_ref[0]) + eps)
    if weight_decay != 0.0:
        u = u + weight_decay * p
    u_out[:] = u
    m_out[:] = m_new
    v_out[:] = v_new
    if rows % block_rows:
        # ragged last block: what it read past the end must not reach the
        # norms (the writes above are masked by the pipeline)
        row = i * block_rows + jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
        p = jnp.where(row < rows, p, 0.0)
        u = jnp.where(row < rows, u, 0.0)
    # per-lane partial sums: Mosaic cannot store a scalar to VMEM, so the
    # cross-lane reduction happens outside, on the [2, 128] result
    acc[0:1, :] += jnp.sum(p * p, axis=0, keepdims=True)
    acc[1:2, :] += jnp.sum(u * u, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _finish():
        norms_out[:] = acc[:]


def _scale_kernel(s_ref, p_ref, u_ref, p_out):
    p_out[:] = (p_ref[:].astype(jnp.float32)
                - s_ref[0] * u_ref[:]).astype(p_out.dtype)


def fused_lamb_update(param, grad, m, v, step, *, lr, beta1: float = 0.9,
                      beta2: float = 0.999, eps: float = 1e-6,
                      weight_decay: float = 0.0, impl: Optional[str] = None):
    """Single-tensor fused LAMB step.  Returns (new_param, new_m, new_v)."""
    impl = resolve_impl(impl)
    stepf = step.astype(jnp.float32)
    c1 = 1.0 / (1.0 - beta1 ** stepf)
    c2 = 1.0 / (1.0 - beta2 ** stepf)
    if impl == "xla":
        p = param.astype(jnp.float32)
        g = grad.astype(jnp.float32)
        m_new = beta1 * m + (1 - beta1) * g
        v_new = beta2 * v + (1 - beta2) * g * g
        u = (m_new * c1) / (jnp.sqrt(v_new * c2) + eps)
        if weight_decay != 0.0:
            u = u + weight_decay * p
        w_norm = jnp.linalg.norm(p)
        u_norm = jnp.linalg.norm(u)
        trust = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm, 1.0)
        return (p - lr * trust * u).astype(param.dtype), m_new, v_new

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
    # same blocking as fused_adam_update: whole array when short, else
    # 512-row blocks with a ragged tail
    block_rows = min(rows, _BLOCK // _LANE)
    grid = pl.cdiv(rows, block_rows)
    bspec = pl.BlockSpec((block_rows, _LANE), lambda i, *_: (i, 0))
    nspec = pl.BlockSpec((2, _LANE), lambda i, *_: (0, 0))
    kernel = functools.partial(_lamb_phase1_kernel, beta1=beta1, beta2=beta2,
                               eps=eps, weight_decay=weight_decay, rows=rows,
                               block_rows=block_rows)
    u, m_new, v_new, norms = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(grid,),
            in_specs=[bspec, bspec, bspec, bspec],
            out_specs=[bspec, bspec, bspec, nspec],
            scratch_shapes=[pltpu.VMEM((2, _LANE), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((rows, _LANE), jnp.float32),
                   jax.ShapeDtypeStruct((rows, _LANE), jnp.float32),
                   jax.ShapeDtypeStruct((rows, _LANE), jnp.float32),
                   jax.ShapeDtypeStruct((2, _LANE), jnp.float32)],
        interpret=interpret_flag(impl),
        name="fused_lamb_moments",
    )(jnp.asarray([c1], jnp.float32), jnp.asarray([c2], jnp.float32),
      pf, gf, mf, vf)
    w_norm = jnp.sqrt(jnp.sum(norms[0]))
    u_norm = jnp.sqrt(jnp.sum(norms[1]))
    trust = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm, 1.0)
    scale = jnp.asarray([lr], jnp.float32) * trust
    p_new = pl.pallas_call(
        _scale_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(grid,),
            in_specs=[bspec, bspec], out_specs=bspec),
        out_shape=jax.ShapeDtypeStruct((rows, _LANE), param.dtype),
        interpret=interpret_flag(impl),
        name="fused_lamb_apply",
    )(scale.reshape(1), pf, u)
    unflat = lambda x: x.reshape(-1)[:n].reshape(orig_shape)
    return unflat(p_new), unflat(m_new), unflat(v_new)


class FusedLambState(NamedTuple):
    count: jnp.ndarray
    mu: any
    nu: any


def fused_lamb(learning_rate, *, beta1: float = 0.9, beta2: float = 0.999,
               eps: float = 1e-6, weight_decay: float = 0.0,
               impl: Optional[str] = None) -> optax.GradientTransformation:
    """optax-style transformation over the fused LAMB kernel (the engine's
    optimizer contract; reference: ``FusedLamb``)."""

    def init_fn(params):
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return FusedLambState(count=jnp.zeros((), jnp.int32),
                              mu=jax.tree.map(zeros, params),
                              nu=jax.tree.map(zeros, params))

    def update_fn(grads, state, params=None):
        assert params is not None, "fused_lamb needs params"
        count = state.count + 1
        lr = (learning_rate(count) if callable(learning_rate)
              else learning_rate)

        new_p, new_mu, new_nu = {}, {}, {}
        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = jax.tree_util.tree_leaves(grads)
        flat_m = jax.tree_util.tree_leaves(state.mu)
        flat_v = jax.tree_util.tree_leaves(state.nu)
        outs = [fused_lamb_update(p, g, m, v, count, lr=lr, beta1=beta1,
                                  beta2=beta2, eps=eps,
                                  weight_decay=weight_decay, impl=impl)
                for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
        unflat = lambda i: jax.tree_util.tree_unflatten(treedef,
                                                        [o[i] for o in outs])
        new_params = unflat(0)
        updates = jax.tree.map(lambda new, old: new - old.astype(new.dtype),
                               new_params, params)
        return updates, FusedLambState(count=count, mu=unflat(1), nu=unflat(2))

    return optax.GradientTransformation(init_fn, update_fn)
