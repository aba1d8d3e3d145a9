"""Mamba-1's selective scan (models/ssm_moe.py, ``layer_types`` kind
``mamba1``): a decay for every (channel, state dim) pair, so the recurrence

    S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] u_t[c] B_t[n]
    y_t[c]    = sum_n S_t[c, n] C_t[n]

has no matrix form (Mamba-2's chunk is three matrix products because its
decay is one number a head: ``ssm_moe.ssm_chunk_scan``).  Two kernels walk it
elementwise on the vector unit, the state a tile ``[N, W]`` float32 (the
state dim down the sublanes, ``W`` = 128 channels across the lanes), the
exponentials formed INSIDE them from a resident ``A``:

- :func:`selective_scan_chunk` — a prefill chunk of ONE slot: a grid step a
  channel tile, the tile's state carried in registers over the chunk's rows
  in order, ``y`` written eight rows at a time;
- :func:`mamba1_decode_step` — one token a LIVE row, on the stacked per-slot
  state in place (``ops/pallas/decode.py:ssm_decode_step``'s grid and alias;
  that kernel takes a ROW of decays made outside it, this one ``A``).

Neither holds the skip ``D u`` or the gate ``y * silu(z)``: both are
elementwise on ``y`` and XLA fuses them into the out-projection's input.

Everything here is in the PACKED layout the cache keeps
(``ops/pallas/decode.py:ssm_state_pack`` at "``d_inner`` heads of one value,
one group": channel ``c`` is lane ``c % W`` of tile ``c // W``): a state
``[T, N, W]``, ``A`` ``[T, N, W]``, the rows' ``u`` and ``dt`` ``[..., T W]``
flat.  Each op keeps a pure-jnp reference, the CPU path and the parity
target; all of it float32.
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
                                             resolve_impl)
from deepspeed_tpu.ops.pallas.decode import _live_rows, ssm_heads_per_tile

F32 = jnp.float32
ROWS = 8            # rows of a chunk the scan kernel works between stores


def mamba1_tile(d_inner: int) -> int:
    """Channels across the lanes of a state tile: 128, or all of a width
    that is no whole number of lane tiles (the tiny sizes of the tests)."""
    return ssm_heads_per_tile(d_inner, 1, 1)


def mamba1_pack(t, W: int):
    """``t`` [..., d_inner, N] (the published ``A_log`` order: channel, state
    dim) as the kernels read it: [..., d_inner / W, N, W]."""
    *lead, di, N = t.shape
    return jnp.swapaxes(t.reshape(*lead, di // W, W, N), -1, -2)


def mamba1_step_ref(S, u, dt, A, Bm, Cm):
    """One token of the recurrence on packed states ``S`` [..., T, N, W]
    float32: u, dt [..., T W] (dt after its softplus), A [T, N, W] < 0, Bm
    and Cm [..., N].  Returns (y [..., T W], S).  Elementwise float32: the
    same on every backend."""
    T, N, W = A.shape
    tile = lambda t: t.reshape(t.shape[:-1] + (T, 1, W))
    col = lambda t: t[..., None, :, None]
    S = S * jnp.exp(tile(dt) * A) + col(Bm) * tile(dt * u)
    y = (S * col(Cm)).sum(-2)
    return y.reshape(u.shape), S


def selective_scan_ref(S, u, dt, A, Bm, Cm):
    """:func:`mamba1_step_ref` over the ``s`` rows of one sequence in order:
    S [T, N, W]; u, dt [s, T W]; Bm, Cm [s, N].  Returns (S, y [s, T W])."""
    def step(S, xs):
        y, S = mamba1_step_ref(S, *xs[:2], A, *xs[2:])
        return S, y

    return jax.lax.scan(step, S, (u, dt, Bm, Cm))


def mamba1_reference_reason(N: int, W: int) -> Optional[str]:
    """Why the decode kernel cannot take these sizes (None = it can)."""
    if W % 128 or N % 8:
        return (f"a state tile of {N} x {W} is not whole (8, 128) float32 "
                "tiles")
    return None


def selective_scan_reference_reason(s: int, N: int, W: int) -> Optional[str]:
    """Why the chunk kernel cannot take these sizes (None = it can)."""
    if s % ROWS:
        return f"a chunk of {s} rows is not whole blocks of {ROWS}"
    return mamba1_reference_reason(N, W)


# ---------------------------------------------------------------------------
# a prefill chunk of one slot
# ---------------------------------------------------------------------------
def _scan_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, s_ref, y_ref, s_out):
    """One grid step = one channel tile over the whole chunk.  ``b_ref`` and
    ``c_ref`` [s / ROWS, N, ROWS] hold B and C as COLUMNS down the state dim,
    ROWS tokens side by side, so that a token's column is a static lane
    slice; ``u`` and ``dt`` are rows across the tile's lanes."""
    A = a_ref[0]

    def block(g, S):
        at = pl.ds(pl.multiple_of(g * ROWS, ROWS), ROWS)
        u, dt = u_ref[at, :], dt_ref[at, :]                      # [ROWS, W]
        bc, cc = b_ref[g], c_ref[g]                              # [N, ROWS]
        ys = []
        for r in range(ROWS):
            d = dt[r:r + 1, :]
            S = S * jnp.exp(d * A) + bc[:, r:r + 1] * (d * u[r:r + 1, :])
            ys.append(jnp.sum(S * cc[:, r:r + 1], axis=0, keepdims=True))
        y_ref[at, :] = jnp.concatenate(ys, axis=0)
        return S

    s_out[0] = jax.lax.fori_loop(0, u_ref.shape[0] // ROWS, block, s_ref[0])


def selective_scan_chunk(S, u, dt, A, Bm, Cm, *, impl: Optional[str] = None):
    """The selective scan for the ``s`` rows of one slot's chunk
    (:func:`selective_scan_ref`): S [T, N, W] float32 the state before the
    chunk; u, dt [s, T W]; A [T, N, W]; Bm, Cm [s, N], all float32.  Returns
    (the state after the last row, y [s, T W]).  A row with ``dt = 0`` (a
    pad row of the bucket) neither decays the state nor adds to it."""
    impl = resolve_impl(impl)
    T, N, W = A.shape
    s = u.shape[0]
    impl = kernel_or_reference("selective_scan_chunk", impl,
                               selective_scan_reference_reason(s, N, W))
    if impl == "xla":
        return selective_scan_ref(S, u, dt, A, Bm, Cm)
    cols = lambda t: t.reshape(s // ROWS, ROWS, N).swapaxes(1, 2)
    rows = pl.BlockSpec((s, W), lambda j: (0, j))
    whole = pl.BlockSpec((s // ROWS, N, ROWS), lambda j: (0, 0, 0))
    tile = pl.BlockSpec((1, N, W), lambda j: (j, 0, 0))
    y, S = pl.pallas_call(
        _scan_kernel,
        grid=(T,),
        in_specs=[rows, rows, whole, whole, tile, tile],
        out_specs=[rows, tile],
        out_shape=[jax.ShapeDtypeStruct((s, T * W), F32),
                   jax.ShapeDtypeStruct((T, N, W), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret_flag(impl),
        name="selective_scan_chunk",
    )(u, dt, cols(Bm), cols(Cm), A, S)
    return S, y


# ---------------------------------------------------------------------------
# one token a live row
# ---------------------------------------------------------------------------
def _step_kernel(rows_ref, layer_ref, cols_ref, dt_ref, x_ref, a_ref, s_ref,
                 y_ref, s_out, *, tiles):
    """One grid step = one LIVE batch row, all of its ``tiles`` state tiles
    [N, W].  ``cols`` [N, 128] holds B (lane 0) and C (lane 1) as columns
    down the state dim; ``dt`` and ``dt u`` are rows across a tile's lanes;
    ``A`` [tiles, N, W] stays resident across the grid.  A tile comes into
    VMEM once, is decayed by ``exp(dt A)``, takes its outer product, is read
    out against C, and goes back through the alias."""
    del rows_ref, layer_ref       # consumed by the index maps
    cols = cols_ref[0]
    b_col, c_col = cols[:, 0:1], cols[:, 1:2]
    for t in range(tiles):
        S = s_ref[0, t] * jnp.exp(dt_ref[0, t:t + 1, :] * a_ref[t]) \
            + b_col * x_ref[0, t:t + 1, :]
        s_out[0, t] = S
        y_ref[0, t:t + 1, :] = jnp.sum(S * c_col, axis=0, keepdims=True)


def mamba1_decode_step(state, u, dt, A, Bm, Cm, *, layer, live=None,
                       impl: Optional[str] = None):
    """The recurrence of a Mamba-1 layer for one token a row
    (:func:`mamba1_step_ref`), on the stacked per-slot state in place:
    ``state`` [L, B, T, N, W] float32; u, dt [B, T W]; ``A`` [L, T, N, W]
    (every mamba1 layer's, ``layer`` of it read; ``layer`` may be traced: it
    rides the index maps); Bm, Cm [B, N], all float32.  Returns (y [B, T W]
    float32, state, rows visited).

    The grid follows the batch as ``ssm_decode_step``'s does: ``live`` [B]
    bool names the rows that decode (None: all), a parked row costs no grid
    step, its state is neither read nor written, and its ``y`` is its ``dt
    u`` (the output is aliased onto it).  The XLA form updates every row and
    keeps the old state where a row is not live: it visits all ``B``."""
    impl = resolve_impl(impl)
    L, B, T, N, W = state.shape
    impl = kernel_or_reference("mamba1_decode_step", impl,
                               mamba1_reference_reason(N, W))
    if impl == "xla":
        old = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
        y, new = mamba1_step_ref(
            old, u, dt, jax.lax.dynamic_index_in_dim(A, layer, 0, False),
            Bm, Cm)
        if live is not None:
            new = jnp.where(live[:, None, None, None], new, old)
        return (y, jax.lax.dynamic_update_index_in_dim(state, new, layer, 0),
                jnp.asarray(B, jnp.int32))
    rows, n_live = _live_rows(live, B)
    cols = jnp.pad(jnp.stack([Bm, Cm], axis=-1),
                   ((0, 0), (0, 0), (0, 126)))                   # [B, N, 128]
    flat = lambda t: t.reshape(B, T, W)
    vec = pl.BlockSpec((1, T, W), lambda i, rows, lay: (rows[i], 0, 0))
    mat = pl.BlockSpec((1, T, N, W),
                       lambda i, rows, lay: (lay[0] * B + rows[i], 0, 0, 0))
    y, new = pl.pallas_call(
        functools.partial(_step_kernel, tiles=T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_live,),
            in_specs=[pl.BlockSpec((1, N, 128),
                                   lambda i, rows, lay: (rows[i], 0, 0)),
                      vec, vec,
                      pl.BlockSpec((None, T, N, W),
                                   lambda i, rows, lay: (lay[0], 0, 0, 0)),
                      mat],
            out_specs=[vec, mat]),
        out_shape=[jax.ShapeDtypeStruct((B, T, W), F32),
                   jax.ShapeDtypeStruct((L * B, T, N, W), F32)],
        # operands count the two scalar-prefetch arrays: dt u is 4, the
        # state 6
        input_output_aliases={4: 0, 6: 1},
        interpret=interpret_flag(impl),
        name="mamba1_decode_step",
    )(rows, jnp.asarray(layer, jnp.int32).reshape(1), cols, flat(dt),
      flat(dt * u), A, state.reshape(L * B, T, N, W))
    return (y.reshape(B, T * W), new.reshape(state.shape),
            jnp.asarray(n_live, jnp.int32))
