"""Grouped matmul over sorted rows, forward only:
``out[rows of group g] = lhs[rows of group g] @ rhs[g]``, the groups laid one
behind the other along ``lhs`` with ``sizes [E]`` rows each (an expert nobody
chose is an empty group; rows behind the last group lie in none and are left
as they come).  It is what ``jax.lax.ragged_dot`` computes, for the prefill
chunk programs' expert blocks (``moe/sharded_moe.py:_moe_grouped``), where
the chip's own grouped matmul follows its TILE COUNT (the largest of 128 /
256 / 512 dividing each of K and N, times the groups) and not its bytes: a
hidden size of 2,688 = 21 lane tiles contracts in tiles of 128 there.

What this kernel does that the custom call does not:

* a weight block is the WHOLE contraction, ``[K, tn]`` with ``tn`` from
  :func:`decode._col_block`'s budget (the rule ``fused_moe_mlp`` streams the
  same stacked arrays by), so an expert's matrix goes through VMEM once in
  long copies whatever K divides by; there is no k loop and one float32
  accumulation over the whole contraction.  The kernel issues those copies
  itself, one block AHEAD OF THE GROUP and not of the grid step: a group
  that lies across a row-tile edge is two visits of one block, and a
  pipeline a step deep starts the next group's 4-6 MB only with the second
  of them, behind a whole matmul (read on the chip: 1.18 ms a call at
  Nemotron's widths where 0.95 was reckoned; PERF.md Findings PR 64);
* it visits (row tile, group) pairs that hold rows and no others (megablox's
  schedule): the grid is ``(N // tn, visits)`` with the second extent read at
  run time, a row tile that spans several small groups is visited once a
  group with the other groups' rows masked, an empty group and the row tiles
  behind the last group cost nothing, and consecutive tiles of one group
  work against the weight block that is there;
* the model's stacked ``[L, E, K, N]`` arrays are read in place: the layer
  (a Python int or a traced scalar) is a scalar-prefetched offset of the
  group numbers, so one traced kernel serves every expert layer of a model
  and nothing is sliced or copied in front of it.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.common import interpret_flag, resolve_impl
from deepspeed_tpu.ops.pallas.decode import _TILE_BYTES, _col_block

# rows a grid step works: the MXU's own height (a taller tile streams the
# other groups' rows of a shared tile through the array for nothing)
ROW_TILE = 128


def row_tile(rows: int) -> int:
    """The row tile of ``rows`` sorted rows: :data:`ROW_TILE` where it
    divides them (every chunk bucket of 128 tokens or more), else their
    largest power-of-two divisor from 16 up (a bucket of 8-64 tokens times k
    choices), else one tile of all of them, else an edge tile cut short."""
    tm = math.gcd(rows, ROW_TILE)
    if tm % 16 == 0:
        return tm
    return rows if rows <= ROW_TILE else ROW_TILE


def visits(sizes, rows: int, tm: int):
    """The schedule of one call: ``(group [V], tile [V], ahead [V], offsets
    [E + 1], count)`` with visit ``v < count`` working row tile ``tile[v]``
    against group ``group[v]``, groups in order and a group's tiles in
    order, so a tile shared by several groups is visited by each in turn and
    stays in VMEM meanwhile.  ``V = tiles + E - 1`` bounds ``count`` (every
    group after the first can add one visit of a tile already counted).
    ``ahead[v]`` is -1 but at a group's first visit, where it names the
    group whose weights to fetch meanwhile: the next that holds rows, and
    behind the last one the first again (its next column block)."""
    E = sizes.shape[0]
    tiles = -(-rows // tm)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    spans = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    last = jnp.cumsum(spans)                    # visits up to and with g
    count = last[-1]
    v = jnp.arange(tiles + E - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(last, v, side="right"), E - 1)
    begin = (last - spans)[group]               # its group's first visit
    tile = jnp.minimum(first[group] + v - begin, tiles - 1)
    following = group[jnp.where(last[group] < count, last[group], 0)]
    ahead = jnp.where(v == begin, following, -1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (group.astype(jnp.int32), tile.astype(jnp.int32),
            ahead.astype(jnp.int32), offsets.astype(jnp.int32),
            count.astype(jnp.int32))


def _grouped_matmul_kernel(base_ref, group_ref, tile_ref, ahead_ref, offs_ref,
                           lhs_ref, rhs_hbm, out_ref, w_buf, sems, slot_ref,
                           *, blocks):
    """One grid step = one visit: the row tile against the group's
    ``[K, tn]`` block, the group's rows of it written and the others kept
    (what an earlier visit of this tile wrote, or nothing yet).  The blocks
    come through ``w_buf``'s two slots by copies of this kernel's own: a
    group's first visit starts the next group's block (``ahead``) into the
    other slot, whose group has had its last visit, and waits for its own."""
    j, v = pl.program_id(0), pl.program_id(1)
    g, nxt = group_ref[v], ahead_ref[v]
    tm, tn = out_ref.shape

    def block(group, col, slot):
        at = pl.multiple_of(col * tn, 128)
        return pltpu.make_async_copy(
            rhs_hbm.at[base_ref[0] + group, :, pl.ds(at, tn)],
            w_buf.at[slot], sems.at[slot])

    @pl.when(nxt >= 0)
    def _a_groups_first_visit():
        opening = (j == 0) & (v == 0)

        @pl.when(opening)
        def _nothing_was_fetched_ahead():
            slot_ref[0] = 0
            block(g, j, 0).start()

        @pl.when(jnp.logical_not(opening))
        def _the_other_slot():
            slot_ref[0] = 1 - slot_ref[0]

        slot = slot_ref[0]
        # behind the last group the first one's next column block, if any
        col = j + (nxt <= g).astype(jnp.int32)

        # (started before this group's block is waited for: the other slot
        # is free since its group's last visit, and the queue never drains)
        @pl.when(col < blocks)
        def _fetch_ahead():
            block(nxt, col, 1 - slot).start()

        block(g, j, slot).wait()

    lhs = lhs_ref[...]
    acc = jax.lax.dot_general(lhs, w_buf[slot_ref[0]].astype(lhs.dtype),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    row = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (row >= offs_ref[g]) & (row < offs_ref[g + 1])
    out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[...])


def grouped_matmul(lhs, rhs, sizes, *, layer=None,
                   impl: Optional[str] = None):
    """``lhs`` [M, K] sorted by group, ``rhs`` [E, K, N] or, with ``layer``
    (an int or a traced scalar), the stacked [L, E, K, N] read at that layer
    in place; ``sizes`` [E] int32.  Returns [M, N] in ``lhs``'s dtype,
    float32 accumulation; rows of no group hold whatever was there (the
    caller masks them, as ``_moe_grouped`` does).  ``impl="xla"`` is the
    reference the tests hold the kernel to, ``ragged_dot`` on the layer's
    own slice."""
    impl = resolve_impl(impl)
    E, K, N = rhs.shape[-3:]
    if impl == "xla":
        w = rhs if layer is None else jax.lax.dynamic_index_in_dim(
            rhs, layer, keepdims=False)
        return jax.lax.ragged_dot(lhs, w.astype(lhs.dtype), sizes)
    # the layer's first group among the stack's [L * E]
    base = jnp.asarray(0 if layer is None else layer * E, jnp.int32)
    return _visit_groups(lhs, rhs.reshape(-1, K, N), sizes, base, impl=impl)


@functools.partial(jax.jit, static_argnames=("impl",))
def _visit_groups(lhs, rhs, sizes, base, *, impl):
    """The schedule and the ``pallas_call`` of :func:`grouped_matmul` over
    ``rhs`` [L * E, K, N], jitted: a program's calls of one shape (an expert
    layer's up and gate projections, every layer of an unrolled stack: 96
    call sites in Solar's eight chunk programs) are traced and lowered
    ONCE, the layer an operand (a kernel a layer cost Ouro's warm set-up
    +18%: PERF.md Findings PR 60)."""
    M, K = lhs.shape
    N = rhs.shape[-1]
    tm = row_tile(M)
    item, wide = lhs.dtype.itemsize, rhs.dtype.itemsize
    # beside the two weight blocks: the row tile twice, the output tile
    # twice and its float32 accumulator, at the widest block the budget has
    widest = min(N, max(128, _TILE_BYTES // (K * wide) // 128 * 128))
    tn = _col_block(K, N, wide,
                    resident=2 * tm * K * item + tm * widest * (2 * item + 4))
    group, tile, ahead, offsets, count = visits(sizes, M, tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(N // tn, count),
        in_specs=[pl.BlockSpec((tm, K), lambda j, v, b, g, t, *_: (t[v], 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tm, tn), lambda j, v, b, g, t, *_: (t[v], j)),
        scratch_shapes=[pltpu.VMEM((2, K, tn), rhs.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_grouped_matmul_kernel, blocks=N // tn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        interpret=interpret_flag(impl),
        name="moe_grouped_matmul",
    )(base.reshape(1), group, tile, ahead, offsets, lhs, rhs)
