"""ZeRO partitioning as sharding specs.

TPU-native replacement for the reference's ZeRO optimizers
(``deepspeed/runtime/zero/stage_1_and_2.py`` + ``stage3.py`` +
``partition_parameters.py``, SURVEY.md §2.1): there is no runtime
bookkeeping — no flattened buffers, no IPG buckets, no gather/release hooks,
no trace-based prefetcher.  A stage is a *placement policy*:

- stage 0: params, grads, optimizer state replicated; gradients all-reduced.
- stage 1: optimizer state sharded over the ``fsdp`` axis.
- stage 2: + gradients reduce-scattered into the sharded accumulator.
- stage 3: + parameters sharded over ``fsdp`` (GSPMD inserts the all-gathers
  in forward/backward and overlaps them with compute — the compiler replaces
  the reference's prefetch coordinator, SURVEY.md §3.3 note).

``choose_pspec`` picks, per parameter, which dimension to shard: the largest
dimension divisible by the axis size.  Parameters smaller than
``persistence_threshold`` stay replicated — the same role as the reference's
``stage3_param_persistence_threshold`` (keep small params resident) with the
same config key.

A parameter's ``fsdp`` sharding never becomes the sharding of an activation:
every consumer gathers the weight and keeps the batch where it is, the loss
tail included (``CausalLM._loss_tail`` gathers its head, tied table or
``lm_head``, like any layer, whichever dimension was chosen here).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.mesh import axis_size
from deepspeed_tpu.utils.logging import logger


def choose_pspec(shape: Tuple[int, ...], mesh: Mesh, axis: str = "fsdp",
                 min_size: int = 0, existing: Optional[P] = None) -> P:
    """Pick a PartitionSpec sharding one dimension of ``shape`` over ``axis``.

    Chooses the largest dimension divisible by the axis size; dimensions
    already claimed in ``existing`` (e.g. by tensor parallelism) are skipped.
    Returns the existing/replicated spec when nothing divides or the tensor is
    below ``min_size`` elements.
    """
    n = axis_size(mesh, axis)
    base = list(existing) if existing is not None else [None] * len(shape)
    while len(base) < len(shape):
        base.append(None)
    if n <= 1 or int(np.prod(shape or (1,))) < max(min_size, n):
        return P(*base)
    candidates = [(dim_size, i) for i, dim_size in enumerate(shape)
                  if base[i] is None and dim_size % n == 0]
    if not candidates:
        return P(*base)
    _, dim = max(candidates)
    base[dim] = axis
    return P(*base)


def params_pspecs(params: Any, mesh: Mesh, shard: bool, axis: str = "fsdp",
                  persistence_threshold: int = 0, logical_specs: Any = None) -> Any:
    """PartitionSpec tree for a parameter pytree.

    ``shard=False`` (stages 0-2) leaves everything replicated apart from any
    ``logical_specs`` (tensor-parallel annotations).  ``shard=True`` (stage 3)
    additionally shards each large-enough param over ``axis``.
    """
    def spec_for(leaf, logical):
        if not shard:
            return logical if logical is not None else P()
        return choose_pspec(leaf.shape, mesh, axis=axis, min_size=persistence_threshold,
                            existing=logical)

    if logical_specs is None:
        return jax.tree.map(lambda l: spec_for(l, None), params)
    return jax.tree.map(spec_for, params, logical_specs)


def shardings_from_pspecs(pspecs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def opt_state_pspecs(opt_state_shapes: Any, mesh: Mesh, shard: bool, axis: str = "fsdp",
                     persistence_threshold: int = 0) -> Any:
    """PartitionSpec tree for an optax optimizer state.

    Optimizer moments have the same shapes as their params, so the same
    chooser yields consistent placement; scalars (step counts) replicate.
    """
    def spec_for(leaf):
        shape = getattr(leaf, "shape", ())
        if not shard or len(shape) == 0:
            return P()
        return choose_pspec(shape, mesh, axis=axis, min_size=persistence_threshold)

    return jax.tree.map(spec_for, opt_state_shapes)


def describe_partitioning(params: Any, pspecs: Any) -> str:
    """Human-readable partition report (reference: ds_report-style)."""
    lines = []
    flat_p = jax.tree_util.tree_leaves_with_path(params)
    flat_s = jax.tree_util.tree_leaves(pspecs, is_leaf=lambda x: isinstance(x, P))
    sharded = replicated = 0
    for (path, leaf), spec in zip(flat_p, flat_s):
        name = jax.tree_util.keystr(path)
        if any(s is not None for s in spec):
            sharded += 1
            lines.append(f"  {name}: {leaf.shape} -> {spec}")
        else:
            replicated += 1
    lines.insert(0, f"partitioning: {sharded} sharded, {replicated} replicated params")
    return "\n".join(lines)
