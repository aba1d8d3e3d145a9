"""Expert-parallel MoE: top-k gating + all-to-all dispatch/combine.

TPU-native rebuild of the reference's ``deepspeed/moe/sharded_moe.py``
(GShard-style ``top1gating``/``top2gating`` + ``MOELayer`` with ``_AllToAll``
over the expert-parallel process group; SURVEY.md §2.1 "MoE / expert
parallelism").  Differences forced by XLA's static shapes — and they are the
same choices GShard itself made:

- **Fixed expert capacity + masking** instead of dynamic token lists: every
  expert processes exactly ``C = ceil(k·N/E · capacity_factor)`` token slots;
  overflow tokens are dropped (their combine weight is zero, so they pass
  through the residual connection untouched).
- **Dispatch/combine as einsums** with a [N, E, C] one-hot tensor; the
  reference's explicit ``all_to_all_single`` calls become GSPMD-inserted
  all-to-alls when the [E, C, D] expert tensor is sharding-constrained onto
  the ``ep`` mesh axis while tokens are sharded over the data axes.
- Load-balancing aux loss (the reference's ``l_aux``): ``E · Σ_e mean_prob_e
  · frac_tokens_e`` over the top-1 assignment.

Two paths, chosen by ``cfg.moe_drop_tokens``:

- ``True`` (default; the DeepSpeed contract above): GShard capacity with
  drops, scatter or einsum dispatch, the ``ep`` all-to-all.  Training.
- ``False``: **dropless**.  The ``N*k`` assignments are sorted by expert and
  the three projections run as grouped matmuls over ``[N*k, D]`` with
  ``group_sizes [E]`` (``jax.lax.ragged_dot``; a chip's chunk programs,
  over the stacked arrays: ``ops/pallas/grouped_matmul.py``), then un-sorted
  and combined with the router weights: the cost is the routed tokens, not
  ``E x N``, and no capacity exists to overflow.  Serving prefill and the
  unfused decode loop run it; the fused decode path has its own kernel
  (``ops/pallas/decode.py:fused_moe_mlp``).  Not built under ``ep > 1``.

Expert weights are sharded over ``ep`` (expert parallelism) and optionally
``tp`` (intra-expert tensor parallelism) via the model's logical specs; the
expert-data-parallel hybrid (reference ``ep_size`` < world) falls out of the
mesh factorization (ep axis size < dp·fsdp·ep extent).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.comm.mesh import axis_size
from deepspeed_tpu.models.layers import activation_fn, constrain
from deepspeed_tpu.ops.pallas.common import resolve_impl
from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul


def compute_capacity(num_tokens: int, num_experts: int, k: int,
                     capacity_factor: float, min_capacity: int = 4) -> int:
    return max(min_capacity,
               int(math.ceil(k * num_tokens / num_experts * capacity_factor)))


def topk_weights(gates, k: int, normalize: bool = True):
    """The router without capacity: (weight [N, k] fp32, expert_idx [N, k]),
    best first (ties to the lower index, as ``argmax`` breaks them).
    ``normalize`` rescales the k kept probabilities to sum to 1 (Mixtral,
    ``top2gating``); off, they stay the softmax's own (OLMoE's
    ``norm_topk_prob: false``).  k = 1 always keeps the raw probability, so
    the router still gets gradient from the task loss (``top1gating``)."""
    weight, idx = jax.lax.top_k(gates, k)
    if normalize and k > 1:
        weight = weight / jnp.maximum(weight.sum(-1, keepdims=True), 1e-9)
    return weight, idx


def load_balance_loss(gates, top1_idx):
    """The reference's ``l_aux``: E * sum_e mean_prob_e * frac_tokens_e over
    the top-1 assignment."""
    E = gates.shape[-1]
    ce = jnp.mean(jax.nn.one_hot(top1_idx, E, dtype=jnp.float32), axis=0)
    return E * jnp.sum(jnp.mean(gates, axis=0) * ce)


def topk_assignments(gates, k: int, capacity: int, rng=None,
                     use_rts: bool = False, normalize: bool = True):
    """Compact top-k assignment: (expert_idx [N,k], pos [N,k], weight [N,k],
    aux scalar).  Same gating math as :func:`topk_gating` but without the
    [N, E, C] one-hot tensors — feeds the O(N·k·D) scatter/gather dispatch
    (VERDICT r2 weak #9: the one-hot dispatch einsum is O(N²·k/E)).

    ``use_rts`` (reference ``top1gating(use_rts=True)`` Random Token
    Selection): capacity slots are granted in a RANDOM token order instead
    of sequence order, so truncation under overflow doesn't systematically
    drop late-sequence tokens.  A no-op when nothing overflows."""
    if use_rts and rng is not None:
        N = gates.shape[0]
        perm = jax.random.permutation(rng, N)
        inv = jnp.argsort(perm)
        e_idx, pos, w, aux = topk_assignments(gates[perm], k, capacity,
                                              normalize=normalize)
        return e_idx[inv], pos[inv], w[inv], aux
    N, E = gates.shape
    C = capacity
    remaining = gates
    location_base = jnp.zeros((E,), jnp.int32)
    aux = jnp.zeros((), jnp.float32)
    idxs, poss, ws = [], [], []
    kept_gate_sum = jnp.zeros((N,), jnp.float32)
    for slot in range(k):
        idx = jnp.argmax(remaining, axis=-1)                      # [N]
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)        # [N, E]
        if slot == 0:
            aux = load_balance_loss(gates, idx)
        pos_in_e = jnp.cumsum(onehot, axis=0) - onehot + location_base[None]
        pos = jnp.sum(pos_in_e * onehot, axis=-1).astype(jnp.int32)
        keep = (pos < C).astype(jnp.float32)
        gate_val = jnp.sum(gates * onehot, axis=-1)
        idxs.append(idx)
        poss.append(pos)
        ws.append(gate_val * keep)
        kept_gate_sum = kept_gate_sum + gate_val * keep
        location_base = location_base + jnp.sum(onehot, axis=0).astype(jnp.int32)
        remaining = jnp.where(onehot > 0, -jnp.inf, remaining)
    weight = jnp.stack(ws, axis=1)                                # [N, k]
    if normalize and k > 1:
        weight = weight / jnp.maximum(kept_gate_sum, 1e-9)[:, None]
    return (jnp.stack(idxs, axis=1), jnp.stack(poss, axis=1), weight, aux)


def topk_gating(gates, k: int, capacity: int, rng=None,
                use_rts: bool = False, normalize: bool = True):
    """GShard top-k gating with fixed capacity.

    gates: [N, E] softmax router probabilities (fp32).
    Returns (combine [N, E, C], dispatch [N, E, C] bool, aux_loss scalar).
    Reference: ``top1gating``/``top2gating`` in deepspeed/moe/sharded_moe.py;
    ``use_rts`` = the reference's Random Token Selection (see
    :func:`topk_assignments`).
    """
    if use_rts and rng is not None:
        N = gates.shape[0]
        perm = jax.random.permutation(rng, N)
        inv = jnp.argsort(perm)
        combine, dispatch, aux = topk_gating(gates[perm], k, capacity,
                                             normalize=normalize)
        return combine[inv], dispatch[inv], aux
    N, E = gates.shape
    C = capacity
    remaining = gates
    location_base = jnp.zeros((E,), jnp.int32)
    combine = jnp.zeros((N, E, C), jnp.float32)
    kept_gate_sum = jnp.zeros((N,), jnp.float32)
    aux = jnp.zeros((), jnp.float32)

    for slot in range(k):
        idx = jnp.argmax(remaining, axis=-1)                      # [N]
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)        # [N, E]
        if slot == 0:
            aux = load_balance_loss(gates, idx)
        # position of each token within its chosen expert's capacity buffer
        pos_in_e = jnp.cumsum(onehot, axis=0) - onehot + location_base[None]
        pos = jnp.sum(pos_in_e * onehot, axis=-1).astype(jnp.int32)  # [N]
        keep = (pos < C).astype(jnp.float32)
        gate_val = jnp.sum(gates * onehot, axis=-1)               # [N]
        pos_oh = jax.nn.one_hot(jnp.clip(pos, 0, C - 1), C, dtype=jnp.float32)
        combine = combine + ((gate_val * keep)[:, None, None]
                             * onehot[:, :, None] * pos_oh[:, None, :])
        kept_gate_sum = kept_gate_sum + gate_val * keep
        location_base = location_base + jnp.sum(onehot, axis=0).astype(jnp.int32)
        remaining = jnp.where(onehot > 0, -jnp.inf, remaining)

    if normalize and k > 1:
        # normalize combine weights over the kept top-k experts per token
        # (Mixtral/top2gating convention); k=1 keeps the raw gate probability
        # so the router still gets gradient from the task loss (top1gating).
        combine = combine / jnp.maximum(kept_gate_sum, 1e-9)[:, None, None]
    dispatch = combine > 0
    return combine, dispatch, aux


# The smallest row tile the chip's grouped matmul works a group in, and the
# one it takes where its ``lhs`` is an odd number of them long
# (:func:`_moe_grouped`; read from timings on a v5e, libtpu 0.0.34:
# tools/moe_grouped_bench.py --held-share 8 --stacked, PERF.md Findings PR 46).
ROW_TILE = 128


def _moe_grouped(params, xt, gates, cfg, normalize: bool, layer=None,
                 assign=None, mesh=None):
    """Dropless expert block on tokens ``xt`` [N, D] with router
    probabilities ``gates`` [N, E]: sort the N*k (token, expert) assignments
    by expert, run each projection as ONE grouped matmul over the sorted rows
    (``group_sizes`` [E] = tokens per expert; an expert nobody chose is an
    empty group), un-sort, and sum each token's k outputs under its router
    weights in float32.  Returns (y [N, D], aux).

    ``assign`` = (weight [N, k] float32, e_idx [N, k]) replaces the router
    (``gates`` is then unused and aux is 0): the caller routed already, and
    ``e_idx`` counts the ``E`` experts HELD HERE, with ``E`` itself for an
    assignment to an expert another chip holds (a chip's share of the
    experts, ``models/afmoe.py``).  Those rows sort behind every group, lie
    in none, and add nothing to their token.  Where ``jax.lax.ragged_dot``
    runs them, the sorted rows then go to the grouped matmuls as an ODD
    number of 128-row tiles, one tile of pad rows (of no group either)
    behind them where N*k is an even number: the chip's grouped matmul works
    a group in row tiles of the largest of 128, 256, 512 that divides its
    ``lhs`` length, a whole tile for a group of sixteen rows too
    (``ROW_TILE``; where the groups hold 128-256 rows the small tile can
    cost, ROADMAP R1).

    ``layer`` (a traced index) says the expert arrays are the model's STACKED
    [L, E, ...] ones, which is inference (no gradient of the stack is
    wanted): on a chip the three projections are then the Pallas grouped
    matmul (``ops/pallas/grouped_matmul.py``: weight blocks of the whole
    contraction, only the (row tile, group) pairs that hold rows, the stack
    read in place at ``layer``), on the N*k sorted rows as they are.  Off
    the chip, and for a layer's own slice (``layer=None``: training, which
    differentiates through it), they are ``ragged_dot``, the stacked arrays
    whole as L*E groups of which the other layers' are empty: slicing one
    layer out, dynamically or statically, copies its 0.8 GB in front of
    every call (the grouped matmul is a custom call, which no slice fuses
    into): 5.2 against 2.9 ms a layer at OLMoE's widths
    (tools/moe_grouped_bench.py, PERF.md Findings PR 27); an empty group
    costs the matmul next to nothing.  The choice reads the platform
    (``ops/pallas/common.py``, as every kernel's), ``layer`` and ``mesh``
    (GSPMD cannot split a Pallas kernel over ``tp`` or ``sp``: expert arrays
    sharded that way keep ``ragged_dot``), nothing else."""
    N, D = xt.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    if assign is None:
        weight, e_idx = topk_weights(gates, k, normalize)        # [N, k]
        aux = load_balance_loss(gates, e_idx[:, 0])
    else:
        weight, e_idx = assign
        aux = jnp.zeros((), jnp.float32)
    flat = e_idx.reshape(-1)
    order = jnp.argsort(flat)                   # stable: token order inside
    # (an index of E, "held elsewhere", is past ``length`` and not counted)
    sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    gather = order
    # inference over the stacked arrays on a chip: the Pallas grouped matmul
    kernel = layer is not None and resolve_impl(None) != "xla" and (
        mesh is None or mesh.empty
        or axis_size(mesh, "tp") == axis_size(mesh, "sp") == 1)
    if assign is not None:
        inside = flat[order] < E                # the row lies in a group
        # a tile of rows of no group (it steers the chip's ``ragged-dot``
        # alone: the kernel visits the tiles that hold a group's rows)
        if N * k % (2 * ROW_TILE) == 0 and not kernel:
            gather, inside = (jnp.pad(a, (0, ROW_TILE))
                              for a in (order, inside))
    rows = xt[gather // k]                      # [N*k (+ a tile), D]
    if layer is not None and not kernel:
        groups = params["w_up"].shape[0] * E
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((groups,), jnp.int32), sizes, (layer * E,))

    def dot(a, w):
        if kernel:
            return grouped_matmul(a, w, sizes, layer=layer)
        if layer is not None:
            w = w.reshape((groups,) + w.shape[2:])
        return jax.lax.ragged_dot(a, w.astype(a.dtype), sizes)

    act = activation_fn(cfg.activation)
    up = dot(rows, params["w_up"])
    hidden = act(dot(rows, params["w_gate"])) * up if cfg.glu else act(up)
    out = dot(hidden, params["w_down"])
    if assign is not None:      # rows of no group: whatever the matmul left
        out = jnp.where(inside[:, None], out, 0)
    out = out[jnp.argsort(order)]               # un-sort (leaves the pad)
    y = jnp.sum(out.reshape(N, k, D).astype(jnp.float32) * weight[..., None],
                axis=1)
    return y.astype(xt.dtype), aux


def moe_mlp(params, x, cfg, mesh=None, rng=None, layer=None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One MoE feed-forward block on [B, S, D] hidden states.

    ``params``: {"gate_w" [D, E], "w_up" [E, D, F], ("w_gate" [E, D, F]),
    "w_down" [E, F, D]} — the per-layer slice of the model's stacked MoE
    weights.  Returns (output [B, S, D], aux_loss scalar).  With ``layer``
    (dropless path only; inference, where no gradient of the stack is
    wanted) the three expert arrays are the stacked [L, E, ...] ones and
    ``gate_w`` still this layer's: see :func:`_moe_grouped`.

    ``cfg.moe_drop_tokens=False`` (reference ``drop_tokens=False``): the
    sorted-and-grouped path (:func:`_moe_grouped`), which has no capacity and
    drops nothing.  ``cfg.moe_use_rts``: Random Token Selection for capacity
    slots (nothing to select without a capacity); the permutation key is
    ``rng`` (the layer's dropout key when the model has one) or, failing
    that, derived from the batch content so it still varies across batches
    inside one compiled step.
    """
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    N = B * S
    xt = x.reshape(N, D)

    logits = xt.astype(jnp.float32) @ params["gate_w"].astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    normalize = bool(getattr(cfg, "moe_norm_topk_prob", True))
    if not getattr(cfg, "moe_drop_tokens", True):
        if mesh is not None and not getattr(mesh, "empty", False) \
                and dict(mesh.shape).get("ep", 1) > 1:
            raise NotImplementedError(
                "moe_drop_tokens=False under ep > 1: the dropless grouped "
                "path has no expert-parallel exchange yet (ROADMAP R1, the "
                "training half); the capacity path would drop tokens")
        y, aux = _moe_grouped(params, xt, gates, cfg, normalize, layer,
                              mesh=mesh)
        return y.reshape(B, S, D), aux
    if layer is not None:
        raise ValueError("moe_mlp(layer=...) reads stacked expert arrays, "
                         "which only the dropless path does")
    use_rts = bool(getattr(cfg, "moe_use_rts", False))
    if use_rts and rng is None:
        seed = jax.lax.bitcast_convert_type(
            xt.astype(jnp.float32).sum(), jnp.int32)
        rng = jax.random.fold_in(jax.random.PRNGKey(17), seed)
    C = compute_capacity(N, E, k, cfg.moe_capacity_factor,
                         getattr(cfg, "moe_min_capacity", 4))
    use_scatter = getattr(cfg, "moe_dispatch", "scatter") == "scatter"
    if use_scatter:
        # O(N·k·D) scatter dispatch / gather combine (VERDICT r2 weak #9):
        # the [N, E, C] one-hot einsum is O(N²·k/E) because C ~ k·N/E.
        e_idx, pos, weight, aux = topk_assignments(
            gates, k, C, rng, use_rts, normalize)               # [N, k]
        keep = pos < C
        safe_pos = jnp.clip(pos, 0, C - 1)
        contrib = jnp.where(keep.reshape(-1)[:, None],
                            jnp.repeat(xt, k, axis=0), 0)         # [N·k, D]
        expert_in = jnp.zeros((E, C, D), x.dtype).at[
            e_idx.reshape(-1), safe_pos.reshape(-1)].add(contrib)
    else:
        combine, dispatch, aux = topk_gating(gates, k, C, rng, use_rts,
                                             normalize)
        # dispatch: tokens (sharded over data axes) -> expert buffers
        # (sharded over ep) — GSPMD inserts the all-to-all here
        # (reference: _AllToAll).
        expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), xt)
    # comm_quantization.all_to_all (engine sets cfg.moe_q_dispatch): the
    # DISPATCH boundary collective moves blockwise-int8 codes + fp32
    # scales instead of dense activations (comm/collectives_q.py
    # q_reshard — the GSPMD form; its custom VJP transports the
    # cotangent quantized too, so training dispatch stays honest)
    q_disp = (getattr(cfg, "moe_q_dispatch", False) and mesh is not None
              and not getattr(mesh, "empty", False)
              and dict(mesh.shape).get("ep", 1) > 1)
    if q_disp:
        from jax.sharding import PartitionSpec as _P

        from deepspeed_tpu.comm.collectives_q import q_reshard
        from deepspeed_tpu.comm.mesh import data_axes

        qblock = int(getattr(cfg, "comm_quant_block", 256))
        # src pinned to the token side (codes' block dim over the data
        # axes), dst to ep: BOTH boundaries constrained so GSPMD cannot
        # hoist the reshard before the quantize and move dense bytes
        # (q_reshard's contract — the exchange happens between the two
        # code constraints)
        daxes = data_axes(mesh)
        expert_in = q_reshard(expert_in, mesh, _P("ep"),
                              src_spec=_P(None, daxes), block=qblock)
    else:
        expert_in = constrain(expert_in, mesh, "ep", None, None)

    act = activation_fn(cfg.activation)
    up = jnp.einsum("ecd,edf->ecf", expert_in, params["w_up"].astype(x.dtype))
    if cfg.glu:
        gate = jnp.einsum("ecd,edf->ecf", expert_in, params["w_gate"].astype(x.dtype))
        hidden = act(gate) * up
    else:
        hidden = act(up)
    out = jnp.einsum("ecf,efd->ecd", hidden, params["w_down"].astype(x.dtype))
    # the combine return path stays DENSE on purpose: redistributing
    # expert outputs to tokens via replicated int8 codes would move
    # ~size*(1+4/block) bytes per device where the dense ep-sharded
    # exchange moves ~size*itemsize/ep — for ep>=4 the "quantized" form
    # is MORE wire bytes, not fewer (and materializes the full [E,C,D]
    # tensor per device).  The dispatch direction above is where the
    # int8 win is; its custom VJP already quantizes the combine-shaped
    # cotangent on the honest per-destination reshard.
    out = constrain(out, mesh, "ep", None, None)

    # combine: expert buffers -> tokens (the return all-to-all)
    if use_scatter:
        gathered = out[e_idx, safe_pos]                           # [N, k, D]
        y = jnp.sum(gathered * (weight * keep).astype(x.dtype)[..., None],
                    axis=1)
    else:
        y = jnp.einsum("ecd,nec->nd", out, combine.astype(x.dtype))
    return y.reshape(B, S, D), aux
