"""Model building blocks wired to the Pallas kernel library.

These are the TPU-native counterparts of the reference's fused module zoo
(``deepspeed/ops/transformer/`` wrappers over ``csrc/transformer/*.cu``,
SURVEY.md §2.2): norms and RoPE dispatch to the Pallas kernels in
``deepspeed_tpu/ops/pallas`` (with jnp/XLA fallback off-TPU), attention runs
the blockwise flash kernel under ``shard_map`` when the mesh layout allows it,
and everything else is left to XLA fusion on purpose (the MXU gets the
matmuls; elementwise epilogues fuse).

Sharding model (GSPMD): weights carry logical tensor-parallel specs
(Megatron-style column/row split over the ``tp`` axis — the analog of the
reference's AutoTP LinearLayer/LinearAllreduce classification,
``deepspeed/module_inject/auto_tp.py``); activations get
``with_sharding_constraint`` pins at layer boundaries so XLA inserts the
all-reduce after row-parallel matmuls exactly where the reference called
``dist.all_reduce``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.mesh import axis_size, data_axes
from deepspeed_tpu.ops.pallas import (apply_rotary_pos_emb, flash_attention,
                                      layer_norm, mha_reference, rms_norm,
                                      rope_angles)
from deepspeed_tpu.ops.pallas.common import kernel_or_reference, resolve_impl


def _mesh_spec(x, mesh: Optional[Mesh], *spec) -> Optional[P]:
    """The part of the logical ``spec`` that ``mesh`` can apply to ``x``, or
    None when there is nothing to shard over.

    Axis names absent from ``mesh`` are dropped, so the built-in models'
    (dp/fsdp/tp/sp/ep) layouts degrade gracefully on custom meshes.  Axes
    that are MANUAL in the current trace context (the model running inside
    a shard_map region, e.g. the ZeRO++ or 1-bit paths) are dropped too —
    the data is already device-local there.  An entry whose dim does not
    divide across its axes is dropped whole (e.g. batch-1 serving on a
    multi-chip data mesh).
    """
    if mesh is None or mesh.empty:
        return None
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    names = set(mesh.axis_names) - manual
    if not names:
        return None  # fully-manual region

    def keep(entry, dim_size):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
        else:
            kept = (entry,) if entry in names else ()
        total = 1
        for a in kept:
            total *= axis_size(mesh, a)
        if not kept or dim_size % total != 0:
            return None
        return kept if len(kept) > 1 else kept[0]

    return P(*(keep(e, d) for e, d in zip(spec, x.shape)))


def constrain(x, mesh: Optional[Mesh], *spec):
    """Pin activation sharding to what :func:`_mesh_spec` keeps of ``spec``;
    no-op without a mesh or inside a fully-manual region
    (with_sharding_constraint rejects manual axes)."""
    pspec = _mesh_spec(x, mesh, *spec)
    if pspec is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, pspec))


def norm(x, params, kind: str, eps: float, mesh: Optional[Mesh] = None):
    """Dispatch to the fused Pallas norm kernels (csrc layer_norm/rms_norm).

    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), so under a multi-device ``mesh`` the kernel runs per shard
    of the activation layout (batch over the data axes, sequence over
    ``sp``): rows are independent, and the scale/bias arrive whole."""
    if kind == "rmsnorm":
        fn = functools.partial(rms_norm, eps=eps)
        weights = (params["scale"],)
    else:
        fn = functools.partial(layer_norm, eps=eps)
        weights = (params["scale"], params["bias"])
    if mesh is None or mesh.empty or mesh.size == 1 \
            or resolve_impl(None) == "xla":
        return fn(x, *weights)
    rows = (("dp", "fsdp", "ep"), "sp")[:x.ndim - 1]
    spec = _mesh_spec(x, mesh, *rows, *((None,) * (x.ndim - len(rows))))
    if spec is None:
        return fn(x, *weights)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) + (P(),) * len(weights),
                         out_specs=spec, check_vma=False)(x, *weights)


def model_norm(cfg, x, params, mesh: Optional[Mesh] = None):
    """A model's own norm of ``x``: :func:`norm` with the configuration's
    kind and eps, the stored gain taken as ``1 + scale`` in float32 where
    ``cfg.norm_add_unit_offset`` says so (evabyte)."""
    if cfg.norm_add_unit_offset:
        params = {**params,
                  "scale": 1.0 + params["scale"].astype(jnp.float32)}
    return norm(x, params, cfg.norm, cfg.norm_eps, mesh)


def qk_norm(q, k, q_scale, k_scale, eps: float,
            mesh: Optional[Mesh] = None):
    """QK-norm (olmoe): RMSNorm over the WHOLE q and k projections
    (``[..., H*Dh]`` / ``[..., Hkv*Dh]``, all heads at once) in float32,
    before the head split and RoPE.  The mean of squares spans the columns
    ``tp`` splits, so under ``tp > 1`` it would be a cross-shard reduction;
    that is not built, and a per-shard norm is another model."""
    if mesh is not None and not mesh.empty and axis_size(mesh, "tp") > 1:
        raise NotImplementedError(
            "qk_norm with tp > 1: the norm spans the tp-split projection "
            "columns and its tp reduction is not built")

    def one(t, scale):
        t32 = t.astype(jnp.float32)
        var = jnp.mean(t32 * t32, axis=-1, keepdims=True)
        return (t32 * jax.lax.rsqrt(var + eps)
                * scale.astype(jnp.float32)).astype(t.dtype)

    return one(q, q_scale), one(k, k_scale)


def activation_fn(name: str):
    return {"silu": jax.nn.silu,
            "gelu": functools.partial(jax.nn.gelu, approximate=True),
            "gelu_exact": functools.partial(jax.nn.gelu, approximate=False),
            "relu": jax.nn.relu,
            "relu2": lambda x: jnp.square(jnp.maximum(x, 0.0))}[name]


def _repeat_kv(k, n_rep: int):
    """GQA: expand [B, Hkv, S, D] -> [B, Hkv*n_rep, S, D]."""
    if n_rep == 1:
        return k
    b, h, s, d = k.shape
    return jnp.broadcast_to(k[:, :, None], (b, h, n_rep, s, d)).reshape(b, h * n_rep, s, d)


def alibi_slopes(num_heads: int) -> jnp.ndarray:
    """Per-head ALiBi slopes (Press et al.): geometric 2^(-8i/H) for
    power-of-two H, with the standard interpolation for other head counts
    (reference: ``(R) csrc/transformer/inference/csrc/softmax.cu`` alibi
    path / HF ``build_alibi_tensor``)."""
    import math

    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    n = 2 ** math.floor(math.log2(num_heads))
    slopes = pow2_slopes(n)
    if n < num_heads:
        extra = pow2_slopes(2 * n)
        slopes += extra[0::2][: num_heads - n]
    return jnp.asarray(slopes, jnp.float32)


def alibi_bias(num_heads: int, q_pos, k_pos) -> jnp.ndarray:
    """[H, |q|, |k|] additive attention bias: slope_h * (k - q) (non-positive
    under the causal mask)."""
    slopes = alibi_slopes(num_heads)
    rel = k_pos[None, :].astype(jnp.float32) - q_pos[:, None].astype(jnp.float32)
    return slopes[:, None, None] * rel[None]


def flash_reference_reason(b: int, h: int, s: int, nb: int = 1, ntp: int = 1,
                           nsp: int = 1) -> Optional[str]:
    """Why training attention on [b, h, s, Dh] cannot take the flash kernel
    under a mesh with ``nb`` data shards, ``ntp`` tensor shards and ``nsp``
    sequence shards (None = it can)."""
    if s % 128:
        return f"sequence length {s} is not a multiple of the 128-lane tile"
    if b % nb or h % ntp:
        return (f"batch {b} / heads {h} do not divide over {nb} data / "
                f"{ntp} tp shards")
    if nsp > 1:
        return f"sequence length {s} does not divide over {nsp} sp shards"
    return None


def attention_core(q, k, v, mesh: Optional[Mesh], causal: bool = True,
                   impl: Optional[str] = None, sp_mode: str = "auto",
                   alibi: bool = False, ring_q: bool = False,
                   ring_q_block: int = 256):
    """Multi-head attention on [B, H, S, Dh] tensors.

    Dispatch (SURVEY.md §5.7):
    - sp > 1 and heads divisible → **Ulysses**: all-to-all seq↔head reshard
      around full-sequence attention (deepspeed_tpu/sequence/layer.py).
    - sp > 1 otherwise (or ``sp_mode="ring"``) → **ring attention**: KV
      rotation via ppermute, O(S/P) memory.
    - sp == 1 on TPU with a compatible layout → flash kernel under shard_map
      (batch over data axes, heads over ``tp``), or called directly when
      there is no mesh.
    - what :func:`flash_reference_reason` names → jnp reference under plain
      GSPMD, logged.
    """
    impl = resolve_impl(impl)
    b, h, s, d = q.shape

    def ref_bias():
        if not alibi:
            return None
        pos = jnp.arange(s)
        return alibi_bias(h, pos, pos)[None]

    if mesh is None or mesh.empty:
        impl = kernel_or_reference("flash_attention", impl,
                                   flash_reference_reason(b, h, s))
        if impl == "xla":
            return mha_reference(q, k, v, causal=causal, bias=ref_bias())
        return flash_attention(q, k, v, causal=causal, alibi=alibi, impl=impl)
    batch_ax = data_axes(mesh)
    nb = 1
    for a in batch_ax:
        nb *= axis_size(mesh, a)
    ntp = axis_size(mesh, "tp")
    nsp = axis_size(mesh, "sp")
    divisible = b % nb == 0 and h % ntp == 0
    if nsp > 1 and divisible and s % nsp == 0:
        if alibi:
            raise NotImplementedError(
                "alibi + sequence parallelism is not supported (the ring/"
                "ulysses shards would need position-offset bias plumbing)")
        from deepspeed_tpu.sequence.layer import ring_attention, ulysses_attention
        local_heads = h // ntp
        if sp_mode == "ring" or local_heads % nsp != 0:
            # ring_q: comm_quantization.sequence_ring — the KV rotation
            # carries int8 codes (quantized once) instead of dense chunks
            return ring_attention(q, k, v, mesh, causal=causal,
                                  quantized=ring_q,
                                  quant_block=ring_q_block)
        inner = None
        if impl == "pallas" and s % 128 == 0:
            inner = functools.partial(flash_attention, causal=causal)
        return ulysses_attention(q, k, v, mesh, attn_fn=inner, causal=causal)
    if alibi and ntp > 1:
        raise NotImplementedError(
            "alibi + tensor parallelism needs per-shard head-slope offsets; "
            "serve BLOOM-class models with tp=1 for now")
    impl = kernel_or_reference("flash_attention", impl,
                               flash_reference_reason(b, h, s, nb, ntp, nsp))
    if impl == "xla":
        return mha_reference(q, k, v, causal=causal, bias=ref_bias())
    spec = P(batch_ax, "tp", None, None)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    def _sharded(qq, kk, vv):
        return flash_attention(qq, kk, vv, causal=causal, alibi=alibi,
                               impl=impl)

    return _sharded(q, k, v)


def rope_cache(seq_len: int, head_dim: int, theta: float):
    return rope_angles(jnp.arange(seq_len), head_dim, theta=theta)


def apply_partial_rope(x, cos, sin):
    """Rotate the first ``2*cos.shape[-1]`` head dims, pass the rest through
    (gpt-neox ``rotary_pct``).  The rotated span is defined by the cos/sin
    width alone — build them with :func:`rope_cache` over ``rope_dim(cfg)``."""
    rot = 2 * cos.shape[-1]
    if rot == x.shape[-1]:
        return apply_rotary_pos_emb(x, cos, sin)
    rotated = apply_rotary_pos_emb(x[..., :rot], cos, sin)
    return jnp.concatenate([rotated, x[..., rot:]], axis=-1)


def rope_dim(cfg) -> int:
    """Rotated head dims (even; head_dim * rotary_pct, neox convention)."""
    d = int(cfg.head_dim * cfg.rotary_pct)
    return max(2, d - (d % 2))
