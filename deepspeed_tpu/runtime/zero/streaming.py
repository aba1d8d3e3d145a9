"""Host->device parameter streaming: double-buffered prefetch, persistent
staging slots, pinned-host routing, int8 relay.

An 8B host-tiered model moves ~48GB of parameters and gradients between
host and device per micro-batch, so that transfer — the RELAY — can be the
wall rather than compute (ROADMAP S2; ZeRO-Infinity arXiv:2104.07857 /
ZeRO-Offload arXiv:2101.06840 attack exactly this regime; its rate on
today's machine is not measured).  This module owns the layer
transport for ``runtime/zero/stream_grad.py`` and shrinks/hides it three
ways:

- **double-buffered prefetch** — :meth:`ParamStreamer.prefetch` dispatches
  layer ``i+1``'s H2D while layer ``i`` computes (the PR 6 barrier-tied
  bucket idiom applied to the memory tier; here the "barrier" is dispatch
  order — ``device_put`` transfers run outside program execution and
  overlap device compute).  ``take(i)`` finding its layer already in
  flight is a prefetch HIT (``ds_offload_prefetch_hits_total``); the
  transport order never changes the math, so prefetch on/off is
  loss-IDENTICAL (tier-1 pinned).
- **persistent staging slots** — on one-memory-space backends each fetched
  layer is re-staged into one of ``staging_slots`` pre-allocated device
  buffers via a donated compiled copy, so steady state holds exactly N
  slot buffers instead of churning a fresh allocation per layer per
  micro-batch.  On pinned-host backends the put targets ``pinned_host``
  directly (the staging tier device DMA reads from) and the layer program
  opens with the in-jit device move — ``transformer.to_dev``'s idiom.
- **int8 relay** — with ``int8=True`` each layer ships as blockwise int8 +
  fp32 block scales (``comm/quant.py``) and :meth:`materialize` fuses the
  dequant into the consuming layer program: ~2x fewer relay bytes than
  bf16, ~4x fewer than fp32.  Payloads are replicated (the sharded int8
  relay belongs to the quantized-collective layer, ROADMAP item 2, which
  reuses the same codec).

Telemetry (docs/OBSERVABILITY.md "Offload streaming"): relay bytes by
direction (``ds_offload_relay_bytes_total{dir=}``), per-take residual
stall (``ds_offload_relay_seconds`` — how long the consumer actually
waited on the relay; ~0 when prefetch fully hides it), prefetch
hits/misses.  All one-branch no-ops while the registry is disabled; the
stall measurement only runs when telemetry is on (it synchronizes on the
fetched layer, which the consumer was about to do anyway).
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.comm.quant import (DEFAULT_BLOCK, dequantize_tree,
                                      quantize_tree_np)


def _tree_nbytes(tree) -> int:
    return sum(int(np.prod(np.shape(a))) * np.dtype(
        getattr(a, "dtype", np.float32)).itemsize
        for a in jax.tree.leaves(tree))


class RelayMeter:
    """The shared ``ds_offload_*`` instruments (one registration per
    process registry; both the streamer and the grad D2H side feed it)."""

    def __init__(self, registry=None):
        if registry is None:
            from deepspeed_tpu.monitor.metrics import get_registry

            registry = get_registry()
        self.registry = registry
        self.h2d_bytes = registry.counter(
            "ds_offload_relay_bytes_total",
            "bytes moved across the offload host<->device relay",
            labels={"dir": "h2d"})
        self.d2h_bytes = registry.counter(
            "ds_offload_relay_bytes_total",
            "bytes moved across the offload host<->device relay",
            labels={"dir": "d2h"})
        self.stall = registry.histogram(
            "ds_offload_relay_seconds",
            "host wall seconds attributed to the offload relay: streamed "
            "path = residual stall per consumed layer fetch (0 when "
            "prefetch fully hid the transfer); optimizer boundary = the "
            "grads-down/params-up window (measured only while telemetry "
            "is on)")
        self.hits = registry.counter(
            "ds_offload_prefetch_hits_total",
            "layer fetches already in flight when consumed")
        self.misses = registry.counter(
            "ds_offload_prefetch_misses_total",
            "layer fetches dispatched on demand (prefetch off or behind)")


class ParamStreamer:
    """Per-layer H2D transport over a stacked ``[L, ...]`` host tree.

    ``layer_shardings``: device NamedSharding tree for ONE layer (stacked
    specs with the leading [L] dim stripped — the ``StreamedFwdBwd``
    contract).  ``refresh(np_layers)`` (re)binds the host source — called
    once at init and after every optimizer step (the int8 mode requantizes
    there, so the relay always ships the current weights).

    Transport payloads are host numpy per layer: the value slice, or the
    (q, scale) pair under int8.  :meth:`materialize` is the TRACEABLE
    stage the consuming layer program opens with (pinned->device move
    and/or fused dequant); plain device-memory fp transport materializes
    to the fetched tree itself.
    """

    def __init__(self, layer_shardings, *, int8: bool = False,
                 quant_block: int = DEFAULT_BLOCK, prefetch: bool = True,
                 staging_slots: int = 2, registry=None,
                 compute_dtype=None):
        from deepspeed_tpu.accelerator.real_accelerator import (
            host_memory_kind, supports_pinned_host)

        self._layer_sh = layer_shardings
        self.int8 = bool(int8)
        self.quant_block = int(quant_block)
        self.prefetch_enabled = bool(prefetch)
        self.staging_slots = max(1, int(staging_slots))
        self.pinned = supports_pinned_host()
        self._host_kind = host_memory_kind()
        self.meter = RelayMeter(registry)
        self._compute_dtype = compute_dtype
        # host source (set by refresh)
        self._np_layers = None
        self._q_layers = None            # per-layer QuantizedTree list
        self._layer_spec = None          # one layer's ShapeDtypeStructs
        self.num_layers = 0
        # in-flight fetches: i -> payload (device arrays)
        self._inflight: Dict[int, Any] = {}
        # non-layer (embed/head) transport: name -> (src_key, host payload)
        # quantized once per source binding, shipped per call
        self._aux_q: Dict[str, Any] = {}
        self._aux_spec: Dict[str, Any] = {}
        self._restage = None             # compiled slot-recycling copy
        self._slots = None               # staging ring (device payloads)
        self._slot_idx = 0
        if self.pinned:
            from jax.sharding import NamedSharding, PartitionSpec

            self._put_sh = jax.tree.map(
                lambda s: NamedSharding(s.mesh, s.spec,
                                        memory_kind=self._host_kind),
                layer_shardings)
            # int8 codes and scales wait replicated in host memory over
            # the layers' mesh, i.e. on the devices of the consuming
            # program's other arguments (codes committed to device 0
            # alone are refused beside tokens placed on the whole mesh)
            self._codes_sh = NamedSharding(
                jax.tree.leaves(layer_shardings)[0].mesh, PartitionSpec(),
                memory_kind=self._host_kind)
        else:
            self._put_sh = layer_shardings

    # ------------------------------------------------------------------
    # host source
    # ------------------------------------------------------------------
    def refresh(self, np_layers: Any) -> None:
        """(Re)bind the stacked host tree.  int8: re-quantize per layer —
        host CPU work amortized over the micro-batches of the next step."""
        self._np_layers = np_layers
        first = jax.tree.map(lambda a: np.asarray(a)[0], np_layers)
        self._layer_spec = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), first)
        self.num_layers = int(np.asarray(
            jax.tree.leaves(np_layers)[0]).shape[0])
        if self.int8:
            self._q_layers = [
                quantize_tree_np(
                    jax.tree.map(lambda a, i=i: np.asarray(a)[i], np_layers),
                    self.quant_block)
                for i in range(self.num_layers)]
        self._inflight.clear()

    def _host_payload(self, i: int):
        if self.int8:
            qt = self._q_layers[i]
            return {"q": qt.q, "scale": qt.scale}
        return jax.tree.map(lambda a: np.asarray(a)[i], self._np_layers)

    def _payload_nbytes(self, payload) -> int:
        return _tree_nbytes(payload)

    # ------------------------------------------------------------------
    # transport
    # ------------------------------------------------------------------
    def _put(self, payload):
        if self.int8:
            # replicated codes (+ the pinned hop where advertised): the
            # leaf shapes are [nb, block]/[nb, 1], unrelated to the layer
            # shardings
            if self.pinned:
                return jax.device_put(payload, self._codes_sh)
            return jax.tree.map(jax.device_put, payload)
        dev = jax.device_put(payload, self._put_sh)
        if not self.pinned and self.staging_slots:
            dev = self._restage_into_slot(dev)
        return dev

    def _restage_into_slot(self, fresh):
        """Recycle one of the persistent staging buffers: a donated
        compiled copy writes the fresh transfer into the ring slot, so the
        per-layer device_put temporary frees immediately and steady state
        holds exactly ``staging_slots`` layer-sized buffers.

        The reuse contract needs payloads consumed ONLY as jit inputs
        (the streamed layer programs): exporting a numpy view of a
        payload (``np.asarray``) marks its buffer externally referenced
        and the next donation of that slot safely falls back to a fresh
        allocation (measured — correctness is never at stake, only the
        reuse)."""
        if self._restage is None:
            sh = self._layer_sh

            @functools.partial(jax.jit, donate_argnums=(0,),
                               out_shardings=sh)
            def restage(slot, fresh):
                # output values = fresh, WRITTEN INTO the donated slot
                # buffers (a bare pass-through would alias the output to
                # ``fresh``'s own buffer and leave the donation unused —
                # measured; the scatter-overwrite form pins the alias to
                # the slot)
                return jax.tree.map(lambda s, f: s.at[...].set(f),
                                    slot, fresh)

            self._restage = restage
            zeros = jax.jit(
                lambda: jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), self._layer_spec),
                out_shardings=sh)
            self._slots = [zeros() for _ in range(self.staging_slots)]
        slot = self._slots[self._slot_idx]
        out = self._restage(slot, fresh)
        self._slots[self._slot_idx] = out
        self._slot_idx = (self._slot_idx + 1) % self.staging_slots
        return out

    def prefetch(self, i: int) -> None:
        """Start layer ``i``'s H2D now (no-op when already in flight or
        prefetch is disabled)."""
        if not self.prefetch_enabled or i in self._inflight:
            return
        self._dispatch(i)

    def _dispatch(self, i: int) -> None:
        payload = self._host_payload(i)
        if self.meter.registry.enabled:
            self.meter.h2d_bytes.inc(self._payload_nbytes(payload))
        self._inflight[i] = self._put(payload)

    def take(self, i: int):
        """The payload for layer ``i`` (device arrays), consuming the
        in-flight entry.  Counts prefetch hit/miss; measures the residual
        stall while telemetry is on."""
        hit = i in self._inflight
        if not hit:
            self._dispatch(i)
        payload = self._inflight.pop(i)
        if self.meter.registry.enabled:
            (self.meter.hits if hit else self.meter.misses).inc()
            t0 = time.perf_counter()
            jax.block_until_ready(payload)
            self.meter.stall.record(time.perf_counter() - t0)
        return payload

    def put_aux(self, name: str, tree, shardings, src_key=None):
        """Non-layer (embed/head) H2D through the same relay codec.

        The layer stream went int8 in PR 10 but embed/head stayed dense
        ("embed/head stay bf16" — ROADMAP item 3 leftover); this closes
        it: with ``int8=True`` the tree ships as blockwise codes + scales
        (quantized ONCE per source binding — ``src_key`` identifies the
        host tree generation, so the fwd/bwd re-puts of one step reuse
        one quantization) and :meth:`materialize_aux` fuses the dequant
        into the consuming program.  Dense mode is the plain device_put
        the caller used before.  Either way the payload bytes land on the
        ``ds_offload_relay_bytes_total{dir="h2d"}`` ledger."""
        if not self.int8:
            if self.meter.registry.enabled:
                self.meter.h2d_bytes.inc(_tree_nbytes(tree))
            return jax.device_put(tree, shardings)
        from deepspeed_tpu.comm.quant import quantize_tree_np

        cached = self._aux_q.get(name)
        if cached is None or cached[0] != src_key:
            qt = quantize_tree_np(
                jax.tree.map(np.asarray, tree), self.quant_block)
            self._aux_q[name] = (src_key, qt)
            self._aux_spec[name] = qt.spec
        qt = self._aux_q[name][1]
        payload = {"q": qt.q, "scale": qt.scale}
        if self.meter.registry.enabled:
            self.meter.h2d_bytes.inc(_tree_nbytes(payload))
        if self.pinned:
            return jax.device_put(payload, self._codes_sh)
        return jax.tree.map(jax.device_put, payload)

    def materialize_aux(self, name: str, payload, dtype=None):
        """TRACEABLE twin of :meth:`materialize` for :meth:`put_aux`
        payloads (fused dequant / pinned->device move; dense passes
        through)."""
        if not self.int8:
            return payload
        dtype = dtype or self._compute_dtype
        q, s = payload["q"], payload["scale"]
        if self.pinned:
            q = jax.tree.map(
                lambda a: jax.device_put(a, jax.memory.Space.Device), q)
            s = jax.tree.map(
                lambda a: jax.device_put(a, jax.memory.Space.Device), s)
        return dequantize_tree(q, s, self._aux_spec[name], dtype=dtype)

    def drop_inflight(self) -> None:
        """Forget queued prefetches (direction change mid fwd/bwd: the
        backward walks layers in reverse, so a stale forward prefetch
        would pin a buffer nobody will take)."""
        self._inflight.clear()

    # ------------------------------------------------------------------
    # traceable consumer stage
    # ------------------------------------------------------------------
    def materialize(self, payload, dtype=None):
        """TRACEABLE: payload -> the layer's compute tree inside the
        consuming program — the fused dequant stage (int8) and/or the
        pinned->device move.  Plain fp device transport passes through."""
        dtype = dtype or self._compute_dtype
        if self.int8:
            q, s = payload["q"], payload["scale"]
            if self.pinned:
                q = jax.tree.map(
                    lambda a: jax.device_put(a, jax.memory.Space.Device), q)
                s = jax.tree.map(
                    lambda a: jax.device_put(a, jax.memory.Space.Device), s)
            return dequantize_tree(q, s, self._layer_spec, dtype=dtype)
        if self.pinned:
            from jax.sharding import NamedSharding

            def move(a, sh):
                if sh.mesh is None or sh.mesh.empty:
                    return jax.device_put(a, jax.memory.Space.Device)
                return jax.device_put(
                    a, NamedSharding(sh.mesh, sh.spec, memory_kind="device"))

            return jax.tree.map(move, payload, self._layer_sh)
        return payload

    # -- accounting hooks for the D2H (grad) side ----------------------
    def record_d2h(self, tree) -> None:
        if self.meter.registry.enabled:
            self.meter.d2h_bytes.inc(_tree_nbytes(tree))
