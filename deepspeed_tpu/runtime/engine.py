"""The DeepSpeed engine, TPU-native.

Analog of the reference's ``deepspeed/runtime/engine.py`` (SURVEY.md §2.1
"Engine", §3.2, §3.3) with a functional core: all training math lives in two
jitted, donated, mesh-sharded functions —

- ``_accum``: one micro-batch forward+backward; gradients (loss-scaled,
  divided by gradient_accumulation_steps) are added into a persistent
  accumulator whose sharding implements the ZeRO stage (reduce-scatter falls
  out of GSPMD when the accumulator is sharded over ``fsdp``).
- ``_apply``: the accumulation-boundary step — overflow check (fp16), unscale,
  global-norm clip, optax update, loss-scale transition, skip-on-overflow via
  select (the reference's eager "skip step" becomes a branchless where).

The imperative reference API (``engine.forward`` / ``backward`` / ``step``,
SURVEY.md §3.3) is preserved on top: ``forward`` runs the fused
forward+backward micro-step (dispatch is async on TPU, so this costs nothing
extra), ``backward`` is the recorded no-op that keeps user loops working, and
``step`` applies the update at the accumulation boundary.

ZeRO stages are placement policies (see runtime/zero/partition.py): the engine
computes PartitionSpecs for params/optimizer/accumulator once, then relies on
XLA/GSPMD for all-gathers, reduce-scatters, and comm/compute overlap — the
TPU replacement for the reference's bucketed IPG reducer and trace-based
prefetcher (SURVEY.md §3.3 TPU note).
"""

from __future__ import annotations

import functools
import json
import math
import os
import time
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu import comm
from deepspeed_tpu.comm.mesh import batch_sharding, get_global_mesh, mesh_from_config
from deepspeed_tpu.monitor.comms import comm_metrics
from deepspeed_tpu.monitor.flight_recorder import get_flight_recorder
from deepspeed_tpu.monitor.goodput import get_goodput_ledger
from deepspeed_tpu.monitor.goodput_core import analytic_comm_seconds
from deepspeed_tpu.monitor.memory import MemoryTelemetry, device_resident_bytes
from deepspeed_tpu.monitor.metrics import get_registry
from deepspeed_tpu.monitor.monitor import MonitorMaster
from deepspeed_tpu.monitor.request_trace import get_step_timeline
from deepspeed_tpu.profiling.flops import TrainFlopsMeter, lm_flops_per_token
from deepspeed_tpu.profiling.trace import annotate, phase
from deepspeed_tpu.runtime import optimizer as opt_builder
from deepspeed_tpu.runtime.checkpoint_engine import (MsgpackCheckpointEngine,
                                                     ShardedCheckpointEngine)
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader, shard_batch
from deepspeed_tpu.runtime.fp16 import loss_scaler as scaler_lib
from deepspeed_tpu.runtime.lr_schedules import LRSchedulerShim, get_lr_schedule
from deepspeed_tpu.runtime.utils import (clip_grad_norm, global_norm, has_overflow,
                                         tree_num_params)
from deepspeed_tpu.runtime.zero.partition import (describe_partitioning, opt_state_pspecs,
                                                  params_pspecs, shardings_from_pspecs)
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer, ThroughputTimer


class TrainState(NamedTuple):
    """The complete, donated training state pytree."""

    params: Any
    opt_state: Any
    grad_acc: Any
    global_steps: jnp.ndarray  # i32: optimizer steps actually applied
    scaler: scaler_lib.LossScaleState


# training-numerics gauges published at every optimizer boundary while the
# registry is enabled (values the engine already computes for _report);
# the namespace guard registers these explicitly so docs can't drift
TRAIN_STEP_GAUGES = {
    "ds_train_loss":
        "loss at the last optimizer boundary (the _report value, "
        "published every boundary while telemetry is on)",
    "ds_train_grad_norm":
        "global grad norm at the last optimizer boundary (pre-clip "
        "value from the step program)",
}


def _spec_world(spec, mesh) -> int:
    """Product of the mesh-axis extents a PartitionSpec shards over."""
    axes = []
    for part in spec:
        if part is None:
            continue
        axes.extend(part if isinstance(part, (tuple, list)) else (part,))
    w = 1
    for a in axes:
        w *= mesh.shape.get(a, 1)
    return max(1, w)


def _build_comm_plan(params, param_specs, acc_specs, mesh, zero_stage,
                     compute_dtype, acc_dtype, overlap_sched=None):
    """Analytic per-step collective volumes for the GSPMD ZeRO path.

    GSPMD inserts the ZeRO collectives implicitly (sharded accumulator ->
    reduce-scatter, sharded params -> all-gather), so there is no wrapper
    call site to count at.  What the schedule MUST move is still fully
    determined by the partitioning specs, so the engine commits this plan
    into the ``ds_comm_*`` series once per executed micro-batch/boundary:

    - stage 3: every sharded param all-gathers twice per micro-batch
      (forward + backward — the reference ZeRO-3 schedule);
    - stage >= 2: gradients reduce-scatter into the sharded accumulator
      once per micro-batch; stages 0/1 all-reduce them instead;
    - stages 1/2: the boundary update on sharded optimizer state implies
      one param all-gather back to the replicated layout.

    With ``overlap_sched`` (the layer-chunked explicit schedule,
    runtime/zero/overlap.py) the MICRO entries come from the schedule's
    own per-bucket accounting — per-bucket call counts and bytes, in the
    dtype the explicit collectives actually move — so the ``ds_comm_*``
    series stays honest when ``overlap_comm`` is on.  Boundary entries
    keep the GSPMD arithmetic (the overlap path leaves the boundary
    update on the GSPMD path).

    Returns ``{"micro": [entries], "boundary": [entries]}`` with entries
    shaped for :meth:`CommMetrics.commit`; empty lists when the mesh has no
    extent to communicate over.  Device-measured truth lives in the xplane
    trace — this is the byte ledger, not a timer.
    """
    dp_world = 1
    for a in ("dp", "fsdp", "ep"):
        dp_world *= mesh.shape.get(a, 1)
    c_item = jnp.dtype(compute_dtype).itemsize
    a_item = jnp.dtype(acc_dtype).itemsize
    cname = jnp.dtype(compute_dtype).name
    aname = jnp.dtype(acc_dtype).name

    p_leaves = jax.tree_util.tree_leaves(params)
    spec_leaves = jax.tree_util.tree_leaves(
        param_specs, is_leaf=lambda s: isinstance(s, P))
    acc_spec_leaves = jax.tree_util.tree_leaves(
        acc_specs, is_leaf=lambda s: isinstance(s, P))

    gather_bytes = gather_calls = 0
    gather_world = 1
    total_bytes = 0
    for leaf, spec in zip(p_leaves, spec_leaves):
        nbytes = int(np.prod(leaf.shape)) * c_item if leaf.shape else c_item
        total_bytes += nbytes
        w = _spec_world(spec, mesh)
        if w > 1:
            gather_bytes += nbytes
            gather_calls += 1
            gather_world = max(gather_world, w)

    rs_bytes = rs_calls = 0
    rs_world = 1
    for leaf, spec in zip(p_leaves, acc_spec_leaves):
        nbytes = int(np.prod(leaf.shape)) * a_item if leaf.shape else a_item
        w = _spec_world(spec, mesh)
        if w > 1:
            rs_bytes += nbytes
            rs_calls += 1
            rs_world = max(rs_world, w)

    micro: List[Tuple[str, int, int, str, int]] = []
    boundary: List[Tuple[str, int, int, str, int]] = []
    if overlap_sched is not None:
        micro = overlap_sched.comm_plan_entries()
        if zero_stage in (1, 2) and dp_world > 1 and total_bytes:
            boundary.append(("all_gather", len(p_leaves), total_bytes,
                             cname, dp_world))
        return {"micro": micro, "boundary": boundary}
    if zero_stage == 3 and gather_bytes:
        micro.append(("all_gather", 2 * gather_calls, 2 * gather_bytes,
                      cname, gather_world))
    if zero_stage >= 2 and rs_bytes:
        micro.append(("reduce_scatter", rs_calls, rs_bytes, aname, rs_world))
    elif dp_world > 1 and total_bytes:
        # replicated accumulator: each micro-batch's grads all-reduce over
        # the data axes (bytes in the accumulation dtype)
        micro.append(("all_reduce", len(p_leaves),
                      total_bytes * a_item // c_item, aname, dp_world))
    if zero_stage in (1, 2) and dp_world > 1 and total_bytes:
        # sharded-optimizer update -> updated params gather back replicated
        boundary.append(("all_gather", len(p_leaves), total_bytes, cname,
                         dp_world))
    return {"micro": micro, "boundary": boundary}


@functools.lru_cache(maxsize=None)
def _dequant_put(shape, dtype_name, sharding):
    """Memoized compiled blockwise dequant for the int8 offload relay:
    (q int8 [nb, block], scale fp32 [nb, 1]) -> compute-dtype param leaf.
    Only the int8 payload crosses host->device; the wide array exists only
    as a program output."""
    from deepspeed_tpu.comm.quant import dequantize_blockwise

    dt = jnp.dtype(dtype_name)
    return jax.jit(lambda q, s: dequantize_blockwise(q, s, shape, dt),
                   out_shardings=sharding)


def _flight_guard(fn):
    """Dump the flight recorder (once) before re-raising an unhandled
    exception out of an engine entry point."""

    @functools.wraps(fn)
    def wrapped(self, *args, **kwargs):
        try:
            return fn(self, *args, **kwargs)
        except Exception as exc:
            self._flight_crash(exc)
            raise

    return wrapped


class DeepSpeedEngine:
    def __init__(self, args=None, model=None, optimizer=None, model_parameters=None,
                 training_data=None, lr_scheduler=None, mpu=None, dist_init_required=None,
                 collate_fn=None, config=None, mesh=None, rng=None, loss_fn=None,
                 param_pspecs=None):
        if model is None and loss_fn is None:
            raise ValueError("deepspeed_tpu.initialize requires a model (flax module or "
                             "callable (params, batch, rng) -> loss)")
        self.module = model
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.collate_fn = collate_fn
        self.mpu = mpu

        if isinstance(config, DeepSpeedConfig):
            self.config = config
        else:
            # With an explicit mesh (and no mpu — the mpu's DP group keeps
            # reference precedence), the batch triad's world size is the
            # mesh's data-parallel extent (dp × fsdp × ep carry batch shards).
            ws = None
            ws_mesh = mesh if mesh is not None else get_global_mesh(create_default=False)
            if ws_mesh is not None and mpu is None:
                ws = comm.get_data_parallel_world_size(ws_mesh)
            self.config = DeepSpeedConfig(config, mpu=mpu, world_size=ws)
        comm.init_distributed(dist_init_required=dist_init_required, config=self.config)
        self.mesh = mesh or get_global_mesh()
        comm.set_global_mesh(self.mesh)
        comm.configure(deepspeed_config=self.config)

        self.zero_stage = self.config.zero_config.stage
        self.fp16_enabled = self.config.fp16_enabled
        self.bfloat16_enabled = self.config.bfloat16_enabled
        self.compute_dtype = self.config.dtype()

        # ZeRO-Offload / ZeRO-Infinity (SURVEY.md §2.1 rows "NVMe swap",
        # "ZeRO stage 1+2" cpu_offload): optimizer states live on host RAM or
        # NVMe; the device holds compute-dtype params + grad accumulator only.
        off_cfg = self.config.zero_config.offload_optimizer
        self._offload_device = off_cfg.device if off_cfg is not None else "none"
        self._offload = self._offload_device in ("cpu", "nvme")
        self._offload_opt = None
        self._relay_meter = None
        self._streamed = None
        self._np_params = None
        self._pinned_stale = False
        self._onebit_stacked = False
        if self._offload:
            log_dist(f"ZeRO-Offload: optimizer states -> {self._offload_device}"
                     + (f" ({off_cfg.nvme_path})" if self._offload_device == "nvme"
                        else ""), ranks=[0])
        p_off = self.config.zero_config.offload_param
        self._param_offload = p_off is not None and p_off.device in ("cpu", "nvme")
        if self._param_offload:
            # ZeRO-Infinity parameter tiering: compute-dtype params live in
            # pinned host memory; the model streams each scanned layer to the
            # device on demand (bounded window).  Implies host-resident
            # optimizer states (reference: offload_param requires
            # offload_optimizer in practice).
            if not self._offload:
                self._offload = True
                self._offload_device = p_off.device
            if self.config.fp16_enabled:
                raise ValueError("offload_param does not support fp16 loss "
                                 "scaling; use bf16 (TPU-native) instead")
            # NOTE: exercised end to end on the CPU mesh only; not yet run
            # on a chip (the memories API path below is the one a TPU
            # takes).
            log_dist(f"ZeRO-Infinity: params tiered to {p_off.device} "
                     "(per-layer device streaming)", ranks=[0])
        # 1-bit optimizers (reference: fp16/onebit/): need per-worker local
        # gradients, so the engine runs accum/apply under full-manual
        # shard_map over the data axes.  Like the reference, incompatible
        # with ZeRO >= 2, fp16 loss scaling, and model parallelism.
        _opt_name = (self.config.optimizer.type.lower().replace("_", "").replace("-", "")
                     if self.config.optimizer else "")
        self._onebit = (_opt_name in ("onebitadam", "zerooneadam", "onebitlamb")
                        and not self._offload)
        if (_opt_name in ("onebitadam", "zerooneadam", "onebitlamb")
                and self._offload):
            logger.warning("%s with offload_optimizer: the compressed-"
                           "communication path does not combine with host-"
                           "offloaded states (reference constraint); states "
                           "will be stepped by DeepSpeedCPUAdam instead",
                           self.config.optimizer.type)
        if self._onebit:
            if self.zero_stage >= 2:
                raise ValueError("1-bit optimizers do not support ZeRO stage >= 2 "
                                 "(reference constraint)")
            if self.fp16_enabled:
                raise ValueError("1-bit optimizers require bf16/fp32 (no fp16 "
                                 "loss scaling)")
            bad = [a for a in ("tp", "sp", "pp") if self.mesh.shape.get(a, 1) > 1]
            if bad:
                raise ValueError(f"1-bit optimizers do not support model "
                                 f"parallelism (axes {bad} > 1)")
            if self.config.gradient_clipping:
                # incompatible by construction (clipping local grads breaks
                # error feedback); the reference silently ignores the knob —
                # a one-shot warning is too easy to miss in a config sweep
                raise ValueError(
                    "gradient_clipping is not supported with 1-bit "
                    "optimizers (clipping local grads would break error "
                    "feedback) — remove gradient_clipping or use a dense "
                    "optimizer")
            log_dist(f"1-bit optimizer active: {self.config.optimizer.type} "
                     f"(compressed momentum exchange after freeze_step)", ranks=[0])
        # ZeRO++ (SURVEY §2.3; VERDICT r3 item 3): quantized weight
        # all-gathers / gradient reduce-scatters + hpZ secondary partition,
        # on the full-manual shard_map path (runtime/zero/zeropp.py).
        zc = self.config.zero_config
        cq = self.config.comm_quantization
        self._qcomm = cq
        # the comm_quantization gather/scatter sites are the comm-layer
        # spellings of the ZeRO++ flags: at stage 3 (and without
        # overlap_comm, which owns its own quantized schedule) they
        # activate the ZeRO++ path by themselves — either spelling alone
        # turns the seam on (config docstring contract)
        want_zpp = (zc.zero_quantized_weights or zc.zero_quantized_gradients
                    or zc.zero_hpz_partition_size > 1
                    or (self.zero_stage == 3 and not zc.overlap_comm
                        and (cq.q_all_gather or cq.q_reduce_scatter)))
        self._zeropp = False
        self._zeropp_reason = None
        if want_zpp:
            bad = [a for a in ("tp", "sp", "pp", "ep")
                   if self.mesh.shape.get(a, 1) > 1]
            P = self.mesh.shape.get("fsdp", 1)
            z = zc.zero_hpz_partition_size
            if self.zero_stage != 3:
                self._zeropp_reason = "requires ZeRO stage 3 (sharded params)"
            elif self._offload or self._onebit:
                self._zeropp_reason = ("not combinable with offload or 1-bit "
                                       "optimizers")
            elif self.fp16_enabled:
                self._zeropp_reason = "requires bf16/fp32 (no fp16 loss scaling)"
            elif bad:
                self._zeropp_reason = (f"model/expert-parallel axes {bad} are "
                                       "not supported on the ZeRO++ path")
            elif P <= 1:
                self._zeropp_reason = "needs an fsdp mesh axis > 1"
            elif z > 1 and P % z:
                self._zeropp_reason = f"hpz size {z} must divide fsdp={P}"
            else:
                self._zeropp = True
                log_dist(
                    f"ZeRO++ active: qw={zc.zero_quantized_weights} "
                    f"qg={zc.zero_quantized_gradients} hpz={max(1, z)} "
                    f"over fsdp={P}", ranks=[0])
        # Layer-chunked compute/collective overlap (runtime/zero/overlap.py;
        # ROADMAP open item 1): ``zero_optimization.overlap_comm: true``
        # replaces the GSPMD-placed ZeRO collectives with an explicit
        # per-layer-bucket schedule so comm hides under the matmuls.
        # Config-level eligibility decided here (audit warns on the knob
        # while ineligible); the model-level half (stream_segments, stacked
        # param layout) resolves at state init.
        self._overlap = False
        self._overlap_sched = None
        self._overlap_reason = None
        self._overlap_want = False
        if zc.overlap_comm:
            bad = [a for a in ("tp", "sp", "pp", "ep")
                   if self.mesh.shape.get(a, 1) > 1]
            if self.zero_stage not in (1, 2, 3):
                self._overlap_reason = ("requires ZeRO stage 1-3 (stage 0 "
                                        "has no sharded state to schedule)")
            elif self._offload or self._param_offload:
                self._overlap_reason = ("offload paths already own their "
                                        "own streaming schedule")
            elif self._onebit:
                self._overlap_reason = ("1-bit optimizers keep local grads "
                                        "(no collective to chunk)")
            elif self._zeropp:
                self._overlap_reason = ("ZeRO++ runs its own quantized "
                                        "collective schedule")
            elif bad:
                self._overlap_reason = (
                    f"model/expert-parallel axes {bad} are not supported "
                    "on the overlap path"
                    + (" (the pipelined program already overlaps its "
                       "boundary rings with stage compute — XLA schedules "
                       "the ppermute hops against the scan body)"
                       if "pp" in bad else ""))
            elif loss_fn is not None:
                self._overlap_reason = ("a client loss_fn cannot route "
                                        "through the model's layer segments")
            else:
                self._overlap_want = True
        # Unified quantized-collective transport (comm/collectives_q.py;
        # ROADMAP item 2): the `comm_quantization` block opts individual
        # call sites into int8 comm.  The grad_all_reduce site routes the
        # ZeRO stage 0/1/2 boundary gradient sync through an explicit
        # manual-region q_all_reduce with an error-feedback residual
        # carried as engine state; the other sites thread through the
        # overlap schedule, ZeRO++, MoE dispatch and the sequence ring.
        self._qcomm_grads = False
        self._qcomm_grads_reason = None
        self._qcomm_residual = None
        if cq.q_grad_all_reduce:
            # ep counts as a bad axis here, not a data axis: expert
            # params shard over ep, and the manual region would feed a
            # full-E dispatch into an E/ep-local expert tree (trace
            # crash) — and q_all_reduce over ep would average DIFFERENT
            # experts' gradient shards together
            bad = [a for a in ("tp", "sp", "pp", "ep")
                   if self.mesh.shape.get(a, 1) > 1]
            data_world = 1
            for a in ("dp", "fsdp", "ep"):
                data_world *= self.mesh.shape.get(a, 1)
            if self.zero_stage > 2:
                self._qcomm_grads_reason = (
                    "stage 3 has no boundary grad all-reduce — its "
                    "gathers/scatters quantize via overlap_comm or the "
                    "ZeRO++ flags")
            elif self._offload or self._param_offload:
                self._qcomm_grads_reason = (
                    "offloaded grads cross the host relay, not a "
                    "collective (offload_optimizer.int8_masters / "
                    "offload_param.int8_stream own that transport)")
            elif self._onebit:
                self._qcomm_grads_reason = ("1-bit optimizers already "
                                            "compress their exchange")
            elif self._overlap_want:
                self._qcomm_grads_reason = (
                    "overlap_comm owns the bucketed reduction schedule "
                    "(enable comm_quantization.reduce_scatter there)")
            elif self.fp16_enabled:
                self._qcomm_grads_reason = ("requires bf16/fp32 (no fp16 "
                                            "loss scaling)")
            elif bad:
                self._qcomm_grads_reason = (
                    f"model/expert-parallel axes {bad} are not supported "
                    "on the manual quantized-grad path (ep shards expert "
                    "params; tp/sp/pp shard the program)")
            elif data_world <= 1:
                self._qcomm_grads_reason = ("no data-parallel axis > 1 — "
                                            "there is no all-reduce to "
                                            "quantize")
            else:
                self._qcomm_grads = True
                log_dist(
                    f"comm_quantization: stage {self.zero_stage} gradient "
                    f"all-reduce -> int8 q_all_reduce (block {cq.block}, "
                    f"error_feedback={'on' if cq.error_feedback else 'OFF'})"
                    + ("" if cq.error_feedback else
                       " — compressed grads without the residual "
                       "accumulate quantization bias"), ranks=[0])
        self.gradient_accumulation_steps = lambda: self.config.gradient_accumulation_steps
        self.train_batch_size = lambda: self.config.train_batch_size
        self.train_micro_batch_size_per_gpu = lambda: self.config.train_micro_batch_size_per_gpu
        self._audit_config()
        if self.config.dump_state and comm.get_rank() == 0:
            self.config.print_config()

        self._rng = rng if rng is not None else jax.random.PRNGKey(self.config.seed)
        self._apply_activation_checkpointing_config(model)
        self._apply_pipeline_config(model)
        self._setup_compression(model)
        if self._param_offload:
            mcfg = getattr(model, "config", None)
            if mcfg is not None and hasattr(mcfg, "param_offload"):
                mcfg.param_offload = True
            else:
                logger.warning(
                    "offload_param: model %s does not expose a param_offload "
                    "hook; params stay host-resident but the model will not "
                    "stream them per-layer", type(model).__name__)
        # comm_quantization sites that live inside the MODEL's program
        # (MoE dispatch, sequence ring) are wired through the model
        # config, the param_offload idiom above.  Assigned UNCONDITIONALLY
        # (True or False): a model object reused across engines must not
        # keep a previous engine's quantization flags stuck on.
        _mcfg = getattr(model, "config", None)
        if _mcfg is not None and hasattr(_mcfg, "moe_q_dispatch"):
            _mcfg.comm_quant_block = cq.block
            _moe_q = bool(cq.q_all_to_all
                          and getattr(_mcfg, "num_experts", 0) > 0)
            _mcfg.moe_q_dispatch = _moe_q
            if _moe_q:
                log_dist("comm_quantization: MoE ep dispatch -> int8 "
                         "q_reshard (combine stays dense — replicated "
                         "codes would move MORE bytes than the "
                         "ep-sharded exchange)", ranks=[0])
            elif cq.q_all_to_all:
                logger.warning(
                    "comm_quantization.all_to_all: model has no MoE "
                    "layers — only explicit "
                    "all_to_all_single(quantized=True) callers quantize")
            # attention_core only takes the RING when sp_mode says so or
            # the head count forces it — otherwise ulysses runs and this
            # knob would be a lying log line
            _nsp = self.mesh.shape.get("sp", 1)
            _ntp = self.mesh.shape.get("tp", 1)
            _heads = int(getattr(_mcfg, "num_heads", 0) or 0)
            _local_heads = _heads // max(1, _ntp)
            _ring = (getattr(_mcfg, "sp_mode", "auto") == "ring"
                     or (_local_heads and _local_heads % _nsp))
            _ring_q = bool(cq.q_sequence_ring and _nsp > 1 and _ring)
            _mcfg.seq_ring_q = _ring_q
            if _ring_q:
                log_dist("comm_quantization: sequence-parallel ring KV "
                         "rotation -> int8 codes", ranks=[0])
            elif cq.q_sequence_ring and _nsp > 1:
                logger.warning(
                    "comm_quantization.sequence_ring is set but this "
                    "configuration resolves to ULYSSES attention "
                    "(sp_mode=%s, %d local heads divisible by sp=%d) — "
                    "the knob is inert; set the model's sp_mode='ring' "
                    "to opt the ring in",
                    getattr(_mcfg, "sp_mode", "auto"), _local_heads,
                    _nsp)
        elif cq.q_all_to_all or cq.q_sequence_ring:
            logger.warning(
                "comm_quantization: model %s exposes no comm-quant hooks "
                "(moe_q_dispatch/seq_ring_q); the all_to_all/"
                "sequence_ring sites stay dense", type(model).__name__)
        # pipeline boundary site (runtime/pipe/spmd.py): same unconditional
        # assignment rule — and the trace-time boundary ledger is ALWAYS
        # off under the engine, which commits its analytic per-execution
        # comm plan instead (_merge_pp_comm_plan; the feed-disjointness
        # rule)
        if _mcfg is not None and hasattr(_mcfg, "pp_boundary_q"):
            _npp = self.mesh.shape.get("pp", 1)
            _pp_q = bool(cq.q_pipeline and _npp > 1)
            _mcfg.pp_boundary_q = _pp_q
            _mcfg.comm_quant_block = cq.block
            _mcfg.pp_comm_record = False
            if _pp_q:
                log_dist("comm_quantization: pipeline boundary rings -> "
                         "int8 carry codec (fwd activation + bwd cotangent "
                         f"hops, block {cq.block})", ranks=[0])
        elif cq.q_pipeline:
            logger.warning(
                "comm_quantization.pipeline: model %s exposes no "
                "pp_boundary_q hook; the pipeline boundary stays dense",
                type(model).__name__)
        self._client_loss_fn = loss_fn is not None
        self._loss_fn = loss_fn or self._make_loss_fn(model)
        if param_pspecs is None and hasattr(model, "logical_pspecs"):
            # Built-in models publish their tensor/expert-parallel layout
            # (the AutoTP-equivalent classification, SURVEY.md §2.1).
            param_pspecs = model.logical_pspecs()
        self._client_param_pspecs = param_pspecs  # tensor-parallel logical specs
        self._micro_count = 0
        self._host_steps = 0
        self._pp_plan_pending = True   # pipeline comm-plan merge, 1st batch
        self._boundary_override: Optional[bool] = None
        self._last_loss = None
        self._last_grad_norm = None
        self._last_overflow = None
        self._state: Optional[TrainState] = None
        self._accum_fn = None
        self._apply_fn = None
        self._eval_fn = None
        self.optimizer = None  # optax transformation, set in _build_optimizer
        self._lr_schedule = None
        self.lr_scheduler = None
        self._build_optimizer()

        # Curriculum learning (reference: data_efficiency.data_sampling.
        # curriculum_learning / legacy top-level curriculum_learning):
        # seqlen difficulty applied by truncating batches before dispatch.
        self.curriculum_scheduler = None
        cl = {}
        if self.config.data_efficiency is not None:
            cl = self.config.data_efficiency.data_sampling.get(
                "curriculum_learning", {})
        if not cl.get("enabled"):
            cl = getattr(self.config, "curriculum_learning", {}) or {}
        if cl.get("enabled"):
            from deepspeed_tpu.runtime.data_pipeline import CurriculumScheduler

            self.curriculum_scheduler = CurriculumScheduler(cl)
            log_dist(f"curriculum learning: {cl.get('curriculum_type')} "
                     f"{self.curriculum_scheduler.min_difficulty} -> "
                     f"{self.curriculum_scheduler.max_difficulty}", ranks=[0])

        self.checkpoint_engine = ShardedCheckpointEngine(self.config.checkpoint_config)
        self.monitor = MonitorMaster(self.config)

        # -- training-side telemetry (docs/OBSERVABILITY.md) ------------
        # comms_logger block = the telemetry master switch for training:
        # turns on the registry so ds_comm_*/ds_mem_*/ds_train_* record.
        if self.config.comms_logger.enabled:
            get_registry().enable()
        self._comm_plan = None            # set by _setup_state_telemetry
        self._flops_per_step_fn = None    # (micro, seq) -> train FLOPs
        self._flops_since_boundary = 0.0
        self._flops_meter = TrainFlopsMeter(num_devices=self.mesh.devices.size)
        self._mem_telemetry = MemoryTelemetry()
        # training step timeline (docs/OBSERVABILITY.md "Distributed
        # tracing"): shares the telemetry master switch — a process that
        # records ds_* series also retains its step/micro spans for
        # /requestz?kind=train scrapes and trace_report --timeline
        self._timeline = get_step_timeline()
        if self.config.comms_logger.enabled:
            self._timeline.enable()
        self._flight = get_flight_recorder()
        self._flight_dumped = False
        frc = self.config.flight_recorder
        if frc.enabled:
            self._flight.enable(capacity=frc.capacity, dump_dir=frc.dump_dir)
            if frc.on_signal:
                self._flight.install_signal_handler()
        # -- run-level goodput ledger (docs/OBSERVABILITY.md "Goodput
        # ledger"): every second of run wall clock attributed to one
        # category, telescoping to now - run_start.  Config block or the
        # DSTPU_RUNLEDGER env (the supervisors' per-incarnation channel).
        self._goodput = get_goodput_ledger()
        gpc = self.config.goodput
        if gpc.enabled or os.environ.get("DSTPU_RUNLEDGER"):
            self._goodput.enable(
                path=gpc.path, role="train",
                min_tick_interval_s=gpc.min_tick_interval_s,
                slo_rules=self.config.slo.rules() or None)
        self._gp_comm_gbps = gpc.assumed_comm_gbps
        # per-boundary compute seconds (lag ring for the anomaly-skip
        # reattribution: the trip classifies the PREVIOUS boundary)
        self._gp_compute_since_boundary = 0.0
        self._gp_step_compute = [0.0, 0.0]   # [prev boundary, last boundary]

        # -- preemption grace-window handling (docs/RESILIENCE.md): the
        # SIGTERM handler only latches a flag; the next optimizer boundary
        # runs one emergency save (the watchdog/_aux_trace_tick boundary-
        # hook pattern).  Config-driven install here; the explicit API is
        # enable_preemption_save().
        self._preempt = None
        self._preempt_cfg = None
        self._preempt_client_state_fn = None
        ckc = self.config.checkpoint_config
        if ckc.preemption_save:
            if ckc.save_dir:
                self.enable_preemption_save(ckc.save_dir)
            else:
                logger.warning(
                    "checkpoint.preemption_save is set but checkpoint."
                    "save_dir is not: SIGTERM handler NOT installed "
                    "(nowhere to save)")

        # -- device-true profiling (docs/OBSERVABILITY.md "Device truth"):
        # one-shot auxiliary capture slot shared by /profilez requests and
        # watchdog trips ((TraceCapture, trigger, payload) or None), polled
        # at optimizer boundaries
        self._aux_trace = None
        from deepspeed_tpu.profiling.device_trace import get_profile_broker

        self._pz_broker = get_profile_broker()
        # step-time watchdog (ds_config `watchdog` block): rolling-median
        # anomaly detector; a trip dumps the flight recorder and arms a
        # one-shot trace capture of the following steps
        self._watchdog = None
        self._wd_last_t = None
        wdc = self.config.watchdog
        if wdc.enabled:
            from deepspeed_tpu.monitor.watchdog import StepWatchdog

            self._watchdog = StepWatchdog(factor=wdc.factor,
                                          window=wdc.window,
                                          warmup=wdc.warmup)
            if not self._flight.enabled:
                # a trip dump needs a populated ring; the watchdog implies
                # the recorder (documented)
                self._flight.enable(capacity=frc.capacity,
                                    dump_dir=wdc.output_path or frc.dump_dir)
            log_dist(f"watchdog armed: step > {wdc.factor:g}x rolling "
                     f"median (window {wdc.window}) dumps the flight "
                     f"recorder"
                     + (f" + captures {wdc.capture_steps} steps"
                        if wdc.trace else ""),
                     ranks=[0])

        # bf16/fp32 anomaly containment (ds_config `anomaly_detection`;
        # docs/RESILIENCE.md "Elastic training"): rolling-median grad-norm
        # spike + non-finite detector.  Where the standard apply/fused
        # step compiles, the trip is a BRANCHLESS in-program select (the
        # fp16 has_overflow idiom); after `patience` consecutive trips
        # the boundary tick rolls back to the last-good checkpoint.
        self._anomaly = None
        self._anomaly_pending = None   # lag-1 deferred grad-norm fetch
        self._anomaly_select = False   # step programs compiled with the bound arg
        anc = self.config.anomaly_detection
        if anc.enabled:
            if self._zeropp or self._onebit:
                logger.warning(
                    "anomaly_detection: the ZeRO++/1-bit step programs do "
                    "not carry the in-program skip select; detector NOT "
                    "armed (use the standard/offload paths)")
            else:
                from deepspeed_tpu.monitor.anomaly import GradAnomalyDetector

                self._anomaly = GradAnomalyDetector(
                    factor=anc.factor, window=anc.window,
                    warmup=anc.warmup, patience=anc.patience)
                log_dist(
                    f"anomaly detector armed: grad norm non-finite or > "
                    f"{anc.factor:g}x rolling median skips the step; "
                    f"{anc.patience} consecutive trips roll back to the "
                    f"last-good checkpoint", ranks=[0])

        self.flops_profiler = None
        self._profile_probes = {}
        if self.config.flops_profiler.enabled:
            from deepspeed_tpu.profiling import FlopsProfiler

            self.flops_profiler = FlopsProfiler(ds_engine=self)
            self.flops_profiler.start_profile()
        # jax.profiler trace window (SURVEY §5.1; the NVTX/nsys analog):
        # enabled explicitly, or implied by wall_clock_breakdown
        self._trace = None
        ptc = self.config.profile_trace
        trace_on = bool(ptc.enabled or (ptc.enabled is None
                                        and self.config.wall_clock_breakdown))
        self.timers = SynchronizedWallClockTimer(
            synchronize=self.config.wall_clock_breakdown, annotate=trace_on)
        if trace_on:
            from deepspeed_tpu.profiling.trace import TraceCapture

            trace_dir = ptc.output_path or os.path.join(
                self.config.csv_monitor.output_path or "./csv_monitor",
                "ds_trace")
            self._trace = TraceCapture(trace_dir, start_step=ptc.start_step,
                                       num_steps=ptc.num_steps)
        # -- always-on continuous profiler (docs/OBSERVABILITY.md
        # "Continuous profiling"): scheduled low-duty-cycle device
        # captures feeding ds_comm_<op>_device_seconds + ds_prof_* with
        # no operator /profilez.  Disabled = a None slot and one branch
        # per boundary tick (the PR 3 contract); enabling it implies the
        # registry switch — an attribution feed nobody records is dead
        # weight.
        self._cprof = None
        cpc = self.config.continuous_profiler
        if cpc.enabled:
            from deepspeed_tpu.profiling.continuous import ContinuousProfiler
            from deepspeed_tpu.profiling.continuous import ensure_registered

            get_registry().enable()
            ensure_registered(get_registry())
            self._cprof = ContinuousProfiler(
                engine="train",
                every_steps=cpc.every_steps,
                every_seconds=cpc.every_seconds,
                capture_steps=cpc.capture_steps,
                max_duty_cycle=cpc.max_duty_cycle,
                history_dir=cpc.history_dir,
                max_windows=cpc.max_windows,
                max_bytes=cpc.max_bytes,
                regression_tolerance=cpc.regression_tolerance,
                min_scope_seconds=cpc.min_scope_seconds,
                bytes_per_op_fn=self._profile_bytes_per_op,
                flight=self._flight)
            log_dist(
                f"continuous profiler armed: {cpc.capture_steps}-step "
                f"window every {cpc.every_steps} steps or "
                f"{cpc.every_seconds:g}s (duty cycle <= "
                f"{100 * cpc.max_duty_cycle:g}%) -> {cpc.history_dir}",
                ranks=[0])
        self.tput_timer = ThroughputTimer(batch_size=self.config.train_batch_size)
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data)
        self._training = True

        # Params supplied eagerly -> materialize state now; else lazy-init on
        # the first batch (zero.Init-equivalent abstract init, SURVEY.md §7.4).
        if model_parameters is not None:
            with phase("ds_setup_params"):
                self._init_state(model_parameters)

    # ------------------------------------------------------------------
    # construction helpers
    # ------------------------------------------------------------------
    def _audit_config(self) -> None:
        """Loud degradation for parsed-but-inert config (VERDICT r3 item 6).

        Two classes of accepted keys exist and only one warns:

        - *by-design no-ops*: knobs whose capability XLA/GSPMD delivers
          structurally (bucket sizes, ``contiguous_gradients``,
          ``prescale_gradients`` — gradient scaling order is numerically
          immaterial inside one XLA program, ``round_robin_gradients`` — a
          CUDA-stream scheduling detail).  These stay silent: the behavior
          the user asked for happens.  ``overlap_comm`` moved OUT of this
          class: true now activates the layer-chunked explicit overlap
          schedule (runtime/zero/overlap.py) and warns when the
          configuration cannot take it.
        - *inert behavior knobs*: sections that would change observable
          behavior and currently change nothing.  Each warns once here so a
          capability gap can never hide behind a successfully-parsed config.
        """
        cfg = self.config
        zc = cfg.zero_config
        inert = []
        if cfg.amp.enabled:
            inert.append(("amp", "torch/apex AMP has no TPU analog; use the "
                                 "bf16 (recommended) or fp16 sections"))
        if cfg.sparse_gradients_enabled:
            inert.append(("sparse_gradients", "sparse gradient compaction is "
                          "not implemented (dense grads are always exchanged)"))
        if cfg.communication_data_type:
            inert.append(("communication_data_type", "collective dtype "
                          "follows the compute dtype under GSPMD"))
        if zc.overlap_comm and not self._overlap_want:
            inert.append(("zero_optimization.overlap_comm",
                          f"{self._overlap_reason}; the GSPMD-placed "
                          "collectives run unchanged"))
        if not self._zeropp_active():
            if zc.zero_quantized_weights:
                inert.append(("zero_optimization.zero_quantized_weights",
                              self._zeropp_inactive_reason()))
            if zc.zero_quantized_gradients:
                inert.append(("zero_optimization.zero_quantized_gradients",
                              self._zeropp_inactive_reason()))
            if zc.zero_hpz_partition_size > 1:
                inert.append(("zero_optimization.zero_hpz_partition_size",
                              self._zeropp_inactive_reason()))
        cq = self.config.comm_quantization
        if cq.q_grad_all_reduce and not self._qcomm_grads:
            inert.append(("comm_quantization.grad_all_reduce",
                          f"{self._qcomm_grads_reason}; the gradient sync "
                          "runs dense"))
        if ((cq.q_all_gather or cq.q_reduce_scatter)
                and not (self._overlap_want or self._zeropp)):
            inert.append(("comm_quantization.all_gather/reduce_scatter",
                          "no explicit gather/scatter seam in this "
                          "configuration (GSPMD places dense collectives) "
                          "— enable zero_optimization.overlap_comm or the "
                          "ZeRO++ stage-3 path"))
        if cq.q_sequence_ring and self.mesh.shape.get("sp", 1) <= 1:
            inert.append(("comm_quantization.sequence_ring",
                          "no sp mesh axis > 1 — there is no ring "
                          "exchange to quantize"))
        if cq.q_pipeline and self.mesh.shape.get("pp", 1) <= 1:
            inert.append(("comm_quantization.pipeline",
                          "no pp mesh axis > 1 — there is no stage "
                          "boundary ring to quantize"))
        import logging as _logging

        for key, why in inert:
            log_dist(f"config key {key!r} is set but INERT: {why}",
                     ranks=[0], level=_logging.WARNING)
        self._inert_config_keys = [k for k, _ in inert]

    def _zeropp_active(self) -> bool:
        """Whether the ZeRO++ quantized-collective path is active;
        _audit_config warns on the ZeRO++ knobs exactly while this is
        False (with the specific reason)."""
        return self._zeropp

    def _zeropp_inactive_reason(self) -> str:
        why = self._zeropp_reason or "ZeRO++ path not applicable"
        return f"{why}; the knob changes nothing"

    def _setup_overlap(self, params, persist: int) -> None:
        """Model-level half of the ``overlap_comm`` gate (config half ran in
        ``__init__``): the bucketed schedule drives the model through its
        streamed per-layer segments, so the model must expose
        ``stream_segments`` and carry the stacked embed/layers/head param
        layout.  On success, replaces ``self._param_specs`` with the
        layer-dim-0-safe variant and marks the overlap path active."""
        from deepspeed_tpu.runtime.zero.overlap import layerwise_pspecs

        reason = None
        seg = None
        if not hasattr(self.module, "stream_segments"):
            reason = (f"model {type(self.module).__name__} exposes no "
                      "stream_segments (the per-layer contract the bucketed "
                      "schedule drives)")
        else:
            seg = self.module.stream_segments()
            if seg is None:
                reason = ("model declined segmenting (e.g. pipeline "
                          "parallelism owns the layer loop)")
        if reason is None:
            keys = set(params) if isinstance(params, dict) else set()
            if not {"embed", "layers", "final_norm"} <= keys or \
                    not keys <= {"embed", "layers", "final_norm", "lm_head",
                                 "lm_head_bias"}:
                reason = ("param tree is not the stacked embed/layers/head "
                          "layout the bucketed schedule slices")
        if reason is not None:
            self._overlap_reason = reason
            logger.warning(
                "zero_optimization.overlap_comm: %s — falling back to the "
                "GSPMD-placed collective schedule", reason)
            return
        self._overlap = True
        self._overlap_segments = seg
        if self.zero_stage == 3:
            self._param_specs = layerwise_pspecs(
                params, self.mesh, shard=True,
                persistence_threshold=persist,
                logical_specs=self._client_param_pspecs)
        log_dist(
            f"overlap_comm active: layer-chunked collective schedule, "
            f"bucket={self.config.zero_config.overlap_bucket_layers} "
            f"layer(s), zero stage {self.zero_stage} "
            f"(runtime/zero/overlap.py)", ranks=[0])

    def _apply_activation_checkpointing_config(self, model) -> None:
        """Push the ds_config ``activation_checkpointing`` section into the
        model (reference: runtime/activation_checkpointing/checkpointing.py
        ``configure()`` — there a global; here the engine owns the remat
        transform applied in the model forward)."""
        ac = self.config.activation_checkpointing
        mcfg = getattr(model, "config", None)
        if mcfg is None or not hasattr(mcfg, "remat"):
            return
        section_active = (ac.enabled is not None or ac.partition_activations
                          or ac.cpu_checkpointing)
        if ac.enabled is not None:
            mcfg.remat = ac.enabled
        elif section_active:
            # reference configs enable the subsystem via these knobs
            mcfg.remat = True
        # Only take over the policy when the config section is actually in
        # play; otherwise a model built with remat_policy="dots" would be
        # silently reset to the section's default.
        if section_active and hasattr(mcfg, "remat_policy"):
            # cpu_checkpointing: saved residuals page to pinned host memory
            # (the offloaded-dots policy) — overrides the plain policy knob
            if ac.cpu_checkpointing and ac.policy not in ("full",
                                                          "offload_dots"):
                logger.warning(
                    "activation_checkpointing: cpu_checkpointing overrides "
                    "policy=%r with 'offload_dots' (host-paged residuals); "
                    "drop cpu_checkpointing to keep the device-resident "
                    "policy", ac.policy)
            mcfg.remat_policy = ("offload_dots" if ac.cpu_checkpointing
                                 else ac.policy)

    def _setup_compression(self, model) -> None:
        """Wire the compression scheduler (reference compression/scheduler.py
        role): when the ds_config ``compression_training`` section enables a
        pruning method — or ``init_compression`` already attached one to the
        model — the engine consults the scheduler after each optimizer step,
        so ``schedule_offset`` activates without the caller threading
        global_step (VERDICT r4 item 8)."""
        from deepspeed_tpu.compression.compress import (CompressedParams,
                                                        CompressionScheduler)

        self._compression_sched = None
        comp = getattr(model, "_compression", None)
        if comp is None:
            sec = self.config.compression_training
            d = {"compression_training": {
                "sparse_pruning": sec.sparse_pruning,
                "row_pruning": sec.row_pruning,
                "head_pruning": sec.head_pruning,
                "channel_pruning": sec.channel_pruning,
                "weight_quantization": sec.weight_quantization,
                "layer_reduction": sec.layer_reduction}}
            probe = CompressedParams(
                d, num_heads=getattr(getattr(model, "config", None),
                                     "num_heads", None))
            if not probe.cfg.any_pruning:
                return
            comp = probe
            model._compression = comp
        if comp.num_heads is None:
            comp.num_heads = getattr(getattr(model, "config", None),
                                     "num_heads", None)
        if comp.cfg.any_pruning:
            self._compression_sched = CompressionScheduler(comp)
            log_dist("compression scheduler active: sparse=%s row=%s head=%s"
                     % (comp.cfg.sp_enabled, comp.cfg.rp_enabled,
                        comp.cfg.hp_enabled), ranks=[0])

    def _maybe_apply_compression(self) -> None:
        if self._compression_sched is None or self._state is None:
            return
        if getattr(self, "_param_offload", False):
            if not getattr(self, "_warned_comp_offload", False):
                self._warned_comp_offload = True
                logger.warning("compression scheduler skipped: params live "
                               "as host masters under param offload (prune "
                               "via redundancy_clean at export instead)")
            return
        new_params = self._compression_sched.after_step(
            self._state.params, self._host_steps)
        if new_params is not None:
            self._state = self._state._replace(params=new_params)

    def _apply_pipeline_config(self, model) -> None:
        """Push the ds_config ``pipeline`` section into the model: reference
        ``PipelineEngine`` knobs mapped to the SPMD pipeline —
        ``micro_batches`` (reference ``train_batch()`` microbatching) and
        ``schedule`` ("gpipe" fill-drain with autodiff, or "1f1b" — the
        reference TrainSchedule's in-flight-bounded fused schedule)."""
        sec = self.config.pipeline or {}
        mcfg = getattr(model, "config", None)
        if mcfg is None or not hasattr(mcfg, "pp_schedule"):
            if sec:
                logger.warning(
                    "ds_config pipeline section %s ignored: the model "
                    "carries no ModelConfig with pipeline knobs", sec)
            return
        if "micro_batches" in sec:
            mcfg.pp_microbatches = int(sec["micro_batches"])
        sched = sec.get("schedule")
        if sched is not None:
            if sched not in ("gpipe", "1f1b"):
                raise ValueError(
                    f"pipeline.schedule must be 'gpipe' or '1f1b', got "
                    f"{sched!r}")
            mcfg.pp_schedule = sched

    @property
    def state(self) -> Optional["TrainState"]:
        """Training state.  In streamed offload mode the pinned-host param
        copy refreshes lazily here — the hot loop trains from the numpy
        masters and never pays the full-model host->pinned copy per step;
        external readers (eval, checkpointing, fragments) always see the
        current weights."""
        if self._pinned_stale:
            self._pinned_stale = False
            self._state = self._state._replace(
                params=jax.device_put(self._np_params,
                                      self._param_shardings))
        return self._state

    @state.setter
    def state(self, value) -> None:
        self._state = value
        self._pinned_stale = False

    def _make_loss_fn(self, model) -> Callable:
        if hasattr(model, "apply"):  # flax module computing loss in __call__
            def loss_fn(params, batch, rng):
                kwargs = {"rngs": {"dropout": rng}}
                if isinstance(batch, (tuple, list)):
                    return model.apply(params, *batch, **kwargs)
                if isinstance(batch, dict):
                    return model.apply(params, **batch, **kwargs)
                return model.apply(params, batch, **kwargs)

            return loss_fn
        if callable(model):
            def loss_fn(params, batch, rng):
                return model(params, batch)

            return loss_fn
        raise TypeError(f"Unsupported model type {type(model)}")

    def _build_optimizer(self) -> None:
        if self.config.scheduler is not None:
            self._lr_schedule = get_lr_schedule(self.config.scheduler.type,
                                                self.config.scheduler.params)
        elif callable(self.client_lr_scheduler):
            self._lr_schedule = self.client_lr_scheduler
        if self._onebit:
            from deepspeed_tpu.runtime.fp16.onebit.adam import onebit_from_config

            waxes = ("dp", "fsdp", "ep")
            world = int(np.prod([self.mesh.shape.get(a, 1) for a in waxes]))
            self.optimizer = onebit_from_config(
                self.config.optimizer.type, dict(self.config.optimizer.params),
                world=world, axis_names=waxes)
            self.lr_scheduler = (LRSchedulerShim(self._lr_schedule)
                                 if self._lr_schedule is not None else None)
            return
        if self._offload:
            # The reference swaps in DeepSpeedCPUAdam when offload is active
            # (SURVEY.md §3.2 _configure_optimizer); the device-side
            # transformation is identity — all update math runs on host.
            import optax

            if self.client_optimizer is not None:
                logger.warning(
                    "offload_optimizer is enabled: the supplied client "
                    "optimizer (%s) is ignored; states will be stepped by "
                    "DeepSpeedCPUAdam on the host",
                    type(self.client_optimizer).__name__)
            opt_type = (self.config.optimizer.type if self.config.optimizer
                        else "AdamW").lower().replace("_", "").replace("-", "")
            if "adagrad" in opt_type:
                self._offload_opt_type = "adagrad"
            elif "lion" in opt_type:
                self._offload_opt_type = "lion"
            else:
                self._offload_opt_type = "adam"
                if "adam" not in opt_type:
                    logger.warning(
                        "offload_optimizer supports the Adam/Adagrad/Lion "
                        "families; %s config will be stepped by "
                        "DeepSpeedCPUAdam", opt_type)
            self.optimizer = optax.identity()
        elif self.client_optimizer is not None:
            self.optimizer = self.client_optimizer
            if self.config.zero_allow_untested_optimizer:
                log_dist("using client optimizer with ZeRO (zero_allow_untested_optimizer)",
                         ranks=[0])
        else:
            self.optimizer = opt_builder.build_from_config(self.config, self._lr_schedule)
        self.lr_scheduler = (LRSchedulerShim(self._lr_schedule)
                             if self._lr_schedule is not None else None)

    def _init_state_zeropp(self, params: Any) -> None:
        """ZeRO++ state: flat per-leaf fp32 shards over ``fsdp`` (+ hpZ
        secondary copy), optimizer state sharded alike.  See
        runtime/zero/zeropp.py for the layout and collectives."""
        from deepspeed_tpu.runtime.zero import zeropp as zpp

        mesh = self.mesh
        zc = self.config.zero_config
        cq = self.config.comm_quantization
        Pfsdp = self.mesh.shape.get("fsdp", 1)
        z = max(1, zc.zero_hpz_partition_size)
        # the comm_quantization sites are the comm-layer spellings of the
        # legacy ZeRO++ flags (same seam, documented precedence): either
        # alone turns the quantized transport on here — otherwise an
        # hpz-only ZeRO++ config would silently ignore the block
        q_weights = zc.zero_quantized_weights or cq.q_all_gather
        q_grads = zc.zero_quantized_gradients or cq.q_reduce_scatter
        if (q_weights != zc.zero_quantized_weights
                or q_grads != zc.zero_quantized_gradients):
            log_dist(f"ZeRO++ transport driven by comm_quantization: "
                     f"qw={q_weights} qg={q_grads}", ranks=[0])
        self._zpp_cfg = zpp.ZeroPPConfig(
            axis="fsdp", world=Pfsdp, hpz=z,
            q_weights=q_weights,
            q_grads=q_grads,
            compute_dtype=self.compute_dtype)
        self._zpp_shapes = jax.tree.map(lambda p: tuple(p.shape), params)
        self._zpp_lens = zpp.flatten_spec(self._zpp_shapes, Pfsdp)
        fsdp_sh = NamedSharding(mesh, P("fsdp"))
        scalar_sh = NamedSharding(mesh, P())
        lens = self._zpp_lens
        if (self.bfloat16_enabled and not self.config.bf16.master_weights) \
                or self.config.data_types.grad_accum_dtype is not None:
            logger.warning(
                "ZeRO++ path keeps fp32 primary shards and fp32 grad "
                "accumulators (ZeRO-3 master semantics); "
                "bf16.master_weights/data_types.grad_accum_dtype are "
                "ignored here")
        from deepspeed_tpu.runtime.zero.zeropp import flat_grads as _flatten

        primary = jax.jit(lambda pr: _flatten(pr, lens),
                          out_shardings=jax.tree.map(
                              lambda _: fsdp_sh, lens))(params)
        prim_spec = jax.tree.map(lambda _: P("fsdp"), lens)
        # non-quantized secondaries carry a scalar scale placeholder, which
        # must stay replicated (P()); quantized scales are per-block arrays
        secs_spec = jax.tree.map(
            lambda _: P("fsdp") if zc.zero_quantized_weights else P(), lens)
        if z > 1:
            import functools

            sec_fn = jax.jit(jax.shard_map(
                functools.partial(zpp.refresh_secondary, cfg=self._zpp_cfg),
                mesh=mesh, in_specs=(prim_spec,),
                out_specs=(prim_spec, secs_spec),
                axis_names={"dp", "fsdp", "ep"}, check_vma=False))
            sec_q, sec_s = sec_fn(primary)
        else:
            sec_q, sec_s = (), ()
        from deepspeed_tpu.runtime.zero.zeropp import ZeroPPParams

        self._zpp_state_param_specs = ZeroPPParams(
            primary=prim_spec,
            secondary_q=jax.tree.map(lambda _: P("fsdp"), lens) if z > 1 else (),
            secondary_s=secs_spec if z > 1 else ())
        zp = ZeroPPParams(primary=primary, secondary_q=sec_q, secondary_s=sec_s)
        self._param_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), self._zpp_state_param_specs,
            is_leaf=lambda x: isinstance(x, P))
        # Optimizer state is initialized on the LOCAL shards (inside
        # shard_map) and stored stacked over fsdp: optimizers whose state
        # layout depends on the leaf size (Adam8bit's [nb, block] int8
        # blocks) must see the same shapes at init and at update — a global
        # init would bake in the unsharded layout and crash the in-region
        # update.  For elementwise optimizers (optax Adam et al.) local
        # init + stacking is identical to sharding a global init.
        local_struct = jax.tree.map(
            lambda L: jax.ShapeDtypeStruct((L // Pfsdp,), jnp.float32), lens)
        opt_shapes = jax.eval_shape(self.optimizer.init, local_struct)
        opt_specs = jax.tree.map(
            lambda l: P() if getattr(l, "ndim", 0) == 0 else P("fsdp"),
            opt_shapes)
        self._zpp_opt_specs = opt_specs
        self._opt_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), opt_specs,
            is_leaf=lambda x: isinstance(x, P))
        prim_spec_tree = jax.tree.map(lambda _: P("fsdp"), lens)
        opt_state = jax.jit(jax.shard_map(
            self.optimizer.init, mesh=mesh, in_specs=(prim_spec_tree,),
            out_specs=opt_specs, check_vma=False))(primary)
        grad_acc = jax.jit(
            lambda pr: jax.tree.map(jnp.zeros_like, pr),
            out_shardings=jax.tree.map(lambda _: fsdp_sh, lens))(primary)
        self._acc_shardings = jax.tree.map(lambda _: fsdp_sh, lens)
        self.state = TrainState(params=zp, opt_state=opt_state,
                                grad_acc=grad_acc,
                                global_steps=jnp.zeros((), jnp.int32),
                                scaler=scaler_lib.make_state(self.config.fp16))
        self._compile_steps()
        n = tree_num_params(params)
        log_dist(f"engine ready (ZeRO++): {n/1e6:.2f}M params, "
                 f"qw={self._zpp_cfg.q_weights} qg={self._zpp_cfg.q_grads} "
                 f"hpz={self._zpp_cfg.hpz}, mesh {dict(self.mesh.shape)}",
                 ranks=[0])
        self._setup_state_telemetry(n)

    def _init_state(self, params: Any) -> None:
        """Build shardings for the full state and compile the step functions."""
        if (self._client_param_pspecs is None
                and self.mesh.shape.get("tp", 1) > 1):
            # model without logical_pspecs on a tp>1 mesh: generic AutoTP —
            # classify column/row splits by name analysis (reference
            # auto_tp.py role)
            from deepspeed_tpu.module_inject.auto_tp import autotp_pspecs

            self._client_param_pspecs = autotp_pspecs(params)
            log_dist("AutoTP: derived tp layout from param names "
                     "(no logical_pspecs on the model)", ranks=[0])
        if self._zeropp:
            return self._init_state_zeropp(params)
        mesh = self.mesh
        zcfg = self.config.zero_config
        persist = zcfg.stage3_param_persistence_threshold if self.zero_stage == 3 else 0

        self._param_specs = params_pspecs(params, mesh, shard=self.zero_stage == 3,
                                          persistence_threshold=persist,
                                          logical_specs=self._client_param_pspecs)
        if self._overlap_want:
            # may replace self._param_specs (stacked-layer dim 0 must stay
            # device-local for the bucketed schedule) and set self._overlap
            self._setup_overlap(params, persist)
        self._onebit_stacked = (self._onebit
                                and getattr(self.optimizer, "stacked_params", False))
        if self._onebit_stacked:
            # 0/1 Adam: replicas legitimately diverge between syncs, so
            # params carry an explicit [W] worker axis sharded over the data
            # axes (each device holds exactly its replica — same bytes as
            # replication)
            waxes = ("dp", "fsdp", "ep")
            self._param_specs = jax.tree.map(
                lambda s: P(waxes, *tuple(s)), self._param_specs)
        self._param_shardings = shardings_from_pspecs(self._param_specs, mesh)
        if self._onebit and hasattr(self.optimizer, "state_pspecs"):
            self._opt_specs = self.optimizer.state_pspecs(params,
                                                          ("dp", "fsdp", "ep"))
        elif self._onebit:
            self._opt_specs = self._onebit_opt_specs(params)
        else:
            opt_shapes = jax.eval_shape(self.optimizer.init, params)
            self._opt_specs = opt_state_pspecs(opt_shapes, mesh, shard=self.zero_stage >= 1)
        self._opt_shardings = shardings_from_pspecs(self._opt_specs, mesh)
        # Gradient accumulator: sharded from stage 2 up (reduce-scatter), or
        # like params under stage 3 (grads of sharded params are sharded).
        acc_shard = self.zero_stage >= 2
        if self._onebit or self._qcomm_grads:
            # per-worker LOCAL grad accumulators, stacked on a leading [W]
            # axis sharded over the data axes (each device holds exactly
            # its own running sum).  The 1-bit path needs this because its
            # compression is defined over local grads; the quantized
            # grad-all-reduce path needs it because the whole point is to
            # defer the reduction to the boundary and move int8 there —
            # note the ZeRO-2 sharded-accumulator memory saving is traded
            # away on this path (full-size local sums, like 1-bit).
            waxes = ("dp", "fsdp", "ep")
            self._acc_specs = jax.tree.map(
                lambda p: P(waxes, *([None] * getattr(p, "ndim", 0))), params)
            if self._qcomm_grads and self.zero_stage == 2:
                log_dist("comm_quantization.grad_all_reduce at ZeRO stage "
                         "2: gradients accumulate LOCALLY (full-size) and "
                         "reduce once per boundary — the stage-2 sharded-"
                         "accumulator memory saving is traded for int8 "
                         "boundary bytes", ranks=[0])
        elif self._overlap:
            # overlap schedule: stage 3 accumulates in EXACTLY the param
            # layout (each bucket's reduce-scatter is the gather's
            # transpose — the shard shapes must line up); stage 2 shards
            # with the same layer-dim-0 constraint; stage 1 replicates as
            # before
            from deepspeed_tpu.runtime.zero.overlap import layerwise_pspecs

            if self.zero_stage == 3:
                self._acc_specs = self._param_specs
            elif self.zero_stage == 2:
                self._acc_specs = layerwise_pspecs(
                    params, mesh, shard=True, persistence_threshold=0,
                    logical_specs=self._client_param_pspecs)
            else:
                self._acc_specs = params_pspecs(
                    params, mesh, shard=False,
                    logical_specs=self._client_param_pspecs)
        else:
            self._acc_specs = params_pspecs(params, mesh, shard=acc_shard,
                                            persistence_threshold=0 if acc_shard else persist,
                                            logical_specs=self._client_param_pspecs)
        self._acc_shardings = shardings_from_pspecs(self._acc_specs, mesh)
        if self._param_offload:
            if hasattr(self.module, "set_param_offload_specs"):
                self.module.set_param_offload_specs(self._param_specs)
            # params live in pinned host memory (streamed per-layer by the
            # model); gradients exit the program on device (XLA's SPMD
            # partitioner cannot yet emit host-placed outputs on multi-device
            # meshes) and are copied straight into numpy accumulators — the
            # only transient device-resident [model]-sized buffer is the grad
            # output at the program boundary.
            self._param_dev_shardings = self._param_shardings
            from deepspeed_tpu.accelerator.real_accelerator import \
                host_memory_kind
            hk = host_memory_kind()
            if hk is not None:
                if hk != "pinned_host":
                    # capability gate (ROADMAP): this backend has no pinned
                    # host memory space — commit the "host" masters to its
                    # host-side kind instead (on CPU that IS the default
                    # memory, so the placement is a no-op and the streamed
                    # offload machinery runs unchanged)
                    log_dist(f"ZeRO-Infinity: backend has no pinned_host "
                             f"memory kind; params placed in {hk!r} "
                             f"(gated fallback)", ranks=[0])
                self._param_shardings = jax.tree.map(
                    lambda s: NamedSharding(s.mesh, s.spec, memory_kind=hk),
                    self._param_shardings)
            else:  # pragma: no cover - clients without the memories API
                logger.warning(
                    "ZeRO-Infinity: backend exposes no memory-kind API; "
                    "params keep the default placement (no host tiering)")
            self._acc_specs = ()
            self._acc_shardings = ()
            self._host_grad_acc = None
        scalar_sh = NamedSharding(mesh, P())
        self._state_shardings = TrainState(
            params=self._param_shardings, opt_state=self._opt_shardings,
            grad_acc=self._acc_shardings, global_steps=scalar_sh,
            scaler=scaler_lib.LossScaleState(scalar_sh, scalar_sh, scalar_sh, scalar_sh))

        # Materialize state on-device, already sharded (zero.Init semantics:
        # nothing is ever resident unsharded).
        if self._offload:
            # Host takes the fp32 masters; the device keeps ONE compute-dtype
            # copy (bf16 halves resident param bytes, and no fp32
            # master/moments ever touch HBM — the ZeRO-Offload contract).
            self._build_offload_optimizer(params)
            cdtype = self.compute_dtype

            def to_compute(p):
                return jax.tree.map(
                    lambda x: x.astype(cdtype)
                    if jnp.issubdtype(x.dtype, jnp.floating) else x, p)

            if self._param_offload:
                # cast on device, then hop to pinned host outside jit (the
                # SPMD partitioner rejects host-placed jit outputs on
                # multi-device meshes)
                params = jax.jit(to_compute,
                                 out_shardings=self._param_dev_shardings)(params)
                params = jax.device_put(params, self._param_shardings)
            else:
                params = jax.jit(to_compute, out_shardings=self._param_shardings)(params)
        elif self._onebit_stacked:
            # must win over the master-free bf16 branch below: the stacked
            # specs/opt state are built for [W]-leading leaves, so the cast
            # (when bf16.master_weights=false) composes with the stacking
            W = self.optimizer.world
            master_free = (self.bfloat16_enabled
                           and not self.config.bf16.master_weights)
            if master_free:
                logger.warning(
                    "bf16.master_weights=false with optimizer %s: plain "
                    "round-to-nearest bf16 updates lose sub-ulp steps",
                    self.config.optimizer.type if self.config.optimizer else "?")

            def stack(x):
                if master_free and jnp.issubdtype(x.dtype, jnp.floating):
                    x = x.astype(jnp.bfloat16)
                return jnp.broadcast_to(x[None], (W,) + x.shape)

            params = jax.jit(lambda p: jax.tree.map(stack, p),
                             out_shardings=self._param_shardings)(params)
        elif self.bfloat16_enabled and not self.config.bf16.master_weights:
            # Master-free bf16: the persistent training state IS bf16 (no
            # fp32 master, no fp32 grads anywhere in the step program).
            # Requires an optimizer that rounds stochastically (Adam8bit);
            # round-to-nearest would drop sub-ulp updates and stall training.
            if not getattr(self.optimizer, "updates_are_new_params", False):
                logger.warning(
                    "bf16.master_weights=false with optimizer %s: plain "
                    "round-to-nearest bf16 updates lose sub-ulp steps; use "
                    "Adam8bit (stochastic rounding) for master-free training",
                    self.config.optimizer.type if self.config.optimizer else "?")
            params = jax.jit(
                lambda p: jax.tree.map(
                    lambda x: x.astype(jnp.bfloat16)
                    if jnp.issubdtype(x.dtype, jnp.floating) else x, p),
                out_shardings=self._param_shardings)(params)
        else:
            params = jax.jit(lambda p: p, out_shardings=self._param_shardings)(params)
        opt_state = jax.jit(self.optimizer.init, out_shardings=self._opt_shardings)(params)
        if self._param_offload:
            grad_acc = ()
        elif self._onebit or self._qcomm_grads:
            W = (self.optimizer.world if self._onebit
                 else comm.get_data_parallel_world_size(self.mesh))
            strip = 1 if self._onebit_stacked else 0
            grad_acc = jax.jit(
                lambda p: jax.tree.map(
                    lambda x: jnp.zeros((W,) + x.shape[strip:], jnp.float32), p),
                out_shardings=self._acc_shardings)(params)
        else:
            grad_acc = jax.jit(
                lambda p: jax.tree.map(lambda x: jnp.zeros(x.shape, self._acc_dtype(x.dtype)), p),
                out_shardings=self._acc_shardings)(params)
        self.state = TrainState(params=params, opt_state=opt_state, grad_acc=grad_acc,
                                global_steps=jnp.zeros((), jnp.int32),
                                scaler=scaler_lib.make_state(self.config.fp16))
        self._compile_steps()
        n = tree_num_params(params)
        log_dist(f"engine ready: {n/1e6:.2f}M params, zero stage {self.zero_stage}, "
                 f"dtype {self.compute_dtype.__name__}, mesh {dict(self.mesh.shape)}", ranks=[0])
        if self.zero_stage == 3:
            logger.info(describe_partitioning(params, self._param_specs))
        self._setup_state_telemetry(n)

    def _acc_dtype(self, param_dtype):
        # data_types.grad_accum_dtype (reference key): bf16 halves the
        # persistent accumulator; fp32 (default) is exact.  The 1-bit path
        # keeps fp32 (error feedback is defined over fp32 local grads).
        if self._onebit:
            return jnp.float32
        return self.config.grad_accum_dtype()

    def _onebit_opt_specs(self, params):
        """PartitionSpecs for OneBitState: moments/count replicated; the
        error-feedback buffers carry a leading per-worker axis."""
        from deepspeed_tpu.runtime.fp16.onebit.adam import OneBitState

        waxes = ("dp", "fsdp", "ep")
        rep = jax.tree.map(lambda p: P(), params)
        stacked = jax.tree.map(
            lambda p: P(waxes, *([None] * getattr(p, "ndim", 0))), params)
        serr = jax.tree.map(lambda p: P(waxes, None), params)
        return OneBitState(exp_avg=rep, exp_avg_sq=jax.tree.map(lambda p: P(), params),
                           error=stacked, server_error=serr, count=P())

    def _build_offload_optimizer(self, params) -> None:
        from deepspeed_tpu.runtime.zero.offload import OffloadedOptimizer
        from deepspeed_tpu.runtime.zero.streaming import RelayMeter

        # one ds_offload_* relay ledger per process; the streamed path's
        # ParamStreamer registers the same instruments (same registry keys)
        self._relay_meter = RelayMeter()

        p = dict(self.config.optimizer.params) if self.config.optimizer else {}
        betas = tuple(p.get("betas", (0.9, 0.999)))
        off = self.config.zero_config.offload_optimizer
        self._offload_opt = OffloadedOptimizer(
            jax.device_get(params),
            backend=self._offload_device,
            lr=p.get("lr", 1e-3), betas=betas, eps=p.get("eps", 1e-8),
            weight_decay=p.get("weight_decay", 0.0),
            adamw_mode=p.get("adam_w_mode", p.get("adamw_mode", True)),
            swap_dir=off.nvme_path, aio_config=self.config.aio,
            pipeline=off.pipeline_read,
            pipeline_write=off.pipeline_write,
            opt_type=getattr(self, "_offload_opt_type", "adam"),
            int8_masters=bool(getattr(off, "int8_masters", False)
                              and self._offload_device == "cpu"),
            quant_block=int(getattr(off, "quant_block", 256)))

    def lazy_init_from_batch(self, batch: Any) -> None:
        """zero.Init-equivalent: abstract-init then shard-on-create
        (reference: ``deepspeed.zero.Init`` module-interception,
        SURVEY.md §2.1 "zero.Init / partitioned params")."""
        if self.state is not None:
            return
        if not hasattr(self.module, "init"):
            raise ValueError("model has no .init(); pass model_parameters to initialize()")
        self._rng, init_rng = jax.random.split(self._rng)

        def init_fn(rng, b):
            if isinstance(b, (tuple, list)):
                return self.module.init(rng, *b)
            if isinstance(b, dict):
                # batch keys init doesn't take (e.g. loss_mask — an apply()
                # arg, irrelevant to param shapes) must not break a
                # first-call dict batch
                import inspect
                try:
                    sig = inspect.signature(self.module.init)
                    if not any(p.kind == p.VAR_KEYWORD
                               for p in sig.parameters.values()):
                        b = {k: v for k, v in b.items()
                             if k in sig.parameters}
                except (TypeError, ValueError):
                    pass
                return self.module.init(rng, **b)
            return self.module.init(rng, b)

        # Master-free bf16: fold the cast into the init program so the fp32
        # init values are per-buffer transients — the full fp32 tree (2x the
        # persistent params) never materializes.  At the 1.34B single-chip
        # rung that transient alone is ~5.4GB of the 15.75GB budget.
        master_free = (self.bfloat16_enabled
                       and not self.config.bf16.master_weights
                       and not self._offload)
        build_fn = init_fn
        if master_free:
            def build_fn(rng, b):
                return jax.tree.map(
                    lambda x: x.astype(jnp.bfloat16)
                    if jnp.issubdtype(x.dtype, jnp.floating) else x,
                    init_fn(rng, b))

        with phase("ds_setup_params"):
            abstract = jax.eval_shape(build_fn, init_rng, batch)
            zcfg = self.config.zero_config
            persist = zcfg.stage3_param_persistence_threshold if self.zero_stage == 3 else 0
            specs = params_pspecs(abstract, self.mesh, shard=self.zero_stage == 3,
                                  persistence_threshold=persist,
                                  logical_specs=self._client_param_pspecs)
            shardings = shardings_from_pspecs(specs, self.mesh)
            params = jax.jit(build_fn, out_shardings=shardings)(init_rng, batch)
            self._init_state(params)

    # ------------------------------------------------------------------
    # jitted step functions
    # ------------------------------------------------------------------
    def _compile_steps(self) -> None:
        self._flight.record("compile", what="train step functions",
                            zero_stage=self.zero_stage)
        # ledger: step-program (re)builds are `recompile`, not compute —
        # nested pushes (an elastic rescale recompiling mid-run) stack
        self._goodput.push("recompile")
        try:
            with phase("ds_setup_compile_steps"):
                self._compile_steps_inner()
        finally:
            self._goodput.pop()

    def _compile_steps_inner(self) -> None:
        self._anomaly_select = False   # set by the paths that compile the bound arg
        cfg = self.config
        gas = cfg.gradient_accumulation_steps
        compute_dtype = self.compute_dtype
        fp16 = self.fp16_enabled
        clip = cfg.gradient_clipping
        loss_fn = self._loss_fn
        fp16_cfg = cfg.fp16

        def cast_params(p):
            if compute_dtype == jnp.float32:
                return p
            return jax.tree.map(
                lambda x: x.astype(compute_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, p)

        def accum(state: TrainState, batch, rng):
            scale = state.scaler.scale if fp16 else jnp.float32(1.0)

            def scaled_loss_fn(params):
                loss = loss_fn(cast_params(params), batch, rng)
                return (loss.astype(jnp.float32) * scale) / gas, loss

            # named_scope: fwd/bwd ops carry this prefix in the xplane trace
            with jax.named_scope("ds_fwd_bwd"):
                grads, loss = jax.grad(scaled_loss_fn, has_aux=True)(state.params)
                new_acc = jax.tree.map(lambda a, g: a + g.astype(a.dtype),
                                       state.grad_acc, grads)
            return state._replace(grad_acc=new_acc), loss

        # bf16/fp32 anomaly containment: compile the step with an extra
        # traced `anomaly_bound` scalar and fold "grad norm non-finite or
        # above the bound" into the SAME branchless skip select fp16
        # overflow uses — the skipped step is a no-op on params/opt state
        # and does not advance global_steps.  Disabled (default): the
        # programs below are exactly the pre-anomaly forms.
        anomaly_on = self._anomaly is not None

        @jax.named_scope("ds_optimizer_step")
        def apply(state: TrainState, anomaly_bound):
            scale = state.scaler.scale if fp16 else jnp.float32(1.0)
            overflow = has_overflow(state.grad_acc) if fp16 else jnp.zeros((), bool)
            # No-op unscale when fp16 is off: dividing a bf16 accumulator by
            # an fp32 scalar would silently promote the whole grad tree to
            # fp32, materializing the O(model) buffer bf16 accumulation
            # exists to avoid.
            grads = (jax.tree.map(lambda g: g / scale, state.grad_acc)
                     if fp16 else state.grad_acc)
            if clip > 0:
                grads, gnorm = clip_grad_norm(grads, clip)
            else:
                gnorm = global_norm(grads)
            if anomaly_on:
                overflow = (overflow | ~jnp.isfinite(gnorm)
                            | (gnorm > anomaly_bound))
            updates, new_opt = self.optimizer.update(grads, state.opt_state, state.params)
            if getattr(self.optimizer, "updates_are_new_params", False):
                # adam8bit-style transformations return new params directly
                # (stochastic rounding cannot round-trip through a delta)
                new_params = updates
            else:
                import optax

                new_params = optax.apply_updates(state.params, updates)
            if fp16 or anomaly_on:
                sel = lambda new, old: jax.tree.map(
                    lambda a, b: jnp.where(overflow, b, a), new, old)
                new_params = sel(new_params, state.params)
                new_opt = sel(new_opt, state.opt_state)
            new_scaler = scaler_lib.update(
                state.scaler, overflow, dynamic=fp16 and fp16_cfg.dynamic_loss_scale,
                loss_scale_window=fp16_cfg.loss_scale_window,
                min_loss_scale=fp16_cfg.min_loss_scale, hysteresis=fp16_cfg.hysteresis)
            zero_acc = jax.tree.map(jnp.zeros_like, state.grad_acc)
            new_state = TrainState(
                params=new_params, opt_state=new_opt, grad_acc=zero_acc,
                global_steps=state.global_steps + (1 - overflow.astype(jnp.int32)),
                scaler=new_scaler)
            return new_state, gnorm, overflow

        def evaluate(params, batch, rng):
            return loss_fn(cast_params(params), batch, rng)

        def apply1(state: TrainState):
            # anomaly off: the bound arg is never read, so this compiles
            # to exactly the historical one-arg program
            return apply(state, None)

        def offload_prep(state: TrainState):
            """Device half of the offload step: unscale + clip; grads leave
            the device once, already final — in bf16 when the engine computes
            in bf16 (halves D2H traffic and feeds the csrc bf16g fast path)."""
            scale = state.scaler.scale if fp16 else jnp.float32(1.0)
            overflow = has_overflow(state.grad_acc) if fp16 else jnp.zeros((), bool)
            grads = (jax.tree.map(lambda g: g / scale, state.grad_acc)
                     if fp16 else state.grad_acc)
            if clip > 0:
                grads, gnorm = clip_grad_norm(grads, clip)
            else:
                gnorm = global_norm(grads)
            if compute_dtype == jnp.bfloat16:
                grads = jax.tree.map(
                    lambda g: g.astype(jnp.bfloat16)
                    if jnp.issubdtype(g.dtype, jnp.floating) else g, grads)
            return grads, gnorm, overflow

        def offload_commit(state: TrainState, overflow):
            new_scaler = scaler_lib.update(
                state.scaler, overflow, dynamic=fp16 and fp16_cfg.dynamic_loss_scale,
                loss_scale_window=fp16_cfg.loss_scale_window,
                min_loss_scale=fp16_cfg.min_loss_scale, hysteresis=fp16_cfg.hysteresis)
            return (jax.tree.map(jnp.zeros_like, state.grad_acc),
                    state.global_steps + (1 - overflow.astype(jnp.int32)),
                    new_scaler)

        def fused(state: TrainState, batches, rng, anomaly_bound):
            """Full optimizer step in ONE XLA program: scan the gas
            micro-batches (grad accumulation), then apply the update.  One
            host dispatch instead of gas+1, and a single program lets XLA
            overlap the update's collectives with the last microbatch's
            compute."""
            rngs = jax.random.split(rng, gas)

            def micro(st, xs):
                b, r = xs
                st, loss = accum(st, b, r)
                return st, loss

            state, losses = jax.lax.scan(micro, state, (batches, rngs))
            state, gnorm, overflow = apply(state, anomaly_bound)
            return state, losses.mean(), gnorm, overflow

        def fused1(state: TrainState, batches, rng):
            return fused(state, batches, rng, None)

        if self._zeropp:
            self._compile_zeropp_steps(loss_fn, gas)
            return
        sh = self._state_shardings
        bs = batch_sharding(self.mesh)
        scalar = NamedSharding(self.mesh, P())
        self._fused_fn = None
        if self._param_offload:
            # Params in pinned host memory; grads land host-resident with the
            # same layout (no device [model]-sized buffers).  Accumulation
            # happens in numpy; the host optimizer consumes it directly.
            def fwdbwd(params, batch, rng):
                def f(p):
                    return loss_fn(cast_params(p), batch, rng).astype(jnp.float32) / gas

                loss, grads = jax.value_and_grad(f)(params)
                return loss * gas, grads

            # No explicit in/out shardings: params arrive committed to pinned
            # host; grads/loss default to device.  Forcing placements here
            # makes jax emit sharding-less annotate_device_placement custom
            # calls that the SPMD partitioner rejects on multi-device meshes.
            self._pofwdbwd_fn = jax.jit(fwdbwd)
            self._accum_fn = None
            self._apply_fn = None
            self._eval_fn = jax.jit(evaluate)
            self._build_streamed_fwdbwd(gas)
            return
        if self._onebit:
            self._compile_onebit_steps(loss_fn, cast_params, gas)
            if not self._onebit_stacked:  # stacked eval is set under shard_map
                self._eval_fn = jax.jit(
                    evaluate, in_shardings=(self._param_shardings, None, None),
                    out_shardings=scalar)
            return
        if self._overlap:
            self._compile_overlap_steps(apply if anomaly_on else apply1,
                                        evaluate, gas, anomaly_on)
            return
        if self._qcomm_grads:
            self._compile_qcomm_steps(loss_fn, cast_params, evaluate, gas,
                                      anomaly_on)
            return
        self._accum_fn = jax.jit(accum, donate_argnums=(0,), in_shardings=(sh, None, None),
                                 out_shardings=(sh, NamedSharding(self.mesh, P())))
        self._anomaly_select = anomaly_on and not self._offload
        if not self._offload:
            if anomaly_on:
                self._fused_fn = jax.jit(
                    fused, donate_argnums=(0,),
                    in_shardings=(sh, None, None, None),
                    out_shardings=(sh, scalar, scalar, scalar))
            else:
                self._fused_fn = jax.jit(
                    fused1, donate_argnums=(0,), in_shardings=(sh, None, None),
                    out_shardings=(sh, scalar, scalar, scalar))
        if self._offload:
            self._offload_prep_fn = jax.jit(offload_prep, in_shardings=(sh,))
            self._offload_commit_fn = jax.jit(
                offload_commit, in_shardings=(sh, None),
                out_shardings=(sh.grad_acc, NamedSharding(self.mesh, P()), sh.scaler))
            self._apply_fn = None
        else:
            if anomaly_on:
                self._apply_fn = jax.jit(
                    apply, donate_argnums=(0,), in_shardings=(sh, None),
                    out_shardings=(sh, NamedSharding(self.mesh, P()),
                                   NamedSharding(self.mesh, P())))
            else:
                self._apply_fn = jax.jit(
                    apply1, donate_argnums=(0,), in_shardings=(sh,),
                    out_shardings=(sh, NamedSharding(self.mesh, P()),
                                   NamedSharding(self.mesh, P())))
        self._eval_fn = jax.jit(evaluate, in_shardings=(self._param_shardings, None, None),
                                out_shardings=NamedSharding(self.mesh, P()))

    def _compile_overlap_steps(self, apply, evaluate, gas,
                               anomaly_on: bool = False) -> None:
        """Accum (and the fused step's micro scan) under full-manual
        ``shard_map`` with the layer-bucketed explicit collective schedule
        (runtime/zero/overlap.py).  The boundary ``apply`` and ``evaluate``
        stay on the GSPMD path — the overlap tentpole targets the per-micro
        collectives; state layout differs from the GSPMD path only in the
        stacked-layer dim-0 constraint, so checkpointing/eval reshard
        transparently."""
        import functools

        from deepspeed_tpu.runtime.zero.overlap import (OverlapSchedule,
                                                        QCommOpts)

        mesh = self.mesh
        mcfg = getattr(self.module, "config", None)
        cq = self.config.comm_quantization
        qcomm = QCommOpts(all_gather=cq.q_all_gather and self.zero_stage == 3,
                          reduce_scatter=cq.q_reduce_scatter
                          and self.zero_stage >= 2,
                          block=cq.block)
        if qcomm.all_gather or qcomm.reduce_scatter:
            log_dist(
                f"comm_quantization on the overlap schedule: "
                f"gathers={'int8' if qcomm.all_gather else 'dense'}, "
                f"reduce-scatters="
                f"{'int8' if qcomm.reduce_scatter else 'dense'} "
                f"(block {qcomm.block})", ranks=[0])
        self._overlap_sched = OverlapSchedule(
            segments=self._overlap_segments,
            params=self._state.params,
            param_specs=self._param_specs,
            acc_specs=self._acc_specs,
            mesh=mesh,
            zero_stage=self.zero_stage,
            compute_dtype=self.compute_dtype,
            bucket_layers=self.config.zero_config.overlap_bucket_layers,
            use_dropout=True,
            # stage 3 ALWAYS remats the layer buckets (the backward must
            # re-gather instead of holding gathered params as residuals —
            # the ZeRO-3 memory contract); stages 1/2 follow the model's
            # activation-checkpointing choice
            remat=(self.zero_stage == 3 or bool(getattr(mcfg, "remat",
                                                        False))),
            qcomm=qcomm)
        state_specs = TrainState(
            params=self._param_specs, opt_state=self._opt_specs,
            grad_acc=self._acc_specs, global_steps=P(),
            scaler=scaler_lib.LossScaleState(P(), P(), P(), P()))
        bspec = P(("dp", "fsdp", "ep"))
        accum_local = self._overlap_sched.make_accum(gas, self.fp16_enabled)
        sm = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
        sm_accum = sm(accum_local, in_specs=(state_specs, bspec, P()),
                      out_specs=(state_specs, P()))
        self._accum_fn = jax.jit(sm_accum, donate_argnums=(0,))
        sh = self._state_shardings
        scalar = NamedSharding(mesh, P())

        self._anomaly_select = anomaly_on

        def fused(state: TrainState, batches, rng, *anomaly_bound):
            # *anomaly_bound: one traced scalar when the anomaly select is
            # compiled in, empty otherwise — `apply` arrives 2-arg or
            # 1-arg to match (see _compile_steps)
            rngs = jax.random.split(rng, gas)

            def micro(st, xs):
                b, r = xs
                st, loss = sm_accum(st, b, r)
                return st, loss

            state, losses = jax.lax.scan(micro, state, (batches, rngs))
            state, gnorm, overflow = apply(state, *anomaly_bound)
            return state, losses.mean(), gnorm, overflow

        extra = (None,) if anomaly_on else ()
        self._fused_fn = jax.jit(
            fused, donate_argnums=(0,),
            in_shardings=(sh, None, None) + extra,
            out_shardings=(sh, scalar, scalar, scalar))
        self._apply_fn = jax.jit(apply, donate_argnums=(0,),
                                 in_shardings=(sh,) + extra,
                                 out_shardings=(sh, scalar, scalar))
        self._eval_fn = jax.jit(
            evaluate, in_shardings=(self._param_shardings, None, None),
            out_shardings=scalar)

    def _compile_zeropp_steps(self, loss_fn, gas) -> None:
        """Accum/apply/fused under full-manual shard_map over the data axes
        with ZeRO++ collectives: params gathered per micro-batch (int8 when
        ``zero_quantized_weights``; subgroup-only under hpZ), grads
        reduce-scattered (int8 qgZ when ``zero_quantized_gradients``), and
        the hpZ secondary refreshed once per boundary."""
        import functools

        from deepspeed_tpu.runtime.zero import zeropp as zpp
        from deepspeed_tpu.runtime.zero.zeropp import ZeroPPParams

        mesh = self.mesh
        cfg = self._zpp_cfg
        shapes = self._zpp_shapes
        lens = self._zpp_lens
        clip = self.config.gradient_clipping
        waxes = ("dp", "fsdp", "ep")
        optimizer = self.optimizer
        new_params_opt = getattr(optimizer, "updates_are_new_params", False)
        prim_spec = jax.tree.map(lambda _: P("fsdp"), lens)
        opt_specs = self._zpp_opt_specs
        state_specs = TrainState(
            params=self._zpp_state_param_specs, opt_state=opt_specs,
            grad_acc=prim_spec, global_steps=P(),
            scaler=scaler_lib.LossScaleState(P(), P(), P(), P()))
        bspec = P(waxes)

        def accum_local(state: TrainState, batch, rng):
            full = zpp.gather_param_tree(state.params, cfg, shapes)

            def f(pt):
                return loss_fn(pt, batch, rng).astype(jnp.float32) / gas

            loss, g_full = jax.value_and_grad(f)(full)
            gflat = zpp.flat_grads(g_full, lens)

            def rs(gl):
                # reduce_scatter SUMS over fsdp; the engine contract is the
                # GLOBAL-batch mean gradient (each worker's loss is a mean
                # over its local shard), so divide by the fsdp extent and
                # pmean the remaining data axes.
                shard = zpp.reduce_scatter_flat(gl, cfg.axis, cfg.q_grads,
                                                cfg.block)
                return jax.lax.pmean(shard / cfg.world, ("dp", "ep"))

            gshard = jax.tree.map(rs, gflat)
            new_acc = jax.tree.map(lambda a, g: a + g, state.grad_acc, gshard)
            return (state._replace(grad_acc=new_acc),
                    jax.lax.pmean(loss * gas, waxes))

        def apply_local(state: TrainState):
            grads = state.grad_acc
            sumsq = sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads))
            gnorm = jnp.sqrt(jax.lax.psum(sumsq, cfg.axis))
            if clip > 0:
                scale = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                grads = jax.tree.map(lambda g: g * scale, grads)
            prim = state.params.primary
            updates, new_opt = optimizer.update(grads, state.opt_state, prim)
            if new_params_opt:
                new_prim = updates
            else:
                import optax

                new_prim = optax.apply_updates(prim, updates)
            if cfg.hpz > 1:
                sec_q, sec_s = zpp.refresh_secondary(new_prim, cfg)
            else:
                sec_q, sec_s = (), ()
            zero_acc = jax.tree.map(jnp.zeros_like, state.grad_acc)
            new_state = state._replace(
                params=ZeroPPParams(new_prim, sec_q, sec_s),
                opt_state=new_opt, grad_acc=zero_acc,
                global_steps=state.global_steps + 1)
            return new_state, gnorm, jnp.zeros((), bool)

        def fused_local(state: TrainState, batches, rng):
            rngs = jax.random.split(rng, gas)

            def micro(st, xs):
                b, r = xs
                st, loss = accum_local(st, b, r)
                return st, loss

            state, losses = jax.lax.scan(micro, state, (batches, rngs))
            state, gnorm, overflow = apply_local(state)
            return state, losses.mean(), gnorm, overflow

        def eval_local(zp_params, batch, rng):
            full = zpp.gather_param_tree(zp_params, cfg, shapes)
            return jax.lax.pmean(loss_fn(full, batch, rng), waxes)

        sm = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
        self._accum_fn = jax.jit(
            sm(accum_local, in_specs=(state_specs, bspec, P()),
               out_specs=(state_specs, P())), donate_argnums=(0,))
        self._apply_fn = jax.jit(
            sm(apply_local, in_specs=(state_specs,),
               out_specs=(state_specs, P(), P())), donate_argnums=(0,))
        self._fused_fn = jax.jit(
            sm(fused_local, in_specs=(state_specs, P(None, waxes), P()),
               out_specs=(state_specs, P(), P(), P())), donate_argnums=(0,))
        self._eval_fn = jax.jit(
            sm(eval_local, in_specs=(self._zpp_state_param_specs, bspec, P()),
               out_specs=P()))

    def _compile_qcomm_steps(self, loss_fn, cast_params, evaluate, gas,
                             anomaly_on: bool) -> None:
        """ZeRO stage 0/1/2 with the comm-layer quantized gradient sync
        (``comm_quantization.grad_all_reduce``; comm/collectives_q.py).

        Accum runs under full-manual ``shard_map`` over the data axes with
        LOCAL gradients (the 1-bit skeleton: every worker keeps its own
        running sum, stacked on the [W] axis) — no implicit GSPMD psum
        ever moves dense grad bytes.  The boundary apply reduces the
        accumulated tree ONCE through :func:`collectives_q.q_all_reduce`
        (int8 codes + fp32 block scales, fp32 reduce after dequant) and
        then runs the standard update under GSPMD.  Quantizing once per
        boundary (not per micro) is both cheaper and kinder to the
        error-feedback residual, which is carried as ENGINE state
        (``self._qcomm_residual``) — donated into and returned from every
        boundary program, reset to zero on (re)compile and on checkpoint
        load (it is transient sync state, not part of the model; a resume
        restarts it at zero, documented in docs/OBSERVABILITY.md).

        The anomaly-detection in-program skip select composes here
        exactly as on the standard path (the ZeRO++/1-bit refuse-to-arm
        list is unchanged — this path is neither)."""
        import functools

        from deepspeed_tpu.comm import collectives_q as cqt

        mesh = self.mesh
        waxes = ("dp", "fsdp", "ep")
        active_axes = tuple(a for a in waxes
                            if mesh.shape.get(a, 1) > 1)
        cq = self.config.comm_quantization
        block = int(cq.block)
        ef = bool(cq.error_feedback)
        clip = self.config.gradient_clipping
        optimizer = self.optimizer
        new_params_opt = getattr(optimizer, "updates_are_new_params", False)
        fp16_cfg = self.config.fp16

        state_specs = TrainState(
            params=jax.tree.map(lambda s: s.spec, self._param_shardings),
            opt_state=self._opt_specs,
            grad_acc=self._acc_specs,
            global_steps=P(),
            scaler=scaler_lib.LossScaleState(P(), P(), P(), P()))
        bspec = P(waxes)

        def accum_local(state: TrainState, batch, rng):
            # twin of _compile_onebit_steps.accum_local (minus the [W]
            # replica stacking): a fix to the local-grad skeleton here
            # almost certainly applies there too
            def f(p):
                return loss_fn(cast_params(p), batch,
                               rng).astype(jnp.float32) / gas

            loss, grads = jax.value_and_grad(f)(state.params)
            new_acc = jax.tree.map(lambda a, g: a + g[None].astype(a.dtype),
                                   state.grad_acc, grads)
            return (state._replace(grad_acc=new_acc),
                    jax.lax.pmean(loss * gas, waxes))

        def qsync_local(acc, res=None):
            """[W]-stacked local sums -> globally-reduced MEAN grads
            (replicated) (+ the new residual when error feedback is on),
            via int8 q_all_reduce."""
            leaves, treedef = jax.tree_util.tree_flatten(acc)
            res_leaves = (jax.tree_util.tree_leaves(res) if ef
                          else [None] * len(leaves))
            outs, new_res = [], []
            for a, r in zip(leaves, res_leaves):
                o, nr = cqt.q_all_reduce(
                    a[0], active_axes, block=block,
                    residual=(r[0] if ef else None), mean=True)
                outs.append(o)
                new_res.append(nr[None] if nr is not None else None)
            reduced = jax.tree_util.tree_unflatten(treedef, outs)
            if not ef:
                return reduced
            return reduced, jax.tree_util.tree_unflatten(treedef, new_res)

        sm = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
        acc_specs = self._acc_specs
        reduced_specs = jax.tree.map(lambda _: P(), acc_specs)
        if ef:
            qsync = sm(qsync_local, in_specs=(acc_specs, acc_specs),
                       out_specs=(reduced_specs, acc_specs))
        else:
            # no residual program state at all with error feedback off:
            # a full-model fp32 tree donated through every boundary for
            # nothing would be pure wasted HBM + dispatch traffic
            qsync = sm(qsync_local, in_specs=(acc_specs,),
                       out_specs=reduced_specs)

        @jax.named_scope("ds_optimizer_step")
        def apply_q(state: TrainState, residual, *anomaly_bound):
            if ef:
                grads, new_res = qsync(state.grad_acc, residual)
            else:
                grads = qsync(state.grad_acc)
                new_res = None
            if clip > 0:
                grads, gnorm = clip_grad_norm(grads, clip)
            else:
                gnorm = global_norm(grads)
            overflow = jnp.zeros((), bool)
            if anomaly_on:
                overflow = (overflow | ~jnp.isfinite(gnorm)
                            | (gnorm > anomaly_bound[0]))
            updates, new_opt = optimizer.update(grads, state.opt_state,
                                                state.params)
            if new_params_opt:
                new_params = updates
            else:
                import optax

                new_params = optax.apply_updates(state.params, updates)
            if anomaly_on:
                sel = lambda new, old: jax.tree.map(
                    lambda a, b: jnp.where(overflow, b, a), new, old)
                new_params = sel(new_params, state.params)
                new_opt = sel(new_opt, state.opt_state)
                if ef:
                    # the residual must roll back WITH the step: it was
                    # computed from the rejected gradients, so carrying
                    # it would leak ~1/254 of them into the next boundary
                    # — and a non-finite gradient would poison the carry
                    # FOREVER (every later comp = grads + NaN),
                    # defeating the skip
                    new_res = sel(new_res, residual)
            new_scaler = scaler_lib.update(
                state.scaler, overflow, dynamic=False,
                loss_scale_window=fp16_cfg.loss_scale_window,
                min_loss_scale=fp16_cfg.min_loss_scale,
                hysteresis=fp16_cfg.hysteresis)
            zero_acc = jax.tree.map(jnp.zeros_like, state.grad_acc)
            new_state = TrainState(
                params=new_params, opt_state=new_opt, grad_acc=zero_acc,
                global_steps=state.global_steps
                + (1 - overflow.astype(jnp.int32)),
                scaler=new_scaler)
            out = (new_state, gnorm, overflow)
            return out + ((new_res,) if ef else ())

        def fused(state: TrainState, residual, batches, rng,
                  *anomaly_bound):
            rngs = jax.random.split(rng, gas)

            def micro(st, xs):
                b, r = xs
                st, loss = sm_accum(st, b, r)
                return st, loss

            state, losses = jax.lax.scan(micro, state, (batches, rngs))
            out = apply_q(state, residual, *anomaly_bound)
            return (out[0], losses.mean()) + out[1:]

        sm_accum = sm(accum_local, in_specs=(state_specs, bspec, P()),
                      out_specs=(state_specs, P()))
        self._accum_fn = jax.jit(sm_accum, donate_argnums=(0,))
        sh = self._state_shardings
        scalar = NamedSharding(mesh, P())
        res_sh = sh.grad_acc
        extra = (None,) if anomaly_on else ()
        res_tail = (res_sh,) if ef else ()
        if ef:
            apply_jit = jax.jit(
                apply_q, donate_argnums=(0, 1),
                in_shardings=(sh, res_sh) + extra,
                out_shardings=(sh, scalar, scalar) + res_tail)
            fused_jit = jax.jit(
                fused, donate_argnums=(0, 1),
                in_shardings=(sh, res_sh, None, None) + extra,
                out_shardings=(sh, scalar, scalar, scalar) + res_tail)
            acc_shapes = jax.tree.map(lambda a: tuple(a.shape),
                                      self.state.grad_acc)
            res_zeros = jax.jit(
                lambda: jax.tree.map(
                    lambda shp: jnp.zeros(shp, jnp.float32), acc_shapes,
                    is_leaf=lambda x: isinstance(x, tuple)),
                out_shardings=res_sh)
        else:
            # ef off: no residual program state at all — the jits take
            # and return only the TrainState tuple
            apply_jit = jax.jit(
                lambda state, *b: apply_q(state, None, *b),
                donate_argnums=(0,), in_shardings=(sh,) + extra,
                out_shardings=(sh, scalar, scalar))
            fused_jit = jax.jit(
                lambda state, batches, rng, *b: fused(state, None,
                                                      batches, rng, *b),
                donate_argnums=(0,), in_shardings=(sh, None, None) + extra,
                out_shardings=(sh, scalar, scalar, scalar))
            res_zeros = None
        self._qcomm_residual = None
        self._qcomm_apply_jit = apply_jit

        def _residual():
            if self._qcomm_residual is None:
                self._qcomm_residual = res_zeros()
            return self._qcomm_residual

        def _apply(state, *bound):
            if ef:
                st, gnorm, overflow, res = apply_jit(state, _residual(),
                                                     *bound)
                self._qcomm_residual = res
            else:
                st, gnorm, overflow = apply_jit(state, *bound)
            return st, gnorm, overflow

        def _fused(state, batches, rng, *bound):
            if ef:
                st, loss, gnorm, overflow, res = fused_jit(
                    state, _residual(), batches, rng, *bound)
                self._qcomm_residual = res
            else:
                st, loss, gnorm, overflow = fused_jit(state, batches,
                                                      rng, *bound)
            return st, loss, gnorm, overflow

        self._apply_fn = _apply
        self._fused_fn = _fused
        self._anomaly_select = anomaly_on
        self._eval_fn = jax.jit(
            evaluate, in_shardings=(self._param_shardings, None, None),
            out_shardings=scalar)

    def _compile_onebit_steps(self, loss_fn, cast_params, gas) -> None:
        """Accum/apply under full-manual shard_map over the data axes: each
        worker keeps LOCAL gradients (no implicit psum), which is what the
        1-bit compression algorithm is defined over (reference:
        fp16/onebit/adam.py + runtime/comm/nccl.py)."""
        import functools

        mesh = self.mesh
        waxes = ("dp", "fsdp", "ep")
        onebit = self.optimizer
        lr_schedule = self._lr_schedule
        base_lr = (self.config.optimizer.params.get("lr", 1e-3)
                   if self.config.optimizer else 1e-3)
        state_specs = TrainState(
            params=jax.tree.map(lambda s: s.spec, self._param_shardings),
            opt_state=self._opt_specs,
            grad_acc=self._acc_specs,
            global_steps=P(),
            scaler=scaler_lib.LossScaleState(P(), P(), P(), P()))
        bspec = P(waxes)
        stacked = self._onebit_stacked

        def local_view(params):
            """This worker's replica (0/1 Adam stacks replicas on [W])."""
            return (jax.tree.map(lambda p: p[0], params) if stacked
                    else params)

        def accum_local(state: TrainState, batch, rng):
            def f(p):
                return loss_fn(cast_params(local_view(p)), batch,
                               rng).astype(jnp.float32) / gas

            loss, grads = jax.value_and_grad(f)(state.params)
            if stacked:  # grads arrive [1, ...]: already the worker slice
                grads = jax.tree.map(lambda g: g[0], grads)
            new_acc = jax.tree.map(lambda a, g: a + g[None].astype(a.dtype),
                                   state.grad_acc, grads)
            return (state._replace(grad_acc=new_acc),
                    jax.lax.pmean(loss * gas, waxes))

        def apply_local(state: TrainState):
            g_local = jax.tree.map(lambda a: a[0], state.grad_acc)
            lr = lr_schedule(state.opt_state.count) if lr_schedule else base_lr
            new_params, new_opt = onebit.update_local(
                g_local, state.opt_state, state.params, lr=lr)
            zero_acc = jax.tree.map(jnp.zeros_like, state.grad_acc)
            new_state = state._replace(params=new_params, opt_state=new_opt,
                                       grad_acc=zero_acc,
                                       global_steps=state.global_steps + 1)
            # grad-norm reporting: norm of the averaged local grads
            gnorm = global_norm(jax.tree.map(
                lambda g: jax.lax.pmean(g.astype(jnp.float32), waxes), g_local))
            return new_state, gnorm, jnp.zeros((), bool)

        sm = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
        self._accum_fn = jax.jit(
            sm(accum_local, in_specs=(state_specs, bspec, P()),
               out_specs=(state_specs, P())),
            donate_argnums=(0,))
        self._apply_fn = jax.jit(
            sm(apply_local, in_specs=(state_specs,),
               out_specs=(state_specs, P(), P())),
            donate_argnums=(0,))
        self._fused_fn = None
        if stacked:
            # eval must also slice each worker's replica; between syncs the
            # replicas differ, so the per-worker losses are averaged
            def eval_local(params, batch, rng):
                return jax.lax.pmean(
                    loss_fn(cast_params(local_view(params)), batch, rng)
                    .astype(jnp.float32), waxes)

            self._eval_fn = jax.jit(
                sm(eval_local, in_specs=(state_specs.params, bspec, P()),
                   out_specs=P()))

    # ------------------------------------------------------------------
    # training-side telemetry (docs/OBSERVABILITY.md)
    # ------------------------------------------------------------------
    def _setup_state_telemetry(self, n_params: int) -> None:
        """Once per state init: the static FLOPs estimator (model config),
        the analytic GSPMD comm plan, and the measured ZeRO shard-group
        memory breakdown.  Failures here must never break training."""
        mcfg = getattr(self.module, "config", None)
        L = getattr(mcfg, "num_layers", 0) or 0
        D = getattr(mcfg, "hidden_size", 0) or 0
        if L and D and n_params:
            self._flops_per_step_fn = (
                lambda tokens, seq, n=n_params, L=L, D=D:
                tokens * lm_flops_per_token(n, L, D, seq))
        # the qcomm grad path's explicit manual collectives record
        # themselves (trace-time q/dense twins) — an analytic GSPMD plan
        # on top would double-count the sync it replaced
        if not (self._zeropp or self._onebit or self._param_offload
                or self._qcomm_grads):
            try:
                plan = _build_comm_plan(
                    self.state.params, self._param_specs, self._acc_specs,
                    self.mesh, self.zero_stage, self.compute_dtype,
                    self._acc_dtype(jnp.float32),
                    overlap_sched=self._overlap_sched)
                if self._offload:
                    # the host optimizer step replaces the boundary
                    # gather with per-leaf device_puts — not a collective
                    plan["boundary"] = []
                self._comm_plan = plan if (plan["micro"] or plan["boundary"]) \
                    else None
            except Exception as exc:
                logger.warning("telemetry: comm plan unavailable (%s)", exc)
        # overlap-schedule gauges (docs/OBSERVABILITY.md "Overlap"):
        # bucket count is static truth; the hidden-comm estimate starts at
        # zero and is backfilled with the measured comm∩compute time by
        # every device-trace capture (profiling/device_trace.py)
        try:
            from deepspeed_tpu.profiling.device_trace import OVERLAP_GAUGES

            reg = get_registry()
            n_buckets = (len(self._overlap_sched.bucket_infos())
                         if self._overlap_sched is not None else 0)
            for name, help_ in OVERLAP_GAUGES.items():
                reg.gauge(name, help_)
            reg.gauge("ds_overlap_buckets").set(n_buckets)
            reg.gauge("ds_overlap_hidden_comm_seconds_est").set(0.0)
            if self._overlap_sched is not None and get_registry().enabled:
                log_dist(
                    f"overlap_comm: {n_buckets} buckets, analytic hideable "
                    f"comm fraction "
                    f"{self._overlap_sched.hideable_comm_fraction():.2f}",
                    ranks=[0])
        except Exception as exc:
            logger.warning("telemetry: overlap gauges unavailable (%s)", exc)
        if get_registry().enabled:
            try:
                st = self.state
                pb = device_resident_bytes(st.params)
                gb = device_resident_bytes(st.grad_acc)
                ob = device_resident_bytes(st.opt_state)
                self._mem_telemetry.set_state_bytes(pb, gb, ob)
                log_dist(
                    f"ZeRO stage {self.zero_stage} per-device state bytes: "
                    f"params={pb/1e6:.2f}MB grads={gb/1e6:.2f}MB "
                    f"optimizer={ob/1e6:.2f}MB "
                    f"(mesh {dict(self.mesh.shape)})", ranks=[0])
                self._mem_telemetry.sample()
            except Exception as exc:
                logger.warning("telemetry: state-bytes breakdown "
                               "unavailable (%s)", exc)

    def _micro_telemetry(self, batch) -> None:
        """Per-micro-batch accounting: FLOPs accrual for the MFU gauge and
        a flight-recorder breadcrumb.  One branch each while disabled."""
        if self._timeline.enabled:
            self._timeline.micro(self._host_steps + 1,
                                 self._micro_count + 1,
                                 time.perf_counter())
        if self._flight.enabled:
            self._flight.record("micro_end", step=self._host_steps + 1,
                                micro=self._micro_count + 1)
        if self._flops_per_step_fn is not None and get_registry().enabled:
            for leaf in jax.tree_util.tree_leaves(batch):
                shape = getattr(leaf, "shape", ())
                if len(shape) >= 2:
                    self._flops_since_boundary += self._flops_per_step_fn(
                        int(shape[0]) * int(shape[1]), int(shape[1]))
                    break
        if self._goodput.enabled:
            for leaf in jax.tree_util.tree_leaves(batch):
                shape = getattr(leaf, "shape", ())
                if len(shape) >= 2:    # [micro, seq, ...] -> tokens
                    self._goodput.add_tokens(int(shape[0]) * int(shape[1]))
                    break

    def _boundary_telemetry(self) -> None:
        """Optimizer-boundary accounting: MFU/TFLOPS gauges off the
        boundary-to-boundary wall clock (anchored on the step's loss
        output — dispatch is async, so the meter blocks on it before
        reading the clock; telemetry users pay that boundary bubble, the
        ``wall_clock_breakdown`` trade), and an HBM sample."""
        flops = self._flops_since_boundary
        self._flops_since_boundary = 0.0
        if self._timeline.enabled:
            # close the step span BEFORE the registry gate: the timeline
            # has its own switch (enable() keys off the same config, but
            # a bench-hygiene registry.reset() must not truncate it)
            self._timeline.boundary(self._host_steps, time.perf_counter(),
                                    comm_plan=self._comm_plan,
                                    bubble_share=self._pp_bubble_share())
        if self._goodput.enabled:
            # goodput ledger boundary tick (own switch, before the
            # registry gate): price the step's analytic comm plan into
            # `exposed_comm` (ZeRO-Infinity bandwidth-model style — the
            # honest CPU-host estimate; device captures refine the bench
            # series, not this attribution), roll the per-step compute
            # window for the lag-1 anomaly reattribution, and persist.
            step_compute = self._gp_compute_since_boundary
            self._gp_compute_since_boundary = 0.0
            exposed = self._gp_analytic_exposed_comm_s()
            if exposed > 0.0:
                exposed = self._goodput.shift(
                    "compute", "exposed_comm", min(exposed, step_compute))
                step_compute -= exposed
            self._gp_step_compute = [self._gp_step_compute[1], step_compute]
            self._goodput.set_steps(self._host_steps)
            self._goodput.tick()
        if not get_registry().enabled:
            return
        self._flops_meter.observe_boundary(flops or None,
                                           anchor=self._last_loss)
        self._mem_telemetry.sample()
        # training-numerics blind spot: loss + grad norm as gauges, every
        # boundary.  Gated on the registry so the disabled path never pays
        # the float() device sync; enabled, LM-shaped configs already
        # blocked on the loss for the FLOPs clock above (same boundary
        # bubble), while non-LM configs opt into one boundary sync — the
        # price of reading the numbers out.
        reg = get_registry()
        if reg.enabled:
            if self._last_loss is not None:
                reg.gauge("ds_train_loss",
                          TRAIN_STEP_GAUGES["ds_train_loss"]).set(
                    float(self._last_loss))
            if self._last_grad_norm is not None:
                reg.gauge("ds_train_grad_norm",
                          TRAIN_STEP_GAUGES["ds_train_grad_norm"]).set(
                    float(self._last_grad_norm))
        if self._overlap_sched is not None:
            # static truth, republished so a bench-hygiene registry.reset()
            # between passes cannot make a live scrape read "overlap: off"
            get_registry().gauge("ds_overlap_buckets").set(
                len(self._overlap_sched.bucket_infos()))

    def _gp_analytic_exposed_comm_s(self) -> float:
        """Analytic EXPOSED comm seconds for one optimizer boundary: the
        step's comm-plan bytes (gas micro executions + the boundary
        entries) priced at ``goodput.assumed_comm_gbps``, scaled by the
        overlap schedule's non-hideable fraction when bucketed overlap is
        active (T3-style exposed-time accounting; arXiv:2401.16677).
        Zero when no plan exists — nothing is invented."""
        if self._comm_plan is None:
            return 0.0
        gas = self.config.gradient_accumulation_steps
        total = (analytic_comm_seconds(self._comm_plan["micro"],
                                       self._gp_comm_gbps) * gas
                 + analytic_comm_seconds(self._comm_plan["boundary"],
                                         self._gp_comm_gbps))
        if self._overlap_sched is not None:
            total *= max(0.0, 1.0
                         - self._overlap_sched.hideable_comm_fraction())
        return total

    def _pp_bubble_share(self) -> Optional[float]:
        """Analytic pipeline bubble fraction of the step's schedule:
        ``(pp-1)/(M+2(pp-1))`` under 1F1B, ``(pp-1)/(M+pp-1)`` under
        GPipe; ``None`` when the mesh has no pp extent (no bubble to
        attribute)."""
        pp = self.mesh.shape.get("pp", 1)
        if pp <= 1:
            return None
        mcfg = getattr(self.module, "config", None)
        M = int(getattr(mcfg, "pp_microbatches", 0) or pp)
        if getattr(mcfg, "pp_schedule", "gpipe") == "1f1b":
            return (pp - 1) / (M + 2 * (pp - 1))
        return (pp - 1) / (M + pp - 1)

    # ------------------------------------------------------------------
    # device-true profiling: /profilez capture + step-time watchdog
    # (docs/OBSERVABILITY.md "Device truth")
    # ------------------------------------------------------------------
    def _maybe_start_aux_trace(self) -> None:
        """Open a pending one-shot capture window before this step's first
        dispatch (the analog of ``self._trace.maybe_start``).  A failed
        start (jax has ONE global profiler session — another holder may
        have it) fails the request / logs instead of crashing training."""
        if self._aux_trace is None:
            return
        cap, trigger, payload = self._aux_trace
        try:
            cap.maybe_start(self._host_steps + 1)
        except Exception as exc:
            self._aux_trace = None
            if trigger == "profilez":
                self._pz_broker.resolve(
                    payload, error=f"trace start failed: {exc}")
            else:
                logger.warning("watchdog: trace start failed: %s", exc)

    def _merge_pp_comm_plan(self, batch) -> None:
        """Analytic pipeline boundary entries, merged into the comm plan's
        MICRO list lazily at the first batch (the boundary tensor shape
        needs the batch's sequence length).  One pipelined execution moves
        ``2*T`` ring hops of one microbatch boundary [mb, S, D] in the
        compute dtype — T forward-ring activation hops plus T reverse-ring
        cotangent hops, with T the schedule length in ticks (``M + pp - 1``
        GPipe, ``M + 2(pp-1)`` 1F1B).  The model's trace-time ledger is off
        under the engine (``pp_comm_record=False``), so this plan is the
        only feed — the repo-wide double-count rule."""
        self._pp_plan_pending = False
        try:
            mcfg = getattr(self.module, "config", None)
            pp = self.mesh.shape.get("pp", 1)
            if pp <= 1 or mcfg is None \
                    or not hasattr(mcfg, "pp_boundary_q"):
                return
            unpacked = self._unpack_lm_batch(batch)
            if unpacked is None:
                return
            toks = unpacked[0]
            if getattr(toks, "ndim", 0) < 2:
                return
            B, S = int(toks.shape[0]), int(toks.shape[1])
            M = int(getattr(mcfg, "pp_microbatches", 0) or pp)
            mb = -(-B // M)                 # padded-batch microbatch rows
            D = int(getattr(mcfg, "hidden_size", 0) or 0)
            if not D:
                return
            is_1f1b = getattr(mcfg, "pp_schedule", "gpipe") == "1f1b"
            T = M + (2 * (pp - 1) if is_1f1b else pp - 1)
            hops = 2 * T
            numel = mb * S * D
            c_item = jnp.dtype(self.compute_dtype).itemsize
            cname = jnp.dtype(self.compute_dtype).name
            dense = hops * numel * c_item
            if getattr(mcfg, "pp_boundary_q", False):
                blk = int(getattr(mcfg, "comm_quant_block", 256) or 256)
                qbytes = hops * (numel + 4 * (-(-numel // blk)))
                entry = ("q_ppermute", hops, qbytes, "int8", pp,
                         (dense, cname))
            else:
                entry = ("ppermute", hops, dense, cname, pp)
            if self._comm_plan is None:
                self._comm_plan = {"micro": [entry], "boundary": []}
            else:
                self._comm_plan["micro"] = (
                    list(self._comm_plan["micro"]) + [entry])
        except Exception as exc:
            logger.warning("telemetry: pipeline comm plan unavailable (%s)",
                           exc)

    def _profile_bytes_per_op(self, steps: int):
        """Payload bytes the analytic comm plan says a ``steps``-step
        window moved, per op slug — feeds the recomputed device busbw."""
        if self._comm_plan is None:
            return None
        gas = self.config.gradient_accumulation_steps
        out = {}
        for mult, entries in ((gas, self._comm_plan["micro"]),
                              (1, self._comm_plan["boundary"])):
            for entry in entries:
                # quantized overlap entries carry a 6th (dense-twin) field
                op, _calls, nbytes, _dtype, world = entry[:5]
                b, w = out.get(op, (0, world))
                out[op] = (b + nbytes * mult * steps, max(w, world))
        return out or None

    def _aux_trace_tick(self) -> None:
        """Per-boundary bookkeeping for the one-shot capture slot: close a
        finished window (post-process + deliver), else claim a pending
        ``/profilez`` request.  One attribute load per step when idle."""
        if self._aux_trace is not None:
            cap, trigger, payload = self._aux_trace
            done = cap.after_step(self._host_steps)
            if done is not None:
                self._aux_trace = None
                self._finish_aux_trace(done, cap, trigger, payload)
            return
        if self._pz_broker.pending is None:
            return
        req = self._pz_broker.claim()
        if req is None:      # another engine grabbed it first
            return
        if self._trace is not None and not self._trace.done:
            # pending counts too: an aux window overlapping the configured
            # profile_trace start would collide in jax's single global
            # profiler session
            self._pz_broker.resolve(
                req, error="the configured profile_trace window is "
                           "capturing (or still ahead); retry after it "
                           "closes")
            return
        if self._cprof is not None and self._cprof.active:
            # the operator wins the single global profiler session: the
            # abandoned continuous window simply reschedules at its next
            # cadence tick
            self._cprof.close()
        import tempfile

        trace_dir = req.trace_dir or tempfile.mkdtemp(prefix="ds_profilez_")
        from deepspeed_tpu.profiling.trace import TraceCapture

        cap = TraceCapture(trace_dir, start_step=self._host_steps + 1,
                           num_steps=req.steps, perfetto=True)
        self._aux_trace = (cap, "profilez", req)

    def _finish_aux_trace(self, trace_dir, cap, trigger, payload) -> None:
        """Post-process a closed capture window and deliver the summary:
        registry backfill always; the HTTP waiter (profilez) or a JSON
        file next to the trace (watchdog).  Failures never break the
        training loop — they fail the request / log instead."""
        from deepspeed_tpu.profiling import device_trace as dtr

        try:
            try:
                summary = dtr.analyze_capture(
                    trace_dir, cap.num_steps,
                    bytes_per_op=self._profile_bytes_per_op(cap.num_steps),
                    clock=cap.clock, trigger=trigger)
            except Exception as exc:
                if trigger == "profilez":
                    self._pz_broker.resolve(
                        payload, error=f"trace post-processing failed: {exc}")
                else:
                    logger.warning(
                        "watchdog: trace post-processing failed: %s", exc)
                return
            if trigger == "profilez":
                self._pz_broker.resolve(payload, summary=summary)
                return
            out = os.path.join(trace_dir, "ds_watchdog_summary.json")
            try:
                with open(out, "w") as fh:
                    json.dump(summary, fh, indent=1, default=str)
            except Exception as exc:
                logger.warning("watchdog: summary write failed: %s", exc)
            logger.warning("watchdog: post-anomaly capture analyzed -> %s "
                           "(per-step gap %.4fs)", out,
                           summary.get("per_step", summary["phases"])["gap_s"])
            if self.config.watchdog.rearm and self._watchdog is not None:
                self._watchdog.reset()
        finally:
            if self._watchdog is not None:
                # the gz+JSON parse above ran inside this boundary interval;
                # exclude it from the next step-time sample or a /profilez
                # capture could spuriously trip the watchdog
                self._wd_last_t = time.perf_counter()

    def _cprof_tick(self) -> None:
        """Boundary hook of the continuous profiler: close a finished
        window (the decompose + history commit run inline here, between
        steps), else open the next one when due — never while another
        holder (profile_trace, a pending/claimed /profilez request, a
        watchdog capture) owns or is about to claim jax's single global
        profiler session.  One attribute load + one branch when off."""
        cp = self._cprof
        if cp is None:
            return
        if cp.active:
            if cp.after_step(self._host_steps) is not None \
                    and self._watchdog is not None:
                # the decompose ran inside this boundary interval; exclude
                # it from the next step-time sample (the
                # _finish_aux_trace idiom)
                self._wd_last_t = time.perf_counter()
            return
        if (self._aux_trace is not None
                or self._pz_broker.pending is not None
                or (self._trace is not None and not self._trace.done)):
            return
        cp.maybe_begin(self._host_steps + 1)

    def _watchdog_tick(self) -> None:
        """Feed the boundary-to-boundary wall time to the watchdog; on a
        trip, dump the flight recorder and arm the one-shot capture.  The
        steady-state cost is the watchdog's contract: one deque append +
        one comparison (plus this clock read)."""
        wd = self._watchdog
        if wd is None:
            return
        now = time.perf_counter()
        last, self._wd_last_t = self._wd_last_t, now
        if last is None or not wd.observe(now - last):
            return
        trip = dict(wd.last_trip)
        trip["step"] = self._host_steps
        self._flight.record("watchdog", **trip)
        reason = (f"watchdog: step {self._host_steps} took "
                  f"{trip['seconds']:.3f}s > {wd.factor:g}x median "
                  f"{trip['median']:.3f}s")
        logger.warning("%s", reason)
        try:
            self._flight.dump(reason=reason)
        except Exception as exc:   # a broken disk must not kill the run
            logger.error("watchdog: flight dump failed: %s", exc)
        wdc = self.config.watchdog
        if (wdc.trace and self._aux_trace is None
                and (self._trace is None or self._trace.done)):
            if self._cprof is not None and self._cprof.active:
                # a trip capture diagnoses an anomaly NOW; the abandoned
                # continuous window reschedules at its next cadence tick
                self._cprof.close()
            import tempfile

            trace_dir = (wdc.output_path
                         or tempfile.mkdtemp(prefix="ds_watchdog_"))
            from deepspeed_tpu.profiling.trace import TraceCapture

            cap = TraceCapture(trace_dir, start_step=self._host_steps + 1,
                               num_steps=wdc.capture_steps, perfetto=True)
            self._aux_trace = (cap, "watchdog", None)

    # ------------------------------------------------------------------
    # anomaly containment: skip -> rollback ladder for bf16/fp32 runs
    # (docs/RESILIENCE.md "Elastic training"; the boundary-hook slot the
    # watchdog and preemption ticks share)
    # ------------------------------------------------------------------
    def _anomaly_tick(self) -> None:
        """Classify the PREVIOUS boundary's realized grad norm (lag-1
        deferred fetch — the serving ``_fetch_block`` idiom: the value has
        long materialized, so this never blocks the step just dispatched)
        and escalate: count the skip, and after ``patience`` consecutive
        trips roll back to the last-good checkpoint."""
        a = self._anomaly
        if a is None:
            return
        pending, self._anomaly_pending = (self._anomaly_pending,
                                          (self._last_grad_norm,
                                           self._last_overflow))
        if pending is None:
            return
        gnorm = float(np.asarray(pending[0]))
        # the device's own select decision for that step: for non-fp16
        # engines the overflow output IS the anomaly trip, which keeps
        # the host ledger truthful even when the cached bound drifted
        # from the live median between dispatch and classification (a
        # dropped step must never go uncounted); fp16 conflates it with
        # loss-scale overflow, so fall back to the host rule there
        skipped = (None if self.fp16_enabled or pending[1] is None
                   else bool(np.asarray(pending[1])))
        if not a.observe(gnorm, skipped=skipped):
            return
        get_registry().counter(
            "ds_train_anomaly_skipped_total",
            "training steps skipped by the grad-norm anomaly select "
            "(non-finite or above factor x rolling median)").inc()
        trip = dict(a.last_trip)
        trip["step"] = self._host_steps
        # the recorder's first positional is the EVENT kind; the
        # detector's trip kind rides as "anomaly"
        trip["anomaly"] = trip.pop("kind")
        self._flight.record("anomaly_skip", **trip)
        # ledger: the skipped step's compute produced nothing — move the
        # classified (lag-1) boundary's compute window to `anomaly_skip`
        self._goodput.shift("compute", "anomaly_skip",
                            self._gp_step_compute[0])
        self._gp_step_compute[0] = 0.0
        if self._timeline.enabled:
            self._timeline.event("anomaly_skip", time.perf_counter(),
                                 **trip)
        logger.warning(
            "anomaly: grad norm %.3e flagged %s (median %.3e, consecutive "
            "%d/%d) — step skipped", gnorm, trip["anomaly"], trip["median"],
            a.consecutive, a.patience)
        if a.should_rollback and self.config.anomaly_detection.rollback:
            self._anomaly_rollback()

    def _anomaly_rollback(self) -> None:
        """``patience`` consecutive anomalous steps: the skip select alone
        is not containing the failure (a poisoned accumulator, or params
        already damaged before the detector armed) — dump the flight
        recorder and restore the newest valid checkpoint."""
        a = self._anomaly
        anc = self.config.anomaly_detection
        if a.rollback_streak >= anc.max_rollbacks:
            raise RuntimeError(
                f"anomaly: {a.rollback_streak} rollbacks without a single "
                f"accepted step in between (max_rollbacks="
                f"{anc.max_rollbacks}) — the anomaly persists across "
                "restores; refusing to loop")
        save_dir = (anc.save_dir or self.config.checkpoint_config.save_dir
                    or (self._preempt_cfg[0] if self._preempt_cfg else None))
        reason = (f"anomaly rollback: {a.consecutive} consecutive anomalous "
                  f"steps at step {self._host_steps}")
        self._flight.record("anomaly_rollback", step=self._host_steps,
                            consecutive=a.consecutive,
                            trip=dict(a.last_trip or {}))
        try:
            self._flight.dump(reason=reason)
        except Exception as exc:     # a broken disk must not kill the run
            logger.error("anomaly: flight dump failed: %s", exc)
        if save_dir is None:
            logger.error("anomaly: rollback requested but no save dir is "
                         "configured (anomaly_detection.save_dir / "
                         "checkpoint.save_dir); continuing with per-step "
                         "skips only")
            a.consecutive = 0        # re-arm the ladder, don't re-enter per step
            return
        # ledger: the rollback window (flight dump + restore) is its own
        # category; the nested load_checkpoint region attributes its own
        # time to checkpoint_load, the remainder stays `rollback`
        self._goodput.push("rollback")
        try:
            ckpt_dir, _ = self.load_checkpoint(save_dir)
        finally:
            self._goodput.pop()
        if ckpt_dir is None:
            logger.error("anomaly: nothing loadable in %s; continuing with "
                         "per-step skips only", save_dir)
            a.consecutive = 0
            return
        get_registry().counter(
            "ds_train_anomaly_rollback_total",
            "anomaly-ladder rollbacks to the last-good checkpoint").inc()
        a.note_rollback()
        self._anomaly_pending = None   # the pending norm belongs to the dead timeline
        logger.warning("%s — restored %s (rollback #%d)", reason, ckpt_dir,
                       a.rollbacks)

    # ------------------------------------------------------------------
    # preemption: SIGTERM -> emergency save at the next optimizer boundary
    # (docs/RESILIENCE.md; same boundary-hook slot as the watchdog)
    # ------------------------------------------------------------------
    def enable_preemption_save(self, save_dir: str, *,
                               client_state_fn: Optional[Callable[[], dict]] = None,
                               exit_after: bool = True,
                               exit_code: Optional[int] = None,
                               signum: Optional[int] = None):
        """Arm the TPU grace-window idiom: SIGTERM latches a flag (a
        handler cannot checkpoint — saves run collectives mid-dispatch);
        the next optimizer boundary performs ONE emergency
        ``save_checkpoint(save_dir)`` carrying ``client_state_fn()`` (the
        dataloader position, so resume is step-accurate) and, when
        ``exit_after``, raises ``SystemExit`` with
        :data:`~deepspeed_tpu.runtime.preemption.PREEMPTED_EXIT_CODE` so a
        supervisor (``tools/train_supervisor.py``, elastic agent)
        restarts-and-resumes instead of treating it as a crash."""
        import signal as _signal

        from deepspeed_tpu.runtime.preemption import (PREEMPTED_EXIT_CODE,
                                                      PreemptionHandler)

        if self._preempt is None:
            self._preempt = PreemptionHandler()
        self._preempt.install(signum if signum is not None
                              else _signal.SIGTERM)
        self._preempt_cfg = (save_dir, bool(exit_after),
                             PREEMPTED_EXIT_CODE if exit_code is None
                             else int(exit_code))
        if client_state_fn is not None:
            self._preempt_client_state_fn = client_state_fn
        log_dist(f"preemption handler armed: SIGTERM -> emergency save to "
                 f"{save_dir} at the next optimizer boundary", ranks=[0])
        return self._preempt

    def set_preemption_client_state(self, fn: Callable[[], dict]) -> None:
        """Register the callable whose dict rides the emergency save's
        ``client_state`` (dataloader position etc.)."""
        self._preempt_client_state_fn = fn

    def _preemption_tick(self) -> None:
        """Boundary poll of the SIGTERM latch: emergency-save once, then
        exit (when configured) with the preempted code.  One attribute
        load + branch while nothing is pending (single-process)."""
        if self._preempt is None:
            return
        requested = self._preempt.requested
        if jax.process_count() > 1:
            # Collective agreement: the signal can land while ranks sit on
            # opposite sides of a boundary, and a rank-local decision
            # would have them enter the save's collectives at DIFFERENT
            # boundaries — a mismatch that hangs out the grace window.
            # Any rank's latch preempts everyone, at the same boundary.
            # Cost: one small host allgather per boundary, only while the
            # handler is armed on a multi-process run.
            from jax.experimental import multihost_utils

            flags = multihost_utils.process_allgather(
                np.asarray(requested, np.int32))
            requested = bool(np.asarray(flags).max())
        if not requested:
            return
        save_dir, exit_after, exit_code = self._preempt_cfg
        tag = f"global_step{self.global_steps}"
        client_state = {}
        if self._preempt_client_state_fn is not None:
            try:
                client_state = dict(self._preempt_client_state_fn() or {})
            except Exception as exc:
                logger.error("preemption: client_state_fn failed: %s", exc)
        self._flight.record("ckpt_emergency", tag=tag, step=self._host_steps,
                            signal_time=self._preempt.signal_time)
        get_registry().counter(
            "ds_ckpt_emergency_saves_total",
            "SIGTERM-triggered boundary emergency saves").inc()
        path = self.save_checkpoint(save_dir, tag=tag,
                                    client_state=client_state)
        # cleared only AFTER the save succeeded: a transient save failure
        # (exception propagates to the caller) leaves the latch set, so
        # the next boundary retries instead of dropping the request
        self._preempt.clear()
        log_dist("preemption: emergency checkpoint %s saved; %s"
                 % (path, "exiting for supervisor restart" if exit_after
                    else "continuing (exit_after=False)"), ranks=[0])
        if exit_after:
            raise SystemExit(exit_code)

    def _flight_crash(self, exc: Exception) -> None:
        """Dump the event ring once, before the exception propagates."""
        if not self._flight.enabled or self._flight_dumped:
            return
        self._flight_dumped = True
        self._flight.record("exception", type=type(exc).__name__,
                            message=str(exc)[:300],
                            step=self._host_steps + 1)
        try:
            self._flight.dump(
                reason=f"unhandled {type(exc).__name__} in engine")
        except Exception as dump_exc:
            logger.error("flight recorder: crash dump failed: %s", dump_exc)

    # ------------------------------------------------------------------
    # reference-parity imperative API (SURVEY.md §3.3)
    # ------------------------------------------------------------------
    def train(self, mode: bool = True):
        self._training = mode
        return self

    def eval(self):
        return self.train(False)

    def __call__(self, batch):
        return self.forward(batch)

    def curriculum_difficulty(self) -> Optional[int]:
        if self.curriculum_scheduler is None:
            return None
        return self.curriculum_scheduler.update_difficulty(self._host_steps)

    @_flight_guard
    def forward(self, batch):
        """One micro-batch forward (+backward: gradients are produced in the
        same XLA program and accumulated — see module docstring)."""
        if self.curriculum_scheduler is not None and self._training:
            # curriculum applies to TRAINING data only (reference semantics);
            # eval always sees full sequences
            from deepspeed_tpu.runtime.data_pipeline import truncate_batch

            batch = truncate_batch(batch, self.curriculum_difficulty())
        batch = shard_batch(batch, self.mesh)
        if self._state is None:
            self.lazy_init_from_batch(batch)
        if not self._training:
            self._rng, rng = jax.random.split(self._rng)
            return self._eval_fn(self.state.params, batch, rng)
        if self._pp_plan_pending:
            self._merge_pp_comm_plan(batch)
        if self._trace is not None and self._micro_count == 0:
            self._trace.maybe_start(self._host_steps + 1)
        if self._micro_count == 0:
            self._maybe_start_aux_trace()
        self.timers(SynchronizedWallClockTimer.FORWARD).start()
        self._rng, rng = jax.random.split(self._rng)
        self._goodput.push("compute")
        try:
            if self._param_offload:
                unpacked = (self._unpack_lm_batch(batch)
                            if self._streamed is not None else None)
                if unpacked is not None:
                    toks, labels, mask = unpacked
                    if self._host_grad_acc is None:
                        self._host_grad_acc = jax.tree.map(
                            lambda a: np.zeros(a.shape, np.float32),
                            self._np_params)
                    loss = self._streamed.run(self._np_params, toks, labels,
                                              mask, rng, self._host_grad_acc)
                else:
                    loss, grads = self._pofwdbwd_fn(self.state.params, batch, rng)
                    self._accum_host_grads(grads)
                    if self.flops_profiler is not None:
                        self._profile_probes["fwdbwd"] = (
                            self._pofwdbwd_fn, (self.state.params, batch, rng))
            else:
                self._check_overlap_batch(batch)
                if self.flops_profiler is not None:
                    self._profile_probes["accum"] = (self._accum_fn,
                                                     (self.state, batch, rng))
                t0 = (time.perf_counter()
                      if self._comm_plan is not None and comm_metrics.active
                      else 0.0)
                # host-timeline twin of the in-jit ds_fwd_bwd named scope: on
                # backends whose trace export drops compiled-op scope names
                # (CPU), the post-processor's degraded mode reads this range
                with annotate("ds_fwd_bwd"):
                    self.state, loss = self._accum_fn(self.state, batch, rng)
                if t0:
                    comm_metrics.commit(self._comm_plan["micro"],
                                        time.perf_counter() - t0)
        finally:
            self._gp_compute_since_boundary += self._goodput.pop()
        self.timers(SynchronizedWallClockTimer.FORWARD).stop()
        self._micro_telemetry(batch)
        self._micro_count += 1
        self._last_loss = loss
        return loss

    def _accum_host_grads(self, grads) -> None:
        """Accumulate host-resident micro-batch grads into fp32 numpy buffers
        (ZeRO-Offload semantics: the accumulator never touches the device)."""
        if self._host_grad_acc is None:
            self._host_grad_acc = jax.tree.map(
                lambda g: np.zeros(g.shape, np.float32), grads)
        jax.tree.map(lambda buf, g: buf.__iadd__(np.asarray(g, np.float32)),
                     self._host_grad_acc, grads)

    def _build_streamed_fwdbwd(self, gas: int) -> None:
        """Construct the per-layer streamed fwd/bwd driver when the model
        supports segmenting (ZeRO-Infinity grad streaming; VERDICT r3 item 2).
        Falls back to the whole-program path (``_pofwdbwd_fn``) otherwise."""
        self._streamed = None
        p_off = self.config.zero_config.offload_param
        if p_off is None or not getattr(p_off, "stream_grads", True):
            return
        if self._client_loss_fn:
            # a custom objective can't route through the model's built-in
            # head segment; the whole-program path honors it
            logger.warning("offload_param.stream_grads: client loss_fn "
                           "supplied — falling back to the whole-program "
                           "fwd/bwd (device grad tree is O(model))")
            return
        if not hasattr(self.module, "stream_segments"):
            logger.warning(
                "offload_param.stream_grads: model %s exposes no "
                "stream_segments; falling back to the whole-program fwd/bwd "
                "(device grad tree is O(model))", type(self.module).__name__)
            return
        seg = self.module.stream_segments()
        if seg is None:
            logger.warning(
                "offload_param.stream_grads: model declined segmenting "
                "(e.g. pipeline parallelism owns the layer loop); falling "
                "back to the whole-program fwd/bwd")
            return
        from deepspeed_tpu.runtime.zero.stream_grad import StreamedFwdBwd

        off_opt = self.config.zero_config.offload_optimizer
        self._streamed = StreamedFwdBwd.from_param_specs(
            seg, self._param_specs, self.mesh, gas=gas, use_dropout=True,
            prefetch=bool(getattr(p_off, "prefetch", True)),
            int8=bool(getattr(p_off, "int8_stream", False)),
            staging_slots=int(getattr(p_off, "staging_slots", 2)),
            quant_block=int(getattr(off_opt, "quant_block", 256)
                            if off_opt is not None else 256))
        # numpy compute-dtype copy for the per-layer H2D slices — built only
        # now that streaming is actually active (a second host-resident model
        # copy is wasted memory on the whole-program fallback)
        self._np_params = jax.device_get(self.state.params)
        log_dist("offload_param: streamed per-layer fwd/bwd active "
                 "(device grads bounded to one layer"
                 + (", int8 relay" if self._streamed.streamer.int8 else "")
                 + (", prefetch off" if not
                    self._streamed.streamer.prefetch_enabled else "")
                 + ")", ranks=[0])

    @staticmethod
    def _unpack_lm_batch(batch):
        """(tokens, labels, loss_mask) matching ``model.apply``'s batch
        conventions, or None for forms the whole-program path defines
        differently (the caller falls back so both paths keep one contract).
        A loss mask is only accepted by its explicit dict key — a positional
        third element is ambiguous (position_ids? attention_mask?) and the
        whole-program path rejects it.  Shared with the overlap schedule
        (one contract for every segment-driven path)."""
        from deepspeed_tpu.runtime.zero.overlap import unpack_lm_batch

        return unpack_lm_batch(batch)

    def _check_overlap_batch(self, batch) -> None:
        """The overlap schedule drives the model through its layer segments,
        which need the LM batch forms; unroutable batches fail loudly here
        (before dispatch) instead of deep inside the shard_map trace."""
        if not self._overlap:
            return
        if self._unpack_lm_batch(batch) is None:
            raise ValueError(
                "zero_optimization.overlap_comm requires (tokens, labels) "
                "tuple or {'tokens': ..., 'labels': ...[, 'loss_mask': ...]} "
                f"dict batches (got {type(batch).__name__}); disable "
                "overlap_comm for custom batch forms")

    def backward(self, loss, retain_graph: bool = False):
        """Reference-parity no-op: gradients were already computed and
        accumulated by ``forward`` (fused fwd+bwd in one XLA program)."""
        return loss

    def is_gradient_accumulation_boundary(self) -> bool:
        if self._boundary_override is not None:
            return self._boundary_override
        gas = self.config.gradient_accumulation_steps
        return self._micro_count % gas == 0 and self._micro_count > 0

    def set_gradient_accumulation_boundary(self, is_boundary: bool) -> None:
        """Manual boundary control (reference API, used by HF Accelerate)."""
        self._boundary_override = is_boundary

    @_flight_guard
    def step(self):
        if not self.is_gradient_accumulation_boundary():
            return
        self._flight.record("step_begin", step=self._host_steps + 1)
        self.timers(SynchronizedWallClockTimer.STEP).start()
        t0 = (time.perf_counter()
              if self._comm_plan is not None and comm_metrics.active
              else 0.0)
        self._goodput.push("compute")
        try:
            if self._param_offload:
                gnorm, overflow = self._step_param_offload()
            elif self._offload:
                gnorm, overflow = self._step_offload()
            else:
                with annotate("ds_optimizer_step"):
                    if self._anomaly_select:
                        self.state, gnorm, overflow = self._apply_fn(
                            self.state, self._anomaly.bound)
                    else:
                        self.state, gnorm, overflow = self._apply_fn(self.state)
        except BaseException:
            # leave the timer re-startable: a caller that catches a
            # mid-step failure and resumes from a checkpoint must not hit
            # "timer already started" on the next boundary
            self._goodput.pop()
            self.timers(SynchronizedWallClockTimer.STEP).stop(record=False)
            raise
        self._gp_compute_since_boundary += self._goodput.pop()
        self.timers(SynchronizedWallClockTimer.STEP).stop()
        if t0 and self._comm_plan["boundary"]:
            comm_metrics.commit(self._comm_plan["boundary"],
                                time.perf_counter() - t0)
        self._last_grad_norm = gnorm
        self._last_overflow = overflow
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        self._micro_count = 0
        # Host-side mirror of state.global_steps: reading the device scalar
        # here would synchronize every step (it ignores fp16 overflow skips,
        # which only matters for print cadence; checkpoint tags still read
        # the authoritative device count).
        self._host_steps += 1
        self._boundary_telemetry()
        self._flight.record("step_end", step=self._host_steps)
        self._maybe_apply_compression()
        if self._host_steps % self.config.steps_per_print == 0:
            self._report(self.global_steps)
        self._maybe_emit_flops_profile()
        if self._trace is not None:
            self._trace.after_step(self._host_steps)
        self._watchdog_tick()
        self._anomaly_tick()
        self._aux_trace_tick()
        self._cprof_tick()
        self._preemption_tick()

    def _maybe_emit_flops_profile(self) -> None:
        if (self.flops_profiler is None
                or self._host_steps != self.config.flops_profiler.profile_step):
            return
        if (self._apply_fn is not None and self._state is not None
                and hasattr(self._apply_fn, "lower")):
            # the qcomm-grad path's apply is a python wrapper carrying the
            # error-feedback residual — no AOT surface to cost-analyze
            self._profile_probes.setdefault("apply", (self._apply_fn, (self._state,)))
        if self._streamed is not None and self._streamed.probes:
            # streamed offload: fwd+bwd is L dispatches of the per-layer
            # programs plus the embed/head segments
            L = self._streamed.L
            parts = [(fn, spec, L if name.startswith("layer") else 1)
                     for name, (fn, spec) in self._streamed.probes.items()]
            self.flops_profiler.collect_scaled("fwdbwd", parts)
        for name, (fn, args) in self._profile_probes.items():
            self.flops_profiler.collect(name, fn, *args)
        fp = self.config.flops_profiler
        self.flops_profiler.print_model_profile(
            profile_step=fp.profile_step, module_depth=fp.module_depth,
            top_modules=fp.top_modules, detailed=fp.detailed)

    def _step_param_offload(self):
        """ZeRO-Infinity step: grads already accumulated on host; clip, step
        the host optimizer, cast masters to compute dtype, and re-place the
        params in pinned host memory for the next streamed forward."""
        import ml_dtypes

        acc = self._host_grad_acc
        if acc is None:
            raise RuntimeError("step() before any forward() in offload_param mode")
        leaves = jax.tree_util.tree_leaves(acc)
        gnorm = float(np.sqrt(sum(float((g.astype(np.float64) ** 2).sum())
                                  for g in leaves)))
        if self._anomaly is not None and (not math.isfinite(gnorm)
                                          or gnorm > self._anomaly.bound):
            # anomaly skip (fp16-overflow semantics for the host-master
            # path): drop the accumulated grads, step nothing
            for g in leaves:
                g[:] = 0.0
            self._last_grad_norm = gnorm
            return gnorm, True
        clip = self.config.gradient_clipping
        if clip and clip > 0 and gnorm > clip:
            scale = clip / (gnorm + 1e-6)
            for g in leaves:
                g *= scale
        lr = self.get_lr()[0]
        masters = self._offload_opt.step([g.reshape(-1) for g in leaves], lr=lr)
        np_dtype = {jnp.bfloat16: ml_dtypes.bfloat16,
                    jnp.float16: np.float16}.get(self.compute_dtype, np.float32)
        master = self._offload_opt.tree_from_masters(masters)
        compute = jax.tree.map(lambda a: a.astype(np_dtype), master)
        if self._streamed is not None:
            # training reads only the numpy masters; the pinned-host
            # state.params refreshes lazily on the next external read
            # (eval/checkpoint) instead of paying a full-model host copy
            # every optimizer step
            self._np_params = compute
            self._state = self._state._replace(
                global_steps=self._state.global_steps + 1)
            self._pinned_stale = True
        else:
            new_params = jax.device_put(compute, self._param_shardings)
            self.state = self._state._replace(
                params=new_params, global_steps=self._state.global_steps + 1)
        for g in leaves:
            g[:] = 0.0
        self._last_grad_norm = gnorm
        return gnorm, False

    def _step_offload(self):
        """Optimizer step with host-resident states (ZeRO-Offload path),
        leaf-streamed and overlapped (reference: pipelined_optimizer_swapper):

        - all grad D2H transfers are put in flight up front
          (``copy_to_host_async``), so leaf i+1 streams while leaf i steps;
        - bf16 engines use the csrc ``ds_adam_step_bf16g`` fast path — bf16
          grads in, bf16 params out, no fp32 conversion pass;
        - each leaf's updated params go back with a per-leaf async
          ``device_put``, overlapping H2D with the next leaf's host step.
        """
        import ml_dtypes

        state = self.state
        t_relay = time.perf_counter()
        grads, gnorm, overflow = self._offload_prep_fn(state)
        # The host optimizer step forces a sync anyway; reading the overflow
        # flag here costs nothing extra (reference offload is host-synced too).
        skipped = self.fp16_enabled and bool(overflow)
        if self._anomaly is not None and not skipped:
            # anomaly skip for the host-stepped path: the same sync
            # rationale as the overflow read above (no in-program select
            # exists — the optimizer step is host code)
            g = float(np.asarray(gnorm))
            if not math.isfinite(g) or g > self._anomaly.bound:
                skipped = True
                overflow = np.bool_(True)   # steps/scaler record the skip
        if not skipped:
            # ledger: the host relay (D2H grads -> host optimizer -> H2D
            # params) is `host_stall`, nested inside step()'s compute
            # region — the stack attributes this window out of compute
            self._goodput.push("host_stall")
            flat, treedef = jax.tree_util.tree_flatten(grads)
            for leaf in flat:  # start every D2H now; np.asarray below collects
                try:
                    leaf.copy_to_host_async()
                except Exception:
                    pass
            lr = self.get_lr()[0]
            opt = self._offload_opt
            meter = self._relay_meter
            metered = meter is not None and meter.registry.enabled
            np_dtype = {jnp.bfloat16: ml_dtypes.bfloat16,
                        jnp.float16: np.float16}.get(self.compute_dtype, np.float32)
            use_bf16g = (opt.opt_type == "adam"
                         and self.compute_dtype == jnp.bfloat16
                         and opt.adam is not None
                         and not opt.int8_masters)
            shardings = jax.tree_util.tree_leaves(self._param_shardings)
            opt.begin_step(lr=lr)
            new_leaves = []
            h2d = d2h = 0
            for i, leaf in enumerate(flat):
                g = np.asarray(leaf)
                d2h += g.nbytes
                if use_bf16g and str(g.dtype) == "bfloat16":
                    # fresh buffer per leaf: device_put is async, so a reused
                    # buffer could be overwritten mid-transfer
                    out = opt.step_leaf_bf16(i, g.reshape(-1),
                                             np.empty(opt._sizes[i],
                                                      ml_dtypes.bfloat16))
                elif opt.int8_masters:
                    # int8 relay: the host step requantized the master; only
                    # the blockwise code + scales travel H2D, and a memoized
                    # compiled dequant materializes the compute-dtype param
                    # on device (~2x fewer relay bytes than bf16).
                    opt.step_leaf(
                        i, np.ascontiguousarray(g, np.float32).reshape(-1),
                        return_master=False)
                    q, s = opt.relay_leaf(i)
                    h2d += q.nbytes + s.nbytes
                    new_leaves.append(_dequant_put(
                        tuple(opt._shapes[i]), np.dtype(np_dtype).name,
                        shardings[i])(q, s))
                    continue
                else:
                    master = opt.step_leaf(
                        i, np.ascontiguousarray(g, np.float32).reshape(-1))
                    out = master.astype(np_dtype)
                h2d += out.nbytes
                # per-leaf async H2D overlaps with the next leaf's host step
                new_leaves.append(jax.device_put(
                    out.reshape(opt._shapes[i]), shardings[i]))
            opt.end_step()
            self._goodput.pop()
            if metered:
                meter.h2d_bytes.inc(h2d)
                meter.d2h_bytes.inc(d2h)
                meter.stall.record(time.perf_counter() - t_relay)
            new_params = jax.tree_util.tree_unflatten(treedef, new_leaves)
        else:
            new_params = state.params
        zero_acc, steps, scaler = self._offload_commit_fn(state, overflow)
        self.state = state._replace(params=new_params, grad_acc=zero_acc,
                                    global_steps=steps, scaler=scaler)
        return gnorm, overflow

    @_flight_guard
    def train_step(self, batch):
        """One full optimizer step from a stacked batch in a single dispatch.

        ``batch`` leaves carry a leading ``[gas, micro, ...]`` axis (or
        ``[gas*micro, ...]``, reshaped here).  Falls back to the
        accum-loop + step path when offload is active (the host optimizer
        step cannot live inside the XLA program)."""
        gas = self.config.gradient_accumulation_steps

        tbs = self.config.train_batch_size

        def stack(x):
            if not (isinstance(x, jax.Array) and getattr(x, "ndim", 0)):
                x = np.asarray(x)
            if not x.ndim:
                return x
            # Disambiguate stacked [gas, micro, ...] from flat [batch, ...]
            # even when gas == batch (micro == 1): the stacked form's second
            # dim is the micro size.
            already = (x.shape[0] == gas
                       and (x.shape[0] != tbs
                            or (x.ndim > 1 and x.shape[1] == tbs // gas)))
            if already:
                return x
            if x.shape[0] % gas:
                raise ValueError(f"batch leading dim {x.shape[0]} not "
                                 f"divisible by gradient_accumulation_steps={gas}")
            return x.reshape((gas, x.shape[0] // gas) + x.shape[1:])

        stacked = jax.tree.map(stack, batch)
        if self.curriculum_scheduler is not None:
            from deepspeed_tpu.runtime.data_pipeline import truncate_batch

            # stacked layout is [gas, micro, seq, ...]: seq is axis 2
            stacked = truncate_batch(stacked, self.curriculum_difficulty(),
                                     seq_axis=2)
        if self.state is None:
            first = jax.tree.map(lambda x: x[0], stacked)
            self.lazy_init_from_batch(shard_batch(first, self.mesh))
        if self._fused_fn is None:  # offload path: host step between programs
            losses = [self.forward(jax.tree.map(lambda x: x[i], stacked))
                      for i in range(gas)]
            self.step()
            return jnp.mean(jnp.stack(losses))
        if self._pp_plan_pending:
            # fused path skips forward(): merge off one micro-slice here
            self._merge_pp_comm_plan(jax.tree.map(lambda x: x[0], stacked))
        stacked = shard_batch(stacked, self.mesh, stacked=True)
        self._check_overlap_batch(stacked)
        self._rng, rng = jax.random.split(self._rng)
        if self.flops_profiler is not None:
            self._profile_probes["train_step"] = (self._fused_fn,
                                                  (self.state, stacked, rng))
        if self._trace is not None:
            self._trace.maybe_start(self._host_steps + 1)
        self._maybe_start_aux_trace()
        self._flight.record("step_begin", step=self._host_steps + 1,
                            fused=True)
        self.timers(SynchronizedWallClockTimer.STEP).start()
        t0 = (time.perf_counter()
              if self._comm_plan is not None and comm_metrics.active
              else 0.0)
        # the fused program runs fwd/bwd AND the update in one dispatch:
        # the host range cannot separate them (device scope rows can)
        self._goodput.push("compute")
        try:
            # the step range gives the profiler's trace its Steps line
            # (the serve engine's ds_serve_step is the same kind of range)
            with jax.profiler.StepTraceAnnotation(
                    "ds_train_step", step_num=self._host_steps), \
                    annotate("ds_fwd_bwd"):
                if self._anomaly_select:
                    self.state, loss, gnorm, overflow = self._fused_fn(
                        self.state, stacked, rng, self._anomaly.bound)
                else:
                    self.state, loss, gnorm, overflow = self._fused_fn(
                        self.state, stacked, rng)
        except BaseException:
            # keep the timer re-startable across a caught mid-step failure
            self._goodput.pop()
            self.timers(SynchronizedWallClockTimer.STEP).stop(record=False)
            raise
        self._gp_compute_since_boundary += self._goodput.pop()
        self.timers(SynchronizedWallClockTimer.STEP).stop()
        if t0:
            # the fused program runs gas micro-batches + the boundary in one
            # dispatch: commit the whole step's plan against its one window
            def scale_entry(e):
                out = e[:1] + (e[1] * gas, e[2] * gas) + e[3:5]
                if len(e) > 5:   # dense twin: bytes or (bytes, dtype)
                    d = e[5]
                    if isinstance(d, (tuple, list)):
                        out += ((d[0] * gas, d[1]),)
                    else:
                        out += (d * gas,)
                return out

            entries = [scale_entry(e) for e in self._comm_plan["micro"]]
            entries += self._comm_plan["boundary"]
            comm_metrics.commit(entries, time.perf_counter() - t0)
        if self._flops_per_step_fn is not None and get_registry().enabled:
            for leaf in jax.tree_util.tree_leaves(stacked):
                shape = getattr(leaf, "shape", ())
                if len(shape) >= 3:
                    self._flops_since_boundary += self._flops_per_step_fn(
                        int(shape[0]) * int(shape[1]) * int(shape[2]),
                        int(shape[2]))
                    break
        if self._goodput.enabled:
            for leaf in jax.tree_util.tree_leaves(stacked):
                shape = getattr(leaf, "shape", ())
                if len(shape) >= 3:    # [gas, micro, seq, ...] -> tokens
                    self._goodput.add_tokens(
                        int(shape[0]) * int(shape[1]) * int(shape[2]))
                    break
        self._last_loss = loss
        self._last_grad_norm = gnorm
        self._last_overflow = overflow
        self._micro_count = 0
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        self._host_steps += 1
        self._boundary_telemetry()
        self._flight.record("step_end", step=self._host_steps, fused=True)
        self._maybe_apply_compression()
        if self._host_steps % self.config.steps_per_print == 0:
            self._report(self.global_steps)
        self._maybe_emit_flops_profile()
        if self._trace is not None:
            self._trace.after_step(self._host_steps)
        self._watchdog_tick()
        self._anomaly_tick()
        self._aux_trace_tick()
        self._cprof_tick()
        self._preemption_tick()
        return loss

    def train_batch(self, data_iter=None):
        """Full global-batch step: gas micro-batches + boundary update
        (reference: ``PipelineEngine.train_batch`` shape).  Pulls the gas
        micro-batches eagerly and runs them through the fused single-dispatch
        ``train_step``."""
        if data_iter is None:
            if self.training_dataloader is None:
                raise ValueError("train_batch needs data_iter or training_data")
            data_iter = iter(self.training_dataloader)
        self.tput_timer.start()
        gas = self.config.gradient_accumulation_steps
        # ledger: dataloader wait is `host_stall` — the eager pull below
        # is exactly the window training blocks on host-side input
        self._goodput.push("host_stall")
        try:
            micros = [next(data_iter) for _ in range(gas)]
        finally:
            self._goodput.pop()

        def stack(*xs):
            # keep device-resident batches on device (shard_batch reshards
            # without a host hop); only host data goes through numpy
            if all(isinstance(x, jax.Array) for x in xs):
                return jnp.stack(xs)
            return np.stack([np.asarray(x) for x in xs])

        stacked = jax.tree.map(stack, *micros)
        loss = self.train_step(stacked)
        self.tput_timer.stop()
        return loss

    def eval_batch(self, data_iter):
        was = self._training
        self._training = False
        try:
            return self.forward(next(data_iter))
        finally:
            self._training = was

    # ------------------------------------------------------------------
    # introspection (reference API surface)
    # ------------------------------------------------------------------
    @property
    def global_steps(self) -> int:
        return int(self._state.global_steps) if self._state is not None else 0

    def get_global_grad_norm(self) -> Optional[float]:
        return float(self._last_grad_norm) if self._last_grad_norm is not None else None

    @property
    def loss_scale(self) -> float:
        return float(self._state.scaler.scale) if self._state is not None else 1.0

    @property
    def skipped_steps(self) -> int:
        return int(self._state.scaler.skipped_steps) if self._state is not None else 0

    def get_lr(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler.get_last_lr()
        if self.config.optimizer is not None:
            return [self.config.optimizer.params.get("lr", 0.0)]
        return [0.0]

    def _report(self, steps: int) -> None:
        lr = self.get_lr()[0]
        loss = float(self._last_loss) if self._last_loss is not None else float("nan")  # dslint: disable=DSL002 -- the log line below needs the value; runs once per steps_per_print boundary, not per step
        log_dist(f"step={steps} loss={loss:.4f} lr={lr:.3e} "
                 f"loss_scale={self.loss_scale:.0f} "
                 f"samples/sec={self.tput_timer.avg_samples_per_sec():.2f}", ranks=[0])
        if self.monitor.enabled:
            self.monitor.write_events([("Train/loss", loss, steps),
                                       ("Train/lr", lr, steps),
                                       ("Train/loss_scale", self.loss_scale, steps)])
            # same-schema bridge: the ds_* registry (serving/inference/
            # timer metrics) fans out to the CSV/TensorBoard backends too
            from deepspeed_tpu.monitor.metrics import get_registry

            get_registry().publish(self.monitor, steps)

    def deepspeed_io(self, dataset, batch_size=None, **kwargs):
        gas_batch = batch_size or self.config.train_micro_batch_size_per_gpu * \
            comm.get_data_parallel_world_size(self.mesh)
        return DeepSpeedDataLoader(dataset, batch_size=gas_batch, mesh=self.mesh,
                                   collate_fn=self.collate_fn, **kwargs)

    # ------------------------------------------------------------------
    # checkpointing (reference layout: SURVEY.md §5.4)
    # ------------------------------------------------------------------
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[dict] = None, save_latest: bool = True):
        """Crash-atomic, sharded, multi-host-safe save (docs/RESILIENCE.md).

        Every process writes only its addressable shards (no full gather —
        reference layout role of ``*_zero_pp_rank_*`` files, SURVEY.md
        §5.4), into a ``tmp.<tag>`` staging directory.  Rank 0 then writes
        ``MANIFEST.json`` (per-file size + sha256, world_size, zero_stage,
        format version) with every data file fsynced, the backend
        ``commit`` runs, and ONLY then is the stage atomically renamed
        into place and the ``latest`` pointer updated via tmp +
        ``os.replace`` — a kill at any byte offset during the save leaves
        ``latest`` naming a tag that still loads."""
        if self.state is None:
            raise RuntimeError("nothing to checkpoint: engine state not initialized")
        tag = str(tag or f"global_step{self.global_steps}")
        gp_t0 = time.perf_counter()
        self._goodput.push("checkpoint_save")
        try:
            final_dir = self._save_checkpoint_inner(save_dir, tag,
                                                    client_state, save_latest)
        finally:
            self._goodput.pop()
        # flight `checkpoint` events carry the save wall time + a ledger
        # event id, so the ledger's checkpoint_save seconds and the
        # flight dump reconcile row-by-row (docs/OBSERVABILITY.md)
        dur_s = round(time.perf_counter() - gp_t0, 6)
        event_id = self._goodput.note_event("checkpoint_save", dur_s,
                                            tag=tag)
        self._flight.record("checkpoint", op="save", tag=tag, dir=final_dir,
                            dur_s=dur_s, event_id=event_id)
        log_dist(f"saved checkpoint {final_dir}", ranks=[0])
        return final_dir

    def _save_checkpoint_inner(self, save_dir: str, tag: str,
                               client_state: Optional[dict],
                               save_latest: bool) -> str:
        from deepspeed_tpu.runtime.checkpoint_engine import atomic

        final_dir = os.path.join(save_dir, tag)
        stage_dir = atomic.stage_path(save_dir, tag)
        rank0 = comm.get_rank() == 0
        # deterministic data resume (docs/RESILIENCE.md "Elastic
        # training"): the attached dataloader's stream state (epoch,
        # sample offset, shuffle seed) rides client_state so an elastic
        # restart replays the exact remaining sample stream — an explicit
        # caller-provided "dataloader" key wins
        client_state = dict(client_state or {})
        dl = self.training_dataloader
        if (dl is not None and "dataloader" not in client_state
                and hasattr(dl, "state_dict")):
            try:
                client_state["dataloader"] = dl.state_dict()
            except Exception as exc:
                logger.warning("checkpoint: dataloader state_dict failed: "
                               "%s", exc)
        # every process ensures the dirs exist (a non-shared filesystem
        # would otherwise FileNotFoundError on non-zero ranks); only rank
        # 0 clears crash debris — concurrent rmtrees could delete a
        # freshly-created stage on a shared filesystem
        os.makedirs(save_dir, exist_ok=True)
        if rank0:
            atomic.clear_stage(save_dir, tag)  # debris of a crashed save
        os.makedirs(stage_dir, exist_ok=True)
        comm.barrier()
        self.checkpoint_engine.create(tag)
        self.checkpoint_engine.save(self.state.params,
                                    os.path.join(stage_dir, "model_states"))
        optim_payload = {"opt_state": self.state.opt_state,
                         "grad_acc": self.state.grad_acc,
                         "global_steps": self.state.global_steps,
                         "scaler": tuple(self.state.scaler)}
        self.checkpoint_engine.save(optim_payload,
                                    os.path.join(stage_dir, "optim_states"))
        if self._offload and rank0:
            # host-resident fp32 master + moments, streamed one leaf at a time
            self._offload_opt.write_state(os.path.join(stage_dir, "offload_states"))
        if rank0:
            # the batch triad rides along so a resume at a DIFFERENT
            # device set can rescale grad accumulation to preserve the
            # recorded global batch (_maybe_elastic_rescale)
            meta = {"client_state": client_state,
                    "micro_count": self._micro_count,
                    "lr_scheduler": (self.lr_scheduler.state_dict()
                                     if self.lr_scheduler else None),
                    "zero_stage": self.zero_stage,
                    "world_size": comm.get_world_size(),
                    "data_parallel_size":
                        comm.get_data_parallel_world_size(self.mesh),
                    "gradient_accumulation_steps":
                        self.config.gradient_accumulation_steps,
                    "train_micro_batch_size_per_gpu":
                        self.config.train_micro_batch_size_per_gpu,
                    "train_batch_size": self.config.train_batch_size}
            with open(os.path.join(stage_dir, "client_state.json"), "w") as fh:
                json.dump(meta, fh, default=str)
        comm.barrier()               # every process's shards are on disk
        if rank0:
            atomic.write_manifest(
                stage_dir, tag,
                extra={"world_size": comm.get_world_size(),
                       "zero_stage": self.zero_stage,
                       "global_steps": int(self.global_steps)})
        comm.barrier()
        # The backend commit point.  Publication happens strictly AFTER it
        # (regression-pinned: a crash between the shard writes and here
        # must leave `latest` untouched — the pointer used to be written
        # before commit, a window that published partial checkpoints).
        self.checkpoint_engine.commit(tag)
        if rank0:
            atomic.publish_dir(stage_dir, final_dir)
            if save_latest:
                atomic.write_latest(save_dir, tag)
            self._ckpt_gc(save_dir)
        comm.barrier()
        get_registry().counter("ds_ckpt_saves_total",
                               "committed checkpoint saves").inc()
        return final_dir

    def _ckpt_gc(self, save_dir: str) -> None:
        """Retention GC (``checkpoint.keep_last_n``): after a successful
        commit, delete the oldest VALID tags beyond the budget — never the
        tag ``latest`` points to, and never unverifiable/corrupt dirs
        (kept as post-mortem evidence).  ``ds_ckpt_retained`` publishes
        the surviving tag count either way."""
        from deepspeed_tpu.runtime.checkpoint_engine import atomic

        keep = self.config.checkpoint_config.keep_last_n
        # any .trash.* here is a leak from a publish that crashed between
        # rename-aside and cleanup (checkpoint-sized, invisible to tags)
        for name in atomic.sweep_trash(save_dir):
            log_dist(f"checkpoint GC: removed crashed-publish debris "
                     f"{name}", ranks=[0])
        tags = atomic.list_tags(save_dir)
        if keep and keep > 0:
            import shutil

            latest = atomic.read_latest(save_dir)
            valid = [t for t in tags
                     if atomic.verify_dir(os.path.join(save_dir, t),
                                          level="fast").ok]
            for t in valid[keep:]:
                if t == latest:
                    continue
                shutil.rmtree(os.path.join(save_dir, t), ignore_errors=True)
                self._flight.record("ckpt_gc", tag=t)
                log_dist(f"checkpoint GC: deleted tag {t} "
                         f"(keep_last_n={keep})", ranks=[0])
            tags = atomic.list_tags(save_dir)
        get_registry().gauge(
            "ds_ckpt_retained",
            "checkpoint tags retained in the save dir after GC").set(
            len(tags))

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_module_strict: bool = True, load_optimizer_states: bool = True,
                        load_lr_scheduler_states: bool = True,
                        load_module_only: bool = False):
        """Verified load with walk-back (docs/RESILIENCE.md): the
        requested tag (or the one ``latest`` names) is manifest-verified
        before any bytes are resharded; a corrupt / partial / missing tag
        records ``ds_ckpt_verify_failures_total`` plus a flight-recorder
        event and the loader walks back to the newest valid tag
        (``ds_ckpt_fallbacks_total``) instead of crashing.  Returns
        ``(ckpt_dir, client_state)``, or ``(None, {})`` when nothing
        loadable exists."""
        if self.state is None:
            raise RuntimeError("load_checkpoint requires initialized state "
                               "(pass model_parameters or run one batch first)")
        gp_t0 = time.perf_counter()
        self._goodput.push("checkpoint_load")
        try:
            result = self._load_checkpoint_verified(
                load_dir, tag, load_optimizer_states,
                load_lr_scheduler_states, load_module_only)
        finally:
            self._goodput.pop()
        if result[0] is not None:
            # duration-carrying flight event + ledger event id, the same
            # reconciliation contract as the save path
            dur_s = round(time.perf_counter() - gp_t0, 6)
            event_id = self._goodput.note_event("checkpoint_load", dur_s,
                                                dir=result[0])
            self._flight.record("checkpoint", op="load", dir=result[0],
                                dur_s=dur_s, event_id=event_id)
        return result

    def _load_checkpoint_verified(self, load_dir: str, tag: Optional[str],
                                  load_optimizer_states: bool,
                                  load_lr_scheduler_states: bool,
                                  load_module_only: bool):
        from deepspeed_tpu.runtime.checkpoint_engine import atomic

        requested = (str(tag) if tag is not None
                     else atomic.read_latest(load_dir))
        candidates = [requested] if requested else []
        for t in atomic.list_tags(load_dir):
            if t not in candidates:
                candidates.append(t)
        if not candidates:
            logger.warning("no 'latest' pointer or checkpoint tags in %s; "
                           "cannot load", load_dir)
            return None, {}
        verify = self.config.checkpoint_config.verify_on_load
        deep = self.config.checkpoint_config.deep_verify_on_load
        reg = get_registry()
        for i, t in enumerate(candidates):
            ckpt_dir = os.path.join(load_dir, t)
            if verify:
                st = atomic.verify_dir(ckpt_dir, level="full")
                if st.state == "no_manifest":
                    logger.warning("checkpoint %s has no MANIFEST.json "
                                   "(pre-manifest save): loading "
                                   "unverified", ckpt_dir)
                elif not st.ok:
                    reg.counter(
                        "ds_ckpt_verify_failures_total",
                        "checkpoint tags that failed manifest verification "
                        "at load").inc()
                    self._flight.record("ckpt_verify_fail", tag=t,
                                        state=st.state,
                                        problems=st.problems[:3])
                    logger.warning(
                        "checkpoint %s failed verification (%s): %s — "
                        "walking back", ckpt_dir, st.state,
                        "; ".join(st.problems[:3]) or "?")
                    continue
            if deep:
                # chunk-level pass (checkpoint.deep_verify_on_load),
                # independent of verify_on_load: names the offending
                # shard/leaf and catches index corruption the per-file
                # manifest hashes cannot
                deep_problems = atomic.deep_verify(ckpt_dir)
                if deep_problems:
                    reg.counter(
                        "ds_ckpt_verify_failures_total",
                        "checkpoint tags that failed manifest "
                        "verification at load").inc()
                    self._flight.record("ckpt_verify_fail", tag=t,
                                        state="corrupt_deep",
                                        problems=deep_problems[:3])
                    logger.warning(
                        "checkpoint %s failed DEEP verification: %s — "
                        "walking back", ckpt_dir,
                        "; ".join(deep_problems[:3]))
                    continue
            result = self._load_checkpoint_dir(
                ckpt_dir, load_optimizer_states, load_lr_scheduler_states,
                load_module_only)
            if i > 0:
                reg.counter(
                    "ds_ckpt_fallbacks_total",
                    "loads that fell back to an older valid tag").inc()
                self._flight.record("ckpt_fallback",
                                    requested=candidates[0], loaded=t)
                logger.warning("checkpoint fallback: tag %r was unloadable; "
                               "resumed from %r instead", candidates[0], t)
            reg.counter("ds_resume_total",
                        "successful checkpoint loads (resumes)").inc()
            return result
        logger.warning("no valid checkpoint in %s (tried %s)", load_dir,
                       candidates)
        return None, {}

    def _load_checkpoint_dir(self, ckpt_dir: str, load_optimizer_states: bool,
                             load_lr_scheduler_states: bool,
                             load_module_only: bool):
        from deepspeed_tpu.runtime.checkpoint_engine import is_sharded_checkpoint

        if not is_sharded_checkpoint(os.path.join(ckpt_dir, "model_states")):
            return self._load_legacy_checkpoint(ckpt_dir, load_optimizer_states,
                                                load_lr_scheduler_states,
                                                load_module_only)
        # Resharding load: each device reads only the byte ranges backing its
        # slice of the target sharding — a checkpoint saved at any ZeRO
        # stage/mesh loads at any other without a host-side full gather.
        params = self.checkpoint_engine.load(
            os.path.join(ckpt_dir, "model_states"),
            shardings=self._param_shardings)
        params = self._cast_like(params, self.state.params)
        new_state = self.state._replace(params=params)
        meta = {}
        meta_path = os.path.join(ckpt_dir, "client_state.json")
        if os.path.exists(meta_path):
            with open(meta_path) as fh:
                meta = json.load(fh)
        if not load_module_only and load_optimizer_states:
            scalar_sh = NamedSharding(self.mesh, P())
            opt_shardings = {"opt_state": self._opt_shardings,
                             "grad_acc": self._acc_shardings,
                             "global_steps": scalar_sh,
                             "scaler": tuple([scalar_sh] * len(self.state.scaler))}
            opt = self.checkpoint_engine.load(
                os.path.join(ckpt_dir, "optim_states"), shardings=opt_shardings)
            offload_dir = os.path.join(ckpt_dir, "offload_states")
            if self._offload and os.path.isdir(offload_dir):
                self._offload_opt.read_state(offload_dir)
            new_state = new_state._replace(
                opt_state=self._cast_like(opt["opt_state"], self.state.opt_state),
                grad_acc=self._cast_like(opt["grad_acc"], self.state.grad_acc),
                global_steps=jnp.asarray(opt["global_steps"], jnp.int32),
                scaler=scaler_lib.LossScaleState(*[jnp.asarray(x) for x in opt["scaler"]]))
            self._host_steps = int(jax.device_get(opt["global_steps"]))
            self._micro_count = int(meta.get("micro_count", 0) or 0)
        if load_lr_scheduler_states and self.lr_scheduler is not None and meta.get("lr_scheduler"):
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        self.state = new_state
        # the error-feedback residual is transient sync state, not part of
        # the checkpoint: a resume restarts it at zero (documented)
        self._qcomm_residual = None
        if self._param_offload and getattr(self, "_streamed", None) is not None:
            self._np_params = jax.device_get(self.state.params)
        self._restore_client_runtime(meta)
        log_dist(f"loaded checkpoint {ckpt_dir}", ranks=[0])
        return ckpt_dir, meta.get("client_state", {})

    def _load_legacy_checkpoint(self, ckpt_dir: str, load_optimizer_states: bool,
                                load_lr_scheduler_states: bool,
                                load_module_only: bool):
        """Read the pre-sharded single-file msgpack layout (checkpoints saved
        by earlier releases remain resumable)."""
        legacy = MsgpackCheckpointEngine()
        params_host = legacy.load(
            os.path.join(ckpt_dir, "model_states.msgpack"),
            target=jax.device_get(self.state.params))
        new_state = self.state._replace(
            params=jax.device_put(params_host, self._param_shardings))
        meta = {}
        meta_path = os.path.join(ckpt_dir, "client_state.json")
        if os.path.exists(meta_path):
            with open(meta_path) as fh:
                meta = json.load(fh)
        if not load_module_only and load_optimizer_states:
            target = {"opt_state": jax.device_get(self.state.opt_state),
                      "grad_acc": jax.device_get(self.state.grad_acc),
                      "global_steps": np.zeros((), np.int32),
                      "scaler": tuple(np.asarray(x) for x in self.state.scaler)}
            if self._offload:
                target["offload"] = self._offload_opt.state_dict()
            opt_host = legacy.load(
                os.path.join(ckpt_dir, "optim_states.msgpack"), target=target)
            if self._offload and "offload" in opt_host:
                self._offload_opt.load_state_dict(opt_host["offload"])
            new_state = new_state._replace(
                opt_state=jax.device_put(opt_host["opt_state"],
                                         self._opt_shardings),
                grad_acc=jax.device_put(opt_host["grad_acc"],
                                        self._acc_shardings),
                global_steps=jnp.asarray(opt_host["global_steps"], jnp.int32),
                scaler=scaler_lib.LossScaleState(
                    *[jnp.asarray(x) for x in opt_host["scaler"]]))
            self._host_steps = int(opt_host["global_steps"])
        if load_lr_scheduler_states and self.lr_scheduler is not None and meta.get("lr_scheduler"):
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        self.state = new_state
        self._qcomm_residual = None   # transient sync state, never loaded
        if self._param_offload and getattr(self, "_streamed", None) is not None:
            self._np_params = jax.device_get(self.state.params)
        self._restore_client_runtime(meta)
        log_dist(f"loaded legacy checkpoint {ckpt_dir}", ranks=[0])
        return ckpt_dir, meta.get("client_state", {})

    def _restore_client_runtime(self, meta: dict) -> None:
        """Elastic-resume hooks shared by both load paths: rescale grad
        accumulation against the recorded batch triad when the device set
        changed, then restore the attached dataloader's stream state."""
        self._maybe_elastic_rescale(meta)
        dl_state = (meta.get("client_state") or {}).get("dataloader")
        dl = self.training_dataloader
        if dl_state and dl is not None and hasattr(dl, "load_state_dict"):
            try:
                dl.load_state_dict(dl_state)
            except Exception as exc:
                logger.warning("checkpoint: dataloader state restore "
                               "failed: %s", exc)

    def _maybe_elastic_rescale(self, meta: dict) -> None:
        """World-size-change resume (docs/RESILIENCE.md "Elastic
        training"): the checkpoint records the batch triad it was trained
        with; when the data-parallel extent changed across the restart,
        rescale ``gradient_accumulation_steps`` (keeping the per-device
        micro batch) so the GLOBAL batch — and therefore the loss
        trajectory — is preserved, and recompile the step programs with
        the new accumulation count.  The divisibility rule: the recorded
        global batch must be an exact multiple of ``micro x new_dp``;
        anything else raises instead of silently training at a different
        batch size."""
        saved_dp = int(meta.get("data_parallel_size") or 0)
        saved_gas = int(meta.get("gradient_accumulation_steps") or 0)
        saved_micro = int(meta.get("train_micro_batch_size_per_gpu") or 0)
        if not (saved_dp and saved_gas and saved_micro):
            return          # pre-elastic checkpoint: no triad recorded
        cfg = self.config
        cur_dp = comm.get_data_parallel_world_size(self.mesh)
        saved_tbs = int(meta.get("train_batch_size")
                        or saved_micro * saved_gas * saved_dp)
        cur_tbs = (cfg.train_micro_batch_size_per_gpu
                   * cfg.gradient_accumulation_steps * cur_dp)
        if cur_tbs == saved_tbs:
            return          # triad already consistent (same world, or the
                            # config pre-resolved gas for the new world)
        if not cfg.checkpoint_config.elastic_resume:
            logger.warning(
                "checkpoint was trained at global batch %d (dp=%d, gas=%d) "
                "but this run computes %d (dp=%d): checkpoint."
                "elastic_resume is OFF — keeping the current triad; the "
                "loss trajectory will NOT match the original run",
                saved_tbs, saved_dp, saved_gas, cur_tbs, cur_dp)
            return
        den = cfg.train_micro_batch_size_per_gpu * cur_dp
        if saved_tbs % den:
            from deepspeed_tpu.elasticity import \
                ElasticityIncompatibleWorldSize

            raise ElasticityIncompatibleWorldSize(
                f"cannot resume the recorded global batch {saved_tbs} at "
                f"data-parallel world {cur_dp} with micro batch "
                f"{cfg.train_micro_batch_size_per_gpu}: {saved_tbs} is not "
                f"a multiple of micro x dp = {den} — resume at a world "
                f"size dividing global_batch/micro "
                f"(docs/RESILIENCE.md 'Elastic training')")
        new_gas = saved_tbs // den
        old_gas = cfg.gradient_accumulation_steps
        cfg.gradient_accumulation_steps = new_gas
        cfg.train_batch_size = saved_tbs
        if self._micro_count:
            logger.warning("elastic resume inside an accumulation window: "
                           "dropping %d partial micro-batches",
                           self._micro_count)
            self._micro_count = 0
        if new_gas != old_gas:
            self._compile_steps()   # gas is baked into the step programs
        self.tput_timer.batch_size = saved_tbs
        get_registry().counter(
            "ds_elastic_resumes_total",
            "checkpoint loads that rescaled grad accumulation to preserve "
            "the global batch across a world-size change").inc()
        self._flight.record("elastic_resume", saved_dp=saved_dp, dp=cur_dp,
                            saved_gas=saved_gas, gas=new_gas,
                            global_batch=saved_tbs)
        if self._timeline.enabled:
            self._timeline.event("elastic_resume", time.perf_counter(),
                                 saved_dp=saved_dp, dp=cur_dp,
                                 saved_gas=saved_gas, gas=new_gas,
                                 global_batch=saved_tbs)
        log_dist(f"elastic resume: dp {saved_dp} -> {cur_dp}; "
                 f"gradient_accumulation_steps {saved_gas} -> {new_gas} "
                 f"preserves global batch {saved_tbs}", ranks=[0])

    def _cast_like(self, tree, like):
        """Cast loaded leaves to the live state's dtypes (cheap jitted map;
        checkpoints may hold a different precision than the running config).
        Shape mismatches get a clear error — e.g. optimizer-state layouts
        that changed between releases cannot be silently coerced."""
        def cast(a, b):
            if tuple(getattr(a, "shape", ())) != tuple(getattr(b, "shape", ())):
                raise ValueError(
                    f"checkpoint leaf shape {getattr(a, 'shape', ())} does "
                    f"not match the live state's {getattr(b, 'shape', ())} — "
                    "the state layout changed (e.g. Adam8bit block layout); "
                    "restart without load or export/import via the "
                    "universal checkpoint")
            return a.astype(b.dtype) if a.dtype != b.dtype else a

        return jax.tree.map(cast, tree, like)

    def module_params(self):
        """Model-shaped param view: strips 0/1 Adam's leading [W] replica
        axis (worker-0's replica, the reference's rank-0 save convention).
        Export/introspection consumers must use this, not ``state.params``."""
        params = self.state.params
        if self._onebit_stacked:
            params = jax.tree.map(lambda x: x[0], params)
        return params

    def save_16bit_model(self, save_dir: str, save_filename: str = "model_states_16bit"):
        """Save compute-dtype weights (reference:
        ``stage3_gather_16bit_weights_on_model_save``) — sharded layout, cast
        on device, written shard-streamed: no rank-0 full gather."""
        os.makedirs(save_dir, exist_ok=True)
        cdtype = self.compute_dtype
        if self._zeropp:
            # flat shards -> full model-shaped tree (explicit export API;
            # the gather here is the point of the call)
            import functools

            from deepspeed_tpu.runtime.zero import zeropp as zpp

            out_specs = jax.tree.map(lambda _: P(), self._zpp_shapes,
                                     is_leaf=lambda x: isinstance(x, tuple))
            gfn = jax.jit(jax.shard_map(
                functools.partial(zpp.gather_param_tree, cfg=self._zpp_cfg,
                                  shapes=self._zpp_shapes),
                mesh=self.mesh, in_specs=(self._zpp_state_param_specs,),
                out_specs=out_specs, check_vma=False))
            full = gfn(self.state.params)
            out = os.path.join(save_dir, save_filename)
            self.checkpoint_engine.save(full, out)
            comm.barrier()
            return out
        # In param_offload mode the live shardings are pinned_host — cast with
        # device outputs (the partitioner rejects host-placed jit outputs on
        # multi-device meshes); the sharded writer streams either way.
        if self._onebit_stacked:
            out_sh = None  # model-shaped view; default placement
        else:
            out_sh = (self._param_dev_shardings if self._param_offload
                      else self._param_shardings)
        cast_fn = (lambda p: jax.tree.map(
            lambda x: x.astype(cdtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, p))
        jit_kw = {} if out_sh is None else {"out_shardings": out_sh}
        cast = jax.jit(cast_fn, **jit_kw)(self.module_params())
        out = os.path.join(save_dir, save_filename)
        self.checkpoint_engine.save(cast, out)
        comm.barrier()
        return out
