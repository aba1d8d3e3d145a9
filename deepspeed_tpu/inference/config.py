"""Inference config (reference: ``deepspeed/inference/config.py``).

Key schema parity (SURVEY.md §2.1 "Inference engine", §3.5):
``dtype``, ``tensor_parallel.tp_size`` (also the legacy ``mp_size`` alias),
``max_out_tokens``, ``replace_with_kernel_inject``, ``checkpoint``,
``min_out_tokens``, ``max_tokens``.  ``replace_with_kernel_inject`` (and the
auto-on ``use_fused_decode`` extension) selects the Pallas kernel-injected
decode path (models/fused_decode.py): fused QKV weights + four fused kernels
per layer, the TPU form of the reference's injection containers.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigModel


class InferenceTPConfig(DeepSpeedConfigModel):
    tp_size: int = 1
    enabled: bool = True


class InferenceCheckpointConfig(DeepSpeedConfigModel):
    checkpoint_dir: Optional[str] = None
    save_mp_checkpoint_path: Optional[str] = None


class DeepSpeedInferenceConfig(DeepSpeedConfigModel):
    # "int8" serves int8 weights (per-output-channel scales, dequant fused
    # into the matmuls; activations stay bf16) — reference
    # ``init_inference(dtype=torch.int8)`` parity.
    dtype: str = "bfloat16"
    # TPU extension: int8 KV cache (per-position/head scales) — halves the
    # cache footprint and its decode read bandwidth.
    quantize_kv_cache: bool = False
    tensor_parallel: Optional[InferenceTPConfig] = None
    max_out_tokens: int = 1024
    min_out_tokens: int = 1              # enforced: generate() raises if the
                                         # cache budget cannot cover it
    max_batch_size: int = 0              # 0 = unlimited; else generate() raises
    replace_with_kernel_inject: bool = False
    # TPU extensions for the fused decode path (models/fused_decode.py):
    # use_fused_decode None = auto (on when the model/config supports it);
    # decode_unroll = tokens generated per while_loop iteration (amortizes
    # per-iteration loop overhead; EOS/max-token tails are masked exactly).
    use_fused_decode: Optional[bool] = None
    decode_unroll: int = 4
    checkpoint: Optional[Any] = None
    enable_cuda_graph: bool = False      # accepted for parity; XLA always "graphs"
    seed: int = 0
    # Continuous-batching serving knobs (serving/engine.py — the
    # MII / DeepSpeed-FastGen dynamic-batching role):
    # num_slots = KV-cache slot pool size (max concurrently-decoding
    # requests; the compiled batch); prefill_chunk = max prompt tokens
    # prefilled per scheduler iteration per slot (bounds the decode stall
    # a long prompt causes); decode_block_tokens = decode steps per
    # compiled block per host sync (0 = follow decode_unroll);
    # max_prefill_chunks = prefill chunks advanced per iteration across
    # slots (decode-latency vs admission-latency trade): one a prefilling
    # request by admission order, the rest round by round over those with
    # prompt left, so a prompt that prefills alone takes them all.
    num_slots: int = 8
    prefill_chunk: int = 64
    decode_block_tokens: int = 0
    max_prefill_chunks: int = 2
    # Paged KV cache (serving/paged_kv.py — the vLLM/PagedAttention-style
    # block allocator): slots draw fixed-size token pages from ONE shared
    # pool instead of reserving max_out_tokens each, so HBM tracks the
    # tokens actually live and the slot count is no longer bounded by the
    # worst-case request.  kv_page_tokens = page granularity (0 = auto:
    # the flash-decode block, capped at the per-slot budget);
    # kv_pool_tokens = total pool capacity in tokens (0 = num_slots *
    # per-slot budget; set it LOWER to oversubscribe slots against a fixed
    # HBM budget, backed by LIFO preempt-and-requeue when the pool runs
    # dry).  For a model whose layers are of two kinds
    # (ModelConfig.layer_types) the pool holds two page budgets
    # (serving/paged_kv.py) and kv_pool_tokens is the FULL one: positions
    # the global layers can hold over all slots; the sliding layers'
    # window budget is num_slots rings of sliding_window rows, derived, so
    # that an admitted slot can always have its ring.
    kv_page_tokens: int = 0
    kv_pool_tokens: int = 0
    # Copy-on-write prefix caching (serving/prefix_cache.py — the
    # vLLM/SGLang radix-cache idiom): finished requests' full prompt
    # pages stay in a page-granular trie; a new request whose prompt
    # shares a cached prefix adopts those pages read-only (refcounted)
    # and prefill starts at the match frontier, with one device-side
    # page copy when the boundary page is only partially matched
    # (copy-on-write).  Greedy outputs are token-identical with the
    # cache on or off.  Off, with the reason logged, for a model whose
    # pages are not a function of the token prefix (serving/cache_kind.py).
    prefix_caching: bool = True
    # KV host tier (serving/host_tier.py — the ZeRO-Infinity move applied
    # to serving): > 0 bounds an LRU host-RAM store of that many pages;
    # prefix-cache eviction victims DEMOTE into it (device->host copy)
    # instead of dropping their KV, and a later admission that matches a
    # demoted chunk PROMOTES it back (host->device, byte-identical — greedy
    # outputs cannot change), so the effective prefix cache is host-RAM
    # sized and a preempt-resume re-adopts instead of re-prefilling.
    # 0 (default) = off: eviction drops, the PR 9 semantics.  Needs
    # prefix_caching.
    kv_host_tier_pages: int = 0
    # Overload protection (serving/scheduler.py, docs/RESILIENCE.md
    # "Serving fleet"): max_queue_depth bounds the admission queue — a
    # submit past the watermark sheds (QueueFull -> HTTP 429 with
    # Retry-After = shed_retry_after_s) instead of growing latency
    # without bound (0 = unbounded, the legacy behavior).
    # request_deadline_s is the DEFAULT per-request service deadline
    # applied at submit when the caller gives none (0 = none): a request
    # still queued past its deadline is cancelled with finish reason
    # "deadline" rather than burning a slot on an answer nobody is
    # waiting for.
    max_queue_depth: int = 0
    shed_retry_after_s: float = 1.0
    request_deadline_s: float = 0.0
    # Goodput ledger + SLO burn rules (monitor/goodput.py,
    # docs/OBSERVABILITY.md "Goodput ledger").  ``goodput`` mirrors the
    # training GoodputConfig as a plain dict ({enabled, path,
    # min_tick_interval_s}); ``slo`` maps rule name -> threshold
    # (goodput_ratio MIN, ttft_p99_s / shed_ratio MAX).  Setting either
    # enables the ledger for the serving engine; DSTPU_RUNLEDGER enables
    # it regardless (the supervisor channel).
    goodput: Optional[Dict[str, Any]] = None
    slo: Optional[Dict[str, float]] = None

    def __init__(self, **kwargs):
        # legacy alias: mp_size -> tensor_parallel.tp_size
        mp = kwargs.pop("mp_size", None)
        tp = kwargs.pop("tensor_parallel", None)
        if isinstance(tp, dict):
            tp = InferenceTPConfig(**tp)
        if tp is None:
            tp = InferenceTPConfig(tp_size=mp or 1)
        super().__init__(tensor_parallel=tp, **kwargs)
