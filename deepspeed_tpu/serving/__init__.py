"""Continuous-batching serving layer (Orca-style iteration-level
scheduling over a slot-based KV cache; the role DeepSpeed ships as
MII / DeepSpeed-FastGen's dynamic batching on top of the reference
inference engine).

- :mod:`deepspeed_tpu.serving.scheduler` — request queue + iteration-level
  scheduler: finished sequences free their slot immediately; queued
  requests are admitted mid-flight.
- :mod:`deepspeed_tpu.serving.paged_kv` — :class:`PagedKVPool`: block
  allocator over one shared pool of fixed-size KV token pages (per-slot
  page tables, alloc-on-append, free-on-finish, LIFO preempt-and-requeue
  under pool pressure) — the vLLM/PagedAttention role.
- :mod:`deepspeed_tpu.serving.cache_kind` — what a slot's cache is made of
  (full pages, window + summary pages, two budgets, latent pages + state):
  the one place that decides it from the model's configuration.
- :mod:`deepspeed_tpu.serving.engine` — :class:`ServingEngine`: KV-cache
  slots decoding in lock-step with PER-ROW positions (every slot at its
  own depth), chunked per-slot prefill interleaved with decode so decode
  latency stays bounded, an active-slot mask so the compiled step keeps a
  static shape while occupancy varies, and device-resident pos/active
  carries so neither no-EOS nor EOS workloads sync the host per step.
- :mod:`deepspeed_tpu.serving.prefix_cache` — :class:`PrefixCache`:
  copy-on-write prefix caching over the page pool (page-granular radix
  trie; shared system prompts / multi-turn histories skip prefill).
- :mod:`deepspeed_tpu.serving.router` — :class:`Router` /
  :class:`RouterServer`: the multi-replica front-end (least-loaded
  dispatch off live ``/statz`` gauges, session affinity for prefix
  locality, ``/healthz``-driven membership, drain-aware redistribution).
  jax-free; ``tools/router.py`` runs it standalone on an operator box.
"""

from deepspeed_tpu.serving.scheduler import (FINISHED, PREFILLING, QUEUED,
                                             RUNNING, IterationScheduler,
                                             QueueFull, Request)
from deepspeed_tpu.serving.host_tier import HostPageStore
from deepspeed_tpu.serving.paged_kv import PagedKVPool, init_paged_kv_cache
from deepspeed_tpu.serving.prefix_cache import PrefixCache
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.serving.router import Router, RouterServer

__all__ = ["Request", "IterationScheduler", "QueueFull", "ServingEngine",
           "PagedKVPool", "init_paged_kv_cache", "PrefixCache",
           "HostPageStore", "Router", "RouterServer", "QUEUED",
           "PREFILLING", "RUNNING", "FINISHED"]
