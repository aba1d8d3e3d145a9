"""Accelerator auto-detection (reference: ``deepspeed/accelerator/real_accelerator.py``).

``get_accelerator()`` returns the process-wide accelerator, honoring the
``DS_ACCELERATOR`` env override exactly like the reference (SURVEY.md §5.6).
"""

from __future__ import annotations

import os
from typing import Optional

from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator
from deepspeed_tpu.accelerator.tpu_accelerator import CPU_Accelerator, TPU_Accelerator
from deepspeed_tpu.utils.logging import logger

_ACCELERATOR: Optional[DeepSpeedAccelerator] = None


def _detect() -> DeepSpeedAccelerator:
    import jax

    kind = os.environ.get("DS_ACCELERATOR") or jax.devices()[0].platform
    if kind == "cpu":
        return CPU_Accelerator()
    if kind == "tpu":
        return TPU_Accelerator()
    raise ValueError(f"accelerator {kind!r} not supported (tpu, cpu)")


def get_accelerator() -> DeepSpeedAccelerator:
    global _ACCELERATOR
    if _ACCELERATOR is None:
        _ACCELERATOR = _detect()
        logger.info("accelerator: %s (%d devices)", _ACCELERATOR.name(), _ACCELERATOR.device_count())
    return _ACCELERATOR


def set_accelerator(accel: DeepSpeedAccelerator) -> None:
    global _ACCELERATOR
    _ACCELERATOR = accel


_HOST_MEMORY_KIND: Optional[str] = None
_HOST_MEMORY_PROBED = False


def host_memory_kind() -> Optional[str]:
    """The memory kind host-tiered state (ZeRO-Infinity param offload)
    should be committed to on this backend, probed ONCE per process:

    - ``"pinned_host"`` where the client advertises it (TPU; the real
      tiered memory space — device programs DMA from it);
    - the backend's host-side kind otherwise (this jax's CPU client
      advertises only ``"unpinned_host"``, which IS its default memory —
      placements become no-ops and the offload machinery still runs);
    - ``None`` when the client exposes no memory-kind API at all
      (callers must then skip memory-space placement entirely).
    """
    global _HOST_MEMORY_KIND, _HOST_MEMORY_PROBED
    if _HOST_MEMORY_PROBED:
        return _HOST_MEMORY_KIND
    import jax

    kind = None
    try:
        kinds = {m.kind for m in jax.devices()[0].addressable_memories()}
        if "pinned_host" in kinds:
            kind = "pinned_host"
        elif "unpinned_host" in kinds:
            kind = "unpinned_host"
    except Exception:  # pragma: no cover - clients without the memories API
        kind = None
    _HOST_MEMORY_KIND = kind
    _HOST_MEMORY_PROBED = True
    if kind != "pinned_host":
        logger.info("backend advertises no pinned_host memory kind "
                    "(got %s); host-tiered params use the fallback placement",
                    kind)
    return kind


def supports_pinned_host() -> bool:
    """Whether the ZeRO-Infinity tiering path gets a REAL second memory
    space (pinned host) on this backend; False = the gated fallback is in
    effect (params stay in the backend's one memory space)."""
    return host_memory_kind() == "pinned_host"
