"""The TPU accelerator — the north star's ``TPU_Accelerator`` (SURVEY.md §2.1).

Reference parity target: ``deepspeed/accelerator/cuda_accelerator.py``'s role,
reimplemented over jax.devices()/memory_stats instead of torch.cuda.
"""

from __future__ import annotations

from typing import Any, Optional

import jax

from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator


class TPU_Accelerator(DeepSpeedAccelerator):
    _name = "tpu"
    _communication_backend_name = "xla"

    def __init__(self, platform: str = "tpu"):
        self._platform = platform

    def _devices(self):
        return jax.devices(self._platform)

    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return self._platform
        return f"{self._platform}:{device_index}"

    def device(self, device_index: Optional[int] = None) -> Any:
        return self._devices()[device_index or 0]

    def device_count(self) -> int:
        return len(self._devices())

    def memory_allocated(self, device_index: Optional[int] = None) -> int:
        stats = self._memory_stats(device_index)
        return stats.get("bytes_in_use", 0)

    def max_memory_allocated(self, device_index: Optional[int] = None) -> int:
        stats = self._memory_stats(device_index)
        return stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))

    def total_memory(self, device_index: Optional[int] = None) -> int:
        stats = self._memory_stats(device_index)
        return stats.get("bytes_limit", 0)

    def _memory_stats(self, device_index: Optional[int] = None) -> dict:
        try:
            dev = self.device(device_index)
            return dev.memory_stats() or {}
        except Exception:
            return {}

    def is_fp16_supported(self) -> bool:
        return True  # storage/compute supported; matmuls prefer bf16 on MXU

    def preferred_dtype(self):
        import jax.numpy as jnp

        return jnp.bfloat16


class CPU_Accelerator(TPU_Accelerator):
    """CPU fallback (reference: ``cpu_accelerator.py``); used in tests via
    ``DS_ACCELERATOR=cpu`` + ``JAX_PLATFORMS=cpu`` with a virtual device mesh."""

    _name = "cpu"
    _communication_backend_name = "xla"

    def __init__(self):
        super().__init__(platform="cpu")

    def total_memory(self, device_index: Optional[int] = None) -> int:
        try:
            with open("/proc/meminfo") as fh:
                for line in fh:
                    if line.startswith("MemTotal"):
                        return int(line.split()[1]) * 1024
        except Exception:
            pass
        return 0

    def memory_allocated(self, device_index: Optional[int] = None) -> int:
        return 0

    def max_memory_allocated(self, device_index: Optional[int] = None) -> int:
        return 0

    def preferred_dtype(self):
        import jax.numpy as jnp

        return jnp.float32
