"""The device a run is on: found, required, reported."""

from __future__ import annotations

import os
from typing import Any, Dict, List, Sequence


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_devices(chips: int, allow_cpu: bool = False) -> List[Any]:
    """The ``chips`` devices the cell runs on.  Without a TPU this raises:
    a measurement path that finds no chip fails, it does not fall back.
    ``allow_cpu`` is for the benchmark's own tests, which pass it as a
    function argument; no command-line flag sets it."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise NoAccelerator(
            f"needs a TPU, jax found {devices[0].platform} "
            f"({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} chips, jax found {len(devices)}")
    return list(devices[:chips])


def device_report(devices: Sequence[Any]) -> Dict[str, Any]:
    """The ``device`` object of the last line, as JAX reports it;
    ``memory_peak_bytes`` is the peak on the fullest chip."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def place_compile_cache() -> None:
    """JAX's persistent compilation cache where the program puts it
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``: a
    fixed path inside the checkout), holding EVERY program however quick its
    compile: the second run of a cell in a checkout must find them all."""
    import jax

    from deepspeed_tpu.utils.compile_cache import place_compile_cache as place

    place()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def process_age_s() -> float:
    """Seconds since this process was started, from the kernel's own
    record, so that ``setup_s`` counts the interpreter's start-up and the
    imports too."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start_ticks = int(fields[19])            # field 22: starttime
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
