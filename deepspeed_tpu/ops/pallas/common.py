"""Shared helpers for Pallas TPU kernels.

TPU-native analog of the reference's ``csrc/includes/`` shared headers
(SURVEY.md §2.2 "Common headers"): dispatch policy, tiling helpers, and the
interpret-mode switch that lets every kernel run (and be parity-tested)
on the CPU backend.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, List, Optional, Tuple

import jax

from deepspeed_tpu.utils.logging import logger

# Resolution order for each op's implementation:
#   "pallas"  - compiled Pallas kernel (TPU)
#   "interpret" - Pallas kernel in interpreter mode (CPU tests)
#   "xla"     - pure jnp reference (always available; XLA fuses well)
_FORCE = os.environ.get("DSTPU_KERNEL_IMPL")  # override for debugging/benchmarks


@functools.lru_cache(maxsize=None)
def default_impl() -> str:
    """``"pallas"`` when the devices are TPUs, the jnp reference anywhere
    else — read off the device, not the backend's registered name."""
    if _FORCE:
        return _FORCE
    return "pallas" if jax.devices()[0].platform == "tpu" else "xla"


def resolve_impl(impl: str | None) -> str:
    return impl if impl is not None else default_impl()


def interpret_flag(impl: str) -> bool:
    return impl == "interpret"


# (op, reason) pairs for which a shape rule put the jnp reference in a
# kernel's place, in first-seen order.  Process-wide on purpose: it is the
# "already logged" set, and chip_smoke.py prints it beside the kernels it
# found in the compiled programs.
_REFERENCE_SELECTED: Dict[Tuple[str, str], None] = {}


def kernel_or_reference(op: str, impl: str, reason: Optional[str]) -> str:
    """The one door through which a shape rule swaps a Pallas kernel for its
    jnp reference.  ``reason`` comes from the op's ``*_reference_reason``
    rule (None = the kernel takes this shape); a swap is logged once per
    (op, reason).  Callers run while tracing, so that is at compile time."""
    if reason is None or impl == "xla":
        return impl
    if (op, reason) not in _REFERENCE_SELECTED:
        _REFERENCE_SELECTED[(op, reason)] = None
        logger.warning("%s: jnp reference in place of the Pallas kernel (%s)",
                       op, reason)
    return "xla"


def reference_selections() -> List[Tuple[str, str]]:
    """What :func:`kernel_or_reference` has swapped so far."""
    return list(_REFERENCE_SELECTED)


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pick_block(n: int, preferred: int, minimum: int = 128) -> int:
    """Largest divisor-of-n block <= preferred, else n itself (small inputs)."""
    if n <= preferred:
        return n
    for b in range(preferred, minimum - 1, -minimum):
        if n % b == 0:
            return b
    return n
