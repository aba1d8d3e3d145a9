"""Static FLOP estimation + live TFLOPS/MFU gauges for the training loop.

The XLA-cost-analysis profiler (``profiling/flops_profiler``) answers "what
does the compiled program do" exactly, but costs a relower/compile per
probe — right for the one-shot model profile, wrong for a per-step gauge.
This module is the cheap static half: per-layer FLOP estimation from the
model config (the standard ``6N + 6·L·D·S`` per-token train cost — 2N fwd
+ 4N bwd matmul, plus causal attention), multiplied by the tokens the
engine actually stepped, divided by measured boundary-to-boundary wall
time, published as ``ds_train_tflops`` / ``ds_train_mfu`` gauges through
the metrics registry (and thus the ``_report`` MonitorMaster bridge and
``/statz``).

``peak_flops()`` (bf16 peak per chip, by device kind) is the gauges'
table.  A device that is not in the table is an error, not a default: a
utilisation against a guessed peak is not a measurement.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, Optional

from deepspeed_tpu.monitor.metrics import MetricsRegistry, get_registry

__all__ = ["PEAK_FLOPS", "peak_flops", "lm_flops_per_token",
           "lm_layer_flops", "TrainFlopsMeter"]

PEAK_FLOPS = {  # bf16 peak per chip (Google Cloud TPU documentation)
    "tpu v5 lite": 197e12, "tpu v5e": 197e12, "tpu v5": 459e12,
    "tpu v4": 275e12, "tpu v6 lite": 918e12,
}


def peak_flops(device=None) -> float:
    """Peak bf16 FLOP/s of (the first) local device, by ``device_kind``.
    Raises ``KeyError`` for a kind the table does not hold (the CPU is
    one)."""
    import jax

    d = device if device is not None else jax.devices()[0]
    kind = d.device_kind.lower()
    for k, v in PEAK_FLOPS.items():
        if kind.startswith(k):
            return v
    raise KeyError(f"no peak FLOP/s on record for device kind "
                   f"{d.device_kind!r}; known: {sorted(PEAK_FLOPS)}")


@functools.lru_cache(maxsize=None)
def _peak_or_none() -> Optional[float]:
    """The first device's peak, looked up once; None where the table has
    none (the MFU gauge is then never set)."""
    try:
        return peak_flops()
    except KeyError:
        return None


def lm_flops_per_token(n_params: int, num_layers: int, hidden_size: int,
                       seq: int) -> float:
    """Train (fwd+bwd) FLOPs per token for a dense causal LM: ``6N`` matmul
    (2N fwd + 4N bwd) + ``6·L·D·S`` causal attention (12·L·D·S for the
    full score/value matmuls, halved by causality)."""
    return 6.0 * n_params + 6.0 * num_layers * hidden_size * seq


def lm_layer_flops(cfg: Any, seq: int) -> Optional[Dict[str, float]]:
    """Static per-layer forward-FLOPs-per-token breakdown from a
    ``models.config.ModelConfig``-shaped object; None when the config does
    not carry the LM fields.  Keys: qkvo / attn_scores / mlp (per layer),
    embed_head (once)."""
    D = getattr(cfg, "hidden_size", None)
    L = getattr(cfg, "num_layers", None)
    if not D or not L:
        return None
    heads = getattr(cfg, "num_heads", 1) or 1
    kv = getattr(cfg, "num_kv_heads", None) or heads
    hd = getattr(cfg, "head_dim", None) or D // heads
    inter = getattr(cfg, "intermediate_size", 4 * D)
    V = getattr(cfg, "vocab_size", 0)
    q_out = heads * hd
    kv_out = kv * hd
    qkvo = 2.0 * D * (q_out + 2 * kv_out) + 2.0 * q_out * D
    attn_scores = 2.0 * 2.0 * q_out * seq / 2.0   # QK^T + AV, causal-halved
    mlp_mats = 3 if getattr(cfg, "glu", False) else 2
    mlp = 2.0 * mlp_mats * D * inter
    return {"qkvo": qkvo, "attn_scores": attn_scores, "mlp": mlp,
            "per_layer": qkvo + attn_scores + mlp,
            "embed_head": 2.0 * D * V, "layers": float(L)}


class TrainFlopsMeter:
    """Boundary-to-boundary TFLOPS/MFU gauges.

    ``observe_boundary(flops, anchor=...)`` is called once per optimizer
    step with the FLOPs that step performed; wall time is measured between
    consecutive calls.  Dispatch is async, so a bare host clock would time
    dispatch, not compute (a tight loop dispatches several steps before
    the first finishes) — the ``anchor`` (the step's loss output) is
    blocked on first, pinning each boundary to real device completion.
    ``ds_train_mfu`` is published only where :func:`peak_flops` knows the
    device; elsewhere (the CPU) only the TFLOP/s gauge moves.  The FLOPs
    are the whole mesh's (the global batch), so the peak is one device's
    times ``num_devices``.
    The sync happens ONLY while the registry is enabled: telemetry users
    pay a boundary bubble (the ``wall_clock_breakdown`` trade, scoped the
    same way); disabled runs are untouched.  The first call only arms the
    clock.  One branch + no work while the registry is disabled.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 num_devices: int = 1):
        reg = registry if registry is not None else get_registry()
        self._registry = reg
        self._num_devices = max(1, int(num_devices))
        self._tflops = reg.gauge(
            "ds_train_tflops", "achieved train TFLOP/s (static FLOP "
            "estimate / boundary-to-boundary wall time)")
        self._mfu = reg.gauge(
            "ds_train_mfu", "model FLOPs utilization: ds_train_tflops / "
            "(device peak x devices in the mesh)")
        self._last_t: Optional[float] = None

    def reset_clock(self) -> None:
        self._last_t = None

    def observe_boundary(self, flops_per_step: Optional[float],
                         anchor=None) -> None:
        if not self._registry._enabled:
            return
        if not flops_per_step:
            # no FLOP estimate (non-LM model config) -> no gauge possible;
            # in particular do NOT pay the anchor sync for nothing
            return
        if anchor is not None:
            try:
                import jax

                jax.block_until_ready(anchor)
            except Exception:
                pass
        now = time.perf_counter()
        last, self._last_t = self._last_t, now
        if last is None:
            return
        dt = now - last
        if dt <= 0:
            return
        tflops = flops_per_step / dt / 1e12
        self._tflops.set(round(tflops, 4))
        peak = _peak_or_none()
        if peak is not None:
            self._mfu.set(round(tflops * 1e12 / (peak * self._num_devices),
                                6))
