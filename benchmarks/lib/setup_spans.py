"""What the program's own counters say of a run's set-up: the reduction
behind ``tools/setup_phases.py`` and the ``jit_host_ms_in_window`` reader.

The program stamps its age at the end of its import
(``ds_setup_import_seconds``), stands a ``ds_setup_<x>`` range around what
``init_serving`` / ``initialize`` build (``<name>_seconds_total``; the
outermost ranges also add up in ``ds_setup_seconds_total``, every second
once) and keeps a ledger of what jax traced, lowered and compiled
(``ds_jit_*``: SELF time, so the three stages never hold a second twice;
``deepspeed_tpu/profiling/trace.py``).  A program older than the ledger has
none of it: every function here then returns None throughout, and a reader
leaves its metric out (the rule of ``host_spans.py``: no value, never a
wrong one).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

PHASES = ("engine", "inject", "pool", "params", "compile_steps")
STAGES = ("trace", "lower", "compile")
COUNTS = {"hits": "ds_jit_cache_hits_total",
          "misses": "ds_jit_cache_misses_total",
          "programs": "ds_jit_programs_total"}
KEYS = (("import",) + PHASES + STAGES + ("cache_read",) + tuple(COUNTS)
        + ("phases", "jit_outside_phases"))


def jit_seconds(counters: Optional[Dict[str, Any]]) -> Optional[float]:
    """Trace + lower + compile self seconds of one snapshot of the
    registry; None where the program has no ledger."""
    names = [f"ds_jit_{s}_seconds_total" for s in STAGES]
    if not counters or any(n not in counters for n in names):
        return None
    return float(sum(counters[n] for n in names))


def setup_parts(counters: Optional[Dict[str, Any]]
                ) -> Dict[str, Optional[float]]:
    """From one snapshot of the registry, in seconds and counts:

    - ``import``: the process's age when the package's import ended;
    - ``engine`` (of which ``inject``), ``pool``: inside ``init_serving``;
      ``params`` (of which ``compile_steps``): inside ``initialize`` or at
      the first batch; 0.0 for a range the run never opened;
    - ``trace``, ``lower``, ``compile`` (of which ``cache_read``): jax's
      three stages, self time, wherever they ran; ``hits``, ``misses``,
      ``programs``: the persistent cache's and the backend's counts;
    - ``phases``: seconds inside any ``ds_setup_*`` range, each once;
      ``jit_outside_phases``: the part of the three stages that began
      outside every range (first calls, the benchmark's own weights).

    None throughout for a program without the counters."""
    if jit_seconds(counters) is None:
        return dict.fromkeys(KEYS)

    def get(name):
        return float(counters.get(name, 0.0))

    parts: Dict[str, Optional[float]] = {
        "import": counters.get("ds_setup_import_seconds")}
    for p in PHASES:
        parts[p] = get(f"ds_setup_{p}_seconds_total")
    for s in STAGES:
        parts[s] = get(f"ds_jit_{s}_seconds_total")
    parts["cache_read"] = get("ds_jit_cache_read_seconds_total")
    for key, name in COUNTS.items():
        parts[key] = get(name)
    parts["phases"] = get("ds_setup_seconds_total")
    parts["jit_outside_phases"] = (
        jit_seconds(counters) - get("ds_jit_in_setup_seconds_total"))
    return parts


def unattributed_s(setup_s: float, parts: Dict[str, Optional[float]]
                   ) -> Optional[float]:
    """``setup_s`` less the import, the ranges and the jit stages outside
    them: what set-up spent where the program measures nothing (the
    benchmark's model and weights, first calls' runs on the chip, warm-up
    traffic).  None where ``parts`` is."""
    if parts["import"] is None or parts["phases"] is None:
        return None
    return (setup_s - parts["import"] - parts["phases"]
            - parts["jit_outside_phases"])
