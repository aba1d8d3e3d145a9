"""Kernels: share of its BYTE roofline the chunk scan reached.  Least time
for what the traced calls of ``selective_scan_chunk`` cannot avoid
(``mamba1_costs.scan_chunk_bytes``: every REAL prompt row's u, dt, y, B and
C once a mamba1 layer, ``ds_serve_prefill_tokens_total`` from the profiler's
start to the run's end as ``prefill_device_ms_per_ktok`` takes it; the state
in and out once a call, the calls counted in the trace) over the chip's
bytes/s, against the kernel's traced time.

It READS LOW by construction: the kernel is bound by the vector and
transcendental units (one exponential and four multiply-adds a state element
a row), for which the chip has no published peak (``lib/peaks.py`` admits
published peaks only).  The entry is there so that a later claim on the scan
has a bound under 100 and a yardstick that no PR which changes the kernel
can move.  None for a program without the kernel, a configuration without
mamba1 layers, or a window in which nothing was prefilled."""

from benchmarks.lib.costs import least_seconds
from benchmarks.lib.mamba1_costs import mamba1_layers, scan_chunk_bytes

KERNEL = "selective_scan_chunk"
P = "ds_serve_prefill_tokens_total"


def read(ctx):
    mc = ctx["config"]["model_config"]
    tr, c = ctx["trace"], ctx["counters"]
    if tr is None or KERNEL not in tr["kernels"] or not mamba1_layers(mc):
        return None
    a, b = c.get("trace_start"), c.get("end")
    rows = (b.get(P, 0) - a.get(P, 0)) if a and b else 0
    if not rows:
        return None
    k = tr["kernels"][KERNEL]
    least, _ = least_seconds(0.0, scan_chunk_bytes(mc, rows, k["count"]),
                             ctx["peaks"])
    return 100.0 * least / k["seconds"]
