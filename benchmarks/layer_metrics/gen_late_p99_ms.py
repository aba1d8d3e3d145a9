"""Entry: how long after its due time each request was handed to
``submit()``, 99th percentile.  The loop is one thread, so this is the rest
of the scheduler iteration in flight; more than the longest iteration
means the generator itself starved.  TTFT counts it, being timed from the
due time.  Traced run: requests due before the profiler started."""

from benchmarks.lib.stats import percentile


def read(ctx):
    loop = ctx["loop"]
    late = [l for l, a in zip(loop["late_s"], loop["schedule"])
            if a.due_s < loop["until_s"]]
    return percentile(late, 99) * 1e3 if late else None
