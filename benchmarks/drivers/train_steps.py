"""Training: optimizer steps of the cell's job through
``deepspeed_tpu.initialize``, a fresh seeded batch each step, every step
ended by ``block_until_ready`` on its loss.
"""

from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

WARM_STEPS = 2          # the first compiles; the second runs the program warm
PROBE_ROWS = 2          # sequences compared with the reference (ISSUE 24)
DEFAULT_TRACE_STEPS = 3
HOST_SCOPES = ("ds_fwd_bwd",)


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import build_mesh

    from benchmarks.lib.compile_clock import CompileClock
    from benchmarks.lib.correctness import TRAIN_LOSS_RTOL, reference_loss
    from benchmarks.lib.device import device_report
    from benchmarks.lib.models import build_model
    from benchmarks.lib.traffic import train_batches

    bench, cell, mix = ctx["bench"], ctx["cell"], ctx["mix"]
    devices, seconds, seed = ctx["devices"], ctx["seconds"], ctx["seed"]
    chips = len(devices)
    mesh = build_mesh(devices=list(devices), **cell["mesh"])
    model = build_model(ctx["config"], mesh)
    vocab = model.config.vocab_size
    ds_config = dict(cell["ds_config"])
    rows = int(ds_config["train_batch_size"])
    seq = int(mix["seq_len"])
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, mesh=mesh, rng=jax.random.PRNGKey(seed),
        config=ds_config)
    batches = train_batches(mix, seed, vocab, rows)

    # The engine's loss at the INITIAL weights, for the reference check
    # after the window: two sequences of the mix from a stream of their
    # own, repeated until every chip has a row (the loss is a mean over
    # rows of one length, so the repeats leave it where it was).  The
    # initial weights go to the host, 4 bytes a parameter, so that nothing
    # of the benchmark's stays on the chips while the cell is measured.
    probe = next(train_batches(mix, seed + 7919, vocab, PROBE_ROWS))
    shown = np.concatenate([probe] * -(-chips // PROBE_ROWS))
    engine.eval()
    engine_loss = float(engine.forward((shown, shown)))
    engine.train()
    initial = jax.device_get(engine.state.params)
    peak_after_init = device_report(devices)["memory_peak_bytes"]

    def step():
        b = next(batches)[None]                   # [gas=1, rows, seq]
        tb = time.perf_counter()
        loss = engine.train_step((b, b))
        loss.block_until_ready()
        return tb, time.perf_counter(), float(loss)

    warm = [step()[2] for _ in range(WARM_STEPS)]
    setup_s = ctx["age"]()
    steps = []
    with CompileClock() as clock:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            tb, te, loss = step()
            steps.append((tb - t0, te - t0, loss))
    inside = [s for s in steps if s[1] <= seconds] or steps[:1]
    # whole steps only, over the time those steps took: counting the steps
    # that fit into a fixed window would quantise the rate by one step in
    # a few dozen
    covered = inside[-1][1]
    tokens = len(inside) * rows * seq
    report = device_report(devices)

    trace_window = None
    if ctx["trace"]:
        n = int(cell.get("trace_steps", DEFAULT_TRACE_STEPS))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # host ranges are TraceAnnotations
        jax.profiler.start_trace(ctx["trace_dir"], profiler_options=opts)
        ta = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench_window"):
            for _ in range(n):
                with jax.profiler.TraceAnnotation("bench_step"):
                    step()
        trace_window = (ta - t0, time.perf_counter() - t0)
        jax.profiler.stop_trace()

    # -- correct? --------------------------------------------------------
    losses = [s[2] for s in steps]
    checks: Dict[str, Any] = {
        "compiles_in_window": clock.compiles, "compiled": clock.names,
        "first_loss": warm[0], "last_losses": losses[-5:],
        "finite": bool(np.all(np.isfinite(warm + losses))),
        "fell": bool(np.mean(losses[-5:]) < warm[0]),
        "global_steps": engine.global_steps}
    checks["hbm_peak_bytes"] = {"after_init": peak_after_init,
                                "after_window": report["memory_peak_bytes"]}
    t_ref = time.perf_counter()
    want = reference_loss(bench.reference(cell["config"]), initial,
                          ctx["config"], probe, devices[0])
    rel = abs(engine_loss - want) / abs(want)
    checks["reference"] = {"engine_loss": engine_loss,
                           "reference_loss": want, "weights": "initial",
                           "relative_difference": rel,
                           "bound": TRAIN_LOSS_RTOL,
                           "ok": bool(rel <= TRAIN_LOSS_RTOL)}
    # what a run costs after its window, beside setup_s before it
    checks["after_window_s"] = {"reference": time.perf_counter() - t_ref}
    correct = (checks["finite"] and checks["fell"] and clock.compiles == 0
               and checks["reference"]["ok"])
    return {
        "kind": "train", "attempted": len(steps), "failed": 0,
        "correct": bool(correct), "checks": checks,
        "values": {"tokens_per_s": tokens / covered},
        "setup_s": setup_s, "device": report,
        "loop": {"steps": inside, "rows": rows, "seq": seq, "chips": chips,
                 "window_s": covered},
        "counters": {}, "compiles_in_window": clock.compiles,
        "trace_window": trace_window, "host_scopes": HOST_SCOPES,
    }
