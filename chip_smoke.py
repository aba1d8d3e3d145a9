#!/usr/bin/env python3
"""Does the system still start on the chip?  The quickest end-to-end proof.

One process, one TPU chip, two phases through the entry points a user calls:

- *train*: ``causal_lm("gpt2-small")`` at published widths and depth
  (seq 1024) -> ``deepspeed_tpu.initialize`` with bf16, ZeRO stage 1,
  FusedAdam and gradient clipping -> a few ``engine.train_step`` calls on a
  seeded batch.  Passes when every loss is finite, the loss fell, the step
  count advanced and the compiled step holds the flash-attention kernels.
- *serve*: ``causal_lm("gpt2-xl")`` at published widths and depth with
  seeded bf16 weights -> ``init_serving`` with its default config (paged KV,
  prefix cache, auto page size, fused decode) -> requests of mixed prompt
  length, two of them sharing a prefix, run to completion.  Passes when
  every request finished with its ``max_new_tokens``, the greedy tokens
  agree with ``init_inference(...)`` on the same weights (below), the page
  pool leaked nothing and the decode program holds the fused-decode
  kernels.

What "agree" means.  The server's tokens are compared with ``generate()``
token for token, and on the CPU in float32 they are identical.  On the chip
both compute in bf16, and the server prefills in 64-token chunks where
``generate()`` prefills the whole prompt at once: the same logits, summed
in another order, differ in their last bits, and seeded random weights
leave the best two of 50257 logits close.  So every token the server
emitted is also scored by the reference's plain forward over the server's
own sequence, and must sit within ``SERVE_TIE_ULPS`` bf16 steps of that
forward's best logit; where the server and ``generate()`` part ways, both
candidates must.  A token from a wrong page or a stale cache sits hundreds
of steps below.

``--chips 4`` runs ONLY the sharded phase and what it is compared with:
gpt2-small from the same seeded weights and batch under ZeRO stage 3 on
``build_mesh(fsdp=4)`` and under stage 0 on one device.  Passes when the
losses agree step by step, params and optimizer state are spread a quarter
per device, and the stage-3 step holds all-gather and reduce-scatter.

Each phase prints one JSON line.  Its ``wall_s`` are SMOKE TIMINGS on a
host clock (``trace``, ``lower`` and ``compile`` are what the package's
compile ledger counted inside the phase, ``compile`` being backend compiles
and reads of the persistent cache; ``steady`` is the rest)
— they say whether the compile cache hit, and are not metrics.  Any failed check or
exception ends the run with a non-zero exit code; nothing is caught and
reported as a status.  Without a TPU the script exits non-zero before it
does any work.  The last line of a passing run is exactly::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import gc
import json
import re
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

SEED = 0
TRAIN_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv")
DECODE_KERNELS = ("fused_norm_qkv", "flash_decode_paged", "fused_proj_norm",
                  "fused_mlp")
SHARDED_COLLECTIVES = ("all-gather", "reduce-scatter")
# bf16 keeps 8 significant bits (eps 2^-8 = 0.4%); the two layouts reduce
# the batch and the gradients in different orders and three Adam steps
# compound the difference, so the step-by-step bound is one eps.  (On four
# v5e chips the worst step differed by 1e-5.)
SHARDED_LOSS_RTOL = 4e-3
# Prompt lengths of the serve phase.  The first five go in together; the
# sixth repeats the first 280 tokens of the fifth after that one finished,
# so one full 256-token page of it comes out of the prefix cache.  Every
# length ends on a 33..64-token tail: chunked prefill then compiles one
# 64-token program instead of one per power-of-two tail.
SERVE_PROMPTS = (40, 100, 128, 300, 440)
SERVE_SHARED = (280, 40)          # shared prefix of the last prompt, new tail
SERVE_NEW_TOKENS = 24
# how far below the reference forward's best logit a served token may sit,
# in steps of bf16 at that logit (8 significant bits: the step is 2^-7 of
# the logit's power of two)
SERVE_TIE_ULPS = 4.0


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def check_has(what: str, want: Sequence[str], found: Sequence[str]) -> None:
    missing = [w for w in want if w not in found]
    check(not missing, f"{what} lacks {missing}; has {list(found)}")


def device_report(devices: Sequence[Any]) -> Dict[str, Any]:
    """The device as JAX reports it — the ``device`` object of the last line."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def final_line(devices: Sequence[Any]) -> str:
    return json.dumps({"ok": True, "device": device_report(devices)})


def kernels_in(compiled_text: str) -> List[str]:
    """Names of the Pallas kernels in a compiled program's text: every
    ``tpu_custom_call`` carries its ``pallas_call(name=...)`` as the scope
    just above ``pallas_call`` in its op_name."""
    names = set()
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            names.update(re.findall(r"/(\w+)/pallas_call", line))
    return sorted(names)


def collectives_in(compiled_text: str) -> List[str]:
    """Collectives of a compiled program.  The TPU compiler writes a
    gradient's reduce-scatter as a fusion named ``all-reduce-scatter`` (the
    all-reduce with the slice that keeps one shard), not as a
    ``reduce-scatter`` op; either counts."""
    found = set(re.findall(
        r"\s(all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\(", compiled_text))
    if "%all-reduce-scatter" in compiled_text:
        found.add("reduce-scatter")
    return sorted(found)


class JitClock:
    """What the package's compile ledger (``profiling/trace.py``) counted
    while the context was open: seconds jax spent tracing, lowering and in
    backend compiles (or reads of the persistent cache in their place), self
    time so that the three never hold a second twice, and how often that
    cache hit.  The ledger listens while the process's registry is enabled,
    so the context enables it, and puts it back as it was."""

    _NAMES = {"trace": "ds_jit_trace_seconds_total",
              "lower": "ds_jit_lower_seconds_total",
              "compile": "ds_jit_compile_seconds_total",
              "cache_hits": "ds_jit_cache_hits_total",
              "cache_misses": "ds_jit_cache_misses_total"}

    def _read(self) -> Dict[str, float]:
        return {k: self._registry.get(name).value
                for k, name in self._NAMES.items()}

    def __enter__(self):
        from deepspeed_tpu.monitor.metrics import get_registry

        self._registry = get_registry()
        self._was_enabled = self._registry.enabled
        self._registry.enable()
        self._before = self._read()
        return self

    def __exit__(self, *exc):
        for k, v in self._read().items():
            setattr(self, k, v - self._before[k])
        if not self._was_enabled:
            self._registry.disable()


def _phase_record(phase: str, model, n_params: int, t0: float,
                  clock: JitClock, device, **rest) -> Dict[str, Any]:
    from deepspeed_tpu.ops.pallas.common import reference_selections

    total = time.perf_counter() - t0
    jit = clock.trace + clock.lower + clock.compile
    stats = device.memory_stats() or {}
    return {"phase": phase, "model": model, "params": int(n_params),
            "wall_s": {"total": round(total, 2),
                       "trace": round(clock.trace, 2),
                       "lower": round(clock.lower, 2),
                       "compile": round(clock.compile, 2),
                       "steady": round(total - jit, 2),
                       "note": "smoke timing, not a metric; trace, lower = "
                               "jax's own stages, compile = XLA backend "
                               "compiles and cache reads, steady = the rest"},
            "compile_cache": {"hits": int(clock.cache_hits),
                              "misses": int(clock.cache_misses)},
            "peak_bytes": stats.get("peak_bytes_in_use"),
            **rest,
            "reference_in_place_of_kernel": [
                {"op": op, "why": why} for op, why in reference_selections()]}


def _n_params(tree) -> int:
    import jax

    return sum(int(x.size) for x in jax.tree.leaves(tree))


def _seeded_tokens(shape, vocab: int):
    import numpy as np

    return np.random.default_rng(SEED).integers(0, vocab, shape, dtype=np.int32)


def _train_config(batch: int, micro: int, stage: int, optimizer: str) -> dict:
    return {"train_batch_size": batch,
            "train_micro_batch_size_per_gpu": micro,
            "gradient_accumulation_steps": 1,
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": stage},
            "optimizer": {"type": optimizer,
                          "params": {"lr": 1e-3, "weight_decay": 0.01}},
            "gradient_clipping": 1.0,
            "steps_per_print": 10**9}


def _train_step_text(engine, batch) -> str:
    """Compiled text of the engine's fused train step for ``batch``, lowered
    again from the engine's own jitted function (with the persistent cache
    on, this reads back the executable the steps ran)."""
    import jax

    from deepspeed_tpu.runtime.dataloader import shard_batch

    stacked = shard_batch(batch, engine.mesh, stacked=True)
    return engine._fused_fn.lower(
        engine.state, stacked, jax.random.PRNGKey(0)).compile().as_text()


def _reference_rows(ref, prompt, tokens, pad_to: int):
    """The reference forward's logits for each position that predicted one
    of ``tokens``, with ``prompt + tokens`` teacher-forced: float32
    [len(tokens), V].  Right padding to ``pad_to`` keeps one compiled shape
    and cannot reach the rows read (attention is causal)."""
    import numpy as np

    seq = np.zeros((1, pad_to), np.int32)
    n = len(prompt) + len(tokens)
    seq[0, :n] = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    logits = np.asarray(ref(seq)[0], np.float32)
    return logits[len(prompt) - 1:n - 1]


def _ulps_below_best(rows, tokens):
    """How many bf16 steps below its row's best logit each token sits."""
    import numpy as np

    best = rows.max(axis=-1)
    got = rows[np.arange(len(tokens)), np.asarray(tokens)]
    step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(best), 1e-30))) - 7)
    return (best - got) / step


def _decode_block_text(serve) -> str:
    """Compiled text of the serving engine's decode-block program, lowered
    from its own jitted function on its own live arguments."""
    import jax.numpy as jnp

    args = [serve._loop_params(), serve._cache, serve._last_dev,
            serve._pos_dev, serve._act_dev, jnp.asarray(serve._limit),
            jnp.asarray(serve._eos), serve._rng,
            jnp.asarray(serve.pool.page_table)]
    return serve._block().lower(*args).compile().as_text()


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def train_phase(devices, *, preset: str = "gpt2-small",
                overrides: Optional[dict] = None, seq: int = 1024,
                micro: int = 4, steps: int = 6,
                want_kernels: Sequence[str] = TRAIN_KERNELS) -> Dict[str, Any]:
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import build_mesh
    from deepspeed_tpu.models import causal_lm

    t0 = time.perf_counter()
    with JitClock() as clock:
        mesh = build_mesh(devices=list(devices[:1]))
        model = causal_lm(preset, mesh=mesh, **(overrides or {}))
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, mesh=mesh, rng=jax.random.PRNGKey(SEED),
            config=_train_config(micro, micro, stage=1,
                                 optimizer="FusedAdam"))
        tokens = _seeded_tokens((1, micro, seq), model.config.vocab_size)
        batch = (tokens, tokens)
        losses = [float(engine.train_step(batch)) for _ in range(steps)]
        kernels = kernels_in(_train_step_text(engine, batch))
    check(all(np.isfinite(losses)), f"train: non-finite loss in {losses}")
    check(losses[-1] < losses[0], f"train: loss did not fall: {losses}")
    check(engine.global_steps == steps,
          f"train: {engine.global_steps} optimizer steps after {steps} calls")
    check_has("train: compiled step", want_kernels, kernels)
    return _phase_record(
        "train", preset, _n_params(engine.state.params), t0, clock,
        devices[0], seq=seq, micro_batch=micro, steps=steps,
        losses=[round(x, 4) for x in losses], kernels=kernels)


def serve_phase(devices, *, preset: str = "gpt2-xl",
                overrides: Optional[dict] = None,
                config: Optional[dict] = None,
                prompts: Sequence[int] = SERVE_PROMPTS,
                shared: Sequence[int] = SERVE_SHARED,
                new_tokens: int = SERVE_NEW_TOKENS,
                tie_ulps: float = SERVE_TIE_ULPS,
                want_kernels: Sequence[str] = DECODE_KERNELS
                ) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import build_mesh
    from deepspeed_tpu.models import causal_lm

    t0 = time.perf_counter()
    with JitClock() as clock:
        mesh = build_mesh(devices=list(devices[:1]))
        model = causal_lm(preset, mesh=mesh, **(overrides or {}))
        # seeded weights, cast inside the init program: the fp32 tree (twice
        # the bf16 one) is never resident
        params = jax.jit(lambda key: jax.tree.map(
            lambda x: x.astype(jnp.bfloat16), model.init(key)))(
                jax.random.PRNGKey(SEED))
        n_params = _n_params(params)
        # the reference and the server share one set of weights
        ref = deepspeed_tpu.init_inference(model, config=config,
                                           params=params, mesh=mesh)
        del params
        serve = deepspeed_tpu.init_serving(engine=ref)

        vocab = model.config.vocab_size
        rng = np.random.default_rng(SEED)
        texts = [rng.integers(0, vocab, n, dtype=np.int32) for n in prompts]
        first_wave = [serve.submit(t, max_new_tokens=new_tokens)
                      for t in texts]
        serve.run()
        # the last prompt has finished and left its full pages in the
        # prefix cache: a request repeating its head is served from them
        head, tail = shared
        texts.append(np.concatenate(
            [texts[-1][:head], rng.integers(0, vocab, tail, dtype=np.int32)]))
        sharer = serve.submit(texts[-1], max_new_tokens=new_tokens)
        serve.run()
        requests = first_wave + [sharer]
        kernels = kernels_in(_decode_block_text(serve))

        # the reference, longest prompt first so its cache never regrows
        order = sorted(range(len(texts)), key=lambda i: -len(texts[i]))
        want = {}
        for i in order:
            out = ref.generate(texts[i][None], max_new_tokens=new_tokens,
                               do_sample=False)
            want[i] = [int(t) for t in np.asarray(out)[0, len(texts[i]):]]
        pad_to = -(-(max(len(t) for t in texts) + new_tokens) // 128) * 128
        equal, ties, worst = 0, [], 0.0
        for i, req in enumerate(requests):
            got = list(req.output_tokens)
            check(req.finish_reason == "length" and len(got) == new_tokens,
                  f"serve: request {i} ended {req.finish_reason!r} with "
                  f"{len(got)} of {new_tokens} tokens")
            rows = _reference_rows(ref, texts[i], got, pad_to)
            below = _ulps_below_best(rows, got)
            worst = max(worst, float(below.max()))
            check(below.max() <= tie_ulps,
                  f"serve: request {i} (prompt {len(texts[i])}) token "
                  f"{int(below.argmax())} sits {below.max():.1f} bf16 steps "
                  f"below the reference's best; served {got}, generate() "
                  f"{want[i]}")
            if got == want[i]:
                equal += 1
                continue
            t = next(j for j in range(new_tokens) if got[j] != want[i][j])
            theirs = float(_ulps_below_best(rows[t:t + 1],
                                            want[i][t:t + 1])[0])
            check(theirs <= tie_ulps,
                  f"serve: request {i} leaves generate() at token {t} and "
                  f"generate()'s {want[i][t]} sits {theirs:.1f} bf16 steps "
                  f"below the reference forward's best; served {got}, "
                  f"generate() {want[i]}")
            ties.append({"request": i, "token": t, "served": got[t],
                         "generate": want[i][t],
                         "steps_below_best": [round(float(below[t]), 2),
                                              round(theirs, 2)]})
    serve.pool.check_no_leak()
    page = serve.pool.page
    check(sharer.prefix_hit_tokens == head // page * page,
          f"serve: {sharer.prefix_hit_tokens} prompt tokens came from the "
          f"prefix cache, expected {head // page * page}")
    check_has("serve: decode program", want_kernels, kernels)
    record = _phase_record(
        "serve", preset, n_params, t0, clock, devices[0],
        requests=len(requests), prompt_lens=[len(t) for t in texts],
        new_tokens=new_tokens, kv_page_tokens=page,
        prefix_hit_tokens=sharer.prefix_hit_tokens,
        equal_generate=f"{equal} of {len(requests)} requests",
        near_ties=ties, worst_steps_below_best=round(worst, 2),
        kernels=kernels)
    serve.close()
    return record


def _shard_report(tree, devices) -> Dict[str, Any]:
    """Where the bytes of ``tree`` live: per-device bytes out of its
    addressable shards, and each device's share of the whole tree."""
    import jax

    per = {d.id: 0 for d in devices}
    total = 0
    for leaf in jax.tree.leaves(tree):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per[shard.device.id] += shard.data.nbytes
    return {"total_bytes": int(total),
            "device_share": {str(k): round(v / total, 4)
                             for k, v in per.items()}}


def sharded_phase(devices, *, preset: str = "gpt2-small",
                  overrides: Optional[dict] = None, seq: int = 1024,
                  batch: int = 8, steps: int = 3,
                  rtol: float = SHARDED_LOSS_RTOL,
                  want_collectives: Sequence[str] = SHARDED_COLLECTIVES,
                  max_share: float = 0.30) -> Dict[str, Any]:
    """ZeRO stage 3 over ``fsdp=len(devices)`` against stage 0 on one
    device, from the same host-side weights and the same batch.

    The optimizer is optax AdamW on both sides: the Pallas FusedAdam update
    is a per-device kernel that GSPMD cannot partition, and the engine does
    not wrap it in a shard_map, so on a multi-device mesh it raises."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import build_mesh
    from deepspeed_tpu.models import causal_lm

    n = len(devices)
    t0 = time.perf_counter()
    with JitClock() as clock:
        probe = causal_lm(preset, **(overrides or {}))
        weights = jax.device_get(
            jax.jit(probe.init)(jax.random.PRNGKey(SEED)))
        tokens = _seeded_tokens((1, batch, seq), probe.config.vocab_size)
        data = (tokens, tokens)

        def run(mesh, stage: int, micro: int):
            model = causal_lm(preset, mesh=mesh, **(overrides or {}))
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=model, mesh=mesh, model_parameters=weights,
                config=_train_config(batch, micro, stage=stage,
                                     optimizer="AdamW"))
            # each engine makes the mesh it was given the global one; all
            # of its steps run before the next engine is built
            return engine, [float(engine.train_step(data))
                            for _ in range(steps)]

        sharded, losses_n = run(build_mesh(fsdp=n, devices=list(devices)),
                                stage=3, micro=batch // n)
        text = _train_step_text(sharded, data)
        kernels = kernels_in(text)
        collectives = collectives_in(text)
        params = _shard_report(sharded.state.params, devices)
        opt = _shard_report(sharded.state.opt_state, devices)
        tok = sharded.state.params["embed"]["tok"]
        piece = tok.addressable_shards[0].data.shape
        split = [g // p for g, p in zip(tok.shape, piece)]
        embedding = {
            "shape": list(tok.shape), "shard_shape": list(piece),
            "layout": ("replicated" if max(split) == 1 else
                       f"vocab dim whole, hidden dim split {split[1]} ways; "
                       "nothing padded or replicated" if split[0] == 1 else
                       f"vocab dim split {split[0]} ways")}
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
        del sharded
        gc.collect()
        _, losses_1 = run(build_mesh(devices=list(devices[:1])),
                          stage=0, micro=batch)
    check(all(np.isfinite(losses_n + losses_1)),
          f"sharded: non-finite loss in {losses_n} / {losses_1}")
    worst = max(abs(a - b) / abs(b) for a, b in zip(losses_n, losses_1))
    check(worst <= rtol,
          f"sharded: stage-3 losses {losses_n} leave the one-device losses "
          f"{losses_1} by {worst:.4f} (> {rtol})")
    for name, rep in (("params", params), ("optimizer state", opt)):
        shares = rep["device_share"]
        check(len(shares) == n and all(0 < s <= max_share
                                       for s in shares.values()),
              f"sharded: {name} not spread over {n} devices: {shares}")
    check(all(b is None or b > 0 for b in in_use),
          f"sharded: a device holds nothing: bytes_in_use {in_use}")
    check_has("sharded: stage-3 step", want_collectives, collectives)
    return _phase_record(
        "sharded", preset, _n_params(weights), t0, clock, devices[0],
        chips=n, seq=seq, batch=batch, steps=steps,
        losses_stage3=[round(x, 4) for x in losses_n],
        losses_one_device=[round(x, 4) for x in losses_1],
        loss_rtol={"bound": rtol, "worst": round(worst, 5)},
        param_shards=params, optimizer_state_shards=opt,
        bytes_in_use=in_use,
        embedding=embedding,
        collectives=collectives, kernels=kernels)


# ---------------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded phase and its one-device "
                         "comparison (default 1: train + serve)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, jax found {devices[0].platform} "
                 f"({devices[0].device_kind})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, jax found {len(devices)}")

    from deepspeed_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    if args.chips == 4:
        print(json.dumps(sharded_phase(devices[:4])), flush=True)
    else:
        print(json.dumps(train_phase(devices)), flush=True)
        gc.collect()        # the train engine's state leaves the chip
        print(json.dumps(serve_phase(devices)), flush=True)
    print(final_line(devices), flush=True)


if __name__ == "__main__":
    main()
