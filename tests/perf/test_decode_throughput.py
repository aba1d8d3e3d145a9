"""Decode throughput microbench (VERDICT r2 item 8 done-criterion).

Runs the jitted lax.while_loop generation path at smoke scale on the CPU
mesh: a sanity run, not a speed (the tests are pinned to the CPU).
"""

import time

import jax
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import build_mesh, set_global_mesh
from deepspeed_tpu.models import causal_lm


def test_decode_tokens_per_sec(capsys):
    on_tpu = jax.default_backend() != "cpu"
    mesh = build_mesh(devices=jax.devices()[:1])
    set_global_mesh(mesh)
    if on_tpu:
        model = causal_lm("gpt2-small", mesh=mesh)
        batch, prompt, new = 8, 128, 128
    else:
        model = causal_lm("gpt2-small", mesh=mesh, num_layers=2, hidden_size=128,
                          intermediate_size=256, num_heads=4, vocab_size=512)
        batch, prompt, new = 2, 16, 16
    rng = jax.random.PRNGKey(0)
    toks = jax.random.randint(rng, (batch, prompt), 0, model.config.vocab_size)
    params = model.init(rng, toks)
    engine = deepspeed_tpu.init_inference(
        model, config={"dtype": "bfloat16" if on_tpu else "float32",
                       "max_out_tokens": prompt + new})
    engine.set_params(params)

    out = engine.generate(toks, max_new_tokens=new)  # warmup + compile
    assert out.shape[1] == prompt + new
    t0 = time.perf_counter()
    out = engine.generate(toks, max_new_tokens=new)
    dt = time.perf_counter() - t0
    tps = batch * new / dt
    with capsys.disabled():
        print(f"\n[perf] decode: {tps:,.0f} tok/s "
              f"(batch={batch}, new={new}, {jax.default_backend()})")
    assert tps > 0
