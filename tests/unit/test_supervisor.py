"""tools/train_supervisor.py: bounded-retry restart loop + the end-to-end
preemption acceptance — SIGTERM mid-train → emergency save at the
boundary → supervisor restart → resume from the newest valid checkpoint
reaches the SAME loss as an uninterrupted run (rtol 2e-5, the PR 6 parity
bar)."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

_TOOLS = os.path.join(os.path.dirname(__file__), "..", "..", "tools")
_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _tool(name):
    sys.path.insert(0, _TOOLS)
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


def test_train_supervisor_selftest():
    """The retry/backoff/preempt state machine against synthetic
    children (crash-twice-then-succeed, budget exhaustion, preempt exit
    without backoff, backoff cap, DS_SUPERVISOR_RESTART visibility)."""
    sup = _tool("train_supervisor")
    assert sup.main(["train_supervisor", "--selftest"]) == 0


def test_supervisor_sigterm_forwarding_no_restart():
    """SIGTERM to the supervisor is forwarded to the child (its grace
    window runs) and the job is NOT restarted — whole-job preemption."""
    sup_mod = _tool("train_supervisor")
    prog = ("import signal, sys, time\n"
            "signal.signal(signal.SIGTERM, lambda *a: sys.exit(5))\n"
            "time.sleep(30)\n")
    sup = sup_mod.TrainSupervisor([sys.executable, "-c", prog],
                                  max_restarts=5, backoff_base=0.0,
                                  grace_s=20.0)
    t = threading.Thread(
        target=lambda: (time.sleep(0.8),
                        os.kill(os.getpid(), signal.SIGTERM)), daemon=True)
    t.start()
    t0 = time.time()
    rc = sup.run()
    assert rc == 5
    assert sup.restarts == 0
    assert time.time() - t0 < 15, "grace forwarding should be fast"


# ---------------------------------------------------------------------------
# the acceptance e2e: kill mid-train, resume to loss parity
# ---------------------------------------------------------------------------

_TRAIN_SCRIPT = r'''
import os, sys

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8 "
    "--xla_cpu_enable_concurrency_optimized_scheduler=false")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["DS_ACCELERATOR"] = "cpu"
sys.path.insert(0, {repo!r})

import json
import signal

import numpy as np
import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.utils.compile_cache import place_compile_cache

place_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

SAVE_DIR, RESULT = sys.argv[1], sys.argv[2]
TOTAL_STEPS, KILL_AT = 8, 4


def batch_for(step):
    # data position IS the step index: resume correctness is observable
    # as loss parity only if the resumed run sees the same batches
    rng = np.random.default_rng(1234 + step)
    x = rng.normal(size=(8, 8)).astype(np.float32)
    y = rng.normal(size=(8, 4)).astype(np.float32)
    return x, y


def loss_fn(params, batch, rng):
    x, y = batch
    out = jnp.tanh(x @ params["w1"]) @ params["w2"]
    return jnp.mean((out - y) ** 2)


init = np.random.default_rng(0)
params = {{"w1": jnp.asarray(init.normal(size=(8, 16)) * 0.3, jnp.float32),
           "w2": jnp.asarray(init.normal(size=(16, 4)) * 0.3, jnp.float32)}}
cfg = {{"train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {{"type": "Adam", "params": {{"lr": 1e-2}}}},
        "steps_per_print": 10**9}}
engine, _, _, _ = deepspeed_tpu.initialize(
    config=cfg, loss_fn=loss_fn, model_parameters=params)

start = 0
ckpt_dir, client_state = engine.load_checkpoint(SAVE_DIR)
if ckpt_dir is not None:
    start = int(client_state["data_step"])
    print(f"resumed from {{ckpt_dir}} at data_step={{start}}", flush=True)

holder = {{"next": start}}
engine.enable_preemption_save(
    SAVE_DIR, client_state_fn=lambda: {{"data_step": holder["next"]}},
    exit_after=True)

incarnation = int(os.environ.get("DS_SUPERVISOR_RESTART", "0"))
kill = os.environ.get("DS_TEST_KILL") == "1" and incarnation == 0

last = None
for i in range(start, TOTAL_STEPS):
    holder["next"] = i + 1            # the boundary save resumes AFTER i
    if kill and i == KILL_AT:
        # the preemption signal arrives mid-step; the optimizer boundary
        # of THIS step takes the emergency save and exits 243
        os.kill(os.getpid(), signal.SIGTERM)
    loss = engine.forward(batch_for(i))
    engine.step()
    last = float(loss)

with open(RESULT, "w") as fh:
    json.dump({{"final_loss": last, "ran_from": start}}, fh)
'''


def test_sigterm_midtrain_supervisor_resume_matches_uninterrupted(tmp_path):
    """SIGTERM lands mid-train on incarnation 0 → the engine's boundary
    hook takes an emergency save (dataloader position in client_state)
    and exits with the preempted code → the supervisor restarts
    immediately → incarnation 1 resumes from the newest valid checkpoint
    at the exact data step → the final loss matches an uninterrupted run
    at rtol 2e-5."""
    sup_mod = _tool("train_supervisor")
    script = tmp_path / "train.py"
    script.write_text(_TRAIN_SCRIPT.format(repo=_REPO))

    # run 1: supervised, killed at step 4 of 8 on incarnation 0
    kill_dir = tmp_path / "kill_ckpts"
    kill_result = tmp_path / "kill_result.json"
    env = dict(os.environ)
    env["DS_TEST_KILL"] = "1"
    sup = sup_mod.TrainSupervisor(
        [sys.executable, str(script), str(kill_dir), str(kill_result)],
        max_restarts=2, backoff_base=0.01, env=env)
    rc = sup.run()
    assert rc == 0, "supervised run did not complete"
    assert sup.preempt_restarts == 1 and sup.crash_restarts == 0
    killed = json.loads(kill_result.read_text())
    assert killed["ran_from"] == 5, \
        "resume was not step-accurate (client_state data_step)"
    # the emergency checkpoint is a valid tag under the manifest contract
    from deepspeed_tpu.runtime.checkpoint_engine import atomic

    tag = atomic.read_latest(str(kill_dir))
    assert tag is not None
    assert atomic.verify_dir(os.path.join(str(kill_dir), tag),
                             level="full").ok

    # run 2: uninterrupted, same data schedule
    ref_result = tmp_path / "ref_result.json"
    env2 = dict(os.environ)
    env2.pop("DS_TEST_KILL", None)
    proc = subprocess.run(
        [sys.executable, str(script), str(tmp_path / "ref_ckpts"),
         str(ref_result)], env=env2, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    ref = json.loads(ref_result.read_text())
    assert ref["ran_from"] == 0

    assert killed["final_loss"] == pytest.approx(ref["final_loss"],
                                                 rel=2e-5)
