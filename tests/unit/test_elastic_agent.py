"""Elastic agent v2 e2e (VERDICT r3 item 9): 2 processes train with
checkpointing, one is killed mid-run, the agent validates the surviving
world against the elastic config and restarts it, and training resumes from
the latest checkpoint and completes.
"""

import json
import os
import socket
import subprocess
import sys
import textwrap

import pytest

from deepspeed_tpu.elasticity import DSElasticAgent

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


ELASTIC_SECTION = {
    "enabled": True,
    "max_train_batch_size": 4,
    "micro_batch_sizes": [1, 2, 4],
    "min_gpus": 1,
    "max_gpus": 2,
    "version": 0.1,
}


def test_validate_world_rejects_outside_set(tmp_path):
    agent = DSElasticAgent({"elasticity": dict(ELASTIC_SECTION, max_gpus=2)},
                           "unused.py", num_procs=2)
    assert agent._validate_world(2) in (1, 2, 4)
    assert agent._validate_world(1) in (1, 2, 4)
    from deepspeed_tpu.elasticity import ElasticityIncompatibleWorldSize

    with pytest.raises(ElasticityIncompatibleWorldSize):
        agent._validate_world(3)


def test_world_probe_validates_and_falls_back(tmp_path):
    """The ``--world-size-file`` probe: missing/garbage files keep the
    default, readings clamp to num_procs, and an elastic-invalid reading
    is rejected at relaunch (unit-level)."""
    path = tmp_path / "world"
    agent = DSElasticAgent({"elasticity": ELASTIC_SECTION}, "unused.py",
                           num_procs=2,
                           world_size_fn=DSElasticAgent.world_size_file_fn(
                               str(path)))
    assert agent._probe_world(2) == 2          # no file: default
    path.write_text("not a number")
    assert agent._probe_world(2) == 2
    path.write_text("1")
    assert agent._probe_world(2) == 1          # shrink reading
    path.write_text("64")
    assert agent._probe_world(1) == 2          # clamped to num_procs
    path.write_text("0")
    assert agent._probe_world(2) == 2          # nonsense: default


def test_world_size_file_grows_next_incarnation(tmp_path):
    """Changed-device-set detection ACROSS a restart: the agent starts at
    the probed world 1 (capacity reported down), the incarnation crashes
    after flipping the probe file to 2 (capacity back), and the agent
    GROWS the relaunch to world 2 instead of relaunching the survivor
    count.  Stdlib-only child: the grow path is agent logic, not jax."""
    world_file = tmp_path / "world"
    world_file.write_text("1")
    marker = tmp_path / "incarnations.txt"
    script = tmp_path / "stub.py"
    script.write_text(textwrap.dedent("""\
        import os, sys
        marker, world_file = sys.argv[1], sys.argv[2]
        restart = int(os.environ["DS_ELASTIC_RESTART"])
        world = int(os.environ["WORLD_SIZE"])
        rank = int(os.environ["RANK"])
        with open(marker, "a") as fh:
            fh.write(f"{restart}:{world}:{rank}\\n")
        if restart == 0:
            # "the preempted hosts came back": flip the availability file
            # the scheduler keeps current, then die as a member loss
            with open(world_file, "w") as fh:
                fh.write("2")
            sys.exit(1)
        sys.exit(0)
        """))
    agent = DSElasticAgent(
        {"elasticity": ELASTIC_SECTION}, str(script),
        user_args=[str(marker), str(world_file)], num_procs=2,
        max_restarts=3, no_local_rank=True,
        world_size_fn=DSElasticAgent.world_size_file_fn(str(world_file)))
    assert agent.run() == 0
    lines = marker.read_text().strip().splitlines()
    by_restart = {}
    for line in lines:
        r, w, rank = map(int, line.split(":"))
        by_restart.setdefault(r, []).append((w, rank))
    # incarnation 0 ran at the probed world 1; incarnation 1 GREW to 2
    assert by_restart[0] == [(1, 0)], by_restart
    assert sorted(by_restart[1]) == [(2, 0), (2, 1)], by_restart
    assert agent.restart_count == 1


def test_kill_one_member_restart_resumes(tmp_path):
    """The done-criterion: rank 1 dies at step 2 of 4; the agent restarts at
    world=1; the survivor resumes from the step-2 checkpoint and finishes."""
    ckdir = tmp_path / "ck"
    ckdir.mkdir()
    cfg_path = tmp_path / "ds_config.json"
    cfg_path.write_text(json.dumps({"elasticity": ELASTIC_SECTION}))
    script = tmp_path / "train_stub.py"
    script.write_text(textwrap.dedent("""\
        import json, os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["DS_ACCELERATOR"] = "cpu"
        os.environ.pop("XLA_FLAGS", None)
        sys.path.insert(0, %r)
        import jax
        from deepspeed_tpu import comm
        comm.init_distributed()
        import deepspeed_tpu
        from tests.unit.simple_model import SimpleModel, random_dataset

        world = int(os.environ["WORLD_SIZE"])
        restart = int(os.environ["DS_ELASTIC_RESTART"])
        ckdir = %r
        total_steps = 4
        # elastic invariant: global batch 4 at any world size
        cfg = {"train_batch_size": 4,
               "train_micro_batch_size_per_gpu": 4 // world,
               "gradient_accumulation_steps": 1,
               "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
               "steps_per_print": 10**9}
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=SimpleModel(hidden_dim=8), config=cfg,
            rng=jax.random.PRNGKey(0))
        x, y = random_dataset(n=8, seed=3)
        engine.forward((x[:4], y[:4]))  # init state before any load
        engine.step()
        start = 1
        loaded, _ = engine.load_checkpoint(ckdir)
        if loaded:
            start = int(os.path.basename(loaded).replace("global_step", "")) + 1
        for step in range(start, total_steps + 1):
            engine.forward((x[:4], y[:4]))
            engine.step()
            engine.save_checkpoint(ckdir, tag=f"global_step{step}")
            comm.barrier()
            if restart == 0 and step == 2 and os.environ["RANK"] == "1":
                os._exit(1)  # simulated member loss
        if os.environ["RANK"] == "0":
            with open(os.path.join(ckdir, "done.json"), "w") as fh:
                json.dump({"restart": restart, "resumed_from": start,
                           "world": world}, fh)
        print("STUB DONE", os.environ["RANK"])
        """) % (REPO, str(ckdir)))

    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.elasticity.elastic_agent",
         "--ds_config", str(cfg_path), "--num_procs", "2",
         "--master_port", str(_free_port()), "--no_local_rank", str(script)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    with open(ckdir / "done.json") as fh:
        done = json.load(fh)
    # the surviving incarnation: restarted once, world shrank to 1, resumed
    # from the step-2 checkpoint (not from scratch)
    assert done["restart"] == 1, done
    assert done["world"] == 1, done
    assert done["resumed_from"] == 3, done
    assert "restart #1 at world=1" in proc.stderr + proc.stdout
