"""Launcher stack tests (reference: tests/unit/launcher/, SURVEY.md §4).

Covers hostfile parsing, include/exclude filters, the per-host agent's env
contract + fail-fast supervision, and an end-to-end CLI run where two local
processes both pass ``comm.init_distributed`` (the VERDICT r2 done-criterion).
"""

import os
import socket
import subprocess
import sys
import textwrap
from collections import OrderedDict

import pytest

from deepspeed_tpu.launcher import launch as launch_mod
from deepspeed_tpu.launcher import runner as runner_mod


def _write(tmp_path, name, text):
    p = tmp_path / name
    p.write_text(textwrap.dedent(text))
    return str(p)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# hostfile + filters
# ---------------------------------------------------------------------------

def test_fetch_hostfile(tmp_path):
    hf = _write(tmp_path, "hostfile", """\
        # comment
        worker-0 slots=4
        worker-1 slots=2
        """)
    pool = runner_mod.fetch_hostfile(hf)
    assert pool == OrderedDict([("worker-0", 4), ("worker-1", 2)])


def test_fetch_hostfile_malformed(tmp_path):
    hf = _write(tmp_path, "hostfile", "worker-0 gpus=4\n")
    with pytest.raises(ValueError):
        runner_mod.fetch_hostfile(hf)


def test_fetch_hostfile_missing():
    assert runner_mod.fetch_hostfile("/nonexistent/hostfile") == OrderedDict()


def test_include_filter():
    pool = OrderedDict([("w0", 4), ("w1", 4)])
    active = runner_mod.parse_inclusion_exclusion(pool, "w1:0,2", "")
    assert active == OrderedDict([("w1", [0, 2])])


def test_include_whole_host():
    pool = OrderedDict([("w0", 2), ("w1", 2)])
    active = runner_mod.parse_inclusion_exclusion(pool, "w0", "")
    assert active == OrderedDict([("w0", [0, 1])])


def test_exclude_filter():
    pool = OrderedDict([("w0", 2), ("w1", 2)])
    active = runner_mod.parse_inclusion_exclusion(pool, "", "w0:1@w1")
    assert active == OrderedDict([("w0", [0])])


def test_include_exclude_mutually_exclusive():
    pool = OrderedDict([("w0", 2)])
    with pytest.raises(ValueError):
        runner_mod.parse_inclusion_exclusion(pool, "w0", "w0")


def test_include_unknown_host():
    pool = OrderedDict([("w0", 2)])
    with pytest.raises(ValueError):
        runner_mod.parse_inclusion_exclusion(pool, "w9", "")


def test_world_info_roundtrip():
    active = OrderedDict([("a", [0, 1]), ("b", [0])])
    assert launch_mod.decode_world_info(runner_mod.encode_world_info(active)) == active


# ---------------------------------------------------------------------------
# per-host agent: env contract + fail-fast
# ---------------------------------------------------------------------------

def test_agent_env_contract(tmp_path):
    script = _write(tmp_path, "child.py", """\
        import json, os, sys
        out = {k: os.environ.get(k) for k in
               ("RANK", "LOCAL_RANK", "WORLD_SIZE", "COORDINATOR_ADDRESS")}
        out["argv"] = sys.argv[1:]
        with open(os.path.join(os.path.dirname(__file__),
                               f"env_{os.environ['RANK']}.json"), "w") as fh:
            json.dump(out, fh)
        """)
    world = runner_mod.encode_world_info(OrderedDict([("localhost", [0, 1])]))
    rc = launch_mod.main(["--world_info", world, "--node_rank", "0",
                          "--master_addr", "127.0.0.1", "--master_port", "29511",
                          script, "--flag", "x"])
    assert rc == 0
    import json

    for rank in (0, 1):
        with open(tmp_path / f"env_{rank}.json") as fh:
            env = json.load(fh)
        assert env["RANK"] == str(rank)
        assert env["LOCAL_RANK"] == str(rank)
        assert env["WORLD_SIZE"] == "2"
        assert env["COORDINATOR_ADDRESS"] == "127.0.0.1:29511"
        assert env["argv"] == [f"--local_rank={rank}", "--flag", "x"]


def test_agent_fail_fast(tmp_path):
    script = _write(tmp_path, "child.py", """\
        import os, sys, time
        if os.environ["RANK"] == "1":
            sys.exit(3)
        time.sleep(60)  # rank 0 hangs; the agent must kill it when rank 1 dies
        """)
    world = runner_mod.encode_world_info(OrderedDict([("localhost", [0, 1])]))
    import time

    t0 = time.time()
    rc = launch_mod.main(["--world_info", world, "--node_rank", "0",
                          "--master_addr", "127.0.0.1", "--master_port", "29512",
                          "--no_local_rank", script])
    assert rc == 3
    assert time.time() - t0 < 30, "fail-fast should not wait for the sleeper"


def test_agent_node_rank_offset(tmp_path):
    script = _write(tmp_path, "child.py", """\
        import os
        with open(os.path.join(os.path.dirname(__file__),
                               f"rank_{os.environ['RANK']}"), "w") as fh:
            fh.write(os.environ["LOCAL_RANK"])
        """)
    world = runner_mod.encode_world_info(
        OrderedDict([("hostA", [0, 1]), ("hostB", [0])]))
    rc = launch_mod.main(["--world_info", world, "--node_rank", "1",
                          "--master_addr", "127.0.0.1", "--master_port", "29513",
                          "--no_local_rank", script])
    assert rc == 0
    # node 1 hosts global rank 2 (offset = 2 slots on hostA), local rank 0
    assert (tmp_path / "rank_2").read_text() == "0"
    assert not (tmp_path / "rank_0").exists()


# ---------------------------------------------------------------------------
# end-to-end: CLI -> agent -> 2 processes -> init_distributed
# ---------------------------------------------------------------------------

def test_cli_two_process_init_distributed(tmp_path):
    """The VERDICT done-criterion: the CLI spawns 2 local processes that BOTH
    bootstrap jax.distributed through comm.init_distributed and agree on
    process_count == 2."""
    port = _free_port()
    script = _write(tmp_path, "train_stub.py", """\
        import os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["DS_ACCELERATOR"] = "cpu"
        os.environ.pop("XLA_FLAGS", None)  # no virtual 8-device mesh here
        sys.path.insert(0, %r)
        from deepspeed_tpu import comm
        comm.init_distributed()
        import jax
        assert jax.process_count() == 2, jax.process_count()
        assert int(os.environ["RANK"]) == jax.process_index()
        comm.barrier()
        print(f"OK rank={jax.process_index()} world={jax.device_count()}")
        """ % os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    # The children start one device each: drop this process's virtual
    # 8-device XLA_FLAGS and pin them to the CPU.
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
         "--num_procs", "2", "--master_port", str(port), "--no_local_rank",
         script],
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        env=env, capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "OK rank=0" in proc.stdout
    assert "OK rank=1" in proc.stdout


def test_env_report_runs():
    proc = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.env_report"],
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        env={**os.environ}, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "deepspeed_tpu C++/Pallas op report" in proc.stdout
    assert "native.cpu_adam" in proc.stdout


def test_cli_two_process_sharded_checkpoint(tmp_path):
    """Multi-host checkpoint validation: 2 real processes save a sharded
    checkpoint (each writes ONLY its shard + index) and reload it — the
    no-full-gather contract exercised with actual process boundaries."""
    port = _free_port()
    ckdir = tmp_path / "ck"
    ckdir.mkdir()
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    script = _write(tmp_path, "ck_stub.py", """\
        import os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["DS_ACCELERATOR"] = "cpu"
        os.environ.pop("XLA_FLAGS", None)
        sys.path.insert(0, %r)
        from deepspeed_tpu import comm
        comm.init_distributed()
        import jax, numpy as np
        assert jax.process_count() == 2
        import deepspeed_tpu
        from deepspeed_tpu.comm.mesh import build_mesh, set_global_mesh
        sys.path.insert(0, os.path.join(%r, "tests"))
        from tests.unit.simple_model import SimpleModel, random_dataset
        mesh = build_mesh(fsdp=2, devices=jax.devices())
        set_global_mesh(mesh)
        x, y = random_dataset(n=8)
        cfg = {"train_micro_batch_size_per_gpu": 4,
               "gradient_accumulation_steps": 1,
               "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
               "zero_optimization": {"stage": 1}, "steps_per_print": 10**9}
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=SimpleModel(hidden_dim=16), config=cfg, mesh=mesh,
            rng=jax.random.PRNGKey(0))
        # each process supplies its local half of the global batch
        lo = jax.process_index() * 4
        eng.forward((x[lo:lo+4], y[lo:lo+4]))
        eng.step()
        eng.save_checkpoint(%r, tag="t")
        comm.barrier()
        ckpt = os.path.join(%r, "t", "model_states")
        mine = f"shard_p{jax.process_index()}.bin"
        assert os.path.exists(os.path.join(ckpt, mine)), mine
        names = sorted(os.listdir(ckpt))
        assert "shard_p0.bin" in names and "shard_p1.bin" in names, names
        eng.load_checkpoint(%r, tag="t")
        loss = eng.forward((x[lo:lo+4], y[lo:lo+4]))
        print(f"CKPT OK rank={jax.process_index()} loss={float(loss):.4f}")
        """ % (repo, repo, str(ckdir), str(ckdir), str(ckdir)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
         "--num_procs", "2", "--master_port", str(port), "--no_local_rank",
         script],
        cwd=repo, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "CKPT OK rank=0" in proc.stdout
    assert "CKPT OK rank=1" in proc.stdout
