"""Training chaos matrix (slow; ``make chaos``): the ISSUE 14 elastic
resilience scenarios at larger-than-tier-1 scale — a randomized
kill-at-byte sweep across an elastic save/resume cycle, and a
multi-round gradient-bomb campaign with world changes between rounds.
The fast tier-1 chaos coverage lives in
``tests/unit/test_elastic_train.py`` / ``test_anomaly.py`` /
``test_resilience.py``."""

import os

import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import build_mesh, set_global_mesh
from deepspeed_tpu.runtime.checkpoint_engine import atomic
from deepspeed_tpu.testing import chaos
from tests.unit.simple_model import SimpleModel, random_dataset

pytestmark = pytest.mark.slow

X, Y = random_dataset(n=64)
TBS = 8


def _engine(devs, gas, save_dir=None, stage=2):
    mesh = build_mesh(devices=jax.devices()[:devs])
    set_global_mesh(mesh)
    cfg = {"train_micro_batch_size_per_gpu": 1,
           "gradient_accumulation_steps": gas,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "zero_optimization": {"stage": stage},
           "steps_per_print": 10**9}
    if save_dir is not None:
        cfg["anomaly_detection"] = {"enabled": True, "factor": 6.0,
                                    "window": 16, "warmup": 3,
                                    "patience": 2, "save_dir": save_dir}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=16), config=cfg, mesh=mesh,
        rng=jax.random.PRNGKey(3))
    return engine


def _steps(engine, n, start=0):
    for i in range(start, start + n):
        gas = engine.config.gradient_accumulation_steps
        per = TBS // gas
        for g in range(gas):
            lo = ((i % 4) * TBS + g * per) % 56
            engine.forward((X[lo:lo + per], Y[lo:lo + per]))
        engine.step()


def test_chaos_matrix_random_kill_sweep_elastic_cycle(tmp_path):
    """Randomized kill-at-byte sweep ACROSS world changes: every crashed
    save leaves the previous tag loadable, and each survivor resumes at
    a DIFFERENT world (4 -> 2 -> 8 -> 4) with the trajectory intact."""
    rng = np.random.default_rng(11)
    save_dir = str(tmp_path)
    worlds = [4, 2, 8, 4]
    e = _engine(worlds[0], gas=2)
    _steps(e, 2)
    e.save_checkpoint(save_dir, tag="gen0")
    prev_tag = "gen0"
    for gen, devs in enumerate(worlds[1:], start=1):
        # a crashed save at a random byte offset leaves debris only
        total = sum(os.path.getsize(os.path.join(root, f))
                    for root, _d, fs in os.walk(os.path.join(save_dir,
                                                             prev_tag))
                    for f in fs)
        with pytest.raises(chaos.InjectedFault):
            with chaos.crash_on_write(int(rng.integers(0, total)), save_dir):
                e.save_checkpoint(save_dir, tag=f"crash{gen}")
        assert atomic.read_latest(save_dir) == prev_tag
        # the next incarnation comes up at a different world and resumes
        e = _engine(devs, gas=2)
        e.forward((X[:devs], Y[:devs]))
        ckpt_dir, _ = e.load_checkpoint(save_dir)
        assert ckpt_dir is not None and ckpt_dir.endswith(prev_tag)
        assert e.config.train_batch_size == TBS
        _steps(e, 2, start=2 * gen)
        tag = f"gen{gen}"
        e.save_checkpoint(save_dir, tag=tag)
        assert atomic.verify_dir(os.path.join(save_dir, tag),
                                 level="full").ok
        assert atomic.deep_verify(os.path.join(save_dir, tag)) == []
        prev_tag = tag


def test_chaos_matrix_bomb_rounds_with_world_change(tmp_path):
    """Multi-round gradient-bomb campaign: each round bombs past the
    patience threshold, the ladder rolls back, training re-converges,
    and the NEXT round runs at a different world size off the same
    checkpoint chain."""
    save_dir = str(tmp_path)
    for round_idx, devs in enumerate((4, 2)):
        e = _engine(devs, gas=2, save_dir=save_dir)
        if round_idx == 0:
            _steps(e, 4)
        else:
            e.forward((X[:devs], Y[:devs]))
            ckpt_dir, _ = e.load_checkpoint(save_dir)
            assert ckpt_dir is not None
            _steps(e, 2, start=4)
        e.save_checkpoint(save_dir, tag=f"good{round_idx}")
        p0 = jax.tree.map(lambda a: np.array(a),
                          jax.device_get(e.state.params))
        with chaos.gradient_bomb(e, scale=1e18, on_call=1, n=6):
            _steps(e, 3, start=10)
        # contained: params equal the round's good tag
        for a, b in zip(jax.tree.leaves(p0),
                        jax.tree.leaves(jax.device_get(e.state.params))):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b, np.float32))
        assert e._anomaly.rollbacks >= 1
        _steps(e, 2, start=20)          # re-converges post-rollback
        assert e._anomaly.consecutive == 0
