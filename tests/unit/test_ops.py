"""Pallas kernel parity tests: every kernel in interpret mode vs the jnp
reference (SURVEY.md §4 implication (b)), plus gradient checks via custom VJP.
"""

import importlib
import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import (apply_rotary_pos_emb, bias_act, flash_attention,
                                      fused_adam_update, layer_norm, mha_reference,
                                      rms_norm, rope_angles, scaled_masked_softmax)


# the module, not the function of the same name the package exports
flash_module = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")


def rand(*shape, dtype=jnp.float32, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype=dtype)


class TestLayerNorm:
    @pytest.mark.parametrize("shape", [(4, 128), (2, 8, 256)])
    def test_forward_parity(self, shape):
        x = rand(*shape)
        g = rand(shape[-1], seed=1) * 0.1 + 1.0
        b = rand(shape[-1], seed=2) * 0.1
        ref = layer_norm(x, g, b, 1e-5, "xla")
        out = layer_norm(x, g, b, 1e-5, "interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)

    def test_backward_parity(self):
        x = rand(8, 128)
        g = rand(128, seed=1) * 0.1 + 1.0
        b = rand(128, seed=2) * 0.1

        def loss(impl):
            def f(x, g, b):
                return jnp.sum(layer_norm(x, g, b, 1e-5, impl) ** 2)
            return jax.grad(f, argnums=(0, 1, 2))(x, g, b)

        for got, ref in zip(loss("interpret"), loss("xla")):
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4)

    def test_bf16_io(self):
        x = rand(8, 128).astype(jnp.bfloat16)
        g = jnp.ones(128, jnp.bfloat16)
        b = jnp.zeros(128, jnp.bfloat16)
        out = layer_norm(x, g, b, 1e-5, "interpret")
        assert out.dtype == jnp.bfloat16


class TestRMSNorm:
    def test_forward_parity(self):
        x = rand(6, 256)
        g = rand(256, seed=3) * 0.1 + 1.0
        ref = rms_norm(x, g, 1e-6, "xla")
        out = rms_norm(x, g, 1e-6, "interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)

    def test_backward_parity(self):
        x = rand(4, 128)
        g = rand(128, seed=1) * 0.1 + 1.0

        def grads(impl):
            def f(x, g):
                return jnp.sum(jnp.sin(rms_norm(x, g, 1e-6, impl)))
            return jax.grad(f, argnums=(0, 1))(x, g)

        for got, ref in zip(grads("interpret"), grads("xla")):
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4)


class TestRoPE:
    def test_forward_parity(self):
        B, H, S, D = 2, 4, 16, 64
        x = rand(B, H, S, D)
        cos, sin = rope_angles(jnp.arange(S), D)
        ref = apply_rotary_pos_emb(x, cos, sin, "xla")
        out = apply_rotary_pos_emb(x, cos, sin, "interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)

    def test_norm_preserved(self):
        x = rand(1, 2, 8, 32)
        cos, sin = rope_angles(jnp.arange(8), 32)
        y = apply_rotary_pos_emb(x, cos, sin, "xla")
        # rotation preserves per-pair norms
        np.testing.assert_allclose(np.linalg.norm(np.asarray(x)), np.linalg.norm(np.asarray(y)),
                                   rtol=1e-5)

    def test_backward_is_inverse_rotation(self):
        x = rand(1, 1, 8, 16)
        cos, sin = rope_angles(jnp.arange(8), 16)

        def f(x):
            return jnp.sum(apply_rotary_pos_emb(x, cos, sin, "xla") * 2.0)

        gx = jax.grad(f)(x)
        expected = apply_rotary_pos_emb(jnp.full_like(x, 2.0), cos, -sin, "xla")
        np.testing.assert_allclose(np.asarray(gx), np.asarray(expected), rtol=1e-5, atol=1e-6)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("S", [128, 256])
    def test_forward_parity(self, causal, S):
        B, H, D = 1, 2, 64
        q, k, v = (rand(B, H, S, D, seed=i) for i in range(3))
        ref = mha_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal, None, 64, 64, "interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_backward_parity(self, causal):
        B, H, S, D = 1, 1, 128, 32
        q, k, v = (rand(B, H, S, D, seed=i + 10) for i in range(3))

        def loss_pallas(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal, None, 64, 64, "interpret") ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

        got = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for g, r in zip(got, ref):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=5e-3, atol=5e-4)

    def test_causal_masks_future(self):
        B, H, S, D = 1, 1, 64, 32
        q, k, v = (rand(B, H, S, D, seed=i) for i in range(3))
        out1 = flash_attention(q, k, v, True, None, 32, 32, "interpret")
        # changing future K/V must not affect past outputs
        k2 = k.at[:, :, S // 2:, :].set(0.0)
        v2 = v.at[:, :, S // 2:, :].set(0.0)
        out2 = flash_attention(q, k2, v2, True, None, 32, 32, "interpret")
        np.testing.assert_allclose(np.asarray(out1[:, :, :S // 2]),
                                   np.asarray(out2[:, :, :S // 2]), rtol=1e-5, atol=1e-6)

    def test_alibi_forward_parity(self):
        """ALiBi in-kernel bias == jnp reference with the explicit bias
        tensor (VERDICT r4 item 3: alibi in the flash kernels)."""
        from deepspeed_tpu.models.layers import alibi_bias

        B, H, S, D = 2, 6, 128, 32   # 6 heads: non-power-of-2 slope path
        q, k, v = (rand(B, H, S, D, seed=i) for i in range(3))
        pos = jnp.arange(S)
        bias = alibi_bias(H, pos, pos)[None]
        ref = mha_reference(q, k, v, causal=True, bias=bias)
        out = flash_attention(q, k, v, True, None, 64, 64, "interpret", True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_alibi_backward_parity(self):
        from deepspeed_tpu.models.layers import alibi_bias

        B, H, S, D = 1, 4, 128, 32
        q, k, v = (rand(B, H, S, D, seed=i + 20) for i in range(3))
        pos = jnp.arange(S)
        bias = alibi_bias(H, pos, pos)[None]

        def loss_pallas(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True, None, 64, 64,
                                           "interpret", True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=True, bias=bias) ** 2)

        got = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for g, r in zip(got, ref):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=5e-3, atol=5e-4)


# One case per branch of the tile schedule.  (B, H, S, Sk, D), the limits
# handed to block_q / block_k, then what differs from causal / default scale.
SCHEDULE_CASES = {
    # four diagonal tiles of 64, three Q tiles with plain tiles before them
    "several-diagonal-tiles": ((1, 2, 256, 256, 64), (64, 64), {}),
    "one-tile": ((1, 2, 128, 128, 64), (1024, 1024), {}),
    # B*H = 5: the heads a grid step takes divide B*H, not H
    "five-heads": ((1, 5, 256, 256, 64), (64, 64), {}),
    "head-dim-128": ((1, 2, 256, 256, 128), (64, 64), {}),
    "non-causal": ((1, 2, 256, 256, 64), (64, 64), {"causal": False}),
    "alibi": ((2, 3, 256, 256, 32), (64, 64), {"alibi": True}),
    "cross-length": ((1, 2, 128, 256, 64), (64, 64), {"causal": False}),
    # a diagonal tile of 512 cut into two strips, the second against the
    # upper half of the queries only
    "diagonal-strips": ((1, 2, 512, 512, 64), (512, 512), {}),
    "diagonal-strips-alibi": ((1, 2, 512, 512, 64), (512, 512),
                              {"alibi": True, "sm_scale": 0.2}),
    # ... and the tile under the diagonal walked strip by strip
    "plain-strips": ((1, 1, 1024, 1024, 64), (512, 512), {}),
    "plain-strips-non-causal": ((1, 1, 512, 1024, 32), (512, 512),
                                {"causal": False}),
    # Q and KV tiles differ: the loop over the tiles the diagonal crosses
    "wide-q-tiles": ((1, 2, 256, 256, 64), (128, 64), {}),
    "wide-kv-tiles": ((1, 2, 256, 256, 64), (64, 128), {}),
    # a scale that is no power of two stays on the scores
    "scale-not-folded": ((1, 2, 256, 256, 64), (64, 64), {"sm_scale": 0.3}),
    # K and V in chunks (a budget too small for the sequence), causal
    # steps that skip the chunks the mask removes
    "kv-chunks": ((1, 3, 512, 512, 64), (64, 64), {"budget": 300 * 1024}),
    "kv-chunks-strips": ((1, 2, 1024, 1024, 64), (512, 512),
                         {"budget": 800 * 1024}),
}


def assert_forward_and_gradients(kernel, reference, q, k, v):
    np.testing.assert_allclose(np.asarray(kernel(q, k, v)),
                               np.asarray(reference(q, k, v)),
                               rtol=2e-4, atol=2e-4)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) ** 2), (0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: jnp.sum(reference(*a) ** 2), (0, 1, 2))(q, k, v)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=5e-3, atol=5e-4)


class TestFlashSchedule:
    @pytest.mark.parametrize("case", sorted(SCHEDULE_CASES))
    def test_parity_forward_and_gradients(self, case, monkeypatch):
        (B, H, S, Sk, D), (bq, bk), opts = SCHEDULE_CASES[case]
        causal, alibi = opts.get("causal", True), opts.get("alibi", False)
        scale = opts.get("sm_scale")
        if "budget" in opts:
            monkeypatch.setattr(flash_module, "_VMEM_BLOCK_BYTES", opts["budget"])
            plan = flash_module._plan(B * H, S, Sk, D, 4, bq, bk)
            assert plan.ck < Sk and plan.cq < S
        q = rand(B, H, S, D, seed=1)
        k, v = rand(B, H, Sk, D, seed=2), rand(B, H, Sk, D, seed=3)
        bias = None
        if alibi:
            from deepspeed_tpu.models.layers import alibi_bias

            bias = alibi_bias(H, jnp.arange(S), jnp.arange(Sk))[None]

        def kernel(q, k, v):
            return flash_attention(q, k, v, causal, scale, bq, bk,
                                   "interpret", alibi)

        def reference(q, k, v):
            return mha_reference(q, k, v, causal=causal, sm_scale=scale,
                                 bias=bias)

        assert_forward_and_gradients(kernel, reference, q, k, v)

    @pytest.mark.parametrize("S,Sk", [(512, 256), (256, 512)])
    def test_causal_cross_length_keeps_row_not_before_column(self, S, Sk):
        """Causal with S != Sk: the kernels keep ``row >= column`` counted
        from the first row and column (as before this schedule), whichever
        of the two is longer."""
        q = rand(1, 2, S, 64, seed=1)
        k, v = rand(1, 2, Sk, 64, seed=2), rand(1, 2, Sk, 64, seed=3)
        keep = jnp.arange(S)[:, None] >= jnp.arange(Sk)[None, :]
        bias = jnp.where(keep, 0.0, -1e30)[None, None]

        def kernel(q, k, v):
            return flash_attention(q, k, v, True, None, 128, 128, "interpret")

        def reference(q, k, v):
            return mha_reference(q, k, v, causal=False, bias=bias)

        assert_forward_and_gradients(kernel, reference, q, k, v)

    @pytest.mark.parametrize("S,tile", [(256, 64), (1024, 128), (1024, 512),
                                        (1024, 1024), (4096, 1024)])
    def test_counts_of_square_tiles(self, S, tile):
        """n (n + 1) / 2 of the n^2 tiles visited, n of them masked, and no
        visited tile wholly above the diagonal."""
        sch = flash_module.tile_schedule(S, S, tile, tile, True)
        n = S // tile
        assert (sch["block_q"], sch["block_k"]) == (tile, tile)
        assert sch["tiles"] == n * n
        assert sch["visited"] == n * (n + 1) // 2 and sch["masked"] == n
        assert sch["masked_pairs"] == [(i, i) for i in range(n)]
        for i, j in sch["visited_pairs"]:
            assert j * tile <= (i + 1) * tile - 1
        # a diagonal tile's strips: what is computed beyond the kept half
        # is one strip wide, not one tile
        strip = sch["strip"]
        assert tile % strip == 0 and strip <= 256
        assert sch["kept_elements"] == S * (S + 1) // 2
        assert sch["score_elements"] == (
            n * (n - 1) // 2 * tile * tile
            + n * strip * sum(tile - r for r in range(0, tile, strip)))
        assert sch["score_elements"] - sch["kept_elements"] <= S * (strip + 1) // 2

    def test_non_causal_visits_the_square(self):
        sch = flash_module.tile_schedule(256, 512, 64, 64, False)
        assert sch["visited"] == sch["tiles"] == 4 * 8 and sch["masked"] == 0
        assert sch["score_elements"] == sch["kept_elements"] == 256 * 512

    @pytest.mark.parametrize("bq,bk", [(64, 64), (128, 64), (64, 128),
                                       (192, 64), (64, 192), (128, 384)])
    @pytest.mark.parametrize("S,Sk", [(384, 384), (768, 384), (384, 768)])
    def test_bounds_against_the_mask_itself(self, S, Sk, bq, bk):
        """The loops' bounds, for any pair of tile sizes and lengths, are
        what the mask ``row >= column`` gives element by element: a tile is
        visited iff it keeps something, plain iff it keeps everything; the
        dK/dV kernel's bounds are the same sets seen from the KV tile."""
        keep = np.arange(S)[:, None] >= np.arange(Sk)[None, :]
        nq, nk = S // bq, Sk // bk
        some = np.zeros((nq, nk), bool)
        every = np.zeros((nq, nk), bool)
        for i, j in itertools.product(range(nq), range(nk)):
            block = keep[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
            some[i, j], every[i, j] = block.any(), block.all()
        for i in range(nq):
            plain, visit = flash_module._kv_bounds(i, bq, bk, nk, True)
            assert [j for j in range(nk) if every[i, j]] == list(range(plain))
            assert [j for j in range(nk) if some[i, j]] == list(range(visit))
        for j in range(nk):
            first, plain = flash_module._q_bounds(j, bq, bk, nq, True)
            assert [i for i in range(nq) if some[i, j]] == list(range(first, nq))
            assert [i for i in range(nq) if every[i, j]] == list(range(plain, nq))

    def test_heads_a_step_divide_all_heads(self):
        """25 heads a layer is odd: the heads of a grid step divide B*H."""
        sch = flash_module.tile_schedule(1024, 1024, head_dim=64, heads=400)
        assert (sch["block_q"], sch["block_k"], sch["strip"]) == (1024, 1024, 256)
        assert (sch["visited"], sch["masked"]) == (1, 1)
        assert sch["score_elements"] == 655360      # of 1,048,576; 524,800 kept
        for kernel, hb in sch["heads_per_step"].items():
            assert 400 % hb == 0
            assert sch["grid_steps"][kernel] == 400 // hb
        odd = flash_module.tile_schedule(256, 256, 64, 64, heads=5)
        assert set(odd["heads_per_step"].values()) <= {1, 5}

    @pytest.mark.parametrize("tile,strip", [(1024, 256), (896, 128), (640, 128),
                                            (512, 256), (128, 128), (64, 64),
                                            (1000, 200)])
    def test_strips_of_a_diagonal_tile(self, tile, strip):
        """Strips divide the tile; where they are whole lane tiles, each
        starts its queries at its own first row; together they cover every
        kept score of the tile."""
        blocks = flash_module._strips(tile, tile, True)
        assert flash_module._strip(tile) == strip
        covered = np.zeros((tile, tile), bool)          # [kv, q]
        for kv_lo, kv_n, q_lo, q_n in blocks:
            assert kv_n == strip
            assert q_lo == (kv_lo if strip % 128 == 0 else 0)
            covered[kv_lo:kv_lo + kv_n, q_lo:q_lo + q_n] = True
        keep = np.arange(tile)[:, None] <= np.arange(tile)[None, :]
        assert not (keep & ~covered).any()


class TestSoftmax:
    def test_parity_with_mask(self):
        x = rand(4, 8, 128)
        mask = (rand(4, 8, 128, seed=5) > 0).astype(jnp.int32)
        ref = scaled_masked_softmax(x, mask, 0.5, "xla")
        out = scaled_masked_softmax(x, mask, 0.5, "interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6)

    def test_no_mask(self):
        x = rand(16, 64)
        ref = scaled_masked_softmax(x, None, 1.0, "xla")
        out = scaled_masked_softmax(x, None, 1.0, "interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6)


class TestBiasAct:
    @pytest.mark.parametrize("act", ["gelu", "relu", "silu"])
    def test_parity(self, act):
        x = rand(8, 256)
        b = rand(256, seed=9)
        ref = bias_act(x, b, act, "xla")
        out = bias_act(x, b, act, "interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


class TestFusedAdam:
    # odd sizes exercise the lane padding; the second is long enough for
    # two 512-row blocks, the last one ragged
    @pytest.mark.parametrize("shape", [(257, 33), (601, 129)])
    def test_parity_with_optax(self, shape):
        p = rand(*shape)
        g = rand(*shape, seed=1)
        m = jnp.zeros_like(p)
        v = jnp.zeros_like(p)
        import optax

        tx = optax.adamw(1e-2, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
        st = tx.init(p)
        upd, _ = tx.update(g, st, p)
        ref = optax.apply_updates(p, upd)

        pn, mn, vn = fused_adam_update(p, g, m, v, jnp.asarray(1), lr=1e-2,
                                       weight_decay=0.01, adam_w_mode=True, impl="interpret")
        np.testing.assert_allclose(np.asarray(pn), np.asarray(ref), rtol=1e-5, atol=1e-6)

    def test_xla_equals_interpret(self):
        p = rand(100)
        g = rand(100, seed=2)
        m = jnp.zeros_like(p); v = jnp.zeros_like(p)
        a = fused_adam_update(p, g, m, v, jnp.asarray(3), lr=1e-3, impl="xla")
        b = fused_adam_update(p, g, m, v, jnp.asarray(3), lr=1e-3, impl="interpret")
        for x, y in zip(a, b):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6, atol=1e-7)

    def test_engine_uses_fused_adam(self, monkeypatch):
        """FusedAdam type in ds_config routes to the Pallas update kernel
        (not a silent optax.adamw fallback) and trains via the engine."""
        import deepspeed_tpu
        import deepspeed_tpu.ops.adam.fused_adam as fa_mod
        from deepspeed_tpu.ops.adam.fused_adam import FusedAdamState
        from tests.unit.simple_model import SimpleModel, random_dataset

        calls = []
        real = fa_mod.fused_adam_update

        def spy(*args, **kwargs):
            calls.append(1)
            return real(*args, **kwargs)

        monkeypatch.setattr(fa_mod, "fused_adam_update", spy)

        x, y = random_dataset()
        cfg = {"train_micro_batch_size_per_gpu": 1,
               "optimizer": {"type": "FusedAdam", "params": {"lr": 1e-2}}}
        engine, _, loader, _ = deepspeed_tpu.initialize(model=SimpleModel(), config=cfg,
                                                        training_data=(x, y))
        from deepspeed_tpu.runtime.dataloader import RepeatingLoader

        it = iter(RepeatingLoader(loader))
        losses = [float(engine.train_batch(it)) for _ in range(10)]
        assert losses[-1] < losses[0]
        assert isinstance(engine.state.opt_state, FusedAdamState), \
            "FusedAdam config did not build the fused transformation"
        assert calls, "Pallas fused_adam_update kernel was never traced"

    def test_engine_fused_adam_matches_adamw(self):
        """Fused kernel numerics track the plain optax path through the
        engine (same data, same seeds)."""
        import deepspeed_tpu
        from tests.unit.simple_model import SimpleModel, random_dataset
        from deepspeed_tpu.runtime.dataloader import RepeatingLoader

        losses = {}
        for typ in ("FusedAdam", "AdamW"):
            x, y = random_dataset()
            cfg = {"train_micro_batch_size_per_gpu": 1,
                   "optimizer": {"type": typ,
                                 "params": {"lr": 1e-2, "weight_decay": 0.01}}}
            engine, _, loader, _ = deepspeed_tpu.initialize(
                model=SimpleModel(), config=cfg, training_data=(x, y))
            it = iter(RepeatingLoader(loader))
            losses[typ] = [float(engine.train_batch(it)) for _ in range(5)]
        np.testing.assert_allclose(losses["FusedAdam"], losses["AdamW"],
                                   rtol=2e-4, atol=1e-5)

    def test_muon_optimizer_trains(self):
        """"Muon" config type (previously a phantom import) builds and trains."""
        import deepspeed_tpu
        from tests.unit.simple_model import SimpleModel, random_dataset
        from deepspeed_tpu.runtime.dataloader import RepeatingLoader

        x, y = random_dataset()
        cfg = {"train_micro_batch_size_per_gpu": 1,
               "optimizer": {"type": "Muon", "params": {"lr": 2e-2}}}
        engine, _, loader, _ = deepspeed_tpu.initialize(model=SimpleModel(), config=cfg,
                                                        training_data=(x, y))
        it = iter(RepeatingLoader(loader))
        losses = [float(engine.train_batch(it)) for _ in range(10)]
        assert losses[-1] < losses[0]


def test_norm_backward_multiblock_grid():
    """rows > 256 exercises the multi-step grid accumulation of dgamma/dbeta
    (zero-on-first-step + VMEM '+=' across sequential grid steps)."""
    import jax, numpy as np
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas import layer_norm, rms_norm

    rng = jax.random.PRNGKey(7)
    x = jax.random.normal(rng, (512, 128), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(8), (128,)) * 0.1 + 1.0
    b = jax.random.normal(jax.random.PRNGKey(9), (128,)) * 0.1

    def loss_pallas(x, g, b):
        return jnp.sum(layer_norm(x, g, b, 1e-5, "interpret") ** 2)

    def loss_xla(x, g, b):
        return jnp.sum(layer_norm(x, g, b, 1e-5, "xla") ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(x, g, b)
    gx = jax.grad(loss_xla, argnums=(0, 1, 2))(x, g, b)
    for a, e in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e), rtol=2e-4, atol=2e-4)

    def rms_pallas(x, g):
        return jnp.sum(rms_norm(x, g, 1e-6, "interpret") ** 2)

    def rms_xla(x, g):
        return jnp.sum(rms_norm(x, g, 1e-6, "xla") ** 2)

    gp = jax.grad(rms_pallas, argnums=(0, 1))(x, g)
    gx = jax.grad(rms_xla, argnums=(0, 1))(x, g)
    for a, e in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e), rtol=2e-4, atol=2e-4)


class TestFusedLamb:
    """Fused LAMB kernel parity (reference: csrc/lamb; SURVEY.md §2.2)."""

    # 300: one padded block; 70001: two 512-row blocks, the last ragged
    # (what it reads past the end must stay out of the trust-ratio norms)
    @pytest.mark.parametrize("n", [300, 70001])
    def test_kernel_matches_xla_reference(self, rng, n):
        from deepspeed_tpu.ops.pallas.fused_lamb import fused_lamb_update

        p = jax.random.normal(rng, (n,)) * 0.1
        g = jax.random.normal(jax.random.fold_in(rng, 1), (n,))
        m = jnp.zeros((n,), jnp.float32)
        v = jnp.zeros((n,), jnp.float32)
        step = jnp.asarray(1, jnp.int32)
        for i in range(3):
            step = jnp.asarray(i + 1, jnp.int32)
            ref = fused_lamb_update(p, g, m, v, step, lr=1e-2,
                                    weight_decay=0.01, impl="xla")
            ker = fused_lamb_update(p, g, m, v, step, lr=1e-2,
                                    weight_decay=0.01, impl="interpret")
            for a, b in zip(ref, ker):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-6)
            p, m, v = ref

    def test_engine_routes_fusedlamb(self):
        from tests.unit.simple_model import SimpleModel, random_dataset
        import deepspeed_tpu

        x, y = random_dataset(n=16)
        cfg = {"train_micro_batch_size_per_gpu": 1,
               "optimizer": {"type": "FusedLamb", "params": {"lr": 5e-3}}}
        engine, opt, _, _ = deepspeed_tpu.initialize(
            model=SimpleModel(hidden_dim=16), config=cfg,
            rng=jax.random.PRNGKey(0))
        from deepspeed_tpu.ops.pallas.fused_lamb import FusedLambState

        assert isinstance(engine.state and engine.state.opt_state
                          or opt.init({"w": jnp.ones((2,))}), object)
        losses = []
        for _ in range(8):
            loss = engine.forward((x[:8], y[:8]))
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        assert losses[-1] < losses[0], losses
        assert isinstance(engine.state.opt_state, FusedLambState)


class TestDeepSpeedTransformerLayer:
    """Fused encoder layer (reference: ops/transformer; SURVEY.md §2.1)."""

    def test_forward_backward_and_mask(self, rng):
        from deepspeed_tpu.ops.transformer import (DeepSpeedTransformerConfig,
                                                   DeepSpeedTransformerLayer)

        cfg = DeepSpeedTransformerConfig(hidden_size=64, intermediate_size=128,
                                         heads=4)
        layer = DeepSpeedTransformerLayer(cfg)
        p = layer.init(rng)
        x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 16, 64))
        y = jax.jit(layer.apply)(p, x)
        assert y.shape == x.shape
        g = jax.grad(lambda p: layer.apply(p, x).astype(jnp.float32).sum())(p)
        assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))
        # key padding mask: masked keys must not influence real positions
        mask = np.ones((2, 16), np.int32)
        mask[:, 8:] = 0
        y_mask = layer.apply(p, x, attention_mask=jnp.asarray(mask))
        x2 = x.at[:, 8:].set(0.0)  # change padded content
        y_mask2 = layer.apply(p, x2, attention_mask=jnp.asarray(mask))
        np.testing.assert_allclose(np.asarray(y_mask[:, :8]),
                                   np.asarray(y_mask2[:, :8]),
                                   rtol=1e-4, atol=1e-5)


class TestQuantizerKernels:
    """Pallas block quant/dequant (reference: csrc/quantization)."""

    @pytest.mark.parametrize("bits", [8, 4])
    def test_roundtrip_error_bound(self, rng, bits):
        from deepspeed_tpu.ops.pallas.quantizer import dequantize, quantize

        x = jax.random.normal(rng, (5000,)) * 2.0
        q, scale, pad = quantize(x, bits=bits, block=256, impl="interpret")
        out = dequantize(q, scale, pad, x.shape)
        qmax = 127 if bits == 8 else 7
        bound = float(jnp.abs(x).max()) / qmax + 1e-6
        assert np.abs(np.asarray(out - x)).max() <= bound

    # 4096: 8 blocks in one row tile; 300000: 586 blocks over three row
    # tiles, the last padded with zero blocks that are sliced off again
    @pytest.mark.parametrize("n", [4096, 300000])
    def test_kernel_matches_xla(self, rng, n):
        from deepspeed_tpu.ops.pallas.quantizer import quantize

        x = jax.random.normal(rng, (n,))
        qk, sk, _ = quantize(x, block=512, impl="interpret")
        qx, sx, _ = quantize(x, block=512, impl="xla")
        assert qk.shape == qx.shape and sk.shape == sx.shape
        np.testing.assert_array_equal(np.asarray(qk), np.asarray(qx))
        np.testing.assert_allclose(np.asarray(sk), np.asarray(sx), rtol=1e-6)

    def test_int4_pack_roundtrip(self, rng):
        from deepspeed_tpu.ops.pallas.quantizer import (pack_int4, quantize,
                                                        unpack_int4)

        x = jax.random.normal(rng, (999,))
        q, scale, pad = quantize(x, bits=4, block=256, impl="xla")
        packed = pack_int4(q)
        restored = unpack_int4(packed, q.size).reshape(q.shape)
        np.testing.assert_array_equal(np.asarray(restored), np.asarray(q))
