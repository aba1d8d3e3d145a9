"""``mla_chunk_attention`` (``ops/pallas/flash_attention.py``), the latent
layers' prefill-chunk attention, in interpret mode on the CPU against its
reference ``afmoe.attend(expand=mla_decompress)`` at lane-tile widths: every
branch of its schedule (a chunk at position 0, at a strip's first row, inside
a strip, over several blocks of rows, a bucket under one lane tile of
queries), rows past the chunk that must not count, its counter's source and
its refusals."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import ModelConfig, afmoe, kda_mla

fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")

KV, N, R, V, W = 128, 128, 64, 128, 256     # latent, nope, rot, value, row
ROPE = {"theta": 10000, "factor": 32, "original_max_position_embeddings": 64,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}


def _cfg(heads, rope):
    """The latent KIND (``MlaKind``) whose sizes ``kda_mla``'s pieces read."""
    return ModelConfig(
        vocab_size=96, hidden_size=64, num_layers=1, num_heads=heads,
        max_seq_len=4096, layer_types=("latent_attention",),
        num_dense_layers=1, dense_intermediate_size=64,
        moe_drop_tokens=False, mla_kv_rank=KV, mla_nope_dim=N, mla_rot_dim=R,
        mla_v_dim=V, mla_rope=rope).mla_kind("latent_attention")


def _inputs(cfg, s, rows, start, dtype, seed):
    """q [s, H, n + r], the slot's view [rows, W] and the layer's weights:
    queries and the shared key part rotated where the model rotates, zeros
    in a row's pad, and GARBAGE at and past ``start + s``."""
    H = cfg.heads
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    a = {"wkvb": (jax.random.normal(ks[0], (KV, H * (N + V))) * KV ** -0.5
                  ).astype(dtype)}
    q = kda_mla.mla_query(
        cfg, jax.random.normal(ks[1], (s, H * (N + R))).astype(dtype),
        start + jnp.arange(s))
    c = jax.random.normal(ks[2], (rows, KV)).astype(dtype)
    k_r = kda_mla.rotate(cfg, jax.random.normal(ks[3], (rows, R)).astype(
        dtype), jnp.arange(rows))
    view = jnp.concatenate(
        [c, k_r, jnp.zeros((rows, W - KV - R), dtype)], axis=-1)
    return q, view.at[start + s:].set(3e4), a


def _attend(cfg, a, q, view, start):
    """What ``kda_mla.cached_layers`` called before the kernel."""
    s = q.shape[0]
    return afmoe.attend(
        q.transpose(1, 0, 2)[None],
        [(view[None, None], None, jnp.arange(view.shape[0]))],
        start + jnp.arange(s), window=0, scale=kda_mla._mla_scale(cfg),
        live_keys=start + s, expand=lambda rb: tuple(
            t[:, 0].transpose(0, 2, 1, 3)
            for t in kda_mla.mla_decompress(rb, *kda_mla._wkvb(cfg, a),
                                            cfg.rot))
    )[0].transpose(1, 0, 2)


# bucket, view rows, start: the cell's buckets 256 / 512 / 1,024 over blocks
# of 1,024 rows scaled down to lane tiles (a block is then the view)
CASES = [
    pytest.param(128, 512, 0, id="at_zero"),
    pytest.param(128, 512, 128, id="one_bucket_in"),
    pytest.param(128, 768, 384, id="several_buckets_in"),
    pytest.param(256, 1024, 512, id="bucket_of_two_strips"),
    pytest.param(512, 2048, 1024, id="second_block_of_rows"),
    pytest.param(512, 2048, 768, id="diagonal_across_two_blocks"),
    pytest.param(128, 512, 72, id="inside_a_strip"),
    pytest.param(256, 2048, 1000, id="inside_a_strip_across_blocks"),
    pytest.param(16, 256, 48, id="bucket_under_a_lane_tile"),
    pytest.param(16, 256, 240, id="padded_queries_past_the_view"),
]


@pytest.mark.parametrize("rotated", [True, False], ids=["yarn", "unrotated"])
@pytest.mark.parametrize("H", [4, 2], ids=["heads_64_over_16",
                                           "heads_32_over_16"])
@pytest.mark.parametrize("s,rows,start", CASES)
def test_kernel_is_attend_over_decompressed_rows(s, rows, start, H, rotated):
    """A.X-K1's 64 heads and Kimi's 32, a sixteenth of each (the head count
    sets the grid's first extent and the heads a step, nothing else), over
    rotated (YaRN) and unrotated rows, through the kernel and through
    ``attend(expand=)``."""
    cfg = _cfg(H, ROPE if rotated else None)
    q, view, a = _inputs(cfg, s, rows, start, jnp.float32, seed=s + start)
    sch = fa.mla_chunk_schedule(start, s, rows, heads=H, nope=N, rot=R,
                                v_dim=V, row_width=W, itemsize=4, kv=KV,
                                impl="interpret")
    assert sch["reason"] is None
    got = fa.mla_chunk_attention(
        q, jnp.stack([view * 0, view]), a["wkvb"].reshape(KV, H, N + V),
        jnp.int32(start), nope=N, scale=kda_mla._mla_scale(cfg), layer=1,
        impl="interpret")
    want = _attend(cfg, a, q, view, start)
    assert got.shape == want.shape == (s, H, V)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_bf16_rounds_keys_values_and_probabilities_as_attend_does():
    """In bf16 the two differ by the step of the online softmax alone (a
    strip against a key block): a few bf16 steps of the output's size."""
    cfg = _cfg(4, ROPE)
    q, view, a = _inputs(cfg, 256, 1024, 512, jnp.bfloat16, seed=3)
    got = fa.mla_chunk_attention(
        q, view[None], a["wkvb"].reshape(KV, 4, N + V), jnp.int32(512),
        nope=N, scale=kda_mla._mla_scale(cfg), impl="interpret")
    want = _attend(cfg, a, q, view, 512)
    assert got.dtype == want.dtype == jnp.bfloat16
    f32 = lambda t: np.asarray(t, np.float32)
    assert np.abs(f32(got) - f32(want)).max() <= 2 ** -6 * np.abs(
        f32(want)).max()


@pytest.mark.parametrize("s,rows,start", CASES)
def test_schedule_counts_the_rows_the_kernels_bounds_walk(s, rows, start):
    kw = dict(heads=4, nope=N, rot=R, v_dim=V, row_width=W, kv=KV)
    sch = fa.mla_chunk_schedule(start, s, rows, impl="interpret", **kw)
    p = fa._mla_plan(s, rows, 4, KV, N, R, V, W, 2)
    assert (sch["block_q"], sch["strip"], sch["strip_under_the_chunk"],
            sch["rows_per_step"]) == p[:4]
    long, plain, aligned, visit, blocks = fa._mla_bounds(start, p, rows)
    # by hand from the same bounds: the strips under the first query, then
    # the strips that hold a row some (padded) query sees
    walked = [t for t in range(rows // p.sk)
              if t * p.sk <= start + p.bq - 1]
    assert sch["visited"] == len(walked) * p.sk == visit * p.sk
    assert plain == sum((t + 1) * p.sk <= start for t in walked)
    assert long == sum((u + 1) * p.sp <= start for u in range(rows // p.sp))
    assert sch["grid_steps"] == 4 // p.hb * blocks == 4 // p.hb * len(
        {t * p.sk // p.kb for t in walked})
    assert aligned == (start % p.sk == 0)
    assert start + s <= sch["visited"] <= min(
        rows, start + p.bq + p.sk - 1)
    # where the reference runs, its key blocks
    ref = fa.mla_chunk_schedule(start, s, rows, impl="xla", **kw)
    assert ref == {"visited": afmoe.keys_visited(rows, start + s),
                   "reason": "impl is xla"}


def test_schedule_at_the_cells_sizes():
    """A.X-K1's cell: a 1,024-row chunk walks what ``attend`` walked, a
    last chunk's smaller bucket stops at its own strip."""
    kw = dict(heads=64, kv=512, nope=128, rot=64, v_dim=128, row_width=640,
              impl="pallas")
    at = lambda start, s: fa.mla_chunk_schedule(start, s, 16384, **kw)
    assert at(0, 1024)["visited"] == 1024
    assert at(7168, 1024)["visited"] == 8192
    assert (at(7168, 256)["visited"], at(7168, 8)["visited"]) == (7424, 7296)
    assert at(15360, 1024)["visited"] == 16384
    sch = at(7168, 1024)
    assert (sch["block_q"], sch["strip"], sch["strip_under_the_chunk"],
            sch["rows_per_step"], sch["heads_per_step"], sch["grid_steps"]
            ) == (1024, 256, 512, 1024, 2, 32 * 8)
    # the fewer the queries, the longer a strip under the chunk
    assert [at(7168, s)["strip_under_the_chunk"] for s in (512, 256, 8)] \
        == [1024, 1024, 1024]
    assert fa.mla_chunk_schedule(0, 1024, 13312, **dict(kw, heads=32))[
        "heads_per_step"] == 2


@pytest.mark.parametrize("sizes,words", [
    (dict(rows=1000), "view of 1000 is not a multiple of the 128-lane tile"),
    (dict(n=96), "nope dim of 96 is not a multiple"),
    (dict(v=64), "value dim of 64 is not a multiple"),
    (dict(row_width=200), "row width of 200 is not a multiple"),
    (dict(kv=96), "latent rank of 96 is not a multiple"),
    (dict(r=192), "rotary part of 192 does not fit the 128 values"),
    (dict(s=2048), "chunk of 2048 queries is not one Q tile of 1024"),
    (dict(kv=8192, row_width=8320), "take over the 8388608 bytes of VMEM"),
])
def test_refusals_by_their_words(sizes, words):
    base = dict(s=128, rows=1024, H=4, kv=128, n=128, r=64, v=128,
                row_width=256, itemsize=2)
    assert fa.mla_chunk_reference_reason(**base) is None
    assert words in fa.mla_chunk_reference_reason(**dict(base, **sizes))


def test_refused_sizes_run_attend_and_say_so_once():
    from deepspeed_tpu.ops.pallas.common import reference_selections

    cfg = ModelConfig(
        vocab_size=96, hidden_size=64, num_layers=1, num_heads=2,
        layer_types=("latent_attention",), num_dense_layers=1,
        dense_intermediate_size=64, moe_drop_tokens=False, mla_kv_rank=32,
        mla_nope_dim=16, mla_rot_dim=8, mla_v_dim=16).mla_kind(
            "latent_attention")
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (8, 2, 24))
    view = jax.random.normal(ks[1], (96, 128))
    a = {"wkvb": jax.random.normal(ks[2], (32, 2 * 32))}
    got = fa.mla_chunk_attention(
        q, view[None], a["wkvb"].reshape(32, 2, 32), 16, nope=16,
        scale=kda_mla._mla_scale(cfg), impl="interpret")
    np.testing.assert_array_equal(got, _attend(cfg, a, q, view, 16))
    assert any(op == "mla_chunk_attention" and "latent rank of 32" in why
               for op, why in reference_selections())
