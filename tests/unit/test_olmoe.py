"""OLMoE through the program against its plain reference
(``benchmarks/reference/olmoe.py``), at a tiny size on the CPU: QK-norm, a
router that may leave its top-k weights unnormalised, the dropless
sorted-and-grouped expert block, and the expert block of the fused decode
path (``fused_moe_mlp``, interpret mode).

Float32 program against float32 reference, so no routing decision can flip
on a rounding.  TOLERANCE: ``rtol`` 2e-4 (with an ``atol`` of 2e-5 for logits
near zero) on logits.  Both sides are float32, but a token's output is a sum
of k expert outputs and of matmul partial sums which the two sides add in
another order (grouped rows, kernel tiles, chunks of a prefill), through two
layers and a 64-wide head.  A bf16 expert matmul would miss it by 30x: bf16
keeps 8 bits, 4e-3 a product.
"""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import build_mesh
from deepspeed_tpu.models import CausalLM, ModelConfig, get_model_config
from deepspeed_tpu.moe.sharded_moe import moe_mlp
from deepspeed_tpu.ops.pallas import common
from deepspeed_tpu.ops.pallas.decode import fused_moe_mlp
from tests.unit._serving import with_noise

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RTOL, ATOL = 2e-4, 2e-5
VOCAB = 256


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "benchmarks", *rel.split("/")))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference/olmoe.py", "_ref_olmoe")
taps_lib = _load("lib/serve_taps.py", "_serve_taps")


def tiny(top_k=2, **over):
    fields = dict(vocab_size=VOCAB, hidden_size=64, intermediate_size=32,
                  num_layers=2, num_heads=4, num_kv_heads=4, head_dim=16,
                  max_seq_len=512, num_experts=8, num_experts_per_tok=top_k,
                  moe_drop_tokens=False, moe_norm_topk_prob=False,
                  qk_norm=True, moe_aux_loss_coef=0.0)
    fields.update(over)
    return ModelConfig(**fields)


def ref_config(cfg):
    """The keys of the HF configuration the reference reads."""
    return dict(num_hidden_layers=cfg.num_layers,
                num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.num_kv_heads,
                rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
                num_experts_per_tok=cfg.num_experts_per_tok,
                norm_topk_prob=cfg.moe_norm_topk_prob, qk_norm=cfg.qk_norm)


def seeded(model, seed=0):
    """Weights with every norm scale off 1, so a dropped scale shows."""
    return with_noise(model.init(jax.random.PRNGKey(seed)), seed + 1)


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n, dtype=np.int32)


def ref_logits(params, cfg, tokens, rows):
    return np.asarray(ref.logits_rows(params, ref_config(cfg), tokens,
                                      list(rows), jax.devices()[0]))


# -- (a), (f), (g): the full forward ------------------------------------
@pytest.mark.parametrize("case", ["top2", "top4", "norm_topk", "no_qk_norm"])
def test_full_forward_logits_match_the_reference(case):
    cfg = {"top2": tiny(2), "top4": tiny(4),
           "norm_topk": tiny(2, moe_norm_topk_prob=True),
           "no_qk_norm": tiny(2, qk_norm=False)}[case]
    model = CausalLM(cfg, None)
    params = seeded(model)
    toks = tokens_of(48)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, toks[None]))[0]
    np.testing.assert_allclose(got, ref_logits(params, cfg, toks, range(48)),
                               rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("field", ["qk_norm", "moe_norm_topk_prob"])
def test_each_new_field_changes_the_logits(field):
    """The two fields describe the model: flipped on the SAME weights they
    give another function (the reference follows either way, above)."""
    model = CausalLM(tiny(), None)
    params = seeded(model)
    other = tiny(**{field: not getattr(model.config, field)})
    toks = tokens_of(32)[None]
    a = np.asarray(model.apply(params, toks))
    b = np.asarray(CausalLM(other, None).apply(params, toks))
    assert np.abs(a - b).max() > 1e-2


def test_qk_norm_under_tp_raises(devices):
    mesh = build_mesh(tp=2, devices=devices[:2])
    model = CausalLM(tiny(), mesh)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="qk_norm with tp > 1"):
        model.apply(params, tokens_of(16)[None])


# -- (b): gradients through the grouped path ----------------------------
def test_loss_gradients_match_the_reference():
    cfg = tiny(2)
    model = CausalLM(cfg, None)
    params = seeded(model)
    toks = tokens_of(40)

    def ref_loss(p):
        logits = ref.logits_rows(p, ref_config(cfg), toks, list(range(39)),
                                 jax.devices()[0])
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return jnp.mean(lse - logits[jnp.arange(39), toks[1:]])

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: model.apply(p, toks[None], toks[None]))(params)
        want_loss, want = jax.value_and_grad(ref_loss)(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want)):
        scale = float(jnp.abs(w).max())
        assert scale > 0, path                 # every leaf takes part
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-3,
                                   atol=1e-4 * scale, err_msg=str(path))


# -- (c), (f): serving, prefill in chunks + paged decode, fused path -----
@pytest.fixture
def interpret_kernels(monkeypatch):
    monkeypatch.setattr(common, "default_impl", lambda: "interpret")


SERVE_CASES = {
    "olmoe-top2": lambda: tiny(2),
    "olmoe-top4": lambda: tiny(4),
    # the preset's router renormalises (moe_norm_topk_prob=True); cut to two
    # layers and a small vocabulary for the interpreter, and dropless: the
    # preset's capacity of 1.25 drops tokens in prefill, which no reference
    # does
    "mixtral-tiny": lambda: get_model_config(
        "mixtral-tiny", num_layers=2, vocab_size=VOCAB, max_seq_len=512,
        moe_drop_tokens=False),
}


@pytest.mark.parametrize("case", sorted(SERVE_CASES))
def test_served_logits_match_the_reference_at_every_generated_position(
        case, devices, interpret_kernels):
    """Through ``ServingEngine.step()``: chunked prefill (the grouped path)
    then the paged decode block (``fused_moe_mlp``), with requests of
    unequal length, a prompt of three chunks that crosses a page, slots
    that park while others run and one never used; each chunk is long
    enough to overflow capacity 1.25."""
    cfg = SERVE_CASES[case]()
    mesh = build_mesh(devices=devices[:1])
    model = CausalLM(cfg, mesh)
    params = seeded(model)
    prompts = [tokens_of(150, 1), tokens_of(20, 2), tokens_of(37, 3)]
    new = [6, 9, 4]
    with taps_lib.ServeTaps() as taps:
        serve = deepspeed_tpu.init_serving(
            model, params=params, mesh=mesh,
            config={"dtype": "float32", "num_slots": 4, "prefill_chunk": 64,
                    "decode_block_tokens": 4, "max_out_tokens": 256,
                    "kv_page_tokens": 128, "kv_pool_tokens": 1024})
        assert serve.engine._dparams is not None          # the fused path
        experts = serve.engine._dparams["experts"]
        assert all(experts[k] is serve.engine._params["layers"]["mlp"][k]
                   for k in experts)                      # resident once
        served = taps_lib.serve_and_read(taps, serve, prompts, new)
        serve.pool.check_no_leak()
        serve.close()
    rcfg = ref_config(cfg)
    for rec, prompt, n in zip(served, prompts, new):
        assert len(rec["tokens"]) == n
        seq = np.concatenate([prompt, np.asarray(rec["tokens"], np.int32)])
        rows = range(len(prompt) - 1, len(seq) - 1)
        want = ref_logits(params, cfg, seq, rows)
        np.testing.assert_allclose(rec["logits"], want, rtol=RTOL, atol=ATOL)
        # float32 both sides: the program's routers chose as the reference's
        _, _, chosen = ref.hidden_states(params, rcfg, seq[:-1],
                                         jax.devices()[0],
                                         return_routing=True)
        for l, mine in enumerate(rec["routing"]):
            assert (np.sort(mine, -1) == np.sort(chosen[l], -1)).all()
        # and the reference, handed the program's choices, agrees too
        given = np.asarray(ref.logits_rows(
            params, rcfg, seq[:-1], list(rows), jax.devices()[0],
            routing=rec["routing"]))
        np.testing.assert_allclose(rec["logits"], given, rtol=RTOL, atol=ATOL)


def test_decode_block_counts_routing_of_live_rows(devices, interpret_kernels):
    from deepspeed_tpu.monitor.metrics import MetricsRegistry

    cfg = tiny(2)
    mesh = build_mesh(devices=devices[:1])
    model = CausalLM(cfg, mesh)
    reg = MetricsRegistry().enable()
    serve = deepspeed_tpu.init_serving(
        model, params=seeded(model), mesh=mesh, registry=reg,
        config={"dtype": "float32", "num_slots": 4, "prefill_chunk": 64,
                "decode_block_tokens": 4, "max_out_tokens": 256,
                "kv_page_tokens": 128, "kv_pool_tokens": 1024})
    serve.submit(tokens_of(12, 5), max_new_tokens=9)
    serve.submit(tokens_of(9, 6), max_new_tokens=5)
    serve.run()
    serve.close()
    value = lambda name: reg.get("ds_serve_moe_" + name).value
    # decode steps run: 8 + 4 tokens after each request's first, 2 experts
    # a token a layer over 2 layers
    assert value("assignments_total") == (8 + 4) * 2 * 2
    blocks = value("expert_slots_total") / (8 * 2 * 4)
    assert blocks == int(blocks) and blocks >= 2
    assert 0 < value("expert_hits_total") <= value("assignments_total")
    # the fullest expert holds between its fair share and every live row
    assert value("assignments_total") / 8 <= value("max_load_total") <= 12 * 2
    # a tiny expert is one block of the kernel's: the gauge says so
    assert value("expert_block_share") == 100.0
    # four float32 rows go through the kernel in one pass: a tile a call,
    # worked (2 layers x 4 steps a block)
    assert value("row_tiles_total") == value("row_tiles_worked_total") \
        == blocks * 2 * 4


def test_decode_block_counts_the_row_tiles_the_kernel_worked(
        devices, interpret_kernels):
    """256 bf16 slots: ``fused_moe_mlp`` cuts its MXU passes to the live
    rows' tiles, and the block's program counts them: two tiles a call, of
    which two requests' rows fill one while either decodes and none after."""
    from deepspeed_tpu.monitor.metrics import MetricsRegistry

    cfg = tiny(2)
    mesh = build_mesh(devices=devices[:1])
    model = CausalLM(cfg, mesh)
    reg = MetricsRegistry().enable()
    serve = deepspeed_tpu.init_serving(
        model, params=seeded(model), mesh=mesh, registry=reg,
        config={"dtype": "bfloat16", "num_slots": 256, "prefill_chunk": 64,
                "decode_block_tokens": 4, "max_out_tokens": 256,
                "kv_page_tokens": 128, "kv_pool_tokens": 1024})
    serve.submit(tokens_of(12, 5), max_new_tokens=9)
    serve.submit(tokens_of(9, 6), max_new_tokens=5)
    serve.run()
    serve.close()
    value = lambda name: reg.get("ds_serve_moe_" + name).value
    blocks = value("expert_slots_total") / (8 * 2 * 4)
    assert value("row_tiles_total") == blocks * 2 * 4 * 2
    # a step with a live row works one tile in each of the two layers: the
    # longer request's 8 steps at least, the two's 12 apart at most
    assert 2 * 8 <= value("row_tiles_worked_total") <= 2 * 12
    assert value("assignments_total") == (8 + 4) * 2 * 2


# -- (d): the kernel alone ------------------------------------------------
# the block the rule takes at the test's float32 experts (D 64, F 256), as
# (``_col_block``'s tile budget in columns | None for the module's, the
# whole-expert share of the chip's VMEM | None for the module's) -> (columns
# a step, a VMEM limit of the call's own): the whole expert as a small op,
# tiles of 128 columns where the share refuses the expert, the whole expert
# under its own limit where the share takes it
BLOCKS = {"small_op": ((None, None), (256, False)),
          "tiles": ((128, 0.0), (128, False)),
          "whole_by_budget": ((128, None), (256, True))}


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("glu", [True, False])
@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_fused_moe_mlp_against_jnp_on_stacked_weights(monkeypatch, layer,
                                                      glu, block):
    from deepspeed_tpu.ops.pallas import decode

    L, E, D, F, B = 3, 8, 64, 256, 5                    # B: no multiple of 8
    (tile, share), (columns, limited) = BLOCKS[block]
    if tile is not None:
        monkeypatch.setattr(decode, "_TILE_BYTES", tile * D * 3 * 4)
    if share is not None:
        monkeypatch.setattr(decode, "_WHOLE_EXPERT_VMEM_SHARE", share)
    bf, limit = decode.moe_expert_block(B, D, F, matrices=3, itemsize=4,
                                        row_itemsize=4)
    assert (bf, limit is not None) == (columns, limited)
    k = jax.random.split(jax.random.PRNGKey(layer), 7)
    h, r = jax.random.normal(k[0], (B, D)), jax.random.normal(k[1], (B, D))
    wu, wg = (0.1 * jax.random.normal(k[i], (L, E, D, F)) for i in (2, 3))
    wd = 0.1 * jax.random.normal(k[4], (L, E, F, D))
    combine = jax.random.uniform(k[5], (B, E)) * \
        (jax.random.uniform(k[6], (B, E)) > 0.6)
    got = fused_moe_mlp(h, r, combine, wu, wd, wg if glu else None,
                        layer=layer, act="silu", impl="interpret")
    want = r
    for e in range(E):
        up = h @ wu[layer, e]
        a = jax.nn.silu(h @ wg[layer, e]) * up if glu else jax.nn.silu(up)
        want = want + combine[:, e:e + 1] * (a @ wd[layer, e])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    ref_ = decode._moe_mlp_ref(h, r, combine, wu[layer],
                               wg[layer] if glu else None, wd[layer],
                               act="silu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref_), rtol=1e-5,
                               atol=1e-5)


# the tiles of 1,920 columns the rule may take (``_col_block``'s 384, the
# 640 a tenth over its budget) and the expert whole, as (``_TILE_BYTES`` in
# columns of the test's experts, the whole-expert share) -> columns a step
WIDE = {"384": ((384, 0.0), 384), "640": ((600, 0.0), 640),
        "whole": ((384, None), 1920)}


def _live_case(B, D, F, E, glu, seed=0, dtype=jnp.bfloat16):
    """Operands of a call at ``B`` rows, bf16 unless said (the cut reads the
    weights' item size: float32 rows engage it only from 481 on)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    h, r = (jax.random.normal(k[i], (B, D), dtype) for i in (0, 1))
    wu, wg = ((0.1 * jax.random.normal(k[i], (2, E, D, F))).astype(dtype)
              for i in (2, 3))
    wd = (0.1 * jax.random.normal(k[4], (2, E, F, D))).astype(dtype)
    combine = jax.random.uniform(k[5], (B, E)) * \
        (jax.random.uniform(k[6], (B, E)) > 0.6)
    return h, r, combine, wu, wd, (wg if glu else None)


@pytest.mark.parametrize("glu", [True, False], ids=["gated", "two_matrices"])
@pytest.mark.parametrize("n_live", [0, 1, 63, 64, 65, 128, 129, 256])
def test_fused_moe_mlp_works_the_live_rows_tiles(n_live, glu):
    """256 bf16 rows, ``n_live`` of them scattered: a live row gets what
    ``_moe_mlp_ref`` gives it, to the bit of the call without a mask, and a
    row that does not decode gets its residual back."""
    from deepspeed_tpu.ops.pallas import decode

    B, D, F, E = 256, 128, 256, 4
    assert decode.moe_row_tile(B, 2) == 128
    h, r, combine, wu, wd, wg = _live_case(B, D, F, E, glu, seed=n_live)
    live = np.zeros(B, bool)
    live[np.random.RandomState(n_live).permutation(B)[:n_live]] = True
    assert int(decode.moe_row_tiles_worked(jnp.asarray(live), 128)) \
        == -(-n_live // 128)
    call = functools.partial(fused_moe_mlp, h, r, combine, wu, wd, wg,
                             layer=1, act="relu2", impl="interpret")
    got = np.asarray(call(live=jnp.asarray(live)), np.float32)
    every = np.asarray(call(), np.float32)
    np.testing.assert_array_equal(got[live], every[live])
    np.testing.assert_array_equal(got[~live], np.asarray(r, np.float32)[~live])
    ref_ = np.asarray(decode._moe_mlp_ref(
        h, r, combine, wu[1], None if wg is None else wg[1], wd[1],
        act="relu2"), np.float32)
    np.testing.assert_allclose(got[live], ref_[live], rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("rows,dtype", [(5, "float32"), (128, "bfloat16"),
                                        (256, "float32"), (192, "bfloat16")])
def test_fused_moe_mlp_keeps_one_pass_where_the_cut_does_not_pay(rows, dtype):
    """Under ~240 rows of bf16 (481 of float32), or rows that are no whole
    tiles, ``live`` changes nothing: the call is the one without it, bit for
    bit, the rows that do not decode included."""
    from deepspeed_tpu.ops.pallas import decode

    dt = jnp.dtype(dtype)
    assert decode.moe_row_tile(rows, dt.itemsize) is None
    ops = _live_case(rows, 64, 128, 4, True, dtype=dt)
    live = jnp.arange(rows) % 3 == 0
    call = functools.partial(fused_moe_mlp, *ops, layer=0, act="silu",
                             impl="interpret")
    np.testing.assert_array_equal(np.asarray(call(live=live), np.float32),
                                  np.asarray(call(), np.float32))


@pytest.mark.parametrize("live", [None, 100], ids=["every_row", "100_live"])
@pytest.mark.parametrize("block", sorted(WIDE))
def test_fused_moe_mlp_at_15_lane_tiles(monkeypatch, block, live):
    """Two-matrix experts 1,920 columns wide, as Nemotron stores them, at
    each block the rule may take there: five tiles of 384, three of 640 (a
    tenth over the budget, under a limit of the call's own), the expert
    whole."""
    from deepspeed_tpu.ops.pallas import decode

    B, D, F, E = 256, 128, 1920, 2
    (tile, share), columns = WIDE[block]
    monkeypatch.setattr(decode, "_TILE_BYTES", tile * D * 2 * 2)
    if share is not None:
        monkeypatch.setattr(decode, "_WHOLE_EXPERT_VMEM_SHARE", share)
    bf, limit = decode.moe_expert_block(B, D, F, matrices=2)
    assert (bf, limit is None) == (columns, block == "384")
    h, r, combine, wu, wd, _ = _live_case(B, D, F, E, False)
    mask = None if live is None else jnp.arange(B) % 256 < live
    got = np.asarray(fused_moe_mlp(h, r, combine, wu, wd, None, layer=1,
                                   act="relu2", live=mask,
                                   impl="interpret"), np.float32)
    want = np.asarray(decode._moe_mlp_ref(h, r, combine, wu[1], None, wd[1],
                                          act="relu2"), np.float32)
    if mask is not None:
        want = np.where(np.asarray(mask)[:, None], want,
                        np.asarray(r, np.float32))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


# the decode block's call of each expert cell (rows = the cell's num_slots,
# hidden, stored expert width, matrices of an expert; bf16): whole expert
# where the expert is small (ISSUE 65's table), the parent's tile elsewhere,
# but three tiles of 640 columns for Nemotron's 15 lane tiles (ISSUE 67)
CELL_BLOCKS = {
    "olmoe-1b-7b-L8.serve-chat": ((64, 2048, 1024, 3), 1024),
    "kimi-linear-L5-ep8.serve-reason-doc-tail": ((128, 2304, 1024, 3), 1024),
    "nemotron3-nano-L9-ep2.serve-reason-4k": ((256, 2688, 1920, 2), 640),
    "solar-open2-L4-ep8.serve-reason-4k": ((128, 4096, 1280, 3), 256),
    "trinity-large-L5-ep8.serve-mixed-16k": ((32, 3072, 3072, 3), 256),
    "axk1-L5-ep16.serve-mixed-16k": ((32, 7168, 2048, 3), 128),
    "dots3-note-L5-ep16.serve-doc-48k": ((16, 5120, 1536, 3), 128),
}


@pytest.mark.parametrize("cell", sorted(CELL_BLOCKS))
def test_the_block_rule_takes_small_experts_whole(cell):
    """The rule's choice at each expert cell's decode shape, read from the
    cell's own files: the whole expert exactly where ISSUE 65's table says,
    under a limit that holds at least the two blocks it asks for and stays
    inside a v5e's VMEM; elsewhere ``_col_block``'s tile and no limit (the
    parent's call)."""
    import json

    from deepspeed_tpu.models.ssm_moe import padded_width
    from deepspeed_tpu.ops.pallas import decode

    (rows, d, f, mats), want = CELL_BLOCKS[cell]
    with open(os.path.join(REPO, "benchmarks", "workloads",
                           cell + ".json")) as fh:
        work = json.load(fh)
    with open(os.path.join(REPO, "benchmarks", "configs",
                           work["config"] + ".json")) as fh:
        fields = json.load(fh)["model_config"]
    assert work["engine"]["num_slots"] == rows
    assert fields["hidden_size"] == d and mats == 2 + fields["glu"]
    assert f in (fields["intermediate_size"],
                 padded_width(fields["intermediate_size"]))
    bf, limit = decode.moe_expert_block(rows, d, f, matrices=mats)
    assert bf == want
    tile = decode._col_block(d * mats, f, 2)
    if bf != tile:
        assert 2 * mats * d * bf * 2 <= limit <= decode._VMEM_BYTES_V5E \
            * decode._WHOLE_EXPERT_VMEM_SHARE
        # a tile past the budget: by less than ``_TILE_OVER``, and the next
        # dividing one up from ``_col_block``'s
        assert bf == f or (mats * d * bf * 2 <= decode._TILE_BYTES
                           * (1 + decode._TILE_OVER)
                           and not [b for b in range(tile + 128, bf, 128)
                                    if f % b == 0])
    else:
        assert limit is None
    # the row cut engages at 256 bf16 rows and nowhere else in the table
    assert decode.moe_row_tile(rows) == (128 if rows == 256 else None)


def _bench_tool():
    spec = importlib.util.spec_from_file_location(
        "_moe_decode_bench", os.path.join(REPO, "tools",
                                          "moe_decode_bench.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


BENCH_FIELDS = dict(vocab_size=VOCAB, hidden_size=128, intermediate_size=256,
                    num_layers=2, num_heads=2, num_kv_heads=2, head_dim=64,
                    max_seq_len=256, num_experts=4, num_experts_per_tok=2,
                    moe_drop_tokens=False, moe_norm_topk_prob=False,
                    qk_norm=True)


@pytest.mark.parametrize("mode", ["alone", "in_layer"])
def test_the_kernels_bench_tool_rehearses_on_the_cpu(tmp_path, mode):
    """``tools/moe_decode_bench.py`` end to end in interpret mode at tiny
    widths: back to back over the stacked arrays, and between the other
    instructions of scanned decode steps; a forced block is the block the
    kernel's grid then asks for, and the module's constants are put back."""
    import json

    from deepspeed_tpu.ops.pallas import decode

    tool = _bench_tool()
    before = decode._TILE_BYTES, decode._WHOLE_EXPERT_VMEM_SHARE
    out = tmp_path / "rows.jsonl"
    assert tool.main(
        ["--allow-cpu", "--model-config", json.dumps(BENCH_FIELDS), "--rows",
         "8", "--block", "rule,128", "--rounds", "1", "--steps", "2", "--out",
         str(out)] + (["--in-layer"] if mode == "in_layer" else [])) == 0
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    assert [(r["mode"], r["block"], r["block_cols"]) for r in rows] == \
        [(mode, "rule", 256), (mode, "128", 128)]
    assert all("error" not in r and r["matrices"] == 3 for r in rows)
    assert (decode._TILE_BYTES, decode._WHOLE_EXPERT_VMEM_SHARE) == before
    assert decode.pl.pallas_call.__name__ == "pallas_call"


def test_the_kernels_bench_tool_takes_live_rows_and_a_stored_width(tmp_path):
    """ISSUE 67's arguments: ``--width`` stores the experts that wide
    (zeros past the model's own), ``--live-rows`` hands the kernel a mask
    (256 bf16 rows: the cut engages) and ``--row-tile`` the rows of a pass;
    a block past the module's budget gets a limit of its own; every patched
    name is put back."""
    import json

    from deepspeed_tpu.ops.pallas import decode

    tool = _bench_tool()
    names = ("_TILE_BYTES", "_WHOLE_EXPERT_VMEM_SHARE", "_TILE_OVER",
             "_MOE_ROW_TILE", "moe_expert_block")
    before = [getattr(decode, n) for n in names]
    out = tmp_path / "rows.jsonl"
    assert tool.main(
        ["--allow-cpu", "--model-config", json.dumps(BENCH_FIELDS), "--rows",
         "256", "--block", "rule,128", "--live-rows", "all,64", "--width",
         "384", "--row-tile", "64", "--rounds", "1", "--out", str(out)]) == 0
    rows = [json.loads(line) for line in out.read_text().splitlines()]
    assert [(r["live_rows"], r["block"], r["block_cols"]) for r in rows] == \
        [("all", "rule", 384), ("64", "rule", 384), ("all", "128", 128),
         ("64", "128", 128)]
    assert all("error" not in r and (r["width"], r["row_tile"]) == (384, 64)
               for r in rows)
    assert [getattr(decode, n) for n in names] == before


# -- (e): grouped path == capacity path where nothing drops ---------------
def _block(router):
    cfg = tiny(2)
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    params = {"gate_w": jax.random.normal(k[0], (64, 8)),
              "w_up": 0.2 * jax.random.normal(k[1], (8, 64, 32)),
              "w_gate": 0.2 * jax.random.normal(k[2], (8, 64, 32)),
              "w_down": 0.2 * jax.random.normal(k[3], (8, 32, 64))}
    x = jax.random.normal(k[4], (2, 24, 64))
    if router == "one_expert_empty":       # nobody can choose expert 5
        params["gate_w"] = params["gate_w"].at[:, 5].set(0.0)
        x = x.at[..., 0].set(30.0)
        params["gate_w"] = params["gate_w"].at[0].set(1.0).at[0, 5].set(-9.0)
    elif router == "all_on_two":           # every token's top-2 is (1, 6)
        x = x.at[..., 0].set(30.0)
        params["gate_w"] = params["gate_w"].at[0].set(-1.0).at[0, 1].set(
            3.0).at[0, 6].set(2.0)
    return cfg, params, x


@pytest.mark.parametrize("norm_topk", [False, True])
@pytest.mark.parametrize("router", ["seeded", "one_expert_empty",
                                    "all_on_two"])
def test_grouped_path_equals_capacity_path_that_drops_nothing(router,
                                                              norm_topk):
    cfg, params, x = _block(router)
    cfg.moe_norm_topk_prob = norm_topk
    y, aux = moe_mlp(params, x, cfg)
    cfg.moe_drop_tokens, cfg.moe_capacity_factor = True, 8 / 2   # C = N
    y_cap, aux_cap = moe_mlp(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_cap), rtol=1e-5,
                               atol=1e-5)
    assert float(aux) == pytest.approx(float(aux_cap), rel=1e-6)
    logits = x.reshape(-1, 64) @ params["gate_w"]
    top = np.asarray(jax.lax.top_k(logits, 2)[1])
    if router == "one_expert_empty":
        assert 5 not in top
    if router == "all_on_two":
        assert (top == [1, 6]).all()


def test_grouped_path_reads_stacked_expert_arrays_at_a_layer_index():
    """Inference hands the model's [L, E, ...] arrays over whole, with the
    layer's index: the other layers' groups are empty."""
    cfg, params, x = _block("seeded")
    want, _ = moe_mlp(params, x, cfg)
    stack = {k: jnp.stack([jnp.zeros_like(v), v, 7.0 * jnp.ones_like(v)])
             for k, v in params.items() if k != "gate_w"}
    got, _ = jax.jit(lambda l: moe_mlp(
        {"gate_w": params["gate_w"], **stack}, x, cfg, layer=l))(
            jnp.asarray(1, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    cfg.moe_drop_tokens = True
    with pytest.raises(ValueError, match="only the dropless path"):
        moe_mlp({"gate_w": params["gate_w"], **stack}, x, cfg, layer=1)


def test_dropless_under_expert_parallelism_raises(devices):
    cfg, params, x = _block("seeded")
    mesh = build_mesh(ep=2, devices=devices[:2])
    with pytest.raises(NotImplementedError, match="ep > 1"):
        moe_mlp(params, x, cfg, mesh)
