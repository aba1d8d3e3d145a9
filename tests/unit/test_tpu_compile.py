"""AOT-compile the Pallas kernels of the train and serve paths for a v5e
that is described, not attached (``/opt/skills/guides/on-chip-measurement``
§2, rehearsal 3).

Interpret mode cannot see what the chip's compiler refuses: a block shape
the (8, 128) tiling rejects, more VMEM than a kernel may hold, a scalar
store to vector memory.  Each case lowers one kernel with ``impl="pallas"``
at the widths of a model the repo advertises and asserts the compiled text
carries the ``tpu_custom_call``.  Nothing runs, so this says nothing about
results (the interpret-mode parity tests do) or times.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.pallas import (flash_attention, fused_adam_update,
                                      layer_norm, quantize, rms_norm)
from deepspeed_tpu.ops.pallas.decode import (eva_decode_paged,
                                             eva_summarize_paged,
                                             flash_decode, fused_mlp,
                                             fused_moe_mlp, fused_norm_qkv,
                                             fused_proj_norm, paged_kv_append)
from deepspeed_tpu.ops.pallas.fused_adam8bit import fused_adam8bit_update
from deepspeed_tpu.ops.pallas.fused_lamb import fused_lamb_update
from deepspeed_tpu.serving.paged_kv import default_page_tokens
from tests.unit.tpu_described import (BF16, F32, I8, I32, SEQ, WIDTHS,  # noqa: F401
                                      v5e)

SLOTS, CACHE = 8, 1024
ADAM8_BLOCK = 512      # adam8bit()'s default state block


def _custom_calls(fn, sharding, *shapes) -> int:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text().count(
        'custom_call_target="tpu_custom_call"')


def _flash_fwd_bwd(w):
    qkv = ((1, w["H"], SEQ, w["Dh"]), BF16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               impl="pallas").astype(F32).sum()

    return jax.grad(loss, argnums=(0, 1, 2)), [qkv] * 3, 3


def _norm_qkv(w):
    D, N = w["D"], (w["H"] + 2 * w["Hkv"]) * w["Dh"]
    fn = lambda x, s, b, wq, bq: fused_norm_qkv(
        x, s, b, wq, bq, kind=w["kind"], impl="pallas")
    return fn, [((SLOTS, D), BF16), ((D,), BF16), ((D,), BF16),
                ((D, N), BF16), ((N,), BF16)], 1


def _norm_qkv_int8(w):
    D, N = w["D"], (w["H"] + 2 * w["Hkv"]) * w["Dh"]
    fn = lambda x, s, b, wq, ws: fused_norm_qkv(
        x, s, b, wq, kind=w["kind"], wscale=ws, impl="pallas")
    return fn, [((SLOTS, D), BF16), ((D,), BF16), ((D,), BF16),
                ((D, N), I8), ((N,), F32)], 1


def _decode_contiguous(w):
    cache = ((SLOTS, w["Hkv"], CACHE, w["Dh"]), BF16)
    fn = lambda q, k, v, pos: flash_decode(q, k, v, pos, impl="pallas")
    return fn, [((SLOTS, w["H"], w["Dh"]), BF16), cache, cache,
                ((SLOTS,), I32)], 1


def _paged_pool(w, slots, page, maxp, layers, pool_pages):
    """Shapes of a stacked paged pool and its page table; the defaults are
    the serving defaults (page = what ``kv_page_tokens: 0`` resolves to,
    every slot's reach backed, plus the junk page)."""
    page = page or default_page_tokens(CACHE)
    maxp = maxp or CACHE // page
    pool_pages = pool_pages or slots * maxp + 1
    return (((layers, pool_pages, w["Hkv"], page, w["Dh"]), BF16),
            ((slots, maxp), I32))


def _decode_paged(w, slots=SLOTS, page=None, maxp=None, layers=2,
                  pool_pages=None):
    """The serving default: stacked pool read at a static layer offset
    through the page table."""
    pool, table = _paged_pool(w, slots, page, maxp, layers, pool_pages)
    fn = lambda q, k, v, pos, pt: flash_decode(
        q, k, v, pos, layer=layers - 1, page_table=pt, impl="pallas")
    return fn, [((slots, w["H"], w["Dh"]), BF16), pool, pool,
                ((slots,), I32), table], 1


def _kv_append(w, slots=SLOTS, page=None, maxp=None, layers=2,
               pool_pages=None):
    pool, table = _paged_pool(w, slots, page, maxp, layers, pool_pages)
    new = ((slots, w["Hkv"], w["Dh"]), BF16)
    fn = lambda kc, vc, k, v, pos, pt: paged_kv_append(
        kc, vc, k, v, pos, pt, layer=layers - 1, impl="pallas")
    return fn, [pool, pool, new, new, ((slots,), I32), table], 1


def _proj_norm(w):
    D, M = w["D"], w["H"] * w["Dh"]
    fn = lambda c, r, wo, bo, s, b: fused_proj_norm(
        c, r, wo, bo, s, b, kind=w["kind"], impl="pallas")
    return fn, [((SLOTS, M), BF16), ((SLOTS, D), BF16), ((M, D), BF16),
                ((D,), BF16), ((D,), BF16), ((D,), BF16)], 1


def _proj_norm_int8(w):
    D, M = w["D"], w["H"] * w["Dh"]
    fn = lambda c, r, wo, ws, s, b: fused_proj_norm(
        c, r, wo, None, s, b, kind=w["kind"], wscale=ws, impl="pallas")
    return fn, [((SLOTS, M), BF16), ((SLOTS, D), BF16), ((M, D), I8),
                ((D,), F32), ((D,), BF16), ((D,), BF16)], 1


def _mlp(w):
    D, F = w["D"], w["F"]
    if w["glu"]:
        fn = lambda h, r, wu, wd, wg: fused_mlp(h, r, wu, wd, wg,
                                                act="silu", impl="pallas")
        extra = [((D, F), BF16)]
    else:
        fn = lambda h, r, wu, wd: fused_mlp(h, r, wu, wd, act="gelu",
                                            impl="pallas")
        extra = []
    return fn, [((SLOTS, D), BF16), ((SLOTS, D), BF16), ((D, F), BF16),
                ((F, D), BF16)] + extra, 1


def _norm_fwd_bwd(w):
    D = w["D"]
    if w["kind"] == "rmsnorm":
        loss = lambda x, g: rms_norm(x, g, impl="pallas").astype(F32).sum()
        return (jax.value_and_grad(loss, argnums=(0, 1)),
                [((2, SEQ, D), BF16), ((D,), F32)], 2)
    loss = lambda x, g, b: layer_norm(x, g, b,
                                      impl="pallas").astype(F32).sum()
    return (jax.value_and_grad(loss, argnums=(0, 1, 2)),
            [((2, SEQ, D), BF16), ((D,), F32), ((D,), F32)], 2)


def _optimizer_leaves(w):
    """An MLP weight (rows divide the 512-row block) and the embedding
    (V=50257 leaves the last block ragged)."""
    return [((w["D"], w["F"]), F32)] * 4 + [((w["V"], w["D"]), F32)] * 4


def _adam(w):
    def fn(p, g, m, v, pe, ge, me, ve, t):
        kw = dict(lr=1e-3, weight_decay=0.01, impl="pallas")
        return (fused_adam_update(p, g, m, v, t, **kw),
                fused_adam_update(pe, ge, me, ve, t, **kw))

    return fn, _optimizer_leaves(w) + [((), I32)], 2


def _adam8bit(w):
    nb = w["D"] * w["F"] // ADAM8_BLOCK
    tile, scale = (nb, ADAM8_BLOCK), (nb, 1)
    fn = lambda p, g, mq, ms, vq, vs, seed: fused_adam8bit_update(
        p, g, mq, ms, vq, vs, 1.1, 1.2, 1e-3, seed, b1=0.9, b2=0.999,
        eps=1e-8, wd=0.01, sr=True, impl="pallas")
    return fn, [(tile, BF16), (tile, BF16), (tile, I8), (scale, F32),
                (tile, I8), (scale, F32), ((), I32)], 1


def _quantize(w):
    fn = lambda x: quantize(x, bits=8, block=ADAM8_BLOCK, impl="pallas")[:2]
    return fn, [((w["D"], w["F"]), F32)], 1


def _lamb(w):
    def fn(p, g, m, v, pe, ge, me, ve, t):
        kw = dict(lr=1e-3, weight_decay=0.01, impl="pallas")
        return (fused_lamb_update(p, g, m, v, t, **kw),
                fused_lamb_update(pe, ge, me, ve, t, **kw))

    return fn, _optimizer_leaves(w) + [((), I32)], 4


KERNELS = {
    "flash_attention_fwd_bwd": _flash_fwd_bwd,
    "fused_norm_qkv": _norm_qkv,
    "fused_norm_qkv_int8": _norm_qkv_int8,
    "flash_decode_contiguous": _decode_contiguous,
    "flash_decode_paged": _decode_paged,
    "paged_kv_append": _kv_append,
    "fused_proj_norm": _proj_norm,
    "fused_proj_norm_int8": _proj_norm_int8,
    "fused_mlp": _mlp,
    "norm_fwd_bwd": _norm_fwd_bwd,
    "fused_adam": _adam,
    "fused_adam8bit": _adam8bit,
    "quantize": _quantize,
    "fused_lamb": _lamb,
}


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(v5e, kernel, widths):
    fn, shapes, want = KERNELS[kernel](WIDTHS[widths])
    assert _custom_calls(fn, v5e, *shapes) >= want


def test_flash_attention_at_the_train_cell_shape(v5e):
    """``jax.grad`` of ``flash_attention`` at the per-chip shape of
    ``gpt2-xl.train-zero3`` ([16, 25, 1024, 64], bf16): exactly the three
    kernels the benchmark's reader finds by name, each reading q, k, v and
    dO as views of what the program was handed (PR 30 found an output of
    ``bwd_dq`` leaving fast memory through an added copy), and the softmax
    statistics in lane-dense rows (an [S, 1] column is padded to 128 lanes
    in HBM: 210 MB an array at this shape)."""
    arg = jax.ShapeDtypeStruct((16, 25, 1024, 64), BF16, sharding=v5e)

    def loss(q, k, v, w):
        o = flash_attention(q, k, v, causal=True, impl="pallas")
        return (o.astype(F32) * w.astype(F32)).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg, arg, arg, arg).compile().as_text()
    calls = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.search(r"\((flash_attention_\w+?)\)+/pallas_call", line)
            calls[name.group(1) if name else line[:40]] = line
    assert sorted(calls) == ["flash_attention_bwd_dkv", "flash_attention_bwd_dq",
                             "flash_attention_fwd"]
    assert text.count('custom_call_target="tpu_custom_call"') == 3

    # instruction name -> (result, opcode) of everything in the module
    made = {m.group(1): (m.group(2), m.group(3)) for m in re.finditer(
        r"(%[\w.\-]+) = (\(?[\w\[\],{}:()\s]*?)\s([\w\-]+)\(", text)}
    for name, line in calls.items():
        operands = re.search(r"custom-call\(([^)]*)\)", line).group(1)
        wide = [op for op in re.findall(r"%[\w.\-]+", operands)
                if made[op][0].startswith("bf16[400,1024,64]")]
        assert len(wide) == (3 if name == "flash_attention_fwd" else 4)
        for op in wide:
            assert made[op][1] == "bitcast", (name, op, made[op])
    # one layout conversion per argument and per gradient, the float32
    # cast of w, and nothing else: no copy or transpose made for a kernel
    moved = re.findall(r" = \S+ (?:copy|transpose)\(", text)
    assert len(moved) <= 4 + 3 + 1, moved
    assert "f32[400,1024,1]" not in text


# the benchmark's mistral-7b-L8.serve-chat cell: 64 slots, pages of 256, 4
# pages a row, 8 layers over a 32,768-token pool and the junk page
SERVE_CHAT = dict(slots=64, page=256, maxp=4, layers=8, pool_pages=129)


@pytest.mark.parametrize("kernel", [_decode_paged, _kv_append],
                         ids=["flash_decode_paged", "paged_kv_append"])
def test_paged_kernels_compile_at_the_serve_chat_shape(v5e, kernel):
    """The block sizes the benchmark's serve cell runs (all 8 KV heads of a
    page in one grid step) are ones the chip's compiler has accepted."""
    fn, shapes, want = kernel(WIDTHS["d4096-gqa8"], **SERVE_CHAT)
    assert _custom_calls(fn, v5e, *shapes) >= want


# (heads, KV heads, slots, pages a row, cache layers, pool pages) of the
# cells whose decode blocks walk per-head K/V pages of 256 x 128 (ISSUE 60):
# Ouro's 48 cache layers, Trinity's rings of 16 pages and full tables of 64,
# Solar's one full layer under 128 slots of 19 pages
WALKED_CELLS = {
    "ouro-2.6b-L12.serve-reason-768": (16, 16, 16, 5, 48, 81),
    "mistral-7b-L8.serve-chat": (32, 8, 64, 4, 8, 129),
    "olmoe-1b-7b-L8.serve-chat": (16, 16, 64, 4, 8, 129),
    "trinity-large-L5-ep8.serve-mixed-16k.ring": (48, 8, 32, 16, 4, 513),
    "trinity-large-L5-ep8.serve-mixed-16k.full": (48, 8, 32, 64, 1, 1537),
    "solar-open2-L4-ep8.serve-reason-4k": (64, 8, 128, 19, 1, 2433),
}


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("cell", sorted(WALKED_CELLS))
def test_walked_pages_compile_at_the_cells_shapes(v5e, cell, traced):
    """ISSUE 60: ``flash_decode_paged`` at the shape each changed cell's
    decode block calls it (the slots, a live mask, the layer a Python int
    or a TRACED scalar as in Ouro's rolled pass loop) compiles for the v5e
    as ONE kernel of that name whose grid has one run-time extent (the live
    rows; operands: it, rows, pos, the layer's first page, the table, q, K,
    V, the slopes), with no
    reference in its place: its two K and two V page buffers, the float32
    copy of a page's values and the score tile fit the scoped VMEM."""
    from deepspeed_tpu.ops.pallas.common import reference_selections

    H, Hkv, slots, maxp, layers, pool_pages = WALKED_CELLS[cell]
    pool = ((layers, pool_pages, Hkv, 256, 128), BF16)
    shapes = [((slots, H, 128), BF16), pool, pool, ((slots,), I32),
              ((slots, maxp), I32), ((slots,), jnp.bool_), ((), I32)]

    def fn(q, k, v, pos, pt, live, layer):
        return flash_decode(q, k, v, pos, page_table=pt, live=live,
                            layer=layer if traced else layers - 1,
                            impl="pallas")

    before = len(reference_selections())
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 1 and "flash_decode_paged" in calls[0]
    operands = re.search(r"custom-call\(([^)]*)\)", calls[0]).group(1)
    assert len(operands.split(", ")) == 9
    assert len(reference_selections()) == before


# the benchmark's olmoe-1b-7b-L8.serve-chat cell: MHA 16 x 128 (all 16 KV
# heads of a page are exactly the attention kernel's 4 MiB of K and V
# buffers), 64 experts of width 1024 read from the stacked arrays
OLMOE = dict(D=2048, H=16, Hkv=16, Dh=128, F=1024, V=50304, glu=True,
             kind="rmsnorm")
OLMOE_EXPERTS, OLMOE_LAYERS = 64, 8


def _moe_mlp(w, slots=64, experts=OLMOE_EXPERTS, layers=OLMOE_LAYERS, **_):
    D, F, E, L = w["D"], w["F"], experts, layers
    fn = lambda h, r, c, wu, wd, wg: fused_moe_mlp(
        h, r, c, wu, wd, wg, layer=L - 1, act="silu", impl="pallas")
    return fn, [((slots, D), BF16), ((slots, D), BF16), ((slots, E), F32),
                ((L, E, D, F), BF16), ((L, E, F, D), BF16),
                ((L, E, D, F), BF16)], 1


# the benchmark's kimi-linear-L5-ep8.serve-reason-doc-tail cell: 128 slots
# against 32 held experts of 2,304 x 1,024 with a gate, four expert layers
def _moe_mlp_kimi(w, **_):
    return _moe_mlp(dict(D=2304, F=1024), slots=128, experts=32, layers=4)


@pytest.mark.parametrize("kernel", [_decode_paged, _kv_append, _moe_mlp,
                                    _moe_mlp_kimi],
                         ids=["flash_decode_paged", "paged_kv_append",
                              "fused_moe_mlp", "fused_moe_mlp_kimi_shape"])
def test_kernels_compile_at_the_olmoe_serve_chat_shape(v5e, kernel):
    """The two ``fused_moe_mlp`` cases take their experts WHOLE (12.6 and
    14.2 MB a block, two of them in flight) under the VMEM limit the call
    sets itself (29 and 36 MiB of the chip's 128): past the compiler's
    scoped 16 MiB, which only a compile for the chip refuses or takes."""
    fn, shapes, want = kernel(OLMOE, **SERVE_CHAT)
    assert _custom_calls(fn, v5e, *shapes) >= want


# the benchmark's nemotron3-nano-L9-ep2.serve-reason-4k cell: 256 slots of
# hidden 2,688; 64 Mamba-2 heads of 64 over a state of 128 (a row's state of
# one layer is 32 tiles [128, 128] float32); 64 held experts of TWO matrices
# stored 1,920 wide (15 lane tiles since PR 67: tiles of 640 columns under a
# VMEM limit of the call's own), the shared one 4,096; no gate matrix anywhere
NEMOTRON = dict(D=2688, slots=256, H=64, P=64, G=8, N=128, F=1920, Fs=4096,
                E=64, L=4)


def _ssm_step(w):
    from deepspeed_tpu.ops.pallas.decode import (ssm_decode_step,
                                                 ssm_heads_per_tile)

    B, H, P, G, N, L = (w[k] for k in ("slots", "H", "P", "G", "N", "L"))
    pk = ssm_heads_per_tile(H, P, G)
    fn = lambda s, x, dt, a, bm, cm, live: ssm_decode_step(
        s, x, dt, a, bm, cm, layer=L - 1, live=live, impl="pallas")[:2]
    return fn, [((L, B, H // pk, N, pk * P), F32), ((B, H, P), F32),
                ((B, H), F32), ((H,), F32), ((B, G, N), F32),
                ((B, G, N), F32), ((B,), jax.numpy.bool_)], 1


def _relu2_moe(w):
    B, D, F, E, L = (w[k] for k in ("slots", "D", "F", "E", "L"))
    fn = lambda h, r, c, wu, wd: fused_moe_mlp(
        h, r, c, wu, wd, None, layer=L - 1, act="relu2", impl="pallas")
    return fn, [((B, D), BF16), ((B, D), BF16), ((B, E), F32),
                ((L, E, D, F), BF16), ((L, E, F, D), BF16)], 1


def _relu2_shared(w):
    B, D, F = w["slots"], w["D"], w["Fs"]
    fn = lambda h, r, wu, wd: fused_mlp(h, r, wu, wd, None, act="relu2",
                                        impl="pallas")
    return fn, [((B, D), BF16), ((B, D), BF16), ((D, F), BF16),
                ((F, D), BF16)], 1


def _wide_in_proj(w):
    B, D = w["slots"], w["D"]
    fn = lambda x, s, wi: fused_norm_qkv(x, s, None, wi, None,
                                         kind="rmsnorm", impl="pallas")
    return fn, [((B, D), BF16), ((D,), BF16), ((D, 10752), BF16)], 1


def _out_proj(w):
    B, D = w["slots"], w["D"]
    fn = lambda c, r, wo, s: fused_proj_norm(c, r, wo, None, s, None,
                                             kind="rmsnorm", impl="pallas")
    return fn, [((B, 4096), BF16), ((B, D), BF16), ((4096, D), BF16),
                ((D,), BF16)], 1


@pytest.mark.parametrize("kernel", [_ssm_step, _relu2_moe, _relu2_shared,
                                    _wide_in_proj, _out_proj],
                         ids=["ssm_decode_step", "fused_moe_mlp_no_gate",
                              "fused_mlp_no_gate", "fused_norm_qkv",
                              "fused_proj_norm"])
def test_kernels_compile_at_the_nemotron_cells_256_slots(v5e, kernel):
    """256 rows of 2,688 resident beside the weight tiles: what a grid step
    holds at the cell's slots (ROADMAP's lesson of PR 59), and the state
    kernel's 2 MB blocks, read and written."""
    from deepspeed_tpu.ops.pallas.common import reference_selections

    before = len(reference_selections())
    fn, shapes, want = kernel(NEMOTRON)
    assert _custom_calls(fn, v5e, *shapes) >= want
    assert len(reference_selections()) == before


# the benchmark's jamba2-3b.serve-reason-768 cell: 256 slots of hidden 2,560;
# 26 Mamba-1 layers of 5,120 channels over a state of 16 (a row's state of
# one layer is 40 tiles [16, 128] float32); chunks of 256 rows; 20 query
# heads over ONE key-value head of 128 in 2 layers; MLPs of 8,192; every
# weight a layer of a stack, the layer a TRACED scalar (a rolled run's)
JAMBA = dict(D=2560, slots=256, di=5120, N=16, F=8192, L=26, H=20, Hkv=1,
             Dh=128, page=256, maxp=5, layers=2, pool_pages=1281)


def _mamba1_step(w):
    from deepspeed_tpu.ops.pallas.selective_scan import mamba1_decode_step

    B, di, N, L = (w[k] for k in ("slots", "di", "N", "L"))
    fn = lambda s, u, dt, a, bm, cm, live, layer: mamba1_decode_step(
        s, u, dt, a, bm, cm, layer=layer, live=live, impl="pallas")[:2]
    return fn, [((L, B, di // 128, N, 128), F32), ((B, di), F32),
                ((B, di), F32), ((L, di // 128, N, 128), F32), ((B, N), F32),
                ((B, N), F32), ((B,), jnp.bool_), ((), I32)], 1


def _scan_chunk(rows):
    def build(w):
        from deepspeed_tpu.ops.pallas.selective_scan import \
            selective_scan_chunk

        di, N = w["di"], w["N"]
        fn = lambda s, u, dt, a, bm, cm: selective_scan_chunk(
            s, u, dt, a, bm, cm, impl="pallas")
        return fn, [((di // 128, N, 128), F32), ((rows, di), F32),
                    ((rows, di), F32), ((di // 128, N, 128), F32),
                    ((rows, N), F32), ((rows, N), F32)], 1
    return build


def _stacked_in_proj(w):
    B, D, L = w["slots"], w["D"], w["L"]
    fn = lambda x, s, wi, layer: fused_norm_qkv(
        x, s, None, wi, None, kind="rmsnorm", layer=layer, impl="pallas")
    return fn, [((B, D), BF16), ((D,), BF16), ((L, D, 2 * w["di"]), BF16),
                ((), I32)], 1


def _stacked_out_proj(w):
    B, D, L = w["slots"], w["D"], w["L"]
    fn = lambda c, r, wo, s, layer: fused_proj_norm(
        c, r, wo, None, s, None, kind="rmsnorm", layer=layer, impl="pallas")
    return fn, [((B, w["di"]), BF16), ((B, D), BF16),
                ((L, w["di"], D), BF16), ((D,), BF16), ((), I32)], 1


def _stacked_mlp(w):
    B, D, F, L = w["slots"], w["D"], w["F"], w["L"] + 2
    fn = lambda h, r, wu, wd, wg, layer: fused_mlp(
        h, r, wu, wd, wg, act="silu", layer=layer, impl="pallas")
    return fn, [((B, D), BF16), ((B, D), BF16), ((L, D, F), BF16),
                ((L, F, D), BF16), ((L, D, F), BF16), ((), I32)], 1


def _mqa_decode(w):
    pool, table = _paged_pool(w, **{k: w[k] for k in (
        "slots", "page", "maxp", "layers", "pool_pages")})
    B = w["slots"]
    fn = lambda q, k, v, pos, pt, live: flash_decode(
        q, k, v, pos, layer=1, page_table=pt, live=live, impl="pallas")
    return fn, [((B, w["H"], w["Dh"]), BF16), pool, pool, ((B,), I32), table,
                ((B,), jnp.bool_)], 1


def _mqa_append(w):
    pool, table = _paged_pool(w, **{k: w[k] for k in (
        "slots", "page", "maxp", "layers", "pool_pages")})
    B = w["slots"]
    fn = lambda kc, vc, k, v, pos, pt: paged_kv_append(
        kc, vc, k, v, pos, pt, layer=1, impl="pallas")
    row = ((B, w["Hkv"], w["Dh"]), BF16)
    return fn, [pool, pool, row, row, ((B,), I32), table], 1


@pytest.mark.parametrize("kernel", [
    _mamba1_step, _scan_chunk(256), _scan_chunk(128), _stacked_in_proj,
    _stacked_out_proj, _stacked_mlp, _mqa_decode, _mqa_append],
    ids=["mamba1_decode_step", "selective_scan_chunk_256",
         "selective_scan_chunk_128", "fused_norm_qkv_of_a_stack",
         "fused_proj_norm_of_a_stack", "fused_mlp_of_a_stack",
         "flash_decode_paged_group_20", "paged_kv_append_one_kv_head"])
def test_kernels_compile_at_the_jamba2_cells_sizes(v5e, kernel):
    """ISSUE 66: the two Mamba-1 kernels over 40 state tiles [16, 128] a row
    a layer, the three fused kernels reading a TRACED layer of a stack
    (``_layer_call``: one scalar-prefetch operand), and multi-query
    attention as Mosaic takes it unchanged: a group of 20 query heads (2.5
    sublane tiles) over ONE key-value head, in the page walk and in the
    append's blocks."""
    from deepspeed_tpu.ops.pallas.common import reference_selections

    before = len(reference_selections())
    fn, shapes, want = kernel(JAMBA)
    assert _custom_calls(fn, v5e, *shapes) >= want
    assert len(reference_selections()) == before


# the benchmark's evabyte-L6.serve-doc cell: MHA 32 x 128, window 2,048 and
# chunk 16 over pages of 256 (8 window + 4 summary pages a row), 32 slots,
# the residual stream float32 between the kernels
EVABYTE = dict(D=4096, H=32, Hkv=32, Dh=128, F=11008, V=320, glu=True,
               kind="rmsnorm")
EVA = dict(window=2048, chunk=16)
SERVE_DOC = dict(slots=32, page=256, maxp=12, layers=6, pool_pages=337)


def _eva_decode(traced):
    """The decode block's call: the slots under a live mask, the layer a
    Python int or a TRACED scalar (it rides with the table either way); the
    walk's two K and two V page buffers of 16 heads fit the scoped VMEM."""
    def build(w):
        pool, table = _paged_pool(w, **SERVE_DOC)
        fn = lambda q, k, v, pos, pt, live, layer: eva_decode_paged(
            q, k, v, pos, pt, layer=layer if traced else 5, live=live,
            impl="pallas", **EVA)
        return fn, [((32, w["H"], w["Dh"]), BF16), pool, pool, ((32,), I32),
                    table, ((32,), jnp.bool_), ((), I32)], 1
    return build


def _eva_summarize(w):
    pool, table = _paged_pool(w, **SERVE_DOC)
    vec = ((w["H"], w["Dh"]), BF16)
    fn = lambda k, v, mu, phi, pos, pt: eva_summarize_paged(
        k, v, mu, phi, pos, pt, layer=5, impl="pallas", **EVA)
    return fn, [pool, pool, vec, vec, ((32,), I32), table], 1


def _eva_chunk(bucket):
    """The chunk program's attention at one prefill bucket: q [1, 32,
    bucket, 128] over the slot's 12 pages as one view."""
    def build(w):
        from deepspeed_tpu.ops.pallas.flash_attention import \
            eva_chunk_attention

        view = ((1, w["H"], SERVE_DOC["maxp"] * SERVE_DOC["page"], w["Dh"]),
                BF16)
        fn = lambda q, k, v, start: eva_chunk_attention(
            q, k, v, start, impl="pallas", **EVA)
        return fn, [((1, w["H"], bucket, w["Dh"]), BF16), view, view,
                    ((), I32)], 1
    return build


def _f32_stream(kernel):
    """A fused kernel's shapes with the residual stream (a [rows, D]
    operand that is not matmul input) in float32."""
    def build(w):
        fn, shapes, want = kernel(w)
        stream = {"_norm_qkv": 0, "_proj_norm": 1, "_mlp": 1}[kernel.__name__]
        shapes = [(s, F32) if i == stream else (s, d)
                  for i, (s, d) in enumerate(shapes)]
        return fn, shapes, want
    return build


@pytest.mark.parametrize("kernel", [
    _eva_decode(False), _eva_decode(True), _eva_summarize,
    _f32_stream(_norm_qkv),
    _f32_stream(_proj_norm), _f32_stream(_mlp), _eva_chunk(1024),
    _eva_chunk(512), _eva_chunk(256), _eva_chunk(128)],
    ids=["eva_decode_paged", "eva_decode_paged_traced_layer",
         "eva_summarize_paged", "fused_norm_qkv_f32",
         "fused_proj_norm_f32", "fused_mlp_f32", "eva_chunk_attention_1024",
         "eva_chunk_attention_512", "eva_chunk_attention_256",
         "eva_chunk_attention_128"])
def test_kernels_compile_at_the_evabyte_serve_doc_shape(v5e, kernel):
    fn, shapes, want = kernel(EVABYTE)
    assert _custom_calls(fn, v5e, *shapes) >= want


# the two cells with latent layers: A.X-K1's 64 heads over a slot's view of
# 16,384 rows of 640 (five latent layers), Kimi-Linear's 32 over 13,312 (one)
LATENT_CELLS = {"axk1": dict(H=64, rows=16384, layers=5),
                "kimi": dict(H=32, rows=13312, layers=1)}


@pytest.mark.parametrize("bucket", [1024, 512, 256, 128, 8])
@pytest.mark.parametrize("cell", sorted(LATENT_CELLS))
def test_mla_chunk_attention_compiles_at_the_latent_cells_shapes(
        v5e, cell, bucket):
    """ISSUE 49: the latent layers' chunk attention at every kind of bucket
    of the two cells (heads of 128 + 64 against one 640-value row, ``W_kvb``
    ``[512, H, 256]``, the LAST layer of the slot's view; a bucket under the
    lane tile pads its queries inside the call) is one Mosaic kernel: no
    size runs the reference."""
    from deepspeed_tpu.ops.pallas.common import reference_selections
    from deepspeed_tpu.ops.pallas.flash_attention import mla_chunk_attention

    w = LATENT_CELLS[cell]
    before = len(reference_selections())
    fn = lambda q, rows, wkvb, start: mla_chunk_attention(
        q, rows, wkvb, start, nope=128, scale=192 ** -0.5,
        layer=w["layers"] - 1, impl="pallas")
    assert _custom_calls(
        fn, v5e, ((bucket, w["H"], 192), BF16),
        ((w["layers"], w["rows"], 640), BF16), ((512, w["H"], 256), BF16),
        ((), I32)) == 1
    assert len(reference_selections()) == before
