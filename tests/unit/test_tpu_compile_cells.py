"""AOT-compile whole programs of the benchmark's serve and train cells for
a v5e that is described, not attached: a cell's configuration and engine as
``benchmarks/configs`` and ``benchmarks/workloads`` give them, one period
of its layer pattern, its chunk programs and its decode block lowered from
shapes.  What only these cases see off the chip: a pool copied or gathered,
a donation not taken, a VMEM or tiling refusal at a cell's own widths.
The kernels one at a time are ``test_tpu_compile.py``'s; nothing runs here
either.
"""

import math
import os
import re

import jax
import pytest

from tests.unit.tpu_described import (BF16, F32, I32, SEQ, WIDTHS,  # noqa: F401
                                      v5e)


def _latent_chunk_kernels(program, heads, bucket):
    """ISSUE 49: (calls of ``mla_chunk_attention`` in a chunk program, is
    there a float32 score array ``[heads, bucket, KEY_BLOCK]`` that
    ``afmoe.attend`` would have made, does any instruction copy a layer's
    ``[rows, 640]`` out of the slot's view for it)."""
    text = program.as_text()
    return (len(re.findall(r"custom-call\([^\n]*mla_chunk_attention", text)),
            bool(re.search(rf"f32\[(1,)?{heads},(1,)?{bucket},1024\]", text)),
            bool(re.search(r"= bf16\[(16384|13312),640\]\S* (fusion|copy)\(",
                           text)))



class _ServeCell:
    """A serve cell of the benchmark as ``benchmarks/configs`` and
    ``benchmarks/workloads`` describe it (``fields`` / ``engine`` overridden
    where a test cuts depth or pool, which changes no shape a copy or an
    alias turns on), its engine built on this process's CPU devices and its
    programs lowered from shapes for the described v5e."""

    def __init__(self, v5e, config, workload, fields=(), engine=()):
        import json

        from deepspeed_tpu.comm.mesh import build_mesh
        from deepspeed_tpu.models import CausalLM, ModelConfig
        from deepspeed_tpu.serving.engine import ServingEngine

        bench = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                             "benchmarks")
        with open(os.path.join(bench, "configs", config + ".json")) as f:
            fields = dict(json.load(f)["model_config"], **dict(fields))
        with open(os.path.join(bench, "workloads", workload + ".json")) as f:
            engine = dict(json.load(f)["engine"], dtype="bfloat16",
                          **dict(engine))
        (device,) = v5e.device_set
        self.v5e = v5e
        self.model = CausalLM(ModelConfig(**fields),
                              build_mesh(devices=[device]))
        self.serve = ServingEngine(self.model, engine)
        self.params = jax.eval_shape(
            lambda key: jax.tree.map(lambda x: x.astype(BF16),
                                     self.model.init(key)),
            jax.random.PRNGKey(0))
        self.smallest_pool = min(v.nbytes for v in self.serve._cache.values())

    def _on_chip(self, tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=self.v5e), tree)

    def _i32(self, *shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=self.v5e)

    def _carries(self):
        s = self.serve
        return self._on_chip((s._last_dev, s._pos_dev, s._act_dev))

    def chunk(self, bucket):
        """The chunk program of one prefill bucket, compiled."""
        s = self.serve
        return s._prefill_fn(bucket).lower(
            self._on_chip(self.params), self._on_chip(s._cache),
            self._carries(), self._i32(s.pool.slot_pages),
            self._i32(1, bucket), self._i32(5),
            self._on_chip(s._rng)).compile()

    def block(self):
        """The decode block, compiled."""
        from deepspeed_tpu.models.fused_decode import inject_decode_params

        s = self.serve
        # stands in for the injected view _block() reads off the engine
        s.engine._dparams = jax.eval_shape(
            lambda p: inject_decode_params(p, self.model.config), self.params)
        return s._block().lower(
            self._on_chip(s.engine._dparams), self._on_chip(s._cache),
            *self._carries(), self._i32(s.num_slots), self._i32(s.num_slots),
            self._on_chip(s._rng),
            self._i32(s.num_slots, s.pool.slot_pages)).compile()

    def assert_grouped_matmuls_are_the_kernel(self, program, rows):
        """ISSUE 64: every grouped matmul of a chunk program is the Pallas
        kernel ``moe_grouped_matmul`` over its bucket's ``rows`` x k sorted
        rows (no tile of pad behind them: ISSUE 46's steered the chip's
        ``ragged-dot``, of which the program holds none), its operands the
        run-time extent of its grid, the five scalar-prefetched vectors,
        the rows and a model's STACKED expert array."""
        text = program.as_text()
        assert "ragged-dot" not in text
        calls = re.findall(
            r"%moe_grouped_matmul\S* = bf16\[(\d+),\d+\]\S* custom-call\("
            r"([^\n]*)custom_call_target=\"tpu_custom_call\"", text)
        assert calls and {int(lhs) for lhs, _ in calls} == {rows}, calls
        assert all(operands.count("%") == 8 for _, operands in calls), calls
        return len(calls)

    def assert_pools_stay_in_place(self, program):
        """No instruction of ``program`` moves half a pool's bytes or more:
        no ``copy``, and no gather or scatter by op or by name (a gather
        through a page-table row came out as ``mini-gather-slice``s of half
        the pool each, the whole pool read and written back)."""
        import math

        for name, shape, op in re.findall(
                r"^\s*(\S+) = bf16\[([\d,]+)\]\S* ([\w-]+)\(",
                program.as_text(), re.M):
            size = 2 * math.prod(int(d) for d in shape.split(","))
            moved = op == "copy" or any(
                w in part for w in ("gather", "scatter")
                for part in (op, name))
            assert not (size >= self.smallest_pool / 2 and moved), (
                name, shape, op)

    def assert_donations_taken(self, program, donated):
        """The ``donated`` arguments behind the parameters' leaves come back
        in their own buffers: results are the token, then they in order."""
        text = program.as_text()
        header = text[:text.index("entry_computation_layout")]
        aliased = {int(arg): int(out) for out, arg in re.findall(
            r"\{(\d+)\}: \((\d+), \{\}", header)}
        first = len(jax.tree.leaves(self.params))
        assert aliased == {first + i: 1 + i for i in range(donated)}


@pytest.fixture
def chip_kernels(monkeypatch):
    """This process's devices are CPUs: take the kernels the chip would."""
    from deepspeed_tpu.ops.pallas import common

    monkeypatch.setattr(common, "default_impl", lambda: "pallas")


def test_evabyte_programs_never_copy_the_pool(v5e, chip_kernels):
    """ISSUE 32: the chunk program and the decode block of the
    ``evabyte-L6.serve-doc`` cell (two layers of its six and a quarter of
    its pool, which changes no shape the copies turn on) compile for the
    v5e with the K and V pools updated in place: no copy of a pool (a
    ``lax.cond`` around the window close cost two) and no gather over half
    of one (``v[:, pt_row]`` on the slot's pages did), so 4.9 GB of weights
    and an 8.8 GB pool fit the chip."""
    cell = _ServeCell(v5e, "evabyte-L6", "evabyte-L6.serve-doc",
                      fields=dict(num_layers=2),
                      engine=dict(kv_pool_tokens=16384))
    pool = cell.serve.pool
    assert (pool.window_pages, pool.summary_pages) == (8, 4)
    cell.assert_pools_stay_in_place(cell.chunk(cell.serve.prefill_chunk))
    block = cell.block()
    cell.assert_pools_stay_in_place(block)
    text = block.as_text()
    for name in ("eva_decode_paged", "eva_summarize_paged",
                 "paged_kv_append", "fused_norm_qkv", "fused_proj_norm",
                 "fused_mlp"):
        assert name in text, name


@pytest.fixture(scope="module")
def evabyte_cell():
    """One engine of the EvaByte cell for its buckets' cases."""
    return {}


@pytest.mark.parametrize("bucket", [1024, 128, 64])
def test_evabyte_chunk_programs_keep_their_scores_in_vmem(
        v5e, chip_kernels, evabyte_cell, bucket):
    """ISSUE 42: the cell's chunk programs (two layers of its six, scanned:
    one attention call in the loop's body) hold the flash kernel and no
    float32 score array ``[(1,) 32, bucket, 3072]`` at every bucket of whole
    lane tiles; a bucket under the tile runs the dense form, scores and
    all."""
    if not evabyte_cell:
        evabyte_cell["cell"] = _ServeCell(
            v5e, "evabyte-L6", "evabyte-L6.serve-doc",
            fields=dict(num_layers=2), engine=dict(kv_pool_tokens=16384))
    text = evabyte_cell["cell"].chunk(bucket).as_text()
    # the Pallas kernels by their instructions' names, as the readers of the
    # EVA decode metrics find theirs over the whole window
    kernels = re.findall(r"^\s*%([a-z_]+)[.\d]* = \S+ custom-call\(.*"
                         r"custom_call_target=\"tpu_custom_call\"", text, re.M)
    # (XLA drops the batch of one from the dense form's arrays)
    scores = bool(re.search(rf"f32\[(1,)?32,{bucket},3072\]", text))
    assert (kernels.count("eva_chunk_attention"), scores) == (
        (1, False) if bucket >= 128 else (0, True))
    assert not {"eva_decode_paged", "eva_summarize_paged"} & set(kernels)


# the power-of-two chunk buckets from 8 up to the chat cells' prefill_chunk
CHAT_BUCKETS = [8, 16, 32, 64, 128, 256]


@pytest.fixture(scope="module")
def chat_cells():
    """One engine a chat cell for all of its buckets' cases."""
    return {}


@pytest.mark.parametrize("bucket", CHAT_BUCKETS)
@pytest.mark.parametrize("cell", ["mistral-7b-L8", "olmoe-1b-7b-L8"])
def test_chat_chunk_programs_never_copy_the_pool(v5e, chip_kernels,
                                                 chat_cells, cell, bucket):
    """ISSUE 37: every chunk program of the ``mistral-7b-L8.serve-chat`` and
    ``olmoe-1b-7b-L8.serve-chat`` cells (two layers of their eight) takes a
    slot's four pages out of the pool by slices and puts them back in
    place: compiled for the v5e, nothing the size of half a pool is copied,
    gathered or scattered (the gather ``v[:, pt_row]`` read and rewrote the
    541 MB / 1.08 GB pools, 3.3 / 6.6 ms of every chunk program), and the
    donated pools and carries keep their buffers.  ISSUE 64: OLMoE's hold
    the Pallas grouped matmul and no ``ragged-dot``."""
    if cell not in chat_cells:
        chat_cells[cell] = _ServeCell(v5e, cell, cell + ".serve-chat",
                                      fields=dict(num_layers=2))
    built = chat_cells[cell]
    serve = built.serve
    assert (serve.pool.slot_pages, serve.pool.page) == (4, 256)
    assert bucket <= serve.prefill_chunk == CHAT_BUCKETS[-1]
    program = built.chunk(bucket)
    built.assert_pools_stay_in_place(program)
    built.assert_donations_taken(program, donated=5)
    if cell == "olmoe-1b-7b-L8":
        # ISSUE 64: the router form (``moe_mlp(layer=)``) takes the kernel
        # too, three calls in the scanned layer's body
        assert built.assert_grouped_matmuls_are_the_kernel(
            program, bucket * 8) == 3


@pytest.mark.parametrize("config,in_place", [
    ("mistral-7b-L8", True), ("olmoe-1b-7b-L8", True), ("gpt2-xl", False)])
def test_decode_blocks_visit_the_live_rows(v5e, chip_kernels, config,
                                           in_place):
    """ISSUE 39: the decode block of the two chat cells, and of ``gpt2-xl``
    served in the Mistral cell's engine (head dim 64, 25 KV heads; not a
    cell), two layers each, compiles for the v5e with the attention kernel's
    grid read at run time: five kernels a layer and the final norm as
    before, the live rows sorted ONCE a step for all layers' calls, each
    call's output in its ``q``'s buffer, and no pool copied for it (at head
    dim 64 the block converts the pool's layout on its way in and out, four
    copies before this change and after).  ISSUE 60: at head dim 128 the
    kernel walks a row's pages itself, so its grid has ONE run-time extent
    (the live rows) where head dim 64 keeps two (and the deepest row's
    pages)."""
    cell = _ServeCell(v5e, config, "mistral-7b-L8.serve-chat",
                      fields=dict(num_layers=2))
    block = cell.block()
    text = block.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 11
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "flash_decode_paged" in line]
    assert len(calls) == 2
    # operands: the grid's run-time extents, rows, pos, (where the kernel
    # walks the pages, the layer's first page,) the table, q
    extents = 1 if in_place else 2
    rows = {re.search(r"custom-call\(([^)]*)\)",
                      line).group(1).split(", ")[extents] for line in calls}
    assert len(rows) == 1 and rows.pop().startswith("%sort")
    assert len(re.findall(r" sort\(%.*argsort", text)) == 1
    assert all("output_to_operand_aliasing={{}: (5, {})}" in line
               for line in calls)
    if in_place:
        cell.assert_pools_stay_in_place(block)
    else:
        pool = ",".join(str(d) for d in cell.serve._cache["k"].shape)
        assert len(re.findall(rf"bf16\[{pool}\]\S* copy\(", text)) == 4


def test_trinity_cell_programs_compile_without_copying_a_budget(
        v5e, chip_kernels):
    """ISSUE 36: the chunk program (bucket 1,024) and the decode block of
    the ``trinity-large-L5-ep8.serve-mixed-16k`` cell (the published widths:
    48 / 8 heads x 128, a GQA group of 6; the pattern cut to [s | s, f], which
    changes no shape; the full budget cut to 65,536 positions) compile for
    the v5e: both page budgets stay where they are (no copy or gather the
    size of either), and the decode block carries the paged attention and
    append kernels and the expert block."""
    cell = _ServeCell(
        v5e, "trinity-large-L5-ep8", "trinity-large-L5-ep8.serve-mixed-16k",
        fields=dict(num_layers=3, layer_types=["sliding_attention"] * 2
                    + ["full_attention"]),
        engine=dict(kv_pool_tokens=65536, num_slots=8))
    pool = cell.serve.pool
    assert (pool.window_pages, pool.slot_pages) == (16, 80)
    chunk = cell.chunk(cell.serve.prefill_chunk)
    cell.assert_pools_stay_in_place(chunk)
    cell.assert_grouped_matmuls_are_the_kernel(
        chunk, cell.serve.prefill_chunk * 4)
    block = cell.block()
    cell.assert_pools_stay_in_place(block)
    text = block.as_text()
    for name in ("flash_decode_paged", "paged_kv_append", "fused_norm_qkv",
                 "fused_proj_norm", "fused_mlp", "fused_moe_mlp"):
        assert name in text, name


def test_last_chunk_program_aliases_cache_and_carries_at_serve_chat(v5e):
    """ISSUE 28: the chunk program of the ``mistral-7b-L8.serve-chat`` cell
    (64 slots, pages of 256, bucket 256; two layers of the cell's eight,
    which changes no shape the aliasing turns on) compiles for the v5e with
    every donated argument taken: K and V pools AND the decode block's
    three carries (``last``, ``pos``, ``active``), which the program now
    updates for its slot, come back in their own buffers."""
    import warnings

    cell = _ServeCell(v5e, "mistral-7b-L8", "mistral-7b-L8.serve-chat",
                      fields=dict(num_layers=2))
    serve = cell.serve
    assert (serve.num_slots, serve.pool.page) == (64, 256)
    assert serve.prefill_chunk == 256
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # "donated buffers not usable"
        program = cell.chunk(256)
    cell.assert_donations_taken(program, donated=5)


def test_train_zero3_loss_tail_keeps_logits_on_their_chip(v5e):
    """ISSUE 30: value and grad of the ``gpt2-xl.train-zero3`` cell's loss
    tail (fsdp=4 over the 2x2 host, 16 x 1,024 tokens a chip, the tied
    table hidden-sharded as ``choose_pspec`` leaves it, chunks of 2,048)
    compiled for the described host: the only collectives with a
    vocabulary-sized operand are the head's gather and its gradient's sum,
    both outside the chunk loop and in bf16; no ``[2048, 50257]`` block
    and no block of rows or labels crosses chips."""
    import re

    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.comm.mesh import build_mesh
    from deepspeed_tpu.models import causal_lm
    from deepspeed_tpu.runtime.zero.partition import choose_pspec
    from tests.unit.hlo_text import collectives

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = build_mesh(fsdp=4, devices=topo.devices)
    w = WIDTHS["gpt2-xl"]
    V, D, B, S = w["V"], w["D"], 64, SEQ
    model = causal_lm("gpt2-xl", mesh=mesh, num_layers=1)
    assert model.config.ce_chunk is None and B * S * V > 1 << 28  # chunked
    sh = lambda *s: NamedSharding(mesh, P(*s))
    table = choose_pspec((V, D), mesh)
    assert table == P(None, "fsdp")
    ln = {"scale": sh(), "bias": sh()}
    args = (jax.ShapeDtypeStruct((V, D), F32, sharding=sh(*table)),
            {k: jax.ShapeDtypeStruct((D,), F32, sharding=s)
             for k, s in ln.items()},
            jax.ShapeDtypeStruct((B, S, D), BF16, sharding=sh("fsdp")),
            jax.ShapeDtypeStruct((B, S), I32, sharding=sh("fsdp")))
    text = jax.jit(
        jax.value_and_grad(
            lambda tok, fnorm, x, labels: model._loss_tail(
                fnorm, tok.T, x, labels, None), argnums=(0, 1, 2)),
        out_shardings=(sh(), (sh(*table), ln, sh("fsdp")))).lower(
            *args).compile().as_text()
    # the block's matmul, the float32 cast and the row max stay ONE fusion
    # (6 ms a step on the chip against a cast in a fusion of its own)
    assert re.search(r"= \([^=]*f32\[2048,50257\][^=]*\) fusion\(.*"
                     r"kind=kOutput", text), "float32 logits left the matmul"
    # all that may be left beside the head: scalars and [D] sums
    big = [(kind, dtype, dims, entry)
           for kind, results, entry in collectives(text)
           for dtype, dims in results if max(dims, default=0) > D]
    assert sorted(big) == [("all-gather", "bf16", (V, D), True),
                           ("all-reduce", "bf16", (D, V), True)] or \
        sorted(big) == [("all-gather", "bf16", (V, D), True),
                        ("reduce-scatter", "bf16", (D // 4, V), True)], big


def test_kimi_linear_cell_programs_compile_with_state_and_pool_in_place(
        v5e, chip_kernels):
    """ISSUE 44: the chunk programs (buckets 1,024 and 64) and the decode
    block of the ``kimi-linear-L5-ep8.serve-reason-doc-tail`` cell (the
    published widths; the pattern cut to [linear | latent], which changes no
    shape; the pool cut to 16 slots' worth) compile for the v5e: the latent
    pool, the recurrent state and the convolution tails stay where they are
    (no copy or gather the size of any of them: the state is float32, which
    ``assert_pools_stay_in_place`` does not read, so its copies are looked
    for by shape), and the decode block carries the two named kernels beside
    the shared ones."""
    cell = _ServeCell(
        v5e, "kimi-linear-L5-ep8", "kimi-linear-L5-ep8.serve-reason-doc-tail",
        fields=dict(num_layers=2, layer_types=["linear_attention",
                                               "latent_attention"]),
        engine=dict(kv_pool_tokens=16 * 13312, num_slots=16))
    cache = cell.serve._cache
    cell.smallest_pool = cache["latent"].nbytes    # the tails are smaller
    shape = lambda k: ",".join(str(d) for d in cache[k].shape)
    chunks = (cell.chunk(1024), cell.chunk(64))
    for program, tokens in zip(chunks, (1024, 64)):
        cell.assert_grouped_matmuls_are_the_kernel(
            program, tokens * 8)
        # ISSUE 49: the one latent layer's chunk attention is the kernel
        assert _latent_chunk_kernels(program, 32, tokens) == (1, False, False)
    for program in chunks + (cell.block(),):
        cell.assert_pools_stay_in_place(program)
        for kind, key in (("f32", "state"), ("bf16", "tail")):
            assert not re.findall(rf"{kind}\[{shape(key)}\]\S* copy\(",
                                  program.as_text()), key
        mem = program.memory_analysis()
        print("memory", mem.temp_size_in_bytes, mem.argument_size_in_bytes,
              mem.output_size_in_bytes, mem.alias_size_in_bytes)
    text = program.as_text()
    for name in ("kda_decode_step", "mla_decode_paged", "paged_kv_append",
                 "fused_norm_qkv", "fused_proj_norm", "fused_mlp",
                 "fused_moe_mlp"):
        assert name in text, name


def test_solar_open2_cell_programs_compile_with_state_and_pages_in_place(
        v5e, chip_kernels):
    """ISSUE 59: the chunk programs (buckets 1,024 and 64) and the decode
    block of the ``solar-open2-L4-ep8.serve-reason-4k`` cell (the published
    widths: 64 KDA heads of 128, 64 query heads over 8 key-value heads of
    128, experts of 1,280; the pattern cut to [full | linear], which changes
    no shape; the pool cut to 16 slots' worth but all 128 SLOTS kept: 128 rows
    of 4,096 beside a [4096, 18432] projection's tiles are what overflowed
    ``fused_norm_qkv``'s VMEM on the chip before ``_col_block(resident=)``)
    compile for the v5e: the K/V
    pages of the ONE full layer, the recurrent state and the convolution
    tails stay where they are, every kernel the decode block calls is the
    Pallas one at these sizes (no reference fallback: ``kda_decode_step`` at
    64 heads, ``flash_decode_paged`` and ``paged_kv_append`` over a pool
    that holds the full layers only, ``fused_moe_mlp`` over 40 experts of
    width 1,280), and a chunk's grouped matmuls take the held-share pad."""
    cell = _ServeCell(
        v5e, "solar-open2-L4-ep8", "solar-open2-L4-ep8.serve-reason-4k",
        fields=dict(num_layers=2, layer_types=["full_attention",
                                               "linear_attention"]),
        engine=dict(kv_pool_tokens=16 * 4864))
    cache = cell.serve._cache
    assert cache["k"].shape == (1, 16 * 19 + 1, 8, 256, 128)
    assert cache["state"].shape == (1, 128, 64, 128, 128)
    cell.smallest_pool = cache["k"].nbytes         # the tails are smaller
    shape = lambda k: ",".join(str(d) for d in cache[k].shape)
    chunks = (cell.chunk(1024), cell.chunk(64))
    for program, tokens in zip(chunks, (1024, 64)):
        cell.assert_grouped_matmuls_are_the_kernel(
            program, tokens * 8)
    for program in chunks + (cell.block(),):
        cell.assert_pools_stay_in_place(program)
        for kind, key in (("f32", "state"), ("bf16", "tail")):
            assert not re.findall(rf"{kind}\[{shape(key)}\]\S* copy\(",
                                  program.as_text()), key
        mem = program.memory_analysis()
        print("memory", mem.temp_size_in_bytes, mem.argument_size_in_bytes,
              mem.output_size_in_bytes, mem.alias_size_in_bytes)
    text = program.as_text()
    for name in ("kda_decode_step", "flash_decode_paged", "paged_kv_append",
                 "fused_norm_qkv", "fused_proj_norm", "fused_mlp",
                 "fused_moe_mlp"):
        assert name in text, name
    assert "mla_decode_paged" not in text


def test_nemotron3_nano_cell_programs_compile_at_the_cells_256_slots(
        v5e, chip_kernels):
    """ISSUE 63: the chunk programs (buckets 1,024 and 128: the scan's one
    block) and the decode block of the
    ``nemotron3-nano-L9-ep2.serve-reason-4k`` cell (the published widths: 64
    Mamba-2 heads of 64 over a state of 128, 32 query heads over 2 key-value
    heads of 128, experts of 1,856 stored at 1,920 (2,048 until PR 67), the
    shared one of 3,712 at 4,096; the pattern cut to one layer of each kind, which changes no shape;
    the pool cut to 32 slots' worth (half of it is then more than the 34 MB
    of expert rows a 1,024-row chunk gathers) but all 256 SLOTS kept,
    ROADMAP's lesson of PR 59: 256 rows of 2,688 beside the weight tiles are
    what a grid step holds) compile for the v5e: the K/V pages of the ONE attention layer, the
    state and the convolution tails stay where they are, every kernel the
    decode block calls is the Pallas one at these sizes (no reference
    fallback: ``ssm_decode_step`` over [32, 128, 128] tiles,
    ``flash_decode_paged`` at a group of 16 query heads a key-value head,
    ``fused_moe_mlp`` and ``fused_mlp`` WITHOUT a gate matrix), and a chunk's
    grouped matmuls take the held-share pad."""
    cell = _ServeCell(
        v5e, "nemotron3-nano-L9-ep2", "nemotron3-nano-L9-ep2.serve-reason-4k",
        fields=dict(num_layers=3, layer_types=["mamba2", "full_attention",
                                               "experts"]),
        engine=dict(kv_pool_tokens=32 * 4864))
    cache = cell.serve._cache
    assert cell.serve.num_slots == 256
    assert cache["k"].shape == (1, 32 * 19 + 1, 2, 256, 128)
    assert cache["state"].shape == (1, 256, 32, 128, 128)
    assert cache["tail"].shape == (1, 256, 3, 6144)
    cell.smallest_pool = cache["k"].nbytes
    shape = lambda k: ",".join(str(d) for d in cache[k].shape)
    chunks = (cell.chunk(1024), cell.chunk(128))
    for program, tokens in zip(chunks, (1024, 128)):
        cell.assert_grouped_matmuls_are_the_kernel(
            program, tokens * 6)
    for program in chunks + (cell.block(),):
        cell.assert_pools_stay_in_place(program)
        for kind, key in (("f32", "state"), ("bf16", "tail")):
            assert not re.findall(rf"{kind}\[{shape(key)}\]\S* copy\(",
                                  program.as_text()), key
        mem = program.memory_analysis()
        print("memory", mem.temp_size_in_bytes, mem.argument_size_in_bytes,
              mem.output_size_in_bytes, mem.alias_size_in_bytes)
    text = program.as_text()
    for name in ("ssm_decode_step", "flash_decode_paged", "paged_kv_append",
                 "fused_norm_qkv", "fused_proj_norm", "fused_mlp",
                 "fused_moe_mlp"):
        assert name in text, name
    assert "kda_decode_step" not in text


# ISSUE 67: Nemotron's routed experts stored 1,920 wide (15 lane tiles)
NEMOTRON_EXPERTS = dict(rows=256, D=2688, F=1920, E=64, L=4)


def _nemotron_moe_mlp(live):
    from deepspeed_tpu.ops.pallas.decode import fused_moe_mlp

    w = NEMOTRON_EXPERTS
    B, D, F, E, L = (w[k] for k in ("rows", "D", "F", "E", "L"))
    fn = lambda h, r, c, wu, wd, mask: fused_moe_mlp(
        h, r, c, wu, wd, None, layer=L - 1, act="relu2",
        live=mask if live else None, impl="pallas")
    return fn, [((B, D), BF16), ((B, D), BF16), ((B, E), F32),
                ((L, E, D, F), BF16), ((L, E, F, D), BF16),
                ((B,), jax.numpy.bool_)]


def _nemotron_grouped(down):
    from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul

    w = NEMOTRON_EXPERTS
    K, N = (w["F"], w["D"]) if down else (w["D"], w["F"])
    fn = lambda lhs, rhs, sizes: grouped_matmul(lhs, rhs, sizes,
                                                layer=w["L"] - 1,
                                                impl="pallas")
    return fn, [((1024 * 6, K), BF16), ((w["L"], w["E"], K, N), BF16),
                ((w["E"],), I32)]


@pytest.mark.parametrize("kernel,name", [
    (lambda: _nemotron_moe_mlp(True), "fused_moe_mlp"),
    (lambda: _nemotron_moe_mlp(False), "fused_moe_mlp"),
    (lambda: _nemotron_grouped(False), "moe_grouped_matmul"),
    (lambda: _nemotron_grouped(True), "moe_grouped_matmul")],
    ids=["fused_moe_mlp_live_rows", "fused_moe_mlp_every_row",
         "grouped_matmul_up", "grouped_matmul_down"])
def test_expert_kernels_compile_at_nemotrons_15_lane_tiles(v5e, kernel, name):
    """256 rows of 2,688 against 64 two-matrix experts stored 1,920 wide:
    ``fused_moe_mlp`` in tiles of 640 columns (two 6.9 MB blocks in flight:
    past the compiler's scoped 16 MiB, under the limit the call sets), with
    its MXU passes cut to the live rows' tiles and without; the chunk
    programs' grouped matmuls at a 1,024-token bucket's 6,144 sorted rows,
    up (640 columns a block) and down (896)."""
    from deepspeed_tpu.ops.pallas import decode

    w = NEMOTRON_EXPERTS
    assert decode.moe_expert_block(w["rows"], w["D"], w["F"],
                                   matrices=2) == (640, 29 * 2**20)
    assert decode.moe_row_tile(w["rows"]) == 128
    fn, shapes = kernel()
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and name in calls[0]


# (rows, hidden, stored width, matrices) of the six other expert cells'
# decode-block call -> what ``moe_expert_block`` gave at the parent of ISSUE
# 67 (PR 66): their programs did not change
PARENT_BLOCKS = {
    "olmoe-1b-7b-L8": ((64, 2048, 1024, 3), (1024, 29 * 2**20)),
    "kimi-linear-L5-ep8": ((128, 2304, 1024, 3), (1024, 36 * 2**20)),
    "solar-open2-L4-ep8": ((128, 4096, 1280, 3), (256, None)),
    "trinity-large-L5-ep8": ((32, 3072, 3072, 3), (256, None)),
    "axk1-L5-ep16": ((32, 7168, 2048, 3), (128, None)),
    "dots3-note-L5-ep16": ((16, 5120, 1536, 3), (128, None)),
}


@pytest.mark.parametrize("config", sorted(PARENT_BLOCKS))
def test_the_other_expert_cells_keep_the_parents_block(config):
    """ISSUE 67 moved Nemotron's block alone: at the other cells' shapes the
    rule returns the parent's (tile, limit), and no call of theirs has the
    rows for the row cut (it starts at ~240 of bf16)."""
    from deepspeed_tpu.ops.pallas import decode

    (rows, d, f, mats), want = PARENT_BLOCKS[config]
    assert decode.moe_expert_block(rows, d, f, matrices=mats) == want
    assert decode.moe_row_tile(rows) is None


def test_jamba2_cell_programs_compile_whole_with_every_weight_once(
        v5e, chip_kernels):
    """ISSUE 66: the chunk programs (buckets 256 and 128: ``chunk_rows``) and
    the decode block of the ``jamba2-3b.serve-reason-768`` cell AS IT IS RUN
    (all 56 one-mixer layers, 256 slots, the whole pool: nothing cut)
    compile for the v5e: the three runs of ``[mamba1, mlp]`` pairs are
    ROLLED (a program holds three calls of each of their kernels, not 26),
    every kernel of both kinds of program is the Pallas one (no reference
    fallback: ``mamba1_decode_step`` and ``selective_scan_chunk`` over 40
    tiles [16, 128], ``flash_decode_paged`` at a group of 20 query heads
    over ONE key-value head), no layer of a weight stack is sliced or copied
    out in front of a product (the stacks are the kernels' operands, the
    layer in their index maps: a dense layer's weights resident ONCE), and
    state, tails and pages stay where they are."""
    cell = _ServeCell(v5e, "jamba2-3b", "jamba2-3b.serve-reason-768")
    cache = cell.serve._cache
    assert cell.serve.num_slots == 256 and cell.serve.kind.chunk_rows == 128
    assert cache["k"].shape == (2, 256 * 5 + 1, 1, 256, 128)
    assert cache["state"].shape == (26, 256, 40, 16, 128)
    assert cache["tail"].shape == (26, 256, 3, 5120)
    cell.smallest_pool = cache["k"].nbytes
    shape = lambda k: ",".join(str(d) for d in cache[k].shape)
    weights = sum(a.size * 2 for a in jax.tree.leaves(cell.params))
    held = sum(v.nbytes for v in cache.values())
    assert weights == 2 * 3029337472
    calls = lambda text, name: len(re.findall(
        rf"%{name}[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text))
    programs = {256: cell.chunk(256), 128: cell.chunk(128),
                "block": cell.block()}
    for which, program in programs.items():
        text = program.as_text()
        cell.assert_pools_stay_in_place(program)
        for kind, key in (("f32", "state"), ("bf16", "tail")):
            assert not re.findall(rf"{kind}\[{shape(key)}\]\S* copy\(",
                                  text), key
        # no layer of a dense stack leaves it: a slice of one is 13 M
        # elements and more (the two attention layers' 6.5 M projections
        # have buffers of their own)
        for dims, op in re.findall(
                r"= bf16\[([\d,]+)\]\S* (copy|dynamic-slice)\(", text):
            if (which, dims, op) == ("block", "26,5120,192", "copy"):
                # ``W_x``'s 192 columns are 1.5 lane tiles: the decode block
                # lays the stack out anew ONCE a call (51 MB an 8-step
                # block, outside its loops; XLA's products read it)
                continue
            assert math.prod(int(d) for d in dims.split(",")) < 2 ** 23, (
                which, dims, op)
        mem = program.memory_analysis()
        print("memory", which, mem.temp_size_in_bytes,
              mem.argument_size_in_bytes, mem.output_size_in_bytes,
              mem.alias_size_in_bytes)
        # the weights ONCE and the cache, and little beside them
        assert mem.argument_size_in_bytes < weights + held + 2 ** 27
        assert mem.temp_size_in_bytes < 2 ** 29
        for name in ("fused_norm_qkv", "fused_proj_norm", "fused_mlp"):
            assert calls(text, name) in (5, 3), (which, name)
        assert calls(text, "fused_mlp") == 5      # 3 runs + 2 after attention
        if which == "block":
            assert calls(text, "mamba1_decode_step") == 3
            assert calls(text, "flash_decode_paged") == 2
            assert "paged_kv_append" in text
            assert calls(text, "selective_scan_chunk") == 0
        else:
            assert calls(text, "selective_scan_chunk") == 3
            assert calls(text, "mamba1_decode_step") == 0
        assert calls(text, "ssm_decode_step") == 0


def test_axk1_cell_programs_compile_with_the_latent_pool_in_place(
        v5e, chip_kernels):
    """ISSUE 48: the chunk programs (buckets 1,024 and 64) and the decode
    block of the ``axk1-L5-ep16.serve-mixed-16k`` cell (the published
    widths: 64 heads against one 640-value row, a query bottleneck of 1,536,
    experts of 88 MB; depth cut to the dense layer and one expert layer,
    the pool to 8 slots' worth, which change no shape) compile for the v5e:
    the latent pool stays where it is, a chunk's grouped matmuls take their
    odd number of tiles, and the decode block carries ``fused_norm_qkv``
    twice a layer (the projections, then ``N_q`` and ``W_qb``), the latent
    kernels and no state kernel."""
    cell = _ServeCell(
        v5e, "axk1-L5-ep16", "axk1-L5-ep16.serve-mixed-16k",
        fields=dict(num_layers=2, layer_types=["latent_attention"] * 2),
        engine=dict(kv_pool_tokens=8 * 16384, num_slots=8))
    assert set(cell.serve._cache) == {"latent"}
    assert cell.serve._cache["latent"].shape[-1] == 640
    chunks = (cell.chunk(1024), cell.chunk(64))
    for program, tokens in zip(chunks, (1024, 64)):
        cell.assert_grouped_matmuls_are_the_kernel(
            program, tokens * 8)
        # ISSUE 49: a kernel a latent layer, no score array, no copied view
        assert _latent_chunk_kernels(program, 64, tokens) == (2, False, False)
    block = cell.block()
    for program in chunks + (block,):
        cell.assert_pools_stay_in_place(program)
    text = block.as_text()
    for name in ("mla_decode_paged", "paged_kv_append", "fused_norm_qkv",
                 "fused_proj_norm", "fused_mlp", "fused_moe_mlp"):
        assert name in text, name
    assert "kda_decode_step" not in text
    calls = lambda name: len(re.findall(
        rf"custom-call\([^\n]*{name}", text))
    assert calls("fused_norm_qkv") == 2 * calls("mla_decode_paged") > 0


def test_dots3_note_cell_programs_compile_with_pages_and_rings_in_place(
        v5e, chip_kernels):
    """ISSUE 52: the chunk programs (buckets 1,024 and 64: a short bucket's
    queries are padded to a lane tile) and the decode block of the
    ``dots3-note-L5-ep16.serve-doc-48k`` cell (the published widths: 128
    heads against a 640-value row under 64 index heads of 128, 64 heads
    against a 1,152-value ring row; depth cut to one full and one sliding
    expert layer, the pool to 6 slots' worth of 32,768 positions, which
    change no shape: half of the latent pages stays larger than a chunk's
    sorted expert rows, the one gather a chunk program may hold) compile for the v5e: the latent pages and the index
    keys stay where they are, a chunk's grouped matmuls take their odd
    number of tiles, a chunk program carries the two selection kernels a
    full layer and no ``mla_chunk_attention``, the decode block the two
    decode kernels a full layer and no ``mla_decode_paged``."""
    cell = _ServeCell(
        v5e, "dots3-note-L5-ep16", "dots3-note-L5-ep16.serve-doc-48k",
        fields=dict(num_layers=2, num_dense_layers=0,
                    layer_types=["latent_attention",
                                 "latent_sliding_attention"]),
        engine=dict(kv_pool_tokens=6 * 32768))
    cache = cell.serve._cache
    assert {k: v.shape[2:] for k, v in cache.items()} == {
        "latent": (1, 256, 640), "index": (1, 256, 128), "ring": (768, 1152)}
    # the index keys and the rings are smaller than a chunk's sorted expert
    # rows: their copies are looked for by shape
    cell.smallest_pool = cache["latent"].nbytes
    shape = lambda k: ",".join(str(d) for d in cache[k].shape)
    calls = lambda text, name: len(re.findall(
        rf"custom-call\([^\n]*{name}", text))
    chunks = (cell.chunk(1024), cell.chunk(64))
    for program, tokens in zip(chunks, (1024, 64)):
        cell.assert_grouped_matmuls_are_the_kernel(
            program, tokens * 8)
        text = program.as_text()
        assert (calls(text, "dsa_index_scores_chunk"),
                calls(text, "dsa_chunk_attention"),
                calls(text, "mla_chunk_attention")) == (1, 1, 0)
        # the per-head index products [64, bucket, keys] never exist
        assert not re.search(rf"f32\[(1,)?64,{tokens},\d{{4,}}\]", text)
    block = cell.block()
    for program in chunks + (block,):
        cell.assert_pools_stay_in_place(program)
        for key in ("index", "ring"):
            assert not re.findall(rf"bf16\[{shape(key)}\]\S* copy\(",
                                  program.as_text()), key
        mem = program.memory_analysis()
        print("memory", mem.temp_size_in_bytes, mem.argument_size_in_bytes,
              mem.output_size_in_bytes, mem.alias_size_in_bytes)
    text = block.as_text()
    for name in ("dsa_index_scores_paged", "dsa_decode_selected",
                 "paged_kv_append", "fused_norm_qkv", "fused_proj_norm",
                 "fused_moe_mlp"):
        assert name in text, name
    # by call, not by name: the text's source table may hold the name of an
    # older model's kernel whose cached helper (``_live_rows``) this one shares
    assert calls(text, "mla_decode_paged") == \
        calls(text, "kda_decode_step") == 0
    assert calls(text, "dsa_index_scores_paged") == \
        calls(text, "dsa_decode_selected") > 0


def test_ouro_cell_programs_roll_the_pass_loop_with_the_pool_in_place(
        v5e, chip_kernels):
    """ISSUE 57: the decode block and the chunk programs of the
    ``ouro-2.6b-L12.serve-reason-768`` cell (two layers of its twelve, run
    four times; four slots and two slots' worth of pool, which changes no
    shape the loop or a copy turns on) compile for the v5e with the pass
    loop ROLLED: the kernel calls of a one-pass model (five a layer and the
    final norm, which here closes every pass inside the loop), one
    attention call a LAYER and not a (pass, layer) pair, its page table the
    prefetched one plus the traced layer's offset, the pool of ``passes x
    layers`` cache layers updated in place and the donations taken."""
    cell = _ServeCell(v5e, "ouro-2.6b-L12", "ouro-2.6b-L12.serve-reason-768",
                      fields=dict(num_layers=2),
                      engine=dict(num_slots=4, kv_pool_tokens=2560))
    cfg, serve = cell.model.config, cell.serve
    assert (cfg.total_ut_steps, cfg.cache_layers) == (4, 8)
    assert serve._cache["k"].shape[0] == 8 and serve.pool.slot_pages == 5
    block = cell.block()
    assert len(serve.engine._dparams["layers"]) == 2
    text = block.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 5 * 2 + 1
    for name in ("flash_decode_paged", "paged_kv_append"):
        calls = [line for line in text.splitlines()
                 if "custom-call(" in line and name in line]
        assert len(calls) == 2, name
    # the pass loop is a loop of the program, inside the block's own
    assert len(re.findall(r" while\(", text)) >= 2
    cell.assert_pools_stay_in_place(block)
    # no serve program reads the exit gate (a threshold of 1), so jit drops
    # its two leaves from a chunk program's arguments
    cell.params = {k: v for k, v in cell.params.items() if k != "exit_gate"}
    for bucket in (8, serve.prefill_chunk):
        chunk = cell.chunk(bucket)
        cell.assert_pools_stay_in_place(chunk)
        cell.assert_donations_taken(chunk, donated=5)
