"""``ops/pallas/grouped_matmul.py`` (ISSUE 64): the Pallas grouped matmul of
the prefill chunk programs' expert blocks against ``jax.lax.ragged_dot`` on
the same operands, in interpret mode, at each expert cell's (K, N, groups)
with the rows cut to CPU size; its schedule; ``_moe_grouped`` whole on the
kernel against itself on ``ragged_dot``; and which of the two a caller gets:
inference over the stacked arrays on a chip takes the kernel, a layer's own
slice (what a gradient flows through) keeps ``ragged_dot``, and nothing but
the platform and the arguments' form reaches that choice."""

import inspect
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.moe.sharded_moe import _moe_grouped, moe_mlp
from deepspeed_tpu.ops.pallas import common, grouped_matmul as gm
from deepspeed_tpu.ops.pallas.grouped_matmul import (ROW_TILE, grouped_matmul,
                                                     row_tile, visits)

KERNEL = "moe_grouped_matmul"

# (K, N, groups held) of a grouped matmul of each expert cell's chunk
# programs (benchmarks/configs): Nemotron's up and down (relu2: two
# matrices), Solar, A.X-K1, Kimi, Trinity, dots3, OLMoE
CELL_SHAPES = {
    "nemotron_up": (2688, 2048, 64), "nemotron_down": (2048, 2688, 64),
    "solar": (4096, 1280, 40), "axk1": (7168, 2048, 12),
    "kimi": (2304, 1024, 32), "trinity": (3072, 3072, 32),
    "dots3": (5120, 1536, 16), "olmoe": (2048, 1024, 64)}


def _layouts(E):
    """rows, group sizes: every case the schedule has.  ``mixed`` = empty
    groups in front, between and behind, a group of one row, a group that
    crosses a row-tile edge (rows 101-230), several groups inside one tile,
    and 16 rows behind the last group (index E: held elsewhere, the pad);
    ``one_group`` = every row in one group, two tiles of it."""
    mixed = np.zeros(E, np.int32)
    mixed[[1, 2, 4, 5, 6, E - 2]] = [100, 1, 130, 3, 4, 2]
    one = np.zeros(E, np.int32)
    one[E // 2] = 2 * ROW_TILE
    return {"mixed": (2 * ROW_TILE, mixed), "one_group": (2 * ROW_TILE, one)}


@pytest.fixture(scope="module")
def stacks():
    """shape -> a [2, E, K, N] stack, built once a shape (a layer's experts
    are 0.35-0.7 GB at these widths): one random [K, N] under a scale a
    (layer, expert), so a block read at another layer or group is off by 6%
    or more."""
    built = {}

    def stack(shape):
        if shape not in built:
            built.clear()                       # one stack alive at a time
            K, N, E = CELL_SHAPES[shape]
            base = jax.random.normal(jax.random.PRNGKey(1), (K, N)) * K ** -0.5
            scale = 1.0 + jnp.arange(2 * E).reshape(2, E, 1, 1) / 16.0
            built[shape] = (base * scale).astype(jnp.bfloat16)
        return built[shape]

    return stack


@pytest.mark.parametrize("layout", ["mixed", "one_group"])
@pytest.mark.parametrize("shape", CELL_SHAPES)
def test_kernel_against_ragged_dot_at_the_cells_widths(stacks, shape, layout):
    """The stacked form at layer 1 (a traced scalar), every case of
    ``_layouts``: the rows of a group bit for bit ``ragged_dot``'s up to the
    bf16 rounding of one float32 accumulation over the whole contraction
    against XLA's."""
    K, N, E = CELL_SHAPES[shape]
    M, sizes = _layouts(E)[layout]
    w = stacks(shape)
    lhs = jax.random.normal(jax.random.PRNGKey(2), (M, K), jnp.bfloat16)
    got = jax.jit(lambda a, w, s, l: grouped_matmul(
        a, w, s, layer=l, impl="interpret"))(
            lhs, w, jnp.asarray(sizes), jnp.asarray(1, jnp.int32))
    # ragged_dot over the groups that hold rows (the CPU's form multiplies
    # every row by every group: 64 groups of these widths are 6 GB)
    hit = np.flatnonzero(sizes)
    want = jax.lax.ragged_dot(lhs, w[1, hit], jnp.asarray(sizes[hit]))
    n = int(sizes.sum())
    assert got.shape == (M, N) and got.dtype == lhs.dtype
    got, want = (np.asarray(a[:n], np.float32) for a in (got, want))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2 ** -7)
    # ... and nearly every element the same bf16 value
    assert np.mean(got == want) > 0.98


@pytest.mark.parametrize("rows,tm", [(6144, 128), (384, 128), (192, 64),
                                     (96, 32), (48, 16), (8, 8), (24, 24),
                                     (200, 128)])
def test_row_tile_divides_the_buckets_rows(rows, tm):
    assert row_tile(rows) == tm


ROW_CASES = {
    # rows, sizes, dtype, layer (None: a layer's own [E, K, N])
    "a_bucket_of_8_tokens": (48, [0, 1, 30, 10], jnp.bfloat16, 2),
    "an_edge_tile_cut_short": (200, [0, 100, 30, 60], jnp.bfloat16, 1),
    "float32_under_a_tile": (10, [3, 0, 2, 4], jnp.float32, None),
    "no_row_in_any_group": (256, [0, 0, 0, 0], jnp.bfloat16, 0),
    "every_group_one_row": (128, [1, 1, 1, 1], jnp.float32, None),
    "an_int_layer": (256, [120, 0, 16, 120], jnp.bfloat16, "int")}


@pytest.mark.parametrize("case", ROW_CASES)
def test_kernel_against_ragged_dot_over_row_counts(case):
    M, sizes, dtype, layer = ROW_CASES[case]
    E, K, N, L = len(sizes), 256, 384, 3
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    lhs = jax.random.normal(keys[0], (M, K), dtype)
    w = (jax.random.normal(keys[1], (L, E, K, N)) * K ** -0.5).astype(dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    if layer is None:
        got = grouped_matmul(lhs, w[1], sizes, impl="interpret")
        want = jax.lax.ragged_dot(lhs, w[1], sizes)
    elif layer == "int":
        got = grouped_matmul(lhs, w, sizes, layer=2, impl="interpret")
        want = jax.lax.ragged_dot(lhs, w[2], sizes)
    else:
        got = jax.jit(lambda l: grouped_matmul(
            lhs, w, sizes, layer=l, impl="interpret"))(jnp.int32(layer))
        want = grouped_matmul(lhs, w, sizes, layer=layer, impl="xla")
    n = int(sizes.sum())
    tol = 1e-5 if dtype == jnp.float32 else 2 ** -7
    np.testing.assert_allclose(np.asarray(got[:n], np.float32),
                               np.asarray(want[:n], np.float32),
                               rtol=tol, atol=tol)


def test_the_schedule_visits_the_pairs_that_hold_rows():
    """Groups in order, a group's tiles in order, a shared tile once a
    group, nothing for an empty group or behind the last group."""
    sizes = jnp.asarray([0, 100, 1, 130, 0, 3, 300, 0], jnp.int32)
    group, tile, ahead, offsets, count = visits(sizes, 1024, 128)
    n = int(count)
    assert group.shape == tile.shape == ahead.shape == (8 + 8 - 1,)
    assert list(zip(np.asarray(group[:n]), np.asarray(tile[:n]))) == [
        (1, 0), (2, 0), (3, 0), (3, 1), (5, 1), (6, 1), (6, 2), (6, 3),
        (6, 4)]
    # a group's first visit names the next group that holds rows, the last
    # group's the first again; its other visits fetch nothing
    assert list(np.asarray(ahead[:n])) == [2, 3, 5, -1, 6, 1, -1, -1, -1]
    assert list(np.asarray(offsets)) == [0, 0, 100, 101, 231, 231, 234, 534,
                                         534]
    # what the grid never reaches still indexes inside the arrays
    assert int(group.max()) < 8 and int(tile.max()) < 8
    assert int(visits(jnp.zeros((8,), jnp.int32), 1024, 128)[4]) == 0


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of ``jaxpr``, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_calls(sub))
    return found


def _primitives(jaxpr):
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


def test_one_kernel_a_call_its_grid_read_at_run_time():
    """Grid (column blocks, visits): the second extent a traced scalar;
    five scalar-prefetched vectors, the rows and the stack as it is."""
    lhs = jnp.zeros((6144, 2688), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((4, 64, 2688, 2048), jnp.bfloat16)
    (eqn,) = _pallas_calls(jax.make_jaxpr(lambda a, w, s, l: grouped_matmul(
        a, w, s, layer=l, impl="interpret"))(
            lhs, w, jnp.zeros((64,), jnp.int32), jnp.int32(0)).jaxpr)
    gm_ = eqn.params["grid_mapping"]
    assert eqn.params["name"] == KERNEL
    assert [isinstance(g, int) for g in gm_.grid] == [True, False]
    assert gm_.grid[0] == 2                    # [2688, 1024] weight blocks
    assert (gm_.num_index_operands, len(gm_.block_mappings)) == (5, 3)
    # (the stack stays where it is, whole: the kernel copies its [2688,
    # 1024] blocks itself)
    assert [tuple(d.block_size for d in b.block_shape)
            for b in gm_.block_mappings] == [
        (128, 2688), (4 * 64, 2688, 2048), (128, 1024)]


# -- _moe_grouped whole, on the kernel against itself on ragged_dot ----------
def _expert_block(glu, N=96, k=4, E=4, D=128, F=256, L=3, seed=0):
    cfg = SimpleNamespace(num_experts=E, num_experts_per_tok=k, glu=glu,
                          activation="silu" if glu else "relu2")
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    stack = {n: jax.random.normal(key, (L, E) + shape) * shape[0] ** -0.5
             for n, key, shape in (("w_up", keys[0], (D, F)),
                                   ("w_gate", keys[1], (D, F)),
                                   ("w_down", keys[2], (F, D)))
             if glu or n != "w_gate"}
    x = jax.random.normal(keys[3], (N, D))
    local = jax.random.randint(keys[4], (N, k), 0, 3 * E)
    local = jnp.where(local < E, local, E)      # two in three held elsewhere
    weight = jnp.where(local < E, jax.random.uniform(keys[5], (N, k)), 0.0)
    return cfg, stack, x, (weight, local)


@pytest.fixture
def chip_kernels(monkeypatch):
    """This process's devices are CPUs: take the kernels a chip would, in
    interpret mode."""
    monkeypatch.setattr(common, "default_impl", lambda: "interpret")


@pytest.mark.parametrize("glu", [True, False], ids=["gated", "relu2"])
@pytest.mark.parametrize("form", ["assign", "router"])
def test_moe_grouped_on_the_kernel_is_itself_on_ragged_dot(form, glu,
                                                           monkeypatch):
    cfg, stack, x, assign = _expert_block(glu)
    gates = jax.nn.softmax(x[:, :cfg.num_experts], axis=-1)
    layer = jnp.asarray(2, jnp.int32)

    def block():                # a new function a call: traced anew
        if form == "assign":
            return lambda: _moe_grouped(stack, x, None, cfg, False,
                                        layer=layer, assign=assign)[0]
        return lambda: _moe_grouped(stack, x, gates, cfg, True,
                                    layer=layer)[0]

    want = block()()
    assert "pallas_call" not in _primitives(jax.make_jaxpr(block())().jaxpr)
    monkeypatch.setattr(common, "default_impl", lambda: "interpret")
    traced = jax.make_jaxpr(block())().jaxpr
    calls = _pallas_calls(traced)
    assert [c.params["name"] for c in calls] == [KERNEL] * (3 if glu else 2)
    assert "ragged_dot_general" not in _primitives(traced)
    # no tile of pad in front of the kernel: N * k rows as they are
    # (operands: the grid's run-time extent, five prefetched vectors, rows)
    assert {c.invars[6].aval.shape[0] for c in calls} == {x.shape[0] * 4}
    got = block()()
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# -- which of the two a caller gets -------------------------------------------
def _olmoe_like():
    cfg = SimpleNamespace(num_experts=4, num_experts_per_tok=2, glu=True,
                          activation="silu", moe_drop_tokens=False,
                          moe_norm_topk_prob=False)
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    D, F = 64, 128
    params = {"gate_w": jax.random.normal(keys[0], (D, 4)) * D ** -0.5,
              "w_up": jax.random.normal(keys[1], (4, D, F)) * D ** -0.5,
              "w_gate": jax.random.normal(keys[2], (4, D, F)) * D ** -0.5,
              "w_down": jax.random.normal(keys[3], (4, F, D)) * F ** -0.5}
    return cfg, params, jax.random.normal(keys[4], (2, 24, D))


def test_a_gradient_flows_through_ragged_dot_on_a_chip_too(chip_kernels):
    """Training (``moe_mlp(moe_drop_tokens=False)`` on a layer's own slice):
    with the chip's kernels chosen the program still holds ``ragged_dot``
    and no kernel, and ``jax.grad`` runs and matches the gradient of a dense
    loop over the experts."""
    cfg, params, x = _olmoe_like()
    loss = lambda p: jnp.sum(moe_mlp(p, x, cfg)[0] ** 2)
    traced = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    assert "ragged_dot_general" in _primitives(traced)
    assert not _pallas_calls(traced)

    def dense(p):
        xt = x.reshape(-1, x.shape[-1])
        gates = jax.nn.softmax(xt @ p["gate_w"], axis=-1)
        weight, idx = sharded_moe.topk_weights(gates, 2, False)
        y = 0.0
        for e in range(4):
            out = (jax.nn.silu(xt @ p["w_gate"][e]) * (xt @ p["w_up"][e])) \
                @ p["w_down"][e]
            y = y + out * jnp.sum(jnp.where(idx == e, weight, 0.0), -1,
                                  keepdims=True)
        return jnp.sum(y ** 2)

    got, want = jax.grad(loss)(params), jax.grad(dense)(params)
    for name in params:
        np.testing.assert_allclose(got[name], want[name], rtol=2e-4,
                                   atol=2e-4, err_msg=name)


def test_the_router_form_over_the_stack_takes_the_kernel(chip_kernels):
    """OLMoE's chunk programs (``moe_mlp(layer=)``, the router inside):
    three kernel calls, no ``ragged_dot``, the layer's own form's result;
    under a mesh that splits ``tp`` (GSPMD cannot split a Pallas kernel)
    ``ragged_dot`` stays."""
    from deepspeed_tpu.comm.mesh import build_mesh

    cfg, params, x = _olmoe_like()
    stack = {n: jnp.stack([params[n] * 0.5, params[n]])
             for n in ("w_up", "w_gate", "w_down")}
    stacked = lambda l, mesh=None: moe_mlp(
        {"gate_w": params["gate_w"], **stack}, x, cfg, mesh, layer=l)[0]
    traced = jax.make_jaxpr(stacked)(jnp.int32(1)).jaxpr
    assert [c.params["name"] for c in _pallas_calls(traced)] == [KERNEL] * 3
    assert "ragged_dot_general" not in _primitives(traced)
    for axes, calls in ((dict(tp=2), 0), (dict(fsdp=2), 3)):
        mesh = build_mesh(devices=jax.devices()[:2], **axes)
        split = jax.make_jaxpr(lambda l: stacked(l, mesh))(jnp.int32(1)).jaxpr
        assert len(_pallas_calls(split)) == calls, axes
    np.testing.assert_allclose(jax.jit(stacked)(jnp.int32(1)),
                               moe_mlp(params, x, cfg)[0],
                               rtol=2e-5, atol=2e-5)


def test_nothing_but_platform_and_form_reaches_the_choice(monkeypatch):
    """No argument of ``_moe_grouped`` / ``moe_mlp`` / ``afmoe.mlp`` names an
    implementation, the choice reads no configuration field and no
    environment variable of its own (``DSTPU_KERNEL_IMPL`` is every kernel's
    debugging override, read once in ``common``), and a configuration of any
    name traces the same program."""
    from deepspeed_tpu.models import afmoe

    for fn in (_moe_grouped, moe_mlp, afmoe.mlp, grouped_matmul):
        assert not {"kernel", "use_kernel", "ragged", "backend"} \
            & set(inspect.signature(fn).parameters), fn
    assert "impl" not in inspect.signature(_moe_grouped).parameters
    assert "impl" not in inspect.signature(moe_mlp).parameters
    for module in (sharded_moe, gm):
        assert "environ" not in inspect.getsource(module)
    chosen = inspect.getsource(_moe_grouped).split("    kernel = ")[1] \
        .split("\n    if assign")[0]
    assert chosen.startswith('layer is not None and resolve_impl(None) != '
                             '"xla" and (\n        mesh is None')
    assert "cfg" not in chosen and "environ" not in chosen

    class Spy(SimpleNamespace):
        """A configuration that records every field read."""
        def __getattribute__(self, name):
            if not name.startswith("__"):
                read.add(name)
            return super().__getattribute__(name)

    read = set()
    monkeypatch.setattr(common, "default_impl", lambda: "interpret")
    cfg, stack, x, assign = _expert_block(True)
    spy = Spy(**vars(cfg), name="olmoe", model_type="nemotron")
    jax.make_jaxpr(lambda: _moe_grouped(
        stack, x, None, spy, False, layer=jnp.int32(0), assign=assign))()
    assert read == {"num_experts", "num_experts_per_tok", "activation",
                    "glu"}


# -- the kernel's own copies under jax's TPU interpreter -----------------------
@pytest.fixture(params=["on_wait", "eager"])
def chips_interpreter(request, monkeypatch, capfd):
    """``grouped_matmul`` under jax's TPU interpreter (as ``tests/conftest.py:
    chips_interpreter`` runs the page walks): VMEM starts as NaN and races
    between a copy and the vector units are looked for.  ``on_wait``: a copy
    lands only when it is waited for, so a block read before its wait reads
    NaN.  ``eager``: a copy lands and signals when it is started, so one
    started and never waited for leaves its semaphore above 0 at the
    kernel's end, which the interpreter prints (under ``on_wait`` such a
    copy never runs and nothing shows)."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    params = pltpu.InterpretParams(detect_races=True,
                                   dma_execution_mode=request.param)
    monkeypatch.setattr(gm, "interpret_flag", lambda impl: params)
    # the call is a jitted function: traced here, under this interpreter
    # and this test's block budget, through a ``jit`` of its own
    monkeypatch.setattr(gm, "_visit_groups", jax.jit(
        gm._visit_groups.__wrapped__, static_argnames=("impl",)))

    def check():
        assert not interpret_pallas_call.races.races_found
        assert "non-zero count" not in capfd.readouterr().out

    return check


COPY_CASES = {
    # sizes over 4 groups, column blocks: every order of waits and fetches
    "groups_across_tile_edges": ([100, 60, 0, 200], 3),
    "one_group_of_many_tiles": ([0, 384, 0, 0], 2),
    "one_column_block": ([5, 0, 300, 1], 1),
    "a_single_visit": ([0, 0, 7, 0], 1),
    "no_visit": ([0, 0, 0, 0], 2)}


@pytest.mark.parametrize("case", COPY_CASES)
def test_every_weight_copy_is_waited_for_before_its_block_is_read(
        chips_interpreter, monkeypatch, case):
    sizes, blocks = COPY_CASES[case]
    M, K, L, E = 384, 256, 2, 4
    N = 128 * blocks
    # a budget of one [256, 128] block: ``blocks`` column blocks a group
    monkeypatch.setattr(gm, "_col_block", lambda *a, **kw: 128)
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    lhs = jax.random.normal(keys[0], (M, K))
    w = jax.random.normal(keys[1], (L, E, K, N)) * K ** -0.5
    sizes = jnp.asarray(sizes, jnp.int32)
    got = grouped_matmul(lhs, w, sizes, layer=jnp.int32(1), impl="interpret")
    n = int(sizes.sum())
    want = jax.lax.ragged_dot(lhs, w[1], sizes)
    np.testing.assert_allclose(got[:n], want[:n], rtol=1e-5, atol=1e-5)
    chips_interpreter()
