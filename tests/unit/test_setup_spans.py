"""Set-up measured inside the program (ISSUE 55): the compile ledger counts
what jax traced, lowered and compiled once each and by name, without a
second counted twice; it exists only while the process's registry is on;
and the ``ds_setup_*`` phases stand around what the entry points build.
CPU, tiny sizes: counts and identities, never a speed."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src import monitoring
from jax.experimental import pallas as pl
from jax.experimental.compilation_cache import compilation_cache

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import build_mesh, set_global_mesh
from deepspeed_tpu.models import causal_lm
from deepspeed_tpu.monitor.metrics import MetricsRegistry, get_registry
from deepspeed_tpu.profiling.trace import (CompileLedger, compile_ledger,
                                           phase)

STAGE_TOTALS = ("ds_jit_trace_seconds_total", "ds_jit_lower_seconds_total",
                "ds_jit_compile_seconds_total")


def _plain(reg):
    return {k: v for k, v in reg.snapshot().items()
            if isinstance(v, (int, float))}


def _row(name):
    rows = [r for r in compile_ledger().rows() if r["program"] == name]
    assert len(rows) == 1, (name, [r["program"]
                                   for r in compile_ledger().rows()])
    return rows[0]


@pytest.fixture()
def registry_on():
    reg = get_registry().enable()
    compile_ledger().clear()
    yield reg
    # tests/conftest.py puts the switch back where it was


def _listeners():
    return (monitoring.get_event_listeners(),
            monitoring.get_event_duration_listeners(),
            monitoring.get_event_time_span_listeners(),
            monitoring.get_scalar_listeners())


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------

def test_one_jit_is_counted_once_in_every_stage_and_named(registry_on):
    def ledger_probe_once(x):
        return jnp.tanh(x) * 3 + 1

    before = _plain(registry_on)
    t0 = time.perf_counter()
    jax.jit(ledger_probe_once)(np.ones((4, 4), np.float32)) \
        .block_until_ready()
    t1 = time.perf_counter()
    after = _plain(registry_on)
    row = _row("jit_ledger_probe_once")
    assert (row["trace"], row["lower"], row["compile"]) == (1, 1, 1)
    assert row["calls"] == 1 and row["phase"] is None
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["compile_s"] > 0
    # rows sit on perf_counter(), the clock of `phase`
    assert t0 - 0.05 <= row["first_start"] <= row["last_end"] <= t1 + 0.05
    assert after["ds_jit_programs_total"] - before["ds_jit_programs_total"] \
        >= 1
    spent = sum(after[k] - before[k] for k in STAGE_TOTALS)
    assert 0 < spent <= t1 - t0
    # the labelled series carry the row
    series = registry_on.get("ds_jit_stage_seconds_total",
                             {"stage": "compile",
                              "program": "jit_ledger_probe_once"})
    assert series.value == pytest.approx(row["compile_s"])
    # a second call compiles nothing
    jax.jit(ledger_probe_once)  # a new wrapper of the same function
    assert _row("jit_ledger_probe_once")["compile"] == 1


def test_nested_spans_are_not_counted_twice(registry_on):
    """An inner jit and an interpreted kernel traced inside an outer jit:
    the rows hold each span whole, the totals hold every second once."""
    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    @jax.jit
    def ledger_probe_inner(x):
        return jnp.sin(x) @ x.T @ x

    def ledger_probe_outer(x):
        y = ledger_probe_inner(x)
        z = pl.pallas_call(
            double, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(y)
        return ledger_probe_inner(z + 1).sum()

    x = np.ones((8, 128), np.float32)
    before = _plain(registry_on)
    t0 = time.perf_counter()
    jax.jit(ledger_probe_outer)(x).block_until_ready()
    wall = time.perf_counter() - t0
    after = _plain(registry_on)
    spent = {k: after[k] - before[k] for k in STAGE_TOTALS}
    assert 0 < sum(spent.values()) <= wall
    outer = _row("jit_ledger_probe_outer")
    inner = _row("jit_ledger_probe_inner")
    assert inner["trace"] >= 1 and inner["compile"] == 0   # traced inline
    assert inner["first_start"] >= outer["first_start"]
    rows = compile_ledger().rows()
    inclusive = sum(r["trace_s"] for r in rows)
    # the outer trace holds the inner ones: inclusive seconds count them
    # twice, self seconds once
    assert inclusive >= outer["trace_s"] + inner["trace_s"]
    assert spent["ds_jit_trace_seconds_total"] < inclusive
    assert spent["ds_jit_trace_seconds_total"] == pytest.approx(
        outer["trace_s"], rel=0.05, abs=0.01)
    assert sum(r["self_s"] for r in rows) == pytest.approx(
        sum(spent.values()), rel=1e-6)


def test_a_second_run_reads_the_cache(registry_on, tmp_path):
    def ledger_probe_cached(x):
        return jnp.cos(x) * 5 - 2

    kept = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        x = np.ones((3, 5), np.float32)
        jax.jit(ledger_probe_cached)(x).block_until_ready()
        cold = _row("jit_ledger_probe_cached")
        assert (cold["hits"], cold["misses"]) == (0, 1)
        assert cold["cache_read_s"] == 0.0
        # a second run of the program: nothing in memory, the directory kept
        jax.clear_caches()
        before = _plain(registry_on)
        jax.jit(ledger_probe_cached)(x).block_until_ready()
        after = _plain(registry_on)
        warm = _row("jit_ledger_probe_cached")
        assert (warm["hits"], warm["misses"]) == (1, 1)
        assert warm["compile"] == 2 and warm["cache_read_s"] > 0
        assert warm["cache_read_s"] <= warm["compile_s"]
        read = (after["ds_jit_cache_read_seconds_total"]
                - before["ds_jit_cache_read_seconds_total"])
        assert read == pytest.approx(warm["cache_read_s"])
        assert after["ds_jit_cache_hits_total"] \
            - before["ds_jit_cache_hits_total"] == 1
        assert after["ds_jit_cache_misses_total"] \
            == before["ds_jit_cache_misses_total"]
    finally:
        for k, v in kept.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_listeners_follow_the_process_registry_and_no_other():
    reg = get_registry()
    reg.disable()
    found = _listeners()
    assert not compile_ledger().installed
    # off: a phase keeps nothing, and a private registry installs nothing
    with phase("ds_setup_engine"):
        pass
    private = MetricsRegistry().enable()
    with phase("ds_setup_pool", registry=private):
        pass
    assert _listeners() == found
    assert private.get("ds_setup_pool_seconds_total").value > 0
    reg.enable()
    reg.enable()                      # twice is once
    assert compile_ledger().installed
    on = _listeners()
    assert [len(a) - len(b) for a, b in zip(on, found)] == [1, 1, 1, 1]
    assert reg.get("ds_setup_import_seconds").value > 0
    reg.disable()
    assert _listeners() == found and not compile_ledger().installed


def test_the_513th_name_lands_in_other():
    reg = MetricsRegistry().enable()
    ledger = CompileLedger(reg)
    event = "/jax/core/compile/jaxpr_trace_duration"
    n = CompileLedger.MAX_PROGRAMS
    for i in range(n + 3):
        ledger._on_span(event, 10.0 + i, 10.5 + i, fun_name=f"f{i}")
    ledger._on_span(event, 1000.0, 1001.0, fun_name="f0")   # a known name
    rows = {r["program"]: r for r in ledger.rows()}
    assert len(rows) == n + 1
    assert rows["other"]["trace"] == 3 and rows["jit_f0"]["trace"] == 2
    assert rows["other"]["trace_s"] == pytest.approx(1.5)
    assert reg.get("ds_jit_trace_seconds_total").value == \
        pytest.approx(0.5 * (n + 3) + 1.0)
    # a disabled registry's ledger keeps nothing
    reg.disable()
    ledger._on_span(event, 2000.0, 2001.0, fun_name="f1")
    assert {r["program"]: r for r in ledger.rows()}["jit_f1"]["trace"] == 1


def test_spans_of_other_stages_nest_too():
    """An eager op compiled while a function is traced: its lowering and
    compile come out of the trace around them."""
    reg = MetricsRegistry().enable()
    ledger = CompileLedger(reg)
    pre = "/jax/core/compile/"
    ledger._on_span(pre + "jaxpr_trace_duration", 1.0, 1.5, fun_name="iota")
    ledger._on_span(pre + "jaxpr_to_mlir_module_duration", 1.5, 2.0,
                    fun_name="jit(iota)")
    ledger._on_event("/jax/compilation_cache/cache_misses")
    ledger._on_span(pre + "backend_compile_duration", 2.0, 4.0,
                    fun_name="jit(iota)")
    ledger._on_span(pre + "jaxpr_trace_duration", 0.0, 10.0, fun_name="step")
    ledger._on_span(pre + "jaxpr_to_mlir_module_duration", 10.0, 11.0,
                    fun_name="jit(step)")
    got = {k: reg.get(k).value for k in STAGE_TOTALS}
    assert got == {"ds_jit_trace_seconds_total": pytest.approx(0.5 + 7.0),
                   "ds_jit_lower_seconds_total": pytest.approx(0.5 + 1.0),
                   "ds_jit_compile_seconds_total": pytest.approx(2.0)}
    assert sum(got.values()) == pytest.approx(11.0)       # the wall time
    rows = {r["program"]: r for r in ledger.rows()}
    assert rows["jit_step"]["trace_s"] == pytest.approx(10.0)   # inclusive
    assert rows["jit_iota"]["misses"] == 1
    assert reg.get("ds_jit_programs_total").value == 1


def test_a_span_with_thousands_of_children_counts_each_second_once():
    """A 48-layer model's trace holds more inner traces than any bound on
    what a thread keeps (found on the chip: `chip_smoke`'s serve phase read
    trace + lower + compile 9 s over its wall time): the begins jax sends
    say when nothing is open, so nothing has to be dropped."""
    reg = MetricsRegistry().enable()
    ledger = CompileLedger(reg)
    event = "/jax/core/compile/jaxpr_trace_duration"
    n = 3 * CompileLedger._PENDING_MAX
    for outer in (0.0, 100000.0):                # twice: state is let go
        ledger._on_begin(event, outer, fun_name="step")
        for i in range(n):
            t = outer + 1.0 + i
            ledger._on_begin(event, t, fun_name="add")
            ledger._on_span(event, t, t + 0.5, fun_name="add")
        ledger._on_span(event, outer, outer + n + 2.0, fun_name="step")
        assert ledger._tls.pending == [] and ledger._tls.open == 0
    assert reg.get("ds_jit_trace_seconds_total").value == \
        pytest.approx(2 * (n + 2.0))              # the wall time, not more
    rows = {r["program"]: r for r in ledger.rows()}
    assert rows["jit_step"]["self_s"] == pytest.approx(2 * (0.5 * n + 2.0))


# ---------------------------------------------------------------------------
# the phases around what the entry points build
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny(devices):
    mesh = build_mesh(fsdp=8, devices=devices)
    set_global_mesh(mesh)
    model = causal_lm("llama-tiny", mesh=mesh, num_layers=2, hidden_size=64,
                      intermediate_size=128, num_heads=4, num_kv_heads=2,
                      vocab_size=256, remat=False)
    return mesh, model, model.init(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 8), jnp.int32))


def test_init_serving_stands_inside_its_phases(tiny, registry_on):
    mesh, model, params = tiny
    jax.clear_caches()         # whatever an earlier test compiled
    before = _plain(registry_on)
    t0 = time.perf_counter()
    serve = deepspeed_tpu.init_serving(
        model, config={"dtype": "float32", "max_out_tokens": 64,
                       "kv_page_tokens": 16},
        params=params, mesh=mesh, num_slots=2, prefill_chunk=4,
        decode_block_tokens=3)
    wall = time.perf_counter() - t0
    rows = compile_ledger().rows()
    serve.close()
    after = _plain(registry_on)
    took = {k: after[f"ds_setup_{k}_seconds_total"]
            - before.get(f"ds_setup_{k}_seconds_total", 0.0)
            for k in ("serving", "engine", "inject", "pool")}
    assert all(v > 0 for v in took.values()), took
    assert took["inject"] <= took["engine"]
    assert took["engine"] + took["pool"] <= took["serving"] <= wall
    # nested ranges add up once
    assert after["ds_setup_seconds_total"] \
        - before.get("ds_setup_seconds_total", 0.0) == \
        pytest.approx(took["serving"])
    # every program that began in there says where
    assert rows and {r["phase"] for r in rows} <= {
        "ds_setup_serving", "ds_setup_engine", "ds_setup_inject",
        "ds_setup_pool"}
    in_setup = (after["ds_jit_in_setup_seconds_total"]
                - before["ds_jit_in_setup_seconds_total"])
    assert in_setup == pytest.approx(sum(r["self_s"] for r in rows))
    assert in_setup <= took["serving"]
    # a program that begins after set-up names no phase
    jax.jit(lambda x: x - 7)(np.ones(3, np.float32))
    assert _row("jit__lambda_")["phase"] is None


def test_initialize_stands_inside_its_phases(tiny, registry_on):
    mesh, model, _ = tiny
    before = _plain(registry_on)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, mesh=mesh, rng=jax.random.PRNGKey(0),
        config={"train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "steps_per_print": 10 ** 9})
    tokens = np.ones((1, 8, 16), np.int32)
    engine.train_step((tokens, tokens)).block_until_ready()  # lazy init
    after = _plain(registry_on)
    took = {k: after[f"ds_setup_{k}_seconds_total"]
            - before.get(f"ds_setup_{k}_seconds_total", 0.0)
            for k in ("initialize", "params", "compile_steps")}
    assert all(v > 0 for v in took.values()), took
    assert took["compile_steps"] <= took["params"]     # _init_state's tail
    # the weights were made at the first batch, outside initialize()
    assert after["ds_setup_seconds_total"] \
        - before.get("ds_setup_seconds_total", 0.0) == \
        pytest.approx(took["initialize"] + took["params"])
    by = {r["program"]: r for r in compile_ledger().rows()}
    assert by["jit_build_fn" if "jit_build_fn" in by
              else "jit_init_fn"]["phase"] == "ds_setup_params"
