"""Test harness configuration.

The reference tests distributed behavior by forking N processes on one box
(SURVEY.md §4 ``DistributedTest``).  The TPU-native equivalent is simpler and
stronger: a single process with N virtual XLA CPU devices, so every test runs
the real SPMD code path (mesh + collectives) deterministically.  This must run
before jax is imported anywhere.
"""

import os

# Tests always run on the virtual CPU mesh.  Set before jax is imported:
# the platform and device count are read when the backend starts.
# The concurrency-optimized scheduler can order two independent
# collectives differently across the in-process CPU "devices", deadlocking
# the rendezvous (observed with MoE's ep all-gathers + loss all-reduce).
# TPU executes collectives in one serialized stream, so this is test-only.
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           "--xla_cpu_enable_concurrency_optimized_scheduler=false "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["DS_ACCELERATOR"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

from deepspeed_tpu.utils.compile_cache import place_compile_cache  # noqa: E402

# Persistent XLA compilation cache: the suite compiles many IDENTICAL
# tiny-model programs (every engine instance re-jits the same decode loop /
# prefill shapes), and compiles dominate tier-1 wall time on small hosts.
# The cache dedupes by HLO hash within a run and persists across runs.
place_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def pytest_configure(config):
    # tier-1 runs with -m 'not slow'; register the marker so the
    # slow-marked suites under tests/perf/ don't warn
    config.addinivalue_line("markers",
                            "slow: long benchmark; excluded from tier-1")


@pytest.fixture(autouse=True)
def _restore_global_mesh():
    """Tests that set_global_mesh (sp/pp/ep layouts) must not leak their
    mesh into later tests that build engines off the global default."""
    from deepspeed_tpu.comm import mesh as mesh_mod

    prev = mesh_mod._GLOBAL_MESH
    yield
    mesh_mod._GLOBAL_MESH = prev


@pytest.fixture(autouse=True)
def _restore_metrics_registry_enabled():
    """The disabled-by-default metrics registry is process-global, and an
    engine built with ``comms_logger.enabled`` flips it on (PR 3) — a test
    doing so must not leave later tests recording into shared counters
    (the serving suite's unknown-finish-reason guard depends on a clean
    enabled-state baseline)."""
    from deepspeed_tpu.monitor.comms import comm_metrics
    from deepspeed_tpu.monitor.metrics import get_registry
    from deepspeed_tpu.monitor.request_trace import get_request_tracer

    reg = get_registry()
    tracer = get_request_tracer()
    prev_reg, prev_comms = reg.enabled, comm_metrics.enabled
    prev_tracer = tracer.enabled
    yield
    # through the switch, not the flag: it takes the compile ledger's
    # jax.monitoring listeners off again (profiling/trace.py)
    (reg.enable if prev_reg else reg.disable)()
    comm_metrics.enabled = prev_comms
    tracer.enabled = prev_tracer


@pytest.fixture(autouse=True)
def _goodput_ledger_guard():
    """A test that leaves the process-global goodput ledger enabled must
    leave it TELESCOPING (category sum == wall at rel 1e-9, the ISSUE 18
    run-attribution contract) — checked after EVERY test, then the
    ledger is reset so run clocks and jsonl paths don't leak across
    tests (the engine enables it from config/env; a leaked enable would
    time unrelated tests into one run)."""
    yield
    from deepspeed_tpu.monitor import goodput_core
    from deepspeed_tpu.monitor.goodput import get_goodput_ledger

    gp = get_goodput_ledger()
    if gp.enabled:
        snap = gp.snapshot()
        gp._path = None          # teardown must not append to a test's jsonl
        gp.disable()
        assert goodput_core.telescopes(snap), (
            "goodput ledger left non-telescoping: wall "
            f"{snap['wall_s']} vs sum {sum(snap['categories'].values())} "
            f"(open regions: {snap['open_regions']})")


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture()
def mesh8(devices):
    from deepspeed_tpu.comm.mesh import build_mesh

    return build_mesh(fsdp=8, devices=devices)


@pytest.fixture()
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture
def chips_interpreter(monkeypatch, capfd):
    """The kernels that walk a row's pages (``ops/pallas/decode.py:
    _walk_pages``) under jax's TPU interpreter for the test: VMEM starts as
    NaN, a copy lands only when it is waited for, and races between a copy
    and the vector units are looked for.  A page scored before its copy was
    waited for would read NaN; a copy started and never waited for (the
    next row's first page after the LAST grid step, a piece past the rows
    that count) leaves its semaphore above 0 at the kernel's end, which the
    interpreter reports.  Returns the check to make after ``calls`` kernel
    calls: each was traced under this interpreter, none raced, every
    semaphore ended at 0."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    from deepspeed_tpu.ops.pallas import decode

    params = pltpu.InterpretParams(detect_races=True,
                                   dma_execution_mode="on_wait")
    asked = []
    monkeypatch.setattr(decode, "interpret_flag",
                        lambda impl: asked.append(impl) or params)
    # the call is a jitted function: traced here, under this interpreter,
    # through a ``jit`` of its own, which goes with the test; clearing jax's
    # caches to the same end cost the worker every file's programs
    walk = decode._walk_pages.__wrapped__
    monkeypatch.setattr(decode, "_walk_pages", jax.jit(
        lambda *a, **kw: walk(*a, **kw),
        static_argnames=("scale", "alibi", "impl", "name", "window",
                         "chunk")))

    def check(calls=1):
        assert asked == ["interpret"] * calls
        assert not interpret_pallas_call.races.races_found
        assert "non-zero count" not in capfd.readouterr().out

    return check
