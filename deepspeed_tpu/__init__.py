"""deepspeed_tpu — a TPU-native training/inference framework with the
capability surface of DeepSpeed (reference: kooyunmo/DeepSpeed; see SURVEY.md).

Public API parity (SURVEY.md §2.1 "Public API"): ``initialize()``,
``init_inference()``, ``init_distributed()``, ``add_config_arguments()``, the
``comm`` and ``zero`` submodules, and ``DeepSpeedConfig`` — reimplemented over
jax/XLA/pjit with a device mesh instead of torch/NCCL.
"""

from __future__ import annotations

__version__ = "0.1.0"
__git_branch__ = "main"

from deepspeed_tpu import comm  # noqa: F401
from deepspeed_tpu.runtime import zero  # noqa: F401
from deepspeed_tpu.accelerator import get_accelerator  # noqa: F401
from deepspeed_tpu.runtime.config import DeepSpeedConfig  # noqa: F401
from deepspeed_tpu.utils.logging import log_dist, logger  # noqa: F401


def initialize(args=None, model=None, optimizer=None, model_parameters=None,
               training_data=None, lr_scheduler=None, distributed_port=29500,
               mpu=None, dist_init_required=None, collate_fn=None, config=None,
               config_params=None, mesh=None, rng=None, loss_fn=None):
    """Create a training engine (reference contract: SURVEY.md §3.2).

    Returns ``(engine, optimizer, training_dataloader, lr_scheduler)``.

    ``model`` may be a flax ``nn.Module`` or any object exposing
    ``init(rng, *inputs)`` / ``apply(params, *inputs)``.  See
    ``deepspeed_tpu/runtime/engine.py`` for the engine design (functional
    jitted train step under an imperative forward/backward/step façade).
    """
    from deepspeed_tpu.profiling.trace import phase
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    cfg = config if config is not None else config_params
    if cfg is None and args is not None and hasattr(args, "deepspeed_config"):
        cfg = args.deepspeed_config
    with phase("ds_setup_initialize"):
        engine = DeepSpeedEngine(args=args, model=model, optimizer=optimizer,
                                 model_parameters=model_parameters, training_data=training_data,
                                 lr_scheduler=lr_scheduler, mpu=mpu,
                                 dist_init_required=dist_init_required, collate_fn=collate_fn,
                                 config=cfg, mesh=mesh, rng=rng, loss_fn=loss_fn)
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def init_inference(model=None, config=None, **kwargs):
    """Create an inference engine (reference: SURVEY.md §3.5)."""
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig

    # engine-level kwargs (not config keys): jax models are functional, so
    # weights arrive separately from the module (torch bundles them)
    params = kwargs.pop("params", None)
    mesh = kwargs.pop("mesh", None)
    config = _merge_inference_config(config, kwargs, DeepSpeedInferenceConfig)
    return InferenceEngine(model, config, params=params, mesh=mesh)


def _merge_inference_config(config, kwargs, cls):
    """Overlay config-key kwargs on ``config`` (dict, model instance, or
    None) without dropping the instance's settings."""
    if config is None:
        config = kwargs
    elif kwargs:
        base = config.model_dump() if isinstance(config, cls) else dict(config)
        config = {**base, **kwargs}
    if not isinstance(config, cls):
        config = cls(**config)
    return config


def init_serving(model=None, config=None, **kwargs):
    """Create a continuous-batching :class:`~deepspeed_tpu.serving.engine.
    ServingEngine` (the MII / DeepSpeed-FastGen dynamic-batching role):
    paged KV cache (slots draw token pages from one shared pool),
    iteration-level scheduling, chunked prefill interleaved with
    per-row-position decode, and sync-free (device-resident) EOS
    termination with deferred finish-event drains.

    ``metrics_port=`` (optional) enables the engine's metrics registry
    and serves it over HTTP for the engine's lifetime: ``GET /metrics``
    (Prometheus text) + ``GET /statz`` (JSON snapshot) + ``GET
    /requestz`` (per-request span timelines) + ``GET /healthz``
    (readiness) + ``POST /generate`` (the multi-replica router's dispatch
    target — ``serving/router.py``; requires a stepping loop, see
    ``serve_loop`` below).  Pass ``0`` for an ephemeral port — read it
    back from ``engine.metrics_server.port``.
    ``request_trace=True`` (optional) additionally enables the
    per-request span tracer (``monitor/request_trace.py``) feeding
    ``/requestz`` and the ``ds_serve_phase_*`` attribution histograms —
    off by default (one branch, zero allocation per lifecycle hook).
    ``serve_loop=True`` starts the background serving loop
    (``ServingEngine.start_loop``) so ``/generate`` requests progress
    without a caller-driven ``step()`` loop.
    ``registry=`` / ``private_health=True`` scope the metrics registry
    and the ``/healthz`` readiness flag to THIS engine instead of the
    process globals — how N replica engines in one process keep
    per-replica truths for the router (docs/OBSERVABILITY.md "Router").
    ``role=`` ("both" | "prefill" | "decode") enables disaggregated
    serving: a ``prefill`` replica answers ``{"phase": "prefill"}``
    requests and ships matched/computed KV pages to the ``handoff_to``
    decode replica over ``/kv_offer`` + ``/kv_adopt`` (int8 on the wire
    by default; ``handoff_wire="raw"`` for engine-dtype bytes) — see
    docs/RESILIENCE.md "Disaggregated serving".
    See docs/OBSERVABILITY.md.
    """
    from deepspeed_tpu.monitor.metrics import get_registry
    from deepspeed_tpu.profiling.trace import phase

    reg = kwargs.get("registry")
    if reg is None:
        reg = get_registry()
    if kwargs.get("metrics_port") is not None:
        reg.enable()       # before anything is built: set-up is measured too
    with phase("ds_setup_serving", registry=reg):
        return _init_serving(model, config, kwargs)


def _init_serving(model, config, kwargs):
    from deepspeed_tpu.serving.engine import ServingEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig

    params = kwargs.pop("params", None)
    mesh = kwargs.pop("mesh", None)
    metrics_port = kwargs.pop("metrics_port", None)
    request_trace = kwargs.pop("request_trace", False)
    serve_loop = kwargs.pop("serve_loop", False)
    registry = kwargs.pop("registry", None)
    if kwargs.pop("private_health", False):
        from deepspeed_tpu.monitor.health import HealthState

        health = HealthState()
    else:
        health = None
    engine_kw = {k: kwargs.pop(k) for k in
                 ("engine", "num_slots", "prefill_chunk",
                  "decode_block_tokens", "do_sample", "temperature",
                  "top_k", "top_p", "role", "handoff_wire") if k in kwargs}
    if config is not None or kwargs:
        # only materialize a config when one was actually given —
        # ServingEngine rejects engine= combined with config/model args
        config = _merge_inference_config(config, kwargs,
                                         DeepSpeedInferenceConfig)
    serve = ServingEngine(model, config, params=params, mesh=mesh,
                          registry=registry, health=health, **engine_kw)
    if request_trace:
        from deepspeed_tpu.monitor.request_trace import get_request_tracer

        get_request_tracer().enable()
    if serve_loop:
        # before the HTTP server comes up: a /generate racing the loop
        # start must find a live stepper
        serve.start_loop()
    if metrics_port is not None:
        import weakref

        from deepspeed_tpu.monitor.metrics import get_registry
        from deepspeed_tpu.monitor.server import MetricsServer

        reg = registry if registry is not None else get_registry()
        server = MetricsServer(reg, port=int(metrics_port),
                               health=serve.health)
        server.set_generate_handler(serve._http_generate)
        server.set_kv_handoff_handlers(serve._http_kv_offer,
                                       serve._http_kv_adopt)
        server.start()
        serve.metrics_server = server
        # "for the engine's lifetime": a discarded engine must not leak its
        # bound port + exporter thread — engine.close() stops it
        # deterministically, this finalizer catches the GC path
        weakref.finalize(serve, server.stop)
    return serve


def init_telemetry(metrics_port=None, comms: bool = True,
                   flight_recorder: bool = False, flight_capacity: int = 512,
                   flight_dump_dir=None, on_signal: bool = False):
    """Turn on the training-side telemetry stack without a ds_config
    (the ``init_serving(metrics_port=...)`` analog for training loops):

    - enables the process-global metrics registry (``ds_*`` series record);
    - ``comms=True`` enables per-collective accounting (``ds_comm_*``);
    - ``metrics_port=`` additionally serves ``/metrics`` + ``/statz`` on an
      HTTP exporter (``0`` = ephemeral port; read ``server.port``);
    - ``flight_recorder=True`` arms the event ring
      (``monitor/flight_recorder.py``), with a SIGUSR2 dump handler only
      when ``on_signal=True``.

    Returns the started :class:`~deepspeed_tpu.monitor.server.MetricsServer`
    (or None when no port was requested).  Equivalent ds_config blocks:
    ``comms_logger`` and ``flight_recorder`` — see docs/OBSERVABILITY.md.
    """
    from deepspeed_tpu.monitor.comms import comm_metrics
    from deepspeed_tpu.monitor.flight_recorder import get_flight_recorder
    from deepspeed_tpu.monitor.metrics import get_registry

    get_registry().enable()
    if comms:
        comm_metrics.configure(enabled=True)
    if flight_recorder:
        rec = get_flight_recorder().enable(capacity=flight_capacity,
                                           dump_dir=flight_dump_dir)
        if on_signal:
            rec.install_signal_handler()
    if metrics_port is None:
        return None
    from deepspeed_tpu.monitor.server import MetricsServer

    return MetricsServer(get_registry(), port=int(metrics_port)).start()


def init_distributed(dist_backend: str = "xla", **kwargs):
    """Bootstrap multi-host + mesh (reference: ``deepspeed.init_distributed``)."""
    return comm.init_distributed(dist_backend=dist_backend, **kwargs)


def add_config_arguments(parser):
    """Add ``--deepspeed``/``--deepspeed_config`` CLI args (reference parity)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag, parity with reference)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the ds_config JSON file")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help=argparse_suppress())
    group.add_argument("--local_rank", type=int, default=-1,
                       help="Local rank injected by the launcher")
    return parser


def argparse_suppress():
    import argparse

    return argparse.SUPPRESS


# the process's age now is the `import` part of set-up (the gauge
# ds_setup_import_seconds once the registry is enabled)
from deepspeed_tpu.profiling.trace import stamp_import_age as _stamp  # noqa: E402

_stamp()
