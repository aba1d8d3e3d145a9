"""Count and time XLA backend compiles from jax.monitoring's own events.

Copied from ``chip_smoke.CompileClock`` (the original stays with the smoke
test; see PERF.md, Open questions).  Tracing and lowering are left out:
their events nest and would count twice.
"""

from __future__ import annotations

import logging

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """While open: ``compiles`` backend compiles (a read of the persistent
    cache in a compile's place counts, it is the same event), ``seconds``
    spent in them, the persistent cache's hits and misses, and ``names``,
    what jax's own log said it compiled (``jax_log_compiles`` is on while
    the clock is open; it costs nothing while nothing compiles)."""

    def __init__(self):
        self.names = []
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.compiles += 1
            self.seconds += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def __enter__(self):
        import jax
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        clock = self

        class Names(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("Compiling"):
                    clock.names.append(msg if len(msg) < 900 else
                                       msg[:200] + " ... " + msg[-700:])

        self._handler = Names(level=logging.DEBUG)
        self._logger = logging.getLogger("jax")
        self._logger.addHandler(self._handler)
        self._log_compiles = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        return self

    def __exit__(self, *exc):
        import jax
        from jax import monitoring

        jax.config.update("jax_log_compiles", self._log_compiles)
        self._logger.removeHandler(self._handler)
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)
