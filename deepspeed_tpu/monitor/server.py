"""Stdlib HTTP exporter for the metrics registry.

``MetricsServer(registry, port=0).start()`` serves two endpoints on a
daemon thread:

- ``GET /metrics`` — Prometheus text exposition (scrape target);
- ``GET /statz``  — the same registry as a JSON snapshot (humans, tests,
  and ``tools/metrics_dump.py``);
- ``GET /statz?window=<key>`` — rate-windowed deltas: each distinct
  ``window`` key remembers the snapshot of its previous scrape, and a
  request returns counter/histogram deltas (plus per-second rates) over
  the *actual* elapsed time since then — long-lived serving gets rates
  without a Prometheus server.  The first scrape of a key primes it
  (``"primed": true``, no deltas); scrape again after your window.
- ``GET /profilez?steps=N`` — on-demand device-true profile: parks a
  capture request on the process-global profile broker
  (profiling/device_trace.py); the next live engine step boundary claims
  it, captures N steps (training steps or serving scheduler iterations)
  with the perfetto export on, runs the post-processor, and the response
  is the JSON phase summary (the same numbers land in the ``ds_profile_*``
  registry series).  ``timeout=S`` bounds the wait (default 60s; 504 when
  nothing is stepping, 409 when a capture is already in flight, 501 on
  jax builds without the perfetto export).
- ``GET /healthz`` — READINESS (not liveness): 200 ``{"ready": true}``
  while the process accepts new work, 503 with a ``reason`` while it does
  not (``ServingEngine.drain()`` flips it for the whole drain window) —
  the router/load-balancer stop-sending signal (monitor/health.py).  A
  server built with ``health=`` serves that state instead of the
  process-global one (N replicas in one process each keep their own
  drain truth).
- ``POST /generate`` — replica inference endpoint (the router's dispatch
  target, ``serving/router.py``): available when a serving engine is
  attached (``init_serving(metrics_port=...)`` wires its handler); the
  JSON body ``{"prompt": [ids], "max_new_tokens", "eos_token_id"?,
  "timeout"?}`` blocks this worker thread until the request finishes and
  returns its tokens; 503 while the engine drains (the router re-sends
  elsewhere — no request is dropped on a drain).  With ``"stream":
  true`` the response is chunked ndjson — one JSON event per line as
  token blocks drain, then a terminal ``done``/``error`` event.
- ``POST /kv_offer`` / ``POST /kv_adopt`` — the disaggregated-serving
  KV-page handoff pair (decode-capable replicas): offer answers which
  page chunks this replica lacks; adopt writes the shipped pages and
  pins them into the local prefix cache (serving/handoff.py).
- ``GET /goodputz`` — run-level goodput ledger snapshot
  (monitor/goodput.py): telescoping wall-clock attribution over the
  closed category set plus the goodput ratio; ``{"enabled": false}``
  when no ledger is enabled in this process.
- ``GET /requestz`` — per-request span timelines from the request tracer
  (monitor/request_trace.py): recent completions, slowest exemplars, and
  the tail-attribution summary.  ``?n=`` bounds the lists;
  ``?format=perfetto`` returns trace-event JSON keyed to the clock anchor
  of the most recent profiler capture, so it loads in ONE Perfetto
  session next to a ``/profilez`` capture with aligned timestamps.

``port=0`` binds an ephemeral port (read it back from ``server.port``) —
the shape tests and multi-engine hosts need.  Zero dependencies: plain
``http.server`` over the registry's lock-free snapshot reads, so a scrape
never blocks the serving loop.
"""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs

from deepspeed_tpu.monitor.metrics import (MetricsRegistry, get_registry,
                                           window_delta)
from deepspeed_tpu.utils.logging import logger

__all__ = ["MetricsServer"]


class _Handler(BaseHTTPRequestHandler):
    registry: MetricsRegistry  # set by the server subclass

    def do_GET(self):  # noqa: N802 - http.server API
        path, _, query = self.path.partition("?")
        if path == "/metrics":
            body = self.registry.prometheus_text().encode()
            ctype = "text/plain; version=0.0.4; charset=utf-8"
        elif path in ("/statz", "/statz/"):
            qs = parse_qs(query)
            window = qs.get("window", [None])[0]
            if window is not None:
                body = json.dumps(self._windowed(window),
                                  sort_keys=True).encode()
            elif "kinds" in qs:
                # instrument kinds alongside the snapshot: fleet
                # aggregation (tools/fleet_dump.py) must know whether to
                # SUM a scalar (counter) or min/max/mean it (gauge) —
                # the plain snapshot erases that.  Both maps derive from
                # ONE typed_snapshot so a metric registered mid-scrape
                # can't appear in metrics but not kinds.
                kinds: dict = {}
                metrics: dict = {}
                for (name, ls), (kind, value) in \
                        self.registry.typed_snapshot().items():
                    kinds[name] = kind
                    if ls:
                        metrics.setdefault(name, {})[ls] = value
                    else:
                        metrics[name] = value
                body = json.dumps(
                    {"enabled": self.registry.enabled,
                     "metrics": metrics,
                     "kinds": kinds}, sort_keys=True).encode()
            else:
                body = self.registry.statz_json().encode()
            ctype = "application/json"
        elif path in ("/requestz", "/requestz/"):
            from deepspeed_tpu.monitor.request_trace import (
                get_request_tracer, get_step_timeline)

            qs = parse_qs(query)
            # ?kind=train serves the training step timeline through the
            # same endpoint/format contract (one scrape surface for
            # fleet_dump --trace, whether the process serves or trains)
            tracer = (get_step_timeline()
                      if qs.get("kind", [""])[0] == "train"
                      else get_request_tracer())
            if qs.get("format", [""])[0] == "perfetto":
                body = json.dumps(tracer.perfetto_trace()).encode()
            else:
                try:
                    limit = int(qs.get("n", ["32"])[0])
                except ValueError:
                    self.send_error(400, "n must be an integer")
                    return
                body = json.dumps(tracer.snapshot(limit),
                                  sort_keys=True).encode()
            ctype = "application/json"
        elif path in ("/profilez/history", "/profilez/history/"):
            # latest continuous-profiler windows of every live engine in
            # this process (docs/OBSERVABILITY.md "Continuous profiling").
            # Serves {"engines": [], "windows": []} when no profiler is
            # armed — a cheap fleet scrape, never a capture trigger.
            from deepspeed_tpu.profiling.continuous import history_snapshot

            qs = parse_qs(query)
            try:
                limit = int(qs.get("n", ["8"])[0])
            except ValueError:
                self.send_error(400, "n must be an integer")
                return
            body = json.dumps(history_snapshot(limit),
                              sort_keys=True).encode()
            ctype = "application/json"
        elif path in ("/profilez", "/profilez/"):
            code, payload = self._profilez(parse_qs(query))
            body = json.dumps(payload, sort_keys=True).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        elif path in ("/goodputz", "/goodputz/"):
            # run-level goodput ledger snapshot (monitor/goodput.py):
            # telescoping wall-clock attribution for the live process —
            # {"enabled": false} when no ledger is enabled, else the
            # category breakdown + goodput_ratio (docs/OBSERVABILITY.md
            # "Goodput ledger").
            from deepspeed_tpu.monitor.goodput import get_goodput_ledger

            body = json.dumps(get_goodput_ledger().snapshot(),
                              sort_keys=True).encode()
            ctype = "application/json"
        elif path in ("/healthz", "/healthz/"):
            # READINESS, not liveness: 503 while draining (or any other
            # not-ready reason) is the router's stop-sending signal —
            # liveness is this server answering at all.  A server-scoped
            # HealthState (multi-replica hosts) wins over the global one.
            health = getattr(self.server, "health", None)
            if health is None:
                from deepspeed_tpu.monitor.health import get_health

                health = get_health()
            snap = health.snapshot()
            body = json.dumps(snap, sort_keys=True).encode()
            self.send_response(200 if snap["ready"] else 503)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        elif path == "/":
            body = json.dumps({"endpoints": ["/goodputz", "/healthz",
                                             "/metrics", "/statz",
                                             "/profilez",
                                             "/profilez/history",
                                             "/requestz",
                                             "/generate", "/kv_offer",
                                             "/kv_adopt"]}
                              ).encode()
            ctype = "application/json"
        else:
            self.send_error(404)
            return
        self.send_response(200)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    # POST endpoints and the server attribute holding each one's handler
    # (/kv_offer and /kv_adopt are the disaggregated-serving page-handoff
    # pair — wired only on decode-capable replicas by init_serving)
    POST_ROUTES = {"/generate": "generate_handler",
                   "/kv_offer": "kv_offer_handler",
                   "/kv_adopt": "kv_adopt_handler"}

    def do_POST(self):  # noqa: N802 - http.server API
        path, _, _ = self.path.partition("?")
        attr = self.POST_ROUTES.get(path.rstrip("/") or path)
        if attr is None:
            self.send_error(404)
            return
        handler = getattr(self.server, attr, None)
        if handler is None:
            code, payload = 503, {"error": "no serving engine attached "
                                           "to this metrics server"}
        else:
            try:
                length = int(self.headers.get("Content-Length") or 0)
                payload = json.loads(self.rfile.read(length) or b"{}")
                if not isinstance(payload, dict):
                    raise ValueError("body must be a JSON object")
            except (ValueError, json.JSONDecodeError) as exc:
                code, payload = 400, {"error": f"bad JSON body: {exc}"}
            else:
                # distributed-trace propagation: the router stamps a
                # traceparent HEADER on its re-POST; surface it to the
                # engine handler as a payload field (an explicit payload
                # traceparent wins — it is the more deliberate signal)
                tp = self.headers.get("traceparent")
                if tp and "traceparent" not in payload:
                    payload["traceparent"] = tp
                # blocks this worker thread until the request completes
                # (ThreadingHTTPServer: scrapes stay responsive)
                code, payload = handler(payload)
        if not isinstance(payload, dict):
            # streaming /generate: the handler returned an EVENT ITERATOR
            # instead of a body — relay it as chunked ndjson, one JSON
            # object per line, flushed per event so TTFT is wire-visible
            self._stream_events(code, payload)
            return
        body = json.dumps(payload, sort_keys=True).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        if code == 429 and isinstance(payload, dict) \
                and payload.get("retry_after_s") is not None:
            # the overload-shed contract (scheduler.QueueFull -> 429):
            # well-behaved clients honor the standard header; the body
            # carries the same value for the router's JSON path
            self.send_header("Retry-After",
                             str(max(1, int(payload["retry_after_s"]))))
        self.end_headers()
        self.wfile.write(body)

    def _stream_events(self, code: int, events) -> None:
        """Chunked-transfer ndjson relay for streaming /generate: each
        event is one JSON line in one HTTP chunk.  A client that hangs
        up mid-stream closes the generator (its engine-side request
        keeps running — an idempotent retry can resume and replay the
        unsent suffix); the generator itself signals failures in-band
        with a terminal ``error`` event."""
        self.send_response(code)
        self.send_header("Content-Type", "application/x-ndjson")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        try:
            for event in events:
                data = json.dumps(event, sort_keys=True).encode() + b"\n"
                self.wfile.write(b"%x\r\n" % len(data) + data + b"\r\n")
                self.wfile.flush()
            self.wfile.write(b"0\r\n\r\n")
        except (BrokenPipeError, ConnectionResetError, OSError):
            pass                 # client went away: stop relaying
        finally:
            close = getattr(events, "close", None)
            if close is not None:
                close()

    MAX_WINDOW_KEYS = 64

    def _windowed(self, key: str) -> dict:
        """Delta snapshot vs the previous scrape of the same ``window``
        key (state lives on the HTTP server object, shared across the
        handler instances it spawns per request).  Each key stores one
        full snapshot, and the key space is CLIENT-supplied — cap it and
        evict the least-recently-scraped key so a scraper that appends a
        timestamp (or a hostile client) cannot grow memory unboundedly."""
        now = time.monotonic()
        snap = self.registry.typed_snapshot()
        srv = self.server
        with srv.window_lock:
            prev = srv.window_state.get(key)
            srv.window_state[key] = (now, snap)
            while len(srv.window_state) > self.MAX_WINDOW_KEYS:
                oldest = min(srv.window_state,
                             key=lambda k: srv.window_state[k][0])
                del srv.window_state[oldest]
        if prev is None:
            return {"window": key, "primed": True, "window_s": 0.0,
                    "metrics": {}}
        dt = now - prev[0]
        return {"window": key, "primed": False,
                "window_s": round(dt, 6),
                "metrics": window_delta(prev[1], snap, dt)}

    def _profilez(self, qs: dict):
        """``/profilez?steps=N[&timeout=S]``: park a capture request on
        the profile broker and block this HTTP worker (ThreadingHTTPServer
        — the scrape endpoints stay responsive) until a live engine
        fulfills it.  Returns (status_code, json_payload)."""
        from deepspeed_tpu.profiling.device_trace import get_profile_broker

        try:
            steps = int(qs.get("steps", ["2"])[0])
            timeout = float(qs.get("timeout", ["60"])[0])
        except ValueError:
            return 400, {"error": "steps/timeout must be numeric"}
        broker = get_profile_broker()
        try:
            req = broker.submit(steps)
        except RuntimeError as exc:
            return 409, {"error": str(exc)}
        try:
            return 200, req.wait(timeout)
        except TimeoutError as exc:
            broker.cancel(req)
            return 504, {"error": str(exc)}
        except RuntimeError as exc:
            return 500, {"error": str(exc)}

    def log_message(self, fmt, *args):  # scrapes are not log lines
        pass


class MetricsServer:
    """Serve ``/metrics`` + ``/statz`` for a registry on a daemon thread."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 port: int = 0, host: str = "127.0.0.1", health=None):
        self.registry = registry if registry is not None else get_registry()
        self._requested_port = port
        self.host = host
        # replica-scoped readiness (None = the process-global HealthState)
        self.health = health
        self._generate_handler = None
        self._kv_offer_handler = None
        self._kv_adopt_handler = None
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        """The BOUND port (differs from the requested one when port=0)."""
        return self._httpd.server_address[1] if self._httpd else \
            self._requested_port

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def start(self) -> "MetricsServer":
        if self._httpd is not None:
            return self
        handler = type("Handler", (_Handler,), {"registry": self.registry})
        self._httpd = ThreadingHTTPServer((self.host, self._requested_port),
                                          handler)
        self._httpd.daemon_threads = True
        # per-window-key previous snapshots for /statz?window= deltas
        self._httpd.window_state = {}
        self._httpd.window_lock = threading.Lock()
        self._httpd.health = self.health
        self._httpd.generate_handler = self._generate_handler
        self._httpd.kv_offer_handler = self._kv_offer_handler
        self._httpd.kv_adopt_handler = self._kv_adopt_handler
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name="ds-metrics-http", daemon=True)
        self._thread.start()
        logger.info("metrics server: %s/metrics (Prometheus), %s/statz "
                    "(JSON)", self.url, self.url)
        return self

    def set_generate_handler(self, fn) -> None:
        """Attach the serving engine's ``POST /generate`` handler
        (``fn(payload: dict) -> (status_code, json_payload)``, where the
        payload may be an ndjson event ITERATOR for streaming
        dispatches); None detaches (subsequent POSTs get 503)."""
        self._generate_handler = fn
        if self._httpd is not None:
            self._httpd.generate_handler = fn

    def set_kv_handoff_handlers(self, offer_fn, adopt_fn) -> None:
        """Attach the decode-side KV-page handoff pair (``POST
        /kv_offer`` + ``POST /kv_adopt`` — disaggregated serving); None
        detaches either."""
        self._kv_offer_handler = offer_fn
        self._kv_adopt_handler = adopt_fn
        if self._httpd is not None:
            self._httpd.kv_offer_handler = offer_fn
            self._httpd.kv_adopt_handler = adopt_fn

    def stop(self) -> None:
        if self._httpd is None:
            return
        self._httpd.shutdown()
        self._httpd.server_close()
        self._httpd = None
        self._thread = None
