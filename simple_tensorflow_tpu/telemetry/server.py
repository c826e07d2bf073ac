"""The telemetry HTTP server: a network-visible window onto a running
stf process (ref: the /monitoring and /varz surfaces of TF-Serving's
model server — tensorflow_serving/model_servers/http_server.cc — and
borg-style statusz pages).

Stdlib-only (``http.server``), one listener thread
(``stf_telemetry_http``) + one short-lived ``stf_telemetry_conn``
thread per request; started via ``stf.telemetry.start(port=...)`` or
``ConfigProto(telemetry_port=...)``. Endpoints:

- ``/metrics``  — Prometheus text exposition of the whole
  ``stf.monitoring`` registry (scrape this).
- ``/healthz``  — READINESS by default: 200 ``{"ready": true}`` once at
  least one live Session (or loaded servable) exists, 503
  ``{"ready": false}`` before that — what a fleet front-end probes
  before routing traffic. ``?live=1`` keeps the old liveness contract
  (200 whenever the process serves HTTP).
- ``/statusz``  — process/build/uptime, loaded serving models (per-model
  signature rows), live sessions + plan-cache summary, device summary.
- ``/memz``     — device-memory ledger: per-class/per-owner live bytes,
  top allocations, high watermark, bytes-over-time history
  (``?reconcile=1`` additionally diffs against ``jax.live_arrays()``).
- ``/tracez``   — recent telemetry spans; ``?trace_id=`` filters to one
  request's linked spans, ``&format=chrome`` renders a chrome trace.
- ``/flightz``  — flight-recorder JSONL dump (``?stacks=0`` omits the
  per-thread stack records).
- ``/trainz``   — training numerics-health plane (stf.debug.numerics):
  resolved mode, watched taps, per-step health history (grad/update
  norms, nonfinite tap counts), and the last-anomaly report with
  first-bad-op forensics when the bisector ran (docs/DEBUG.md).
- ``/syncz``    — runtime concurrency plane (stf.analysis.concurrency):
  named-lock registry with ranks, lock-order witness edges, potential
  deadlocks (cycles with both acquisition sites), rank violations,
  per-thread held locks, and the live wait-for graph.

The server binds 127.0.0.1 by default: metrics surfaces are internal,
exposure beyond localhost is a deployment decision (front it with your
mesh/sidecar), not a library default.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional
from urllib.parse import parse_qs, urlparse

from ..platform import monitoring
from ..platform import sync as _sync
from ..platform import tf_logging as logging
from ..version import __version__
from . import recorder as _recorder_mod
from . import tracing as _tracing_mod

_metric_scrapes = monitoring.Counter(
    "/stf/telemetry/http_requests",
    "Telemetry-server HTTP requests served, by endpoint", "endpoint")
_metric_scrape_seconds = monitoring.Sampler(
    "/stf/telemetry/http_seconds",
    monitoring.ExponentialBuckets(1e-5, 4.0, 12),
    "Telemetry-server request handling seconds", "endpoint")

_PROCESS_START_S = time.time()


def _ready() -> bool:
    """Readiness: at least one live (unclosed) Session, or a
    ModelServer with at least one loaded servable. sys.modules checks —
    a probe must never be what first drags jax or serving into the
    process."""
    sess_mod = sys.modules.get("simple_tensorflow_tpu.client.session")
    if sess_mod is not None:
        for s in list(getattr(sess_mod, "live_sessions", ())):
            if not getattr(s, "_closed", True):
                return True
    serving_mod = sys.modules.get("simple_tensorflow_tpu.serving.server")
    if serving_mod is not None:
        for srv in list(getattr(serving_mod, "live_servers", ())):
            try:
                if not srv.closed and srv.model_names:
                    return True
            except Exception:  # noqa: BLE001 — racing close()
                continue
    return False


def _memz_info(reconcile: bool = False, top: int = 20) -> Dict[str, Any]:
    """The /memz payload: ledger breakdown + history; docs/
    OBSERVABILITY.md "Device memory"."""
    from . import memory as _memory_mod

    led = _memory_mod.get_ledger()
    info = led.snapshot(top=top)
    hist = led.history()
    # history is (perf_counter, bytes); export as relative seconds so
    # the payload is self-contained
    now = time.perf_counter()
    info["history"] = [[round(t - now, 3), b] for t, b in hist[-512:]]
    sess_mod = sys.modules.get("simple_tensorflow_tpu.client.session")
    if sess_mod is not None:
        budgets = []
        for s in list(getattr(sess_mod, "live_sessions", ())):
            b = getattr(s, "_memory_budget", None)
            if b:
                budgets.append(int(b))
        if budgets:
            info["session_budgets_bytes"] = sorted(budgets)
    if reconcile:
        try:
            info["reconcile"] = _memory_mod.reconcile()
        except Exception as e:  # noqa: BLE001 — memz is best-effort
            info["reconcile"] = {"error": str(e)}
    return info


def _trainz_info() -> Dict[str, Any]:
    """The /trainz payload. sys.modules-guarded like /statusz: a scrape
    must never be what first imports the numerics plane — before any
    Session instruments a plan, /trainz reports the env-derived mode
    and an empty history."""
    num_mod = sys.modules.get("simple_tensorflow_tpu.debug.numerics")
    if num_mod is not None:
        return num_mod.trainz_info()
    env = os.environ.get("STF_NUMERICS", "").strip().lower()
    return {
        "mode": env if env in ("off", "metrics", "raise", "dump")
        else "off",
        "steps_observed": 0, "anomalies": 0, "taps": [],
        "history": [], "last_anomaly": None,
    }


def _statusz_info() -> Dict[str, Any]:
    """The /statusz payload. Only reports on subsystems the process has
    actually imported (sys.modules checks — a metrics scrape must never
    be what first drags jax or serving into the process)."""
    info: Dict[str, Any] = {
        "process": {
            "pid": os.getpid(),
            "argv": sys.argv,
            "start_time_unix": _PROCESS_START_S,
            "uptime_s": round(time.time() - _PROCESS_START_S, 3),
            "python": sys.version.split()[0],
            "stf_version": __version__,
        },
        "flight_recorder": _recorder_mod.get_recorder().stats(),
    }
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            devs = jax.devices()
            kinds: Dict[str, int] = {}
            for d in devs:
                k = f"{d.platform}:{getattr(d, 'device_kind', '')}"
                kinds[k] = kinds.get(k, 0) + 1
            info["devices"] = {"count": len(devs), "by_kind": kinds,
                               "jax_version": jax.__version__}
        except Exception as e:  # noqa: BLE001 — statusz is best-effort
            info["devices"] = {"error": str(e)}
    sess_mod = sys.modules.get("simple_tensorflow_tpu.client.session")
    if sess_mod is not None:
        sessions = []
        for s in list(getattr(sess_mod, "live_sessions", ())):
            try:
                steps = list(s._cache.values())
                sessions.append({
                    "closed": s._closed,
                    "graph_ops": len(s._graph.get_operations()),
                    "plan_cache": {
                        "plans": len(steps),
                        "total_calls": sum(st.n_calls for st in steps),
                        "aot_buckets": sum(len(st.aot_cache)
                                           for st in steps),
                    },
                    "variables": len(s._variable_store.values),
                })
            except Exception:  # noqa: BLE001 — racing close()
                continue
        info["sessions"] = sessions
    serving_mod = sys.modules.get("simple_tensorflow_tpu.serving.server")
    if serving_mod is not None:
        models = []
        for srv in list(getattr(serving_mod, "live_servers", ())):
            try:
                models.extend(srv.statusz_info())
            except Exception:  # noqa: BLE001 — racing close()
                continue
        info["serving"] = {"models": models}
    kernels_mod = sys.modules.get(
        "simple_tensorflow_tpu.kernels.registry")
    if kernels_mod is not None:
        try:
            # kernel tier (stf.kernels): mode, per-op routed/fallback
            # counters (docs/PERFORMANCE.md)
            info["kernels"] = kernels_mod.snapshot()
        except Exception as e:  # noqa: BLE001 — statusz is best-effort
            info["kernels"] = {"error": str(e)}
    watchdog_mod = sys.modules.get(
        "simple_tensorflow_tpu.telemetry.watchdog")
    if watchdog_mod is not None:
        wd = watchdog_mod.get_watchdog()
        info["watchdog"] = {"armed": wd.armed_count(),
                            "wedges_detected": wd.wedges_detected}
    from . import memory as _memory_mod

    led = _memory_mod.get_ledger()
    info["memory"] = {"total_bytes": led.total_bytes(),
                      "high_watermark_bytes": led.high_watermark(),
                      "by_class_owner": led.breakdown()}
    return info


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    server_version = "stf-telemetry"

    def log_message(self, fmt, *args):  # stdlib default spams stderr
        pass

    def _reply(self, body: str, content_type: str, code: int = 200):
        payload = body.encode("utf-8")
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        self.wfile.write(payload)

    def do_GET(self):  # noqa: N802 — http.server API
        url = urlparse(self.path)
        endpoint = url.path.rstrip("/") or "/"
        q = parse_qs(url.query)
        t0 = time.perf_counter()
        try:
            if endpoint == "/metrics":
                self._reply(monitoring.to_prometheus(),
                            "text/plain; version=0.0.4; charset=utf-8")
            elif endpoint == "/healthz":
                live_only = (q.get("live") or ["0"])[0] not in ("0", "")
                ready = True if live_only else _ready()
                self._reply(json.dumps({
                    "status": "ok" if ready else "unavailable",
                    "ready": ready, "pid": os.getpid(),
                    "uptime_s": round(time.time() - _PROCESS_START_S, 3),
                }), "application/json",
                    code=200 if ready else 503)
            elif endpoint == "/statusz":
                self._reply(json.dumps(_statusz_info(), default=str,
                                       indent=2), "application/json")
            elif endpoint == "/memz":
                reconcile = (q.get("reconcile") or ["0"])[0] \
                    not in ("0", "")
                top = int((q.get("top") or ["20"])[0])
                self._reply(json.dumps(
                    _memz_info(reconcile=reconcile, top=top),
                    default=str, indent=2), "application/json")
            elif endpoint == "/tracez":
                trace_id = (q.get("trace_id") or [None])[0]
                if (q.get("format") or [""])[0] == "chrome":
                    self._reply(_tracing_mod.chrome_trace(trace_id),
                                "application/json")
                else:
                    limit = int((q.get("limit") or ["0"])[0]) or None
                    self._reply(json.dumps({
                        "spans": _tracing_mod.recent_spans(
                            n=limit, trace_id=trace_id)}, default=str),
                        "application/json")
            elif endpoint == "/trainz":
                self._reply(json.dumps(_trainz_info(), default=str,
                                       indent=2), "application/json")
            elif endpoint == "/syncz":
                from ..platform import sync as _sync_mod

                info = _sync_mod.witness_snapshot()
                info["held"] = _sync_mod.all_held_locks()
                info["wait_graph"] = _sync_mod.wait_graph()
                self._reply(json.dumps(info, default=str, indent=2),
                            "application/json")
            elif endpoint == "/flightz":
                stacks = (q.get("stacks") or ["1"])[0] != "0"
                self._reply(
                    _recorder_mod.get_recorder().dump_jsonl(
                        stacks=stacks, reason="flightz"),
                    "application/x-ndjson")
            elif endpoint == "/":
                self._reply(
                    "<html><body><h1>stf telemetry</h1><ul>"
                    + "".join(f'<li><a href="{p}">{p}</a></li>'
                              for p in ("/metrics", "/healthz", "/statusz",
                                        "/memz", "/tracez", "/flightz",
                                        "/trainz", "/syncz"))
                    + "</ul></body></html>", "text/html")
            else:
                self._reply(f"no such endpoint: {endpoint}\n",
                            "text/plain", code=404)
                endpoint = "(404)"
        except BrokenPipeError:
            return
        except Exception as e:  # noqa: BLE001 — a bad page must 500, not die
            try:
                self._reply(f"internal error: {e}\n", "text/plain",
                            code=500)
            except Exception:  # noqa: BLE001
                return
            endpoint = "(500)"
        _metric_scrapes.get_cell(endpoint).increase_by(1)
        _metric_scrape_seconds.get_cell(endpoint).add(
            time.perf_counter() - t0)


class _HTTPServer(ThreadingHTTPServer):
    daemon_threads = True
    # reuse the listening port across fast restart cycles (tests)
    allow_reuse_address = True

    def process_request(self, request, client_address):
        # ThreadingMixIn.process_request, with the connection threads
        # NAMED so the conftest leak fixture can see them
        t = threading.Thread(
            target=self.process_request_thread,
            args=(request, client_address),
            name="stf_telemetry_conn", daemon=True)
        t.start()


class TelemetryServer:
    """One running telemetry HTTP server (module-level singleton via
    ``start()``/``stop()``)."""

    def __init__(self, port: int = 0, address: str = "127.0.0.1"):
        self._httpd = _HTTPServer((address, port), _Handler)
        self.address = address
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            kwargs={"poll_interval": 0.1},
            name="stf_telemetry_http", daemon=True)
        self._thread.start()
        self._closed = False
        _recorder_mod.get_recorder().record(
            "telemetry_server", action="start", port=self.port)
        logging.info("telemetry: serving /metrics /healthz /statusz "
                     "/memz /tracez /flightz /trainz /syncz on "
                     "http://%s:%d", address, self.port)

    @property
    def url(self) -> str:
        return f"http://{self.address}:{self.port}"

    @property
    def closed(self) -> bool:
        return self._closed

    def stop(self, timeout: float = 5.0):
        if self._closed:
            return
        self._closed = True
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread.is_alive() and \
                self._thread is not threading.current_thread():
            _recorder_mod.checked_join(self._thread, timeout,
                                       "TelemetryServer.stop")

    def __repr__(self):
        state = "closed" if self._closed else "serving"
        return f"<TelemetryServer {self.url} {state}>"


_server_lock = _sync.Lock("telemetry/server",
                          rank=_sync.RANK_LIFECYCLE)
_server: Optional[TelemetryServer] = None


def start(port: int = 0, address: str = "127.0.0.1") -> TelemetryServer:
    """Start the process's telemetry server (idempotent: a second
    ``start`` returns the running server — one process, one telemetry
    plane; asking for a DIFFERENT fixed port while one runs raises).
    ``port=0`` binds an ephemeral port (see ``server.port``). Also
    installs the SIGTERM flight-recorder dump handler when called from
    the main thread."""
    global _server
    with _server_lock:
        if _server is not None and not _server.closed:
            if port not in (0, _server.port):
                raise RuntimeError(
                    f"telemetry server already running on port "
                    f"{_server.port}; stop() it before binding "
                    f"port {port}")
            return _server
        _server = TelemetryServer(port=port, address=address)
    _recorder_mod.install_signal_handlers()
    return _server


def get_server() -> Optional[TelemetryServer]:
    """The running server, or None."""
    with _server_lock:
        return _server if _server is not None and not _server.closed \
            else None


def stop(timeout: float = 5.0) -> None:
    """Stop the process's telemetry server (no-op when none runs)."""
    global _server
    with _server_lock:
        srv, _server = _server, None
    if srv is not None:
        srv.stop(timeout)
