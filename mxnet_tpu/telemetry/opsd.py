"""Live ops endpoint: a read-only HTTP daemon over the telemetry plane.

A stdlib ``http.server`` on a daemon thread — the scrape/health surface
a fleet of workers exposes so an operator (or the fleet forensics tool)
can ask a *live* process what it knows, without signals, ptrace, or a
log round-trip. Armed by ``MXNET_OPS_PORT`` at telemetry import, or
explicitly via ``telemetry.serve_ops()``.

Routes (all GET, all read-only):

* ``/metrics`` — Prometheus text exposition of the registry; answers
  OpenMetrics (exemplars included) when the ``Accept`` header asks for
  ``application/openmetrics-text``.
* ``/healthz`` — liveness JSON: fleet identity, dead ranks from the
  live kvstore's heartbeats (``get_dead_nodes()``), circuit-breaker
  states, queue depths, last committed checkpoint seq, training-health
  state, and compiles-since-warmup. ``"ok"`` is false (HTTP 503) when
  any rank is dead, any breaker sits OPEN, or the training-health
  plane reports *diverged*.
* ``/varz`` — process vitals: filtered env, argv, mesh/device summary
  (only if jax is *already* imported — the ops thread never triggers
  the heavy import), memory-plan gauges, telemetry switch state.
* ``/tracez`` — the slowest request span trees from the trace plane.
* ``/trainz`` — the live training-health document (telemetry/health.py):
  arming, ok/degraded/diverged state, recent rule firings, and the
  rolling stat series the detectors chew on.
* ``/fleetz`` — this rank's versioned ``fleet.snapshot()`` (the lossless
  scrape ``tools/fleetstat.py --scrape`` merges across ranks).

Zero interaction with the dispatch path: handlers only *read* the
registry/ring/trace buffers (GIL-consistent snapshots of plain Python
state), never take framework locks, never touch jax.
"""
from __future__ import annotations

import json
import logging
import os
import sys
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from . import fleet as _fleet
from . import health as _health
from . import metrics as _metrics
from . import prometheus as _prometheus
from . import trace as _trace

__all__ = ["serve_ops", "stop_ops", "active", "maybe_serve_from_env",
           "OpsServer"]

log = logging.getLogger(__name__)

_OPENMETRICS_CT = "application/openmetrics-text; version=1.0.0; " \
                  "charset=utf-8"
_PROM_CT = "text/plain; version=0.0.4; charset=utf-8"

_BREAKER_STATES = {0: "closed", 1: "half-open", 2: "open"}

_ENV_PREFIXES = ("MXNET_", "JAX_", "XLA_", "DMLC_", "PS_", "TPU_")

_server = None
_lock = threading.Lock()


# ------------------------------------------------------------- payloads
def metrics_text(accept=""):
    """(body, content_type) for /metrics with OpenMetrics negotiation."""
    if "application/openmetrics-text" in (accept or ""):
        return _prometheus.render(openmetrics=True), _OPENMETRICS_CT
    return _prometheus.render(), _PROM_CT


def healthz():
    """The /healthz JSON document (also callable in-process)."""
    doc = {"rank": _fleet.rank(), "host": _fleet.host(),
           "pid": os.getpid(), "num_workers": _fleet.num_workers(),
           "generation": _fleet.generation()}
    kv = _fleet.kvstore()
    if kv is not None:
        kvdoc = {"attached": True}
        try:
            kvdoc["rank"] = kv.rank
            kvdoc["num_workers"] = kv.num_workers
        except Exception as e:
            kvdoc["error"] = repr(e)
        try:
            kvdoc["dead_nodes"] = sorted(kv.get_dead_nodes())
        except Exception as e:
            kvdoc["dead_nodes"] = []
            kvdoc["heartbeat_error"] = repr(e)
        doc["kvstore"] = kvdoc
    else:
        doc["kvstore"] = {"attached": False, "dead_nodes": []}
    breakers, queues, compiles = {}, {}, {}
    last_seq = None
    for m in _metrics.all_metrics():
        if not isinstance(m, _metrics.Gauge):
            continue
        if m.name.endswith(".state") and "breaker" in m.name:
            state = int(m.value)
            breakers[m.key] = {
                "state": state,
                "name": _BREAKER_STATES.get(state, str(state))}
        elif m.name.endswith("queue.depth"):
            queues[m.key] = m.value
        elif m.name == "serve.program_cache.compiles_since_warmup":
            compiles[m.key] = m.value
        elif m.name == "ckpt.last_seq":
            last_seq = m.value
    doc["breakers"] = breakers
    doc["queues"] = queues
    doc["compiles_since_warmup"] = compiles
    doc["last_ckpt_seq"] = last_seq
    health_state = _health.state()
    doc["train_health"] = {
        "state": health_state,
        "name": _health.STATE_NAMES.get(health_state, str(health_state)),
        "rules": sorted({f["rule"] for f in _health.status()["rules"]})}
    doc["ok"] = (not doc["kvstore"]["dead_nodes"] and
                 not any(b["state"] == 2 for b in breakers.values()) and
                 health_state < 2)
    return doc


def varz():
    """The /varz JSON document: env + mesh + plan summary."""
    from . import core as _core
    doc = {"pid": os.getpid(), "argv": list(sys.argv),
           "rank": _fleet.rank(), "host": _fleet.host(),
           "env": {k: v for k, v in sorted(os.environ.items())
                   if k.startswith(_ENV_PREFIXES)},
           "telemetry": {"enabled": _core.enabled(),
                         "spans": len(_core.get_spans()),
                         "events": len(_core.get_events())}}
    jax = sys.modules.get("jax")     # never *import* jax from here
    if jax is not None:
        try:
            doc["mesh"] = {
                "backend": jax.default_backend(),
                "process_index": jax.process_index(),
                "process_count": jax.process_count(),
                "local_devices": [
                    {"id": d.id, "platform": d.platform,
                     "device_kind": d.device_kind}
                    for d in jax.local_devices()]}
        except Exception as e:
            doc["mesh"] = {"error": repr(e)}
    else:
        doc["mesh"] = {"backend": None}
    plan = {}
    for m in _metrics.all_metrics():
        if isinstance(m, _metrics.Gauge) and m.name.startswith("memplan."):
            plan[m.key] = m.value
    doc["plan"] = plan
    return doc


def tracez(top=10):
    """The /tracez JSON document: slowest request trees, deepest first."""
    root_recs = sorted(_trace.roots(), key=lambda r: -r.get("dur_us", 0))
    trees = []
    for rec in root_recs[:top]:
        t = _trace.tree(rec["trace"])
        if t is not None:
            trees.append(t)
    return {"slowest": trees, "traces_buffered": len(_trace.trace_ids())}


# --------------------------------------------------------------- server
class _Handler(BaseHTTPRequestHandler):
    server_version = "mxnet-opsd/1"

    def log_message(self, fmt, *args):   # keep the training log clean
        log.debug("opsd: " + fmt, *args)

    def _send(self, body, content_type, status=200):
        data = body.encode() if isinstance(body, str) else body
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def _send_json(self, doc, status=200):
        self._send(json.dumps(doc, indent=2, sort_keys=True, default=str),
                   "application/json", status)

    def do_GET(self):
        path = self.path.split("?", 1)[0].rstrip("/") or "/"
        try:
            if path == "/metrics":
                body, ct = metrics_text(self.headers.get("Accept", ""))
                self._send(body, ct)
            elif path == "/healthz":
                doc = healthz()
                self._send_json(doc, status=200 if doc["ok"] else 503)
            elif path == "/varz":
                self._send_json(varz())
            elif path == "/tracez":
                self._send_json(tracez())
            elif path == "/trainz":
                self._send_json(_health.status())
            elif path == "/fleetz":
                self._send_json(_fleet.snapshot())
            elif path == "/":
                self._send_json({"routes": ["/metrics", "/healthz",
                                            "/varz", "/tracez",
                                            "/trainz", "/fleetz"]})
            else:
                self._send_json({"error": f"no route {path}"}, status=404)
        except BrokenPipeError:
            pass
        except Exception as e:       # a broken handler must never kill
            try:                     # the scrape surface
                self._send_json({"error": repr(e)}, status=500)
            except Exception:
                pass


class OpsServer:
    """A running ops endpoint: ``.host``/``.port``/``.url`` + ``close()``."""

    def __init__(self, host, port):
        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self.host, self.port = self._httpd.server_address[:2]
        self.url = f"http://{self.host}:{self.port}"
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="mxnet-opsd",
            daemon=True)
        self._thread.start()

    def close(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)


def serve_ops(port=None, host="127.0.0.1"):
    """Start (or return the already-running) ops endpoint.

    ``port`` defaults to ``MXNET_OPS_PORT`` (0 = ephemeral — read the
    bound port back from ``.port``). The server is a daemon thread: it
    never blocks interpreter exit.
    """
    global _server
    with _lock:
        if _server is not None:
            return _server
        if port is None:
            try:
                port = int(os.environ.get("MXNET_OPS_PORT", "0") or 0)
            except ValueError:
                port = 0
        _server = OpsServer(host, int(port))
        log.info("ops endpoint listening on %s", _server.url)
        return _server


def stop_ops():
    """Shut the endpoint down (tests; production lets the daemon die
    with the process)."""
    global _server
    with _lock:
        srv, _server = _server, None
    if srv is not None:
        srv.close()


def active():
    """The running OpsServer, or None."""
    return _server


def maybe_serve_from_env():
    """Arm the endpoint iff MXNET_OPS_PORT is set (telemetry import
    calls this; a malformed value is ignored rather than fatal)."""
    port = os.environ.get("MXNET_OPS_PORT")
    if not port:
        return None
    try:
        int(port)
    except ValueError:
        log.warning("MXNET_OPS_PORT=%r is not a port; ops endpoint "
                    "not started", port)
        return None
    try:
        return serve_ops()
    except OSError as e:
        log.warning("ops endpoint failed to bind (%s); continuing "
                    "without", e)
        return None
