"""Stdlib-only HTTP endpoint for the live observability plane.

One daemon thread serving three read-only endpoints off the process's
metrics registry (obs/metrics.py):

  /metrics   Prometheus/OpenMetrics text exposition
  /healthz   {"status": "ready"|"overloaded"|"draining", ...} — HTTP
             200 while ready, 503 otherwise.  ``draining`` means the
             process is on its way OUT (a SIGTERM handler flips it so
             load balancers stop routing before exit); ``overloaded``
             means it is alive but SHEDDING load (admission control)
             and will return to ready when the backlog clears
  /statusz   JSON operational snapshot: server info merged with the
             runner-provided ``statusz`` callable (tick, window,
             replica shards, checkpoint age — see obs/runtime.py
             RunObserver.statusz)

The ``statusz`` callable MUST be cheap and sync-free: it is invoked
from the serving thread on every scrape, so it may only read host-side
snapshots that the runner updated at its last window boundary — never
a device leaf.

``port=0`` binds an ephemeral port (the CI smoke's mode); the bound
port is available as ``server.port`` after ``start()`` and is printed/
recorded by the runners so scrapers can find it.
"""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

CONTENT_TYPE_METRICS = "text/plain; version=0.0.4; charset=utf-8"

READY = "ready"
DRAINING = "draining"
OVERLOADED = "overloaded"


class ObsServer:
    def __init__(self, registry=None, *, port: int = 0,
                 host: str = "127.0.0.1", statusz=None):
        if registry is None:
            from oversim_tpu.obs.metrics import REGISTRY as registry
        self.registry = registry
        self.host = host
        self.port = port
        self.statusz_fn = statusz
        self.health = READY
        self._httpd = None
        self._thread = None
        self._t0 = time.monotonic()

    # ------------------------------------------------------ lifecycle --
    def start(self) -> int:
        """Bind + serve on a daemon thread; returns the bound port."""
        obs = self

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):      # no per-scrape stderr spam
                pass

            def _send(self, code, body: bytes, ctype: str):
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                path = self.path.split("?", 1)[0]
                try:
                    if path == "/metrics":
                        body = obs.registry.render().encode()
                        self._send(200, body, CONTENT_TYPE_METRICS)
                    elif path == "/healthz":
                        doc = {"status": obs.health,
                               "uptime_s": round(obs.uptime_s(), 3)}
                        code = 200 if obs.health == READY else 503
                        self._send(code, json.dumps(doc).encode(),
                                   "application/json")
                    elif path == "/statusz":
                        self._send(200, json.dumps(obs.status()).encode(),
                                   "application/json")
                    else:
                        self._send(404, b"not found\n", "text/plain")
                except BrokenPipeError:
                    pass
                except Exception as e:  # noqa: BLE001 — a scrape bug
                    # must never kill the serving thread
                    try:
                        self._send(500, f"error: {e}\n".encode(),
                                   "text/plain")
                    except Exception:  # noqa: BLE001
                        pass

        self._httpd = ThreadingHTTPServer((self.host, self.port), _Handler)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, kwargs={"poll_interval": 0.2},
            name="obs-server", daemon=True)
        self._thread.start()
        return self.port

    def stop(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    # --------------------------------------------------------- status --
    def uptime_s(self) -> float:
        return time.monotonic() - self._t0

    def set_health(self, state: str) -> None:
        if state not in (READY, DRAINING, OVERLOADED):
            raise ValueError(f"unknown health state {state!r}")
        self.health = state

    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def status(self) -> dict:
        doc = {"health": self.health, "port": self.port,
               "uptime_s": round(self.uptime_s(), 3)}
        if self.statusz_fn is not None:
            try:
                doc.update(self.statusz_fn() or {})
            except Exception as e:  # noqa: BLE001 — scrape must not die
                doc["statusz_error"] = str(e)
        return doc
