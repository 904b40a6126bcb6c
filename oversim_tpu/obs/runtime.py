"""RunObserver: the one-per-process glue every runner publishes into.

Bundles the plane's pieces — metrics registry, HTTP endpoint, flight
recorder, optional request tracer — behind the two callbacks the
runners already have at their host-sync points:

  * ``on_window(window, summary, wall_s)``   ← service loop / bench
    measurement-window ``on_window`` callbacks (the per-window host
    sync that fetched ``summary`` is the loop's own; the observer only
    reads the already-fetched dict), and
  * ``loop_event(kind, **fields)``           ← ``ServiceLoop(events=)``
    (window dispatched/fetched, checkpoint written) and ad-hoc runner
    events (retry/backoff, chaos kill, AOT hit/miss, contract verdict).

``statusz()`` assembles the ``/statusz`` snapshot — tick, window,
replica shards, checkpoint age — purely from those host-side
updates, so a scrape never touches the device.

Typical runner wiring (scripts/service_run.py)::

    obs = RunObserver(role="service", port=args.metrics_port,
                      flight_path=args.flight)
    obs.set_static(replicas=args.replicas)
    obs.start()                       # → bound port (0 = ephemeral)
    loop = ServiceLoop(..., on_window=..., events=obs.loop_event)
    ...
    obs.draining()                    # SIGTERM: healthz → 503
    obs.close()
"""

from __future__ import annotations

import time

from oversim_tpu.obs import metrics as metrics_mod
from oversim_tpu.obs.flight import FlightRecorder
from oversim_tpu.obs.server import DRAINING, OVERLOADED, READY, ObsServer

# per-window wall cost (dispatch-to-drain), seconds
WINDOW_WALL_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                       5.0, 10.0, 30.0, 60.0, 120.0)


class RunObserver:
    def __init__(self, *, role: str = "service", registry=None,
                 port: int | None = None, host: str = "127.0.0.1",
                 flight_path: str | None = None,
                 flight_capacity: int = 512, tracer=None):
        self.role = role
        self.registry = registry or metrics_mod.get_registry()
        self._port_req = port
        self.host = host
        self.port: int | None = None
        self.server: ObsServer | None = None
        self.flight = FlightRecorder(flight_path, capacity=flight_capacity)
        self.tracer = tracer
        self._static: dict = {"role": role}
        self._last: dict = {}
        self._last_wall_s: float | None = None
        self._last_checkpoint_mono: float | None = None
        r = self.registry
        self.up = r.gauge("oversim_up", "1 while the process serves",
                          labels={"role": role})
        self.up.set(1)
        self.windows = r.counter("oversim_windows_total",
                                 "serving/measurement windows drained")
        self.ticks = r.gauge("oversim_ticks",
                             "simulation ticks at the last drain")
        self.sim_seconds = r.gauge("oversim_sim_seconds",
                                   "simulated seconds at the last drain")
        self.alive = r.gauge("oversim_alive_nodes",
                             "alive overlay nodes at the last drain")
        # awake-set plane (engine/sim.py SPARSE_COUNTERS): cumulative
        # per-tick awake-set sizes and the lanes the node step paid for
        # them — the live view of "tick cost scales with traffic, not
        # N".  Only set when the engine carries the counters (dense
        # runs never touch them).
        self.awake_nodes = r.gauge(
            "oversim_sparse_awake_nodes",
            "cumulative awake nodes summed over ticks (awake-set tick)")
        self.active_dst = r.gauge(
            "oversim_sparse_active_dst",
            "cumulative due-message destinations summed over ticks "
            "(awake-set tick)")
        self.lanes_stepped = r.gauge(
            "oversim_sparse_lanes_stepped",
            "cumulative node-step lanes (rounds x active_cap) summed "
            "over ticks (awake-set tick; the dense sweep pays ticks x N)")
        self.window_wall = r.histogram(
            "oversim_window_wall_seconds",
            "wall seconds per drained window",
            buckets=WINDOW_WALL_BUCKETS)
        self.checkpoints = r.counter("oversim_checkpoints_total",
                                     "checkpoints written")
        self.events = r.counter("oversim_flight_events_total",
                                "flight-recorder events recorded")
        # gateway/ingest RX export (attach_rx_source): the host-side
        # rx_* counters mirrored into the registry as monotone counters
        self._rx_src = None
        self._rx_counters: dict = {}
        self._rx_last: dict = {}

    # ------------------------------------------------------ lifecycle --
    def start(self) -> int | None:
        """Start the HTTP endpoint when a port was requested (0 =
        ephemeral); returns the bound port (None = endpoint off)."""
        if self._port_req is None:
            return None
        self.server = ObsServer(self.registry, port=self._port_req,
                                host=self.host, statusz=self.statusz)
        self.port = self.server.start()
        self.flight.event("obs_start", port=self.port, role=self.role)
        return self.port

    def draining(self) -> None:
        """Flip /healthz ready → draining (503) and log it — call from
        the SIGTERM handler BEFORE the graceful stop begins."""
        if self.server is not None:
            self.server.set_health(DRAINING)
        self.record("draining")

    def overloaded(self, **fields) -> None:
        """Flip /healthz ready → overloaded (503): admission control is
        SHEDDING.  Distinct from draining (the process is staying, load
        balancers should back off, not deregister); a process already
        draining keeps that terminal state."""
        if self.server is None or self.server.health != READY:
            return
        self.server.set_health(OVERLOADED)
        self.record("overloaded", **fields)

    def ready(self, **fields) -> None:
        """Clear an overload: overloaded → ready.  Draining is terminal
        and never cleared from here."""
        if self.server is None or self.server.health != OVERLOADED:
            return
        self.server.set_health(READY)
        self.record("overload_cleared", **fields)

    def close(self, *, dump_tail: bool = False) -> None:
        if dump_tail:
            self.flight.dump_tail()
        self.flight.close()
        if self.server is not None:
            self.server.stop()
            self.server = None

    def describe(self) -> dict:
        """Manifest-ready endpoint description."""
        return {"metrics_port": self.port, "flight": self.flight.path}

    # -------------------------------------------------------- updates --
    def set_static(self, **fields) -> None:
        """Scrape-visible run facts that don't change per window:
        replicas, shards, ..."""
        self._static.update(fields)

    def record(self, kind: str, **fields) -> None:
        """A flight event + the event counter (ad-hoc runner events:
        retry, chaos_kill, aot hit/miss, contract verdict...)."""
        self.events.inc()
        self.flight.event(kind, **fields)

    def loop_event(self, kind: str, **fields) -> None:
        """ServiceLoop ``events=`` hook: every loop lifecycle event into
        the flight ring; checkpoint writes also feed the counter/age."""
        if kind == "checkpoint_written":
            self.checkpoints.inc()
            self._last_checkpoint_mono = time.monotonic()
        self.record(kind, **fields)

    def attach_rx_source(self, src) -> None:
        """Mirror a gateway/ingest's host-side ``rx_*`` counters into
        the registry so they reach ``/metrics`` (ISSUE 17: today they
        are counted host-side but invisible to scrapers).  ``src`` is
        duck-typed — any object carrying integer ``rx_frames`` /
        ``rx_batches`` / ``rx_dropped`` / ``rx_socket_errors`` /
        ``rx_shed`` attributes (missing ones are skipped).  Deltas are
        synced at every ``on_window`` / ``statusz`` scrape."""
        self._rx_src = src
        specs = (
            ("rx_frames", "oversim_gateway_rx_frames_total",
             "external frames injected into the pool (post-parse)"),
            ("rx_batches", "oversim_gateway_rx_batches_total",
             "batched EXT_IN pool writes performed"),
            ("rx_dropped", "oversim_gateway_rx_dropped_total",
             "malformed/unauthenticated frames dropped"),
            ("rx_socket_errors", "oversim_gateway_rx_socket_errors_total",
             "transient socket-level receive errors"),
            ("rx_shed", "oversim_gateway_rx_shed_total",
             "well-formed frames refused by admission control (NACKed)"),
        )
        for attr, name, help_ in specs:
            if hasattr(src, attr):
                self._rx_counters[attr] = self.registry.counter(name, help_)
                self._rx_last.setdefault(attr, 0)
        self.sync_rx()

    def sync_rx(self) -> None:
        """Push the rx source's counter deltas into the registry
        (counters are monotone: only positive deltas are applied)."""
        if self._rx_src is None:
            return
        for attr, counter in self._rx_counters.items():
            val = getattr(self._rx_src, attr, None)
            if val is None:
                continue
            delta = int(val) - self._rx_last[attr]
            if delta > 0:
                counter.inc(delta)
                self._rx_last[attr] = int(val)

    def on_window(self, window: int, summary: dict, wall_s: float) -> None:
        """Per-drained-window update off the ALREADY-FETCHED summary —
        chain it from the runner's own on_window callback."""
        self.windows.inc()
        self.sync_rx()
        if "_ticks" in summary:
            self.ticks.set(summary["_ticks"])
        if "_t_sim" in summary:
            self.sim_seconds.set(summary["_t_sim"])
        if "_alive" in summary:
            self.alive.set(summary["_alive"])
        eng = summary.get("_engine") or {}
        if "awake_nodes" in eng:
            self.awake_nodes.set(eng["awake_nodes"])
            self.active_dst.set(eng.get("active_dst", 0))
            self.lanes_stepped.set(eng.get("lanes_stepped", 0))
        if self._last_wall_s is not None and wall_s >= self._last_wall_s:
            self.window_wall.observe(wall_s - self._last_wall_s)
        self._last_wall_s = wall_s
        self._last = {"window": window,
                      "tick": summary.get("_ticks"),
                      "t_sim": summary.get("_t_sim"),
                      "alive": summary.get("_alive")}

    # --------------------------------------------------------- status --
    def checkpoint_age_s(self) -> float | None:
        if self._last_checkpoint_mono is None:
            return None
        return time.monotonic() - self._last_checkpoint_mono

    def statusz(self) -> dict:
        age = self.checkpoint_age_s()
        self.sync_rx()
        doc = dict(self._static)
        doc.update(self._last)
        doc["windows_done"] = int(self.windows.value)
        doc["checkpoints_written"] = int(self.checkpoints.value)
        doc["checkpoint_age_s"] = (round(age, 3)
                                   if age is not None else None)
        doc["flight"] = self.flight.summary()
        if self.tracer is not None:
            doc["requests"] = {
                "minted": int(self.tracer.minted.value),
                "settled": int(self.tracer.settled.value),
                "nacked": int(getattr(self.tracer, "nacked").value)
                if hasattr(self.tracer, "nacked") else 0,
                "outstanding": self.tracer.outstanding()}
        if self._rx_src is not None:
            doc["rx"] = {attr: self._rx_last.get(attr, 0)
                         for attr in self._rx_counters}
        return doc
