"""bench.run_measurement_windows dispatch-count pins (round 7).

The bench measurement loop must be device-resident: per wall-clock
window exactly ONE ``run_until_device`` dispatch and ONE host sync (a
single ``jax.device_get`` of the counter leaves in
``_fetch_window_leaves``).  A fake-timer fake-sim pins that contract
without touching a backend — a regression back to per-chunk syncing
shows up here as extra dispatch or fetch calls.
"""

import json

import numpy as np

import bench


class FakeState:
    """Quacks like SimState for bench's leaf fetch: numpy leaves only."""

    def __init__(self, t_now=0, tick=0):
        self.stats = {"c:fake_counter": np.int64(tick)}
        self.counters = {"pool_overflow": np.int64(0)}
        self.t_now = np.int64(t_now)
        self.tick = np.int64(tick)
        self.alive = np.ones((4,), bool)


class FakeSim:
    """Counts dispatches; each run_until_device jumps to the target."""

    def __init__(self):
        self.device_calls = []
        self.host_calls = []

    def run_until_device(self, s, t_sim, chunk=256):
        self.device_calls.append((float(t_sim), chunk))
        return FakeState(t_now=int(t_sim * 1e9), tick=s.tick + chunk)

    def run_until(self, s, t_sim, chunk=256, check_invariants=None):
        self.host_calls.append((float(t_sim), chunk, check_invariants))
        return FakeState(t_now=int(t_sim * 1e9), tick=s.tick + chunk)


class FakeClock:
    """now() advances 10 fake-seconds per call — fully deterministic."""

    def __init__(self, dt=10.0):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        t, self.t = self.t, self.t + self.dt
        return t


def test_one_dispatch_and_one_fetch_per_window(monkeypatch):
    fetches = []
    real_fetch = bench._fetch_window_leaves
    monkeypatch.setattr(bench, "_fetch_window_leaves",
                        lambda s: fetches.append(1) or real_fetch(s))
    sim = FakeSim()
    summaries = []
    # clock: t0=0; cond at 10/30/50 pass, at 70 fails -> exactly 3 windows
    s, windows = bench.run_measurement_windows(
        sim, FakeState(), start_sim_t=100.0, window_sim_s=6.25,
        measure_wall=55.0, chunk=32,
        on_window=lambda out, wall: summaries.append((out, wall)),
        now=FakeClock(dt=10.0))
    assert windows == 3
    assert len(sim.device_calls) == 3          # ONE dispatch per window
    assert len(fetches) == 3                   # ONE device_get per window
    assert sim.host_calls == []                # host loop never engaged
    # each window advances the sim-time target by exactly one window span
    targets = [t for t, _ in sim.device_calls]
    assert targets == [100.0 + 6.25 * k for k in (1, 2, 3)]
    assert all(chunk == 32 for _, chunk in sim.device_calls)
    # the summary handed to on_window came from the fetched leaves
    assert [out["fake_counter"] for out, _ in summaries] == [32, 64, 96]
    assert [wall for _, wall in summaries] == [20.0, 40.0, 60.0]
    assert s.tick == 96


class FakeTelemetry:
    """Quacks like telemetry.TelemetryState ring leaves (numpy only)."""

    def __init__(self, tick=0):
        self.n = np.int64(tick)
        self.alive = np.ones((4,), np.int64)


class FakeStateTel(FakeState):
    def __init__(self, t_now=0, tick=0):
        super().__init__(t_now, tick)
        self.telemetry = FakeTelemetry(tick)


class FakeSimTel(FakeSim):
    def run_until_device(self, s, t_sim, chunk=256):
        self.device_calls.append((float(t_sim), chunk))
        return FakeStateTel(t_now=int(t_sim * 1e9), tick=s.tick + chunk)


def test_one_dispatch_one_fetch_with_telemetry_and_trace(monkeypatch):
    """Telemetry riding in SimState must NOT add a second sync: the ring
    leaves come back inside the same single ``_fetch_window_leaves``
    device_get, and the Perfetto trace records exactly one
    window_dispatch + one window_fetch span per window."""
    from oversim_tpu import telemetry as telemetry_mod
    fetched = []
    real_fetch = bench._fetch_window_leaves
    monkeypatch.setattr(bench, "_fetch_window_leaves",
                        lambda s: fetched.append(real_fetch(s))
                        or fetched[-1])
    trace = telemetry_mod.PerfettoTrace("test")
    sim = FakeSimTel()
    # with a trace the loop reads now() 5x per window (cond, dispatch
    # start/end, fetch end, on_window wall): windows end at wall 50/100/
    # 150, the cond at 160 stops -> exactly 3 windows
    s, windows = bench.run_measurement_windows(
        sim, FakeStateTel(), start_sim_t=100.0, window_sim_s=6.25,
        measure_wall=150.0, chunk=32, on_window=lambda out, wall: None,
        now=FakeClock(dt=10.0), trace=trace)
    assert windows == 3
    assert len(sim.device_calls) == 3          # ONE dispatch per window
    assert len(fetched) == 3                   # ONE device_get per window
    # the rings came along inside that one fetch
    assert all("telemetry" in leaves for leaves in fetched)
    assert fetched[-1]["telemetry"].n == s.telemetry.n
    spans = [e for e in trace.to_dict()["traceEvents"]
             if e.get("ph") == "X"]
    disp = [e for e in spans if e["name"] == "window_dispatch"]
    fetch = [e for e in spans if e["name"] == "window_fetch"]
    assert len(disp) == 3 and len(fetch) == 3
    assert [e["args"]["window"] for e in disp] == [0, 1, 2]
    assert all(e["dur"] == 10.0e6 for e in disp + fetch)  # one clock step


def test_untelemetried_fake_state_fetch_has_no_telemetry_key():
    leaves = bench._fetch_window_leaves(FakeState(tick=5))
    assert "telemetry" not in leaves
    assert leaves["tick"] == 5


def test_sparse_counters_ride_the_single_fetch():
    """The awake-set counters are ordinary SimState.counters leaves:
    they come back inside the one per-window device_get and land in the
    summary's _engine dict — no extra sync — and none of them matches
    the bench health gate's unhealthy-counter pattern (the plane never
    defers: a busy tick takes more rounds, and ``lanes_stepped`` says
    how many)."""
    st = FakeState(tick=8)
    st.counters = dict(st.counters, awake_nodes=np.int64(37),
                       active_dst=np.int64(21),
                       lanes_stepped=np.int64(64))
    leaves = bench._fetch_window_leaves(st)
    assert leaves["counters"]["awake_nodes"] == 37
    out = bench._summary_from_leaves(leaves)
    assert out["_engine"]["awake_nodes"] == 37
    assert out["_engine"]["active_dst"] == 21
    assert out["_engine"]["lanes_stepped"] == 64
    # the health gate flags any positive-delta counter whose name
    # contains "overflow" or "deferred"
    flagged = {k for k in out["_engine"]
               if "overflow" in k or "deferred" in k}
    assert not flagged & {"awake_nodes", "active_dst", "lanes_stepped"}


def test_stop_event_finishes_in_flight_window_then_exits():
    """Graceful SIGTERM: the handler sets a threading.Event; the loop
    checks it at window boundaries only, so a signal landing MID-window
    lets that window complete (its on_window summary is emitted) and
    stops before the next — never a torn window."""
    import threading

    stop = threading.Event()
    summaries = []

    class SimThatGetsSignalled(FakeSim):
        def run_until_device(self, s, t_sim, chunk=256):
            if len(self.device_calls) == 1:
                stop.set()          # "SIGTERM" arrives mid-window 2
            return super().run_until_device(s, t_sim, chunk=chunk)

    sim = SimThatGetsSignalled()
    # the wall budget alone would allow 3+ windows (cf. the first test)
    s, windows = bench.run_measurement_windows(
        sim, FakeState(), start_sim_t=100.0, window_sim_s=6.25,
        measure_wall=55.0, chunk=32,
        on_window=lambda out, wall: summaries.append(out),
        now=FakeClock(dt=10.0), stop=stop)
    assert windows == 2                        # window 2 completed ...
    assert len(sim.device_calls) == 2          # ... and no window 3
    assert [out["fake_counter"] for out in summaries] == [32, 64]
    assert s.tick == 64

    # an already-set event stops before the first window
    sim2 = FakeSim()
    _, w0 = bench.run_measurement_windows(
        sim2, FakeState(), start_sim_t=0.0, window_sim_s=1.0,
        measure_wall=55.0, chunk=8, on_window=lambda out, wall: None,
        now=FakeClock(dt=10.0), stop=stop)
    assert w0 == 0 and sim2.device_calls == []


def test_host_loop_mode_uses_run_until_with_invariants():
    """OVERSIM_INVARIANTS=1 debug tier: the per-chunk-synced run_until
    (with the structural validator on) replaces the device loop."""
    sim = FakeSim()
    _, windows = bench.run_measurement_windows(
        sim, FakeState(), start_sim_t=0.0, window_sim_s=1.0,
        measure_wall=35.0, chunk=8, on_window=lambda out, wall: None,
        host_loop=True, now=FakeClock(dt=10.0))
    assert windows == 2
    assert sim.device_calls == []
    assert sim.host_calls == [(1.0, 8, True), (2.0, 8, True)]


# -- incremental artifact persistence (OVERSIM_BENCH_ARTIFACT) ---------------


def test_artifact_writer_valid_after_every_add(tmp_path):
    """The artifact file must be complete, parseable JSON after EVERY
    add() — a SIGKILL between windows leaves a valid partial artifact
    with complete=False and final = the last window measured."""
    path = str(tmp_path / "bench.json")
    w = bench.ArtifactWriter(path)
    # the file exists (empty but valid) before any window completes
    with open(path) as f:
        doc = json.load(f)
    assert doc == {"records": [], "final": None, "complete": False}

    for i in range(3):
        w.add({"window": i, "value": 10.0 * i})
        with open(path) as f:
            doc = json.load(f)
        # simulated kill here: everything measured so far is on disk
        assert len(doc["records"]) == i + 1
        assert doc["final"] == {"window": i, "value": 10.0 * i}
        assert doc["complete"] is False

    w.finish()
    with open(path) as f:
        doc = json.load(f)
    assert doc["complete"] is True
    assert doc["final"]["window"] == 2
    assert [r["window"] for r in doc["records"]] == [0, 1, 2]
    # no torn-write leftovers
    assert not (tmp_path / "bench.json.tmp").exists()


def test_artifact_writer_disabled_without_path(tmp_path):
    """path=None (env var unset) must be a no-op sink."""
    w = bench.ArtifactWriter(None)
    w.add({"x": 1})
    w.finish()
    assert list(tmp_path.iterdir()) == []


def test_atomic_write_json_replaces_whole_file(tmp_path):
    path = str(tmp_path / "a.json")
    bench.atomic_write_json(path, {"v": 1})
    bench.atomic_write_json(path, {"v": 2, "w": [1, 2]})
    with open(path) as f:
        assert json.load(f) == {"v": 2, "w": [1, 2]}
    # unwritable destination is swallowed, not raised (bench must never
    # die because the artifact disk path is bad)
    bench.atomic_write_json(str(tmp_path / "no_dir" / "b.json"), {"v": 3})
