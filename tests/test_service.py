"""Service plane (oversim_tpu/service/): loop, ingest, config surface.

Fake-harness pins — deliberately NO Simulation compiles in this file:
it sorts mid-alphabet in the tier-1 run, whose hard timeout cuts the
suite alphabetically, so every test here must stay sub-second.  The
heavy churny resume-identity and end-to-end ingest pins live in
tests/test_zz_service_resume.py (late-alphabet, standalone budget) and
scripts/service_smoke.py (real SIGKILL across a process boundary).

The acceptance pin lives here: with double-buffering, window k+1 is
dispatched STRICTLY BEFORE window k's fetch, and the loop performs
exactly ONE host sync per window (the fetch of the copied counter
leaves) — verified on a fake runner/clock where every dispatch and
fetch is an observable event.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oversim_tpu import checkpoint as ckpt_mod
from oversim_tpu import gateway as gateway_mod
from oversim_tpu.config.ini import IniFile
from oversim_tpu.config.scenario import ScenarioError, build_service
from oversim_tpu.engine import pool as pool_mod
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.service import (InProcessIngest, ServiceLoop,
                                 ServiceParams)

from oracles import build_inbox_sort

I32 = jnp.int32
I64 = jnp.int64
NS = 1_000_000_000


# ---------------------------------------------------------------------------
# fake harness: every dispatch/fetch is an event, the clock is a counter
# ---------------------------------------------------------------------------

class FakeClock:
    """Deterministic monotone host clock (1 ms per reading)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t


@dataclasses.dataclass
class FakeSvcState:
    """Duck-typed state: only the attributes counter_leaf_refs reads."""

    t_now: int
    tick: int                 # carries the last-dispatched window index
    stats: dict
    counters: dict
    alive: np.ndarray


class FakeRunner:
    """run_until_device contract: logs the dispatch, returns instantly
    (the real one is async under jax dispatch — the fake preserves the
    observable property that dispatch does not block)."""

    def __init__(self, events):
        self.events = events
        self.n = 0

    def run_until_device(self, s, t_sim, chunk=32):
        self.events.append(("dispatch", self.n, t_sim))
        s = dataclasses.replace(s, t_now=int(t_sim * NS), tick=self.n)
        self.n += 1
        return s


class FakeTrace:
    def __init__(self):
        self.spans = []

    def span(self, name, t0, dur, args=None):
        self.spans.append((name, t0, dur, args or {}))


def _fake_loop(events, params, **kw):
    st = FakeSvcState(t_now=0, tick=-1, stats={}, counters={},
                      alive=np.ones((2,), bool))

    def fetch(snap):
        # the ONE host sync of a window: observable as a fetch event
        # tagged with the window index the snapshot belongs to
        events.append(("fetch", snap["tick"]))
        return snap

    return ServiceLoop(FakeRunner(events), st, params,
                       start_sim_t=0.0, fetch=fetch,
                       copy=lambda tree: dict(tree),
                       summarize=lambda lv: {"_t_sim": lv["t_now"] / NS},
                       now=FakeClock(), **kw)


def test_double_buffer_dispatches_ahead_of_fetch():
    """THE pipelining pin: dispatch k+1 strictly before fetch k; exactly
    one fetch per window; the trailing window drains on exit."""
    events = []
    loop = _fake_loop(events, ServiceParams(window_sim_s=1.0, chunk=4))
    state, done = loop.run(n_windows=3)
    assert done == 3
    assert events == [
        ("dispatch", 0, 1.0),
        ("dispatch", 1, 2.0), ("fetch", 0),
        ("dispatch", 2, 3.0), ("fetch", 1),
        ("fetch", 2),
    ]
    assert sum(e[0] == "fetch" for e in events) == 3
    assert state.t_now == 3 * NS


def test_second_run_continues_the_window_grid():
    """Window targets are start + (k+1)*w computed from the origin —
    a second run() continues the exact grid, never re-accumulates."""
    events = []
    loop = _fake_loop(events, ServiceParams(window_sim_s=0.5, chunk=4))
    loop.run(n_windows=2)
    loop.run(n_windows=2)
    targets = [e[2] for e in events if e[0] == "dispatch"]
    assert targets == [0.5, 1.0, 1.5, 2.0]
    assert loop.windows_done == 4


def test_single_buffer_interleaves():
    events = []
    loop = _fake_loop(events, ServiceParams(window_sim_s=1.0, chunk=4,
                                            double_buffer=False))
    _, done = loop.run(n_windows=2)
    assert done == 2
    assert events == [("dispatch", 0, 1.0), ("fetch", 0),
                      ("dispatch", 1, 2.0), ("fetch", 1)]


def test_trace_spans_show_overlap():
    """The PerfettoTrace evidence of pipelining: window k+1's dispatch
    span starts BEFORE window k's fetch span on the same fake clock."""
    events = []
    trace = FakeTrace()
    loop = _fake_loop(events, ServiceParams(window_sim_s=1.0, chunk=4),
                      trace=trace)
    loop.run(n_windows=3)
    d = {s[3]["window"]: s[1] for s in trace.spans
         if s[0] == "window_dispatch"}
    f = {s[3]["window"]: s[1] for s in trace.spans
         if s[0] == "window_fetch"}
    assert set(d) == set(f) == {0, 1, 2}
    assert d[1] < f[0] and d[2] < f[1], (
        "dispatch k+1 must begin before fetch k")


def test_limits_and_stop():
    events = []
    loop = _fake_loop(events, ServiceParams(window_sim_s=1.0, chunk=4,
                                            max_windows=2))
    _, done = loop.run()
    assert done == 2, "max_windows is an absolute limit"

    events2 = []
    loop2 = _fake_loop(events2, ServiceParams(window_sim_s=1.0, chunk=4))
    loop2.on_window = lambda w, s, t: loop2.stop()
    _, done2 = loop2.run(n_windows=10)
    assert done2 < 10, "stop() must end the run early"
    assert not any(e[0] == "dispatch" and e[1] >= done2
                   for e in events2), "stopped loop drained everything"


# ---------------------------------------------------------------------------
# checkpoint cadence + resume on a real (tiny) pytree state
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TinyState:
    t_now: jnp.ndarray
    tick: jnp.ndarray
    alive: jnp.ndarray
    stats: dict
    counters: dict


class TinyRunner:
    def run_until_device(self, s, t_sim, chunk=32):
        return dataclasses.replace(
            s, t_now=jnp.int64(int(t_sim * NS)),
            tick=s.tick + jnp.int64(chunk))


def _tiny_state():
    return TinyState(t_now=jnp.int64(0), tick=jnp.int64(0),
                     alive=jnp.ones((2,), bool),
                     stats={"c:x": jnp.int64(0)},
                     counters={"ticks": jnp.int64(0)})


def test_checkpoint_cadence_resume_and_refusals(tmp_path):
    path = str(tmp_path / "svc.npz")
    cfg = {"scenario": "tiny", "n": 2}
    p = ServiceParams(window_sim_s=0.5, chunk=4,
                      checkpoint_every=2, checkpoint_path=path)
    loop = ServiceLoop(TinyRunner(), _tiny_state(), p, config=cfg)
    state, done = loop.run(n_windows=5)
    assert done == 5
    # cadence: checkpoints land at windows_done 2 and 4, never 5
    assert loop.checkpoints_written == 2
    assert loop.last_checkpoint == 4

    meta = ckpt_mod.read_meta(path)
    assert meta["format"] == ckpt_mod.FORMAT
    assert meta["config_hash"]
    assert meta["service"] == {
        "windows_done": 4, "start_sim_t": 0.0, "window_sim_s": 0.5,
        "chunk": 4, "checkpoint_every": 2}
    assert meta["tick"] == 16   # auto-read off the snapshotted state

    # resume restores bookkeeping + state, finishes on the same grid
    r = ServiceLoop.resume(TinyRunner(), _tiny_state(), p, config=cfg)
    assert r.windows_done == 4 and r.start_sim_t == 0.0
    assert int(r.state.tick) == 16
    state2, done2 = r.run(n_windows=1)
    assert done2 == 5
    assert int(state2.t_now) == int(state.t_now)

    # a checkpoint from a different scenario is refused
    with pytest.raises(ValueError, match="scenario mismatch"):
        ServiceLoop.resume(TinyRunner(), _tiny_state(), p,
                           config={"scenario": "other", "n": 2})
    # a changed window cadence would silently break bit-identity: refuse
    p2 = dataclasses.replace(p, window_sim_s=1.0)
    with pytest.raises(ValueError, match="cadence mismatch"):
        ServiceLoop.resume(TinyRunner(), _tiny_state(), p2, config=cfg)


def test_override_cadence_reanchors_window_origin(tmp_path):
    """The cadence-mismatch escape hatch: ``override_cadence=True``
    re-anchors the window origin so the NEXT target is the restored
    clock plus one NEW window, and every later target is recomputed as
    ``start + (k+1)*w`` from that origin — never accumulated."""
    path = str(tmp_path / "svc.npz")
    cfg = {"scenario": "tiny", "n": 2}
    p = ServiceParams(window_sim_s=0.5, chunk=4,
                      checkpoint_every=2, checkpoint_path=path)
    ServiceLoop(TinyRunner(), _tiny_state(), p, config=cfg).run(
        n_windows=5)
    # checkpoint landed at windows_done=4, t_now = 4 * 0.5s = 2.0s

    # the refusal names the hatch so operators can find it
    p2 = dataclasses.replace(p, window_sim_s=1.25)
    with pytest.raises(ValueError, match="override_cadence"):
        ServiceLoop.resume(TinyRunner(), _tiny_state(), p2, config=cfg)

    class Recorder(TinyRunner):
        def __init__(self):
            self.targets = []

        def run_until_device(self, s, t_sim, chunk=32):
            self.targets.append(float(t_sim))
            return super().run_until_device(s, t_sim, chunk=chunk)

    rec = Recorder()
    # same cadence + override: a plain resume, origin untouched
    r2 = ServiceLoop.resume(TinyRunner(), _tiny_state(), p, config=cfg,
                            override_cadence=True)
    assert r2.start_sim_t == 0.0 and r2.windows_done == 4

    r = ServiceLoop.resume(rec, _tiny_state(), p2, config=cfg,
                           override_cadence=True)
    assert r.windows_done == 4
    # re-anchored origin: restored clock minus windows_done NEW windows
    assert r.start_sim_t == pytest.approx(2.0 - 4 * 1.25)
    state, done = r.run(n_windows=2)
    assert done == 6
    # next target = restored t_now + one new window; then the grid
    assert rec.targets == [pytest.approx(2.0 + 1.25),
                           pytest.approx(2.0 + 2 * 1.25)]
    assert rec.targets == [pytest.approx(r.start_sim_t + k * 1.25)
                           for k in (5, 6)]


def test_checkpoint_now_graceful_shutdown(tmp_path):
    """The SIGTERM path: stop() drains the in-flight window, then
    checkpoint_now() snapshots the CURRENT state even when the cadence
    checkpoint isn't due — and the result resumes."""
    path = str(tmp_path / "svc.npz")
    cfg = {"scenario": "tiny", "n": 2}
    p = ServiceParams(window_sim_s=0.5, chunk=4,
                      checkpoint_every=100, checkpoint_path=path)
    loop = ServiceLoop(TinyRunner(), _tiny_state(), p, config=cfg)
    loop.run(n_windows=3)
    assert loop.checkpoints_written == 0      # cadence never fired
    assert loop.checkpoint_now() is True
    meta = ckpt_mod.read_meta(path)
    assert meta["service"]["windows_done"] == 3
    r = ServiceLoop.resume(TinyRunner(), _tiny_state(), p, config=cfg)
    assert r.windows_done == 3 and int(r.state.tick) == 12

    # without a checkpoint path it reports False instead of raising
    free = ServiceLoop(TinyRunner(), _tiny_state(),
                       ServiceParams(window_sim_s=0.5, chunk=4))
    free.run(n_windows=1)
    assert free.checkpoint_now() is False


# ---------------------------------------------------------------------------
# ingest: batched injection, drain, and the engine's EXT_OUT hold
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PoolState:
    """Minimal state with the fields the gateway pool helpers touch."""

    pool: pool_mod.MsgPool
    t_now: jnp.ndarray


def _pool_state(p=16):
    return PoolState(pool=pool_mod.empty(p, key_lanes=2, rmax=2),
                     t_now=jnp.int64(1000))


def test_ext_out_kind_matches_gateway():
    """engine/sim.py mirrors gateway.EXT_OUT (the engine must not
    import the gateway) — the mirror must never drift."""
    assert sim_mod.EXT_OUT_KIND == gateway_mod.EXT_OUT


def test_in_process_ingest_batches_and_drains():
    st = _pool_state()
    ing = InProcessIngest(gw_slot=0)
    s1 = ing.submit(b=1, c=100)
    s2 = ing.submit(b=2, c=200)

    st = ing.before_window(st, target_ns=5000)
    assert ing.num_batches == 1 and ing.num_injected == 2, (
        "accumulated requests must enter the pool as ONE batched write")
    valid = np.asarray(st.pool.valid)
    assert valid.sum() == 2
    order = np.argsort(np.asarray(st.pool.t_deliver)[valid])
    assert list(np.asarray(st.pool.a)[valid][order]) == [s1, s2]
    assert set(np.asarray(st.pool.kind)[valid]) == {gateway_mod.EXT_IN}

    # nothing pending -> no write, state unchanged
    assert ing.before_window(st, target_ns=9000) is st
    assert ing.num_batches == 1

    # a parked EXT_OUT response is collected and freed by after_window
    st, _ = gateway_mod.inject_ext_batch(
        st, [gateway_mod.ExtFrame(a=s1, b=1, c=142,
                                  kind=gateway_mod.EXT_OUT)], 0)
    st = ing.after_window(st)
    assert ing.responses == {s1: (1, 142)}
    kinds = np.asarray(st.pool.kind)[np.asarray(st.pool.valid)]
    assert gateway_mod.EXT_OUT not in kinds, "drained EXT_OUT not freed"
    assert ing.overflow() == 0


def test_ext_hold_parks_ext_out_for_the_drain():
    """The engine-side half of serving: a hold mask keeps EXT_OUT
    responses addressed to the gateway slot OUT of the inbox (they'd be
    consumed one tick after being sent otherwise); everything else
    delivers normally.  The selection and its sort oracle honor it
    identically."""
    st = _pool_state(p=8)
    frames = [
        gateway_mod.ExtFrame(a=1, b=7, c=70, kind=gateway_mod.EXT_OUT,
                             dst=0),                      # parked
        gateway_mod.ExtFrame(a=2, b=8, c=80, dst=1),      # EXT_IN: delivers
    ]
    st, _ = gateway_mod.inject_ext_batch(st, frames, 0)
    pool = st.pool
    alive = jnp.ones((2,), bool)
    t_end = jnp.int64(10_000)
    hold = (pool.valid & (pool.kind == sim_mod.EXT_OUT_KIND)
            & (pool.dst == 0))
    for impl, build in (("scatter", pool_mod.build_inbox),
                        ("sort", build_inbox_sort)):
        inbox, delivered, _ = build(pool, 2, 2, t_end, alive, hold=hold)
        kinds = np.asarray(pool.kind)
        dlv = np.asarray(delivered)
        assert not dlv[np.asarray(pool.valid)
                       & (kinds == gateway_mod.EXT_OUT)].any(), impl
        assert dlv[np.asarray(pool.valid)
                   & (kinds == gateway_mod.EXT_IN)].all(), impl
        # without the hold the response WOULD be consumed — the hazard
        # the ext_hold_slot engine knob exists for
        _, dlv_nohold, _ = build(pool, 2, 2, t_end, alive)
        assert np.asarray(dlv_nohold)[np.asarray(pool.valid)].all(), impl


def test_ingest_forces_single_buffer_and_tracks_clock():
    """With ingest attached the loop single-buffers (inject → dispatch →
    fetch → drain per window) and window targets track the ACTUAL clock
    when chunk overshoot has run past the grid."""
    events = []

    class Ingest:
        def before_window(self, state, target_ns):
            events.append(("inject", target_ns))
            return state

        def after_window(self, state):
            events.append(("drain",))
            return state

    class OvershootRunner(FakeRunner):
        def run_until_device(self, s, t_sim, chunk=32):
            s = super().run_until_device(s, t_sim, chunk)
            # event-driven ticks + whole-chunk dispatch overshoot the
            # target by several windows
            return dataclasses.replace(s, t_now=int((t_sim + 5.0) * NS))

    st = FakeSvcState(t_now=0, tick=-1, stats={}, counters={},
                      alive=np.ones((2,), bool))
    loop = ServiceLoop(OvershootRunner(events), st,
                       ServiceParams(window_sim_s=1.0, chunk=4),
                       start_sim_t=0.0, ingest=Ingest(),
                       fetch=lambda snap: snap,
                       copy=lambda tree: dict(tree),
                       summarize=lambda lv: {}, now=FakeClock())
    loop.run(n_windows=2)
    kinds = [e[0] for e in events]
    assert kinds == ["inject", "dispatch", "drain",
                     "inject", "dispatch", "drain"]
    # window 1: clock sits at 6.0 after the overshoot, so the target
    # must advance to 7.0 — the grid value (2.0) would run zero ticks
    # and strand the injected requests
    targets = [e[2] for e in events if e[0] == "dispatch"]
    assert targets == [1.0, 7.0]
    assert events[3] == ("inject", 7 * NS)


# ---------------------------------------------------------------------------
# config surface: **.service.* ini keys
# ---------------------------------------------------------------------------

def test_build_service_from_ini():
    ini = IniFile.loads(
        "**.service.windowSimS = 0.25\n"
        "**.service.chunk = 64\n"
        "**.service.checkpointEvery = 5\n"
        '**.service.checkpointPath = "svc.npz"\n'
        "**.service.maxWindows = 200\n"
        "**.service.doubleBuffer = false\n"
        "**.service.realtime = true\n")
    p = build_service(ini)
    assert p == ServiceParams(window_sim_s=0.25, chunk=64,
                              checkpoint_every=5,
                              checkpoint_path="svc.npz",
                              max_windows=200, max_wall_s=0.0,
                              double_buffer=False, realtime=True)


def test_build_service_defaults_and_validation():
    assert build_service(IniFile.loads("**.x = 1\n")) == ServiceParams()
    with pytest.raises(ScenarioError, match="windowSimS"):
        build_service(IniFile.loads("**.service.windowSimS = 0\n"))
    with pytest.raises(ScenarioError, match="chunk"):
        build_service(IniFile.loads("**.service.chunk = 0\n"))
    with pytest.raises(ScenarioError, match="checkpointPath"):
        build_service(IniFile.loads("**.service.checkpointEvery = 4\n"))


def test_in_process_ingest_max_pending_sheds_with_nack():
    """Admission control on the in-process queue (ISSUE 17): past
    ``max_pending`` waiting frames, submit() mints the sid but refuses
    the frame — ``nacked`` + ``rx_shed``, tracer.nack — so every minted
    request either settles or carries an explicit NACK.  Injection
    drains the queue and re-opens admission."""

    class Tr:
        def __init__(self):
            self.minted, self.nacks, self.settled = [], [], []

        def mint(self, sid, **kw):
            self.minted.append(sid)

        def settle(self, sid, **kw):
            self.settled.append(sid)

        def nack(self, sid, **kw):
            self.nacks.append(sid)
            return True

    tr = Tr()
    ing = InProcessIngest(gw_slot=0, tracer=tr, max_pending=2)
    s1 = ing.submit(b=1, c=100)
    s2 = ing.submit(b=2, c=200)
    s3 = ing.submit(b=3, c=300)          # bound hit: shed
    assert ing.rx_shed == 1 and ing.nacked == {s3: (3, 300)}
    assert tr.minted == [s1, s2, s3] and tr.nacks == [s3]

    st = _pool_state()
    st = ing.before_window(st, target_ns=5000)
    # ONLY the admitted frames entered the pool
    assert ing.num_injected == 2
    valid = np.asarray(st.pool.valid)
    assert sorted(np.asarray(st.pool.a)[valid]) == sorted([s1, s2])
    # the queue drained: admission is open again
    s4 = ing.submit(b=4, c=400)
    assert s4 not in ing.nacked and ing.rx_shed == 1
    # unbounded ingest never sheds
    free = InProcessIngest(gw_slot=0)
    for i in range(64):
        free.submit(b=i, c=i)
    assert free.rx_shed == 0 and free.nacked == {}


def test_ingest_one_fetch_per_window_after_first():
    """Serving windows reuse the drained snapshot's clock: after window
    0's fresh current-time read, every window costs exactly ONE
    fetch-hook sync (the drain).  A second per-window read is the
    regression this pins — scripts/slo_soak.py's pin phase asserts the
    same count on the real campaign-stacked daemon."""
    events = []

    class Ingest:
        def before_window(self, state, target_ns):
            return state

        def after_window(self, state):
            return state

    st = FakeSvcState(t_now=0, tick=-1, stats={}, counters={},
                      alive=np.ones((2,), bool))
    clock_reads, drains = [], []

    def fetch(snap):
        # drain fetches pass the copied leaf dict; the boundary's
        # current-time read passes the raw t_now scalar
        (drains if isinstance(snap, dict) else clock_reads).append(snap)
        return snap

    loop = ServiceLoop(FakeRunner(events), st,
                       ServiceParams(window_sim_s=1.0, chunk=4),
                       start_sim_t=0.0, ingest=Ingest(), fetch=fetch,
                       copy=lambda tree: dict(tree),
                       summarize=lambda lv: {}, now=FakeClock())
    loop.run(n_windows=4)
    assert len(drains) == 4
    assert len(clock_reads) == 1, (
        "only the very first serving window pays a fresh clock read")
    loop.run(n_windows=2)   # a continuation reuses the cached clock too
    assert len(drains) == 6 and len(clock_reads) == 1
