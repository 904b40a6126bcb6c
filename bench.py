"""Headline benchmark: simulated KBR lookups per wallclock second.

Scenario ≈ BASELINE.md driver config #2: Kademlia (the reference's
scale protocol — its 1M-node rows), SimpleUnderlay delay model,
KBRTestApp one-way workload, no churn.  The reference (trucndt/oversim)
runs this as a single-threaded discrete-event loop (~1e5-1e6
events/core-s, one handleMessage per event); here every tick advances
all N nodes at once on the accelerator, so throughput scales with the
node batch (lookups-per-tick), not with the event count.

Process layout:

  * the top-level process is a thin ORCHESTRATOR that never touches
    jax: it prints a provisional JSON line immediately, re-runs itself
    as a CHILD with a hard wall-clock deadline (OVERSIM_BENCH_DEADLINE),
    relays every child JSON line to stdout as it appears, SIGKILLs the
    child at the deadline, and returns the CHILD'S exit code — a run
    that dies (compile stall, mid-run crash, no chip) leaves the lines
    it got out and a non-zero exit;
  * the child measures on the device jax finds and exits non-zero when
    that device is not a TPU, unless OVERSIM_BENCH_PLATFORM=cpu asked
    for the host tier by name.  There is no fallback to the CPU and no
    replay of an older number.  It emits an updated JSON line after
    EVERY measurement window — the last line printed is the result.

Measurement windows are DEVICE-RESIDENT (round 7): warm-up and each
window run through ``run_until_device``'s donated ``lax.while_loop`` —
one dispatch per window, one host sync per window (a single
``jax.device_get`` of the counter leaves; ``run_measurement_windows``).
``OVERSIM_INVARIANTS=1`` keeps the old host-synced ``run_until`` loop
with the structural validator between chunks.

Env overrides: OVERSIM_BENCH_N (nodes), OVERSIM_BENCH_MEASURE_WALL
(seconds of wall-clock to measure for), OVERSIM_BENCH_INTERVAL (per-node
test period, s), OVERSIM_BENCH_PLATFORM ("cpu" = the host tier, asked
for by name; unset = the TPU or fail), OVERSIM_BENCH_DEADLINE
(orchestrator kill watchdog, s),
OVERSIM_BENCH_CHUNK (scan ticks per while_loop body; default 256 TPU /
32 CPU), OVERSIM_BENCH_TICK_IMPL ("dense" | "sparse" — the active-set
tick plane, engine/sim.py) + OVERSIM_BENCH_ACTIVE_CAP (sparse lane
bound, 0 = auto).

OVERSIM_BENCH_ACTIVITY="0.01,0.1,1.0" switches to the ACTIVITY-SWEEP
tier: one ms/tick row per activity fraction (per-node test interval =
window / fraction) into the standard atomic artifact — the sparse
plane's tick-cost-scales-with-traffic success metric, runnable on CPU
today and TPU later unchanged.

OVERSIM_BENCH_REPLICAS=S (S >= 1) switches to the CAMPAIGN tier: one
vmapped program advances S independent replicas of the same scenario
(oversim_tpu/campaign/), replica axis sharded across the visible devices
when S divides their count.  The emitted rate is the AGGREGATE
lookups/s summed over replicas — the compile-amortization headline
(PERFORMANCE.md round 8): one compile, S times the batch.

OVERSIM_BENCH_ARTIFACT=path makes the orchestrator ALSO persist every
relayed measurement record to ``path`` incrementally — the file is
rewritten atomically (tmp + os.replace) after EVERY window, so a SIGKILL
at any point leaves a valid, parseable JSON artifact of everything
measured so far ({"records": [...], "final": last, "complete": bool}).

Where a tick's device time goes, phase by phase: the tick program
names its phases (oversim_tpu/core/scopes.py), and
``benchmark/phases.py`` (a cell's own run) or ``benchmark/
phase_reduce.py <dir>`` (any profiler dump, OVERSIM_XPROF's included)
reduces a device trace by them.

Telemetry plane (oversim_tpu/telemetry.py): OVERSIM_BENCH_TELEMETRY=K
samples the KPI ring buffers every K ticks INSIDE the device loop
(window capacity OVERSIM_BENCH_TELEMETRY_WINDOW, default 256) and emits
the time series as a ``telemetry_series`` side-channel line after the
run; OVERSIM_BENCH_TRACE=path writes a Perfetto/Chrome-trace JSON of
the per-window dispatch/fetch spans.  Every run emits a ``run_manifest`` line (config
hash, mesh layout, git rev) that the orchestrator attaches to the
artifact's top-level ``manifest`` key.
"""

import json
import os
import subprocess
import sys
import time

DEADLINE_S = int(os.environ.get("OVERSIM_BENCH_DEADLINE", 235))
_T0 = time.time()

# The reference publishes no benchmark numbers (BASELINE.json published={}).
# Baseline estimate for the same workload on one CPU core: an OMNeT++
# SimpleUnderlay event costs ~2-10us (hashmap lookup + calcDelay + FES
# insert, SURVEY.md §2.2), and one KBR lookup is ~12-16 events (6 RPC
# round trips + final hop + timers) → ~2e4 lookups/core-s.  This constant
# is the denominator for vs_baseline until a measured reference number
# replaces it.
BASELINE_LOOKUPS_PER_SEC = 2.0e4


def _json_line(rate: float, unit: str, *, healthy: bool = False,
               extra: dict | None = None) -> str:
    """One emitted measurement record.  ``healthy`` is the hard delivery
    gate (VERDICT r4 weak #5): True only for windows with ≥95% delivery
    AND zero engine overflow counters — only those may become the
    record.  ``cached`` is always False (no line is ever replayed); the
    key stays for consumers of older artifacts."""
    rec = {
        "metric": "kbr_lookups_per_sec",
        "value": round(rate, 2),
        "unit": unit,
        "vs_baseline": round(rate / BASELINE_LOOKUPS_PER_SEC, 4),
        "healthy": bool(healthy),
        "cached": False,
    }
    if extra:
        rec.update(extra)
    return json.dumps(rec)


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------

def atomic_write_json(path: str, obj) -> None:
    """Crash-safe JSON write: tmp file + os.replace.  A reader (or the
    driver, after SIGKILLing us) either sees the previous complete file
    or the new complete file — never a torn write."""
    tmp = path + ".tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(obj, f, indent=1)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except OSError:
        pass


class ArtifactWriter:
    """Incremental measurement artifact (OVERSIM_BENCH_ARTIFACT /
    OVERSIM_SCALE_ARTIFACT): every ``add`` rewrites the whole file
    atomically, so the artifact is valid JSON after every window and a
    deadline SIGKILL merely truncates the record LIST, never the file."""

    def __init__(self, path: str | None):
        self.path = path
        self.records = []
        self.manifest = None
        if path:
            self._flush(complete=False)

    def add(self, record: dict) -> None:
        if not self.path:
            return
        self.records.append(record)
        self._flush(complete=False)

    def set_manifest(self, manifest: dict) -> None:
        """Attach a RunManifest (oversim_tpu/telemetry.py run_manifest):
        kept OUT of the record list under its own top-level key, and
        re-flushed atomically like any add."""
        self.manifest = manifest
        if self.path:
            self._flush(complete=False)

    def finish(self) -> None:
        if self.path:
            self._flush(complete=True)

    def _flush(self, *, complete: bool) -> None:
        doc = {
            "records": self.records,
            "final": self.records[-1] if self.records else None,
            "complete": complete,
        }
        if self.manifest is not None:
            doc["manifest"] = self.manifest
        atomic_write_json(self.path, doc)


def orchestrate() -> int:
    """Run the measurement in a child with a hard deadline; relay its
    JSON lines; return the child's exit code (-9 after a deadline
    kill).  The provisional line goes out first so even a run that dies
    at once leaves a parseable, healthy:false record."""
    artifact = ArtifactWriter(os.environ.get("OVERSIM_BENCH_ARTIFACT"))
    prov = _json_line(0.0, "lookups/s (provisional: no measurement "
                           "completed yet)")
    print(prov, flush=True)
    artifact.add(json.loads(prov))
    env = dict(os.environ, OVERSIM_BENCH_CHILD="1")
    child = subprocess.Popen([sys.executable, os.path.abspath(__file__)],
                             stdout=subprocess.PIPE, text=True, env=env)
    import threading

    def _watchdog():
        remain = DEADLINE_S - (time.time() - _T0)
        if remain > 0:
            time.sleep(remain)
        if child.poll() is None:
            sys.stderr.write("bench: deadline %ds hit — killing child\n"
                             % DEADLINE_S)
            child.kill()

    threading.Thread(target=_watchdog, daemon=True).start()
    # graceful SIGTERM: forward to the child (which finishes its
    # in-flight window and exits 0); the relay loop then drains the
    # child's remaining lines and finish() commits a COMPLETE artifact
    import signal as signal_mod
    signal_mod.signal(signal_mod.SIGTERM,
                      lambda *_: child.terminate())
    last_healthy = None         # most recent gate-passing line
    last_line_healthy = False
    for line in child.stdout:
        line = line.rstrip("\n")
        if not line:
            continue
        try:
            parsed = json.loads(line)
        except ValueError:
            sys.stderr.write("bench child: %s\n" % line)
            continue
        if parsed.get("metric") not in (None, "kbr_lookups_per_sec"):
            # diagnostic side-channel lines (e.g. the telemetry_series
            # record) are
            # relayed verbatim but never enter the measurement-record
            # logic below; the child's run_manifest line attaches as
            # the artifact's top-level manifest instead of a record
            print(line, flush=True)
            if parsed.get("metric") == "run_manifest":
                artifact.set_manifest(parsed)
            else:
                artifact.add(parsed)
            continue
        last_line_healthy = bool(parsed.get("healthy"))
        if last_line_healthy:
            last_healthy = line
        print(line, flush=True)  # the driver parses the LAST line
        artifact.add(parsed)     # atomic rewrite after EVERY window
    child.wait()
    if not last_line_healthy and last_healthy is not None:
        # the FINAL printed window failed the delivery gate — it must
        # not stand as the record when a gate-passing window of THIS
        # run exists: re-print the most recent healthy line.  If
        # nothing healthy exists, the unhealthy line stays last —
        # machine-readably flagged healthy:false, the honest record of
        # a run with no valid measurement.
        print(last_healthy, flush=True)
        artifact.add(json.loads(last_healthy))
    artifact.finish()
    sys.stderr.write("bench: child rc=%s, done in %.0fs\n"
                     % (child.returncode, time.time() - _T0))
    return child.returncode


# ---------------------------------------------------------------------------
# device-resident measurement windows
# ---------------------------------------------------------------------------

def _fetch_window_leaves(s):
    """ONE host sync: a single jax.device_get of the counter leaves
    (stats accumulators, engine counters, clock, alive mask) — plus the
    telemetry ring buffers when the state carries them (still the same
    single device_get; the rings are small [W]-shaped leaves)."""
    import jax
    leaves = {"stats": s.stats, "counters": s.counters,
              "t_now": s.t_now, "tick": s.tick, "alive": s.alive}
    tel = getattr(s, "telemetry", None)
    if tel is not None:
        leaves["telemetry"] = tel
    return jax.device_get(leaves)


def _summary_from_leaves(leaves) -> dict:
    """Host-side summary off already-fetched leaves (no device access —
    the per-window sync stays the one device_get above).  The body
    lives in oversim_tpu/service/loop.py (the serving loop shares it);
    imported lazily — bench must not import the package at module
    scope (the child sets jax config first)."""
    from oversim_tpu.service.loop import summarize_counter_leaves
    return summarize_counter_leaves(leaves)


def _campaign_summary_from_leaves(leaves) -> dict:
    """Campaign tier: leaves carry a leading [S] replica axis; the
    cross-replica merge lives in oversim_tpu/service/loop.py."""
    from oversim_tpu.service.loop import campaign_summarize_leaves
    return campaign_summarize_leaves(leaves)


def run_measurement_windows(sim, s, *, start_sim_t, window_sim_s,
                            measure_wall, chunk, on_window,
                            host_loop=False, now=time.perf_counter,
                            summarize_leaves=_summary_from_leaves,
                            trace=None, stop=None):
    """Drive wall-clock measurement windows, device-resident.

    Each window advances the sim by ``window_sim_s`` simulated seconds
    with ONE dispatch (``run_until_device``'s donated while_loop) and
    ONE host sync (a single ``jax.device_get`` of the counter leaves),
    then calls ``on_window(summary, wall_s)``.  ``host_loop=True``
    falls back to the per-chunk-synced ``run_until`` WITH invariant
    checking — the OVERSIM_INVARIANTS=1 debug tier.  Returns
    ``(s, n_windows)``.  Tested against a fake-timer simulation in
    tests/test_bench_windows.py (exactly one dispatch per window).
    ``summarize_leaves`` turns the fetched counter leaves into the
    per-window summary — the campaign tier passes
    ``_campaign_summary_from_leaves`` (leaves carry a [S] replica axis).
    ``trace`` (a telemetry.PerfettoTrace) records a ``window_dispatch``
    and a ``window_fetch`` span per window — exactly one of each, the
    Perfetto view of the one-dispatch-one-fetch contract.  The extra
    ``now()`` reads happen only with a trace, so the fake-timer pins of
    the untraced loop are unchanged.
    ``stop`` (a ``threading.Event``) requests a graceful early finish:
    checked only at the window boundary, so the in-flight window always
    completes and its summary is reported — the SIGTERM handler's half
    of the clean-shutdown contract (tests/test_bench_windows.py).
    """
    t0 = now()
    sim_t = start_sim_t
    windows = 0
    while ((stop is None or not stop.is_set())
           and now() - t0 < measure_wall):
        sim_t += window_sim_s
        t_d0 = now() if trace is not None else None
        if host_loop:
            s = sim.run_until(s, sim_t, chunk=chunk, check_invariants=True)
        else:
            s = sim.run_until_device(s, sim_t, chunk=chunk)
        if trace is not None:
            t_d1 = now()
            trace.span("window_dispatch", t_d0, t_d1 - t_d0,
                       args={"window": windows, "target_sim_t": sim_t})
        summary = summarize_leaves(_fetch_window_leaves(s))
        if trace is not None:
            trace.span("window_fetch", t_d1, now() - t_d1,
                       args={"window": windows})
        windows += 1
        on_window(summary, now() - t0)
    return s, windows


# ---------------------------------------------------------------------------
# activity-sweep tier (OVERSIM_BENCH_ACTIVITY)
# ---------------------------------------------------------------------------

def run_activity_sweep(fracs, *, n, overlay, window, inbox, pool_f, slots,
                       tick_impl, active_cap, chunk, platform,
                       warm_extra=5.0, reps=3):
    """ms/tick vs activity fraction — the sparse plane's success metric
    (ISSUE 16: steady ms/tick at N=65k with 1% activity within 2x of
    N=1k) becomes measurable the moment a chip is available, and runs
    on CPU today at small N.

    Each fraction f drives a KBRTest workload whose per-node test
    interval is ``window / f`` — the expected share of nodes with a due
    app event per tick is f (maintenance timers add a floor on top).
    Per fraction: fresh sim, device-resident warm past the init fill,
    then ``reps`` timed ``run_chunk`` dispatches; one
    ``activity_sweep`` JSON row per fraction (the orchestrator relays
    them into the standard atomic artifact; ``tick_impl`` rides the
    run manifest).  The measured awake share comes from the sparse
    plane's own ``awake_nodes`` counter when available."""
    import jax

    from oversim_tpu import churn as churn_mod
    from oversim_tpu import telemetry as telemetry_mod  # noqa: F401
    from oversim_tpu.apps import kbrtest
    from oversim_tpu.apps.kbrtest import KbrTestApp
    from oversim_tpu.common import lookup as lk_mod
    from oversim_tpu.engine import sim as sim_mod

    for frac in fracs:
        interval = window / max(frac, 1e-6)
        app = KbrTestApp(kbrtest.KbrTestParams(test_interval=interval))
        if overlay == "chord":
            from oversim_tpu.overlay.chord import ChordLogic
            logic = ChordLogic(app=app,
                               lcfg=lk_mod.LookupConfig(slots=slots))
        else:
            from oversim_tpu.overlay.kademlia import KademliaLogic
            logic = KademliaLogic(app=app,
                                  lcfg=lk_mod.LookupConfig(slots=slots,
                                                           merge=True))
        cp = churn_mod.ChurnParams(model="none", target_num=n,
                                   init_interval=20.0 / n,
                                   init_deviation=2.0 / n)
        ep = sim_mod.EngineParams(window=window, inbox_slots=inbox,
                                  pool_factor=pool_f,
                                  tick_impl=tick_impl,
                                  active_cap=active_cap)
        sim = sim_mod.Simulation(logic, cp, engine_params=ep)
        t0 = time.perf_counter()
        s = sim.init(seed=7)
        s = sim.run_until_device(s, cp.init_finished_time + warm_extra,
                                 chunk=chunk)
        jax.block_until_ready(s.t_now)
        warm_wall = time.perf_counter() - t0
        base = jax.device_get({"counters": s.counters, "tick": s.tick})
        t0 = time.perf_counter()
        for _ in range(reps):
            s = jax.block_until_ready(sim.run_chunk(s, chunk))
        wall = time.perf_counter() - t0
        cur = jax.device_get({"counters": s.counters, "tick": s.tick})
        ticks = int(cur["tick"]) - int(base["tick"])
        row = {
            "metric": "activity_sweep",
            "activity": frac,
            "test_interval": round(interval, 4),
            "ms_per_tick": round(wall / max(ticks, 1) * 1e3, 3),
            "ticks": ticks,
            "n": n,
            "overlay": overlay,
            "window": window,
            "tick_impl": tick_impl,
            "active_cap": active_cap,
            "platform": platform,
            "warm_wall_s": round(warm_wall, 1),
        }
        if "awake_nodes" in cur["counters"]:
            awake = (int(cur["counters"]["awake_nodes"])
                     - int(base["counters"]["awake_nodes"]))
            lanes = (int(cur["counters"]["lanes_stepped"])
                     - int(base["counters"]["lanes_stepped"]))
            row["awake_frac"] = round(awake / max(ticks, 1) / n, 4)
            row["lane_frac"] = round(lanes / max(ticks, 1) / n, 4)
        print(json.dumps(row), flush=True)
        sys.stderr.write("bench: activity %.4f -> %.3f ms/tick "
                         "(%d ticks)\n"
                         % (frac, row["ms_per_tick"], ticks))


# ---------------------------------------------------------------------------
# child: measure
# ---------------------------------------------------------------------------

def child_main():
    # OVERSIM_BENCH_PLATFORM=cpu asks for the host tier by name; unset,
    # the run is on whatever jax finds and REFUSES anything but a TPU
    # (below, once the device is known)
    platform = os.environ.get("OVERSIM_BENCH_PLATFORM") or None
    on_cpu = platform == "cpu"

    if on_cpu:
        # XLA-CPU -O0: compiles ~40% faster AND runs ~30% faster on these
        # graph shapes (tests/conftest.py measurements) — on the 1-core
        # box the CPU tier is compile-bound, and compile time is the
        # whole time-to-first-measurement problem
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_backend_optimization_level" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_backend_optimization_level=0"
                " --xla_llvm_disable_expensive_passes=true").strip()

    # hostcache.enable owns the pre-import ritual (zstandard poison, x64,
    # persistent cache at hostcache.cache_dir()).  persistent=False on CPU: this
    # box's XLA-CPU executable serialize() segfaults sporadically on big
    # sim-step graphs (tests/conftest.py note)
    from oversim_tpu import hostcache
    hostcache.enable(persistent=not on_cpu)
    import jax

    if platform is not None:
        jax.config.update("jax_platforms", platform)

    from oversim_tpu import churn as churn_mod
    from oversim_tpu.apps import kbrtest
    from oversim_tpu.apps.kbrtest import KbrTestApp
    from oversim_tpu.engine import sim as sim_mod
    from oversim_tpu.common import lookup as lk_mod

    # The TPU wins on BATCH: lookups/s = (lookups per tick) / (tick wall
    # cost), and the tick cost is op-issue-bound, nearly independent of
    # N until the VPU saturates — so drive a dense workload on a wide
    # overlay.  Kademlia is the reference's scale protocol (BASELINE.md
    # 1M-node rows).
    n = int(os.environ.get("OVERSIM_BENCH_N", "128" if on_cpu else "4096"))
    interval = float(os.environ.get("OVERSIM_BENCH_INTERVAL", 0.2))
    # window 0.2 s: the tick graph is op-issue-bound (~0.2 s/tick at
    # N=4096 regardless of window), so fewer, fatter ticks per sim-s is
    # the single biggest throughput lever — measured 12k lookups/s at
    # 0.2 vs ~3k at 0.05 (PERFORMANCE.md round-3 table)
    window = float(os.environ.get("OVERSIM_BENCH_WINDOW", 0.2))
    # short CPU warm-up (6 sim-s past init) + half-size CPU chunks:
    # time-to-first-measurement on the host tier must fit well inside
    # the deadline even when every graph compiles cold
    warm_extra = float(os.environ.get(
        "OVERSIM_BENCH_WARM", "6" if on_cpu else "25"))
    measure_wall = float(os.environ.get(
        "OVERSIM_BENCH_MEASURE_WALL", "45"))
    overlay = os.environ.get("OVERSIM_BENCH_OVERLAY", "kademlia")
    # TPU chunk 256 (was 64): with the device-resident window loop a
    # whole measurement window is one dispatch regardless, but fatter
    # scan chunks amortize the while_loop body launch (PERFORMANCE.md
    # lever #4); CPU keeps 32 (compile-bound tier)
    chunk = int(os.environ.get("OVERSIM_BENCH_CHUNK",
                               "32" if on_cpu else "256"))
    # OVERSIM_INVARIANTS=1 keeps the host-synced run_until loop with the
    # structural validator between chunks; default is the sync-free
    # device-resident loop (run_until_device)
    host_loop = bool(os.environ.get("OVERSIM_INVARIANTS")
                     or os.environ.get("OVERSIM_DEBUG_INVARIANTS"))

    # device acquisition under the elastic retry classes: a transient
    # backend failure here is retried, not a run-killer
    # (oversim_tpu/elastic/)
    from oversim_tpu import elastic
    retries = []

    def _on_retry(attempt, delay, exc):
        retries.append(str(exc))
        sys.stderr.write("bench: transient device failure (attempt %d, "
                         "retry in %.1fs): %s\n" % (attempt + 1, delay, exc))

    dev = elastic.with_retry(lambda: jax.devices()[0],
                             policy=elastic.RetryPolicy(attempts=3),
                             on_retry=_on_retry,
                             label="bench device acquisition")
    elastic_ann = {"attempts": len(retries) + 1,
                   **({"retried": retries} if retries else {})}
    sys.stderr.write("bench: platform=%s device=%s n=%d\n"
                     % (dev.platform, str(dev), n))
    if dev.platform != "tpu" and not on_cpu:
        # jax falls back to the CPU by itself when it finds no chip; a
        # number from there must not pass for a chip number
        sys.stderr.write("bench: no TPU found and OVERSIM_BENCH_PLATFORM="
                         "cpu was not asked for — refusing to measure\n")
        sys.exit(2)

    cp = churn_mod.ChurnParams(model="none", target_num=n,
                               init_interval=20.0 / n,
                               init_deviation=2.0 / n)
    app = KbrTestApp(kbrtest.KbrTestParams(test_interval=interval))
    # lookup concurrency: at `interval` issue rate with ~0.5-1 s lookup
    # durations, steady-state in-flight lookups per node ≈ duration /
    # interval — slots below that turn sends into instant failures
    slots = int(os.environ.get("OVERSIM_BENCH_SLOTS", 8))
    if overlay == "chord":
        from oversim_tpu.overlay.chord import ChordLogic
        logic = ChordLogic(app=app, lcfg=lk_mod.LookupConfig(slots=slots))
    else:
        from oversim_tpu.overlay.kademlia import KademliaLogic
        logic = KademliaLogic(app=app,
                              lcfg=lk_mod.LookupConfig(slots=slots,
                                                       merge=True))
    inbox = int(os.environ.get("OVERSIM_BENCH_INBOX", 8))
    # pool_factor 8: at interval 0.2 the in-flight message population is
    # ~4-6 per node; factor 4 overflowed (tens of thousands of drops →
    # RPC timeouts → failed lookups at 64% delivery)
    pool_f = int(os.environ.get("OVERSIM_BENCH_POOL", 8))
    # OVERSIM_BENCH_TELEMETRY=K: sample the KPI ring buffers every K
    # ticks inside the device loop (oversim_tpu/telemetry.py) — the
    # window loop still does ONE dispatch + ONE device_get; the series
    # is emitted as a telemetry_series side-channel line after the run
    tel_ticks = int(os.environ.get("OVERSIM_BENCH_TELEMETRY", "0"))
    tel_window = int(os.environ.get("OVERSIM_BENCH_TELEMETRY_WINDOW", "256"))
    from oversim_tpu.config import scenario as scenario_mod
    # OVERSIM_BENCH_TICK_IMPL: dense (full-N oracle, default) | sparse
    # (active-set plane — tick cost bounded by traffic, not N;
    # engine/sim.py _step_sparse).  OVERSIM_BENCH_ACTIVE_CAP bounds the
    # sparse lane count (0 = auto).
    tick_impl = scenario_mod.resolve_tick_impl(
        os.environ.get("OVERSIM_BENCH_TICK_IMPL", "dense"))
    active_cap = int(os.environ.get("OVERSIM_BENCH_ACTIVE_CAP", "0"))
    # OVERSIM_BENCH_NODE_SHARDS=K: shard the node axis over K devices
    # (2D replica x node mesh, parallel/mesh.py make_mesh_2d).  0/1 =
    # replicated node axis (the pre-2D behavior).  Placement refuses
    # loudly when K does not divide N and the pool, or when fewer than
    # K devices exist — a silently-replicated "sharded" run would
    # poison the ladder.
    node_shards = int(os.environ.get("OVERSIM_BENCH_NODE_SHARDS", "0"))
    from oversim_tpu import telemetry as telemetry_mod
    ep = sim_mod.EngineParams(window=window, inbox_slots=inbox,
                              pool_factor=pool_f,
                              tick_impl=tick_impl,
                              active_cap=active_cap,
                              telemetry=telemetry_mod.TelemetryParams(
                                  sample_ticks=tel_ticks,
                                  window=tel_window))
    sim = sim_mod.Simulation(logic, cp, engine_params=ep)

    # OVERSIM_BENCH_TRACE=path: Perfetto/Chrome-trace JSON of the
    # window dispatch/fetch spans, rewritten atomically after every
    # window
    trace_path = os.environ.get("OVERSIM_BENCH_TRACE")
    trace = telemetry_mod.PerfettoTrace("bench") if trace_path else None

    # OVERSIM_BENCH_REPLICAS=S: campaign tier — S independent replicas
    # as ONE vmapped program (oversim_tpu/campaign/), replica axis
    # sharded when S divides the device count.  The campaign run loop is
    # device-resident only (no host-synced invariant tier).
    replicas = int(os.environ.get("OVERSIM_BENCH_REPLICAS", "0"))

    # Mesh layout string ("RxK") for the manifest and every artifact
    # row — ladder rows from different mesh shapes must never silently
    # merge.
    if node_shards > 1:
        _avail = len(jax.devices())
        if replicas >= 1:
            _r_fit = max(_avail // node_shards, 1)
            r_dev = max(d for d in range(1, min(_r_fit, replicas) + 1)
                        if replicas % d == 0)
            mesh_layout = "%dx%d" % (r_dev, node_shards)
        else:
            r_dev = 1
            mesh_layout = "1x%d" % node_shards
    else:
        r_dev = 0
        mesh_layout = None

    # AOT pre-warm: deserialize-or-export the entry this run will
    # compile, so a second process on the same config skips trace+lower
    # entirely (oversim_tpu/aot/).  Default ON in the bench drivers
    # (ROADMAP Speed 2 — time to the first window); OVERSIM_AOT=0 opts
    # out.  The report rides the manifest and
    # the Perfetto trace.
    from oversim_tpu import aot
    from oversim_tpu.analysis import contracts as contracts_mod
    aot_ctx = contracts_mod.EntryContext(
        n=n, overlay=overlay, window=window, inbox=inbox,
        pool_factor=pool_f, replicas=max(replicas, 1), tel_ticks=tel_ticks,
        chunk=chunk)
    aot_on = aot.enabled_by_env({"OVERSIM_AOT":
                                 os.environ.get("OVERSIM_AOT", "1")})
    aot_rep = aot.warmup(("campaign_tick",) if replicas >= 1
                         else ("run_until_device",), ctx=aot_ctx,
                         enabled=aot_on)
    if trace is not None and aot_rep["enabled"]:
        aot.trace_spans(trace, aot_rep)

    # Live observability plane (OVERSIM_METRICS_PORT / OVERSIM_FLIGHT):
    # metrics endpoint + flight recorder, fed strictly from the
    # on_window host sync below — started before the manifest so the
    # bound port rides the manifest's artifacts
    from oversim_tpu.obs import runtime as obs_runtime
    from oversim_tpu.obs import xprof as xprof_mod
    metrics_port = os.environ.get("OVERSIM_METRICS_PORT")
    flight_path = os.environ.get("OVERSIM_FLIGHT") or None
    obs = None
    if metrics_port is not None or flight_path is not None:
        obs = obs_runtime.RunObserver(
            role="bench",
            port=int(metrics_port) if metrics_port is not None else None,
            flight_path=flight_path)
        obs.set_static(n=n, overlay=overlay, tick_impl=tick_impl,
                       replicas=int(os.environ.get(
                           "OVERSIM_BENCH_REPLICAS", "0")),
                       node_shards=node_shards)
        obs.start()
        obs.record("aot", enabled=aot_rep.get("enabled"),
                   artifact_hits=aot_rep.get("artifact_hits"),
                   fresh_compiles=aot_rep.get("fresh_compiles"))

    # RunManifest side-channel line — the orchestrator attaches it to
    # the artifact's top-level "manifest" key
    print(json.dumps(telemetry_mod.run_manifest(
        config={"n": n, "overlay": overlay, "interval": interval,
                "window": window, "inbox": inbox, "pool_factor": pool_f,
                "tick_impl": tick_impl, "active_cap": active_cap,
                "chunk": chunk, "slots": slots,
                "telemetry_sample_ticks": tel_ticks,
                "telemetry_window": tel_window,
                "replicas": os.environ.get("OVERSIM_BENCH_REPLICAS", "0"),
                "node_shards": node_shards, "mesh": mesh_layout},
        artifacts={"artifact": os.environ.get("OVERSIM_BENCH_ARTIFACT"),
                   "trace": trace_path,
                   "metrics_port": obs.port if obs is not None else None,
                   "flight": flight_path,
                   "xprof": xprof_mod.xprof_dir()},
        extra={"aot": aot_rep, "elastic": elastic_ann})), flush=True)

    # OVERSIM_BENCH_ACTIVITY="0.01,0.1,1.0": the activity-sweep tier
    # REPLACES the measurement loop — one ms/tick row per fraction into
    # the same atomic artifact (tick_impl rides the manifest above)
    activity_env = os.environ.get("OVERSIM_BENCH_ACTIVITY")
    if activity_env:
        fracs = [float(x) for x in activity_env.split(",") if x.strip()]
        run_activity_sweep(
            fracs, n=n, overlay=overlay, window=window, inbox=inbox,
            pool_f=pool_f, slots=slots, tick_impl=tick_impl,
            active_cap=active_cap, chunk=chunk,
            platform=dev.platform)
        if obs is not None:
            obs.close()
        if trace is not None:
            trace.write(trace_path)
        return
    camp = None
    summarize_leaves = _summary_from_leaves
    if replicas >= 1:
        from oversim_tpu.campaign import Campaign, CampaignParams
        camp = Campaign(sim, CampaignParams(replicas=replicas, base_seed=7))
        summarize_leaves = _campaign_summary_from_leaves
        if host_loop:
            sys.stderr.write("bench: OVERSIM_INVARIANTS ignored on the "
                             "campaign tier (device loop only)\n")
            host_loop = False

    warm_until = cp.init_finished_time + warm_extra
    t0 = time.perf_counter()
    if camp is None:
        s = sim.init(seed=7)
        runner = sim
        if node_shards > 1:
            # 2D (1 x K) placement: GSPMD partitions the node axis of
            # pool/logic leaves; the run loop is unchanged.  shard_state_2d
            # raises when K does not divide N / the pool or devices are
            # short — fail the run rather than measure a replicated mesh.
            from oversim_tpu.parallel import mesh as mesh_mod
            mesh2d = mesh_mod.make_mesh_2d(1, node_shards)
            s = mesh_mod.shard_state_2d(s, mesh2d)
            sys.stderr.write("bench: node axis sharded over %d device(s) "
                             "(mesh %s)\n" % (node_shards, mesh_layout))
    else:
        s = camp.init()
        runner = camp
        from oversim_tpu.parallel import mesh as mesh_mod
        if node_shards > 1:
            # 2D (R x K) placement: replica axis over the largest
            # divisor of S that still fits, node axis over K
            mesh2d = mesh_mod.make_mesh_2d(r_dev, node_shards)
            s = mesh_mod.shard_campaign_state_2d(s, mesh2d)
            sys.stderr.write("bench: campaign S=%d on mesh %s\n"
                             % (camp.s, mesh_layout))
        else:
            # shard over the LARGEST device count that divides S (even
            # split keeps the replica axis collective-free)
            avail = len(jax.devices())
            n_dev = max(d for d in range(1, min(avail, camp.s) + 1)
                        if camp.s % d == 0)
            if n_dev > 1:
                mesh = mesh_mod.make_replica_mesh(n_dev)
                s = mesh_mod.shard_campaign_state(s, mesh)
            sys.stderr.write("bench: campaign S=%d over %d device(s)\n"
                             % (camp.s, n_dev))
    if host_loop:
        s = sim.run_until(s, warm_until, chunk=chunk, check_invariants=True)
    else:
        s = runner.run_until_device(s, warm_until, chunk=chunk)
    base = summarize_leaves(_fetch_window_leaves(s))
    warm_wall = time.perf_counter() - t0
    sys.stderr.write("bench: warmup (%.0f sim-s) took %.1fs wall\n"
                     % (warm_until, warm_wall))
    sys.stderr.write("bench: post-warm counters %r alive=%d\n"
                     % (base["_engine"], base["_alive"]))

    # measure in wall-clock windows (each ONE device dispatch + ONE host
    # sync, run_measurement_windows), emitting an updated JSON line after
    # each — the orchestrator relays them, the driver takes the last
    windows_seen = [0]

    def on_window(out, wall):
        if obs is not None:
            obs.on_window(windows_seen[0], out, wall)
            windows_seen[0] += 1
        delivered = out["kbr_delivered"] - base["kbr_delivered"]
        sent = out["kbr_sent"] - base["kbr_sent"]
        rate = delivered / wall if wall > 0 else 0.0
        # HARD health gate (VERDICT r4 next-step #3): a window may only
        # become the record at ≥95% delivery with zero overflow
        # counters — lost lookups are cheap, so a lossy config could
        # otherwise post a big number legitimately per the old rules
        overflow = {k: v - base["_engine"].get(k, 0)
                    for k, v in out["_engine"].items()
                    if ("overflow" in k or "deferred" in k)
                    and v - base["_engine"].get(k, 0) > 0}
        delivery = delivered / sent if sent else 0.0
        healthy = sent > 0 and delivery >= 0.95 and not overflow
        shape = (f"{n} nodes" if camp is None
                 else f"{n} nodes x {camp.s} replicas")
        unit = (f"lookups/s ({overlay} {shape}, {dev.platform}, "
                f"delivery {delivered}/{sent}, {out['_ticks']} ticks, "
                f"{wall:.1f}s wall)")
        extra = {"delivery": round(delivery, 4),
                 "tick_impl": tick_impl,
                 "node_shards": node_shards,
                 "mesh": mesh_layout,
                 "measured_utc": time.strftime(
                     "%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
        if camp is not None:
            extra["replicas"] = camp.s
            extra["warm_wall_s"] = round(warm_wall, 1)
        line = _json_line(rate, unit, healthy=healthy, extra=extra)
        print(line, flush=True)
        sys.stderr.write("bench: %.0f lookups/s after %.1fs (%d/%d) "
                         "healthy=%s counters=%r\n"
                         % (rate, wall, delivered, sent, healthy,
                            out["_engine"]))
        if trace is not None:
            # atomic rewrite per window: a deadline SIGKILL leaves the
            # trace of every completed window
            trace.write(trace_path)

    # graceful SIGTERM: finish the in-flight window (stop is only
    # checked at window boundaries), fall through to the normal
    # telemetry/trace finish, and exit 0 — the orchestrator forwards
    # its own SIGTERM here, so a polite preemption ends with a complete
    # artifact instead of the SIGKILL-shaped partial one
    import signal as signal_mod
    import threading
    stop_evt = threading.Event()

    def _on_term(*_):
        stop_evt.set()
        if obs is not None:
            obs.draining()

    signal_mod.signal(signal_mod.SIGTERM, _on_term)

    # OVERSIM_XPROF=dir: on-chip capture of exactly the measurement
    # windows — host metrics see window walls, the xprof sees inside them
    with xprof_mod.capture("bench_measure") as xprof_info:
        s, _ = run_measurement_windows(
            runner, s, start_sim_t=warm_until, window_sim_s=chunk * window,
            measure_wall=measure_wall, chunk=chunk, on_window=on_window,
            host_loop=host_loop, summarize_leaves=summarize_leaves,
            trace=trace, stop=stop_evt)
    if xprof_info["dir"]:
        print(json.dumps({"metric": "xprof_capture",
                          "kind": "xprof_capture", **xprof_info}),
              flush=True)
        if obs is not None:
            obs.record("xprof_capture", **xprof_info)

    ckpt_path = os.environ.get("OVERSIM_BENCH_CHECKPOINT")
    if ckpt_path:
        # final checkpoint (atomic, reshard-aware meta when a campaign
        # ran) — a SIGTERMed bench is resumable, not just recorded
        from oversim_tpu import checkpoint as ckpt_mod
        meta = {"bench": {"sigterm": stop_evt.is_set()}}
        if camp is not None:
            meta["campaign"] = camp.describe()
        ckpt_mod.save(ckpt_path, s, meta=meta)
        sys.stderr.write("bench: final checkpoint -> %s (sigterm=%s)\n"
                         % (ckpt_path, stop_evt.is_set()))

    if obs is not None:
        obs.close(dump_tail=stop_evt.is_set())

    if tel_ticks > 0 and getattr(s, "telemetry", None) is not None:
        # KPI time series off the ring buffers — for the campaign tier
        # the stacked [S, W, ...] rings become per-replica series with
        # cross-replica CI bands (stats.series_summary)
        if camp is None:
            print(json.dumps(telemetry_mod.series_report(s.telemetry)),
                  flush=True)
        else:
            rec = telemetry_mod.ensemble_series(jax.device_get(s.telemetry))
            rec["metric"] = "telemetry_series"
            print(json.dumps(rec), flush=True)
    if trace is not None:
        trace.write(trace_path)


def main():
    if os.environ.get("OVERSIM_BENCH_CHILD") != "1":
        sys.exit(orchestrate())
    child_main()


if __name__ == "__main__":
    main()
