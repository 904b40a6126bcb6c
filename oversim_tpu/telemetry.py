"""Device-resident telemetry plane: in-graph KPI time series + exporters.

The reference streams every statistic through the GlobalStatistics
singleton as it happens — cOutVector rows into ``results/*.vec`` plus
finish()-time scalars (GlobalStatistics.cc recordScalar/addStdDev) — so
a run is observable while it runs.  The TPU build's device-resident run
loops (``run_chunk`` / ``run_until_device``, one dispatch per bench
window) made a million-tick window a black box between dispatch and
fetch: only the END-of-window accumulator values came back.

This module restores the time axis WITHOUT giving up the one-dispatch /
one-``device_get`` contract: preallocated ``[W, ...]`` ring buffers ride
as one extra ``SimState`` leaf (``SimState.telemetry``) and a sample is
folded in every ``TelemetryParams.sample_ticks`` ticks INSIDE the jitted
tick (engine/sim.py ``_phase_alloc_stats``).  Each sample snapshots

  * the cumulative stats accumulators of the tapped keys ("s:" [5]
    accumulators, "h:" histograms, "c:" counters — the app's
    ``kpi_spec()`` registry picks the taps, see apps/base.py),
  * every engine drop/overflow counter (sim.ENGINE_COUNTERS),
  * the alive population, sim time and tick number.

The write is a gated scatter (``buf.at[idx].set(v, mode="drop")`` with
``idx == W`` on non-sample ticks — out of bounds drops to a no-op), so
telemetry adds a bounded number of scatters and ZERO sorts/collectives
to the tick (pinned by scripts/hlo_breakdown.py --telemetry), consumes
no rng, and leaves every non-telemetry leaf bit-identical to a
telemetry-off run (tests/test_zz_telemetry_identity.py).  Under the
campaign vmap the buffers stack to ``[S, W, ...]`` and shard over the
replica axis like any other leaf — per-replica KPI series with
cross-replica CI bands via ``stats.series_summary``.

Host-side exporters (all dependency-free):

  * ``kpi_series`` — ring unwrap into named, time-ordered series
    (``name.mean`` / ``name.count`` for scalar accumulators, raw counts
    for counters, ``engine.*`` for the drop counters, ``aliveNodes``,
    derived ``kbr_delivery_ratio``) + raw histogram snapshots;
  * ``write_vec`` — the series as OMNeT++ .vec rows through
    recorder.py's writer (native vecwriter.c or the byte-identical
    Python fallback);
  * ``PerfettoTrace`` — Chrome-trace/Perfetto JSON (``traceEvents``)
    for bench window dispatch/fetch spans, per-tick phase breakdowns
    (``add_profile``) and KPI counter tracks
    (``add_series``); load in ui.perfetto.dev or chrome://tracing;
  * ``run_manifest`` — the unified RunManifest (config hash, mesh/
    sharding layout, HLO op-budget results, git rev, artifact paths)
    attached to every bench/campaign artifact
    (bench.ArtifactWriter.set_manifest).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import subprocess

import jax
import jax.numpy as jnp
import numpy as np

I32 = jnp.int32
I64 = jnp.int64
F64 = jnp.float64
NS = 1_000_000_000


@dataclasses.dataclass(frozen=True)
class TelemetryParams:
    """Static telemetry shape (``**.telemetry.*`` ini keys).

    ``sample_ticks``  — snapshot period in ticks; 0 (default) disables
                        telemetry entirely (SimState.telemetry = None,
                        zero graph cost, bit-identical state layout).
    ``window``        — W, the ring capacity: the LAST ``window``
                        samples survive (older ones are overwritten
                        in ring order).
    ``include``       — stat-key substring filters; empty = the app's
                        ``kpi_spec()`` registry (apps/base.py), or every
                        stats key when the app declares none.
    """

    sample_ticks: int = 0
    window: int = 256
    include: tuple = ()


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TelemetryState:
    """Ring buffers carried as a SimState leaf.  ``n`` counts samples
    taken so far; sample ``j`` (0-based) lives at row ``j % W`` — the
    ring holds the last ``min(n, W)`` samples."""

    n: jnp.ndarray            # i64 scalar — total samples taken
    t_ns: jnp.ndarray         # [W] i64 — sim time of each sample
    tick: jnp.ndarray         # [W] i64 — tick number of each sample
    alive: jnp.ndarray        # [W] i64 — alive population
    series: dict              # stats key -> [W, *leaf.shape] snapshots
    counters: dict            # engine counter name -> [W] i64


def resolve_taps(stats: dict, tp: TelemetryParams, app=None) -> tuple:
    """Pick which stats keys the ring snapshots.

    Priority: explicit ``include`` substring filters > the app's
    ``kpi_spec()`` registry (names without the "s:"/"h:"/"c:" class
    prefix) > every key.  An app registry that matches nothing falls
    back to every key rather than recording an empty plane."""
    keys = tuple(stats)
    if tp.include:
        sel = tuple(k for k in keys if any(p in k for p in tp.include))
        return sel or keys
    if app is not None and hasattr(app, "kpi_spec"):
        want = set(app.kpi_spec())
        sel = tuple(k for k in keys if k[2:] in want)
        return sel or keys
    return keys


def init(stats: dict, counter_names, tp: TelemetryParams,
         app=None) -> TelemetryState | None:
    """Zeroed ring buffers for the resolved taps; None when disabled."""
    if tp is None or tp.sample_ticks <= 0:
        return None
    w = int(tp.window)
    if w < 1:
        raise ValueError(f"telemetry.window must be >= 1, got {w}")
    taps = resolve_taps(stats, tp, app=app)
    return TelemetryState(
        n=jnp.zeros((), I64),
        t_ns=jnp.zeros((w,), I64),
        tick=jnp.zeros((w,), I64),
        alive=jnp.zeros((w,), I64),
        series={k: jnp.zeros((w,) + stats[k].shape, stats[k].dtype)
                for k in taps},
        counters={name: jnp.zeros((w,), I64) for name in counter_names},
    )


def fold(tel: TelemetryState | None, tp: TelemetryParams, *, t_end, tick,
         alive, stats: dict, counters: dict):
    """In-graph sample point (called from ``_phase_alloc_stats`` with
    the END-of-tick values).  On non-sample ticks the write index is W —
    ``mode="drop"`` turns every scatter into a no-op — so the only
    divergent state is ``n``.  No rng, no sorts, no collectives."""
    if tel is None or tp is None or tp.sample_ticks <= 0:
        return tel
    w = tel.t_ns.shape[-1]
    do = (tick % jnp.int64(tp.sample_ticks)) == 0
    idx = jnp.where(do, (tel.n % w).astype(I32), jnp.int32(w))
    put = lambda buf, v: buf.at[idx].set(  # noqa: E731
        jnp.asarray(v).astype(buf.dtype), mode="drop")
    return TelemetryState(
        n=tel.n + do.astype(I64),
        t_ns=put(tel.t_ns, t_end),
        tick=put(tel.tick, tick),
        alive=put(tel.alive, jnp.sum(alive)),
        series={k: put(buf, stats[k]) for k, buf in tel.series.items()},
        counters={k: put(buf, counters[k])
                  for k, buf in tel.counters.items()},
    )


# ---------------------------------------------------------------------------
# host-side: ring unwrap + KPI series
# ---------------------------------------------------------------------------

def _ring_order(n: int, w: int) -> np.ndarray:
    """Row indices oldest-first for a ring that has taken n samples."""
    if n <= w:
        return np.arange(n)
    return (n + np.arange(w)) % w


def unwrap(tel) -> dict:
    """Time-order a (device_get of a) TelemetryState's rings.

    Returns {"k": samples kept, "n": samples taken, "t_ns"/"tick"/
    "alive": [K] arrays, "series": {key: [K, ...]}, "counters":
    {name: [K]}} — oldest sample first."""
    n = int(np.asarray(tel.n))
    w = int(np.asarray(tel.t_ns).shape[-1])
    order = _ring_order(n, w)
    take = lambda buf: np.asarray(buf)[order]  # noqa: E731
    return {
        "k": len(order), "n": n,
        "t_ns": take(tel.t_ns), "tick": take(tel.tick),
        "alive": take(tel.alive),
        "series": {k: take(v) for k, v in tel.series.items()},
        "counters": {k: take(v) for k, v in tel.counters.items()},
    }


def kpi_series(tel) -> dict:
    """Flat, named KPI time series off a fetched TelemetryState.

    Output: {"k", "n", "t_s": [K], "tick": [K], "series":
    {flat_name: float [K]}, "hists": {name: int [K, B]}}.  Scalar
    accumulators ("s:name", cumulative (n, sum, sumsq, min, max))
    become ``name.mean`` (NaN until the first event) and ``name.count``;
    counters keep their name; engine counters get an ``engine.`` prefix;
    the alive population is ``aliveNodes``; ``kbr_delivery_ratio`` is
    derived when the KBRTest counters are tapped.  Histogram snapshots
    stay 2-D in ``hists`` (per-sample bin counts)."""
    u = unwrap(tel)
    series = {"aliveNodes": u["alive"].astype(float)}
    hists = {}
    for key, v in u["series"].items():
        name = key[2:]
        v = np.asarray(v)
        if key.startswith("s:"):
            cnt = v[:, 0]
            with np.errstate(invalid="ignore", divide="ignore"):
                series[name + ".mean"] = np.where(
                    cnt > 0, v[:, 1] / np.maximum(cnt, 1.0), np.nan)
            series[name + ".count"] = cnt
        elif key.startswith("h:"):
            hists[name] = v
        else:
            series[name] = v.astype(float)
    for name, v in u["counters"].items():
        series["engine." + name] = np.asarray(v, float)
    if "kbr_sent" in series and "kbr_delivered" in series:
        sent = series["kbr_sent"]
        with np.errstate(invalid="ignore", divide="ignore"):
            series["kbr_delivery_ratio"] = np.where(
                sent > 0, series["kbr_delivered"] / np.maximum(sent, 1.0),
                np.nan)
    return {"k": u["k"], "n": u["n"],
            "t_s": u["t_ns"].astype(float) / NS,
            "tick": u["tick"], "series": series, "hists": hists}


def series_report(tel) -> dict:
    """JSON-safe form of ``kpi_series`` (lists, NaN -> None) — the
    per-window/artifact record shape."""
    ks = kpi_series(tel)
    clean = lambda a: [None if (isinstance(x, float) and x != x)  # noqa: E731
                       else float(x) for x in np.asarray(a, float)]
    return {
        "metric": "telemetry_series", "samples": ks["k"],
        "samples_taken": ks["n"],
        "t_s": clean(ks["t_s"]),
        "tick": np.asarray(ks["tick"]).astype(int).tolist(),
        "series": {k: clean(v) for k, v in ks["series"].items()},
        "hists": {k: np.asarray(v).astype(int).tolist()
                  for k, v in ks["hists"].items()},
    }


def write_vec(tel_or_series, path, run_id: str = "telemetry-0",
              module: str = "OverSimTpu.telemetry") -> int:
    """Flush KPI series as OMNeT++ .vec rows through recorder.py's
    writer (native vecwriter.c when it builds, byte-identical Python
    fallback otherwise).  Accepts a TelemetryState or a ``kpi_series``
    dict; returns the number of vectors written.  Histogram snapshots
    are .vec-inexpressible (2-D) and are left to the JSON exporters."""
    from oversim_tpu import recorder
    ks = (tel_or_series if isinstance(tel_or_series, dict)
          else kpi_series(tel_or_series))
    w = recorder._writer(path, run_id)
    try:
        t = np.asarray(ks["t_s"], float)
        for name in sorted(ks["series"]):
            vid = w.declare(module, name)
            w.rows(vid, t, np.nan_to_num(
                np.asarray(ks["series"][name], float)))
    finally:
        w.close()
    return len(ks["series"])


# ---------------------------------------------------------------------------
# cross-replica ensemble series (campaign tier)
# ---------------------------------------------------------------------------

def ensemble_series(tel_stacked, confidence: float = 0.95) -> dict:
    """Per-replica KPI series + cross-replica CI bands off a fetched
    ``[S, W, ...]``-stacked TelemetryState (campaign runner).

    Replicas tick on independent event horizons but share the sampling
    cadence (every ``sample_ticks`` ticks), so sample index j is
    comparable across replicas; series are truncated to the shortest
    replica before banding.  Returns {"enabled", "samples", "replicas",
    "tick": [K], "t_s": per-replica [S][K], "per_replica":
    {name: [S][K]}, "bands": {name: stats.series_summary schema}}."""
    from oversim_tpu import stats as stats_mod
    s_count = int(np.asarray(tel_stacked.n).shape[0])
    per = [kpi_series(jax.tree.map(lambda x: np.asarray(x)[r], tel_stacked))
           for r in range(s_count)]
    k = min(p["k"] for p in per)
    names = sorted(per[0]["series"])
    clean = lambda a: [None if (isinstance(x, float) and x != x)  # noqa: E731
                       else float(x) for x in np.asarray(a, float)]
    stacked = {name: np.stack([p["series"][name][:k] for p in per])
               for name in names}
    return {
        "enabled": True, "samples": k, "replicas": s_count,
        "confidence": confidence,
        "tick": (np.asarray(per[0]["tick"][:k]).astype(int).tolist()
                 if k else []),
        "t_s": [clean(p["t_s"][:k]) for p in per],
        "per_replica": {name: [clean(row) for row in stacked[name]]
                        for name in names},
        "bands": {name: stats_mod.series_summary(stacked[name], confidence)
                  for name in names},
    }


# ---------------------------------------------------------------------------
# Perfetto / Chrome-trace exporter
# ---------------------------------------------------------------------------

class PerfettoTrace:
    """Chrome-trace-JSON builder (the format ui.perfetto.dev and
    chrome://tracing both load).  Timestamps are absolute seconds
    (``time.perf_counter`` readings); the writer rebases to the first
    event so traces start at 0."""

    def __init__(self, process_name: str = "oversim-tpu"):
        self.events = []
        self.process_name = process_name

    def span(self, name, t0_s, dur_s, *, tid=0, pid=0, args=None):
        """Complete event ("ph": "X"): a [t0, t0+dur) slice."""
        ev = {"name": name, "ph": "X", "ts": float(t0_s) * 1e6,
              "dur": max(float(dur_s), 0.0) * 1e6, "pid": pid, "tid": tid}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, name, t_s, *, tid=0, pid=0, args=None):
        ev = {"name": name, "ph": "i", "ts": float(t_s) * 1e6,
              "pid": pid, "tid": tid, "s": "t"}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def counter(self, name, t_s, value, *, pid=0):
        self.events.append({"name": name, "ph": "C",
                            "ts": float(t_s) * 1e6, "pid": pid,
                            "args": {name: float(value)}})

    def add_profile(self, report: dict, *, t0_s: float = 0.0, tid=1):
        """Lay per-tick phase durations out as back-to-back spans (one
        track per call): ``report["phase_ticks_ms"]``, a list of
        {phase: ms} dicts, one a tick, when present, else one averaged
        tick from ``report["phase_ms_per_tick"]`` (the last line of
        ``benchmark/phases.py`` carries one: a device trace reduced by
        the tick program's named scopes)."""
        ticks = report.get("phase_ticks_ms")
        if not ticks:
            avg = report.get("phase_ms_per_tick")
            ticks = [avg] if avg else []
        t = t0_s
        for i, phases in enumerate(ticks):
            for phase, ms in phases.items():
                self.span(f"tick.{phase}", t, ms / 1e3, tid=tid,
                          args={"tick_index": i})
                t += ms / 1e3
        return t

    def add_series(self, ks: dict, *, pid=2,
                   names: tuple | None = None):
        """KPI counter tracks from a ``kpi_series`` dict — the time axis
        is SIMULATED seconds (its own pid so sim-time tracks don't
        interleave with wall-clock spans).  An ``ensemble_series``
        record (``bands``) emits ``name.mean`` plus ``name.ci_lo`` /
        ``name.ci_hi`` band-edge tracks instead of raw values."""
        if "bands" in ks:
            t = np.asarray(ks["t_s"][0] if ks.get("t_s") else [], float)
            for name in (names or sorted(ks["bands"])):
                b = ks["bands"][name]
                mean = np.asarray(b["mean"], float)
                ci = b.get("ci")
                ci = np.asarray(ci if ci is not None
                                else [np.nan] * len(mean), float)
                for ti, m, c in zip(t, mean, ci):
                    if m != m:                     # skip NaN gaps
                        continue
                    self.counter(f"{name}.mean", ti, m, pid=pid)
                    if c == c:
                        self.counter(f"{name}.ci_lo", ti, m - c, pid=pid)
                        self.counter(f"{name}.ci_hi", ti, m + c, pid=pid)
            return
        t = np.asarray(ks["t_s"], float)
        for name in (names or sorted(ks["series"])):
            vals = np.asarray(ks["series"][name], float)
            for ti, vi in zip(t, vals):
                if vi == vi:                       # skip NaN gaps
                    self.counter(name, ti, vi, pid=pid)

    def to_dict(self) -> dict:
        base = min((e["ts"] for e in self.events), default=0.0)
        events = []
        for e in self.events:
            e = dict(e)
            e["ts"] = round(e["ts"] - base, 3)
            events.append(e)
        meta = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                 "args": {"name": name}}
                for pid, name in ((0, self.process_name),
                                  (2, "sim-time KPIs"))
                if any(e.get("pid") == pid for e in events)]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def write(self, path) -> None:
        """Atomic write (tmp + replace) so a kill mid-run leaves the
        previous complete trace."""
        tmp = str(path) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(), f)
        os.replace(tmp, str(path))


# ---------------------------------------------------------------------------
# RunManifest
# ---------------------------------------------------------------------------

def config_hash(config) -> str:
    """Stable sha256 over a JSON-serializable config mapping (sorted
    keys, default=str for dataclasses/paths)."""
    blob = json.dumps(config, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def git_rev(root=None) -> str | None:
    try:
        r = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10,
            cwd=root or os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
        return r.stdout.strip() or None if r.returncode == 0 else None
    except (OSError, subprocess.TimeoutExpired):
        return None


def mesh_layout(mesh=None) -> dict:
    """Mesh/sharding description for the manifest; with no mesh, the
    visible-device inventory."""
    out = {}
    try:
        devs = jax.devices()
        out["devices"] = len(devs)
        out["platform"] = devs[0].platform if devs else None
    except Exception:  # noqa: BLE001 — manifests must never kill a run
        pass
    if mesh is not None:
        out["mesh_axes"] = {str(k): int(v)
                            for k, v in mesh.shape.items()}
    return out


def analysis_verdict(path=None):
    """Compact graph-contract verdict for the manifest's ``hlo_budget``
    field, read from the analyzer's JSON document (``scripts/analyze.py
    --json``).  ``path`` defaults to $OVERSIM_ANALYSIS_VERDICT — which
    scripts/run_suite.sh exports after its analyze gate — so every
    bench/campaign/service artifact records which contract revision its
    graphs passed.  None when no verdict document is available."""
    import json
    import os
    path = path or os.environ.get("OVERSIM_ANALYSIS_VERDICT")
    if not path or not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    from oversim_tpu.analysis.findings import verdict_summary
    return verdict_summary(doc)


def env_knobs(environ=None) -> dict:
    """Every effective ``OVERSIM_*`` environment knob, sorted — the
    run-shaping side channel (OVERSIM_AOT, OVERSIM_BENCH_*,
    OVERSIM_XPROF, OVERSIM_METRICS_PORT, ...) that the flags/ini config
    does NOT capture, so a manifest alone reproduces the run."""
    env = os.environ if environ is None else environ
    return {k: env[k] for k in sorted(env) if k.startswith("OVERSIM")}


def run_manifest(*, config=None, mesh=None, hlo_budget=None,
                 artifacts=None, extra=None) -> dict:
    """The unified RunManifest attached to every bench/campaign
    artifact: enough provenance to re-run or audit the
    measurement — config hash (and the config itself), mesh/sharding
    layout, HLO op-budget results, git rev, artifact paths, effective
    OVERSIM_* env knobs, runtime versions.  ``hlo_budget`` defaults to
    :func:`analysis_verdict` (the graph-contract analyzer's verdict
    document, when one is present)."""
    import platform as _platform
    if hlo_budget is None:
        hlo_budget = analysis_verdict()
    man = {
        "metric": "run_manifest",
        "kind": "run_manifest",
        "git_rev": git_rev(),
        "config": config,
        "config_hash": config_hash(config) if config is not None else None,
        "mesh": mesh_layout(mesh),
        "hlo_budget": hlo_budget,
        "artifacts": artifacts or {},
        "env": env_knobs(),
        "versions": {"python": _platform.python_version(),
                     "jax": getattr(jax, "__version__", None)},
    }
    if extra:
        man.update(extra)
    return man
