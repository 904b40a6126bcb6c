"""Scale ladder + 10k churn smoke (VERDICT r3 next-step #2).

Two jobs:

  * ``--ladder``: measured-throughput rows at growing N (default
    8192/16384/65536 Kademlia, no churn — BASELINE.md configs #2-#4
    envelope; thesis.ini:16-36 is the reference's 20k-node proof), each
    a short warm + measure window like bench.py;
  * default / ``--n N``: the LifetimeChurn smoke at N (default 10k)
    proving the static bounds (pool/outbox/inbox, frontier/visited)
    hold at driver-config scale — all overflow counters must be zero.

Robustness (the round-3 artifact was a 0-byte file): the process is
deadline-guarded (OVERSIM_SCALE_DEADLINE, default 1500 s), prints a
provisional JSON line immediately and an updated line after every
completed row, and ALWAYS exits 0 — the last line is the result.
Completed rows are also appended to ``scale_cache.json`` next to the
repo root so a later stalled run still has committed evidence.
OVERSIM_SCALE_ARTIFACT=path additionally persists every emitted record
to ``path`` with an atomic tmp+rename after EVERY row (bench.py's
ArtifactWriter) — a deadline SIGKILL leaves a valid partial artifact.

Usage:  python scripts/scale_smoke.py [--ladder] [--n 10000]
        [--overlay kademlia|chord] [--t 600] [--platform cpu]
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
CACHE = ROOT / "scale_cache.json"
_T0 = time.time()
DEADLINE_S = int(os.environ.get("OVERSIM_SCALE_DEADLINE", 1500))


def _emit(obj):
    print(json.dumps(obj), flush=True)


def _merge(rows, row):
    """Replace any cached row for the same config; returns new list."""
    return [r for r in rows
            if not (r.get("n") == row.get("n")
                    and r.get("mode") == row.get("mode")
                    and r.get("overlay") == row.get("overlay")
                    and r.get("platform") == row.get("platform")
                    and r.get("inbox_impl", "scatter")
                    == row.get("inbox_impl", "scatter")
                    and r.get("tick_impl", "dense")
                    == row.get("tick_impl", "dense")
                    and r.get("node_shards", 0)
                    == row.get("node_shards", 0))] + [row]


def _save_row(row):
    try:
        rows = json.loads(CACHE.read_text()) if CACHE.exists() else []
    except ValueError:
        rows = []
    tmp = CACHE.with_suffix(".json.tmp")
    tmp.write_text(json.dumps(_merge(rows, row), indent=1) + "\n")
    os.replace(tmp, CACHE)   # atomic: a mid-write kill can't corrupt


def _remaining():
    return DEADLINE_S - (time.time() - _T0)


def _setup_jax(platform):
    # --platform is "cpu" or nothing (nothing = the device jax finds)
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        # KEEP IN SYNC: the same -O0 bootstrap lives in
        # tests/conftest.py, __graft_entry__.py and scripts/
        # make_goldens.py — XLA-CPU at -O0 compiles ~40% faster AND
        # runs ~30% faster on these graph shapes
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_backend_optimization_level" not in flags:
            flags = (flags + " --xla_backend_optimization_level=0"
                     " --xla_llvm_disable_expensive_passes=true").strip()
        # FMA capped off for graph-structure-independent floats
        # (tests/conftest.py rationale)
        if "xla_cpu_max_isa" not in flags:
            flags += " --xla_cpu_max_isa=AVX"
        os.environ["XLA_FLAGS"] = flags
    # hostcache.enable owns the shared ritual (zstandard poison, x64,
    # compile cache at hostcache.cache_dir()); persistence off on CPU
    # (XLA-CPU serialize() segfault, tests/conftest.py note)
    from oversim_tpu import hostcache
    hostcache.enable(persistent=platform != "cpu")
    import jax
    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    return jax


def _build(jax, overlay, n, churn, window, interval=0.2,
           inbox_impl="scatter", tick_impl="dense"):
    from oversim_tpu import churn as churn_mod
    from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
    from oversim_tpu.common import lookup as lk_mod
    from oversim_tpu.engine import sim as sim_mod

    app = KbrTestApp(KbrTestParams(test_interval=interval))
    if overlay == "kademlia":
        from oversim_tpu.overlay.kademlia import KademliaLogic
        logic = KademliaLogic(app=app,
                              lcfg=lk_mod.LookupConfig(slots=8, merge=True))
    else:
        from oversim_tpu.overlay.chord import ChordLogic
        logic = ChordLogic(app=app, lcfg=lk_mod.LookupConfig(slots=8))
    cp = churn_mod.ChurnParams(
        model=churn, target_num=n,
        lifetime_mean=10_000.0, init_interval=10.0 / n)
    ep = sim_mod.EngineParams(window=window, inbox_slots=8, pool_factor=8,
                              inbox_impl=inbox_impl, tick_impl=tick_impl)
    return sim_mod.Simulation(logic, cp, engine_params=ep), cp


def _place_2d(jax, st, node_shards):
    """Node-axis 2D placement (1 x K mesh) for a solo SimState.  Raises
    loudly (ValueError from mesh.py) when K does not divide N / the
    pool or fewer than K devices exist — a silently replicated
    "sharded" row would poison the ladder cache."""
    if node_shards <= 1:
        return st
    from oversim_tpu.parallel import mesh as mesh_mod
    mesh = mesh_mod.make_mesh_2d(1, node_shards)
    return mesh_mod.shard_state_2d(st, mesh)


def ladder_row(jax, overlay, n, measure_wall, inbox_impl="scatter",
               tick_impl="dense", node_shards=0):
    """Throughput measurement at N: warm, then measured windows — both
    device-resident (run_until_device; one dispatch + one device_get of
    the counter leaves per window, the bench.py round-7 loop)."""
    from bench import _fetch_window_leaves, _summary_from_leaves
    sim, cp = _build(jax, overlay, n, "none", window=0.2,
                     inbox_impl=inbox_impl, tick_impl=tick_impl)
    dev = jax.devices()[0]
    st = _place_2d(jax, sim.init(seed=7), node_shards)
    warm_until = cp.init_finished_time + 20.0
    t0 = time.time()
    st = sim.run_until_device(st, warm_until, chunk=64)
    base = _summary_from_leaves(_fetch_window_leaves(st))
    compile_wall = time.time() - t0
    t0 = time.time()
    sim_t = warm_until
    rate = 0.0
    delivered = sent = 0
    out = base
    while time.time() - t0 < measure_wall and _remaining() > 30:
        sim_t += 64 * 0.2
        st = sim.run_until_device(st, sim_t, chunk=64)
        out = _summary_from_leaves(_fetch_window_leaves(st))
        wall = time.time() - t0
        delivered = out["kbr_delivered"] - base["kbr_delivered"]
        sent = out["kbr_sent"] - base["kbr_sent"]
        rate = delivered / wall if wall else 0.0
    if delivered == 0:
        return None   # deadline ate the measure loop — keep cached rows
    eng = out["_engine"]
    return {
        "mode": "ladder", "overlay": overlay, "n": n,
        "platform": dev.platform,
        "inbox_impl": inbox_impl,
        "tick_impl": tick_impl,
        "node_shards": node_shards,
        "mesh": "1x%d" % node_shards if node_shards > 1 else None,
        "kernel_plane": inbox_impl == "pallas",
        "lookups_per_sec": round(rate, 1),
        "delivered": int(delivered), "sent": int(sent),
        "warm_wall_s": round(compile_wall, 1),
        "pool_overflow": eng["pool_overflow"],
        "outbox_overflow": eng["outbox_overflow"],
        "queue_lost": eng["queue_lost"],
        "inbox_deferred": eng["inbox_deferred"],
    }


def churn_row(jax, overlay, n, t_sim, inbox_impl="scatter",
              tick_impl="dense", node_shards=0):
    """LifetimeChurn bounds smoke at N (config #2 envelope)."""
    sim, cp = _build(jax, overlay, n, "lifetime", window=0.2,
                     interval=60.0, inbox_impl=inbox_impl,
                     tick_impl=tick_impl)
    dev = jax.devices()[0]
    t0 = time.time()
    st = _place_2d(jax, sim.init(seed=1), node_shards)
    target = min(t_sim, cp.init_finished_time + 300.0)
    step = 64 * 0.2
    sim_t = 0.0
    while sim_t < target and _remaining() > 60:
        sim_t = min(sim_t + step * 4, target)
        # device-resident advance; the block is the deadline guard's one
        # host sync per outer iteration
        st = sim.run_until_device(st, sim_t, chunk=64)
        jax.block_until_ready(st.t_now)
    from oversim_tpu import profiling
    if profiling.enabled() and _remaining() > 90:
        report, st = profiling.profile_ticks(sim, st, n_ticks=3)
        report.update(mode="churn_smoke", overlay=overlay, n=n)
        _emit(report)
    out = sim.summary(st)
    eng = out["_engine"]
    row = {
        "mode": "churn_smoke", "overlay": overlay, "n": n,
        "platform": dev.platform,
        "inbox_impl": inbox_impl,
        "tick_impl": tick_impl,
        "node_shards": node_shards,
        "mesh": "1x%d" % node_shards if node_shards > 1 else None,
        "kernel_plane": inbox_impl == "pallas",
        "t_sim": out["_t_sim"], "wall_s": round(time.time() - t0, 1),
        "alive": out["_alive"],
        "sent": int(out.get("kbr_sent", 0)),
        "delivered": int(out.get("kbr_delivered", 0)),
        "pool_overflow": eng["pool_overflow"],
        "outbox_overflow": eng["outbox_overflow"],
        "queue_lost": eng["queue_lost"],
        "inbox_deferred": eng["inbox_deferred"],
    }
    # the smoke's contract: static bounds must HOLD at scale
    row["bounds_ok"] = (eng["pool_overflow"] == 0
                        and eng["outbox_overflow"] == 0)
    if not row["bounds_ok"]:
        print("FAIL: static bounds overflowed at scale", file=sys.stderr)
    return row


def orchestrate() -> int:
    """Parent: re-run self as a child under a SIGKILL watchdog (a native
    XLA hang is immune to SIGALRM — the bench.py lesson), relay its JSON
    lines, always exit 0 with at least the cached rows emitted."""
    import subprocess
    import threading

    from bench import ArtifactWriter
    artifact = ArtifactWriter(os.environ.get("OVERSIM_SCALE_ARTIFACT"))
    try:
        rows = json.loads(CACHE.read_text()) if CACHE.exists() else []
    except ValueError:
        rows = []
    prov = {"rows": rows, "note": "provisional (cached rows only)"}
    _emit(prov)
    artifact.add(prov)
    env = dict(os.environ, OVERSIM_SCALE_CHILD="1")
    child = subprocess.Popen([sys.executable] + sys.argv,
                             stdout=subprocess.PIPE, text=True, env=env)

    def _watchdog():
        remain = DEADLINE_S - (time.time() - _T0)
        if remain > 0:
            time.sleep(remain)
        if child.poll() is None:
            sys.stderr.write("scale: deadline %ds hit — killing child\n"
                             % DEADLINE_S)
            child.kill()

    threading.Thread(target=_watchdog, daemon=True).start()
    for line in child.stdout:
        line = line.rstrip("\n")
        if not line:
            continue
        try:
            parsed = json.loads(line)
        except ValueError:
            sys.stderr.write("scale child: %s\n" % line)
            continue
        print(line, flush=True)
        if parsed.get("metric") == "run_manifest":
            artifact.set_manifest(parsed)   # top-level "manifest" key
        else:
            artifact.add(parsed)   # atomic rewrite after EVERY row
    child.wait()
    artifact.finish()
    sys.stderr.write("scale: child rc=%s after %.0fs\n"
                     % (child.returncode, time.time() - _T0))
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ladder", action="store_true")
    ap.add_argument("--ns", type=str, default="8192,16384,65536")
    ap.add_argument("--n", type=int, default=10_000)
    ap.add_argument("--overlay", default="kademlia",
                    choices=["kademlia", "chord"])
    ap.add_argument("--t", type=float, default=600.0)
    ap.add_argument("--measure", type=float, default=60.0)
    ap.add_argument("--platform", default=None)
    ap.add_argument("--inbox-impl", default="scatter",
                    choices=["scatter", "pallas", "sort"],
                    help="inbox implementation (pallas = fused kernel "
                    "plane; an error when unavailable)")
    ap.add_argument("--tick-impl", default="dense",
                    choices=["dense", "sparse"],
                    help="tick implementation (sparse = active-set "
                    "plane; tick cost bounded by traffic, not N)")
    ap.add_argument("--node-shards", type=int, default=0,
                    help="shard the node axis over K devices (2D "
                    "replica x node mesh); 0/1 = replicated node axis. "
                    "Refuses loudly when K does not divide N/pool or "
                    "devices are short.")
    args = ap.parse_args()

    if os.environ.get("OVERSIM_SCALE_CHILD") != "1":
        return orchestrate()

    rows = []
    try:
        rows = json.loads(CACHE.read_text()) if CACHE.exists() else []
    except ValueError:
        pass

    try:
        jax = _setup_jax(args.platform)
        from oversim_tpu.config import scenario as scenario_mod
        inbox_impl = scenario_mod.resolve_inbox_impl(args.inbox_impl)
        tick_impl = scenario_mod.resolve_tick_impl(args.tick_impl)
        # device acquisition under the elastic retry classes — a
        # transient backend failure at scale-probe time is retried,
        # not a run-killer (bench.py wiring)
        from oversim_tpu import elastic
        dev = elastic.with_retry(lambda: jax.devices()[0],
                                 policy=elastic.RetryPolicy(attempts=3),
                                 label="scale device acquisition")
        # AOT pre-warm (default ON, OVERSIM_AOT=0 opts out): both jobs
        # drive run_until_device, so a prior bench/probe run on the
        # same config skips trace+lower here entirely
        from oversim_tpu import aot
        from oversim_tpu.analysis import contracts as contracts_mod
        aot_rep = aot.warmup(
            ("run_until_device",),
            ctx=contracts_mod.EntryContext(
                n=args.n, overlay=args.overlay, window=0.2, chunk=64),
            enabled=aot.enabled_by_env(
                {"OVERSIM_AOT": os.environ.get("OVERSIM_AOT", "1")}))
        # run manifest — the orchestrator routes this line to the
        # artifact's top-level "manifest" key (telemetry.run_manifest)
        from oversim_tpu import telemetry as telemetry_mod
        _emit(telemetry_mod.run_manifest(
            config={"mode": "ladder" if args.ladder else "churn_smoke",
                    "ns": args.ns if args.ladder else None, "n": args.n,
                    "overlay": args.overlay, "t": args.t,
                    "measure": args.measure, "platform": args.platform,
                    "inbox_impl": inbox_impl,
                    "tick_impl": tick_impl,
                    "node_shards": args.node_shards,
                    "mesh": ("1x%d" % args.node_shards
                             if args.node_shards > 1 else None),
                    "kernel_plane": inbox_impl == "pallas"},
            artifacts={"artifact":
                       os.environ.get("OVERSIM_SCALE_ARTIFACT")},
            extra={"aot": aot_rep, "device": str(dev)}))
        if args.ladder:
            for n in [int(x) for x in args.ns.split(",") if x]:
                if _remaining() < 120:
                    break
                row = ladder_row(jax, args.overlay, n, args.measure,
                                 inbox_impl=inbox_impl,
                                 tick_impl=tick_impl,
                                 node_shards=args.node_shards)
                if row is None:
                    continue
                _save_row(row)
                rows = _merge(rows, row)
                _emit({"rows": rows})
        else:
            row = churn_row(jax, args.overlay, args.n, args.t,
                            inbox_impl=inbox_impl, tick_impl=tick_impl,
                            node_shards=args.node_shards)
            _save_row(row)
            rows = _merge(rows, row)
            _emit({"rows": rows})
    except Exception as e:  # noqa: BLE001 — always leave a parsable artifact
        _emit({"rows": rows, "error": repr(e)[:400]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
