"""Campaign CLI: run S replicas (seed sweep + optional parameter grid)
as ONE compiled vmapped program and emit the ensemble report.

The reference runs repetitions as separate processes (``./OverSim -r N``,
one scalar file each) and leaves the cross-run averaging to scripts; here
the whole campaign is a single device-resident program
(oversim_tpu/campaign/) whose replica axis is sharded across the visible
devices, and the report carries cross-replica mean/stddev/Student-t CI
per metric plus the per-replica breakdown.

Usage:
  python scripts/campaign_run.py --ini simulations/my.ini [--config X]
      Build from ``**.campaign.*`` ini keys (replicas, baseSeed,
      sweep.lifetimeMean / sweep.testMsgInterval / sweep.window).
  python scripts/campaign_run.py --replicas 8 [--n 256] [--overlay
      kademlia|chord] [--seed 1] [--sweep churn.lifetimeMean=100,1000]
      Flag-built Kademlia/Chord KBRTestApp scenario (bench.py shape).

Common:  [--t 120] simulated seconds  [--chunk 64] ticks per scan
         [--platform cpu]  [--out report.json]
         [--telemetry K] in-graph KPI sampling every K ticks (emits a
         telemetry_series record: per-replica series + CI bands)
         [--telemetry-window W] ring capacity   [--trace trace.json]
         Perfetto host-phase spans + ensemble KPI counter tracks

Every artifact carries a top-level ``manifest`` (config hash, mesh
layout, git rev, artifact paths — oversim_tpu/telemetry.py
``run_manifest``).

The report JSON is written INCREMENTALLY with atomic tmp+rename
(bench.py's ArtifactWriter): a phase record after init, one after the
run, then the full report — a deadline SIGKILL leaves a valid partial
artifact.  The final line on stdout is the report itself.
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

_T0 = time.time()


def _setup_jax(platform):
    # --platform is "cpu" or nothing (nothing = the device jax finds)
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_backend_optimization_level" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_backend_optimization_level=0"
                " --xla_llvm_disable_expensive_passes=true").strip()
    # hostcache.enable owns the shared ritual (zstandard poison, x64,
    # cache at hostcache.cache_dir()); persistent=False on CPU — this
    # box's XLA-CPU executable serialize() segfaults (conftest note)
    from oversim_tpu import hostcache
    hostcache.enable(persistent=platform != "cpu")
    import jax
    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    return jax


def _parse_sweep(specs):
    """--sweep name=v1,v2,... (repeatable) → CampaignParams.sweep tuple."""
    out = []
    for spec in specs or ():
        name, _, vals = spec.partition("=")
        vals = tuple(float(x) for x in vals.replace(",", " ").split())
        if not name or not vals:
            raise SystemExit(f"bad --sweep spec: {spec!r}")
        out.append((name, vals))
    return tuple(out)


def _build_from_flags(args):
    from oversim_tpu import churn as churn_mod
    from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
    from oversim_tpu.campaign import Campaign, CampaignParams
    from oversim_tpu.common import lookup as lk_mod
    from oversim_tpu.engine import sim as sim_mod

    app = KbrTestApp(KbrTestParams(test_interval=args.interval))
    if args.overlay == "chord":
        from oversim_tpu.overlay.chord import ChordLogic
        logic = ChordLogic(app=app, lcfg=lk_mod.LookupConfig(slots=8))
    else:
        from oversim_tpu.overlay.kademlia import KademliaLogic
        logic = KademliaLogic(app=app,
                              lcfg=lk_mod.LookupConfig(slots=8, merge=True))
    cp = churn_mod.ChurnParams(model=args.churn, target_num=args.n,
                               lifetime_mean=args.lifetime,
                               init_interval=10.0 / args.n)
    from oversim_tpu import telemetry as telemetry_mod
    ep = sim_mod.EngineParams(
        window=args.window, inbox_slots=8, pool_factor=8,
        telemetry=telemetry_mod.TelemetryParams(
            sample_ticks=args.telemetry,
            window=args.telemetry_window))
    sim = sim_mod.Simulation(logic, cp, engine_params=ep)
    return Campaign(sim, CampaignParams(replicas=args.replicas,
                                        base_seed=args.seed,
                                        sweep=_parse_sweep(args.sweep)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ini", default=None, help="build from ini "
                    "**.campaign.* keys instead of flags")
    ap.add_argument("--config", default="General")
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--sweep", action="append", default=[],
                    metavar="NAME=V1,V2", help="grid axis (repeatable): "
                    "churn.lifetimeMean / app.testMsgInterval / "
                    "engine.window")
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--overlay", default="kademlia",
                    choices=["kademlia", "chord"])
    ap.add_argument("--churn", default="none")
    ap.add_argument("--lifetime", type=float, default=10_000.0)
    ap.add_argument("--interval", type=float, default=0.2)
    ap.add_argument("--window", type=float, default=0.2)
    ap.add_argument("--t", type=float, default=120.0)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--confidence", type=float, default=0.95)
    ap.add_argument("--platform", default=None)
    ap.add_argument("--out", default=None, help="incremental atomic "
                    "report artifact path")
    ap.add_argument("--telemetry", type=int, default=0, metavar="K",
                    help="device-resident KPI time-series sampling every "
                    "K ticks (0 = off; oversim_tpu/telemetry.py)")
    ap.add_argument("--telemetry-window", type=int, default=256,
                    metavar="W", help="telemetry ring-buffer capacity")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Perfetto/Chrome trace JSON of the "
                    "host phases + sampled KPI counter tracks")
    args = ap.parse_args()

    jax = _setup_jax(args.platform)
    from bench import ArtifactWriter
    from oversim_tpu import telemetry as telemetry_mod
    from oversim_tpu.parallel import mesh as mesh_mod

    artifact = ArtifactWriter(args.out)
    trace = (telemetry_mod.PerfettoTrace("campaign_run")
             if args.trace else None)

    if args.ini:
        from oversim_tpu.config.ini import IniFile
        from oversim_tpu.config.scenario import build_campaign
        camp = build_campaign(IniFile.load(args.ini), args.config)
    else:
        camp = _build_from_flags(args)

    t0 = time.perf_counter()
    cs = camp.init()
    # shard the replica axis over the largest device count dividing S
    avail = len(jax.devices())
    n_dev = max(d for d in range(1, min(avail, camp.s) + 1)
                if camp.s % d == 0)
    mesh = None
    if n_dev > 1:
        mesh = mesh_mod.make_replica_mesh(n_dev)
        cs = mesh_mod.shard_campaign_state(cs, mesh)
    init_rec = {"phase": "init", "replicas": camp.p.replicas,
                "grid": camp.grid, "s": camp.s, "devices": n_dev,
                "init_wall_s": round(time.perf_counter() - t0, 2)}
    print(json.dumps(init_rec), flush=True)
    artifact.add(init_rec)
    if trace:
        trace.span("init", t0, time.perf_counter() - t0,
                   args={"s": camp.s, "devices": n_dev})

    # AOT pre-warm ($OVERSIM_AOT=1): deserialize-or-export campaign_tick
    # before the first dispatch (oversim_tpu/aot/); report → manifest
    from oversim_tpu import aot
    from oversim_tpu.analysis import contracts as contracts_mod
    aot_rep = aot.warmup(("campaign_tick",), ctx=contracts_mod.EntryContext(
        n=args.n, overlay=args.overlay, window=args.window,
        inbox=8, pool_factor=8, replicas=camp.p.replicas,
        chunk=args.chunk))
    if trace and aot_rep["enabled"]:
        aot.trace_spans(trace, aot_rep)

    # run manifest: config hash + mesh layout + artifact paths attached
    # to the artifact as its top-level "manifest" key
    manifest = telemetry_mod.run_manifest(
        config={"ini": args.ini, "config": args.config,
                "replicas": camp.p.replicas, "base_seed": camp.p.base_seed,
                "grid": camp.grid, "n": getattr(args, "n", None),
                "overlay": args.overlay, "t": args.t, "chunk": args.chunk,
                "telemetry": {"sampleTicks": args.telemetry,
                              "window": args.telemetry_window}},
        mesh=mesh,
        artifacts={"report": args.out, "trace": args.trace},
        extra={"aot": aot_rep})
    artifact.set_manifest(manifest)

    t0 = time.perf_counter()
    cs = camp.run_until_device(cs, args.t, chunk=args.chunk)
    jax.block_until_ready(cs.t_now)
    run_rec = {"phase": "run", "target_t_sim": args.t,
               "run_wall_s": round(time.perf_counter() - t0, 2)}
    print(json.dumps(run_rec), flush=True)
    artifact.add(run_rec)
    if trace:
        trace.span("run", t0, time.perf_counter() - t0,
                   args={"target_t_sim": args.t, "chunk": args.chunk})

    t0 = time.perf_counter()
    report = camp.report(cs, confidence=args.confidence)
    # merge the timing records WITHOUT clobbering report keys (the
    # report's "t_sim" is the per-replica list; the run record's target
    # is a scalar, renamed target_t_sim)
    report["_campaign"].update(init_rec, **run_rec)
    report["_campaign"].pop("phase", None)
    artifact.add(report)

    # per-replica KPI time series + cross-replica CI bands (telemetry
    # rings sampled in-graph; one extra device_get)
    tel_rec = camp.telemetry_report(cs, confidence=args.confidence)
    if tel_rec.get("enabled", True):
        tel_rec["metric"] = "telemetry_series"
        artifact.add(tel_rec)
        print(json.dumps(tel_rec), flush=True)
        if trace and tel_rec.get("bands"):
            # ensemble-mean KPI tracks over SIM time (counter events)
            t_s = tel_rec["t_s"][0] if tel_rec.get("t_s") else []
            for name, band in sorted(tel_rec["bands"].items()):
                for t, v in zip(t_s, band["mean"]):
                    if v is not None:
                        trace.counter(name, t, v, pid=2)
    if trace:
        trace.span("report", t0, time.perf_counter() - t0)
        trace.write(args.trace)
    artifact.finish()
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
