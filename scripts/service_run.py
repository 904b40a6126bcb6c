"""Service CLI: run the resident double-buffered serving loop.

Turns a scenario into a long-running service (oversim_tpu/service/):
windows are dispatched device-resident with the NEXT window enqueued
before the previous window's fetch (the device never idles), the full
state is checkpointed atomically every C windows (kill-safe: SIGKILL at
any instant leaves a complete checkpoint), and ``--resume`` continues a
killed run bit-identically from the last checkpoint.

Usage:
  python scripts/service_run.py --ini simulations/my.ini [--config X]
      Build from the ini; ``**.service.*`` keys (windowSimS, chunk,
      checkpointEvery, checkpointPath, maxWindows, maxWallS,
      doubleBuffer, realtime) select the loop parameters
      (config/scenario.py build_service).
  python scripts/service_run.py --windows 100 [--n 256] [--overlay
      kademlia|chord] [--seed 1] [--churn lifetime --lifetime 1000]
      Flag-built KBRTestApp scenario (bench.py shape).

Common:  [--window-sim-s 1.0] [--chunk 32] [--checkpoint ck.npz]
         [--checkpoint-every 10] [--resume] [--replicas S]
         [--platform cpu] [--out artifact.json] [--trace t.json]
         [--telemetry K] [--telemetry-window W] [--single-buffer]

Live observability (oversim_tpu/obs/): ``--metrics-port P`` serves
/metrics (OpenMetrics), /healthz (ready → draining on SIGTERM) and
/statusz (tick/window/checkpoint-age JSON) from a stdlib HTTP thread
(P=0 picks an ephemeral port, announced in the ``"phase": "obs"``
line); ``--flight F`` streams the structured event ring to F as JSONL
and dumps its tail on SIGTERM/fatal.  ``--ingest-rate R`` switches the
scenario to the echo serving app (RealworldEchoApp + ext_hold_slot)
and drives R traced synthetic requests per window from
``--ingest-clients`` clients — request-to-response latency lands in
the metrics and the final artifact record.

``--replicas S`` serves the stacked campaign state (S replicas as one
vmapped program, cross-replica summaries per window); checkpoints then
snapshot the whole [S]-stacked state, and resume restores every
replica.

The artifact (bench.py ArtifactWriter) is written incrementally with
atomic tmp+rename — one record per window plus a run manifest
(oversim_tpu/telemetry.py run_manifest) — so a deadline SIGKILL leaves
a valid partial artifact next to a resumable checkpoint.
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


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


def _build_sim(args):
    from oversim_tpu import churn as churn_mod
    from oversim_tpu import telemetry as telemetry_mod
    from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
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
    ep = sim_mod.EngineParams(
        window=args.engine_window, inbox_slots=8, pool_factor=8,
        telemetry=telemetry_mod.TelemetryParams(
            sample_ticks=args.telemetry,
            window=args.telemetry_window))
    return sim_mod.Simulation(logic, cp, engine_params=ep)


def _build_echo_sim(args):
    """The serving scenario: every EXT_IN answered with EXT_OUT
    (RealworldEchoApp), responses parked by ext_hold_slot until the
    boundary drain (service/ingest.py module docstring)."""
    from oversim_tpu import churn as churn_mod
    from oversim_tpu import telemetry as telemetry_mod
    from oversim_tpu.apps.realworld import RealworldEchoApp
    from oversim_tpu.engine import sim as sim_mod
    from oversim_tpu.overlay.myoverlay import (MyOverlayLogic,
                                               MyOverlayParams)

    logic = MyOverlayLogic(params=MyOverlayParams(),
                           app=RealworldEchoApp(transform=1))
    cp = churn_mod.ChurnParams(model="none", target_num=args.n,
                               init_interval=10.0 / args.n)
    ep = sim_mod.EngineParams(
        window=args.engine_window, ext_hold_slot=0,
        telemetry=telemetry_mod.TelemetryParams(
            sample_ticks=args.telemetry,
            window=args.telemetry_window))
    return sim_mod.Simulation(logic, cp, engine_params=ep)


def _run_daemon(args):
    """Overlay-as-a-service: the echo scenario campaign-stacked to
    ``--tenants`` replica rows, served to real UDP/TCP clients through
    the socket mux with per-tenant admission, tracing and metrics.

    Announces bound ports as a ``{"phase": "daemon", ...}`` JSON line
    (the slo_soak gate parses it), serves ``--windows`` boundaries (or
    until SIGTERM / ``--max-wall-s``), then drains every in-flight sid
    and writes the final accounting identity into the artifact."""
    _setup_jax(args.platform)
    from bench import ArtifactWriter
    from oversim_tpu import aot
    from oversim_tpu import elastic
    from oversim_tpu import telemetry as telemetry_mod
    from oversim_tpu import xmlrpcif
    from oversim_tpu.analysis import contracts as contracts_mod
    from oversim_tpu.campaign import Campaign, CampaignParams
    from oversim_tpu.obs import RequestTracer
    from oversim_tpu.service import (OverlayDaemon, ServiceLoop,
                                     ServiceParams, SocketMux,
                                     TenantIngest, TenantTable,
                                     campaign_summarize_leaves)

    T = args.tenants
    if T < 1:
        raise SystemExit("--daemon needs --tenants >= 1")
    config = {"app": "echo", "daemon": True, "n": args.n,
              "seed": args.seed, "tenants": T,
              "engine_window": args.engine_window,
              "tenant_max_pending": args.tenant_max_pending,
              "telemetry": {"sampleTicks": args.telemetry,
                            "window": args.telemetry_window}}
    sim = _build_echo_sim(args)
    camp = Campaign(sim, CampaignParams(replicas=T, base_seed=args.seed))
    artifact = ArtifactWriter(args.out)

    # default-ON backend acquisition + AOT warm-up (bench.py pattern):
    # transient chip failures are retried (exhausted attempts raise),
    # and the daemon_window executable is deserialized or
    # exported before the first client connects
    backend = elastic.acquire_backend()
    aot_rep = aot.warmup(
        ("daemon_window",),
        ctx=contracts_mod.EntryContext(
            n=args.n, window=args.engine_window,
            replicas=T, chunk=args.chunk),
        enabled=aot.enabled_by_env(
            {"OVERSIM_AOT": os.environ.get("OVERSIM_AOT", "1")}))

    tracer = RequestTracer(keep_samples=True)
    tenant_tracers = [
        RequestTracer(prefix="oversim_tenant", labels={"tenant": str(t)})
        for t in range(T)]
    table = TenantTable(T, max_pending=args.tenant_max_pending,
                        tracers=tenant_tracers)
    ingest = TenantIngest(table, gw_slot=0, tracer=tracer)
    mux = SocketMux(udp_port=args.udp_port, tcp_port=args.tcp_port)
    daemon = OverlayDaemon(ingest, mux=mux)
    xmlrpc_port = None
    if args.xmlrpc_port is not None:
        frontend = xmlrpcif.XmlRpcFrontend(daemon)
        _, xmlrpc_port = xmlrpcif.serve_frontend(
            frontend, port=args.xmlrpc_port)

    obs = None
    if args.metrics_port is not None or args.flight:
        from oversim_tpu.obs import RunObserver
        obs = RunObserver(role="daemon", port=args.metrics_port,
                          flight_path=args.flight, tracer=tracer)
        obs.set_static(n=args.n, overlay="myoverlay", replicas=T,
                       tenants=T)
        obs_rec = {"phase": "obs", "metrics_port": obs.start(),
                   "flight": args.flight}
        print(json.dumps(obs_rec), flush=True)
        artifact.add(obs_rec)

    t0 = time.perf_counter()
    # warm until every node has joined so the echo app answers from the
    # first served window (same warm-up as the --ingest-rate path)
    cs = camp.run_until_device(camp.init(), 10.0 + args.engine_window,
                               chunk=args.chunk)
    params = ServiceParams(
        window_sim_s=args.window_sim_s, chunk=args.chunk,
        checkpoint_every=args.checkpoint_every,
        checkpoint_path=args.checkpoint, realtime=args.realtime,
        max_wall_s=args.max_wall_s)
    manifest = telemetry_mod.run_manifest(
        config=config,
        artifacts={"artifact": args.out,
                   "checkpoint": args.checkpoint,
                   "metrics_port": obs.port if obs is not None else None,
                   "flight": args.flight},
        extra={"aot": aot_rep, "backend": backend})
    artifact.set_manifest(manifest)

    def on_window(window, summary, wall):
        if obs is not None:
            obs.on_window(window, summary, wall)
        rec = {"window": window, "wall_s": round(wall, 3),
               "outstanding": ingest.outstanding(),
               "shed": ingest.rx_shed}
        print(json.dumps(rec), flush=True)
        artifact.add(rec)

    loop = ServiceLoop(camp, cs, params, config=config,
                       on_window=on_window, ingest=daemon,
                       summarize=campaign_summarize_leaves,
                       events=obs.loop_event if obs is not None else None)

    daemon_rec = {"phase": "daemon", "udp_port": mux.udp_port,
                  "tcp_port": mux.tcp_port, "xmlrpc_port": xmlrpc_port,
                  "tenants": T, "init_wall_s":
                  round(time.perf_counter() - t0, 2),
                  "aot": aot_rep.get("enabled", False),
                  "platform": backend.get("platform")}
    print(json.dumps(daemon_rec), flush=True)
    artifact.add(daemon_rec)

    got_term = []

    def _on_sigterm(signum, frame):
        got_term.append(signum)
        if obs is not None:
            obs.draining()
        loop.stop()

    import signal
    signal.signal(signal.SIGTERM, _on_sigterm)

    loop.run(n_windows=args.windows or None)
    acct = daemon.drain(loop)
    final = {"phase": "final", "windows_done": loop.windows_done,
             "sigterm": bool(got_term),
             "accounting": acct,
             "requests": tracer.percentiles(),
             "wall_s": round(time.perf_counter() - t0, 2)}
    artifact.add(final)
    artifact.finish()
    sys.stderr.write(tracer.table() + "\n")
    print(json.dumps(final), flush=True)
    daemon.close()
    if obs is not None:
        if got_term and args.term_grace > 0:
            time.sleep(args.term_grace)
        obs.close(dump_tail=bool(got_term))
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ini", default=None, help="build from ini "
                    "(**.service.* keys) instead of flags")
    ap.add_argument("--config", default="General")
    ap.add_argument("--windows", type=int, default=10, metavar="W",
                    help="windows to serve this invocation")
    ap.add_argument("--window-sim-s", type=float, default=1.0)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--checkpoint", default=None, metavar="PATH",
                    help="checkpoint file (atomic tmp+rename npz)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    metavar="C", help="windows between checkpoints")
    ap.add_argument("--resume", action="store_true",
                    help="restore the checkpoint and continue "
                    "bit-identically")
    ap.add_argument("--override-cadence", action="store_true",
                    help="resume despite a changed window_sim_s/chunk: "
                    "re-anchor the window origin at the restored clock "
                    "instead of refusing (trades the uninterrupted-run "
                    "identity for the new cadence)")
    ap.add_argument("--reshard", action="store_true",
                    help="resume a campaign checkpoint at THIS run's "
                    "--replicas even when it differs: surviving "
                    "replicas restored bit-identically, grown slots "
                    "re-seeded deterministically (oversim_tpu/elastic)")
    ap.add_argument("--single-buffer", action="store_true",
                    help="disable the dispatch/fetch pipeline")
    ap.add_argument("--replicas", type=int, default=0, metavar="S",
                    help="serve the S-replica stacked campaign state")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--overlay", default="kademlia",
                    choices=["kademlia", "chord"])
    ap.add_argument("--churn", default="none")
    ap.add_argument("--lifetime", type=float, default=10_000.0)
    ap.add_argument("--interval", type=float, default=0.2)
    ap.add_argument("--engine-window", type=float, default=0.2)
    ap.add_argument("--platform", default=None)
    ap.add_argument("--out", default=None, help="incremental atomic "
                    "artifact path")
    ap.add_argument("--telemetry", type=int, default=0, metavar="K")
    ap.add_argument("--telemetry-window", type=int, default=256)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="Perfetto trace: window_dispatch/window_fetch/"
                    "checkpoint_write spans (overlap = pipelining)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="P", help="serve /metrics /healthz /statusz "
                    "on P (0 = ephemeral; bound port in the obs phase "
                    "line)")
    ap.add_argument("--flight", default=None, metavar="PATH",
                    help="JSONL flight-recorder path (tail dumped on "
                    "SIGTERM/fatal)")
    ap.add_argument("--ingest-rate", type=int, default=0, metavar="R",
                    help="serve R traced synthetic echo requests per "
                    "window (switches to the echo serving scenario)")
    ap.add_argument("--ingest-clients", type=int, default=4)
    ap.add_argument("--term-grace", type=float, default=0.0,
                    metavar="S", help="keep /healthz serving the "
                    "draining state S seconds after a SIGTERMed loop "
                    "stops (deterministic scrape window for smoke "
                    "gates)")
    ap.add_argument("--daemon", action="store_true",
                    help="overlay-as-a-service: serve real UDP/TCP "
                    "clients through the socket mux with per-replica "
                    "multi-tenant sessions (service/daemon.py)")
    ap.add_argument("--tenants", type=int, default=2, metavar="T",
                    help="daemon tenant count == campaign replica rows")
    ap.add_argument("--tenant-max-pending", type=int, default=64,
                    metavar="B", help="per-tenant admission bound: "
                    "submits past B pending are shed with EXT_NACK")
    ap.add_argument("--udp-port", type=int, default=0,
                    help="daemon UDP port (0 = ephemeral, announced in "
                    "the daemon phase line)")
    ap.add_argument("--tcp-port", type=int, default=0,
                    help="daemon TCP listener port (0 = ephemeral)")
    ap.add_argument("--xmlrpc-port", type=int, default=None,
                    metavar="P", help="also serve the XML-RPC bridge "
                    "front-end on P (0 = ephemeral; omit = off)")
    ap.add_argument("--realtime", action="store_true",
                    help="pace serving windows to wall clock")
    ap.add_argument("--max-wall-s", type=float, default=0.0,
                    help="wall-clock budget for the serving run (0 = "
                    "unbounded)")
    args = ap.parse_args()

    if args.daemon:
        return _run_daemon(args)

    _setup_jax(args.platform)
    from bench import ArtifactWriter
    from oversim_tpu import telemetry as telemetry_mod
    from oversim_tpu.service import (ServiceLoop, ServiceParams,
                                     campaign_summarize_leaves)

    # the scenario-defining config (hashed into checkpoints; resume
    # refuses a checkpoint whose hash differs) — run-shape flags like
    # --windows/--out/--resume deliberately excluded.  --replicas is
    # run shape too: the per-replica scenario is identical at any
    # ensemble size, and hashing it would veto every --reshard resume
    config = {"ini": args.ini, "config": args.config,
              "overlay": args.overlay, "n": args.n, "seed": args.seed,
              "churn": args.churn, "lifetime": args.lifetime,
              "interval": args.interval,
              "engine_window": args.engine_window,
              "telemetry": {"sampleTicks": args.telemetry,
                            "window": args.telemetry_window}}

    if args.ini:
        from oversim_tpu.config.ini import IniFile
        from oversim_tpu.config.scenario import (build_service,
                                                 build_simulation)
        ini = IniFile.load(args.ini)
        sim = build_simulation(ini, args.config)
        params = build_service(ini, args.config)
    else:
        if args.ingest_rate:
            if args.replicas:
                raise SystemExit("--ingest-rate serves the SOLO echo "
                                 "state (no campaign session plumbing)")
            sim = _build_echo_sim(args)
            config["app"] = "echo"
        else:
            sim = _build_sim(args)
        params = ServiceParams(
            window_sim_s=args.window_sim_s, chunk=args.chunk,
            checkpoint_every=args.checkpoint_every,
            checkpoint_path=args.checkpoint,
            double_buffer=not args.single_buffer)

    summarize = None
    if args.replicas:
        from oversim_tpu.campaign import Campaign, CampaignParams
        runner = Campaign(sim, CampaignParams(replicas=args.replicas,
                                              base_seed=args.seed))
        summarize = campaign_summarize_leaves
    else:
        runner = sim

    artifact = ArtifactWriter(args.out)
    trace = (telemetry_mod.PerfettoTrace("service_run")
             if args.trace else None)

    # live observability plane: tracer for the synthetic serving load,
    # observer for /metrics + /healthz + /statusz + the flight ring —
    # all updates happen at the loop's existing host-sync points
    tracer = None
    ingest = None
    if args.ingest_rate:
        from oversim_tpu.obs import RequestTracer, SyntheticLoad
        from oversim_tpu.service.ingest import InProcessIngest
        tracer = RequestTracer(keep_samples=True)
        ingest = SyntheticLoad(
            InProcessIngest(gw_slot=0, tracer=tracer),
            clients=args.ingest_clients, per_window=args.ingest_rate)
    obs = None
    if args.metrics_port is not None or args.flight:
        from oversim_tpu.obs import RunObserver
        obs = RunObserver(role="service", port=args.metrics_port,
                          flight_path=args.flight, tracer=tracer)
        obs.set_static(n=args.n, overlay=args.overlay,
                       replicas=args.replicas,
                       ingest_rate=args.ingest_rate)
        obs_rec = {"phase": "obs", "metrics_port": obs.start(),
                   "flight": args.flight}
        print(json.dumps(obs_rec), flush=True)
        artifact.add(obs_rec)

    t0 = time.perf_counter()
    example = (runner.init() if args.replicas
               else runner.init(seed=args.seed))
    if args.ingest_rate and not args.resume:
        # warm until every node has joined (init_interval * n) so the
        # echo app answers from the first served window
        example = runner.run_until(example, 10.0 + args.engine_window,
                                   chunk=params.chunk)
    init_rec = {"phase": "init", "resume": bool(args.resume),
                "replicas": args.replicas,
                "init_wall_s": round(time.perf_counter() - t0, 2)}
    print(json.dumps(init_rec), flush=True)
    artifact.add(init_rec)

    # AOT pre-warm ($OVERSIM_AOT=1): deserialize-or-export the window
    # entry this service will compile (oversim_tpu/aot/); report → manifest
    from oversim_tpu import aot
    from oversim_tpu.analysis import contracts as contracts_mod
    if args.ingest_rate:
        # the echo serving graph is not a registered AOT entry
        aot_rep = {"enabled": False, "skipped": "echo serving scenario"}
    else:
        aot_rep = aot.warmup(
            ("campaign_tick",) if args.replicas else ("service_window",),
            ctx=contracts_mod.EntryContext(
                n=args.n, overlay=args.overlay,
                window=args.engine_window,
                inbox=8, pool_factor=8, replicas=max(args.replicas, 1),
                chunk=params.chunk))
        if trace and aot_rep["enabled"]:
            aot.trace_spans(trace, aot_rep)
    if obs is not None and aot_rep.get("enabled"):
        obs.record("aot", artifact_hits=aot_rep.get("artifact_hits"),
                   fresh_compiles=aot_rep.get("fresh_compiles"))

    from oversim_tpu.obs import xprof_dir
    manifest = telemetry_mod.run_manifest(
        config=config,
        artifacts={"artifact": args.out, "trace": args.trace,
                   "checkpoint": params.checkpoint_path,
                   "metrics_port": obs.port if obs is not None else None,
                   "flight": args.flight, "xprof": xprof_dir()},
        extra={"aot": aot_rep})
    artifact.set_manifest(manifest)

    def on_window(window, summary, wall):
        if obs is not None:
            obs.on_window(window, summary, wall)
        rec = {"window": window, "wall_s": round(wall, 3), **summary}
        print(json.dumps(rec), flush=True)
        artifact.add(rec)
        if trace is not None:
            trace.write(args.trace)  # atomic: valid trace after every window

    kw = dict(config=config, on_window=on_window, trace=trace,
              summarize=summarize, ingest=ingest,
              events=obs.loop_event if obs is not None else None)
    if args.resume:
        if args.reshard and not args.replicas:
            raise SystemExit("--reshard needs --replicas (campaign "
                             "checkpoints only)")
        loop = ServiceLoop.resume(runner, example, params,
                                  override_cadence=args.override_cadence,
                                  reshard=args.reshard, **kw)
        print(json.dumps({"phase": "resume",
                          "windows_done": loop.windows_done,
                          "start_sim_t": loop.start_sim_t,
                          "reshard": args.reshard,
                          "override_cadence": args.override_cadence}),
              flush=True)
    else:
        loop = ServiceLoop(runner, example, params, **kw)

    # graceful SIGTERM: finish the in-flight window, write a final
    # checkpoint + complete artifact manifest, exit 0 (the SIGKILL path
    # — torn nothing, resumable checkpoint — is service_smoke's pin)
    got_term = []

    def _on_sigterm(signum, frame):
        got_term.append(signum)
        if obs is not None:
            obs.draining()      # /healthz → 503 before the stop lands
        loop.stop()

    import signal
    signal.signal(signal.SIGTERM, _on_sigterm)

    from oversim_tpu.obs import xprof_capture
    with xprof_capture("service_windows") as xprof_info:
        state, done = loop.run(n_windows=args.windows)
    final = {"phase": "final", "windows_done": done,
             "checkpoints_written": loop.checkpoints_written,
             "last_checkpoint": loop.last_checkpoint,
             "wall_s": round(time.perf_counter() - t0, 2)}
    if xprof_info["dir"]:
        final["xprof"] = xprof_info
    if got_term:
        final["sigterm"] = True
        final["final_checkpoint"] = loop.checkpoint_now()
    if tracer is not None:
        final["requests"] = tracer.percentiles()
        sys.stderr.write(tracer.table() + "\n")
    artifact.add(final)
    if trace is not None:
        trace.write(args.trace)
    artifact.finish()
    print(json.dumps(final), flush=True)
    if obs is not None:
        if got_term and args.term_grace > 0:
            # hold the endpoint in the draining state so an external
            # probe can observe the flip before the process exits
            time.sleep(args.term_grace)
        obs.close(dump_tail=bool(got_term))
    return 0


if __name__ == "__main__":
    sys.exit(main())
