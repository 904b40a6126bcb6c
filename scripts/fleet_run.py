"""Fleet supervisor: run a campaign across preemptible worker processes.

Shards a campaign's replica grid (``elastic.shard_replicas`` +
``CampaignParams.replica_ids``) across worker processes, each advancing
its rows by fixed-tick ``run_chunk`` strides with an atomic checkpoint
after every chunk.  The supervisor monitors heartbeat files, SIGKILLs
workers on demand (built-in chaos mode: seeded random kills), and
respawns dead or hung workers — each respawn resumes its shard from the
latest checkpoint via ``elastic.reshard_load`` at whatever mesh shape
is free.  When every shard finishes, the per-shard counter leaves are
merged by global replica id into ONE ensemble report identical to an
uninterrupted single-process run (``--verify`` proves it in-process,
with EXACT per-replica counter equality).

Usage:
  python scripts/fleet_run.py --workers 2 --replicas 4 --ticks 96 \
      --chunk 16 --n 64 --overlay chord --out /tmp/fleet
  python scripts/fleet_run.py ... --chaos --kills 3 [--chaos-seed 7] \
      [--chaos-span 6.0]       # seeded random SIGKILLs, still converges
  python scripts/fleet_run.py ... --verify
      # also run the uninterrupted reference in-process and demand
      # exact ensemble equality (exit 2 on divergence)
  python scripts/fleet_run.py ... --autoscale --autoscale-min 1 \
      --autoscale-max 4 [--autoscale-up 256] [--autoscale-down 64]
      # closed loop: the supervisor scrapes its own gauges (backlog
      # rows, heartbeat liveness, chunk wall latency), applies a
      # hysteresis policy, and resizes the worker set mid-campaign by
      # re-splitting the live replica rows (elastic.plan_resize +
      # regroup_shard_leaves) into a new worker generation — every
      # decision goes to the flight recorder and oversim_autoscale_*

Determinism contract: workers and the reference BOTH advance by
``run_chunk(chunk)`` strides (never ``run_until_device``, whose
any-replica stop condition is stack-dependent), so every replica's
final state is a pure function of (base_seed, replica id, ticks) —
independent of sharding, kills, and resume points.
"""

import argparse
import dataclasses
import json
import os
import random
import signal
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "scripts"))


# ----------------------------------------------------------- scenario --


def _scenario(args) -> dict:
    """The scenario-defining config (hashed into checkpoints; shipped
    to workers verbatim via the spec file)."""
    return {"overlay": args.overlay, "n": args.n, "seed": args.seed,
            "churn": args.churn, "lifetime": args.lifetime,
            "interval": args.interval,
            "engine_window": args.engine_window,
            "replicas": args.replicas, "ticks": args.ticks,
            "chunk": args.chunk}


def _build_campaign(scn: dict, replica_ids=None):
    import service_run
    from oversim_tpu.campaign import Campaign, CampaignParams

    ns = argparse.Namespace(
        overlay=scn["overlay"], n=scn["n"], churn=scn["churn"],
        lifetime=scn["lifetime"], interval=scn["interval"],
        engine_window=scn["engine_window"], telemetry=0,
        telemetry_window=256)
    sim = service_run._build_sim(ns)
    p = CampaignParams(
        replicas=scn["replicas"], base_seed=scn["seed"],
        replica_ids=None if replica_ids is None else tuple(replica_ids))
    return Campaign(sim, p)


def _final_leaves(state):
    """Host copies of the per-window counter leaves (no telemetry —
    fleet artifacts carry only what the ensemble summary needs)."""
    import jax
    from oversim_tpu.service.loop import counter_leaf_refs
    leaves = counter_leaf_refs(state)
    leaves.pop("telemetry", None)
    return jax.device_get(leaves)


def _run_reference(scn: dict):
    """The uninterrupted single-process run: the full campaign advanced
    by the same run_chunk cadence the workers use."""
    camp = _build_campaign(scn)
    cs = camp.init()
    for _ in range(scn["ticks"] // scn["chunk"]):
        cs = camp.run_chunk(cs, scn["chunk"])
    return _final_leaves(cs)


# ------------------------------------------------------------- worker --


def _worker_main(spec_path: str) -> int:
    import service_run
    spec = json.load(open(spec_path))
    service_run._setup_jax(spec.get("platform", "cpu"))

    import jax
    from oversim_tpu import checkpoint as ckpt_mod
    from oversim_tpu import telemetry as telemetry_mod
    from oversim_tpu.elastic import (RetryPolicy, acquire_backend,
                                     backoff_delays, classify, fleet,
                                     place_campaign, reshard_load)
    from oversim_tpu.elastic.retry import FATAL

    scn = spec["scenario"]
    widx = spec["worker"]
    chunk = scn["chunk"]
    ticks = spec["ticks"]
    ckpt_path = spec["checkpoint"]
    cfg_hash = telemetry_mod.config_hash(scn)
    policy = RetryPolicy(attempts=spec.get("retry_attempts", 4),
                         base_s=0.2, seed=widx,
                         max_total_seconds=spec.get("retry_budget_s"))

    # backend bring-up under the retry policy; when the attempts run
    # out the last error raises and the supervisor sees the worker die
    ann = acquire_backend(policy)

    # AOT pre-warm ($OVERSIM_AOT=1): every worker of a fleet runs the
    # same campaign graph — the first to export it feeds the rest from
    # the shared artifact store (oversim_tpu/aot/)
    from oversim_tpu import aot
    from oversim_tpu.analysis import contracts as contracts_mod
    aot_rep = aot.warmup(("campaign_tick",), ctx=contracts_mod.EntryContext(
        n=scn["n"], overlay=scn["overlay"], window=scn["engine_window"],
        inbox=8, pool_factor=8, replicas=max(len(spec["replica_ids"]), 1),
        chunk=scn["chunk"]))

    camp = _build_campaign(scn, replica_ids=spec["replica_ids"])
    fresh = camp.init()
    ticks_done, retries = 0, 0
    if os.path.exists(ckpt_path):
        state, meta = reshard_load(ckpt_path, camp,
                                   expect_config=cfg_hash, fresh=fresh)
        ticks_done = int((meta.get("fleet") or {}).get("ticks_done", 0))
    else:
        state = fresh
    # placement over whatever mesh is free NOW (1 device on a plain CPU
    # worker; the widest replica-dividing mesh on a pod)
    state, mesh = place_campaign(state)

    def checkpoint():
        ckpt_mod.save(ckpt_path, state, meta={
            "config_hash": cfg_hash,
            "campaign": camp.describe(),
            "fleet": {"ticks_done": ticks_done, "worker": widx,
                      "retries": retries}})

    last_chunk_wall = None

    def heartbeat():
        # chunk_wall_s feeds the supervisor's fleet-level
        # metric rollup (fleet.aggregate_heartbeats → obs plane)
        fleet.write_heartbeat(spec["heartbeat"], worker=widx,
                              ticks_done=ticks_done, ticks=ticks,
                              retries=retries,
                              chunk_wall_s=last_chunk_wall)

    heartbeat()
    delays = backoff_delays(policy)
    while ticks_done < ticks:
        try:
            t_c0 = time.monotonic()
            nxt = camp.run_chunk(state, chunk)
            jax.block_until_ready(nxt)
            last_chunk_wall = round(time.monotonic() - t_c0, 4)
        except Exception as exc:  # noqa: BLE001 — classified below
            # run_chunk DONATES its input: after any failure the old
            # buffers are unusable, so transient recovery is restore-
            # from-checkpoint, never a naive re-call
            if classify(exc) == FATAL or not os.path.exists(ckpt_path):
                raise
            if retries >= len(delays):
                raise
            time.sleep(delays[retries])
            retries += 1
            fresh = camp.init()
            state, meta = reshard_load(ckpt_path, camp,
                                       expect_config=cfg_hash,
                                       fresh=fresh)
            ticks_done = int(
                (meta.get("fleet") or {}).get("ticks_done", 0))
            state, mesh = place_campaign(state)
            continue
        state = nxt
        ticks_done += chunk
        checkpoint()
        heartbeat()

    fleet.write_json_atomic(spec["artifact"], {
        "done": True, "worker": widx,
        "replica_ids": list(spec["replica_ids"]),
        "ticks_done": ticks_done, "retries": retries,
        "elastic": ann, "aot": aot_rep,
        "leaves": fleet.encode_leaves(_final_leaves(state))})
    return 0


# --------------------------------------------------------- supervisor --


class _Worker:
    def __init__(self, idx, spec_path, log_path, spec=None):
        self.idx = idx
        self.spec_path = spec_path
        self.log_path = log_path
        self.spec = spec if spec is not None else json.load(open(spec_path))
        self.proc = None
        self.spawned_at = 0.0
        self.respawns = 0
        self.done = False
        self.kills = 0

    def spawn(self):
        log = open(self.log_path, "ab")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--worker", "--spec", self.spec_path],
            stdout=log, stderr=subprocess.STDOUT)
        log.close()
        self.spawned_at = time.monotonic()

    def alive(self):
        return self.proc is not None and self.proc.poll() is None

    def kill(self):
        if self.alive():
            os.kill(self.proc.pid, signal.SIGKILL)
            self.proc.wait()
            self.kills += 1
            return True
        return False


def _make_worker(out: Path, scn: dict, args, w: int, ids, gen: int):
    """Spec file + _Worker for shard ``w`` of generation ``gen``
    (generation-prefixed paths keep every resize's checkpoints/logs
    distinct on disk; gen 0 keeps the historical flat names)."""
    from oversim_tpu.elastic import fleet
    prefix = f"g{gen}_shard{w}" if gen else f"shard{w}"
    spec = {"worker": w, "scenario": scn, "replica_ids": list(ids),
            "ticks": args.ticks, "platform": args.platform or "cpu",
            "checkpoint": str(out / f"{prefix}.ckpt.npz"),
            "heartbeat": str(out / f"{prefix}.heartbeat.json"),
            "artifact": str(out / f"{prefix}.artifact.json")}
    if args.retry_budget_s is not None:
        spec["retry_budget_s"] = args.retry_budget_s
    spec_path = str(out / f"{prefix}.spec.json")
    fleet.write_json_atomic(spec_path, spec)
    return _Worker(w, spec_path, str(out / f"{prefix}.log"), spec)


def _supervise(args) -> int:
    from oversim_tpu.elastic import autoscaler as autoscaler_mod
    from oversim_tpu.elastic import fleet

    scn = _scenario(args)
    if args.ticks % args.chunk:
        raise SystemExit("--ticks must be a multiple of --chunk "
                         "(fixed-stride determinism contract)")
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    shards = fleet.shard_replicas(args.replicas, args.workers)
    workers = [_make_worker(out, scn, args, w, ids, 0)
               for w, ids in enumerate(shards)]
    finished: list = []            # workers whose artifact says done

    # live observability: the supervisor aggregates per-worker heartbeat
    # JSON into fleet-level series each poll; chaos/respawn/hang events
    # land in the flight ring (--metrics-port 0 = ephemeral port)
    obs = None
    fleet_gauges = {}
    if args.metrics_port is not None or args.flight:
        from oversim_tpu.obs import runtime as obs_runtime
        obs = obs_runtime.RunObserver(role="fleet",
                                      port=args.metrics_port,
                                      flight_path=args.flight)
        obs.set_static(workers=len(workers), replicas=args.replicas,
                       ticks=args.ticks, chaos=bool(args.chaos))
        r = obs.registry
        fleet_gauges = {
            "reporting": r.gauge("oversim_fleet_workers_reporting",
                                 "workers with a readable heartbeat"),
            "ticks_done": r.gauge("oversim_fleet_ticks_done",
                                  "summed ticks_done across heartbeats"),
            "ticks_target": r.gauge("oversim_fleet_ticks_target",
                                    "summed per-worker tick targets"),
            "retries": r.gauge("oversim_fleet_retries",
                               "summed transient-retry counts"),
            "age_max": r.gauge("oversim_fleet_heartbeat_age_max_s",
                               "oldest heartbeat age"),
        }
        print(json.dumps({"phase": "obs", "metrics_port": obs.start(),
                          "flight": args.flight}), flush=True)

    # the closed loop (ISSUE 17): hysteresis policy over the fleet's
    # own gauges; decisions re-split the live replica rows across a new
    # worker generation (plan_resize + regroup_shard_leaves) — never a
    # respawn-in-place
    autoscaler = None
    auto_gauges = {}
    auto_last = {"ups": 0, "downs": 0, "deferred": 0}
    if args.autoscale:
        autoscaler = autoscaler_mod.Autoscaler(autoscaler_mod.AutoscalePolicy(
            min_workers=args.autoscale_min,
            max_workers=args.autoscale_max,
            up_backlog_per_worker=args.autoscale_up,
            down_backlog_per_worker=args.autoscale_down,
            p99_up_s=args.autoscale_p99_s,
            cooldown_s=args.autoscale_cooldown))
        if obs is not None:
            r = obs.registry
            auto_gauges = {
                "target": r.gauge("oversim_autoscale_workers_target",
                                  "worker count the last decision chose"),
                "backlog": r.gauge("oversim_autoscale_backlog_rows",
                                   "outstanding row-ticks across shards"),
                "per_worker": r.gauge(
                    "oversim_autoscale_backlog_per_worker",
                    "backlog rows per provisioned worker"),
                "ups": r.counter("oversim_autoscale_scale_ups_total",
                                 "scale-up decisions taken"),
                "downs": r.counter("oversim_autoscale_scale_downs_total",
                                   "scale-down decisions taken"),
                "deferred": r.counter(
                    "oversim_autoscale_deferred_total",
                    "decisions deferred (alignment) or cooldown-skipped"),
            }

    def poll_obs():
        if obs is None:
            return
        agg = fleet.aggregate_heartbeats(
            {w.idx: fleet.read_json(w.spec["heartbeat"])
             for w in workers})
        fleet_gauges["reporting"].set(agg["workers_reporting"])
        fleet_gauges["ticks_done"].set(agg["ticks_done"])
        fleet_gauges["ticks_target"].set(agg["ticks_target"])
        fleet_gauges["retries"].set(agg["retries"])
        if agg["heartbeat_age_max_s"] is not None:
            fleet_gauges["age_max"].set(agg["heartbeat_age_max_s"])
        obs.set_static(fleet=agg)     # /statusz carries the full rollup

    chaos = (fleet.chaos_schedule(args.kills, len(workers),
                                  args.chaos_seed, span_s=args.chaos_span)
             if args.chaos else [])
    print(json.dumps({"phase": "fleet_start", "workers": len(workers),
                      "shards": [list(s) for s in shards],
                      "chaos": chaos}), flush=True)
    if obs is not None:
        obs.record("fleet_start", workers=len(workers),
                   chaos_kills=len(chaos))

    t0 = time.monotonic()
    gen = 0
    resizes: list = []

    def sweep_done():
        """Move workers whose artifact says done into ``finished``."""
        for w in list(workers):
            art = fleet.read_json(w.spec["artifact"])
            if art and art.get("done"):
                w.done = True
                if w.alive():
                    w.proc.wait()
                workers.remove(w)
                finished.append(w)

    def apply_resize(decision):
        """Execute one autoscale decision: SIGKILL the live generation,
        regroup its checkpointed rows into the new shard layout
        (fleet.plan_resize + fleet.regroup_shard_leaves), spawn the next
        generation.  Atomic per-chunk checkpoints make the freeze safe:
        at most the in-flight chunk is redone.  Returns the index of
        the chaos kill landed DURING the reshard (or None)."""
        from oversim_tpu import checkpoint as ckpt_mod
        from oversim_tpu import telemetry as telemetry_mod
        for w in workers:
            w.kill()
        # kill happens-before this sweep, so a shard that finished in
        # the race window keeps its artifact and leaves the resize set
        sweep_done()
        if not workers:
            return None
        cfg_hash = telemetry_mod.config_hash(scn)
        old = []                       # (ids, leaves | None, ticks_done)
        row_ticks = {}
        for w in workers:
            ids = [int(i) for i in w.spec["replica_ids"]]
            leaves, td = None, 0
            if os.path.exists(w.spec["checkpoint"]):
                try:
                    leaves, meta = ckpt_mod.load_raw(w.spec["checkpoint"])
                    td = int((meta.get("fleet") or {}).get("ticks_done", 0))
                except (OSError, ValueError):
                    leaves, td = None, 0   # torn/foreign: redo from seed
            old.append((ids, leaves, td))
            for gid in ids:
                row_ticks[gid] = td
        plan = fleet.plan_resize(row_ticks, decision.to_workers)
        with_leaves = [(ids, lv) for ids, lv, _td in old if lv is not None]
        new_workers = []
        for w, (ids, td) in enumerate(plan):
            wk = _make_worker(out, scn, args, w, ids, gen)
            if td > 0:
                # synthesized meta carries exactly what reshard_load
                # checks (base seed + replica ids) plus the resume point
                lv = fleet.regroup_shard_leaves(with_leaves, ids)
                ckpt_mod.save(wk.spec["checkpoint"], lv, meta={
                    "config_hash": cfg_hash,
                    "campaign": {"base_seed": scn["seed"],
                                 "replica_ids": list(ids)},
                    "fleet": {"ticks_done": td, "worker": w,
                              "retries": 0, "resize_gen": gen}})
            # seed the heartbeat with the KNOWN resume point: until the
            # worker's first own heartbeat (a whole compile away), the
            # backlog signal must not read "nothing done" — that lie is
            # exactly what would make the loop flap through generations
            fleet.write_heartbeat(wk.spec["heartbeat"], worker=w,
                                  ticks_done=td, ticks=args.ticks,
                                  retries=0)
            new_workers.append(wk)
        for wk in new_workers:
            wk.spawn()
        # chaos ∩ resize (ISSUE 17 satellite): SIGKILL one just-spawned
        # worker of the new generation — a failure DURING the live
        # reshard; the ordinary respawn path must recover it from the
        # generation checkpoint it was spawned with
        resize_kill = None
        if args.chaos and new_workers:
            rnd = random.Random(args.chaos_seed + gen)
            resize_kill = rnd.randrange(len(new_workers))
            new_workers[resize_kill].kill()
        workers[:] = new_workers
        return resize_kill

    def autoscale_tick(now):
        """One scrape → signals → at most one decision → resize."""
        nonlocal gen
        backlog, alive, walls, tds, rows = 0, 0, [], [], 0
        for w in workers:
            hb = fleet.read_json(w.spec["heartbeat"]) or {}
            td = int(hb.get("ticks_done", 0))
            tds.append(td)
            rows += len(w.spec["replica_ids"])
            backlog += (len(w.spec["replica_ids"])
                        * max(0, args.ticks - td))
            if w.alive():
                alive += 1
            if hb.get("chunk_wall_s") is not None:
                walls.append(float(hb["chunk_wall_s"]))
        if auto_gauges:
            auto_gauges["backlog"].set(backlog)
            auto_gauges["per_worker"].set(backlog / max(1, len(workers)))
        # CLOSED loop: when the endpoint is up, decide off the fleet's
        # own /metrics exposition — the same bytes an external scraper
        # reads — with the host-side rollup as the fallback signal
        sig_backlog = float(backlog)
        via_scrape = False
        if obs is not None and obs.port:
            doc = autoscaler_mod.scrape_exposition(
                f"http://127.0.0.1:{obs.port}/metrics")
            if doc and "oversim_autoscale_backlog_rows" in doc:
                sig_backlog = doc["oversim_autoscale_backlog_rows"]
                via_scrape = True
        sig = autoscaler_mod.Signals(
            backlog=sig_backlog, workers=len(workers), now_s=now,
            p99_s=max(walls) if walls else None, workers_alive=alive)
        # alignment: defer decisions a resize cannot actually honor.
        # Rows at different resume points can never share a worker, so
        # shrinking needs fewer tick classes than workers; growing
        # needs more unfinished rows than workers.  Without this gate a
        # blocked scale-down re-decides every cooldown, each no-op
        # resize killing the very progress that would unblock it.
        target, _ = autoscaler.target_for(sig)
        if target > len(workers):
            achievable = len(workers) < rows
        elif target < len(workers):
            achievable = len(set(tds)) < len(workers)
        else:
            achievable = True
        if not achievable:
            sig = dataclasses.replace(sig, aligned=False)
        decision = autoscaler.decide(sig)
        if auto_gauges:
            for key, attr in (("ups", "scale_ups"),
                              ("downs", "scale_downs"),
                              ("deferred", "deferred")):
                delta = getattr(autoscaler, attr) - auto_last[key]
                if delta > 0:
                    auto_gauges[key].inc(delta)
                    auto_last[key] += delta
        if decision is None:
            return
        print(json.dumps({"phase": "autoscale",
                          **decision.describe(),
                          "via_scrape": via_scrape}), flush=True)
        if obs is not None:
            obs.record("autoscale_decision", **decision.describe(),
                       via_scrape=via_scrape)
        gen += 1
        resize_kill = apply_resize(decision)
        resizes.append({"gen": gen, **decision.describe(),
                        "workers_after": len(workers),
                        "chaos_kill_during_resize": resize_kill})
        if auto_gauges:
            auto_gauges["target"].set(decision.to_workers)
        if obs is not None:
            obs.set_static(workers=len(workers))
            obs.record("autoscale_resize_done", gen=gen,
                       workers=len(workers),
                       chaos_kill_during_resize=resize_kill)

    for w in workers:
        w.spawn()
    pending_chaos = list(chaos)
    executed_kills = []
    fail = None
    last_auto = -1e9
    while True:
        now = time.monotonic() - t0
        # seeded chaos kills: SIGKILL scheduled workers that are still
        # running (a finished shard can't be killed — recorded as a
        # no-op so the report stays honest about delivered chaos; after
        # a resize the schedule's index folds onto the live generation)
        while pending_chaos and pending_chaos[0][0] <= now:
            delay, w_idx = pending_chaos.pop(0)
            landed = (workers[w_idx % len(workers)].kill()
                      if workers else False)
            executed_kills.append({"delay_s": round(delay, 3),
                                   "worker": w_idx, "landed": landed})
            if landed:
                print(json.dumps({"phase": "chaos_kill",
                                  "worker": w_idx,
                                  "t": round(now, 2)}), flush=True)
                if obs is not None:
                    obs.record("chaos_kill", worker=w_idx,
                               t=round(now, 2))
        sweep_done()
        for w in workers:
            if not w.alive():
                # died without finishing: reschedule; the respawn
                # resumes from the shard's latest checkpoint
                if w.respawns >= args.max_respawns:
                    fail = f"worker {w.idx} exceeded --max-respawns"
                    break
                w.respawns += 1
                print(json.dumps({"phase": "respawn", "worker": w.idx,
                                  "n": w.respawns}), flush=True)
                if obs is not None:
                    obs.record("respawn", worker=w.idx, n=w.respawns)
                w.spawn()
            elif (time.monotonic() - w.spawned_at
                    > args.heartbeat_timeout):
                age = fleet.heartbeat_age(w.spec["heartbeat"])
                if age is not None and age > args.heartbeat_timeout:
                    # hung, not dead: SIGKILL and let the respawn
                    # branch above reschedule it next poll
                    print(json.dumps({"phase": "hang_kill",
                                      "worker": w.idx,
                                      "heartbeat_age_s": round(age, 1)}),
                          flush=True)
                    if obs is not None:
                        obs.record("hang_kill", worker=w.idx,
                                   heartbeat_age_s=round(age, 1))
                    w.kill()
        poll_obs()
        if fail:
            break
        if autoscaler is not None and workers \
                and now - last_auto >= args.autoscale_interval:
            last_auto = now
            autoscale_tick(now)
        if not workers:
            break
        if now > args.deadline:
            fail = f"fleet deadline ({args.deadline}s) exceeded"
            break
        time.sleep(args.poll_s)

    if fail:
        for w in workers:
            w.kill()
        print(json.dumps({"phase": "fleet_fail", "error": fail}),
              flush=True)
        if obs is not None:
            obs.record("fleet_fail", error=fail)
            obs.close(dump_tail=True)
        return 1

    # ------------------------------------------------------- merge ----
    # the merge itself is pure host numpy, but the manifest and the
    # --verify reference touch jax — same backend setup as the workers
    # (x64 on, cpu flags) so the reference runs the workers' program
    import service_run
    service_run._setup_jax(args.platform or "cpu")
    arts = [fleet.read_json(w.spec["artifact"]) for w in finished]
    merged = fleet.merge_shard_leaves(
        [(a["replica_ids"], fleet.decode_leaves(a["leaves"]))
         for a in arts],
        total=args.replicas)
    from oversim_tpu import telemetry as telemetry_mod
    from oversim_tpu.service.loop import campaign_summarize_leaves
    summary = campaign_summarize_leaves(merged)
    elastic_ann = {
        "chaos": bool(args.chaos), "chaos_seed": args.chaos_seed,
        "kills_requested": args.kills if args.chaos else 0,
        "kills_landed": sum(1 for k in executed_kills if k["landed"]),
        "kill_log": executed_kills,
        "respawns": {Path(w.spec_path).stem: w.respawns
                     for w in finished},
        "worker_retries": {Path(w.spec_path).stem: a["retries"]
                           for w, a in zip(finished, arts)},
    }
    if autoscaler is not None:
        elastic_ann["autoscale"] = {**autoscaler.describe(),
                                    "generations": gen,
                                    "resizes": resizes}
    report = {
        "summary": summary,
        "fleet": {"workers": len(finished),
                  "shards": [list(s) for s in shards],
                  "final_shards": [list(w.spec["replica_ids"])
                                   for w in finished],
                  "ticks": args.ticks, "chunk": args.chunk,
                  "wall_s": round(time.monotonic() - t0, 2),
                  **elastic_ann},
        "manifest": telemetry_mod.run_manifest(
            config=scn, extra={"elastic": elastic_ann}),
    }

    verdict = 0
    if args.verify:
        ref_leaves = _run_reference(scn)
        ref_summary = campaign_summarize_leaves(ref_leaves)
        leaves_ok = fleet.encode_leaves(merged) == fleet.encode_leaves(
            ref_leaves)
        summary_ok = (json.dumps(summary, sort_keys=True)
                      == json.dumps(ref_summary, sort_keys=True))
        report["verify"] = {"leaves_equal": leaves_ok,
                            "summary_equal": summary_ok}
        if leaves_ok and summary_ok:
            print("VERIFY OK: fleet ensemble == uninterrupted run "
                  "(exact counter equality)", flush=True)
        else:
            print("VERIFY FAIL: fleet ensemble diverged from the "
                  "uninterrupted run", flush=True)
            verdict = 2

    fleet.write_json_atomic(str(out / "fleet_report.json"), report)
    print(json.dumps({"phase": "fleet_done",
                      "kills_landed": elastic_ann["kills_landed"],
                      "respawns": sum(w.respawns for w in finished),
                      "scale_ups": (autoscaler.scale_ups
                                    if autoscaler else 0),
                      "scale_downs": (autoscaler.scale_downs
                                      if autoscaler else 0),
                      "wall_s": report["fleet"]["wall_s"]}), flush=True)
    if obs is not None:
        obs.record("fleet_done",
                   kills_landed=elastic_ann["kills_landed"],
                   respawns=sum(w.respawns for w in finished))
        obs.close()
    return verdict


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true",
                    help="internal: run one shard (needs --spec)")
    ap.add_argument("--spec", default=None)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--ticks", type=int, default=96,
                    help="run_chunk ticks per replica (multiple of "
                    "--chunk)")
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--overlay", default="chord",
                    choices=["kademlia", "chord"])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--churn", default="none")
    ap.add_argument("--lifetime", type=float, default=10_000.0)
    ap.add_argument("--interval", type=float, default=0.2)
    ap.add_argument("--engine-window", type=float, default=0.2)
    ap.add_argument("--platform", default=None)
    ap.add_argument("--out", default="/tmp/oversim_fleet")
    ap.add_argument("--chaos", action="store_true",
                    help="seeded random SIGKILLs of running workers")
    ap.add_argument("--kills", type=int, default=1)
    ap.add_argument("--chaos-seed", type=int, default=7)
    ap.add_argument("--chaos-span", type=float, default=6.0)
    ap.add_argument("--verify", action="store_true",
                    help="also run the uninterrupted reference and "
                    "demand exact ensemble equality")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics /healthz /statusz with "
                    "fleet-level heartbeat rollups (0 = ephemeral)")
    ap.add_argument("--flight", default=None,
                    help="JSONL flight-recorder path (chaos/respawn/"
                    "hang events)")
    ap.add_argument("--heartbeat-timeout", type=float, default=120.0)
    ap.add_argument("--max-respawns", type=int, default=8)
    ap.add_argument("--deadline", type=float, default=900.0)
    ap.add_argument("--poll-s", type=float, default=0.2)
    ap.add_argument("--autoscale", action="store_true",
                    help="closed-loop autoscaling: hysteresis policy "
                    "over the fleet's own gauges grows/shrinks the "
                    "worker set mid-campaign (live reshard)")
    ap.add_argument("--autoscale-min", type=int, default=1)
    ap.add_argument("--autoscale-max", type=int, default=4)
    ap.add_argument("--autoscale-up", type=float, default=256.0,
                    help="scale-up threshold: backlog rows per worker")
    ap.add_argument("--autoscale-down", type=float, default=64.0,
                    help="scale-down threshold (hysteresis band floor)")
    ap.add_argument("--autoscale-cooldown", type=float, default=5.0)
    ap.add_argument("--autoscale-interval", type=float, default=0.5,
                    help="seconds between autoscaler scrapes")
    ap.add_argument("--autoscale-p99-s", type=float, default=None,
                    help="optional latency trigger: scale up when the "
                    "slowest heartbeat chunk_wall_s exceeds this")
    ap.add_argument("--retry-budget-s", type=float, default=None,
                    help="total-wall-clock retry budget per worker "
                    "(RetryPolicy.max_total_seconds)")
    args = ap.parse_args()
    if args.worker:
        if not args.spec:
            raise SystemExit("--worker needs --spec")
        return _worker_main(args.spec)
    return _supervise(args)


if __name__ == "__main__":
    sys.exit(main())
