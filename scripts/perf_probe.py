"""Timing probe: compile vs per-tick execution cost on the live backend.

Usage: python scripts/perf_probe.py [n] [chunk] [overlay]
Prints timestamped stages so a hang is attributable to a stage.
OVERSIM_PROFILE=1 appends a per-phase tick-time breakdown JSON line
(oversim_tpu/profiling.py).

OVERSIM_PROBE_ARTIFACT=path persists every stage record to ``path``
through bench.py's ArtifactWriter (atomic tmp+rename after every add,
with a run_manifest attached) — a hang or SIGKILL mid-probe leaves a
valid partial artifact naming the last completed stage.

OVERSIM_PROBE_REPLICAS="1,4,8" appends the CAMPAIGN stage: for each S it
compiles the vmapped S-replica program (oversim_tpu/campaign/), then
reports compile wall, time-to-first-chunk and steady ms/tick — the
S=1-vs-S>=4 compile-amortization table for PERFORMANCE.md (vmapping the
tick multiplies the measurement streams, not the compile count).
"""

import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

T0 = time.time()


def log(msg):
    print(f"[{time.time() - T0:7.1f}s] {msg}", flush=True)


# hostcache.enable owns the pre-import ritual (zstandard poison, x64,
# persistent compilation cache at hostcache.cache_dir())
from oversim_tpu import hostcache  # noqa: E402

hostcache.enable(persistent=True)
import jax  # noqa: E402

n = int(sys.argv[1]) if len(sys.argv) > 1 else 1024
chunk = int(sys.argv[2]) if len(sys.argv) > 2 else 16
overlay = sys.argv[3] if len(sys.argv) > 3 else "kademlia"

# backend bring-up under the elastic retry policy: transient failures
# retry with backoff, exhausted attempts raise (oversim_tpu/elastic/)
from oversim_tpu import elastic  # noqa: E402

elastic_ann = elastic.acquire_backend(elastic.RetryPolicy(attempts=3,
                                                          base_s=0.2))
dev = jax.devices()[0]
log(f"backend up: {dev} platform={dev.platform}")

from bench import ArtifactWriter  # noqa: E402
from oversim_tpu import aot  # noqa: E402
from oversim_tpu import telemetry as telemetry_mod  # noqa: E402
from oversim_tpu.analysis import contracts as _contracts  # noqa: E402

# AOT pre-warm of the two entries this probe compiles — default ON in
# the bench drivers (ROADMAP item 1); OVERSIM_AOT=0 opts out
aot_rep = aot.warmup(("solo_chunk", "run_until_device"),
                     ctx=_contracts.EntryContext(
                         n=n, overlay=overlay, window=0.05, inbox=4,
                         pool_factor=4, chunk=chunk),
                     enabled=aot.enabled_by_env(
                         {"OVERSIM_AOT":
                          os.environ.get("OVERSIM_AOT", "1")}))

artifact = ArtifactWriter(os.environ.get("OVERSIM_PROBE_ARTIFACT"))
artifact.set_manifest(telemetry_mod.run_manifest(
    config={"probe": "perf_probe", "n": n, "chunk": chunk,
            "overlay": overlay, "platform": dev.platform},
    artifacts={"report": os.environ.get("OVERSIM_PROBE_ARTIFACT")},
    extra={"aot": aot_rep, "elastic": elastic_ann}))

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps import kbrtest
from oversim_tpu.apps.kbrtest import KbrTestApp
from oversim_tpu.common import lookup as lk_mod
from oversim_tpu.engine import sim as sim_mod

app = KbrTestApp(kbrtest.KbrTestParams(test_interval=0.2))
if overlay == "chord":
    from oversim_tpu.overlay.chord import ChordLogic
    logic = ChordLogic(app=app, lcfg=lk_mod.LookupConfig(slots=8))
else:
    from oversim_tpu.overlay.kademlia import KademliaLogic
    logic = KademliaLogic(app=app,
                          lcfg=lk_mod.LookupConfig(slots=8, merge=True))
cp = churn_mod.ChurnParams(model="none", target_num=n,
                           init_interval=20.0 / n, init_deviation=2.0 / n)
ep = sim_mod.EngineParams(window=0.05, inbox_slots=4, pool_factor=4)
sim = sim_mod.Simulation(logic, cp, engine_params=ep)

s = sim.init(seed=7)
jax.block_until_ready(s.t_now)
log("init done")

lowered = sim.run_chunk.lower(sim, s, chunk)
log("lowered (traced)")
compiled = lowered.compile()
log("compiled")
try:
    txt = compiled.as_text()
    log(f"hlo ops≈{txt.count(chr(10))} lines")
except Exception as e:  # a backend may not expose text
    log(f"as_text unavailable: {e}")

# run via the normal path so the jit cache is used
t = time.perf_counter()
s = sim.run_chunk(s, chunk)
jax.block_until_ready(s.t_now)
first_chunk_s = time.perf_counter() - t
log(f"chunk1 ({chunk} ticks): {first_chunk_s:.3f}s")
artifact.add({"stage": "first_chunk", "wall_s": round(first_chunk_s, 3)})
steady = []
for i in range(4):
    t = time.perf_counter()
    s = sim.run_chunk(s, chunk)
    jax.block_until_ready(s.t_now)
    dt = time.perf_counter() - t
    steady.append(round(dt / chunk * 1e3, 2))
    log(f"chunk{i + 2}: {dt:.3f}s = {dt / chunk * 1e3:.1f} ms/tick")
artifact.add({"stage": "steady_chunks", "ms_per_tick": steady})

# device-resident loop: the same 4-chunk span as ONE dispatch
# (run_until_device's lax.while_loop) — the gap vs 4x run_chunk is the
# per-chunk host dispatch + sync overhead the bench loop no longer pays
target_s = float(s.t_now) / 1e9 + 4 * chunk * sim.ep.window
t = time.perf_counter()
s = sim.run_until_device(s, target_s, chunk=chunk)
jax.block_until_ready(s.t_now)
dt = time.perf_counter() - t
log(f"run_until_device (4 chunks, 1 dispatch): {dt:.3f}s = "
    f"{dt / (4 * chunk) * 1e3:.1f} ms/tick")
artifact.add({"stage": "run_until_device",
              "ms_per_tick": round(dt / (4 * chunk) * 1e3, 2)})

from oversim_tpu import profiling  # noqa: E402

if profiling.enabled():
    log("profiling phases (OVERSIM_PROFILE=1) ...")
    report, s = profiling.profile_ticks(sim, s, n_ticks=4)
    import json

    print(json.dumps(report), flush=True)
    artifact.add(report)

out = sim.summary(s)
log(f"summary: alive={out['_alive']} ticks={out['_ticks']} "
    f"sent={out.get('kbr_sent')} delivered={out.get('kbr_delivered')}")
artifact.add({"stage": "summary", "alive": out["_alive"],
              "ticks": out["_ticks"],
              "sent": int(out.get("kbr_sent", 0)),
              "delivered": int(out.get("kbr_delivered", 0))})

# -- campaign stage: compile amortization over the replica axis -------------
replicas_env = os.environ.get("OVERSIM_PROBE_REPLICAS")
if replicas_env:
    import json

    from oversim_tpu.campaign import Campaign, CampaignParams
    from oversim_tpu.parallel import mesh as mesh_mod

    rows = []
    for s_rep in [int(x) for x in replicas_env.replace(",", " ").split()]:
        camp = Campaign(sim, CampaignParams(replicas=s_rep, base_seed=7))
        t = time.perf_counter()
        cs = camp.init()
        jax.block_until_ready(cs.t_now)
        init_wall = time.perf_counter() - t
        avail = len(jax.devices())
        n_dev = max(d for d in range(1, min(avail, camp.s) + 1)
                    if camp.s % d == 0)
        if n_dev > 1:
            cs = mesh_mod.shard_campaign_state(
                cs, mesh_mod.make_replica_mesh(n_dev))
        # first chunk = compile + run (time-to-first-window); later
        # chunks = steady state
        t = time.perf_counter()
        cs = camp.run_chunk(cs, chunk)
        jax.block_until_ready(cs.t_now)
        first_wall = time.perf_counter() - t
        t = time.perf_counter()
        for _ in range(3):
            cs = camp.run_chunk(cs, chunk)
        jax.block_until_ready(cs.t_now)
        steady = (time.perf_counter() - t) / (3 * chunk)
        row = {"replicas": s_rep, "devices": n_dev,
               "init_wall_s": round(init_wall, 2),
               "first_chunk_wall_s": round(first_wall, 2),
               "steady_ms_per_tick": round(steady * 1e3, 2),
               "replica_ticks_per_sec": round(s_rep / steady, 1)}
        rows.append(row)
        log(f"campaign S={s_rep} ({n_dev} dev): init {init_wall:.2f}s, "
            f"first chunk {first_wall:.2f}s, steady "
            f"{steady * 1e3:.2f} ms/tick "
            f"({s_rep / steady:.0f} replica-ticks/s)")
    print(json.dumps({"campaign_probe": rows, "n": n, "chunk": chunk,
                      "overlay": overlay}), flush=True)
    artifact.add({"stage": "campaign_probe", "rows": rows})

artifact.finish()
