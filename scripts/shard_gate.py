"""2D-mesh sharded-tick gate (run_suite.sh; parallel/shard_tick.py,
ISSUE 19).

Three checks on a small chord scenario under LifetimeChurn, CPU-only,
on the 8-virtual-device mesh:

  1. IDENTITY: 64 churned ticks through ``ShardedSim`` on the (1, 8)
     ``(replica, node)`` mesh produce a SimState whose every leaf is
     bit-identical to the unsharded oracle — same delivery order, same
     rng consumption, same churn cascade.
  2. COLLECTIVE CENSUS: the compiled sharded step may carry ONLY
     ``all-reduce:min`` collectives (the min-gather primitive — no
     all-gather, no all-to-all, no sort-based exchange), at least one
     of them, and zero sorts.
  3. CROSS-REPLICA FREEDOM: on the (2, 4) campaign mesh every
     ``replica_groups`` set in the compiled HLO must stay inside ONE
     replica row — node-axis pmins never synchronize replicas.

Prints one JSON verdict line; exits non-zero on any failure.
"""

import json
import os
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

N_TICKS = 64
K = 8          # node shards for the solo identity/census checks


def _setup_jax():
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    if "xla_backend_optimization_level" not in flags:
        flags = (flags + " --xla_backend_optimization_level=0"
                 " --xla_llvm_disable_expensive_passes=true").strip()
    # identity gates need graph-structure-independent floats: cap the
    # ISA below FMA (tests/conftest.py rationale)
    if "xla_cpu_max_isa" not in flags:
        flags += " --xla_cpu_max_isa=AVX"
    os.environ["XLA_FLAGS"] = flags
    sys.modules["zstandard"] = None
    import jax
    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_enable_compilation_cache", False)
    return jax


def _build(n=16):
    from oversim_tpu import churn as churn_mod
    from oversim_tpu.engine import sim as sim_mod
    from oversim_tpu.overlay.chord import ChordLogic

    cp = churn_mod.ChurnParams(model="lifetime", target_num=n,
                               init_interval=0.2, lifetime_mean=8.0)
    # dense by name: ShardedSim refuses the awake-set plane, which the
    # engine's default gives Chord under KBRTestApp
    ep = sim_mod.EngineParams(window=0.1, inbox_slots=4, pool_factor=4,
                              tick_impl="dense")
    return sim_mod.Simulation(ChordLogic(), cp, engine_params=ep)


def _replica_rows_ok(txt, node_extent):
    """True iff every replica_groups set stays inside one replica row
    of a row-major (R, node_extent) device mesh."""
    for m in re.finditer(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}",
                         txt):
        for grp in re.findall(r"\{([^}]*)\}", m.group(1)):
            ids = [int(x) for x in grp.split(",") if x.strip()]
            if len({i // node_extent for i in ids}) > 1:
                return False
    return True


def main() -> int:
    jax = _setup_jax()
    import numpy as np

    from oversim_tpu.analysis import hlo_text
    from oversim_tpu.parallel import mesh as mesh_mod
    from oversim_tpu.parallel.shard_tick import ShardedCampaign, ShardedSim

    verdict = {"gate": "shard_tick", "n_ticks": N_TICKS,
               "node_shards": K}
    failures = []

    # -- 1. identity: every leaf bit-identical --------------------------
    mesh = mesh_mod.make_mesh_2d(1, K)
    sim = _build()
    s = sim.init(seed=3)
    step = jax.jit(sim.step)
    for _ in range(N_TICKS):
        s = step(s)
    solo = jax.device_get(s)

    ssim = ShardedSim(sim, mesh)
    sh = ssim.place(sim.init(seed=3))
    sstep = jax.jit(ssim.step, in_shardings=(ssim.shardings,),
                    out_shardings=ssim.shardings)
    for _ in range(N_TICKS):
        sh = sstep(sh)
    sharded = jax.device_get(sh)

    la, ta = jax.tree_util.tree_flatten(solo)
    lb, tb = jax.tree_util.tree_flatten(sharded)
    if ta != tb:
        failures.append("state treedef mismatch")
    bad = [i for i, (x, y) in enumerate(zip(la, lb))
           if not np.array_equal(np.asarray(x), np.asarray(y))]
    verdict["identity_ok"] = ta == tb and not bad
    if bad:
        paths = jax.tree_util.tree_flatten_with_path(solo)[0]
        failures.append(
            "divergent leaves: "
            + ", ".join(jax.tree_util.keystr(paths[i][0])
                        for i in bad[:8]))
    verdict["alive"] = int(np.sum(solo.alive))

    # -- 2. collective census: all-reduce:min ONLY, no sorts -----------
    txt = jax.jit(ssim.step, in_shardings=(ssim.shardings,),
                  out_shardings=ssim.shardings,
                  donate_argnums=(0,)).lower(
                      sim.init(seed=3)).compile().as_text()
    census = hlo_text.collective_census(txt)
    verdict["census"] = census
    verdict["sorts"] = hlo_text.hlo_op_counts(txt).get("sort", 0)
    if set(census) - {"all-reduce:min"}:
        failures.append("off-allowlist collectives in the sharded "
                        f"step: {census}")
    if census.get("all-reduce:min", 0) < 1:
        failures.append("no all-reduce:min in the sharded step — the "
                        "node axis is not actually exchanging")
    if verdict["sorts"]:
        failures.append(f"{verdict['sorts']} sorts in the sharded step")

    # -- 3. (2, 4) campaign mesh: no cross-replica groups --------------
    from oversim_tpu.campaign import Campaign, CampaignParams
    camp = Campaign(_build(), CampaignParams(replicas=2, base_seed=7))
    mesh24 = mesh_mod.make_mesh_2d(2, 4)
    scamp = ShardedCampaign(camp, mesh24)
    ctxt = jax.jit(scamp.vstep, in_shardings=(scamp.shardings,),
                   out_shardings=scamp.shardings,
                   donate_argnums=(0,)).lower(
                       scamp.place(camp.init())).compile().as_text()
    ccensus = hlo_text.collective_census(ctxt)
    verdict["campaign_census"] = ccensus
    verdict["cross_replica_free"] = _replica_rows_ok(ctxt, 4)
    if set(ccensus) - {"all-reduce:min"}:
        failures.append("off-allowlist collectives in the campaign "
                        f"step: {ccensus}")
    if not verdict["cross_replica_free"]:
        failures.append("a replica_groups set spans replica rows — "
                        "node pmins are synchronizing replicas")

    verdict["ok"] = not failures
    if failures:
        verdict["failures"] = failures
        for f in failures:
            print(f"shard_gate: FAIL {f}", file=sys.stderr)
    print(json.dumps(verdict), flush=True)
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
