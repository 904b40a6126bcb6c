"""Fault-injection tests: node-type partitions + heal, graceful-leave DHT
handover, malicious-node attacks (reference: partition.trace +
connectionMatrix, NF_OVERLAY_NODE_GRACEFUL_LEAVE, BaseOverlay.h:203-206)."""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.dht import DhtApp, DhtParams
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.common.malicious import MaliciousParams
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.chord import ChordLogic
from oversim_tpu.underlay import simple as underlay_mod


def test_partition_and_heal():
    """Split 16 nodes into two 8-node types at t=80s, heal at t=160s.
    During the split cross-type traffic must drop (partition_lost > 0);
    after healing, deliveries must flow again."""
    up = underlay_mod.UnderlayParams(
        num_node_types=2, type_boundaries=(8,),
        partition_events=(
            (80.0, 0, 1, False), (80.0, 1, 0, False),
            (160.0, 0, 1, True), (160.0, 1, 0, True)))
    cp = churn_mod.ChurnParams(model="none", target_num=16,
                               init_interval=0.5)
    logic = ChordLogic(app=KbrTestApp(KbrTestParams(test_interval=15.0)))
    # the 16 nodes have joined by second 8, measurement opens at 38: each
    # of the three stretches (whole, split, healed) holds 40 to 80 s of
    # one test per node per 15 s
    s = sim_mod.Simulation(logic, cp, up,
                           sim_mod.EngineParams(window=0.1,
                                                transition_time=30.0,
                                                inbox_slots=2))
    st = s.init(seed=9)
    # stop well short of the split: run_until overshoots by up to a chunk
    st = s.run_until(st, 70.0, chunk=64)
    assert float(st.t_now) / 1e9 < 80.0
    delivered_before = s.summary(st)["kbr_delivered"]
    assert s.summary(st)["_engine"]["partition_lost"] == 0

    st = s.run_until(st, 160.0, chunk=64)
    mid = s.summary(st)
    assert mid["_engine"]["partition_lost"] > 0, mid["_engine"]

    st = s.run_until(st, 240.0, chunk=64)
    after = s.summary(st)
    # healed: deliveries keep accumulating after the merge
    assert after["kbr_delivered"] > mid["kbr_delivered"] + 10, (
        delivered_before, mid["kbr_delivered"], after["kbr_delivered"])


def test_partition_aware_bootstrap():
    """A node joining during the split must bootstrap inside its own
    partition (GlobalNodeList per-type bootstrap + connectionMatrix)."""
    import jax
    import jax.numpy as jnp
    from oversim_tpu.engine.logic import Ctx

    conn = jnp.asarray([[True, False], [False, True]])
    node_type = jnp.asarray([0] * 4 + [1] * 4, jnp.int32)
    ready = jnp.asarray([True] * 8)
    tmask = node_type[None, :] == jnp.arange(2)[:, None]
    cum_t = jnp.cumsum((ready[None, :] & tmask).astype(jnp.int32), axis=1)
    ctx = Ctx(t_start=jnp.int64(0), t_end=jnp.int64(1),
              keys=jnp.zeros((8, 5), jnp.uint32), alive=ready,
              ready=ready, ready_cumsum=jnp.cumsum(ready.astype(jnp.int32)),
              n_ready=jnp.int32(8), measuring=jnp.bool_(False),
              node_type=node_type, conn=conn, ready_cum_t=cum_t)
    for seed in range(20):
        pick = int(ctx.sample_ready(jax.random.PRNGKey(seed),
                                    jnp.int32(1)))
        assert 0 <= pick < 4, pick    # type-0 node draws type-0 peers
        pick = int(ctx.sample_ready(jax.random.PRNGKey(seed),
                                    jnp.int32(6)))
        assert 4 <= pick < 8, pick


@pytest.mark.slow
def test_dht_handover_under_churn():
    """With graceful-leave handover, DHT gets must keep succeeding while
    nodes churn (the reference's ownership-transfer KPI)."""
    cp = churn_mod.ChurnParams(model="lifetime", target_num=16,
                               init_interval=0.5, lifetime_mean=600.0,
                               graceful_leave_delay=15.0,
                               graceful_leave_probability=1.0)
    # storage sized to the workload's steady state: ~N·ttl/(interval·
    # 3 modes) ≈ 480 live keys × numReplica 4 / 16 nodes = 120 records
    # per node — the reference's DHTDataStorage is UNBOUNDED, so a
    # bounded store must not evict the live working set or get-success
    # decays with runtime regardless of protocol correctness
    logic = ChordLogic(app=DhtApp(DhtParams(test_interval=20.0,
                                            test_ttl=600.0,
                                            storage_slots=192)))
    s = sim_mod.Simulation(logic, cp,
                           engine_params=sim_mod.EngineParams(
                               window=0.05, transition_time=60.0,
                               inbox_slots=2))
    st = s.init(seed=4)
    st = s.run_until(st, 650.0, chunk=256)
    out = s.summary(st)
    assert out["dht_get_attempts"] > 20, out
    ok = out["dht_get_success"] / max(out["dht_get_attempts"], 1)
    # bar restored to the original 0.6 (VERDICT r4 next-step #1) after
    # the round-5 ownership-transfer fixes: sibling-set responsibility
    # filter (DHT.cc:746-747), Chord new-predecessor transfer (the
    # DHT.cc:779-797 err-hack path), best-of-received evaluation on GET
    # timeout (DHT::handleRpcTimeout), and workload-sized storage (the
    # reference's DHTDataStorage is unbounded).  Fresh measured run:
    # 0.679 (scripts/dev_dht_handover.py, seed 4)
    assert ok > 0.6, out


def test_malicious_sibling_attack_degrades_lookups():
    """30% isSiblingAttack nodes must push wrong-node deliveries up while
    the simulation stays stable (BaseOverlay.cc:1875-1881)."""
    mp = MaliciousParams(probability=0.3, is_sibling=True)
    logic = ChordLogic(app=KbrTestApp(KbrTestParams(test_interval=15.0)),
                       mparams=mp)
    cp = churn_mod.ChurnParams(model="none", target_num=16,
                               init_interval=0.5)
    s = sim_mod.Simulation(logic, cp,
                           engine_params=sim_mod.EngineParams(
                               window=0.1, transition_time=30.0,
                               malicious=mp, inbox_slots=2))
    st = s.init(seed=8)
    # measurement from second 38: 122 s of one test per node per 15 s
    st = s.run_until(st, 160.0, chunk=256)
    out = s.summary(st)
    n_mal = int(np.asarray(st.malicious).sum())
    assert n_mal >= 2, n_mal
    assert out["kbr_sent"] > 30
    # attackers attract traffic: wrong-node deliveries appear
    assert out["kbr_wrong_node"] > 0, out
    # honest fraction still mostly delivers somewhere (sim stays live)
    total = out["kbr_delivered"] + out["kbr_wrong_node"]
    assert total / out["kbr_sent"] > 0.5, out


@pytest.mark.slow
def test_overlay_partition_merge():
    """BootstrapList::mergeOverlayPartitions (BootstrapList.cc:171-195,
    default.ini:436-438): two rings FORM independently during a
    from-the-start network split; after the heal the merge probes must
    knit them into ONE global successor cycle — not just restore
    delivery."""
    from oversim_tpu.core import keys as K
    from oversim_tpu.overlay.chord import ChordParams

    n = 16
    up = underlay_mod.UnderlayParams(
        num_node_types=2, type_boundaries=(8,),
        partition_events=(
            (0.0, 0, 1, False), (0.0, 1, 0, False),
            (200.0, 0, 1, True), (200.0, 1, 0, True)))
    cp = churn_mod.ChurnParams(model="none", target_num=n,
                               init_interval=0.5)
    logic = ChordLogic(app=KbrTestApp(KbrTestParams(test_interval=30.0)),
                       params=ChordParams(merge_partitions=True,
                                          merge_interval=15.0))
    s = sim_mod.Simulation(logic, cp, up,
                           sim_mod.EngineParams(window=0.05,
                                                transition_time=60.0,
                                                inbox_slots=2))
    st = s.init(seed=17)
    st = s.run_until(st, 190.0, chunk=128)

    # two separate rings formed: the global successor graph is NOT one
    # 16-cycle (each side closes over its own 8 nodes)
    def cycle_ok(st):
        keys_int = [K.to_int(k) for k in np.asarray(st.node_keys)]
        order = sorted(range(n), key=lambda i: keys_int[i])
        succ = np.asarray(st.logic.succ)
        return sum(1 for pos, i in enumerate(order)
                   if succ[i, 0] != order[(pos + 1) % n])

    assert cycle_ok(st) > 0, "rings unexpectedly merged during split"

    st = s.run_until(st, 700.0, chunk=128)
    bad = cycle_ok(st)
    assert bad == 0, f"{bad}/{n} successor pointers wrong after merge"
