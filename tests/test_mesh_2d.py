"""The 2D (replica x node) mesh: placement pins + sharded-tick
bit-identity against the solo oracle, on the 8-device virtual CPU mesh
(see test_mesh.py)."""

import jax
import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.chord import ChordLogic
from oversim_tpu.parallel import mesh as mesh_mod

N2D = 16   # churn target; engine headroom doubles it -> N=32, 4/shard
K2D = 8
TICKS_2D = 64


def _make_churn_sim(overlay="chord", n=N2D):
    app = KbrTestApp(KbrTestParams(test_interval=1.0))
    if overlay == "kademlia":
        from oversim_tpu.overlay.kademlia import KademliaLogic
        logic = KademliaLogic(app=app)
    else:
        logic = ChordLogic(app=app)
    cp = churn_mod.ChurnParams(model="lifetime", target_num=n,
                               init_interval=0.2, lifetime_mean=8.0)
    # dense by name: the hand-sharded tick refuses the awake-set plane,
    # which the engine's default gives these logics
    ep = sim_mod.EngineParams(window=0.1, inbox_slots=4, pool_factor=4,
                              tick_impl="dense")
    return sim_mod.Simulation(logic, cp, engine_params=ep)


def test_make_mesh_2d_shape():
    mesh = mesh_mod.make_mesh_2d(2, 4)
    assert mesh.axis_names == (mesh_mod.REPLICA_AXIS, mesh_mod.NODE_AXIS)
    assert mesh.shape[mesh_mod.REPLICA_AXIS] == 2
    assert mesh.shape[mesh_mod.NODE_AXIS] == 4
    assert mesh_mod.make_mesh_2d(1, 8).shape[mesh_mod.NODE_AXIS] == 8
    with pytest.raises(ValueError):
        mesh_mod.make_mesh_2d(4, 4)   # 16 > 8 virtual devices


def test_state_pspecs_2d_placement():
    sim = _make_churn_sim()
    st = jax.eval_shape(sim.init_from_rng, jax.random.PRNGKey(0))
    sp = mesh_mod.state_pspecs_2d(st)
    P, NODE = mesh_mod.P, mesh_mod.NODE_AXIS
    # the dominant bytes shard: every pool leaf on its leading [P] dim
    for leaf in jax.tree.leaves(
            jax.tree.map(lambda s: s, sp.pool)):
        assert leaf[0] == NODE
    # replication ledger: cross-indexed [N] planes + scalars replicated
    assert sp.alive == P()
    assert sp.node_keys == P()
    assert sp.t_now == P()
    # per-node logic rows shard; at least one leaf must
    n = st.alive.shape[0]
    node_leaves = [l for l, s in zip(jax.tree.leaves(sp.logic),
                                     jax.tree.leaves(st.logic))
                   if s.shape and s.shape[0] == n]
    assert node_leaves and all(l[0] == NODE for l in node_leaves)


def test_state_shardings_2d_refuses_indivisible():
    sim = _make_churn_sim()   # target 16 -> N=32 with headroom
    st = jax.eval_shape(sim.init_from_rng, jax.random.PRNGKey(0))
    assert st.alive.shape[0] % 3 != 0
    with pytest.raises(ValueError, match="not divisible"):
        mesh_mod.state_shardings_2d(st, mesh_mod.make_mesh_2d(1, 3))


def test_campaign_pspecs_2d_placement():
    from oversim_tpu.campaign import Campaign, CampaignParams
    sim = _make_churn_sim()
    camp = Campaign(sim, CampaignParams(replicas=2, base_seed=7))
    cs = camp.init()
    sp = mesh_mod.campaign_state_pspecs_2d(cs)
    P = mesh_mod.P
    R, NODE = mesh_mod.REPLICA_AXIS, mesh_mod.NODE_AXIS
    assert sp.alive == P(R)
    assert sp.t_now == P(R)
    for leaf in jax.tree.leaves(jax.tree.map(lambda s: s, sp.pool)):
        assert leaf[0] == R and leaf[1] == NODE


def test_reshard_place_2d():
    from oversim_tpu.elastic import reshard
    sim = _make_churn_sim()
    st = sim.init(seed=3)
    st2, _mesh = reshard.place_solo(st, node_shards=K2D)
    want = mesh_mod.NamedSharding(
        mesh_mod.make_mesh_2d(1, K2D), mesh_mod.P(mesh_mod.NODE_AXIS))
    assert st2.pool.valid.sharding.is_equivalent_to(
        want, st2.pool.valid.ndim)
    # alive stays replicated across the node axis
    assert st2.alive.sharding.is_equivalent_to(
        mesh_mod.NamedSharding(mesh_mod.make_mesh_2d(1, K2D),
                               mesh_mod.P()), st2.alive.ndim)
    with pytest.raises(ValueError):
        reshard.place_solo(st, node_shards=3)    # N=32, 32 % 3 != 0
    with pytest.raises(ValueError):
        reshard.place_solo(st, node_shards=16)   # only 8 devices


def test_reshard_place_campaign_2d():
    from oversim_tpu.campaign import Campaign, CampaignParams
    from oversim_tpu.elastic import reshard
    sim = _make_churn_sim()
    camp = Campaign(sim, CampaignParams(replicas=2, base_seed=7))
    cs = camp.init()
    cs2, _mesh = reshard.place_campaign(cs, node_shards=4)
    shd = cs2.pool.valid.sharding
    assert shd.mesh.shape[mesh_mod.NODE_AXIS] == 4
    assert shd.spec[0] == mesh_mod.REPLICA_AXIS
    assert shd.spec[1] == mesh_mod.NODE_AXIS
    with pytest.raises(ValueError):
        reshard.place_campaign(cs, node_shards=5)   # N=32, 32 % 5 != 0


@pytest.mark.parametrize("overlay", ["chord", "kademlia"])
def test_sharded_tick_bit_identical(overlay):
    """THE 2D contract: 64 churned ticks through parallel/shard_tick.py
    on the (1, 8) mesh reproduce the solo oracle BIT-IDENTICALLY on
    every SimState leaf — churn joins/leaves, KBR traffic, pool
    alloc/free and stats all crossing shard boundaries."""
    from oversim_tpu.parallel.shard_tick import ShardedSim

    sim = _make_churn_sim(overlay=overlay)
    s = sim.init(seed=3)
    step = jax.jit(sim.step)
    for _ in range(TICKS_2D):
        s = step(s)
    solo = jax.device_get(s)

    ssim = ShardedSim(sim, mesh_mod.make_mesh_2d(1, K2D))
    sh = ssim.place(sim.init(seed=3))
    sstep = jax.jit(ssim.step, in_shardings=(ssim.shardings,),
                    out_shardings=ssim.shardings)
    for _ in range(TICKS_2D):
        sh = sstep(sh)
    sharded = jax.device_get(sh)

    bad = []

    def cmp(path, a, b):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype != b.dtype or a.shape != b.shape:
            bad.append((path, "meta", str(a.dtype), a.shape,
                        str(b.dtype), b.shape))
        elif not np.array_equal(a, b):
            bad.append((path, "value", int((a != b).sum())))

    jax.tree_util.tree_map_with_path(
        lambda p, a, b: cmp(jax.tree_util.keystr(p), a, b), solo, sharded)
    assert not bad, f"sharded tick diverged from solo oracle: {bad[:8]}"
    # the workload actually exercised the engine across shards
    assert int(solo.tick) == TICKS_2D
