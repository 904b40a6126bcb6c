"""Campaign runner contracts (oversim_tpu/campaign/).

The load-bearing guarantee: ``jax.vmap`` of ``Simulation.step`` over a
leading replica axis is BIT-IDENTICAL per slice to S independent runs
with the same rngs — so a campaign's ensemble statistics are exactly the
statistics of S solo runs, at one compile.  Pinned here for chord AND
kademlia under lifetime churn over a FIXED tick count (replicas advance
on independent event horizons, so time-target runs legitimately diverge
in tick counts; ``run_chunk`` is the identity surface).

Also pinned: the ensemble reduce/summary math against plain numpy, the
sweep-override (``ov``) per-replica identity, the report()'s hop-count
histogram CI schema, and ZERO cross-replica collectives + zero
full-pool sorts in the compiled replica-sharded campaign tick
(scripts/hlo_breakdown.py counting).

NOTE this file is intentionally named test_vmap_campaign so it sorts
late in the alphabetical tier-1 run: its compiles are heavy, and the
870 s tier-1 timeout cuts the suite mid-alphabet — everything here must
stay runnable standalone without shrinking the budget of the files
before the cut.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu import stats as stats_mod
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.campaign import Campaign, CampaignParams, expand_grid
from oversim_tpu.common import lookup as lk_mod
from oversim_tpu.core import keys as keys_mod
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.engine.logic import Outbox
from oversim_tpu.parallel import mesh as mesh_mod

I32 = jnp.int32
I64 = jnp.int64
NS = 1_000_000_000


# ---------------------------------------------------------------------------
# cheap logic for the structural tests (the overlay identity tests below
# use the real chord/kademlia stacks)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PingState:
    t_next: jnp.ndarray
    joined: jnp.ndarray


class PingLogic:
    key_spec = keys_mod.KeySpec(32)

    def init(self, rng, n):
        return PingState(t_next=jnp.full((n,), sim_mod.T_INF, I64),
                         joined=jnp.zeros((n,), bool))

    def reset(self, state, mask, created, t_now, rng):
        t0 = t_now + jnp.int64(int(0.05 * NS))
        return PingState(
            t_next=jnp.where(created, t0,
                             jnp.where(mask, sim_mod.T_INF, state.t_next)),
            joined=jnp.where(mask, created, state.joined))

    def ready_mask(self, state):
        return state.joined

    def next_event(self, state):
        return state.t_next

    def stat_spec(self):
        return stats_mod.StatSpec(
            scalars=("ping.rtt",), hists=(("ping.rttBins", 8),),
            counters=("ping.sent", "ping.recv"))

    def step(self, ctx, state_n, msgs_n, rng_n, node_idx,
             *, outbox_slots, rmax):
        ob = Outbox(outbox_slots, self.key_spec.lanes, rmax)
        due = state_n.t_next < ctx.t_end
        now = jnp.maximum(state_n.t_next, ctx.t_start)
        dst = ctx.sample_ready(rng_n)
        send = due & (dst >= 0)
        ob.send(send, now, dst, 1, stamp=now)
        got = msgs_n.valid & (msgs_n.kind == 1)
        rtt = (msgs_n.t_deliver - msgs_n.stamp).astype(jnp.float32) / NS
        # swept via **.campaign.sweep.testMsgInterval (ov_get hook)
        iv = ctx.ov_get("app.testMsgInterval")
        step_ns = (jnp.int64(int(0.2 * NS)) if iv is None
                   else (jnp.asarray(iv) * NS).astype(I64))
        state_n = PingState(t_next=jnp.where(due, now + step_ns,
                                             state_n.t_next),
                            joined=state_n.joined)
        events = {
            "s:ping.rtt": (rtt, got),
            "h:ping.rttBins": ((rtt * 20).astype(I32), got),
            "c:ping.sent": send.astype(I32),
            "c:ping.recv": jnp.sum(got.astype(I32)),
        }
        return state_n, ob, events


def make_ping_sim(n=12):
    cp = churn_mod.ChurnParams(model="lifetime", target_num=n,
                               init_interval=0.2, lifetime_mean=8.0)
    ep = sim_mod.EngineParams(window=0.1, inbox_slots=4, pool_factor=4,
                              outbox_slots=8, rmax=4)
    return sim_mod.Simulation(PingLogic(), cp, engine_params=ep)


def make_overlay_sim(overlay, n=12):
    app = KbrTestApp(KbrTestParams(test_interval=0.5))
    if overlay == "chord":
        from oversim_tpu.overlay.chord import ChordLogic
        logic = ChordLogic(app=app, lcfg=lk_mod.LookupConfig(slots=4))
    else:
        from oversim_tpu.overlay.kademlia import KademliaLogic
        logic = KademliaLogic(app=app,
                              lcfg=lk_mod.LookupConfig(slots=4, merge=True))
    cp = churn_mod.ChurnParams(model="lifetime", target_num=n,
                               init_interval=0.2, lifetime_mean=8.0)
    ep = sim_mod.EngineParams(window=0.1, inbox_slots=4, pool_factor=4)
    return sim_mod.Simulation(logic, cp, engine_params=ep)


def assert_leaves_identical(a, b, label):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    bad = [jax.tree_util.keystr(path)
           for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                                   lb)
           if not np.array_equal(np.asarray(x), np.asarray(y),
                                 equal_nan=True)]
    assert not bad, f"{label}: leaves diverged: {bad}"


# ---------------------------------------------------------------------------
# bit-identity: campaign slice r == solo run from replica_rng(r)
# ---------------------------------------------------------------------------

CHUNK = 32    # ONE chunk length a runner: run_chunk compiles for each


def run_ticks(runner, state, ticks):
    for _ in range(ticks // CHUNK):
        state = runner.run_chunk(state, CHUNK)
    return state


@pytest.fixture(scope="module")
def campaign_of():
    """overlay -> its four-replica campaign, built (and its chunk
    compiled) once for the tests that run it."""
    made = {}

    def get(overlay):
        if overlay not in made:
            made[overlay] = Campaign(make_overlay_sim(overlay),
                                     CampaignParams(replicas=4, base_seed=3))
        return made[overlay]

    return get


@pytest.mark.parametrize("overlay", ["chord", "kademlia"])
def test_campaign_bit_identity_vs_solo_runs(campaign_of, overlay):
    camp = campaign_of(overlay)
    # the campaign's own simulation: the vmapped step takes the dense
    # sweep where the engine's default would give these logics the
    # awake-set plane (same results, two more counters in the layout)
    sim = camp.sim
    assert sim.tick_impl == "dense"
    cs = run_ticks(camp, camp.init(), 64)
    for r in range(camp.s):
        solo = sim_mod._dedupe_buffers(
            sim.init_from_rng(camp.replica_rng(r)))
        solo = run_ticks(sim, solo, 64)
        assert_leaves_identical(camp.replica_state(cs, r), solo,
                                f"{overlay} replica {r}")


def test_campaign_report_hop_hist_ensemble(campaign_of):
    """report()'s kbr_hop_hist carries cross-replica mean/stddev/CI that
    match a numpy recomputation from the per-replica counts."""
    camp = campaign_of("kademlia")
    cs = run_ticks(camp, camp.init(), 160)  # past init (2.4 s) + lookups
    rep = camp.report(cs)

    hh = rep["kbr_hop_hist"]
    counts = np.asarray(hh["per_replica"]["counts"], float)   # [S, B]
    totals = counts.sum(axis=1)
    assert (totals > 0).sum() >= 2, f"hop hist empty: {totals}"
    pmf = counts[totals > 0] / totals[totals > 0, None]
    k = pmf.shape[0]
    np.testing.assert_allclose(hh["mean"], pmf.mean(axis=0), atol=1e-12)
    np.testing.assert_allclose(hh["stddev"], pmf.std(axis=0, ddof=1),
                               atol=1e-12)
    sem = pmf.std(axis=0, ddof=1) / math.sqrt(k)
    np.testing.assert_allclose(hh["sem"], sem, atol=1e-12)
    t = stats_mod.t_critical(k - 1, 0.95)
    np.testing.assert_allclose(hh["ci"], t * sem, atol=1e-12)
    assert hh["k"] == k and hh["kind"] == "hist"
    # aggregate counts = sum of per-replica counts
    np.testing.assert_array_equal(hh["total"], counts.sum(axis=0))

    # the derived delivery ratio exists and averages per-replica ratios
    dr = rep["kbr_delivery_ratio"]
    sent = np.asarray(rep["kbr_sent"]["per_replica"], float)
    deliv = np.asarray(rep["kbr_delivered"]["per_replica"], float)
    exp = (deliv / sent)[sent > 0]
    np.testing.assert_allclose(dr["mean"], exp.mean(), atol=1e-12)
    assert rep["_campaign"]["s"] == 4
    assert len(rep["_campaign"]["t_sim"]) == 4


# ---------------------------------------------------------------------------
# sweep overrides: replica r == solo run with ov = replica_ov(r)
# ---------------------------------------------------------------------------

def test_campaign_sweep_matches_solo_ov_run():
    sim = make_ping_sim()
    camp = Campaign(sim, CampaignParams(
        replicas=2, base_seed=5,
        sweep=(("churn.lifetimeMean", (4.0, 16.0)),
               ("app.testMsgInterval", (0.1, 0.4)))))
    assert camp.s == 8 and len(camp.grid) == 4
    # 48 ticks = 4.8 sim-s: past the 2.4 s init phase, so the stats gate
    # is open and the interval sweep shows up in the sent counters
    cs = camp.run_chunk(camp.init(), 48)

    @jax.jit
    def solo_chunk(s, ov):
        def body(c, _):
            return sim.step(c, ov=ov), None
        s, _ = jax.lax.scan(body, s, None, length=48)
        return s

    for r in (0, 3, 5, 6):   # one replica from each grid point
        ov = camp.replica_ov(r)
        assert ov == camp.grid[r // 2]
        ov = {k: jnp.asarray(v, jnp.result_type(float))
              for k, v in ov.items()}
        solo = sim_mod._dedupe_buffers(
            sim.init_from_rng(camp.replica_rng(r), ov=ov))
        solo = solo_chunk(solo, ov)
        assert_leaves_identical(camp.replica_state(cs, r), solo,
                                f"sweep replica {r}")

    # the grid actually changes behavior: the slow-interval points must
    # send fewer pings than the fast-interval points
    sent = np.asarray(cs.stats["c:ping.sent"])
    fast = sent[0:2].sum() + sent[4:6].sum()   # interval 0.1 points
    slow = sent[2:4].sum() + sent[6:8].sum()   # interval 0.4 points
    assert fast > slow


def test_expand_grid_row_major():
    grid = expand_grid((("a", (1.0, 2.0)), ("b", (10.0, 20.0, 30.0))))
    assert len(grid) == 6
    assert grid[0] == {"a": 1.0, "b": 10.0}
    assert grid[1] == {"a": 1.0, "b": 20.0}
    assert grid[3] == {"a": 2.0, "b": 10.0}
    assert expand_grid(()) == [{}]


# ---------------------------------------------------------------------------
# ensemble math vs numpy (no simulation)
# ---------------------------------------------------------------------------

def test_ensemble_reduce_matches_numpy():
    rng = np.random.RandomState(0)
    samples = [rng.rand(m) * 10 for m in (5, 9, 0, 7)]   # replica 2 empty
    acc = np.stack([
        np.array([len(x), x.sum(), (x * x).sum(),
                  x.min() if len(x) else np.inf,
                  x.max() if len(x) else -np.inf]) for x in samples])
    hist = rng.randint(0, 50, size=(4, 6)).astype(np.int64)
    hist[2] = 0                                          # replica 2 empty
    ctr = np.array([3, 11, 0, 7], np.int64)
    stats = {"s:m": jnp.asarray(acc), "h:h": jnp.asarray(hist),
             "c:c": jnp.asarray(ctr)}
    out = stats_mod.ensemble_summary(
        jax.device_get(jax.jit(stats_mod.ensemble_reduce)(stats)))

    means = np.array([x.mean() for x in samples if len(x)])
    m = out["m"]
    assert m["k"] == 3
    np.testing.assert_allclose(m["mean"], means.mean(), rtol=1e-12)
    np.testing.assert_allclose(m["stddev"], means.std(ddof=1), rtol=1e-12)
    np.testing.assert_allclose(m["sem"], means.std(ddof=1) / math.sqrt(3),
                               rtol=1e-12)
    np.testing.assert_allclose(m["ci"],
                               stats_mod.t_critical(2) * m["sem"],
                               rtol=1e-12)
    assert m["per_replica"]["count"] == [5, 9, 0, 7]

    h = out["h"]
    pmf = hist[[0, 1, 3]] / hist[[0, 1, 3]].sum(axis=1, keepdims=True)
    np.testing.assert_allclose(h["mean"], pmf.mean(axis=0), rtol=1e-12)
    np.testing.assert_allclose(h["stddev"], pmf.std(axis=0, ddof=1),
                               atol=1e-12)
    np.testing.assert_array_equal(h["total"], hist.sum(axis=0))

    c = out["c"]
    assert c["total"] == int(ctr.sum())
    np.testing.assert_allclose(c["mean"], ctr.mean(), rtol=1e-12)
    np.testing.assert_allclose(c["stddev"], ctr.std(ddof=1), rtol=1e-12)


def test_t_critical_table():
    assert stats_mod.t_critical(1) == pytest.approx(12.706)
    assert stats_mod.t_critical(10) == pytest.approx(2.228)
    assert stats_mod.t_critical(100) == pytest.approx(1.960)
    assert stats_mod.t_critical(5, 0.99) == pytest.approx(4.032)
    assert math.isnan(stats_mod.t_critical(0))
    with pytest.raises(ValueError):
        stats_mod.t_critical(5, 0.90)


# ---------------------------------------------------------------------------
# sharding: replica axis = pure data parallelism, zero collectives
# ---------------------------------------------------------------------------

def test_campaign_tick_sharded_zero_collectives():
    """The compiled replica-sharded campaign tick must contain ZERO
    cross-replica collectives and zero full-pool sorts — the HLO budget
    scripts/hlo_breakdown.py --campaign pins in CI form."""
    from scripts.hlo_breakdown import check_budget

    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices (conftest forces 8 host devices)")
    sim = make_ping_sim()
    camp = Campaign(sim, CampaignParams(replicas=4, base_seed=7))
    cs = camp.init()
    mesh = mesh_mod.make_replica_mesh(4)
    sh = mesh_mod.campaign_state_shardings(cs, mesh)
    txt = (jax.jit(camp._vstep, in_shardings=(sh,), out_shardings=sh)
           .lower(cs).compile().as_text())
    pool_dim = sim.ep.pool_factor * 12
    ok, counts = check_budget(txt, pool_dim, 0, 200, max_collectives=0)
    assert ok, f"campaign tick over budget: {counts}"
    assert counts["collective_count"] == 0
    assert counts["full_pool_sort_count"] == 0


def test_make_replica_mesh_and_shardings():
    mesh = mesh_mod.make_replica_mesh(4)
    assert mesh.axis_names == (mesh_mod.REPLICA_AXIS,)
    assert mesh.devices.size == 4
    sim = make_ping_sim()
    camp = Campaign(sim, CampaignParams(replicas=8))
    cs = camp.init()
    cs = mesh_mod.shard_campaign_state(cs, mesh)
    # leading [S=8] axis split 4 ways -> per-shard leading dim 2
    shard = cs.t_now.addressable_shards[0]
    assert shard.data.shape == (2,)
    cs = camp.run_chunk(cs, 2)   # sharded state steps fine
    assert int(np.asarray(cs.tick).min()) == 2
