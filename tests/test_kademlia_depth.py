"""Kademlia depth features: replacement cache, bucket pings, downlists,
S/Kademlia sibling verification, R/Kademlia recursive routing.

Reference mechanisms: routingAdd full-bucket branch + replacement cache
(src/overlay/kademlia/Kademlia.cc:432-700, Kademlia.h:86-89), downlist
modification (Kademlia.cc:1305-1319, 1543-1585), S/Kademlia verified
siblings (src/common/IterativeLookup.cc:295-340), R/Kademlia recursive
hook (Kademlia.cc:1022).
"""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.common import lookup as lk_mod
from oversim_tpu.common import route as rt_mod
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.kademlia import (KademliaLogic, KademliaParams,
                                          READY)


def run_sim(n=16, sim_s=240.0, seed=5, churn=None, **kw):
    logic = KademliaLogic(**kw)
    cp = churn or churn_mod.ChurnParams(model="none", target_num=n,
                                        init_interval=0.5)
    ep = sim_mod.EngineParams(window=0.100, transition_time=30.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=seed)
    st = s.run_until(st, sim_s, chunk=128)
    return s, st


@pytest.fixture(scope="module")
def depth_run():
    """One churny run with every depth knob on — shared by the feature
    assertions below (single compile on the 1-core CI box)."""
    cp = churn_mod.ChurnParams(model="lifetime", target_num=16,
                               init_interval=0.5, lifetime_mean=120.0)
    return run_sim(
        n=16, sim_s=260.0, churn=cp,
        params=KademliaParams(replacement_cands=4,
                              replacement_cache_ping=True,
                              bucket_ping_interval=30.0,
                              enable_downlists=True,
                              adaptive_timeouts=True),
        lcfg=lk_mod.LookupConfig(merge=True, verify_siblings=True))


def test_depth_run_delivers(depth_run):
    s, st = depth_run
    out = s.summary(st)
    assert out["kbr_sent"] > 30
    # churny run: most sends deliver; verified completions must work
    assert out["kbr_delivered"] >= 0.7 * out["kbr_sent"]
    assert out["_engine"]["pool_overflow"] == 0
    assert out["_engine"]["outbox_overflow"] == 0


def test_depth_ping_table_cycles(depth_run):
    """Bucket pings / downlist pings actually fire and resolve: the
    in-flight ping table must not be stuck full at run end."""
    _, st = depth_run
    dst = np.asarray(st.logic.ping_dst)
    assert (dst == -1).any(axis=1).all(), "ping table wedged full"


def test_depth_ping_timeouts_sit_where_pings_fly(depth_run):
    """A ping slot holds a node exactly while it holds a timeout:
    ``ping_to`` is written by mask beside ``ping_dst``'s scatter (no
    64-bit scatter, PR 40) and cleared with it, so the two never
    part."""
    _, st = depth_run
    dst = np.asarray(st.logic.ping_dst)
    to = np.asarray(st.logic.ping_to)
    assert ((dst >= 0) == (to < int(sim_mod.T_INF))).all()


def test_replacement_cache_populates():
    """With tiny buckets (k=1) on a 16-node static net, full buckets must
    push live candidates into the replacement cache."""
    s, st = run_sim(
        n=16, sim_s=180.0, seed=9,
        params=KademliaParams(k=1, replacement_cands=2))
    rc = np.asarray(st.logic.rc_nodes)
    alive = np.asarray(st.alive)
    assert (rc[alive] >= 0).any(), "replacement cache never populated"
    out = s.summary(st)
    assert out["kbr_delivered"] >= 0.9 * max(out["kbr_sent"], 1)


def test_verified_lookup_static():
    """S/Kademlia verification on a static net: every completion pays a
    ping round-trip but still succeeds."""
    s, st = run_sim(n=8, sim_s=200.0, seed=3,
                    lcfg=lk_mod.LookupConfig(merge=True,
                                             verify_siblings=True))
    out = s.summary(st)
    assert out["kbr_sent"] > 10
    assert out["kbr_delivered"] >= out["kbr_sent"] - 2
    assert out["kbr_wrong_node"] == 0


def test_rkademlia_recursive_delivers():
    """R/Kademlia: the recursive hook forwards app payloads hop-by-hop
    (COVERAGE.md claim made real — route engine wired into Kademlia)."""
    from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
    s, st = run_sim(n=12, sim_s=200.0, seed=7,
                    app=KbrTestApp(KbrTestParams(test_interval=30.0,
                                                 rpc_test=True)),
                    rcfg=rt_mod.RouteConfig(mode="semi"))
    out = s.summary(st)
    assert out["kbr_sent"] > 15
    assert out["kbr_delivered"] >= out["kbr_sent"] - 2
    assert out["kbr_rpc_success"] > 0          # routed RPC round trips
    assert out["_engine"]["pool_overflow"] == 0
