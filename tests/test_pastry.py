"""End-to-end Pastry/Bamboo slice: leafset formation + KBR delivery.

Both routing modes run: "pastry"/"bamboo" use the reference default
SEMI_RECURSIVE with per-hop ACKs (default.ini:245-246), "pastry-iter"
pins ITERATIVE (lookup + final direct hop)."""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.core import keys as K
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.pastry import (BambooLogic, PastryLogic,
                                        PastryParams, READY)


# R, messages a node consumes per tick.  Pastry's handler is unrolled
# over the R inbox slots, and on XLA-CPU the tick program's cost grows
# faster than R: at N=8 the engine default R=8 compiles in 344 s and
# runs 167 ms/tick, R=4 in 87 s and 37 ms/tick, R=2 in 43 s and
# 17 ms/tick (PR 22, CPU test durations).  At these N a window rarely
# holds more than 2 messages for one node, and a third is deferred to
# the next tick, never lost.
INBOX_SLOTS = 2


@pytest.fixture(scope="module", params=["pastry", "bamboo", "pastry-iter"])
def pastry_run(request):
    if request.param == "pastry":
        logic = PastryLogic()
    elif request.param == "bamboo":
        logic = BambooLogic()
    else:
        logic = PastryLogic(params=PastryParams(routing_mode="iterative"))
    cp = churn_mod.ChurnParams(model="none", target_num=8, init_interval=1.0)
    ep = sim_mod.EngineParams(window=0.010, transition_time=30.0,
                              inbox_slots=INBOX_SLOTS)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=17)
    st = s.run_until(st, 300.0, chunk=128)
    return s, st


def test_all_ready(pastry_run):
    _, st = pastry_run
    assert np.asarray(st.alive).sum() == 8
    assert (np.asarray(st.logic.state) == READY).all()


def test_leafsets_are_ring_neighbors(pastry_run):
    """8 nodes, leafset >= 8: every node must know all others, and
    leaf_cw[0] must be the ring successor."""
    _, st = pastry_run
    keys_int = [K.to_int(k) for k in np.asarray(st.node_keys)]
    order = sorted(range(8), key=lambda i: keys_int[i])
    cw = np.asarray(st.logic.leaf_cw)
    for pos, i in enumerate(order):
        assert cw[i, 0] == order[(pos + 1) % 8], f"node {i} cw successor"


def test_deliveries(pastry_run):
    s, st = pastry_run
    out = s.summary(st)
    assert out["kbr_sent"] > 20
    assert out["kbr_delivered"] >= out["kbr_sent"] - 2
    assert out["kbr_delivered"] <= out["kbr_sent"]
    assert out["kbr_wrong_node"] == 0
    assert out["kbr_hopcount"]["max"] <= 3


def test_no_engine_losses(pastry_run):
    s, st = pastry_run
    eng = s.summary(st)["_engine"]
    assert eng["pool_overflow"] == 0
    assert eng["outbox_overflow"] == 0


@pytest.fixture(scope="module")
def pastry32():
    """N=32 exercises real multi-hop semi-recursive forwarding (the
    routing table, not just the leafset span)."""
    cp = churn_mod.ChurnParams(model="none", target_num=32,
                               init_interval=0.4)
    # window 0.1: at N=32 nearly every 10 ms window holds an event, so
    # 10 ms ticks would be tens of thousands; the ACK timeout is 1.5 s.
    # One test per node per 20 s so 200 s send well over 100 lookups
    ep = sim_mod.EngineParams(window=0.100, transition_time=60.0,
                              inbox_slots=INBOX_SLOTS)
    app = KbrTestApp(KbrTestParams(test_interval=20.0))
    s = sim_mod.Simulation(PastryLogic(app=app), cp, engine_params=ep)
    st = s.init(seed=23)
    st = s.run_until(st, 200.0, chunk=128)
    return s, st


def test_semirecursive_delivery_multihop(pastry32):
    """Reference-default mode (semi-recursive + ACKs): full delivery, no
    wrong-node, no route drops under no churn."""
    s, st = pastry32
    out = s.summary(st)
    assert (np.asarray(st.logic.state) == READY).all()
    assert out["kbr_sent"] > 100
    # nothing failed or was dropped; the only lookups not delivered are
    # the one or two still in flight when the run stops
    assert out["kbr_sent"] - 2 <= out["kbr_delivered"] <= out["kbr_sent"]
    assert out["kbr_lookup_failed"] == 0
    assert out["kbr_wrong_node"] == 0
    assert out["route_dropped"] == 0
    # prefix routing: mean hops small but multi-hop traffic exists
    assert 1.0 <= out["kbr_hopcount"]["mean"] <= 4.0
    assert out["kbr_hopcount"]["max"] >= 2
