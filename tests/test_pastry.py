"""End-to-end Pastry/Bamboo slice: leafset formation + KBR delivery.

Both routing modes run: "pastry"/"bamboo" use the reference default
SEMI_RECURSIVE with per-hop ACKs (default.ini:245-246), "pastry-iter"
pins ITERATIVE (lookup + final direct hop)."""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.core import keys as K
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.pastry import (BambooLogic, PastryLogic,
                                        PastryParams, READY)

# PR 22: moved to the slow tier.  Until PR 22 a donated-buffer bug
# (churn.T_INF) made most simulation tests of a worker fail in
# milliseconds, so tier-1 "fitted" its limit; with the bug fixed this
# module's fixture alone runs for minutes (measured more than 1900 s under the
# suite's load) and the whole suite no longer fitted.  Run with
# scripts/run_suite.sh or `pytest -m slow`.
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module", params=["pastry", "bamboo", "pastry-iter"])
def pastry_run(request):
    if request.param == "pastry":
        logic = PastryLogic()
    elif request.param == "bamboo":
        logic = BambooLogic()
    else:
        logic = PastryLogic(params=PastryParams(routing_mode="iterative"))
    cp = churn_mod.ChurnParams(model="none", target_num=8, init_interval=1.0)
    ep = sim_mod.EngineParams(window=0.010, transition_time=30.0)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=17)
    st = s.run_until(st, 300.0, chunk=512)
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
    ep = sim_mod.EngineParams(window=0.010, transition_time=60.0)
    s = sim_mod.Simulation(PastryLogic(), cp, engine_params=ep)
    st = s.init(seed=23)
    st = s.run_until(st, 400.0, chunk=512)
    return s, st


def test_semirecursive_delivery_multihop(pastry32):
    """Reference-default mode (semi-recursive + ACKs): full delivery, no
    wrong-node, no route drops under no churn."""
    s, st = pastry32
    out = s.summary(st)
    assert (np.asarray(st.logic.state) == READY).all()
    assert out["kbr_sent"] > 100
    assert out["kbr_delivered"] == out["kbr_sent"]
    assert out["kbr_wrong_node"] == 0
    assert out["route_dropped"] == 0
    # prefix routing: mean hops small but multi-hop traffic exists
    assert 1.0 <= out["kbr_hopcount"]["mean"] <= 4.0
    assert out["kbr_hopcount"]["max"] >= 2
