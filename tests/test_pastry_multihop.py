"""Pastry at N=32: real multi-hop semi-recursive forwarding (the
routing table, not just the leafset span of test_pastry.py's N=8)."""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.pastry import PastryLogic, READY
from test_pastry import INBOX_SLOTS


@pytest.fixture(scope="module")
def pastry32():
    cp = churn_mod.ChurnParams(model="none", target_num=32,
                               init_interval=0.4)
    # window 0.1: at N=32 nearly every 10 ms window holds an event, so
    # 10 ms ticks would be tens of thousands; the ACK timeout is 1.5 s.
    # The 32 nodes have joined by second 12.8 and measurement opens at
    # 42.8; one test per node per 20 s from there to 128 s (the end of
    # the run's tenth chunk) sends 128 lookups
    ep = sim_mod.EngineParams(window=0.100, transition_time=30.0,
                              inbox_slots=INBOX_SLOTS)
    app = KbrTestApp(KbrTestParams(test_interval=20.0))
    s = sim_mod.Simulation(PastryLogic(app=app), cp, engine_params=ep)
    st = s.init(seed=23)
    st = s.run_until(st, 123.0, chunk=128)
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
