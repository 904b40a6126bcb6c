"""End-to-end Kademlia slice: table formation + KBR one-way delivery.

Self-validating-workload strategy (SURVEY.md §4): deliveries are checked
against sibling responsibility; table contents are checked against the
global key oracle (the analogue of GlobalNodeList-based verification).
"""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.core import keys as K
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.kademlia import KademliaLogic, READY


@pytest.fixture(scope="module")
def kad_run():
    logic = KademliaLogic()
    cp = churn_mod.ChurnParams(model="none", target_num=8, init_interval=1.0)
    ep = sim_mod.EngineParams(window=0.050, transition_time=20.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=11)
    st = s.run_until(st, 300.0, chunk=128)
    return s, st


def test_all_nodes_ready(kad_run):
    _, st = kad_run
    assert np.asarray(st.alive).sum() == 8
    assert (np.asarray(st.logic.state) == READY).all()


def test_sibling_tables_complete(kad_run):
    """8 nodes, s=8: every node must know all 7 others as siblings."""
    _, st = kad_run
    sib = np.asarray(st.logic.sib)
    for i in range(8):
        known = {x for x in sib[i] if x >= 0}
        assert known == set(range(8)) - {i}, f"node {i}: {known}"


def test_sibling_tables_sorted_by_xor(kad_run):
    _, st = kad_run
    keys_int = [K.to_int(k) for k in np.asarray(st.node_keys)]
    sib = np.asarray(st.logic.sib)
    for i in range(8):
        entries = [x for x in sib[i] if x >= 0]
        dists = [keys_int[i] ^ keys_int[x] for x in entries]
        assert dists == sorted(dists), f"node {i} sibling table unsorted"


def test_deliveries(kad_run):
    s, st = kad_run
    out = s.summary(st)
    assert out["kbr_sent"] > 20
    # the run stops at a chunk boundary: the last send(s) may still be in
    # flight (the reference has the same end-of-run truncation)
    assert out["kbr_delivered"] >= out["kbr_sent"] - 2
    assert out["kbr_delivered"] <= out["kbr_sent"]
    assert out["kbr_wrong_node"] == 0
    assert out["kbr_lookup_failed"] == 0
    # everyone knows everyone: lookups resolve in at most a hop or two
    assert out["kbr_hopcount"]["max"] <= 2


def test_no_engine_losses(kad_run):
    s, st = kad_run
    eng = s.summary(st)["_engine"]
    assert eng["pool_overflow"] == 0
    assert eng["outbox_overflow"] == 0
    assert eng["queue_lost"] == 0
