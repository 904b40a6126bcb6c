"""End-to-end Chord slice: ring formation + KBR one-way delivery.

Mirrors the reference's self-validating workload strategy (SURVEY.md §4):
KBRTestApp checks deliveries against the global oracle; here we addition-
ally assert ring-pointer correctness against the sorted key order, the
analogue of the fingerprint regression runs (simulations/verify.ini).
"""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.core import keys as K
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.chord import ChordLogic, READY


@pytest.fixture(scope="module")
def chord_run():
    logic = ChordLogic()
    cp = churn_mod.ChurnParams(model="none", target_num=8, init_interval=1.0)
    ep = sim_mod.EngineParams(window=0.050, transition_time=20.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=7)
    st = s.run_until(st, 300.0, chunk=128)
    return s, st


def test_all_nodes_ready(chord_run):
    _, st = chord_run
    assert np.asarray(st.alive).sum() == 8
    assert (np.asarray(st.logic.state) == READY).all()


def test_ring_pointers_correct(chord_run):
    _, st = chord_run
    keys_int = [K.to_int(k) for k in np.asarray(st.node_keys)]
    order = sorted(range(len(keys_int)), key=lambda i: keys_int[i])
    succ = np.asarray(st.logic.succ)
    pred = np.asarray(st.logic.pred)
    for pos, i in enumerate(order):
        assert succ[i, 0] == order[(pos + 1) % len(order)], \
            f"node {i} successor wrong"
        assert pred[i] == order[(pos - 1) % len(order)], \
            f"node {i} predecessor wrong"


def test_deliveries(chord_run):
    s, st = chord_run
    out = s.summary(st)
    assert out["kbr_sent"] > 20
    # the run stops at a chunk boundary: the last send(s) may still be in
    # flight (the reference has the same end-of-run truncation)
    assert out["kbr_delivered"] >= out["kbr_sent"] - 2
    assert out["kbr_delivered"] <= out["kbr_sent"]
    assert out["kbr_wrong_node"] == 0
    assert out["kbr_lookup_failed"] == 0
    # small ring: every lookup must finish within a few hops
    assert out["kbr_hopcount"]["max"] <= 4


def test_no_engine_losses(chord_run):
    s, st = chord_run
    eng = s.summary(st)["_engine"]
    assert eng["pool_overflow"] == 0
    assert eng["outbox_overflow"] == 0
    assert eng["queue_lost"] == 0
