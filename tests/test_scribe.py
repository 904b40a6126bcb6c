"""Scribe ALM over Chord: tree formation + multicast delivery
(reference src/applications/scribe + almtest)."""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.scribe import ScribeApp, ScribeParams
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.chord import ChordLogic, READY


N = 16


@pytest.fixture(scope="module")
def scribe_run():
    app = ScribeApp(ScribeParams(num_groups=3, publish_interval=20.0,
                                 subscribe_refresh=15.0))
    logic = ChordLogic(app=app)
    cp = churn_mod.ChurnParams(model="none", target_num=N, init_interval=0.5)
    # the 16 nodes have joined by second 8 and measurement opens at 48;
    # a publish per node per 20 s from there to 140 s and more is some
    # 75 for the > 30 below
    ep = sim_mod.EngineParams(window=0.100, transition_time=40.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=13)
    st = s.run_until(st, 140.0, chunk=128)
    return s, st


def test_tree_forms(scribe_run):
    """Every member must be attached: root of its group or has a parent."""
    _, st = scribe_run
    app = st.logic.app
    group = np.asarray(app.group)
    parent = np.asarray(app.parent)
    is_root = np.asarray(app.is_root)
    assert (np.asarray(st.logic.state) == READY).all()
    attached = is_root | (parent >= 0)
    assert attached.sum() >= N - 2, (group, parent, is_root)
    # exactly one root per populated group
    for g in set(group.tolist()):
        members = group == g
        assert is_root[members].sum() <= 1, (g, is_root, group)


def test_multicast_delivers(scribe_run):
    """Published multicasts must fan out to the group (≈ group size
    receipts per publish, incl. the publisher's own loopback)."""
    s, st = scribe_run
    out = s.summary(st)
    assert out["alm_published"] > 30, out
    ratio = out["alm_received"] / out["alm_published"]
    # 16 nodes over 3 groups → mean group size ≈ 5.3; trees may briefly
    # miss members while (re)subscribing
    assert ratio > 2.0, out
    assert out["alm_hops"]["mean"] >= 1.0


def test_no_engine_losses(scribe_run):
    s, st = scribe_run
    eng = s.summary(st)["_engine"]
    assert eng["pool_overflow"] == 0
    assert eng["outbox_overflow"] == 0
