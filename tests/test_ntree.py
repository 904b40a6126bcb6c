"""NTree quadtree game overlay: registration soft-state, divide/collapse
dynamics, event dissemination (reference src/overlay/ntree —
NTree.h:124-137 group division/collapse)."""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.ntree import NTreeApp, NTreeParams
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.chord import ChordLogic, READY

N = 16


@pytest.fixture(scope="module")
def ntree_run():
    # max_children 3 with 16 players in a 1000-field: the root cell must
    # divide; collapse_below high enough that deep sparse cells collapse
    app = NTreeApp(NTreeParams(max_children=3, collapse_below=1,
                               move_interval=10.0, refresh=10.0,
                               event_interval=10.0))
    logic = ChordLogic(app=app)
    cp = churn_mod.ChurnParams(model="none", target_num=N, init_interval=0.5)
    # sized for XLA-CPU: window 0.1 and chunk 128 bound the tick count,
    # inbox_slots 2 (engine default 8) shrinks the handler unrolled over
    # the inbox slots — a third message in one 100 ms window is deferred
    # to the next tick, never lost.  The 16 players have joined by second
    # 8 and measurement opens at 48; an event per player per 10 s from
    # there to 130 s and more is over a hundred for the > 20 below
    ep = sim_mod.EngineParams(window=0.100, transition_time=40.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=41)
    st = s.run_until(st, 130.0, chunk=128)
    return s, st


def test_all_ready_and_registered(ntree_run):
    s, st = ntree_run
    out = s.summary(st)
    assert (np.asarray(st.logic.state) == READY).all()
    assert out["ntree_registers"] > N, out
    # most players end registered in some cell
    cell = np.asarray(st.logic.app.cell)
    assert (cell >= 0).sum() >= N - 2, cell


def test_tree_divides_under_load(ntree_run):
    """16 players >> max_children=3 at the root: the quadtree must have
    divided — players sit at depth > 0 (group division,
    NTree.h:124-137)."""
    s, st = ntree_run
    out = s.summary(st)
    assert out["ntree_divides"] > 0, out
    depth = np.asarray(st.logic.app.depth)
    assert (depth > 0).sum() >= N // 2, depth


def test_events_disseminate(ntree_run):
    """Game events reach the cell's registered members through the
    leader fan-out."""
    s, st = ntree_run
    out = s.summary(st)
    assert out["ntree_events"] > 20, out
    assert out["ntree_event_delivered"] > 0, out
    # mean group size must stay near/below the divide threshold once
    # the tree settles
    gs = out["ntree_group_size"]
    assert gs["count"] > 0
    assert gs["mean"] <= 8.0, gs


def test_no_engine_losses(ntree_run):
    s, st = ntree_run
    out = s.summary(st)
    assert out["_engine"]["pool_overflow"] == 0
    assert out["_engine"]["outbox_overflow"] == 0
