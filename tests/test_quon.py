"""Quon quadrant AOI overlay: join, quadrant-binding neighbor retention,
position flow (reference src/overlay/quon — QuON quadrant softstate,
Quon.h binding/direct neighbor classification)."""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.quon import QuonLogic, QuonParams
from oversim_tpu.overlay.vast import READY

N = 16


@pytest.fixture(scope="module")
def quon_run():
    logic = QuonLogic(params=QuonParams())
    cp = churn_mod.ChurnParams(model="none", target_num=N, init_interval=0.5)
    ep = sim_mod.EngineParams(window=0.050, transition_time=60.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=37)
    st = s.run_until(st, 300.0, chunk=128)
    return s, st


def test_all_ready(quon_run):
    _, st = quon_run
    assert (np.asarray(st.logic.state) == READY).all()


def test_quadrant_binding_neighbors(quon_run):
    """QuON's defining invariant: a node keeps its nearest neighbor in
    EVERY populated quadrant (binding neighbors keep the overlay
    connected in all directions, Quon.h binding classification)."""
    _, st = quon_run
    pos = np.asarray(st.logic.pos)
    nbr = np.asarray(st.logic.nbr)
    covered = want = 0
    for i in range(N):
        known = set(int(x) for x in nbr[i] if x >= 0)
        for q in range(4):
            # nodes in quadrant q of node i
            inq = []
            for j in range(N):
                if j == i:
                    continue
                dx, dy = pos[j] - pos[i]
                if (dx > 0) * 2 + (dy > 0) == q:
                    inq.append((np.hypot(dx, dy), j))
            if not inq:
                continue
            want += 1
            nearest = min(inq)[1]
            if nearest in known:
                covered += 1
    assert want > 0
    # the nearest-per-quadrant must be retained for the vast majority
    assert covered / want > 0.7, (covered, want)


def test_position_updates_flow(quon_run):
    """Moves and updates counted, stored neighbor positions track the
    real ones (same machinery as Vast with the quon_ stat prefix)."""
    s, st = quon_run
    out = s.summary(st)
    assert out["quon_moves"] > 100, out
    assert out["quon_updates"] > 200, out
    pos = np.asarray(st.logic.pos)
    nbr = np.asarray(st.logic.nbr)
    nbr_pos = np.asarray(st.logic.nbr_pos)
    errs = []
    for i in range(N):
        for slot, j in enumerate(nbr[i]):
            if j < 0:
                continue
            errs.append(np.linalg.norm(nbr_pos[i, slot] - pos[j]))
    assert errs, "no neighbors at all"
    # a couple of movement steps of staleness at most (speed*interval)
    p = QuonParams()
    bound = 3.0 * p.move.speed * p.move_interval
    assert np.median(errs) < bound, (np.median(errs), bound)


def test_no_engine_losses(quon_run):
    s, st = quon_run
    out = s.summary(st)
    assert out["_engine"]["pool_overflow"] == 0
    assert out["_engine"]["outbox_overflow"] == 0
