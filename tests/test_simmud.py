"""SimMud region MMOG over Scribe: region grouping, boundary-crossing
re-subscription, position-update multicast (reference src/tier2/simmud
— SimMud.h:33-46 regionSize/playerMoveMessages)."""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.movement import MoveParams
from oversim_tpu.apps.simmud import SimMudApp, SimMudParams
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.chord import ChordLogic, READY

N = 16


@pytest.fixture(scope="module")
def simmud_run():
    # fast movement over a small field so boundary crossings happen
    app = SimMudApp(SimMudParams(grid=2, move_interval=5.0,
                                 publish_interval=10.0,
                                 subscribe_refresh=15.0,
                                 move=MoveParams(field=400.0, speed=20.0)))
    logic = ChordLogic(app=app)
    cp = churn_mod.ChurnParams(model="none", target_num=N, init_interval=0.5)
    # MMOG multicast is bursty: each publish fans out through the region
    # tree in one tick — size the pool for the burst (counted, never
    # silent; engine/pool.py docstring)
    # The 16 players have joined by second 8 and measurement opens at
    # 48; 112 s and more from there hold a publish per player per 10 s
    ep = sim_mod.EngineParams(window=0.1, transition_time=40.0,
                              pool_factor=16, outbox_slots=64, inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=43)
    st = s.run_until(st, 160.0, chunk=128)
    return s, st


def test_all_ready_in_region_groups(simmud_run):
    """Every player subscribes to the multicast group of the region
    under its feet."""
    _, st = simmud_run
    assert (np.asarray(st.logic.state) == READY).all()
    app = st.logic.app
    pos = np.asarray(app.pos)
    group = np.asarray(app.group)
    p = SimMudParams(grid=2, move=MoveParams(field=400.0, speed=20.0))
    cell = np.clip((pos / (p.move.field / p.grid)).astype(int), 0,
                   p.grid - 1)
    region = cell[:, 0] * p.grid + cell[:, 1]
    # group matches the current region for the large majority (a node
    # mid-crossing may not have re-subscribed yet)
    assert (group == region).sum() >= N - 3, (group, region)


def test_region_crossings_resubscribe(simmud_run):
    """Fast movement over a 2x2 grid must produce boundary crossings,
    each re-targeting the player's group (SimMud::handleMove)."""
    s, st = simmud_run
    crossings = int(np.asarray(st.logic.app.region_moves).sum())
    assert crossings > 5, crossings


def test_position_multicast_flows(simmud_run):
    """Position updates are published into the region group and received
    by co-located players (the alm_* Scribe KPIs double as SimMud's
    move-delivery stats)."""
    s, st = simmud_run
    out = s.summary(st)
    assert out["alm_published"] > 30, out
    assert out["alm_received"] > 30, out
    assert out["alm_latency_s"]["count"] > 0


def test_no_engine_losses(simmud_run):
    s, st = simmud_run
    out = s.summary(st)
    assert out["_engine"]["pool_overflow"] == 0
    assert out["_engine"]["outbox_overflow"] == 0
