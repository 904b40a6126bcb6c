"""KBR broadcast API over Chord: full-ring coverage
(reference BaseOverlay forwardBroadcast + BroadcastTestApp)."""

import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.broadcast import BroadcastTestApp, BroadcastTestParams
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.chord import ChordLogic


N = 16


def test_broadcast_reaches_the_ring():
    app = BroadcastTestApp(BroadcastTestParams(interval=40.0))
    logic = ChordLogic(app=app)
    cp = churn_mod.ChurnParams(model="none", target_num=N, init_interval=0.5)
    # the 16 nodes have joined by second 8 and measurement opens at 48;
    # a broadcast per node per 40 s from there to 160 s and more is 45
    # and more for the > 20 below
    ep = sim_mod.EngineParams(window=0.100, transition_time=40.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=23)
    st = s.run_until(st, 160.0, chunk=512)
    out = s.summary(st)
    assert out["bcast_started"] > 20, out
    # keyspace splitting must reach nearly every node per broadcast
    # (the initiator's own copy included)
    reach = out["bcast_received"] / out["bcast_started"]
    assert reach > 0.8 * N, out
    assert out["bcast_hops"]["mean"] < 8.0
