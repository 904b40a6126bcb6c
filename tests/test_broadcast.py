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
    ep = sim_mod.EngineParams(window=0.050, transition_time=120.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=23)
    st = s.run_until(st, 420.0, chunk=512)
    out = s.summary(st)
    assert out["bcast_started"] > 20, out
    # keyspace splitting must reach nearly every node per broadcast
    # (the initiator's own copy included)
    reach = out["bcast_received"] / out["bcast_started"]
    assert reach > 0.8 * N, out
    assert out["bcast_hops"]["mean"] < 8.0
