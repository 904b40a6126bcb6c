"""Broose end-to-end slice: bucket formation, join state machine, KBR
delivery over de Bruijn shift routing (reference src/overlay/broose/)."""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.core import keys as K
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.broose import BrooseLogic, BrooseParams, READY


N = 16


@pytest.fixture(scope="module")
def broose_run():
    logic = BrooseLogic(app=KbrTestApp(KbrTestParams(test_interval=20.0)))
    cp = churn_mod.ChurnParams(model="none", target_num=N,
                               init_interval=0.5)
    # sized for XLA-CPU: window 0.05 and chunk 128 bound the tick count,
    # inbox_slots 2 (engine default 8) shrinks the handler unrolled over
    # the inbox slots — a third message in one 50 ms window is deferred
    # to the next tick, never lost
    ep = sim_mod.EngineParams(window=0.050, transition_time=80.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=7)
    st = s.run_until(st, 220.0, chunk=128)
    return s, st


def test_all_ready(broose_run):
    _, st = broose_run
    assert (np.asarray(st.logic.state) == READY).all(), \
        np.asarray(st.logic.state)


def test_brother_buckets_hold_xor_closest(broose_run):
    """Every node's B bucket must contain its k XOR-closest peers
    (BrooseBucket keyed by XOR distance to the own key)."""
    _, st = broose_run
    p = BrooseParams()
    keys_int = [K.to_int(k) for k in np.asarray(st.node_keys)]
    bb = np.asarray(st.logic.bb)
    missing = 0
    for i in range(N):
        true_close = sorted((j for j in range(N) if j != i),
                            key=lambda j: keys_int[j] ^ keys_int[i])
        want = set(true_close[:p.bucket_size])
        have = set(int(x) for x in bb[i] if x >= 0)
        missing += len(want - have)
    # learns are READY-gated + pull-based; allow a convergence tail
    assert missing <= 0.3 * N * p.bucket_size, \
        f"{missing} sibling entries missing across {N} nodes"


def test_deliveries(broose_run):
    s, st = broose_run
    out = s.summary(st)
    assert out["kbr_sent"] > 50
    ratio = out["kbr_delivered"] / out["kbr_sent"]
    assert ratio > 0.95, out
    assert out["kbr_wrong_node"] == 0
    # shift routing is bounded by keyLength/shiftingBits per direction
    assert out["kbr_hopcount"]["max"] <= 16


def test_no_engine_losses(broose_run):
    s, st = broose_run
    eng = s.summary(st)["_engine"]
    assert eng["pool_overflow"] == 0
    assert eng["outbox_overflow"] == 0
