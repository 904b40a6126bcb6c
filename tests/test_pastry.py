"""End-to-end Pastry slice: leafset formation + KBR delivery.

Both routing modes run: this module and test_pastry_bamboo.py use the
reference default SEMI_RECURSIVE with per-hop ACKs
(default.ini:245-246), test_pastry_iterative.py pins ITERATIVE (lookup
+ final direct hop); test_pastry_multihop.py is the N=32 case.  The
four are modules of their own because a module is one unit of work on
one xdist worker (tests/conftest.py) and each Pastry program is
minutes of XLA-CPU compile; the other three collect this module's
checks against their own ``pastry_run``."""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.core import keys as K
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.pastry import PastryLogic, READY


# R, messages a node consumes per tick.  Pastry's handler is unrolled
# over the R inbox slots, and on XLA-CPU the tick program's cost grows
# faster than R: at N=8 the engine default R=8 compiles in 344 s and
# runs 167 ms/tick, R=4 in 87 s and 37 ms/tick, R=2 in 43 s and
# 17 ms/tick (PR 22, CPU test durations), and R=1 compiles in two
# thirds of R=2's time (PR 26).  At these N a window hardly ever holds
# a second message for one node (one or two in a whole run, counted in
# `inbox_deferred`), and it is deferred to the next tick, never lost.
INBOX_SLOTS = 1


def run_small(logic):
    """N=8: one leafset spans the whole ring."""
    cp = churn_mod.ChurnParams(model="none", target_num=8, init_interval=1.0)
    ep = sim_mod.EngineParams(window=0.010, transition_time=30.0,
                              inbox_slots=INBOX_SLOTS)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=17)
    st = s.run_until(st, 300.0, chunk=128)
    return s, st


@pytest.fixture(scope="module")
def pastry_run():
    return run_small(PastryLogic())


def test_all_ready(pastry_run):
    _, st = pastry_run
    assert np.asarray(st.alive).sum() == st.alive.shape[0]
    assert (np.asarray(st.logic.state) == READY).all()


def test_leafsets_are_ring_neighbors(pastry_run):
    """8 nodes, leafset >= 8: every node must know all others, and
    leaf_cw[0] must be the ring successor (at any N)."""
    _, st = pastry_run
    n = st.alive.shape[0]
    keys_int = [K.to_int(k) for k in np.asarray(st.node_keys)]
    order = sorted(range(n), key=lambda i: keys_int[i])
    cw = np.asarray(st.logic.leaf_cw)
    for pos, i in enumerate(order):
        assert cw[i, 0] == order[(pos + 1) % n], f"node {i} cw successor"


def test_deliveries(pastry_run):
    s, st = pastry_run
    out = s.summary(st)
    assert out["kbr_sent"] > 20
    assert out["kbr_delivered"] >= out["kbr_sent"] - 2
    assert out["kbr_delivered"] <= out["kbr_sent"]
    assert out["kbr_wrong_node"] == 0
    assert out["kbr_hopcount"]["max"] <= 3


def test_no_engine_losses(pastry_run):
    s, st = pastry_run
    eng = s.summary(st)["_engine"]
    assert eng["pool_overflow"] == 0
    assert eng["outbox_overflow"] == 0
