"""The Chord ring closes at upstream's fill.

One join every 0.1 s (``initPhaseCreationInterval``, default.ini) under
the benchmark cells' 0.2 s engine window: a join's lookup spans several
ticks, so calls reach nodes that are no longer responsible and several
joiners reach one node in one window.  Every READY node's first
successor has to be the next alive key clockwise and its predecessor the
previous one, well before upstream's transition ends (simulated second
200 at N = 1000; here the ring is read at second 40 and 80, which is
the harder claim).  Before PR 39 the fixed point was a loopy ring:
two creators in one tick, and joiners of one window taken in inbox
order (PERF.md, Findings).
"""

import jax
import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.core import keys as K
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay import chord
from oversim_tpu.overlay.chord import ChordLogic


CHUNK = 50     # 10 s of 0.2 s windows: a run ends ON the second it names


def _sim(n, interval=0.1):
    logic = ChordLogic(app=KbrTestApp(KbrTestParams(test_interval=60.0)))
    cp = churn_mod.ChurnParams(model="none", target_num=n,
                               init_interval=interval)
    ep = sim_mod.EngineParams(window=0.2, transition_time=100.0)
    return sim_mod.Simulation(logic, cp, engine_params=ep)


def ring_faults(st):
    """(READY nodes, wrong first successors, wrong predecessors) against
    the sorted keys of the READY nodes."""
    keys = [K.to_int(k) for k in np.asarray(st.node_keys)]
    state = np.asarray(st.logic.state)
    order = sorted(np.nonzero(state == chord.READY)[0],
                   key=lambda i: keys[i])
    succ, pred = np.asarray(st.logic.succ), np.asarray(st.logic.pred)
    m = len(order)
    succ_wrong = sum(succ[i, 0] != order[(p + 1) % m]
                     for p, i in enumerate(order))
    pred_wrong = sum(pred[i] != order[(p - 1) % m]
                     for p, i in enumerate(order))
    return m, int(succ_wrong), int(pred_wrong)


@pytest.fixture(scope="module")
def sim64():
    return _sim(64)


@pytest.mark.parametrize("seed", [42, 7, 2147483659])
def test_ring_closes_at_upstreams_fill_n64(sim64, seed):
    st = sim64.run_until(sim64.init(seed=seed), 40.0, chunk=CHUNK)
    assert ring_faults(st) == (64, 0, 0)
    lost = {k: int(v) for k, v in st.counters.items()
            if k.endswith(("_lost", "_overflow")) and int(v)}
    assert not lost


def test_ring_closes_at_upstreams_fill_n256():
    s = _sim(256)
    st = s.run_until(s.init(seed=42), 80.0, chunk=CHUNK)
    assert ring_faults(st) == (256, 0, 0)


def test_one_node_starts_the_ring_when_two_are_created_in_one_tick():
    """Eight nodes created 10 ms apart: all fall due inside the first
    ticks, none finds a READY node, and exactly ONE may start a ring;
    the others keep their join timer (so they stay due, and awake under
    the awake-set plane) and join through it a tick later."""
    s = _sim(8, interval=0.01)
    step = jax.jit(s.step)
    st = s.init(seed=42)
    for _ in range(8):
        st = step(st)
        state = np.asarray(st.logic.state)
        if (state == chord.READY).any():
            break
    assert int((state == chord.READY).sum()) == 1
    # a joiner that was due in that tick and had to wait keeps its
    # timer: it is due again at once
    waiting = (state == chord.JOINING) & np.asarray(st.alive) & (
        np.asarray(st.logic.t_join) < int(st.t_now))
    assert waiting.sum() >= 1, "no second joiner was due in that tick"
    st = step(st)
    assert (np.asarray(st.logic.state)[waiting] == chord.JOINING).all()
    assert np.asarray(st.logic.lk.active)[waiting].any(axis=1).all()
    for _ in range(100):                # to simulated second 20
        st = step(st)
    assert ring_faults(st) == (8, 0, 0)
