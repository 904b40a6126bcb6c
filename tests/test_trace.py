"""GlobalTraceManager/TraceChurn tests: trace parsing, schedule building,
and a trace-driven Chord run (reference simulations/dht.trace format)."""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu import trace as trace_mod
from oversim_tpu.core import keys as K

TRACE = """\
1 1 JOIN
5 2 JOIN
10 3 JOIN
11 1 LEAVE
15 4 JOIN
16 3 LEAVE
50 4 PUT foo bar
60 2 GET foo
"""

PART = """\
100 0 DISCONNECT_NODETYPES 0 1
200 0 CONNECT_NODETYPES 0 1
"""


def test_parse():
    ev = trace_mod.parse_trace(TRACE)
    assert len(ev) == 8
    assert ev[0].cmd == "JOIN" and ev[0].node == 1
    assert ev[-1].cmd == "GET" and ev[-1].args == ("foo",)


def test_churn_schedule():
    ev = trace_mod.parse_trace(TRACE)
    cp = trace_mod.churn_from_trace(ev)
    assert cp.num_slots == 4
    import jax
    st = churn_mod.init(jax.random.PRNGKey(0), cp)
    t_c = np.asarray(st.t_create) / 1e9
    t_k = np.asarray(st.t_kill) / 1e9
    assert list(t_c) == [1, 5, 10, 15]
    assert t_k[0] == 11 and t_k[2] == 16
    assert t_k[1] > 1e9 and t_k[3] > 1e9    # never leave


def test_workload():
    ev = trace_mod.parse_trace(TRACE)
    w = trace_mod.workload_from_trace(ev, 4)
    assert w.kind[3, 0] == 1 and w.t[3, 0] == 50      # node 4 PUT
    assert w.kind[1, 0] == 2 and w.t[1, 0] == 60      # node 2 GET
    np.testing.assert_array_equal(w.key[3, 0], w.key[1, 0])  # same "foo"


def test_partitions():
    ev = trace_mod.parse_trace(PART)
    ps = trace_mod.partitions_from_trace(ev)
    assert list(ps.t) == [100, 200]
    assert list(ps.connect) == [False, True]


def test_trace_driven_run():
    """A traced population must follow the schedule inside the engine."""
    from oversim_tpu.engine import sim as sim_mod
    from oversim_tpu.overlay.chord import ChordLogic

    ev = trace_mod.parse_trace(TRACE)
    cp = trace_mod.churn_from_trace(ev)
    s = sim_mod.Simulation(ChordLogic(), cp,
                           engine_params=sim_mod.EngineParams(window=0.05,
                                                              inbox_slots=2))
    st = s.init(seed=3)
    st = s.run_until(st, 30.0, chunk=128)
    alive = np.asarray(st.alive)
    # nodes 2 and 4 (slots 1, 3) alive; 1 and 3 departed
    assert alive[1] and alive[3]
    assert not alive[0] and not alive[2]
