"""GIA unstructured overlay: topology adaptation + random-walk search."""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.gia import GiaLogic, GiaParams, READY


@pytest.fixture(scope="module")
def gia_run():
    logic = GiaLogic(params=GiaParams(search_interval=20.0,
                                      token_interval=1.0))
    cp = churn_mod.ChurnParams(model="none", target_num=12,
                               init_interval=0.5)
    ep = sim_mod.EngineParams(window=0.050, transition_time=60.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=31)
    st = s.run_until(st, 400.0, chunk=128)
    return s, st


def test_all_ready_and_connected(gia_run):
    _, st = gia_run
    assert (np.asarray(st.logic.state) == READY).all()
    deg = (np.asarray(st.logic.nbr) >= 0).sum(1)
    assert (deg >= 1).all()
    assert deg.mean() >= 2.0


def test_neighbor_symmetry_mostly(gia_run):
    """Connections are negotiated pairwise; the overwhelming majority of
    edges must be symmetric (drops send disconnect notices)."""
    _, st = gia_run
    nbr = np.asarray(st.logic.nbr)
    edges = {(i, j) for i in range(nbr.shape[0]) for j in nbr[i] if j >= 0}
    sym = sum(1 for (i, j) in edges if (j, i) in edges)
    assert sym >= 0.7 * len(edges)


def test_searches_succeed(gia_run):
    s, st = gia_run
    out = s.summary(st)
    assert out["gia_searches"] > 20
    ratio = out["gia_search_success"] / out["gia_searches"]
    # biased random walks in a small connected graph: most must hit
    assert ratio > 0.5, out
    assert out["gia_search_hops"]["mean"] < 15


def test_tokens_flow(gia_run):
    _, st = gia_run
    # token buckets get replenished — some tokens outstanding at any time
    assert int(np.asarray(st.logic.tokens).sum()) > 0
