"""InetUnderlay/ReaSE router-topology underlay."""

import jax
import numpy as np

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.chord import ChordLogic
from oversim_tpu.underlay import inet as inet_mod


def test_topology_metric_properties():
    for topo in ("inet", "rease"):
        p = inet_mod.InetUnderlayParams(topology=topo, routers=12)
        d = inet_mod.build_topology(seed=42, p=p)
        assert d.shape == (12, 12)
        assert np.allclose(d, d.T), "delay matrix must be symmetric"
        assert (np.diag(d) == 0).all()
        assert (d[~np.eye(12, dtype=bool)] > 0).all()
        assert d.max() < 1.0, "graph must be connected (no inf paths)"
        # triangle inequality holds after APSP
        for k in range(12):
            assert (d <= d[:, k:k + 1] + d[k:k + 1, :] + 1e-6).all()


def test_rease_core_is_faster():
    p = inet_mod.InetUnderlayParams(topology="rease", routers=16, transit=4)
    d = inet_mod.build_topology(seed=1, p=p)
    core = d[:4, :4][~np.eye(4, dtype=bool)].mean()
    edge = d[4:, 4:][~np.eye(12, dtype=bool)].mean()
    assert core < edge, "transit core must be lower-latency than stubs"


def test_chord_runs_over_inet_underlay():
    n = 16
    logic = ChordLogic(app=KbrTestApp(KbrTestParams(test_interval=10.0)))
    cp = churn_mod.ChurnParams(model="none", target_num=n,
                               init_interval=0.3)
    up = inet_mod.InetUnderlayParams(topology="rease", routers=8)
    ep = sim_mod.EngineParams(window=0.100, transition_time=40.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, up, ep, underlay_module=inet_mod)
    state = s.init(seed=2)
    # measurement opens at second 44.8; a test per node per 10 s from
    # there to 140 s and more is some 150 tests for the ratio below
    state = s.run_until(state, 140.0)
    out = s.summary(state)
    sent = float(out["kbr_sent"])
    delivered = float(out["kbr_delivered"])
    assert sent > 0
    assert delivered / sent > 0.9, f"delivery {delivered}/{sent}"
    # inet delays are larger than simple-underlay coords: sanity-bound
    lat = out["kbr_latency_s"]["mean"]
    assert 0.001 < lat < 2.0
