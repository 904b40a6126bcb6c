"""NICE hierarchical-cluster ALM: structure invariants + dissemination.

Mirrors the reference's expectations for src/overlay/nice/: clusters
bounded k..3k-1 after convergence (split/merge, Nice.cc:2220,2247) and
multicast reaching every member (handleNiceMulticast fan-out)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.nice import NiceLogic, NiceParams, READY


_EP = sim_mod.EngineParams(window=0.05, outbox_slots=64,
                           transition_time=40.0, rmax=16, inbox_slots=2)


def _run(n, t_sim, seed=3, **pkw):
    logic = NiceLogic(params=NiceParams(**pkw))
    cp = churn_mod.ChurnParams(model="none", target_num=n,
                               init_interval=0.5)
    s = sim_mod.Simulation(logic, cp, engine_params=_EP)
    state = s.init(seed=seed)
    state = s.run_until(state, t_sim)
    return s, state


@pytest.fixture(scope="module")
def nice24():
    """ONE 24-node simulation for the two structure tests: its state at
    second 120 (where the first reads it; fetched, because the run
    donates it) and at second 200."""
    logic = NiceLogic(params=NiceParams())
    cp = churn_mod.ChurnParams(model="none", target_num=24,
                               init_interval=0.5)
    s = sim_mod.Simulation(logic, cp, engine_params=_EP)
    early = s.run_until(s.init(seed=3), 120.0)
    at_120 = jax.device_get(early)
    return s, at_120, s.run_until(early, 200.0)


def test_all_nodes_ready_and_clustered(nice24):
    _, state, _ = nice24
    st = state.logic
    alive = np.asarray(state.alive)
    assert alive.sum() == 24
    ready = np.asarray(st.state) == READY
    assert (ready[alive]).all(), "every alive node must reach READY"
    # everyone alive is in a layer-0 cluster with a live leader
    in0 = np.asarray(st.in_layer)[:, 0]
    assert (in0[alive]).all()
    leaders = np.asarray(st.leader)[:, 0]
    assert (leaders[alive] >= 0).all()
    assert alive[leaders[alive]].all(), "layer-0 leaders must be alive"


def test_cluster_size_invariants(nice24):
    s, _, state = nice24
    st = state.logic
    alive = np.asarray(state.alive)
    in_layer = np.asarray(st.in_layer)
    leader = np.asarray(st.leader)
    member = np.asarray(st.member)
    k = s.logic.p.k
    # leader-view cluster sizes within [1, 3k+2] (cap; k..3k-1 steady)
    for i in np.nonzero(alive)[0]:
        for l in range(in_layer.shape[1]):
            if in_layer[i, l] and leader[i, l] == i:
                size = (member[i, l] >= 0).sum()
                assert 1 <= size <= 3 * k + 2
    # the leader hierarchy is consistent: my layer-l leader is in layer l
    for i in np.nonzero(alive)[0]:
        if in_layer[i, 0]:
            ld = leader[i, 0]
            assert in_layer[ld, 0], "my leader must be in my layer"


def test_multicast_reaches_members():
    # measurement starts at transition_time=40s; publishers fire every
    # pub_interval thereafter; every alive READY node should receive
    # (almost) every foreign publication through the cluster hierarchy
    s, state = _run(12, 260.0, pub_interval=15.0)
    out = s.summary(state)
    pub = float(out["nice_pub"])
    recv = float(out["nice_recv"])
    alive = int(np.asarray(state.alive).sum())
    assert pub > 0
    expected = pub * (alive - 1)          # everyone but the origin
    ratio = recv / max(expected, 1.0)
    assert ratio > 0.9, f"ALM delivery ratio {ratio:.3f} (recv={recv}, pub={pub})"


def test_survives_churn():
    logic = NiceLogic(params=NiceParams())
    cp = churn_mod.ChurnParams(model="lifetime", target_num=16,
                               lifetime_mean=120.0, init_interval=0.5)
    s = sim_mod.Simulation(logic, cp, engine_params=_EP)
    state = s.init(seed=5)
    state = s.run_until(state, 240.0)
    st = state.logic
    alive = np.asarray(state.alive)
    ready = np.asarray(st.state) == READY
    # under churn most alive nodes are clustered (joiners may be mid-join)
    frac = (ready & alive).sum() / max(alive.sum(), 1)
    assert frac > 0.7, f"only {frac:.2f} of alive nodes READY under churn"
