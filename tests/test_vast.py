"""Vast spatial overlay: join via greedy point query, AOI neighbor
consistency, move-update delivery (reference src/overlay/vast)."""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.vast import VastLogic, VastParams, READY


N = 16


@pytest.fixture(scope="module")
def vast_run():
    logic = VastLogic(params=VastParams())
    cp = churn_mod.ChurnParams(model="none", target_num=N, init_interval=0.5)
    ep = sim_mod.EngineParams(window=0.050, transition_time=60.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=31)
    st = s.run_until(st, 300.0, chunk=128)
    return s, st


def test_all_ready(vast_run):
    _, st = vast_run
    assert (np.asarray(st.logic.state) == READY).all()


def test_aoi_neighbors_known(vast_run):
    """Most pairs within the AOI radius must know each other."""
    _, st = vast_run
    p = VastParams()
    pos = np.asarray(st.logic.pos)
    nbr = np.asarray(st.logic.nbr)
    want = have = 0
    for i in range(N):
        for j in range(N):
            if i == j:
                continue
            if np.linalg.norm(pos[i] - pos[j]) < p.aoi * 0.8:
                want += 1
                if j in nbr[i]:
                    have += 1
    assert want > 0
    assert have / want > 0.7, (have, want)


def test_position_updates_flow(vast_run):
    """Stored neighbor positions must track the real ones within a couple
    of movement steps."""
    s, st = vast_run
    p = VastParams()
    out = s.summary(st)
    assert out["vast_moves"] > 100, out
    assert out["vast_updates"] > 200, out
    pos = np.asarray(st.logic.pos)
    nbr = np.asarray(st.logic.nbr)
    nbr_pos = np.asarray(st.logic.nbr_pos)
    errs = []
    for i in range(N):
        for d in range(nbr.shape[1]):
            j = nbr[i, d]
            if j >= 0:
                errs.append(np.linalg.norm(nbr_pos[i, d] - pos[j]))
    assert errs
    # within ~2 movement steps of truth on average
    assert np.mean(errs) <= 2.5 * p.move.speed * p.move_interval, \
        np.mean(errs)


def test_no_engine_losses(vast_run):
    s, st = vast_run
    eng = s.summary(st)["_engine"]
    assert eng["pool_overflow"] == 0
    assert eng["outbox_overflow"] == 0


def test_group_roaming_flocks():
    """groupRoaming (groupRoaming.cc): members of one group share a
    target, so within-group spread shrinks well below the field size."""
    import jax
    import jax.numpy as jnp
    from oversim_tpu.apps import movement as mv

    p = mv.MoveParams(generator="groupRoaming", field=1000.0, speed=50.0,
                      group_size=8)
    rng = jax.random.PRNGKey(0)
    pos, wp = mv.init_positions(rng, 32, p)
    for i in range(60):
        pos, wp = mv.step(pos, wp, 1.0, jax.random.PRNGKey(10 + i), p,
                          t_s=float(i))
    pos = np.asarray(pos)
    spread = []
    for g in range(4):
        grp = pos[g * 8:(g + 1) * 8]
        spread.append(np.linalg.norm(grp - grp.mean(0), axis=1).mean())
    assert np.mean(spread) < 200.0, spread  # flocked vs 1000-unit field


def test_realworld_roaming_follows_script():
    import jax
    from oversim_tpu.apps import movement as mv

    p = mv.MoveParams(generator="realWorldRoaming", field=1000.0,
                      speed=100.0, script=((100.0, 100.0),))
    rng = jax.random.PRNGKey(1)
    pos, wp = mv.init_positions(rng, 4, p)
    for i in range(40):
        pos, wp = mv.step(pos, wp, 1.0, jax.random.PRNGKey(i), p,
                          t_s=float(i))
    # single-waypoint script: everyone converges on it
    assert np.abs(np.asarray(pos) - 100.0).max() < 1.0


def test_connectivity_probe_metrics(vast_run):
    """ConnectivityProbeApp equivalent: a converged Vast run must show
    near-complete AOI neighborhoods and bounded drift."""
    from oversim_tpu.apps.probe import connectivity_probe

    s, st = vast_run
    out = connectivity_probe(st.logic.pos, st.alive, st.logic.nbr,
                             st.logic.nbr_pos, s.logic.p.aoi)
    assert out["node_count"] >= 8
    # nearest-K+AOI (the documented Voronoi deviation) leaves a tail of
    # 1-2 missing far-AOI neighbors on some nodes; the probe's job is to
    # MEASURE that, so the bands assert plausibility, not perfection
    assert out["zero_missing"] >= out["node_count"] * 0.25
    assert out["avg_missing"] < 2.0, out
    assert out["avg_drift"] < s.logic.p.aoi, out
