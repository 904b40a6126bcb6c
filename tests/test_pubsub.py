"""PubSubMMOG: lobby duty assignment, subscriptions, move-list flow.

Mirrors the reference's stats for src/overlay/pubsubmmog/: movement
lists reach subscribers (receivedMovementLists) and arrive within the
timeslot bound (numEventsCorrectTimeslot vs maxMoveDelay)."""

import numpy as np

from oversim_tpu import churn as churn_mod
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.pubsubmmog import (PubSubMMOGLogic, PubSubParams,
                                            READY)


def _run(n, t_sim, seed=7, **pkw):
    logic = PubSubMMOGLogic(params=PubSubParams(**pkw))
    cp = churn_mod.ChurnParams(model="none", target_num=n,
                               init_interval=0.3)
    ep = sim_mod.EngineParams(window=0.050, outbox_slots=64,
                              transition_time=20.0, rmax=16, inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    state = s.init(seed=seed)
    state = s.run_until(state, t_sim)
    return s, state


def test_players_ready_and_subscribed():
    s, state = _run(12, 60.0)
    st = state.logic
    alive = np.asarray(state.alive)
    ready = np.asarray(st.state) == READY
    assert ready[alive].all()
    # every player eventually holds a confirmed subscription to its
    # current subspace (AOI covers it)
    sub_ok = np.asarray(st.sub_ok)
    frac = (sub_ok.any(axis=1) & alive).sum() / max(alive.sum(), 1)
    assert frac > 0.9, f"only {frac:.2f} players confirmed-subscribed"


def test_lobby_assigns_responsibles():
    s, state = _run(12, 60.0)
    glob = state.logic.glob
    resp = np.asarray(glob.resp)
    alive = np.asarray(state.alive)
    assigned = resp[resp >= 0]
    assert len(assigned) > 0, "no subspace got a responsible node"
    assert alive[assigned].all(), "dead responsible left in the lobby"
    # responsibles actually hold the duty
    duty = np.asarray(state.logic.duty)
    for s_id in np.nonzero(resp >= 0)[0]:
        assert (duty[resp[s_id]] == s_id).any()


def test_move_lists_flow():
    s, state = _run(10, 120.0, move_rate=2.0)
    out = s.summary(state)
    moves = float(out["ps_moves"])
    sent = float(out["ps_lists_sent"])
    recv = float(out["ps_lists_recv"])
    ok = float(out["ps_events_ok"])
    late = float(out["ps_events_late"])
    assert moves > 0, "no move messages reached a responsible node"
    assert sent > 0 and recv > 0
    # near-lossless dissemination under no churn
    assert recv / sent > 0.95, f"move-list loss: {recv}/{sent}"
    # timeslot discipline: most events inside maxMoveDelay
    assert ok / max(ok + late, 1) > 0.9


def test_survives_churn():
    logic = PubSubMMOGLogic()
    cp = churn_mod.ChurnParams(model="lifetime", target_num=12,
                               lifetime_mean=100.0, init_interval=0.3)
    ep = sim_mod.EngineParams(window=0.050, outbox_slots=64,
                              transition_time=20.0, rmax=16, inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    state = s.init(seed=11)
    state = s.run_until(state, 150.0)
    glob = state.logic.glob
    resp = np.asarray(glob.resp)
    alive = np.asarray(state.alive)
    live_resp = resp[resp >= 0]
    assert alive[live_resp].all(), "lobby kept a dead responsible"
