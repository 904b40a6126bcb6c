"""DHT put/get over Chord: replica storage + oracle-validated gets.

Mirrors the reference verify.ini scenario shape (Chord + DHT + DHTTestApp
+ GlobalDhtTestMap, SURVEY.md §4) at toy scale: puts must reach replicas,
gets must return the value recorded in the global truth map.
"""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.dht import DhtApp, DhtParams
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.chord import ChordLogic, READY


@pytest.fixture(scope="module")
def dht_run():
    app = DhtApp(DhtParams(test_interval=20.0, num_test_keys=16,
                           test_ttl=600.0))
    logic = ChordLogic(app=app)
    cp = churn_mod.ChurnParams(model="none", target_num=8, init_interval=1.0)
    ep = sim_mod.EngineParams(window=0.100, transition_time=20.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=23)
    # measurement opens at second 28 (8 nodes a second apart, then 20 s);
    # 132 s and more of one put or get per node per 20 s from there
    st = s.run_until(st, 160.0, chunk=512)
    return s, st


def test_ready_and_puts_flow(dht_run):
    s, st = dht_run
    out = s.summary(st)
    assert (np.asarray(st.logic.state) == READY).all()
    assert out["dht_put_attempts"] > 10
    # almost every put must fully ack (no churn, no loss)
    assert out["dht_put_success"] >= out["dht_put_attempts"] - 2
    assert out["dht_stored"] >= out["dht_put_success"] * 2  # replicas > 1


def test_truth_map_committed(dht_run):
    _, st = dht_run
    glob = st.logic.app_glob
    assert (np.asarray(glob.val) >= 0).sum() > 3  # several keys written


def test_gets_validate_against_truth(dht_run):
    s, st = dht_run
    out = s.summary(st)
    assert out["dht_get_attempts"] > 5
    assert out["dht_get_wrong"] == 0
    # replica placement + single-get quorum: the vast majority must hit
    assert out["dht_get_success"] >= 0.8 * out["dht_get_attempts"] - 2


def test_storage_has_replicated_entries(dht_run):
    _, st = dht_run
    stored = (np.asarray(st.logic.app.s_val) >= 0).sum()
    assert stored > 10


@pytest.mark.slow
def test_crash_kill_churn_replication():
    """update()-driven maintenance puts (Common API update(),
    BaseApp.h:223; DHT.cc update path): under CRASH-KILL churn
    (graceful_leave_probability=0, so the leave-handover path never
    runs) GET success must stay high because records re-replicate when
    new nodes enter a replica set."""
    app = DhtApp(DhtParams(test_interval=10.0, num_test_keys=16,
                           test_ttl=900.0, num_replica=4))
    logic = ChordLogic(app=app)
    cp = churn_mod.ChurnParams(model="lifetime", target_num=16,
                               init_interval=0.5, lifetime_mean=150.0,
                               graceful_leave_probability=0.0)
    ep = sim_mod.EngineParams(window=0.05, transition_time=40.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=31)
    st = s.run_until(st, 400.0, chunk=128)
    out = s.summary(st)
    gets = out["dht_get_attempts"]
    assert gets > 20
    ok = out["dht_get_success"] / gets
    # without re-replication two full population turnovers would strand
    # nearly every record (success -> ~0); with update()-driven puts the
    # measured ratio stays above half even though ring-lookup failures
    # under this churn rate cap it (~38% of ops die at the lookup stage)
    assert ok > 0.5, (ok, out["dht_get_success"], gets)
    assert out["dht_mnt_puts"] > 100          # the mechanism actually ran
    # stale resurrection is bounded (maintenance puts cannot roll a
    # record back; nodes that never held the key remain a stale path,
    # as in the reference without the responsibility-drop sweep)
    assert out["dht_get_wrong"] < out["dht_get_success"] / 2
