"""DHT variants: SymmetricDHT + RepeatedHashingDHT replica-team
key derivation over the base DHT (src/applications/dht/
{Symmetric,RepeatedHashing}DHT.cc — numReplica splits across
numReplicaTeams, each team stored under a derived key).

CBR-DHT is intentionally absent: the reference marks its own DHT
directory !WORK_IN_PROGRESS! and CBR-DHT depends on the WIP Landmark
coordinate flow (VERDICT r2/r3 notes)."""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.dht import DhtApp, DhtParams
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.chord import ChordLogic


def run_variant(variant: str):
    # mod_test off: a mod re-put racing a get of the same key counts
    # as wrong-by-truth (legitimate concurrent-write ambiguity, also in
    # the reference) — this fixture isolates the TEAM machinery, so
    # gets must only read stable keys
    app = DhtApp(DhtParams(test_interval=20.0, num_test_keys=32,
                           test_ttl=600.0, num_replica=4,
                           variant=variant, num_replica_teams=2,
                           mod_test=False))
    logic = ChordLogic(app=app)
    cp = churn_mod.ChurnParams(model="none", target_num=8,
                               init_interval=1.0)
    ep = sim_mod.EngineParams(window=0.1, transition_time=20.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=29)
    # measurement opens at second 28; 92 s and more of one put or get
    # per node per 20 s from there
    st = s.run_until(st, 120.0, chunk=128)
    return s, st, s.summary(st)


@pytest.fixture(scope="module", params=["symmetric", "repeated"])
def variant_run(request):
    return request.param, run_variant(request.param)


def test_puts_complete_across_teams(variant_run):
    """A put succeeds only after EVERY team's replica set majority-acks
    (sequential-team engine mapping of the parallel team fan-out)."""
    variant, (s, st, out) = variant_run
    assert out["dht_put_attempts"] > 10, (variant, out)
    assert out["dht_put_success"] >= 0.8 * out["dht_put_attempts"], (
        variant, out)


def test_gets_validate(variant_run):
    variant, (s, st, out) = variant_run
    assert out["dht_get_attempts"] > 3, (variant, out)
    assert out["dht_get_success"] >= 0.8 * out["dht_get_attempts"], (
        variant, out)
    assert out["dht_get_wrong"] == 0, (variant, out)


def test_records_stored_under_team_keys(variant_run):
    """Each logical record occupies MORE distinct storage keys than a
    plain DHT would use: teams store under derived keys, so the number
    of distinct stored keys exceeds the distinct base keys put."""
    variant, (s, st, out) = variant_run
    app = st.logic.app
    stored = np.asarray(app.s_val) != -1
    keys = np.asarray(app.s_key)[stored]
    distinct_stored = len({tuple(k) for k in keys})
    glob = st.logic.app_glob
    distinct_base = int((np.asarray(glob.val) >= 0).sum())
    assert distinct_base > 3
    # 2 teams => roughly twice the key population (replicas collapse
    # duplicates, teams multiply them)
    assert distinct_stored > distinct_base * 1.3, (
        variant, distinct_stored, distinct_base)
