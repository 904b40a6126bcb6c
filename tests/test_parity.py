"""Statistical parity checks vs the reference's expected behavior.

The reference's golden tests are event-hash fingerprints (verify.ini,
SURVEY.md §4) — impossible to reproduce without the OMNeT++ RNG streams.
The rebuild's equivalent is distribution-level: Chord iterative lookups
must visit ~O(log N) nodes (0.5*log2(N) expected fingers + successor
walk), delivery must be ~100% without churn, and latencies must sit in
the SimpleUnderlay delay envelope.
"""

import math

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.chord import ChordLogic


N = 64


@pytest.fixture(scope="module")
def chord64():
    logic = ChordLogic(app=KbrTestApp(KbrTestParams(test_interval=20.0)))
    cp = churn_mod.ChurnParams(model="none", target_num=N,
                               init_interval=0.2)
    # window 0.1 / 160 s: the tick count, not the compile, is this
    # fixture's cost on XLA-CPU (w=0.02 to 600 s was 18,944 ticks); the
    # bands below hold at any window well under the 1.5 s RPC timeout.
    # The 64 nodes have joined by second 12.8 and the ring is closed by
    # 40 (test_chord_ring.py); measurement opens at 72.8, and 87 s and
    # more of one test per node per 20 s is the four rounds (256 tests)
    # behind the > 200 below.
    # inbox_slots 2 (engine default 8) shrinks the per-tick handler; a
    # third message in one window is deferred a tick, never lost
    ep = sim_mod.EngineParams(window=0.100, transition_time=60.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=42)
    st = s.run_until(st, 160.0, chunk=128)
    return s, st


def test_delivery_ratio(chord64):
    s, st = chord64
    out = s.summary(st)
    assert out["kbr_sent"] > 200
    ratio = out["kbr_delivered"] / out["kbr_sent"]
    assert ratio > 0.98
    assert out["kbr_wrong_node"] == 0


def test_hopcount_scales_logarithmically(chord64):
    """Chord iterative lookup: expected ~0.5*log2(N) finger hops (+1
    delivery hop).  For N=64: ~3-4 mean; fail far outside the band."""
    s, st = chord64
    out = s.summary(st)
    mean = out["kbr_hopcount"]["mean"]
    expected = 0.5 * math.log2(N) + 1
    assert 0.4 * expected < mean < 1.9 * expected, mean
    assert out["kbr_hopcount"]["max"] <= 16


def test_latency_envelope(chord64):
    """Per-hop latency = SimpleUnderlay delay (coord distance 0.001 s/unit
    in a 150x150 field + tx delays + jitter): mean one-hop must be tens of
    ms, total lookup latency under a second."""
    s, st = chord64
    out = s.summary(st)
    lat = out["kbr_latency_s"]
    assert 0.005 < lat["mean"] < 1.5
    assert lat["max"] < 10.0


def test_ring_is_globally_consistent(chord64):
    _, st = chord64
    from oversim_tpu.core import keys as K
    keys_int = [K.to_int(k) for k in np.asarray(st.node_keys)]
    order = sorted(range(N), key=lambda i: keys_int[i])
    succ = np.asarray(st.logic.succ)
    bad = sum(1 for pos, i in enumerate(order)
              if succ[i, 0] != order[(pos + 1) % N])
    assert bad == 0, f"{bad}/{N} successor pointers wrong"


# ---------------------------------------------------------------------------
# Pinned regression goldens (scripts/make_goldens.py; VERDICT r1 item #6).
# The reference's event-hash fingerprints need its OMNeT++ RNG streams;
# the rebuild pins measured distribution goldens at N=256 with tight
# tolerances instead, with the analytic O(log N) expectation recorded as
# provenance inside goldens.json.
# ---------------------------------------------------------------------------
import json
import os

_GOLDENS = os.path.join(os.path.dirname(__file__), "goldens.json")


# slow: a N=256 sim is minutes of XLA-CPU compile on the 1-core CI box.
# Drift cover in the fast tier comes from the unmarked chord64 fixture
# tests above (delivery/hop/latency bands at N=64); the pinned 256
# goldens tighten that to ±5%/±1% in the full-suite runs.
@pytest.mark.slow
@pytest.mark.skipif(not os.path.exists(_GOLDENS),
                    reason="goldens.json not generated yet")
@pytest.mark.parametrize("name", ["chord_256", "kademlia_256",
                                  "pastry_256"])
def test_pinned_goldens(name):
    """Replays scripts/make_goldens.measure — ONE config source, so the
    pin can never drift from the generator.  Pins the full hop-count
    HISTOGRAM (total-variation distance), not just mean bands — the
    reproducible analogue of verify.ini's event-hash fingerprints
    (VERDICT r4 next-step #5)."""
    all_g = json.load(open(_GOLDENS))
    if name not in all_g:
        pytest.skip(f"{name} golden not generated yet")
    g = all_g[name]
    overlay, n = name.split("_")
    from scripts.make_goldens import measure
    out = measure(overlay, int(n), seed=g["seed"])

    assert out["delivery_ratio"] <= 1.0    # send-time measuring fix
    assert abs(out["delivery_ratio"] - g["delivery_ratio"]) < 0.01
    assert abs(out["hop_mean"] - g["hop_mean"]) / g["hop_mean"] < 0.05, (
        out["hop_mean"], g["hop_mean"])
    # pinned hop-count distribution: normalized total-variation
    # distance must stay tight (identical seeds + static shapes make
    # the run nearly deterministic; the tolerance absorbs scheduling
    # nondeterminism only)
    if "hop_hist" in g:
        p = np.asarray(out["hop_hist"], float)
        q = np.asarray(g["hop_hist"], float)
        assert p.sum() > 0 and q.sum() > 0
        tv = 0.5 * np.abs(p / p.sum() - q / q.sum()).sum()
        assert tv < 0.05, (tv, out["hop_hist"], g["hop_hist"])
    # the golden itself must sit near the analytic expectation
    assert 0.6 * g["analytic_hop_mean"] < g["hop_mean"] \
        < 1.5 * g["analytic_hop_mean"]


@pytest.mark.slow
@pytest.mark.skipif(not os.path.exists(_GOLDENS),
                    reason="goldens.json not generated yet")
@pytest.mark.parametrize("overlay", ["chord", "kademlia", "pastry"])
def test_pinned_verify_scenario(overlay):
    """The reference's fingerprint-regression scenario shape
    (simulations/verify.ini:1-14): 100 nodes, LifetimeChurn
    lifetimeMean=1000s, DHT+DHTTestApp stack, 100s transition + 100s
    measurement — pinned as distribution goldens per overlay."""
    g = json.load(open(_GOLDENS)).get(f"verify_{overlay}")
    if g is None:
        pytest.skip("verify goldens not generated yet")
    from scripts.make_goldens import measure_verify
    out = measure_verify(overlay, seed=g["seed"])
    assert abs(out["put_success_ratio"] - g["put_success_ratio"]) < 0.05
    assert abs(out["get_success_ratio"] - g["get_success_ratio"]) < 0.05
    assert out["get_wrong"] <= g["get_wrong"] + 2
    # the golden itself must clear the verify.ini bar: a churny DHT
    # stack still stores and finds most values.  Measured r3 values:
    # chord .90/.79, pastry .85/.85, kademlia .74/.74 (kademlia's
    # stale-sibling repair lag between 1000s refreshes is the residual
    # gap — VERDICT-tracked)
    assert g["put_success_ratio"] > 0.7
    assert g["get_success_ratio"] > 0.65
