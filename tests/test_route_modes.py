"""Recursive routing-mode family across overlays (VERDICT r3 item #5).

The reference's RoutingType enum (CommonMessages.msg:130-141) and the
generic recursive machinery (BaseOverlay.cc:1441-1581) give EVERY
overlay SEMI_RECURSIVE (replies direct), FULL_RECURSIVE (replies routed
by the originator's nodeId key, BaseOverlay.cc:1813-1819) and
RECURSIVE_SOURCE_ROUTING (visitedHops recorded; replies source-routed
back along the reversed path — verify.ini's ChordSource config).

Coverage here: Chord runs the full three-mode matrix (it exercises the
shared engine: common/route.py); Koorde (de Bruijn ext riding the
routed message), EpiChord and Broose (shift-routing ext) each prove
their wiring on one recursive mode — each of those three rows is
collected in a module of its own (test_route_modes_koorde.py,
_epichord.py, _broose.py): a module is one unit of work on one xdist
worker (tests/conftest.py), and each simulation is minutes of XLA-CPU.
Kademlia's recursive hook (R/Kademlia) is covered by
test_kademlia_depth, Pastry's semi-recursive default by test_pastry.
Each mode run drives the KBRTestApp one-way AND routed-RPC tests: the
one-way exercises request forwarding, the RPC test the mode's reply
transport.
"""

import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.common import route as rt_mod
from oversim_tpu.engine import sim as sim_mod

N = 32
# R: the handlers are unrolled over the inbox slots, and the XLA-CPU
# tick costs what its program holds; 2 (engine default 8).  A third
# message for one node in one window is deferred a tick, never lost.
INBOX_SLOTS = 2
# the 32 nodes have joined by second 6.4 and measurement opens
# TRANSITION_S later; the 80 s and more from there to the end of the
# run's last chunk are four rounds of one test per node per 20 s, 128
# one-way and 128 RPC tests for the > 100 below
TRANSITION_S = 30.0
RUN_S = 120.0


def run_mode(overlay: str, mode: str, seed: int = 11,
             transition_s: float = TRANSITION_S, run_s: float = RUN_S):
    rcfg = rt_mod.RouteConfig(mode=mode)
    app = KbrTestApp(KbrTestParams(test_interval=20.0, rpc_test=True),
                     rcfg=rcfg)
    if overlay == "chord":
        from oversim_tpu.overlay.chord import ChordLogic
        logic = ChordLogic(app=app, rcfg=rcfg)
    elif overlay == "koorde":
        from oversim_tpu.overlay.koorde import KoordeLogic
        logic = KoordeLogic(app=app, rcfg=rcfg)
    elif overlay == "epichord":
        from oversim_tpu.overlay.epichord import EpiChordLogic
        logic = EpiChordLogic(app=app, rcfg=rcfg)
    else:
        from oversim_tpu.overlay.broose import BrooseLogic
        logic = BrooseLogic(app=app, rcfg=rcfg)
    # the app may hold a stale rcfg copy if the overlay rewrote
    # ext_words (koorde/broose)
    app.rcfg = logic.rcfg
    cp = churn_mod.ChurnParams(model="none", target_num=N,
                               init_interval=0.2)
    # window 0.1: recursive ACK timeouts are 1.5 s — ordering
    # semantics are insensitive at this scale and the tick count (the
    # run cost on XLA-CPU) falls with the window
    ep = sim_mod.EngineParams(window=0.100, transition_time=transition_s,
                              inbox_slots=INBOX_SLOTS)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=seed)
    st = s.run_until(st, run_s, chunk=128)
    return s, st, s.summary(st)


MODES = ["semi", "full", "source"]


@pytest.fixture(scope="module")
def chord_runs():
    """Chord under each mode, built together: the latency test below
    compares two of them."""
    return {m: run_mode("chord", m) for m in MODES}


@pytest.fixture(scope="module", params=MODES,
                ids=[f"chord-{m}" for m in MODES])
def mode_run(request, chord_runs):
    return "chord", request.param, chord_runs[request.param]


def test_oneway_delivery(mode_run):
    overlay, mode, (s, st, out) = mode_run
    assert out["kbr_sent"] > 100, out
    ratio = out["kbr_delivered"] / out["kbr_sent"]
    assert ratio > 0.95, (overlay, mode, ratio, out)
    assert out["kbr_wrong_node"] == 0


def test_rpc_roundtrip(mode_run):
    """The reply transport is what separates the modes: semi = direct,
    full = routed by key, source = reversed visitedHops."""
    overlay, mode, (s, st, out) = mode_run
    assert out["kbr_rpc_sent"] > 100, out
    ratio = out["kbr_rpc_success"] / out["kbr_rpc_sent"]
    assert ratio > 0.93, (overlay, mode, ratio, out)


def test_recursive_hops_bounded(mode_run):
    """Recursive routes stay near the overlay's hop geometry (~O(log N);
    de Bruijn overlays re-derive their ext per restart, which costs a
    bit more than the reference's carried ext — still far below the
    hop_max drop bound)."""
    overlay, mode, (s, st, out) = mode_run
    mean = out["kbr_hopcount"]["mean"]
    assert 1.0 <= mean <= 12.0, (overlay, mode, mean)


def test_reply_latency_ordering(chord_runs):
    """Full/source replies traverse the overlay (multi-hop) — their RPC
    RTT must exceed the semi-recursive direct reply's on average."""
    _, _, sem = chord_runs["semi"]
    _, _, src = chord_runs["source"]
    assert (src["kbr_rpc_rtt_s"]["mean"]
            > sem["kbr_rpc_rtt_s"]["mean"] * 1.2), (
        sem["kbr_rpc_rtt_s"], src["kbr_rpc_rtt_s"])


def test_prox_aware_iterative():
    """PROX_AWARE_ITERATIVE (CommonMessages.msg:140 — enum-only in the
    reference, implemented in common/lookup.py): the next FindNode goes
    to the proximity-best of the closest unqueried candidates.  Lookups
    must still converge with full delivery."""
    from oversim_tpu.common import lookup as lk_mod
    from oversim_tpu.overlay.kademlia import KademliaLogic

    app = KbrTestApp(KbrTestParams(test_interval=20.0))
    logic = KademliaLogic(
        app=app, lcfg=lk_mod.LookupConfig(merge=True, prox_aware=True))
    cp = churn_mod.ChurnParams(model="none", target_num=N,
                               init_interval=0.2)
    # window 0.1: recursive ACK timeouts are 1.5 s — ordering
    # semantics are insensitive at this scale and the tick count (the
    # run cost on XLA-CPU) falls with the window
    ep = sim_mod.EngineParams(window=0.100, transition_time=TRANSITION_S,
                              inbox_slots=INBOX_SLOTS)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=11)
    st = s.run_until(st, RUN_S, chunk=128)
    out = s.summary(st)
    assert out["kbr_sent"] > 100, out
    assert out["kbr_delivered"] / out["kbr_sent"] > 0.95, out
    assert out["kbr_wrong_node"] == 0
