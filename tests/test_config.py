"""Config front-end: OMNeT++ ini parsing, wildcard resolution, scenario
factory — exercised against assignment lines written exactly like the
reference's simulations/default.ini / omnetpp.ini."""

import os
import textwrap

import pytest

from oversim_tpu.config.ini import IniFile, Study, parse_value
from oversim_tpu.config import scenario


def test_parse_value_literals():
    assert parse_value("true") is True
    assert parse_value("false") is False
    assert parse_value("42") == 42
    assert parse_value("0.5") == 0.5
    assert parse_value('"iterative"') == "iterative"
    assert parse_value("60s") == 60.0
    assert parse_value("20ms") == 0.02
    assert parse_value("100B") == 100.0
    assert parse_value("10Mbps") == 10e6


def test_parse_study():
    st = parse_value("${50,100,200}")
    assert isinstance(st, Study)
    assert st.values == (50, 100, 200)
    st = parse_value("${N=1..5 step 2}")
    assert st.name == "N"
    assert st.values == (1, 3, 5)


INI = textwrap.dedent("""
    [General]
    **.overlay*.chord.stabilizeDelay = 20s
    **.overlay*.chord.successorListSize = 8
    **.targetOverlayTerminalNum = 10
    **.overlayType = "oversim.overlay.chord.ChordModules"
    **.tier1*.kbrTestApp.testMsgInterval = 60s

    [Config ChordFast]
    **.overlay*.chord.stabilizeDelay = 5s

    [Config ChordFaster]
    extends = ChordFast
    **.targetOverlayTerminalNum = 32

    [Config Kad]
    **.overlayType = "oversim.overlay.kademlia.KademliaModules"
    **.overlay*.kademlia.k = 16

    [Config KadSparseTick]
    extends = Kad
    **.tickImpl = "sparse"
    **.activeCap = 16

    [Config KadBadTick]
    extends = Kad
    **.tickImpl = "dense_ish"
""")


@pytest.fixture()
def ini():
    return IniFile.loads(INI)


def test_wildcard_resolution(ini):
    path = "OverSim.overlayTerminal[3].overlay.chord.stabilizeDelay"
    assert ini.get(path) == 20.0
    assert ini.get(path, "ChordFast") == 5.0
    # extends chain: ChordFaster -> ChordFast -> General
    assert ini.get(path, "ChordFaster") == 5.0
    assert ini.get("**.targetOverlayTerminalNum".replace("**", "OverSim"),
                   "ChordFaster") == 32
    assert ini.get("OverSim.x.overlay.chord.successorListSize",
                   "ChordFaster") == 8


def test_star_does_not_cross_segments():
    ini = IniFile.loads("*.foo = 1\n**.bar = 2\n")
    assert ini.get("a.foo") == 1
    assert ini.get("a.b.foo") is None
    assert ini.get("a.b.bar") == 2


def test_scenario_chord(ini):
    sim = scenario.build_simulation(ini, "ChordFaster")
    from oversim_tpu.overlay.chord import ChordLogic
    assert isinstance(sim.logic, ChordLogic)
    assert sim.logic.p.stabilize_delay == 5.0
    assert sim.n == 32


def test_scenario_kademlia(ini):
    sim = scenario.build_simulation(ini, "Kad")
    from oversim_tpu.overlay.kademlia import KademliaLogic
    assert isinstance(sim.logic, KademliaLogic)
    assert sim.logic.p.k == 16
    assert sim.logic.lcfg.merge is True


def test_scenario_tick_impl_key(ini):
    """``**.tickImpl`` selects the tick implementation (dense full-N
    oracle vs the awake-set plane; with no key the engine's "auto",
    which gives Kademlia under KBRTestApp the awake-set plane) and
    ``**.activeCap`` sets the lanes a round; anything but
    auto/dense/sparse is a config error."""
    sim = scenario.build_simulation(ini, "Kad")
    assert sim.ep.tick_impl == "auto"            # the engine's default
    assert sim.tick_impl == "sparse"             # ... for this logic
    assert sim.ep.active_cap == 0
    sim = scenario.build_simulation(ini, "KadSparseTick")
    assert sim.ep.tick_impl == "sparse"
    assert sim.ep.active_cap == 16
    with pytest.raises(scenario.ScenarioError):
        scenario.build_simulation(ini, "KadBadTick")


def test_resolve_tick_impl():
    """No availability dimension here — sparse is pure XLA, so the
    resolver is a straight validator (which plane "auto" comes to is
    the engine's to say, per logic)."""
    assert scenario.resolve_tick_impl("auto") == "auto"
    assert scenario.resolve_tick_impl("dense") == "dense"
    assert scenario.resolve_tick_impl('"sparse"') == "sparse"
    with pytest.raises(scenario.ScenarioError):
        scenario.resolve_tick_impl("eager")


@pytest.mark.skipif(
    not os.path.isdir("/root/reference/simulations"),
    reason="reference simulations not present")
def test_reference_default_ini_loads():
    """The actual reference ini tree must parse and resolve (BASELINE.json:
    'Existing .ini configs ... run unchanged')."""
    ini = IniFile.load("/root/reference/simulations/default.ini")
    assert ini.get(
        "OverSim.overlayTerminal[0].overlay.chord.stabilizeDelay") == 20.0
    assert ini.get(
        "OverSim.overlayTerminal[0].overlay.kademlia.k") == 8
    assert ini.get(
        "OverSim.overlayTerminal[0].tier1.kbrTestApp.testMsgInterval") == 60.0
