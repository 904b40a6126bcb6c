"""Generic tier stacking (ITier equivalent): any app combination on any
overlay without per-combo wiring (reference SimpleOverlayHost.ned:14-100
tier1Type/tier2Type/tier3Type, default.ini:622-628)."""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.dht import DhtApp, DhtParams
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.apps.stack import TierStack
from oversim_tpu.engine import sim as sim_mod

N = 8


def run_stack(overlay: str):
    stack = TierStack([
        KbrTestApp(KbrTestParams(test_interval=20.0)),
        DhtApp(DhtParams(test_interval=20.0, num_test_keys=32,
                         test_ttl=600.0)),
    ])
    if overlay == "chord":
        from oversim_tpu.overlay.chord import ChordLogic
        logic = ChordLogic(app=stack)
    else:
        from oversim_tpu.overlay.kademlia import KademliaLogic
        logic = KademliaLogic(app=stack)
    cp = churn_mod.ChurnParams(model="none", target_num=N,
                               init_interval=1.0)
    ep = sim_mod.EngineParams(window=0.1, transition_time=30.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=17)
    st = s.run_until(st, 200.0, chunk=128)
    return s, st, s.summary(st)


@pytest.fixture(scope="module", params=["chord", "kademlia"])
def stack_run(request):
    return request.param, run_stack(request.param)


def test_both_tiers_run(stack_run):
    """KBR one-way tests AND DHT put/gets flow through ONE node stack —
    the reference's tier1+tier2 coexistence."""
    overlay, (s, st, out) = stack_run
    assert out["kbr_sent"] > 30, (overlay, out)
    assert out["kbr_delivered"] >= 0.9 * out["kbr_sent"], (overlay, out)
    assert out["dht_put_attempts"] > 10, (overlay, out)
    assert out["dht_put_success"] >= 0.8 * out["dht_put_attempts"], (
        overlay, out)


def test_gets_validate(stack_run):
    overlay, (s, st, out) = stack_run
    assert out["dht_get_attempts"] > 3, (overlay, out)
    assert out["dht_get_wrong"] == 0, (overlay, out)


def test_stack_swap_needs_no_code(stack_run):
    """The SAME composite moved across overlays (the swap the reference
    does by editing one ini line)."""
    overlay, (s, st, out) = stack_run
    # both parametrized overlays reached here with the same TierStack
    assert out["_engine"]["pool_overflow"] == 0


def test_scenario_builds_stack_from_tier_strings(tmp_path):
    """tier1Type/tier2Type/tier3Type ini lines → TierStack, reference
    namespace (default.ini:622-628)."""
    ini_text = """
[General]
**.overlayType = "oversim.overlay.chord.ChordModules"
**.tier1Type = "oversim.applications.kbrtestapp.KBRTestAppModules"
**.tier2Type = "oversim.applications.dht.DHTModules"
**.tier3Type = "oversim.applications.xmlrpcinterface.XmlRpcInterfaceModules"
**.targetOverlayTerminalNum = 4
"""
    f = tmp_path / "stack.ini"
    f.write_text(ini_text)
    from oversim_tpu.config.ini import IniFile
    from oversim_tpu.config.scenario import build_simulation
    sim = build_simulation(IniFile.load(str(f)), "General")
    from oversim_tpu.apps.stack import TierStack as TS
    assert isinstance(sim.logic.app, TS)
    names = [type(a).__name__ for a in sim.logic.app.apps]
    assert names == ["KbrTestApp", "DhtApp"], names
