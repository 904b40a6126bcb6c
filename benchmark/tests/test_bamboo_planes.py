"""``PastryLogic.awake_set_exact`` by hand, where tier-1 cannot afford it.

tests/test_pastry_bamboo.py pins Bamboo in ``bamboo1000.kbr60``'s mode
(semi-recursive, per-hop ACKs) at N = 16: the dense sweep and the
awake-set plane leaf for leaf.  The declaration is the shared class's,
so the other modes it covers get the same test here, two tick programs
a case (minutes of XLA-CPU compile each; tier-1's clock has no room):
Pastry's defaults (16 leaves, no local tuning), Bamboo routing
iteratively, Bamboo with ACKs off, each under LifetimeChurn too (a
failed hop, the reroute, the repair), and the cell's own ini at N = 128
over a whole fill at upstream's rate, where the ring is judged from the
sorted keys as well.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_bamboo_planes.py -q -p no:cacheprovider

Nothing here is a device number.
"""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from conftest import ROOT

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.config.ini import IniFile
from oversim_tpu.config.scenario import build_simulation
from oversim_tpu.core import keys as K
from oversim_tpu.engine.sim import PLANE_COUNTERS, EngineParams, Simulation
from oversim_tpu.overlay import pastry

CASES = {
    "pastry-defaults": dict(params=pastry.PastryParams()),
    "bamboo-iterative": dict(params=dataclasses.replace(
        pastry.bamboo_params(), routing_mode="iterative")),
    "bamboo-no-acks": dict(params=dataclasses.replace(
        pastry.bamboo_params(), route_acks=False)),
    "pastry-defaults-churn": dict(params=pastry.PastryParams(),
                                  churn="lifetime"),
    "bamboo-churn": dict(params=pastry.bamboo_params(), churn="lifetime"),
}


def _sim(case, tick_impl):
    logic = pastry.PastryLogic(
        params=case["params"],
        app=KbrTestApp(KbrTestParams(test_interval=3.0)))
    cp = churn_mod.ChurnParams(model=case.get("churn", "none"),
                               target_num=16, init_interval=0.1,
                               lifetime_mean=20.0)
    ep = EngineParams(window=0.1, inbox_slots=2, pool_factor=4,
                      tick_impl=tick_impl)
    return Simulation(logic, cp, engine_params=ep)


def _strip(st):
    return dataclasses.replace(
        st, counters={k: v for k, v in st.counters.items()
                      if k not in PLANE_COUNTERS})


def _same(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    paths = jax.tree_util.tree_flatten_with_path(a)[0]
    for (path, _), x, y in zip(paths, la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("name", sorted(CASES))
def test_identity_on_both_planes(name):
    finals = {}
    for impl in ("dense", "sparse"):
        sim = _sim(CASES[name], impl)
        s = sim.init(seed=3)
        for _ in range(6):                      # 60 simulated seconds
            s = sim.run_chunk(s, 100)
        finals[impl] = jax.device_get(s)
    _same(finals["dense"], _strip(finals["sparse"]))
    st = finals["sparse"]
    # (idle rows WERE skipped; a churn law keeps twice the target's slots)
    assert 0 < int(st.counters["awake_nodes"]) < st.alive.shape[0] * 600
    assert int(st.stats["c:kbr_delivered"]) > 50


def test_the_cells_own_ini_at_n128_over_a_whole_fill():
    """Both planes leaf for leaf at second 40 (the last join is at
    12.8), and every leaf set the ring's."""
    with open(os.path.join(ROOT, "benchmark/configs/bamboo1000.json")) as f:
        config = json.load(f)
    ini = IniFile.loads("\n".join(config["ini"]))
    section = ini.with_overrides("General",
                                 {"**.targetOverlayTerminalNum": 128})
    finals = {}
    for impl in ("dense", "sparse"):
        ep = EngineParams(window=0.2, inbox_slots=4, pool_factor=16,
                          transition_time=10.0, tick_impl=impl)
        sim = build_simulation(ini, section, ep)
        s = sim.init(seed=7)
        for _ in range(4):
            s = sim.run_chunk(s, 50)
        finals[impl] = jax.device_get(s)
    _same(finals["dense"], _strip(finals["sparse"]))
    st = finals["sparse"]
    keys = [K.to_int(k) for k in np.asarray(st.node_keys)]
    order = sorted(range(128), key=lambda i: keys[i])
    assert (np.asarray(st.logic.state) == pastry.READY).all()
    cw, ccw = np.asarray(st.logic.leaf_cw), np.asarray(st.logic.leaf_ccw)
    wrong = sum(cw[i, k - 1] != order[(p + k) % 128]
                or ccw[i, k - 1] != order[(p - k) % 128]
                for p, i in enumerate(order) for k in range(1, 5))
    assert wrong == 0
