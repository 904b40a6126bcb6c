"""Multi-device integration: the full simulation sharded over the
8-device virtual CPU mesh must reproduce the unsharded run.

conftest.py forces --xla_force_host_platform_device_count=8, the same
GSPMD compilation path real TPU meshes take (parallel/mesh.py).  Same
seed ⇒ the integer workload counters must match exactly (the math is
identical; only reduction orders could differ, and those only touch the
float stat accumulators).

The 2D (replica x node) mesh is test_mesh_2d.py and the driver's
multichip dryrun test_mesh_dryrun.py: a module is one unit of work on
one xdist worker (tests/conftest.py)."""

import jax
import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.chord import ChordLogic
from oversim_tpu.parallel import mesh as mesh_mod

N = 32
TICKS = 400    # some 40 sim-s at 100 ms windows: the 32 nodes have
               # joined by second 6.4, measurement opens 10 s later, and
               # the rest is four to five rounds of one test per node per
               # 5 s (some 150 tests for the > 100 below)


def _make_sim():
    logic = ChordLogic(app=KbrTestApp(KbrTestParams(test_interval=5.0)))
    cp = churn_mod.ChurnParams(model="none", target_num=N,
                               init_interval=0.2)
    ep = sim_mod.EngineParams(window=0.100, transition_time=10.0,
                              inbox_slots=2)
    return sim_mod.Simulation(logic, cp, engine_params=ep)


@pytest.fixture(scope="module")
def pair():
    sim = _make_sim()
    # unsharded
    st = sim.init(seed=5)
    st = sim.run_chunk(st, TICKS)
    plain = sim.summary(st)

    # sharded over all 8 virtual devices
    assert len(jax.devices()) >= 8, "conftest must provide 8 devices"
    mesh = mesh_mod.make_mesh(8)
    st2 = mesh_mod.shard_state(sim.init(seed=5), mesh)
    run = mesh_mod.jit_run(sim, mesh, TICKS, donate=False)
    st2 = run(st2)
    sharded = sim.summary(st2)
    return plain, sharded, st2, mesh


def test_sharded_state_placement(pair):
    _, _, st2, mesh = pair
    shd = st2.alive.sharding
    assert shd.is_equivalent_to(
        mesh_mod.NamedSharding(mesh, mesh_mod.P(mesh_mod.NODE_AXIS)),
        st2.alive.ndim)


def test_sharded_run_matches_unsharded(pair):
    plain, sharded, _, _ = pair
    assert plain["_ticks"] == sharded["_ticks"] == TICKS
    assert plain["_alive"] == sharded["_alive"] == N
    # the workload actually ran
    assert plain["kbr_sent"] > 100
    # integer counters: identical math ⇒ identical results
    for key in ("kbr_sent", "kbr_delivered", "kbr_wrong_node",
                "chord_joins"):
        assert plain[key] == sharded[key], key
    # float accumulators may differ by reduction order only
    assert np.isclose(plain["kbr_hopcount"]["mean"],
                      sharded["kbr_hopcount"]["mean"], rtol=1e-6)
    d = plain["kbr_delivered"] / plain["kbr_sent"]
    assert d > 0.95


def test_sharded_engine_counters(pair):
    """Every engine counter agrees, the awake-set plane's own tallies
    among them: since PR 28 the GSPMD builders step the plane the
    Simulation resolves (mesh._gspmd_step), here Chord's default, the
    awake-set tick, on both sides over 400 ticks."""
    plain, sharded, _, _ = pair
    assert plain["_engine"]["lanes_stepped"] > 0
    for k, v in plain["_engine"].items():
        assert sharded["_engine"][k] == v, k
