"""The multi-chip entry a timed run takes (parallel/mesh.py; ISSUE 28):
``shard_state`` + ``jit_run_until`` on four of the eight virtual devices
against one device, Kademlia under KBRTestApp at N=128, same seed, same
ticks.

Pinned here: the node-sharded run lands on the one-device run on every
integer counter, the application's timers and sequence numbers and the
routing tables, on the plane ``jit_run_until`` gives by default (what
the Simulation resolves: the awake-set plane for Kademlia, since PR 28,
so ``tick_impl="sparse"`` by name is the same program) and on the dense
sweep asked by name; the
node-row and pool leaves really are four blocks on four devices; calls
with different targets share ONE compiled program; the shardings are
sized from shapes, not from a second eager ``sim.init()``.

One module, its simulations built once (a module is one unit of work on
one xdist worker, tests/conftest.py); engine sizes as the suite's notes
say (``inbox_slots`` 2, window 0.1 s).
"""

import dataclasses

import jax
import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.kademlia import KademliaLogic
from oversim_tpu.parallel import mesh as mesh_mod

N = 128
CHIPS = 4
CHUNK = 8
SEED = 7
T_HALF = 8.0          # the fill (6.4 s) and a little settling
T_END = 16.0          # then 8 s of tests: some 100 lookups
NS = sim_mod.NS


def _sim(tick_impl="auto"):
    logic = KademliaLogic(app=KbrTestApp(KbrTestParams(test_interval=10.0)))
    cp = churn_mod.ChurnParams(model="none", target_num=N,
                               init_interval=0.05)
    ep = sim_mod.EngineParams(window=0.1, inbox_slots=2,
                              tick_impl=tick_impl)
    return sim_mod.Simulation(logic, cp, engine_params=ep)


def _target(t_sim):
    return np.int64(int(t_sim * NS))


@pytest.fixture(scope="module")
def solo():
    """One device, the engine's default plane (the awake-set tick)."""
    sim = _sim()
    assert sim.tick_impl == "sparse"
    s = sim._run_until_device(sim.init(SEED), _target(T_HALF), CHUNK)
    s = sim._run_until_device(s, _target(T_END), CHUNK)
    return jax.device_get(s)


@pytest.fixture(scope="module")
def meshed():
    """Each plane's run on four devices, built once: ``{plane: (state
    on the mesh, runner, mesh)}``."""
    assert len(jax.devices()) >= CHIPS, "conftest must provide 8 devices"
    made = {}

    def get(tick_impl):
        if tick_impl not in made:
            sim = _sim(tick_impl)
            mesh = mesh_mod.make_mesh(CHIPS)
            run = mesh_mod.jit_run_until(sim, mesh, chunk=CHUNK)
            s = mesh_mod.shard_state(sim.init(SEED), mesh)
            s = run(s, _target(T_HALF))
            s = run(s, _target(T_END))
            made[tick_impl] = (jax.block_until_ready(s), run, mesh)
        return made[tick_impl]

    return get


@pytest.mark.parametrize("tick_impl", ["auto", "dense"])
def test_four_devices_equal_one_device(solo, meshed, tick_impl):
    """Every integer leaf the benchmark's comparison reads, and every
    integer statistic and engine counter: equal, to the unit."""
    assert _sim(tick_impl).tick_impl == (
        "dense" if tick_impl == "dense" else "sparse")
    s4 = jax.device_get(meshed(tick_impl)[0])
    assert int(s4.tick) == int(solo.tick) and int(solo.tick) % CHUNK == 0
    assert int(s4.t_now) == int(solo.t_now) >= int(T_END * NS)
    assert int(np.sum(s4.alive)) == N
    assert int(solo.stats["c:kbr_sent"]) > 50       # the workload ran
    for k, v in solo.stats.items():
        if np.issubdtype(np.asarray(v).dtype, np.integer):
            assert np.array_equal(s4.stats[k], v), k
    # the dense layout carries no awake-set tallies and no account of
    # the inbox selection; all else is equal, and on the awake-set plane
    # those too (rounds x A lanes; D lanes or P slots a tick)
    for k, v in solo.counters.items():
        if tick_impl != "dense" or k not in sim_mod.PLANE_COUNTERS:
            assert int(s4.counters[k]) == int(v), k
    if tick_impl != "dense":
        p = sim_mod.EngineParams().pool_factor * N
        assert int(s4.counters["inbox_pool_slots"]) == p * int(s4.tick)
        assert 0 < int(s4.counters["inbox_lanes"]) < p * int(s4.tick)
    for name in ("t_test", "seq"):
        assert np.array_equal(getattr(s4.logic.app, name),
                              getattr(solo.logic.app, name)), name
    for name in ("buckets", "sib", "state"):
        assert np.array_equal(getattr(s4.logic, name),
                              getattr(solo.logic, name)), name
    for name in ("valid", "blk", "t_deliver"):
        assert np.array_equal(getattr(s4.pool, name),
                              getattr(solo.pool, name)), name
    assert np.array_equal(s4.node_keys, solo.node_keys)


def test_the_default_plane_is_the_one_the_simulation_resolves(solo, meshed):
    """``jit_run_until`` partitions ``sim.step``: for a Simulation that
    asks for no plane by name that is the awake-set tick, whose lanes
    (rounds x A, never 0) are a small share of the rows a dense sweep
    pays and equal one device's."""
    s4 = meshed("auto")[0]
    lanes = int(s4.counters["lanes_stepped"])
    assert lanes == int(solo.counters["lanes_stepped"])
    assert 0 < int(s4.counters["awake_nodes"]) <= lanes < N * int(s4.tick)
    assert mesh_mod._gspmd_step(_sim()).__func__ is sim_mod.Simulation.step
    assert _sim("sparse").tick_impl == _sim().tick_impl == "sparse"


def test_node_rows_and_pool_are_four_blocks_on_four_devices(meshed):
    s4, _, mesh = meshed("auto")
    leaves = {"alive": s4.alive, "node_keys": s4.node_keys,
              "buckets": s4.logic.buckets, "sib": s4.logic.sib,
              "state": s4.logic.state, "t_test": s4.logic.app.t_test,
              "pool.valid": s4.pool.valid, "pool.blk": s4.pool.blk,
              "pool.t_deliver": s4.pool.t_deliver}
    leaves.update({"lk." + f.name: getattr(s4.logic.lk, f.name)
                   for f in dataclasses.fields(s4.logic.lk)})
    for name, x in leaves.items():
        shards = x.addressable_shards
        rows = [sh.data.shape[0] for sh in shards]
        starts = sorted(sh.index[0].start or 0 for sh in shards)
        block = x.shape[0] // CHIPS
        assert rows == [block] * CHIPS, name
        assert starts == [i * block for i in range(CHIPS)], name
        assert len({sh.device for sh in shards}) == CHIPS, name
        assert x.sharding.is_equivalent_to(
            mesh_mod.NamedSharding(mesh, mesh_mod.P(mesh_mod.NODE_AXIS)),
            x.ndim), name
    # scalars are copies on every device
    assert s4.t_now.sharding.is_fully_replicated


def test_one_program_serves_every_target(meshed):
    """Set-up's long call and the window's short ones: the target is a
    traced scalar, so there is one compiled program, and a call whose
    target lies one ns ahead advances exactly one dispatch."""
    s4, run, _ = meshed("auto")
    assert run._cache_size() == 1
    tick, t_now = int(s4.tick), int(s4.t_now)
    s4 = run(s4, np.int64(t_now + 1))
    assert int(s4.tick) == tick + CHUNK
    s4 = run(s4, np.int64(int(s4.t_now)))       # already there: no tick
    assert int(s4.tick) == tick + CHUNK
    assert run._cache_size() == 1


def test_shardings_come_from_shapes_not_from_a_second_init(monkeypatch):
    """``jit_step``, ``jit_run`` and ``jit_run_until`` size their
    shardings from ``jax.eval_shape(sim.init_from_rng, ...)``."""
    sim = _sim()

    def no_init(*a, **kw):
        raise AssertionError("an eager sim.init() inside a mesh builder")

    monkeypatch.setattr(sim, "init", no_init)
    mesh = mesh_mod.make_mesh(CHIPS)
    mesh_mod.jit_step(sim, mesh)
    mesh_mod.jit_run(sim, mesh, 4)
    mesh_mod.jit_run_until(sim, mesh, chunk=CHUNK)
    example = jax.eval_shape(sim.init_from_rng, jax.random.PRNGKey(0))
    from_shapes = mesh_mod.state_shardings(example, mesh)
    assert from_shapes.pool.blk.spec == mesh_mod.P(mesh_mod.NODE_AXIS, None)
    assert from_shapes.t_now.spec == mesh_mod.P()
