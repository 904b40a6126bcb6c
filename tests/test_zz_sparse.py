"""Awake-set tick tests (engine/sim.py _step_sparse; ISSUE 16, ISSUE 27).

The dense tick is the bit-identity ORACLE: every SimState leaf after a
churned run must match the dense engine exactly — chord and kademlia,
across awake-set occupancy extremes (an idle tick, 100% awake,
R-overflow pressure) and at ANY active_cap: awake
nodes past one round's A lanes are stepped in further rounds of the
same tick, never deferred.

(Late-alphabet filename on purpose: these are compile-heavy tests.
Tier-1 keeps the identity runs, the default resolution, the
one-node-step-body pin and the awake-order oracle here, and in
test_zz_sparse_rounds.py (a module of its own: a module is one unit of
work on one xdist worker) the cell's own deployment at N=128 under a
cap that forces several rounds a tick and the all-awake and idle
extremes; the remaining occupancy/window variants are marked
slow — scripts/sparse_gate.py re-covers the identity in every
run_suite pass.)
"""

import dataclasses
import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.core import keys as keys_mod
from oversim_tpu.engine.sim import (
    CHURN_COUNTERS, ENGINE_COUNTERS, PLANE_COUNTERS, EngineParams,
    Simulation)


def _sim(overlay, tick_impl="dense", active_cap=0,
         churn="lifetime", interval=None, slots=4, n=12,
         init_interval=0.2):
    app = (KbrTestApp(KbrTestParams(test_interval=interval))
           if interval else None)
    if overlay == "chord":
        from oversim_tpu.overlay.chord import ChordLogic
        logic = ChordLogic(app=app)
    else:
        from oversim_tpu.overlay.kademlia import KademliaLogic
        logic = KademliaLogic(app=app)
    cp = churn_mod.ChurnParams(model=churn, target_num=n,
                               init_interval=init_interval,
                               lifetime_mean=8.0)
    ep = EngineParams(window=0.1, inbox_slots=slots, pool_factor=4,
                      tick_impl=tick_impl, active_cap=active_cap)
    return Simulation(logic, cp, engine_params=ep)


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL_N = 128


def _cell_sim(tick_impl="auto", active_cap=0, n=CELL_N, **engine):
    """``kademlia4096.kbr60``'s deployment as the benchmark builds it
    (benchmark/program.py: the configuration file's own ini text and
    engine sizes, the traffic file's overrides, ``build_simulation``)
    at N=128 with the fill time kept."""
    from oversim_tpu.config.ini import IniFile
    from oversim_tpu.config.scenario import build_simulation
    with open(os.path.join(ROOT, "benchmark/configs/kademlia4096.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark/traffic/kbr60.json")) as f:
        pairs = dict(json.load(f)["overrides"])
    pairs["**.targetOverlayTerminalNum"] = n
    pairs["**.initPhaseCreationInterval"] = float(config["fill_s"]) / n
    ini = IniFile.loads("\n".join(config["ini"]))
    section = ini.with_overrides("General", pairs)
    ep = EngineParams(**{**config["engine"], **engine},
                      tick_impl=tick_impl, active_cap=active_cap)
    return build_simulation(ini, section, ep), config


def _strip_sparse(st):
    """Drop the sparse-only counters so the dense and sparse SimState
    pytrees become layout-comparable (the dense engine never carries
    them — sim.counter_names)."""
    return dataclasses.replace(
        st, counters={k: v for k, v in st.counters.items()
                      if k not in PLANE_COUNTERS})


def _assert_tree_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    paths = jax.tree_util.tree_flatten_with_path(a)[0]
    for (path, _), x, y in zip(paths, la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            jax.tree_util.keystr(path)


def _identity_run(overlay, n_ticks=64, seed=3, **kw):
    """64 churned ticks full-step: the awake-set plane (auto cap =
    full-N here unless ``active_cap`` says less) must land on the EXACT
    dense SimState, bit for bit."""
    finals = {}
    for tick_impl in ("dense", "sparse"):
        sim = _sim(overlay, tick_impl=tick_impl, **kw)
        s = sim.init(seed=seed)
        finals[tick_impl] = jax.device_get(sim.run_chunk(s, n_ticks))
    # counter layout: dense stays pre-sparse, sparse rides its three
    # and the inbox selection's two
    assert set(finals["dense"].counters) == set(ENGINE_COUNTERS)
    assert set(finals["sparse"].counters) \
        == set(ENGINE_COUNTERS + PLANE_COUNTERS)
    _assert_tree_equal(finals["dense"], _strip_sparse(finals["sparse"]))
    assert int(finals["dense"].tick) == n_ticks
    return finals


def test_dense_step_counts_its_rows_on_an_awake_set_layout():
    """The dense step handed a state that carries the awake-set counters
    (``tick_impl="dense"`` by name on the layout of another
    Simulation's ``init()``): ``lanes_stepped`` gains the alive rows of
    every tick, the other two tallies stay 0, and every other leaf is
    the dense run's, bit for bit."""
    dense = _sim("kademlia", tick_impl="dense")
    sparse = _sim("kademlia", tick_impl="sparse")
    step = jax.jit(dense.step)
    s, ref = sparse.init(seed=3), dense.init(seed=3)
    assert set(s.counters) == set(ENGINE_COUNTERS + PLANE_COUNTERS)
    rows = 0
    for _ in range(48):
        s, ref = step(s), step(ref)
        rows += int(np.sum(s.alive))
    assert rows > 0
    assert int(s.counters["lanes_stepped"]) == rows
    assert int(s.counters["awake_nodes"]) == 0
    assert int(s.counters["active_dst"]) == 0
    assert set(ref.counters) == set(ENGINE_COUNTERS)
    _assert_tree_equal(jax.device_get(ref), _strip_sparse(jax.device_get(s)))


# -- bit-identity under lifetime churn ----------------------------------------


def test_sparse_identity_chord_scatter_under_churn():
    finals = _identity_run("chord")
    assert int(np.sum(finals["dense"].alive)) > 0
    assert int(np.sum(finals["dense"].pool.valid)) > 0   # traffic ran
    assert int(finals["sparse"].counters["awake_nodes"]) > 0


def test_sparse_identity_chord_two_creations_in_one_tick():
    """A fill of one node every 20 ms under a 0.1 s window: several
    nodes fall due in a tick that finds no READY node, ONE starts the
    ring (``ChordLogic.ring_starter``) and the others keep their join
    timer.  A joiner that waits stays due, so the awake-set plane must
    step it in the next tick as the dense sweep does: every leaf equal,
    and every node READY in one ring at the end."""
    from test_chord_ring import ring_faults
    finals = {}
    for tick_impl in ("dense", "sparse"):
        sim = _sim("chord", tick_impl=tick_impl, churn="none",
                   init_interval=0.02)
        finals[tick_impl] = jax.device_get(
            sim.run_chunk(sim.init(seed=3), 64))
    _assert_tree_equal(finals["dense"], _strip_sparse(finals["sparse"]))
    st = finals["sparse"]
    assert int(st.counters["awake_nodes"]) > 0
    t_born = np.sort(np.asarray(st.churn.t_born))
    assert (np.diff(t_born) == 0).any(), "no two creations in one tick"
    assert ring_faults(st) == (12, 0, 0)


def test_sparse_identity_kademlia_scatter_under_churn():
    finals = _identity_run("kademlia")
    assert int(np.sum(finals["dense"].alive)) > 0
    assert int(finals["sparse"].counters["active_dst"]) > 0


# -- occupancy extremes -----------------------------------------------------


@pytest.mark.slow
def test_sparse_identity_empty_active_set():
    """Near-empty windows: joins staggered ~50s out, so only the t=0
    bootstrap node ever wakes in the first 8 ticks (no messages at
    all)."""
    finals = {}
    for tick_impl in ("dense", "sparse"):
        sim = _sim("chord", tick_impl=tick_impl, churn="none")
        sim.cp = dataclasses.replace(sim.cp, init_interval=50.0)
        s = sim.init(seed=11)
        finals[tick_impl] = jax.device_get(sim.run_chunk(s, 8))
    _assert_tree_equal(finals["dense"], _strip_sparse(finals["sparse"]))
    assert int(np.sum(finals["dense"].alive)) == 1     # bootstrap only
    assert int(finals["sparse"].counters["awake_nodes"]) <= 8
    assert int(finals["sparse"].counters["active_dst"]) == 0



@pytest.mark.slow
def test_sparse_identity_full_activity():
    """100% awake: a KBRTest re-arm interval shorter than the window
    fires on every READY node every tick — after a 128-tick warm (join
    + ring stabilization; saturation measured to arrive by tick ~110)
    the active set IS the alive population every tick, and identity
    must survive the densest case."""
    n, warm, meas = 12, 128, 32
    finals = {}
    marks = {}
    for tick_impl in ("dense", "sparse"):
        sim = _sim("chord", tick_impl=tick_impl, churn="none",
                   interval=0.05, n=n)
        s = sim.run_chunk(sim.init(seed=3), warm)
        if tick_impl == "sparse":
            marks["warm"] = int(jax.device_get(
                s.counters["awake_nodes"]))
        finals[tick_impl] = jax.device_get(sim.run_chunk(s, meas))
    _assert_tree_equal(finals["dense"], _strip_sparse(finals["sparse"]))
    alive = int(np.sum(finals["dense"].alive))
    assert alive == n
    awake = int(finals["sparse"].counters["awake_nodes"]) - marks["warm"]
    # saturated steady state: every alive node awake in every measured
    # tick (one-tick slack for a re-arm landing on a window boundary)
    assert awake >= alive * (meas - 1)
    assert awake <= alive * meas


@pytest.mark.slow
def test_sparse_identity_r_overflow_pressure():
    """inbox_slots=2 under kbr traffic + churn: per-dest R-overflow
    defers deliveries to later ticks (inbox_deferred > 0) and the
    deferred pool slots re-enter compaction identically."""
    finals = _identity_run("chord", slots=2, interval=0.2)
    assert int(finals["dense"].counters["inbox_deferred"]) > 0


# -- which plane a deployment gets ------------------------------------------


# an overlay with no declaration of its own (Koorde inherits Chord's;
# Pastry and Bamboo declare theirs since ISSUE 45,
# tests/test_pastry_bamboo.py)
UNDECLARED_INI = """
[General]
**.overlayType = "oversim.overlay.broose.BrooseModules"
**.tier1Type = "oversim.applications.kbrtestapp.KBRTestAppModules"
**.targetOverlayTerminalNum = 8
"""


def test_default_tick_plane_resolution():
    """The engine's default: the awake-set plane for a logic that
    declares it exact (the benchmark cells' deployment), the dense
    sweep for every other overlay; the vmapped campaign runner and the
    hand-sharded tick take dense for themselves; and no logic without
    the declaration is given the plane, by default or by name."""
    from oversim_tpu.campaign import Campaign
    from oversim_tpu.config.ini import IniFile
    from oversim_tpu.config.scenario import ScenarioError, build_simulation
    from oversim_tpu.engine.sim import resolve_tick_impl
    from oversim_tpu.parallel import mesh as mesh_mod
    from oversim_tpu.parallel.shard_tick import ShardedSim

    assert EngineParams().tick_impl == "auto"
    cell, config = _cell_sim()
    assert "tick_impl" not in config["engine"]
    assert not any("tickImpl" in ln for ln in config["ini"])
    assert cell.logic.awake_set_exact and cell.tick_impl == "sparse"
    assert cell.acap == min(cell.n, max(32, cell.n // 32)) == 32
    # (the churn phase's counters ride along under a churn law only)
    assert set(cell.counter_names) == set(
        ENGINE_COUNTERS + PLANE_COUNTERS) - set(CHURN_COUNTERS)

    broose = build_simulation(IniFile.loads(UNDECLARED_INI))
    assert type(broose.logic).__name__ == "BrooseLogic"
    assert not getattr(broose.logic, "awake_set_exact", False)
    assert broose.ep.tick_impl == "auto" and broose.tick_impl == "dense"
    assert broose.counter_names == ENGINE_COUNTERS
    twin = broose.for_vmap()
    assert twin.tick_impl == "dense" and twin.ep == broose.ep
    assert twin.inbox_lanes == broose.ep.pool_factor * broose.n
    assert twin.for_vmap() is twin
    with pytest.raises(ScenarioError, match="awake_set_exact"):
        build_simulation(IniFile.loads(
            UNDECLARED_INI + '**.tickImpl = "sparse"\n'))
    with pytest.raises(ValueError, match="awake_set_exact"):
        resolve_tick_impl("sparse", broose.logic)
    # Pastry and Bamboo declare it: the same ini with the overlay line
    # changed is given the awake-set plane
    pastry = build_simulation(IniFile.loads(UNDECLARED_INI.replace(
        "broose.BrooseModules", "pastry.PastryModules")))
    assert pastry.logic.awake_set_exact and pastry.tick_impl == "sparse"
    with pytest.raises(ValueError, match="unsupported"):
        resolve_tick_impl("eager", cell.logic)

    # an app that does not declare itself keeps its overlay on dense
    from oversim_tpu.apps.dht import DhtApp
    from oversim_tpu.overlay.kademlia import KademliaLogic
    assert not KademliaLogic(app=DhtApp()).awake_set_exact
    assert KademliaLogic().awake_set_exact

    camp = Campaign(cell)
    assert camp.sim is not cell and camp.sim.tick_impl == "dense"
    assert camp.sim.counter_names == ENGINE_COUNTERS
    # ... and the P-wide inbox selection (under vmap a cond runs both
    # its branches); the cell compacts the due messages into P/32 lanes
    p = cell.ep.pool_factor * cell.n
    assert cell.inbox_lanes == p // 32 and camp.sim.inbox_lanes == p
    asked, _ = _cell_sim(tick_impl="sparse")
    twin = Campaign(asked).sim                         # asked by name
    assert twin.tick_impl == "sparse" and twin.ep == asked.ep
    assert twin.inbox_lanes == p
    # the hand-sharded tick runs dense alone, and has to be asked so
    mesh = mesh_mod.make_mesh_2d(1, 2)
    for refused in (cell, asked):
        with pytest.raises(ValueError, match="tick_impl='dense'"):
            ShardedSim(refused, mesh)
    dense, _ = _cell_sim(tick_impl="dense")
    assert ShardedSim(dense, mesh).sim is dense


def test_default_kademlia_tick_holds_one_node_step_body():
    """The default Kademlia tick program (the awake-set plane) holds
    exactly as many sorts as the dense oracle's — one copy of the node
    step, not a sparse branch beside a dense fallback — none of them
    full-pool, and no wide [N, R, W] payload gather."""
    from oversim_tpu.analysis import hlo_text
    counts = {}
    for tick_impl in ("dense", "auto"):
        sim = _sim("kademlia", tick_impl=tick_impl, churn="none", n=64,
                   active_cap=16 if tick_impl == "auto" else 0)
        s = sim.init(seed=3)
        txt = jax.jit(sim.step).lower(s).compile().as_text()
        pool_dim = sim.ep.pool_factor * sim.n
        counts[sim.tick_impl] = dict(
            hlo_text.hlo_op_counts(txt, pool_dim),
            **hlo_text.gather_counts(txt, wide_dims=(sim.n, pool_dim)))
    dense, sparse = counts["dense"], counts["sparse"]
    assert sparse["sort_count"] == dense["sort_count"] > 0, counts
    assert sparse["full_pool_sort_count"] == 0
    assert sparse["wide_gather_count"] < dense["wide_gather_count"]


@pytest.mark.slow
def test_active_cap_at_capacity_is_exact():
    """cap == n is the auto-cap small-N case spelled explicitly: one
    round a tick, bit-identity to dense."""
    dense = _sim("chord", churn="none", interval=0.2)
    sparse = _sim("chord", tick_impl="sparse", active_cap=12,
                  churn="none", interval=0.2)
    a = jax.device_get(dense.run_chunk(dense.init(seed=5), 32))
    b = jax.device_get(sparse.run_chunk(sparse.init(seed=5), 32))
    assert int(b.counters["lanes_stepped"]) <= 32 * 12
    _assert_tree_equal(a, _strip_sparse(b))


# -- the awake order vs numpy nonzero -----------------------------------------


class _TimersOnly:
    """The least a Simulation asks of a logic to compact an awake set:
    its state IS the [N] next-event times."""
    key_spec = keys_mod.KeySpec(160)
    awake_set_exact = True

    def next_event(self, state):
        return state


# name -> (n, A, the awake nodes: a count taken at random, or "random")
AWAKE_CASES = {
    "none_awake": (16, 4, 0),
    "all_awake": (16, 4, 16),
    "awake_eq_a": (16, 4, 4),
    "awake_a_plus_1": (16, 4, 5),
    "random_a": (48, 8, "random"),
    "random_b": (1000, 32, "random"),      # N=1000's own N and A
    "n_not_multiple_of_a": (13, 4, 13),
}


@pytest.mark.parametrize("name", list(AWAKE_CASES))
def test_awake_order_equals_nonzero(name):
    """``_phase_active_compact`` on a made-up inbox table and made-up
    timers: ``order`` is ``np.nonzero`` of the awake mask (a message in
    slot 0, a due timer, a slot churn made this tick), ascending, then
    the sentinels n + j out to ceil(N/A) * A lanes; ``rounds`` is
    ceil(awake / A); and ``active`` tallies the awake nodes, the nodes
    with a message and the lanes the rounds step."""
    n, cap, k = AWAKE_CASES[name]
    rng = np.random.default_rng(sum(map(ord, name)))
    if k == "random":
        awake = rng.random(n) < rng.random()
    else:
        awake = np.zeros(n, bool)
        awake[rng.choice(n, size=k, replace=False)] = True
    # every awake node has a message, a due timer, both, or was created
    # by this tick's churn
    has_msg = awake & (rng.random(n) < 0.5)
    timer = awake & (rng.random(n) < 0.4)
    created = awake & ~has_msg & ~timer
    t_end = 1000
    sim = Simulation(
        _TimersOnly(), churn_mod.ChurnParams(model="none", target_num=n),
        engine_params=EngineParams(inbox_slots=3, active_cap=cap))
    assert sim.n == n and sim.acap == cap and sim.tick_impl == "sparse"
    inbox = np.full((n, 3), -1, np.int32)
    inbox[has_msg, 0] = rng.integers(0, 8 * n, size=int(has_msg.sum()))
    alive = jnp.ones((n,), bool)
    order, rounds, active = jax.jit(
        lambda was_alive, timers, ib: sim._phase_active_compact(
            SimpleNamespace(alive=was_alive), jnp.int64(t_end), alive,
            jnp.zeros((n,), bool), timers, ib))(
        jnp.asarray(~created),
        jnp.asarray(np.where(timer, t_end - 1, t_end + 5), jnp.int64),
        jnp.asarray(inbox))
    lanes = -(-n // cap) * cap
    want = np.concatenate([np.nonzero(awake)[0],
                           n + np.arange(awake.sum(), lanes)])
    assert order.dtype == jnp.int32 and order.shape == (lanes,)
    assert (np.asarray(order) == want).all()
    assert (np.diff(np.asarray(order)) > 0).all()      # sorted, unique
    assert int(rounds) == -(-int(awake.sum()) // cap)
    assert [int(x) for x in active] == [
        awake.sum(), has_msg.sum(), int(rounds) * cap]


# -- the measurement loop stays one-dispatch-one-fetch ----------------------


@pytest.mark.slow
def test_sparse_window_one_dispatch_one_fetch(monkeypatch):
    """A REAL sparse sim under bench.run_measurement_windows: per
    window exactly one run_until_device dispatch and one
    _fetch_window_leaves device_get, with the sparse counters riding
    inside that single fetch."""
    import bench

    fetched = []
    real_fetch = bench._fetch_window_leaves
    monkeypatch.setattr(bench, "_fetch_window_leaves",
                        lambda s: fetched.append(real_fetch(s))
                        or fetched[-1])
    sim = _sim("chord", tick_impl="sparse", churn="none", interval=0.2)
    dispatches = []
    real_run = sim.run_until_device

    def counting_run(s, t_sim, chunk=256):
        dispatches.append(float(t_sim))
        return real_run(s, t_sim, chunk=chunk)

    sim.run_until_device = counting_run

    class Clock:
        t = 0.0

        def __call__(self):
            Clock.t += 10.0
            return Clock.t - 10.0

    s = sim.init(seed=7)
    s = real_run(s, 1.0, chunk=8)               # warm outside the pin
    s, windows = bench.run_measurement_windows(
        sim, s, start_sim_t=1.0, window_sim_s=0.4, measure_wall=35.0,
        chunk=4, on_window=lambda out, wall: None, now=Clock())
    assert windows == 2
    assert len(dispatches) == 2                 # ONE dispatch per window
    assert len(fetched) == 2                    # ONE device_get per window
    for leaves in fetched:
        assert "awake_nodes" in leaves["counters"]
        assert "lanes_stepped" in leaves["counters"]
