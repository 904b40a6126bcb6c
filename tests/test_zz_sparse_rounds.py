"""The awake-set tick's rounds (engine/sim.py _phase_sparse_step; ISSUE 27):
awake nodes past one round's A lanes are stepped in further rounds of
the same tick, never deferred, so the plane is the dense oracle's bit
for bit at any active_cap.

Pinned here: the benchmark cell's own deployment at N=128 through fill,
settling and 100 ticks of window at A=4 (most ticks take several
rounds), ``lanes_stepped`` against the recorded awake counts, every
node awake (N/A rounds a tick), and an idle tick (no round).  The same
recorded run pins the inbox selection over the due messages' D lanes
(engine/pool.py build_inbox; ISSUE 34): its oracle selects by the
full-pool sort (tests/oracles.py SortSimulation), and ``inbox_lanes``
adds D in a tick whose due messages fit the lanes and P in a tick that
fell back.  The helpers are test_zz_sparse.py's; a module of its own because a module
is one unit of work on one xdist worker (tests/conftest.py).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.engine import pool as pool_mod
from oversim_tpu.engine.sim import (
    INBOX_COUNTERS, SEND_COUNTERS, SPARSE_COUNTERS, Simulation)

from oracles import SortSimulation

from test_zz_sparse import (
    CELL_N, _assert_tree_equal, _cell_sim, _sim, _strip_sparse)


CELL_CAP = 4          # A: most ticks of the run below take 2+ rounds


@pytest.fixture(scope="module")
def cell_run():
    """The cell's deployment at N=128 through its fill (20 s), its
    settling (20 s) and 100 ticks of window, tick by tick, under the
    oracle of both planes (``tick_impl="dense"``, every row swept, and
    ``SortSimulation``, the full-pool sort) and under the engine's
    defaults at A=4 (the awake-set plane; the inbox selected over the
    due messages' D lanes); the plane's counters after every tick, and
    the due messages each tick's selection met, are the recorded run."""
    sparse, config = _cell_sim(active_cap=CELL_CAP)
    dense = SortSimulation.of(_cell_sim(tick_impl="dense")[0])
    assert sparse.tick_impl == "sparse" and sparse.acap == CELL_CAP
    assert type(sparse) is Simulation
    assert dense.tick_impl == "dense"
    ticks = int(round((config["fill_s"] + config["settle_s"])
                      / config["engine"]["window"])) + 100

    @jax.jit
    def due_met(s):
        """The due messages the tick about to run selects among."""
        t_next, t_end, rngs = sparse._phase_horizon(s)
        alive = sparse._phase_churn(s, t_next, t_end, rngs[1], rngs[2],
                                    rngs[3], rngs[5])[1]
        return jnp.sum(pool_mod._due_masks(
            s.pool, sparse.n, t_end, alive)[0])

    sd, ss = dense.init(seed=5), sparse.init(seed=5)
    rec, n_due = [], []
    for _ in range(ticks):
        n_due.append(int(due_met(ss)))
        ss = sparse.run_chunk(ss, 1)
        rec.append(jax.device_get(ss.counters))   # ss is donated next
    sd = dense.run_chunk(sd, ticks)
    return dict(dense=jax.device_get(sd), sparse=jax.device_get(ss),
                rec=rec, n_due=np.asarray(n_due), ticks=ticks, n=sparse.n,
                p=sparse.ep.pool_factor * sparse.n, d=sparse.inbox_lanes,
                fill_ticks=int(round(config["fill_s"]
                                     / config["engine"]["window"])))


def test_rounds_identity_on_the_cells_deployment(cell_run):
    """(replaces the deferral pin) Full-SimState identity of the rounds
    plane against the dense oracle on the benchmark cell's own
    deployment, with A so small that most ticks need several rounds:
    every awake node is stepped in the tick it is due."""
    _assert_tree_equal(cell_run["dense"], _strip_sparse(cell_run["sparse"]))
    assert int(cell_run["dense"].tick) == cell_run["ticks"]
    assert int(np.sum(cell_run["dense"].alive)) == CELL_N
    assert int(cell_run["dense"].stats["c:kbr_delivered"]) > 0
    for name in ("pool_overflow", "outbox_overflow", "queue_lost"):
        assert int(cell_run["sparse"].counters[name]) == 0, name
    awake = np.diff([0] + [int(c["awake_nodes"]) for c in cell_run["rec"]])
    assert np.mean(awake > CELL_CAP) > 0.5          # 2+ rounds, mostly
    assert awake.max() > 4 * CELL_CAP


def test_lanes_stepped_counts_the_rounds(cell_run):
    """On the recorded run: every tick steps ceil(awake / A) rounds of
    A lanes, no awake node is counted twice, and the window steps a
    small share of the dense sweep's rows."""
    rec, n = cell_run["rec"], cell_run["n"]
    awake = np.diff([0] + [int(c["awake_nodes"]) for c in rec])
    lanes = np.diff([0] + [int(c["lanes_stepped"]) for c in rec])
    assert (lanes == CELL_CAP * -(-awake // CELL_CAP)).all()
    assert int(rec[-1]["lanes_stepped"]) == CELL_CAP * int(
        np.sum(-(-awake // CELL_CAP)))
    assert (awake >= 0).all() and (awake <= n).all()
    assert int(rec[-1]["awake_nodes"]) <= len(rec) * n
    assert (np.diff([0] + [int(c["active_dst"]) for c in rec])
            <= awake).all()
    assert lanes[-100:].sum() < 0.5 * 100 * n       # the window's share


def test_inbox_lanes_counts_d_under_d_and_p_over_it(cell_run):
    """On the recorded run (whose every leaf equals the sort oracle's,
    above): ``inbox_pool_slots`` adds P every tick; ``inbox_lanes`` adds
    D in a tick whose due messages fit the D lanes and P in a tick that
    fell back to the P-wide rounds; the fill overflows D, the window's
    steady ticks do not."""
    rec, p, d = cell_run["rec"], cell_run["p"], cell_run["d"]
    n_due, fill = cell_run["n_due"], cell_run["fill_ticks"]
    assert d == pool_mod.inbox_lanes(p) == 32 and p == 8 * CELL_N
    slots = np.diff([0] + [int(c["inbox_pool_slots"]) for c in rec])
    lanes = np.diff([0] + [int(c["inbox_lanes"]) for c in rec])
    assert (slots == p).all()
    assert (lanes == np.where(n_due <= d, d, p)).all()
    over = n_due > d
    assert over[:fill].any() and (~over[:fill]).any()   # both branches ran
    assert n_due.max() < p and n_due.min() == 0
    assert not over[-100:].any()                         # the window
    assert lanes[-100:].sum() == 100 * d


def test_every_node_awake_takes_n_over_a_rounds():
    """The dense-equivalent load: a KBRTest interval shorter than the
    window keeps every ready node awake in every tick; at A = N/8 that
    is eight rounds a tick, exact, and ``lanes_stepped`` counts it."""
    n, warm, meas = 16, 128, 16
    finals, marks = {}, {}
    for tick_impl in ("dense", "sparse"):
        sim = _sim("chord", tick_impl=tick_impl, churn="none",
                   interval=0.05, n=n, active_cap=n // 8)
        assert sim.n == n
        # ONE chunk length a plane: run_chunk compiles again for each
        s = sim.init(seed=3)
        for _ in range(warm // meas):
            s = sim.run_chunk(s, meas)
        if tick_impl == "sparse":
            assert sim.acap == n // 8
            marks = {k: int(v) for k, v in
                     jax.device_get(s.counters).items()}
        finals[tick_impl] = jax.device_get(sim.run_chunk(s, meas))
    _assert_tree_equal(finals["dense"], _strip_sparse(finals["sparse"]))
    assert int(np.sum(finals["dense"].alive)) == n
    got = {k: int(finals["sparse"].counters[k]) - marks[k]
           for k in SPARSE_COUNTERS}
    # saturated: every node awake in every measured tick (one-tick
    # slack for a re-arm landing on a window boundary)
    assert n * (meas - 1) <= got["awake_nodes"] <= n * meas
    # eight rounds of two lanes a tick (seven where a node sat one out),
    # at most one lane of a tick's last round empty
    assert (got["awake_nodes"] <= got["lanes_stepped"]
            <= min(n * meas, got["awake_nodes"] + meas))


def test_idle_tick_runs_no_round():
    """With nothing due anywhere the tick runs zero rounds and leaves
    every leaf but the clock, the tick count, the rng and the two
    counters each of the inbox selection and of the closing phase as it
    was."""
    sim = _sim("kademlia", tick_impl="sparse", churn="none")
    s0 = sim.init(seed=11)
    s0 = dataclasses.replace(s0, churn=dataclasses.replace(
        s0.churn, t_create=jnp.full_like(s0.churn.t_create,
                                         churn_mod.T_INF)))
    before = jax.device_get(s0)
    n = sim.n
    inbox = jnp.full((n, sim.ep.inbox_slots), -1, jnp.int32)
    order, rounds, active = sim._phase_active_compact(
        s0, jnp.int64(0), s0.alive, jnp.zeros((n,), bool), s0.logic, inbox)
    assert (np.asarray(order) >= n).all()              # pure sentinels
    assert int(rounds) == 0 and [int(x) for x in active] == [0, 0, 0]
    after = jax.device_get(jax.jit(sim.step)(s0))
    assert int(after.tick) == 1 and int(after.t_now) > int(before.t_now)
    assert not np.array_equal(jax.random.key_data(after.rng),
                              jax.random.key_data(before.rng))
    # ... and the inbox selection's account of the tick: no message was
    # due, so its rounds swept the D empty lanes, of the pool's P slots
    p = sim.ep.pool_factor * n
    assert int(after.counters["inbox_lanes"]) == sim.inbox_lanes < p
    assert int(after.counters["inbox_pool_slots"]) == p
    # ... and the closing phase's: no outbox slot was wanted, so it ran
    # over its K empty lanes, of the Q outbox slots
    q = sim.ep.outbox_slots * n
    assert int(after.counters["send_lanes"]) == sim.send_lanes < q
    assert int(after.counters["send_outbox_slots"]) == q
    same = dict(t_now=before.t_now, tick=before.tick, rng=before.rng,
                counters=before.counters)
    _assert_tree_equal(dataclasses.replace(after, **same), before)
    assert all(int(after.counters[k]) == int(v)
               for k, v in before.counters.items()
               if k not in INBOX_COUNTERS + SEND_COUNTERS)
