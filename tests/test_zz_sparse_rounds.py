"""The awake-set tick's rounds (engine/sim.py _phase_sparse_step; ISSUE 27):
awake nodes past one round's A lanes are stepped in further rounds of
the same tick, never deferred, so the plane is the dense oracle's bit
for bit at any active_cap.

Pinned here: the benchmark cell's own deployment at N=128 through fill,
settling and 100 ticks of window at A=4 (most ticks take several
rounds), ``lanes_stepped`` against the recorded awake counts, every
node awake (N/A rounds a tick), and an idle tick (no round).  The
helpers are test_zz_sparse.py's; a module of its own because a module
is one unit of work on one xdist worker (tests/conftest.py).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.engine.sim import SPARSE_COUNTERS

from test_zz_sparse import (
    CELL_N, _assert_tree_equal, _cell_sim, _sim, _strip_sparse)


CELL_CAP = 4          # A: most ticks of the run below take 2+ rounds


@pytest.fixture(scope="module")
def cell_run():
    """The cell's deployment at N=128 through its fill (20 s), its
    settling (20 s) and 100 ticks of window, tick by tick, under
    ``tick_impl="dense"`` and under the awake-set plane at A=4; the
    plane's counters after every tick are the recorded run."""
    sparse, config = _cell_sim(active_cap=CELL_CAP)
    dense, _ = _cell_sim(tick_impl="dense")
    assert sparse.tick_impl == "sparse" and sparse.acap == CELL_CAP
    assert dense.tick_impl == "dense"
    ticks = int(round((config["fill_s"] + config["settle_s"])
                      / config["engine"]["window"])) + 100
    sd, ss = dense.init(seed=5), sparse.init(seed=5)
    rec = []
    for _ in range(ticks):
        ss = sparse.run_chunk(ss, 1)
        rec.append(jax.device_get(ss.counters))   # ss is donated next
    sd = dense.run_chunk(sd, ticks)
    return dict(dense=jax.device_get(sd), sparse=jax.device_get(ss),
                rec=rec, ticks=ticks, n=sparse.n)


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
        s = sim.run_chunk(sim.init(seed=3), warm)
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
    every leaf but the clock, the tick count and the rng as it was."""
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
    same = dict(t_now=before.t_now, tick=before.tick, rng=before.rng)
    _assert_tree_equal(dataclasses.replace(after, **same), before)
