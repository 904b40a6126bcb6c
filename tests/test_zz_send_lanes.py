"""The closing phase over the tick's wanted outbox slots (ISSUE 38;
engine/sim.py _phase_alloc_stats, underlay/simple.py send_tx / send_rx,
engine/pool.py alloc, core/lanes.py).

What is indexed by a message's receiver or by its pool slot runs over K
static lanes that hold the tick's wanted outbox slots, and over all
Q = N x outbox_slots only in a tick that wants more than K.  Pinned
here: one deployment under LifetimeChurn with slot recycling (its fill,
then its steady churn), tick by tick, at K = 1, at a K that its fill
overruns and its steady ticks mostly fit, and at K = Q (the Q-wide form
alone), every leaf of the state the same and the engine's two counters
equal to the wanted messages counted on the side; the receiver's stage
over lanes against all Q on random outboxes with every option of the
underlay on; the traced closing phase itself (no gather and no scatter
of Q lanes in its steady branch; no ``cond`` at K = Q, which is what
``for_vmap`` hands the campaign runner); the helpers alone.
"""

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.core import lanes as lanes_mod
from oversim_tpu.engine import pool as pool_mod
from oversim_tpu.engine.sim import (
    PLANE_COUNTERS, SEND_COUNTERS, EngineParams, Simulation)
from oversim_tpu.overlay.kademlia import KademliaLogic
from oversim_tpu.underlay import simple as ul

from test_engine import _eqns, _lanes_of
from test_zz_sparse import _assert_tree_equal, _cell_sim

I32, I64 = jnp.int32, jnp.int64
TARGET, TICKS, FILL_TICKS = 32, 400, 40
K_MID = 12


class Counting(Simulation):
    """The engine's own tick, with the tick's wanted outbox slots
    counted on the side (``counters["wanted"]``, which the test adds to
    the initial state: the engine carries a key it does not know)."""

    def _phase_alloc_stats(self, *a, **kw):
        out = super()._phase_alloc_stats(*a, **kw)
        valid = inspect.signature(super()._phase_alloc_stats).bind(
            *a, **kw).arguments["out_valid"]
        return dataclasses.replace(out, counters=dict(
            out.counters,
            wanted=out.counters["wanted"] + jnp.sum(valid).astype(I64)))


def _churn_sim(k):
    logic = KademliaLogic(app=KbrTestApp(KbrTestParams(test_interval=10.0)))
    cp = churn_mod.ChurnParams(
        model="lifetime", target_num=TARGET, init_interval=8.0 / TARGET,
        init_deviation=8.0 / TARGET / 3.0, lifetime_mean=40.0,
        graceful_leave_delay=5.0)
    ep = EngineParams(window=0.2, inbox_slots=2, pool_factor=4)
    return Counting(logic, cp, engine_params=ep, send_lanes=k)


Q = 2 * TARGET * EngineParams().outbox_slots


@pytest.fixture(scope="module")
def churned():
    """{K: (final state, the counters after every tick)} over 400 ticks
    of 0.2 s: the fill's 40, then steady churn at lifetimes of 40 s."""
    out = {}
    for k in (1, K_MID, Q):
        sim = _churn_sim(k)
        assert sim.n * sim.ep.outbox_slots == Q and sim.send_lanes == k
        assert sim.tick_impl == "sparse"

        @jax.jit
        def run(s, sim=sim):
            def body(c, _):
                c = sim.step(c)
                return c, c.counters
            return jax.lax.scan(body, s, None, length=TICKS)

        s = sim.init(seed=5)
        s = dataclasses.replace(
            s, counters=dict(s.counters, wanted=jnp.zeros((), I64)))
        out[k] = jax.device_get(run(s))
    return out


def _per_tick(rec, name):
    return np.diff(np.concatenate([[0], np.asarray(rec[name])]))


@pytest.mark.parametrize("k", [1, K_MID, Q])
def test_k_lanes_equal_the_q_wide_closing_phase_on_every_leaf(churned, k):
    """Fill, steady stretch and slot recycling under LifetimeChurn:
    every leaf of the state (floats and deliver times included) is the
    Q-wide form's, whichever branch each tick took; the run crosses
    both branches and switches between them many times."""
    final, rec = churned[k]
    wide, wide_rec = churned[Q]
    strip = lambda st: dataclasses.replace(  # noqa: E731
        st, counters={c: v for c, v in st.counters.items()
                      if c != "send_lanes"})
    _assert_tree_equal(strip(wide), strip(final))
    assert int(final.tick) == TICKS
    # a churned run that recycles slots: more births than there are
    # slots to be born in, deaths, messages lost to dead receivers only
    c = {name: int(v) for name, v in final.counters.items()}
    assert c["churn_created"] > 2 * TARGET
    assert c["churn_killed"] > TARGET // 2
    assert c["pool_overflow"] == c["outbox_overflow"] == 0
    assert c["queue_lost"] == 0
    assert c["dest_unavailable_lost"] > 0
    # the engine's two counters against the wanted messages of each tick
    wanted, lanes = _per_tick(rec, "wanted"), _per_tick(rec, "send_lanes")
    assert (_per_tick(rec, "send_outbox_slots") == Q).all()
    assert (lanes == np.where(wanted <= k, k, Q)).all()
    assert (wanted == _per_tick(wide_rec, "wanted")).all()
    assert wanted.max() > K_MID > wanted.min() == 0
    if k == Q:
        assert (lanes == Q).all()
        return
    fit = wanted <= k
    assert fit.any() and (~fit).any()                # both branches ran
    assert np.count_nonzero(fit[1:] != fit[:-1]) >= 2    # and switched
    if k == K_MID:
        assert (~fit[:FILL_TICKS]).mean() > 0.5      # the fill overruns
        assert fit[FILL_TICKS:].mean() > 0.75        # steady ticks fit
        assert np.count_nonzero(fit[1:] != fit[:-1]) > 4


def test_counter_layout_and_the_k_rule():
    """K is a rule of Q alone, Q/32 and at least 32; a constructor
    argument, never an EngineParams field; the two counters ride with
    the plane's, the dense layout stays what it was."""
    assert [pool_mod.send_lanes(q) for q in (16, 1024, 16000, 65536,
                                             131072, 262144)] == [
        16, 32, 500, 2048, 4096, 8192]
    assert "send_lanes" not in {f.name for f in
                                dataclasses.fields(EngineParams)}
    assert set(SEND_COUNTERS) <= set(PLANE_COUNTERS)
    cell, _ = _cell_sim()
    q = cell.n * cell.ep.outbox_slots
    assert cell.send_lanes == pool_mod.send_lanes(q) == q // 32
    assert set(SEND_COUNTERS) <= set(cell.counter_names)
    dense, _ = _cell_sim(tick_impl="dense")
    assert not set(SEND_COUNTERS) & set(dense.counter_names)
    assert dense.send_lanes == cell.send_lanes      # both planes close alike
    # an underlay without the two stages keeps the Q-wide form
    from oversim_tpu.underlay import inet
    assert not hasattr(inet, "send_rx")
    assert Simulation(cell.logic, cell.cp, None, cell.ep,
                      inet).send_lanes == q


# -- the receiver's stage alone ----------------------------------------------

def _random_outbox(seed, n=48, m=6, p_want=0.15):
    p = ul.UnderlayParams(
        jitter=0.1, channel_types=("simple_ethernetline_lossy",
                                   "simple_dsl_lossy", "simple_dsl"),
        tcp_kinds=(7,), tcp_connection_cache=4, num_node_types=2,
        type_boundaries=(n // 2,),
        partition_events=((1.0, 0, 1, False), (1.0, 1, 0, False)))
    r = jax.random.split(jax.random.PRNGKey(seed), 9)
    st = ul.init(r[0], n, p)
    st = dataclasses.replace(
        st, tx_finished=jax.random.randint(r[1], (n,), 0, 6 * ul.NS, I64),
        tcp_conn=jax.random.randint(r[2], (n, 4), -1, n, I32))
    src = jnp.broadcast_to(jnp.arange(n, dtype=I32)[:, None], (n, m))
    dst = jax.random.randint(r[3], (n, m), 0, n, I32)
    dst = jnp.where(jax.random.uniform(r[4], (n, m)) < 0.1, src, dst)
    size = jax.random.randint(r[5], (n, m), 40, 60000, I32)
    t_send = 2 * ul.NS + jax.random.randint(r[6], (n, m), 0, ul.NS // 5, I64)
    want = jax.random.uniform(r[7], (n, m)) < p_want
    kind = jnp.where(jax.random.uniform(r[8], (n, m)) < 0.5, 7, 3)
    alive = jnp.arange(n) % 5 != 0
    return p, st, r[0], (src, dst, size, t_send, want), kind, alive


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_receiver_stage_over_lanes_equals_all_q(seed):
    """``send_rx`` over the wanted slots' lanes against ``send_batch``
    over all Q, with jitter, bit errors, a partition cut and TCP kinds
    on: the same deliver time to the nanosecond and the same verdict for
    every wanted message (its random draws are its slot's), the same
    drop counts, the same connection cache and queues."""
    p, st, rng, box, kind, alive = _random_outbox(seed)
    want = box[-1]
    n, m = want.shape
    q = n * m
    t_wide, ok_wide, st_wide, drops_wide = ul.send_batch(
        st, p, rng, *box, alive, kind=kind)
    assert min(int(v) for v in drops_wide.values()) > 0     # every kind
    assert int(jnp.sum(ok_wide)) > 0

    @jax.jit
    def over_lanes(st):
        tx, st2 = ul.send_tx(st, p, rng, *box, kind)
        tx = {f: v.reshape((q,) + v.shape[2:]) for f, v in tx.items()}
        lane = lanes_mod.compact(want.reshape(-1), 64)
        tx = lanes_mod.take(tx, lane)
        tx = dict(tx, want=tx["want"] & (lane < q))
        return lane, ul.send_rx(st2, p, tx, alive)

    lane, (t_l, ok_l, st_l, drops_l) = over_lanes(st)
    lane = np.asarray(lane)
    k = int(np.sum(want))
    assert 0 < k < 64 and (lane[k:] == q).all()
    assert (lane[:k] == np.nonzero(np.asarray(want).reshape(-1))[0]).all()
    assert (np.asarray(t_l)[:k] == np.asarray(t_wide).reshape(-1)[lane[:k]]
            ).all()
    assert (np.asarray(ok_l)[:k] == np.asarray(ok_wide).reshape(-1)[lane[:k]]
            ).all()
    assert not np.asarray(ok_l)[k:].any()
    assert {f: int(v) for f, v in drops_l.items()} == {
        f: int(v) for f, v in drops_wide.items()}
    _assert_tree_equal(st_wide, st_l)
    assert not np.array_equal(np.asarray(st.tcp_conn),
                              np.asarray(st_l.tcp_conn))


# -- the traced closing phase --------------------------------------------------

def _closing_phase(sim):
    """The jaxpr of ``_phase_alloc_stats`` as ``sim.step`` calls it."""
    got = {}
    real = sim._phase_alloc_stats

    def spy(*a, **kw):
        got["jaxpr"] = jax.make_jaxpr(lambda a, kw: real(*a, **kw))(a, kw)
        return real(*a, **kw)

    sim._phase_alloc_stats = spy
    try:
        jax.eval_shape(sim.step, jax.eval_shape(
            lambda: sim.init_from_rng(jax.random.PRNGKey(1))))
    finally:
        del sim._phase_alloc_stats
    return got["jaxpr"].jaxpr


@pytest.mark.parametrize("tick_impl", ["auto", "dense"])
def test_steady_branch_holds_no_gather_or_scatter_of_q_lanes(tick_impl):
    """On the chip a gather costs 7.6 ns a LANE, whether the lane holds
    a message or not, and six gathers over the Q = 16 N outbox slots
    were a fifth of the cells' tick (PERF.md, PR 38).  So in the traced
    closing phase of the cells' own deployment, outside the Q-wide
    branch of its one ``cond``, no gather has more than K index rows
    and no scatter Q updates or a 64-bit operand; the Q-wide branch is
    the same two calls over all Q."""
    sim, _ = _cell_sim(tick_impl=tick_impl)
    q, k = sim.n * sim.ep.outbox_slots, sim.send_lanes
    n, p = sim.n, sim.n * sim.ep.pool_factor
    assert k == q // 32 < n < p < q
    jaxpr = _closing_phase(sim)
    conds = [e for e in _eqns(jaxpr) if e.primitive.name == "cond"]
    assert len(conds) == 1
    wide, steady = (br.jaxpr for br in conds[0].params["branches"])
    for e in _eqns(jaxpr, into_cond=False):
        lanes = _lanes_of(e)
        assert lanes is None or lanes < q, (e.primitive.name, lanes)
    seen = {"gather": [], "scatter": []}
    for e in _eqns(steady):
        lanes = _lanes_of(e)
        if lanes is None:
            continue
        seen[e.primitive.name[:7]].append(lanes)
        assert e.invars[0].aval.dtype.itemsize < 8, e
    # the lanes' one row gather, the receivers', fslot[want_rank] (and
    # the compaction's binary search): K lanes each, and every node's
    # channel row, N; the free slots' ranking scatters P updates, the
    # pool's row scatter K
    assert seen["gather"].count(k) >= 3 and seen["gather"].count(n) == 1
    assert set(seen["gather"]) == {k, n}
    assert sorted(seen["scatter"]) == [k, p]
    wide_lanes = [n for n in map(_lanes_of, _eqns(wide)) if n is not None]
    assert max(wide_lanes) == q and wide_lanes.count(q) >= 3


def test_for_vmap_program_holds_no_cond():
    """Under vmap a ``cond`` runs both branches, so what the campaign
    runner takes (``for_vmap``) is K = Q and D = P: the closing phase is
    the Q-wide form alone, and the whole tick holds neither ``cond``."""
    cell, _ = _cell_sim()
    twin = cell.for_vmap()
    q = cell.n * cell.ep.outbox_slots
    assert cell.send_lanes == q // 32 and twin.send_lanes == q
    assert twin.inbox_lanes == cell.n * cell.ep.pool_factor
    assert twin.tick_impl == "dense" and twin.for_vmap() is twin
    assert not set(SEND_COUNTERS) & set(twin.counter_names)
    assert not [e for e in _eqns(_closing_phase(twin))
                if e.primitive.name == "cond"]

    def lane_conds(sim):
        shapes = jax.eval_shape(
            lambda: sim.init_from_rng(jax.random.PRNGKey(1)))
        return sum(e.primitive.name == "cond"
                   for e in _eqns(jax.make_jaxpr(sim.step)(shapes).jaxpr))

    dense, _ = _cell_sim(tick_impl="dense")
    assert lane_conds(dense) - lane_conds(twin) == 2


# -- the helpers alone -----------------------------------------------------------

def test_compact_and_fits():
    mask = jnp.asarray([0, 1, 0, 0, 1, 1, 0, 1], bool)
    assert np.asarray(lanes_mod.compact(mask, 6)).tolist() == [
        1, 4, 5, 7, 8, 8]
    assert np.asarray(lanes_mod.compact(mask, 2)).tolist() == [1, 4]
    assert np.asarray(lanes_mod.compact(jnp.zeros((8,), bool), 3)
                      ).tolist() == [8, 8, 8]
    assert bool(lanes_mod.fits(mask, 4)) and not bool(lanes_mod.fits(mask, 3))


def test_take_is_one_row_gather_with_every_leafs_bits():
    """``take`` against leaf-by-leaf indexing: bools, 32- and 64-bit
    words, rows of any width; an index of -1 wraps and one past the end
    reads the last row, as ``leaf[idx]`` does; ``None`` is the tree
    itself; the traced form holds ONE gather."""
    r = jax.random.split(jax.random.PRNGKey(4), 5)
    tree = {"flag": jax.random.uniform(r[0], (10,)) < 0.5,
            "t": jax.random.randint(r[1], (10,), -2**62, 2**62, I64),
            "x": jax.random.normal(r[2], (10, 3), jnp.float32),
            "key": jax.random.bits(r[3], (10, 5), jnp.uint32),
            "pair": (jax.random.randint(r[4], (10, 2, 2), -9, 9, I32),)}
    idx = jnp.asarray([3, 3, 0, 9, 10, -1, 7], I32)
    got = lanes_mod.take(tree, idx)
    want = jax.tree_util.tree_map(lambda x: x[idx], tree)
    _assert_tree_equal(want, got)
    assert jax.tree_util.tree_map(lambda x: x.dtype, got) == \
        jax.tree_util.tree_map(lambda x: x.dtype, tree)
    assert lanes_mod.take(tree, None) is tree
    eqns = list(_eqns(jax.make_jaxpr(lanes_mod.take)(tree, idx).jaxpr))
    assert sum(e.primitive.name == "gather" for e in eqns) == 1
    with pytest.raises(TypeError):
        lanes_mod.take({"h": jnp.zeros((4,), jnp.float16)}, idx)
