"""The tick under a churn law that recycles slots (ISSUE 37).

One recorded run serves the module: Kademlia under KBRTestApp and
LifetimeChurn at target 128 (256 context slots), lifetimes of 60 s mean,
480 ticks of 0.2 s (over a hundred deaths and as many rebirths, each
under a fresh key), tick by tick, on the dense sweep and on the awake-set
plane.  Pinned on it: the two planes end on the same state, every leaf
(further cases of test_zz_sparse.py's identity test, whose helpers these
are; a module of its own because a module is one unit of work on one
xdist worker); the engine's churn counters against the ``alive`` flips
and leave notices counted on the host tick by tick; and what the
routing tables hold of a slot that was reborn.  The Kademlia rule behind
the last is pinned alone too, on a hand-made table: a slot reborn under
another key is not refreshed in, and leaves, the bucket its old key
earned.
"""

import dataclasses
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
from oversim_tpu.overlay.kademlia import NO_NODE, KademliaLogic

from test_zz_sparse import _assert_tree_equal, _strip_sparse

TARGET, TICKS = 128, 480
T_INF = int(churn_mod.T_INF)


def _churn_sim(tick_impl):
    logic = KademliaLogic(app=KbrTestApp(KbrTestParams(test_interval=20.0)))
    cp = churn_mod.ChurnParams(
        model="lifetime", target_num=TARGET, init_interval=16.0 / TARGET,
        init_deviation=16.0 / TARGET / 3.0, lifetime_mean=60.0,
        graceful_leave_delay=5.0)
    ep = EngineParams(window=0.2, inbox_slots=4, pool_factor=4,
                      tick_impl=tick_impl)
    return Simulation(logic, cp, engine_params=ep)


@pytest.fixture(scope="module")
def churned():
    """Both planes through the same 480 ticks; of each tick, who was
    alive and who was under a leave notice after it."""
    out = {}
    for tick_impl in ("dense", "sparse"):
        sim = _churn_sim(tick_impl)

        @jax.jit
        def run(s, sim=sim):
            def body(c, _):
                c = sim.step(c)
                return c, (c.alive, c.churn.t_dead < T_INF)
            return jax.lax.scan(body, s, None, length=TICKS)

        s0 = sim.init(seed=5)
        alive0 = np.asarray(s0.alive)
        final, (alive, noticed) = jax.device_get(run(s0))
        out[tick_impl] = SimpleNamespace(
            sim=sim, final=final,
            alive=np.concatenate([alive0[None], np.asarray(alive)]),
            noticed=np.concatenate([np.zeros_like(alive0)[None],
                                    np.asarray(noticed)]))
    return out


@pytest.mark.parametrize("leaves", ["state", "counters"])
def test_sparse_identity_kademlia_under_lifetime_churn(churned, leaves):
    """The awake-set plane against the dense sweep under slot
    recycling, every leaf; the dense layout carries none of the
    plane's counters."""
    dense, sparse = churned["dense"].final, churned["sparse"].final
    if leaves == "counters":
        assert set(dense.counters) == set(ENGINE_COUNTERS)
        assert set(sparse.counters) == set(ENGINE_COUNTERS + PLANE_COUNTERS)
        assert set(CHURN_COUNTERS) <= set(PLANE_COUNTERS)
        return
    _assert_tree_equal(dense, _strip_sparse(sparse))
    assert int(dense.tick) == TICKS
    # the run is a churned one: deaths and rebirths by the hundred
    alive = churned["dense"].alive
    assert int((alive[:-1] & ~alive[1:]).sum()) >= 100
    assert int((~alive[TICKS // 4:-1] & alive[TICKS // 4 + 1:]).sum()) >= 100


def test_churn_counters_equal_the_flips_counted_on_the_host(churned):
    run = churned["sparse"]
    alive, noticed = run.alive, run.noticed
    created = ~alive[:-1] & alive[1:]
    killed = alive[:-1] & ~alive[1:]
    leaving = ~noticed[:-1] & noticed[1:]
    c = {k: int(v) for k, v in run.final.counters.items()}
    assert c["churn_created"] == int(created.sum()) > TARGET
    assert c["churn_killed"] == int(killed.sum()) > 0
    assert c["churn_prekilled"] == int(leaving.sum()) >= c["churn_killed"]
    touched = (created | killed | leaving).any(axis=1)
    assert c["churn_ticks"] == int(touched.sum()) < TICKS
    # the churn phase is dense selects over all rows, every tick
    assert c["reset_rows"] == TICKS * run.sim.n
    # ... and the incarnation is the start of the creating tick
    t_born = np.asarray(run.final.churn.t_born)
    assert ((t_born >= 0) == alive.any(axis=0)).all()
    assert (t_born < int(run.final.t_now)).all()


def _ids(node_keys):
    return [int.from_bytes(b"".join(int(w).to_bytes(4, "big") for w in row),
                           "big") for row in np.asarray(node_keys)]


def test_tables_hold_no_reborn_slot_in_its_old_bucket(churned):
    """After 480 churned ticks: an entry stands in another bucket than
    its slot's CURRENT key earns only where the slot was born after
    anything its holder has seen (the holder has not stepped since, and
    puts it right when it next does); no bucket holds a slot twice."""
    s = churned["sparse"].final
    sim = churned["sparse"].sim
    ids, bits = _ids(s.node_keys), sim.spec.bits
    alive, t_born = np.asarray(s.alive), np.asarray(s.churn.t_born)
    buckets, b_seen = np.asarray(s.logic.buckets), np.asarray(s.logic.b_seen)
    nb = buckets.shape[1]
    held = wrong = excused = 0
    for i in np.nonzero(alive)[0]:
        heard = int(b_seen[i].max())
        for b in range(nb):
            row = [int(e) for e in buckets[i, b] if e != int(NO_NODE)]
            assert len(set(row)) == len(row)
            for e in row:
                held += 1
                want = min(bits - (ids[i] ^ ids[e]).bit_length(), nb - 1)
                if alive[e] and want != b:
                    excused += int(t_born[e]) > heard
                    wrong += int(t_born[e]) <= heard
    assert held > 1000 and wrong == 0, (held, wrong, excused)
    assert not (buckets[~alive] != int(NO_NODE)).any()
    # ... and a sibling row is nearest-first but for the slots reborn
    # since (alive again or dead again: their keys moved all the same)
    sib = np.asarray(s.logic.sib)
    rows = disorder = 0
    for i in np.nonzero(alive)[0]:
        heard = int(b_seen[i].max())
        row = [int(e) for e in sib[i] if e != int(NO_NODE)]
        assert len(set(row)) == len(row) and i not in row
        d = [ids[e] ^ ids[i] for e in row if int(t_born[e]) <= heard]
        rows += bool(d)
        disorder += sum(1 for x, y in zip(d, d[1:]) if not x < y)
    assert rows > TARGET // 2 and disorder == 0, (rows, disorder)


def test_a_reborn_slot_is_not_refreshed_in_and_leaves_its_old_bucket():
    """Holder 0 holds slot 5 in the bucket 5's key earned; slot 5 dies
    and is reborn under a key that earns another bucket, and calls.
    The old entry's last-seen time stays, the slot enters the bucket its
    new key earns, findNode never offers the old entry, and the sweep
    the step makes evicts it."""
    logic = KademliaLogic()
    spec, n, x = logic.key_spec, 16, 5
    keys = np.asarray(keys_mod.random_keys(jax.random.PRNGKey(7), (n,), spec))
    me = jnp.asarray(keys[0])
    index = lambda k: int(logic._bucket_index(me, jnp.asarray(k)))  # noqa: E731
    b_old = index(keys[x])
    fresh = keys[0].copy()               # the holder's key, but for the
    fresh[0] ^= np.uint32(1 << (30 - b_old))     # bit after 5's old prefix
    b_new = index(fresh)
    assert b_old < 30 and b_new == b_old + 1
    node = jax.tree_util.tree_map(
        lambda a: a[0], dataclasses.replace(
            logic.init(jax.random.PRNGKey(0), n), app_glob=None))
    node = dataclasses.replace(
        node, state=jnp.int32(2),
        buckets=node.buckets.at[b_old, 0].set(x),
        b_seen=node.b_seen.at[b_old, 0].set(5))
    reborn = keys.copy()
    reborn[x] = fresh
    ctx = SimpleNamespace(keys=jnp.asarray(reborn))

    @jax.jit
    def call(node):
        st, _ = logic._bucket_update_batch(
            ctx, node, me, jnp.asarray([x], jnp.int32),
            jnp.asarray([True]), jnp.int64(100))
        out, _, stale = logic._find_node_batch(
            ctx, st, me, jnp.int32(0), jnp.asarray(keys[x])[None], 16)
        return st, out, stale, logic._handle_failed(
            ctx, st, me, jnp.int32(0), NO_NODE, also=stale)

    st, out, stale, swept = jax.device_get(call(node))
    assert int(st.buckets[b_old, 0]) == x and int(st.b_seen[b_old, 0]) == 5
    assert x in st.buckets[b_new] and 100 in st.b_seen[b_new]
    assert stale[b_old, 0] and int(stale.sum()) == 1
    assert list(out[0]).count(x) == 1
    assert int(swept.buckets[b_old, 0]) == int(NO_NODE)
    assert x in swept.buckets[b_new]
    # a slot that keeps its key is refreshed where it stands, as ever
    same = SimpleNamespace(keys=jnp.asarray(keys))
    st2, _ = logic._bucket_update_batch(
        same, node, me, jnp.asarray([x], jnp.int32), jnp.asarray([True]),
        jnp.int64(100))
    assert int(st2.b_seen[b_old, 0]) == 100
    assert int((np.asarray(st2.buckets) == x).sum()) == 1
