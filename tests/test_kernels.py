"""Kernel-plane tests (oversim_tpu/kernels/; ISSUE 14).

Everything here runs the fused Pallas kernels under
``pallas_call(interpret=True)`` on CPU — the pins are bit-identity
against the lax scatter path (with the legacy sort path as a second
oracle) and the compiled-graph op-count reduction, so the kernels are
gated without TPU hardware.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu import kernels
from oversim_tpu.engine import pool as pool_mod
from oversim_tpu.engine.sim import EngineParams, Simulation

I32 = jnp.int32
I64 = jnp.int64

pytestmark = pytest.mark.skipif(not kernels.available(),
                                reason="pallas unavailable")


def _random_pool(rng, p, n, occupancy):
    """A random MsgPool at the given valid-slot occupancy, with coarse
    t_deliver (tie pressure) and distinct blk payloads per slot."""
    base = pool_mod.empty(p, key_lanes=5, rmax=4)
    valid = rng.random(p) < occupancy
    t = rng.integers(0, 6, size=p).astype(np.int64)
    dst = rng.integers(0, n, size=p).astype(np.int32)
    blk = base.blk.at[:, pool_mod._COL["dst"]].set(jnp.asarray(dst))
    blk = blk.at[:, pool_mod._COL["nonce"]].set(
        jnp.arange(p, dtype=I32))   # payload identity per slot
    return dataclasses.replace(
        base,
        valid=jnp.asarray(valid),
        t_deliver=jnp.where(jnp.asarray(valid), jnp.asarray(t),
                            pool_mod.T_INF),
        blk=blk)


def _assert_three_way(pool, n, r, t_end, alive, hold=None):
    """sort == scatter == fused on one snapshot, gather included."""
    a = pool_mod.build_inbox_sort(pool, n, r, t_end, alive, hold)
    b = pool_mod.build_inbox_scatter(pool, n, r, t_end, alive, hold)
    inbox, delivered, to_dead, gblk = kernels.inbox.fused_inbox(
        pool, n, r, t_end, alive, hold=hold, interpret=True)
    c = (inbox, delivered, to_dead)
    for x, y, name in zip(a, b, ("inbox", "delivered", "dropped_dead")):
        assert (np.asarray(x) == np.asarray(y)).all(), ("sort/scatter",
                                                        name)
    for x, y, name in zip(b, c, ("inbox", "delivered", "dropped_dead")):
        assert (np.asarray(x) == np.asarray(y)).all(), ("scatter/fused",
                                                        name)
    # the fused gather must equal gathering the oracle's inbox rows
    want = np.asarray(pool.blk)[np.maximum(np.asarray(inbox), 0)]
    assert (np.asarray(gblk) == want).all()


def test_fused_inbox_identity_randomized_pool():
    """pallas(interpret) vs scatter vs sort, bit-identical across pool
    occupancies — ties, dead destinations, R-overflow all included."""
    rng = np.random.default_rng(7)
    n, p, r = 7, 40, 3
    occupancies = [0.0, 0.15, 0.5, 0.85, 1.0]
    for trial in range(30):
        occ = occupancies[trial % len(occupancies)]
        pool = _random_pool(rng, p, n, occ)
        alive = jnp.asarray(rng.random(n) < 0.8)
        t_end = jnp.int64(int(rng.integers(1, 8)))
        _assert_three_way(pool, n, r, t_end, alive)


def test_fused_inbox_empty_and_full_pool():
    """The occupancy extremes, deterministically: a fully-empty pool
    delivers nothing; a fully-valid all-due pool exercises every
    R-overflow eviction path."""
    rng = np.random.default_rng(11)
    n, p, r = 4, 24, 3
    empty = _random_pool(rng, p, n, 0.0)
    alive = jnp.ones((n,), bool)
    inbox, delivered, to_dead, _ = kernels.inbox.fused_inbox(
        empty, n, r, jnp.int64(10), alive, interpret=True)
    assert (np.asarray(inbox) == -1).all()
    assert not np.asarray(delivered).any()
    assert not np.asarray(to_dead).any()
    full = _random_pool(rng, p, n, 1.1)   # occupancy > 1 → all valid
    assert bool(jnp.all(full.valid))
    _assert_three_way(full, n, r, jnp.int64(10), alive)
    # every destination row saturates at R and overflow stays pooled
    _, delivered, _, _ = kernels.inbox.fused_inbox(
        full, n, r, jnp.int64(10), alive, interpret=True)
    assert int(np.asarray(delivered).sum()) == n * r


def test_fused_inbox_overflow_keeps_earliest_r():
    """R-overflow retention on the fused path: exactly the R earliest
    (t_deliver, idx) messages deliver; the rest stay valid and deliver
    next tick (mirrors the scatter/sort pin in test_engine.py)."""
    p = pool_mod.empty(16, key_lanes=5, rmax=4)
    q = 6
    out = {
        "t_deliver": jnp.asarray([5, 3, 3, 7, 4, 6], I64),
        "src": jnp.arange(q, dtype=I32),
        "dst": jnp.zeros((q,), I32),
        "kind": jnp.full((q,), 7, I32),
        "key": jnp.zeros((q, 5), jnp.uint32),
        "nonce": jnp.arange(q, dtype=I32),
        "hops": jnp.zeros((q,), I32),
        "a": jnp.zeros((q,), I32), "b": jnp.zeros((q,), I32),
        "c": jnp.zeros((q,), I32), "d": jnp.zeros((q,), I32),
        "nodes": jnp.full((q, 4), -1, I32),
        "size_b": jnp.zeros((q,), I32),
        "stamp": jnp.zeros((q,), I64),
    }
    p, _ = pool_mod.alloc(p, out, jnp.ones((q,), bool))
    alive = jnp.ones((2,), bool)
    inbox, delivered, _ = pool_mod.build_inbox(
        p, n=2, r=2, t_end=jnp.int64(10), alive=alive, impl="pallas")
    assert list(np.asarray(inbox[0])) == [1, 2]   # t=3 ties → lower idx
    assert int(jnp.sum(delivered)) == 2
    p2 = pool_mod.free(p, delivered)
    assert int(jnp.sum(p2.valid)) == 4
    inbox2, delivered2, _ = pool_mod.build_inbox(
        p2, n=2, r=2, t_end=jnp.int64(10), alive=alive, impl="pallas")
    assert list(np.asarray(inbox2[0])) == [4, 0]  # t=4 then t=5


def test_fused_inbox_hold_mask():
    """ext_hold_slot semantics ride through the fused path: held
    messages are never due, never delivered, never dropped-dead."""
    rng = np.random.default_rng(13)
    n, p, r = 5, 32, 3
    pool = _random_pool(rng, p, n, 0.7)
    alive = jnp.asarray(rng.random(n) < 0.8)
    hold = jnp.asarray(rng.random(p) < 0.3)
    _assert_three_way(pool, n, r, jnp.int64(6), alive, hold=hold)
    inbox, delivered, to_dead, _ = kernels.inbox.fused_inbox(
        pool, n, r, jnp.int64(6), alive, hold=hold, interpret=True)
    held = np.asarray(hold)
    assert not np.asarray(delivered)[held].any()
    assert not np.asarray(to_dead)[held].any()
    assert not np.isin(np.asarray(inbox), np.nonzero(held)[0]).any()


def test_alloc_dest_identity_randomized():
    """The fused outbox allocator assigns the SAME slots as the
    cumsum/scatter path: k-th wanted message → k-th free slot, overflow
    counted identically, sentinel p for dropped/unwanted."""
    rng = np.random.default_rng(17)
    p = 24
    for trial in range(20):
        valid = jnp.asarray(rng.random(p) < rng.random())
        q = int(rng.integers(1, 2 * p))
        want = jnp.asarray(rng.random(q) < 0.6)
        dest, over = kernels.outbox.alloc_dest(valid, want,
                                               interpret=True)
        # oracle: the cumsum/fslot path from pool_mod.alloc
        free = np.nonzero(~np.asarray(valid))[0]
        w = np.asarray(want)
        rank = np.cumsum(w) - 1
        exp = np.full((q,), p, np.int32)
        for j in range(q):
            if w[j] and rank[j] < len(free):
                exp[j] = free[rank[j]]
        assert (np.asarray(dest) == exp).all(), trial
        assert int(over) == max(int(w.sum()) - len(free), 0), trial


def _churn_sim(overlay, inbox_impl, tick_impl="auto"):
    if overlay == "chord":
        from oversim_tpu.overlay.chord import ChordLogic
        logic = ChordLogic()
    else:
        from oversim_tpu.overlay.kademlia import KademliaLogic
        logic = KademliaLogic()
    cp = churn_mod.ChurnParams(model="lifetime", target_num=12,
                               init_interval=0.2, lifetime_mean=8.0)
    ep = EngineParams(window=0.1, inbox_slots=4, pool_factor=4,
                      inbox_impl=inbox_impl, tick_impl=tick_impl)
    return Simulation(logic, cp, engine_params=ep)


def _fused_identity_run(overlay, n_ticks=64, seed=3):
    """64 churned ticks, full-step: every SimState leaf after the run
    must be bit-identical between the fused and scatter engines, but
    for ``inbox_lanes``, each selection's own count of what it swept
    (P every tick for the kernel, D or P for the scatter rounds)."""
    finals = {}
    for impl in ("scatter", "pallas"):
        sim = _churn_sim(overlay, impl)
        s = sim.init(seed=seed)
        finals[impl] = jax.device_get(sim.run_chunk(s, n_ticks))
    p = sim.ep.pool_factor * sim.n
    swept = {impl: int(st.counters.pop("inbox_lanes"))
             for impl, st in finals.items()}
    assert swept["scatter"] < swept["pallas"] == p * n_ticks \
        == int(finals["pallas"].counters["inbox_pool_slots"])
    la, ta = jax.tree_util.tree_flatten(finals["scatter"])
    lb, tb = jax.tree_util.tree_flatten(finals["pallas"])
    assert ta == tb
    paths = jax.tree_util.tree_flatten_with_path(finals["scatter"])[0]
    for (path, _), x, y in zip(paths, la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            jax.tree_util.keystr(path)
    assert int(np.sum(finals["scatter"].alive)) > 0
    assert int(finals["scatter"].tick) == n_ticks
    # the run carried traffic (messages still in flight at the end)
    assert int(np.sum(finals["scatter"].pool.valid)) > 0


def test_fused_tick_identity_chord_under_churn():
    _fused_identity_run("chord")


def test_fused_tick_identity_kademlia_under_churn():
    _fused_identity_run("kademlia")


def test_fused_tick_hlo_scatter_reduction():
    """The compiled fused tick must carry EXACTLY 4R+2 fewer scatter
    ops than the scatter tick (R scatter-min key rounds + R index
    rounds in EACH branch of the default selection — over the due
    messages' D compacted lanes, and P-wide behind its ``lax.cond`` —
    the D-lane branch's ``delivered`` write-back and the outbox fslot
    scatter all fold into the kernels), zero full-pool sorts, no sort
    the scatter tick lacks, and — in interpret mode — zero
    custom-calls.  The
    pin is the dense oracle's (on the awake-set plane, Chord's default,
    the kernel plane also folds the awake-set compaction's scatter)."""
    from oversim_tpu.analysis import hlo_text

    census = {}
    for impl in ("scatter", "pallas"):
        sim = _churn_sim("chord", impl, tick_impl="dense")
        s = sim.init(seed=3)
        txt = jax.jit(sim.step).lower(s).compile().as_text()
        m = hlo_text.hlo_op_counts(txt, sim.ep.pool_factor * sim.n)
        m["custom_calls"] = hlo_text.custom_call_census(txt)
        census[impl] = m
        r = sim.ep.inbox_slots
    drop = census["scatter"]["scatter_count"] \
        - census["pallas"]["scatter_count"]
    assert drop == 4 * r + 2, census
    assert census["pallas"]["full_pool_sort_count"] == 0
    assert census["pallas"]["custom_calls"] == {}
    assert census["pallas"]["sort_count"] \
        == census["scatter"]["sort_count"]


def test_ext_hold_slot_identity_fused():
    """A sim with ext_hold_slot armed behaves identically on the fused
    path (the gateway hold mask flows through kernels.inbox)."""
    from oversim_tpu.overlay.chord import ChordLogic
    finals = {}
    for impl in ("scatter", "pallas"):
        cp = churn_mod.ChurnParams(model="none", target_num=8,
                                   init_interval=0.2)
        ep = EngineParams(window=0.1, inbox_slots=4, pool_factor=4,
                          inbox_impl=impl, ext_hold_slot=0)
        sim = Simulation(ChordLogic(), cp, engine_params=ep)
        s = sim.init(seed=5)
        finals[impl] = jax.device_get(sim.run_chunk(s, 32))
    la, _ = jax.tree_util.tree_flatten(finals["scatter"])
    lb, _ = jax.tree_util.tree_flatten(finals["pallas"])
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y))
