"""Engine smoke tests: pool mechanics + a full ping/pong simulation.

The PingLogic below is the minimal per-node protocol: every node pings a
random ready node once a second; the receiver echoes.  It exercises every
engine subsystem — timers, horizon stepping, inbox grouping, outbox
allocation, underlay delays, stats — without any overlay logic on top.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu import stats as stats_mod
from oversim_tpu.core import keys as keys_mod
from oversim_tpu.engine import pool as pool_mod
from oversim_tpu.engine.logic import Msg, Outbox, T_INF
from oversim_tpu.engine.sim import EngineParams, Simulation
from oversim_tpu.underlay import simple as underlay_mod

from oracles import SortSimulation, build_inbox_sort

I32 = jnp.int32
I64 = jnp.int64
NS = 1_000_000_000

KIND_PING = 1
KIND_PONG = 2


# ---------------------------------------------------------------------------
# pool unit tests
# ---------------------------------------------------------------------------

def test_pool_alloc_inbox_free_roundtrip():
    p = pool_mod.empty(16, key_lanes=5, rmax=4)
    q = 6
    out = {
        "t_deliver": jnp.asarray([5, 3, 9, 7, 1, 100], I64),
        "src": jnp.asarray([0, 1, 2, 3, 4, 5], I32),
        "dst": jnp.asarray([2, 2, 2, 1, 1, 0], I32),
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
    want = jnp.asarray([True, True, True, True, True, True])
    p, overflow = pool_mod.alloc(p, out, want)
    assert int(overflow) == 0
    assert int(jnp.sum(p.valid)) == 6

    # window [0, 10): all but the t=100 message are due
    alive = jnp.ones((3,), bool)
    inbox, delivered, to_dead = pool_mod.build_inbox(
        p, n=3, r=2, t_end=jnp.int64(10), alive=alive)
    assert int(jnp.sum(delivered)) == 4  # node2 gets 2 of its 3 (R=2), node1 two
    assert int(jnp.sum(to_dead)) == 0
    # node 2's two slots must be its earliest msgs (t=3 then t=5)
    row2 = np.asarray(inbox[2])
    ts = np.asarray(p.t_deliver)[row2]
    assert list(ts) == [3, 5]
    # node 0 has nothing due (its msg is t=100)
    assert np.asarray(inbox[0] == -1).all()

    p2 = pool_mod.free(p, delivered)
    assert int(jnp.sum(p2.valid)) == 2
    # next tick: node 2's deferred third message (t=9) arrives
    inbox2, delivered2, _ = pool_mod.build_inbox(
        p2, n=3, r=2, t_end=jnp.int64(10), alive=alive)
    ts2 = np.asarray(p2.t_deliver)[np.asarray(inbox2[2])]
    assert ts2[0] == 9


def test_inbox_identity_randomized_pool():
    """The inbox grouping must be BIT-IDENTICAL to the sort oracle
    (tests/oracles.py) on random pools — including t_deliver ties (index tie-break), dead
    destinations and R-overflow rows."""
    rng = np.random.default_rng(42)
    sort_j = jax.jit(build_inbox_sort, static_argnames=("n", "r"))
    scat_j = jax.jit(pool_mod.build_inbox, static_argnames=("n", "r"))
    # fixed shapes -> ONE compile per impl; randomness lives in the data
    n, p, r = 7, 40, 3
    base = pool_mod.empty(p, key_lanes=5, rmax=4)
    for trial in range(40):
        valid = rng.random(p) < 0.7
        t = rng.integers(0, 6, size=p).astype(np.int64)  # coarse → ties
        dst = rng.integers(0, n, size=p).astype(np.int32)
        pool = dataclasses.replace(
            base,
            valid=jnp.asarray(valid),
            t_deliver=jnp.where(jnp.asarray(valid), jnp.asarray(t),
                                pool_mod.T_INF),
            blk=base.blk.at[:, pool_mod._COL["dst"]].set(jnp.asarray(dst)))
        alive = jnp.asarray(rng.random(n) < 0.8)
        t_end = jnp.int64(int(rng.integers(1, 8)))
        a = sort_j(pool, n=n, r=r, t_end=t_end, alive=alive)
        b = scat_j(pool, n=n, r=r, t_end=t_end, alive=alive)
        for x, y, name in zip(a, b, ("inbox", "delivered", "dropped_dead")):
            assert (np.asarray(x) == np.asarray(y)).all(), (trial, name)


T_END = 1000       # the selection cases' window end (ns)


def _case_pool(seed, n, p, n_due, *, t_due=(0, T_END), later=0.3,
               dst=None, t_end=T_END, t_mul=1):
    """A [P] pool with exactly ``n_due`` slots valid and due before
    ``t_end`` at random places (deliver times drawn from ``t_due``,
    times ``t_mul``), and a share ``later`` of the others valid but due
    after it; destinations random in [0, n) unless given."""
    rng = np.random.default_rng(seed)
    due = np.zeros(p, bool)
    due[rng.choice(p, size=n_due, replace=False)] = True
    valid = due | (rng.random(p) < later)
    t = np.where(due, rng.integers(*t_due, size=p) * t_mul,
                 rng.integers(t_end, 2 * t_end, size=p)).astype(np.int64)
    if dst is None:
        dst = rng.integers(0, n, size=p)
    base = pool_mod.empty(p, key_lanes=5, rmax=4)
    return dataclasses.replace(
        base, valid=jnp.asarray(valid),
        t_deliver=jnp.where(jnp.asarray(valid), jnp.asarray(t),
                            pool_mod.T_INF),
        blk=base.blk.at[:, pool_mod._COL["dst"]].set(
            jnp.asarray(dst, np.int32)))


# name -> (n, p, r, n_due, lanes asked (None: the rule of P), what else)
N_A, P_A, D_A = 16, 128, 32            # inbox_lanes(128) == 32
SELECT_CASES = {
    "due_0": (N_A, P_A, 3, 0, None, {}),
    "due_1": (N_A, P_A, 3, 1, None, {}),
    "due_d_minus_1": (N_A, P_A, 3, D_A - 1, None, {}),
    "due_d": (N_A, P_A, 3, D_A, None, {}),
    "due_d_plus_1": (N_A, P_A, 3, D_A + 1, None, {}),
    "due_p": (N_A, P_A, 3, P_A, None, {}),
    # two deliver times only: nearly every pick is a tie on t_deliver,
    # broken by the pool index
    "ties": (N_A, P_A, 3, 30, None, {"t_due": (5, 7)}),
    # every due message for ONE destination: R go, the rest wait
    "over_r": (N_A, P_A, 3, 20, None, {"dst": np.full(P_A, 5)}),
    "dead_dst": (N_A, P_A, 3, 40, 64, {"dead": (2, 5, 11)}),
    "hold": (N_A, P_A, 3, 30, None, {"hold_every": 3}),
    # what tells the one sort of the D lanes from the rounds: ONE
    # deliver time for every message of one row (the pool index alone
    # orders them, and only R go) ...
    "ties_in_one_row": (N_A, P_A, 3, 20, None,
                        {"t_due": (5, 6), "dst": np.full(P_A, 5)}),
    # ... three rows that each hold more than R, nearly all ties ...
    "ties_rows_over_r": (N_A, P_A, 3, 30, None,
                         {"t_due": (5, 7), "dst": np.arange(P_A) % 3}),
    # ... exactly D due for one row, D + 1 (the rounds) likewise ...
    "due_d_one_row": (N_A, P_A, 3, D_A, None, {"dst": np.full(P_A, 0)}),
    "due_d_plus_1_one_row": (N_A, P_A, 3, D_A + 1, None,
                             {"dst": np.full(P_A, N_A - 1)}),
    # ... ties among held and unheld messages, and a tick whose every
    # message is held (an empty tick that is not an empty pool) ...
    "hold_ties": (N_A, P_A, 3, 30, None,
                  {"hold_every": 2, "t_due": (5, 7),
                   "dst": np.arange(P_A) % 5}),
    "hold_all": (N_A, P_A, 3, 30, None, {"hold_every": 1}),
    # ... and deliver times that differ in the high 32-bit word alone,
    # in the low one alone where it reads negative as an i32, and in
    # both (the sort compares the words)
    "times_high_word": (N_A, P_A, 3, 30, None,
                        {"t_due": (0, 4), "t_mul": 2**32, "t_end": 2**40,
                         "dst": np.arange(P_A) % 4}),
    "times_low_word_sign": (N_A, P_A, 3, 30, None,
                            {"t_due": (2**31 - 3, 2**31 + 3),
                             "t_end": 2**33, "dst": np.arange(P_A) % 4}),
    "times_both_words": (N_A, P_A, 3, 30, None,
                         {"t_due": (0, 2**45), "t_end": 2**45}),
    # dst outside [0, n) is clipped into the end rows, as the P-wide
    # rounds do (the sort oracle wraps or drops such rows: no oracle here)
    "dst_out_of_range": (N_A, P_A, 3, 30, None,
                         {"dst": np.arange(P_A) % (N_A + 6) - 3,
                          "oracle": "wide"}),
    "lanes_asked_5": (N_A, P_A, 3, 5, 5, {}),
    "lanes_asked_5_over": (N_A, P_A, 3, 6, 5, {}),
    # no power of two anywhere: N=1000, P=8000, D=250
    "n1000_under": (1000, 8000, 8, 250, None, {}),
    "n1000_over": (1000, 8000, 8, 251, None, {}),
    "n1000_ties_over_r": (1000, 8000, 8, 200, None,
                          {"t_due": (5, 8),
                           "dst": np.arange(8000) % 7}),
}
_SELECTORS = {}


def _selectors(lanes):
    """(sort oracle, P-wide rounds, default selection, lanes swept),
    jitted once for each lane count asked."""
    if lanes not in _SELECTORS:
        static = ("n", "r")
        _SELECTORS[lanes] = (
            jax.jit(build_inbox_sort, static_argnames=static),
            jax.jit(lambda pool, **kw: pool_mod.build_inbox(
                pool, lanes=pool.capacity, **kw), static_argnames=static),
            jax.jit(lambda *a, **kw: pool_mod.build_inbox(
                *a, lanes=lanes, **kw), static_argnames=static),
            jax.jit(lambda pool, n, t_end, alive, hold:
                    pool_mod.lanes_swept(pool, n, t_end, alive, hold,
                                         lanes), static_argnums=(1,)))
    return _SELECTORS[lanes]


@pytest.mark.parametrize("name", list(SELECT_CASES))
def test_inbox_select_over_due_lanes_equals_sort(name):
    """The default selection (the due messages compacted into D lanes
    and ranked by one sort of those, the P-wide rounds when they do not
    fit) equals the sort oracle AND the P-wide rounds on ``inbox``,
    ``delivered`` and ``to_dead`` at every load around D, and sweeps D
    lanes exactly when the due messages fit them."""
    n, p, r, n_due, lanes, extra = SELECT_CASES[name]
    extra = dict(extra)
    dead = extra.pop("dead", ())
    hold_every = extra.pop("hold_every", 0)
    oracle = extra.pop("oracle", "sort")
    pool = _case_pool(sum(map(ord, name)), n, p, n_due, **extra)
    alive = jnp.ones((n,), bool).at[jnp.asarray(dead, I32)].set(False)
    hold = (jnp.arange(p) % hold_every == 0) if hold_every else None
    t_end = jnp.int64(extra.get("t_end", T_END))
    sort_j, wide_j, sel_j, swept_j = _selectors(lanes)
    kw = dict(n=n, r=r, t_end=t_end, alive=alive, hold=hold)
    want, got = wide_j(pool, **kw), sel_j(pool, **kw)
    oracles = [want] + ([sort_j(pool, **kw)] if oracle == "sort" else [])
    for ref in oracles:
        for x, y, leaf in zip(ref, got, ("inbox", "delivered", "to_dead")):
            assert x.dtype == y.dtype and x.shape == y.shape, leaf
            assert (np.asarray(x) == np.asarray(y)).all(), leaf
    # the selection's own count of due messages: live destination, not
    # held
    live = np.asarray(pool.valid & (pool.t_deliver < t_end)
                      & ~want[2])
    if hold is not None:
        live = live & ~np.asarray(hold)
    if not dead and hold is None:
        assert live.sum() == n_due
    d = pool_mod.inbox_lanes(p) if lanes is None else lanes
    assert int(swept_j(pool, n, t_end, alive, hold)) == (
        d if live.sum() <= d else p)
    # slot 0 is filled first (what _phase_active_compact reads)
    inbox = np.asarray(got[0])
    assert ((inbox[:, 1:] < 0) | (inbox[:, :-1] >= 0)).all()


def test_inbox_lanes_rule_and_wide_forms():
    """D follows from P alone; ``lanes >= P`` and the tiled
    (``axis_name``) form hold no branch: the P-wide rounds only."""
    assert [pool_mod.inbox_lanes(p) for p in (8, 32, 128, 8000, 32768,
                                              131072)] == \
        [8, 32, 32, 250, 1024, 4096]
    pool = _case_pool(1, N_A, P_A, 10)
    args = (pool, N_A, 3, jnp.int64(T_END), jnp.ones((N_A,), bool))
    txt = str(jax.make_jaxpr(
        lambda: pool_mod.build_inbox(*args, lanes=P_A))())
    assert "cond" not in txt and "cumsum" not in txt
    assert "cond" in str(jax.make_jaxpr(
        lambda: pool_mod.build_inbox(*args))())


def test_inbox_overflow_keeps_earliest_r():
    """A node with more than R due messages must receive exactly the R
    EARLIEST (t_deliver, idx)-ordered ones this tick; the overflow stays
    valid in the pool and delivers next tick (backpressure, not loss) —
    pinned on the selection and on its sort oracle."""
    p = pool_mod.empty(16, key_lanes=5, rmax=4)
    q = 6
    out = {
        # node 0 gets 6 due messages, R=2: ties at t=3 break by index
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
    for impl, build in (("sort", build_inbox_sort),
                        ("scatter", pool_mod.build_inbox)):
        inbox, delivered, _ = build(
            p, n=2, r=2, t_end=jnp.int64(10), alive=alive)
        # earliest two: t=3@idx1, t=3@idx2 (tie → lower pool index first)
        assert list(np.asarray(inbox[0])) == [1, 2], impl
        assert int(jnp.sum(delivered)) == 2, impl
        # the four overflow messages stay pooled for next tick
        p2 = pool_mod.free(p, delivered)
        assert int(jnp.sum(p2.valid)) == 4, impl
        inbox2, delivered2, _ = build(
            p2, n=2, r=2, t_end=jnp.int64(10), alive=alive)
        assert list(np.asarray(inbox2[0])) == [4, 0], impl  # t=4 then t=5


def test_pool_overflow_counted():
    p = pool_mod.empty(4, key_lanes=5, rmax=4)
    q = 6
    out = {k: (jnp.zeros((q, 5), jnp.uint32) if k == "key" else
               jnp.full((q, 4), -1, I32) if k == "nodes" else
               jnp.zeros((q,), I64 if k == "t_deliver" else I32))
           for k in pool_mod.FIELDS}
    p, overflow = pool_mod.alloc(p, out, jnp.ones((q,), bool))
    assert int(overflow) == 2
    assert int(jnp.sum(p.valid)) == 4


# name -> (P, Q, occupied slots: a share taken at random or an exact
# count, wanted messages: likewise)
ALLOC_CASES = {
    "empty_pool": (24, 10, 0, 0.6),
    "full_pool": (24, 10, 24, 0.6),
    "no_wants": (24, 10, 0.5, 0),
    "one_free_slot": (24, 10, 23, 0.6),
    "wants_exceed_free": (24, 24, 0.8, 24),
    "q_larger_than_p": (8, 40, 0.25, 0.9),
    "all_wanted_all_free": (16, 16, 0, 16),
    # N=1000's own shapes (P = 8 N, Q = 16 N): no power of two
    "p8000_q16000": (8000, 16000, 0.4, 0.02),
    "random_a": (40, 30, 0.5, 0.5),
    "random_b": (40, 70, 0.7, 0.4),
    # t_deliver and stamp span the 64 bits (WORDS_CASES, below)
    "words_random": (40, 30, 0.5, 0.5),
    "words_wants_exceed_free": (24, 24, 0.8, 24),
    "words_p8000_q16000": (8000, 16000, 0.4, 0.02),
    # Q = 1: xmlrpcif's one injected packet
    "words_q1_inject": (16, 1, 0.5, 1),
    "words_q1_full_pool": (8, 1, 8, 1),
}
# the cases whose two i64 fields use both of their 32-bit words, in the
# pool and in the outbox: negative, above 2**32, T_INF in the slots that
# are free.  (The others draw them from 0 to 2,000: the high word is 0
# there, and a swapped or dropped word would pass.)
WORDS_CASES = {name for name in ALLOC_CASES if name.startswith("words_")}
KL, RMAX = 5, 4


def _pick(rng, size, how):
    """[size] bool: exactly ``how`` at random places when an int, else
    each with probability ``how``."""
    if isinstance(how, int):
        mask = np.zeros(size, bool)
        mask[rng.choice(size, size=how, replace=False)] = True
        return mask
    return rng.random(size) < how


def _span64(rng, size):
    """[size] i64 over all 64 bits, with the values a word mix-up would
    turn into one another planted first."""
    v = rng.integers(-2**63, 2**63 - 1, size=size, dtype=np.int64)
    edge = np.array([-1, 2**32, -2**32, 2**32 + 1, 2**31, 1, 0, 2**62],
                    np.int64)[:size]
    v[:edge.size] = edge
    return rng.permutation(v)


def _random_pool(rng, p, occupied, words=False):
    """A [P] pool whose every slot, valid or not, holds random content:
    a write to a slot it should not touch shows.  ``words``: the two i64
    fields span the 64 bits, and a free slot's ``t_deliver`` is T_INF,
    as ``pool.free`` leaves it."""
    w = len(pool_mod.SCAL_COLS) + KL + RMAX
    valid = _pick(rng, p, occupied)
    if words:
        t_deliver = np.where(valid, _span64(rng, p), int(pool_mod.T_INF))
        stamp = _span64(rng, p)
    else:
        t_deliver = rng.integers(0, 1000, size=p)
        stamp = rng.integers(0, 1000, size=p)
    return pool_mod.MsgPool(
        valid=jnp.asarray(valid),
        t_deliver=jnp.asarray(t_deliver, I64),
        stamp=jnp.asarray(stamp, I64),
        blk=jnp.asarray(rng.integers(-5, 1000, size=(p, w)), I32),
        kl=KL, rmax=RMAX)


@pytest.mark.parametrize("name", list(ALLOC_CASES))
def test_pool_alloc_equals_plain_allocator(name):
    """``pool.alloc`` against a plain allocator: the j-th wanted message
    goes into the j-th free slot (both in index order), wanted messages
    past the free supply are counted as overflow, ``blk``,
    ``t_deliver``, ``stamp`` and ``valid`` are written there and no
    other slot is touched."""
    p, q, occupied, wanted = ALLOC_CASES[name]
    words = name in WORDS_CASES
    rng = np.random.default_rng(sum(map(ord, name)))
    pool = _random_pool(rng, p, occupied, words)
    want = _pick(rng, q, wanted)
    out = {k: rng.integers(0, 1000, size=q).astype(np.int32)
           for k in pool_mod.SCAL_COLS}
    out["key"] = rng.integers(0, 2**32, size=(q, KL), dtype=np.uint32)
    out["nodes"] = rng.integers(-1, 50, size=(q, RMAX)).astype(np.int32)
    for k in ("t_deliver", "stamp"):
        out[k] = (_span64(rng, q) if words
                  else rng.integers(1000, 2000, size=q))
    new, overflow = jax.jit(pool_mod.alloc)(
        pool, {k: jnp.asarray(v) for k, v in out.items()},
        jnp.asarray(want))

    exp = {k: np.array(getattr(pool, k))
           for k in ("valid", "t_deliver", "stamp", "blk")}
    rows = np.concatenate(
        [np.stack([out[c] for c in pool_mod.SCAL_COLS], axis=1),
         out["key"].view(np.int32), out["nodes"]], axis=1)
    free = np.nonzero(~exp["valid"])[0]
    wanted_idx = np.nonzero(want)[0]
    for slot, j in zip(free, wanted_idx):      # the shorter one ends it
        exp["valid"][slot] = True
        exp["t_deliver"][slot] = out["t_deliver"][j]
        exp["stamp"][slot] = out["stamp"][j]
        exp["blk"][slot] = rows[j]
    assert int(overflow) == max(len(wanted_idx) - len(free), 0)
    for k, v in exp.items():
        got = np.asarray(getattr(new, k))
        assert got.dtype == v.dtype and (got == v).all(), k
    assert (new.kl, new.rmax) == (KL, RMAX)


# name -> the freed slots of 40 (a count or a share, as above)
FREE_CASES = {"none_freed": 0, "all_freed": 40, "random": 0.5}


@pytest.mark.parametrize("name", list(FREE_CASES))
def test_pool_free_and_next_deliver_time(name):
    """``pool.free`` clears ``valid`` and parks ``t_deliver`` at T_INF in
    the freed slots and nowhere else, payload left as it was;
    ``next_deliver_time`` is the earliest deliver time among the valid
    slots, T_INF when there is none."""
    p = 40
    rng = np.random.default_rng(sum(map(ord, name)))
    pool = _random_pool(rng, p, 0.6)
    mask = _pick(rng, p, FREE_CASES[name])
    new = jax.jit(pool_mod.free)(pool, jnp.asarray(mask))
    valid, t = np.asarray(pool.valid), np.asarray(pool.t_deliver)
    assert (np.asarray(new.valid) == (valid & ~mask)).all()
    assert (np.asarray(new.t_deliver)
            == np.where(mask, int(pool_mod.T_INF), t)).all()
    assert (np.asarray(new.stamp) == np.asarray(pool.stamp)).all()
    assert (np.asarray(new.blk) == np.asarray(pool.blk)).all()
    for pl in (pool, new):
        live = np.asarray(pl.t_deliver)[np.asarray(pl.valid)]
        assert int(pool_mod.next_deliver_time(pl)) == (
            live.min() if live.size else int(pool_mod.T_INF))
    if name == "all_freed":
        assert int(pool_mod.next_deliver_time(new)) == int(pool_mod.T_INF)
    if name == "none_freed":
        assert int(np.sum(new.valid)) == int(valid.sum()) > 0


# ---------------------------------------------------------------------------
# ping/pong end-to-end
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PingState:
    t_ping: jnp.ndarray   # [N] i64 next ping timer
    t_sent: jnp.ndarray   # [N] i64 time of outstanding ping
    ready: jnp.ndarray    # [N] bool


class PingLogic:
    key_spec = keys_mod.KeySpec(160)
    interval_ns = 1 * NS

    def stat_spec(self):
        return stats_mod.StatSpec(
            scalars=("ping.rtt",),
            hists=(("ping.rttBins", 8),),
            counters=("ping.sent", "pong.received"))

    def init(self, rng, n):
        return PingState(
            t_ping=jnp.full((n,), T_INF, I64),
            t_sent=jnp.zeros((n,), I64),
            ready=jnp.zeros((n,), bool))

    def reset(self, state, clear, join, t_now, rng):
        jitter = jax.random.randint(
            rng, clear.shape, 0, self.interval_ns, dtype=I64)
        return PingState(
            t_ping=jnp.where(join, t_now + jitter,
                             jnp.where(clear, T_INF, state.t_ping)),
            t_sent=jnp.where(clear, 0, state.t_sent),
            ready=jnp.where(clear, join, state.ready))

    def ready_mask(self, state):
        return state.ready

    def next_event(self, state):
        return state.t_ping

    def step(self, ctx, st, msgs, rng, node_idx, *, outbox_slots, rmax):
        out = Outbox(outbox_slots, self.key_spec.lanes, rmax)
        rtt_vals = jnp.zeros((msgs.valid.shape[0],), jnp.float32)
        rtt_mask = jnp.zeros((msgs.valid.shape[0],), bool)
        pongs = jnp.int32(0)

        # inbox
        for r in range(msgs.valid.shape[0]):
            m = msgs.slot(r)
            is_ping = m.valid & (m.kind == KIND_PING)
            out.send(is_ping, m.t_deliver, m.src, KIND_PONG, nonce=m.nonce,
                     size_b=40)
            is_pong = m.valid & (m.kind == KIND_PONG)
            rtt = (m.t_deliver - st.t_sent).astype(jnp.float32) / NS
            rtt_vals = rtt_vals.at[r].set(rtt)
            rtt_mask = rtt_mask.at[r].set(is_pong)
            pongs += is_pong.astype(I32)

        # ping timer
        due = st.t_ping < ctx.t_end
        dst = ctx.sample_ready(rng)
        fire = due & (dst >= 0) & (dst != node_idx)
        out.send(fire, st.t_ping, dst, KIND_PING, nonce=node_idx, size_b=40)
        st = dataclasses.replace(
            st,
            t_ping=jnp.where(due, st.t_ping + self.interval_ns, st.t_ping),
            t_sent=jnp.where(fire, st.t_ping, st.t_sent))

        events = {
            "s:ping.rtt": (rtt_vals, rtt_mask),
            "h:ping.rttBins": ((rtt_vals * 20).astype(I32), rtt_mask),
            "c:ping.sent": fire.astype(I32),
            "c:pong.received": pongs,
        }
        return st, out, events


def make_sim(n=16, window=0.010):
    logic = PingLogic()
    cp = churn_mod.ChurnParams(model="none", target_num=n, init_interval=0.1)
    ep = EngineParams(window=window, inbox_slots=4, outbox_slots=8,
                      pool_factor=8, rmax=4)
    return Simulation(logic, cp, underlay_mod.UnderlayParams(), ep)


def test_ping_pong_end_to_end():
    sim = make_sim(n=16)
    s = sim.init(seed=3)
    s = sim.run_until(s, t_sim=10.0, chunk=64)
    out = sim.summary(s)

    assert out["_alive"] == 16
    assert out["ping.sent"] > 50
    # most pings must be answered (no churn, no loss on ethernet channel)
    assert out["pong.received"] >= 0.9 * out["ping.sent"] - 16
    rtt = out["ping.rtt"]
    assert rtt["count"] == out["pong.received"]
    # RTT plausibility: 2 * (coord delay within 150x150 field + tx delays)
    # max coord distance ~212 units -> one-way <= ~0.25s + jitter
    assert 0.0005 < rtt["mean"] < 0.5
    assert out["_engine"]["pool_overflow"] == 0
    assert out["_engine"]["outbox_overflow"] == 0
    assert out["_engine"]["dest_unavailable_lost"] == 0


def test_tick_equals_the_sort_oracles_tick():
    """Whole ticks under the sort oracle (tests/oracles.py
    SortSimulation: ``_phase_inbox_select`` overridden, every other
    phase the engine's) end on the engine's own SimState, leaf for
    leaf, over a fill and a window of ping/pong traffic."""
    sim = make_sim(n=16)
    a, b = (jax.device_get(s.run_chunk(s.init(seed=3), 400))
            for s in (sim, SortSimulation.of(sim)))
    assert jax.tree.structure(a) == jax.tree.structure(b)
    assert all(jax.tree.leaves(jax.tree.map(np.array_equal, a, b)))
    assert int(a.stats["c:pong.received"]) > 20


# ---------------------------------------------------------------------------
# hot-path structure: sort count, donation, device-resident loop
# ---------------------------------------------------------------------------

def test_tick_hlo_zero_sorts_bounded_scatters():
    """The inbox selection leaves the tick graph with ZERO full-pool
    sorts (its one sort is over the due messages' D = 32 compacted
    lanes), and the scatter count stays within the engine budget: 4
    baseline scatters (of which the outbox allocation takes 2: the
    free-slot list and the ONE row scatter that carries the packed
    block with ``t_deliver``, ``stamp`` and ``valid`` as words; a
    histogram is counted by comparison and takes none), 2 in the
    selection's D-lane branch (the [N, R] table and ``delivered``) and
    2 per inbox round in its P-wide branch, which a tick whose due
    messages outnumber the lanes takes: 12 today, pinned via
    scripts/hlo_breakdown.py's counting helpers so the --budget CLI and
    this test share one definition.  n=24 makes the pool dimension
    P = 24*8 = 192 distinctive in shape strings."""
    from scripts.hlo_breakdown import check_budget
    sim = make_sim(n=24)
    s = sim.init(seed=1)
    txt = jax.jit(lambda st: sim.step(st)).lower(s).compile().as_text()
    ok, counts = check_budget(
        txt, pool_dim=192, max_full_pool_sorts=0,
        max_scatters=4 + 2 + 2 * sim.ep.inbox_slots)
    assert ok, counts
    assert counts["full_pool_sort_count"] == 0, counts
    # the selection's one D-lane sort, and the ping logic's own argsort
    assert counts["sort_count"] == 2, counts


def _eqns(jaxpr, into_cond=True):
    """Every equation of a jaxpr, those of its sub-jaxprs (cond
    branches unless ``into_cond`` is false, loop bodies, calls)
    included."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name == "cond" and not into_cond:
            continue
        for v in e.params.values():
            for x in (v if isinstance(v, (tuple, list)) else (v,)):
                x = getattr(x, "jaxpr", x)
                if hasattr(x, "eqns"):
                    yield from _eqns(x, into_cond)


def _lanes_of(e):
    """Index rows of a gather, updates of a scatter (a lane of a vmapped
    one counted once); None for any other equation."""
    name = e.primitive.name
    if name == "gather":
        batch = e.params["dimension_numbers"].start_indices_batching_dims
    elif name.startswith("scatter"):
        batch = e.params["dimension_numbers"].scatter_indices_batching_dims
    else:
        return None
    return math.prod(d for i, d in enumerate(e.invars[1].aval.shape[:-1])
                     if i not in batch)


@pytest.mark.parametrize("tick_impl", ["auto", "dense"])
def test_tick_holds_no_wide_64_bit_scatter(tick_impl):
    """On the chip a scatter into a 64-bit operand costs 53 to 134 ns an
    UPDATE, the dropped and the zero ones too, a 32-bit one a twentieth:
    three such scatters of 13 N and 16 N updates were half of the cells'
    tick (PERF.md, PR 36), the selection's 2R rounds over D lanes and
    the bucket update's A x C updates into ``b_seen`` a sixth of what
    was left (PR 40).  So in the traced tick of the cells' own
    deployment no scatter into a 64-bit operand has D or more updates,
    but for the R scatter-min rounds of the inbox selection's P-wide
    branch, which only a tick with more due messages than lanes runs;
    and none of the node step's (vmapped over its lanes, counted a
    lane) has C or more, the candidates of one bucket update.  N=256,
    where A = 32 < D = 64 < C = 80 tells the three apart: the awake-set
    write-back's row scatters of A rows a leaf stay.  The lookup engine
    scatters nothing (PR 42: a slot is written by mask)."""
    from test_zz_sparse import _cell_sim    # kademlia4096.kbr60 at N=256
    sim, _ = _cell_sim(tick_impl=tick_impl, n=256)
    n, p, r = sim.n, sim.n * sim.ep.pool_factor, sim.ep.inbox_slots
    d = sim.inbox_lanes
    c = sim.logic.p.s + r * (1 + sim.logic.lcfg.frontier)
    assert (sim.acap, d, c) == (32, 64, 80)
    shapes = jax.eval_shape(lambda: sim.init_from_rng(jax.random.PRNGKey(1)))
    wide_rounds, seen, lane_wise = 0, 0, []
    for e in _eqns(jax.make_jaxpr(sim.step)(shapes).jaxpr):
        if not e.primitive.name.startswith("scatter"):
            continue
        operand, updates = e.invars[0].aval, _lanes_of(e)
        lane = bool(
            e.params["dimension_numbers"].scatter_indices_batching_dims)
        seen += 1
        if operand.dtype.itemsize < 8:
            continue
        if updates < (c if lane else d):
            if lane:
                lane_wise.append((operand.shape[-1], updates))
            continue
        assert (e.primitive.name, operand.shape, updates) == (
            "scatter-min", (n,), p), (e.primitive.name, operand, updates)
        wide_rounds += 1
    assert wide_rounds == r and seen > 2 * r, (wide_rounds, seen)
    # the pin is sharp: ONE 64-bit scatter of the node step is left under
    # the threshold, ``b_used`` [B] at the buckets of the L completed
    # refresh lookups (kademlia.refresh); the lookup engine's six 64-bit
    # leaves (t_sent, t_to, deadline, t0, t_done, ver_to) are written by
    # mask since PR 42 (common/lookup.py)
    assert lane_wise == [(sim.logic.p.num_buckets, sim.logic.lcfg.slots)]


def test_run_chunk_donates_state():
    """run_chunk declares donate_argnums on the SimState: after the
    call the caller's input buffers must be gone (re-used in place by
    XLA), so holding the old state is a use-after-donate bug."""
    sim = make_sim(n=8)
    s = sim.init(seed=2)
    old_t, old_valid = s.t_now, s.pool.valid
    s2 = sim.run_chunk(s, 2)
    jax.block_until_ready(s2.t_now)
    assert old_t.is_deleted()
    assert old_valid.is_deleted()
    assert not s2.t_now.is_deleted()


def test_init_never_hands_shared_scalars_to_donation():
    """A state leaf must own its buffer: churn.init writes
    ``t_tick=T_INF`` and overlays write ``rp=NO_NODE`` — module-level
    device scalars.  Simulation.init copies every 0-d leaf
    (_dedupe_buffers), so a donated run deletes neither the constants
    nor the chance of a second init in the same process."""
    from oversim_tpu.engine.sim import _dedupe_buffers
    shared = jnp.int32(-1)
    owned = _dedupe_buffers({"rp": shared, "x": jnp.zeros((4,), I32)})
    assert (owned["rp"].unsafe_buffer_pointer()
            != shared.unsafe_buffer_pointer())
    assert int(owned["rp"]) == -1

    sim = make_sim(n=8)
    s = sim.init(seed=2)
    assert (s.churn.t_tick.unsafe_buffer_pointer()
            != churn_mod.T_INF.unsafe_buffer_pointer())
    s = sim.run_chunk(s, 2)
    jax.block_until_ready(s.t_now)
    assert not churn_mod.T_INF.is_deleted()
    s = sim.run_chunk(sim.init(seed=3), 2)    # second init, same process
    assert int(s.tick) == 2


def test_run_until_device_matches_host_loop_chord64():
    """The lax.while_loop device-resident runner must be bit-identical
    to the host chunk loop on a real overlay scenario (chord, N=64):
    same ticks, same RNG stream, same summary."""
    from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
    from oversim_tpu.overlay.chord import ChordLogic

    app = KbrTestApp(KbrTestParams(test_interval=2.0))
    logic = ChordLogic(app=app)
    cp = churn_mod.ChurnParams(model="none", target_num=64,
                               init_interval=0.1)
    # R=2 (engine default 8): the two programs hold the handler unrolled
    # over the inbox slots, and the identity is the run loop's, at any R
    ep = EngineParams(window=0.2, transition_time=10.0, inbox_slots=2)
    sim = Simulation(logic, cp, engine_params=ep)

    target = cp.init_finished_time + 8.0
    s_host = sim.init(seed=11)
    s_dev = sim.init(seed=11)
    a = sim.run_until(s_host, target, chunk=16)
    b = sim.run_until_device(s_dev, target, chunk=16)
    assert int(a.t_now) == int(b.t_now)
    oa, ob = sim.summary(a), sim.summary(b)
    assert oa.keys() == ob.keys()
    for k in oa:
        assert str(oa[k]) == str(ob[k]), k


def _inbox_identity_run(overlay: str, n_ticks: int = 64, seed: int = 3):
    """Run ``n_ticks`` overlay ticks under LifetimeChurn; at every tick
    compare the sort oracle's and the engine's inbox selections on the
    SAME pool/alive snapshot (one fused scan, one dispatch).  Returns
    per-tick equality, due-message counts and dead-destination drop
    counts."""
    if overlay == "chord":
        from oversim_tpu.overlay.chord import ChordLogic
        logic = ChordLogic()
    else:
        from oversim_tpu.overlay.kademlia import KademliaLogic
        logic = KademliaLogic()
    cp = churn_mod.ChurnParams(model="lifetime", target_num=12,
                               init_interval=0.2, lifetime_mean=8.0)
    ep = EngineParams(window=0.1, inbox_slots=4, pool_factor=4)
    sim = Simulation(logic, cp, engine_params=ep)
    s = sim.init(seed=seed)

    def body(st, _):
        t_next, t_end, rngs = sim._phase_horizon(st)
        _, alive, *_rest = sim._phase_churn(
            st, t_next, t_end, rngs[1], rngs[2], rngs[3], rngs[5])
        a = build_inbox_sort(
            st.pool, sim.n, sim.ep.inbox_slots, t_end, alive)
        b = pool_mod.build_inbox(
            st.pool, sim.n, sim.ep.inbox_slots, t_end, alive)
        same = jnp.array(True)
        for x, y in zip(a, b):
            same &= jnp.array_equal(x, y)
        due = jnp.sum(st.pool.valid & (st.pool.t_deliver < t_end))
        return sim.step(st), (same, due, jnp.sum(a[2]))

    @jax.jit
    def run(st):
        return jax.lax.scan(body, st, None, length=n_ticks)

    final, (same, due, to_dead) = run(s)
    return (np.asarray(same), np.asarray(due), np.asarray(to_dead),
            int(jnp.sum(final.alive)))


def test_inbox_identity_chord_under_churn():
    """Satellite pin: sort vs scatter inbox selection is bit-identical
    (inbox, delivered, dropped_dead) across 64 chord ticks with lifetime
    churn killing/rebirthing nodes mid-run."""
    same, due, to_dead, _alive = _inbox_identity_run("chord")
    assert same.all(), f"first divergent tick: {int(np.argmin(same))}"
    assert int(due.sum()) > 50, int(due.sum())   # the run carried traffic


def test_inbox_identity_kademlia_under_churn():
    same, due, to_dead, _alive = _inbox_identity_run("kademlia")
    assert same.all(), f"first divergent tick: {int(np.argmin(same))}"
    assert int(due.sum()) > 50, int(due.sum())


def test_ping_rtt_matches_analytic_delay():
    """With jitter off, RTT between two specific nodes must equal twice the
    calcDelay formula (SimpleNodeEntry.cc:155-195)."""
    logic = PingLogic()
    cp = churn_mod.ChurnParams(model="none", target_num=2, init_interval=0.01)
    up = underlay_mod.UnderlayParams(jitter=0.0)
    ep = EngineParams(window=0.001, inbox_slots=2, outbox_slots=4, rmax=4)
    sim = Simulation(logic, cp, up, ep)
    s = sim.init(seed=5)
    s = sim.run_until(s, t_sim=5.0, chunk=64)
    out = sim.summary(s)

    coords = np.asarray(s.underlay.coords)
    dist = np.linalg.norm(coords[0] - coords[1])
    bits = (40 + 28) * 8
    one_way = bits / 10e6 + 0.001 * dist + bits / 10e6
    rtt = out["ping.rtt"]
    assert rtt["count"] > 0
    np.testing.assert_allclose(rtt["mean"], 2 * one_way, rtol=0.02)
