"""End-to-end Kademlia slice: table formation + KBR one-way delivery.

Self-validating-workload strategy (SURVEY.md §4): deliveries are checked
against sibling responsibility; table contents are checked against the
global key oracle (the analogue of GlobalNodeList-based verification).
"""

import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.core import keys as K
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.kademlia import (KademliaLogic, KademliaParams,
                                          READY)

from oracles import bucket_update_three_scatters


@pytest.fixture(scope="module")
def kad_run():
    logic = KademliaLogic()
    cp = churn_mod.ChurnParams(model="none", target_num=8, init_interval=1.0)
    ep = sim_mod.EngineParams(window=0.050, transition_time=20.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=11)
    st = s.run_until(st, 300.0, chunk=128)
    return s, st


def test_all_nodes_ready(kad_run):
    _, st = kad_run
    assert np.asarray(st.alive).sum() == 8
    assert (np.asarray(st.logic.state) == READY).all()


def test_sibling_tables_complete(kad_run):
    """8 nodes, s=8: every node must know all 7 others as siblings."""
    _, st = kad_run
    sib = np.asarray(st.logic.sib)
    for i in range(8):
        known = {x for x in sib[i] if x >= 0}
        assert known == set(range(8)) - {i}, f"node {i}: {known}"


def test_sibling_tables_sorted_by_xor(kad_run):
    _, st = kad_run
    keys_int = [K.to_int(k) for k in np.asarray(st.node_keys)]
    sib = np.asarray(st.logic.sib)
    for i in range(8):
        entries = [x for x in sib[i] if x >= 0]
        dists = [keys_int[i] ^ keys_int[x] for x in entries]
        assert dists == sorted(dists), f"node {i} sibling table unsorted"


def test_deliveries(kad_run):
    s, st = kad_run
    out = s.summary(st)
    assert out["kbr_sent"] > 20
    # the run stops at a chunk boundary: the last send(s) may still be in
    # flight (the reference has the same end-of-run truncation)
    assert out["kbr_delivered"] >= out["kbr_sent"] - 2
    assert out["kbr_delivered"] <= out["kbr_sent"]
    assert out["kbr_wrong_node"] == 0
    assert out["kbr_lookup_failed"] == 0
    # everyone knows everyone: lookups resolve in at most a hop or two
    assert out["kbr_hopcount"]["max"] <= 2


def test_no_engine_losses(kad_run):
    s, st = kad_run
    eng = s.summary(st)["_engine"]
    assert eng["pool_overflow"] == 0
    assert eng["outbox_overflow"] == 0
    assert eng["queue_lost"] == 0


# ---------------------------------------------------------------------------
# the bucket update against its plain form (tests/oracles.py)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class _Tables:
    """The leaves of one node's KademliaState the bucket update reads
    and writes."""
    buckets: object
    b_seen: object
    b_stale: object
    rc_nodes: object
    rc_pos: object

# name -> (KademliaParams, nodes, candidates a call, share of the nodes
# already in the table, what else)
BUCKET_CASES = {
    # k=2: the first buckets are full, most candidates find no slot
    "full_buckets": (dict(k=2, max_stale=1), 64, 12, 0.9, {}),
    # stale counts above maxStaleCount: alive candidates evict, highest
    # count first; unverified ones never do
    "stale_to_evict": (dict(k=2, max_stale=1), 64, 12, 0.9,
                       {"stale_to": 4}),
    # every candidate earns bucket 0 (its first bit differs from ours),
    # alive and unverified mixed: alive ones rank first
    "one_bucket_alive_and_unverified": (dict(k=4, max_stale=0), 64, 12, 0.2,
                                        {"one_bucket": True}),
    "disabled_candidates": (dict(k=2, max_stale=1), 64, 12, 0.5,
                            {"disabled": 0.7}),
    "all_disabled": (dict(k=2, max_stale=1), 64, 12, 0.5, {"disabled": 1.0}),
    "empty_table": (dict(k=2, max_stale=1), 64, 12, 0.0, {}),
    "present_refresh": (dict(k=8, max_stale=0), 64, 12, 1.0, {}),
    "replacement_ring": (dict(k=1, max_stale=0, replacement_cands=2), 64, 12,
                         0.9, {}),
    "replacement_ping": (dict(k=1, max_stale=0, replacement_cands=2,
                              replacement_cache_ping=True), 64, 12, 0.9, {}),
    # the cells' own sizes: B=32, K=8, C=80, stepped A lanes at once
    "cell_sizes_vmapped": (dict(k=8, max_stale=0), 256, 80, 0.3,
                           {"lanes": 4}),
}
_BUCKET_FNS = {}


def _bucket_fns(params, lanes):
    """(overlay's update, plain form), jitted once for each parameter
    set; over ``lanes`` nodes at once where asked, as the node step
    runs them."""
    key = (tuple(sorted(params.items())), lanes)
    if key not in _BUCKET_FNS:
        logic = KademliaLogic(params=KademliaParams(**params))

        def pair(fn):
            def one(keys, st, me_key, cands, alive, now):
                return fn(SimpleNamespace(keys=keys), st, me_key, cands,
                          alive, now)
            if lanes:
                one = jax.vmap(one, in_axes=(None, 0, 0, 0, 0, None))
            return jax.jit(one)
        _BUCKET_FNS[key] = (
            logic, pair(logic._bucket_update_batch),
            pair(lambda *a: bucket_update_three_scatters(logic, *a)))
    return _BUCKET_FNS[key]


def _random_tables(rng, logic, keys, me, fill, stale_to):
    """One node's tables, each entry in the bucket its key earns."""
    p = logic.p
    n = keys.shape[0]
    bi = np.asarray(logic._bucket_index(keys[me], keys))
    buckets = np.full((p.num_buckets, p.k), -1, np.int32)
    for j in rng.permutation(n):
        if j == me or rng.random() >= fill:
            continue
        free = np.flatnonzero(buckets[bi[j]] < 0)
        if free.size:
            buckets[bi[j], rng.choice(free)] = j
    held = buckets >= 0
    rc = p.replacement_cands
    return _Tables(
        buckets=buckets,
        b_seen=np.where(held, rng.integers(1, 2**40, buckets.shape), 0),
        b_stale=np.where(held, rng.integers(0, stale_to + 1, buckets.shape),
                         0).astype(np.int32),
        rc_nodes=rng.integers(-1, n, (p.num_buckets, rc)).astype(np.int32),
        rc_pos=rng.integers(0, max(rc, 1), (p.num_buckets,)).astype(
            np.int32))


@pytest.mark.parametrize("name", list(BUCKET_CASES))
def test_bucket_update_equals_three_scatter_form(name):
    """``_bucket_update_batch`` (the placed slot and its flag by ONE
    32-bit scatter, ``b_seen`` and ``b_stale`` by mask) leaves
    ``buckets``, ``b_seen``, ``b_stale``, the replacement cache, its
    cursor and ``rc_ping`` the bits of the plain three-scatter form,
    over randomised tables."""
    params, n, c_dim, fill, extra = BUCKET_CASES[name]
    lanes = extra.get("lanes", 0)
    logic, got_j, want_j = _bucket_fns(params, lanes)
    rng = np.random.default_rng(sum(map(ord, name)))
    placed = 0
    for trial in range(6):
        keys = rng.integers(0, 2**32, (n, logic.key_spec.lanes),
                            dtype=np.uint32)
        rows = []
        for me in rng.choice(n, size=max(lanes, 1), replace=False):
            if extra.get("one_bucket"):
                # every other node's first bit differs from ours
                keys[:, 0] |= np.uint32(1 << 31)
                keys[me, 0] &= np.uint32((1 << 31) - 1)
            st = _random_tables(rng, logic, jnp.asarray(keys), me, fill,
                                extra.get("stale_to", 1))
            others = np.setdiff1d(np.arange(n), [me])
            cands = rng.choice(others, size=c_dim, replace=False).astype(
                np.int32)
            cands[rng.random(c_dim) < extra.get("disabled", 0.1)] = -1
            rows.append((st, keys[me], cands, rng.random(c_dim) < 0.5))
        if lanes:
            st, me_key, cands, alive = jax.tree.map(
                lambda *x: np.stack(x), *rows)
        else:
            st, me_key, cands, alive = rows[0]
        args = (jnp.asarray(keys), jax.tree.map(jnp.asarray, st),
                jnp.asarray(me_key), jnp.asarray(cands), jnp.asarray(alive),
                jnp.int64(2**41 + trial))
        got, want = jax.device_get((got_j(*args), want_j(*args)))
        la, ta = jax.tree.flatten(got)
        lb, tb = jax.tree.flatten(want)
        assert ta == tb
        for x, y in zip(la, lb):
            assert x.dtype == y.dtype and x.shape == y.shape
            assert np.array_equal(x, y), (trial, name)
        placed += int((got[0].buckets != np.asarray(st.buckets)).sum())
    if "disabled" not in name and name != "present_refresh":
        assert placed > 0       # the case does place candidates
