"""Oracles the tests hold the engine to; nothing under oversim_tpu/
imports this module.

``build_inbox_sort`` is the inbox selection as one lexicographic
(dst, t_deliver) full-pool stable sort, O(P log P): what
``engine/pool.py build_inbox`` (one sort of the due messages' compacted
lanes, R rounds of scatter-min over the pool in a tick that overruns
them) must equal bit for bit.  ``SortSimulation``
runs a whole tick on it by overriding the ONE phase that selects —
``Simulation._phase_inbox_select`` is the seam, and only the tests use
it.  ``bucket_update_three_scatters`` is Kademlia's bucket update with
its writes as three plain scatters, what
``KademliaLogic._bucket_update_batch`` must equal leaf for leaf.
"""

import dataclasses

import jax
import jax.numpy as jnp

from oversim_tpu.engine import pool as pool_mod
from oversim_tpu.engine.pool import I32, NO_NODE, T_INF, MsgPool
from oversim_tpu.engine.sim import SimState, Simulation


def build_inbox_sort(pool: MsgPool, n: int, r: int, t_end, alive,
                     hold=None):
    """Inbox grouping by one lexicographic (dst, t_deliver) full-pool
    stable sort, O(P log P): same arguments and same
    ``(inbox, delivered, to_dead)`` as ``pool.build_inbox``."""
    p = pool.capacity
    due, to_dead = pool_mod._due_masks(pool, n, t_end, alive, hold)

    dst_k = jnp.where(due, pool.dst, n).astype(I32)
    t_k = jnp.where(due, pool.t_deliver, T_INF)
    idx = jnp.arange(p, dtype=I32)
    dst_s, _, idx_s = jax.lax.sort((dst_k, t_k, idx), dimension=0, num_keys=2)

    # rank of each message within its destination group
    first = jnp.searchsorted(dst_s, dst_s, side="left").astype(I32)
    rank = jnp.arange(p, dtype=I32) - first
    take = (dst_s < n) & (rank < r)

    rows = jnp.where(take, dst_s, n)  # row n is out-of-bounds -> dropped
    inbox = jnp.full((n, r), NO_NODE, I32).at[rows, jnp.minimum(rank, r - 1)].set(
        idx_s, mode="drop")
    delivered = jnp.zeros((p,), bool).at[idx_s].set(take)
    return inbox, delivered, to_dead


class SortSimulation(Simulation):
    """A Simulation whose every tick selects its inbox by the sort
    oracle: every other phase is the engine's own."""

    @classmethod
    def of(cls, sim: Simulation) -> "SortSimulation":
        """``sim``'s deployment (what a builder such as
        ``build_simulation`` returned) under the oracle selection."""
        return cls(sim.logic, sim.cp, sim.up, sim.ep, sim.ul)

    def _phase_inbox_select(self, s: SimState, t_end, alive):
        return build_inbox_sort(s.pool, self.n, self.ep.inbox_slots, t_end,
                                alive, hold=self._hold_mask(s))


def bucket_update_three_scatters(logic, ctx, st, me_key, cands, alive,
                                  now):
    """``KademliaLogic._bucket_update_batch`` in its plain form: the
    accepted candidates written by THREE scatters over the same
    ``(rows, col)``, one of them into the i64 ``b_seen`` (the body the
    overlay had until PR 40, kept here word for word).  The overlay
    places them by one 32-bit scatter and writes ``b_seen`` and
    ``b_stale`` by mask; every leaf and ``rc_ping`` must be the same
    bits."""
    p = logic.p
    num_b, kk = p.num_buckets, p.k
    c_dim = cands.shape[0]
    en = cands != NO_NODE
    ck = ctx.keys[jnp.maximum(cands, 0)]
    bi = jnp.where(en, logic._bucket_index(me_key, ck), num_b)

    # --- presence refresh (alive contacts only) ---
    # ... in the bucket the contact's CURRENT key earns: an entry is
    # a slot index, a recycled slot comes back under a fresh key, and
    # the copy its old key left in another bucket is another node's
    # (upstream's handle is address AND key), never refreshed
    # (slot and bucket folded into one word, so that the [B, K, C]
    # comparison stays ONE comparison: a disabled candidate reads -1,
    # which only an empty slot of the last row equals, masked below)
    ckey = jnp.where(en & alive, cands * num_b + bi, -1)
    bkey = st.buckets * num_b + jnp.arange(num_b, dtype=I32)[:, None]
    hit = jnp.any(
        bkey[:, :, None] == ckey[None, None, :], axis=-1) & (
        st.buckets != NO_NODE)
    b_seen = jnp.where(hit, now, st.b_seen)
    b_stale = jnp.where(hit, 0, st.b_stale)
    buckets = st.buckets

    # --- slot assignment for absent candidates ---
    row_c = buckets[jnp.minimum(bi, num_b - 1)]           # [C, K]
    present = jnp.any(row_c == cands[:, None], axis=1)
    need = en & ~present
    # candidates ordered by (bucket, alive-first, arrival order)
    k1 = jnp.where(need, bi, num_b).astype(I32)
    k2 = (~alive).astype(I32)
    k3 = jnp.arange(c_dim, dtype=I32)
    b_s, a_s, idx_s = jax.lax.sort((k1, k2, k3), num_keys=3)
    rank = k3 - jnp.searchsorted(b_s, b_s, side="left").astype(I32)
    # per-bucket column order: free columns first, then evictable by
    # stale count descending, then untouchable
    free = buckets == NO_NODE
    evictable = ~free & (b_stale > p.max_stale)
    cls = jnp.where(free, 0, jnp.where(evictable, 1, 2))
    colkey = cls * (1 << 20) - jnp.where(
        evictable, jnp.minimum(b_stale, (1 << 19) - 1), 0)
    order = jnp.argsort(colkey, axis=1).astype(I32)       # [B, K]
    free_cnt = jnp.sum(free, axis=1, dtype=I32)           # [B]
    avail_cnt = free_cnt + jnp.sum(evictable, axis=1, dtype=I32)

    bi_c = jnp.minimum(b_s, num_b - 1)
    limit = jnp.where(a_s == 0, avail_cnt[bi_c], free_cnt[bi_c])
    okc = (b_s < num_b) & (rank < limit) & (rank < kk)
    col = order[bi_c, jnp.clip(rank, 0, kk - 1)]
    rows = jnp.where(okc, bi_c, num_b)
    vals = cands[idx_s]
    al_v = a_s == 0
    st = dataclasses.replace(
        st,
        buckets=buckets.at[rows, col].set(vals, mode="drop"),
        b_seen=b_seen.at[rows, col].set(
            jnp.where(al_v, now, jnp.int64(0)), mode="drop"),
        b_stale=b_stale.at[rows, col].set(0, mode="drop"))

    # --- replacement cache (enableReplacementCache, Kademlia.cc:
    # routingAdd full-bucket branch): alive candidates that found no
    # slot enter the bucket's bounded candidate ring; a later
    # eviction promotes one (see _handle_failed).  Ring overwrite
    # replaces the reference's LRU-bounded cache list.
    rc = p.replacement_cands
    if rc:
        rej = (b_s < num_b) & ~okc & al_v
        rej_rank = rank - limit
        pos = (st.rc_pos[bi_c] + jnp.maximum(rej_rank, 0)) % rc
        rrows = jnp.where(rej, bi_c, num_b)
        new_rc = st.rc_nodes.at[rrows, pos].set(vals, mode="drop")
        rej_per_b = jnp.zeros((num_b,), I32).at[rrows].add(
            1, mode="drop")
        st = dataclasses.replace(
            st, rc_nodes=new_rc,
            rc_pos=(st.rc_pos + rej_per_b) % rc)
        # replacementCachePing: give the least-recently-seen entry
        # of each cache-fed bucket a liveness check so stale entries
        # make room (one ping candidate per tick, bounded ping slots)
        if p.replacement_cache_ping:
            fed = jnp.zeros((num_b,), bool).at[rrows].set(
                True, mode="drop")
            seen_k = jnp.where(
                (st.buckets != NO_NODE) & fed[:, None],
                st.b_seen, T_INF)
            flat_i = jnp.argmin(seen_k.reshape(-1))
            cand_p = st.buckets.reshape(-1)[flat_i]
            rc_ping = jnp.where(
                jnp.any(fed) & (cand_p != NO_NODE), cand_p, NO_NODE)
            return st, rc_ping
    return st, NO_NODE
