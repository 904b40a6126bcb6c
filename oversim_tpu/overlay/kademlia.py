"""Kademlia XOR-metric DHT as vectorized per-node logic.

TPU-native rebuild of the reference Kademlia
(src/overlay/kademlia/Kademlia.{h,cc} + KademliaBucket/KademliaBucketEntry),
default configuration (simulations/default.ini:185-200: k=8, s=8, b=1,
maxStaleCount=0, lookupMerge=true, iterative routing, exhaustiveRefresh,
minSibling/BucketRefreshInterval=1000s).  State is structure-of-arrays:

  * sibling table [N, S]: the s XOR-closest known nodes, kept sorted by
    distance from own key (KademliaBucket extends BaseKeySortedVector);
  * k-buckets [N, B, K] with last-seen [N, B, K] and stale counters:
    bucket index = sharedPrefixLength(own, other) clipped to B-1
    (reference routingBucketIndex Kademlia.cc:357 = first non-zero digit
    of the XOR delta — identical partition for b=1; distant-prefix
    buckets beyond B-1 collapse onto the last row, which only matters
    for astronomically-close non-sibling keys);
  * routingAdd (Kademlia.cc:432): every message source is added alive
    with the full policy (sibling merge incl. displacement of the
    furthest sibling into a bucket; in-bucket lastSeen refresh; free-slot
    insert; stale-entry replacement).  Nodes learned from
    FindNodeResponse payloads are added unverified (isAlive=false,
    Kademlia.cc:1412): they merge into the sibling table and fill FREE
    bucket slots only — no displacement;
  * replacement cache (enableReplacementCache/replacementCandidates,
    Kademlia.h:86-89): alive candidates rejected by a full bucket enter
    a per-bucket candidate ring; evictions promote from it
    (_promote_from_cache); replacementCachePing probes the
    least-recently-seen entry of a cache-fed bucket;
  * bucket pings (bucketPingInterval): periodic liveness probe of the
    oldest-seen routing-table entry, via a bounded per-node ping table
    (KAD_PING kinds);
  * downlists (enableDownlists, Kademlia.cc:1543-1585): when a lookup's
    RPC target finally times out, the responder that reported it gets a
    KAD_DOWNLIST naming the dead node and pings it before evicting
    (downlist forwarding to siblings is not modeled);
  * S/Kademlia secure lookups via LookupConfig(verify_siblings=True)
    (common/lookup.py: candidate siblings are ping-verified before a
    lookup completes, IterativeLookup.cc:295-340);
  * R/Kademlia recursive routing via rcfg (common/route.py:
    recursiveRoutingHook equivalent — per-hop forwarding over k-bucket
    findNode with ACK/reroute; Kademlia.cc:1022, Heep ATNAC 2010);
  * isSiblingFor (Kademlia.cc:888): table smaller than numSiblings →
    true; key farther than the furthest sibling while full → false;
    otherwise membership of self in the numSiblings closest of
    siblings ∪ self;
  * findNode (Kademlia.cc:1101): top-R by XOR distance over
    self ∪ siblings ∪ all buckets (the reference walks best bucket →
    surrounding buckets → siblings; same result set);
  * join (Kademlia.cc:1027-1081): iterative lookup of the own key seeded
    from the bootstrap node, then bucket refresh;
  * periodic refresh: sibling-table refresh = lookup own key; bucket
    refresh = lookup a random key with the bucket's exact shared-prefix
    length, for buckets unused for minBucketRefreshInterval
    (handleBucketRefreshTimerExpired Kademlia.cc:1591) — repaired one
    lookup at a time off a dirty mask (bounded concurrency);
  * handleFailedNode (Kademlia.cc:979): drop from siblings; stale+1 in
    buckets, evict when staleCount > maxStaleCount, promote a
    replacement-cache candidate into the freed slot;
  * recycled slots: a table entry is a slot index, and a churn law that
    recycles slots brings a dead slot back under a FRESH key, where
    upstream's NodeHandle is address and key.  So a contact refreshes
    only the entry in the bucket its current key earns, findNode never
    offers an entry whose slot's current key earns another bucket, and
    a step that heard a message evicts such entries the failed node's
    way.  All table maintenance (this, the sibling merge) runs in steps
    that heard a message, never in an idle node: it reads other slots'
    current keys, and an idle node has to stay a fixed point of ``step``
    (``awake_set_exact``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from oversim_tpu import stats as stats_mod
from oversim_tpu.apps import base as app_base
from oversim_tpu.apps.kbrtest import KbrTestApp
from oversim_tpu.common import lookup as lk_mod
from oversim_tpu.common import malicious as mal_mod
from oversim_tpu.common import neighborcache as nc_mod
from oversim_tpu.common import route as rt_mod
from oversim_tpu.common import wire
from oversim_tpu.core import keys as K
from oversim_tpu.core.scopes import scope, scoped
from oversim_tpu.engine.logic import Outbox, select_tree

I32 = jnp.int32
I64 = jnp.int64
U32 = jnp.uint32
NS = 1_000_000_000
T_INF = jnp.int64(2**62)
NO_NODE = jnp.int32(-1)
UMAX = jnp.uint32(0xFFFFFFFF)

DEAD, JOINING, READY = 0, 1, 2

# lookup purposes
P_JOIN, P_REFRESH, P_APP, P_SIB = 1, 2, 3, 4


@dataclasses.dataclass(frozen=True)
class KademliaParams:
    """default.ini:185-200 + Kademlia.ned defaults."""

    k: int = 8                    # bucket size
    s: int = 8                    # sibling table size
    num_buckets: int = 32         # B — prefix-length clip (see module doc)
    max_stale: int = 0            # maxStaleCount
    join_delay: float = 10.0      # joinDelay (BaseOverlay)
    sibling_refresh: float = 1000.0   # minSiblingTableRefreshInterval
    bucket_refresh: float = 1000.0    # minBucketRefreshInterval
    redundant_nodes: int = 8      # lookupRedundantNodes
    rpc_timeout: float = 1.5
    # --- routingAdd depth knobs (Kademlia.h:86-107) ---
    replacement_cands: int = 0    # replacementCandidates per bucket
                                  # (0 = enableReplacementCache off)
    replacement_cache_ping: bool = False  # replacementCachePing: ping the
                                  # least-recently-seen bucket entry when a
                                  # candidate enters the cache
    bucket_ping_interval: float = 0.0  # bucketPingInterval (0 = off):
                                  # periodic ping of the oldest-seen
                                  # routing-table entry (NICE-style pings)
    enable_downlists: bool = False  # enableDownlists (Kademlia.cc:1567):
                                  # tell responders about dead nodes they
                                  # returned; receiver pings before evicting
    ping_slots: int = 4           # bounded concurrent maintenance pings
    adaptive_timeouts: bool = False  # optimizeTimeouts (BaseRpc.cc:197-
                                  # 205): RPC timeouts from the
                                  # NeighborCache RTT estimator
                                  # (getNodeTimeout, NeighborCache.cc:802)
                                  # fed by FindNode response RTTs


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KademliaState:
    state: jnp.ndarray      # [N] i32
    sib: jnp.ndarray        # [N, S] i32 sorted by xor distance from own key
    buckets: jnp.ndarray    # [N, B, K] i32
    b_seen: jnp.ndarray     # [N, B, K] i64 — lastSeen (0 = unverified)
    b_stale: jnp.ndarray    # [N, B, K] i32
    b_used: jnp.ndarray     # [N, B] i64 — bucket lastUsage
    refresh_dirty: jnp.ndarray  # [N, B] bool
    t_join: jnp.ndarray     # [N] i64
    t_refresh: jnp.ndarray  # [N] i64 — periodic bucket/sibling refresh tick
    sib_used: jnp.ndarray   # [N] i64 — sibling table lastUsage
    rc_nodes: jnp.ndarray   # [N, B, RC] i32 — replacement cache ring
    rc_pos: jnp.ndarray     # [N, B] i32 — its write cursor
    ping_dst: jnp.ndarray   # [N, Pp] i32 — in-flight maintenance pings
    ping_to: jnp.ndarray    # [N, Pp] i64 — their timeouts
    t_bping: jnp.ndarray    # [N] i64 — periodic bucket-ping timer
    rr: object              # rt_mod.RouteState — R/Kademlia recursive hook
    nc: object              # nc_mod.NcState — RTT cache (adaptive timeouts)
    lk: lk_mod.LookupState
    app: object                # [N, ...] tier-app state (apps/base.py)
    app_glob: object           # simulation-global app state (oracle maps)


class KademliaLogic:
    """Engine logic interface (see engine/logic.py docstring)."""

    def __init__(self, spec: K.KeySpec = K.DEFAULT_SPEC,
                 params: KademliaParams = KademliaParams(),
                 lcfg: lk_mod.LookupConfig | None = None,
                 app=None,
                 mparams: mal_mod.MaliciousParams = mal_mod.MaliciousParams(),
                 rcfg: rt_mod.RouteConfig | None = None):
        """``rcfg`` switches the app data path to R/Kademlia recursive
        routing (Kademlia::recursiveRoutingHook, Kademlia.cc:1022;
        B. Heep, R/Kademlia, ATNAC 2010) — per-hop forwarding over the
        same k-bucket findNode, with the route engine's ACK/reroute
        machinery; mode full/source selects the reply transport."""
        self.key_spec = spec
        self.p = params
        self.lcfg = lcfg or lk_mod.LookupConfig(merge=True)
        self.app = app or KbrTestApp()
        self.mp = mparams
        self.rcfg = rcfg
        # the app's RPC replies follow the call's routing mode
        if rcfg is not None and getattr(self.app, "rcfg", "no") is None:
            self.app.rcfg = rcfg
        self._pow2 = K.pow2_table(spec)

    # -- engine interface ---------------------------------------------------

    @property
    def awake_set_exact(self) -> bool:
        """The engine may skip this logic's idle nodes (engine/sim.py
        ``resolve_tick_impl``): a node with no inbox message, no due
        ``next_event`` and no churn is a fixed point of ``step``, for
        the overlay's own part and, where the app says the same of
        itself, for the app's.  Pinned against the dense sweep by
        tests/test_zz_sparse.py."""
        return bool(getattr(self.app, "awake_set_exact", False))

    def split(self, st: KademliaState):
        return dataclasses.replace(st, app_glob=None), st.app_glob

    def merge(self, node_part: KademliaState, glob):
        return dataclasses.replace(node_part, app_glob=glob)

    def post_step(self, ctx, st: KademliaState, events):
        app, glob = self.app.post_step(ctx, st.app, st.app_glob, events)
        return dataclasses.replace(st, app=app, app_glob=glob)

    def stat_spec(self) -> stats_mod.StatSpec:
        app = self.app.stat_spec()
        return stats_mod.StatSpec(
            scalars=tuple(app["scalars"]) + ("lookup_hops",),
            hists=tuple(app["hists"]),
            counters=tuple(app["counters"]) + (
                "kad_joins", "lookup_success", "lookup_failed",
                "route_dropped"),
        )

    def init(self, rng, n: int) -> KademliaState:
        p = self.p
        return KademliaState(
            state=jnp.zeros((n,), I32),
            sib=jnp.full((n, p.s), NO_NODE, I32),
            buckets=jnp.full((n, p.num_buckets, p.k), NO_NODE, I32),
            b_seen=jnp.zeros((n, p.num_buckets, p.k), I64),
            b_stale=jnp.zeros((n, p.num_buckets, p.k), I32),
            b_used=jnp.zeros((n, p.num_buckets), I64),
            refresh_dirty=jnp.zeros((n, p.num_buckets), bool),
            t_join=jnp.full((n,), T_INF, I64),
            t_refresh=jnp.full((n,), T_INF, I64),
            sib_used=jnp.zeros((n,), I64),
            rc_nodes=jnp.full((n, p.num_buckets, p.replacement_cands),
                              NO_NODE, I32),
            rc_pos=jnp.zeros((n, p.num_buckets), I32),
            ping_dst=jnp.full((n, p.ping_slots), NO_NODE, I32),
            ping_to=jnp.full((n, p.ping_slots), T_INF, I64),
            t_bping=jnp.full((n,), T_INF, I64),
            rr=jax.vmap(lambda _: rt_mod.init(
                self.rcfg or rt_mod.RouteConfig(), self.key_spec.lanes,
                16))(jnp.arange(n)),
            nc=nc_mod.init(n, nc_mod.NcParams(
                capacity=16 if p.adaptive_timeouts else 1)),
            lk=jax.vmap(lambda _: lk_mod.init(self.lcfg, self.key_spec.lanes))(
                jnp.arange(n)),
            app=self.app.init(n),
            app_glob=self.app.glob_init(rng),
        )

    def reset(self, st: KademliaState, clear, join, t_now, rng):
        n = st.state.shape[0]
        glob = st.app_glob
        st = dataclasses.replace(st, app_glob=None)
        fresh = dataclasses.replace(self.init(rng, n), app_glob=None)
        st = select_tree(clear, fresh, st)
        st = dataclasses.replace(st, app_glob=glob)
        jitter = (jax.random.uniform(rng, (n,)) * 0.1 * NS).astype(I64)
        return dataclasses.replace(
            st,
            state=jnp.where(join, JOINING, st.state),
            t_join=jnp.where(join, t_now + jitter, st.t_join))

    def ready_mask(self, st: KademliaState):
        return st.state == READY

    def next_event(self, st: KademliaState):
        joining = st.state == JOINING
        ready = st.state == READY
        t = jnp.where(joining, st.t_join, T_INF)
        t = jnp.minimum(t, jnp.where(ready, st.t_refresh, T_INF))
        t = jnp.minimum(t, jnp.where(ready, self.app.next_event(st.app),
                                     T_INF))
        t = jnp.minimum(t, jax.vmap(lk_mod.next_event)(st.lk))
        t = jnp.minimum(t, jnp.min(st.ping_to, axis=1))
        if self.p.bucket_ping_interval > 0:
            t = jnp.minimum(t, jnp.where(ready, st.t_bping, T_INF))
        if self.rcfg is not None:
            t = jnp.minimum(t, jax.vmap(rt_mod.next_event)(st.rr))
        return t

    # -- key-space helpers (single node slice) -------------------------------

    def _xor_to(self, ctx, slots, key):
        """[C] slots → [C, KL] xor distance of slot keys to ``key``
        (NO_NODE → max distance)."""
        ck = ctx.keys[jnp.maximum(slots, 0)]
        d = ck ^ jnp.broadcast_to(key, ck.shape)
        return jnp.where((slots == NO_NODE)[:, None], UMAX, d)

    def _bucket_index(self, me_key, other_key):
        """Shared-prefix bucket index, clipped to B-1."""
        pl = K.shared_prefix_length(me_key, other_key, self.key_spec)
        return jnp.clip(pl, 0, self.p.num_buckets - 1)

    def _sib_merge(self, ctx, me_key, node_idx, sib, cands, cand_ok):
        """Merge candidate slots into the sibling table.

        Returns (new_sib [S], displaced [S] i32): every node pushed out of
        a previously-full table (NO_NODE padded) — reference routingAdd
        moves verified ex-siblings into their buckets (Kademlia.cc:613
        area); a batch merge can displace several at once.
        """
        s = self.p.s
        c = jnp.concatenate([sib, jnp.where(cand_ok, cands, NO_NODE)])
        # dedupe (keep first occurrence — table entries win over candidates)
        bad = (c == NO_NODE) | (c == node_idx) | K.dup_mask(c)
        c = jnp.where(bad, NO_NODE, c)
        d = self._xor_to(ctx, c, me_key)
        (c_s,) = K.sort_by_distance(d, (c,), approx=True)[1]
        new_sib = c_s[:s]
        # displaced: previously a sibling, no longer one
        was = sib != NO_NODE
        still = jnp.any(sib[:, None] == new_sib[None, :], axis=1)
        disp = jnp.where(was & ~still, sib, NO_NODE)
        return new_sib, disp

    @scoped("kademlia.bucket_update")
    def _bucket_update_batch(self, ctx, st, me_key, cands, alive, now):
        """One-pass batched bucket update — the bucket half of routingAdd
        (Kademlia.cc:432-700) for ALL of a tick's C candidates at once.

        Per-candidate policy, equal to the sequential reference up to
        within-tick ordering: present+alive → lastSeen refresh / stale
        reset; absent → take a free slot, or (alive candidates only)
        evict a stale entry (staleCount > maxStaleCount, highest count
        first); unverified candidates (alive=False, nodes learned from
        FindNodeResponse payloads, Kademlia.cc:1412) fill free slots only
        and never displace.  Alive candidates get slot priority.  The
        whole policy is one candidate sort + one column sort + ONE
        32-bit scatter (the placed slot with its flag; ``b_seen`` and
        ``b_stale`` follow by mask) instead of C unrolled scatter chains
        (the round-2 tick graph was dominated by exactly those chains).

        ``cands`` must be deduplicated by the caller; NO_NODE = disabled.
        """
        p = self.p
        num_b, kk = p.num_buckets, p.k
        c_dim = cands.shape[0]
        en = cands != NO_NODE
        ck = ctx.keys[jnp.maximum(cands, 0)]
        bi = jnp.where(en, self._bucket_index(me_key, ck), num_b)

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
        b_s, a_s, idx_s = jax.lax.sort((k1, k2, k3), num_keys=3)  # analysis: allow(sort-call)
        rank = k3 - jnp.searchsorted(b_s, b_s, side="left").astype(I32)
        # per-bucket column order: free columns first, then evictable by
        # stale count descending, then untouchable
        free = buckets == NO_NODE
        evictable = ~free & (b_stale > p.max_stale)
        cls = jnp.where(free, 0, jnp.where(evictable, 1, 2))
        colkey = cls * (1 << 20) - jnp.where(
            evictable, jnp.minimum(b_stale, (1 << 19) - 1), 0)
        order = jnp.argsort(colkey, axis=1).astype(I32)       # [B, K]  # analysis: allow(sort-call)
        free_cnt = jnp.sum(free, axis=1, dtype=I32)           # [B]
        avail_cnt = free_cnt + jnp.sum(evictable, axis=1, dtype=I32)

        bi_c = jnp.minimum(b_s, num_b - 1)
        limit = jnp.where(a_s == 0, avail_cnt[bi_c], free_cnt[bi_c])
        okc = (b_s < num_b) & (rank < limit) & (rank < kk)
        col = order[bi_c, jnp.clip(rank, 0, kk - 1)]
        rows = jnp.where(okc, bi_c, num_b)
        vals = cands[idx_s]
        al_v = a_s == 0
        # ONE 32-bit scatter places the accepted candidates: the slot
        # (never NO_NODE here) with the unverified flag as its low bit,
        # -1 where nothing is placed.  ``b_seen`` and ``b_stale`` take
        # one of two constants a placed entry, so they are written by
        # mask, elementwise over [B, K]: on the chip a scatter into the
        # i64 ``b_seen`` cost 68 ns an update, the dropped ones too, and
        # was the tick's longest operation (PERF.md, PR 40).  (rows,
        # col) never repeats among accepted candidates (ranks within a
        # bucket are distinct, ``order`` permutes the columns).
        put = jnp.full((num_b, kk), -1, I32).at[rows, col].set(
            vals * 2 + a_s, mode="drop")
        placed = put >= 0
        st = dataclasses.replace(
            st,
            buckets=jnp.where(placed, put >> 1, buckets),
            b_seen=jnp.where(placed,
                             jnp.where((put & 1) == 0, now, jnp.int64(0)),
                             b_seen),
            b_stale=jnp.where(placed, 0, b_stale))

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

    @scoped("kademlia.routing_add")
    def _routing_add_batch(self, ctx, st, me_key, node_idx, cands, alive,
                           now, heard):
        """Batched routingAdd (Kademlia.cc:432) for a tick's whole
        candidate set: one sibling-table merge sort + one batched bucket
        pass.  ``alive`` marks verified contacts (message sources); false
        = unverified learned nodes.  An alive occurrence of a node wins
        over an unverified duplicate.  ``heard``: an inbox message came
        this tick (no table is touched without one)."""
        en = (cands != NO_NODE) & (cands != node_idx)
        cands = jnp.where(en, cands, NO_NODE)
        eq = cands[None, :] == cands[:, None]
        alive = jnp.any(eq & (alive & en)[None, :], axis=1) & en
        dup = K.dup_mask(cands)
        en = en & ~dup
        cands = jnp.where(en, cands, NO_NODE)

        new_sib, disp_vec = self._sib_merge(ctx, me_key, node_idx, st.sib,
                                            cands, en)
        # a node that heard from nobody keeps its table as it stands: the
        # merge sorts by the slots' CURRENT keys, and a recycled slot's
        # fresh key would otherwise re-sort the tables of nodes that no
        # message woke (an idle node is a fixed point of the step)
        new_sib = jnp.where(heard, new_sib, st.sib)
        disp_vec = jnp.where(heard, disp_vec, NO_NODE)
        st = dataclasses.replace(st, sib=new_sib)
        became_sib = jnp.any(cands[:, None] == new_sib[None, :], axis=1) & en
        # bucket candidates: displaced ex-siblings re-file as verified
        # contacts (reference routingAdd moves them into their buckets,
        # Kademlia.cc:613 area); non-sibling candidates keep their flag.
        # A displaced node that is ALSO a same-tick candidate must enter
        # the bucket once (the bucket pass requires caller-side dedup):
        # drop the disp_vec copy and promote the candidate to verified.
        in_disp = jnp.any(cands[:, None] == disp_vec[None, :], axis=1) & en
        disp_vec = jnp.where(
            jnp.any(disp_vec[:, None] == jnp.where(en, cands, NO_NODE)[None, :],
                    axis=1), NO_NODE, disp_vec)
        bc = jnp.concatenate([disp_vec,
                              jnp.where(became_sib, NO_NODE, cands)])
        ba = jnp.concatenate([jnp.ones(disp_vec.shape, bool),
                              alive | in_disp])
        st, rc_ping = self._bucket_update_batch(ctx, st, me_key, bc, ba,
                                                now)
        return st, rc_ping

    def _promote_from_cache(self, st, evict):
        """Replacement-cache promotion: for each bucket that just lost an
        entry, move one cached candidate into the freed slot (reference
        routingTimeout pulls from the replacement cache).  ``evict``
        [B, K] marks the slots freed this pass."""
        rc = self.p.replacement_cands
        if not rc:
            return st
        have_rc = st.rc_nodes != NO_NODE                       # [B, RC]
        can = jnp.any(evict, axis=1) & jnp.any(have_rc, axis=1)  # [B]
        col_rc = jnp.argmax(have_rc, axis=1)                   # [B]
        col_k = jnp.argmax(evict, axis=1)                      # [B]
        num_b = evict.shape[0]
        promoted = st.rc_nodes[jnp.arange(num_b), col_rc]
        # the ring is not deduplicated: a cached node may have re-entered
        # its bucket (or hold a second ring copy) since it was cached —
        # promotion of an already-present node would break the one-slot-
        # per-node bucket invariant, so such copies are only purged here
        already = jnp.any(st.buckets == promoted[:, None], axis=1)
        rows_any = jnp.where(can, jnp.arange(num_b, dtype=I32), num_b)
        rows = jnp.where(can & ~already,
                         jnp.arange(num_b, dtype=I32), num_b)
        return dataclasses.replace(
            st,
            buckets=st.buckets.at[rows, col_k].set(promoted, mode="drop"),
            b_seen=st.b_seen.at[rows, col_k].set(0, mode="drop"),
            b_stale=st.b_stale.at[rows, col_k].set(0, mode="drop"),
            rc_nodes=st.rc_nodes.at[rows_any, col_rc].set(NO_NODE,
                                                          mode="drop"))

    def _find_node(self, ctx, st, me_key, node_idx, key, rmax):
        """Top-R closest known nodes by XOR distance (Kademlia.cc:1101).

        Returns ([rmax] i32 slots NO_NODE-padded, is_sibling bool)."""
        out, is_sib, _ = self._find_node_batch(ctx, st, me_key, node_idx,
                                               key[None], rmax)
        return out[0], is_sib[0]

    @scoped("kademlia.find_node")
    def _find_node_batch(self, ctx, st, me_key, node_idx, keys, rmax):
        """Batched findNode for T target keys at once ([T, KL] → ([T, rmax]
        slots, [T] is_sibling, [B, K] stale)) over ONE shared candidate
        set per tick instead of one per unrolled call site.

        findNode: top-R by XOR distance over self ∪ siblings ∪ all buckets
        (Kademlia.cc:1101 walks best bucket → surrounding buckets →
        siblings; same result set).  isSiblingFor: Kademlia.cc:888.

        Of the 1 + s + B·k candidates (265) it keeps
        ``lookupRedundantNodes`` (8), so the closest are taken by that
        many argmin passes (``K.closest_k_by_distance``: the stable
        sort's prefix, ties and padding included) and nothing is sorted:
        ordering all 265 cost ten times the passes on the chip, for
        every key of every stepped lane (PERF.md section 5, "Kademlia's
        closest k").  The module's other sorts stay sorts,
        because they keep the whole order or the displaced set:
        ``_sib_merge``'s, ``_handle_failed``'s re-sort of the sibling
        table, ``_bucket_update_batch``'s, the local lookup seed's.

        ``stale`` marks the bucket entries whose slot's CURRENT key
        earns another bucket than the one they sit in: entries of a slot
        that died and was recycled under a fresh key, another node now.
        They are read off the key gather this call makes anyway and are
        never among its candidates; ``step`` hands them to
        ``_handle_failed``.  (The step's calls see the same tables, so
        the compiler shares the gather, the masks and this between them:
        nothing may touch the tables in between.)"""
        p = self.p
        t_dim = keys.shape[0]
        # mask bucket entries that were since promoted into the sibling
        # table (routingAdd can adopt a bucket resident without purging
        # its bucket slot) so the result set never repeats a node
        held = st.buckets.reshape(-1)
        drop = jnp.any(held[:, None] == st.sib[None, :], axis=1)
        cands = jnp.concatenate([node_idx[None], st.sib, held])    # [C]
        ck = ctx.keys[jnp.maximum(cands, 0)]                       # [C, KL]
        row = jnp.repeat(jnp.arange(p.num_buckets, dtype=I32), p.k)
        stale = (held != NO_NODE) & (
            self._bucket_index(me_key, ck[1 + p.s:]) != row)
        drop = drop | stale
        # (a dropped entry's key is never read: its distance is UMAX below)
        cands = jnp.concatenate(
            [cands[:1 + p.s], jnp.where(drop, NO_NODE, held)])
        d = ck[None, :, :] ^ keys[:, None, :]                      # [T, C, KL]
        d = jnp.where((cands == NO_NODE)[None, :, None], UMAX, d)
        r = min(p.redundant_nodes, rmax)
        (near,) = K.closest_k_by_distance(
            d, (jnp.broadcast_to(cands, (t_dim, cands.shape[0])),), r,
            approx=True)
        ready = st.state == READY
        out = jnp.pad(jnp.where(ready, near, NO_NODE), ((0, 0), (0, rmax - r)),
                      constant_values=NO_NODE)

        # isSiblingFor(self, key, numSiblings=1) (Kademlia.cc:888)
        n_sib = jnp.sum((st.sib != NO_NODE).astype(I32))
        full = n_sib >= p.s
        d_me = me_key[None, :] ^ keys                              # [T, KL]
        d_far = self._xor_to(ctx, st.sib[-1:], me_key)             # [1, KL]
        not_ours = full & K.gt(d_me, jnp.broadcast_to(d_far, d_me.shape))
        sk = ctx.keys[jnp.maximum(st.sib, 0)]                      # [S, KL]
        d_sib_key = sk[None, :, :] ^ keys[:, None, :]              # [T, S, KL]
        d_sib_key = jnp.where((st.sib == NO_NODE)[None, :, None], UMAX,
                              d_sib_key)
        closer_sib = jnp.any(
            K.lt(d_sib_key, jnp.broadcast_to(d_me[:, None, :],
                                             d_sib_key.shape)), axis=1)
        is_sib = ready & (n_sib < 1) | (ready & ~not_ours & ~closer_sib)
        return out, is_sib, stale.reshape(p.num_buckets, p.k)

    @scoped("kademlia.failed")
    def _handle_failed(self, ctx, st, me_key, node_idx, failed, also=None):
        """handleFailedNode (Kademlia.cc:979): drop sibling / stale+evict.

        ``failed`` may be a scalar or a [K] batch — the whole tick's
        failure list is folded in one sort + one bucket sweep (each
        occurrence of a node in the batch counts one stale strike, like
        the reference's one call per RPC timeout).  ``also`` [B, K] marks
        bucket entries to evict whatever their count: a recycled slot's
        entries in the bucket its old key earned."""
        failed = jnp.atleast_1d(jnp.asarray(failed, I32))
        en = jnp.any(failed != NO_NODE)
        # sibling drop + re-sort
        hit = jnp.any(st.sib[:, None] == failed[None, :], axis=-1) & (
            st.sib != NO_NODE)
        sib_masked = jnp.where(hit, NO_NODE, st.sib)
        d = self._xor_to(ctx, sib_masked, me_key)
        (sib_s,) = K.sort_by_distance(d, (sib_masked,), approx=True)[1]
        st = dataclasses.replace(
            st, sib=jnp.where(en, sib_s, st.sib))
        # bucket stale increment (one strike per batch occurrence)
        strikes = jnp.sum(
            st.buckets[..., None] == failed[None, None, :], axis=-1,
            dtype=I32)
        strikes = jnp.where(st.buckets != NO_NODE, strikes, 0)
        stale = st.b_stale + strikes
        evict = (strikes > 0) & (stale > self.p.max_stale)
        if also is not None:
            evict = evict | also
        st = dataclasses.replace(
            st,
            buckets=jnp.where(evict, NO_NODE, st.buckets),
            b_stale=jnp.where(evict, 0, stale),
            b_seen=jnp.where(evict, 0, st.b_seen))
        return self._promote_from_cache(st, evict)

    def _become_ready(self, ctx, st, en, now, rng):
        p = self.p
        t_bping = st.t_bping
        if p.bucket_ping_interval > 0:
            t_bping = jnp.where(
                en, now + jnp.int64(int(p.bucket_ping_interval * NS)),
                t_bping)
        return dataclasses.replace(
            st,
            state=jnp.where(en, READY, st.state),
            t_join=jnp.where(en, T_INF, st.t_join),
            # immediate bucket refresh pass after join (Kademlia.cc:1043)
            t_refresh=jnp.where(en, now, st.t_refresh),
            # ...and an immediate sibling-table refresh (own-key lookup)
            # so a partially seeded table converges to the true closest
            # set right away instead of after minSiblingTableRefresh
            sib_used=jnp.where(
                en, now - jnp.int64(int(p.sibling_refresh * NS)) - 1,
                st.sib_used),
            t_bping=t_bping,
            app=self.app.on_ready(st.app, en, now, rng))

    # -- the per-node step ---------------------------------------------------

    def step(self, ctx, st, msgs, rng, node_idx, *, outbox_slots, rmax):
        p, lcfg, spec = self.p, self.lcfg, self.key_spec
        ob = Outbox(outbox_slots, spec.lanes, rmax)
        me_key = ctx.keys[node_idx]
        rngs = jax.random.split(rng, 8)
        t0 = ctx.t_start
        t_end = ctx.t_end
        r_in = msgs.valid.shape[0]

        def metric_fn(cand_slots, target):
            return self._xor_to(ctx, cand_slots, target)

        ev = app_base.AppEvents()
        joins_cnt = jnp.int32(0)
        anyfail_cnt = jnp.int32(0)
        lksucc_cnt = jnp.int32(0)
        old_sib = st.sib                     # update() delta base

        # --------------------------------------------- inbox (batched) -----
        # All R inbox slots are consumed in ONE pass per handler class —
        # a masked [R]-batch instead of R unrolled handler chains (the
        # round-2 graph was op-issue-bound on exactly that unrolling).
        # Within-window ordering across the slots is already relaxed by
        # the engine; the batch passes commute the same way.
        v_r = msgs.valid
        t_del_r = msgs.t_deliver

        # FindNodeResponses → lookup engine (one batched pass)
        en_res = v_r & (msgs.kind == wire.FINDNODE_RES)
        if p.adaptive_timeouts:
            # RTT samples from this tick's responses feed the
            # NeighborCache estimator BEFORE the pendings are cleared
            # (NeighborCache::updateNode on every RPC response)
            rtt_src, rtt_s, rtt_ok = lk_mod.response_rtts(
                st.lk, dataclasses.replace(msgs, valid=en_res))
            st = dataclasses.replace(st, nc=nc_mod.feed_response_rtts(
                st.nc, rtt_src, rtt_s, t_del_r, rtt_ok))
        st = dataclasses.replace(st, lk=lk_mod.on_responses(
            st.lk, dataclasses.replace(msgs, valid=en_res), metric_fn, lcfg))

        # batched routingAdd (Kademlia.cc:1027/1419): every message source
        # as a verified contact + every FindNodeResponse payload node as
        # an unverified learn (Kademlia.cc:1412)
        learned = jnp.where(en_res[:, None], msgs.nodes[:, :lcfg.frontier],
                            NO_NODE)                            # [R, F]
        add_cands = jnp.concatenate(
            [jnp.where(v_r, msgs.src, NO_NODE), learned.reshape(-1)])
        add_alive = jnp.concatenate(
            [jnp.ones((r_in,), bool),
             jnp.zeros((learned.size,), bool)])
        now_add = jnp.max(jnp.where(v_r, t_del_r, 0))
        heard = now_add > 0         # an inbox message came (none is due at 0)
        st, rc_ping = self._routing_add_batch(
            ctx, st, me_key, node_idx, add_cands, add_alive, now_add, heard)

        # batched findNode + sibling flags for every inbox key: consumed
        # by the FindNodeCall responder below AND (R/Kademlia) by the
        # recursive route pre-pass as its forwarding candidates
        res_b, sib_b, stale_b = self._find_node_batch(
            ctx, st, me_key, node_idx, msgs.key, rmax)

        if self.rcfg is not None:
            # R/Kademlia recursive hook (Kademlia::recursiveRoutingHook,
            # Kademlia.cc:1022; generic loop BaseOverlay.cc:1441-1581):
            # ACK the previous hop, forward or decapsulate — the same
            # pre-pass chord.py runs, driven by k-bucket findNode results
            rcfg = self.rcfg
            st = dataclasses.replace(st, rr=rt_mod.on_acks(
                st.rr, dataclasses.replace(
                    msgs,
                    valid=v_r & (msgs.kind == wire.KBR_ROUTE_ACK))))
            en_sro = v_r & (msgs.kind == wire.KBR_SROUTE)
            deliver_sr = rt_mod.sroute_step(ob, msgs)
            msgs = dataclasses.replace(
                msgs,
                kind=jnp.where(deliver_sr, msgs.d, msgs.kind),
                src=jnp.where(deliver_sr, msgs.c, msgs.src),
                valid=v_r & (~en_sro | deliver_sr))
            v_r = msgs.valid
            en_rt = v_r & (msgs.kind == wire.KBR_ROUTE) & (
                st.state == READY)
            ob.send(en_rt & (msgs.nonce > 0), t_del_r, msgs.src,
                    wire.KBR_ROUTE_ACK, nonce=msgs.nonce,
                    size_b=wire.BASE_CALL_B)
            deliver_rt = en_rt & sib_b
            nxt_v, found_v = jax.vmap(
                rt_mod.pick_next_hop, in_axes=(0, 0, 0, 0, None, 0))(
                res_b, msgs.nodes, msgs.src, msgs.nodes[:, 0], node_idx,
                sib_b)
            fwd = en_rt & ~sib_b & found_v & (msgs.hops < rcfg.hop_max)
            if hasattr(self.app, "forward"):
                # Common API forward() veto (BaseApp.h:214)
                fwd = fwd & ~self.app.forward(st.app, msgs, ctx)
            visited2 = rt_mod.append_visited(msgs.nodes, node_idx, fwd)
            st = dataclasses.replace(st, rr=rt_mod.forward_batch(
                st.rr, ob, fwd, t_del_r, nxt_v, key=msgs.key, inner=msgs.d,
                a=msgs.a, b=msgs.b, c=msgs.c, hops=msgs.hops + 1,
                stamp=msgs.stamp, size_b=msgs.size_b - rcfg.overhead_b,
                visited=visited2, cfg=rcfg))
            routedrop_cnt = jnp.sum((en_rt & ~sib_b & ~fwd).astype(I32))
            msgs = dataclasses.replace(
                msgs,
                kind=jnp.where(deliver_rt, msgs.d, msgs.kind),
                src=jnp.where(deliver_rt, msgs.nodes[:, 0], msgs.src),
                valid=v_r & (~en_rt | deliver_rt))
            v_r = msgs.valid
        else:
            routedrop_cnt = jnp.int32(0)

        # FindNodeCalls → responder
        en_call = v_r & (msgs.kind == wire.FINDNODE_CALL)
        # byzantine switches (common/malicious.py; statically no-op by
        # default).  Only the wire copy is attacked; the honest ``sib_b``
        # feeds the app deliver check below (wrong-node detection)
        if self.mp.active:
            res_atk, sib_atk, respond = jax.vmap(
                lambda rr, ss, rg: mal_mod.attack_findnode(
                    ctx, self.mp, node_idx, rr, ss, rg))(
                res_b, sib_b, jax.random.split(rngs[7], r_in))
        else:
            res_atk, sib_atk, respond = res_b, sib_b, jnp.ones((r_in,), bool)
        ob.send(en_call & respond, t_del_r, msgs.src, wire.FINDNODE_RES,
                key=msgs.key, a=msgs.a, b=msgs.b, c=sib_atk.astype(I32),
                nodes=res_atk,
                size_b=wire.findnode_res_b(p.redundant_nodes))

        with scope("kademlia.pings"):
            # ping (generic liveness; b echoes the caller's generation so
            # verification pongs can be stale-guarded, lookup.on_pongs)
            ob.send(v_r & (msgs.kind == wire.PING_CALL), t_del_r, msgs.src,
                    wire.PING_RES, a=msgs.a, b=msgs.b, size_b=wire.BASE_CALL_B)

            # S/Kademlia sibling-verification pongs (lookup engine pings its
            # staged candidate, IterativeLookup.cc:295-340)
            if lcfg.verify_siblings:
                st = dataclasses.replace(st, lk=lk_mod.on_pongs(
                    st.lk, dataclasses.replace(
                        msgs, valid=v_r & (msgs.kind == wire.PING_RES)), lcfg))

            # maintenance pings (bucket pings / replacement-cache pings /
            # downlist verification, Kademlia.h bucketPingInterval &
            # replacementCachePing): KAD_PING kinds keep their pongs separate
            # from the lookup engine's verification pings
            ob.send(v_r & (msgs.kind == wire.KAD_PING_CALL), t_del_r, msgs.src,
                    wire.KAD_PING_RES, a=msgs.a, size_b=wire.BASE_CALL_B)
            en_kpr = v_r & (msgs.kind == wire.KAD_PING_RES)
            pong_hit = jnp.any(
                st.ping_dst[:, None] == jnp.where(en_kpr, msgs.src,
                                                  NO_NODE)[None, :], axis=1)
            st = dataclasses.replace(
                st,
                ping_dst=jnp.where(pong_hit, NO_NODE, st.ping_dst),
                ping_to=jnp.where(pong_hit, T_INF, st.ping_to))

            # downlist receive (KademliaDownlistMessage, Kademlia.cc:1305-
            # 1319): ping each reported-dead node before believing it —
            # queued into the bounded ping table below
            dl_cands = jnp.where(
                v_r & (msgs.kind == wire.KAD_DOWNLIST), msgs.a, NO_NODE)

        # app-owned message kinds (Common API deliver path)
        if hasattr(self.app, "on_msgs"):
            st = dataclasses.replace(st, app=self.app.on_msgs(
                st.app, msgs, ctx, ob, ev, sib_b))
        else:
            for r in range(r_in):
                st = dataclasses.replace(st, app=self.app.on_msg(
                    st.app, msgs.slot(r), ctx, ob, ev, sib_b[r]))

        # ------------------------------------------------------- timers ----
        with scope("kademlia.join"):
            # join (joinOverlay: lookup own key via bootstrap,
            # Kademlia.cc:1027-1081)
            en_j = (st.state == JOINING) & (st.t_join < t_end)
            now_j = jnp.maximum(st.t_join, t0)
            boot = ctx.sample_ready(rngs[1], node_idx)
            no_join_lk = ~jnp.any(st.lk.active & (st.lk.purpose == P_JOIN))
            alone_start = en_j & (boot == NO_NODE)
            st = self._become_ready(ctx, st, alone_start, now_j, rngs[2])
            joins_cnt += alone_start.astype(I32)
            slot, have = lk_mod.free_slot(st.lk)
            start_join = en_j & (boot != NO_NODE) & no_join_lk & have
            seed = jnp.full((lcfg.frontier,), NO_NODE, I32).at[0].set(boot)
            st = dataclasses.replace(st, lk=lk_mod.start(
                st.lk, start_join, slot, P_JOIN, 0, me_key, seed, now_j, lcfg))
            st = dataclasses.replace(st, t_join=jnp.where(
                en_j & ~alone_start,
                now_j + jnp.int64(int(p.join_delay * NS)), st.t_join))

        with scope("kademlia.refresh"):
            # periodic refresh tick: mark stale buckets dirty + sibling refresh
            en_r = (st.state == READY) & (st.t_refresh < t_end)
            now_r = jnp.maximum(st.t_refresh, t0)
            refresh_ns = jnp.int64(int(p.bucket_refresh * NS))
            # only buckets for prefixes we can actually populate: any bucket
            # whose index <= index of the furthest sibling (reference refreshes
            # buckets up to routingBucketIndex(siblingTable->back()),
            # Kademlia.cc:1591 area)
            far_sib = st.sib[-1]
            has_sib = far_sib != NO_NODE
            max_bi = jnp.where(
                has_sib,
                self._bucket_index(me_key, ctx.keys[jnp.maximum(far_sib, 0)]),
                -1)
            bi_range = jnp.arange(p.num_buckets, dtype=I32)
            stale_bucket = st.b_used + refresh_ns < now_r
            mark = en_r & (bi_range <= max_bi) & stale_bucket
            st = dataclasses.replace(
                st,
                refresh_dirty=st.refresh_dirty | mark,
                t_refresh=jnp.where(en_r, now_r + refresh_ns, st.t_refresh))
            # sibling-table refresh timing: lookup own key when unused for the
            # interval (start fires below, after the batched findNode)
            sib_stale = en_r & (st.sib_used + jnp.int64(
                int(p.sibling_refresh * NS)) < now_r)

        with scope("kademlia.pings"):
            # ----------------------------------------- maintenance pings ----
            # ping timeouts: unresponsive pinged nodes are failures
            ping_exp = (st.ping_dst != NO_NODE) & (st.ping_to < t_end)
            ping_failed = jnp.where(ping_exp, st.ping_dst, NO_NODE)   # [Pp]
            st = dataclasses.replace(
                st,
                ping_dst=jnp.where(ping_exp, NO_NODE, st.ping_dst),
                ping_to=jnp.where(ping_exp, T_INF, st.ping_to))

            # bucket-ping timer (bucketPingInterval): probe the oldest-seen
            # routing-table entry so silent deaths surface between refreshes
            if p.bucket_ping_interval > 0:
                en_bp = (st.state == READY) & (st.t_bping < t_end)
                now_bp = jnp.maximum(st.t_bping, t0)
                seen_all = jnp.where(st.buckets != NO_NODE, st.b_seen, T_INF)
                flat_bp = jnp.argmin(seen_all.reshape(-1))
                bp_cand = jnp.where(en_bp, st.buckets.reshape(-1)[flat_bp],
                                    NO_NODE)
                st = dataclasses.replace(st, t_bping=jnp.where(
                    en_bp,
                    now_bp + jnp.int64(int(p.bucket_ping_interval * NS)),
                    st.t_bping))
            else:
                bp_cand = NO_NODE

            # queue this tick's ping candidates (downlist verifications, the
            # replacement-cache ping, the bucket ping) into free ping slots —
            # the same rank trick as route.forward_batch; overflow lanes drop
            # (retried next downlist/interval)
            ping_cands = jnp.concatenate(
                [dl_cands,
                 jnp.stack([jnp.asarray(rc_ping, I32),
                            jnp.asarray(bp_cand, I32)])])            # [R+2]
            # skip nodes already being pinged
            dup_p = jnp.any(
                ping_cands[:, None] == st.ping_dst[None, :], axis=1)
            ping_cands = jnp.where(dup_p | K.dup_mask(ping_cands), NO_NODE,
                                   ping_cands)
            en_p = ping_cands != NO_NODE
            lane_rank = jnp.cumsum(en_p.astype(I32)) - 1
            free_p = st.ping_dst == NO_NODE
            slot_rank = jnp.cumsum(free_p.astype(I32)) - 1
            n_free_p = jnp.sum(free_p.astype(I32))
            pp = p.ping_slots
            slot_of_rank = jnp.full((pp,), pp, I32).at[
                jnp.where(free_p, slot_rank, pp)].set(
                jnp.arange(pp, dtype=I32), mode="drop")
            lane_slot = jnp.where(
                en_p & (lane_rank < n_free_p),
                slot_of_rank[jnp.clip(lane_rank, 0, pp - 1)], pp)
            sent_p = lane_slot < pp
            ob.send(sent_p, t0, ping_cands, wire.KAD_PING_CALL,
                    size_b=wire.BASE_CALL_B)
            # a ping takes a FREE slot and every ping of the tick the same
            # timeout, so the i64 ``ping_to`` is written by mask, not by a
            # second (64-bit) scatter: the slots that were free and hold a
            # node now
            ping_dst = st.ping_dst.at[lane_slot].set(ping_cands, mode="drop")
            st = dataclasses.replace(
                st,
                ping_dst=ping_dst,
                ping_to=jnp.where(
                    free_p & (ping_dst != NO_NODE),
                    t0 + jnp.int64(int(p.rpc_timeout * NS)), st.ping_to))

        # app timer
        # graceful-leave: hand app data to the closest sibling and stop
        # firing app tests during the grace window (apps/base.py on_leave)
        st = dataclasses.replace(st, app=app_base.leave_protocol(
            self.app, st.app, ctx, ob, ev, t0, node_idx, st.sib[0],
            st.state == READY))
        en_a = (st.state == READY) & (
            self.app.next_event(st.app) < t_end)
        now_a = jnp.maximum(self.app.next_event(st.app), t0)
        app, req = self.app.on_timer(st.app, en_a, ctx, now_a, rngs[3], ev, node_idx)
        st = dataclasses.replace(st, app=app)

        with scope("kademlia.refresh"):
            # bucket-refresh target (pump below): random key with
            # sharedPrefixLength(me, target) == bi —
            # delta = 2^(bits-1-bi) | (rand & (2^(bits-1-bi) - 1)); target=me^delta
            bi_ref = jnp.argmax(st.refresh_dirty).astype(I32)
            jbit = jnp.clip(spec.bits - 1 - bi_ref, 0, spec.bits - 1)
            top = self._pow2[jbit]
            mask = K.sub(top, K.from_int(1, spec), spec)
            rnd = K.random_keys(rngs[5], (), spec)
            target_ref = me_key ^ (top | (rnd & mask))

        # ONE batched findNode for every timer consumer: sibling refresh
        # (own key), the app lookup seed, and the bucket-refresh seed
        seeds3, sib3, _ = self._find_node_batch(
            ctx, st, me_key, node_idx,
            jnp.stack([me_key, req.key, target_ref]), rmax)
        res0, seed_a, seed_r = seeds3[0], seeds3[1], seeds3[2]
        sib_a = sib3[1]

        with scope("kademlia.refresh"):
            # sibling refresh start
            no_sib_lk = ~jnp.any(st.lk.active & (st.lk.purpose == P_SIB))
            slot, have = lk_mod.free_slot(st.lk)
            start_sib = sib_stale & no_sib_lk & have & (res0[0] != NO_NODE)
            st = dataclasses.replace(st, lk=lk_mod.start(
                st.lk, start_sib, slot, P_SIB, 0, me_key,
                res0[:lcfg.frontier], now_r, lcfg))
            st = dataclasses.replace(
                st, sib_used=jnp.where(start_sib, now_r, st.sib_used))
        # local responsibility → full sibling set (top-s of self ∪
        # siblings by XOR distance to the key), matching the responder-side
        # FINDNODE_RES payload so numReplica consumers get the replica set
        local = req.want & sib_a
        loc_cands = jnp.concatenate([node_idx[None], st.sib])
        loc_d = self._xor_to(ctx, loc_cands, req.key)
        (loc_s,) = K.sort_by_distance(loc_d, (loc_cands,), approx=True)[1]
        res_local = loc_s[:lcfg.frontier]
        if res_local.shape[0] < lcfg.frontier:
            res_local = jnp.concatenate([res_local, jnp.full(
                (lcfg.frontier - res_local.shape[0],), NO_NODE, I32)])
        slot, have = lk_mod.free_slot(st.lk)
        if self.rcfg is not None and hasattr(self.app, "route_policy"):
            # R/Kademlia data path: payloads the app declares routable
            # are forwarded hop-by-hop (recursiveRoutingHook at the
            # originator); the rest keep the iterative engine
            routable, inner_a, is_rpc = self.app.route_policy(req.tag)
            route_fire = (req.want & ~sib_a & routable
                          & (seed_a[0] != NO_NODE))
            vis0 = jnp.full((rmax,), NO_NODE, I32).at[0].set(node_idx)
            st = dataclasses.replace(st, rr=rt_mod.forward(
                st.rr, ob, route_fire, now_a, seed_a[0], key=req.key,
                inner=inner_a, a=req.tag, b=jnp.int32(0),
                c=ctx.measuring.astype(I32), hops=jnp.int32(1),
                stamp=now_a, size_b=jnp.int32(100), visited=vis0,
                cfg=self.rcfg))
            if hasattr(self.app, "on_route_fired"):
                st = dataclasses.replace(st, app=self.app.on_route_fired(
                    st.app, route_fire & is_rpc, now_a, req.tag))
            start_app = (req.want & ~sib_a & ~routable & have
                         & (seed_a[0] != NO_NODE))
        else:
            route_fire = jnp.bool_(False)
            start_app = req.want & ~sib_a & have & (seed_a[0] != NO_NODE)
        insta_fail = req.want & ~sib_a & ~start_app & ~route_fire
        st = dataclasses.replace(st, app=self.app.on_lookup_done(
            st.app, app_base.LookupDone(
                en=local | insta_fail, success=local, tag=req.tag,
                target=req.key,
                results=jnp.where(local, res_local, NO_NODE),
                hops=jnp.int32(0), t0=now_a),
            ctx, ob, ev, now_a, node_idx))
        st = dataclasses.replace(st, lk=lk_mod.start(
            st.lk, start_app, slot, P_APP, req.tag, req.key,
            seed_a[:lcfg.frontier], now_a, lcfg))

        # ------------------------------------------------ lookup timeouts --
        new_lk, failed_nodes, failed_prov = lk_mod.on_timeouts(
            st.lk, t_end, t0, lcfg)
        st = dataclasses.replace(st, lk=new_lk)
        # downlists (Kademlia.cc:1543-1585): tell each responder which of
        # the nodes it returned turned out dead; the receiver pings them
        # (KAD_DOWNLIST handler above) before evicting
        if p.enable_downlists:
            en_dl = (failed_nodes != NO_NODE) & (failed_prov != NO_NODE)
            ob.send(en_dl, t0, failed_prov, wire.KAD_DOWNLIST,
                    a=failed_nodes,
                    size_b=wire.BASE_CALL_B + wire.NODEHANDLE_B)
        # one batched repair for the tick's failures: lookup RPC
        # timeouts + maintenance-ping timeouts
        # ... and a recycled slot's entries in the bucket its old key
        # earned, a dead node's: they go the failed node's way whatever
        # their count, in a step that heard a message (never in an idle
        # node, which is a fixed point of the step)
        st = self._handle_failed(
            ctx, st, me_key, node_idx,
            jnp.concatenate([failed_nodes, ping_failed]),
            also=stale_b & heard)
        # R/Kademlia: reroute parked route messages around failed hops
        # (the failed hop was just dropped from the tables; a node that
        # became responsible meanwhile self-delivers)
        if self.rcfg is not None:
            new_rr, rt_failed, rt_retry = rt_mod.on_timeouts(
                st.rr, t_end, self.rcfg)
            st = dataclasses.replace(st, rr=new_rr)
            st = self._handle_failed(ctx, st, me_key, node_idx, rt_failed)
            nxt_q, sib_q, _ = self._find_node_batch(
                ctx, st, me_key, node_idx, st.rr.key, rmax)
            nxt_q2, found_q = jax.vmap(
                rt_mod.pick_next_hop, in_axes=(0, 0, 0, 0, None, 0))(
                nxt_q, st.rr.visited, rt_failed,
                st.rr.visited[:, 0], node_idx, sib_q)
            nxt_fin = jnp.where(sib_q, node_idx, nxt_q2)
            ok_q = rt_retry & (sib_q | found_q)
            st = dataclasses.replace(st, rr=rt_mod.reforward_batch(
                st.rr, ob, ok_q, t0, nxt_fin, self.rcfg))
            give_up = rt_retry & ~ok_q
            st = dataclasses.replace(st, rr=rt_mod.drop_slots(
                st.rr, give_up))
            routedrop_cnt += jnp.sum(give_up.astype(I32))

        # ------------------------------------------------- completions -----
        new_lk, comp = lk_mod.take_completions(st.lk, t_end)
        st = dataclasses.replace(st, lk=new_lk)
        taken = comp["taken"]                                   # [L]
        suc_l = comp["success"] & (comp["result"] != NO_NODE)
        pur_l = comp["purpose"]
        comp_hops_ev = (comp["hops"].astype(jnp.float32),
                        taken & comp["success"])
        lksucc_cnt += jnp.sum((taken & suc_l).astype(I32))
        anyfail_cnt += jnp.sum((taken & ~suc_l).astype(I32))

        with scope("kademlia.join"):
            # join completion → READY.  The reference becomes READY whenever
            # the sibling table is non-empty (lookupFinished,
            # Kademlia.cc:1543) — but its join lookup is exhaustive enough
            # that the table then holds the true closest set.  A node going
            # READY off a 1-2 entry table claims siblinghood for keys it
            # does not own (isSiblingFor: not-full tables accept broadly,
            # Kademlia.cc:888) and black-holes DHT traffic, so the
            # vectorized build requires a SUCCESSFUL own-key lookup or a
            # half-full sibling table before serving.
            # At most one join lookup exists per node (no_join_lk gate above).
            enj = taken & (pur_l == P_JOIN)
            any_j = jnp.any(enj)
            n_sib_j = jnp.sum((st.sib != NO_NODE).astype(I32))
            got = any_j & (jnp.any(enj & suc_l)
                           | (n_sib_j >= min(p.s, 4)))
            joins_cnt += got.astype(I32)
            st = self._become_ready(ctx, st, got, t0, rngs[4])
            # join failed with nothing learned → retry via t_join
            st = dataclasses.replace(st, t_join=jnp.where(
                any_j & ~got, t0 + jnp.int64(int(p.join_delay * NS)),
                st.t_join))

        with scope("kademlia.refresh"):
            # bucket refresh completions → clear dirty bits (one scatter)
            enr_l = taken & (pur_l == P_REFRESH)
            rows_r = jnp.where(enr_l, jnp.clip(comp["aux"], 0,
                                               p.num_buckets - 1),
                               p.num_buckets)
            st = dataclasses.replace(
                st,
                refresh_dirty=st.refresh_dirty.at[rows_r].set(
                    False, mode="drop"),
                b_used=st.b_used.at[rows_r].set(t0, mode="drop"))

        # app lookups → app completion hook (batched when the app
        # supports it; per-slot fold otherwise)
        ena_l = taken & (pur_l == P_APP)
        if hasattr(self.app, "on_lookup_done_batch"):
            st = dataclasses.replace(st, app=self.app.on_lookup_done_batch(
                st.app, app_base.LookupDone(
                    en=ena_l, success=ena_l & suc_l, tag=comp["aux"],
                    target=comp["target"], results=comp["results"],
                    hops=comp["hops"], t0=comp["t0"]),
                ctx, ob, ev, t0, node_idx))
        else:
            for li in range(lcfg.slots):
                st = dataclasses.replace(st, app=self.app.on_lookup_done(
                    st.app, app_base.LookupDone(
                        en=ena_l[li], success=ena_l[li] & suc_l[li],
                        tag=comp["aux"][li], target=comp["target"][li],
                        results=comp["results"][li], hops=comp["hops"][li],
                        t0=comp["t0"][li]),
                    ctx, ob, ev, t0, node_idx))

        with scope("kademlia.refresh"):
            # ------------------------------------------- bucket refresh pump ---
            # target/seed were computed in the batched findNode above; gate on
            # the POST-completion dirty bit so a bucket whose refresh just
            # finished is not immediately re-queried
            dirty_now = st.refresh_dirty[jnp.minimum(bi_ref, p.num_buckets - 1)]
            dirty_any = (st.state == READY) & dirty_now
            no_ref_lk = ~jnp.any(st.lk.active & (st.lk.purpose == P_REFRESH))
            slot, have = lk_mod.free_slot(st.lk)
            start_ref = dirty_any & no_ref_lk & have & (seed_r[0] != NO_NODE)
            # no candidates at all → just clear the bit
            clear_only = dirty_any & no_ref_lk & (seed_r[0] == NO_NODE)
            st = dataclasses.replace(
                st,
                refresh_dirty=jnp.where(clear_only,
                                        st.refresh_dirty.at[bi_ref].set(False),
                                        st.refresh_dirty),
                lk=lk_mod.start(st.lk, start_ref, slot, P_REFRESH, bi_ref,
                                target_ref, seed_r[:lcfg.frontier], t0, lcfg))

        # ------------------------------------------------------- pump ------
        # adaptive per-destination RPC timeouts from the RTT cache
        # (getNodeTimeout, NeighborCache.cc:802; optimizeTimeouts)
        timeout_fn = (nc_mod.adaptive_timeout_fn(st.nc, lcfg.rpc_timeout_ns)
                      if p.adaptive_timeouts else None)
        new_lk, _ = lk_mod.pump(st.lk, ob, ctx, node_idx, t0, rngs[6], lcfg,
                                num_redundant=p.redundant_nodes,
                                timeout_fn=timeout_fn,
                                prox_fn=(nc_mod.prox_fn(st.nc)
                                         if lcfg.prox_aware else None))
        st = dataclasses.replace(st, lk=new_lk)

        # Common API update() (BaseOverlay::callUpdate, BaseOverlay.cc:640
        # → BaseApp::update, BaseApp.h:223): report nodes that entered
        # the sibling set this tick so the app can re-replicate (the
        # DHT's update()-driven maintenance puts)
        if hasattr(self.app, "on_update"):
            new_in = jnp.where(
                (st.sib != NO_NODE)
                & ~jnp.any(st.sib[:, None] == old_sib[None, :], axis=1),
                st.sib, NO_NODE)
            st = dataclasses.replace(st, app=self.app.on_update(
                st.app, st.state == READY, ctx, ob, ev, t0, node_idx,
                new_in,
                sib_keys=ctx.keys[jnp.maximum(st.sib, 0)],
                sib_valid=st.sib != NO_NODE))

        # ------------------------------------------------------ events -----
        events = {
            "c:kad_joins": joins_cnt,
            "c:lookup_success": lksucc_cnt,
            "c:lookup_failed": anyfail_cnt,
            "c:route_dropped": routedrop_cnt,
            "s:lookup_hops": comp_hops_ev,
        }
        ev.finish(events, self.app.hist_map)
        return st, ob, events
