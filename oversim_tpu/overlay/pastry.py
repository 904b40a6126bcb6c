"""Pastry / Bamboo prefix-routing DHT as vectorized per-node logic.

TPU-native rebuild of the reference BasePastry/Pastry/Bamboo family
(src/overlay/pastry/BasePastry.{h,cc}, Pastry.{h,cc}, bamboo/Bamboo.{h,cc};
defaults simulations/default.ini:226-267: bitsPerDigit=4,
numberOfLeaves=16 (Bamboo 8)).  State is structure-of-arrays:

  * leaf set as two ring-sorted halves [N, L/2] (clockwise successors +
    counter-clockwise predecessors — reference PastryLeafSet keeps the
    bigger/smaller halves);
  * prefix routing table [N, ROWS, 2^b]: row r column c holds a node
    sharing r digits with our key whose digit r is c
    (PastryRoutingTable); rows are capped (ROWS*b prefix bits is far
    beyond the populated region for any realistic N — deeper keys are
    the leafset's job);
  * findNode (BasePastry.cc:1100): leafset if the key is within leafset
    range (numerically closest leaf wins), else the routing-table entry
    for [sharedPrefixDigits, next digit], else the numerically-closest
    known node with at-least-equal prefix (fallback);
  * isSiblingFor: numSiblings closest of leafset ∪ self by Pastry's
    plain numeric metric;
  * join: iterative lookup of the own key, then a state exchange with
    the responsible node (the reference collects PastryStateMessages
    from every hop of the routed join, Pastry.cc:1071; here the
    leafset arrives from the responsible node and the routing table
    fills from exchanges + observed traffic — Bamboo's push-pull
    convergence, Bamboo.cc localTuning/leafsetMaintenance);
  * maintenance (Bamboo-style, used for both variants; Bamboo.cc's three
    periodic tasks, one timer each): ``t_ls`` leafset push-pull with a
    random leaf (`leafsetMaintenanceInterval`: the call carries the
    caller's leaf set, the response the callee's, and each side merges
    the other's), ``t_lt`` local tuning (`localTuningInterval`: a
    random routing-table entry is asked for the row it sits in, the
    reply merged through `_rt_add` with the measured RTT of the
    responder; 0 switches the task off, Pastry's default), ``t_gt``
    global tuning (`globalTuningInterval`: a random-key lookup whose
    responses fill routing-table rows); a node that completes its join
    announces itself to every member of its new leaf set in the same
    exchange message (Pastry's doJoinUpdate; Bamboo pushes its leaf set
    to its members on a change), so a newcomer's neighbours need no
    gossip round to hear of it; Pastry's reactive leafset repair
    (handleFailedNode → state request to the farthest leaf) rides the
    same exchange message;
  * proximity neighbor selection (PNS, BasePastry.cc:439-570
    pingNodes/proximity compare): every state exchange carries an RTT
    stamp; the responder's measured RTT gates routing-table adoption —
    a measured-closer candidate replaces an occupied slot (rt_rtt
    table), unmeasured candidates only fill empty slots.  The
    neighborhood set (purely a PNS seed cache in the reference) is
    subsumed by the same RTT table.

Routing mode defaults to SEMI_RECURSIVE with per-hop ACKs — the
reference's Pastry configuration (default.ini:245-246 routeMsgAcks=true,
routingType="semi-recursive"): application payloads hop node-to-node via
common/route.py (findNode → loop-detect → forward, NextHop ACK, reroute
on hop failure), while join/maintenance lookups stay iterative.
``routing_mode="iterative"`` restores lookup-then-direct-hop routing.

Read from the ini (config/scenario.py, `**.overlay.pastry.*` or
`**.overlay.bamboo.*`): bitsPerDigit, numberOfLeaves, joinTimeout,
leafsetMaintenanceInterval, localTuningInterval, globalTuningInterval,
routeMsgAcks, routingType and recNumRedundantNodes (``rec_redundant``
follows the key; the default of 4 is this module's, upstream's
default.ini:386 says 3).  An overlay that no node has started yet is
started by ONE of a tick's due joiners (``ring_starter`` /
``Ctx.starter``); the engine's awake-set plane may skip this logic's
idle nodes (``awake_set_exact``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from oversim_tpu import stats as stats_mod
from oversim_tpu.apps import base as app_base
from oversim_tpu.apps.kbrtest import KbrTestApp
from oversim_tpu.common import lookup as lk_mod
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
RTT_INF = jnp.int32(2**30)

DEAD, JOINING, READY = 0, 1, 2

P_JOIN, P_TUNE, P_APP = 1, 2, 3

# what the overlay's upkeep did, cumulative (stats "c:" counters, gated
# like every counter on the measurement phase): leaf-set push-pulls the
# ``t_ls`` timer started, local-tuning probes the ``t_lt`` timer sent,
# global-tuning rounds the ``t_gt`` timer fired (a round whose random
# key is the node's own ends where it starts and is counted), state
# exchanges answered (leaf-set and routing-row responses sent), and the
# application payloads handed to common/route.py at their sender
UPKEEP_COUNTERS = (
    "bamboo_ls_rounds", "bamboo_lt_probes", "bamboo_gt_lookups",
    "bamboo_state_msgs", "bamboo_app_routes")


@dataclasses.dataclass(frozen=True)
class PastryParams:
    """default.ini:226-267.  Every field with an ini key beside it is
    read from the ini by config/scenario.py; the values here are the
    defaults where the ini is silent."""

    bits_per_digit: int = 4       # bitsPerDigit
    num_leaves: int = 16          # numberOfLeaves (Bamboo: 8)
    rows: int = 16                # routing-table row cap (see module doc)
    join_delay: float = 10.0      # joinTimeout
    # Bamboo's three periodic tasks (Bamboo.cc), one timer each
    leafset_interval: float = 10.0   # leafsetMaintenanceInterval (t_ls)
    local_tuning_interval: float = 0.0  # localTuningInterval (t_lt);
                                     # 0 = off (Pastry has no such task)
    tuning_interval: float = 30.0    # globalTuningInterval (t_gt)
    rpc_timeout: float = 1.5
    # reference default.ini:245-246: semi-recursive with per-hop ACKs
    routing_mode: str = "semi-recursive"   # routingType; or "iterative"
    route_acks: bool = True       # routeMsgAcks
    rec_redundant: int = 4        # recNumRedundantNodes (upstream's
                                  # default.ini:386 says 3; this
                                  # module's default stays 4)
    adaptive_timeouts: bool = False  # optimizeTimeouts (BaseRpc.cc:197-
                                  # 205): iterative-lookup RPC timeouts
                                  # from the NeighborCache estimator
                                  # (getNodeTimeout, NeighborCache.cc:802)

    @property
    def cols(self) -> int:
        return 1 << self.bits_per_digit

    @property
    def half(self) -> int:
        return self.num_leaves // 2


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PastryState:
    state: jnp.ndarray      # [N] i32
    leaf_cw: jnp.ndarray    # [N, L/2] i32 clockwise (successor side)
    leaf_ccw: jnp.ndarray   # [N, L/2] i32 counter-clockwise
    rt: jnp.ndarray         # [N, ROWS, COLS] i32
    rt_rtt: jnp.ndarray     # [N, ROWS, COLS] i32 RTT ms of each entry
                            # (PNS state, BasePastry.cc:439-570 pingNodes)
    t_join: jnp.ndarray     # [N] i64
    t_ls: jnp.ndarray       # [N] i64 leafset maintenance
    t_gt: jnp.ndarray       # [N] i64 global tuning
    t_lt: jnp.ndarray       # [N] i64 local tuning (T_INF where off)
    lk: lk_mod.LookupState
    rr: rt_mod.RouteState   # [N, Q, ...] pending-ACK recursive routes
    nc: object              # nc_mod.NcState — RTT cache (adaptive timeouts)
    app: object
    app_glob: object


class PastryLogic:
    """Engine logic interface; Bamboo = PastryLogic(bamboo defaults)."""

    def __init__(self, spec: K.KeySpec = K.DEFAULT_SPEC,
                 params: PastryParams = PastryParams(),
                 lcfg: lk_mod.LookupConfig | None = None,
                 app=None):
        self.key_spec = spec
        self.p = params
        self.lcfg = lcfg or lk_mod.LookupConfig()
        self.rcfg = rt_mod.RouteConfig(route_acks=params.route_acks)
        self.app = app or KbrTestApp()
        if getattr(self.app, "rcfg", None) is None:
            # Pastry routes semi-recursively by default: the app must
            # know (for reply transport + the deliver dedup ring,
            # apps/kbrtest.py KbrTestApp.buf)
            self.app.rcfg = self.rcfg
        # Pastry responsibility = numeric closeness on the ring
        # (BasePastry::distance, KeyDiffMetric)
        if getattr(self.app, "dist_fn", "no") is None:
            self.app.dist_fn = (
                lambda nk, rk: K.bidir_ring_distance(nk, rk, spec))

    # -- engine interface ---------------------------------------------------

    @property
    def awake_set_exact(self) -> bool:
        """The engine may skip this logic's idle nodes (engine/sim.py
        ``resolve_tick_impl``): a node with no inbox message, no due
        ``next_event`` (the join, leaf-set and tuning timers, the
        lookups' and the pending ACKs' timeouts, the app's timer) and no
        churn is a fixed point of ``step``, for the overlay's own part
        and, where the app says the same of itself, for the app's.
        Pinned against the dense sweep by tests/test_pastry_bamboo.py
        (semi-recursive with ACKs) and by hand for Pastry's defaults and
        the iterative mode (benchmark/tests/test_bamboo_planes.py)."""
        return bool(getattr(self.app, "awake_set_exact", False))

    def split(self, st: PastryState):
        return dataclasses.replace(st, app_glob=None), st.app_glob

    def merge(self, node_part: PastryState, glob):
        return dataclasses.replace(node_part, app_glob=glob)

    def post_step(self, ctx, st: PastryState, events):
        app, glob = self.app.post_step(ctx, st.app, st.app_glob, events)
        return dataclasses.replace(st, app=app, app_glob=glob)

    def stat_spec(self) -> stats_mod.StatSpec:
        app = self.app.stat_spec()
        return stats_mod.StatSpec(
            scalars=tuple(app["scalars"]) + ("lookup_hops",),
            hists=tuple(app["hists"]),
            counters=tuple(app["counters"]) + (
                "pastry_joins", "lookup_success", "lookup_failed",
                "route_dropped") + UPKEEP_COUNTERS + rt_mod.ROUTE_COUNTERS,
        )

    def init(self, rng, n: int) -> PastryState:
        p = self.p
        return PastryState(
            state=jnp.zeros((n,), I32),
            leaf_cw=jnp.full((n, p.half), NO_NODE, I32),
            leaf_ccw=jnp.full((n, p.half), NO_NODE, I32),
            rt=jnp.full((n, p.rows, p.cols), NO_NODE, I32),
            rt_rtt=jnp.full((n, p.rows, p.cols), RTT_INF, I32),
            t_join=jnp.full((n,), T_INF, I64),
            t_ls=jnp.full((n,), T_INF, I64),
            t_gt=jnp.full((n,), T_INF, I64),
            t_lt=jnp.full((n,), T_INF, I64),
            lk=jax.vmap(lambda _: lk_mod.init(self.lcfg, self.key_spec.lanes))(
                jnp.arange(n)),
            rr=jax.vmap(lambda _: rt_mod.init(
                self.rcfg, self.key_spec.lanes, 16))(jnp.arange(n)),
            nc=nc_mod.init(n, nc_mod.NcParams(
                capacity=16 if self.p.adaptive_timeouts else 1)),
            app=self.app.init(n),
            app_glob=self.app.glob_init(rng),
        )

    def reset(self, st: PastryState, clear, join, t_now, rng):
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

    def ready_mask(self, st: PastryState):
        return st.state == READY

    def next_event(self, st: PastryState):
        joining = st.state == JOINING
        ready = st.state == READY
        t = jnp.where(joining, st.t_join, T_INF)
        for timer in (st.t_ls, st.t_gt, st.t_lt):
            t = jnp.minimum(t, jnp.where(ready, timer, T_INF))
        t = jnp.minimum(t, jnp.where(ready, self.app.next_event(st.app),
                                     T_INF))
        t = jnp.minimum(t, jax.vmap(lk_mod.next_event)(st.lk))
        t = jnp.minimum(t, jax.vmap(rt_mod.next_event)(st.rr))
        return t

    def ring_starter(self, st: PastryState, alive, t_end):
        """The one joiner that may start an overlay in a tick that finds
        no node READY (``Ctx.starter``): of the nodes whose join timer
        is due, the earliest, ties to the lowest slot; NO_NODE where
        none is due.  Upstream's first node is READY inside its own
        creation event, so its second node always finds it; two nodes
        created inside one tick window (a fill of 0.1 s a join under a
        0.2 s window) must not both see an empty overlay and start one
        each: leaf sets only ever merge what they hear of, and two
        overlays that never exchange a message stay two."""
        due = alive & (st.state == JOINING) & (st.t_join < t_end)
        first = jnp.argmin(jnp.where(due, st.t_join, T_INF)).astype(I32)
        return jnp.where(jnp.any(due), first, NO_NODE)

    # -- internals (per-node slice) ------------------------------------------

    def _half_sorted(self, ctx, me_key, node_idx, cands, clockwise: bool):
        """L/2 ring-closest candidates on one side, sorted by distance."""
        h = self.p.half
        bad = (cands == NO_NODE) | (cands == node_idx) | K.dup_mask(cands)
        ck = ctx.keys[jnp.maximum(cands, 0)]
        me_b = jnp.broadcast_to(me_key, ck.shape)
        d = K.sub(ck, me_b, self.key_spec) if clockwise \
            else K.sub(me_b, ck, self.key_spec)
        d = jnp.where(bad[:, None], UMAX, d)
        (c_s, bad_s) = K.sort_by_distance(d, (cands, bad.astype(I32)),
                                          approx=True)[1]
        return jnp.where(bad_s[:h] != 0, NO_NODE, c_s[:h])

    def _leaf_merge(self, ctx, st, me_key, node_idx, cands, en):
        """Merge candidate slots into both leafset halves
        (PastryLeafSet::mergeNode)."""
        cands = jnp.where(en, cands, NO_NODE)
        all_cw = jnp.concatenate([st.leaf_cw, cands])
        all_ccw = jnp.concatenate([st.leaf_ccw, cands])
        return dataclasses.replace(
            st,
            leaf_cw=self._half_sorted(ctx, me_key, node_idx, all_cw, True),
            leaf_ccw=self._half_sorted(ctx, me_key, node_idx, all_ccw,
                                       False))

    def _rt_add(self, ctx, st, me_key, node_idx, cands, en, rtt=None):
        """Insert candidates into routing-table slots with proximity
        neighbor selection (PastryRoutingTable::mergeNode + the PNS
        ping-before-adopt comparison, BasePastry.cc:439-570: a measured
        closer candidate replaces an occupied slot; unmeasured
        candidates only fill empty slots).

        All candidates at once: of those that earn one cell the one
        with the least RTT wins (the earliest where two are as near,
        the entry the cell holds before any of them), which is what
        taking them one after the other comes to; then ONE indexed
        write of the winners, each to a cell of its own."""
        p = self.p
        n_c = cands.shape[0]
        c = jnp.where(en & (cands != node_idx), cands, NO_NODE)
        c_rtt = (jnp.full((n_c,), RTT_INF, I32) if rtt is None
                 else jnp.asarray(rtt, I32))
        ck = ctx.keys[jnp.maximum(c, 0)]
        row = jnp.minimum(
            K.shared_prefix_digits(jnp.broadcast_to(me_key, ck.shape), ck,
                                   p.bits_per_digit, self.key_spec),
            p.rows - 1)
        col = K.digit(ck, row, p.bits_per_digit, self.key_spec)
        cell = row * p.cols + col
        valid = c != NO_NODE
        idx = jnp.arange(n_c, dtype=I32)
        # beaten by another candidate of the same cell
        rival = (valid[None, :] & (cell[None, :] == cell[:, None])
                 & ((c_rtt[None, :] < c_rtt[:, None])
                    | ((c_rtt[None, :] == c_rtt[:, None])
                       & (idx[None, :] < idx[:, None]))))
        wins = valid & ~jnp.any(rival, axis=1)
        held, held_rtt = st.rt[row, col], st.rt_rtt[row, col]
        same = held == c
        closer = c_rtt < held_rtt
        do = wins & ((held == NO_NODE) | closer | same)
        r = jnp.where(do, row, p.rows + idx)   # OOB, each its own: dropped
        return dataclasses.replace(
            st,
            rt=st.rt.at[r, col].set(c, mode="drop", unique_indices=True),
            rt_rtt=st.rt_rtt.at[r, col].set(
                jnp.where(same & ~closer, held_rtt, c_rtt), mode="drop",
                unique_indices=True))

    @scoped("pastry.learn")
    def _learn(self, ctx, st, me_key, node_idx, cands, en, rtt=None):
        st = self._leaf_merge(ctx, st, me_key, node_idx, cands, en)
        return self._rt_add(ctx, st, me_key, node_idx, cands, en, rtt)

    def _leafset_nodes(self, st, node_idx):
        """Own state payload: self + both halves (PastryStateMessage)."""
        return jnp.concatenate([node_idx[None], st.leaf_cw, st.leaf_ccw])

    @scoped("pastry.find_node")
    def _find_node(self, ctx, st, me_key, node_idx, key, rmax):
        """BasePastry::findNode (BasePastry.cc:1100).

        All closeness uses the reference's keyDist = bidirectional ring
        distance (PastryStateObject::keyDist, PastryStateObject.cc:107).
        Returns ([rmax] result slots, is_sibling bool).
        """
        p, spec = self.p, self.key_spec

        def kdist(slots, target):
            ck = ctx.keys[jnp.maximum(slots, 0)]
            d = K.bidir_ring_distance(ck, jnp.broadcast_to(target, ck.shape),
                                      spec)
            return jnp.where((slots == NO_NODE)[:, None], UMAX, d)

        ready = st.state == READY
        me_d = K.bidir_ring_distance(me_key, key, spec)

        # isClosestNode (PastryLeafSet.cc:136): neither the immediate
        # clockwise nor counter-clockwise neighbor is closer than us
        big, small = st.leaf_cw[0], st.leaf_ccw[0]
        no_nbrs = (big == NO_NODE) & (small == NO_NODE)
        big_closer = (big != NO_NODE) & K.lt(kdist(big[None], key)[0], me_d)
        small_closer = (small != NO_NODE) & K.lt(kdist(small[None], key)[0],
                                                 me_d)
        is_sib = ready & (K.eq(key, me_key) | no_nbrs
                          | (~big_closer & ~small_closer))

        # getDestinationNode (PastryLeafSet.cc:106): key within the
        # leafset span [farthest-ccw, farthest-cw] → closest leaf
        def farthest(half):
            n_valid = jnp.sum((half != NO_NODE).astype(I32))
            return jnp.where(n_valid > 0, half[jnp.maximum(n_valid - 1, 0)],
                             NO_NODE)

        cw_far, ccw_far = farthest(st.leaf_cw), farthest(st.leaf_ccw)
        span_ok = (cw_far != NO_NODE) & (ccw_far != NO_NODE)
        in_span = span_ok & K.is_between_lr(
            key, ctx.keys[jnp.maximum(ccw_far, 0)],
            ctx.keys[jnp.maximum(cw_far, 0)], spec)
        leafs = self._leafset_nodes(st, node_idx)
        d_leafs = kdist(leafs, key)
        (leafs_s,) = K.sort_by_distance(d_leafs, (leafs,), approx=True)[1]
        leaf_dest = leafs_s[0]

        # routing table hop (PastryRoutingTable::lookupNextHop)
        row = jnp.minimum(
            K.shared_prefix_digits(me_key, key, p.bits_per_digit, spec),
            p.rows - 1)
        col = K.digit(key, row, p.bits_per_digit, spec)
        rt_hop = st.rt[row, col]
        rt_ok = rt_hop != NO_NODE

        # 'rare case' fallback (BasePastry.cc:1132-1165 findCloserNode):
        # any known node with >= shared prefix strictly closer by keyDist
        known = jnp.concatenate([leafs, st.rt.reshape(-1)])
        kk = ctx.keys[jnp.maximum(known, 0)]
        key_b = jnp.broadcast_to(key, kk.shape)
        dk = kdist(known, key)
        closer = K.lt(dk, jnp.broadcast_to(me_d, dk.shape))
        pfx = K.shared_prefix_digits(me_key, key, p.bits_per_digit, spec)
        kpfx = K.shared_prefix_digits(kk, key_b, p.bits_per_digit, spec)
        ok = (known != NO_NODE) & closer & (kpfx >= pfx)
        # the closest few of them, each an argmin (nothing is sorted; a
        # known node that is no closer is no candidate at any place)
        fb = []
        for _ in range(max(p.rec_redundant - 1, 1)):
            at = K.argmin_by_distance(jnp.where(ok[:, None], dk, UMAX),
                                      approx=True)
            fb.append(jnp.where(jnp.any(ok), known[at], NO_NODE))
            ok = ok & (known != known[at])
        fb_s = jnp.stack(fb)
        fallback = fb_s[0]

        # result set: sibling case → closest leafs (replica set); else hop
        nxt = jnp.where(in_span & (leaf_dest != node_idx), leaf_dest,
                        jnp.where(rt_ok, rt_hop, fallback))
        res = jnp.full((rmax,), NO_NODE, I32)
        res_sib = res.at[:leafs_s.shape[0]].set(leafs_s[:rmax])
        res = jnp.where(is_sib, res_sib, res.at[0].set(nxt))
        res = jnp.where(ready, res, jnp.full((rmax,), NO_NODE, I32))

        # redundant next-hop candidates for recursive forwarding, in
        # preference order (recNumRedundantNodes, default.ini:386): the
        # primary hop (self when responsible), then the keyDist-sorted
        # closer-known fallbacks for loop avoidance/reroute
        cands = jnp.concatenate(
            [jnp.where(is_sib, node_idx, nxt)[None],
             fb_s[:max(p.rec_redundant - 1, 0)]])
        cands = jnp.where(ready, cands, NO_NODE)
        return res, is_sib, cands

    @scoped("pastry.failed")
    def _handle_failed(self, ctx, st, me_key, node_idx, failed, ob, now):
        """BasePastry::handleFailedNode + Pastry leafset repair: drop the
        failed nodes everywhere; if a leafset half lost a member, request
        state from the farthest remaining leaf."""
        any_failed = jnp.any(failed != NO_NODE)

        def hit(x):
            return (x[..., None] == failed).any(-1) & (x != NO_NODE)

        lost_leaf = jnp.any(hit(st.leaf_cw)) | jnp.any(hit(st.leaf_ccw))
        leaf_cw = jnp.where(hit(st.leaf_cw), NO_NODE, st.leaf_cw)
        leaf_ccw = jnp.where(hit(st.leaf_ccw), NO_NODE, st.leaf_ccw)
        # re-sort each half so survivors from the other half can slide in
        st2 = self._leaf_merge(
            ctx, dataclasses.replace(st, leaf_cw=leaf_cw, leaf_ccw=leaf_ccw),
            me_key, node_idx,
            jnp.concatenate([leaf_cw, leaf_ccw]),
            jnp.ones((2 * self.p.half,), bool))
        st = select_tree(any_failed, st2, st)
        st = dataclasses.replace(
            st, rt=jnp.where(hit(st.rt), NO_NODE, st.rt),
            rt_rtt=jnp.where(hit(st.rt), RTT_INF, st.rt_rtt))
        # repair: ask the farthest remaining leaf for its state
        repair_tgt = jnp.where(st.leaf_cw[-1] != NO_NODE, st.leaf_cw[-1],
                               st.leaf_cw[0])
        fire = any_failed & lost_leaf & (repair_tgt != NO_NODE) & (
            st.state == READY)
        ob.send(fire, now, repair_tgt, wire.PASTRY_STATE_CALL,
                stamp=now, size_b=wire.BASE_CALL_B)
        return st

    def _become_ready(self, ctx, st, en, now, rng):
        p = self.p
        return dataclasses.replace(
            st,
            state=jnp.where(en, READY, st.state),
            t_join=jnp.where(en, T_INF, st.t_join),
            t_ls=jnp.where(en, now, st.t_ls),
            t_gt=jnp.where(en, now + jnp.int64(
                int(p.tuning_interval * NS)), st.t_gt),
            t_lt=jnp.where(
                en & (p.local_tuning_interval > 0), now + jnp.int64(
                    int(p.local_tuning_interval * NS)), st.t_lt),
            app=self.app.on_ready(st.app, en, now, rng))

    # -- the per-node step ---------------------------------------------------

    def step(self, ctx, st, msgs, rng, node_idx, *, outbox_slots, rmax):
        p, lcfg, spec = self.p, self.lcfg, self.key_spec
        ob = Outbox(outbox_slots, spec.lanes, rmax)
        me_key = ctx.keys[node_idx]
        rngs = jax.random.split(rng, 6)
        t0 = ctx.t_start
        t_end = ctx.t_end

        def metric_fn(cand_slots, target):
            ck = ctx.keys[jnp.maximum(cand_slots, 0)]
            d = K.bidir_ring_distance(
                ck, jnp.broadcast_to(target, ck.shape), spec)
            return jnp.where((cand_slots == NO_NODE)[:, None], UMAX, d)

        def pad_nodes(vec):
            out = jnp.full((rmax,), NO_NODE, I32)
            return out.at[:min(vec.shape[0], rmax)].set(vec[:rmax])

        ev = app_base.AppEvents()
        joins_cnt = jnp.int32(0)
        anyfail_cnt = jnp.int32(0)
        lksucc_cnt = jnp.int32(0)
        routedrop_cnt = jnp.int32(0)
        # upkeep (UPKEEP_COUNTERS) and routed-path (rt_mod.ROUTE_COUNTERS)
        # tallies of this step
        cnt = {k: jnp.int32(0)
               for k in UPKEEP_COUNTERS + rt_mod.ROUTE_COUNTERS}

        def tally(name, what):
            cnt[name] = cnt[name] + jnp.sum(jnp.asarray(what).astype(I32))

        acks_on = self.rcfg.route_acks
        # a completed join is announced to the new leaf set once, after
        # the inbox (one vector send)
        announce = jnp.bool_(False)
        announce_t = t0
        old_leaf = jnp.concatenate([st.leaf_cw, st.leaf_ccw])
        # update() delta base (the leafset is Pastry's sibling set)

        # ------------------------------------------------------- inbox -----
        if p.adaptive_timeouts:
            # FindNode RTT samples feed the NeighborCache estimator
            # before the per-slot handlers clear the pendings
            # (NeighborCache::updateNode on every RPC response)
            en_rtt = msgs.valid & (msgs.kind == wire.FINDNODE_RES)
            rtt_src, rtt_s, rtt_ok = lk_mod.response_rtts(
                st.lk, dataclasses.replace(msgs, valid=en_rtt))
            st = dataclasses.replace(st, nc=nc_mod.feed_response_rtts(
                st.nc, rtt_src, rtt_s, msgs.t_deliver, rtt_ok))
        for r in range(msgs.valid.shape[0]):
            m = msgs.slot(r)
            now = m.t_deliver
            v = m.valid

            # learn every READY message source (observed-traffic table
            # fill, Bamboo's passive learning).  Joining nodes must NOT
            # enter leafsets: the reference only merges overlay members
            # (PastryStateMessage senders); adopting a joiner would route
            # its own-key join lookup straight back at it.
            # (ONE merge a slot, below: the source beside what the
            # message carries)
            src0 = m.src
            src_ready = v & ctx.ready[jnp.maximum(src0, 0)]

            # local findNode on this slot's key — shared by the FindNode
            # RPC server, the recursive forwarding pre-pass, and the app
            # delivery sibling check below
            res, sib, cands = self._find_node(ctx, st, me_key, node_idx,
                                              m.key, rmax)

            # per-hop ACK bookkeeping (NextHopResponse)
            rr_acked = rt_mod.on_ack(
                st.rr, dataclasses.replace(
                    m, valid=v & (m.kind == wire.KBR_ROUTE_ACK)))
            tally("route_acked", st.rr.active & ~rr_acked.active)
            st = dataclasses.replace(st, rr=rr_acked)

            # recursive route pre-pass (sendToKey SEMI_RECURSIVE hop,
            # BaseOverlay.cc:1441-1581): ACK the last hop, then either
            # decapsulate (responsible) or forward to the first candidate
            # surviving loop detection.  visitedHops ride m.nodes; the
            # originator is visited[0].
            en_rt = v & (m.kind == wire.KBR_ROUTE) & (st.state == READY)
            with scope("route.acks"):
                ob.send(en_rt & (m.nonce > 0), now, m.src,
                        wire.KBR_ROUTE_ACK, nonce=m.nonce,
                        size_b=wire.BASE_CALL_B)
            deliver = en_rt & sib
            nxt_rt, found_rt = rt_mod.pick_next_hop(
                cands, m.nodes, m.src, m.nodes[0], node_idx, sib)
            in_bound = m.hops < self.rcfg.hop_max
            fwd = en_rt & ~sib & found_rt & in_bound
            if hasattr(self.app, "forward"):
                # Common API forward() veto (BaseApp.h:214)
                fwd = fwd & ~self.app.forward(st.app, m, ctx)
            with scope("route.forward"):
                vis_n = jnp.sum((m.nodes != NO_NODE).astype(I32))
                visited2 = jnp.where(
                    fwd & (jnp.arange(rmax, dtype=I32)
                           == jnp.minimum(vis_n, rmax - 1)),
                    node_idx, m.nodes)
            tally("route_delivered", deliver)
            tally("route_forwarded", fwd)
            tally("route_unacked_table_full",
                  fwd & acks_on & ~rt_mod.parks(st.rr, fwd, self.rcfg))
            tally("route_dropped_no_candidate", en_rt & ~sib & ~found_rt)
            tally("route_dropped_hop_bound",
                  en_rt & ~sib & found_rt & ~in_bound)
            st = dataclasses.replace(st, rr=rt_mod.forward(
                st.rr, ob, fwd, now, nxt_rt, key=m.key, inner=m.d,
                a=m.a, b=m.b, c=m.c, hops=m.hops + 1, stamp=m.stamp,
                size_b=m.size_b - self.rcfg.overhead_b, visited=visited2,
                cfg=self.rcfg))
            routedrop_cnt += (en_rt & ~sib & ~fwd).astype(I32)
            # decapsulate at the responsible node: the payload kind takes
            # over and src becomes the originator, so the handlers below
            # (incl. FindNodeCall for recursive lookups and app kinds)
            # consume it as if it arrived directly
            m = dataclasses.replace(
                m,
                kind=jnp.where(deliver, m.d, m.kind),
                src=jnp.where(deliver, m.nodes[0], m.src),
                valid=v & (~en_rt | deliver))
            v = m.valid

            # FindNodeCall
            en = v & (m.kind == wire.FINDNODE_CALL)
            n_res = jnp.sum((res != NO_NODE).astype(I32))
            ob.send(en, now, m.src, wire.FINDNODE_RES, key=m.key,
                    a=m.a, b=m.b, c=sib.astype(I32), nodes=res,
                    size_b=wire.BASE_CALL_B + 1 + wire.NODEHANDLE_B * n_res)

            # FindNodeResponse → lookup engine + learn payload
            en = v & (m.kind == wire.FINDNODE_RES)
            with scope("lookup.responses"):
                st = dataclasses.replace(st, lk=lk_mod.on_response(
                    st.lk, dataclasses.replace(m, valid=en), metric_fn,
                    lcfg))
            en_found = en

            # state exchange (leafset push-pull; PastryStateMessage):
            # the call carries the caller's leaf set, the response the
            # callee's (before it merged the caller's)
            with scope("pastry.leafset_maint"):
                en_call = v & (m.kind == wire.PASTRY_STATE_CALL) & (
                    st.state == READY)
                ob.send(en_call, now, m.src, wire.PASTRY_STATE_RES,
                        nodes=pad_nodes(self._leafset_nodes(st, node_idx)),
                        stamp=m.stamp, size_b=wire.BASE_CALL_B
                        + wire.NODEHANDLE_B * (p.num_leaves + 1))
            # local tuning, served: the row asked for, with ourselves in
            # the column our own digit leaves empty
            with scope("pastry.tuning"):
                en_row = v & (m.kind == wire.PASTRY_ROW_CALL) & (
                    st.state == READY)
                row_r = jnp.clip(m.a, 0, p.rows - 1)
                own_row = st.rt[row_r].at[K.digit(
                    me_key, row_r, p.bits_per_digit, spec)].set(node_idx)
                ob.send(en_row, now, m.src, wire.PASTRY_ROW_RES, a=row_r,
                        nodes=pad_nodes(own_row), stamp=m.stamp,
                        size_b=wire.BASE_CALL_B + 1
                        + wire.NODEHANDLE_B * p.cols)
            tally("bamboo_state_msgs", en_call | en_row)
            # what a message teaches, merged by ONE pass a slot: its
            # READY source, a FindNode response's nodes, a READY
            # caller's pushed leaf set, a response's leaf set or row;
            # the responder's own entry carries the measured RTT
            is_res = v & ((m.kind == wire.PASTRY_STATE_RES)
                          | (m.kind == wire.PASTRY_ROW_RES))
            taught = jnp.concatenate([src0[None], m.nodes[:rmax]])
            en_taught = (taught != NO_NODE) & jnp.concatenate([
                src_ready[None], jnp.broadcast_to(
                    is_res | en_found | (en_call & src_ready), (rmax,))])
            rtt_ms = jnp.clip((now - m.stamp) // 1_000_000, 0,
                              RTT_INF - 1).astype(I32)
            rtt_vec = jnp.where(
                is_res & (m.stamp > 0) & (taught == src0), rtt_ms, RTT_INF)
            st = select_tree(
                jnp.any(en_taught),
                self._learn(ctx, st, me_key, node_idx, taught, en_taught,
                            rtt=rtt_vec), st)
            # joining node: first state response completes the join
            with scope("pastry.join"):
                got_state = v & (m.kind == wire.PASTRY_STATE_RES) & (
                    st.state == JOINING)
                joins_cnt += got_state.astype(I32)
                st = self._become_ready(ctx, st, got_state, now, rngs[0])
                announce = announce | got_state
                announce_t = jnp.where(got_state, now, announce_t)

            # app-owned kinds (reuse the sibling flag computed for this
            # slot's FindNode handler — no app-kind handler above mutates
            # the tables it read)
            st = dataclasses.replace(st, app=self.app.on_msg(
                st.app, m, ctx, ob, ev, sib))

            # generic ping
            ob.send(v & (m.kind == wire.PING_CALL), now, m.src,
                    wire.PING_RES, a=m.a, size_b=wire.BASE_CALL_B)

        # a node whose join ended in this tick tells every member of its
        # new leaf set (the call carries that leaf set and is answered
        # with the member's own)
        state_b = wire.BASE_CALL_B + wire.NODEHANDLE_B * (p.num_leaves + 1)
        with scope("pastry.join"):
            leafs = jnp.concatenate([st.leaf_cw, st.leaf_ccw])
            ob.send(announce & (leafs != NO_NODE), announce_t, leafs,
                    wire.PASTRY_STATE_CALL,
                    nodes=pad_nodes(self._leafset_nodes(st, node_idx)),
                    stamp=announce_t, size_b=state_b)

        # ------------------------------------------------------- timers ----
        # join: lookup own key, then state request to the responsible node
        with scope("pastry.join"):
            en_j = (st.state == JOINING) & (st.t_join < t_end)
            now_j = jnp.maximum(st.t_join, t0)
            boot = ctx.sample_ready(rngs[1], node_idx)
            no_join_lk = ~jnp.any(st.lk.active & (st.lk.purpose == P_JOIN))
            # no node READY: ONE due joiner starts the overlay
            # (ring_starter); the others keep their timer, so they stay
            # due (and awake) and find the starter READY in the next tick
            alone_start = en_j & (boot == NO_NODE) & (
                ctx.starter == node_idx)
            st = self._become_ready(ctx, st, alone_start, now_j, rngs[2])
            joins_cnt += alone_start.astype(I32)
            slot, have = lk_mod.free_slot(st.lk)
            start_join = en_j & (boot != NO_NODE) & no_join_lk & have
            seed = jnp.full((lcfg.frontier,), NO_NODE, I32).at[0].set(boot)
            st = dataclasses.replace(st, lk=lk_mod.start(
                st.lk, start_join, slot, P_JOIN, 0, me_key, seed, now_j,
                lcfg))
            st = dataclasses.replace(st, t_join=jnp.where(
                en_j & (boot != NO_NODE),
                now_j + jnp.int64(int(p.join_delay * NS)), st.t_join))

        # leafset maintenance: push-pull with a random leaf (Bamboo
        # leafsetMaintenance)
        with scope("pastry.leafset_maint"):
            en_l = (st.state == READY) & (st.t_ls < t_end)
            now_l = jnp.maximum(st.t_ls, t0)
            leafs = jnp.concatenate([st.leaf_cw, st.leaf_ccw])
            held_l = leafs != NO_NODE
            n_leafs = jnp.sum(held_l.astype(I32))
            pick = jax.random.randint(rngs[3], (), 0,
                                      jnp.maximum(n_leafs, 1), dtype=I32)
            # the pick-th held leaf, counted and not sorted
            tgt = leafs[jnp.argmax(jnp.cumsum(held_l.astype(I32)) > pick)]
            fire_l = en_l & (n_leafs > 0)
            ob.send(fire_l, now_l, tgt, wire.PASTRY_STATE_CALL,
                    nodes=pad_nodes(self._leafset_nodes(st, node_idx)),
                    stamp=now_l, size_b=state_b)
            tally("bamboo_ls_rounds", fire_l)
            st = dataclasses.replace(st, t_ls=jnp.where(
                en_l, now_l + jnp.int64(int(p.leafset_interval * NS)),
                st.t_ls))

        with scope("pastry.tuning"):
            # local tuning (Bamboo localTuning): a random routing-table
            # entry is asked for the row it sits in
            if p.local_tuning_interval > 0:
                en_t = (st.state == READY) & (st.t_lt < t_end)
                now_t = jnp.maximum(st.t_lt, t0)
                flat = st.rt.reshape(-1)
                held_t = flat != NO_NODE
                n_held = jnp.sum(held_t.astype(I32))
                pick_t = jax.random.randint(
                    jax.random.fold_in(rngs[4], 1), (), 0,
                    jnp.maximum(n_held, 1), dtype=I32)
                at = jnp.argmax(jnp.cumsum(held_t.astype(I32)) > pick_t)
                fire_t = en_t & (n_held > 0)
                ob.send(fire_t, now_t, flat[at], wire.PASTRY_ROW_CALL,
                        a=(at // p.cols).astype(I32), stamp=now_t,
                        size_b=wire.BASE_CALL_B + 1)
                tally("bamboo_lt_probes", fire_t)
                st = dataclasses.replace(st, t_lt=jnp.where(
                    en_t, now_t + jnp.int64(
                        int(p.local_tuning_interval * NS)), st.t_lt))

            # global tuning: random-key lookup fills routing rows (Bamboo
            # globalTuning)
            en_g = (st.state == READY) & (st.t_gt < t_end)
            now_g = jnp.maximum(st.t_gt, t0)
            no_tune = ~jnp.any(st.lk.active & (st.lk.purpose == P_TUNE))
            target = K.random_keys(rngs[4], (), spec)
            seed_g, sib_g, _ = self._find_node(ctx, st, me_key, node_idx,
                                               target, rmax)
            slot, have = lk_mod.free_slot(st.lk)
            start_g = en_g & no_tune & have & ~sib_g & (
                seed_g[0] != NO_NODE)
            tally("bamboo_gt_lookups", en_g)
            st = dataclasses.replace(
                st,
                lk=lk_mod.start(st.lk, start_g, slot, P_TUNE, 0, target,
                                seed_g[:lcfg.frontier], now_g, lcfg),
                t_gt=jnp.where(en_g, now_g + jnp.int64(
                    int(p.tuning_interval * NS)), st.t_gt))

        # app timer
        # graceful-leave: hand app data to the clockwise leaf and stop
        # firing app tests during the grace window (apps/base.py on_leave)
        st = dataclasses.replace(st, app=app_base.leave_protocol(
            self.app, st.app, ctx, ob, ev, t0, node_idx, st.leaf_cw[0],
            st.state == READY))
        en_a = (st.state == READY) & (
            self.app.next_event(st.app) < t_end)
        now_a = jnp.maximum(self.app.next_event(st.app), t0)
        app, req = self.app.on_timer(st.app, en_a, ctx, now_a, rngs[5], ev, node_idx)
        st = dataclasses.replace(st, app=app)
        seed_a, sib_a, cands_a = self._find_node(ctx, st, me_key, node_idx,
                                                 req.key, rmax)
        local = req.want & sib_a
        st = dataclasses.replace(st, app=self.app.on_lookup_done(
            st.app, app_base.LookupDone(
                en=local, success=local, tag=req.tag,
                target=req.key,
                results=jnp.where(local, seed_a[:lcfg.frontier], NO_NODE),
                hops=jnp.int32(0), t0=now_a),
            ctx, ob, ev, now_a, node_idx))
        # Which app requests ride the recursive data path?  Only the
        # payloads the app DECLARES routable (route_policy — kbrtest's
        # one-way/RPC tests).  Everything else (DHT LookupCalls, the
        # kbr lookup test) needs a SIBLING-SET completion and goes
        # through the iterative lookup engine even in semi-recursive
        # mode, exactly like the reference (DHT.cc issues LookupCall
        # regardless of the overlay's data routingType).  Routing every
        # request as APP_ONEWAY data was the round-3 verify_pastry
        # golden's 1%-put-success bug.
        use_route = (self.p.routing_mode == "semi-recursive"
                     and hasattr(self.app, "route_policy"))
        if use_route:
            routable, inner_a, is_rpc = self.app.route_policy(req.tag)
            vis0 = jnp.full((rmax,), NO_NODE, I32).at[0].set(node_idx)
            nxt0, found0 = rt_mod.pick_next_hop(
                cands_a, jnp.full((rmax,), NO_NODE, I32), NO_NODE,
                node_idx, node_idx, sib_a)
            fire0 = req.want & ~sib_a & routable & found0
            tally("bamboo_app_routes", fire0)
            tally("route_forwarded", fire0)
            tally("route_unacked_table_full",
                  fire0 & acks_on & ~rt_mod.parks(st.rr, fire0, self.rcfg))
            tally("route_dropped_no_candidate",
                  req.want & ~sib_a & routable & ~found0)
            st = dataclasses.replace(st, rr=rt_mod.forward(
                st.rr, ob, fire0, now_a, nxt0, key=req.key,
                inner=inner_a, a=req.tag, b=jnp.int32(0),
                c=ctx.measuring.astype(I32), hops=jnp.int32(1),
                stamp=now_a, size_b=jnp.int32(100), visited=vis0,
                cfg=self.rcfg))
            if hasattr(self.app, "on_route_fired"):
                st = dataclasses.replace(st, app=self.app.on_route_fired(
                    st.app, fire0 & is_rpc, now_a, req.tag))
            routedrop_cnt += (req.want & ~sib_a & routable
                              & ~found0).astype(I32)
        else:
            routable = jnp.bool_(False)
            fire0 = jnp.bool_(False)
        slot, have = lk_mod.free_slot(st.lk)
        start_app = (req.want & ~sib_a & ~routable & have
                     & (seed_a[0] != NO_NODE))
        # a routable request with NO next hop must fail its op too
        # (chord/kademlia: insta_fail = ~start_app & ~route_fire) — else
        # routed-RPC tests leak into a never-resolved state
        insta_fail = req.want & ~sib_a & ~start_app & ~fire0
        st = dataclasses.replace(st, app=self.app.on_lookup_done(
            st.app, app_base.LookupDone(
                en=insta_fail, success=jnp.bool_(False), tag=req.tag,
                target=req.key,
                results=jnp.full((lcfg.frontier,), NO_NODE, I32),
                hops=jnp.int32(0), t0=now_a),
            ctx, ob, ev, now_a, node_idx))
        st = dataclasses.replace(st, lk=lk_mod.start(
            st.lk, start_app, slot, P_APP, req.tag, req.key,
            seed_a[:lcfg.frontier], now_a, lcfg))

        # ------------------------------------------------ lookup timeouts --
        new_lk, failed_nodes, _ = lk_mod.on_timeouts(st.lk, t_end, t0, lcfg)
        st = dataclasses.replace(st, lk=new_lk)
        # route-hop ACK timeouts: unresponsive next hops are failures too
        new_rr, rt_failed, rt_retry = rt_mod.on_timeouts(st.rr, t_end,
                                                         self.rcfg)
        tally("route_ack_timeouts", rt_failed != NO_NODE)
        st = dataclasses.replace(st, rr=new_rr)
        st = self._handle_failed(
            ctx, st, me_key, node_idx,
            jnp.concatenate([failed_nodes, rt_failed]), ob, t0)

        # reroute parked messages around the failed hop (the hop was just
        # dropped from all tables by _handle_failed, so a fresh findNode
        # yields the alternative; internalHandleRpcTimeout :1697-1729)
        for qi in range(self.rcfg.slots):
            en_q = rt_retry[qi]
            _, sib_q, cands_q = self._find_node(
                ctx, st, me_key, node_idx, st.rr.key[qi], rmax)
            nxt_q, found_q = rt_mod.pick_next_hop(
                cands_q, st.rr.visited[qi], NO_NODE,
                st.rr.visited[qi, 0], node_idx, sib_q)
            # became responsible ourselves meanwhile → self-forward
            # delivers (decap) next tick
            st = dataclasses.replace(st, rr=rt_mod.reforward(
                st.rr, ob, qi, en_q & found_q, t0, nxt_q, self.rcfg))
            tally("route_rerouted", en_q & found_q)
            tally("route_forwarded", en_q & found_q)
            give_up = en_q & ~found_q
            tally("route_dropped_no_candidate", give_up)
            st = dataclasses.replace(
                st, rr=rt_mod.drop_slot(st.rr, qi, give_up))
            routedrop_cnt += give_up.astype(I32)

        # ------------------------------------------------- completions -----
        new_lk, comp = lk_mod.take_completions(st.lk, t_end)
        st = dataclasses.replace(st, lk=new_lk)
        comp_hops_ev = (comp["hops"].astype(jnp.float32),
                        comp["taken"] & comp["success"])
        for li in range(lcfg.slots):
            en = comp["taken"][li]
            suc = comp["success"][li] & (comp["result"][li] != NO_NODE)
            res = comp["result"][li]
            pur = comp["purpose"][li]
            lksucc_cnt += (en & suc).astype(I32)
            anyfail_cnt += (en & ~suc).astype(I32)

            # join lookup done → request state from the responsible node
            enj = en & (pur == P_JOIN)
            ob.send(enj & suc, t0, res, wire.PASTRY_STATE_CALL,
                    stamp=t0, size_b=wire.BASE_CALL_B)
            # join lookup failed → retry
            st = dataclasses.replace(st, t_join=jnp.where(
                enj & ~suc, t0 + jnp.int64(int(p.join_delay * NS)),
                st.t_join))

            # tuning lookups: results already learned via responses

            # app lookups
            ena = en & (pur == P_APP)
            st = dataclasses.replace(st, app=self.app.on_lookup_done(
                st.app, app_base.LookupDone(
                    en=ena, success=ena & suc, tag=comp["aux"][li],
                    target=comp["target"][li], results=comp["results"][li],
                    hops=comp["hops"][li], t0=comp["t0"][li]),
                ctx, ob, ev, t0, node_idx))

        # ------------------------------------------------------- pump ------
        # getNodeTimeout (NeighborCache.cc:802) per destination
        timeout_fn = (nc_mod.adaptive_timeout_fn(st.nc, lcfg.rpc_timeout_ns)
                      if p.adaptive_timeouts else None)
        new_lk, _ = lk_mod.pump(st.lk, ob, ctx, node_idx, t0, rngs[0], lcfg,
                                timeout_fn=timeout_fn,
                                prox_fn=(nc_mod.prox_fn(st.nc)
                                         if lcfg.prox_aware else None))
        st = dataclasses.replace(st, lk=new_lk)

        # ------------------------------------------------------ events -----
        # Common API update() (BaseOverlay::callUpdate → BaseApp::update,
        # BaseApp.h:223): nodes that entered the leafset — Pastry's
        # replica/sibling set — trigger app re-replication
        if hasattr(self.app, "on_update"):
            new_leaf = jnp.concatenate([st.leaf_cw, st.leaf_ccw])
            new_in = jnp.where(
                (new_leaf != NO_NODE)
                & ~jnp.any(new_leaf[:, None] == old_leaf[None, :], axis=1),
                new_leaf, NO_NODE)
            st = dataclasses.replace(st, app=self.app.on_update(
                st.app, st.state == READY, ctx, ob, ev, t0, node_idx,
                new_in,
                sib_keys=ctx.keys[jnp.maximum(new_leaf, 0)],
                sib_valid=new_leaf != NO_NODE))

        events = {
            "c:pastry_joins": joins_cnt,
            "c:lookup_success": lksucc_cnt,
            "c:lookup_failed": anyfail_cnt,
            "c:route_dropped": routedrop_cnt,
            "s:lookup_hops": comp_hops_ev,
        }
        events.update({"c:" + k: v for k, v in cnt.items()})
        ev.finish(events, self.app.hist_map)
        return st, ob, events


def bamboo_params() -> PastryParams:
    """Bamboo defaults (default.ini:251-267): smaller leafset, periodic
    push maintenance (already the maintenance style here), and the
    third of Bamboo's periodic tasks, local tuning, at the leaf-set
    task's interval."""
    return PastryParams(num_leaves=8, local_tuning_interval=10.0)


class BambooLogic(PastryLogic):
    """Bamboo (src/overlay/bamboo/Bamboo.{h,cc}): Pastry variant whose
    maintenance is periodic push-pull instead of reactive repair — which
    is exactly this implementation's native style (module docstring)."""

    def __init__(self, spec: K.KeySpec = K.DEFAULT_SPEC,
                 params: PastryParams | None = None,
                 lcfg: lk_mod.LookupConfig | None = None,
                 app=None):
        super().__init__(spec, params or bamboo_params(), lcfg, app)

    def stat_spec(self) -> stats_mod.StatSpec:
        return super().stat_spec()
