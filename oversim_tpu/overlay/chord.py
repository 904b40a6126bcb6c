"""Chord ring DHT as vectorized per-node logic.

TPU-native rebuild of the reference Chord (src/overlay/chord/Chord.{h,cc} +
ChordSuccessorList/ChordFingerTable), with protocol semantics preserved and
state held as structure-of-arrays:

  * successor list [N, S] node slots kept ring-distance sorted (reference
    ChordSuccessorList: std::map sorted by distance from own key);
  * predecessor [N]; finger table [N, B] (B = key bits) with 2^i targets;
  * aggressive join (rpcJoin Chord.cc:917: responsible node adopts the
    joiner as predecessor, hints its old predecessor in the JoinResponse,
    and sends NEWSUCCESSORHINT to the old predecessor);
  * periodic stabilize (StabilizeCall → successor's predecessor; adopt if
    in (me, succ); then NotifyCall; NotifyResponse carries the successor's
    successor list which replaces ours — Chord.cc:793/rpcStabilize/
    rpcNotify/handleRpcNotifyResponse);
  * periodic fixfingers (handleFixFingersTimerExpired Chord.cc:845: route
    a lookup to me+2^i for every non-trivial finger — offset greater than
    the distance to the successor; trivial fingers are removed).  We mark
    those fingers dirty and repair them one lookup at a time, chained off
    lookup completions (same convergence, bounded concurrency);
  * predecessor liveness via periodic ping (checkPredecessorDelay=5s,
    default.ini:172, handleCheckPredecessorTimerExpired);
  * failure repair (handleFailedNode Chord.cc:502: drop from successor
    list / fingers / predecessor, immediate re-stabilize, rejoin when the
    last successor is gone);
  * findNode (Chord.cc:548): siblings if responsible; successor list if
    key in (me, succ]; otherwise closest preceding node over fingers +
    successor list (closestPreceedingNode Chord.cc:602).

Defaults follow simulations/default.ini:167-183 (joinDelay 10s,
stabilizeDelay 20s, fixfingersDelay 120s, checkPredecessorDelay 5s,
successorListSize 8, aggressiveJoinMode true, iterative routing).

The embedded tier-1 app is pluggable in spirit; this first slice wires
KBRTestApp (apps/kbrtest.py) directly.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from oversim_tpu import stats as stats_mod
from oversim_tpu.apps import base as app_base
from oversim_tpu.apps import kbrtest
from oversim_tpu.apps.kbrtest import KbrTestApp
from oversim_tpu.common import lookup as lk_mod
from oversim_tpu.common import malicious as mal_mod
from oversim_tpu.common import ncs as ncs_mod
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

# node lifecycle (reference BaseOverlay States, BaseOverlay.h:86-102)
DEAD, JOINING, READY = 0, 1, 2

# lookup purposes (owner dispatch tags)
P_JOIN, P_FINGER, P_APP, P_MERGE = 1, 2, 3, 4

# what the overlay's upkeep did, cumulative (stats "c:" counters, gated
# like every counter on the measurement phase): timer rounds started,
# the RPC calls they sent, and the FindNode calls of the lookups by
# purpose (the finger repair's against the application's)
MAINTENANCE_COUNTERS = (
    "chord_stab_rounds", "chord_notify_calls", "chord_notify_taken",
    "chord_pred_pings", "chord_fix_rounds", "chord_fix_lookups",
    "chord_fix_ended", "chord_fix_calls", "chord_app_calls",
    "chord_join_passed", "chord_join_dropped")

JOIN_HOP_MAX = 32  # hops a JoinCall is passed on towards its key
BCAST_FANOUT = 8   # broadcast copies per hop (≥ distinct fingers at test N)


@dataclasses.dataclass(frozen=True)
class ChordParams:
    """default.ini:167-183."""

    join_delay: float = 10.0
    stabilize_delay: float = 20.0
    fixfingers_delay: float = 120.0
    check_pred_delay: float = 5.0
    succ_size: int = 8
    aggressive_join: bool = True
    rpc_timeout: float = 1.5        # rpcUdpTimeout, default.ini:483
    # BootstrapList::mergeOverlayPartitions (BootstrapList.cc:273,
    # default.ini:436-438, default false): periodically look up an
    # oracle-drawn candidate's key through the OWN overlay; if the
    # lookup does not find the candidate, it lives in a foreign
    # partition (two formed rings after a network heal) →
    # joinForeignPartition: adopt it as a successor candidate and hint
    # ourselves to it, knitting the rings back together
    merge_partitions: bool = False
    merge_interval: float = 20.0


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ChordState:
    state: jnp.ndarray         # [N] i32 DEAD/JOINING/READY
    pred: jnp.ndarray          # [N] i32
    succ: jnp.ndarray          # [N, S] i32 ring-sorted, NO_NODE padded
    finger: jnp.ndarray        # [N, B] i32
    finger_dirty: jnp.ndarray  # [N, B] bool
    t_join: jnp.ndarray        # [N] i64
    t_stab: jnp.ndarray        # [N] i64
    t_fix: jnp.ndarray         # [N] i64
    t_cp: jnp.ndarray          # [N] i64
    stab_op: jnp.ndarray       # [N] i32 0=idle 1=stabilize 2=notify pending
    stab_dst: jnp.ndarray      # [N] i32
    stab_to: jnp.ndarray       # [N] i64
    cp_to: jnp.ndarray         # [N] i64 pending predecessor-ping timeout
    cp_dst: jnp.ndarray        # [N] i32 the node that ping targeted
    lk: lk_mod.LookupState     # [N, L, ...]
    rr: rt_mod.RouteState      # [N, Q, ...] pending-ACK recursive routes
    cp_sent: jnp.ndarray       # [N] i64 — predecessor-ping send time (RTT)
    t_merge: jnp.ndarray       # [N] i64 — partition-merge probe timer
    t_nps: jnp.ndarray         # [N] i64 — GNP/NPS landmark-probe timer
    nps_dst: jnp.ndarray       # [N] i32 — in-flight probe target
    nps_sent: jnp.ndarray      # [N] i64 — its send time (RTT base)
    ncs: ncs_mod.NcsState      # [N, ...] coordinates (common/ncs.py:
                               # vivaldi/svivaldi or gnp/nps landmark
                               # layers, Nps.h:119-133)
    nc: nc_mod.NcState         # [N, C] RTT cache (adaptive RPC timeouts)
    app: object                # [N, ...] tier-app state (apps/base.py)
    app_glob: object           # simulation-global app state (oracle maps)


def _sort_lanes(dist, payload):
    return K.sort_by_distance(dist, payload, approx=True)[1]


def _lex_argmin(dist):
    """Index of the lexicographically smallest [C, KL] distance row (by
    ``_sort_lanes``' comparator, the lowest index among equal rows).
    Both callers keep ONE candidate (findNode runs with
    numRedundantNodes = 1, a node has one predecessor), so the index is
    reduced, not sorted for."""
    return K.argmin_by_distance(dist, approx=True)


class ChordLogic:
    """Implements the engine logic interface (engine/logic.py docstring)."""

    def __init__(self, spec: K.KeySpec = K.DEFAULT_SPEC,
                 params: ChordParams = ChordParams(),
                 lcfg: lk_mod.LookupConfig = lk_mod.LookupConfig(),
                 app=None,
                 mparams: mal_mod.MaliciousParams = mal_mod.MaliciousParams(),
                 ncs_params: ncs_mod.NcsParams = ncs_mod.NcsParams(),
                 nc_params: nc_mod.NcParams = nc_mod.NcParams(),
                 rcfg: rt_mod.RouteConfig | None = None):
        """``rcfg=None`` keeps the reference Chord default (iterative
        routing, default.ini:167-183); a RouteConfig switches the app
        data path to the recursive family — rcfg.mode selects
        SEMI_RECURSIVE / FULL_RECURSIVE / RECURSIVE_SOURCE_ROUTING
        (verify.ini's ChordSource config = mode="source").  App lookups
        (M_LOOKUP / DHT LookupCall) stay on the iterative engine either
        way (documented deviation: the reference wraps them in
        RecursiveLookup; the sibling resolution is equivalent, the
        FindNode round trips differ)."""
        self.key_spec = spec
        self.p = params
        self.lcfg = lcfg
        self.app = app or KbrTestApp()
        self.rcfg = rcfg
        # hand the routing mode to the app's RPC-reply path (BaseRpc
        # response transport follows the call's routingType)
        if rcfg is not None and getattr(self.app, "rcfg", "no") is None:
            self.app.rcfg = rcfg
        # overlay->distance for the DHT maintenance responsibility
        # filter: Chord responsibility is CLOCKWISE distance key→node
        # (successor-of-key holds it; Chord::distance, Chord.cc:1403)
        if getattr(self.app, "dist_fn", "no") is None:
            self.app.dist_fn = (
                lambda nk, rk: K.ring_distance(rk, nk, spec))
        self.mp = mparams
        self.ncs = ncs_params
        self.ncp = nc_params
        if spec.lanes < ncs_params.dims + (
                2 if ncs_params.is_landmark_type else 1):
            raise ValueError("key lanes too narrow for the NCS piggyback")
        self._pow2 = K.pow2_table(spec)          # [B, KL] finger offsets

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

    def stat_spec(self) -> stats_mod.StatSpec:
        app = self.app.stat_spec()
        return stats_mod.StatSpec(
            scalars=tuple(app["scalars"]) + ("lookup_hops",),
            hists=tuple(app["hists"]),
            counters=tuple(app["counters"]) + (
                "chord_joins", "lookup_success", "lookup_failed",
                "route_dropped") + MAINTENANCE_COUNTERS,
        )

    def split(self, st: ChordState):
        return dataclasses.replace(st, app_glob=None), st.app_glob

    def merge(self, node_part: ChordState, glob):
        return dataclasses.replace(node_part, app_glob=glob)

    def post_step(self, ctx, st: ChordState, events):
        app, glob = self.app.post_step(ctx, st.app, st.app_glob, events)
        return dataclasses.replace(st, app=app, app_glob=glob)

    def init(self, rng, n: int) -> ChordState:
        s, b = self.p.succ_size, self.key_spec.bits
        return ChordState(
            state=jnp.zeros((n,), I32),
            pred=jnp.full((n,), NO_NODE, I32),
            succ=jnp.full((n, s), NO_NODE, I32),
            finger=jnp.full((n, b), NO_NODE, I32),
            finger_dirty=jnp.zeros((n, b), bool),
            t_join=jnp.full((n,), T_INF, I64),
            t_stab=jnp.full((n,), T_INF, I64),
            t_fix=jnp.full((n,), T_INF, I64),
            t_cp=jnp.full((n,), T_INF, I64),
            stab_op=jnp.zeros((n,), I32),
            stab_dst=jnp.full((n,), NO_NODE, I32),
            stab_to=jnp.full((n,), T_INF, I64),
            cp_to=jnp.full((n,), T_INF, I64),
            cp_dst=jnp.full((n,), NO_NODE, I32),
            lk=jax.vmap(lambda _: lk_mod.init(self.lcfg, self.key_spec.lanes))(
                jnp.arange(n)),
            rr=jax.vmap(lambda _: rt_mod.init(
                self.rcfg or rt_mod.RouteConfig(), self.key_spec.lanes,
                16))(jnp.arange(n)),
            cp_sent=jnp.zeros((n,), I64),
            t_merge=jnp.full((n,), T_INF, I64),
            t_nps=jnp.full((n,), T_INF, I64),
            nps_dst=jnp.full((n,), NO_NODE, I32),
            nps_sent=jnp.zeros((n,), I64),
            ncs=ncs_mod.init(rng, n, self.ncs),
            nc=nc_mod.init(n, self.ncp),
            app=self.app.init(n),
            app_glob=self.app.glob_init(rng),
        )

    def reset(self, st: ChordState, clear, join, t_now, rng) -> ChordState:
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

    def ready_mask(self, st: ChordState):
        return st.state == READY

    def next_event(self, st: ChordState):
        joining = st.state == JOINING
        ready = st.state == READY
        t = jnp.where(joining, st.t_join, T_INF)
        for timer in (st.t_stab, st.t_fix, st.t_cp):
            t = jnp.minimum(t, jnp.where(ready, timer, T_INF))
        t = jnp.minimum(t, st.stab_to)
        t = jnp.minimum(t, st.cp_to)
        t = jnp.minimum(t, jnp.where(ready, self.app.next_event(st.app),
                                     T_INF))
        t = jnp.minimum(t, jax.vmap(lk_mod.next_event)(st.lk))
        if self.ncs.is_landmark_type:
            t = jnp.minimum(t, jnp.where(ready, st.t_nps, T_INF))
        if self.p.merge_partitions:
            t = jnp.minimum(t, jnp.where(ready, st.t_merge, T_INF))
        if self.rcfg is not None:
            t = jnp.minimum(t, jax.vmap(rt_mod.next_event)(st.rr))
        return t

    def ring_starter(self, st: ChordState, alive, t_end):
        """The one joiner that may start a ring in a tick that finds no
        node READY (``Ctx.starter``): of the nodes whose join timer is
        due, the earliest, ties to the lowest slot; NO_NODE where none
        is due.  Upstream's first node is READY inside its own creation
        event, so its second node always finds it; two nodes created
        inside one tick window must not both see an empty overlay and
        start a ring each (the rings interleave and weak stabilization
        never merges them)."""
        due = alive & (st.state == JOINING) & (st.t_join < t_end)
        first = jnp.argmin(jnp.where(due, st.t_join, T_INF)).astype(I32)
        return jnp.where(jnp.any(due), first, NO_NODE)

    # -- internals (all per-node; vmapped by the engine) ---------------------

    @scoped("chord.find_node")
    def _find_node(self, ctx, st, me_key, node_idx, key):
        """Chord::findNode (Chord.cc:548) with numRedundantNodes=1.

        Returns (next_hop i32 slot, is_sibling bool).  NO_NODE next hop
        when not READY (reference returns an empty NodeVector).  One next
        hop is wanted, so the closest preceding node is an argmin over
        the fingers and successors and nothing is sorted.
        """
        spec = self.key_spec
        ready = st.state == READY
        pred_ok = st.pred != NO_NODE
        pk = ctx.keys[jnp.maximum(st.pred, 0)]
        succ0 = st.succ[0]
        has_succ = succ0 != NO_NODE
        s0k = ctx.keys[jnp.maximum(succ0, 0)]

        alone = ~pred_ok & ~has_succ
        is_sib = ready & (alone
                          | (~pred_ok & K.eq(key, me_key))
                          | (pred_ok & K.is_between_r(key, pk, me_key, spec)))
        succ_case = ready & has_succ & ~is_sib & K.is_between_r(
            key, me_key, s0k, spec)

        # closest preceding node over fingers + successor list
        cands = jnp.concatenate([st.finger, st.succ])
        cks = ctx.keys[jnp.maximum(cands, 0)]
        me_b = jnp.broadcast_to(me_key, cks.shape)
        key_b = jnp.broadcast_to(key, cks.shape)
        usable = (cands != NO_NODE) & (cands != node_idx) & K.is_between_r(
            cks, me_b, key_b, spec)
        d = K.sub(key_b, cks, spec)            # clockwise candidate→key
        d = jnp.where(usable[:, None], d, UMAX)
        best = cands[_lex_argmin(d)]
        best = jnp.where(jnp.any(usable), best, succ0)  # fallback: successor

        nxt = jnp.where(is_sib, node_idx, jnp.where(succ_case, succ0, best))
        nxt = jnp.where(ready, nxt, NO_NODE)
        return nxt, is_sib

    def _respond_find(self, ctx, st, me_key, node_idx, m, rmax, pad_nodes):
        """FindNode RPC server payload: ([rmax] result slots, sibling
        flag).  Overridable hop-choice hook (Koorde)."""
        nxt, sib = self._find_node(ctx, st, me_key, node_idx, m.key)
        sib_set = pad_nodes(jnp.concatenate([node_idx[None], st.succ]))
        return jnp.where(
            sib, sib_set,
            jnp.full((rmax,), NO_NODE, I32).at[0].set(nxt)), sib

    def _extra_timers(self, ctx, st, ob, me_key, node_idx, t0, t_end, rng):
        """Subclass timer hook (Koorde de Bruijn stabilization)."""
        return st

    def _on_completion(self, ctx, st, ob, li, comp, en, suc, res, t0):
        """Subclass lookup-purpose dispatch hook (per completion slot)."""
        return st

    def _succ_sorted(self, ctx, me_key, node_idx, cands):
        """Ring-distance-sorted unique successor list from candidate slots
        (ChordSuccessorList semantics: excludes self, sorted by clockwise
        distance from own key, capacity S)."""
        s = self.p.succ_size
        c = cands
        ck = ctx.keys[jnp.maximum(c, 0)]
        bad = (c == NO_NODE) | (c == node_idx) | K.dup_mask(c)
        d = K.sub(ck, jnp.broadcast_to(me_key, ck.shape), self.key_spec)
        d = jnp.where(bad[:, None], UMAX, d)
        c_s, bad_s = _sort_lanes(d, (c, bad.astype(I32)))
        out = jnp.where(bad_s[:s] != 0, NO_NODE, c_s[:s])
        if out.shape[0] < s:
            out = jnp.concatenate(
                [out, jnp.full((s - out.shape[0],), NO_NODE, I32)])
        return out

    def _succ_add(self, ctx, me_key, node_idx, succ, node, en):
        node = jnp.where(en, node, NO_NODE)
        return self._succ_sorted(ctx, me_key, node_idx,
                                 jnp.concatenate([succ, node[None]]))

    @scoped("chord.failed")
    def _handle_failed(self, ctx, st, me_key, node_idx, failed, now):
        """Chord::handleFailedNode (Chord.cc:502) for a [F] vector of
        failed slots (NO_NODE entries ignored) — one sort for the whole
        batch instead of one call per failure source."""
        failed = jnp.where(failed == node_idx, NO_NODE, failed)
        any_failed = jnp.any(failed != NO_NODE)

        def hit(x):
            return (x[..., None] == failed).any(-1) & (x != NO_NODE)

        en = any_failed
        pred = jnp.where(hit(st.pred), NO_NODE, st.pred)
        was_succ0 = hit(st.succ[0])
        succ_masked = jnp.where(hit(st.succ), NO_NODE, st.succ)
        succ = self._succ_sorted(ctx, me_key, node_idx, succ_masked)
        succ = jnp.where(en, succ, st.succ)
        fhit = hit(st.finger)
        finger = jnp.where(fhit, NO_NODE, st.finger)
        finger_dirty = st.finger_dirty | fhit
        t_stab = jnp.where(was_succ0, now, st.t_stab)

        # lost the last successor while READY → rejoin
        # (handleFailedNode: successorList empty → cancel timers, wait for
        # join; BaseOverlay rejoinOnFailure path)
        rejoin = en & (st.state == READY) & (succ[0] == NO_NODE)
        st = dataclasses.replace(
            st, pred=pred, succ=succ, finger=finger,
            finger_dirty=finger_dirty, t_stab=t_stab)
        fresh_lk = lk_mod.init(self.lcfg, self.key_spec.lanes)
        st = dataclasses.replace(
            st,
            state=jnp.where(rejoin, JOINING, st.state),
            t_join=jnp.where(rejoin, now, st.t_join),
            t_stab=jnp.where(rejoin, T_INF, st.t_stab),
            t_fix=jnp.where(rejoin, T_INF, st.t_fix),
            t_cp=jnp.where(rejoin, T_INF, st.t_cp),
            stab_op=jnp.where(rejoin, 0, st.stab_op),
            stab_to=jnp.where(rejoin, T_INF, st.stab_to),
            cp_to=jnp.where(rejoin, T_INF, st.cp_to),
            cp_dst=jnp.where(rejoin, NO_NODE, st.cp_dst),
            lk=select_tree(rejoin, fresh_lk, st.lk),
            app=self.app.on_stop(st.app, rejoin))
        return st

    def _become_ready(self, ctx, st, en, now, rng):
        """Schedule periodic protocols on entering READY.

        Join response handler schedules immediate stabilize + fixfingers
        (handleRpcJoinResponse Chord.cc: scheduleAt(simTime(), ...))."""
        p = self.p
        st = dataclasses.replace(
            st,
            state=jnp.where(en, READY, st.state),
            t_join=jnp.where(en, T_INF, st.t_join),
            t_stab=jnp.where(en, now, st.t_stab),
            t_fix=jnp.where(en, now, st.t_fix),
            t_cp=jnp.where(en, now + jnp.int64(int(p.check_pred_delay * NS)),
                           st.t_cp),
            app=self.app.on_ready(st.app, en, now, rng))
        if self.ncs.is_landmark_type:
            st = dataclasses.replace(st, t_nps=jnp.where(
                en, now + jnp.int64(int(0.3 * NS)), st.t_nps))
        if p.merge_partitions:
            st = dataclasses.replace(st, t_merge=jnp.where(
                en, now + jnp.int64(int(p.merge_interval * NS)),
                st.t_merge))
        return st

    # -- the per-node step ---------------------------------------------------

    def step(self, ctx, st, msgs, rng, node_idx, *, outbox_slots, rmax):
        p, lcfg, spec = self.p, self.lcfg, self.key_spec
        ob = Outbox(outbox_slots, spec.lanes, rmax)
        me_key = ctx.keys[node_idx]
        rpc_to_ns = jnp.int64(int(p.rpc_timeout * NS))
        rngs = jax.random.split(rng, 7)
        t0 = ctx.t_start

        def pad_nodes(vec):
            out = jnp.full((rmax,), NO_NODE, I32)
            k = min(vec.shape[0], rmax)
            return out.at[:k].set(vec[:k])

        def metric_fn(cand_slots, target):
            ck = ctx.keys[jnp.maximum(cand_slots, 0)]
            return K.sub(jnp.broadcast_to(target, ck.shape), ck, spec)

        # event accumulators
        ev = app_base.AppEvents()
        joins_cnt = jnp.int32(0)
        anyfail_cnt = jnp.int32(0)  # failed lookups of any purpose
        lksucc_cnt = jnp.int32(0)
        routedrop_cnt = jnp.int32(0)
        old_succ = st.succ                   # update() delta base
        old_pred = st.pred

        # --------------------------------------------- inbox (batched) -----
        # Kind-major batching: each message kind is handled in ONE masked
        # pass over the R inbox slots (kinds in the original per-slot
        # order) instead of R unrolled handler chains — the round-2 tick
        # graph was op-issue-bound on exactly that unrolling (52k eqns).
        # Within-window ordering across slots is already relaxed by the
        # engine (engine/sim.py docstring); the kind-major permutation is
        # the same relaxation.  Each kind's reads see every earlier
        # kind's writes; response payloads read the state as of their
        # kind's turn (the unrolled loop exposed mid-loop state the same
        # way, just slot-major).
        v_r = msgs.valid                                     # [R]
        now_r = msgs.t_deliver                               # [R]
        r_in = v_r.shape[0]

        # FindNode + sibling flag for EVERY inbox slot's key (findNodeRpc,
        # BaseOverlay.cc:1841), vmapped.  Subclasses (Koorde) override
        # _respond_find for their own hop choice + lookup extension
        # handling.  Computed before the recursive-route pre-pass: route
        # forwarding reuses these results as its next-hop candidates, and
        # decapsulation preserves msgs.key, so the flags stay valid for
        # the decapsulated inner kinds below.
        res_b, sib_b = jax.vmap(
            lambda mm: self._respond_find(ctx, st, me_key, node_idx, mm,
                                          rmax, pad_nodes))(msgs)

        if self.rcfg is not None:
            rcfg = self.rcfg
            # per-hop ACKs for routes we forwarded (NextHopResponse)
            st = dataclasses.replace(st, rr=rt_mod.on_acks(
                st.rr, dataclasses.replace(
                    msgs,
                    valid=v_r & (msgs.kind == wire.KBR_ROUTE_ACK))))

            # source-routed replies: pop one hop / deliver at originator
            en_sro = v_r & (msgs.kind == wire.KBR_SROUTE)
            deliver_sr = rt_mod.sroute_step(ob, msgs)
            msgs = dataclasses.replace(
                msgs,
                kind=jnp.where(deliver_sr, msgs.d, msgs.kind),
                src=jnp.where(deliver_sr, msgs.c, msgs.src),
                valid=v_r & (~en_sro | deliver_sr))
            v_r = msgs.valid

            # recursive route pre-pass (sendToKey recursive branch,
            # BaseOverlay.cc:1441-1581): ACK the last hop, then either
            # decapsulate (responsible) or forward to the first
            # candidate surviving loop detection.  visitedHops ride
            # msgs.nodes; the originator is visited[0].
            en_rt = v_r & (msgs.kind == wire.KBR_ROUTE) & (
                st.state == READY)
            ob.send(en_rt & (msgs.nonce > 0), now_r, msgs.src,
                    wire.KBR_ROUTE_ACK, nonce=msgs.nonce,
                    size_b=wire.BASE_CALL_B)
            deliver_rt = en_rt & sib_b
            # overlay routing ext (Koorde routeKey/step) rides the head
            # of msgs.nodes; the visited list occupies the tail.  The
            # responder writes its updated ext into res_b's tail (the
            # same packing _respond_find uses for FINDNODE_RES), which
            # must be masked out of the next-hop candidate scan.
            ew = rcfg.ext_words
            if ew:
                vis_in = msgs.nodes[:, ew:]
                cands = res_b.at[:, rmax - ew:].set(NO_NODE)
            else:
                vis_in = msgs.nodes
                cands = res_b
            nxt_v, found_v = jax.vmap(
                rt_mod.pick_next_hop, in_axes=(0, 0, 0, 0, None, 0))(
                cands, vis_in, msgs.src, vis_in[:, 0], node_idx,
                sib_b)
            fwd = en_rt & ~sib_b & found_v & (msgs.hops < rcfg.hop_max)
            if hasattr(self.app, "forward"):
                # Common API forward() (BaseApp.h:214 / callForward,
                # BaseOverlay.cc:523): the app may veto messages being
                # routed THROUGH this node (veto = drop, the reference's
                # forwardResponse without a next hop)
                fwd = fwd & ~self.app.forward(st.app, msgs, ctx)
            # visitedHops appended unconditionally (deviation: the
            # reference records only for source/recordRoute and falls
            # back to last-hop-only loop detection in semi/full —
            # recording always makes pick_next_hop's visited check real
            # in every mode for a few wire bytes; pastry.py does the same)
            visited2 = rt_mod.append_visited(vis_in, node_idx, fwd)
            if ew:
                nodes_out = jnp.concatenate(
                    [res_b[:, rmax - ew:], visited2], axis=1)
            else:
                nodes_out = visited2
            st = dataclasses.replace(st, rr=rt_mod.forward_batch(
                st.rr, ob, fwd, now_r, nxt_v, key=msgs.key, inner=msgs.d,
                a=msgs.a, b=msgs.b, c=msgs.c, hops=msgs.hops + 1,
                stamp=msgs.stamp, size_b=msgs.size_b - rcfg.overhead_b,
                visited=nodes_out, cfg=rcfg))
            routedrop_cnt += jnp.sum((en_rt & ~sib_b & ~fwd).astype(I32))
            # decapsulate at the responsible node: the payload kind takes
            # over and src becomes the originator; handlers below (incl.
            # the app kinds) consume it as if it arrived directly.
            # msgs.nodes keeps the visitedHops for source-routed replies.
            msgs = dataclasses.replace(
                msgs,
                kind=jnp.where(deliver_rt, msgs.d, msgs.kind),
                src=jnp.where(deliver_rt, msgs.nodes[:, ew], msgs.src),
                valid=v_r & (~en_rt | deliver_rt))
            v_r = msgs.valid

        en_call = v_r & (msgs.kind == wire.FINDNODE_CALL)
        # byzantine switches (common/malicious.py; statically no-op by
        # default).  Only the wire copy is attacked; the honest ``sib_b``
        # feeds the app deliver check below (wrong-node detection,
        # KBRTestApp.cc:252-286 oracle check)
        if self.mp.active:
            res_atk, sib_atk, respond = jax.vmap(
                lambda rr, ss, rg: mal_mod.attack_findnode(
                    ctx, self.mp, node_idx, rr, ss, rg))(
                res_b, sib_b, jax.random.split(rngs[6], r_in))
        else:
            res_atk, sib_atk, respond = res_b, sib_b, jnp.ones((r_in,), bool)
        n_res = jnp.sum((res_atk != NO_NODE).astype(I32), axis=1)
        ob.send(en_call & respond, now_r, msgs.src, wire.FINDNODE_RES,
                key=msgs.key, a=msgs.a, b=msgs.b, c=sib_atk.astype(I32),
                nodes=res_atk,
                size_b=wire.BASE_CALL_B + 1 + wire.NODEHANDLE_B * n_res)

        # FindNodeResponse -> lookup engine (one batched pass)
        en_res = v_r & (msgs.kind == wire.FINDNODE_RES)
        st = dataclasses.replace(st, lk=lk_mod.on_responses(
            st.lk, dataclasses.replace(msgs, valid=en_res), metric_fn, lcfg))

        with scope("chord.join"):
            # JoinCall (rpcJoin, Chord.cc:917) — response compiled BEFORE
            # the aggressive-join mutations (reference order).  The call
            # carries the joiner's slot (``a``) and key: it may have been
            # passed on, so its sender need not be the joiner.
            #
            # RESPONSIBILITY: upstream's joiner sends the call to its
            # lookup's result, which is stale when joins come faster than a
            # lookup takes (ten a second against five hops of two ticks).
            # Accepting a joiner whose key is NOT in (pred, me] would drag
            # pred backwards, widen this node's claimed range, attract more
            # mis-routed joins, and cascade into a loopy succ permutation
            # that weak stabilization provably cannot repair.  A receiver
            # that is not responsible passes the call on towards the key, as
            # a routed call travels (findNode's next hop), so it reaches
            # the node responsible NOW; only a call out of hops is dropped,
            # and that joiner's join timer retries with a fresh lookup.
            # So (pred, me] of the READY nodes always tile the key space:
            # every accepted join splits one range in two.
            en_jc = v_r & (msgs.kind == wire.CHORD_JOIN_CALL) & (
                st.state == READY)
            joiner = msgs.a                                      # [R]
            jk = msgs.key                                        # [R, KL]
            alone = (st.pred == NO_NODE) & (st.succ[0] == NO_NODE)
            pk_j = ctx.keys[jnp.maximum(st.pred, 0)]
            responsible = alone | ((st.pred != NO_NODE) & K.is_between(
                jk, jnp.broadcast_to(pk_j, jk.shape),
                jnp.broadcast_to(me_key, jk.shape), spec))
            # a call of a joiner already taken (its timer fired twice)
            stale_jc = (joiner == node_idx) | (joiner == st.pred)
            en = en_jc & responsible & ~stale_jc & ~K.dup_mask(
                jnp.where(en_jc, joiner, NO_NODE))
            if type(self)._respond_find is ChordLogic._respond_find:
                nxt_j = res_b[:, 0]          # findNode of the call's key
            else:
                nxt_j = jax.vmap(lambda kk: self._find_node(
                    ctx, st, me_key, node_idx, kk)[0])(jk)
            astray = en_jc & ~responsible & ~stale_jc
            pass_on = astray & (msgs.hops < JOIN_HOP_MAX) & (
                nxt_j != NO_NODE) & (nxt_j != node_idx)
            ob.send(pass_on, now_r, nxt_j, wire.CHORD_JOIN_CALL, key=jk,
                    a=joiner, hops=msgs.hops + 1,
                    size_b=wire.BASE_CALL_B + wire.NODEHANDLE_B)
            joinpass_cnt = jnp.sum(pass_on.astype(I32))
            joindrop_cnt = jnp.sum((astray & ~pass_on).astype(I32))
            # the joiners of one window are taken in KEY order, clockwise
            # from the predecessor (from this node where it is alone), as
            # if their calls had arrived one after the other in that order:
            # each is told the one before it as its predecessor (the first:
            # the old predecessor), the last becomes this node's
            # predecessor, and every response carries them all beside the
            # successor list, so each finds its successors among them.
            # Taken in inbox order, three joiners of an alone node left one
            # of them nobody's successor: a side branch that the nodes
            # joining through it prolong, and the ring never closes.
            base_j = jnp.where(st.pred != NO_NODE, pk_j, me_key)
            d_j = K.sub(jk, jnp.broadcast_to(base_j, jk.shape), spec)
            d_j = jnp.where(en[:, None], d_j, UMAX)
            (perm_j,) = _sort_lanes(d_j, (jnp.arange(r_in, dtype=I32),))
            ord_j, ord_en, now_o = joiner[perm_j], en[perm_j], now_r[perm_j]
            n_acc = jnp.sum(en.astype(I32))
            any_en = n_acc > 0
            first_j = ord_j[0]
            last_j = ord_j[jnp.clip(n_acc - 1, 0, r_in - 1)]
            hint0 = jnp.where(alone, node_idx, st.pred)
            pred_hint = jnp.concatenate([hint0[None], ord_j[:-1]])
            ob.send(ord_en, now_o, ord_j, wire.CHORD_JOIN_RES, a=pred_hint,
                    nodes=pad_nodes(jnp.concatenate(
                        [jnp.where(ord_en, ord_j, NO_NODE), st.succ])),
                    size_b=wire.BASE_CALL_B
                    + wire.NODEHANDLE_B * (p.succ_size + n_acc))
            if p.aggressive_join:
                # the old predecessor learns its new successor: the first
                # joiner in key order
                ob.send(any_en & (st.pred != NO_NODE), now_o[0], st.pred,
                        wire.CHORD_SUCC_HINT, a=first_j,
                        size_b=wire.BASE_CALL_B + wire.NODEHANDLE_B)
                pred2 = jnp.where(any_en, last_j, st.pred)
            else:
                pred2 = st.pred
            # an empty successor list is seeded by the joiner next clockwise
            succ2 = jnp.where(any_en & (st.succ[0] == NO_NODE),
                              st.succ.at[0].set(first_j), st.succ)
            st = dataclasses.replace(st, pred=pred2, succ=succ2)

            # JoinResponse (handleRpcJoinResponse): merge every enabled
            # response's successor candidates in one sorted pass
            en = v_r & (msgs.kind == wire.CHORD_JOIN_RES) & (st.state == JOINING)
            cand_jr = jnp.where(
                en[:, None],
                jnp.concatenate([msgs.nodes, msgs.src[:, None]], axis=1),
                NO_NODE).reshape(-1)                         # [R*(RMAX+1)]
            succ3 = self._succ_sorted(ctx, me_key, node_idx, cand_jr)
            got_succ = jnp.any(en) & (succ3[0] != NO_NODE)
            joins_cnt += got_succ.astype(I32)
            hint_ok = en & (msgs.a != NO_NODE)
            last_h = jnp.clip(r_in - 1 - jnp.argmax(hint_ok[::-1]).astype(I32),
                              0, r_in - 1)
            st = dataclasses.replace(
                st,
                succ=jnp.where(got_succ, succ3, st.succ),
                pred=jnp.where(got_succ & jnp.any(hint_ok)
                               & jnp.bool_(p.aggressive_join),
                               msgs.a[last_h], st.pred))
            st = self._become_ready(ctx, st, got_succ,
                                    jnp.max(jnp.where(en, now_r, 0)), rngs[0])

        with scope("chord.stabilize"):
            # StabilizeCall -> reply with predecessor (rpcStabilize)
            en = v_r & (msgs.kind == wire.CHORD_STABILIZE_CALL) & (
                st.state == READY)
            ob.send(en, now_r, msgs.src, wire.CHORD_STABILIZE_RES, a=st.pred,
                    size_b=wire.BASE_CALL_B + wire.NODEHANDLE_B)

            # StabilizeResponse (handleRpcStabilizeResponse): at most one
            # inbox slot matches the single in-flight stabilize RPC
            en_sr = v_r & (msgs.kind == wire.CHORD_STABILIZE_RES) & (
                st.state == READY) & (st.stab_op == 1) & (
                msgs.src == st.stab_dst)
            any_sr = jnp.any(en_sr)
            r_sr = jnp.clip(jnp.argmax(en_sr).astype(I32), 0, r_in - 1)
            src_sr = msgs.src[r_sr]
            now_sr = msgs.t_deliver[r_sr]
            cand = msgs.a[r_sr]
            ck = ctx.keys[jnp.maximum(cand, 0)]
            s0 = st.succ[0]
            s0k = ctx.keys[jnp.maximum(s0, 0)]
            succ_empty = s0 == NO_NODE
            adopt = (cand != NO_NODE) & (succ_empty | K.is_between(
                ck, me_key, s0k, spec))
            new_node = jnp.where(adopt, cand,
                                 jnp.where(succ_empty, src_sr, NO_NODE))
            succ4 = self._succ_add(ctx, me_key, node_idx, st.succ, new_node,
                                   any_sr)
            succ4 = jnp.where(any_sr, succ4, st.succ)
            # notify the (possibly new) successor
            fire_nc = any_sr & (succ4[0] != NO_NODE)
            ob.send(fire_nc, now_sr, succ4[0], wire.CHORD_NOTIFY_CALL,
                    size_b=wire.BASE_CALL_B + wire.NODEHANDLE_B)
            st = dataclasses.replace(
                st, succ=succ4,
                stab_op=jnp.where(any_sr, 2, st.stab_op),
                stab_dst=jnp.where(any_sr, succ4[0], st.stab_dst),
                stab_to=jnp.where(any_sr, now_sr + rpc_to_ns, st.stab_to))

            # NotifyCall (rpcNotify): adopt closer predecessor, reply with
            # successor list.  The sequential fold adopts every strictly
            # closer notifier in turn; its fixed point is the clockwise-
            # closest enabled source — pick it with one distance argmin.
            en = v_r & (msgs.kind == wire.CHORD_NOTIFY_CALL) & (
                st.state == READY)
            sk = ctx.keys[jnp.maximum(msgs.src, 0)]              # [R, KL]
            pk = ctx.keys[jnp.maximum(st.pred, 0)]
            closer = en & ((st.pred == NO_NODE) | K.is_between(
                sk, jnp.broadcast_to(pk, sk.shape),
                jnp.broadcast_to(me_key, sk.shape), spec))
            d_nc = K.sub(jnp.broadcast_to(me_key, sk.shape), sk, spec)
            d_nc = jnp.where(closer[:, None], d_nc, UMAX)
            best_r = _lex_argmin(d_nc)
            any_nc = jnp.any(closer)
            newpred_src = msgs.src[best_r]
            succ5 = jnp.where(any_nc & (st.succ[0] == NO_NODE),
                              st.succ.at[0].set(newpred_src), st.succ)
            st = dataclasses.replace(
                st, pred=jnp.where(any_nc, newpred_src, st.pred), succ=succ5)
            ob.send(en, now_r, msgs.src, wire.CHORD_NOTIFY_RES,
                    nodes=pad_nodes(st.succ),
                    size_b=wire.BASE_CALL_B
                    + wire.NODEHANDLE_B * (p.succ_size + 1))

            # NotifyResponse (handleRpcNotifyResponse): replace successor
            # list with successor's list; at most one slot matches the
            # in-flight notify
            fin_m = v_r & (msgs.kind == wire.CHORD_NOTIFY_RES) & (
                st.stab_op == 2) & (msgs.src == st.stab_dst)
            any_fin = jnp.any(fin_m)
            r_nr = jnp.clip(jnp.argmax(fin_m).astype(I32), 0, r_in - 1)
            take_nr = any_fin & (st.state == READY) & (
                msgs.src[r_nr] == st.succ[0])
            succ6 = self._succ_sorted(
                ctx, me_key, node_idx,
                jnp.concatenate([msgs.nodes[r_nr][:p.succ_size],
                                 msgs.src[r_nr][None]]))
            st = dataclasses.replace(
                st, succ=jnp.where(take_nr, succ6, st.succ),
                stab_op=jnp.where(any_fin, 0, st.stab_op),
                stab_to=jnp.where(any_fin, T_INF, st.stab_to))

            # NewSuccessorHint (handleNewSuccessorHint): adopt hinted nodes
            # inside (me, succ0) — batch = one sorted merge of all taken
            # hints.  Documented deviation from the sequential fold: the fold
            # re-checks each hint against the SHRINKING (me, succ0) interval,
            # so with two same-tick hints h1 < h2 < succ0 it would adopt only
            # h1; the batch checks both against the pre-tick succ0 and keeps
            # both (h2 is still a valid, closer-than-old-succ0 successor that
            # the next stabilize round would have learned anyway).
            en = v_r & (msgs.kind == wire.CHORD_SUCC_HINT) & (st.state == READY)
            hk = ctx.keys[jnp.maximum(msgs.a, 0)]
            s0k2 = ctx.keys[jnp.maximum(st.succ[0], 0)]
            take = en & (msgs.a != NO_NODE) & (
                (st.succ[0] == NO_NODE)
                | K.is_between(hk, jnp.broadcast_to(me_key, hk.shape),
                               jnp.broadcast_to(s0k2, hk.shape), spec))
            succ7 = self._succ_sorted(
                ctx, me_key, node_idx,
                jnp.concatenate([st.succ, jnp.where(take, msgs.a, NO_NODE)]))
            st = dataclasses.replace(
                st, succ=jnp.where(jnp.any(take), succ7, st.succ))

        with scope("chord.broadcast"):
            # KBR broadcast (Chord::forwardBroadcast, Chord.cc:1410-1446):
            # walk fingers+successors by DESCENDING clockwise distance;
            # every candidate inside (me, limit) gets a copy whose limit
            # is the previous candidate, shrinking the covered range.
            # Fan-out is capped at BCAST_FANOUT copies with the closest
            # successor always last so the near range stays covered
            # (distinct fingers ~ log N; the cap only binds at huge N).
            # The per-slot fanout walk is vmapped; all copies leave in one
            # vector send.
            en_b = v_r & (msgs.kind == wire.BROADCAST) & (st.state == READY)
            bc = jnp.concatenate([st.finger, st.succ])
            bck = ctx.keys[jnp.maximum(bc, 0)]
            me_bb = jnp.broadcast_to(me_key, bck.shape)
            dup_bc = K.dup_mask(bc)
            d_bc = K.sub(bck, me_bb, spec)          # cw distance me -> cand

            def _bcast_slot(mkey, enb):
                lim_b = jnp.broadcast_to(mkey, bck.shape)
                ok_b = (bc != NO_NODE) & (bc != node_idx) & ~dup_bc \
                    & K.is_between(bck, me_bb, lim_b, spec)
                db = jnp.where(ok_b[:, None], d_bc, jnp.zeros_like(d_bc))
                (bc_s,) = _sort_lanes(db, (jnp.where(ok_b, bc, NO_NODE),))
                n_ok = jnp.sum(ok_b.astype(I32))
                cdim = bc_s.shape[0]
                j = jnp.arange(BCAST_FANOUT, dtype=I32)
                idx_j = jnp.clip(cdim - 1 - j, 0, cdim - 1)
                tgt = jnp.where(j < n_ok, bc_s[idx_j], NO_NODE)  # far -> near
                # copy j's limit = the previous copy's target key (j=0: mkey)
                tk = ctx.keys[jnp.maximum(tgt, 0)]               # [F, KL]
                lim = jnp.concatenate([mkey[None], tk[:-1]], axis=0)
                fire = enb & (tgt != NO_NODE)
                # cap bound (> FANOUT candidates): one extra copy to the
                # NEAREST candidate carries the remaining (me, limit) range,
                # which it re-splits recursively — without it the near range
                # would never see the broadcast.  fire_n requires n_ok >
                # FANOUT, so the last fired copy is always index FANOUT-1.
                near = bc_s[jnp.clip(cdim - n_ok, 0, cdim - 1)]
                fire_n = enb & (n_ok > BCAST_FANOUT) & (near != NO_NODE)
                lim_n = tk[BCAST_FANOUT - 1]
                return tgt, lim, fire, near, fire_n, lim_n

            tgt_v, lim_v, fire_v, near_v, firen_v, limn_v = jax.vmap(
                _bcast_slot)(msgs.key, en_b)
            bshape = (r_in, BCAST_FANOUT)
            ob.send(fire_v.reshape(-1),
                    jnp.broadcast_to(now_r[:, None], bshape).reshape(-1),
                    tgt_v.reshape(-1), wire.BROADCAST,
                    key=lim_v.reshape(r_in * BCAST_FANOUT, -1),
                    a=jnp.broadcast_to(msgs.a[:, None], bshape).reshape(-1),
                    b=jnp.broadcast_to(msgs.b[:, None], bshape).reshape(-1),
                    hops=jnp.broadcast_to((msgs.hops + 1)[:, None],
                                          bshape).reshape(-1),
                    size_b=wire.BASE_CALL_B + 20)
            ob.send(firen_v, now_r, jnp.maximum(near_v, 0), wire.BROADCAST,
                    key=limn_v, a=msgs.a, b=msgs.b, hops=msgs.hops + 1,
                    size_b=wire.BASE_CALL_B + 20)

        # app-owned message kinds (Common API deliver path,
        # BaseApp::handleCommonAPIMessage), with the per-slot findNode
        # sibling flags computed above
        if hasattr(self.app, "on_msgs"):
            st = dataclasses.replace(st, app=self.app.on_msgs(
                st.app, msgs, ctx, ob, ev, sib_b, node_idx=node_idx))
        else:
            for r in range(r_in):
                st = dataclasses.replace(st, app=self.app.on_msg(
                    st.app, msgs.slot(r), ctx, ob, ev, sib_b[r]))

        with scope("chord.ping"):
            # ping (predecessor liveness + generic); the response piggybacks
            # this node's Vivaldi coordinates (the reference attaches
            # ncsInfo[] to every RPC response, CommonMessages.msg:233 /
            # NeighborCache piggybacking)
            if self.ncs.is_landmark_type:
                ping_key = ncs_mod.pack_wire_nps(
                    st.ncs.coords, st.ncs.error, st.ncs.layer, spec.lanes)
            else:
                ping_key = ncs_mod.pack_wire(st.ncs.coords, st.ncs.error,
                                             spec.lanes)
            ob.send(v_r & (msgs.kind == wire.PING_CALL), now_r, msgs.src,
                    wire.PING_RES, a=msgs.a, key=ping_key,
                    size_b=wire.BASE_CALL_B + 4 * (
                        self.ncs.dims
                        + (2 if self.ncs.is_landmark_type else 1)))
            # ping response: at most one slot matches the in-flight
            # predecessor ping (a == -3 marks NPS probe pongs — the probe
            # target can BE the predecessor, so src alone is ambiguous)
            en_p = v_r & (msgs.kind == wire.PING_RES) & (
                msgs.src == st.cp_dst) & (msgs.a != -3)
            any_p = jnp.any(en_p)
            r_p = jnp.clip(jnp.argmax(en_p).astype(I32), 0, r_in - 1)
            now_p = msgs.t_deliver[r_p]
            rtt_s = (now_p - st.cp_sent).astype(jnp.float32) / NS
            nc_row = dict(peer=st.nc.peer, rtt_mean=st.nc.rtt_mean,
                          rtt_var=st.nc.rtt_var, last=st.nc.last,
                          live=st.nc.live)
            nc_row = nc_mod.insert_rtt(nc_row, msgs.src[r_p], rtt_s, now_p,
                                       any_p)
            st = dataclasses.replace(st, nc=nc_mod.NcState(**nc_row))
            if self.ncs.ncs_type in ("vivaldi", "svivaldi"):
                xj, ej = ncs_mod.unpack_wire(msgs.key[r_p], self.ncs.dims)
                me_ncs = dict(coords=st.ncs.coords, height=st.ncs.height,
                              error=st.ncs.error, loss=st.ncs.loss)
                upd = ncs_mod.update(me_ncs, jnp.where(any_p, rtt_s, -1.0),
                                     xj, ej, jnp.float32(0.0), self.ncs)
                st = dataclasses.replace(
                    st, ncs=dataclasses.replace(st.ncs, **upd))
            st = dataclasses.replace(
                st, cp_to=jnp.where(any_p, T_INF, st.cp_to),
                cp_dst=jnp.where(any_p, NO_NODE, st.cp_dst))

        # GNP/NPS landmark-probe pong: RTT sample to the reference point
        # → triangulate own coords (Nps::doTriangulation equivalent) and
        # adopt layer = max(ref layers)+1 (Nps.h:119-133)
        if self.ncs.is_landmark_type:
            en_np = (v_r & (msgs.kind == wire.PING_RES)
                     & (msgs.src == st.nps_dst) & (msgs.a == -3))
            any_np = jnp.any(en_np)
            r_np = jnp.clip(jnp.argmax(en_np).astype(I32), 0, r_in - 1)
            xj, ej, lj = ncs_mod.unpack_wire_nps(msgs.key[r_np],
                                                 self.ncs.dims)
            rtt_np = (msgs.t_deliver[r_np] - st.nps_sent).astype(
                jnp.float32) / NS
            me_np = dict(coords=st.ncs.coords, error=st.ncs.error,
                         layer=st.ncs.layer, ref_rtt=st.ncs.ref_rtt,
                         ref_xy=st.ncs.ref_xy, ref_layer=st.ncs.ref_layer,
                         ref_n=st.ncs.ref_n)
            me_np = ncs_mod.nps_add_sample(
                me_np, jnp.where(any_np, rtt_np, -1.0), xj, lj, self.ncs)
            me_np = ncs_mod.nps_solve(me_np, self.ncs)
            st = dataclasses.replace(
                st,
                ncs=dataclasses.replace(st.ncs, **{
                    k: jnp.where(any_np, v, getattr(st.ncs, k))
                    for k, v in me_np.items()}),
                nps_dst=jnp.where(any_np, NO_NODE, st.nps_dst))

        # ------------------------------------------------------- timers ----
        t_end = ctx.t_end

        with scope("chord.join"):
            # join (joinOverlay / handleJoinTimerExpired Chord.cc:758)
            en_j = (st.state == JOINING) & (st.t_join < t_end)
            now_j = jnp.maximum(st.t_join, t0)
            boot = ctx.sample_ready(rngs[1], node_idx)
            no_join_lk = ~jnp.any(st.lk.active & (st.lk.purpose == P_JOIN))
            # no node READY: ONE due joiner starts the ring (ring_starter);
            # the others keep their timer, so they stay due (and awake) and
            # find the starter READY in the next tick
            alone_start = en_j & (boot == NO_NODE) & (ctx.starter == node_idx)
            st = self._become_ready(ctx, st, alone_start, now_j, rngs[2])
            joins_cnt += alone_start.astype(I32)
            slot, have = lk_mod.free_slot(st.lk)
            start_join = en_j & (boot != NO_NODE) & no_join_lk & have
            seed = jnp.full((lcfg.frontier,), NO_NODE, I32).at[0].set(boot)
            st = dataclasses.replace(st, lk=lk_mod.start(
                st.lk, start_join, slot, P_JOIN, 0, me_key, seed, now_j, lcfg))
            st = dataclasses.replace(st, t_join=jnp.where(
                en_j & (boot != NO_NODE),
                now_j + jnp.int64(int(p.join_delay * NS)), st.t_join))

        # GNP/NPS probe timer: measure RTT to a reference point — GNP
        # pings landmarks only; NPS alternates landmarks and random
        # positioned nodes so higher layers form (Nps.h:119-133)
        if self.ncs.is_landmark_type:
            en_np = (st.state == READY) & (st.t_nps < t_end)
            now_np = jnp.maximum(st.t_nps, t0)
            # a probe still unanswered when the next probe tick arrives
            # has timed out (probe_interval >> rpc timeout): clear it so
            # a lost pong never wedges probing (lost-probe expiry)
            st = dataclasses.replace(st, nps_dst=jnp.where(
                en_np, NO_NODE, st.nps_dst))
            r_a, r_b, r_c = jax.random.split(
                jax.random.fold_in(rngs[5], 17), 3)
            lm = jax.random.randint(r_a, (), 0,
                                    self.ncs.num_landmarks).astype(I32)
            lm = jnp.where(ctx.alive[jnp.clip(lm, 0, ctx.alive.shape[0]
                                              - 1)], lm, NO_NODE)
            if self.ncs.ncs_type == "nps":
                alt = ctx.sample_ready(r_b, node_idx)
                use_alt = (jax.random.uniform(r_c, ()) < 0.5) & (
                    alt != NO_NODE)
                target_np = jnp.where(use_alt, alt, lm)
            else:
                target_np = lm
            target_np = jnp.where(target_np == node_idx, NO_NODE,
                                  target_np)
            fire_np = en_np & (target_np != NO_NODE)
            ob.send(fire_np, now_np, target_np, wire.PING_CALL,
                    a=jnp.int32(-3), size_b=wire.BASE_CALL_B)
            st = dataclasses.replace(
                st,
                nps_dst=jnp.where(fire_np, target_np, st.nps_dst),
                nps_sent=jnp.where(fire_np, now_np, st.nps_sent),
                t_nps=jnp.where(
                    en_np, now_np + jnp.int64(
                        int(self.ncs.probe_interval * NS)), st.t_nps))

        # partition-merge probe (BootstrapList::locateBootstrapNode,
        # BootstrapList.cc:268-280; mergeOverlayPartitions): look up an
        # oracle-drawn candidate's key through the OWN overlay — the
        # completion handler detects a foreign partition when the lookup
        # does not come back with the candidate itself
        if p.merge_partitions:
            en_m = (st.state == READY) & (st.t_merge < t_end)
            now_m = jnp.maximum(st.t_merge, t0)
            cand_m = ctx.sample_ready(jax.random.fold_in(rngs[1], 23),
                                      node_idx)
            ck_m = ctx.keys[jnp.maximum(cand_m, 0)]
            nxt_m, sib_m = self._find_node(ctx, st, me_key, node_idx,
                                           ck_m)
            no_merge_lk = ~jnp.any(st.lk.active & (st.lk.purpose
                                                   == P_MERGE))
            slot, have = lk_mod.free_slot(st.lk)
            start_m = (en_m & (cand_m != NO_NODE) & (cand_m != node_idx)
                       & ~sib_m & no_merge_lk & have
                       & (nxt_m != NO_NODE))
            seed_m = jnp.full((lcfg.frontier,), NO_NODE, I32).at[0].set(
                nxt_m)
            st = dataclasses.replace(st, lk=lk_mod.start(
                st.lk, start_m, slot, P_MERGE, cand_m, ck_m, seed_m,
                now_m, lcfg))
            st = dataclasses.replace(st, t_merge=jnp.where(
                en_m, now_m + jnp.int64(int(p.merge_interval * NS)),
                st.t_merge))

        with scope("chord.stabilize"):
            # stabilize (handleStabilizeTimerExpired)
            en_s = (st.state == READY) & (st.t_stab < t_end)
            now_s = jnp.maximum(st.t_stab, t0)
            has_succ = st.succ[0] != NO_NODE
            fire_s = en_s & has_succ
            ob.send(fire_s, now_s, st.succ[0], wire.CHORD_STABILIZE_CALL,
                    size_b=wire.BASE_CALL_B)
            st = dataclasses.replace(
                st,
                stab_op=jnp.where(fire_s, 1, st.stab_op),
                stab_dst=jnp.where(fire_s, st.succ[0], st.stab_dst),
                stab_to=jnp.where(fire_s, now_s + rpc_to_ns, st.stab_to),
                t_stab=jnp.where(en_s, now_s + jnp.int64(
                    int(p.stabilize_delay * NS)), st.t_stab))

        with scope("chord.fix_fingers"):
            # fixfingers (handleFixFingersTimerExpired): mark non-trivial
            # fingers dirty, remove trivial ones
            due_f = (st.state == READY) & (st.t_fix < t_end)
            en_f = due_f & has_succ
            s0k = ctx.keys[jnp.maximum(st.succ[0], 0)]
            sdist = K.sub(s0k, me_key, spec)                    # me → succ
            nontrivial = K.gt(self._pow2, jnp.broadcast_to(sdist,
                                                           self._pow2.shape))
            st = dataclasses.replace(
                st,
                finger_dirty=jnp.where(en_f, nontrivial, st.finger_dirty),
                finger=jnp.where(en_f & ~nontrivial, NO_NODE, st.finger),
                t_fix=jnp.where(due_f,
                                jnp.maximum(st.t_fix, t0)
                                + jnp.int64(int(p.fixfingers_delay * NS)),
                                st.t_fix))

        # subclass periodic protocols (Koorde de Bruijn timer)
        st = self._extra_timers(ctx, st, ob, me_key, node_idx, t0, t_end,
                                rngs[5])

        with scope("chord.ping"):
            # predecessor check (handleCheckPredecessorTimerExpired)
            en_c = (st.state == READY) & (st.t_cp < t_end)
            now_c = jnp.maximum(st.t_cp, t0)
            fire_c = en_c & (st.pred != NO_NODE) & (st.cp_to == T_INF)
            ob.send(fire_c, now_c, st.pred, wire.PING_CALL,
                    size_b=wire.BASE_CALL_B)
            st = dataclasses.replace(
                st,
                cp_to=jnp.where(fire_c, now_c + rpc_to_ns, st.cp_to),
                cp_dst=jnp.where(fire_c, st.pred, st.cp_dst),
                cp_sent=jnp.where(fire_c, now_c, st.cp_sent),
                t_cp=jnp.where(en_c, now_c + jnp.int64(
                    int(p.check_pred_delay * NS)), st.t_cp))

        # app timer → start an app lookup (KBRTestApp::handleTimerEvent →
        # callRoute → iterative lookup, SURVEY §3.2)
        # graceful-leave: hand app data to the successor and stop
        # firing app tests during the grace window (apps/base.py on_leave)
        st = dataclasses.replace(st, app=app_base.leave_protocol(
            self.app, st.app, ctx, ob, ev, t0, node_idx, st.succ[0],
            st.state == READY))
        en_a = (st.state == READY) & (
            self.app.next_event(st.app) < t_end)
        now_a = jnp.maximum(self.app.next_event(st.app), t0)
        app, req = self.app.on_timer(st.app, en_a, ctx, now_a, rngs[3], ev, node_idx)
        st = dataclasses.replace(st, app=app)
        nxt_a, sib_a = self._find_node(ctx, st, me_key, node_idx, req.key)
        # local responsibility → immediate completion, hopCount 0
        # (sendToKey with local sibling → direct deliver).  The result set
        # is the full sibling set — self + successor list — matching the
        # responder-side FINDNODE_RES payload (Chord::findNode sibling
        # case, Chord.cc:548-560), so numReplica consumers (DHT puts) get
        # the whole replica set for locally-owned keys too.
        local = req.want & sib_a
        res_local = jnp.concatenate([node_idx[None], st.succ])[
            :lcfg.frontier]
        if res_local.shape[0] < lcfg.frontier:
            res_local = jnp.concatenate([res_local, jnp.full(
                (lcfg.frontier - res_local.shape[0],), NO_NODE, I32)])
        slot, have = lk_mod.free_slot(st.lk)
        if self.rcfg is None:
            start_app = req.want & ~sib_a & have & (nxt_a != NO_NODE)
            route_fire = jnp.bool_(False)
        elif hasattr(self.app, "route_policy"):
            # recursive data path (sendToKey recursive branch at the
            # originator): payloads the app declares routable are
            # forwarded hop-by-hop; everything else (lookup test, DHT
            # LookupCall) keeps the iterative engine.  Gated on the app
            # speaking the protocol — an app without route_policy never
            # has its lookups diverted.
            routable, inner_a, is_rpc = self.app.route_policy(req.tag)
            route_fire = req.want & ~sib_a & routable & (nxt_a != NO_NODE)
            ew0 = self.rcfg.ext_words
            vis0 = jnp.full((rmax,), NO_NODE, I32).at[ew0].set(node_idx)
            if ew0:
                # zeroed ext head → the first hop lazily initializes the
                # overlay routing ext (Koorde findDeBruijnHop init path)
                vis0 = vis0.at[:ew0].set(0)
            st = dataclasses.replace(st, rr=rt_mod.forward(
                st.rr, ob, route_fire, now_a, nxt_a, key=req.key,
                inner=inner_a, a=req.tag, b=jnp.int32(0),
                c=ctx.measuring.astype(I32), hops=jnp.int32(1),
                stamp=now_a, size_b=jnp.int32(100), visited=vis0,
                cfg=self.rcfg))
            if hasattr(self.app, "on_route_fired"):
                st = dataclasses.replace(st, app=self.app.on_route_fired(
                    st.app, route_fire & is_rpc, now_a, req.tag))
            start_app = (req.want & ~sib_a & ~routable & have
                         & (nxt_a != NO_NODE))
        else:
            start_app = req.want & ~sib_a & have & (nxt_a != NO_NODE)
            route_fire = jnp.bool_(False)
        # could not even start (no slot / empty local findNode) → failed
        # completion right away
        insta_fail = req.want & ~sib_a & ~start_app & ~route_fire
        st = dataclasses.replace(st, app=self.app.on_lookup_done(
            st.app, app_base.LookupDone(
                en=local | insta_fail, success=local, tag=req.tag,
                target=req.key,
                results=jnp.where(local, res_local, NO_NODE),
                hops=jnp.int32(0), t0=now_a),
            ctx, ob, ev, now_a, node_idx))
        seed = jnp.full((lcfg.frontier,), NO_NODE, I32).at[0].set(nxt_a)
        st = dataclasses.replace(st, lk=lk_mod.start(
            st.lk, start_app, slot, P_APP, req.tag, req.key, seed, now_a,
            lcfg))

        # ------------------------------------------------ lookup timeouts --
        new_lk, failed_nodes, _ = lk_mod.on_timeouts(st.lk, t_end, t0, lcfg)
        st = dataclasses.replace(st, lk=new_lk)

        with scope("chord.failed"):
            # stabilize / notify RPC timeout → failed successor
            en = (st.stab_op != 0) & (st.stab_to < t_end)
            stab_failed = jnp.where(en, st.stab_dst, NO_NODE)
            st = dataclasses.replace(
                st, stab_op=jnp.where(en, 0, st.stab_op),
                stab_to=jnp.where(en, T_INF, st.stab_to))

            # predecessor ping timeout → the PINGED node failed (a predecessor
            # adopted after the ping was sent is NOT dropped)
            en = st.cp_to < t_end
            cp_failed = jnp.where(en, st.cp_dst, NO_NODE)
            st = dataclasses.replace(
                st, cp_to=jnp.where(en, T_INF, st.cp_to),
                cp_dst=jnp.where(en, NO_NODE, st.cp_dst))

        # route-hop ACK timeouts: unresponsive next hops are failures too
        if self.rcfg is not None:
            new_rr, rt_failed, rt_retry = rt_mod.on_timeouts(
                st.rr, t_end, self.rcfg)
            st = dataclasses.replace(st, rr=new_rr)
        else:
            rt_failed = jnp.full((0,), NO_NODE, I32)

        # one batched repair pass for every failure source this tick
        st = self._handle_failed(
            ctx, st, me_key, node_idx,
            jnp.concatenate([failed_nodes, stab_failed[None],
                             cp_failed[None], rt_failed]), t0)

        # reroute parked route messages around the failed hop (it was
        # just dropped from the tables, so findNode picks an alternative;
        # internalHandleRpcTimeout reroute, BaseOverlay.cc:1697-1729).
        # One vmapped findNode over the Q slot keys; a node that became
        # responsible for a parked key meanwhile self-forwards so the
        # message still delivers (pastry.py does the same).
        if self.rcfg is not None:
            ew_q = self.rcfg.ext_words
            nxt_q, sib_q = jax.vmap(
                lambda kk: self._find_node(ctx, st, me_key, node_idx, kk))(
                st.rr.key)
            nxt_q2, found_q = jax.vmap(
                rt_mod.pick_next_hop, in_axes=(0, 0, 0, 0, None, 0))(
                nxt_q[:, None], st.rr.visited[:, ew_q:], rt_failed,
                st.rr.visited[:, ew_q], node_idx, sib_q)
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
        taken = comp["taken"]                                # [L]
        suc_l = comp["success"] & (comp["result"] != NO_NODE)
        pur_l = comp["purpose"]
        res_l = comp["result"]
        comp_hops_ev = (comp["hops"].astype(jnp.float32),
                        taken & comp["success"])
        lksucc_cnt += jnp.sum((taken & suc_l).astype(I32))
        anyfail_cnt += jnp.sum((taken & ~suc_l).astype(I32))

        with scope("chord.join"):
            # join: contact our successor directly (one vector send)
            ob.send(taken & suc_l & (pur_l == P_JOIN), t0, res_l,
                    wire.CHORD_JOIN_CALL, key=me_key, a=node_idx,
                    size_b=wire.BASE_CALL_B + wire.NODEHANDLE_B)

        # partition-merge probe completions (handleLookupResponse,
        # BootstrapList.cc:171-195): the candidate's key resolved to a
        # sibling set that does NOT contain the candidate → it lives in
        # a foreign formed ring.  joinForeignPartition equivalent: adopt
        # it as a successor candidate and hint ourselves to it — the
        # rings then knit via normal stabilize/notify rounds.
        if p.merge_partitions:
            enm_l = taken & (pur_l == P_MERGE) & suc_l
            any_m = jnp.any(enm_l)
            li_m = jnp.clip(jnp.argmax(enm_l).astype(I32), 0,
                            lcfg.slots - 1)
            x_m = comp["aux"][li_m]
            foreign = any_m & jnp.all(comp["results"][li_m] != x_m) & (
                x_m != NO_NODE) & ctx.alive[jnp.maximum(x_m, 0)]
            succ_m = self._succ_sorted(
                ctx, me_key, node_idx,
                jnp.concatenate([st.succ,
                                 jnp.where(foreign, x_m, NO_NODE)[None]]))
            st = dataclasses.replace(
                st, succ=jnp.where(foreign, succ_m, st.succ))
            ob.send(foreign, t0, x_m, wire.CHORD_SUCC_HINT, a=node_idx,
                    size_b=wire.BASE_CALL_B + wire.NODEHANDLE_B)

        with scope("chord.fix_fingers"):
            # finger repair results (one scatter per field)
            enf = taken & (pur_l == P_FINGER)
            fi_l = jnp.clip(comp["aux"], 0, spec.bits - 1)
            st = dataclasses.replace(
                st,
                finger=st.finger.at[jnp.where(enf & suc_l, fi_l, spec.bits)]
                .set(res_l, mode="drop"),
                finger_dirty=st.finger_dirty
                .at[jnp.where(enf, fi_l, spec.bits)].set(False, mode="drop"))

        # app lookups → app completion hook (batched when supported)
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

        # subclass purposes (Koorde de Bruijn resolution) — the per-slot
        # hook only traces when a subclass actually overrides it
        if type(self)._on_completion is not ChordLogic._on_completion:
            for li in range(lcfg.slots):
                st = self._on_completion(
                    ctx, st, ob, li, comp, taken[li], suc_l[li], res_l[li],
                    t0)

        with scope("chord.fix_fingers"):
            # -------------------------------------------- finger repair pump ---
            dirty_any = (st.state == READY) & jnp.any(st.finger_dirty)
            no_finger_lk = ~jnp.any(st.lk.active & (st.lk.purpose == P_FINGER))
            fi = jnp.argmax(st.finger_dirty).astype(I32)
            target = K.add(me_key, self._pow2[fi], spec)
            nxt_f, sib_f = self._find_node(ctx, st, me_key, node_idx, target)
            # responsible ourselves → no finger needed (covered by succ list)
            self_fix = dirty_any & no_finger_lk & sib_f
            st = dataclasses.replace(
                st,
                finger_dirty=jnp.where(self_fix,
                                       st.finger_dirty.at[fi].set(False),
                                       st.finger_dirty))
            slot, have = lk_mod.free_slot(st.lk)
            start_fix = dirty_any & no_finger_lk & ~sib_f & have & (
                nxt_f != NO_NODE)
            seed = jnp.full((lcfg.frontier,), NO_NODE, I32).at[0].set(nxt_f)
            st = dataclasses.replace(st, lk=lk_mod.start(
                st.lk, start_fix, slot, P_FINGER, fi, target, seed, t0, lcfg))

        # ------------------------------------------------------- pump ------
        # adaptive per-destination RPC timeouts from the RTT cache
        # (NeighborCache::getNodeTimeout, NeighborCache.cc:802)
        new_lk, _ = lk_mod.pump(
            st.lk, ob, ctx, node_idx, t0, rngs[4], lcfg,
            timeout_fn=nc_mod.adaptive_timeout_fn(st.nc,
                                                  lcfg.rpc_timeout_ns),
            prox_fn=(nc_mod.prox_fn(st.nc) if lcfg.prox_aware else None))
        # the FindNode calls the pump sent, a lookup slot: the RPC slots
        # it filled (it fills free ones only)
        calls_l = jnp.sum(((new_lk.pending_dst != NO_NODE)
                           & (st.lk.pending_dst == NO_NODE)).astype(I32),
                          axis=1)
        st = dataclasses.replace(st, lk=new_lk)

        # Common API update() (BaseOverlay::callUpdate → BaseApp::update,
        # BaseApp.h:223): nodes that entered the successor list — Chord's
        # replica/sibling set — trigger app re-replication this tick
        if hasattr(self.app, "on_update"):
            new_in = jnp.where(
                (st.succ != NO_NODE)
                & ~jnp.any(st.succ[:, None] == old_succ[None, :], axis=1),
                st.succ, NO_NODE)
            # a NEW PREDECESSOR is an ownership transfer: the joiner
            # inherits the keyspace between the old and new pred, and
            # must receive this node's records for it.  The reference
            # reaches the same spot via the isSiblingFor err-hack
            # (DHT.cc:779-797 "For Chord: we've got a new predecessor"
            # → sendMaintenancePutCall regardless) — without it every
            # join creates a data-less primary and DHT get-success
            # erodes under churn.  Listed FIRST so the app's one-target
            # stager prioritizes the ownership transfer over ordinary
            # succ-list deltas.
            new_pred = jnp.where(
                (st.pred != NO_NODE) & (st.pred != old_pred)
                & (st.pred != node_idx), st.pred, NO_NODE)
            new_in = jnp.concatenate([new_pred[None], new_in])
            st = dataclasses.replace(st, app=self.app.on_update(
                st.app, st.state == READY, ctx, ob, ev, t0, node_idx,
                new_in,
                sib_keys=ctx.keys[jnp.maximum(st.succ, 0)],
                sib_valid=st.succ != NO_NODE,
                urgent=new_pred != NO_NODE))

        # ------------------------------------------------------ events -----
        events = {
            "c:chord_joins": joins_cnt,
            "c:lookup_success": lksucc_cnt,
            "c:lookup_failed": anyfail_cnt,
            "c:route_dropped": routedrop_cnt,
            "c:chord_stab_rounds": fire_s.astype(I32),
            "c:chord_notify_calls": fire_nc.astype(I32),
            "c:chord_notify_taken": take_nr.astype(I32),
            "c:chord_pred_pings": fire_c.astype(I32),
            "c:chord_fix_rounds": due_f.astype(I32),
            "c:chord_fix_lookups": start_fix.astype(I32),
            "c:chord_fix_ended": jnp.sum(enf.astype(I32)),
            "c:chord_fix_calls": jnp.sum(
                jnp.where(new_lk.purpose == P_FINGER, calls_l, 0)),
            "c:chord_app_calls": jnp.sum(
                jnp.where(new_lk.purpose == P_APP, calls_l, 0)),
            "c:chord_join_passed": joinpass_cnt,
            "c:chord_join_dropped": joindrop_cnt,
            "s:lookup_hops": comp_hops_ev,
        }
        ev.finish(events, self.app.hist_map)
        return st, ob, events
