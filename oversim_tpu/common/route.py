"""Recursive KBR routing: per-hop forwarding state machine.

TPU-native rebuild of the reference's generic recursive routing loop
(BaseOverlay::sendToKey SEMI_RECURSIVE branch, BaseOverlay.cc:1441-1581 +
sendRouteMessage :1107; RoutingType enum CommonMessages.msg:130-141).
Semantics implemented:

  * a routed message hops node-to-node; each hop runs the overlay's local
    findNode (recNumRedundantNodes=3 candidates, default.ini:386) and
    forwards to the first candidate that survives loop detection —
    not in visitedHops, not the last hop, not the source node
    (BaseOverlay.cc:1500-1521);
  * no usable candidate → the message is dropped and counted
    (BaseOverlay.cc:1524-1542 "No useful nextHop found");
  * hopCount is carried on the wire and bounded (hopCountMax drop,
    BaseOverlay.cc:1465-1489);
  * optional per-hop acknowledgement (routeMsgAcks, wrapped NextHopCall
    in the reference, BaseOverlay.cc:1107-1147): the forwarding node
    keeps the message in a bounded slot table until the next hop ACKs;
    on timeout the next hop is reported failed (handleFailedNode) and
    the message is re-routed to an alternative candidate
    (internalHandleRpcTimeout :1697-1729), up to ``max_retries`` times.

Wire mapping (engine/pool.py message fields): kind=KBR_ROUTE carries
destKey in ``key``, the encapsulated message kind in ``d``
(BaseRouteMessage encapsulates the payload), the payload scalars in
``a/b/c/stamp/size_b``, the route hop count in ``hops``, the visited-hop
list in ``nodes``, and the per-hop ACK nonce in ``nonce`` (0 = no ACK
requested).  At the responsible node the payload is decapsulated by
re-dispatching the message view with kind := d.

All functions operate on a single node's slice (vmapped by the engine),
mirroring common/lookup.py's structure.

The rule for writes is common/lookup.py's: a write whose index is an ACK
slot of the node (or a slot and a visited column of it) is a SELECT over
the whole leaf by a one-hot mask (``_slot_mask``, ``_put``), never
``leaf.at[slot].set``, and one slot's word at a traced index is read by
a masked sum over the Q slots, never ``leaf[slot]``.  Under the node
step's ``vmap`` an indexed write is one scatter of A updates a leaf, and
on the chip a scatter costs by its updates, the dropped ones too: 61 ns
each, so the Bamboo step's 170 such writes (``forward``'s fourteen leaves
at nine call sites, ``on_ack``, ``reforward``, ``drop_slot``, the visited
append) were 21.5 us each over 352 stepped lanes, a quarter of the tick
of N=1000, whether or not one lane forwarded anything (PERF.md, PR 45
and PR 47).  A leaf is [Q] or [Q, <=8] with Q = 4: the select reads a
few dozen words a lane and fuses with its neighbours.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from oversim_tpu.common import wire
from oversim_tpu.common.lookup import _col_mask, _put, _slot_mask
from oversim_tpu.core.scopes import scoped

I32 = jnp.int32
I64 = jnp.int64
U32 = jnp.uint32
NO_NODE = jnp.int32(-1)
T_INF = jnp.int64(2**62)

# what a hop does, cumulative (stats "c:" counters of an overlay that
# counts its routed path, gated like every counter on the measurement
# phase): KBR_ROUTE hops sent (a payload's first hop, every forward,
# every reroute), hops whose ACK came, hops whose ACK timed out, the
# timed-out hops sent again to another candidate, hops sent with no ACK
# slot free (un-ACKed: the message is never the price of a full table),
# payloads decapsulated at the node that holds itself responsible, and
# the payloads dropped, by cause: no candidate survived loop detection,
# or the hop count reached ``hop_max``.  Every hop sent ends in exactly
# one of acked, timed out, un-ACKed or still pending:
#   route_forwarded = route_acked + route_ack_timeouts
#                     + route_unacked_table_full + pending slots
ROUTE_COUNTERS = (
    "route_forwarded", "route_acked", "route_ack_timeouts",
    "route_rerouted", "route_unacked_table_full", "route_delivered",
    "route_dropped_no_candidate", "route_dropped_hop_bound")


@dataclasses.dataclass(frozen=True)
class RouteConfig:
    """Static knobs (reference BaseOverlay params)."""

    slots: int = 4              # Q — in-flight ACK-pending msgs per node
    max_retries: int = 2        # reroutes after a hop timeout
    hop_max: int = 32           # hopCountMax equivalent (drop bound)
    ack_timeout_ns: int = 1_500_000_000   # rpcUdpTimeout (NextHopCall)
    route_acks: bool = True     # routeMsgAcks (default.ini:245 for pastry)
    overhead_b: int = 28        # BaseRouteMessage header (destKey+visited)
    # RoutingType (CommonMessages.msg:130-141) for the recursive family:
    #   "semi"   SEMI_RECURSIVE_ROUTING    — replies travel direct UDP
    #   "full"   FULL_RECURSIVE_ROUTING    — replies routed by the
    #            originator's nodeId key (BaseOverlay.cc:1813-1819)
    #   "source" RECURSIVE_SOURCE_ROUTING  — visitedHops recorded on the
    #            request, replies source-routed back along the reversed
    #            path (BaseOverlay.cc:888-908 visited recording)
    mode: str = "semi"
    record_route: bool = False  # recordRoute param (BaseOverlay.cc:137)
    # head words of the wire ``nodes`` field reserved for an overlay
    # routing extension that travels WITH the routed message (the
    # reference attaches overlay ext messages to BaseRouteMessage, e.g.
    # KoordeFindNodeExtMessage routeKey/step — Koorde.cc:293-358); the
    # visited list occupies nodes[ext_words:].  0 for stateless-per-hop
    # overlays (Chord, Kademlia, Pastry, EpiChord).
    ext_words: int = 0

    @property
    def records_visited(self) -> bool:
        return self.mode == "source" or self.record_route


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RouteState:
    """One node's Q pending-ACK route slots ([N, Q, ...] at rest)."""

    active: jnp.ndarray    # [Q] bool
    gen: jnp.ndarray       # [Q] i32
    dst: jnp.ndarray       # [Q] i32 — awaiting ACK from
    t_to: jnp.ndarray      # [Q] i64
    retries: jnp.ndarray   # [Q] i32
    key: jnp.ndarray       # [Q, KL] u32 — destKey
    inner: jnp.ndarray     # [Q] i32 — encapsulated kind
    a: jnp.ndarray         # [Q] i32
    b: jnp.ndarray         # [Q] i32
    c: jnp.ndarray         # [Q] i32
    hops: jnp.ndarray      # [Q] i32 — hop count already on the wire copy
    stamp: jnp.ndarray     # [Q] i64
    size_b: jnp.ndarray    # [Q] i32
    visited: jnp.ndarray   # [Q, V] i32 — visitedHops of the sent copy


def init(cfg: RouteConfig, kl: int, visited_cap: int) -> RouteState:
    q = cfg.slots
    return RouteState(
        active=jnp.zeros((q,), bool),
        gen=jnp.zeros((q,), I32),
        dst=jnp.full((q,), NO_NODE, I32),
        t_to=jnp.full((q,), T_INF, I64),
        retries=jnp.zeros((q,), I32),
        key=jnp.zeros((q, kl), U32),
        inner=jnp.zeros((q,), I32),
        a=jnp.zeros((q,), I32),
        b=jnp.zeros((q,), I32),
        c=jnp.zeros((q,), I32),
        hops=jnp.zeros((q,), I32),
        stamp=jnp.zeros((q,), I64),
        size_b=jnp.zeros((q,), I32),
        visited=jnp.full((q, visited_cap), NO_NODE, I32),
    )


@scoped("route.forward")
def pick_next_hop(cands, visited, last_hop, src_node, self_idx, is_sib):
    """Loop-detection candidate scan (BaseOverlay.cc:1500-1521).

    ``cands`` [C] candidate slots in preference order; returns
    (next_hop, found bool).  A candidate is rejected if it is the last
    hop (and not us), already visited, the source node (and we aren't),
    or ourselves while not sibling.
    """
    in_visited = (cands[:, None] == visited[None, :]).any(-1)
    bad = ((cands == NO_NODE)
           | ((cands == last_hop) & (cands != self_idx))
           | in_visited
           | ((cands == src_node) & (self_idx != src_node))
           | ((cands == self_idx) & ~is_sib))
    ok = ~bad
    found = jnp.any(ok)
    nxt = cands[jnp.argmax(ok)]
    return jnp.where(found, nxt, NO_NODE), found


def _route_nonce(slot, gen, q: int):
    """Nonzero ACK nonce encoding (slot, gen)."""
    return 1 + slot + q * (gen & jnp.int32(0x003FFFFF))


def _park(rt: RouteState, sel, **new):
    """``rt`` with a fresh hop's copy in the slots ``sel`` [Q] marks:
    ``new`` names every leaf but ``active`` and ``retries``, each value
    one slot's (broadcast over the slot axis) or a [Q, ...] row a slot."""
    return dataclasses.replace(
        rt, active=rt.active | sel, retries=_put(rt.retries, sel, 0),
        **{name: _put(getattr(rt, name), sel, value)
           for name, value in new.items()})


@scoped("route.forward")
def forward(rt: RouteState, ob, en, now, next_hop, *, key, inner, a, b, c,
            hops, stamp, size_b, visited, cfg: RouteConfig):
    """Send one route hop; when ACKs are on, also park a copy in a free
    slot for reroute-on-timeout (sendRouteMessage + NextHopCall wrap).

    ``visited`` is the [V] visitedHops INCLUDING ourselves (the caller
    appends self before forwarding — recordRoute semantics).
    Returns rt'.  If no slot is free the message is sent un-ACKed (the
    reference's RPC table is unbounded; losing the reroute option is the
    bounded-memory tradeoff, never the message itself).
    """
    q = rt.active.shape[0]
    if not cfg.route_acks:
        ob.send(en, now, next_hop, wire.KBR_ROUTE, key=key, nonce=0,
                hops=hops, a=a, b=b, c=c, d=inner, nodes=visited,
                stamp=stamp, size_b=size_b + cfg.overhead_b)
        return rt

    free = ~rt.active
    slot = jnp.argmax(free).astype(I32)
    use = en & jnp.any(free)
    sel = _slot_mask(q, slot, use)                         # [Q]
    gen = jnp.sum(jnp.where(sel, rt.gen, 0), dtype=I32) + 1
    nonce = jnp.where(use, _route_nonce(slot, gen, q), 0)
    ob.send(en, now, next_hop, wire.KBR_ROUTE, key=key, nonce=nonce,
            hops=hops, a=a, b=b, c=c, d=inner, nodes=visited,
            stamp=stamp, size_b=size_b + cfg.overhead_b)
    return _park(rt, sel, gen=gen, dst=next_hop,
                 t_to=now + cfg.ack_timeout_ns, key=key, inner=inner, a=a,
                 b=b, c=c, hops=hops, stamp=stamp, size_b=size_b,
                 visited=visited[:rt.visited.shape[1]])


@scoped("route.forward")
def forward_batch(rt: RouteState, ob, en, now, next_hop, *, key, inner, a,
                  b, c, hops, stamp, size_b, visited, cfg: RouteConfig):
    """Vector-valued :func:`forward`: ``en``/``now``/``next_hop`` and every
    field carry a leading [R] axis (one lane per inbox slot).  The whole
    batch leaves in ONE Outbox send; ACK bookkeeping allocates the j-th
    enabled lane the j-th free slot (same sort-free rank trick as
    engine/pool.alloc, R and Q both small).  Lanes beyond the free-slot
    supply are sent un-ACKed, like the scalar path on a full table."""
    q = rt.active.shape[0]
    r = en.shape[0]
    if not cfg.route_acks:
        ob.send(en, now, next_hop, wire.KBR_ROUTE, key=key, nonce=0,
                hops=hops, a=a, b=b, c=c, d=inner, nodes=visited,
                stamp=stamp, size_b=size_b + cfg.overhead_b)
        return rt

    # rank of each enabled lane / each free slot; lane j parks in the
    # free slot of its own rank, so an enabled lane holds a slot no other
    # lane holds and a slot takes its one lane's value by a sum over R
    lane_rank = jnp.cumsum(en.astype(I32)) - 1            # [R]
    free = ~rt.active
    slot_rank = jnp.cumsum(free.astype(I32)) - 1          # [Q]
    hit = (en[:, None] & free[None, :]
           & (lane_rank[:, None] == slot_rank[None, :]))  # [R, Q]
    parked = jnp.any(hit, axis=1)                          # [R]
    lane_slot = jnp.sum(jnp.where(hit, jnp.arange(q, dtype=I32), 0),
                        axis=1, dtype=I32)
    gen = jnp.sum(jnp.where(hit, rt.gen, 0), axis=1, dtype=I32) + 1
    nonce = jnp.where(parked, _route_nonce(lane_slot, gen, q), 0)
    ob.send(en, now, next_hop, wire.KBR_ROUTE, key=key, nonce=nonce,
            hops=hops, a=a, b=b, c=c, d=inner, nodes=visited,
            stamp=stamp, size_b=size_b + cfg.overhead_b)

    def of_slot(leaf, value):
        """[Q, ...] of ``leaf``'s dtype: slot s's row is the value of the
        lane that hits it (``value`` [R, ...] or one for all lanes)."""
        value = jnp.asarray(value, leaf.dtype)
        value = jnp.broadcast_to(value, (r,) + leaf.shape[1:])
        at = hit.reshape(hit.shape + (1,) * (leaf.ndim - 1))
        return jnp.sum(jnp.where(at, value[:, None], 0), axis=0,
                       dtype=leaf.dtype)

    new = dict(gen=gen, dst=next_hop, t_to=now + cfg.ack_timeout_ns,
               key=key, inner=inner, a=a, b=b, c=c, hops=hops, stamp=stamp,
               size_b=size_b, visited=visited[:, :rt.visited.shape[1]])
    return _park(rt, jnp.any(hit, axis=0),
                 **{name: of_slot(getattr(rt, name), value)
                    for name, value in new.items()})


def _ack_hit(rt: RouteState, m):
    """[Q] bool (``m``'s fields scalars) or [R, Q] (an [R] inbox axis):
    the pending slot that the KBR_ROUTE_ACK ``m`` answers (its nonce
    names the slot and the slot's ``gen``; the slot awaits ``m.src``),
    none for any other message."""
    q = rt.active.shape[0]
    nonce = jnp.asarray(m.nonce)[..., None]
    ok = jnp.asarray(m.valid)[..., None] & (nonce > 0)
    return (_slot_mask(q, (nonce - 1) % q, ok) & rt.active
            & ((rt.gen & jnp.int32(0x003FFFFF)) == (nonce - 1) // q)
            & (rt.dst == jnp.asarray(m.src)[..., None]))


def _free(rt: RouteState, en):
    """``rt`` with the slots ``en`` [Q] marks free and their timers off."""
    return dataclasses.replace(
        rt,
        active=rt.active & ~en,
        t_to=jnp.where(en, T_INF, rt.t_to))


@scoped("route.acks")
def on_acks(rt: RouteState, m):
    """Batched :func:`on_ack`: ``m`` fields carry an [R] inbox axis.  A
    slot is freed where any lane's ACK matches it."""
    return _free(rt, jnp.any(_ack_hit(rt, m), axis=0))


def append_visited(visited, self_idx, en):
    """recordRoute semantics (BaseOverlay.cc:893-898): append ``self_idx``
    to each enabled lane's [R, V] visited list (first NO_NODE slot; a full
    list keeps its prefix — bounded-width deviation, overflow harmless:
    loop detection just loses the oldest hops)."""
    vcap = visited.shape[1]
    n_vis = jnp.sum((visited != NO_NODE).astype(I32), axis=1)   # [R]
    at = _col_mask(en, vcap, jnp.minimum(n_vis, vcap - 1))
    return _put(visited, at, self_idx)


def sroute_send(ob, en, now, *, path, responder, inner, key, a, hops,
                stamp, size_b, overhead_b=28):
    """Emit a source-routed reply along the reversed ``path`` [R, V]
    (the request's visitedHops; path[0] is the originator).  The wire
    cursor ``b`` indexes the NEXT receiver: we send to path[last] with
    b=last; each hop at cursor j>0 forwards to path[j-1] with b=j-1;
    the receiver at b==0 is the originator and delivers (wire.KBR_SROUTE).
    """
    n_path = jnp.sum((path != NO_NODE).astype(I32), axis=-1)    # [R]
    last = jnp.maximum(n_path - 1, 0)
    first_dst = jnp.take_along_axis(
        path, last[:, None], axis=1)[:, 0] if path.ndim == 2 else \
        path[last]
    en = en & (n_path > 0)
    ob.send(en, now, first_dst, wire.KBR_SROUTE, key=key, a=a,
            b=last, c=responder, d=inner, nodes=path, hops=hops,
            stamp=stamp, size_b=size_b + overhead_b)


def sroute_step(ob, msgs, overhead_b=28):
    """One source-route hop for an [R] inbox batch (the intermediate-hop
    pop of the reference's nextHops source route, BaseOverlay.cc:896-907).

    Returns ``deliver`` [R] — lanes whose receiver is the originator
    (cursor 0); the caller rewrites kind := d, src := c for those lanes.
    Forwarding lanes are sent here."""
    en = msgs.valid & (msgs.kind == wire.KBR_SROUTE)
    j = msgs.b
    deliver = en & (j <= 0)
    fwd = en & (j > 0)
    jc = jnp.clip(j - 1, 0, msgs.nodes.shape[-1] - 1)
    nxt = jnp.take_along_axis(msgs.nodes, jc[:, None], axis=1)[:, 0]
    ob.send(fwd & (nxt != NO_NODE), msgs.t_deliver, nxt, wire.KBR_SROUTE,
            key=msgs.key, a=msgs.a, b=jc, c=msgs.c, d=msgs.d,
            nodes=msgs.nodes, hops=msgs.hops + 1, stamp=msgs.stamp,
            size_b=msgs.size_b)
    return deliver


def reply(ob, cfg: RouteConfig, en, now, msgs, ctx, node_idx, inner_kind,
          *, key=None, a=0, stamp=0, size_b=40):
    """Send an RPC reply for decapsulated routed calls ``msgs`` [R] in the
    transport the routing mode dictates (BaseRpc::internalSendRpcResponse
    transport choice, BaseOverlay.cc:1790-1825):

      semi   → direct UDP to the originator (msgs.src after decap);
      full   → KBR_ROUTE keyed to the originator's nodeId, re-entering the
               overlay via a self-send (visited starts at [self]);
      source → KBR_SROUTE along the request's reversed visitedHops
               (rides msgs.nodes through decapsulation).
    """
    if key is None:
        key = msgs.key
    ew = cfg.ext_words
    if cfg.mode == "full":
        # fresh route back to the originator's nodeId: the ext head (if
        # any) starts zeroed so the first hop lazily initializes it, and
        # the visited list starts at [self]
        vis0 = jnp.full(msgs.nodes.shape, NO_NODE, I32).at[:, ew].set(
            node_idx)
        if ew:
            vis0 = vis0.at[:, :ew].set(0)
        ob.send(en, now, node_idx, wire.KBR_ROUTE,
                key=ctx.keys[jnp.maximum(msgs.src, 0)], nonce=0,
                hops=0, a=a, d=inner_kind, nodes=vis0, stamp=stamp,
                size_b=size_b + cfg.overhead_b)
    elif cfg.mode == "source":
        sroute_send(ob, en, now, path=msgs.nodes[:, ew:],
                    responder=node_idx,
                    inner=inner_kind, key=key, a=a, hops=0, stamp=stamp,
                    size_b=size_b, overhead_b=cfg.overhead_b)
    else:
        ob.send(en, now, msgs.src, inner_kind, key=key, a=a, stamp=stamp,
                size_b=size_b)


@scoped("route.acks")
def on_ack(rt: RouteState, m):
    """Consume a KBR_ROUTE_ACK (NextHopResponse): free the matched slot."""
    return _free(rt, _ack_hit(rt, m))


@scoped("route.timeouts")
def on_timeouts(rt: RouteState, t_end, cfg: RouteConfig):
    """Expire pending ACKs due before ``t_end``.

    Returns (rt', failed [Q] i32, retry [Q] bool): ``failed`` lists the
    unresponsive next hops (→ overlay handleFailedNode), ``retry`` marks
    slots the caller must re-route (pick a new candidate from its CURRENT
    tables — the failed hop was just dropped from them — and call
    ``reforward``) or abandon via ``drop_slots``.
    """
    expired = rt.active & (rt.t_to < t_end)
    failed = jnp.where(expired, rt.dst, NO_NODE)
    can_retry = expired & (rt.retries < cfg.max_retries)
    give_up = expired & ~can_retry
    return dataclasses.replace(
        rt,
        active=rt.active & ~give_up,
        t_to=jnp.where(expired, T_INF, rt.t_to),
        dst=jnp.where(expired, NO_NODE, rt.dst),
        retries=rt.retries + expired.astype(I32),
    ), failed, can_retry


@scoped("route.forward")
def reforward(rt: RouteState, ob, slot: int, en, now, next_hop,
              cfg: RouteConfig):
    """Re-send slot ``slot``'s parked message to a new next hop (reroute
    after hop failure).  ``en`` false or next_hop==NO_NODE → caller uses
    ``drop_slot``."""
    q = rt.active.shape[0]
    en = en & (next_hop != NO_NODE)
    gen = rt.gen[slot] + 1
    nonce = jnp.where(en, _route_nonce(jnp.int32(slot), gen, q), 0)
    ob.send(en, now, next_hop, wire.KBR_ROUTE, key=rt.key[slot],
            nonce=nonce, hops=rt.hops[slot], a=rt.a[slot], b=rt.b[slot],
            c=rt.c[slot], d=rt.inner[slot], nodes=rt.visited[slot],
            stamp=rt.stamp[slot],
            size_b=rt.size_b[slot] + cfg.overhead_b)
    sel = _slot_mask(q, slot, en)
    return dataclasses.replace(
        rt,
        gen=_put(rt.gen, sel, gen),
        dst=_put(rt.dst, sel, next_hop),
        t_to=_put(rt.t_to, sel, now + cfg.ack_timeout_ns))


@scoped("route.forward")
def reforward_batch(rt: RouteState, ob, en, now, next_hop,
                    cfg: RouteConfig):
    """Vectorized :func:`reforward` over all Q slots at once: ``en`` [Q]
    marks slots to re-send, ``next_hop`` [Q] their new hops.  One Outbox
    send + per-field masked updates (no per-slot python loop)."""
    q = rt.active.shape[0]
    en = en & (next_hop != NO_NODE)
    gen = rt.gen + 1
    slots = jnp.arange(q, dtype=I32)
    nonce = jnp.where(en, _route_nonce(slots, gen, q), 0)
    ob.send(en, now, next_hop, wire.KBR_ROUTE, key=rt.key, nonce=nonce,
            hops=rt.hops, a=rt.a, b=rt.b, c=rt.c, d=rt.inner,
            nodes=rt.visited, stamp=rt.stamp,
            size_b=rt.size_b + cfg.overhead_b)
    return dataclasses.replace(
        rt,
        gen=jnp.where(en, gen, rt.gen),
        dst=jnp.where(en, next_hop, rt.dst),
        t_to=jnp.where(en, now + cfg.ack_timeout_ns, rt.t_to))


@scoped("route.timeouts")
def drop_slots(rt: RouteState, en):
    """Vectorized :func:`drop_slot`: free every slot marked in ``en`` [Q]."""
    return _free(rt, en)


@scoped("route.timeouts")
def drop_slot(rt: RouteState, slot: int, en):
    return _free(rt, _slot_mask(rt.active.shape[0], slot, en))


def next_event(rt: RouteState):
    """The earliest pending ACK's timeout.  Exact for the awake-set
    plane: a slot is active only with a finite ``t_to`` (``forward`` and
    ``reforward`` arm it, ``on_ack``, ``on_timeouts`` and ``drop_slot``
    clear both or re-arm), and ``on_timeouts`` is the only reader of a
    slot that no message addresses (tests/test_pastry_bamboo.py)."""
    return jnp.min(jnp.where(rt.active, rt.t_to, T_INF))


def parks(rt: RouteState, en, cfg: RouteConfig):
    """Whether ``forward(rt, ob, en, ...)`` finds an ACK slot for its
    message (False with ACKs off: nothing is parked)."""
    if not cfg.route_acks:
        return jnp.bool_(False)
    return en & jnp.any(~rt.active)


# ---------------------------------------------------------------------------
# shared wiring helpers: the three blocks every recursive overlay needs.
# Chord (and via inheritance Koorde) carries an inline copy of the same
# logic grown before these helpers existed (chord.py step); new overlays
# (EpiChord, Broose) wire these directly — the overlay only supplies its
# own findNode results.
# ---------------------------------------------------------------------------


def prepass(rt: RouteState, ob, msgs, res_b, sib_b, ready, node_idx,
            cfg: RouteConfig, forward_veto=None):
    """Inbound recursive-route pre-pass over an [R] inbox batch
    (BaseOverlay.cc:1441-1581): consume ACKs, pop source-routed replies,
    ACK + forward-or-decapsulate KBR_ROUTE messages using the overlay's
    batched findNode results ``res_b`` [R, RMAX] / ``sib_b`` [R].

    Returns (rt', msgs', drop_count) — ``msgs'`` has routed payloads
    decapsulated (kind := d, src := originator) and consumed wrapper
    lanes invalidated, ready for the overlay's normal dispatch.  With
    ``cfg.ext_words`` set, nodes is partitioned [ext | visited] and the
    responder's updated ext is taken from res_b's tail (the packing
    _respond_find-style responders use)."""
    v_r = msgs.valid
    now_r = msgs.t_deliver
    rmax = msgs.nodes.shape[-1]
    ew = cfg.ext_words

    rt = on_acks(rt, dataclasses.replace(
        msgs, valid=v_r & (msgs.kind == wire.KBR_ROUTE_ACK)))

    en_sro = v_r & (msgs.kind == wire.KBR_SROUTE)
    deliver_sr = sroute_step(ob, msgs)
    msgs = dataclasses.replace(
        msgs,
        kind=jnp.where(deliver_sr, msgs.d, msgs.kind),
        src=jnp.where(deliver_sr, msgs.c, msgs.src),
        valid=v_r & (~en_sro | deliver_sr))
    v_r = msgs.valid

    en_rt = v_r & (msgs.kind == wire.KBR_ROUTE) & ready
    ob.send(en_rt & (msgs.nonce > 0), now_r, msgs.src,
            wire.KBR_ROUTE_ACK, nonce=msgs.nonce,
            size_b=wire.BASE_CALL_B)
    deliver_rt = en_rt & sib_b
    if ew:
        vis_in = msgs.nodes[:, ew:]
        cands = res_b.at[:, rmax - ew:].set(NO_NODE)
    else:
        vis_in = msgs.nodes
        cands = res_b
    nxt_v, found_v = jax.vmap(
        pick_next_hop, in_axes=(0, 0, 0, 0, None, 0))(
        cands, vis_in, msgs.src, vis_in[:, 0], node_idx, sib_b)
    fwd = en_rt & ~sib_b & found_v & (msgs.hops < cfg.hop_max)
    if forward_veto is not None:
        fwd = fwd & ~forward_veto(msgs)
    visited2 = append_visited(vis_in, node_idx, fwd)
    if ew:
        nodes_out = jnp.concatenate(
            [res_b[:, rmax - ew:], visited2], axis=1)
    else:
        nodes_out = visited2
    rt = forward_batch(
        rt, ob, fwd, now_r, nxt_v, key=msgs.key, inner=msgs.d,
        a=msgs.a, b=msgs.b, c=msgs.c, hops=msgs.hops + 1,
        stamp=msgs.stamp, size_b=msgs.size_b - cfg.overhead_b,
        visited=nodes_out, cfg=cfg)
    drop = jnp.sum((en_rt & ~sib_b & ~fwd).astype(jnp.int32))
    msgs = dataclasses.replace(
        msgs,
        kind=jnp.where(deliver_rt, msgs.d, msgs.kind),
        src=jnp.where(deliver_rt, msgs.nodes[:, ew], msgs.src),
        valid=v_r & (~en_rt | deliver_rt))
    return rt, msgs, drop


def originate(rt: RouteState, ob, app_obj, app_state, req, next_hop,
              is_sib, have_slot, now, node_idx, rmax: int,
              cfg: RouteConfig, measuring, ext0=None):
    """Originator-side recursive data path for an app LookupReq (the
    sendToKey recursive branch at the source): payloads the app declares
    routable leave as KBR_ROUTE via ``next_hop``; everything else stays
    with the caller (the iterative engine).  ``ext0`` optionally seeds
    the routing-ext head with the originator's initialized ext (zeroed
    otherwise → the first hop lazily initializes).

    Returns (rt', app_state', route_fire, start_iterative)."""
    routable, inner_a, is_rpc = app_obj.route_policy(req.tag)
    route_fire = req.want & ~is_sib & routable & (next_hop != NO_NODE)
    ew = cfg.ext_words
    vis0 = jnp.full((rmax,), NO_NODE, jnp.int32).at[ew].set(node_idx)
    if ew:
        vis0 = vis0.at[:ew].set(0 if ext0 is None
                                else jnp.asarray(ext0, jnp.int32))
    rt = forward(rt, ob, route_fire, now, next_hop, key=req.key,
                 inner=inner_a, a=req.tag, b=jnp.int32(0),
                 c=measuring.astype(jnp.int32), hops=jnp.int32(1),
                 stamp=now, size_b=jnp.int32(100), visited=vis0,
                 cfg=cfg)
    if hasattr(app_obj, "on_route_fired"):
        app_state = app_obj.on_route_fired(
            app_state, route_fire & is_rpc, now, req.tag)
    start_iter = (req.want & ~is_sib & ~routable & have_slot
                  & (next_hop != NO_NODE))
    return rt, app_state, route_fire, start_iter


def reroute(rt: RouteState, ob, res_q, sib_q, rt_failed, rt_retry, now,
            node_idx, cfg: RouteConfig):
    """Timeout reroute pass: re-send parked messages around failed hops
    using fresh findNode results ``res_q`` [Q, C] / ``sib_q`` [Q] over
    the parked keys (internalHandleRpcTimeout, BaseOverlay.cc:1697-1729).
    A node that became responsible meanwhile self-forwards.  Returns
    (rt', give_up_count)."""
    ew = cfg.ext_words
    if res_q.ndim == 1:
        res_q = res_q[:, None]
    nxt_q, found_q = jax.vmap(
        pick_next_hop, in_axes=(0, 0, 0, 0, None, 0))(
        res_q, rt.visited[:, ew:], rt_failed,
        rt.visited[:, ew], node_idx, sib_q)
    nxt_fin = jnp.where(sib_q, node_idx, nxt_q)
    ok_q = rt_retry & (sib_q | found_q)
    rt = reforward_batch(rt, ob, ok_q, now, nxt_fin, cfg)
    give_up = rt_retry & ~ok_q
    rt = drop_slots(rt, give_up)
    return rt, jnp.sum(give_up.astype(jnp.int32))
