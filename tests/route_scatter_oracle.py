"""``common/route.py``'s slot-indexed writes as the scatters they were
until PR 47: ``forward``, ``forward_batch``, ``on_acks``,
``append_visited``, ``on_ack``, ``reforward`` and ``drop_slot`` of commit
1cf0ab5, bodies unchanged (the ``@scoped`` decorators alone left off), as
the plain reference of ``tests/test_route_mask_writes.py``.  The module
writes an ACK slot by a one-hot mask and must give every leaf of these,
dtype and all, and send what these send.  Nothing under oversim_tpu/
imports this module.
"""

import dataclasses

import jax.numpy as jnp

from oversim_tpu.common import wire
from oversim_tpu.common.route import (
    I32, I64, NO_NODE, T_INF, RouteConfig, RouteState, _route_nonce)


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
    have = jnp.any(free)
    use = en & have
    gen = rt.gen[jnp.minimum(slot, q - 1)] + 1
    nonce = jnp.where(use, _route_nonce(slot, gen, q), 0)
    ob.send(en, now, next_hop, wire.KBR_ROUTE, key=key, nonce=nonce,
            hops=hops, a=a, b=b, c=c, d=inner, nodes=visited,
            stamp=stamp, size_b=size_b + cfg.overhead_b)
    sl = jnp.where(use, slot, q)  # OOB drop
    return dataclasses.replace(
        rt,
        active=rt.active.at[sl].set(True, mode="drop"),
        gen=rt.gen.at[sl].set(gen, mode="drop"),
        dst=rt.dst.at[sl].set(next_hop, mode="drop"),
        t_to=rt.t_to.at[sl].set(now + cfg.ack_timeout_ns, mode="drop"),
        retries=rt.retries.at[sl].set(0, mode="drop"),
        key=rt.key.at[sl].set(key, mode="drop"),
        inner=rt.inner.at[sl].set(jnp.asarray(inner, I32), mode="drop"),
        a=rt.a.at[sl].set(jnp.asarray(a, I32), mode="drop"),
        b=rt.b.at[sl].set(jnp.asarray(b, I32), mode="drop"),
        c=rt.c.at[sl].set(jnp.asarray(c, I32), mode="drop"),
        hops=rt.hops.at[sl].set(jnp.asarray(hops, I32), mode="drop"),
        stamp=rt.stamp.at[sl].set(jnp.asarray(stamp, I64), mode="drop"),
        size_b=rt.size_b.at[sl].set(jnp.asarray(size_b, I32), mode="drop"),
        visited=rt.visited.at[sl].set(visited[:rt.visited.shape[1]],
                                      mode="drop"))


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

    # rank of each enabled lane / each free slot
    lane_rank = jnp.cumsum(en.astype(I32)) - 1            # [R]
    free = ~rt.active
    slot_rank = jnp.cumsum(free.astype(I32)) - 1          # [Q]
    n_free = jnp.sum(free.astype(I32))
    # lane j -> the free slot with rank lane_rank[j]
    slot_of_rank = jnp.full((q,), q, I32).at[
        jnp.where(free, slot_rank, q)].set(jnp.arange(q, dtype=I32),
                                           mode="drop")  # [Q] rank->slot
    lane_slot = jnp.where(en & (lane_rank < n_free),
                          slot_of_rank[jnp.clip(lane_rank, 0, q - 1)], q)
    parked = lane_slot < q                                 # [R]
    gen = rt.gen[jnp.clip(lane_slot, 0, q - 1)] + 1
    nonce = jnp.where(parked, _route_nonce(
        jnp.clip(lane_slot, 0, q - 1), gen, q), 0)
    ob.send(en, now, next_hop, wire.KBR_ROUTE, key=key, nonce=nonce,
            hops=hops, a=a, b=b, c=c, d=inner, nodes=visited,
            stamp=stamp, size_b=size_b + cfg.overhead_b)
    vis_cap = rt.visited.shape[1]
    return dataclasses.replace(
        rt,
        active=rt.active.at[lane_slot].set(True, mode="drop"),
        gen=rt.gen.at[lane_slot].set(gen, mode="drop"),
        dst=rt.dst.at[lane_slot].set(next_hop, mode="drop"),
        t_to=rt.t_to.at[lane_slot].set(now + cfg.ack_timeout_ns,
                                       mode="drop"),
        retries=rt.retries.at[lane_slot].set(0, mode="drop"),
        key=rt.key.at[lane_slot].set(key, mode="drop"),
        inner=rt.inner.at[lane_slot].set(
            jnp.broadcast_to(jnp.asarray(inner, I32), (r,)), mode="drop"),
        a=rt.a.at[lane_slot].set(jnp.asarray(a, I32), mode="drop"),
        b=rt.b.at[lane_slot].set(jnp.asarray(b, I32), mode="drop"),
        c=rt.c.at[lane_slot].set(jnp.asarray(c, I32), mode="drop"),
        hops=rt.hops.at[lane_slot].set(jnp.asarray(hops, I32), mode="drop"),
        stamp=rt.stamp.at[lane_slot].set(jnp.asarray(stamp, I64),
                                         mode="drop"),
        size_b=rt.size_b.at[lane_slot].set(jnp.asarray(size_b, I32),
                                           mode="drop"),
        visited=rt.visited.at[lane_slot].set(visited[:, :vis_cap],
                                             mode="drop"))


def on_acks(rt: RouteState, m):
    """Batched :func:`on_ack`: ``m`` fields carry an [R] inbox axis.  Each
    valid ACK addresses a distinct slot (the nonce encodes the slot), so
    one scatter clears them all."""
    q = rt.active.shape[0]
    slot = (m.nonce - 1) % q                               # [R]
    gen = (m.nonce - 1) // q
    sc = jnp.clip(slot, 0, q - 1)
    ok = (m.valid & (m.nonce > 0) & rt.active[sc]
          & ((rt.gen[sc] & jnp.int32(0x003FFFFF)) == gen)
          & (rt.dst[sc] == m.src))
    sl = jnp.where(ok, sc, q)
    return dataclasses.replace(
        rt,
        active=rt.active.at[sl].set(False, mode="drop"),
        t_to=rt.t_to.at[sl].set(T_INF, mode="drop"))


def append_visited(visited, self_idx, en):
    """recordRoute semantics (BaseOverlay.cc:893-898): append ``self_idx``
    to each enabled lane's [R, V] visited list (first NO_NODE slot; a full
    list keeps its prefix — bounded-width deviation, overflow harmless:
    loop detection just loses the oldest hops)."""
    r, vcap = visited.shape
    n_vis = jnp.sum((visited != NO_NODE).astype(I32), axis=1)   # [R]
    pos = jnp.where(en, jnp.minimum(n_vis, vcap - 1), vcap)
    return visited.at[jnp.arange(r), pos].set(
        jnp.where(en, self_idx, NO_NODE), mode="drop")


def on_ack(rt: RouteState, m):
    """Consume a KBR_ROUTE_ACK (NextHopResponse): free the matched slot."""
    q = rt.active.shape[0]
    slot = (m.nonce - 1) % q
    gen = (m.nonce - 1) // q
    ok = (m.valid & (m.nonce > 0) & rt.active[slot]
          & ((rt.gen[slot] & jnp.int32(0x003FFFFF)) == gen)
          & (rt.dst[slot] == m.src))
    sl = jnp.where(ok, slot, q)
    return dataclasses.replace(
        rt,
        active=rt.active.at[sl].set(False, mode="drop"),
        t_to=rt.t_to.at[sl].set(T_INF, mode="drop"))


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
    sl = jnp.where(en, jnp.int32(slot), q)
    return dataclasses.replace(
        rt,
        gen=rt.gen.at[sl].set(gen, mode="drop"),
        dst=rt.dst.at[sl].set(next_hop, mode="drop"),
        t_to=rt.t_to.at[sl].set(now + cfg.ack_timeout_ns, mode="drop"))


def drop_slot(rt: RouteState, slot: int, en):
    q = rt.active.shape[0]
    sl = jnp.where(en, jnp.int32(slot), q)
    return dataclasses.replace(
        rt,
        active=rt.active.at[sl].set(False, mode="drop"),
        t_to=rt.t_to.at[sl].set(T_INF, mode="drop"))
