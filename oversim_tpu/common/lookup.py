"""Iterative KBR lookup engine as per-node state-machine arrays.

TPU-native rebuild of the reference's IterativeLookup
(src/common/IterativeLookup.{h,cc}): per lookup, a frontier of candidate
next-hops is maintained and FindNode RPCs are issued to the closest
unvisited candidates until a node answers with its sibling flag set
(BaseOverlay::findNodeRpc sets `siblings` when the responder
isSiblingFor the key, BaseOverlay.cc:1866-1871; a flagged non-empty
response finishes the path, IterativeLookup.cc:893-902).

Semantics implemented (configuration flags read at BaseOverlay.cc:140-160):
  * lookupRedundantNodes=1, lookupParallelPaths=1, lookupParallelRpcs=1,
    lookupMerge=false — each FindNodeResponse *replaces* the frontier
    (IterativePathLookup::handleResponse clears nextHops when !merge,
    IterativeLookup.cc:839-841) and the next RPC goes to the first entry.
  * merge=true (Kademlia style) — response nodes are merged into the
    frontier, kept sorted by a pluggable distance metric, capacity F
    (BaseKeySortedVector semantics, NodeVector.h:40-44).
  * parallel RPCs — up to R = parallelPaths x parallelRpcs FindNode
    calls in flight per lookup against the shared sorted frontier.  The
    reference tracks paths as separate IterativePathLookup objects with
    a shared visited set (IterativeLookup.h:219, IterativeLookup.cc:529
    addPath); in the vectorized engine the disjoint-path bookkeeping
    collapses into in-flight width R over one frontier — same RPC
    fan-out and the same shared-visited pruning, without per-path
    object state.  Pair R>1 with merge=true (as Kademlia does).
  * retries — a timed-out FindNodeCall is re-sent to the same node up
    to `retries` times before the node is marked failed (BaseRpc
    retry counter, RpcState.numRetries / BaseRpc.cc:435-449).
  * exhaustive-iterative — sibling-flagged responses do not finish the
    lookup; discovered siblings accumulate and the lookup completes
    when the frontier is exhausted (EXHAUSTIVE_ITERATIVE_ROUTING,
    IterativeLookup.cc:219-226 appends all, stops on empty frontier).
  * lookupVisitOnlyOnce=true — a bounded visited ring buffer skips
    re-queries (IterativePathLookup::sendRpc visited check).
  * RPC timeout (rpcUdpTimeout=1.5s, default.ini:483) marks the queried
    node failed and reports it to the overlay's handleFailedNode; the
    global LOOKUP_TIMEOUT=10s (IterativeLookup.h:44) fails the lookup.
  * Exhaustion (no unvisited candidate, nothing pending) fails the lookup
    (IterativePathLookup::sendRpc "no further nodes to query").

Every function operates on a SINGLE node's slice (the engine vmaps the
whole per-node step); the L lookup slots of one node are a static axis.

A lookup completion is recorded in the ``done/success/result`` fields and
consumed by the owner (overlay logic) via ``take_completions`` — purpose
dispatch (join / finger repair / app route) lives with the owner.

The rule for writes: a write whose index is a lookup slot of the node
(or a slot and an RPC, frontier or visited column of it) is a SELECT
over the whole leaf by a one-hot mask (``_slot_mask``, ``_put``), never
``leaf.at[slot].set``.  Under the node step's ``vmap`` an indexed write
is one scatter of A updates a leaf, and on the chip a scatter costs by
its updates, the dropped ones too: 52 to 66 ns each, so ``start``'s 27
leaves at four call sites were 108 operations of 7.5 us in a tick of
N=4096 whether or not one lookup started (PERF.md, PR 41 and PR 42).  A
leaf is [L] or [L, <=16] with L <= 8: the select reads a few hundred
words a lane and fuses with its neighbours.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from oversim_tpu.common import wire
from oversim_tpu.core import keys as keys_mod
from oversim_tpu.core.scopes import scoped

I32 = jnp.int32
I64 = jnp.int64
U32 = jnp.uint32
NO_NODE = jnp.int32(-1)
T_INF = jnp.int64(2**62)

# frontier entry flags
F_NEW = 0        # known, not queried
F_PENDING = 1    # FindNodeCall in flight
F_RESPONDED = 2
F_FAILED = 3     # RPC timed out

LOOKUP_TIMEOUT_NS = 10 * 1_000_000_000   # IterativeLookup.h:44
RPC_TIMEOUT_NS = 1_500_000_000           # rpcUdpTimeout, default.ini:483
MAX_HOPS = 32                            # engine bound (overflow-counted)


@dataclasses.dataclass(frozen=True)
class LookupConfig:
    """Static knobs (reference: IterativeLookupConfiguration /
    BaseOverlay.cc:140-160 `lookup*` params)."""

    slots: int = 4          # L — concurrent lookups per node
    frontier: int = 8       # F — candidate set width
    visited: int = 16       # V — visited ring capacity
    merge: bool = False     # lookupMerge
    parallel_rpcs: int = 1  # R — lookupParallelPaths x lookupParallelRpcs
    retries: int = 0        # per-RPC re-sends before fail (BaseRpc retries)
    exhaustive: bool = False  # EXHAUSTIVE_ITERATIVE_ROUTING
    # S/Kademlia secure lookups (lookupVerifySiblings, read at
    # BaseOverlay.cc:144; IterativeLookup::checkStop pings candidate
    # siblings before accepting them, IterativeLookup.cc:295-340): a
    # sibling-flagged response no longer completes the lookup — the
    # head candidate is pinged first, and only a pong completes it.
    # A verification timeout marks the candidate failed and the lookup
    # continues from its merged frontier (one verification in flight
    # per lookup; the reference pings the whole candidate set).
    verify_siblings: bool = False
    rpc_timeout_ns: int = RPC_TIMEOUT_NS
    deadline_ns: int = LOOKUP_TIMEOUT_NS
    # PROX_AWARE_ITERATIVE_ROUTING (CommonMessages.msg:140 — declared
    # but never implemented in the reference; this is the rebuild's
    # implementation): among the ``prox_window`` closest unqueried
    # frontier candidates, the next FindNode RPC goes to the one with
    # the best NeighborCache RTT estimate (getProx semantics,
    # NeighborCache.cc) instead of strictly the closest — trading a few
    # extra hops for lower per-hop latency.  Requires the overlay to
    # pass ``prox_fn`` to pump().
    prox_aware: bool = False
    prox_window: int = 3
    # opaque per-lookup extension words threaded through every FindNode
    # round trip (reference: message-attached state like Koorde's
    # KoordeFindNodeExtMessage routeKey/step, Koorde.cc findDeBruijnHop).
    # A call carries the ext in nodes[:EW]; the responder returns an
    # updated ext in nodes[rmax-EW:] of the response.
    ext_words: int = 0


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LookupState:
    """One node's L lookup slots ([N, L, ...] at rest in the engine)."""

    active: jnp.ndarray       # [L] bool
    purpose: jnp.ndarray      # [L] i32 — owner-defined dispatch tag
    aux: jnp.ndarray          # [L] i32 — owner payload (finger idx, seq, …)
    target: jnp.ndarray       # [L, KL] u32
    gen: jnp.ndarray          # [L] i32 — slot generation (stale-response guard)
    frontier: jnp.ndarray     # [L, F] i32 node slots (NO_NODE padded)
    fr_flags: jnp.ndarray     # [L, F] i32 F_* flags
    fr_src: jnp.ndarray       # [L, F] i32 — who reported each frontier
                              # entry (NO_NODE = local seed).  Downlist
                              # provenance: the reference's Downlist maps
                              # source → dead nodes it returned
                              # (IterativeLookup lookup->getDownlist(),
                              # Kademlia.cc:1543-1585)
    visited: jnp.ndarray      # [L, V] i32
    vis_n: jnp.ndarray        # [L] i32 visited write cursor
    pending_dst: jnp.ndarray  # [L, R] i32 (NO_NODE = free RPC slot)
    pend_prov: jnp.ndarray    # [L, R] i32 — fr_src of the queried entry
    t_sent: jnp.ndarray       # [L, R] i64 — RPC send time (RTT base for
                              # NeighborCache sampling, NeighborCache.cc
                              # updateNode on every RPC response)
    t_to: jnp.ndarray         # [L, R] i64 — per-RPC timeout
    retry: jnp.ndarray        # [L, R] i32 — re-sends used on this RPC
    refire: jnp.ndarray       # [L, R] bool — timed out, re-send pending
    deadline: jnp.ndarray     # [L] i64 — whole-lookup timeout
    hops: jnp.ndarray         # [L] i32
    t0: jnp.ndarray           # [L] i64 — start time
    done: jnp.ndarray         # [L] bool — completed, not yet dispatched
    success: jnp.ndarray      # [L] bool
    result: jnp.ndarray       # [L] i32 — sibling node slot (NO_NODE on fail)
    results: jnp.ndarray      # [L, F] i32 — full final sibling set (the
                              # FindNodeResponse payload; DHT replica puts
                              # need numReplica siblings, DHT.cc:504)
    res_n: jnp.ndarray        # [L] i32 — accumulated siblings (exhaustive)
    t_done: jnp.ndarray       # [L] i64 — completion time (next_event wake)
    ext: jnp.ndarray          # [L, EW] i32 — opaque per-lookup extension
    ver_dst: jnp.ndarray      # [L] i32 — sibling candidate under ping
                              # verification (NO_NODE = none; S/Kademlia)
    ver_to: jnp.ndarray       # [L] i64 — its ping timeout (T_INF = ping
                              # not sent yet — pump sends it)


def init(cfg: LookupConfig, kl: int) -> LookupState:
    l, f, v, r = cfg.slots, cfg.frontier, cfg.visited, cfg.parallel_rpcs
    return LookupState(
        active=jnp.zeros((l,), bool),
        purpose=jnp.zeros((l,), I32),
        aux=jnp.zeros((l,), I32),
        target=jnp.zeros((l, kl), U32),
        gen=jnp.zeros((l,), I32),
        frontier=jnp.full((l, f), NO_NODE, I32),
        fr_flags=jnp.zeros((l, f), I32),
        fr_src=jnp.full((l, f), NO_NODE, I32),
        visited=jnp.full((l, v), NO_NODE, I32),
        vis_n=jnp.zeros((l,), I32),
        pending_dst=jnp.full((l, r), NO_NODE, I32),
        pend_prov=jnp.full((l, r), NO_NODE, I32),
        t_sent=jnp.zeros((l, r), I64),
        t_to=jnp.full((l, r), T_INF, I64),
        retry=jnp.zeros((l, r), I32),
        refire=jnp.zeros((l, r), bool),
        deadline=jnp.full((l,), T_INF, I64),
        hops=jnp.zeros((l,), I32),
        t0=jnp.zeros((l,), I64),
        done=jnp.zeros((l,), bool),
        success=jnp.zeros((l,), bool),
        result=jnp.full((l,), NO_NODE, I32),
        results=jnp.full((l, f), NO_NODE, I32),
        res_n=jnp.zeros((l,), I32),
        t_done=jnp.full((l,), T_INF, I64),
        ext=jnp.zeros((l, cfg.ext_words), I32),
        ver_dst=jnp.full((l,), NO_NODE, I32),
        ver_to=jnp.full((l,), T_INF, I64),
    )


def free_slot(lk: LookupState):
    """(slot index of a free lookup slot, have_free bool)."""
    free = ~lk.active
    return jnp.argmax(free).astype(I32), jnp.any(free)


def num_free(lk: LookupState):
    return jnp.sum((~lk.active).astype(I32))


def _slot_mask(l_dim: int, slot, en):
    """[L] bool, one-hot at ``slot`` where ``en``; a ``slot`` outside
    [0, L) selects nothing."""
    return (jnp.arange(l_dim, dtype=I32) == slot) & en


def _col_mask(rows, width: int, col):
    """[L, width] bool: row ``l`` one-hot at ``col[l]`` (or at the one
    ``col`` of all rows) where ``rows[l]``."""
    return rows[:, None] & (
        jnp.arange(width, dtype=I32) == jnp.reshape(col, (-1, 1)))


def _put(old, sel, new):
    """``old`` with ``new`` where ``sel``: ``sel`` covers ``old``'s
    leading axes ([L] or [L, C]) and ``new`` broadcasts against
    ``old``."""
    sel = sel.reshape(sel.shape + (1,) * (old.ndim - sel.ndim))
    return jnp.where(sel, jnp.asarray(new, old.dtype), old)


@scoped("lookup.start")
def start(lk: LookupState, en, slot, purpose, aux, target, seed_nodes,
          now, cfg: LookupConfig, ext=None) -> LookupState:
    """Occupy ``slot`` with a new lookup (no RPC fired yet — ``pump`` does).

    ``seed_nodes``: [F] i32 candidate slots from the owner's local
    findNode() (IterativeLookup::start seeds nextHops from the local
    routing state, IterativeLookup.cc:159).  If the seed is empty the
    lookup will fail at the next pump (reference: empty local findNode →
    path fails).
    """
    l_dim, f = lk.frontier.shape
    sel = _slot_mask(l_dim, slot, en)

    def put(old, new):
        return _put(old, sel, new)

    return dataclasses.replace(
        lk,
        active=lk.active | sel,
        purpose=put(lk.purpose, purpose),
        aux=put(lk.aux, aux),
        target=put(lk.target, target),
        gen=lk.gen + sel.astype(I32),
        frontier=put(lk.frontier, seed_nodes[:f]),
        fr_flags=put(lk.fr_flags, F_NEW),
        fr_src=put(lk.fr_src, NO_NODE),
        visited=put(lk.visited, NO_NODE),
        vis_n=put(lk.vis_n, 0),
        pending_dst=put(lk.pending_dst, NO_NODE),
        pend_prov=put(lk.pend_prov, NO_NODE),
        t_sent=put(lk.t_sent, 0),
        t_to=put(lk.t_to, T_INF),
        retry=put(lk.retry, 0),
        refire=put(lk.refire, False),
        deadline=put(lk.deadline, now + cfg.deadline_ns),
        hops=put(lk.hops, 0),
        t0=put(lk.t0, now),
        done=put(lk.done, False),
        success=put(lk.success, False),
        result=put(lk.result, NO_NODE),
        results=put(lk.results, NO_NODE),
        res_n=put(lk.res_n, 0),
        t_done=put(lk.t_done, T_INF),
        ext=put(lk.ext, 0 if ext is None else ext),
        ver_dst=put(lk.ver_dst, NO_NODE),
        ver_to=put(lk.ver_to, T_INF),
    )


def _visited_mask(visited, frontier):
    """[L, F] bool: frontier entry already in its slot's visited ring."""
    l_dim = frontier.shape[0]
    return jax.vmap(
        lambda li: jnp.any(
            visited[li][None, :] == frontier[li][:, None], axis=1) &
        (frontier[li] != NO_NODE))(jnp.arange(l_dim))


def on_response(lk: LookupState, msg, metric_fn, cfg: LookupConfig):
    """Consume a FINDNODE_RES inbox message addressed to this node.

    ``msg`` is a single-slot Msg view with a=lookup slot, b=generation,
    c=siblings flag, nodes=[RMAX] closest-node payload.  ``metric_fn(nodes)
    -> [K, KL]`` distances to the target (only used when cfg.merge).

    Returns lk'.  Completion (sibling-flagged response) is recorded in
    done/success/result (IterativeLookup.cc:893-902: flagged non-empty
    response → path finished, returned nodes are the siblings); in
    exhaustive mode the siblings accumulate in results/res_n instead.
    """
    l_dim = lk.active.shape[0]
    l = jnp.clip(msg.a, 0, l_dim - 1)
    match = (lk.pending_dst[l] == msg.src) & (msg.src != NO_NODE)   # [R]
    ok = (msg.valid & lk.active[l] & (lk.gen[l] == msg.b) &
          jnp.any(match) & ~lk.done[l])
    j = jnp.argmax(match).astype(I32)

    f = lk.frontier.shape[1]
    resp_nodes = msg.nodes[:f]
    has_nodes = jnp.any(resp_nodes != NO_NODE)
    is_sib = (msg.c != 0) & has_nodes

    # clear the matched pending RPC; count the hop (IterativeLookup.cc:825)
    at_ok = _slot_mask(l_dim, l, ok)                            # [L]
    hit = _col_mask(at_ok, match.shape[0], j)                   # [L, R]
    lk = dataclasses.replace(
        lk,
        pending_dst=_put(lk.pending_dst, hit, NO_NODE),
        t_to=_put(lk.t_to, hit, T_INF),
        retry=_put(lk.retry, hit, 0),
        refire=_put(lk.refire, hit, False),
        hops=lk.hops + at_ok.astype(I32))

    if cfg.verify_siblings and not cfg.exhaustive:
        # S/Kademlia: stage the head candidate for ping verification
        # instead of completing (IterativeLookup.cc:295-340); pump sends
        # the ping.  The response still merges into the frontier below so
        # a failed verification continues the lookup.
        at_fin = _slot_mask(l_dim, l, ok & is_sib & (lk.ver_dst[l] == NO_NODE))
        lk = dataclasses.replace(
            lk,
            ver_dst=_put(lk.ver_dst, at_fin, resp_nodes[0]),
            ver_to=_put(lk.ver_to, at_fin, T_INF),
            result=_put(lk.result, at_fin, resp_nodes[0]),
            results=_put(lk.results, at_fin, resp_nodes))
        upd = ok
    elif not cfg.exhaustive:
        # finished: responder was a sibling → result = first returned node
        at_fin = _slot_mask(l_dim, l, ok & is_sib)
        lk = dataclasses.replace(
            lk,
            done=lk.done | at_fin,
            success=lk.success | at_fin,
            result=_put(lk.result, at_fin, resp_nodes[0]),
            results=_put(lk.results, at_fin, resp_nodes),
            t_done=_put(lk.t_done, at_fin, msg.t_deliver))
        upd = ok & ~is_sib
    else:
        # exhaustive: accumulate the responder's sibling set and keep going
        # (IterativeLookup.cc EXHAUSTIVE branch appends to the
        # key-distance-sorted siblings NodeVector — keep the set sorted
        # by the metric so results[0] is always the closest found)
        acc = ok & is_sib
        cur = jnp.concatenate([lk.results[l], resp_nodes])
        dup = keys_mod.dup_mask(cur) | (cur == NO_NODE)
        cur = jnp.where(dup, NO_NODE, cur)
        sdist = metric_fn(cur, lk.target[l])
        sdist = jnp.where(dup[:, None], jnp.uint32(0xFFFFFFFF), sdist)
        _, (packed_full,) = keys_mod.sort_by_distance(sdist, (cur,), approx=True)
        packed = packed_full[:f]
        at_acc = _slot_mask(l_dim, l, acc)
        lk = dataclasses.replace(
            lk,
            results=_put(lk.results, at_acc, packed),
            res_n=_put(lk.res_n, at_acc,
                       jnp.sum(packed != NO_NODE, dtype=I32)))
        upd = ok   # frontier always advances; exhaustion completes the lookup

    if cfg.merge:
        # sorted union of old frontier + response, cap F, drop visited dups
        cand = jnp.concatenate([lk.frontier[l], resp_nodes])
        flags = jnp.concatenate([lk.fr_flags[l],
                                 jnp.full((f,), F_NEW, I32)])
        srcs = jnp.concatenate([lk.fr_src[l],
                                jnp.broadcast_to(msg.src, (f,)).astype(I32)])
        # dedupe: a response node equal to an existing frontier entry is
        # invalidated (keeps the entry with its flag state)
        dup = keys_mod.dup_mask(cand) | (cand == NO_NODE)
        cand = jnp.where(dup, NO_NODE, cand)
        dist = metric_fn(cand, lk.target[l])          # [2F, KL]
        dist = jnp.where(dup[:, None], jnp.uint32(0xFFFFFFFF), dist)
        _, (cand_s, flags_s, src_s) = keys_mod.sort_by_distance(
            dist, (cand, flags, srcs), approx=True)
        new_frontier = cand_s[:f]
        new_flags = jnp.where(cand_s[:f] == NO_NODE, F_NEW, flags_s[:f])
        new_src = src_s[:f]
    else:
        # replace mode: frontier := response nodes, in responder order
        # (IterativeLookup.cc:839-841 + push_back add)
        new_frontier = resp_nodes
        new_flags = jnp.full((f,), F_NEW, I32)
        new_src = jnp.broadcast_to(msg.src, (f,)).astype(I32)
        # if the response was empty keep the old frontier (reference keeps
        # nextHops when ClosestNodesArraySize()==0, IterativeLookup.cc:843)
        new_frontier = jnp.where(has_nodes, new_frontier, lk.frontier[l])
        new_flags = jnp.where(has_nodes, new_flags, lk.fr_flags[l])
        new_src = jnp.where(has_nodes, new_src, lk.fr_src[l])

    at_upd = _slot_mask(l_dim, l, upd)
    lk = dataclasses.replace(
        lk,
        frontier=_put(lk.frontier, at_upd, new_frontier),
        fr_flags=_put(lk.fr_flags, at_upd, new_flags),
        fr_src=_put(lk.fr_src, at_upd, new_src))
    ew = cfg.ext_words
    if ew:
        # responder-updated extension rides the response tail
        lk = dataclasses.replace(
            lk, ext=_put(lk.ext, at_upd, msg.nodes[-ew:]))
    return lk


@scoped("lookup.responses")
def on_responses(lk: LookupState, msgs, metric_fn, cfg: LookupConfig):
    """Batched ``on_response``: consume ALL of a node's FINDNODE_RES inbox
    messages ([R]-batch Msg view, ``msgs.valid`` pre-masked to response
    kind) in one pass.

    Semantically equivalent to folding :func:`on_response` over the R
    slots, except (a) several same-tick responses for one lookup slot
    merge into the frontier through ONE sort over [F + R·F] candidates
    instead of R sorts, and (b) when two sibling-flagged responses land
    in one tick the lowest inbox slot wins (the fold took the first too).
    This is the op-count lever: the unrolled fold dominated the tick
    graph (PERFORMANCE.md round-2 analysis).
    """
    r_in = msgs.valid.shape[0]
    l_dim, f = lk.frontier.shape
    lixs = jnp.arange(l_dim, dtype=I32)

    l_r = jnp.clip(msgs.a, 0, l_dim - 1)                       # [R]
    match = (lk.pending_dst[l_r] == msgs.src[:, None]) & (
        msgs.src != NO_NODE)[:, None]                          # [R, Rrpc]
    ok = (msgs.valid & lk.active[l_r] & (lk.gen[l_r] == msgs.b) &
          jnp.any(match, axis=1) & ~lk.done[l_r])
    # a duplicate response (same slot, same responder) in the same tick
    # must not double-count: the sequential fold rejected it because the
    # first response cleared the pending entry (BaseRpc nonce matching)
    same = (l_r[None, :] == l_r[:, None]) & (
        msgs.src[None, :] == msgs.src[:, None])
    earlier = jnp.tril(jnp.ones((r_in, r_in), bool), k=-1)
    ok = ok & ~jnp.any(same & earlier & ok[None, :], axis=1)
    j = jnp.argmax(match, axis=1).astype(I32)

    def per_slot(pred):
        """[R] bool → ([L] any, [L] first-r index, [R, L] the mask)."""
        m_rl = pred[:, None] & (l_r[:, None] == lixs[None, :])
        return jnp.any(m_rl, axis=0), jnp.argmax(m_rl, axis=0), m_rl

    # clear matched pending RPCs; count hops (IterativeLookup.cc:825)
    _, _, m_ok = per_slot(ok)
    hit = jnp.any(m_ok[:, :, None] & (
        j[:, None, None] == jnp.arange(match.shape[1], dtype=I32)),
        axis=0)                                                 # [L, Rrpc]
    lk = dataclasses.replace(
        lk,
        pending_dst=_put(lk.pending_dst, hit, NO_NODE),
        t_to=_put(lk.t_to, hit, T_INF),
        retry=_put(lk.retry, hit, 0),
        refire=_put(lk.refire, hit, False),
        hops=lk.hops + jnp.sum(m_ok, axis=0, dtype=I32))

    resp_nodes = msgs.nodes[:, :f]                              # [R, F]
    has_nodes = jnp.any(resp_nodes != NO_NODE, axis=1)
    is_sib = (msgs.c != 0) & has_nodes

    if cfg.verify_siblings and not cfg.exhaustive:
        # S/Kademlia: stage head candidate for ping verification instead
        # of completing (IterativeLookup.cc:295-340); pump sends the ping
        fin, win, _ = per_slot(ok & is_sib)
        fin = fin & (lk.ver_dst == NO_NODE)
        wnodes = resp_nodes[win]                                # [L, F]
        lk = dataclasses.replace(
            lk,
            ver_dst=jnp.where(fin, wnodes[:, 0], lk.ver_dst),
            ver_to=jnp.where(fin, T_INF, lk.ver_to),
            result=jnp.where(fin, wnodes[:, 0], lk.result),
            results=jnp.where(fin[:, None], wnodes, lk.results))
        upd = ok
    elif not cfg.exhaustive:
        fin, win, _ = per_slot(ok & is_sib)
        wnodes = resp_nodes[win]                                # [L, F]
        lk = dataclasses.replace(
            lk,
            done=lk.done | fin,
            success=lk.success | fin,
            result=jnp.where(fin, wnodes[:, 0], lk.result),
            results=jnp.where(fin[:, None], wnodes, lk.results),
            t_done=jnp.where(fin, msgs.t_deliver[win], lk.t_done))
        upd = ok & ~is_sib
    else:
        # exhaustive: accumulate every sibling-flagged response's node set,
        # kept metric-sorted so results[0] is the closest found
        _, _, m_acc = per_slot(ok & is_sib)
        contrib = jnp.where(m_acc.T[:, :, None], resp_nodes[None, :, :],
                            NO_NODE).reshape(l_dim, r_in * f)
        cur = jnp.concatenate([lk.results, contrib], axis=1)    # [L, F+RF]
        dup = jax.vmap(keys_mod.dup_mask)(cur) | (cur == NO_NODE)
        cur = jnp.where(dup, NO_NODE, cur)
        sdist = jax.vmap(metric_fn)(cur, lk.target)
        sdist = jnp.where(dup[..., None], jnp.uint32(0xFFFFFFFF), sdist)
        _, (packed,) = keys_mod.sort_by_distance(sdist, (cur,), approx=True)
        packed = packed[:, :f]
        acc_any = jnp.any(m_acc, axis=0)
        lk = dataclasses.replace(
            lk,
            results=jnp.where(acc_any[:, None], packed, lk.results),
            res_n=jnp.where(acc_any,
                            jnp.sum(packed != NO_NODE, axis=1, dtype=I32),
                            lk.res_n))
        upd = ok

    if cfg.merge:
        any_upd, _, m_upd = per_slot(upd)
        contrib = jnp.where(m_upd.T[:, :, None], resp_nodes[None, :, :],
                            NO_NODE).reshape(l_dim, r_in * f)
        c_src = jnp.where(m_upd.T, msgs.src[None, :], NO_NODE)
        c_src = jnp.broadcast_to(c_src[:, :, None],
                                 (l_dim, r_in, f)).reshape(l_dim, r_in * f)
        cand = jnp.concatenate([lk.frontier, contrib], axis=1)  # [L, F+RF]
        flags = jnp.concatenate(
            [lk.fr_flags, jnp.full((l_dim, r_in * f), F_NEW, I32)], axis=1)
        srcs = jnp.concatenate([lk.fr_src, c_src], axis=1)
        dup = jax.vmap(keys_mod.dup_mask)(cand) | (cand == NO_NODE)
        cand = jnp.where(dup, NO_NODE, cand)
        dist = jax.vmap(metric_fn)(cand, lk.target)
        dist = jnp.where(dup[..., None], jnp.uint32(0xFFFFFFFF), dist)
        _, (cand_s, flags_s, src_s) = keys_mod.sort_by_distance(
            dist, (cand, flags, srcs), approx=True)
        new_frontier = cand_s[:, :f]
        new_flags = jnp.where(new_frontier == NO_NODE, F_NEW, flags_s[:, :f])
        new_src = src_s[:, :f]
    else:
        # replace mode: the first consuming response replaces the frontier
        # (IterativeLookup.cc:839-841); empty responses keep the old one
        any_upd, win_u, _ = per_slot(upd & has_nodes)
        new_frontier = resp_nodes[win_u]
        new_flags = jnp.full((l_dim, f), F_NEW, I32)
        new_src = jnp.broadcast_to(msgs.src[win_u][:, None], (l_dim, f))

    lk = dataclasses.replace(
        lk,
        frontier=jnp.where(any_upd[:, None], new_frontier, lk.frontier),
        fr_flags=jnp.where(any_upd[:, None], new_flags, lk.fr_flags),
        fr_src=jnp.where(any_upd[:, None], new_src, lk.fr_src))
    ew = cfg.ext_words
    if ew:
        any_e, win_e, _ = per_slot(upd)
        lk = dataclasses.replace(lk, ext=jnp.where(
            any_e[:, None], msgs.nodes[win_e][:, -ew:], lk.ext))
    return lk


def response_rtts(lk: LookupState, msgs):
    """RTT samples from a tick's FINDNODE_RES batch ([R]-masked msgs),
    computed against the matched pending RPC's send time — the
    reference's NeighborCache::updateNode on every RPC response
    (BaseRpc response path).  Call BEFORE on_responses (which clears
    the pending entries).  Returns (src [R] i32, rtt_s [R] f32, ok [R])."""
    l_dim = lk.active.shape[0]
    l_r = jnp.clip(msgs.a, 0, l_dim - 1)
    match = (lk.pending_dst[l_r] == msgs.src[:, None]) & (
        msgs.src != NO_NODE)[:, None]                          # [R, Rrpc]
    ok = (msgs.valid & lk.active[l_r] & (lk.gen[l_r] == msgs.b) &
          jnp.any(match, axis=1))
    j = jnp.argmax(match, axis=1).astype(I32)
    sent = lk.t_sent[l_r, j]
    rtt_s = (msgs.t_deliver - sent).astype(jnp.float32) / 1e9
    return jnp.where(ok, msgs.src, NO_NODE), rtt_s, ok


@scoped("lookup.timeouts")
def on_timeouts(lk: LookupState, t_end, now, cfg: LookupConfig):
    """Expire pending RPCs / deadlines due strictly before ``t_end``.

    An expired RPC with retries left is queued for re-send (``refire``,
    BaseRpc.cc:435-449 retry path); otherwise the queried node is
    reported failed.  Returns (lk', failed_nodes [L*R + L] i32,
    failed_prov [L*R + L] i32) — failed nodes feed the overlay's
    handleFailedNode repair (BaseOverlay.cc:1697-1729;
    IterativePathLookup::handleTimeout); ``failed_prov`` pairs each
    failure with the node that REPORTED the dead contact (downlist
    provenance, Kademlia.cc:1543-1585; NO_NODE = locally seeded).  The
    trailing L lanes carry S/Kademlia verification-ping timeouts.
    """
    act = lk.active[:, None]
    exp = act & (lk.pending_dst != NO_NODE) & (lk.t_to < t_end)
    can_retry = exp & (lk.retry < cfg.retries)
    final = exp & ~can_retry
    failed_nodes = jnp.where(final, lk.pending_dst, NO_NODE).reshape(-1)
    failed_prov = jnp.where(final, lk.pend_prov, NO_NODE).reshape(-1)

    # mark finally-failed nodes in the frontier
    fmask = jnp.any(final[:, None, :] &
                    (lk.frontier[:, :, None] == lk.pending_dst[:, None, :]),
                    axis=2)
    fr_flags = jnp.where(fmask, F_FAILED, lk.fr_flags)
    pending_dst = jnp.where(final, NO_NODE, lk.pending_dst)
    pend_prov = jnp.where(final, NO_NODE, lk.pend_prov)
    t_to = jnp.where(exp, T_INF, lk.t_to)
    refire = lk.refire | can_retry
    retry = lk.retry + can_retry.astype(I32)
    # a finally timed-out round still counts as a hop attempt
    hops = lk.hops + jnp.sum(final, axis=1, dtype=I32)

    # S/Kademlia verification-ping timeout: the candidate sibling is
    # dead — report it failed, flag it in the frontier, and let the
    # lookup continue (pump picks the next candidate)
    if cfg.verify_siblings:
        vexp = (lk.active & ~lk.done & (lk.ver_dst != NO_NODE)
                & (lk.ver_to < t_end))
        failed_nodes = jnp.concatenate(
            [failed_nodes, jnp.where(vexp, lk.ver_dst, NO_NODE)])
        failed_prov = jnp.concatenate(
            [failed_prov, jnp.full((lk.active.shape[0],), NO_NODE, I32)])
        vmask = vexp[:, None] & (lk.frontier == lk.ver_dst[:, None])
        fr_flags = jnp.where(vmask, F_FAILED, fr_flags)
        hops = hops + vexp.astype(I32)
        ver_dst = jnp.where(vexp, NO_NODE, lk.ver_dst)
        ver_to = jnp.where(vexp, T_INF, lk.ver_to)
    else:
        ver_dst, ver_to = lk.ver_dst, lk.ver_to

    # whole-lookup deadline (only for not-yet-done active lookups)
    dead = lk.active & ~lk.done & (lk.deadline < t_end)
    done = lk.done | dead
    t_done = jnp.where(dead, now, lk.t_done)

    return dataclasses.replace(
        lk, fr_flags=fr_flags, pending_dst=pending_dst,
        pend_prov=pend_prov, t_to=t_to, retry=retry, refire=refire,
        hops=hops, done=done, t_done=t_done, ver_dst=ver_dst,
        ver_to=ver_to), failed_nodes, failed_prov


def on_pongs(lk: LookupState, msgs, cfg: LookupConfig):
    """Consume PING_RES messages for S/Kademlia sibling verification
    ([R]-batch; ``msgs.valid`` pre-masked to the ping-response kind with
    a == lookup slot).  A pong from the staged candidate completes the
    lookup verified (IterativeLookup::checkStop ping path)."""
    if not cfg.verify_siblings:
        return lk
    l_dim = lk.active.shape[0]
    l_r = jnp.clip(msgs.a, 0, l_dim - 1)                       # [R]
    ok = (msgs.valid & lk.active[l_r] & ~lk.done[l_r]
          & (lk.gen[l_r] == msgs.b)
          & (lk.ver_dst[l_r] == msgs.src) & (msgs.src != NO_NODE))
    # the lowest inbox slot wins a slot that two pongs answer
    m_rl = ok[:, None] & (l_r[:, None] == jnp.arange(l_dim, dtype=I32))
    fin, win = jnp.any(m_rl, axis=0), jnp.argmax(m_rl, axis=0)
    return dataclasses.replace(
        lk,
        done=lk.done | fin,
        success=lk.success | fin,
        t_done=jnp.where(fin, msgs.t_deliver[win], lk.t_done),
        ver_dst=jnp.where(fin, NO_NODE, lk.ver_dst),
        ver_to=jnp.where(fin, T_INF, lk.ver_to))


@scoped("lookup.pump")
def pump(lk: LookupState, outbox, ctx, node_idx, now, rng,
         cfg: LookupConfig, *, num_siblings: int = 1,
         num_redundant: int = 1, timeout_fn=None, prox_fn=None):
    """Fire FindNodeCalls for every active slot with free RPC capacity
    (up to R in flight); re-send timed-out RPCs with retries left;
    exhausted slots complete (as failed, or — exhaustive mode — with
    the accumulated sibling set).

    ``timeout_fn([L] dsts) -> [L] i64 ns``: optional per-destination
    RPC timeout (NeighborCache adaptive timeouts, getNodeTimeout /
    NeighborCache.cc:802 — the overlay passes its RTT-cache estimate);
    default is the static cfg.rpc_timeout_ns.

    Mirrors IterativePathLookup::sendRpc: pick the first unvisited,
    not-failed frontier entries; if none and nothing pending, the path
    finishes.
    """
    del rng
    l_dim, f = lk.frontier.shape
    r_dim = lk.pending_dst.shape[1]
    call_size = wire.findnode_call_b() + 4 * cfg.ext_words

    # ---- re-sends (BaseRpc retry): same destination, fresh timeout ----
    # refire is statically impossible with retries == 0 (the default):
    # skip tracing the L×R send fan-out entirely in that case
    if cfg.retries:
        t_to = jnp.where(lk.refire, now + cfg.rpc_timeout_ns, lk.t_to)
        li_grid = jnp.broadcast_to(
            jnp.arange(l_dim, dtype=I32)[:, None], (l_dim, r_dim))
        outbox.send(
            lk.refire.reshape(-1), now, lk.pending_dst.reshape(-1),
            wire.FINDNODE_CALL,
            key=jnp.broadcast_to(lk.target[:, None, :],
                                 (l_dim, r_dim, lk.target.shape[1])
                                 ).reshape(l_dim * r_dim, -1),
            a=li_grid.reshape(-1),
            b=jnp.broadcast_to(lk.gen[:, None], (l_dim, r_dim)).reshape(-1),
            c=jnp.int32(num_siblings), d=jnp.int32(num_redundant),
            nodes=(jnp.broadcast_to(lk.ext[:, None, :],
                                    (l_dim, r_dim, cfg.ext_words)
                                    ).reshape(l_dim * r_dim, -1)
                   if cfg.ext_words else None),
            size_b=call_size)
        lk = dataclasses.replace(
            lk, t_to=t_to, refire=jnp.zeros_like(lk.refire))

    # ---- S/Kademlia verification pings (one per staged candidate) ----
    if cfg.verify_siblings:
        need_ping = (lk.active & ~lk.done & (lk.ver_dst != NO_NODE)
                     & (lk.ver_to >= T_INF))
        outbox.send(need_ping, now, lk.ver_dst, wire.PING_CALL,
                    a=jnp.arange(l_dim, dtype=I32), b=lk.gen,
                    size_b=wire.BASE_CALL_B)
        lk = dataclasses.replace(lk, ver_to=jnp.where(
            need_ping, now + cfg.rpc_timeout_ns, lk.ver_to))

    # ---- new fires: fill free RPC slots from the frontier ----
    frontier, fr_flags = lk.frontier, lk.fr_flags
    visited, vis_n = lk.visited, lk.vis_n
    pending_dst, t_to = lk.pending_dst, lk.t_to
    pend_prov = lk.pend_prov
    t_sent_arr = lk.t_sent
    retry = lk.retry
    fired_any = jnp.zeros((l_dim,), bool)
    for _ in range(r_dim):
        cand_ok = (frontier != NO_NODE) & (fr_flags == F_NEW)
        cand_ok = cand_ok & ~_visited_mask(visited, frontier) & (
            frontier != node_idx)
        has_cand = jnp.any(cand_ok, axis=1)
        if cfg.prox_aware and prox_fn is not None:
            # PROX_AWARE_ITERATIVE: within the prox_window closest
            # eligible candidates, query the lowest-RTT one (unknown
            # RTTs rank behind known ones but ahead of out-of-window)
            rank = jnp.cumsum(cand_ok.astype(I32), axis=1) - 1
            in_win = cand_ok & (rank < cfg.prox_window)
            rtt = prox_fn(frontier)                       # [L, F] f32 s
            # unknown RTTs rank behind EVERY measured one (sentinel far
            # above any achievable RTT, not a mid-range placeholder)
            rtt = jnp.where(rtt > 0, rtt, 1e3)
            # stable tiny distance-order bias so equal RTTs keep the
            # closest-first order
            rtt = rtt + jnp.arange(f, dtype=jnp.float32) * 1e-6
            first = jnp.argmin(
                jnp.where(in_win, rtt, jnp.inf), axis=1).astype(I32)
        else:
            first = jnp.argmax(cand_ok, axis=1).astype(I32)
        cand = jnp.take_along_axis(frontier, first[:, None], axis=1)[:, 0]
        prov = jnp.take_along_axis(lk.fr_src, first[:, None], axis=1)[:, 0]

        free_col_ok = pending_dst == NO_NODE
        has_free = jnp.any(free_col_ok, axis=1)
        col = jnp.argmax(free_col_ok, axis=1).astype(I32)

        idle = lk.active & ~lk.done
        fire = idle & has_cand & has_free & (lk.hops < MAX_HOPS)

        # a firing row writes ONE column of each leaf
        v_dim = visited.shape[1]
        at_rpc = _col_mask(fire, r_dim, col)
        visited = _put(visited, _col_mask(fire, v_dim, vis_n % v_dim),
                       cand[:, None])
        vis_n = vis_n + fire.astype(I32)
        fr_flags = _put(fr_flags, _col_mask(fire, f, first), F_PENDING)
        pending_dst = _put(pending_dst, at_rpc, cand[:, None])
        pend_prov = _put(pend_prov, at_rpc, prov[:, None])
        t_sent_arr = _put(t_sent_arr, at_rpc, now)
        to_ns = (cfg.rpc_timeout_ns if timeout_fn is None
                 else timeout_fn(cand))
        t_to = _put(t_to, at_rpc,
                    jnp.broadcast_to(now + to_ns, (l_dim,))[:, None])
        retry = _put(retry, at_rpc, 0)
        fired_any = fired_any | fire

        outbox.send(
            fire, now, cand, wire.FINDNODE_CALL,
            key=lk.target, a=jnp.arange(l_dim, dtype=I32), b=lk.gen,
            c=jnp.int32(num_siblings), d=jnp.int32(num_redundant),
            nodes=lk.ext if cfg.ext_words else None,
            size_b=call_size)

    # ---- exhaustion: nothing in flight and nothing left to query ----
    cand_ok = (frontier != NO_NODE) & (fr_flags == F_NEW)
    cand_ok = cand_ok & ~_visited_mask(visited, frontier) & (
        frontier != node_idx)
    has_cand = jnp.any(cand_ok, axis=1)
    inflight = jnp.any(pending_dst != NO_NODE, axis=1)
    if cfg.verify_siblings:
        # a staged verification counts as in-flight work
        inflight = inflight | (lk.ver_dst != NO_NODE)
    fail = (lk.active & ~lk.done & ~inflight &
            (~has_cand | (lk.hops >= MAX_HOPS)))

    if cfg.exhaustive:
        # exhaustion IS the completion; success = found any sibling
        success = jnp.where(fail, lk.res_n > 0, lk.success)
        result = jnp.where(fail & (lk.res_n > 0), lk.results[:, 0],
                           lk.result)
    else:
        success, result = lk.success, lk.result

    done = lk.done | fail
    t_done = jnp.where(fail, now, lk.t_done)

    lk = dataclasses.replace(
        lk, frontier=frontier, fr_flags=fr_flags, visited=visited,
        vis_n=vis_n, pending_dst=pending_dst, pend_prov=pend_prov,
        t_sent=t_sent_arr, t_to=t_to, retry=retry,
        success=success, result=result, done=done, t_done=t_done)
    return lk, fired_any


@scoped("lookup.completions")
def take_completions(lk: LookupState, t_end):
    """Harvest slots whose completion is due (done & t_done < t_end).

    Returns (lk', comp) where comp is a dict of [L] arrays:
    taken/success/result/purpose/aux/hops/t0/target.  Taken slots are freed.
    """
    taken = lk.done & (lk.t_done < t_end)
    comp = dict(taken=taken, success=lk.success & taken, result=lk.result,
                results=lk.results, purpose=lk.purpose, aux=lk.aux,
                hops=lk.hops, t0=lk.t0, target=lk.target)
    t2 = taken[:, None]
    lk = dataclasses.replace(
        lk,
        active=lk.active & ~taken,
        done=lk.done & ~taken,
        pending_dst=jnp.where(t2, NO_NODE, lk.pending_dst),
        pend_prov=jnp.where(t2, NO_NODE, lk.pend_prov),
        ver_dst=jnp.where(taken, NO_NODE, lk.ver_dst),
        ver_to=jnp.where(taken, T_INF, lk.ver_to),
        t_to=jnp.where(t2, T_INF, lk.t_to),
        retry=jnp.where(t2, 0, lk.retry),
        refire=jnp.where(t2, False, lk.refire),
        deadline=jnp.where(taken, T_INF, lk.deadline),
        t_done=jnp.where(taken, T_INF, lk.t_done))
    return lk, comp


def next_event(lk: LookupState):
    """Earliest timeout/completion wake-up for this node's lookups ([L]→scalar)."""
    act = lk.active[:, None]
    t = jnp.min(jnp.where(act, lk.t_to, T_INF), axis=1)
    t = jnp.minimum(t, jnp.where(lk.active & ~lk.done, lk.deadline, T_INF))
    t = jnp.minimum(t, jnp.where(lk.done, lk.t_done, T_INF))
    # staged verification: wake immediately when the ping is unsent
    # (ver_to == T_INF), then at its timeout
    t = jnp.where(lk.active & ~lk.done & (lk.ver_dst != NO_NODE)
                  & (lk.ver_to >= T_INF), jnp.int64(0),
                  jnp.minimum(t, jnp.where(
                      lk.active & ~lk.done & (lk.ver_dst != NO_NODE),
                      lk.ver_to, T_INF)))
    # a queued re-send must wake the node immediately (refire can only
    # be set when retries are in play; the engine passes no cfg here so
    # the cheap mask-any stays — it folds to False when never set)
    t = jnp.where(jnp.any(lk.refire & act, axis=1), jnp.int64(0), t)
    return jnp.min(t)
