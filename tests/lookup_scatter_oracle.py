"""``common/lookup.py``'s slot-indexed writes as the scatters they were
until PR 42: ``start``, ``on_response``, ``on_responses``, ``on_pongs``
and ``pump`` of commit 6fab175, bodies unchanged, as the plain reference
of ``tests/test_lookup_mask_writes.py``.  The module writes a slot by a
one-hot mask and must give every leaf of these, dtype and all.  Nothing
under oversim_tpu/ imports this module.
"""

import dataclasses

import jax
import jax.numpy as jnp

from oversim_tpu.common import wire
from oversim_tpu.common.lookup import (
    F_NEW, F_PENDING, I32, I64, MAX_HOPS, NO_NODE, T_INF, LookupConfig,
    LookupState, _visited_mask)
from oversim_tpu.core import keys as keys_mod


def start(lk: LookupState, en, slot, purpose, aux, target, seed_nodes,
          now, cfg: LookupConfig, ext=None) -> LookupState:
    """Occupy ``slot`` with a new lookup (no RPC fired yet — ``pump`` does).

    ``seed_nodes``: [F] i32 candidate slots from the owner's local
    findNode() (IterativeLookup::start seeds nextHops from the local
    routing state, IterativeLookup.cc:159).  If the seed is empty the
    lookup will fail at the next pump (reference: empty local findNode →
    path fails).
    """
    f = lk.frontier.shape[1]
    r = lk.pending_dst.shape[1]
    slot = jnp.where(en, slot, jnp.int32(lk.active.shape[0]))  # OOB drop
    seed = seed_nodes[:f]
    return dataclasses.replace(
        lk,
        active=lk.active.at[slot].set(True, mode="drop"),
        purpose=lk.purpose.at[slot].set(jnp.asarray(purpose, I32), mode="drop"),
        aux=lk.aux.at[slot].set(jnp.asarray(aux, I32), mode="drop"),
        target=lk.target.at[slot].set(target, mode="drop"),
        gen=lk.gen.at[slot].add(1, mode="drop"),
        frontier=lk.frontier.at[slot].set(seed, mode="drop"),
        fr_flags=lk.fr_flags.at[slot].set(jnp.full((f,), F_NEW, I32),
                                          mode="drop"),
        fr_src=lk.fr_src.at[slot].set(
            jnp.full((f,), NO_NODE, I32), mode="drop"),
        visited=lk.visited.at[slot].set(
            jnp.full((lk.visited.shape[1],), NO_NODE, I32), mode="drop"),
        vis_n=lk.vis_n.at[slot].set(0, mode="drop"),
        pending_dst=lk.pending_dst.at[slot].set(
            jnp.full((r,), NO_NODE, I32), mode="drop"),
        pend_prov=lk.pend_prov.at[slot].set(
            jnp.full((r,), NO_NODE, I32), mode="drop"),
        t_sent=lk.t_sent.at[slot].set(jnp.zeros((r,), I64), mode="drop"),
        t_to=lk.t_to.at[slot].set(jnp.full((r,), T_INF, I64), mode="drop"),
        retry=lk.retry.at[slot].set(jnp.zeros((r,), I32), mode="drop"),
        refire=lk.refire.at[slot].set(jnp.zeros((r,), bool), mode="drop"),
        deadline=lk.deadline.at[slot].set(now + cfg.deadline_ns, mode="drop"),
        hops=lk.hops.at[slot].set(0, mode="drop"),
        t0=lk.t0.at[slot].set(now, mode="drop"),
        done=lk.done.at[slot].set(False, mode="drop"),
        success=lk.success.at[slot].set(False, mode="drop"),
        result=lk.result.at[slot].set(NO_NODE, mode="drop"),
        results=lk.results.at[slot].set(
            jnp.full((f,), NO_NODE, I32), mode="drop"),
        res_n=lk.res_n.at[slot].set(0, mode="drop"),
        t_done=lk.t_done.at[slot].set(T_INF, mode="drop"),
        ext=lk.ext.at[slot].set(
            jnp.zeros((cfg.ext_words,), I32) if ext is None else ext,
            mode="drop"),
        ver_dst=lk.ver_dst.at[slot].set(NO_NODE, mode="drop"),
        ver_to=lk.ver_to.at[slot].set(T_INF, mode="drop"),
    )

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
    row = jnp.where(ok, l, l_dim)
    lk = dataclasses.replace(
        lk,
        pending_dst=lk.pending_dst.at[row, j].set(NO_NODE, mode="drop"),
        t_to=lk.t_to.at[row, j].set(T_INF, mode="drop"),
        retry=lk.retry.at[row, j].set(0, mode="drop"),
        refire=lk.refire.at[row, j].set(False, mode="drop"),
        hops=lk.hops.at[row].add(1, mode="drop"))

    if cfg.verify_siblings and not cfg.exhaustive:
        # S/Kademlia: stage the head candidate for ping verification
        # instead of completing (IterativeLookup.cc:295-340); pump sends
        # the ping.  The response still merges into the frontier below so
        # a failed verification continues the lookup.
        fin = ok & is_sib & (lk.ver_dst[l] == NO_NODE)
        slot_fin = jnp.where(fin, l, l_dim)
        lk = dataclasses.replace(
            lk,
            ver_dst=lk.ver_dst.at[slot_fin].set(resp_nodes[0], mode="drop"),
            ver_to=lk.ver_to.at[slot_fin].set(T_INF, mode="drop"),
            result=lk.result.at[slot_fin].set(resp_nodes[0], mode="drop"),
            results=lk.results.at[slot_fin].set(resp_nodes, mode="drop"))
        upd = ok
    elif not cfg.exhaustive:
        # finished: responder was a sibling → result = first returned node
        fin = ok & is_sib
        slot_fin = jnp.where(fin, l, l_dim)
        lk = dataclasses.replace(
            lk,
            done=lk.done.at[slot_fin].set(True, mode="drop"),
            success=lk.success.at[slot_fin].set(True, mode="drop"),
            result=lk.result.at[slot_fin].set(resp_nodes[0], mode="drop"),
            results=lk.results.at[slot_fin].set(resp_nodes, mode="drop"),
            t_done=lk.t_done.at[slot_fin].set(msg.t_deliver, mode="drop"))
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
        slot_acc = jnp.where(acc, l, l_dim)
        lk = dataclasses.replace(
            lk,
            results=lk.results.at[slot_acc].set(packed, mode="drop"),
            res_n=lk.res_n.at[slot_acc].set(
                jnp.sum(packed != NO_NODE, dtype=I32), mode="drop"))
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

    slot_upd = jnp.where(upd, l, l_dim)
    lk = dataclasses.replace(
        lk,
        frontier=lk.frontier.at[slot_upd].set(new_frontier, mode="drop"),
        fr_flags=lk.fr_flags.at[slot_upd].set(new_flags, mode="drop"),
        fr_src=lk.fr_src.at[slot_upd].set(new_src, mode="drop"))
    ew = cfg.ext_words
    if ew:
        # responder-updated extension rides the response tail
        lk = dataclasses.replace(lk, ext=lk.ext.at[slot_upd].set(
            msg.nodes[-ew:], mode="drop"))
    return lk

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

    # clear matched pending RPCs; count hops (IterativeLookup.cc:825)
    rows = jnp.where(ok, l_r, l_dim)
    lk = dataclasses.replace(
        lk,
        pending_dst=lk.pending_dst.at[rows, j].set(NO_NODE, mode="drop"),
        t_to=lk.t_to.at[rows, j].set(T_INF, mode="drop"),
        retry=lk.retry.at[rows, j].set(0, mode="drop"),
        refire=lk.refire.at[rows, j].set(False, mode="drop"),
        hops=lk.hops.at[rows].add(1, mode="drop"))

    resp_nodes = msgs.nodes[:, :f]                              # [R, F]
    has_nodes = jnp.any(resp_nodes != NO_NODE, axis=1)
    is_sib = (msgs.c != 0) & has_nodes

    def per_slot(pred):
        """[R] bool → ([L] any, [L] first-r index)."""
        m_rl = pred[:, None] & (l_r[:, None] == lixs[None, :])
        return jnp.any(m_rl, axis=0), jnp.argmax(m_rl, axis=0), m_rl

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
    fin = jnp.zeros((l_dim,), bool).at[jnp.where(ok, l_r, l_dim)].set(
        True, mode="drop")
    win = jnp.zeros((l_dim,), I32).at[jnp.where(ok, l_r, l_dim)].set(
        jnp.arange(msgs.valid.shape[0], dtype=I32), mode="drop")
    return dataclasses.replace(
        lk,
        done=lk.done | fin,
        success=lk.success | fin,
        t_done=jnp.where(fin, msgs.t_deliver[win], lk.t_done),
        ver_dst=jnp.where(fin, NO_NODE, lk.ver_dst),
        ver_to=jnp.where(fin, T_INF, lk.ver_to))

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

        rows = jnp.where(fire, jnp.arange(l_dim, dtype=I32), l_dim)
        vcol = vis_n % visited.shape[1]
        visited = visited.at[rows, vcol].set(cand, mode="drop")
        vis_n = vis_n + fire.astype(I32)
        fr_flags = fr_flags.at[rows, first].set(F_PENDING, mode="drop")
        pending_dst = pending_dst.at[rows, col].set(cand, mode="drop")
        pend_prov = pend_prov.at[rows, col].set(prov, mode="drop")
        t_sent_arr = t_sent_arr.at[rows, col].set(now, mode="drop")
        to_ns = (cfg.rpc_timeout_ns if timeout_fn is None
                 else timeout_fn(cand))
        t_to = t_to.at[rows, col].set(now + to_ns, mode="drop")
        retry = retry.at[rows, col].set(0, mode="drop")
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
