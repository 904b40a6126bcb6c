"""Plain reference for a Kademlia deployment under KBRTestApp.

What the deployment's semantics say the timed window must have left
behind, written straightforwardly in numpy and Python integers.  It
imports nothing of the program and reads nothing but what the window
produced: the state at the window's opening (``O``) and close (``C``),
the routing tables and pending RPCs at the close (``T``) and the message
pool at the end of every dispatch (``snaps``).  Keys are turned into
Python integers of their full width, times stay integer nanoseconds,
coordinates go to float64.

``readings(...)`` returns every number the verdict rests on;
``compare(readings, limits)`` holds each to its limit.  ``control(...)``
is the same semantics computed in the precision below the one the
configuration states, put in the program's place: it rewrites the
program's timers as float32 seconds and its message delays from bfloat16
coordinates, and has to come out as not correct.
"""

from __future__ import annotations

import heapq
import math

import numpy as np

NO_NODE = -1


# -- keys ---------------------------------------------------------------------

def keys_to_int(lanes: np.ndarray) -> list:
    """[M, KL] uint32 lanes, most significant first -> M Python ints."""
    out = []
    for row in np.asarray(lanes, dtype=np.uint64):
        v = 0
        for lane in row:
            v = (v << 32) | int(lane)
        out.append(v)
    return out


def shared_prefix_length(a: int, b: int, bits: int) -> int:
    """Leading bits two ``bits``-wide keys share (``bits`` if equal)."""
    return bits - (a ^ b).bit_length()


def closest(ids: list, alive: np.ndarray, key: int, count: int = 1) -> list:
    """The ``count`` alive nodes XOR-closest to ``key``, nearest first."""
    return heapq.nsmallest(count, (i for i in range(len(ids)) if alive[i]),
                           key=lambda i: ids[i] ^ key)


# -- the single checks ----------------------------------------------------------

def routing_table_faults(T: dict, ids: list, bits: int, nbuckets: int) -> dict:
    """Entries of a k-bucket that the XOR metric does not put there:
    wrong bucket for the shared prefix, a dead node, the owner itself, a
    node twice in one bucket.  Sibling rows that are not nearest-first,
    or hold the owner, a dead node or a node twice."""
    alive = T["alive"]
    buckets, sib = T["buckets"], T["sib"]
    n = len(ids)
    misplaced = entries = 0
    for i in range(n):
        rows = buckets[i]
        if not (rows != NO_NODE).any():
            continue
        for b in range(rows.shape[0]):
            held = [int(e) for e in rows[b] if e != NO_NODE]
            entries += len(held)
            if len(set(held)) != len(held):
                misplaced += len(held) - len(set(held))
            for e in held:
                if e == i or not 0 <= e < n or not alive[e]:
                    misplaced += 1
                    continue
                want = min(shared_prefix_length(ids[i], ids[e], bits),
                           nbuckets - 1)
                if want != b:
                    misplaced += 1
    disorder = sib_entries = 0
    for i in range(n):
        held = [int(e) for e in sib[i] if e != NO_NODE]
        sib_entries += len(held)
        bad = len(held) - len(set(held))
        bad += sum(1 for e in held
                   if e == i or not 0 <= e < n or not alive[e])
        if not bad:
            d = [ids[e] ^ ids[i] for e in held]
            bad += sum(1 for x, y in zip(d, d[1:]) if not x < y)
        disorder += bad
    return {"bucket_misplaced": misplaced, "bucket_entries": entries,
            "sibling_disorder": disorder, "sibling_entries": sib_entries}


def sibling_recall(T: dict, ids: list, sample: np.ndarray, s: int) -> dict:
    """Of a seeded sample of nodes: the share whose nearest sibling is
    the XOR-nearest alive node, and the share of the true ``s`` nearest
    that their sibling rows hold."""
    alive = T["alive"]
    first = held = 0
    for i in sample:
        i = int(i)
        mask = alive.copy()
        mask[i] = False
        true = closest(ids, mask, ids[i], s)
        row = [int(e) for e in T["sib"][i] if e != NO_NODE]
        first += bool(row) and row[0] == true[0]
        held += len(set(row) & set(true))
    return {"sibling_first_share": first / max(len(sample), 1),
            "sibling_recall": held / max(len(sample) * s, 1)}


def payload_answers(snaps: list, ids: list, alive: np.ndarray,
                    kind_oneway: int, rng: np.random.Generator,
                    cap: int, s: int) -> dict:
    """One-way test payloads seen in flight at the dispatch ends: the
    share addressed to a node outside the ``s`` nodes XOR-closest to the
    payload's key (Kademlia's sibling set), the share addressed to
    another node than the very closest, and the share that did not even
    reach the key's neighbourhood, the N/16 nodes nearest to it.  A
    payload is in flight for less than a tick, so none is seen twice."""
    seen = []
    for snap in snaps:
        rows = np.nonzero(snap["valid"] & (snap["kind"] == kind_oneway))[0]
        for r in rows:
            seen.append((int(snap["dst"][r]), snap["key"][r]))
    total = len(seen)
    if total > cap:
        pick = rng.choice(total, size=cap, replace=False)
        seen = [seen[i] for i in pick]
    # rank of the addressed node among all alive nodes, nearest = 0
    ranks = []
    for dst, lanes in seen:
        key = keys_to_int(lanes[None, :])[0]
        d_dst = ids[dst] ^ key if 0 <= dst < len(ids) else None
        ranks.append(len(ids) if d_dst is None else sum(
            1 for i in range(len(ids)) if alive[i] and ids[i] ^ key < d_dst))
    r = np.asarray(ranks, dtype=np.int64)
    far = max(len(ids) // 16, 1)
    m = len(seen)

    def share(hit):
        # no payload seen is no answer checked, not a share of 0
        return float(hit.sum()) / m if m else None

    return {"payloads_seen": total, "payloads_checked": len(seen),
            "payload_far_share": share(r >= far),
            "payload_outside_share": share(r >= s),
            "payload_not_nearest_share": share(r >= 1),
            "payload_rank_median": float(np.median(r)) if len(r) else None,
            "payload_rank_max": int(r.max()) if len(r) else None}


def least_delay_ns(coords, src, dst, size_b, ul: dict) -> np.ndarray:
    """The underlay model's delay without queueing and jitter, in ns:
    sender's and receiver's bandwidth delay for the bytes with headers,
    both access delays, and the coordinate distance."""
    c = np.asarray(coords, dtype=np.float64)
    bits = (np.asarray(size_b, dtype=np.float64) + ul["header_bytes"]) * 8
    dist = np.sqrt(((c[src] - c[dst]) ** 2).sum(axis=-1))
    sec = (2 * bits / ul["bandwidth_bit_s"] + 2 * ul["access_delay_s"]
           + ul["coord_delay_s_per_unit"] * dist)
    return sec * 1e9


def rpc_flights(snap: dict, kind_call: int):
    """FindNode calls in flight at a dispatch's end that one pending RPC
    of their sender accounts for: (src, dst, size_b, t_sent, t_deliver)."""
    rows = np.nonzero(snap["valid"] & (snap["kind"] == kind_call))[0]
    dst_tab, sent_tab = snap["rpc_dst"], snap["rpc_t_sent"]
    out = []
    for r in rows:
        src, dst = int(snap["src"][r]), int(snap["dst"][r])
        hit = np.argwhere((dst_tab[src] == dst)
                          & snap["rpc_active"][src][:, None])
        if len(hit) != 1:
            continue        # none (a ping) or two lookups asking one node
        l, k = hit[0]
        out.append((src, dst, int(snap["size_b"][r]),
                    int(sent_tab[src, l, k]), int(snap["t_deliver"][r])))
    return out


def delay_readings(flights: list, coords, ul: dict) -> dict:
    """How far the earliest message beats the model's least delay, and
    how far the latest exceeds it (ns, and as a share)."""
    if not flights:
        return {"rpc_flights": 0}
    f = np.asarray(flights, dtype=np.int64)
    least = least_delay_ns(coords, f[:, 0], f[:, 1], f[:, 2], ul)
    took = (f[:, 4] - f[:, 3]).astype(np.float64)
    return {"rpc_flights": len(flights),
            "delay_early_ns": float(np.max(least - took)),
            "delay_late_share": float(np.max(took / least - 1.0))}


# -- all readings ---------------------------------------------------------------

def readings(O: dict, C: dict, T: dict, snaps: list, *, config: dict,
             wire: dict, interval_ns: int, ticks_per_dispatch: int,
             dispatches: int, seed: int) -> dict:
    """Every number the verdict rests on, from what the window left."""
    rng = np.random.default_rng(int(seed))
    n = len(T["alive"])
    window_ns = int(round(config["engine"]["window"] * 1e9))
    bits = int(wire["key_bits"])
    kad = config["kademlia"]
    ids = keys_to_int(T["node_keys"])
    alive = np.asarray(T["alive"], dtype=bool)
    out = {}

    # the deployment is whole and the engine lost nothing
    out["alive_missing"] = n - int(alive.sum())
    out["not_ready"] = int((~T["ready"]).sum())
    lost = ("pool_overflow", "outbox_overflow", "queue_lost",
            "bit_error_lost", "dest_unavailable_lost", "partition_lost")
    out["messages_lost"] = int(sum(C["engine"][k] for k in lost))
    out["inbox_deferred_peak"] = int(C["engine"]["inbox_deferred"])

    # the run loop: whole dispatches, time moved on
    out["tick_count_gap"] = abs((C["tick"] - O["tick"])
                                - dispatches * ticks_per_dispatch)
    out["sim_ns_advanced"] = C["t_now_ns"] - O["t_now_ns"]

    # engine: nothing due is left behind in the pool beyond what the
    # engine says it ever deferred; every message goes to a live node
    last = snaps[-1] if snaps else None
    if last is not None:
        v = last["valid"]
        # (what a tick sends with a due time inside its own window is
        # delivered by the next tick: due, not overdue)
        overdue = int((v & (last["t_deliver"]
                            < last["t_now_ns"] - window_ns)).sum())
        out["pool_overdue_excess"] = max(
            0, overdue - out["inbox_deferred_peak"])
        dst = last["dst"][v]
        out["pool_bad_dst"] = int(((dst < 0) | (dst >= n)).sum()
                                  + (~alive[np.clip(dst, 0, n - 1)]).sum())
        out["pool_messages"] = int(v.sum())

    # KBRTestApp's accounting, recounted from the nodes' own sequence
    # numbers and the hop histogram
    st_o, st_c = O["stats"], C["stats"]
    d = lambda k: int(st_c["c:" + k]) - int(st_o["c:" + k])  # noqa: E731
    sent, delivered = d("kbr_sent"), d("kbr_delivered")
    ended = delivered + d("kbr_lookup_failed") + d("kbr_wrong_node")
    dseq = (C["seq"].astype(np.int64) - O["seq"].astype(np.int64))
    out["sent_recount_gap"] = abs(int(dseq.sum()) - sent)
    hist = (st_c["h:kbr_hop_hist"].astype(np.int64)
            - st_o["h:kbr_hop_hist"].astype(np.int64))
    hop = st_c["s:kbr_hopcount"] - st_o["s:kbr_hopcount"]
    out["hist_recount_gap"] = (abs(int(hist.sum()) - delivered)
                               + abs(int(round(float(hop[0]))) - delivered))
    if hist[-1] == 0:       # no delivery clipped into the last bin
        out["hist_recount_gap"] += abs(
            int((hist * np.arange(len(hist))).sum())
            - int(round(float(hop[1]))))
    out["lookups_sent"] = sent
    out["lookups_ended"] = ended
    out["lookups_delivered"] = delivered
    out["lookups_failed"] = d("kbr_lookup_failed")
    out["lookups_wrong_node"] = d("kbr_wrong_node")
    out["delivery_share"] = delivered / max(sent, ended, 1)
    # steadier from seed to seed than the share above, which wanders with
    # the lookups in flight at a short window's two ends
    out["lookup_failed_share"] = (ended - delivered) / max(ended, 1)

    # time: a test timer fires at its own due time, so it moves by whole
    # intervals, to the nanosecond
    dt = C["t_test"].astype(np.int64) - O["t_test"].astype(np.int64)
    out["timer_off_lattice"] = int((dt != dseq * interval_ns).sum())
    # ... and no tick skips a node: at the close no joined node's test is
    # overdue by more than a tick, none that fired was due beyond the
    # last tick's end, and the tests sent follow the arrival law the
    # traffic states (each node's phase is uniform in the interval, so
    # the sends of a window W are N * floor(W/I) + Binomial(N, frac(W/I)))
    due = C["t_test"].astype(np.int64)
    joined = alive & np.asarray(T["ready"], dtype=bool)
    out["timers_overdue"] = int((joined
                                 & (due < C["t_now_ns"] - window_ns)).sum())
    out["timers_early"] = int(((dseq > 0) & (due - interval_ns
                                             >= C["t_now_ns"] + window_ns)
                               ).sum())
    q = out["sim_ns_advanced"] / interval_ns
    p = q - math.floor(q)
    out["sent_off_binomial"] = (abs(sent - n * q)
                                / math.sqrt(max(n * p * (1.0 - p), 1.0)))

    # Kademlia's routing table against the XOR metric
    out.update(routing_table_faults(T, ids, bits, int(kad["buckets"])))
    sample = rng.choice(n, size=min(n, 128), replace=False)
    out.update(sibling_recall(T, ids, sample, int(kad["s"])))

    # the lookups' answers: where the payloads went
    out.update(payload_answers(snaps, ids, alive, wire["APP_ONEWAY"],
                               rng, cap=300, s=int(kad["s"])))

    # the underlay's delays of the calls in flight at the dispatch ends
    flights = [f for snap in snaps
               for f in rpc_flights(snap, wire["FINDNODE_CALL"])]
    out.update(delay_readings(flights, T["coords"], config["underlay"]))
    return out


# -- the verdict ----------------------------------------------------------------

def compare(r: dict, limits: dict) -> list:
    """Hold each reading to its limit.  Returns rows
    ``(name, value, how, limit, ok)``; a reading that a limit names and
    the window did not produce fails."""
    rows = []
    for name, (how, limit) in limits.items():
        value = r.get(name)
        if value is None:
            ok = False
        elif how == "max":
            ok = value <= limit
        elif how == "min":
            ok = value >= limit
        else:
            raise ValueError(f"limit {name}: unknown kind {how!r}")
        rows.append((name, value, how, limit, bool(ok)))
    return rows


# -- the control ----------------------------------------------------------------

def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to bfloat16 (nearest even), back as float32."""
    u = np.asarray(x, dtype=np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32)


def control(O: dict, C: dict, T: dict, snaps: list, *, config: dict,
            wire: dict, interval_ns: int):
    """The reference in the program's place, one precision down.

    Time as float32 seconds: each node's next test time is the time it
    had at the opening plus its tests times the interval, summed in
    float32.  Coordinates as bfloat16: each call in flight at the close
    is given the least delay the model computes from the rounded
    coordinates, with the jitter it really had.  Returns ``(C', snaps')``
    to be read by ``readings`` exactly as the program's are."""
    dseq = C["seq"].astype(np.int64) - O["seq"].astype(np.int64)
    t = (O["t_test"].astype(np.float64) / 1e9).astype(np.float32)
    step = np.float32(interval_ns / 1e9)
    for _ in range(int(dseq.max(initial=0))):
        t = np.where(dseq > 0, t + step, t).astype(np.float32)
        dseq = dseq - (dseq > 0)
    c2 = dict(C)
    c2["t_test"] = np.round(t.astype(np.float64) * 1e9).astype(np.int64)

    snaps2 = []
    ul = config["underlay"]
    low = _bf16(T["coords"])
    for snap in snaps:
        last = dict(snap)
        t_del = last["t_deliver"].copy()
        for src, dst, size_b, t_sent, t_deliver in rpc_flights(
                last, wire["FINDNODE_CALL"]):
            full = least_delay_ns(T["coords"], src, dst, size_b, ul)
            jitter = (t_deliver - t_sent) / full
            lowd = least_delay_ns(low, src, dst, size_b, ul)
            rows = np.nonzero(last["valid"] & (last["src"] == src)
                              & (last["dst"] == dst)
                              & (last["kind"] == wire["FINDNODE_CALL"])
                              & (last["t_deliver"] == t_deliver))[0]
            t_del[rows] = t_sent + int(lowd * jitter)
        last["t_deliver"] = t_del
        snaps2.append(last)
    return c2, snaps2
