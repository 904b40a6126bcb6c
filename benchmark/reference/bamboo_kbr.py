"""Plain reference for a Bamboo (Pastry-family) deployment under KBRTestApp.

A prefix-routing overlay has an owner too: of a key, the alive node
numerically closest to it on the ring (the lesser of the two ways
round).  From the SORTED KEYS of the alive nodes alone this reference
knows every node's two leaf-set halves, the row and column every
routing-table entry has to sit in, which cells of a table could be
filled at all, and the owner of every payload's key; from the
configuration's ``bamboo`` block (the file's, not the program's) it
knows how many leaf-set push-pulls, local-tuning probes and
global-tuning lookups the window must have started, what a routed hop
may and may not do, and it routes every payload it saw arrive once more,
greedily, over the tables it read at the close.

Written in numpy and Python integers; imports nothing of the program,
and ``kademlia_kbr`` only for the checks that hold unchanged under any
overlay (``SHARED``: the engine's losses, the pool, the run loop, the
recounts of KBRTestApp's accounting, the test timers' lattice and
arrival law, the underlay's least delay), for ``compare`` and for the
lower-precision ``control``.
"""

from __future__ import annotations

import bisect
import importlib.util
import math
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "benchmark_reference_kademlia_kbr",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "kademlia_kbr.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

NO_NODE = base.NO_NODE
compare = base.compare
control = base.control
keys_to_int = base.keys_to_int

# the readings of ``kademlia_kbr`` that no overlay changes
SHARED = (
    "alive_missing", "not_ready", "messages_lost", "inbox_deferred_peak",
    "tick_count_gap", "sim_ns_advanced", "pool_overdue_excess",
    "pool_bad_dst", "pool_messages", "sent_recount_gap", "hist_recount_gap",
    "lookups_sent", "lookups_ended", "lookups_delivered", "lookups_failed",
    "lookups_wrong_node", "delivery_share", "lookup_failed_share",
    "timer_off_lattice", "timers_overdue", "timers_early",
    "sent_off_binomial", "rpc_flights", "delay_early_ns",
    "delay_late_share")
# (reading, the program's counter, the file's interval, the timer)
UPKEEP = (("leafset", "bamboo_ls_rounds", "leafset_interval_s", "t_ls"),
          ("local_tuning", "bamboo_lt_probes", "local_tuning_interval_s",
           "t_lt"),
          ("global_tuning", "bamboo_gt_lookups", "global_tuning_interval_s",
           "t_gt"))
FORWARD_ENDS = ("route_acked", "route_ack_timeouts",
                "route_unacked_table_full")
ROUTE_ENDS = ("route_delivered", "route_dropped_no_candidate",
              "route_dropped_hop_bound")


class Ring:
    """The alive nodes in key order."""

    def __init__(self, ids: list, alive: np.ndarray, bits: int):
        self.ids, self.bits, self.mod = ids, bits, 1 << bits
        self.order = sorted((i for i in range(len(ids)) if alive[i]),
                            key=lambda i: ids[i])
        self.sorted_ids = [ids[i] for i in self.order]
        self.pos = {node: p for p, node in enumerate(self.order)}

    def __len__(self):
        return len(self.order)

    def at(self, p: int) -> int:
        return self.order[p % len(self.order)]

    def dist(self, a: int, b: int) -> int:
        """The lesser way round between two keys."""
        d = (a - b) % self.mod
        return min(d, self.mod - d)

    def owner(self, key: int) -> int:
        """The alive node numerically closest to ``key`` on the ring
        (the lower key where two are as close)."""
        p = bisect.bisect_left(self.sorted_ids, key % self.mod)
        return min((self.at(p - 1), self.at(p)),
                   key=lambda i: (self.dist(self.ids[i], key), self.ids[i]))

    def between(self, key: int, a: int, b: int) -> bool:
        """``key`` in [a, b] going up from ``a``."""
        return (key - a) % self.mod <= (b - a) % self.mod


class Digits:
    """Keys as digits of ``b`` bits, most significant first."""

    def __init__(self, bits: int, b: int, rows: int):
        self.bits, self.b, self.rows = bits, b, rows

    def shared(self, x: int, y: int) -> int:
        """Leading digits two keys share (all of them if equal)."""
        return (self.bits - (x ^ y).bit_length()) // self.b

    def digit(self, x: int, r: int) -> int:
        return (x >> (self.bits - self.b * (r + 1))) & ((1 << self.b) - 1)

    def cell(self, holder: int, x: int):
        """The (row, column) ``x`` earns in ``holder``'s table."""
        r = min(self.shared(holder, x), self.rows - 1)
        return r, self.digit(x, r)


# -- the single checks ----------------------------------------------------------

def leaf_faults(T: dict, ring: Ring) -> dict:
    """Each half of every alive node's leaf set against the sorted ring:
    place k of the clockwise half has to hold the k-th next alive key,
    of the other half the k-th previous (``leaf_wrong`` counts the
    places that do not); a half whose held entries are not strictly
    farther and farther on its own side, or hold its owner, a dead node
    or a node twice, is out of order (``leaf_disorder`` counts halves).
    The cycles that following the first clockwise leaf ends in: one."""
    m = len(ring)
    wrong = disorder = entries = 0
    first_wrong = 0
    for p, i in enumerate(ring.order):
        me = ring.ids[i]
        for half, step in ((T["leaf_cw"][i], 1), (T["leaf_ccw"][i], -1)):
            want = [ring.at(p + step * k)
                    for k in range(1, min(len(half), m - 1) + 1)]
            got = [int(e) for e in half[:len(want)]]
            bad = sum(a != b for a, b in zip(got, want))
            wrong += bad
            first_wrong += bool(want) and got[0] != want[0]
            held = [int(e) for e in half if e != NO_NODE]
            entries += len(held)
            last = 0
            for k, e in enumerate(held):
                d = ((ring.ids[e] - me) * step) % ring.mod \
                    if e in ring.pos else None
                if d is None or e == i or e in held[:k] or d <= last:
                    disorder += 1
                    break
                last = d
    seen, cycles = set(), 0
    for i in ring.order:
        path = []
        while i not in seen and i in ring.pos:
            seen.add(i)
            path.append(i)
            i = int(T["leaf_cw"][i][0])
        cycles += i in path
    return {"leaf_wrong": wrong, "leaf_first_wrong": first_wrong,
            "leaf_disorder": disorder, "leaf_entries": entries,
            "leaf_cycles": cycles}


def table_faults(T: dict, ring: Ring, dg: Digits) -> dict:
    """Every held routing-table entry against the row and column its key
    earns against its holder's (a dead node or the holder itself earns
    none), and the share of the cells that COULD be filled (some alive
    node earns them) that are empty."""
    rt = T["rt"]
    ids = ring.ids
    misplaced = entries = fillable = unfilled = 0
    for i in ring.order:
        me = ids[i]
        for r, c in zip(*np.nonzero(rt[i] != NO_NODE)):
            e = int(rt[i, r, c])
            entries += 1
            if (e == i or e not in ring.pos
                    or dg.cell(me, ids[e]) != (int(r), int(c))):
                misplaced += 1
        could = {dg.cell(me, ids[e]) for e in ring.order if e != i}
        fillable += len(could)
        unfilled += sum(rt[i, r, c] == NO_NODE for r, c in could)
    return {"rt_misplaced": misplaced, "rt_entries": entries,
            "rt_entries_a_node": entries / max(len(ring), 1),
            "rt_unfilled_share": unfilled / fillable if fillable else None}


class Router:
    """Pastry's next hop, from the tables read at the close: the key's
    closest leaf where the key lies inside the leaf set's span, else the
    routing-table entry of the key's cell, else the closest known node
    that shares at least as long a prefix and is nearer."""

    def __init__(self, T: dict, ring: Ring, dg: Digits):
        self.T, self.ring, self.dg = T, ring, dg

    def leaves(self, i: int) -> list:
        return [int(e) for half in (self.T["leaf_cw"][i],
                                    self.T["leaf_ccw"][i])
                for e in half if e != NO_NODE]

    def delivers(self, i: int, key: int) -> bool:
        """Whether node ``i`` holds itself responsible for ``key``:
        neither of its two nearest leaves is closer."""
        ring = self.ring
        me = ring.dist(ring.ids[i], key)
        for half in (self.T["leaf_cw"][i], self.T["leaf_ccw"][i]):
            e = int(half[0])
            if e != NO_NODE and ring.dist(ring.ids[e], key) < me:
                return False
        return True

    def in_span(self, i: int, key: int) -> bool:
        cw = [int(e) for e in self.T["leaf_cw"][i] if e != NO_NODE]
        ccw = [int(e) for e in self.T["leaf_ccw"][i] if e != NO_NODE]
        return bool(cw and ccw) and self.ring.between(
            key, self.ring.ids[ccw[-1]], self.ring.ids[cw[-1]])

    def next_hop(self, i: int, key: int):
        ring, dg = self.ring, self.dg
        ids = ring.ids
        if self.in_span(i, key):
            best = min(self.leaves(i) + [i],
                       key=lambda e: ring.dist(ids[e], key))
            if best != i:
                return best
        r, c = dg.cell(ids[i], key)
        e = int(self.T["rt"][i, r, c])
        if e != NO_NODE:
            return e
        me, pfx = ring.dist(ids[i], key), dg.shared(ids[i], key)
        known = self.leaves(i) + [int(x) for x in
                                  self.T["rt"][i].reshape(-1) if x != NO_NODE]
        ok = [x for x in known if ring.dist(ids[x], key) < me
              and dg.shared(ids[x], key) >= pfx]
        return min(ok, key=lambda x: ring.dist(ids[x], key)) if ok else None

    def hops(self, src: int, key: int, bound: int):
        """Hops of greedy routing from ``src`` to the node that
        delivers, or None where it does not arrive within ``bound``."""
        at, n = src, 0
        while not self.delivers(at, key):
            at = self.next_hop(at, key)
            n += 1
            if at is None or n > bound:
                return None
        return n


def routed_hops(snaps: list, router: Router, wire: dict, hop_max: int) -> dict:
    """Every ``KBR_ROUTE`` message seen in the pool after a dispatch (a
    hop is in flight for less than a tick, so none is seen twice): its
    next hop is not in its visited list and is not its source
    (``route_loops``), it is within the hop bound, and the next hop is
    nearer the key than the forwarder by Pastry's measure: a longer
    shared prefix, or as long a one and numerically closer, or a leaf
    of the forwarder that is numerically closer (``route_no_progress``).
    A message whose next hop holds itself responsible is on its LAST
    hop: that node has to be the key's owner (``payload_not_owner``),
    and the hops it took are set against greedy routing from its source
    over the tables at the close (``hops_off_greedy``: the mean of the
    differences' sizes)."""
    ring, dg = router.ring, router.dg
    ids = ring.ids
    seen = loops = over = stuck = last = beside = 0
    took, greedy, off = [], [], []
    for snap in snaps:
        rows = np.nonzero(snap["valid"]
                          & (snap["kind"] == wire["KBR_ROUTE"]))[0]
        if not len(rows):
            continue
        keys = keys_to_int(snap["key"][rows])
        for r, key in zip(rows, keys):
            f, d = int(snap["src"][r]), int(snap["dst"][r])
            visited = [int(e) for e in snap["visited"][r] if e != NO_NODE]
            hops = int(snap["hops"][r])
            seen += 1
            loops += d in visited or (bool(visited) and d == visited[0])
            over += hops > hop_max
            if d not in ring.pos or f not in ring.pos:
                stuck += 1
                continue
            pf, pd = dg.shared(ids[f], key), dg.shared(ids[d], key)
            nearer = ring.dist(ids[d], key) < ring.dist(ids[f], key)
            if not (pd > pf or (pd == pf and nearer)
                    or (nearer and d in router.leaves(f))):
                stuck += 1
            if (snap["inner"][r] != wire["APP_ONEWAY"]
                    or not router.delivers(d, key)):
                continue
            last += 1
            beside += d != ring.owner(key)
            g = router.hops(visited[0], key, hop_max) if visited else None
            took.append(hops)
            if g is not None:
                greedy.append(g)
                off.append(abs(hops - g))
    n = len(took)
    return {"routes_seen": seen, "route_loops": loops,
            "route_hops_over_bound": over, "route_no_progress": stuck,
            "payloads_seen": last, "payload_not_owner": beside,
            "hops_mean": sum(took) / n if n else None,
            "hops_greedy_mean": sum(greedy) / len(greedy) if greedy else None,
            "hops_greedy_lost": n - len(greedy),
            "hops_off_greedy": sum(off) / len(off) if off else None}


def route_recounts(O: dict, C: dict) -> dict:
    """Every hop sent ends as ACKed, timed out, sent with no ACK slot or
    still pending; every payload handed to the routed path ends as
    delivered, dropped or still in flight.  Counters at the close less
    at the opening, the slots and messages at both.  The recount
    BALANCES a drop the program counts, so the drops have a reading of
    their own: ``route_dropped_share`` of the payloads handed to the
    routed path (a payload the engine loses is in neither: it is the
    recount's gap and ``messages_lost``)."""
    d = lambda k: (int(C["stats"]["c:" + k])      # noqa: E731
                   - int(O["stats"]["c:" + k]))
    hops = d("route_forwarded")
    ended = sum(d(k) for k in FORWARD_ENDS)
    pending = C["route_pending"] - O["route_pending"]
    routes = d("bamboo_app_routes")
    done = sum(d(k) for k in ROUTE_ENDS)
    flying = C["routes_in_flight"] - O["routes_in_flight"]
    dropped = d("route_dropped_no_candidate") + d("route_dropped_hop_bound")
    return {"route_recount_gap": (abs(hops - ended - pending)
                                  + abs(routes - done - flying)),
            "route_dropped_share": dropped / max(routes, 1),
            "route_forwarded": hops, "route_acked": d("route_acked"),
            "route_ack_timeouts": d("route_ack_timeouts"),
            "route_rerouted": d("route_rerouted"),
            "route_unacked": d("route_unacked_table_full"),
            "route_delivered": d("route_delivered"),
            "route_dropped": dropped,
            "app_routes": routes,
            "route_hops_a_delivery": (hops / d("route_delivered")
                                      if d("route_delivered") else None)}


def upkeep_law(O: dict, C: dict, T: dict, law: dict, window_ns: int) -> dict:
    """The rounds each of the overlay's three timers started in the
    window against the law the file states: a node's phase is uniform in
    the interval I, so the rounds of a window W are N floor(W/I) +
    Binomial(N, frac(W/I)); the distance in deviations of that law (as
    ``sent_off_binomial`` holds the test timers).  And the timers
    overdue by more than a tick at the close."""
    n = int((T["ready"] & T["alive"]).sum())
    sim_s = (C["t_now_ns"] - O["t_now_ns"]) / 1e9
    late = C["t_now_ns"] - window_ns
    ready = T["ready"] & T["alive"]
    out, overdue = {}, 0
    for name, counter, period, timer in UPKEEP:
        rounds = (int(C["stats"]["c:" + counter])
                  - int(O["stats"]["c:" + counter]))
        q = sim_s / float(law[period])
        p = q - math.floor(q)
        out[name + "_rounds"] = rounds
        out[name + "_off_law"] = (abs(rounds - n * q)
                                  / math.sqrt(max(n * p * (1.0 - p), 1.0)))
        overdue += int((ready & (T[timer] < late)).sum())
    out["upkeep_timers_overdue"] = overdue
    out["state_msgs_answered"] = (int(C["stats"]["c:bamboo_state_msgs"])
                                  - int(O["stats"]["c:bamboo_state_msgs"]))
    return out


# -- all readings ---------------------------------------------------------------

def readings(O: dict, C: dict, T: dict, snaps: list, *, config: dict,
             wire: dict, interval_ns: int, ticks_per_dispatch: int,
             dispatches: int, seed: int) -> dict:
    """Every number the verdict rests on, from what the window left."""
    n = len(T["alive"])
    # what holds under any overlay, by the reference that holds it for
    # Kademlia, handed a table with no bucket and no sibling
    empty = dict(T, buckets=np.full((n, 1, 1), NO_NODE, np.int32),
                 sib=np.full((n, 1), NO_NODE, np.int32))
    shared = base.readings(
        O, C, empty, snaps, wire=wire, interval_ns=interval_ns,
        ticks_per_dispatch=ticks_per_dispatch, dispatches=dispatches,
        seed=seed, config=dict(config, kademlia={"k": 1, "s": 1,
                                                 "buckets": 1}))
    out = {k: shared[k] for k in SHARED if k in shared}

    law = config["bamboo"]
    window_ns = int(round(config["engine"]["window"] * 1e9))
    bits = int(wire["key_bits"])
    ring = Ring(keys_to_int(T["node_keys"]),
                np.asarray(T["alive"], dtype=bool), bits)
    dg = Digits(bits, int(law["bits_per_digit"]), int(law["rows"]))
    out.update(leaf_faults(T, ring))
    out.update(table_faults(T, ring, dg))
    out.update(routed_hops(snaps, Router(T, ring, dg), wire,
                           int(law["hop_max"])))
    out.update(route_recounts(O, C))
    out.update(upkeep_law(O, C, T, law, window_ns))
    return out
