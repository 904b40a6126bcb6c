"""Plain reference for a Chord deployment under KBRTestApp.

A ring has an owner: of a key, the first alive node key at or after it
clockwise.  From the SORTED KEYS of the alive nodes alone this reference
knows every node's successor, predecessor and successor list, the node
each finger has to lie beyond, and the owner of every payload's key, and
holds the program to them; from the configuration's ``chord`` block (the
file's, not the program's) it knows how many stabilise rounds,
predecessor pings and fix-fingers rounds the window must have started.

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
UPKEEP = (("stabilise", "chord_stab_rounds", "stabilize_delay_s"),
          ("pred_ping", "chord_pred_pings", "check_pred_delay_s"),
          ("fix_fingers", "chord_fix_rounds", "fixfingers_delay_s"))


class Ring:
    """The alive nodes in key order."""

    def __init__(self, ids: list, alive: np.ndarray, bits: int):
        self.ids, self.mod = ids, 1 << bits
        self.order = sorted((i for i in range(len(ids)) if alive[i]),
                            key=lambda i: ids[i])
        self.sorted_ids = [ids[i] for i in self.order]
        self.pos = {node: p for p, node in enumerate(self.order)}

    def __len__(self):
        return len(self.order)

    def at(self, p: int) -> int:
        return self.order[p % len(self.order)]

    def owner(self, key: int) -> int:
        """The first alive node at or after ``key`` clockwise."""
        return self.at(bisect.bisect_left(self.sorted_ids, key % self.mod))

    def clockwise(self, a: int, b: int) -> int:
        """Distance from key ``a`` to key ``b`` going up."""
        return (b - a) % self.mod


# -- the single checks ----------------------------------------------------------

def ring_faults(T: dict, ring: Ring, succ_size: int) -> dict:
    """First successor and predecessor of every alive node against the
    sorted ring; successor-list entries that are the node itself, dead,
    twice in the list, out of clockwise order, or not among the next
    ``2 x succ_size`` nodes clockwise."""
    succ, pred = T["succ"], T["pred"]
    m = len(ring)
    succ_wrong = pred_wrong = faults = entries = short = 0
    for p, i in enumerate(ring.order):
        if m > 1:
            succ_wrong += int(succ[i, 0]) != ring.at(p + 1)
            pred_wrong += int(pred[i]) != ring.at(p - 1)
        held = [int(e) for e in succ[i] if e != NO_NODE]
        entries += len(held)
        short += min(succ_size, m - 1) - len(held)
        near = {ring.at(p + k) for k in range(1, 2 * succ_size + 1)}
        last = 0
        for k, e in enumerate(held):
            d = ring.clockwise(ring.ids[i], ring.ids[e]) \
                if e in ring.pos else None
            if (d is None or e == i or e in held[:k] or d <= last
                    or e not in near):
                faults += 1
            if d is not None:
                last = max(last, d)
    return {"succ_wrong": succ_wrong, "pred_wrong": pred_wrong,
            "succ_list_faults": faults, "succ_list_entries": entries,
            "succ_list_missing": short}


def finger_faults(T: dict, ring: Ring) -> dict:
    """Finger ``i`` of node ``me`` has to lie in ``[me + 2^i, me)``
    clockwise and be alive; of the fingers that are set and not dirty,
    the share that is not the owner of ``me + 2^i`` at the close."""
    finger, dirty = T["finger"], T["finger_dirty"]
    outside = held = clean = stale = 0
    for i in ring.order:
        me = ring.ids[i]
        for b in np.nonzero(finger[i] != NO_NODE)[0]:
            f = int(finger[i, b])
            held += 1
            if (f not in ring.pos or f == i
                    or ring.clockwise(me, ring.ids[f]) < (1 << int(b))):
                outside += 1
                continue
            if not dirty[i, b]:
                clean += 1
                stale += f != ring.owner(me + (1 << int(b)))
    return {"finger_outside": outside, "finger_entries": held,
            "finger_dirty": int(dirty[ring.order].sum()),
            "finger_stale_share": stale / clean if clean else None}


def payload_owners(snaps: list, ring: Ring, kind_oneway: int) -> dict:
    """One-way test payloads seen in flight at the dispatch ends: how
    many are addressed to another node than the owner of their key.  A
    payload is in flight for less than a tick, so none is seen twice."""
    seen = beside = 0
    for snap in snaps:
        rows = np.nonzero(snap["valid"] & (snap["kind"] == kind_oneway))[0]
        if not len(rows):
            continue
        keys = keys_to_int(snap["key"][rows])
        for dst, key in zip(snap["dst"][rows], keys):
            seen += 1
            beside += int(dst) != ring.owner(key)
    return {"payloads_seen": seen, "payload_not_owner": beside}


def upkeep_law(O: dict, C: dict, T: dict, law: dict, window_ns: int) -> dict:
    """The rounds each of the overlay's timers started in the window
    against READY node-seconds over its period, in rounds a node (a
    timer that fires every period starts floor or ceil of window/period
    rounds), and the timers overdue by more than a tick at the close."""
    n = int(T["ready"].sum())
    sim_s = (C["t_now_ns"] - O["t_now_ns"]) / 1e9
    d = lambda k: (int(C["stats"]["c:" + k])     # noqa: E731
                   - int(O["stats"]["c:" + k]))
    out = {}
    for name, counter, period in UPKEEP:
        rounds = d(counter)
        out[name + "_rounds"] = rounds
        out[name + "_rounds_off"] = (abs(rounds - n * sim_s / law[period])
                                     / max(n, 1))
    late = C["t_now_ns"] - window_ns
    ready = T["ready"] & T["alive"]
    out["upkeep_timers_overdue"] = int(sum(
        (ready & (T[t] < late)).sum() for t in ("t_stab", "t_fix", "t_cp")))
    out["fix_lookups_started"] = d("chord_fix_lookups")
    out["fix_lookups_ended"] = d("chord_fix_ended")
    out["notify_taken"] = d("chord_notify_taken")
    out["join_calls_passed_on"] = d("chord_join_passed")
    out["join_calls_dropped"] = d("chord_join_dropped")
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

    law = config["chord"]
    window_ns = int(round(config["engine"]["window"] * 1e9))
    ring = Ring(keys_to_int(T["node_keys"]),
                np.asarray(T["alive"], dtype=bool), int(wire["key_bits"]))
    out.update(ring_faults(T, ring, int(law["succ_size"])))
    out.update(finger_faults(T, ring))
    out.update(payload_owners(snaps, ring, wire["APP_ONEWAY"]))
    out.update(upkeep_law(O, C, T, law, window_ns))
    return out
