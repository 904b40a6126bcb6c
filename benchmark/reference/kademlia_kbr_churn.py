"""Plain reference for a Kademlia deployment under KBRTestApp AND a churn law.

``kademlia_kbr`` holds a population that never changes.  Here nodes join
and die while the window runs (LifetimeChurn: ``2 x target`` context
slots, half of them dead at any time; a slot that dies is recycled, after
a dead time, under a FRESH key and fresh coordinates), so this reference
follows joins and deaths from what ``program_churn.py`` adds to every
read-back under ``"churn"``: who was alive, under which key and where,
who had joined, each node's test timer and sequence number, the churn
schedule and each slot's incarnation ``t_born`` (the start of the tick
that last created a node in the slot).  A NODE here is (slot, t_born).

Written in numpy and Python integers; imports nothing of the program, and
``kademlia_kbr`` only for the checks that hold unchanged (key arithmetic,
the underlay's least delay, the RPC flights, ``compare``).

What two read-backs may be apart (``readings`` refuses a window that
breaks it): less than the configuration's ``graceful_leave_delay_s`` of
simulated time.  A node lives at least that long (its leave notice comes
that long before its death, and no earlier than its birth), so between
two read-backs a slot is born at most once and killed at most once, and a
node's notice and its death never fall between the same two.  A node
under notice sends no test (its timer is parked in the tick of the
notice), so every test a node ever sent was counted at a read-back that
still saw it: the recount of sends from the sequence numbers has NO
residue, and its limit is 0.
"""

from __future__ import annotations

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
keys_to_int = base.keys_to_int
shared_prefix_length = base.shared_prefix_length

T_INF = 2 ** 62
LOST_OTHERWISE = ("pool_overflow", "outbox_overflow", "queue_lost",
                  "bit_error_lost", "partition_lost")


# -- the timeline -----------------------------------------------------------

def timeline(O: dict, snaps: list) -> list:
    """The churn views in order: the opening's, then one a dispatch.
    (The close is read right after the last dispatch: the last view.)"""
    return ([dict(O["churn"], t_now_ns=O["t_now_ns"])]
            + [dict(s["churn"], t_now_ns=s["t_now_ns"]) for s in snaps])


def serving(view: dict) -> np.ndarray:
    """Alive, joined and under no leave notice: the nodes whose test
    timers run."""
    return view["alive"] & view["ready"] & ~(view["t_dead"] < T_INF)


def whole_window(views: list) -> np.ndarray:
    """The slots that served at every read-back from the opening to the
    close as ONE node."""
    whole = views[0]["t_born"] == views[-1]["t_born"]
    for view in views:
        whole = whole & serving(view)
    return whole


def population(views: list, law: dict) -> dict:
    """Births, leave notices and kills recounted from the incarnations
    between consecutive read-backs against the engine's counters; the
    population against the target; both rates against the law."""
    mean_s = float(law["lifetime_mean_s"])
    slots = len(views[0]["alive"])
    target = slots // 2            # LifetimeChurn: 2 x target context slots
    birth_gap = kill_gap = notice_gap = odd = 0
    births = kills = 0
    want_births = want_kills = 0.0
    for a, b in zip(views, views[1:]):
        born = b["t_born"] != a["t_born"]
        # a slot's kills between two views: what its alive flag and its
        # births leave over; 0 or 1 by the module's rule
        k = (a["alive"].astype(np.int64) + born.astype(np.int64)
             - b["alive"].astype(np.int64))
        odd += int(((k < 0) | (k > 1)).sum())
        odd += int((born & ~((a["t_now_ns"] <= b["t_born"])
                             & (b["t_born"] < b["t_now_ns"]))).sum())
        # a notice: a final kill is scheduled where none was, or, in a
        # slot reborn since the last view, the NEW node's (a node under
        # notice can die, its slot be recycled at once and the newcomer,
        # drawn a life shorter than the notice, be given its own)
        noticed = (b["t_dead"] < T_INF) & (~(a["t_dead"] < T_INF) | born)
        birth_gap += abs(int(born.sum())
                         - (b["churn_created"] - a["churn_created"]))
        kill_gap += abs(int(k.sum())
                        - (b["churn_killed"] - a["churn_killed"]))
        notice_gap += abs(int(noticed.sum())
                          - (b["churn_prekilled"] - a["churn_prekilled"]))
        births += int(born.sum())
        kills += int(k.sum())
        dt = (b["t_now_ns"] - a["t_now_ns"]) / 1e9
        alive = int(a["alive"].sum())
        want_kills += alive * dt / mean_s
        want_births += (slots - alive) * dt / mean_s
    dev = math.sqrt(target / 2.0)
    off = max(abs(int(v["alive"].sum()) - target) for v in views) / dev
    return {
        "births_recount_gap": birth_gap, "kills_recount_gap": kill_gap,
        "notices_recount_gap": notice_gap, "incarnations_odd": odd,
        "births": births, "kills": kills,
        "births_expected": want_births, "kills_expected": want_kills,
        "alive_off_target": off,
        "alive_least": min(int(v["alive"].sum()) for v in views),
        "alive_greatest": max(int(v["alive"].sum()) for v in views),
        "births_off_poisson": abs(births - want_births)
        / math.sqrt(max(want_births, 1.0)),
        "kills_off_poisson": abs(kills - want_kills)
        / math.sqrt(max(want_kills, 1.0)),
    }


def dead_slots_busy(views: list, snaps: list, T: dict) -> int:
    """Dead slots that still hold a test timer or an active lookup at a
    read-back, or any overlay timer or lookup at the close."""
    busy = 0
    for view in views:
        busy += int((~view["alive"] & (view["t_test"] < T_INF)).sum())
    for snap in snaps:
        dead = ~snap["churn"]["alive"]
        busy += int((dead & snap["rpc_active"].any(axis=-1)).sum())
    dead = ~views[-1]["alive"]
    for name in ("t_join", "t_refresh"):
        busy += int((dead & (T[name] < T_INF)).sum())
    busy += int((dead & (T["ping_to"] < T_INF).any(axis=-1)).sum())
    busy += int((dead & T["lookup_active"].any(axis=-1)).sum())
    return busy


def sends(views: list) -> tuple:
    """Tests sent, recounted per incarnation from the sequence numbers
    across the read-backs; and the serving node-seconds they came from."""
    sent = 0
    node_s = 0.0
    for a, b in zip(views, views[1:]):
        same = (b["t_born"] == a["t_born"]) & a["alive"] & b["alive"]
        born = (b["t_born"] != a["t_born"]) & b["alive"]
        sent += int((b["seq"].astype(np.int64)
                     - a["seq"].astype(np.int64))[same].sum())
        sent += int(b["seq"].astype(np.int64)[born].sum())
        dt = (b["t_now_ns"] - a["t_now_ns"]) / 1e9
        node_s += 0.5 * (int(serving(a).sum()) + int(serving(b).sum())) * dt
    return sent, node_s


# -- the routing tables, by each slot's CURRENT key --------------------------

def routing_table_faults(T: dict, ids: list, bits: int, nbuckets: int,
                         alive: np.ndarray, t_born: np.ndarray) -> dict:
    """Every alive holder's buckets and sibling row against the XOR
    metric of the keys the slots hold NOW.

    A fault (``bucket_misplaced``, ``sibling_disorder``): the holder
    itself, a slot out of range, a slot twice in one bucket or row, an
    entry in another bucket than its slot's current key earns, a sibling
    row not nearest-first, any entry held by a dead slot.

    No fault, but counted (``bucket_dead_share``): an entry that points
    at a dead slot, and one that points at a slot REBORN since: born
    after the entry was last verified, or, where it now stands in a
    wrong bucket or out of order, born after anything the holder has
    seen (its greatest last-seen time: the holder steps only when
    something wakes it, and puts its tables right by the keys of that
    tick; what was born later it cannot know yet).  An entry in a wrong
    bucket whose slot was born BEFORE the holder last heard anyone is a
    fault: the holder has stepped since and kept it."""
    buckets, sib, b_seen = T["buckets"], T["sib"], T["b_seen"]
    n = len(ids)
    heard = b_seen.reshape(n, -1).max(axis=1)        # 0: never
    misplaced = entries = dead_e = reborn_e = 0
    disorder = sib_entries = sib_dead = sib_reborn = 0
    for i in range(n):
        rows = buckets[i]
        held_any = (rows != NO_NODE).any() or (sib[i] != NO_NODE).any()
        if not held_any:
            continue
        if not alive[i]:
            misplaced += int((rows != NO_NODE).sum())
            disorder += int((sib[i] != NO_NODE).sum())
            continue
        since = int(heard[i])
        for b in range(rows.shape[0]):
            held = [(int(e), int(b_seen[i, b, k]))
                    for k, e in enumerate(rows[b]) if e != NO_NODE]
            entries += len(held)
            misplaced += len(held) - len({e for e, _ in held})
            for e, seen in held:
                if e == i or not 0 <= e < n:
                    misplaced += 1
                elif not alive[e]:
                    dead_e += 1
                else:
                    want = min(shared_prefix_length(ids[i], ids[e], bits),
                               nbuckets - 1)
                    born = int(t_born[e])
                    if want != b:
                        if born > since:
                            reborn_e += 1
                        else:
                            misplaced += 1
                    elif 0 < seen < born:
                        reborn_e += 1
        held = [int(e) for e in sib[i] if e != NO_NODE]
        sib_entries += len(held)
        bad = len(held) - len(set(held))
        bad += sum(1 for e in held if e == i or not 0 <= e < n)
        if not bad:
            # (a slot reborn since may have died again: its key moved
            # all the same, and the holder cannot know)
            later = [e for e in held if int(t_born[e]) > since]
            sib_reborn += len(later)
            sib_dead += sum(1 for e in held
                            if not alive[e] and e not in later)
            d = [ids[e] ^ ids[i] for e in held if e not in later]
            bad += sum(1 for x, y in zip(d, d[1:]) if not x < y)
        disorder += bad
    return {"bucket_misplaced": misplaced, "bucket_entries": entries,
            "bucket_dead_entries": dead_e, "bucket_reborn_entries": reborn_e,
            "bucket_dead_share": (dead_e + reborn_e) / max(entries, 1),
            "sibling_disorder": disorder, "sibling_entries": sib_entries,
            "sibling_dead_share": (sib_dead + sib_reborn)
            / max(sib_entries, 1)}


# -- answers and delays, by the read-back that saw them -----------------------

class Keys:
    """The slots' keys as Python integers, brought up to a view by the
    slots whose incarnation changed."""

    def __init__(self, view: dict):
        self.ids = keys_to_int(view["node_keys"])
        self.t_born = view["t_born"].copy()

    def at(self, view: dict) -> list:
        moved = np.nonzero(view["t_born"] != self.t_born)[0]
        if len(moved):
            fresh = keys_to_int(view["node_keys"][moved])
            for slot, key in zip(moved, fresh):
                self.ids[int(slot)] = key
            self.t_born = view["t_born"].copy()
        return self.ids


def payload_answers(snaps: list, kind_oneway: int, rng, cap: int, s: int,
                    far: int) -> dict:
    """One-way test payloads seen in flight at the dispatch ends, each
    ranked among the nodes alive AT THAT read-back under the keys they
    had THEN: the share addressed outside the ``far`` nodes XOR-closest
    to the payload's key, outside the ``s`` closest, and to another than
    the very closest; the share addressed to a slot dead by then."""
    seen = []
    for at, snap in enumerate(snaps):
        rows = np.nonzero(snap["valid"] & (snap["kind"] == kind_oneway))[0]
        seen += [(at, int(snap["dst"][r]), snap["key"][r]) for r in rows]
    total = len(seen)
    if total > cap:
        pick = np.sort(rng.choice(total, size=cap, replace=False))
        seen = [seen[i] for i in pick]
    ranks, to_dead = [], 0
    keys = Keys(snaps[0]["churn"]) if snaps else None
    for at, dst, lanes in seen:          # in the order of the read-backs
        view = snaps[at]["churn"]
        ids = keys.at(view)
        alive = view["alive"]
        key = keys_to_int(lanes[None, :])[0]
        if not 0 <= dst < len(ids):
            ranks.append(len(ids))
            continue
        to_dead += not alive[dst]
        d_dst = ids[dst] ^ key
        ranks.append(sum(1 for i in np.nonzero(alive)[0]
                         if ids[int(i)] ^ key < d_dst))
    r = np.asarray(ranks, dtype=np.int64)
    m = len(seen)

    def share(hit):
        return float(hit.sum()) / m if m else None

    return {"payloads_seen": total, "payloads_checked": m,
            "payload_far_share": share(r >= far),
            "payload_outside_share": share(r >= s),
            "payload_not_nearest_share": share(r >= 1),
            "payload_dead_dst_share": to_dead / m if m else None,
            "payload_rank_median": float(np.median(r)) if m else None,
            "payload_rank_max": int(r.max()) if m else None}


def flights_of(snap: dict, kind_call: int) -> list:
    """The FindNode calls in flight at this read-back between two nodes
    that were both born before the call was sent (the coordinates of the
    read-back are then the ones the delay was computed from)."""
    t_born = snap["churn"]["t_born"]
    return [f for f in base.rpc_flights(snap, kind_call)
            if t_born[f[0]] <= f[3] and t_born[f[1]] <= f[3]]


def delay_readings(snaps: list, kind_call: int, ul: dict) -> dict:
    early, late, count = -math.inf, -math.inf, 0
    for snap in snaps:
        got = base.delay_readings(flights_of(snap, kind_call),
                                  snap["churn"]["coords"], ul)
        if got["rpc_flights"]:
            count += got["rpc_flights"]
            early = max(early, got["delay_early_ns"])
            late = max(late, got["delay_late_share"])
    if not count:
        return {"rpc_flights": 0}
    return {"rpc_flights": count, "delay_early_ns": early,
            "delay_late_share": late}


# -- all readings ---------------------------------------------------------------

def readings(O: dict, C: dict, T: dict, snaps: list, *, config: dict,
             wire: dict, interval_ns: int, ticks_per_dispatch: int,
             dispatches: int, seed: int) -> dict:
    """Every number the verdict rests on, from what the window left."""
    rng = np.random.default_rng(int(seed))
    law = config["churn"]
    window_ns = int(round(config["engine"]["window"] * 1e9))
    bits = int(wire["key_bits"])
    kad = config["kademlia"]
    views = timeline(O, snaps)
    first, last = views[0], views[-1]
    out = {}

    # the read-backs are close enough to follow every node (module doc)
    apart = max((b["t_now_ns"] - a["t_now_ns"]
                 for a, b in zip(views, views[1:])), default=0)
    out["readbacks_apart_s"] = apart / 1e9
    out["readbacks_too_far_apart"] = int(
        apart >= float(law["graceful_leave_delay_s"]) * 1e9)
    out["close_is_last_readback"] = int(
        C["t_now_ns"] == last["t_now_ns"]
        and np.array_equal(C["churn"]["t_born"], last["t_born"])
        and np.array_equal(C["churn"]["alive"], last["alive"]))

    # population and law
    out.update(population(views, law))
    out["dead_slots_busy"] = dead_slots_busy(views, snaps, T)
    out["churn_ticks"] = last["churn_ticks"] - first["churn_ticks"]
    out["reset_rows"] = last["reset_rows"] - first["reset_rows"]

    # the engine: only a receiver dead at delivery loses a message
    out["messages_lost"] = int(sum(C["engine"][k] for k in LOST_OTHERWISE))
    out["dest_unavailable_lost"] = int(C["engine"]["dest_unavailable_lost"]
                                       - O["engine"]["dest_unavailable_lost"])
    out["inbox_deferred_peak"] = int(C["engine"]["inbox_deferred"])
    out["tick_count_gap"] = abs((C["tick"] - O["tick"])
                                - dispatches * ticks_per_dispatch)
    out["sim_ns_advanced"] = C["t_now_ns"] - O["t_now_ns"]
    if snaps:
        end = snaps[-1]
        n = len(last["alive"])
        overdue = int((end["t_deliver"] < end["t_now_ns"] - window_ns).sum())
        out["pool_overdue_excess"] = max(
            0, overdue - out["inbox_deferred_peak"])
        out["pool_bad_dst"] = int(sum(
            ((s["dst"] < 0) | (s["dst"] >= n)).sum() for s in snaps))
        out["pool_messages"] = int(len(end["dst"]))
        out["pool_dead_dst_share"] = float(
            (~last["alive"][np.clip(end["dst"], 0, n - 1)]).sum()
            / max(len(end["dst"]), 1))

    # KBRTestApp's accounting, recounted per incarnation
    st_o, st_c = O["stats"], C["stats"]
    d = lambda k: int(st_c["c:" + k]) - int(st_o["c:" + k])  # noqa: E731
    sent, delivered = d("kbr_sent"), d("kbr_delivered")
    ended = delivered + d("kbr_lookup_failed") + d("kbr_wrong_node")
    recount, node_s = sends(views)
    out["sent_recount_gap"] = abs(recount - sent)
    hist = (st_c["h:kbr_hop_hist"].astype(np.int64)
            - st_o["h:kbr_hop_hist"].astype(np.int64))
    hop = st_c["s:kbr_hopcount"] - st_o["s:kbr_hopcount"]
    out["hist_recount_gap"] = (abs(int(hist.sum()) - delivered)
                               + abs(int(round(float(hop[0]))) - delivered))
    if hist[-1] == 0:
        out["hist_recount_gap"] += abs(
            int((hist * np.arange(len(hist))).sum())
            - int(round(float(hop[1]))))
    out["lookups_sent"] = sent
    out["lookups_ended"] = ended
    out["lookups_delivered"] = delivered
    out["lookups_failed"] = d("kbr_lookup_failed")
    out["lookups_wrong_node"] = d("kbr_wrong_node")
    out["delivery_share"] = delivered / max(sent, ended, 1)
    out["lookup_failed_share_window"] = (ended - delivered) / max(ended, 1)
    # the share the verdict holds: over the window's first
    # ``failures_over_sim_s`` simulated seconds (the result line's
    # stretch), not over however far this tree's window reaches; under
    # churn the share grows as dead entries gather, so a faster tree
    # would read another number off the same run
    over_ns = int(round(float(config["failures_over_sim_s"]) * 1e9))
    at = next((s["stats"] for s in snaps
               if s["t_now_ns"] - O["t_now_ns"] >= over_ns), st_c)
    # (a window that never gets that far is read up to its close, as
    # the result line's two integers are)
    ds = lambda k: int(at["c:" + k]) - int(st_o["c:" + k])  # noqa: E731
    done = ds("kbr_delivered")
    over = done + ds("kbr_lookup_failed") + ds("kbr_wrong_node")
    out["lookup_failed_share"] = (over - done) / max(over, 1)

    # time, per incarnation: the nodes that served from the opening to
    # the close as ONE node hold the timer lattice to the nanosecond
    whole = whole_window(views)
    out["nodes_whole_window"] = int(whole.sum())
    dseq = (last["seq"].astype(np.int64) - first["seq"].astype(np.int64))
    dt = last["t_test"].astype(np.int64) - first["t_test"].astype(np.int64)
    out["timer_off_lattice"] = int((whole & (dt != dseq * interval_ns)).sum())
    due = last["t_test"].astype(np.int64)
    out["timers_overdue"] = int((serving(last)
                                 & (due < last["t_now_ns"] - window_ns)).sum())
    out["timers_early"] = int((whole & (dseq > 0)
                               & (due - interval_ns
                                  >= last["t_now_ns"] + window_ns)).sum())
    # the arrival law over the serving node-seconds: one test an
    # interval a node, each node's phase uniform; the spread is the
    # whole-window nodes' Binomial and a quarter of a test for every
    # node that came or went
    q = out["sim_ns_advanced"] / interval_ns
    p = q - math.floor(q)
    want = node_s * 1e9 / interval_ns
    spread = math.sqrt(max(out["nodes_whole_window"] * p * (1.0 - p)
                           + (out["births"] + out["kills"]) / 4.0, 1.0))
    out["sent_expected"] = want
    out["sent_off_law"] = abs(sent - want) / spread

    # joining: alive, under no notice, older than the stated join time,
    # and still not joined (the most at any read-back)
    join_ns = int(round(float(law["join_within_s"]) * 1e9))
    out["joining_late"] = max(int((
        v["alive"] & ~v["ready"] & ~(v["t_dead"] < T_INF)
        & (v["t_now_ns"] - v["t_born"] > join_ns)).sum()) for v in views)
    out["not_ready_at_close"] = int((last["alive"] & ~last["ready"]).sum())

    # Kademlia's routing tables by each slot's current key
    ids = keys_to_int(last["node_keys"])
    out.update(routing_table_faults(T, ids, bits, int(kad["buckets"]),
                                    last["alive"], last["t_born"]))

    # the lookups' answers and the underlay's delays
    out.update(payload_answers(
        snaps, wire["APP_ONEWAY"], rng, cap=300, s=int(kad["s"]),
        far=max(len(last["alive"]) // 32, 1)))
    out.update(delay_readings(snaps, wire["FINDNODE_CALL"],
                              config["underlay"]))
    return out


# -- the control ----------------------------------------------------------------

def control(O: dict, C: dict, T: dict, snaps: list, *, config: dict,
            wire: dict, interval_ns: int):
    """The reference in the program's place, one precision down
    (``kademlia_kbr.control``'s two steps, per incarnation and per
    read-back): the test timers of the nodes that served the whole
    window summed in float32 seconds; each call in flight given the
    least delay that bfloat16 coordinates of ITS read-back compute, with
    the jitter it really had.  Returns ``(C', snaps')``."""
    views = timeline(O, snaps)
    first, last = views[0], views[-1]
    whole = whole_window(views)
    dseq = np.where(whole, last["seq"].astype(np.int64)
                    - first["seq"].astype(np.int64), 0)
    t = (np.where(whole, first["t_test"], 0).astype(np.float64)
         / 1e9).astype(np.float32)
    step = np.float32(interval_ns / 1e9)
    for _ in range(int(dseq.max(initial=0))):
        t = np.where(dseq > 0, t + step, t).astype(np.float32)
        dseq = dseq - (dseq > 0)
    low_t = np.where(whole,
                     np.round(t.astype(np.float64) * 1e9).astype(np.int64),
                     last["t_test"])

    ul = config["underlay"]
    kind = wire["FINDNODE_CALL"]
    snaps2 = []
    for snap in snaps:
        out = dict(snap)
        coords = snap["churn"]["coords"]
        low = base._bf16(coords)
        t_del = snap["t_deliver"].copy()
        for src, dst, size_b, t_sent, t_deliver in flights_of(snap, kind):
            full = base.least_delay_ns(coords, src, dst, size_b, ul)
            jitter = (t_deliver - t_sent) / full
            lowd = base.least_delay_ns(low, src, dst, size_b, ul)
            rows = np.nonzero((snap["src"] == src) & (snap["dst"] == dst)
                              & (snap["kind"] == kind)
                              & (snap["t_deliver"] == t_deliver))[0]
            t_del[rows] = t_sent + int(lowd * jitter)
        out["t_deliver"] = t_del
        snaps2.append(out)
    if snaps2:
        snaps2[-1] = dict(snaps2[-1], churn=dict(snaps2[-1]["churn"],
                                                 t_test=low_t))
    c2 = dict(C, churn=dict(C["churn"], t_test=low_t))
    return c2, snaps2
