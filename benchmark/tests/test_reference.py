"""The plain reference on a hand-made network of 8 nodes with 8-bit
keys: every fault it is there to catch, one at a time."""

import copy

import numpy as np
import pytest

from reference import kademlia_kbr as ref

BITS, NB, S = 8, 4, 2
IDS = [0b00000001, 0b00000110, 0b00011000, 0b00100000,
       0b01000000, 0b01100011, 0b10000000, 0b11110000]
N = len(IDS)
CFG = {"engine": {"window": 0.2},
       "kademlia": {"k": 2, "s": S, "buckets": NB},
       "underlay": {"header_bytes": 28, "bandwidth_bit_s": 10e6,
                    "access_delay_s": 0.0, "coord_delay_s_per_unit": 0.001}}
WIRE = {"APP_ONEWAY": 30, "FINDNODE_CALL": 1, "key_bits": BITS}
INTERVAL = 60 * 10**9


def lanes(v):
    return np.array([v], dtype=np.uint32)


def tables():
    """Routing tables filled by the plain rule itself."""
    buckets = np.full((N, NB, 2), -1, np.int32)
    sib = np.full((N, S), -1, np.int32)
    for i in range(N):
        order = sorted((j for j in range(N) if j != i),
                       key=lambda j: IDS[i] ^ IDS[j])
        sib[i] = order[:S]
        fill = {}
        for j in order:
            b = min(ref.shared_prefix_length(IDS[i], IDS[j], BITS), NB - 1)
            if fill.get(b, 0) < 2:
                buckets[i, b, fill.get(b, 0)] = j
                fill[b] = fill.get(b, 0) + 1
    rng = np.random.default_rng(0)
    return {"node_keys": np.array([[v] for v in IDS], np.uint32),
            "alive": np.ones(N, bool), "ready": np.ones(N, bool),
            "coords": rng.uniform(0, 150, (N, 2)).astype(np.float32),
            "sib": sib, "buckets": buckets}


def stats(sent, delivered, hist):
    hist = np.array(hist, np.int64)
    hops = float((hist * np.arange(len(hist))).sum())
    return {"c:kbr_sent": sent, "c:kbr_delivered": delivered,
            "c:kbr_lookup_failed": 0, "c:kbr_wrong_node": 0,
            "h:kbr_hop_hist": hist,
            "s:kbr_hopcount": np.array([delivered, hops, 0, 0, 0], float)}


def window(T):
    """Opening, close and two dispatch ends of a sound window."""
    t_o, t_c = 40 * 10**9, 40 * 10**9 + 3_200_000_000
    seq_o = np.zeros(N, np.int32)
    seq_c = np.array([1, 1, 1, 0, 0, 0, 0, 0], np.int32)   # those due by t_c
    t_test_o = t_o + np.arange(1, N + 1, dtype=np.int64) * 1_000_000_007 + 123_456_789
    eng = {k: 0 for k in ("pool_overflow", "outbox_overflow", "queue_lost",
                          "bit_error_lost", "dest_unavailable_lost",
                          "partition_lost", "inbox_deferred")}
    O = {"t_now_ns": t_o, "tick": 200, "stats": stats(0, 0, [0] * 6),
         "engine": dict(eng), "t_test": t_test_o, "seq": seq_o}
    C = {"t_now_ns": t_c, "tick": 216, "stats": stats(3, 3, [0, 1, 2, 0, 0, 0]),
         "engine": dict(eng), "seq": seq_c,
         "t_test": t_test_o + seq_c.astype(np.int64) * INTERVAL}
    # one payload on its way to the owner of its key, one FindNode call
    src, dst = 0, 5
    least = float(ref.least_delay_ns(T["coords"], src, dst, 40,
                                     CFG["underlay"]))
    t_sent = t_c - 30_000_000
    rpc_dst = np.full((N, 1, 1), -1, np.int32)
    rpc_dst[src, 0, 0] = dst
    rpc_sent = np.zeros((N, 1, 1), np.int64)
    rpc_sent[src, 0, 0] = t_sent
    active = np.zeros((N, 1), bool)
    active[src, 0] = True
    snap = {"valid": np.ones(2, bool),
            "t_deliver": np.array([t_c + 10**7, t_sent + int(least * 1.05)]),
            "src": np.array([2, src]), "dst": np.array([7, dst]),
            "kind": np.array([30, 1]), "c": np.array([1, 0]),
            "size_b": np.array([100, 40]),
            "key": np.array([lanes(IDS[7]), lanes(IDS[3])]),
            "t_now_ns": t_c, "rpc_dst": rpc_dst, "rpc_t_sent": rpc_sent,
            "rpc_active": active}
    return O, C, [copy.deepcopy(snap), snap]


LIMITS = {"alive_missing": ("max", 0), "messages_lost": ("max", 0),
          "tick_count_gap": ("max", 0), "sim_ns_advanced": ("min", 1),
          "pool_overdue_excess": ("max", 0), "pool_bad_dst": ("max", 0),
          "sent_recount_gap": ("max", 0), "hist_recount_gap": ("max", 0),
          "timer_off_lattice": ("max", 0), "not_ready": ("max", 0),
          "timers_overdue": ("max", 0), "timers_early": ("max", 0),
          "sent_off_binomial": ("max", 6.0), "bucket_misplaced": ("max", 0),
          "sibling_disorder": ("max", 0), "payload_far_share": ("max", 0.1),
          "delay_early_ns": ("max", 1000.0),
          "lookup_failed_share": ("max", 0.01)}


def verdict(O, C, T, snaps):
    r = ref.readings(O, C, T, snaps, config=CFG, wire=WIRE,
                     interval_ns=INTERVAL, ticks_per_dispatch=8,
                     dispatches=len(snaps), seed=1)
    rows = ref.compare(r, LIMITS)
    return r, {row[0] for row in rows if not row[4]}


def test_keys_are_whole_integers_most_significant_lane_first():
    k = np.array([[1, 2, 3, 4, 5]], np.uint32)
    assert ref.keys_to_int(k) == [(1 << 128) | (2 << 96) | (3 << 64)
                                  | (4 << 32) | 5]
    assert ref.shared_prefix_length(0b1010, 0b1000, 4) == 2
    assert ref.shared_prefix_length(7, 7, 160) == 160


def test_a_sound_window_passes_every_limit():
    T = tables()
    r, bad = verdict(*window(T)[:2], T, window(T)[2])
    assert bad == set(), (bad, r)
    assert r["sibling_recall"] == 1.0 and r["payloads_seen"] == 2
    assert r["payload_not_nearest_share"] == 0.0
    assert r["rpc_flights"] == 2 and r["delay_early_ns"] < 0


@pytest.mark.parametrize("fault,expect", [
    ("bucket", "bucket_misplaced"), ("self", "bucket_misplaced"),
    ("twice", "bucket_misplaced"), ("sibling", "sibling_disorder"),
    ("timer", "timer_off_lattice"), ("sent", "sent_recount_gap"),
    ("hist", "hist_recount_gap"), ("lost", "messages_lost"),
    ("dead", "alive_missing"), ("ticks", "tick_count_gap"),
    ("frozen", "sim_ns_advanced"), ("payload", "payload_far_share"),
    ("early", "delay_early_ns"), ("overdue", "pool_overdue_excess"),
    ("delivery", "lookup_failed_share"), ("skipped", "timers_overdue"),
    ("fired_early", "timers_early"), ("rate", "sent_off_binomial"),
    ("unjoined", "not_ready")])
def test_each_fault_fails_the_number_that_is_there_to_catch_it(fault, expect):
    T = tables()
    O, C, snaps = window(T)
    if fault == "bucket":       # an entry moved to the neighbouring bucket
        b = int(np.argwhere(T["buckets"][0, :, 0] >= 0)[0, 0])
        T["buckets"][0, (b + 1) % NB, 1] = T["buckets"][0, b, 0]
        T["buckets"][0, b, 0] = -1
    elif fault == "self":
        T["buckets"][3, 0, 1] = 3
    elif fault == "twice":
        b = int(np.argwhere(T["buckets"][0, :, 0] >= 0)[0, 0])
        T["buckets"][0, b, 1] = T["buckets"][0, b, 0]
    elif fault == "sibling":
        T["sib"][4] = T["sib"][4][::-1]
    elif fault == "timer":
        C["t_test"][0] += 1     # one nanosecond off
    elif fault == "sent":
        C["stats"]["c:kbr_sent"] += 1
    elif fault == "hist":
        C["stats"]["h:kbr_hop_hist"][3] += 1
    elif fault == "lost":
        C["engine"]["pool_overflow"] = 1
    elif fault == "dead":
        T["alive"][6] = False
    elif fault == "ticks":
        C["tick"] -= 8          # a dispatch that did not run
    elif fault == "frozen":
        C["t_now_ns"] = O["t_now_ns"]
    elif fault == "payload":    # the payload sent to the farthest node
        for s in snaps:
            s["dst"][0] = 0
    elif fault == "early":      # delivered before light could get there
        for s in snaps:
            s["t_deliver"][1] -= int(0.06 * (s["t_deliver"][1]
                                             - s["rpc_t_sent"][0, 0, 0]))
    elif fault == "overdue":
        snaps[-1]["t_deliver"][0] = C["t_now_ns"] - 10**9
    elif fault == "delivery":
        C["stats"]["c:kbr_delivered"] = 1     # two of three lookups fail
        C["stats"]["c:kbr_lookup_failed"] = 2
        C["stats"]["h:kbr_hop_hist"][:] = [0, 1, 0, 0, 0, 0]
        C["stats"]["s:kbr_hopcount"][:2] = [1, 1]
    elif fault == "skipped":    # a tick that passes a node over: its test
        C["seq"][1] = 0         # stays due, and every count agrees
        C["t_test"][1] = O["t_test"][1]
        C["stats"] = stats(2, 2, [0, 1, 1, 0, 0, 0])
    elif fault == "fired_early":    # a test sent 2.9 s before its time
        C["seq"][5] = 1
        C["t_test"][5] += INTERVAL
        C["stats"] = stats(4, 4, [0, 2, 2, 0, 0, 0])
    elif fault == "rate":       # every node sends in 3.2 s of a 60 s law
        C["seq"][:] = 1
        C["t_test"] = O["t_test"] + INTERVAL
        C["stats"] = stats(8, 8, [0, 4, 4, 0, 0, 0])
    elif fault == "unjoined":
        T["ready"][2] = False
    r, bad = verdict(O, C, T, snaps)
    assert expect in bad, (bad, r)
    if fault == "skipped":      # nothing else sees it
        assert bad == {"timers_overdue"}, bad


def test_the_control_one_precision_down_is_not_correct():
    T = tables()
    O, C, snaps = window(T)
    C2, snaps2 = ref.control(O, C, T, snaps, config=CFG, wire=WIRE,
                             interval_ns=INTERVAL)
    r, bad = verdict(O, C2, T, snaps2)
    assert "timer_off_lattice" in bad, r
    # float32 seconds cannot hold a nanosecond at 40 s: every timer that
    # fired is off the lattice, and those that did not are rounded too
    assert r["timer_off_lattice"] >= int((C["seq"] > 0).sum())


def test_bfloat16_coordinates_move_a_least_delay_by_microseconds():
    T = tables()
    low = ref._bf16(T["coords"])
    assert np.abs(low - T["coords"]).max() > 0.01
    a = ref.least_delay_ns(T["coords"], [0, 1, 2], [5, 6, 7], [40] * 3,
                           CFG["underlay"])
    b = ref.least_delay_ns(low, [0, 1, 2], [5, 6, 7], [40] * 3,
                           CFG["underlay"])
    assert np.abs(a - b).max() > 1000.0
