"""The cell whose payloads hop node to node: found through new files and
entries only; ``program_bamboo`` through the harness at N = 128 on the
CPU, sound and one precision down; each fault planted in a sound
window's evidence against the number that must catch it (a table entry
one column off, a leaf half out of order, a routed message whose next
hop is in its visited list, one that makes no progress, a payload
delivered beside the owner, a forward the ACK recount misses, payloads
dropped on the way and counted as dropped, an upkeep timer that skips
rounds); the ini texts of ``chord1000`` and
``bamboo1000`` differing by ``overlay`` lines alone; ``SURFACE`` by
name; the two metrics on made-up counters.  Some five minutes (one
compile of the cell's tick, at R = 2); nothing here is a device
number.
"""

import copy
import json
import os
import time
import types

import numpy as np
import pytest

import cells
import cellrun
import sweep
from conftest import BENCH, HERE

# a mix that is no cell's and ships as no traffic file (testMsgInterval
# 10 s, from no source): enough payloads in flight at this size
TRAFFIC = os.path.join(HERE, "data", "kbr10.json")
N = 128
CELL = "bamboo1000.kbr60"
NO_NODE = -1


def bamboo_cell():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, CELL)
    with open(TRAFFIC) as f:
        cell["traffic"] = json.load(f)
    cell["config"] = copy.deepcopy(cell["config"])
    cell["config"]["failures_over_sim_s"] = 16.0
    # the handler is unrolled over the inbox slots and XLA-CPU compiles
    # the cell's own R = 8 in a quarter of an hour; at N = 128 a window
    # hardly ever holds a third message for one node, and a message past
    # R waits a tick and is not lost
    cell["config"]["engine"]["inbox_slots"] = 2
    return bench, cell


def window(cell, prog, seed=7, seconds=6.0):
    return cellrun.run_cell(prog, cell, seed, seconds,
                            t_proc=time.perf_counter(), say=lambda m: None)


def judged(cell, rec, evidence, seed=7):
    readings, rows = cellrun.judge(
        cell, evidence, cellrun.interval_ns_of(cell["traffic"]),
        rec["dispatches"], seed, rec["programs"])
    return readings, [r[0] for r in rows if not r[4]]


def with_tables(rec, **changed):
    ev = rec["evidence"]
    return dict(ev, tables=dict(ev["tables"], **changed))


def with_route(ev, pick, **changed):
    """The evidence with ONE routed message (the first that ``pick``
    takes, as ``(snap, row)``) changed."""
    kind = ev["wire"]["KBR_ROUTE"]
    for at, snap in enumerate(ev["snaps"]):
        for row in np.nonzero(snap["valid"] & (snap["kind"] == kind))[0]:
            if not pick(snap, int(row)):
                continue
            snap = dict(snap)
            for name, value in changed.items():
                col = snap[name].copy()
                col[row] = value(snap, int(row)) if callable(value) else value
                snap[name] = col
            return dict(ev, snaps=ev["snaps"][:at] + [snap]
                        + ev["snaps"][at + 1:])
    raise AssertionError("no routed message to plant the fault in")


@pytest.fixture(scope="module")
def sound():
    bench, cell = bamboo_cell()
    program = cells.load_program(cell["config"])
    prog = program.Program(cell["config"], cell["traffic"], 1, n=N,
                           persistent_cache=False)
    return bench, cell, prog, window(cell, prog)


# -- found by name, through new files and entries only ------------------------

def test_the_cell_is_found_through_new_files_and_entries():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, CELL)
    config = cell["config"]
    assert cell["chips"] == config["chips"] == 1
    assert any("bamboo.BambooModules" in ln for ln in config["ini"])
    assert config["program"] == "program_bamboo"
    assert config["reference"] == "bamboo_kbr"
    assert cells.load_program(config).__name__.endswith("program_bamboo")
    for fn in ("readings", "compare", "control"):
        assert callable(getattr(cell["reference"], fn))
    for zero in ("leaf_wrong", "leaf_disorder", "rt_misplaced",
                 "payload_not_owner", "lookups_wrong_node", "route_loops",
                 "route_no_progress", "route_recount_gap",
                 "upkeep_timers_overdue", "messages_lost", "not_ready"):
        assert config["limits"][zero] == ["max", 0]
    # a drop on the way is limited by the routed path's own counters
    assert 0 < config["limits"]["route_dropped_share"][1] < 0.1
    # the file, not the code, states the deployment
    law = config["bamboo"]
    for key, ini in (("leafset_interval_s", "leafsetMaintenanceInterval"),
                     ("local_tuning_interval_s", "localTuningInterval"),
                     ("global_tuning_interval_s", "globalTuningInterval"),
                     ("join_timeout_s", "joinTimeout")):
        assert "**.overlay.bamboo.%s = %gs" % (ini, law[key]) in config["ini"]
    for key, ini in (("bits_per_digit", "bitsPerDigit"),
                     ("num_leaves", "numberOfLeaves"),
                     ("rec_redundant", "recNumRedundantNodes")):
        assert "**.overlay.bamboo.%s = %d" % (ini, law[key]) in config["ini"]
    assert "**.overlay.bamboo.routeMsgAcks = true" in config["ini"]
    assert ('**.overlay.bamboo.routingType = "semi-recursive"'
            in config["ini"])
    assert config["reduced"] == ["kbrRpcTest", "kbrLookupTest"]
    entry = next(c for c in bench["configs"] if c["name"] == "bamboo1000")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # the three overlays stand at one N, one fill, one mix: the ini
    # texts differ by ``overlay`` lines alone
    for other in ("chord1000.kbr60", "kademlia1000.kbr60"):
        plain = cells.find_cell(bench, other)["config"]
        for key in ("underlay", "fill_s", "settle_s", "nodes", "precisions"):
            assert config[key] == plain[key], (other, key)
        assert config["engine"]["window"] == plain["engine"]["window"]
        differ = set(config["ini"]) ^ set(plain["ini"])
        assert differ and all("overlay" in ln for ln in differ), differ
    per_layer = [m["name"] for m, _ in cells.metrics_for(
        bench, CELL, "per_layer")]
    mine = ["route_hops_per_delivery", "bamboo_upkeep_call_share"]
    assert per_layer[-2:] == mine
    assert "maintenance_call_share" not in per_layer
    had = [m["name"] for m in bench["per_layer"] if "workloads" not in m]
    assert set(had) <= set(per_layer)
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert not set(mine) & {m["name"] for m, _ in cells.metrics_for(
                bench, w["name"], "per_layer")}
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert [m["name"] for m in bench["per_layer"]][-2:] == mine
    # the harness's own files name nothing the cell brought
    brought = (config["program"], config["reference"], config["name"]) \
        + tuple(mine)
    for f in ("run.py", "cellrun.py", "cells.py", "window.py",
              "trace_reduce.py", "program.py", "program_mesh.py",
              "program_churn.py", "program_chord.py", "sweep.py",
              "phases.py", "phase_reduce.py"):
        text = open(os.path.join(BENCH, f)).read()
        assert not [b for b in brought if b in text], f


def test_the_reference_imports_nothing_of_the_program():
    for name in ("bamboo_kbr.py", "kademlia_kbr.py"):
        text = open(os.path.join(BENCH, "reference", name)).read()
        assert "oversim_tpu" not in text and "import jax" not in text, name


def test_the_program_file_reads_every_leaf_by_name(sound):
    _, cell, prog, _ = sound
    program = cells.load_program(cell["config"])
    s = prog.init(3)
    prog.check_surface(s)
    for path in ("logic.leaf_cw", "logic.leaf_ccw", "logic.rt",
                 "logic.t_ls", "logic.t_lt", "logic.t_gt",
                 "logic.rr.active", "stats.c:bamboo_ls_rounds",
                 "stats.c:route_forwarded", "stats.c:route_acked"):
        assert path in program.SURFACE
    assert "logic.buckets" not in program.SURFACE
    with pytest.raises(program.SurfaceError, match="leaf_gone"):
        program.leaf(s, "logic.leaf_gone")
    broken = types.SimpleNamespace(stats={
        k: v for k, v in s.stats.items() if k != "c:route_acked"})
    with pytest.raises(program.SurfaceError, match="route_acked"):
        program.leaf(broken, "stats.c:route_acked")


def test_a_tree_without_the_counters_fails_by_name(monkeypatch):
    """What the parent of PR 45 is: it fails before a state is built."""
    _, cell = bamboo_cell()
    program = cells.load_program(cell["config"])
    from oversim_tpu import stats as stats_mod
    from oversim_tpu.overlay import pastry
    monkeypatch.setattr(
        pastry.PastryLogic, "stat_spec", lambda self: stats_mod.StatSpec(
            scalars=(), hists=(), counters=("pastry_joins",
                                            "route_dropped")))
    with pytest.raises(program.SurfaceError, match="bamboo_ls_rounds"):
        program.check_program()


# -- through the harness at N = 128 -----------------------------------------------

def test_a_sound_window_is_correct_and_one_ring(sound):
    _, _, prog, rec = sound
    assert rec["correct"], [r for r in rec["rows"] if not r[4]]
    r = rec["readings"]
    assert r["leaf_wrong"] == r["leaf_disorder"] == 0
    assert r["leaf_cycles"] == 1 and r["leaf_entries"] == 8 * N
    assert r["rt_entries"] > 10 * N and r["rt_misplaced"] == 0
    assert r["routes_seen"] >= r["payloads_seen"] > 0
    assert r["payload_not_owner"] == r["route_loops"] == 0
    assert r["route_no_progress"] == r["route_recount_gap"] == 0
    assert r["route_forwarded"] > r["route_delivered"] > 0
    assert r["hops_off_greedy"] is not None
    assert r["leafset_rounds"] > 0 and r["local_tuning_rounds"] > 0
    assert r["lookups_delivered"] > 0 and r["messages_lost"] == 0
    assert prog.tick_programs() >= 1 and r["tick_programs_extra"] == 0
    assert prog.sim.tick_impl == "sparse"


def test_one_precision_down_is_not_correct(sound):
    _, cell, _, rec = sound
    _, rows = sweep.control_of(rec, cell, 7)
    bad = [r[0] for r in rows if not r[4]]
    assert "timer_off_lattice" in bad, bad


# -- each planted fault against the number that must catch it ------------------

def test_an_entry_one_column_off_is_caught(sound):
    _, cell, _, rec = sound
    rt = rec["evidence"]["tables"]["rt"].copy()
    i, r, c = map(int, np.argwhere(rt != NO_NODE)[5])
    rt[i, r, (c + 1) % rt.shape[2]], rt[i, r, c] = rt[i, r, c], NO_NODE
    got, bad = judged(cell, rec, with_tables(rec, rt=rt))
    assert got["rt_misplaced"] == 1 and bad == ["rt_misplaced"]
    # the holder itself, in the right row
    rt = rec["evidence"]["tables"]["rt"].copy()
    rt[i, r, c] = i
    assert judged(cell, rec, with_tables(rec, rt=rt))[0]["rt_misplaced"] == 1


def test_a_leaf_half_out_of_order_is_caught(sound):
    _, cell, _, rec = sound
    T = rec["evidence"]["tables"]
    cw = T["leaf_cw"].copy()
    cw[5, [1, 2]] = cw[5, [2, 1]]
    got, bad = judged(cell, rec, with_tables(rec, leaf_cw=cw))
    assert got["leaf_wrong"] == 2 and got["leaf_disorder"] == 1
    assert set(bad) == {"leaf_wrong", "leaf_disorder"}
    # a first leaf that skips a node: two cycles where there was one
    ccw = T["leaf_ccw"].copy()
    ccw[9, 0] = ccw[9, 1]
    got, bad = judged(cell, rec, with_tables(rec, leaf_ccw=ccw))
    assert got["leaf_wrong"] >= 1 and "leaf_wrong" in bad
    cw = T["leaf_cw"].copy()
    cw[:, 0] = cw[:, 1]
    got, bad = judged(cell, rec, with_tables(rec, leaf_cw=cw))
    assert got["leaf_cycles"] == 2 and "leaf_cycles" in bad


def test_a_hop_into_its_visited_list_is_caught(sound):
    _, cell, _, rec = sound
    ev = with_route(
        rec["evidence"],
        lambda snap, row: (snap["visited"][row] != NO_NODE).sum() >= 2,
        dst=lambda snap, row: snap["visited"][row][1])
    got, bad = judged(cell, rec, ev)
    assert got["route_loops"] == 1 and "route_loops" in bad


def test_a_hop_that_makes_no_progress_is_caught(sound):
    """The forwarder sends the message AWAY from the key: to the node on
    the ring's far side."""
    _, cell, _, rec = sound
    T = rec["evidence"]["tables"]
    ids = cell["reference"].keys_to_int(T["node_keys"])

    def far_side(snap, row):
        key = cell["reference"].keys_to_int(snap["key"][row][None, :])[0]
        far = (key + (1 << 159)) % (1 << 160)
        return min(range(N), key=lambda j: abs(ids[j] - far))

    ev = with_route(rec["evidence"], lambda snap, row: True, dst=far_side)
    got, bad = judged(cell, rec, ev)
    assert got["route_no_progress"] == 1 and "route_no_progress" in bad


def test_a_payload_delivered_beside_the_owner_is_caught(sound):
    """A node that holds itself responsible for a key it does not own
    (its first clockwise leaf is gone from its view) takes a payload on
    its last hop."""
    _, cell, _, rec = sound
    ev = rec["evidence"]
    T = ev["tables"]
    ref = cell["reference"]
    ring = ref.Ring(ref.keys_to_int(T["node_keys"]),
                    np.asarray(T["alive"], bool), 160)
    kind = ev["wire"]["KBR_ROUTE"]
    # a last hop, readdressed to the owner's predecessor, which is made
    # to see nobody between itself and the key
    for at, snap in enumerate(ev["snaps"]):
        rows = np.nonzero(snap["valid"] & (snap["kind"] == kind)
                          & (snap["inner"] == ev["wire"]["APP_ONEWAY"]))[0]
        for row in rows:
            key = ref.keys_to_int(snap["key"][row][None, :])[0]
            owner = ring.owner(key)
            if int(snap["dst"][row]) == owner:
                break
        else:
            continue
        break
    else:
        raise AssertionError("no last hop seen")
    beside = ring.at(ring.pos[owner] - 1)
    snap = dict(snap)
    dst = snap["dst"].copy()
    dst[row] = beside
    snap["dst"] = dst
    cw = T["leaf_cw"].copy()
    cw[beside, 0] = NO_NODE
    planted = dict(ev, snaps=ev["snaps"][:at] + [snap] + ev["snaps"][at + 1:],
                   tables=dict(T, leaf_cw=cw))
    got, bad = judged(cell, rec, planted)
    assert got["payload_not_owner"] == 1 and "payload_not_owner" in bad


def test_a_forward_the_ack_recount_misses_is_caught(sound):
    _, cell, _, rec = sound
    ev = rec["evidence"]
    for name in ("route_forwarded", "route_acked", "bamboo_app_routes",
                 "route_delivered"):
        stats = dict(ev["close"]["stats"])
        stats["c:" + name] = stats["c:" + name] + 1
        got, bad = judged(cell, rec, dict(
            ev, close=dict(ev["close"], stats=stats)))
        assert got["route_recount_gap"] == 1, name
        assert bad == ["route_recount_gap"], (name, bad)
    # a slot left pending that no counter accounts for
    got, bad = judged(cell, rec, dict(ev, close=dict(
        ev["close"], route_pending=ev["close"]["route_pending"] + 1)))
    assert got["route_recount_gap"] == 1 and bad == ["route_recount_gap"]


def test_payloads_dropped_on_the_way_are_caught_and_have_failed(sound):
    """A drop the program COUNTS balances the recount, and KBRTestApp
    never hears of it: the reference's share of drops catches it, and
    through the program file's ``stats`` so do ``lookup_failed_share``
    and the result line's ``failed``."""
    import dataclasses
    import window as window_mod
    _, cell, prog, rec = sound
    ev = rec["evidence"]
    routes = rec["readings"]["app_routes"]
    n = routes // 20 + 1                      # 5% of the window's payloads
    stats = dict(ev["close"]["stats"])
    stats["c:route_dropped_hop_bound"] = stats["c:route_dropped_hop_bound"] + n
    stats["c:route_delivered"] = stats["c:route_delivered"] - n
    got, bad = judged(cell, rec, dict(ev, close=dict(ev["close"],
                                                     stats=stats)))
    assert got["route_recount_gap"] == 0
    assert got["route_dropped_share"] == n / routes > 0.01
    assert bad == ["route_dropped_share"]
    # the program file puts the routed path's drops among the payloads
    # that failed, in every stats it hands on
    s = prog.init(3)
    more = {"c:route_dropped_no_candidate": 2, "c:route_dropped_hop_bound": 3,
            "c:kbr_sent": 9, "c:kbr_delivered": 4}
    s = dataclasses.replace(s, stats=dict(s.stats, **{
        k: s.stats[k] + v for k, v in more.items()}))
    opening, close, snap = prog.counters(prog.init(3)), prog.counters(s), \
        prog.payloads(s)
    assert int(close["stats"]["c:kbr_lookup_failed"]) == 5
    assert snap["stats"]["c:kbr_lookup_failed"] == 5
    assert int(close["stats"]["c:route_dropped_hop_bound"]) == 3
    look = window_mod.lookups(opening, close)
    assert (look["attempted"], look["failed"]) == (9, 5)
    assert window_mod.lookups(opening, snap)["failed"] == 5


def test_an_upkeep_timer_that_skips_rounds_is_caught(sound):
    _, cell, _, rec = sound
    ev = rec["evidence"]
    t_ls = ev["tables"]["t_ls"].copy()
    t_ls[3] -= int(9e9)
    got, bad = judged(cell, rec, with_tables(rec, t_ls=t_ls))
    assert got["upkeep_timers_overdue"] == 1
    assert bad == ["upkeep_timers_overdue"]
    # every node fired half its local-tuning rounds
    stats = dict(ev["close"]["stats"])
    o = ev["opening"]["stats"]["c:bamboo_lt_probes"]
    stats["c:bamboo_lt_probes"] = o + (stats["c:bamboo_lt_probes"] - o) // 2
    got, bad = judged(cell, rec, dict(ev, close=dict(ev["close"],
                                                     stats=stats)))
    assert got["local_tuning_off_law"] > 6.0
    assert bad == ["local_tuning_off_law"]


# -- the metrics on made-up counters ------------------------------------------------

def _readers():
    bench = cells.load_benchmark()
    return dict((m["name"], rd) for m, rd in cells.metrics_for(
        bench, CELL, "per_layer"))


def test_the_two_metrics_on_made_up_counters():
    read = _readers()
    names = ("bamboo_ls_rounds", "bamboo_lt_probes", "bamboo_gt_lookups",
             "bamboo_state_msgs", "bamboo_app_routes", "route_forwarded",
             "route_delivered")
    opening = {"stats": {"c:" + k: 100 for k in names}}
    close = {"stats": dict(opening["stats"])}
    for k, more in zip(names, (25, 10, 5, 35, 5, 20, 8)):
        close["stats"]["c:" + k] += more
    rec = {"evidence": {"opening": opening, "close": close}}
    assert read["bamboo_upkeep_call_share"](rec) == 75.0
    assert read["route_hops_per_delivery"](rec) == 2.5
    # nothing started: nothing to read; a program without the counters
    # (Kademlia, Chord, the parent's Pastry): nothing to read, no error
    same = {"evidence": {"opening": opening, "close": opening}}
    assert read["bamboo_upkeep_call_share"](same) is None
    assert read["route_hops_per_delivery"](same) is None
    bare = {"stats": {"c:kbr_delivered": 5, "c:route_dropped": 0}}
    for rd in (read["bamboo_upkeep_call_share"],
               read["route_hops_per_delivery"]):
        assert rd({"evidence": {"opening": bare, "close": bare}}) is None
