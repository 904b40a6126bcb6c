"""The cell whose overlay is a ring: found through new files and entries
only; ``program_chord`` through the harness at N = 128 on the CPU, sound
and one precision down; each fault planted in a sound window's evidence
against the number that must catch it (a swapped successor, a finger
outside its interval, a payload sent beside the owner, a stabilise timer
that skips a round); the program with a maintenance law broken (G2);
``SURFACE`` by name; the upkeep metric on made-up counters.  Some three
minutes; nothing here is a device number.
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
CELL = "chord1000.kbr60"
NO_NODE = -1


def chord_cell():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, CELL)
    with open(TRAFFIC) as f:
        cell["traffic"] = json.load(f)
    cell["config"] = copy.deepcopy(cell["config"])
    cell["config"]["failures_over_sim_s"] = 16.0
    return bench, cell


def program_for(cell, *, ini=None):
    """The cell's program at N = 128, optionally as a G control."""
    config, traffic = cell["config"], cell["traffic"]
    if ini:
        traffic = dict(traffic, overrides=dict(traffic["overrides"], **ini))
    program = cells.load_program(config)
    return program.Program(config, traffic, 1, n=N, persistent_cache=False)


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


@pytest.fixture(scope="module")
def sound():
    bench, cell = chord_cell()
    prog = program_for(cell)
    return bench, cell, prog, window(cell, prog)


# -- found by name, through new files and entries only ------------------------

def test_the_cell_is_found_through_new_files_and_entries():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, CELL)
    config = cell["config"]
    assert cell["chips"] == config["chips"] == 1
    assert any("chord.ChordModules" in ln for ln in config["ini"])
    assert config["program"] == "program_chord"
    assert config["reference"] == "chord_kbr"
    assert cells.load_program(config).__name__.endswith("program_chord")
    for fn in ("readings", "compare", "control"):
        assert callable(getattr(cell["reference"], fn))
    for zero in ("succ_wrong", "pred_wrong", "succ_list_faults",
                 "finger_outside", "payload_not_owner",
                 "lookups_wrong_node", "upkeep_timers_overdue",
                 "messages_lost", "not_ready"):
        assert config["limits"][zero] == ["max", 0]
    # the file, not the code, states the deployment: upstream's timers
    law = config["chord"]
    for key, ini in (("join_delay_s", "joinDelay"),
                     ("stabilize_delay_s", "stabilizeDelay"),
                     ("fixfingers_delay_s", "fixfingersDelay"),
                     ("check_pred_delay_s", "checkPredecessorDelay")):
        assert "**.overlay.chord.%s = %gs" % (ini, law[key]) in config["ini"]
    assert ("**.overlay.chord.successorListSize = %d" % law["succ_size"]
            in config["ini"])
    # the pair differs by the overlay alone
    plain = cells.find_cell(bench, "kademlia1000.kbr60")["config"]
    for key in ("underlay", "fill_s", "settle_s", "nodes", "precisions"):
        assert config[key] == plain[key], key
    # ... and by ChordLarge's own N, which the records give as 10,000
    assert config["reduced"] == plain["reduced"] + [
        "targetOverlayTerminalNum"]
    assert config["engine"]["window"] == plain["engine"]["window"]
    differ = set(config["ini"]) ^ set(plain["ini"])
    assert all("overlay" in ln for ln in differ), differ
    per_layer = [m["name"] for m, _ in cells.metrics_for(
        bench, CELL, "per_layer")]
    assert "maintenance_call_share" in per_layer
    had = [m["name"] for m in bench["per_layer"] if "workloads" not in m]
    assert set(had) <= set(per_layer)
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert "maintenance_call_share" not in [
                m["name"] for m, _ in cells.metrics_for(
                    bench, w["name"], "per_layer")]
    # the harness's own files name nothing the cell brought
    brought = (config["program"], config["reference"],
               "maintenance_call_share", config["name"])
    for f in ("run.py", "cellrun.py", "cells.py", "window.py",
              "trace_reduce.py", "program.py", "program_mesh.py",
              "program_churn.py", "sweep.py"):
        text = open(os.path.join(BENCH, f)).read()
        assert not [b for b in brought if b in text], f


def test_the_program_file_reads_every_leaf_by_name(sound):
    _, cell, prog, _ = sound
    program = cells.load_program(cell["config"])
    s = prog.init(3)
    prog.check_surface(s)
    for path in ("logic.succ", "logic.pred", "logic.finger",
                 "logic.finger_dirty", "logic.t_stab", "logic.t_fix",
                 "logic.t_cp", "logic.stab_op",
                 "stats.c:chord_stab_rounds", "stats.c:chord_fix_calls"):
        assert path in program.SURFACE
    assert "logic.buckets" not in program.SURFACE
    with pytest.raises(program.SurfaceError, match="succ_gone"):
        program.leaf(s, "logic.succ_gone")
    broken = types.SimpleNamespace(stats={
        k: v for k, v in s.stats.items() if k != "c:chord_pred_pings"})
    with pytest.raises(program.SurfaceError, match="chord_pred_pings"):
        program.leaf(broken, "stats.c:chord_pred_pings")


def test_a_tree_without_the_upkeep_counters_fails_by_name(monkeypatch):
    """What the parent of PR 39 is: it fails before a state is built."""
    _, cell = chord_cell()
    program = cells.load_program(cell["config"])
    from oversim_tpu import stats as stats_mod
    from oversim_tpu.overlay import chord
    monkeypatch.setattr(
        chord.ChordLogic, "stat_spec", lambda self: stats_mod.StatSpec(
            scalars=(), hists=(), counters=("chord_joins",)))
    with pytest.raises(program.SurfaceError, match="chord_stab_rounds"):
        program.check_program()


# -- through the harness at N = 128 -----------------------------------------------

def test_a_sound_window_is_correct_and_the_ring_is_closed(sound):
    _, _, prog, rec = sound
    assert rec["correct"], [r for r in rec["rows"] if not r[4]]
    r = rec["readings"]
    assert r["succ_wrong"] == r["pred_wrong"] == r["succ_list_faults"] == 0
    assert r["succ_list_entries"] == 8 * N and r["succ_list_missing"] == 0
    assert r["finger_entries"] > N and r["finger_outside"] == 0
    assert r["payloads_seen"] > 0 and r["payload_not_owner"] == 0
    assert r["stabilise_rounds"] > 0 and r["pred_ping_rounds"] > 0
    assert r["lookups_delivered"] > 0 and r["messages_lost"] == 0
    assert prog.tick_programs() >= 1 and r["tick_programs_extra"] == 0


def test_one_precision_down_is_not_correct(sound):
    _, cell, _, rec = sound
    _, rows = sweep.control_of(rec, cell, 7)
    bad = [r[0] for r in rows if not r[4]]
    assert "timer_off_lattice" in bad, bad


def test_another_maintenance_law_is_not_correct(sound):
    """G2 as the chip runs it (``run.py --ini``; there with 40 s and a
    window of hundreds of simulated seconds): the program stabilises
    every 80 s under a file that says 20."""
    _, cell, _, _ = sound
    broken = window(cell, program_for(
        cell, ini={"**.overlay.chord.stabilizeDelay": 80.0}), seconds=10.0)
    bad = [r[0] for r in broken["rows"] if not r[4]]
    assert "stabilise_rounds_off" in bad, (bad, broken["readings"])


# -- each planted fault against the number that must catch it ------------------

def test_a_swapped_successor_is_caught(sound):
    _, cell, _, rec = sound
    T = rec["evidence"]["tables"]
    succ = T["succ"].copy()
    succ[5, [0, 1]] = succ[5, [1, 0]]
    r, bad = judged(cell, rec, with_tables(rec, succ=succ))
    assert r["succ_wrong"] == 1 and "succ_wrong" in bad
    assert r["succ_list_faults"] >= 1 and "succ_list_faults" in bad
    pred = T["pred"].copy()
    pred[9] = pred[10]
    r, bad = judged(cell, rec, with_tables(rec, pred=pred))
    assert r["pred_wrong"] == 1 and bad == ["pred_wrong"]
    # a list that holds its owner, or a node from the far side
    succ = T["succ"].copy()
    succ[7, 3] = 7
    assert judged(cell, rec, with_tables(rec, succ=succ))[0][
        "succ_list_faults"] >= 1
    ids = cell["reference"].keys_to_int(T["node_keys"])
    far = max(range(N), key=lambda j: (ids[j] - ids[7]) % (1 << 160))
    succ = T["succ"].copy()
    succ[7, 7] = far
    r, bad = judged(cell, rec, with_tables(rec, succ=succ))
    assert r["succ_list_faults"] == 1 and bad == ["succ_list_faults"]


def test_a_finger_outside_its_interval_is_caught(sound):
    _, cell, _, rec = sound
    T = rec["evidence"]["tables"]
    finger = T["finger"].copy()
    i, b = map(int, np.argwhere(finger != NO_NODE)[-1])
    finger[i, b] = T["succ"][i, 0]        # the successor: nearer than 2^b
    r, bad = judged(cell, rec, with_tables(rec, finger=finger))
    assert r["finger_outside"] == 1 and bad == ["finger_outside"]
    finger[i, b] = i                      # the node itself
    assert judged(cell, rec, with_tables(rec, finger=finger))[0][
        "finger_outside"] == 1
    # every clean finger turned to ONE node: stale, though inside
    finger = T["finger"].copy()
    top = finger.shape[1] - 1
    finger[:, top] = np.where(finger[:, top] != NO_NODE,
                              T["pred"], NO_NODE)
    r, _ = judged(cell, rec, with_tables(rec, finger=finger))
    assert r["finger_outside"] == 0 and r["finger_stale_share"] > 0.05


def test_a_payload_sent_beside_the_owner_is_caught(sound):
    _, cell, _, rec = sound
    ev = rec["evidence"]
    kind = ev["wire"]["APP_ONEWAY"]
    at = next(i for i, snap in enumerate(ev["snaps"])
              if (snap["valid"] & (snap["kind"] == kind)).any())
    snap = dict(ev["snaps"][at])
    row = int(np.nonzero(snap["valid"] & (snap["kind"] == kind))[0][0])
    dst = snap["dst"].copy()
    owner = int(dst[row])
    dst[row] = ev["tables"]["pred"][owner]
    snap["dst"] = dst
    snaps = ev["snaps"][:at] + [snap] + ev["snaps"][at + 1:]
    r, bad = judged(cell, rec, dict(ev, snaps=snaps))
    assert r["payload_not_owner"] == 1 and bad == ["payload_not_owner"]


def test_a_stabilise_timer_that_skips_a_round_is_caught(sound):
    _, cell, _, rec = sound
    ev = rec["evidence"]
    # a timer left behind: overdue by more than a tick at the close
    t_stab = ev["tables"]["t_stab"].copy()
    t_stab[3] -= int(25e9)
    r, bad = judged(cell, rec, with_tables(rec, t_stab=t_stab))
    assert r["upkeep_timers_overdue"] == 1
    assert bad == ["upkeep_timers_overdue"]
    # every node skipped rounds: fewer started than the law says
    n = int(ev["tables"]["ready"].sum())
    sim_s = (ev["close"]["t_now_ns"] - ev["opening"]["t_now_ns"]) / 1e9
    skipped = int(n * sim_s / 20.0) - 2 * n
    stats = dict(ev["close"]["stats"])
    stats["c:chord_stab_rounds"] = (
        ev["opening"]["stats"]["c:chord_stab_rounds"] + max(skipped, 0))
    r, bad = judged(cell, rec, dict(ev, close=dict(ev["close"],
                                                   stats=stats)))
    assert r["stabilise_rounds_off"] > 1.0
    assert bad == ["stabilise_rounds_off"]


# -- the metric on made-up counters ------------------------------------------------

def test_maintenance_call_share_on_made_up_counters():
    bench = cells.load_benchmark()
    read = dict((m["name"], rd) for m, rd in cells.metrics_for(
        bench, CELL, "per_layer"))["maintenance_call_share"]
    names = ("chord_stab_rounds", "chord_notify_calls", "chord_pred_pings",
             "chord_fix_calls", "chord_app_calls", "kbr_delivered",
             "kbr_wrong_node")
    opening = {"stats": {"c:" + k: 100 for k in names}}
    close = {"stats": dict(opening["stats"])}
    for k, more in zip(names, (10, 10, 40, 30, 8, 2, 0)):
        close["stats"]["c:" + k] += more
    rec = {"evidence": {"opening": opening, "close": close}}
    assert read(rec) == 90.0
    # nothing started: nothing to read; a program without the counters
    # (Kademlia, the parent's Chord): nothing to read, and no error
    assert read({"evidence": {"opening": opening,
                              "close": opening}}) is None
    bare = {"stats": {"c:kbr_delivered": 5, "c:kbr_wrong_node": 0}}
    assert read({"evidence": {"opening": bare, "close": bare}}) is None
