"""The cell whose nodes join and die while the window runs: found through
new files and entries only; ``program_churn`` through the harness at
target 128 (256 slots) on the CPU, sound, one precision down and under
each of the five G controls; each fault planted in a sound window's
evidence against the number that must catch it; the churn metric on
made-up counters.  Some three minutes; nothing here is a device number.

The law is the cell's but for the mean lifetime, 60 s where the cell
has 1,000 (in the ini text the program is given AND in the block the
reference judges by): at 128 nodes a window of a test's length would
otherwise see two or three deaths.  Limits are the cell's own, except
those set from readings at the cell's size on the chip that are shares
of small counts here.
"""

import argparse
import copy
import json
import os
import time

import numpy as np
import pytest

import cells
import cellrun
import run
import sweep
from conftest import BENCH, HERE

# a mix that is no cell's and ships as no traffic file (testMsgInterval
# 10 s, from no source): enough payloads in flight at this size
TRAFFIC = os.path.join(HERE, "data", "kbr10.json")
N = 128
CELL = "kademlia4096-lifetime.kbr60"
MEAN_S = 60.0


def churn_cell():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, CELL)
    with open(TRAFFIC) as f:
        cell["traffic"] = json.load(f)
    config = cell["config"] = copy.deepcopy(cell["config"])
    config["ini"] = [("**.lifetimeMean = %gs" % MEAN_S)
                     if ln.startswith("**.lifetimeMean") else ln
                     for ln in config["ini"]]
    config["churn"]["lifetime_mean_s"] = MEAN_S
    config["failures_over_sim_s"] = 16.0
    lim = config["limits"]
    lim["payload_far_share"] = ["max", 0.5]       # slots/32 is 8 nodes here
    lim["lookup_failed_share"] = ["max", 0.35]    # some 30 lookups end
    lim["bucket_dead_share"] = ["max", 0.6]       # lifetimes of a minute
    lim["joining_late"] = ["max", 3]
    return bench, cell


def program_for(cell, *, ini=None, engine=None):
    """The cell's program at target 128, optionally as a G control."""
    config, traffic = cell["config"], cell["traffic"]
    if engine:
        config = dict(config, engine=dict(config["engine"], **engine))
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


@pytest.fixture(scope="module")
def sound():
    bench, cell = churn_cell()
    prog = program_for(cell)
    return bench, cell, prog, window(cell, prog)


# -- found by name, through new files and entries only ------------------------

def test_the_cell_is_found_through_new_files_and_entries():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, CELL)
    config = cell["config"]
    assert cell["chips"] == config["chips"] == 1
    assert config["slots"] == 2 * config["nodes"]
    assert any("LifetimeChurn" in ln for ln in config["ini"])
    assert config["program"] == "program_churn"
    assert config["reference"] == "kademlia_kbr_churn"
    assert cells.load_program(config).__name__.endswith("program_churn")
    for fn in ("readings", "compare", "control"):
        assert callable(getattr(cell["reference"], fn))
    for zero in ("bucket_misplaced", "sibling_disorder", "dead_slots_busy",
                 "births_recount_gap", "kills_recount_gap",
                 "sent_recount_gap", "messages_lost"):
        assert config["limits"][zero] == ["max", 0]
    # the pair differs by the churn law alone
    plain = cells.find_cell(bench, "kademlia4096.kbr60")["config"]
    for key in ("engine", "underlay", "kademlia", "fill_s", "settle_s",
                "nodes", "reduced"):
        assert config[key] == plain[key], key
    differ = set(config["ini"]) ^ set(plain["ini"])
    assert all("hurn" in ln or "ifetime" in ln or "raceful" in ln
               for ln in differ), differ
    per_layer = [m["name"] for m, _ in cells.metrics_for(
        bench, CELL, "per_layer")]
    assert "churn_reset_row_share" in per_layer
    had = [m["name"] for m in bench["per_layer"] if "workloads" not in m]
    assert set(had) <= set(per_layer)
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert "churn_reset_row_share" not in [
                m["name"] for m, _ in cells.metrics_for(
                    bench, w["name"], "per_layer")]
    # the harness's own files name nothing the cell brought
    brought = (config["program"], config["reference"],
               "churn_reset_row_share", config["name"])
    for f in ("run.py", "cellrun.py", "cells.py", "window.py",
              "trace_reduce.py", "program.py", "program_mesh.py",
              "sweep.py"):
        text = open(os.path.join(BENCH, f)).read()
        assert not [b for b in brought if b in text], f


def test_the_program_file_reads_every_leaf_by_name(sound):
    _, cell, prog, _ = sound
    program = cells.load_program(cell["config"])
    s = prog.init(3)
    prog.check_surface(s)
    for path in ("churn.t_born", "counters.reset_rows", "logic.b_seen"):
        assert path in program.SURFACE
    with pytest.raises(program.SurfaceError, match="t_born"):
        program.leaf(s, "churn.t_born_gone")


# -- through the harness at target 128 ------------------------------------------

def test_a_sound_window_is_correct_and_saw_churn(sound):
    bench, cell, prog, rec = sound
    assert rec["correct"], [r for r in rec["rows"] if not r[4]]
    r = rec["readings"]
    assert r["births"] >= 10 and r["kills"] >= 10
    assert r["readbacks_apart_s"] < cell["config"]["churn"][
        "graceful_leave_delay_s"]
    assert r["dest_unavailable_lost"] > 0 and r["messages_lost"] == 0
    assert r["bucket_entries"] > 1000 and r["bucket_misplaced"] == 0
    assert r["nodes_whole_window"] > N // 4
    assert prog.tick_programs() >= 1 and r["tick_programs_extra"] == 0


def test_run_drive_reports_the_contract_keys(sound):
    bench, cell, prog, _ = sound
    args = argparse.Namespace(workload=CELL, seed=9, seconds=3.0, trace=0,
                              rehearsal=None, changed={})
    result, lines = run.drive(bench, cell, prog, args, prog.device_record(),
                              None)
    assert result["correct"] is True, lines
    assert set(result["metrics"]) == {"sim_s_per_wall_s", "lookups_per_s",
                                      "setup_s"}
    assert result["attempted"] > 0


def test_one_precision_down_is_not_correct(sound):
    _, cell, _, rec = sound
    _, rows = sweep.control_of(rec, cell, 7)
    bad = [r[0] for r in rows if not r[4]]
    assert "timer_off_lattice" in bad, bad


G_CONTROLS = {
    "G1": (dict(engine={"outbox_slots": 1}), "messages_lost"),
    "G2": (dict(ini={"**.overlay.kademlia.lookupRedundantNodes": 1}), None),
    "G3": (dict(ini={"**.tier1*.kbrTestApp.testMsgInterval": 120.0}),
           "timer_off_lattice"),
    "G4": (dict(ini={"**.lifetimeMean": 4 * MEAN_S}), "kills_off_poisson"),
    "G5": (dict(ini={"**.overlay.kademlia.maxStaleCount": 1000000}), None),
}


@pytest.mark.parametrize("name", sorted(G_CONTROLS))
def test_the_program_with_a_guarantee_broken(sound, name):
    """Each G control as the chip runs it (``run.py --set/--ini``).  At
    this size and a test's length G2 (lookups cut short) and G5 (failed
    nodes never evicted: the dead entries of half a simulated minute)
    need not fail by a number of their own, and are run to show that the
    harness drives them; the others fail by the number named."""
    _, cell, _, rec = sound
    how, by = G_CONTROLS[name]
    broken = window(cell, program_for(cell, **how), seconds=8.0)
    bad = [r[0] for r in broken["rows"] if not r[4]]
    print(name, bad, {k: broken["readings"].get(k) for k in (
        "lookup_failed_share", "bucket_dead_share", "kills_off_poisson",
        "births_off_poisson", "payload_far_share", "joining_late")})
    if by is not None:
        assert by in bad, (name, bad)


# -- faults planted in a sound window's evidence --------------------------------

def test_each_planted_fault_is_caught_by_its_number(sound):
    _, cell, _, rec = sound
    ev0 = rec["evidence"]
    base, bad0 = judged(cell, rec, ev0)
    assert not bad0
    last = ev0["snaps"][-1]["churn"]
    dead = int(np.nonzero(~last["alive"])[0][0])

    # a dead slot with a live timer
    ev = copy.deepcopy(ev0)
    ev["snaps"][-1]["churn"]["t_test"][dead] = 1
    r, bad = judged(cell, rec, ev)
    assert r["dead_slots_busy"] == 1 and bad == ["dead_slots_busy"]

    # ... and with a lookup still active at the close
    ev = copy.deepcopy(ev0)
    ev["tables"]["lookup_active"][dead, 0] = True
    assert judged(cell, rec, ev)[1] == ["dead_slots_busy"]

    # a birth the counter missed
    ev = copy.deepcopy(ev0)
    for view in (ev["snaps"][-1]["churn"], ev["close"]["churn"]):
        view["churn_created"] -= 1
    r, bad = judged(cell, rec, ev)
    assert r["births_recount_gap"] == 1 and bad == ["births_recount_gap"]

    # a slot whose incarnation changed while it stayed dead
    ev = copy.deepcopy(ev0)
    ev["snaps"][-1]["churn"]["t_born"][dead] = \
        ev0["snaps"][-1]["t_now_ns"] - 1
    ev["close"]["churn"]["t_born"][dead] = ev0["snaps"][-1]["t_now_ns"] - 1
    r, bad = judged(cell, rec, ev)
    assert "births_recount_gap" in bad and "kills_recount_gap" in bad

    # a test the application counted and no node's sequence number shows
    ev = copy.deepcopy(ev0)
    ev["close"]["stats"]["c:kbr_sent"] = \
        ev["close"]["stats"]["c:kbr_sent"] + 1
    assert "sent_recount_gap" in judged(cell, rec, ev)[1]

    # a population off its target at one read-back
    ev = copy.deepcopy(ev0)
    view = ev["snaps"][len(ev["snaps"]) // 2]["churn"]
    view["alive"][np.nonzero(~view["alive"])[0][:60]] = True
    assert "alive_off_target" in judged(cell, rec, ev)[1]

    # a message lost to something else than a dead receiver
    ev = copy.deepcopy(ev0)
    ev["close"]["engine"]["pool_overflow"] += 1
    assert judged(cell, rec, ev)[1] == ["messages_lost"]


def test_a_reborn_slot_in_its_old_bucket_is_caught_or_excused(sound):
    """An entry in another bucket than its slot's current key earns is a
    fault where its holder has heard anyone since the slot was born, and
    counted with the dead entries where it has not."""
    _, cell, _, rec = sound
    ev0 = rec["evidence"]
    base, _ = judged(cell, rec, ev0)
    T = ev0["tables"]
    view = ev0["snaps"][-1]["churn"]
    heard = T["b_seen"].reshape(len(view["alive"]), -1).max(axis=1)
    # a holder, and an entry of its whose slot is alive and was born
    # before the holder last heard anyone
    holder, b, slot = next(
        (int(i), int(b), int(e))
        for i in np.nonzero(view["alive"] & (heard > 0))[0]
        for b, e in zip(*np.nonzero(T["buckets"][i] != -1)[:1],
                        T["buckets"][i][T["buckets"][i] != -1])
        if view["alive"][e] and view["t_born"][e] <= heard[i])
    other = (b + 7) % T["buckets"].shape[1]
    free = int(np.nonzero(T["buckets"][holder, other] == -1)[0][0])

    ev = copy.deepcopy(ev0)
    ev["tables"]["buckets"][holder, other, free] = slot
    r, bad = judged(cell, rec, ev)
    assert r["bucket_misplaced"] == 1 and bad == ["bucket_misplaced"]
    # the same entry, the slot reborn after anything the holder heard
    for views in (ev["snaps"][-1]["churn"], ev["close"]["churn"]):
        views["alive"][slot] = True
        views["t_born"][slot] = int(heard[holder]) + 1
    r, bad = judged(cell, rec, ev)
    assert r["bucket_misplaced"] == 0
    assert r["bucket_reborn_entries"] > base["bucket_reborn_entries"]
    # an entry held by a dead slot is a fault whatever it points at
    dead = int(np.nonzero(~view["alive"])[0][0])
    ev = copy.deepcopy(ev0)
    ev["tables"]["buckets"][dead, 0, 0] = holder
    assert "bucket_misplaced" in judged(cell, rec, ev)[1]


def test_a_payload_is_ranked_under_its_own_read_backs_keys(sound):
    _, cell, _, rec = sound
    ev0 = rec["evidence"]
    wire = ev0["wire"]
    base, _ = judged(cell, rec, ev0)
    assert base["payloads_checked"] > 0
    at, dst = next((i, int(s["dst"][r])) for i, s in enumerate(ev0["snaps"])
                   for r in np.nonzero(s["kind"] == wire["APP_ONEWAY"])[0])
    # the addressed slot is reborn under another key AFTER that
    # read-back: the payload's rank does not move
    ev = copy.deepcopy(ev0)
    for snap in ev["snaps"][at + 1:]:
        snap["churn"]["node_keys"][dst] ^= np.uint32(0xFFFFFFFF)
    r, _ = judged(cell, rec, ev)
    assert r["payload_rank_max"] == base["payload_rank_max"]
    assert r["payload_far_share"] == base["payload_far_share"]
    # under another key AT that read-back it lands far off
    ev = copy.deepcopy(ev0)
    ev["snaps"][at]["churn"]["node_keys"][dst] ^= np.uint32(0xFFFFFFFF)
    r, _ = judged(cell, rec, ev)
    assert r["payload_far_share"] > base["payload_far_share"]
    assert r["payload_rank_max"] > len(ev0["close"]["seq"]) // 8


# -- the metric on made-up counters ------------------------------------------------

def test_churn_reset_row_share_on_made_up_counters():
    bench = cells.load_benchmark()
    read = dict((m["name"], r) for m, r in cells.metrics_for(
        bench, CELL, "per_layer"))["churn_reset_row_share"]

    def rec(rows_open, rows_close, ticks=(100, 300), slots=256,
            carries=True):
        side = lambda tick, rows: {  # noqa: E731
            "tick": tick, "seq": np.zeros(slots, np.int32),
            "engine": {"reset_rows": rows} if carries else {}}
        return {"evidence": {"opening": side(ticks[0], rows_open),
                             "close": side(ticks[1], rows_close)}}

    # dense selects over all rows every tick
    assert read(rec(100 * 256, 300 * 256)) == pytest.approx(100.0)
    # a churn phase that rewrote 64 rows a tick
    assert read(rec(6400, 6400 + 200 * 64)) == pytest.approx(25.0)
    # a program without the counter sweeps every row by definition
    assert read(rec(0, 0, carries=False)) == 100.0
    # a window of no ticks reports nothing
    assert read(rec(0, 0, ticks=(100, 100))) is None
    m = next(m for m in bench["per_layer"]
             if m["name"] == "churn_reset_row_share")
    assert m["workloads"] == [CELL] and m["better"] == "lower"
    assert json.dumps(m)         # plain data
