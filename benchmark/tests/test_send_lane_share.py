"""``send_lane_share``: 100 for a record whose engine counters hold no
``send_lanes`` (the closing phase runs over every outbox slot), None for
a window of no ticks, and the slots the closing phase ran over of the
outbox's for a recorded run of the cell's program at N=128 through
``run.drive``."""

import argparse
import copy
import json
import os

import cellrun
import cells
import run
from conftest import HERE

N = 128


def reader(bench, name):
    return dict((m["name"], r) for m, r in cells.metrics_for(
        bench, name, "per_layer"))["send_lane_share"]


def test_entry_is_one_of_the_tick_phases_and_in_every_cell():
    bench = cells.load_benchmark()
    entry, = (m for m in bench["per_layer"]
              if m["name"] == "send_lane_share")
    assert entry == {
        "name": "send_lane_share", "unit": "%",
        "better": "lower", "source": "program_counter",
        "layer": "tick phases (_phase_*)", "moves": "sim_s_per_wall_s"}
    for w in bench["workloads"]:
        assert callable(reader(bench, w["name"]))


def record(tick0, tick1, eng0, eng1):
    return {"evidence": {
        "opening": {"tick": tick0, "alive": 4096, "engine": eng0},
        "close": {"tick": tick1, "alive": 4096, "engine": eng1}}}


def test_made_up_records():
    bench = cells.load_benchmark()
    read = reader(bench, bench["workloads"][0]["name"])
    # a program without the counters (the parent's): every slot, every tick
    assert read(record(200, 440, {"pool_overflow": 0},
                       {"pool_overflow": 0, "inbox_lanes": 7})) == 100.0
    # 240 ticks over Q = 65536: 237 on K = 2048 lanes, 3 took the wide form
    q, k = 65536, 2048
    eng0 = {"send_lanes": 5 * q, "send_outbox_slots": 200 * q}
    eng1 = {"send_lanes": 5 * q + 237 * k + 3 * q,
            "send_outbox_slots": 440 * q}
    assert read(record(200, 440, eng0, eng1)) == \
        100.0 * (237 * k + 3 * q) / (240 * q)
    # every tick fit: K over Q
    assert read(record(0, 10, {"send_lanes": 0, "send_outbox_slots": 0},
                       {"send_lanes": 10 * k,
                        "send_outbox_slots": 10 * q})) == 3.125
    # every tick took the wide form: the Q-wide form's own share
    assert read(record(0, 10, {"send_lanes": 0, "send_outbox_slots": 0},
                       {"send_lanes": 10 * q,
                        "send_outbox_slots": 10 * q})) == 100.0
    assert read(record(200, 200, eng0, eng0)) is None     # no tick ran
    assert read(record(200, 200, {}, {})) is None


def test_recorded_run_reads_its_lanes(monkeypatch):
    """The cell's program at N=128 through drive: the engine's two
    counters reach ``evidence`` through ``prog.counters`` as they are,
    every tick adds Q to the one and K or Q to the other."""
    bench = cells.load_benchmark()
    name = next(w["name"] for w in bench["workloads"] if w["chips"] == 1)
    read = reader(bench, name)
    cell = cells.find_cell(bench, name)
    with open(os.path.join(HERE, "data", "kbr10.json")) as f:
        cell["traffic"] = json.load(f)
    cell["config"] = copy.deepcopy(cell["config"])
    cell["config"]["limits"]["payload_far_share"] = ["max", 0.5]
    cell["config"]["limits"]["lookup_failed_share"] = ["max", 0.1]
    import program
    prog = program.Program(cell["config"], cell["traffic"], 1, n=N,
                           persistent_cache=False)
    q = prog.sim.ep.outbox_slots * N
    k = prog.sim.send_lanes
    assert k == max(32, q // 32) < q
    recs = []
    real = cellrun.run_cell
    monkeypatch.setattr(
        cellrun, "run_cell",
        lambda *a, **kw: recs.append(real(*a, **kw)) or recs[-1])
    args = argparse.Namespace(workload=name, seed=7, seconds=4.0, trace=0,
                              rehearsal=None, changed={})
    result, lines = run.drive(bench, cell, prog, args,
                              prog.device_record(), None)
    assert result["correct"] is True, lines
    rec, = recs
    opening, close = rec["evidence"]["opening"], rec["evidence"]["close"]
    ticks = close["tick"] - opening["tick"]
    assert ticks == rec["dispatches"] * rec["ticks_per_dispatch"] > 0
    slots = (close["engine"]["send_outbox_slots"]
             - opening["engine"]["send_outbox_slots"])
    lanes = close["engine"]["send_lanes"] - opening["engine"]["send_lanes"]
    assert slots == ticks * q
    assert opening["engine"]["send_outbox_slots"] == opening["tick"] * q
    wide, rem = divmod(lanes - ticks * k, q - k)  # ticks that went wide
    assert rem == 0 and 0 <= wide <= ticks
    assert read(rec) == 100.0 * lanes / slots
    assert 100.0 * k / q <= read(rec) <= 100.0
