"""``inbox_select_lane_share``: 100 for a record whose engine counters
hold no ``inbox_lanes`` (every round sweeps the pool), None for a window
of no ticks, and the lanes a round swept over the pool's slots for a
recorded run of the cell's program at N=128 through ``run.drive``."""

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
        bench, name, "per_layer"))["inbox_select_lane_share"]


def test_entry_is_one_of_the_tick_phases_and_in_every_cell():
    bench = cells.load_benchmark()
    assert bench["per_layer"][-1] == {
        "name": "inbox_select_lane_share", "unit": "%",
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
    # a program without the counters: every slot, every round
    assert read(record(200, 440, {"pool_overflow": 0},
                       {"pool_overflow": 0, "lanes_stepped": 7})) == 100.0
    # 240 ticks over P = 32768: 237 on D = 1024 lanes, 3 fell back
    p, d = 32768, 1024
    eng0 = {"inbox_lanes": 5 * p, "inbox_pool_slots": 200 * p}
    eng1 = {"inbox_lanes": 5 * p + 237 * d + 3 * p,
            "inbox_pool_slots": 440 * p}
    assert read(record(200, 440, eng0, eng1)) == \
        100.0 * (237 * d + 3 * p) / (240 * p)
    # every tick fell back: the P-wide rounds' own share
    assert read(record(0, 10, {"inbox_lanes": 0, "inbox_pool_slots": 0},
                       {"inbox_lanes": 10 * p,
                        "inbox_pool_slots": 10 * p})) == 100.0
    assert read(record(200, 200, eng0, eng0)) is None     # no tick ran
    assert read(record(200, 200, {}, {})) is None


def test_recorded_run_reads_its_lanes(monkeypatch):
    """The cell's program at N=128 through drive: the engine's two
    counters reach ``evidence`` through ``prog.counters`` as they are,
    every tick adds P to the one and D or P to the other."""
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
    p = prog.sim.ep.pool_factor * N
    d = prog.sim.inbox_lanes
    assert d == max(32, p // 32) < p
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
    slots = (close["engine"]["inbox_pool_slots"]
             - opening["engine"]["inbox_pool_slots"])
    lanes = close["engine"]["inbox_lanes"] - opening["engine"]["inbox_lanes"]
    assert slots == ticks * p
    assert opening["engine"]["inbox_pool_slots"] == opening["tick"] * p
    wide, rem = divmod(lanes - ticks * d, p - d)  # ticks that fell back
    assert rem == 0 and 0 <= wide <= ticks
    assert read(rec) == 100.0 * lanes / slots
    assert 100.0 * d / p <= read(rec) <= 100.0
