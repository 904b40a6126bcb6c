"""``node_step_lane_share``: 100 for a record of the dense plane (no
``lanes_stepped`` among its engine counters), and the lanes the window
stepped over ticks x alive for a recorded run of the awake-set plane at
N=128 through ``run.drive``."""

import argparse
import copy
import json
import os

import cellrun
import cells
import run
from conftest import HERE

N, CAP = 128, 16


def reader(bench, name):
    return dict((m["name"], r) for m, r in cells.metrics_for(
        bench, name, "per_layer"))["node_step_lane_share"]


def test_entry_is_one_of_the_tick_phases():
    bench = cells.load_benchmark()
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "node_step_lane_share")
    assert entry == {"name": "node_step_lane_share", "unit": "%",
                     "better": "lower", "source": "program_counter",
                     "layer": "tick phases (_phase_*)",
                     "moves": "sim_s_per_wall_s"}


def test_dense_record_reads_100_and_awake_set_run_its_lanes(monkeypatch):
    bench = cells.load_benchmark()
    name = next(w["name"] for w in bench["workloads"] if w["chips"] == 1)
    read = reader(bench, name)

    def record(tick0, tick1, alive, eng0, eng1):
        return {"evidence": {
            "opening": {"tick": tick0, "alive": alive, "engine": eng0},
            "close": {"tick": tick1, "alive": alive, "engine": eng1}}}

    # the dense plane carries no such counter: every row, every tick
    assert read(record(200, 440, 4096, {"pool_overflow": 0},
                       {"pool_overflow": 0})) == 100.0
    # 240 ticks, 200 of one round of 512 lanes and 40 of two
    assert read(record(200, 440, 4096, {"lanes_stepped": 1000},
                       {"lanes_stepped": 1000 + 280 * 512})) == \
        100.0 * 280 * 512 / (240 * 4096)
    assert read(record(200, 200, 4096, {}, {})) is None   # no tick ran

    # a recorded run: the cell's program at N=128, A=16, through drive
    cell = cells.find_cell(bench, name)
    with open(os.path.join(HERE, "data", "kbr10.json")) as f:
        cell["traffic"] = json.load(f)
    cell["config"] = copy.deepcopy(cell["config"])
    cell["config"]["engine"]["active_cap"] = CAP
    cell["config"]["limits"]["payload_far_share"] = ["max", 0.5]
    cell["config"]["limits"]["lookup_failed_share"] = ["max", 0.1]
    import program
    prog = program.Program(cell["config"], cell["traffic"], 1, n=N,
                           persistent_cache=False)
    assert prog.sim.tick_impl == "sparse" and prog.sim.acap == CAP
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
    lanes = (close["engine"]["lanes_stepped"]
             - opening["engine"]["lanes_stepped"])
    awake = (close["engine"]["awake_nodes"]
             - opening["engine"]["awake_nodes"])
    assert ticks == rec["dispatches"] * rec["ticks_per_dispatch"] > 0
    assert close["alive"] == N
    assert lanes % CAP == 0 and awake <= lanes < awake + ticks * CAP
    assert read(rec) == 100.0 * lanes / (ticks * N)
    assert 0.0 < read(rec) < 100.0
