"""The rest of a run with the timed path sound, one precision down, and
broken underneath.  Skips the harness's look for a chip: the program is
built at a size a test run can hold (N=128 on the CPU) and ``run.drive``
does everything a run does after that look.  About two minutes.

Limits are the cell's own, except those set from readings at the cell's
size on the chip that are shares of small counts at N=128.
"""

import argparse
import copy
import dataclasses

import numpy as np
import pytest

import json
import os

import cells
import cellrun
import run
import sweep
from conftest import HERE

# a mix that is no cell's and ships as no traffic file (testMsgInterval
# 10 s, from no source): enough calls in flight at this size
TRAFFIC = os.path.join(HERE, "data", "kbr10.json")
N = 128
STRETCH_SIM_S = 8.0


@pytest.fixture(scope="module")
def setup():
    bench = cells.load_benchmark()
    name = next(w["name"] for w in bench["workloads"] if w["chips"] == 1)
    cell = cells.find_cell(bench, name)
    with open(TRAFFIC) as f:
        cell["traffic"] = json.load(f)
    cell["config"] = copy.deepcopy(cell["config"])
    lim = cell["config"]["limits"]
    lim["payload_far_share"] = ["max", 0.5]       # N/16 is 8 nodes here
    lim["lookup_failed_share"] = ["max", 0.1]     # some 50 lookups end
    # the counted stretch: five dispatches, which the shortest run here
    # passes (the cell's own is 0.8 of what 51 s cover on the chip)
    cell["config"]["failures_over_sim_s"] = STRETCH_SIM_S
    import program
    prog = program.Program(cell["config"], cell["traffic"], 1, n=N,
                           persistent_cache=False)
    return bench, name, cell, prog


def args_for(name, seed):
    return argparse.Namespace(workload=name, seed=seed, seconds=4.0,
                              trace=0, rehearsal=None, changed={})


def drive(setup, prog, seed=7):
    bench, name, cell, _ = setup
    result, lines = run.drive(bench, cell, prog, args_for(name, seed),
                              prog.device_record(), None)
    return result, lines


@pytest.fixture
def recs(monkeypatch):
    """The records ``run.drive`` gets from ``cellrun.run_cell``."""
    kept = []
    real = cellrun.run_cell
    monkeypatch.setattr(
        cellrun, "run_cell",
        lambda *a, **kw: kept.append(real(*a, **kw)) or kept[-1])
    return kept


class Wrapped:
    """The program with one method replaced; everything else as it is."""

    def __init__(self, prog, **replaced):
        self._prog, self._replaced = prog, replaced

    def __getattr__(self, name):
        if name in self._replaced:
            return self._replaced[name]
        return getattr(self._prog, name)


def test_a_sound_run_is_correct_and_reports_the_contract_keys(setup):
    _, _, _, prog = setup
    result, lines = drive(setup, prog)
    assert result["correct"] is True, lines
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert set(result["metrics"]) == {"sim_s_per_wall_s", "lookups_per_s",
                                      "setup_s"}
    assert result["attempted"] > 0 and result["failed"] >= 0
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert any(line.startswith("compare timer_off_lattice = 0 ")
               for line in lines)


def test_two_lengths_of_one_seed_count_the_same_stretch(setup, recs):
    """A parent and an exact change twice as fast, stood in for by one
    seed run for two lengths: the result line's ``attempted`` and
    ``failed`` are the same two integers, the whole window's are not."""
    bench, name, cell, prog = setup
    lines = []
    for seconds in (2.0, 5.0):
        args = args_for(name, 13)
        args.seconds = seconds
        result, _ = run.drive(bench, cell, prog, args,
                              prog.device_record(), None)
        lines.append((result["attempted"], result["failed"]))
    short, long_ = recs
    assert short["stretch"]["reached"] and long_["stretch"]["reached"]
    assert short["stretch"] == long_["stretch"]
    assert short["stretch"]["sim_s"] == STRETCH_SIM_S
    assert lines[0] == lines[1] == (short["attempted"], short["failed"])
    assert lines[0][0] > 0
    assert long_["dispatches"] > short["dispatches"]
    assert long_["window_attempted"] > short["window_attempted"] \
        >= short["attempted"]
    # the rates and the comparison still read the whole window
    assert long_["rates"]["lookups"]["attempted"] == \
        long_["window_attempted"]
    assert sum(long_["failed_by_tenth"]["ended"]) == \
        long_["window_attempted"]


def test_lookups_cut_short_fail_more_over_the_stretch(setup, recs):
    """The control G2 as the chip runs it (``run.py --ini
    '**.overlay.kademlia.lookupRedundantNodes=1'``): its failed share
    over the counted stretch is many times the sound run's."""
    bench, name, cell, prog = setup
    drive(setup, prog, seed=11)
    cut = dict(cell["traffic"], overrides=dict(
        cell["traffic"]["overrides"],
        **{"**.overlay.kademlia.lookupRedundantNodes": 1}))
    import program
    broken = program.Program(cell["config"], cut, 1, n=N,
                             persistent_cache=False)
    result, _ = run.drive(bench, dict(cell, traffic=cut), broken,
                          args_for(name, 11), broken.device_record(), None)
    sound, control = recs
    assert sound["stretch"]["reached"] and control["stretch"]["reached"]
    assert result["correct"] is False
    assert (result["attempted"], result["failed"]) == (
        control["attempted"], control["failed"])
    share = control["failed"] / control["attempted"]
    assert share >= 0.05
    assert share >= 3 * sound["failed"] / sound["attempted"]


def test_the_control_at_test_size_is_not_correct(setup):
    """The reference in the program's place, time as float32 seconds and
    coordinates as bfloat16, read from the same window."""
    bench, name, cell, prog = setup
    rec = cellrun.run_cell(prog, cell, 11, 4.0, t_proc=0.0,
                           say=lambda m: None)
    assert rec["correct"], cellrun.verdict_lines(rec)
    readings, rows = sweep.control_of(rec, cell, 11)
    failed = {r[0] for r in rows if not r[4]}
    assert "timer_off_lattice" in failed
    assert readings["timer_off_lattice"] > N // 2
    # everything that does not depend on the precision still holds
    assert failed <= {"timer_off_lattice", "delay_early_ns"}


def test_a_step_that_returns_its_state_unchanged_is_not_correct(setup):
    _, _, _, prog = setup
    calls = {"n": 0}

    def run_to(s, target_ns):
        calls["n"] += 1
        if calls["n"] <= 2:             # set-up runs; the window does not
            return prog.run_to(s, target_ns)
        return s

    result, lines = drive(setup, Wrapped(prog, run_to=run_to))
    assert result["correct"] is False
    bad = [ln for ln in lines if "NOT OK" in ln]
    assert any("tick_count_gap" in ln for ln in bad), lines
    assert any("sim_ns_advanced" in ln for ln in bad), lines
    assert result["attempted"] == 0


def test_an_answer_altered_where_it_is_produced_is_not_correct(setup):
    """Every test payload readdressed to the node after the right one
    (the slot numbering has nothing to do with the key space)."""
    _, _, _, prog = setup
    kind = prog.wire()["APP_ONEWAY"]

    def payloads(s):
        snap = prog.payloads(s)
        hit = snap["kind"] == kind
        snap["dst"] = np.where(hit, (snap["dst"] + N // 2) % N, snap["dst"])
        return snap

    result, lines = drive(setup, Wrapped(prog, payloads=payloads))
    assert result["correct"] is False
    assert any("payload_far_share" in ln and "NOT OK" in ln
               for ln in lines), lines


def test_a_timer_that_fires_off_its_due_time_is_not_correct(setup):
    _, _, _, prog = setup
    state = {"n": 0}

    def counters(s):
        out = prog.counters(s)
        state["n"] += 1
        if state["n"] == 2:             # the close: timers re-armed from
            out["t_test"] = out["t_test"].copy()   # the tick's start
            out["t_test"] -= out["t_test"] % 200_000_000
        return out

    result, lines = drive(setup, Wrapped(prog, counters=counters))
    assert result["correct"] is False
    assert any("timer_off_lattice" in ln and "NOT OK" in ln
               for ln in lines), lines


def test_a_step_that_leaves_out_a_part_of_the_batch_is_not_correct(setup):
    """Half the nodes' test timers are passed over by every dispatch of
    the window: their state comes back as it went in."""
    _, _, _, prog = setup
    calls = {"n": 0}
    rows = slice(0, N // 2)

    def run_to(s, target_ns):
        calls["n"] += 1
        if calls["n"] <= 2:             # set-up runs whole
            return prog.run_to(s, target_ns)
        t_old = np.asarray(s.logic.app.t_test)[rows]
        seq_old = np.asarray(s.logic.app.seq)[rows]
        out = prog.run_to(s, target_ns)
        app = out.logic.app
        app = dataclasses.replace(
            app, t_test=app.t_test.at[rows].set(t_old),
            seq=app.seq.at[rows].set(seq_old))
        return dataclasses.replace(
            out, logic=dataclasses.replace(out.logic, app=app))

    result, lines = drive(setup, Wrapped(prog, run_to=run_to))
    assert result["correct"] is False
    assert any("timers_overdue" in ln and "NOT OK" in ln
               for ln in lines), lines


def test_the_program_with_a_guarantee_broken_is_not_correct(setup):
    """The control as the chip ran it (``run.py --seeds ... --set
    engine.outbox_slots=1``): the program itself with one message a node
    a tick, so the engine loses what a lookup sends beside it."""
    bench, name, cell, _ = setup
    broken = copy.deepcopy(cell["config"])
    broken["engine"]["outbox_slots"] = 1
    import program
    prog = program.Program(broken, cell["traffic"], 1, n=N,
                           persistent_cache=False)
    result, lines = run.drive(bench, dict(cell, config=broken), prog,
                              args_for(name, 7), prog.device_record(), None)
    assert result["correct"] is False
    assert any("messages_lost" in ln and "NOT OK" in ln
               for ln in lines), lines
