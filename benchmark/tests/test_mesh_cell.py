"""The cell whose deployment is split over four chips: found through new
files and entries only, driven through ``run.drive`` at N=128 on four
virtual CPU devices (sound, and with the state placed as a copy on every
device), its layout check with faults planted, its collective metric on
a made-up trace.  About a minute and a half; nothing here is a device
number.

The four virtual devices come from ``XLA_FLAGS``, set below before jax
first looks for a backend (the other test files use device 0 of however
many there are).
"""

import argparse
import copy
import json
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + ("" if "xla_force_host_platform_device_count"
       in os.environ.get("XLA_FLAGS", "")
       else " --xla_force_host_platform_device_count=4")).strip()

import pytest                            # noqa: E402

import cells                             # noqa: E402
import run                               # noqa: E402
import trace_reduce                      # noqa: E402
from conftest import BENCH, HERE         # noqa: E402

TRAFFIC = os.path.join(HERE, "data", "kbr10.json")
N = 128
CHIPS = 4


def mesh_cell_name(bench):
    return next(w["name"] for w in bench["workloads"]
                if w["chips"] == CHIPS)


# -- found by name, through new files and entries only ------------------------

def test_the_cell_is_found_through_new_files_and_entries():
    bench = cells.load_benchmark()
    name = mesh_cell_name(bench)
    cell = cells.find_cell(bench, name)
    config = cell["config"]
    assert cell["chips"] == config["chips"] == CHIPS
    assert config["placement"] == "node_sharded"
    program = cells.load_program(config)
    assert program.__name__.endswith(config["program"])
    assert config["program"] != "program"
    for fn in ("readings", "compare", "control"):
        assert callable(getattr(cell["reference"], fn))
    assert config["limits"]["shards_misplaced"] == ["max", 0]
    per_layer = [m["name"] for m, _ in cells.metrics_for(
        bench, name, "per_layer")]
    assert "collective_ms_per_tick" in per_layer
    # every metric the benchmark had is reported by the new cell too
    had = [m["name"] for m in bench["per_layer"] if "workloads" not in m]
    assert set(had) <= set(per_layer)
    for w in bench["workloads"]:
        if w["chips"] == 1:
            names = [m["name"] for m, _ in cells.metrics_for(
                bench, w["name"], "per_layer")]
            assert "collective_ms_per_tick" not in names
    # the harness's own files name nothing the cell brought
    brought = (config["program"], config["reference"],
               "collective_ms_per_tick", config["name"])
    for f in ("run.py", "cellrun.py", "cells.py", "window.py",
              "trace_reduce.py", "program.py", "sweep.py"):
        text = open(os.path.join(BENCH, f)).read()
        assert not [b for b in brought if b in text], f


# -- the layout check, faults planted -----------------------------------------

def sound_layout(ref, rows=N, slots=8 * N):
    one = lambda total: {  # noqa: E731
        "rows_total": total, "shards": CHIPS,
        "rows": [total // CHIPS] * CHIPS,
        "starts": [i * total // CHIPS for i in range(CHIPS)],
        "devices": list(range(CHIPS))}
    names = (ref.NODE_ROW_LEAVES + ref.LOOKUP_LEAVES_READ
             + ("logic.lk.frontier",))
    out = {k: one(rows) for k in names}
    out.update({k: one(slots) for k in ref.POOL_LEAVES})
    return out


def test_layout_check_counts_each_planted_fault():
    bench = cells.load_benchmark()
    ref = cells.find_cell(bench, mesh_cell_name(bench))["reference"]
    lay = sound_layout(ref)
    got = ref.layout_readings(lay, CHIPS)
    assert got["shards_misplaced"] == 0
    assert got["shards_checked"] == len(lay)

    def planted(leaf, **changed):
        bad = copy.deepcopy(lay)
        if changed:
            bad[leaf].update(changed)
        else:
            del bad[leaf]
        return ref.layout_readings(bad, CHIPS)

    # a copy on every device: four shards, each of all the rows
    r = planted("logic.buckets", rows=[N] * CHIPS, starts=[0] * CHIPS)
    assert (r["shards_misplaced"], r["shards_misplaced_first"]) == (
        1, "logic.buckets")
    # two blocks on one device
    assert planted("pool.blk", devices=[0, 1, 2, 2])["shards_misplaced"] == 1
    # blocks of unequal rows
    assert planted("logic.sib", rows=[N // 2, N // 4, N // 8, N // 8],
                   starts=[0, N // 2, 3 * N // 4, 7 * N // 8]
                   )["shards_misplaced"] == 1
    # the same block twice, another missing
    assert planted("pool.valid", starts=[0, 0, 2 * N * 2, 3 * N * 2]
                   )["shards_misplaced"] == 1
    # all on one device
    assert planted("logic.lk.frontier", shards=1, rows=[N], starts=[0],
                   devices=[0])["shards_misplaced"] == 1
    # a leaf the program file did not record
    assert planted("pool.t_deliver")["shards_misplaced"] == 1
    assert ref.layout_readings({}, CHIPS)["shards_misplaced"] == len(lay) - 1


# -- through run.drive on four virtual devices --------------------------------

@pytest.fixture(scope="module")
def setup():
    import jax
    if len(jax.devices()) < CHIPS:
        pytest.skip("needs four devices: XLA_FLAGS=--xla_force_host_"
                    "platform_device_count=4 before jax finds its backend")
    bench = cells.load_benchmark()
    name = mesh_cell_name(bench)
    cell = cells.find_cell(bench, name)
    with open(TRAFFIC) as f:
        cell["traffic"] = json.load(f)
    cell["config"] = copy.deepcopy(cell["config"])
    lim = cell["config"]["limits"]
    lim["payload_far_share"] = ["max", 0.5]       # N/16 is 8 nodes here
    lim["lookup_failed_share"] = ["max", 0.1]     # some 50 lookups end
    return bench, name, cell


def drive(setup, config, seed=7):
    bench, name, cell = setup
    program = cells.load_program(config)
    prog = program.Program(config, cell["traffic"], CHIPS, n=N,
                           persistent_cache=False)
    args = argparse.Namespace(workload=name, seed=seed, seconds=4.0,
                              trace=0, rehearsal=None, changed={})
    result, lines = run.drive(bench, dict(cell, config=config), prog, args,
                              prog.device_record(), None)
    return prog, result, lines


def test_a_sound_run_on_four_devices_is_correct(setup):
    prog, result, lines = drive(setup, setup[2]["config"])
    assert result["correct"] is True, lines
    assert result["device"]["count"] >= CHIPS
    assert set(result["metrics"]) == {"sim_s_per_wall_s", "lookups_per_s",
                                      "setup_s"}
    assert result["attempted"] > 0
    assert any(ln.startswith("compare shards_misplaced = 0 ")
               for ln in lines), lines
    assert any(ln.startswith("compare tick_programs_extra = 0 ")
               for ln in lines), lines
    assert prog.tick_programs() == 1
    assert dict(prog.mesh.shape) == {"nodes": CHIPS}


def test_a_copy_on_every_device_is_not_correct(setup):
    """The control as the chip runs it (``run.py --set
    placement='"replicated"'``): the same entry, every device holding
    every row.  Everything else still holds."""
    config = copy.deepcopy(setup[2]["config"])
    config["placement"] = "replicated"
    _, result, lines = drive(setup, config)
    assert result["correct"] is False
    bad = [ln for ln in lines if "NOT OK" in ln]
    assert len(bad) == 1 and "shards_misplaced" in bad[0], lines


def test_the_program_file_refuses_one_chip_and_too_few_devices(setup):
    _, _, cell = setup
    program = cells.load_program(cell["config"])
    with pytest.raises(ValueError):
        program.Program(cell["config"], cell["traffic"], 1, n=N,
                        persistent_cache=False)
    prog = program.Program(cell["config"], cell["traffic"], 64, n=N,
                           persistent_cache=False)
    with pytest.raises(ValueError):
        prog.init(1)


# -- the collective metric on a made-up trace ---------------------------------

def test_collective_ms_per_tick_on_a_made_up_trace():
    bench = cells.load_benchmark()
    name = mesh_cell_name(bench)
    read = dict((m["name"], r) for m, r in cells.metrics_for(
        bench, name, "per_layer"))["collective_ms_per_tick"]
    ms = 1e6

    def device(extra):
        # a while loop over two ticks; in each a fusion, an all-gather
        # that holds an inner event (only the leaf counts), an
        # all-reduce, and on one device a longer wait in it
        ops = [("while.1", 0.0, 40 * ms)]
        for t in (0.0, 20 * ms):
            ops += [("fusion.7 u32[4096]", t + 0 * ms, 8 * ms),
                    ("all-gather-start.3 u32[16384,5]", t + 8 * ms, 3 * ms),
                    ("all-gather-done.3 u32[16384,5]", t + 11 * ms,
                     1 * ms),
                    ("all-reduce.9 s64[]", t + 12 * ms, (2 + extra) * ms),
                    ("fusion.8 s32[4096,8]", t + 16 * ms, 4 * ms)]
        return {trace_reduce.OPS_LINE: ops,
                trace_reduce.MODULES_LINE: [("jit_run", 0.0, 40 * ms)]}

    trace = {"devices": {0: device(0), 1: device(0), 2: device(2),
                         3: device(0)},
             "host": [("bench.dispatch", 0.0, 41 * ms)]}
    tr = trace_reduce.reduce_trace(trace, CHIPS)
    # the busiest device: 2 ticks x (3 + 1 + 4) ms
    assert tr["collective_s"] == pytest.approx(16e-3)
    rec = {"trace": tr, "traced": [(0.0, 0.02), (0.02, 0.04)],
           "ticks_per_dispatch": 1}
    assert read(rec) == pytest.approx(8.0)
    assert read(dict(rec, ticks_per_dispatch=4)) == pytest.approx(2.0)
    # nothing to read: no trace, an untraced run, a reduction without it
    assert read({"traced": []}) is None
    assert read(dict(rec, traced=[])) is None
    assert read(dict(rec, trace={"busy_s": 1.0})) is None
