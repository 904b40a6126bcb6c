"""A configuration, a traffic mix and a metric come in as new files and
new entries; no file that is there is edited."""

import json
import os
import shutil

import pytest

import cells
from conftest import BENCH, ROOT


@pytest.fixture()
def copy(tmp_path):
    """A copy of BENCHMARK.json and the benchmark's directory."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    return tmp_path


def digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hash(fh.read())
    return out


def test_every_cell_of_the_benchmark_is_found_by_name():
    bench = cells.load_benchmark()
    for w in bench["workloads"]:
        cell = cells.find_cell(bench, w["name"])
        assert cell["chips"] == w["chips"]
        assert cell["config"]["name"] == w["config"]
        assert cell["traffic"]["name"] == w["traffic"]
        assert hasattr(cell["reference"], "readings")
        for group in ("end_to_end", "per_layer"):
            got = cells.metrics_for(bench, w["name"], group)
            assert got, (w["name"], group)
            assert all(callable(read) for _, read in got)
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}


def test_new_cell_and_metric_are_new_files_and_entries_only(copy):
    root, here = str(copy), str(copy / "benchmark")
    before = digest(here)
    # a configuration, a traffic mix and a per-layer metric: new files
    cfg = json.load(open(os.path.join(here, "configs", "kademlia4096.json")))
    cfg["name"] = "kademlia1024"
    cfg["nodes"] = 1024
    cfg["ticks_per_dispatch"] = 4
    json.dump(cfg, open(os.path.join(here, "configs", "kademlia1024.json"),
                        "w"))
    json.dump({"name": "kbr1", "test_interval_s": 1.0,
               "overrides": {"**.tier1*.kbrTestApp.testMsgInterval": 1.0}},
              open(os.path.join(here, "traffic", "kbr1.json"), "w"))
    with open(os.path.join(here, "metrics", "ticks_per_lookup.py"),
              "w") as f:
        f.write("def read(rec):\n"
                "    n = rec['rates']['lookups']['delivered']\n"
                "    return rec['rates']['ticks'] / n if n else None\n")
    # ... and new entries in BENCHMARK.json
    bench = cells.load_benchmark(root)
    bench["configs"].append({"name": "kademlia1024", "source": "x",
                             "file": "benchmark/configs/kademlia1024.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "kademlia1024.kbr1",
                               "config": "kademlia1024", "traffic": "kbr1",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "ticks_per_lookup", "unit": "ticks",
                               "better": "lower", "source": "program_counter",
                               "layer": "run loop", "moves": "lookups_per_s",
                               "workloads": ["kademlia1024.kbr1"]})
    cell = cells.find_cell(bench, "kademlia1024.kbr1", root=root, here=here)
    assert cell["config"]["nodes"] == 1024
    assert cell["config"]["ticks_per_dispatch"] == 4
    assert cell["traffic"]["test_interval_s"] == 1.0
    names = [m["name"] for m, _ in cells.metrics_for(
        bench, "kademlia1024.kbr1", "per_layer", here=here)]
    assert "ticks_per_lookup" in names
    read = dict((m["name"], r) for m, r in cells.metrics_for(
        bench, "kademlia1024.kbr1", "per_layer", here=here))
    rec = {"rates": {"ticks": 80, "lookups": {"delivered": 40}}}
    assert read["ticks_per_lookup"](rec) == 2.0
    # the old cells do not report the new metric, and no old file changed
    old = [m["name"] for m, _ in cells.metrics_for(
        bench, "kademlia4096.kbr60", "per_layer", here=here)]
    assert "ticks_per_lookup" not in old
    after = digest(here)
    assert all(after[k] == v for k, v in before.items())


def test_run_py_names_no_cell():
    names = set()
    bench = cells.load_benchmark()
    for group in ("configs", "workloads"):
        names |= {e["name"] for e in bench[group]}
    names |= {w["traffic"] for w in bench["workloads"]}
    for f in ("run.py", "cellrun.py", "cells.py", "window.py",
              "trace_reduce.py", "program.py", "sweep.py"):
        text = open(os.path.join(BENCH, f)).read()
        hits = [n for n in names if n in text]
        assert not hits, (f, hits)


def test_unknown_names_are_errors():
    bench = cells.load_benchmark()
    with pytest.raises(cells.CellError):
        cells.find_cell(bench, "no.such")
    with pytest.raises(cells.CellError):
        cells.peaks_for("TPU v99")
    assert cells.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_one_parser_serves_the_run_the_sweep_and_the_control():
    import run
    one = run.parse(["--workload", "w", "--seed", "2200000001",
                     "--seconds", "51", "--trace", "1"])
    assert (one.seed, one.seeds, one.trace, one.changed) == (
        2200000001, None, 1, {})
    many = run.parse(["--workload", "w", "--seconds", "10", "--seeds", "1",
                      "2", "--set", "engine.outbox_slots=1", "--ini",
                      "**.x=120.0"])
    assert many.seeds == [1, 2] and many.seed is None
    assert many.changed == {"engine.outbox_slots": 1,
                            "ini_overrides": {"**.x": 120.0}}
    for argv in (["--workload", "w", "--seconds", "1"],
                 ["--workload", "w", "--seconds", "1", "--seed", "1",
                  "--seeds", "2"]):
        with pytest.raises(SystemExit):
            run.parse(argv)
    # the sweep has no command of its own
    text = open(os.path.join(BENCH, "sweep.py")).read()
    assert "argparse" not in text and "__main__" not in text
