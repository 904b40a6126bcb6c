"""Finding a cell's files by the names in ``BENCHMARK.json``.

A cell is ``<config>.<traffic>``.  Its configuration is the file the
``configs`` entry names (ini text, engine sizes, ticks per dispatch,
limits, the name of its plain reference), its traffic mix
``traffic/<traffic>.json`` (numeric ini overrides, the test interval the
reference holds the program to), and each metric ``metrics/<name>.py``
(a reader of its own).  A configuration may name its own program file
(``load_program``).  A later PR adds
files and entries; nothing here names a cell, a mix or a metric.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class CellError(Exception):
    pass


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_traffic(name: str, here: str = HERE) -> dict:
    return _load_json(os.path.join(here, "traffic", name + ".json"))


def find_cell(bench: dict, workload: str, root: str = ROOT,
              here: str = HERE) -> dict:
    """Everything one cell is made of, by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {workload!r} names an unknown "
                        f"config {w['config']!r}")
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_traffic(w["traffic"], here)
    reference = load_module(
        os.path.join(here, "reference", config["reference"] + ".py"),
        "benchmark_reference_" + config["reference"])
    return {"name": workload, "chips": int(w["chips"]), "config": config,
            "traffic": traffic, "reference": reference}


def load_program(config: dict, here: str = HERE):
    """The file that drives the system under test for this
    configuration: ``program.py``, or ``<name>.py`` beside it where the
    configuration names one under ``"program"`` (a deployment across
    chips brings its own)."""
    name = config.get("program", "program")
    return load_module(os.path.join(here, name + ".py"),
                       "benchmark_program_" + name)


def metrics_for(bench: dict, workload: str, group: str,
                here: str = HERE) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports,
    each with its reader: ``[(entry, read), ...]``."""
    out = []
    for m in bench[group]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        mod = load_module(os.path.join(here, "metrics", m["name"] + ".py"),
                          "benchmark_metric_" + m["name"].replace(".", "_"))
        out.append((m, mod.read))
    return out


def peaks_for(kind: str, here: str = HERE) -> dict:
    table = _load_json(os.path.join(here, "peaks.json"))["devices"]
    if kind not in table:
        raise CellError(f"device kind {kind!r} is not in peaks.json; "
                        "add its published peaks with their source")
    return table[kind]
