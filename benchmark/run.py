"""The benchmark's command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run.  Refuses anything but a TPU with the
cell's number of chips.  The last line of standard output is the one
JSON object of the contract; every number compared stands beside its
limit on earlier lines and, as the last lines, on standard error.

The builder's tools ride on the same arguments and the same refusal, so
that no second entry point parses or looks for a chip:

``--rehearsal N``: the same code at N nodes on whatever backend is
there.  A rehearsal's last line has ``"correct": false`` and
``"rehearsal": true`` and its exit code is 3, so nothing can take it for
a chip's result.

``--seeds a b c`` (with ``--out file``): many seeds of the cell in this
one process, the lower-precision control read beside each (``sweep.py``).

``--set key=json`` / ``--ini key=json``: a control run, the program
itself with one guarantee of its configuration broken (an engine size,
an ini key); its last line says ``"control": true`` and its exit code is
never 0.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()        # the process's start, for setup_s

import argparse                      # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import sys                           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import shutil                        # noqa: E402
import tempfile                      # noqa: E402

import cells                         # noqa: E402
import cellrun                       # noqa: E402
import trace_reduce as trace_mod     # noqa: E402


def say(msg: str) -> None:
    print("bench: " + msg, flush=True)


def drive(bench, cell, prog, args, device, peaks):
    """The rest of a run once the chip has been looked for: the cell's
    run, the trace's reduction, the metrics by their readers, the
    verdict.  Returns the result object and the verdict's lines."""
    rehearsal = args.rehearsal is not None
    traced = bool(args.trace)
    # the profiler's dump is no cache: it goes under $TMPDIR and is
    # removed once it has been reduced
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    try:
        rec = cellrun.run_cell(prog, cell, args.seed, args.seconds,
                               t_proc=T_PROC, trace_dir=trace_dir, say=say)
        rec["peaks"] = peaks
        if traced and rehearsal and device["platform"] != "tpu":
            say("rehearsal: a CPU trace has no device plane; nothing "
                "reduced")
            traced = False
        if traced:
            rec["trace"] = trace_mod.reduce_trace(
                trace_mod.load_xplane(trace_mod.find_xplane(trace_dir)),
                cell["chips"])
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    if traced:
        say("trace: " + json.dumps({k: v for k, v in rec["trace"].items()
                                    if k not in ("device_ops", "idle_gaps")}))

    group = "per_layer" if traced else "end_to_end"
    metrics = {}
    for entry, read in cells.metrics_for(bench, args.workload, group):
        value = read(rec)
        if value is None:
            continue                # nothing to read: left out of the line
        metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
        say(f"metric {entry['name']} = {value} {entry['unit']}")

    lines = cellrun.verdict_lines(rec)
    for line in lines:
        say(line)
    device_out = dict(device,
                      memory_peak_bytes=rec["peak_bytes"])
    result = {"correct": rec["correct"] and not rehearsal,
              "attempted": rec["attempted"], "failed": rec["failed"],
              "metrics": metrics, "device": device_out}
    if traced:
        device_out["busy_s"] = rec["trace"]["busy_s"]
        device_out["window_s"] = rec["trace"]["window_s"]
        result["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                               "idle_gaps": rec["trace"]["idle_gaps"]}
    if rehearsal:
        result["rehearsal"] = True
        result["rehearsal_correct"] = rec["correct"]
    return result, lines


def open_cell(workload: str, rehearsal: int | None,
              changed: dict | None = None):
    """The cell's files, its program and the device record, or None
    where the machine is not the one the cell asks for: anything but a
    TPU with the cell's chips (a rehearsal only needs the devices).  The
    one place that refuses a machine.  ``changed`` is the builder's, for
    a control run: dotted keys of the configuration set to other values
    (``--set``), and under ``ini_overrides`` ini keys set to other
    values (``--ini``)."""
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, workload)
    changed = dict(changed or {})
    more_ini = changed.pop("ini_overrides", None)
    if more_ini:
        cell["traffic"] = dict(cell["traffic"], overrides=dict(
            cell["traffic"]["overrides"], **more_ini))
    for dotted, value in changed.items():
        at = cell["config"]
        *path, last = dotted.split(".")
        for part in path:
            at = at[part]
        at[last] = value
    # the file that imports the system under test: program.py, or the one
    # a configuration brings and names
    program = cells.load_program(cell["config"])
    prog = program.Program(cell["config"], cell["traffic"], cell["chips"],
                           n=rehearsal, persistent_cache=rehearsal is None)
    device = prog.device_record()
    say(f"device {json.dumps(device)}; compile cache {prog.cache_dir}")
    if rehearsal is None and (device["platform"] != "tpu"
                              or device["count"] < cell["chips"]):
        print(f"bench: {workload} needs {cell['chips']} TPU chip(s); "
              f"jax.devices() reports {device}; refusing to run",
              file=sys.stderr)
        return None
    if device["count"] < cell["chips"]:
        print(f"bench: rehearsal needs {cell['chips']} devices "
              "(XLA_FLAGS=--xla_force_host_platform_device_count=4)",
              file=sys.stderr)
        return None
    return bench, cell, prog, device


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    tools = ap.add_argument_group("the builder's tools, never the driver's")
    tools.add_argument("--rehearsal", type=int, default=None, metavar="N")
    tools.add_argument("--seeds", type=int, nargs="+", default=None)
    tools.add_argument("--out", default=None, metavar="FILE",
                       help="with --seeds: the per-seed table as JSON")
    tools.add_argument("--set", nargs="*", default=[], metavar="KEY=JSON",
                       help="a control run: dotted configuration keys set "
                       "to other values, e.g. engine.outbox_slots=1")
    tools.add_argument("--ini", nargs="*", default=[], metavar="KEY=JSON",
                       help="a control run: ini keys set to other values, "
                       "e.g. **.overlay.kademlia.lookupRedundantNodes=1")
    args = ap.parse_args(argv)
    if (args.seed is None) == (args.seeds is None):
        ap.error("give --seed <n> (one run) or --seeds <n> ... (a sweep)")
    args.changed = {}
    for pair in args.set:
        key, _, value = pair.partition("=")
        args.changed[key] = json.loads(value)
    for pair in args.ini:
        key, _, value = pair.partition("=")
        args.changed.setdefault("ini_overrides", {})[key] = json.loads(value)
    return args


def main(argv=None) -> int:
    args = parse(argv)
    opened = open_cell(args.workload, args.rehearsal, args.changed)
    if opened is None:
        return 2
    bench, cell, prog, device = opened
    rehearsal = args.rehearsal is not None
    control = bool(args.changed)
    if control:
        say("A CONTROL RUN, the configuration changed: "
            + json.dumps(args.changed))
    say(f"cell {args.workload}: n={prog.n} fill {prog.fill_s:.1f} s "
        f"settle {cell['config']['settle_s']} s chips {cell['chips']} "
        f"ticks/dispatch {prog.chunk} seconds {args.seconds} "
        f"trace {args.trace}")
    if args.seeds is not None:
        import sweep
        return sweep.sweep(cell, prog, device, args, say)

    peaks = None if rehearsal else cells.peaks_for(device["kind"])
    result, lines = drive(bench, cell, prog, args, device, peaks)
    if control:
        result["control"] = True
        result["control_correct"] = result["correct"]
        result["correct"] = False
    sys.stdout.flush()
    for line in lines:
        print("bench: " + line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 3 if rehearsal or control else 0


if __name__ == "__main__":
    sys.exit(main())
