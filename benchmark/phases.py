"""Where a tick's device time goes, phase by phase: the builder's and an
operator's tool, never the driver's.

    python3 benchmark/phases.py --workload <name> --seed <n> --seconds <s>
                                [--rehearsal N] [--out FILE]

One run of the cell exactly as ``run.py --trace 1`` makes it (the same
arguments through ``run.parse``, the one refusal of a machine in
``run.open_cell``, the cell's own timed path in ``cellrun.run_cell``
with the profiler on over two dispatches), with the profiler's dump kept
until ``phase_reduce.py`` has reduced it by the tick program's named
scopes (``oversim_tpu/core/scopes.py``): device ms, leaf operations and
the median leaf of every phase and part, the idle inside program runs by
``while`` / ``cond`` boundary, the idle between them by host event.  It
prints the busiest device's table and, as its last line, one JSON
object; ``--out`` keeps every device's tables.  Exit code 3, like a
rehearsal's: nothing here is a result of the benchmark.

Any other dump, ``OVERSIM_XPROF``'s included:
``python3 benchmark/phase_reduce.py <dir>``.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()

import json                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import sys                           # noqa: E402
import tempfile                      # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import cellrun                       # noqa: E402
import phase_reduce                  # noqa: E402
import run as bench_run              # noqa: E402
import trace_reduce                  # noqa: E402

say = bench_run.say


def main(argv=None) -> int:
    args = bench_run.parse(argv)
    if args.seed is None or args.changed:
        print("phases: one cell, one seed, the configuration as it is",
              file=sys.stderr)
        return 2
    opened = bench_run.open_cell(args.workload, args.rehearsal)
    if opened is None:
        return 2
    _, cell, prog, device = opened
    trace_dir = tempfile.mkdtemp(prefix="bench_phases_")
    try:
        rec = cellrun.run_cell(prog, cell, args.seed, args.seconds,
                               t_proc=T_PROC, trace_dir=trace_dir, say=say)
        say(cellrun.verdict_lines(rec)[-1])
        ticks = len(rec["traced"]) * rec["ticks_per_dispatch"]
        if device["platform"] != "tpu":
            say("a CPU trace has no device plane; nothing reduced")
            return 3
        path = trace_reduce.find_xplane(trace_dir)
        trace = phase_reduce.load(path)
        result = phase_reduce.reduce_trace(trace, ticks)
        # the benchmark's own reduction of the same events, beside it
        plain = {d: trace_reduce.reduce_device(
            {name: [e[:3] for e in events] for name, events in lines.items()})
            for d, lines in trace["devices"].items()
            if lines.get(trace_reduce.OPS_LINE)}
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    rates = rec["rates"]
    result.update(
        workload=args.workload, seed=args.seed, device=device,
        correct=rec["correct"], ticks=ticks,
        traced_dispatch_s=[done - call for call, done in rec["traced"]],
        tick_ms_window=rates["tick_ms"],
        trace_reduce_busy_s={str(d): r["busy_ns"] / 1e9
                             for d, r in plain.items()})
    phase_reduce.show(result, say)
    for d, table in result["devices"].items():
        rows = sum(r["device_s"] for r in table["phases"].values())
        say(f"device {d}: phases sum to {rows:.6f} s, trace_reduce's busy "
            f"{result['trace_reduce_busy_s'][d]:.6f} s, "
            f"{table['scoped_share']:.2%} under a phase")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f)
    table = result["devices"][result["busiest"]]
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "ticks": ticks,
        "correct": rec["correct"], "device": device,
        "busy_ms_per_tick": 1e3 * table["busy_s"] / ticks,
        "leaf_ops_per_tick": table["leaf_ops"] / ticks,
        "scoped_share": table["scoped_share"],
        "in_program_idle_ms_per_tick":
        1e3 * table["in_program_idle_s"] / ticks,
        "phase_ms_per_tick": {k: 1e3 * r["device_s"] / ticks
                              for k, r in table["phases"].items()}}),
        flush=True)
    return 3


if __name__ == "__main__":
    sys.exit(main())
