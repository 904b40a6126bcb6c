"""Many seeds of one cell in one process, the control beside each.

The builder's tool for what ``PERF.md`` has to show before a cell is let
in: for every seed, every number the verdict rests on (the per-seed
table), and the control's reading of the same window (the reference in
the precision below the configuration's, put in the program's place),
which has to come out as not correct.  One process pays the compile and
the imports once; each seed still pays its own init, fill and settling.

It has no command of its own: ``run.py`` parses the arguments, opens the
cell and refuses a machine, and hands over here when ``--seeds`` is
given:

    python3 benchmark/run.py --workload <name> --seeds 1 2 3 --seconds 10 --out chiprun_out/sweep.json

With ``--set``/``--ini`` the sweep is a control run of another kind: the
program itself with one guarantee of the configuration broken, which has
to come out as not correct too:

    ... --set engine.outbox_slots=1
    ... --ini '**.overlay.kademlia.lookupRedundantNodes=1'
"""

from __future__ import annotations

import json
import os
import time

import cellrun


def control_of(rec: dict, cell: dict, seed: int):
    """The same window read with the control in the program's place."""
    ev = rec["evidence"]
    interval_ns = cellrun.interval_ns_of(cell["traffic"])
    close2, snaps2 = cell["reference"].control(
        ev["opening"], ev["close"], ev["tables"], ev["snaps"],
        config=cell["config"], wire=ev["wire"], interval_ns=interval_ns)
    return cellrun.judge(cell, dict(ev, close=close2, snaps=snaps2),
                         interval_ns, rec["dispatches"], seed,
                         rec["programs"])


def sweep(cell: dict, prog, device: dict, args, say) -> int:
    """``args.seeds`` one after another through ``cellrun.run_cell``.
    Exit code 0 only where every seed is correct and the control failed
    on every seed."""
    table = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        rec = cellrun.run_cell(prog, cell, seed, args.seconds, t_proc=t0,
                               say=say)
        c_read, c_rows = control_of(rec, cell, seed)
        failed_by_control = [r[0] for r in c_rows if not r[4]]
        row = {"seed": seed, "correct": rec["correct"],
               "readings": rec["readings"],
               "not_ok": [r[0] for r in rec["rows"] if not r[4]],
               "control_correct": not failed_by_control,
               "control_fails": failed_by_control,
               "control_readings": {k: c_read[k] for k in
                                    ("timer_off_lattice", "delay_early_ns")
                                    if k in c_read},
               # over the counted stretch, and over the whole window
               "stretch": rec["stretch"],
               "window_attempted": rec["window_attempted"],
               "window_failed": rec["window_failed"],
               "failed_by_tenth": rec["failed_by_tenth"],
               "rates": {k: v for k, v in rec["rates"].items()
                         if k != "lookups"},
               "spans": rec["spans"], "peak_bytes": rec["peak_bytes"]}
        table.append(row)
        say("seed %d correct %s not_ok %s | control correct %s fails %s %s"
            % (seed, row["correct"], row["not_ok"], row["control_correct"],
               failed_by_control, json.dumps(row["control_readings"])))
        say("readings " + json.dumps(rec["readings"]))
        say("rates " + json.dumps(row["rates"]) + " spans "
            + json.dumps(rec["spans"]))
        del rec
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "device": device,
                       "seconds": args.seconds, "changed": args.changed,
                       "rows": table}, f)
    # the least and the greatest of every reading over the seeds
    names = []
    for r in table:
        names += [k for k, v in r["readings"].items()
                  if isinstance(v, (int, float)) and k not in names]
    for k in names:
        vals = [r["readings"][k] for r in table
                if r["readings"].get(k) is not None]
        lim = cell["config"]["limits"].get(k)
        say(f"{k}: least {min(vals)} greatest {max(vals)} limit {lim}")
    ok = all(r["correct"] for r in table)
    ctl = all(not r["control_correct"] for r in table)
    say(f"all seeds correct {ok}; control failed on every seed {ctl}")
    return 0 if ok and ctl else 1
