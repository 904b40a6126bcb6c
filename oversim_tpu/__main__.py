"""Command-line entry point: ``python -m oversim_tpu -f file.ini -c Config``.

Equivalent of the reference's ``src/OverSim -f omnetpp.ini -cConfigName``
binary (Makefile:31-40): loads an OMNeT++-style ini, builds the scenario
(config/scenario.py), runs the simulation for the configured init +
transition + measurement phases, and prints GlobalStatistics-style
scalars (``name.mean/.stddev/.min/.max``, GlobalStatistics.cc:107-145).

``${...}`` parameter studies expand into a run matrix like OMNeT++ run
numbers (thesis.ini:16); ``-r N`` picks one run, ``--all-runs`` sweeps.
"""

from __future__ import annotations

import argparse
import json
import sys


def _fmt_scalars(label: str, out: dict) -> str:
    lines = []
    if label:
        lines.append(f"# run {label}")
    for name, v in sorted(out.items()):
        if name.startswith("_"):
            continue
        if isinstance(v, dict):
            for k in ("mean", "stddev", "min", "max", "count"):
                lines.append(f"scalar {name}.{k}\t{v[k]}")
        elif isinstance(v, list):
            lines.append(f"histogram {name}\t{v}")
        else:
            lines.append(f"scalar {name}\t{v}")
    eng = out.get("_engine", {})
    for k, v in sorted(eng.items()):
        lines.append(f"scalar engine.{k}\t{v}")
    lines.append(f"scalar sim.time\t{out.get('_t_sim', 0.0)}")
    lines.append(f"scalar sim.ticks\t{out.get('_ticks', 0)}")
    lines.append(f"scalar sim.aliveNodes\t{out.get('_alive', 0)}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m oversim_tpu",
        description="TPU-native OverSim: run a .ini scenario")
    ap.add_argument("-f", "--ini", required=True, help="ini file path")
    ap.add_argument("-c", "--config", default="General",
                    help="[Config X] section name")
    ap.add_argument("-r", "--run", type=int, default=None,
                    help="parameter-study run number")
    ap.add_argument("--all-runs", action="store_true",
                    help="sweep the whole parameter-study matrix")
    ap.add_argument("--until", type=float, default=None,
                    help="simulated seconds to run (default: init + "
                         "transition + measurement, or 600)")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--trace", default=None,
                    help="trace file driving joins/leaves + PUT/GET + "
                         "partitions (GlobalTraceManager format, e.g. "
                         "simulations/dht.trace)")
    ap.add_argument("--json", action="store_true",
                    help="print one JSON object per run instead of scalars")
    ap.add_argument("--output-vectors", default=None, metavar="FILE.vec",
                    help="record counter time series into an "
                         "OMNeT++-format .vec file (vector-recording)")
    ap.add_argument("--output-scalars", default=None, metavar="FILE.sca",
                    help="write finish()-time scalars into an "
                         "OMNeT++-format .sca file")
    ap.add_argument("--vector-interval", type=float, default=10.0,
                    help="sampling period for --output-vectors (sim s)")
    ap.add_argument("--platform", default=None,
                    help="jax platform override (e.g. cpu); default keeps "
                         "the device jax finds (the TPU when present)")
    args = ap.parse_args(argv)

    if args.platform:
        sys.modules.setdefault("zstandard", None)
        import jax
        jax.config.update("jax_platforms", args.platform)

    from oversim_tpu.config.ini import IniFile
    from oversim_tpu.config.scenario import build_simulation

    trace_events = None
    if args.trace:
        from oversim_tpu.trace import parse_trace
        trace_events = parse_trace(args.trace)

    ini = IniFile.load(args.ini)
    runs = list(ini.expand_study_runs(args.config))
    if args.run is not None:
        if not 0 <= args.run < len(runs):
            print(f"run {args.run} out of range (0..{len(runs) - 1})",
                  file=sys.stderr)
            return 2
        runs = [runs[args.run]]
    elif not args.all_runs:
        runs = runs[:1]

    for label, config in runs:
        sim = build_simulation(ini, config, trace_events=trace_events)
        state = sim.init(seed=args.seed)
        horizon = args.until
        if horizon is None:
            meas = sim.ep.measurement_time
            horizon = (sim.cp.init_finished_time + sim.ep.transition_time
                       + (meas if meas and meas > 0 else 600.0))
        if args.output_vectors:
            from oversim_tpu.recorder import VectorRecorder
            rec = VectorRecorder(sim, args.output_vectors,
                                 run_id=f"{config}-{label}")
            state = rec.run(state, horizon,
                            sample_every=args.vector_interval)
            rec.close()
        else:
            state = sim.run_until(state, horizon)
        out = sim.summary(state)
        if args.output_scalars:
            from oversim_tpu.recorder import write_scalars
            write_scalars(sim, state, args.output_scalars,
                          run_id=f"{config}-{label}")
        if args.json:
            print(json.dumps({"run": label, **out}))
        else:
            print(_fmt_scalars(label, out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
