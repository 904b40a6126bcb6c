"""One run of one cell: set-up, the timed window, the comparison.

``run_cell`` is what ``run.py`` calls once, what ``sweep.py`` calls for
many seeds in one process, and what the tests call with the timed path
broken underneath.  It names no cell: everything particular comes in as
the cell's files (``cells.find_cell``).

Set-up (counted in ``setup_s``): ``init(seed)``, the first dispatch
(compile or cache load), the fill and the settling, the read-back of the
opening's counters, and one warm-up of the read-back the window makes.
The window then repeats ONE dispatch of ``ticks_per_dispatch`` ticks of
the same compiled program until ``seconds`` of wall time have passed,
and stops on a dispatch boundary.  After each dispatch it copies the
message pool and the four KBR counters to the host (a few MB): that
read-back is part of the window and of every rate.  The comparison with
the plain reference runs once the window has closed, the peak has been
read and the state is freed.

Both rates, ``tick_ms`` and every number of ``correct`` are read over
the whole window.  The result line's ``attempted`` and ``failed`` are
not: they count the window's first ``failures_over_sim_s`` simulated
seconds (the configuration's; ``window.counted_stretch``), the same
lookups on one seed however far a tree's window reaches, so that a
faster tree is not held against failures its parent never got to.
"""

from __future__ import annotations

import shutil
import time

import window as window_mod

TRACED_DISPATCHES = 2


def run_cell(prog, cell: dict, seed: int, seconds: float, *, t_proc: float,
             trace_dir: str | None = None, say=print) -> dict:
    """Drive ``prog`` (a ``program.Program`` or a stand-in with its
    methods) through one run.  Returns the record the metric readers and
    the verdict read."""
    clock = time.perf_counter
    config, traffic = cell["config"], cell["traffic"]
    chips = cell["chips"]
    spans = {}

    # -- set-up -----------------------------------------------------------
    t0 = clock()
    s = prog.init(seed)
    spans["init_s"] = clock() - t0
    n_before = len(prog.compiles)
    t1 = clock()
    s = prog.run_to(s, 1)                     # one dispatch: compiles
    t2 = clock()
    # the run's own baseline: a process may hold tick programs of other
    # simulations (a sweep's earlier seeds, a test's); what this run may
    # not do is compile another after its first dispatch
    programs_before = prog.tick_programs() - 1
    spans["first_dispatch_s"] = t2 - t1
    big = [c for c in prog.compiles[n_before:] if c >= 1.0]
    spans["compile_s"] = sum(big)
    opening_ns = int(round((prog.fill_s + config["settle_s"]) * 1e9))
    s = prog.run_to(s, opening_ns)            # fill + settling
    opening = prog.counters(s)
    state_bytes = prog.state_bytes(s)
    prog.payloads(s)                          # warm the window's read-back
    t3 = clock()
    spans["fill_s"] = t3 - t2
    spans["setup_s"] = t3 - t_proc
    say(f"set-up {spans['setup_s']:.1f} s: init {spans['init_s']:.1f} "
        f"first dispatch {spans['first_dispatch_s']:.1f} (backend compile "
        f"{spans['compile_s']:.1f} s in {len(big)}) fill+settle "
        f"{spans['fill_s']:.1f}; opening at sim "
        f"{opening['t_now_ns'] / 1e9:.1f} s, tick {opening['tick']}, "
        f"state {state_bytes} bytes")

    # -- the window -------------------------------------------------------
    import jax
    span = jax.profiler.TraceAnnotation   # host spans in the profiler's trace
    dispatches, snaps = [], []
    tracing = False
    traced = []
    after_profiler = set()      # dispatches whose gap before them holds
                                # the profiler's own start or stop
    t_open = clock()
    t_now = opening["t_now_ns"]
    while True:
        if (trace_dir is not None and not tracing and not traced
                and len(dispatches) == 1):
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            tracing = True
            after_profiler.add(len(dispatches))
        with span("bench.dispatch"):
            c0 = clock()
            s = prog.run_to(s, t_now + 1)     # exactly one dispatch
            c1 = clock()
        with span("bench.readback"):
            snap = prog.payloads(s)
        t_now = snap["t_now_ns"]
        dispatches.append((c0, c1))
        snaps.append(snap)
        if tracing:
            traced.append((c0, c1))
            if len(traced) == TRACED_DISPATCHES:
                jax.profiler.stop_trace()
                tracing = False
                after_profiler.add(len(dispatches))
        if c1 - t_open >= seconds and not tracing:
            break
    close = prog.counters(s)
    tables = prog.tables(s)
    peaks = prog.memory_peaks()               # one per chip used
    peak = max((p for p in peaks if p is not None), default=None)
    programs = prog.tick_programs() - programs_before
    del s                                     # the state is freed

    rates = window_mod.window_rates(opening, close, dispatches, t_open,
                                    skip_gaps_before=after_profiler)
    look = rates["lookups"]
    stretch = window_mod.counted_stretch(opening, snaps, close,
                                         config["failures_over_sim_s"])
    tenths = window_mod.by_tenth(opening, snaps)
    took = sorted(done - call for call, done in dispatches)
    mid = took[len(took) // 2]
    say(f"dispatch seconds: least {took[0]:.4f} median "
        f"{mid:.4f} greatest {took[-1]:.4f}")
    # the same device work took longer: the host was paused over the
    # dispatch's end (PERF.md section 2); it is in every rate
    late = [(i, done - call - mid) for i, (call, done)
            in enumerate(dispatches) if done - call > 1.005 * mid]
    if late:
        say("dispatches over 1.005 x the median: " + ", ".join(
            f"#{i} +{x:.4f} s" for i, x in late))
    say(f"window {rates['wall_s']:.2f} s: {len(dispatches)} dispatches, "
        f"{rates['ticks']} ticks, {rates['sim_s']:.2f} sim-s; sent "
        f"{look['sent']} ended {look['attempted']} delivered "
        f"{look['delivered']} in flight {look['in_flight_open']} -> "
        f"{look['in_flight_close']}; tick programs {programs}; "
        f"peak_bytes_in_use {peaks}")
    if not stretch["reached"]:
        say(f"counted stretch: reached {stretch['sim_s']:.1f} of "
            f"{stretch['over_sim_s']} simulated seconds")
    say(f"counted stretch: {stretch['over_sim_s']} sim-s, "
        f"{stretch['dispatches']} dispatches, ended {stretch['attempted']} "
        f"failed {stretch['failed']}; whole window ended "
        f"{look['attempted']} failed {look['failed']}")
    say("failed by tenth: " + " ".join(map(str, tenths["failed"]))
        + "; ended by tenth: " + " ".join(map(str, tenths["ended"])))

    # -- the comparison ---------------------------------------------------
    t4 = clock()
    evidence = {"opening": opening, "close": close, "tables": tables,
                "snaps": snaps, "wire": prog.wire()}
    readings, rows = judge(cell, evidence, interval_ns_of(traffic),
                           len(dispatches), seed, programs)
    spans["reference_s"] = clock() - t4
    return {"spans": spans, "rates": rates, "readings": readings,
            "rows": rows, "correct": all(r[4] for r in rows) and bool(rows),
            # the result line's: over the counted stretch
            "attempted": stretch["attempted"], "failed": stretch["failed"],
            "stretch": stretch, "failed_by_tenth": tenths,
            "window_attempted": look["attempted"],
            "window_failed": look["failed"],
            "peak_bytes": peak, "state_bytes": state_bytes, "chips": chips,
            "traced": traced, "dispatches": len(dispatches),
            "ticks_per_dispatch":
            int(config["ticks_per_dispatch"]), "programs": programs,
            # kept for the control (sweep.py) and the tests
            "evidence": evidence}


def interval_ns_of(traffic: dict) -> int:
    """The interval between a node's tests as the traffic file states
    it, not as the program holds it."""
    return int(round(float(traffic["test_interval_s"]) * 1e9))


def judge(cell: dict, evidence: dict, interval_ns: int, dispatches: int,
          seed: int, programs: int):
    """The reference's readings of what a window left, each held to the
    configuration's limit: ``(readings, rows)``."""
    ref, config = cell["reference"], cell["config"]
    readings = ref.readings(
        evidence["opening"], evidence["close"], evidence["tables"],
        evidence["snaps"], config=config, wire=evidence["wire"],
        interval_ns=interval_ns,
        ticks_per_dispatch=int(config["ticks_per_dispatch"]),
        dispatches=dispatches, seed=seed)
    readings["tick_programs_extra"] = programs - 1
    limits = {k: tuple(v) for k, v in config["limits"].items()}
    return readings, ref.compare(readings, limits)


def verdict_lines(rec: dict) -> list:
    """Each number compared beside its limit, one line each."""
    out = []
    for name, value, how, limit, ok in rec["rows"]:
        sign = "<=" if how == "max" else ">="
        out.append(f"compare {name} = {value} (limit {sign} {limit}) "
                   f"{'ok' if ok else 'NOT OK'}")
    shown = {r[0] for r in rec["rows"]}
    rest = {k: v for k, v in rec["readings"].items() if k not in shown}
    out.append("readings not held to a limit: " + ", ".join(
        f"{k} {v}" for k, v in rest.items()))
    out.append(f"correct {rec['correct']} (reference "
               f"{rec['spans']['reference_s']:.1f} s)")
    return out
