"""Compile the bench-shaped sim step and break the optimized HLO down by
opcode — evidence for which op classes dominate the op-issue-bound tick.

The three budget modes below are now THIN SHIMS over the graph-contract
registry (oversim_tpu/analysis/): same positionals, same output lines,
same exit codes (0 ok / 1 breach), with a deprecation note on stderr.
New code should run ``scripts/analyze.py`` instead — it checks the same
budgets as declarative contracts over EVERY compiled entry point, plus
trace-time and AST-lint passes.

Usage:
  python scripts/hlo_breakdown.py [n] [overlay] [window] [inbox]
      Prints instruction counts by opcode inside the scan body, the
      largest sort/scatter/gather shapes, and fusion count.
  python scripts/hlo_breakdown.py --budget [n] [overlay] [window] [inbox]
      Compiles ONE tick and exits non-zero when the HLO exceeds the
      pinned op budget (→ analyze.py solo_tick contract).  Override with
      --max-sorts / --max-scatters.
  python scripts/hlo_breakdown.py --campaign S [n] [overlay] [window] [inbox]
      One vmapped replica-sharded campaign tick; additionally pins ZERO
      cross-replica collectives (→ analyze.py campaign_tick contract).
  python scripts/hlo_breakdown.py --telemetry K [--campaign S] [n] ...
      Telemetry-off vs telemetry-on tick delta (→ analyze.py
      telemetry_tick delta contract): no new sorts, bounded scatter
      delta (--max-scatter-delta, default 64), zero new collectives.

The counting helpers (``hlo_op_counts`` / ``check_budget`` /
``check_telemetry_budget``) live in oversim_tpu/analysis/hlo_text.py and
are re-exported here for back-compat (tests/test_hlo_budget.py and
tests/test_engine.py import them from this module); both homes are import-safe (no jax at module level).
"""

import collections
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from oversim_tpu.analysis.hlo_text import (  # noqa: E402,F401  (back-compat re-exports)
    check_budget,
    check_telemetry_budget,
    hlo_op_counts,
)

T0 = time.time()


def log(msg):
    print(f"[{time.time() - T0:6.1f}s] {msg}", flush=True)


def _deprecation(mode: str, entry: str):
    print(f"note: hlo_breakdown {mode} is a shim over the graph-contract "
          f"registry — prefer `python scripts/analyze.py --hlo "
          f"--entries {entry}` (oversim_tpu/analysis/)",
          file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# CLI: compile + report / budget-check
# ---------------------------------------------------------------------------

def _setup_jax():
    # hostcache.enable owns the shared ritual (zstandard poison, x64,
    # host-keyed persistent compilation cache)
    from oversim_tpu import hostcache
    hostcache.enable(persistent=True)
    import jax
    return jax


def _build_sim(n, overlay, window, inbox, pool_factor=4, telemetry_ticks=0):
    """Back-compat wrapper over the registry's shared sim builder."""
    from oversim_tpu.analysis import contracts as contracts_mod
    ctx = contracts_mod.EntryContext(n=n, overlay=overlay, window=window,
                                     inbox=inbox, pool_factor=pool_factor)
    return contracts_mod.build_sim(ctx, telemetry_ticks=telemetry_ticks)


def _ctx(n, overlay, window, inbox, **kw):
    from oversim_tpu.analysis import contracts as contracts_mod
    return contracts_mod.EntryContext(n=n, overlay=overlay, window=window,
                                      inbox=inbox, pool_factor=4, **kw)


def budget_main(n, overlay, window, inbox, max_sorts, max_scatters) -> int:
    """--budget: shim over the registry's solo_tick entry — compile one
    tick, check the sort/scatter budget, exit non-zero on breach."""
    _deprecation("--budget", "solo_tick")
    _setup_jax()
    from oversim_tpu.analysis import contracts as contracts_mod
    from oversim_tpu.analysis import hlo_pass

    txt, built = hlo_pass.lower_entry(
        contracts_mod.REGISTRY["solo_tick"], _ctx(n, overlay, window, inbox))
    log(f"one-tick HLO compiled: {txt.count(chr(10))} lines")
    if max_scatters is None:
        # measured: kademlia 151 / chord 123 scatters at inbox=8 (mostly
        # per-node logic scatters) — 200 catches gross regressions while
        # the zero-full-pool-sort pin stays the sharp budget
        max_scatters = 200
    ok, counts = check_budget(txt, built.pool_dim, max_sorts, max_scatters)
    print(f"budget: full_pool_sorts {counts['full_pool_sort_count']} "
          f"(max {max_sorts}), scatters {counts['scatter_count']} "
          f"(max {max_scatters}), total sorts {counts['sort_count']} "
          f"-> {'OK' if ok else 'EXCEEDED'}", flush=True)
    return 0 if ok else 1


def campaign_budget_main(n, overlay, window, inbox, replicas, max_sorts,
                         max_scatters) -> int:
    """--campaign S: shim over the registry's campaign_tick entry —
    zero full-pool sorts, bounded scatters, ZERO cross-replica
    collectives."""
    _deprecation("--campaign", "campaign_tick")
    _setup_jax()
    from oversim_tpu.analysis import contracts as contracts_mod
    from oversim_tpu.analysis import hlo_pass

    txt, built = hlo_pass.lower_entry(
        contracts_mod.REGISTRY["campaign_tick"],
        _ctx(n, overlay, window, inbox, replicas=replicas))
    n_dev = built.info["devices"]
    log(f"campaign-tick HLO compiled on {n_dev} device(s): "
        f"{txt.count(chr(10))} lines")
    if max_scatters is None:
        max_scatters = 200   # same rationale as budget_main
    ok, counts = check_budget(txt, built.pool_dim, max_sorts, max_scatters,
                              max_collectives=0)
    print(f"campaign budget (S={replicas}, {n_dev} dev): "
          f"full_pool_sorts {counts['full_pool_sort_count']} "
          f"(max {max_sorts}), scatters {counts['scatter_count']} "
          f"(max {max_scatters}), collectives "
          f"{counts['collective_count']} (max 0), total sorts "
          f"{counts['sort_count']} -> {'OK' if ok else 'EXCEEDED'}",
          flush=True)
    return 0 if ok else 1


def telemetry_budget_main(n, overlay, window, inbox, tel_ticks, replicas,
                          max_sorts, max_scatter_delta) -> int:
    """--telemetry K: shim over the registry's telemetry delta contract
    — compile the tick telemetry-off AND telemetry-on and pin the
    delta.  With --campaign S the compare runs on the replica-sharded
    campaign tick."""
    _deprecation("--telemetry", "solo_tick,telemetry_tick")
    jax = _setup_jax()
    from oversim_tpu.analysis import contracts as contracts_mod

    ctx = _ctx(n, overlay, window, inbox,
               replicas=replicas if replicas is not None else 4)
    sim_off = contracts_mod.build_sim(ctx)
    sim_on = contracts_mod.build_sim(ctx, telemetry_ticks=tel_ticks)
    pool_dim = sim_off.ep.pool_factor * n

    texts = []
    if replicas is not None:
        for sim in (sim_off, sim_on):
            step, make_args, n_dev = contracts_mod._campaign_step(ctx, sim)
            texts.append(step.lower(*make_args()).compile().as_text())
            log(f"campaign tick compiled "
                f"(telemetry={'on' if sim is sim_on else 'off'}, "
                f"S={replicas}, {n_dev} dev)")
        what = f"campaign S={replicas}"
    else:
        for sim in (sim_off, sim_on):
            s = sim.init(seed=7)
            texts.append(jax.jit(sim.step).lower(s).compile().as_text())
            log(f"one-tick HLO compiled "
                f"(telemetry={'on' if sim is sim_on else 'off'})")
        what = "solo tick"

    base = hlo_op_counts(texts[0], pool_dim)
    tel = hlo_op_counts(texts[1], pool_dim)
    ok, delta = check_telemetry_budget(
        base, tel, max_full_pool_sorts=max_sorts,
        max_scatter_delta=max_scatter_delta)
    print(f"telemetry budget ({what}, sampleTicks={tel_ticks}): "
          f"full_pool_sorts {delta['full_pool_sort_count']} "
          f"(max {max_sorts}), sort delta {delta['sort_delta']} (max 0), "
          f"scatter delta {delta['scatter_delta']} "
          f"(max {max_scatter_delta}), collective delta "
          f"{delta['collective_delta']} (max 0) "
          f"-> {'OK' if ok else 'EXCEEDED'}", flush=True)
    return 0 if ok else 1


def breakdown_main(n, overlay, window, inbox) -> int:
    jax = _setup_jax()
    sim = _build_sim(n, overlay, window, inbox)
    s = sim.init(seed=7)
    log("init done")

    lowered = sim.run_chunk.lower(sim, s, 4)
    log("lowered")
    compiled = lowered.compile()
    log("compiled")
    txt = compiled.as_text()
    log(f"text: {len(txt)} chars, {txt.count(chr(10))} lines")

    # find the while-loop body computation (the scan body = one tick)
    # opcode histogram over every computation, plus top-level of body
    op_re = re.compile(
        r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*[\w\[\]{}, ]+\s+(\w+)\(")
    counts = collections.Counter()
    big = collections.Counter()
    cur_comp = None
    comp_sizes = collections.Counter()
    for line in txt.splitlines():
        m_hdr = re.match(r"^\s*(?:ENTRY\s+)?(%?[\w.\-]+)\s.*\{\s*(//.*)?$",
                         line)
        if m_hdr:
            cur_comp = m_hdr.group(1).lstrip("%")
        m = op_re.match(line)
        if m:
            op = m.group(1)
            counts[op] += 1
            comp_sizes[cur_comp] += 1
            if op in ("sort", "scatter", "gather", "custom-call",
                      "all-to-all", "while", "dynamic-update-slice",
                      "reduce"):
                shape = line.split("=", 1)[1].strip().split(" ")[0]
                big[f"{op} {shape[:70]}"] += 1

    log("opcode histogram (all computations):")
    for op, c in counts.most_common(25):
        print(f"  {op:26s} {c}")
    log("sort/scatter/gather shapes (top 30):")
    for k, c in big.most_common(30):
        print(f"  {c:4d}x {k}")
    log("largest computations:")
    for name, c in comp_sizes.most_common(10):
        print(f"  {c:6d} ops  {name}")
    ops = hlo_op_counts(txt, sim.ep.pool_factor * n)
    log(f"pinned-op summary: {ops}")
    return 0


def main(argv) -> int:
    budget = "--budget" in argv
    argv = [a for a in argv if a != "--budget"]
    max_sorts, max_scatters, replicas = 0, None, None
    tel_ticks, max_scatter_delta = None, 64
    if "--campaign" in argv:
        i = argv.index("--campaign")
        replicas = int(argv[i + 1])
        del argv[i:i + 2]
    if "--telemetry" in argv:
        i = argv.index("--telemetry")
        tel_ticks = int(argv[i + 1])
        del argv[i:i + 2]
    if "--max-sorts" in argv:
        i = argv.index("--max-sorts")
        max_sorts = int(argv[i + 1])
        del argv[i:i + 2]
    if "--max-scatters" in argv:
        i = argv.index("--max-scatters")
        max_scatters = int(argv[i + 1])
        del argv[i:i + 2]
    if "--max-scatter-delta" in argv:
        i = argv.index("--max-scatter-delta")
        max_scatter_delta = int(argv[i + 1])
        del argv[i:i + 2]
    n = int(argv[1]) if len(argv) > 1 else (
        256 if (budget or replicas or tel_ticks) else 4096)
    overlay = argv[2] if len(argv) > 2 else "kademlia"
    window = float(argv[3]) if len(argv) > 3 else 0.2
    inbox = int(argv[4]) if len(argv) > 4 else 8
    if tel_ticks is not None:
        return telemetry_budget_main(n, overlay, window, inbox, tel_ticks,
                                     replicas, max_sorts, max_scatter_delta)
    if replicas is not None:
        return campaign_budget_main(n, overlay, window, inbox, replicas,
                                    max_sorts, max_scatters)
    if budget:
        return budget_main(n, overlay, window, inbox, max_sorts,
                           max_scatters)
    return breakdown_main(n, overlay, window, inbox)


if __name__ == "__main__":
    sys.exit(main(sys.argv))
