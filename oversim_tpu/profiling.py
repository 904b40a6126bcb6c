"""Per-phase tick profiling — OVERSIM_PROFILE=1 (PERFORMANCE.md lever).

The tick graph is op-issue/compile-bound and opaque: when a bench run
dies or posts a bad number, nothing says WHICH of the tick's phases ate
the time (the round-5 bench artifact was a deadline-killed 0.0 with no
diagnosis).  This module times the phases of ``Simulation.step``
(engine/sim.py splits them exactly for this):

  horizon       event-horizon scan + rng split
  churn         churn events, alive flips, key/coord migration, resets
  inbox_select  due-message top-R selection (one D-lane sort; P-wide
                scatter-min rounds in a tick that overruns the lanes)
  inbox_gather  packed-block gather of the selected messages → Msg view
  node_step     tick context + the vmapped per-node logic sweep
  alloc_stats   underlay send, sort-free pool alloc, stat folding

Under the sparse plane (``tick_impl="sparse"``) the layout is
``horizon / churn / inbox_select / active_compact / sparse_step /
alloc_stats``: selection never gathers the full payload block,
``active_compact`` orders the awake set into rounds of A lanes, and
``sparse_step`` is the logic sweep over those rounds only (the report
carries ``tick_impl`` so artifact readers can tell the layouts apart).

Each phase is jitted SEPARATELY and timed with ``block_until_ready``
over ``n_ticks`` real ticks.  Sub-jits lose cross-phase fusion, so the
phase sum exceeds the fused tick cost — the per-phase SHARES are the
diagnostic signal, and the fused cost is measured alongside via
``run_chunk`` for the honest denominator.  The report also carries
``sort_count`` / ``scatter_count`` pinned-op counts off the fused
compiled tick (scripts/hlo_breakdown.py counting rules), so a lever
regression (a sort sneaking back into the hot path) shows up in every
profiled bench artifact.

Usage:
    from oversim_tpu import profiling
    if profiling.enabled():
        report, s = profiling.profile_ticks(sim, s, n_ticks=4)
        print(json.dumps(report))

``bench.py`` emits the report as a JSON line when OVERSIM_PROFILE=1.
"""

from __future__ import annotations

import os
import time

import jax

PHASES = ("horizon", "churn", "inbox_select", "inbox_gather", "node_step",
          "alloc_stats")
# sparse-plane layout (tick_impl="sparse"): selection never gathers the
# full [N, R, W] payload; the awake set compacts into A lanes
# (active_compact) and only those lanes run the logic sweep (sparse_step)
PHASES_SPARSE = ("horizon", "churn", "inbox_select", "active_compact",
                 "sparse_step", "alloc_stats")


def phases_for(tick_impl: str = "dense") -> tuple:
    """The phase layout a Simulation's tick decomposes into."""
    return PHASES_SPARSE if tick_impl == "sparse" else PHASES


def enabled() -> bool:
    """True when OVERSIM_PROFILE is set to a non-empty, non-"0" value."""
    return os.environ.get("OVERSIM_PROFILE", "") not in ("", "0")


def _jit_phases(sim):
    """Jit the phase methods of a Simulation (closures keep ``sim``
    static, mirroring run_chunk's static ``self``)."""
    return {
        "horizon": jax.jit(
            lambda s: sim._phase_horizon(s)),
        "churn": jax.jit(
            lambda s, tn, te, rc, rk, rr, rm: sim._phase_churn(
                s, tn, te, rc, rk, rr, rm)),
        "inbox_select": jax.jit(
            lambda s, te, alive: sim._phase_inbox_select(s, te, alive)),
        "inbox_gather": jax.jit(
            lambda s, tn, inbox: sim._phase_inbox_gather(s, tn, inbox)),
        "node_step": jax.jit(
            lambda s, tn, te, alive, pk, cs, nk, ul, lg, msgs, rn:
            sim._phase_node_step(s, tn, te, alive, pk, cs, nk, ul, lg,
                                 msgs, rn)),
        "alloc_stats": jax.jit(
            lambda s, te, rng, rs, alive, pk, nk, ul, cs, lg, dlv, dead,
            of, ov, oo, ev, ms: sim._phase_alloc_stats(
                s, te, rng, rs, alive, pk, nk, ul, cs, lg, dlv, dead,
                of, ov, oo, ev, ms)),
        # sparse plane (tick_impl="sparse")
        "active_compact": jax.jit(
            lambda s, te, alive, pk, lg, inbox:
            sim._phase_active_compact(s, te, alive, pk, lg, inbox)),
        "sparse_step": jax.jit(
            lambda s, tn, te, alive, pk, cs, nk, ul, lg, inbox, order, nr,
            rn: sim._phase_sparse_step(s, tn, te, alive, pk, cs, nk, ul,
                                       lg, inbox, order, nr, rn)),
        "alloc_stats_sparse": jax.jit(
            lambda s, te, rng, rs, alive, pk, nk, ul, cs, lg, dlv, dead,
            of, ov, oo, ev, ms, act: sim._phase_alloc_stats(
                s, te, rng, rs, alive, pk, nk, ul, cs, lg, dlv, dead,
                of, ov, oo, ev, ms, active=act)),
    }


def tick_op_counts(sim, s) -> dict:
    """sort/scatter pinned-op counts off the FUSED compiled tick.

    Compiles ``jit(sim.step)`` (cache-shared with run_chunk's scan body
    where the backend persists compilations) and applies the
    scripts/hlo_breakdown.py counting rules.  Returns {} when the
    backend does not expose compiled HLO text.
    """
    try:
        from scripts.hlo_breakdown import hlo_op_counts
        txt = jax.jit(sim.step).lower(s).compile().as_text()
        return hlo_op_counts(txt, sim.ep.pool_factor * sim.n)
    except Exception:  # noqa: BLE001 — diagnostics must never kill a bench
        return {}


def profile_ticks(sim, s, n_ticks: int = 4, fused_reference: bool = True,
                  op_counts: bool = True):
    """Run ``n_ticks`` real ticks phase by phase, timing each phase.

    Returns ``(report, s)`` — the report dict (JSON-serializable) and
    the advanced SimState (the profiled ticks are real simulation
    progress; callers keep using the returned state).  The first tick
    pays all phase compiles and is EXCLUDED from the averages.
    """
    fns = _jit_phases(sim)
    sparse = sim.tick_impl == "sparse"
    phases = phases_for(sim.tick_impl)
    totals = {p: 0.0 for p in phases}
    compile_s = 0.0
    measured = 0
    tick_rows = []    # per measured tick: {phase: ms} — Perfetto feed

    for tick in range(n_ticks + 1):
        first = tick == 0
        t_tick0 = time.perf_counter()

        t0 = time.perf_counter()
        t_next, t_end, rngs = jax.block_until_ready(
            fns["horizon"](s))
        dt_h = time.perf_counter() - t0
        (rng, r_churn, r_keys, r_reset, r_nodes, r_mig, r_send) = rngs

        t0 = time.perf_counter()
        (churn_state, alive, pre_killed, node_keys, ul_state,
         logic_state) = jax.block_until_ready(
            fns["churn"](s, t_next, t_end, r_churn, r_keys, r_reset, r_mig))
        dt_c = time.perf_counter() - t0

        if sparse:
            t0 = time.perf_counter()
            inbox, delivered, to_dead = jax.block_until_ready(
                fns["inbox_select"](s, t_end, alive))
            dt_is = time.perf_counter() - t0

            t0 = time.perf_counter()
            order, rounds, active = jax.block_until_ready(
                fns["active_compact"](s, t_end, alive, pre_killed,
                                      logic_state, inbox))
            inbox_dts = (dt_is, time.perf_counter() - t0)

            t0 = time.perf_counter()
            (logic_state, out_fields, out_valid, out_overflow, events,
             measuring) = jax.block_until_ready(
                fns["sparse_step"](s, t_next, t_end, alive, pre_killed,
                                   churn_state, node_keys, ul_state,
                                   logic_state, inbox, order, rounds,
                                   r_nodes))
            dt_n = time.perf_counter() - t0

            t0 = time.perf_counter()
            s = jax.block_until_ready(
                fns["alloc_stats_sparse"](
                    s, t_end, rng, r_send, alive, pre_killed, node_keys,
                    ul_state, churn_state, logic_state, delivered, to_dead,
                    out_fields, out_valid, out_overflow, events, measuring,
                    active))
            dt_a = time.perf_counter() - t0
        else:
            t0 = time.perf_counter()
            inbox, delivered, to_dead = jax.block_until_ready(
                fns["inbox_select"](s, t_end, alive))
            dt_is = time.perf_counter() - t0

            t0 = time.perf_counter()
            msgs = jax.block_until_ready(
                fns["inbox_gather"](s, t_next, inbox))
            inbox_dts = (dt_is, time.perf_counter() - t0)

            t0 = time.perf_counter()
            (logic_state, out_fields, out_valid, out_overflow, events,
             measuring) = jax.block_until_ready(
                fns["node_step"](s, t_next, t_end, alive, pre_killed,
                                 churn_state, node_keys, ul_state,
                                 logic_state, msgs, r_nodes))
            dt_n = time.perf_counter() - t0

            t0 = time.perf_counter()
            s = jax.block_until_ready(
                fns["alloc_stats"](s, t_end, rng, r_send, alive, pre_killed,
                                   node_keys, ul_state, churn_state,
                                   logic_state, delivered, to_dead,
                                   out_fields, out_valid, out_overflow,
                                   events, measuring))
            dt_a = time.perf_counter() - t0

        if first:
            compile_s = time.perf_counter() - t_tick0
            continue
        measured += 1
        row = {}
        for p, dt in zip(phases, (dt_h, dt_c, *inbox_dts, dt_n, dt_a)):
            totals[p] += dt
            row[p] = round(dt * 1e3, 3)
        tick_rows.append(row)

    denom = max(measured, 1)
    phase_ms = {p: round(totals[p] / denom * 1e3, 3) for p in phases}
    split_sum = sum(totals.values()) / denom
    report = {
        "metric": "tick_phase_breakdown",
        "n_ticks": measured,
        "tick_impl": sim.tick_impl,
        "phase_ms_per_tick": phase_ms,
        "phase_frac": {p: round(totals[p] / max(sum(totals.values()), 1e-12),
                                4) for p in phases},
        "split_sum_ms_per_tick": round(split_sum * 1e3, 3),
        # per-tick phase rows (ms) — telemetry.PerfettoTrace.add_profile
        # lays them out as back-to-back tick.<phase> spans
        "phase_ticks_ms": tick_rows,
        "phase_compile_s": round(compile_s, 2),
    }

    if op_counts:
        report.update(tick_op_counts(sim, s))

    if fused_reference:
        # fused cost via run_chunk (donating; rebind s both times).  The
        # first call may compile — time only the second.
        s = jax.block_until_ready(sim.run_chunk(s, n_ticks))
        t0 = time.perf_counter()
        s = jax.block_until_ready(sim.run_chunk(s, n_ticks))
        fused = (time.perf_counter() - t0) / max(n_ticks, 1)
        report["fused_ms_per_tick"] = round(fused * 1e3, 3)
        report["split_overhead_x"] = round(split_sum / max(fused, 1e-12), 2)

    return report, s
