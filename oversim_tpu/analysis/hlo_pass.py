"""HLO contract pass: lower + compile every registered entry point and
diff its optimized module against the entry's :class:`GraphContract`.

For each entry the pass records a census dict (op counts, collective
census, host transfers, donated leaves, off-allowlist dtypes) in the
verdict document's ``passes.hlo.entries`` — delta contracts
(telemetry_tick vs solo_tick) diff against the base entry's recorded
counts, so registration order matters (contracts.register_entry
enforces base-first).
"""

from __future__ import annotations

import time

from oversim_tpu.analysis import hlo_text
from oversim_tpu.analysis.findings import Finding


def measure_entry(txt: str, pool_dim: int, wide_dims=()) -> dict:
    """Every census the contracts can pin, from one optimized module.

    ``wide_dims`` feeds the gather census (hlo_text.gather_counts):
    the full-width leading dims — node count N and pool capacity P —
    whose gathers the sparse plane exists to eliminate."""
    m = dict(hlo_text.hlo_op_counts(txt, pool_dim))
    m.update(hlo_text.gather_counts(txt, wide_dims))
    m["collectives"] = hlo_text.collective_census(txt)
    m["host_transfers"] = hlo_text.host_transfer_count(txt)
    m["donated_leaves"] = hlo_text.donated_leaf_count(txt)
    m["dtypes"] = hlo_text.dtype_census(txt)
    return m


def check_contract(name: str, contract, m: dict) -> list:
    """Diff one entry's measurements against its GraphContract."""
    out = []

    def breach(rule, message, measured, limit):
        out.append(Finding(pass_name="hlo", rule=rule, where=name,
                           message=message, measured=measured, limit=limit))

    if m["full_pool_sort_count"] > contract.max_full_pool_sorts:
        breach("full-pool-sorts",
               "full-pool sorts appeared in the compiled graph — the "
               "zero-full-pool-sort tick regressed (engine/pool.py "
               "inbox selection)",
               m["full_pool_sort_count"], contract.max_full_pool_sorts)
    if contract.max_sorts is not None and \
            m["sort_count"] > contract.max_sorts:
        breach("sorts", "total sort ops over budget",
               m["sort_count"], contract.max_sorts)
    if m["scatter_count"] > contract.max_scatters:
        breach("scatters",
               "scatter count (incl. XLA-CPU while-expanded scatters) "
               "over budget",
               m["scatter_count"], contract.max_scatters)
    if contract.collectives_enforced:
        bad = {k: v for k, v in m["collectives"].items()
               if k not in contract.allowed_collectives}
        if bad:
            breach("collectives",
                   "cross-device collectives outside the allowed set — "
                   "for replica-sharded entries this means the "
                   "partitioner found a cross-replica data dependency",
                   bad, sorted(contract.allowed_collectives))
    if m["host_transfers"] > contract.max_host_transfers:
        breach("host-transfers",
               "infeed/outfeed/send/recv/host-callback ops inside the "
               "compiled module break the one-dispatch/one-fetch "
               "contract",
               m["host_transfers"], contract.max_host_transfers)
    if contract.require_donation and \
            m["donated_leaves"] < contract.min_donated_leaves:
        breach("donation",
               "input→output buffer aliasing missing from the optimized "
               "module — donation was dropped; every dispatch "
               "round-trips the state through fresh allocations",
               m["donated_leaves"], f">= {contract.min_donated_leaves}")
    bad_dtypes = {k: v for k, v in m["dtypes"].items()
                  if k not in contract.dtype_allowlist}
    if bad_dtypes:
        breach("dtypes",
               "instruction result dtypes outside the allowlist — an "
               "x64 accumulator silently lost precision",
               bad_dtypes, sorted(contract.dtype_allowlist))
    return out


def check_delta(name: str, delta, base_m: dict, m: dict) -> list:
    """Diff one entry against its DeltaContract base entry."""
    out = []
    d = {
        "full_pool_sort_count": m["full_pool_sort_count"],
        "sort_delta": m["sort_count"] - base_m["sort_count"],
        "scatter_delta": m["scatter_count"] - base_m["scatter_count"],
        "collective_delta": (m["collective_count"]
                             - base_m["collective_count"]),
        # gather deltas are RECORDED for every delta entry (the verdict
        # JSON carries the sparse plane's measured reduction); only
        # max_wide_gather_delta != None enforces one
        "gather_delta": (m.get("gather_count", 0)
                         - base_m.get("gather_count", 0)),
        "wide_gather_delta": (m.get("wide_gather_count", 0)
                              - base_m.get("wide_gather_count", 0)),
    }

    def breach(rule, message, measured, limit):
        out.append(Finding(pass_name="hlo", rule=rule,
                           where=f"{name} (vs {delta.base})",
                           message=message, measured=measured, limit=limit))

    if d["full_pool_sort_count"] > delta.max_full_pool_sorts:
        breach("delta-full-pool-sorts",
               "full-pool sorts in the delta entry",
               d["full_pool_sort_count"], delta.max_full_pool_sorts)
    if d["sort_delta"] > delta.max_sort_delta:
        breach("delta-sorts", "new sorts relative to the base entry",
               d["sort_delta"], delta.max_sort_delta)
    if d["scatter_delta"] > delta.max_scatter_delta:
        breach("delta-scatters",
               "scatter delta over budget (one gated drop-scatter per "
               "telemetry ring is the whole allowance)",
               d["scatter_delta"], delta.max_scatter_delta)
    if d["collective_delta"] > delta.max_collective_delta:
        breach("delta-collectives",
               "new cross-device collectives relative to the base entry",
               d["collective_delta"], delta.max_collective_delta)
    if delta.max_wide_gather_delta is not None and \
            d["wide_gather_delta"] > delta.max_wide_gather_delta:
        breach("delta-wide-gathers",
               "full-width gather delta over budget — a negative bound "
               "is a REQUIRED reduction: the sparse tick must replace "
               "the [N, R, W] payload gather with the [A]-lane one, "
               "not stack compaction on top of it",
               d["wide_gather_delta"], delta.max_wide_gather_delta)
    return out, d


def timed_lower_compile(built) -> tuple:
    """(optimized HLO text, compile-seconds dict) for one EntryBuild,
    timing lower (trace+StableHLO) and compile (XLA backend) apart —
    the two stages the AOT artifact plane (oversim_tpu/aot/) and the
    persistent cache attack separately.  The timing is also stashed in
    ``built.info["compile_seconds"]`` for the verdict document."""
    t0 = time.perf_counter()
    lowered = built.fn.lower(*built.make_args())
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    timing = {"lower": round(t_lower, 3), "compile": round(t_compile, 3),
              "total": round(t_lower + t_compile, 3)}
    built.info["compile_seconds"] = timing
    return compiled.as_text(), timing


def check_compile_budget(name: str, budget, timing: dict) -> list:
    """Budget breach finding (empty when within budget or unbudgeted)."""
    if budget is None or timing["total"] <= budget:
        return []
    return [Finding(
        pass_name="hlo", rule="compile-seconds", where=name,
        message="lower+compile wall time over the CI compile budget — "
                "compile-latency regressions burn the TPU deadline "
                "before the first measured window (--compile-budget / "
                "GraphContract.max_compile_seconds)",
        measured=timing["total"], limit=budget)]


def lower_entry(entry, ctx, builds=None) -> tuple:
    """(optimized HLO text, EntryBuild) for one registry entry."""
    if builds is not None and entry.name in builds:
        built = builds[entry.name]
    else:
        built = entry.build(ctx)
        if builds is not None:
            builds[entry.name] = built
    txt, _ = timed_lower_compile(built)
    return txt, built


def run(ctx, selected=None, *, progress=None, builds=None,
        compile_budget=None):
    """The whole pass: (findings, summary) over the selected entries.

    ``progress`` is an optional ``callable(str)`` for per-entry status
    lines (compiles are the slow part of the analyzer); ``builds`` an
    optional shared ``{name: EntryBuild}`` cache across passes.
    ``compile_budget`` (seconds, ``--compile-budget``) is the default
    per-entry lower+compile ceiling; an entry's
    ``contract.max_compile_seconds`` overrides it.  Timings are
    recorded in the summary regardless — only enforcement is gated."""
    from oversim_tpu.analysis import contracts as contracts_mod

    findings = []
    entries_summary = {}
    measured = {}
    for entry in contracts_mod.entries(selected):
        if progress:
            progress(f"hlo: compiling {entry.name} ...")
        txt, built = lower_entry(entry, ctx, builds)
        m = measure_entry(txt, built.pool_dim,
                          wide_dims=(built.info.get("n"), built.pool_dim))
        measured[entry.name] = m
        findings.extend(check_contract(entry.name, entry.contract, m))
        timing = built.info.get("compile_seconds",
                                {"lower": 0.0, "compile": 0.0,
                                 "total": 0.0})
        budget = entry.contract.max_compile_seconds
        if budget is None:
            budget = compile_budget
        findings.extend(check_compile_budget(entry.name, budget, timing))
        delta_info = None
        if entry.delta is not None:
            base_m = measured.get(entry.delta.base)
            if base_m is None:
                findings.append(Finding(
                    pass_name="hlo", rule="delta-base-missing",
                    where=entry.name,
                    message=f"delta base {entry.delta.base!r} was not "
                            f"measured in this run (select it too)"))
            else:
                delta_findings, delta_info = check_delta(
                    entry.name, entry.delta, base_m, m)
                findings.extend(delta_findings)
        entries_summary[entry.name] = {
            "counts": {k: m[k] for k in
                       ("sort_count", "full_pool_sort_count",
                        "scatter_count", "collective_count",
                        "gather_count", "wide_gather_count")},
            "collectives": m["collectives"],
            "host_transfers": m["host_transfers"],
            "donated_leaves": m["donated_leaves"],
            "compile_seconds": timing,
            "info": built.info,
            **({"delta": delta_info} if delta_info else {}),
        }
    summary = {"entries": entries_summary,
               "findings": len(findings)}
    return findings, summary
