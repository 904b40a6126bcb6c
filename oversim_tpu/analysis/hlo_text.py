"""Pure-text analysis of optimized HLO — the measurement half of the
graph-contract plane (oversim_tpu/analysis/contracts.py).

Import-safe: no jax at module level, so the fast test tier can pin the
counting semantics on synthetic HLO strings without a backend
(tests/test_hlo_budget.py, tests/test_analysis.py).  Everything here
consumes ``compiled.as_text()`` output.

History: ``hlo_op_counts`` / ``check_budget`` / ``check_telemetry_budget``
grew up inside scripts/hlo_breakdown.py's three ad-hoc budget modes
(--budget / --campaign / --telemetry).  They now live here as the shared
measurement layer; hlo_breakdown re-exports them for back-compat and the
contract registry drives them for every compiled entry point.

XLA-CPU at -O0 expands scatters into ``while`` loops (ScatterExpander),
so :func:`hlo_op_counts` counts native ``scatter(`` ops PLUS while ops
carrying a ``.../scatter`` op_name — the same graph compiled for TPU
keeps them as native scatters.
"""

from __future__ import annotations

import collections
import re

_SCATTER_WHILE = re.compile(r'op_name="[^"]*/scatter')

# cross-device collective opcodes (GSPMD partitioning output).  The
# campaign budget pins their count at ZERO inside the replica-sharded
# tick: the replica axis is pure data parallelism (oversim_tpu/campaign/)
# — any collective appearing there means the partitioner found a
# cross-replica data dependency, i.e. replicas stopped being independent.
_COLLECTIVE_OPS = ("all-reduce(", "all-gather(", "all-to-all(",
                   "collective-permute(", "reduce-scatter(",
                   "collective-broadcast(")

# ops that talk to the host mid-execution: infeed/outfeed, cross-program
# send/recv, and python-callback custom-calls.  The device-resident run
# loops pin these at ZERO — a host transfer inside the compiled window
# breaks the one-dispatch/one-fetch contract.
_HOST_OPS = (" infeed(", " outfeed(", " send(", " recv(",
             " send-done(", " recv-done(")

# result-dtype tokens as they appear in HLO shapes (``f64[8]{0}``).
_DTYPE_RE = re.compile(
    r"=\s*\(?\s*((?:pred|token|[sf]\d+|u\d+|bf16|f8e\w+|c\d+)"
    r"(?:\[[^\]]*\]\{?[^)\s,]*\}?)?"
    r"(?:\s*,\s*(?:pred|token|[sf]\d+|u\d+|bf16|f8e\w+|c\d+)"
    r"\[[^\]]*\]\{?[^)\s,]*\}?)*)")
_DTYPE_TOKEN = re.compile(r"\b(pred|token|bf16|f8e\w+|[sfuc]\d+)\[?")


def hlo_op_counts(txt: str, pool_dim: int | None = None) -> dict:
    """Count sort/scatter/collective ops in optimized HLO text.

    Returns ``{"sort_count", "full_pool_sort_count", "scatter_count",
    "collective_count"}``.
    ``full_pool_sort_count`` counts sorts whose operand shape contains
    the pool dimension ``pool_dim`` (0 when pool_dim is None).
    ``scatter_count`` = native ``scatter(`` ops + XLA-CPU's
    scatter-expanded ``while`` loops (identified by op_name metadata).
    ``collective_count`` = cross-device collectives (all-reduce /
    all-gather / all-to-all / collective-permute / reduce-scatter /
    collective-broadcast, including their ``-start`` async forms).
    """
    sorts = full = scatters = collectives = 0
    # the pool dim counts as "full-pool" wherever it sits in the shape:
    # leading ([P,...]) in the solo step, second ([S,P,...]) under the
    # campaign's replica vmap
    pool_re = (re.compile(rf"\[(\d+,)?{pool_dim}[\],]")
               if pool_dim is not None else None)
    for ln in txt.splitlines():
        if " sort(" in ln:
            sorts += 1
            if pool_re is not None and pool_re.search(ln):
                full += 1
        elif " scatter(" in ln:
            scatters += 1
        elif " while(" in ln and _SCATTER_WHILE.search(ln):
            scatters += 1
        # async collectives lower to op-start/op-done pairs — counting
        # only the -start (plus the sync form) avoids double counting
        if any((" " + op in ln) or (" " + op[:-1] + "-start(" in ln)
               for op in _COLLECTIVE_OPS):
            collectives += 1
    return {"sort_count": sorts, "full_pool_sort_count": full,
            "scatter_count": scatters, "collective_count": collectives}


_GATHER_RESULT = re.compile(r"=\s*(?:pred|[sfuc]\d+|u\d+|bf16)\[(\d+)[,\]]")


def gather_counts(txt: str, wide_dims=()) -> dict:
    """Gather census: ``{"gather_count", "wide_gather_count"}``.

    A SEPARATE function from :func:`hlo_op_counts` — its return keys are
    pinned by synthetic-HLO tests and by every recorded artifact, so the
    sparse-plane gather census (ISSUE 16) adds a new dict instead of
    widening the old one.  ``wide_gather_count`` counts gathers whose
    RESULT's leading dimension is in ``wide_dims`` (the full node count
    N or the pool capacity P): the dense tick's [N, R, W] payload gather
    is wide, the sparse tick's [A, R, W] gather is not — the
    ``sparse_tick`` delta contract pins that replacement as a REQUIRED
    wide-gather reduction vs ``solo_tick``.  ``" gather("`` with the
    leading space keeps ``all-gather(`` out of the census.
    """
    wide = {int(d) for d in wide_dims if d}
    gathers = wides = 0
    for ln in txt.splitlines():
        if " gather(" not in ln:
            continue
        gathers += 1
        m = _GATHER_RESULT.search(ln)
        if m and int(m.group(1)) in wide:
            wides += 1
    return {"gather_count": gathers, "wide_gather_count": wides}


def collective_census(txt: str) -> dict:
    """Per-opcode collective census, all-reduce refined by its reduce
    computation when recognizable.

    Returns a ``{token: count}`` dict where ``token`` is the collective
    opcode (``"all-gather"``, ``"all-to-all"``, ...) or, for all-reduce,
    ``"all-reduce:min"`` / ``"all-reduce:add"`` / ... when the
    ``to_apply=`` computation reveals the combiner — the contract
    language for "all-reduce-min-only sharded ticks".  The combiner is
    read from the computation NAME when it carries one (``%min_s64``)
    and otherwise resolved from the computation BODY: compiler-named
    regions (``%region_1.7``) say nothing, but their root op
    (``minimum``/``add``/...) does.  Unrecognizable combiners stay
    plain ``"all-reduce"``.
    """
    body_comb = _combiner_by_region(txt)
    out = collections.Counter()
    for ln in txt.splitlines():
        for op in _COLLECTIVE_OPS:
            base = op[:-1]
            if (" " + op in ln) or (" " + base + "-start(" in ln):
                token = base
                if base == "all-reduce":
                    m = re.search(r"to_apply=%?([\w.\-]+)", ln)
                    if m:
                        name = m.group(1).lower()
                        for comb in ("min", "max", "add", "sum", "and",
                                     "or", "mul"):
                            if comb in name:
                                token = f"all-reduce:{comb}"
                                break
                        else:
                            comb = body_comb.get(m.group(1))
                            if comb:
                                token = f"all-reduce:{comb}"
                out[token] += 1
    return dict(out)


_ROOT_COMBINERS = (("minimum(", "min"), ("maximum(", "max"),
                   ("add(", "add"), ("multiply(", "mul"),
                   ("and(", "and"), ("or(", "or"))


def _combiner_by_region(txt: str) -> dict:
    """Map computation name -> combiner token, resolved from each
    computation's ROOT op.  Covers compiler-generated region names
    (``%region_1.7``) whose names carry no combiner hint."""
    out = {}
    name = None
    for ln in txt.splitlines():
        m = re.match(r"%([\w.\-]+)\s*\([^)]*\)\s*->\s*[^{]+{", ln)
        if m:
            name = m.group(1)
            continue
        if name is None:
            continue
        if ln.strip().startswith("}"):
            name = None
            continue
        if "ROOT " in ln:
            for needle, comb in _ROOT_COMBINERS:
                if needle in ln:
                    out[name] = comb
                    break
            name = None
    return out


def host_transfer_count(txt: str) -> int:
    """Ops that reach the host mid-execution: infeed/outfeed/send/recv
    plus python-callback custom-calls (io_callback/pure_callback/debug
    prints)."""
    n = 0
    for ln in txt.splitlines():
        if any(op in ln for op in _HOST_OPS):
            n += 1
        elif " custom-call(" in ln and "callback" in ln:
            n += 1
    return n


def dtype_census(txt: str) -> dict:
    """Instruction-result dtype census: ``{dtype_token: count}``.

    Used for the contract's dtype allowlist — with x64 enabled the
    engine's accumulators are pinned s64/f64; a bf16/f16 appearing in
    the tick means an accumulator silently lost precision.
    """
    out = collections.Counter()
    for ln in txt.splitlines():
        m = _DTYPE_RE.search(ln)
        if not m:
            continue
        for tok in _DTYPE_TOKEN.findall(m.group(1)):
            out[tok] += 1
    return dict(out)


def donated_leaf_count(txt: str) -> int:
    """Number of input→output aliased buffers in the module header.

    Donation that survived to the optimized module shows up as
    ``input_output_alias={ {}: (0, {}, may-alias), ... }`` — one
    ``may-alias``/``must-alias`` entry per aliased leaf.  0 means the
    donation was dropped (or never requested): every chunk would then
    round-trip the full state through fresh HBM allocations.
    """
    for ln in txt.splitlines():
        if "input_output_alias=" in ln:
            return len(re.findall(r"(?:may|must)-alias", ln))
    return 0


def check_budget(txt: str, pool_dim: int, max_full_pool_sorts: int,
                 max_scatters: int, max_collectives: int | None = None):
    """(ok, counts) — does the compiled tick fit the pinned op budget?
    ``max_collectives`` is only enforced when given (the campaign budget
    pins it at 0; single-replica node-sharded steps legitimately carry
    collectives)."""
    counts = hlo_op_counts(txt, pool_dim)
    ok = (counts["full_pool_sort_count"] <= max_full_pool_sorts
          and counts["scatter_count"] <= max_scatters)
    if max_collectives is not None:
        ok = ok and counts["collective_count"] <= max_collectives
    return ok, counts


def check_telemetry_budget(base_counts: dict, tel_counts: dict,
                           max_full_pool_sorts: int = 0,
                           max_scatter_delta: int = 64,
                           max_new_collectives: int = 0):
    """(ok, delta) — the telemetry-enabled tick vs the telemetry-off tick.

    The telemetry plane's entire graph cost is one gated ``mode="drop"``
    scatter per ring buffer (oversim_tpu/telemetry.py fold), so the
    pinned contract is: still ZERO full-pool sorts (no sort may appear
    anywhere — the rings never sort), a BOUNDED scatter delta (one per
    ring; KBRTest taps + engine counters + time/tick/alive meta fit well
    under 64), and ZERO new collectives (the [W] rings are replicated /
    per-replica — sampling must not create cross-device traffic).
    ``base_counts``/``tel_counts`` are :func:`hlo_op_counts` dicts.
    """
    delta = {
        "full_pool_sort_count": tel_counts["full_pool_sort_count"],
        "sort_delta": (tel_counts["sort_count"]
                       - base_counts["sort_count"]),
        "scatter_delta": (tel_counts["scatter_count"]
                          - base_counts["scatter_count"]),
        "collective_delta": (tel_counts["collective_count"]
                             - base_counts["collective_count"]),
    }
    ok = (delta["full_pool_sort_count"] <= max_full_pool_sorts
          and delta["sort_delta"] <= 0
          and delta["scatter_delta"] <= max_scatter_delta
          and delta["collective_delta"] <= max_new_collectives)
    return ok, delta
