"""Graph-contract analyzer CLI — the gate every compiled entry point
must pass (oversim_tpu/analysis/; ISSUE 10).

Usage:
  python scripts/analyze.py [--all] [--hlo] [--trace] [--ast] [--fast]
                            [--entries a,b,...] [--json PATH] [--list]
                            [--n N] [--overlay chord|kademlia]
                            [--window W] [--inbox I] [--replicas S]
                            [--compile-budget S]
                            [--seed-breach hlo|trace|ast|compile|sparse|shard]

  No pass flag = --all.  Prints ONE machine-readable JSON verdict
  document on stdout (kind "graph_contract_verdict"), human-readable
  breach lines on stderr, and exits non-zero on any breach.

  --fast         shrink entry sizes (n=64, S=2) — the tier-1 /
                 run_suite.sh gate; op-count contracts are
                 size-independent, so the pins hold at any n.
  --entries      comma-separated registry subset (see --list).  A delta
                 entry needs its base selected too.
  --json PATH    additionally write the verdict document to PATH
                 (atomic); run_suite.sh points OVERSIM_ANALYSIS_VERDICT
                 at it so run_manifest embeds the verdict.
  --list         print registered entries + lint rules and exit.
  --compile-budget S
                 enforce a per-entry lower+compile wall ceiling of S
                 seconds during the hlo pass (implies --hlo); an
                 entry's GraphContract.max_compile_seconds overrides
                 the ceiling.  compile_seconds timings are recorded in
                 the JSON verdict regardless — this flag only arms the
                 breach (run_suite.sh passes it so a compile-time
                 regression fails CI before it burns a TPU deadline).
  --seed-breach  deliberately violate ONE pass with a toy entry/fixture
                 and run only that — the self-test hook
                 (tests/test_analysis.py pins each seeded breach exits
                 non-zero with a JSON finding).
"""

import json
import os
import sys
import time
from pathlib import Path

T0 = time.time()
REPO = Path(__file__).resolve().parent.parent


def log(msg):
    print(f"[{time.time() - T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


def _setup_env():
    """Everything that must happen before jax is imported (mirrors
    tests/conftest.py: CPU backend, 8 virtual devices for the sharded
    campaign entry, -O0, zstandard poisoned)."""
    sys.path.insert(0, str(REPO))
    sys.modules["zstandard"] = None
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    if "xla_backend_optimization_level" not in flags:
        flags += (" --xla_backend_optimization_level=0"
                  " --xla_llvm_disable_expensive_passes=true")
    os.environ["XLA_FLAGS"] = flags


def _setup_jax():
    from oversim_tpu import hostcache
    # persistent=False: the analyzer compiles on CPU, where this box's
    # executable serialize() segfaults sporadically (conftest note) —
    # and a COLD compile is exactly what --compile-budget must measure
    hostcache.enable(persistent=False)
    import jax
    jax.config.update("jax_platforms", "cpu")
    return jax


# ---------------------------------------------------------------------------
# seeded breaches (--seed-breach): one deliberate violation per pass
# ---------------------------------------------------------------------------

_SEED_AST_FIXTURE = '''\
def drain(counters):
    total = counters["sent"].item()
    return total
'''


def _seed_hlo(ctx):
    """A toy jitted fn whose graph contains one full-pool sort."""
    import jax
    import jax.numpy as jnp
    from oversim_tpu.analysis import contracts as C
    from oversim_tpu.analysis import hlo_pass

    fn = jax.jit(lambda x: jnp.sort(x))
    x = jnp.arange(64, dtype=jnp.float32)
    built = C.EntryBuild(fn=fn, make_args=lambda: (x,), pool_dim=64,
                         info={"seeded": True})
    txt = built.fn.lower(*built.make_args()).compile().as_text()
    m = hlo_pass.measure_entry(txt, built.pool_dim)
    findings = hlo_pass.check_contract("seeded_sort", C.GraphContract(), m)
    return findings, {"entries": {"seeded_sort": {"counts": {
        k: m[k] for k in ("sort_count", "full_pool_sort_count",
                          "scatter_count", "collective_count")}}}}


def _seed_trace(ctx):
    """A toy entry whose second call arrives with a NEW shape — the
    harness must report the forced recompile."""
    import jax
    import jax.numpy as jnp
    from oversim_tpu.analysis import contracts as C
    from oversim_tpu.analysis import trace_pass

    fn = jax.jit(lambda x: x * 2)
    sizes = iter((8, 9, 10))
    built = C.EntryBuild(
        fn=fn, make_args=lambda: (jnp.zeros(next(sizes)),), pool_dim=8,
        info={"seeded": True})
    findings, stats = trace_pass.harness_entry(
        "seeded_recompile", built, C.GraphContract())
    return findings, {"entries": {"seeded_recompile": stats}}


def _seed_ast(ctx):
    """Lint a planted fixture containing a hot-path ``.item()``."""
    from oversim_tpu.analysis import ast_pass
    findings = ast_pass.lint_source(
        _SEED_AST_FIXTURE, "seeded/fixture.py", ast_pass.HOT_RULES)
    return findings, {"files_scanned": 1, "findings": len(findings)}


def _seed_compile(ctx):
    """A toy entry timed against an impossible 0.0-second compile
    budget — any real lower+compile breaches it."""
    import jax
    import jax.numpy as jnp
    from oversim_tpu.analysis import contracts as C
    from oversim_tpu.analysis import hlo_pass

    fn = jax.jit(lambda x: x + 1)
    built = C.EntryBuild(fn=fn, make_args=lambda: (jnp.arange(8),),
                         pool_dim=8, info={"seeded": True})
    _, timing = hlo_pass.timed_lower_compile(built)
    findings = hlo_pass.check_compile_budget("seeded_compile", 0.0, timing)
    return findings, {"entries": {"seeded_compile":
                                  {"compile_seconds": timing}}}


# synthetic base/sparse module pair where the "sparse" module KEEPS
# the full-width gathers and stacks a new one on top — the sparse_tick
# delta contract's required wide-gather REDUCTION must flag it
# (pure-text, no backend)
_SEED_SPARSE_BASE = '''\
HloModule seeded_base

ENTRY %main {
  %g0 = f32[64,4,6]{2,1,0} gather(%pool, %idx), offset_dims={1}
}
'''

_SEED_SPARSE_HLO = '''\
HloModule seeded_sparse

ENTRY %main {
  %g0 = f32[64,4,6]{2,1,0} gather(%pool, %idx), offset_dims={1}
  %g1 = s32[256]{0} gather(%pool, %due)
}
'''


def _seed_sparse(ctx):
    """Diff a planted compaction-on-top module against its dense base
    with the REAL sparse_tick delta contract: the wide-gather delta is
    +1 where a NEGATIVE delta (a reduction) is required."""
    from oversim_tpu.analysis import contracts as C
    from oversim_tpu.analysis import hlo_pass

    delta = C.REGISTRY["sparse_tick"].delta
    wide = (64, 256)
    base_m = hlo_pass.measure_entry(_SEED_SPARSE_BASE, 256,
                                    wide_dims=wide)
    m = hlo_pass.measure_entry(_SEED_SPARSE_HLO, 256, wide_dims=wide)
    findings, d = hlo_pass.check_delta("seeded_sparse", delta, base_m, m)
    return findings, {"entries": {"seeded_sparse": {"delta": d}}}


# synthetic HLO carrying an all-reduce:add and an all-to-all — both
# off the sharded tick's all-reduce:min-only collective allowlist
# (pure-text, no backend; the combiner resolves through the region
# body like real pmin lowerings do)
_SEED_SHARD_HLO = '''\
HloModule seeded_shard

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %ar = f32[64]{0} all-reduce(f32[64]{0} %p0), replica_groups={}, \
to_apply=%region_0.1
  ROOT %a2a = f32[64]{0} all-to-all(f32[64]{0} %ar), dimensions={0}
}
'''


def _seed_shard(ctx):
    """Check a planted all-reduce:add + all-to-all against the sharded
    tick's contract (allowed_collectives = {all-reduce:min} only)."""
    from oversim_tpu.analysis import contracts as C
    from oversim_tpu.analysis import hlo_pass

    contract = C.REGISTRY["sharded_tick"].contract
    m = hlo_pass.measure_entry(_SEED_SHARD_HLO, 64)
    findings = hlo_pass.check_contract("seeded_shard", contract, m)
    return findings, {"entries": {"seeded_shard": {
        "collectives": m["collectives"]}}}


_SEEDS = {"hlo": _seed_hlo, "trace": _seed_trace, "ast": _seed_ast,
          "compile": _seed_compile, "sparse": _seed_sparse,
          "shard": _seed_shard}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _parse(argv):
    import argparse
    p = argparse.ArgumentParser(
        prog="analyze.py", description="graph-contract analyzer")
    p.add_argument("--all", action="store_true")
    p.add_argument("--hlo", action="store_true")
    p.add_argument("--trace", action="store_true")
    p.add_argument("--ast", action="store_true")
    p.add_argument("--fast", action="store_true")
    p.add_argument("--entries", default=None)
    p.add_argument("--json", dest="json_path", default=None)
    p.add_argument("--list", action="store_true")
    p.add_argument("--seed-breach", choices=sorted(_SEEDS), default=None)
    p.add_argument("--n", type=int, default=None)
    p.add_argument("--overlay", default="kademlia",
                   choices=("chord", "kademlia"))
    p.add_argument("--window", type=float, default=0.2)
    p.add_argument("--inbox", type=int, default=8)
    p.add_argument("--replicas", type=int, default=None)
    p.add_argument("--compile-budget", type=float, default=None,
                   metavar="S", help="per-entry lower+compile wall "
                   "ceiling in seconds (implies --hlo); "
                   "GraphContract.max_compile_seconds overrides per "
                   "entry")
    return p.parse_args(argv[1:])


def _emit(doc, json_path):
    from oversim_tpu.analysis import findings as findings_mod
    print(json.dumps(doc, indent=1), flush=True)
    if json_path:
        findings_mod.write_document(doc, json_path)
    for f in doc["findings"]:
        line = (f"analyze: [{f['pass']}] {f['rule']} @ {f['where']}: "
                f"{f['message']}")
        if "measured" in f:
            line += f" (measured={f['measured']}, limit={f.get('limit')})"
        print(line, file=sys.stderr, flush=True)
    verdict = "OK" if doc["ok"] else f"{doc['errors']} breach(es)"
    log(f"verdict: {verdict}")
    return 0 if doc["ok"] else 1


def main(argv) -> int:
    args = _parse(argv)
    _setup_env()
    from oversim_tpu.analysis import ast_pass
    from oversim_tpu.analysis import contracts as contracts_mod
    from oversim_tpu.analysis import findings as findings_mod

    if args.list:
        print("entries:")
        for e in contracts_mod.REGISTRY.values():
            print(f"  {e.name:18s} {e.doc}")
        print("ast rules:")
        for rule, doc in ast_pass.RULES.items():
            print(f"  {rule:18s} {doc}")
        return 0

    if args.seed_breach:
        # ast + sparse + shard breaches are pure-text — no backend
        if args.seed_breach not in ("ast", "sparse", "shard"):
            _setup_jax()
        findings, summary = _SEEDS[args.seed_breach](None)
        doc = findings_mod.document(
            findings, {args.seed_breach: summary}, fast=True)
        doc["seeded"] = args.seed_breach
        return _emit(doc, args.json_path)

    run_hlo = args.all or args.hlo or args.compile_budget is not None
    run_trace = args.all or args.trace
    run_ast = args.all or args.ast
    if not (run_hlo or run_trace or run_ast):
        run_hlo = run_trace = run_ast = True

    selected = args.entries.split(",") if args.entries else None
    ctx_kw = {}
    if args.n is not None:
        ctx_kw["n"] = args.n
    if args.replicas is not None:
        ctx_kw["replicas"] = args.replicas
    ctx = contracts_mod.EntryContext.make(
        fast=args.fast, overlay=args.overlay, window=args.window,
        inbox=args.inbox, **ctx_kw)

    findings, passes = [], {}
    if run_ast:
        f, summary = ast_pass.run(REPO)
        log(f"ast: {summary['files_scanned']} files, "
            f"{len(f)} finding(s)")
        findings.extend(f)
        passes["ast"] = summary
    if run_hlo or run_trace:
        _setup_jax()
        # OVERSIM_OBS_ARMED=1: run the whole compile/trace census with a
        # live RunObserver in-process (metrics endpoint + flight ring on
        # an ephemeral port) — the obs_smoke gate compares this verdict
        # against the obs-off baseline to prove the observability plane
        # changes NOTHING in the compiled graphs
        obs = None
        if os.environ.get("OVERSIM_OBS_ARMED") == "1":
            from oversim_tpu.obs import RunObserver
            obs = RunObserver(role="analyze", port=0)
            log(f"obs armed: metrics endpoint on port {obs.start()}")
            obs.record("analysis_start", fast=args.fast)
        builds = {}
        if run_hlo:
            from oversim_tpu.analysis import hlo_pass
            f, summary = hlo_pass.run(ctx, selected, progress=log,
                                      builds=builds,
                                      compile_budget=args.compile_budget)
            log(f"hlo: {len(summary['entries'])} entries, "
                f"{len(f)} finding(s)")
            findings.extend(f)
            passes["hlo"] = summary
        if run_trace:
            from oversim_tpu.analysis import trace_pass
            f, summary = trace_pass.run(ctx, selected, progress=log,
                                        builds=builds)
            log(f"trace: {len(summary['entries'])} entries, "
                f"{len(f)} finding(s)")
            findings.extend(f)
            passes["trace"] = summary

        if obs is not None:
            obs.record("analysis_done", findings=len(findings))
            obs.close()

    doc = findings_mod.document(findings, passes, fast=args.fast)
    return _emit(doc, args.json_path)


if __name__ == "__main__":
    sys.exit(main(sys.argv))
