"""Graph-contract analysis plane (oversim_tpu/analysis/; ISSUE 10).

Fast tier-1 pins: the AST rules + suppression syntax on crafted
sources, bytecode guards on tmp trees, the new HLO text censuses on
synthetic modules, the contract registry's shape, and — per pass —
one DELIBERATE seeded breach through
the scripts/analyze.py CLI exiting non-zero with a machine-readable
JSON finding.  The repo itself must lint clean (the allow markers are
part of the tree)."""

import json
import textwrap
from pathlib import Path

import pytest

from oversim_tpu.analysis import ast_pass, findings as findings_mod
from oversim_tpu.analysis import contracts as contracts_mod
from oversim_tpu.analysis.hlo_text import (
    collective_census, donated_leaf_count, dtype_census, gather_counts,
    host_transfer_count)

REPO = Path(__file__).resolve().parent.parent


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# AST lint rules
# ---------------------------------------------------------------------------

def test_ast_hot_rules_fire():
    src = textwrap.dedent("""\
        import time
        import numpy as np
        import jax

        def tick(s):
            total = np.sum(s.buf)
            x = s.counters["sent"].item()
            y = float(x)
            z = jax.device_get(s)
            t0 = time.time()
            order = jnp.argsort(x)
            return total, y, z, t0, order
    """)
    fs = ast_pass.lint_source(src, "fixture.py", ast_pass.HOT_RULES)
    assert _rules(fs) == ["host-device-get", "host-float", "host-item",
                          "host-numpy", "sort-call", "wall-clock"]


def test_ast_wide_tier_is_narrower():
    src = "import numpy as np\ndef f(x):\n    return float(x)\n"
    assert ast_pass.lint_source(src, "w.py", ast_pass.WIDE_RULES) == []
    # but .item()/time.time()/state-leaf syncs still fire everywhere
    src2 = ("import time\n"
            "def f(s):\n"
            "    return s.x.item(), time.time(), int(s.t_now)\n")
    fs = ast_pass.lint_source(src2, "w.py", ast_pass.WIDE_RULES)
    assert _rules(fs) == ["device-sync", "host-item", "wall-clock"]


def test_ast_undonated_jit_rule():
    src = textwrap.dedent("""\
        from functools import partial
        import jax

        @partial(jax.jit, static_argnames=("self",))
        def run(s, n):
            return s

        @partial(jax.jit, static_argnames=("self",), donate_argnums=(0,))
        def run_ok(s, n):
            return s

        @jax.jit
        def helper(x):
            return x
    """)
    fs = ast_pass.lint_source(src, "f.py", ast_pass.HOT_RULES)
    assert [f.rule for f in fs] == ["undonated-jit"]
    assert "run(s" in fs[0].message


def test_ast_line_suppression():
    src = ("def f(s):\n"
           "    return int(s.tick)  # analysis: allow(device-sync)\n")
    assert ast_pass.lint_source(src, "f.py", ast_pass.HOT_RULES) == []


def test_ast_def_scope_suppression_covers_body():
    src = textwrap.dedent("""\
        def report(s):  # analysis: allow(host-float, device-sync)
            a = float(s.t_now)
            b = int(s.tick)
            return a, b

        def other(s):
            return float(s.t_now)
    """)
    fs = ast_pass.lint_source(src, "f.py", ast_pass.HOT_RULES)
    # only `other` (outside the def-scoped allow) still fires
    assert all(":7" in f.where for f in fs) and fs


def test_ast_bad_allow_is_a_finding():
    src = "x = 1  # analysis: allow(no-such-rule)\n"
    fs = ast_pass.lint_source(src, "f.py", ast_pass.HOT_RULES)
    assert _rules(fs) == ["bad-allow"]


def test_ast_jnp_prefix_not_confused_with_np():
    # jnp.* and names merely ending in "np" must not trip host-numpy
    src = ("import jax.numpy as jnp\n"
           "def f(x, me_np):\n"
           "    return jnp.sum(x) + len(me_np.items())\n")
    assert ast_pass.lint_source(src, "f.py", ("host-numpy",)) == []


def test_repo_tree_lints_clean():
    """The shipped tree (with its in-tree allow markers) has ZERO
    findings — this is the same gate run_suite.sh runs."""
    fs, summary = ast_pass.run(REPO)
    assert fs == [], [f.to_dict() for f in fs]
    assert summary["files_scanned"] > 50


# ---------------------------------------------------------------------------
# bytecode guards
# ---------------------------------------------------------------------------

def test_bytecode_guards(tmp_path):
    tree = tmp_path / "oversim_tpu"
    (tree / "__pycache__").mkdir(parents=True)
    (tree / "mod.py").write_text("x = 1\n")
    # healthy cache entry: ignored
    (tree / "__pycache__" / "mod.cpython-310.pyc").write_bytes(b"ok")
    fs = ast_pass.bytecode_findings(tmp_path)
    assert fs == []
    # legacy pyc next to sources: shadows imports
    (tree / "mod.pyc").write_bytes(b"bad")
    # orphan: source deleted, bytecode stays
    (tree / "__pycache__" / "gone.cpython-310.pyc").write_bytes(b"bad")
    fs = ast_pass.bytecode_findings(tmp_path)
    assert _rules(fs) == ["legacy-pyc", "orphan-pyc"]


def test_untracked_pycache_rule(tmp_path):
    """A __pycache__ dir NOT covered by .gitignore is a finding; adding
    `__pycache__/` to .gitignore clears it (scripts/ tree included)."""
    import subprocess
    subprocess.run(["git", "init", "-q", str(tmp_path)], check=True)
    tree = tmp_path / "scripts"
    (tree / "__pycache__").mkdir(parents=True)
    (tree / "x.py").write_text("x = 1\n")
    (tree / "__pycache__" / "x.cpython-311.pyc").write_bytes(b"ok")

    fs = ast_pass.bytecode_findings(tmp_path)
    [f] = [f for f in fs if f.rule == "untracked-pycache"]
    assert "scripts" in f.where and "__pycache__" in f.where

    (tmp_path / ".gitignore").write_text("__pycache__/\n")
    fs = ast_pass.bytecode_findings(tmp_path)
    assert [f for f in fs if f.rule == "untracked-pycache"] == []


# ---------------------------------------------------------------------------
# HLO text censuses (synthetic modules — no backend)
# ---------------------------------------------------------------------------

def test_collective_census_refines_all_reduce():
    txt = (
        "HloModule m\n"
        "%min_s64 (a: s64[], b: s64[]) -> s64[] { ... }\n"
        "ENTRY %main {\n"
        "  %ar = s64[] all-reduce(%x), replica_groups={}, "
        "to_apply=%min_s64\n"
        "  %ag = f64[8]{0} all-gather(%y), dimensions={0}\n"
        "  %ar2 = f64[] all-reduce-start(%z), to_apply=%add.7\n"
        "}\n")
    c = collective_census(txt)
    assert c == {"all-reduce:min": 1, "all-gather": 1, "all-reduce:add": 1}


def test_gather_counts_wide_vs_lane():
    """The sparse-plane census: a gather is "wide" when its RESULT
    keeps a full-width leading dim (N or P); [A]-lane gathers and
    all-gather collectives must not count."""
    txt = (
        "HloModule m\n"
        "ENTRY %main {\n"
        "  %g1 = f32[256,8,6]{2,1,0} gather(%pool, %idx), offset_dims={1}\n"
        "  %g2 = s32[2048]{0} gather(%pool, %due)\n"
        "  %g3 = s64[32]{0} gather(%fields, %act)\n"
        "  %g4 = pred[32,8]{1,0} gather(%mask, %act)\n"
        "  %ag = f64[256]{0} all-gather(%y), dimensions={0}\n"
        "}\n")
    c = gather_counts(txt, wide_dims=(256, 2048))
    assert c == {"gather_count": 4, "wide_gather_count": 2}
    # no wide dims supplied -> everything is lane-width
    assert gather_counts(txt)["wide_gather_count"] == 0
    assert gather_counts("")["gather_count"] == 0


def test_host_transfer_count():
    txt = ("ENTRY %e {\n"
           "  %t = token[] infeed(%tok)\n"
           "  %o = token[] outfeed(%v, %tok)\n"
           "  %c = f32[] custom-call(%x), custom_call_target="
           "\"xla_python_cpu_callback\"\n"
           "  %k = f32[] custom-call(%x), custom_call_target=\"topk\"\n"
           "}\n")
    assert host_transfer_count(txt) == 3


def test_dtype_census_and_allowlist():
    txt = ("  %a = f64[8]{0} add(%x, %y)\n"
           "  %b = bf16[4]{0} convert(%a)\n"
           "  %p = pred[] compare(%x, %y), direction=LT\n")
    c = dtype_census(txt)
    assert c["f64"] == 1 and c["bf16"] == 1 and c["pred"] == 1
    assert "bf16" not in contracts_mod.DEFAULT_DTYPES
    assert "f64" in contracts_mod.DEFAULT_DTYPES


def test_donated_leaf_count_reads_module_header():
    txt = ("HloModule jit_run_chunk, is_scheduled=true, "
           "input_output_alias={ {0}: (0, {}, may-alias), "
           "{1}: (1, {}, may-alias), {2}: (2, {}, must-alias) }, "
           "entry_computation_layout={...}\n"
           "ENTRY %main { ROOT %r = f32[] parameter(0) }\n")
    assert donated_leaf_count(txt) == 3
    assert donated_leaf_count("HloModule m\nENTRY %e {}\n") == 0


def test_hlo_breakdown_reexports_are_the_registry_helpers():
    """tests/test_hlo_budget.py pins semantics through the old import
    path; both names must be the SAME objects (shim, not fork)."""
    from scripts import hlo_breakdown
    from oversim_tpu.analysis import hlo_text
    assert hlo_breakdown.hlo_op_counts is hlo_text.hlo_op_counts
    assert hlo_breakdown.check_budget is hlo_text.check_budget
    assert (hlo_breakdown.check_telemetry_budget
            is hlo_text.check_telemetry_budget)


# ---------------------------------------------------------------------------
# contract registry
# ---------------------------------------------------------------------------

def test_registry_shape():
    names = list(contracts_mod.REGISTRY)
    assert names == ["solo_tick", "solo_chunk", "run_until_device",
                     "campaign_tick", "telemetry_tick", "service_window",
                     "daemon_window", "sparse_tick", "sparse_chunk",
                     "sharded_tick", "sharded_campaign_tick",
                     "resharded_resume"]
    tel = contracts_mod.REGISTRY["telemetry_tick"]
    assert tel.delta is not None and tel.delta.base == "solo_tick"
    for donated in ("solo_chunk", "run_until_device", "service_window",
                    "daemon_window"):
        assert contracts_mod.REGISTRY[donated].contract.require_donation
    camp = contracts_mod.REGISTRY["campaign_tick"].contract
    assert camp.collectives_enforced
    assert camp.allowed_collectives == frozenset()
    # sparse active-set entries: donation required, no new sorts or
    # collectives vs the dense base, and the wide-gather bound is a
    # REQUIRED reduction (negative) — the whole point of the plane
    sparse = contracts_mod.REGISTRY["sparse_tick"]
    assert sparse.contract.require_donation
    assert sparse.delta is not None and sparse.delta.base == "solo_tick"
    assert sparse.delta.max_sort_delta == 0
    assert sparse.delta.max_collective_delta == 0
    assert sparse.delta.max_wide_gather_delta is not None
    assert sparse.delta.max_wide_gather_delta < 0
    assert contracts_mod.REGISTRY["sparse_chunk"].contract.require_donation


def test_register_entry_validation():
    e = contracts_mod.REGISTRY["solo_tick"]
    with pytest.raises(ValueError):
        contracts_mod.register_entry(e)            # duplicate
    bad = contracts_mod.EntryPoint(
        name="new_entry", doc="", contract=contracts_mod.GraphContract(),
        build=e.build,
        delta=contracts_mod.DeltaContract(base="no_such_base"))
    with pytest.raises(ValueError):
        contracts_mod.register_entry(bad)          # dangling delta base
    with pytest.raises(KeyError):
        contracts_mod.entries(["bogus_entry"])


# ---------------------------------------------------------------------------
# verdict document + manifest feed
# ---------------------------------------------------------------------------

def test_document_and_verdict_summary(tmp_path):
    f = findings_mod.Finding(pass_name="hlo", rule="sorts", where="e",
                             message="m", measured=3, limit=0)
    info = findings_mod.Finding(pass_name="ast", rule="note", where="w",
                                message="fyi", severity="info")
    doc = findings_mod.document(
        [f, info], {"hlo": {"entries": {"solo_tick": {}}}}, fast=True)
    assert doc["kind"] == "graph_contract_verdict"
    assert doc["ok"] is False and doc["errors"] == 1
    assert doc["findings"][0]["pass"] == "hlo"
    v = findings_mod.verdict_summary(doc)
    assert v["entries"] == ["solo_tick"] and v["ok"] is False
    path = tmp_path / "v.json"
    findings_mod.write_document(doc, path)
    assert json.loads(path.read_text())["errors"] == 1


def test_run_manifest_picks_up_verdict(tmp_path, monkeypatch):
    from oversim_tpu import telemetry
    doc = findings_mod.document([], {"hlo": {"entries": {}}}, fast=True)
    path = tmp_path / "analysis.json"
    findings_mod.write_document(doc, path)
    monkeypatch.setenv("OVERSIM_ANALYSIS_VERDICT", str(path))
    man = telemetry.run_manifest(config=None, mesh=None)
    assert man["hlo_budget"]["ok"] is True
    monkeypatch.setenv("OVERSIM_ANALYSIS_VERDICT", str(tmp_path / "no"))
    assert telemetry.run_manifest()["hlo_budget"] is None


# ---------------------------------------------------------------------------
# seeded breaches through the CLI: one non-zero exit per pass
# ---------------------------------------------------------------------------

def _run_seed(which, tmp_path):
    from scripts import analyze
    out = tmp_path / f"seed_{which}.json"
    rc = analyze.main(["analyze.py", "--seed-breach", which,
                       "--json", str(out)])
    return rc, json.loads(out.read_text())


def test_seeded_ast_breach_exits_nonzero(tmp_path):
    rc, doc = _run_seed("ast", tmp_path)
    assert rc == 1 and doc["ok"] is False
    [f] = [f for f in doc["findings"] if f["rule"] == "host-item"]
    assert f["pass"] == "ast" and "fixture.py" in f["where"]


def test_seeded_hlo_breach_exits_nonzero(tmp_path):
    rc, doc = _run_seed("hlo", tmp_path)
    assert rc == 1 and doc["ok"] is False
    [f] = [f for f in doc["findings"] if f["rule"] == "full-pool-sorts"]
    assert f["pass"] == "hlo" and f["measured"] >= 1


def test_seeded_trace_breach_exits_nonzero(tmp_path):
    rc, doc = _run_seed("trace", tmp_path)
    assert rc == 1 and doc["ok"] is False
    [f] = [f for f in doc["findings"] if f["rule"] == "recompile"]
    assert f["pass"] == "trace" and f["measured"] == 1


def test_seeded_sparse_breach_exits_nonzero(tmp_path):
    """--seed-breach sparse: a planted compaction-on-top module pair
    diffed with the REAL sparse_tick delta contract — a +1 wide-gather
    delta where a reduction is required, pure-text, exits non-zero."""
    rc, doc = _run_seed("sparse", tmp_path)
    assert rc == 1 and doc["ok"] is False
    [f] = [f for f in doc["findings"] if f["rule"] == "delta-wide-gathers"]
    assert f["pass"] == "hlo" and f["measured"] == 1 and f["limit"] == -1
    d = doc["passes"]["sparse"]["entries"]["seeded_sparse"]["delta"]
    assert d["wide_gather_delta"] == 1 and d["gather_delta"] == 1


def test_seeded_shard_breach_exits_nonzero(tmp_path):
    """--seed-breach shard: a planted all-reduce:add + all-to-all vs the
    sharded tick's all-reduce:min-only collective allowlist — pure-text,
    no backend, exits non-zero."""
    rc, doc = _run_seed("shard", tmp_path)
    assert rc == 1 and doc["ok"] is False
    [f] = [f for f in doc["findings"] if f["rule"] == "collectives"]
    assert f["pass"] == "hlo"
    assert f["measured"] == {"all-reduce:add": 1, "all-to-all": 1}
    assert f["limit"] == ["all-reduce:min"]


def test_seeded_compile_breach_exits_nonzero(tmp_path):
    """--seed-breach compile: a toy entry vs a 0.0-second budget — the
    --compile-budget enforcement path exits non-zero with the timing in
    the JSON verdict."""
    rc, doc = _run_seed("compile", tmp_path)
    assert rc == 1 and doc["ok"] is False
    [f] = [f for f in doc["findings"] if f["rule"] == "compile-seconds"]
    assert f["pass"] == "hlo" and f["measured"] > 0 and f["limit"] == 0.0
    timing = (doc["passes"]["compile"]["entries"]["seeded_compile"]
              ["compile_seconds"])
    assert timing["total"] >= timing["compile"] >= 0


# ---------------------------------------------------------------------------
# trace harness internals
# ---------------------------------------------------------------------------

def test_host_sync_monitor_counts_bool_and_device_get():
    import jax
    import jax.numpy as jnp
    from oversim_tpu.analysis.trace_pass import HostSyncMonitor
    y = jax.jit(lambda x: x + 1)(jnp.arange(4))
    with HostSyncMonitor() as mon:
        assert bool(y[0] >= 0)
        jax.device_get(y)
    assert mon.syncs.get("__bool__", 0) >= 1
    assert mon.device_gets == 1
    # restored after exit
    before = dict(mon.syncs)
    bool(y[1] >= 0)
    assert mon.syncs == before


def test_trace_harness_clean_toy_passes():
    import jax
    import jax.numpy as jnp
    from oversim_tpu.analysis.trace_pass import harness_entry
    fn = jax.jit(lambda x: x * 3)
    built = contracts_mod.EntryBuild(
        fn=fn, make_args=lambda: (jnp.arange(8),), pool_dim=8)
    fs, stats = harness_entry("toy", built, contracts_mod.GraphContract())
    assert fs == []
    assert stats["recompiles"] == 0
