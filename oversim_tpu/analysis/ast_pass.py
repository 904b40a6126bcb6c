"""AST lint pass: host-hazard rules over the hot-path layers.

The compiled-graph contracts (hlo_pass.py) catch a regression AFTER it
reaches XLA; this pass catches the source patterns that cause them —
host numpy / ``.item()`` / ``float()`` / ``jax.device_get`` /
``time.time()`` inside the hot-path modules, ``lax.sort`` family calls
that no ``allow(sort-call)`` marker owns up to, un-donated ``jit``
decorators on state-carrying functions, and silent host reads of
SimState leaves anywhere in the package.

Rule tiers
----------
* HOT tier (``oversim_tpu/engine``, ``overlay``, ``campaign``,
  ``service/loop.py``): every rule.  Host-side reporting functions that
  legitimately touch numpy/floats are tagged in-tree.
* WIDE tier (the rest of ``oversim_tpu``): only the rules that are
  hazards everywhere — ``.item()``, ``time.time()`` wall-clock reads,
  and ``device-sync`` (``float()``/``int()``/``np.asarray()`` directly
  over a SimState leaf attribute — an implicit device→host sync).

Suppressions
------------
``# analysis: allow(host-numpy, host-float)`` on the offending line
suppresses those rules for that line; on a ``def`` line it suppresses
them for the whole function body — host-side functions inside hot-path modules carry
one def-level marker each, so the allowlist is greppable in-tree
(``grep -rn 'analysis: allow'``).  An ``allow`` naming an unknown rule
is itself a finding (``bad-allow``) so stale markers can't rot.

Bytecode guards
---------------
``scan`` also walks the target trees for bytecode that could shadow
sources: legacy ``*.pyc`` files OUTSIDE ``__pycache__`` (importable in
place of a ``.py``), orphaned ``__pycache__/*.pyc`` whose source is
gone, and git-TRACKED bytecode (committed ``.pyc`` shadowed a source
edit once before — PR 1 removed one).
"""

from __future__ import annotations

import ast
import dataclasses
import re
import subprocess
from pathlib import Path

from oversim_tpu.analysis.findings import Finding

# -- rule registry -----------------------------------------------------------

RULES = {
    "host-numpy": "host numpy (np.*) in a hot-path module — traced code "
                  "must use jnp; host reporting needs an allow marker",
    "host-item": ".item() forces a device→host sync",
    "host-float": "float(...) in a hot-path module — a tracer here would "
                  "force a host sync; host-side math needs an allow marker",
    "host-device-get": "jax.device_get in a hot-path module — fetches "
                       "belong to the designated window-drain points",
    "wall-clock": "time.time() is not monotonic — use time.monotonic()/"
                  "perf_counter() for intervals and pacing",
    "sort-call": "lax/jnp sort-family call — the tick is pinned "
                 "zero-full-pool-sort; every sort site must be "
                 "explicitly allowlisted",
    "undonated-jit": "jit on a state-carrying function without "
                     "donate_argnums — every chunk round-trips the "
                     "state through fresh allocations",
    "device-sync": "float()/int()/np.asarray() directly over a SimState "
                   "leaf — an implicit device→host sync",
    "bad-allow": "allow marker names an unknown rule",
    "legacy-pyc": "*.pyc outside __pycache__ can shadow its source",
    "orphan-pyc": "__pycache__ bytecode whose source file is gone",
    "tracked-bytecode": "bytecode committed to git can shadow source edits",
    "untracked-pycache": "__pycache__ not git-ignored — stray bytecode "
                         "pollutes grep/status and is one `git add .` "
                         "from being committed",
    "obs-import": "oversim_tpu.obs import outside the obs package — the "
                  "live observability plane is host-runner-only "
                  "(scripts/, bench.py); in-package code takes "
                  "tracer/observer objects as duck-typed parameters",
}

HOT_RULES = ("host-numpy", "host-item", "host-float", "host-device-get",
             "wall-clock", "sort-call", "undonated-jit", "device-sync",
             "obs-import")
WIDE_RULES = ("host-item", "wall-clock", "device-sync", "obs-import")

# hot-path layers (ISSUE/ROADMAP: the modules whose compiled graphs the
# HLO contracts pin) — paths relative to the repo root
HOT_PATHS = ("oversim_tpu/engine", "oversim_tpu/overlay",
             "oversim_tpu/campaign", "oversim_tpu/service/loop.py")
WIDE_PATH = "oversim_tpu"

# SimState leaves whose direct host conversion is an implicit sync
STATE_LEAF_ATTRS = frozenset({
    "t_now", "tick", "alive", "node_keys", "pool", "stats", "counters",
    "telemetry", "churn", "malicious"})

_SORT_NAMES = frozenset({"sort", "argsort", "lexsort"})
_STATEISH_PARAMS = frozenset({"s", "cs", "state", "carry"})

_ALLOW_RE = re.compile(r"#\s*analysis:\s*allow\(([^)]*)\)")


# -- suppression map ---------------------------------------------------------

def _parse_allows(src: str) -> dict:
    """line number -> set of rule names allowed on that line."""
    allows = {}
    for i, line in enumerate(src.splitlines(), 1):
        m = _ALLOW_RE.search(line)
        if m:
            allows[i] = {r.strip() for r in m.group(1).split(",")
                         if r.strip()}
    return allows


class _Suppressions:
    """Per-line allows + def-scope allows (marker on the ``def`` line
    covers the whole function body, nested defs included)."""

    def __init__(self, tree: ast.AST, allows: dict):
        self.line_allows = allows
        self.spans = []       # (first, last, rules)
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # the marker may sit on ANY signature line (multi-line
                # defs put it after the closing paren)
                sig_end = (node.body[0].lineno - 1 if node.body
                           else node.lineno)
                rules = set()
                for ln in range(node.lineno, sig_end + 1):
                    rules |= allows.get(ln, set())
                if rules:
                    self.spans.append(
                        (node.lineno, node.end_lineno, rules))

    def allowed(self, line: int, rule: str) -> bool:
        if rule in self.line_allows.get(line, ()):
            return True
        return any(a <= line <= b and rule in rules
                   for a, b, rules in self.spans)

    def bad_allows(self) -> list:
        return [(ln, r) for ln, rules in self.line_allows.items()
                for r in sorted(rules) if r not in RULES]


# -- the visitor -------------------------------------------------------------

def _base_name(node):
    """Leftmost Name id of an attribute chain (jax.lax.sort -> 'jax')."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _mentions_state_leaf(node) -> bool:
    return any(isinstance(n, ast.Attribute) and n.attr in STATE_LEAF_ATTRS
               for n in ast.walk(node))


def _jit_decorator_kind(dec):
    """('jit'|'partial-jit'|None, has_donation) for a decorator node."""
    def is_jit(n):
        return ((isinstance(n, ast.Attribute) and n.attr == "jit")
                or (isinstance(n, ast.Name) and n.id == "jit"))

    if is_jit(dec):
        return "jit", False
    if isinstance(dec, ast.Call):
        if is_jit(dec.func):
            donated = any(kw.arg and kw.arg.startswith("donate")
                          for kw in dec.keywords)
            return "jit", donated
        if (isinstance(dec.func, ast.Name) and dec.func.id == "partial"
                and dec.args and is_jit(dec.args[0])):
            donated = any(kw.arg and kw.arg.startswith("donate")
                          for kw in dec.keywords)
            return "partial-jit", donated
    return None, False


class _Linter(ast.NodeVisitor):
    def __init__(self, rules, rel, sup):
        self.rules = frozenset(rules)
        self.rel = rel
        self.sup = sup
        self.findings = []
        self._seen = set()

    def _emit(self, node, rule, message, measured=None):
        if rule not in self.rules:
            return
        line = getattr(node, "lineno", 0)
        if self.sup.allowed(line, rule):
            return
        key = (line, rule)
        if key in self._seen:
            return
        self._seen.add(key)
        self.findings.append(Finding(
            pass_name="ast", rule=rule, where=f"{self.rel}:{line}",
            message=message, measured=measured, limit="0 occurrences"))

    # imports ---------------------------------------------------------------
    def visit_Import(self, node):
        for alias in node.names:
            if alias.name.split(".")[0] == "numpy":
                self._emit(node, "host-numpy",
                           "imports numpy in a hot-path module")
            if alias.name.split(".")[:2] == ["oversim_tpu", "obs"]:
                self._emit(node, "obs-import",
                           f"imports {alias.name} inside the package")
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        if node.module and node.module.split(".")[0] == "numpy":
            self._emit(node, "host-numpy",
                       "imports from numpy in a hot-path module")
        if node.module:
            parts = node.module.split(".")
            if parts[:2] == ["oversim_tpu", "obs"]:
                self._emit(node, "obs-import",
                           f"imports from {node.module} inside the "
                           f"package")
            elif parts == ["oversim_tpu"] and any(
                    alias.name == "obs" for alias in node.names):
                self._emit(node, "obs-import",
                           "imports obs from oversim_tpu inside the "
                           "package")
        self.generic_visit(node)

    # attribute / call rules ------------------------------------------------
    def visit_Attribute(self, node):
        if isinstance(node.value, ast.Name) and node.value.id == "np":
            self._emit(node, "host-numpy", f"np.{node.attr} host-numpy use")
        if node.attr == "device_get":
            self._emit(node, "host-device-get", "jax.device_get call site")
        self.generic_visit(node)

    def visit_Call(self, node):
        f = node.func
        if isinstance(f, ast.Name):
            if f.id == "float":
                self._emit(node, "host-float", "float(...) call")
            if f.id in ("float", "int") and any(
                    _mentions_state_leaf(a) for a in node.args):
                self._emit(node, "device-sync",
                           f"{f.id}(...) over a SimState leaf")
        elif isinstance(f, ast.Attribute):
            if f.attr == "item" and not node.args:
                self._emit(node, "host-item", ".item() call")
            if (f.attr == "time" and isinstance(f.value, ast.Name)
                    and f.value.id == "time"):
                self._emit(node, "wall-clock", "time.time() call")
            if f.attr in _SORT_NAMES:
                base = _base_name(f.value)
                is_lax = (isinstance(f.value, ast.Attribute)
                          and f.value.attr == "lax")
                if base in ("jnp", "lax", "jax", "np") or is_lax:
                    self._emit(node, "sort-call",
                               f"{ast.unparse(f)} call")
            if (f.attr in ("asarray", "array")
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "np"
                    and any(_mentions_state_leaf(a) for a in node.args)):
                self._emit(node, "device-sync",
                           f"np.{f.attr}(...) over a SimState leaf")
        self.generic_visit(node)

    # donation rule ---------------------------------------------------------
    def _first_real_param(self, node):
        args = [a.arg for a in node.args.args if a.arg not in ("self",
                                                               "cls")]
        return args[0] if args else None

    def visit_FunctionDef(self, node, _async=False):
        for dec in node.decorator_list:
            kind, donated = _jit_decorator_kind(dec)
            if kind and not donated:
                first = self._first_real_param(node)
                if first in _STATEISH_PARAMS:
                    self._emit(
                        dec, "undonated-jit",
                        f"jit of {node.name}({first}, ...) without "
                        f"donate_argnums — the state buffer is copied "
                        f"every call")
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef


# -- file / tree scanning ----------------------------------------------------

def lint_source(src: str, rel: str, rules=HOT_RULES) -> list:
    """Lint one module's source text; returns Finding rows."""
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding(pass_name="ast", rule="syntax",
                        where=f"{rel}:{e.lineno or 0}",
                        message=f"does not parse: {e.msg}")]
    sup = _Suppressions(tree, _parse_allows(src))
    linter = _Linter(rules, rel, sup)
    linter.visit(tree)
    for line, rule in sup.bad_allows():
        linter.findings.append(Finding(
            pass_name="ast", rule="bad-allow", where=f"{rel}:{line}",
            message=f"allow({rule}) names an unknown rule "
                    f"(known: {', '.join(sorted(RULES))})"))
    return linter.findings


def _is_hot(rel: str) -> bool:
    rel = rel.replace("\\", "/")
    return any(rel == p or rel.startswith(p.rstrip("/") + "/")
               for p in HOT_PATHS)


def iter_targets(root: Path):
    """(path, rel, rules) for every scanned module under ``root``."""
    for path in sorted((root / WIDE_PATH).rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        rel = str(path.relative_to(root))
        rules = HOT_RULES if _is_hot(rel) else WIDE_RULES
        if rel.replace("\\", "/").startswith("oversim_tpu/obs/"):
            # the plane may of course import itself
            rules = tuple(r for r in rules if r != "obs-import")
        yield path, rel, rules


def bytecode_findings(root: Path,
                      trees=("oversim_tpu", "scripts", "tests")) -> list:
    """Stale/shadowing-bytecode + __pycache__-hygiene guards over the
    source trees — the runner entry points under ``scripts/`` are
    covered the same as the package (a stale scripts/__pycache__ once
    fed binary .pyc matches into every repo grep)."""
    out = []
    pycache_dirs = []
    for tree in trees:
        base = root / tree
        if not base.is_dir():
            continue
        pycache_dirs.extend(sorted(
            p for p in base.rglob("__pycache__") if p.is_dir()))
        for pyc in sorted(base.rglob("*.pyc")):
            rel = str(pyc.relative_to(root))
            if "__pycache__" not in pyc.parts:
                out.append(Finding(
                    pass_name="ast", rule="legacy-pyc", where=rel,
                    message="bytecode outside __pycache__ shadows its "
                            "source on import — delete it"))
                continue
            src_name = pyc.name.split(".")[0] + ".py"
            if not (pyc.parent.parent / src_name).exists():
                out.append(Finding(
                    pass_name="ast", rule="orphan-pyc", where=rel,
                    message=f"orphaned bytecode: {src_name} no longer "
                            f"exists next to its __pycache__"))
    try:
        r = subprocess.run(
            ["git", "ls-files", "*.pyc", "**/__pycache__/*"],
            capture_output=True, text=True, timeout=15, cwd=root)
        tracked = [ln for ln in r.stdout.splitlines() if ln.strip()]
    except (OSError, subprocess.TimeoutExpired):
        tracked = []
    for rel in tracked:
        out.append(Finding(
            pass_name="ast", rule="tracked-bytecode", where=rel,
            message="bytecode is committed to git — `git rm --cached` "
                    "it and keep __pycache__/ in .gitignore"))
    if pycache_dirs:
        rels = [str(p.relative_to(root)) for p in pycache_dirs]
        try:
            # rc 0 = some ignored, 1 = none ignored; 128 (not a git
            # work tree) skips the rule rather than spamming findings
            r = subprocess.run(["git", "check-ignore", *rels],
                               capture_output=True, text=True,
                               timeout=15, cwd=root)
            if r.returncode in (0, 1):
                ignored = set(r.stdout.splitlines())
                for rel in rels:
                    if rel not in ignored:
                        out.append(Finding(
                            pass_name="ast", rule="untracked-pycache",
                            where=rel,
                            message="__pycache__ is not git-ignored — "
                                    "add `__pycache__/` to .gitignore "
                                    "so bytecode never reaches grep or "
                                    "a commit"))
        except (OSError, subprocess.TimeoutExpired):
            pass
    return out


def run(root, *, include_bytecode_guards: bool = True):
    """The whole AST pass: (findings, summary-dict)."""
    root = Path(root)
    findings = []
    files = 0
    for path, rel, rules in iter_targets(root):
        files += 1
        findings.extend(lint_source(
            path.read_text(encoding="utf-8"), rel, rules))
    if include_bytecode_guards:
        findings.extend(bytecode_findings(root))
    summary = {"files_scanned": files,
               "rules": {"hot": list(HOT_RULES), "wide": list(WIDE_RULES)},
               "findings": len(findings)}
    return findings, summary
