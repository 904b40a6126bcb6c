"""The harness's own rules (tests/conftest.py), checked without a
simulation: a module is one xdist work unit, units go out in cost
order, and a module that borrows another's tests owns their fixture.

Every simulation of tests/ lives in a module-scoped fixture; a module
dealt to two workers builds it twice (minutes of XLA-CPU each).
"""

import ast
import pathlib
import types

import pytest

import conftest

TESTS = pathlib.Path(__file__).parent


class _StubConfig:
    """As much of a pytest config as xdist's scope schedulers read."""

    def __init__(self):
        self.option = types.SimpleNamespace(loadscopereorder=True)

    def getvalue(self, name):
        assert name == "tx"
        return ["2*popen"]


def test_scheduler_keeps_a_module_on_one_worker():
    pytest.importorskip("xdist")
    from xdist.scheduler import LoadScopeScheduling

    config = _StubConfig()
    sched = conftest.pytest_xdist_make_scheduler(config, log=None)
    assert isinstance(sched, LoadScopeScheduling)
    scope = sched._split_scope
    one = scope("tests/test_a.py::test_x")
    assert scope("tests/test_a.py::test_y[p-q]") == one
    assert scope("tests/test_a.py::TestC::test_z") == one
    assert scope("tests/test_b.py::test_x") != one
    # units leave the queue as collected (COST_ORDER), not by test count
    assert config.option.loadscopereorder is False


def _item(name):
    return types.SimpleNamespace(path=pathlib.Path("tests") / name)


def test_collection_puts_the_costly_modules_first():
    first, second = conftest.COST_ORDER[:2]
    items = [_item("test_aaa.py"), _item(second), _item("test_zzz.py"),
             _item(first), _item(second)]
    tail_a, sec_1, tail_z, fst, sec_2 = items
    conftest.pytest_collection_modifyitems(items)
    assert items == [fst, sec_1, sec_2, tail_a, tail_z]


def test_cost_order_names_real_files():
    assert len(set(conftest.COST_ORDER)) == len(conftest.COST_ORDER)
    missing = [n for n in conftest.COST_ORDER if not (TESTS / n).is_file()]
    assert not missing, missing


def _module_fixtures(tree):
    """Names of the module-scoped fixtures a parsed test file defines."""
    names = set()
    for node in tree.body:
        if not isinstance(node, ast.FunctionDef):
            continue
        for dec in node.decorator_list:
            if (isinstance(dec, ast.Call)
                    and ast.unparse(dec.func).endswith("fixture")
                    and any(kw.arg == "scope"
                            and ast.literal_eval(kw.value) == "module"
                            for kw in dec.keywords)):
                names.add(node.name)
    return names


def test_borrowed_tests_come_with_their_fixture():
    """``from test_x import test_y`` collects test_y in the importing
    module too, against THAT module's fixtures.  The importer must
    define every module-scoped fixture the borrowed tests take, or they
    would silently run against nothing the module built."""
    trees = {p.stem: ast.parse(p.read_text())
             for p in TESTS.glob("test_*.py")}
    fixtures = {name: _module_fixtures(tree) for name, tree in trees.items()}
    bad = []
    for name, tree in trees.items():
        for node in ast.walk(tree):
            if not (isinstance(node, ast.ImportFrom)
                    and node.module in trees):
                continue
            borrowed = {a.name for a in node.names
                        if a.name.startswith("test_")}
            for fn in trees[node.module].body:
                if isinstance(fn, ast.FunctionDef) and fn.name in borrowed:
                    lacks = ({a.arg for a in fn.args.args}
                             & fixtures[node.module]) - fixtures[name]
                    if lacks:
                        bad.append((name, fn.name, sorted(lacks)))
    assert not bad, bad
