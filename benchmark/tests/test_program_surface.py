"""What ``program.py`` reads of the program that is no documented
interface (``program.SURFACE``, ``MsgPool``'s column views, the jitted
loop) is looked up by name: when a PR to the program renames or repacks
one of them, this test and every run fail with that name, not somewhere
in the comparison.  Half a minute on the CPU (one ``sim.init`` at N=64).
"""

import copy
import dataclasses
import types

import numpy as np
import pytest

import cells
import program

N = 64


@pytest.fixture(scope="module")
def state():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, bench["workloads"][0]["name"])
    prog = program.Program(copy.deepcopy(cell["config"]), cell["traffic"],
                           1, n=N, persistent_cache=False)
    return prog, prog.init(3)


@pytest.mark.parametrize("path", program.SURFACE)
def test_the_state_has_every_leaf_the_comparison_reads(state, path):
    _, s = state
    assert program.leaf(s, path) is not None


def test_the_pool_columns_come_from_the_pools_own_views(state):
    prog, s = state
    cols = program.pool_columns(s.pool)
    assert set(cols) == set(program.POOL_VIEWS)
    scalars = [int(cols[k]) for k in program.POOL_VIEWS if k != "key"]
    assert len(set(scalars)) == len(scalars)
    assert len(cols["key"]) * 32 == prog.wire()["key_bits"]
    assert not set(scalars) & set(int(c) for c in cols["key"])
    # ... and they find a message's fields in the block as the pool does
    pool = s.pool
    blk = np.asarray(pool.blk)
    for name in ("src", "dst", "kind", "size_b"):
        assert (blk[:, cols[name]] == np.asarray(getattr(pool, name))).all()
    assert (blk[:, cols["key"]].view(np.uint32)
            == np.asarray(pool.key).view(np.uint32)).all()
    prog.check_surface(s)


@pytest.mark.parametrize("path", ["pool.blk", "logic.lk.pending_dst",
                                  "logic.app.t_test", "underlay.coords"])
def test_a_missing_leaf_fails_loudly_by_name(state, path):
    _, s = state
    *parents, last = path.split(".")

    def without(node, parts):
        """A copy of ``node`` whose leaf ``parts`` is gone."""
        fields = {f.name: getattr(node, f.name)
                  for f in dataclasses.fields(node)}
        if len(parts) == 1:
            del fields[parts[0]]
        else:
            fields[parts[0]] = without(fields[parts[0]], parts[1:])
        return types.SimpleNamespace(**fields)

    broken = without(s, path.split("."))
    with pytest.raises(program.SurfaceError) as err:
        program.leaf(broken, path)
    assert repr(path) in str(err.value) and repr(last) in str(err.value)


def test_every_read_back_carries_the_four_kbr_counters(state):
    """The window's read-back brings the counters the counted stretch is
    read from, as ``counters`` reads them, and a state without one fails
    with its name."""
    prog, s = state
    assert program.KBR_COUNTERS == (
        "stats.c:kbr_sent", "stats.c:kbr_delivered",
        "stats.c:kbr_lookup_failed", "stats.c:kbr_wrong_node")
    assert set(program.KBR_COUNTERS) <= set(program.SURFACE)
    snap, full = prog.payloads(s), prog.counters(s)
    assert set(snap["stats"]) == {k.partition(".")[2]
                                  for k in program.KBR_COUNTERS}
    for name, value in snap["stats"].items():
        assert isinstance(value, int) and value == int(full["stats"][name])
    assert snap["t_now_ns"] == full["t_now_ns"]
    broken = types.SimpleNamespace(stats={
        k: v for k, v in s.stats.items() if k != "c:kbr_wrong_node"})
    with pytest.raises(program.SurfaceError) as err:
        program.leaf(broken, "stats.c:kbr_wrong_node")
    assert "'stats.c:kbr_wrong_node'" in str(err.value)


def test_a_pool_without_a_view_fails_loudly_by_name():
    @dataclasses.dataclass
    class Repacked:
        blk: np.ndarray

        @property
        def src(self):
            return self.blk[:, 0]

    with pytest.raises(program.SurfaceError) as err:
        program.pool_columns(Repacked(np.zeros((1, 8), np.int32)))
    assert "'dst'" in str(err.value)


def test_a_deployment_across_chips_brings_its_own_program_file(tmp_path):
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench, bench["workloads"][0]["name"])
    with pytest.raises(ValueError):
        program.Program(cell["config"], cell["traffic"], 4, n=N)
    (tmp_path / "program_mesh.py").write_text("class Program:\n    four = 4\n")
    mod = cells.load_program({"program": "program_mesh"}, here=str(tmp_path))
    assert mod.Program.four == 4
    assert cells.load_program({}).Program is not None
