"""A profiler dump reduced by the tick program's named scopes
(``phase_reduce.py``): on made-up lines, on a dump the CPU backend
writes (the HLO a dump carries), and on a slice recorded on the chip
(``data/phase_sample.json``, cut by ``phase_reduce.sample``)."""

import copy
import json
import os

import pytest

import phase_reduce as pr
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "phase_sample.json")
TICK = "jit(_run_until_device)/while/body/while/body/closed_call/"


# -- an operation's place ----------------------------------------------------

def test_wrappers_are_stripped_and_the_innermost_part_names_the_operation():
    phase, part, frames = pr.place(
        TICK + "phase.node_step/while/body/kademlia.routing_add/"
        "kademlia.bucket_update/vmap(jit(searchsorted))/vmap()/while/body/"
        "closed_call/gather")
    assert phase == "phase.node_step"
    assert part == "kademlia.bucket_update"
    assert frames == (
        ("loop/while", "body"), ("loop/while", "body"),
        ("phase.node_step/while", "body"),
        ("phase.node_step/kademlia.bucket_update/while", "body"))
    assert pr.components("a/vmap(b.c/jit(sort))/sort") == [
        "a", "b.c", "sort", "sort"]
    # a part inside vmap reads as the part it is
    assert pr.place(TICK + "phase.node_step/vmap(lookup.pump)/mul")[:2] == (
        "phase.node_step", "lookup.pump")


def test_an_operation_without_a_phase_is_unscoped():
    assert pr.place("") == (pr.UNSCOPED, "", None)
    assert pr.place(TICK[:-1]) == (
        pr.UNSCOPED, "", (("loop/while", "body"), ("loop/while", "body")))
    # a part's name above every phase names nothing
    assert pr.place("jit(f)/pool.alloc/add")[:2] == (pr.UNSCOPED, "")


def test_a_boundary_is_the_outermost_frame_two_places_differ_in():
    tick = (("loop/while", "body"), ("loop/while", "body"))
    straight = pr.place(TICK + "phase.closing/closing.compact/ge")[2]
    branch = pr.place(TICK + "phase.closing/cond/branch_1_fun/"
                      "closing.compact/cumsum")[2]
    other = pr.place(TICK + "phase.closing/cond/branch_0_fun/add")[2]
    assert straight == tick
    assert pr.boundary(straight, branch) == "phase.closing/cond"
    assert pr.boundary(branch, straight) == "phase.closing/cond"
    assert pr.boundary(branch, other) == "phase.closing/cond"
    assert pr.boundary(branch, branch) == ""
    body = pr.place(TICK + "phase.node_step/while/body/step.gather/g")[2]
    cond = pr.place(TICK + "phase.node_step/while/cond/lt")[2]
    assert pr.boundary(body, cond) == "phase.node_step/while"
    assert pr.boundary(straight, body) == "phase.node_step/while"
    # the run loop's own test, between two dispatches' scans
    assert pr.boundary(tick, tick[:1] + (("loop/while", "cond"),)) == \
        "loop/while"


# -- the table ---------------------------------------------------------------

def op(name, start, dur, path):
    """``path`` under the tick's scan; None: the scan body's own level
    (where XLA's copies of the carry sit); "": no op_name at all."""
    full = TICK[:-1] if path is None else (TICK + path if path else "")
    return (name, float(start), float(dur), full)


def a_run(t0=0.0):
    """One made-up program run of one tick: 1000 ns."""
    ops = [
        ("while.1", t0, 1000.0, "jit(_run_until_device)/while"),
        op("fusion.1", t0 + 0, 100, "phase.horizon/reduce_min"),
        op("fusion.2", t0 + 100, 200, "phase.inbox_select/pool.due_masks/lt"),
        op("cond.1", t0 + 320, 180, "phase.inbox_select/cond"),
        op("sort.1", t0 + 350, 150,
           "phase.inbox_select/cond/branch_1_fun/inbox.rank/sort"),
        op("while.2", t0 + 500, 300, "phase.node_step/while"),
        op("fusion.3", t0 + 520, 80,
           "phase.node_step/while/body/step.gather/gather"),
        op("fusion.4", t0 + 600, 150,
           "phase.node_step/while/body/vmap(kademlia.find_node)/sort"),
        op("copy.9", t0 + 800, 50, None),
        op("fusion.5", t0 + 850, 150, "phase.closing/pool.alloc/cumsum"),
    ]
    return ops, [("jit__run_until_device(1)", t0, 1000.0, "")]


def lines(ops, mods):
    return {tr.OPS_LINE: list(ops), tr.MODULES_LINE: list(mods)}


def plain(events):
    return [e[:3] for e in events]


def test_the_rows_sum_to_trace_reduce_s_busy_time():
    ops, mods = a_run()
    table = pr.reduce_device(lines(ops, mods))
    busy = tr.reduce_device(lines(plain(ops), plain(mods)))["busy_ns"]
    assert busy == 100 + 200 + 150 + 80 + 150 + 50 + 150
    for group in ("phases", "parts"):
        assert sum(r["device_s"] for r in table[group].values()) \
            == pytest.approx(busy / 1e9)
    assert table["busy_s"] == pytest.approx(busy / 1e9)
    ph = table["phases"]
    assert ph["phase.inbox_select"]["device_s"] == pytest.approx(350e-9)
    assert ph["phase.inbox_select"]["leaf_ops"] == 2
    assert ph["phase.inbox_select"]["median_leaf_us"] == pytest.approx(0.175)
    assert ph[pr.UNSCOPED]["device_s"] == pytest.approx(50e-9)
    assert table["scoped_share"] == pytest.approx(1 - 50 / busy)
    parts = table["parts"]
    assert parts["phase.node_step/kademlia.find_node"]["leaf_ops"] == 1
    assert parts["phase.horizon/-"]["device_s"] == pytest.approx(100e-9)
    assert table["unscoped_ops"] == [["copy.9", pytest.approx(50e-9)]]
    # overlapping leaves are counted once, as the union counts them
    ops2 = ops + [op("fusion.6", 900, 150, "phase.closing/stats.record/add")]
    t2 = pr.reduce_device(lines(ops2, [("m", 0.0, 1100.0, "")]))
    assert sum(r["device_s"] for r in t2["phases"].values()) == \
        pytest.approx(tr.reduce_device(
            lines(plain(ops2), [("m", 0.0, 1100.0)]))["busy_ns"] / 1e9)


def test_idle_inside_a_run_goes_to_the_boundary_that_holds_it():
    ops, mods = a_run()
    table = pr.reduce_device(lines(ops, mods))
    idle = table["in_program_idle"]
    # 300 -> 350: into the selection's cond; 500 -> 520: into the rounds'
    # while; 750 -> 800: out of it, ended by a copy of the carry
    assert idle["phase.inbox_select/cond"] == pytest.approx(50e-9)
    assert idle["phase.node_step/while"] == pytest.approx(70e-9)
    assert table["in_program_idle_s"] == pytest.approx(120e-9)
    assert table["in_program_idle_after_scalar_s"] == 0
    scalar = [e if e[0] != "fusion.2" else
              op("fusion.2 f32[]", 100, 200, "phase.closing/stats.record/max")
              for e in ops]
    assert pr.reduce_device(lines(scalar, mods))[
        "in_program_idle_after_scalar_s"] == pytest.approx(50e-9)
    assert table["phases"]["phase.inbox_select"]["idle_after_s"] == \
        pytest.approx(50e-9)
    assert table["phases"][pr.UNSCOPED]["idle_after_s"] == \
        pytest.approx(50e-9)
    # a gap planted before the closing phase's cond is named by it
    late = [e if e[0] != "fusion.5" else
            op("fusion.5", 900, 100, "phase.closing/cond/branch_0_fun/"
               "pool.alloc/cumsum") for e in ops]
    t2 = pr.reduce_device(lines(late, mods))
    assert t2["in_program_idle"]["phase.closing/cond"] == pytest.approx(50e-9)
    # straight-line idle is named by the phases it lies between
    gap = [e if e[0] != "fusion.2" else
           op("fusion.2", 130, 170, "phase.inbox_select/pool.due_masks/lt")
           for e in ops]
    t3 = pr.reduce_device(lines(gap, mods))
    assert t3["in_program_idle"]["phase.horizon -> phase.inbox_select"] == \
        pytest.approx(30e-9)
    # an operation that does not say where it sits names no boundary
    mute = [e if e[0] != "copy.9" else op("copy.9", 800, 50, "")
            for e in ops]
    t4 = pr.reduce_device(lines(mute, mods))
    assert t4["in_program_idle"][pr.UNSCOPED] == pytest.approx(50e-9)
    assert t4["in_program_idle"]["phase.node_step/while"] == \
        pytest.approx(20e-9)


def test_idle_between_runs_goes_to_the_host_s_innermost_events():
    ops0, mods0 = a_run(0.0)
    ops1, mods1 = a_run(3000.0)
    host = {"python": [("bench.dispatch", -100.0, 1150.0),
                       ("bench.readback", 1100.0, 1500.0),
                       ("device_get", 1200.0, 1000.0),
                       ("bench.dispatch", 2700.0, 1400.0)],
            "other/1": [("TransferFromDevice", 1500.0, 500.0)]}
    assert pr.innermost(host["python"]) == [
        ("bench.dispatch", -100.0, 1050.0), ("bench.readback", 1100.0, 1200.0),
        ("device_get", 1200.0, 2200.0), ("bench.readback", 2200.0, 2600.0),
        ("bench.dispatch", 2700.0, 4100.0)]
    out = pr.reduce_trace({"devices": {0: lines(ops0 + ops1, mods0 + mods1)},
                           "host": host}, ticks=2)
    table = out["devices"]["0"]
    assert table["program_runs"] == 2 and table["ticks"] == 2
    assert table["between_runs_idle_s"] == pytest.approx(2000e-9)
    named = table["between_runs_idle"]
    assert named["python: device_get"] == pytest.approx(1000e-9)
    assert named["python: bench.readback"] == pytest.approx(500e-9)
    assert named["python: bench.dispatch"] == pytest.approx(350e-9)
    assert named["other: TransferFromDevice"] == pytest.approx(500e-9)
    assert named["no host event"] == pytest.approx(150e-9)
    # nothing between the runs is taken for idle inside one
    assert table["in_program_idle_s"] == pytest.approx(240e-9)


def test_a_trace_with_15_percent_unscoped_busy_time_is_refused():
    ops, mods = a_run()
    # the unscoped copy made 150 of 1000 busy ns
    ops = [e if e[0] != "copy.9" else op("copy.9", 750, 100, None)
           for e in ops if e[0] != "fusion.1"]
    ops.append(op("copy.9", 0, 50, None))
    table = pr.reduce_device(lines(ops, mods))
    assert table["scoped_share"] == pytest.approx(1 - 150 / 880)
    with pytest.raises(pr.PhaseError, match="copy.9"):
        pr.reduce_trace({"devices": {0: lines(ops, mods)}, "host": {}}, 1)
    # the parent's program names no phase at all
    bare = [(n, s, d, p.replace("phase.", "").replace(".", "_"))
            for n, s, d, p in a_run()[0]]
    with pytest.raises(pr.PhaseError, match="0.0%"):
        pr.reduce_trace({"devices": {0: lines(bare, mods)}, "host": {}}, 1)
    with pytest.raises(tr.TraceError):
        pr.reduce_trace({"devices": {0: lines([], mods)}, "host": {}}, 1)


def test_a_two_device_trace_gives_two_tables():
    ops0, mods0 = a_run()
    ops1, mods1 = a_run()
    ops1 = [e for e in ops1 if e[0] not in ("sort.1", "cond.1")]
    out = pr.reduce_trace({"devices": {0: lines(ops0, mods0),
                                       1: lines(ops1, mods1),
                                       2: lines([], [])}, "host": {}})
    assert sorted(out["devices"]) == ["0", "1"]
    assert out["busiest"] == "0"
    assert out["devices"]["1"]["busy_s"] == pytest.approx(730e-9)
    assert "phase.inbox_select/inbox.rank" not in out["devices"]["1"]["parts"]
    # nobody said how many ticks: phase.horizon's operations run once each
    assert out["devices"]["0"]["ticks"] == 1


# -- the HLO a dump carries -----------------------------------------------------

def test_instruction_names_come_from_the_dump_s_own_hlo(tmp_path):
    """On the CPU backend: the dump of a jitted function under scopes
    holds its HLO, and every instruction's op_name is read from it with
    no proto library."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("phase.one"):
            y = x @ x
            with jax.named_scope("part.two"):
                y = jax.lax.while_loop(lambda c: c.sum() < 1e9,
                                       lambda c: c * 2 + jnp.sort(c), y)
            return y.sum()

    g = jax.jit(f)
    x = jnp.ones((64, 64))
    g(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    g(x).block_until_ready()
    jax.profiler.stop_trace()
    names = pr.op_names_of(pr.find_xplane(str(tmp_path)))
    where = {pr.place(v)[:2] for v in names.values()}
    assert ("phase.one", "part.two") in where and ("phase.one", "") in where
    sorts = [v for k, v in names.items() if k.startswith("sort")]
    assert sorts and all(
        pr.place(v) == ("phase.one", "part.two",
                        (("phase.one/part.two/while", "body"),))
        for v in sorts if "phase.one" in v)
    assert pr.instruction("%fusion.3366 = s32[8]{0} fusion(...)") == \
        "fusion.3366"
    trace = pr.load(pr.find_xplane(str(tmp_path)), names)
    assert trace["devices"] == {} and trace["host"]     # a CPU: no plane


def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if not n:
            return out + bytes([b])
        out += bytes([b | 0x80])


def _field(num, val):
    if isinstance(val, int):
        return _varint(num << 3) + _varint(val)
    val = val.encode() if isinstance(val, str) else val
    return _varint(num << 3 | 2) + _varint(len(val)) + val


def _inst(name, opcode, op_name, inst_id, called=()):
    return (_field(1, name) + _field(2, opcode)
            + (_field(7, _field(2, op_name)) if op_name else b"")
            + _field(35, inst_id) + b"".join(_field(38, c) for c in called))


def test_what_has_no_metadata_takes_its_root_s_or_its_neighbours_place():
    """A handmade ``HloModuleProto``: a fusion without metadata belongs
    to its root; of names XLA merged with ";" the first counts; a copy
    XLA added to a ``while`` body sits where the body's named
    instructions sit."""
    fused = (_field(1, "fused") + _field(2, _inst("p.1", "parameter", "", 1))
             + _field(2, _inst("add.2", "add", "jit(f)/phase.a/x.y/add;"
                               "jit(f)/phase.b/z.w/mul", 2))
             + _field(5, 10) + _field(6, 2))
    body = (_field(1, "body")
            + _field(2, _inst("fusion.3", "fusion", "", 3, (10,)))
            + _field(2, _inst("copy.4", "copy", "", 4))
            + _field(2, _inst("sub.5", "subtract",
                              "jit(f)/phase.a/while/body/k.l/sub", 5))
            + _field(2, _inst("mul.6", "multiply",
                              "jit(f)/phase.a/while/body/mul", 6))
            + _field(5, 11) + _field(6, 5))
    names = pr._module_op_names(
        _field(1, "jit_f") + _field(3, fused) + _field(3, body))
    assert names["add.2"] == names["fusion.3"] == "jit(f)/phase.a/x.y/add"
    assert names["copy.4"] == "jit(f)/phase.a/while/body"
    assert pr.place(names["copy.4"]) == (
        "phase.a", "", (("phase.a/while", "body"),))
    assert pr.frame_prefix("a/cond/branch_1_fun/b/while/cond/lt") == \
        "a/cond/branch_1_fun/b/while/cond"
    assert pr.frame_prefix("jit(f)/phase.a/add") == ""


# -- the slice recorded on the chip -------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(DATA):
        pytest.skip("no recorded sample")
    with open(DATA) as f:
        return pr.from_sample(json.load(f))


def test_the_recorded_slice_sums_to_trace_reduce_s_busy_time(recorded):
    for d, dev in recorded["devices"].items():
        table = pr.reduce_device(dev)
        busy = tr.reduce_device(
            {n: plain(ev) for n, ev in dev.items()})["busy_ns"]
        assert busy > 0
        for group in ("phases", "parts"):
            assert sum(r["device_s"] for r in table[group].values()) == \
                pytest.approx(busy / 1e9, rel=1e-9)
        assert table["scoped_share"] >= pr.MIN_SCOPED
        assert sum(table["in_program_idle"].values()) == \
            pytest.approx(table["in_program_idle_s"])


def test_the_recorded_slice_holds_the_awake_set_plane_s_phases(recorded):
    out = pr.reduce_trace(recorded)
    table = out["devices"][out["busiest"]]
    met = set(table["phases"]) - {pr.UNSCOPED}
    assert met == {"phase.horizon", "phase.churn", "phase.inbox_select",
                   "phase.active_compact", "phase.node_step",
                   "phase.closing"}
    assert table["ticks"] >= 1
    node = table["phases"]["phase.node_step"]
    assert node["device_s"] == max(
        r["device_s"] for r in table["phases"].values())
    # the three data-dependent branches of the steady tick are met
    for at in ("phase.inbox_select/cond", "phase.node_step/while",
               "phase.closing/cond"):
        assert at in table["in_program_idle"], at


def test_a_gap_planted_at_a_cond_of_the_recorded_slice_is_named_by_it(
        recorded):
    dev = copy.deepcopy(recorded["devices"][min(recorded["devices"])])
    before = pr.reduce_device(dev)
    ops = sorted(dev[tr.OPS_LINE], key=lambda e: e[1])
    leaf = tr.leaves(ops)
    # the first leaf inside the closing phase's cond: everything from it
    # on starts 5 us later
    first = next(e for e in leaf
                 if "phase.closing/cond/branch" in e[3])
    shift = 5000.0
    moved = {tr.OPS_LINE: [
        e if e[1] + e[2] <= first[1] else
        ((e[0], e[1] + shift, e[2], e[3]) if e[1] >= first[1]
         else (e[0], e[1], e[2] + shift, e[3])) for e in ops],
        tr.MODULES_LINE: [
            (n, s, d + shift, p) if s <= first[1] < s + d else
            ((n, s + shift, d, p) if s > first[1] else (n, s, d, p))
            for n, s, d, p in dev[tr.MODULES_LINE]]}
    after = pr.reduce_device(moved)
    key = "phase.closing/cond"
    assert after["in_program_idle"][key] - before["in_program_idle"][key] \
        == pytest.approx(shift / 1e9)
    assert after["busy_s"] == pytest.approx(before["busy_s"])
