"""The reduction from trace events to numbers: on made-up lines, and on
a small sample recorded on the chip (``data/trace_sample.json``)."""

import json
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_sample.json")


def test_union_merges_overlaps_and_keeps_holes():
    assert tr.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert tr.union_ns([]) == 0
    assert tr.union_ns([(0, 10), (0, 10), (2, 3)]) == 10


def test_a_loop_s_own_span_is_not_work():
    # while [0, 100) holds fusion.1 [10, 30) and fusion.2 [50, 60);
    # fusion.1 holds a nested copy [12, 20)
    ops = [("while.1", 0, 100), ("fusion.1", 10, 20), ("copy.3", 12, 8),
           ("fusion.2", 50, 10), ("tail", 120, 5)]
    leaf = tr.leaves(ops)
    assert [e[0] for e in leaf] == ["copy.3", "fusion.2", "tail"]
    assert tr.union_ns((e[1], e[1] + e[2]) for e in leaf) == 23


def test_gaps_are_what_no_leaf_covers():
    leaf = [("a", 10, 10), ("b", 30, 5)]
    assert tr.gaps(leaf, 0, 50) == [(0, 10), (20, 30), (35, 50)]


def lines(ops, mods=()):
    return {tr.OPS_LINE: list(ops), tr.MODULES_LINE: list(mods)}


def test_device_busy_collectives_and_top_operations():
    ops = [("while", 0, 1000), ("fusion.7", 0, 300), ("all-gather.2", 300, 100),
           ("fusion.7", 500, 300), ("all-reduce-start.1", 850, 50),
           ("collective-permute.4", 900, 60)]
    r = tr.reduce_device(lines(ops, [("jit_run(1)", 0, 1000)]))
    assert r["span_ns"] == 1000 and r["module_ns"] == 1000
    assert r["busy_ns"] == 300 + 100 + 300 + 110
    assert r["collective_ns"] == 100 + 110
    assert r["top_ops"][0] == ("fusion.7", 600)
    assert sum(e - s for s, e in r["gaps"]) == 1000 - r["busy_ns"]


def test_the_whole_trace_worst_device_and_named_gaps():
    d0 = lines([("f", 0, 800), ("g", 900, 100), ("f", 2000, 1000)],
               [("m", 0, 1000), ("m", 2000, 1000)])
    d1 = lines([("f", 0, 400), ("f", 2000, 600)],
               [("m", 0, 1000), ("m", 2000, 1000)])
    host = [("bench.dispatch", 0, 1000), ("bench.readback", 1000, 1000),
            ("bench.dispatch", 2000, 1000)]
    out = tr.reduce_trace({"devices": {0: d0, 1: d1}, "host": host}, 2)
    assert out["devices"] == 2 and out["modules"] == 2
    assert out["window_s"] == pytest.approx(3000e-9)
    assert out["busy_s"] == pytest.approx((1900 + 1000) / 2 * 1e-9)
    # device 1 is the worst: 1000 busy of the 3000 its two runs span
    assert out["idle_share_worst"] == pytest.approx(1 - 1000 / 3000)
    assert out["module_s"] == pytest.approx(2000e-9)
    gaps = dict(out["idle_gaps"])
    assert gaps["bench.readback"] == pytest.approx(1000e-9)
    assert gaps["bench.dispatch"] == pytest.approx(1000e-9)
    assert "no_host_span" not in gaps
    assert out["device_ops"][0][0] == "f"


def test_nothing_from_the_profiler_is_an_error(tmp_path):
    with pytest.raises(tr.TraceError):
        tr.find_xplane(str(tmp_path))
    with pytest.raises(tr.TraceError):
        tr.reduce_trace({"devices": {}, "host": []}, 1)
    with pytest.raises(tr.TraceError):
        tr.reduce_device({"Steps": [("s", 0, 1)]})
    idle = lines([], [("m", 0, 10)])
    with pytest.raises(tr.TraceError):
        tr.reduce_trace({"devices": {0: idle}, "host": []}, 1)


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded sample")
def test_the_recorded_sample_from_the_chip():
    with open(DATA) as f:
        raw = json.load(f)
    devices = {int(d): {n: [tuple(e) for e in ev]
                        for n, ev in lines_.items() if n != "_lines"}
               for d, lines_ in raw["devices"].items()}
    trace = {"devices": devices, "host": [tuple(e) for e in raw["host"]]}
    out = tr.reduce_trace(trace, len(devices))
    assert 0 < out["busy_s"] <= out["window_s"]
    assert 0 <= out["idle_share_worst"] < 1
    assert len(out["device_ops"]) <= 10 and out["device_ops"][0][1] > 0
    # the loop that holds the ticks is no leaf: it never leads the list
    ops = devices[min(devices)][tr.OPS_LINE]
    longest = max(ops, key=lambda e: e[2])
    assert longest[0] not in [n for n, _ in out["device_ops"]]
    if len(devices) > 1:
        assert out["collective_s"] > 0
