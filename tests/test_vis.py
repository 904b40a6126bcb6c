"""Topology snapshot (TopologyVis equivalent, TopologyVis.h:37-70):
ring edges from a converged Chord run must form the sorted-key cycle
in the DOT/JSON dump."""

import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu import vis
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.chord import ChordLogic

N = 8


@pytest.fixture(scope="module")
def chord_state():
    logic = ChordLogic()
    cp = churn_mod.ChurnParams(model="none", target_num=N,
                               init_interval=0.5)
    s = sim_mod.Simulation(logic, cp,
                           engine_params=sim_mod.EngineParams(window=0.05,
                                                              inbox_slots=2))
    st = s.init(seed=3)
    st = s.run_until(st, 120.0, chunk=256)
    return s, st


def test_snapshot_has_ring_edges(chord_state):
    s, st = chord_state
    snap = vis.snapshot(st)
    assert len(snap["nodes"]) == N
    succ = [(e["src"], e["dst"]) for e in snap["edges"]
            if e["kind"] == "successor"]
    # a converged ring: every alive node has a successor arrow, and the
    # first-successor arrows form the sorted-key cycle
    from oversim_tpu.core import keys as K
    keys_int = [K.to_int(k) for k in np.asarray(st.node_keys)]
    order = sorted(range(N), key=lambda i: keys_int[i])
    first_succ = {int(i): int(np.asarray(st.logic.succ)[i, 0])
                  for i in range(N)}
    for pos, i in enumerate(order):
        expected = order[(pos + 1) % N]
        assert first_succ[i] == expected
        assert (i, expected) in succ


def test_dot_renders(chord_state):
    s, st = chord_state
    dot = vis.to_dot(st)
    assert dot.startswith("digraph overlay {")
    assert "->" in dot and dot.rstrip().endswith("}")


def test_json_roundtrips(chord_state):
    import json
    s, st = chord_state
    data = json.loads(vis.to_json(st))
    assert data["nodes"] and data["edges"]


# ---------------------------------------------------------------------------
# histogram_svg (obs/loadgen latency histograms)
# ---------------------------------------------------------------------------

def test_histogram_svg_bars_and_labels():
    import math
    svg = vis.histogram_svg([3, 0, 5, 1], [0.01, 0.1, 1.0, math.inf],
                            title="request latency", unit="s")
    assert svg.startswith("<svg") and svg.rstrip().endswith("</svg>")
    # one bar per bucket (plus the frame rect)
    assert svg.count("<rect") == 5
    assert "request latency" in svg
    # finite bounds label as numbers, the +Inf bucket as >last-finite
    assert ">0.01</text>" in svg
    assert ">>1</text>" in svg
    # bar tooltips carry the per-bucket counts
    assert "&#8804;0.01s: 3" in svg
    assert "bucket upper bound (s)" in svg


def test_histogram_svg_scales_to_top_count():
    svg = vis.histogram_svg([10], [1.0])
    # count axis ticks at 0 / half / top
    assert ">0<" in svg and ">5<" in svg and ">10<" in svg


def test_histogram_svg_empty_and_mismatch_fallback():
    assert "no histogram samples" in vis.histogram_svg([], [])
    assert "no histogram samples" in vis.histogram_svg([1, 2], [1.0])


def test_write_histogram_svg(tmp_path):
    p = tmp_path / "h.svg"
    out = vis.write_histogram_svg([1, 2], [0.5, 1.0], p, title="t")
    assert out == str(p)
    assert p.read_text().startswith("<svg")
