"""Runtime invariant checker (SURVEY §5 sanitizer tier;
oversim_tpu/invariants.py)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu import invariants as inv
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.chord import ChordLogic


@pytest.fixture(scope="module")
def chord8():
    logic = ChordLogic(app=KbrTestApp(KbrTestParams(test_interval=20.0)))
    cp = churn_mod.ChurnParams(model="none", target_num=8,
                               init_interval=0.3)
    s = sim_mod.Simulation(logic, cp,
                           engine_params=sim_mod.EngineParams(window=0.05,
                                                              inbox_slots=2))
    st = s.init(seed=3)
    # checker runs BETWEEN chunks for the whole convergence run
    st = s.run_until(st, 120.0, chunk=128, check_invariants=True)
    return s, st


def test_clean_run_passes(chord8):
    s, st = chord8
    inv.check_state(st)          # converged state re-validates
    out = s.summary(st)
    assert out["kbr_delivered"] > 0


def test_detects_succ_compaction_hole(chord8):
    _, st = chord8
    lg = st.logic
    succ = jnp.asarray(lg.succ)
    # punch a hole: [live, NO_NODE, live] violates compaction
    bad = succ.at[0, 0].set(succ[0, 1]).at[0, 1].set(-1)
    bad = bad.at[0, 2].set(succ[0, 0])
    broken = dataclasses.replace(
        st, logic=dataclasses.replace(lg, succ=bad))
    with pytest.raises(inv.InvariantViolation, match="succ_compact"):
        inv.check_state(broken)


def test_detects_ring_order_breakage(chord8):
    _, st = chord8
    lg = st.logic
    from oversim_tpu.core import keys as K
    keys_int = [K.to_int(k) for k in np.asarray(st.node_keys)]
    a, b, c, d = sorted(range(8), key=lambda i: keys_int[i])[:4]
    s0 = np.asarray(lg.succ[:, 0])
    if [int(s0[a]), int(s0[b]), int(s0[c])] != [b, c, d]:
        pytest.skip("ring not at its fixed point: the order check is "
                    "quiet by design")
    # rotate three consecutive nodes: a -> c -> b -> d.  succ0 stays ONE
    # cycle over the ready set (the gate check_chord needs before it
    # judges order; a plain swap of two successors splits the ring in
    # two cycles and is taken for a transient), but in the wrong key
    # order: the quiet-ring order check must fire
    succ = jnp.asarray(lg.succ)
    succ = succ.at[a, 0].set(c).at[c, 0].set(b).at[b, 0].set(d)
    broken = dataclasses.replace(
        st, logic=dataclasses.replace(lg, succ=succ))
    with pytest.raises(inv.InvariantViolation, match="chord_ring_order"):
        inv.check_state(broken)


def test_detects_negative_counter(chord8):
    _, st = chord8
    counters = dict(st.counters)
    counters["pool_overflow"] = jnp.int64(-1)
    broken = dataclasses.replace(st, counters=counters)
    with pytest.raises(inv.InvariantViolation, match="nonnegative"):
        inv.check_state(broken)
