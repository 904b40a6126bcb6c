"""``stats.record``'s histogram class (``h:``) against ``numpy.bincount``.

The bins are counted by comparison, not by a scatter-add (PERF.md,
PR 36): the same integers, with the index clipped into the first and
last bin, the mask and the measurement gate applied, ``int64`` kept.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oversim_tpu import stats as stats_mod

# name -> (bins, events, how the indices are drawn)
HIST_CASES = {
    "bins16_in_range": (16, 500, "in_range"),
    "bins16_below_zero_and_past_the_end": (16, 500, "clipped"),
    "bins16_mask_all_false": (16, 200, "mask_off"),
    "bins16_gate_false": (16, 200, "gate_off"),
    "bins16_no_events": (16, 0, "in_range"),
    "bins1": (1, 300, "clipped"),
    "bins100": (100, 2000, "clipped"),
    # the cells' own shape: 13 events a node, nearly every one masked
    "bins16_kbr_shape": (16, 13 * 1000, "sparse"),
    "bins100_no_events": (100, 0, "in_range"),
    "bins16_two_ticks": (16, 400, "clipped"),
}


def _events(rng, bins, events, how):
    if how == "in_range":
        idx = rng.integers(0, bins, size=events)
    else:
        idx = rng.integers(-3 * bins - 5, 4 * bins + 5, size=events)
    mask = rng.random(events) < (0.01 if how == "sparse" else 0.6)
    if how == "mask_off":
        mask[:] = False
    return idx.astype(np.int32), mask


@pytest.mark.parametrize("name", list(HIST_CASES))
def test_hist_record_equals_bincount(name):
    bins, events, how = HIST_CASES[name]
    rng = np.random.default_rng(sum(map(ord, name)))
    acc = rng.integers(0, 2**40, size=bins)       # past 32 bits: kept
    stats = {"h:x": jnp.asarray(acc, jnp.int64)}
    gate = how != "gate_off"
    exp = acc.copy()
    record = jax.jit(stats_mod.record)
    for _ in range(2 if name.endswith("two_ticks") else 1):
        idx, mask = _events(rng, bins, events, how)
        stats = record(stats, {"h:x": (jnp.asarray(idx), jnp.asarray(mask))},
                       jnp.asarray(gate))
        if gate:
            exp += np.bincount(np.clip(idx, 0, bins - 1)[mask],
                               minlength=bins)
    got = np.asarray(stats["h:x"])
    assert got.dtype == np.int64 and got.shape == (bins,)
    assert (got == exp).all(), (got, exp)
    if how in ("mask_off", "gate_off") or events == 0:
        assert (got == acc).all()
    elif how != "sparse":
        assert (got != acc).any()


def test_hist_record_takes_a_node_by_slot_event_array():
    """The node step hands ``[N, E]`` event arrays: every entry counts."""
    rng = np.random.default_rng(7)
    idx = rng.integers(-2, 20, size=(50, 13)).astype(np.int32)
    mask = rng.random((50, 13)) < 0.3
    out = stats_mod.record(
        {"h:x": jnp.zeros((16,), jnp.int64)},
        {"h:x": (jnp.asarray(idx), jnp.asarray(mask))}, jnp.asarray(True))
    assert (np.asarray(out["h:x"]) == np.bincount(
        np.clip(idx, 0, 15)[mask], minlength=16)).all()
