"""The system under test as a Chord ring, as the benchmark drives it.

The program file of a deployment whose overlay is ``overlay/chord.py``
(named under ``"program"`` in its configuration).  Beside ``program.py``,
``program_mesh.py`` and ``program_churn.py`` it is the only file of the
benchmark that imports ``oversim_tpu``, and it edits nothing there: the
deployment is built and run exactly as in ``program.py`` (``IniFile`` ->
``build_simulation`` -> ``sim.init(seed)`` -> the jitted loop behind
``run_until_device``, one chip, the default tick plane).

What differs is what the comparison reads at the close.  A ring has no
buckets and no sibling table: ``tables`` carries each node's successor
list, predecessor, finger table with its dirty flags, the overlay's own
timers (stabilise, fix-fingers, predecessor check) and the pending
stabilise operation.  The overlay's upkeep counters
(``MAINTENANCE_COUNTERS``: timer rounds started, the RPC calls they sent,
the FindNode calls of the lookups by purpose) ride in the ``stats`` of
the opening and the close, where ``program.py`` already reads every
counter.  Everything is looked up by name (``SURFACE``), so a PR that
renames one fails with that name, and a tree whose Chord keeps no such
counters fails within seconds, before a state is built.
"""

from __future__ import annotations

import numpy as np

import program as one_chip
from program import SurfaceError, leaf, pool_columns

# the close's tables, by the name the reference reads them under
RING_VIEW = (
    ("node_keys", "node_keys"), ("alive", "alive"),
    ("coords", "underlay.coords"), ("channel", "underlay.channel"),
    ("state", "logic.state"), ("succ", "logic.succ"),
    ("pred", "logic.pred"), ("finger", "logic.finger"),
    ("finger_dirty", "logic.finger_dirty"), ("t_stab", "logic.t_stab"),
    ("t_fix", "logic.t_fix"), ("t_cp", "logic.t_cp"),
    ("stab_op", "logic.stab_op"),
)
MAINTENANCE_COUNTERS = (
    "chord_stab_rounds", "chord_notify_calls", "chord_notify_taken",
    "chord_pred_pings", "chord_fix_rounds", "chord_fix_lookups",
    "chord_fix_ended", "chord_fix_calls", "chord_app_calls",
    "chord_join_passed", "chord_join_dropped")
# program.py's surface less Kademlia's tables, and the ring's
SURFACE = tuple(p for p in one_chip.SURFACE
                if p not in ("logic.sib", "logic.buckets")) + tuple(
    p for _, p in RING_VIEW if p not in one_chip.SURFACE) + tuple(
    "stats.c:" + c for c in MAINTENANCE_COUNTERS)
READY = 2                      # overlay/chord.py's state of a joined node


def check_program() -> None:
    """What this file needs of the program and an older tree lacks,
    looked up before a state is built or a tick compiled."""
    from oversim_tpu.overlay import chord
    have = set(chord.ChordLogic().stat_spec().counters)
    missing = [c for c in MAINTENANCE_COUNTERS if c not in have]
    if missing:
        raise SurfaceError(
            "benchmark/program_chord.py reads the overlay's upkeep "
            "counters from SimState.stats, and overlay/chord.py "
            f"ChordLogic.stat_spec has no {missing}")


class Program(one_chip.Program):
    """One Chord deployment on one chip."""

    def __init__(self, config: dict, traffic: dict, chips: int,
                 n: int | None = None, persistent_cache: bool = True):
        # (the compile cache is placed before the engine is imported)
        super().__init__(config, traffic, chips, n=n,
                         persistent_cache=persistent_cache)
        check_program()

    def check_surface(self, s) -> None:
        for path in SURFACE:
            leaf(s, path)
        pool_columns(leaf(s, "pool"))

    def tables(self, s) -> dict:
        """Node identities, coordinates, the ring's pointers and the
        overlay's timers."""
        out = dict(zip((name for name, _ in RING_VIEW),
                       map(np.asarray, self.jax.device_get(
                           tuple(leaf(s, p) for _, p in RING_VIEW)))))
        out["ready"] = out.pop("state") == READY
        return out
