"""The system under test under a churn law, as the benchmark drives it.

The program file of a deployment whose nodes join and die while the
window runs (named under ``"program"`` in its configuration).  Beside
``program.py`` and ``program_mesh.py`` it is the only file of the
benchmark that imports ``oversim_tpu``, and it edits nothing there: the
deployment is built and run exactly as in ``program.py`` (``IniFile`` ->
``build_simulation`` -> ``sim.init(seed)`` -> the jitted loop behind
``run_until_device``, one chip, the default tick plane).

What it adds is what a reference needs to FOLLOW joins and deaths: every
read-back (the opening's, the one after each dispatch, the close's)
carries, under ``"churn"``, who was alive, under which key and at which
coordinates, who had joined, each node's test timer and sequence number,
the churn schedule and each slot's incarnation:

    ``t_born``   ``SimState.churn.t_born``: the start of the tick that last
                 created a node in the slot (-1: never).  A slot is
                 recycled under a fresh key, so (slot, t_born) names a
                 node where the slot alone names an address.
    ``t_kill``   the pending (or, in a grace window, the fired) leave notice
    ``t_dead``   the final kill of a node under notice (2**62: none)

and the engine's churn counters (``churn_created``, ``churn_prekilled``,
``churn_killed``, ``churn_ticks``, ``reset_rows``).  The close's tables
also carry the buckets' last-seen times and the overlay's own timers.
Everything is looked up by name (``SURFACE``), so a PR that renames one
fails with that name.
"""

from __future__ import annotations

import numpy as np

import program as one_chip
from program import KBR_COUNTERS, SurfaceError, leaf, pool_columns

# per-slot leaves that ride EVERY read-back, by the name the reference
# reads them under
CHURN_VIEW = (
    ("alive", "alive"), ("node_keys", "node_keys"),
    ("coords", "underlay.coords"), ("state", "logic.state"),
    ("t_test", "logic.app.t_test"), ("seq", "logic.app.seq"),
    ("t_kill", "churn.t_kill"), ("t_dead", "churn.t_dead"),
    ("t_born", "churn.t_born"),
)
CHURN_COUNTERS = ("counters.churn_created", "counters.churn_prekilled",
                  "counters.churn_killed", "counters.churn_ticks",
                  "counters.reset_rows")
# the close's tables: last-seen times and the overlay's own timers
CLOSE_VIEW = (
    ("b_seen", "logic.b_seen"), ("t_join", "logic.t_join"),
    ("t_refresh", "logic.t_refresh"), ("ping_to", "logic.ping_to"),
    ("lookup_active", "logic.lk.active"),
)
POOL_LEAVES = ("pool.valid", "pool.blk", "pool.t_deliver",
               "logic.lk.pending_dst", "logic.lk.t_sent",
               "logic.lk.active", "t_now")
SURFACE = (one_chip.SURFACE + tuple(p for _, p in CHURN_VIEW)
           + CHURN_COUNTERS + tuple(p for _, p in CLOSE_VIEW))
READY = 2                      # overlay/kademlia.py's state of a joined node


def churn_view(values) -> dict:
    """The per-slot leaves and the five counters, as plain numpy."""
    n = len(CHURN_VIEW)
    out = {name: np.asarray(v) for (name, _), v in zip(CHURN_VIEW, values)}
    out["ready"] = out.pop("state") == READY
    for path, v in zip(CHURN_COUNTERS, values[n:]):
        out[path.partition(".")[2]] = int(v)
    return out


def check_program() -> None:
    """What this file needs of the program and an older tree lacks,
    looked up before a state is built or a tick compiled, so that such a
    tree fails within seconds and by name."""
    import dataclasses
    from oversim_tpu import churn
    from oversim_tpu.engine import sim
    have = {f.name for f in dataclasses.fields(churn.ChurnState)}
    if "t_born" not in have:
        raise SurfaceError(
            "benchmark/program_churn.py reads each slot's incarnation, "
            "churn.ChurnState.t_born, and the program's ChurnState has "
            "no such field")
    missing = [p.partition(".")[2] for p in CHURN_COUNTERS
               if p.partition(".")[2] not in getattr(sim, "PLANE_COUNTERS", ())]
    if missing:
        raise SurfaceError(
            "benchmark/program_churn.py reads the engine's churn counters "
            f"and engine/sim.py PLANE_COUNTERS has no {missing}")


class Program(one_chip.Program):
    """One deployment under a churn law on one chip."""

    def __init__(self, config: dict, traffic: dict, chips: int,
                 n: int | None = None, persistent_cache: bool = True):
        # (the compile cache is placed before the engine is imported)
        super().__init__(config, traffic, chips, n=n,
                         persistent_cache=persistent_cache)
        check_program()

    def _churn_leaves(self, s) -> tuple:
        return tuple(leaf(s, p) for _, p in CHURN_VIEW) + tuple(
            leaf(s, p) for p in CHURN_COUNTERS)

    def check_surface(self, s) -> None:
        for path in SURFACE:
            leaf(s, path)
        pool_columns(leaf(s, "pool"))

    # -- what the comparison reads -------------------------------------------

    def counters(self, s) -> dict:
        """``program.py``'s counters and the churn view (the opening
        and the close: outside the window, so a second read)."""
        out = super().counters(s)
        out["churn"] = churn_view(self.jax.device_get(self._churn_leaves(s)))
        return out

    def payloads(self, s) -> dict:
        """``program.py``'s read-back (the pool, the pending RPCs, the
        four KBR counters) and the churn view, in ONE batched read."""
        if self._cols is None:            # set-up's warming call
            self._cols = pool_columns(leaf(s, "pool"))
        col = self._cols
        n_pool, n_kbr = len(POOL_LEAVES), len(KBR_COUNTERS)
        got = self.jax.device_get(
            tuple(leaf(s, k) for k in POOL_LEAVES + KBR_COUNTERS)
            + self._churn_leaves(s))
        (valid, blk, t_deliver, rpc_dst, rpc_t_sent, rpc_active,
         t_now) = got[:n_pool]
        kbr = got[n_pool:n_pool + n_kbr]
        rows = np.nonzero(np.asarray(valid))[0]
        blk = np.asarray(blk)[rows]
        return {
            "valid": np.ones(len(rows), bool),
            "t_deliver": np.asarray(t_deliver)[rows],
            "src": blk[:, col["src"]], "dst": blk[:, col["dst"]],
            "kind": blk[:, col["kind"]],
            "size_b": blk[:, col["size_b"]],
            "key": np.ascontiguousarray(
                blk[:, col["key"]]).view(np.uint32),
            "t_now_ns": int(t_now),
            "stats": {k.partition(".")[2]: int(v)
                      for k, v in zip(KBR_COUNTERS, kbr)},
            "rpc_dst": np.asarray(rpc_dst),
            "rpc_t_sent": np.asarray(rpc_t_sent),
            "rpc_active": np.asarray(rpc_active),
            "churn": churn_view(got[n_pool + n_kbr:]),
        }

    def tables(self, s) -> dict:
        """``program.py``'s tables, the buckets' last-seen times and the
        overlay's own timers."""
        out = super().tables(s)
        out.update(zip((name for name, _ in CLOSE_VIEW),
                       map(np.asarray, self.jax.device_get(
                           tuple(leaf(s, p) for _, p in CLOSE_VIEW)))))
        return out
