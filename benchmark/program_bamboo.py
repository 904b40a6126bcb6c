"""The system under test as a Bamboo overlay, as the benchmark drives it.

The program file of a deployment whose overlay is ``overlay/pastry.py``
(named under ``"program"`` in its configuration).  Beside ``program.py``
and its siblings it is the only file of the benchmark that imports
``oversim_tpu``, and it edits nothing there: the deployment is built and
run exactly as in ``program.py`` (``IniFile`` -> ``build_simulation`` ->
``sim.init(seed)`` -> the jitted loop behind ``run_until_device``, one
chip, the default tick plane).

What differs is what the comparison reads.  A prefix-routing overlay has
no buckets: ``tables`` carries each node's two leaf-set halves, its
routing table, the three upkeep timers and the ACK-pending route slots.
Its payloads hop node to node (``common/route.py``), so every read-back
of the pool also brings each message's hop count, encapsulated kind and
visited list, and the opening's and the close's counters the route slots
that await an ACK and the routed messages in flight.  The overlay's
upkeep counters and the routed path's (``UPKEEP_COUNTERS``,
``ROUTE_COUNTERS``) ride in the ``stats`` of the opening and the close,
where ``program.py`` already reads every counter.

A routed payload has an end that a fetched one has not: a forwarder with
no candidate left, or the hop bound, DROPS it, and KBRTestApp (which
counts an iterative lookup that fails) never hears of it.  The window's
arithmetic knows three ends (``window.KBR_ENDS``: delivered, lookup
failed, wrong node), so every ``stats`` this file hands on carries the
routed path's drops (``ROUTE_DROPS``) INSIDE ``c:kbr_lookup_failed``:
"the payloads that ended undelivered", whichever way they travelled.
That is what makes ``failed`` on the result line and the reference's
``lookup_failed_share`` see a drop; the raw route counters stay beside
it under their own names, and a payload the engine loses never ends at
all (``messages_lost``, ``route_recount_gap``).  Everything is looked
up by name (``SURFACE``), so a PR that renames one fails with that name,
and a tree whose Pastry keeps no such counters fails within seconds,
before a state is built.
"""

from __future__ import annotations

import numpy as np

import program as one_chip
from program import SurfaceError, leaf, pool_columns

# the close's tables, by the name the reference reads them under
OVERLAY_VIEW = (
    ("node_keys", "node_keys"), ("alive", "alive"),
    ("coords", "underlay.coords"), ("channel", "underlay.channel"),
    ("state", "logic.state"), ("leaf_cw", "logic.leaf_cw"),
    ("leaf_ccw", "logic.leaf_ccw"), ("rt", "logic.rt"),
    ("t_ls", "logic.t_ls"), ("t_lt", "logic.t_lt"), ("t_gt", "logic.t_gt"),
    ("route_active", "logic.rr.active"), ("route_t_to", "logic.rr.t_to"),
)
UPKEEP_COUNTERS = (
    "bamboo_ls_rounds", "bamboo_lt_probes", "bamboo_gt_lookups",
    "bamboo_state_msgs", "bamboo_app_routes")
ROUTE_COUNTERS = (
    "route_forwarded", "route_acked", "route_ack_timeouts",
    "route_rerouted", "route_unacked_table_full", "route_delivered",
    "route_dropped_no_candidate", "route_dropped_hop_bound")
# a routed payload's ends that KBRTestApp does not count (module docstring)
ROUTE_DROPS = ("route_dropped_no_candidate", "route_dropped_hop_bound")
FAILED = "c:kbr_lookup_failed"
# a routed message's own fields in the pool's block (MsgPool's views)
ROUTE_VIEWS = ("hops", "d", "nodes")
# program.py's surface less Kademlia's tables, and the overlay's
SURFACE = tuple(p for p in one_chip.SURFACE
                if p not in ("logic.sib", "logic.buckets")) + tuple(
    p for _, p in OVERLAY_VIEW if p not in one_chip.SURFACE) + tuple(
    "stats.c:" + c for c in UPKEEP_COUNTERS + ROUTE_COUNTERS)
READY = 2                      # overlay/pastry.py's state of a joined node


def check_program() -> None:
    """What this file needs of the program and an older tree lacks,
    looked up before a state is built or a tick compiled."""
    from oversim_tpu.overlay import pastry
    have = set(pastry.BambooLogic().stat_spec().counters)
    missing = [c for c in UPKEEP_COUNTERS + ROUTE_COUNTERS if c not in have]
    if missing:
        raise SurfaceError(
            "benchmark/program_bamboo.py reads the overlay's upkeep "
            "counters and the routed path's from SimState.stats, and "
            f"overlay/pastry.py BambooLogic.stat_spec has no {missing}")


def route_columns(pool) -> dict:
    """``program.pool_columns`` for the fields of a routed message."""
    import dataclasses
    width = leaf(pool, "blk").shape[-1]
    probe = dataclasses.replace(
        pool, blk=np.arange(width, dtype=np.int32)[None, :])
    cols = {}
    for name in ROUTE_VIEWS:
        try:
            cols[name] = np.asarray(getattr(probe, name))[0]
        except AttributeError:
            raise SurfaceError(
                f"benchmark/program_bamboo.py reads the pool's column view "
                f"{name!r} (MsgPool.{name}) and the pool has none") from None
    return cols


class Program(one_chip.Program):
    """One Bamboo deployment on one chip."""

    def __init__(self, config: dict, traffic: dict, chips: int,
                 n: int | None = None, persistent_cache: bool = True):
        # (the compile cache is placed before the engine is imported)
        super().__init__(config, traffic, chips, n=n,
                         persistent_cache=persistent_cache)
        check_program()
        self._route_cols = None
        from oversim_tpu.common import wire
        self._kbr_route = int(wire.KBR_ROUTE)

    def check_surface(self, s) -> None:
        for path in SURFACE:
            leaf(s, path)
        pool_columns(leaf(s, "pool"))
        route_columns(leaf(s, "pool"))

    def _columns(self, s) -> dict:
        if self._route_cols is None:
            self._route_cols = dict(pool_columns(leaf(s, "pool")),
                                    **route_columns(leaf(s, "pool")))
        return self._route_cols

    def counters(self, s) -> dict:
        """``program.py``'s, with the route slots that await an ACK and
        the routed messages in the pool (the two recounts' ends)."""
        out = super().counters(s)
        st = out["stats"]
        st[FAILED] = st[FAILED] + sum(st["c:" + k] for k in ROUTE_DROPS)
        col = self._columns(s)
        active, valid, blk = self.jax.device_get(tuple(leaf(s, k) for k in (
            "logic.rr.active", "pool.valid", "pool.blk")))
        kind = np.asarray(blk)[:, col["kind"]]
        out["route_pending"] = int(np.sum(active))
        out["routes_in_flight"] = int(np.sum(
            np.asarray(valid) & (kind == self._kbr_route)))
        return out

    def payloads(self, s) -> dict:
        """``program.py``'s read-back (ONE ``device_get``), with each
        message's hop count, encapsulated kind and visited list taken
        from the block it already brought, and the routed path's drops
        among the payloads that failed."""
        col = self._columns(s)
        if self._cols is None:
            self._cols = {k: col[k] for k in one_chip.POOL_VIEWS}
        (valid, blk, t_deliver, rpc_dst, rpc_t_sent, rpc_active, t_now,
         *kbr) = self.jax.device_get(tuple(leaf(s, k) for k in (
             "pool.valid", "pool.blk", "pool.t_deliver",
             "logic.lk.pending_dst", "logic.lk.t_sent",
             "logic.lk.active", "t_now") + one_chip.KBR_COUNTERS
            + tuple("stats.c:" + k for k in ROUTE_DROPS)))
        stats = {k.partition(".")[2]: int(v)
                 for k, v in zip(one_chip.KBR_COUNTERS, kbr)}
        stats[FAILED] += sum(int(v) for v in kbr[len(one_chip.KBR_COUNTERS):])
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
            "hops": blk[:, col["hops"]], "inner": blk[:, col["d"]],
            "visited": blk[:, col["nodes"]],
            "t_now_ns": int(t_now),
            "stats": stats,
            "rpc_dst": np.asarray(rpc_dst),
            "rpc_t_sent": np.asarray(rpc_t_sent),
            "rpc_active": np.asarray(rpc_active),
        }

    def tables(self, s) -> dict:
        """Node identities, coordinates, the leaf sets, the routing
        table, the upkeep timers and the route slots."""
        out = dict(zip((name for name, _ in OVERLAY_VIEW),
                       map(np.asarray, self.jax.device_get(
                           tuple(leaf(s, p) for _, p in OVERLAY_VIEW)))))
        out["ready"] = out.pop("state") == READY
        return out

    def wire(self) -> dict:
        from oversim_tpu.common import wire
        return dict(super().wire(), KBR_ROUTE=int(wire.KBR_ROUTE),
                    KBR_ROUTE_ACK=int(wire.KBR_ROUTE_ACK))
