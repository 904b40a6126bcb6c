"""The window's arithmetic, on plain numbers.

Copied from ``chip_smoke.py`` (PR 22), the one piece proven on the chip:
a lookup is in flight from its send until it ends as delivered, failed
or delivered to a wrong node, and every KBR counter is gated on the
send-time measurement bit, so ``in flight = sent - ended`` exactly.
"""

from __future__ import annotations

KBR_ENDS = ("kbr_delivered", "kbr_lookup_failed", "kbr_wrong_node")


def counter(stats: dict, name: str) -> int:
    return int(stats["c:" + name])


def in_flight(stats: dict) -> int:
    return counter(stats, "kbr_sent") - sum(counter(stats, k)
                                            for k in KBR_ENDS)


def lookups(opening: dict, close: dict) -> dict:
    """What the window did, from the counters at its two ends."""
    so, sc = opening["stats"], close["stats"]
    sent = counter(sc, "kbr_sent") - counter(so, "kbr_sent")
    delivered = counter(sc, "kbr_delivered") - counter(so, "kbr_delivered")
    ended = sent - (in_flight(sc) - in_flight(so))
    return {"sent": sent, "delivered": delivered, "attempted": ended,
            "failed": ended - delivered,
            "in_flight_open": in_flight(so), "in_flight_close": in_flight(sc),
            "delivery": delivered / max(sent, ended, 1)}


def counted_stretch(opening: dict, snaps: list, close: dict,
                    over_sim_s: float) -> dict:
    """What the result line's ``attempted`` and ``failed`` count: the
    lookups that ended in the window's first ``over_sim_s`` simulated
    seconds, the configuration's ``failures_over_sim_s``.  ``snaps`` are
    the read-backs after each dispatch, each with ``t_now_ns`` and the
    KBR counters; the stretch ends at the FIRST whose ``t_now_ns`` less
    the opening's is at least that long.  The program is deterministic
    in its seed, so a faster tree reads the same two integers there, and
    a share of failures is compared between parent and change over the
    same lookups.  A window that never gets that far counts over what it
    reached, up to the close, and says so (``reached`` False)."""
    over_ns = int(round(float(over_sim_s) * 1e9))
    t_open = opening["t_now_ns"]
    at = next((i for i, snap in enumerate(snaps)
               if snap["t_now_ns"] - t_open >= over_ns), None)
    end = close if at is None else snaps[at]
    look = lookups(opening, end)
    return {"over_sim_s": float(over_sim_s), "reached": at is not None,
            "sim_s": (end["t_now_ns"] - t_open) / 1e9,
            "dispatches": len(snaps) if at is None else at + 1,
            "attempted": look["attempted"], "failed": look["failed"]}


def by_tenth(opening: dict, snaps: list) -> dict:
    """WHEN in simulated time lookups fail: the lookups that ended and
    that failed in each tenth of the window's dispatches (a dispatch is
    a fixed stretch of simulated time).  Held to no limit."""
    cum = [(0, 0)] + [(look["attempted"], look["failed"]) for look in
                      (lookups(opening, snap) for snap in snaps)]
    edges = [(k * len(snaps)) // 10 for k in range(11)]
    tenths = list(zip(edges, edges[1:]))
    return {"ended": [cum[b][0] - cum[a][0] for a, b in tenths],
            "failed": [cum[b][1] - cum[a][1] for a, b in tenths]}


def window_rates(opening: dict, close: dict, dispatches: list,
                 t_open: float, skip_gaps_before=()) -> dict:
    """Rates over all the work and all the time of the window: from its
    opening to the end of the last dispatch that completed.
    ``dispatches`` is ``[(call, done), ...]`` on the host's clock, each
    ``done`` taken after ``block_until_ready``.  The mean gap between
    dispatches leaves out the gaps before the dispatches numbered in
    ``skip_gaps_before`` (a traced run starts and stops the profiler
    there)."""
    if not dispatches:
        raise ValueError("the window completed no dispatch")
    wall = dispatches[-1][1] - t_open
    sim_s = (close["t_now_ns"] - opening["t_now_ns"]) / 1e9
    ticks = close["tick"] - opening["tick"]
    look = lookups(opening, close)
    in_call = sum(done - call for call, done in dispatches)
    gaps = [dispatches[i + 1][0] - dispatches[i][1]
            for i in range(len(dispatches) - 1)
            if i + 1 not in skip_gaps_before]
    mean_gap = sum(gaps) / len(gaps) if gaps else None
    between = None if mean_gap is None else mean_gap * (len(dispatches) - 1)
    return {"wall_s": wall, "sim_s": sim_s, "ticks": ticks,
            "sim_s_per_wall_s": sim_s / wall,
            "lookups_per_s": look["delivered"] / wall,
            "tick_ms": 1e3 * in_call / max(ticks, 1),
            "dispatch_gap_ms": None if mean_gap is None else 1e3 * mean_gap,
            # the benchmark's own read-back between dispatches, as a share
            # of the window the rates divide by (the profiler's gaps are
            # in neither part)
            "readback_share": None if between is None
            else 100.0 * between / (in_call + between),
            "lookups": look}
