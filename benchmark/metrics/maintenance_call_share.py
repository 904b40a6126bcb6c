"""The share of the window's RPC calls that the overlay's own upkeep
started: stabilise calls, notify calls, predecessor pings and the
FindNode calls of finger-repair lookups (the overlay's cumulative
counters in ``SimState.stats``, at the close less at the opening) over
those plus the application's, the FindNode calls of its lookups and the
payloads they ended in.  The plain reference holds the upkeep to its
law from below, so a tree that lowers this by skipping upkeep is not
``correct``.  A program that keeps no such counters has nothing to
read."""

UPKEEP = ("chord_stab_rounds", "chord_notify_calls", "chord_pred_pings",
          "chord_fix_calls")
APPLICATION = ("chord_app_calls", "kbr_delivered", "kbr_wrong_node")


def read(rec):
    opening, close = rec["evidence"]["opening"], rec["evidence"]["close"]
    so, sc = opening["stats"], close["stats"]
    if any("c:" + k not in sc for k in UPKEEP + APPLICATION):
        return None

    def started(names):
        return sum(int(sc["c:" + k]) - int(so["c:" + k]) for k in names)

    upkeep, application = started(UPKEEP), started(APPLICATION)
    if upkeep + application <= 0:
        return None
    return 100.0 * upkeep / (upkeep + application)
