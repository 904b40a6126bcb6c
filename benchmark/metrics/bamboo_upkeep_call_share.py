"""The share of the window's calls that the overlay's own upkeep
started: leaf-set push-pulls, local-tuning probes, global-tuning lookups
and the state exchanges answered (``overlay/pastry.py``'s cumulative
counters in ``SimState.stats``, at the close less at the opening) over
those plus the application's, the payloads handed to the routed path and
the hops they were forwarded over.  The plain reference holds the upkeep
to its law from below (each of the three timers against the file's
interval), so a tree that lowers this by skipping upkeep is not
``correct``.  A program that keeps no such counters has nothing to
read."""

UPKEEP = ("bamboo_ls_rounds", "bamboo_lt_probes", "bamboo_gt_lookups",
          "bamboo_state_msgs")
APPLICATION = ("bamboo_app_routes", "route_forwarded")


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
