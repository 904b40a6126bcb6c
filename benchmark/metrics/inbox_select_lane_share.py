"""The share of the pool's slots that a round of the tick's inbox
selection still sweeps: the engine counter ``inbox_lanes`` (D in a tick
whose due messages fit the D compacted lanes, P in a tick that took the
P-wide rounds) at the close less at the opening, over ``inbox_pool_slots``
(P every tick).  A program whose engine counters hold no ``inbox_lanes``
sweeps every slot in every round: 100 by definition."""


def read(rec):
    opening, close = rec["evidence"]["opening"], rec["evidence"]["close"]
    if close["tick"] - opening["tick"] <= 0:
        return None
    if "inbox_lanes" not in close["engine"]:
        return 100.0
    lanes, slots = (close["engine"][k] - opening["engine"][k]
                    for k in ("inbox_lanes", "inbox_pool_slots"))
    return 100.0 * lanes / slots
