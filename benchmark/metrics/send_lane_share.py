"""The share of the tick's outbox slots that its closing phase still
runs over (the receiver's stage of the underlay and the pool's
allocation): the engine counter ``send_lanes`` (K in a tick whose wanted
messages fit the K compacted lanes, Q = N x outbox_slots in a tick that
took the Q-wide form) at the close less at the opening, over
``send_outbox_slots`` (Q every tick).  A program whose engine counters
hold no ``send_lanes`` runs over every slot in every tick: 100 by
definition."""


def read(rec):
    opening, close = rec["evidence"]["opening"], rec["evidence"]["close"]
    if close["tick"] - opening["tick"] <= 0:
        return None
    if "send_lanes" not in close["engine"]:
        return 100.0
    lanes, slots = (close["engine"][k] - opening["engine"][k]
                    for k in ("send_lanes", "send_outbox_slots"))
    return 100.0 * lanes / slots
