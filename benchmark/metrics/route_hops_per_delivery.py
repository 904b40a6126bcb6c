"""Routed hops a delivered payload: the ``KBR_ROUTE`` hops the window
sent (a payload's first hop, every forward, every reroute;
``common/route.py``'s cumulative ``route_forwarded`` in
``SimState.stats``, at the close less at the opening) over the payloads
decapsulated at the node that holds itself responsible
(``route_delivered``).  The plain reference holds it from below: every
hop seen has to make progress towards the key (``route_no_progress``),
the node a payload ends at has to be the key's owner
(``payload_not_owner``) and the hops taken are set against greedy
routing over the tables at the close (``hops_off_greedy``), so a tree
that lowers this by delivering short of the owner is not ``correct``.
A program that keeps no such counters has nothing to read."""

HOPS, DELIVERED = "c:route_forwarded", "c:route_delivered"


def read(rec):
    opening, close = rec["evidence"]["opening"], rec["evidence"]["close"]
    so, sc = opening["stats"], close["stats"]
    if HOPS not in sc or DELIVERED not in sc:
        return None
    delivered = int(sc[DELIVERED]) - int(so[DELIVERED])
    if delivered <= 0:
        return None
    return (int(sc[HOPS]) - int(so[HOPS])) / delivered
