"""Device time inside collective operations a tick: the union of the
collectives' leaf events (``trace_reduce``'s ``collective_s``) on the
device that spends most in them, over the traced dispatches' ticks, in
ms.  It holds the time a collective waits for the slowest chip, and
says nothing of what overlaps it.  A trace without the number (a
reduction that does not carry it) gives nothing to read."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not rec.get("traced") or tr.get("collective_s") is None:
        return None
    ticks = len(rec["traced"]) * rec["ticks_per_dispatch"]
    return 1e3 * tr["collective_s"] / ticks
