"""The share of the dense sweep's rows that the tick's node-step phase
still steps: lanes stepped in the window (the engine counter
``lanes_stepped``, rounds x A, at the close less at the opening) over
ticks x alive nodes.  A program whose engine counters hold no
``lanes_stepped`` sweeps every row in every tick (the dense plane): 100
by definition."""


def read(rec):
    opening, close = rec["evidence"]["opening"], rec["evidence"]["close"]
    rows = (close["tick"] - opening["tick"]) * close["alive"]
    if rows <= 0:
        return None
    if "lanes_stepped" not in close["engine"]:
        return 100.0
    lanes = (close["engine"]["lanes_stepped"]
             - opening["engine"]["lanes_stepped"])
    return 100.0 * lanes / rows
