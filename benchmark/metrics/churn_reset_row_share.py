"""The share of the node rows that the tick's churn phase rewrites: the
engine counter ``reset_rows`` (the rows ``logic.reset``, the fresh keys
and ``underlay.migrate`` select over: all N in every tick while they are
dense selects, whatever churn touched) at the close less at the opening,
over ticks x slots.  A program whose engine counters hold no
``reset_rows`` rewrites every row in every tick: 100 by definition."""


def read(rec):
    opening, close = rec["evidence"]["opening"], rec["evidence"]["close"]
    ticks = close["tick"] - opening["tick"]
    slots = len(close["seq"])
    if ticks <= 0 or slots <= 0:
        return None
    if "reset_rows" not in close["engine"]:
        return 100.0
    rows = close["engine"]["reset_rows"] - opening["engine"]["reset_rows"]
    return 100.0 * rows / (ticks * slots)
