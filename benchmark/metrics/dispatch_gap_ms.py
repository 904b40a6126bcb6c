"""Mean host time between one dispatch's ``block_until_ready`` and the
next dispatch's call (the window's read-back of the pool is in it)."""


def read(rec):
    return rec["rates"]["dispatch_gap_ms"]
