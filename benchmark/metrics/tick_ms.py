"""Wall time inside the window's dispatch calls over their ticks."""


def read(rec):
    return rec["rates"]["tick_ms"]
