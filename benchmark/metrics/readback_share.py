"""The share of the window's wall time spent between dispatches, which
is the benchmark's own read-back of the message pool for the comparison:
the part of every rate that is the yardstick's and not the program's."""


def read(rec):
    return rec["rates"]["readback_share"]
