"""Simulated seconds advanced in the window (read from ``t_now``) over
the wall seconds from its opening to the end of its last dispatch."""


def read(rec):
    return rec["rates"]["sim_s_per_wall_s"]
