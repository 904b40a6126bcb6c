"""Host clock around ``block_until_ready(sim.init(seed))`` (eager)."""


def read(rec):
    return rec["spans"]["init_s"]
