"""KBR lookups the program counts delivered in the window over its wall
seconds.  The program counts a one-way test delivered when the receiver
holds itself a sibling of the key (its own view, upstream's
``isSiblingFor``), not when it is the key's XOR-closest node: of the
payloads counted, 90 to 100% at N=4096 (filled in 20 s) and 5 to 68%
at N=1000 (upstream's fill) reach another node than the key's owner
(PERF.md, Findings).  So this is a rate of lookups that ended at a
self-declared sibling in the key's neighbourhood."""


def read(rec):
    return rec["rates"]["lookups_per_s"]
