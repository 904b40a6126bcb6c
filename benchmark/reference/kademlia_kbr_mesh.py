"""Plain reference for a Kademlia deployment under KBRTestApp whose node
rows and message pool are split over the chips of one host.

The semantics are ``kademlia_kbr``'s, read from the same evidence by the
same code (the file beside this one); a mesh changes where the state
lives, never what a window must have left behind.  On top comes the one
thing a mesh adds to the configuration's guarantees: every node-row and
pool leaf is held in ``chips`` blocks of equal rows, each on a device of
its own.  ``shards_misplaced`` counts the leaves that are not, from the
layout the program file recorded beside its tables (shards, rows of
each, first row of each, device of each); a leaf it did not record
counts as misplaced.  Imports nothing of the program.
"""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_reference_kademlia_kbr",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "kademlia_kbr.py"))
kademlia_kbr = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(kademlia_kbr)

compare = kademlia_kbr.compare
control = kademlia_kbr.control

# the node-row leaves ([N, ...]) and the pool leaves ([P, ...]) the
# comparison reads; "logic.lk." stands for every leaf of the lookups'
# state the layout names
NODE_ROW_LEAVES = ("logic.buckets", "logic.sib", "logic.state",
                   "logic.app.t_test")
LOOKUP_PREFIX = "logic.lk."
LOOKUP_LEAVES_READ = ("logic.lk.pending_dst", "logic.lk.t_sent",
                      "logic.lk.active")
POOL_LEAVES = ("pool.valid", "pool.blk", "pool.t_deliver")


def in_equal_blocks(h: dict, chips: int) -> bool:
    """One leaf's record: ``chips`` shards, each of rows_total / chips
    rows, starting at 0, 1, 2, ... blocks, on ``chips`` different
    devices."""
    total = int(h["rows_total"])
    if total % chips:
        return False
    block = total // chips
    return (int(h["shards"]) == chips
            and [int(r) for r in h["rows"]] == [block] * chips
            and sorted(int(x) for x in h["starts"])
            == [i * block for i in range(chips)]
            and len({int(d) for d in h["devices"]}) == chips)


def layout_readings(layout: dict, chips: int) -> dict:
    names = list(NODE_ROW_LEAVES + LOOKUP_LEAVES_READ + POOL_LEAVES)
    names += [k for k in layout if k.startswith(LOOKUP_PREFIX)
              and k not in names]
    bad = [k for k in names
           if k not in layout or not in_equal_blocks(layout[k], chips)]
    return {"shards_misplaced": len(bad), "shards_checked": len(names),
            "shards_misplaced_first": bad[0] if bad else None}


def readings(O: dict, C: dict, T: dict, snaps: list, *, config: dict,
             **kw) -> dict:
    """``kademlia_kbr.readings`` of what the window left, and the
    layout's one number."""
    out = kademlia_kbr.readings(O, C, T, snaps, config=config, **kw)
    out.update(layout_readings(T.get("layout") or {}, int(config["chips"])))
    return out
