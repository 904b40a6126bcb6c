"""From the profiler's ``.xplane.pb`` to numbers.

The benchmark starts the profiler itself around a few dispatches of the
window and fails when it gets nothing back.  Everything below
``load_xplane`` works on plain lists ``(name, start_ns, duration_ns)``,
so it is tested on a small recorded trace (``tests/data``).

A device plane's "XLA Ops" line nests: a ``while`` or a fusion's parent
covers the operations inside it.  Busy time is the union of the LEAF
events, those that contain no other event, so a loop's own span never
counts as work.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|collective-permute|all-to-all|reduce-scatter"
    r"|collective-broadcast", re.IGNORECASE)
HOST_SPAN_PREFIX = "bench."


SHAPE = re.compile(r"\b[a-z]+\d*\[[\d,]*\]")


class TraceError(Exception):
    pass


def short_name(text: str) -> str:
    """XLA names a device event by the operation's whole HLO line; keep
    its name and its first result shape: ``fusion.3632 u32[1048576]``."""
    name, sep, rest = text.partition(" = ")
    name = name.lstrip("%")
    if not sep:
        return name[:80]
    m = SHAPE.search(rest)
    return (name + " " + m.group(0))[:80] if m else name[:80]


# -- reading --------------------------------------------------------------------

def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise TraceError(f"the profiler wrote no .xplane.pb under {logdir}")
    return paths[-1]


def load_xplane(path: str) -> dict:
    """{"devices": {id: {line: [(name, start, dur)]}}, "host": [...]}
    with times in ns.  Host events are the benchmark's own annotations
    (``bench.*``) from every host line."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {}
            for line in plane.lines:
                lines[line.name] = [
                    (short_name(ev.name), float(ev.start_ns),
                     float(ev.duration_ns)) for ev in line.events]
            devices[int(m.group(1))] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


# -- intervals ------------------------------------------------------------------

def union_ns(intervals) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def leaves(events) -> list:
    """Events that contain no other event of the same line."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []          # stack of [event, has_child]
    for e in ev:
        while stack and e[1] >= stack[-1][0][1] + stack[-1][0][2]:
            top, has_child = stack.pop()
            if not has_child:
                out.append(top)
        if stack:
            stack[-1][1] = True
        stack.append([e, False])
    for top, has_child in stack:
        if not has_child:
            out.append(top)
    return sorted(out, key=lambda e: e[1])


def gaps(events, lo: float, hi: float) -> list:
    """Idle stretches ``(start, end)`` of [lo, hi] that no event covers."""
    out, cur = [], lo
    for s, e in sorted((e[1], e[1] + e[2]) for e in events):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


# -- the reduction --------------------------------------------------------------

def reduce_device(lines: dict) -> dict:
    """One device plane -> its span, busy time, collectives, top
    operations and idle gaps (ns)."""
    ops = lines.get(OPS_LINE)
    if not ops:
        raise TraceError(f"a device plane has no {OPS_LINE!r} line "
                         f"(lines: {sorted(lines)})")
    leaf = leaves(ops)
    mods = lines.get(MODULES_LINE) or []
    lo = min(e[1] for e in ops)
    hi = max(e[1] + e[2] for e in ops)
    if mods:
        lo = min(lo, min(e[1] for e in mods))
        hi = max(hi, max(e[1] + e[2] for e in mods))
    busy = union_ns((e[1], e[1] + e[2]) for e in leaf)
    coll = [e for e in leaf if COLLECTIVE.search(e[0])]
    by_name = {}
    for name, _, dur in leaf:
        by_name[name] = by_name.get(name, 0.0) + dur
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    module_ns = (union_ns((e[1], e[1] + e[2]) for e in mods)
                 if mods else hi - lo)
    return {"span_ns": hi - lo, "busy_ns": busy,
            "module_ns": module_ns, "modules": len(mods),
            "collective_ns": union_ns((e[1], e[1] + e[2]) for e in coll),
            "top_ops": top, "gaps": gaps(leaf, lo, hi), "leaf_ops": len(leaf)}


def name_gaps(gaps_ns, host_spans) -> dict:
    """Idle time of the device by what the host was doing: each idle
    stretch is shared out among the benchmark's own host spans that
    overlap it (inside ``bench.dispatch`` the host only waits, so that
    share is the program's own issue gaps); the rest has no span."""
    named = {}
    for g0, g1 in gaps_ns:
        left = g1 - g0
        for name, s, d in host_spans:
            overlap = min(g1, s + d) - max(g0, s)
            if overlap > 0:
                named[name] = named.get(name, 0.0) + overlap
                left -= overlap
        if left > 0:
            named["no_host_span"] = named.get("no_host_span", 0.0) + left
    return named


def reduce_trace(trace: dict, chips: int) -> dict:
    """The whole trace -> what the metrics and the result line read."""
    devs = trace["devices"]
    if len(devs) < chips:
        raise TraceError(f"the trace holds {len(devs)} device plane(s), "
                         f"the cell runs on {chips}")
    per = {d: reduce_device(lines) for d, lines in sorted(devs.items())}
    used = [r for r in per.values() if r["busy_ns"] > 0]
    if not used:
        raise TraceError("no operation ran on any device in the trace")
    span = max(r["span_ns"] for r in used)
    busy_mean = sum(r["busy_ns"] for r in used) / len(used)
    worst = max(used, key=lambda r: 1.0 - r["busy_ns"] / r["span_ns"])
    busiest = max(used, key=lambda r: r["collective_ns"])
    # idle gaps of the worst device, by what the host was doing
    named = name_gaps(worst["gaps"], trace["host"])
    ops = {}
    for r in used:
        for name, ns in r["top_ops"]:
            ops[name] = ops.get(name, 0.0) + ns / len(used)
    return {
        "devices": len(used),
        "window_s": span / 1e9,
        "busy_s": busy_mean / 1e9,
        "idle_share_worst": 1.0 - worst["busy_ns"] / worst["span_ns"],
        "module_s": max(r["module_ns"] for r in used) / 1e9,
        "modules": max(r["modules"] for r in used),
        "collective_s": busiest["collective_ns"] / 1e9,
        "device_ops": [[n, s / 1e9] for n, s in sorted(
            ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[n, s / 1e9] for n, s in sorted(
            named.items(), key=lambda kv: -kv[1])[:10]],
        "leaf_ops": sum(r["leaf_ops"] for r in used),
    }


# -- a small recorded sample, for the tests ---------------------------------

def sample(trace: dict, slice_ns: float = 12e6) -> dict:
    """A slice of the trace small enough to keep under ``tests/data``:
    from the start of each device's longest program run, ``slice_ns`` of
    its operations (the loop that holds them cut to the slice), the
    program runs cut likewise, and the host's spans."""
    out = {"devices": {}, "host": []}
    lo = hi = None
    for d, lines in trace["devices"].items():
        mods = lines.get(MODULES_LINE) or lines[OPS_LINE]
        start = max(mods, key=lambda e: e[2])[1]
        lo, hi = start, start + slice_ns
        cut = {}
        for name in (OPS_LINE, MODULES_LINE):
            cut[name] = [(n, s, min(dur, hi - s))
                         for n, s, dur in lines.get(name, [])
                         if lo <= s < hi]
        out["devices"][str(d)] = cut
    out["host"] = [(n, max(s, lo), min(s + dur, hi) - max(s, lo))
                   for n, s, dur in trace["host"]
                   if s < hi and s + dur > lo]
    return out


if __name__ == "__main__":
    import json
    import sys
    tr = load_xplane(find_xplane(sys.argv[1]))
    with open(sys.argv[2], "w") as f:
        json.dump(sample(tr), f)
    print(json.dumps({d: {n: len(e) for n, e in lines.items()}
                      for d, lines in tr["devices"].items()}))
