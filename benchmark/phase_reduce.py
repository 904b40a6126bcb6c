"""A profiler dump reduced by the tick program's own names.

    python3 benchmark/phase_reduce.py <dump dir> [--ticks N] [--out FILE]

The tick program enters a named scope for each of its phases and for
their parts (``oversim_tpu/core/scopes.py``), so every operation's
``op_name`` says where it comes from:
``jit(_run_until_device)/while/body/while/body/closed_call/
phase.node_step/while/body/kademlia.find_node/vmap(jit(sort))/sort``.
A device event carries its instruction's name only (``%fusion.3366 =
...``).  The dump also holds the HLO of every program that ran while the
profiler was on (the ``/host:metadata`` plane: what the profile's own
viewers read), metadata and all, so instruction -> ``op_name`` is read
from the dump itself and belongs to the executable that ran, whatever
tree or cache it came from.  A fusion without metadata of its own
belongs to its root.

For each device: device seconds, leaf operations and the median leaf of
every phase (first level: ``phase.*``) and of every part (second level:
the innermost ``word.word`` component under the phase), an ``unscoped``
row, and the idle split in two.  INSIDE a program run each gap between
consecutive leaf operations goes to the phase of the operation that
ends it and, where the two operations sit in different ``while`` or
``cond`` frames, to that boundary by name (``phase.closing/cond``).
BETWEEN program runs the idle is shared out among the host's events
over it, JAX's own included, innermost first.

``trace_reduce.py`` is the benchmark's reduction and stays what it is;
this file takes its leaf rule and its union from it and brings only its
own loader, which keeps what a phase is read from.  It fails, by name,
where under ``MIN_SCOPED`` of a device's busy time has a phase.
"""

from __future__ import annotations

import json
import os
import re
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from trace_reduce import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,  # noqa: E402
                          TraceError, find_xplane, gaps, leaves,
                          short_name, union_ns)

MIN_SCOPED = 0.90
LONG_NS = 1000.0    # a leaf of 1 us and more: half the events of a trace
                    # are markers of a few ns (copy-start, copy-done)
UNSCOPED = "unscoped"
PHASE = re.compile(r"^phase\.\w+$")
PART = re.compile(r"^[a-z_]+\.[a-z_]+$")
WRAPPER = re.compile(r"\b\w+\(")


def is_frame(kind: str, arm: str) -> bool:
    """``while/body``, ``while/cond`` or ``cond/branch_<k>_fun``: two
    components of an ``op_name`` that open a computation of its own."""
    if kind == "while":
        return arm in ("body", "cond")
    return kind == "cond" and arm.startswith("branch_")


class PhaseError(TraceError):
    pass


# -- protobuf, as far as a dump needs it -----------------------------------

def _varint(buf, i):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf):
    """(field number, wire type, value) of one protobuf message: a varint
    as int, a length-delimited field as a memoryview."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
            if i > n:
                raise TraceError("a protobuf field runs past its message")
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = buf[i:i + size], i + size
        else:
            raise TraceError(f"protobuf wire type {wire} in the dump")
        yield num, wire, val


def _varints(view):
    """A packed repeated varint field."""
    view, i = memoryview(view), 0
    while i < len(view):
        val, i = _varint(view, i)
        yield val


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _module_op_names(module) -> dict:
    """``HloModuleProto`` -> {instruction name: op_name}.  Field numbers
    of xla/service/hlo.proto: module.computations = 3; computation.name
    = 1, .instructions = 2, .id = 5, .root_id = 6; instruction.name = 1,
    .opcode = 2, .metadata = 7 (OpMetadata.op_name = 2), .id = 35,
    .called_computation_ids = 38."""
    insts, roots = {}, {}           # name -> (op_name, opcode, called ids)
    by_id = {}
    for num, wire, comp in _fields(module):
        if num != 3 or wire != 2:
            continue
        comp_id = root_id = None
        local = []
        for cnum, cwire, cval in _fields(comp):
            if cnum == 5 and cwire == 0:
                comp_id = cval
            elif cnum == 6 and cwire == 0:
                root_id = cval
            elif cnum == 2 and cwire == 2:
                name = opcode = op_name = ""
                inst_id, called = None, []
                for inum, iwire, ival in _fields(cval):
                    if inum == 1 and iwire == 2:
                        name = _text(ival)
                    elif inum == 2 and iwire == 2:
                        opcode = _text(ival)
                    elif inum == 7 and iwire == 2:
                        for mnum, mwire, mval in _fields(ival):
                            if mnum == 2 and mwire == 2:
                                # XLA joins the names of operations
                                # it merges with ";": the first's
                                op_name = _text(mval).split(";")[0]
                    elif inum == 35 and iwire == 0:
                        inst_id = ival
                    elif inum == 38:
                        if iwire == 0:
                            called.append(ival)
                        else:                   # packed
                            called.extend(_varints(ival))
                insts[name] = (op_name, opcode, called)
                by_id[inst_id] = name
                local.append(name)
        if comp_id is not None:
            roots[comp_id] = (by_id.get(root_id), local)
    out = {}
    for name, (op_name, opcode, called) in insts.items():
        if not op_name and opcode == "fusion":
            # a fusion belongs to its root's scope; a root without
            # metadata (a tuple, a bitcast): the last instruction of the
            # fused computation that has some
            for comp_id in called:
                root, local = roots.get(comp_id, (None, []))
                op_name = insts.get(root, ("",))[0] or next(
                    (insts[n][0] for n in reversed(local) if insts[n][0]),
                    "")
                if op_name:
                    break
        out[name] = op_name
    # what XLA adds of its own (the copies of a loop's carry, the
    # prefetches' copy-start and copy-done) has no metadata, but it sits
    # in a computation, and a computation is one ``while`` body or one
    # ``cond`` branch: such an instruction gets the frames its named
    # neighbours share, and with them the phase where the frame lies in
    # one (``.../phase.node_step/while/body``)
    for _, local in roots.values():
        frames = {}
        for n in local:
            if out[n]:
                f = frame_prefix(out[n])
                frames[f] = frames.get(f, 0) + 1
        if not frames:
            continue
        most = max(frames, key=frames.get)
        for n in local:
            if not out[n]:
                out[n] = most
    return out


def frame_prefix(op_name: str) -> str:
    """``op_name`` cut after its last ``while/body``, ``while/cond`` or
    ``cond/branch_<k>_fun``: where its computation sits."""
    parts = op_name.split("/")
    for i in range(len(parts) - 2, -1, -1):
        if is_frame(parts[i], parts[i + 1]):
            return "/".join(parts[:i + 2])
    return ""


def _hlo_modules(plane):
    """The ``HloModuleProto`` of every program a plane's event metadata
    holds (tsl/profiler/protobuf/xplane.proto: XPlane.event_metadata =
    4, a map entry's value = 2; XEventMetadata.stats = 5;
    XStat.bytes_value = 6; the bytes are an ``HloProto``, hlo_module =
    1)."""
    for num, wire, entry in _fields(plane):
        if num != 4 or wire != 2:
            continue
        for enum, ewire, meta in _fields(entry):
            if enum != 2 or ewire != 2:
                continue
            for mnum, mwire, stat in _fields(meta):
                if mnum != 5 or mwire != 2:
                    continue
                for snum, swire, blob in _fields(stat):
                    if snum == 6 and swire == 2 and len(blob) > 16:
                        try:
                            first = next(iter(_fields(blob)))
                        except (StopIteration, IndexError, TraceError):
                            continue
                        if first[0] == 1 and first[1] == 2:
                            yield first[2]


def op_names_of(path: str) -> dict:
    """{instruction name: op_name} over every program in the dump.  Two
    programs that name an instruction alike and disagree leave it out
    (the tick program's names are its own: ``fusion.3366``)."""
    with open(path, "rb") as f:
        raw = f.read()
    out, clash = {}, set()
    for num, wire, plane in _fields(raw):
        if num != 1 or wire != 2:
            continue
        for module in _hlo_modules(plane):
            for inst, op_name in _module_op_names(module).items():
                if out.setdefault(inst, op_name) != op_name:
                    clash.add(inst)
    for inst in clash:
        out[inst] = ""
    return out


# -- reading ---------------------------------------------------------------

def instruction(text: str) -> str:
    """``%fusion.3366 = s32[...] fusion(...)`` -> ``fusion.3366``."""
    return text.partition(" = ")[0].strip().lstrip("%")


def load(path: str, op_names: dict | None = None) -> dict:
    """{"devices": {id: {line: [(name, start, dur, op_name)]}}, "host":
    {line: [(name, start, dur)]}} with times in ns.  Every host event of
    every host line is kept."""
    from jax.profiler import ProfileData
    if op_names is None:
        op_names = op_names_of(path)
    data = ProfileData.from_file(path)
    devices, host = {}, {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {}
            for line in plane.lines:
                lines[line.name] = [
                    (short_name(ev.name), float(ev.start_ns),
                     float(ev.duration_ns),
                     op_names.get(instruction(ev.name), ""))
                    for ev in line.events]
            devices[int(m.group(1))] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(ev.name, float(ev.start_ns),
                           float(ev.duration_ns)) for ev in line.events]
                if events:
                    host.setdefault(line.name, []).extend(events)
    return {"devices": devices, "host": host}


# -- an operation's place ----------------------------------------------------

def components(op_name: str) -> list:
    """The path's components with ``jit(...)``, ``vmap(...)`` and such
    wrappers stripped: ``a/vmap(b.c/jit(sort))/sort`` -> a, b.c, sort,
    sort."""
    bare = WRAPPER.sub("", op_name).replace(")", "")
    return [c for c in bare.split("/") if c]


def place(op_name: str):
    """``(phase, part, frames)``: the first ``phase.*`` component
    (``unscoped`` without one), the innermost part under it ("" for the
    phase's own operations) and the ``while`` / ``cond`` frames the
    operation sits in, outermost first, each as ``(name, arm)``: the
    frame's name is the phase, the part it opens under and its kind
    (``phase.node_step/while``, ``phase.closing/cond``; ``loop/while``
    for the run loop and the scan of ticks above every phase), its arm
    ``body``, ``cond`` or ``branch_1_fun``.  An operation without an
    ``op_name`` (a copy XLA made of a loop's carry) has no frames: None."""
    if not op_name:
        return UNSCOPED, "", None       # nothing says where it sits
    comps = components(op_name)
    phase, part, frames = UNSCOPED, "", []
    i = 0
    while i < len(comps):
        c = comps[i]
        if PHASE.match(c):
            if phase == UNSCOPED:
                phase = c
        elif PART.match(c):
            if phase != UNSCOPED:
                part = c
        elif i + 1 < len(comps) and is_frame(c, comps[i + 1]):
            where = "loop" if phase == UNSCOPED else (
                phase + ("/" + part if part else ""))
            frames.append((where + "/" + c, comps[i + 1]))
            i += 1
        i += 1
    return phase, part, tuple(frames)


def boundary(before, after) -> str:
    """The ``while`` or ``cond`` two consecutive operations are divided
    by: the outermost frame in which their places differ ("" where both
    sit in the same frames: straight-line code; ``unscoped`` where one
    of them does not say where it sits)."""
    if before is None or after is None:
        return UNSCOPED
    for a, b in zip(before, after):
        if a != b:
            return b[0]
    if len(before) != len(after):
        longer = before if len(before) > len(after) else after
        return longer[min(len(before), len(after))][0]
    return ""


# -- the reduction -----------------------------------------------------------

def _row():
    return {"device_s": 0.0, "leaf_ops": 0, "durs": [], "idle_after_s": 0.0}


def _median(values) -> float:
    return statistics.median(values) if values else 0.0


def _close(rows: dict) -> dict:
    out = {}
    for name, r in sorted(rows.items(), key=lambda kv: -kv[1]["device_s"]):
        long = [d for d in r["durs"] if d >= LONG_NS]
        out[name] = {
            "device_s": r["device_s"] / 1e9, "leaf_ops": r["leaf_ops"],
            "median_leaf_us": _median(r["durs"]) / 1e3,
            # the leaves of LONG_NS and more: how many, their median,
            # their sum (count x median near the sum: the row is as many
            # operations of one fixed cost, and fewer is the lever)
            "long_ops": len(long), "long_median_us": _median(long) / 1e3,
            "long_s": sum(long) / 1e9,
            "idle_after_s": r["idle_after_s"] / 1e9}
    return out


def innermost(events) -> list:
    """One host line as stretches that do not overlap: at every instant
    the innermost event, ``(name, start, end)``."""
    out, stack = [], []             # stack of [name, end, cursor]

    def pop():
        name, end, cursor = stack.pop()
        if end > cursor:
            out.append((name, cursor, end))
        if stack:
            stack[-1][2] = max(stack[-1][2], end)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            pop()
        if stack and start > stack[-1][2]:
            out.append((stack[-1][0], stack[-1][2], start))
        stack.append([name, start + dur, start])
    while stack:
        pop()
    return out


def share_out(gap_list, host: dict) -> dict:
    """Idle stretches by what the host was doing over them: each host
    line's innermost events, every line on its own (threads run side by
    side), and ``no host event`` for what the busiest line leaves."""
    named = {}
    total = sum(e - s for s, e in gap_list)
    covered_most = 0.0
    for line, events in host.items():
        covered = 0.0
        flat = sorted(innermost(events), key=lambda e: e[1])
        for g0, g1 in gap_list:
            for name, s, e in flat:
                if s >= g1:
                    break
                over = min(g1, e) - max(g0, s)
                if over > 0:
                    key = f"{line.split('/')[0]}: {name}"
                    named[key] = named.get(key, 0.0) + over
                    covered += over
        covered_most = max(covered_most, covered)
    if total - covered_most > 0:
        named["no host event"] = total - covered_most
    return named


def reduce_device(lines: dict) -> dict:
    """One device plane -> its table (times in seconds)."""
    ops = lines.get(OPS_LINE)
    if not ops:
        raise TraceError(f"a device plane has no {OPS_LINE!r} line "
                         f"(lines: {sorted(lines)})")
    leaf = leaves(ops)
    runs = sorted((e[1], e[1] + e[2]) for e in lines.get(MODULES_LINE) or [])
    if not runs:
        runs = [(min(e[1] for e in ops), max(e[1] + e[2] for e in ops))]
    phases, parts, idle_at, idle_by_ops = {}, {}, {}, {}
    unscoped_ops = {}
    places = {}
    covered_to = None
    prev = None                     # (end, frames) of the last leaf
    run_i = 0
    in_program = after_scalar = 0.0
    for name, start, dur, op_name in leaf:
        if op_name not in places:
            places[op_name] = place(op_name)
        phase, part, frames = places[op_name]
        # the part of this leaf no earlier leaf covers: the rows then sum
        # to the union of the leaves, trace_reduce's busy_ns
        end = start + dur
        own = end - max(start, covered_to) if covered_to is not None else dur
        own = max(own, 0.0)
        covered_to = end if covered_to is None else max(covered_to, end)
        part_key = phase + "/" + (part or "-")
        for table, key in ((phases, phase), (parts, part_key)):
            row = table.setdefault(key, _row())
            row["device_s"] += own
            row["leaf_ops"] += 1
            row["durs"].append(dur)
        if phase == UNSCOPED:
            unscoped_ops[name] = unscoped_ops.get(name, 0.0) + own
        # the gap this leaf ends, where both lie in one program run
        while run_i < len(runs) and runs[run_i][1] <= start:
            run_i += 1
        if prev is not None and start > prev[0] and run_i < len(runs) \
                and runs[run_i][0] <= prev[0]:
            gap = start - prev[0]
            in_program += gap
            if prev[3].endswith("[]"):
                # the operation before reduced to a scalar (its first
                # result shape: ``reduce.2058 u32[]``): what follows
                # waits for the value to cross to the scalar core
                after_scalar += gap
            phases[phase]["idle_after_s"] += gap
            parts[part_key]["idle_after_s"] += gap
            at = boundary(prev[1], frames) or (
                phase if phase == prev[2] else prev[2] + " -> " + phase)
            idle_at[at] = idle_at.get(at, 0.0) + gap
            pair = (prev[3], name, phase + ("/" + part if part else ""))
            seen = idle_by_ops.setdefault(pair, [0.0, 0])
            seen[0] += gap
            seen[1] += 1
        if prev is None or end >= prev[0]:
            prev = (end, frames, phase, name)
    busy = union_ns((e[1], e[1] + e[2]) for e in leaf)
    lo, hi = runs[0][0], runs[-1][1]
    lo = min(lo, min(e[1] for e in ops))
    hi = max(hi, max(e[1] + e[2] for e in ops))
    all_gaps = gaps(leaf, lo, hi)
    between = [(s, e) for s, e in all_gaps
               if not any(r0 <= s and e <= r1 for r0, r1 in runs)]
    scoped = busy - phases.get(UNSCOPED, _row())["device_s"]
    return {
        "busy_s": busy / 1e9, "span_s": (hi - lo) / 1e9,
        "scoped_share": scoped / busy if busy else 0.0,
        "program_runs": len(runs), "leaf_ops": len(leaf),
        "phases": _close(phases), "parts": _close(parts),
        "in_program_idle_s": in_program / 1e9,
        "in_program_idle_after_scalar_s": after_scalar / 1e9,
        "in_program_idle": {k: v / 1e9 for k, v in sorted(
            idle_at.items(), key=lambda kv: -kv[1])},
        # the longest of it by the two operations it lies between: a
        # wait is the first one's (a scalar it reduces to, a copy it
        # starts) as often as the second's
        "in_program_idle_top": [
            {"after": a, "before": b, "scope": where, "idle_s": v / 1e9,
             "gaps": n} for (a, b, where), (v, n) in sorted(
                idle_by_ops.items(), key=lambda kv: -kv[1][0])[:24]],
        "between_runs_idle_s": sum(e - s for s, e in between) / 1e9,
        "between_runs_gaps": between,
        "unscoped_ops": [[n, s / 1e9] for n, s in sorted(
            unscoped_ops.items(), key=lambda kv: -kv[1])[:10]],
    }


def ticks_of(lines: dict) -> int:
    """The ticks a device's trace holds, where nobody says: an operation
    of ``phase.horizon`` outside every inner loop runs once a tick, so
    the most common count among such instructions."""
    counts = {}
    for name, _, _, op_name in leaves(lines.get(OPS_LINE) or []):
        phase, _, frames = place(op_name)
        if phase == "phase.horizon" and all(
                f[0] == "loop/while" for f in frames or ()):
            counts[name] = counts.get(name, 0) + 1
    if not counts:
        return 0
    return statistics.mode(counts.values())


def reduce_trace(trace: dict, ticks: int | None = None) -> dict:
    """The whole dump: a table for each device that ran something, the
    idle between program runs by host event on the busiest, and the
    refusal where a device's operations do not say where they come
    from."""
    per = {}
    for dev, lines in sorted(trace["devices"].items()):
        if not lines.get(OPS_LINE):
            continue
        table = reduce_device(lines)
        table["ticks"] = ticks or ticks_of(lines)
        named = share_out(table.pop("between_runs_gaps"), trace["host"])
        table["between_runs_idle"] = {k: v / 1e9 for k, v in sorted(
            named.items(), key=lambda kv: -kv[1])[:12]}
        per[str(dev)] = table
    if not per:
        raise TraceError("no operation ran on any device in the trace")
    for dev, table in per.items():
        if table["scoped_share"] < MIN_SCOPED:
            raise PhaseError(
                f"device {dev}: {table['scoped_share']:.1%} of its busy "
                f"time lies under a phase.* scope, under "
                f"{MIN_SCOPED:.0%}: the program that ran names no phases "
                f"(a tree before core/scopes.py, or an executable out of "
                f"a cache that does not key by metadata), or the dump "
                f"holds no HLO for it; longest unscoped operations: "
                f"{table['unscoped_ops'][:5]}")
    busiest = max(per, key=lambda d: per[d]["busy_s"])
    return {"devices": per, "busiest": busiest}


# -- showing ---------------------------------------------------------------

def show(result: dict, say=print) -> None:
    """The busiest device's table, a tick at a time."""
    table = result["devices"][result["busiest"]]
    ticks = max(table["ticks"], 1)
    say(f"device {result['busiest']} of {len(result['devices'])}: "
        f"{ticks} ticks, busy {table['busy_s'] * 1e3 / ticks:.3f} ms a "
        f"tick, {table['leaf_ops'] / ticks:.0f} leaf operations a tick, "
        f"{table['scoped_share']:.1%} under a phase")
    say(f"{'scope (ms and operations a tick)':44s} {'ms':>7s} {'ops':>7s} "
        f"{'median us':>9s} {'>=1us':>6s} {'their ms':>8s} {'median':>7s} "
        f"{'n x med':>7s} {'idle ms':>7s}")
    for group in ("phases", "parts"):
        for name, r in table[group].items():
            issue = r["long_ops"] * r["long_median_us"] / 1e3 / ticks
            say(f"{name:44s} {r['device_s'] * 1e3 / ticks:7.4f} "
                f"{r['leaf_ops'] / ticks:7.1f} {r['median_leaf_us']:9.3f} "
                f"{r['long_ops'] / ticks:6.1f} "
                f"{r['long_s'] * 1e3 / ticks:8.4f} "
                f"{r['long_median_us']:7.2f} {issue:7.4f} "
                f"{r['idle_after_s'] * 1e3 / ticks:7.4f}")
        say("")
    say(f"idle inside program runs {table['in_program_idle_s'] * 1e3 / ticks:.4f} "
        f"ms a tick ("
        f"{table['in_program_idle_after_scalar_s'] * 1e3 / ticks:.4f} after "
        f"an operation with a scalar result), by boundary:")
    for name, s in list(table["in_program_idle"].items())[:14]:
        say(f"  {name:60s} {s * 1e3 / ticks:8.4f}")
    say("  the longest, by the operations it lies between:")
    for row in table["in_program_idle_top"][:12]:
        say(f"  {row['idle_s'] * 1e3 / ticks:8.4f} x{row['gaps'] / ticks:6.1f} "
            f"a tick  {row['after'][:30]:30s} -> {row['before'][:30]:30s} "
            f"{row['scope']}")
    say(f"idle between {table['program_runs']} program runs "
        f"{table['between_runs_idle_s'] * 1e3:.3f} ms, by host event:")
    for name, s in table["between_runs_idle"].items():
        say(f"  {name[:70]:70s} {s * 1e3:8.3f}")
    if table["unscoped_ops"]:
        say("longest unscoped operations (ms a tick): " + ", ".join(
            f"{n} {s * 1e3 / ticks:.4f}" for n, s in table["unscoped_ops"][:6]))


# -- a small recorded sample, for the tests ------------------------------------

def sample(trace: dict, slice_ns: float = 7e6) -> dict:
    """A slice small enough to keep under ``tests/data``: from the start
    of each device's longest program run ``slice_ns`` of its operations
    and program runs, cut to the slice, the ``op_name`` of each kept once
    in a table, and the host's events over it."""
    out = {"op_names": [], "devices": {}, "host": {}}
    index = {}
    lo = hi = None
    for d, lines in trace["devices"].items():
        mods = lines.get(MODULES_LINE) or lines[OPS_LINE]
        lo = max(mods, key=lambda e: e[2])[1]
        hi = lo + slice_ns
        cut = {}
        for name in (OPS_LINE, MODULES_LINE):
            cut[name] = []
            for ev in lines.get(name, []):
                if not lo <= ev[1] < hi:
                    continue
                op_name = ev[3] if len(ev) > 3 else ""
                at = index.setdefault(op_name, len(index))
                cut[name].append([ev[0], ev[1], min(ev[2], hi - ev[1]), at])
        out["devices"][str(d)] = cut
    out["op_names"] = sorted(index, key=index.get)
    for line, events in trace["host"].items():
        kept = [[n, max(s, lo), min(s + dur, hi) - max(s, lo)]
                for n, s, dur in events if s < hi and s + dur > lo]
        if kept:
            out["host"][line] = kept
    return out


def from_sample(data: dict) -> dict:
    """A recorded sample as ``load`` returns a dump."""
    names = data["op_names"]
    return {
        "devices": {int(d): {line: [(n, s, dur, names[at])
                                    for n, s, dur, at in events]
                             for line, events in lines.items()}
                    for d, lines in data["devices"].items()},
        "host": {line: [tuple(e) for e in events]
                 for line, events in data["host"].items()}}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("dump", help="a profiler's log directory")
    ap.add_argument("--ticks", type=int, default=None,
                    help="the ticks the dump holds (default: counted)")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="the tables as JSON")
    ap.add_argument("--sample", default=None, metavar="FILE",
                    help="write a recorded slice for the tests")
    args = ap.parse_args(argv)
    trace = load(find_xplane(args.dump))
    if args.sample:
        with open(args.sample, "w") as f:
            json.dump(sample(trace), f)
    result = reduce_trace(trace, args.ticks)
    show(result)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f)
    print(json.dumps({"busiest": result["busiest"], "devices": {
        d: {k: t[k] for k in ("ticks", "busy_s", "scoped_share", "leaf_ops",
                              "in_program_idle_s", "between_runs_idle_s")}
        for d, t in result["devices"].items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
