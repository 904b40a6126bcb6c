"""Host-side machinery of the fleet supervisor (scripts/fleet_run.py).

A fleet run splits a campaign's replica grid into contiguous shards,
runs each shard in its own worker process (its own jax runtime — a
worker dying takes out only its shard), and merges the per-shard
artifacts back into ONE ensemble identical to an uninterrupted
single-process campaign.  Everything here is pure host code (json,
numpy, no jax) so the supervisor never initializes a backend and the
pieces unit-test without compiles:

  * :func:`shard_replicas` — contiguous near-even split of global
    replica ids; together with ``CampaignParams.replica_ids`` a shard
    worker advances exactly its rows of the full campaign,
    bit-identically (run_chunk is replica-independent).
  * heartbeat files — one atomic JSON per worker, rewritten after every
    chunk; the supervisor SIGKILLs-and-reschedules workers whose
    heartbeat goes stale (hang detection, not just death detection).
  * :func:`chaos_schedule` — the seeded chaos mode: (delay, worker)
    kill events from ``random.Random(seed)``, reproducible end to end.
  * :func:`encode_leaves` / :func:`decode_leaves` — dtype-preserving
    JSON codec for the counter-leaf pytree (dtype fidelity matters: the
    ensemble-identity check is EXACT equality, so a float32 leaf must
    not come back float64).
  * :func:`merge_shard_leaves` — row-merge of per-shard counter leaves
    by global replica id, refusing overlaps/holes; feed the result to
    ``service.loop.campaign_summarize_leaves`` for the ensemble summary.

Determinism contract: workers and any reference run MUST advance by the
same fixed-tick ``run_chunk`` cadence.  ``run_until_device`` is NOT
stack-invariant (its ``any(t_now < target)`` cond lets fast replicas
keep ticking until the slowest passes, so the stop tick depends on who
shares the stack) — fixed tick counts are what make shard == rows.
"""

from __future__ import annotations

import json
import os
import random
import time

import numpy as np


# ------------------------------------------------------------- shards --


def shard_replicas(total: int, workers: int) -> list:
    """Contiguous near-even split of global replica ids ``0..total-1``
    into at most ``workers`` non-empty shards (fewer when
    workers > total).  Deterministic: earlier shards take the remainder."""
    if total < 1 or workers < 1:
        raise ValueError("need total >= 1 and workers >= 1")
    workers = min(workers, total)
    base, rem = divmod(total, workers)
    out, start = [], 0
    for w in range(workers):
        n = base + (1 if w < rem else 0)
        out.append(tuple(range(start, start + n)))
        start += n
    return out


# ------------------------------------------------ atomic json + hearts --


def write_json_atomic(path: str, doc: dict) -> None:
    """tmp+fsync+rename — a SIGKILL mid-write never leaves a torn file
    (the checkpoint.py discipline, for heartbeats and shard artifacts)."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def read_json(path: str):
    """The parsed file, or None when missing/torn (a worker killed
    before its first heartbeat is a normal fleet condition)."""
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def write_heartbeat(path: str, **fields) -> None:  # analysis: allow(wall-clock)
    """Worker liveness: atomic JSON stamped with the wall clock, plus
    caller fields (ticks_done, retries, ...)."""
    write_json_atomic(path, {"wall": time.time(), **fields})


def heartbeat_age(path: str, now: float | None = None):  # analysis: allow(wall-clock)
    """Seconds since the worker last heartbeat, or None when it never
    wrote one."""
    doc = read_json(path)
    if not doc or "wall" not in doc:
        return None
    return (time.time() if now is None else now) - float(doc["wall"])


def aggregate_heartbeats(docs: dict, now: float | None = None) -> dict:  # analysis: allow(wall-clock)
    """Fleet-level rollup of per-worker heartbeat docs.

    ``docs`` maps worker index → parsed heartbeat JSON (or None for a
    worker that never wrote / whose file is torn).  Pure host-side
    arithmetic: the supervisor polls this into fleet-level metric
    series (obs plane), ``fleet_report.json``, and the watcher."""
    t = time.time() if now is None else now
    out = {"workers_total": len(docs), "workers_reporting": 0,
           "ticks_done": 0, "ticks_target": 0, "retries": 0,
           "heartbeat_age_max_s": None,
           "per_worker": {}}
    ages = []
    for widx, doc in sorted(docs.items()):
        if not doc:
            out["per_worker"][str(widx)] = None
            continue
        out["workers_reporting"] += 1
        age = (t - float(doc["wall"])) if "wall" in doc else None
        if age is not None:
            ages.append(age)
        out["ticks_done"] += int(doc.get("ticks_done", 0))
        out["ticks_target"] += int(doc.get("ticks", 0))
        out["retries"] += int(doc.get("retries", 0))
        out["per_worker"][str(widx)] = {
            "age_s": round(age, 3) if age is not None else None,
            "ticks_done": int(doc.get("ticks_done", 0)),
            "ticks": int(doc.get("ticks", 0)),
            "retries": int(doc.get("retries", 0)),
            "chunk_wall_s": doc.get("chunk_wall_s"),
        }
    if ages:
        out["heartbeat_age_max_s"] = round(max(ages), 3)
    return out


# -------------------------------------------------------------- chaos --


def chaos_schedule(kills: int, workers: int, seed: int,
                   span_s: float = 10.0, min_delay_s: float = 0.5) -> list:
    """The seeded kill plan: ``kills`` events of ``(delay_s, worker)``,
    delays uniform over [min_delay_s, min_delay_s + span_s), sorted by
    delay.  Same seed → same plan, so a chaos failure reproduces."""
    rnd = random.Random(seed)
    events = [(min_delay_s + rnd.random() * span_s, rnd.randrange(workers))
              for _ in range(kills)]
    return sorted(events)


# ----------------------------------------------------- leaves json i/o --


def encode_leaves(tree):
    """Counter-leaf pytree (nested dicts of arrays) → JSON-able doc,
    dtype-preserving."""
    if isinstance(tree, dict):
        return {k: encode_leaves(v) for k, v in tree.items()}
    arr = np.asarray(tree)
    return {"__nd__": arr.tolist(), "dtype": str(arr.dtype)}


def decode_leaves(doc):
    """Inverse of :func:`encode_leaves` — numpy arrays with their
    original dtypes."""
    if isinstance(doc, dict) and "__nd__" in doc:
        return np.asarray(doc["__nd__"], dtype=np.dtype(doc["dtype"]))
    return {k: decode_leaves(v) for k, v in doc.items()}


# ------------------------------------------------------------- resize --


def plan_resize(row_ticks: dict, new_workers: int) -> list:
    """Re-split live replica rows across a CHANGED worker count.

    ``row_ticks`` maps global replica id → that row's checkpointed
    ``ticks_done`` (0 for a row never checkpointed).  A shard worker
    resumes from ONE ``ticks_done``, so rows are first grouped into
    tick classes (rows sharing a resume point) and each class is then
    split contiguously; shards are allocated to classes proportionally
    to class size (largest remainder), every class keeping at least
    one.  Returns ``[(replica_ids, ticks_done), ...]`` — at least
    ``len(classes)`` shards even when ``new_workers`` is smaller (rows
    at different resume points can never share a worker), never more
    shards than rows.

    This is what makes the autoscaler's resize safe WITHOUT a global
    barrier: ``run_chunk`` is replica-independent (the fleet
    determinism contract), so a row's future depends only on
    (base_seed, id, ticks_done) — not on which worker advances it."""
    if not row_ticks:
        raise ValueError("plan_resize needs at least one replica row")
    if new_workers < 1:
        raise ValueError("need new_workers >= 1")
    classes: dict = {}
    for gid, td in sorted(row_ticks.items()):
        classes.setdefault(int(td), []).append(int(gid))
    new_workers = min(new_workers, len(row_ticks))
    n_classes = len(classes)
    total = len(row_ticks)
    # proportional shard allocation, >= 1 per class, largest remainder
    counts = {td: 1 for td in classes}
    extra = max(new_workers - n_classes, 0)
    if extra:
        quotas = sorted(
            ((len(ids) * extra / total, td) for td, ids in classes.items()),
            reverse=True)
        whole = {td: int(q) for q, td in quotas}
        left = extra - sum(whole.values())
        for q, td in quotas:
            add = 1 if left > 0 and q - whole[td] > 0 else 0
            counts[td] += whole[td] + add
            left -= add
    out = []
    for td in sorted(classes):
        ids = classes[td]
        k = min(counts[td], len(ids))
        base, rem = divmod(len(ids), k)
        start = 0
        for w in range(k):
            n = base + (1 if w < rem else 0)
            out.append((tuple(ids[start:start + n]), td))
            start += n
    return out


def regroup_shard_leaves(old_shards, new_ids) -> list:
    """Rows for ONE new shard, drawn from the old shards' checkpoint
    leaves.

    ``old_shards`` — list of ``(replica_ids, leaves_list)`` where
    ``leaves_list`` holds the shard checkpoint's arrays in flatten
    order, each with the shard rows on axis 0.  Returns the new shard's
    leaves (same flatten order, rows in ``new_ids`` order).  Refuses a
    duplicated or missing global id loudly — a resize bug must not
    silently mint or lose a replica row."""
    loc: dict = {}
    for si, (ids, _) in enumerate(old_shards):
        for ri, gid in enumerate(ids):
            if int(gid) in loc:
                raise ValueError(
                    f"replica id {gid} appears in more than one shard")
            loc[int(gid)] = (si, ri)
    missing = [int(g) for g in new_ids if int(g) not in loc]
    if missing:
        raise ValueError(
            f"replica ids {missing} missing from the old shards")
    nleaf = {len(lv) for _, lv in old_shards}
    if len(nleaf) != 1:
        raise ValueError(
            f"old shards disagree on leaf count ({sorted(nleaf)})")
    out = []
    for j in range(nleaf.pop()):
        rows = []
        for gid in new_ids:
            si, ri = loc[int(gid)]
            rows.append(np.asarray(old_shards[si][1][j])[ri])
        out.append(np.stack(rows, axis=0))
    return out


# -------------------------------------------------------------- merge --


def merge_shard_leaves(shards, total: int | None = None):
    """Row-merge per-shard counter leaves into full-campaign leaves.

    ``shards`` — list of ``(replica_ids, leaves)`` where every leaf
    array's leading axis indexes the shard's rows in ``replica_ids``
    order.  The global ids must tile ``0..total-1`` exactly (no holes,
    no overlaps — a supervisor bug here must not silently produce a
    plausible ensemble).  Output rows are in global id order, so the
    merged leaves are positionally identical to an uninterrupted
    full-campaign run's."""
    ids = [int(i) for rid, _ in shards for i in rid]
    if total is None:
        total = max(ids) + 1 if ids else 0
    if sorted(ids) != list(range(total)):
        raise ValueError(
            f"shard replica ids do not tile 0..{total - 1}: got "
            f"{sorted(ids)}")
    order = np.argsort(np.asarray(ids, dtype=np.int64), kind="stable")

    def rec(parts):
        if isinstance(parts[0], dict):
            keys = list(parts[0].keys())
            for p in parts[1:]:
                if list(p.keys()) != keys:
                    raise ValueError("shard leaves disagree on keys")
            return {k: rec([p[k] for p in parts]) for k in keys}
        cat = np.concatenate([np.asarray(p) for p in parts], axis=0)
        return cat[order]

    return rec([leaves for _, leaves in shards])
