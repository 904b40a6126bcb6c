"""Global statistics collection with measurement-phase gating.

TPU-native equivalent of the reference's ``GlobalStatistics`` singleton
(src/common/GlobalStatistics.{h,cc}): named StdDev accumulators
(``addStdDev`` :97), histograms (:103) and the measurement gating that only
records after init + transition phases finish (``startMeasuring`` :113-118,
RECORD_STATS macro GlobalStatistics.h:35-39).  Instead of per-call mutexed
accumulators, per-node handler code emits (value, mask) event arrays and
the engine folds them in with masked reductions each tick.

Scalar accumulators keep (n, sum, sumsq, min, max) so finish() can report
name.mean/.stddev/.min/.max exactly like GlobalStatistics::finish
(GlobalStatistics.cc:107-145).
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
from oversim_tpu.core.scopes import scoped

F32 = jnp.float32
F64 = jnp.float64  # accumulators: f32 would silently drop increments >2^24
I64 = jnp.int64


@dataclasses.dataclass(frozen=True)
class StatSpec:
    """Static declaration of a simulation's metric namespace."""

    scalars: tuple = ()            # names of StdDev-style accumulators
    hists: tuple = ()              # (name, num_bins) pairs
    counters: tuple = ()           # monotonically increasing counts


def init_stats(spec: StatSpec) -> dict:
    s = {}
    for name in spec.scalars:
        s["s:" + name] = jnp.zeros((5,), F64).at[3].set(jnp.inf).at[4].set(-jnp.inf)
    for name, bins in spec.hists:
        s["h:" + name] = jnp.zeros((bins,), I64)
    for name in spec.counters:
        s["c:" + name] = jnp.zeros((), I64)
    return s


@scoped("stats.record")
def record(stats: dict, events: dict, gate) -> dict:
    """Fold one tick's events into the accumulators.

    ``events`` maps "s:name" -> (values, mask), "h:name" -> (bin_idx, mask),
    "c:name" -> count; ``gate`` is the measurement-phase flag (scalar bool).
    """
    out = dict(stats)
    for key, ev in events.items():
        if key.startswith("s:"):
            vals, mask = ev
            vals = vals.astype(F64)
            m = (mask & gate).astype(F64)
            acc = out[key]
            n = jnp.sum(m)
            out[key] = jnp.stack([
                acc[0] + n,
                acc[1] + jnp.sum(vals * m),
                acc[2] + jnp.sum(vals * vals * m),
                jnp.minimum(acc[3], jnp.min(jnp.where(m > 0, vals, jnp.inf))),
                jnp.maximum(acc[4], jnp.max(jnp.where(m > 0, vals, -jnp.inf))),
            ])
        elif key.startswith("h:"):
            idx, mask = ev
            acc = out[key]
            bins = acc.shape[0]
            idx = jnp.clip(idx, 0, bins - 1).ravel()
            hit = (mask & gate).ravel()
            # counted by comparison, one fused reduction a bin: a
            # scatter-add into the i64 bins costs 60 ns an EVENT on the
            # chip, nearly every one adding 0 (PERF.md, PR 36)
            out[key] = acc + jnp.sum(
                (idx[None, :] == jnp.arange(bins, dtype=idx.dtype)[:, None])
                & hit[None, :], axis=1, dtype=jnp.int32).astype(I64)
        elif key.startswith("c:"):
            out[key] = out[key] + jnp.sum(jnp.asarray(ev, I64)) * gate.astype(I64)
        elif key.startswith("g:"):
            pass  # logic-global update request, consumed by post_step
        else:
            raise KeyError(f"unknown stat class: {key}")
    return out


def summarize(stats: dict) -> dict:
    """Host-side: accumulators -> {name: {mean, stddev, min, max, count}} /
    histograms -> list / counters -> int (GlobalStatistics::finish style)."""
    out = {}
    for key, val in stats.items():
        import numpy as np
        v = np.asarray(val)
        name = key[2:]
        if key.startswith("s:"):
            n, s, s2 = float(v[0]), float(v[1]), float(v[2])
            mean = s / n if n else math.nan
            var = max(s2 / n - mean * mean, 0.0) if n else math.nan
            out[name] = {
                "count": int(n), "mean": mean, "stddev": math.sqrt(var) if n else math.nan,
                "min": float(v[3]) if n else math.nan,
                "max": float(v[4]) if n else math.nan,
            }
        elif key.startswith("h:"):
            out[name] = v.tolist()
        else:
            out[name] = int(v)
    return out


# -- cross-replica ensemble layer (oversim_tpu/campaign/) -------------------
#
# A campaign state stacks every accumulator with a leading replica axis:
# "s:name" -> [S, 5], "h:name" -> [S, bins], "c:name" -> [S].  The reduce
# runs ON DEVICE (one jit, one device_get of small [S]-shaped leaves);
# the CI half-widths (Student-t, no scipy dependency) attach host-side in
# ``ensemble_summary``.  This is the TPU-native analogue of scripting
# ``./OverSim -r N`` and averaging the N scalar files by hand.

def ensemble_reduce(stats: dict) -> dict:
    """Device-side: stacked accumulators -> per-replica + cross-replica
    moments.  Returns a dict of small jnp arrays, safe to device_get.

    Scalars ("s:") -> {per_mean[S], per_stddev[S], per_count[S],
    mean, stddev, sem, k} where the cross-replica moments are over the
    k replicas that recorded data (sample stddev, /(k-1)).
    Histograms ("h:") -> per-replica probability mass functions and
    their cross-replica mean/stddev/sem per bin (+ raw count sums).
    Counters ("c:") -> per-replica values + cross-replica mean/stddev.
    """
    out = {}
    for key, acc in stats.items():
        if key.startswith("s:"):
            n = acc[:, 0]                                    # [S]
            has = n > 0
            safe_n = jnp.maximum(n, 1.0)
            per_mean = acc[:, 1] / safe_n
            per_var = jnp.maximum(acc[:, 2] / safe_n - per_mean * per_mean,
                                  0.0)
            per_stddev = jnp.sqrt(per_var)
            k = jnp.sum(has.astype(F64))
            safe_k = jnp.maximum(k, 1.0)
            mean = jnp.sum(jnp.where(has, per_mean, 0.0)) / safe_k
            dev2 = jnp.where(has, (per_mean - mean) ** 2, 0.0)
            var = jnp.sum(dev2) / jnp.maximum(k - 1.0, 1.0)
            stddev = jnp.sqrt(var)
            sem = stddev / jnp.sqrt(safe_k)
            out[key] = dict(
                per_count=n, per_mean=per_mean,
                per_stddev=per_stddev, mean=mean, stddev=stddev,
                sem=sem, k=k)
        elif key.startswith("h:"):
            counts = acc.astype(F64)                         # [S, B]
            tot = jnp.sum(counts, axis=1, keepdims=True)     # [S, 1]
            has = tot[:, 0] > 0
            pmf = counts / jnp.maximum(tot, 1.0)             # [S, B]
            k = jnp.sum(has.astype(F64))
            safe_k = jnp.maximum(k, 1.0)
            mean = jnp.sum(jnp.where(has[:, None], pmf, 0.0),
                           axis=0) / safe_k                  # [B]
            dev2 = jnp.where(has[:, None], (pmf - mean[None, :]) ** 2, 0.0)
            var = jnp.sum(dev2, axis=0) / jnp.maximum(k - 1.0, 1.0)
            stddev = jnp.sqrt(var)
            sem = stddev / jnp.sqrt(safe_k)
            out[key] = dict(
                per_counts=acc, per_total=tot[:, 0],
                per_pmf=pmf, mean=mean, stddev=stddev, sem=sem, k=k,
                total=jnp.sum(acc, axis=0))
        elif key.startswith("c:"):
            v = acc.astype(F64)                              # [S]
            s = v.shape[0]
            mean = jnp.mean(v)
            var = (jnp.sum((v - mean) ** 2) / (s - 1.0)) if s > 1 \
                else jnp.zeros(())
            out[key] = dict(
                per_replica=acc, total=jnp.sum(acc),
                mean=mean, stddev=jnp.sqrt(var),
                sem=jnp.sqrt(var) / math.sqrt(s))
    return out


# two-sided Student-t critical values, t_{df, 1-alpha/2} — enough rows
# for any sane replica count; falls back to the normal quantile past 30
_T_TABLE = {
    0.95: (12.706, 4.303, 3.182, 2.776, 2.571, 2.447, 2.365, 2.306, 2.262,
           2.228, 2.201, 2.179, 2.160, 2.145, 2.131, 2.120, 2.110, 2.101,
           2.093, 2.086, 2.080, 2.074, 2.069, 2.064, 2.060, 2.056, 2.052,
           2.048, 2.045, 2.042),
    0.99: (63.657, 9.925, 5.841, 4.604, 4.032, 3.707, 3.499, 3.355, 3.250,
           3.169, 3.106, 3.055, 3.012, 2.977, 2.947, 2.921, 2.898, 2.878,
           2.861, 2.845, 2.831, 2.819, 2.807, 2.797, 2.787, 2.779, 2.771,
           2.763, 2.756, 2.750),
}
_T_NORMAL = {0.95: 1.960, 0.99: 2.576}


def t_critical(df: int, confidence: float = 0.95) -> float:
    """Two-sided Student-t critical value (table lookup, no scipy)."""
    if confidence not in _T_TABLE:
        raise ValueError(f"confidence must be one of {sorted(_T_TABLE)}")
    if df < 1:
        return math.nan
    tab = _T_TABLE[confidence]
    return tab[df - 1] if df <= len(tab) else _T_NORMAL[confidence]


def series_summary(values, confidence: float = 0.95) -> dict:
    """Cross-replica CI bands for a stacked time series (host-side).

    ``values`` is a ``[S, K]`` array: S replica series over K aligned
    sample points (the telemetry plane's ring samples — replicas share
    the tick-based sampling cadence, see oversim_tpu/telemetry.py).
    NaN entries (e.g. a scalar mean before its first event) are
    excluded per sample point.  Returns JSON-ready lists — {kind, k,
    mean[K], stddev[K], sem[K], ci[K], confidence} with the Student-t
    half-width over the replicas that carry data at each point (None
    where fewer than two do)."""
    import numpy as np

    v = np.asarray(values, float)
    if v.ndim != 2:
        raise ValueError(f"series_summary wants [S, K], got {v.shape}")
    s, _ = v.shape
    has = ~np.isnan(v)
    k = has.sum(axis=0)                                   # [K]
    safe_k = np.maximum(k, 1)
    mean = np.where(k > 0, np.nansum(v, axis=0) / safe_k, np.nan)
    dev2 = np.where(has, (v - mean[None, :]) ** 2, 0.0)
    var = dev2.sum(axis=0) / np.maximum(k - 1, 1)
    stddev = np.sqrt(var)
    sem = stddev / np.sqrt(safe_k)
    t = np.array([t_critical(int(ki) - 1, confidence) if ki > 1
                  else math.nan for ki in k])
    ci = t * sem
    clean = lambda a: [None if x != x else float(x)  # noqa: E731
                       for x in np.asarray(a, float)]
    return {"kind": "series", "replicas": s, "k": k.astype(int).tolist(),
            "mean": clean(mean), "stddev": clean(stddev),
            "sem": clean(sem), "ci": clean(ci), "confidence": confidence}


def ensemble_summary(reduced: dict, confidence: float = 0.95) -> dict:
    """Host-side: attach Student-t CI half-widths (ci = t_{k-1} * sem)
    to a (device_get of a) ``ensemble_reduce`` result and convert leaves
    to plain python.  Schema per metric — scalar: {kind, k, mean, stddev,
    sem, ci, confidence, per_replica: {count, mean, stddev}[S]};
    hist: the same per-bin (lists of length B) plus raw counts;
    counter: {kind, total, mean, stddev, sem, ci, per_replica[S]}."""
    import numpy as np

    out = {}
    for key, r in reduced.items():
        name = key[2:]
        if key.startswith("s:"):
            k = int(np.asarray(r["k"]))
            t = t_critical(k - 1, confidence) if k > 1 else math.nan
            sem = float(np.asarray(r["sem"]))
            out[name] = {
                "kind": "scalar", "k": k,
                "mean": float(np.asarray(r["mean"])),
                "stddev": float(np.asarray(r["stddev"])),
                "sem": sem,
                "ci": t * sem if k > 1 else math.nan,
                "confidence": confidence,
                "per_replica": {
                    "count": np.asarray(r["per_count"]).astype(int).tolist(),
                    "mean": np.asarray(r["per_mean"]).tolist(),
                    "stddev": np.asarray(r["per_stddev"]).tolist(),
                },
            }
        elif key.startswith("h:"):
            k = int(np.asarray(r["k"]))
            t = t_critical(k - 1, confidence) if k > 1 else math.nan
            sem = np.asarray(r["sem"])
            ci = (t * sem).tolist() if k > 1 \
                else [math.nan] * sem.shape[0]
            out[name] = {
                "kind": "hist", "k": k,
                "mean": np.asarray(r["mean"]).tolist(),
                "stddev": np.asarray(r["stddev"]).tolist(),
                "sem": sem.tolist(),
                "ci": ci,
                "confidence": confidence,
                "total": np.asarray(r["total"]).astype(int).tolist(),
                "per_replica": {
                    "counts": np.asarray(r["per_counts"]).astype(int).tolist(),
                    "total": np.asarray(r["per_total"]).astype(int).tolist(),
                },
            }
        else:
            pr = np.asarray(r["per_replica"])
            s = pr.shape[0]
            t = t_critical(s - 1, confidence) if s > 1 else math.nan
            sem = float(np.asarray(r["sem"]))
            out[name] = {
                "kind": "counter",
                "total": int(np.asarray(r["total"])),
                "mean": float(np.asarray(r["mean"])),
                "stddev": float(np.asarray(r["stddev"])),
                "sem": sem,
                "ci": t * sem if s > 1 else math.nan,
                "confidence": confidence,
                "per_replica": pr.astype(int).tolist(),
            }
    return out
