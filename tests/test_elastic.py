"""Elastic-fleet machinery pins (oversim_tpu/elastic/) — fast tier.

Everything here runs WITHOUT compiling a simulation: the failure
classes, the seeded backoff schedule, backend acquisition, the
synthetic reshard grow/shrink identity (including the loud
fingerprint-mismatch refusals), campaign ``replica_ids`` subsetting, and
the supervisor's host-side shard/merge/heartbeat/chaos helpers.  The
real-sim reshard identities live in tests/test_zz_elastic.py.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from oversim_tpu import checkpoint as ckpt_mod
from oversim_tpu.campaign import Campaign, CampaignParams
from oversim_tpu.elastic import (FATAL, TRANSIENT, AutoscalePolicy,
                                 Autoscaler, RetryBudgetExceeded,
                                 RetryPolicy, Signals, acquire_backend,
                                 backoff_delays, chaos_schedule,
                                 classify, decode_leaves, encode_leaves,
                                 heartbeat_age, merge_shard_leaves,
                                 parse_exposition_text, plan_resize,
                                 read_json, regroup_shard_leaves,
                                 replica_fingerprint, reshard_load,
                                 reshard_stacked, scrape_exposition,
                                 shard_replicas, with_retry,
                                 write_heartbeat, write_json_atomic)


# -- failure classes ---------------------------------------------------------


def test_classify_classes():
    # transient by type: the whole I/O family retries
    assert classify(ConnectionResetError("peer reset")) == TRANSIENT
    assert classify(BrokenPipeError("pipe")) == TRANSIENT
    assert classify(TimeoutError()) == TRANSIENT
    assert classify(OSError("nfs is sad")) == TRANSIENT
    # transient by marker: XLA runtime errors arrive as RuntimeError
    # with a gRPC-style status in the text
    assert classify(RuntimeError("UNAVAILABLE: backend went away")) \
        == TRANSIENT
    assert classify(RuntimeError("DEADLINE_EXCEEDED: 30s")) == TRANSIENT
    assert classify(RuntimeError("device preempted by scheduler")) \
        == TRANSIENT
    assert classify(RuntimeError("RESOURCE_EXHAUSTED: hbm")) == TRANSIENT
    # fatal by type: a retry would fail identically
    assert classify(ValueError("bad shape")) == FATAL
    assert classify(TypeError("no")) == FATAL
    assert classify(AssertionError()) == FATAL
    # fatal markers OUTRANK transient types/markers: an OSError carrying
    # INVALID_ARGUMENT is a program bug, not a capacity problem
    assert classify(OSError("INVALID_ARGUMENT: bad buffer")) == FATAL
    assert classify(RuntimeError("UNIMPLEMENTED: collective")) == FATAL
    # and a fatal TYPE stays fatal even when the text smells transient
    assert classify(ValueError("timeout while parsing")) == FATAL
    # unknown errors default fatal — silently retrying a bug hides it
    assert classify(RuntimeError("some novel failure")) == FATAL


def test_backoff_delays_seeded_and_capped():
    p = RetryPolicy(attempts=6, base_s=1.0, factor=4.0, max_s=10.0,
                    jitter=0.5, seed=3)
    d1, d2 = backoff_delays(p), backoff_delays(p)
    assert d1 == d2                       # same seed -> same schedule
    assert len(d1) == p.attempts - 1
    bases = [min(p.max_s, p.base_s * p.factor ** i) for i in range(5)]
    for d, b in zip(d1, bases):
        assert b <= d <= b * (1 + p.jitter)
    # pre-jitter ceiling engaged: last delays never exceed max*(1+jitter)
    assert max(d1) <= p.max_s * (1 + p.jitter)
    # different seeds de-synchronize (fleet workers seeded by index)
    assert backoff_delays(RetryPolicy(attempts=6, seed=0)) \
        != backoff_delays(RetryPolicy(attempts=6, seed=1))
    assert backoff_delays(RetryPolicy(attempts=1)) == []


def test_with_retry_transient_then_success():
    p = RetryPolicy(attempts=5, base_s=0.1, seed=7)
    slept, seen, calls = [], [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionResetError("blip")
        return "ok"

    out = with_retry(flaky, policy=p, sleep=slept.append,
                     on_retry=lambda a, d, e: seen.append((a, d)))
    assert out == "ok" and len(calls) == 3
    # slept exactly the policy's first two delays, observed by on_retry
    assert slept == backoff_delays(p)[:2]
    assert [a for a, _ in seen] == [0, 1]
    assert [d for _, d in seen] == slept


def test_with_retry_fatal_immediate_and_exhaustion():
    slept = []
    with pytest.raises(ValueError, match="bad program"):
        with_retry(lambda: (_ for _ in ()).throw(ValueError("bad program")),
                   sleep=slept.append)
    assert slept == []                    # fatal never sleeps

    p = RetryPolicy(attempts=3, base_s=0.1, seed=0)
    calls = []

    def always_down():
        calls.append(1)
        raise TimeoutError("still down")

    with pytest.raises(TimeoutError):
        with_retry(always_down, policy=p, sleep=slept.append)
    assert len(calls) == p.attempts       # budget honored exactly
    assert slept == backoff_delays(p)     # attempts-1 sleeps


def test_acquire_backend_success_and_exhaustion_raises(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    ok = acquire_backend(RetryPolicy(attempts=2), probe=lambda: "tpu",
                         sleep=lambda _: None)
    assert ok == {"platform": "tpu", "attempts": 1}

    # a transient failure is retried, then succeeds
    tries = []

    def flaky():
        tries.append(1)
        if len(tries) < 3:
            raise RuntimeError("UNAVAILABLE: chip down")
        return "tpu"

    assert acquire_backend(RetryPolicy(attempts=3, base_s=0.01),
                           probe=flaky, sleep=lambda _: None) \
        == {"platform": "tpu", "attempts": 3}

    # exhausted attempts RAISE the last error — there is no degradation
    # to another platform, and the environment is never touched
    calls = []
    with pytest.raises(RuntimeError, match="chip down"):
        acquire_backend(
            RetryPolicy(attempts=3, base_s=0.01),
            probe=lambda: (calls.append(1), (_ for _ in ()).throw(
                RuntimeError("UNAVAILABLE: chip down")))[1],
            sleep=lambda _: None)
    assert len(calls) == 3
    assert "JAX_PLATFORMS" not in os.environ

    # fatal probe errors raise at once, no retry
    calls.clear()
    with pytest.raises(ValueError):
        acquire_backend(RetryPolicy(attempts=3),
                        probe=lambda: (calls.append(1), (_ for _ in ()).throw(
                            ValueError("bad build")))[1],
                        sleep=lambda _: None)
    assert len(calls) == 1
    assert "JAX_PLATFORMS" not in os.environ


# -- synthetic reshard -------------------------------------------------------


def _stacked(s, fill=0.0, dtype=np.float32):
    """A campaign-stacked-shaped pytree: every leaf leads with [s]."""
    return {
        "a": jnp.asarray(np.arange(s * 3).reshape(s, 3) + fill, dtype),
        "b": jnp.asarray(np.arange(s) + int(fill), jnp.int64),
    }


def test_reshard_stacked_grow_shrink_roundtrip():
    old = _stacked(2, fill=100.0)
    fresh = _stacked(5, fill=0.0)
    grown = reshard_stacked(old, fresh)
    # surviving rows 0..1 are the checkpointed arrays UNCHANGED
    np.testing.assert_array_equal(grown["a"][:2], old["a"])
    np.testing.assert_array_equal(grown["b"][:2], old["b"])
    # grown rows come verbatim from fresh (deterministic re-seed slots)
    np.testing.assert_array_equal(grown["a"][2:], fresh["a"][2:])
    np.testing.assert_array_equal(grown["b"][2:], fresh["b"][2:])
    # shrink straight back: bit-identical round trip for the survivors
    back = reshard_stacked(grown, _stacked(2))
    for k in ("a", "b"):
        np.testing.assert_array_equal(back[k], old[k])
        assert back[k].dtype == old[k].dtype
    # same-size reshard is the identity on values
    same = reshard_stacked(old, _stacked(2))
    np.testing.assert_array_equal(same["a"], old["a"])


def test_reshard_stacked_refusals():
    old = _stacked(2)
    # trailing-shape mismatch -> loud fingerprint error, never silent
    bad = {"a": jnp.zeros((5, 4), jnp.float32),
           "b": jnp.zeros((5,), jnp.int64)}
    with pytest.raises(ValueError, match="reshard fingerprint mismatch"):
        reshard_stacked(old, bad)
    # dtype is part of the per-replica fingerprint too
    baddt = {"a": jnp.zeros((5, 3), jnp.float64),
             "b": jnp.zeros((5,), jnp.int64)}
    with pytest.raises(ValueError, match="reshard fingerprint mismatch"):
        reshard_stacked(old, baddt)
    # pytree structure mismatch
    with pytest.raises(ValueError, match="pytree structure"):
        reshard_stacked(old, {"a": jnp.zeros((5, 3), jnp.float32)})
    # scalar leaf = not stacked state
    with pytest.raises(ValueError, match="scalar"):
        reshard_stacked({"a": jnp.zeros((2, 3)), "b": jnp.float32(0)},
                        {"a": jnp.zeros((5, 3)), "b": jnp.float32(0)})


def test_replica_fingerprint_is_extent_independent():
    assert replica_fingerprint(_stacked(2)) \
        == replica_fingerprint(_stacked(8))
    assert replica_fingerprint(_stacked(2)) != replica_fingerprint(
        {"a": jnp.zeros((2, 4), jnp.float32),
         "b": jnp.zeros((2,), jnp.int64)})


class _FakeCamp:
    """Quacks like Campaign for reshard_load: describe() + init() + grid."""

    def __init__(self, s, base_seed=1, sweep=(), replicas=None,
                 replica_ids=None, fill=0.0):
        self._s = s
        self._fill = fill
        self.grid = [{}] if not sweep else [dict(p) for p in
                                            ({"x": v} for _, vs in sweep
                                             for v in vs)]
        self._desc = {
            "replicas": s if replicas is None else replicas,
            "base_seed": base_seed,
            "sweep": [[n, list(v)] for n, v in sweep],
            "replica_ids": (list(range(s)) if replica_ids is None
                            else list(replica_ids)),
            "s": s, "total": s,
        }

    def describe(self):
        return dict(self._desc)

    def init(self):
        return _stacked(self._s, fill=self._fill)


def test_reshard_load_grow_and_meta(tmp_path):
    path = str(tmp_path / "ck.npz")
    old = _stacked(2, fill=100.0)
    ckpt_mod.save(path, old, meta={
        "config_hash": "cafe", "campaign": _FakeCamp(2).describe(),
        "fleet": {"ticks_done": 32}})
    camp = _FakeCamp(5, fill=7.0)
    state, meta = reshard_load(path, camp, expect_config="cafe")
    np.testing.assert_array_equal(state["a"][:2], old["a"])
    np.testing.assert_array_equal(state["a"][2:], camp.init()["a"][2:])
    # meta rides back so callers recover fleet/service bookkeeping
    assert meta["fleet"]["ticks_done"] == 32
    assert meta["format"] == ckpt_mod.FORMAT
    # scenario refusal, exactly like checkpoint.load
    with pytest.raises(ValueError, match="scenario mismatch"):
        reshard_load(path, camp, expect_config="beef")


def test_reshard_load_campaign_identity_refusals(tmp_path):
    path = str(tmp_path / "ck.npz")
    ckpt_mod.save(path, _stacked(2), meta={
        "campaign": _FakeCamp(2, base_seed=1).describe()})
    # wrong base seed -> grown slots would be mis-seeded
    with pytest.raises(ValueError, match="base_seed"):
        reshard_load(path, _FakeCamp(5, base_seed=2))
    # replica-id prefix must agree: row k keeps its identity
    with pytest.raises(ValueError, match="replica-id prefix"):
        reshard_load(path, _FakeCamp(5, replica_ids=(4, 5, 6, 7, 8)))
    # sweep grid must match
    with pytest.raises(ValueError, match="sweep grid"):
        reshard_load(path, _FakeCamp(5, sweep=(("x", (1.0, 2.0)),),
                                     replica_ids=(0, 1, 2, 3, 4)))
    # under a sweep the id->grid-point map is id // replicas: changing
    # `replicas` renumbers every parameter point, so it is refused ...
    sweep = (("x", (1.0, 2.0)),)
    ckpt_mod.save(path, _stacked(4), meta={
        "campaign": _FakeCamp(4, sweep=sweep, replicas=2).describe()})
    with pytest.raises(ValueError, match="grid-point mapping"):
        reshard_load(path, _FakeCamp(8, sweep=sweep, replicas=4,
                                     replica_ids=range(8)))
    # ... but a PURE seed sweep may grow/shrink the replica axis freely
    ckpt_mod.save(path, _stacked(2), meta={
        "campaign": _FakeCamp(2, replicas=2).describe()})
    state, _ = reshard_load(path, _FakeCamp(5, replicas=5))
    assert int(np.shape(state["a"])[0]) == 5
    # leaf-count mismatch is a structural refusal
    ckpt_mod.save(path, {"a": jnp.zeros((2, 3), jnp.float32)})
    with pytest.raises(ValueError, match="leaves"):
        reshard_load(path, _FakeCamp(5))


# -- campaign replica_ids ----------------------------------------------------


def test_campaign_replica_ids_validation_and_mapping():
    # __init__ never touches sim, so the id bookkeeping tests compile
    # nothing
    camp = Campaign(None, CampaignParams(replicas=8, base_seed=3,
                                         replica_ids=(4, 5, 6, 7)))
    assert camp.ids == (4, 5, 6, 7)
    assert camp.s == 4 and camp.total == 8
    d = camp.describe()
    assert d["replica_ids"] == [4, 5, 6, 7]
    assert d["total"] == 8 and d["s"] == 4
    # full campaign: ids are the identity
    assert Campaign(None, CampaignParams(replicas=3)).ids == (0, 1, 2)

    with pytest.raises(ValueError, match="at least one replica id"):
        Campaign(None, CampaignParams(replicas=4, replica_ids=()))
    with pytest.raises(ValueError, match="outside"):
        Campaign(None, CampaignParams(replicas=4, replica_ids=(0, 9)))

    # a subset campaign's rows carry their FULL-campaign sweep point:
    # global id i sits at grid point i // replicas
    sweep = (("churn.lifetimeMean", (100.0, 200.0)),)
    sub = Campaign(None, CampaignParams(replicas=2, sweep=sweep,
                                        replica_ids=(2, 3)))
    assert sub.replica_ov(0) == {"churn.lifetimeMean": 200.0}
    assert float(sub.sweep_stack["churn.lifetimeMean"][0]) == 200.0


# -- fleet host helpers ------------------------------------------------------


def test_shard_replicas_tiles_contiguously():
    assert shard_replicas(8, 2) == [(0, 1, 2, 3), (4, 5, 6, 7)]
    assert shard_replicas(7, 3) == [(0, 1, 2), (3, 4), (5, 6)]
    # more workers than replicas: only non-empty shards
    assert shard_replicas(3, 5) == [(0,), (1,), (2,)]
    assert shard_replicas(1, 1) == [(0,)]
    for total, workers in ((8, 3), (13, 4), (5, 5)):
        flat = [i for sh in shard_replicas(total, workers) for i in sh]
        assert flat == list(range(total))
    with pytest.raises(ValueError):
        shard_replicas(0, 2)
    with pytest.raises(ValueError):
        shard_replicas(4, 0)


def test_merge_shard_leaves_global_order_and_refusals():
    leaves = {"kbr": {"sent": np.arange(6, dtype=np.int64) * 10},
              "alive": np.arange(6, dtype=np.float32)}

    def rows(ids):
        return jax.tree.map(lambda x: x[list(ids)], leaves)

    # shards handed over OUT of order still merge into global id order
    merged = merge_shard_leaves([((3, 4, 5), rows((3, 4, 5))),
                                 ((0, 1, 2), rows((0, 1, 2)))])
    np.testing.assert_array_equal(merged["kbr"]["sent"],
                                  leaves["kbr"]["sent"])
    np.testing.assert_array_equal(merged["alive"], leaves["alive"])
    assert merged["alive"].dtype == np.float32

    with pytest.raises(ValueError, match="do not tile"):
        merge_shard_leaves([((0, 1), rows((0, 1))),
                            ((1, 2), rows((1, 2)))])       # overlap
    with pytest.raises(ValueError, match="do not tile"):
        merge_shard_leaves([((0, 1), rows((0, 1))),
                            ((3,), rows((3,)))], total=4)  # hole
    with pytest.raises(ValueError, match="disagree on keys"):
        merge_shard_leaves([((0,), {"a": np.zeros((1,))}),
                            ((1,), {"b": np.zeros((1,))})])


def test_leaves_json_codec_preserves_dtype():
    tree = {"counters": {"lost": np.asarray([1, 2], np.int64)},
            "ratio": np.asarray([0.5, 0.25], np.float32),
            "mask": np.asarray([True, False])}
    doc = encode_leaves(tree)
    # JSON round trip exactly as the shard artifact files do it
    back = decode_leaves(json.loads(json.dumps(doc)))
    assert back["counters"]["lost"].dtype == np.int64
    assert back["ratio"].dtype == np.float32        # NOT widened to f64
    assert back["mask"].dtype == np.bool_
    np.testing.assert_array_equal(back["ratio"], tree["ratio"])
    # the ensemble-identity check compares encoded docs for equality
    assert encode_leaves(back) == doc


def test_chaos_schedule_seeded():
    plan = chaos_schedule(5, workers=3, seed=11, span_s=4.0,
                          min_delay_s=0.5)
    assert plan == chaos_schedule(5, 3, 11, span_s=4.0, min_delay_s=0.5)
    assert plan != chaos_schedule(5, 3, 12, span_s=4.0, min_delay_s=0.5)
    assert len(plan) == 5
    assert plan == sorted(plan)
    for delay, worker in plan:
        assert 0.5 <= delay < 4.5
        assert 0 <= worker < 3


def test_heartbeat_files(tmp_path):
    hb = str(tmp_path / "w0.heartbeat.json")
    assert heartbeat_age(hb) is None          # never written: normal
    write_heartbeat(hb, ticks_done=32, retries=0)
    doc = read_json(hb)
    assert doc["ticks_done"] == 32 and "wall" in doc
    age = heartbeat_age(hb, now=doc["wall"] + 3.5)
    assert age == pytest.approx(3.5)
    # torn/garbage file reads as None, not an exception
    bad = str(tmp_path / "torn.json")
    with open(bad, "w") as f:
        f.write('{"wall": 1.')
    assert read_json(bad) is None
    # atomic writer leaves no tmp droppings
    write_json_atomic(str(tmp_path / "a.json"), {"v": 1})
    assert [p.name for p in tmp_path.glob("*.tmp.*")] == []


# -- total-wall-clock retry budget (ISSUE 17 satellite) ----------------------


def test_with_retry_total_budget_fails_loud():
    # deterministic time: jitter 0 -> every delay exactly 10s; the fake
    # clock only advances when the injected sleep runs
    p = RetryPolicy(attempts=8, base_s=10.0, factor=1.0, jitter=0.0,
                    seed=0, max_total_seconds=25.0)
    now = [0.0]
    calls = []

    def always_down():
        calls.append(1)
        raise TimeoutError("still down")

    with pytest.raises(RetryBudgetExceeded) as ei:
        with_retry(always_down, policy=p, clock=lambda: now[0],
                   sleep=lambda d: now.__setitem__(0, now[0] + d),
                   on_retry=lambda *a: None, label="probe")
    exc = ei.value
    # attempts land at t=0/10/20; the sleep after the third would end at
    # 30s > 25s, so the budget trips there — NOT after all 8 attempts
    assert len(calls) == 3
    assert exc.label == "probe"
    assert exc.budget_s == 25.0 and exc.elapsed_s == 20.0
    assert [a for a, _d, _e in exc.history] == [0, 1, 2]
    assert isinstance(exc.last_error, TimeoutError)
    # the message IS the storm log: every burned attempt, then the budget
    msg = str(exc)
    assert "attempt 3" in msg and "TimeoutError" in msg
    assert "25.0s" in msg
    # a blown budget classifies as TRANSIENT so an outer retry loop
    # treats it like the storm itself
    assert classify(exc) == TRANSIENT


def test_with_retry_budget_generous_falls_through_to_exhaustion():
    p = RetryPolicy(attempts=3, base_s=0.1, jitter=0.0, seed=0,
                    max_total_seconds=3600.0)
    now = [0.0]
    with pytest.raises(TimeoutError):     # attempt budget, not wall
        with_retry(lambda: (_ for _ in ()).throw(TimeoutError("down")),
                   policy=p, clock=lambda: now[0],
                   sleep=lambda d: now.__setitem__(0, now[0] + d),
                   on_retry=lambda *a: None)
    # fatal errors raise before any budget bookkeeping
    with pytest.raises(ValueError):
        with_retry(lambda: (_ for _ in ()).throw(ValueError("bug")),
                   policy=RetryPolicy(max_total_seconds=0.0),
                   sleep=lambda _d: None)


def test_acquire_backend_budget_exhaustion_raises_the_storm_log(monkeypatch):
    # the wall-clock budget runs out first: RetryBudgetExceeded raises
    # with every burned attempt attached — nothing degrades to the cpu
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    p = RetryPolicy(attempts=10, base_s=5.0, factor=1.0, jitter=0.0,
                    seed=0, max_total_seconds=12.0)
    now = [0.0]
    with pytest.raises(RetryBudgetExceeded) as ei:
        acquire_backend(
            p, probe=lambda: (_ for _ in ()).throw(
                RuntimeError("UNAVAILABLE: chip down")),
            clock=lambda: now[0],
            sleep=lambda d: now.__setitem__(0, now[0] + d))
    exc = ei.value
    assert exc.budget_s == 12.0 and exc.elapsed_s == 10.0
    assert [a for a, _d, _e in exc.history] == [0, 1, 2]
    assert all("UNAVAILABLE" in e for _a, _d, e in exc.history)
    assert "budget exceeded" in str(exc)
    assert "JAX_PLATFORMS" not in os.environ


# -- autoscaler policy (ISSUE 17 tentpole) -----------------------------------


def test_autoscale_policy_validation():
    with pytest.raises(ValueError, match="min_workers"):
        AutoscalePolicy(min_workers=0)
    with pytest.raises(ValueError, match="min_workers"):
        AutoscalePolicy(min_workers=3, max_workers=2)
    with pytest.raises(ValueError, match="hysteresis"):
        AutoscalePolicy(up_backlog_per_worker=10.0,
                        down_backlog_per_worker=10.0)
    with pytest.raises(ValueError, match="step"):
        AutoscalePolicy(step=0)


def test_autoscaler_hysteresis_band_and_bounds():
    a = Autoscaler(AutoscalePolicy(
        min_workers=1, max_workers=3, up_backlog_per_worker=100.0,
        down_backlog_per_worker=25.0, cooldown_s=0.0))
    # inside the dead band: the policy wants nothing
    assert a.decide(Signals(backlog=50, workers=1, now_s=0.0)) is None
    d = a.decide(Signals(backlog=150, workers=1, now_s=0.0))
    assert d.action == "scale_up" and (d.from_workers, d.to_workers) == (1, 2)
    assert "backlog/worker 150.0 > 100.0" == d.reason
    # max bound clamps even under unbounded backlog
    assert a.target_for(
        Signals(backlog=1e9, workers=3, now_s=1.0))[0] == 3
    d2 = a.decide(Signals(backlog=10, workers=2, now_s=1.0))
    assert d2.action == "scale_down" and d2.to_workers == 1
    # min bound clamps; thresholds are strict (per == down stays put)
    assert a.target_for(Signals(backlog=0, workers=1, now_s=2.0))[0] == 1
    assert a.target_for(Signals(backlog=25, workers=1, now_s=2.0))[0] == 1
    assert a.scale_ups == 1 and a.scale_downs == 1
    assert len(a.history) == 2


def test_autoscaler_cooldown_and_alignment_deferral():
    a = Autoscaler(AutoscalePolicy(
        max_workers=4, up_backlog_per_worker=100.0,
        down_backlog_per_worker=25.0, cooldown_s=10.0))
    assert a.decide(Signals(backlog=500, workers=1, now_s=0.0)) is not None
    # wants to act again, but inside the cooldown: counted, not taken
    assert a.decide(Signals(backlog=500, workers=2, now_s=3.0)) is None
    assert a.cooldown_skips == 1
    # cooldown elapsed but the caller is mid-resize (not aligned):
    # deferred, and the deferral must NOT burn the cooldown clock
    assert a.decide(Signals(backlog=5000, workers=2, now_s=11.0,
                            aligned=False)) is None
    assert a.deferred == 1
    d = a.decide(Signals(backlog=5000, workers=2, now_s=12.0))
    assert d is not None and d.action == "scale_up"
    # an in-band signal is a no-op, never a skip/deferral
    assert a.decide(Signals(backlog=225, workers=3, now_s=30.0)) is None
    assert a.cooldown_skips == 1 and a.deferred == 1
    desc = a.describe()
    assert desc["scale_ups"] == 2 and desc["scale_downs"] == 0
    assert len(desc["decisions"]) == 2
    assert desc["policy"]["cooldown_s"] == 10.0


def test_autoscaler_p99_latency_trigger():
    a = Autoscaler(AutoscalePolicy(
        p99_up_s=1.0, up_backlog_per_worker=100.0,
        down_backlog_per_worker=25.0, max_workers=4))
    # p99 above the trigger scales up even with the backlog in band
    t, reason = a.target_for(
        Signals(backlog=50, workers=2, now_s=0.0, p99_s=2.5))
    assert t == 3 and "p99" in reason
    # no latency sample -> backlog rules alone
    assert a.target_for(Signals(backlog=50, workers=2, now_s=0.0))[0] == 2


def test_parse_exposition_text_and_scrape_soft_failure():
    text = ("# HELP oversim_autoscale_backlog_rows outstanding\n"
            "# TYPE oversim_autoscale_backlog_rows gauge\n"
            "oversim_autoscale_backlog_rows 640\n"
            'oversim_window_wall_seconds_bucket{le="0.1"} 3\n'
            "garbage line without a number\n")
    fam = parse_exposition_text(text)
    assert fam["oversim_autoscale_backlog_rows"] == 640.0
    # labeled series collapse to the family/series name
    assert fam["oversim_window_wall_seconds_bucket"] == 3.0
    assert "garbage" not in str(sorted(fam))
    # a dead endpoint is a soft miss (None), never an exception — the
    # supervisor keeps deciding off its host-side fallback signal
    assert scrape_exposition("http://127.0.0.1:9/metrics",
                             timeout=0.2) is None


# -- live resize planning (ISSUE 17 tentpole) --------------------------------


def test_plan_resize_grow_shrink_and_classes():
    # uniform resume point: a plain contiguous proportional split
    assert plan_resize({0: 32, 1: 32, 2: 32, 3: 32}, 2) == \
        [((0, 1), 32), ((2, 3), 32)]
    assert plan_resize({0: 0, 1: 0, 2: 0, 3: 0}, 1) == [((0, 1, 2, 3), 0)]
    # mixed resume points can NEVER share a worker: shrinking to 1 still
    # yields one shard per tick class (the supervisor's achievability
    # gate defers the decision instead of forcing this)
    plan = plan_resize({0: 10, 1: 10, 2: 0, 3: 0}, 1)
    assert sorted(plan) == [((0, 1), 10), ((2, 3), 0)]
    # growing splits the biggest class first (largest remainder)
    plan = plan_resize({0: 10, 1: 10, 2: 0, 3: 0}, 3)
    assert len(plan) == 3
    rows = sorted(r for ids, _ in plan for r in ids)
    assert rows == [0, 1, 2, 3]           # every row exactly once
    # never more shards than rows; degenerate inputs refuse loudly
    assert len(plan_resize({0: 0, 1: 0}, 5)) == 2
    with pytest.raises(ValueError):
        plan_resize({}, 2)
    with pytest.raises(ValueError):
        plan_resize({0: 0}, 0)


def test_plan_resize_rows_conserved_property():
    # conservation across a messy mix of classes and worker counts
    row_ticks = {0: 64, 1: 64, 2: 64, 3: 32, 4: 32, 5: 0, 6: 64, 7: 32}
    for new_workers in range(1, 9):
        plan = plan_resize(row_ticks, new_workers)
        rows = sorted(r for ids, _ in plan for r in ids)
        assert rows == sorted(row_ticks), (new_workers, plan)
        for ids, td in plan:
            assert {row_ticks[r] for r in ids} == {td}


def test_regroup_shard_leaves_identity_and_refusals():
    # two old shards, two leaves each, rows tagged by global id
    def leaves(ids):
        return [np.asarray([[gid, gid + 0.5] for gid in ids], np.float32),
                np.asarray(ids, np.int64) * 10]

    old = [((0, 1), leaves((0, 1))), ((2, 3), leaves((2, 3)))]
    # regroup to a different split: rows follow their global ids
    out = regroup_shard_leaves(old, (1, 2))
    np.testing.assert_array_equal(
        out[0], np.asarray([[1, 1.5], [2, 2.5]], np.float32))
    np.testing.assert_array_equal(out[1], np.asarray([10, 20]))
    # regroup-then-merge equals the original merge (no row invented or
    # lost by the resize path); merge runs per leaf — a shard
    # checkpoint's leaves are positional, not a pytree
    re0, re1 = regroup_shard_leaves(old, (0, 1, 2)), \
        regroup_shard_leaves(old, (3,))
    for j in range(2):
        merged = merge_shard_leaves(
            [((0, 1, 2), re0[j]), ((3,), re1[j])], total=4)
        ref = merge_shard_leaves(
            [(ids, lv[j]) for ids, lv in old], total=4)
        np.testing.assert_array_equal(merged, ref)
    # loud refusals: duplicated id, missing id, leaf-count disagreement
    with pytest.raises(ValueError, match="more than one shard"):
        regroup_shard_leaves(
            [((0, 1), leaves((0, 1))), ((1, 2), leaves((1, 2)))], (0,))
    with pytest.raises(ValueError, match="missing"):
        regroup_shard_leaves(old, (0, 7))
    with pytest.raises(ValueError, match="leaf count"):
        regroup_shard_leaves(
            [((0, 1), leaves((0, 1))), ((2, 3), leaves((2, 3))[:1])],
            (0, 2))
