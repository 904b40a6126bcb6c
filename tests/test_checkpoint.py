"""Checkpoint/resume: restored runs continue bit-identically.

The format-level tests (v2 manifest, atomic write, v1 back-compat,
scenario-hash refusal) run on tiny dict pytrees — cheap, no sim
compile; the end-to-end resume pin compiles one small chord sim."""

import json
import os

import numpy as np
import pytest

from oversim_tpu import checkpoint as ckpt
from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.chord import ChordLogic


def _make_sim():
    logic = ChordLogic(app=KbrTestApp(KbrTestParams(test_interval=5.0)))
    cp = churn_mod.ChurnParams(model="none", target_num=8,
                               init_interval=0.2)
    return sim_mod.Simulation(logic, cp)


def test_roundtrip_and_exact_resume(tmp_path):
    sim = _make_sim()
    def run(st, n_chunks):
        # ONE chunk length: run_chunk compiles again for every length
        for _ in range(n_chunks):
            st = sim.run_chunk(st, 50)
        return st

    st = run(sim.init(seed=3), 3)       # 150 ticks
    path = str(tmp_path / "ck.npz")
    ckpt.save(path, st)

    # continue the original: 100 ticks
    a = run(st, 2)
    # restore and continue the copy
    b = run(ckpt.load(path, sim.init(seed=0)), 2)

    import jax
    la, _ = jax.tree.flatten(a)
    lb, _ = jax.tree.flatten(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_v2_meta_and_atomic_write(tmp_path):
    """v2 checkpoints embed a JSON manifest (format, git rev, caller
    extras) and the write is tmp+rename atomic: no torn .tmp survives,
    and an existing checkpoint is only ever replaced whole."""
    path = str(tmp_path / "ck.npz")
    state = {"a": np.arange(4, dtype=np.int64),
             "b": np.ones((2, 2), np.float32)}
    ckpt.save(path, state, meta={"config_hash": "abc123",
                                 "note": "hello"})
    assert not os.path.exists(path + ".tmp"), "tmp file must not remain"

    meta = ckpt.read_meta(path)
    assert meta["format"] == ckpt.FORMAT
    assert meta["config_hash"] == "abc123"
    assert meta["note"] == "hello"
    assert "git_rev" in meta

    example = {"a": np.zeros(4, np.int64), "b": np.zeros((2, 2),
                                                         np.float32)}
    out = ckpt.load(path, example, expect_config="abc123")
    np.testing.assert_array_equal(np.asarray(out["a"]), state["a"])

    # same arrays, different recorded scenario -> refused
    with pytest.raises(ValueError, match="scenario mismatch"):
        ckpt.load(path, example, expect_config="zzz999")


def test_v1_checkpoint_still_loads(tmp_path):
    """A hand-written v1 file (no __meta__) must load, report its
    format from read_meta, and pass expect_config (v1 has no hash)."""
    import jax
    path = str(tmp_path / "v1.npz")
    state = {"x": np.arange(3, dtype=np.int32),
             "y": np.full((2,), 7.0, np.float64)}
    leaves = jax.tree.leaves(state)
    with open(path, "wb") as f:
        np.savez_compressed(
            f, __format__=np.asarray(ckpt.FORMAT_V1),
            __fingerprint__=np.asarray(ckpt._fingerprint(
                [np.asarray(x) for x in leaves])),
            **{f"leaf{i}": np.asarray(x) for i, x in enumerate(leaves)})

    assert ckpt.read_meta(path) == {"format": ckpt.FORMAT_V1}
    out = ckpt.load(path, jax.tree.map(np.zeros_like, state),
                    expect_config="whatever")
    np.testing.assert_array_equal(np.asarray(out["y"]), state["y"])


def test_v1_campaign_stacked_checkpoint_reshard_loads(tmp_path):
    """v1 back-compat on CAMPAIGN-STACKED state: a hand-written v1 file
    of [S]-stacked leaves (no __meta__, so no campaign identity record)
    must restore through BOTH checkpoint.load and the elastic reshard
    path — load_raw reports the v1 format, the meta checks are skipped
    (nothing recorded = nothing to refuse), and a grow keeps the v1
    rows bit-identical."""
    import jax
    import jax.numpy as jnp

    from oversim_tpu.elastic import reshard_stacked

    path = str(tmp_path / "v1camp.npz")
    stacked = {"x": np.arange(6, dtype=np.int64).reshape(2, 3),
               "y": np.full((2, 4), 7.0, np.float64)}
    leaves = jax.tree.leaves(stacked)
    with open(path, "wb") as f:
        np.savez_compressed(
            f, __format__=np.asarray(ckpt.FORMAT_V1),
            __fingerprint__=np.asarray(ckpt._fingerprint(
                [np.asarray(x) for x in leaves])),
            **{f"leaf{i}": np.asarray(x) for i, x in enumerate(leaves)})

    raw, meta = ckpt.load_raw(path)
    assert meta == {"format": ckpt.FORMAT_V1}
    old = jax.tree.unflatten(jax.tree.structure(stacked), raw)
    fresh = {"x": jnp.zeros((5, 3), jnp.int64),
             "y": jnp.ones((5, 4), jnp.float64)}
    grown = reshard_stacked(old, fresh)
    np.testing.assert_array_equal(np.asarray(grown["x"])[:2],
                                  stacked["x"])
    np.testing.assert_array_equal(np.asarray(grown["y"])[2:],
                                  np.ones((3, 4)))
    # ... and the same file still loads same-shape via checkpoint.load
    out = ckpt.load(path, jax.tree.map(np.zeros_like, stacked))
    np.testing.assert_array_equal(np.asarray(out["x"]), stacked["x"])

    # negative pin: a shape-mismatched reshard fails with the
    # fingerprint error, never silently corrupts
    with pytest.raises(ValueError, match="reshard fingerprint mismatch"):
        reshard_stacked(old, {"x": jnp.zeros((5, 9), jnp.int64),
                              "y": jnp.ones((5, 4), jnp.float64)})


def test_save_tolerates_directory_fsync_refusal(tmp_path, monkeypatch):
    """Some network/overlay filesystems refuse fsync on directory fds
    (EINVAL).  The post-rename directory fsync must swallow that —
    rename-level atomicity still holds — and the checkpoint must land
    complete and loadable."""
    import stat

    real_fsync = os.fsync
    synced_dirs = []

    def picky_fsync(fd):
        if stat.S_ISDIR(os.fstat(fd).st_mode):
            synced_dirs.append(fd)
            raise OSError(22, "Invalid argument")
        return real_fsync(fd)

    monkeypatch.setattr(os, "fsync", picky_fsync)
    path = str(tmp_path / "ck.npz")
    state = {"a": np.arange(4, dtype=np.int64)}
    ckpt.save(path, state)                 # must not raise
    assert synced_dirs, "directory fsync was attempted"
    assert not os.path.exists(path + ".tmp")
    out = ckpt.load(path, {"a": np.zeros(4, np.int64)})
    np.testing.assert_array_equal(np.asarray(out["a"]), state["a"])


def test_meta_auto_fills_tick_and_service_extras(tmp_path):
    """tick/t_now are read off states that carry them; caller extras
    (the service loop's window bookkeeping) round-trip via JSON."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    @jax.tree_util.register_dataclass
    @dataclasses.dataclass
    class S:
        tick: jnp.ndarray
        t_now: jnp.ndarray

    path = str(tmp_path / "s.npz")
    svc = {"windows_done": 3, "start_sim_t": 0.0,
           "window_sim_s": 0.5, "chunk": 8, "checkpoint_every": 1}
    ckpt.save(path, S(tick=jnp.int64(42), t_now=jnp.int64(9 * 10**9)),
              meta={"service": svc})
    meta = ckpt.read_meta(path)
    assert meta["tick"] == 42
    assert meta["t_now"] == 9 * 10**9
    assert meta["service"] == json.loads(json.dumps(svc))


def test_structure_mismatch_rejected(tmp_path):
    sim = _make_sim()
    st = sim.init(seed=3)
    path = str(tmp_path / "ck.npz")
    ckpt.save(path, st)
    other = churn_mod.ChurnParams(model="none", target_num=16,
                                  init_interval=0.2)
    sim2 = sim_mod.Simulation(
        ChordLogic(app=KbrTestApp(KbrTestParams(test_interval=5.0))), other)
    with pytest.raises(ValueError, match="structure mismatch"):
        ckpt.load(path, sim2.init(seed=0))
