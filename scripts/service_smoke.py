"""Service-plane smoke: kill-and-resume on a short CPU run.

The end-to-end pin of the serving loop's preemption story, runnable
standalone (no pytest) and from scripts/run_suite.sh:

  1. child process serves 6 windows with a checkpoint every 2 windows
     and SIGKILLs ITSELF mid-flight (right after the windows_done=4
     checkpoint lands) — a real kill -9, not an exception;
  2. the parent validates the kill artifacts: rc=-9, a complete v2
     checkpoint at windows_done=4 (atomic write: no .tmp leftover), a
     parseable incremental artifact JSON with a run manifest;
  3. a checkpoint from a DIFFERENT scenario config is refused on
     resume (checkpoint.load expect_config);
  4. the parent resumes from the checkpoint, serves the remaining
     windows, and compares the final SimState leaf-for-leaf against an
     uninterrupted 6-window reference run — BIT-identical, across a
     process boundary.

Scenario shape: chord KBRTestApp, N=8, no churn (the churny identity
pins live in tests/test_zz_service_resume.py) — small enough to
compile + run twice in a couple of minutes on the CPU backend.
"""

import json
import os
import signal
import subprocess
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

WINDOWS = 6
CKPT_EVERY = 2
KILL_AT_WINDOW = 4          # on_window index: after the wd=4 checkpoint
SEED = 3
CONFIG = {"smoke": "service", "overlay": "chord", "n": 8, "seed": SEED}


def _setup_jax():
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_backend_optimization_level" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_backend_optimization_level=0"
            " --xla_llvm_disable_expensive_passes=true").strip()
    sys.modules["zstandard"] = None
    import jax
    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_enable_compilation_cache", False)
    return jax


def _build_sim():
    from oversim_tpu import churn as churn_mod
    from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
    from oversim_tpu.engine import sim as sim_mod
    from oversim_tpu.overlay.chord import ChordLogic

    logic = ChordLogic(app=KbrTestApp(KbrTestParams(test_interval=5.0)))
    cp = churn_mod.ChurnParams(model="none", target_num=8,
                               init_interval=0.2)
    return sim_mod.Simulation(logic, cp)


def _params(ckpt_path):
    from oversim_tpu.service import ServiceParams
    return ServiceParams(window_sim_s=2.0, chunk=16,
                         checkpoint_every=CKPT_EVERY,
                         checkpoint_path=ckpt_path)


def child(ckpt_path, artifact_path):
    """Serve windows, then kill -9 ourselves mid-drain."""
    _setup_jax()
    from bench import ArtifactWriter
    from oversim_tpu import telemetry as telemetry_mod
    from oversim_tpu.service import ServiceLoop

    sim = _build_sim()
    artifact = ArtifactWriter(artifact_path)
    artifact.set_manifest(telemetry_mod.run_manifest(
        config=CONFIG, artifacts={"artifact": artifact_path,
                                  "checkpoint": ckpt_path}))

    def on_window(window, summary, wall):
        artifact.add({"window": window, "t_sim": summary["_t_sim"]})
        if window == KILL_AT_WINDOW:
            os.kill(os.getpid(), signal.SIGKILL)   # preemption, for real

    loop = ServiceLoop(sim, sim.init(seed=SEED), _params(ckpt_path),
                       config=CONFIG, on_window=on_window)
    loop.run(n_windows=WINDOWS)
    raise SystemExit("unreachable: the child must die at window "
                     f"{KILL_AT_WINDOW}")


def main():
    tmp = tempfile.mkdtemp(prefix="service_smoke_")
    ckpt = os.path.join(tmp, "service.ckpt.npz")
    artifact = os.path.join(tmp, "service.json")

    t0 = time.time()
    print(f"service_smoke: child serving {WINDOWS} windows, "
          f"kill at window {KILL_AT_WINDOW} ...", flush=True)
    r = subprocess.run([sys.executable, __file__, "--child",
                        ckpt, artifact], cwd=str(ROOT))
    assert r.returncode == -signal.SIGKILL, (
        f"child should die by SIGKILL, got rc={r.returncode}")

    # kill-safe artifacts: complete v2 checkpoint, no torn tmp file,
    # parseable incremental artifact with the run manifest
    _setup_jax()
    from oversim_tpu import checkpoint as ckpt_mod
    from oversim_tpu.service import ServiceLoop
    assert not os.path.exists(ckpt + ".tmp"), "torn checkpoint tmp left"
    meta = ckpt_mod.read_meta(ckpt)
    assert meta["format"] == ckpt_mod.FORMAT, meta
    assert meta["service"]["windows_done"] == KILL_AT_WINDOW, meta
    with open(artifact) as f:
        doc = json.load(f)
    assert doc["complete"] is False
    assert doc["manifest"]["config_hash"]
    assert [r["window"] for r in doc["records"]] == list(
        range(KILL_AT_WINDOW + 1))
    print(f"service_smoke: kill artifacts OK "
          f"(ckpt at windows_done={KILL_AT_WINDOW}, "
          f"{len(doc['records'])} windows in artifact)", flush=True)

    sim = _build_sim()
    params = _params(ckpt)

    # a checkpoint from a different scenario must be refused
    try:
        ServiceLoop.resume(sim, sim.init(seed=SEED), params,
                           config={**CONFIG, "n": 9999})
        raise AssertionError("resume accepted a foreign checkpoint")
    except ValueError as e:
        assert "scenario mismatch" in str(e), e
    print("service_smoke: foreign-config checkpoint refused OK",
          flush=True)

    # resume → finish → bit-identical to the uninterrupted run
    import jax
    import numpy as np
    loop = ServiceLoop.resume(sim, sim.init(seed=SEED), params,
                              config=CONFIG)
    assert loop.windows_done == KILL_AT_WINDOW
    resumed, done = loop.run(n_windows=WINDOWS - loop.windows_done)
    assert done == WINDOWS

    ref_loop = ServiceLoop(sim, sim.init(seed=SEED), _params(None),
                           config=CONFIG)
    reference, _ = ref_loop.run(n_windows=WINDOWS)

    a = jax.tree.leaves(jax.device_get(reference))
    b = jax.tree.leaves(jax.device_get(resumed))
    bad = [i for i, (x, y) in enumerate(zip(a, b))
           if not np.array_equal(x, y)]
    assert len(a) == len(b) and not bad, (
        f"resumed state diverged from uninterrupted run: leaves {bad}")
    print(f"service_smoke: PASS — kill-and-resume bit-identical "
          f"({len(a)} leaves, {time.time() - t0:.0f}s)", flush=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child(sys.argv[2], sys.argv[3])
    sys.exit(main())
