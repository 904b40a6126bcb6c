"""Service-plane resume identity under churn + end-to-end ingest.

The PR's hard pin: a serving run interrupted after a checkpoint and
resumed into a FRESH ServiceLoop reaches a final state BIT-IDENTICAL to
the uninterrupted run — for chord AND kademlia under lifetime churn,
both solo and with the campaign-stacked replica state.  (The
cross-process half — a real SIGKILL — is scripts/service_smoke.py;
in-process resume exercises the same checkpoint.load + window-grid
recomputation path at a fraction of the wall cost.)

NOTE this file is intentionally named test_zz_* so it sorts LAST in the
alphabetical tier-1 run: its compiles are heavy and the tier-1 timeout
cuts the suite mid-alphabet — everything here must stay runnable
standalone (scripts/run_suite.sh gives each module its own budget)
without shrinking the files before the cut.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.campaign import Campaign, CampaignParams
from oversim_tpu.common import lookup as lk_mod
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.service import (InProcessIngest, ServiceLoop,
                                 ServiceParams, campaign_summarize_leaves)

WINDOWS = 4
CKPT_AT = 2          # checkpoint cadence: resume picks up from window 2


def make_overlay_sim(overlay, n=12):
    # same shapes as tests/test_vmap_campaign.py so standalone runs of
    # the two files share the persistent compile cache
    app = KbrTestApp(KbrTestParams(test_interval=0.5))
    if overlay == "chord":
        from oversim_tpu.overlay.chord import ChordLogic
        logic = ChordLogic(app=app, lcfg=lk_mod.LookupConfig(slots=4))
    else:
        from oversim_tpu.overlay.kademlia import KademliaLogic
        logic = KademliaLogic(app=app,
                              lcfg=lk_mod.LookupConfig(slots=4, merge=True))
    cp = churn_mod.ChurnParams(model="lifetime", target_num=n,
                               init_interval=0.2, lifetime_mean=8.0)
    ep = sim_mod.EngineParams(window=0.1, inbox_slots=2, pool_factor=4)
    return sim_mod.Simulation(logic, cp, engine_params=ep)


def assert_leaves_identical(a, b, label):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    bad = [jax.tree_util.keystr(path)
           for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                                   lb)
           if not np.array_equal(np.asarray(x), np.asarray(y),
                                 equal_nan=True)]
    assert not bad, f"{label}: leaves diverged: {bad}"


def _serve_interrupted(runner, init, params, cfg, tmp_path, label,
                       **kw):
    """Serve 3 windows (checkpoint lands at 2), abandon the loop, resume
    from the checkpoint, finish to WINDOWS; return the final state."""
    loop = ServiceLoop(runner, init(), params, config=cfg, **kw)
    loop.run(n_windows=CKPT_AT + 1)
    assert loop.last_checkpoint == CKPT_AT, label
    del loop                       # the "kill": resume sees only the file

    resumed_loop = ServiceLoop.resume(runner, init(), params,
                                      config=cfg, **kw)
    assert resumed_loop.windows_done == CKPT_AT, label
    state, done = resumed_loop.run(n_windows=WINDOWS - CKPT_AT)
    assert done == WINDOWS, label
    return state


@pytest.mark.parametrize("overlay", ["chord", "kademlia"])
def test_service_resume_bit_identity_solo(tmp_path, overlay):
    """Kill-after-checkpoint + resume == uninterrupted, under lifetime
    churn (joins, deaths, timer state, RNG keys all in flight)."""
    sim = make_overlay_sim(overlay)
    cfg = {"overlay": overlay, "n": 12, "churn": "lifetime"}
    params = ServiceParams(
        window_sim_s=0.5, chunk=16, checkpoint_every=CKPT_AT,
        checkpoint_path=str(tmp_path / f"{overlay}.npz"))

    ref, done = ServiceLoop(
        sim, sim.init(seed=5),
        ServiceParams(window_sim_s=0.5, chunk=16)).run(n_windows=WINDOWS)
    assert done == WINDOWS

    resumed = _serve_interrupted(sim, lambda: sim.init(seed=5), params,
                                 cfg, tmp_path, overlay)
    assert_leaves_identical(ref, resumed, f"{overlay} service resume")


@pytest.mark.parametrize("overlay", ["chord", "kademlia"])
def test_service_resume_bit_identity_campaign(tmp_path, overlay):
    """Same pin for the campaign-stacked state: the checkpoint snapshots
    every replica of the vmapped [S] state and resume restores them all."""
    sim = make_overlay_sim(overlay)
    camp = Campaign(sim, CampaignParams(replicas=2, base_seed=7))
    cfg = {"overlay": overlay, "n": 12, "replicas": 2}
    params = ServiceParams(
        window_sim_s=0.5, chunk=16, checkpoint_every=CKPT_AT,
        checkpoint_path=str(tmp_path / f"camp_{overlay}.npz"))

    ref, done = ServiceLoop(
        camp, camp.init(), ServiceParams(window_sim_s=0.5, chunk=16),
        summarize=campaign_summarize_leaves).run(n_windows=WINDOWS)
    assert done == WINDOWS

    resumed = _serve_interrupted(camp, camp.init, params, cfg, tmp_path,
                                 f"campaign {overlay}",
                                 summarize=campaign_summarize_leaves)
    assert_leaves_identical(ref, resumed,
                            f"campaign {overlay} service resume")


def test_service_ingest_echo_end_to_end():
    """Gateway request batching through a real sim: requests submitted
    between windows are injected as ONE batched EXT_IN pool write at the
    window boundary, served by the echo app inside the window, and their
    EXT_OUT responses — parked by the engine's ext_hold_slot hold —
    are drained with the correct payloads after the window."""
    from oversim_tpu.apps.realworld import RealworldEchoApp
    from oversim_tpu.overlay.myoverlay import (MyOverlayLogic,
                                               MyOverlayParams)

    logic = MyOverlayLogic(params=MyOverlayParams(),
                           app=RealworldEchoApp(transform=5))
    cp = churn_mod.ChurnParams(model="none", target_num=4,
                               init_interval=0.2)
    ep = sim_mod.EngineParams(window=0.020, ext_hold_slot=0, inbox_slots=2)
    sim = sim_mod.Simulation(logic, cp, engine_params=ep)
    state = sim.run_until(sim.init(seed=9), 10.0)

    ing = InProcessIngest(gw_slot=0)
    loop = ServiceLoop(sim, state, ServiceParams(window_sim_s=1.0,
                                                 chunk=32), ingest=ing)
    sids = [ing.submit(b=i, c=100 + i) for i in range(3)]
    loop.run(n_windows=2)
    late = ing.submit(b=9, c=900)
    loop.run(n_windows=2)

    assert ing.num_batches == 2, "one pool write per non-empty boundary"
    assert ing.num_injected == 4
    assert ing.overflow() == 0
    for i, sid in enumerate(sids):
        assert ing.responses.get(sid) == (i, 100 + i + 5), ing.responses
    assert ing.responses.get(late) == (9, 905), ing.responses
