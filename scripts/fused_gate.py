"""Interpret-mode fused-tick gate (run_suite.sh; kernel plane, ISSUE 14).

Two checks on a small chord scenario under LifetimeChurn, both with
``pallas_call(interpret=True)`` on CPU — no hardware needed:

  1. IDENTITY: 64 churned ticks under ``inbox_impl="pallas"`` produce a
     SimState whose every leaf is bit-identical to the lax-scatter
     oracle (``inbox_impl="scatter"``) — same inbox order, same
     delivery, same rng consumption.
  2. OP CENSUS: the compiled fused tick must drop at least 4R+2 scatter
     ops vs the scatter tick (R scatter-min key rounds + R index rounds
     + the outbox fslot scatter all fold into the kernels), with zero
     full-pool sorts and zero custom-calls (interpret mode lowers the
     kernels inline).

Prints one JSON verdict line; exits non-zero on any failure.
"""

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

N_TICKS = 64


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


def _build(inbox_impl):
    from oversim_tpu import churn as churn_mod
    from oversim_tpu.engine import sim as sim_mod
    from oversim_tpu.overlay.chord import ChordLogic

    cp = churn_mod.ChurnParams(model="lifetime", target_num=12,
                               init_interval=0.2, lifetime_mean=8.0)
    ep = sim_mod.EngineParams(window=0.1, inbox_slots=4, pool_factor=4,
                              inbox_impl=inbox_impl)
    return sim_mod.Simulation(ChordLogic(), cp, engine_params=ep)


def main() -> int:
    jax = _setup_jax()
    import numpy as np

    from oversim_tpu import kernels
    from oversim_tpu.analysis import hlo_text

    verdict = {"gate": "fused_tick", "n_ticks": N_TICKS,
               "kernels_available": kernels.available()}
    if not kernels.available():
        # kernel-less install: the gate has nothing to pin (the scenario
        # pin already covers the pallas→scatter fallback) — skip, pass
        verdict["skipped"] = "pallas unavailable"
        print(json.dumps(verdict), flush=True)
        return 0

    failures = []

    # -- 1. identity: 64 churned ticks, every leaf bit-identical -------
    sims = {impl: _build(impl) for impl in ("scatter", "pallas")}
    finals = {}
    for impl, sim in sims.items():
        s = sim.init(seed=3)
        finals[impl] = jax.device_get(sim.run_chunk(s, N_TICKS))
        # each selection's own count of what it swept (P a tick for the
        # kernel, D or P for the scatter rounds) is no part of identity
        finals[impl].counters.pop("inbox_lanes", None)
    la, ta = jax.tree_util.tree_flatten(finals["scatter"])
    lb, tb = jax.tree_util.tree_flatten(finals["pallas"])
    if ta != tb:
        failures.append("state treedef mismatch")
    bad = [i for i, (x, y) in enumerate(zip(la, lb))
           if not np.array_equal(np.asarray(x), np.asarray(y))]
    verdict["identity_ok"] = ta == tb and not bad
    verdict["alive"] = int(np.sum(finals["scatter"].alive))
    if bad:
        paths = jax.tree_util.tree_flatten_with_path(finals["scatter"])[0]
        failures.append("divergent leaves: "
                        + ", ".join(jax.tree_util.keystr(paths[i][0])
                                    for i in bad[:8]))

    # -- 2. op census: the kernels replace the 4R+2 scatter ops (PR 34) -
    census = {}
    for impl, sim in sims.items():
        s = sims[impl].init(seed=3)
        txt = jax.jit(sim.step).lower(s).compile().as_text()
        m = hlo_text.hlo_op_counts(txt, sim.ep.pool_factor * sim.n)
        m["custom_calls"] = hlo_text.custom_call_census(txt)
        census[impl] = m
    r = sims["pallas"].ep.inbox_slots
    need = 4 * r + 2
    drop = (census["scatter"]["scatter_count"]
            - census["pallas"]["scatter_count"])
    verdict["census"] = census
    verdict["scatter_drop"] = drop
    verdict["scatter_drop_required"] = need
    if drop < need:
        failures.append(f"fused tick dropped only {drop} scatters "
                        f"(need >= {need} = 4R+2)")
    if census["pallas"]["full_pool_sort_count"]:
        failures.append("full-pool sort in the fused tick")
    if census["pallas"]["custom_calls"]:
        failures.append("custom-calls in the interpret-mode fused tick: "
                        f"{census['pallas']['custom_calls']}")

    verdict["ok"] = not failures
    if failures:
        verdict["failures"] = failures
        for f in failures:
            print(f"fused_gate: FAIL {f}", file=sys.stderr)
    print(json.dumps(verdict), flush=True)
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
