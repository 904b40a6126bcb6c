"""Awake-set tick gate (run_suite.sh; engine/sim.py, ISSUE 16, ISSUE 27).

Two checks on a small chord scenario under LifetimeChurn, CPU-only:

  1. IDENTITY: 64 churned ticks under ``tick_impl="sparse"`` (auto
     active_cap = full-N at this size) produce a SimState whose every
     leaf is bit-identical to the dense oracle — same delivery order,
     same rng consumption, same churn cascade.  The sparse-only
     counters are stripped before the compare (the dense layout never
     carries them).  Since PR 27 the
     plane steps awake nodes past A in further rounds of the same tick,
     so identity holds at any active_cap; tests/test_zz_sparse.py pins
     the several-rounds cases.
  2. GATHER CENSUS: the compiled sparse tick must carry FEWER
     full-width gathers (result leading dim N or P —
     hlo_text.gather_counts) than the dense tick: compaction must
     REPLACE the wide payload gathers with [A]-lane ones, not stack on
     top of them.

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
        flags = (flags + " --xla_backend_optimization_level=0"
                 " --xla_llvm_disable_expensive_passes=true").strip()
    # identity gates need graph-structure-independent floats: cap the
    # ISA below FMA (tests/conftest.py rationale)
    if "xla_cpu_max_isa" not in flags:
        flags += " --xla_cpu_max_isa=AVX"
    os.environ["XLA_FLAGS"] = flags
    sys.modules["zstandard"] = None
    import jax
    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_enable_compilation_cache", False)
    return jax


def _build(tick_impl, n=12, active_cap=0):
    from oversim_tpu import churn as churn_mod
    from oversim_tpu.engine import sim as sim_mod
    from oversim_tpu.overlay.chord import ChordLogic

    cp = churn_mod.ChurnParams(model="lifetime", target_num=n,
                               init_interval=0.2, lifetime_mean=8.0)
    ep = sim_mod.EngineParams(window=0.1, inbox_slots=4, pool_factor=4,
                              tick_impl=tick_impl, active_cap=active_cap)
    return sim_mod.Simulation(ChordLogic(), cp, engine_params=ep)


def _strip_sparse(st):
    import dataclasses

    from oversim_tpu.engine.sim import PLANE_COUNTERS
    return dataclasses.replace(
        st, counters={k: v for k, v in st.counters.items()
                      if k not in PLANE_COUNTERS})


def main() -> int:
    jax = _setup_jax()
    import numpy as np

    from oversim_tpu.analysis import hlo_text

    verdict = {"gate": "sparse_tick", "n_ticks": N_TICKS}
    failures = []

    # -- 1. identity: every leaf bit-identical --------------------------
    finals = {}
    for tick_impl in ("dense", "sparse"):
        sim = _build(tick_impl)
        s = sim.init(seed=3)
        finals[tick_impl] = jax.device_get(sim.run_chunk(s, N_TICKS))
    sparse = _strip_sparse(finals["sparse"])
    la, ta = jax.tree_util.tree_flatten(finals["dense"])
    lb, tb = jax.tree_util.tree_flatten(sparse)
    if ta != tb:
        failures.append("state treedef mismatch")
    bad = [i for i, (x, y) in enumerate(zip(la, lb))
           if not np.array_equal(np.asarray(x), np.asarray(y))]
    verdict["identity_ok"] = ta == tb and not bad
    if bad:
        paths = jax.tree_util.tree_flatten_with_path(finals["dense"])[0]
        failures.append(
            "divergent leaves: "
            + ", ".join(jax.tree_util.keystr(paths[i][0])
                        for i in bad[:8]))
    verdict["alive"] = int(np.sum(finals["dense"].alive))
    verdict["awake_nodes"] = int(finals["sparse"].counters["awake_nodes"])

    # -- 2. gather census: compaction REPLACES the wide gathers --------
    # Measured at n=64 / cap=16 (the analyzer's sparse_tick geometry):
    # the identity runs above use the auto cap (= full-N at n=12),
    # where every [A]-lane gather would itself classify as N-wide.
    census = {}
    for tick_impl in ("dense", "sparse"):
        sim = _build(tick_impl, n=64, active_cap=16)
        s = sim.init(seed=3)
        txt = jax.jit(sim.step).lower(s).compile().as_text()
        census[tick_impl] = hlo_text.gather_counts(
            txt, wide_dims=(sim.n, sim.ep.pool_factor * sim.n))
        census[tick_impl].update(
            hlo_text.hlo_op_counts(txt, sim.ep.pool_factor * sim.n))
    drop = (census["dense"]["wide_gather_count"]
            - census["sparse"]["wide_gather_count"])
    verdict["census"] = census
    verdict["wide_gather_drop"] = drop
    if drop < 1:
        failures.append(f"sparse tick dropped {drop} wide gathers "
                        "(need >= 1: the [N/P]-width payload gathers "
                        "must become [A]-lane ones)")
    if census["sparse"]["full_pool_sort_count"]:
        failures.append("full-pool sort in the sparse tick")
    if census["sparse"]["sort_count"] > census["dense"]["sort_count"]:
        failures.append("sparse tick added sorts vs dense")

    verdict["ok"] = not failures
    if failures:
        verdict["failures"] = failures
        for f in failures:
            print(f"sparse_gate: FAIL {f}", file=sys.stderr)
    print(json.dumps(verdict), flush=True)
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
