"""The kernel plane: fused Pallas TPU kernels for the tick hot path.

The per-tick cost at scale is dominated by message selection and
delivery (PERFORMANCE.md): the default scatter-min inbox issues 2R
separate [P]->[N] scatters plus a [P, W] payload gather, and the outbox
allocator adds a full-pool cumsum + compaction scatter — all independent
XLA ops that round-trip the pool block through HBM.  This package fuses
them:

  inbox.py   one kernel doing the R-round top-R inbox selection AND the
             packed [P, W] payload gather in a single pass over the
             pool block (serial stable insertion into per-destination
             sorted registers — bit-identical to the scatter-min
             oracle's (t_deliver, pool-index) order);
  outbox.py  the free-slot compaction + destination assignment of the
             sort-free allocator as one serial pass (replaces the
             cumsum/fslot-scatter pair).

Selection: ``EngineParams.inbox_impl="pallas"`` / ``**.inboxImpl =
"pallas"`` arms BOTH kernels; ``"scatter"`` (the default) stays the
bit-identity oracle, exactly as ``"sort"`` did for the scatter
migration (tests/test_kernels.py pins the three-way identity).

On hosts without a TPU the kernels run under
``pallas_call(interpret=True)``: the kernel body is discharged into
plain HLO (no custom-call), so tier-1 tests and the analysis plane pin
bit-identical behaviour AND the fused op-count reduction without
hardware.  On TPU the same bodies lower through Mosaic as
``tpu_custom_call`` ops — the ``fused_tick`` graph contract's
custom-call allowlist (oversim_tpu/analysis/contracts.py).
"""

from __future__ import annotations

_AVAILABLE = None


def available() -> bool:
    """True when the Pallas toolchain imports on this install — the
    scenario layer raises ``ScenarioError`` when ``**.inboxImpl =
    "pallas"`` is requested without it."""
    global _AVAILABLE
    if _AVAILABLE is None:
        try:
            from jax.experimental import pallas  # noqa: F401
            from jax.experimental.pallas import tpu  # noqa: F401
            _AVAILABLE = True
        except Exception:  # noqa: BLE001 — any import failure = no plane
            _AVAILABLE = False
    return _AVAILABLE


def interpret_default() -> bool:
    """Interpret mode unless running on real TPU hardware: CPU CI runs
    the kernels through the Pallas interpreter (inline HLO, bit-exact),
    TPUs get the Mosaic-compiled kernels."""
    import jax
    return jax.default_backend() != "tpu"


# submodules import jax.experimental.pallas at module level; guard so
# `import oversim_tpu.kernels` (and the scenario fallback probe) still
# works on a pallas-less install
if available():
    from oversim_tpu.kernels import inbox, outbox  # noqa: E402,F401
