"""Graph-contract analysis plane (ISSUE 10).

Three passes over every compiled entry point, driven by
``scripts/analyze.py`` (exit non-zero on any breach):

* ``hlo_pass``  — lower/compile each registered entry and diff the
  optimized module against its declarative :class:`GraphContract`
  (sorts / scatters / collectives / host transfers / donation / dtypes).
* ``trace_pass`` — run each entry twice; fail on recompilation across
  same-shape calls, tracer leaks, and implicit host syncs.
* ``ast_pass``  — host-hazard lint over the hot-path modules with
  in-tree ``# analysis: allow(host-numpy)``-style suppressions, plus
  stale-bytecode guards.

Import-safe: nothing here imports jax at module level — the fast test
tier exercises the text/AST layers without a backend.
"""

from oversim_tpu.analysis.contracts import (      # noqa: F401
    DEFAULT_DTYPES,
    DeltaContract,
    EntryBuild,
    EntryContext,
    EntryPoint,
    GraphContract,
    REGISTRY,
    entries,
    register_entry,
)
from oversim_tpu.analysis.findings import (       # noqa: F401
    Finding,
    document,
    errors,
    verdict_summary,
    write_document,
)
from oversim_tpu.analysis.hlo_text import (       # noqa: F401
    check_budget,
    check_telemetry_budget,
    collective_census,
    donated_leaf_count,
    dtype_census,
    hlo_op_counts,
    host_transfer_count,
)
