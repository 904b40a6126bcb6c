"""Test harness config: run everything on a virtual 8-device CPU mesh.

The sandbox the tests run in has no accelerator; multi-chip sharding is
validated on XLA's host-platform virtual devices (same partitioner).
The platform is pinned to the CPU both in the environment (before jax
is imported) and with jax.config.update after it (last update wins), so
the unit tests stay local whatever the ambient selection is.  The one
file that describes a chip to the TPU compiler is
tests/test_chip_compile.py, from inside a fixture.
"""

import os
import sys

# jax's persistent compile cache compresses with the zstandard C
# extension when importable; that extension segfaulted mid-write on
# this box (put_executable_and_time → zstandard.backend_c).  Poisoning
# the import BEFORE jax loads makes the cache fall back to zlib.
sys.modules["zstandard"] = None

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# KEEP IN SYNC: the same -O0 bootstrap lives in tests/conftest.py, __graft_entry__.py and scripts/make_goldens.py
if "xla_backend_optimization_level" not in flags:
    # XLA-CPU at -O0 both COMPILES ~40% faster and RUNS ~30% faster on
    # this suite's tiny-N graphs (measured: chord N=16 compile 86->49s,
    # 64 ticks 78->54s on the 1-core box) — the suite is compile-bound
    # (SURVEY §4 strategy; VERDICT r3 weak #3)
    flags += (" --xla_backend_optimization_level=0"
              " --xla_llvm_disable_expensive_passes=true")
# FMA contraction is fusion-context-dependent: the same f32 mul+add can
# round differently in two differently-structured graphs (observed: the
# sparse tick diverging from the dense oracle by 1 ULP in Vivaldi
# coords, PR 16).  Capping the CPU ISA below FMA makes every
# cross-graph bit-identity pin (dense/sparse, scatter/pallas, telemetry
# on/off, plain/sharded) exact by construction; at -O0 tiny-N shapes
# the vector-width cost is noise.
if "xla_cpu_max_isa" not in flags:
    flags += " --xla_cpu_max_isa=AVX"
os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402  (import after env setup)

# something may have imported jax BEFORE this file runs, so
# jax._src.compilation_cache may already hold a live reference to the
# zstandard C extension (sys.modules poisoning alone is too late) —
# null the module attribute so compress/decompress use zlib
from jax._src import compilation_cache as _cc  # noqa: E402

if getattr(_cc, "zstandard", None) is not None:
    _cc.zstandard = None
if getattr(_cc, "zstd", None) is not None:
    _cc.zstd = None

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
# NO persistent compile cache for the suite: this jax/XLA build's CPU
# executable serialize() segfaults sporadically on the big sim-step
# graphs (put_executable_and_time → executable.serialize(), observed
# twice at different tests; the machine-feature mismatch warnings from
# cpu_aot_loader point at the same AOT path).  In-process jit caching
# still dedupes within the run; only cross-session reuse is lost.
jax.config.update("jax_enable_compilation_cache", False)
