"""Test harness config: run everything on a virtual 8-device CPU mesh.

The sandbox the tests run in has no accelerator; multi-chip sharding is
validated on XLA's host-platform virtual devices (same partitioner).
The platform is pinned to the CPU both in the environment (before jax
is imported) and with jax.config.update after it (last update wins), so
the unit tests stay local whatever the ambient selection is.  The one
file that describes a chip to the TPU compiler is
tests/test_chip_compile.py, from inside a fixture.

The file also decides how xdist deals the suite out: one module is one
unit of work, and the costliest modules go first (see the two hooks at
the end).
"""

import os
import sys

import pytest

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
# cross-graph bit-identity pin (dense/sparse, inbox selection/sort
# oracle, telemetry on/off, plain/sharded) exact by construction; at -O0
# tiny-N shapes the vector-width cost is noise.
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


# ---------------------------------------------------------------------------
# How the suite is dealt to xdist workers.  Every simulation lives in a
# module-scoped fixture (minutes of XLA-CPU compile and fill) and the
# persistent compile cache is off, so a module whose tests land on two
# workers builds its simulation twice.  The suite therefore states its
# own unit of distribution, whatever --dist the command line names.
# ---------------------------------------------------------------------------

# Every module that takes over 100 s in a whole run under six workers,
# in descending order (test-seconds of the junit file of the first whole
# run of PR 43's final tree, 5,598 in all; CHANGES.md has the table,
# and the verify skill the one-liner that makes it).  They are collected
# first so the long fixtures start at second 0 and the tail of the run
# is cheap tests; every other module follows as collected.
COST_ORDER = (
    "test_route_modes_epichord.py", "test_epichord.py",
    "test_route_modes.py", "test_zz_sparse.py", "test_engine.py",
    "test_mesh_dryrun.py", "test_route_modes_broose.py",
    "test_chord_ring.py", "test_pastry_bamboo.py",
    "test_pastry_multihop.py",
    "test_zz_sparse_rounds.py", "test_zz_sparse_churn.py",
    "test_vmap_campaign.py", "test_mesh.py", "test_route_modes_koorde.py",
    "test_zz_service_resume.py", "test_kademlia_depth.py",
    "test_mesh_2d.py",
)


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs():
    """After a module, let go of everything it compiled.

    Every XLA-CPU executable holds its own memory mappings (13,000 for
    one module's simulations), a worker keeps every module's in jax's
    caches, and the sandbox allows a process 65,530 (vm.max_map_count).
    At PR 27 the fullest worker of a whole run peaked at 58,900; with
    PR 28's mesh programs one worker in each of three whole runs crossed
    the limit and died inside a compile (``Segmentation fault`` or
    ``Aborted`` in backend_compile_and_load, in whatever module it had
    reached).  Cleared, a worker is back at 660 mappings after every
    module; no simulation is shared between modules, so what is compiled
    again is the eager init's small operations.
    """
    yield
    import gc
    jax.clear_caches()
    gc.collect()


@pytest.hookimpl(optionalhook=True)   # xdist may not be loaded
def pytest_xdist_make_scheduler(config, log):
    """One module = one work unit, on one worker.

    xdist's own implementation of this hook is ``trylast``, so this one
    is taken under any ``--dist``.  The scheduler hands units out in
    collection order, one to each free worker; its default of sorting
    units by their number of tests would undo COST_ORDER, so it is
    turned off here.
    """
    from xdist.scheduler import LoadFileScheduling

    config.option.loadscopereorder = False
    return LoadFileScheduling(config, log)


@pytest.hookimpl(trylast=True)
def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(COST_ORDER)}
    # stable: a module's tests keep their order, the unlisted modules theirs
    items.sort(key=lambda it: rank.get(it.path.name, len(rank)))
