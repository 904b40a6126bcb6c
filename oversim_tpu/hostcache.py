"""Compile-cache setup shared by every runner script.

Two jobs:

* :func:`cache_dir` — where the persistent compile cache lives.  The
  directory is placed from OUTSIDE: ``$JAX_COMPILATION_CACHE_DIR`` when
  set (and then no code of this repo sets another), else one fixed path
  inside the checkout (``<repo>/.jax_cache``, git-ignored).  The path
  never moves between runs — no /tmp, no pid, no time — so whoever
  keeps the directory keeps the compiles.
* :func:`enable` — the ONE compile-cache boilerplate block.  Before this
  helper existed, six scripts each carried the same zstandard poisoning
  + x64 + ``jax_compilation_cache_dir`` stanza (bench.py,
  campaign_run.py, service_run.py, hlo_breakdown.py, diag_ring64.py,
  dev_dht_*.py); drift between the copies is how the round-4 cache
  poisoning shipped.  ``persistent=False`` is the per-script opt-out —
  this box's XLA-CPU ``executable.serialize()`` segfaults sporadically
  on big sim-step graphs (tests/conftest.py), so the CPU tier disables
  persistence entirely.

:func:`device_signature` keys the AOT export artifacts
(oversim_tpu/aot/) on the accelerator actually visible at warm-up time;
:func:`host_signature` is the raw CPU identity string those keys carry
(XLA-CPU executables embed machine features).  Module import stays pure
stdlib — safe before jax.
"""

from __future__ import annotations

import os
import platform
import re
import sys

_CPUINFO = "/proc/cpuinfo"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def host_signature(cpuinfo_path: str = _CPUINFO) -> str:
    """CPU identity string: machine arch + model name + ISA flags.
    Falls back to ``platform.processor()`` when cpuinfo is unreadable
    (non-Linux, restricted /proc)."""
    sig = platform.machine()
    try:
        with open(cpuinfo_path) as f:
            lines = f.read().splitlines()
        sig += "".join(ln for ln in lines
                       if ln.startswith(("model name", "flags")))[:8192]
    except OSError:
        sig += platform.processor() or ""
    return sig


def cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
    ``<repo>/.jax_cache``.  The one function that decides it."""
    return os.environ.get(CACHE_ENV) or os.path.join(_REPO_ROOT,
                                                     ".jax_cache")


def device_signature() -> str:
    """Identity of the visible accelerator set, for keying exported AOT
    artifacts: ``platform:kind0[+kind1...]:xN``.  Imports jax lazily —
    call only after the backend env (JAX_PLATFORMS/XLA_FLAGS) is set."""
    import jax
    devs = jax.devices()
    if not devs:
        return "none:x0"
    kinds = sorted({str(getattr(d, "device_kind", "?")) for d in devs})
    return f"{devs[0].platform}:{'+'.join(kinds)}:x{len(devs)}"


def enable(*, persistent: bool = True, min_compile_secs: float = 1.0,
           x64: bool = True) -> str | None:
    """Configure jax's compile cache the one blessed way.

    Poisons the zstandard C extension (segfaults on this box), nulls the
    already-bound ``compilation_cache`` module references when jax beat
    us to the import, enables x64, then either points the persistent
    cache at :func:`cache_dir` (``persistent=True``; returns the
    path) or disables persistence entirely (``persistent=False``; the
    CPU-tier opt-out — returns None).  Call AFTER platform env vars are
    final; safe whether or not jax is already imported.

    The cache keys by the program's METADATA too
    (``jax_compilation_cache_include_metadata_in_key``): by default the
    key leaves ``op_name`` and source locations out, so an executable
    out of the cache carries the scopes (core/scopes.py) and the line
    numbers of the tree that FIRST compiled it, and a trace of a tree
    that renamed or added a scope is reduced by another tree's names
    (PERF.md, PR 35 and PR 41).  The price is one compile for each tree
    that moves a line of the tick's code, and for each entry script (the
    call stack is metadata too).  Source paths go into the key relative
    to the checkout (``jax_hlo_source_file_canonicalization_regex``
    strips its root), so a checkout that lives elsewhere finds the same
    entries.
    """
    sys.modules["zstandard"] = None
    import jax
    from jax._src import compilation_cache as _cc
    for attr in ("zstandard", "zstd"):
        if getattr(_cc, attr, None) is not None:
            setattr(_cc, attr, None)
    if x64:
        jax.config.update("jax_enable_x64", True)
    if not persistent:
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    d = cache_dir()
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(_REPO_ROOT + os.sep))
    return d
