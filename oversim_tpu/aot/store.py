"""Versioned on-disk store for ``jax.export`` entry-point artifacts.

One artifact per registry entry: a StableHLO blob (``<entry>.bin``)
plus a JSON meta sidecar (``<entry>.json``) carrying the FULL key it
was exported under.  The key is

    (entry name, config sha256 of the EntryContext
     [telemetry.config_hash], jax version, device signature
     [hostcache.device_signature], host CPU hash, format version)

Refusal semantics mirror checkpoint v2: a load whose stored key differs
from the caller's key in ANY field is REFUSED with a reason naming the
differing fields — the caller recompiles fresh and ``save`` overwrites
the stale artifact.  Corrupt meta or a missing blob refuse the same
way.  Nothing in this module ever raises on a bad artifact: stale or
torn state degrades to a recompile, never a crash or a silent stale
execution.

Writes are atomic (tmp + ``os.replace``, meta last) so a kill mid-save
leaves either the previous consistent pair or a blob whose meta still
describes the previous blob — which the size check then refuses.
"""

from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path

# bump when the artifact layout or the export wrapper convention
# changes — old artifacts are then refused and rewritten
FORMAT_VERSION = 1

# the meta fields compared on load, in refusal-message order
KEY_FIELDS = ("entry", "config_hash", "jax_version", "device_signature",
              "host", "format")


def artifact_key(entry_name: str, config) -> dict:
    """The full versioned key for one entry under the CURRENT runtime.
    ``config`` is any JSON-serializable mapping (the warm-up plane
    passes the EntryContext fields)."""
    import jax

    from oversim_tpu import hostcache
    from oversim_tpu.telemetry import config_hash
    host = hashlib.sha1(
        hostcache.host_signature().encode()).hexdigest()[:10]
    return {
        "entry": entry_name,
        "config_hash": config_hash(config),
        "jax_version": str(jax.__version__),
        "device_signature": hostcache.device_signature(),
        "host": host,
        "format": FORMAT_VERSION,
    }


def default_root() -> str:
    """$OVERSIM_AOT_DIR, else ``aot/`` inside the XLA persistent cache
    directory (hostcache.cache_dir: placed by $JAX_COMPILATION_CACHE_DIR,
    else the fixed path in the checkout) — one directory holds both."""
    env = os.environ.get("OVERSIM_AOT_DIR")
    if env:
        return env
    from oversim_tpu import hostcache
    return os.path.join(hostcache.cache_dir(), "aot")


class ArtifactStore:
    """Load/save exported entry artifacts under one root directory."""

    def __init__(self, root=None):
        self.root = Path(root if root is not None else default_root())
        self.root.mkdir(parents=True, exist_ok=True)

    def blob_path(self, entry_name: str) -> Path:
        return self.root / f"{entry_name}.bin"

    def meta_path(self, entry_name: str) -> Path:
        return self.root / f"{entry_name}.json"

    def load(self, entry_name: str, key: dict):
        """``(blob, None)`` on a clean hit; ``(None, None)`` on a plain
        miss (nothing stored); ``(None, reason)`` on a REFUSAL (stale
        key / corrupt meta / torn blob).  Never raises."""
        meta_p = self.meta_path(entry_name)
        if not meta_p.exists():
            return None, None
        try:
            meta = json.loads(meta_p.read_text())
        except (OSError, ValueError) as e:
            return None, f"corrupt meta sidecar ({e})"
        stored = meta.get("key", {})
        diffs = [f for f in KEY_FIELDS if stored.get(f) != key.get(f)]
        if diffs:
            detail = ", ".join(
                f"{f}: stored={stored.get(f)!r} != current={key.get(f)!r}"
                for f in diffs)
            return None, f"stale key ({detail})"
        blob_p = self.blob_path(entry_name)
        try:
            blob = blob_p.read_bytes()
        except OSError as e:
            return None, f"blob unreadable ({e})"
        if len(blob) != meta.get("size"):
            return None, (f"blob size {len(blob)} != recorded "
                          f"{meta.get('size')} (torn write)")
        return blob, None

    def save(self, entry_name: str, key: dict, blob: bytes) -> str:
        """Atomic overwrite: blob first, meta (the commit point) last."""
        blob_p = self.blob_path(entry_name)
        tmp = str(blob_p) + ".tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, blob_p)
        meta_p = self.meta_path(entry_name)
        tmp = str(meta_p) + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"key": dict(key), "size": len(blob)}, f, indent=1)
        os.replace(tmp, meta_p)
        return str(blob_p)

    def entries(self) -> list:
        return sorted(p.stem for p in self.root.glob("*.json"))
