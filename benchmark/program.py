"""The system under test, as the benchmark drives it.

The ONLY file of the benchmark that imports ``oversim_tpu``.  It builds
the deployment through the user's entry points (``IniFile`` ->
``build_simulation`` -> ``sim.init`` -> the jitted loop behind
``run_until_device``) on one chip, and copies the state leaves the
comparison reads into plain numpy under plain names.  Nothing here
decides ``correct`` and nothing here computes a metric.

A configuration may name another file of this kind under ``"program"``
(``run.open_cell``): a deployment across chips brings its own, with its
own proof on the chip, and edits nothing here.

What it reads of the program that is no documented interface is listed
in ``SURFACE`` and looked up by name, so a PR that renames or repacks
one of them fails with that name (``SurfaceError``) and not somewhere in
the comparison: the loop ``Simulation._run_until_device`` (the public
``run_until_device`` takes float seconds; the window needs a target in
whole nanoseconds) with its jit cache's size, and the state leaves
below.  The pool's packed block is read through ``MsgPool``'s own column
views (``src``, ``dst``, ``kind``, ``size_b``, ``key``), never by column
number.
"""

from __future__ import annotations

import numpy as np

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# every state leaf read for the comparison, by the name used below
SURFACE = (
    "t_now", "tick", "stats", "counters", "alive", "node_keys",
    "logic.app.t_test", "logic.app.seq",
    "pool.valid", "pool.blk", "pool.t_deliver",
    "logic.lk.pending_dst", "logic.lk.t_sent", "logic.lk.active",
    "underlay.coords", "underlay.channel",
    "logic.state", "logic.sib", "logic.buckets",
)
# the four KBR counters (scalars of the ``stats`` dict) that ride every
# read-back of the window, so that the lookups ended and failed can be
# counted up to any dispatch boundary (``window.counted_stretch``)
KBR_COUNTERS = ("stats.c:kbr_sent", "stats.c:kbr_delivered",
                "stats.c:kbr_lookup_failed", "stats.c:kbr_wrong_node")
SURFACE += KBR_COUNTERS
POOL_VIEWS = ("src", "dst", "kind", "size_b", "key")


class SurfaceError(AttributeError):
    """The program no longer has something this file reads of it."""


def leaf(s, path: str):
    """``s.<path>`` (an attribute, or a key where the state holds a
    dict), or a ``SurfaceError`` that names the leaf."""
    at = s
    for part in path.split("."):
        try:
            at = at[part] if isinstance(at, dict) else getattr(at, part)
        except (AttributeError, KeyError):
            raise SurfaceError(
                f"benchmark/program.py reads the state leaf {path!r} for "
                f"the comparison, and the program's state has no "
                f"{part!r} there any more: the program has to keep or "
                f"export it (PERF.md, Open questions)") from None
    return at


def pool_columns(pool) -> dict:
    """Where the pool's packed block keeps each field the comparison
    reads, asked of ``MsgPool``'s own column views: a block whose row is
    0, 1, 2, ... read through ``pool.src`` gives the column of ``src``."""
    import dataclasses
    width = leaf(pool, "blk").shape[-1]
    probe = dataclasses.replace(
        pool, blk=np.arange(width, dtype=np.int32)[None, :])
    cols = {}
    for name in POOL_VIEWS:
        try:
            cols[name] = np.asarray(getattr(probe, name))[0]
        except AttributeError:
            raise SurfaceError(
                f"benchmark/program.py reads the pool's column view "
                f"{name!r} (MsgPool.{name}) and the pool has none") from None
    return cols


class Program:
    """One deployment: a configuration under a traffic mix on one chip.

    ``n`` overrides the node count for a rehearsal or a test (the fill
    time stays the configuration's, as ``chip_smoke.py --rehearsal``).
    """

    def __init__(self, config: dict, traffic: dict, chips: int,
                 n: int | None = None, persistent_cache: bool = True):
        from oversim_tpu import hostcache
        # before the engine is imported: the cache sits where
        # $JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache.
        # Every compile is kept (the eager init is ~90 small ones).
        self.cache_dir = hostcache.enable(persistent=persistent_cache,
                                          min_compile_secs=0.0)
        import jax
        self.jax = jax
        self.compiles = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

        from oversim_tpu.config.ini import IniFile
        from oversim_tpu.config.scenario import build_simulation
        from oversim_tpu.engine.sim import EngineParams

        if chips != 1:
            raise ValueError(
                "this program file drives one chip; a deployment across "
                "chips names its own under \"program\" in its "
                "configuration")
        self.chips = chips
        self.chunk = int(config["ticks_per_dispatch"])
        ini = IniFile.loads("\n".join(config["ini"]))
        pairs = dict(traffic["overrides"])
        if n is not None:
            pairs["**.targetOverlayTerminalNum"] = int(n)
            pairs["**.initPhaseCreationInterval"] = (
                float(config["fill_s"]) / int(n))
        section = ini.with_overrides("General", pairs)
        self.sim = build_simulation(ini, section,
                                    EngineParams(**config["engine"]))
        self.n = self.sim.n
        self.fill_s = float(self.sim.cp.init_finished_time)
        try:
            self._loop = type(self.sim)._run_until_device
            self._loop._cache_size
        except AttributeError:
            raise SurfaceError(
                "benchmark/program.py drives Simulation._run_until_device "
                "(the jitted loop behind run_until_device, for a target in "
                "whole ns) and counts its programs by _cache_size(); one "
                "of the two is gone") from None
        self._cols = None

    def _on_event(self, event, secs, **kw):
        if event == COMPILE_EVENT:
            self.compiles.append(float(secs))

    # -- devices ------------------------------------------------------------

    def device_record(self) -> dict:
        devs = self.jax.devices()
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs)}

    def memory_peaks(self) -> list:
        """``peak_bytes_in_use`` of each chip used (None where the
        backend does not report it)."""
        return [(d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in self.jax.devices()[:self.chips]]

    # -- the run ------------------------------------------------------------

    def init(self, seed: int):
        """``sim.init(seed)``."""
        return self.jax.block_until_ready(self.sim.init(int(seed)))

    def run_to(self, s, target_ns: int):
        """ONE program for the whole run: whole dispatches of
        ``ticks_per_dispatch`` ticks until ``t_now >= target_ns``.  The
        target is a traced argument, so set-up's long call and the
        window's one-dispatch calls share the compiled program.  Ends in
        ``block_until_ready``."""
        target = np.int64(int(target_ns))    # no device op of its own
        out = self.sim._run_until_device(s, target, self.chunk)
        return self.jax.block_until_ready(out)

    def tick_programs(self) -> int:
        return int(self._loop._cache_size())

    def state_bytes(self, s) -> int:
        """Bytes of every leaf of the state (global, all shards)."""
        return int(sum(x.nbytes for x in self.jax.tree_util.tree_leaves(s)))

    def check_surface(self, s) -> None:
        """Every leaf and view this file reads, looked up by name."""
        for path in SURFACE:
            leaf(s, path)
        pool_columns(leaf(s, "pool"))

    # -- what the comparison reads -------------------------------------------

    def counters(self, s) -> dict:
        """Clock, application counters and timers and engine counters
        (one read of a few dozen scalars and two vectors)."""
        t_now, tick, st, eng, alive, t_test, seq = self.jax.device_get(
            tuple(leaf(s, k) for k in (
                "t_now", "tick", "stats", "counters", "alive",
                "logic.app.t_test", "logic.app.seq")))
        return {"t_now_ns": int(t_now), "tick": int(tick),
                "stats": {k: np.asarray(v) for k, v in st.items()},
                "engine": {k: int(v) for k, v in eng.items()},
                "alive": int(np.sum(alive)),
                "t_test": np.asarray(t_test), "seq": np.asarray(seq)}

    def payloads(self, s) -> dict:
        """The message pool as the engine holds it (valid mask, deliver
        times, the packed 32-bit block), the lookups' pending RPCs and
        the four KBR counters."""
        if self._cols is None:            # set-up's warming call
            self._cols = pool_columns(leaf(s, "pool"))
        col = self._cols
        # one read for all eleven leaves: the copies overlap
        (valid, blk, t_deliver, rpc_dst, rpc_t_sent, rpc_active, t_now,
         *kbr) = self.jax.device_get(tuple(leaf(s, k) for k in (
             "pool.valid", "pool.blk", "pool.t_deliver",
             "logic.lk.pending_dst", "logic.lk.t_sent",
             "logic.lk.active", "t_now") + KBR_COUNTERS))
        # only the slots that hold a message come to the host's record
        rows = np.nonzero(np.asarray(valid))[0]
        blk = np.asarray(blk)[rows]
        return {
            "valid": np.ones(len(rows), bool),
            "t_deliver": np.asarray(t_deliver)[rows],
            "src": blk[:, col["src"]], "dst": blk[:, col["dst"]],
            "kind": blk[:, col["kind"]],
            "size_b": blk[:, col["size_b"]],
            "key": np.ascontiguousarray(
                blk[:, col["key"]]).view(np.uint32),
            "t_now_ns": int(t_now),
            "stats": {k.partition(".")[2]: int(v)
                      for k, v in zip(KBR_COUNTERS, kbr)},
            # the lookups' pending RPCs: whom each asked, and when
            "rpc_dst": np.asarray(rpc_dst),
            "rpc_t_sent": np.asarray(rpc_t_sent),
            "rpc_active": np.asarray(rpc_active),
        }

    def tables(self, s) -> dict:
        """Node identities, coordinates and routing tables."""
        names = ("node_keys", "alive", "coords", "channel", "state", "sib",
                 "buckets")
        out = dict(zip(names, map(np.asarray, self.jax.device_get(
            tuple(leaf(s, k) for k in (
                "node_keys", "alive", "underlay.coords", "underlay.channel",
                "logic.state", "logic.sib", "logic.buckets"))))))
        out["ready"] = out.pop("state") == 2
        return out

    def wire(self) -> dict:
        """The message kinds the reference has to tell apart, and the
        key width, as the program numbers them."""
        from oversim_tpu.common import wire
        return {"APP_ONEWAY": int(wire.APP_ONEWAY),
                "FINDNODE_CALL": int(wire.FINDNODE_CALL),
                "key_bits": int(self.sim.spec.bits)}
