"""The declarative graph-contract registry.

Every compiled entry point of the simulator — the solo tick, the fused
``run_chunk``, the device-resident ``run_until_device`` while-loop, the
vmapped replica-sharded campaign tick, the telemetry-enabled tick, and
the service window — registers ONE :class:`EntryPoint` here: how to
build it (:class:`EntryContext` → jitted fn + fresh-args factory) and
what its compiled graph is allowed to look like (:class:`GraphContract`)
— op budgets, collective allowlist, host-transfer pin, donation
requirement, dtype allowlist, plus the trace-time limits (recompiles /
implicit host syncs) enforced by trace_pass.py.

``scripts/analyze.py --all`` walks the registry; a new subsystem makes
its graph a checked contract by calling :func:`register_entry` (or
adding to :data:`DEFAULT_ENTRIES`) instead of hand-extending a script.

The budgets consolidate what used to be three ad-hoc
``scripts/hlo_breakdown.py`` modes: ``--budget`` → ``solo_tick``,
``--campaign`` → ``campaign_tick``, ``--telemetry`` → the
``telemetry_tick`` delta contract (hlo_breakdown's modes are now shims
over this registry).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

# result dtypes a compiled entry may contain.  x64 is globally enabled:
# time/keys/accumulators are s64/f64, rng bits u32, masks pred.  Reduced
# precision (bf16/f16/f8*) anywhere in the tick means an accumulator
# silently lost precision — disallowed until a PR introduces it
# deliberately (with its own contract revision).
DEFAULT_DTYPES = frozenset({
    "pred", "token",
    "s8", "s16", "s32", "s64",
    "u8", "u16", "u32", "u64",
    "f32", "f64",
})

# measured at -O0/inbox=8: kademlia 151 / chord 123 scatters per tick
# before PR 34 (mostly small per-node logic scatters; engine share
# 8 + 2*inbox).  PR 34's inbox selection held its rounds twice (over
# the due messages' compacted lanes and, behind a lax.cond, P-wide) and
# PR 36 took four scatters out of the closing phase; since PR 40 the
# compacted branch is ONE sort of the D lanes and two scatters (the
# [N, R] table and ``delivered``), so the engine share is
# 4 + 2 + 2*inbox (tests/test_engine.py pins it on the compiled tick
# through the same ``check_budget``) and Kademlia's bucket update holds
# one scatter where it had three.  200 still catches gross regressions
# while zero-full-pool-sorts stays the sharp pin
DEFAULT_MAX_SCATTERS = 200


@dataclasses.dataclass(frozen=True)
class GraphContract:
    """What one compiled entry point's optimized HLO may contain."""

    max_full_pool_sorts: int = 0
    max_sorts: int | None = None          # total sorts; None = unpinned
    max_scatters: int = DEFAULT_MAX_SCATTERS
    # collective census tokens allowed in the graph ("all-gather",
    # "all-reduce:min", ...).  Enforced only when collectives_enforced —
    # node-sharded single-replica steps legitimately carry collectives
    # whose census is mesh-dependent.
    allowed_collectives: frozenset = frozenset()
    collectives_enforced: bool = True
    max_host_transfers: int = 0
    # donation: the optimized module header must carry input→output
    # buffer aliases (may-/must-alias) — dropped donation round-trips
    # the full state through fresh allocations every dispatch
    require_donation: bool = False
    min_donated_leaves: int = 1
    dtype_allowlist: frozenset = DEFAULT_DTYPES
    # trace-time limits (trace_pass.py): the second same-shape call may
    # not recompile, and no tracer/array may be host-synced
    # (__bool__/__index__/__int__/__float__/__array__/device_get)
    # inside the harnessed calls
    max_recompiles: int = 0
    max_host_syncs: int = 0
    max_device_gets: int = 0
    check_leaks: bool = True
    # compile-seconds budget (hlo_pass times lower+compile per entry):
    # None defers to the analyzer-wide --compile-budget ceiling; a float
    # pins THIS entry tighter.  Wall-clock, so budgets must carry slack
    # for a loaded CI box — the point is catching 2x compile blowups
    # (the unrolled-on_msg class), not 10% noise.
    max_compile_seconds: float | None = None


@dataclasses.dataclass(frozen=True)
class DeltaContract:
    """A contract on the DIFF between two entries' op counts.

    ``telemetry_tick`` pins its cost relative to ``solo_tick``: zero
    full-pool sorts, no new sorts anywhere, a bounded scatter delta (one
    gated ``mode="drop"`` scatter per ring buffer), zero new
    collectives (replicated [W] rings must not create traffic)."""

    base: str                           # name of the baseline entry
    max_full_pool_sorts: int = 0
    max_sort_delta: int = 0
    max_scatter_delta: int = 64
    max_collective_delta: int = 0
    # wide-gather delta (hlo_text.gather_counts: gathers whose result
    # keeps a full-width leading dim — N or P).  None = recorded in the
    # verdict JSON but unenforced; a NEGATIVE bound is a REQUIRED
    # reduction (sparse_tick must actually drop the [N, R, W] payload
    # gather, not just add compaction on top of it).
    max_wide_gather_delta: int | None = None


@dataclasses.dataclass(frozen=True)
class EntryContext:
    """Build-time knobs shared by every entry (mirrors the historical
    hlo_breakdown CLI positionals).  ``fast`` shrinks sizes for the
    tier-1 gate; op counts are size-independent, so the contracts hold
    at any n."""

    n: int = 256
    overlay: str = "kademlia"
    window: float = 0.2
    inbox: int = 8
    pool_factor: int = 4
    replicas: int = 4
    tel_ticks: int = 4
    chunk: int = 4
    fast: bool = False

    @classmethod
    def make(cls, *, fast: bool = False, **kw):
        if fast:
            kw.setdefault("n", 64)
            kw.setdefault("replicas", 2)
        return cls(fast=fast, **kw)


@dataclasses.dataclass
class EntryBuild:
    """What :attr:`EntryPoint.build` returns: a jitted callable plus a
    fresh-argument factory (donated entries consume their state — every
    call needs fresh buffers), and the pool dimension for full-pool-sort
    classification."""

    fn: Callable                        # jit wrapper (.lower works)
    make_args: Callable[[], tuple]      # fresh args per call
    pool_dim: int
    info: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    name: str
    doc: str
    contract: GraphContract
    build: Callable[[EntryContext], EntryBuild]
    delta: DeltaContract | None = None


# ---------------------------------------------------------------------------
# builders (import jax lazily — the registry itself stays import-safe)
# ---------------------------------------------------------------------------

def build_sim(ctx: EntryContext, *, telemetry_ticks: int = 0,
              ext_hold_slot: int = -1, tick_impl: str = "dense",
              active_cap: int = 0):
    """The bench-shaped Simulation every entry compiles (KbrTestApp over
    chord/kademlia, churn off — the same construction the historical
    hlo_breakdown modes used).  ``tick_impl`` is "dense" unless an
    entry asks otherwise: the solo/sharded pins are the dense
    ORACLE's, and ``sparse_tick``/``sparse_chunk`` are the engine's
    default plane for these logics (EngineParams.tick_impl "auto")."""
    from oversim_tpu import churn as churn_mod
    from oversim_tpu import telemetry as telemetry_mod
    from oversim_tpu.apps import kbrtest
    from oversim_tpu.apps.kbrtest import KbrTestApp
    from oversim_tpu.common import lookup as lk_mod
    from oversim_tpu.engine import sim as sim_mod

    app = KbrTestApp(kbrtest.KbrTestParams(test_interval=0.2))
    if ctx.overlay == "chord":
        from oversim_tpu.overlay.chord import ChordLogic
        logic = ChordLogic(app=app, lcfg=lk_mod.LookupConfig(slots=8))
    else:
        from oversim_tpu.overlay.kademlia import KademliaLogic
        logic = KademliaLogic(app=app,
                              lcfg=lk_mod.LookupConfig(slots=8, merge=True))
    cp = churn_mod.ChurnParams(model="none", target_num=ctx.n,
                               init_interval=20.0 / ctx.n,
                               init_deviation=2.0 / ctx.n)
    ep = sim_mod.EngineParams(
        window=ctx.window, inbox_slots=ctx.inbox,
        pool_factor=ctx.pool_factor, ext_hold_slot=ext_hold_slot,
        tick_impl=tick_impl, active_cap=active_cap,
        telemetry=telemetry_mod.TelemetryParams(
            sample_ticks=telemetry_ticks))
    return sim_mod.Simulation(logic, cp, engine_params=ep)


def _build_solo_tick(ctx):
    import jax
    sim = build_sim(ctx)
    fn = jax.jit(sim.step)
    s0 = sim.init(seed=7)
    return EntryBuild(fn=fn, make_args=lambda: (s0,),
                      pool_dim=sim.ep.pool_factor * ctx.n,
                      info={"n": ctx.n, "overlay": ctx.overlay})


def _build_solo_chunk(ctx):
    sim = build_sim(ctx)
    # run_chunk donates s: every call needs freshly initialized buffers.
    # `self` is a static argname — reuse ONE sim instance or the cache
    # keys differ and the recompile pin trips on its own harness.  Use
    # the UNBOUND class-level jit (type(sim).run_chunk) so __call__ and
    # .lower see the same explicit-self signature.
    return EntryBuild(
        fn=type(sim).run_chunk,
        make_args=lambda: (sim, sim.init(seed=7), ctx.chunk),
        pool_dim=sim.ep.pool_factor * ctx.n,
        info={"n": ctx.n, "overlay": ctx.overlay, "n_ticks": ctx.chunk})


def _build_run_until_device(ctx):
    import jax.numpy as jnp
    from oversim_tpu.engine.sim import NS
    sim = build_sim(ctx)
    target = jnp.int64(int(2 * ctx.window * NS))
    return EntryBuild(
        fn=type(sim)._run_until_device,
        make_args=lambda: (sim, sim.init(seed=7), target, ctx.chunk),
        pool_dim=sim.ep.pool_factor * ctx.n,
        info={"n": ctx.n, "overlay": ctx.overlay, "chunk": ctx.chunk})


def _campaign_step(ctx, sim):
    """(jitted sharded _vstep, fresh-stacked-state factory, n_dev)."""
    import jax
    from oversim_tpu.campaign import Campaign, CampaignParams
    from oversim_tpu.parallel import mesh as mesh_mod

    camp = Campaign(sim, CampaignParams(replicas=ctx.replicas, base_seed=7))
    cs0 = camp.init()
    avail = len(jax.devices())
    n_dev = max(d for d in range(1, min(avail, camp.s) + 1)
                if camp.s % d == 0)
    mesh = mesh_mod.make_replica_mesh(n_dev)
    sh = mesh_mod.campaign_state_shardings(cs0, mesh)
    step = jax.jit(camp._vstep, in_shardings=(sh,), out_shardings=sh)
    return step, (lambda: (cs0,)), n_dev


def _build_campaign_tick(ctx):
    sim = build_sim(ctx)
    step, make_args, n_dev = _campaign_step(ctx, sim)
    return EntryBuild(
        fn=step, make_args=make_args,
        pool_dim=sim.ep.pool_factor * ctx.n,
        info={"n": ctx.n, "overlay": ctx.overlay,
              "replicas": ctx.replicas, "devices": n_dev})


def _build_telemetry_tick(ctx):
    import jax
    sim = build_sim(ctx, telemetry_ticks=ctx.tel_ticks)
    fn = jax.jit(sim.step)
    s0 = sim.init(seed=7)
    return EntryBuild(fn=fn, make_args=lambda: (s0,),
                      pool_dim=sim.ep.pool_factor * ctx.n,
                      info={"n": ctx.n, "overlay": ctx.overlay,
                            "sample_ticks": ctx.tel_ticks})


def _build_resharded_resume(ctx):
    """Reshard-on-resume (oversim_tpu/elastic/): a campaign checkpoint
    written at HALF the replica extent is restored into the full-width
    campaign via ``elastic.reshard_load`` (surviving rows bit-identical,
    grown rows re-seeded), and the compiled entry is the replica-sharded
    campaign tick on the RESHARDED state.  Resharding is a host-side
    restore — the compiled graph must be indistinguishable from
    ``campaign_tick``'s: the collective allowlist stays EMPTY."""
    import os
    import tempfile

    from oversim_tpu import checkpoint as ckpt_mod
    from oversim_tpu.campaign import Campaign, CampaignParams
    from oversim_tpu.elastic import reshard_load

    sim = build_sim(ctx)
    small = Campaign(sim, CampaignParams(
        replicas=max(1, ctx.replicas // 2), base_seed=7))
    fd, path = tempfile.mkstemp(suffix=".ckpt.npz")
    os.close(fd)
    try:
        ckpt_mod.save(path, small.init(),
                      meta={"campaign": small.describe()})
        full_sim = build_sim(ctx)
        step, _, n_dev = _campaign_step(ctx, full_sim)
        camp = Campaign(full_sim,
                        CampaignParams(replicas=ctx.replicas, base_seed=7))
        cs, _ = reshard_load(path, camp)
    finally:
        os.unlink(path)
    return EntryBuild(
        fn=step, make_args=lambda: (cs,),
        pool_dim=sim.ep.pool_factor * ctx.n,
        info={"n": ctx.n, "overlay": ctx.overlay,
              "replicas_from": small.s, "replicas_to": camp.s,
              "devices": n_dev})


def _build_sparse_tick(ctx):
    import jax
    # a genuinely sparse lane count (cap < n) so the compiled graph has
    # the [A]-shaped round, not a full-width alias of the dense tick
    cap = max(8, ctx.n // 4)
    sim = build_sim(ctx, tick_impl="sparse", active_cap=cap)
    # donation REQUIRED by the contract: the sparse plane exists for the
    # steady-state loop, where the full-width state must update in place
    fn = jax.jit(sim.step, donate_argnums=(0,))
    return EntryBuild(fn=fn, make_args=lambda: (sim.init(seed=7),),
                      pool_dim=sim.ep.pool_factor * ctx.n,
                      info={"n": ctx.n, "overlay": ctx.overlay,
                            "tick_impl": "sparse", "active_cap": cap})


def _build_sparse_chunk(ctx):
    cap = max(8, ctx.n // 4)
    sim = build_sim(ctx, tick_impl="sparse", active_cap=cap)
    # same static-self discipline as solo_chunk
    return EntryBuild(
        fn=type(sim).run_chunk,
        make_args=lambda: (sim, sim.init(seed=7), ctx.chunk),
        pool_dim=sim.ep.pool_factor * ctx.n,
        info={"n": ctx.n, "overlay": ctx.overlay, "n_ticks": ctx.chunk,
              "tick_impl": "sparse", "active_cap": cap})


def _node_shard_extent(n: int, p: int, avail: int) -> int:
    """Largest node-shard count ≤ avail dividing BOTH n and the pool."""
    return max(d for d in range(1, avail + 1) if n % d == 0 and p % d == 0)


def _build_sharded_tick(ctx):
    """The genuinely node-sharded tick (parallel/shard_tick.py): K-way
    shard_map over the (1, K) 2-D mesh, every cross-shard exchange a
    hand-written min-gather — the compiled step's collective census is
    ``all-reduce:min`` and nothing else, with zero sorts (the sort
    path's all-to-all merge exchange never enters the graph)."""
    import jax
    from oversim_tpu.parallel import mesh as mesh_mod
    from oversim_tpu.parallel.shard_tick import ShardedSim

    sim = build_sim(ctx)
    k = _node_shard_extent(ctx.n, sim.ep.pool_factor * ctx.n,
                           len(jax.devices()))
    mesh = mesh_mod.make_mesh_2d(1, k)
    ssim = ShardedSim(sim, mesh)
    fn = jax.jit(ssim.step, in_shardings=(ssim.shardings,),
                 out_shardings=ssim.shardings, donate_argnums=(0,))
    return EntryBuild(
        fn=fn, make_args=lambda: (ssim.place(sim.init(seed=7)),),
        pool_dim=sim.ep.pool_factor * ctx.n,
        info={"n": ctx.n, "overlay": ctx.overlay, "node_shards": k,
              "mesh": [1, k]})


def _build_sharded_campaign_tick(ctx):
    """S stacked replicas × K node shards on one (R, K) 2-D mesh: the
    campaign axis composed with node sharding.  Same allowlist as
    ``sharded_tick`` — and since every pmin names NODE_AXIS only, the
    replica groups span node subgroups: cross-replica traffic stays
    structurally zero (scripts/shard_gate.py pins the replica_groups)."""
    import jax
    from oversim_tpu.campaign import Campaign, CampaignParams
    from oversim_tpu.parallel import mesh as mesh_mod
    from oversim_tpu.parallel.shard_tick import ShardedCampaign

    sim = build_sim(ctx)
    camp = Campaign(sim, CampaignParams(replicas=ctx.replicas, base_seed=7))
    avail = len(jax.devices())
    r_dev = max(d for d in range(1, min(avail, camp.s) + 1)
                if camp.s % d == 0)
    k = _node_shard_extent(ctx.n, sim.ep.pool_factor * ctx.n,
                           avail // r_dev)
    mesh = mesh_mod.make_mesh_2d(r_dev, k)
    scamp = ShardedCampaign(camp, mesh)
    fn = jax.jit(scamp.vstep, in_shardings=(scamp.shardings,),
                 out_shardings=scamp.shardings, donate_argnums=(0,))
    return EntryBuild(
        fn=fn, make_args=lambda: (scamp.place(camp.init()),),
        pool_dim=sim.ep.pool_factor * ctx.n,
        info={"n": ctx.n, "overlay": ctx.overlay,
              "replicas": ctx.replicas, "node_shards": k,
              "mesh": [r_dev, k]})


def _build_service_window(ctx):
    import jax.numpy as jnp
    from oversim_tpu.engine.sim import NS
    # the serving loop's dispatch unit: run_until_device with the
    # EXT_OUT hold slot armed (gateway responses parked until the
    # window-boundary drain, oversim_tpu/service/loop.py)
    sim = build_sim(ctx, ext_hold_slot=0)
    target = jnp.int64(int(2 * ctx.window * NS))
    return EntryBuild(
        fn=type(sim)._run_until_device,
        make_args=lambda: (sim, sim.init(seed=7), target, ctx.chunk),
        pool_dim=sim.ep.pool_factor * ctx.n,
        info={"n": ctx.n, "overlay": ctx.overlay, "ext_hold_slot": 0})


def _build_daemon_window(ctx):
    import jax.numpy as jnp
    from oversim_tpu.campaign import Campaign, CampaignParams
    from oversim_tpu.engine.sim import NS
    # the daemon tier's dispatch unit: the CAMPAIGN-stacked
    # run_until_device with the EXT_OUT hold armed — S tenants (replica
    # rows, service/tenant.py) served by one compiled program.  Same
    # donated-window contract as service_window: tenancy adds batched
    # pool writes at the boundary, never graph structure.
    sim = build_sim(ctx, ext_hold_slot=0)
    camp = Campaign(sim, CampaignParams(replicas=ctx.replicas,
                                        base_seed=7))
    target = jnp.int64(int(2 * ctx.window * NS))
    return EntryBuild(
        fn=type(camp)._run_until_device,
        make_args=lambda: (camp, camp.init(), target, ctx.chunk),
        pool_dim=sim.ep.pool_factor * ctx.n,
        info={"n": ctx.n, "overlay": ctx.overlay,
              "replicas": ctx.replicas, "ext_hold_slot": 0})


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

_TICK = GraphContract()
_DONATED = GraphContract(require_donation=True)

DEFAULT_ENTRIES = (
    EntryPoint(
        name="solo_tick",
        doc="jit(sim.step): one engine tick, telemetry off",
        contract=_TICK,
        build=_build_solo_tick),
    EntryPoint(
        name="solo_chunk",
        doc="sim.run_chunk: fused n-tick scan, donated state",
        contract=_DONATED,
        build=_build_solo_chunk),
    EntryPoint(
        name="run_until_device",
        doc="sim._run_until_device: while-loop run-to-time, donated",
        contract=_DONATED,
        build=_build_run_until_device),
    EntryPoint(
        name="campaign_tick",
        doc="vmapped replica-sharded campaign tick: ZERO cross-replica "
            "collectives (pure data parallelism)",
        contract=GraphContract(),       # allowed_collectives stays empty
        build=_build_campaign_tick),
    EntryPoint(
        name="telemetry_tick",
        doc="jit(sim.step) with telemetry rings: delta vs solo_tick "
            "bounded (one drop-scatter per ring, no sorts, no "
            "collectives)",
        contract=GraphContract(max_scatters=DEFAULT_MAX_SCATTERS + 64),
        build=_build_telemetry_tick,
        delta=DeltaContract(base="solo_tick")),
    EntryPoint(
        name="service_window",
        doc="service window: run_until_device with EXT_OUT hold armed",
        contract=_DONATED,
        build=_build_service_window),
    EntryPoint(
        name="daemon_window",
        doc="daemon serving window: campaign-stacked run_until_device "
            "with EXT_OUT hold armed — S tenants from one compiled "
            "program, donated, zero cross-replica collectives",
        contract=_DONATED,
        build=_build_daemon_window),
    EntryPoint(
        name="sparse_tick",
        doc="jit(sim.step, donate) on the awake-set plane (tick_impl="
            "\"sparse\", the engine's default for Kademlia and Chord "
            "under KBRTestApp): donation required, zero full-pool "
            "sorts, NO sort more than solo_tick (ONE node-step body: "
            "the round loop's, not a sparse branch beside a dense "
            "fallback), no new collectives, and a NEGATIVE wide-gather "
            "delta vs solo_tick — the [A]-lane rounds must actually "
            "replace the full [N, R, W] payload gather",
        contract=GraphContract(require_donation=True,
                               max_scatters=DEFAULT_MAX_SCATTERS + 128),
        build=_build_sparse_tick,
        # scatter delta bounded, not negative: the A-lane write-backs
        # (logic-state leaves + outbox/event planes) are each one gated
        # drop-scatter; the REQUIRED reduction is the wide-gather one
        # (max_sort_delta stays at its default 0)
        delta=DeltaContract(base="solo_tick", max_scatter_delta=128,
                            max_wide_gather_delta=-1)),
    EntryPoint(
        name="sparse_chunk",
        doc="run_chunk on the awake-set plane: donation must survive "
            "the round loop (the full-width state updates in place "
            "across rounds and chunks)",
        contract=GraphContract(require_donation=True,
                               max_scatters=DEFAULT_MAX_SCATTERS + 128),
        build=_build_sparse_chunk),
    EntryPoint(
        name="sharded_tick",
        doc="node-sharded tick on the (1, K) 2-D mesh (shard_map, "
            "parallel/shard_tick.py): donation required and the "
            "collective allowlist is all-reduce:min ONLY — no "
            "all-to-all, no all-gather of pool payloads, zero sorts "
            "(bit-identity vs the solo oracle is pinned by "
            "tests/test_mesh.py and scripts/shard_gate.py)",
        contract=GraphContract(
            require_donation=True,
            allowed_collectives=frozenset({"all-reduce:min"}),
            max_scatters=DEFAULT_MAX_SCATTERS + 64),
        build=_build_sharded_tick),
    EntryPoint(
        name="sharded_campaign_tick",
        doc="S replicas × K node shards on the (R, K) 2-D mesh: the "
            "same all-reduce:min-only allowlist; every collective "
            "names the node axis only, so replica groups span node "
            "subgroups — zero cross-replica collectives stays pinned "
            "(replica_groups structure checked by shard_gate.py)",
        contract=GraphContract(
            require_donation=True,
            allowed_collectives=frozenset({"all-reduce:min"}),
            max_scatters=DEFAULT_MAX_SCATTERS + 64),
        build=_build_sharded_campaign_tick),
    EntryPoint(
        name="resharded_resume",
        doc="campaign tick on a state reshard-restored from a "
            "half-width checkpoint (oversim_tpu/elastic/): identical "
            "contract to campaign_tick — resharding happens at restore "
            "time, never in the graph",
        contract=GraphContract(),       # allowlist unchanged vs base
        build=_build_resharded_resume),
)

REGISTRY: dict = {e.name: e for e in DEFAULT_ENTRIES}


def register_entry(entry: EntryPoint, *, replace: bool = False) -> None:
    """How a future subsystem joins the gate (see README 'Analysis
    plane').  Entries run in registration order; a DeltaContract's base
    must be registered first."""
    if entry.name in REGISTRY and not replace:
        raise ValueError(f"entry {entry.name!r} already registered")
    if entry.delta is not None and entry.delta.base not in REGISTRY:
        raise ValueError(f"delta base {entry.delta.base!r} not registered")
    REGISTRY[entry.name] = entry


def entries(names=None) -> list:
    """Resolve ``--entries`` selections (None = everything, in order)."""
    if names is None:
        return list(REGISTRY.values())
    missing = [n for n in names if n not in REGISTRY]
    if missing:
        raise KeyError(f"unknown entries: {', '.join(missing)} "
                       f"(known: {', '.join(REGISTRY)})")
    return [REGISTRY[n] for n in names]
