"""The simulation engine: event-horizon tick loop over sharded node state.

This replaces the reference's single-threaded OMNeT++ discrete-event kernel
(one `handleMessage` per event) with a batched synchronous design:

  every tick
    1. advance simulated time to the earliest pending event (message
       deliveries, per-node timers, churn) and open a window of
       ``window_ns`` nanoseconds;
    2. group all messages due in the window by destination (one sort of
       the due messages' compacted lanes, R rounds of scatter-min over
       the pool in a tick that overruns them — zero full-pool sorts;
       engine/pool.py) and
       run the vmapped per-node logic step — each node consumes up to R
       messages plus its due timers and appends to a bounded outbox;
    3. push the outbox through the analytic underlay delay model and write
       it into free message-pool slots (sort-free cumsum allocation);
    4. apply churn create/kill events as alive-mask flips + state resets;
    5. fold the tick's stat events into global accumulators.

Everything is jit-compiled; `run` wraps the tick in `lax.scan`.  The node
axis of all state arrays can be sharded over a jax Mesh — gathers/scatters
across the pool then ride XLA collectives (see parallel/mesh.py).

Causality: a handler runs at the logical time of the event that triggered
it (the message's deliver time), and everything it emits is timestamped
from that moment — so event chains carry exact per-hop latencies even
though unrelated events inside one window commute.  Within-window ordering
is the one semantic relaxation vs the reference's total event order; shrink
``window_ns`` to tighten it.
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial

import jax
import jax.numpy as jnp

from oversim_tpu import churn as churn_mod
from oversim_tpu import stats as stats_mod
from oversim_tpu import telemetry as telemetry_mod
from oversim_tpu.common.malicious import MaliciousParams
from oversim_tpu.core import keys as keys_mod
from oversim_tpu.core import lanes as lanes_mod
from oversim_tpu.core.scopes import scope, scoped
from oversim_tpu.engine import pool as pool_mod
from oversim_tpu.engine.logic import Ctx, Msg
from oversim_tpu.underlay import simple as underlay_mod

I32 = jnp.int32
I64 = jnp.int64
NS = 1_000_000_000
T_INF = pool_mod.T_INF
# gateway.EXT_OUT mirrored here — the engine must not import the gateway
# (layering: gateway builds on engine); consistency pinned by a test
EXT_OUT_KIND = 151


@dataclasses.dataclass(frozen=True)
class EngineParams:
    """Engine-level knobs (sizes are static; times in seconds)."""

    window: float = 0.010          # tick window (s)
    inbox_slots: int = 8           # R — msgs consumed per node per tick
    tick_impl: str = "auto"        # node-step execution: "sparse" (the
                                   # awake-set plane: the awake nodes
                                   # are stepped in rounds of A
                                   # compacted lanes, every one in the
                                   # tick it is due — bit-identical to
                                   # dense at any load) | "dense"
                                   # (vmapped full-N sweep, the ORACLE)
                                   # | "auto" (default: sparse where the
                                   # logic declares ``awake_set_exact``,
                                   # dense for every other logic)
    active_cap: int = 0            # A — lanes a round of the awake-set
                                   # plane steps; 0 = auto (a rule of N
                                   # alone, Simulation.acap: N/32, at
                                   # least 32).  Moves the cost of a
                                   # tick, never its result
    outbox_slots: int = 16         # MOUT — msgs emitted per node per tick
    pool_factor: int = 8           # P = pool_factor * N message slots
    rmax: int = 16                 # node-list payload width
    transition_time: float = 0.0   # default.ini:491
    measurement_time: float = -1.0  # default.ini:492 (-1 = unbounded)
    # byzantine fault injection (common/malicious.py; default.ini:529-536)
    malicious: MaliciousParams = MaliciousParams()
    # device-resident KPI time-series rings (oversim_tpu/telemetry.py;
    # **.telemetry.* ini keys).  sample_ticks=0 (default) disables them:
    # SimState.telemetry stays None and the tick graph is unchanged.
    telemetry: telemetry_mod.TelemetryParams = telemetry_mod.TelemetryParams()
    # service/gateway plane: EXT_OUT messages addressed to this node slot
    # are HELD in the pool (never inbox-selected) until a host drain
    # frees them — required for window-granular response draining, where
    # the device runs many ticks between drains (oversim_tpu/service/).
    # -1 (default) disables the hold: tick graph unchanged.
    ext_hold_slot: int = -1


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SimState:
    t_now: jnp.ndarray        # i64 scalar ns
    tick: jnp.ndarray         # i64 scalar
    rng: jax.Array
    alive: jnp.ndarray        # [N] bool
    node_keys: jnp.ndarray    # [N, KL] u32 — the GlobalNodeList key oracle
    underlay: underlay_mod.UnderlayState
    pool: pool_mod.MsgPool
    churn: churn_mod.ChurnState
    malicious: jnp.ndarray    # [N] bool — attacker flags (GlobalNodeList
                              # malicious-node marks, default.ini:529-536)
    logic: object             # per-node logic state pytree
    stats: dict
    counters: dict            # engine drop/overflow counters
    # telemetry ring buffers (telemetry.TelemetryState) or None when
    # telemetry.sample_ticks == 0 — None is an empty pytree, so the
    # disabled layout is leaf-identical to the pre-telemetry engine
    telemetry: object = None


ENGINE_COUNTERS = ("queue_lost", "bit_error_lost", "dest_unavailable_lost",
                   "partition_lost", "pool_overflow", "outbox_overflow",
                   "inbox_deferred")
# awake-set accounting, carried in SimState.counters ONLY under the
# awake-set plane (the dense SimState layout stays bit-identical to the
# pre-sparse engine), cumulative over the run: awake nodes, nodes with
# inbox traffic, and lanes stepped (rounds x A — what the node step
# paid, where the dense sweep pays ticks x N: the dense step, handed a
# state that carries the counter, adds the alive rows it swept)
SPARSE_COUNTERS = ("awake_nodes", "active_dst", "lanes_stepped")
# inbox-selection accounting, carried where SPARSE_COUNTERS are (so the
# dense layout — the oracle's, the campaign's, shard_tick's — stays what
# it was; a state without them runs the same selection, uncounted),
# cumulative: candidates a round of the selection swept (D in a tick
# whose due messages fit the D compacted lanes, P in a tick that took
# the P-wide rounds; engine/pool.py build_inbox) and the pool
# slots the P-wide rounds would have swept (P every tick)
INBOX_COUNTERS = ("inbox_lanes", "inbox_pool_slots")
# closing-phase accounting, carried where INBOX_COUNTERS are, cumulative:
# outbox slots the receiver's stage of the underlay and the pool's
# allocation ran over (K in a tick whose wanted messages fit the K
# compacted lanes, Q = N x outbox_slots in a tick that took the Q-wide
# form; _phase_alloc_stats) and the slots the Q-wide form runs over (Q
# every tick)
SEND_COUNTERS = ("send_lanes", "send_outbox_slots")
# churn accounting, carried where SPARSE_COUNTERS are by a deployment
# whose churn law can kill a node (every model but NoChurn: a population
# that only fills has nothing to count, and every counter is a handful
# of operations in every tick), cumulative: slots
# a tick created, pre-killed (the leave notice) and finally killed,
# ticks in which churn touched a slot at all, and the rows the churn
# phase rewrote: N every tick, since ``logic.reset``, the fresh keys and
# ``underlay.migrate`` are dense selects over all rows whatever churn
# did (what a churn phase that follows the touched slots would lower)
CHURN_COUNTERS = ("churn_created", "churn_prekilled", "churn_killed",
                  "churn_ticks", "reset_rows")
# what a state of the awake-set plane may carry beside ENGINE_COUNTERS
# (the last group under a churn law only: Simulation.counter_names)
PLANE_COUNTERS = (SPARSE_COUNTERS + INBOX_COUNTERS + SEND_COUNTERS
                  + CHURN_COUNTERS)


def resolve_tick_impl(tick_impl: str, logic) -> str:
    """The plane a Simulation's tick runs: ``"auto"`` gives the
    awake-set plane to a logic that declares ``awake_set_exact`` (a
    node with no inbox message, no due ``next_event`` and no churn is a
    fixed point of its ``step``, pinned against the dense sweep by an
    identity test) and the dense sweep to every other; ``"sparse"``
    asked by name for a logic without the declaration is refused, since
    nothing says its idle nodes may be skipped."""
    exact = bool(getattr(logic, "awake_set_exact", False))
    if tick_impl == "auto":
        return "sparse" if exact else "dense"
    if tick_impl not in ("dense", "sparse"):
        raise ValueError(f"unsupported tick_impl: {tick_impl!r} "
                         "(expected \"auto\", \"dense\" or \"sparse\")")
    if tick_impl == "sparse" and not exact:
        raise ValueError(
            f"tick_impl=\"sparse\" needs a logic that declares "
            f"awake_set_exact; {type(logic).__name__} does not (no "
            "identity test pins its idle nodes as fixed points)")
    return tick_impl


def _dedupe_buffers(state):
    """Make every state leaf own its device buffer.

    ``run_chunk``/``run_until_device`` DONATE the state, so two things
    must never be a leaf: a buffer that an earlier leaf already holds
    (XLA refuses to donate the same buffer twice — a logic/churn init
    that assigns one array object to two fields would poison every
    later chunk), and a buffer that lives on outside the state.  The
    second is the module-level scalar constants every overlay defines
    (``T_INF``, ``NO_NODE``, ``UMAX`` ...): an init that writes
    ``t_tick=T_INF`` or ``rp=NO_NODE`` hands the shared constant to the
    donation, and every later eager use of it in the process reads a
    deleted array.  All such constants are 0-d, so every 0-d leaf is
    copied; larger leaves only when they share a buffer.  One-time cost
    at init."""
    leaves, treedef = jax.tree_util.tree_flatten(state)
    seen, out = set(), []
    for leaf in leaves:
        try:
            ptr = leaf.unsafe_buffer_pointer()
        except (AttributeError, ValueError):
            out.append(leaf)
            continue
        if leaf.ndim == 0 or ptr in seen:
            leaf = jnp.array(leaf, copy=True)
        else:
            seen.add(ptr)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


class Simulation:
    """Host-side driver binding logic + underlay + churn params."""

    def __init__(self, logic, churn_params: churn_mod.ChurnParams,
                 underlay_params=None,
                 engine_params: EngineParams | None = None,
                 underlay_module=None, *, inbox_lanes: int | None = None,
                 send_lanes: int | None = None):
        # the underlay is a strategy module (init/migrate/send_batch/
        # connection_matrix): underlay.simple (SimpleUnderlay, default)
        # or underlay.inet (InetUnderlay/ReaSEUnderlay router topology)
        self.ul = underlay_module or underlay_mod
        self.logic = logic
        self.cp = churn_params
        self.up = (self.ul.UnderlayParams()
                   if underlay_params is None else underlay_params)
        self.ep = engine_params or EngineParams()
        self.n = churn_params.num_slots
        self.spec = logic.key_spec
        # "dense" | "sparse": what ep.tick_impl comes to for this logic
        self.tick_impl = resolve_tick_impl(self.ep.tick_impl, logic)
        # D — lanes the inbox selection compacts a tick's due messages
        # into (engine/pool.py inbox_lanes: a rule of P alone); P = the
        # P-wide rounds only.  Code's to pass (``for_vmap``, a test),
        # never a user's: it moves the cost of a tick, not its result
        p = self.ep.pool_factor * self.n
        if inbox_lanes is None:
            inbox_lanes = pool_mod.inbox_lanes(p)
        self.inbox_lanes = min(inbox_lanes, p)
        # K — lanes the closing phase compacts a tick's wanted outbox
        # slots into (engine/pool.py send_lanes: a rule of Q alone);
        # Q = the Q-wide form only, which is also all an underlay
        # without the two stages (``send_tx`` / ``send_rx``:
        # underlay/inet.py) can take.  Code's to pass, like D
        q = self.n * self.ep.outbox_slots
        if send_lanes is None:
            send_lanes = pool_mod.send_lanes(q)
        if not hasattr(self.ul, "send_rx"):
            send_lanes = q
        self.send_lanes = min(send_lanes, q)

    def for_vmap(self) -> "Simulation":
        """This deployment as a caller that vmaps the step wants it
        (campaign/runner.py): ``tick_impl="auto"`` settled as the dense
        sweep (under vmap the round loop runs every replica for the
        busiest one's rounds), the inbox selection P-wide and the
        closing phase Q-wide (under vmap a ``lax.cond`` runs both
        branches, so the D and the K compacted lanes would come on top
        of the wide forms: with D = P and K = Q the program holds
        neither ``cond``).  Same results either way.
        The GSPMD builders of parallel/mesh.py do not take it: on four
        chips the awake-set plane is the faster one (PERF.md, PR 28)."""
        p = self.ep.pool_factor * self.n
        q = self.ep.outbox_slots * self.n
        dense = self.ep.tick_impl != "auto" or self.tick_impl == "dense"
        if dense and self.inbox_lanes == p and self.send_lanes == q:
            return self
        ep = self.ep if dense else dataclasses.replace(
            self.ep, tick_impl="dense")
        return Simulation(self.logic, self.cp, self.up, ep, self.ul,
                          inbox_lanes=p, send_lanes=q)

    @property
    def counter_names(self) -> tuple:
        """Counter keys carried in SimState.counters for this engine
        config (the awake-set plane rides its accounting, the inbox
        selection's and the closing phase's along, and the churn phase's
        where the churn law can kill a node; the dense layout is
        untouched)."""
        if self.tick_impl != "sparse":
            return ENGINE_COUNTERS
        if self.cp.model == "none":
            return tuple(c for c in ENGINE_COUNTERS + PLANE_COUNTERS
                         if c not in CHURN_COUNTERS)
        return ENGINE_COUNTERS + PLANE_COUNTERS

    @property
    def acap(self) -> int:
        """A — static lane count of one round of the awake-set plane.
        ``active_cap=0`` sizes it from N alone: full-N up to 32 nodes,
        N/32 once n outgrows 32*32.  On the chip a round costs by its
        lanes (43 us a lane at N=1000 and N=4096 alike) and next to
        nothing besides, so A wants to be no larger than a steady
        tick's awake set: under KBRTestApp's upstream interval that is
        2.2 to 2.4% of the nodes at either N (3.3% at most), and up to
        35% while a network fills at 205 joins a second, which then
        takes a dozen rounds a tick (PERF.md, PR 27)."""
        if self.ep.active_cap > 0:
            return min(self.ep.active_cap, self.n)
        return lanes_mod.rule(self.n)

    # -- init ---------------------------------------------------------------

    def init(self, seed: int = 1, ov=None) -> SimState:
        return _dedupe_buffers(
            self.init_from_rng(jax.random.PRNGKey(seed), ov=ov))

    def init_from_rng(self, rng: jax.Array, ov=None) -> SimState:
        """Pure-JAX init from an explicit PRNG key (vmappable — the
        campaign runner vmaps this over per-replica folded keys).  ``ov``
        is an optional {dotted-name: scalar} sweep-override dict (values
        may be traced); ``None`` reproduces ``init(seed)`` bit-exactly.
        NOTE: no ``_dedupe_buffers`` here — under a trace there are no
        device buffers to compare; callers holding concrete outputs
        (``init``, campaign stacked init) apply it host-side."""
        (r_keys, r_ul, r_churn, r_logic, r_run,
         r_mal) = jax.random.split(rng, 6)
        n = self.n
        life_mean = None if ov is None else ov.get("churn.lifetimeMean")
        node_keys = keys_mod.random_keys(r_keys, (n,), self.spec)
        stats = stats_mod.init_stats(self.logic.stat_spec())
        return SimState(
            t_now=jnp.int64(0),
            tick=jnp.int64(0),
            rng=r_run,
            alive=jnp.zeros((n,), bool),
            node_keys=node_keys,
            underlay=self.ul.init(r_ul, n, self.up),
            pool=pool_mod.empty(self.ep.pool_factor * n, self.spec.lanes,
                                self.ep.rmax),
            churn=churn_mod.init(r_churn, self.cp, life_mean=life_mean),
            malicious=(jax.random.uniform(r_mal, (n,))
                       < self.ep.malicious.probability),
            logic=self.logic.init(r_logic, n),
            stats=stats,
            counters={name: jnp.zeros((), I64)
                      for name in self.counter_names},
            telemetry=telemetry_mod.init(
                stats, self.counter_names, self.ep.telemetry,
                app=getattr(self.logic, "app", None)),
        )

    # -- one tick -----------------------------------------------------------
    #
    # The tick is split into PHASE methods (horizon / churn /
    # inbox_select / inbox_gather / node_step / alloc_stats).  ``step``
    # composes them, each under its first-level scope (core/scopes.py:
    # the names a device trace is reduced by, benchmark/phase_reduce.py);
    # under one jit the split is invisible to XLA (same fused graph as
    # the old monolithic step).

    def _phase_horizon(self, s: SimState, *, ov=None):
        """Phase 1/5: advance to the event horizon + per-tick rng split."""
        w = None if ov is None else ov.get("engine.window")
        if w is None:
            window_ns = jnp.int64(int(self.ep.window * NS))
        else:
            # traced sweep value (campaign grid over the tick window)
            window_ns = (jnp.asarray(w) * NS).astype(I64)
        t_next = jnp.minimum(
            pool_mod.next_deliver_time(s.pool),
            jnp.minimum(
                jnp.min(jnp.where(s.alive, self.logic.next_event(s.logic),
                                  T_INF)),
                churn_mod.next_event(s.churn)))
        t_next = jnp.maximum(t_next, s.t_now)
        # with no pending events anywhere t_next is T_INF; keep t_end there
        # too so T_INF-parked timers/churn sentinels never satisfy `< t_end`
        t_end = jnp.where(t_next >= T_INF, t_next, t_next + window_ns)
        rngs = jax.random.split(s.rng, 7)
        return t_next, t_end, rngs

    def _phase_churn(self, s: SimState, t_next, t_end, r_churn, r_keys,
                     r_reset, r_mig, *, ov=None):
        """Phase 2/5: churn events (incl. graceful-leave grace windows)."""
        n, cp, up = self.n, self.cp, self.up
        logic = self.logic
        life_mean = None if ov is None else ov.get("churn.lifetimeMean")
        churn_state, created, killed, _leaving = churn_mod.step(
            s.churn, cp, s.alive, t_next, t_end, r_churn,
            life_mean=life_mean)
        alive = (s.alive | created) & ~killed
        # pre-killed nodes run until their final kill but leave the
        # bootstrap oracle immediately (preKillNode removePeer,
        # SimpleUnderlayConfigurator.cc:350)
        pre_killed = churn_state.t_dead < T_INF
        # created slots get fresh nodeIds (BaseOverlay::join draws a random
        # nodeId, BaseOverlay.cc:597-608) and fresh coordinates — unless
        # rejoin_context keeps the slot's previous identity
        # (GlobalNodeList::getContext/restoreContext, BaseOverlay.cc:
        # 823-831: the rejoining peer reclaims its nodeId + flags)
        if cp.rejoin_context:
            node_keys = s.node_keys
        else:
            node_keys = jnp.where(
                created[:, None],
                keys_mod.random_keys(r_keys, (n,), self.spec),
                s.node_keys)
        with scope("underlay.migrate"):
            ul_state = self.ul.migrate(s.underlay, created, r_mig, up)
        # clear both created and killed slots; created ones schedule a join
        with scope("logic.reset"):
            logic_state = logic.reset(s.logic, created | killed, created,
                                      t_next, r_reset)
        return churn_state, alive, pre_killed, node_keys, ul_state, logic_state

    def _hold_mask(self, s: SimState):
        """[P] hold mask for the service plane's parked EXT_OUT
        responses, or None when ``ext_hold_slot`` is disarmed."""
        if self.ep.ext_hold_slot < 0:
            return None
        return ((s.pool.kind == EXT_OUT_KIND)
                & (s.pool.dst == self.ep.ext_hold_slot))

    def _phase_inbox_select(self, s: SimState, t_end, alive):
        """Phase 3a: pick each destination's R earliest due messages
        (the due messages compacted into D lanes and ranked by one sort
        of those; P-wide scatter-min rounds in a tick with more due
        messages than lanes — zero full-pool sorts and, in the steady
        branch, no 64-bit scatter; see engine/pool.py).  The awake-set plane
        stops here: each of its rounds gathers only its A compacted
        rows' payload.  The tests' sort oracle overrides this one phase
        (tests/oracles.py), so it keeps its name and its triple."""
        return pool_mod.build_inbox(
            s.pool, self.n, self.ep.inbox_slots, t_end, alive,
            hold=self._hold_mask(s), lanes=self.inbox_lanes)

    def _msgs_from_block(self, s: SimState, t_next, inbox, blk,
                         t_deliver=None, stamp=None):
        """[N, R] index table + gathered [N, R, W] payload block → the
        Msg view (shared by the dense sweep's full gather and the
        awake-set plane's per-round gather; the two i64 fields are
        gathered here off the index table unless the caller already
        holds them: the sharded tick (parallel/shard_tick.py) passes
        its owner-gathered [N, R] ``t_deliver``/``stamp``, since the
        local pool tile cannot be indexed by global inbox entries)."""
        safe = jnp.maximum(inbox, 0)
        if t_deliver is None:
            t_deliver = s.pool.t_deliver[safe]
        if stamp is None:
            stamp = s.pool.stamp[safe]
        ncol = len(pool_mod.SCAL_COLS)
        col = lambda name: blk[..., pool_mod._COL[name]]  # noqa: E731
        return Msg(
            valid=inbox >= 0,
            t_deliver=jnp.maximum(t_deliver, t_next),
            src=col("src"), dst=col("dst"),
            kind=col("kind"),
            key=jax.lax.bitcast_convert_type(
                blk[..., ncol:ncol + s.pool.kl], jnp.uint32),
            nonce=col("nonce"), hops=col("hops"),
            a=col("a"), b=col("b"),
            c=col("c"), d=col("d"),
            nodes=blk[..., ncol + s.pool.kl:], size_b=col("size_b"),
            stamp=stamp)

    def _phase_inbox_gather(self, s: SimState, t_next, inbox):
        """Phase 3b: ONE gather of the packed [P, W] block for all the
        32-bit fields of the selected messages (pool.py packed layout,
        PERFORMANCE.md lever #3) into the [N, R] Msg view."""
        blk = s.pool.blk[jnp.maximum(inbox, 0)]       # [N, R, W]
        return self._msgs_from_block(s, t_next, inbox, blk)

    def _phase_inbox(self, s: SimState, t_next, t_end, alive):
        """Phase 3: inbox select + gather composed (two first-level
        scopes: the awake-set plane runs the first alone)."""
        with scope("phase.inbox_select"):
            inbox, delivered, to_dead = self._phase_inbox_select(
                s, t_end, alive)
        with scope("phase.inbox_gather"):
            msgs = self._phase_inbox_gather(s, t_next, inbox)
        return msgs, delivered, to_dead

    @scoped("step.ctx")
    def _make_ctx(self, s: SimState, t_next, t_end, alive, pre_killed,
                  churn_state, node_keys, ul_state, logic_state, *, ov=None):
        """Tick context shared by the dense and awake-set node-step
        phases.

        The Ctx is always FULL-WIDTH — node handlers index the ready/
        bootstrap vectors by true node id, so the awake-set plane can
        broadcast the same ctx over its compacted lanes.  Returns
        ``(ctx, node_part, glob, measuring)``."""
        n, ep, up, cp = self.n, self.ep, self.up, self.cp
        logic = self.logic
        ready = logic.ready_mask(logic_state) & alive & ~pre_killed
        ready_cumsum = jnp.cumsum(ready.astype(I32))
        measure_start = jnp.int64(
            int((cp.init_finished_time + ep.transition_time) * NS))
        # measurement window: [start, start + measurementTime), unbounded
        # when measurement_time < 0 (default.ini:492)
        measuring = t_next >= measure_start
        if ep.measurement_time >= 0:
            measuring &= t_next < measure_start + jnp.int64(
                int(ep.measurement_time * NS))
        node_part, glob = (logic.split(logic_state)
                           if hasattr(logic, "split") else (logic_state, None))
        # partition support: per-type ready cumsums + live conn matrix
        # (GlobalNodeList per-type bootstrap vectors + connectionMatrix)
        if up.num_node_types > 1:
            conn = self.ul.connection_matrix(up, t_next)
            tmask = (ul_state.node_type[None, :]
                     == jnp.arange(up.num_node_types)[:, None])
            ready_cum_t = jnp.cumsum(
                (ready[None, :] & tmask).astype(I32), axis=1)
            part_kw = dict(node_type=ul_state.node_type, conn=conn,
                           ready_cum_t=ready_cum_t)
        else:
            part_kw = {}
        if hasattr(logic, "ring_starter"):
            part_kw["starter"] = logic.ring_starter(logic_state, alive, t_end)
        ctx = Ctx(t_start=t_next, t_end=t_end, keys=node_keys, alive=alive,
                  ready=ready, ready_cumsum=ready_cumsum,
                  n_ready=ready_cumsum[-1], measuring=measuring, glob=glob,
                  leaving=pre_killed & alive,
                  graceful=pre_killed & alive & churn_state.graceful,
                  malicious=s.malicious, ov=ov,
                  **part_kw)
        return ctx, node_part, glob, measuring

    def _node_rngs(self, r_nodes, tick, idx):
        """Per-node rng streams: fold tick, then node index.  The sparse
        path folds the TRUE node index of each compacted lane (same
        dtype as the dense ``jnp.arange``), so the streams are
        bit-identical between tick impls."""
        return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            jax.random.fold_in(r_nodes, tick), idx)

    def _phase_node_step(self, s: SimState, t_next, t_end, alive, pre_killed,
                         churn_state, node_keys, ul_state, logic_state, msgs,
                         r_nodes, *, ov=None):
        """Phase 4/5: tick context + the vmapped per-node logic step."""
        n = self.n
        logic = self.logic
        ctx, node_part, glob, measuring = self._make_ctx(
            s, t_next, t_end, alive, pre_killed, churn_state, node_keys,
            ul_state, logic_state, ov=ov)
        node_rngs = self._node_rngs(r_nodes, s.tick, jnp.arange(n))
        node_idx = jnp.arange(n, dtype=I32)

        node_part, out_fields, out_valid, out_overflow, events = jax.vmap(
            self._node_step, in_axes=(None, 0, 0, 0, 0))(
                ctx, node_part, msgs, node_rngs, node_idx)
        logic_state = (logic.merge(node_part, glob)
                       if hasattr(logic, "merge") else node_part)
        if hasattr(logic, "post_step"):
            logic_state = logic.post_step(ctx, logic_state, events)
        return (logic_state, out_fields, out_valid, out_overflow, events,
                measuring)

    # -- awake-set plane (tick_impl="sparse") -------------------------------

    def _phase_active_compact(self, s: SimState, t_end, alive, pre_killed,
                              logic_state, inbox):
        """Awake-set phase 4a: the awake nodes' indices in ascending
        order (the ``pool.alloc`` cumsum-compaction idiom).

        A node is awake when it has inbox traffic this window
        (``inbox[:, 0] >= 0`` — the selectors fill slot 0 first), a due
        local timer (``logic.next_event < t_end`` — the same oracle the
        event horizon trusts), or churn touched its slot this tick
        (created, killed, or pre-killed).  Every other node is an exact
        fixed point of ``_node_step`` for a logic that declares
        ``awake_set_exact``, pinned bit-for-bit against the dense oracle
        by tests/test_zz_sparse.py and scripts/sparse_gate.py.

        Returns ``(order, rounds, active)``: ``order`` i32, ceil(N/A)
        rounds of A lanes, the awake nodes first and sentinels (n and
        up) after them, ascending and without repeats throughout, so a
        round's write-back may tell XLA so; ``rounds`` i32, how many of
        them hold an awake node; ``active`` the i64 tallies (awake
        nodes, nodes with inbox traffic, lanes the rounds step)."""
        n, cap = self.n, self.acap
        lanes = -(-n // cap) * cap
        has_msg = inbox[:, 0] >= 0
        timer_due = alive & (self.logic.next_event(logic_state) < t_end)
        churned = (alive ^ s.alive) | (pre_killed & alive)
        awake = has_msg | timer_due | churned
        node_idx = jnp.arange(n, dtype=I32)
        sentinels = n + jnp.arange(lanes, dtype=I32)
        aw_i = awake.astype(I32)
        rank = jnp.cumsum(aw_i) - aw_i
        n_awake = jnp.sum(aw_i)
        order = sentinels.at[jnp.where(awake, rank, lanes)].set(
            node_idx, mode="drop")
        rounds = ((n_awake + (cap - 1)) // cap).astype(I32)
        active = (n_awake.astype(I64),
                  jnp.sum(has_msg.astype(I32)).astype(I64),
                  rounds.astype(I64) * cap)
        return order, rounds, active

    def _phase_sparse_step(self, s: SimState, t_next, t_end, alive,
                           pre_killed, churn_state, node_keys, ul_state,
                           logic_state, inbox, order, rounds, r_nodes, *,
                           ov=None):
        """Awake-set phase 4b: the vmapped logic step over the awake
        nodes only, A compacted lanes a round, until every awake node
        has been stepped — once, in this tick, with the tick's one Ctx
        and its own rng stream, so the result is the dense sweep's bit
        for bit whatever the load and whatever A.  An idle tick runs no
        round at all.

        The program holds ONE copy of the node step (the loop body).
        Sentinel lanes (``order >= n``) clamp to node n-1 for the
        compute and drop at every write-back; the outbox/event bases
        are zeros, which is write-equivalent to the dense path's
        idle-lane junk because every downstream consumer (send_batch,
        alloc, stats.record) is mask-gated."""
        n, cap = self.n, self.acap
        logic = self.logic
        ctx, node_part, glob, measuring = self._make_ctx(
            s, t_next, t_end, alive, pre_killed, churn_state, node_keys,
            ul_state, logic_state, ov=ov)

        def step_lanes(part, act):
            with scope("step.gather"):
                act_c = jnp.minimum(act, n - 1)
                inbox_act = jnp.where((act < n)[:, None], inbox[act_c], -1)
                gblk = s.pool.blk[jnp.maximum(inbox_act, 0)]   # [A, R, W]
                msgs = self._msgs_from_block(s, t_next, inbox_act, gblk)
                part_act = jax.tree_util.tree_map(lambda x: x[act_c], part)
                node_rngs = self._node_rngs(r_nodes, s.tick,
                                            act_c.astype(jnp.int_))
            return jax.vmap(self._node_step, in_axes=(None, 0, 0, 0, 0))(
                ctx, part_act, msgs, node_rngs, act_c)

        # full-width zero bases for what a round writes besides the rows
        outs0 = jax.tree_util.tree_map(
            lambda x: jnp.zeros((n,) + x.shape[1:], x.dtype),
            jax.eval_shape(step_lanes, node_part, order[:cap])[1:])

        def one_round(carry):
            r, part, outs = carry
            act = jax.lax.dynamic_slice(order, (r * cap,), (cap,))
            part_act, *outs_act = step_lanes(part, act)
            # a row scatter: ``order`` ascends without repeats, its
            # sentinels too.  On the chip it costs what the inverse form
            # (a [N] lane-of-node map, a gather and a select) does, and
            # laying the leaves side by side for one wide scatter buys
            # 2% at N=4096 (PERF.md, PR 27)
            write = lambda base, upd: base.at[act].set(  # noqa: E731
                upd, mode="drop", indices_are_sorted=True,
                unique_indices=True)
            with scope("step.write_back"):
                return (r + 1,
                        jax.tree_util.tree_map(write, part, part_act),
                        jax.tree_util.tree_map(write, outs,
                                               tuple(outs_act)))

        _, node_part, outs = jax.lax.while_loop(
            lambda carry: carry[0] < rounds, one_round,
            (jnp.zeros((), I32), node_part, outs0))
        out_fields, out_valid, out_overflow, events = outs

        logic_state = (logic.merge(node_part, glob)
                       if hasattr(logic, "merge") else node_part)
        if hasattr(logic, "post_step"):
            logic_state = logic.post_step(ctx, logic_state, events)
        return (logic_state, out_fields, out_valid, out_overflow, events,
                measuring)

    def _phase_alloc_stats(self, s: SimState, t_end, rng, r_send, alive,
                           pre_killed, node_keys, ul_state, churn_state,
                           logic_state, delivered, to_dead, out_fields,
                           out_valid, out_overflow, events, measuring, *,
                           active=None):
        """Phase 5/5: free delivered slots, send the outbox through the
        underlay into free pool slots (sort-free alloc), fold stats.

        What is indexed by a message's RECEIVER (the underlay's
        ``send_rx``: liveness, channel, coordinates, node type) or by
        its POOL SLOT (``pool.alloc``: ``fslot[want_rank]``, the row
        scatter) runs over the tick's WANTED outbox slots, compacted
        ascending into K static lanes (``pool.send_lanes``, a rule of
        Q alone), through one row gather of everything a lane needs: a
        gather costs by its lanes, and a steady tick wants a few of its
        Q = N x M slots sent.  What is indexed by the sender's own row
        stays [N, M] wide (``send_tx``: the queue model and the two
        random draws, which so stay at their slots; the outbox fields'
        packing for the gather): plain passes.  A tick that wants more
        than K messages (a fill, a saturated mix, flooding) takes the
        same two calls over all Q through a ``lax.cond``, for the same
        answer: exact at any load, nothing dropped, deferred or
        reordered, every leaf the same bits.  ``send_lanes >= Q`` is the
        Q-wide form alone (``for_vmap``; an underlay without the two
        stages)."""
        ep, up = self.ep, self.up
        node_idx = jnp.arange(self.n, dtype=I32)
        new_pool = pool_mod.free(s.pool, delivered | to_dead)
        src = jnp.broadcast_to(node_idx[:, None], out_valid.shape)
        q, k = out_valid.size, self.send_lanes
        want = out_valid.reshape(-1)
        flat = {f: v.reshape((q,) + v.shape[2:])
                for f, v in out_fields.items() if f != "t_send"}
        flat["src"] = src.reshape(-1)
        fit = None          # whether the tick fit the lanes; None: no lanes

        if hasattr(self.ul, "send_rx"):
            with scope("underlay.send_tx"):
                tx, ul_state = self.ul.send_tx(
                    ul_state, up, r_send, src, out_fields["dst"],
                    out_fields["size_b"], out_fields["t_send"], out_valid,
                    kind=out_fields["kind"])
            tx = {f: v.reshape((q,) + v.shape[2:]) for f, v in tx.items()}

            def close(lane):
                """Receiver's stage and allocation over the slots
                ``lane`` ([K] i32, Q where a lane holds none), or over
                all Q (None)."""
                with scope("closing.compact"):
                    tx_l, out_l = lanes_mod.take((tx, flat), lane)
                    if lane is not None:
                        tx_l = dict(tx_l, want=tx_l["want"] & (lane < q))
                with scope("underlay.send_rx"):
                    t_del, ok, ul_l, drops = self.ul.send_rx(
                        ul_state, up, tx_l, alive)
                pool_l, overflow = pool_mod.alloc(
                    new_pool, dict(out_l, t_deliver=t_del), ok)
                return pool_l, overflow, ul_l, drops

            if k >= q:
                new_pool, pool_overflow, ul_state, drops = close(None)
            else:
                def close_lanes():
                    with scope("closing.compact"):
                        lane = lanes_mod.compact(want, k)
                    return close(lane)

                with scope("closing.compact"):
                    fit = lanes_mod.fits(want, k)
                new_pool, pool_overflow, ul_state, drops = jax.lax.cond(
                    fit, close_lanes, lambda: close(None))
        else:
            t_del, ok, ul_state, drops = self.ul.send_batch(
                ul_state, up, r_send, src, out_fields["dst"],
                out_fields["size_b"], out_fields["t_send"], out_valid,
                alive, kind=out_fields["kind"])
            new_pool, pool_overflow = pool_mod.alloc(
                new_pool, dict(flat, t_deliver=t_del.reshape(-1)),
                ok.reshape(-1))

        # stats
        new_stats = stats_mod.record(s.stats, events, measuring)
        with scope("closing.counters"):
            counters = dict(s.counters)
            counters["queue_lost"] += drops["queue_lost"]
            counters["bit_error_lost"] += drops["bit_error_lost"]
            counters["partition_lost"] += drops["partition_lost"]
            counters["dest_unavailable_lost"] += (
                drops["dest_unavailable_lost"] + jnp.sum(to_dead))
            counters["pool_overflow"] += pool_overflow
            counters["outbox_overflow"] += jnp.sum(out_overflow)
            # high-water mark, not a sum: peak count of messages backpressured
            # behind full inboxes in any one tick (a per-tick sum would count
            # each waiting message once per tick it waits; a point-in-time
            # gauge is noise at readout — the peak is stable and still proves
            # whether the deferral path ever engaged)
            counters["inbox_deferred"] = jnp.maximum(
                counters["inbox_deferred"],
                (jnp.sum(s.pool.valid & (s.pool.t_deliver < t_end)) -
                 jnp.sum(delivered | to_dead)).astype(jnp.int64))
            if active is not None:
                # awake-set accounting (SPARSE_COUNTERS): the tallies of
                # _phase_active_compact — cumulative like the loss counters,
                # so the telemetry rings carry the series
                for name, tally in zip(SPARSE_COUNTERS, active):
                    counters[name] += tally
            elif "lanes_stepped" in counters:
                # the dense sweep on a state laid out by the awake-set plane
                # (tick_impl="dense" by name on another Simulation's init):
                # every alive row was stepped, so a reader of lanes over
                # rows gets 100% and never 0; the other two tallies stay
                counters["lanes_stepped"] += jnp.sum(alive).astype(I64)
            if "inbox_lanes" in counters:
                # INBOX_COUNTERS: what this tick's selection swept a round
                # over what the P-wide rounds sweep
                counters["inbox_lanes"] += pool_mod.lanes_swept(
                    s.pool, self.n, t_end, alive, self._hold_mask(s),
                    self.inbox_lanes).astype(I64)
                counters["inbox_pool_slots"] += s.pool.capacity
            if "send_lanes" in counters:
                # SEND_COUNTERS: the slots this tick's closing phase ran its
                # receiver's stage and its allocation over, over all Q
                counters["send_lanes"] += (
                    q if fit is None else jnp.where(fit, k, q).astype(I64))
                counters["send_outbox_slots"] += q
            if "reset_rows" in counters:
                # CHURN_COUNTERS, from what the churn phase left: a slot is
                # created or finally killed where ``alive`` flipped (the two
                # exclude each other within a tick, churn.step), pre-killed
                # where its grace window opened (one reduction for the three)
                touched = jnp.sum(jnp.stack([
                    alive & ~s.alive, pre_killed & ~(s.churn.t_dead < T_INF),
                    s.alive & ~alive]).astype(I32), axis=1).astype(I64)
                counters["churn_created"] += touched[0]
                counters["churn_prekilled"] += touched[1]
                counters["churn_killed"] += touched[2]
                counters["churn_ticks"] += (jnp.sum(touched) > 0).astype(I64)
                counters["reset_rows"] += self.n

        # telemetry sample point (telemetry.py): END-of-tick snapshot of
        # the accumulators into the ring buffers, gated on the sampling
        # cadence via an out-of-bounds-dropped scatter index — no rng,
        # no sorts, and every non-telemetry leaf above is untouched
        # (the tests/test_zz_telemetry_identity.py bit-identity pin)
        with scope("telemetry.fold"):
            tel = telemetry_mod.fold(
                s.telemetry, self.ep.telemetry, t_end=t_end,
                tick=s.tick + 1, alive=alive, stats=new_stats,
                counters=counters)

        # advance to the window END: anything generated during this tick
        # with a due time inside the window is delivered next tick with
        # its original timestamp (build_inbox consumes `t_deliver <
        # t_end` regardless of the past), so no event is lost and no
        # latency is distorted — but the engine is guaranteed ≥ one full
        # window of progress per tick.  Returning t_next instead lets
        # sub-window message delays drag the horizon back and collapses
        # the batching (observed: 6-7x more ticks than windows).
        return SimState(t_now=t_end, tick=s.tick + 1, rng=rng, alive=alive,
                        node_keys=node_keys, underlay=ul_state, pool=new_pool,
                        churn=churn_state, malicious=s.malicious,
                        logic=logic_state, stats=new_stats,
                        counters=counters, telemetry=tel)

    def step(self, s: SimState, *, ov=None) -> SimState:
        """One tick: the five phases composed (see the phase methods).

        ``ov`` — optional {dotted-name: scalar} sweep-override dict
        (values may be traced; see oversim_tpu/campaign/).  Recognised
        keys: ``engine.window``, ``churn.lifetimeMean``, plus any
        ``app.*`` key a handler reads via ``Ctx.ov_get``.  ``None``
        (the default everywhere) keeps the trace bit-identical to the
        pre-campaign engine."""
        if self.tick_impl == "sparse":
            return self._step_sparse(s, ov=ov)
        with scope("phase.horizon"):
            t_next, t_end, rngs = self._phase_horizon(s, ov=ov)
            (rng, r_churn, r_keys, r_reset, r_nodes, r_mig, r_send) = rngs
        with scope("phase.churn"):
            (churn_state, alive, pre_killed, node_keys, ul_state,
             logic_state) = self._phase_churn(
                s, t_next, t_end, r_churn, r_keys, r_reset, r_mig, ov=ov)
        msgs, delivered, to_dead = self._phase_inbox(s, t_next, t_end, alive)
        with scope("phase.node_step"):
            (logic_state, out_fields, out_valid, out_overflow, events,
             measuring) = self._phase_node_step(
                s, t_next, t_end, alive, pre_killed, churn_state, node_keys,
                ul_state, logic_state, msgs, r_nodes, ov=ov)
        with scope("phase.closing"):
            return self._phase_alloc_stats(
                s, t_end, rng, r_send, alive, pre_killed, node_keys,
                ul_state, churn_state, logic_state, delivered, to_dead,
                out_fields, out_valid, out_overflow, events, measuring)

    def _step_sparse(self, s: SimState, *, ov=None) -> SimState:
        """One tick of the awake-set plane: horizon/churn/alloc phases
        are shared with the dense oracle; the inbox skips the full-width
        gather, and ``_node_step`` runs over the awake nodes only, in
        rounds of A compacted lanes.  Bit-identical to the dense
        ``step`` at any load and any ``active_cap`` (but for the
        PLANE_COUNTERS it carries): nothing is ever deferred."""
        with scope("phase.horizon"):
            t_next, t_end, rngs = self._phase_horizon(s, ov=ov)
            (rng, r_churn, r_keys, r_reset, r_nodes, r_mig, r_send) = rngs
        with scope("phase.churn"):
            (churn_state, alive, pre_killed, node_keys, ul_state,
             logic_state) = self._phase_churn(
                s, t_next, t_end, r_churn, r_keys, r_reset, r_mig, ov=ov)
        with scope("phase.inbox_select"):
            inbox, delivered, to_dead = self._phase_inbox_select(
                s, t_end, alive)
        with scope("phase.active_compact"):
            order, rounds, active = self._phase_active_compact(
                s, t_end, alive, pre_killed, logic_state, inbox)
        with scope("phase.node_step"):
            (logic_state, out_fields, out_valid, out_overflow, events,
             measuring) = self._phase_sparse_step(
                s, t_next, t_end, alive, pre_killed, churn_state, node_keys,
                ul_state, logic_state, inbox, order, rounds, r_nodes, ov=ov)
        with scope("phase.closing"):
            return self._phase_alloc_stats(
                s, t_end, rng, r_send, alive, pre_killed, node_keys,
                ul_state, churn_state, logic_state, delivered, to_dead,
                out_fields, out_valid, out_overflow, events, measuring,
                active=active)

    def _node_step(self, ctx, state_n, msgs_n, rng_n, node_idx):
        """Single-node step (vmapped): logic consumes inbox + timers."""
        state_n, outbox, events = self.logic.step(
            ctx, state_n, msgs_n, rng_n, node_idx,
            outbox_slots=self.ep.outbox_slots, rmax=self.ep.rmax)
        with scope("outbox.finish"):
            fields, valid, overflow = outbox.finish()
        return state_n, fields, valid, overflow, events

    # -- run ----------------------------------------------------------------

    @partial(jax.jit, static_argnames=("self", "n_ticks"),
             donate_argnums=(1,))
    def run_chunk(self, s: SimState, n_ticks: int) -> SimState:
        """One fused dispatch of ``n_ticks`` ticks.

        The incoming SimState is DONATED: XLA writes the output state
        into the input's buffers instead of round-tripping the whole
        state through fresh HBM allocations every chunk
        (parallel/mesh.py already donated; this is the default
        single-chip path).  Callers must rebind
        (``s = sim.run_chunk(s, k)``) and never touch the old reference
        afterwards.
        """
        def body(carry, _):
            return self.step(carry), None
        s, _ = jax.lax.scan(body, s, None, length=n_ticks)
        return s

    def run_until(self, s: SimState, t_sim: float, chunk: int = 256,
                  check_invariants: bool | None = None) -> SimState:
        """Host loop: run chunks until simulated time passes t_sim seconds.

        One device→host sync (``t_now``) per chunk; use
        ``run_until_device`` for the sync-free single-dispatch loop.
        ``check_invariants`` (or OVERSIM_DEBUG_INVARIANTS=1) runs the
        host-side structural validator between chunks — the reference's
        debug-build assert tier (SURVEY §5; oversim_tpu/invariants.py).
        """
        if check_invariants is None:
            check_invariants = bool(os.environ.get(
                "OVERSIM_DEBUG_INVARIANTS"))
        target = int(t_sim * NS)
        while int(s.t_now) < target:  # analysis: allow(device-sync)
            s = self.run_chunk(s, chunk)
            if check_invariants:
                from oversim_tpu import invariants as inv_mod
                inv_mod.check_state(s)
        return s

    @partial(jax.jit, static_argnames=("self", "chunk"), donate_argnums=(1,))
    def _run_until_device(self, s: SimState, target, chunk: int) -> SimState:
        def cond(carry):
            return carry.t_now < target

        def body(carry):
            def sbody(c, _):
                return self.step(c), None
            c, _ = jax.lax.scan(sbody, carry, None, length=chunk)
            return c

        return jax.lax.while_loop(cond, body, s)

    def run_until_device(self, s: SimState, t_sim: float,
                         chunk: int = 256) -> SimState:
        """Device-resident run loop: the whole run is ONE dispatch.

        Wraps the ``chunk``-tick scan in a ``lax.while_loop`` guarded by
        ``t_now < target`` so the host never reads ``t_now`` back
        between chunks (``run_until`` pays one device→host sync per
        chunk).  Both advance in whole chunks until ``t_now >= target``,
        so results are bit-identical to ``run_until`` at equal ``chunk``.
        The state is donated, like ``run_chunk``.  Keep ``run_until``
        for invariant-checking or per-chunk host work.
        """
        target = jnp.int64(int(t_sim * NS))
        return self._run_until_device(s, target, chunk)

    # host-side end-of-run report — syncs by design
    def summary(self, s: SimState) -> dict:  # analysis: allow(host-float, device-sync)
        out = stats_mod.summarize(s.stats)
        out["_engine"] = {k: int(v) for k, v in s.counters.items()}
        out["_t_sim"] = float(s.t_now) / NS
        out["_ticks"] = int(s.tick)
        out["_alive"] = int(jnp.sum(s.alive))
        return out
