"""The genuinely node-sharded tick: shard_map over the (replica, node) mesh.

`parallel/mesh.py` shards state PLACEMENT and lets GSPMD partition the
solo tick; this module is the explicit plane: the tick body runs under
``shard_map`` with every cross-shard exchange written out by hand as a
minimum-reduction, so the collective census of the compiled step is
``all-reduce:min`` and NOTHING else — no all-to-all, no all-gather of
pool payloads, zero cross-replica collectives (contract entries
``sharded_tick`` / ``sharded_campaign_tick`` in analysis/contracts.py).

The one collective primitive — the min-gather
--------------------------------------------
Every exchange here is "each shard owns a disjoint slice; everyone needs
the union".  That is an all-gather, but an all-gather is expressible as
an all-reduce with the MIN combiner over a buffer where each shard
writes its slice and leaves the identity (dtype max) elsewhere:

    min(x, MAX, MAX, ...) == x   for every bit pattern
    (and when x == MAX the result is MAX — still bit-identical).

Bools ride as i32, floats as bitcast unsigned ints (ordering among real
values is irrelevant — only owner-vs-identity matters), ints as
themselves.  This is EXACT, not approximate, so the sharded tick is
bit-identical to the solo oracle while lowering to a single collective
kind.  Per-destination inbox minima and scalar horizon minima are
additionally TRUE mins, where `lax.pmin` is the natural op anyway.

What runs sharded vs replicated (the bit-identity split)
--------------------------------------------------------
Sharded (the dominant bytes and FLOPs):
  * the [P]/[P, W] message pool — inbox scatter-min select, payload
    gather, free/alloc writes all touch only the local tile;
  * the per-node logic rows ([N, F] leaves) — the vmapped `_node_step`
    runs over the local N/K rows only, with rng streams folded on the
    TRUE global node index (bit-identical to the dense sweep).

Replicated (full-width rng draws and cross-indexed small vectors):
  * churn step, `logic.reset`, `underlay.send_batch`, stats/telemetry
    fold — each draws full-width [N]/[N, M] rng planes; re-running them
    identically on every shard is what keeps the trace bit-identical
    to the solo tick (sharding the draw would change the stream);
  * `alive`/`node_keys`/`malicious` [N] — cross-indexed by every
    handler through the full-width Ctx (`ctx.keys[slot]`).

The awake-set plane (tick_impl="sparse") compacts across the whole
node axis and is NOT supported here: `ShardedSim` refuses a Simulation
whose tick is that plane, by name or by the engine's default ("auto")
for its logic; the caller builds it with `tick_impl="dense"` (the
sharded state's layout is the dense one).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from oversim_tpu import stats as stats_mod
from oversim_tpu import telemetry as telemetry_mod
from oversim_tpu.engine import pool as pool_mod
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.parallel import mesh as mesh_mod

try:  # jax >= 0.6: public API, replication checked via varying-manual-axes
    from jax import shard_map as _shard_map_impl
    _SMAP_KW = {"check_vma": False}
except ImportError:  # jax 0.4.x
    from jax.experimental.shard_map import shard_map as _shard_map_impl
    _SMAP_KW = {"check_rep": False}

I32 = jnp.int32
I64 = jnp.int64
T_INF = pool_mod.T_INF


def _smap(f, mesh, in_specs, out_specs):
    return _shard_map_impl(f, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, **_SMAP_KW)


def _carrier(x):
    """(integer carrier, restore fn) for the min-gather: a dtype whose
    ``iinfo.max`` is a min-identity for every payload bit pattern."""
    dt = x.dtype
    if dt == jnp.bool_:
        return x.astype(I32), lambda y: y != 0
    if jnp.issubdtype(dt, jnp.floating):
        u = jnp.dtype(f"uint{dt.itemsize * 8}")
        return (jax.lax.bitcast_convert_type(x, u),
                lambda y: jax.lax.bitcast_convert_type(y, dt))
    return x, lambda y: y


class ShardedSim:
    """One Simulation's tick, hand-sharded K ways along the node axis.

    ``mesh`` must carry ``mesh_mod.NODE_AXIS``; a REPLICA_AXIS may be
    present (and is simply not named by any collective — replica groups
    span node subgroups only, so cross-replica traffic is structurally
    zero).  ``step`` is the global entry; `_local_step` is the
    shard_map body (also vmapped by :class:`ShardedCampaign`).
    """

    def __init__(self, sim, mesh):
        if mesh_mod.NODE_AXIS not in mesh.axis_names:
            raise ValueError(f"mesh {mesh.axis_names} has no "
                             f"{mesh_mod.NODE_AXIS!r} axis")
        if sim.tick_impl != "dense":
            raise ValueError(
                "sharded tick requires EngineParams(tick_impl='dense'), "
                f"asked by name (this Simulation's is {sim.ep.tick_impl!r}"
                f", which runs {sim.tick_impl!r} for "
                f"{type(sim.logic).__name__}): the awake-set plane "
                "compacts across the whole node axis")
        self.sim = sim
        self.mesh = mesh
        self.axis = mesh_mod.NODE_AXIS
        self.k = int(mesh.shape[self.axis])
        n = sim.n
        p = sim.ep.pool_factor * n
        if n % self.k or p % self.k:
            raise ValueError(f"n={n} / pool={p} not divisible by node "
                             f"shards k={self.k}")
        self.nl = n // self.k
        self.pl = p // self.k
        example = jax.eval_shape(sim.init_from_rng, jax.random.PRNGKey(0))
        self.pspecs = mesh_mod.state_pspecs_2d(example)
        self.shardings = jax.tree.map(
            lambda _, sp: jax.sharding.NamedSharding(mesh, sp),
            example, self.pspecs)
        logic_leaves, self._logic_def = jax.tree.flatten(example.logic)
        self._logic_node = [len(l.shape) >= 1 and l.shape[0] == n
                            for l in logic_leaves]

    # -- collective primitives (everything lowers to all-reduce:min) -----

    def _gmin(self, x, ax):
        """Min-gather: per-shard contiguous tiles [T, ...] -> the full
        [K*T, ...] array on every shard, via ONE all-reduce:min."""
        car, back = _carrier(x)
        buf = jnp.full((self.k,) + car.shape, jnp.iinfo(car.dtype).max,
                       car.dtype).at[ax].set(car)
        g = jax.lax.pmin(buf, self.axis)
        return back(g.reshape((self.k * x.shape[0],) + x.shape[1:]))

    def _pervec(self, v, ax):
        """[K] vector of one per-shard scalar (int sums ride this: local
        partial -> [K] min-gather -> local sum, exact for ints)."""
        buf = jnp.full((self.k,), jnp.iinfo(v.dtype).max,
                       v.dtype).at[ax].set(v)
        return jax.lax.pmin(buf, self.axis)

    def _owned(self, vals_l, idx, base_p):
        """Gather rows of a pool-sharded array by GLOBAL index: the
        owning shard contributes the row, everyone else the identity."""
        loc = idx - base_p
        mine = (loc >= 0) & (loc < self.pl)
        rows = vals_l[jnp.clip(loc, 0, self.pl - 1)]
        car, back = _carrier(rows)
        m = mine.reshape(mine.shape + (1,) * (car.ndim - mine.ndim))
        contrib = jnp.where(m, car, jnp.iinfo(car.dtype).max)
        return back(jax.lax.pmin(contrib, self.axis))

    def _gather_logic(self, logic_l, ax):
        """Local logic rows -> the full-width logic state (node leaves
        min-gathered; glob leaves are replicated and pass through)."""
        leaves = self._logic_def.flatten_up_to(logic_l)
        out = [self._gmin(x, ax) if is_node else x
               for x, is_node in zip(leaves, self._logic_node)]
        return jax.tree.unflatten(self._logic_def, out)

    def _slice_logic(self, logic_full, rows_l):
        leaves = self._logic_def.flatten_up_to(logic_full)
        out = [rows_l(x) if is_node else x
               for x, is_node in zip(leaves, self._logic_node)]
        return jax.tree.unflatten(self._logic_def, out)

    # -- the sharded tick body (runs under shard_map) --------------------

    def _local_step(self, s):
        sim = self.sim
        n, k, nl, pl = sim.n, self.k, self.nl, self.pl
        p = pl * k
        ax = jax.lax.axis_index(self.axis).astype(I32)
        base_n = ax * nl
        base_p = ax * pl

        def rows_l(x):  # full-width -> my contiguous node-tile rows
            return jax.lax.dynamic_slice_in_dim(x, base_n, nl, axis=0)

        def csum(v):  # global int sum: [K] min-gather of partials
            return jnp.sum(self._pervec(v, ax))

        # ---- phase 1: horizon.  The pool term is the only cross-shard
        # min; logic/churn next-events run replicated on the gathered
        # full logic state (also needed by the replicated reset below).
        logic_full = self._gather_logic(s.logic, ax)
        pool_next = jax.lax.pmin(
            jnp.min(jnp.where(s.pool.valid, s.pool.t_deliver, T_INF)),
            self.axis)
        window_ns = jnp.int64(int(sim.ep.window * sim_mod.NS))
        t_next = jnp.minimum(
            pool_next,
            jnp.minimum(
                jnp.min(jnp.where(s.alive, sim.logic.next_event(logic_full),
                                  T_INF)),
                sim_mod.churn_mod.next_event(s.churn)))
        t_next = jnp.maximum(t_next, s.t_now)
        t_end = jnp.where(t_next >= T_INF, t_next, t_next + window_ns)
        rngs = jax.random.split(s.rng, 7)
        (rng, r_churn, r_keys, r_reset, r_nodes, r_mig, r_send) = rngs

        # ---- phase 2: churn — REPLICATED (full-width rng draws; see
        # module docstring), reusing the solo phase verbatim on a state
        # view whose logic is the gathered full-width state.
        (churn_state, alive, pre_killed, node_keys, ul_state,
         logic_res) = sim._phase_churn(
            dataclasses.replace(s, logic=logic_full), t_next, t_end,
            r_churn, r_keys, r_reset, r_mig)

        # ---- phase 3: inbox — local select over the pool tile + the
        # cross-shard all-reduce:min merge (engine/pool.py build_inbox).
        hold = sim._hold_mask(s)  # local: pool columns only
        inbox, delivered, to_dead = pool_mod.build_inbox(
            s.pool, n, sim.ep.inbox_slots, t_end, alive, hold,
            axis_name=self.axis, base=base_p, p_total=p)

        # payload gather: owner-contributed rows of the packed block +
        # the two i64 fields (empty slots read global row 0 — owned by
        # shard 0, matching the solo safe-index gather).
        safe = jnp.maximum(inbox, 0)
        gblk = self._owned(s.pool.blk, safe, base_p)
        g_tdel = self._owned(s.pool.t_deliver, safe, base_p)
        g_stamp = self._owned(s.pool.stamp, safe, base_p)
        msgs = sim._msgs_from_block(s, t_next, inbox, gblk,
                                    t_deliver=g_tdel, stamp=g_stamp)
        msgs_l = jax.tree.map(rows_l, msgs)

        # ---- phase 4: node step over MY rows only (rng folded on the
        # TRUE global node index -> bit-identical streams), then
        # min-gather the per-node outputs back to full width for the
        # replicated merge/post_step/send path.
        ctx, node_part_full, glob, measuring = sim._make_ctx(
            s, t_next, t_end, alive, pre_killed, churn_state, node_keys,
            ul_state, logic_res)
        part_l = jax.tree.map(rows_l, node_part_full)
        idx64 = base_n.astype(I64) + jnp.arange(nl, dtype=I64)
        node_rngs = sim._node_rngs(r_nodes, s.tick, idx64)
        node_idx = base_n + jnp.arange(nl, dtype=I32)
        part_l, out_f_l, out_v_l, out_o_l, ev_l = jax.vmap(
            sim._node_step, in_axes=(None, 0, 0, 0, 0))(
                ctx, part_l, msgs_l, node_rngs, node_idx)
        gm = lambda t: jax.tree.map(lambda x: self._gmin(x, ax), t)  # noqa: E731
        node_part = gm(part_l)
        out_fields = gm(out_f_l)
        out_valid = self._gmin(out_v_l, ax)
        out_overflow = self._gmin(out_o_l, ax)
        events = gm(ev_l)
        logic_state = (sim.logic.merge(node_part, glob)
                       if hasattr(sim.logic, "merge") else node_part)
        if hasattr(sim.logic, "post_step"):
            logic_state = sim.logic.post_step(ctx, logic_state, events)

        # ---- phase 5: free + underlay send (replicated) + SHARDED
        # sort-free alloc: the free-slot ranking becomes a [K] per-shard
        # free-count vector (exclusive prefix -> global ranks) and the
        # compacted fslot table one contribution-scatter + pmin; each
        # shard then writes only destinations inside its tile.
        new_pool = pool_mod.free(s.pool, delivered | to_dead)
        node_idx_full = jnp.arange(n, dtype=I32)
        t_del, ok, ul_state, drops = sim.ul.send_batch(
            ul_state, sim.up, r_send,
            jnp.broadcast_to(node_idx_full[:, None], out_fields["dst"].shape),
            out_fields["dst"], out_fields["size_b"], out_fields["t_send"],
            out_valid, alive, kind=out_fields["kind"])
        flat = {k2: v.reshape((-1,) + v.shape[2:])
                for k2, v in out_fields.items() if k2 != "t_send"}
        flat["t_deliver"] = t_del.reshape(-1)
        flat["src"] = jnp.broadcast_to(node_idx_full[:, None],
                                       out_valid.shape).reshape(-1)
        want = (out_valid & ok).reshape(-1)

        free_l = ~new_pool.valid
        free_vec = self._pervec(jnp.sum(free_l.astype(I32)), ax)
        n_free = jnp.sum(free_vec)
        rank0 = (jnp.cumsum(free_vec) - free_vec)[ax]
        free_i = free_l.astype(I32)
        grank = jnp.cumsum(free_i) - free_i + rank0
        fslot = jax.lax.pmin(
            jnp.full((p,), p, I32).at[jnp.where(free_l, grank, p)].set(
                base_p + jnp.arange(pl, dtype=I32), mode="drop"),
            self.axis)
        n_want = jnp.sum(want.astype(I32))
        want_i = want.astype(I32)
        want_rank = jnp.cumsum(want_i) - want_i
        dest = jnp.where(want & (want_rank < n_free),
                         fslot[jnp.minimum(want_rank, p - 1)], p)
        pool_overflow = jnp.maximum(n_want - n_free, 0)
        dl = dest - base_p
        dloc = jnp.where((dl >= 0) & (dl < pl), dl, pl)  # pl drops
        new_pool = pool_mod.write_slots(new_pool, dloc, flat)

        # stats + counters (global sums of pool-local masks ride [K]
        # count-vector min-gathers — integer-exact, census-clean)
        new_stats = stats_mod.record(s.stats, events, measuring)
        counters = dict(s.counters)
        counters["queue_lost"] += drops["queue_lost"]
        counters["bit_error_lost"] += drops["bit_error_lost"]
        counters["partition_lost"] += drops["partition_lost"]
        counters["dest_unavailable_lost"] += (
            drops["dest_unavailable_lost"] + csum(jnp.sum(to_dead)))
        counters["pool_overflow"] += pool_overflow
        counters["outbox_overflow"] += jnp.sum(out_overflow)
        counters["inbox_deferred"] = jnp.maximum(
            counters["inbox_deferred"],
            (csum(jnp.sum(s.pool.valid & (s.pool.t_deliver < t_end))) -
             csum(jnp.sum(delivered | to_dead))).astype(I64))
        tel = telemetry_mod.fold(
            s.telemetry, sim.ep.telemetry, t_end=t_end, tick=s.tick + 1,
            alive=alive, stats=new_stats, counters=counters)

        return sim_mod.SimState(
            t_now=t_end, tick=s.tick + 1, rng=rng, alive=alive,
            node_keys=node_keys, underlay=ul_state, pool=new_pool,
            churn=churn_state, malicious=s.malicious,
            logic=self._slice_logic(logic_state, rows_l),
            stats=new_stats, counters=counters, telemetry=tel)

    # -- global entries ---------------------------------------------------

    def step(self, s):
        """One node-sharded tick on the full (replicated+sharded) state."""
        return _smap(self._local_step, self.mesh,
                     (self.pspecs,), self.pspecs)(s)

    def place(self, s):
        """Put a solo SimState onto this mesh with the 2-D layout."""
        return jax.device_put(s, self.shardings)


class ShardedCampaign:
    """S stacked replicas × K node shards on one 2-D mesh: shard_map
    over BOTH axes, vmapping the sharded tick body over each device's
    local replica rows.  No collective names REPLICA_AXIS, so the
    cross-replica traffic is structurally zero — same pin as the 1-D
    replica mesh, now composed with node sharding."""

    def __init__(self, camp, mesh):
        if camp.sweep_stack:
            raise NotImplementedError(
                "sharded campaign tick supports pure seed replicas only "
                "(sweep overrides change the per-replica trace; run grid "
                "sweeps on the 1-D replica mesh)")
        if mesh_mod.REPLICA_AXIS not in mesh.axis_names:
            raise ValueError(f"mesh {mesh.axis_names} has no "
                             f"{mesh_mod.REPLICA_AXIS!r} axis")
        self.camp = camp
        self.mesh = mesh
        self.ssim = ShardedSim(camp.sim, mesh)
        self.r = int(mesh.shape[mesh_mod.REPLICA_AXIS])
        if camp.s % self.r:
            raise ValueError(f"S={camp.s} replicas not divisible by "
                             f"replica mesh extent r={self.r}")
        example = jax.eval_shape(
            lambda ids: jax.vmap(camp.sim.init_from_rng)(
                jax.vmap(camp.replica_rng)(ids)),
            jnp.asarray(camp.ids))
        self.pspecs = mesh_mod.campaign_state_pspecs_2d(example)
        self.shardings = jax.tree.map(
            lambda _, sp: jax.sharding.NamedSharding(mesh, sp),
            example, self.pspecs)

    def vstep(self, cs):
        """One tick of every replica, node-sharded K ways."""
        f = jax.vmap(self.ssim._local_step)
        return _smap(f, self.mesh, (self.pspecs,), self.pspecs)(cs)

    def place(self, cs):
        """Put a stacked campaign state onto the 2-D mesh."""
        return jax.device_put(cs, self.shardings)
