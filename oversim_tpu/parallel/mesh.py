"""Device-mesh sharding of the simulation state (multi-chip scale-out).

The reference scales by running ONE process over all N simulated nodes
(OMNeT++ kernel, single-threaded; SURVEY.md §2.5).  The TPU rebuild's
scale axis is the node-slot dimension: every [N, ...] state array (and the
[P, ...] message pool, P = pool_factor*N) is sharded over a 1-D
`jax.sharding.Mesh` along its leading axis, and the whole tick step runs
under `jit` with GSPMD partitioning — XLA inserts the collectives:

  * the global key-table gathers (`ctx.keys[slot]`) become all-gathers of
    the [N, KL] key table (small: 20 B/node) over ICI;
  * the pool's inbox selection (engine/pool.py ``build_inbox``): its
    P-wide scatter-min rounds partition into a LOCAL per-shard select +
    an all-reduce-min of the [N] per-destination minima — O(N)
    reduction traffic per round, where a full-pool sort would be an
    all-to-all merge exchange (XLA's partitioned `lax.sort` moves the
    whole [P] pool's keys across chips); the steady tick's one sort is
    over the D = P/32 compacted lanes, which every device holds whole;
  * per-node vmapped logic stays fully local to each shard (the dominant
    FLOPs — finger scans, key arithmetic — never cross chips);
  * scalar stats/counters are replicated and all-reduced.

Multi-host (DCN) fits the same program: initialize jax.distributed and
build the mesh over all processes' devices — jit/GSPMD handles the rest.
No NCCL/MPI translation (reference has none anyway): ICI/DCN collectives
are the communication backend.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NODE_AXIS = "nodes"
REPLICA_AXIS = "replicas"


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D mesh over the first ``n_devices`` local devices."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices).reshape(-1), (NODE_AXIS,))


def make_replica_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D mesh over the REPLICA axis (oversim_tpu/campaign/).

    Campaign state leaves are [S, ...]; sharding the leading replica
    axis is pure data parallelism — replicas never exchange data inside
    the tick, so the partitioned step compiles with ZERO cross-replica
    collectives (pinned by scripts/hlo_breakdown.py --campaign and
    tests/test_campaign.py): 4 chips run 4× replicas at solo speed.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices).reshape(-1), (REPLICA_AXIS,))


def make_mesh_2d(replica_devices: int = 1, node_devices: int | None = None,
                 devices=None) -> Mesh:
    """2-D ``(REPLICA_AXIS, NODE_AXIS)`` mesh: one program runs
    ``replica_devices`` replica groups, each over ``node_devices``
    node shards.  ``node_devices=None`` takes every remaining device.
    ``make_mesh_2d(1, k)`` is the solo node-sharded layout; composed
    with the campaign's stacked [S, ...] axis it is S replicas ×
    K-way-sharded nodes in one compiled tick."""
    if devices is None:
        devices = jax.devices()
    if node_devices is None:
        node_devices = len(devices) // replica_devices
    need = replica_devices * node_devices
    if need < 1 or need > len(devices):
        raise ValueError(
            f"mesh {replica_devices}x{node_devices} needs {need} devices, "
            f"have {len(devices)}")
    return Mesh(np.array(devices[:need]).reshape(replica_devices,
                                                 node_devices),
                (REPLICA_AXIS, NODE_AXIS))


def _shape(leaf):
    return tuple(getattr(leaf, "shape", None) or np.shape(leaf))


def _node_spec(leaf, lead: int):
    """P sharding dim ``lead`` on NODE_AXIS (replica dims prepended by
    the campaign builders)."""
    nd = len(_shape(leaf))
    return P(*([None] * lead), NODE_AXIS, *([None] * (nd - lead - 1)))


def state_pspecs_2d(state):
    """PartitionSpec pytree for a solo SimState on a (replica, node)
    mesh: pool leaves ([P]/[P, W]) and logic leaves with leading dim N
    shard along NODE_AXIS; EVERYTHING else is replicated.

    The replication ledger (why not "every [N, ...] leaf"):

      * ``alive``/``node_keys``/``malicious`` [N] — cross-indexed by
        every handler through the full-width Ctx (``ctx.keys[slot]``);
        at 20 B/node replicating is cheaper than an all-gather per use;
      * churn/underlay/stats/counters/telemetry + scalars — the churn
        step, ``logic.reset`` and ``send_batch`` draw FULL-WIDTH rng
        planes; running them replicated is what keeps the sharded tick
        bit-identical to the solo oracle (parallel/shard_tick.py);
      * the dominant bytes — the [P, W] pool block (O(N·pool_factor·W))
        and the per-node logic rows (O(N·F)) — do shard.
    """
    n = _shape(state.alive)[0]

    def logic_spec(leaf):
        shp = _shape(leaf)
        return _node_spec(leaf, 0) if shp and shp[0] == n else P()

    import dataclasses
    sp = jax.tree.map(lambda _: P(), state)
    return dataclasses.replace(
        sp,
        pool=jax.tree.map(lambda l: _node_spec(l, 0), state.pool),
        logic=jax.tree.map(logic_spec, state.logic))


def state_shardings_2d(state, mesh: Mesh):
    """NamedSharding pytree for a solo SimState on a 2-D mesh (node
    leaves sharded on NODE_AXIS, replicated across REPLICA_AXIS)."""
    k = int(mesh.shape[NODE_AXIS])
    n = _shape(state.alive)[0]
    p = _shape(state.pool.valid)[0]
    if n % k or p % k:
        raise ValueError(
            f"n={n} / pool={p} not divisible by node shards k={k}")
    return jax.tree.map(lambda _, sp: NamedSharding(mesh, sp), state,
                        state_pspecs_2d(state))


def shard_state_2d(state, mesh: Mesh):
    """Place a solo SimState onto a 2-D (replica, node) mesh."""
    return jax.device_put(state, state_shardings_2d(state, mesh))


def campaign_state_pspecs_2d(cs):
    """PartitionSpec pytree for a stacked [S, ...] campaign state on the
    2-D mesh: every leaf shards its leading replica axis; pool and
    logic-node leaves additionally shard dim 1 along NODE_AXIS (same
    replication ledger as :func:`state_pspecs_2d`, shifted one dim)."""
    n = _shape(cs.alive)[1]

    import dataclasses
    sp = jax.tree.map(lambda l: P(REPLICA_AXIS), cs)

    def logic_spec(leaf):
        shp = _shape(leaf)
        return (P(REPLICA_AXIS, NODE_AXIS)
                if len(shp) >= 2 and shp[1] == n else P(REPLICA_AXIS))

    sp = dataclasses.replace(
        sp,
        pool=jax.tree.map(lambda l: P(REPLICA_AXIS, NODE_AXIS), cs.pool),
        logic=jax.tree.map(logic_spec, cs.logic))
    return sp


def campaign_state_shardings_2d(cs, mesh: Mesh):
    """NamedSharding pytree for a stacked campaign state on a 2-D
    (replica, node) mesh."""
    r = int(mesh.shape[REPLICA_AXIS])
    k = int(mesh.shape[NODE_AXIS])
    s = _shape(cs.alive)[0]
    n = _shape(cs.alive)[1]
    p = _shape(cs.pool.valid)[1]
    if s % r:
        raise ValueError(f"S={s} replicas not divisible by replica "
                         f"mesh extent r={r}")
    if n % k or p % k:
        raise ValueError(
            f"n={n} / pool={p} not divisible by node shards k={k}")
    return jax.tree.map(lambda _, sp: NamedSharding(mesh, sp), cs,
                        campaign_state_pspecs_2d(cs))


def shard_campaign_state_2d(cs, mesh: Mesh):
    """Place a stacked campaign state onto a 2-D (replica, node) mesh."""
    return jax.device_put(cs, campaign_state_shardings_2d(cs, mesh))


def jit_sharded_step(sim, mesh: Mesh, donate: bool = True):
    """jit the genuinely node-sharded one-tick step (shard_map plane,
    parallel/shard_tick.py) with matching in/out shardings."""
    from oversim_tpu.parallel.shard_tick import ShardedSim
    ssim = ShardedSim(sim, mesh)
    return jax.jit(ssim.step, in_shardings=(ssim.shardings,),
                   out_shardings=ssim.shardings,
                   donate_argnums=(0,) if donate else ())


def jit_sharded_run(sim, mesh: Mesh, n_ticks: int, donate: bool = True):
    """jit a ``lax.scan`` of n_ticks node-sharded steps."""
    from oversim_tpu.parallel.shard_tick import ShardedSim
    ssim = ShardedSim(sim, mesh)

    def run(s):
        def body(carry, _):
            return ssim.step(carry), None
        s, _ = jax.lax.scan(body, s, None, length=n_ticks)
        return s

    return jax.jit(run, in_shardings=(ssim.shardings,),
                   out_shardings=ssim.shardings,
                   donate_argnums=(0,) if donate else ())


def jit_sharded_campaign_step(camp, mesh: Mesh, donate: bool = True):
    """jit the S-replica × K-node-shard campaign step on the 2-D mesh
    (zero cross-replica collectives: every pmin names NODE_AXIS only,
    so replica groups span node subgroups — pinned by the shard gate)."""
    from oversim_tpu.parallel.shard_tick import ShardedCampaign
    scamp = ShardedCampaign(camp, mesh)
    return jax.jit(scamp.vstep, in_shardings=(scamp.shardings,),
                   out_shardings=scamp.shardings,
                   donate_argnums=(0,) if donate else ())


def state_shardings(state, mesh: Mesh):
    """NamedSharding pytree for a SimState (arrays, or the shapes
    ``jax.eval_shape`` gives): leading axis of every array
    whose first dim divides evenly over the mesh is sharded; scalars and
    ragged leaves are replicated.  Telemetry ring buffers (leading axis
    = the sample window W, not a node dimension) are always replicated —
    a W that happens to divide the device count must not turn the gated
    ring scatter into a cross-shard update."""
    n_dev = mesh.devices.size
    replicated = NamedSharding(mesh, P())

    def spec(leaf):
        shp = _shape(leaf)
        if shp and shp[0] > 0 and shp[0] % n_dev == 0:
            return NamedSharding(mesh, P(NODE_AXIS, *([None] * (len(shp) - 1))))
        return replicated

    sh = jax.tree.map(spec, state)
    if getattr(state, "telemetry", None) is not None:
        import dataclasses
        sh = dataclasses.replace(
            sh, telemetry=jax.tree.map(lambda _: replicated, state.telemetry))
    return sh


def shard_state(state, mesh: Mesh):
    """Place a SimState onto the mesh with node-axis sharding."""
    return jax.device_put(state, state_shardings(state, mesh))


def campaign_state_shardings(cs, mesh: Mesh):
    """NamedSharding pytree for a stacked [S, ...] campaign state:
    shard the leading REPLICA axis of every leaf whose first dim divides
    evenly over the mesh; replicate the rest (per-replica scalars like
    t_now are [S] and shard too — they are one element per replica)."""
    n_dev = mesh.devices.size

    def spec(leaf):
        leaf = jnp.asarray(leaf)
        if leaf.ndim >= 1 and leaf.shape[0] % n_dev == 0 and leaf.shape[0] > 0:
            return NamedSharding(
                mesh, P(REPLICA_AXIS, *([None] * (leaf.ndim - 1))))
        return NamedSharding(mesh, P())

    return jax.tree.map(spec, cs)


def shard_campaign_state(cs, mesh: Mesh):
    """Place a stacked campaign state onto the mesh, replica-sharded."""
    return jax.device_put(cs, campaign_state_shardings(cs, mesh))


def jit_campaign_run_until(camp, mesh: Mesh, chunk: int = 64,
                           donate: bool = True):
    """jit a replica-sharded ``(cs, target_ns) -> cs`` campaign runner.

    The campaign analogue of ``jit_run_until``: a donated
    ``lax.while_loop`` of ``chunk``-tick vmapped scans with cond
    ``any(t_now < target_ns)`` (all replicas run until the slowest
    passes).  The only cross-device op the cond needs is a reduce over
    the [S] t_now vector — outside the tick body; the tick itself has
    zero cross-replica collectives.
    """
    example = camp.init()
    shardings = campaign_state_shardings(example, mesh)

    def run(cs, target_ns):
        def cond(carry):
            return jnp.any(carry.t_now < target_ns)

        def body(carry):
            def sbody(c, _):
                return camp._vstep(c), None
            c, _ = jax.lax.scan(sbody, carry, None, length=chunk)
            return c

        return jax.lax.while_loop(cond, body, cs)

    return jax.jit(run,
                   in_shardings=(shardings, NamedSharding(mesh, P())),
                   out_shardings=shardings,
                   donate_argnums=(0,) if donate else ())


def _gspmd_step(sim):
    """The step the GSPMD builders below partition: the Simulation's
    own (``sim.step``), so the plane ``EngineParams.tick_impl`` resolves
    to — the awake-set plane for a logic that declares
    ``awake_set_exact``, the dense sweep for every other or by name.

    That plane's compaction gathers rows from the whole node axis, so
    across shards every round is collectives, and still it is the one to
    shard.  Read on four v5e chips at N = 16,384 (Kademlia under
    KBRTestApp, 4,096 rows a device, same seed, six dispatches after the
    fill; PERF.md, PR 28): the dense sweep ticks in 320.3 ms with 4.6 ms
    of collectives, the awake-set plane in 171.4 ms with 6.1 ms, and it
    is the awake-set plane that lands on the one-device run, leaf for
    leaf (the dense sweep on the mesh does not, at that N on the chip).
    A dense step handed a state that carries the awake-set counters
    (``tick_impl="dense"`` by name on the layout of another Simulation's
    ``init()``) adds the alive rows it swept to ``lanes_stepped`` and
    leaves the other two at 0."""
    return sim.step


def _example_shardings(sim, mesh: Mesh):
    """The shardings of this deployment's SimState on ``mesh``, from the
    state's shapes alone (``jax.eval_shape``, as ``ShardedSim``): no
    state is built to size them."""
    example = jax.eval_shape(sim.init_from_rng, jax.random.PRNGKey(0))
    return state_shardings(example, mesh)


def jit_step(sim, mesh: Mesh, donate: bool = True):
    """jit the one-tick step with sharded in/out state.

    Returns a compiled callable state -> state.  The sharding constraint is
    placed on the argument/result; everything inside is GSPMD-partitioned.
    """
    shardings = _example_shardings(sim, mesh)
    return jax.jit(_gspmd_step(sim), in_shardings=(shardings,),
                   out_shardings=shardings,
                   donate_argnums=(0,) if donate else ())


def jit_run(sim, mesh: Mesh, n_ticks: int, donate: bool = True):
    """jit a ``lax.scan`` of n_ticks sharded steps (one dispatch for the
    whole run — the multi-chip equivalent of Simulation.run_chunk)."""
    shardings = _example_shardings(sim, mesh)
    step = _gspmd_step(sim)

    def run(s):
        def body(carry, _):
            return step(carry), None
        s, _ = jax.lax.scan(body, s, None, length=n_ticks)
        return s

    return jax.jit(run, in_shardings=(shardings,), out_shardings=shardings,
                   donate_argnums=(0,) if donate else ())


def jit_run_until(sim, mesh: Mesh, chunk: int = 64, donate: bool = True):
    """jit a device-resident ``(state, target_ns) -> state`` runner.

    The multi-chip equivalent of ``Simulation.run_until_device``: a
    ``lax.while_loop`` re-runs ``chunk``-tick scans until
    ``t_now >= target_ns``, so the whole run to a simulation-time target
    is ONE dispatch — no per-chunk host round-trip (the per-chunk sync
    in the host loop costs a full ICI/DCN drain at scale).  ``target_ns``
    is an i64 scalar in engine ns (``t_sim * sim_mod.NS``; a host
    ``np.int64`` costs no device operation of its own), replicated and
    traced: every target shares the ONE compiled program, whose count
    the returned callable gives as ``_cache_size()``.  The state is
    donated: rebind it.
    """
    shardings = _example_shardings(sim, mesh)
    step = _gspmd_step(sim)

    def run(s, target_ns):
        def cond(carry):
            return carry.t_now < target_ns

        def body(carry):
            def sbody(c, _):
                return step(c), None
            c, _ = jax.lax.scan(sbody, carry, None, length=chunk)
            return c

        return jax.lax.while_loop(cond, body, s)

    return jax.jit(run,
                   in_shardings=(shardings, NamedSharding(mesh, P())),
                   out_shardings=shardings,
                   donate_argnums=(0,) if donate else ())
