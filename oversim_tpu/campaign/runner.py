"""Campaign runner: vmapped multi-replica simulation with device stats.

The reference workflow for a publishable hop-count distribution is N
repetitions of the same scenario (``./OverSim -r 0..N-1``) and a
hand-rolled average over N scalar files.  Here the N replicas ARE the
leading axis of one SimState pytree: ``jax.vmap`` of ``Simulation.step``
over every leaf turns the whole ensemble into ONE compiled program —
one compile amortized over S measurement streams, with the replica axis
shardable across chips (parallel/mesh.py REPLICA_AXIS) as pure data
parallelism: zero cross-replica collectives in the tick.

Replicas are either pure seed replicas (``CampaignParams.replicas`` per
grid point, per-replica rng = ``fold_in(PRNGKey(base_seed), r)``) or a
grid sweep: ``CampaignParams.sweep`` maps dotted parameter names
(``churn.lifetimeMean``, ``engine.window``, ``app.testMsgInterval``) to
value lists; the cartesian product is materialized as per-replica traced
scalars fed through ``Simulation.step(s, ov=...)`` — same graph, S
different parameter points.

Time semantics: replicas do NOT advance in lockstep.  Each replica's
tick horizon is its own earliest event, so after ``run_until_device``
(cond: ``any(t_now < target)``) fast replicas have overshot the target
by up to a window while slow ones just passed it — exactly like S
independent ``run_until_device`` calls, except replicas that finish
early keep ticking (harmlessly, past-target events only) until the last
one passes.  ``run_chunk`` (fixed tick count) is bit-identical to S solo
``run_chunk`` calls — the identity contract pinned by
tests/test_vmap_campaign.py.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from functools import partial

import jax
import jax.numpy as jnp

from oversim_tpu import stats as stats_mod
from oversim_tpu.engine.sim import NS, SimState, _dedupe_buffers

I64 = jnp.int64


@dataclasses.dataclass(frozen=True)
class CampaignParams:
    """Static campaign shape.

    ``replicas``  — seed replicas PER grid point (S = replicas × #points)
    ``base_seed`` — replica r uses rng = fold_in(PRNGKey(base_seed), r)
    ``sweep``     — ((dotted_name, (v0, v1, ...)), ...) grid axes;
                    empty = pure seed sweep (ov=None, the engine's
                    bit-identical static-param trace)
    ``replica_ids`` — optional GLOBAL replica-id subset: run only these
                    replicas of the full replicas×grid campaign, with
                    their full-campaign rng and sweep point.  A fleet
                    worker (oversim_tpu/elastic/) holding shard
                    ``replica_ids=(4,5,6,7)`` advances rows 4..7 of the
                    full campaign bit-identically; None = all ids in
                    order (the classic full campaign).
    """

    replicas: int = 4
    base_seed: int = 1
    sweep: tuple = ()
    replica_ids: tuple | None = None


def expand_grid(sweep) -> list:
    """Cartesian product of sweep axes -> list of {name: value} dicts
    (one per grid point, declaration order = row-major)."""
    sweep = tuple(sweep)
    if not sweep:
        return [{}]
    names = [name for name, _ in sweep]
    axes = [tuple(vals) for _, vals in sweep]
    return [dict(zip(names, combo)) for combo in itertools.product(*axes)]


class Campaign:
    """Host-side driver running S replicas of one Simulation.

    Usage::

        camp = Campaign(sim, CampaignParams(replicas=8))
        cs = camp.init()                      # stacked [S, ...] SimState
        cs = camp.run_until_device(cs, 600.0) # ONE dispatch, donated
        report = camp.report(cs)              # ensemble mean/stddev/CI
    """

    def __init__(self, sim, params: CampaignParams | None = None):
        # the vmapped step takes the dense sweep (unless the awake-set
        # plane was asked for by name) and the P-wide inbox selection:
        # under vmap the round loop runs every replica for the busiest
        # replica's rounds, and a lax.cond runs both its branches
        self.sim = sim.for_vmap() if sim is not None else sim
        self.p = params or CampaignParams()
        if self.p.replicas < 1:
            raise ValueError("campaign needs at least one replica")
        self.grid = expand_grid(self.p.sweep)
        # total extent of the FULL campaign; self.ids are global replica
        # ids into it (identity for classic whole-campaign runs)
        self.total = self.p.replicas * len(self.grid)
        if self.p.replica_ids is None:
            self.ids = tuple(range(self.total))
        else:
            self.ids = tuple(int(i) for i in self.p.replica_ids)
            if not self.ids:
                raise ValueError("campaign needs at least one replica id")
            bad = [i for i in self.ids if i < 0 or i >= self.total]
            if bad:
                raise ValueError(
                    f"replica_ids {bad} outside the campaign's "
                    f"0..{self.total - 1} id space")
        self.s = len(self.ids)
        # per-replica sweep values, stacked [S] in id order (global
        # replica id i belongs to grid point i // replicas)
        ftype = jnp.result_type(float)
        self.sweep_stack = {
            name: jnp.asarray(
                [self.grid[i // self.p.replicas][name] for i in self.ids],
                ftype)
            for name in (self.grid[0] or {})
        }

    # -- per-replica identities (the bit-identity contract) -----------------

    def replica_rng(self, r: int) -> jax.Array:
        """The rng replica r is initialized from — a solo
        ``sim.init_from_rng(camp.replica_rng(r))`` run IS replica r."""
        return jax.random.fold_in(
            jax.random.PRNGKey(self.p.base_seed), jnp.uint32(r))

    def replica_ov(self, r: int):
        """Local row r's sweep-override dict (None for pure seed
        sweeps) — pass to ``sim.step(s, ov=...)`` to reproduce that row
        solo.  ``r`` indexes THIS campaign's rows; ``self.ids[r]`` is
        the global replica id (identical for full campaigns)."""
        pt = self.grid[self.ids[r] // self.p.replicas]
        return dict(pt) if pt else None

    def describe(self) -> dict:
        """JSON-able campaign identity for checkpoint manifests: the
        reshard path (oversim_tpu/elastic/reshard.py) refuses to graft a
        checkpoint onto a campaign with a different base seed / grid,
        and prefix-checks ``replica_ids`` so row k always means the same
        replica before and after a grow/shrink."""
        return {
            "replicas": self.p.replicas,
            "base_seed": self.p.base_seed,
            "sweep": [[name, list(vals)] for name, vals in self.p.sweep],
            "replica_ids": list(self.ids),
            "s": self.s,
            "total": self.total,
        }

    # -- init ---------------------------------------------------------------

    def init(self) -> SimState:
        """Stacked init: every SimState leaf gains a leading [S] axis.
        Row r is GLOBAL replica ``self.ids[r]`` — a subset campaign
        initializes exactly the corresponding rows of the full one."""
        rngs = jax.vmap(self.replica_rng)(jnp.asarray(self.ids))
        if self.sweep_stack:
            f = jax.jit(jax.vmap(
                lambda rng, ov: self.sim.init_from_rng(rng, ov=ov)))
            cs = f(rngs, self.sweep_stack)
        else:
            cs = jax.jit(jax.vmap(self.sim.init_from_rng))(rngs)
        # run_chunk donates; XLA CSE may alias identical stacked outputs
        # (e.g. two all-zero accumulators), so dedupe host-side like
        # Simulation.init does
        return _dedupe_buffers(cs)

    # -- stepping -----------------------------------------------------------

    def _vstep(self, cs: SimState) -> SimState:
        if self.sweep_stack:
            return jax.vmap(
                lambda s, ov: self.sim.step(s, ov=ov))(cs, self.sweep_stack)
        return jax.vmap(self.sim.step)(cs)

    @partial(jax.jit, static_argnames=("self", "n_ticks"),
             donate_argnums=(1,))
    def run_chunk(self, cs: SimState, n_ticks: int) -> SimState:
        """``n_ticks`` ticks of EVERY replica, one fused dispatch.
        Donated like Simulation.run_chunk — rebind the result."""
        def body(c, _):
            return self._vstep(c), None
        cs, _ = jax.lax.scan(body, cs, None, length=n_ticks)
        return cs

    @partial(jax.jit, static_argnames=("self", "chunk"), donate_argnums=(1,))
    def _run_until_device(self, cs: SimState, target, chunk: int) -> SimState:
        def cond(c):
            return jnp.any(c.t_now < target)

        def body(c):
            def sbody(cc, _):
                return self._vstep(cc), None
            cc, _ = jax.lax.scan(sbody, c, None, length=chunk)
            return cc

        return jax.lax.while_loop(cond, body, cs)

    def run_until_device(self, cs: SimState, t_sim: float,
                         chunk: int = 256) -> SimState:
        """Run ALL replicas past ``t_sim`` seconds in one dispatch.
        Replicas that pass the target early keep ticking (their
        past-target windows deliver only already-scheduled events) until
        the slowest replica crosses — see the module docstring."""
        target = jnp.int64(int(t_sim * NS))
        return self._run_until_device(cs, target, chunk)

    # -- reporting ----------------------------------------------------------

    # cs is deliberately NOT donated: report() is safe to call mid-run,
    # so the caller keeps using the state afterwards
    @partial(jax.jit, static_argnames=("self",))  # analysis: allow(undonated-jit)
    def _reduce(self, cs: SimState):
        return (stats_mod.ensemble_reduce(cs.stats),
                dict(t_now=cs.t_now, tick=cs.tick,
                     alive=jnp.sum(cs.alive, axis=1),
                     counters=cs.counters))

    def report(self, cs: SimState, confidence: float = 0.95) -> dict:  # analysis: allow(host-numpy, host-float, host-device-get)
        """Ensemble report: every metric as cross-replica mean/stddev/
        Student-t CI + per-replica breakdown (stats.ensemble_summary
        schema), plus ``_campaign`` metadata (grid, per-replica t_sim/
        ticks/alive, engine counters summed over replicas) and a derived
        ``kbr_delivery_ratio`` when the KBRTest counters are present.
        One jitted reduce + one device_get; safe to call mid-run."""
        import numpy as np

        reduced, meta = jax.device_get(self._reduce(cs))
        out = stats_mod.ensemble_summary(reduced, confidence)

        if "kbr_sent" in out and "kbr_delivered" in out:
            sent = np.asarray(out["kbr_sent"]["per_replica"], float)
            deliv = np.asarray(out["kbr_delivered"]["per_replica"], float)
            has = sent > 0
            ratio = np.where(has, deliv / np.maximum(sent, 1.0), np.nan)
            k = int(has.sum())
            mean = float(ratio[has].mean()) if k else math.nan
            stddev = float(ratio[has].std(ddof=1)) if k > 1 else 0.0
            sem = stddev / math.sqrt(k) if k else math.nan
            t = stats_mod.t_critical(k - 1, confidence) if k > 1 else math.nan
            out["kbr_delivery_ratio"] = {
                "kind": "derived", "k": k, "mean": mean, "stddev": stddev,
                "sem": sem, "ci": t * sem if k > 1 else math.nan,
                "confidence": confidence,
                "per_replica": [None if math.isnan(x) else float(x)
                                for x in ratio],
            }

        out["_campaign"] = {
            "replicas": self.p.replicas,
            "grid": self.grid,
            "s": self.s,
            "replica_ids": list(self.ids),
            "base_seed": self.p.base_seed,
            "confidence": confidence,
            "t_sim": (np.asarray(meta["t_now"]) / NS).tolist(),
            "ticks": np.asarray(meta["tick"]).tolist(),
            "alive": np.asarray(meta["alive"]).tolist(),
            "engine": {k: int(np.asarray(v).sum())
                       for k, v in meta["counters"].items()},
        }
        return out

    def telemetry_report(self, cs: SimState,  # analysis: allow(host-device-get)
                         confidence: float = 0.95) -> dict:
        """Per-replica KPI time series + cross-replica CI bands off the
        stacked ``[S, W, ...]`` telemetry rings (oversim_tpu/telemetry.py
        ``ensemble_series``; bands via ``stats.series_summary``).  ONE
        device_get of the ring leaves; {"enabled": False} when the sim
        was built without ``telemetry.sample_ticks``."""
        if cs.telemetry is None:
            return {"enabled": False}
        from oversim_tpu import telemetry as telemetry_mod
        return telemetry_mod.ensemble_series(
            jax.device_get(cs.telemetry), confidence=confidence)

    def replica_state(self, cs: SimState, r: int) -> SimState:
        """Slice replica r out of the stacked state (host-side copy) —
        handy for ``sim.summary`` on one replica or debugging."""
        return jax.tree.map(lambda x: x[r], cs)
