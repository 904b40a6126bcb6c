"""Churn generators: node create/kill processes as scheduled slot events.

TPU-native equivalent of the reference's ChurnGenerator family
(src/common/{ChurnGenerator,NoChurn,LifetimeChurn,ParetoChurn,RandomChurn}):
instead of scheduling per-node create/kill self-messages through the event
kernel, every slot carries a next-create and next-kill time in an [N] i64
array and the engine flips the alive mask for the slots whose event falls
inside the tick window — churn never reshapes any array (SURVEY.md §7.2
"dynamic population": preallocated slots with alive masks, mirroring
LifetimeChurn's contextVector slot recycling, LifetimeChurn.cc:40-52).

Population conventions match the reference:
  * NoChurn (NoChurn.cc:20-52): creates one node every
    ~truncnormal(initPhaseCreationInterval, dev) until the target count,
    then signals init-finished; nodes never die.  Slots = target.
  * LifetimeChurn (LifetimeChurn.cc): 2×target context slots; during init,
    slot i (< target) is created at ~truncnormal(mean·i, dev) and killed at
    initFinished + L() where L ~ lifetime distribution; the other target
    slots go live at initFinished + L(); thereafter each kill schedules a
    re-create after a dead-time draw from the same distribution, with a
    fresh lifetime.  Distributions (LifetimeChurn.cc:distributionFunction):
    weibull (scale mean/Γ(1+1/k)), pareto_shifted, truncnormal.
  * ParetoChurn (ParetoChurn.cc:44-219): two-level process — per-slot
    individual mean life/dead times from a generalized pareto (alpha 3),
    equilibrium init-phase population (alive w.p. l/(l+d)), a stretch
    factor correcting the population-mean session to lifetimeMean, and
    residual (alpha 2) draws for the sessions in progress at init.
  * RandomChurn (RandomChurn.{h,cc}): a periodic tick every
    churnChangeInterval that probabilistically creates or removes one
    random node.
  * TraceChurn replays GlobalTraceManager traces (see trace.py).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from oversim_tpu.core.scopes import scoped

I64 = jnp.int64
NS = 1_000_000_000
T_INF = jnp.int64(2**62)


def _truncnormal(rng, mean, stddev, shape=()):
    """OMNeT++ truncnormal: normal redrawn until non-negative; we fold the
    redraw into |N| which matches the half-normal-plus-shift closely enough
    for schedule jitter (exact for mean=0)."""
    x = mean + stddev * jax.random.normal(rng, shape)
    return jnp.abs(x)


@dataclasses.dataclass(frozen=True)
class ChurnParams:
    """Reference params: default.ini:498-506 + ChurnGenerator.ned."""

    model: str = "none"               # "none"|"lifetime"|"pareto"|"random"
    target_num: int = 10              # targetOverlayTerminalNum
    init_interval: float = 1.0        # initPhaseCreationInterval (s)
    init_deviation: float = 0.1
    lifetime_mean: float = 10000.0    # lifetimeMean (s)
    deadtime_mean: float | None = None  # deadtimeMean (pareto; None = life)
    lifetime_dist: str = "weibull"    # lifetimeDistName
    lifetime_par1: float = 1.0        # lifetimeDistPar1
    graceful_leave_delay: float = 15.0        # gracefulLeaveDelay, default.ini:493
    graceful_leave_probability: float = 0.5   # default.ini:494
    # per-peer rejoin context (GlobalNodeList::getContext/storeContext,
    # GlobalNodeList.h:194; BaseOverlay.cc:823-831: a node created in a
    # recycled slot reclaims the slot's previous nodeId and flags
    # instead of drawing fresh ones — LifetimeChurn context slots)
    rejoin_context: bool = False
    # RandomChurn (RandomChurn.{h,cc}): periodic probabilistic events
    churn_change_interval: float = 10.0   # churnChangeInterval
    creation_probability: float = 0.5     # creationProbability
    removal_probability: float = 0.5      # removalProbability
    # TraceChurn (TraceChurn.{h,cc} + GlobalTraceManager): precomputed
    # per-slot join/leave schedules from a trace file (trace.py parses
    # `<time> <nodeID> JOIN|LEAVE` lines into these tuples)
    trace_create: tuple = ()              # seconds, one entry per slot
    trace_kill: tuple = ()

    @property
    def num_slots(self) -> int:
        if self.model == "trace":
            return len(self.trace_create)
        if self.model == "none":
            return self.target_num
        if self.model == "pareto":
            # the reference draws nodes until `target` come up alive
            # (expected availability l/(l+d)); 3x slots bounds the draw
            return 3 * self.target_num
        return 2 * self.target_num

    @property
    def init_finished_time(self) -> float:
        """When the init phase ends and transition time starts counting."""
        if self.model == "trace":
            return 0.0
        return self.init_interval * self.target_num


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ChurnState:
    t_create: jnp.ndarray  # [N] i64 — pending create events (T_INF if none)
    t_kill: jnp.ndarray    # [N] i64 — pending pre-kill (leave notification)
    t_dead: jnp.ndarray    # [N] i64 — scheduled final kill (grace window end;
                           # preKillNode schedules removal gracefulLeaveDelay
                           # later, SimpleUnderlayConfigurator.cc:375-376)
    graceful: jnp.ndarray  # [N] bool — NF_OVERLAY_NODE_GRACEFUL_LEAVE drawn
                           # (w.p. gracefulLeaveProbability, :370-373)
    t_born: jnp.ndarray    # [N] i64 — the slot's INCARNATION: the start of
                           # the tick that last created a node in it (-1:
                           # never).  A slot is recycled under a fresh key,
                           # so (slot, t_born) names a node where the slot
                           # alone names an address; read by whoever
                           # follows joins and deaths from outside
    l_mean: jnp.ndarray    # [N] f32 — per-slot mean lifetime (pareto)
    d_mean: jnp.ndarray    # [N] f32 — per-slot mean deadtime (pareto)
    t_tick: jnp.ndarray    # [] i64 — next periodic churn tick (random model)


def _with_grace(state_kw, n):
    state_kw.setdefault("t_dead", jnp.full((n,), T_INF, I64))
    state_kw.setdefault("graceful", jnp.zeros((n,), bool))
    state_kw.setdefault("t_born", jnp.full((n,), -1, I64))
    return state_kw


def _draw_lifetime(rng, p: ChurnParams, shape, mean=None):
    """Session/dead-time draw (LifetimeChurn::distributionFunction).

    ``mean`` overrides ``p.lifetime_mean`` and may be a TRACED scalar —
    the campaign runner sweeps churn intensity across replicas inside
    one compiled program (oversim_tpu/campaign/).  All three
    distributions take the mean as an array-valued scale, so the same
    graph serves every replica."""
    if mean is None:
        mean = p.lifetime_mean
    if p.lifetime_dist == "weibull":
        scale = mean / math.gamma(1.0 + 1.0 / p.lifetime_par1)
        return jax.random.weibull_min(rng, scale, p.lifetime_par1, shape)
    if p.lifetime_dist == "pareto_shifted":
        k = p.lifetime_par1
        scale = mean * (k - 1.0) / k
        u = jax.random.uniform(rng, shape)
        return scale * (jnp.power(u, -1.0 / k) - 1.0)
    if p.lifetime_dist == "truncnormal":
        return _truncnormal(rng, mean, mean / 3.0, shape)
    raise ValueError(f"unknown lifetime distribution {p.lifetime_dist}")


def _shifted_pareto(rng, alpha: float, mean, shape=()):
    """ParetoChurn::shiftedPareto with betaByMean folded in
    (ParetoChurn.cc:209-219): mean*(3-1)*(u^(-1/alpha) - 1).  beta derives
    from the *schedule* alpha 3 even for the residual draw (alpha 2)."""
    u = jax.random.uniform(rng, shape, minval=1e-12, maxval=1.0)
    return mean * 2.0 * (jnp.power(u, -1.0 / alpha) - 1.0)


def init(rng: jax.Array, p: ChurnParams, life_mean=None) -> ChurnState:
    """``life_mean`` (optional, may be traced) overrides
    ``p.lifetime_mean`` for the lifetime model's session draws — the
    campaign sweep axis.  ``None`` keeps the static-param graph
    bit-identical to before."""
    n = p.num_slots
    tgt = p.target_num
    # NOTE: l_mean/d_mean must be DISTINCT arrays — a shared object
    # would alias their buffers and break run_chunk's state donation
    # (XLA rejects donating the same buffer twice)
    zeros = lambda: jnp.zeros((n,), jnp.float32)  # noqa: E731
    r1, r2, r3, r4 = jax.random.split(rng, 4)
    if p.model == "none":
        stagger = _truncnormal(r1, p.init_interval, p.init_deviation, (n,))
        t_create = jnp.cumsum(stagger)
        return ChurnState(**_with_grace(dict(
            t_create=(t_create * NS).astype(I64),
            t_kill=jnp.full((n,), T_INF, I64),
            l_mean=zeros(), d_mean=zeros(), t_tick=T_INF), n))
    if p.model == "trace":
        # TraceChurn: the schedule IS the trace (GlobalTraceManager
        # createNode/deleteNode at the traced times)
        t_create = jnp.asarray(
            [t * NS if t is not None else int(T_INF)
             for t in p.trace_create], I64)
        t_kill = jnp.asarray(
            [t * NS if t is not None else int(T_INF)
             for t in p.trace_kill], I64)
        return ChurnState(**_with_grace(dict(t_create=t_create, t_kill=t_kill,
                          l_mean=zeros(), d_mean=zeros(), t_tick=T_INF), n))
    if p.model == "lifetime":
        fin = p.init_finished_time
        i = jnp.arange(tgt)
        first_create = _truncnormal(r1, p.init_interval * i,
                                    p.init_deviation, (tgt,))
        first_kill = fin + _draw_lifetime(r2, p, (tgt,), mean=life_mean)
        second_create = fin + _draw_lifetime(r3, p, (tgt,), mean=life_mean)
        second_kill = second_create + _draw_lifetime(r4, p, (tgt,),
                                                     mean=life_mean)
        t_create = jnp.concatenate([first_create, second_create])
        t_kill = jnp.concatenate([first_kill, second_kill])
        # pre-kill (leave notification) fires gracefulLeaveDelay before
        # the session end; the node survives the grace window so total
        # session length == the drawn lifetime (LifetimeChurn.cc:112-113)
        t_kill = jnp.maximum(t_kill - p.graceful_leave_delay, t_create)
        return ChurnState(**_with_grace(dict(t_create=(t_create * NS).astype(I64),
            t_kill=(t_kill * NS).astype(I64),
            l_mean=zeros(), d_mean=zeros(), t_tick=T_INF), n))
    if p.model == "pareto":
        # ParetoChurn.cc:66-126: per-slot individual mean life/dead times,
        # equilibrium init (alive w.p. availability), stretch to hit the
        # configured global mean, residual draws for the first sessions
        fin = p.init_finished_time
        dmean = p.deadtime_mean if p.deadtime_mean is not None \
            else p.lifetime_mean
        ra, rb, rc, rd, re, rf, rg = jax.random.split(rng, 7)
        l_i = _shifted_pareto(ra, 3.0, p.lifetime_mean, (n,))
        d_i = _shifted_pareto(rb, 3.0, dmean, (n,))
        avail = l_i / (l_i + d_i)
        alive0 = jax.random.uniform(rc, (n,)) < avail
        # the reference draws slots until `tgt` come up alive
        # (ParetoChurn.cc:71): only slots up to (and including) the
        # tgt-th alive draw participate; later slots never exist — this
        # keeps the long-run population at target (each participating
        # slot contributes availability a_i, sum ≈ tgt)
        alive_rank = jnp.cumsum(alive0.astype(jnp.int32))
        is_init_alive = alive0 & (alive_rank <= tgt)
        participating = alive_rank <= tgt
        # (if fewer than tgt come up alive — vanishingly unlikely with 3x
        # slots — the surplus dead slots simply all participate)
        # stretch normalization over exactly the participating population
        # (ParetoChurn.cc normalizes over the drawn slots, not the 3x pool)
        sum_li = jnp.sum(jnp.where(participating, 1.0 / (l_i + d_i), 0.0))
        mean_life = jnp.sum(
            jnp.where(participating, l_i / ((l_i + d_i) * sum_li), 0.0))
        stretch = p.lifetime_mean / mean_life
        l_i = l_i * stretch
        d_i = d_i * stretch
        live_idx = jnp.where(is_init_alive, alive_rank - 1, 0)
        stagger = _truncnormal(rd, p.init_interval * live_idx,
                               p.init_deviation, (n,))
        res_l = _shifted_pareto(re, 2.0, l_i, (n,))
        res_d = _shifted_pareto(rf, 2.0, d_i, (n,))
        t_create = jnp.where(is_init_alive, stagger, fin + res_d)
        first_life = jnp.where(is_init_alive, fin - stagger + res_l,
                               _shifted_pareto(rg, 3.0, l_i, (n,)))
        t_kill = jnp.maximum(t_create + first_life - p.graceful_leave_delay,
                             t_create)
        t_create = jnp.where(participating, t_create, T_INF / NS)
        t_kill = jnp.where(participating, t_kill, T_INF / NS)
        return ChurnState(**_with_grace(dict(t_create=(t_create * NS).astype(I64),
            t_kill=(t_kill * NS).astype(I64),
            l_mean=l_i.astype(jnp.float32), d_mean=d_i.astype(jnp.float32),
            t_tick=T_INF), n))
    if p.model == "random":
        # RandomChurn: start tgt nodes, then probabilistic create/remove
        # ticks every churnChangeInterval (step() drives the process)
        stagger = _truncnormal(r1, p.init_interval, p.init_deviation, (n,))
        t_create = jnp.cumsum(stagger)
        t_create = jnp.where(jnp.arange(n) < tgt, t_create, T_INF / NS)
        return ChurnState(**_with_grace(dict(
            t_create=(t_create * NS).astype(I64),
            t_kill=jnp.full((n,), T_INF, I64),
            l_mean=zeros(), d_mean=zeros(),
            t_tick=jnp.int64(int((p.init_finished_time
                                  + p.churn_change_interval) * NS))), n))
    raise ValueError(f"unknown churn model {p.model}")


def next_event(state: ChurnState):
    # t_kill holds the already-fired pre-kill time during a grace window
    # (rebirth anchor) — mask it so the engine doesn't spin on it
    kill_eff = jnp.where(state.t_dead < T_INF, T_INF, state.t_kill)
    t = jnp.minimum(state.t_tick,
                    jnp.minimum(jnp.min(state.t_create),
                                jnp.min(kill_eff)))
    return jnp.minimum(t, jnp.min(state.t_dead))


@scoped("churn.step")
def step(state: ChurnState, p: ChurnParams, alive, t_start, t_end, rng,
         life_mean=None):
    """Fire create/pre-kill/kill events inside [t_start, t_end).

    Returns (state', created, killed, leaving — all [N] bool).  A pre-kill
    (t_kill) starts the grace window: the node keeps running for
    gracefulLeaveDelay, is removed from the bootstrap oracle, and — w.p.
    gracefulLeaveProbability — receives the graceful-leave notification
    (``state.graceful``) so overlay/apps can hand data over
    (SimpleUnderlayConfigurator::preKillNode, :312-377).  The final kill
    (t_dead) frees the slot and schedules its next incarnation
    (LifetimeChurn::deleteNode re-creates after a dead-time draw).
    ``leaving`` marks the pre-kills fired THIS window.
    """
    created = (state.t_create < t_end) & ~alive
    leaving = (state.t_kill < t_end) & alive & ~created & (
        state.t_dead >= T_INF)
    killed = (state.t_dead < t_end) & alive & ~created

    r_grace, rng = jax.random.split(rng)
    grace_ns = jnp.int64(int(p.graceful_leave_delay * NS))
    coin = jax.random.uniform(r_grace, (p.num_slots,)) \
        < p.graceful_leave_probability
    t_dead = jnp.where(leaving, state.t_kill + grace_ns, state.t_dead)
    graceful = jnp.where(leaving, coin, state.graceful)
    t_dead = jnp.where(killed, T_INF, t_dead)
    graceful = jnp.where(killed, False, graceful)
    # t_kill keeps the pre-kill time through the grace window: the rebirth
    # dead-time below starts at deleteNode (= the pre-kill), matching
    # LifetimeChurn::deleteNode; next_event() masks it while t_dead runs

    t_create = jnp.where(created, T_INF, state.t_create)
    t_born = jnp.where(created, t_start, state.t_born)
    t_kill = state.t_kill
    t_tick = state.t_tick
    n = p.num_slots

    if p.model == "lifetime":
        r1, r2 = jax.random.split(rng)
        dead_time = (_draw_lifetime(r1, p, (n,), mean=life_mean)
                     * NS).astype(I64)
        lifetime = (_draw_lifetime(r2, p, (n,), mean=life_mean)
                    * NS).astype(I64)
        next_create = state.t_kill + dead_time
        next_kill = jnp.maximum(next_create + lifetime - grace_ns,
                                next_create)
        t_create = jnp.where(killed, next_create, t_create)
        t_kill = jnp.where(killed, next_kill, t_kill)
    elif p.model == "pareto":
        # ParetoChurn::deleteNode (ParetoChurn.cc:182-196): rebirth after
        # individualLifetime(d_i), next session individualLifetime(l_i)
        r1, r2 = jax.random.split(rng)
        dead_time = (_shifted_pareto(r1, 3.0, state.d_mean, (n,))
                     * NS).astype(I64)
        lifetime = (_shifted_pareto(r2, 3.0, state.l_mean, (n,))
                    * NS).astype(I64)
        next_create = state.t_kill + dead_time
        next_kill = jnp.maximum(next_create + lifetime - grace_ns,
                                next_create)
        t_create = jnp.where(killed, next_create, t_create)
        t_kill = jnp.where(killed, next_kill, t_kill)
    elif p.model == "random":
        # RandomChurn::handleMessage: every churnChangeInterval flip a coin
        # for one create and one removal (probabilistic population drift)
        t_kill = jnp.where(killed, T_INF, t_kill)
        del n  # slots indexed directly below
        tick = t_tick < t_end
        r1, r2, r3, r4 = jax.random.split(rng, 4)
        do_create = tick & (jax.random.uniform(r1) < p.creation_probability)
        do_remove = tick & (jax.random.uniform(r2) < p.removal_probability)
        cur_alive = (alive | created) & ~killed
        # random dead slot → create now; random alive slot → kill now
        dead_w = jnp.where(~cur_alive & (t_create >= T_INF), 1.0, 0.0)
        alive_w = jnp.where(cur_alive, 1.0, 0.0)
        has_dead = jnp.sum(dead_w) > 0
        has_alive = jnp.sum(alive_w) > 0
        di = jax.random.categorical(r3, jnp.log(jnp.maximum(dead_w, 1e-30)))
        ai = jax.random.categorical(r4, jnp.log(jnp.maximum(alive_w, 1e-30)))
        t_create = t_create.at[di].set(
            jnp.where(do_create & has_dead, t_end, t_create[di]))
        t_kill = t_kill.at[ai].set(
            jnp.where(do_remove & has_alive, t_end, t_kill[ai]))
        t_tick = jnp.where(
            tick, t_tick + jnp.int64(int(p.churn_change_interval * NS)),
            t_tick)
    else:
        t_kill = jnp.where(killed, T_INF, t_kill)
    # a next-incarnation pre-kill drawn inside the current window must be
    # DEFERRED past it (cancelling would make the slot immortal; leaving
    # it stale would pin the event horizon)
    t_kill = jnp.where(killed & (t_kill <= t_end), t_end + 1, t_kill)

    return ChurnState(
        t_create=t_create, t_kill=t_kill, t_dead=t_dead, graceful=graceful,
        t_born=t_born, l_mean=state.l_mean, d_mean=state.d_mean,
        t_tick=t_tick), created, killed, leaving
