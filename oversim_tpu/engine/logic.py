"""Per-node logic scaffolding: message views, outbox builder, tick context.

A "logic" object plays the role of the whole per-node module stack of the
reference (overlay + tier apps + RPC glue, reference SimpleOverlayHost.ned)
— but as pure functions over structure-of-arrays state, written against a
*single* node's slice and vmapped over all N nodes by the engine.

Logic interface (duck-typed; see engine/sim.py):

  key_spec              -> core.keys.KeySpec
  stat_spec()           -> StatSpec
  init(rng, n)          -> state pytree of [N, ...] arrays
  reset(state, clear, join, t_now, rng) -> state
      # churn transitions: ``clear`` [N] marks slots to wipe (created AND
      # killed), ``join`` [N] the subset that goes live and must schedule
      # its join; t_now is the window start (i64 scalar)
  ready_mask(state)     -> [N] bool           # overlay READY (bootstrappable)
  next_event(state)     -> [N] i64            # earliest local timer/timeout
  step(ctx, state_n, inbox, rng, node_idx, *, outbox_slots, rmax)
      -> (state_n, Outbox, events)            # per-node; vmapped over N

``events`` is a dict stat-name -> (values, mask) pairs consumed by
engine/stats.py.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

I32 = jnp.int32
I64 = jnp.int64
U32 = jnp.uint32
NO_NODE = jnp.int32(-1)
T_INF = jnp.int64(2**62)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Msg:
    """View of one (or a batch of) pool message(s); see engine/pool.py."""

    valid: jnp.ndarray
    t_deliver: jnp.ndarray
    src: jnp.ndarray
    dst: jnp.ndarray
    kind: jnp.ndarray
    key: jnp.ndarray
    nonce: jnp.ndarray
    hops: jnp.ndarray
    a: jnp.ndarray
    b: jnp.ndarray
    c: jnp.ndarray
    d: jnp.ndarray
    nodes: jnp.ndarray
    size_b: jnp.ndarray
    stamp: jnp.ndarray

    def slot(self, r: int) -> "Msg":
        """Select inbox slot r (fields lose their leading R axis)."""
        return jax.tree.map(lambda x: x[r], self)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Ctx:
    """Broadcast tick context available to every node's handlers."""

    t_start: jnp.ndarray      # i64 scalar — window start
    t_end: jnp.ndarray        # i64 scalar — window end (exclusive)
    keys: jnp.ndarray         # [N, KL] u32 — global node-key table (oracle)
    alive: jnp.ndarray        # [N] bool
    ready: jnp.ndarray        # [N] bool — overlay READY at window start
    ready_cumsum: jnp.ndarray  # [N] i32 inclusive cumsum of ready mask
    n_ready: jnp.ndarray      # i32 scalar
    measuring: jnp.ndarray    # bool scalar — inside measurement phase
    glob: object = None       # logic-global read-only state (see LogicBase)
    # graceful-leave grace windows (engine/sim.py step; reference
    # NF_OVERLAY_NODE_LEAVE / NF_OVERLAY_NODE_GRACEFUL_LEAVE):
    leaving: object = None      # [N] bool — pre-killed, still running
    graceful: object = None     # [N] bool — subset doing data handover
    malicious: object = None    # [N] bool — byzantine attacker flags
    # partition support (set only when the underlay defines >1 node type):
    node_type: object = None    # [N] i32
    conn: object = None         # [T, T] bool connectivity matrix
    ready_cum_t: object = None  # [T, N] i32 per-type ready cumsums
    # campaign sweep overrides: {dotted-name: traced scalar} or None.
    # Handlers opt in via ov_get(); absent keys keep the static-param
    # code path so a no-sweep trace stays bit-identical.
    ov: object = None
    # the one due joiner that may start an overlay where no node is READY
    # (i32 scalar, NO_NODE where none is due), built only for a logic
    # that defines ``ring_starter`` (overlay/chord.py)
    starter: object = None

    def ov_get(self, name, default=None):
        """Traced sweep-override lookup (trace-time dict access)."""
        if self.ov is None:
            return default
        return self.ov.get(name, default)

    def sample_ready(self, rng, me=None):
        """Draw a uniformly random READY node slot (-1 if none).

        Oracle bootstrap draw, reference GlobalNodeList::getBootstrapNode
        (GlobalNodeList.h:115) / getRandomNode — O(1) via the per-type
        bootstrapped-peer vectors; here a searchsorted over the cumsum.

        With partitions active and ``me`` given (the drawing node's slot),
        the draw is restricted to node types connected to ``me``'s type
        (the reference's per-type bootstrap vectors + connectionMatrix,
        GlobalNodeList.h:232-235) so a partitioned node never bootstraps
        across the cut.
        """
        if self.conn is not None and me is not None:
            my_type = self.node_type[me]
            allowed = self.conn[my_type]                  # [T]
            counts = self.ready_cum_t[:, -1]              # [T]
            eff = jnp.where(allowed, counts, 0)
            total = jnp.sum(eff)
            k = jax.random.randint(rng, (), 0, jnp.maximum(total, 1),
                                   dtype=I32)
            cum_t = jnp.cumsum(eff)
            tpick = jnp.searchsorted(cum_t, k + 1, side="left").astype(I32)
            tpick = jnp.clip(tpick, 0, counts.shape[0] - 1)
            within = k - jnp.where(tpick > 0, cum_t[jnp.maximum(tpick - 1, 0)],
                                   0)
            idx = jnp.searchsorted(self.ready_cum_t[tpick], within + 1,
                                   side="left").astype(I32)
            return jnp.where(total > 0, idx, NO_NODE)
        k = jax.random.randint(rng, (), 0, jnp.maximum(self.n_ready, 1),
                               dtype=I32)
        idx = jnp.searchsorted(self.ready_cumsum, k + 1, side="left").astype(I32)
        return jnp.where(self.n_ready > 0, idx, NO_NODE)


class LogicBase:
    """Optional base for logic objects: splits state into a vmapped
    per-node part and a simulation-global part.

    The reference has true singletons next to the per-node module stacks
    (GlobalNodeList, GlobalStatistics, GlobalDhtTestMap — SURVEY.md §1).
    Per-node handlers run vmapped and cannot write shared arrays, so
    global state follows a gather/scatter discipline:

      * ``split(state) -> (node_part, glob)``: ``node_part`` is the
        [N, ...] pytree the engine vmaps over; ``glob`` is broadcast
        read-only into every handler as ``ctx.glob``;
      * handlers emit ``"g:name"`` entries in their events dict
        (per-node update requests; ignored by the stats sink);
      * ``post_step(ctx, state, events) -> state`` runs un-vmapped after
        the node sweep and folds those events into the global part.
    """

    def split(self, state):
        return state, None

    def merge(self, node_part, glob):
        return node_part

    def post_step(self, ctx, state, events):
        del ctx, events
        return state


class Outbox:
    """Append-only per-node message emitter used inside vmapped handlers.

    Every ``send`` records the message lazily; ``finish`` materializes
    the whole batch with ONE stack + ONE compacting gather per field.
    A naive implementation scatters ~14 fields per send — with tens of
    send sites unrolled in a handler chain that dominates the tick
    graph's op count (the engine is op-issue-bound, not FLOP-bound).
    Deferring to finish() collapses S sends × 14 scatters into 14
    stack+gather pairs.

    ``en`` picks whether a send occupies a slot; disabled sends cost a
    lane in the stacked batch but no slot.  Slots beyond capacity are
    dropped (the engine counts the overflow).  The reference equivalent
    is the unbounded sendMessageToUDP path (BaseOverlay.cc:1147).

    A send site may be VECTOR-VALUED: pass ``en`` with shape [B] and the
    other fields with shape [B] (or scalar, broadcast) to emit B
    candidate messages from ONE trace-time call.  This is the op-count
    lever: an unrolled loop of B scalar sends costs B×14 graph nodes,
    a single vector send costs 14.
    """

    def __init__(self, m: int, key_lanes: int, rmax: int):
        self.m = m
        self.key_lanes = key_lanes
        self.rmax = rmax
        self._en = []      # list of [B_i] bool
        self._rows = []    # list of per-send field dicts ([B_i, ...] leaves)

    def send(self, en, t_send, dst, kind, *, key=None, nonce=0, hops=0,
             a=0, b=0, c=0, d=0, nodes=None, size_b=40, stamp=0):
        en = jnp.atleast_1d(jnp.asarray(en))
        bdim = en.shape[0]

        def f(v, dt):
            v = jnp.asarray(v, dt)
            if v.ndim == 0:
                v = jnp.broadcast_to(v, (bdim,))
            return v

        if key is not None:
            key = jnp.asarray(key)
            if key.ndim == 1:
                key = jnp.broadcast_to(key, (bdim,) + key.shape)
        if nodes is not None:
            nodes = jnp.asarray(nodes, I32)
            if nodes.ndim == 1:
                nodes = jnp.broadcast_to(nodes, (bdim,) + nodes.shape)
            if nodes.shape[-1] > self.rmax:
                raise ValueError("node-list payload exceeds RMAX")
        self._en.append(en)
        self._rows.append(dict(
            t_send=f(t_send, I64),
            dst=f(dst, I32),
            kind=f(kind, I32),
            key=key, nonce=f(nonce, I32),
            hops=f(hops, I32),
            a=f(a, I32), b=f(b, I32),
            c=f(c, I32), d=f(d, I32),
            nodes=nodes, size_b=f(size_b, I32),
            stamp=f(stamp, I64)))

    @property
    def cursor(self):
        """Number of enabled sends so far (inspection/debug only)."""
        if not self._en:
            return jnp.int32(0)
        return jnp.sum(jnp.concatenate(self._en).astype(I32))

    def finish(self):
        """Returns (fields dict, valid [M], overflow count)."""
        m = self.m
        zero_key = jnp.zeros((self.key_lanes,), U32)
        no_nodes = jnp.full((self.rmax,), NO_NODE, I32)
        s = sum(int(e.shape[0]) for e in self._en)
        if s == 0:
            fields = dict(
                t_send=jnp.zeros((m,), I64), dst=jnp.zeros((m,), I32),
                kind=jnp.zeros((m,), I32),
                key=jnp.zeros((m, self.key_lanes), U32),
                nonce=jnp.zeros((m,), I32), hops=jnp.zeros((m,), I32),
                a=jnp.zeros((m,), I32), b=jnp.zeros((m,), I32),
                c=jnp.zeros((m,), I32), d=jnp.zeros((m,), I32),
                nodes=jnp.full((m, self.rmax), NO_NODE, I32),
                size_b=jnp.zeros((m,), I32), stamp=jnp.zeros((m,), I64))
            return fields, jnp.zeros((m,), bool), jnp.int32(0)

        en = jnp.concatenate([e.astype(I32) for e in self._en])  # [S]
        # slot of send j = number of enabled sends before it
        slots = jnp.cumsum(en) - en                              # [S]
        # compaction: out[i] = the send occupying slot i.  gather form
        # (argsort of disabled-last order) keeps everything one fused
        # sort instead of S scatters
        order_key = jnp.where(en > 0, slots, s)                  # [S]
        # [S] send-slot argsort, NOT a pool-sized sort ([:m] is a no-op
        # when s <= m)
        src = jnp.argsort(order_key)[:m]  # analysis: allow(sort-call)
        n_sent = jnp.sum(en)

        def pick(name, fill, width=None):
            rows = []
            for e, r in zip(self._en, self._rows):
                v = r[name]
                b = int(e.shape[0])
                if name == "key":
                    v = (jnp.broadcast_to(zero_key, (b, self.key_lanes))
                         if v is None else v)
                elif name == "nodes":
                    if v is None:
                        v = jnp.broadcast_to(no_nodes, (b, self.rmax))
                    elif v.shape[-1] < self.rmax:
                        v = jnp.concatenate([
                            v, jnp.full(v.shape[:-1]
                                        + (self.rmax - v.shape[-1],),
                                        NO_NODE, I32)], axis=-1)
                rows.append(v)
            stacked = jnp.concatenate(rows)                      # [S, ...]
            out = stacked[src]                                   # [S'≤M]
            pad = m - out.shape[0]
            if pad > 0:
                fill_row = jnp.broadcast_to(
                    fill, out.shape[1:]) if out.ndim > 1 else fill
                out = jnp.concatenate([
                    out, jnp.broadcast_to(
                        fill_row, (pad,) + out.shape[1:])])
            return out

        fields = dict(
            t_send=pick("t_send", jnp.int64(0)),
            dst=pick("dst", jnp.int32(0)),
            kind=pick("kind", jnp.int32(0)),
            key=pick("key", jnp.uint32(0)),
            nonce=pick("nonce", jnp.int32(0)),
            hops=pick("hops", jnp.int32(0)),
            a=pick("a", jnp.int32(0)), b=pick("b", jnp.int32(0)),
            c=pick("c", jnp.int32(0)), d=pick("d", jnp.int32(0)),
            nodes=pick("nodes", NO_NODE),
            size_b=pick("size_b", jnp.int32(0)),
            stamp=pick("stamp", jnp.int64(0)))
        valid = jnp.arange(m, dtype=I32) < n_sent
        overflow = jnp.maximum(n_sent - m, 0)
        return fields, valid, overflow


def select_tree(pred, a, b):
    """Predicated pytree merge: where(pred, a, b) with pred broadcast up to
    each leaf's rank (the state-merge step after a conditional handler)."""
    def sel(x, y):
        p = pred
        while p.ndim < x.ndim:
            p = p[..., None]
        return jnp.where(p, x, y)
    return jax.tree.map(sel, a, b)
