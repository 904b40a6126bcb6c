"""Global bounded message pool — the TPU replacement for the future-event set.

The reference delivers packets by inserting them into OMNeT++'s
future-event set one at a time (`sendDirect`, SimpleUDP.cc:418).  Here all
in-flight packets live in one structure-of-arrays pool of P slots; each
simulation tick:

  * the due messages (deliver time inside the tick window) are grouped by
    destination into a fixed-width inbox index table: each row holds its
    destination's R earliest due messages by (t_deliver, pool index).
    A steady tick's due messages are a few of the pool's P slots, so
    they are compacted into D = P/32 lanes and ranked by ONE stable
    sort of those D lanes by (dst, t_deliver) and a rank within each
    destination's run; one 32-bit scatter of D updates writes the
    [N, R] table.  A tick with more due messages than lanes takes R
    rounds of deterministic scatter-min selection over all P slots
    (each round one scatter-min on t_deliver over the destination axis
    picks every destination's earliest remaining due message, a second
    on the pool index breaks t_deliver ties exactly like a stable sort,
    and the winners are masked out): the same table, bit for bit, at
    any load, with ZERO full-pool sorts in the tick graph
    (tests/test_engine.py pins sort and scatter counts on the HLO, and
    that the steady branch holds no 64-bit scatter).  The oracle of
    both forms, one lexicographic (dst, t_deliver) full-pool
    ``lax.sort``, lives with the tests (tests/oracles.py), which hold
    all three bit-identical;
  * delivered slots are freed, and the tick's outbox is written into free
    slots with a sort-free cumsum allocation (prefix sum over the free
    mask + one scatter).

Messages that overflow a node's R inbox slots in one window simply stay in
the pool and deliver next tick (receive-queue backpressure).  Pool
exhaustion is counted, never silent (SURVEY.md §7.2 "no silent truncation").

A message carries: src/dst slot, kind, a key, a nonce, hop count, four i32
payload scalars, and a node-list payload of RMAX slot indices (the
FindNodeResponse closest-node set, CommonMessages.msg:246-262, travels as
slot indices — node keys are recoverable from the global key table).

Packed layout (PERFORMANCE.md lever #3): every 32-bit field — the ten
i32 scalars, the key lanes (bitcast u32↔i32) and the RMAX node list —
lives in ONE [P, W] i32 block, so the per-tick inbox build is one gather
and the outbox allocation one scatter, instead of 12+ of each
field-by-field.  Only the two i64 fields (t_deliver, stamp) and the
valid mask stay separate leaves (the allocation's one row scatter
carries them beside the block as 32-bit words, ``write_slots``);
per-field access is provided by zero-copy
column-slice properties, keeping the old field API for host-side readers
(gateway drain, xmlrpcif) and the Msg view builder.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from oversim_tpu.core import lanes as lanes_mod
from oversim_tpu.core.scopes import scope, scoped

I32 = jnp.int32
I64 = jnp.int64
U32 = jnp.uint32
T_INF = jnp.int64(2**62)
NO_NODE = jnp.int32(-1)

# column layout of the packed block: scalars first, then key lanes, then
# the node list
SCAL_COLS = ("src", "dst", "kind", "nonce", "hops", "a", "b", "c", "d",
             "size_b")
_COL = {name: i for i, name in enumerate(SCAL_COLS)}

FIELDS = ("t_deliver", "src", "dst", "kind", "key", "nonce", "hops",
          "a", "b", "c", "d", "nodes", "size_b", "stamp")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MsgPool:
    """Packed pool: [P] masks/times + one [P, W] i32 payload block."""

    valid: jnp.ndarray      # [P] bool
    t_deliver: jnp.ndarray  # [P] i64 ns
    stamp: jnp.ndarray      # [P] i64 ns timestamp payload (send time for
                            # app-latency stats; reference keeps simTime()
                            # in message fields, KBRTestApp.cc)
    blk: jnp.ndarray        # [P, W] i32 — SCAL_COLS + key lanes + nodes
    kl: int = dataclasses.field(metadata=dict(static=True), default=5)
    rmax: int = dataclasses.field(metadata=dict(static=True), default=8)

    @property
    def capacity(self):
        return self.valid.shape[0]

    # -- zero-copy column views (old field API) --------------------------
    @property
    def src(self):
        return self.blk[:, _COL["src"]]

    @property
    def dst(self):
        return self.blk[:, _COL["dst"]]

    @property
    def kind(self):
        return self.blk[:, _COL["kind"]]

    @property
    def nonce(self):
        return self.blk[:, _COL["nonce"]]

    @property
    def hops(self):
        return self.blk[:, _COL["hops"]]

    @property
    def a(self):
        return self.blk[:, _COL["a"]]

    @property
    def b(self):
        return self.blk[:, _COL["b"]]

    @property
    def c(self):
        return self.blk[:, _COL["c"]]

    @property
    def d(self):
        return self.blk[:, _COL["d"]]

    @property
    def size_b(self):
        return self.blk[:, _COL["size_b"]]

    @property
    def key(self):
        s = len(SCAL_COLS)
        return jax.lax.bitcast_convert_type(
            self.blk[..., s:s + self.kl], U32)

    @property
    def nodes(self):
        return self.blk[..., len(SCAL_COLS) + self.kl:]


def pack_block(out: dict, kl: int, rmax: int):
    """Pack a field dict ([Q]-leading arrays, the Outbox.finish() /
    gateway-inject format) into the [Q, W] i32 block."""
    cols = [jnp.asarray(out[name], I32)[:, None] for name in SCAL_COLS]
    cols.append(jax.lax.bitcast_convert_type(
        jnp.asarray(out["key"], U32), I32).reshape(-1, kl))
    cols.append(jnp.asarray(out["nodes"], I32).reshape(-1, rmax))
    return jnp.concatenate(cols, axis=1)


def empty(p: int, key_lanes: int, rmax: int) -> MsgPool:
    w = len(SCAL_COLS) + key_lanes + rmax
    blk = jnp.zeros((p, w), I32)
    blk = blk.at[:, _COL["src"]].set(NO_NODE)
    blk = blk.at[:, _COL["dst"]].set(NO_NODE)
    blk = blk.at[:, len(SCAL_COLS) + key_lanes:].set(NO_NODE)
    return MsgPool(
        valid=jnp.zeros((p,), bool),
        t_deliver=jnp.full((p,), T_INF, I64),
        stamp=jnp.zeros((p,), I64),
        blk=blk,
        kl=key_lanes,
        rmax=rmax,
    )


def next_deliver_time(pool: MsgPool):
    """Earliest pending deliver time (i64; T_INF when pool empty)."""
    return jnp.min(jnp.where(pool.valid, pool.t_deliver, T_INF))


@scoped("pool.due_masks")
def _due_masks(pool: MsgPool, n: int, t_end, alive, hold=None):
    """(due, to_dead) masks of the inbox selection (and of its oracle
    under tests/).

    ``hold`` ([P] bool or None) marks messages that are NEVER due: the
    service/gateway plane parks ``EXT_OUT`` responses in the pool until
    a host drain frees them, instead of having the engine re-deliver
    (and thereby consume) them on the next tick."""
    due = pool.valid & (pool.t_deliver < t_end)
    if hold is not None:
        due = due & ~hold
    to_dead = due & ~alive[jnp.clip(pool.dst, 0, n - 1)]
    return due & ~to_dead, to_dead


def inbox_lanes(p: int) -> int:
    """D — the static lane count of the compacted inbox selection, from
    P alone (as ``Simulation.acap`` is from N): P/32, at least 32.  On
    the chip a scatter costs by its UPDATES and a sort by its lanes, a
    steady tick of the KBR cells has under a hundred due messages among
    8,000 to 131,072 slots, and a tick with more than D takes the
    P-wide rounds, so D moves the cost of a tick and never its result
    (PERF.md, PR 34 and PR 40)."""
    return lanes_mod.rule(p)


def send_lanes(q: int) -> int:
    """K — the static lane count of the compacted closing phase, from
    Q = N x outbox_slots alone (as D is from P and A from N): Q/32, at
    least 32.  On the chip a gather costs by its lanes (7.6 ns a lane
    at Q = 16,000 and 131,072 alike), a steady tick of the KBR cells
    wants under a hundred of its 16,000 to 262,144 outbox slots sent,
    and a tick that wants more than K takes the Q-wide form, so K moves
    the cost of a tick and never its result (PERF.md, PR 38;
    ``engine/sim.py _phase_alloc_stats``)."""
    return lanes_mod.rule(q)


def _lanes(p: int, lanes) -> int:
    return inbox_lanes(p) if lanes is None else min(lanes, p)


def lanes_swept(pool: MsgPool, n: int, t_end, alive, hold=None,
                lanes=None):
    """Candidates a round of :func:`build_inbox` sweeps in this
    tick (i32): ``lanes`` when the due messages fit them, else all P.
    What the engine's ``inbox_lanes`` counter adds; in one program with
    the selection the due mask is computed once (same operands)."""
    p = pool.capacity
    d = _lanes(p, lanes)
    if d >= p:
        return jnp.int32(p)
    due, _ = _due_masks(pool, n, t_end, alive, hold)
    return jnp.where(lanes_mod.fits(due, d), d, p).astype(I32)


@scoped("inbox.rounds")
def _scatter_rounds(tkey, dstc, idx, n: int, r: int, pt: int,
                    axis_name=None):
    """R rounds of deterministic scatter-min over the P pool slots (a
    shard's tile of them under ``axis_name``): ``tkey`` [P] i64 deliver
    time (T_INF = no candidate), ``dstc`` [P] clipped destination,
    ``idx`` [P] GLOBAL pool index.  Returns the [N, R] table and the
    [P] mask of candidates placed in it."""
    cols, taken = [], jnp.zeros(tkey.shape, bool)
    for _ in range(r):
        min_t = jnp.full((n,), T_INF, I64).at[dstc].min(tkey)
        if axis_name is not None:
            min_t = jax.lax.pmin(min_t, axis_name)
        cand = (tkey < T_INF) & (tkey == min_t[dstc])
        win = jnp.full((n,), pt, I32).at[dstc].min(jnp.where(cand, idx, pt))
        if axis_name is not None:
            win = jax.lax.pmin(win, axis_name)
        cols.append(jnp.where(win < pt, win, NO_NODE))
        is_win = cand & (idx == win[dstc])
        taken |= is_win
        tkey = jnp.where(is_win, T_INF, tkey)
    return jnp.stack(cols, axis=1), taken


@scoped("inbox.rank")
def _sort_ranks(tkey, dstc, li, n: int, r: int, p: int):
    """The D compacted lanes ranked by ONE sort: ``tkey`` [D] i64
    deliver time, ``dstc`` [D] clipped destination, ``li`` [D] pool
    index, ASCENDING, ``p`` and more in a lane that holds no message.
    Returns the [N, R] table and the [P] mask of the slots placed in
    it, the bits :func:`_scatter_rounds` returns over the same lanes.

    The lanes ascend by pool index, so a STABLE sort by (dst,
    t_deliver) leaves each destination's run in the rounds' own
    (t_deliver, pool index) order; a lane without a message is keyed
    past every row.  A lane's rank within its run, capped at R, is the
    count of its R predecessors that share its destination (a run is
    contiguous): R shifted comparisons, no ``searchsorted``.  No
    scatter here is 64-bit: on the chip one costs 53 to 134 ns an
    update, and the rounds' 2R of D updates each were a tenth of the
    cells' tick (PERF.md, PR 40)."""
    d = li.shape[0]
    dkey = jnp.where(li < p, dstc, n)
    # t_deliver as its two 32-bit words (signed high, unsigned low):
    # the same order as the i64's
    d_s, _, _, li_s = jax.lax.sort(  # analysis: allow(sort-call)
        (dkey, (tkey >> 32).astype(I32), tkey.astype(U32), li),
        num_keys=3, is_stable=True)
    rank = jnp.zeros((d,), I32)
    for k in range(1, min(r, d - 1) + 1):
        rank += jnp.concatenate(
            [jnp.zeros((k,), bool), d_s[k:] == d_s[:-k]]).astype(I32)
    taken = (d_s < n) & (rank < r)
    inbox = jnp.full((n, r), NO_NODE, I32).at[
        jnp.where(taken, d_s, n), jnp.minimum(rank, r - 1)].set(
        li_s, mode="drop")
    delivered = jnp.zeros((p,), bool).at[
        jnp.where(taken, li_s, p)].set(True, mode="drop")
    return inbox, delivered


def build_inbox(pool: MsgPool, n: int, r: int, t_end, alive,
                        hold=None, *, lanes=None, axis_name=None, base=0,
                        p_total=None):
    """Group due messages by destination into an index table: each
    row its destination's R earliest due messages by (t_deliver, pool
    index), found over the tick's DUE messages compacted into D lanes
    by one sort of those lanes, and by R rounds of deterministic
    scatter-min over all P slots in a tick that overruns them.

    A scatter costs by its updates and a sort by its lanes, and a tick's
    due messages are a few of the pool's P slots: so the due slots' pool
    indices are compacted, ascending, into ``lanes`` static lanes (None:
    :func:`inbox_lanes`, a rule of P alone), :func:`_sort_ranks` ranks
    them (one stable D-lane sort by (dst, t_deliver), one 32-bit scatter
    of D updates into the [N, R] table), and ``delivered`` is written
    back at full width by one more: O(P) elementwise work, no full-pool
    sort and no 64-bit scatter.  A tick with more due messages than
    lanes (a fill, a saturated mix, flooding) takes the P-wide rounds
    (:func:`_scatter_rounds`) through a ``lax.cond``: round k
    scatter-mins t_deliver over the destination axis to find each row's
    earliest remaining due message, then scatter-mins the POOL INDEX
    over the messages matching that minimum — a stable sort's exact
    (t_deliver, idx) tie-break — and masks the winners out; 2R [P]→[N]
    scatters and O(R·P) work, for the same answer: exact at any load,
    nothing deferred that the P-wide rounds would deliver.  Both forms
    are bit-identical to the full-pool sort oracle (tests/oracles.py
    ``build_inbox_sort``; pinned by the identity tests in
    tests/test_engine.py).  ``hold`` ([P] bool) excludes messages from
    delivery entirely — see :func:`_due_masks`.  ``lanes >= P`` is the
    P-wide rounds alone (what a caller that vmaps the step wants: under
    vmap a cond runs both branches).

    Under explicit node sharding (parallel/shard_tick.py) ``pool`` is
    one shard's contiguous tile: pass the shard_map ``axis_name``, the
    tile's ``base`` pool offset and the global ``p_total``.  Each round's
    two scatter-mins then run on the LOCAL tile, P-wide, and merge across
    shards with ``lax.pmin`` — the local-select + all-reduce:min form
    the rounds were designed for.  The per-round global minimum over
    (t_deliver, pool index) is the min of the per-shard minima, so the
    sharded table is bit-identical to the solo one; ``delivered`` /
    ``to_dead`` come back tile-local.

    Returns:
      inbox: [N, R] i32 pool indices, -1 for empty slots, ordered by
             (deliver time, pool index) within each row.
      delivered: [P] bool — messages placed into the inbox this tick.
      dropped_dead: [P] bool — messages due for a dead node (freed, counted;
             reference drops these as "dest unavailable", SimpleUDP.cc:307).
    """
    p = pool.capacity
    pt = p if p_total is None else p_total
    due, to_dead = _due_masks(pool, n, t_end, alive, hold)

    dstc = jnp.clip(pool.dst, 0, n - 1)

    def wide(_):
        # remaining-candidate key; winners flip to T_INF between rounds
        return _scatter_rounds(jnp.where(due, pool.t_deliver, T_INF), dstc,
                               base + jnp.arange(p, dtype=I32), n, r, pt,
                               axis_name)

    d = _lanes(p, lanes)
    if axis_name is not None or d >= p:
        inbox, delivered = wide(None)
        return inbox, delivered, to_dead

    def compacted(_):
        # lane j holds the pool index of the (j+1)-th due slot (p past
        # the last)
        with scope("inbox.compact"):
            li = lanes_mod.compact(due, d)
            lic = jnp.minimum(li, p - 1)
            t_l, dst_l = pool.t_deliver[lic], dstc[lic]
        return _sort_ranks(t_l, dst_l, li, n, r, p)

    with scope("inbox.compact"):
        fit = lanes_mod.fits(due, d)
    inbox, delivered = jax.lax.cond(fit, compacted, wide, None)
    return inbox, delivered, to_dead


@scoped("pool.free")
def free(pool: MsgPool, mask) -> MsgPool:
    return dataclasses.replace(
        pool,
        valid=pool.valid & ~mask,
        t_deliver=jnp.where(mask, T_INF, pool.t_deliver))


def _words(x):
    """[., 2] i32: the two 32-bit words of an i64 array, the same bits."""
    return jax.lax.bitcast_convert_type(jnp.asarray(x, I64), I32)


def write_slots(pool: MsgPool, dest, out: dict) -> MsgPool:
    """Write the messages ``out`` ([Q]-leading field arrays) into the
    slots ``dest`` ([Q] i32; an index of P or more writes nothing).

    ONE row scatter, and no 64-bit one: on the chip a scatter into an
    i64 operand costs 53 to 112 ns an UPDATE, dropped ones too (the two
    fields' 16 N updates were 11.7 of a 28.9 ms tick at N=4096), and
    every further scatter of Q updates 0.4 ms, where five more columns
    of the packed block's row scatter cost nothing that shows (PERF.md,
    PR 36).  So ``t_deliver`` and ``stamp`` ride it as two i32 words
    each and ``valid`` as one, [Q, W+5] rows into [P, W+5], and are
    split off again: plain copies over P and Q words, every leaf the
    same bits as field-by-field writes."""
    w = pool.blk.shape[1]
    q = dest.shape[0]
    rows = jnp.concatenate(
        [pack_block(out, pool.kl, pool.rmax), _words(out["t_deliver"]),
         _words(out["stamp"]), jnp.ones((q, 1), I32)], axis=1)
    wide = jnp.concatenate(
        [pool.blk, _words(pool.t_deliver), _words(pool.stamp),
         pool.valid.astype(I32)[:, None]],
        axis=1).at[dest].set(rows, mode="drop")
    return dataclasses.replace(
        pool,
        blk=wide[:, :w],
        t_deliver=jax.lax.bitcast_convert_type(wide[:, w:w + 2], I64),
        stamp=jax.lax.bitcast_convert_type(wide[:, w + 2:w + 4], I64),
        valid=wide[:, w + 4] != 0)


@scoped("pool.alloc")
def alloc(pool: MsgPool, out: dict, want):
    """Write the tick's outbox into free pool slots — SORT-FREE.

    ``out`` maps field name -> [L, ...] arrays of messages in outbox
    order and ``want`` is [L] bool: all Q = N x M flattened outbox
    slots, or the K lanes the closing phase compacted the tick's wanted
    slots into, ascending (``engine/sim.py _phase_alloc_stats``: the
    ranking below is then a K-wide running sum, ``fslot[want_rank]`` a
    K-lane gather and the row scatter K rows, where each cost by Q; the
    free slots' ranking stays P-wide, plain passes and one 32-bit
    scatter).  Same slots either way: a lane that holds no message
    wants nothing.  Returns (pool', overflow_count).

    The j-th wanted message goes to the j-th free slot (both in index
    order), exactly as the old two-`lax.sort` allocator did, but the
    mapping is built from two prefix sums plus ONE tiny [P] i32 scatter
    (the compacted free-slot list) — O(P) work instead of two
    O(P log P) full-pool sorts, the dominant per-tick cost at P = 8N.
    The payload write is ``write_slots``: ONE row scatter of the packed
    block with the two i64 fields and the valid mask beside it as
    32-bit words.
    """
    p = pool.capacity
    n_want = jnp.sum(want.astype(I32))
    free = ~pool.valid
    n_free = jnp.sum(free.astype(I32))

    # rank of each free slot among free slots / of each wanted
    # message among wanted messages (exclusive prefix sums)
    free_i = free.astype(I32)
    free_rank = jnp.cumsum(free_i) - free_i            # [P]
    want_i = want.astype(I32)
    want_rank = jnp.cumsum(want_i) - want_i            # [Q]

    # compact free-slot list: fslot[j] = index of the j-th free slot
    # (p elsewhere, which scatters/reads as "dropped")
    fslot = jnp.full((p,), p, I32).at[
        jnp.where(free, free_rank, p)].set(
        jnp.arange(p, dtype=I32), mode="drop")
    # destination slot per outbox message; p (out of bounds,
    # dropped) for unwanted messages and for wanted ones past the
    # free supply
    dest = jnp.where(want & (want_rank < n_free),
                     fslot[jnp.minimum(want_rank, p - 1)], p)
    overflow = jnp.maximum(n_want - n_free, 0)

    return write_slots(pool, dest, out), overflow
