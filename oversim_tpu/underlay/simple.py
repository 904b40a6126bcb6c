"""Analytic underlay network model (vectorized SimpleUnderlay).

TPU-native equivalent of the reference's SimpleUnderlay
(src/underlay/simpleunderlay/): no packet-level simulation — every node has
an N-dim coordinate and per-direction channel parameters, and the
end-to-end delay of a packet is computed analytically:

    delay = send-queue carry + tx bandwidth delay + tx access delay
          + 0.001 * euclidean(coords_src, coords_dst)
          + rx bandwidth delay + rx access delay
          (+ positive half-normal jitter with sigma = jitter * delay)

mirroring SimpleNodeEntry::calcDelay (SimpleNodeEntry.cc:155-195, the
0.001 s/coord-unit constant at :186) and SimpleUDP::processMsgFromApp
(SimpleUDP.cc:274-434: self-sends bypass the delay model, dest-unavailable
and partition drops, jitter workaround loop).  Drops: send-queue overrun
(calcDelay :169-180), bit errors from channel error rate, destination dead,
node-type partition (GlobalNodeList::areNodeTypesConnected).

All of it is computed for a whole ``[N, MOUT]`` outbox batch at once; the
per-sender transmit-queue serialization (``tx.finished`` carry) becomes a
cumulative sum along the outbox axis.  In two stages: the sender's
(``send_tx``: the queue model and the random draws, [N, MOUT] wide,
indexed by the sender's own row) and the receiver's (``send_rx``:
whatever is indexed by the destination, over a vector of messages: all
N x MOUT slots for ``send_batch``, the engine's K lanes of wanted slots in
a steady tick).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from oversim_tpu.core import lanes as lanes_mod

I64 = jnp.int64
F32 = jnp.float32
NS = 1_000_000_000  # ns per second
T_MAX = jnp.int64(2**62)

# Channel catalogue (reference: src/common/channels.ned:3-34).
# columns: bandwidth bit/s, access delay s, bit error rate
CHANNELS = {
    "simple_ethernetline": (10e6, 0.0, 0.0),
    "simple_ethernetline_lossy": (10e6, 0.0, 1e-5),
    "simple_dsl": (1e6, 0.020, 0.0),
    "simple_dsl_lossy": (1e6, 0.020, 1e-5),
}


@dataclasses.dataclass(frozen=True)
class UnderlayParams:
    """Static SimpleUnderlay configuration (simulations/default.ini:545-563)."""

    dims: int = 2
    field_size: float = 150.0          # default.ini:552
    # node-coordinate XML pool (nodeCoordinateSource, default.ini:555:
    # PlanetLab-derived positions instead of uniform draws; parsed by
    # native/coordpool.c).  Empty = uniform random in the field.
    coord_source: str = ""
    coord_delay_per_unit: float = 0.001  # s per coord unit, SimpleNodeEntry.cc:186
    use_coordinate_based_delay: bool = True  # default.ini:547
    constant_delay: float = 0.050      # fallback, default.ini:545
    jitter: float = 0.1                # default.ini:549
    send_queue_bytes: int = 1_000_000  # default.ini:553 "1MB"
    channel_types: tuple = ("simple_ethernetline",)
    header_bytes: int = 28             # UDP(8) + IP(20), SimpleUDP.cc:291
    # --- node-type partitions (GlobalNodeList connectionMatrix,
    # GlobalNodeList.h:232-235 + SimpleUDP.cc:349-358 partition drop;
    # driven by CONNECT/DISCONNECT_NODETYPES trace events,
    # simulations/partition.trace) ---
    # --- PlanetLab delay-fault model (delayFaultType, SimpleUDP.cc:
    # 126-141; SimpleNodeEntry::getFaultyDelay :197-254): inject
    # triangle-inequality-violating delay errors with ratios drawn from
    # the Kumaraswamy fits of "Network Coordinates in the Wild" Fig. 7.
    # ""|"live_all"|"live_planetlab"|"simulation".  The error is a
    # DETERMINISTIC hash of the un-faulted delay (the reference hashes
    # the delay string) so a given pair distance always gets the same
    # distortion — stable violations, not jitter.
    delay_fault_type: str = ""
    # --- SimpleTCP / BaseTcpSupport (src/underlay/simpleunderlay/
    # SimpleTCP.{h,cc}, src/common/BaseTcpSupport.{h,cc}): message kinds
    # listed here ride a simulated TCP stream to their destination —
    # reliable (a bit error retransmits, adding one RTO-scaled delay,
    # instead of dropping) and connection-oriented (first contact with a
    # peer outside the open-connection cache pays a SYN/SYN-ACK/ACK
    # handshake of 1.5 one-way delays, ExtTCPSocketMap connection
    # reuse).  Empty = everything is UDP, zero state/graph cost.
    tcp_kinds: tuple = ()
    tcp_connection_cache: int = 8     # open connections kept per node
    num_node_types: int = 1
    # slots < type_boundaries[0] are type 0, < [1] type 1, ...; the last
    # type takes the rest (multiple ChurnGenerators = one type each,
    # ChurnGenerator.h:42-50)
    type_boundaries: tuple = ()
    # static schedule: (time_s, type_a, type_b, connect) — applied in
    # order; the matrix starts fully connected
    partition_events: tuple = ()

    @property
    def channel_table(self):
        """[C, 3] float32 table of (bandwidth, access_delay, ber)."""
        rows = [CHANNELS[c] for c in self.channel_types]
        return jnp.asarray(rows, dtype=F32)


def node_types(n: int, p: UnderlayParams) -> jnp.ndarray:
    """[N] i32 node type per slot from the static boundaries."""
    t = jnp.zeros((n,), jnp.int32)
    for b in p.type_boundaries:
        t = t + (jnp.arange(n) >= b).astype(jnp.int32)
    return jnp.clip(t, 0, p.num_node_types - 1)


def connection_matrix(p: UnderlayParams, t_now) -> jnp.ndarray:
    """[T, T] bool connectivity at simulated time ``t_now`` (ns scalar),
    replayed from the static partition schedule each tick (the reference
    mutates GlobalNodeList::connectionMatrix via trace commands).

    Events are ONE-directional like the reference's connect/
    disconnectNodeTypes (GlobalNodeList.cc; simulations/partition.trace
    issues both directions explicitly) — a full split needs (a,b) and
    (b,a) events."""
    t = p.num_node_types
    conn = jnp.ones((t, t), bool)
    for (ts, a, b, connect) in p.partition_events:
        en = jnp.int64(int(ts * NS)) <= t_now
        conn = conn.at[a, b].set(jnp.where(en, bool(connect), conn[a, b]))
    return conn


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class UnderlayState:
    """Per-node underlay state, all arrays [N, ...]."""

    coords: jnp.ndarray       # [N, D] f32
    channel: jnp.ndarray      # [N] i32 index into channel_table
    tx_finished: jnp.ndarray  # [N] i64 ns — when the send queue drains
    node_type: jnp.ndarray    # [N] i32 — churn-generator/partition type
    tcp_conn: jnp.ndarray     # [N, Ct] i32 — open-connection peer cache
                              # (SimpleTCP/BaseTcpSupport, zero-width
                              # when no tcp_kinds are configured)


_POOL_CACHE: dict = {}


def _coord_pool(p: UnderlayParams):
    """[P, D] device constant from the XML pool (trace-time cached)."""
    if p.coord_source not in _POOL_CACHE:
        from oversim_tpu import native as native_mod
        arr = native_mod.load_coord_pool(p.coord_source)
        _POOL_CACHE[p.coord_source] = jnp.asarray(
            arr[:, :p.dims], dtype=F32)
    return _POOL_CACHE[p.coord_source]


def _draw_coords(rng, n: int, p: UnderlayParams):
    if p.coord_source:
        pool = _coord_pool(p)
        idx = jax.random.randint(rng, (n,), 0, pool.shape[0])
        return pool[idx]
    return jax.random.uniform(
        rng, (n, p.dims), dtype=F32, minval=0.0, maxval=p.field_size)


def init(rng: jax.Array, n: int, p: UnderlayParams) -> UnderlayState:
    """Coordinates from the XML pool (or uniform in the field), random
    channel type per node (reference: SimpleUnderlayConfigurator.cc:143-184
    draws coords from the pool and the channel type uniformly from
    churnGenerator channelTypes)."""
    ck, xk = jax.random.split(rng)
    coords = _draw_coords(xk, n, p)
    channel = jax.random.randint(ck, (n,), 0, len(p.channel_types), dtype=jnp.int32)
    ct = p.tcp_connection_cache if p.tcp_kinds else 0
    return UnderlayState(coords=coords, channel=channel,
                         tx_finished=jnp.zeros((n,), dtype=I64),
                         node_type=node_types(n, p),
                         tcp_conn=jnp.full((n, ct), -1, jnp.int32))


def migrate(state: UnderlayState, mask, rng, p: UnderlayParams) -> UnderlayState:
    """Redraw coordinates for masked nodes (node create / IP migration;
    reference SimpleUnderlayConfigurator::migrateNode)."""
    n = state.coords.shape[0]
    new_coords = _draw_coords(rng, n, p)
    coords = jnp.where(mask[:, None], new_coords, state.coords)
    tx_finished = jnp.where(mask, jnp.int64(0), state.tx_finished)
    if state.tcp_conn.shape[1]:
        # connections die with either endpoint (ExtTCPSocketMap): clear
        # the migrated node's own row AND every stale entry pointing at
        # the recycled slot in other nodes' caches
        stale_to = mask[jnp.clip(state.tcp_conn, 0, n - 1)] & (
            state.tcp_conn >= 0)
        state = dataclasses.replace(
            state, tcp_conn=jnp.where(mask[:, None] | stale_to, -1,
                                      state.tcp_conn))
    return dataclasses.replace(state, coords=coords,
                               tx_finished=tx_finished)


def send_tx(state: UnderlayState, p: UnderlayParams, rng,
            src, dst, size_bytes, t_send, want, kind=None):
    """The SENDER's stage of :func:`send_batch`, [N, M] wide: the
    transmit-queue model and everything else that is indexed by the
    sender's own row or by the outbox slot, which needs no gather and
    costs a few elementwise passes.  The two random draws stay AT THEIR
    SLOTS here, so a message meets the same draw whichever lanes the
    receiver's stage runs over.

    Returns ``(tx, new_state)``: ``tx`` a dict of per-slot arrays
    ([N, M, ...]; flattened to [Q, ...] or read at K lanes, it is what
    :func:`send_rx` takes), ``new_state`` with the queues drained
    (``tx_finished``)."""
    n, m = src.shape
    tbl = p.channel_table
    bits = (size_bytes + p.header_bytes) * 8

    chan = tbl[state.channel]                        # [N,3] sender's channel
    tx_bw = chan[:, 0][:, None]                      # [N,1] sender bandwidth
    queued = want & (src != dst)

    # --- sender transmit queue (SimpleNodeEntry.cc:163-181) ---
    # Serialize this tick's messages through the sender's queue in outbox
    # order: finish_j = max(tx_finished, t_send_j) + cumsum(bw_delay).
    bw_delay_ns = jnp.where(queued, (bits.astype(F32) / tx_bw * NS), 0.0).astype(I64)
    # start of service for each message: queue may already be busy
    start0 = jnp.maximum(state.tx_finished[:, None], t_send)
    # cumulative: each message waits for all previous *sent* messages this tick
    cum = jnp.cumsum(bw_delay_ns, axis=1)
    finish = start0 + cum  # monotone approx: uses first msg's start for all
    # queue bound in bytes per the sender's own channel bandwidth
    # (SimpleNodeEntry.cc:169-180: maxQueueTime = queueBytes*8/bandwidth)
    max_queue_ns = (jnp.float32(p.send_queue_bytes * 8) / tx_bw * NS).astype(I64)
    overrun = queued & (finish - t_send > max_queue_ns)
    new_tx_finished = jnp.where(
        jnp.any(queued & ~overrun, axis=1),
        jnp.max(jnp.where(queued & ~overrun, finish, 0), axis=1),
        state.tx_finished)

    def slots(x):                       # a sender's value at its M slots
        return jnp.broadcast_to(x[:, None], (n, m) + x.shape[1:])

    tx = dict(
        src=src, dst=dst, bits=bits, t_send=t_send, want=want,
        overrun=overrun, queue_ns=finish - t_send,
        tx_access=slots(chan[:, 1]), tx_ber=slots(chan[:, 2]),
        tx_coords=slots(state.coords),
        u=jax.random.uniform(jax.random.fold_in(rng, 1), (n, m), dtype=F32))
    # --- jitter: positive half-normal (SimpleUDP.cc:360-373) ---
    if p.jitter > 0:
        tx["jit"] = jnp.abs(jax.random.normal(rng, (n, m), dtype=F32))
    # --- SimpleTCP: the probe of the sender's own open-connection cache
    if p.tcp_kinds and kind is not None:
        is_tcp = jnp.zeros((n, m), bool)
        for k in p.tcp_kinds:
            is_tcp = is_tcp | (kind == k)
        ct = p.tcp_connection_cache
        tx["is_tcp"] = is_tcp & queued
        tx["row"] = slots(jnp.arange(n, dtype=jnp.int32))
        tx["open_hit"] = state.tcp_conn[
            tx["row"], jnp.clip(dst % ct, 0, ct - 1)] == dst
    # --- node-type partitions: the sender's row of the connection
    # matrix, as it stands at the tick's first send
    if p.partition_events:
        conn = connection_matrix(p, jnp.min(jnp.where(want, t_send, T_MAX)))
        tx["conn_row"] = conn[state.node_type[src]]              # [N, M, T]
    return tx, dataclasses.replace(state, tx_finished=new_tx_finished)


def send_rx(state: UnderlayState, p: UnderlayParams, tx: dict, alive):
    """The RECEIVER's stage of :func:`send_batch`: everything that is
    indexed by a message's destination (its liveness, its channel, its
    coordinates, its node type), the delay that follows from them and
    the drop decisions, over a VECTOR of messages: ``tx`` is
    :func:`send_tx`'s dict with one leading axis [L, ...], all Q = N x M
    outbox slots or the K lanes the engine compacted the tick's wanted
    slots into (``engine/sim.py _phase_alloc_stats``; a lane that holds
    no message has ``want`` false).  One body of logic for both widths:
    the same elementwise arithmetic on the same operands at another
    position, so every deliver time is the same to the bit.  The
    receiver's values come through ONE row gather (``lanes.take``): a
    gather costs by its lanes, not by its row's width.

    Returns ``(t_deliver [L] i64, ok [L] bool, new_state, drops)``."""
    dst, want, bits, t_send = tx["dst"], tx["want"], tx["bits"], tx["t_send"]
    self_send = tx["src"] == dst
    queued = want & ~self_send
    tx_access, tx_ber = tx["tx_access"], tx["tx_ber"]
    rx_alive, rx_chan, rx_coords, rx_type = lanes_mod.take(
        (alive, p.channel_table[state.channel], state.coords,
         state.node_type), dst)
    rx_bw, rx_access, rx_ber = rx_chan[:, 0], rx_chan[:, 1], rx_chan[:, 2]

    # --- propagation: coordinate distance (SimpleNodeEntry.cc:144-152) ---
    d = tx["tx_coords"] - rx_coords                           # [L, D]
    dist = jnp.sqrt(jnp.sum(d * d, axis=-1))
    coord_delay = p.coord_delay_per_unit * dist

    rx_delay = bits.astype(F32) / rx_bw

    if p.use_coordinate_based_delay:
        total_ns = tx["queue_ns"] + (
            (tx_access + coord_delay + rx_delay + rx_access) * NS).astype(I64)
    else:
        total_ns = jnp.full(dst.shape, jnp.int64(p.constant_delay * NS))

    # --- PlanetLab delay faults (getFaultyDelay, SimpleNodeEntry.cc:
    # 197-254): errorRatio = Kumaraswamy⁻¹(hash(delay)) + shift, sign
    # from hash parity, negative ratios clamped at 0.6.  splitmix64
    # replaces the reference's SHA1-of-delay-string as the
    # deterministic delay→uniform hash (same role, integer-native).
    if p.delay_fault_type:
        a_b_shift = {"live_all": (2.03, 14.0, 0.04),
                     "live_planetlab": (1.95, 50.0, 0.105),
                     "simulation": (1.96, 23.0, 0.02)}[p.delay_fault_type]
        ka, kb, kshift = a_b_shift
        # hash the PAIR-STABLE propagation delay (coordinate distance),
        # not the full per-message delay — queue wait and serialization
        # vary per packet and would turn the stable triangle violations
        # into jitter; the ratio then distorts that propagation term
        prop_ns = (coord_delay * NS).astype(I64)
        h = prop_ns.astype(jnp.uint64)
        h = (h ^ (h >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
        h = (h ^ (h >> 27)) * jnp.uint64(0x94D049BB133111EB)
        h = h ^ (h >> 31)
        frac = (h >> 40).astype(F32) / jnp.float32(1 << 24)
        ratio = (1.0 - frac ** (1.0 / kb)) ** (1.0 / ka) + kshift
        neg = (h & 1) == 1
        ratio = jnp.where(neg, -jnp.minimum(ratio, 0.6), ratio)
        total_ns = total_ns + (ratio * prop_ns.astype(F32)).astype(I64)

    # --- SimpleTCP (tcp_kinds; SimpleTCP.cc / BaseTcpSupport):
    # direct-mapped open-connection cache — a first contact pays the
    # SYN/SYN-ACK/ACK handshake (1.5 one-way delays); a collision
    # evicts the older connection (ExtTCPSocketMap reuse semantics,
    # bounded state)
    tcp = "is_tcp" in tx
    if tcp:
        is_tcp = tx["is_tcp"] & queued
        handshake = is_tcp & ~tx["open_hit"]
        one_way_ns = ((tx_access + coord_delay + rx_access) * NS).astype(I64)
        total_ns = total_ns + jnp.where(handshake,
                                        (one_way_ns * 3) // 2,
                                        jnp.int64(0))
        # cache write deferred until the drop decisions are known — a
        # handshake on a message lost to a partition cut / dead peer /
        # queue overrun establishes nothing

    # --- jitter: positive half-normal, sigma = jitter * delay
    # (SimpleUDP.cc:360-373 truncnormal(0, delay*jitter)) ---
    if p.jitter > 0:
        total_ns = total_ns + (
            tx["jit"] * p.jitter * total_ns.astype(F32)).astype(I64)

    # --- drops ---
    bit_err_p = 1.0 - (1.0 - tx_ber) ** bits * (1.0 - rx_ber) ** bits
    bit_error = queued & (tx["u"] < bit_err_p)
    # TCP retransmits instead of losing the segment: one RTO-scaled
    # extra delay (doubled transfer time), no drop
    if tcp:
        retrans = bit_error & is_tcp
        total_ns = total_ns + jnp.where(retrans, total_ns, jnp.int64(0))
        bit_error = bit_error & ~is_tcp
    overrun = tx["overrun"] & want
    dest_dead = want & ~rx_alive

    # node-type partition drop (SimpleUDP.cc:349-358:
    # !areNodeTypesConnected(src, dst) → numPartitionLost)
    if "conn_row" in tx:
        part_cut = want & ~jnp.take_along_axis(
            tx["conn_row"], rx_type[:, None], axis=1)[:, 0]
    else:
        part_cut = jnp.zeros_like(want)

    ok = want & ~overrun & ~bit_error & ~dest_dead & ~part_cut
    t_deliver = jnp.where(self_send, t_send, t_send + total_ns)

    if tcp:
        n, ct = state.tcp_conn.shape
        new_conn = state.tcp_conn.at[
            jnp.where(handshake & ok, tx["row"], n),
            jnp.clip(dst % ct, 0, ct - 1)].set(dst, mode="drop")
        state = dataclasses.replace(state, tcp_conn=new_conn)

    drops = {
        "queue_lost": jnp.sum(overrun),
        "bit_error_lost": jnp.sum(bit_error),
        "dest_unavailable_lost": jnp.sum(dest_dead),
        "partition_lost": jnp.sum(part_cut),
    }
    return t_deliver, ok, state, drops


@partial(jax.jit, static_argnames=("p",))
def send_batch(state: UnderlayState, p: UnderlayParams, rng,
               src, dst, size_bytes, t_send, want, alive, kind=None):
    """Compute deliver times and drop decisions for an outbox batch.

    Args:
      src, dst: [N, M] i32 sender/receiver slots (src row i is node i).
      size_bytes: [N, M] i32 payload bytes (headers added here).
      t_send: [N, M] i64 ns logical send times.
      want: [N, M] bool — slot actually carries a message.
      alive: [N] bool.

    Returns (t_deliver [N,M] i64, ok [N,M] bool, new_state, drop_stats dict).
    Messages with ok=False are dropped (queue overrun / bit error / dest
    dead); t_deliver for self-sends is t_send (SimpleUDP.cc:322 skips the
    delay model when srcAddr == destAddr).

    Two stages, one body of logic each: :func:`send_tx` (the sender's
    queue model and the random draws, [N, M] wide: it indexes by the
    sender's own row and needs no gather) and :func:`send_rx` (whatever
    is indexed by the RECEIVER, over a vector of messages).  This entry
    runs the receiver's stage over all Q = N x M slots; the engine's
    closing phase runs it over the K lanes that hold the tick's wanted
    slots and comes here, in effect, only in a tick that wants more than
    K (``engine/sim.py _phase_alloc_stats``).  Same answers either way.
    """
    n, m = src.shape
    tx, state = send_tx(state, p, rng, src, dst, size_bytes, t_send, want,
                        kind)
    t_deliver, ok, state, drops = send_rx(
        state, p, {k: v.reshape((n * m,) + v.shape[2:])
                   for k, v in tx.items()}, alive)
    return t_deliver.reshape(n, m), ok.reshape(n, m), state, drops
