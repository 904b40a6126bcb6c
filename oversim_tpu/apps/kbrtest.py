"""KBRTestApp — the reference's benchmark workload, vectorized.

Rebuild of src/applications/kbrtestapp/KBRTestApp.{h,cc}: three periodic
tests (KBRTestApp.cc:131-216), each drawing its destination key from a
random live node's nodeId (lookupNodeIds=true, default.ini:40;
KBRTestApp::createDestKey):

  * **one-way test** (testMsgInterval=60s, default.ini:38): route a test
    payload to the key; the receiver checks it is actually responsible
    and records delivery, hop count and latency; wrong-node deliveries
    count as failures (KBRTestApp.cc:252-292).  Delivery ratio =
    delivered/sent is THE headline KPI (GlobalStatistics
    sentKBRTestAppMessages/deliveredKBRTestAppMessages,
    GlobalStatistics.h:79-80);
  * **routed-RPC test** (kbrRpcTest): KbrTestCall routed to the key, the
    responsible node responds directly; success ratio + RTT recorded
    (handleRpcResponse KBRTestApp.cc:237-292).  An unanswered call is
    failed when the next RPC fires (single outstanding call per node);
  * **lookup test** (kbrLookupTest): resolve the key to its sibling set
    and validate against the global oracle — since the key IS a live
    node's nodeId, the lookup succeeds iff the first returned sibling is
    that (still-alive) node (handleLookupResponse KBRTestApp.cc:331+,
    lookupNodeIds oracle check).

Engine mapping (documented deviation): the reference runs three
independent timers with the same interval; here one timer round-robins
the enabled modes at interval/len(modes), preserving each mode's rate
while keeping the one-lookup-per-timer app interface (apps/base.py).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from oversim_tpu.apps import base
from oversim_tpu.common import wire
from oversim_tpu.core.scopes import scoped

I32 = jnp.int32
I64 = jnp.int64
NS = 1_000_000_000
T_INF = jnp.int64(2**62)
NO_NODE = jnp.int32(-1)
ANY_NODE = jnp.int32(-2)   # rpc_dst wildcard: recursive routed call — the
                           # responder is unknown until the response lands
                           # (reference BaseRpc matches by nonce, not node)

# test modes (tag low bits)
M_ONEWAY, M_RPC, M_LOOKUP = 0, 1, 2


@dataclasses.dataclass(frozen=True)
class KbrTestParams:
    test_interval: float = 60.0     # testMsgInterval, default.ini:38
    test_msg_bytes: int = 100       # testMsgSize, default.ini:37
    hop_hist_bins: int = 16
    oneway_test: bool = True        # kbrOneWayTest
    rpc_test: bool = False          # kbrRpcTest
    lookup_test: bool = False       # kbrLookupTest
    rpc_timeout: float = 10.0       # rpcKeyTimeout, default.ini:485
    msg_handle_buf: int = 8         # msgHandleBufSize, default.ini:39

    @property
    def modes(self) -> tuple:
        out = []
        if self.oneway_test:
            out.append(M_ONEWAY)
        if self.rpc_test:
            out.append(M_RPC)
        if self.lookup_test:
            out.append(M_LOOKUP)
        return tuple(out) or (M_ONEWAY,)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KbrTestState:
    t_test: jnp.ndarray   # [N] i64 — next test fire
    seq: jnp.ndarray      # [N] i32 — sequence number
    rpc_dst: jnp.ndarray  # [N] i32 — outstanding routed-RPC responder
    rpc_to: jnp.ndarray   # [N] i64 — its timeout
    rpc_t0: jnp.ndarray   # [N] i64 — its start (RTT base)
    rpc_nonce: jnp.ndarray  # [N] i32 — call nonce (stale-response guard)
    # circular (src, seqTag) duplicate filter (KBRTestApp::checkSeen,
    # KBRTestApp.cc:458-476, msgHandleBufSize ring).  Width 0 when the
    # overlay routes iteratively — the pool delivers exactly once there,
    # duplicates only arise from the recursive ACK/reroute path.
    seen_src: jnp.ndarray   # [N, B] i32
    seen_seq: jnp.ndarray   # [N, B] i32
    seen_ptr: jnp.ndarray   # [N] i32


class KbrTestApp:
    """Tier-1 app object (interface: apps/base.py docstring).

    ``rcfg`` is set by a recursive-routing overlay (common/route.py
    RouteConfig): RPC replies then travel in the transport the routing
    mode dictates (rt_mod.reply) instead of direct UDP, mirroring
    BaseRpc's routingType-driven response transport."""

    # with no message and neither timer due (``next_event``) the app
    # leaves its state as it is: an overlay that is itself exact under
    # the engine's awake-set plane stays so with this app on top
    awake_set_exact = True

    def __init__(self, params: KbrTestParams = KbrTestParams(), rcfg=None):
        self.p = params
        self.rcfg = rcfg

    @property
    def buf(self) -> int:
        """Dedup-ring width: active only under recursive routing (the
        iterative pool delivers exactly once).  A property, not frozen at
        construction — overlays patch ``app.rcfg`` after constructing the
        default app (chord.py/kademlia.py ``self.app.rcfg = rcfg``),
        before ``init`` sizes the state arrays."""
        return self.p.msg_handle_buf if self.rcfg is not None else 0

    def route_policy(self, tag):
        """Which of this app's lookup requests a recursive overlay may
        route as data instead (returns (routable, inner_kind, is_rpc)).
        One-way and routed-RPC test payloads route; the lookup test needs
        a sibling resolution and stays on the lookup engine."""
        mode = (tag // 2) % 4
        routable = (mode == M_ONEWAY) | (mode == M_RPC)
        inner = jnp.where(mode == M_ONEWAY, jnp.int32(wire.APP_ONEWAY),
                          jnp.int32(wire.APP_RPC_CALL))
        return routable, inner, mode == M_RPC

    def on_route_fired(self, app, fired, now, tag):
        """A recursive overlay routed our APP_RPC_CALL payload (no lookup
        completion will follow): arm the single-outstanding-call state
        with the ANY_NODE responder wildcard."""
        return dataclasses.replace(
            app,
            rpc_dst=jnp.where(fired, ANY_NODE, app.rpc_dst),
            rpc_to=jnp.where(fired, now + jnp.int64(
                int(self.p.rpc_timeout * NS)), app.rpc_to),
            rpc_t0=jnp.where(fired, now, app.rpc_t0),
            rpc_nonce=jnp.where(fired, tag, app.rpc_nonce))

    def kpi_spec(self):
        """Telemetry tap registry (apps/base.py; oversim_tpu/telemetry.py
        ``resolve_taps``): the KPI subset of ``stat_spec`` worth a
        time-resolved ring-buffer track — the headline parity metrics
        (hop count + its histogram, one-way latency) and the counters
        the derived delivery ratio needs.  The remaining stats stay
        end-of-run accumulators (``**.telemetry.include`` overrides)."""
        return ("kbr_hopcount", "kbr_latency_s", "kbr_hop_hist",
                "kbr_sent", "kbr_delivered", "kbr_wrong_node",
                "kbr_lookup_failed")

    def stat_spec(self):
        return dict(
            scalars=("kbr_hopcount", "kbr_latency_s", "kbr_rpc_rtt_s",
                     "kbr_lookup_latency_s"),
            hists=(("kbr_hop_hist", self.p.hop_hist_bins),),
            counters=("kbr_sent", "kbr_delivered", "kbr_wrong_node",
                      "kbr_lookup_failed", "kbr_rpc_sent",
                      "kbr_rpc_success", "kbr_rpc_failed",
                      "kbr_lookups_sent", "kbr_lookup_success",
                      "kbr_lookup_wrong"))

    def init(self, n: int) -> KbrTestState:
        return KbrTestState(t_test=jnp.full((n,), T_INF, I64),
                            seq=jnp.zeros((n,), I32),
                            rpc_dst=jnp.full((n,), NO_NODE, I32),
                            rpc_to=jnp.full((n,), T_INF, I64),
                            rpc_t0=jnp.zeros((n,), I64),
                            rpc_nonce=jnp.full((n,), -1, I32),
                            seen_src=jnp.full((n, self.buf), NO_NODE, I32),
                            seen_seq=jnp.zeros((n, self.buf), I32),
                            seen_ptr=jnp.zeros((n,), I32))

    def _check_seen(self, app, src, seq, cand):
        """Circular (src, seqTag) duplicate filter — KBRTestApp::checkSeen
        (KBRTestApp.cc:458-476).  ``cand`` [R] marks lanes to screen;
        returns (app', dup [R]).  Fresh lanes are inserted into the ring
        (oldest-overwritten), duplicates-within-the-batch also flagged."""
        b = self.buf
        dup_buf = ((app.seen_src[None, :] == src[:, None])
                   & (app.seen_seq[None, :] == seq[:, None])).any(-1)
        same = (src[:, None] == src[None, :]) & (seq[:, None] == seq[None, :])
        earlier = (jnp.tril(same, k=-1) & cand[None, :]).any(-1)
        dup = cand & (dup_buf | earlier)
        fresh = cand & ~dup
        rank = jnp.cumsum(fresh.astype(I32)) - fresh.astype(I32)
        # a batch with more than ``b`` fresh entries would wrap the ring
        # WITHIN one scatter — later lanes silently overwriting earlier
        # ones that then never entered the dedup ring.  Overflow lanes
        # are dropped from insertion instead (still screened this batch
        # via ``earlier``; the reference ring is bounded the same way,
        # KBRTestApp.cc:458-476 overwrites oldest)
        ins = fresh & (rank < b)
        pos = jnp.where(ins, (app.seen_ptr + rank) % b, b)
        app = dataclasses.replace(
            app,
            seen_src=app.seen_src.at[pos].set(src, mode="drop"),
            seen_seq=app.seen_seq.at[pos].set(seq, mode="drop"),
            seen_ptr=(app.seen_ptr
                      + jnp.sum(ins.astype(I32), dtype=I32)) % b)
        return app, dup

    def glob_init(self, rng):
        return None

    def post_step(self, ctx, state, glob, events):
        return state, glob

    def on_ready(self, app, en, now, rng):
        """Overlay became READY: first test after a uniform offset
        (reference: BaseApp periodicTimer starts uniform(0, interval))."""
        off = jax.random.uniform(rng, (), minval=0.0,
                                 maxval=self.p.test_interval)
        t = now + (off * NS).astype(I64)
        return dataclasses.replace(app,
                                   t_test=jnp.where(en, t, app.t_test))

    def on_stop(self, app, en):
        return dataclasses.replace(
            app,
            t_test=jnp.where(en, T_INF, app.t_test),
            rpc_dst=jnp.where(en, NO_NODE, app.rpc_dst),
            rpc_to=jnp.where(en, T_INF, app.rpc_to))

    def next_event(self, app):
        return jnp.minimum(app.t_test, app.rpc_to)

    @scoped("app.kbrtest")
    def on_timer(self, app, en, ctx, now, rng, ev, node_idx):
        """Fire the periodic test; round-robin the enabled modes."""
        modes = self.p.modes
        # outstanding routed RPC timed out → failed (KBRTestApp counts
        # RPC timeouts as failures, handleRpcTimeout)
        rpc_dead = en & (app.rpc_to < ctx.t_end)
        # gate on the call's send-time measurement bit (tag low bit), like
        # handleRpcTimeout's getMeasurementPhase() check
        ev.count("kbr_rpc_failed", rpc_dead & ((app.rpc_nonce % 2) != 0))
        app = dataclasses.replace(
            app,
            rpc_dst=jnp.where(rpc_dead, NO_NODE, app.rpc_dst),
            rpc_to=jnp.where(rpc_dead, T_INF, app.rpc_to))

        en = en & (app.t_test < ctx.t_end)
        mode_idx = app.seq % len(modes)
        mode = jnp.asarray(modes, I32)[mode_idx]
        dest = ctx.sample_ready(rng)
        dest_key = ctx.keys[jnp.maximum(dest, 0)]
        want = en & (dest != NO_NODE)
        ev.count("kbr_sent", want & (mode == M_ONEWAY))
        ev.count("kbr_rpc_sent", want & (mode == M_RPC))
        ev.count("kbr_lookups_sent", want & (mode == M_LOOKUP))
        # campaign sweep hook (Ctx.ov_get): "app.testMsgInterval"
        # overrides the steady-state re-arm interval per replica.  The
        # initial on_ready offset has no Ctx and stays at the static
        # param — documented COVERAGE.md gap, irrelevant in steady state.
        iv = ctx.ov_get("app.testMsgInterval")
        if iv is None:
            interval_ns = jnp.int64(
                int(self.p.test_interval / len(modes) * NS))
        else:
            interval_ns = (jnp.asarray(iv) / len(modes) * NS).astype(I64)
        app2 = dataclasses.replace(
            app,
            t_test=jnp.where(en, now + interval_ns, app.t_test),
            seq=app.seq + en.astype(I32))
        # tag layout: (seq*4 + mode)*2 + measuring-at-SEND-time.  The low
        # bit rides through the lookup/route so delivery stats gate on the
        # send-time measurement phase exactly like the reference's
        # setMeasurementPhase-at-creation (KBRTestApp.cc:165-202) — a
        # lookup straddling measurement start can then never count as
        # delivered-but-not-sent (delivered <= sent is a reference
        # invariant, KBRTestApp::evaluateData numSent < numDelivered check)
        return app2, base.LookupReq(
            want=want, key=dest_key,
            tag=(app.seq * 4 + mode) * 2 + ctx.measuring.astype(I32))

    @scoped("app.kbrtest")
    def on_lookup_done(self, app, done: base.LookupDone, ctx, ob, ev, now,
                       node_idx):
        en = done.en
        mode = (done.tag // 2) % 4
        meas = (done.tag % 2) != 0      # measuring at SEND time (tag bit)
        suc = done.success & (done.results[0] != NO_NODE)
        res = done.results[0]

        # ---- one-way: final payload hop to the sibling -----------------
        en_1 = en & (mode == M_ONEWAY)
        ev.count("kbr_lookup_failed", en_1 & ~suc)
        # hops on the wire = total overlay hops including this final one,
        # so iterative (lookup hops + final hop) and recursive (per-hop
        # increments) deliveries record identically.  ``c`` carries the
        # send-time measurement flag; ``a`` the seq tag for receiver dedup.
        ob.send(en_1 & suc & (res != node_idx), now, res, wire.APP_ONEWAY,
                key=done.target, hops=done.hops + 1, a=done.tag,
                c=meas.astype(I32), stamp=done.t0,
                size_b=self.p.test_msg_bytes)
        # lookup ended on ourselves → local delivery
        self_del = en_1 & suc & (res == node_idx)
        ev.count("kbr_delivered", self_del & meas)
        ev.value("kbr_hopcount", done.hops, self_del & meas)
        ev.value("kbr_latency_s",
                 (now - done.t0).astype(jnp.float32) / NS,
                 self_del & meas)

        # ---- routed RPC: KbrTestCall to the responsible node -----------
        en_r = en & (mode == M_RPC)
        ev.count("kbr_rpc_failed", en_r & ~suc & meas)
        fire_r = en_r & suc & (res != node_idx)
        ob.send(fire_r, now, res, wire.APP_RPC_CALL, key=done.target,
                a=done.tag, stamp=done.t0, size_b=self.p.test_msg_bytes)
        # resolved to ourselves → trivially successful zero-RTT call
        self_r = en_r & suc & (res == node_idx)
        ev.count("kbr_rpc_success", self_r & meas)
        app = dataclasses.replace(
            app,
            rpc_dst=jnp.where(fire_r, res, app.rpc_dst),
            rpc_to=jnp.where(fire_r, now + jnp.int64(
                int(self.p.rpc_timeout * NS)), app.rpc_to),
            rpc_t0=jnp.where(fire_r, done.t0, app.rpc_t0),
            rpc_nonce=jnp.where(fire_r, done.tag, app.rpc_nonce))

        # ---- lookup test: oracle validation ----------------------------
        # the target IS a live node's key, so the first sibling must be
        # exactly that node (KBRTestApp lookupNodeIds oracle check)
        en_l = en & (mode == M_LOOKUP)
        resk = ctx.keys[jnp.maximum(res, 0)]
        target_alive = ctx.alive[jnp.maximum(res, 0)]
        right = suc & jnp.all(resk == done.target) & target_alive
        ev.count("kbr_lookup_success", en_l & right & meas)
        ev.count("kbr_lookup_wrong", en_l & suc & ~right & meas)
        ev.count("kbr_lookup_failed", en_l & ~suc & meas)
        ev.value("kbr_lookup_latency_s",
                 (now - done.t0).astype(jnp.float32) / NS,
                 en_l & right & meas)
        return app

    @scoped("app.kbrtest")
    def on_lookup_done_batch(self, app, done: base.LookupDone, ctx, ob, ev,
                             now, node_idx):
        """Batched completion hook: ``done`` fields are [L]-shaped (one
        lane per lookup slot).  Semantics = folding :meth:`on_lookup_done`
        over the L lanes; the at-most-one outstanding routed RPC keeps
        last-fired-wins semantics like the fold did."""
        en = done.en                                   # [L]
        mode = (done.tag // 2) % 4
        meas = (done.tag % 2) != 0      # measuring at SEND time (tag bit)
        suc = done.success & (done.results[:, 0] != NO_NODE)
        res = done.results[:, 0]

        # ---- one-way: final payload hop to the sibling -----------------
        en_1 = en & (mode == M_ONEWAY)
        ev.count("kbr_lookup_failed", en_1 & ~suc)
        ob.send(en_1 & suc & (res != node_idx), now, res, wire.APP_ONEWAY,
                key=done.target, hops=done.hops + 1, a=done.tag,
                c=meas.astype(I32), stamp=done.t0,
                size_b=self.p.test_msg_bytes)
        self_del = en_1 & suc & (res == node_idx)
        ev.count("kbr_delivered", self_del & meas)
        ev.value("kbr_hopcount", done.hops, self_del & meas)
        ev.value("kbr_latency_s",
                 (now - done.t0).astype(jnp.float32) / NS,
                 self_del & meas)

        # ---- routed RPC: KbrTestCall to the responsible node -----------
        en_r = en & (mode == M_RPC)
        ev.count("kbr_rpc_failed", en_r & ~suc & meas)
        fire_r = en_r & suc & (res != node_idx)
        ob.send(fire_r, now, res, wire.APP_RPC_CALL, key=done.target,
                a=done.tag, stamp=done.t0, size_b=self.p.test_msg_bytes)
        self_r = en_r & suc & (res == node_idx)
        ev.count("kbr_rpc_success", self_r & meas)
        # one outstanding call per node: the LAST fired lane wins (the
        # sequential fold's later where() overwrote earlier ones)
        l_dim = en.shape[0]
        any_f = jnp.any(fire_r)
        last = l_dim - 1 - jnp.argmax(fire_r[::-1]).astype(I32)
        sel = jnp.clip(last, 0, l_dim - 1)
        app = dataclasses.replace(
            app,
            rpc_dst=jnp.where(any_f, res[sel], app.rpc_dst),
            rpc_to=jnp.where(any_f, now + jnp.int64(
                int(self.p.rpc_timeout * NS)), app.rpc_to),
            rpc_t0=jnp.where(any_f, done.t0[sel], app.rpc_t0),
            rpc_nonce=jnp.where(any_f, done.tag[sel], app.rpc_nonce))

        # ---- lookup test: oracle validation ----------------------------
        en_l = en & (mode == M_LOOKUP)
        resk = ctx.keys[jnp.maximum(res, 0)]
        target_alive = ctx.alive[jnp.maximum(res, 0)]
        right = suc & jnp.all(resk == done.target, axis=-1) & target_alive
        ev.count("kbr_lookup_success", en_l & right & meas)
        ev.count("kbr_lookup_wrong", en_l & suc & ~right & meas)
        ev.count("kbr_lookup_failed", en_l & ~suc & meas)
        ev.value("kbr_lookup_latency_s",
                 (now - done.t0).astype(jnp.float32) / NS,
                 en_l & right & meas)
        return app

    @scoped("app.kbrtest")
    def on_msgs(self, app, msgs, ctx, ob, ev, is_sib, node_idx=None):
        """Batched deliver hook: ``msgs`` is the [R]-batch Msg view and
        ``is_sib[r]`` the receiver's responsibility flag for msgs.key[r].
        Semantics = folding :meth:`on_msg` over the R slots (at most one
        outstanding RPC means at most one lane can match the client
        response check)."""
        v = msgs.valid
        en = v & (msgs.kind == wire.APP_ONEWAY)
        if self.buf:
            # duplicate screen BEFORE any accounting (checkSeen early
            # return, KBRTestApp.cc:390-399) — the recursive ACK/reroute
            # path can deliver the same payload twice
            app, dup = self._check_seen(app, msgs.src, msgs.a, en)
            en = en & ~dup
        good = en & is_sib & (msgs.c != 0)
        ev.count("kbr_delivered", good)
        ev.count("kbr_wrong_node", en & ~is_sib & (msgs.c != 0))
        ev.value("kbr_hopcount", msgs.hops, good)
        ev.value("kbr_latency_s",
                 (msgs.t_deliver - msgs.stamp).astype(jnp.float32) / NS,
                 good)

        # routed-RPC server: reply in the routing mode's transport
        # (direct UDP unless a recursive overlay set rcfg full/source)
        en = v & (msgs.kind == wire.APP_RPC_CALL)
        if (self.rcfg is not None and self.rcfg.mode in ("full", "source")
                and node_idx is not None):
            from oversim_tpu.common import route as rt_mod
            rt_mod.reply(ob, self.rcfg, en, msgs.t_deliver, msgs, ctx,
                         node_idx, wire.APP_RPC_RES, key=msgs.key,
                         a=msgs.a, stamp=msgs.stamp,
                         size_b=wire.BASE_CALL_B)
        else:
            ob.send(en, msgs.t_deliver, msgs.src, wire.APP_RPC_RES,
                    key=msgs.key, a=msgs.a, stamp=msgs.stamp,
                    size_b=wire.BASE_CALL_B)

        # routed-RPC client: RTT + success (nonce-matched; ANY_NODE
        # wildcard when the call was routed recursively)
        en = v & (msgs.kind == wire.APP_RPC_RES) & (
            (msgs.src == app.rpc_dst) | (app.rpc_dst == ANY_NODE)) & (
            msgs.a == app.rpc_nonce)
        # one success per call even if the reroute path duplicated the
        # request and both responses land in this batch (nonce matching
        # in the reference consumes the RPC state on the first response)
        en = en & (jnp.cumsum(en.astype(I32)) == 1)
        hit = jnp.any(en)
        meas_r = (app.rpc_nonce % 2) != 0   # call's send-time phase bit
        ev.count("kbr_rpc_success", en & meas_r)
        ev.value("kbr_rpc_rtt_s",
                 (msgs.t_deliver - msgs.stamp).astype(jnp.float32) / NS,
                 en & meas_r)
        app = dataclasses.replace(
            app,
            rpc_dst=jnp.where(hit, NO_NODE, app.rpc_dst),
            rpc_to=jnp.where(hit, T_INF, app.rpc_to))
        return app

    def on_leave(self, app, en, ctx, ob, ev, now, node_idx, handover):
        """No state to hand over; leaving nodes just stop testing (the
        engine stops firing app timers during the grace window)."""
        return app

    @scoped("app.kbrtest")
    def on_msg(self, app, m, ctx, ob, ev, is_sib):
        """KBRTestApp::deliver — (src, seq) dedup under recursive routing
        (checkSeen ring); wrong-node check mirrors KBRTestApp.cc:252-286."""
        en = m.valid & (m.kind == wire.APP_ONEWAY)
        if self.buf:
            app, dup = self._check_seen(app, m.src[None], m.a[None],
                                        en[None])
            en = en & ~dup[0]
        good = en & is_sib & (m.c != 0)
        ev.count("kbr_delivered", good)
        ev.count("kbr_wrong_node", en & ~is_sib & (m.c != 0))
        ev.value("kbr_hopcount", m.hops, good)
        ev.value("kbr_latency_s",
                 (m.t_deliver - m.stamp).astype(jnp.float32) / NS, good)

        # routed-RPC server: reply directly (KbrTestCall → Response)
        en = m.valid & (m.kind == wire.APP_RPC_CALL)
        ob.send(en, m.t_deliver, m.src, wire.APP_RPC_RES, key=m.key,
                a=m.a, stamp=m.stamp, size_b=wire.BASE_CALL_B)

        # routed-RPC client: RTT + success.  The echoed nonce (a) rejects
        # a straggler response from a previously timed-out call to the
        # same responder (BaseRpc nonce matching, BaseRpc.cc:293)
        en = m.valid & (m.kind == wire.APP_RPC_RES) & (
            m.src == app.rpc_dst) & (m.a == app.rpc_nonce)
        meas_r = (app.rpc_nonce % 2) != 0   # call's send-time phase bit
        ev.count("kbr_rpc_success", en & meas_r)
        ev.value("kbr_rpc_rtt_s",
                 (m.t_deliver - m.stamp).astype(jnp.float32) / NS,
                 en & meas_r)
        app = dataclasses.replace(
            app,
            rpc_dst=jnp.where(en, NO_NODE, app.rpc_dst),
            rpc_to=jnp.where(en, T_INF, app.rpc_to))
        return app

    @property
    def hist_map(self):
        return {"kbr_hopcount": "kbr_hop_hist"}
