"""Daemon tier (oversim_tpu/service/{mux,tenant,daemon}.py): socket
mux framing, per-replica multi-tenant sessions, sid routing.

Everything here drives the window protocol DIRECTLY on a tiny stacked
pool state — no Simulation compiles, no ServiceLoop — so the file
stays sub-second in the alphabetically-cut tier-1 run (the e2e daemon
pins live in scripts/slo_soak.py on a standalone budget).  Responses
are crafted by injecting EXT_OUT frames through the same batched
stacked alloc the engine's echo path would produce.
"""

import dataclasses
import socket

import jax
import jax.numpy as jnp
import numpy as np

from oversim_tpu import gateway as gateway_mod
from oversim_tpu.engine import pool as pool_mod
from oversim_tpu.service import (LocalCall, OverlayDaemon, SocketMux,
                                 TenantIngest, TenantTable,
                                 inject_ext_batch_stacked)
from oversim_tpu.service.mux import _HDR


# ---------------------------------------------------------------------------
# tiny stacked state: S replica rows over a P-slot pool
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StackedState:
    """Minimal stacked state with the fields the tenant helpers touch."""

    pool: pool_mod.MsgPool
    t_now: jnp.ndarray      # [S] i64


def _stacked_state(s=2, p=16):
    pool = pool_mod.empty(p, key_lanes=2, rmax=2)
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (s,) + x.shape), pool)
    return StackedState(pool=stacked,
                        t_now=jnp.full((s,), 1000, jnp.int64))


def _respond(ingest, st, sids, transform=1):
    """Craft the engine's echo responses: for every open sid, one
    EXT_OUT frame (b, c + transform) in ITS TENANT'S replica row."""
    rows = [[] for _ in range(len(ingest.table))]
    for sid in sids:
        tenant, b, c = ingest._open[sid]
        rows[tenant].append(gateway_mod.ExtFrame(
            a=sid, b=b, c=c + transform, kind=gateway_mod.EXT_OUT))
    st, _ = inject_ext_batch_stacked(st, rows, ingest.gw)
    return st


# ---------------------------------------------------------------------------
# TenantIngest: stacked inject/drain, admission, row isolation
# ---------------------------------------------------------------------------

def test_tenant_ingest_stacked_roundtrip():
    """submit → ONE batched stacked alloc into the tenant's row →
    after_window settles each sid from its own row."""
    table = TenantTable(2)
    ing = TenantIngest(table, gw_slot=0)
    s0 = ing.submit(0, b=7, c=100)
    s1 = ing.submit(1, b=8, c=200)
    s2 = ing.submit(1, b=9, c=300)

    st = ing.before_window(_stacked_state(), target_ns=5000)
    assert ing.num_batches == 1 and ing.num_injected == 3
    valid = np.asarray(jax.vmap(lambda p: p.valid)(st.pool))
    assert valid[0].sum() == 1 and valid[1].sum() == 2, (
        "tenant id must select the replica row")

    st = _respond(ing, st, (s0, s1, s2))
    st = ing.after_window(st)
    assert ing.responses == {s0: (7, 101), s1: (8, 201), s2: (9, 301)}
    assert ing.outstanding() == 0
    valid = np.asarray(jax.vmap(lambda p: p.valid)(st.pool))
    kind = np.asarray(jax.vmap(lambda p: p.kind)(st.pool))
    assert gateway_mod.EXT_OUT not in kind[valid], "responses not freed"
    acct = ing.accounting()
    assert acct["minted"] == acct["settled"] == 3
    assert acct["per_tenant"][0]["settled"] == 1
    assert acct["per_tenant"][1]["settled"] == 2


def test_tenant_admission_sheds_hot_tenant_only():
    """Tenant 0 over its max_pending sheds (immediate nack, never
    queued); tenant 1 rides the same window untouched."""
    table = TenantTable(2, max_pending=[2, 64])
    ing = TenantIngest(table, gw_slot=0)
    sids0 = [ing.submit(0, b=1, c=i) for i in range(5)]
    sids1 = [ing.submit(1, b=2, c=i) for i in range(3)]
    assert sum(s in ing.nacked for s in sids0) == 3
    assert not any(s in ing.nacked for s in sids1)
    assert ing.rx_shed == 3 and ing.pending(0) == 2

    st = ing.before_window(_stacked_state(), target_ns=5000)
    st = _respond(ing, st, [s for s in sids0 + sids1
                            if s not in ing.nacked])
    ing.after_window(st)
    acct = ing.accounting()
    assert acct["minted"] == 8
    assert acct["settled"] == 5 and acct["nacked"] == 3
    assert acct["outstanding"] == 0
    assert acct["per_tenant"][0]["shed"] == 3
    assert acct["per_tenant"][1]["nacked"] == 0


def test_cross_tenant_row_mismatch_refused():
    """A response surfacing in a FOREIGN replica row (cross-tenant
    leakage) is refused: the sid stays open, the frame stays pooled."""
    table = TenantTable(2)
    ing = TenantIngest(table, gw_slot=0)
    sid = ing.submit(0, b=7, c=100)
    st = ing.before_window(_stacked_state(), target_ns=5000)
    # forge the response into tenant 1's row
    rows = [[], [gateway_mod.ExtFrame(a=sid, b=7, c=101,
                                      kind=gateway_mod.EXT_OUT)]]
    st, _ = inject_ext_batch_stacked(st, rows, 0)
    st = ing.after_window(st)
    assert ing.outstanding() == 1 and sid not in ing.responses
    kind = np.asarray(jax.vmap(lambda p: p.kind)(st.pool))
    valid = np.asarray(jax.vmap(lambda p: p.valid)(st.pool))
    assert gateway_mod.EXT_OUT in kind[valid], (
        "a refused response must not be freed")


def test_window_unit_tracing_per_tenant():
    """Mint/settle carry the ingest's window counter to both the
    global and the tenant tracer (the /metrics latency unit)."""
    class Trace:
        def __init__(self):
            self.events = []

        def mint(self, sid, *, window=None):
            self.events.append(("mint", sid, window))

        def settle(self, sid, *, window=None):
            self.events.append(("settle", sid, window))

        def nack(self, sid, *, window=None):
            self.events.append(("nack", sid, window))

    glob, t0 = Trace(), Trace()
    table = TenantTable(2, tracers=[t0, None])
    ing = TenantIngest(table, gw_slot=0, tracer=glob)
    sid = ing.submit(0, b=1, c=2)
    st = ing.before_window(_stacked_state(), target_ns=5000)
    st = ing.after_window(st)                   # window 0: no response
    st = _respond(ing, st, (sid,))
    ing.after_window(st)                        # window 1: settles
    assert ("mint", sid, 0) in glob.events
    assert ("settle", sid, 1) in glob.events
    assert t0.events == [("mint", sid, 0), ("settle", sid, 1)]


# ---------------------------------------------------------------------------
# OverlayDaemon: local calls, socket sid routing, disconnects
# ---------------------------------------------------------------------------

def _window(daemon, st, respond_sids=None):
    """One daemon window without an engine: admit, optionally craft
    echo responses, drain."""
    st = daemon.before_window(st, target_ns=5000)
    if respond_sids:
        st = _respond(daemon.ingest, st, respond_sids)
    return daemon.after_window(st)


def test_daemon_local_call_roundtrip():
    ing = TenantIngest(TenantTable(2), gw_slot=0)
    daemon = OverlayDaemon(ing)
    call = daemon.submit_local(1, b=5, c=40)
    assert isinstance(call, LocalCall) and not call.done.is_set()
    st = daemon.before_window(_stacked_state(), target_ns=5000)
    st = _respond(ing, st, (call.sid,))
    daemon.after_window(st)
    assert call.done.is_set() and call.status == "ok"
    assert (call.resp_b, call.resp_c) == (5, 41)
    acct = daemon.accounting()
    assert acct["leaked_sessions"] == 0 and acct["orphaned"] == 0


def test_daemon_bad_tenant_is_nacked_without_a_session():
    ing = TenantIngest(TenantTable(2), gw_slot=0)
    daemon = OverlayDaemon(ing)
    call = daemon.submit_local(9, b=1, c=2)
    daemon.before_window(_stacked_state(), target_ns=5000)
    assert call.done.is_set() and call.status == "nack"
    assert daemon.bad_tenant == 1 and ing.outstanding() == 0
    assert not daemon.sessions


class _TcpClient:
    """One blocking-connect, select-free test client."""

    def __init__(self, port):
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=5.0)

    def send(self, kind, a, b, c):
        payload = _HDR.pack(kind, a, b, c)
        self.sock.sendall(len(payload).to_bytes(4, "big") + payload)

    def send_raw(self, payload: bytes):
        self.sock.sendall(len(payload).to_bytes(4, "big") + payload)

    def _recv_exact(self, n):
        """n bytes, or an error: the socket's 5 s timeout bounds a
        silent peer, and a closed one (recv gives b"") ends the read."""
        buf = b""
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            assert chunk, "connection closed mid-frame"
            buf += chunk
        return buf

    def recv_frame(self):
        ln = int.from_bytes(self._recv_exact(4), "big")
        return _HDR.unpack_from(self._recv_exact(ln))

    def close(self):
        self.sock.close()


def test_daemon_socket_sid_routing():
    """Two TCP clients + one UDP client share the mux; every response
    routes back on the submitting client's own connection/address."""
    ing = TenantIngest(TenantTable(2), gw_slot=0)
    mux = SocketMux(udp_port=0, tcp_port=0)
    daemon = OverlayDaemon(ing, mux=mux)
    a = _TcpClient(mux.tcp_port)
    b = _TcpClient(mux.tcp_port)
    udp = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    udp.settimeout(5.0)
    try:
        a.send(gateway_mod.EXT_IN, 0, 11, 100)
        b.send(gateway_mod.EXT_IN, 1, 22, 200)
        udp.sendto(_HDR.pack(gateway_mod.EXT_IN, 0, 33, 300),
                   ("127.0.0.1", mux.udp_port))
        deadline_rounds = 50
        while len(ing._open) < 3 and deadline_rounds:
            st = _window(daemon, _stacked_state())
            deadline_rounds -= 1
        assert len(ing._open) == 3, "mux never surfaced all 3 frames"
        _window(daemon, _stacked_state(),
                respond_sids=list(daemon.sessions))

        ka, _, ba, ca = a.recv_frame()
        kb, _, bb, cb = b.recv_frame()
        kinds = {ka, kb}
        assert kinds == {gateway_mod.EXT_OUT}
        assert (ba, ca) == (11, 101), "client A got a foreign response"
        assert (bb, cb) == (22, 201), "client B got a foreign response"
        data, _ = udp.recvfrom(4096)
        ku, _, bu, cu = _HDR.unpack_from(data)
        assert (ku, bu, cu) == (gateway_mod.EXT_OUT, 33, 301)
        acct = daemon.accounting()
        assert acct["orphaned"] == 0 and acct["leaked_sessions"] == 0
        assert acct["settled"] == 3
    finally:
        a.close()
        b.close()
        udp.close()
        daemon.close()


def test_daemon_disconnect_mid_flight_orphans_without_leak():
    """A client that vanishes between submit and drain: its response
    still settles (counted, freed), lands in ``orphaned``, and leaves
    no session behind — the other client is untouched."""
    ing = TenantIngest(TenantTable(2), gw_slot=0)
    mux = SocketMux(udp_port=0, tcp_port=0)
    daemon = OverlayDaemon(ing, mux=mux)
    a = _TcpClient(mux.tcp_port)
    b = _TcpClient(mux.tcp_port)
    try:
        a.send(gateway_mod.EXT_IN, 0, 11, 100)
        b.send(gateway_mod.EXT_IN, 1, 22, 200)
        rounds = 50
        while len(ing._open) < 2 and rounds:
            st = _window(daemon, _stacked_state())
            rounds -= 1
        assert len(ing._open) == 2
        a.close()                      # vanish mid-flight
        # let the mux notice the dead connection, then drain
        rounds = 50
        while not any(c.closed for c in list(mux.conns)) and rounds:
            mux.pump(timeout=0.01)
            rounds -= 1
        _window(daemon, _stacked_state(),
                respond_sids=list(daemon.sessions))
        kb, _, bb, cb = b.recv_frame()
        assert (kb, bb, cb) == (gateway_mod.EXT_OUT, 22, 201)
        acct = daemon.accounting()
        assert acct["settled"] == 2, "the orphan must still settle"
        assert acct["orphaned"] == 1
        assert acct["leaked_sessions"] == 0
        assert ing.outstanding() == 0
    finally:
        b.close()
        daemon.close()


def test_mux_malformed_frames_never_perturb_neighbours():
    """Client A's garbage (short frame, wrong kind) is dropped and
    counted; A's connection survives and client B's valid frame in the
    same pump is untouched."""
    mux = SocketMux(udp_port=0, tcp_port=0)
    a = _TcpClient(mux.tcp_port)
    b = _TcpClient(mux.tcp_port)
    try:
        a.send_raw(b"\x01\x02")                          # undersized
        a.send_raw(_HDR.pack(gateway_mod.EXT_OUT, 0, 1, 2))  # bad kind
        b.send(gateway_mod.EXT_IN, 1, 22, 200)
        frames = []
        rounds = 50
        while not frames and rounds:
            mux.pump(timeout=0.01)
            frames = mux.take_frames()
            rounds -= 1
        assert [(f.a, f.b, f.c) for f in frames] == [(1, 22, 200)]
        assert mux.rx_dropped == 2
        # A's connection is still serviceable after its garbage
        a.send(gateway_mod.EXT_IN, 0, 11, 100)
        frames = []
        rounds = 50
        while not frames and rounds:
            mux.pump(timeout=0.01)
            frames = mux.take_frames()
            rounds -= 1
        assert [(f.a, f.b, f.c) for f in frames] == [(0, 11, 100)]
    finally:
        a.close()
        b.close()
        mux.close()
