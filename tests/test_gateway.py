"""Real-network gateway (SingleHostUnderlay equivalent) + XML-RPC.

Loopback tests: a real UDP datagram / TCP frame must traverse the
simulated gateway node (RealworldEchoApp transforms the payload word)
and come back on the wire; the XML-RPC surface must answer
local_lookup/put/get against a live DHT simulation."""

import socket
import struct
import xmlrpc.client

import jax
import numpy as np
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.dht import DhtApp, DhtParams
from oversim_tpu.apps.realworld import RealworldEchoApp, TcpEchoApp
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.gateway import (EXT_IN, EXT_OUT, ExtFrame,
                                 GenericPacketParser, RealtimeGateway,
                                 _HDR, inject_ext_batch)
from oversim_tpu.overlay.chord import ChordLogic
from oversim_tpu.overlay.myoverlay import MyOverlayLogic, MyOverlayParams
from oversim_tpu.xmlrpcif import XmlRpcInterface, serve


def _ring_sim(app, n=4, seed=9):
    logic = MyOverlayLogic(params=MyOverlayParams(), app=app)
    cp = churn_mod.ChurnParams(model="none", target_num=n,
                               init_interval=0.2)
    ep = sim_mod.EngineParams(window=0.020, inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    # the gateway and the XML-RPC interface advance the simulation with
    # sim.step, tick by tick: eager that is seconds a tick on XLA-CPU,
    # compiled it is milliseconds
    s.step = jax.jit(s.step)
    state = s.init(seed=seed)
    state = s.run_until(state, 10.0)
    return s, state


@pytest.fixture(scope="module")
def echo_ring():
    """One settled ring under RealworldEchoApp(transform=5) for the UDP
    tests: each starts a gateway of its own from this state, and a
    gateway steps functionally, so the state here is never consumed."""
    return _ring_sim(RealworldEchoApp(transform=5))


def test_udp_echo_through_sim(echo_ring):
    s, state = echo_ring
    gw = RealtimeGateway(s, state, gw_slot=0)
    client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    client.settimeout(0.3)
    try:
        client.sendto(_HDR.pack(EXT_IN, 0, 42, 1000),
                      ("127.0.0.1", gw.udp_port))
        for _ in range(50):
            gw.pump(0.2)
            try:
                data, _ = client.recvfrom(4096)
                break
            except socket.timeout:
                continue
        else:
            raise AssertionError("no echo from the gateway")
        kind, sid, b, c = _HDR.unpack_from(data)
        assert b == 42
        assert c == 1000 + 5, "payload must traverse the sim-side app"
    finally:
        client.close()
        gw.close()


def test_tcp_echo_through_sim():
    s, state = _ring_sim(TcpEchoApp(transform=7), seed=10)
    gw = RealtimeGateway(s, state, gw_slot=0, tcp_port=0)
    client = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    client.settimeout(0.3)
    try:
        client.connect(("127.0.0.1", gw.tcp_port))
        frame = _HDR.pack(EXT_IN, 0, 7, 100)
        client.sendall(len(frame).to_bytes(4, "big") + frame)
        buf = b""
        for _ in range(50):
            gw.pump(0.2)
            try:
                chunk = client.recv(4096)
            except socket.timeout:
                continue
            buf += chunk
            if len(buf) >= 4:
                ln = int.from_bytes(buf[:4], "big")
                if len(buf) >= 4 + ln:
                    break
        else:
            raise AssertionError("no TCP echo")
        kind, sid, b, c = _HDR.unpack_from(buf[4:])
        assert b == 7 and c == 107
    finally:
        client.close()
        gw.close()


@pytest.fixture(scope="module")
def dht_sim():
    app = DhtApp(DhtParams(test_interval=20.0, num_test_keys=16,
                           test_ttl=600.0))
    logic = ChordLogic(app=app)
    cp = churn_mod.ChurnParams(model="none", target_num=8,
                               init_interval=1.0)
    ep = sim_mod.EngineParams(window=0.050, transition_time=20.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    s.step = jax.jit(s.step)   # as in _ring_sim
    st = s.init(seed=31)
    st = s.run_until(st, 60.0, chunk=128)
    return s, st


def test_xmlrpc_local_lookup_put_get(dht_sim):
    s, st = dht_sim
    iface = XmlRpcInterface(s, st, injector_slot=0)
    key = "ab" * (s.spec.bits // 8)
    near = iface.local_lookup(key, 3)
    assert 1 <= len(near) <= 3
    alive = np.asarray(st.alive)
    assert all(alive[i] for i in near)
    acks = iface.put(key, value=777, ttl=600.0)
    assert acks >= 1, "no replica acked the external DHT put"
    got = iface.get(key)
    assert got == 777


def test_xmlrpc_over_the_wire(dht_sim):
    s, st = dht_sim
    iface = XmlRpcInterface(s, st, injector_slot=0)
    server, port = serve(iface)
    try:
        proxy = xmlrpc.client.ServerProxy(f"http://127.0.0.1:{port}/",
                                          allow_none=True)
        stats = proxy.stats()
        assert isinstance(stats, dict) and len(stats) > 0
        key = "cd" * (s.spec.bits // 8)
        near = proxy.local_lookup(key, 2)
        assert len(near) >= 1
    finally:
        server.shutdown()


def test_xmlrpc_full_surface(dht_sim):
    """The XmlRpcInterface.h:102-166 methods beyond put/get: wire-level
    KBR lookup, dump_dht aggregation, join_overlay node spawn."""
    s, st = dht_sim
    iface = XmlRpcInterface(s, st, injector_slot=0)
    key = "cd" * (s.spec.bits // 8)
    # full lookup over real FINDNODE traffic resolves to the node
    # RESPONSIBLE for the key — on Chord its clockwise successor.  (The
    # local_lookup oracle ranks by bidirectional ring distance; for this
    # key it names the predecessor, one slot short of the successor.)
    sibs = iface.lookup(key, 2)
    assert sibs, "wire lookup found no sibling"
    from oversim_tpu.core import keys as K
    kint = int(key, 16)
    cw = [(K.to_int(k) - kint) % (1 << s.spec.bits)
          for k in np.asarray(st.node_keys)]
    assert sibs[0] == min(range(len(cw)), key=cw.__getitem__)
    # a put must become visible in the global dump
    iface.put(key, value=4242, ttl=600.0)
    dump = iface.dump_dht()
    assert any(v == 4242 for _, v in dump), dump
    # join_overlay: all 8 slots alive -> -1 (the spawn path is churn-
    # covered elsewhere; here the guard is what's reachable)
    assert iface.join_overlay() == -1


def test_signed_gateway_rejects_unsigned(tmp_path, echo_ring):
    """Real-crypto SingleHost path (CryptoModule.h:56 signMessage /
    verifyMessage with keyFile): an unsigned datagram is dropped, a
    signed one traverses the sim and the reply verifies under the
    shared key; a tampered frame fails verification."""
    from oversim_tpu.common.crypto import CryptoModule

    kf = str(tmp_path / "node.key")
    cm = CryptoModule(key_file=kf)
    cm2 = CryptoModule(key_file=kf)      # second load shares the secret
    assert cm.key == cm2.key

    s, state = echo_ring
    gw = RealtimeGateway(s, state, gw_slot=0, crypto=cm)
    client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    client.settimeout(0.25)
    try:
        # unsigned: must be dropped at the gateway
        client.sendto(_HDR.pack(EXT_IN, 0, 1, 50),
                      ("127.0.0.1", gw.udp_port))
        gw.pump(0.3)
        assert gw.crypto.num_verify_failed >= 1

        # signed: traverses; reply carries a VALID auth block
        client.sendto(cm2.sign_frame(_HDR.pack(EXT_IN, 0, 9, 500)),
                      ("127.0.0.1", gw.udp_port))
        data = None
        for _ in range(50):
            gw.pump(0.2)
            try:
                data, _ = client.recvfrom(4096)
                break
            except socket.timeout:
                continue
        assert data is not None, "no signed echo from the gateway"
        stripped = cm2.verify_frame(data)
        assert stripped is not None, "reply auth block must verify"
        _, sid, b, c = _HDR.unpack_from(stripped)
        assert b == 9 and c == 500 + 5
        assert cm.num_sign >= 1

        # tampered: flip a payload byte, keep the block -> reject
        forged = bytearray(cm2.sign_frame(_HDR.pack(EXT_IN, 0, 2, 60)))
        forged[8] ^= 0xFF
        assert cm2.verify_frame(bytes(forged)) is None
    finally:
        client.close()
        gw.close()


def test_pluggable_packet_parser(echo_ring):
    """GenericPacketParser surface (src/common/GenericPacketParser.h:
    parserType-selected codec): a custom parser speaking a different
    external wire format (ascii "b:c" datagrams) drives the same sim
    path; malformed packets are rejected by the parser, not the
    gateway."""
    from oversim_tpu.gateway import GenericPacketParser

    class AsciiParser(GenericPacketParser):
        def decapsulate(self, data):
            try:
                b, c = data.decode("ascii").strip().split(":")
                return int(b), int(c)
            except (ValueError, UnicodeDecodeError):
                return None

        def encapsulate(self, sid, b, c):
            return f"{b}:{c}".encode("ascii")

    s, state = echo_ring
    gw = RealtimeGateway(s, state, gw_slot=0, parser=AsciiParser())
    client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    client.settimeout(0.25)
    try:
        client.sendto(b"\x00\x01garbage", ("127.0.0.1", gw.udp_port))
        client.sendto(b"6:900", ("127.0.0.1", gw.udp_port))
        data = None
        for _ in range(50):
            gw.pump(0.2)
            try:
                data, _ = client.recvfrom(4096)
                break
            except socket.timeout:
                continue
        assert data is not None, "no ascii echo from the gateway"
        assert data == b"6:905", data   # 900 + transform 5
    finally:
        client.close()
        gw.close()


# ---------------------------------------------------------------------------
# RX hardening + batching: sockets only, no simulation needed (the
# gateway's poll/flush half runs against a bare state) — keep these
# CHEAP, they sort before the tier-1 timeout cut
# ---------------------------------------------------------------------------

import dataclasses
import time

import jax
import jax.numpy as jnp

from oversim_tpu.engine import pool as pool_mod


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class _PoolOnlyState:
    pool: pool_mod.MsgPool
    t_now: jnp.ndarray


def _pool_state(p=16):
    return _PoolOnlyState(pool=pool_mod.empty(p, key_lanes=2, rmax=2),
                          t_now=jnp.int64(1000))


def _poll_until(gw, cond, timeout_s=3.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        gw._poll_udp()
        gw._poll_tcp()
        if cond():
            return True
        time.sleep(0.01)
    return False


def test_udp_garbage_datagram_dropped_not_fatal():
    """A malformed datagram from the real network must be dropped and
    COUNTED — never unwind the poll loop; a good frame right after it
    still gets through."""
    gw = RealtimeGateway(None, None)   # sockets only
    client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        client.sendto(b"\x01", ("127.0.0.1", gw.udp_port))  # < header
        assert _poll_until(gw, lambda: gw.rx_dropped == 1)
        assert gw._rx == []

        client.sendto(_HDR.pack(EXT_IN, 0, 5, 500),
                      ("127.0.0.1", gw.udp_port))
        assert _poll_until(gw, lambda: len(gw._rx) == 1)
        assert gw.rx_dropped == 1
        assert (gw._rx[0].b, gw._rx[0].c) == (5, 500)
    finally:
        client.close()
        gw.close()


def test_udp_raising_parser_counted_not_raised():
    """A parser that CRASHES on hostile bytes (the plausible bug in a
    custom GenericPacketParser) is contained: counted as dropped, one
    warning, the gateway keeps polling."""

    class BoomParser(GenericPacketParser):
        def decapsulate(self, data):
            raise RuntimeError("boom")

    gw = RealtimeGateway(None, None, parser=BoomParser())
    client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        for _ in range(2):
            client.sendto(b"hostile bytes", ("127.0.0.1", gw.udp_port))
        assert _poll_until(gw, lambda: gw.rx_dropped == 2)
        assert gw._rx == []
        gw._poll_udp()                 # still alive after the crashes
    finally:
        client.close()
        gw.close()


def test_tcp_desynced_stream_drops_connection():
    """Garbage where the 4-byte length prefix should be desyncs the
    stream forever: the connection is dropped (and counted), the
    gateway survives."""
    gw = RealtimeGateway(None, None, tcp_port=0)
    client = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    try:
        client.connect(("127.0.0.1", gw.tcp_port))
        client.sendall(b"\xff\xff\xff\xffgarbage")   # prefix ~4 GiB
        assert _poll_until(gw, lambda: gw.rx_dropped >= 1)
        assert gw._tcp_conns == {}, "desynced connection must be dropped"
    finally:
        client.close()
        gw.close()


def test_rx_batching_one_pool_write(tmp_path):
    """Accumulated datagrams enter the pool as ONE batched alloc on
    flush_rx (rx_batches counts pool writes, rx_frames counts frames),
    in arrival order, with zero overflow on an empty pool."""
    gw = RealtimeGateway(None, _pool_state())
    client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        for i in range(3):
            client.sendto(_HDR.pack(EXT_IN, 0, i, 100 + i),
                          ("127.0.0.1", gw.udp_port))
        assert _poll_until(gw, lambda: len(gw._rx) == 3)
        assert gw.rx_batches == 0      # nothing flushed yet

        gw.flush_rx()
        assert gw.rx_batches == 1 and gw.rx_frames == 3
        assert gw.rx_overflow() == 0
        pool = gw.state.pool
        valid = np.asarray(pool.valid)
        assert valid.sum() == 3
        got = sorted(zip(np.asarray(pool.a)[valid],
                         np.asarray(pool.b)[valid],
                         np.asarray(pool.c)[valid]))
        assert [(b, c) for _, b, c in got] == [(0, 100), (1, 101),
                                               (2, 102)]
        assert set(np.asarray(pool.kind)[valid]) == {EXT_IN}
    finally:
        client.close()
        gw.close()


# ------------------------------------- admission control (ISSUE 17) --

from oversim_tpu.gateway import EXT_NACK  # noqa: E402


class _NackTracer:
    """Tracer double recording mint/nack calls (duck-typed, the
    gateway takes any object with these methods)."""

    def __init__(self):
        self.minted = []
        self.nacks = []

    def mint(self, sid, **kw):
        self.minted.append(sid)

    def nack(self, sid, **kw):
        self.nacks.append(sid)
        return True


def test_udp_admission_bound_sheds_with_nack():
    """Frames past max_rx_backlog are refused with an explicit NACK
    datagram back to the sender — counted in rx_shed, traced as nacked,
    never a session entry — while admitted frames are untouched."""
    tr = _NackTracer()
    gw = RealtimeGateway(None, None, max_rx_backlog=2, tracer=tr)
    client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    client.settimeout(3.0)
    try:
        for i in range(3):
            client.sendto(_HDR.pack(EXT_IN, 0, i, 100 + i),
                          ("127.0.0.1", gw.udp_port))
        assert _poll_until(gw, lambda: gw.rx_shed == 1)
        # exactly the first two admitted, in arrival order
        assert [(f.b, f.c) for f in gw._rx] == [(0, 100), (1, 101)]
        # the shed frame got a NACK with ITS OWN identity echoed back
        kind, sid, b, c = _HDR.unpack(client.recv(65536))
        assert kind == EXT_NACK and (b, c) == (2, 102)
        # minted-then-NACKed: the trace closes explicitly (the
        # zero-lost-sessions identity), and no session entry exists
        assert sid in tr.minted and tr.nacks == [sid]
        assert sid not in gw._sessions
        # backlog drained -> the next frame is admitted again
        gw._rx.clear()
        client.sendto(_HDR.pack(EXT_IN, 0, 7, 700),
                      ("127.0.0.1", gw.udp_port))
        assert _poll_until(gw, lambda: len(gw._rx) == 1)
        assert gw.rx_shed == 1
    finally:
        client.close()
        gw.close()


def test_tcp_admission_shed_keeps_connection():
    """A shed TCP frame answers a length-prefixed NACK on the SAME
    connection and the stream survives — only the one frame is
    refused."""
    gw = RealtimeGateway(None, None, tcp_port=0, max_rx_backlog=1)
    client = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    client.settimeout(3.0)
    try:
        client.connect(("127.0.0.1", gw.tcp_port))
        for i in range(2):
            frame = _HDR.pack(EXT_IN, 0, i, 200 + i)
            client.sendall(len(frame).to_bytes(4, "big") + frame)
        assert _poll_until(gw, lambda: gw.rx_shed == 1)
        assert [(f.b, f.c) for f in gw._rx] == [(0, 200)]
        # the NACK arrives length-prefixed on the same stream
        ln = int.from_bytes(client.recv(4), "big")
        kind, _sid, b, c = _HDR.unpack(client.recv(ln))
        assert kind == EXT_NACK and (b, c) == (1, 201)
        # the connection is still serviced: drain, then send another
        assert len(gw._tcp_conns) == 1
        gw._rx.clear()
        frame = _HDR.pack(EXT_IN, 0, 9, 900)
        client.sendall(len(frame).to_bytes(4, "big") + frame)
        assert _poll_until(gw, lambda: len(gw._rx) == 1)
        assert (gw._rx[0].b, gw._rx[0].c) == (9, 900)
    finally:
        client.close()
        gw.close()


# ------------------------------- TX buffering + window accounting --

def test_tcp_partial_write_survives_tiny_sndbuf():
    """Outbound frames survive kernel backpressure intact: with a tiny
    server-side SO_SNDBUF and an unread client, ``send`` goes partial
    mid-frame; the per-connection TX buffer must keep the
    length-prefixed stream byte-exact (the old ``sendall`` on a
    non-blocking socket could desync it) and count the partials."""
    gw = RealtimeGateway(None, None, tcp_port=0)
    client = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    client.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
    client.settimeout(5.0)
    frames = 200
    body = b"x" * 1000
    try:
        client.connect(("127.0.0.1", gw.tcp_port))
        assert _poll_until(gw, lambda: len(gw._tcp_conns) == 1)
        sid = next(iter(gw._tcp_conns))
        conn = gw._tcp_conns[sid][0]
        conn.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        for i in range(frames):
            gw._send_tcp(sid, _HDR.pack(EXT_OUT, sid, i, 1000 + i)
                         + body)
        # slow reader: drain one frame at a time, pumping between reads
        def read_exact(n):
            buf = b""
            while len(buf) < n:
                gw._pump_tx()
                chunk = client.recv(n - len(buf))
                assert chunk, "stream closed mid-frame"
                buf += chunk
            return buf

        for i in range(frames):
            ln = int.from_bytes(read_exact(4), "big")
            assert ln == _HDR.size + len(body)
            data = read_exact(ln)
            kind, _s, b, c = _HDR.unpack_from(data)
            assert (kind, b, c) == (EXT_OUT, i, 1000 + i), (
                f"frame {i} corrupted/reordered")
            assert data[_HDR.size:] == body
        assert gw.tx_partial_writes > 0, (
            "the test never exercised a partial write — shrink the "
            "buffers or grow the frames")
        assert not gw._tcp_tx.get(sid), "residue left in the TX buffer"
    finally:
        client.close()
        gw.close()


def test_gateway_ingest_window_accounting():
    """GatewayIngest pins the serving-window index on the gateway per
    boundary: mints/settles trace latency in WINDOW units and the
    adapter's ``windows`` counter advances once per after_window."""
    from oversim_tpu.service import GatewayIngest

    class Trace:
        def __init__(self):
            self.events = []

        def mint(self, sid, *, window=None):
            self.events.append(("mint", sid, window))

        def settle(self, sid, *, window=None):
            self.events.append(("settle", sid, window))

    tr = Trace()
    gw = RealtimeGateway(None, _pool_state(), tracer=tr)
    ing = GatewayIngest(gw)
    client = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        client.sendto(_HDR.pack(EXT_IN, 0, 5, 500),
                      ("127.0.0.1", gw.udp_port))
        st = _pool_state()
        deadline = time.monotonic() + 3.0
        while not tr.events and time.monotonic() < deadline:
            st = ing.before_window(st, target_ns=0)
            time.sleep(0.01)
        assert tr.events and tr.events[0][0] == "mint"
        sid = tr.events[0][1]
        assert tr.events[0] == ("mint", sid, 0), (
            "window-0 mint must carry window index 0")
        # craft the engine's response and drain it in the SAME window
        from oversim_tpu.gateway import inject_ext_batch
        st, _ = inject_ext_batch(
            st, [ExtFrame(a=sid, b=5, c=501, kind=EXT_OUT)], 0)
        st = ing.after_window(st)
        assert ("settle", sid, 0) in tr.events
        assert ing.windows == 1
        # next boundary mints with the advanced window index
        client.sendto(_HDR.pack(EXT_IN, 0, 6, 600),
                      ("127.0.0.1", gw.udp_port))
        deadline = time.monotonic() + 3.0
        while len(tr.events) < 3 and time.monotonic() < deadline:
            st = ing.before_window(st, target_ns=0)
            time.sleep(0.01)
        assert tr.events[2] == ("mint", sid + 1, 1)
    finally:
        client.close()
        gw.close()
