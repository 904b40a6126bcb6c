"""SingleHost interop surface: TUN raw-packet codec + bridge, Zeroconf
DNS-SD announce/browse (reference src/underlay/singlehostunderlay +
ZeroconfConnector.h:38-44)."""

import socket

import pytest

from oversim_tpu.singlehost import (ZeroconfDiscovery, build_announce,
                                    build_ipv4_udp, parse_announce,
                                    parse_ipv4_udp)


def test_ipv4_udp_roundtrip():
    pkt = build_ipv4_udp("10.0.0.2", 5555, "10.0.0.1", 4000,
                         b"\x00" * 16 + b"payload")
    parsed = parse_ipv4_udp(pkt)
    assert parsed is not None
    src_ip, sport, dst_ip, dport, payload = parsed
    assert (src_ip, sport, dst_ip, dport) == ("10.0.0.2", 5555,
                                              "10.0.0.1", 4000)
    assert payload.endswith(b"payload")


def test_parser_rejects_garbage():
    assert parse_ipv4_udp(b"short") is None
    # corrupt the checksum
    pkt = bytearray(build_ipv4_udp("1.2.3.4", 1, "5.6.7.8", 2, b"x" * 20))
    pkt[10] ^= 0xFF
    assert parse_ipv4_udp(bytes(pkt)) is None
    # TCP proto
    pkt = bytearray(build_ipv4_udp("1.2.3.4", 1, "5.6.7.8", 2, b"x" * 20))
    pkt[9] = 6
    assert parse_ipv4_udp(bytes(pkt)) is None


def test_mdns_announce_roundtrip():
    frame = build_announce("node7", "gamma", 4711)
    rec = parse_announce(frame)
    assert rec == ("node7", "gamma", 4711)


def test_mdns_ignores_foreign_frames():
    assert parse_announce(b"\x00" * 12) is None
    assert parse_announce(b"nonsense") is None


def test_mdns_parses_compressed_frames():
    """Real responders (Avahi — the reference ZeroconfConnector's
    backend) compress names with RFC 1035 pointers; parse_announce must
    decode them, not substring-match raw bytes."""
    import struct
    from oversim_tpu.singlehost import SERVICE, _dns_name

    svc = _dns_name(SERVICE)                      # at offset 12
    hdr = struct.pack("!HHHHHH", 0, 0x8400, 0, 1, 0, 1)
    inst_off = 12 + len(svc) + 10                 # PTR rdata start
    inst = b"\x05peerX" + struct.pack("!H", 0xC000 | 12)  # ptr -> svc
    ptr = svc + struct.pack("!HHIH", 12, 1, 120, len(inst)) + inst
    # "local" label offset inside svc: 1+8 ("_oversim") + 1+4 ("_udp")
    local_off = 12 + 14
    target = b"\x04host" + struct.pack("!H", 0xC000 | local_off)
    srv_rd = struct.pack("!HHH", 0, 0, 4242) + target
    owner = struct.pack("!H", 0xC000 | inst_off)  # ptr -> instance name
    srv = owner + struct.pack("!HHIH", 33, 1, 120, len(srv_rd)) + srv_rd
    frame = hdr + ptr + srv
    assert parse_announce(frame) == ("peerX", "host", 4242)


def test_tun_bridge_packet_roundtrip():
    """A raw IPv4/UDP packet (as a TUN device would deliver) traverses
    the simulated gateway node's echo app and comes back as a raw
    reply packet — the tunoutscheduler + packetparser path."""
    from oversim_tpu import churn as churn_mod
    from oversim_tpu.apps.realworld import RealworldEchoApp
    from oversim_tpu.engine import sim as sim_mod
    from oversim_tpu.gateway import EXT_IN, RealtimeGateway, _HDR
    from oversim_tpu.overlay.myoverlay import MyOverlayLogic, MyOverlayParams
    from oversim_tpu.singlehost import TunBridge

    logic = MyOverlayLogic(params=MyOverlayParams(),
                           app=RealworldEchoApp(transform=7))
    cp = churn_mod.ChurnParams(model="none", target_num=4,
                               init_interval=0.2)
    s = sim_mod.Simulation(logic, cp,
                           engine_params=sim_mod.EngineParams(window=0.020,
                                                              inbox_slots=2))
    import jax
    s.step = jax.jit(s.step)   # the gateway steps tick by tick
    state = s.init(seed=9)
    state = s.run_until(state, 10.0)
    gw = RealtimeGateway(s, state, gw_slot=0)
    try:
        bridge = TunBridge(gw, local_ip="10.0.0.1", local_port=4000)
        raw = build_ipv4_udp("10.0.0.9", 5050, "10.0.0.1", 4000,
                             _HDR.pack(EXT_IN, 0, 42, 1000))
        assert bridge.feed_raw(raw)
        replies = []
        for _ in range(50):
            gw.pump(0.2)
            replies = bridge.collect_raw()
            if replies:
                break
        assert replies, "no raw reply packet emitted"
        parsed = parse_ipv4_udp(replies[0])
        assert parsed is not None
        src_ip, sport, dst_ip, dport, payload = parsed
        assert (dst_ip, dport) == ("10.0.0.9", 5050)
        _kind, _sid, b, c = __import__("struct").unpack_from("!IIII",
                                                             payload)
        assert b == 42 and c == 1007, (b, c)
    finally:
        gw.close()


def test_zeroconf_announce_browse_loopback():
    """Two discovery endpoints on the host: one announces, the other
    browses the same group/port (multicast loopback, or plain loopback
    when the sandbox forbids multicast)."""
    try:
        a = ZeroconfDiscovery(port=53530)
    except OSError:
        pytest.skip("no loopback sockets available")
    b = None
    try:
        if a.multicast:
            b = ZeroconfDiscovery(port=53530)
            announcer, browser = a, b
        else:
            # plain loopback: single socket sees its own datagram
            announcer = browser = a
        announcer.announce("peer1", "testhost", 4001)
        found = browser.browse(wait_s=0.5)
        assert ("peer1", "testhost", 4001) in found, found
    finally:
        a.close()
        if b is not None:
            b.close()


# ---------------------------------------------------------------------------
# STUN (reference src/underlay/singlehostunderlay/stun/ + the
# SingleHostUnderlayConfigurator.cc:108-134 stunServer bootstrap path)
# ---------------------------------------------------------------------------

def test_stun_codec_roundtrip():
    from oversim_tpu.singlehost import (STUN_BIND_REQ, STUN_BIND_RES,
                                        build_binding_request,
                                        build_binding_response,
                                        parse_stun)
    txid = bytes(range(12))
    req = parse_stun(build_binding_request(txid))
    assert req and req["type"] == STUN_BIND_REQ and req["txid"] == txid
    # modern XOR-MAPPED-ADDRESS and the classic MAPPED-ADDRESS the
    # reference's vovida 0.96 library answers with (stun.h:36)
    for xor_mapped in (True, False):
        res = parse_stun(build_binding_response(
            txid, "203.0.113.7", 61234, xor_mapped=xor_mapped))
        assert res and res["type"] == STUN_BIND_RES
        assert res["mapped"] == ("203.0.113.7", 61234), res
    assert parse_stun(b"\xff\xff not stun") is None


def test_stun_discover_loopback():
    """Binding request against a loopback responder returns the
    reflexive address of the asking socket (both RFC 5389 and classic
    response encodings)."""
    from oversim_tpu.singlehost import StunResponder, stun_discover
    for classic in (False, True):
        try:
            srv = StunResponder(classic=classic)
        except OSError:
            pytest.skip("no loopback sockets available")
        try:
            cli = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            cli.bind(("127.0.0.1", 0))
            mapped = stun_discover(cli, srv.addr, rto_s=0.5, retries=2)
            assert mapped == cli.getsockname(), (mapped, classic)
            cli.close()
        finally:
            srv.close()


def test_gateway_stun_bootstrap():
    """RealtimeGateway learns its public address via **.stunServer the
    way SingleHostUnderlayConfigurator does before the overlay joins."""
    from oversim_tpu.gateway import RealtimeGateway
    from oversim_tpu.singlehost import StunResponder

    class _SimStub:       # the STUN path runs before any sim pumping
        pass

    try:
        srv = StunResponder()
    except OSError:
        pytest.skip("no loopback sockets available")
    try:
        gw = RealtimeGateway.__new__(RealtimeGateway)
        RealtimeGateway.__init__(gw, sim=_SimStub(), state=None,
                                 stun_server=srv.addr)
        assert gw.public_addr == ("127.0.0.1", gw.udp_port)
        assert gw.nat_detected is False     # loopback: reflexive == local
        gw.udp.close()
    finally:
        srv.close()
