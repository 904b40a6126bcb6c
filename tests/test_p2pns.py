"""P2PNS name service over Chord: register, resolve, cache
(reference src/tier2/p2pns)."""

import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.p2pns import P2pnsApp, P2pnsParams
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.chord import ChordLogic


N = 16


@pytest.fixture(scope="module")
def p2pns_run():
    app = P2pnsApp(P2pnsParams(resolve_interval=15.0, keepalive=60.0),
                   num_slots=N)
    logic = ChordLogic(app=app)
    cp = churn_mod.ChurnParams(model="none", target_num=N, init_interval=0.5)
    # the 16 nodes have joined by second 8 and measurement opens at 68
    # (at 38 a sixth of the resolves went unanswered, 0.85 against the
    # 0.8 below); 112 s and more from there hold a resolve per node per
    # 15 s (the > 50 below) and a keepalive registration per node per
    # 60 s
    ep = sim_mod.EngineParams(window=0.100, transition_time=60.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=17)
    st = s.run_until(st, 180.0, chunk=128)
    return s, st


def test_resolutions_succeed(p2pns_run):
    s, st = p2pns_run
    out = s.summary(st)
    assert out["p2pns_registers"] >= N, out
    assert out["p2pns_stored"] >= N, out
    assert out["p2pns_resolves"] > 50, out
    answered = out["p2pns_resolve_success"]
    assert answered / out["p2pns_resolves"] > 0.8, out


def test_cache_used(p2pns_run):
    s, st = p2pns_run
    out = s.summary(st)
    # with 16 names and a resolve every 15s, repeats hit the cache
    assert out["p2pns_cache_hits"] > 5, out


def test_no_engine_losses(p2pns_run):
    s, st = p2pns_run
    eng = s.summary(st)["_engine"]
    assert eng["pool_overflow"] == 0
    assert eng["outbox_overflow"] == 0


def test_xmlrpc_register_resolve(p2pns_run):
    """External XML-RPC register/resolve through the P2PNS tier
    (XmlRpcInterface.h register/resolve → P2pns calls)."""
    import jax
    from oversim_tpu.xmlrpcif import XmlRpcInterface
    s, st = p2pns_run
    # the interface steps tick by tick: compiled (see test_gateway.py)
    s.step = jax.jit(s.step)
    iface = XmlRpcInterface(s, st, injector_slot=0)
    assert iface.register("alice.example", 31337, ttl=900.0)
    assert iface.resolve("alice.example") == 31337
    assert iface.resolve("nobody.example") == -1


def test_i3_prefix_anycast_and_stack():
    """i3 longest-prefix anycast + trigger stacks (I3.h:56-120) at the
    table level: a packet to an unregistered id matches the trigger with
    the longest shared prefix; a continuation id chains before
    delivering."""
    import dataclasses as dc
    import jax
    import jax.numpy as jnp
    from oversim_tpu.apps.i3 import I3App, I3Params
    from oversim_tpu.common import wire as w
    from oversim_tpu.engine.logic import Outbox, Msg

    app_obj = I3App(I3Params(min_prefix_bits=8), num_slots=4)
    st = app_obj.init(1)
    st = jax.tree.map(lambda x: x[0], st)        # single-node slice
    # trigger A: id 0b1010...0 owner 2; trigger B: id 0x0F000000 owner 3
    # with a continuation to A's id
    ida = jnp.int32(0x50F0F0F0)
    idb = jnp.int32(0x0F000000)
    st = dc.replace(
        st,
        tr_id=st.tr_id.at[0].set(ida).at[1].set(idb),
        tr_owner=st.tr_owner.at[0].set(2).at[1].set(3),
        tr_expire=st.tr_expire.at[0].set(10**15).at[1].set(10**15),
        tr_next=st.tr_next.at[1].set(ida))

    from oversim_tpu.apps.i3 import I3Global

    class Ctx:  # minimal ctx stub for on_msg
        glob = I3Global(trigger_ids=jnp.zeros((4, 5), jnp.uint32))
        measuring = jnp.bool_(True)

    def mk_msg(pkt_id, hops=0):
        z = jnp.int32(0)
        return Msg(valid=jnp.bool_(True), t_deliver=jnp.int64(1000),
                   src=jnp.int32(1), dst=jnp.int32(0),
                   kind=jnp.int32(w.I3_PACKET),
                   key=jnp.zeros((5,), jnp.uint32), nonce=z,
                   hops=jnp.int32(hops), a=jnp.int32(pkt_id), b=z, c=z,
                   d=z, nodes=jnp.full((8,), -1, jnp.int32),
                   size_b=jnp.int32(40), stamp=jnp.int64(0))

    class Ev:
        def count(self, *a): pass
        def value(self, *a): pass

    # near-A id (shares 24 bits with A, ~4 with B) → anycast to A owner 2
    ob = Outbox(4, 5, 8)
    app_obj.on_msg(st, mk_msg(0x50F0F0FF), Ctx(), ob, Ev(),
                   jnp.bool_(True))
    fields, valid, _ = ob.finish()
    sent = [(int(k), int(d)) for k, d, v in
            zip(fields["kind"], fields["dst"], valid) if v]
    assert (int(w.I3_DELIVER), 2) in sent, sent

    # B's exact id → stack chaining: re-enters as a packet for A's id
    ob = Outbox(4, 5, 8)
    app_obj.on_msg(st, mk_msg(0x0F000000), Ctx(), ob, Ev(),
                   jnp.bool_(True))
    fields, valid, _ = ob.finish()
    sent = [(int(k), int(a)) for k, a, v in
            zip(fields["kind"], fields["a"], valid) if v]
    assert (int(w.I3_PACKET), int(ida)) in sent, sent


def test_i3_cross_server_continuation():
    """Cross-server trigger-stack forwarding (I3.h:56-120): a matched
    trigger whose continuation lives on ANOTHER server repacketizes the
    payload as a KBR_ROUTE keyed to the continuation's full overlay id;
    decapsulated at the responsible server, it rematches and delivers —
    a two-server chain."""
    import dataclasses as dc
    import jax
    import jax.numpy as jnp
    from oversim_tpu.apps.i3 import I3App, I3Global, I3Params, wire_id
    from oversim_tpu.common import route as rt_mod
    from oversim_tpu.common import wire as w
    from oversim_tpu.core import keys as K
    from oversim_tpu.engine.logic import Msg, Outbox

    app_obj = I3App(I3Params(min_prefix_bits=8), num_slots=4)
    app_obj.rcfg = rt_mod.RouteConfig()      # overlay-processed routes
    glob = I3Global(trigger_ids=K.random_keys(
        jax.random.PRNGKey(3), (4,), app_obj.spec))

    class Ctx:
        measuring = jnp.bool_(True)
    Ctx.glob = glob

    class Ev:
        def __init__(self):
            self.c = {}

        def count(self, name, inc):
            self.c[name] = self.c.get(name, 0) + int(jnp.sum(
                jnp.asarray(inc).astype(jnp.int32)))

        def value(self, *a):
            pass

    # server 0 stores trigger A (node 2's id) chaining to trigger B
    # (node 3's id, full key attached); server 1 stores plain trigger B
    ida = wire_id(glob, jnp.int32(2))
    idb = wire_id(glob, jnp.int32(3))
    s0 = jax.tree.map(lambda x: x[0], app_obj.init(1))
    s0 = dc.replace(
        s0,
        tr_id=s0.tr_id.at[0].set(ida),
        tr_owner=s0.tr_owner.at[0].set(2),
        tr_expire=s0.tr_expire.at[0].set(10**15),
        tr_next=s0.tr_next.at[0].set(idb),
        tr_next_key=s0.tr_next_key.at[0].set(glob.trigger_ids[3]))
    s1 = jax.tree.map(lambda x: x[0], app_obj.init(1))
    s1 = dc.replace(
        s1,
        tr_id=s1.tr_id.at[0].set(idb),
        tr_owner=s1.tr_owner.at[0].set(3),
        tr_expire=s1.tr_expire.at[0].set(10**15))

    def mk(kind, pkt_id, dst, c=0):
        z = jnp.int32(0)
        return Msg(valid=jnp.bool_(True), t_deliver=jnp.int64(1000),
                   src=jnp.int32(5), dst=jnp.int32(dst), kind=jnp.int32(kind),
                   key=jnp.zeros((5,), jnp.uint32), nonce=z,
                   hops=z, a=jnp.int32(pkt_id), b=jnp.int32(7),
                   c=jnp.int32(c), d=z,
                   nodes=jnp.full((8,), -1, jnp.int32),
                   size_b=jnp.int32(40), stamp=jnp.int64(123))

    # packet for A hits server 0 → cross-server KBR_ROUTE to B's key
    ob = Outbox(4, 5, 8)
    app_obj.on_msg(s0, mk(w.I3_PACKET, ida, 0), Ctx(), ob, Ev(),
                   jnp.bool_(True))
    fields, valid, _ = ob.finish()
    routed = [i for i in range(len(valid))
              if valid[i] and int(fields["kind"][i]) == int(w.KBR_ROUTE)]
    assert routed, "no cross-server route emitted"
    i = routed[0]
    assert int(fields["d"][i]) == int(w.I3_PACKET)
    assert int(fields["a"][i]) == int(idb)
    # c = chain depth (low 16 bits) | payload kind + 1 (high bits)
    assert int(fields["c"][i]) & 0xFFFF == 1           # chain depth
    assert int(fields["c"][i]) >> 16 == 1              # payload kind 0
    assert (fields["key"][i] == glob.trigger_ids[3]).all()

    # the route layer decapsulates at server 1 (kind := d) — replay the
    # decapsulated packet there: plain trigger B delivers to owner 3
    ob = Outbox(4, 5, 8)
    ev = Ev()
    app_obj.on_msg(s1, mk(w.I3_PACKET, int(fields["a"][i]), 1,
                          c=int(fields["c"][i])), Ctx(), ob, ev,
                   jnp.bool_(True))
    fields, valid, _ = ob.finish()
    sent = [(int(k), int(d)) for k, d, v in
            zip(fields["kind"], fields["dst"], valid) if v]
    assert (int(w.I3_DELIVER), 3) in sent, sent
