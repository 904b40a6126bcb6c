"""i3 sample applications over Chord (reference src/applications/i3/
i3Apps/: I3Multicast, I3Anycast, I3HostMobility, I3LatencyStretch)."""

import dataclasses

import jax.numpy as jnp
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.i3 import (I3App, I3Params, M_INSERT, M_SEND, NO_NODE,
                                 wire_id)
from oversim_tpu.apps.i3apps import (I3AnycastApp, I3MobilityApp,
                                     I3MulticastApp, I3StretchApp)
from oversim_tpu.common import route as rt_mod
from oversim_tpu.common import wire
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.overlay.chord import ChordLogic

N = 12


def _run(app, t_end=200.0, seed=11):
    logic = ChordLogic(app=app)
    cp = churn_mod.ChurnParams(model="none", target_num=N,
                               init_interval=0.5)
    ep = sim_mod.EngineParams(window=0.05, transition_time=40.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=seed)
    st = s.run_until(st, t_end, chunk=256)
    return s.summary(st)


@pytest.mark.slow
def test_multicast_reaches_whole_group():
    """I3Multicast.cc: all members register the identical identifier;
    one send reaches every member via the server's trigger-set fan-out
    (I3.cc sendPacket 'send to all friends')."""
    out = _run(I3MulticastApp(I3Params(send_interval=20.0, refresh=25.0),
                              num_groups=2, num_slots=N))
    assert out["i3_sent"] > 10, out
    # each group has N/2 = 6 members (sender included in the set)
    fanout = out["i3_mcast_recv"] / out["i3_sent"]
    assert fanout > 3.0, (fanout, out)
    assert out["i3_misdelivered"] == 0, out


@pytest.mark.slow
def test_anycast_chain_circulates():
    """I3Anycast.cc: prefix-class triggers with random-suffix sends —
    each delivery lands on one member and immediately re-sends, so the
    chain keeps circulating through the rendezvous server."""
    out = _run(I3AnycastApp(I3Params(send_interval=15.0, refresh=25.0),
                            num_slots=N))
    # the chain re-sends on every delivery: far more deliveries than
    # the handful of seeded sends
    assert out["i3_delivered"] > 3 * max(out["i3_sent"], 1), out
    assert out["i3_misdelivered"] == 0, out


@pytest.mark.slow
def test_mobility_pings_survive_moves():
    """I3HostMobility.cc: partners discovered by anycast QUERY_ID are
    pinged continuously; identifier re-randomization (the mobility
    event) loses stale-id pings until rediscovery — pings must flow,
    moves must happen, and most pings must still complete."""
    out = _run(I3MobilityApp(I3Params(refresh=10.0, trigger_ttl=30.0),
                             ping_interval=2.0,
                             rediscover_interval=20.0,
                             move_interval=60.0,
                             num_slots=N),
               t_end=260.0)
    assert out["i3_mob_partners"] > 0, out
    assert out["i3_mob_moves"] > 0, out
    assert out["i3_mob_ping_sent"] > 40, out
    ratio = out["i3_mob_pong_recv"] / out["i3_mob_ping_sent"]
    # stale-id losses are EXPECTED around moves; the rest must complete
    assert ratio > 0.5, (ratio, out)


I32 = jnp.int32
NS = 1_000_000_000
D_TESTPING = 3


class StackedPingApp(I3App):
    """Every node's trigger is a STACK: id_i chains to id_{(i+1) % n}
    with the continuation's full overlay key, so a matched packet takes
    the cross-server KBR_ROUTE continuation leg (i3.py on_msg cross_v)
    to the next id's responsible server.  Packets carry a typed payload
    (``d = D_TESTPING``, the i3apps.py D_* convention) which must
    survive that leg — the route layer needs ``d`` for the decap kind,
    so the payload kind rides ``c``'s high bits."""

    def stat_spec(self):
        spec = super().stat_spec()
        spec["counters"] = spec["counters"] + (
            "i3_ping_survived", "i3_kind_lost")
        return spec

    def on_lookup_done(self, app, done, ctx, ob, ev, now, node_idx):
        p, glob = self.p, ctx.glob
        en = done.en
        mode = done.tag % 4
        name = done.tag // 4
        suc = done.success & (done.results[0] != NO_NODE)
        ev.count("i3_lookup_failed", en & ~suc)
        server = done.results[0]
        tid = wire_id(glob, name)
        # stacked insert: continuation = the NEXT node's trigger, full
        # key on the wire (i3.py I3_INSERT c/key fields)
        nxt = (name + 1) % self.n
        ob.send(en & suc & (mode == M_INSERT), now, server, wire.I3_INSERT,
                a=tid, b=node_idx, c=wire_id(glob, nxt),
                key=glob.trigger_ids[jnp.maximum(nxt, 0)],
                stamp=now + jnp.int64(int(p.trigger_ttl * NS)),
                size_b=wire.BASE_CALL_B + 12)
        # typed data packet (the pre-fix path dropped d on the
        # continuation leg)
        ob.send(en & suc & (mode == M_SEND), now, server, wire.I3_PACKET,
                a=tid, b=node_idx, d=jnp.int32(D_TESTPING), stamp=now,
                size_b=p.payload_bytes)
        return app

    def _on_deliver(self, app, m, ctx, ob, ev, en):
        ev.count("i3_ping_survived",
                 en & (m.d == D_TESTPING) & ctx.measuring)
        ev.count("i3_kind_lost",
                 en & (m.d != D_TESTPING) & ctx.measuring)
        return super()._on_deliver(app, m, ctx, ob, ev, en)


def test_stacked_trigger_cross_server_keeps_payload_kind():
    """With stack_hop_max=1 every delivery crossed EXACTLY one
    cross-server continuation leg; the typed D_TESTPING payload must
    arrive intact on all of them (zero kind-lost deliveries)."""
    rcfg = rt_mod.RouteConfig(mode="semi")
    app = StackedPingApp(I3Params(send_interval=10.0, refresh=25.0,
                                  trigger_ttl=90.0, stack_hop_max=1),
                         num_slots=N)
    logic = ChordLogic(app=app, rcfg=rcfg)
    app.rcfg = logic.rcfg
    cp = churn_mod.ChurnParams(model="none", target_num=N,
                               init_interval=0.5)
    ep = sim_mod.EngineParams(window=0.1, transition_time=40.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=11)
    # measurement opens at second 46; a ping per node per 10 s from there
    # to 110 s and more is some 75 for the two > 5 below
    st = s.run_until(st, 110.0, chunk=256)
    out = s.summary(st)
    assert out["i3_sent"] > 5, out
    assert out["i3_ping_survived"] > 5, out
    # the payload kind never degrades to a raw/other kind at delivery
    assert out["i3_kind_lost"] == 0, out
    # deliveries == ping deliveries (every one went through the stack)
    assert out["i3_delivered"] == out["i3_ping_survived"], out


@pytest.mark.slow
def test_latency_stretch_at_least_one():
    """I3LatencyStretch.cc: the i3 leg crosses the rendezvous server,
    the direct pong leg does not — mean stretch must be >= ~1."""
    out = _run(I3StretchApp(I3Params(send_interval=15.0, refresh=25.0),
                            num_slots=N))
    assert out["i3_delivered"] > 10, out
    i3_leg = out["i3_leg_s"]["mean"]
    direct = out["direct_leg_s"]["mean"]
    assert direct > 0, out
    stretch = i3_leg / direct
    assert stretch > 0.9, (stretch, i3_leg, direct)
