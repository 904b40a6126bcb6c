"""``common/route.py`` writes an ACK slot by a one-hot mask (ISSUE 47).

Each rewritten function against the scatter form it replaces
(``tests/route_scatter_oracle.py``: the functions of the commit before),
under ``vmap`` over random lanes: every leaf equal, dtype and all, and
every field of what was sent.  Pure functions, no ``Simulation``.
"""

import dataclasses
import functools
import inspect
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import route_scatter_oracle as oracle
from test_lookup_mask_writes import Outbox, assert_same
from oversim_tpu.common import route as rt_mod
from oversim_tpu.common.route import I32, I64, NO_NODE, RouteConfig

LANES = 128
KL = 5
NODES = 40          # node slots drawn for next hops, senders, visited lists
R_IN = 4            # inbox slots of a batch
RMAX = 8            # width of the wire's ``nodes`` field

CONFIGS = {
    "pastry": RouteConfig(),
    "acks_off": RouteConfig(route_acks=False),
    "koorde": RouteConfig(ext_words=2),
    "eight_slots": RouteConfig(slots=8, max_retries=1),
    "record_route": RouteConfig(record_route=True, mode="full"),
    "source_koorde": RouteConfig(mode="source", ext_words=2, slots=8),
}
# how much of the wire's ``nodes`` a parked copy keeps
VISITED_CAP = {"pastry": RMAX, "acks_off": RMAX, "koorde": RMAX,
               "eight_slots": 6, "record_route": RMAX, "source_koorde": 4}


class Ack(NamedTuple):
    valid: jnp.ndarray
    nonce: jnp.ndarray
    src: jnp.ndarray


def random_state(rng, cfg, name, lanes=LANES, full=0.25):
    """[lanes, Q, ...] route states with every leaf drawn at random, so a
    write that strays from its slot shows in some leaf; ``full`` of the
    lanes hold no free slot."""
    shapes = jax.eval_shape(lambda: rt_mod.init(cfg, KL, VISITED_CAP[name]))

    def draw(field, leaf):
        shape = (lanes,) + leaf.shape
        if field == "active":
            active = rng.random(shape) < 0.5
            active[rng.random(lanes) < full] = True
            return active
        if field in ("dst", "visited"):
            nodes = rng.integers(0, NODES, shape)
            return np.where(rng.random(shape) < 0.3, -1, nodes)
        if field == "gen":
            # past the 22 bits a nonce carries, too
            return rng.integers(0, 2**24, shape)
        if field == "retries":
            return rng.integers(0, cfg.max_retries + 2, shape)
        if leaf.dtype == jnp.int64:
            return rng.integers(0, 2**40, shape)
        if leaf.dtype == jnp.uint32:
            return rng.integers(0, 2**32, shape)
        return rng.integers(0, 50, shape)

    return rt_mod.RouteState(**{
        f.name: jnp.asarray(draw(f.name, getattr(shapes, f.name)),
                            getattr(shapes, f.name).dtype)
        for f in dataclasses.fields(shapes)})


def random_hop(rng, lead):
    """The fields of one hop to forward, with ``lead`` leading axes."""
    nodes = rng.integers(0, NODES, lead + (RMAX,))
    return dict(
        key=jnp.asarray(rng.integers(0, 2**32, lead + (KL,)), jnp.uint32),
        inner=jnp.asarray(rng.integers(0, 99, lead), I32),
        a=jnp.asarray(rng.integers(0, 99, lead), I32),
        b=jnp.asarray(rng.integers(0, 99, lead), I32),
        c=jnp.asarray(rng.integers(0, 2, lead), I32),
        hops=jnp.asarray(rng.integers(1, 32, lead), I32),
        stamp=jnp.asarray(rng.integers(0, 2**40, lead), I64),
        size_b=jnp.asarray(rng.integers(40, 400, lead), I32),
        visited=jnp.asarray(
            np.where(rng.random(nodes.shape) < 0.5, -1, nodes), I32))


def random_acks(rng, rt, lead=()):
    """ACKs that mostly answer a slot of their lane: of the others some
    carry a nonce of 0, a stale ``gen`` or another sender."""
    lanes, q = rt.active.shape
    shape = (lanes,) + lead
    lane = np.arange(lanes).reshape((lanes,) + (1,) * len(lead))
    slot = rng.integers(0, q, shape)
    gen = np.asarray(rt.gen)[lane, slot] & 0x003FFFFF
    dst = np.asarray(rt.dst)[lane, slot]
    kind = rng.integers(0, 8, shape)
    gen = np.where(kind == 1, (gen + 1) & 0x003FFFFF, gen)
    nonce = np.where(kind == 0, 0, 1 + slot + q * gen)
    src = np.where(kind == 2, rng.integers(0, NODES, shape), dst)
    return Ack(valid=jnp.asarray(rng.random(shape) < 0.85),
               nonce=jnp.asarray(nonce, I32), src=jnp.asarray(src, I32))


def both(fn, *args):
    """``fn(module, *args)`` of the module and of the oracle, jitted and
    vmapped over the lanes."""
    return tuple(jax.jit(jax.vmap(functools.partial(fn, m)))(*args)
                 for m in (rt_mod, oracle))


def changed(got, before, leaf):
    return (np.asarray(getattr(got, leaf))
            != np.asarray(getattr(before, leaf))).sum()


# -------------------------------------------------------------- forward


@pytest.mark.parametrize("cfg_name", sorted(CONFIGS))
def test_forward(cfg_name):
    cfg = CONFIGS[cfg_name]
    rng = np.random.default_rng(47)
    rt = random_state(rng, cfg, cfg_name)
    en = jnp.asarray(rng.random(LANES) < 0.7)
    now = jnp.asarray(rng.integers(0, 2**40, LANES), I64)
    nxt = jnp.asarray(rng.integers(0, NODES, LANES), I32)
    hop = random_hop(rng, (LANES,))

    def run(m, rt, en, now, nxt, hop):
        ob = Outbox()
        return m.forward(rt, ob, en, now, nxt, cfg=cfg, **hop), ob.sent

    got, want = both(run, rt, en, now, nxt, hop)
    assert_same(got, want)
    rt2, sent = got
    assert len(sent) == 1
    if not cfg.route_acks:
        assert_same(rt2, rt)
        return
    # the reference is no identity: an enabled lane with a free slot
    # parks its hop in the first one, under a nonce that names it
    free = ~np.asarray(rt.active)
    lanes = np.flatnonzero(np.asarray(en) & free.any(1))
    assert lanes.size > LANES // 3
    s = free.argmax(1)[lanes]
    assert np.asarray(rt2.active)[lanes, s].all()
    np.testing.assert_array_equal(np.asarray(rt2.gen)[lanes, s],
                                  np.asarray(rt.gen)[lanes, s] + 1)
    np.testing.assert_array_equal(np.asarray(rt2.dst)[lanes, s],
                                  np.asarray(nxt)[lanes])
    np.testing.assert_array_equal(
        np.asarray(rt2.visited)[lanes, s],
        np.asarray(hop["visited"])[lanes, :VISITED_CAP[cfg_name]])
    nonce = np.asarray(sent[0][1]["nonce"])
    np.testing.assert_array_equal((nonce[lanes] - 1) % cfg.slots, s)
    # a full table sends the hop un-ACKed, a disabled lane nothing, and
    # both keep every leaf
    idle = np.setdiff1d(np.arange(LANES), lanes)
    assert (np.asarray(en) & ~free.any(1)).sum() > LANES // 16
    assert not nonce[idle].any()
    assert_same(jax.tree.map(lambda x: x[idle], rt2),
                jax.tree.map(lambda x: x[idle], rt))


def test_forward_python_scalars():
    """The originator's call passes constants for ``b``, ``hops`` and
    ``size_b`` and a bool cast for ``c``."""
    cfg = CONFIGS["pastry"]
    rt = jax.tree.map(lambda x: x[0], random_state(
        np.random.default_rng(1), cfg, "pastry", lanes=1, full=0.0))
    rt = dataclasses.replace(rt, active=rt.active.at[2].set(False))
    kw = dict(key=jnp.arange(KL, dtype=jnp.uint32), inner=3, a=jnp.int32(9),
              b=0, c=jnp.bool_(True).astype(I32), hops=1, stamp=jnp.int64(7),
              size_b=100, visited=jnp.arange(RMAX, dtype=I32), cfg=cfg)
    args = (jnp.bool_(True), jnp.int64(7), jnp.int32(5))
    obs = Outbox(), Outbox()
    assert_same(rt_mod.forward(rt, obs[0], *args, **kw),
                oracle.forward(rt, obs[1], *args, **kw))
    assert_same(*(jax.tree.map(jnp.asarray, ob.sent) for ob in obs))


@pytest.mark.parametrize("cfg_name", sorted(CONFIGS))
def test_forward_batch(cfg_name):
    cfg = CONFIGS[cfg_name]
    rng = np.random.default_rng(53)
    rt = random_state(rng, cfg, cfg_name, full=0.1)
    en = jnp.asarray(rng.random((LANES, R_IN)) < 0.6)
    now = jnp.asarray(rng.integers(0, 2**40, (LANES, R_IN)), I64)
    nxt = jnp.asarray(rng.integers(0, NODES, (LANES, R_IN)), I32)
    hop = random_hop(rng, (LANES, R_IN))
    # ``prepass`` hands one encapsulated kind to all lanes at some sites
    hop["inner"] = hop["inner"][:, 0]

    def run(m, rt, en, now, nxt, hop):
        ob = Outbox()
        return m.forward_batch(rt, ob, en, now, nxt, cfg=cfg, **hop), ob.sent

    got, want = both(run, rt, en, now, nxt, hop)
    assert_same(got, want)
    rt2, sent = got
    assert len(sent) == 1
    if not cfg.route_acks:
        assert_same(rt2, rt)
        return
    # the j-th enabled lane holds the j-th free slot; lanes past the
    # free slots go un-ACKed
    free = ~np.asarray(rt.active)
    n_park = np.minimum(np.asarray(en).sum(1), free.sum(1))
    np.testing.assert_array_equal(
        np.asarray(rt2.active).sum(1), np.asarray(rt.active).sum(1) + n_park)
    nonce = np.asarray(sent[0][1]["nonce"])
    np.testing.assert_array_equal((nonce > 0).sum(1), n_park)
    assert (np.asarray(en).sum(1) > free.sum(1)).sum() > LANES // 16
    assert (n_park >= 2).sum() > LANES // 4
    for lane in np.flatnonzero(n_park >= 2)[:16]:
        slots = (nonce[lane][nonce[lane] > 0] - 1) % cfg.slots
        np.testing.assert_array_equal(slots, np.flatnonzero(free[lane])[
            :n_park[lane]])
        np.testing.assert_array_equal(
            np.asarray(rt2.dst)[lane, slots],
            np.asarray(nxt)[lane][nonce[lane] > 0])


# ----------------------------------------------------------------- ACKs


@pytest.mark.parametrize("cfg_name", ["pastry", "eight_slots"])
def test_on_ack(cfg_name):
    cfg = CONFIGS[cfg_name]
    rng = np.random.default_rng(59)
    rt = random_state(rng, cfg, cfg_name)
    ack = random_acks(rng, rt)
    got, want = both(lambda m, rt, ack: m.on_ack(rt, ack), rt, ack)
    assert_same(got, want)
    freed = changed(got, rt, "active")
    assert LANES // 8 < freed < LANES // 2 + LANES // 8
    assert changed(got, rt, "t_to") >= freed


@pytest.mark.parametrize("cfg_name", ["pastry", "eight_slots"])
def test_on_acks(cfg_name):
    cfg = CONFIGS[cfg_name]
    rng = np.random.default_rng(61)
    rt = random_state(rng, cfg, cfg_name)
    acks = random_acks(rng, rt, lead=(R_IN,))
    got, want = both(lambda m, rt, acks: m.on_acks(rt, acks), rt, acks)
    assert_same(got, want)
    assert changed(got, rt, "active") > LANES // 2


def test_two_acks_for_one_slot():
    """Both inbox slots answer ACK slot 1: it is freed once, and the
    other slots keep their state."""
    cfg = CONFIGS["pastry"]
    rt = random_state(np.random.default_rng(3), cfg, "pastry", lanes=1)
    rt = dataclasses.replace(rt, active=rt.active.at[0].set(True))
    nonce = 1 + 1 + cfg.slots * (int(rt.gen[0, 1]) & 0x003FFFFF)
    acks = Ack(valid=jnp.asarray([[True, False, True, True]]),
               nonce=jnp.asarray([[nonce, nonce, nonce, 0]], I32),
               src=jnp.broadcast_to(rt.dst[:, 1:2], (1, R_IN)))
    got, want = both(lambda m, rt, acks: m.on_acks(rt, acks), rt, acks)
    assert_same(got, want)
    assert np.asarray(got.active[0]).tolist() == [True, False, True, True]
    assert int(got.t_to[0, 1]) == int(rt_mod.T_INF)
    others = np.asarray([0, 2, 3])
    np.testing.assert_array_equal(np.asarray(got.t_to)[0, others],
                                  np.asarray(rt.t_to)[0, others])


# ------------------------------------------------------ timeouts' slots


@pytest.mark.parametrize("cfg_name", ["pastry", "source_koorde"])
def test_reforward_and_drop_slot(cfg_name):
    cfg = CONFIGS[cfg_name]
    rng = np.random.default_rng(67)
    rt = random_state(rng, cfg, cfg_name)
    en = jnp.asarray(rng.random((LANES, cfg.slots)) < 0.5)
    now = jnp.asarray(rng.integers(0, 2**40, LANES), I64)
    nxt = jnp.asarray(np.where(rng.random((LANES, cfg.slots)) < 0.2, -1,
                               rng.integers(0, NODES, (LANES, cfg.slots))),
                      I32)

    def run(m, rt, en, now, nxt):
        """Pastry's loop over the slots: re-send or give up, slot by slot."""
        ob = Outbox()
        for qi in range(cfg.slots):
            rt = m.reforward(rt, ob, qi, en[qi], now, nxt[qi], cfg)
            rt = m.drop_slot(rt, qi, en[qi] & (nxt[qi] == NO_NODE))
        return rt, ob.sent

    got, want = both(run, rt, en, now, nxt)
    assert_same(got, want)
    rt2, sent = got
    assert len(sent) == cfg.slots
    sends = np.asarray(en) & (np.asarray(nxt) != NO_NODE)
    np.testing.assert_array_equal(
        np.asarray(rt2.gen), np.asarray(rt.gen) + sends)
    np.testing.assert_array_equal(
        np.asarray(rt2.active),
        np.asarray(rt.active) & ~(np.asarray(en) & ~sends))
    assert sends.sum() > LANES and (np.asarray(en) & ~sends).sum() > LANES // 8


# -------------------------------------------------------- visited lists


@pytest.mark.parametrize("vcap", [1, 4, RMAX])
def test_append_visited(vcap):
    rng = np.random.default_rng(71)
    n_vis = rng.integers(0, vcap + 1, (LANES, R_IN))
    nodes = rng.integers(0, NODES, (LANES, R_IN, vcap))
    visited = jnp.asarray(
        np.where(np.arange(vcap) < n_vis[..., None], nodes, -1), I32)
    me = jnp.asarray(rng.integers(0, NODES, LANES), I32)
    en = jnp.asarray(rng.random((LANES, R_IN)) < 0.6)
    got, want = both(
        lambda m, visited, me, en: m.append_visited(visited, me, en),
        visited, me, en)
    assert_same(got, want)
    # first empty place, or the last of a full list
    at = np.minimum(n_vis, vcap - 1)
    lane, r = np.nonzero(np.asarray(en))
    np.testing.assert_array_equal(np.asarray(got)[lane, r, at[lane, r]],
                                  np.asarray(me)[lane])
    assert (np.asarray(got) != np.asarray(visited)).sum() <= len(lane)
    assert (n_vis == vcap).sum() > 8


def test_the_module_indexes_no_slot_write():
    """The rule of the module's docstring, read off its source: the
    indexed writes left are constants at static positions (a fresh
    visited list's first entry, the candidates' ext tail)."""
    code = inspect.getsource(rt_mod).split('"""', 2)[2]
    left = [line for line in code.splitlines() if ".at[" in line]
    assert left and all("vis0" in line or "res_b" in line for line in left)
    # and a slot's word is read at a static position alone (``reforward``)
    assert set(re.findall(r"rt\.\w+\[(\w+)", code)) == {"slot"}
