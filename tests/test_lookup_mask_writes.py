"""``common/lookup.py`` writes a lookup slot by a one-hot mask (ISSUE 42).

Each rewritten function against the scatter form it replaces
(``tests/lookup_scatter_oracle.py``: the functions of the commit
before), under ``vmap`` over random lanes: every leaf equal, dtype and
all, and whatever else the function returns or sends.
"""

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lookup_scatter_oracle as oracle
from oversim_tpu.common import lookup as lk_mod
from oversim_tpu.common.lookup import I32, I64, NO_NODE, LookupConfig

LANES = 128
KL = 5
NODES = 40          # node slots drawn for frontiers, pending RPCs, senders
R_IN = 4            # inbox slots of a batch of responses

CONFIGS = {
    "chord": LookupConfig(),
    "kademlia": LookupConfig(merge=True, parallel_rpcs=3),
    "koorde": LookupConfig(ext_words=2),
    "exhaustive": LookupConfig(merge=True, parallel_rpcs=2, exhaustive=True,
                               ext_words=2),
    "skademlia": LookupConfig(merge=True, parallel_rpcs=3,
                              verify_siblings=True),
    "broose": LookupConfig(slots=8, frontier=4, visited=8, parallel_rpcs=2,
                           retries=1, prox_aware=True),
}


class Msgs(NamedTuple):
    valid: jnp.ndarray
    a: jnp.ndarray
    b: jnp.ndarray
    c: jnp.ndarray
    src: jnp.ndarray
    nodes: jnp.ndarray
    t_deliver: jnp.ndarray


class Outbox:
    """Records what ``pump`` sends; the engine's is not needed."""

    def __init__(self):
        self.sent = []

    def send(self, *args, **kw):
        self.sent.append((args, kw))


def metric(nodes, target):
    return nodes.astype(jnp.uint32)[:, None] ^ target[None, :]


def random_state(rng, cfg, lanes=LANES):
    """[lanes, L, ...] lookup states with every leaf drawn at random, so
    a write that strays from its slot or column shows in some leaf."""
    shapes = jax.eval_shape(lambda: lk_mod.init(cfg, KL))

    def draw(name, leaf):
        shape = (lanes,) + leaf.shape
        if leaf.dtype == bool:
            return rng.random(shape) < (0.75 if name == "active" else 0.2)
        if name in ("frontier", "visited", "pending_dst", "pend_prov",
                    "fr_src", "result", "results", "ver_dst"):
            nodes = rng.integers(0, NODES, shape)
            return np.where(rng.random(shape) < 0.4, -1, nodes)
        if name == "fr_flags":
            return rng.integers(0, 4, shape)
        if name == "hops":
            return rng.integers(0, lk_mod.MAX_HOPS + 2, shape)
        if leaf.dtype == jnp.int64:
            return rng.integers(0, 2**40, shape)
        return rng.integers(0, 50, shape)

    return lk_mod.LookupState(**{
        f.name: jnp.asarray(draw(f.name, getattr(shapes, f.name)),
                            getattr(shapes, f.name).dtype)
        for f in dataclasses.fields(shapes)})


def random_msgs(rng, lk, r_in=R_IN, answer=0.8):
    """[lanes, R] response batches that mostly answer a pending RPC (or
    the staged verification) of the slot they name."""
    lanes, l_dim, r_rpc = lk.pending_dst.shape
    lane = np.arange(lanes)[:, None]
    a = rng.integers(-1, l_dim + 1, (lanes, r_in))
    l_r = np.clip(a, 0, l_dim - 1)
    col = rng.integers(0, r_rpc, (lanes, r_in))
    answers = rng.random((lanes, r_in)) < answer
    pend = np.asarray(lk.pending_dst)[lane, l_r, col]
    ver = np.asarray(lk.ver_dst)[lane, l_r]
    src = np.where(answers, np.where(rng.random(pend.shape) < 0.75, pend, ver),
                   rng.integers(-1, NODES, pend.shape))
    gen = np.asarray(lk.gen)[lane, l_r]
    b = np.where(answers, gen, rng.integers(0, 50, gen.shape))
    nodes = rng.integers(0, NODES, (lanes, r_in, 16))
    nodes = np.where(rng.random(nodes.shape) < 0.5, -1, nodes)
    return Msgs(valid=jnp.asarray(rng.random((lanes, r_in)) < 0.85),
                a=jnp.asarray(a, I32), b=jnp.asarray(b, I32),
                c=jnp.asarray(rng.integers(0, 2, (lanes, r_in)), I32),
                src=jnp.asarray(src, I32), nodes=jnp.asarray(nodes, I32),
                t_deliver=jnp.asarray(rng.integers(0, 2**40, (lanes, r_in)),
                                      I64))


def assert_same(got, want):
    got_l, got_t = jax.tree_util.tree_flatten_with_path(got)
    want_l, want_t = jax.tree_util.tree_flatten_with_path(want)
    assert got_t == want_t
    for (path, g), (_, w) in zip(got_l, want_l):
        name = jax.tree_util.keystr(path)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)


def both(fn, *args):
    """``fn(module, *args)`` of the module and of the oracle, jitted and
    vmapped over the lanes."""
    return tuple(jax.jit(jax.vmap(functools.partial(fn, m)))(*args)
                 for m in (lk_mod, oracle))


# ---------------------------------------------------------------- start


@pytest.mark.parametrize("cfg_name", ["chord", "koorde", "broose"])
@pytest.mark.parametrize("with_ext", [False, True])
def test_start(cfg_name, with_ext):
    cfg = CONFIGS[cfg_name]
    rng = np.random.default_rng(42)
    lk = random_state(rng, cfg)
    l_dim = cfg.slots
    # every slot, the one past the last too, with ``en`` both ways
    slot = jnp.asarray(np.arange(LANES) % (l_dim + 1), I32)
    en = jnp.asarray(rng.random(LANES) < 0.7)
    purpose = jnp.asarray(rng.integers(0, 9, LANES), I32)
    aux = jnp.asarray(rng.integers(0, 99, LANES), I32)
    target = jnp.asarray(rng.integers(0, 2**32, (LANES, KL)), jnp.uint32)
    seed = jnp.asarray(rng.integers(-1, NODES, (LANES, 12)), I32)
    now = jnp.asarray(rng.integers(0, 2**40, LANES), I64)
    ext = jnp.asarray(rng.integers(0, 99, (LANES, cfg.ext_words)), I32)

    def run(m, lk, en, slot, purpose, aux, target, seed, now, ext):
        return m.start(lk, en, slot, purpose, aux, target, seed, now, cfg,
                       ext=ext if with_ext else None)

    got, want = both(run, lk, en, slot, purpose, aux, target, seed, now, ext)
    assert_same(got, want)
    # the reference is no identity: an enabled lane's slot is occupied
    lanes = np.flatnonzero(np.asarray(en) & (np.asarray(slot) < l_dim))
    assert lanes.size > LANES // 3
    s = np.asarray(slot)[lanes]
    assert np.asarray(got.active)[lanes, s].all()
    np.testing.assert_array_equal(np.asarray(got.gen)[lanes, s],
                                  np.asarray(lk.gen)[lanes, s] + 1)
    np.testing.assert_array_equal(np.asarray(got.t0)[lanes, s],
                                  np.asarray(now)[lanes])
    # a disabled lane and a slot past the last keep every leaf
    idle = np.setdiff1d(np.arange(LANES), lanes)
    assert idle.size
    assert_same(jax.tree.map(lambda x: x[idle], got),
                jax.tree.map(lambda x: x[idle], lk))


def test_start_python_scalars():
    """The call sites pass Python constants for ``purpose`` and ``aux``."""
    cfg = CONFIGS["chord"]
    lk = jax.tree.map(lambda x: x[0], random_state(
        np.random.default_rng(1), cfg, lanes=1))
    args = (jnp.bool_(True), jnp.int32(2), 3, 0,
            jnp.arange(KL, dtype=jnp.uint32), jnp.arange(8, dtype=I32),
            jnp.int64(7), cfg)
    assert_same(lk_mod.start(lk, *args), oracle.start(lk, *args))


# ------------------------------------------------------------ responses


@pytest.mark.parametrize(
    "cfg_name", ["chord", "kademlia", "koorde", "exhaustive", "skademlia"])
def test_on_responses(cfg_name):
    cfg = CONFIGS[cfg_name]
    rng = np.random.default_rng(7)
    lk = random_state(rng, cfg)
    msgs = random_msgs(rng, lk)

    got, want = both(
        lambda m, lk, msgs: m.on_responses(lk, msgs, metric, cfg), lk, msgs)
    assert_same(got, want)
    assert (np.asarray(got.hops) != np.asarray(lk.hops)).sum() > LANES // 4


def _two_for_one_slot(cfg, same_sender):
    """One lane whose inbox slots 0 and 2 both answer lookup slot 1: two
    pending RPCs of it, or one of them twice."""
    rng = np.random.default_rng(3)
    lk = random_state(rng, cfg, lanes=1)
    lk = dataclasses.replace(
        lk,
        active=lk.active.at[0, 1].set(True),
        done=lk.done.at[0, 1].set(False),
        hops=lk.hops.at[0, 1].set(5),
        pending_dst=lk.pending_dst.at[0, 1].set(
            jnp.asarray([11, 12, 13], I32)))
    msgs = random_msgs(rng, lk, answer=0.0)
    srcs = [11, 11] if same_sender else [11, 13]
    msgs = msgs._replace(
        valid=jnp.asarray([[True, False, True, False]]),
        a=msgs.a.at[0, 0].set(1).at[0, 2].set(1),
        b=msgs.b.at[0, 0].set(lk.gen[0, 1]).at[0, 2].set(lk.gen[0, 1]),
        src=msgs.src.at[0, 0].set(srcs[0]).at[0, 2].set(srcs[1]))
    return lk, msgs


@pytest.mark.parametrize("same_sender,hops,left", [
    (False, 2, [NO_NODE, 12, NO_NODE]),
    (True, 1, [NO_NODE, 12, 13]),
], ids=["two_responses_one_slot", "duplicate_response"])
def test_on_responses_two_messages_for_one_slot(same_sender, hops, left):
    cfg = CONFIGS["kademlia"]
    lk, msgs = _two_for_one_slot(cfg, same_sender)

    got, want = both(
        lambda m, lk, msgs: m.on_responses(lk, msgs, metric, cfg), lk, msgs)
    assert_same(got, want)
    assert int(got.hops[0, 1]) == 5 + hops
    assert np.asarray(got.pending_dst[0, 1]).tolist() == [int(x) for x in left]
    others = np.asarray([0, 2, 3])
    np.testing.assert_array_equal(np.asarray(got.hops)[0, others],
                                  np.asarray(lk.hops)[0, others])


@pytest.mark.parametrize(
    "cfg_name", ["chord", "kademlia", "koorde", "exhaustive", "skademlia"])
def test_on_response(cfg_name):
    """The one-message form (Pastry, Broose and EpiChord fold it over
    their inbox)."""
    cfg = CONFIGS[cfg_name]
    rng = np.random.default_rng(11)
    lk = random_state(rng, cfg)
    msgs = random_msgs(rng, lk, r_in=1)
    msg = jax.tree.map(lambda x: x[:, 0], msgs)

    got, want = both(
        lambda m, lk, msg: m.on_response(lk, msg, metric, cfg), lk, msg)
    assert_same(got, want)
    assert (np.asarray(got.hops) != np.asarray(lk.hops)).sum() > LANES // 16


def test_on_pongs():
    cfg = CONFIGS["skademlia"]
    rng = np.random.default_rng(13)
    lk = random_state(rng, cfg)
    msgs = random_msgs(rng, lk)
    # the scatter form leaves the winner of two pongs for one slot to
    # the backend: keep the first of each lane's slots
    a = np.clip(np.asarray(msgs.a), 0, cfg.slots - 1)
    first = np.stack([np.r_[True, [a[i, r] not in a[i, :r]
                                   for r in range(1, R_IN)]]
                      for i in range(LANES)])
    msgs = msgs._replace(valid=msgs.valid & jnp.asarray(first))

    got, want = both(lambda m, lk, msgs: m.on_pongs(lk, msgs, cfg), lk, msgs)
    assert_same(got, want)
    assert (np.asarray(got.done) != np.asarray(lk.done)).sum() > LANES // 16
    assert lk_mod.on_pongs(lk, msgs, CONFIGS["chord"]) is lk


def test_on_pongs_lowest_inbox_slot_wins():
    cfg = CONFIGS["skademlia"]
    lk, msgs = _two_for_one_slot(cfg, same_sender=True)
    lk = dataclasses.replace(lk, ver_dst=lk.ver_dst.at[0, 1].set(11))
    got = jax.vmap(lambda lk, msgs: lk_mod.on_pongs(lk, msgs, cfg))(lk, msgs)
    assert bool(got.done[0, 1]) and bool(got.success[0, 1])
    assert int(got.t_done[0, 1]) == int(msgs.t_deliver[0, 0])
    assert int(got.ver_dst[0, 1]) == NO_NODE


# ----------------------------------------------------------------- pump


def _pump_both(cfg, lk, node_idx, now):
    kw = {}
    if cfg.prox_aware:
        kw = dict(prox_fn=lambda fr: (fr % 7).astype(jnp.float32) * 0.01,
                  timeout_fn=lambda dst: (dst.astype(I64) + 1) * 1_000_000)

    def run(m, lk, node_idx, now):
        ob = Outbox()
        out = m.pump(lk, ob, None, node_idx, now, None, cfg, **kw)
        return out, ob.sent

    return both(run, lk, node_idx, now)


@pytest.mark.parametrize("cfg_name", sorted(CONFIGS))
def test_pump(cfg_name):
    cfg = CONFIGS[cfg_name]
    rng = np.random.default_rng(17)
    lk = random_state(rng, cfg)
    node_idx = jnp.asarray(rng.integers(0, NODES, LANES), I32)
    now = jnp.asarray(rng.integers(0, 2**40, LANES), I64)
    got, want = _pump_both(cfg, lk, node_idx, now)
    assert_same(got, want)
    (_, fired), sent = got
    assert np.asarray(fired).sum() > LANES // 4
    assert len(sent) >= cfg.parallel_rpcs


def test_pump_with_no_free_column():
    cfg = CONFIGS["kademlia"]
    rng = np.random.default_rng(19)
    lk = random_state(rng, cfg)
    lk = dataclasses.replace(lk, pending_dst=jnp.where(
        lk.pending_dst == NO_NODE, 7, lk.pending_dst))
    node_idx = jnp.asarray(rng.integers(0, NODES, LANES), I32)
    now = jnp.asarray(rng.integers(0, 2**40, LANES), I64)
    got, want = _pump_both(cfg, lk, node_idx, now)
    assert_same(got, want)
    (lk2, fired), _ = got
    assert not np.asarray(fired).any()
    for leaf in ("visited", "vis_n", "fr_flags", "pending_dst", "pend_prov",
                 "t_sent", "t_to", "retry"):
        np.testing.assert_array_equal(np.asarray(getattr(lk2, leaf)),
                                      np.asarray(getattr(lk, leaf)), leaf)


def test_the_module_indexes_no_write():
    """The rule of the module's docstring, read off its source."""
    import inspect
    import re
    code = inspect.getsource(lk_mod).split('"""', 2)[2]
    assert not re.findall(r"\.at\[", code)
