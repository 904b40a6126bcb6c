"""Bamboo (Pastry's periodic-maintenance variant) in
``bamboo1000.kbr60``'s mode, on both tick planes (ISSUE 45).

``PastryLogic.awake_set_exact`` rests on this module: the cell's overlay
lines (semi-recursive routing with per-hop ACKs, the three upkeep tasks
at the file's intervals) at N = 16 under upstream's fill of one join
every 0.1 s and the cells' 0.2 s window, run on the dense sweep and on
the awake-set plane from one seed; every leaf of the state has to be
equal at the fill's end and after a steady stretch.  The two programs
are the module's only ones: test_pastry.py's checks (imported and so
collected here against THIS module's ``pastry_run``, the dense run), the
ring test, the recounts and the local-tuning test read the same runs or
step the same compiled program.  Pastry's defaults and the iterative
mode get the identity by hand (benchmark/tests/test_bamboo_planes.py),
as does N = 128 over a whole fill.
"""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from oversim_tpu.common import route as rt_mod
from oversim_tpu.config.ini import IniFile
from oversim_tpu.config.scenario import ScenarioError, build_simulation
from oversim_tpu.core import keys as K
from oversim_tpu.engine.sim import EngineParams
from oversim_tpu.overlay import pastry
from test_pastry import (  # noqa: F401  (collected here)
    INBOX_SLOTS, test_all_ready, test_deliveries,
    test_leafsets_are_ring_neighbors, test_no_engine_losses)
from test_zz_sparse import _assert_tree_equal, _strip_sparse

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 16
CHUNK = 50              # 10 s of 0.2 s windows
FILL_CHUNKS, MID_CHUNKS, STEADY_CHUNKS = 1, 4, 3
# counters open at second 40 (the last join is created at 1.5; one whose
# first lookup fails waits out a joinTimeout of 20 s), the run ends at 80
TRANSITION = 38.5
TEST_INTERVAL = 5.0     # a payload every 5 s a node: some 128 counted


def cell_ini():
    """``bamboo1000``'s own ini text."""
    with open(os.path.join(ROOT, "benchmark/configs/bamboo1000.json")) as f:
        return json.load(f)["ini"]


def _sim(tick_impl):
    ini = IniFile.loads("\n".join(cell_ini()))
    section = ini.with_overrides("General", {
        "**.targetOverlayTerminalNum": N,
        "**.tier1*.kbrTestApp.testMsgInterval": TEST_INTERVAL})
    ep = EngineParams(window=0.2, inbox_slots=INBOX_SLOTS, pool_factor=4,
                      transition_time=TRANSITION, tick_impl=tick_impl)
    return build_simulation(ini, section, ep)


def leaf_faults(st):
    """(READY nodes, leaf entries that are not the ring's, cycles that
    following ``leaf_cw[:, 0]`` ends in) against the sorted keys."""
    keys = [K.to_int(k) for k in np.asarray(st.node_keys)]
    ready = np.nonzero(np.asarray(st.logic.state) == pastry.READY)[0]
    order = sorted(ready, key=lambda i: keys[i])
    m = len(order)
    cw, ccw = np.asarray(st.logic.leaf_cw), np.asarray(st.logic.leaf_ccw)
    h = min(cw.shape[1], m - 1)
    wrong = 0
    for p, i in enumerate(order):
        for k in range(1, h + 1):
            wrong += cw[i, k - 1] != order[(p + k) % m]
            wrong += ccw[i, k - 1] != order[(p - k) % m]
    seen, cycles = set(), 0
    for i in order:
        path = []
        while i not in seen and i >= 0:
            seen.add(i)
            path.append(i)
            i = int(cw[i, 0])
        cycles += i in path
    return m, int(wrong), cycles


@pytest.fixture(scope="module")
def runs():
    """Both planes from one seed: ``(sim, fill, steady, mid)``, the
    state at second 10 (the last node is created at 1.5; the joins are
    still under way), at second 80 after a steady stretch, and at second
    50 (the counters are open: the recounts' opening)."""
    out = {}
    for impl in ("dense", "sparse"):
        sim = _sim(impl)
        s = sim.init(seed=5)
        got = []
        for chunks in (FILL_CHUNKS, MID_CHUNKS, STEADY_CHUNKS):
            for _ in range(chunks):
                s = sim.run_chunk(s, CHUNK)
            got.append(jax.device_get(s))
        out[impl] = (sim, got[0], got[2], got[1])
    return out


@pytest.fixture(scope="module")
def pastry_run(runs):
    """What test_pastry.py's checks read: the dense sweep's run."""
    sim, _, steady, _ = runs["dense"]
    return sim, steady


def test_the_cells_mode_is_what_runs(runs):
    sim = runs["sparse"][0]
    assert type(sim.logic).__name__ == "BambooLogic"
    p = sim.logic.p
    assert (p.routing_mode, p.route_acks) == ("semi-recursive", True)
    assert (p.leafset_interval, p.local_tuning_interval,
            p.tuning_interval, p.rec_redundant) == (4.0, 10.0, 20.0, 3)
    assert sim.logic.awake_set_exact and sim.tick_impl == "sparse"
    assert runs["dense"][0].tick_impl == "dense"
    # the default plane of the deployment is the awake-set plane
    assert _sim("auto").tick_impl == "sparse"


@pytest.mark.parametrize("at", ["fill", "steady", "mid"])
def test_identity_on_both_planes(runs, at):
    """Every leaf, bit for bit: an idle node is a fixed point of the
    Pastry/Bamboo step with the routed path and its ACK table on."""
    which = ("fill", "steady", "mid").index(at) + 1
    dense, sparse = runs["dense"][which], runs["sparse"][which]
    _assert_tree_equal(dense, _strip_sparse(sparse))
    ticks = CHUNK * (FILL_CHUNKS, FILL_CHUNKS + MID_CHUNKS + STEADY_CHUNKS,
                     FILL_CHUNKS + MID_CHUNKS)[which - 1]
    assert int(sparse.tick) == ticks
    # ... and idle nodes WERE skipped
    assert 0 < int(sparse.counters["awake_nodes"]) < N * ticks // 2


def test_two_joiners_of_one_tick_end_in_one_ring(runs):
    """Upstream's fill under the cells' window creates two nodes a tick:
    ONE starts the overlay (``ring_starter``), and by the fill's end
    every leaf set is the ring's, in one cycle."""
    _, fill, steady, _ = runs["sparse"]
    t_born = np.sort(np.asarray(steady.churn.t_born))
    first_tick = t_born < int(0.2e9)
    assert first_tick.sum() >= 2, "no two creations in the first tick"
    assert 2 <= leaf_faults(fill)[0] <= N     # the joins under way
    assert leaf_faults(steady) == (N, 0, 1)
    lost = {k: int(v) for k, v in steady.counters.items()
            if k.endswith(("_lost", "_overflow")) and int(v)}
    assert not lost


def test_payloads_are_routed_hop_by_hop_and_every_hop_recounted(runs):
    """Between second 50 and second 80 (the counters open at 40): every
    hop sent ends as ACKed, timed out, un-ACKed or pending, every
    payload handed to the routed path as delivered, dropped or in
    flight."""
    sim, _, st, mid = runs["sparse"]
    out = sim.summary(st)
    assert out["kbr_sent"] > 100
    assert out["kbr_sent"] - 2 <= out["kbr_delivered"] <= out["kbr_sent"]
    assert out["route_ack_timeouts"] == 0
    assert out["kbr_wrong_node"] == 0 and out["route_dropped"] == 0
    for name in pastry.UPKEEP_COUNTERS + rt_mod.ROUTE_COUNTERS:
        assert name in out, name

    def d(name):
        return int(st.stats["c:" + name]) - int(mid.stats["c:" + name])

    def pending(s):
        return int(np.asarray(s.logic.rr.active).sum())

    def in_flight(s):
        kinds = np.asarray(s.pool.kind)[np.asarray(s.pool.valid)]
        return int((kinds == 7).sum())          # wire.KBR_ROUTE

    assert d("bamboo_app_routes") > 50
    assert d("route_forwarded") > d("route_delivered") > 50
    assert d("route_forwarded") == (
        d("route_acked") + d("route_ack_timeouts")
        + d("route_unacked_table_full") + pending(st) - pending(mid))
    assert d("bamboo_app_routes") == (
        d("route_delivered") + d("route_dropped_no_candidate")
        + d("route_dropped_hop_bound") + in_flight(st) - in_flight(mid))


def test_upkeep_runs_at_the_files_intervals(runs):
    """Counters open at the measurement phase (second 40): over the
    40 s to the run's end each READY node fires each timer every
    interval."""
    sim, _, st, _ = runs["sparse"]
    out = sim.summary(st)
    span = 80.0 - (1.5 + TRANSITION)
    for name, every in (("bamboo_ls_rounds", 4.0),
                        ("bamboo_lt_probes", 10.0),
                        ("bamboo_gt_lookups", 20.0)):
        want = N * span / every
        assert abs(out[name] - want) <= N, (name, out[name], want)
    assert out["bamboo_state_msgs"] >= out["bamboo_ls_rounds"]


def test_local_tuning_fills_a_row_that_nothing_else_would(runs):
    """Node ``a`` holds ONE routing-table entry and no leaf; every timer
    but its local-tuning timer is off and so is every other node's.  The
    probe asks that entry for its row, and the reply brings what the
    entry holds there: ``a``'s table is filled from it (and, the clock
    running on from event to event, from the rows of what it learned),
    and nothing else ran."""
    sim, _, st, _ = runs["sparse"]
    lg = st.logic
    keys = np.asarray(st.node_keys)
    t_inf = np.int64(2**62)
    a = 0
    rt_a = np.full(lg.rt.shape[1:], -1, np.int32)
    # a peer of row 0 whose own row 0 is well filled
    rows0 = (np.asarray(lg.rt)[:, 0] >= 0).sum(axis=1)
    digit0 = np.asarray(keys[:, 0] >> 28)
    peers = [i for i in np.argsort(-rows0)
             if i != a and digit0[i] != digit0[a]]
    b = int(peers[0])
    rt_a[0, digit0[b]] = b
    n = lg.state.shape[0]

    def put(x, row):
        x = np.array(x)
        x[a] = row
        return x

    off = np.full((n,), t_inf, np.int64)
    logic = dataclasses.replace(
        lg,
        rt=put(lg.rt, rt_a),
        rt_rtt=put(lg.rt_rtt, np.full(rt_a.shape, 2**30, np.int32)),
        leaf_cw=put(lg.leaf_cw, -1), leaf_ccw=put(lg.leaf_ccw, -1),
        t_ls=off, t_gt=off, t_lt=put(off, np.int64(st.t_now)),
        app=dataclasses.replace(lg.app, t_test=off))
    s = dataclasses.replace(
        st, logic=logic,
        pool=dataclasses.replace(st.pool,
                                 valid=np.zeros_like(st.pool.valid)))
    s = sim.run_chunk(jax.device_put(s), CHUNK)
    # every probe is a's (one every 10 s of a clock that idles ahead)
    probes = int(s.stats["c:bamboo_lt_probes"]) - int(
        st.stats["c:bamboo_lt_probes"])
    assert probes >= 1
    assert probes == (int(s.logic.t_lt[a]) - int(st.t_now)) // int(10e9)
    for other in ("bamboo_ls_rounds", "bamboo_gt_lookups", "kbr_sent"):
        assert int(s.stats["c:" + other]) == int(st.stats["c:" + other])
    got = np.asarray(s.logic.rt)[a]
    theirs = {int(e) for e in np.asarray(lg.rt)[b, 0] if e >= 0} - {a}
    assert len(theirs) >= 3
    held = {int(e) for e in got.reshape(-1) if e >= 0}
    assert theirs | {b} <= held
    # each in the row and column its key earns against a's
    for row, col in zip(*np.nonzero(got >= 0)):
        e = got[row, col]
        shared = 0
        while (keys[e, 0] >> (28 - 4 * shared)) & 15 == (
                keys[a, 0] >> (28 - 4 * shared)) & 15:
            shared += 1
        assert (row, col) == (shared, (keys[e, 0] >> (28 - 4 * shared)) & 15)
    # the responder's entry carries the RTT the reply measured
    assert np.asarray(s.logic.rt_rtt)[a, 0, digit0[b]] < 2**30


def test_route_next_event_is_the_earliest_pending_ack(runs):
    """On the awake-set plane a node with a pending ACK and no message
    wakes by ``route.next_event`` alone: every active slot has a finite
    timeout, and the logic's ``next_event`` is at or before it."""
    sim, _, st, _ = runs["sparse"]
    rr = st.logic.rr
    active = np.asarray(rr.active)
    t_to = np.asarray(rr.t_to)
    assert (t_to[active] < 2**62).all()
    assert (t_to[~active] == 2**62).all()
    nxt = np.asarray(sim.logic.next_event(st.logic))
    per_node = np.where(active, t_to, 2**62).min(axis=1)
    assert (nxt <= per_node).all()


# -- the ini states the deployment (config/scenario.py) ---------------------


def test_the_new_ini_keys_reach_pastry_params():
    lines = [ln for ln in cell_ini() if "**.overlay.bamboo." not in ln]
    sim = build_simulation(IniFile.loads("\n".join(lines + [
        "**.targetOverlayTerminalNum = 8",
        "**.overlay.bamboo.bitsPerDigit = 2",
        "**.overlay.bamboo.numberOfLeaves = 4",
        "**.overlay.bamboo.joinTimeout = 7s",
        "**.overlay.bamboo.leafsetMaintenanceInterval = 3s",
        "**.overlay.bamboo.localTuningInterval = 0s",
        "**.overlay.bamboo.globalTuningInterval = 11s",
        "**.overlay.bamboo.routeMsgAcks = false",
        '**.overlay.bamboo.routingType = "iterative"',
        "**.overlay.bamboo.recNumRedundantNodes = 2"])))
    p = sim.logic.p
    assert (p.bits_per_digit, p.num_leaves, p.join_delay) == (2, 4, 7)
    assert (p.leafset_interval, p.local_tuning_interval,
            p.tuning_interval) == (3.0, 0.0, 11.0)
    assert (p.routing_mode, p.route_acks, p.rec_redundant) == (
        "iterative", False, 2)
    assert sim.logic.rcfg.route_acks is False
    # an ini that is silent reads the module's defaults, as before
    quiet = build_simulation(IniFile.loads("\n".join(lines + [
        "**.targetOverlayTerminalNum = 8"]))).logic.p
    assert quiet == dataclasses.replace(pastry.bamboo_params(),
                                        join_delay=20)
    assert pastry.PastryParams().local_tuning_interval == 0.0
    assert pastry.PastryParams().rec_redundant == 4
    with pytest.raises(ScenarioError, match="routingType"):
        build_simulation(IniFile.loads("\n".join(lines + [
            '**.overlay.bamboo.routingType = "full-recursive"'])))


# -- ``_rt_add`` takes its candidates all at once: against taking them in turn --

@dataclasses.dataclass
class _Tables:
    rt: object
    rt_rtt: object


def _rt_add_in_turn(logic, keys, rt, rt_rtt, me, cands, en, rtt):
    """The loop the batched ``_rt_add`` replaced (PR 45), in Python
    integers: one candidate after the other, each against the table the
    one before left."""
    p, bits = logic.p, logic.key_spec.bits
    rt, rt_rtt = rt.copy(), rt_rtt.copy()
    ids = [K.to_int(k) for k in keys]
    for i, c in enumerate(cands):
        if not en[i] or c == me or c < 0:
            continue
        row = min((bits - (ids[me] ^ ids[c]).bit_length())
                  // p.bits_per_digit, p.rows - 1)
        col = (ids[c] >> (bits - p.bits_per_digit * (row + 1))) % p.cols
        c_rtt = int(pastry.RTT_INF) if rtt is None else int(rtt[i])
        same, closer = rt[row, col] == c, c_rtt < rt_rtt[row, col]
        if rt[row, col] < 0 or closer or same:
            rt[row, col] = c
            if not (same and not closer):
                rt_rtt[row, col] = c_rtt
    return rt, rt_rtt


@pytest.mark.parametrize("case", [
    "equal_rtts", "unmeasured", "holders_again", "duplicates", "mixed"])
def test_rt_add_at_once_is_rt_add_in_turn(case):
    """Of the candidates that earn one cell the least RTT wins, the
    earliest among equals, the cell's holder before any: ties, a
    candidate that already holds its cell (with a better and a worse
    RTT), the same candidate twice, candidates switched off and the
    node itself, over tables that are empty, half full and full of
    holders with few distinct RTTs."""
    logic = pastry.BambooLogic()
    p = logic.p
    n, n_c = 48, 25
    rng = np.random.default_rng(sorted(
        ["equal_rtts", "unmeasured", "holders_again", "duplicates",
         "mixed"]).index(case))
    add = jax.jit(lambda keys, rt, rt_rtt, me, cands, en, rtt: (
        lambda st: (st.rt, st.rt_rtt))(logic._rt_add(
            type("Ctx", (), {"keys": keys}), _Tables(rt, rt_rtt), keys[me],
            me, cands, en, rtt)))
    add_unmeasured = jax.jit(lambda keys, rt, rt_rtt, me, cands, en: (
        lambda st: (st.rt, st.rt_rtt))(logic._rt_add(
            type("Ctx", (), {"keys": keys}), _Tables(rt, rt_rtt), keys[me],
            me, cands, en)))
    for trial in range(40):
        # keys that share long prefixes, so that many candidates earn
        # one cell: a few random digits on top of a common stem
        lanes = rng.integers(0, 2**32, (n, logic.key_spec.lanes),
                             dtype=np.uint64).astype(np.uint32)
        lanes[:, 0] = (lanes[:, 0] & np.uint32(0x0FF00000)) | np.uint32(
            0xA0000000)
        me = int(rng.integers(n))
        rt = np.full((p.rows, p.cols), -1, np.int32)
        rt_rtt = np.full((p.rows, p.cols), int(pastry.RTT_INF), np.int32)
        # a table to start from: some nodes taken in turn, few RTT values
        first = rng.integers(0, n, (trial % 3) * 12).astype(np.int32)
        rt, rt_rtt = _rt_add_in_turn(
            logic, lanes, rt, rt_rtt, me, first, np.ones(len(first), bool),
            rng.integers(1, 4, len(first)) * 10)
        cands = rng.integers(0, n, n_c).astype(np.int32)
        en = rng.random(n_c) < 0.85
        rtt = rng.integers(1, 4, n_c).astype(np.int32) * 10
        if case == "equal_rtts":
            rtt[:] = 20
        elif case == "holders_again":
            held = rt[rt >= 0]
            if len(held):
                cands[:len(held[:10])] = held[:10]
        elif case == "duplicates":
            cands[n_c // 2:] = cands[:n_c - n_c // 2]
        cands[rng.integers(n_c)] = me           # the node itself: never added
        cands[rng.integers(n_c)] = -1
        if case == "unmeasured":
            got = add_unmeasured(lanes, rt, rt_rtt, np.int32(me), cands, en)
            want = _rt_add_in_turn(logic, lanes, rt, rt_rtt, me, cands, en,
                                   None)
        else:
            got = add(lanes, rt, rt_rtt, np.int32(me), cands, en, rtt)
            want = _rt_add_in_turn(logic, lanes, rt, rt_rtt, me, cands, en,
                                   rtt)
        np.testing.assert_array_equal(np.asarray(got[0]), want[0],
                                      err_msg=f"{case} trial {trial}: rt")
        np.testing.assert_array_equal(np.asarray(got[1]), want[1],
                                      err_msg=f"{case} trial {trial}: rtt")


# -- the un-ACKed tally's rule is ``forward``'s own ----------------------------------

class _Outbox:
    """What ``route.forward`` sends, kept."""

    def __init__(self):
        self.sent = []

    def send(self, en, now, dst, kind, **fields):
        self.sent.append((bool(en), fields["nonce"]))


@pytest.mark.parametrize("acks", [True, False])
def test_route_parks_says_what_forward_does(acks):
    """``route_unacked_table_full`` is tallied by ``route.parks``, which
    restates ``forward``'s free-slot rule: the two agree at every
    fullness of the ACK table, for a hop that is sent and one that is
    not, with ACKs on and off (where nothing is parked and the hop
    carries no nonce)."""
    cfg = rt_mod.RouteConfig(route_acks=acks)
    kl, v = K.DEFAULT_SPEC.lanes, 8
    for held in range(cfg.slots + 1):
        for en in (True, False):
            rt = rt_mod.init(cfg, kl, v)
            rt = dataclasses.replace(rt, active=rt.active.at[:held].set(True))
            ob = _Outbox()
            out = rt_mod.forward(
                rt, ob, np.bool_(en), np.int64(10**9), np.int32(3),
                key=np.zeros(kl, np.uint32), inner=1, a=0, b=0, c=0, hops=1,
                stamp=np.int64(0), size_b=100,
                visited=np.full(v, -1, np.int32), cfg=cfg)
            parked = int(out.active.sum()) - held
            assert parked == int(rt_mod.parks(rt, np.bool_(en), cfg)), (
                held, en)
            (sent, nonce), = ob.sent
            assert sent == en and (int(nonce) != 0) == bool(parked)
            assert parked == (acks and en and held < cfg.slots)
