"""Network-coordinate + NeighborCache unit tests (reference
src/common/Vivaldi.cc, NeighborCache.cc)."""

import jax
import jax.numpy as jnp
import numpy as np

from oversim_tpu.common import ncs as ncs_mod
from oversim_tpu.common import neighborcache as nc_mod


def _true_rtts(n, rng):
    pos = jax.random.uniform(rng, (n, 2), minval=0.0, maxval=0.1)
    d = pos[:, None, :] - pos[None, :, :]
    return jnp.sqrt(jnp.sum(d * d, axis=-1)), pos


def _embedding_err(st, rtt):
    n = rtt.shape[0]
    pred = ncs_mod.distance(st.coords[:, None, :], st.height[:, None],
                            st.coords[None, :, :], st.height[None, :])
    mask = ~jnp.eye(n, dtype=bool)
    return float(jnp.mean(jnp.abs(pred - rtt)[mask]))


def test_vivaldi_converges():
    """Spring relaxation must shrink the mean embedding error by >5x on a
    synthetic euclidean RTT matrix."""
    n = 24
    p = ncs_mod.NcsParams(ncs_type="vivaldi")
    rng = jax.random.PRNGKey(0)
    rtt, _ = _true_rtts(n, rng)
    st = ncs_mod.init(jax.random.PRNGKey(1), n, p)
    err0 = _embedding_err(st, rtt)

    def one_round(st, r):
        # every node samples one random peer
        peers = jax.random.randint(r, (n,), 0, n)
        me = dict(coords=st.coords, height=st.height, error=st.error,
                  loss=st.loss)
        upd = jax.vmap(
            lambda i, pr: ncs_mod.update(
                jax.tree.map(lambda x: x[i], me),
                jnp.where(pr == i, -1.0, rtt[i, pr]),
                st.coords[pr], st.error[pr], st.height[pr], p))(
                    jnp.arange(n), peers)
        import dataclasses as _dc
        return _dc.replace(st, **upd)

    for i in range(300):
        st = one_round(st, jax.random.PRNGKey(100 + i))
    err1 = _embedding_err(st, rtt)
    assert err1 < err0 / 5, (err0, err1)
    # error estimates must have dropped below the initial 1.0
    assert float(jnp.mean(st.error)) < 0.5


def test_simplencs_is_ground_truth():
    coords = jnp.asarray([[0.0, 0.0], [30.0, 40.0]])
    st = ncs_mod.from_underlay(coords, delay_per_unit=0.001)
    d = ncs_mod.distance(st.coords[0], st.height[0],
                         st.coords[1], st.height[1])
    np.testing.assert_allclose(float(d), 0.05, rtol=1e-5)  # 50 coord units


def test_neighborcache_timeouts():
    nc = nc_mod.init(1, nc_mod.NcParams(capacity=4))
    row = nc_mod.slice_of(nc, 0)
    # unknown peer → default timeout
    assert float(nc_mod.node_timeout(row, jnp.int32(5), 1.5)) == 1.5
    # one sample → mean*1.2*1.3
    row = nc_mod.insert_rtt(row, jnp.int32(5), jnp.float32(0.1),
                            jnp.int64(100))
    t = float(nc_mod.node_timeout(row, jnp.int32(5), 1.5))
    np.testing.assert_allclose(t, 0.1 * 1.2 * 1.3, rtol=1e-5)
    # repeated samples tighten toward mean + 4 var
    for i in range(6):
        row = nc_mod.insert_rtt(row, jnp.int32(5), jnp.float32(0.1),
                                jnp.int64(200 + i))
    t = float(nc_mod.node_timeout(row, jnp.int32(5), 1.5))
    assert 0.1 < t < 0.3
    rtt, alive = nc_mod.get_prox(row, jnp.int32(5))
    np.testing.assert_allclose(float(rtt), 0.1, rtol=1e-4)
    assert bool(alive)


def test_neighborcache_eviction_lru():
    nc = nc_mod.init(1, nc_mod.NcParams(capacity=2))
    row = nc_mod.slice_of(nc, 0)
    row = nc_mod.insert_rtt(row, jnp.int32(1), jnp.float32(0.1), jnp.int64(1))
    row = nc_mod.insert_rtt(row, jnp.int32(2), jnp.float32(0.2), jnp.int64(2))
    row = nc_mod.insert_rtt(row, jnp.int32(3), jnp.float32(0.3), jnp.int64(3))
    r1, _ = nc_mod.get_prox(row, jnp.int32(1))
    r3, _ = nc_mod.get_prox(row, jnp.int32(3))
    assert float(r1) == -1.0      # evicted (oldest)
    assert float(r3) > 0


def test_timeout_state():
    nc = nc_mod.init(1, nc_mod.NcParams(capacity=4))
    row = nc_mod.slice_of(nc, 0)
    row = nc_mod.insert_rtt(row, jnp.int32(7), jnp.float32(0.05),
                            jnp.int64(10))
    row = nc_mod.set_state(row, jnp.int32(7), nc_mod.S_TIMEOUT)
    _, alive = nc_mod.get_prox(row, jnp.int32(7))
    assert not bool(alive)


def _nps_round(st, p, n, rng, rtt):
    """One probe round: every node samples one reference point (GNP:
    a landmark; NPS: a landmark or a random positioned node)."""
    import dataclasses as dc
    r1, r2, r3 = jax.random.split(rng, 3)
    lm = jax.random.randint(r1, (n,), 0, p.num_landmarks)
    if p.ncs_type == "nps":
        alt = jax.random.randint(r2, (n,), 0, n)
        use_alt = (jax.random.uniform(r3, (n,)) < 0.5) & (
            st.layer[alt] >= 0)
        peers = jnp.where(use_alt, alt, lm)
    else:
        peers = lm
    peers = jnp.where(peers == jnp.arange(n), (peers + 1) % n, peers)

    def per_node(i, pr):
        me = dict(coords=st.coords[i], error=st.error[i],
                  layer=st.layer[i], ref_rtt=st.ref_rtt[i],
                  ref_xy=st.ref_xy[i], ref_layer=st.ref_layer[i],
                  ref_n=st.ref_n[i])
        me = ncs_mod.nps_add_sample(me, rtt[i, pr], st.coords[pr],
                                    st.layer[pr], p)
        return ncs_mod.nps_solve(me, p)

    upd = jax.vmap(per_node)(jnp.arange(n), peers)
    return dc.replace(st, **upd)


def test_gnp_landmark_embedding():
    """GNP: landmarks anchor the space; every other node resolves layer-1
    coordinates whose pairwise predictions track the true RTT matrix."""
    n, p = 24, ncs_mod.NcsParams(ncs_type="gnp", num_landmarks=4,
                                 ref_points=4)
    rtt, _ = _true_rtts(n, jax.random.PRNGKey(2))
    st = ncs_mod.init(jax.random.PRNGKey(3), n, p)
    assert int((np.asarray(st.layer) == 0).sum()) == 4
    err0 = _embedding_err(st, rtt)
    for i in range(60):
        st = _nps_round(st, p, n, jax.random.PRNGKey(200 + i), rtt)
    layer = np.asarray(st.layer)
    assert (layer[:4] == 0).all()
    assert (layer[4:] == 1).all(), layer       # all resolved via landmarks
    err1 = _embedding_err(st, rtt)
    assert err1 < err0 / 3, (err0, err1)


def test_nps_layers_form():
    """NPS: nodes may triangulate off positioned non-landmarks, so layers
    above 1 appear (layer = max(ref layers)+1, Nps.h:119-133)."""
    n, p = 24, ncs_mod.NcsParams(ncs_type="nps", num_landmarks=4,
                                 ref_points=4)
    rtt, _ = _true_rtts(n, jax.random.PRNGKey(4))
    st = ncs_mod.init(jax.random.PRNGKey(5), n, p)
    for i in range(80):
        st = _nps_round(st, p, n, jax.random.PRNGKey(400 + i), rtt)
    layer = np.asarray(st.layer)
    assert (layer[:4] == 0).all()
    assert (layer[4:] >= 1).all(), layer
    assert (layer > 1).any(), layer            # hierarchy actually formed
    err1 = _embedding_err(st, rtt)
    assert err1 < 0.05, err1


def test_nps_wire_roundtrip():
    p = ncs_mod.NcsParams(ncs_type="nps")
    coords = jnp.asarray([0.01, -0.02], jnp.float32)
    key = ncs_mod.pack_wire_nps(coords, jnp.float32(0.3), jnp.int32(2), 5)
    c2, e2, l2 = ncs_mod.unpack_wire_nps(key, 2)
    np.testing.assert_allclose(np.asarray(c2), np.asarray(coords))
    assert abs(float(e2) - 0.3) < 1e-6
    assert int(l2) == 2


def test_gnp_hosted_by_chord():
    """End-to-end: Chord hosts GNP probing (t_nps timer + PING piggyback);
    non-landmark nodes resolve layer-1 coordinates whose predicted RTTs
    are physically plausible for the underlay's coord field."""
    from oversim_tpu import churn as churn_mod
    from oversim_tpu.engine import sim as sim_mod
    from oversim_tpu.overlay.chord import ChordLogic

    p = ncs_mod.NcsParams(ncs_type="gnp", num_landmarks=4, ref_points=4,
                          probe_interval=5.0)
    logic = ChordLogic(ncs_params=p)
    cp = churn_mod.ChurnParams(model="none", target_num=12,
                               init_interval=0.5)
    ep = sim_mod.EngineParams(window=0.100, transition_time=20.0,
                              inbox_slots=2)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=13)
    # the 12 nodes have joined by second 6; 24 rounds of probes (one per
    # 5 s) embed the four landmarks and then the layers over them
    st = s.run_until(st, 120.0, chunk=256)
    layer = np.asarray(st.logic.ncs.layer)
    assert (layer[:4] == 0).all()
    assert (layer[4:] >= 1).all(), layer
    # coordinates embed one-way delays: for the default 150x150 field at
    # 0.001 s/unit, predicted distances must land in (0, ~0.5 s)
    coords = np.asarray(st.logic.ncs.coords)
    d = np.linalg.norm(coords[:, None, :] - coords[None, :, :], axis=-1)
    off = ~np.eye(12, dtype=bool)
    assert 0.0 < d[off].mean() < 0.5, d[off].mean()
