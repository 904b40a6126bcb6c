"""Key-core parity tests: packed-lane ops vs python bignum ground truth.

Mirrors the semantics of the reference's OverlayKey (src/common/OverlayKey.cc):
modular ring arithmetic, interval tests, prefix lengths, metrics.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oversim_tpu.core import keys as K

SPECS = [K.KeySpec(160), K.KeySpec(512), K.KeySpec(100), K.KeySpec(32), K.KeySpec(17)]


def rand_ints(spec, n, seed):
    r = random.Random(seed)
    edge = [0, 1, (1 << spec.bits) - 1, (1 << spec.bits) // 2]
    vals = edge + [r.getrandbits(spec.bits) for _ in range(n - len(edge))]
    return vals[:n]


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: f"bits{s.bits}")
def test_roundtrip(spec):
    for v in rand_ints(spec, 16, 1):
        assert K.to_int(K.from_int(v, spec), spec) == v


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: f"bits{s.bits}")
def test_add_sub_mod(spec):
    m = 1 << spec.bits
    avals = rand_ints(spec, 12, 2)
    bvals = rand_ints(spec, 12, 3)
    a = jnp.stack([K.from_int(v, spec) for v in avals])
    b = jnp.stack([K.from_int(v, spec) for v in bvals])
    s = K.add(a, b, spec)
    d = K.sub(a, b, spec)
    for i, (av, bv) in enumerate(zip(avals, bvals)):
        assert K.to_int(s[i], spec) == (av + bv) % m
        assert K.to_int(d[i], spec) == (av - bv) % m


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: f"bits{s.bits}")
def test_compare(spec):
    avals = rand_ints(spec, 12, 4)
    bvals = rand_ints(spec, 12, 5)
    bvals[0] = avals[0]  # force an equal pair
    a = jnp.stack([K.from_int(v, spec) for v in avals])
    b = jnp.stack([K.from_int(v, spec) for v in bvals])
    np.testing.assert_array_equal(
        np.asarray(K.lt(a, b)), np.array([x < y for x, y in zip(avals, bvals)]))
    np.testing.assert_array_equal(
        np.asarray(K.gt(a, b)), np.array([x > y for x, y in zip(avals, bvals)]))
    np.testing.assert_array_equal(
        np.asarray(K.eq(a, b)), np.array([x == y for x, y in zip(avals, bvals)]))


@pytest.mark.parametrize("spec", [K.KeySpec(160), K.KeySpec(32)],
                         ids=lambda s: f"bits{s.bits}")
def test_is_between(spec):
    m = 1 << spec.bits
    r = random.Random(7)
    cases = []
    for _ in range(200):
        cases.append((r.getrandbits(spec.bits), r.getrandbits(spec.bits),
                      r.getrandbits(spec.bits)))
    # edge cases incl. wraparound and degenerate intervals
    cases += [(5, 5, 5), (5, 5, 9), (5, 9, 5), (0, m - 1, 1), (m - 1, m - 2, 0)]
    key = jnp.stack([K.from_int(c[0], spec) for c in cases])
    a = jnp.stack([K.from_int(c[1], spec) for c in cases])
    b = jnp.stack([K.from_int(c[2], spec) for c in cases])

    def py_between(k, x, y):  # open interval on the ring, ref semantics
        if x == y:
            return k != x
        return 0 < (k - x) % m < (y - x) % m

    expect = np.array([py_between(*c) for c in cases])
    np.testing.assert_array_equal(np.asarray(K.is_between(key, a, b, spec)), expect)
    expect_r = np.array([py_between(*c) or c[0] == c[2] for c in cases])
    np.testing.assert_array_equal(np.asarray(K.is_between_r(key, a, b, spec)), expect_r)


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: f"bits{s.bits}")
def test_shared_prefix_length(spec):
    r = random.Random(9)
    pairs = []
    for plen in [0, 1, spec.bits // 2, spec.bits - 1, spec.bits]:
        a = r.getrandbits(spec.bits)
        if plen == spec.bits:
            b = a
        else:
            # force first differing bit exactly at position plen from MSB
            flip = 1 << (spec.bits - 1 - plen)
            b = a ^ flip ^ (r.getrandbits(spec.bits) & (flip - 1))
        pairs.append((a, b, plen))
    a = jnp.stack([K.from_int(p[0], spec) for p in pairs])
    b = jnp.stack([K.from_int(p[1], spec) for p in pairs])
    got = np.asarray(K.shared_prefix_length(a, b, spec))
    np.testing.assert_array_equal(got, np.array([p[2] for p in pairs]))


def test_bit_indexing():
    spec = K.KeySpec(160)
    v = 0b1011 << 77 | 1
    k = K.from_int(v, spec)
    idx = jnp.arange(spec.bits)
    bits = np.asarray(jax.vmap(lambda i: K.bit(k, i, spec))(idx))
    expect = np.array([(v >> i) & 1 for i in range(spec.bits)])
    np.testing.assert_array_equal(bits, expect)


def test_ring_distance_and_metrics():
    spec = K.KeySpec(160)
    m = 1 << 160
    a, b = 1234567, m - 999
    ka, kb = K.from_int(a, spec), K.from_int(b, spec)
    assert K.to_int(K.ring_distance(ka, kb, spec), spec) == (b - a) % m
    assert K.to_int(K.cw_ring_distance(ka, kb, spec), spec) == (a - b) % m
    assert K.to_int(K.xor_metric(ka, kb), spec) == a ^ b
    bd = K.to_int(K.bidir_ring_distance(ka, kb, spec), spec)
    assert bd == min((b - a) % m, (a - b) % m)


def test_random_keys_masked_and_distinct():
    spec = K.KeySpec(100)
    ks = K.random_keys(jax.random.PRNGKey(0), (64,), spec)
    vals = [K.to_int(ks[i], spec) for i in range(64)]
    assert all(0 <= v < (1 << 100) for v in vals)
    assert len(set(vals)) == 64  # collisions astronomically unlikely


def test_sort_by_distance_topk():
    spec = K.KeySpec(160)
    r = random.Random(11)
    target = r.getrandbits(160)
    cand = [r.getrandbits(160) for _ in range(32)]
    tk = K.from_int(target, spec)
    ck = jnp.stack([K.from_int(c, spec) for c in cand])
    dist = K.ring_distance(jnp.broadcast_to(tk, ck.shape), ck, spec)
    idx = jnp.arange(32, dtype=jnp.int32)
    _, (order,) = K.sort_by_distance(dist, (idx,))
    m = 1 << 160
    expect = sorted(range(32), key=lambda i: (cand[i] - target) % m)
    np.testing.assert_array_equal(np.asarray(order), np.array(expect, dtype=np.int32))


def _argmin_rows(case):
    """[A, R, C, KL] u32 distance rows for one case of the argmin test."""
    rng = np.random.default_rng(44)
    a, r, c, kl = 3, 4, 168, 5
    umax = np.uint32(0xFFFFFFFF)
    if case == "uniform":
        return rng.integers(0, 1 << 32, (a, r, c, kl), dtype=np.uint32)
    if case == "ties":
        return rng.integers(0, 4, (a, r, c, kl), dtype=np.uint32)
    if case == "mostly_umax":
        d = rng.integers(0, 1 << 32, (a, r, c, kl), dtype=np.uint32)
        return np.where(rng.random((a, r, c, 1)) < 0.9, umax, d)
    if case == "all_umax":
        return np.full((a, r, c, kl), umax)
    if case == "one_row":
        return rng.integers(0, 1 << 32, (a, r, 1, kl), dtype=np.uint32)
    assert case == "tie_above_differ_below"
    d = rng.integers(0, 1 << 32, (a, r, c, kl), dtype=np.uint32)
    d[..., :2] = 7                  # every row ties in the top two lanes
    d[..., 0, 2] = umax             # and row 0 is not the smallest below
    return d


@pytest.mark.parametrize("approx", [False, True], ids=["exact", "approx"])
@pytest.mark.parametrize("case", ["uniform", "ties", "mostly_umax", "all_umax",
                                  "one_row", "tie_above_differ_below"])
def test_argmin_by_distance_is_the_sorts_first_index(case, approx):
    dist = jnp.asarray(_argmin_rows(case))

    def by_sort(d):
        idx = jnp.arange(d.shape[0], dtype=jnp.int32)
        return K.sort_by_distance(d, (idx,), approx=approx)[1][0][0]

    def by_argmin(d):
        return K.argmin_by_distance(d, approx=approx)

    want = np.asarray(jax.vmap(jax.vmap(by_sort))(dist))
    # one row at a time, under vmap over the leading [A, R], and batched
    for got in (jax.vmap(jax.vmap(by_argmin))(dist), by_argmin(dist),
                jnp.stack([by_argmin(dist[0, j]) for j in range(4)])[None]):
        got = np.asarray(got)
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, want[:got.shape[0]])
    if case == "tie_above_differ_below":
        # the compressed comparator sees ties where the exact one does not
        assert (want == 0).all() == approx
    if case == "all_umax":
        assert (want == 0).all()


def _closest_k_rows(case):
    """([A, T, C, KL] u32 distance rows, k) for one case of the closest-k
    test: the argmin test's rows at k = 8, and the cases only a k has."""
    rng = np.random.default_rng(46)
    a, t, kl = 3, 4, 5
    umax = np.uint32(0xFFFFFFFF)
    if case == "fewer_real_than_k":
        # three real candidates a row among 265, the rest padding
        d = np.full((a, t, 265, kl), umax)
        at = rng.permuted(np.tile(np.arange(265), (a, t, 1)), axis=-1)[..., :3]
        real = rng.integers(0, 1 << 32, (a, t, 3, kl), dtype=np.uint32)
        np.put_along_axis(d, at[..., None], real, axis=2)
        return d, 8
    if case == "k_is_1":
        return _argmin_rows("ties"), 1
    if case == "k_is_c":
        return rng.integers(0, 3, (a, t, 12, kl), dtype=np.uint32), 12
    return _argmin_rows(case), 1 if case == "one_row" else 8


@pytest.mark.parametrize("approx", [False, True], ids=["exact", "approx"])
@pytest.mark.parametrize("case", ["uniform", "ties", "mostly_umax", "all_umax",
                                  "fewer_real_than_k", "one_row", "k_is_1",
                                  "k_is_c", "tie_above_differ_below"])
def test_closest_k_is_the_sorts_prefix(case, approx):
    rows, k = _closest_k_rows(case)
    dist = jnp.asarray(rows)
    c = dist.shape[-2]
    # two payloads: the index, and data that repeats (a node held twice)
    idx = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32), dist.shape[:-1])
    data = jnp.asarray(np.random.default_rng(7).integers(
        -1, 5, dist.shape[:-1], dtype=np.int32))

    def by_sort(d, i, x):
        return tuple(s[..., :k] for s in K.sort_by_distance(
            d, (i, x), approx=approx)[1])

    def by_passes(d, i, x):
        return K.closest_k_by_distance(d, (i, x), k, approx=approx)

    want = jax.vmap(jax.vmap(by_sort))(dist, idx, data)
    # under vmap over the leading [A, T], batched, and one row at a time
    for got in (jax.vmap(jax.vmap(by_passes))(dist, idx, data),
                by_passes(dist, idx, data),
                tuple(g[None, None] for g in by_passes(
                    dist[0, 0], idx[0, 0], data[0, 0]))):
        assert len(got) == 2
        for g, w in zip(got, want):
            g, w = np.asarray(g), np.asarray(w)
            assert g.dtype == np.int32 and g.shape[-1] == min(k, c)
            np.testing.assert_array_equal(g, w[:g.shape[0], :g.shape[1]])
    first = np.asarray(want[0])
    if case in ("k_is_1", "one_row"):
        np.testing.assert_array_equal(
            first[..., 0], np.asarray(K.argmin_by_distance(dist, approx=approx)))
    if case == "all_umax":
        # padding follows in index order: a taken row is not picked twice
        assert (first == np.arange(k)).all()
    if case == "fewer_real_than_k":
        real = np.sort(np.nonzero(rows[..., 0] != 0xFFFFFFFF)[-1].reshape(3, 4, 3))
        np.testing.assert_array_equal(np.sort(first[..., :3]), real)
        pad = np.stack([[np.setdiff1d(np.arange(c), real[i, j])[:k - 3]
                         for j in range(4)] for i in range(3)])
        np.testing.assert_array_equal(first[..., 3:], pad)
    if case == "tie_above_differ_below":
        # the compressed comparator sees ties where the exact one does not
        assert (first == np.arange(k)).all() == approx


def test_log2_floor():
    spec = K.KeySpec(160)
    vals = [0, 1, 2, 3, 4, 1 << 80, (1 << 159) + 5]
    ks = jnp.stack([K.from_int(v, spec) for v in vals])
    got = np.asarray(K.log2_floor(ks, spec))
    expect = np.array([v.bit_length() - 1 for v in vals], dtype=np.int32)
    np.testing.assert_array_equal(got, expect)


def test_sha1_key_matches_hashlib():
    import hashlib
    spec = K.KeySpec(160)
    v = int.from_bytes(hashlib.sha1(b"oversim").digest(), "big")
    assert K.to_int(K.sha1_key(b"oversim", spec), spec) == v
