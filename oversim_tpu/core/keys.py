"""Fixed-width overlay-key arithmetic on packed uint32 lanes.

TPU-native equivalent of the reference's GMP-backed ``OverlayKey``
(reference: src/common/OverlayKey.{h,cc} — arbitrary-width keys on
``mp_limb_t`` arrays, MAX_KEYLENGTH=512, ring/xor/prefix metrics used by
every overlay protocol).  Instead of per-object bignum limbs we represent a
key as a little vector of ``KL`` uint32 lanes, **most-significant lane
first**, so a batch of N keys is a ``[N, KL]`` uint32 array and every
operation below vectorizes over arbitrary leading batch dimensions.

keyLength is a static (trace-time) property carried by the module-level
``KeySpec``; 160-bit keys (the default, default.ini:393 ``keyLength=160``)
pack into KL=5 lanes.  All ops are pure jnp and fuse under jit; the
multi-lane compares unroll a python loop over the (static, tiny) lane count.
"""

from __future__ import annotations

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32
U64 = jnp.uint64
LANE_BITS = 32
MAX_KEY_BITS = 512


@dataclasses.dataclass(frozen=True)
class KeySpec:
    """Static description of the key space (reference: OverlayKey keyLength
    global, set from par("keyLength") in BaseOverlay.cc:80)."""

    bits: int = 160

    def __post_init__(self):
        if not (0 < self.bits <= MAX_KEY_BITS):
            raise ValueError(f"keyLength must be in (0, {MAX_KEY_BITS}]")

    @property
    def lanes(self) -> int:
        return (self.bits + LANE_BITS - 1) // LANE_BITS

    @property
    def top_lane_bits(self) -> int:
        """Number of significant bits in lane 0."""
        r = self.bits % LANE_BITS
        return LANE_BITS if r == 0 else r

    @property
    def top_lane_mask(self) -> int:
        return (1 << self.top_lane_bits) - 1


DEFAULT_SPEC = KeySpec(160)


# ---------------------------------------------------------------------------
# construction / conversion
# ---------------------------------------------------------------------------

def from_int(value: int, spec: KeySpec = DEFAULT_SPEC) -> jnp.ndarray:
    """Build a single [KL] key from a python int (host-side helper)."""
    value &= (1 << spec.bits) - 1
    lanes = [(value >> (LANE_BITS * i)) & 0xFFFFFFFF for i in range(spec.lanes)]
    return jnp.asarray(lanes[::-1], dtype=U32)


def to_int(key, spec: KeySpec = DEFAULT_SPEC) -> int:
    """Convert a single [KL] key back to a python int (host-side helper)."""
    lanes = np.asarray(key, dtype=np.uint64)
    out = 0
    for lane in lanes:
        out = (out << LANE_BITS) | int(lane)
    return out


def zero(spec: KeySpec = DEFAULT_SPEC) -> jnp.ndarray:
    return jnp.zeros((spec.lanes,), dtype=U32)


def max_key(spec: KeySpec = DEFAULT_SPEC) -> jnp.ndarray:
    k = jnp.full((spec.lanes,), 0xFFFFFFFF, dtype=U32)
    return k.at[0].set(jnp.uint32(spec.top_lane_mask))


def mask_to_width(key, spec: KeySpec = DEFAULT_SPEC):
    """Clear the unused high bits of lane 0."""
    top = key[..., :1] & jnp.uint32(spec.top_lane_mask)
    return jnp.concatenate([top, key[..., 1:]], axis=-1) if spec.lanes > 1 else top


def random_keys(rng: jax.Array, batch_shape, spec: KeySpec = DEFAULT_SPEC):
    """Uniform random keys, shape ``batch_shape + (KL,)``.

    Reference: OverlayKey::random() (OverlayKey.cc:477) draws each limb from
    the module RNG; we draw uint32 lanes from a counter-based PRNG instead.
    """
    bits = jax.random.bits(rng, tuple(batch_shape) + (spec.lanes,), dtype=U32)
    return mask_to_width(bits, spec)


def sha1_key(data: bytes, spec: KeySpec = DEFAULT_SPEC) -> jnp.ndarray:
    """Host-side sha1 → key (reference: OverlayKey::sha1, OverlayKey.cc:493).

    Used for hashing values/names into the key space (DHT, Scribe groups);
    runs on host at config/workload-build time, never inside jit.
    """
    digest = hashlib.sha1(data).digest()
    value = int.from_bytes(digest, "big")
    if spec.bits < 160:
        value >>= 160 - spec.bits
    return from_int(value, spec)


# ---------------------------------------------------------------------------
# comparisons (lexicographic over most-significant-first lanes)
# ---------------------------------------------------------------------------

def eq(a, b):
    return jnp.all(a == b, axis=-1)


def _lex(a, b):
    """Returns (lt, gt) bool arrays comparing multi-lane keys."""
    lt = jnp.zeros(a.shape[:-1], dtype=bool)
    gt = jnp.zeros(a.shape[:-1], dtype=bool)
    done = jnp.zeros(a.shape[:-1], dtype=bool)
    for i in range(a.shape[-1]):  # static, tiny lane count — unrolled
        ai, bi = a[..., i], b[..., i]
        lt = jnp.where(~done & (ai < bi), True, lt)
        gt = jnp.where(~done & (ai > bi), True, gt)
        done = done | (ai != bi)
    return lt, gt


def lt(a, b):
    return _lex(a, b)[0]


def gt(a, b):
    return _lex(a, b)[1]


def le(a, b):
    return ~gt(a, b)


def ge(a, b):
    return ~lt(a, b)


# ---------------------------------------------------------------------------
# modular ring arithmetic (mod 2**bits)
# ---------------------------------------------------------------------------

def add(a, b, spec: KeySpec = DEFAULT_SPEC):
    """(a + b) mod 2**bits, lane-wise with carry propagation."""
    kl = spec.lanes
    out = []
    carry = jnp.zeros(a.shape[:-1], dtype=U64)
    for i in range(kl - 1, -1, -1):  # least-significant lane last in layout
        s = a[..., i].astype(U64) + b[..., i].astype(U64) + carry
        out.append((s & jnp.uint64(0xFFFFFFFF)).astype(U32))
        carry = s >> jnp.uint64(32)
    key = jnp.stack(out[::-1], axis=-1)
    return mask_to_width(key, spec)


def neg(a, spec: KeySpec = DEFAULT_SPEC):
    """Two's complement: (-a) mod 2**bits."""
    one = jnp.zeros_like(a).at[..., -1].set(jnp.uint32(1))
    return add(~a, one, spec)


def sub(a, b, spec: KeySpec = DEFAULT_SPEC):
    """(a - b) mod 2**bits."""
    return add(a, neg(b, spec), spec)


def bit(key, index, spec: KeySpec = DEFAULT_SPEC):
    """Bit ``index`` of the key, where index 0 is the LSB (reference:
    OverlayKey::getBit).  ``index`` may be a traced int array."""
    index = jnp.asarray(index)
    lane = spec.lanes - 1 - (index // LANE_BITS)
    off = index % LANE_BITS
    word = jnp.take_along_axis(key, lane[..., None].astype(jnp.int32), axis=-1)[..., 0]
    return (word >> off.astype(U32)) & jnp.uint32(1)


def digit(key, index, b: int, spec: KeySpec = DEFAULT_SPEC):
    """b-bit digit ``index`` counted from the MSB (Pastry prefix digits;
    reference OverlayKey::getBitRange as used by PastryRoutingTable).
    ``index`` may be traced."""
    index = jnp.asarray(index)
    out = jnp.zeros(jnp.broadcast_shapes(index.shape, key.shape[:-1]),
                    dtype=jnp.int32)
    for j in range(b):
        pos = spec.bits - 1 - (index * b + j)
        out = (out << 1) | jnp.where(
            pos >= 0, bit(key, jnp.maximum(pos, 0), spec).astype(jnp.int32), 0)
    return out


def shared_prefix_digits(a, b_key, b: int, spec: KeySpec = DEFAULT_SPEC):
    """Number of common leading b-bit digits (Pastry row index)."""
    return shared_prefix_length(a, b_key, spec) // b


def abs_diff(a, b, spec: KeySpec = DEFAULT_SPEC):
    """Plain numerical |a - b| (NON-modular; Pastry's numeric-closeness
    metric, BasePastry 'numerically closest' comparisons)."""
    a_ge = ge(a, b)
    d1 = sub(a, b, spec)
    d2 = sub(b, a, spec)
    return jnp.where(a_ge[..., None], d1, d2)


def pow2(exponent: int, spec: KeySpec = DEFAULT_SPEC) -> jnp.ndarray:
    """Single key 2**exponent (host-side; finger-table offsets)."""
    return from_int(1 << exponent, spec)


def pow2_table(spec: KeySpec = DEFAULT_SPEC) -> jnp.ndarray:
    """[bits, KL] table of 2**i for i in 0..bits-1 (finger offsets)."""
    return jnp.stack([from_int(1 << i, spec) for i in range(spec.bits)])


def shl_const(key, c: int, spec: KeySpec = DEFAULT_SPEC):
    """Logical left shift by a STATIC bit count (reference OverlayKey
    operator<<; Koorde digit-shift routing)."""
    if c == 0:
        return mask_to_width(key, spec)
    kl = spec.lanes
    lane_sh, bit_sh = c // LANE_BITS, c % LANE_BITS
    out = []
    for i in range(kl):
        src = i + lane_sh
        lo = key[..., src] if src < kl else jnp.zeros_like(key[..., 0])
        if bit_sh:
            nxt = key[..., src + 1] if src + 1 < kl else jnp.zeros_like(
                key[..., 0])
            lo = (lo << jnp.uint32(bit_sh)) | (
                nxt >> jnp.uint32(LANE_BITS - bit_sh))
        out.append(lo)
    return mask_to_width(jnp.stack(out, axis=-1), spec)


def shr_const(key, c: int, spec: KeySpec = DEFAULT_SPEC):
    """Logical right shift by a STATIC bit count (counts from the
    significant width: the unused high bits of lane 0 stay zero)."""
    if c == 0:
        return mask_to_width(key, spec)
    kl = spec.lanes
    key = mask_to_width(key, spec)
    lane_sh, bit_sh = c // LANE_BITS, c % LANE_BITS
    out = []
    for i in range(kl):
        src = i - lane_sh
        lo = key[..., src] if src >= 0 else jnp.zeros_like(key[..., 0])
        if bit_sh:
            prv = key[..., src - 1] if src - 1 >= 0 else jnp.zeros_like(
                key[..., 0])
            lo = (lo >> jnp.uint32(bit_sh)) | (
                prv << jnp.uint32(LANE_BITS - bit_sh))
        out.append(lo)
    return jnp.stack(out, axis=-1)


def _barrel(key, n, spec: KeySpec, const_fn):
    """Dynamic shift by traced ``n`` via a barrel of static shifts."""
    n = jnp.asarray(n, jnp.int32)
    out = key
    p = 0
    while (1 << p) < spec.bits:
        amt = 1 << p
        bit = ((n >> p) & 1) != 0
        out = jnp.where(bit[..., None], const_fn(out, amt, spec), out)
        p += 1
    # shifts >= bits clear everything
    return jnp.where((n >= spec.bits)[..., None], jnp.zeros_like(out), out)


def shl_dyn(key, n, spec: KeySpec = DEFAULT_SPEC):
    """Left shift by a TRACED amount (Koorde findStartKey)."""
    return _barrel(key, n, spec, shl_const)


def shr_dyn(key, n, spec: KeySpec = DEFAULT_SPEC):
    """Right shift by a TRACED amount (Koorde findStartKey)."""
    return _barrel(key, n, spec, shr_const)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def xor_metric(a, b):
    """XOR distance (Kademlia; reference KeyXorMetric, Comparator.h)."""
    return a ^ b


def ring_distance(a, b, spec: KeySpec = DEFAULT_SPEC):
    """Clockwise (unidirectional) ring distance a→b: (b - a) mod 2**bits.

    Reference: KeyRingMetric / Chord::distance (Chord.cc:1403).
    """
    return sub(b, a, spec)


def cw_ring_distance(a, b, spec: KeySpec = DEFAULT_SPEC):
    """Counter-clockwise ring distance (KeyCwRingMetric): (a - b) mod 2**bits."""
    return sub(a, b, spec)


def bidir_ring_distance(a, b, spec: KeySpec = DEFAULT_SPEC):
    """min(|a-b|, |b-a|) on the ring (used by e.g. Broose bucket metrics)."""
    d1 = sub(b, a, spec)
    d2 = sub(a, b, spec)
    use1 = lt(d1, d2)
    return jnp.where(use1[..., None], d1, d2)


def is_between(key, a, b, spec: KeySpec = DEFAULT_SPEC):
    """True iff key ∈ (a, b) on the ring, endpoints excluded.

    Reference: OverlayKey::isBetween.  Implemented as
    0 < (key - a) < (b - a) in modular arithmetic, which handles wraparound
    uniformly; degenerate a==b follows the reference convention (empty
    interval unless key != a: the full-ring interval (a,a) contains every
    key except a itself).
    """
    dk = sub(key, a, spec)
    db = sub(b, a, spec)
    k_nonzero = ~eq(key, a)
    full = eq(a, b)
    return jnp.where(full, k_nonzero, lt(dk, db) & k_nonzero)


def is_between_r(key, a, b, spec: KeySpec = DEFAULT_SPEC):
    """key ∈ (a, b] (right-closed; reference OverlayKey::isBetweenR)."""
    return is_between(key, a, b, spec) | eq(key, b)


def is_between_l(key, a, b, spec: KeySpec = DEFAULT_SPEC):
    """key ∈ [a, b) (left-closed; reference OverlayKey::isBetweenL)."""
    return is_between(key, a, b, spec) | eq(key, a)


def is_between_lr(key, a, b, spec: KeySpec = DEFAULT_SPEC):
    """key ∈ [a, b] (closed; reference OverlayKey::isBetweenLR)."""
    return is_between(key, a, b, spec) | eq(key, a) | eq(key, b)


def shared_prefix_length(a, b, spec: KeySpec = DEFAULT_SPEC):
    """Length of the common MSB prefix (reference OverlayKey.cc:411).

    Counts from the top of the *significant* width (spec.bits), i.e. the
    unused high bits of lane 0 are ignored.
    """
    x = a ^ b
    # clz per lane, then accumulate full-lane prefixes lexicographically.
    total = jnp.zeros(x.shape[:-1], dtype=jnp.int32)
    done = jnp.zeros(x.shape[:-1], dtype=bool)
    for i in range(spec.lanes):
        lane = x[..., i]
        lane_clz = jax.lax.clz(lane).astype(jnp.int32)
        if i == 0:
            # ignore the dead bits above the key width
            lane_clz = jnp.minimum(lane_clz - (LANE_BITS - spec.top_lane_bits),
                                   spec.top_lane_bits)
            lane_bits = spec.top_lane_bits
        else:
            lane_bits = LANE_BITS
        contrib = jnp.where(lane == 0, lane_bits, lane_clz)
        total = total + jnp.where(done, 0, contrib)
        done = done | (lane != 0)
    return jnp.minimum(total, spec.bits)


def log2_floor(key, spec: KeySpec = DEFAULT_SPEC):
    """floor(log2(key)) as int32; -1 for key == 0 (bucket indexing)."""
    return spec.bits - 1 - shared_prefix_length(key, jnp.zeros_like(key), spec)


def dup_mask(vec):
    """[C] → [C] bool marking every later duplicate of an earlier entry
    (keep-first semantics).  Shared dedupe primitive for candidate-set
    merges (NodeVector::add rejects keys already present, NodeVector.h)."""
    c = vec.shape[0]
    eq = vec[None, :] == vec[:, None]
    return jnp.any(eq & jnp.tril(jnp.ones((c, c), bool), k=-1), axis=1)


# ---------------------------------------------------------------------------
# sorting / top-k by multi-lane distance
# ---------------------------------------------------------------------------

def sort_by_distance(dist, payload, num_keys: int | None = None, *,
                     approx: bool = False):
    """Sort ``payload`` (tuple of [..., C] arrays) by multi-lane distance
    ``dist`` [..., C, KL], ascending lexicographically.

    TPU-native replacement for the reference's ``BaseKeySortedVector`` /
    ``NodeVector`` (src/common/NodeVector.h:40-44: fixed-capacity vector kept
    sorted by a pluggable key comparator) — instead of incremental sorted
    insertion we batch-sort candidate sets with XLA's lexicographic
    ``lax.sort`` and take a prefix.

    The DEFAULT comparator is exact (all KL lanes — NodeVector.h:40-44
    semantics).  ``approx=True`` opts into sort-key compression: only
    the top TWO u32 lanes (64 bits) of the distance feed the
    comparator.  That is exact-in-practice ONLY for high-entropy
    distances — distinct 160+-bit node keys drawn uniformly
    (engine/sim.py random nodeIds) tie in the top 64 bits of a
    ring/XOR distance with probability ~N²·2⁻⁶⁴ per simulation — and
    it halves-to-thirds the lax.sort operand count on the hot
    findNode/frontier paths (the tick graph is op-issue-bound,
    PERFORMANCE.md).  A caller sorting STRUCTURED or low-entropy
    distances (keys sharing long prefixes by construction, team-offset
    keys, distances clamped to a small range) must NOT pass approx:
    compression was previously the silent default and was flagged as a
    wrongness trap (VERDICT r3/r4) — it is now opt-in at every site.

    Returns (sorted_dist, sorted_payloads).  On the compressed path
    sorted_dist carries only the comparator lanes (no caller consumes
    it — every call site takes ``[1]``).  ``num_keys`` still forces an
    exact sort with that many comparator lanes (back-compat).
    """
    kl = dist.shape[-1]
    if num_keys is None and approx:
        nk = min(2, kl)
        lanes = tuple(dist[..., i] for i in range(nk))
    else:
        nk = kl if num_keys is None else num_keys
        lanes = tuple(dist[..., i] for i in range(kl))
    operands = lanes + tuple(payload)
    out = jax.lax.sort(operands, dimension=-1, num_keys=nk)
    sorted_dist = jnp.stack(out[:len(lanes)], axis=-1)
    return sorted_dist, tuple(out[len(lanes):])


def argmin_by_distance(dist, *, approx: bool = False):
    """Index [...] (i32) of the lexicographically smallest row of ``dist``
    [..., C, KL], the LOWEST index among equal rows: for every input
    ``sort_by_distance(dist, (iota,), approx=approx)[1][0][..., 0]``,
    what a caller that keeps ONE candidate wants, found by reduction
    (no sort, no scatter, no 64-bit intermediate): ``min`` of lane 0,
    ``min`` of lane 1 among the rows that hold it, ..., ``min`` of the
    index among those.  The comparator lanes are ``sort_by_distance``'s:
    all KL, the top two under ``approx=True`` (the same caveat holds).
    """
    c, kl = dist.shape[-2:]
    top = jnp.iinfo(dist.dtype).max
    among = None
    for i in range(min(2, kl) if approx else kl):
        lane = dist[..., i]
        if among is not None:
            lane = jnp.where(among, lane, top)
        hit = lane == jnp.min(lane, axis=-1, keepdims=True)
        among = hit if among is None else among & hit
    idx = jax.lax.broadcasted_iota(jnp.int32, among.shape, among.ndim - 1)
    return jnp.min(jnp.where(among, idx, c), axis=-1)


def closest_k_by_distance(dist, payload, k: int, *, approx: bool = False):
    """The first ``k`` of ``payload`` (tuple of [..., C] numeric arrays) in
    the order of ``dist`` [..., C, KL]: for every input
    ``sort_by_distance(dist, payload, approx=approx)[1]`` cut to
    ``[..., :k]``, what a caller that keeps a FEW of many candidates
    wants, found by ``k`` passes of ``argmin_by_distance``'s lane-by-lane
    reduction (no sort, no scatter, no gather, no 64-bit intermediate: a
    pass reads the payload off by its winner's one-hot mask, a sum of
    one term).  The winner leaves the running by that mask over the
    INDEX, not by its distance: rows that tie, all-``UMAX`` padding among
    them, then follow in index order as the stable sort leaves them.
    ``k`` is static; the comparator lanes are ``sort_by_distance``'s (the
    same caveat holds under ``approx=True``).
    """
    c, kl = dist.shape[-2:]
    top = jnp.iinfo(dist.dtype).max
    lanes = [dist[..., i] for i in range(min(2, kl) if approx else kl)]
    idx = jax.lax.broadcasted_iota(jnp.int32, dist.shape[:-1], dist.ndim - 2)
    free = jnp.ones(dist.shape[:-1], bool)
    picks = [[] for _ in payload]
    for _ in range(min(k, c)):
        among = free
        for lane in lanes:
            lane = jnp.where(among, lane, top)
            among = among & (lane == jnp.min(lane, axis=-1, keepdims=True))
        hit = idx == jnp.min(jnp.where(among, idx, c), axis=-1, keepdims=True)
        for out, p in zip(picks, payload):
            out.append(jnp.sum(jnp.where(hit, p, 0), axis=-1, keepdims=True,
                               dtype=p.dtype))
        free = free & ~hit
    return tuple(jnp.concatenate(out, axis=-1) for out in picks)
