"""Fused inbox kernel: top-R selection + packed payload gather in ONE
Pallas pass over the pool.

The scatter-min oracle (engine/pool.py ``build_inbox_scatter``) builds
the [N, R] inbox table in R rounds of two [P]->[N] scatter-mins each,
then ``Simulation._phase_inbox_gather`` issues a separate [P, W] block
gather — 2R+1 independent XLA ops, each streaming the pool through HBM.
This kernel keeps the per-destination top-R registers in VMEM and does
everything in one serial sweep:

  pass 1 (over P): for each due message, a stable insertion into its
    destination's R-row register file sorted by (t_deliver, pool index).
    Pool indices arrive in increasing order and (t, idx) keys are
    unique, so "count of existing entries with key <= mine" IS the
    insertion position — exactly the oracle's stable tie-break.  An
    insertion into a full row evicts the current last entry, whose
    delivered flag is undone (R-overflow retention: the evicted message
    stays pooled for next tick).
  pass 2 (over N*R): gather the packed [P, W] payload rows of the
    selected indices into the [N, R, W] message block (row 0 for empty
    slots, masked by ``inbox < 0`` downstream — the oracle's
    ``jnp.maximum(inbox, 0)`` gather semantics).

i64 on Pallas-TPU: the core has no 64-bit lanes, so ``t_deliver`` is
decomposed OUTSIDE the kernel into two non-negative i32 halves
(hi = t >> 31, lo = t & 0x7fffffff; t < 2^62 so both fit signed i32)
— lexicographic (hi, lo) compare reproduces the i64 order exactly.
The two i64 fields themselves (t_deliver, stamp) are gathered outside
the kernel off the returned index table ([N, R] gathers from [P], tiny
next to the [P, W] block).

Bit-identity with the oracle — including t ties, R-overflow eviction,
dead destinations and the ``ext_hold_slot`` hold mask (both applied
outside via ``pool._due_masks``) — is pinned by
tests/test_kernels.py under ``pallas_call(interpret=True)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from oversim_tpu.engine import pool as pool_mod

I32 = jnp.int32
_I32_MAX = jnp.iinfo(jnp.int32).max


def _inbox_kernel(occ_ref, due_ref, dst_ref, thi_ref, tlo_ref, *refs,
                  p, n, r, w, gather):
    """One program: select pass over P, then (optional) gather over N*R.

    khi/klo are the VMEM [N, R] sort-key registers mirroring inbox_ref
    (i32 max = empty, so any real key inserts before them).  All loop
    indices are cast to i32 — under x64 ``fori_loop`` counts in i64,
    which must not leak into i32 ref stores.

    ``occ_ref`` (SMEM scalar) is the OCCUPANCY early-out: the highest
    due pool index + 1, computed outside.  The select walk runs to occ,
    not capacity P — bit-identity is free (slots past the last due
    index can never insert) and a near-empty pool costs a near-empty
    walk.  ``gather=False`` (the sparse tick's select-only mode) skips
    the N*R gather pass entirely and takes no blk input.
    """
    if gather:
        blk_ref, inbox_ref, delivered_ref, gblk_ref, khi_ref, klo_ref = refs
    else:
        inbox_ref, delivered_ref, khi_ref, klo_ref = refs
    inbox_ref[:] = jnp.full((n, r), -1, I32)
    delivered_ref[:] = jnp.zeros((p,), I32)
    khi_ref[:] = jnp.full((n, r), _I32_MAX, I32)
    klo_ref[:] = jnp.full((n, r), _I32_MAX, I32)
    pos_iota = jax.lax.broadcasted_iota(I32, (r, 1), 0).reshape(r)

    def select_body(iv, carry):
        i = iv.astype(I32)

        @pl.when(due_ref[i] != 0)
        def _():
            d = dst_ref[i]
            hi = thi_ref[i]
            lo = tlo_ref[i]
            row_hi = khi_ref[d, :]
            row_lo = klo_ref[d, :]
            row_ix = inbox_ref[d, :]
            # stable position: entries with key <= (hi, lo) stay ahead;
            # earlier pool indices inserted at equal t compare <= via lo
            le = (row_hi < hi) | ((row_hi == hi) & (row_lo <= lo))
            # NOT a plain jnp.sum to a scalar: Mosaic lowers a
            # reduce-to-scalar by re-tracing jnp.sum, which under x64
            # promotes the i32 lanes to i64 and is then refused
            # ("64-bit types are not supported").  A keepdims reduce
            # of a 2-D view stays i32.
            pos = jnp.sum(le.astype(I32).reshape(1, r), axis=1,
                          keepdims=True, promote_integers=False)[0, 0]

            @pl.when(pos < r)
            def _():
                evict = row_ix[r - 1]
                keep = pos_iota < pos
                shift = pos_iota > pos
                prev_hi = pltpu.roll(row_hi, 1, 0)
                prev_lo = pltpu.roll(row_lo, 1, 0)
                prev_ix = pltpu.roll(row_ix, 1, 0)
                khi_ref[d, :] = jnp.where(
                    keep, row_hi, jnp.where(shift, prev_hi, hi))
                klo_ref[d, :] = jnp.where(
                    keep, row_lo, jnp.where(shift, prev_lo, lo))
                inbox_ref[d, :] = jnp.where(
                    keep, row_ix, jnp.where(shift, prev_ix, i))
                delivered_ref[i] = I32(1)

                @pl.when(evict >= 0)
                def _():
                    # R-overflow: the displaced last entry goes back to
                    # "not delivered" — it stays pooled for next tick
                    delivered_ref[evict] = I32(0)

        return carry

    jax.lax.fori_loop(I32(0), occ_ref[0], select_body, None)

    if gather:
        def gather_body(jv, carry):
            j = jv.astype(I32)
            nn = j // I32(r)
            rr = j % I32(r)
            ix = inbox_ref[nn, rr]
            gblk_ref[nn, rr, :] = blk_ref[jnp.maximum(ix, 0), :]
            return carry

        jax.lax.fori_loop(I32(0), I32(n * r), gather_body, None)


@functools.partial(jax.jit,
                   static_argnames=("n", "r", "interpret", "gather"))
def _fused_call(due, dstc, thi, tlo, blk, *, n, r, interpret, gather=True):
    p, w = blk.shape
    kernel = functools.partial(_inbox_kernel, p=p, n=n, r=r, w=w,
                               gather=gather)
    # occupancy bound: highest due index + 1 — the select walk's true
    # extent (SMEM scalar; kernel work scales with traffic, not P)
    occ = jnp.max(jnp.where(due != 0, jnp.arange(p, dtype=I32) + 1,
                            0)).reshape((1,))
    # array operands stay whole-array in VMEM (the pre-occupancy
    # default); only the occ scalar needs an explicit SMEM placement
    arr = pl.BlockSpec(memory_space=pltpu.VMEM)
    out_shape = [
        jax.ShapeDtypeStruct((n, r), I32),          # inbox
        jax.ShapeDtypeStruct((p,), I32),            # delivered
    ]
    operands = (occ, due, dstc, thi, tlo)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), arr, arr, arr, arr]
    if gather:
        out_shape.append(
            jax.ShapeDtypeStruct((n, r, w), I32))   # gathered block
        operands += (blk,)
        in_specs.append(arr)
    return pl.pallas_call(
        kernel,
        out_shape=tuple(out_shape),
        in_specs=in_specs,
        out_specs=tuple(arr for _ in out_shape),
        scratch_shapes=[
            pltpu.VMEM((n, r), I32),                # khi
            pltpu.VMEM((n, r), I32),                # klo
        ],
        interpret=interpret,
    )(*operands)


def fused_inbox(pool, n: int, r: int, t_end, alive, hold=None,
                interpret: bool | None = None, gather: bool = True):
    """Fused inbox select + gather.

    Same contract as ``pool.build_inbox`` plus the gathered payload:
    returns ``(inbox [N,R] i32, delivered [P] bool, dropped_dead [P]
    bool, gblk [N,R,W] i32)``.  ``interpret=None`` auto-selects the
    Pallas interpreter off-TPU (kernels.interpret_default).
    ``gather=False`` (the sparse tick) returns the 3-tuple without
    ``gblk`` and skips the N*R gather pass in-kernel."""
    from oversim_tpu import kernels

    if interpret is None:
        interpret = kernels.interpret_default()
    due, to_dead = pool_mod._due_masks(pool, n, t_end, alive, hold)
    # oracle semantics: destinations clip into [0, n) BEFORE grouping
    dstc = jnp.clip(pool.dst, 0, n - 1).astype(I32)
    # hi/lo i32 halves of t_deliver; non-due slots masked to 0 so the
    # T_INF sentinel (2^62) never overflows the decomposition — the
    # kernel only reads keys where due != 0
    t_m = jnp.where(due, pool.t_deliver, 0)
    thi = (t_m >> 31).astype(I32)
    tlo = (t_m & jnp.int64(0x7FFFFFFF)).astype(I32)
    out = _fused_call(
        due.astype(I32), dstc, thi, tlo, pool.blk,
        n=n, r=r, interpret=bool(interpret), gather=gather)
    if not gather:
        inbox, delivered = out
        return inbox, delivered.astype(bool), to_dead
    inbox, delivered, gblk = out
    return inbox, delivered.astype(bool), to_dead, gblk


def fused_select(pool, n: int, r: int, t_end, alive, hold=None,
                 interpret: bool | None = None):
    """Select-only fused inbox (sparse tick plane): ``pool.build_inbox``
    semantics — ``(inbox, delivered, dropped_dead)`` — with the
    occupancy-bounded kernel walk and NO payload gather."""
    return fused_inbox(pool, n, r, t_end, alive, hold=hold,
                       interpret=interpret, gather=False)


def fused_select_sharded(pool, n: int, r: int, t_end, alive, hold=None, *,
                         axis_name, base, p_total, interpret=None):
    """Shard-aware fused select (parallel/shard_tick.py): the kernel
    runs UNMODIFIED on each shard's local pool tile, producing that
    shard's per-destination top-R list; the global table is then a
    K-way sorted merge driven purely by ``lax.pmin``.

    Per round, every shard offers its list head ``(t, global idx)``;
    an i64 pmin picks the winning deliver time, an i32 pmin over the
    matching heads breaks ties by global pool index (tiles are
    contiguous, so local-index order IS global-index order within a
    shard — the oracle's exact (t_deliver, idx) tie-break), and the
    winning shard advances its head.  2R all-reduce:min per call, the
    same collective count and kind as the sharded scatter path.

    Correctness of the local prefilter: each destination's global
    top-R draws at most R entries from any one shard, and those are
    necessarily that shard's R earliest — so the global table is a
    subset of the union of local tables.  ``delivered`` is recomputed
    as membership of the local tile in the FINAL table (the local
    kernel's provisional flags — including its R-overflow evictions —
    are discarded; the oracle's delivered set is exactly the final
    table's membership).  Returns ``(inbox [N, R] GLOBAL pool indices,
    delivered [P_local] bool, dropped_dead [P_local] bool)``.
    """
    p_local = pool.capacity
    inbox_l, _prov, to_dead = fused_inbox(pool, n, r, t_end, alive,
                                          hold=hold, interpret=interpret,
                                          gather=False)
    valid_l = inbox_l >= 0
    safe_l = jnp.maximum(inbox_l, 0)
    t_tab = jnp.where(valid_l, pool.t_deliver[safe_l], pool_mod.T_INF)
    g_tab = jnp.where(valid_l, base + inbox_l, _I32_MAX)

    head = jnp.zeros((n,), I32)
    cols = []
    for _ in range(r):
        hc = jnp.minimum(head, r - 1)[:, None]
        in_range = head < r
        t_cand = jnp.where(
            in_range, jnp.take_along_axis(t_tab, hc, axis=1)[:, 0],
            pool_mod.T_INF)
        g_cand = jnp.where(
            in_range, jnp.take_along_axis(g_tab, hc, axis=1)[:, 0],
            _I32_MAX)
        t_win = jax.lax.pmin(t_cand, axis_name)
        g_win = jax.lax.pmin(
            jnp.where(t_cand == t_win, g_cand, _I32_MAX), axis_name)
        got = g_win < _I32_MAX  # global indices < p_total << i32 max
        cols.append(jnp.where(got, g_win, pool_mod.NO_NODE))
        head += ((t_cand == t_win) & (g_cand == g_win) & got).astype(I32)
    inbox = jnp.stack(cols, axis=1)

    flat = inbox.reshape(-1)
    loc = flat - base
    mine = (flat >= 0) & (loc >= 0) & (loc < p_local)
    delivered = jnp.zeros((p_local,), bool).at[
        jnp.where(mine, loc, p_local)].set(True, mode="drop")
    return inbox, delivered, to_dead
