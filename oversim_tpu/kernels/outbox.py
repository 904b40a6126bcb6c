"""Fused outbox-allocation kernel: free-slot compaction + destination
assignment in one Pallas pass.

The sort-free allocator (engine/pool.py ``alloc``) builds the
wanted-message -> free-slot mapping from two full-length exclusive
cumsums plus a compaction scatter (``fslot``).  This kernel replaces
that trio with two serial counting passes — the compacted free-slot
list lives in VMEM, the two running counters in SMEM:

  pass 1 (over P): append each free slot's index to the fslot list;
  pass 2 (over Q): each wanted message takes the next fslot entry (or
    the out-of-bounds sentinel ``p`` once the free supply is exhausted
    — exactly the oracle's ``mode="drop"`` overflow semantics).

The payload write itself (one gather + one scatter of the packed
[·, W] block plus the i64 fields) stays outside: it is already a
single fused scatter per field group, and keeping it in lax means the
kernel output is just the [Q] destination vector + the overflow count,
bit-identical to the cumsum path (tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I32 = jnp.int32


def _dest_kernel(valid_ref, want_ref, dest_ref, over_ref,
                 fslot_ref, cnt_ref, *, p, q):
    """cnt_ref (SMEM): [0] = free slots seen, [1] = wanted msgs seen."""
    cnt_ref[0] = I32(0)
    cnt_ref[1] = I32(0)
    fslot_ref[:] = jnp.full((p,), p, I32)

    def free_body(iv, carry):
        i = iv.astype(I32)

        @pl.when(valid_ref[i] == 0)
        def _():
            fslot_ref[cnt_ref[0]] = i
            cnt_ref[0] = cnt_ref[0] + 1

        return carry

    jax.lax.fori_loop(I32(0), I32(p), free_body, None)
    n_free = cnt_ref[0]

    def want_body(jv, carry):
        j = jv.astype(I32)

        @pl.when(want_ref[j] != 0)
        def _():
            wr = cnt_ref[1]
            dest_ref[j] = jnp.where(wr < n_free,
                                    fslot_ref[jnp.minimum(wr, p - 1)],
                                    I32(p))
            cnt_ref[1] = wr + 1

        @pl.when(want_ref[j] == 0)
        def _():
            dest_ref[j] = I32(p)

        return carry

    jax.lax.fori_loop(I32(0), I32(q), want_body, None)
    over_ref[0] = jnp.maximum(cnt_ref[1] - n_free, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _dest_call(valid, want, *, interpret):
    p = valid.shape[0]
    q = want.shape[0]
    kernel = functools.partial(_dest_kernel, p=p, q=q)
    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((q,), I32),        # dest
            jax.ShapeDtypeStruct((1,), I32),        # overflow
        ),
        scratch_shapes=[
            pltpu.VMEM((p,), I32),                  # fslot
            pltpu.SMEM((2,), I32),                  # counters
        ],
        interpret=interpret,
    )(valid, want)


def alloc_dest(valid, want, interpret: bool | None = None):
    """(dest [Q] i32, overflow i32 scalar) — the j-th wanted message maps
    to the j-th free slot, ``p`` (dropped) for unwanted/overflowed
    messages; bit-identical to the cumsum/fslot path in
    ``pool.alloc``."""
    from oversim_tpu import kernels

    if interpret is None:
        interpret = kernels.interpret_default()
    dest, over = _dest_call(valid.astype(I32), want.astype(I32),
                            interpret=bool(interpret))
    return dest, over[0]


def _compact_kernel(mask_ref, vals_ref, out_ref, count_ref, cnt_ref, *,
                    m, cap, sentinel):
    """Serial counting compaction: the k-th set mask bit (walk order)
    writes ``vals[i]`` to lane k; lanes past ``cap`` defer (the counter
    keeps running so the caller learns the TRUE active count)."""
    cnt_ref[0] = I32(0)
    out_ref[:] = jnp.full((cap,), sentinel, I32)

    def body(iv, carry):
        i = iv.astype(I32)

        @pl.when(mask_ref[i] != 0)
        def _():
            c = cnt_ref[0]

            @pl.when(c < cap)
            def _():
                out_ref[c] = vals_ref[i]

            cnt_ref[0] = c + 1

        return carry

    jax.lax.fori_loop(I32(0), I32(m), body, None)
    count_ref[0] = cnt_ref[0]


@functools.partial(jax.jit, static_argnames=("cap", "sentinel", "interpret"))
def _compact_call(mask, vals, *, cap, sentinel, interpret):
    m = mask.shape[0]
    kernel = functools.partial(_compact_kernel, m=m, cap=cap,
                               sentinel=sentinel)
    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((cap,), I32),      # compacted lanes
            jax.ShapeDtypeStruct((1,), I32),        # active count
        ),
        scratch_shapes=[
            pltpu.SMEM((1,), I32),                  # running counter
        ],
        interpret=interpret,
    )(mask, vals)


def compact_indices(mask, vals, cap: int, sentinel: int,
                    interpret: bool | None = None):
    """(lanes [cap] i32, count i32 scalar) — the sparse tick's
    active-set compaction (engine/sim.py ``_phase_active_compact``):
    lane k holds ``vals[i]`` for the k-th set ``mask`` bit, ``sentinel``
    beyond the active count; ``count`` is the total set-bit count (may
    exceed ``cap``; the engine passes a cap that holds every node).
    Bit-identical to the cumsum-compaction idiom from ``pool.alloc``,
    pinned in tests/test_kernels.py."""
    from oversim_tpu import kernels

    if interpret is None:
        interpret = kernels.interpret_default()
    lanes, count = _compact_call(mask.astype(I32), vals.astype(I32),
                                 cap=int(cap), sentinel=int(sentinel),
                                 interpret=bool(interpret))
    return lanes, count[0]
