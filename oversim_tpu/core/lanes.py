"""Static lanes that follow the few set entries of a wide mask.

On the chip a gather, a scatter and a sort cost by their LANES (7.6 ns a
lane of a gather; 53 to 134 ns an update of a scatter into a 64-bit
operand and a twentieth of that into a 32-bit one; PERF.md), whether a
lane holds anything or not, and a steady tick's due messages, awake
nodes and wanted outbox slots are a few of thousands.  So the wide mask
is compacted into K static lanes, the costly indexing runs over those,
and a tick whose entries do not fit takes the wide form through a
``lax.cond``: exact at any load.  ``engine/pool.py build_inbox`` (the D
lanes of the due messages) and ``engine/sim.py _phase_alloc_stats`` (the
K lanes of the wanted outbox slots) share these helpers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

I32 = jnp.int32


def rule(width: int) -> int:
    """The static lane count for a mask of ``width`` entries, from the
    width alone: a thirty-second of it, at least 32 (the whole width
    where that is less).  What it is measured against at each of its
    uses is said there: ``Simulation.acap`` (A of N),
    ``pool.inbox_lanes`` (D of P), ``pool.send_lanes`` (K of Q)."""
    return min(width, max(32, width // 32))


def compact(mask, k: int):
    """[k] i32: lane j holds the index of the (j+1)-th set entry of the
    flat ``mask``, ascending; ``mask.size`` past the last.  The gather
    form: a running sum and a binary search for the first entry whose
    inclusive count reaches j+1 (0.15 ms at 32,768 entries and 1,024
    lanes, three quarters of the scatter form; PERF.md, PR 34)."""
    return jnp.searchsorted(jnp.cumsum(mask.astype(I32)),
                            jnp.arange(1, k + 1, dtype=I32),
                            side="left").astype(I32)


def fits(mask, k: int):
    """The mask's set entries fit ``k`` lanes."""
    return jnp.sum(mask.astype(I32)) <= k


def _words(x):
    """[L, c] i32: a leaf's rows as 32-bit words, the same bits."""
    if x.dtype == jnp.bool_:
        x = x.astype(I32)
    elif x.dtype.itemsize != 4 and x.dtype.itemsize != 8:
        raise TypeError(f"take: {x.dtype} is neither 1, 32 nor 64 bits")
    return jax.lax.bitcast_convert_type(x, I32).reshape(x.shape[0], -1)


def _unwords(w, like):
    """The inverse of :func:`_words` for ``w`` [K, c]: ``like``'s dtype
    and trailing shape."""
    shape = (w.shape[0],) + like.shape[1:]
    if like.dtype == jnp.bool_:
        return w.reshape(shape) != 0
    if like.dtype.itemsize == 8:
        w = w.reshape(shape + (2,))
    return jax.lax.bitcast_convert_type(w, like.dtype).reshape(shape)


def take(tree, idx):
    """Every leaf of ``tree`` ([L, ...] arrays of one leading L) at the
    rows ``idx`` ([K] i32), through ONE row gather: the leaves are laid
    side by side as 32-bit words ([L, C] i32, plain copies), gathered
    once, and split again, so each comes back with the bits, the dtype
    and the out-of-range rule of ``leaf[idx]`` (a negative index wraps,
    one past the end reads the last row) for the price of one gather of
    K lanes and not one for each leaf.  ``idx=None`` is the wide form:
    the tree as it is."""
    if idx is None:
        return tree
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    words = [_words(x) for x in leaves]
    rows = jnp.concatenate(words, axis=1)[idx]
    out, at = [], 0
    for x, w in zip(leaves, words):
        out.append(_unwords(rows[:, at:at + w.shape[1]], x))
        at += w.shape[1]
    return jax.tree_util.tree_unflatten(treedef, out)
