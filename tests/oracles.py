"""Oracles the tests hold the engine to; nothing under oversim_tpu/
imports this module.

``build_inbox_sort`` is the inbox selection as one lexicographic
(dst, t_deliver) full-pool stable sort, O(P log P): what
``engine/pool.py build_inbox`` (R rounds of scatter-min over the due
messages' compacted lanes) must equal bit for bit.  ``SortSimulation``
runs a whole tick on it by overriding the ONE phase that selects —
``Simulation._phase_inbox_select`` is the seam, and only the tests use
it.
"""

import jax
import jax.numpy as jnp

from oversim_tpu.engine import pool as pool_mod
from oversim_tpu.engine.pool import I32, NO_NODE, T_INF, MsgPool
from oversim_tpu.engine.sim import SimState, Simulation


def build_inbox_sort(pool: MsgPool, n: int, r: int, t_end, alive,
                     hold=None):
    """Inbox grouping by one lexicographic (dst, t_deliver) full-pool
    stable sort, O(P log P): same arguments and same
    ``(inbox, delivered, to_dead)`` as ``pool.build_inbox``."""
    p = pool.capacity
    due, to_dead = pool_mod._due_masks(pool, n, t_end, alive, hold)

    dst_k = jnp.where(due, pool.dst, n).astype(I32)
    t_k = jnp.where(due, pool.t_deliver, T_INF)
    idx = jnp.arange(p, dtype=I32)
    dst_s, _, idx_s = jax.lax.sort((dst_k, t_k, idx), dimension=0, num_keys=2)

    # rank of each message within its destination group
    first = jnp.searchsorted(dst_s, dst_s, side="left").astype(I32)
    rank = jnp.arange(p, dtype=I32) - first
    take = (dst_s < n) & (rank < r)

    rows = jnp.where(take, dst_s, n)  # row n is out-of-bounds -> dropped
    inbox = jnp.full((n, r), NO_NODE, I32).at[rows, jnp.minimum(rank, r - 1)].set(
        idx_s, mode="drop")
    delivered = jnp.zeros((p,), bool).at[idx_s].set(take)
    return inbox, delivered, to_dead


class SortSimulation(Simulation):
    """A Simulation whose every tick selects its inbox by the sort
    oracle: every other phase is the engine's own."""

    @classmethod
    def of(cls, sim: Simulation) -> "SortSimulation":
        """``sim``'s deployment (what a builder such as
        ``build_simulation`` returned) under the oracle selection."""
        return cls(sim.logic, sim.cp, sim.up, sim.ep, sim.ul)

    def _phase_inbox_select(self, s: SimState, t_end, alive):
        return build_inbox_sort(s.pool, self.n, self.ep.inbox_slots, t_end,
                                alive, hold=self._hold_mask(s))
