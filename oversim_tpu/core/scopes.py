"""The names of a tick's parts, as a device trace shows them.

Every operation of the tick program carries the scopes it was traced
under in its ``op_name`` (``.../phase.node_step/while/body/
kademlia.find_node/...``), and ``benchmark/phase_reduce.py`` reduces a
profiler dump by them: device time, leaf operations and idle of every
name below.  A scope is metadata: it adds no operation and moves none,
so there is no switch.  This module is the one list of the names and
the one place that calls ``jax.named_scope``.

First level: the phases ``Simulation.step`` and ``_step_sparse``
compose (``PHASES`` / ``PHASES_SPARSE``, in the tick's order); every
operation of a tick lies under exactly one.  Second level: the parts of
a phase, entered where the part is computed (a decorator on the
function, a ``with`` at the call); the innermost one names an
operation.  No name holds ``scatter``: ``analysis/hlo_text`` tells a
scatter's own ``while`` loop by ``/scatter`` in its ``op_name``.
"""

from __future__ import annotations

import functools

import jax

PHASES = ("phase.horizon", "phase.churn", "phase.inbox_select",
          "phase.inbox_gather", "phase.node_step", "phase.closing")
# the awake-set plane (tick_impl="sparse"): the selection never gathers
# the full [N, R, W] payload, the awake set is compacted into rounds of
# A lanes and only those run the node step
PHASES_SPARSE = ("phase.horizon", "phase.churn", "phase.inbox_select",
                 "phase.active_compact", "phase.node_step", "phase.closing")

PARTS = {
    "phase.churn": ("churn.step", "logic.reset", "underlay.migrate"),
    "phase.inbox_select": ("pool.due_masks", "inbox.compact", "inbox.rank",
                           "inbox.rounds"),
    "phase.node_step": (
        "step.ctx", "step.gather", "step.write_back",
        "kademlia.bucket_update", "kademlia.find_node",
        "kademlia.routing_add", "kademlia.failed", "kademlia.join",
        "kademlia.refresh", "kademlia.pings",
        "chord.find_node", "chord.failed", "chord.join", "chord.stabilize",
        "chord.fix_fingers", "chord.ping", "chord.broadcast",
        "pastry.find_node", "pastry.learn", "pastry.failed", "pastry.join",
        "pastry.leafset_maint", "pastry.tuning",
        "route.forward", "route.acks", "route.timeouts",
        "lookup.responses", "lookup.timeouts", "lookup.pump",
        "lookup.start", "lookup.completions", "app.kbrtest",
        "outbox.finish"),
    "phase.closing": ("pool.free", "underlay.send_tx", "closing.compact",
                      "underlay.send_rx", "pool.alloc", "stats.record",
                      "closing.counters", "pool.due_masks", "telemetry.fold"),
}

REGISTRY = frozenset(PHASES + PHASES_SPARSE
                     + tuple(p for ps in PARTS.values() for p in ps))


def phases_for(tick_impl: str = "dense") -> tuple:
    """The first-level names a Simulation's tick is made of."""
    return PHASES_SPARSE if tick_impl == "sparse" else PHASES


def _registered(name: str) -> str:
    if name not in REGISTRY:
        raise KeyError(f"{name!r} is not in oversim_tpu.core.scopes")
    return name


def scope(name: str):
    """``with scope("phase.churn"): ...`` — a registered name only."""
    return jax.named_scope(_registered(name))


def scoped(name: str):
    """``@scoped("pool.alloc")`` — the function's body under the scope,
    whoever calls it."""
    _registered(name)

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
