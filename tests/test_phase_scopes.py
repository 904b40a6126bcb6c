"""The tick program names its phases (oversim_tpu/core/scopes.py; ISSUE 41).

Read off the tick's jaxpr: ``jax.make_jaxpr(sim.step)`` and each
equation's name stack, the sub-jaxprs of ``while``, ``cond``, ``scan``
and ``pjit`` walked with their caller's stack in front.  Trace only:
nothing is lowered, nothing compiled, nothing run but ``sim.init``.
"""

import os
import re

import jax
import pytest

from oversim_tpu import churn as churn_mod
from oversim_tpu import hostcache
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.core import scopes
from oversim_tpu.engine.sim import EngineParams, Simulation

N = 32
CASES = [("kademlia", "dense"), ("kademlia", "sparse"),
         ("chord", "dense"), ("chord", "sparse"), ("bamboo", "sparse")]
# the names an overlay's own parts start with
OWN = {"KademliaLogic": ("kademlia.",), "ChordLogic": ("chord.",),
       "BambooLogic": ("pastry.", "route.")}


def _sim(overlay, tick_impl):
    app = KbrTestApp(KbrTestParams(test_interval=6.0))
    if overlay == "chord":
        from oversim_tpu.overlay.chord import ChordLogic
        logic = ChordLogic(app=app)
    elif overlay == "bamboo":
        # semi-recursive with per-hop ACKs: the routed path's parts too
        from oversim_tpu.overlay.pastry import BambooLogic
        logic = BambooLogic(app=app)
    else:
        from oversim_tpu.overlay.kademlia import KademliaLogic
        logic = KademliaLogic(app=app)
    cp = churn_mod.ChurnParams(model="lifetime", target_num=N,
                               init_interval=0.2,
                               lifetime_mean=60.0)
    ep = EngineParams(window=0.1, inbox_slots=2, pool_factor=4,
                      tick_impl=tick_impl)
    return Simulation(logic, cp, engine_params=ep)


def _subjaxprs(eqn):
    for value in eqn.params.values():
        for v in (value if isinstance(value, (tuple, list)) else (value,)):
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield inner


def scope_paths(jaxpr, above=()):
    """Every equation of ``jaxpr`` and of what it calls as ``(primitive,
    names)``: the ``jax.named_scope`` names it lies under, outermost
    first (a sub-jaxpr's stacks are relative to the equation that holds
    it).  Transforms (``vmap``, ``jit``) are no names."""
    for eqn in jaxpr.eqns:
        names = above + tuple(
            e.name for e in eqn.source_info.name_stack.stack
            if type(e).__name__ == "Scope")
        yield eqn.primitive.name, names
        for inner in _subjaxprs(eqn):
            yield from scope_paths(inner, names)


@pytest.fixture(scope="module", params=CASES, ids=lambda c: "-".join(c))
def tick(request):
    overlay, tick_impl = request.param
    sim = _sim(overlay, tick_impl)
    state = jax.eval_shape(sim.init_from_rng, jax.random.PRNGKey(1))
    paths = list(scope_paths(jax.make_jaxpr(sim.step)(state).jaxpr))
    assert len(paths) > 1000
    return sim, paths


def test_every_equation_lies_under_exactly_one_phase(tick):
    _, paths = tick
    bad = [(prim, names) for prim, names in paths
           if sum(n.startswith("phase.") for n in names) != 1]
    assert not bad, f"{len(bad)} of {len(paths)}: {bad[:5]}"
    assert all(names[0].startswith("phase.") for _, names in paths)


def test_the_phases_met_are_the_planes_own(tick):
    sim, paths = tick
    met = {names[0] for _, names in paths}
    assert met == set(scopes.phases_for(sim.tick_impl))
    assert scopes.phases_for("sparse") == scopes.PHASES_SPARSE
    assert scopes.phases_for("dense") == scopes.PHASES


def test_every_name_met_is_registered_under_its_phase(tick):
    _, paths = tick
    for _, names in paths:
        for name in names[1:]:
            assert name in scopes.PARTS[names[0]], (names[0], name)


def test_the_overlays_parts_are_met(tick):
    sim, paths = tick
    met = {n for _, names in paths for n in names[1:]}
    mine = OWN[type(sim.logic).__name__]
    own = {p for p in scopes.PARTS["phase.node_step"] if p.startswith(mine)}
    assert own and own <= met, own - met
    others = tuple(p for ps in OWN.values() for p in ps if p not in mine)
    assert not [n for n in met if n.startswith(others)]
    for part in ("lookup.responses", "lookup.timeouts", "lookup.pump",
                 "app.kbrtest", "pool.alloc", "stats.record", "churn.step"):
        assert part in met, part
    if sim.tick_impl == "sparse":
        assert {"step.gather", "step.write_back", "inbox.rank"} <= met


def _parts_under(paths, prefix):
    """The primitives of every part whose name starts with ``prefix``."""
    under = {}
    for prim, names in paths:
        for part in names[1:]:
            if part.startswith(prefix):
                under.setdefault(part, []).append(prim)
    return under


def test_the_lookup_engine_scatters_nothing(tick):
    """A lookup slot is written by mask (common/lookup.py, ISSUE 42):
    under the node step's vmap an indexed write is a scatter of A
    updates a leaf, 108 of them under ``lookup.start`` before."""
    _, paths = tick
    under = _parts_under(paths, "lookup.")
    assert {"lookup.start", "lookup.pump", "lookup.responses"} <= set(under)
    scatters = {part: [p for p in prims if p.startswith("scatter")]
                for part, prims in under.items()}
    assert not any(scatters.values()), scatters
    # the writes are there, as selects
    for part in ("lookup.start", "lookup.pump", "lookup.responses"):
        assert under[part].count("select_n") >= 4, part


def test_the_routed_path_scatters_nothing(tick):
    """An ACK slot is written by mask (common/route.py, ISSUE 47): under
    the node step's vmap an indexed write is a scatter of A updates a
    leaf, some 170 of them under ``route.*`` in a Bamboo step of R = 8
    before (fourteen leaves a ``forward``)."""
    sim, paths = tick
    under = _parts_under(paths, "route.")
    if not under:
        pytest.skip(f"{type(sim.logic).__name__}'s tick meets no route.* "
                    "part (iterative routing)")
    assert set(under) == {"route.forward", "route.acks", "route.timeouts"}
    scatters = {part: [p for p in prims if p.startswith("scatter")]
                for part, prims in under.items()}
    assert not any(scatters.values()), scatters
    # the writes are there, as selects: thirteen leaves a ``forward`` at
    # R + 1 call sites, two an ACK, two a slot given up
    r, q = sim.ep.inbox_slots, sim.logic.rcfg.slots
    assert under["route.forward"].count("select_n") >= 13 * (r + 1)
    assert under["route.acks"].count("select_n") >= r
    assert under["route.timeouts"].count("select_n") >= q


# ``sort`` equations in a tick (N = 32, R = 2) on either plane: Kademlia's
# 10 less ``_find_node_batch``'s two (the inbox keys' and the timer
# keys', ISSUE 46), Chord's 13 less the three calls of ``_find_node`` and
# the notify handler's closest notifier
SORTS = {"KademliaLogic": 8, "ChordLogic": 9, "BambooLogic": None}


def test_chord_find_node_sorts_nothing(tick):
    """Chord's closest preceding node is an argmin (ISSUE 44:
    ``K.argmin_by_distance``): findNode keeps one next hop, and a sort
    of the 168 fingers and successors ran for each inbox slot of each
    stepped lane."""
    sim, paths = tick
    sorts = [names for prim, names in paths if prim == "sort"]
    if type(sim.logic).__name__ == "BambooLogic":
        # Pastry keeps its sorts (findNode's closest leaf, the leaf
        # halves' merge; findNode's closer-known fallbacks are argmins
        # since PR 45); every one lies under a part of Pastry's own
        assert sorts and all(
            any(n.startswith("pastry.") for n in names) or "outbox.finish"
            in names or names[0] != "phase.node_step" for names in sorts)
        return
    assert len(sorts) == SORTS[type(sim.logic).__name__]
    if type(sim.logic).__name__ == "KademliaLogic":
        return
    under = [prim for prim, names in paths if "chord.find_node" in names]
    assert len(under) > 100 and "sort" not in under
    # the reductions are there
    assert under.count("reduce_min") >= 3
    # the sorts that keep more than one candidate stay (the broadcast's)
    assert [names for names in sorts if "chord.broadcast" in names]


def test_kademlia_find_node_sorts_nothing(tick):
    """Kademlia's findNode keeps ``lookupRedundantNodes`` of its 1 + s +
    B k candidates (ISSUE 46: ``K.closest_k_by_distance``, that many
    passes of the argmin), where a sort of all of them ran for each
    inbox key and each timer key of each stepped lane."""
    sim, paths = tick
    under = [prim for prim, names in paths if "kademlia.find_node" in names]
    if type(sim.logic).__name__ != "KademliaLogic":
        assert not under
        return
    assert len(under) > 1000
    assert "sort" not in under
    assert not [prim for prim in under if prim.startswith("scatter")]
    # the passes are there: a ``min`` a comparator lane and one of the
    # index, and the payload by a masked sum
    k = sim.logic.p.redundant_nodes
    assert under.count("reduce_min") >= 3 * k
    assert under.count("reduce_sum") >= k
    # the sorts that keep the whole order or the displaced set stay
    sorts = [names for prim, names in paths if prim == "sort"]
    for part, least in (("kademlia.routing_add", 3),
                        ("kademlia.bucket_update", 2), ("kademlia.failed", 1)):
        assert sum(part in names for names in sorts) >= least, part


def test_no_registered_name_holds_scatter():
    # analysis/hlo_text._SCATTER_WHILE tells a scatter's own loop by
    # ``/scatter`` in its op_name
    assert scopes.REGISTRY
    assert not [n for n in scopes.REGISTRY if "scatter" in n]
    every = set(scopes.PHASES + scopes.PHASES_SPARSE)
    assert set(scopes.PARTS) <= every
    for parts in scopes.PARTS.values():
        assert not every & set(parts)


def test_an_unregistered_name_is_refused():
    with pytest.raises(KeyError, match="phase.nowhere"):
        scopes.scope("phase.nowhere")
    with pytest.raises(KeyError):
        scopes.scoped("kademlia.nothing")


def test_the_compile_cache_keys_by_metadata(tmp_path, monkeypatch):
    """An executable out of the cache otherwise carries the scopes of
    the tree that first compiled it (hostcache.enable)."""
    flags = ("jax_compilation_cache_include_metadata_in_key",
             "jax_hlo_source_file_canonicalization_regex",
             "jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs")
    before = {f: getattr(jax.config, f) for f in flags}
    monkeypatch.setenv(hostcache.CACHE_ENV, str(tmp_path))
    try:
        assert hostcache.enable(persistent=True) == str(tmp_path)
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        # and not by where the checkout lives
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert re.sub(jax.config.jax_hlo_source_file_canonicalization_regex,
                      "", hostcache.__file__) == "oversim_tpu/hostcache.py"
        assert hostcache.__file__.startswith(root)
    finally:
        for f, v in before.items():
            jax.config.update(f, v)
