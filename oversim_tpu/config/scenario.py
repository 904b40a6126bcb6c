"""Scenario builder: resolved .ini parameters → a runnable Simulation.

The reference wires a simulation from string-configured module types
(``**.overlayType = "oversim.overlay.chord.ChordModules"``,
``**.tier1Type = "...KBRTestAppModules"``, churnGeneratorTypes —
simulations/default.ini:622-628) plus per-module parameter namespaces.
This module is the equivalent factory: it reads the same namespaces off an
`IniFile` and instantiates the engine's typed params / logic objects, so a
reference config runs against the TPU backend unchanged.
"""

from __future__ import annotations

import dataclasses

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps import kbrtest
from oversim_tpu.common import lookup as lk_mod
from oversim_tpu.config.ini import IniFile, Study
from oversim_tpu.core import keys as K
from oversim_tpu.engine import sim as sim_mod
from oversim_tpu.underlay import simple as underlay_mod

HOST = "OverSim.overlayTerminal[0]"   # representative node path


def _value(x, default=None):
    if isinstance(x, Study):
        x = x.default()
    return default if x is None else x


class ScenarioError(ValueError):
    pass


def resolve_tick_impl(value: str) -> str:
    """Validate a raw ``**.tickImpl`` string — ``"auto"`` (the default,
    also with no key at all: the awake-set plane for an overlay and app
    that declare it exact, today Kademlia and Chord under KBRTestApp,
    the dense sweep for every other), ``"dense"`` (the full-N vmapped
    sweep, the bit-identity oracle) or ``"sparse"`` (the awake-set
    plane by name: only awake nodes run the logic step, in rounds of
    ``**.activeCap`` lanes, bit-identical to dense; engine/sim.py
    ``_step_sparse``; refused for a logic without the declaration).
    Which plane ``"auto"`` comes to is the engine's to say
    (``sim_mod.resolve_tick_impl``); anything else raises
    :class:`ScenarioError`."""
    impl = str(value).strip().strip('"')
    if impl not in ("auto", "dense", "sparse"):
        raise ScenarioError(f"unsupported tickImpl: {impl!r} (expected "
                            "\"auto\", \"dense\" or \"sparse\")")
    return impl


def _get(ini, config, suffix, default=None):
    return _value(ini.get(f"{HOST}.{suffix}", config), default)


def build_churn(ini: IniFile, config: str) -> churn_mod.ChurnParams:
    gen = str(ini.get("OverSim.churnGenerator[0].__type__", config)
              or _value(ini.get("**.churnGeneratorTypes", config),
                        "oversim.common.NoChurn"))
    target = int(_value(ini.get("**.targetOverlayTerminalNum", config), 10))
    init_interval = float(_value(
        ini.get("**.initPhaseCreationInterval", config), 0.1))
    model = ("lifetime" if "LifetimeChurn" in gen
             else "pareto" if "ParetoChurn" in gen
             else "random" if "RandomChurn" in gen
             else "none")
    kw = {}
    if model in ("lifetime", "pareto"):
        kw["lifetime_mean"] = float(_value(
            ini.get("**.lifetimeMean", config), 10000.0))
        dist = str(_value(ini.get("**.lifetimeDistName", config), "weibull"))
        kw["lifetime_dist"] = dist
        kw["lifetime_par1"] = float(_value(
            ini.get("**.lifetimeDistPar1", config), 1.0))
        # the leave notice (default.ini:493-494); ChurnParams' defaults
        # are upstream's, so an ini that is silent reads as before
        for key, name in (("**.gracefulLeaveDelay", "graceful_leave_delay"),
                          ("**.gracefulLeaveProbability",
                           "graceful_leave_probability")):
            raw = ini.get(key, config)
            if raw is not None:
                kw[name] = float(_value(raw))
    if model == "pareto":
        dm = ini.get("**.deadtimeMean", config)
        if dm is not None:
            kw["deadtime_mean"] = float(_value(dm))
    # the reference draws each creation gap from truncnormal(interval,
    # interval / 3) (NoChurn.cc handleMessage; LifetimeChurn.cc
    # initialDeviation = initialMean / 3) — the deviation SCALES with
    # the interval.  ChurnParams' absolute 0.1 s default would stretch
    # a 4096-node fill at 20 s / 4096 per node to ~330 s (the mean of
    # |N(0.005, 0.1)| is 0.08 s, not 0.005 s).
    return churn_mod.ChurnParams(
        model=model, target_num=target, init_interval=init_interval,
        init_deviation=init_interval / 3.0, **kw)


def build_underlay(ini: IniFile, config: str):
    """(params, module) — the ``network`` line picks the underlay family
    (reference default.ini:16 SimpleUnderlayNetwork vs omnetpp.ini
    InetUnderlayNetwork/ReaSEUnderlayNetwork configs)."""
    net = str(_value(ini.get("network", config), "")).lower()
    if "inet" in net or "rease" in net:
        from oversim_tpu.underlay import inet as inet_mod
        params = inet_mod.InetUnderlayParams(
            topology="rease" if "rease" in net else "inet",
            routers=int(_value(
                ini.get("**.accessRouterNum", config), 16)),
            send_queue_bytes=int(_value(
                ini.get("**.sendQueueLength", config), 1_000_000)),
        )
        return params, inet_mod
    coord_src = str(_value(
        ini.get("**.nodeCoordinateSource", config), "")).strip('"')
    if coord_src:
        import os as _os
        if not _os.path.isabs(coord_src):
            coord_src = str(ini.base_dir / coord_src)
    params = underlay_mod.UnderlayParams(
        coord_source=coord_src,
        field_size=float(_value(ini.get("**.fieldSize", config), 150.0)),
        send_queue_bytes=int(_value(
            ini.get("**.sendQueueLength", config), 1_000_000)),
        constant_delay=float(_value(
            ini.get("**.constantDelay", config), 0.050)),
        use_coordinate_based_delay=bool(_value(
            ini.get("**.useCoordinateBasedDelay", config), True)),
    )
    return params, underlay_mod


def _build_dht(ini, config, spec, trace):
    from oversim_tpu.apps.dht import DhtApp, DhtParams
    return DhtApp(DhtParams(
        num_replica=int(_get(ini, config, "tier1.dht.numReplica", 4)),
        num_get_requests=int(_get(
            ini, config, "tier1.dht.numGetRequests", 4)),
        ratio_identical=float(_get(
            ini, config, "tier1.dht.ratioIdentical", 0.5)),
        test_interval=float(_get(
            ini, config, "tier2.dhtTestApp.testInterval", 60.0)),
        test_ttl=float(_get(
            ini, config, "tier2.dhtTestApp.testTtl", 300.0)),
    ), spec, trace=trace)


def _build_kbrtest(ini, config, spec, trace):
    from oversim_tpu.apps.kbrtest import KbrTestApp
    return KbrTestApp(kbrtest.KbrTestParams(
        test_interval=float(_get(
            ini, config, "tier1.kbrTestApp.testMsgInterval", 60.0)),
        test_msg_bytes=int(_get(
            ini, config, "tier1.kbrTestApp.testMsgSize", 100)),
        oneway_test=bool(_get(
            ini, config, "tier1.kbrTestApp.kbrOneWayTest", True)),
        rpc_test=bool(_get(
            ini, config, "tier1.kbrTestApp.kbrRpcTest", False)),
        lookup_test=bool(_get(
            ini, config, "tier1.kbrTestApp.kbrLookupTest", False)),
    ))


def _build_scribe(ini, config, spec, trace):
    from oversim_tpu.apps.scribe import ScribeApp, ScribeParams
    return ScribeApp(ScribeParams(
        num_groups=int(_get(ini, config, "tier2.almTest.groupNum", 4)),
    ), spec)


def _build_simmud(ini, config, spec, trace):
    from oversim_tpu.apps.simmud import SimMudApp, SimMudParams
    return SimMudApp(SimMudParams(), spec)


def _build_i3(ini, config, spec, trace):
    from oversim_tpu.apps.i3 import I3App
    return I3App(spec=spec)


def _build_p2pns(ini, config, spec, trace):
    from oversim_tpu.apps.p2pns import P2pnsApp
    return P2pnsApp(spec=spec)


def _build_ntree_app(ini, config, spec, trace):
    from oversim_tpu.apps.ntree import NTreeApp
    return NTreeApp(spec=spec)


def _build_broadcast(ini, config, spec, trace):
    from oversim_tpu.apps.broadcast import BroadcastTestApp
    return BroadcastTestApp()


def _build_dummy(ini, config, spec, trace):
    from oversim_tpu.apps.dummy import TierDummyApp
    return TierDummyApp()


# substring → factory; ordered (first match wins); entries absorbing a
# second tier list the partner substrings to consume
_TIER_FACTORIES = (
    ("KBRTestApp", _build_kbrtest, ()),
    ("DHTTestApp", _build_dht, ("DHT",)),      # tier2 naming the pair
    ("DHT", _build_dht, ("DHTTestApp",)),      # tier1 DHT + tier2 tester
    ("SimMud", _build_simmud, ("Scribe",)),
    ("Scribe", _build_scribe, ("ALMTest",)),
    ("ALMTest", _build_scribe, ("Scribe",)),
    ("I3", _build_i3, ()),
    ("P2pns", _build_p2pns, ()),
    ("P2PNS", _build_p2pns, ()),
    ("NTree", _build_ntree_app, ()),
    ("Broadcast", _build_broadcast, ()),
    ("TierDummy", _build_dummy, ()),
    ("MyApplication", _build_dummy, ()),
)


def build_app(ini: IniFile, config: str, spec: K.KeySpec, trace=None):
    """tier1Type/tier2Type/tier3Type strings → app object (reference
    default.ini:622-628 ITier plugin selection, SimpleOverlayHost.ned:
    14-100).  Multiple distinct tier apps compose into a generic
    :class:`~oversim_tpu.apps.stack.TierStack`; pairs the rebuild fuses
    into one object (DHT+DHTTestApp, Scribe+ALMTest) count as one tier.
    ``trace`` is an optional trace.TraceWorkload for trace-driven DHT
    runs (forces a DHT tier like the reference's trace manager)."""
    tiers = [str(_value(ini.get(f"**.tier{i}Type", config), ""))
             for i in (1, 2, 3)]
    # pre-scan ALL tiers before absorbing: the reference orders fused
    # pairs both ways (tier1 DHT + tier2 DHTTestApp, but tier1 Scribe +
    # tier2 SimMud), so first-match-wins in tier order would build both
    # halves of a pair
    matched = []
    for tname in tiers:
        if not tname or tname in ("\"\"",):
            continue
        for sub, factory, absorbs in _TIER_FACTORIES:
            if sub in tname:
                matched.append((sub, factory, absorbs))
                break
        # XmlRpcInterface (tier3) is the host-side gateway surface
        # (xmlrpcif.py over gateway.py), not an in-sim tier — ignored
        # here like the reference's GUI-only modules
    # fused pairs hitting the same factory collapse to one instance
    uniq, seen_fac = [], set()
    for sub, factory, absorbs in matched:
        if factory not in seen_fac:
            uniq.append((sub, factory, absorbs))
            seen_fac.add(factory)
    # an entry another surviving entry absorbs is that entry's lower
    # half (Scribe under SimMud) — drop it
    apps = [factory(ini, config, spec, trace)
            for sub, factory, absorbs in uniq
            if not any(sub in o[2] for o in uniq if o[1] is not factory)]
    if trace is not None and not any(
            type(a).__name__ == "DhtApp" for a in apps):
        apps.insert(0, _build_dht(ini, config, spec, trace))
    if not apps:
        return _build_kbrtest(ini, config, spec, trace)
    if len(apps) == 1:
        return apps[0]
    from oversim_tpu.apps.stack import TierStack
    return TierStack(apps)


def build_malicious(ini: IniFile, config: str):
    """maliciousNodeProbability + attack switches (default.ini:529-536,
    BaseOverlay.h:203-206) → MaliciousParams."""
    from oversim_tpu.common.malicious import MaliciousParams
    return MaliciousParams(
        probability=float(_value(
            ini.get("**.maliciousNodeProbability", config), 0.0)),
        drop_find_node=bool(_get(
            ini, config, "overlay.dropFindNodeAttack", False)),
        is_sibling=bool(_get(
            ini, config, "overlay.isSiblingAttack", False)),
        invalid_nodes=bool(_get(
            ini, config, "overlay.invalidNodesAttack", False)),
    )


def build_lookup_config(ini: IniFile, config: str, proto: str,
                        merge_default: bool) -> lk_mod.LookupConfig:
    ns = f"overlay.{proto}"
    paths = int(_get(ini, config, f"{ns}.lookupParallelPaths", 1))
    rpcs = int(_get(ini, config, f"{ns}.lookupParallelRpcs", 1))
    rt = str(_value(ini.get("**.routingType", config),
                    "iterative")).strip('"')
    return lk_mod.LookupConfig(
        merge=bool(_get(ini, config, f"{ns}.lookupMerge", merge_default)),
        # reference tracks paths as separate objects sharing one visited
        # set (IterativeLookup.cc:529); the vectorized engine expresses
        # paths x rpcs as total in-flight width R (lookup.py docstring)
        parallel_rpcs=max(1, paths * rpcs),
        # per-RPC re-send count.  The reference passes retries as a
        # lookup() call argument (AbstractLookup.h), not an ini param
        # (lookupFailedNodeRpcs is the unrelated failed-node-notice
        # bool) — `lookupRetries` is this framework's ini extension
        retries=int(_get(ini, config, f"{ns}.lookupRetries", 0)),
        exhaustive=rt == "exhaustive-iterative",
        # PROX_AWARE_ITERATIVE_ROUTING (CommonMessages.msg:140; enum-only
        # in the reference — implemented here, lookup.py prox_aware)
        prox_aware=rt == "prox-aware-iterative",
        rpc_timeout_ns=int(float(_value(
            ini.get("**.rpcUdpTimeout", config), 1.5)) * 1e9),
    )


def build_telemetry(ini: IniFile, config: str):
    """``**.telemetry.*`` keys → TelemetryParams (framework ini
    extension — the device-resident KPI time-series plane,
    oversim_tpu/telemetry.py):

      **.telemetry.sampleTicks = 16       snapshot cadence (0 = off)
      **.telemetry.window      = 256      ring-buffer capacity W
      **.telemetry.include     = "kbr_hopcount kbr_hop_hist"
                                          substring tap filter (optional;
                                          overrides the app's kpi_spec)
    """
    from oversim_tpu import telemetry as telemetry_mod
    sample_ticks = int(_value(
        ini.get("**.telemetry.sampleTicks", config), 0))
    if sample_ticks < 0:
        raise ScenarioError(f"**.telemetry.sampleTicks must be >= 0, "
                            f"got {sample_ticks}")
    window = int(_value(ini.get("**.telemetry.window", config), 256))
    if sample_ticks > 0 and window < 1:
        raise ScenarioError(f"**.telemetry.window must be >= 1, "
                            f"got {window}")
    raw = _value(ini.get("**.telemetry.include", config), "")
    include = tuple(str(raw).strip().strip('"').replace(",", " ").split())
    return telemetry_mod.TelemetryParams(
        sample_ticks=sample_ticks, window=window, include=include)


def build_simulation(ini: IniFile, config: str = "General",
                     engine_params: sim_mod.EngineParams | None = None,
                     trace_events=None):
    """Instantiate the full Simulation for one [Config ...] section.

    ``trace_events``: parsed trace.TraceEvent list — overrides the churn
    model with the trace schedule, drives the DHT workload from PUT/GET
    commands, and applies CONNECT/DISCONNECT_NODETYPES partitions
    (reference GlobalTraceManager)."""
    overlay_type = str(_value(ini.get("**.overlayType", config), ""))
    spec = K.KeySpec(int(_value(ini.get("**.keyLength", config), 160)))
    up, ul_mod = build_underlay(ini, config)
    workload = None
    if trace_events is not None:
        from oversim_tpu import trace as trace_mod
        cp = trace_mod.churn_from_trace(trace_events)
        workload = trace_mod.workload_from_trace(trace_events, cp.num_slots,
                                                 spec)
        ps = trace_mod.partitions_from_trace(trace_events)
        if len(ps.t):
            ntypes = int(max(ps.a.max(), ps.b.max())) + 1
            bounds = tuple(cp.num_slots * i // ntypes
                           for i in range(1, ntypes))
            up = dataclasses.replace(
                up, num_node_types=ntypes, type_boundaries=bounds,
                partition_events=tuple(
                    (float(t), int(a), int(b), bool(c))
                    for t, a, b, c in zip(ps.t, ps.a, ps.b, ps.connect)))
    else:
        cp = build_churn(ini, config)
    ap = build_app(ini, config, spec, trace=workload)
    mp = build_malicious(ini, config)
    tick_impl = resolve_tick_impl(_value(
        ini.get("**.tickImpl", config), "auto"))
    ep = engine_params or sim_mod.EngineParams(
        transition_time=float(_value(
            ini.get("**.transitionTime", config), 0.0)),
        measurement_time=float(_value(
            ini.get("**.measurementTime", config), -1.0)),
        # **.tickImpl: "auto" (default: the awake-set plane where the
        # logic declares it exact, else dense) | "dense" (full-N
        # oracle) | "sparse" (awake-set plane); **.activeCap sets the
        # lanes a round (0 = auto; never moves a result) — this
        # framework's ini extension, engine/sim.py
        tick_impl=tick_impl,
        active_cap=int(_value(ini.get("**.activeCap", config), 0)),
        malicious=mp,
        telemetry=build_telemetry(ini, config),
    )

    if "chord" in overlay_type.lower():
        from oversim_tpu.overlay.chord import ChordLogic, ChordParams
        params = ChordParams(
            join_delay=float(_get(ini, config, "overlay.chord.joinDelay",
                                  10.0)),
            stabilize_delay=float(_get(
                ini, config, "overlay.chord.stabilizeDelay", 20.0)),
            fixfingers_delay=float(_get(
                ini, config, "overlay.chord.fixfingersDelay", 120.0)),
            check_pred_delay=float(_get(
                ini, config, "overlay.chord.checkPredecessorDelay", 5.0)),
            succ_size=int(_get(
                ini, config, "overlay.chord.successorListSize", 8)),
            aggressive_join=bool(_get(
                ini, config, "overlay.chord.aggressiveJoinMode", True)),
        )
        logic = ChordLogic(spec, params,
                           build_lookup_config(ini, config, "chord", False),
                           ap, mparams=mp)
    elif "kademlia" in overlay_type.lower():
        from oversim_tpu.overlay.kademlia import (KademliaLogic,
                                                  KademliaParams)
        params = KademliaParams(
            k=int(_get(ini, config, "overlay.kademlia.k", 8)),
            s=int(_get(ini, config, "overlay.kademlia.s", 8)),
            max_stale=int(_get(
                ini, config, "overlay.kademlia.maxStaleCount", 0)),
            sibling_refresh=float(_get(
                ini, config,
                "overlay.kademlia.minSiblingTableRefreshInterval", 1000.0)),
            bucket_refresh=float(_get(
                ini, config,
                "overlay.kademlia.minBucketRefreshInterval", 1000.0)),
            redundant_nodes=int(_get(
                ini, config, "overlay.kademlia.lookupRedundantNodes", 8)),
        )
        logic = KademliaLogic(spec, params,
                              build_lookup_config(ini, config, "kademlia",
                                                  True), ap, mparams=mp)
    elif "pastry" in overlay_type.lower() or "bamboo" in overlay_type.lower():
        from oversim_tpu.overlay.pastry import (BambooLogic, PastryLogic,
                                                PastryParams, bamboo_params)
        proto = ("bamboo" if "bamboo" in overlay_type.lower() else "pastry")
        base = bamboo_params() if proto == "bamboo" else PastryParams()

        def key(name, default):
            return _get(ini, config, f"overlay.{proto}.{name}", default)

        routing = str(key("routingType", base.routing_mode)).strip('"')
        if routing not in ("semi-recursive", "iterative"):
            raise ScenarioError(
                f"overlay.{proto}.routingType = {routing!r}: "
                "overlay/pastry.py routes \"semi-recursive\" or "
                "\"iterative\"")
        acks = key("routeMsgAcks", base.route_acks)
        params = PastryParams(
            bits_per_digit=int(key("bitsPerDigit", base.bits_per_digit)),
            num_leaves=int(key("numberOfLeaves", base.num_leaves)),
            join_delay=int(key("joinTimeout", 20)),
            leafset_interval=float(key("leafsetMaintenanceInterval",
                                       base.leafset_interval)),
            local_tuning_interval=float(key("localTuningInterval",
                                            base.local_tuning_interval)),
            tuning_interval=float(key("globalTuningInterval",
                                      base.tuning_interval)),
            routing_mode=routing,
            route_acks=(acks if isinstance(acks, bool)
                        else str(acks).strip('"').lower() == "true"),
            rec_redundant=int(key("recNumRedundantNodes",
                                  base.rec_redundant)),
        )
        cls = BambooLogic if proto == "bamboo" else PastryLogic
        logic = cls(spec, params,
                    build_lookup_config(ini, config, proto, False), ap)
    elif "koorde" in overlay_type.lower():
        from oversim_tpu.overlay.koorde import KoordeLogic, KoordeParams
        params = KoordeParams(
            stabilize_delay=float(_get(
                ini, config, "overlay.koorde.stabilizeDelay", 10.0)),
            succ_size=int(_get(
                ini, config, "overlay.koorde.successorListSize", 16)),
            de_bruijn_delay=float(_get(
                ini, config, "overlay.koorde.deBruijnDelay", 30.0)),
            de_bruijn_size=int(_get(
                ini, config, "overlay.koorde.deBruijnListSize", 16)),
            shifting_bits=int(_get(
                ini, config, "overlay.koorde.shiftingBits", 4)),
        )
        logic = KoordeLogic(spec, params, app=ap)
    elif "broose" in overlay_type.lower():
        from oversim_tpu.overlay.broose import BrooseLogic, BrooseParams
        params = BrooseParams(
            bucket_size=int(_get(
                ini, config, "overlay.broose.bucketSize", 8)),
            r_bucket_size=int(_get(
                ini, config, "overlay.broose.rBucketSize", 8)),
            shifting_bits=int(_value(
                ini.get("**.brooseShiftingBits", config), 2)),
            join_delay=float(_get(
                ini, config, "overlay.broose.joinDelay", 10.0)),
            refresh_time=float(_get(
                ini, config, "overlay.broose.refreshTime", 180.0)),
        )
        logic = BrooseLogic(spec, params, app=ap)
    elif "epichord" in overlay_type.lower():
        from oversim_tpu.overlay.epichord import (EpiChordLogic,
                                                  EpiChordParams)
        params = EpiChordParams(
            succ_size=int(_get(
                ini, config, "overlay.epichord.successorListSize", 4)),
            join_delay=float(_get(
                ini, config, "overlay.epichord.joinDelay", 10.0)),
            stabilize_delay=float(_get(
                ini, config, "overlay.epichord.stabilizeDelay", 20.0)),
            cache_flush_delay=float(_get(
                ini, config, "overlay.epichord.cacheFlushDelay", 20.0)),
            cache_check_mult=int(_get(
                ini, config, "overlay.epichord.cacheCheckMultiplier", 3)),
            cache_ttl=float(_get(
                ini, config, "overlay.epichord.cacheTTL", 120.0)),
            nodes_per_slice=int(_get(
                ini, config, "overlay.epichord.nodesPerSlice", 2)),
            redundant_nodes=int(_get(
                ini, config, "overlay.epichord.lookupRedundantNodes", 3)),
        )
        logic = EpiChordLogic(spec, params,
                              build_lookup_config(ini, config, "epichord",
                                                  True), ap)
    elif "gia" in overlay_type.lower():
        from oversim_tpu.overlay.gia import GiaLogic, GiaParams
        params = GiaParams(
            min_neighbors=int(_get(
                ini, config, "overlay.gia.minNeighbors", 3)),
            max_neighbors=int(_get(
                ini, config, "overlay.gia.maxNeighbors", 10)),
            adapt_interval=float(_get(
                ini, config, "overlay.gia.maxTopAdaptionInterval", 10.0)),
            search_ttl=int(_get(
                ini, config, "overlay.gia.maxHopCount", 20)),
            max_responses=int(_get(
                ini, config, "overlay.gia.maxResponses", 1)),
            token_wait=float(_get(
                ini, config, "overlay.gia.tokenWaitTime", 1.0)),
        )
        logic = GiaLogic(spec, params)
    elif "nice" in overlay_type.lower():
        from oversim_tpu.overlay.nice import NiceLogic, NiceParams
        params = NiceParams(
            k=int(_get(ini, config, "overlay.nice.k", 3)),
            hb_interval=float(_get(
                ini, config, "overlay.nice.heartbeatInterval", 5.0)),
            maint_interval=float(_get(
                ini, config, "overlay.nice.maintenanceInterval", 3.3)),
            query_interval=float(_get(
                ini, config, "overlay.nice.queryInterval", 2.0)),
            peer_timeout_hbs=float(_get(
                ini, config, "overlay.nice.peerTimeoutHeartbeats", 3.0)),
        )
        logic = NiceLogic(spec, params)
    elif "quon" in overlay_type.lower():
        from oversim_tpu.overlay.quon import QuonLogic, QuonParams
        params = QuonParams(
            aoi=float(_get(ini, config, "overlay.quon.AOIWidth", 100.0)),
        )
        logic = QuonLogic(spec, params)
    elif "vast" in overlay_type.lower():
        from oversim_tpu.overlay.vast import VastLogic, VastParams
        params = VastParams(
            aoi=float(_get(ini, config, "overlay.vast.AOIWidth", 100.0)),
        )
        logic = VastLogic(spec, params)
    elif "ntree" in overlay_type.lower():
        # NTree runs as a tier app over a KBR overlay here (rendezvous-
        # hashed cell leadership; apps/ntree.py docstring) — the
        # reference's NTreeModules overlay maps to Chord + NTreeApp
        from oversim_tpu.apps.ntree import NTreeApp, NTreeParams
        from oversim_tpu.overlay.chord import ChordLogic
        ap = NTreeApp(NTreeParams(
            max_children=int(_value(
                ini.get("**.maxChildren", config), 5))), spec=spec)
        logic = ChordLogic(spec, app=ap)
    elif "pubsub" in overlay_type.lower():
        from oversim_tpu.overlay.pubsubmmog import (PubSubMMOGLogic,
                                                    PubSubParams)
        params = PubSubParams(
            field=float(_get(
                ini, config, "overlay.pubsubmmog.areaDimension", 1000.0)),
            grid=int(_get(
                ini, config, "overlay.pubsubmmog.numSubspaces", 4)),
            aoi=float(_get(
                ini, config, "overlay.pubsubmmog.AOIWidth", 100.0)),
            move_rate=float(_get(
                ini, config, "overlay.pubsubmmog.movementRate", 2.0)),
            parent_timeout=float(_get(
                ini, config, "overlay.pubsubmmog.parentTimeout", 2.0)),
            max_move_delay=float(_get(
                ini, config, "overlay.pubsubmmog.maxMoveDelay", 1.0)),
            max_children=int(_get(
                ini, config, "overlay.pubsubmmog.maxChildren", 12)),
        )
        logic = PubSubMMOGLogic(spec, params)
    else:
        raise ScenarioError(f"unsupported overlayType: {overlay_type!r}")

    try:
        return sim_mod.Simulation(logic, cp, up, ep, underlay_module=ul_mod)
    except ValueError as e:     # tickImpl "sparse" for an undeclared logic
        raise ScenarioError(str(e)) from None


# -- campaign (multi-replica) configuration ---------------------------------
#
# Framework ini extension (no reference equivalent — the reference runs
# repetitions as separate ./OverSim -r N processes):
#
#   **.campaign.replicas  = 8            seed replicas per grid point
#   **.campaign.baseSeed  = 1            replica r rng = fold_in(seed, r)
#   **.campaign.sweep.lifetimeMean    = "5000 10000 20000"
#   **.campaign.sweep.testMsgInterval = "10, 60"
#   **.campaign.sweep.window          = "0.05 0.1"
#
# Sweep values are space/comma-separated (quotes optional); declared
# axes form a cartesian grid, total replicas S = replicas × grid size.

_SWEEP_KEYS = (
    ("**.campaign.sweep.lifetimeMean", "churn.lifetimeMean"),
    ("**.campaign.sweep.testMsgInterval", "app.testMsgInterval"),
    ("**.campaign.sweep.window", "engine.window"),
)


def _sweep_values(raw, key):
    s = str(raw).strip().strip('"')
    try:
        vals = tuple(float(x) for x in s.replace(",", " ").split())
    except ValueError:
        vals = ()
    if not vals:
        raise ScenarioError(f"bad sweep value list for {key}: {raw!r}")
    return vals


def build_campaign_params(ini: IniFile, config: str = "General"):
    """``**.campaign.*`` keys → CampaignParams (see the comment above)."""
    from oversim_tpu.campaign import CampaignParams
    replicas = int(_value(ini.get("**.campaign.replicas", config), 1))
    if replicas < 1:
        raise ScenarioError(f"**.campaign.replicas must be >= 1, "
                            f"got {replicas}")
    base_seed = int(_value(ini.get("**.campaign.baseSeed", config), 1))
    sweep = []
    for ini_key, ov_name in _SWEEP_KEYS:
        raw = _value(ini.get(ini_key, config))
        if raw is None:
            continue
        sweep.append((ov_name, _sweep_values(raw, ini_key)))
    return CampaignParams(replicas=replicas, base_seed=base_seed,
                          sweep=tuple(sweep))


def build_campaign(ini: IniFile, config: str = "General",
                   engine_params: sim_mod.EngineParams | None = None,
                   trace_events=None):
    """build_simulation + ``**.campaign.*`` keys → a Campaign driver."""
    from oversim_tpu.campaign import Campaign
    sim = build_simulation(ini, config, engine_params=engine_params,
                           trace_events=trace_events)
    return Campaign(sim, build_campaign_params(ini, config))


def build_service(ini: IniFile, config: str = "General"):
    """``**.service.*`` keys → ServiceParams (framework ini extension —
    the resident serving loop, oversim_tpu/service/):

      **.service.windowSimS      = 1.0    simulated seconds per window
      **.service.chunk           = 32     ticks per device scan chunk
      **.service.checkpointEvery = 0      windows between checkpoints
      **.service.checkpointPath  = "x.npz"
      **.service.maxWindows      = 0      absolute window count (0 = ∞)
      **.service.maxWallS        = 0      wall budget per run() (0 = ∞)
      **.service.doubleBuffer    = true   pipeline fetch k / dispatch k+1
      **.service.realtime        = false  pace windows to wall clock
    """
    from oversim_tpu.service import ServiceParams
    window_sim_s = float(_value(
        ini.get("**.service.windowSimS", config), 1.0))
    if window_sim_s <= 0:
        raise ScenarioError(f"**.service.windowSimS must be > 0, "
                            f"got {window_sim_s}")
    chunk = int(_value(ini.get("**.service.chunk", config), 32))
    if chunk < 1:
        raise ScenarioError(f"**.service.chunk must be >= 1, got {chunk}")
    ckpt_every = int(_value(
        ini.get("**.service.checkpointEvery", config), 0))
    if ckpt_every < 0:
        raise ScenarioError(f"**.service.checkpointEvery must be >= 0, "
                            f"got {ckpt_every}")
    raw_path = _value(ini.get("**.service.checkpointPath", config))
    ckpt_path = (None if raw_path is None
                 else str(raw_path).strip().strip('"') or None)
    if ckpt_every > 0 and ckpt_path is None:
        raise ScenarioError("**.service.checkpointEvery set without a "
                            "**.service.checkpointPath")
    max_windows = int(_value(ini.get("**.service.maxWindows", config), 0))
    if max_windows < 0:
        raise ScenarioError(f"**.service.maxWindows must be >= 0, "
                            f"got {max_windows}")
    max_wall_s = float(_value(ini.get("**.service.maxWallS", config), 0.0))
    dbuf = bool(_value(ini.get("**.service.doubleBuffer", config), True))
    realtime = bool(_value(ini.get("**.service.realtime", config), False))
    return ServiceParams(
        window_sim_s=window_sim_s, chunk=chunk,
        checkpoint_every=ckpt_every, checkpoint_path=ckpt_path,
        max_windows=max_windows, max_wall_s=max_wall_s,
        double_buffer=dbuf, realtime=realtime)
