"""Chip smoke: one real deployment through the normal entry points on a TPU.

    python chip_smoke.py             one chip (what the driver runs)
    python chip_smoke.py --chips 4   ONLY the node-sharded path on a
                                     4-device mesh and the one-device
                                     run it is compared with

Drives simulations/kademlia4096.ini (Kademlia + KBRTestApp, N=4096, no
churn) the way a user would: ``IniFile.load`` -> ``build_simulation`` ->
``sim.init(seed)`` -> ``sim.run_until_device`` -> ``sim.summary``, with
ONE ``chunk`` for the whole run so exactly one tick program is compiled
(the target time is a traced argument).  The engine sizing is the ini's
documented assumption: ``EngineParams(window=0.2, inbox_slots=8,
pool_factor=8)``.

The run is one process, has no retry, no AOT store, no subprocess and
no ``try/except`` around a phase: any failure ends it non-zero.  It
refuses to start unless ``jax.devices()`` reports a TPU — JAX itself
falls back to the CPU when it finds no chip, and that fallback is the
one this script closes.  ``--rehearsal N`` (the builder's tool, never
the driver's) runs the same code at N nodes on whatever backend there
is; a rehearsal never prints ``"ok": true``.

Health gate (the bench's, bench.py ``on_window``): every node alive,
``kbr_sent > 0``, delivery >= 0.95, every ``*overflow*`` /
``*deferred*`` engine counter zero.  Delivery is taken over the steady
window [fill + 20 s, fill + 40 s] between two snapshots, and over
lookups old enough to have finished: ``delivered / max(sent,
finished)`` where ``finished = delivered + failed + wrong-node`` in the
window.  A lookup still in flight when the run stops is too young to
count as lost; but when fewer are in flight at the close than at the
opening, more end in the window than were sent in it and a plain
delivered/sent would read above 1 (1.0048 in PR 22's first chip run)
and hide that much real loss — so the denominator is whichever count
is larger, and the ratio can never exceed 1.

The last line of stdout is the contract's JSON object and nothing more.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
INI = os.path.join(HERE, "simulations", "kademlia4096.ini")
ENGINE = dict(window=0.2, inbox_slots=8, pool_factor=8)
CHUNK = 32          # ticks per scan; ONE value -> one tick program
FIRST_S = 1.0       # first call: compile + first ticks, to fill + 1 s
WARM_S = 20.0       # steady window opens at fill + 20 s
RUN_S = 40.0        # horizon: fill + 40 s
MIN_DELIVERY = 0.95
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(msg: str) -> None:
    print("chip_smoke: " + msg, flush=True)


def in_flight(out: dict) -> int:
    """Counted lookups that have not ended yet (every counter is gated
    on the SEND-time measurement bit, so the difference is exact)."""
    return (out["kbr_sent"] - out["kbr_delivered"]
            - out["kbr_lookup_failed"] - out["kbr_wrong_node"])


def health(base: dict, out: dict, n: int) -> list:
    """The bench gate over the window base -> out; returns the list of
    failures (empty = healthy)."""
    sent = out["kbr_sent"] - base["kbr_sent"]
    delivered = out["kbr_delivered"] - base["kbr_delivered"]
    finished = sent - (in_flight(out) - in_flight(base))
    ratio = delivered / max(sent, finished, 1)
    bad = []
    if out["_alive"] != n:
        bad.append(f"alive {out['_alive']} != {n}")
    if sent <= 0:
        bad.append("kbr_sent == 0 in the steady window")
    elif not MIN_DELIVERY <= ratio <= 1.0:
        bad.append(f"delivery {delivered}/max({sent}, {finished}) = "
                   f"{ratio:.4f} outside [{MIN_DELIVERY}, 1]")
    for k, v in out["_engine"].items():
        if ("overflow" in k or "deferred" in k) and v != 0:
            bad.append(f"engine counter {k} = {v}")
    say(f"steady window: sent {sent} finished {finished} delivered "
        f"{delivered} in flight {in_flight(base)} -> {in_flight(out)} "
        f"delivery {ratio:.4f}")
    return bad


def report(tag: str, out: dict) -> None:
    say(f"{tag}: ticks {out['_ticks']} sim_s {out['_t_sim']:.1f} "
        f"alive {out['_alive']} sent {out['kbr_sent']} "
        f"delivered {out['kbr_delivered']} "
        f"hop_mean {out['kbr_hopcount']['mean']:.3f} "
        f"latency_mean_s {out['kbr_latency_s']['mean']:.3f} "
        f"engine {json.dumps(out['_engine'])}")


def build_sim(n: int | None = None):
    """The deployment, through the normal entry points.  ``n`` overrides
    the ini's node count for a rehearsal (same 20 s fill)."""
    from oversim_tpu.config.ini import IniFile
    from oversim_tpu.config.scenario import build_simulation
    from oversim_tpu.engine.sim import EngineParams

    ini = IniFile.load(INI)
    config = "General"
    if n is not None:
        config = ini.with_overrides("General", {
            "**.targetOverlayTerminalNum": n,
            "**.initPhaseCreationInterval": 20.0 / n})
    return build_simulation(ini, config, EngineParams(**ENGINE))


def drive(run_to, sim, s, tag: str):
    """Three calls of ONE program: to fill+1 s (compile + first ticks),
    to fill+20 s (steady-window base), to the horizon.  ``run_to(s, t)``
    returns the advanced state.  Returns (final state, final summary,
    health failures)."""
    import jax
    fill, summary = sim.cp.init_finished_time, sim.summary
    t0 = time.perf_counter()
    s = jax.block_until_ready(run_to(s, fill + FIRST_S))
    t1 = time.perf_counter()
    say(f"{tag}: compile + first dispatch {t1 - t0:.1f} s "
        f"(to sim {float(s.t_now) / 1e9:.1f} s, {int(s.tick)} ticks)")
    s = jax.block_until_ready(run_to(s, fill + WARM_S))
    base = summary(s)
    t2 = time.perf_counter()
    s = jax.block_until_ready(run_to(s, fill + RUN_S))
    t3 = time.perf_counter()
    out = summary(s)
    ticks = out["_ticks"] - base["_ticks"]
    say(f"{tag}: rest {t3 - t1:.1f} s (warm {t2 - t1:.1f} s + steady "
        f"window {t3 - t2:.1f} s: {ticks} ticks, "
        f"{out['_t_sim'] - base['_t_sim']:.1f} sim-s)")
    report(tag, out)
    return s, out, health(base, out, sim.n)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = run ONLY the node-sharded mesh path and "
                         "the one-device run it is compared with")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearsal", type=int, default=None, metavar="N",
                    help="builder's rehearsal: N nodes on whatever "
                         "backend is there; never prints \"ok\": true")
    args = ap.parse_args(argv)

    # cache directory from outside ($JAX_COMPILATION_CACHE_DIR) or the
    # fixed path in the checkout — before the engine is imported
    from oversim_tpu import hostcache
    # (a rehearsal does not persist: XLA-CPU serialize() is the known
    # segfault of tests/conftest.py)
    cache = hostcache.enable(persistent=args.rehearsal is None)
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"device {json.dumps(device)}")
    say(f"compile cache {cache}")
    if device["platform"] != "tpu" and args.rehearsal is None:
        print(f"chip_smoke: no TPU: jax.devices() reports {devs!r}; "
              "refusing to run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.append(secs)
        if ev == COMPILE_EVENT else None)

    from oversim_tpu.engine.sim import NS

    sim = build_sim(args.rehearsal)
    n = sim.n
    say(f"scenario {os.path.relpath(INI, HERE)} n={n} "
        f"fill {sim.cp.init_finished_time:.1f} s engine "
        f"{json.dumps(ENGINE)} chunk {CHUNK} seed {args.seed}")

    def init():
        t0 = time.perf_counter()
        s = jax.block_until_ready(sim.init(args.seed))
        say(f"init {time.perf_counter() - t0:.1f} s "
            f"({len(compiles)} small compiles so far)")
        return s

    def solo_run_to(s, t):
        return sim.run_until_device(s, t, chunk=CHUNK)

    failures = []
    s = init()
    n_before = len(compiles)
    s, solo, bad = drive(solo_run_to, sim, s, "one device")
    failures += bad
    big = [c for c in compiles[n_before:] if c >= 1.0]
    programs = type(sim)._run_until_device._cache_size()
    say(f"tick programs compiled {programs} (backend compile "
        f"{sum(big):.1f} s in {len(big)} compile(s) >= 1 s)")
    if programs != 1:
        failures.append(f"{programs} tick programs compiled, expected 1")

    if args.chips == 4:
        from jax.sharding import NamedSharding
        from oversim_tpu.parallel import mesh as mesh_mod

        mesh = mesh_mod.make_mesh(4)
        run = mesh_mod.jit_run_until(sim, mesh, chunk=CHUNK)
        s4 = mesh_mod.shard_state(init(), mesh)
        # a [N, ...] leaf really is split: 4 shards of N/4 rows on 4
        # distinct devices
        shards = s4.alive.addressable_shards
        rows = sorted(sh.data.shape[0] for sh in shards)
        owners = {sh.device for sh in shards}
        say(f"mesh {dict(mesh.shape)}: alive[{n}] in {len(shards)} shards "
            f"of rows {rows} on {len(owners)} devices")
        if not (isinstance(s4.alive.sharding, NamedSharding)
                and rows == [n // 4] * 4 and len(owners) == 4):
            failures.append(f"alive[{n}] not split 4 x {n // 4}: rows "
                            f"{rows} on {len(owners)} devices")

        def mesh_run_to(st, t):
            return run(st, jax.numpy.int64(int(t * NS)))

        s4, quad, bad = drive(mesh_run_to, sim, s4, "four devices")
        failures += bad
        keys = ["_ticks", "_t_sim", "_alive", "kbr_sent", "kbr_delivered"]
        diff = [f"{k}: one {solo[k]} four {quad[k]}" for k in keys
                if solo[k] != quad[k]]
        # since PR 28 the mesh steps what the Simulation resolves
        # (mesh._gspmd_step: the awake-set plane for Kademlia, 171 ms a
        # tick against the dense sweep's 320 on four chips at N=16384,
        # PERF.md), so its own tallies agree with one device too
        diff += [f"_engine.{k}: one {v} four {quad['_engine'][k]}"
                 for k, v in solo["_engine"].items()
                 if v != quad["_engine"][k]]
        say("four devices vs one device: "
            + ("counters equal" if not diff else "; ".join(diff)))
        failures += diff

    peaks = {str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs[:args.chips]}
    say(f"peak_bytes_in_use {json.dumps(peaks)}")

    for f in failures:
        print("chip_smoke: FAILED: " + f, file=sys.stderr)
    if failures:
        return 1
    if args.rehearsal is not None:
        print(json.dumps({"ok": False, "rehearsal": "passed",
                          "device": device}), flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
