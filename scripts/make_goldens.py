"""Measure and pin parity goldens (VERDICT r1 next-step #6).

The reference's golden tests are event-hash fingerprints tied to the
OMNeT++ RNG streams (simulations/verify.ini) — unreproducible without
building OMNeT++ (not present in this image).  The rebuild's parity
bar is therefore distribution-level REGRESSION goldens: measured once
from a converged run at N=256, pinned with tight tolerances in
tests/test_parity.py, with the analytic expectation recorded alongside
as provenance (Chord iterative lookup ≈ 0.5·log2(N) finger hops + 1).

Usage: python scripts/make_goldens.py   # writes tests/goldens.json
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.modules["zstandard"] = None
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# XLA-CPU -O0: compiles AND runs faster at these tiny-N graph shapes
# (tests/conftest.py measurements)
# KEEP IN SYNC: the same -O0 bootstrap lives in tests/conftest.py, __graft_entry__.py and scripts/make_goldens.py
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_backend_optimization_level" not in _flags:
    _flags = (_flags + " --xla_backend_optimization_level=0"
              " --xla_llvm_disable_expensive_passes=true").strip()
# FMA capped off so golden floats match the suite's replay bit-for-bit
# regardless of graph structure (see tests/conftest.py)
if "xla_cpu_max_isa" not in _flags:
    _flags += " --xla_cpu_max_isa=AVX"
os.environ["XLA_FLAGS"] = _flags
import jax

from oversim_tpu import hostcache

jax.config.update("jax_platforms", "cpu")
hostcache.enable()

import math

from oversim_tpu import churn as churn_mod
from oversim_tpu.apps.kbrtest import KbrTestApp, KbrTestParams
from oversim_tpu.engine import sim as sim_mod


def measure(overlay: str, n: int, seed: int = 42):
    app = KbrTestApp(KbrTestParams(test_interval=20.0))
    if overlay == "chord":
        from oversim_tpu.overlay.chord import ChordLogic
        logic = ChordLogic(app=app)
    elif overlay == "pastry":
        from oversim_tpu.overlay.pastry import PastryLogic
        logic = PastryLogic(app=app)
    else:
        from oversim_tpu.overlay.kademlia import KademliaLogic
        logic = KademliaLogic(app=app)
    cp = churn_mod.ChurnParams(model="none", target_num=n,
                               init_interval=0.2)
    # window 0.2: hop-count and delivery distributions are window-
    # insensitive (tests/test_window.py).  End-to-end LATENCY is not:
    # each RPC leg's processing quantizes to a window boundary, adding
    # ~window/2 per leg (measured: chord_256 latency_mean 0.58 s at
    # window 0.05 vs 1.41 s at 0.2, hop_mean within 1.5%) — the pin
    # and its replay share this config, and the reference-fidelity
    # latency checks live in test_parity's window-0.020 fixture.  0.05
    # cost 4x the wall-clock (~25 min per N=256 golden, paid again on
    # every suite's parity replay).
    ep = sim_mod.EngineParams(window=0.2, transition_time=120.0)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=seed)
    st = s.run_until(st, 500.0, chunk=512)
    out = s.summary(st)
    return {
        "n": n,
        "seed": seed,
        "sent": int(out["kbr_sent"]),
        "delivery_ratio": round(
            float(out["kbr_delivered"]) / max(out["kbr_sent"], 1), 4),
        "hop_mean": round(float(out["kbr_hopcount"]["mean"]), 4),
        "hop_stddev": round(float(out["kbr_hopcount"]["stddev"]), 4),
        "hop_max": int(out["kbr_hopcount"]["max"]),
        # full per-hop-count histogram (VERDICT r4 next-step #5: pinned
        # DISTRIBUTIONS, not just mean bands — the closest reproducible
        # analogue of verify.ini's event-hash fingerprints)
        "hop_hist": [int(c) for c in out["kbr_hop_hist"]],
        "latency_mean_s": round(float(out["kbr_latency_s"]["mean"]), 4),
        # per-overlay analytic expectation: Chord iterative visits
        # ~0.5·log2 N fingers (+1 deliver); Kademlia's bucket walk is
        # the same order; Pastry resolves bitsPerDigit=4 bits per hop
        # (log16 N)
        "analytic_hop_mean": round(
            (math.log2(n) / 4 + 1) if overlay == "pastry"
            else (0.5 * math.log2(n) + 1), 4),
    }


def measure_verify(overlay: str, seed: int = 7):
    """The reference's fingerprint-regression scenario shape
    (simulations/verify.ini:1-14): 100 nodes, LifetimeChurn
    lifetimeMean=1000s, DHT+DHTTestApp+GlobalDhtTestMap, 100s
    transition + 100s measurement.  Deviation documented: the DHT test
    interval is 10s instead of 60s so the 100s measurement window holds
    ~1000 operations (the reference pins event hashes, which need no
    sample density; distribution goldens do)."""
    from oversim_tpu.apps.dht import DhtApp, DhtParams

    app = DhtApp(DhtParams(test_interval=10.0, num_test_keys=32,
                           test_ttl=600.0))
    if overlay == "chord":
        from oversim_tpu.overlay.chord import ChordLogic
        logic = ChordLogic(app=app)
    elif overlay == "pastry":
        from oversim_tpu.overlay.pastry import PastryLogic
        logic = PastryLogic(app=app)
    else:
        from oversim_tpu.overlay.kademlia import KademliaLogic
        logic = KademliaLogic(app=app)
    cp = churn_mod.ChurnParams(model="lifetime", target_num=100,
                               init_interval=0.1, lifetime_mean=1000.0)
    # window 0.2: same insensitivity argument as measure() above; the
    # DHT op timeout (10 s) dwarfs the window
    ep = sim_mod.EngineParams(window=0.2, transition_time=100.0,
                              measurement_time=100.0)
    s = sim_mod.Simulation(logic, cp, engine_params=ep)
    st = s.init(seed=seed)
    st = s.run_until(st, cp.init_finished_time + 200.0, chunk=512)
    out = s.summary(st)
    puts, gets = out["dht_put_attempts"], out["dht_get_attempts"]
    return {
        "seed": seed,
        "alive": int(out["_alive"]),
        "put_attempts": int(puts),
        "put_success_ratio": round(
            float(out["dht_put_success"]) / max(puts, 1), 4),
        "get_attempts": int(gets),
        "get_success_ratio": round(
            float(out["dht_get_success"]) / max(gets, 1), 4),
        "get_wrong": int(out["dht_get_wrong"]),
    }


def main():
    only = sys.argv[1] if len(sys.argv) > 1 else None
    path = Path(__file__).resolve().parent.parent / "tests" / "goldens.json"
    goldens = json.loads(path.read_text()) if path.exists() else {}
    for overlay, n in (("chord", 256), ("kademlia", 256),
                       ("pastry", 256)):
        name = f"{overlay}_{n}"
        if only and only not in (name, "kbr"):
            continue
        print(f"measuring {name} ...", flush=True)
        goldens[name] = measure(overlay, n)
        print(json.dumps(goldens[name]), flush=True)
        path.write_text(json.dumps(goldens, indent=1) + "\n")
    for overlay in ("chord", "kademlia", "pastry"):
        name = f"verify_{overlay}"
        if only and only not in (name, "verify"):
            continue
        print(f"measuring {name} (verify.ini shape) ...", flush=True)
        goldens[name] = measure_verify(overlay)
        print(json.dumps(goldens[name]), flush=True)
        path.write_text(json.dumps(goldens, indent=1) + "\n")
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
