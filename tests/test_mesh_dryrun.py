"""The driver's multichip dryrun, at CI size, on the 8-device virtual
CPU mesh (see test_mesh.py).

``__graft_entry__.dryrun_multichip`` runs two tiers, each ONE
``mesh.jit_run_until`` program (the run loop of the four-chip benchmark
cell) called in four legs to rising horizons, a progress line a leg:
Chord + KBRTestApp to the end of its fill and 30 s more, then Kademlia
under LifetimeChurn with the KBRTestApp/DHT tier stack to 40 s past its
fill.  It asserts inside: every leg shares its tier's one compiled
program, every node alive, traffic sent, delivery, no overflow."""


def test_rich_dryrun_scenario():
    """Mirror of the driver's dryrun_multichip (VERDICT r3 item #6):
    Kademlia + LifetimeChurn + KBR/DHT tier stack sharded over the
    8-device mesh — churn recycling, lookups, puts and gets crossing
    shard boundaries, counters asserted inside the run.  Smaller per-
    device node count than the driver run keeps CI time bounded."""
    import importlib.util
    import os
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "graft_entry", Path(__file__).resolve().parent.parent
        / "__graft_entry__.py")
    mod = importlib.util.module_from_spec(spec)
    # both tiers at CI size: 8-way sharded XLA-CPU ticks are a fifth of
    # a second each under the suite's load, and the driver's 8x32 and
    # 8x128 take several times the ticks' work
    sizes = {"OVERSIM_DRYRUN_NODES_PER_DEV": "8",
             "OVERSIM_DRYRUN_T1_NODES_PER_DEV": "4"}
    os.environ.update(sizes)
    try:
        spec.loader.exec_module(mod)
        mod.dryrun_multichip(8)   # asserts delivery + overflow inside
    finally:
        for k in sizes:
            os.environ.pop(k, None)
