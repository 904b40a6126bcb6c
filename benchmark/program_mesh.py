"""The system under test, driven across the chips of one host.

The program file of a deployment whose node rows and message pool are
split over a mesh (named under ``"program"`` in its configuration;
``program.py`` drives one chip and refuses more).  Beside ``program.py``
it is the only file of the benchmark that imports ``oversim_tpu``, and
it edits nothing there: the deployment is built as in ``program.py``
(``IniFile`` -> ``build_simulation`` -> ``sim.init(seed)``), then placed
and run through the multi-chip entry a user has,

    mesh.shard_state(s, mesh.make_mesh(chips))
    mesh.jit_run_until(sim, mesh, chunk=ticks_per_dispatch)

whose one compiled program serves set-up's long call and the window's
one-dispatch calls (the target is a traced scalar in whole ns).  Which
plane of the tick that entry partitions is the program's own choice
(``mesh._gspmd_step``); nothing here asks for one.

It reads the same leaves by the same names as ``program.py``
(``SURFACE``, ``leaf``, and through the inherited readers
``pool_columns``), and adds to ``tables`` how
each of them is held: number of shards, rows of each, the devices that
hold them.  The plain reference counts the node-row and pool leaves that
are not in ``chips`` equal blocks on ``chips`` devices.

The configuration's ``"placement"`` is ``"node_sharded"``.  Any other
value is the builder's control (``run.py --set placement='"replicated"'``):
the same entry on a mesh whose node axis has ONE shard, so every device
holds every row, which has to come out as not correct.
"""

from __future__ import annotations

import numpy as np

import program as one_chip
from program import SURFACE, SurfaceError, leaf

# every leaf of the lookups' state is a node-row leaf: all of them
LOOKUP_STATE = "logic.lk"


def held(x) -> dict:
    """How one array is held: its shards' row ranges and devices."""
    shards = sorted(x.addressable_shards, key=lambda sh: sh.device.id)
    rows = [sh.index[0].indices(x.shape[0]) for sh in shards]
    return {"rows_total": int(x.shape[0]), "shards": len(shards),
            "rows": [stop - start for start, stop, _ in rows],
            "starts": [start for start, _, _ in rows],
            "devices": [int(sh.device.id) for sh in shards]}


class Program(one_chip.Program):
    """One deployment on ``chips`` chips of one host."""

    def __init__(self, config: dict, traffic: dict, chips: int,
                 n: int | None = None, persistent_cache: bool = True):
        if chips < 2:
            raise ValueError(
                "this program file drives a mesh of several chips; a "
                "deployment on one chip takes program.py")
        # the deployment is built exactly as on one chip (the compile
        # cache, the ini, the engine sizes, the compile listener); only
        # the placement and the run loop below differ
        super().__init__(config, traffic, 1, n=n,
                         persistent_cache=persistent_cache)
        from oversim_tpu.parallel import mesh as mesh_mod
        self._mesh_mod = mesh_mod
        self.chips = chips
        self.placement = config["placement"]
        self.mesh = self._run = None

    # -- the mesh -------------------------------------------------------------

    def _open_mesh(self):
        """The mesh and the ONE runner, once the devices are known."""
        mesh_mod = self._mesh_mod
        devs = self.jax.devices()
        if len(devs) < self.chips:
            raise ValueError(
                f"the deployment is split over {self.chips} devices and "
                f"jax.devices() has {len(devs)}")
        if self.placement == "node_sharded":
            self.mesh = mesh_mod.make_mesh(self.chips)
        else:
            # the control: a node axis of one shard, copied on every chip
            from jax.sharding import Mesh
            self.mesh = Mesh(np.array(devs[:self.chips]).reshape(1, -1),
                             (mesh_mod.NODE_AXIS, "copies"))
        try:
            self._run = mesh_mod.jit_run_until(self.sim, self.mesh,
                                               chunk=self.chunk)
            self._run._cache_size
        except AttributeError:
            raise SurfaceError(
                "benchmark/program_mesh.py drives mesh.jit_run_until and "
                "counts its programs by _cache_size(); one of the two is "
                "gone") from None

    # -- the run --------------------------------------------------------------

    def init(self, seed: int):
        """``mesh.shard_state(sim.init(seed), mesh)``."""
        if self._run is None:
            self._open_mesh()
        s = self._mesh_mod.shard_state(self.sim.init(int(seed)), self.mesh)
        return self.jax.block_until_ready(s)

    def run_to(self, s, target_ns: int):
        """Whole dispatches of ``ticks_per_dispatch`` ticks of the one
        program until ``t_now >= target_ns``; ends in
        ``block_until_ready``."""
        target = np.int64(int(target_ns))    # no device op of its own
        return self.jax.block_until_ready(self._run(s, target))

    def tick_programs(self) -> int:
        return int(self._run._cache_size())

    # -- what the comparison reads ---------------------------------------------

    def tables(self, s) -> dict:
        """``program.py``'s tables, and how each leaf read is held."""
        out = super().tables(s)
        paths = [p for p in SURFACE if getattr(leaf(s, p), "ndim", 0) >= 1]
        lookups = leaf(s, LOOKUP_STATE)
        paths += [f"{LOOKUP_STATE}.{name}" for name in vars(lookups)
                  if f"{LOOKUP_STATE}.{name}" not in paths
                  and getattr(getattr(lookups, name), "ndim", 0) >= 1]
        out["layout"] = {p: held(leaf(s, p)) for p in paths}
        return out
