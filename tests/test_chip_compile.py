"""Ask the TPU's compiler, without a TPU (on-chip-measurement guide §2.3).

The ONE test file that describes a chip.  libtpu is installed in the
sandbox and compiles for a chip that is described and not attached;
nothing runs.  What a CPU run cannot show — the compiler refusing an
op, a program that does not fit — shows here, at no chip time.

The topology is described inside a module-scoped fixture, never at
import, never in a ``skipif``/``parametrize`` argument, not in
conftest.py and not ``autouse``: only one process may hold libtpu, and
under pytest-xdist every worker imports every test file.  No child
process is started for the same reason.

The case: the whole Kademlia tick at N=128 through the normal entry
points (``build_simulation`` -> ``_run_until_device``).  The program
holds no hand-written kernel: its compiled text has no
``tpu_custom_call``.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure = cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_kademlia_tick_compiles_for_v5e(one_chip):
    """The chip_smoke program (ini -> build_simulation ->
    _run_until_device) at N=128: compiles for the described v5e, with
    the donated state aliased and no custom kernel call in it."""
    import chip_smoke

    sim = chip_smoke.build_sim(128)
    # Simulation.init cannot be shape-evaluated (_dedupe_buffers reads
    # buffer pointers); init_from_rng can
    shapes = jax.eval_shape(
        lambda: sim.init_from_rng(jax.random.PRNGKey(1)))
    state = jax.tree.map(
        lambda l: _shape(l.shape, l.dtype, one_chip), shapes)
    compiled = type(sim)._run_until_device.lower(
        sim, state, _shape((), jnp.int64, one_chip),
        chunk=chip_smoke.CHUNK).compile()
    ma = compiled.memory_analysis()
    assert ma.generated_code_size_in_bytes > 0
    # the state is donated: (nearly) every argument byte is aliased
    assert ma.alias_size_in_bytes > 0.9 * ma.argument_size_in_bytes
    assert "tpu_custom_call" not in compiled.as_text()
