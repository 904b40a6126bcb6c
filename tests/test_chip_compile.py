"""Ask the TPU's compiler, without a TPU (on-chip-measurement guide §2.3).

The ONE test file that describes a chip.  libtpu is installed in the
sandbox and compiles for a chip that is described and not attached;
nothing runs.  What interpret mode cannot show — Mosaic refusing a
kernel, a program that does not fit — shows here, at no chip time.

The topology is described inside a module-scoped fixture, never at
import, never in a ``skipif``/``parametrize`` argument, not in
conftest.py and not ``autouse``: only one process may hold libtpu, and
under pytest-xdist every worker imports every test file.  No child
process is started for the same reason.

Cases: the whole Kademlia tick at N=128 through the normal entry points
(``build_simulation`` -> ``_run_until_device``), and the four Pallas
kernel calls with ``interpret=False`` at the chip_smoke deployment's
sizes (N=4096, P=32768, W=31, R=8).  A kernel that compiles asserts so
and that its text holds a ``tpu_custom_call``; a kernel the compiler
refuses is a STRICT xfail whose reason is the compiler's own message —
when a later PR makes it compile, the xfail fails and must be removed.
As of PR 22 all four are refused: the i64 refusal ("64-bit types are
not supported", from x64 loop indices and integer sums) is repaired,
and the next one is structural — every kernel stores single elements
(``delivered_ref[i] = 1``, ``fslot_ref[k] = i``, ``out_ref[c] = v``)
into VMEM arrays, which Mosaic does not do; that needs a re-tiling
(vector stores, or SMEM for the scalar-indexed arrays) and is left to
the PR that decides whether the kernel plane stays (ROADMAP Design 1).
``kernels.interpret_default()`` decides by ``jax.default_backend()``,
which is the CPU here, so every case passes ``interpret=False`` itself.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N, P, W, R = 4096, 32768, 31, 8
MOUT = 16                    # EngineParams.outbox_slots
ACAP = N // 32               # Simulation.acap at N=4096
I32 = jnp.int32


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
    the donated state aliased and no Pallas kernel on the default
    path."""
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


def _inbox_args(sh):
    vec = _shape((P,), I32, sh)
    return vec, vec, vec, vec, _shape((P, W), I32, sh)


KERNEL_CASES = ["inbox-gather", "inbox-select", "outbox-dest",
                "outbox-compact"]
# the compiler's own words, per kernel (jax 0.9.0 / libtpu 0.0.34,
# jax/_src/pallas/mosaic/lowering.py _masked_swap_lowering_rule)
REFUSED = {case: "Mosaic refuses: ValueError: Cannot store scalars to VMEM"
           for case in KERNEL_CASES}


def _lower_kernel(case, sh):
    from oversim_tpu.kernels import inbox, outbox
    vec = _shape((P,), I32, sh)
    if case == "inbox-gather":
        return inbox._fused_call.lower(*_inbox_args(sh), n=N, r=R,
                                       interpret=False, gather=True)
    if case == "inbox-select":
        return inbox._fused_call.lower(*_inbox_args(sh), n=N, r=R,
                                       interpret=False, gather=False)
    if case == "outbox-dest":
        return outbox._dest_call.lower(vec, _shape((N * MOUT,), I32, sh),
                                       interpret=False)
    if case == "outbox-compact":
        nvec = _shape((N,), I32, sh)
        return outbox._compact_call.lower(nvec, nvec, cap=ACAP, sentinel=N,
                                          interpret=False)
    raise AssertionError(case)


@pytest.mark.parametrize("case", [
    pytest.param(c, marks=pytest.mark.xfail(
        strict=True, raises=ValueError, reason=REFUSED[c]))
    if c in REFUSED else c for c in KERNEL_CASES])
def test_kernel_compiles_for_v5e(one_chip, case):
    compiled = _lower_kernel(case, one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
