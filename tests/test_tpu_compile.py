"""Compiles for a described TPU v5e — no chip needed, no chip time spent.

The TPU's compiler is installed with JAX and compiles for a chip that is
described and not attached.  It refuses what interpret mode accepts:
blocks off the (8, 128) tiling, DMA windows narrower than a tile, more
fast memory than a kernel may use.  These tests compile the pack and
unpack kernels (``interpret=False``) at the widths ``chip_smoke.py``
runs, and the native ragged exchange on a described 2x2 mesh.

The topology is described in a module fixture, never at import: only
one process may hold the TPU library, and pytest-xdist workers all
import this file.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from chip_smoke import CYCLE, HALO_SIDE, pack_objects
from repro.comm import Communicator
from repro.comm.wireplan import has_ragged_all_to_all, plan_wire
from repro.halo import HaloSpec, halo_exchange, make_halo_plan
from repro.halo.exchange import make_halo_types
from repro.halo.stencil import cycle_halo_radii
from repro.launch.smoother import smoother_cycle


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _objects():
    """chip_smoke's phase-1 objects, its 3D halo face included."""
    comm = Communicator()
    n = HALO_SIDE
    spec = HaloSpec(
        grid=(1, 1, 1), interior=(n, n, n),
        radius=cycle_halo_radii(smoother_cycle(CYCLE), 1),
    )
    face = make_halo_types(spec, comm)[(0, 0, 1)][0].datatype
    objs = pack_objects(("halo x-face 3D", face, spec.alloc, np.float32))
    return comm, {label: (dt, shape, dtype) for label, dt, shape, dtype in objs}


COMM, OBJECTS = _objects()


@pytest.mark.parametrize("strategy", ["rows", "dma"])
@pytest.mark.parametrize("label", list(OBJECTS))
def test_pack_unpack_kernels_compile(one_chip, label, strategy):
    dt, shape, dtype = OBJECTS[label]
    ct = COMM.commit(dt)
    strat = COMM.strategies.get(strategy)
    if not strat.applicable(ct):
        # contiguous objects need no kernel; the DMA kernel needs
        # 8-aligned row chunks, which the 3D face's planes do not have
        assert label in ("vector 512B x2048", "halo x-face 3D"), label
        return
    buf = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    packed = jax.ShapeDtypeStruct((ct.size,), jnp.uint8, sharding=one_chip)
    pack = jax.jit(lambda b: strat.pack(b, ct, interpret=False))
    unpack = jax.jit(lambda b, p: strat.unpack(b, p, ct, interpret=False))
    for compiled in (
        pack.lower(buf).compile(), unpack.lower(buf, packed).compile()
    ):
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("label, strategy, kernels", [
    ("subarray 32B x32768", "rows", ("tempi_pack_rows", "tempi_unpack_rows")),
    ("subarray 32B x32768", "dma", ("tempi_pack_dma", "tempi_unpack_dma")),
    ("halo x-face 3D", "rows", ("tempi_pack_planes", "tempi_unpack_planes")),
])
def test_compiled_kernels_carry_their_names_and_scopes(one_chip, label,
                                                       strategy, kernels):
    # a trace names each Pallas kernel's custom call by its own name, and
    # its metadata credits it to tempi.pack / tempi.unpack; the word view
    # of the float buffer is a tempi.bytes relayout
    import re

    dt, shape, dtype = OBJECTS[label]
    ct = COMM.commit(dt)
    strat = COMM.strategies.get(strategy)
    buf = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    packed = jax.ShapeDtypeStruct((ct.size,), jnp.uint8, sharding=one_chip)
    pack = jax.jit(lambda b: strat.pack(b, ct, interpret=False))
    unpack = jax.jit(lambda b, p: strat.unpack(b, p, ct, interpret=False))
    for compiled, kernel, phase in (
        (pack.lower(buf).compile(), kernels[0], "tempi.pack"),
        (unpack.lower(buf, packed).compile(), kernels[1], "tempi.unpack"),
    ):
        calls = [line for line in compiled.as_text().splitlines()
                 if "tpu_custom_call" in line and " = " in line]
        assert calls
        for line in calls:
            name = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line).group(1)
            assert name.startswith(kernel)
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            assert re.findall(r"tempi\.[a-z]+", op_name)[-1] == phase
        assert "tempi.bytes" in compiled.as_text()


@pytest.mark.parametrize("strategy", ["production", "dma"])
def test_pack_cell_programs_never_view_the_buffer_as_words(one_chip, tmp_path,
                                                           strategy):
    # the pack cell's two programs: Vector(131072, 8, 512, BYTE) out of
    # and into a flat 64 MiB uint8 buffer.  Viewing that buffer as 32-bit
    # words relayouts all of it through a [16777216, 4] shape, ~18 GB of
    # traffic a program; the byte-wide kernels read the pitch rows once
    from repro.comm.perfmodel import TPU_V5E
    from repro.core import BYTE, Vector
    from repro.measure.production import production_communicator

    comm, _ = production_communicator(tmp_path, calibrate=False,
                                      params=TPU_V5E)
    ct = comm.commit(Vector(131072, 8, 512, BYTE))
    strat = (comm.select(ct, 1, wire=False) if strategy == "production"
             else comm.strategies.get(strategy))
    n = 131072 * 512
    buf = jax.ShapeDtypeStruct((n,), jnp.uint8, sharding=one_chip)
    packed = jax.ShapeDtypeStruct((ct.size,), jnp.uint8, sharding=one_chip)
    pack = jax.jit(lambda b: strat.pack(b, ct, interpret=False))
    unpack = jax.jit(lambda b, p: strat.unpack(b, p, ct, interpret=False),
                     donate_argnums=0)
    for compiled in (
        pack.lower(buf).compile(), unpack.lower(buf, packed).compile()
    ):
        text = compiled.as_text()
        assert "tpu_custom_call" in text
        assert f"[{n // 4},4]" not in text
        assert compiled.cost_analysis()["bytes accessed"] < 1e9


def test_native_ragged_exchange_compiles_on_2x2(topo):
    assert has_ragged_all_to_all(topo.devices)
    spec = HaloSpec(grid=(4, 1, 1), interior=(16, 16, 16), radius=1)
    comm = Communicator(axis_name="ranks")
    plan = make_halo_plan(spec, comm, schedule_policy="exact")
    wire = plan_wire(
        tuple(s.nbytes for s in plan.wire.segments), plan.perms,
        fingerprints=tuple(s.fingerprint for s in plan.wire.segments),
        native=True,
    )
    assert wire.schedule == "ragged"
    plan = dataclasses.replace(plan, wire=wire)
    mesh = Mesh(np.array(topo.devices), ("ranks",))
    step = jax.jit(shard_map(
        lambda x: halo_exchange(x, spec, comm, "ranks", plan=plan),
        mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks"),
        check_vma=False,
    ))
    az, ay, ax = spec.alloc
    x = jax.ShapeDtypeStruct(
        (4 * az, ay, ax), jnp.float32,
        sharding=NamedSharding(mesh, P("ranks")),
    )
    assert "ragged-all-to-all" in step.lower(x).compile().as_text()
