"""Integration tests: 26-neighbor halo exchange on an 8-device mesh.

Runs in a subprocess with --xla_force_host_platform_device_count=8.
Correctness oracle: assemble the global periodic array in numpy and check
every halo cell of every rank equals the wrapped global neighbor value —
for both interposer modes (baseline per-block copies and tempi kernels),
which must agree bit-exactly.
"""

import pytest

from tests._subproc import run_with_devices

HALO_CODE = r"""
import itertools
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.comm import Interposer
from repro.halo import HaloSpec, make_halo_step

grid = (2, 2, 2)
spec = HaloSpec(grid=grid, interior=(6, 5, 4), radius=2)
r = spec.radius
nz, ny, nx = spec.interior
az, ay, ax = spec.alloc
R = spec.nranks
assert len(jax.devices()) == R

# global periodic field with unique values
gz, gy, gx = grid[0] * nz, grid[1] * ny, grid[2] * nx
gvals = np.arange(gz * gy * gx, dtype=np.float32).reshape(gz, gy, gx)

# build each rank's local block (interior filled, halos poisoned)
locals_np = np.full((R, az, ay, ax), -1.0, np.float32)
for rank in range(R):
    cz, cy, cx = spec.coords(rank)
    locals_np[rank, r:r+nz, r:r+ny, r:r+nx] = gvals[
        cz*nz:(cz+1)*nz, cy*ny:(cy+1)*ny, cx*nx:(cx+1)*nx
    ]

mesh = Mesh(np.array(jax.devices()), ("ranks",))
results = {}
for mode in ("baseline", "tempi"):
    ip = Interposer(mode=mode)
    step = make_halo_step(spec, ip, mesh)
    x0 = jnp.asarray(locals_np.reshape(R * az, ay, ax))
    out = np.asarray(step(x0))
    results[mode] = out.reshape(R, az, ay, ax)

np.testing.assert_array_equal(results["baseline"], results["tempi"])

# the whole 26-region exchange must ride the fused exact-byte wire
# schedule: one wire op per displacement class (7 on a 2x2x2 grid),
# moving exactly the sum of per-peer packed extents — no class padding.
# Forced pack strategy makes the expected byte count Σ ct.size exactly.
from repro.comm import Communicator, FixedPolicy, collective_payload_bytes
from repro.halo import make_halo_plan
comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"))
plan = make_halo_plan(spec, comm, schedule_policy="exact")
step = make_halo_step(spec, comm, mesh, schedule_policy="exact")
counts = collective_payload_bytes(step, x0)
assert plan.wire.ngroups == 7
assert counts["ops"] == plan.wire.wire_ops == 7, counts
assert counts["total"] == plan.wire_bytes == plan.wire.issued_bytes, counts
assert plan.wire_bytes == sum(ct.packed_extent() for ct in plan.send_cts)
print("FUSED_OK")

# oracle: every cell (including halos) must equal the periodic global value
out = results["tempi"]
for rank in range(R):
    cz, cy, cx = spec.coords(rank)
    zz = (np.arange(az) - r + cz * nz) % gz
    yy = (np.arange(ay) - r + cy * ny) % gy
    xx = (np.arange(ax) - r + cx * nx) % gx
    want = gvals[np.ix_(zz, yy, xx)]
    np.testing.assert_array_equal(out[rank], want, err_msg=f"rank {rank}")
print("HALO_OK")
"""


STENCIL_CODE = r"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.comm import Communicator
from jax import shard_map
from repro.halo import HaloSpec, halo_exchange, make_halo_types, stencil_iterations

grid = (2, 2, 2)
spec = HaloSpec(grid=grid, interior=(4, 4, 4), radius=2)
r = spec.radius
R = spec.nranks
az, ay, ax = spec.alloc
nz, ny, nx = spec.interior

rng = np.random.default_rng(7)
gz, gy, gx = grid[0]*nz, grid[1]*ny, grid[2]*nx
gvals = rng.normal(size=(gz, gy, gx)).astype(np.float32)

locals_np = np.zeros((R, az, ay, ax), np.float32)
for rank in range(R):
    cz, cy, cx = spec.coords(rank)
    locals_np[rank, r:r+nz, r:r+ny, r:r+nx] = gvals[
        cz*nz:(cz+1)*nz, cy*ny:(cy+1)*ny, cx*nx:(cx+1)*nx]

comm = Communicator(axis_name="ranks")
mesh = Mesh(np.array(jax.devices()), ("ranks",))
types = make_halo_types(spec, comm)

def iteration(local):
    local = halo_exchange(local, spec, comm, "ranks", types)
    return stencil_iterations(local, spec, steps=2)

step = jax.jit(shard_map(iteration, mesh=mesh, in_specs=P("ranks"),
                         out_specs=P("ranks"), check_vma=False))
out = np.asarray(step(jnp.asarray(locals_np.reshape(R*az, ay, ax)))).reshape(R, az, ay, ax)

# single-"rank" numpy oracle on the periodic global array
def stencil_np(g):
    acc = np.zeros_like(g)
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                if (dz, dy, dx) == (0, 0, 0):
                    continue
                acc += np.roll(g, (-dz, -dy, -dx), axis=(0, 1, 2))
    return (1 - 0.4) * g + (0.4 / 26.0) * acc

want = stencil_np(stencil_np(gvals))
for rank in range(R):
    cz, cy, cx = spec.coords(rank)
    got = out[rank, r:r+nz, r:r+ny, r:r+nx]
    np.testing.assert_allclose(
        got, want[cz*nz:(cz+1)*nz, cy*ny:(cy+1)*ny, cx*nx:(cx+1)*nx],
        rtol=2e-6, atol=2e-6, err_msg=f"rank {rank}")
print("STENCIL_OK")
"""


@pytest.mark.slow
def test_halo_exchange_8_ranks():
    out = run_with_devices(HALO_CODE, ndev=8)
    assert "FUSED_OK" in out
    assert "HALO_OK" in out


@pytest.mark.slow
def test_stencil_matches_global_oracle():
    out = run_with_devices(STENCIL_CODE, ndev=8)
    assert "STENCIL_OK" in out
