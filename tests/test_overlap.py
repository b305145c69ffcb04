"""Tests: exchange/compute overlap in the stencil iteration (ROADMAP:
steps-deep pipelining — the wire now hides behind the interior chain of
ALL fused applications, not just the first one)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.comm import Communicator
from repro.halo import (
    HaloSpec,
    STENCIL26,
    StencilOp,
    halo_exchange,
    make_halo_types,
    max_pipeline_depth,
    overlapped_stencil_iteration,
    stencil26,
    stencil26_interior,
    stencil_interior_chain,
    stencil_steps,
)


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("ranks",))


def test_interior_update_is_halo_independent():
    """The overlap's legality: the deep-interior update must not read
    halo cells, so poisoning every halo cell cannot change it."""
    spec = HaloSpec(grid=(1, 1, 1), interior=(6, 5, 4), radius=2)
    r = spec.radius
    az, ay, ax = spec.alloc
    rng = np.random.default_rng(0)
    full = rng.normal(size=(az, ay, ax)).astype(np.float32)
    poisoned = np.full_like(full, 1e6)
    nz, ny, nx = spec.interior
    poisoned[r:r + nz, r:r + ny, r:r + nx] = full[r:r + nz, r:r + ny, r:r + nx]

    inner_poisoned = np.asarray(stencil26_interior(jnp.asarray(poisoned), spec))
    stepped_full = np.asarray(stencil26(jnp.asarray(full), spec))
    np.testing.assert_array_equal(
        inner_poisoned,
        stepped_full[r + 1:r + 1 + nz - 2, r + 1:r + 1 + ny - 2,
                     r + 1:r + 1 + nx - 2],
    )


def test_interior_chain_is_halo_independent_steps_deep():
    """Steps-deep pipelining legality: EVERY chain block must be
    poison-proof, and block k must equal the corresponding region of k
    full shrinking-region applications."""
    op = StencilOp((2, 1, 1))
    spec = HaloSpec(grid=(1, 1, 1), interior=(12, 8, 8),
                    radius=op.halo_radii(2))
    rz, ry, rx = spec.radii
    nz, ny, nx = spec.interior
    az, ay, ax = spec.alloc
    rng = np.random.default_rng(1)
    full = rng.normal(size=(az, ay, ax)).astype(np.float32)
    poisoned = np.full_like(full, 1e6)
    poisoned[rz:rz + nz, ry:ry + ny, rx:rx + nx] = \
        full[rz:rz + nz, ry:ry + ny, rx:rx + nx]

    depth = max_pipeline_depth(spec, op, 2)
    assert depth == 2
    chain = stencil_interior_chain(jnp.asarray(poisoned), spec, depth, op)

    stepped = jnp.asarray(full)
    valid = spec.radii
    for k in range(1, depth + 1):
        from repro.halo import stencil_apply

        stepped = stencil_apply(stepped, spec, valid, op)
        valid = tuple(v - r for v, r in zip(valid, op.radii))
        oz, oy, ox = (hr + k * r for hr, r in zip(spec.radii, op.radii))
        sz, sy, sx = chain[k - 1].shape
        np.testing.assert_array_equal(
            np.asarray(chain[k - 1]),
            np.asarray(stepped)[oz:oz + sz, oy:oy + sy, ox:ox + sx],
            err_msg=f"chain block {k}",
        )


def test_overlapped_iteration_matches_plain_single_rank():
    spec = HaloSpec(grid=(1, 1, 1), interior=(6, 5, 4), radius=2)
    az, ay, ax = spec.alloc
    comm = Communicator(axis_name="ranks")
    types = make_halo_types(spec, comm)
    probe = {}

    def plain(local):
        local = halo_exchange(local, spec, comm, "ranks", types)
        return stencil_steps(local, spec, steps=2)

    def overlapped(local):
        return overlapped_stencil_iteration(
            local, spec, comm, "ranks", types, steps=2, probe=probe
        )

    mesh = _mesh1()
    jp = jax.jit(shard_map(plain, mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False))
    jo = jax.jit(shard_map(overlapped, mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(az, ay, ax)).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(jp(x)), np.asarray(jo(x)))

    # the overlap invariant: the wire was issued but NOT waited on when
    # the interior compute was built
    assert probe["pending_during_interior"] is True
    # interior (6,5,4): the x dim (4 - 2*2 = 0) caps the chain at depth 1
    assert probe["pipeline_depth"] == 1

    # single-rank periodic grid: all 26 transfers share one delta class,
    # so the fused exact-byte schedule issues exactly one collective
    from repro.comm import collective_payload_bytes

    counts = collective_payload_bytes(jo, x)
    assert counts["ops"] == 1, counts


def test_overlapped_iteration_steps_deep_pipeline():
    """A roomier interior pipelines BOTH fused applications; result stays
    bit-identical to the plain path."""
    spec = HaloSpec(grid=(1, 1, 1), interior=(8, 7, 6), radius=2)
    az, ay, ax = spec.alloc
    comm = Communicator(axis_name="ranks")
    types = make_halo_types(spec, comm)
    probe = {}

    def plain(local):
        local = halo_exchange(local, spec, comm, "ranks", types)
        return stencil_steps(local, spec, steps=2)

    def overlapped(local):
        return overlapped_stencil_iteration(
            local, spec, comm, "ranks", types, steps=2, probe=probe
        )

    mesh = _mesh1()
    jp = jax.jit(shard_map(plain, mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False))
    jo = jax.jit(shard_map(overlapped, mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False))
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(az, ay, ax)).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(jp(x)), np.asarray(jo(x)))
    assert probe["pending_during_interior"] is True
    assert probe["pipeline_depth"] == 2  # both applications precomputed


OVERLAP_8RANK_CODE = r"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.comm import Communicator
from repro.halo import (HaloSpec, halo_exchange, make_halo_types,
                        overlapped_stencil_iteration, stencil_steps)

spec = HaloSpec(grid=(2, 2, 2), interior=(6, 5, 4), radius=2)
R = spec.nranks
az, ay, ax = spec.alloc
assert len(jax.devices()) == R

comm = Communicator(axis_name="ranks")
mesh = Mesh(np.array(jax.devices()), ("ranks",))
types = make_halo_types(spec, comm)
probe = {}

# byte-exact ladder: the 7-wire-op / ragged-bytes assertions below gate
# the exact schedule (the model-priced default may buy uniform padding)
from repro.halo import make_halo_plan
plan = make_halo_plan(spec, comm, types, schedule_policy="exact")

def plain(local):
    local = halo_exchange(local, spec, comm, "ranks", types, plan=plan)
    return stencil_steps(local, spec, steps=2)

def overlapped(local):
    return overlapped_stencil_iteration(
        local, spec, comm, "ranks", types, steps=2, probe=probe, plan=plan)

jp = jax.jit(shard_map(plain, mesh=mesh, in_specs=P("ranks"),
                       out_specs=P("ranks"), check_vma=False))
jo = jax.jit(shard_map(overlapped, mesh=mesh, in_specs=P("ranks"),
                       out_specs=P("ranks"), check_vma=False))

rng = np.random.default_rng(7)
x = jnp.asarray(rng.normal(size=(R * az, ay, ax)).astype(np.float32))
np.testing.assert_array_equal(np.asarray(jp(x)), np.asarray(jo(x)))
assert probe["pending_during_interior"] is True
assert probe["pipeline_depth"] == 1
# 2x2x2 grid: 7 delta classes -> 7 exact-payload wire ops, ragged bytes
from repro.comm import collective_payload_bytes
counts = collective_payload_bytes(jo, x)
assert counts["ops"] == plan.wire.wire_ops == 7, counts
assert counts["total"] == plan.wire_bytes, counts
print("OVERLAP_OK")
"""


@pytest.mark.slow
def test_overlapped_iteration_matches_plain_8_ranks():
    from tests._subproc import run_with_devices

    out = run_with_devices(OVERLAP_8RANK_CODE, ndev=8)
    assert "OVERLAP_OK" in out
