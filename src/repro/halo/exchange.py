"""3D stencil halo exchange on datatype-described halo regions
(paper §6.4 case study).

Each rank owns an interior block of ``(nz, ny, nx)`` gridpoints inside a
local allocation ``(nz+2r, ny+2r, nx+2r)`` (halo shells of radius ``r``).
The 26 neighbor regions (6 faces, 12 edges, 8 corners, periodic domain)
are each described by an MPI-style ``Subarray`` datatype — "a variety of
different 3D strided datatypes" — committed once and exchanged every
iteration through a :class:`~repro.comm.api.Communicator`.

The paper transports the packed buffers with one ``MPI_Alltoallv``; this
is :meth:`Communicator.neighbor_alltoallv`: all 26 regions are packed at
their **exact** wire extents into one flat buffer laid out by a
:class:`~repro.comm.wireplan.WirePlan`, and the plan's wire schedule
moves exactly those bytes — on a periodic process grid the 26 directions
collapse into the distinct displacement classes mod the grid (7 on a
2x2x2 grid), each class a single exact-payload wire op (or one native
ragged collective where the running JAX provides it).  The whole layout
— committed types, strategy selection, wire plan — is built ONCE at
:func:`make_halo_step` time (:class:`HaloPlan`); every iteration after
that is dictionary lookups.

Halos may be asymmetric: ``HaloSpec.radius`` accepts a per-dimension
``(rz, ry, rx)`` tuple, and the region datatypes, allocations, and wire
layout all follow the per-dimension radii (the ragged wire layout is
what makes this free — unequal region sizes never padded each other).

On a two-level machine (a communicator constructed with a
:class:`repro.comm.topology.Topology`), the same planning pass annotates
each delta class with the link tier it crosses: classes that stay on one
node price at the fast tier, node-crossing classes at the slow tier, and
the model may pick the ``tiered`` schedule — every class bound for the
same peer node coalesced into ONE slow-tier collective, corrected to its
true destination rank by cheap intra-node hops.  Nothing here changes:
the topology rides ``Communicator.plan_neighbor`` into the wire plan.

Switching the communicator policy between baseline and model selection
reproduces the paper's comparison with zero changes here.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.comm.api import (
    Communicator,
    Request,
    Strategy,
    WirePlan,
    as_communicator,
)
from repro.core.commit import CommittedType
from repro.core.datatypes import FLOAT, Named, Subarray

__all__ = [
    "HaloSpec",
    "HaloPlan",
    "DIRECTIONS",
    "halo_exchange",
    "ihalo_exchange",
    "make_halo_types",
    "make_halo_plan",
    "make_halo_step",
]

#: the 26 neighbor directions (dz, dy, dx)
DIRECTIONS: Tuple[Tuple[int, int, int], ...] = tuple(
    d for d in itertools.product((-1, 0, 1), repeat=3) if d != (0, 0, 0)
)


@dataclass(frozen=True)
class HaloSpec:
    """Geometry of one rank's local block.

    ``radius`` is either one scalar (the paper's symmetric radius-2
    setup) or a per-dimension ``(rz, ry, rx)`` tuple for asymmetric
    halos (e.g. a deeper halo on the slow axis only).
    """

    grid: Tuple[int, int, int]     # process grid (pz, py, px)
    interior: Tuple[int, int, int]  # (nz, ny, nx) gridpoints per rank
    radius: Union[int, Tuple[int, int, int]] = 2  # paper: stencil radius 2
    element: Named = FLOAT          # paper: 4-byte gridpoints

    @property
    def radii(self) -> Tuple[int, int, int]:
        """Per-dimension halo radii (scalar radius broadcast).  Every
        consumer — region datatypes, allocations, and the stencil
        kernels — is per-dimension aware; the old ``scalar_radius``
        symmetry guard is gone."""
        if isinstance(self.radius, tuple):
            return self.radius
        return (self.radius, self.radius, self.radius)

    @property
    def alloc(self) -> Tuple[int, int, int]:
        return tuple(n + 2 * r for n, r in zip(self.interior, self.radii))

    @property
    def nranks(self) -> int:
        return int(np.prod(self.grid))

    def coords(self, rank: int) -> Tuple[int, int, int]:
        pz, py, px = self.grid
        return (rank // (py * px), (rank // px) % py, rank % px)

    def rank_of(self, c: Sequence[int]) -> int:
        pz, py, px = self.grid
        return (c[0] % pz) * py * px + (c[1] % py) * px + (c[2] % px)

    def perm(self, d: Tuple[int, int, int]) -> List[Tuple[int, int]]:
        """ppermute edges: every rank sends toward direction ``d``
        (periodic)."""
        return [
            (r, self.rank_of(tuple(ci + di for ci, di in zip(self.coords(r), d))))
            for r in range(self.nranks)
        ]


def _region_type(spec: HaloSpec, d, kind: str) -> Subarray:
    """Subarray datatype for the send/recv region of direction ``d``.

    kind="send": the interior slab facing ``d``.
    kind="recv": the halo shell on side ``-d`` (filled by the neighbor at
    ``-d`` during round ``d``; see module docstring).
    """
    radii = spec.radii
    sizes_zyx = spec.alloc
    sub, start = [], []
    for axis in range(3):
        n = spec.interior[axis]
        r = radii[axis]
        di = d[axis]
        if di == 0:
            sub.append(n)
            start.append(r)
        else:
            sub.append(r)
            if kind == "send":
                start.append(r if di < 0 else n)       # low/high interior slab
            else:
                start.append(n + r if di < 0 else 0)   # halo shell on side -d
    # paper order: index 0 = innermost (x); local arrays are (z, y, x)
    return Subarray(
        tuple(reversed(sizes_zyx)),
        tuple(reversed(sub)),
        tuple(reversed(start)),
        spec.element,
    )


def make_halo_types(
    spec: HaloSpec, comm
) -> Dict[Tuple[int, int, int], Tuple[CommittedType, CommittedType]]:
    """Commit all 26 (send, recv) datatypes once (paper: 26 MPI_Pack +
    26 MPI_Unpack per iteration on committed types).  Accepts a
    Communicator or the deprecated Interposer shim."""
    return {
        d: (comm.commit(_region_type(spec, d, "send")),
            comm.commit(_region_type(spec, d, "recv")))
        for d in DIRECTIONS
    }


@dataclass(frozen=True)
class HaloPlan:
    """Everything a halo exchange needs, computed once: the committed
    (send, recv) types, their permutations, the selected strategies, and
    the exact-byte :class:`~repro.comm.wireplan.WirePlan`.  Build with
    :func:`make_halo_plan` at setup time (``make_halo_step`` does); the
    per-iteration host work is then dictionary lookups only."""

    spec: HaloSpec
    send_cts: Tuple[CommittedType, ...]
    recv_cts: Tuple[CommittedType, ...]
    perms: Tuple[Tuple[Tuple[int, int], ...], ...]
    strategies: Tuple[Strategy, ...]
    wire: WirePlan

    @property
    def wire_bytes(self) -> int:
        """Exact bytes one exchange puts on the wire (the ragged
        optimum: the sum of per-peer packed extents)."""
        return self.wire.wire_bytes


def make_halo_plan(
    spec: HaloSpec, comm, types=None, schedule_policy: Optional[str] = None
) -> HaloPlan:
    """Commit the 26 region types, select strategies, and lay out the
    exact-byte wire plan — the full setup cost of a halo exchange, paid
    once.  ``schedule_policy`` defaults to the communicator's policy
    (model-priced: grouped launch latencies traded against uniform
    padding bytes — see :meth:`Communicator.plan_neighbor`); pass
    ``"exact"`` for the byte-exact ladder the wire-bytes gates assert."""
    comm = as_communicator(comm)
    if types is None:
        types = make_halo_types(spec, comm)
    send_cts = tuple(types[d][0] for d in DIRECTIONS)
    recv_cts = tuple(types[d][1] for d in DIRECTIONS)
    perms = tuple(tuple(spec.perm(d)) for d in DIRECTIONS)
    strategies, wire = comm.plan_neighbor(
        send_cts, perms, schedule_policy=schedule_policy
    )
    return HaloPlan(
        spec=spec,
        send_cts=send_cts,
        recv_cts=recv_cts,
        perms=perms,
        strategies=strategies,
        wire=wire,
    )


def ihalo_exchange(
    local: jax.Array,
    spec: HaloSpec,
    comm,
    axis_name: str = "ranks",
    types=None,
    plan: Optional[HaloPlan] = None,
) -> Request:
    """Nonblocking 26-neighbor halo exchange: the fused wire transport
    (exact ragged payloads) is issued immediately; ``wait()``
    materializes the 26 unpacks.  Must run inside shard_map over a 1D
    mesh axis of ``spec.nranks`` devices.  Pass a prebuilt ``plan``
    (:func:`make_halo_plan`) to skip per-call planning."""
    comm = as_communicator(comm)
    if plan is None:
        plan = make_halo_plan(spec, comm, types)
    return comm.ineighbor_alltoallv(
        local,
        plan.send_cts,
        plan.recv_cts,
        plan.perms,
        axis_name,
        plan=plan.wire,
        strategies=plan.strategies,
    )


def halo_exchange(
    local: jax.Array,
    spec: HaloSpec,
    comm,
    axis_name: str = "ranks",
    types=None,
    plan: Optional[HaloPlan] = None,
) -> jax.Array:
    """One full 26-neighbor halo exchange for this rank's ``local`` block
    (exact wire bytes, fused schedule).  Returns ``local`` with all halo
    shells filled."""
    return ihalo_exchange(local, spec, comm, axis_name, types, plan).wait()


def make_halo_step(spec: HaloSpec, comm, mesh: Mesh, axis_name="ranks",
                   schedule_policy: Optional[str] = None):
    """jit-compiled shard_map wrapper: (nranks*az, ay, ax) global array,
    sharded on the leading axis, -> exchanged.  The halo plan (types,
    strategies, wire layout) is built here, once."""
    plan = make_halo_plan(spec, comm, schedule_policy=schedule_policy)

    def step(local):
        return halo_exchange(local, spec, comm, axis_name, plan=plan)

    fn = shard_map(
        step,
        mesh=mesh,
        in_specs=P(axis_name),
        out_specs=P(axis_name),
        check_vma=False,
    )
    return jax.jit(fn)
