"""Tests: region-split halo overlap (ROADMAP: per-direction wire
completion).  The 3^3 core/face/edge/corner decomposition must exactly
partition the first application's output window; per-delta-class
ClassRequest/NeighborRequest drains must compose in any completion
order; the model's core/rim pricing must pick and pin an
``overlap/mode=...`` decision; and region mode must stay bit-identical
to the monolithic path on a real 2x2x2 grid for s in {1, 2, 3}."""

import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.comm import ClassRequest, Communicator, NeighborRequest
from repro.halo import (
    DIRECTIONS,
    HaloSpec,
    STENCIL26,
    StencilOp,
    as_ops,
    cycle_halo_radii,
    halo_exchange,
    halo_regions,
    make_halo_plan,
    make_halo_types,
    overlap_region_descriptors,
    overlapped_stencil_iteration,
    stencil_steps,
)
from repro.measure import DecisionCache


# ---------------------------------------------------------------------------
# the decomposition: core + faces/edges/corners exactly partition
# ---------------------------------------------------------------------------

def _assert_partition(spec, ops):
    """Brute force: summing every region's indicator must give exactly 1
    on the first application's output window and 0 elsewhere."""
    ops = as_ops(ops)
    first = ops[0]
    cover = np.zeros(spec.alloc, dtype=np.int32)
    for reg in halo_regions(spec, ops):
        sl = tuple(slice(o, o + s) for o, s in zip(reg.origin, reg.shape))
        cover[sl] += 1
    window = np.zeros(spec.alloc, dtype=np.int32)
    window[tuple(
        slice(r, r + n + 2 * (hr - r))
        for n, hr, r in zip(spec.interior, spec.radii, first.radii)
    )] = 1
    np.testing.assert_array_equal(cover, window)


def test_regions_structure_26_point():
    """Roomy interior, single-step halo: the full 3^3 decomposition —
    one core, 6 faces, 12 edges, 8 corners — with the expected band ->
    transfer wiring."""
    spec = HaloSpec(grid=(1, 1, 1), interior=(8, 7, 6), radius=1)
    regions = halo_regions(spec, STENCIL26)
    by_rank = {}
    for reg in regions:
        by_rank.setdefault(sum(abs(s) for s in reg.sig), []).append(reg)
    assert len(by_rank[0]) == 1      # core
    assert len(by_rank[1]) == 6      # faces
    assert len(by_rank[2]) == 12     # edges
    assert len(by_rank[3]) == 8      # corners

    core = by_rank[0][0]
    assert core.sig == (0, 0, 0)
    assert core.bands == () and core.transfers == ()
    assert core.shape == (6, 5, 4)   # interior - 2r per axis

    face = next(r for r in by_rank[1] if r.sig == (-1, 0, 0))
    assert face.bands == ((-1, 0, 0),)
    assert face.transfers == (DIRECTIONS.index((1, 0, 0)),)

    corner = next(r for r in by_rank[3] if r.sig == (1, 1, 1))
    # the corner's neighborhood reaches the face, edge and corner bands
    # on its octant: 2^3 - 1 bands
    assert len(corner.bands) == 7
    assert len(corner.transfers) == 7


@pytest.mark.parametrize("interior,radius,ops", [
    # the classic 26-point smoother, two fused steps
    ((8, 7, 6), 2, STENCIL26),
    # asymmetric per-dim radii: deep along the slow axis
    ((6, 5, 4), (4, 2, 2), StencilOp((2, 1, 1))),
    # heterogeneous cycle, radii from the cycle (s = 2 repeats)
    ((6, 5, 4), None, (StencilOp((2, 1, 1), 0.5), StencilOp((1, 1, 1), 0.25))),
    # interior shallower than 2r: the low/high read-sets overlap
    ((2, 5, 4), 2, STENCIL26),
    # tiny domain
    ((1, 1, 1), 1, STENCIL26),
    # deep shell (hr > 2r): dependency over-approximation territory
    ((6, 6, 6), 4, STENCIL26),
])
def test_regions_exact_partition(interior, radius, ops):
    if radius is None:
        radius = cycle_halo_radii(as_ops(ops), 2)
    spec = HaloSpec(grid=(1, 1, 1), interior=interior, radius=radius)
    _assert_partition(spec, ops)


def test_regions_partition_property():
    """Property test: for any geometry — asymmetric per-dim radii,
    heterogeneous cycle radii, interiors down to the halo depth — the
    nonempty regions exactly partition the window (no overlap, no
    gap)."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @st.composite
    def geometries(draw):
        ncycle = draw(st.integers(1, 2))
        ops = tuple(
            StencilOp(tuple(
                draw(st.integers(1, 2)) for _ in range(3)
            ))
            for _ in range(ncycle)
        )
        steps = draw(st.integers(1, 2))
        hr = cycle_halo_radii(ops, steps)
        interior = tuple(draw(st.integers(h, h + 5)) for h in hr)
        return interior, hr, ops

    @settings(max_examples=80, deadline=None)
    @given(geometries())
    def check(geom):
        interior, hr, ops = geom
        spec = HaloSpec(grid=(1, 1, 1), interior=interior, radius=hr)
        _assert_partition(spec, ops)

    check()


# ---------------------------------------------------------------------------
# per-class Request semantics
# ---------------------------------------------------------------------------

class _FakePayload:
    """Stands in for a received jax.Array: readiness is scripted."""

    def __init__(self, ready=False):
        self.ready = ready

    def is_ready(self):
        return self.ready


def _class(index, ready=False):
    # unpacking appends the class index to the (tuple-valued) buffer —
    # enough to observe exactly which classes landed, in which order
    return ClassRequest(
        index, _FakePayload(ready), transfers=(index,), nbytes=8 * index,
        unpack=lambda buf, payload, i=index: buf + (i,),
    )


def test_class_request_out_of_order_completion():
    classes = [_class(0), _class(1, ready=True), _class(2)]
    drains = []
    req = NeighborRequest(
        (), classes, on_drain=lambda r, c: drains.append(c.index)
    )
    assert not req.completed
    assert len(req.pending) == 3

    # class 1's wire landed first: wait_any must drain IT, not plan order
    got = req.wait_any()
    assert got.index == 1 and got.applied
    assert req.buffer == (1,)

    # class 2 lands next; class 0 still in flight
    classes[2]._value.ready = True
    assert req.wait_any().index == 2
    # nothing ready -> fall back to plan order (deterministic drain)
    assert req.wait_any().index == 0

    assert req.drained == [1, 2, 0]
    assert drains == [1, 2, 0]
    assert req.buffer == (1, 2, 0)
    assert req.completed and req.wait() == (1, 2, 0)
    with pytest.raises(ValueError):
        req.wait_any()


def test_class_request_wait_drains_everything():
    req = NeighborRequest((), [_class(i) for i in range(4)])
    assert req.wait() == (0, 1, 2, 3)  # plan order when nothing is ready
    assert req.drained == [0, 1, 2, 3]
    assert all(c.applied for c in req.classes)


def test_class_request_empty_exchange_completes_immediately():
    req = NeighborRequest("buf", [])
    assert req.completed and req.wait() == "buf"


# ---------------------------------------------------------------------------
# model pricing: per-class completions, core/rim schedule, pinning
# ---------------------------------------------------------------------------

def _plan_7_classes(comm, schedule_policy="exact"):
    spec = HaloSpec(grid=(2, 2, 2), interior=(6, 5, 4), radius=2)
    types = make_halo_types(spec, comm)
    plan = make_halo_plan(spec, comm, types, schedule_policy=schedule_policy)
    return spec, plan


def test_price_class_completions_profile():
    comm = Communicator(axis_name="ranks")
    spec, plan = _plan_7_classes(comm)
    from repro.comm import reschedule

    grouped = reschedule(plan.wire, "grouped")
    comps = comm.model.price_class_completions(grouped)
    assert len(comps) == grouped.ngroups == 7
    # grouped: class k rides the k-th collective — completions must be
    # strictly increasing (cumulative bytes + per-launch latency)
    assert all(b > a for a, b in zip(comps, comps[1:]))
    # fused schedules complete every class together
    uniform = reschedule(plan.wire, "uniform")
    ucomps = comm.model.price_class_completions(uniform)
    assert len(set(ucomps)) == 1 and len(ucomps) == 7


def test_overlap_descriptors_and_pricing():
    comm = Communicator(axis_name="ranks")
    spec, plan = _plan_7_classes(comm)
    core_bytes, rims = overlap_region_descriptors(spec, STENCIL26, plan.wire)
    # radius 2, interior (6,5,4): core is the (2,1,0)-shaped... empty in
    # x -> core_bytes 0 is allowed; rims must all be nonempty with deps
    # inside the plan's class space
    assert core_bytes >= 0
    assert rims and all(nb > 0 for nb, _ in rims)
    ncls = plan.wire.ngroups
    assert all(
        deps and all(0 <= c < ncls for c in deps) for _, deps in rims
    )

    ests = comm.model.price_overlap(
        plan.wire, rims, core_bytes, STENCIL26.nneighbors
    )
    assert set(ests) == {"monolithic", "region"}
    mono, region = ests["monolithic"], ests["region"]
    assert mono.t_total >= max(mono.t_wire, mono.t_core)
    assert len(mono.t_rims) == len(rims)
    assert region.class_completions == mono.class_completions
    # neither mode finishes before the slowest class has landed
    assert region.t_total >= region.t_wire


def test_choose_overlap_mode_records_then_pins():
    comm = Communicator(axis_name="ranks", decisions=DecisionCache())
    spec, plan = _plan_7_classes(comm)
    core_bytes, rims = overlap_region_descriptors(spec, STENCIL26, plan.wire)

    mode, ests, pinned = comm.model.choose_overlap_mode(
        plan.wire, rims, core_bytes, STENCIL26.nneighbors
    )
    assert mode in ("monolithic", "region") and not pinned
    rows = [
        d for d in comm.model.decisions.log
        if d.strategy.startswith("overlap/mode=")
    ]
    assert len(rows) == 1
    assert rows[0].strategy == f"overlap/mode={mode}"
    assert "regions=" in rows[0].signature

    # the recorded row pins the rerun — no re-pricing flip possible
    mode2, _, pinned2 = comm.model.choose_overlap_mode(
        plan.wire, rims, core_bytes, STENCIL26.nneighbors
    )
    assert (mode2, pinned2) == (mode, True)

    # a hand-pinned row overrides the priced winner entirely
    import dataclasses

    other = "region" if mode == "monolithic" else "monolithic"
    forced = DecisionCache([
        dataclasses.replace(rows[0], strategy=f"overlap/mode={other}")
    ])
    comm2 = Communicator(axis_name="ranks", decisions=forced)
    mode3, _, pinned3 = comm2.model.choose_overlap_mode(
        plan.wire, rims, core_bytes, STENCIL26.nneighbors
    )
    assert pinned3 and mode3 == other


# ---------------------------------------------------------------------------
# end to end: region mode bit-identical, single rank + 8 ranks
# ---------------------------------------------------------------------------

def _mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("ranks",))


@pytest.mark.parametrize("mode", ["region", "auto"])
def test_region_mode_matches_plain_single_rank(mode):
    spec = HaloSpec(grid=(1, 1, 1), interior=(6, 5, 4), radius=2)
    az, ay, ax = spec.alloc
    comm = Communicator(axis_name="ranks", decisions=DecisionCache())
    types = make_halo_types(spec, comm)
    probe = {}

    def plain(local):
        local = halo_exchange(local, spec, comm, "ranks", types)
        return stencil_steps(local, spec, steps=2)

    def split(local):
        return overlapped_stencil_iteration(
            local, spec, comm, "ranks", types, steps=2, probe=probe,
            mode=mode,
        )

    mesh = _mesh1()
    jp = jax.jit(shard_map(plain, mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False))
    jo = jax.jit(shard_map(split, mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False))
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(az, ay, ax)).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(jp(x)), np.asarray(jo(x)))
    assert probe["pending_during_interior"] is True
    assert probe["overlap_mode"] in ("monolithic", "region")
    if mode == "region":
        assert probe["overlap_mode"] == "region"
        # single-rank periodic grid: one delta class carries all 26
        # transfers, every rim drains on the first (only) wait_any
        assert probe["rim_regions"] == 26
        assert probe["class_drain_order"] == (0,)
        assert len(probe["region_order"]) == 26
    else:
        # auto resolved and pinned an overlap/mode decision
        assert any(
            d.strategy == f"overlap/mode={probe['overlap_mode']}"
            for d in comm.model.decisions.log
        )


def test_region_mode_rejects_unknown():
    spec = HaloSpec(grid=(1, 1, 1), interior=(6, 5, 4), radius=1)
    comm = Communicator(axis_name="ranks")
    with pytest.raises(ValueError, match="overlap mode"):
        overlapped_stencil_iteration(
            jnp.zeros(spec.alloc, jnp.float32), spec, comm, "ranks",
            steps=1, mode="sideways",
        )


REGION_8RANK_CODE = r"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.comm import Communicator
from repro.halo import (HaloSpec, halo_exchange, make_halo_plan,
                        make_halo_types, overlapped_stencil_iteration,
                        stencil_steps)

mesh = Mesh(np.array(jax.devices()), ("ranks",))
for s in (1, 2, 3):
    spec = HaloSpec(grid=(2, 2, 2), interior=(6, 5, 4), radius=s)
    R = spec.nranks
    az, ay, ax = spec.alloc
    assert len(jax.devices()) == R
    comm = Communicator(axis_name="ranks")
    types = make_halo_types(spec, comm)
    plan = make_halo_plan(spec, comm, types, schedule_policy="exact")
    probe = {}

    def plain(local):
        local = halo_exchange(local, spec, comm, "ranks", types, plan=plan)
        return stencil_steps(local, spec, steps=s)

    def region(local):
        return overlapped_stencil_iteration(
            local, spec, comm, "ranks", types, steps=s, probe=probe,
            plan=plan, mode="region")

    def mono(local):
        return overlapped_stencil_iteration(
            local, spec, comm, "ranks", types, steps=s,
            plan=plan, mode="monolithic")

    kw = dict(mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks"),
              check_vma=False)
    jp = jax.jit(shard_map(plain, **kw))
    jr = jax.jit(shard_map(region, **kw))
    jm = jax.jit(shard_map(mono, **kw))
    rng = np.random.default_rng(11 + s)
    x = jnp.asarray(rng.normal(size=(R * az, ay, ax)).astype(np.float32))
    ref = np.asarray(jp(x))
    np.testing.assert_array_equal(ref, np.asarray(jr(x)),
                                  err_msg=f"region s={s}")
    np.testing.assert_array_equal(ref, np.asarray(jm(x)),
                                  err_msg=f"monolithic s={s}")
    assert probe["overlap_mode"] == "region"
    assert probe["rim_regions"] == 26, probe
    assert sorted(probe["class_drain_order"]) == list(
        range(plan.wire.ngroups)), probe
    assert plan.wire.ngroups == 7
print("REGION_SPLIT_OK")
"""


@pytest.mark.slow
def test_region_mode_matches_monolithic_8_ranks_deep():
    """The tentpole invariant on a real 2x2x2 grid: region-split is
    bit-identical to BOTH the plain exchange-then-cycle path and the
    monolithic overlap path, for fusion depths s in {1, 2, 3}."""
    from tests._subproc import run_with_devices

    out = run_with_devices(REGION_8RANK_CODE, ndev=8)
    assert "REGION_SPLIT_OK" in out
