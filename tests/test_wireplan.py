"""Tests: the exact-byte WirePlan layer (ISSUE 3).

Covers the wire planner (segment layout, schedule ladder, grid-size
fallback), the ragged pack/unpack kernel entry points, wire-byte
accounting end-to-end (traced payload == plan == PerfModel/DecisionCache
records), asymmetric halos against the per-direction ppermute reference,
the int8 compressed-wire plugin, per-axis wire tables, and the
production communicator wiring.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.comm import (
    Communicator,
    FixedPolicy,
    INT8_WIRE,
    PerfModel,
    SystemParams,
    TPU_V5E,
    collective_payload_bytes,
    default_registry,
)
from repro.comm.api import ROWS
from repro.comm.wireplan import has_ragged_all_to_all, plan_wire
from repro.core import BYTE, FLOAT, Subarray, TypeRegistry, Vector, WireSegment
from repro.halo import HaloSpec, make_halo_plan
from repro.kernels.pack import pack_ragged
from repro.kernels.unpack import unpack_ragged
from repro.measure import DecisionCache
from tests._subproc import run_with_devices


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("x",))


def _ring(n):
    return tuple((r, (r + 1) % n) for r in range(n))


# ===========================================================================
# the planner: exact segments, schedule ladder, thresholds
# ===========================================================================

class TestPlanWire:
    def test_exact_segment_layout(self):
        n = 4
        sizes = (10, 3, 7, 5)
        perms = (_ring(n),) * 2 + (tuple((r, (r + 2) % n) for r in range(n)),) * 2
        plan = plan_wire(sizes, perms, fingerprints=("a", "b", "c", "d"),
                         native=False)
        assert plan.ngroups == 2
        assert plan.wire_bytes == sum(sizes)
        assert plan.padding_bytes == 0
        # segments tile the flat buffer exactly, in group order
        segs = sorted(plan.segments, key=lambda s: s.offset)
        assert segs[0].offset == 0
        for a, b in zip(segs, segs[1:]):
            assert a.end == b.offset
        assert segs[-1].end == plan.wire_bytes
        assert {s.fingerprint for s in plan.segments} == {"a", "b", "c", "d"}
        # group-local offsets are consistent with the global segments
        for goff, grp in zip(plan.group_offsets, plan.groups):
            for i, off in zip(grp.transfers, grp.offsets):
                assert plan.segments[i].offset == goff + off

    def test_schedule_ladder(self):
        n = 4
        sizes = (8, 8)
        perms = (_ring(n), tuple((r, (r - 1) % n) for r in range(n)))
        # native ragged collective available -> single ragged op
        ragged = plan_wire(sizes, perms, native=True)
        assert ragged.schedule == "ragged" and ragged.wire_ops == 1
        # no native op, zero tolerance, unequal-to-rank groups -> grouped
        grouped = plan_wire(sizes, perms, native=False)
        assert grouped.schedule == "grouped" and grouped.wire_ops == 2
        assert grouped.issued_bytes == grouped.wire_bytes == 16
        # tolerance admits the padded uniform collective
        uniform = plan_wire(sizes, perms, native=False,
                            uniform_waste_tolerance=float("inf"))
        assert uniform.schedule == "uniform" and uniform.wire_ops == 1
        assert uniform.issued_bytes == n * uniform.seg_bytes

    def test_grid_size_threshold(self):
        # 32 ranks, 1 delta class: fused rows would be 31/32 zeros — the
        # plan must fall back to grouped regardless of native support
        n = 32
        plan = plan_wire((64,), (_ring(n),), native=True,
                         uniform_waste_tolerance=float("inf"))
        assert plan.schedule == "grouped"

    def test_byte_exact_uniform_is_allowed(self):
        # 1 rank, self-exchange: ngroups == nranks and zero padding —
        # the single uniform collective is byte-exact and admissible
        plan = plan_wire((8, 4), (((0, 0),), ((0, 0),)), native=False)
        assert plan.schedule == "uniform"
        assert plan.padding_bytes == 0
        assert plan.issued_bytes == plan.wire_bytes == 12

    def test_validation(self):
        with pytest.raises(ValueError, match="align"):
            plan_wire((8, 8), (((0, 0),),))
        with pytest.raises(ValueError, match="not a permutation"):
            plan_wire((8,), (((0, 0), (1, 0)),))

    def test_fingerprint_stable_and_content_keyed(self):
        a = plan_wire((8, 4), (((0, 0),), ((0, 0),)), fingerprints=("x", "y"))
        b = plan_wire((8, 4), (((0, 0),), ((0, 0),)), fingerprints=("x", "y"))
        c = plan_wire((8, 5), (((0, 0),), ((0, 0),)), fingerprints=("x", "y"))
        assert a.fingerprint == b.fingerprint
        assert a.fingerprint != c.fingerprint


# ===========================================================================
# ragged kernel entry points
# ===========================================================================

class TestRaggedKernels:
    def test_pack_unpack_ragged_roundtrip(self):
        rng = np.random.default_rng(5)
        buf = jnp.asarray(rng.integers(0, 255, (64,), dtype=np.uint8))
        leaves = [
            (0, lambda b: jax.lax.dynamic_slice(b, (0,), (8,))),
            (8, lambda b: jax.lax.dynamic_slice(b, (16,), (4,))),
            (12, lambda b: jax.lax.dynamic_slice(b, (32,), (3,))),
        ]
        wire = pack_ragged(buf, leaves, 15)
        assert wire.shape == (15,)
        w = np.asarray(wire)
        np.testing.assert_array_equal(w[0:8], np.asarray(buf)[0:8])
        np.testing.assert_array_equal(w[8:12], np.asarray(buf)[16:20])
        np.testing.assert_array_equal(w[12:15], np.asarray(buf)[32:35])

        def put(at):
            return lambda dst, part: jax.lax.dynamic_update_slice(
                dst, part, (at,)
            )

        dst = unpack_ragged(jnp.zeros((64,), jnp.uint8), wire,
                            [(0, 8, put(40)), (8, 4, put(50)), (12, 3, put(60))])
        d = np.asarray(dst)
        np.testing.assert_array_equal(d[40:48], np.asarray(buf)[0:8])
        np.testing.assert_array_equal(d[50:54], np.asarray(buf)[16:20])
        np.testing.assert_array_equal(d[60:63], np.asarray(buf)[32:35])


# ===========================================================================
# wire-byte accounting: traced payload == plan == model/decision records
# ===========================================================================

class TestWireAccounting:
    def test_neighbor_accounting_and_decision_record(self):
        dc = DecisionCache()
        comm = Communicator(axis_name="x", decisions=dc)
        send_cts = [
            comm.commit(Subarray((64,), (8,), (0,), BYTE)),
            comm.commit(Subarray((64,), (4,), (16,), BYTE)),
        ]
        recv_cts = [
            comm.commit(Subarray((64,), (8,), (32,), BYTE)),
            comm.commit(Subarray((64,), (4,), (48,), BYTE)),
        ]
        perms = [[(0, 0)], [(0, 0)]]
        strats, plan = comm.plan_neighbor(send_cts, perms)
        assert plan.wire_bytes == 12

        def body(b):
            return comm.neighbor_alltoallv(
                b, send_cts, recv_cts, perms, plan=plan, strategies=strats
            )

        fn = jax.jit(shard_map(body, mesh=_mesh1(), in_specs=P(),
                               out_specs=P(), check_vma=False))
        before_ops, before_bytes = comm.wire_ops, comm.wire_payload_bytes
        fn(jnp.arange(64, dtype=jnp.uint8))
        assert comm.wire_ops - before_ops == plan.wire_ops
        assert comm.wire_payload_bytes - before_bytes == plan.issued_bytes
        # the traced program moves exactly the plan's bytes
        counts = collective_payload_bytes(fn, jnp.arange(64, dtype=jnp.uint8))
        assert counts["total"] == plan.issued_bytes == plan.wire_bytes
        # ...and the decision cache recorded that same byte count
        rows = [d for d in dc.log if d.fingerprint == plan.fingerprint]
        assert len(rows) == 1
        assert rows[0].wire_bytes == plan.wire_bytes
        assert rows[0].strategy == f"wire/{plan.schedule}"
        assert str(plan.wire_bytes) in dc.report()

    def test_caller_plan_kept_when_strategies_omitted(self):
        # a plan built with non-default knobs must not be silently
        # re-planned (at default tolerance) just because strategies
        # weren't passed alongside it
        comm = Communicator(axis_name="x")
        send_cts = [
            comm.commit(Subarray((64,), (8,), (0,), BYTE)),
            comm.commit(Subarray((64,), (4,), (16,), BYTE)),
        ]
        recv_cts = [
            comm.commit(Subarray((64,), (8,), (32,), BYTE)),
            comm.commit(Subarray((64,), (4,), (48,), BYTE)),
        ]
        perms = [[(0, 0)], [(0, 0)]]
        sizes = tuple(ct.packed_extent() for ct in send_cts)
        custom = plan_wire(sizes, (((0, 0),), ((0, 0),)), native=False,
                           uniform_waste_tolerance=float("inf"))

        def body(b):
            return comm.neighbor_alltoallv(
                b, send_cts, recv_cts, perms, plan=custom
            )

        fn = jax.jit(shard_map(body, mesh=_mesh1(), in_specs=P(),
                               out_specs=P(), check_vma=False))
        buf = jnp.arange(64, dtype=jnp.uint8)
        out = np.asarray(fn(buf))
        want = np.arange(64, dtype=np.uint8)
        want[32:40] = want[0:8]
        want[48:52] = want[16:20]
        np.testing.assert_array_equal(out, want)
        counts = collective_payload_bytes(fn, buf)
        assert counts["ops"] == custom.wire_ops  # the caller's schedule ran
        assert counts["total"] == custom.issued_bytes
        # a plan for a different transfer count is rejected loudly
        with pytest.raises(ValueError, match="wire plan describes"):
            comm.ineighbor_alltoallv(buf, send_cts[:1], recv_cts[:1],
                                     perms[:1], plan=custom)

    def test_exchange_recorded_once_per_plan(self):
        dc = DecisionCache()
        comm = Communicator(axis_name="x", decisions=dc)
        ct = comm.commit(Subarray((64,), (8,), (0,), BYTE))
        for _ in range(3):
            comm.plan_neighbor([ct], [[(0, 0)]])
        rows = [d for d in dc.log if d.strategy.startswith("wire/")]
        assert len(rows) == 1

    def test_per_type_decisions_carry_wire_bytes(self):
        dc = DecisionCache()
        model = PerfModel(TPU_V5E, decisions=dc)
        ct = TypeRegistry().commit(Vector(16, 64, 512, BYTE))
        est = model.select(ct)
        assert est.wire_bytes > 0
        assert dc.log[0].wire_bytes == est.wire_bytes

    def test_isend_accounting(self):
        comm = Communicator(axis_name="x")
        ct = comm.commit(Subarray((64,), (8,), (0,), BYTE))

        def body(b):
            req = comm.isend(b, ct, [(0, 0)])
            return comm.irecv(b, ct, req).wait()

        fn = jax.jit(shard_map(body, mesh=_mesh1(), in_specs=P(),
                               out_specs=P(), check_vma=False))
        buf = jnp.arange(64, dtype=jnp.uint8)
        fn(buf)
        counts = collective_payload_bytes(fn, buf)
        s = comm.select(ct, 1, wire=True)
        assert counts["total"] == s.wire_bytes(ct)


# ===========================================================================
# strategy wire segments
# ===========================================================================

class TestWireSegments:
    def test_packed_extent_and_segment(self):
        ct = TypeRegistry().commit(Vector(4, 8, 16, BYTE))
        assert ct.packed_extent() == 32
        assert ct.packed_extent(3) == 96
        seg = ct.wire_segment(offset=7)
        assert seg == WireSegment(ct.fingerprint, 7, 32)
        assert seg.end == 39

    def test_strategy_segments_differ_from_packed_size(self):
        reg = TypeRegistry()
        ct = reg.commit(Vector(4, 8, 64, BYTE))     # sparse in its extent
        rows_seg = ROWS.wire_segment(ct)
        assert rows_seg.nbytes == ct.size == 32
        from repro.comm.api import BOUNDING

        bseg = BOUNDING.wire_segment(ct)
        assert bseg.nbytes == ct.block.extent      # the window, not the data
        assert bseg.nbytes != ct.size
        iseg = INT8_WIRE.wire_segment(ct)
        assert iseg.nbytes == 4 + ct.size // 4     # compressed + header
        assert iseg.fingerprint == ct.fingerprint


# ===========================================================================
# int8 compressed-wire plugin
# ===========================================================================

class TestInt8Wire:
    def test_registered_but_never_auto_selected(self):
        reg = default_registry()
        assert INT8_WIRE.name in reg
        assert INT8_WIRE not in reg.selectable()
        assert INT8_WIRE not in reg.measurable()

    def test_sendrecv_roundtrip_within_quantization_error(self):
        comm = Communicator(axis_name="x", policy=FixedPolicy(INT8_WIRE.name))
        # a strided float32 region (Subarray dims innermost-first):
        # 8 rows x 4 floats starting at column 2 of a (16, 16) array
        dt = Subarray((16, 16), (4, 8), (2, 0), FLOAT)
        ct = comm.commit(dt)
        assert INT8_WIRE.applicable(ct)
        rng = np.random.default_rng(0)
        src = rng.normal(size=(16, 16)).astype(np.float32)

        def body(b):
            return comm.sendrecv(b, jnp.zeros_like(b), ct, [(0, 0)])

        fn = jax.jit(shard_map(body, mesh=_mesh1(), in_specs=P(),
                               out_specs=P(), check_vma=False))
        out = np.asarray(fn(jnp.asarray(src)))
        region = np.s_[0:8, 2:6]
        scale = np.abs(src[region]).max() / 127.0
        np.testing.assert_allclose(out[region], src[region],
                                   atol=scale / 2 + 1e-7)
        # untouched cells stay zero
        mask = np.ones_like(src, dtype=bool)
        mask[region] = False
        assert (out[mask] == 0).all()

    def test_wire_plan_accounts_compressed_bytes(self):
        comm = Communicator(axis_name="x", policy=FixedPolicy(INT8_WIRE.name))
        ct = comm.commit(Subarray((16, 16), (4, 8), (2, 0), FLOAT))
        strats, plan = comm.plan_neighbor([ct], [[(0, 0)]])
        assert strats[0] is INT8_WIRE
        want = 4 + ct.size // 4
        assert plan.wire_bytes == want != ct.size

        def body(b):
            return comm.neighbor_alltoallv(b, [ct], [ct], [[(0, 0)]],
                                           plan=plan, strategies=strats)

        fn = jax.jit(shard_map(body, mesh=_mesh1(), in_specs=P(),
                               out_specs=P(), check_vma=False))
        x = jnp.zeros((16, 16), jnp.float32)
        counts = collective_payload_bytes(fn, x)
        assert counts["total"] == plan.issued_bytes
        assert plan.issued_bytes == want  # wire_bytes != ct.size, exactly

    def test_estimate_prices_compressed_link(self):
        model = PerfModel(TPU_V5E)
        ct = TypeRegistry().commit(Subarray((64, 64), (16, 32), (8, 0), FLOAT))
        est = model.estimate(ct, 1, INT8_WIRE.name)
        full = model.estimate(ct, 1, "rows")
        assert est.wire_bytes < full.wire_bytes
        assert est.t_link < full.t_link


# ===========================================================================
# asymmetric halos (unequal radii) vs the per-direction ppermute reference
# ===========================================================================

ASYM_HALO_CODE = r"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.comm import Communicator, FixedPolicy, collective_payload_bytes
from repro.halo import HaloSpec, halo_exchange, make_halo_plan
from repro.halo.exchange import DIRECTIONS

spec = HaloSpec(grid=(2, 2, 2), interior=(6, 5, 4), radius=(2, 1, 1))
rz, ry, rx = spec.radii
nz, ny, nx = spec.interior
az, ay, ax = spec.alloc
R = spec.nranks
assert (az, ay, ax) == (10, 7, 6)

gz, gy, gx = 2 * nz, 2 * ny, 2 * nx
gvals = np.arange(gz * gy * gx, dtype=np.float32).reshape(gz, gy, gx)
locals_np = np.full((R, az, ay, ax), -1.0, np.float32)
for rank in range(R):
    cz, cy, cx = spec.coords(rank)
    locals_np[rank, rz:rz+nz, ry:ry+ny, rx:rx+nx] = gvals[
        cz*nz:(cz+1)*nz, cy*ny:(cy+1)*ny, cx*nx:(cx+1)*nx]
x0 = jnp.asarray(locals_np.reshape(R * az, ay, ax))

comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"))
mesh = Mesh(np.array(jax.devices()), ("ranks",))
plan = make_halo_plan(spec, comm, schedule_policy="exact")

fused = jax.jit(shard_map(
    lambda x: halo_exchange(x, spec, comm, "ranks", plan=plan),
    mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks"), check_vma=False))

# reference: 26 independent sendrecv ppermutes, one per direction
ref_types = {d: (plan.send_cts[i], plan.recv_cts[i])
             for i, d in enumerate(DIRECTIONS)}
def reference(local):
    for d in DIRECTIONS:
        s, r = ref_types[d]
        local = comm.sendrecv(local, local, s, spec.perm(d), "ranks", r)
    return local
ref = jax.jit(shard_map(reference, mesh=mesh, in_specs=P("ranks"),
                        out_specs=P("ranks"), check_vma=False))

out_f = np.asarray(fused(x0)).reshape(R, az, ay, ax)
out_r = np.asarray(ref(x0)).reshape(R, az, ay, ax)
np.testing.assert_array_equal(out_f, out_r)
print("BITEXACT_OK")

# periodic oracle with per-dimension radii
for rank in range(R):
    cz, cy, cx = spec.coords(rank)
    zz = (np.arange(az) - rz + cz * nz) % gz
    yy = (np.arange(ay) - ry + cy * ny) % gy
    xx = (np.arange(ax) - rx + cx * nx) % gx
    np.testing.assert_array_equal(out_f[rank], gvals[np.ix_(zz, yy, xx)],
                                  err_msg=f"rank {rank}")
print("ORACLE_OK")

# wire accounting: the fused path transfers exactly the sum of the
# per-peer packed extents — no padding anywhere, despite the unequal
# per-dimension radii making every class a different size
counts = collective_payload_bytes(fused, x0)
want = sum(ct.packed_extent() for ct in plan.send_cts)
assert plan.wire_bytes == want, (plan.wire_bytes, want)
assert counts["total"] == want, (counts, want)
assert counts["ops"] == plan.wire.wire_ops == plan.wire.ngroups
print("WIREBYTES_OK", want)
"""


@pytest.mark.slow
def test_asymmetric_halo_bit_exact_and_ragged():
    out = run_with_devices(ASYM_HALO_CODE, ndev=8)
    assert "BITEXACT_OK" in out
    assert "ORACLE_OK" in out
    assert "WIREBYTES_OK" in out


class TestHaloSpecRadii:
    def test_scalar_radius_broadcasts(self):
        spec = HaloSpec(grid=(1, 1, 1), interior=(4, 4, 4), radius=2)
        assert spec.radii == (2, 2, 2)
        assert spec.alloc == (8, 8, 8)

    def test_asymmetric_radii(self):
        # the old scalar_radius symmetry guard is gone: asymmetric specs
        # are first-class all the way into the stencil kernels
        spec = HaloSpec(grid=(1, 1, 1), interior=(6, 5, 4), radius=(2, 1, 1))
        assert spec.radii == (2, 1, 1)
        assert spec.alloc == (10, 7, 6)
        assert not hasattr(spec, "scalar_radius")

    def test_halo_plan_wire_bytes_property(self):
        comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"))
        spec = HaloSpec(grid=(1, 1, 1), interior=(4, 4, 4), radius=(2, 2, 1))
        plan = make_halo_plan(spec, comm)
        assert plan.wire_bytes == sum(ct.packed_extent() for ct in plan.send_cts)
        assert plan.wire.padding_bytes == 0


# ===========================================================================
# per-axis wire tables
# ===========================================================================

class TestPerAxisWire:
    def _params(self):
        return SystemParams(
            name="axes",
            wire_table=((10.0, 5e-5), (20.0, 5e-5)),
            wire_latency=1e-6,
            wire_tables={
                "ici": ((10.0, 1e-6), (20.0, 1e-6)),
                "dcn": ((10.0, 9e-4), (20.0, 9e-4)),
            },
            wire_fits={"ici": (1e-7, 5e10), "dcn": (1e-4, 1e9)},
        )

    def test_roundtrip(self):
        p = self._params()
        q = SystemParams.from_json(p.to_json())
        assert q == p
        assert q.wire_tables["dcn"][0] == (10.0, 9e-4)
        assert q.wire_fits["ici"] == (1e-7, 5e10)

    def test_t_link_prices_per_axis(self):
        model = PerfModel(self._params())
        assert model.t_link(1024, axis="ici") == pytest.approx(1e-6)
        assert model.t_link(1024, axis="dcn") == pytest.approx(9e-4)
        # unknown axis / no axis falls back to the flat table
        assert model.t_link(1024) == pytest.approx(5e-5)
        assert model.t_link(1024, axis="nope") == pytest.approx(5e-5)

    def test_extra_hops_use_axis_fit(self):
        model = PerfModel(self._params())
        base = model.t_link(1024, axis="dcn")
        assert model.t_link(1024, hops=3, axis="dcn") == pytest.approx(
            base + 2 * 1e-4
        )

    def test_model_axis_binding(self):
        model = PerfModel(self._params(), axis="dcn")
        assert model.t_link(1024) == pytest.approx(9e-4)
        comm = Communicator(axis_name="dcn", params=self._params())
        assert comm.model.axis == "dcn"

    def test_selection_can_flip_per_axis(self):
        # a dense 8-byte block inside a 64-byte Subarray extent, repeated
        # twice: bounding ships the 72-byte window with zero staging,
        # the pack strategies ship 16 exact bytes.  On a fast axis the
        # free pack wins it for bounding; on a slow, byte-steep DCN axis
        # the 4.5x over-transfer must flip the selection to a pack path.
        p = SystemParams(
            name="flip",
            wire_tables={
                "ici": ((0.0, 1e-9), (30.0, 1e-9)),
                "dcn": ((0.0, 1e-9), (4.0, 1e-9), (6.0, 6e-2), (30.0, 7e-2)),
            },
            wire_fits={"ici": (1e-9, 1e12), "dcn": (1e-9, 1e6)},
        )
        reg = TypeRegistry()
        ct = reg.commit(Subarray((64,), (8,), (0,), BYTE))
        from repro.comm.api import BOUNDING

        assert BOUNDING.wire_bytes(ct, 2) == 72 > ct.packed_extent(2) == 16
        fast = PerfModel(p, axis="ici").select(ct, incount=2).strategy
        slow = PerfModel(p, axis="dcn").select(ct, incount=2).strategy
        assert fast == "bounding"
        assert slow != "bounding"


PER_AXIS_SWEEP_CODE = r"""
from repro.measure import calibrate_params, fit_latency_bandwidth
from repro.measure.bench import REDUCED_TOTAL_BYTES, measure_wire_tables

tables = measure_wire_tables({"ici": 2, "dcn": 2},
                             total_bytes=REDUCED_TOTAL_BYTES, iters=1)
assert set(tables) == {"ici", "dcn"}
for ax, rows in tables.items():
    assert len(rows) == len(REDUCED_TOTAL_BYTES)
    assert all(sec > 0 for _, sec in rows)
params = calibrate_params(reduced=True, iters=1,
                          mesh_axes={"ici": 2, "dcn": 2})
assert set(params.wire_tables) == {"ici", "dcn"}
assert set(params.wire_fits) == {"ici", "dcn"}
from repro.comm import PerfModel
m = PerfModel(params, axis="ici")
assert m.t_link(4096) > 0
print("AXES_OK")
"""


@pytest.mark.slow
def test_per_axis_wire_sweep_on_mesh():
    out = run_with_devices(PER_AXIS_SWEEP_CODE, ndev=4)
    assert "AXES_OK" in out


# ===========================================================================
# store format compatibility
# ===========================================================================

class TestStoreFormats:
    def test_format2_envelope_still_loads(self, tmp_path):
        from repro.measure import ParamsStore
        from repro.measure.fingerprint import system_fingerprint

        from repro.measure import STORE_FORMAT

        store = ParamsStore(tmp_path)
        out = store.save(SystemParams(name="x"))
        d = json.loads(out.read_text())
        assert d["format"] == STORE_FORMAT == 6
        d["format"] = 2  # what a pre-per-axis envelope looks like
        d["params"].pop("wire_tables", None)
        d["params"].pop("wire_fits", None)
        d["params"].pop("stencil_table", None)
        d["params"].pop("link_tables", None)
        d["params"].pop("link_fits", None)
        out.write_text(json.dumps(d))
        got = store.load()
        assert got is not None and got.name == "x"
        assert got.wire_tables is None

    def test_ci_params_still_loadable(self):
        from repro.measure import load_ci_params

        params = load_ci_params()
        assert params.pack_table and params.wire_table

    def test_unknown_format_refused(self, tmp_path):
        from repro.measure import ParamsStore

        store = ParamsStore(tmp_path)
        out = store.save(SystemParams(name="x"))
        d = json.loads(out.read_text())
        d["format"] = 1
        out.write_text(json.dumps(d))
        assert store.load() is None


# ===========================================================================
# production communicator (train/serve wiring)
# ===========================================================================

class TestProductionCommunicator:
    def test_second_run_pins_decisions(self, tmp_path, monkeypatch):
        import repro.measure.store as store_mod
        from repro.measure.production import production_communicator

        monkeypatch.setattr(
            store_mod, "calibrate_params",
            lambda name=None, reduced=False: SystemParams(name="fake"),
        )
        dt = Vector(4096, 8, 4096, BYTE)

        comm1, save1 = production_communicator(tmp_path, axis_name="data")
        first = comm1.select(comm1.commit(dt)).name
        assert len(comm1.model.decisions) == 1
        save1()

        comm2, _ = production_communicator(tmp_path, axis_name="data")
        dc2 = comm2.model.decisions
        assert len(dc2) == 1  # loaded from disk, model not consulted
        assert comm2.select(comm2.commit(dt)).name == first
        assert dc2.pinned_hits >= 1

    def test_no_calibrate_falls_back_to_analytic(self, tmp_path, monkeypatch):
        from repro.measure import production

        # the analytic table describes a v5e: it stands in for a missing
        # calibration there, and nowhere else
        with pytest.raises(RuntimeError, match="TPU v5 lite"):
            production.production_communicator(tmp_path, calibrate=False)

        class V5e:
            device_kind = production.V5E_DEVICE_KIND

        monkeypatch.setattr(jax, "devices", lambda *a: [V5e()])
        comm, _ = production.production_communicator(tmp_path, calibrate=False)
        assert comm.model.params.name == TPU_V5E.name

    def test_train_loop_reports_comm_stats(self, tmp_path):
        from repro.configs.base import ModelConfig
        from repro.launch.train import train

        cfg = ModelConfig(
            name="tiny", family="dense", num_layers=1, d_model=32,
            num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=128,
            remat=False,
        )
        comm = Communicator(axis_name="data")
        out = train(cfg, steps=1, seq_len=8, global_batch=2,
                    ckpt_dir=str(tmp_path / "ckpt"), comm=comm)
        assert out["comm_stats"]["wire_ops"] == comm.wire_ops


def test_native_gate_follows_the_backend():
    """XLA:CPU has no ragged-all-to-all emitter, so on CPU devices the
    planner must not pick the native schedule; asked for it, it does."""
    assert not has_ragged_all_to_all()
    assert not has_ragged_all_to_all(jax.devices())
    # two delta classes of unequal size on 2 ranks: fused, but a uniform
    # collective would pad, so the exact ladder is grouped unless native
    sizes = (8, 16)
    perms = (((0, 1), (1, 0)), ((0, 0), (1, 1)))
    assert plan_wire(sizes, perms).schedule == "grouped"
    assert plan_wire(sizes, perms, native=True).schedule == "ragged"
