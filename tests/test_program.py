"""Tests: the deep-halo HaloProgram layer (ISSUE 4).

Covers the per-dimension stencil kernels (shrinking valid region, no
symmetric-radius guard), HaloProgram bit-exactness against the naive
per-step reference for s in {1,2,3} x per-dim radii (2,1,1), the
``price_program`` oracle on the CI-pinned params, ``--halo-steps auto``
pinning through the DecisionCache, the model-priced wire-schedule
choice, the per-block Int8Wire format, and the (gated) native ragged
collective integration.
"""

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
    Int8Wire,
    PerfModel,
    SystemParams,
    TPU_V5E,
    collective_payload_bytes,
    reschedule,
)
from repro.core import BYTE, FLOAT, Subarray
from repro.halo import (
    HaloSpec,
    STENCIL26,
    StencilOp,
    build_halo_program,
    cycle_radii,
    get_default_halo_steps,
    halo_exchange,
    op_sequence,
    program_fingerprint,
    set_default_halo_steps,
    stencil_apply,
    stencil_cycle,
    stencil_steps,
)
from repro.measure import DecisionCache, load_ci_params
from tests._subproc import run_with_devices


def _mesh1(axis="ranks"):
    return Mesh(np.array(jax.devices()[:1]), (axis,))


def _stencil_np(a, op):
    """Periodic numpy oracle for one StencilOp application."""
    acc = np.zeros_like(a)
    for d in op.offsets:
        acc += np.roll(a, tuple(-x for x in d), axis=(0, 1, 2))
    w = np.float32(op.weight)
    return (np.float32(1) - w) * a + (w / np.float32(op.nneighbors)) * acc


# ===========================================================================
# per-dimension stencil kernels
# ===========================================================================

class TestStencilOp:
    def test_offsets_and_radii(self):
        assert STENCIL26.nneighbors == 26
        assert len(STENCIL26.offsets) == 26
        op = StencilOp((2, 1, 1))
        assert op.nneighbors == 5 * 3 * 3 - 1 == len(op.offsets)
        assert op.halo_radii(3) == (6, 3, 3)
        with pytest.raises(ValueError, match="positive"):
            StencilOp((0, 1, 1))

    def test_apply_validates_valid_depth(self):
        spec = HaloSpec(grid=(1, 1, 1), interior=(4, 4, 4), radius=1)
        x = jnp.zeros(spec.alloc, jnp.float32)
        with pytest.raises(ValueError, match="shallower"):
            stencil_apply(x, spec, valid=(0, 0, 0))
        with pytest.raises(ValueError, match="exhaust"):
            stencil_steps(x, spec, steps=2)

    def test_per_dim_stencil_matches_periodic_oracle(self):
        """Asymmetric radii (2,1,1), two fused steps on one exchange, on
        the single-rank periodic domain — the scalar_radius guard is
        gone and the per-dim path must match the roll oracle."""
        op = StencilOp((2, 1, 1))
        spec = HaloSpec(grid=(1, 1, 1), interior=(8, 7, 6),
                        radius=op.halo_radii(2))
        rz, ry, rx = spec.radii
        nz, ny, nx = spec.interior
        comm = Communicator(axis_name="ranks")
        rng = np.random.default_rng(0)
        g = rng.normal(size=spec.interior).astype(np.float32)
        local = np.zeros(spec.alloc, np.float32)
        local[rz:rz + nz, ry:ry + ny, rx:rx + nx] = g

        def it(x):
            x = halo_exchange(x, spec, comm, "ranks")
            return stencil_steps(x, spec, 2, op)

        fn = jax.jit(shard_map(it, mesh=_mesh1(), in_specs=P(),
                               out_specs=P(), check_vma=False))
        out = np.asarray(fn(jnp.asarray(local)))
        want = _stencil_np(_stencil_np(g, op), op)
        np.testing.assert_allclose(
            out[rz:rz + nz, ry:ry + ny, rx:rx + nx], want,
            rtol=2e-6, atol=2e-6,
        )


# ===========================================================================
# HaloProgram: build, validate, price, pin
# ===========================================================================

class TestBuildProgram:
    def test_fixed_steps_and_geometry(self):
        comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"))
        prog = build_halo_program((2, 2, 2), (6, 5, 4), comm, steps=2)
        assert prog.steps == 2
        assert prog.spec.radii == (2, 2, 2)
        assert prog.exchanges_per_step == 0.5
        assert prog.plan.wire_bytes == sum(
            ct.packed_extent() for ct in prog.plan.send_cts
        )

    def test_infeasible_depth_raises(self):
        comm = Communicator(axis_name="ranks")
        with pytest.raises(ValueError, match="cannot host"):
            build_halo_program((2, 2, 2), (4, 4, 4), comm, steps=5)

    def test_default_steps_follow_process_setting(self):
        comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"))
        before = get_default_halo_steps()
        try:
            set_default_halo_steps(2)
            prog = build_halo_program((2, 2, 2), (6, 5, 4), comm)
            assert prog.steps == 2
        finally:
            set_default_halo_steps(before)

    def test_fingerprint_content_keyed(self):
        a = program_fingerprint((2, 2, 2), (6, 5, 4), STENCIL26, FLOAT)
        b = program_fingerprint((2, 2, 2), (6, 5, 4), STENCIL26, FLOAT)
        c = program_fingerprint((2, 2, 2), (6, 5, 4), StencilOp((2, 1, 1)),
                                FLOAT)
        assert a == b != c

    def test_price_program_oracle_on_ci_params(self):
        """The auto chooser must never select a depth whose predicted
        per-step cost exceeds step-per-exchange, on the CI-pinned
        measured tables (regression oracle for the model)."""
        comm = Communicator(axis_name="ranks", params=load_ci_params(),
                            policy=FixedPolicy("rows"))
        prog = build_halo_program((2, 2, 2), (8, 8, 8), comm, steps="auto")
        assert prog.candidates, "auto must price the candidate depths"
        by_steps = {e.steps: e for e in prog.candidates}
        assert 1 in by_steps
        assert prog.estimate.per_step <= by_steps[1].per_step
        # deeper halos must price strictly more wire bytes per exchange
        wire = [by_steps[s].wire_bytes for s in sorted(by_steps)]
        assert wire == sorted(wire) and wire[0] < wire[-1]

    def test_auto_choice_pinned_across_processes(self):
        dc = DecisionCache()
        comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"),
                            decisions=dc)
        prog = build_halo_program((2, 2, 2), (6, 5, 4), comm, steps="auto")
        assert not prog.pinned
        rows = [d for d in dc.log if d.strategy.startswith("program/s=")]
        assert len(rows) == 1
        assert rows[0].strategy == f"program/s={prog.steps}"
        assert rows[0].wire_bytes == prog.estimate.wire_bytes
        assert f"s={prog.steps}:" in rows[0].signature

        # "another process": the decision file round-trips and pins
        dc2 = DecisionCache.from_json(dc.to_json())
        comm2 = Communicator(axis_name="ranks", policy=FixedPolicy("rows"),
                             decisions=dc2)
        prog2 = build_halo_program((2, 2, 2), (6, 5, 4), comm2, steps="auto")
        assert prog2.pinned
        assert prog2.steps == prog.steps
        assert dc2.pinned_hits >= 1
        # pinned path prices nothing: no second program row recorded
        assert len([d for d in dc2.log
                    if d.strategy.startswith("program/s=")]) == 1

    def test_pin_beyond_max_steps_is_repriced(self):
        """A pin recorded under a looser cap must not smuggle a deeper
        halo past this caller's max_steps."""
        dc = DecisionCache()
        comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"),
                            decisions=dc)
        prog = build_halo_program((2, 2, 2), (6, 5, 4), comm, steps="auto")
        assert prog.steps > 1  # analytic latency dominates: fuses deeper
        cap = prog.steps - 1
        dc2 = DecisionCache.from_json(dc.to_json())
        comm2 = Communicator(axis_name="ranks", policy=FixedPolicy("rows"),
                             decisions=dc2)
        prog2 = build_halo_program((2, 2, 2), (6, 5, 4), comm2,
                                   steps="auto", max_steps=cap)
        assert not prog2.pinned
        assert prog2.steps <= cap

    def test_production_communicator_installs_halo_default(self, tmp_path):
        from repro.measure.production import production_communicator

        before = get_default_halo_steps()
        try:
            comm, _ = production_communicator(tmp_path, params=TPU_V5E,
                                              halo_steps=2)
            assert get_default_halo_steps() == 2
            prog = build_halo_program((2, 2, 2), (6, 5, 4), comm)
            assert prog.steps == 2
        finally:
            set_default_halo_steps(before)


# ===========================================================================
# heterogeneous op cycles (ISSUE 5)
# ===========================================================================

#: the predictor/corrector pair with unequal per-dimension radii used
#: throughout the cycle tests
CYCLE_OPS = (StencilOp((2, 1, 1), weight=0.5), StencilOp((1, 1, 1), weight=0.25))


class TestCyclePrograms:
    def test_cycle_radii_and_sequence(self):
        assert cycle_radii(CYCLE_OPS) == (3, 2, 2)
        assert cycle_radii(STENCIL26) == (1, 1, 1)
        seq = op_sequence(CYCLE_OPS, 3)
        assert len(seq) == 6
        assert seq[0] is CYCLE_OPS[0] and seq[1] is CYCLE_OPS[1]
        assert seq[4] is CYCLE_OPS[0]
        with pytest.raises(ValueError, match="repeats"):
            op_sequence(CYCLE_OPS, 0)

    def test_stencil_cycle_matches_periodic_oracle(self):
        """Two repeats of the [predictor, corrector] cycle on one
        exchange, single periodic rank, vs the roll oracle applied
        op-by-op."""
        spec = HaloSpec(grid=(1, 1, 1), interior=(8, 7, 6),
                        radius=tuple(2 * r for r in cycle_radii(CYCLE_OPS)))
        rz, ry, rx = spec.radii
        nz, ny, nx = spec.interior
        comm = Communicator(axis_name="ranks")
        rng = np.random.default_rng(0)
        g = rng.normal(size=spec.interior).astype(np.float32)
        local = np.zeros(spec.alloc, np.float32)
        local[rz:rz + nz, ry:ry + ny, rx:rx + nx] = g

        def it(x):
            x = halo_exchange(x, spec, comm, "ranks")
            return stencil_cycle(x, spec, CYCLE_OPS, 2)

        fn = jax.jit(shard_map(it, mesh=_mesh1(), in_specs=P(),
                               out_specs=P(), check_vma=False))
        out = np.asarray(fn(jnp.asarray(local)))
        want = g
        for op in op_sequence(CYCLE_OPS, 2):
            want = _stencil_np(want, op)
        np.testing.assert_allclose(
            out[rz:rz + nz, ry:ry + ny, rx:rx + nx], want,
            rtol=2e-6, atol=2e-6,
        )

    def test_cycle_exhaustion_validated(self):
        spec = HaloSpec(grid=(1, 1, 1), interior=(8, 8, 8),
                        radius=cycle_radii(CYCLE_OPS))
        x = jnp.zeros(spec.alloc, jnp.float32)
        with pytest.raises(ValueError, match="exhaust"):
            stencil_cycle(x, spec, CYCLE_OPS, 2)

    def test_cycle_program_geometry(self):
        comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"))
        prog = build_halo_program((2, 2, 2), (8, 6, 6), comm, ops=CYCLE_OPS,
                                  steps=2, schedule_policy="exact")
        assert prog.spec.radii == (6, 4, 4)
        assert prog.cycle_len == 2
        assert prog.applications == 4
        assert prog.exchanges_per_step == 0.25
        assert prog.exchanges_per_cycle == 0.5
        assert prog.plan.wire_bytes == sum(
            ct.packed_extent() for ct in prog.plan.send_cts
        )
        with pytest.raises(ValueError, match="cycle"):
            prog.op  # a 2-op program has no single 'the' op

    def test_cycle_infeasible_depth_raises(self):
        comm = Communicator(axis_name="ranks")
        with pytest.raises(ValueError, match="cannot host"):
            build_halo_program((2, 2, 2), (8, 6, 6), comm, ops=CYCLE_OPS,
                               steps=3)  # 3 * (3,2,2) exceeds (8,6,6)

    def test_cycle_fingerprint_order_sensitive_and_v1_compatible(self):
        a, b = CYCLE_OPS
        fab = program_fingerprint((2, 2, 2), (8, 6, 6), (a, b), FLOAT)
        fba = program_fingerprint((2, 2, 2), (8, 6, 6), (b, a), FLOAT)
        assert fab != fba  # the shrinking schedule is order-sensitive
        # single-op cycles keep the v1 key: decision files recorded
        # before cycles existed still pin
        f1 = program_fingerprint((2, 2, 2), (8, 6, 6), a, FLOAT)
        f1_seq = program_fingerprint((2, 2, 2), (8, 6, 6), (a,), FLOAT)
        assert f1 == f1_seq != fab

    def test_cycle_price_oracle_on_ci_params(self):
        """The auto chooser on the CI-pinned measured tables: never a
        repeat count predicted worse per application than s=1, per-op
        redundant terms split and summing to t_redundant, wire bytes
        strictly growing with depth."""
        comm = Communicator(axis_name="ranks", params=load_ci_params(),
                            policy=FixedPolicy("rows"))
        prog = build_halo_program((2, 2, 2), (9, 8, 8), comm, ops=CYCLE_OPS,
                                  steps="auto", schedule_policy="exact")
        assert prog.candidates
        by_steps = {e.steps: e for e in prog.candidates}
        assert 1 in by_steps
        assert prog.estimate.per_step <= by_steps[1].per_step
        for est in prog.candidates:
            assert est.cycle_len == 2
            assert est.applications == 2 * est.steps
            assert len(est.op_redundant) == 2
            assert est.t_redundant == pytest.approx(sum(est.op_redundant))
        wire = [by_steps[s].wire_bytes for s in sorted(by_steps)]
        assert wire == sorted(wire) and wire[0] < wire[-1]

    def test_cycle_auto_pinned_across_processes(self):
        """Pinned cycle Decision replay: the program/s=N row records the
        cycle signature, round-trips through JSON, and pins the repeat
        count in a fresh process."""
        dc = DecisionCache()
        comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"),
                            decisions=dc)
        prog = build_halo_program((2, 2, 2), (8, 6, 6), comm, ops=CYCLE_OPS,
                                  steps="auto")
        assert not prog.pinned
        rows = dc.program_rows()
        assert len(rows) == 1
        assert rows[0].strategy == f"program/s={prog.steps}"
        assert rows[0].fingerprint == prog.fingerprint
        assert "cycle=[2x1x1w0.5,1x1x1w0.25]" in rows[0].signature

        dc2 = DecisionCache.from_json(dc.to_json())
        comm2 = Communicator(axis_name="ranks", policy=FixedPolicy("rows"),
                             decisions=dc2)
        prog2 = build_halo_program((2, 2, 2), (8, 6, 6), comm2, ops=CYCLE_OPS,
                                   steps="auto")
        assert prog2.pinned
        assert prog2.steps == prog.steps
        assert len(dc2.program_rows()) == 1
        # a different cycle (swapped order) must NOT ride that pin
        a, b = CYCLE_OPS
        prog3 = build_halo_program((2, 2, 2), (8, 6, 6), comm2, ops=(b, a),
                                   steps="auto")
        assert not prog3.pinned

    def test_price_program_cycle_normalizes_scalar_form(self):
        """A one-op cycle prices identically through the scalar and the
        sequence signatures."""
        from repro.comm import PerfModel, plan_wire

        model = PerfModel(load_ci_params())
        plan = plan_wire((256,), (((0, 0),),), native=False)
        one = model.price_program(plan, (8, 8, 8), (1, 1, 1), 26, 2)
        seq = model.price_program(plan, (8, 8, 8), [(1, 1, 1)], [26], 2)
        assert one.total == seq.total
        assert one.per_step == seq.per_step
        assert one.applications == seq.applications == 2
        with pytest.raises(ValueError, match="match the cycle"):
            model.price_program(plan, (8, 8, 8), [(1, 1, 1)], [26, 8], 2)


CYCLE_DEEP_CODE = r"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from repro.comm import Communicator, FixedPolicy, collective_payload_bytes
from repro.halo import StencilOp, build_halo_program, make_program_step

# unequal per-dim radii: cycle radii (3, 2, 2); s in {1,2,3} all fit the
# (9, 6, 6) interior and divide 6 total cycle repeats
ops = [StencilOp((2, 1, 1), weight=0.5), StencilOp((1, 1, 1), weight=0.25)]
grid, interior = (2, 2, 2), (9, 6, 6)
nz, ny, nx = interior
R = 8
mesh = Mesh(np.array(jax.devices()), ("ranks",))
field = np.random.default_rng(0).normal(size=(R, nz, ny, nx)).astype(np.float32)

def run(prog, comm, state_field, iters):
    fn = make_program_step(prog, comm, mesh)
    az, ay, ax = prog.spec.alloc
    rz, ry, rx = prog.spec.radii
    state = np.zeros((R, az, ay, ax), np.float32)
    state[:, rz:rz+nz, ry:ry+ny, rx:rx+nx] = state_field
    x = jnp.asarray(state.reshape(R * az, ay, ax))
    for _ in range(iters):
        x = fn(x)
    return np.asarray(x).reshape(R, az, ay, ax)[
        :, rz:rz+nz, ry:ry+ny, rx:rx+nx]

TOTAL = 6  # cycle repeats in every variant
interiors = {}
for s in (1, 2, 3):
    comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"))
    prog = build_halo_program(grid, interior, comm, ops=ops, steps=s,
                              schedule_policy="exact")
    assert prog.spec.radii == (3 * s, 2 * s, 2 * s)
    fn = make_program_step(prog, comm, mesh)
    az, ay, ax = prog.spec.alloc
    counts = collective_payload_bytes(fn, jnp.zeros((R * az, ay, ax), jnp.float32))
    assert counts["ops"] == prog.plan.wire.wire_ops, (s, counts)
    assert counts["total"] == prog.plan.wire_bytes, (s, counts)
    interiors[s] = run(prog, comm, field, TOTAL // s)

np.testing.assert_array_equal(interiors[1], interiors[2])
np.testing.assert_array_equal(interiors[1], interiors[3])

# the exchange-per-application reference: one single-op program per op,
# exchanged before EVERY application
comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"))
ref_progs = [build_halo_program(grid, interior, comm, ops=[op], steps=1,
                                schedule_policy="exact") for op in ops]
ref = field
for _ in range(TOTAL):
    for prog in ref_progs:
        ref = run(prog, comm, ref, 1)
np.testing.assert_array_equal(interiors[1], ref)
print("CYCLE_DEEP_OK")
"""


@pytest.mark.slow
def test_cycle_bit_exact_s123_vs_per_step_reference():
    out = run_with_devices(CYCLE_DEEP_CODE, ndev=8)
    assert "CYCLE_DEEP_OK" in out


DEEP_HALO_CODE = r"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from repro.comm import Communicator, FixedPolicy, collective_payload_bytes
from repro.halo import StencilOp, build_halo_program, make_program_step

# per-dim stencil radii (2,1,1); depths 1..3 all divide 6 total steps
op = StencilOp((2, 1, 1))
grid, interior = (2, 2, 2), (6, 4, 4)
nz, ny, nx = interior
R = 8
mesh = Mesh(np.array(jax.devices()), ("ranks",))
field = np.random.default_rng(0).normal(size=(R, nz, ny, nx)).astype(np.float32)

TOTAL = 6
interiors = {}
for s in (1, 2, 3):
    comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"))
    prog = build_halo_program(grid, interior, comm, op=op, steps=s,
                              schedule_policy="exact")
    assert prog.spec.radii == (2 * s, s, s)
    fn = make_program_step(prog, comm, mesh)
    az, ay, ax = prog.spec.alloc
    rz, ry, rx = prog.spec.radii
    state = np.zeros((R, az, ay, ax), np.float32)
    state[:, rz:rz+nz, ry:ry+ny, rx:rx+nx] = field
    x = jnp.asarray(state.reshape(R * az, ay, ax))
    # one fused exchange per iteration of s stencil steps
    counts = collective_payload_bytes(fn, x)
    assert counts["ops"] == prog.plan.wire.wire_ops, (s, counts)
    assert counts["total"] == prog.plan.wire_bytes, (s, counts)
    out = x
    for _ in range(TOTAL // s):
        out = fn(out)
    interiors[s] = np.asarray(out).reshape(R, az, ay, ax)[
        :, rz:rz+nz, ry:ry+ny, rx:rx+nx]

# the naive per-step reference is s=1; every depth must be bit-exact
np.testing.assert_array_equal(interiors[1], interiors[2])
np.testing.assert_array_equal(interiors[1], interiors[3])
print("DEEP_HALO_OK")
"""


@pytest.mark.slow
def test_deep_halo_bit_exact_s123_per_dim_radii():
    out = run_with_devices(DEEP_HALO_CODE, ndev=8)
    assert "DEEP_HALO_OK" in out


# ===========================================================================
# model-priced wire-schedule choice (ROADMAP open item)
# ===========================================================================

def _two_group_case(comm):
    n = 4
    cts = [
        comm.commit(Subarray((64,), (8,), (0,), BYTE)),
        comm.commit(Subarray((64,), (8,), (16,), BYTE)),
    ]
    ring = tuple((r, (r + 1) % n) for r in range(n))
    back = tuple((r, (r - 1) % n) for r in range(n))
    return cts, (ring, back)


class TestModelPricedSchedule:
    def test_latency_heavy_params_pick_uniform(self):
        # 2 delta classes: grouped pays an extra collective launch;
        # the padding (16 extra bytes) is nearly free on the analytic
        # bandwidth — the model must buy the single padded collective
        dc = DecisionCache()
        p = SystemParams(name="lat", ici_latency=1e-3)
        comm = Communicator(axis_name="x", params=p, decisions=dc)
        cts, perms = _two_group_case(comm)
        _, plan = comm.plan_neighbor(cts, perms, schedule_policy="model")
        assert plan.schedule == "uniform"
        assert plan.wire_ops == 1
        assert plan.issued_bytes == plan.nranks * plan.seg_bytes == 32
        assert plan.padding_bytes == 16
        # the decision row records the chosen schedule AND the prices of
        # the alternatives the model rejected
        rows = [d for d in dc.log if d.strategy == "wire/uniform"]
        assert len(rows) == 1
        assert "priced[" in rows[0].signature
        assert "grouped=" in rows[0].signature
        assert rows[0].wire_bytes == 32

    def test_byte_steep_wire_table_keeps_grouped(self):
        # measured table where 32 B costs 10 ms and 16 B costs 1 ns:
        # padding is ruinous, launches are free — grouped must survive
        p = SystemParams(
            name="steep",
            wire_table=((0.0, 1e-9), (4.0, 1e-9), (5.0, 1e-2), (30.0, 1e-1)),
            wire_latency=1e-9,
        )
        comm = Communicator(axis_name="x", params=p)
        cts, perms = _two_group_case(comm)
        _, plan = comm.plan_neighbor(cts, perms, schedule_policy="model")
        assert plan.schedule == "grouped"
        assert plan.issued_bytes == plan.wire_bytes == 16

    def test_default_policy_is_model(self):
        # ROADMAP flip: plan_neighbor defaults to the model-priced
        # schedule choice — on latency-heavy analytic params the two
        # delta classes fuse into one padded uniform collective without
        # anyone passing schedule_policy
        from repro.comm import DEFAULT_SCHEDULE_POLICY

        assert DEFAULT_SCHEDULE_POLICY == "model"
        p = SystemParams(name="lat", ici_latency=1e-3)
        comm = Communicator(axis_name="x", params=p)
        cts, perms = _two_group_case(comm)
        _, plan = comm.plan_neighbor(cts, perms)
        assert plan.schedule == "uniform"
        # the padding the model may buy is bounded by the row-equalized
        # layout (the CI padded-allowance gate asserts the same bound)
        assert plan.issued_bytes <= plan.nranks * plan.seg_bytes

    def test_exact_policy_selectable(self):
        # the byte-exact ladder stays selectable per plan (the strict
        # wire-bytes CI gates request it)
        comm = Communicator(axis_name="x")
        cts, perms = _two_group_case(comm)
        _, plan = comm.plan_neighbor(cts, perms, schedule_policy="exact")
        assert plan.schedule == "grouped"
        assert plan.issued_bytes == plan.wire_bytes
        with pytest.raises(ValueError, match="schedule_policy"):
            comm.plan_neighbor(cts, perms, schedule_policy="nope")

    def test_large_grid_threshold_survives_model_pricing(self):
        # past rank_factor * ngroups the fused layouts are mostly dead
        # rows/metadata — a cost t_link cannot see — so the model
        # chooser must not offer them even when ragged/uniform look
        # cheap on paper
        from repro.comm import plan_wire

        n = 32
        ring = tuple((r, (r + 1) % n) for r in range(n))
        plan = plan_wire((64,), (ring,), native=False)
        assert plan.schedule == "grouped"
        model = PerfModel(SystemParams(name="lat", ici_latency=1e-3))
        new_plan, costs = model.choose_wire_schedule(plan, native=True)
        assert set(costs) == {"grouped"}
        assert new_plan.schedule == "grouped"

    def test_reschedule_validation_and_fingerprint(self):
        from repro.comm import plan_wire

        plan = plan_wire((8, 4), (((0, 0),), ((0, 0),)), native=False)
        same = reschedule(plan, plan.schedule)
        assert same is plan
        with pytest.raises(ValueError, match="unknown wire schedule"):
            reschedule(plan, "carrier-pigeon")
        # a rescheduled plan keeps the layout but re-fingerprints
        grouped = reschedule(plan, "grouped")
        assert grouped.segments == plan.segments
        assert grouped.fingerprint != plan.fingerprint

    def test_model_scheduled_uniform_executes_correctly(self):
        # the rescheduled plan must still move the right bytes end-to-end
        p = SystemParams(name="lat", ici_latency=1e-3)
        comm = Communicator(axis_name="x", params=p)
        send_cts = [
            comm.commit(Subarray((64,), (8,), (0,), BYTE)),
            comm.commit(Subarray((64,), (4,), (16,), BYTE)),
        ]
        recv_cts = [
            comm.commit(Subarray((64,), (8,), (32,), BYTE)),
            comm.commit(Subarray((64,), (4,), (48,), BYTE)),
        ]
        perms = [[(0, 0)], [(0, 0)]]
        strats, plan = comm.plan_neighbor(send_cts, perms,
                                          schedule_policy="model")

        def body(b):
            return comm.neighbor_alltoallv(
                b, send_cts, recv_cts, perms, plan=plan, strategies=strats
            )

        fn = jax.jit(shard_map(body, mesh=_mesh1("x"), in_specs=P(),
                               out_specs=P(), check_vma=False))
        out = np.asarray(fn(jnp.arange(64, dtype=jnp.uint8)))
        want = np.arange(64, dtype=np.uint8)
        want[32:40] = want[0:8]
        want[48:52] = want[16:20]
        np.testing.assert_array_equal(out, want)
        counts = collective_payload_bytes(fn, jnp.arange(64, dtype=jnp.uint8))
        assert counts["ops"] == plan.wire_ops
        assert counts["total"] == plan.issued_bytes


# ===========================================================================
# Int8Wire per-block scales
# ===========================================================================

class TestInt8PerBlock:
    def _big_ct(self, comm):
        # 20 rows x 20 floats = 400 member floats -> 2 blocks of <=256
        # (Subarray dims innermost-first: rows 4..23, cols 0..19)
        return comm.commit(Subarray((32, 32), (20, 20), (0, 4), FLOAT))

    def test_wire_bytes_grow_per_block(self):
        comm = Communicator(axis_name="x")
        ct = self._big_ct(comm)
        nfloats = ct.size // 4
        assert nfloats == 400
        assert INT8_WIRE.wire_bytes(ct) == 2 * 4 + nfloats
        legacy = Int8Wire(block_elems=None)
        assert legacy.wire_bytes(ct) == 4 + nfloats
        # small payloads: identical format (one block == one payload)
        small = comm.commit(Subarray((16, 16), (4, 8), (2, 0), FLOAT))
        assert INT8_WIRE.wire_bytes(small) == legacy.wire_bytes(small)

    def test_per_block_scale_widens_usable_range(self):
        """A payload mixing tiny and huge magnitudes: one payload-wide
        scale crushes the tiny block to zero; per-block scales keep it."""
        comm = Communicator(axis_name="x",
                            policy=FixedPolicy(INT8_WIRE.name))
        ct = self._big_ct(comm)
        src = np.zeros((32, 32), np.float32)
        rng = np.random.default_rng(0)
        # region rows 4..23, cols 0..19, packed row-major: block 0 is
        # floats 0..255 (rows 4..15 + most of straddling row 16), block 1
        # is the rest.  Tiny magnitudes through row 16, huge after.
        src[4:17, 0:20] = rng.uniform(1e-3, 2e-3, size=(13, 20))
        src[17:24, 0:20] = rng.uniform(500.0, 1000.0, size=(7, 20))

        def body(b):
            return comm.sendrecv(b, jnp.zeros_like(b), ct, [(0, 0)])

        fn = jax.jit(shard_map(body, mesh=_mesh1("x"), in_specs=P(),
                               out_specs=P(), check_vma=False))
        out = np.asarray(fn(jnp.asarray(src)))
        # assert only the rows fully inside each block (row 16 straddles:
        # its tail rides block 1's huge scale and rounds to ~0)
        small = np.s_[4:16, 0:20]
        big = np.s_[17:24, 0:20]
        # per-block: the tiny block quantizes against its own max
        small_scale = np.abs(src[small]).max() / 127.0
        np.testing.assert_allclose(out[small], src[small],
                                   atol=small_scale / 2 + 1e-7)
        big_scale = np.abs(src[big]).max() / 127.0
        np.testing.assert_allclose(out[big], src[big],
                                   atol=big_scale / 2 + 1e-4)
        # a payload-wide scale could not represent the tiny block at all
        payload_scale = np.abs(src[4:24, 0:20]).max() / 127.0
        assert small_scale < payload_scale / 1000
        assert np.abs(out[small] - src[small]).max() < payload_scale / 100

    def test_legacy_per_payload_format_still_readable(self):
        comm = Communicator(axis_name="x")
        ct = self._big_ct(comm)
        rng = np.random.default_rng(1)
        src = np.zeros((32, 32), np.float32)
        src[4:24, 0:20] = rng.normal(size=(20, 20)).astype(np.float32)
        legacy = Int8Wire(block_elems=None)
        wire = legacy.pack(jnp.asarray(src), ct)
        assert wire.shape[0] == legacy.wire_bytes(ct)
        # the default (per-block) instance decodes the one-scale payload
        out = np.asarray(
            INT8_WIRE.unpack_wire(comm, jnp.zeros((32, 32), jnp.float32),
                                  wire, ct)
        )
        scale = np.abs(src[4:24, 0:20]).max() / 127.0
        np.testing.assert_allclose(out[4:24, 0:20], src[4:24, 0:20],
                                   atol=scale / 2 + 1e-7)

    def test_truncated_wire_refused(self):
        comm = Communicator(axis_name="x")
        ct = self._big_ct(comm)
        bad = jnp.zeros((4 * 3 + 400,), jnp.uint8)  # 3 scales for 2 blocks
        with pytest.raises(ValueError, match="scales"):
            INT8_WIRE.unpack_wire(comm, jnp.zeros((32, 32), jnp.float32),
                                  bad, ct)


# ===========================================================================
# RleWire: lossless zero-run wire compression
# ===========================================================================

class TestRleWire:
    def _ct(self, comm):
        return comm.commit(Subarray((32, 32), (16, 16), (4, 4), FLOAT))

    def test_wire_bytes_and_plan_accounting(self):
        from repro.comm import RLE_WIRE, RleWire

        comm = Communicator(axis_name="x",
                            policy=FixedPolicy(RleWire.name))
        ct = self._ct(comm)
        assert RLE_WIRE.wire_bytes(ct) == ct.size + 8
        assert RLE_WIRE.wire_segment(ct).nbytes == ct.size + 8
        # the WirePlan carries the capacity bytes (header included), and
        # the traced collective moves exactly that
        strats, plan = comm.plan_neighbor([ct], [[(0, 0)]],
                                          schedule_policy="exact")
        assert strats[0].name == RleWire.name
        assert plan.wire_bytes == ct.size + 8

        recv = comm.commit(Subarray((32, 32), (16, 16), (4, 4), FLOAT))

        def body(b):
            return comm.neighbor_alltoallv(
                b, [ct], [recv], [[(0, 0)]], plan=plan, strategies=strats
            )

        fn = jax.jit(shard_map(body, mesh=_mesh1("x"), in_specs=P(),
                               out_specs=P(), check_vma=False))
        counts = collective_payload_bytes(fn, jnp.zeros((32, 32), jnp.float32))
        assert counts["total"] == plan.issued_bytes == ct.size + 8

    def test_zero_run_payload_rides_rle_mode_exactly(self):
        from repro.comm import RLE_WIRE

        comm = Communicator(axis_name="x")
        ct = self._ct(comm)
        src = np.zeros((32, 32), np.float32)
        src[10:12, 4:20] = 3.25  # a few runs in a sea of zeros
        wire = RLE_WIRE.pack(jnp.asarray(src), ct)
        assert wire.shape[0] == RLE_WIRE.wire_bytes(ct)
        mode, nruns = np.asarray(wire[:8]).view(np.uint32)
        assert mode == 1  # fits the run capacity -> rle mode
        assert nruns <= ct.size // 5
        out = np.asarray(RLE_WIRE.unpack_wire(
            comm, jnp.zeros((32, 32), jnp.float32), wire, ct))
        # LOSSLESS: bit-exact, not allclose
        np.testing.assert_array_equal(out[4:20, 4:20], src[4:20, 4:20])

    def test_incompressible_payload_stored_exactly(self):
        from repro.comm import RLE_WIRE

        comm = Communicator(axis_name="x")
        ct = self._ct(comm)
        rng = np.random.default_rng(0)
        src = rng.normal(size=(32, 32)).astype(np.float32)
        wire = RLE_WIRE.pack(jnp.asarray(src), ct)
        mode, _ = np.asarray(wire[:8]).view(np.uint32)
        assert mode == 0  # too many runs -> stored-block fallback
        out = np.asarray(RLE_WIRE.unpack_wire(
            comm, jnp.zeros((32, 32), jnp.float32), wire, ct))
        np.testing.assert_array_equal(out[4:20, 4:20], src[4:20, 4:20])

    def test_end_to_end_sendrecv_both_modes(self):
        from repro.comm import RleWire

        comm = Communicator(axis_name="x", policy=FixedPolicy(RleWire.name))
        ct = self._ct(comm)

        def body(b):
            return comm.sendrecv(b, jnp.zeros_like(b), ct, [(0, 0)])

        fn = jax.jit(shard_map(body, mesh=_mesh1("x"), in_specs=P(),
                               out_specs=P(), check_vma=False))
        sparse = np.zeros((32, 32), np.float32)
        sparse[5, 5] = 1.0
        dense = np.random.default_rng(1).normal(size=(32, 32)).astype(np.float32)
        for src in (sparse, dense):
            out = np.asarray(fn(jnp.asarray(src)))
            np.testing.assert_array_equal(out[4:20, 4:20], src[4:20, 4:20])

    def test_selectable_only_with_probe_and_wire_only(self):
        from repro.comm import RLE_WIRE, default_registry

        assert RLE_WIRE.name in default_registry()
        # byte-exact in both modes, so the strategy is selectable — but
        # priced at CAPACITY (member + 8 B, strictly worse than rows)
        # unless the selection carries a payload probe, so the model
        # must still never auto-pick it without one
        assert RLE_WIRE.selectable
        assert RLE_WIRE.supports_varlen
        assert RLE_WIRE.wire_only
        comm = Communicator(axis_name="x")
        ct = self._ct(comm)
        assert comm.select(ct, wire=True).name != RLE_WIRE.name
        with pytest.raises(TypeError, match="wire-only"):
            RLE_WIRE.unpack(jnp.zeros(4), jnp.zeros(4, jnp.uint8), ct)

    def test_wrong_length_refused(self):
        from repro.comm import RLE_WIRE

        comm = Communicator(axis_name="x")
        ct = self._ct(comm)
        with pytest.raises(ValueError, match="rle wire"):
            RLE_WIRE.unpack_wire(comm, jnp.zeros((32, 32), jnp.float32),
                                 jnp.zeros((ct.size,), jnp.uint8), ct)


# ===========================================================================
# native ragged collective (gated integration test)
# ===========================================================================

RAGGED_NATIVE_CODE = r"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from repro.comm import Communicator, FixedPolicy, collective_payload_bytes
from repro.halo import HaloSpec, make_halo_plan, make_halo_step

spec = HaloSpec(grid=(2, 2, 2), interior=(6, 5, 4), radius=2)
r = spec.radius
nz, ny, nx = spec.interior
az, ay, ax = spec.alloc
R = spec.nranks
comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"))
plan = make_halo_plan(spec, comm)
# with the native collective available the 2x2x2 ladder must pick it
assert plan.wire.schedule == "ragged", plan.wire.schedule
assert plan.wire.wire_ops == 1

mesh = Mesh(np.array(jax.devices()), ("ranks",))
step = make_halo_step(spec, comm, mesh)

gz, gy, gx = 2 * nz, 2 * ny, 2 * nx
gvals = np.arange(gz * gy * gx, dtype=np.float32).reshape(gz, gy, gx)
locals_np = np.full((R, az, ay, ax), -1.0, np.float32)
for rank in range(R):
    cz, cy, cx = spec.coords(rank)
    locals_np[rank, r:r+nz, r:r+ny, r:r+nx] = gvals[
        cz*nz:(cz+1)*nz, cy*ny:(cy+1)*ny, cx*nx:(cx+1)*nx]
x0 = jnp.asarray(locals_np.reshape(R * az, ay, ax))

# byte accounting: ONE ragged collective moving exactly the plan bytes
counts = collective_payload_bytes(step, x0)
assert counts["ops"] == 1, counts
assert counts.get("ragged_all_to_all", 0) == plan.wire_bytes, counts
assert counts["total"] == plan.wire_bytes == sum(
    ct.packed_extent() for ct in plan.send_cts)

# bit-exactness: every halo cell equals the periodic global value
out = np.asarray(step(x0)).reshape(R, az, ay, ax)
for rank in range(R):
    cz, cy, cx = spec.coords(rank)
    zz = (np.arange(az) - r + cz * nz) % gz
    yy = (np.arange(ay) - r + cy * ny) % gy
    xx = (np.arange(ax) - r + cx * nx) % gx
    np.testing.assert_array_equal(out[rank], gvals[np.ix_(zz, yy, xx)],
                                  err_msg=f"rank {rank}")
print("RAGGED_NATIVE_OK")

# with the native collective available, the varlen (length-aware
# compressed) transport must prefer it too: a zero-heavy probed payload
# plans schedule=varlen on a fused layout and the traced exchange is
# ONE ragged_all_to_all moving exactly the stream bytes
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core import Subarray, FLOAT

vcomm = Communicator(axis_name="ranks")
vct = vcomm.commit(Subarray((32, 32), (16, 16), (4, 4), FLOAT))
vsrc = np.zeros((32, 32), np.float32)
vsrc[10, 6] = 3.0
vstrats, vplan = vcomm.plan_neighbor(
    [vct], [[(0, 0)]], probe=jnp.asarray(vsrc))
assert vplan.schedule == "varlen", vplan.schedule
assert vplan.fused, "varlen layout must stay native-ragged eligible"

def vbody(b):
    return vcomm.neighbor_alltoallv(
        b, [vct], [vct], [[(0, 0)]], plan=vplan, strategies=vstrats)

vfn = jax.jit(shard_map(
    vbody, mesh=Mesh(np.array(jax.devices()[:1]), ("ranks",)),
    in_specs=P(), out_specs=P(), check_vma=False))
vcounts = collective_payload_bytes(vfn, jnp.asarray(vsrc))
assert vcounts.get("ragged_all_to_all", 0) == vplan.effective_wire_bytes, vcounts
assert vcounts["total"] == vplan.issued_bytes < vplan.wire_bytes, vcounts
vout = np.asarray(vfn(jnp.asarray(vsrc)))
np.testing.assert_array_equal(vout[4:20, 4:20], vsrc[4:20, 4:20])
print("VARLEN_NATIVE_OK")
"""


@pytest.mark.slow
@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs a backend that runs lax.ragged_all_to_all natively "
           "(TPU); XLA:CPU has no emitter for it, so CPU plans take the "
           "grouped schedule (tests/test_tpu_compile.py compiles the "
           "native exchange for a described v5e instead)",
)
def test_native_ragged_schedule_end_to_end():
    out = run_with_devices(RAGGED_NATIVE_CODE, ndev=8)
    assert "RAGGED_NATIVE_OK" in out
    assert "VARLEN_NATIVE_OK" in out
