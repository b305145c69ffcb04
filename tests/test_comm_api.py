"""Unit tests: the Communicator API — strategy registry, policies,
request-based transfers, and the fused neighborhood alltoallv.

These are direct (non-hypothesis) tests; they run on a single CPU device
(self-permutes on a 1-rank mesh exercise the full pack -> wire -> unpack
machinery in-process)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.comm import (
    BaselinePolicy,
    Communicator,
    FixedPolicy,
    Interposer,
    MODES,
    Strategy,
    StrategyRegistry,
    as_communicator,
    default_registry,
    policy_for_mode,
    resolve_strategy,
)
from repro.comm.api import AUTO, BOUNDING, DMA, REF, ROWS, XLA, plan_neighbor_alltoallv
from repro.core import BYTE, Contiguous, Subarray, TypeRegistry, Vector
from repro.halo.exchange import DIRECTIONS, HaloSpec
from repro.kernels.ref import pack_ref


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("x",))


# ===========================================================================
# registry
# ===========================================================================

class TestRegistry:
    def test_default_registry_contents(self):
        names = default_registry().names()
        for s in (ROWS, DMA, XLA, REF, AUTO, BOUNDING):
            assert s.name in names

    def test_resolve(self):
        assert resolve_strategy(ROWS.name) is ROWS
        assert resolve_strategy(None) is AUTO
        assert resolve_strategy(DMA) is DMA

    def test_unknown_strategy_raises(self):
        with pytest.raises(ValueError, match="unknown strategy"):
            resolve_strategy("warp-drive")

    def test_duplicate_register_raises(self):
        reg = default_registry().copy()
        with pytest.raises(ValueError, match="already registered"):
            reg.register(type(ROWS)())

    def test_copy_is_isolated(self):
        reg = default_registry().copy()

        class Probe(Strategy):
            name = "probe"

        reg.register(Probe)
        assert "probe" in reg
        assert "probe" not in default_registry()

    def test_selectable_excludes_oracle_and_auto(self):
        sel = {s.name for s in default_registry().selectable()}
        assert REF.name not in sel
        assert AUTO.name not in sel
        assert BOUNDING.name in sel


class TestPluginSelection:
    def test_registered_plugin_wins_selection(self):
        class Teleport(Strategy):
            name = "teleport"

            def model_pack(self, model, ct, incount):
                return 0.0

            def model_unpack(self, model, ct, incount):
                return 0.0

            def wire_bytes(self, ct, incount=1):
                return 0

        reg = default_registry().copy()
        comm = Communicator(strategies=reg)
        ct = comm.commit(Vector(4096, 8, 4096, BYTE))
        before = comm.select(ct).name  # populate the selection cache
        assert before != "teleport"
        # registering a plugin must invalidate cached selections
        reg.register(Teleport())
        assert comm.select(ct).name == "teleport"
        # the default registry is untouched
        assert Communicator().select(ct).name != "teleport"

    def test_model_selects_among_registered(self):
        # with bounding removed from the registry, a dense contiguous
        # type must fall back to a pack-based strategy
        reg = StrategyRegistry((ROWS, DMA, XLA, REF, AUTO))
        comm = Communicator(strategies=reg)
        ct = comm.commit(Contiguous(1000, BYTE))
        assert comm.select(ct).name != BOUNDING.name
        assert Communicator().select(
            Communicator().commit(Contiguous(1000, BYTE))
        ).name == BOUNDING.name


# ===========================================================================
# policies / shim
# ===========================================================================

class TestPolicies:
    def test_policy_for_mode_rejects_unknown(self):
        with pytest.raises(ValueError):
            policy_for_mode("nope")

    def test_modes_cover_shim_surface(self):
        for mode in MODES:
            policy_for_mode(mode)  # must not raise

    def test_fixed_policy_forces_strategy(self):
        comm = Communicator(policy=FixedPolicy(DMA.name))
        ct = comm.commit(Contiguous(64, BYTE))
        assert comm.select(ct) is DMA

    def test_fixed_wire_only_policy_still_unpacks(self):
        # forcing the wire-only bounding strategy must not break local
        # pack/unpack calls (they fall back to the auto heuristic)
        comm = Communicator(policy=FixedPolicy(BOUNDING.name))
        ct = comm.commit(Vector(4, 8, 16, BYTE))
        assert comm.select(ct, wire=True) is BOUNDING
        assert comm.select(ct, wire=False) is AUTO
        buf = jnp.arange(ct.extent + 8, dtype=jnp.uint8)
        packed = comm.pack(buf, ct)
        out = comm.unpack(jnp.zeros_like(buf), packed, ct)
        assert out.shape == buf.shape

    def test_baseline_policy_degrades_past_cap(self):
        comm = Communicator(policy=BaselinePolicy(block_cap=16))
        ct = comm.commit(Vector(32, 8, 64, BYTE))
        assert comm.select(ct) is REF
        small = comm.commit(Vector(4, 8, 64, BYTE))
        assert comm.select(small) is XLA

    def test_interposer_is_shim_over_communicator(self):
        ip = Interposer()
        assert isinstance(ip.comm, Communicator)
        assert as_communicator(ip) is ip.comm
        assert as_communicator(ip.comm) is ip.comm
        with pytest.raises(TypeError):
            as_communicator(object())


# ===========================================================================
# pack/unpack through the Communicator (every strategy agrees with ref)
# ===========================================================================

class TestPackUnpack:
    def test_all_strategies_roundtrip(self):
        rng = np.random.default_rng(3)
        dt = Subarray((96, 8, 4), (40, 5, 2), (8, 1, 1), BYTE)
        buf = jnp.asarray(rng.integers(0, 255, (96 * 8 * 4,), dtype=np.uint8))
        dst = jnp.asarray(rng.integers(0, 255, (96 * 8 * 4,), dtype=np.uint8))
        want_p = None
        want_u = None
        for s in (ROWS, DMA, XLA, REF, AUTO):
            comm = Communicator(policy=FixedPolicy(s))
            ct = comm.commit(dt)
            if want_p is None:
                want_p = np.asarray(pack_ref(buf, ct.block))
            p = comm.pack(buf, ct)
            np.testing.assert_array_equal(np.asarray(p), want_p, err_msg=s.name)
            u = np.asarray(comm.unpack(dst, p, ct))
            if want_u is None:
                want_u = u
            np.testing.assert_array_equal(u, want_u, err_msg=s.name)


# ===========================================================================
# requests + wire ops (1-rank mesh: self-permutes)
# ===========================================================================

class TestRequests:
    def _setup(self):
        comm = Communicator(axis_name="x")
        send = comm.commit(Subarray((64,), (8,), (0,), BYTE))
        recv = comm.commit(Subarray((64,), (8,), (32,), BYTE))
        return comm, send, recv

    def test_isend_irecv_roundtrip(self):
        comm, send, recv = self._setup()
        seen = {}

        def body(b):
            req = comm.isend(b, send, [(0, 0)])
            out = comm.irecv(b, recv, req)
            seen["pending"] = out.completed
            res = out.wait()
            seen["done"] = out.completed
            assert out.wait() is res  # idempotent
            return res

        fn = jax.jit(shard_map(
            body, mesh=_mesh1(), in_specs=P(), out_specs=P(), check_vma=False
        ))
        buf = jnp.arange(64, dtype=jnp.uint8)
        out = np.asarray(fn(buf))
        assert seen == {"pending": False, "done": True}
        want = np.arange(64, dtype=np.uint8)
        want[32:40] = want[0:8]
        np.testing.assert_array_equal(out, want)

    def test_overlapped_requests(self):
        """Two exchanges issued before either wait — both land."""
        comm, send, recv = self._setup()
        send2 = comm.commit(Subarray((64,), (4,), (16,), BYTE))
        recv2 = comm.commit(Subarray((64,), (4,), (48,), BYTE))

        def body(b):
            r1 = comm.isend(b, send, [(0, 0)])
            r2 = comm.isend(b, send2, [(0, 0)])
            out = comm.irecv(b, recv, r1).wait()
            return comm.irecv(out, recv2, r2).wait()

        fn = jax.jit(shard_map(
            body, mesh=_mesh1(), in_specs=P(), out_specs=P(), check_vma=False
        ))
        out = np.asarray(fn(jnp.arange(64, dtype=jnp.uint8)))
        want = np.arange(64, dtype=np.uint8)
        want[32:40] = want[0:8]
        want[48:52] = want[16:20]
        np.testing.assert_array_equal(out, want)


# ===========================================================================
# fused neighborhood alltoallv
# ===========================================================================

class TestNeighborAlltoallv:
    def test_plan_groups_halo_directions_into_delta_classes(self):
        spec = HaloSpec(grid=(2, 2, 2), interior=(4, 4, 4))
        perms = tuple(
            tuple(spec.perm(d)) for d in DIRECTIONS
        )
        sizes = tuple(64 for _ in DIRECTIONS)
        plan = plan_neighbor_alltoallv(sizes, perms)
        assert plan.fused
        assert plan.nranks == 8
        # 26 directions collapse into the 7 displacement classes mod 2
        assert len(plan.groups) == 7
        assert sorted(
            i for g in plan.groups for i in g.transfers
        ) == list(range(26))
        for r in range(8):
            dests = [d for d in range(8) if plan.send_rows[r][d] != 7]
            assert len(dests) == 7  # one segment per peer, none to self
        # exact-byte layout: every transfer has its own wire segment, the
        # total is the ragged optimum, and the segments tile the buffer
        assert plan.wire_bytes == 26 * 64
        assert sorted(s.offset for s in plan.segments) == [
            64 * i for i in range(26)
        ]
        # class totals are unequal (2/4/8 members x 64B) so a uniform
        # all_to_all would have to pad: the plan must not choose it at
        # zero waste tolerance on this JAX
        assert plan.seg_bytes == 8 * 64
        assert plan.padding_bytes == 0
        assert plan.issued_bytes == plan.wire_bytes

    def test_plan_uniform_schedule_requires_tolerance(self):
        # same halo layout: opting into waste tolerance re-enables the
        # single uniform collective (1 op, padded rows)
        from repro.comm.wireplan import plan_wire

        spec = HaloSpec(grid=(2, 2, 2), interior=(4, 4, 4))
        perms = tuple(tuple(map(tuple, spec.perm(d))) for d in DIRECTIONS)
        sizes = tuple(64 for _ in DIRECTIONS)
        exact = plan_wire(sizes, perms, native=False)
        assert exact.schedule == "grouped"
        assert exact.wire_ops == 7
        tolerant = plan_wire(sizes, perms, native=False,
                             uniform_waste_tolerance=10.0)
        assert tolerant.schedule == "uniform"
        assert tolerant.wire_ops == 1
        assert tolerant.issued_bytes == 8 * tolerant.seg_bytes
        assert tolerant.padding_bytes > 0

    def test_plan_grid_size_threshold_forces_grouped(self):
        # past rank_factor x ngroups the fused layout is mostly zero
        # rows: the plan must take the grouped fallback even when a
        # native ragged collective (or infinite tolerance) is claimed
        from repro.comm.wireplan import plan_wire

        nranks = 16
        ring = tuple((r, (r + 1) % nranks) for r in range(nranks))
        plan = plan_wire((128,), (ring,), native=True,
                         uniform_waste_tolerance=float("inf"))
        assert plan.ngroups == 1
        assert plan.schedule == "grouped"
        assert plan.issued_bytes == plan.wire_bytes == 128

    def test_plan_rejects_non_permutation(self):
        with pytest.raises(ValueError):
            plan_neighbor_alltoallv((8,), (((0, 0), (1, 0)),))

    def test_single_rank_fused_exchange(self):
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

        def body(b):
            return comm.neighbor_alltoallv(b, send_cts, recv_cts, perms)

        fn = jax.jit(shard_map(
            body, mesh=_mesh1(), in_specs=P(), out_specs=P(), check_vma=False
        ))
        buf = jnp.arange(64, dtype=jnp.uint8)
        out = np.asarray(fn(buf))
        want = np.arange(64, dtype=np.uint8)
        want[32:40] = want[0:8]
        want[48:52] = want[16:20]
        np.testing.assert_array_equal(out, want)

        # the whole exchange must be ONE collective whichever schedule
        # the default (model-priced) policy lands on for the single
        # delta class
        from repro.comm import collective_payload_bytes

        counts = collective_payload_bytes(fn, buf)
        assert counts["ops"] == 1
        # the exact ladder keeps the old shape: one uniform all_to_all
        strats, plan = comm.plan_neighbor(send_cts, perms,
                                          schedule_policy="exact")
        assert plan.schedule == "uniform" and plan.wire_ops == 1

        def body_exact(b):
            return comm.neighbor_alltoallv(
                b, send_cts, recv_cts, perms, plan=plan, strategies=strats
            )

        fn_exact = jax.jit(shard_map(
            body_exact, mesh=_mesh1(), in_specs=P(), out_specs=P(),
            check_vma=False
        ))
        np.testing.assert_array_equal(np.asarray(fn_exact(buf)), want)
        jaxpr = str(jax.make_jaxpr(fn_exact)(buf))
        assert jaxpr.count("all_to_all") == 1
        assert "ppermute" not in jaxpr

    def test_mismatched_lengths_raise(self):
        comm = Communicator(axis_name="x")
        ct = comm.commit(Contiguous(8, BYTE))
        with pytest.raises(ValueError):
            comm.ineighbor_alltoallv(jnp.zeros(8, jnp.uint8), [ct], [], [])


# ===========================================================================
# stats plumbing
# ===========================================================================

def test_stats_include_wire_ops_and_strategies():
    comm = Communicator(axis_name="x")
    s = comm.stats()
    assert s["wire_ops"] == 0
    assert s["strategies"] == len(default_registry())
    assert s["committed_types"] == 0
