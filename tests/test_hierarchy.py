"""Two-level hierarchy tests: topology maps, per-link-class tables
(STORE_FORMAT 5), tier-aware pricing with the inter == intra oracle,
the tiered coalesced transport, simulated-scale pricing toward the
3072-process regime, and elastic re-planning of topology-keyed pins.
"""

import dataclasses
import json

import pytest

from repro.comm import (
    PerfModel,
    SystemParams,
    Topology,
    WIRE_SCHEDULES,
    build_scale_plan,
    classify_and_coalesce,
    plan_wire,
    reschedule,
    scale_ladder,
    synthetic_two_tier,
)
from repro.measure import (
    COMPATIBLE_FORMATS,
    Decision,
    DecisionCache,
    ParamsStore,
    STORE_FORMAT,
    load_ci_params,
)
from tests._subproc import run_with_devices

# ===========================================================================
# shared geometry: 8 ranks, 4 per node (ranks 0-3 node 0, 4-7 node 1)
# ===========================================================================

TOPO84 = Topology.blocked(8, 4)


def _xor1(n):
    """Swap within on-node pairs — every edge stays intra."""
    return tuple((r, r ^ 1) for r in range(n))


def _shift(n, k):
    return tuple((r, (r + k) % n) for r in range(n))


def _shift_xor(n, k):
    """Shift then pair-swap: same destination-NODE vector as the plain
    shift, different destination ranks — the bundle condition."""
    return tuple((r, ((r + k) % n) ^ 1) for r in range(n))


#: three delta classes on TOPO84: intra, inter, inter (same node vector
#: as the other inter class -> they coalesce into one tier bundle)
PERMS_TIER = (_xor1(8), _shift(8, 4), _shift_xor(8, 4))
SIZES_TIER = (8, 12, 16)


def _topo_plan():
    return plan_wire(SIZES_TIER, PERMS_TIER, native=False, topology=TOPO84)


def _flat_plan():
    return plan_wire(SIZES_TIER, PERMS_TIER, native=False)


# ===========================================================================
# Topology: the rank -> node map
# ===========================================================================

class TestTopology:
    def test_flat_is_single_node(self):
        t = Topology.flat(6)
        assert t.nranks == 6 and t.nnodes == 1
        assert all(
            t.link_class(a, b) == "intra" for a in range(6) for b in range(6)
        )

    def test_blocked_partitions_contiguously(self):
        t = Topology.blocked(8, 4)
        assert t.nodes == (0, 0, 0, 0, 1, 1, 1, 1)
        assert t.nnodes == 2
        assert t.link_class(0, 3) == "intra"
        assert t.link_class(3, 4) == "inter"

    def test_validation(self):
        with pytest.raises(ValueError):
            Topology.blocked(8, 0)
        with pytest.raises(ValueError):
            Topology(nodes=())

    def test_fingerprint_content_keyed(self):
        assert Topology.blocked(8, 4).fingerprint == TOPO84.fingerprint
        assert Topology.blocked(8, 2).fingerprint != TOPO84.fingerprint
        assert Topology.flat(8).fingerprint != TOPO84.fingerprint

    def test_classify_intra_only_has_no_bundles(self):
        dsts = (tuple(r ^ 1 for r in range(8)),)
        classes, bundles = classify_and_coalesce(dsts, TOPO84)
        assert classes == ("intra",)
        assert bundles == ()

    def test_classify_bundles_by_node_vector(self):
        # inter, intra, inter — the two inter classes target the same
        # peer node from every rank, so they ride one bundle (in
        # first-appearance order)
        dsts = (
            tuple((r + 4) % 8 for r in range(8)),
            tuple(r ^ 1 for r in range(8)),
            tuple(((r + 4) % 8) ^ 1 for r in range(8)),
        )
        classes, bundles = classify_and_coalesce(dsts, TOPO84)
        assert classes == ("inter", "intra", "inter")
        assert bundles == ((0, 2),)

    def test_any_crossing_edge_makes_the_class_inter(self):
        # a +1 ring shift stays on-node for most ranks but crosses at
        # the block boundaries — the bulk-synchronous collective
        # completes at its slowest edge, so the class is inter
        dsts = (tuple((r + 1) % 8 for r in range(8)),)
        classes, _ = classify_and_coalesce(dsts, TOPO84)
        assert classes == ("inter",)

    def test_wrong_length_destination_vector_raises(self):
        with pytest.raises(ValueError):
            classify_and_coalesce(((0, 1, 2, 3),), TOPO84)


# ===========================================================================
# STORE_FORMAT 5: per-link-class wire tables persist and round-trip
# ===========================================================================

class TestStoreFormat5:
    def test_format_constants(self):
        # format 6 added the compress_table sweep; 5 (this PR's link
        # tables) stays loadable
        assert STORE_FORMAT == 6
        assert set(COMPATIBLE_FORMATS) == {2, 3, 4, 5, 6}

    def test_link_tables_roundtrip_params_json(self):
        p = synthetic_two_tier(load_ci_params())
        assert p.link_tables and set(p.link_tables) == {"intra", "inter"}
        p2 = SystemParams.from_json(p.to_json())
        assert p2.link_tables == p.link_tables
        assert p2.link_fits == p.link_fits

    def test_link_tables_roundtrip_store(self, tmp_path):
        p = synthetic_two_tier(load_ci_params())
        store = ParamsStore(tmp_path)
        store.save(p, system="sysA")
        p2 = store.load(system="sysA")
        assert p2 is not None
        assert p2.link_tables == p.link_tables
        assert p2.link_fits == p.link_fits

    def test_older_envelope_loads_as_intra_only(self, tmp_path):
        # a format-4 (pre-hierarchy) envelope has no link tables: it
        # must still load, and the model then prices every class intra
        p = synthetic_two_tier(load_ci_params())
        store = ParamsStore(tmp_path)
        path = store.save(p, system="sysB")
        env = json.loads(path.read_text())
        env["format"] = 4
        del env["params"]["link_tables"]
        del env["params"]["link_fits"]
        path.write_text(json.dumps(env))
        p2 = store.load(system="sysB")
        assert p2 is not None and p2.link_tables is None
        model = PerfModel(p2)
        a = model.t_link(4096, 1, link_class="intra")
        b = model.t_link(4096, 1, link_class="inter")
        assert a == b

    def test_synthetic_two_tier_degrades_inter(self):
        p = synthetic_two_tier(load_ci_params())
        intra = dict(p.link_tables["intra"])
        inter = dict(p.link_tables["inter"])
        assert set(intra) == set(inter)
        assert all(inter[x] > intra[x] for x in intra)

    def test_synthetic_two_tier_unit_factors_are_identity(self):
        p = synthetic_two_tier(
            load_ci_params(), latency_factor=1.0, bandwidth_factor=1.0
        )
        assert p.link_tables["inter"] == p.link_tables["intra"]


# ===========================================================================
# tier-aware pricing: the inter == intra oracle, and the coalescing win
# ===========================================================================

class TestTierPricing:
    def test_inter_equals_intra_reproduces_flat_prices_bitwise(self):
        # with equal tier tables every surcharge is exactly 0.0, so the
        # topology-annotated plan prices bit-identically to the flat
        # plan on every shared schedule and selects the same winner
        eq = PerfModel(
            synthetic_two_tier(
                load_ci_params(), latency_factor=1.0, bandwidth_factor=1.0
            )
        )
        flat_costs = eq.price_wire_schedules(_flat_plan(), native=False)
        topo_costs = eq.price_wire_schedules(_topo_plan(), native=False)
        for s, c in flat_costs.items():
            assert topo_costs[s] == c, s
        assert set(topo_costs) == set(flat_costs) | {"tiered"}
        # coalescing must WIN, not draw, to buy its correction hops
        assert topo_costs["tiered"] >= topo_costs["grouped"]
        assert min(topo_costs.values()) == min(flat_costs.values())
        best_flat = min(flat_costs, key=flat_costs.get)
        best_topo = min(topo_costs, key=topo_costs.get)
        assert best_topo == best_flat

    def test_flat_plan_ignores_link_tables(self):
        # a plan laid out without a topology prices identically whether
        # or not the params carry link tables (pre-hierarchy behaviour)
        base = PerfModel(load_ci_params())
        two = PerfModel(synthetic_two_tier(load_ci_params()))
        plan = _flat_plan()
        assert base.price_wire_schedules(plan, native=False) == \
            two.price_wire_schedules(plan, native=False)

    def test_slow_inter_makes_coalescing_win(self):
        # one slow-tier latency for the 2-member bundle beats two: the
        # tiered schedule undercuts grouped despite its correction hop
        slow = PerfModel(synthetic_two_tier(load_ci_params()))
        costs = slow.price_wire_schedules(_topo_plan(), native=False)
        assert costs["tiered"] < costs["grouped"]
        plan2, costs2 = slow.choose_wire_schedule(_topo_plan(), native=False)
        assert costs2 == costs
        assert plan2.schedule == min(costs, key=costs.get)


# ===========================================================================
# WirePlan: the tiered schedule's layout and accounting
# ===========================================================================

class TestWirePlanTiered:
    def test_topology_annotation(self):
        plan = _topo_plan()
        assert plan.link_classes == ("intra", "inter", "inter")
        assert plan.tier_bundles == ((1, 2),)
        assert plan.topology is TOPO84

    def test_tiered_accounting(self):
        plan = _topo_plan()
        tiered = reschedule(plan, "tiered")
        # one ppermute per intra class + one per bundle + one correction
        # per non-representative member == ngroups, same as grouped
        assert tiered.wire_ops == tiered.ngroups == 3
        assert tiered.correction_bytes == SIZES_TIER[2]
        assert tiered.issued_bytes == plan.wire_bytes + SIZES_TIER[2]
        assert plan.inter_messages == 2        # grouped: one per class
        assert tiered.inter_messages == 1      # tiered: one per bundle

    def test_fingerprint_keys_topology_and_schedule(self):
        flat, topo = _flat_plan(), _topo_plan()
        assert flat.fingerprint != topo.fingerprint
        tiered = reschedule(topo, "tiered")
        assert tiered.fingerprint != topo.fingerprint

    def test_tiered_requires_annotation(self):
        with pytest.raises(ValueError, match="topology-annotated"):
            reschedule(_flat_plan(), "tiered")

    def test_mismatched_topology_plans_flat(self):
        # a single-host test mesh planned against a production topology:
        # the annotation is dropped, not misapplied
        plan = plan_wire(
            SIZES_TIER, PERMS_TIER, native=False,
            topology=Topology.blocked(16, 4),
        )
        assert plan.link_classes is None
        assert plan.topology is None
        assert plan.tier_bundles == ()

    def test_tiered_in_schedule_set(self):
        assert WIRE_SCHEDULES == (
            "ragged", "uniform", "grouped", "tiered", "varlen"
        )


# ===========================================================================
# simulated-scale pricing: the 3072-process regime on measured tables
# ===========================================================================

class TestAtScale:
    def test_cost_monotone_in_ranks_on_ci_params(self):
        # the satellite oracle: predicted exchange cost is non-decreasing
        # in rank count on the checked-in CI tables
        model = PerfModel(load_ci_params())
        ladder = scale_ladder(
            model, (8, 16, 64, 256, 1024, 3072), 8, pin=False
        )
        best = [min(e.costs.values()) for e in ladder]
        assert all(b >= a - 1e-15 for a, b in zip(best, best[1:]))

    def test_flip_to_tiered_at_scale_and_pinning(self):
        dc = DecisionCache()
        model = PerfModel(synthetic_two_tier(load_ci_params()), decisions=dc)
        est = model.at_scale(3072, ranks_per_node=8)
        assert est.schedule == "tiered"
        assert not est.pinned
        assert est.costs["tiered"] <= est.costs["grouped"]
        assert est.inter_messages["tiered"] < est.inter_messages["grouped"]
        assert est.correction_bytes > 0
        # the decision is topology-keyed: the pin carries the rank->node
        # map's fingerprint in its signature
        rows = [d for d in dc.log if d.strategy == "wire/tiered"]
        assert rows and "topo=" in rows[0].signature
        # second pricing replays the pin
        again = model.at_scale(3072, ranks_per_node=8)
        assert again.pinned and again.schedule == "tiered"
        assert again.fingerprint == est.fingerprint

    def test_single_node_never_tiers(self):
        model = PerfModel(synthetic_two_tier(load_ci_params()))
        est = model.at_scale(8, ranks_per_node=8)
        assert est.nodes == 1
        assert est.schedule != "tiered"
        assert "tiered" not in est.costs

    def test_build_scale_plan_geometry(self):
        plan = build_scale_plan(3072, 8)
        assert plan.nranks == 3072
        assert plan.topology.nnodes == 384
        assert plan.grid[0] == 384
        # leading-axis classes cross nodes and coalesce per peer node
        assert "inter" in plan.link_classes
        assert plan.tier_bundles
        assert plan.correction_bytes > 0

    def test_build_scale_plan_validation(self):
        with pytest.raises(ValueError):
            build_scale_plan(10, 8)
        with pytest.raises(ValueError):
            build_scale_plan(0, 8)


# ===========================================================================
# elastic re-planning: topology-keyed pins are demoted on reshape
# ===========================================================================

def _decision(strategy, fingerprint, signature=""):
    return Decision(
        fingerprint=fingerprint, incount=1, hops=1, allow_bounding=True,
        strategy=strategy, t_pack=0.0, t_link=1e-5, t_unpack=0.0,
        signature=signature,
    )


class TestReplanOnRemesh:
    def _comm(self, dc, topology=None):
        from types import SimpleNamespace

        model = PerfModel(
            synthetic_two_tier(load_ci_params()), decisions=dc,
            topology=topology,
        )
        return SimpleNamespace(model=model)

    def test_reshape_prunes_stale_topology_pins(self):
        from repro.train.elastic import replan_on_remesh

        old = Topology.blocked(8, 4)
        new = Topology.blocked(4, 4)
        dc = DecisionCache([
            _decision("wire/tiered", "fp1", f"... topo={old.fingerprint}"),
            _decision("program/s=2", "fp2", "grid=(2,2,2)"),  # untagged
            _decision("overlap/mode=region", "fp3", ""),
            _decision("xla", "fp4", "contig"),  # topology-insensitive
            _decision("wire/grouped", "fp5", f"... topo={new.fingerprint}"),
        ])
        comm = self._comm(dc, topology=old)
        report = replan_on_remesh(comm, new)
        assert report.old_topology == old.fingerprint
        assert report.new_topology == new.fingerprint
        assert report.cache_cleared
        pruned = set(report.pruned)
        assert pruned == {
            "wire/tiered@fp1", "program/s=2@fp2", "overlap/mode=region@fp3",
        }
        kept = {d.fingerprint for d in dc.log}
        assert kept == {"fp4", "fp5"}
        assert comm.model.topology is new

    def test_same_topology_is_a_noop(self):
        from repro.train.elastic import replan_on_remesh

        topo = Topology.blocked(8, 4)
        dc = DecisionCache([
            _decision("wire/tiered", "fp1", f"topo={topo.fingerprint}"),
        ])
        comm = self._comm(dc, topology=topo)
        report = replan_on_remesh(comm, Topology.blocked(8, 4))
        assert report.npruned == 0
        assert len(dc.log) == 1

    def test_remesh_and_replan_repins_fresh(self):
        from repro.train.elastic import ElasticPolicy, replan_on_remesh

        dc = DecisionCache()
        comm = self._comm(dc, topology=Topology.blocked(8, 4))
        est = comm.model.at_scale(3072, ranks_per_node=8)
        assert comm.model.at_scale(3072, ranks_per_node=8).pinned

        policy = ElasticPolicy(model_parallel=4, global_batch=64)
        mesh, report = policy.remesh_and_replan(
            16, comm, ranks_per_node=4
        )
        assert mesh.shape == (4, 4)
        assert report.npruned >= 1
        assert comm.model.topology.nranks == 16
        # the stale 3072-rank pin is gone: pricing again is a fresh
        # (unpinned) decision, not a replay
        redo = comm.model.at_scale(3072, ranks_per_node=8)
        assert not redo.pinned
        assert redo.fingerprint == est.fingerprint


# ===========================================================================
# overlap drift: measured per-mode timings audit overlap/mode= pins
# ===========================================================================

class TestOverlapDrift:
    def _cache(self):
        return DecisionCache([
            _decision("overlap/mode=region", "fpo", "overlap trade"),
        ])

    def test_out_of_band_mode_is_flagged(self):
        from repro.fleet import DriftDetector

        dc = self._cache()
        report = DriftDetector().audit(
            dc, load_ci_params(), system="t",
            overlap_timings={
                "fpo": {"off": 5.0, "monolithic": 2.97, "region": 4.0}
            },
        )
        (f,) = [x for x in report.findings if x.fingerprint == "fpo"]
        assert f.drifted
        assert f.term == "overlap"
        assert f.source == "telemetry"
        assert f.ratio == pytest.approx(4.0 / 2.97)
        assert f.observed_ratio == pytest.approx(4.0 / 2.97)

    def test_in_band_mode_is_not_flagged(self):
        from repro.fleet import DriftDetector

        report = DriftDetector().audit(
            self._cache(), load_ci_params(), system="t",
            overlap_timings={
                "fpo": {"off": 5.0, "monolithic": 2.9, "region": 3.0}
            },
        )
        (f,) = [x for x in report.findings if x.fingerprint == "fpo"]
        assert not f.drifted
        assert f.term == ""

    def test_off_is_baseline_not_alternative(self):
        from repro.fleet import DriftDetector

        # "off" being much faster must NOT flag the pin: it is the
        # no-overlap baseline, not an alternative overlap schedule
        report = DriftDetector().audit(
            self._cache(), load_ci_params(), system="t",
            overlap_timings={"fpo": {"off": 1.0, "region": 4.0}},
        )
        (f,) = [x for x in report.findings if x.fingerprint == "fpo"]
        assert not f.drifted

    def test_demote_stale_modes_prunes_the_pin(self):
        from repro.fleet import DriftDetector, demote_stale_modes

        dc = self._cache()
        report = DriftDetector().audit(
            dc, load_ci_params(), system="t",
            overlap_timings={"fpo": {"monolithic": 1.0, "region": 4.0}},
        )
        demoted = demote_stale_modes(dc, report)
        assert demoted == ["overlap/mode=region@fpo"]
        assert dc.lookup("fpo", 1, 1, True) is None
        assert len(dc.log) == 0


class TestDecisionPrune:
    def test_prune_returns_dropped_and_rebuilds_index(self):
        dc = DecisionCache([
            _decision("wire/grouped", "a"),
            _decision("xla", "b"),
        ])
        dropped = dc.prune(lambda d: d.strategy.startswith("wire/"))
        assert [d.fingerprint for d in dropped] == ["a"]
        assert dc.lookup("a", 1, 1, True) is None
        assert dc.lookup("b", 1, 1, True) is not None
        assert len(dc.log) == 1

    def test_prune_nothing_is_harmless(self):
        dc = DecisionCache([_decision("xla", "b")])
        assert dc.prune(lambda d: False) == []
        assert len(dc.log) == 1


# ===========================================================================
# provenance: bundles and program fingerprints carry the topology
# ===========================================================================

class TestBundleTopology:
    def test_topology_roundtrips(self):
        from repro.fleet import DecisionBundle

        b = DecisionBundle(
            decisions=DecisionCache([_decision("xla", "a")]),
            generation=3, system="sys", topology=TOPO84.fingerprint,
        )
        b2 = DecisionBundle.from_json(b.to_json())
        assert b2.topology == TOPO84.fingerprint
        assert TOPO84.fingerprint in b.summary()

    def test_old_bundle_without_topology_loads(self):
        from repro.fleet import DecisionBundle

        d = json.loads(
            DecisionBundle(decisions=DecisionCache()).to_json()
        )
        del d["topology"]
        b = DecisionBundle.from_json(json.dumps(d))
        assert b.topology == ""

    def test_merge_carries_topology_only_when_unanimous(self):
        from repro.fleet import DecisionBundle, merge_bundles

        fp = TOPO84.fingerprint
        same = merge_bundles([
            DecisionBundle(decisions=DecisionCache(), topology=fp),
            DecisionBundle(decisions=DecisionCache(), topology=fp),
        ])
        assert same.topology == fp
        mixed = merge_bundles([
            DecisionBundle(decisions=DecisionCache(), topology=fp),
            DecisionBundle(decisions=DecisionCache(), topology="other"),
        ])
        assert mixed.topology == ""


class TestProgramTopologyKey:
    def test_topology_fingerprint_keys_program_decisions(self):
        from repro.halo import StencilOp, program_fingerprint
        from repro.core.datatypes import FLOAT

        op = StencilOp(radii=(1, 1, 1))
        base = program_fingerprint((2, 2, 2), (8, 8, 8), op, FLOAT)
        topo = program_fingerprint(
            (2, 2, 2), (8, 8, 8), op, FLOAT,
            topology_fingerprint=TOPO84.fingerprint,
        )
        assert base != topo
        # empty fingerprint preserves every pre-hierarchy key
        again = program_fingerprint(
            (2, 2, 2), (8, 8, 8), op, FLOAT, topology_fingerprint=""
        )
        assert again == base


# ===========================================================================
# the tiered transport is bit-exact (subprocess, 8 CPU devices)
# ===========================================================================

TIERED_TRANSPORT_CODE = r"""
import dataclasses
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.comm import (
    Communicator, FixedPolicy, Topology, collective_payload_bytes,
    reschedule,
)
from repro.halo import HaloSpec, halo_exchange, make_halo_plan

# 2x2x2 grid, 4 ranks per node: rank = z*4 + y*2 + x, node = z — every
# delta class with a leading-axis component crosses nodes, and all four
# inter classes share the destination-node vector (one tier bundle)
spec = HaloSpec(grid=(2, 2, 2), interior=(4, 4, 4), radius=1)
topo = Topology.blocked(8, 4)
R = spec.nranks
az, ay, ax = spec.alloc
nz, ny, nx = spec.interior

comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"),
                    topology=topo)
mesh = Mesh(np.array(jax.devices()), ("ranks",))
plan = make_halo_plan(spec, comm, schedule_policy="exact")
wire = plan.wire
assert wire.schedule == "grouped", wire.schedule
assert wire.link_classes is not None
assert wire.link_classes.count("inter") == 4, wire.link_classes
assert len(wire.tier_bundles) == 1 and len(wire.tier_bundles[0]) == 4
tiered_wire = reschedule(wire, "tiered")
tiered_plan = dataclasses.replace(plan, wire=tiered_wire)

gz, gy, gx = 2 * nz, 2 * ny, 2 * nx
gvals = np.arange(gz * gy * gx, dtype=np.float32).reshape(gz, gy, gx)
locals_np = np.full((R, az, ay, ax), -1.0, np.float32)
for rank in range(R):
    cz, cy, cx = spec.coords(rank)
    locals_np[rank, 1:1+nz, 1:1+ny, 1:1+nx] = gvals[
        cz*nz:(cz+1)*nz, cy*ny:(cy+1)*ny, cx*nx:(cx+1)*nx]
x0 = jnp.asarray(locals_np.reshape(R * az, ay, ax))

def runner(p):
    return jax.jit(shard_map(
        lambda x: halo_exchange(x, spec, comm, "ranks", plan=p),
        mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks"),
        check_vma=False))

grouped_fn, tiered_fn = runner(plan), runner(tiered_plan)
out_g = np.asarray(grouped_fn(x0)).reshape(R, az, ay, ax)
out_t = np.asarray(tiered_fn(x0)).reshape(R, az, ay, ax)
np.testing.assert_array_equal(out_t, out_g)
print("BITEXACT_OK")

# periodic oracle: the tiered transport fills every halo cell right
for rank in range(R):
    cz, cy, cx = spec.coords(rank)
    zz = (np.arange(az) - 1 + cz * nz) % gz
    yy = (np.arange(ay) - 1 + cy * ny) % gy
    xx = (np.arange(ax) - 1 + cx * nx) % gx
    np.testing.assert_array_equal(out_t[rank], gvals[np.ix_(zz, yy, xx)],
                                  err_msg=f"rank {rank}")
print("ORACLE_OK")

# accounting: tiered re-transmits exactly correction_bytes on the fast
# tier and issues ngroups collectives, same count as grouped — the win
# is one slow-tier message instead of four
counts = collective_payload_bytes(tiered_fn, x0)
assert tiered_wire.correction_bytes > 0
want = wire.wire_bytes + tiered_wire.correction_bytes
assert counts["total"] == want == tiered_wire.issued_bytes, (counts, want)
assert counts["ops"] == tiered_wire.wire_ops == wire.ngroups
assert tiered_wire.inter_messages == 1 and wire.inter_messages == 4
print("ACCOUNTING_OK", want)
"""


@pytest.mark.slow
def test_tiered_transport_bit_exact():
    out = run_with_devices(TIERED_TRANSPORT_CODE, ndev=8)
    assert "BITEXACT_OK" in out
    assert "ORACLE_OK" in out
    assert "ACCOUNTING_OK" in out
