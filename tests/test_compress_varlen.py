"""Length-aware compressed wire transport (satellites of the varlen PR):

- compression round trips on adversarial payloads (all-zero, zero-free,
  alternating short runs, block-boundary runs) — deterministic always,
  property-based when ``hypothesis`` is installed;
- varlen truncation correctness under jit: ``stream_bytes <=
  wire_bytes`` invariant, traced bytes == ``issued_bytes``, bit-exact
  against the capacity (grouped) transport;
- honest accounting: compress counters, ratio telemetry ring, decision
  signatures carrying ``stream_bytes=``/``ratio=``;
- the compress-throughput sweep + measure-store format 6 round trip;
- ratio drift detection and ``demote_stale_compress``;
- the gradient wire (``GradWire`` / ``make_grad_step``) end to end.
"""

import dataclasses
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
    RLE_WIRE,
    RleWire,
)
from repro.comm.compress import RLE_HEADER_BYTES, RLE_RUN_BYTES
from repro.comm.perfmodel import SystemParams, TPU_V5E
from repro.comm.wireplan import collective_payload_bytes, reschedule
from repro.core import BYTE, FLOAT, Subarray, TypeRegistry, Vector
from repro.fleet import (
    DriftDetector,
    ExchangeTelemetry,
    demote_stale_compress,
    remeasure_term,
)
from repro.measure.decisions import Decision, DecisionCache


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("x",))


def _nruns(member: np.ndarray) -> int:
    return int(np.count_nonzero(member[1:] != member[:-1])) + 1


def _byte_ct(n: int):
    """A contiguous n-byte committed type (pack == identity)."""
    return TypeRegistry().commit(Vector(1, n, n, BYTE))


# the adversarial payload zoo: name -> member bytes.  Every entry is a
# shape the run-length layout can get wrong — degenerate run counts,
# runs straddling the 5-byte record and 256-element quantization
# boundaries, and streams that exactly fill / just overflow capacity.
def _adversarial_payloads():
    out = {}
    n = 1024
    out["all_zero"] = np.zeros(n, np.uint8)
    # no zero byte anywhere AND no two equal neighbours: run count == n,
    # which cannot fit n//5 run slots -> stored mode
    out["zero_free"] = (np.arange(n, dtype=np.int64) % 7 + 1).astype(np.uint8)
    # alternating short runs of length 2: n//2 runs, still > capacity
    out["alt_short_runs"] = np.repeat(
        np.tile(np.array([1, 2], np.uint8), n // 4), 2
    )
    # runs whose boundaries land exactly on the 5-byte record stride and
    # the 256-byte quantization block edge
    block = np.zeros(n, np.uint8)
    block[:RLE_RUN_BYTES] = 9          # one run exactly one record wide
    block[256:512] = 3                 # run spanning a full 256-block
    block[511:513] = 7                 # run straddling a block boundary
    out["block_boundary_runs"] = block
    # exactly at the run-capacity cliff: R = n // 5 runs fits the fixed
    # record layout with zero slack (one more run would ship stored)
    R = n // RLE_RUN_BYTES
    cap = np.zeros(n, np.uint8)
    cap[: R - 1] = np.arange(R - 1) % 2 + 1  # R-1 length-1 runs + zero tail
    assert _nruns(cap) == R
    out["at_run_capacity"] = cap
    rng = np.random.RandomState(0)
    out["random"] = rng.randint(0, 256, n).astype(np.uint8)
    out["single_byte"] = np.array([42], np.uint8)
    out["empty_tail"] = np.concatenate(
        [rng.randint(0, 4, 64).astype(np.uint8), np.zeros(960, np.uint8)]
    )
    return out


# ===========================================================================
# round trips (deterministic)
# ===========================================================================

class TestRleRoundTrip:
    @pytest.mark.parametrize("name", sorted(_adversarial_payloads()))
    def test_capacity_wire_round_trips_bit_exact(self, name):
        member = _adversarial_payloads()[name]
        n = member.size
        wire = np.asarray(RLE_WIRE.encode_wire(jnp.asarray(member)))
        assert wire.shape[0] == RLE_HEADER_BYTES + n  # capacity layout
        out = np.asarray(RLE_WIRE.decode_wire(jnp.asarray(wire), n))
        np.testing.assert_array_equal(out, member)

    @pytest.mark.parametrize("name", sorted(_adversarial_payloads()))
    def test_stream_prefix_decodes_when_rle_mode(self, name):
        """The live stream is a literal prefix of the capacity wire:
        decoding ``wire[:probe_stream_bytes]`` must reproduce the member
        bytes whenever the payload fits rle mode; a stored-mode payload
        must report stream == capacity (never truncates)."""
        member = _adversarial_payloads()[name]
        n = member.size
        ct = _byte_ct(n)
        cap = RLE_WIRE.wire_bytes(ct)
        stream = RLE_WIRE.probe_stream_bytes(ct, 1, jnp.asarray(member))
        assert stream <= cap  # the invariant the transport relies on
        runs = _nruns(member)
        if runs > n // RLE_RUN_BYTES:
            assert stream == cap  # stored mode: stream IS the capacity
            return
        assert stream == RLE_HEADER_BYTES + RLE_RUN_BYTES * runs
        wire = np.asarray(RLE_WIRE.encode_wire(jnp.asarray(member)))
        out = np.asarray(
            RLE_WIRE.decode_wire(jnp.asarray(wire[:stream]), n)
        )
        np.testing.assert_array_equal(out, member)

    def test_mode_matches_run_capacity(self):
        # a compressible payload ships rle (mode 1), an incompressible
        # one ships stored (mode 0) — read back from the wire header
        for name, member in _adversarial_payloads().items():
            if member.size < RLE_RUN_BYTES:
                continue
            wire = np.asarray(RLE_WIRE.encode_wire(jnp.asarray(member)))
            mode = int(wire[:4].view(np.uint32)[0])
            fits = _nruns(member) <= member.size // RLE_RUN_BYTES
            assert mode == (1 if fits else 0), name

    def test_decode_rejects_ragged_stream_lengths(self):
        member = np.zeros(100, np.uint8)
        wire = np.asarray(RLE_WIRE.encode_wire(jnp.asarray(member)))
        # neither capacity (108) nor header + whole 5-byte records
        with pytest.raises(ValueError, match="rle wire"):
            RLE_WIRE.decode_wire(jnp.asarray(wire[:11]), 100)
        with pytest.raises(ValueError, match="rle wire"):
            RLE_WIRE.decode_wire(jnp.asarray(wire[:4]), 100)

    def test_round_trip_under_jit(self):
        member = _adversarial_payloads()["block_boundary_runs"]
        n = member.size
        enc = jax.jit(RLE_WIRE.encode_wire)
        dec = jax.jit(lambda w: RLE_WIRE.decode_wire(w, n))
        out = np.asarray(dec(enc(jnp.asarray(member))))
        np.testing.assert_array_equal(out, member)


class TestInt8RoundTrip:
    @pytest.mark.parametrize("n", [64, 256, 1000])
    def test_quantized_round_trip_is_close(self, n):
        rng = np.random.RandomState(1)
        f = rng.randn(n).astype(np.float32)
        member = f.view(np.uint8)
        wire = INT8_WIRE.encode_wire(jnp.asarray(member))
        out = np.asarray(
            INT8_WIRE.decode_wire(wire, member.size)
        ).view(np.float32)
        assert np.max(np.abs(out - f)) <= np.max(np.abs(f)) / 127 + 1e-7

    def test_all_zero_floats_survive_exactly(self):
        member = np.zeros(256, np.uint8)
        wire = INT8_WIRE.encode_wire(jnp.asarray(member))
        out = np.asarray(INT8_WIRE.decode_wire(wire, 256))
        np.testing.assert_array_equal(out, member)

    def test_int8_never_truncates_and_stays_opt_in(self):
        # lossy wire: the base-class probe reports capacity (no stream
        # to truncate at) and the strategy is never auto-selected
        n = 256
        ct = _byte_ct(n)
        probe = INT8_WIRE.probe_stream_bytes(
            ct, 1, jnp.zeros((n,), jnp.uint8)
        )
        assert probe == INT8_WIRE.wire_bytes(ct)
        assert not getattr(INT8_WIRE, "supports_varlen", False)
        assert not INT8_WIRE.selectable


# ===========================================================================
# round trips (property-based; skipped when hypothesis is absent)
# ===========================================================================

class TestRleProperties:
    def test_arbitrary_payloads_round_trip(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=200, deadline=None)
        @given(
            st.lists(st.integers(0, 255), min_size=1, max_size=512),
        )
        def check(data):
            member = np.array(data, np.uint8)
            n = member.size
            wire = np.asarray(RLE_WIRE.encode_wire(jnp.asarray(member)))
            assert wire.shape[0] == RLE_HEADER_BYTES + n
            out = np.asarray(RLE_WIRE.decode_wire(jnp.asarray(wire), n))
            np.testing.assert_array_equal(out, member)
            ct = _byte_ct(n)
            stream = RLE_WIRE.probe_stream_bytes(ct, 1, jnp.asarray(member))
            assert stream <= RLE_WIRE.wire_bytes(ct)
            if stream < RLE_WIRE.wire_bytes(ct):
                trunc = np.asarray(
                    RLE_WIRE.decode_wire(jnp.asarray(wire[:stream]), n)
                )
                np.testing.assert_array_equal(trunc, member)

        check()

    def test_run_structured_payloads_round_trip(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=100, deadline=None)
        @given(
            st.lists(
                st.tuples(st.integers(0, 255), st.integers(1, 64)),
                min_size=1, max_size=32,
            ),
        )
        def check(runs):
            member = np.concatenate(
                [np.full(c, v, np.uint8) for v, c in runs]
            )
            wire = np.asarray(RLE_WIRE.encode_wire(jnp.asarray(member)))
            out = np.asarray(
                RLE_WIRE.decode_wire(jnp.asarray(wire), member.size)
            )
            np.testing.assert_array_equal(out, member)

        check()


# ===========================================================================
# the varlen transport under jit
# ===========================================================================

def _halo_setup(telemetry=None):
    """The canonical probed halo exchange: one rank, zero-heavy
    16x16-core Subarray with a 4-wide halo — the probe compresses, so
    selection picks rlewire and the model prices the varlen schedule."""
    comm = Communicator(axis_name="x", telemetry=telemetry)
    ct = comm.commit(Subarray((32, 32), (16, 16), (4, 4), FLOAT))
    src = np.zeros((32, 32), np.float32)
    src[10:12, 6:8] = 3.0  # a short nonzero patch inside the halo shell
    perms = [[(0, 0)]]
    strats, plan = comm.plan_neighbor(
        [ct], perms, probe=jnp.asarray(src)
    )
    return comm, ct, src, perms, strats, plan


def _run_exchange(comm, ct, src, perms, strats, plan):
    def body(buf):
        return comm.neighbor_alltoallv(
            buf, [ct], [ct], perms, plan=plan, strategies=strats
        )

    fn = jax.jit(shard_map(
        body, mesh=_mesh1(), in_specs=P(), out_specs=P(), check_vma=False
    ))
    return fn, np.asarray(fn(jnp.asarray(src)))


class TestVarlenTransport:
    def test_probed_plan_selects_varlen_rle(self):
        comm, ct, src, perms, strats, plan = _halo_setup()
        assert strats[0].name == RleWire.name
        assert plan.schedule == "varlen"
        assert plan.stream_bytes  # annotated
        # the invariant: every class's stream fits its capacity slot
        for sb, g in zip(plan.stream_bytes, plan.groups):
            assert 0 < sb <= g.nbytes
        assert plan.effective_wire_bytes < plan.wire_bytes
        assert plan.issued_bytes == plan.effective_wire_bytes
        assert 0.0 < plan.stream_ratio < 1.0

    def test_traced_bytes_equal_issued_bytes(self):
        comm, ct, src, perms, strats, plan = _halo_setup()
        fn, _ = _run_exchange(comm, ct, src, perms, strats, plan)
        counts = collective_payload_bytes(fn, jnp.asarray(src))
        assert counts["total"] == plan.issued_bytes
        assert counts["total"] < plan.wire_bytes  # strictly fewer bytes

    def test_varlen_is_bit_exact_against_capacity_transport(self):
        comm, ct, src, perms, strats, plan = _halo_setup()
        _, out_varlen = _run_exchange(comm, ct, src, perms, strats, plan)
        cap_plan = reschedule(plan, "grouped")
        assert cap_plan.issued_bytes == cap_plan.wire_bytes
        _, out_cap = _run_exchange(comm, ct, src, perms, strats, cap_plan)
        np.testing.assert_array_equal(out_varlen, out_cap)
        # the self-permute halo exchange reproduces the halo shell
        np.testing.assert_array_equal(
            out_varlen[10:12, 6:8], src[10:12, 6:8]
        )

    def test_dense_probe_honestly_declines_varlen(self):
        # an incompressible probe must not buy the compressed wire
        comm = Communicator(axis_name="x")
        ct = comm.commit(Subarray((32, 32), (16, 16), (4, 4), FLOAT))
        rng = np.random.RandomState(2)
        src = rng.randn(32, 32).astype(np.float32)
        strats, plan = comm.plan_neighbor(
            [ct], [[(0, 0)]], probe=jnp.asarray(src)
        )
        assert plan.schedule != "varlen"
        assert strats[0].name != RleWire.name

    def test_compress_counters_and_stats(self):
        comm, ct, src, perms, strats, plan = _halo_setup()
        fn, _ = _run_exchange(comm, ct, src, perms, strats, plan)
        jax.block_until_ready(fn(jnp.asarray(src)))
        s = comm.stats()
        assert s["compress_exchanges"] >= 1
        assert s["compress_capacity_bytes"] >= plan.wire_bytes
        assert s["compress_stream_bytes"] >= plan.effective_wire_bytes
        assert s["compress_stream_bytes"] < s["compress_capacity_bytes"]
        assert 0.0 < s["compress_ratio"] < 1.0

    def test_ratio_gauge_published(self):
        from repro.obs.metrics import MetricsRegistry, publish_comm_stats

        comm, ct, src, perms, strats, plan = _halo_setup()
        fn, _ = _run_exchange(comm, ct, src, perms, strats, plan)
        reg = MetricsRegistry()
        publish_comm_stats(comm.stats(), registry=reg)
        assert 0.0 < reg.gauge("comm.compress.ratio") < 1.0
        assert reg.counter("comm.compress.stream_bytes") == comm.stats()[
            "compress_stream_bytes"
        ]

    def test_ratio_telemetry_ring_registered_and_observed(self):
        tel = ExchangeTelemetry()
        comm, ct, src, perms, strats, plan = _halo_setup(telemetry=tel)
        ring = tel.get(f"{plan.fingerprint}/ratio")
        assert ring is not None and ring.strategy == "compress/ratio"
        assert ring.predicted == pytest.approx(plan.stream_ratio)
        fn, _ = _run_exchange(comm, ct, src, perms, strats, plan)
        assert ring.count >= 1
        assert ring.mean == pytest.approx(plan.stream_ratio)

    def test_decision_signature_carries_stream_and_ratio(self):
        dc = DecisionCache()
        comm = Communicator(axis_name="x", decisions=dc)
        ct = comm.commit(Subarray((32, 32), (16, 16), (4, 4), FLOAT))
        src = np.zeros((32, 32), np.float32)
        src[10:12, 6:8] = 3.0
        _, plan = comm.plan_neighbor([ct], [[(0, 0)]],
                                     probe=jnp.asarray(src))
        rows = [d for d in dc.log if d.strategy == "wire/varlen"]
        assert len(rows) == 1
        assert f"stream_bytes={plan.effective_wire_bytes}" in rows[0].signature
        assert "ratio=" in rows[0].signature
        sel = [d for d in dc.log if d.strategy == RleWire.name]
        assert sel and " stream_bytes=" in f" {sel[0].signature}"

    def test_with_stream_bytes_clamps_and_validates(self):
        comm, ct, src, perms, strats, plan = _halo_setup()
        base = reschedule(plan, "grouped")
        with pytest.raises(ValueError, match="one length per delta class"):
            base.with_stream_bytes((1,) * (base.ngroups + 1))
        huge = base.with_stream_bytes((10 ** 9,) * base.ngroups)
        assert huge.stream_bytes == tuple(g.nbytes for g in base.groups)
        assert huge.effective_wire_bytes == base.wire_bytes

    def test_reschedule_to_varlen_requires_stream_annotation(self):
        comm = Communicator(axis_name="x")
        ct = comm.commit(Subarray((32, 32), (16, 16), (4, 4), FLOAT))
        _, plan = comm.plan_neighbor([ct], [[(0, 0)]])  # no probe
        assert not plan.stream_bytes
        with pytest.raises(ValueError, match="stream-annotated"):
            reschedule(plan, "varlen")

    def test_stream_annotation_keys_the_fingerprint(self):
        comm, ct, src, perms, strats, plan = _halo_setup()
        plain = dataclasses.replace(
            reschedule(plan, "grouped"), stream_bytes=()
        )
        assert plan.fingerprint != plain.fingerprint


# ===========================================================================
# the compress-throughput sweep + store format
# ===========================================================================

class TestCompressTable:
    def test_sweep_rows_are_well_formed(self):
        from repro.measure.bench import measure_compress_table

        table = measure_compress_table(
            total_bytes=(1 << 10, 1 << 12), iters=1
        )
        assert set(table) == {"rlewire", "int8wire"}
        for name, rows in table.items():
            assert len(rows) == 2
            for log2n, csec, dsec, ratio in rows:
                assert csec > 0 and dsec > 0
                assert 0.0 < ratio <= 1.0 + 1e-9, name
            # the zero-heavy sweep payload compresses hard under rle
            if name == "rlewire":
                assert all(r[3] < 0.5 for r in rows)

    def test_measured_compress_interpolates_after_json_round_trip(self):
        from repro.measure.bench import measure_compress_table

        table = measure_compress_table(
            total_bytes=(1 << 10, 1 << 12), iters=1
        )
        params = dataclasses.replace(
            TPU_V5E, name="compress-test",
            compress_table={k: tuple(v) for k, v in table.items()},
        )
        back = SystemParams.from_json(params.to_json())
        from repro.comm.perfmodel import PerfModel

        model = PerfModel(back)
        m = model.measured_compress("rlewire", 1 << 11)
        assert m is not None and m[0] > 0 and m[1] > 0
        assert model.measured_compress("nosuch", 1 << 11) is None

    def test_store_round_trip_format_6(self, tmp_path):
        from repro.measure.store import (
            COMPATIBLE_FORMATS,
            STORE_FORMAT,
            ParamsStore,
        )

        assert STORE_FORMAT == 6
        params = dataclasses.replace(
            TPU_V5E, name="fmt6",
            compress_table={"rlewire": ((10.0, 1e-5, 1e-5, 0.05),)},
        )
        store = ParamsStore(tmp_path)
        store.save(params, system="s")
        loaded = store.load("s")
        assert loaded.compress_table["rlewire"][0][3] == 0.05
        # a format-5 envelope (predates compress_table) still loads
        assert 5 in COMPATIBLE_FORMATS
        path = store.path_for("s")
        d = json.loads(path.read_text())
        d["format"] = 5
        d["params"].pop("compress_table", None)
        path.write_text(json.dumps(d))
        old = store.load("s")
        assert old is not None and not old.compress_table


# ===========================================================================
# ratio drift + demotion
# ===========================================================================

def _varlen_decision(fp="wp-varlen", ratio=0.05):
    return Decision(
        fp, 1, 1, True, "wire/varlen", 0.0, 1e-6, 0.0,
        f"exchange schedule=varlen stream_bytes=53 ratio={ratio:g} "
        f"priced[grouped=2e-06 varlen=1e-06]", 1032,
    )


class TestCompressDrift:
    def test_decayed_ratio_ring_flags_compress_drift(self):
        dc = DecisionCache([
            _varlen_decision(),
            Decision("ct-halo", 1, 1, True, "rlewire", 1e-6, 1e-6, 1e-6,
                     "subarray stream_bytes=53 ratio=0.05", 1032),
        ])
        tel = ExchangeTelemetry()
        tel.register("wp-varlen/ratio", 0.05, "compress/ratio")
        for _ in range(8):
            tel.observe("wp-varlen/ratio", 0.40)  # payload stopped compressing
        report = DriftDetector(min_samples=4).audit(
            dc, TPU_V5E, telemetry=tel, system="t"
        )
        flagged = [f for f in report.drifted if f.term == "compress"]
        assert len(flagged) == 1
        f = flagged[0]
        assert f.strategy == "wire/varlen" and f.source == "telemetry"
        assert f.ratio == pytest.approx(0.40 / 0.05)
        # demotion drops the schedule pin AND the probed selection row
        labels = demote_stale_compress(dc, report)
        assert set(labels) == {"wire/varlen@wp-varlen", "rlewire@ct-halo"}
        assert len(dc) == 0

    def test_healthy_ratio_ring_stays_pinned(self):
        dc = DecisionCache([_varlen_decision()])
        tel = ExchangeTelemetry()
        tel.register("wp-varlen/ratio", 0.05, "compress/ratio")
        for _ in range(8):
            tel.observe("wp-varlen/ratio", 0.052)
        report = DriftDetector(min_samples=4).audit(
            dc, TPU_V5E, telemetry=tel, system="t"
        )
        assert not [f for f in report.drifted if f.term == "compress"]
        assert demote_stale_compress(dc, report) == []
        assert len(dc) == 1

    def test_demote_leaves_unrelated_rows(self):
        dc = DecisionCache([
            _varlen_decision(),
            Decision("other", 1, 1, True, "rows", 1e-6, 1e-6, 1e-6,
                     "vec", 64),
            Decision("wp2", 2, 3, True, "wire/grouped", 0.0, 1e-6, 0.0,
                     "exchange", 4096),
        ])
        tel = ExchangeTelemetry()
        tel.register("wp-varlen/ratio", 0.05, "compress/ratio")
        for _ in range(8):
            tel.observe("wp-varlen/ratio", 0.40)
        report = DriftDetector(min_samples=4).audit(
            dc, TPU_V5E, telemetry=tel, system="t"
        )
        assert demote_stale_compress(dc, report) == ["wire/varlen@wp-varlen"]
        assert {d.strategy for d in dc.log} == {"rows", "wire/grouped"}

    def test_remeasure_compress_term_refreshes_the_table(self):
        params = dataclasses.replace(TPU_V5E, name="rm", compress_table={})
        fresh = remeasure_term(params, "compress", iters=1)
        assert set(fresh.compress_table) == {"rlewire", "int8wire"}
        assert fresh.compress_table["rlewire"]
        # the other tables are untouched (targeted re-measurement)
        assert fresh.wire_table == params.wire_table


# ===========================================================================
# the gradient wire
# ===========================================================================

def _grad_tree():
    rng = np.random.RandomState(3)
    emb = np.zeros((64, 16), np.float32)
    emb[5] = rng.randn(16)  # sparsely-updated embedding: zero-heavy
    w = np.zeros((16, 16), np.float32)
    w[3, :4] = rng.randn(4) * 0.1
    return {
        "emb": jnp.asarray(emb),
        "w": jnp.asarray(w),
        "b": jnp.asarray(np.zeros((16,), np.float32)),
    }


class TestGradWire:
    def test_unknown_mode_raises(self):
        from repro.train import GradWire

        with pytest.raises(ValueError, match="unknown grad-wire mode"):
            GradWire(Communicator(axis_name="x"), mode="zstd")

    def test_off_mode_is_a_passthrough(self):
        from repro.train import GradWire

        wire = GradWire(Communicator(axis_name="x"), mode="off")
        grads = _grad_tree()
        assert wire.exchange(grads) is grads
        assert not wire.planned

    @pytest.mark.parametrize("mode", ["auto", "rle"])
    def test_lossless_modes_round_trip_bit_exact(self, mode):
        from repro.train import GradWire

        dc = DecisionCache()
        comm = Communicator(axis_name="x", decisions=dc)
        wire = GradWire(comm, mode=mode)
        grads = _grad_tree()
        out = wire.exchange(grads)
        assert wire.planned
        for k in grads:
            np.testing.assert_array_equal(
                np.asarray(out[k]), np.asarray(grads[k]), err_msg=k
            )
        desc = wire.describe()
        assert f"mode={mode}" in desc and "schedule=" in desc
        assert [d for d in dc.log if d.strategy.startswith("wire/")]

    def test_forced_rle_rides_the_varlen_wire(self):
        from repro.train import GradWire

        comm = Communicator(axis_name="x")
        wire = GradWire(comm, mode="rle")
        wire.plan_for(_grad_tree())
        assert wire._strats[0].name == RleWire.name
        p = wire._plan_fwd
        # the zero-heavy gradient probe annotates a real stream
        assert p.stream_bytes and p.effective_wire_bytes < p.wire_bytes
        assert p.schedule == "varlen"

    def test_int8_mode_is_lossy_but_close_and_opt_in(self):
        from repro.train import GradWire

        comm = Communicator(axis_name="x")
        wire = GradWire(comm, mode="int8")
        grads = _grad_tree()
        out = wire.exchange(grads)
        assert wire._strats[0].name == "int8wire"
        assert not wire._plan_fwd.stream_bytes  # lossy: never probed
        for k in grads:
            g = np.asarray(grads[k])
            o = np.asarray(out[k])
            tol = 2 * (np.max(np.abs(g)) / 127 + 1e-7)  # two quantize hops
            assert np.max(np.abs(o - g)) <= tol, k

    def test_exchange_traces_exactly_the_planned_bytes(self):
        from repro.train import GradWire

        comm = Communicator(axis_name="x")
        wire = GradWire(comm, mode="rle")
        grads = _grad_tree()
        wire.plan_for(grads)
        wire._exchange_fn = wire._build(grads)
        # the jitted exchange moves fwd + back issued bytes, nothing more
        fn = wire._exchange_fn

        def flatcall(*leaves):
            tree = jax.tree.unflatten(jax.tree.structure(grads), leaves)
            return fn(tree)

        counts = collective_payload_bytes(
            flatcall, *jax.tree.leaves(grads)
        )
        expect = wire._plan_fwd.issued_bytes + wire._plan_back.issued_bytes
        assert counts["total"] == expect


class TestGradStepFactories:
    def _tiny(self):
        from repro.configs.base import ModelConfig, ShapeConfig
        from repro.data.pipeline import synthetic_batch
        from repro.models.model import build_model
        from repro.train.optimizer import AdamWConfig, init_opt_state

        cfg = ModelConfig(
            name="tiny", family="dense", num_layers=1, d_model=16,
            num_heads=2, num_kv_heads=2, d_ff=32, vocab_size=64,
        )
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt_cfg = AdamWConfig(total_steps=10)
        opt = init_opt_state(params, opt_cfg)
        batch = synthetic_batch(cfg, ShapeConfig("train", 8, 2, "train"), 0)
        return model, opt_cfg, params, opt, batch

    def test_split_factories_compose_to_the_fused_step(self):
        from repro.train import make_grad_step
        from repro.train.train_step import make_train_step

        model, opt_cfg, params, opt, batch = self._tiny()
        fused = make_train_step(model, opt_cfg)
        p1, o1, m1 = jax.jit(fused)(params, opt, batch)
        grad_fn, update_fn = make_grad_step(model, opt_cfg)
        loss, metrics, grads = jax.jit(grad_fn)(params, batch)
        p2, o2, m2 = jax.jit(update_fn)(params, opt, grads, loss, metrics)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6
            ),
            (p1, m1["loss"]), (p2, m2["loss"]),
        )

    def test_wire_between_the_halves_preserves_training(self):
        from repro.train import GradWire, make_grad_step
        from repro.train.train_step import make_train_step

        model, opt_cfg, params, opt, batch = self._tiny()
        fused = make_train_step(model, opt_cfg)
        p1, _, m1 = jax.jit(fused)(params, opt, batch)
        grad_fn, update_fn = make_grad_step(model, opt_cfg)
        wire = GradWire(Communicator(axis_name="x"), mode="rle")
        loss, metrics, grads = jax.jit(grad_fn)(params, batch)
        grads = wire.exchange(grads)  # lossless: must not perturb the step
        p2, _, m2 = jax.jit(update_fn)(params, opt, grads, loss, metrics)
        np.testing.assert_allclose(
            float(m1["loss"]), float(m2["loss"]), rtol=1e-6
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6
            ),
            p1, p2,
        )
