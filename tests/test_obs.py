"""Observability layer: hierarchical spans, the jit tracer guard,
Chrome-trace export/validation, the metrics registry, trace-sourced
drift attribution, and the ``tempi.*`` named scopes and profiler host
spans that put the program's phases in a device trace."""

import re

import json

import pytest

import jax
import jax.numpy as jnp

from repro.fleet import ExchangeTelemetry
from repro.measure.decisions import Decision, DecisionCache
from repro.obs import (
    MetricsRegistry,
    Span,
    Tracer,
    aggregate_events,
    aggregate_spans,
    default_metrics,
    load_chrome_trace,
    publish_comm_stats,
    save_chrome_trace,
    summary,
    to_chrome_trace,
    validate,
)


# ===========================================================================
# Tracer: recording, nesting, the jit guard
# ===========================================================================

class TestTracer:
    def test_spans_nest_by_open_context(self):
        tr = Tracer()
        with tr.span("outer") as o:
            with tr.span("inner") as i:
                pass
        assert o.parent_id is None
        assert i.parent_id == o.span_id
        assert o.duration >= i.duration >= 0.0

    def test_disabled_tracer_records_nothing(self):
        tr = Tracer(enabled=False)
        with tr.span("x") as sp:
            assert sp is None
        assert tr.add_manual("y", 0.0, 1.0) is None
        assert len(tr) == 0

    def test_span_cap_drops_and_counts(self):
        tr = Tracer(max_spans=2)
        for _ in range(5):
            with tr.span("s"):
                pass
        assert len(tr) == 2
        assert tr.dropped == 3
        tr.clear()
        assert len(tr) == 0 and tr.dropped == 0

    def test_attrs_mutable_until_exit(self):
        tr = Tracer()
        with tr.span("exchange") as sp:
            sp.attrs.update(fingerprint="fp", strategy="wire/uniform")
        assert tr.spans[0].attrs["fingerprint"] == "fp"

    def test_no_spans_inside_jit(self):
        # the tracer guard: a perf_counter pair inside a jax trace
        # measures tracing, not transfer — span() must record nothing
        tr = Tracer()
        seen = []

        @jax.jit
        def f(x):
            with tr.span("should-not-record") as sp:
                seen.append(sp)
            return x + 1

        f(jnp.zeros(4))
        assert seen == [None]
        assert len(tr) == 0
        assert not any(s.name == "should-not-record" for s in tr.spans)

    @pytest.mark.parametrize("stage", [
        lambda f, x: jax.block_until_ready(jax.jit(f)(x)),
        lambda f, x: jax.make_jaxpr(f)(x),
        jax.eval_shape,
    ], ids=["jit", "make_jaxpr", "eval_shape"])
    def test_inactive_while_tracing(self, stage):
        # the eager check reads the installed JAX's trace context: every
        # way of staging a function must switch the tracer off
        tr = Tracer()
        seen = []

        def f(x):
            seen.append(tr.active)
            return x + 1

        assert tr.active
        stage(f, jnp.zeros(4))
        assert seen == [False]
        assert tr.active

    def test_no_spans_inside_shard_map(self):
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map

        tr = Tracer()
        mesh = Mesh(np.array(jax.devices()[:1]), ("x",))

        def f(x):
            with tr.span("should-not-record") as sp:
                assert sp is None
            return x

        shard_map(f, mesh=mesh, in_specs=P(), out_specs=P())(jnp.zeros(4))
        assert len(tr) == 0

    def test_add_manual_nests_under_open_span(self):
        tr = Tracer()
        with tr.span("exchange") as ex:
            tr.add_manual("plan", 0.0, 1e-4, nsegments=3)
        plan = [s for s in tr.spans if s.name == "plan"][0]
        assert plan.parent_id == ex.span_id
        assert plan.attrs["nsegments"] == 3
        # explicit parent wins over the (now empty) stack
        child = tr.add_manual("pack", 0.0, 1e-5, parent=ex)
        assert child.parent_id == ex.span_id
        # no parent, empty stack -> root
        root = tr.add_manual("loose", 0.0, 1e-5)
        assert root.parent_id is None


def test_communicator_sendrecv_records_phase_spans(monkeypatch):
    # eager blocking sendrecv under the tracer: one exchange span
    # carrying the decision signature, with pack/wire/unpack children in
    # execution order.  The wire op is stubbed to a self-send (no eager
    # collective eval on CPU); pack/unpack run for real.
    from repro.comm import api
    from repro.core import BYTE, Vector

    monkeypatch.setattr(api.lax, "ppermute", lambda x, axis, perm: x)
    tr = Tracer()
    comm = api.Communicator(axis_name="x", tracer=tr)
    ct = comm.commit(Vector(4, 8, 16, BYTE))
    buf = jnp.arange(ct.extent, dtype=jnp.uint8)
    comm.sendrecv(buf, jnp.zeros_like(buf), ct, [(0, 0)])

    ex = [s for s in tr.spans if s.name == "exchange"]
    assert len(ex) == 1
    assert ex[0].attrs["fingerprint"] == ct.fingerprint
    assert ex[0].attrs["strategy"]
    assert ex[0].attrs["pred"] > 0.0
    kids = [s for s in tr.spans if s.parent_id == ex[0].span_id]
    assert [s.name for s in kids] == ["pack", "wire", "unpack"]
    assert all(s.attrs["pred"] >= 0.0 for s in kids)
    assert all(not s.attrs.get("attributed") for s in kids)


def test_communicator_sendrecv_under_jit_records_nothing(monkeypatch):
    from repro.comm import api
    from repro.core import BYTE, Vector

    monkeypatch.setattr(api.lax, "ppermute", lambda x, axis, perm: x)
    tr = Tracer()
    comm = api.Communicator(axis_name="x", tracer=tr)
    ct = comm.commit(Vector(4, 8, 16, BYTE))

    @jax.jit
    def step(buf):
        return comm.sendrecv(buf, jnp.zeros_like(buf), ct, [(0, 0)])

    step(jnp.arange(ct.extent, dtype=jnp.uint8))
    assert len(tr) == 0


def test_communicator_neighbor_alltoallv_span_hierarchy(monkeypatch):
    # the fused path: exchange > {plan, pack, wire, unpack}, decision
    # signature (plan fingerprint + schedule) on the exchange span
    from repro.comm import api
    from repro.core import BYTE, Vector

    monkeypatch.setattr(api.lax, "ppermute", lambda x, axis, perm: x)
    tr = Tracer()
    comm = api.Communicator(
        axis_name="x", tracer=tr, decisions=DecisionCache()
    )
    cts = [comm.commit(Vector(4, 8, 16, BYTE)),
           comm.commit(Vector(2, 16, 32, BYTE))]
    buf = jnp.arange(max(ct.extent for ct in cts), dtype=jnp.uint8)
    comm.neighbor_alltoallv(
        buf, cts, cts, [((0, 0),), ((0, 0),)]
    )

    by_name = {}
    for s in tr.spans:
        by_name.setdefault(s.name, []).append(s)
    assert len(by_name["exchange"]) == 1
    ex = by_name["exchange"][0]
    assert ex.attrs["strategy"].startswith("wire/")
    assert ex.attrs["fingerprint"]
    assert ex.attrs["wire_bytes"] > 0
    # plan/pack/wire/unpack all nest (directly) under the exchange
    for name in ("plan", "pack", "wire", "unpack"):
        assert by_name[name][0].parent_id == ex.span_id, name
    # the plan span carries its own prediction for the drift join
    assert by_name["plan"][0].attrs["pred"] > 0.0
    # the decision signature joins the decisions cache by fingerprint
    assert any(
        d.fingerprint == ex.attrs["fingerprint"]
        for d in comm.model.decisions.log
    )


# ===========================================================================
# compiled program iterations: one measured span each
# ===========================================================================

@pytest.fixture(scope="module")
def smoother_spans():
    """The tracer of a three-iteration compiled smoother run, and the
    run's report."""
    from repro.comm.api import Communicator
    from repro.launch.smoother import run_smoother

    tr = Tracer()
    comm = Communicator(
        axis_name="data", decisions=DecisionCache(), tracer=tr
    )
    report = run_smoother(comm, iters=3, interior=(8, 8, 8),
                          cycle="smooth", halo_steps=2)
    return tr, report


class TestCompiledIterationSpans:
    def test_one_measured_span_per_iteration(self, smoother_spans):
        tr, report = smoother_spans
        iters = [s for s in tr.spans if s.name == "program_iteration"]
        assert [s.attrs["iteration"] for s in iters] == [0, 1, 2]
        assert all(s.duration > 0 for s in iters)
        assert all(s.attrs["fingerprint"] == report.program.fingerprint
                   for s in iters)
        assert all(s.attrs["strategy"] == f"program/s={report.program.steps}"
                   and s.attrs["pred"] > 0 for s in iters)

    def test_no_invented_children(self, smoother_spans):
        # the phases of a compiled iteration are fused: the tracer
        # records none of them, so the drift feed gets no phase evidence
        # from it and no span is model-proportioned
        tr, _ = smoother_spans
        iters = {s.span_id for s in tr.spans if s.name == "program_iteration"}
        assert len(iters) == 3
        assert not [s for s in tr.spans if s.parent_id in iters]
        assert not {s.name for s in tr.spans} & {"pack", "wire", "unpack",
                                                 "stencil"}
        assert tr.phase_aggregates() == {}
        assert not any("attributed" in s.attrs for s in tr.spans)


def test_run_smoother_traced_exchanges_bounded_by_iterations(smoother_spans):
    # the launch loop records one measured span per compiled iteration
    # and no exchange span of its own: exchanges <= iterations is the
    # communication-avoidance invariant the CI trace check gates on
    tr, report = smoother_spans
    iters = [s for s in tr.spans if s.name == "program_iteration"]
    ex = [s for s in tr.spans if s.name == "exchange"]
    assert len(iters) == 3
    assert len(ex) <= len(iters)
    assert all(s.attrs["fingerprint"] == report.program.fingerprint
               for s in iters)
    assert validate(to_chrome_trace(tr)) == []


# ===========================================================================
# export: Chrome trace, aggregation, summary, validation
# ===========================================================================

def _sample_tracer() -> Tracer:
    tr = Tracer()
    it = tr.add_manual("program_iteration", 0.0, 1e-3,
                       fingerprint="fp1", strategy="program/s=2", steps=2)
    ex = tr.add_manual("exchange", 0.0, 6e-4, parent=it,
                       fingerprint="fp1", strategy="program/s=2",
                       schedule="uniform", wire_bytes=4096, pred=5e-4)
    tr.add_manual("pack", 0.0, 2e-4, parent=ex, pred=1e-4)
    tr.add_manual("wire", 2e-4, 2e-4, parent=ex, pred=2e-4)
    tr.add_manual("unpack", 4e-4, 2e-4, parent=ex, pred=2e-4)
    tr.add_manual("stencil", 6e-4, 2e-4, parent=it, pred=1e-4)
    tr.add_manual("stencil", 8e-4, 2e-4, parent=it, pred=1e-4)
    return tr


class TestExport:
    def test_chrome_trace_round_trip(self, tmp_path):
        tr = _sample_tracer()
        p = save_chrome_trace(tr, tmp_path / "t.json")
        trace = load_chrome_trace(p)
        assert trace["otherData"]["generator"] == "repro.obs"
        assert len(trace["traceEvents"]) == len(tr.spans)
        ev = trace["traceEvents"][1]
        assert ev["ph"] == "X" and ev["cat"] == "comm"
        assert ev["args"]["fingerprint"] == "fp1"
        assert ev["args"]["parent_id"] == tr.spans[0].span_id
        # aggregates computed from the file match the live tracer's
        # (timestamps round-trip through integer-ish microseconds)
        live = tr.phase_aggregates()
        from_file = aggregate_events(trace)
        assert set(from_file) == set(live)
        for fp, rec in live.items():
            assert set(from_file[fp]) == set(rec)
            for ph, r in rec.items():
                for k, v in r.items():
                    assert from_file[fp][ph][k] == pytest.approx(v), (ph, k)

    def test_numpy_attrs_export_jsonable(self, tmp_path):
        import numpy as np

        tr = Tracer()
        tr.add_manual("exchange", 0.0, 1e-4, fingerprint="f",
                      strategy="s", wire_bytes=np.int64(4096))
        s = json.dumps(to_chrome_trace(tr))
        assert json.loads(s)["traceEvents"][0]["args"]["wire_bytes"] == 4096

    def test_aggregate_credits_nearest_fingerprinted_ancestor(self):
        agg = aggregate_spans(_sample_tracer().spans)
        assert set(agg) == {"fp1"}
        rec = agg["fp1"]
        # pack/wire/unpack credited through the exchange, stencil
        # through the iteration — same decision key
        assert rec["pack"]["count"] == 1
        assert rec["stencil"]["count"] == 2
        assert rec["stencil"]["observed"] == pytest.approx(4e-4)
        assert rec["wire"]["predicted"] == pytest.approx(2e-4)
        # unparented phase spans are not credited anywhere
        lone = Tracer()
        lone.add_manual("pack", 0.0, 1e-4)
        assert aggregate_spans(lone.spans) == {}

    def test_summary_joins_observed_and_predicted(self):
        text = summary(to_chrome_trace(_sample_tracer()))
        assert "program_iteration" in text
        assert "fp1" in text and "program/s=2" in text
        assert "obs/pred" in text
        assert "uniform/4096B" in text
        # observed 2e-4 vs predicted 1e-4 on pack -> ratio 2.000
        assert "2.000" in text

    def test_validate_passes_well_formed(self):
        assert validate(to_chrome_trace(_sample_tracer())) == []

    def test_validate_flags_unsigned_exchange(self):
        tr = Tracer()
        tr.add_manual("exchange", 0.0, 1e-4, strategy="wire/uniform")
        errs = validate(to_chrome_trace(tr))
        assert any("fingerprint missing" in e for e in errs)

    def test_validate_flags_multi_exchange_iteration(self):
        tr = Tracer()
        it = tr.add_manual("program_iteration", 0.0, 1e-3,
                           fingerprint="f", strategy="program/s=2")
        for i in range(2):
            tr.add_manual("exchange", 0.0, 1e-4, parent=it,
                          fingerprint="f", strategy="s")
        errs = validate(to_chrome_trace(tr))
        assert any("2 exchanges in one iteration" in e for e in errs)

    def test_validate_flags_malformed_json(self):
        assert validate({}) == ["traceEvents missing or not a list"]
        errs = validate({"traceEvents": [{"name": "x", "ph": "B"}]})
        assert any("ph" in e for e in errs)

    def test_cli_validate_and_summary(self, tmp_path, capsys):
        from repro.obs.__main__ import main

        p = save_chrome_trace(_sample_tracer(), tmp_path / "t.json")
        assert main(["validate", str(p)]) == 0
        assert "trace OK" in capsys.readouterr().out
        assert main(["summary", str(p)]) == 0
        assert "program_iteration" in capsys.readouterr().out
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"traceEvents": [{"ph": "B"}]}))
        assert main(["validate", str(bad)]) == 1
        assert main(["validate", str(tmp_path / "missing.json")]) == 2


# ===========================================================================
# metrics
# ===========================================================================

class TestMetricsRegistry:
    def test_counters_and_gauges(self):
        m = MetricsRegistry()
        m.inc("a")
        m.inc("a", 2)
        m.set_gauge("g", 0.5)
        assert m.counter("a") == 3.0
        assert m.gauge("g") == 0.5
        assert len(m) == 2
        snap = m.snapshot()
        assert snap == {"counters": {"a": 3.0}, "gauges": {"g": 0.5}}

    def test_save_load_round_trip(self, tmp_path):
        m = MetricsRegistry()
        m.set_counter("comm.exchanges", 7)
        m.set_gauge("occ", 0.25)
        p = m.save(tmp_path / "metrics.json")
        back = MetricsRegistry.load(p)
        assert back.snapshot() == m.snapshot()
        # absent file -> empty registry
        assert len(MetricsRegistry.load(tmp_path / "nope.json")) == 0
        # format mismatch refused
        p.write_text(json.dumps({"format": 99}))
        with pytest.raises(ValueError, match="format"):
            MetricsRegistry.load(p)

    def test_report_renders_both_kinds(self):
        m = MetricsRegistry()
        m.inc("c", 2)
        m.set_gauge("g", 0.125)
        rep = m.report()
        assert "counter" in rep and "gauge" in rep and "0.1250" in rep


def test_publish_comm_stats_maps_counters_and_occupancy():
    tel = ExchangeTelemetry(capacity=4)
    tel.observe("k", 1e-4)
    tel.observe("k", 1e-4)
    m = MetricsRegistry()
    publish_comm_stats(
        {"wire_ops": 5, "wire_payload_bytes": 1024,
         "committed_types": 3, "commit_hits": 1,
         "model_lookups": 10, "model_hits": 4},
        telemetry=tel, registry=m,
    )
    assert m.counter("comm.exchanges") == 5
    assert m.counter("comm.wire_payload_bytes") == 1024
    assert m.counter("decisions.cache_hits") == 4
    assert m.counter("decisions.cache_misses") == 6
    assert m.counter("telemetry.observations") == 2
    assert m.gauge("telemetry.ring_occupancy") == pytest.approx(0.5)


def test_communicator_stats_publishes_to_default_registry():
    from repro.comm.api import Communicator

    comm = Communicator(axis_name="x")
    comm.stats()
    assert default_metrics().counter("comm.exchanges") >= 0
    assert "comm.committed_types" in default_metrics().snapshot()["counters"]


# ===========================================================================
# trace-sourced drift attribution
# ===========================================================================

def _decisions() -> DecisionCache:
    return DecisionCache([
        Decision("prog1", 0, 1, True, "program/s=2", 1e-5, 3e-5, 0.0,
                 "deep halo", 2048),
        Decision("ct1", 1, 1, True, "rows", 2e-6, 1e-5, 3e-6, "vec", 1024),
    ])


def _trace_agg(obs_scale: float, count: int = 4, key: str = "prog1") -> dict:
    # phase aggregates as Tracer.phase_aggregates() shapes them
    return {key: {
        ph: {"count": count, "observed": obs_scale * pred,
             "predicted": pred}
        for ph, pred in
        (("pack", 1e-5), ("wire", 2e-5), ("unpack", 1e-5),
         ("stencil", 4e-5))
    }}


class TestTraceDrift:
    def test_trace_gives_direct_term_attribution(self):
        from repro.comm.perfmodel import TPU_V5E
        from repro.fleet import DriftDetector

        trace = {**_trace_agg(10.0), **_trace_agg(10.0, key="ct1")}
        rep = DriftDetector(threshold=3.0, min_samples=4).audit(
            _decisions(), TPU_V5E, trace=trace
        )
        by_fp = {f.fingerprint: f for f in rep.findings}
        prog = by_fp["prog1"]
        assert prog.source == "trace"
        assert prog.drifted
        assert prog.samples == 4
        # program rows price wire + stencil terms; the trace supplies
        # both ratios directly
        assert set(prog.phase_ratios) == {"wire", "stencil"}
        assert prog.term in ("wire", "stencil")
        assert prog.phase_ratios["wire"] == pytest.approx(10.0)
        # a point-to-point row pools pack+unpack into pack_unpack
        ct = by_fp["ct1"]
        assert ct.source == "trace" and ct.drifted
        assert set(ct.phase_ratios) == {"wire", "pack_unpack"}
        assert ct.phase_ratios["pack_unpack"] == pytest.approx(10.0)

    def test_trace_drift_needs_min_samples(self):
        from repro.comm.perfmodel import TPU_V5E
        from repro.fleet import DriftDetector

        det = DriftDetector(threshold=3.0, min_samples=4)
        rep = det.audit(_decisions(), TPU_V5E, trace=_trace_agg(10.0, count=3))
        assert rep.drifted_count == 0  # 3 samples: outlier, not drift
        assert [f.source for f in rep.findings
                if f.fingerprint == "prog1"] == ["trace"]

    def test_in_band_trace_does_not_drift(self):
        from repro.comm.perfmodel import TPU_V5E
        from repro.fleet import DriftDetector

        rep = DriftDetector(threshold=3.0, min_samples=4).audit(
            _decisions(), TPU_V5E, trace=_trace_agg(1.1)
        )
        assert rep.drifted_count == 0
        prog = [f for f in rep.findings if f.fingerprint == "prog1"][0]
        assert prog.source == "trace"
        # the row without coverage stays interpolated
        ct = [f for f in rep.findings if f.fingerprint == "ct1"][0]
        assert ct.source == "interpolated" and not ct.drifted

    def test_format_1_reports_still_load(self):
        # DRIFT_FORMAT 1 predates the trace source: "params" rows load
        # as "interpolated" and phase_ratios default empty
        from repro.fleet import DriftReport

        old = {
            "format": 1, "system": "s", "threshold": 1.5,
            "min_samples": 3, "term_ratios": {"wire": 1.0},
            "findings": [{
                "fingerprint": "f", "strategy": "rows", "term": "",
                "ratio": 1.0, "drifted": False, "source": "params",
                "recorded_total": 1e-5, "repriced_total": 1e-5,
                "observed_mean": 0.0, "observed_ratio": 0.0,
                "samples": 0, "signature": "vec",
            }],
        }
        rep = DriftReport.from_json(json.dumps(old))
        assert rep.findings[0].source == "interpolated"
        assert rep.findings[0].phase_ratios == {}

    def test_current_report_round_trips_with_phase_ratios(self):
        from repro.comm.perfmodel import TPU_V5E
        from repro.fleet import DriftDetector, DriftReport
        from repro.fleet.drift import DRIFT_FORMAT

        rep = DriftDetector(threshold=3.0, min_samples=4).audit(
            _decisions(), TPU_V5E, trace=_trace_agg(10.0), system="t"
        )
        back = DriftReport.from_json(rep.to_json())
        assert back.to_json() == rep.to_json()
        assert json.loads(rep.to_json())["format"] == DRIFT_FORMAT
        prog = [f for f in back.findings if f.fingerprint == "prog1"][0]
        assert prog.phase_ratios["wire"] == pytest.approx(10.0)

    def test_tracer_aggregates_feed_audit_end_to_end(self, monkeypatch):
        # Tracer -> phase_aggregates -> audit, on the spans an eager
        # iteration measures phase by phase (a compiled iteration is one
        # span, with no phase evidence).  The wire ops are stubbed to a
        # self-send: one rank, no eager collective on the CPU.
        from repro.comm import api
        from repro.fleet import DriftDetector
        from repro.halo.program import build_halo_program
        from repro.launch.smoother import initial_state, smoother_cycle

        monkeypatch.setattr(api.lax, "ppermute", lambda x, axis, perm: x)
        monkeypatch.setattr(api.lax, "all_to_all", lambda x, axis, **kw: x)
        monkeypatch.setattr(api.lax, "axis_index", lambda axis: 0)
        tr = Tracer()
        decisions = DecisionCache()
        comm = api.Communicator(
            axis_name="data", decisions=decisions, tracer=tr
        )
        program = build_halo_program(
            (1, 1, 1), (8, 8, 8), comm, ops=smoother_cycle("smooth"),
            steps="auto", schedule_policy="exact",
        )
        x = jnp.asarray(initial_state(program, 0))
        for _ in range(2):
            x = program.iteration(x, comm, "data")
        rep = DriftDetector(min_samples=2).audit(
            decisions, comm.model.params, trace=tr.phase_aggregates()
        )
        prog = [f for f in rep.findings
                if f.strategy.startswith("program/")]
        assert len(prog) == 1
        assert prog[0].source == "trace"
        assert prog[0].phase_ratios  # direct per-term evidence on file
        assert prog[0].samples >= 2


# ===========================================================================
# fleet stats CLI
# ===========================================================================

def test_fleet_stats_cli_renders_persisted_metrics(tmp_path, capsys):
    from repro.fleet.__main__ import main
    from repro.obs.metrics import METRICS_FILENAME

    m = MetricsRegistry()
    m.set_counter("comm.exchanges", 12)
    m.set_gauge("telemetry.ring_occupancy", 0.5)
    m.save(tmp_path / METRICS_FILENAME)
    assert main(["stats", "--store", str(tmp_path), "--json"]) == 0
    out = capsys.readouterr().out
    assert "comm.exchanges" in out and "12" in out
    assert '"gauges"' in out
    # empty store: still exits 0 with an empty table
    assert main(["stats", "--store", str(tmp_path / "empty")]) == 0


# ===========================================================================
# named scopes in the compiled program, host spans on the profiler clock
# ===========================================================================

def _scope_resolver(mlir: str):
    """A function from an op line of a module lowered with debug info to
    the innermost ``tempi.`` scope of the op's location ('' if none)."""
    defs = dict(re.findall(r"^(#loc\d+) = (loc\(.*\))$", mlir, re.M))

    def names(loc: str, depth: int = 0):
        out = re.findall(r'"([^"]*)"', loc)
        for ref in re.findall(r"#loc\d+", loc):
            if depth < 32 and ref in defs:
                out += names(defs[ref], depth + 1)
        return out

    def scope_of(line: str) -> str:
        m = re.search(r"loc\((#loc\d+|\".*\")\)\s*$", line)
        paths = [n for n in names(m.group(1)) if "tempi." in n] if m else []
        found = re.findall(r"tempi\.[a-z]+", paths[0]) if paths else []
        return found[-1] if found else ""

    return scope_of


@pytest.fixture(scope="module")
def lowered_steps():
    """The program step for a small one-rank grid, lowered on the default
    path and with region-split overlap (debug info on)."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.comm.api import Communicator
    from repro.halo import build_halo_program, make_program_step

    comm = Communicator(axis_name="ranks", decisions=DecisionCache())
    program = build_halo_program((1, 1, 1), (8, 8, 8), comm, steps=2)
    mesh = Mesh(np.array(jax.devices()[:1]), ("ranks",))
    x = jax.ShapeDtypeStruct(program.spec.alloc, jnp.float32)
    return program, {
        overlap: make_program_step(program, comm, mesh, overlap=overlap)
        .lower(x).as_text(debug_info=True)
        for overlap in (False, "region")
    }


def _scopes_found(text: str) -> set:
    scope_of = _scope_resolver(text)
    return {scope_of(line) for line in text.splitlines()
            if "stablehlo." in line}


@pytest.fixture(scope="module")
def step_scopes(lowered_steps):
    _, text = lowered_steps
    return {overlap: _scopes_found(t) for overlap, t in text.items()}


@pytest.mark.parametrize("overlap", [False, "region"])
@pytest.mark.parametrize("name", ["pack", "unpack", "wire", "bytes",
                                  "stencil", "splice"])
def test_program_step_carries_scope(step_scopes, name, overlap):
    assert f"tempi.{name}" in step_scopes[overlap]


@pytest.mark.parametrize("overlap", [False, "region"])
def test_stencil_write_backs_are_splices(lowered_steps, overlap):
    # every write-back of an updated window into the fp32 field is a
    # whole-field splice, credited to tempi.splice and nothing else
    program, text = lowered_steps
    az, ay, ax = program.spec.alloc
    field = f"-> tensor<{az}x{ay}x{ax}xf32>"
    scope_of = _scope_resolver(text[overlap])
    dus = [line for line in text[overlap].splitlines()
           if "stablehlo.dynamic_update_slice" in line and field in line]
    assert dus
    assert {scope_of(line) for line in dus} == {"tempi.splice"}


@pytest.mark.parametrize("strategy", ["rows", "dma", "xla"])
@pytest.mark.parametrize("phase", ["pack", "unpack"])
def test_comm_pack_and_unpack_carry_scopes(strategy, phase):
    from repro.comm import Communicator, FixedPolicy
    from repro.core import BYTE, Vector

    comm = Communicator(policy=FixedPolicy(strategy))
    ct = comm.commit(Vector(64, 8, 512, BYTE))
    # a word buffer: its byte view is a relayout for every strategy
    buf = jax.ShapeDtypeStruct((ct.extent // 4,), jnp.float32)
    packed = jax.ShapeDtypeStruct((ct.size,), jnp.uint8)
    if phase == "pack":
        low = jax.jit(lambda b: comm.pack(b, ct)).lower(buf)
    else:
        low = jax.jit(lambda b, p: comm.unpack(b, p, ct)).lower(buf, packed)
    found = _scopes_found(low.as_text(debug_info=True))
    assert {f"tempi.{phase}", "tempi.bytes"} <= found
    if phase == "pack":
        assert "tempi.unpack" not in found


def _host_events(trace_dir):
    import pathlib

    from jax.profiler import ProfileData

    (path,) = pathlib.Path(trace_dir).rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return [e.name for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


@pytest.mark.parametrize("enabled", [True, False])
def test_tracer_span_is_a_profiler_host_span(tmp_path, enabled):
    # an active span is also a tempi.<name> host event of the profiler
    # trace, on the device's clock; a guarded-off span writes none
    tr = Tracer(enabled=enabled)
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("exchange"):
            jnp.ones(8).block_until_ready()

        @jax.jit
        def traced(x):
            with tr.span("pack"):  # inside a jax trace: guarded off
                return x + 1

        traced(jnp.ones(8)).block_until_ready()
    names = _host_events(tmp_path)
    assert names.count("tempi.exchange") == (1 if enabled else 0)
    assert "tempi.pack" not in names
    assert len(tr) == (1 if enabled else 0)
