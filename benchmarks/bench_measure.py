"""Measurement subsystem benchmark: the calibrate -> store -> select
lifecycle on the running backend (paper §6.3's "record once, reuse"
binary, here over ALL model terms).

Reports the reduced-grid calibration cost, the measured term values the
model will interpolate, and the effect on selection: how often the
measured tables flip the decision the analytic constants would make.

``--telemetry-overhead`` measures the fleet layer's own cost: the
per-call price of the :class:`repro.fleet.ExchangeTelemetry` probe
against a pinned-decision exchange loop (the smoother's compiled deep-
halo step), so the observability layer is held to the same standard as
everything else it observes.  ``--assert-telemetry-overhead`` gates it
at <2%.  ``--trace-overhead`` / ``--assert-trace-overhead`` do the same
for the :mod:`repro.obs` tracer: the per-iteration cost of recording a
compiled iteration's measured span (with its profiler annotation), held
to the SAME budget.
"""

from __future__ import annotations

import argparse
import time

from benchmarks.common import emit, time_host_us
from repro.comm.perfmodel import PerfModel, TPU_V5E
from repro.core import BYTE, TypeRegistry, Vector
from repro.measure import DecisionCache, calibrate_params

REG = TypeRegistry()

#: the probe may add at most this fraction to a pinned-decision
#: exchange iteration (the --assert-telemetry-overhead gate)
TELEMETRY_OVERHEAD_BUDGET = 0.02


def run() -> None:
    t0 = time.perf_counter()
    params = calibrate_params(name="bench_reduced", reduced=True)
    emit("measure/calibrate-reduced", (time.perf_counter() - t0) * 1e6, "host")

    for strat, rows in sorted((params.pack_table or {}).items()):
        emit(f"measure/pack-table/{strat}", rows[0][2] * 1e6,
             f"points={len(rows)}")
    for strat, rows in sorted((params.unpack_table or {}).items()):
        emit(f"measure/unpack-table/{strat}", rows[0][2] * 1e6,
             f"points={len(rows)}")
    if params.wire_table:
        emit("measure/wire-smallest", params.wire_table[0][1] * 1e6,
             f"fit_lat={params.wire_latency};fit_bw={params.wire_bw}")

    # selection flips: measured tables vs analytic constants
    analytic = PerfModel(TPU_V5E)
    measured = PerfModel(params, decisions=DecisionCache())
    flips = 0
    cases = [(blk, kib) for blk in (8, 64, 512) for kib in (1, 16, 256)]
    for blk, kib in cases:
        count = max(kib * 1024 // blk, 1)
        ct = REG.commit(Vector(count, blk, max(512, 2 * blk), BYTE))
        a = analytic.select(ct).strategy
        m = measured.select(ct).strategy
        flips += a != m
        emit(f"measure/select/blk{blk}/{kib}KiB",
             measured.select(ct).total * 1e6, f"analytic={a};measured={m}")
    emit("measure/selection-flips", float(flips), f"of={len(cases)}")
    # the audit log doubles as the report artifact
    emit("measure/decisions-recorded", float(len(measured.decisions)), "audit")


def telemetry_overhead(iters: int = 30) -> float:
    """The probe's cost relative to one pinned-decision exchange loop
    iteration.

    The loop is the smoother's compiled deep-halo step — every strategy
    and depth decision pinned after the first iteration — timed by the
    probe itself (its ``mean`` is the per-iteration wall cost).  The
    probe's own per-call price is measured directly (one dict lookup +
    one ring write) rather than by differencing two noisy loop timings:
    the ratio is the overhead the probe adds when every iteration is
    observed, without the gate flapping on loop-to-loop noise.
    """
    from repro.comm.api import Communicator
    from repro.fleet import ExchangeTelemetry
    from repro.launch.smoother import run_smoother

    tel = ExchangeTelemetry()
    comm = Communicator(
        axis_name="data", decisions=DecisionCache(), telemetry=tel
    )
    report = run_smoother(
        comm, iters=iters, interior=(8, 8, 8), cycle="smooth", halo_steps=2
    )
    agg = tel.get(report.program.fingerprint)
    assert agg is not None and agg.count == iters
    t_iter = agg.mean
    t_probe = time_host_us(
        lambda: tel.observe(agg.key, t_iter), iters=2000
    ) * 1e-6
    overhead = t_probe / t_iter
    emit("measure/telemetry/exchange-iter", t_iter * 1e6,
         f"iters={iters};pinned={report.program.pinned}")
    emit("measure/telemetry/probe-call", t_probe * 1e6, "observe()")
    emit("measure/telemetry/overhead-pct", overhead * 100.0,
         f"budget={TELEMETRY_OVERHEAD_BUDGET * 100:.0f}%")
    return overhead


def trace_overhead(iters: int = 30) -> float:
    """The tracer's per-iteration cost relative to one pinned-decision
    exchange loop iteration — the span-recording analog of
    :func:`telemetry_overhead`, held to the same budget.

    A compiled deep-halo iteration is recorded as ONE
    ``program_iteration`` span (:meth:`repro.obs.trace.Tracer.span`,
    which also opens a ``jax.profiler.TraceAnnotation``), so that span's
    host cost *is* the probe price; it is measured directly against the
    loop's observed iteration time, like the telemetry probe.
    """
    from repro.comm.api import Communicator
    from repro.fleet import ExchangeTelemetry
    from repro.launch.smoother import run_smoother
    from repro.obs.trace import Tracer

    tel = ExchangeTelemetry()
    comm = Communicator(
        axis_name="data", decisions=DecisionCache(), telemetry=tel
    )
    report = run_smoother(
        comm, iters=iters, interior=(8, 8, 8), cycle="smooth", halo_steps=2
    )
    agg = tel.get(report.program.fingerprint)
    assert agg is not None and agg.count == iters
    t_iter = agg.mean
    tracer = Tracer()
    program = report.program

    def probe() -> None:
        with tracer.span(
            "program_iteration", fingerprint=program.fingerprint,
            strategy=f"program/s={program.steps}", steps=program.steps,
            cycle_len=program.cycle_len, pinned=bool(program.pinned),
            pred=t_iter, iteration=0,
        ):
            pass

    t_probe = time_host_us(probe, iters=500) * 1e-6
    overhead = t_probe / t_iter
    emit("measure/trace/exchange-iter", t_iter * 1e6,
         f"iters={iters};pinned={report.program.pinned}")
    emit("measure/trace/probe-call", t_probe * 1e6,
         "Tracer.span()")
    emit("measure/trace/overhead-pct", overhead * 100.0,
         f"budget={TELEMETRY_OVERHEAD_BUDGET * 100:.0f}%")
    return overhead


def main() -> None:
    ap = argparse.ArgumentParser(prog="benchmarks.bench_measure",
                                 description=__doc__)
    ap.add_argument("--telemetry-overhead", action="store_true",
                    help="measure only the telemetry probe's relative "
                         "cost (skips the calibration lifecycle rows)")
    ap.add_argument("--assert-telemetry-overhead", action="store_true",
                    help="exit 1 when the probe adds >= "
                         f"{TELEMETRY_OVERHEAD_BUDGET:.0%} to a pinned-"
                         "decision exchange iteration (implies "
                         "--telemetry-overhead)")
    ap.add_argument("--trace-overhead", action="store_true",
                    help="measure only the span tracer's relative cost "
                         "per compiled iteration (skips the calibration "
                         "lifecycle rows)")
    ap.add_argument("--assert-trace-overhead", action="store_true",
                    help="exit 1 when the tracer adds >= "
                         f"{TELEMETRY_OVERHEAD_BUDGET:.0%} to a pinned-"
                         "decision exchange iteration (implies "
                         "--trace-overhead)")
    args = ap.parse_args()
    probes_only = False
    if args.telemetry_overhead or args.assert_telemetry_overhead:
        probes_only = True
        overhead = telemetry_overhead()
        if (
            args.assert_telemetry_overhead
            and overhead >= TELEMETRY_OVERHEAD_BUDGET
        ):
            raise SystemExit(
                f"telemetry probe overhead {overhead:.2%} >= "
                f"{TELEMETRY_OVERHEAD_BUDGET:.0%} budget"
            )
    if args.trace_overhead or args.assert_trace_overhead:
        probes_only = True
        overhead = trace_overhead()
        if (
            args.assert_trace_overhead
            and overhead >= TELEMETRY_OVERHEAD_BUDGET
        ):
            raise SystemExit(
                f"trace probe overhead {overhead:.2%} >= "
                f"{TELEMETRY_OVERHEAD_BUDGET:.0%} budget"
            )
    if probes_only:
        return
    run()


if __name__ == "__main__":
    main()
