"""In-launch diffusion-style smoother over the data axis.

The deep-halo :class:`~repro.halo.program.HaloProgram` layer existed
(PR 4) but no in-tree launch workload built one — ``--halo-steps`` on
``launch.train`` / ``launch.serve`` installed a default nobody read.
This module is that workload: a 3D scalar field sharded over the data
axis, smoothed by a stencil cycle compiled into ONE fused deep-halo
program — so the production communicator's calibrated tables price the
fusion depth, the choice lands in the job's decisions file as a
``program/s=N`` row, and a rerun pins it.  The train driver runs it as
a data-conditioning pass before the step loop; the serve driver runs it
once at deployment startup, before the serve loop is built; CI runs it
one step and asserts the decision row exists.

Cycles:

``smooth``
    the paper's 26-point op applied each repeat — the classic diffusion
    smoother.
``predictor-corrector``
    a two-op cycle: a far-reaching predictor (radii ``(2, 1, 1)`` —
    deeper along the slow/sharded axis) followed by a local corrector
    (the 26-point op at a lighter weight).  Unequal per-dimension radii
    exercise the cumulative-radii halo end to end.

    PYTHONPATH=src python -m repro.launch.smoother --iters 1 \
        --halo-steps auto --comm-cache /tmp/ci_store --assert-decision
"""

from __future__ import annotations

import argparse
from dataclasses import dataclass
from typing import Optional, Tuple, Union

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.comm.api import as_communicator
from repro.halo.program import (
    HaloProgram,
    build_halo_program,
    make_program_step,
)
from repro.halo.stencil import STENCIL26, StencilOp

__all__ = [
    "CYCLES", "SmootherReport", "initial_state", "run_smoother",
    "smoother_cycle",
]

#: the in-launch cycles by name (argparse choices on every driver)
CYCLES: Tuple[str, ...] = ("smooth", "predictor-corrector")


def smoother_cycle(name: str) -> Tuple[StencilOp, ...]:
    """The op cycle a ``--smoother-cycle`` name denotes."""
    if name == "smooth":
        return (STENCIL26,)
    if name == "predictor-corrector":
        return (StencilOp((2, 1, 1), weight=0.5), StencilOp((1, 1, 1), weight=0.25))
    raise ValueError(f"unknown smoother cycle {name!r}; expected one of {CYCLES}")


@dataclass(frozen=True)
class SmootherReport:
    """What one smoother run did — the launch drivers print it and the
    CI step asserts on it."""

    program: HaloProgram
    iterations: int
    checksum: float      # interior sum after the run (reproducibility probe)
    decision_recorded: bool  # a program/s=N row exists in the decisions
    field: jax.Array     # the sharded (ranks*az, ay, ax) state after the run

    @property
    def summary(self) -> str:
        p = self.program
        return (
            f"smoother: cycle_len={p.cycle_len} steps={p.steps}"
            f"{' (pinned)' if p.pinned else ''} "
            f"applications={self.iterations * p.applications} "
            f"exchanges/cycle={p.exchanges_per_cycle:.2f} "
            f"wire={p.plan.wire.schedule}/{p.plan.wire.issued_bytes}B "
            f"checksum={self.checksum:.6e}"
        )


def initial_state(program: HaloProgram, seed: int = 0) -> np.ndarray:
    """The smoother's starting field: standard-normal interiors drawn
    from ``seed``, zero halo shells, as the (ranks*az, ay, ax) global
    array the program step takes."""
    R = program.spec.nranks
    nz, ny, nx = program.spec.interior
    rz, ry, rx = program.spec.radii
    az, ay, ax = program.spec.alloc
    state = np.zeros((R, az, ay, ax), np.float32)
    state[:, rz:rz + nz, ry:ry + ny, rx:rx + nx] = (
        np.random.default_rng(seed).normal(size=(R, nz, ny, nx))
        .astype(np.float32)
    )
    return state.reshape(R * az, ay, ax)


def run_smoother(
    comm,
    iters: int = 1,
    interior: Tuple[int, int, int] = (8, 8, 8),
    cycle: str = "predictor-corrector",
    halo_steps: Union[int, str, None] = None,
    axis_name: str = "data",
    seed: int = 0,
    devices=None,
    overlap: str = "off",
    schedule_policy: Optional[str] = None,
) -> SmootherReport:
    """Smooth a sharded 3D field with one fused deep-halo program.

    The field is sharded over ``len(devices)`` ranks along the leading
    (slow) dimension — the data axis — with a periodic domain; each
    iteration is ONE exchange plus ``steps`` repeats of the cycle.
    ``halo_steps=None`` resolves through the process default
    (``--halo-steps`` / ``production_communicator(halo_steps=...)``), so
    this is the end-to-end path for the fusion-depth seam: with
    ``"auto"`` the depth is priced on the communicator's calibrated
    tables and recorded/pinned in its decisions cache.

    ``overlap`` selects exchange/compute overlap for the compiled step:
    ``"off"`` (the plain exchange-then-cycle iteration) or an overlap
    mode — ``"monolithic"``, ``"region"`` (per-delta-class drains feed
    the core/face/edge/corner region scheduler), or ``"auto"`` (the
    model picks and pins an ``overlap/mode=...`` decision).  All modes
    are bit-identical; the checksum must not move.

    ``schedule_policy`` is forwarded to the wire planner (``None``: the
    communicator's model-priced default; ``"exact"``: the byte-exact
    ladder, which takes the native ragged collective wherever the
    backend has it).
    """
    comm = as_communicator(comm)
    if overlap not in ("off", "monolithic", "region", "auto"):
        raise ValueError(
            f"unknown overlap {overlap!r}; expected off, monolithic, "
            "region or auto"
        )
    devs = list(devices if devices is not None else jax.devices())
    R = len(devs)
    grid = (R, 1, 1)
    ops = smoother_cycle(cycle)
    program = build_halo_program(
        grid, interior, comm, ops=ops, steps=halo_steps,
        schedule_policy=schedule_policy,
    )
    mesh = Mesh(np.array(devs), (axis_name,))
    step = make_program_step(
        program, comm, mesh, axis_name,
        overlap=False if overlap == "off" else overlap,
    )

    nz, ny, nx = interior
    rz, ry, rx = program.spec.radii
    az, ay, ax = program.spec.alloc
    x = jax.device_put(
        initial_state(program, seed), NamedSharding(mesh, P(axis_name))
    )
    telemetry = getattr(comm, "telemetry", None)
    tracer = getattr(comm, "tracer", None)
    if tracer is not None and not getattr(tracer, "enabled", False):
        tracer = None
    if telemetry is None and tracer is None:
        for _ in range(iters):
            x = step(x)
    else:
        # telemetry/tracing: the program runs jitted, so the
        # Communicator's eager probes never fire — time the compiled
        # step here instead.  AOT-compile first so compile time never
        # pollutes the samples, and block each iteration (async dispatch
        # would under-report).  The tracer records each iteration as one
        # measured span; its phases are fused, and a profiler trace
        # splits them by the program's named scopes.
        import time
        from contextlib import nullcontext

        from repro.fleet.telemetry import predict_program_iteration

        predicted = predict_program_iteration(program, comm.model)
        if telemetry is not None:
            telemetry.register(
                program.fingerprint, predicted, f"program/s={program.steps}"
            )
        try:
            run = step.lower(x).compile()
        except AttributeError:  # not a jit-wrapped callable
            run = step
        jax.block_until_ready(x)
        for i in range(iters):
            span = nullcontext() if tracer is None else tracer.span(
                "program_iteration", fingerprint=program.fingerprint,
                strategy=f"program/s={program.steps}", steps=program.steps,
                cycle_len=program.cycle_len, pinned=bool(program.pinned),
                pred=predicted, iteration=i,
            )
            with span:
                t0 = time.perf_counter()
                x = run(x)
                jax.block_until_ready(x)
                dt = time.perf_counter() - t0
            if telemetry is not None:
                telemetry.observe(program.fingerprint, dt)
    out = np.asarray(x).reshape(R, az, ay, ax)
    checksum = float(
        out[:, rz:rz + nz, ry:ry + ny, rx:rx + nx].sum()
    )
    decisions = comm.model.decisions
    recorded = bool(
        decisions is not None
        and any(
            d.fingerprint == program.fingerprint
            for d in decisions.program_rows()
        )
    )
    return SmootherReport(
        program=program,
        iterations=iters,
        checksum=checksum,
        decision_recorded=recorded,
        field=x,
    )


def main() -> None:
    ap = argparse.ArgumentParser(prog="repro.launch.smoother",
                                 description=__doc__)
    ap.add_argument("--iters", type=int, default=1)
    ap.add_argument("--interior", type=int, default=8,
                    help="interior cube side per rank")
    ap.add_argument("--cycle", default="predictor-corrector", choices=CYCLES)
    ap.add_argument("--halo-steps", default="auto", metavar="auto|N")
    ap.add_argument("--overlap", default="off",
                    choices=("off", "monolithic", "region", "auto"),
                    help="exchange/compute overlap for the compiled "
                         "step: off, monolithic (one wait), region "
                         "(per-delta-class drains feed the core/rim "
                         "scheduler), or auto (model-priced, pinned "
                         "as an overlap/mode=... decision)")
    ap.add_argument("--comm-cache", default=None, metavar="DIR",
                    help="measure-store root for the production "
                         "communicator (calibrated params + decisions "
                         "file; decisions are saved back)")
    ap.add_argument("--assert-decision", action="store_true",
                    help="exit 1 unless a program/s=N decision row was "
                         "recorded (or pinned) for this program — the "
                         "CI gate on the end-to-end --halo-steps seam")
    ap.add_argument("--telemetry", action="store_true",
                    help="attach the runtime exchange probe: per-"
                         "iteration wall time vs the model's prediction, "
                         "persisted to telemetry.json in the store "
                         "(render with `python -m repro.fleet report`)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record hierarchical spans (repro.obs) and "
                         "export a Chrome-trace JSON here — loadable in "
                         "Perfetto/chrome://tracing, rendered by "
                         "`python -m repro.obs summary`, validated by "
                         "`python -m repro.obs validate`")
    ap.add_argument("--drift-report", default=None, metavar="FILE",
                    help="write a DriftReport JSON after the run "
                         "(implies --telemetry)")
    ap.add_argument("--drift-reference", default=None, metavar="ENVELOPE",
                    help="reference params envelope for the drift audit "
                         "(default: self-audit on telemetry only)")
    ap.add_argument("--assert-no-drift", action="store_true",
                    help="exit 1 when the drift audit flags any decision "
                         "— the CI drift gate")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    from repro.halo.program import parse_halo_steps
    from repro.measure.production import production_communicator

    halo_steps = parse_halo_steps(args.halo_steps)
    want_telemetry = bool(
        args.telemetry or args.drift_report or args.assert_no_drift
    )
    comm, save_decisions = production_communicator(
        args.comm_cache, axis_name="data", halo_steps=halo_steps,
        telemetry=want_telemetry or None,
        tracer=bool(args.trace) or None,
    )
    n = args.interior
    report = run_smoother(comm, iters=args.iters, interior=(n, n, n),
                          cycle=args.cycle, overlap=args.overlap)
    print(report.summary)
    if args.trace:
        from repro.obs.export import save_chrome_trace

        path = save_chrome_trace(comm.tracer, args.trace)
        print(f"trace ({len(comm.tracer)} spans) -> {path}")
    rows = comm.model.decisions.program_rows()
    for d in rows:
        print(f"decision: {d.strategy} fp={d.fingerprint} {d.signature}")
    path = save_decisions()
    print(f"decisions -> {path}")
    if want_telemetry:
        print(comm.telemetry.report())
    if args.drift_report or args.assert_no_drift:
        from repro.fleet.drift import DriftDetector
        from repro.measure.store import ParamsStore

        reference = (
            ParamsStore.read_envelope(args.drift_reference)
            if args.drift_reference else None
        )
        if args.drift_reference and reference is None:
            raise SystemExit(
                f"unreadable reference envelope {args.drift_reference}"
            )
        trace_agg = (
            comm.tracer.phase_aggregates()
            if args.trace and getattr(comm, "tracer", None) is not None
            else None
        )
        drift = DriftDetector().audit(
            comm.model.decisions, comm.model.params,
            reference=reference, telemetry=comm.telemetry,
            system="smoother", trace=trace_agg,
        )
        print(drift.summary())
        if args.drift_report:
            print(f"drift report -> {drift.save(args.drift_report)}")
        if args.assert_no_drift and drift.drifted_count:
            raise SystemExit(
                f"DRIFT: {drift.drifted_count} decision(s) out of band"
            )
    if args.assert_decision:
        ok = report.decision_recorded or report.program.pinned
        if not ok:
            raise SystemExit(
                "no program/s=N decision row recorded for the smoother "
                "program — the --halo-steps auto seam is broken"
            )
        print("SMOOTHER_DECISION_OK")


if __name__ == "__main__":
    main()
