"""HaloProgram: communication-avoiding deep-halo stencil schedules.

TEMPI's discipline is that an interposed layer with empirical system
measurements should restructure non-contiguous communication wherever
the model says it wins.  The one-exchange-per-step halo loop leaves the
biggest knob untouched: *how often* to exchange.  A
:class:`HaloProgram` compiles the alternative — exchange a halo of
depth ``s * r`` ONCE, then apply ``s`` stencil steps locally over a
shrinking valid region (:func:`repro.halo.stencil.stencil_steps`) — and
lets :meth:`repro.comm.perfmodel.PerfModel.price_program` choose ``s``
from the same measured wire/copy tables every other strategy selection
uses: deeper halos buy fewer collective launches and amortized wire
latency at the price of more wire bytes per exchange and redundant
ghost-shell compute.  Nothing is heuristic; the chosen depth is recorded
in the :class:`~repro.measure.decisions.DecisionCache` like any other
strategy selection, so ``--halo-steps auto`` is reproducible (pinned)
across runs and auditable in the decisions file.

Lifecycle (all host-side, paid once):

```
op + grid + interior ──▶ candidate depths s=1..max ──▶ price_program
       │                        (deep HaloSpec,            │
       │                         deep-halo WirePlan)       ▼
       └────────────── pinned? ◀── DecisionCache ◀── argmin per-step
                                                        cost
```

then per iteration: ONE fused exchange (the depth-``s*r`` region types
are just bigger canonical strided blocks — the ragged wire path at new
sizes) + ``s`` shrinking-region applications, bit-exact on the interior
against the step-per-exchange reference.

Programs also fuse heterogeneous *cycles*: ``build_halo_program(ops=
[op_a, op_b], steps=s)`` exchanges ONE halo of depth
``s * cycle_radii([op_a, op_b])`` (the per-op radii summed, per
dimension) and applies the cycle ``s`` times over the per-application
shrinking valid region — the predictor/corrector and smoother patterns
that dominate real stencil codes ride the same mechanism, priced per
application by the same model.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from functools import cached_property
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.comm.api import as_communicator
from repro.comm.perfmodel import ProgramEstimate
from repro.core.datatypes import FLOAT, Named
from repro.halo.exchange import HaloPlan, HaloSpec, halo_exchange, make_halo_plan
from repro.halo.stencil import (
    STENCIL26,
    Ops,
    StencilOp,
    as_ops,
    cycle_halo_radii,
    cycle_radii,
    overlapped_stencil_iteration,
    stencil_cycle,
)

__all__ = [
    "HaloProgram",
    "build_halo_program",
    "make_program_step",
    "program_fingerprint",
    "parse_halo_steps",
    "get_default_halo_steps",
    "set_default_halo_steps",
    "MAX_AUTO_STEPS",
]

#: deepest fusion the auto chooser considers (bounded: past a few steps
#: the ghost shells dominate any realistic wire saving)
MAX_AUTO_STEPS = 3

#: process default for ``steps=None`` — what ``--halo-steps`` on the
#: launch drivers configures for every program the job builds
_DEFAULT_HALO_STEPS: Union[int, str] = "auto"


def parse_halo_steps(value: Union[str, int]) -> Union[int, str]:
    """CLI value of ``--halo-steps``: ``"auto"`` or a positive int."""
    if value == "auto":
        return "auto"
    steps = int(value)
    if steps < 1:
        raise ValueError(f"--halo-steps must be >= 1 or 'auto', got {value!r}")
    return steps


def get_default_halo_steps() -> Union[int, str]:
    return _DEFAULT_HALO_STEPS


def set_default_halo_steps(steps: Union[int, str]) -> Union[int, str]:
    """Set the process-wide default fusion depth (the launch drivers'
    ``--halo-steps`` lands here; programs built with ``steps=None`` use
    it)."""
    global _DEFAULT_HALO_STEPS
    _DEFAULT_HALO_STEPS = parse_halo_steps(steps)
    return _DEFAULT_HALO_STEPS


def program_fingerprint(
    grid: Tuple[int, int, int],
    interior: Tuple[int, int, int],
    op: Ops,
    element: Named,
    topology_fingerprint: str = "",
) -> str:
    """Stable content hash of a program's geometry — the DecisionCache
    key that pins ``--halo-steps auto`` across processes (the analogue
    of ``CommittedType.fingerprint`` for per-type selections).

    ``op`` is one :class:`StencilOp` or a cycle of them.  Single-op
    programs keep the original (v1) key so decision files recorded
    before cycles existed still pin; a cycle hashes every op in
    application order under a v2 key (``[a, b] != [b, a]`` — the
    shrinking-region schedule is order-sensitive).

    ``topology_fingerprint`` (a :attr:`repro.comm.topology.Topology.
    fingerprint`) is appended to the key only when non-empty, so pins
    recorded without a topology keep their keys — but a ``program/s=N``
    pinned on a 2x2x2 mesh can never be replayed on a reshaped mesh: a
    different topology is a different fingerprint, which is a decision
    cache *miss*.
    """
    ops = as_ops(op)
    if len(ops) == 1:
        key = (
            "haloprogram.v1",
            tuple(grid),
            tuple(interior),
            tuple(ops[0].radii),
            float(ops[0].weight),
            element.name,
            element.size,
        )
    else:
        key = (
            "haloprogram.v2",
            tuple(grid),
            tuple(interior),
            tuple((tuple(o.radii), float(o.weight)) for o in ops),
            element.name,
            element.size,
        )
    if topology_fingerprint:
        key = key + (topology_fingerprint,)
    return hashlib.sha256(repr(key).encode()).hexdigest()[:16]


def _describe_cycle(ops: Tuple[StencilOp, ...]) -> str:
    """Short human-readable cycle signature for the audit log."""
    return "[" + ",".join(
        f"{'x'.join(map(str, o.radii))}w{o.weight:g}" for o in ops
    ) + "]"


@dataclass(frozen=True)
class HaloProgram:
    """A compiled deep-halo schedule: {exchange at depth
    ``steps * cycle_radii(ops)``, apply the op cycle ``steps`` times
    over the shrinking valid region}.

    ``ops`` is the heterogeneous cycle applied in order each repeat —
    ``(STENCIL26,)`` is the classic single-op program, a
    predictor/corrector pair is ``(op_a, op_b)``.  Build with
    :func:`build_halo_program`; every per-iteration cost after that is
    device compute plus the prebuilt :class:`HaloPlan`'s dictionary
    lookups.
    """

    spec: HaloSpec              # deep geometry: radius == steps * cycle_radii
    ops: Tuple[StencilOp, ...]
    steps: int                  # cycle repeats per iteration
    plan: HaloPlan              # the one exchange, at the deep radius
    estimate: ProgramEstimate   # model price that selected (or priced) steps
    candidates: Tuple[ProgramEstimate, ...] = ()  # every depth priced
    pinned: bool = False        # steps came from a pinned Decision
    #: topology fingerprint the program was planned under ("" = flat);
    #: part of the decision key so mesh reshapes never replay this pin
    topology_fingerprint: str = ""

    @property
    def op(self) -> StencilOp:
        """The single op of a one-op cycle (raises on real cycles — a
        heterogeneous program has no 'the' op)."""
        if len(self.ops) != 1:
            raise ValueError(
                f"program fuses a {len(self.ops)}-op cycle; inspect .ops"
            )
        return self.ops[0]

    @property
    def cycle_len(self) -> int:
        return len(self.ops)

    @property
    def applications(self) -> int:
        """Stencil applications per iteration (``steps * cycle_len``)."""
        return self.steps * len(self.ops)

    @property
    def exchanges_per_step(self) -> float:
        """Exchange collectives issued per stencil application — the
        communication-avoidance figure the CI gate asserts."""
        return 1.0 / self.applications

    @property
    def exchanges_per_cycle(self) -> float:
        """Exchange collectives issued per cycle repeat (``1/steps``) —
        the cycle-mode CI gate asserts this is ``<= 1``."""
        return 1.0 / self.steps

    @cached_property
    def fingerprint(self) -> str:
        # content hash over frozen fields; cached because the tracer's
        # per-iteration hook reads it on the launch hot loop
        return program_fingerprint(
            self.spec.grid, self.spec.interior, self.ops, self.spec.element,
            self.topology_fingerprint,
        )

    def iteration(
        self,
        local: jax.Array,
        comm,
        axis_name: str = "ranks",
        overlap=False,
        probe: Optional[dict] = None,
    ) -> jax.Array:
        """One program iteration: ONE fused exchange + ``steps`` repeats
        of the shrinking-region op cycle.  With ``overlap`` the wire op
        hides behind the steps-deep interior chain: ``True`` (or
        ``"monolithic"``) waits for the whole fused collective,
        ``"region"`` drains per-delta-class requests and computes each
        core/face/edge/corner region as its classes land, ``"auto"``
        lets the model pick (pinned as ``overlap/mode=...`` — see
        :func:`repro.halo.stencil.overlapped_stencil_iteration`).

        When the communicator carries a :class:`repro.obs.Tracer` and
        the call is eager (no jax trace, no tracer operands), the
        iteration records the full span hierarchy: ``program_iteration``
        hosting the fused ``exchange`` (with its pack/wire/unpack
        phases, via :meth:`Communicator.neighbor_alltoallv`) and one
        ``stencil`` span per application — each phase blocked at its
        boundary.  Jitted runs skip this entirely: the launch layer
        records a compiled iteration as one measured span, and a
        profiler trace splits it by the ``tempi.*`` named scopes."""
        if overlap:
            mode = "monolithic" if overlap is True else str(overlap)
            return overlapped_stencil_iteration(
                local, self.spec, comm, axis_name,
                steps=self.steps, probe=probe, plan=self.plan, op=self.ops,
                mode=mode,
            )
        comm = as_communicator(comm)
        tracer = getattr(comm, "tracer", None)
        if (
            tracer is not None
            and tracer.active
            and not isinstance(local, jax.core.Tracer)
        ):
            return self._traced_iteration(local, comm, axis_name, tracer)
        local = halo_exchange(local, self.spec, comm, axis_name, plan=self.plan)
        return stencil_cycle(local, self.spec, self.ops, self.steps)

    def _traced_iteration(
        self, local: jax.Array, comm, axis_name: str, tracer
    ) -> jax.Array:
        """Eager iteration under the tracer: spans per phase, blocking
        at each boundary (a debug/observation path — the hot path is the
        jitted ``make_program_step``)."""
        from repro.fleet.telemetry import predict_program_phases
        from repro.halo.stencil import op_sequence, stencil_apply

        try:
            phases = predict_program_phases(self, comm.model)
        except Exception:
            phases = {}
        napp = max(self.applications, 1)
        with tracer.span(
            "program_iteration",
            fingerprint=self.fingerprint,
            strategy=f"program/s={self.steps}",
            steps=self.steps, cycle_len=self.cycle_len,
            pinned=bool(self.pinned),
            pred=sum(phases.values()),
        ):
            # the fused exchange span (and its pack/wire/unpack
            # children) is recorded by the blocking Communicator path
            local = comm.neighbor_alltoallv(
                local, self.plan.send_cts, self.plan.recv_cts,
                self.plan.perms, axis_name, plan=self.plan.wire,
                strategies=self.plan.strategies,
            )
            valid = self.spec.radii
            pred_app = phases.get("stencil", 0.0) / napp
            for i, o in enumerate(op_sequence(self.ops, self.steps)):
                with tracer.span(
                    "stencil", application=i, op=i % self.cycle_len,
                    pred=pred_app,
                ):
                    local = stencil_apply(local, self.spec, valid, o)
                    jax.block_until_ready(local)
                valid = tuple(v - r for v, r in zip(valid, o.radii))
        return local


def _feasible_steps(
    interior: Tuple[int, int, int], ops: Tuple[StencilOp, ...], max_steps: int
) -> List[int]:
    """Repeat counts whose halo (= send-slab depth ``s * cycle_radii``)
    still fits inside the interior in every dimension."""
    cr = cycle_radii(ops)
    return [
        s
        for s in range(1, max_steps + 1)
        if all(s * r <= n for n, r in zip(interior, cr))
    ]


def _price_candidate(
    comm,
    grid: Tuple[int, int, int],
    interior: Tuple[int, int, int],
    ops: Tuple[StencilOp, ...],
    steps: int,
    element: Named,
    schedule_policy: Optional[str],
) -> Tuple[HaloSpec, HaloPlan, ProgramEstimate]:
    """Build the deep geometry + wire plan for one candidate repeat
    count and price the full iteration: member pack/unpack + wire per
    exchange, redundant ghost-shell compute per fused application."""
    spec = HaloSpec(
        grid=grid, interior=interior,
        radius=cycle_halo_radii(ops, steps),
        element=element,
    )
    plan = make_halo_plan(spec, comm, schedule_policy=schedule_policy)
    model = comm.model
    t_members = 0.0
    for ct, strat in zip(plan.send_cts, plan.strategies):
        est = model.estimate(ct, 1, strat)
        t_members += est.t_pack + est.t_unpack
    estimate = model.price_program(
        plan.wire,
        interior,
        [o.radii for o in ops],
        [o.nneighbors for o in ops],
        steps,
        element_bytes=element.size,
        t_members=t_members,
        axis=model.axis,
    )
    return spec, plan, estimate


def build_halo_program(
    grid: Tuple[int, int, int],
    interior: Tuple[int, int, int],
    comm,
    op: StencilOp = STENCIL26,
    steps: Union[int, str, None] = None,
    element: Named = FLOAT,
    max_steps: int = MAX_AUTO_STEPS,
    schedule_policy: Optional[str] = None,
    ops: Optional[Sequence[StencilOp]] = None,
) -> HaloProgram:
    """Compile a deep-halo program for one rank geometry.

    ``ops`` fuses a heterogeneous *cycle* ``[op_1..op_k]`` applied in
    order each repeat (``op`` is the single-op shorthand and is ignored
    when ``ops`` is given).  One exchange at halo depth
    ``steps * cycle_radii(ops)`` then hosts ``steps`` whole cycle
    passes.

    ``steps`` counts cycle repeats: a fixed count, ``"auto"`` (the model
    prices every feasible count and takes the cheapest per stencil
    application), or ``None`` (the process default — ``--halo-steps`` on
    the launch drivers).  With ``"auto"`` and a communicator that
    carries a :class:`~repro.measure.decisions.DecisionCache`, the
    choice is looked up first and recorded after — reruns pin it, the
    audit log shows it, CI can assert it.

    ``schedule_policy`` is forwarded to the wire planner (``None`` =
    the communicator's default — model-priced; pass ``"exact"`` for the
    byte-exact ladder the wire-bytes gates assert).
    """
    comm = as_communicator(comm)
    ops = as_ops(ops if ops is not None else op)
    if steps is None:
        steps = get_default_halo_steps()
    topo = getattr(comm.model, "topology", None)
    topo_fp = topo.fingerprint if topo is not None else ""
    fp = program_fingerprint(grid, interior, ops, element, topo_fp)
    decisions = comm.model.decisions
    candidates: Tuple[ProgramEstimate, ...] = ()
    pinned = False
    built: Optional[Tuple[HaloSpec, HaloPlan, ProgramEstimate]] = None

    if steps == "auto":
        feasible = _feasible_steps(interior, ops, max_steps)
        if not feasible:
            raise ValueError(
                f"no feasible fusion depth: interior {interior} cannot host "
                f"a depth-{cycle_radii(ops)} halo"
            )
        pin = decisions.lookup(fp, 0, 1, True) if decisions is not None else None
        if (
            pin is not None
            and pin.strategy.startswith("program/s=")
            # a pin recorded under a looser cap (or different geometry
            # assumptions) must not smuggle in a depth this caller's
            # max_steps/feasibility would refuse
            and int(pin.strategy.split("=", 1)[1]) in feasible
        ):
            steps = int(pin.strategy.split("=", 1)[1])
            pinned = True
        else:
            priced: Dict[int, Tuple[HaloSpec, HaloPlan, ProgramEstimate]] = {
                s: _price_candidate(
                    comm, grid, interior, ops, s, element, schedule_policy
                )
                for s in feasible
            }
            candidates = tuple(priced[s][2] for s in feasible)
            steps = min(priced, key=lambda s: priced[s][2].per_step)
            built = priced[steps]
            if decisions is not None:
                from repro.comm.perfmodel import StrategyEstimate

                best = priced[steps][2]
                decisions.record(
                    fp, 0, 1, True,
                    StrategyEstimate(
                        f"program/s={steps}",
                        t_pack=best.t_redundant,
                        t_link=best.t_exchange,
                        t_unpack=0.0,
                        wire_bytes=best.wire_bytes,
                    ),
                    signature=(
                        f"halo program grid={tuple(grid)} "
                        f"interior={tuple(interior)} "
                        f"cycle={_describe_cycle(ops)} "
                        + (f"topo={topo_fp} " if topo_fp else "")
                        + " ".join(
                            f"s={e.steps}:{e.per_step:.3e}" for e in candidates
                        )
                    ),
                )
    else:
        steps = parse_halo_steps(steps)
        if steps not in _feasible_steps(interior, ops, steps):
            raise ValueError(
                f"interior {interior} cannot host a depth-"
                f"{cycle_halo_radii(ops, steps)} halo "
                "(send slabs exceed the interior)"
            )

    if built is None:
        built = _price_candidate(
            comm, grid, interior, ops, steps, element, schedule_policy
        )
    spec, plan, estimate = built
    return HaloProgram(
        spec=spec, ops=ops, steps=steps, plan=plan, estimate=estimate,
        candidates=candidates, pinned=pinned, topology_fingerprint=topo_fp,
    )


def make_program_step(
    program: HaloProgram,
    comm,
    mesh: Mesh,
    axis_name: str = "ranks",
    overlap=False,
):
    """jit-compiled shard_map wrapper over one program iteration:
    (nranks*az, ay, ax) global array, sharded on the leading axis ->
    one exchange + ``program.steps`` stencil applications.  ``overlap``
    is a bool or an overlap-mode string (``"monolithic"``/``"region"``/
    ``"auto"``), forwarded to :meth:`HaloProgram.iteration`."""
    comm = as_communicator(comm)

    def step(local):
        return program.iteration(local, comm, axis_name, overlap=overlap)

    fn = shard_map(
        step,
        mesh=mesh,
        in_specs=P(axis_name),
        out_specs=P(axis_name),
        check_vma=False,
    )
    return jax.jit(fn)
