"""Structured tracing: hierarchical spans over the exchange stack.

The telemetry ring buffers (:mod:`repro.fleet.telemetry`) answer "is
this decision's *total* wall time tracking the model?" — one scalar per
decision key.  TEMPI's empirical claim is finer than that: the latency
of a non-contiguous exchange decomposes into pack / wire / unpack terms
the model prices *separately*, and Hunold et al. show the terms drift
independently.  This module records that decomposition as it happens:

* :class:`Span` — one timed region with free-form attributes.  The
  hierarchy mirrors the execution structure of an eager iteration (a
  compiled one is a single ``program_iteration`` span)::

      program_iteration            (one deep-halo iteration)
        exchange                   (the fused collective, decision-keyed)
          plan                     (host-side WirePlan construction)
          pack / wire / unpack     (the paper's three phases)
            wire_class × classes   (per-delta-class completion, under
                                    wire/unpack — region-split overlap)
        stencil × applications     (per-application compute)

  Every ``exchange`` span carries the decision signature: the
  fingerprint the :class:`~repro.measure.decisions.DecisionCache` keys
  on, the chosen strategy/schedule, ``wire_bytes``, and — for deep-halo
  programs — the fusion depth ``s=N``.  Phase spans carry the model's
  predicted seconds (``pred``), so an exported trace joins observed
  against predicted without the model in hand.

* :class:`Tracer` — the per-process recorder.  It is **tracer-guarded**
  exactly like the telemetry probe: a ``perf_counter`` pair inside a
  ``jit``/``shard_map`` trace measures tracing, not transfer, so
  :meth:`Tracer.span` records nothing unless
  ``jax.core.trace_ctx.is_top_level()`` says execution is eager (callers
  additionally skip on tracer *operands*, same as telemetry).  Eager
  paths time phases with ``block_until_ready`` at each span exit, and
  each recorded span also opens a ``jax.profiler.TraceAnnotation``
  named ``tempi.<name>``, so it lands in a profiler trace on the same
  clock as the device's operations.  A compiled iteration is one
  measured ``program_iteration`` span: its phases are fused, and their
  split comes from the device trace instead.

* :func:`scope` — ``jax.named_scope("tempi.<name>")``.  The program
  wraps each phase in one at trace time (``pack``, ``unpack``,
  ``bytes``, ``wire``, ``stencil``, ``splice``), which only adds HLO
  metadata: every instruction of the compiled program carries its
  innermost ``tempi.`` scope in its ``op_name``, so a profiler trace
  joined with the optimized HLO splits a compiled step's time into
  pack / unpack / bytes / wire / stencil / splice, with no run-time
  cost or switch.

Export to Chrome-trace JSON / text flamecharts lives in
:mod:`repro.obs.export`; ``python -m repro.obs`` is the CLI.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional

import jax

__all__ = [
    "TRACE_FORMAT",
    "PHASES",
    "DEFAULT_MAX_SPANS",
    "SCOPE_PREFIX",
    "Span",
    "Tracer",
    "scope",
]

#: bump when the exported span schema changes incompatibly
TRACE_FORMAT = 1

#: the phase span names drift attribution understands (module order is
#: the execution order inside an exchange)
PHASES = ("pack", "wire", "unpack", "stencil")

#: prefix of the program's named scopes and of its host spans in a
#: profiler trace
SCOPE_PREFIX = "tempi."

#: span-count cap — a million-iteration job must not grow an unbounded
#: trace; past the cap spans are dropped and counted, never an error
DEFAULT_MAX_SPANS = 200_000


def scope(name: str):
    """``jax.named_scope("tempi.<name>")``: a context manager, or a
    decorator, that credits the device operations traced inside it to
    the phase ``name``.  It only adds metadata to
    the HLO, so it costs nothing at run time."""
    return jax.named_scope(SCOPE_PREFIX + name)


def _trace_state_clean() -> bool:
    """True when no jax trace is being staged (eager execution)."""
    return jax.core.trace_ctx.is_top_level()


@dataclass(slots=True)
class Span:
    """One recorded region.  ``start`` is ``perf_counter`` seconds (the
    tracer exports relative to its earliest span); ``attrs`` is free-form
    but ``exchange`` spans carry the decision signature and phase spans
    carry the model's predicted seconds under ``pred``."""

    name: str
    start: float
    duration: float
    span_id: int
    parent_id: Optional[int] = None
    attrs: Dict[str, object] = field(default_factory=dict)


class Tracer:
    """Low-overhead hierarchical span recorder (process-local).

    Attach to a :class:`~repro.comm.api.Communicator` (``tracer=...``)
    or request one from ``production_communicator(tracer=True)``; the
    launch drivers expose it as ``--trace PATH``.
    """

    def __init__(self, enabled: bool = True,
                 max_spans: int = DEFAULT_MAX_SPANS):
        self.enabled = bool(enabled)
        self.max_spans = int(max_spans)
        self.dropped = 0
        self._spans: List[Span] = []
        self._stack: List[int] = []
        self._next_id = 0

    # -- state -----------------------------------------------------------
    @property
    def active(self) -> bool:
        """Whether :meth:`span` would record right now: enabled AND not
        inside a jax trace (the tracer guard)."""
        return self.enabled and _trace_state_clean()

    @property
    def spans(self) -> List[Span]:
        return self._spans

    def __len__(self) -> int:
        return len(self._spans)

    def clear(self) -> None:
        self._spans.clear()
        self._stack.clear()
        self.dropped = 0
        self._next_id = 0

    # -- recording -------------------------------------------------------
    def _alloc(self, name: str, start: float, duration: float,
               parent_id: Optional[int], attrs: Dict[str, object]
               ) -> Optional[Span]:
        spans = self._spans
        if len(spans) >= self.max_spans:
            self.dropped += 1
            return None
        sp = Span(name, start, duration, self._next_id, parent_id, attrs)
        self._next_id += 1
        spans.append(sp)
        return sp

    @contextmanager
    def span(self, name: str, **attrs) -> Iterator[Optional[Span]]:
        """Record a timed region.  Yields the :class:`Span` (mutate
        ``.attrs`` freely before exit) — or ``None`` when guarded off
        (inside a jax trace, disabled, or at the span cap), in which
        case nothing is recorded and the body runs untouched.  A
        recorded span is also a ``tempi.<name>`` host span of any
        profiler trace that is running.

        The caller owns synchronization: block (``block_until_ready``)
        before exit or the span under-reports async dispatch.
        """
        if not self.active:
            yield None
            return
        parent = self._stack[-1] if self._stack else None
        sp = self._alloc(name, time.perf_counter(), 0.0, parent, attrs)
        if sp is None:
            yield None
            return
        self._stack.append(sp.span_id)
        try:
            with jax.profiler.TraceAnnotation(SCOPE_PREFIX + name):
                yield sp
        finally:
            sp.duration = time.perf_counter() - sp.start
            self._stack.pop()

    def add_manual(self, name: str, start: float, duration: float,
                   parent: Optional[Span] = None, **attrs) -> Optional[Span]:
        """Record a span with explicit timing (a compiled iteration timed
        around its blocking call, host-side planning timed outside a
        ``with``).
        Nests under ``parent`` when given, else under the innermost open
        :meth:`span`, else at the root."""
        if not self.enabled:
            return None
        parent_id = (
            parent.span_id if parent is not None
            else (self._stack[-1] if self._stack else None)
        )
        return self._alloc(name, float(start), float(duration), parent_id,
                           attrs)

    # -- aggregation -----------------------------------------------------
    def phase_aggregates(self) -> Dict[str, Dict[str, Dict[str, float]]]:
        """Per-decision-fingerprint phase sums for drift attribution:
        ``{fingerprint: {phase: {count, observed, predicted}}}``.  Each
        phase span is credited to the nearest enclosing span that
        carries a ``fingerprint`` attribute (the decision key).  See
        :func:`repro.obs.export.aggregate_phases`."""
        from repro.obs.export import aggregate_spans

        return aggregate_spans(self._spans)
