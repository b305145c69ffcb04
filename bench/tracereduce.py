"""Reduction of a profiler trace to the numbers the layer metrics read.

A traced run writes one ``.xplane.pb`` under its trace directory.
:func:`load` reads it into a :class:`Trace`: for each device plane the
intervals of its operations, and the host spans that the harness and
the drivers open with ``jax.profiler.TraceAnnotation`` (names starting
with ``bench.``).  :func:`reduce` then works on plain tuples, so the
arithmetic is tested on synthetic traces without a chip.

Which planes are devices, which of their lines hold operations, and
which operation names form a class (collectives, Pallas kernels) is
data, in ``op_classes.json`` beside this file.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "Interval", "Trace", "OpClasses", "load", "reduce", "short_name", "union",
    "busy_ns", "gaps", "label_gap",
]

HERE = Path(__file__).resolve().parent

#: (start_ns, end_ns, name)
Interval = Tuple[float, float, str]


@dataclass(frozen=True)
class OpClasses:
    """What ``op_classes.json`` says about a trace's layout."""

    device_plane: str          # regex on plane names
    op_lines: Tuple[str, ...]  # regexes on the names of lines of operations
    classes: Dict[str, Tuple[str, ...]]  # class -> regexes on op names

    @classmethod
    def load(cls, path: Path = HERE / "op_classes.json") -> "OpClasses":
        raw = json.loads(Path(path).read_text())
        return cls(
            device_plane=raw["device_plane"],
            op_lines=tuple(raw["op_lines"]),
            classes={k: tuple(v) for k, v in raw["classes"].items()},
        )

    def classify(self, name: str) -> Optional[str]:
        """The first class one of whose patterns matches ``name``."""
        for label, patterns in self.classes.items():
            if any(re.search(p, name) for p in patterns):
                return label
        return None


@dataclass
class Trace:
    """Device operations per device plane and the harness's host spans."""

    devices: Dict[str, List[Interval]] = field(default_factory=dict)
    host: List[Interval] = field(default_factory=list)

    def span(self, name: str) -> Tuple[float, float]:
        """(start, end) of the one host span called ``name``."""
        found = [(s, e) for s, e, n in self.host if n == name]
        if len(found) != 1:
            raise ValueError(f"{len(found)} host spans named {name!r}")
        return found[0]


def load(trace_dir: Path, classes: OpClasses) -> Trace:
    """Read the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(
            f"{len(files)} .xplane.pb files under {trace_dir}, expected 1"
        )
    data = ProfileData.from_file(str(files[0]))
    names: Dict[str, str] = {}
    trace = Trace()
    plane_re = re.compile(classes.device_plane)
    line_res = [re.compile(p) for p in classes.op_lines]
    for plane in data.planes:
        device = plane_re.search(plane.name) is not None
        if device:
            ops = trace.devices.setdefault(plane.name, [])
        for line in plane.lines:
            if device and any(r.search(line.name) for r in line_res):
                for e in line.events:
                    if e.name not in names:
                        names[e.name] = short_name(e.name)
                    ops.append((e.start_ns, e.end_ns, names[e.name]))
            if plane.name.startswith("/host:"):
                trace.host.extend(
                    (e.start_ns, e.end_ns, e.name)
                    for e in line.events if e.name.startswith("bench.")
                )
    return trace


def short_name(hlo: str) -> str:
    """``<instruction> <opcode> <shape>`` of an operation that the trace
    names by its whole HLO text (``%fusion.5 = f32[..]{..} fusion(..)``):
    the operands are left out, so a pattern that names an opcode does
    not match the operations that consume its result."""
    lhs, sep, rhs = hlo.partition(" = ")
    if not sep:
        return hlo
    m = re.search(r" ([a-z][a-z0-9_-]*)\(", rhs)
    opcode = m.group(1) if m else "?"
    shape = rhs.split("{", 1)[0].split(" ", 1)[0][:60]
    return f"{lhs.lstrip('%')} {opcode} {shape}"


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """The intervals clipped to [lo, hi] and merged where they touch."""
    spans = sorted(
        (max(s, lo), min(e, hi)) for s, e, _ in intervals if e > lo and s < hi
    )
    merged: List[Tuple[float, float]] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def busy_ns(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside [lo, hi]."""
    return sum(e - s for s, e in union(intervals, lo, hi))


def gaps(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] that no interval covers."""
    out = []
    t = lo
    for s, e in union(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label_gap(gap: Tuple[float, float], host: Sequence[Interval]) -> str:
    """Name of the innermost host span that overlaps the gap most: what
    the host was doing while the device idled (``none`` if nothing)."""
    s, e = gap
    best, best_key = "none", None
    for hs, he, name in host:
        overlap = min(e, he) - max(s, hs)
        if overlap <= 0:
            continue
        key = (overlap, -(he - hs))  # most overlap, then the shortest span
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def reduce(trace: Trace, classes: OpClasses, window: str = "bench.window",
           top: int = 10) -> dict:
    """Numbers of the traced window, averaged over the device planes.

    Returns ``window_s``, ``busy_s`` (union of operation intervals),
    ``class_s`` (summed durations of each class's operations),
    ``device_ops`` (the ``top`` operation names by summed duration) and
    ``idle_gaps`` (the ``top`` longest gaps, named by the host span they
    fell in), everything clipped to the host span ``window``.
    """
    if not trace.devices:
        raise ValueError("the trace holds no device plane")
    lo, hi = trace.span(window)
    n = len(trace.devices)
    busy = 0.0
    class_ns: Dict[str, float] = {}
    by_name: Dict[str, float] = {}
    all_gaps: List[Tuple[float, str]] = []
    for ops in trace.devices.values():
        busy += busy_ns(ops, lo, hi)
        for s, e, name in ops:
            d = min(e, hi) - max(s, lo)
            if d <= 0:
                continue
            by_name[name] = by_name.get(name, 0.0) + d
            label = classes.classify(name)
            if label is not None:
                class_ns[label] = class_ns.get(label, 0.0) + d
        all_gaps.extend(
            (ge - gs, label_gap((gs, ge), trace.host))
            for gs, ge in gaps(ops, lo, hi)
        )
    ops_top = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    gaps_top = sorted(all_gaps, key=lambda g: -g[0])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / n * 1e-9,
        "class_s": {k: v / n * 1e-9 for k, v in class_ns.items()},
        "device_ops": [[name, v / n * 1e-9] for name, v in ops_top],
        "idle_gaps": [[name, d * 1e-9] for d, name in gaps_top],
        "devices": n,
    }
