"""Device time by the program's named scopes, read from a profiler trace.

The program wraps each phase in ``jax.named_scope("tempi.<phase>")``
(``repro.obs.scope``): ``tempi.pack``, ``tempi.unpack``, ``tempi.bytes``
(byte/word relayouts), ``tempi.wire``, ``tempi.stencil`` and
``tempi.splice``.  The scope is HLO metadata, the ``op_name`` path of
each instruction, so every device operation of a trace can be credited
to its innermost ``tempi.`` scope.  A v5e trace does not carry that
path: an ``XLA Ops`` event holds only its device offset and duration,
and its name is the instruction's HLO text without metadata.  So
:func:`load` takes each operation's scope from the optimized HLO text of
the programs that ran (``compiled.as_text()``), by instruction name.
There an instruction that the compiler made, and that so carries no
``op_name`` (a copy it inserted, the parts of a bitcast-convert it
expanded), takes the scope of the first instruction downstream of it
that has one, else that of the loop running its body.

:func:`scope_times` then splits the busy time of the window by scope:
each busy nanosecond of a device belongs to the innermost operation
that covers it (on the ``XLA Ops`` line a ``while`` spans its body), so
a loop and its body count once, for the body's scope, and
``sum(scope_s) + unscoped_s == busy_s``.

This adds to ``tracereduce`` and changes nothing in it: its operations
are named by instruction (for ``device_ops`` and the classes), these by
scope, from the same file.
"""

from __future__ import annotations

import heapq
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import tracereduce as tr

__all__ = [
    "PREFIX", "innermost", "hlo_scopes", "load", "innermost_ns",
    "scope_times", "strip_hlo",
]

PREFIX = "tempi."
_SCOPE = re.compile(r"tempi\.[a-z_]+")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_METADATA = re.compile(r",? metadata=\{[^}]*\}")
_NAME = re.compile(r"%[\w.\-]+")


def innermost(path: str) -> str:
    """The last ``tempi.<phase>`` of an ``op_name`` path ('' if none)."""
    found = _SCOPE.findall(path)
    return found[-1] if found else ""


_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)")
_CALLED_ANY = re.compile(r"(calls|body|condition|to_apply)=%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")


def _computations(text: str) -> Dict[str, List[Tuple[str, str, str]]]:
    """Each computation of an HLO text: its instructions in order, as
    ``(name, own scope, the rest of the line)``."""
    comps: Dict[str, List[Tuple[str, str, str]]] = {}
    body: Optional[List[Tuple[str, str, str]]] = None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            body = comps.setdefault(m.group(1), []) if m else None
            continue
        im = _INSTR.match(line)
        if body is None or im is None:
            continue
        om = _OP_NAME.search(line)
        body.append((im.group(1), innermost(om.group(1)) if om else "",
                     line[im.end():]))
    return comps


def _module_scopes(text: str) -> Dict[str, str]:
    """Scope of each instruction of one HLO module.  An instruction the
    compiler made (a copy it inserted, the parts of an expanded
    bitcast-convert) has no ``op_name``; it takes the scope of the first
    instruction downstream of it that has one, else that of the
    instruction calling its computation (a ``while`` for its body)."""
    comps = _computations(text)
    own: Dict[str, str] = {}
    users: Dict[str, List[str]] = {}
    caller: Dict[str, str] = {}
    for instrs in comps.values():
        for name, scope, rest in instrs:
            own[name] = scope
            rest = _METADATA.sub("", rest)
            for callee in _CALLED.findall(rest):
                caller.setdefault(callee, name)
            for operand in _NAME.findall(_CALLED.sub("", rest)):
                users.setdefault(operand[1:], []).append(name)
    home = {name: comp for comp, instrs in comps.items()
            for name, _, _ in instrs}
    found: Dict[str, str] = {}

    def resolve(name: str, seen: set) -> str:
        if name in found:
            return found[name]
        scope = own.get(name, "")
        if not scope and name not in seen:
            seen.add(name)
            for user in users.get(name, ()):
                scope = resolve(user, seen)
                if scope:
                    break
            else:
                up = caller.get(home.get(name, ""))
                scope = resolve(up, seen) if up else ""
        found[name] = scope
        return scope

    for name in own:
        resolve(name, set())
    return found


def hlo_scopes(hlo_texts: Iterable[str]
               ) -> Tuple[Dict[Tuple[str, str], str], Dict[str, str]]:
    """Scope of each instruction of optimized HLO texts (see
    :func:`_module_scopes`): by ``(module, instruction)``, and by
    instruction alone where every module that has the name agrees."""
    by_module: Dict[Tuple[str, str], str] = {}
    by_name: Dict[str, Optional[str]] = {}
    for text in hlo_texts:
        m = _MODULE.search(text)
        module = m.group(1) if m else ""
        for name, scope in _module_scopes(text).items():
            by_module[(module, name)] = scope
            if by_name.get(name, scope) != scope:
                scope = None
            by_name[name] = scope
    return by_module, {k: v for k, v in by_name.items() if v is not None}


def _event_scope(name: str, stats, by_module, by_name) -> Optional[str]:
    """Scope of one trace event's instruction; None where no HLO text
    has it."""
    module = next((v for k, v in stats if k == "hlo_module"), "")
    instr = tr.short_name(name).split(" ", 1)[0]
    return by_module.get((module, instr), by_name.get(instr))


def load(trace_dir: Path, classes: tr.OpClasses,
         hlo_texts: Sequence[str]) -> Tuple[tr.Trace, int]:
    """Read the one ``.xplane.pb`` under ``trace_dir``: each device
    operation as ``(start_ns, end_ns, scope)`` (``''`` where it has
    none), and the host spans named ``bench.*`` or ``tempi.*`` (the
    program's :class:`repro.obs.Tracer` spans).  Also returns how many
    distinct operations of the trace no HLO text holds (their time
    counts as unscoped)."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(
            f"{len(files)} .xplane.pb files under {trace_dir}, expected 1"
        )
    data = ProfileData.from_file(str(files[0]))
    by_module, by_name = hlo_scopes(hlo_texts)
    scopes: Dict[str, str] = {}
    unmatched = 0
    trace = tr.Trace()
    plane_re = re.compile(classes.device_plane)
    line_res = [re.compile(p) for p in classes.op_lines]
    for plane in data.planes:
        device = plane_re.search(plane.name) is not None
        if device:
            ops = trace.devices.setdefault(plane.name, [])
        for line in plane.lines:
            if device and any(r.search(line.name) for r in line_res):
                for e in line.events:
                    if e.name not in scopes:
                        scope = _event_scope(e.name, e.stats, by_module,
                                             by_name)
                        unmatched += scope is None
                        scopes[e.name] = scope or ""
                    ops.append((e.start_ns, e.end_ns, scopes[e.name]))
            if plane.name.startswith("/host:"):
                trace.host.extend(
                    (e.start_ns, e.end_ns, e.name) for e in line.events
                    if e.name.startswith(("bench.", PREFIX))
                )
    return trace, unmatched


def innermost_ns(intervals: Sequence[tr.Interval], lo: float, hi: float
                 ) -> Dict[str, float]:
    """Nanoseconds of [lo, hi] credited to each interval's label, each
    covered nanosecond to the innermost interval over it: the one that
    started last (of two that start together, the one that ends first).
    The values sum to ``tracereduce.busy_ns(intervals, lo, hi)``."""
    events: List[Tuple[float, int, int]] = []
    clipped = []
    for i, (s, e, _) in enumerate(intervals):
        s, e = max(s, lo), min(e, hi)
        clipped.append((s, e))
        if e > s:
            events.append((s, 1, i))
            events.append((e, 0, i))
    events.sort()  # at one instant, intervals end before others start
    out: Dict[str, float] = {}
    heap: List[Tuple[float, float, int]] = []
    open_: set = set()
    t = lo
    for when, starts, i in events:
        while heap and heap[0][2] not in open_:
            heapq.heappop(heap)
        if heap and when > t:
            label = intervals[heap[0][2]][2]
            out[label] = out.get(label, 0.0) + (when - t)
        t = when
        if starts:
            open_.add(i)
            heapq.heappush(heap, (-clipped[i][0], clipped[i][1], i))
        else:
            open_.discard(i)
    return out


def scope_times(trace: tr.Trace, window: str = "bench.window") -> dict:
    """Busy seconds of each scope inside the host span ``window``,
    averaged over the device planes: ``scope_s`` (scope -> seconds),
    ``unscoped_s`` (operations with no scope) and ``busy_s``, with
    ``sum(scope_s.values()) + unscoped_s == busy_s``."""
    if not trace.devices:
        raise ValueError("the trace holds no device plane")
    lo, hi = trace.span(window)
    n = len(trace.devices)
    total: Dict[str, float] = {}
    busy = 0.0
    for ops in trace.devices.values():
        busy += tr.busy_ns(ops, lo, hi)
        for label, ns in innermost_ns(ops, lo, hi).items():
            total[label] = total.get(label, 0.0) + ns
    unscoped = total.pop("", 0.0)
    return {
        "scope_s": {k: v / n * 1e-9 for k, v in sorted(total.items())},
        "unscoped_s": unscoped / n * 1e-9,
        "busy_s": busy / n * 1e-9,
    }


_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_TABLES = re.compile(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)"
                     r"\n(?:\d+ .*\n)*", re.M)


def _kernel_text(blob: str) -> str:
    """A serialized Mosaic kernel (a Pallas TPU custom call's ``body``)
    as MLIR text without source locations or its symbol name."""
    import base64

    from jax._src.lib.mlir import ir

    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(blob))
        text = module.operation.get_asm(enable_debug_info=False)
    text = re.sub(r'sym_name = "[^"]*"', 'sym_name = "kernel"', text)
    return re.sub(r"@[\w.$\-]+", "@kernel", text)


def strip_hlo(text: str) -> str:
    """Optimized HLO text without what scopes and names change: the
    source tables and each instruction's ``metadata={...}``, the
    locations and symbol names inside serialized Pallas kernels, and the
    names of instructions and computations.
    Instructions are renumbered in order of first appearance within
    their computation, and a reference to a computation becomes a hash
    of that computation's stripped text (the text lists callees before
    their callers).  Two programs that differ only in their named
    scopes, kernel names and source lines strip to the same text."""
    import hashlib

    text = _METADATA.sub("", _TABLES.sub("", text))
    text = _BODY.sub(lambda m: '"body":' + repr(_kernel_text(m.group(1))),
                     text)
    digest: Dict[str, str] = {}
    out = []
    for block in re.split(r"\n(?=\S)", text):
        m = _COMPUTATION.match(block.split("\n", 1)[0])
        block = _CALLED_ANY.sub(
            lambda c: f"{c.group(1)}=#{digest.get(c.group(2), '?')}", block)
        seen: Dict[str, str] = {}
        block = _NAME.sub(
            lambda n: seen.setdefault(n.group(0), f"%v{len(seen)}"), block)
        if m:
            digest[m.group(1)] = hashlib.sha1(block.encode()).hexdigest()[:12]
        out.append(block)
    return "\n".join(out)
